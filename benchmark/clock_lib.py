"""The scheduler's own wall clock, read over the measured window (PR 41).

What ``ctx["timelines"]`` carries since PR 41, beside the stages it always had: the batched scheduler writes a
snapshot of its loop's clock (``xotorch_support_jetson_tpu/inference/sched_clock.py``) as ``attributes.clock`` on
every request's ``decode`` stage (its first token) and on a new stage ``released`` (finish, preemption, drain), and
each timeline has a top-level ``resident_ms``, the difference of the request's own two. A snapshot is cumulative —
``seconds`` and ``intervals`` by kind (``decode`` | ``mixed`` | ``spec`` | ``prefill``: a dispatch of that kind is the
oldest not yet read back; ``host``: the loop has nothing dispatched, work pending; ``idle``: nothing dispatched, nothing
pending; the six partition the loop's wall time), ``phases`` (the ``xot.sched.*`` spans' own seconds, by name),
``ticks``, ``steps``, ``t`` (the program's ``perf_counter``) — so any two give an exact delta, with no new door into
the program: ``run.py fetch_timelines`` already hands the readers the whole JSON of every request of the window.

**The readers read the window up to the traced interval's opening, and nothing after it.** A capture leaves the
host slower until the window closes (``stage`` + ``settle`` cost about half as much again, on the parent as on the
change: PERF.md §6, PR 41), so the whole window is two regimes and a reading over it is 20-50 % above the capture's
own in ``sched_host_ms_per_tick_window``; cut at the opening the same reading agrees with the capture's to 1-7 %.
The tracer keeps the newest 256 timelines, so a window that finishes more requests than that has lost its early
snapshots: where those that are left span less than half of the stretch before the capture (Ling's 64-caller cell:
they begin as it opens), there is no reading and every reader says None — which is why ``BENCHMARK.json`` leaves
that cell out of the three clock metrics' ``workloads``. A capture taken in the window's last seconds would give every
cell a reading with no edit here. (Where ``run.py`` discarded an earlier attempt, ``kept: false`` in its log, the
stretch before the kept one holds that attempt's after-effect.)

The ``wall`` event (stderr, one a run) holds, for all of the window's snapshots, every kind's seconds (they sum to
``clock_span_s``), share and closed intervals, the phases per tick, ticks and steps — both regimes, for the builder and
for the ``benchmark`` PR that moves the capture — and under ``before_capture`` the same for the pair the readers read,
or null where there is none.

A program without the clock (the parent of PR 41) yields None from every reader here, never a zero.
"""

from __future__ import annotations

import json
import sys

from span_lib import WORKING_PHASES  # admit, plan, stage, settle: readback is mostly a wait for the device


def window_clock(ctx: dict):
  """``(first, last)``: of all clock snapshots on the timelines of the window's requests whose event lies between the
  window's opening and the traced interval's (``cap_start``; the window's close where no capture was taken) on the
  client's clock (``sent + at_ms``, as ``layer_lib.prefill_device_ms_per_ktok`` places events), the two with the least
  and the greatest ``t``. None with fewer than two, with no time between them, or where they span less than half of
  that stretch. Logs the ``wall`` event once a run."""
  if "_window_clock" in ctx:
    return ctx["_window_clock"]
  snaps = []  # (when on the client's clock, snapshot)
  for r in ctx["recs"]:
    tl = (ctx.get("timelines") or {}).get(r.rid)
    if tl is None or r.sent is None:
      continue
    for ev in tl.get("events", ()):
      clock = (ev.get("attributes") or {}).get("clock")
      if clock and ctx["t_open"] <= r.sent + ev["at_ms"] / 1e3 <= ctx["t_close"]:
        snaps.append((r.sent + ev["at_ms"] / 1e3, clock))
  opened = ctx.get("cap_start", ctx["t_close"])
  pair = _ends([c for at, c in snaps if at <= opened])
  if pair and pair[1]["t"] - pair[0]["t"] < (opened - ctx["t_open"]) / 2:
    pair = None
  ctx["_window_clock"] = pair
  whole = _ends([c for _, c in snaps])
  if whole:
    print(json.dumps({"event": "wall", "snapshots": len(snaps), **_view(whole), "before_capture": _view(pair) if pair else None}), file=sys.stderr, flush=True)
  return pair


def _ends(clocks: list):
  clocks = sorted(clocks, key=lambda c: c["t"])
  return (clocks[0], clocks[-1]) if len(clocks) >= 2 and clocks[-1]["t"] > clocks[0]["t"] else None


def _view(pair) -> dict:
  first, last = pair
  span, seconds, ticks = last["t"] - first["t"], delta(pair, "seconds"), last["ticks"] - first["ticks"]
  return {
    "clock_span_s": span, "seconds": seconds, "share": {k: v / span for k, v in seconds.items()}, "intervals": delta(pair, "intervals"),
    "ticks": ticks, "steps": last["steps"] - first["steps"], "phase_ms_per_tick": {k: v * 1e3 / ticks for k, v in delta(pair, "phases").items()} if ticks else None,
  }


def delta(pair, key: str) -> dict:
  """How far each entry of a snapshot's dict ``key`` moved between the two (an entry the first lacks started at 0)."""
  first, last = pair
  return {k: v - first[key].get(k, 0) for k, v in last[key].items()}


def wall_share(ctx: dict, kind: str):
  """``kind``'s share of the loop's busy wall time between ``window_clock``'s two snapshots: every kind but ``idle``,
  so a server that waits for arrivals does not dilute what its resident rows lose."""
  pair = window_clock(ctx)
  if pair is None:
    return None
  seconds = delta(pair, "seconds")
  busy = sum(v for k, v in seconds.items() if k != "idle")
  return seconds.get(kind, 0.0) / busy if busy > 0 else None


def host_ms_per_tick(ctx: dict):
  """Host milliseconds the scheduler works per tick (admit + plan + stage + settle) between the same two snapshots."""
  pair = window_clock(ctx)
  if pair is None or pair[1]["ticks"] == pair[0]["ticks"]:
    return None
  phases = delta(pair, "phases")
  return sum(phases.get(p, 0.0) for p in WORKING_PHASES) * 1e3 / (pair[1]["ticks"] - pair[0]["ticks"])
