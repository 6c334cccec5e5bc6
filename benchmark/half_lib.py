"""The two halves of a mixed tick, and the scheduler's own counts (PR 55): what the program writes since then, read.

**The halves, from the device trace.** ``decode.mixed_paged_batch`` is two halves in sequence — one admission's
prefill slice (gather, ``shard_forward``, scatter), then the decode chunk's scan — and both run under the same
component scopes, so ``span_lib``'s split by ``xot.<component>`` holds the slice's work inside every decode-side
number. Since PR 55 the program traces the first half under ONE outer ``jax.named_scope("mixed.prefill")``: a device
op's ``op_name`` reads ``…/mixed.prefill/…/xot.moe_experts/…``. ``span_lib.component_of`` keeps the ``xot.`` parts
and reads what it read; this file walks the newest capture once more and files every op of the decode families
under (half, component) — half ``prefill`` where the ``op_name`` has the path component ``MARK``, ``decode``
otherwise — by self time, as ``span_lib`` does. What a slice carried is on the same clock: the executor's
``xot.sched.stage`` span of a mixed tick has the arguments ``pf_tokens`` (the slice's real tokens) and ``pf_pad``
(the padded slice the program ran). The profiler keeps a host span or a device event whole or not at all, so at
the capture's two edges a dispatch may have its span and not its execution, or the reverse: a thousand slice
tokens' time is therefore (the marked half's seconds ÷ the mixed program's executions) over (``pf_tokens`` ÷ the
spans that carry it) — the mean slice's time over the mean slice's tokens, equal to seconds over tokens where the
two counts agree; both are logged.

**The counts, from the clock's snapshots.** ``SchedClock.snapshot()`` (``attributes.clock`` on every request's
``decode`` and ``released`` stages: ``clock_lib``) carries ``counts`` since PR 55, each moved in the program by the
call that moves its ``/metrics`` counter: ``dispatch_behind`` / ``dispatch_empty``, ``slice_tokens`` /
``slice_pad_tokens``, ``kv_pages_read`` / ``kv_pages_resident``, ``experts_visited`` / ``expert_layer_steps``.
``count_ratio`` divides two deltas over the stretch the clock's readers read (``clock_lib.window_clock``: the
window up to the capture's opening).

One ``halves`` event on stderr a run: both halves by component — the decode half per step, the prefill half per
thousand slice tokens —, the executions of each family, the slices, and ``halves_over_device_s``: the two halves'
self seconds over the families' device seconds as their ``XLA Modules`` events give them (1 less the space between
ops inside the programs), and ``walk_s``, what this second walk of the capture cost the run after its window.

A capture of a program without the mark, or a timeline without ``counts`` (the parent of PR 55), yields None from
every reader here, never a zero.
"""

from __future__ import annotations

import bisect
import json
import sys
import time
from collections import defaultdict

import clock_lib
import span_lib
import trace_reduce
from common import ROOT

MARK = "mixed.prefill"  # models/decoder.py MIXED_PREFILL_SCOPE
MIXED = "decode.mixed_paged_batch"
HALVES = ("prefill", "decode")


def halve(planes: list, stages: list) -> dict:
  """``planes``: per chip ``(modules, ops, names)`` — ``modules`` the decode families' ``XLA Modules`` events as
  ``(start_s, end_s, family, program id)`` sorted by start, ``ops`` the chip's ``XLA Ops`` events ``(start_s, end_s,
  name)``, ``names`` ``span_lib.event_op_names``' ``{(program id, event name): op_name}``. ``stages``: the arguments
  of the capture's ``xot.sched.stage`` spans. Seconds are means over chips."""
  half_s = {h: defaultdict(float) for h in HALVES}
  executions: dict[str, int] = defaultdict(int)
  device_s = 0.0
  marked = False
  for modules, ops, names in planes:
    starts = [m[0] for m in modules]
    for s, e, family, _ in modules:
      executions[family] += 1
      device_s += e - s
    for t_self, i in trace_reduce.self_times([(s, e, i) for i, (s, e, _) in enumerate(ops)]):
      s, _, name = ops[i]
      m = bisect.bisect_right(starts, s) - 1
      if m < 0 or s >= modules[m][1]:
        continue
      op_name = names.get((modules[m][3], name))
      half = "prefill" if MARK in (op_name or "").split("/") else "decode"
      marked |= half == "prefill"
      half_s[half][span_lib.component_of(op_name)[0]] += t_self
  n = max(len(planes), 1)
  slices = [st for st in stages if "pf_tokens" in st]
  return {
    "marked": marked,  # False: a program without the mark (or a capture without a mixed tick), whose halves must read as absent
    "half_s": {h: {k: v / n for k, v in parts.items()} for h, parts in half_s.items()},
    "executions": {f: c / n for f, c in executions.items()},
    "device_s": device_s / n,
    "slices": len(slices), "pf_tokens": sum(int(st["pf_tokens"]) for st in slices), "pf_pad": sum(int(st["pf_pad"]) for st in slices),
  }


def reduce(path: str, families: dict[str, str]) -> dict:
  """One capture: its decode families' events and the ``stage`` spans' arguments, handed to ``halve``."""
  with open(path, "rb") as f:
    op_names, _collisions = span_lib.event_op_names(f.read())
  pd = trace_reduce.load(path)
  planes = []
  for plane in trace_reduce.device_planes(pd):
    modules = []
    for s, e, name in trace_reduce._events(trace_reduce._line(plane, "xla modules")):
      family = families.get(trace_reduce.module_base(name), trace_reduce.module_base(name))
      if family in span_lib.DECODE_FAMILIES:
        modules.append((s, e, family, int(m.group(1)) if (m := span_lib._PROGRAM_ID.search(name)) else 0))
    planes.append((modules, trace_reduce._events(trace_reduce._line(plane, "xla ops")), op_names.get(plane.name, {})))
  stages = [dict(ev.stats) for plane in pd.planes if not plane.name.startswith("/device:") for line in plane.lines for ev in line.events if ev.name == "xot.sched.stage"]
  return halve(planes, stages)


# ------------------------------------------------------------------ what the readers call

_MEMO: dict[str, dict] = {}


def capture(ctx: dict) -> dict | None:
  """The newest capture's halves, reduced once per process; None when the run traced nothing or its programs carry no
  mark. Logs the ``halves`` event."""
  if not ctx.get("trace"):
    return None
  path = trace_reduce.find_xplane(str(ROOT / "_work" / "bench_trace"))
  if path is None:
    return None
  if path not in _MEMO:
    t0 = time.perf_counter()
    red = _MEMO[path] = reduce(path, trace_reduce.program_families())
    print(json.dumps({"event": "halves", **_view(red, ctx["chunk"]), "walk_s": time.perf_counter() - t0}), file=sys.stderr, flush=True)
  return _MEMO[path] if _MEMO[path]["marked"] else None


def half_seconds(red: dict, half: str, components: tuple[str, ...] | None = None) -> float:
  parts = red["half_s"][half]
  return sum(parts.values()) if components is None else sum(parts.get(c, 0.0) for c in components)


def decode_steps(red: dict, chunk: int) -> float:
  return sum(red["executions"].values()) * chunk


def slice_ktok(red: dict):
  """Thousands of real slice tokens the marked half's seconds stand for (the module docstring says why it is a product of means)."""
  mixed = red["executions"].get(MIXED, 0)
  return red["pf_tokens"] / red["slices"] * mixed / 1e3 if red["slices"] and mixed else None


def _view(red: dict, chunk: int) -> dict:
  steps, ktok = decode_steps(red, chunk), slice_ktok(red)
  halves = sum(half_seconds(red, h) for h in HALVES)
  return {
    "marked": red["marked"], "executions": red["executions"], "slices": red["slices"], "pf_tokens": red["pf_tokens"], "pf_pad": red["pf_pad"],
    "decode_half_step_ms": {k: v * 1e3 / steps for k, v in sorted(red["half_s"]["decode"].items())} if steps else None,
    "prefill_half_ms_per_ktok": {k: v * 1e3 / ktok for k, v in sorted(red["half_s"]["prefill"].items())} if ktok else None,
    "prefill_half_s": half_seconds(red, "prefill"), "decode_half_s": half_seconds(red, "decode"), "device_s": red["device_s"],
    "halves_over_device_s": halves / red["device_s"] if red["device_s"] else None,
  }


def count_ratio(ctx: dict, over: tuple[str, ...], under: tuple[str, ...]):
  """Σ Δ``over`` ÷ Σ Δ``under`` of the snapshots' ``counts`` between ``clock_lib.window_clock``'s two; None where the
  program wrote no counts, there is no pair, or nothing under the line moved."""
  pair = clock_lib.window_clock(ctx)
  if pair is None or any("counts" not in snap for snap in pair):
    return None
  moved = clock_lib.delta(pair, "counts")
  below = sum(moved.get(k, 0) for k in under)
  return sum(moved.get(k, 0) for k in over) / below if below > 0 else None
