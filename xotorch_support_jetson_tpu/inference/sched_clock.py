"""The scheduler loop's wall clock, split by what the loop waits for (ISSUE 41).

At every instant the loop of one ``BatchedServer`` is in exactly one *kind*:

- ``decode`` | ``mixed`` | ``spec`` | ``prefill``: a dispatch of that kind is
  the oldest one not yet read back. A chained chunk (dispatched behind one
  still in flight) owns the clock from its predecessor's readback to its own
  (ready-to-ready); an unchained one from its dispatch to its readback.
- ``host``: the loop has nothing dispatched and work is pending (last readback
  to the loop's next hand-over). It is NOT the chip's idle share: a dispatch
  takes the clock when the loop hands it to the executor, so the executor's
  half of ``stage`` (transfers, the jitted call), during which the chip still
  waits, is inside the dispatch's kind (measured, PR 41: a third to a half of
  a capture's idle share).
- ``idle``: nothing is in flight and nothing is pending (the loop's one
  unbounded wait, on its queue), and the time before the loop's first request
  and after it has failed.

The kinds' seconds therefore sum to ``t - t_started`` whatever the order of
calls. ``/metrics`` carries them as ``sched_wall_seconds_total{kind}``;
``snapshot()`` is what a request's timeline carries at its first token and at
its release, so two snapshots give an exact delta over any stretch of a run
(``Tracer.timeline``'s ``resident_ms``; the benchmark's ``clock_lib``). The
snapshot also carries the scheduler's own counts (``counts``, ISSUE 55), each
moved with its ``/metrics`` counter by ``inc()`` at the call site that counts:
``dispatch_behind`` / ``dispatch_empty`` (``sched_dispatches_total{queue}``),
``slice_tokens`` / ``slice_pad_tokens`` (``sched_tick_prefill_tokens_total`` /
``sched_tick_prefill_pad_tokens_total``: a settled mixed tick's real and padded
slice), ``kv_pages_read`` / ``kv_pages_resident`` (``kv_pages_*_total``),
``experts_visited`` / ``expert_layer_steps`` (``moe_experts_visited_total`` /
``moe_expert_layer_steps_total``) — the same delta over the same stretch, with
no second door into the program. A count appears with its first increment.

One writer at a time: the loop and the engine's executor thread take turns (a
phase on one waits for the other), so nothing here locks.
"""

from __future__ import annotations

import time
from collections import deque

from ..utils.metrics import metrics

DEVICE_KINDS = ("decode", "mixed", "spec", "prefill")
KINDS = (*DEVICE_KINDS, "host", "idle")


class SchedClock:
  def __init__(self, now=time.perf_counter):
    self._now = now
    self.t_started = self._t = now()  # _t: up to where the seconds are booked
    self._kind = "idle"
    self._inflight: deque[str] = deque()  # kinds dispatched and not yet read back, oldest first
    self._since = self._t  # when the oldest in-flight dispatch took the clock
    self.seconds = dict.fromkeys(KINDS, 0.0)
    self.intervals = dict.fromkeys(KINDS, 0)  # closed stretches of each kind: a snapshot's group counts (on /metrics the chunk histograms count the device kinds)
    self.phases: dict[str, float] = {}
    self.last: dict[str, float] = {}  # the newest closed interval of each device kind: what the next one of that kind is expected to take
    self.ticks = 0
    self.steps = 0  # decode steps read back (a chunk's worth per decode / mixed / spec interval)
    self.counts: dict[str, int] = {}  # the scheduler's own counts, each moved with its /metrics counter by ``inc``

  def _book(self) -> float:
    now = self._now()
    dt = now - self._t
    self.seconds[self._kind] += dt
    metrics.inc("sched_wall_seconds_total", dt, labels={"kind": self._kind})
    self._t = now
    return now

  def _enter(self, kind: str) -> None:
    """Close the current interval (booked up to now by the caller) and open one of ``kind``."""
    self.intervals[self._kind] += 1
    self._kind = kind

  def dispatched(self, kind: str) -> None:
    self._inflight.append(kind)
    if len(self._inflight) == 1:  # unchained: the host's (or the idle wait's) interval ends here
      self._since = self._book()
      self._enter(kind)

  @property
  def queued(self) -> int:
    """Dispatches handed over and not yet read back: above 0, the next one is enqueued behind them."""
    return len(self._inflight)

  def expected(self) -> tuple[float, float] | None:
    """(seconds the oldest dispatch in flight is expected to run on, what its kind last took), or None where nothing
    is in flight or none of its kind has been read back yet. An estimate: a chunk's time hardly moves from one to
    the next, a mixed tick's follows its slice."""
    took = self.last.get(self._inflight[0]) if self._inflight else None
    return None if took is None else (self._since + took - self._now(), took)

  def withdrawn(self) -> None:
    """The newest dispatch failed before it reached the device (a prefill group whose enqueue raised): it leaves the
    queue, and if it had the clock the host takes it back. What is older keeps its place."""
    self._book()
    if self._inflight:
      self._inflight.pop()
      if not self._inflight:
        self._enter("host")

  def ready(self, steps: int = 0) -> float:
    """The oldest dispatch has been read back (or has failed): close its interval and return its length."""
    now = self._book()
    if not self._inflight:
      return 0.0
    dt, self._since = now - self._since, now
    self.last[self._inflight.popleft()] = dt
    self.steps += steps
    self._enter(self._inflight[0] if self._inflight else "host")
    return dt

  def idle_begin(self) -> None:
    if self._kind == "host":
      self._book()
      self._enter("idle")

  def idle_end(self) -> None:
    if self._kind == "idle":
      self._book()
      self._enter("host")

  def reset(self) -> None:
    """The loop failed or was shut down: whatever was in flight is gone, and until a new loop starts nothing is pending."""
    self._book()
    self._inflight.clear()
    if self._kind != "idle":
      self._enter("idle")

  def phase(self, name: str, dt: float) -> None:
    self.phases[name] = self.phases.get(name, 0.0) + dt

  def tick(self) -> None:
    self.ticks += 1

  def inc(self, family: str, amount: int = 1, *, count: str, labels: dict | None = None) -> None:
    """Move the ``/metrics`` counter ``family`` and the snapshots' count ``count`` by the same amount, in one call at
    the boundary where the scheduler counts (ISSUE 55): an operator's scrape and a reader of two snapshots
    (benchmark/half_lib.py) see the same growth over the same stretch. Spelled as ``metrics.inc`` so that the
    family stays a literal at its call site (scripts/check_metrics_docs.py reads those)."""
    self.counts[count] = self.counts.get(count, 0) + amount
    metrics.inc(family, amount, labels=labels)

  def snapshot(self) -> dict:
    """Everything cumulative, booked up to ``t`` (``perf_counter`` seconds): ``sum(seconds.values()) == t - self.t_started``."""
    now = self._book()
    return {"t": now, "ticks": self.ticks, "steps": self.steps, "seconds": dict(self.seconds), "intervals": dict(self.intervals), "phases": dict(self.phases), "counts": dict(self.counts)}
