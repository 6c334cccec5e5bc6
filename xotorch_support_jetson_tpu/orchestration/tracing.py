"""Request tracing with cross-node propagation — live, not vestigial.

The reference ships a full OpenTelemetry tracer that nothing imports and no
proto field carries (``orchestration/tracing.py`` — dead code, SURVEY.md §5.1).
This one is wired in: ``Node.process_prompt`` opens a request span,
per-token-group spans (every 10 tokens) record decode cadence, and the W3C
``traceparent`` rides both the opaque-status JSON and — since ISSUE 4 — the
gRPC metadata of every data-plane RPC, so multi-node rings stitch into one
trace. Self-contained (no otel dependency); export is an in-memory ring
buffer + optional JSONL file (``XOT_TPU_TRACE_FILE``) — file appends are
BUFFERED under the lock and flushed outside it, so the token hot path never
blocks on disk.

Per-request STAGE TIMELINES (ISSUE 2): producers mark lifecycle stages
(queued → admitted → prefill_chunk… → decode → detokenize) via ``stage()``;
``timeline()`` serves the per-stage breakdown (the API's
``/v1/requests/{id}/timeline``). Finished timelines outlive the request in a
bounded LRU so a client can fetch the breakdown after the response.
``XOT_TPU_SLOW_REQUEST_MS`` > 0 logs a structured JSON line with the stage
attribution for any request slower than the threshold.

CROSS-NODE ATTRIBUTION (ISSUE 4): data-plane RPCs record per-hop entries on
both sides via ``record_hop()`` — client-side serialize/RPC latency/payload
bytes, server-side deserialize/handler time — kept as spans in the ring
buffer AND as a bounded per-request hop list (+ exact per-link aggregates)
on the timeline. ``timeline_export()`` ships a node's raw-ns fragment over
the opaque-status channel; ``merge_cluster_timeline()`` normalizes remote
timestamps with the NTP-style per-peer clock offsets (clocksync.py) and
merges the fragments into one hop-annotated cluster timeline that splits
each hop into serialize / wire / deserialize / compute.

All cross-node-comparable timestamps route through ``node_now_ns(node_id)``
so tests can inject a synthetic per-node clock skew (``set_test_skew``) and
verify the offset normalization end-to-end; with no skew registered it is a
plain ``time.perf_counter_ns()``.
"""

from __future__ import annotations

import json
import os
import secrets
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from dataclasses import dataclass, field

# As many finished timelines as live contexts (below). At 256 a 64-slot server that finishes ~300 requests in a 51 s
# window had dropped the window's first fifth by its end, and what reads a window from its requests' timelines (the
# benchmark's wall-clock readers) found the stretch before its capture covered by a half, give or take (PERF.md §6, PR 44).
MAX_TIMELINES = 1024
# Live TraceContexts are bounded the same way (satellite of ISSUE 4): a
# request cancelled or failed before end_request used to leave its context in
# the dict forever. LRU-evicting at this cap loses only token-group cadence
# for requests that outlive 1024 newer ones — never correctness.
MAX_CONTEXTS = 1024
# Per-request hop DETAIL is capped (a 200-token ring decode crosses 400+
# hops); the per-link aggregates keep exact totals past the cap.
MAX_TIMELINE_HOPS = 256

# Terminal classification (ISSUE 9): every request must reach EXACTLY ONE of
# these — the refusal stages set it at their terminal stage() call, and
# end_request classifies everything else "complete". First writer wins, so a
# later end_request on a shed request is a no-op — the goodput and
# availability denominators depend on this being airtight (test-pinned by
# the terminal-invariant suite).
TERMINAL_STAGES = frozenset({"shed", "rejected", "rate_limited", "stalled", "error"})

# Stages that are consequential state transitions — forwarded to the flight
# recorder (orchestration/flightrec.py) from this single choke point instead
# of a hook per call site. Deliberately EXCLUDES the per-chunk cadence
# (queued / prefill_chunk / decode / decode_chunk / detokenize): the
# recorder holds transitions, not traffic.
FLIGHT_STAGES = frozenset({
  "admitted", "shed", "rejected", "rate_limited", "preempted", "parked", "unparked",
  "spilled", "restored", "drain", "migrated", "stalled", "error", "disagg_handoff",
})


# ---------------------------------------------------------- test clock skew
# Synthetic per-node monotonic-clock skew, injectable by tests ONLY: two
# in-process nodes share one time.perf_counter_ns(), so verifying that the
# cluster-timeline merge actually corrects a clock offset requires skewing
# one "node's" clock at the record points. Empty dict (the default) keeps the
# hot path at one falsy check.
_test_skew_ns: dict[str, int] = {}


def set_test_skew(node_id: str, skew_ns: int | None) -> None:
  """Register (or clear, with None) a synthetic clock skew for ``node_id``.
  Affects stage/hop timestamps and the HealthCheck clock echo — exactly the
  cross-node-comparable reads — as if that node's monotonic clock ran ahead
  by ``skew_ns``."""
  if skew_ns is None:
    _test_skew_ns.pop(node_id, None)
  else:
    _test_skew_ns[node_id] = int(skew_ns)


def node_now_ns(node_id: str | None = None) -> int:
  now = time.perf_counter_ns()
  if _test_skew_ns and node_id in _test_skew_ns:
    now += _test_skew_ns[node_id]
  return now


@dataclass
class Span:
  trace_id: str
  span_id: str
  parent_id: str | None
  name: str
  start_ns: int
  end_ns: int | None = None
  attributes: dict = field(default_factory=dict)

  @property
  def duration_ms(self) -> float | None:
    return None if self.end_ns is None else (self.end_ns - self.start_ns) / 1e6

  def to_dict(self) -> dict:
    return {
      "trace_id": self.trace_id,
      "span_id": self.span_id,
      "parent_id": self.parent_id,
      "name": self.name,
      "start_ns": self.start_ns,
      "end_ns": self.end_ns,
      "duration_ms": self.duration_ms,
      "attributes": self.attributes,
    }


def new_trace_id() -> str:
  return secrets.token_hex(16)


def new_span_id() -> str:
  return secrets.token_hex(8)


def format_traceparent(trace_id: str, span_id: str) -> str:
  return f"00-{trace_id}-{span_id}-01"


_HEX_DIGITS = frozenset("0123456789abcdef")


def _is_hex(s: str) -> bool:
  return bool(s) and all(c in _HEX_DIGITS for c in s)


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
  """Strict W3C traceparent parsing (hardened, ISSUE 4 satellite): the old
  parser accepted any 4-dash-part string of the right lengths, silently
  adopting garbage trace/span ids from a corrupted or hostile header. Reject
  non-(lowercase-)hex ids, all-zero ids, and any version other than ``00``
  (including the explicitly-invalid ``ff``) — an unparseable header means
  "start a fresh trace", never "join id 'deadbeef-oops'"."""
  if not header:
    return None
  parts = header.strip().split("-")
  if len(parts) != 4:
    return None
  version, trace_id, span_id, flags = parts
  if version != "00":
    return None
  if len(trace_id) != 32 or not _is_hex(trace_id) or trace_id == "0" * 32:
    return None
  if len(span_id) != 16 or not _is_hex(span_id) or span_id == "0" * 16:
    return None
  if len(flags) != 2 or not _is_hex(flags):
    return None
  return trace_id, span_id


class TraceContext:
  """Per-request trace state: ids + token-group bookkeeping."""

  def __init__(self, trace_id: str, parent_id: str | None = None, group_size: int = 10) -> None:
    self.trace_id = trace_id
    self.parent_id = parent_id
    self.request_span_id: str | None = None
    self.group_size = group_size
    self.token_count = 0
    self._group_start_ns: int | None = None

  def traceparent(self) -> str:
    return format_traceparent(self.trace_id, self.request_span_id or new_span_id())


def stage_summary(events: list[dict], start_ns: int, end_ns: int) -> list[dict]:
  """Per-stage rollup: each event's duration runs to the next event (or the
  timeline end); same-named events (chunked prefill) aggregate. Works on any
  raw-ns event list — the single-node timeline and the per-node sections of
  the merged cluster timeline both use it."""
  order: list[str] = []
  agg: dict[str, dict] = {}
  for i, ev in enumerate(events):
    nxt = events[i + 1]["t_ns"] if i + 1 < len(events) else end_ns
    entry = agg.get(ev["stage"])
    if entry is None:
      order.append(ev["stage"])
      entry = agg[ev["stage"]] = {
        "stage": ev["stage"],
        "count": 0,
        "first_at_ms": round((ev["t_ns"] - start_ns) / 1e6, 3),
        "duration_ms": 0.0,
      }
    entry["count"] += 1
    entry["duration_ms"] = round(entry["duration_ms"] + max(nxt - ev["t_ns"], 0) / 1e6, 3)
  return [agg[name] for name in order]


def parked_wait_ms(events: list[dict], end_ns: int) -> float:
  """Total page-starvation wait: each ``parked`` span runs to the matching
  ``unparked`` (the scheduler emits one per admission after a park), or to
  ``end_ns`` for a request still parked / refused while parked. Repeated
  ``parked`` events inside one starvation span (each failed retry re-marks)
  collapse into that single span."""
  total = 0
  t_park: int | None = None
  for ev in events:
    if ev["stage"] == "parked":
      if t_park is None:
        t_park = ev["t_ns"]
    elif ev["stage"] == "unparked" and t_park is not None:
      total += max(ev["t_ns"] - t_park, 0)
      t_park = None
  if t_park is not None:
    total += max(end_ns - t_park, 0)
  return round(total / 1e6, 3)


def resident_ms(events: list[dict]) -> dict:
  """Where the scheduler loop's wall time went while the request held a slot:
  the batched scheduler writes its clock (``inference/sched_clock.py``) on the
  request's ``decode`` stage (first token) and on ``released`` (finish,
  preemption, drain), and the differences of the two, summed over the
  request's residencies, say how many milliseconds of each interval between
  two of its tokens a decode / mixed / speculative chunk was the oldest
  dispatch in flight, how many a prefill (someone else's prompt: every
  resident row waits), how many the loop had nothing dispatched — every kind
  the clock keeps but ``idle``, which a loop with a resident row never is;
  ``steps`` is the decode steps read back meanwhile. A residency still open
  counts nothing; a path that writes no clock (solo, ``--pp`` / ``--sp``
  rings) has ``steps`` 0 and no kinds."""
  total: dict[str, float] = {}
  steps = 0
  first = None
  for ev in events:
    clock = ev["attributes"].get("clock")
    if not clock:
      continue
    if ev["stage"] == "decode":
      first = clock
    elif ev["stage"] == "released" and first is not None:
      for kind, s in clock["seconds"].items():
        if kind != "idle":
          total[kind] = total.get(kind, 0.0) + s - first["seconds"][kind]
      steps += clock["steps"] - first["steps"]
      first = None
  return {**{kind: round(s * 1e3, 3) for kind, s in total.items()}, "steps": steps}


def _active_program_families(window_ms: float) -> list[str]:
  """Program-ledger families dispatched within the last ``window_ms`` — the
  slow-request window, converted from the timeline's monotonic span to a
  wall-clock cutoff (best effort; an empty ledger yields [])."""
  try:
    from ..utils.programs import ledger

    return ledger.families_active_since(time.time() - window_ms / 1e3)
  except Exception:  # noqa: BLE001 — the slow line must never fail to print
    return []


class Tracer:
  def __init__(self, max_spans: int = 4096) -> None:
    self.spans: deque[Span] = deque(maxlen=max_spans)
    self.contexts: OrderedDict[str, TraceContext] = OrderedDict()
    self.timelines: OrderedDict[str, dict] = OrderedDict()
    self._lock = threading.Lock()
    self._export_path = os.getenv("XOT_TPU_TRACE_FILE")
    self._export_pending: list[dict] = []
    self._export_lock = threading.Lock()  # serializes file writes only

  # -------------------------------------------------------------- contexts

  def request_context(self, request_id: str, traceparent: str | None = None) -> TraceContext:
    with self._lock:
      ctx = self.contexts.get(request_id)
      if ctx is None:
        parsed = parse_traceparent(traceparent)
        if parsed:
          ctx = TraceContext(parsed[0], parent_id=parsed[1])
        else:
          ctx = TraceContext(new_trace_id())
        self.contexts[request_id] = ctx
        while len(self.contexts) > MAX_CONTEXTS:
          self.contexts.popitem(last=False)
      self.contexts.move_to_end(request_id)
      return ctx

  def trace_ids(self, request_id: str) -> tuple[str, str | None] | None:
    """(trace_id, request_span_id) for an EXISTING context — None rather
    than creating one (hop recording for ids this node merely forwards must
    not churn the context LRU)."""
    with self._lock:
      ctx = self.contexts.get(request_id)
      return (ctx.trace_id, ctx.request_span_id or ctx.parent_id) if ctx else None

  def end_request(self, request_id: str) -> None:
    """Close out a request: emit the trailing PARTIAL token group (tokens
    past the last multiple of ``group_size`` were previously dropped),
    finalize the stage timeline, and log the slow-request line if the
    request overran ``XOT_TPU_SLOW_REQUEST_MS``."""
    now = time.perf_counter_ns()
    slow_line = None
    completed = False
    with self._lock:
      ctx = self.contexts.pop(request_id, None)
      if ctx is not None:
        residual = ctx.token_count % ctx.group_size
        if residual and ctx._group_start_ns is not None:
          self._record_locked(Span(
            trace_id=ctx.trace_id,
            span_id=new_span_id(),
            parent_id=ctx.request_span_id,
            name="token_group",
            start_ns=ctx._group_start_ns,
            end_ns=now,
            attributes={"n_tokens": residual, "total_tokens": ctx.token_count},
          ))
      tl = self.timelines.get(request_id)
      if tl is not None and not tl.get("finished"):
        tl["end_ns"] = now
        tl["finished"] = True
        if ctx is not None:
          tl["tokens"] = ctx.token_count
        # Terminal classification: a request that finished without a refusal
        # stage completed normally. First writer wins (a shed request's later
        # end_request must not relabel it).
        if tl.get("terminal") is None:
          tl["terminal"] = "complete"
          completed = True
        threshold_ms = float(os.getenv("XOT_TPU_SLOW_REQUEST_MS", "0") or 0)
        total_ms = (now - tl["start_ns"]) / 1e6
        if threshold_ms > 0 and total_ms > threshold_ms:
          slow_line = json.dumps({
            "event": "slow_request",
            "request_id": request_id,
            "trace_id": tl.get("trace_id"),
            "total_ms": round(total_ms, 3),
            "threshold_ms": threshold_ms,
            "tokens": tl.get("tokens", 0),
            "stages": stage_summary(tl["events"], tl["start_ns"], tl["end_ns"] or now),
            "resident_ms": resident_ms(tl["events"]),
            # Per-link hop attribution (exact aggregates, not the capped
            # detail): which peer link ate the time is answerable from the
            # log line alone.
            "hops": dict(tl.get("hop_agg") or {}),
            # Device-program families dispatched inside this request's
            # window (ISSUE 19) — the slow line joins against the ledger:
            # a recompile stall shows up here as its program family plus a
            # ``compile`` stage in ``stages``.
            "programs": _active_program_families(total_ms),
          })
    self._flush_export()
    if completed:
      from .flightrec import flightrec

      flightrec.record("complete", request_id=request_id)
    if slow_line is not None:
      print(slow_line)

  # -------------------------------------------------------- stage timelines

  def stage(self, request_id: str, stage: str, attributes: dict | None = None, node: str | None = None, terminal: bool = False) -> None:
    """Mark a request-lifecycle stage (queued/admitted/prefill_chunk/decode/
    detokenize/…). Cheap: one dict append under the lock; repeated stages
    (each prefill chunk) append their own events. Events after the request
    finished (e.g. the API's detokenize following a blocking generation) are
    still recorded — the timeline is an LRU entry, not live request state.
    ``node`` labels the event for cross-node merging and routes the
    timestamp through the (test-skewable) per-node clock. ``terminal``
    (ISSUE 5: shed / rate_limited / rejected refusals) finalizes the
    timeline at this event, so a request the QoS layer refused BEFORE it
    ever ran still serves a finished timeline explaining why — even on
    paths where no ``end_request`` follows; a later ``end_request`` is a
    no-op on the already-finished entry.

    This is also the flight recorder's request-lifecycle choke point
    (ISSUE 9): consequential stages (``FLIGHT_STAGES``) forward as wide
    events, and terminal refusal stages feed the SLO engine's availability
    accounting — one hook here instead of one per call site."""
    now = node_now_ns(node)
    claimed = False
    with self._lock:
      tl = self._timeline_locked(request_id, now)
      tl["events"].append({"stage": stage, "t_ns": now, "node": node, "attributes": dict(attributes or {})})
      if terminal and not tl.get("finished"):
        tl["end_ns"] = now
        tl["finished"] = True
        if tl.get("terminal") is None and stage in TERMINAL_STAGES:
          tl["terminal"] = stage
          claimed = True
      self.timelines.move_to_end(request_id)
    if stage in FLIGHT_STAGES:
      from .flightrec import flightrec

      flightrec.record(stage, request_id=request_id, node=node,
                       cause=(attributes or {}).get("reason"), attributes=attributes)
      if claimed:
        # Availability accounting rides the terminal CLAIM, not the stage
        # call: a second terminal on the same request (a stall raced by a
        # later replay-budget 'error') must not double-count one request
        # as two bad events.
        from .slo import note_bad

        note_bad((attributes or {}).get("class") or "standard", stage)

  def _timeline_locked(self, request_id: str, now: int) -> dict:
    tl = self.timelines.get(request_id)
    if tl is None:
      ctx = self.contexts.get(request_id)
      tl = self.timelines[request_id] = {
        "request_id": request_id,
        "trace_id": ctx.trace_id if ctx else None,
        "start_ns": now,
        "end_ns": None,
        "finished": False,
        "terminal": None,
        "tokens": 0,
        "events": [],
        "hops": [],
        "hops_dropped": 0,
        "hop_agg": {},
      }
      while len(self.timelines) > MAX_TIMELINES:
        # Evict the oldest FINISHED timeline first: a QoS refusal flood
        # (each refusal is a one-event finished timeline) must not evict
        # live in-flight requests' timelines exactly during the overload
        # they would explain. Protection is bounded at half the capacity —
        # beyond that many unfinished entries (leaked/abandoned requests),
        # plain oldest-first eviction resumes so zombies can't pin the LRU.
        victim = None
        unfinished = 0
        for rid, entry in self.timelines.items():
          if entry.get("finished"):
            victim = rid
            break
          unfinished += 1
          if unfinished > MAX_TIMELINES // 2:
            break
        if victim is None:
          self.timelines.popitem(last=False)
        else:
          del self.timelines[victim]
    elif tl.get("trace_id") is None:
      ctx = self.contexts.get(request_id)
      if ctx:
        tl["trace_id"] = ctx.trace_id
    return tl

  # ------------------------------------------------------------------ hops

  def record_hop(
    self,
    request_id: str,
    *,
    side: str,  # "client" (sender) | "server" (receiver)
    method: str,
    peer: str,
    node: str | None = None,
    t_start_ns: int,
    dur_ms: float,
    hop_id: str | None = None,
    trace_id: str | None = None,
    attributes: dict | None = None,
  ) -> str:
    """Record one side of a data-plane RPC hop (ISSUE 4 tentpole).

    Client side: ``hop_id`` is the client's span id (it rides the RPC's
    traceparent metadata so the server parents to it); attributes carry
    serialize_ms / rpc_ms / payload_bytes. Server side: a fresh span id with
    ``parent_id=hop_id``; attributes carry deserialize_ms / handler_ms /
    payload_bytes. Both land as spans in the ring buffer AND as timeline hop
    entries — detail capped at MAX_TIMELINE_HOPS per request, per-link
    aggregates exact. Returns the hop span id."""
    attrs = dict(attributes or {})
    with self._lock:
      ctx = self.contexts.get(request_id) if request_id else None
      tid = trace_id or (ctx.trace_id if ctx else new_trace_id())
      if side == "client":
        span_id = hop_id or new_span_id()
        parent = ctx.request_span_id or ctx.parent_id if ctx else None
      else:
        span_id = new_span_id()
        parent = hop_id
      # The span-ring entry rides the SAME per-request cap as the timeline
      # hop detail: a 200-token ring decode crosses 400+ hops per node, and
      # uncapped hop spans would cycle the whole 4096-entry ring (burying
      # request/pp/token-group spans) while flushing the JSONL export on the
      # per-token data plane. Aggregates stay exact past the cap.
      over_cap = False
      if request_id:
        tl = self._timeline_locked(request_id, t_start_ns)
        over_cap = len(tl["hops"]) >= MAX_TIMELINE_HOPS
      if not over_cap:
        self._record_locked(Span(
          trace_id=tid,
          span_id=span_id,
          parent_id=parent,
          name=f"rpc.{side}.{method}",
          start_ns=t_start_ns,
          end_ns=t_start_ns + int(dur_ms * 1e6),
          attributes={"peer": peer, "node": node, **attrs},
        ))
      if request_id:
        if not over_cap:
          tl["hops"].append({
            "side": side,
            "t_ns": t_start_ns,
            "node": node,
            "hop_id": span_id if side == "client" else hop_id,
            "peer": peer,
            "method": method,
            "attributes": attrs,
          })
        else:
          tl["hops_dropped"] += 1
        key = f"{side}|{node or '-'}|{peer}|{method}"
        agg = tl["hop_agg"].get(key)
        if agg is None:
          agg = tl["hop_agg"][key] = {"count": 0}
        agg["count"] += 1
        for k, v in attrs.items():
          if isinstance(v, (int, float)) and (k.endswith("_ms") or k.endswith("_bytes")):
            agg[f"{k}_sum"] = round(agg.get(f"{k}_sum", 0.0) + v, 3)
        self.timelines.move_to_end(request_id)
    self._flush_export()
    return span_id

  def timeline(self, request_id: str) -> dict | None:
    """The request's stage breakdown, or None if unknown (expired/never
    seen). Safe to call mid-flight: durations run to "now" until finished."""
    now = time.perf_counter_ns()
    with self._lock:
      tl = self.timelines.get(request_id)
      if tl is None:
        return None
      end_ns = tl["end_ns"] or now
      return {
        "request_id": request_id,
        "trace_id": tl.get("trace_id"),
        "finished": bool(tl.get("finished")),
        "terminal": tl.get("terminal"),
        "tokens": tl.get("tokens", 0),
        "total_ms": round((end_ns - tl["start_ns"]) / 1e6, 3),
        # Page-starvation wait (ISSUE 6 satellite): the summed parked →
        # unparked span, top-level so "why was this request slow" is
        # answerable without walking the event list. A request still parked
        # at query time accrues to "now".
        "parked_ms": parked_wait_ms(tl["events"], end_ns),
        # The same question about a slow TPOT (ISSUE 41): what the scheduler
        # loop waited for between this request's first token and its release.
        "resident_ms": resident_ms(tl["events"]),
        "stages": stage_summary(tl["events"], tl["start_ns"], end_ns),
        "events": [
          {
            "stage": ev["stage"],
            "at_ms": round((ev["t_ns"] - tl["start_ns"]) / 1e6, 3),
            "node": ev.get("node"),
            "attributes": ev["attributes"],
          }
          for ev in tl["events"]
        ],
        "hops": [
          {
            "side": h["side"],
            "at_ms": round((h["t_ns"] - tl["start_ns"]) / 1e6, 3),
            "node": h.get("node"),
            "hop_id": h.get("hop_id"),
            "peer": h["peer"],
            "method": h["method"],
            "attributes": h["attributes"],
          }
          for h in tl.get("hops", [])
        ],
        "hops_dropped": tl.get("hops_dropped", 0),
        "hop_agg": dict(tl.get("hop_agg") or {}),
      }

  def timeline_export(self, request_id: str) -> dict | None:
    """Raw-ns fragment of this node's view of the request — the wire format
    peers ship over the opaque-status channel for ``?scope=cluster``.
    Timestamps stay in the LOCAL monotonic clock; the merging node
    normalizes them with its per-peer offset estimates."""
    with self._lock:
      tl = self.timelines.get(request_id)
      if tl is None:
        return None
      return {
        "request_id": request_id,
        "trace_id": tl.get("trace_id"),
        "start_ns": tl["start_ns"],
        "end_ns": tl["end_ns"],
        "finished": bool(tl.get("finished")),
        "terminal": tl.get("terminal"),
        "tokens": tl.get("tokens", 0),
        "events": [dict(ev) for ev in tl["events"]],
        "hops": [dict(h) for h in tl.get("hops", [])],
        "hops_dropped": tl.get("hops_dropped", 0),
        "hop_agg": {k: dict(v) for k, v in (tl.get("hop_agg") or {}).items()},
      }

  def terminal_of(self, request_id: str) -> str | None:
    """The request's claimed terminal classification, or None. Lets the
    scheduler's completion accounting skip a request a refusal terminal
    already counted bad (a stalled-then-locally-recovered request must be
    ONE availability event, not one bad plus one good)."""
    with self._lock:
      tl = self.timelines.get(request_id)
      return tl.get("terminal") if tl else None

  def inflight_timelines(self, max_n: int = 16) -> list[dict]:
    """Raw-ns exports of the newest UNFINISHED timelines — what an incident
    bundle (ISSUE 9) captures as "requests in flight at trigger time". The
    post-mortem question is always about the requests that were mid-stream
    when things went wrong, not the finished history."""
    with self._lock:
      ids = [rid for rid, tl in reversed(self.timelines.items()) if not tl.get("finished")][:max_n]
    return [te for rid in ids if (te := self.timeline_export(rid)) is not None]

  # ----------------------------------------------------------------- spans

  @contextmanager
  def start_span(self, name: str, request_id: str | None = None, attributes: dict | None = None):
    ctx = self.request_context(request_id) if request_id else None
    span = Span(
      trace_id=ctx.trace_id if ctx else new_trace_id(),
      span_id=new_span_id(),
      parent_id=(ctx.request_span_id or ctx.parent_id) if ctx else None,
      name=name,
      start_ns=time.perf_counter_ns(),
      attributes=dict(attributes or {}),
    )
    if ctx and ctx.request_span_id is None and name.startswith("request"):
      ctx.request_span_id = span.span_id
    try:
      yield span
    finally:
      span.end_ns = time.perf_counter_ns()
      self._record(span)

  def handle_token(self, request_id: str) -> None:
    """Count a token; emit a token-group span every ``group_size`` tokens."""
    with self._lock:
      ctx = self.contexts.get(request_id)
      if ctx is None:
        return
      now = time.perf_counter_ns()
      if ctx._group_start_ns is None:
        ctx._group_start_ns = now
      ctx.token_count += 1
      if ctx.token_count % ctx.group_size == 0:
        span = Span(
          trace_id=ctx.trace_id,
          span_id=new_span_id(),
          parent_id=ctx.request_span_id,
          name="token_group",
          start_ns=ctx._group_start_ns,
          end_ns=now,
          attributes={"n_tokens": ctx.group_size, "total_tokens": ctx.token_count},
        )
        ctx._group_start_ns = now
        self._record_locked(span)
    self._flush_export()

  def _record(self, span: Span) -> None:
    with self._lock:
      self._record_locked(span)
    self._flush_export()

  def _record_locked(self, span: Span) -> None:
    # No I/O here: the caller may be on the token hot path with the lock
    # held. File export is queued and flushed outside the lock.
    self.spans.append(span)
    if self._export_path:
      self._export_pending.append(span.to_dict())

  def _flush_export(self) -> None:
    """Drain the queued span dicts to the JSONL file OUTSIDE the tracer
    lock. A separate flush lock serializes the file writes themselves —
    buffered writers flush at buffer boundaries, not line boundaries, so two
    concurrent appenders could otherwise tear a line — while recorders keep
    making progress under the main lock."""
    if not self._export_path:
      return
    with self._export_lock:
      with self._lock:
        if not self._export_pending:
          return
        pending, self._export_pending = self._export_pending, []
      try:
        with open(self._export_path, "a") as f:
          f.writelines(json.dumps(d) + "\n" for d in pending)
      except OSError:
        pass

  def recent_spans(self, n: int = 100) -> list[dict]:
    with self._lock:
      return [s.to_dict() for s in list(self.spans)[-n:]]


# ------------------------------------------------- cluster timeline merging


def _num(d: dict, key: str) -> float | None:
  v = d.get(key)
  return float(v) if isinstance(v, (int, float)) else None


def merge_cluster_timeline(
  local_node_id: str,
  local: dict | None,
  fragments: list[dict],
  offsets: dict | None = None,
) -> dict | None:
  """Merge timeline fragments from the whole ring into ONE cluster-scope
  timeline in the LOCAL node's clock domain.

  ``fragments`` are ``{"node_id": ..., "fragment": timeline_export()|None}``
  as returned by ``Node.collect_cluster_timeline``. ``offsets`` maps node_id
  → ``PeerClockEstimate`` (or a dict with ``offset_ns``): a remote timestamp
  ``t`` normalizes to ``t - offset_ns`` (the estimate is peer−local).

  Events/hops whose ``node`` field is unset adopt their fragment's node id;
  duplicates (the in-process shared-tracer case, where every "fragment" is
  the same object) collapse by identity key — (stage, t_ns) for events,
  (side, hop_id, method) for hops — keeping the first occurrence, which is
  the local fragment's.

  Each hop pairs its client and server entries by hop id and splits into
  serialize (client, before the RPC), wire (client RPC latency − server
  handler time: network + HTTP/2 framing + compression), deserialize
  (server, proto → numpy), and compute (server handler − deserialize; on a
  ring middle node this INCLUDES awaiting the downstream hops — span-tree
  semantics, the nested hops are attributed on their own entries)."""
  offsets = offsets or {}

  def offset_ns(node_id: str) -> float:
    if node_id == local_node_id:
      return 0.0
    est = offsets.get(node_id)
    if est is None:
      return 0.0
    raw = est.get("offset_ns", 0.0) if isinstance(est, dict) else getattr(est, "offset_ns", 0.0)
    return float(raw or 0.0)

  frags: list[tuple[str, dict]] = []
  if local is not None:
    frags.append((local_node_id, local))
  for entry in fragments:
    frag = entry.get("fragment")
    nid = entry.get("node_id")
    if frag is not None and nid:
      frags.append((nid, frag))
  if not frags:
    return None

  starts = [frag["start_ns"] - offset_ns(nid) for nid, frag in frags]

  events: list[dict] = []
  seen_ev: set = set()
  raw_hops: list[dict] = []
  seen_hop: set = set()
  node_events: dict[str, list[dict]] = {}
  hop_agg: dict[str, dict] = {}
  hops_dropped = 0
  tokens = 0
  finished = False
  trace_id = None
  end_norm = min(starts)
  for nid, frag in frags:
    off = offset_ns(nid)
    trace_id = trace_id or frag.get("trace_id")
    tokens = max(tokens, int(frag.get("tokens") or 0))
    finished = finished or bool(frag.get("finished"))
    hops_dropped += int(frag.get("hops_dropped") or 0)
    if frag.get("end_ns"):
      end_norm = max(end_norm, frag["end_ns"] - off)
    for ev in frag.get("events", []):
      key = (ev["stage"], ev["t_ns"])
      if key in seen_ev:
        continue
      seen_ev.add(key)
      node = ev.get("node") or nid
      t_norm = ev["t_ns"] - (offset_ns(node) if node != nid else off)
      end_norm = max(end_norm, t_norm)
      events.append({
        "stage": ev["stage"],
        "node": node,
        "t_norm_ns": t_norm,
        "attributes": ev.get("attributes", {}),
      })
      node_events.setdefault(node, []).append({"stage": ev["stage"], "t_ns": t_norm})
    for h in frag.get("hops", []):
      # Anonymous hops (no traceparent reached the server — origin context
      # LRU-evicted, or an older peer) get an identity key from their node +
      # timestamp: still collapses shared-tracer duplicate fragments, never
      # collapses DISTINCT hops of the same method.
      key = (h["side"], h.get("hop_id") or (h.get("node"), h["t_ns"]), h["method"])
      if key in seen_hop:
        continue
      seen_hop.add(key)
      node = h.get("node") or nid
      t_norm = h["t_ns"] - (offset_ns(node) if node != nid else off)
      end_norm = max(end_norm, t_norm)
      raw_hops.append({**h, "node": node, "t_norm_ns": t_norm})
    for key, agg in (frag.get("hop_agg") or {}).items():
      cur = hop_agg.get(key)
      if cur is None:
        hop_agg[key] = dict(agg)
      elif cur != agg:
        # Same link key from two fragments with DIFFERENT content: genuinely
        # distinct contributions, sum them. Equal content is the shared-tracer
        # duplicate-fragment case (the key embeds the recording node, so two
        # real nodes never collide) — keep one copy.
        for k, v in agg.items():
          if isinstance(v, (int, float)):
            cur[k] = round(cur.get(k, 0) + v, 3)

  # Reference t=0: the earliest normalized time anyone recorded for the
  # request — NOT the local fragment's start, which on a non-origin node is
  # the SendPrompt arrival and would push the origin's queued/admitted
  # stages to negative at_ms (and silently exclude them from total_ms).
  all_t = [e["t_norm_ns"] for e in events] + [h["t_norm_ns"] for h in raw_hops]
  ref_start = min(all_t) if all_t else min(starts)
  end_norm = max(end_norm, ref_start)
  for e in events:
    e["at_ms"] = round((e.pop("t_norm_ns") - ref_start) / 1e6, 3)

  # Pair client/server hop entries by hop id into annotated hop records.
  by_id: dict[str, dict] = {}
  unpaired = []
  for h in raw_hops:
    hid = h.get("hop_id")
    if not hid:
      unpaired.append(h)
      continue
    by_id.setdefault(hid, {})[h["side"]] = h
  hops: list[dict] = []
  for hid, sides in by_id.items():
    c, s = sides.get("client"), sides.get("server")
    ref = c or s
    ca, sa = (c or {}).get("attributes", {}), (s or {}).get("attributes", {})
    rpc_ms = _num(ca, "rpc_ms")
    handler_ms = _num(sa, "handler_ms")
    deserialize_ms = _num(sa, "deserialize_ms")
    hop = {
      "hop_id": hid,
      "method": ref["method"],
      "from": c["node"] if c else None,
      "to": (s["node"] if s else None) or (c["peer"] if c else None),
      "at_ms": round(((c or s)["t_norm_ns"] - ref_start) / 1e6, 3),
      "recv_at_ms": round((s["t_norm_ns"] - ref_start) / 1e6, 3) if s else None,
      "serialize_ms": _num(ca, "serialize_ms"),
      "rpc_ms": rpc_ms,
      "payload_bytes": _num(ca, "payload_bytes") or _num(sa, "payload_bytes"),
      "handler_ms": handler_ms,
      "deserialize_ms": deserialize_ms,
      "wire_ms": round(max(rpc_ms - handler_ms, 0.0), 3) if rpc_ms is not None and handler_ms is not None else None,
      "compute_ms": round(max(handler_ms - deserialize_ms, 0.0), 3) if handler_ms is not None and deserialize_ms is not None else None,
    }
    hops.append(hop)
  for h in unpaired:
    hops.append({
      "hop_id": None,
      "method": h["method"],
      "from": h["node"] if h["side"] == "client" else None,
      "to": h["peer"] if h["side"] == "client" else h["node"],
      "at_ms": round((h["t_norm_ns"] - ref_start) / 1e6, 3),
      "recv_at_ms": None,
      **{k: _num(h.get("attributes", {}), k) for k in ("serialize_ms", "rpc_ms", "payload_bytes", "handler_ms", "deserialize_ms")},
      "wire_ms": None,
      "compute_ms": None,
    })

  events.sort(key=lambda e: e["at_ms"])
  hops.sort(key=lambda h: h["at_ms"])
  est_dicts = {}
  for nid, est in offsets.items():
    est_dicts[nid] = est.to_dict() if hasattr(est, "to_dict") else dict(est)
  return {
    "request_id": frags[0][1].get("request_id"),
    "scope": "cluster",
    "trace_id": trace_id,
    "finished": finished,
    "tokens": tokens,
    "nodes": sorted({nid for nid, _ in frags}),
    "offsets": est_dicts,
    "total_ms": round((end_norm - ref_start) / 1e6, 3),
    "events": events,
    "hops": hops,
    "hops_dropped": hops_dropped,
    "hop_agg": hop_agg,
    "stages": {
      node: stage_summary(evs, ref_start, end_norm)
      for node, evs in ((n, sorted(e, key=lambda x: x["t_ns"])) for n, e in node_events.items())
    },
  }


tracer = Tracer()
