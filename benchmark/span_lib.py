"""What the program itself wrote into the profiler's trace (ISSUE 24), read once
per process from the newest capture under ``_work/bench_trace``:

- **Component scopes.** The model code traces under ``jax.named_scope("xot.<component>")``;
  the name ends up in each device op's ``op_name``. Looked at by hand in
  ``tests/data/*.xplane.pb.gz``: an ``XLA Ops`` event is named by its whole HLO
  instruction and ``ProfileData`` exposes only the event's own stats (device
  offset, duration); the ``op_name`` is the ``tf_op`` stat of the plane's
  **event metadata**, which ``ProfileData`` does not expose. So this file walks
  the protobuf wire format of ``XSpace.planes[].event_metadata`` itself
  (``event_op_names``) and joins it to ``ProfileData``'s events by program id
  (in the name of the ``XLA Modules`` event an op runs inside, and a stat of its
  metadata) and event name: two programs may hold the same instruction text
  under different scopes.
  An op belongs to the first ``xot.`` component of its ``op_name``
  (``…/xot.moe_experts/xot.dequant/…`` is the experts', and also a
  dequantisation); an op with none — a copy the compiler added, an older
  program — is ``unscoped``. Time is self time: a ``while`` gives its body's
  time to the ops of its body.
- **Host spans.** ``xot.sched.<phase>``, ``xot.program:<family>``,
  ``xot.trace:<family>`` (``jax.profiler.TraceAnnotation``), on the trace's one
  clock, with their ``tick`` argument.
- **Idle gaps between programs**: the space between consecutive ``XLA Modules``
  events of a chip, where the chip waits for the host's next dispatch. The space
  between ops inside a running program is the compiler's and is only totalled.

A capture of a program without scopes or spans (the parent of ISSUE 24) yields
None from every reader here, never a zero.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict

import trace_reduce
from common import ROOT

FFN = ("ffn", "moe_router", "moe_experts", "moe_shared")
PHASES = ("admit", "plan", "stage", "readback", "settle")
WORKING_PHASES = ("admit", "plan", "stage", "settle")  # readback is mostly a wait for the device
DECODE_FAMILIES = ("decode.paged_batch", "decode.mixed_paged_batch")

# ------------------------------------------------------------------ protobuf wire format
# tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
# .stat_metadata = 5 (maps: entry.key = 1, entry.value = 2); XEventMetadata.name = 2, .stats = 5;
# XStatMetadata.name = 2; XStat.metadata_id = 1, .uint64_value = 3, .str_value = 5, .ref_value = 7 (a stat_metadata id).


def _varint(buf, i: int) -> tuple[int, int]:
  value = shift = 0
  while True:
    b = buf[i]
    i += 1
    value |= (b & 0x7F) << shift
    if b < 0x80:
      return value, i
    shift += 7


def _fields(buf):
  """(field number, value) of one message: an int for a varint, a memoryview for a length-delimited field."""
  i, n = 0, len(buf)
  while i < n:
    key, i = _varint(buf, i)
    wire = key & 7
    if wire == 0:
      value, i = _varint(buf, i)
    elif wire == 2:
      size, i = _varint(buf, i)
      value, i = buf[i : i + size], i + size
    elif wire in (1, 5):
      size = 8 if wire == 1 else 4
      value, i = buf[i : i + size], i + size
    else:
      raise ValueError(f"wire type {wire} in an xplane file")
    yield key >> 3, value


def _map_value(entry):
  return next((v for f, v in _fields(entry) if f == 2), None)


def event_op_names(raw: bytes) -> tuple[dict[str, dict[tuple[int, str], str]], int]:
  """``({plane name: {(program id, event name): op_name}}, collisions)`` over every event
  metadata that carries a ``tf_op`` stat. A collision is a second metadata of one program
  with the same event name and another ``op_name``: the join cannot tell the two apart
  (the first is kept), so the count is logged beside the split it may have blurred."""
  out: dict[str, dict[tuple[int, str], str]] = {}
  collisions = 0
  for f, plane in _fields(memoryview(raw)):
    if f != 1:
      continue
    name, events, stat_names = "", [], {}
    for pf, value in _fields(plane):
      if pf == 2:
        name = bytes(value).decode()
      elif pf == 4:
        events.append(_map_value(value))
      elif pf == 5:
        meta = dict(_fields(_map_value(value)))
        stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    ids = {v: k for k, v in stat_names.items()}
    tf_op, program_id = ids.get("tf_op"), ids.get("program_id")
    names: dict[tuple[int, str], str] = {}
    for ev in events if tf_op is not None else ():
      ev_name, op_name, program = "", None, 0
      for ef, value in _fields(ev):
        if ef == 2:
          ev_name = bytes(value).decode()
        elif ef == 5:
          stat = dict(_fields(value))
          if stat.get(1) == tf_op:
            op_name = bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7), "")
          elif stat.get(1) == program_id:
            program = stat.get(3, 0)
      if op_name:
        collisions += names.setdefault((program, ev_name), op_name) != op_name
    out[name] = names
  return out, collisions


# ------------------------------------------------------------------ the reduction


_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")  # ``jit__fused_paged_batch_decode_impl(1204502711026768107)``


def component_of(op_name: str | None) -> tuple[str, bool]:
  """(component, is a dequantisation) of one device op."""
  parts = [p[4:] for p in (op_name or "").split("/") if p.startswith("xot.")]
  owner = next((p for p in parts if p != "dequant"), "dequant" if parts else "unscoped")
  return owner, "dequant" in parts


def reduce(path: str, families: dict[str, str]) -> dict:
  """One capture, reduced. Seconds are means over chips."""
  with open(path, "rb") as f:
    op_names, collisions = event_op_names(f.read())
  pd = trace_reduce.load(path)
  planes = trace_reduce.device_planes(pd)
  scope_s: dict[str, float] = defaultdict(float)  # decode families only
  unscoped: dict[str, float] = defaultdict(float)  # ... by instruction name, to say what the compiler added
  dequant_s = in_program_gap_s = 0.0
  decode = {"device_s": 0.0, "executions": 0}
  gaps: list[tuple[float, float]] = []
  scoped_ops = 0
  for plane in planes:
    names = op_names.get(plane.name, {})
    mods = trace_reduce._events(trace_reduce._line(plane, "xla modules"))
    starts = [s for s, _, _ in mods]
    programs = [int(m.group(1)) if (m := _PROGRAM_ID.search(n)) else 0 for _, _, n in mods]
    is_decode = [families.get(trace_reduce.module_base(n), trace_reduce.module_base(n)) in DECODE_FAMILIES for _, _, n in mods]
    for (s, e, _), dec in zip(mods, is_decode):
      if dec:
        decode["device_s"] += e - s
        decode["executions"] += 1
    merged = trace_reduce._merge([(s, e) for s, e, _ in mods])
    gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    ops = trace_reduce._events(trace_reduce._line(plane, "xla ops"))
    busy_in_decode = 0.0
    for t_self, i in trace_reduce.self_times([(s, e, i) for i, (s, e, _) in enumerate(ops)]):
      s, _, name = ops[i]
      m = bisect.bisect_right(starts, s) - 1
      if m < 0 or s >= mods[m][1] or not is_decode[m]:
        continue
      owner, dequant = component_of(names.get((programs[m], name)))
      scope_s[owner] += t_self
      if owner == "unscoped":
        unscoped[trace_reduce.op_base(name)] += t_self
      busy_in_decode += t_self
      dequant_s += t_self if dequant else 0.0
      scoped_ops += owner != "unscoped"
    in_program_gap_s += sum(e - s for (s, e, _), dec in zip(mods, is_decode) if dec) - busy_in_decode
  n = max(len(planes), 1)
  host = []
  for plane in pd.planes:
    if plane.name.startswith("/device:"):
      continue
    for line in plane.lines:
      for ev in line.events:
        if ev.name.startswith("xot."):
          stats = dict(ev.stats)
          host.append((ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9, ev.name, stats.get("tick")))
  return {
    "chips": len(planes),
    "decode": {"device_s": decode["device_s"] / n, "executions": decode["executions"] / n},
    "scope_s": {k: v / n for k, v in scope_s.items()},
    "scoped": scoped_ops > 0,  # False: a program without scopes, whose split must read as absent and not as zero
    "dequant_s": dequant_s / n,
    "unscoped_ops": [[k, v / n] for k, v in sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]],
    "in_program_gap_s": in_program_gap_s / n,
    "op_name_collisions": collisions,
    "gaps": gaps,
    "host": sorted(host, key=lambda h: h[:2]),
  }


def idle_named_share(red: dict):
  """Of the idle seconds between programs, the share overlapped by one of the program's own host spans."""
  spans = trace_reduce._merge([(s, e) for s, e, _, _ in red["host"]])
  if not spans or not red["gaps"]:
    return None
  starts = [s for s, _ in spans]
  idle = named = 0.0
  for a, b in red["gaps"]:
    idle += b - a
    i = max(bisect.bisect_right(starts, a) - 1, 0)
    while i < len(spans) and spans[i][0] < b:
      named += max(min(spans[i][1], b) - max(spans[i][0], a), 0.0)
      i += 1
  return named / idle if idle > 0 else None


def phase_ms_per_tick(red: dict) -> dict | None:
  """Host milliseconds per scheduler tick by phase, over the ticks the capture holds
  whole. The ``xot.sched.*`` spans share their boundaries with
  ``sched_phase_seconds_total``. The profiler keeps a host span whole or not at all, so
  the capture's edges cut ticks, not spans: a tick begins with its ``stage`` spans (the
  loop thread's, then the executor thread's) and ends with its ``settle``, and it counts
  when the capture holds its ``settle`` and as many ``stage`` spans as any tick has.
  ``admit`` and ``plan`` carry no tick: they are the host's way to the next dispatch and
  belong to the tick of the next ``stage`` span. Spans of other ticks are left out of
  numerator and denominator alike; ``ticks`` is how many were counted."""
  phases = sorted(((s, e, name[10:], tick) for s, e, name, tick in red["host"] if name[:10] == "xot.sched." and name[10:] in PHASES), key=lambda p: p[:2])
  stages: dict[int, int] = defaultdict(int)
  settled = set()
  for _, _, phase, tick in phases:
    if tick is not None and phase == "stage":
      stages[int(tick)] += 1
    elif tick is not None and phase == "settle":
      settled.add(int(tick))
  whole = {t for t, n in stages.items() if n == max(stages.values()) and t in settled}
  if not whole:
    return None
  stage_starts = [(s, int(tick)) for s, _, phase, tick in phases if phase == "stage" and tick is not None]
  total: dict[str, float] = defaultdict(float)
  for s, e, phase, tick in phases:
    if tick is None:  # admit, plan
      i = bisect.bisect_left(stage_starts, (e,))
      tick = stage_starts[i][1] if i < len(stage_starts) else None
    if tick is not None and int(tick) in whole:
      total[phase] += e - s
  return {**{k: v * 1e3 / len(whole) for k, v in total.items()}, "ticks": len(whole)}


# ------------------------------------------------------------------ what the readers call

_MEMO: dict[str, dict] = {}


def capture(ctx: dict) -> dict | None:
  """The newest capture, reduced once per process; None when the run traced nothing."""
  if not ctx.get("trace"):
    return None
  path = trace_reduce.find_xplane(str(ROOT / "_work" / "bench_trace"))
  if path is None:
    return None
  if path not in _MEMO:
    red = _MEMO[path] = reduce(path, trace_reduce.program_families())
    steps = red["decode"]["executions"] * ctx["chunk"]
    per_step = lambda s: s * 1e3 / steps if steps else None  # noqa: E731
    print(json.dumps({
      "event": "scopes",
      "decode_step_ms": {k: per_step(v) for k, v in sorted(red["scope_s"].items())} if red["scoped"] else None,
      "decode_dequant_ms": per_step(red["dequant_s"]) if red["scoped"] else None,
      "decode_unscoped_ops_ms": [[k, per_step(v)] for k, v in red["unscoped_ops"]] if red["scoped"] else None,
      "decode_program_ms": per_step(red["decode"]["device_s"]),
      "decode_in_program_gap_ms": per_step(red["in_program_gap_s"]),
      "idle_between_programs_s": sum(b - a for a, b in red["gaps"]) / max(red["chips"], 1),
      "idle_named_share": idle_named_share(red),
      "sched_ms_per_tick": phase_ms_per_tick(red),
      "op_name_collisions": red["op_name_collisions"],
    }), file=sys.stderr, flush=True)
  return _MEMO[path]


def decode_scope_ms(ctx: dict, components: tuple[str, ...]):
  """Device self time per decode step under the named components (``"dequant"``: every dequantisation, whatever its owner)."""
  red = capture(ctx)
  if red is None or not red["scoped"] or not red["decode"]["executions"]:
    return None
  seconds = red["dequant_s"] if components == ("dequant",) else sum(red["scope_s"].get(c, 0.0) for c in components)
  return seconds * 1e3 / (red["decode"]["executions"] * ctx["chunk"])
