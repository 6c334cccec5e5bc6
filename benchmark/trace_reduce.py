"""From the profiler's ``.xplane.pb`` to numbers, with nothing but
``jax.profiler.ProfileData``.

What the TPU's trace holds (looked at by hand, PR 23; ``describe`` prints it):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Modules`` has one
event per executed program, named ``jit_<wrapped function>(<fingerprint>)``,
and whose line ``XLA Ops`` has one event per HLO operation that ran (fusions,
copies, custom calls — a Pallas kernel is a custom call carrying the kernel's
name). Host planes (``/host:CPU``) hold one line per thread.

A program family's device time is the sum of its module events. Busy time is
the union of the ``XLA Ops`` intervals; an idle gap is the space between two
merged intervals, and is named by the host event that overlaps it most.
Durations are picoseconds-accurate in the file; seconds here.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict


def find_xplane(trace_dir: str) -> str | None:
  files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
  return files[-1] if files else None


def load(path: str):
  from jax.profiler import ProfileData

  return ProfileData.from_file(path)


def _line(plane, *needles: str):
  for line in plane.lines:
    name = line.name.lower()
    if any(n in name for n in needles):
      return line
  return None


def _events(line) -> list[tuple[float, float, str]]:
  """(start_s, end_s, name), sorted by start."""
  out = [(ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9, ev.name) for ev in line.events]
  out.sort()
  return out


def _merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
  merged: list[list[float]] = []
  for s, e in sorted(intervals):
    if merged and s <= merged[-1][1]:
      merged[-1][1] = max(merged[-1][1], e)
    else:
      merged.append([s, e])
  return [(s, e) for s, e in merged]


_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(.*\))?$")


def module_base(name: str) -> str:
  """``jit__fused_paged_batch_decode_impl(123)`` -> ``_fused_paged_batch_decode_impl``."""
  return _MODULE.match(name.strip()).group(1)


_SUFFIX = re.compile(r"\.\d+")


def op_base(name: str) -> str:
  """An ``XLA Ops`` event is named by its whole HLO instruction
  (``%copy.152 = s8[...] copy(...)``); keep the instruction's name without its
  numbering: ``copy``, ``fusion``, ``_paged_decode_attention_impl``."""
  head = name.split(" = ", 1)[0].strip().lstrip("%")
  return _SUFFIX.sub("", head)[:80]


def self_times(ops: list[tuple[float, float, str]]) -> list[tuple[float, str]]:
  """(self seconds, name) per op event: a ``while`` or ``call`` event spans the
  ops of its body, which the line also holds; their time is taken out of it."""
  out: list[list] = []
  stack: list[int] = []
  for s, e, name in sorted(ops, key=lambda x: (x[0], -(x[1] - x[0]))):
    while stack and out[stack[-1]][2] <= s:
      stack.pop()
    if stack:
      out[stack[-1]][0] -= e - s
    out.append([e - s, name, e])
    stack.append(len(out) - 1)
  return [(max(t, 0.0), name) for t, name, _ in out]


def device_planes(pd) -> list:
  return [p for p in pd.planes if p.name.startswith("/device:TPU:") and _line(p, "xla ops") is not None]


def reduce(pd, families: dict[str, str], window_s: float, kernels: tuple[str, ...] = ()) -> dict:
  """``families`` maps a wrapped function's ``__name__`` to its ``tracked_jit``
  family; ``kernels`` are substrings that mark a kernel's op events. Returns
  busy seconds (mean over chips), per-family and per-kernel device seconds
  and counts, the ten heaviest ops and the ten longest idle gaps."""
  planes = device_planes(pd)
  if not planes:
    return {"chips": 0, "busy_s": 0.0, "window_s": window_s, "programs": {}, "kernels": {}, "device_ops": [], "idle_gaps": []}
  host = _host_events(pd)
  busy, programs, kern, op_time = [], defaultdict(lambda: {"device_s": 0.0, "executions": 0}), defaultdict(lambda: {"device_s": 0.0, "calls": 0}), defaultdict(float)
  gaps: list[tuple[float, float, float]] = []
  for plane in planes:
    ops = _events(_line(plane, "xla ops"))
    merged = _merge([(s, e) for s, e, _ in ops])
    busy.append(sum(e - s for s, e in merged))
    gaps += [(b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])]
    for t_self, name in self_times(ops):
      base = op_base(name)
      op_time[base] += t_self
      for k in kernels:
        if k in base:
          kern[k]["device_s"] += t_self
          kern[k]["calls"] += 1
    mods = _line(plane, "xla modules")
    for s, e, name in _events(mods) if mods is not None else ():
      fam = families.get(module_base(name), module_base(name))
      programs[fam]["device_s"] += e - s
      programs[fam]["executions"] += 1
  n = len(planes)
  for d in (*programs.values(), *kern.values()):
    d["device_s"] /= n
  gaps.sort(reverse=True)
  named: dict[str, float] = defaultdict(float)
  for length, s, e in gaps[:200]:
    named[_host_name(host, s, e)] += length
  return {
    "chips": n,
    "busy_s": sum(busy) / n,
    "window_s": window_s,
    "programs": {k: dict(v) for k, v in programs.items()},
    "kernels": {k: dict(v) for k, v in kern.items()},
    "device_ops": [[k, v / n] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
    "idle_gaps": [[k, v / n] for k, v in sorted(named.items(), key=lambda kv: -kv[1])[:10]],
  }


def _host_events(pd) -> list[tuple[float, float, str]]:
  out = []
  for plane in pd.planes:
    if plane.name.startswith("/device:"):
      continue
    for line in plane.lines:
      out += [(s, e, f"{line.name.split('/')[0]}:{name}"[:96]) for s, e, name in _events(line) if e > s]
  out.sort()
  return out


def _host_name(host: list[tuple[float, float, str]], s: float, e: float) -> str:
  """The host event covering most of the gap [s, e); the shortest such event
  wins ties, so an enclosing thread-long span does not name everything."""
  best, best_key = "unattributed", (0.0, 0.0)
  for hs, he, name in host:
    if hs >= e:
      break
    overlap = min(he, e) - max(hs, s)
    if overlap <= 0:
      continue
    key = (round(overlap / (e - s), 2), -(he - hs))
    if key > best_key:
      best, best_key = name, key
  return best if best_key[0] >= 0.5 else "unattributed"


def describe(pd, limit: int = 12) -> str:
  """Planes, lines and the first event names: what to read before writing a reader."""
  rows = []
  for plane in pd.planes:
    rows.append(f"plane {plane.name!r}")
    for line in plane.lines:
      evs = list(line.events)
      names = defaultdict(int)
      for ev in evs:
        names[ev.name] += 1
      top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
      rows.append(f"  line {line.name!r}: {len(evs)} events; " + ", ".join(f"{n[:70]}x{c}" for n, c in top))
  return "\n".join(rows)


PROGRAM_PACKAGE = "xotorch_support_jetson_tpu"


def program_families() -> dict[str, str]:
  """wrapped ``__name__`` -> family, for every ``tracked_jit`` callable the
  process holds: the device trace shows ``jit_<name>``, the ledger and the
  metrics speak of families. ``utils/programs.py`` keeps no list of what it
  wrapped, and half of the program's tracked callables are closures made when
  a batch is built (``parallel/pp_batch.py``, ``sp_batch.py``,
  ``inference/kv_tier.py``), which no walk over module attributes reaches; so
  this asks the collector for every function of the program's package that
  carries ``xot_family``, wherever it lives. Built at run time, after the
  window, so a refactor that keeps the family keeps the metric and a program a
  new architecture defines in a new module is found. A name that two families
  share is left out (its events then go by the function's own name)."""
  import gc
  import types

  out: dict[str, str] = {}
  clash: set[str] = set()
  for obj in gc.get_objects():
    if not isinstance(obj, types.FunctionType) or not (obj.__module__ or "").startswith(PROGRAM_PACKAGE):
      continue
    fam = getattr(obj, "xot_family", None)
    if not fam:
      continue
    inner = getattr(obj, "xot_jitted", obj)
    name = getattr(inner, "__name__", None) or getattr(getattr(inner, "__wrapped__", None), "__name__", None)
    if name and out.setdefault(name, fam) != fam:
      clash.add(name)
  return {name: fam for name, fam in out.items() if name not in clash}
