"""Device-program ledger: compile tracking for every serving-path jit.

The repo's perf trajectory rests on "mix changes never recompile" claims
(per-row spec gamma ISSUE 7/12, mixed-tick pad buckets ISSUE 14, LoRA slot
swaps ISSUE 15) that were asserted in CHANGES.md but measured nowhere. This
module turns them into a gated measurement (ISSUE 19):

- ``tracked_jit(family, fn, **jit_kwargs)`` wraps ``jax.jit`` at every
  serving-path jit site (enforced by ``scripts/check_tracked_jit.py``). The
  inner python body only executes while JAX is *tracing* — i.e. exactly when
  a new device program is being built — so a hook at the top of the wrapped
  body is a dependency-free compile detector: it bumps the family's compile
  count and captures the abstract shape signature that triggered the trace.
- Per family the ledger records: compile count, ``program_compile_seconds``
  (wall time of the compiling dispatch: trace + lower + backend compile),
  dispatch count, and ``program_dispatch_seconds`` (host wall time of a
  steady dispatch: JAX returns once the program is enqueued, so this is
  never device time — the profiler's trace has that). A ``jax.monitoring``
  duration listener additionally records the backend's own compile seconds
  into the ledger snapshot (``xla_compile_s``).
- **On the profiler's clock** (ISSUE 24): every top-level dispatch runs
  under ``jax.profiler.TraceAnnotation("xot.program:<family>")`` carrying
  the scheduler's tick number and row count (from :func:`dispatch_context`),
  and the python body of a program being traced under
  ``xot.trace:<family>``, so a capture shows which host span a device idle
  gap fell into and tells a compiling dispatch from a steady one. Both cost
  nothing while no capture runs. The scheduler's ``xot.sched.stage`` span
  around a MIXED tick's dispatch also carries ``pf_tokens`` / ``pf_pad``:
  the real and the padded tokens of the prefill slice it hands over.
- **Component scopes** (:func:`component_scope`, ``jax.named_scope``): the
  model code names its components in HLO metadata, one vocabulary for every
  program — ``xot.embed``, ``xot.attn_proj`` (norm, q/k/v or latent
  projections, rope, ``wo``), ``xot.kv_write`` (KV quantisation, cache and
  page writes, the prefill's page gather/scatter, the split and join of the
  cache between a model's two layer stacks), ``xot.attn`` (the
  attention core), ``xot.ffn``, ``xot.moe_router`` / ``xot.moe_experts`` /
  ``xot.moe_shared``, ``xot.dequant`` (nested: a weight conversion that XLA
  did not fuse into its matmul), ``xot.head``, ``xot.sample``. The trace
  reducer (benchmark/span_lib.py) reads them from each device op's
  ``op_name``; they change no optimised program (tests/test_named_scopes.py).
  One name is NOT a component: ``mixed.prefill`` (models/decoder.py
  ``MIXED_PREFILL_SCOPE``) is the outer scope of a mixed tick's prefill half,
  so its ops read ``…/mixed.prefill/xot.<component>/…``; the component
  readers keep the ``xot.`` parts and see what they saw, benchmark/half_lib.py
  splits the program's device time into its two halves by that one part.
- **Warmup manifest**: the scheduler enumerates the program set expected for
  the active config; ``POST /v1/warmup`` pre-compiles it off the serving
  path and calls :meth:`ProgramLedger.mark_steady`.
- **Recompile sentinel**: any post-steady compile increments
  ``program_steady_compiles_total{family}``, emits a flight-recorder
  ``compile`` event and a ``compile`` timeline stage on the request whose
  dispatch triggered it (set by the scheduler via :func:`dispatch_context`),
  and feeds the ``recompile_storm`` anomaly-watcher rule.

Nesting: a tracked program's body may call other tracked programs (e.g. the
fused decode calls the paged-attention kernel). During a steady-state
dispatch none of those python bodies run; during a compile the inner
families' trace hooks fire too. The ledger counts those inner traces per
family (they ARE program builds) but emits exactly ONE sentinel event per
top-level compiling dispatch, so the storm threshold counts compile
*stalls*, not call-graph fan-out.

Knobs:

- ``XOT_TPU_PROGRAMS`` (default on) — ``0`` disables all recording at the
  dispatch wrapper; the jitted computation is byte-identical either way
  (poison-pinned in tests/test_programs.py).
- ``XOT_TPU_ANOMALY_RECOMPILE_WINDOW_S`` / ``XOT_TPU_ANOMALY_RECOMPILES``
  (orchestration/flightrec.py) — the storm rule's window and threshold.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from contextlib import contextmanager

from .metrics import metrics


def programs_enabled() -> bool:
  """Checked per CALL (not at import) so tests can toggle without reload."""
  return os.getenv("XOT_TPU_PROGRAMS", "1") not in ("0", "false")


def component_scope(name: str):
  """Decorator: trace ``fn`` under ``jax.named_scope(name)``. A scope of its
  own per call — ``jax.named_scope`` used as a decorator shares one context
  object between threads that trace the same function at once."""

  def deco(fn):
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
      import jax

      with jax.named_scope(name):
        return fn(*args, **kwargs)

    return scoped

  return deco


def _describe_one(x) -> str:
  """One argument → compact abstract signature token.

  Tracers and arrays render as ``dtype[shape]``; pytrees (param dicts) as a
  leaf-count summary — the signature must be cheap and must not retain
  tracers."""
  shape = getattr(x, "shape", None)
  dtype = getattr(x, "dtype", None)
  if shape is not None and dtype is not None:
    return f"{dtype}[{','.join(str(d) for d in shape)}]"
  if isinstance(x, dict):
    try:
      import jax

      leaves = jax.tree_util.tree_leaves(x)
      return f"tree({len(leaves)} leaves)"
    except Exception:
      return f"dict({len(x)})"
  if isinstance(x, (tuple, list)):
    if len(x) > 4:
      return f"{type(x).__name__}[{len(x)}]"
    return f"({','.join(_describe_one(e) for e in x)})"
  if isinstance(x, (bool, int, float, str, type(None))):
    return repr(x)
  return type(x).__name__


def describe_signature(args: tuple, kwargs: dict) -> str:
  parts = [_describe_one(a) for a in args]
  parts += [f"{k}={_describe_one(v)}" for k, v in sorted(kwargs.items())]
  sig = ", ".join(parts)
  return sig if len(sig) <= 512 else sig[:509] + "..."


class ProgramLedger:
  """Process-wide compile/dispatch bookkeeping, keyed by program family."""

  MAX_SIGNATURES = 8  # per family — enough to see a storm's shapes, bounded

  def __init__(self) -> None:
    self._lock = threading.Lock()
    self._tls = threading.local()
    self._families: dict[str, dict] = {}
    self._steady = False
    self._steady_ts: float | None = None
    self._manifest: list[dict] = []
    self._warmup: dict = {}

  # ------------------------------------------------------------- state

  def _family(self, family: str) -> dict:
    st = self._families.get(family)
    if st is None:
      st = {
        "compiles": 0,
        "steady_compiles": 0,
        "dispatches": 0,
        "compile_s": 0.0,
        "dispatch_s": 0.0,
        "xla_compile_s": 0.0,
        "signatures": [],
        "last_compile_ts": None,
        "last_dispatch_ts": None,
      }
      self._families[family] = st
    return st

  @property
  def steady(self) -> bool:
    return self._steady

  def mark_steady(self, manifest: list[dict] | None = None) -> None:
    """Warmup is done: from here on, every compile is an anomaly."""
    with self._lock:
      self._steady = True
      self._steady_ts = time.time()
      if manifest is not None:
        self._manifest = list(manifest)
    metrics.set_gauge("programs_steady", 1.0)

  def unmark_steady(self) -> None:
    with self._lock:
      self._steady = False
      self._steady_ts = None
    metrics.set_gauge("programs_steady", 0.0)

  def reset(self) -> None:
    """Test/bench hook: forget everything (metrics series are left alone —
    the registry owns its own reset)."""
    with self._lock:
      self._families.clear()
      self._steady = False
      self._steady_ts = None
      self._manifest = []
      self._warmup = {}
    metrics.set_gauge("programs_steady", 0.0)

  def note_warmup(self, manifest: list[dict], per_family_s: dict[str, float], total_s: float) -> None:
    with self._lock:
      self._warmup = {
        "ts": time.time(),
        "total_s": total_s,
        "families": dict(per_family_s),
      }
      self._manifest = list(manifest)
    metrics.set_gauge("warmup_programs", float(len(manifest)))
    metrics.observe_hist("warmup_compile_seconds", total_s)

  # ----------------------------------------------------------- queries

  def compile_count(self, family: str | None = None) -> int:
    with self._lock:
      if family is not None:
        return self._families.get(family, {}).get("compiles", 0)
      return sum(st["compiles"] for st in self._families.values())

  def steady_compile_count(self, family: str | None = None) -> int:
    with self._lock:
      if family is not None:
        return self._families.get(family, {}).get("steady_compiles", 0)
      return sum(st["steady_compiles"] for st in self._families.values())

  def dispatch_count(self, family: str | None = None) -> int:
    with self._lock:
      if family is not None:
        return self._families.get(family, {}).get("dispatches", 0)
      return sum(st["dispatches"] for st in self._families.values())

  def dispatch_counts(self) -> dict[str, int]:
    with self._lock:
      return {f: st["dispatches"] for f, st in self._families.items()}

  def active_families(self, baseline: dict[str, int]) -> list[str]:
    """Families dispatched since ``baseline`` (a prior dispatch_counts()) —
    how profile captures and slow-request logs join against the ledger."""
    cur = self.dispatch_counts()
    return sorted(f for f, n in cur.items() if n > baseline.get(f, 0))

  def families_active_since(self, wall_ts: float) -> list[str]:
    """Families with a dispatch at or after ``wall_ts`` — the slow-request
    log's "which programs ran inside this request's window" annotation."""
    with self._lock:
      return sorted(
        f for f, st in self._families.items()
        if st.get("last_dispatch_ts") is not None and st["last_dispatch_ts"] >= wall_ts
      )

  def warmup_compile_s_total(self) -> float:
    with self._lock:
      return float(self._warmup.get("total_s", 0.0))

  def snapshot(self) -> dict:
    """JSON-safe introspection payload (GET /v1/programs, bundles)."""
    with self._lock:
      fams = {
        f: {
          "compiles": st["compiles"],
          "steady_compiles": st["steady_compiles"],
          "dispatches": st["dispatches"],
          "compile_s": round(st["compile_s"], 6),
          "dispatch_s": round(st["dispatch_s"], 6),
          "xla_compile_s": round(st["xla_compile_s"], 6),
          "signatures": list(st["signatures"]),
          "last_compile_ts": st["last_compile_ts"],
        }
        for f, st in sorted(self._families.items())
      }
      return {
        "enabled": programs_enabled(),
        "steady": self._steady,
        "steady_ts": self._steady_ts,
        "families": fams,
        "manifest": list(self._manifest),
        "warmup": dict(self._warmup),
        "totals": {
          "compiles": sum(st["compiles"] for st in fams.values()),
          "steady_compiles": sum(st["steady_compiles"] for st in fams.values()),
          "dispatches": sum(st["dispatches"] for st in fams.values()),
        },
      }

  @staticmethod
  def merge_snapshots(parts: list[dict]) -> dict:
    """Cluster scope: sum counts per family across node snapshots; a family
    is steady only if every reporting node is steady."""
    fams: dict[str, dict] = {}
    nodes = []
    for p in parts:
      nodes.append(p.get("node_id"))
      for f, st in (p.get("families") or {}).items():
        agg = fams.setdefault(
          f, {"compiles": 0, "steady_compiles": 0, "dispatches": 0, "compile_s": 0.0, "dispatch_s": 0.0, "xla_compile_s": 0.0, "signatures": []}
        )
        for k in ("compiles", "steady_compiles", "dispatches"):
          agg[k] += int(st.get(k, 0))
        for k in ("compile_s", "dispatch_s", "xla_compile_s"):
          agg[k] = round(agg[k] + float(st.get(k, 0.0)), 6)
        for sig in st.get("signatures", []):
          if sig not in agg["signatures"] and len(agg["signatures"]) < ProgramLedger.MAX_SIGNATURES:
            agg["signatures"].append(sig)
    return {
      "scope": "cluster",
      "nodes": [n for n in nodes if n],
      "steady": all(bool(p.get("steady")) for p in parts) if parts else False,
      "families": {f: fams[f] for f in sorted(fams)},
      "totals": {
        "compiles": sum(a["compiles"] for a in fams.values()),
        "steady_compiles": sum(a["steady_compiles"] for a in fams.values()),
        "dispatches": sum(a["dispatches"] for a in fams.values()),
      },
    }

  # ----------------------------------------------------- trace/dispatch

  def _on_trace(self, family: str, args: tuple, kwargs: dict) -> None:
    """Runs inside the wrapped function body — i.e. only while tracing."""
    if not programs_enabled():
      return
    sig = describe_signature(args, kwargs)
    with self._lock:
      st = self._family(family)
      st["compiles"] += 1
      st["last_compile_ts"] = time.time()
      if sig not in st["signatures"]:
        st["signatures"].append(sig)
        del st["signatures"][: -self.MAX_SIGNATURES]
    metrics.inc("program_compiles_total", labels={"family": family})
    traced = getattr(self._tls, "traced", None)
    if traced is not None:
      traced.append((family, sig))
    # current family for the jax.monitoring backend-compile listener
    self._tls.compiling_family = family

  def _dispatch(self, family: str, jitted, args: tuple, kwargs: dict):
    depth = getattr(self._tls, "depth", 0)
    if depth:
      # Nested call: our python body is running, so an ENCLOSING tracked
      # program is tracing. The inner trace hook has already counted this
      # family's build; don't double-record a dispatch.
      return jitted(*args, **kwargs)
    import jax

    self._tls.depth = 1
    self._tls.traced = traced = []
    ctx = current_dispatch_context() or {}
    t0 = time.perf_counter()
    try:
      with jax.profiler.TraceAnnotation(f"xot.program:{family}", tick=ctx.get("tick", -1), rows=len(ctx.get("request_ids") or ())):
        out = jitted(*args, **kwargs)
    finally:
      self._tls.depth = 0
      self._tls.traced = None
      self._tls.compiling_family = None
    dt = time.perf_counter() - t0
    with self._lock:
      st = self._family(family)
      st["dispatches"] += 1
      st["last_dispatch_ts"] = time.time()
      if traced:
        st["compile_s"] += dt
      else:
        st["dispatch_s"] += dt
    metrics.inc("program_dispatch_total", labels={"family": family})
    if traced:
      metrics.observe_hist("program_compile_seconds", dt, labels={"family": family})
      if self._steady:
        self._steady_compile_sentinel(family, traced, dt)
    else:
      metrics.observe_hist("program_dispatch_seconds", dt, labels={"family": family})
    return out

  def _steady_compile_sentinel(self, family: str, traced: list, seconds: float) -> None:
    """One post-steady compiling dispatch → one sentinel: counter + flight
    event + a ``compile`` timeline stage on the triggering request(s)."""
    with self._lock:
      self._family(family)["steady_compiles"] += 1
    metrics.inc("program_steady_compiles_total", labels={"family": family})
    sig = traced[0][1] if traced else ""
    nested = sorted({f for f, _ in traced if f != family})
    ctx = current_dispatch_context()
    rids = list(ctx.get("request_ids") or []) if ctx else []
    node = ctx.get("node") if ctx else None
    attrs = {
      "family": family,
      "signature": sig,
      "seconds": round(seconds, 6),
      "nested": nested,
      "request_ids": rids,
    }
    try:  # lazy: utils must not drag orchestration in at import time
      from ..orchestration.flightrec import flightrec

      flightrec.record("compile", request_id=rids[0] if rids else None, node=node, cause="steady_recompile", attributes=attrs)
    except Exception:
      pass
    try:
      from ..orchestration.tracing import tracer

      for rid in rids:
        tracer.stage(rid, "compile", attributes={"family": family, "signature": sig, "seconds": round(seconds, 6)}, node=node)
    except Exception:
      pass

  def note_xla_compile_seconds(self, seconds: float) -> None:
    """jax.monitoring listener feed: backend compile wall, attributed to the
    family whose trace is in flight on this thread (best effort)."""
    family = getattr(self._tls, "compiling_family", None) or "_untracked"
    with self._lock:
      self._family(family)["xla_compile_s"] += float(seconds)


ledger = ProgramLedger()

_DISPATCH_TLS = threading.local()


@contextmanager
def dispatch_context(request_ids, node: str | None = None, tick: int = -1):
  """Scheduler-side attribution: set inside the executor-thread ``run()``
  closure around device dispatches, so a compile triggered by that dispatch
  can name the request(s) it stalled, and the dispatch's ``xot.program:*``
  span the scheduler tick that issued it."""
  prev = getattr(_DISPATCH_TLS, "ctx", None)
  _DISPATCH_TLS.ctx = {"request_ids": [r for r in (request_ids or []) if r], "node": node, "tick": tick}
  try:
    yield
  finally:
    _DISPATCH_TLS.ctx = prev


def current_dispatch_context() -> dict | None:
  return getattr(_DISPATCH_TLS, "ctx", None)


# --------------------------------------------------- jax.monitoring bridge

_MON_INSTALLED = False
# jax's duration event for the backend's own compile (XLA + Mosaic). It is
# not emitted for a program the persistent compilation cache served, so
# ``xla_compile_s`` falls to near zero on a warm start while the trace-side
# ``compiles`` count stays what it was.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _install_monitoring_listener() -> None:
  global _MON_INSTALLED
  if _MON_INSTALLED:
    return
  from jax import monitoring

  def _listener(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT and programs_enabled():
      ledger.note_xla_compile_seconds(duration)

  monitoring.register_event_duration_secs_listener(_listener)
  _MON_INSTALLED = True


# ---------------------------------------------------------------- wrapper


def tracked_jit(family: str, fn=None, **jit_kwargs):
  """``jax.jit`` with ledger hooks; decorator or direct form.

  ``tracked_jit("decode.fused", fn, static_argnames=...)`` or::

    @partial(tracked_jit, "decode.fused", static_argnames=(...))
    def _fused_decode_impl(...): ...

  ``jit_kwargs`` pass through verbatim (static_argnames/donate_argnums keep
  working: ``functools.wraps`` preserves the wrapped signature for jax's
  name→index resolution, and arguments pass through positionally)."""
  if fn is None:
    return lambda f: tracked_jit(family, f, **jit_kwargs)

  import jax

  _install_monitoring_listener()

  @functools.wraps(fn)
  def _traced(*args, **kwargs):
    ledger._on_trace(family, args, kwargs)
    with jax.profiler.TraceAnnotation(f"xot.trace:{family}"):
      return fn(*args, **kwargs)

  jitted = jax.jit(_traced, **jit_kwargs)

  @functools.wraps(fn)
  def _dispatching(*args, **kwargs):
    if not programs_enabled():
      return jitted(*args, **kwargs)
    return ledger._dispatch(family, jitted, args, kwargs)

  _dispatching.xot_family = family
  _dispatching.xot_jitted = jitted  # AOT escape hatch (.lower() etc.)
  return _dispatching
