"""One mechanism for an architecture kind: ``benchmark/arch_<kind>.py``, found
by the ``arch_kind`` of a configuration file. Everything the harness needs to
know about a kind is in that file, under the same names for every kind; the
shared files (``weights.py``, ``reference.py``, ``flops_bytes.py``,
``correctness.py``, ``layer_lib.py``, ``run.py``) ask ``load(kind)`` and hold no
table and no ``if kind ==``.

A kind's file exports:

  make_params(hf, key) -> dict       every weight leaf from a typed key, in the type it is served in (traced
                                     inside one jit by ``weights.build_params``); ``hf`` is the hashable part
                                     of the configuration file
  reference_forward(params, hf, tokens, **probe) -> [S, V] logits
                                     the plain float32 forward pass, written from the published equations;
                                     ``probe`` takes the keywords ``probes`` names and nothing else
  LIMITS                             {"mean_abs", "max_abs", "greedy_margin"}: the three numbers that decide
                                     ``correct`` (``correctness.verdict``)
  LIMITS_WHY                         the same three keys: the readings each limit was set from
  probes(hf) -> {name: keywords}     deliberately wrong references the limits must refuse
                                     (``run.py --probe-sensitivity``)
  REHEARSE_WIDTHS                    the tiny widths of ``run.py --rehearse`` and of the CPU tests
  step_weight_bytes(hf, rows)        bytes of the weights that take part in one decode step of ``rows`` rows
  cache_read_bytes(hf, rows, resident_tokens, kv_quant) -> [bytes per layer]
                                     what each layer's attention reads of the cache in one decode step of
                                     ``rows`` rows that hold ``resident_tokens`` cached tokens in all; one
                                     entry a layer, so a kind whose layers read different amounts says so
  step_matmul_flops(hf, rows)        matrix-multiply operations of one decode step (2 a multiply-add)
  CACHE_TYPE_ENV                     the ``serving_env`` key that names the cache's stored type, or None
                                     where the kind's cache has one type whatever that key says

A file that lacks a part is refused here, by the name of the part, before any
weight is made. Parts a reader or a tool asks for only where the kind has them
(``getattr``; no table): ``ssm_state_bytes(hf, rows)`` and ``moe_expert_bytes(hf,
rows)`` for the two rooflines of those names; for a kind with routed experts
``routed_experts(hf) -> (first, counted, routed, top_k)``, the experts a step's
bytes count, and ``router_tables(hf, key)``, its topic router's tables as
``make_params`` draws them (``tools/experts_touched.py``); ``reference_forward``
of such a kind also takes ``routed=[]`` and fills it with the router's choices."""

from __future__ import annotations

import importlib
from functools import cache

PARTS = (
  "make_params", "reference_forward", "LIMITS", "LIMITS_WHY", "probes", "REHEARSE_WIDTHS",
  "step_weight_bytes", "cache_read_bytes", "step_matmul_flops", "CACHE_TYPE_ENV",
)
LIMIT_NAMES = ("mean_abs", "max_abs", "greedy_margin")


@cache
def load(kind: str):
  """The module ``arch_<kind>``, whole: every part of PARTS present, the limits complete."""
  try:
    mod = importlib.import_module(f"arch_{kind}")
  except ModuleNotFoundError as e:
    if e.name != f"arch_{kind}":
      raise
    raise SystemExit(f"arch_kind {kind!r}: no benchmark/arch_{kind}.py (see benchmark/arch.py for what it exports)") from None
  missing = [p for p in PARTS if not hasattr(mod, p)]
  missing += [f"{table}[{name!r}]" for table in ("LIMITS", "LIMITS_WHY") if hasattr(mod, table) for name in LIMIT_NAMES if name not in getattr(mod, table)]
  if missing:
    raise SystemExit(f"benchmark/arch_{kind}.py lacks {', '.join(missing)} (see benchmark/arch.py for what a kind exports)")
  return mod
