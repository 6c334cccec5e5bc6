"""Ahead-of-time HBM budgeting for serving plans (SURVEY.md §7 "hard parts").

The reference's answer to a model that doesn't fit was to drop it AFTER the
OOM (``reference/xotorch/inference/torch/sharded_inference_engine.py:85-106``
catches the crash and clears the model). Here per-chip weight + KV-cache
bytes are computed BEFORE any compile, from the EXACT shapes the engine will
allocate — ``jax.eval_shape`` over the same constructors
(``models.decoder.init_shard_params`` / ``init_kv_cache`` /
``models.quantize.quantize_params``) — divided per leaf by the mesh axes its
sharding spec names. A plan that cannot fit is refused with the numbers and
a fitting alternative (``choose_serving_plan``) instead of OOMing mid-load.

Per-leaf division rules mirror the actual placements:
- tp: megatron specs (``mesh.decoder_param_specs``) — qkv/gate/up/down shard,
  norms replicate. Used by the default engine mesh, SPServing, and the tp
  part of PPServing.
- pp: layer stacks split 1/pp per stage (``pp_serving.split_pp_params``);
  embed/head replicate on every stage.
- sp: weights replicate (the CACHE shards: S axis 1/sp).
- Cache: layer axis 1/pp, sequence axis 1/sp, kv heads 1/tp when divisible
  (``pp_serving.pp_cache_spec`` / ``sp_serving`` cache spec).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import numpy as np

from ..inference.shard import Shard
from ..models.config import ModelConfig
from ..utils.helpers import device_memory
from .mesh import MeshPlan, pow2_degree

_HEAD_KEYS = ("embed", "final_norm", "lm_head", "lm_head_scale")


def _tree_bytes(tree) -> int:
  return sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree))


def param_shapes(cfg: ModelConfig, shard: Shard | None = None, quant: str | None = None):
  """ShapeDtypeStruct pytree of the shard's params — no allocation."""
  from ..models.decoder import init_shard_params

  shard = shard or Shard("planner", 0, cfg.n_layers - 1, cfg.n_layers)
  shapes = jax.eval_shape(lambda key: init_shard_params(key, cfg, shard), jax.random.PRNGKey(0))
  if quant:
    from ..models.quantize import quantize_params

    shapes = jax.eval_shape(lambda p: quantize_params(p, quant), shapes)
  return shapes


def model_bytes(cfg: ModelConfig, shard: Shard | None = None, quant: str | None = None) -> int:
  """Total weight bytes of a shard (un-sharded)."""
  return _tree_bytes(param_shapes(cfg, shard, quant))


def _leaf_bytes(leaf) -> int:
  return int(np.prod(leaf.shape)) * leaf.dtype.itemsize


def _axis_div(spec, plan: MeshPlan) -> int:
  """How many ways ``spec`` splits a leaf over the plan's mesh axes (tp for
  megatron weights, ep for MoE expert stacks; 1 if unsharded)."""
  sizes = {"tp": plan.tp, "ep": plan.ep}
  div = 1
  for entry in spec or ():
    for ax in (entry,) if isinstance(entry, str) else (entry or ()):
      div *= sizes.get(ax, 1)
  return div


def param_bytes_per_chip(cfg: ModelConfig, plan: MeshPlan, shard: Shard | None = None, quant: str | None = None) -> int:
  """Per-chip weight bytes under ``plan`` (leaf-exact for tp via the
  megatron specs; layer stacks 1/pp; sp replicates weights)."""
  from .mesh import specs_for_params

  shapes = param_shapes(cfg, shard, quant)
  specs = specs_for_params(shapes)
  total = 0
  for key, sub in shapes.items():
    if key in ("layers", "moe_layers"):
      for lk, leaf in sub.items():
        div = _axis_div(specs[key].get(lk), plan) * (plan.pp if plan.pp > 1 else 1)
        total += math.ceil(_leaf_bytes(leaf) / div)
    elif isinstance(sub, dict):  # vision tower / projector: replicated
      total += _tree_bytes(sub)
    else:
      total += math.ceil(_leaf_bytes(sub) / _axis_div(specs.get(key), plan))
  return total


def kv_cache_bytes_per_chip(cfg: ModelConfig, plan: MeshPlan, batch: int, max_seq: int, n_layers: int | None = None) -> int:
  """Per-chip KV cache bytes: layers 1/pp, sequence 1/sp, heads 1/tp (when
  divisible) — matching pp_cache_spec / SPServing's cache spec. Under pp,
  a dense-prefix MoE model's ``first_k_dense`` layers are NOT divided: the
  prefix cache lives full-size on every stage (replicated in pp_serving,
  stage-owned in pp_batch)."""
  from ..models.decoder import init_kv_cache

  L = n_layers if n_layers is not None else cfg.n_layers
  shapes = jax.eval_shape(lambda: init_kv_cache(cfg, L, batch, max_seq))
  total = _tree_bytes(shapes)
  div = max(plan.pp, 1) * max(plan.sp, 1)
  heads = cfg.cache_kv_heads
  if plan.tp > 1 and heads > 1 and heads % plan.tp == 0:
    div *= plan.tp
  n_pre = min(int(getattr(cfg, "first_k_dense", 0) or 0), L) if plan.pp > 1 else 0
  per_layer = total / max(L, 1)
  pre_bytes = per_layer * n_pre  # full-size on every stage
  return math.ceil(pre_bytes + (total - pre_bytes) / div)


@dataclass(frozen=True)
class PlanReport:
  plan: MeshPlan
  param_bytes: int  # per chip
  cache_bytes: int  # per chip
  hbm_bytes: int | None  # per chip, None = unknown
  headroom: float  # fraction of HBM reserved for activations/XLA scratch

  @property
  def total_bytes(self) -> int:
    return self.param_bytes + self.cache_bytes

  @property
  def fits(self) -> bool | None:
    if self.hbm_bytes is None:
      return None
    return self.total_bytes <= self.hbm_bytes * (1.0 - self.headroom)

  def describe(self) -> str:
    gib = 1024**3
    have = "unknown" if self.hbm_bytes is None else f"{self.hbm_bytes / gib:.1f}"
    return (
      f"plan [{self.plan.describe()}]: {self.param_bytes / gib:.2f} GiB weights + "
      f"{self.cache_bytes / gib:.2f} GiB cache per chip vs {have} GiB HBM "
      f"(headroom {self.headroom:.0%})"
    )


# Activations + XLA scratch + fragmentation reserve. Decode activations are
# tiny but prefill at long S and compile-time scratch are not; 15% matches
# what the round-2 8B-int8 run (~8.5 GiB model on a 16 GiB v5e) left free.
DEFAULT_HEADROOM = 0.15


def plan_report(cfg: ModelConfig, plan: MeshPlan, batch: int, max_seq: int, hbm_bytes: int | None, quant: str | None = None, headroom: float = DEFAULT_HEADROOM, shard: Shard | None = None) -> PlanReport:
  return PlanReport(
    plan=plan,
    param_bytes=param_bytes_per_chip(cfg, plan, shard=shard, quant=quant),
    cache_bytes=kv_cache_bytes_per_chip(cfg, plan, batch, max_seq, n_layers=shard.n_shard_layers if shard else None),
    hbm_bytes=hbm_bytes,
    headroom=headroom,
  )


class HBMBudgetError(RuntimeError):
  """A serving plan cannot fit; carries the report and any fitting fallback."""

  def __init__(self, report: PlanReport, fallback: PlanReport | None):
    self.report = report
    self.fallback = fallback
    hint = f" A fitting plan exists: {fallback.describe()}." if fallback else " No plan over the available chips fits this model."
    super().__init__(f"model does not fit: {report.describe()}.{hint}")


def candidate_plans(cfg: ModelConfig, n_devices: int) -> list[MeshPlan]:
  """Serving plans to consider, cheapest-communication first: pure tp, then
  pp (deep pipelines divide BOTH weights and cache), then pp x tp."""
  plans: list[MeshPlan] = []

  def add(p: MeshPlan):
    if p.n_devices <= n_devices and p not in plans:
      plans.append(p)

  if cfg.n_experts:
    ep = pow2_degree(n_devices, cfg.n_experts, divides=cfg.n_experts)
    add(MeshPlan(ep=ep, tp=pow2_degree(n_devices // ep, cfg.n_heads)))
  add(MeshPlan(tp=pow2_degree(n_devices, cfg.n_heads)))
  pp = 2
  while pp <= n_devices:
    if cfg.n_layers % pp == 0:
      add(MeshPlan(pp=pp))
      tp = pow2_degree(n_devices // pp, cfg.n_heads)
      if tp > 1:
        add(MeshPlan(pp=pp, tp=tp))
    pp *= 2
  return plans


def choose_serving_plan(cfg: ModelConfig, n_devices: int, hbm_bytes: int, batch: int, max_seq: int, quant: str | None = None, headroom: float = DEFAULT_HEADROOM, shard: Shard | None = None) -> PlanReport:
  """First candidate plan that fits, or raise HBMBudgetError with the best
  (smallest-footprint) attempt for the error message."""
  best: PlanReport | None = None
  for plan in candidate_plans(cfg, n_devices):
    report = plan_report(cfg, plan, batch, max_seq, hbm_bytes, quant=quant, headroom=headroom, shard=shard)
    if report.fits:
      return report
    if best is None or report.total_bytes < best.total_bytes:
      best = report
  raise HBMBudgetError(best, None)


def check_plan(cfg: ModelConfig, plan: MeshPlan, n_devices: int, hbm_bytes: int | None, batch: int, max_seq: int, quant: str | None = None, shard: Shard | None = None) -> PlanReport:
  """Validate an explicitly requested plan; on refusal, suggest a fitting
  alternative over the same chips (the error the engine raises instead of
  letting XLA OOM mid-compile)."""
  report = plan_report(cfg, plan, batch, max_seq, hbm_bytes, quant=quant, shard=shard)
  if report.fits is False:
    fallback = None
    try:
      fallback = choose_serving_plan(cfg, n_devices, hbm_bytes, batch, max_seq, quant=quant, shard=shard)
    except HBMBudgetError:
      pass
    raise HBMBudgetError(report, fallback)
  return report


def device_hbm_bytes() -> int | None:
  """Per-chip HBM of the local accelerator as the runtime reports it; None
  on CPU (no HBM to budget). An accelerator that reports no ``bytes_limit``
  is an error — the check is switched off with ``XOT_TPU_HBM_CHECK=0``, never
  by a failed probe."""
  dev = device_memory()[0]
  if dev["platform"] == "cpu":
    return None
  if not dev["bytes_limit"]:
    raise RuntimeError(f"{dev['kind']!r} reports no bytes_limit in memory_stats(); set XOT_TPU_HBM_CHECK=0 to serve without the HBM budget check")
  return int(dev["bytes_limit"])


class RingBudgetError(RuntimeError):
  """A multi-node ring partition cannot hold the model — raised by the Node
  BEFORE any download or weight load begins (orchestration/node.py
  ``_ring_budget_problems``), instead of the reference's OOM mid-prefill."""


def ring_partition_fits(cfg: ModelConfig, shards: list[Shard], memories_bytes: list[int], quant: str | None = None, headroom: float = DEFAULT_HEADROOM) -> list[str]:
  """Validate a ring partition (topology/partitioning map_partitions_to_shards
  output) against each node's reported memory: returns human-readable
  problems (empty = fits). Wired into the Node's prompt path (node.py): the
  head validates the current partition map against every peer's probed
  memory before the download/load begins rather than as an OOM
  mid-prefill."""
  def fmt(n: int) -> str:
    return f"{n / 1024**3:.2f} GiB" if n >= 1024**3 else f"{n / 1024**2:.1f} MiB"

  problems = []
  for shard, mem in zip(shards, memories_bytes):
    need = model_bytes(cfg, shard, quant)
    if need > mem * (1.0 - headroom):
      problems.append(f"node span [{shard.start_layer}-{shard.end_layer}] needs {fmt(need)} weights but has {fmt(mem)}")
  return problems
