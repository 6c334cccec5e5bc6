"""Device mesh construction and parameter sharding.

This is the TPU-native replacement for the reference's cluster-of-peers
execution model (SURVEY.md §7 design-translation table): where the reference
assigns a ``Shard`` per gRPC peer, this framework assigns shardings over a
``jax.sharding.Mesh`` and lets XLA place collectives on ICI.

Axes (any may be 1):
  dp — data parallel (batch dim; gradients all-reduce here)
  pp — pipeline stages (layer ranges; activations ppermute here)
  sp — sequence/context parallel (ring attention shards the sequence here)
  ep — expert parallel (MoE expert axis; dispatch/combine all-to-alls here)
  tp — tensor parallel (attention heads / MLP width; megatron-style)
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("dp", "pp", "sp", "ep", "tp")


@dataclass(frozen=True)
class MeshPlan:
  dp: int = 1
  pp: int = 1
  sp: int = 1
  tp: int = 1
  ep: int = 1

  @property
  def n_devices(self) -> int:
    return self.dp * self.pp * self.sp * self.ep * self.tp

  def describe(self) -> str:
    return f"dp={self.dp} pp={self.pp} sp={self.sp} ep={self.ep} tp={self.tp}"


def manual_axes(mesh: Mesh, *axes: str) -> frozenset:
  """The manual set of a serving shard_map: ``axes`` plus every mesh axis of
  size 1. A size-1 axis has nothing to partition, and with it manual a mesh
  whose remaining axes are all 1 (``--pp 4`` on four chips) is manual
  throughout — the only kind of region a Mosaic kernel can be lowered in.
  Axes of more than one device stay GSPMD-auto (tp under pp/sp)."""
  return frozenset(axes) | {a for a in mesh.axis_names if mesh.shape[a] == 1}


def auto_partitioned(plan: MeshPlan, manual: str | None = None) -> bool:
  """Whether a serving plan leaves an axis of more than one device to GSPMD
  (every axis but ``manual``) — where Mosaic kernels cannot go."""
  return plan.n_devices // (getattr(plan, manual) if manual else 1) > 1


def build_mesh(plan: MeshPlan, devices: list | None = None) -> Mesh:
  devices = devices if devices is not None else jax.devices()
  if len(devices) < plan.n_devices:
    raise ValueError(f"mesh plan {plan.describe()} needs {plan.n_devices} devices, have {len(devices)}")
  devices = devices[: plan.n_devices]
  shape = (plan.dp, plan.pp, plan.sp, plan.ep, plan.tp)
  try:
    from jax.experimental import mesh_utils

    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
  except Exception:  # noqa: BLE001 — heterogeneous/virtual devices: plain reshape
    dev_array = np.asarray(devices).reshape(shape)
  return Mesh(dev_array, AXES)


def auto_plan(n_devices: int | None = None, n_kv_heads: int | None = None) -> MeshPlan:
  """Default single-slice plan: TP up to the KV-head count, rest DP.

  TP is the axis the hardware wants first (head-parallel matmuls stay on the
  MXU and the all-reduce rides ICI); beyond n_kv_heads, extra TP only
  replicates KV, so remaining chips go to DP.
  """
  n = n_devices if n_devices is not None else len(jax.devices())
  tp = pow2_degree(n, n_kv_heads or n)
  dp = n // tp
  return MeshPlan(dp=dp, tp=tp)


def pow2_degree(n_devices: int, *limits: int, divides: int | None = None) -> int:
  """Largest power of 2 ≤ n_devices and every limit, that divides n_devices
  (and ``divides`` when given — e.g. an expert count the axis must split)."""
  d = 1
  while d * 2 <= min(n_devices, *limits) and n_devices % (d * 2) == 0 and (divides is None or divides % (d * 2) == 0):
    d *= 2
  return d


def inference_plan(n_devices: int | None = None, n_heads: int | None = None, n_experts: int = 0) -> MeshPlan:
  """Serving plan for one request stream: pure TP for dense models (batch is
  tiny, so DP would idle; TP caps at the q-head count and GSPMD replicates
  GQA KV heads when tp exceeds them). MoE models split the chips ep × tp —
  expert weights are the bulk of a big-E model's bytes, and sharding them
  over ep divides per-chip HBM where extra TP would only shrink the already
  small per-chip matmuls (the dispatch/combine einsums become GSPMD
  all-to-alls on the ep axis)."""
  n = n_devices if n_devices is not None else len(jax.devices())
  # ep must divide the expert count (the [E, ...] leaves shard over it).
  ep = pow2_degree(n, n_experts, divides=n_experts) if n_experts else 1
  tp = pow2_degree(n // ep, n_heads or n)
  return MeshPlan(ep=ep, tp=tp)


# ---------------------------------------------------------------- shardings


def decoder_param_specs(fsdp: bool = False) -> dict:
  """PartitionSpecs for the decoder pytree (models/decoder.py layout).

  TP follows the megatron pattern: qkv/gate/up column-parallel, o/down
  row-parallel — XLA then places exactly one psum per block on ICI. With
  ``fsdp=True`` the weights are additionally sharded over dp on the
  non-tp dim and all-gathered just-in-time (GSPMD handles the gathers).
  """
  d = "dp" if fsdp else None
  layers = {
    "attn_norm": P(None, None),
    "wq": P(None, d, "tp"),
    "wk": P(None, d, "tp"),
    "wv": P(None, d, "tp"),
    "wo": P(None, "tp", d),
    "bq": P(None, "tp"),
    "bk": P(None, "tp"),
    "bv": P(None, "tp"),
    # MLA (deepseek): the latent projections are shared across heads
    # (replicated); the per-head up-projections (wq_b, wkv_b) are
    # column-parallel like wq, and wo stays row-parallel.
    "wq_a": P(None, d, None),
    "q_a_norm": P(None, None),
    "wq_b": P(None, None, "tp"),
    "wkv_a": P(None, d, None),
    "kv_a_norm": P(None, None),
    "wkv_b": P(None, None, "tp"),
    "wq_a_scale": P(None, None),
    "wq_b_scale": P(None, "tp"),
    "wkv_a_scale": P(None, None),
    "wkv_b_scale": P(None, "tp"),
    "mlp_norm": P(None, None),
    "w_gate": P(None, d, "tp"),
    "w_up": P(None, d, "tp"),
    "w_down": P(None, "tp", d),
    # LoRA adapters: A column stays replicated (rank dim is tiny), B follows
    # the target's column-parallel sharding.
    "wq_lora_a": P(None, d, None),
    "wq_lora_b": P(None, None, "tp"),
    "wv_lora_a": P(None, d, None),
    "wv_lora_b": P(None, None, "tp"),
    "wq_b_lora_a": P(None, None, None),
    "wq_b_lora_b": P(None, None, "tp"),
    "wkv_b_lora_a": P(None, None, None),
    "wkv_b_lora_b": P(None, None, "tp"),
    # int8 per-output-channel scales (models/quantize.py) follow their
    # weight's output-dim sharding.
    "wq_scale": P(None, "tp"),
    "wk_scale": P(None, "tp"),
    "wv_scale": P(None, "tp"),
    "wo_scale": P(None, d),
    "w_gate_scale": P(None, "tp"),
    "w_up_scale": P(None, "tp"),
    "w_down_scale": P(None, d),
  }
  # MoE leaves (models/decoder.py "moe_layers" stack): experts shard over ep,
  # each expert's FFN width additionally over tp; the router and shared
  # expert are small and follow the dense pattern. GSPMD turns the
  # dispatch/combine einsums (ops/moe.py) into all-to-alls on the ep axis.
  moe_layers = {
    **layers,
    "w_router": P(None, None, None),
    "router_bias": P(None, None),
    "w_experts_gate": P(None, "ep", d, "tp"),
    "w_experts_up": P(None, "ep", d, "tp"),
    "w_experts_down": P(None, "ep", "tp", d),
    "w_experts_up_t": P(None, "ep", "tp", d),  # an ungated expert's first matrix, stored [F, D] as its second is
    "w_shared_gate": P(None, d, "tp"),
    "w_shared_up": P(None, d, "tp"),
    "w_shared_down": P(None, "tp", d),
    "w_shared_expert_gate": P(None, None, None),
    "w_experts_gate_scale": P(None, "ep", "tp"),
    "w_experts_up_scale": P(None, "ep", "tp"),
    "w_experts_down_scale": P(None, "ep", d),
    "w_shared_gate_scale": P(None, "tp"),
    "w_shared_up_scale": P(None, "tp"),
    "w_shared_down_scale": P(None, d),
  }
  return {
    "embed": P("tp", d),  # vocab-sharded
    "layers": layers,
    "moe_layers": moe_layers,
    "final_norm": P(None),
    "lm_head": P(d, "tp"),
    "lm_head_scale": P("tp"),
  }


def specs_for_params(params, fsdp: bool = False) -> dict:
  """Match the spec tree to an actual params pytree (drop absent keys)."""
  full = decoder_param_specs(fsdp)
  out = {}
  for key, value in params.items():
    if key in ("layers", "moe_layers"):
      out[key] = {k: full[key].get(k, P()) for k in value}
    elif isinstance(value, dict):  # e.g. vision tower / projector: replicate
      out[key] = jax.tree.map(lambda _: P(), value)
    else:
      out[key] = full.get(key, P())
  return out


def kv_cache_specs() -> dict:
  # [L, B, S, Hkv, hd] — batch over dp, kv heads over tp, sequence over sp.
  return {"k": P(None, "dp", "sp", "tp", None), "v": P(None, "dp", "sp", "tp", None)}


def shard_params(params, mesh: Mesh, fsdp: bool = False):
  """device_put the params pytree with NamedShardings over the mesh."""
  specs = specs_for_params(params, fsdp)
  return jax.tree.map(
    lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
    params,
    specs,
    is_leaf=lambda x: isinstance(x, P),
  )
