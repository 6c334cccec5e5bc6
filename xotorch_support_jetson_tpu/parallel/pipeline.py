"""In-slice pipeline parallelism: GPipe-schedule stages over the ``pp`` mesh
axis with ``shard_map`` + ``lax.ppermute``.

This is the TPU-native delivery of the reference's one parallelism strategy
(SURVEY.md §2.11: layer-range ring pipeline over gRPC peers,
``node.py:424-443``), redesigned for ICI:

- activations move device→device as on-chip ``ppermute``s, never touching
  host memory (vs per-hop protobuf serialization);
- **microbatching** overlaps stages (the reference runs one request step at a
  time through the whole ring — its pipeline never overlaps);
- the schedule is a fixed-length SPMD loop (M + P - 1 ticks), so the whole
  pipeline jits into one XLA program;
- the shard_map is *manual only over pp* (``auto`` over dp/sp/tp), so data
  parallelism and megatron tensor sharding compose with the pipeline via
  GSPMD inside each stage.

The pipeline wraps only the layer stack; embedding, LM head and loss run
under plain GSPMD around it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.decoder import _layer_step
from ..ops.rope import rope_inv_freq


def stack_stage_params(layer_params: dict, n_stages: int) -> dict:
  """Reshape stacked layer leaves [L, ...] → [P, L/P, ...] for pp sharding."""
  out = {}
  for key, leaf in layer_params.items():
    L = leaf.shape[0]
    if L % n_stages:
      raise ValueError(f"n_layers={L} not divisible by n_stages={n_stages}")
    out[key] = leaf.reshape(n_stages, L // n_stages, *leaf.shape[1:])
  return out


def unstack_stage_params(stage_params: dict) -> dict:
  return {k: v.reshape(v.shape[0] * v.shape[1], *v.shape[2:]) for k, v in stage_params.items()}


def run_layer_stack(stage_layers: dict, h: jnp.ndarray, positions: jnp.ndarray, inv_freq, cfg: ModelConfig, attn_fn=None, remat: bool = False, with_aux: bool = False):
  """Run a stack of layers (cache-less) via lax.scan; h [B,S,D].

  ``remat=True`` wraps each layer in ``jax.checkpoint`` (rematerialize
  activations in backward — HBM for FLOPs, the standard TPU training trade).
  ``with_aux=True`` also returns the summed MoE load-balancing loss.
  """

  def one_layer(carry, lp):
    h, aux = carry
    out, _, a = _layer_step(h, lp, None, positions, positions[0], inv_freq, cfg, False, attn_fn)
    return (out, aux + a), None

  body = jax.checkpoint(one_layer) if remat else one_layer
  (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), stage_layers)
  return (h, aux) if with_aux else h


def make_pipeline_layers_fn(mesh: Mesh, cfg: ModelConfig, n_stages: int, n_micro: int, ring_sp: bool = False, remat: bool = False):
  """Build fn(stage_params, h [B,S,D], positions [B,S]) -> final hidden [B,S,D].

  ``stage_params`` leaves are [P, L/P, ...] sharded over "pp". h is the
  embedded input (dp-sharded batch is fine — dp/tp are auto axes). The global
  batch is split into ``n_micro`` microbatches inside. With ``ring_sp`` the
  sequence dim is additionally manual over "sp" and every layer's attention
  runs as ring attention around the sp axis (pp×sp compose: K/V blocks rotate
  on sp while activations ppermute on pp).
  """
  from .ring_attention import ring_attention

  seq = "sp" if ring_sp else None
  attn_fn = (lambda q, k, v, qp, kp, **opts: ring_attention(q, k, v, qp, kp, axis_name="sp", **opts)) if ring_sp else None

  if n_stages == 1 and not ring_sp:
    # No manual axes needed: plain GSPMD layer stack (XLA's SPMD partitioner
    # rejects manual subgroups over size-1 axes in some programs).
    def apply_plain(stage_params, h, positions):
      layers = {k: v[0] for k, v in stage_params.items()}
      return run_layer_stack(layers, h, positions, rope_inv_freq(cfg), cfg, remat=remat, with_aux=True)

    return apply_plain

  manual = {a for a, used in (("pp", n_stages > 1), ("sp", ring_sp)) if used}
  pp_spec = "pp" if n_stages > 1 else None

  @partial(
    jax.shard_map,
    mesh=mesh,
    in_specs=(P(pp_spec), P(None, seq, None), P(None, seq)),
    out_specs=(P(pp_spec, None, seq, None), P()),
    axis_names=manual,  # manual over pp (and sp if ring); dp/tp stay GSPMD-auto
    check_vma=False,
  )
  def pp_fn(stage_params, h, positions):
    stage_layers = {k: v[0] for k, v in stage_params.items()}  # [1,L/P,...] → [L/P,...]
    stage = jax.lax.axis_index("pp") if n_stages > 1 else jnp.int32(0)
    B, S, D = h.shape
    mb = B // n_micro
    inv_freq = rope_inv_freq(cfg)
    x_mb = h.reshape(n_micro, mb, S, D)
    pos_mb = positions[:mb]

    outputs = jnp.zeros((n_micro, mb, S, D), h.dtype)
    carry_out = jnp.zeros((mb, S, D), h.dtype)
    aux_total = jnp.float32(0.0)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    for t in range(n_micro + n_stages - 1):
      recv = jax.lax.ppermute(carry_out, "pp", perm) if n_stages > 1 else carry_out
      m = t - stage
      m_clamped = jnp.clip(m, 0, n_micro - 1)
      active = jnp.logical_and(m >= 0, m < n_micro)
      my_in = jnp.where(stage == 0, jax.lax.dynamic_index_in_dim(x_mb, m_clamped, axis=0, keepdims=False), recv)
      out, aux = run_layer_stack(stage_layers, my_in, pos_mb, inv_freq, cfg, attn_fn=attn_fn, remat=remat, with_aux=True)
      aux_total = aux_total + jnp.where(active, aux, 0.0)
      out = jnp.where(active, out, carry_out)
      prev_slice = jax.lax.dynamic_index_in_dim(outputs, m_clamped, axis=0, keepdims=False)
      collect = jnp.logical_and(stage == n_stages - 1, active)
      outputs = jax.lax.dynamic_update_index_in_dim(outputs, jnp.where(collect, out, prev_slice), m_clamped, axis=0)
      carry_out = out

    aux_total = aux_total / n_micro  # mean over microbatches
    if n_stages > 1:
      aux_total = jax.lax.psum(aux_total, "pp")  # sum each stage's layer contributions
    if ring_sp:
      aux_total = jax.lax.pmean(aux_total, "sp")  # mean over sequence shards
    return outputs.reshape(B, S, D)[None], aux_total  # [1,B,S,D] per stage → [P,B,S,D] global

  def apply(stage_params, h, positions):
    if h.shape[0] % n_micro:
      raise ValueError(f"batch {h.shape[0]} not divisible by n_micro={n_micro}")
    stacked, aux = pp_fn(stage_params, h, positions)
    return stacked[-1], aux  # only the last stage's slot holds real outputs

  return apply
