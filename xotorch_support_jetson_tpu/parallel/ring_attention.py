"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has **no** long-context story — sequence length is bounded by
one node's KV cache and the whole mask travels the wire (SURVEY.md §5.7).
Here the sequence is sharded over ``sp``: each device holds Q/K/V blocks of
S/sp positions; K/V blocks rotate around the ring with ``lax.ppermute`` while
each device accumulates blockwise softmax (the log-sum-exp online update of
flash/ring attention). HBM per device is O(S/sp), and the ring transfers ride
ICI concurrently with compute.

Causality is by absolute position (consistent with ops/attention.py): block
masks derive from per-position indices, so any block rotation order is
correct without special-casing the diagonal.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, kv_pos, scale, logit_softcap=0.0, sliding_window=None):
  """One blockwise attention contribution, returning (numerator, row-max, row-sum).

  q [B,Sq,Hkv,G,hd]; k [B,Skv,Hkv,hd]; v [B,Skv,Hkv,hd_v] (MLA's naive
  training K/V has v narrower than q/k). All math fp32. The gemma2 options
  go through the SHARED cap/mask helper (ops/attention.py
  cap_and_mask_scores) — per-score transforms commute with the ring's
  blockwise log-sum-exp merge, and one implementation keeps ring training
  bit-consistent with serving attention.
  """
  from ..ops.attention import cap_and_mask_scores

  scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
  scores = cap_and_mask_scores(scores, q_pos, kv_pos, logit_softcap, sliding_window)
  m = jnp.max(scores, axis=-1)  # [B,H,G,Sq]
  p = jnp.exp(scores - m[..., None])
  # Fully-masked rows: m == NEG_INF → p would be exp(0)=1 garbage; zero them.
  p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
  l = jnp.sum(p, axis=-1)  # [B,H,G,Sq]
  num = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
  return num, m, l


def ring_attention(q, k, v, q_positions, kv_positions, axis_name: str = "sp", scale=None, logit_softcap: float = 0.0, sliding_window=None):
  """Blockwise ring attention; call inside shard_map with sequence sharded
  over ``axis_name``.

  q [B,Sq_local,Hq,hd]; k [B,Skv_local,Hkv,hd]; v [B,Skv_local,Hkv,hd_v]
  (hd_v may differ — MLA); q_positions [B,Sq_local]; kv_positions
  [Skv_local] (absolute positions of the local KV block — 1-D, shared
  across batch; it rotates around the ring with K/V). ``scale`` defaults to
  1/sqrt(hd), matching gqa_attention; the gemma2 options (scale override,
  logit softcap, sliding window — possibly a traced per-layer scalar)
  match ops/attention.py cap_and_mask_scores semantics, so gemma2 trains
  under ring sequence parallelism too. Returns [B,Sq_local,Hq,hd_v].
  """
  axis_size = jax.lax.psum(1, axis_name)
  B, Sq, Hq, hd = q.shape
  Hkv = k.shape[2]
  hd_v = v.shape[3]  # MLA: v head dim differs from q/k's (192 vs 128 on deepseek)
  G = Hq // Hkv
  if scale is None:
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
  qg = q.reshape(B, Sq, Hkv, G, hd).astype(jnp.float32)

  num0 = jnp.zeros((B, Sq, Hkv, G, hd_v), jnp.float32)
  m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
  l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)

  def body(carry, _):
    k_blk, v_blk, kv_pos, num, m, l = carry
    blk_num, blk_m, blk_l = _block_attn(
      qg, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32), q_positions, kv_pos, scale,
      logit_softcap=logit_softcap, sliding_window=sliding_window,
    )
    new_m = jnp.maximum(m, blk_m)
    alpha = jnp.exp(m - new_m)
    beta = jnp.exp(blk_m - new_m)
    # alpha/beta [B,H,G,Sq] → broadcast onto num [B,Sq,H,G,hd]
    a = jnp.moveaxis(alpha, 3, 1)[..., None]
    b = jnp.moveaxis(beta, 3, 1)[..., None]
    num = num * a + blk_num * b
    l = l * alpha + blk_l * beta
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
    v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
    return (k_blk, v_blk, kv_pos, num, new_m, l), None

  (k_f, v_f, kvp_f, num, m, l), _ = jax.lax.scan(body, (k, v, kv_positions, num0, m0, l0), None, length=axis_size)
  l_safe = jnp.where(l == 0.0, 1.0, l)
  out = num / jnp.moveaxis(l_safe, 3, 1)[..., None]
  return out.reshape(B, Sq, Hq, hd_v).astype(q.dtype)


def make_sharded_ring_attention(mesh: Mesh, **attn_opts):
  """shard_map-wrapped ring attention, manual over ``sp`` only (dp/tp auto).
  ``attn_opts`` (scale / logit_softcap / sliding_window) close over the
  wrapper — concrete values, as in tests."""
  spec_q = P(None, "sp", None, None)
  spec_pos = P(None, "sp")

  @partial(
    jax.shard_map,
    mesh=mesh,
    in_specs=(spec_q, spec_q, spec_q, spec_pos, P("sp")),
    out_specs=spec_q,
    axis_names={"sp"},
    check_vma=False,
  )
  def fn(q, k, v, q_positions, kv_positions):
    return ring_attention(q, k, v, q_positions, kv_positions, axis_name="sp", **attn_opts)

  # Partial-manual shard_map composes with the auto axes only under jit.
  return jax.jit(fn)
