"""Grouped-query attention with position-index masking.

The reference materializes boolean causal masks and ships them between peers
(``llm_utils.py:497-503`` — O(seq²) per hop). Here masks are *computed* from
absolute position indices inside the op: a query at absolute position p
attends exactly the KV slots whose slot-index ≤ p. Because the KV cache is
slot-indexed by absolute position, stale prefill padding (slots > p) is
masked out for free and gets overwritten as decode advances.

This is the XLA-fusable dense path; ``ops/pallas_attention.py`` provides the
flash-attention Pallas kernel for long-sequence prefill with the same
signature, and ``parallel/ring_attention.py`` builds the sequence-parallel
ring on top of the same blockwise math.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.programs import component_scope

NEG_INF = -1e30


def kv_scale_to_scores(scale_leaf: jnp.ndarray) -> jnp.ndarray:
  """Cache scale leaf [B, Skv, Hkv, 1] → broadcastable over scores
  [B, Hkv, group, Sq, Skv]. Shared with the sp stat-merge path so both stay
  bit-consistent."""
  return jnp.transpose(scale_leaf[..., 0], (0, 2, 1))[:, :, None, None, :]


@component_scope("xot.attn")
def gqa_attention(
  q: jnp.ndarray,  # [B, Sq, Hq, hd]
  k: jnp.ndarray,  # [B, Skv, Hkv, hd] (int8 codes when k_scale is given)
  v: jnp.ndarray,  # [B, Skv, Hkv, hd]
  q_positions: jnp.ndarray,  # [B, Sq] absolute positions of queries
  kv_positions: jnp.ndarray,  # [Skv] absolute positions (slot indices) of keys
  scale: float | None = None,
  logit_softcap: float = 0.0,
  sliding_window=None,  # int or traced scalar; None ⇒ global attention
  k_scale: jnp.ndarray | None = None,  # [B, Skv, Hkv, 1] int8-KV scales
  v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
  """Returns [B, Sq, Hq, hd_v]; softmax in fp32; output in q.dtype.

  ``v``'s head dim may differ from q/k's (MLA: qk 192, v 128); the default
  scale is 1/sqrt(qk head dim) (gemma2 overrides via query_pre_attn_scalar).
  ``logit_softcap`` applies gemma2's ``cap·tanh(s/cap)`` before masking;
  ``sliding_window`` restricts each query to the last W kv positions.

  With ``k_scale``/``v_scale`` (models/quantize.py quantize_kv) k/v are int8
  codes; the einsum operand stays the raw codes (the int8→f32 convert fuses
  into the contraction, so HBM reads 1 byte/element — the long-context
  decode win) and the per-(token, head) scales apply outside it: k's on the
  scores BEFORE softcap/mask (the true score is code·scale), v's folded
  into the probs.
  """
  B, Sq, Hq, hd = q.shape
  Hkv = k.shape[2]
  hd_v = v.shape[3]
  group = Hq // Hkv
  if scale is None:
    scale = 1.0 / float(hd) ** 0.5

  qg = q.reshape(B, Sq, Hkv, group, hd)
  # scores: [B, Hkv, group, Sq, Skv]
  scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32)) * scale
  if k_scale is not None:
    scores = scores * kv_scale_to_scores(k_scale)
  scores = cap_and_mask_scores(scores, q_positions, kv_positions, logit_softcap, sliding_window)
  probs = jax.nn.softmax(scores, axis=-1)
  if v_scale is not None:
    probs = probs * kv_scale_to_scores(v_scale)
  out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
  return out.reshape(B, Sq, Hq, hd_v).astype(q.dtype)


def cap_and_mask_scores(scores, q_positions, kv_positions, logit_softcap: float = 0.0, sliding_window=None):
  """Shared softcap + causal/window masking for [B,Hkv,g,Sq,Skv] scores —
  ONE implementation so the sp-serving partial-stat path (which merges
  online-softmax stats across ranks) stays bit-consistent with this one.
  Softcap applies BEFORE masking (HF gemma2 order)."""
  if logit_softcap:
    scores = logit_softcap * jnp.tanh(scores / logit_softcap)
  kv = kv_positions[None, None, None, None, :]  # [1,1,1,1,Skv]
  qp = q_positions[:, None, None, :, None]  # [B,1,1,Sq,1]
  mask = kv <= qp
  if sliding_window is not None:
    mask = mask & (kv > qp - sliding_window)
  return jnp.where(mask, scores, NEG_INF)


def mla_absorb(q_nope: jnp.ndarray, w_kv_b: jnp.ndarray, v_dim: int):
  """The kv_b up-projection [rank, H*(nope+v)] folded into the query side: (q_abs = q_nope · W_k [B, Sq, H, rank], W_v
  [rank, H, v]), both float32 — the two XLA ends of absorbed MLA, whichever core attends between them (the gathered
  window below, or the latent body of the paged decode kernel: ops/paged.py ``paged_latent_decode_attention``)."""
  H, nope = q_nope.shape[-2:]
  W = w_kv_b.reshape(-1, H, nope + v_dim)
  w_k, w_v = W[..., :nope].astype(jnp.float32), W[..., nope:].astype(jnp.float32)  # [rank, H, nope], [rank, H, v]
  return jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), w_k), w_v


@component_scope("xot.attn")
def mla_absorbed_attention(
  q_nope: jnp.ndarray,  # [B, Sq, H, nope]
  q_pe: jnp.ndarray,  # [B, Sq, H, rope] (rope already applied)
  ckv: jnp.ndarray,  # [B, Skv, rank] cached KV latent (post kv_a_norm)
  kpe: jnp.ndarray,  # [B, Skv, rope] cached rope channel (rope already applied)
  w_kv_b: jnp.ndarray,  # [rank, H*(nope+v)] up-projection
  q_positions: jnp.ndarray,  # [B, Sq]
  kv_positions: jnp.ndarray,  # [Skv]
  v_dim: int,
  q_block: int = 0,
) -> jnp.ndarray:
  """MLA attention against the *latent* cache (weight absorption).

  Instead of materializing per-head K/V (H·(qk+v) floats per cached token),
  the cache holds only the shared latent + rope channel (rank+rope floats —
  ~9× smaller for deepseek-v2-lite, ~71× for v3 geometry), and the kv_b
  up-projection is folded into the query/output sides:

    score_h(t) = (q_nope_h · W_k_hᵀ) · ckv(t) + q_pe_h · kpe(t)
    out_h      = (Σ_t p_t ckv(t)) · W_v_h

  Decode is HBM-bound on the cache read, so shrinking cached bytes is the
  long-context lever (SURVEY.md §5.7 is greenfield in the reference).
  Returns [B, Sq, H, v_dim] in q_nope.dtype.

  ``q_block`` > 0 takes the queries ``q_block`` positions at a time (each
  block's softmax is whole: it is exact), so the float32 scores that exist
  at once are [B, H, q_block, Skv] and not [B, H, Sq, Skv] — 4 GB twice over
  for a prefill group of 8 rows x 1024 queries x 32 heads against a window of
  4096, which a pool with state leaves beside 10 GB of weights has no room for.
  """
  B, Sq, H, nope = q_nope.shape
  if q_block and Sq > q_block:
    pad = -Sq % q_block
    blocks = lambda t: jnp.moveaxis(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)).reshape(B, -1, q_block, *t.shape[2:]), 1, 0)  # noqa: E731
    out = jax.lax.map(lambda t: mla_absorbed_attention(t[0], t[1], ckv, kpe, w_kv_b, t[2], kv_positions, v_dim), (blocks(q_nope), blocks(q_pe), blocks(q_positions)))
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq + pad, H, v_dim)[:, :Sq]
  rope = q_pe.shape[-1]
  q_abs, w_v = mla_absorb(q_nope, w_kv_b, v_dim)
  scale = 1.0 / jnp.sqrt(jnp.asarray(nope + rope, dtype=jnp.float32))

  scores = jnp.einsum("bshr,btr->bhst", q_abs, ckv.astype(jnp.float32))
  scores = scores + jnp.einsum("bshp,btp->bhst", q_pe.astype(jnp.float32), kpe.astype(jnp.float32))
  scores = scores * scale
  mask = kv_positions[None, None, None, :] <= q_positions[:, None, :, None]  # [B,1,Sq,Skv]
  scores = jnp.where(mask, scores, NEG_INF)
  probs = jax.nn.softmax(scores, axis=-1)
  ctx = jnp.einsum("bhst,btr->bshr", probs, ckv.astype(jnp.float32))  # [B,Sq,H,rank]
  out = jnp.einsum("bshr,rhv->bshv", ctx, w_v)
  return out.astype(q_nope.dtype)
