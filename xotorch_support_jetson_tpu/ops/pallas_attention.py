"""Pallas flash-attention (prefill) kernel for TPU.

Blockwise online-softmax attention: K/V stream through VMEM in BLOCK_K
chunks while each grid step owns one (batch, q-head, q-block) tile — O(S)
memory instead of materializing [Sq, Skv] scores in HBM, and the QK^T /
PV matmuls stay on the MXU back-to-back.

Causality is positional, consistent with ops/attention.py: query row i at
absolute position ``q_offset + i`` attends KV slot j iff ``j <= pos``. GQA is
handled in the index map (q head h reads kv head ``h // group``).

Used by the decoder for prefill when shapes allow (models/decoder.py);
``ops.attention.gqa_attention`` is the XLA fallback everywhere else
(decode steps, CPU tests, odd shapes).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..utils.programs import component_scope, tracked_jit

NEG_INF = -1e30
BLOCK_Q = 128
BLOCK_K = 128


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, *scale_refs_and_out, block_k: int, scale: float, quantized: bool, window: int = 0):
  """Grid: (B, Hq, Sq/BQ, Skv/BK) — the KV axis is GRID-tiled (innermost,
  sequential) with the online-softmax state carried in VMEM scratch, so
  VMEM holds one [BK, hd] K/V tile at a time regardless of Skv. (The first
  design kept the whole [Skv, hd] row resident and fori_loop'ed over it —
  at a 32K cache that is ~16.2 MB of operand stack, over the 16 MB scoped
  VMEM limit: long-context chunked prefill crashed at COMPILE time.)

  ``quantized``: k/v refs hold int8 codes and two extra [BK, 1] f32 scale
  refs precede the outputs — dequantization is per-(token, head) scales
  applied to scores/probs in-register (cf. ops/attention.py gqa_attention),
  so the HBM stream stays 1 byte/element and the quantized prefill never
  materializes a dequantized cache.

  ``window`` (static; 0: none): a query at t sees the keys in (t - window, t]
  (ops/attention.py ``cap_and_mask_scores``'s rule): blocks wholly before the
  tile's first query's window are skipped like those past its causal horizon,
  and the edge blocks masked. Every ``if window`` is Python's: 0 traces the
  kernel as it was."""
  import jax.experimental.pallas as pl

  if quantized:
    ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = scale_refs_and_out
  else:
    o_ref, m_ref, l_ref, acc_ref = scale_refs_and_out
  b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  q = q_ref[0, 0].astype(jnp.float32)  # [BQ, hd]
  bq = q.shape[0]
  # Per-row dynamic offset (scalar-prefetched): query row i is at absolute
  # position off[b] + i. Prefix-cached prefills start mid-sequence
  # (models/decoder.py prefill_into_pages), so the offset cannot be static 0.
  q_pos = off_ref[b] + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)  # [BQ,1]
  start = kb * block_k

  # Blocks entirely past this query tile's causal horizon contribute only
  # NEG_INF columns: skip their COMPUTE. Their DMA still streams: there is
  # no index-map clamp here, so the kernel needs no scalar-prefetch grid. The
  # compute skip alone keeps the MXU work O(context).
  needed = start <= off_ref[b] + (qi + 1) * bq - 1
  if window:  # ... and the block's last key is inside the window of the tile's first query
    needed = jnp.logical_and(needed, start + block_k - 1 > off_ref[b] + qi * bq - window)

  @pl.when(needed)
  def _block():
    k_blk = k_ref[0, 0].astype(jnp.float32)  # [BK, hd]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    scores = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale  # [BQ, BK]
    if quantized:
      # codes·scale = true k: the per-token scale multiplies each score
      # COLUMN ([BK,1] transposed to a [1,BK] row broadcast).
      scores = scores * jnp.transpose(ks_ref[0, 0], (1, 0))
    kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)  # [1,BK]
    mask = kv_pos <= q_pos
    if window:
      mask = jnp.logical_and(mask, kv_pos > q_pos - window)
    scores = jnp.where(mask, scores, NEG_INF)
    m = m_ref[...]
    blk_m = jnp.max(scores, axis=1, keepdims=True)  # [BQ,1]
    new_m = jnp.maximum(m, blk_m)
    p = jnp.exp(scores - new_m)
    p = jnp.where(new_m <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m - new_m)
    m_ref[...] = new_m
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if quantized:
      p = p * jnp.transpose(vs_ref[0, 0], (1, 0))  # v's scale folds into probs (after the l update)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  @pl.when(kb == pl.num_programs(3) - 1)
  def _finish():
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


# A layer with a window computes the K blocks its window touches and skips the rest, so its block is kept near the
# window's size: at the 2048 of a layer without one, a window of 512 would compute what full causal attention does.
WINDOW_BLOCK_K = 512


@functools.partial(tracked_jit, "ops.flash_prefill", static_argnames=("interpret", "window"))
@component_scope("xot.attn")
def flash_attention_prefill(q, k, v, q_offset=0, k_scale=None, v_scale=None, interpret: bool = False, window: int = 0):
  """q [B,Sq,Hq,hd], k/v [B,Skv,Hkv,hd] → [B,Sq,Hq,hd].

  ``q_offset`` — int or [B] int32 (TRACED): absolute position of each row's
  first query. Requires Sq % BLOCK_Q == 0 and Skv % BLOCK_K == 0 (callers
  pad; the positional mask keeps padded KV slots (slot index > pos) inert as
  long as they hold finite values). With ``k_scale``/``v_scale``
  [B,Skv,Hkv,1] (int8 KV — models/quantize.py quantize_kv), k/v are int8
  codes dequantized in-register per block. ``window`` (static; 0: none)
  restricts each query to its last ``window`` positions, whole-prompt and
  chunked (``q_offset``) alike.
  """
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  if (k_scale is None) != (v_scale is None):
    # A half-specified quant call would silently ignore v_scale (or treat
    # int8 v codes as values): fail loudly instead (ADVICE r5).
    raise ValueError("flash_attention_prefill: k_scale and v_scale must be passed together (int8-KV codes carry both scale leaves)")
  B, Sq, Hq, hd = q.shape
  Skv, Hkv = k.shape[1], k.shape[2]
  group = Hq // Hkv
  scale = float(1.0 / (hd**0.5))
  offsets = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
  quantized = k_scale is not None

  # Layout: [B, H, S, hd] so the S×hd tile is contiguous per (b, h).
  qt = jnp.moveaxis(q, 2, 1)  # [B, Hq, Sq, hd]
  kt = jnp.moveaxis(k, 2, 1)
  vt = jnp.moveaxis(v, 2, 1)

  # KV grid-block size: as LARGE as divides Skv (≤2048). Grid-step overhead
  # on this platform is ~25 µs; at BLOCK_K=128 a 32K cache is 512K steps
  # (~13 s per 512-token chunk, measured) — at 2048 it is 32× fewer. VMEM
  # per step stays ≤ ~1 MB ([2048, hd] K+V tiles + the [BQ, 2048] scores).
  block_k = next((bk for bk in (2048, 1024, 512, 256, 128) if Skv % bk == 0 and (not window or bk <= max(WINDOW_BLOCK_K, BLOCK_K))), BLOCK_K)
  grid = (B, Hq, Sq // BLOCK_Q, Skv // block_k)
  kernel = functools.partial(_flash_kernel, block_k=block_k, scale=scale, quantized=quantized, **({"window": int(window)} if window else {}))
  in_specs = [
    pl.BlockSpec(memory_space=pltpu.SMEM),
    pl.BlockSpec((1, 1, BLOCK_Q, hd), lambda b, h, i, kb: (b, h, i, 0)),
    pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, kb: (b, h // group, kb, 0)),
    pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, kb: (b, h // group, kb, 0)),
  ]
  operands = [offsets, qt, kt, vt]
  if quantized:
    in_specs += [pl.BlockSpec((1, 1, block_k, 1), lambda b, h, i, kb: (b, h // group, kb, 0))] * 2
    operands += [jnp.moveaxis(k_scale, 2, 1), jnp.moveaxis(v_scale, 2, 1)]
  out = pl.pallas_call(
    kernel,
    out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
    grid=grid,
    in_specs=in_specs,
    out_specs=pl.BlockSpec((1, 1, BLOCK_Q, hd), lambda b, h, i, kb: (b, h, i, 0)),
    scratch_shapes=[
      pltpu.VMEM((BLOCK_Q, 1), jnp.float32),  # running max
      pltpu.VMEM((BLOCK_Q, 1), jnp.float32),  # running denom
      pltpu.VMEM((BLOCK_Q, hd), jnp.float32),  # accumulator
    ],
    interpret=interpret,
  )(*operands)
  return jnp.moveaxis(out, 1, 2)  # [B, Sq, Hq, hd]


def flash_supported(q_shape, kv_len: int, platform: str | None = None) -> bool:
  if os.getenv("XOT_TPU_NO_FLASH"):
    return False
  platform = platform or jax.default_backend()
  B, Sq, Hq, hd = q_shape
  return platform == "tpu" and Sq % BLOCK_Q == 0 and kv_len % BLOCK_K == 0 and hd in (64, 128, 256)


# ------------------------------------------------------------- flash decode
#
# Single-token decode attention against a LONG cache. XLA's einsum path
# reads the [S, Hkv, hd] cache at ~12 GB/s effective on v5e at 32K (measured
# — transposes + f32 staging dominate); this kernel streams the cache in
# [BLOCK_D, Hkv·hd] tiles — contiguous full-lane rows in the cache's native
# layout, no transpose, no staging — carrying online-softmax state across
# blocks. All kv heads ride in one tile (the head axis is the minor-most
# non-lane dim), so the DMA is dense even though each head's scores are
# computed separately on the MXU.

BLOCK_D = 1024


def _flash_decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, qb_ref, m_ref, l_ref, acc_ref, *, block: int, n_kv_heads: int, scale: float):
  import jax.experimental.pallas as pl

  b, i = pl.program_id(0), pl.program_id(1)
  hd = q_ref.shape[-1]
  Hq = q_ref.shape[1]
  group = Hq // n_kv_heads
  D = n_kv_heads * hd

  @pl.when(i == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # Block-diagonal queries [Hq, Hkv·hd]: row r holds q_r in its kv head's
    # lane range, zeros elsewhere — so ONE [Hq,D]@[D,blk] dot against the
    # flat tile scores every head (zeros kill the cross-head terms). Built
    # once per row; each tile then costs two large MXU dots, no per-head
    # lane slicing (which relayouts and was 5x slower than XLA).
    q_rep = jnp.concatenate([q_ref[0]] * n_kv_heads, axis=1)  # [Hq, D]
    col_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, D), 1) // hd
    row_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, D), 0) // group
    qb_ref[...] = jnp.where(col_head == row_head, q_rep, 0).astype(qb_ref.dtype)

  q_pos = pos_ref[b]
  start = i * block

  @pl.when(start <= q_pos)
  def _block():
    kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)  # [1, blk]
    mask = kv_pos <= q_pos
    # Keep MXU operands in the cache dtype (bf16×bf16→f32 is native; an
    # astype here would stage f32 tile copies through the VPU every block).
    s = jax.lax.dot_general(qb_ref[...], k_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale  # [Hq, blk]
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]  # [Hq, 1]
    blk_m = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, blk_m)
    p = jnp.exp(s - m_new)
    p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    # acc rows accumulate p_r @ v_flat [Hq, D]; only the own-head lane range
    # is meaningful and the finalize step extracts it.
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  @pl.when(i == pl.num_programs(1) - 1)
  def _finish():
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    acc = acc_ref[...] / l  # [Hq, D]
    col_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, D), 1) // hd
    row_head = jax.lax.broadcasted_iota(jnp.int32, (Hq, D), 0) // group
    own = jnp.where(col_head == row_head, acc, 0.0)
    # Fold the hd-strided own-head lanes with one [Hq,D]@[D,hd] dot against a
    # 0/1 selector (no reshape/slicing — Mosaic rejects those shape casts).
    sel_r = jax.lax.broadcasted_iota(jnp.int32, (D, hd), 0) % hd
    sel_c = jax.lax.broadcasted_iota(jnp.int32, (D, hd), 1)
    fold = (sel_r == sel_c).astype(jnp.float32)
    o_ref[0] = jax.lax.dot_general(own, fold, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(tracked_jit, "ops.flash_decode", static_argnames=("interpret",))
@component_scope("xot.attn")
def flash_decode_attention(q, k, v, q_positions, interpret: bool = False):
  """One-token decode attention: q [B,1,Hq,hd], k/v [B,Skv,Hkv,hd] (slot-
  indexed cache, native layout), q_positions [B,1] → [B,1,Hq,hd].

  Blocks past a row's position are clamped in the index map (repeat DMA =
  no-op) and skipped in compute, so cost scales with the row's actual
  context, not the cache allocation."""
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  B, Sq, Hq, hd = q.shape
  Skv, Hkv = k.shape[1], k.shape[2]
  block = min(BLOCK_D, Skv)
  n_blocks = Skv // block
  scale = float(1.0 / (hd**0.5))
  pos = q_positions[:, 0].astype(jnp.int32)

  kf = k.reshape(B, Skv, Hkv * hd)
  vf = v.reshape(B, Skv, Hkv * hd)
  qf = q[:, 0]  # [B, Hq, hd]

  def kv_index(b, i, pos_ref):
    last = jnp.maximum(pos_ref[b], 0) // block  # last block with valid slots
    return (b, jnp.minimum(i, last), 0)

  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=1,
    grid=(B, n_blocks),
    in_specs=[
      pl.BlockSpec((1, Hq, hd), lambda b, i, pos_ref: (b, 0, 0)),
      pl.BlockSpec((1, block, Hkv * hd), kv_index),
      pl.BlockSpec((1, block, Hkv * hd), kv_index),
    ],
    out_specs=pl.BlockSpec((1, Hq, hd), lambda b, i, pos_ref: (b, 0, 0)),
    scratch_shapes=[
      pltpu.VMEM((Hq, Hkv * hd), q.dtype),  # block-diagonal queries (MXU operand dtype)
      pltpu.VMEM((Hq, 1), jnp.float32),  # running max
      pltpu.VMEM((Hq, 1), jnp.float32),  # running denom
      pltpu.VMEM((Hq, Hkv * hd), jnp.float32),  # accumulator
    ],
  )
  out = pl.pallas_call(
    functools.partial(_flash_decode_kernel, block=block, n_kv_heads=Hkv, scale=scale),
    out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
    grid_spec=grid_spec,
    interpret=interpret,
  )(pos, qf, kf, vf)
  return out[:, None]


def flash_decode_supported(q_shape, kv_len: int, platform: str | None = None) -> bool:
  """Use the flash-decode kernel for a decode step (Sq==1) on a long cache.

  OPT-IN (``XOT_TPU_FLASH_DECODE=1``), from ``XOT_TPU_FLASH_DECODE_MIN``
  cached tokens up. Off by default: the one chip figure (stale — measured
  before PR 1, not reproduced) had it behind XLA's einsum at 32K, 1.79 vs
  1.50 ms/layer, with both far below the HBM roofline (ROADMAP.md A2/D2
  decide whether it stays)."""
  from ..utils.helpers import env_flag

  if os.getenv("XOT_TPU_NO_FLASH") or not env_flag("XOT_TPU_FLASH_DECODE"):
    return False
  platform = platform or jax.default_backend()
  B, Sq, Hq, hd = q_shape
  threshold = int(os.getenv("XOT_TPU_FLASH_DECODE_MIN", "8192"))
  return platform == "tpu" and Sq == 1 and kv_len >= threshold and kv_len % min(BLOCK_D, kv_len) == 0 and hd in (64, 128, 256)
