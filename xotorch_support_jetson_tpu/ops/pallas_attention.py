"""Pallas flash-attention (prefill) kernel for TPU.

Blockwise online-softmax attention: K/V stream through VMEM in ``block_k``
chunks while each grid step owns one (batch, KV head, q-block) tile — the
group's query heads over a stretch of queries, folded into the rows of ONE
product against the K/V tile — O(S) memory instead of materializing
[Sq, Skv] scores in HBM, and the QK^T / PV matmuls stay on the MXU
back-to-back.

Causality is positional, consistent with ops/attention.py: query row i at
absolute position ``q_offset + i`` attends KV slot j iff ``j <= pos``. GQA is
handled in the block shapes (a query block is its KV head's group of heads).

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

# The tile rule's numbers (``_tile``; chosen from Mosaic's final bundles for a described v5e and a sweep on the chip, PERF.md §6, PR 54).
# Rows of one product (14 × 128): the grid's ~0.35 µs a step and the K/V latch are paid once a tile, so the more the faster — 2048
# fit VMEM and read 3-7 % faster where a group reaches them, but Mosaic's compile time grows faster than the tile (2.4-3.5 s a
# kernel at 2048 rows, 1.1-1.4 at 1024, 0.4-1.8 before PR 54) and a cell with many prefill shapes pays it in setup_s.
TILE_MAX_ROWS = 1792
TILE_MAX_Q = 512  # queries a tile: every tile computes its diagonal block whole, so a longer stretch wastes more of it
TILE_SCORES = 1024 * 1024  # elements of the float32 scores tile [rows, block_k]: with its temporaries it stays under the 16 MiB of scoped VMEM
# A K/V block is 512 keys at most, fewer under a narrower window: a tile computes its edge blocks whole (the diagonal; the
# window's first), so a wider block computes more of what the mask throws away — at 2048 a window of 512 would compute what
# full causal attention does — and at 256 the accumulator's rescale and the step's fixed cost weigh as much as the products.
MAX_BLOCK_K = 512
P_TERMS = 2  # bfloat16 terms p enters the value product as (bfloat16 inputs): 2 carry 16 bits of p's mantissa


def _tile(group: int, hd: int, window: int, sq: int, skv: int) -> tuple[int, int, int]:
  """(heads, block_q, block_k) of a grid step: ``heads`` query heads of one KV head × ``block_q`` queries are the
  rows of one product against a ``[block_k, hd]`` K/V tile. One algorithm that wants different sizes, read from
  the shapes alone: the whole group where its rows fit (else the largest set of heads that divides it; 1 is the
  tile of before PR 54), then the longest stretch of queries up to ``TILE_MAX_Q`` that keeps the rows inside
  ``TILE_MAX_ROWS`` at head size 128 (group 7: 256 queries, 1792 rows; group 8: 128; group 16: two sets of 8 heads; MHA: 512) and half of that
  at 256, whose query, output and accumulator tiles are twice as wide, then the largest power of two that divides
  ``Skv`` and keeps the scores tile inside ``TILE_SCORES``, ``MAX_BLOCK_K`` keys at most and no more than a window."""
  max_rows = TILE_MAX_ROWS * 128 // max(hd, 128)
  heads = max(h for h in range(1, group + 1) if group % h == 0 and (h == 1 or h * BLOCK_Q <= max_rows))
  block_q = max(bq for bq in range(BLOCK_Q, sq + 1, BLOCK_Q) if sq % bq == 0 and (bq == BLOCK_Q or (bq <= TILE_MAX_Q and heads * bq <= max_rows)))
  cap = min(TILE_SCORES // (heads * block_q), MAX_BLOCK_K, window or MAX_BLOCK_K)
  block_k = next((bk for bk in (512, 256) if skv % bk == 0 and bk <= cap), BLOCK_K)
  return heads, block_q, block_k


def _kv_blocks(q0, block_q: int, block_k: int, n_blocks: int, window: int):
  """(first, last) K/V block a tile whose first query stands at ``q0`` needs: up to its last query's causal
  horizon, from its first query's window. The index maps and the kernel body read the same two numbers."""
  last = jnp.minimum((q0 + block_q - 1) // block_k, n_blocks - 1)
  first = jnp.minimum(jnp.maximum(q0 - window + 1, 0) // block_k, last) if window else 0
  return first, last


def _flash_kernel(off_ref, q_ref, k_ref, v_ref, *scale_refs_and_out, block_k: int, n_blocks: int, scale: float, quantized: bool, window: int, p_terms: int):
  """Grid: (B, Hq/heads, Sq/BQ, KV steps) — the KV axis is GRID-tiled (innermost,
  sequential) with the online-softmax state carried in VMEM scratch, so
  VMEM holds one [BK, hd] K/V tile at a time regardless of Skv. (The first
  design kept the whole [Skv, hd] row resident and fori_loop'ed over it —
  at a 32K cache that is ~16.2 MB of operand stack, over the 16 MB scoped
  VMEM limit: long-context chunked prefill crashed at COMPILE time.)

  The query block is ``[heads, BQ, hd]`` — query heads of ONE KV head — folded to ``[heads·BQ, hd]`` rows, so a
  K/V tile is fetched once a group and not once a query head; a row's query is ``row % BQ``. Step ``kb`` of the
  KV axis is the tile's block ``first + kb`` (``_kv_blocks``; the index maps clamp to ``last``, and a repeated
  block is not fetched again): blocks past the causal horizon or before the window cost neither a DMA nor a
  product, only what is left of the grid's steps.

  Products take their operands as stored (ops/paged.py ``dot_dtype``'s rule): with bfloat16 queries the score
  product is bfloat16 × bfloat16 (int8 codes are exact in bfloat16) summed in float32 — every product exact, so
  only the order of the sum differs from a float32 product — and ``p`` enters the value product as ``p_terms``
  bfloat16 terms (``p_hi + p_lo`` carries 16 bits of its mantissa), each one MXU pass against the stored ``v``.
  float32 queries keep float32 products. The running max, denominator and accumulator are float32 either way.
  (On the chip Mosaic multiplies float32 operands at default precision in ONE bfloat16 pass, so the float32 casts of
  before PR 54 bought no precision there: that kernel's ``p`` was a single bfloat16 term on a v5e — PERF.md §6, PR 54.)

  ``quantized``: k/v refs hold int8 codes and two extra [1, BK] f32 scale
  refs precede the outputs — dequantization is per-(token, head) scales
  applied to scores/probs in-register (cf. ops/attention.py gqa_attention),
  so the HBM stream stays 1 byte/element and the quantized prefill never
  materializes a dequantized cache.

  ``window`` (static; 0: none): a query at t sees the keys in (t - window, t]
  (ops/attention.py ``cap_and_mask_scores``'s rule). Every ``if window`` is Python's."""
  import jax.experimental.pallas as pl

  if quantized:
    ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = scale_refs_and_out
  else:
    o_ref, m_ref, l_ref, acc_ref = scale_refs_and_out
  b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
  heads, bq, hd = q_ref.shape[1:]
  rows = heads * bq
  mxu = jnp.bfloat16 if q_ref.dtype == jnp.bfloat16 else jnp.float32
  terms = p_terms if mxu == jnp.bfloat16 else 1
  unit = scale * 1.4426950408889634  # exp(scale·x) = 2^(unit·x): the softmax scale rides in the exponent's one multiply, and the max is taken over unscaled scores

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  # Per-row dynamic offset (scalar-prefetched): query i of the tile is at absolute
  # position off[b] + qi·BQ + i. Prefix-cached prefills start mid-sequence
  # (models/decoder.py prefill_into_pages), so the offset cannot be static 0.
  q0 = off_ref[b] + qi * bq
  first, last = _kv_blocks(q0, bq, block_k, n_blocks, window)
  blk = first + kb

  @pl.when(blk <= last)
  def _block():
    q = q_ref[0].reshape(rows, hd).astype(mxu)
    k_blk = k_ref[0, 0].astype(mxu)  # [BK, hd]
    v_blk = v_ref[0, 0].astype(mxu)
    scores = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # [rows, BK], unscaled
    if quantized:
      scores = scores * ks_ref[0, 0]  # codes·scale = true k: the per-token scale multiplies each score COLUMN ([1, BK])
    q_pos = q0 + jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), bq)  # [rows,1]: a row's query is row % BQ
    kv_pos = blk * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)  # [1,BK]
    mask = kv_pos <= q_pos
    if window:
      mask = jnp.logical_and(mask, kv_pos > q_pos - window)
    # Every block is masked, the interior ones for nothing: a second body without the mask was 4 % fewer bundles a step
    # and half as much again to compile (2.4-3.5 s a kernel against 1.6-2.7; PERF.md §6, PR 54), which a cell pays in setup_s.
    scores = jnp.where(mask, scores, NEG_INF)
    m = m_ref[...]
    new_m = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))  # [rows,1]
    # A row with no key yet (its window starts after this block) has new_m == NEG_INF: 2^(NEG_INF - 0) is the 0 its p must be.
    p = jnp.exp2((scores - jnp.where(new_m <= NEG_INF / 2, 0.0, new_m)) * unit)
    alpha = jnp.exp2((m - new_m) * unit)
    m_ref[...] = new_m
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if quantized:
      p = p * vs_ref[0, 0]  # v's scale folds into probs (after the l update)
    pv = None
    for term in range(terms):
      p_term = p.astype(mxu)
      if term + 1 < terms:
        p = p - p_term.astype(jnp.float32)
      part = jax.lax.dot_general(p_term, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
      pv = part if pv is None else pv + part
    acc_ref[...] = acc_ref[...] * alpha + pv

  @pl.when(kb == pl.num_programs(3) - 1)
  def _finish():
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype).reshape(heads, bq, hd)


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
  window = int(window)

  # Layout: [B, H, S, hd] so the S×hd tile is contiguous per (b, h).
  qt = jnp.moveaxis(q, 2, 1)  # [B, Hq, Sq, hd]
  kt = jnp.moveaxis(k, 2, 1)
  vt = jnp.moveaxis(v, 2, 1)

  heads, block_q, block_k = _tile(group, hd, window, Sq, Skv)
  n_blocks = Skv // block_k
  # The KV axis is as long as a tile can need where that is static: under a window the blocks that
  # block_q + window - 1 keys can touch, walked from the tile's first; else every block (the offsets are traced).
  kv_steps = min(n_blocks, pl.cdiv(block_q + window - 1, block_k) + 1) if window else n_blocks

  def kv_block(b, i, kb, off_ref):  # step kb of a tile's walk, clamped into what it needs: a repeated index is no new DMA
    first, last = _kv_blocks(off_ref[b] + i * block_q, block_q, block_k, n_blocks, window)
    return jnp.minimum(first + kb, last)

  def kv_head(h):  # grid head h is a set of ``heads`` query heads of one KV head
    return h * heads // group

  kv_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, kb, off_ref: (b, kv_head(h), kv_block(b, i, kb, off_ref), 0))
  q_spec = pl.BlockSpec((1, heads, block_q, hd), lambda b, h, i, kb, off_ref: (b, h, i, 0))
  in_specs = [q_spec, kv_spec, kv_spec]
  operands = [offsets, qt, kt, vt]
  if quantized:
    # [B, Skv, Hkv, 1] → [B, Hkv, 1, Skv]: a block is a lane-dense [1, BK] row, the form both products want.
    in_specs += [pl.BlockSpec((1, 1, 1, block_k), lambda b, h, i, kb, off_ref: (b, kv_head(h), 0, kv_block(b, i, kb, off_ref)))] * 2
    operands += [jnp.moveaxis(s, 2, 1).reshape(B, Hkv, 1, Skv) for s in (k_scale, v_scale)]
  rows = heads * block_q
  out = pl.pallas_call(
    functools.partial(_flash_kernel, block_k=block_k, n_blocks=n_blocks, scale=scale, quantized=quantized, window=window, p_terms=P_TERMS),
    out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
    grid_spec=pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=1,
      grid=(B, Hq // heads, Sq // block_q, kv_steps),
      in_specs=in_specs,
      out_specs=q_spec,
      scratch_shapes=[
        pltpu.VMEM((rows, 1), jnp.float32),  # running max
        pltpu.VMEM((rows, 1), jnp.float32),  # running denom
        pltpu.VMEM((rows, hd), jnp.float32),  # accumulator
      ],
    ),
    compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
    interpret=interpret,
    name="flash_prefill",
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
