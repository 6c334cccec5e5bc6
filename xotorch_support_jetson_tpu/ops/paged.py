"""Paged KV cache: block-table indirection over a shared page pool.

The batched server's round-1 cache gave every slot ``max_seq`` tokens of HBM
up front — the concurrency ceiling was ``n_slots × max_seq`` bytes whether or
not requests used their window. Here the cache is a pool of fixed-size pages;
each request maps logical positions onto pages through a block table, so HBM
holds only the tokens that exist, concurrent capacity is bounded by *aggregate*
context instead of per-slot worst case, and page-aligned prompt prefixes can be
shared between requests (inference/batch_scheduler.py owns allocation and
prefix dedup; this module owns the device-side ops).

No reference counterpart: the reference's torch engine has a dense per-request
cache (``SURVEY.md §5.7`` marks long-context serving greenfield). The design
target is TPU: static shapes everywhere (the block table is a traced [B, mp]
int32 operand — one compiled program for every allocation state), and decode
attention reads pages through a Pallas kernel whose block-table indirection
rides scalar prefetch, clamped so out-of-range grid steps re-fetch the same
page (no DMA) instead of touching unallocated memory.

Pool layout: ``[L, P, Hkv, ps, hd]`` — one logical page id addresses the same
page index in every layer, and the per-(page, head) ``[ps, hd]`` tile is
contiguous for the kernel's DMA.

Page 0 is reserved as a trash page: gathers of unallocated block-table entries
read it (positionally masked anyway) and masked scatters dump there, which
keeps every shape static without conditional writes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.programs import component_scope, tracked_jit
from .attention import NEG_INF, gqa_attention, mla_absorbed_attention

DEFAULT_PAGE_SIZE = 64


def init_paged_pool(cfg, n_shard_layers: int, n_pages: int, page_size: int, dtype=None, quant: str | None = None) -> dict:
  """Page pool for a shard. ``n_pages`` INCLUDES the reserved trash page 0.

  Geometry follows ``models/decoder.py init_kv_cache``: GQA heads for dense
  models; for MLA "k" holds the kv latent and "v" the rope channel.
  ``quant="int8"`` (default from ``XOT_TPU_KV_QUANT``; dense only) adds
  per-(slot, head) scale leaves [..., 1] — halving pool bytes DOUBLES the
  contexts resident at a fixed HBM budget. ``quant="int4"`` (ISSUE 11)
  packs two code nibbles per byte along the head dim — the code leaves
  carry a HALVED trailing axis (the detection idiom everywhere: packed
  iff ``shape[-1] * 2 == cfg.cache_k_dim``) and the same per-(slot, head)
  scales, halving page bytes AGAIN vs int8 (~2x pages, ~2x effective pool
  read bandwidth, half the host-tier and wire bytes per page).
  """
  from ..models.decoder import kv_quant_mode

  dtype = dtype or cfg.dtype
  mode = kv_quant_mode(cfg, quant)
  kd, vd = cfg.cache_k_dim, cfg.cache_v_dim
  if mode == "int4":
    if kd % 2 or vd % 2:
      raise ValueError(f"int4 KV pages need even cache dims; got k={kd} v={vd}")
    kd, vd = kd // 2, vd // 2
  k_shape = (n_shard_layers, n_pages, cfg.cache_kv_heads, page_size, kd)
  v_shape = (n_shard_layers, n_pages, cfg.cache_kv_heads, page_size, vd)
  if mode:
    scale_shape = k_shape[:-1] + (1,)
    return {
      "k": jnp.zeros(k_shape, dtype=jnp.int8),
      "v": jnp.zeros(v_shape, dtype=jnp.int8),
      "k_scale": jnp.ones(scale_shape, dtype=jnp.float32),
      "v_scale": jnp.ones(scale_shape, dtype=jnp.float32),
    }
  return {"k": jnp.zeros(k_shape, dtype=dtype), "v": jnp.zeros(v_shape, dtype=dtype)}


@component_scope("xot.kv_write")
def write_token_kv(pool_l: jnp.ndarray, new: jnp.ndarray, block_tables: jnp.ndarray, pos: jnp.ndarray, page_size: int) -> jnp.ndarray:
  """Scatter one decode step's KV into the pool (one layer).

  pool_l [P, Hkv, ps, hd]; new [B, Hkv, hd]; block_tables [B, mp] int32;
  pos [B] int32 (the logical position being written). Rows own disjoint
  pages, so the scatter indices never collide.
  """
  page = jnp.take_along_axis(block_tables, (pos // page_size)[:, None], axis=1)[:, 0]  # [B]
  off = pos % page_size
  return pool_l.at[page, :, off].set(new.astype(pool_l.dtype))


def gather_pages(pool_l: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
  """[P, Hkv, ps, hd] × [B, mp] → position-ordered KV [B, mp·ps, Hkv, hd].

  The XLA fallback path (CPU tests, MLA models): materializes the gathered
  cache per layer. The Pallas kernel below avoids this copy on TPU.
  """
  g = jnp.take(pool_l, block_tables, axis=0)  # [B, mp, Hkv, ps, hd]
  B, mp, Hkv, ps, hd = g.shape
  return jnp.swapaxes(g, 2, 3).reshape(B, mp * ps, Hkv, hd)


@component_scope("xot.kv_write")
def gather_row_pages(pool_part: jnp.ndarray, bt_rows: jnp.ndarray) -> jnp.ndarray:
  """All-layer per-row page gather: [L, P, H, slots, hd] × [K, mp] →
  position-ordered [L, K, mp·slots, H, hd].

  ``slots`` is the per-device page width: the full page_size on a single
  device, or ps/sp when the pool's page-slot axis is striped over sp
  (parallel/sp_batch.py) — the shape carries the difference.
  """
  g = jnp.take(pool_part, bt_rows, axis=1)  # [L, K, mp, H, slots, hd]
  L, K, mp, H, st, hd = g.shape
  return jnp.swapaxes(g, 3, 4).reshape(L, K, mp * st, H, hd)


def touched_page_targets(bt_rows: jnp.ndarray, prefix_lens: jnp.ndarray, prompt_lens: jnp.ndarray, page_size: int) -> jnp.ndarray:
  """Per-row scatter targets for a prefill: each row's pages from its reused
  prefix boundary up to its prompt end scatter back to their real page ids;
  everything else (shared prefix pages, unallocated entries, padding rows)
  targets the trash page 0."""
  mp = bt_rows.shape[1]
  page_ids = jnp.arange(mp, dtype=jnp.int32)[None, :]
  touched = (page_ids >= prefix_lens[:, None] // page_size) & (page_ids * page_size < prompt_lens[:, None])
  return jnp.where(touched, bt_rows, 0)


@component_scope("xot.kv_write")
def scatter_row_pages(pool_part: jnp.ndarray, t: jnp.ndarray, target: jnp.ndarray) -> jnp.ndarray:
  """Inverse of ``gather_row_pages`` restricted to ``target`` pages:
  t [L, K, mp·slots, H, hd] scatters back into [L, P, H, slots, hd]."""
  L, K, N, H, hd = t.shape
  mp = target.shape[1]
  st = pool_part.shape[3]
  pages = jnp.swapaxes(t.reshape(L, K, mp, st, H, hd), 3, 4)  # [L, K, mp, H, slots, hd]
  return pool_part.at[:, target].set(pages.astype(pool_part.dtype))


@component_scope("xot.attn")
def paged_gqa_attention_ref(q, k_pool_l, v_pool_l, block_tables, lengths, page_size: int, k_scale_pool_l=None, v_scale_pool_l=None, q_positions=None, **attn_opts) -> jnp.ndarray:
  """Reference paged decode attention via gather (q [B, Sq, Hq, hd]; Sq is 1
  on the decode path). ``attn_opts`` forward gemma2's
  scale/softcap/sliding-window (models/decoder.py _attn_opts). With scale
  pools (int8/int4 KV), the gathered codes stay the einsum operand and the
  scales gather alongside — the page gather itself moves the quantized
  bytes; packed int4 pools (trailing code axis == hd/2) unpack to int8
  nibble values AFTER the gather, so the HBM-side move is 0.5 byte/element
  and the unpack is a register-level fixup XLA fuses into the consumer.
  ``q_positions`` [B, Sq] overrides the single-query default — the batched
  speculative VERIFY window (models/decoder.py paged_window_forward) passes
  each row's own window positions."""
  k = gather_pages(k_pool_l, block_tables)
  v = gather_pages(v_pool_l, block_tables)
  kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)
  if q_positions is None:
    q_positions = (lengths - 1)[:, None]  # current token's position
  if k_scale_pool_l is not None:
    if k.shape[-1] * 2 == q.shape[-1]:  # packed int4 codes (ISSUE 11)
      from ..models.quantize import unpack_int4_kv

      k = unpack_int4_kv(k)
      v = unpack_int4_kv(v)
    attn_opts = dict(attn_opts, k_scale=gather_pages(k_scale_pool_l, block_tables), v_scale=gather_pages(v_scale_pool_l, block_tables))
  return gqa_attention(q, k, v, q_positions, kv_positions, **attn_opts)


@component_scope("xot.attn")
def paged_mla_attention_ref(q_nope, q_pe, k_pool_l, v_pool_l, block_tables, lengths, w_kv_b, v_dim: int, page_size: int) -> jnp.ndarray:
  """Paged MLA decode attention: gather the latent pages, then the absorbed op."""
  ckv = gather_pages(k_pool_l, block_tables)[:, :, 0, :]  # [B, mp·ps, rank]
  kpe = gather_pages(v_pool_l, block_tables)[:, :, 0, :]
  kv_positions = jnp.arange(ckv.shape[1], dtype=jnp.int32)
  q_positions = (lengths - 1)[:, None]
  return mla_absorbed_attention(q_nope, q_pe, ckv, kpe, w_kv_b, q_positions, kv_positions, v_dim)


# ------------------------------------------------- Pallas paged decode kernel
#
# One-token-per-row decode attention straight off the page pool. Split-K
# flash-decode over pages: grid (B, Hkv, ceil(mp/G)) — the innermost axis
# runs sequentially per (row, kv-head) carrying online-softmax state in VMEM
# scratch, so long contexts stream page tiles through VMEM without ever
# materializing the gathered cache. Each grid step fetches a TILE of G pages
# (G separate block-spec'd views of the same pool operand, one index map per
# tile slot): at serving shapes (B=8-48, ctx 1K-32K, ps=64) the per-page
# grid was step-overhead-bound — G=4 cuts the sequential step count 4× while
# each page's DMA stays a contiguous [ps, hd] block. The block table and
# per-row lengths are scalar-prefetched: the index map picks each step's
# pages BEFORE the body runs, and clamps past-the-end steps to the last
# valid page so their DMA is a no-op re-fetch (Pallas skips the copy when
# the block index repeats).
#
# int8-KV pools ride through IN-KERNEL: k/v hold int8 codes and the
# per-(token, head) scale pools [P, Hkv, ps, 1] stream alongside as extra
# [ps, 1] tiles — k's scale multiplies each score column, v's folds into the
# probabilities after the denominator update (same factoring as
# ops/pallas_attention.py _flash_kernel), so the HBM page reads stay
# 1 byte/element and the paged path never materializes a dequantized cache.
# (The previous design dequantized OUTSIDE the kernel path via the gather
# reference — doubling cache-read bytes exactly where the paged path was
# losing to dense slots.)
#
# int4-KV pools (ISSUE 11) go one step further: the code tiles are PACKED
# two nibbles per byte along hd ([ps, hd/2] int8 blocks — 0.5 byte/element
# HBM reads), and the dequant stays in-register via the two-dot
# formulation models/quantize.py qdot proved out for int4 weights: with q
# DEINTERLEAVED outside the kernel (even channels first, odd second), the
# score dot is q_even·signext(packed)ᵀ + q_odd·(packed>>4)ᵀ — each operand
# a pure shift of the packed tile, nothing materialized — and the output
# accumulator is kept deinterleaved the same way (even/odd halves), with
# one channel re-interleave applied to the tiny [B, Hq, hd] result OUTSIDE
# the kernel. Scales are per (token, head) over the whole hd vector, so
# one [ps, 1] scale column serves both halves.

_PAGE_TILE_DEFAULT = 4


def _page_tile(mp: int, batch: int | None = None, context: int | None = None, kv_quant: str = "") -> int:
  """Pages fetched per grid step: the largest power of two ≤ mp, capped at
  the shape-aware dispatch verdict (inference/paging.py ``select_page_tile``
  — the flat G=4 default was tuned at B=16 and left sequential-step
  overhead on the table at B=48/96). ``XOT_TPU_PAGED_TILE`` force-caps
  every shape (the in-process sweep knob). mp need not divide the tile:
  trailing slots clamp to the last valid page and mask."""
  import os

  forced = os.getenv("XOT_TPU_PAGED_TILE")
  if forced is not None:
    cap = int(forced)
  elif batch is not None:
    from ..inference.paging import select_page_tile

    cap = select_page_tile(batch, context if context is not None else mp * DEFAULT_PAGE_SIZE, kv_quant)
  else:
    cap = _PAGE_TILE_DEFAULT
  g = 1
  while g * 2 <= min(mp, max(cap, 1)):
    g *= 2
  return g


def _paged_decode_kernel(bt_ref, len_ref, q_ref, *refs, page_size: int, scale: float, pages_per_step: int, kv_quant: str):
  import jax.experimental.pallas as pl

  G = pages_per_step
  quantized = bool(kv_quant)
  packed = kv_quant == "int4"
  k_refs, v_refs = refs[0:G], refs[G : 2 * G]
  if quantized:
    ks_refs, vs_refs = refs[2 * G : 3 * G], refs[3 * G : 4 * G]
    o_ref, m_ref, l_ref, acc_ref = refs[4 * G :]
  else:
    o_ref, m_ref, l_ref, acc_ref = refs[2 * G :]
  b, i = pl.program_id(0), pl.program_id(2)

  @pl.when(i == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  length = len_ref[b]
  # int4: q arrives DEINTERLEAVED (even channels in the first half, odd in
  # the second — paged_decode_attention reorders outside the kernel), and
  # acc/o stay in that layout until the caller re-interleaves.
  q = q_ref[0, 0].astype(jnp.float32)  # [group, hd]
  half = q.shape[-1] // 2
  # Static unroll over the tile: each page's block chains the online-softmax
  # state exactly like a dedicated grid step would (same math, G× fewer
  # sequential steps). Pages clamped by the index map land with start >=
  # length, so their whole block is skipped.
  for j in range(G):
    start = (i * G + j) * page_size

    @pl.when(start < length)
    def _block(j=j, start=start):
      if packed:
        # Two-dot in-register dequant (see the int4 note above): lo/hi are
        # pure shifts of the SAME packed [ps, hd/2] tile — read from HBM
        # once at 0.5 byte/element, never materialized unpacked.
        # Widened to int32 first: Mosaic has no int8 vector shift on v5e
        # (same idiom as ops/pallas_int4.py).
        kp = k_refs[j][0, 0].astype(jnp.int32)
        k_lo = ((kp << 28) >> 28).astype(jnp.float32)  # even channels, sign-extended
        k_hi = ((kp << 24) >> 28).astype(jnp.float32)  # odd channels
        s = jax.lax.dot_general(q[:, :half], k_lo, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(q[:, half:], k_hi, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = s * scale
      else:
        k = k_refs[j][0, 0].astype(jnp.float32)  # [ps, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale  # [group, ps]
      if quantized:
        # codes·scale = true k: the per-token scale multiplies each score
        # COLUMN ([ps, 1] transposed to a [1, ps] row broadcast). One scale
        # covers the whole hd vector, so it applies after both int4 halves.
        s = s * jnp.transpose(ks_refs[j][0, 0], (1, 0))
      kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
      s = jnp.where(kv_pos < length, s, NEG_INF)
      m_prev = m_ref[...]
      blk_m = jnp.max(s, axis=1, keepdims=True)
      m_new = jnp.maximum(m_prev, blk_m)
      p = jnp.exp(s - m_new)
      p = jnp.where(m_new <= NEG_INF / 2, 0.0, p)
      alpha = jnp.exp(m_prev - m_new)
      m_ref[...] = m_new
      l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
      if quantized:
        p = p * jnp.transpose(vs_refs[j][0, 0], (1, 0))  # v's scale folds into probs (after the l update)
      if packed:
        vp_ = v_refs[j][0, 0].astype(jnp.int32)
        v_lo = ((vp_ << 28) >> 28).astype(jnp.float32)
        v_hi = ((vp_ << 24) >> 28).astype(jnp.float32)
        upd = jnp.concatenate(
          [
            jax.lax.dot_general(p, v_lo, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32),
            jax.lax.dot_general(p, v_hi, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32),
          ],
          axis=-1,
        )  # deinterleaved [group, hd]: even half, then odd half
        acc_ref[...] = acc_ref[...] * alpha + upd
      else:
        v = v_refs[j][0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

  @pl.when(i == pl.num_programs(2) - 1)
  def _finish():
    l = l_ref[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@component_scope("xot.attn")
def paged_decode_attention(
  q, k_pool_l, v_pool_l, block_tables, lengths, page_size: int,
  k_scale_pool_l=None, v_scale_pool_l=None, pages_per_step: int | None = None, interpret: bool = False,
):
  """Decode attention off the page pool (dense GQA models).

  q [B, Hq, hd] (the single new token per row); k/v pool [P, Hkv, ps, hd];
  block_tables [B, mp] int32 (unallocated entries may hold anything — steps
  past ``lengths`` are clamped to the last valid page and masked);
  lengths [B] int32 = number of valid KV slots INCLUDING the token just
  written. With ``k_scale_pool_l``/``v_scale_pool_l`` [P, Hkv, ps, 1]
  (int8-KV pools — init_paged_pool quant="int8"), k/v hold int8 codes
  dequantized in-register per page tile; a pool whose code axis is HALVED
  ([P, Hkv, ps, hd/2] — init_paged_pool quant="int4") holds packed int4
  nibbles dequantized via the two-dot split (module note above).
  ``pages_per_step`` (static) overrides the shape-aware page-tile verdict
  (inference/paging.py ``select_page_tile``). Returns [B, Hq, hd].
  """
  if (k_scale_pool_l is None) != (v_scale_pool_l is None):
    raise ValueError("paged_decode_attention: k_scale_pool_l and v_scale_pool_l must be passed together")
  kv_quant = ""
  if k_scale_pool_l is not None:
    kv_quant = "int4" if jnp.shape(k_pool_l)[-1] * 2 == jnp.shape(q)[-1] else "int8"
  # Resolve the env-tunable tile width OUTSIDE the jitted body: baked-in-at-
  # first-trace env reads silently ignore later changes for identical shapes
  # (an in-process XOT_TPU_PAGED_TILE sweep would re-time one width forever).
  mp = jnp.shape(block_tables)[1]
  G = pages_per_step or _page_tile(mp, batch=jnp.shape(q)[0], context=mp * page_size, kv_quant=kv_quant)
  return _paged_decode_attention_impl(
    q, k_pool_l, v_pool_l, block_tables, lengths, k_scale_pool_l, v_scale_pool_l,
    page_size=page_size, pages_per_step=G, kv_quant=kv_quant, interpret=interpret,
  )


@functools.partial(tracked_jit, "ops.paged_attention", static_argnames=("page_size", "pages_per_step", "kv_quant", "interpret"))
def _paged_decode_attention_impl(
  q, k_pool_l, v_pool_l, block_tables, lengths, k_scale_pool_l, v_scale_pool_l,
  page_size: int, pages_per_step: int, kv_quant: str, interpret: bool,
):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  quantized = bool(kv_quant)
  packed = kv_quant == "int4"
  B, Hq, hd = q.shape
  Hkv = k_pool_l.shape[1]
  group = Hq // Hkv
  mp = block_tables.shape[1]
  kd = k_pool_l.shape[-1]  # hd, or hd/2 for packed int4 codes
  G = pages_per_step
  n_steps = (mp + G - 1) // G
  scale = float(1.0 / (hd**0.5))
  qg = q.reshape(B, Hkv, group, hd)
  if packed:
    # Deinterleave q once outside the kernel (even channels first, odd
    # second) so the in-kernel two-dot uses contiguous halves; the output
    # comes back in the same layout and is re-interleaved below.
    qg = jnp.concatenate([qg[..., 0::2], qg[..., 1::2]], axis=-1)

  def page_index(j):
    def index(b, h, i, bt_ref, len_ref):
      # Clamp past-the-end tile slots to the row's last valid page: the
      # repeated block index makes the DMA a no-op instead of fetching
      # garbage (also covers mp % G != 0 trailing slots).
      last = jnp.maximum(len_ref[b] - 1, 0) // page_size
      return (bt_ref[b, jnp.minimum(i * G + j, last)], h, 0, 0)

    return index

  in_specs = [pl.BlockSpec((1, 1, group, hd), lambda b, h, i, bt, ln: (b, h, 0, 0))]
  in_specs += [pl.BlockSpec((1, 1, page_size, kd), page_index(j)) for j in range(G)]
  in_specs += [pl.BlockSpec((1, 1, page_size, kd), page_index(j)) for j in range(G)]
  operands = [qg] + [k_pool_l] * G + [v_pool_l] * G
  if quantized:
    in_specs += [pl.BlockSpec((1, 1, page_size, 1), page_index(j)) for j in range(G)]
    in_specs += [pl.BlockSpec((1, 1, page_size, 1), page_index(j)) for j in range(G)]
    operands += [k_scale_pool_l] * G + [v_scale_pool_l] * G

  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=2,
    grid=(B, Hkv, n_steps),
    in_specs=in_specs,
    out_specs=pl.BlockSpec((1, 1, group, hd), lambda b, h, i, bt, ln: (b, h, 0, 0)),
    scratch_shapes=[
      pltpu.VMEM((group, 1), jnp.float32),
      pltpu.VMEM((group, 1), jnp.float32),
      pltpu.VMEM((group, hd), jnp.float32),
    ],
  )
  out = pl.pallas_call(
    functools.partial(_paged_decode_kernel, page_size=page_size, scale=scale, pages_per_step=G, kv_quant=kv_quant),
    out_shape=jax.ShapeDtypeStruct((B, Hkv, group, hd), q.dtype),
    grid_spec=grid_spec,
    interpret=interpret,
  )(block_tables, lengths, *operands)
  if packed:
    # Undo the deinterleave on the [B, Hkv, group, hd] result: channel 2i
    # from the even half, 2i+1 from the odd half.
    half = hd // 2
    out = jnp.stack([out[..., :half], out[..., half:]], axis=-1).reshape(B, Hkv, group, hd)
  return out.reshape(B, Hq, hd)


def paged_kernel_supported(cfg, platform: str | None = None) -> bool:
  """Whether the Pallas paged kernel CAN run for this model/platform.

  Capability + kill-switches only — whether it SHOULD run for a given
  (batch, context, quant-mode) is the dispatch table's call
  (inference/paging.py select_decode_path; models/decoder.py resolves
  ``use_kernel`` through both). ``XOT_TPU_NO_FLASH`` and
  ``XOT_TPU_PAGED_KERNEL=0`` force it off everywhere."""
  import os

  from ..utils.helpers import env_flag

  if os.getenv("XOT_TPU_NO_FLASH") or not env_flag("XOT_TPU_PAGED_KERNEL", default=True):
    return False
  platform = platform or jax.default_backend()
  return platform == "tpu" and cfg.plain_attention and not cfg.is_mla and cfg.head_dim in (64, 128, 256)
