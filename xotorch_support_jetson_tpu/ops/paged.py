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
attention reads pages through a Pallas kernel whose work follows each row's
resident pages: the block table and the lengths ride scalar prefetch, a
loop bounded by the row's own length fetches its pages from HBM by DMA,
double-buffered, and nothing past a row's length is read. What a call costs
is set by the tokens it attends, not by the table's width or the pool's size.

Pool layout: ``[L, P, Hkv, ps, hd]`` — one logical page id addresses the same
page index in every layer, and a page's ``[Hkv, ps, hd]`` block is contiguous:
one DMA brings it for every kv head.

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
# One-token-per-row decode attention straight off the page pool, with work
# proportional to the pages each row really holds. The grid runs over rows
# only; inside, a loop with the dynamic trip count cdiv(length, G·ps) walks
# the row's own block-table entries (scalar-prefetched into SMEM). No axis
# is sized by the table's width mp: a row of length 0 does nothing, and
# entries past a row's length are never read — not the table entry, not the
# page behind it.
#
# The pool operands stay in HBM (``pl.ANY``). A page's [Hkv, ps, hd] block
# is contiguous in the [P, Hkv, ps, hd] layer, so one DMA brings a page for
# ALL kv heads; a tile of G pages (``pages_per_step``) is fetched per loop
# iteration into one of two VMEM slots while the other slot's tile is
# computed, and a row's last iteration already fetches the next row's first
# tile (the slot parity crosses grid steps in SMEM), so only the call's very
# first fetch is exposed. All Hq query heads of the row are computed per
# fetched page, in three phases over the kv heads (score dots, one softmax
# update over the stacked scores, value dots), carrying the online-softmax
# state (f32 running max, sum, accumulator per query head) in VMEM scratch.
#
# int8-KV pools ride through IN-KERNEL: k/v hold int8 codes, and the
# per-(token, head) scales arrive lane-dense as [P, Hkv, ps] (a reshape of
# the pool's [P, Hkv, ps, 1] leaf made outside the kernel — a trailing axis
# of 1 would be padded 128× to lanes), one small DMA a page. k's scale
# multiplies each score column, v's folds into the probabilities after the
# denominator update (same factoring as ops/pallas_attention.py
# _flash_kernel), so the HBM page reads stay 1 byte/element and the paged
# path never materializes a dequantized cache. The probabilities stay f32.
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
# one [1, ps] scale row serves both halves.

_PAGE_TILE_DEFAULT = 4


def _page_tile(mp: int, batch: int | None = None, context: int | None = None, kv_quant: str = "") -> int:
  """Pages fetched and computed per loop iteration: the largest power of two
  ≤ mp, capped at the shape-aware dispatch verdict (inference/paging.py
  ``select_page_tile``). ``XOT_TPU_PAGED_TILE`` force-caps every shape (the
  in-process sweep knob). mp need not divide the tile: a row's last tile
  holds only the pages the row has."""
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
  from jax.experimental.pallas import tpu as pltpu

  G, ps = pages_per_step, page_size
  quantized = bool(kv_quant)
  packed = kv_quant == "int4"
  n_pools = 4 if quantized else 2  # k, v (+ their scales): HBM operands first, then the output, then their VMEM tiles
  pools_hbm, o_ref, pools_buf = refs[:n_pools], refs[n_pools], refs[n_pools + 1 : 2 * n_pools + 1]
  sem, slot_ref, m_ref, l_ref, acc_ref = refs[2 * n_pools + 1 :]
  k_buf, v_buf, ks_buf, vs_buf = (*pools_buf, None, None)[:4]
  n_rows, mp = bt_ref.shape
  n_heads = k_buf.shape[2]
  b = pl.program_id(0)

  def tile_pages(row, tile):
    """Resident pages of one tile of a row (the clamp to mp keeps a length
    beyond the table inside it)."""
    return jnp.clip(jnp.minimum(pl.cdiv(len_ref[row], ps), mp) - tile * G, 0, G)

  def tile_dmas(row, tile, slot, act):
    """Start or wait for (``act``) the DMAs of the resident pages of one
    tile: per page one copy of its [Hkv, ps, hd] codes for k and for v, and
    of its [Hkv, ps] scales when quantized (both padded to whole lanes), all
    on the slot's semaphore."""

    def page(j, carry):
      p = bt_ref[row, tile * G + j]
      for hbm, buf in zip(pools_hbm, pools_buf):
        act(pltpu.make_async_copy(hbm.at[p], buf.at[slot, j], sem.at[slot]))
      return carry

    jax.lax.fori_loop(0, tile_pages(row, tile), page, 0)

  def start(row, tile, slot):
    tile_dmas(row, tile, slot, lambda dma: dma.start())

  length = len_ref[b]
  n_tiles = pl.cdiv(jnp.minimum(pl.cdiv(length, ps), mp), G)

  @pl.when(b == 0)
  def _first_row():
    slot_ref[0] = 0

  first_slot = slot_ref[0]
  # The row before, if it held anything, started this row's first tile.
  prefetched = jnp.logical_and(b > 0, len_ref[jnp.maximum(b - 1, 0)] > 0)

  @pl.when(jnp.logical_not(prefetched))
  def _fetch_first_tile():
    start(b, 0, first_slot)

  m_ref[...] = jnp.full_like(m_ref, NEG_INF)
  l_ref[...] = jnp.zeros_like(l_ref)
  acc_ref[...] = jnp.zeros_like(acc_ref)
  hd = q_ref.shape[-1]
  kd = hd // 2 if packed else hd  # the lanes of a code tile that are codes, not padding
  # The score dot runs in q's dtype over quantized pages: int8 codes are
  # exact in bf16 and in f32, and products of bf16 pairs are exact in the
  # f32 accumulator.
  dot_dtype = q_ref.dtype if quantized else jnp.promote_types(q_ref.dtype, k_buf.dtype)

  def code_halves(x):
    """A [ps, kd] code tile as the dot's right operands. Packed int4: the
    (even, odd) channel halves, pure shifts of the SAME packed bytes,
    widened to int32 first — Mosaic has no int8 vector shift on v5e (same
    idiom as ops/pallas_int4.py)."""
    if not packed:
      return (x,)
    x = x.astype(jnp.int32)
    return ((x << 28) >> 28, (x << 24) >> 28)

  # Per kv head, its group of query heads [group, hd] as the dot's left
  # operands. Packed int4: q arrives DEINTERLEAVED (even channels in the
  # first half, odd in the second — paged_decode_attention reorders outside
  # the kernel), and acc/o stay in that layout until the caller re-interleaves.
  group = q_ref.shape[1] // n_heads
  q = q_ref[0].astype(dot_dtype)
  qs = [q[h * group : (h + 1) * group] for h in range(n_heads)]
  qs = [(x[:, :kd], x[:, kd:]) if packed else (x,) for x in qs]

  def attend_page(tile, slot, j):
    """Fold page j of the slot's tile into the online softmax, in three
    phases over the kv heads — every score dot, one softmax update over the
    stacked [Hq, ps] scores, every value dot — so that the heads' matmuls
    stand side by side: head by head, each dot waited for the softmax before
    it and a page cost twice as much (PERF.md §6, PR 25)."""
    valid = (tile * G + j) * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1) < length
    scores = []
    for h in range(n_heads):
      s = sum(
        jax.lax.dot_general(qx, kx.astype(dot_dtype), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        for qx, kx in zip(qs[h], code_halves(k_buf[slot, j, h, :, :kd]))
      ) * scale  # [group, ps]
      if quantized:
        # codes·scale = true k: the per-token scale multiplies each score
        # COLUMN. One scale covers the whole hd vector, so it applies after
        # both int4 halves.
        s = s * ks_buf[slot, j, pl.ds(h, 1), :ps]
      scores.append(jnp.where(valid, s, NEG_INF))  # a fetched page holds at least one valid slot: the max stays finite
    s = jnp.concatenate(scores, axis=0)  # [Hq, ps]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    upd = []
    for h in range(n_heads):
      ph = p[h * group : (h + 1) * group]
      if quantized:
        ph = ph * vs_buf[slot, j, pl.ds(h, 1), :ps]  # v's scale folds into probs (after the l update)
      # packed: even half, then odd half
      upd.append(jnp.concatenate([jax.lax.dot_general(ph, vx.astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) for vx in code_halves(v_buf[slot, j, h, :, :kd])], axis=-1))
    acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(upd, axis=0)

  def tile_body(i, carry):
    slot = (first_slot + i) % 2

    @pl.when(i + 1 < n_tiles)
    def _fetch_next_tile():
      start(b, i + 1, 1 - slot)

    @pl.when(jnp.logical_and(i + 1 == n_tiles, b + 1 < n_rows))
    def _fetch_next_rows_first_tile():
      start(jnp.minimum(b + 1, n_rows - 1), 0, 1 - slot)

    tile_dmas(b, i, slot, lambda dma: dma.wait())

    def page(j, c):
      attend_page(i, slot, j)
      return c

    jax.lax.fori_loop(0, tile_pages(b, i), page, 0)
    return carry

  jax.lax.fori_loop(0, n_tiles, tile_body, 0)
  slot_ref[0] = (first_slot + n_tiles) % 2
  l = l_ref[...]
  o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@component_scope("xot.attn")
def paged_decode_attention(
  q, k_pool_l, v_pool_l, block_tables, lengths, page_size: int,
  k_scale_pool_l=None, v_scale_pool_l=None, pages_per_step: int | None = None, interpret: bool = False,
):
  """Decode attention off the page pool (dense GQA models).

  q [B, Hq, hd] (the single new token per row); k/v pool [P, Hkv, ps, hd];
  block_tables [B, mp] int32 (entries past a row's ``lengths`` may hold
  anything — they are never read); lengths [B] int32 = number of valid KV
  slots INCLUDING the token just written. With
  ``k_scale_pool_l``/``v_scale_pool_l`` [P, Hkv, ps, 1]
  (int8-KV pools — init_paged_pool quant="int8"), k/v hold int8 codes
  dequantized in-register per page; a pool whose code axis is HALVED
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
  G = pages_per_step
  scale = float(1.0 / (hd**0.5))
  if packed:
    # Deinterleave q once outside the kernel (even channels first, odd
    # second) so the in-kernel two-dot uses contiguous halves; the output
    # comes back in the same layout and is re-interleaved below.
    q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)

  row_block = pl.BlockSpec((1, Hq, hd), lambda b, bt, ln: (b, 0, 0))
  in_hbm = pl.BlockSpec(memory_space=pl.ANY)

  def lane_dense(x):
    """Mosaic slices a page out of an HBM operand only along whole lanes:
    the minor axis padded to a multiple of 128 (a no-op for hd 128/256
    codes; sub-128 code axes — hd 64, packed int4 — pay a copy of the layer)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, -x.shape[-1] % 128)])

  operands = [q, lane_dense(k_pool_l), lane_dense(v_pool_l)]
  tile = lambda pool: pltpu.VMEM((2, G, *pool.shape[1:]), pool.dtype)  # noqa: E731 — two slots of G pages
  if quantized:
    # The scales with tokens on lanes: [P, Hkv, ps, 1] → [P, Hkv, ps].
    operands += [lane_dense(s.reshape(s.shape[:-1])) for s in (k_scale_pool_l, v_scale_pool_l)]
  scratch = [tile(x) for x in operands[1:]]
  tile_bytes = sum(2 * G * x[0].size * x.dtype.itemsize for x in operands[1:])  # wide tiles of wide pages pass the default 16 MiB
  scratch += [
    pltpu.SemaphoreType.DMA((2,)),
    pltpu.SMEM((1,), jnp.int32),  # the slot the next tile goes to, across rows
    pltpu.VMEM((Hq, 1), jnp.float32),  # running max, sum and accumulator of every query head
    pltpu.VMEM((Hq, 1), jnp.float32),
    pltpu.VMEM((Hq, hd), jnp.float32),
  ]
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=2,
    grid=(B,),
    in_specs=[row_block] + [in_hbm] * (len(operands) - 1),
    out_specs=row_block,
    scratch_shapes=scratch,
  )
  out = pl.pallas_call(
    functools.partial(_paged_decode_kernel, page_size=page_size, scale=scale, pages_per_step=G, kv_quant=kv_quant),
    out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
    grid_spec=grid_spec,
    # Rows in order: the prefetch chain crosses them.
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=tile_bytes + (16 << 20)),
    interpret=interpret,
  )(block_tables, lengths, *operands)
  if packed:
    # Undo the deinterleave: channel 2i from the even half, 2i+1 from the odd half.
    out = jnp.stack([out[..., : hd // 2], out[..., hd // 2 :]], axis=-1).reshape(B, Hq, hd)
  return out


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
