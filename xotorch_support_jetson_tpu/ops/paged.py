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
Latent attention (MLA) decodes through the same kernel's latent body since
PR 52 (``paged_latent_decode_attention``); until then it took the gather
reference, which reads the table's whole width a row whatever the row holds.

Pool layout: ``[L, P, Hkv, ps, hd]`` — one logical page id addresses the same
page index in every layer, and a page's ``[Hkv, ps, hd]`` block is contiguous:
one DMA brings it for every kv head. The decode programs carry the stacked
leaves through their layer loop and address them by ``(layer, page)``: a
token write touches ``(layer, page, :, slot)`` alone, the kernel's DMA source
is ``pool.at[layer, page]`` and the gather reference reads
``pool[layer, block_table]`` — no layer is ever sliced out of the pool or
written back whole (PERF.md §6, PR 29).

Paired heads (PR 58) — THE statement of the form; everything else points
here. Mosaic slices a page out of an HBM operand only along whole lanes, so a
head of 64 channels, stored alone, had to be padded to 128 in a copy of the
whole pool once a dispatch and was then read at twice its bytes. An
unquantised pool whose K and V heads are both 64 wide and even in number
(``pairs_kv_heads``) is therefore STORED as ``[L, P, Hkv/2, ps, 128]``: KV
head 2j in lanes 0-63 of leaf head j, head 2j+1 in lanes 64-127 — the same
bytes in whole lanes, already the kernel's form. The rule is one of shapes,
as packed int4's is: a leaf is paired iff ``leaf.shape[2] * 2`` is the
model's ``cache_kv_heads``. Position-major, ``[…, slots, Hkv/2, 128]`` is
``[…, slots, Hkv, 64]`` by a reshape (``heads_as``), and that is all the
accessors between pages and tokens do about it (``write_token_kv``,
``gather_pages``, ``gather_row_pages``, ``scatter_row_pages``); to the decode
kernel such a pool is a GQA pool of Hkv/2 heads of 128 whose groups hold both
heads' queries, zero-extended to their head's half (``_pair_queries``).
Quantised pools (a scale is per token and head), MLA's leaves (one head), an
odd head count and every other width keep ``[L, P, Hkv, ps, hd]``.

Page 0 is reserved as a trash page: gathers of unallocated block-table entries
read it (positionally masked anyway) and masked scatters dump there, which
keeps every shape static without conditional writes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..utils.programs import component_scope, tracked_jit
from .attention import NEG_INF, gqa_attention, mla_absorbed_attention

DEFAULT_PAGE_SIZE = 64

# A hybrid's per-slot leaves of the pool dict: the state of its state-space
# layers, indexed [layer, slot row] and not by page. They are donated, carried
# and returned with the page leaves; whatever walks the pool by page takes
# ``page_leaves`` of it.
STATE_LEAVES = ("ssm", "conv")


def page_leaves(pool: dict) -> dict:
  return {name: leaf for name, leaf in pool.items() if name not in STATE_LEAVES}


def state_leaves(pool: dict) -> dict:
  return {name: leaf for name, leaf in pool.items() if name in STATE_LEAVES}


def init_paged_pool(cfg, n_shard_layers: int, n_pages: int, page_size: int, dtype=None, quant: str | None = None, n_slots: int = 0) -> dict:
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

  An unquantised pool of 64-channel heads, even in number, pairs them on
  the lanes: [L, P, Hkv/2, ps, 128] (``pairs_kv_heads``; the module note).

  A configuration with recurrent layers has pages for its attention layers
  only (the layer axis is ``cfg.n_attn_layers``) and, beside them, the state
  of its state-space layers for each of ``n_slots`` rows: ``ssm``
  [Ls, n_slots, H, P, N] in float32 (the recurrence multiplies it by a decay
  near 1 for thousands of steps; what it loses in bfloat16 is read in PERF.md
  §6, PR 34) and ``conv`` [Ls, n_slots, K-1, di+2GN] (G groups of B and C: ``cfg.ssm_conv_dim``), the rows the convolution
  still needs, in the model dtype. Zeros: a slot's state before its first
  tenant, and what a prefill from position 0 starts from. In decode the
  ``ssm`` leaf is stepped in place at (layer) by ``ops/ssm.py
  ssm_state_step`` — float32 in both of its forms, the XLA expression and
  the one-pass Mosaic kernel — and ``conv`` by ``_ssm_decode_step`` itself.
  A kind with no state matrix (``cfg.state_matrix`` false: the gated short
  convolution, whose whole state is its tail) gets the ``conv`` leaf alone,
  [Ls, n_slots, K-1, dim]: no ``ssm`` leaf, of any size.
  """
  from ..models.decoder import kv_quant_mode

  dtype = dtype or cfg.dtype
  state = {}
  if cfg.recurrent_layers:
    n_shard_layers, Ls = cfg.n_attn_layers, cfg.recurrent_layers
    state = {"ssm": jnp.zeros((Ls, n_slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)} if cfg.state_matrix else {}
    state["conv"] = jnp.zeros((Ls, n_slots, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype)
  mode = kv_quant_mode(cfg, quant)
  kd, vd = cfg.cache_k_dim, cfg.cache_v_dim
  if mode == "int4":
    if kd % 2 or vd % 2:
      raise ValueError(f"int4 KV pages need even cache dims; got k={kd} v={vd}")
    kd, vd = kd // 2, vd // 2
  heads = cfg.cache_kv_heads
  if pairs_kv_heads(cfg, mode):
    heads, kd, vd = heads // 2, 2 * kd, 2 * vd
  k_shape = (n_shard_layers, n_pages, heads, page_size, kd)
  v_shape = (n_shard_layers, n_pages, heads, page_size, vd)
  if mode:
    scale_shape = k_shape[:-1] + (1,)
    return {
      "k": jnp.zeros(k_shape, dtype=jnp.int8),
      "v": jnp.zeros(v_shape, dtype=jnp.int8),
      "k_scale": jnp.ones(scale_shape, dtype=jnp.float32),
      "v_scale": jnp.ones(scale_shape, dtype=jnp.float32),
      **state,
    }
  return {"k": jnp.zeros(k_shape, dtype=dtype), "v": jnp.zeros(v_shape, dtype=dtype), **state}


def pairs_kv_heads(cfg, quant: str | None = "") -> bool:
  """Whether a pool of ``cfg`` in KV mode ``quant`` stores two KV heads a lane group (the module note): float pages,
  K and V heads both half a lane group wide, an even number of them."""
  return not quant and cfg.cache_k_dim == cfg.cache_v_dim == 64 and cfg.cache_kv_heads % 2 == 0


def heads_as(x: jnp.ndarray, heads: int | None) -> jnp.ndarray:
  """Position-major K/V ``[…, H, w]`` with its head axis regrouped to ``heads`` (None: as it is): paired leaf heads
  [Hkv/2, 128] ↔ the model's [Hkv, 64], either way a reshape of adjacent heads (the module note)."""
  return x if heads in (None, x.shape[-2]) else x.reshape(*x.shape[:-2], heads, x.shape[-2] * x.shape[-1] // heads)


def code_lanes_filled(leaf) -> float:
  """The share of a code leaf's row, as the kernel's DMA takes it (``_kernel_leaf``), that holds codes: 1.0 for
  whole lane groups (hd 128 / 256, paired heads of 64), 0.5 for a 64-wide row padded to 128 — the gauge
  ``kv_page_lanes_filled``."""
  return leaf.shape[-1] / (leaf.shape[-1] + -leaf.shape[-1] % 128)


def _stacked(leaf, layer):
  """``(leaf, layer)`` of a stacked pool leaf; a single layer's leaf (``layer``
  None: the direct callers — tests, ``chip_smoke.py``) is a stack of one."""
  return (leaf[None], 0) if layer is None else (leaf, layer)


@component_scope("xot.kv_write")
def write_token_kv(pool: dict, new: dict, layer, block_tables: jnp.ndarray, pos: jnp.ndarray, page_size: int, kernel: bool = False, interpret: bool = False) -> dict:
  """Write one decode step's KV of one layer into the stacked pool, in place.

  pool: the stacked leaves {"k", "v"} [L, P, Hkv, ps, hd] (+ "k_scale" /
  "v_scale" [L, P, Hkv, ps, 1]); new: the same keys, [B, Hkv, hd]
  ([B, Hkv, 1] for a scale; a paired leaf takes its token as [B, Hkv/2, 128],
  the module note); layer a traced scalar; block_tables [B, mp]
  int32; pos [B] int32 (the logical position being written). Only the
  ``B × Hkv`` token rows at ``(layer, page, :, slot)`` are touched. Rows own
  disjoint pages, so the writes never collide (inactive rows all land in the
  trash page 0 of this layer).

  ``kernel``: the pool is in the kernel's form (``kernel_pool_form``) and
  the write is Mosaic's too (``_token_write_kernel``) — on a TPU the XLA
  scatter wants the stacked code leaves in another layout than the attention
  kernel, and a carry both touch is then copied whole, per layer (PERF.md §6,
  PR 29). Otherwise one XLA scatter a leaf.
  """
  page = jnp.take_along_axis(block_tables, (pos // page_size)[:, None], axis=1)[:, 0]  # [B]
  off = pos % page_size
  new = {name: heads_as(new[name], leaf.shape[2]) for name, leaf in pool.items()}
  if kernel:
    return _write_token_kv_kernel(pool, new, layer, page, off, interpret)
  return {name: leaf.at[layer, page, :, off].set(new[name].astype(leaf.dtype)) for name, leaf in pool.items()}


# ------------------------------------------------- Pallas token-write kernel
#
# The kernel path's token write: per row, the aligned group of slots that
# holds the token's slot — [Hkv, g, lanes] of a code leaf, g the rows of one
# packed tile (32 for int8, 16 for bf16, 8 for f32), and the page's
# [Hkv, lanes] tile of a lane-dense scale leaf — comes from HBM into VMEM by
# DMA, the token's row (a scale's lane) is put in by a select, and the group
# goes back: a single row of a packed dtype is under what a DMA or a store
# addresses. The pool leaves stay in HBM (``pl.ANY``) and alias their outputs,
# so the program's pool is ONE buffer per leaf that this call and the
# attention kernel both address by (layer, page): XLA never relays or copies
# it. Every row's inbound DMAs start before the first is waited for. A row
# on the trash page 0 (inactive) is skipped: several such rows would race on
# one page, and nothing reads what they write.

_WRITE_ROWS = 16  # rows of one grid step: their groups are in flight together


def _token_write_kernel(page_ref, off_ref, layer_ref, *refs, n: int, rows: int, groups: tuple):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  news, pools, bufs, sem = refs[:n], refs[2 * n : 3 * n], refs[3 * n : 4 * n], refs[4 * n]  # refs[n:2n]: the pools as inputs, aliased to the outputs
  layer = layer_ref[0]
  first = pl.program_id(0) * rows

  def dmas(r, inbound: bool):
    """The copies of row r's groups, HBM → VMEM or back, on the row's semaphore of that direction."""
    page, off = page_ref[first + r], off_ref[first + r]
    out = []
    for pool, buf, g in zip(pools, bufs, groups):
      hbm = pool.at[layer, page] if g is None else pool.at[layer, page, :, pl.ds(pl.multiple_of(off // g * g, g), g), :]
      src, dst = (hbm, buf.at[r]) if inbound else (buf.at[r], hbm)
      out.append(pltpu.make_async_copy(src, dst, sem.at[0 if inbound else 1, r]))
    return out

  def each_real_row(fn):
    """``fn(r)`` for every row of this grid step that is not on the trash page: a loop, not an unrolled body —
    the kernel is traced and lowered once per program variant, on the serving host."""

    def body(r, carry):
      pl.when(page_ref[first + r] != 0)(lambda: fn(r))
      return carry

    jax.lax.fori_loop(0, rows, body, 0)

  def fetch(r):
    for dma in dmas(r, True):
      dma.start()

  def insert_and_store(r):
    for dma in dmas(r, True):
      dma.wait()
    off = off_ref[first + r]
    for new, buf, g in zip(news, bufs, groups):
      if g is None:  # lane-dense scales [Hkv, lanes]: the slot is a lane; new[r] is [Hkv, 1]
        lane = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 1)
        buf[r] = jnp.where(lane == off, new[r], buf[r])
      else:  # codes [Hkv, g, lanes]: the slot is a row of the group; new[r] is [Hkv, 1, lanes]
        # Packed dtypes are widened to 32 bits for the select (Mosaic has few 8- and 16-bit vector ops on v5e); every value survives the round trip.
        wide = jnp.int32 if jnp.issubdtype(buf.dtype, jnp.integer) else jnp.float32
        row = jax.lax.broadcasted_iota(jnp.int32, buf.shape[1:], 1)
        buf[r] = jnp.where(row == off % g, new[r].astype(wide), buf[r].astype(wide)).astype(buf.dtype)
    for dma in dmas(r, False):
      dma.start()

  def stored(r):
    for dma in dmas(r, False):
      dma.wait()

  each_real_row(fetch)
  each_real_row(insert_and_store)
  each_real_row(stored)


def _write_token_kv_kernel(pool: dict, new: dict, layer, page, off, interpret: bool) -> dict:
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  names = list(pool)
  stored, pool = pool, kernel_pool_form(pool)  # stored leaves are converted per call, as in paged_decode_attention: a direct caller's cost
  B = page.shape[0]
  rows = max(r for r in range(1, min(B, _WRITE_ROWS) + 1) if B % r == 0)
  news, groups, scratch = [], [], []
  for name in names:
    leaf, x = pool[name], new[name].astype(pool[name].dtype)
    if leaf.ndim == 4:  # lane-dense scales: one [Hkv, lanes] tile a page
      groups.append(None)
      scratch.append(pltpu.VMEM((rows, *leaf.shape[2:]), leaf.dtype))
    else:
      if x.shape[-1] < leaf.shape[-1]:  # a leaf padded to whole lanes: the token's row too
        x = jnp.pad(x, [(0, 0), (0, 0), (0, leaf.shape[-1] - x.shape[-1])])
      x = x[:, :, None, :]  # a row of a group
      ps, tile = leaf.shape[3], 8 * (4 // leaf.dtype.itemsize)
      groups.append(tile if ps % tile == 0 else ps)
      scratch.append(pltpu.VMEM((rows, leaf.shape[2], groups[-1], leaf.shape[4]), leaf.dtype))
    news.append(x)
  n = len(names)
  in_hbm = pl.BlockSpec(memory_space=pl.ANY)
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=3,
    grid=(B // rows,),
    in_specs=[pl.BlockSpec((rows, *x.shape[1:]), lambda c, *_, nd=x.ndim: (c,) + (0,) * (nd - 1)) for x in news] + [in_hbm] * n,
    out_specs=[in_hbm] * n,
    scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2, rows))],
  )
  out = pl.pallas_call(
    functools.partial(_token_write_kernel, n=n, rows=rows, groups=tuple(groups)),
    out_shape=[jax.ShapeDtypeStruct(pool[name].shape, pool[name].dtype) for name in names],
    grid_spec=grid_spec,
    input_output_aliases={3 + n + i: i for i in range(n)},  # after the three scalar-prefetch operands and the new rows
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    interpret=interpret,
    name="kv_token_write",  # not the attention kernel's: the roofline reader counts calls by that name
  )(page, off, jnp.asarray(layer, jnp.int32).reshape(1), *news, *(pool[name] for name in names))
  return stored_pool_form(dict(zip(names, out)), stored)


def gather_pages(pool: jnp.ndarray, block_tables: jnp.ndarray, layer=None, kv_heads: int | None = None) -> jnp.ndarray:
  """[L, P, Hkv, ps, hd] at ``layer`` × [B, mp] → position-ordered KV
  [B, mp·ps, Hkv, hd]; ``kv_heads``: the model's, which unpairs a paired
  leaf's heads (the module note; None: the leaf's own).

  The XLA fallback path (every backend off the TPU, ``use_kernel=False``
  callers): one gather by ``(layer, page)`` reads every page the TABLE names —
  its whole width a row, whatever the row holds — and materializes the gathered
  window. The Pallas kernel below walks the pages a row holds instead.
  """
  pool, layer = _stacked(pool, layer)
  g = pool[layer, block_tables]  # [B, mp, Hkv, ps, hd]
  B, mp, Hkv, ps, hd = g.shape
  return heads_as(jnp.swapaxes(g, 2, 3).reshape(B, mp * ps, Hkv, hd), kv_heads)


@component_scope("xot.kv_write")
def gather_row_pages(pool_part: jnp.ndarray, bt_rows: jnp.ndarray, kv_heads: int | None = None) -> jnp.ndarray:
  """All-layer per-row page gather: [L, P, H, slots, hd] × [K, mp] →
  position-ordered [L, K, mp·slots, H, hd]; ``kv_heads`` as in
  ``gather_pages`` (a scale leaf's and a latent's heads are the model's: untouched).

  ``slots`` is the per-device page width: the full page_size on a single
  device, or ps/sp when the pool's page-slot axis is striped over sp
  (parallel/sp_batch.py) — the shape carries the difference.
  """
  g = jnp.take(pool_part, bt_rows, axis=1)  # [L, K, mp, H, slots, hd]
  L, K, mp, H, st, hd = g.shape
  return heads_as(jnp.swapaxes(g, 3, 4).reshape(L, K, mp * st, H, hd), kv_heads)


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
  t [L, K, mp·slots, H, hd] scatters back into [L, P, H, slots, hd] (a
  paired leaf's heads read off the leaf: the module note)."""
  t = heads_as(t, pool_part.shape[2])
  L, K, N, H, hd = t.shape
  mp = target.shape[1]
  st = pool_part.shape[3]
  pages = jnp.swapaxes(t.reshape(L, K, mp, st, H, hd), 3, 4)  # [L, K, mp, H, slots, hd]
  return pool_part.at[:, target].set(pages.astype(pool_part.dtype))


@component_scope("xot.attn")
def paged_gqa_attention_ref(q, k_pool, v_pool, block_tables, lengths, page_size: int, k_scale_pool=None, v_scale_pool=None, q_positions=None, layer=None, **attn_opts) -> jnp.ndarray:
  """Reference paged decode attention via gather (q [B, Sq, Hq, hd]; Sq is 1
  on the decode path). The pools are stacked leaves [L, P, Hkv, ps, hd] read
  at ``layer`` (``layer`` None: one layer's [P, Hkv, ps, hd]); float pools
  are cast to q's dtype after the gather, and unpaired by it where their rows
  are two of q's heads wide (stored leaves say so themselves: the module note). ``attn_opts`` forward gemma2's
  scale/softcap/sliding-window (models/decoder.py _attn_opts). With scale
  pools (int8/int4 KV), the gathered codes stay the einsum operand and the
  scales gather alongside — the page gather itself moves the quantized
  bytes; packed int4 pools (trailing code axis == hd/2) unpack to int8
  nibble values AFTER the gather, so the HBM-side move is 0.5 byte/element
  and the unpack is a register-level fixup XLA fuses into the consumer.
  ``q_positions`` [B, Sq] overrides the single-query default — the batched
  speculative VERIFY window (models/decoder.py paged_window_forward) passes
  each row's own window positions."""
  kv_heads = _stored_kv_heads(k_pool, q.shape[-1], k_scale_pool is not None)
  k = gather_pages(k_pool, block_tables, layer, kv_heads)
  v = gather_pages(v_pool, block_tables, layer, kv_heads)
  kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)
  if q_positions is None:
    q_positions = (lengths - 1)[:, None]  # current token's position
  if k_scale_pool is not None:
    if k.shape[-1] * 2 == q.shape[-1]:  # packed int4 codes (ISSUE 11)
      from ..models.quantize import unpack_int4_kv

      k = unpack_int4_kv(k)
      v = unpack_int4_kv(v)
    attn_opts = dict(attn_opts, k_scale=gather_pages(k_scale_pool, block_tables, layer), v_scale=gather_pages(v_scale_pool, block_tables, layer))
  else:
    k, v = k.astype(q.dtype), v.astype(q.dtype)
  return gqa_attention(q, k, v, q_positions, kv_positions, **attn_opts)


@component_scope("xot.attn")
def paged_mla_attention_ref(q_nope, q_pe, k_pool, v_pool, block_tables, lengths, w_kv_b, v_dim: int, page_size: int, layer=None) -> jnp.ndarray:
  """Paged MLA decode attention: gather the latent pages of ``layer`` out of
  the stacked leaves, then the absorbed op. The reference of the kernel's
  latent body (``paged_latent_decode_attention``) and what ``use_kernel=False``
  callers take: it reads the table's whole width a row, twice, in float32."""
  ckv = gather_pages(k_pool, block_tables, layer)[:, :, 0, :].astype(q_nope.dtype)  # [B, mp·ps, rank]
  kpe = gather_pages(v_pool, block_tables, layer)[:, :, 0, :].astype(q_nope.dtype)
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
# page behind it. A layer with a window (``window``, static, since PR 46)
# walks from the tile that holds position ``length - window`` on, fetches from
# the page that holds it and masks the positions before it: its pages stay
# resident for the whole context and what the call reads is the window's.
# Such a call is named ``paged_decode_window``; ``window`` 0 traces the kernel
# without any of it.
#
# Absorbed MLA is one more body of the same walk (``latent``, static, since
# PR 52; the call is named ``paged_decode_latent``): multi-query attention over
# the ONE cached "head" whose key is latent(rank) ‖ rope and whose value is the
# latent again. q arrives as q_abs ‖ q_pe [H, rank + lanes] (q_abs = q_nope · W_k
# and the result's · W_v stay in XLA), the pools are the latent leaf "k" and the
# rope leaf "v", a fold's scores are two dots summed and its value product
# takes the latent tile already in VMEM: a page is fetched once and serves
# both. ``latent`` False traces the kernel as it was.
#
# The pool operands are the STACKED leaves and stay in HBM (``pl.ANY``); the
# layer rides scalar prefetch beside the block table and the lengths, and the
# DMA source of a page is ``pool.at[layer, page]``. A page's [Hkv, ps, hd]
# block is contiguous in [L, P, Hkv, ps, hd], so one DMA brings a page for
# ALL kv heads; a tile of G pages (``pages_per_step``) is fetched per loop
# iteration into one of two VMEM slots while the other slot's tile is
# computed, and a row's last iteration already fetches the next row's first
# tile (the slot parity crosses grid steps in SMEM), so only the call's very
# first fetch is exposed. All Hq query heads of the row are computed per
# fetched TILE: one online-softmax update folds the whole tile of pages — or
# the half or quarter of it that covers what a row's last tile holds — in
# three phases over the kv heads (a score dot over the fold's tokens, one
# softmax update over the stacked scores, a value dot), carrying the state
# (f32 running max, sum, accumulator per query head) in VMEM scratch. Folded a
# page at a time (until PR 43) a 64-token page filled half of every 128-deep
# MXU tile and of every lane register and was one link of the chain through
# that state: 0.49 µs a page where its bytes take 0.17; a tile a fold, 0.18.
#
# int8-KV pools ride through IN-KERNEL: k/v hold int8 codes, and the
# per-(token, head) scales arrive lane-dense as [L, P, Hkv, lanes] (tokens
# on lanes, padded to whole lanes: a trailing axis of 1 would be padded 128×),
# one small DMA a page. The decode programs make that form of the stored
# [L, P, Hkv, ps, 1] leaf ONCE a dispatch, outside their step loop, carry and
# write it in that form and turn it back at the end (``kernel_pool_form`` /
# ``stored_pool_form``): made per call it is a copy of the whole stacked leaf
# per layer. k's scale
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

# The tile is the DMA's granularity, the VMEM held (two slots of G pages) and, since PR 43, the widest softmax fold.
# µs a call, Mistral's shapes (16 rows, int8, hd 128) at 1 / 4 / 7 / 15 pages a row and the closed cell's mix of 4-11:
# G 4: 19.0 / 24.9 / 38.5 / 69.5 / 40.6; G 8: 19.9 / 25.1 / 32.9 / 55.6 / 39.4; G 16: 21.5 / 25.2 / 32.7 / 53.8 / 36.3
# (a page at a time, any G: 19.9 / 43.8 / 68.1 / 132.0 / 69.5). 16 gains 2-8 % where rows hold 9-16 pages, loses 8 % at
# one page, folds packed int4 12 % slower (its codes widen to int32) and doubles the VMEM and the kernel's code:
# 8 stays (PERF.md §6, PR 43; with a per-page fold no width from 4 up won, PR 25).
PAGE_TILE = 8


def _page_tile(mp: int) -> int:
  """Pages fetched and computed per loop iteration: the largest power of two
  ≤ min(mp, PAGE_TILE). mp need not divide the tile: a row's last tile holds
  only the pages the row has."""
  g = 1
  while g * 2 <= min(mp, PAGE_TILE):
    g *= 2
  return g


def _paged_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, *refs, page_size: int, scale: float, pages_per_step: int, kv_quant: str, window: int = 0, latent: bool = False):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  G, ps, W = pages_per_step, page_size, window
  quantized = bool(kv_quant)
  packed = kv_quant == "int4"
  n_pools = 4 if quantized else 2  # k, v (+ their scales): HBM operands first, then the output, then their VMEM tiles
  pools_hbm, o_ref, pools_buf = refs[:n_pools], refs[n_pools], refs[n_pools + 1 : 2 * n_pools + 1]
  sem, slot_ref, m_ref, l_ref, acc_ref = refs[2 * n_pools + 1 :]
  k_buf, v_buf, ks_buf, vs_buf = (*pools_buf, None, None)[:4]
  value_buf = k_buf if latent else v_buf  # the latent body's value is the latent tile its scores read ("k"; "v" is the rope channel)
  n_rows, mp = bt_ref.shape
  n_heads = k_buf.shape[2]
  b = pl.program_id(0)
  layer = layer_ref[0]

  def tile_pages(row, tile):
    """Resident pages of one tile of a row (the clamp to mp keeps a length
    beyond the table inside it)."""
    return jnp.clip(jnp.minimum(pl.cdiv(len_ref[row], ps), mp) - tile * G, 0, G)

  # A layer with a window (``window`` > 0, static): the row's one query, at
  # position length - 1, sees the keys from ``seen_from`` on, so the walk
  # starts at the tile that holds that position, fetches from the page that
  # holds it and masks the positions before it. THE owner of the window's
  # tile arithmetic; every ``if W`` below is Python's, and ``window`` 0 traces
  # the kernel as it was.

  def seen_from(row):
    return jnp.maximum(len_ref[row] - W, 0)

  def first_tile(row):
    """The first tile a row's walk takes (held under the row's last: a length beyond the table)."""
    if not W:
      return 0
    last = jnp.maximum(pl.cdiv(jnp.minimum(pl.cdiv(len_ref[row], ps), mp), G) - 1, 0)
    return jnp.minimum(seen_from(row) // (G * ps), last)

  def first_page(row, tile):
    """The first page of a tile the window lets the query see: no DMA below it."""
    return jnp.clip(seen_from(row) // ps - tile * G, 0, G) if W else 0

  def tile_dmas(row, tile, slot, act):
    """Start or wait for (``act``) the DMAs of the resident pages of one
    tile: per page one copy of its [Hkv, ps, hd] codes for k and for v, and
    of its [Hkv, ps] scales when quantized (both padded to whole lanes), all
    on the slot's semaphore."""

    def page(j, carry):
      p = bt_ref[row, tile * G + j]
      for hbm, buf in zip(pools_hbm, pools_buf):
        act(pltpu.make_async_copy(hbm.at[layer, p], buf.at[slot, j], sem.at[slot]))
      return carry

    jax.lax.fori_loop(first_page(row, tile), tile_pages(row, tile), page, 0)

  def start(row, tile, slot):
    tile_dmas(row, tile, slot, lambda dma: dma.start())

  length = len_ref[b]
  n_tiles = pl.cdiv(jnp.minimum(pl.cdiv(length, ps), mp), G)

  @pl.when(b == 0)
  def _first_row():
    slot_ref[0] = 0
    if not quantized:
      # A fold multiplies probabilities 0 into the slot's pages that no tile
      # has fetched yet: float codes there must be finite (integer codes are).
      value_buf[...] = jnp.zeros_like(value_buf)

  first_slot = slot_ref[0]
  # The row before, if it held anything, started this row's first tile.
  prefetched = jnp.logical_and(b > 0, len_ref[jnp.maximum(b - 1, 0)] > 0)

  t0 = first_tile(b)

  @pl.when(jnp.logical_not(prefetched))
  def _fetch_first_tile():
    start(b, t0, first_slot)

  m_ref[...] = jnp.full_like(m_ref, NEG_INF)
  l_ref[...] = jnp.zeros_like(l_ref)
  acc_ref[...] = jnp.zeros_like(acc_ref)
  hd = q_ref.shape[-1]
  kd = None if latent else hd // 2 if packed else hd  # the lanes of a code tile that are codes, not padding (a latent's tiles are whole)
  # The score dot runs in q's dtype over quantized pages: int8 codes are
  # exact in bf16 and in f32, and products of bf16 pairs are exact in the
  # f32 accumulator. (The latent body's q is float32 — q_abs is a float32
  # product — so both of its dots run in float32 over the bfloat16 pages, as
  # the value dot of every body does: on the chip that costs what bfloat16
  # operands cost, 42.2 against 42.3 µs a call of 16 rows: PERF.md §5, PR 52.)
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
  # The latent body: the one cached head's group is every query head, q_abs
  # [H, rank] beside q_pe [H, lanes] — one left operand a tile of the key.
  group = q_ref.shape[1] // n_heads
  q = q_ref[0].astype(dot_dtype)
  qs = [q[h * group : (h + 1) * group] for h in range(n_heads)]
  rank = k_buf.shape[-1]  # (the latent body's; a GQA body's is hd)
  qs = [(x[:, :rank], x[:, rank:]) if latent else (x[:, :kd], x[:, kd:]) if packed else (x,) for x in qs]

  def fold_codes(buf, slot, h, F):
    """Kv head h's codes of the slot's first F pages, their tokens merged along sublanes: [F·ps, kd]."""
    return jnp.concatenate([buf[slot, j, h, :, :kd] for j in range(F)], axis=0)

  def key_operands(slot, h, F):
    """The score dots' right operands of kv head h over the slot's first F pages, one a left operand of ``qs[h]``:
    the latent body's key is the latent tile ‖ the rope tile."""
    if latent:
      return fold_codes(k_buf, slot, h, F), fold_codes(v_buf, slot, h, F)
    return code_halves(fold_codes(k_buf, slot, h, F))

  def value_operands(slot, h, F):
    """The value dot's: the latent body's value is the latent tile again, already in VMEM."""
    return (fold_codes(k_buf, slot, h, F),) if latent else code_halves(fold_codes(v_buf, slot, h, F))

  def fold_scales(buf, slot, F):
    """Every kv head's scales of the slot's first F pages, their tokens merged along lanes: [Hkv, F·ps]."""
    return jnp.concatenate([buf[slot, j, :, :ps] for j in range(F)], axis=1)

  def attend_fold(tile, slot, F):
    """Fold the first F pages of the slot's tile (F static, at least the
    pages the tile holds) into the online softmax in ONE update: per kv head
    one score product over the F pages' codes, [group, hd] × [F·ps, hd]ᵀ, one
    max / exp / sum over the stacked [Hq, F·ps] scores, per kv head one value
    product [group, F·ps] × [F·ps, hd] — whole 128-token MXU tiles and whole
    lane registers where a page alone (64 tokens) filled half of each, and
    one link of the m / l / acc chain where a page at a time made F (PERF.md
    §6, PR 43: a page cost 0.49 µs folded alone, 0.18 in a fold of 8). The
    heads' products stand side by side, in three phases, as before: head by
    head each dot waited for the softmax before it (PR 25). Pages of the fold
    that the row does not hold were never fetched — the slot holds what an
    earlier tile left there — and are masked by position with SELECTS: a
    stale scale lane may be anything, NaN too, and must not reach a product."""
    pos = tile * G * ps + jax.lax.broadcasted_iota(jnp.int32, (1, F * ps), 1)
    valid = jnp.logical_and(pos < length, pos >= length - W) if W else pos < length
    if quantized:
      ks = fold_scales(ks_buf, slot, F)  # (its stale lanes end in the scores' select)
      vs = jnp.where(valid, fold_scales(vs_buf, slot, F), 0.0)
    scores = []
    for h in range(n_heads):
      s = sum(
        jax.lax.dot_general(qx, kx.astype(dot_dtype), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        for qx, kx in zip(qs[h], key_operands(slot, h, F))
      ) * scale  # [group, F·ps]
      if quantized:
        # codes·scale = true k: the per-token scale multiplies each score
        # COLUMN. One scale covers the whole hd vector, so it applies after
        # both int4 halves.
        s = s * ks[h : h + 1]
      scores.append(s)
    s = jnp.where(valid, jnp.concatenate(scores, axis=0), NEG_INF)  # [Hq, F·ps]; a fetched tile holds at least one valid slot: the max stays finite
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
        ph = ph * vs[h : h + 1]  # v's scale folds into probs (after the l update)
      # packed: even half, then odd half
      upd.append(jnp.concatenate([jax.lax.dot_general(ph, vx.astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32) for vx in value_operands(slot, h, F)], axis=-1))
    acc_ref[...] = acc_ref[...] * alpha + jnp.concatenate(upd, axis=0)

  # The widths a tile's fold may take: the whole tile, or the half or the
  # quarter of it that still covers the pages a row's last tile holds — a
  # fold costs what its width costs, masked pages too (one page folded 8 wide
  # 1.5 µs a row, 2 wide 1.2, as the page alone did).
  folds = [G >> k for k in range(3) if G >> k]

  def tile_body(i, carry):
    slot = (first_slot + i - t0) % 2 if W else (first_slot + i) % 2

    @pl.when(i + 1 < n_tiles)
    def _fetch_next_tile():
      start(b, i + 1, 1 - slot)

    @pl.when(jnp.logical_and(i + 1 == n_tiles, b + 1 < n_rows))
    def _fetch_next_rows_first_tile():
      nxt = jnp.minimum(b + 1, n_rows - 1)
      start(nxt, first_tile(nxt), 1 - slot)

    tile_dmas(b, i, slot, lambda dma: dma.wait())
    n = tile_pages(b, i)  # ≥ 1: the loop runs over resident tiles
    for F, narrower in zip(folds, folds[1:] + [0]):
      pl.when(jnp.logical_and(n <= F, n > narrower))(functools.partial(attend_fold, i, slot, F))
    return carry

  jax.lax.fori_loop(t0, n_tiles, tile_body, 0)
  slot_ref[0] = (first_slot + n_tiles - t0) % 2 if W else (first_slot + n_tiles) % 2
  l = l_ref[...]
  o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _stored_kv_heads(k_pool, hd: int, quantized: bool) -> int:
  """The model's KV heads, read off a STORED code leaf and q's head width: a float row twice that wide is a pair of
  heads (the module note). A leaf in the kernel's form cannot say — its caller names the heads."""
  heads, lanes = jnp.shape(k_pool)[-3], jnp.shape(k_pool)[-1]
  return heads if quantized or lanes != 2 * hd else 2 * heads


def _under_odd_head(Hq: int, pairs: int) -> jnp.ndarray:
  """[Hq, 1] bool: the query heads whose KV head is the SECOND of its pair (the pair's query heads are contiguous, the
  even head's first)."""
  return (jnp.arange(Hq) // (Hq // (2 * pairs)) % 2 == 1)[:, None]


def _pair_queries(q: jnp.ndarray, pairs: int) -> jnp.ndarray:
  """q [B, Hq, hd] for a paired pool of ``pairs`` leaf heads (the module note): each query head zero-extended to the
  pair's lanes, [q, 0] under an even KV head and [0, q] under an odd one — [B, Hq, 2·hd]. The kernel's group of leaf
  head j is then the 2·Hq/Hkv query heads of both; a zero meets the other head's key of the same token, which is finite."""
  odd, zero = _under_odd_head(q.shape[1], pairs), jnp.zeros_like(q)
  return jnp.concatenate([jnp.where(odd, zero, q), jnp.where(odd, q, zero)], axis=-1)


def _own_halves(out: jnp.ndarray, pairs: int) -> jnp.ndarray:
  """Inverse of ``_pair_queries`` on the kernel's result [B, Hq, 2·hd]: each query head's own KV head's half of the
  value product (the other half is the pair's other head's values under this head's probabilities: dropped). Two
  slices and a select: picked head by head out of a reshape to [B, pairs, 2, group, 2·hd], XLA:TPU dropped the odd
  heads' lane offset and handed them the even half (my chip runs, PR 58; the CPU compiled it right)."""
  half = out.shape[-1] // 2
  return jnp.where(_under_odd_head(out.shape[1], pairs), out[..., half:], out[..., :half])


def _kernel_leaf(x: jnp.ndarray) -> jnp.ndarray:
  """A stacked pool leaf as the kernel's DMA takes it: Mosaic slices a page
  out of an HBM operand only along whole lanes, so the minor axis is padded
  to a multiple of 128 (a no-op for hd 128/256 codes and for paired heads of
  64, the module note; the sub-128 code axes that are left — int8 at hd 64,
  packed int4, MLA's rope leaf — pay a copy of the leaf), and a scale leaf
  [L, P, Hkv, ps, 1] puts its tokens on lanes first: [L, P, Hkv, ps → lanes].
  A leaf already in that form passes through untouched."""
  if x.ndim == 5 and x.shape[-1] == 1:
    x = x.reshape(x.shape[:-1])
  short = -x.shape[-1] % 128
  return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)]) if short else x


@component_scope("xot.kv_write")
def kernel_pool_form(pool: dict) -> dict:
  """The stacked pool in the kernel's form (``_kernel_leaf``), made ONCE a
  dispatch outside the step loop by the programs whose attention is the
  Pallas kernel: the token writes and the kernel's reads then share one
  buffer per leaf from the first step to the last. The identity for code
  leaves of whole lanes (int8/bf16 at hd 128/256, paired heads of 64: the
  module note); the scale leaves (3 % of an int8 pool) are relaid, and code
  leaves under 128 lanes copied, once."""
  return {name: leaf if name in STATE_LEAVES else _kernel_leaf(leaf) for name, leaf in pool.items()}


@component_scope("xot.kv_write")
def stored_pool_form(pool: dict, like: dict) -> dict:
  """Inverse of ``kernel_pool_form``: back to the shapes of ``like`` (the
  pool as ``init_paged_pool`` lays it out), at the program's end."""
  out = {}
  for name, leaf in pool.items():
    want = like[name].shape  # (a per-slot state leaf has one form: it passes through)
    if leaf.ndim < len(want):  # lane-dense scales → [L, P, Hkv, ps, 1]
      leaf = leaf[..., : want[-2], None]
    out[name] = leaf if leaf.shape == want else leaf[..., : want[-1]]
  return out


@component_scope("xot.attn")
def paged_decode_attention(
  q, k_pool, v_pool, block_tables, lengths, page_size: int,
  k_scale_pool=None, v_scale_pool=None, pages_per_step: int | None = None, interpret: bool = False, layer=None, kv_quant: str | None = None, window: int = 0,
  kv_heads: int | None = None,
):
  """Decode attention off the page pool (dense GQA models).

  q [B, Hq, hd] (the single new token per row); k/v pool the stacked leaves
  [L, P, Hkv, ps, hd] read at ``layer`` (a traced scalar; ``layer`` None: one
  layer's [P, Hkv, ps, hd]); block_tables [B, mp] int32 (entries past a
  row's ``lengths`` may hold anything — they are never read); lengths [B]
  int32 = number of valid KV slots INCLUDING the token just written. With
  ``k_scale_pool``/``v_scale_pool`` [L, P, Hkv, ps, 1]
  (int8-KV pools — init_paged_pool quant="int8"), k/v hold int8 codes
  dequantized in-register per page; a pool whose code axis is HALVED
  ([…, ps, hd/2] — init_paged_pool quant="int4") holds packed int4
  nibbles dequantized via the two-dot split (module note above). Leaves in
  the kernel's form (``kernel_pool_form``) are taken as they are — their
  padded code axis no longer tells int8 from packed int4, so their caller
  names the mode (``kv_quant``: "", "int8", "int4"; None reads it off stored
  shapes) and, for a float pool, the model's KV heads (``kv_heads``: leaves of
  half as many hold them paired, the module note; None reads that off stored
  shapes too); stored leaves that need it are converted per call — a copy of
  the leaf, which a program with a layer loop must make outside it.
  ``pages_per_step`` (static) overrides the tile (``PAGE_TILE`` clamped to
  the table's width: the same rule for a layer with a window and one
  without). ``window`` (static; 0: none): the layer's window — the row's
  query sees its last ``window`` positions only, and the pages wholly before
  them are neither fetched nor folded; such a call is named
  ``paged_decode_window`` in the trace. Returns [B, Hq, hd].
  """
  if (k_scale_pool is None) != (v_scale_pool is None):
    raise ValueError("paged_decode_attention: k_scale_pool and v_scale_pool must be passed together")
  pools = [_stacked(x, layer) for x in (k_pool, v_pool, k_scale_pool, v_scale_pool) if x is not None]
  layer = jnp.asarray(pools[0][1], jnp.int32).reshape(1)
  if kv_quant is None:
    kv_quant = "" if k_scale_pool is None else "int4" if jnp.shape(k_pool)[-1] * 2 == jnp.shape(q)[-1] else "int8"
  if kv_heads is None:
    kv_heads = _stored_kv_heads(k_pool, jnp.shape(q)[-1], bool(kv_quant))
  G = pages_per_step or _page_tile(jnp.shape(block_tables)[1])
  return _paged_decode_attention_impl(
    q, block_tables, lengths, layer, *(x for x, _ in pools),
    page_size=page_size, pages_per_step=G, kv_quant=kv_quant, interpret=interpret, window=int(window), paired=jnp.shape(k_pool)[-3] * 2 == kv_heads,
  )


@component_scope("xot.attn")
def paged_latent_decode_attention(q_nope, q_pe, k_pool, v_pool, block_tables, lengths, w_kv_b, v_dim: int, page_size: int, layer=None, pages_per_step: int | None = None, interpret: bool = False) -> jnp.ndarray:
  """Absorbed-MLA decode attention off the page pool: ``paged_mla_attention_ref``'s operands and result (q_nope
  [B, 1, H, nope], q_pe [B, 1, H, rope] roped; "k" the latent leaf [L, P, 1, ps, rank], "v" the rope leaf
  [L, P, 1, ps, rope], stored or in the kernel's form; → [B, 1, H, v_dim]), read by the kernel's latent body — the
  pages each row holds, once, where the reference gathers the table's whole width. The two absorbed products stay in
  XLA on either side of the call (``ops/attention.py mla_absorb``): q_abs = q_nope · W_k goes in beside q_pe (padded to
  the rope leaf's lanes, whose padding is zeros), and out = (Σ p·latent) · W_v comes after it."""
  from .attention import mla_absorb

  nope, rope = q_nope.shape[-1], q_pe.shape[-1]
  q_abs, w_v = mla_absorb(q_nope, w_kv_b, v_dim)
  q = jnp.concatenate([q_abs[:, 0], jnp.pad(q_pe[:, 0].astype(jnp.float32), [(0, 0), (0, 0), (0, -rope % 128)])], axis=-1)  # [B, H, rank + lanes]
  (k_pool, layer), (v_pool, _) = _stacked(k_pool, layer), _stacked(v_pool, layer)
  ctx = _paged_decode_attention_impl(
    q, block_tables, lengths, jnp.asarray(layer, jnp.int32).reshape(1), k_pool, v_pool,
    page_size=page_size, pages_per_step=pages_per_step or _page_tile(jnp.shape(block_tables)[1]), kv_quant="", interpret=interpret, latent_scale=float((nope + rope) ** -0.5),
  )  # [B, H, rank] float32
  return jnp.einsum("bhr,rhv->bhv", ctx, w_v)[:, None].astype(q_nope.dtype)


@functools.partial(tracked_jit, "ops.paged_attention", static_argnames=("page_size", "pages_per_step", "kv_quant", "interpret", "window", "latent_scale", "paired"))
def _paged_decode_attention_impl(q, block_tables, lengths, layer, *pools, page_size: int, pages_per_step: int, kv_quant: str, interpret: bool, window: int = 0, latent_scale: float = 0.0, paired: bool = False):
  """``latent_scale`` > 0 (static): the latent body — q is q_abs ‖ q_pe [B, H, rank + lanes], the pools the latent and
  the rope leaf, the result Σ p·latent [B, H, rank], and the scores' scale this, the model's (nope + rope)^-1/2, which
  the operand's width says nothing of. ``paired`` (static): the pools hold two KV heads a leaf head (the module note) —
  the kernel is handed the pairs as heads of twice the width, q extended to them, under the scale of q's own width."""
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  packed = kv_quant == "int4"
  B, Hq, hd = q.shape
  G = pages_per_step
  scale = latent_scale or float(1.0 / (hd**0.5))
  if packed:
    # Deinterleave q once outside the kernel (even channels first, odd
    # second) so the in-kernel two-dot uses contiguous halves; the output
    # comes back in the same layout and is re-interleaved below.
    q = jnp.concatenate([q[..., 0::2], q[..., 1::2]], axis=-1)
  if paired:
    q, hd = _pair_queries(q, pools[0].shape[2]), 2 * hd

  in_hbm = pl.BlockSpec(memory_space=pl.ANY)
  pools = [_kernel_leaf(x) for x in pools]  # k, v (+ their scales), stacked: [L, P, Hkv, ps, lanes] / [L, P, Hkv, lanes]
  out_dim = pools[0].shape[-1] if latent_scale else hd
  row_block = lambda width: pl.BlockSpec((1, Hq, width), lambda b, bt, ln, ly: (b, 0, 0))  # noqa: E731
  if not interpret:  # (the interpreter cannot slice an array that carries a memory space)
    # In HBM by constraint: left to XLA, a scale leaf (34 MB of Mistral's pool) is copied into VMEM before every call, once a layer.
    pools = [pltpu.with_memory_space_constraint(x, pltpu.HBM) for x in pools]
  scratch = [pltpu.VMEM((2, G, *x.shape[2:]), x.dtype) for x in pools]  # two slots of G pages
  tile_bytes = sum(2 * G * math.prod(x.shape[2:]) * x.dtype.itemsize for x in pools)  # wide tiles of wide pages pass the default 16 MiB
  scratch += [
    pltpu.SemaphoreType.DMA((2,)),
    pltpu.SMEM((1,), jnp.int32),  # the slot the next tile goes to, across rows
    pltpu.VMEM((Hq, 1), jnp.float32),  # running max, sum and accumulator of every query head
    pltpu.VMEM((Hq, 1), jnp.float32),
    pltpu.VMEM((Hq, out_dim), jnp.float32),
  ]
  grid_spec = pltpu.PrefetchScalarGridSpec(
    num_scalar_prefetch=3,
    grid=(B,),
    in_specs=[row_block(hd)] + [in_hbm] * len(pools),
    out_specs=row_block(out_dim),
    scratch_shapes=scratch,
  )
  out = pl.pallas_call(
    functools.partial(_paged_decode_kernel, page_size=page_size, scale=scale, pages_per_step=G, kv_quant=kv_quant, **({"window": window} if window else {}), **({"latent": True} if latent_scale else {})),
    out_shape=jax.ShapeDtypeStruct((B, Hq, out_dim), q.dtype),
    grid_spec=grid_spec,
    # Rows in order: the prefetch chain crosses them.
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=tile_bytes + (16 << 20)),
    interpret=interpret,
    **({"name": "paged_decode_window"} if window else {"name": "paged_decode_latent"} if latent_scale else {}),  # read apart in a trace; the GQA call without a window keeps the name it had
  )(block_tables, lengths, layer, q, *pools)
  if packed:
    # Undo the deinterleave: channel 2i from the even half, 2i+1 from the odd half.
    out = jnp.stack([out[..., : hd // 2], out[..., hd // 2 :]], axis=-1).reshape(B, Hq, hd)
  return _own_halves(out, pools[0].shape[2]) if paired else out


def paged_kernel_supported(cfg, platform: str | None = None) -> bool:
  """Whether a paged program's attention core is the Pallas kernel: wherever
  it can run — a TPU, plain attention (``cfg.plain_attention``: no softcap,
  no scale override, no window that rides a traced flag; a window that is
  static per layer kind is the kernel's operand), a head width the kernel
  tiles (MLA: a latent its latent body tiles) — and ``XOT_TPU_NO_FLASH`` is
  unset. It is what the decode programs resolve ``use_kernel=None`` to — the
  recurrent layers' Mosaic state steps (ops/ssm.py) ride the same answer —
  and what the scheduler labels its chunks by; everything else takes the XLA
  gather."""
  return _mosaic_platform(cfg, platform) and cfg.plain_attention and (_latent_body_tiles(cfg) if cfg.is_mla else cfg.head_dim in (64, 128, 256))


def _latent_body_tiles(cfg) -> bool:
  """Absorbed MLA is the kernel's latent body where the cached "head" tiles: a latent of whole lane groups (it is a
  DMA's minor axis and the result's) and a rope channel of at most one (``kernel_pool_form`` pads it to 128 lanes)."""
  return cfg.is_mla and cfg.kv_lora_rank % 128 == 0 and cfg.qk_rope_head_dim <= 128


def _mosaic_platform(cfg, platform: str | None = None) -> bool:
  """A TPU whose plan leaves the Mosaic kernels in (``cfg.mosaic_kernels``), and ``XOT_TPU_NO_FLASH`` unset."""
  import os

  return not os.getenv("XOT_TPU_NO_FLASH") and (platform or jax.default_backend()) == "tpu" and cfg.mosaic_kernels


def kernel_attends(cfg, use_kernel) -> bool:
  """Whether a paged program told ``use_kernel`` attends through the Pallas
  kernel: the one test of the layer steps, the token write and the pool-form
  conversion. The kernel has no softcap and takes a window only as a static
  operand (``cfg.plain_attention``), and absorbed MLA is its latent body
  where that tiles the model, so a model outside that takes the gather
  whatever it was told."""
  return bool(use_kernel) and cfg.plain_attention and (not cfg.is_mla or _latent_body_tiles(cfg))
