"""Paged KV cache tests (ops/paged.py, inference/paging.py, scheduler).

Correctness claims:
- the Pallas paged-decode kernel == the gather reference (interpret mode);
- paged prefill/decode are token-identical to the dense slot-pool paths;
- prefix-cached admission (skipping cached prompt pages) is exact;
- the allocator's free list / refcounts / LRU eviction behave;
- the scheduler serves MORE aggregate context than a dense layout of the
  same memory could (the point of paging), and parks page-starved
  admissions instead of failing them.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.inference.paging import PageAllocator
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_batch_decode,
  fused_paged_batch_decode,
  init_kv_cache,
  prefill_into_pages,
  prefill_into_pages_many,
  prefill_into_slot,
  prefill_into_slots,
)
from xotorch_support_jetson_tpu.ops.paged import (
  init_paged_pool,
  kernel_pool_form,
  paged_decode_attention,
  paged_gqa_attention_ref,
  paged_latent_decode_attention,
  paged_mla_attention_ref,
)

CFG = tiny_test_config(n_layers=2, max_seq_len=128)
KEY = jax.random.PRNGKey(0)
PS = 16  # page size for tests


def test_paged_kernel_matches_gather_reference():
  rng = np.random.default_rng(0)
  B, Hq, Hkv, hd, ps, P = 2, 8, 4, 64, 8, 12
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32)
  vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32)
  bt = jnp.asarray([[3, 5, 7, 0], [1, 2, 0, 0]], jnp.int32)  # ragged rows
  lengths = jnp.asarray([19, 9], jnp.int32)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lengths, ps)[:, 0]
  ker = paged_decode_attention(q, kp, vp, bt, lengths, ps, interpret=True)
  assert jnp.allclose(ref, ker, atol=1e-5)


@pytest.mark.parametrize("pages_per_step", [1, 2, 4])
def test_paged_kernel_page_tile_geometry_matches_reference(pages_per_step):
  """Every page-tile width (including tiles that do not divide mp — a row's
  last tile holds only the pages the row has) gives the same output as the
  single-page gather reference."""
  rng = np.random.default_rng(5)
  B, Hq, Hkv, hd, ps, P = 2, 4, 2, 64, 8, 16
  mp = 6  # deliberately not a multiple of 4
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp = jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32)
  vp = jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32)
  bt = jnp.asarray([[3, 5, 7, 9, 11, 0], [1, 2, 4, 0, 0, 0]], jnp.int32)
  lengths = jnp.asarray([5 * ps - 3, 2 * ps + 1], jnp.int32)  # page-boundary crossings
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lengths, ps)[:, 0]
  ker = paged_decode_attention(q, kp, vp, bt, lengths, ps, pages_per_step=pages_per_step, interpret=True)
  assert jnp.allclose(ref, ker, atol=1e-5), f"page tile {pages_per_step} diverges"


def test_paged_kernel_int8kv_dequant_matches_gather_reference():
  """int8-KV pools through the kernel (in-register dequant) == the gather
  reference consuming the same codes + scale pools."""
  rng = np.random.default_rng(9)
  B, Hq, Hkv, hd, ps, P = 2, 4, 2, 64, 8, 10
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp = jnp.asarray(rng.integers(-127, 128, size=(P, Hkv, ps, hd)), jnp.int8)
  vp = jnp.asarray(rng.integers(-127, 128, size=(P, Hkv, ps, hd)), jnp.int8)
  ks = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, Hkv, ps, 1)), jnp.float32)
  vs = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, Hkv, ps, 1)), jnp.float32)
  bt = jnp.asarray([[3, 5, 7, 0], [1, 2, 0, 0]], jnp.int32)
  lengths = jnp.asarray([3 * ps - 2, ps + 3], jnp.int32)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lengths, ps, k_scale_pool=ks, v_scale_pool=vs)[:, 0]
  for g in (1, 2):
    ker = paged_decode_attention(q, kp, vp, bt, lengths, ps, k_scale_pool=ks, v_scale_pool=vs, pages_per_step=g, interpret=True)
    assert jnp.allclose(ref, ker, atol=1e-5), f"int8 kernel (tile {g}) diverges"
  with pytest.raises(ValueError):
    paged_decode_attention(q, kp, vp, bt, lengths, ps, k_scale_pool=ks, interpret=True)


def _kernel_case_pools(rng, quant: str, P: int, Hkv: int, ps: int, hd: int):
  """(k, v, scale kwargs) of a page pool in one of the three stored forms."""
  if quant == "":
    return jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.bfloat16), jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.bfloat16), {}
  if quant == "int8":
    codes = lambda: jnp.asarray(rng.integers(-127, 128, size=(P, Hkv, ps, hd)), jnp.int8)  # noqa: E731
    scales = lambda: jnp.asarray(rng.uniform(0.005, 0.02, size=(P, Hkv, ps, 1)), jnp.float32)  # noqa: E731
    return codes(), codes(), {"k_scale_pool": scales(), "v_scale_pool": scales()}
  from xotorch_support_jetson_tpu.models.quantize import quantize_kv_int4

  kp, ks = quantize_kv_int4(jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32))
  vp, vs = quantize_kv_int4(jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32))
  return kp, vp, {"k_scale_pool": ks, "v_scale_pool": vs}


_RAGGED_PS, _RAGGED_MP, _RAGGED_TILE = 8, 6, 2
_RAGGED_ROWS = {  # the middle row of three; its neighbours hold ordinary contexts
  "empty": 0,  # does nothing, and the row after it fetches its own first tile
  "one_token": 1,
  "page_boundary": 2 * _RAGGED_PS,  # the last page is full: no page after it is touched
  "partial_tile": 3 * _RAGGED_PS - 2,  # not a multiple of tile × page_size: the last tile holds one page
  "all_pages": _RAGGED_MP * _RAGGED_PS,  # the whole block table
}


@pytest.mark.parametrize("row", list(_RAGGED_ROWS))
@pytest.mark.parametrize("quant", ["", "int8", "int4"])
def test_paged_kernel_ragged_rows_match_reference(quant, row):
  """The kernel's loop is bounded by each row's own length: rows of very
  different occupancy in one batch, for every stored form of the pool,
  equal the gather reference; a row of length 0 comes back as zeros."""
  rng = np.random.default_rng(41)
  B, Hq, Hkv, hd, ps, mp, P = 3, 4, 2, 64, _RAGGED_PS, _RAGGED_MP, 24
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp, vp, scales = _kernel_case_pools(rng, quant, P, Hkv, ps, hd)
  bt = jnp.asarray(1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp))
  lens = np.asarray([ps + 3, _RAGGED_ROWS[row], 4 * ps - 1], np.int32)
  ker = np.asarray(paged_decode_attention(q, kp, vp, bt, jnp.asarray(lens), ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales))
  ref = np.asarray(paged_gqa_attention_ref(q[:, None], kp, vp, bt, jnp.asarray(np.maximum(lens, 1)), ps, **scales)[:, 0])
  live = lens > 0
  assert np.allclose(ker[live], ref[live], atol=2e-5), np.abs(ker[live] - ref[live]).max()
  assert not ker[~live].any()


_FOLD_PS = 8
_FOLD_ROWS = {  # length of the middle row of three, from the tile width G: one softmax update folds a whole tile (PR 43)
  "page-1": lambda g, ps: ps - 1,
  "page": lambda g, ps: ps,
  "page+1": lambda g, ps: ps + 1,  # a fold of one page and one token: the other columns hold pages never fetched
  "tile-1": lambda g, ps: g * ps - 1,
  "tile": lambda g, ps: g * ps,  # the fold is full: no masked column
  "tile+1": lambda g, ps: g * ps + 1,  # the second fold holds one token beside the first tile's stale pages
  "past_two_tiles": lambda g, ps: 2 * g * ps + ps + 3,
  "empty_between_long": lambda g, ps: 0,  # the slots keep the longer row's pages; the row after fetches its own first tile
}


def check_fold_boundary_case(quant: str, hd: int, g: int, row: str):
  """Three rows around the fold's boundaries, the kernel against the gather
  reference (``test_paged_int4.py`` runs the packed cases). The kernel reads a POISONED pool: every page no row
  holds (the trash page 0, which the table's entries past a row's length
  name, among them) and every slot of a row's last page past its length
  carry NaN scales and ±127 codes (NaN keys and, in the free pages, NaN
  values in a bf16 pool) — so the tile slot that a long row leaves behind
  holds NaN scale lanes where the next row's fold is masked; the reference
  reads the same pool with all of that zeroed."""
  rng = np.random.default_rng(43)
  B, Hq, Hkv, ps = 3, 4, 2, _FOLD_PS
  mp = 2 * g + 2
  P = 1 + B * mp
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp, vp, scales = _kernel_case_pools(rng, quant, P, Hkv, ps, hd)
  lens = np.asarray([2 * g * ps + 5, _FOLD_ROWS[row](g, ps), g * ps + ps + 2], np.int32)
  bt = 1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp)
  held, tail = np.zeros(P, bool), np.zeros((P, 1, ps, 1), bool)
  for r in range(B):
    n = -(-int(lens[r]) // ps)
    held[bt[r, :n]] = True
    if lens[r] % ps:
      tail[bt[r, n - 1], 0, lens[r] % ps :] = True  # the slots of a row's last page that it has not written yet
    bt[r, n:] = 0
  free = jnp.asarray(~held)[:, None, None, None]
  unwritten = free | jnp.asarray(tail)
  sign = jnp.asarray(rng.choice([-127, 127], size=kp.shape))

  def poisoned(x, where, bad):
    return jnp.where(where, jnp.asarray(bad, x.dtype), x), jnp.where(where, jnp.zeros((), x.dtype), x)

  kp_bad, kp_ok = poisoned(kp, unwritten, sign if quant else jnp.nan)
  # (float values a row has not written are probabilities 0 × whatever lies there, in the kernel as in the reference: those stay finite)
  vp_bad, vp_ok = poisoned(vp, unwritten if quant else free, sign if quant else jnp.nan)
  bad, ok = {}, {}
  for name, x in scales.items():
    bad[name], ok[name] = poisoned(x, unwritten, jnp.nan)
  ker = np.asarray(paged_decode_attention(q, kp_bad, vp_bad, jnp.asarray(bt), jnp.asarray(lens), ps, pages_per_step=g, interpret=True, **bad))
  ref = np.asarray(paged_gqa_attention_ref(q[:, None], kp_ok, vp_ok, jnp.asarray(bt), jnp.asarray(np.maximum(lens, 1)), ps, **ok)[:, 0])
  live = lens > 0
  assert np.isfinite(ker).all()
  assert np.allclose(ker[live], ref[live], atol=2e-5), np.abs(ker[live] - ref[live]).max()
  assert not ker[~live].any()


@pytest.mark.parametrize("row", list(_FOLD_ROWS))
@pytest.mark.parametrize("g", [4, 8])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("quant", ["", "int8"])
def test_paged_kernel_fold_boundaries_match_reference(quant, hd, g, row):
  """One softmax update takes a tile of pages: lengths that end one token
  before, on and one token after a page and a tile, a length past two tiles
  and an empty row between two long ones equal the gather reference, and
  nothing a row does not hold — a page never fetched, a stale scale lane, a
  slot the row before left — reaches its result (no NaN, no ±127 code)."""
  check_fold_boundary_case(quant, hd, g, row)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (6, 2), (8, 1)])
def test_paged_kernel_head_groupings_match_reference(hq, hkv):
  """The kernel stacks every query head's scores for one softmax update and
  hands each kv head its own group's rows: MHA (group 1), a group that is
  not a power of two, and MQA equal the gather reference."""
  rng = np.random.default_rng(47)
  B, hd, ps, mp, P = 2, 64, _RAGGED_PS, _RAGGED_MP, 16
  q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.float32)
  kp, vp, scales = _kernel_case_pools(rng, "int8", P, hkv, ps, hd)
  bt = jnp.asarray(1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp))
  lens = jnp.asarray([3 * ps + 5, ps - 1], jnp.int32)
  ker = paged_decode_attention(q, kp, vp, bt, lens, ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lens, ps, **scales)[:, 0]
  assert jnp.allclose(ker, ref, atol=2e-5), jnp.abs(ker - ref).max()


@pytest.mark.parametrize("quant", ["", "int8", "int4"])
def test_paged_kernel_never_reads_past_a_rows_length(quant):
  """Block-table entries past a row's length point at a page whose codes
  and scales are poison (NaN where the dtype has one): nothing changes,
  because neither the entry nor the page is ever fetched."""
  rng = np.random.default_rng(43)
  B, Hq, Hkv, hd, ps, mp, P = 3, 4, 2, 64, _RAGGED_PS, _RAGGED_MP, 24
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp, vp, scales = _kernel_case_pools(rng, quant, P, Hkv, ps, hd)
  poison = P - 1
  bad = lambda x: x.at[poison].set(jnp.nan if jnp.issubdtype(x.dtype, jnp.floating) else 127)  # noqa: E731
  kp, vp, scales = bad(kp), bad(vp), {name: bad(x) for name, x in scales.items()}
  lens = np.asarray([ps + 3, 0, 2 * ps], np.int32)  # a partial page, an empty row, a full last page
  clean = 1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp)
  held = np.arange(mp)[None, :] * ps < lens[:, None]
  run = lambda table: np.asarray(paged_decode_attention(q, kp, vp, jnp.asarray(table), jnp.asarray(lens), ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales))  # noqa: E731
  got = run(np.where(held, clean, poison).astype(np.int32))
  assert np.isfinite(got).all()
  assert np.array_equal(got, run(clean))


# ------------------------------------------------- the kernel's latent body (absorbed MLA)


def _latent_case(rng, H: int, lens, ps: int, mp: int, rank: int = 512, rope: int = 64, nope: int = 32, v_dim: int = 32, layers: int = 3, dtype=jnp.bfloat16):
  """Operands of one call at ``layer`` 2 of stacked leaves: rows of ``lens`` tokens whose table entries past their
  length name a page that is NaN in every layer (it must never be read), and beside them what the gather reference
  may read — the same table with those entries on the trash page, the same pool with that page zeroed."""
  B, lens = len(lens), np.asarray(lens, np.int32)
  held = [-(-int(n) // ps) for n in lens]
  poison = 1 + sum(held)
  bt, nxt = np.full((B, mp), poison, np.int32), 1
  for r, n in enumerate(held):
    bt[r, :n] = range(nxt, nxt + n)
    nxt += n
  k = jnp.asarray(rng.normal(size=(layers, poison + 1, 1, ps, rank)), dtype)
  v = jnp.asarray(rng.normal(size=(layers, poison + 1, 1, ps, rope)), dtype)
  q_nope, q_pe = jnp.asarray(rng.normal(size=(B, 1, H, nope)), jnp.float32), jnp.asarray(rng.normal(size=(B, 1, H, rope)), jnp.float32)
  w_kv_b = jnp.asarray(rng.normal(size=(rank, H * (nope + v_dim))) / rank**0.5, jnp.float32)
  rest = (w_kv_b, v_dim, ps)
  seen = (q_nope, q_pe, k.at[:, poison].set(jnp.nan), v.at[:, poison].set(jnp.nan), jnp.asarray(bt), jnp.asarray(lens), *rest)
  clean = (q_nope, q_pe, k.at[:, poison].set(0), v.at[:, poison].set(0), jnp.asarray(np.where(bt == poison, 0, bt)), jnp.asarray(np.maximum(lens, 1)), *rest)
  return seen, clean, lens > 0


# Both products take float32 operands over pages of either dtype (q_abs is a float32 product; the probabilities stay
# float32): the body and the reference differ by the order of their sums. A page or a mask wrong reads 0.1-1.
_LATENT_ATOL = 2e-5


@pytest.mark.parametrize("H", [16, 32], ids=["moonlight-16-heads", "ling-32-heads"])
def test_latent_body_matches_the_gather_reference_at_the_cells_widths(H):
  """The latent body against ``paged_mla_attention_ref`` at the two cells' head counts, rank 512 / rope 64, pages of
  64 and a table of 64: rows of 1, 7, 9 and 64 pages and a row of length 0 in one call, ``layer`` 2 of stacked
  leaves, every table entry past a row's length naming a NaN page."""
  ps = mp = 64
  seen, clean, live = _latent_case(np.random.default_rng(51), H, [ps - 3, 7 * ps, 0, 9 * ps + 5, 64 * ps], ps, mp)
  got = np.asarray(paged_latent_decode_attention(*seen, layer=jnp.int32(2), interpret=True))
  ref = np.asarray(paged_mla_attention_ref(*clean, layer=jnp.int32(2)))
  assert got.shape == ref.shape == (5, 1, H, 32) and np.isfinite(got).all()
  assert np.allclose(got[live], ref[live], atol=_LATENT_ATOL), np.abs(got[live] - ref[live]).max()
  assert not got[~live].any()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("tile", [1, 2, 4, 8])
def test_latent_body_tile_widths_and_fold_boundaries_match_reference(tile, dtype):
  """Every tile width over lengths that end one token before, on and one after a page and a tile, past two tiles,
  and an empty row between two long ones (its slots keep the longer row's pages; the row after fetches its own first
  tile) — small pages of a 128-wide latent and a 16-wide rope channel, which the kernel's form pads to a lane group."""
  ps, mp, g = 8, 20, tile
  lens = [2 * g * ps + 5, 0, ps - 1, ps, ps + 1, g * ps - 1, g * ps, g * ps + 1, 2 * g * ps + ps + 3, 1]
  seen, clean, live = _latent_case(np.random.default_rng(53), 6, lens, ps, mp, rank=128, rope=16, nope=8, v_dim=8, dtype=dtype)
  got = np.asarray(paged_latent_decode_attention(*seen, layer=jnp.int32(2), pages_per_step=g, interpret=True))
  ref = np.asarray(paged_mla_attention_ref(*clean, layer=jnp.int32(2)))
  assert np.isfinite(got).all() and np.allclose(got[live], ref[live], atol=_LATENT_ATOL), np.abs(got[live] - ref[live]).max()
  assert not got[~live].any()


def test_latent_body_takes_the_leaves_stored_or_in_the_kernels_form():
  """A decode program hands the kernel the pool in the kernel's form (the rope leaf padded to a lane group once a
  dispatch); a direct caller the stored leaves, padded per call: the same bits. A single layer's leaves are a stack of one."""
  ps, mp = 8, 6
  seen, _, _ = _latent_case(np.random.default_rng(55), 4, [3 * ps + 2, ps], ps, mp, rank=128, rope=16, nope=8, v_dim=8)
  q_nope, q_pe, k, v, *rest = seen
  form = kernel_pool_form({"k": k, "v": v})
  assert form["k"].shape == k.shape and form["v"].shape == (*v.shape[:-1], 128)
  stored = np.asarray(paged_latent_decode_attention(*seen, layer=jnp.int32(1), interpret=True))
  assert np.array_equal(stored, np.asarray(paged_latent_decode_attention(q_nope, q_pe, form["k"], form["v"], *rest, layer=jnp.int32(1), interpret=True)))
  assert np.array_equal(stored, np.asarray(paged_latent_decode_attention(q_nope, q_pe, k[1], v[1], *rest, interpret=True)))


def test_latent_body_scales_by_the_models_head_width_not_the_operands():
  """The scores' scale is (nope + rope)^-1/2 — the kernel's operand is rank + 128 lanes wide, which says nothing of it:
  the same latents under a wider nope give other probabilities, and the reference agrees at both."""
  ps, mp = 8, 4
  for nope in (8, 64):
    seen, clean, _ = _latent_case(np.random.default_rng(57), 4, [2 * ps + 3, ps], ps, mp, rank=128, rope=16, nope=nope, v_dim=8, dtype=jnp.float32)
    got, ref = paged_latent_decode_attention(*seen, interpret=True, layer=jnp.int32(0)), paged_mla_attention_ref(*clean, layer=jnp.int32(0))
    assert np.allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_latent_model_decodes_the_same_tokens_through_the_kernel_and_the_gather(interpreted_paged_kernels):
  """A small MLA model whose latent the kernel tiles (rank 128): ``fused_paged_batch_decode`` told ``use_kernel``
  (the latent body and the Mosaic token write, interpreted, on the pool in the kernel's form) and told not (the
  gather reference) emit the same greedy tokens and leave the same pool, an inactive row and a page boundary included."""
  from xotorch_support_jetson_tpu.ops.paged import kernel_attends

  cfg = tiny_test_config(n_layers=2, max_seq_len=128, n_heads=4, n_kv_heads=4, kv_lora_rank=128, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
  assert kernel_attends(cfg, True)
  params, shard = full_model_params(KEY, cfg)
  mp, n_slots = 128 // PS, 3
  prompts = [[3, 25, 9], list(range(5, 5 + PS - 2)), [100]]  # the second row's decode crosses into a fresh page
  bt = np.arange(1, 1 + n_slots * mp, dtype=np.int32).reshape(n_slots, mp)
  firsts = []
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + n_slots * mp, PS)
  for r, p in enumerate(prompts):
    pad = np.zeros((1, 16), np.int32)
    pad[0, : len(p)] = p
    last, pool = prefill_into_pages(params, cfg, shard, jnp.asarray(pad), pool, jnp.asarray(bt[r]), jnp.int32(0), jnp.int32(len(p)), PS)
    firsts.append(int(np.argmax(np.asarray(last)[0])))
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions, active = jnp.asarray([len(p) for p in prompts], jnp.int32), jnp.asarray([True, True, False])
  run = lambda use_kernel: fused_paged_batch_decode(params, cfg, shard, tok, jax.tree.map(jnp.copy, pool), jnp.asarray(bt), positions, active, jnp.zeros((n_slots,), jnp.float32), 10, page_size=PS, use_kernel=use_kernel)  # noqa: E731  (the pool is donated)
  ref, got = run(False), run(True)
  assert interpreted_paged_kernels and np.array_equal(np.asarray(got[0])[:2], np.asarray(ref[0])[:2]) and np.array_equal(np.asarray(got[2]), np.asarray(ref[2]))
  for name in ("k", "v"):  # the same tokens written to the same slots, in the stored form
    assert got[3][name].shape == ref[3][name].shape and np.allclose(np.asarray(got[3][name])[:, 1:], np.asarray(ref[3][name])[:, 1:], atol=1e-5)


# The one owner of "which attention core does a paged program run" (ops/paged.py):
# name -> (config overrides, the kernel can run for it on a TPU, it attends through the kernel when told to).
_MLA = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, family="deepseek-v2")
_OWNER_CONFIGS = {
  "gqa-hd64": (dict(head_dim=64), True, True),
  "gqa-hd128": (dict(head_dim=128), True, True),
  "gqa-hd256": (dict(head_dim=256), True, True),
  "gqa-hd96": (dict(head_dim=96), False, True),  # no tiling for that width on the chip; interpret-mode tests may still ask for it
  "softcap": (dict(head_dim=64, attn_logit_softcap=30.0), False, False),
  "window": (dict(head_dim=64, sliding_window=32), False, False),
  "mla": (_MLA, False, False),  # a latent of 16: no whole lane group for the latent body to tile
  "mla-rank128": (dict(_MLA, kv_lora_rank=128), True, True),  # the latent body (absorbed MLA)
  "mla-rope256": (dict(_MLA, kv_lora_rank=128, qk_rope_head_dim=256), False, False),  # a rope channel past one lane group
}


@pytest.mark.parametrize("no_flash", [False, True], ids=["flash", "XOT_TPU_NO_FLASH"])
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("name", list(_OWNER_CONFIGS))
def test_paged_kernel_supported_is_the_resolver(monkeypatch, name, platform, no_flash):
  """The kernel wherever it can run — a TPU, plain GQA attention at a head
  width it tiles or latent attention its latent body tiles, ``XOT_TPU_NO_FLASH``
  unset — and the gather elsewhere; nothing else (batch, context, KV mode) has a say."""
  from xotorch_support_jetson_tpu.ops.paged import paged_kernel_supported

  overrides, can_run, _ = _OWNER_CONFIGS[name]
  monkeypatch.delenv("XOT_TPU_NO_FLASH", raising=False)
  if no_flash:
    monkeypatch.setenv("XOT_TPU_NO_FLASH", "1")
  assert paged_kernel_supported(tiny_test_config(**overrides), platform=platform) is (can_run and platform == "tpu" and not no_flash)


@pytest.mark.parametrize("use_kernel", [True, False])
@pytest.mark.parametrize("name", list(_OWNER_CONFIGS))
def test_kernel_attends_is_the_layer_steps_predicate(name, use_kernel):
  from xotorch_support_jetson_tpu.ops.paged import kernel_attends

  overrides, _, attends = _OWNER_CONFIGS[name]
  assert kernel_attends(tiny_test_config(**overrides), use_kernel) is (use_kernel and attends)


@pytest.mark.parametrize("mp,tile", [(6, 4), (64, 8), (3, 2)])
def test_page_tile_is_the_constant_clamped_to_the_table(mp, tile):
  from xotorch_support_jetson_tpu.ops.paged import PAGE_TILE, _page_tile

  assert PAGE_TILE == 8
  assert _page_tile(mp) == tile


@pytest.mark.parametrize("can_run", [True, False], ids=["kernel", "gather"])
@pytest.mark.parametrize("program", ["plain", "mixed", "spec"])
def test_decode_programs_resolve_use_kernel_through_the_owner(monkeypatch, program, can_run):
  """``use_kernel=None`` is ``paged_kernel_supported(cfg)`` in all three
  public programs; an explicit value passes through."""
  from xotorch_support_jetson_tpu.models import decoder

  from xotorch_support_jetson_tpu.inference.shard import Shard

  params = pool = None  # the jitted programs are stood in for below: nothing reads them
  shard = Shard("m", 0, CFG.n_layers - 1, CFG.n_layers)
  B, mp = 2, 128 // PS
  tok, bt = jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, mp), jnp.int32)
  pos, active, temps = jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool), jnp.zeros((B,), jnp.float32)
  impl, at, call = {
    "plain": ("_fused_paged_batch_decode_impl", 13, lambda **kw: decoder.fused_paged_batch_decode(params, CFG, shard, tok, pool, bt, pos, active, temps, 2, page_size=PS, **kw)),
    "mixed": ("_fused_mixed_paged_batch_decode_impl", 17, lambda **kw: decoder.fused_mixed_paged_batch_decode(
      params, CFG, shard, tok, pool, bt, pos, active, temps, jnp.zeros((1, 16), jnp.int32), bt[:1], jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), 2, page_size=PS, **kw)),
    "spec": ("_fused_spec_paged_batch_decode_impl", -2, lambda **kw: decoder.fused_spec_paged_batch_decode(
      params, CFG, shard, None, CFG, shard, tok, pool, None, bt, pos, active, jnp.zeros((B,), jnp.int32), temps, 1, 1, page_size=PS, **kw)),
  }[program]
  seen = []
  monkeypatch.setattr(decoder, impl, lambda *a: seen.append(a[at]) or (None,) * 5)  # (the plain and mixed programs return five, the public forms their first four)
  monkeypatch.setattr("xotorch_support_jetson_tpu.ops.paged.paged_kernel_supported", lambda cfg, platform=None: can_run)
  call()
  call(use_kernel=not can_run)
  assert seen == [can_run, not can_run]


def _prefill_both(params, shard, prompts, n_slots, max_seq=128):
  """Prefill the same prompts into a dense pool and a page pool."""
  mp = max_seq // PS
  dense = init_kv_cache(CFG, shard.n_shard_layers, n_slots, max_seq)
  pool = init_paged_pool(CFG, shard.n_shard_layers, 1 + n_slots * mp, PS)
  bt = np.zeros((n_slots, mp), np.int32)
  nxt = 1
  firsts = []
  for r, p in enumerate(prompts):
    S = len(p)
    pad = np.zeros((1, 16 * ((S + 15) // 16)), np.int32)
    pad[0, :S] = p
    last_d, dense = prefill_into_slot(params, CFG, shard, jnp.asarray(pad), dense, jnp.int32(r), jnp.int32(S))
    need = (S + 64) // PS + 1
    bt[r, :need] = range(nxt, nxt + need)
    nxt += need
    last_p, pool = prefill_into_pages(params, CFG, shard, jnp.asarray(pad), pool, jnp.asarray(bt[r]), jnp.int32(0), jnp.int32(S), PS)
    assert jnp.allclose(last_d, last_p, atol=1e-4), f"prefill logits diverge, row {r}"
    firsts.append(int(np.argmax(np.asarray(last_d)[0])))
  return dense, pool, bt, firsts


def test_paged_decode_matches_dense_decode():
  """Same prompts through both cache layouts -> identical greedy tokens,
  including an inactive row that must not advance (its table is pinned to
  the trash page inside the program)."""
  params, shard = full_model_params(KEY, CFG)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100]]
  n_slots = 3
  dense, pool, bt, firsts = _prefill_both(params, shard, prompts, n_slots)
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.asarray([True, True, False])
  temps = jnp.zeros((n_slots,), jnp.float32)
  td, _, pd, _ = fused_batch_decode(params, CFG, shard, tok, dense, positions, active, temps, 12)
  tp, _, pp, _ = fused_paged_batch_decode(params, CFG, shard, tok, pool, jnp.asarray(bt), positions, active, temps, 12, page_size=PS, use_kernel=False)
  td, tp = np.asarray(td), np.asarray(tp)
  assert np.array_equal(td[:2], tp[:2])
  assert np.array_equal(np.asarray(pd), np.asarray(pp))


@pytest.mark.parametrize("B", [16, 48])
def test_paged_int8kv_batched_decode_matches_dense(B):
  """Paged int8-KV batched decode == dense int8-KV batched decode, token for
  token, at B=16 and at the B=48 dense knee on the CPU virtual mesh. The
  batch includes a prompt that crosses a page boundary (PS+2), a row whose
  DECODE run crosses into a fresh page (prompt PS-1), and a prefix-cache-hit
  admission (the last row reuses the first row's leading prompt page and
  prefills only its suffix, prefix_len > 0)."""
  params, shard = full_model_params(KEY, CFG)
  rng = np.random.default_rng(11)
  mp = 128 // PS
  lens = [PS + 2, PS - 1] + [int(rng.integers(2, 2 * PS + 4)) for _ in range(B - 3)] + [PS + 2]
  prompts = [list(rng.integers(1, CFG.vocab_size, size=(s,))) for s in lens]
  prompts[-1] = list(prompts[0])  # prefix-cache-hit row: same prompt as row 0

  S_pad = 48
  tok = np.zeros((B, S_pad), np.int32)
  prompt_lens = np.asarray(lens, np.int32)
  for i, p in enumerate(prompts):
    tok[i, : len(p)] = p

  dense = init_kv_cache(CFG, shard.n_shard_layers, B, 128, quant="int8")
  last_d, dense = prefill_into_slots(params, CFG, shard, jnp.asarray(tok), dense, jnp.arange(B, dtype=jnp.int32), jnp.asarray(prompt_lens))

  pool = init_paged_pool(CFG, shard.n_shard_layers, 1 + B * mp, PS, quant="int8")
  bts = np.zeros((B, mp), np.int32)
  for r in range(B):
    bts[r] = range(1 + r * mp, 1 + (r + 1) * mp)
  # First dispatch: all rows except the prefix-reuser, from position 0.
  last_p1, pool = prefill_into_pages_many(
    params, CFG, shard, jnp.asarray(tok[: B - 1]), pool, jnp.asarray(bts[: B - 1]),
    jnp.zeros((B - 1,), jnp.int32), jnp.asarray(prompt_lens[: B - 1]), PS,
  )
  # Second dispatch: the last row reuses row 0's (now-written) first page —
  # the scheduler's prefix-cache-hit shape — and prefills only its suffix.
  bts[-1, 0] = bts[0, 0]
  suffix = np.zeros((1, 16), np.int32)
  suffix[0, : lens[-1] - PS] = prompts[-1][PS:]
  last_p2, pool = prefill_into_pages(
    params, CFG, shard, jnp.asarray(suffix), pool, jnp.asarray(bts[-1]), jnp.int32(PS), jnp.int32(lens[-1]), PS
  )
  last_p = np.concatenate([np.asarray(last_p1), np.asarray(last_p2)])

  assert np.allclose(np.asarray(last_d), last_p, atol=1e-4)
  firsts = np.argmax(np.asarray(last_d), axis=-1).astype(np.int32)
  assert np.array_equal(firsts, np.argmax(last_p, axis=-1))

  tok1 = jnp.asarray(firsts[:, None], jnp.int32)
  positions = jnp.asarray(prompt_lens, jnp.int32)
  active = jnp.ones((B,), bool)
  temps = jnp.zeros((B,), jnp.float32)
  n_steps = PS + 3  # every row's decode crosses at least one page boundary
  td, _, pd, _ = fused_batch_decode(params, CFG, shard, tok1, dense, positions, active, temps, n_steps)
  tp, _, pq, _ = fused_paged_batch_decode(
    params, CFG, shard, tok1, pool, jnp.asarray(bts), positions, active, temps, n_steps, page_size=PS, use_kernel=False
  )
  assert np.array_equal(np.asarray(td), np.asarray(tp))
  assert np.array_equal(np.asarray(pd), np.asarray(pq))


def test_scheduler_int8kv_pool_uses_block_math_capacity(monkeypatch):
  """With int8-KV pages (half the bytes per token) the default pool holds 2x
  the dense layout's pages — large-batch admission is bounded by
  paged+int8-KV block math, not dense-slot math — and requests still serve."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  monkeypatch.setenv("XOT_TPU_KV_QUANT", "int8")
  monkeypatch.delenv("XOT_TPU_BATCH_PAGES", raising=False)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2)

  async def run():
    return await server.submit("q", np.asarray([3, 25, 9], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)

  out = asyncio.run(run())
  assert len(out) == 4
  mp = 128 // PS
  hd = CFG.head_dim  # int8 page bytes/token = hd + 4 (scale) vs 2*hd bf16
  assert server.allocator.n_pages == (2 * server.n_slots * mp * hd) // (hd + 4) + 1
  assert server.allocator.n_pages > server.n_slots * mp + 1  # strictly beyond dense-slot math
  assert server.cache["k"].dtype == jnp.int8


def test_paged_prefix_reuse_is_exact():
  """A request admitted on top of another's cached prompt pages produces the
  same last-token logits as a full prefill."""
  params, shard = full_model_params(KEY, CFG)
  rng = np.random.default_rng(1)
  mp = 8
  pool = init_paged_pool(CFG, shard.n_shard_layers, 16, PS)
  prompt = rng.integers(0, CFG.vocab_size, size=(2 * PS + 4,)).astype(np.int32)  # 2 full pages + 4
  pad = np.zeros((1, 48), np.int32)
  pad[0, : len(prompt)] = prompt
  bt_full = np.zeros((mp,), np.int32)
  bt_full[:4] = [1, 2, 3, 4]
  last_full, pool = prefill_into_pages(params, CFG, shard, jnp.asarray(pad), pool, jnp.asarray(bt_full), jnp.int32(0), jnp.int32(len(prompt)), PS)

  # Second request: same first 2 pages, different tail.
  bt_new = np.zeros((mp,), np.int32)
  bt_new[:4] = [1, 2, 5, 6]
  suffix = np.zeros((1, 16), np.int32)
  suffix[0, :4] = prompt[2 * PS :]
  last_reuse, pool = prefill_into_pages(params, CFG, shard, jnp.asarray(suffix), pool, jnp.asarray(bt_new), jnp.int32(2 * PS), jnp.int32(len(prompt)), PS)
  assert jnp.allclose(last_full, last_reuse, atol=1e-4)


def test_page_allocator_refcount_and_eviction():
  a = PageAllocator(n_pages=6, page_size=4)  # pages 1..5 usable
  assert a.n_available == 5
  got = a.alloc(3)
  assert sorted(got) == [1, 2, 3]
  # Donate two pages to the cache under distinct chains.
  k1 = a.chain_keys([1, 2, 3, 4], 4)[0]
  k2 = a.chain_keys([9, 9, 9, 9], 4)[0]
  assert a.insert_cached(k1, got[0])
  assert a.insert_cached(k2, got[1])
  a.free([got[2]])
  assert a.n_free == 3 and a.n_available == 5
  # Prefix hit pins the page against eviction.
  hit = a.lookup_prefix([k1])
  assert hit == [got[0]]
  big = a.alloc(4)  # forces eviction of the idle cached page (k2) only
  assert big is not None and got[0] not in big
  assert a.lookup_prefix([k2]) == []  # evicted
  a.release(got[0])
  assert a.lookup_prefix([k1]) == [got[0]]  # still cached while idle
  a.release(got[0])
  assert a.alloc(99) is None  # over capacity


def _engine(params, shard):
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)
  return engine


def _solo(params, shard, prompt, n_gen):
  from tests.test_batched import _single_row_reference

  return _single_row_reference(params, shard, prompt, n_gen - 1)


def test_scheduler_admits_more_context_than_dense_equivalent(monkeypatch):
  """4 concurrent requests on a pool HALF the dense layout's size: a dense
  slot pool with this memory would fit 2 slots; paging admits all 4 at once
  (their aggregate live context fits in pages) and every answer is exact."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  mp = 128 // PS
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", str(2 * mp + 1))  # dense-2-slot memory
  server = BatchedServer(_engine(params, shard), n_slots=4, chunk=2)

  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  n_gen = 5
  expected = [_solo(params, shard, p, n_gen) for p in prompts]

  async def run():
    outs = await asyncio.gather(
      *(
        server.submit(f"p{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
        for i, p in enumerate(prompts)
      )
    )
    # All four were RESIDENT simultaneously at some point iff aggregate
    # admitted context exceeded the dense-equivalent's 2 slots.
    return outs

  outs = asyncio.run(run())
  for i, out in enumerate(outs):
    assert out == expected[i], f"req {i}: {out} != {expected[i]}"


def test_scheduler_prefix_cache_reuses_pages_and_stays_exact(monkeypatch):
  """Second request with the same long prompt: admitted against cached pages
  (fewer new pages allocated) and produces the identical greedy answer."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2)

  rng = np.random.default_rng(3)
  prompt = list(rng.integers(0, CFG.vocab_size, size=(2 * PS + 3,)))
  n_gen = 4
  expected = _solo(params, shard, prompt, n_gen)

  async def run():
    out1 = await server.submit("a", np.asarray(prompt, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
    cached_after_first = len(server.allocator._by_key)
    free_before = server.allocator.n_available
    out2 = await server.submit("b", np.asarray(prompt, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
    return out1, out2, cached_after_first, free_before

  out1, out2, cached_after_first, _ = asyncio.run(run())
  assert out1 == expected and out2 == expected
  assert cached_after_first == 2  # both full prompt pages were donated


def test_scheduler_parks_starved_admission_until_pages_free(monkeypatch):
  """With pages for ~one request only, two concurrent submits serialize (the
  second parks, then runs) — both exact, neither errors."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  mp = 128 // PS
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", str(mp + 2))
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2)

  prompts = [[3, 25, 9], [7, 1, 88, 42, 5]]
  n_gen = 5
  expected = [_solo(params, shard, p, n_gen) for p in prompts]

  async def run():
    return await asyncio.gather(
      *(
        server.submit(f"s{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
        for i, p in enumerate(prompts)
      )
    )

  outs = asyncio.run(run())
  for i, out in enumerate(outs):
    assert out == expected[i], f"req {i}: {out} != {expected[i]}"


def test_parked_big_request_keeps_priority_over_later_small_ones(monkeypatch):
  """A page-starved big prompt retains its queue position: a small request
  arriving AFTER it must not leapfrog it by consuming the freed pages
  (ADVICE r2 fairness/liveness finding — previously the starved request was
  requeued at the tail and could wait unboundedly under sustained load)."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "5")  # 4 usable pages (page 0 is trash)
  server = BatchedServer(_engine(params, shard), n_slots=3, chunk=2)

  rng = np.random.default_rng(7)
  small_a = [3, 25, 9]  # grows to 3 pages over its 40-token run
  big = list(rng.integers(0, CFG.vocab_size, size=(3 * PS + 3,)))  # needs all 4 pages
  small_c = [7, 1, 88]
  n_gen = 6
  expected_big = _solo(params, shard, big, n_gen)
  expected_c = _solo(params, shard, small_c, n_gen)

  first_emits: list[str] = []

  def emit(rid, toks, fin):
    if toks and rid not in first_emits:
      first_emits.append(rid)

  async def run():
    # "a" runs long enough (20 chunk ticks) that "big" parks while it holds
    # pages — and its growth to 3 pages means "big" can only admit after it.
    fa = asyncio.ensure_future(server.submit("a", np.asarray(small_a, np.int32), max_tokens=40, temp=0.0, top_k=35, eos_ids=(), emit=emit))
    for _ in range(200):  # wait until "a" is resident
      await asyncio.sleep(0.02)
      if any(s is not None for s in server.slots):
        break
    fb = asyncio.ensure_future(server.submit("big", np.asarray(big, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=emit))
    for _ in range(500):  # wait until "big" has actually parked
      await asyncio.sleep(0.02)
      if server._parked:
        break
    assert server._parked, "big request never parked — pool sizing assumption broke"
    fc = asyncio.ensure_future(server.submit("c", np.asarray(small_c, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=emit))
    return await asyncio.gather(fa, fb, fc)

  out_a, out_big, out_c = asyncio.run(run())
  assert out_big == expected_big and out_c == expected_c
  # "c" arrived while "big" was parked; page priority means "big" streams
  # its first token before "c" does.
  assert first_emits.index("big") < first_emits.index("c"), first_emits


@pytest.mark.parametrize("flavor", ["int8", "moe", "mla", "gemma2"])
def test_paged_decode_covers_engine_modes(flavor):
  """int8-quantized, MoE, and MLA (latent-cache) models through the paged
  decode == their dense batch decode."""
  if flavor == "int8":
    cfg = CFG
    params, shard = full_model_params(KEY, cfg)
    from xotorch_support_jetson_tpu.models.quantize import quantize_params

    params = quantize_params(params)
  elif flavor == "moe":
    cfg = tiny_test_config(n_layers=2, max_seq_len=128, n_experts=4, n_active_experts=2, moe_hidden_dim=32, first_k_dense=1)
    params, shard = full_model_params(KEY, cfg)
  elif flavor == "mla":
    cfg = tiny_test_config(
      n_layers=2, max_seq_len=128, n_heads=4, n_kv_heads=4, kv_lora_rank=16,
      q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    )
    params, shard = full_model_params(KEY, cfg)
  else:  # gemma2: softcaps + alternating sliding window through the page pool
    cfg = tiny_test_config(
      n_layers=2, max_seq_len=128, post_norms=True, mlp_act="gelu_tanh",
      attn_logit_softcap=50.0, final_logit_softcap=30.0, query_pre_attn_scalar=24.0,
      sliding_window=4, embed_scale=8.0, tied_embedding=True,
    )
    params, shard = full_model_params(KEY, cfg)

  mp = 128 // PS
  n_slots = 2
  prompts = [[3, 25, 9], [7, 1, 88, 42]]
  dense = init_kv_cache(cfg, shard.n_shard_layers, n_slots, 128)
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + n_slots * mp, PS)
  bt = np.zeros((n_slots, mp), np.int32)
  nxt = 1
  firsts = []
  for r, p in enumerate(prompts):
    S = len(p)
    pad = np.zeros((1, 16), np.int32)
    pad[0, :S] = p
    last_d, dense = prefill_into_slot(params, cfg, shard, jnp.asarray(pad), dense, jnp.int32(r), jnp.int32(S))
    need = (S + 32) // PS + 1
    bt[r, :need] = range(nxt, nxt + need)
    nxt += need
    last_p, pool = prefill_into_pages(params, cfg, shard, jnp.asarray(pad), pool, jnp.asarray(bt[r]), jnp.int32(0), jnp.int32(S), PS)
    assert jnp.allclose(last_d, last_p, atol=1e-4)
    firsts.append(int(np.argmax(np.asarray(last_d)[0])))
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.ones((n_slots,), bool)
  temps = jnp.zeros((n_slots,), jnp.float32)
  td, _, _, _ = fused_batch_decode(params, cfg, shard, tok, dense, positions, active, temps, 8)
  tp, _, _, _ = fused_paged_batch_decode(params, cfg, shard, tok, pool, jnp.asarray(bt), positions, active, temps, 8, page_size=PS, use_kernel=False)
  assert np.array_equal(np.asarray(td), np.asarray(tp))


def test_scheduler_chaos_pages_fully_recover(monkeypatch):
  """Chaos invariant: after a burst of concurrent requests with random
  cancels on a small pool, every future resolves and EVERY page returns to
  the allocator (free list + idle prefix cache == full capacity) — no leaks
  through the admit/park/starve/cancel/finish paths."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  mp = 128 // PS
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", str(3 * mp + 1))
  server = BatchedServer(_engine(params, shard), n_slots=3, chunk=2)
  rng = np.random.default_rng(23)

  async def run():
    async def one(i):
      prompt = list(rng.integers(1, CFG.vocab_size, size=int(rng.integers(2, 2 * PS + 5))))
      task = asyncio.ensure_future(
        server.submit(f"c{i}", np.asarray(prompt, np.int32), max_tokens=int(rng.integers(1, 12)), temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
      )
      if rng.random() < 0.4:
        await asyncio.sleep(float(rng.random()) * 0.05)
        server.cancel(f"c{i}")
      try:
        return await task
      except Exception:  # noqa: BLE001 — overload errors are acceptable outcomes
        return None

    return await asyncio.gather(*(one(i) for i in range(16)))

  outs = asyncio.run(run())
  assert len(outs) == 16
  alloc = server.allocator
  assert alloc.n_available == alloc.n_pages - 1  # all pages back (page 0 reserved)
  assert all(s is None for s in server.slots)
  assert not alloc._refs, f"leaked refcounts: {alloc._refs}"
