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


def _in_pairs(x):
  """A float code leaf [..., Hkv, ps, 64] as ``init_paged_pool`` stores heads of 64 since ISSUE 58, [..., Hkv/2, ps, 128]:
  KV head 2j in lanes 0-63 of leaf head j, head 2j+1 in lanes 64-127 — written out here, not through ops/paged.py."""
  return jnp.concatenate([x[..., 0::2, :, :], x[..., 1::2, :, :]], axis=-1)


def _as_stored(quant: str, x):
  """The leaf as the kernel is handed it: ``"pairs"`` pairs the heads, every other form is stored as it is built."""
  return _in_pairs(x) if quant == "pairs" else x


def _kernel_case_pools(rng, quant: str, P: int, Hkv: int, ps: int, hd: int):
  """(k, v, scale kwargs) of a page pool in one of the three stored forms (``"pairs"``: bfloat16 too — the tests hand
  the kernel ``_as_stored`` of it and the reference the leaf as built here)."""
  if quant in ("", "pairs"):
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
@pytest.mark.parametrize("quant", ["", "int8", "int4", "pairs"])
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
  ker = np.asarray(paged_decode_attention(q, _as_stored(quant, kp), _as_stored(quant, vp), bt, jnp.asarray(lens), ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales))
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
  coded = bool(scales)  # integer codes: their poison is ±127; a float leaf's is NaN
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

  kp_bad, kp_ok = poisoned(kp, unwritten, sign if coded else jnp.nan)
  # (float values a row has not written are probabilities 0 × whatever lies there, in the kernel as in the reference: those stay finite)
  vp_bad, vp_ok = poisoned(vp, unwritten if coded else free, sign if coded else jnp.nan)
  bad, ok = {}, {}
  for name, x in scales.items():
    bad[name], ok[name] = poisoned(x, unwritten, jnp.nan)
  ker = np.asarray(paged_decode_attention(q, _as_stored(quant, kp_bad), _as_stored(quant, vp_bad), jnp.asarray(bt), jnp.asarray(lens), ps, pages_per_step=g, interpret=True, **bad))
  ref = np.asarray(paged_gqa_attention_ref(q[:, None], kp_ok, vp_ok, jnp.asarray(bt), jnp.asarray(np.maximum(lens, 1)), ps, **ok)[:, 0])
  live = lens > 0
  assert np.isfinite(ker).all()
  assert np.allclose(ker[live], ref[live], atol=2e-5), np.abs(ker[live] - ref[live]).max()
  assert not ker[~live].any()


@pytest.mark.parametrize("row", list(_FOLD_ROWS))
@pytest.mark.parametrize("g", [4, 8])
@pytest.mark.parametrize("quant,hd", [("", 64), ("", 128), ("int8", 64), ("int8", 128), ("pairs", 64)], ids=["-64", "-128", "int8-64", "int8-128", "pairs-64"])
def test_paged_kernel_fold_boundaries_match_reference(quant, hd, g, row):
  """One softmax update takes a tile of pages: lengths that end one token
  before, on and one token after a page and a tile, a length past two tiles
  and an empty row between two long ones equal the gather reference, and
  nothing a row does not hold — a page never fetched, a stale scale lane, a
  slot the row before left — reaches its result (no NaN, no ±127 code). In a
  pool of paired heads (ISSUE 58) a query's zeros meet the pair's OTHER head's
  key at every position: at a position the row holds that key was written with
  the token and is finite, and at every other the score is masked as before."""
  check_fold_boundary_case(quant, hd, g, row)


@pytest.mark.parametrize("hq,hkv,quant", [(2, 2, "int8"), (6, 2, "int8"), (8, 1, "int8"), (4, 4, "pairs"), (12, 4, "pairs"), (32, 8, "pairs")])
def test_paged_kernel_head_groupings_match_reference(hq, hkv, quant):
  """The kernel stacks every query head's scores for one softmax update and
  hands each kv head its own group's rows: MHA (group 1), a group that is
  not a power of two, and MQA equal the gather reference — and so do paired
  heads (ISSUE 58), whose leaf head's group is both KV heads' queries: MHA
  (a group of 2), groups of 3 and granite's and LFM2's 32 / 8."""
  rng = np.random.default_rng(47)
  B, hd, ps, mp, P = 2, 64, _RAGGED_PS, _RAGGED_MP, 16
  q = jnp.asarray(rng.normal(size=(B, hq, hd)), jnp.float32)
  kp, vp, scales = _kernel_case_pools(rng, quant, P, hkv, ps, hd)
  bt = jnp.asarray(1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp))
  lens = jnp.asarray([3 * ps + 5, ps - 1], jnp.int32)
  ker = paged_decode_attention(q, _as_stored(quant, kp), _as_stored(quant, vp), bt, lens, ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lens, ps, **scales)[:, 0]
  assert jnp.allclose(ker, ref, atol=2e-5), jnp.abs(ker - ref).max()


@pytest.mark.parametrize("quant", ["", "int8", "int4", "pairs"])
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
  kp, vp, scales = _as_stored(quant, bad(kp)), _as_stored(quant, bad(vp)), {name: bad(x) for name, x in scales.items()}
  lens = np.asarray([ps + 3, 0, 2 * ps], np.int32)  # a partial page, an empty row, a full last page
  clean = 1 + np.arange(B * mp, dtype=np.int32).reshape(B, mp)
  held = np.arange(mp)[None, :] * ps < lens[:, None]
  run = lambda table: np.asarray(paged_decode_attention(q, kp, vp, jnp.asarray(table), jnp.asarray(lens), ps, pages_per_step=_RAGGED_TILE, interpret=True, **scales))  # noqa: E731
  got = run(np.where(held, clean, poison).astype(np.int32))
  assert np.isfinite(got).all()
  assert np.array_equal(got, run(clean))


# ------------------------------------------------- paired heads of 64 (ISSUE 58)

_MLA_TINY = dict(n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
_STORED_FORMS = {  # config overrides, KV mode → the code leaves' trailing [heads, slots, lanes] and the gauge's k / v
  "float-64-even-heads": (dict(dim=512, n_heads=8, n_kv_heads=4), "", (2, PS, 128), (2, PS, 128), 1.0, 1.0),  # THE paired form
  "bf16-64-granite-lfm2": (dict(dim=2048, n_heads=32, n_kv_heads=8, dtype=jnp.bfloat16), "", (4, PS, 128), (4, PS, 128), 1.0, 1.0),
  "int8-64": (dict(dim=512, n_heads=8, n_kv_heads=4), "int8", (4, PS, 64), (4, PS, 64), 0.5, 0.5),  # a scale is per token and head: as it was
  "int4-64": (dict(dim=512, n_heads=8, n_kv_heads=4), "int4", (4, PS, 32), (4, PS, 32), 0.25, 0.25),
  "float-64-odd-heads": (dict(dim=384, n_heads=6, n_kv_heads=3), "", (3, PS, 64), (3, PS, 64), 0.5, 0.5),  # no head to pair the last with
  "float-128": (dict(dim=512, n_heads=4, n_kv_heads=2), "", (2, PS, 128), (2, PS, 128), 1.0, 1.0),  # whole lanes already: as it was
  "int4-128": (dict(dim=512, n_heads=4, n_kv_heads=2), "int4", (2, PS, 64), (2, PS, 64), 0.5, 0.5),
  "float-32": (dict(dim=256, n_heads=8, n_kv_heads=4), "", (4, PS, 32), (4, PS, 32), 0.25, 0.25),  # pairs are of 64 alone
  "mla-latent-and-rope": (_MLA_TINY, "", (1, PS, 16), (1, PS, 8), 0.125, 0.0625),  # one head: nothing to pair
}


@pytest.mark.parametrize("case", list(_STORED_FORMS))
def test_a_pool_stores_float_heads_of_64_in_pairs_and_every_other_pool_as_it_was(case):
  """``init_paged_pool`` pairs the heads from ``cfg`` and the KV mode alone — unquantised, K and V heads of 64, an even
  number of them: [L, P, Hkv/2, ps, 128], the same bytes — and leaves every other pool [L, P, Hkv, ps, hd]; the rule a
  leaf's reader applies is ``leaf.shape[2] * 2 == cfg.cache_kv_heads``; ``code_lanes_filled`` (the gauge
  ``kv_page_lanes_filled``) says what of a row the decode kernel's DMA carries is codes."""
  from xotorch_support_jetson_tpu.ops.paged import code_lanes_filled, kernel_pool_form, pairs_kv_heads

  overrides, quant, k_tail, v_tail, k_lanes, v_lanes = _STORED_FORMS[case]
  cfg = tiny_test_config(n_layers=2, **overrides)
  pool = init_paged_pool(cfg, 2, 5, PS, quant=quant)
  assert pool["k"].shape == (2, 5, *k_tail) and pool["v"].shape == (2, 5, *v_tail)
  paired = case in ("float-64-even-heads", "bf16-64-granite-lfm2")
  assert pairs_kv_heads(cfg, quant) == paired == (pool["k"].shape[2] * 2 == cfg.cache_kv_heads)
  assert pool["k"].size == 2 * 5 * cfg.cache_kv_heads * PS * cfg.cache_k_dim // (2 if quant == "int4" else 1)  # the bytes it had
  assert (code_lanes_filled(pool["k"]), code_lanes_filled(pool["v"])) == (k_lanes, v_lanes)
  if k_lanes == 1.0:  # whole lanes: the stored pool IS the kernel's form
    assert all(kernel_pool_form(pool)[name].shape == pool[name].shape for name in ("k", "v"))


def _paired_case(seed=53, L=3, B=3, Hq=8, Hkv=4, hd=64, ps=8, mp=4):
  """Position-major K/V of L layers and B rows [L, B, mp·ps, Hkv, hd], the same in pages as they were stored until
  ISSUE 58 ([L, P, Hkv, ps, hd], filled by hand) and in pairs, a table and a query."""
  rng = np.random.default_rng(seed)
  k, v = (jnp.asarray(rng.normal(size=(L, B, mp * ps, Hkv, hd)), jnp.float32) for _ in range(2))
  bt = jnp.asarray(1 + rng.permutation(B * mp).reshape(B, mp), jnp.int32)
  pages = lambda t: jnp.zeros((L, 1 + B * mp, Hkv, ps, hd), t.dtype).at[:, bt].set(jnp.swapaxes(t.reshape(L, B, mp, ps, Hkv, hd), 3, 4))  # noqa: E731
  plain = {"k": pages(k), "v": pages(v)}
  return k, v, bt, plain, {name: _in_pairs(leaf) for name, leaf in plain.items()}, jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32), ps


@pytest.mark.parametrize("accessor", ["scatter_row_pages", "gather_row_pages", "gather_pages", "write_token_kv", "write_token_kv-kernel", "reference", "kernel-vs-unpaged"])
def test_paired_pool_accessors_read_and_write_what_the_plain_pool_holds(accessor):
  """Every accessor between pages and tokens, on a pool of paired heads against the same K/V stored a head a row (the
  form until ISSUE 58): a prefill's scatter lays the pairs out as ``_in_pairs`` says, the gathers give back the model's
  [.., Hkv, 64] bit for bit, a token write touches the same channels, the gather reference reads either form to the
  same bits, and the kernel (interpret mode) agrees with attention over the unpaged K/V."""
  from xotorch_support_jetson_tpu.ops.attention import gqa_attention
  from xotorch_support_jetson_tpu.ops.paged import gather_pages, gather_row_pages, scatter_row_pages, write_token_kv

  k, v, bt, plain, pairs, q, ps = _paired_case()
  Hkv = k.shape[3]
  same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))  # noqa: E731
  lens = jnp.asarray([5, 17, 32], jnp.int32)
  if accessor == "scatter_row_pages":
    got = scatter_row_pages(jnp.zeros_like(pairs["k"]), k, bt)
    assert same(got, pairs["k"]) and same(scatter_row_pages(jnp.zeros_like(plain["k"]), k, bt), plain["k"])
  elif accessor == "gather_row_pages":
    assert same(gather_row_pages(pairs["k"], bt, Hkv), k) and same(gather_row_pages(plain["k"], bt, Hkv), k)
    assert same(gather_row_pages(scatter_row_pages(jnp.zeros_like(pairs["v"]), v, bt), bt, Hkv), v)  # a prefill's round trip
  elif accessor == "gather_pages":
    assert same(gather_pages(pairs["k"], bt, 1, Hkv), k[1]) and same(gather_pages(pairs["k"][2], bt, None, Hkv), k[2])
  elif accessor.startswith("write_token_kv"):
    new = {"k": q[:, :Hkv] + 1, "v": q[:, Hkv:] - 1}  # [B, Hkv, 64], as the layer step hands them
    pos = lens - 1
    kwargs = dict(kernel=True, interpret=True) if accessor.endswith("kernel") else {}
    wrote, want = write_token_kv(pairs, new, 1, bt, pos, ps, **kwargs), write_token_kv(plain, new, 1, bt, pos, ps)
    for name in ("k", "v"):
      assert same(wrote[name], _in_pairs(want[name])) and not same(wrote[name], pairs[name])
      assert same(gather_row_pages(wrote[name], bt, Hkv)[1, np.arange(3), np.asarray(pos)], new[name])
  elif accessor == "reference":
    for layer in range(3):
      assert same(paged_gqa_attention_ref(q[:, None], pairs["k"], pairs["v"], bt, lens, ps, layer=layer), paged_gqa_attention_ref(q[:, None], plain["k"], plain["v"], bt, lens, ps, layer=layer))
  else:
    for layer in range(3):
      want = gqa_attention(q[:, None], k[layer], v[layer], (lens - 1)[:, None], jnp.arange(k.shape[2], dtype=jnp.int32))[:, 0]
      named = paged_decode_attention(q, pairs["k"], pairs["v"], bt, lens, ps, interpret=True, layer=layer, kv_quant="", kv_heads=Hkv)  # as the layer step calls it
      assert same(named, paged_decode_attention(q, pairs["k"], pairs["v"], bt, lens, ps, interpret=True, layer=layer))  # a direct caller's stored leaves say it themselves
      assert jnp.allclose(named, want, atol=2e-5), jnp.abs(named - want).max()
      padded = paged_decode_attention(q, plain["k"], plain["v"], bt, lens, ps, interpret=True, layer=layer)  # the form until ISSUE 58, padded per call
      assert jnp.allclose(named, padded, atol=1e-6), jnp.abs(named - padded).max()


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


def _prefill_both(params, shard, prompts, n_slots, max_seq=128, cfg=CFG):
  """Prefill the same prompts into a dense pool and a page pool."""
  mp = max_seq // PS
  dense = init_kv_cache(cfg, shard.n_shard_layers, n_slots, max_seq)
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + n_slots * mp, PS)
  bt = np.zeros((n_slots, mp), np.int32)
  nxt = 1
  firsts = []
  for r, p in enumerate(prompts):
    S = len(p)
    pad = np.zeros((1, 16 * ((S + 15) // 16)), np.int32)
    pad[0, :S] = p
    last_d, dense = prefill_into_slot(params, cfg, shard, jnp.asarray(pad), dense, jnp.int32(r), jnp.int32(S))
    need = (S + 64) // PS + 1
    bt[r, :need] = range(nxt, nxt + need)
    nxt += need
    last_p, pool = prefill_into_pages(params, cfg, shard, jnp.asarray(pad), pool, jnp.asarray(bt[r]), jnp.int32(0), jnp.int32(S), PS)
    assert jnp.allclose(last_d, last_p, atol=1e-4), f"prefill logits diverge, row {r}"
    firsts.append(int(np.argmax(np.asarray(last_d)[0])))
  return dense, pool, bt, firsts


PAIRS_CFG = tiny_test_config(n_layers=2, max_seq_len=128, dim=256, n_heads=4, n_kv_heads=2)  # heads of 64: the pool pairs them (ISSUE 58)


@pytest.mark.parametrize("cfg", [CFG, PAIRS_CFG], ids=["heads-of-16", "paired-heads-of-64"])
def test_paged_decode_matches_dense_decode(cfg):
  """Same prompts through both cache layouts -> identical greedy tokens,
  including an inactive row that must not advance (its table is pinned to
  the trash page inside the program). Heads of 64 go through pages that hold
  them in pairs: prefilled by ``scatter_row_pages``, written a token at a
  time, read by the gather reference."""
  params, shard = full_model_params(KEY, cfg)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100]]
  n_slots = 3
  dense, pool, bt, firsts = _prefill_both(params, shard, prompts, n_slots, cfg=cfg)
  assert (pool["k"].shape[2] * 2 == cfg.cache_kv_heads) == (cfg is PAIRS_CFG)
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.asarray([True, True, False])
  temps = jnp.zeros((n_slots,), jnp.float32)
  td, _, pd, _ = fused_batch_decode(params, cfg, shard, tok, dense, positions, active, temps, 12)
  tp, _, pp, _ = fused_paged_batch_decode(params, cfg, shard, tok, pool, jnp.asarray(bt), positions, active, temps, 12, page_size=PS, use_kernel=False)
  td, tp = np.asarray(td), np.asarray(tp)
  assert np.array_equal(td[:2], tp[:2])
  assert np.array_equal(np.asarray(pd), np.asarray(pp))


def test_a_model_of_paired_heads_scores_the_same_through_the_kernels_and_the_gather():
  """``paged_window_forward`` (speculation's verify: the one decode-side program with an ``interpret`` argument) over a
  pool of paired heads, told the kernels — the Mosaic token write and ``paged_decode_attention`` as the layer steps call
  them, the model's KV heads named — against the XLA scatter and the gather reference: the first layer's pages bit for
  bit, the logits and the later layers' pages to rounding."""
  from xotorch_support_jetson_tpu.models.decoder import paged_window_forward

  params, shard = full_model_params(KEY, PAIRS_CFG)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5] + [4] * 20, [100]]
  _, pool, bt, firsts = _prefill_both(params, shard, prompts, 3, cfg=PAIRS_CFG)
  window = jnp.concatenate([jnp.asarray(firsts, jnp.int32)[:, None], jnp.asarray([[17, 40], [2, 91], [64, 8]], jnp.int32)], axis=1)
  wpos = jnp.asarray([len(p) for p in prompts], jnp.int32)[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
  ref_logits, ref_pool = paged_window_forward(params, PAIRS_CFG, shard, window, wpos, pool, jnp.asarray(bt), PS, use_kernel=False)
  ker_logits, ker_pool = paged_window_forward(params, PAIRS_CFG, shard, window, wpos, pool, jnp.asarray(bt), PS, use_kernel=True, interpret=True)
  assert ker_pool["k"].shape == pool["k"].shape == (2, pool["k"].shape[1], 1, PS, 128)
  assert np.array_equal(np.asarray(ker_pool["k"][0, 1:]), np.asarray(ref_pool["k"][0, 1:])) and not np.array_equal(np.asarray(ker_pool["k"][0]), np.asarray(pool["k"][0]))
  assert all(np.allclose(np.asarray(ker_pool[name][:, 1:]), np.asarray(ref_pool[name][:, 1:]), atol=1e-5) for name in pool)
  assert jnp.allclose(ker_logits, ref_logits, atol=1e-4) and np.array_equal(np.asarray(jnp.argmax(ker_logits, -1)), np.asarray(jnp.argmax(ref_logits, -1)))


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
