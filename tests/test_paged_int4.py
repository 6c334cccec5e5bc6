"""int4-KV pages (ISSUE 11).

Correctness claims:
- int4 KV pack/unpack round-trips exactly and the dequant error is bounded;
- the Pallas paged kernel's in-kernel int4 dequant (two-dot nibble split)
  == the gather reference consuming the SAME packed pools + scales —
  token-exact at the same quantization, across page-tile widths;
- the wide page tiles (8/16) stay exact for int8 pools too;
- paged int4-KV decode == dense int4-KV decode, token for token (int4 is
  exact vs its OWN quantized reference — never vs int8/bf16);
- scheduler pool block math under int4: ~2x the int8 pages at the same
  bf16 dense budget, enough that the dense-48 budget covers 96 FULL context
  windows (the B>=96 admission knee) — and requests still serve.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_paged import _FOLD_ROWS, check_fold_boundary_case

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_batch_decode,
  fused_paged_batch_decode,
  init_kv_cache,
  prefill_into_pages_many,
  prefill_into_slots,
)
from xotorch_support_jetson_tpu.models.quantize import quantize_kv_int4, unpack_int4_kv
from xotorch_support_jetson_tpu.ops.paged import (
  init_paged_pool,
  paged_decode_attention,
  paged_gqa_attention_ref,
)

CFG = tiny_test_config(n_layers=2, max_seq_len=128)
KEY = jax.random.PRNGKey(0)
PS = 16


def test_quantize_kv_int4_roundtrip_and_bounds():
  rng = np.random.default_rng(3)
  x = jnp.asarray(rng.normal(size=(5, 3, 64)), jnp.float32)
  packed, scale = quantize_kv_int4(x)
  assert packed.shape == (5, 3, 32) and packed.dtype == jnp.int8
  assert scale.shape == (5, 3, 1)
  codes = unpack_int4_kv(packed)
  assert codes.shape == x.shape
  # Nibble range and pack/unpack exactness (unpack(pack(q)) == q).
  c = np.asarray(codes)
  assert c.min() >= -8 and c.max() <= 7
  repacked, _ = quantize_kv_int4(jnp.asarray(c * np.asarray(scale), jnp.float32))
  assert np.array_equal(np.asarray(repacked), np.asarray(packed))
  # Dequant error bounded by half a quantization step (scale = absmax/7).
  err = np.abs(c * np.asarray(scale) - np.asarray(x))
  assert np.all(err <= np.asarray(scale) / 2 + 1e-6)
  with pytest.raises(ValueError):
    quantize_kv_int4(jnp.zeros((2, 7)))  # odd head dim cannot pack


def _int4_pools(rng, P, Hkv, ps, hd):
  kp, ks = quantize_kv_int4(jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32))
  vp, vs = quantize_kv_int4(jnp.asarray(rng.normal(size=(P, Hkv, ps, hd)), jnp.float32))
  return kp, ks, vp, vs


def test_paged_kernel_int4_dequant_matches_gather_reference():
  """Packed int4 pools through the kernel (two-dot in-register dequant,
  deinterleaved accumulator) == the gather reference unpacking the SAME
  packed pools — across tile widths including ones that don't divide mp."""
  rng = np.random.default_rng(21)
  B, Hq, Hkv, hd, ps, P = 2, 4, 2, 64, 8, 14
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp, ks, vp, vs = _int4_pools(rng, P, Hkv, ps, hd)
  bt = jnp.asarray([[3, 5, 7, 9, 11, 0], [1, 2, 4, 0, 0, 0]], jnp.int32)
  lengths = jnp.asarray([5 * ps - 3, 2 * ps + 1], jnp.int32)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, bt, lengths, ps, k_scale_pool=ks, v_scale_pool=vs)[:, 0]
  for g in (1, 2, 4):
    ker = paged_decode_attention(q, kp, vp, bt, lengths, ps, k_scale_pool=ks, v_scale_pool=vs, pages_per_step=g, interpret=True)
    assert jnp.allclose(ref, ker, atol=1e-5), f"int4 kernel (tile {g}) diverges"


@pytest.mark.parametrize("pages_per_step", [8, 16])
def test_paged_kernel_wide_tiles_match_reference(pages_per_step):
  """The wide tiles (the served ``PAGE_TILE`` and twice it) stay exact for
  int8 pools — including mp that the tile doesn't divide."""
  rng = np.random.default_rng(31)
  B, Hq, Hkv, hd, ps, P = 2, 4, 2, 64, 4, 40
  mp = 18  # not a multiple of 8 or 16
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), jnp.float32)
  kp = jnp.asarray(rng.integers(-127, 128, size=(P, Hkv, ps, hd)), jnp.int8)
  vp = jnp.asarray(rng.integers(-127, 128, size=(P, Hkv, ps, hd)), jnp.int8)
  ks = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, Hkv, ps, 1)), jnp.float32)
  vs = jnp.asarray(rng.uniform(0.005, 0.02, size=(P, Hkv, ps, 1)), jnp.float32)
  bt = np.zeros((B, mp), np.int32)
  bt[0, :15] = np.arange(1, 16)
  bt[1, :7] = np.arange(20, 27)
  lengths = jnp.asarray([15 * ps - 1, 6 * ps + 2], jnp.int32)
  ref = paged_gqa_attention_ref(q[:, None], kp, vp, jnp.asarray(bt), lengths, ps, k_scale_pool=ks, v_scale_pool=vs)[:, 0]
  ker = paged_decode_attention(q, kp, vp, jnp.asarray(bt), lengths, ps, k_scale_pool=ks, v_scale_pool=vs, pages_per_step=pages_per_step, interpret=True)
  assert jnp.allclose(ref, ker, atol=1e-5), f"tile {pages_per_step} diverges"


@pytest.mark.parametrize("row", list(_FOLD_ROWS))
@pytest.mark.parametrize("g", [4, 8])
@pytest.mark.parametrize("hd", [64, 128])
def test_paged_kernel_int4_fold_boundaries_match_reference(hd, g, row):
  """``test_paged.py``'s fold-boundary cases for packed int4 pages: both
  nibble halves of a fold, the poisoned pool's NaN scales and ±127 bytes."""
  check_fold_boundary_case("int4", hd, g, row)


def test_paged_int4kv_decode_matches_dense_int4kv():
  """Paged int4-KV batched decode == dense int4-KV batched decode token for
  token (both quantize per (token, head) with the same nibble codes — int4
  is exact vs its OWN reference). Covers the packed write path in both
  layouts, the paged prefill's gathered-pool forward, and decode runs
  crossing page boundaries."""
  params, shard = full_model_params(KEY, CFG)
  rng = np.random.default_rng(17)
  B, mp = 4, 128 // PS
  lens = [PS + 2, PS - 1, 7, 2 * PS + 3]
  prompts = [list(rng.integers(1, CFG.vocab_size, size=(s,))) for s in lens]
  S_pad = 48
  tok = np.zeros((B, S_pad), np.int32)
  for i, p in enumerate(prompts):
    tok[i, : len(p)] = p
  prompt_lens = np.asarray(lens, np.int32)

  dense = init_kv_cache(CFG, shard.n_shard_layers, B, 128, quant="int4")
  assert dense["k"].shape[-1] == CFG.cache_k_dim // 2 and dense["k"].dtype == jnp.int8
  last_d, dense = prefill_into_slots(params, CFG, shard, jnp.asarray(tok), dense, jnp.arange(B, dtype=jnp.int32), jnp.asarray(prompt_lens))

  pool = init_paged_pool(CFG, shard.n_shard_layers, 1 + B * mp, PS, quant="int4")
  assert pool["k"].shape[-1] == CFG.cache_k_dim // 2
  bts = np.zeros((B, mp), np.int32)
  for r in range(B):
    bts[r] = range(1 + r * mp, 1 + (r + 1) * mp)
  last_p, pool = prefill_into_pages_many(
    params, CFG, shard, jnp.asarray(tok), pool, jnp.asarray(bts),
    jnp.zeros((B,), jnp.int32), jnp.asarray(prompt_lens), PS,
  )
  assert np.allclose(np.asarray(last_d), np.asarray(last_p), atol=1e-4)
  firsts = np.argmax(np.asarray(last_d), axis=-1).astype(np.int32)
  assert np.array_equal(firsts, np.argmax(np.asarray(last_p), axis=-1))

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


def test_int4_block_math_moves_admission_knee_past_96():
  """The scheduler's default-pool block math at the dense-48 bf16 budget:
  int4 pages cover >= 96 FULL context windows where int8 pages cannot —
  the ISSUE 11 admission-knee criterion, pinned at a production-like
  geometry (hd=64) straight on the shared ``kv_cache_bytes`` formula."""
  from xotorch_support_jetson_tpu.inference.paging import kv_cache_bytes, pages_to_cover

  cfg = tiny_test_config(dim=512, n_heads=8, n_kv_heads=8, max_seq_len=1024)
  assert cfg.head_dim == 64
  ps, n_slots, L = 64, 48, cfg.n_layers
  pages_per_row = pages_to_cover(cfg.max_seq_len, ps)
  # The scheduler's budget baseline: the dense bf16 layout of n_slots rows.
  heads, per_side = cfg.cache_kv_heads, cfg.cache_k_dim + cfg.cache_v_dim
  dense_budget = L * n_slots * pages_per_row * ps * heads * per_side * 2
  pages_int8 = dense_budget // kv_cache_bytes(cfg, L, ps, "int8")
  pages_int4 = dense_budget // kv_cache_bytes(cfg, L, ps, "int4")
  # ~1.88x and ~3.56x the dense page count respectively (hd=64).
  assert pages_int8 < 2 * n_slots * pages_per_row
  assert pages_int4 > 1.8 * pages_int8
  # The knee: 96 full windows fit under int4, not under int8.
  assert pages_int4 >= 96 * pages_per_row
  assert pages_int8 < 96 * pages_per_row


def _engine(params, shard):
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)
  return engine


def test_scheduler_int4kv_pool_block_math_and_serves(monkeypatch):
  """XOT_TPU_KV_QUANT=int4 end to end through the batched scheduler: the
  default pool is sized by the int4 block math (the shared kv_cache_bytes
  formula against the bf16 dense budget), the pool leaves are packed, the
  quant tag lands on scheduler + tier, and requests serve exactly."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.paging import kv_cache_bytes

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  monkeypatch.setenv("XOT_TPU_KV_QUANT", "int4")
  monkeypatch.delenv("XOT_TPU_BATCH_PAGES", raising=False)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2)

  async def run():
    return await server.submit("q4", np.asarray([3, 25, 9], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)

  out = asyncio.run(run())
  assert len(out) == 4
  mp = 128 // PS
  L = shard.n_shard_layers
  heads, per_side = CFG.cache_kv_heads, CFG.cache_k_dim + CFG.cache_v_dim
  dense_budget = L * server.n_slots * mp * PS * heads * per_side * 2
  expect = dense_budget // kv_cache_bytes(CFG, L, PS, "int4") + 1
  assert server.allocator.n_pages == expect
  int8_pages = dense_budget // kv_cache_bytes(CFG, L, PS, "int8") + 1
  assert server.allocator.n_pages > int8_pages  # strictly beyond int8 block math
  assert server.cache["k"].dtype == jnp.int8
  assert server.cache["k"].shape[-1] == CFG.cache_k_dim // 2  # packed codes
  assert server.kv_quant == "int4"
  if server.tier is not None:
    assert server.tier.kv_quant == "int4"
  server.shutdown()


def test_spec_paged_window_kernel_identity():
  """Satellite (ISSUE 11): the batched-spec VERIFY window routed through the
  tuned kernel (per-position unroll, interpret mode) == the gather
  reference path — for int8 pools, packed int4 pools, and bf16 pools."""
  from xotorch_support_jetson_tpu.models.decoder import paged_window_forward

  params, shard = full_model_params(KEY, CFG)
  rng = np.random.default_rng(41)
  B, W, mp = 2, 3, 128 // PS
  for quant in ("", "int8", "int4"):
    pool = init_paged_pool(CFG, shard.n_shard_layers, 1 + B * mp, PS, quant=quant)
    bts = np.zeros((B, mp), np.int32)
    for r in range(B):
      bts[r] = range(1 + r * mp, 1 + (r + 1) * mp)
    # Seed some prior context through the prefill path so the window reads
    # real pages behind its own writes.
    lens = [PS + 1, 5]
    tok = np.zeros((B, 32), np.int32)
    for i, s in enumerate(lens):
      tok[i, :s] = rng.integers(1, CFG.vocab_size, size=(s,))
    _, pool = prefill_into_pages_many(
      params, CFG, shard, jnp.asarray(tok), pool, jnp.asarray(bts),
      jnp.zeros((B,), jnp.int32), jnp.asarray(lens, np.int32), PS,
    )
    window = jnp.asarray(rng.integers(1, CFG.vocab_size, size=(B, W)), jnp.int32)
    wpos = jnp.asarray([[lens[0] + j for j in range(W)], [lens[1] + j for j in range(W)]], jnp.int32)
    ref_logits, ref_pool = paged_window_forward(params, CFG, shard, window, wpos, dict(pool), jnp.asarray(bts), PS, use_kernel=False)
    ker_logits, ker_pool = paged_window_forward(params, CFG, shard, window, wpos, dict(pool), jnp.asarray(bts), PS, use_kernel=True, interpret=True)
    assert np.allclose(np.asarray(ref_logits), np.asarray(ker_logits), atol=1e-4), f"window kernel diverges ({quant or 'bf16'})"
    assert np.argmax(np.asarray(ref_logits), -1).tolist() == np.argmax(np.asarray(ker_logits), -1).tolist()
    # Pool writes land on the same slots with the same shapes; deeper-layer
    # values may differ in the last ulp (the kernel's online-softmax reduces
    # in a different order than the gather einsum, and layer N's attention
    # feeds layer N+1's K/V), so the write pin is allclose, not byte-equal.
    for name in ref_pool:
      assert ref_pool[name].shape == ker_pool[name].shape
      assert np.allclose(np.asarray(ref_pool[name], np.float32), np.asarray(ker_pool[name], np.float32), atol=1e-2), f"pool writes diverge ({quant}/{name})"


def test_fused_spec_paged_kernel_ab_identity(monkeypatch):
  """Full batched-spec program A/B: use_kernel=True (interpret) emits the
  exact token streams of the gather-reference program — batched speculation
  no longer forfeits the kernel win (ISSUE 11 satellite)."""
  from xotorch_support_jetson_tpu.models.decoder import fused_spec_paged_batch_decode

  params, shard = full_model_params(KEY, CFG)
  params_d, shard_d = full_model_params(jax.random.PRNGKey(5), CFG, "draft")
  rng = np.random.default_rng(53)
  B, mp = 2, 128 // PS
  pool = init_paged_pool(CFG, shard.n_shard_layers, 1 + B * mp, PS, quant="int8")
  cache_d = init_kv_cache(CFG, shard_d.n_shard_layers, B, 128, quant="")
  bts = np.zeros((B, mp), np.int32)
  for r in range(B):
    bts[r] = range(1 + r * mp, 1 + (r + 1) * mp)
  lens = [4, 6]
  tok = np.zeros((B, 16), np.int32)
  for i, s in enumerate(lens):
    tok[i, :s] = rng.integers(1, CFG.vocab_size, size=(s,))
  _, pool = prefill_into_pages_many(
    params, CFG, shard, jnp.asarray(tok), pool, jnp.asarray(bts),
    jnp.zeros((B,), jnp.int32), jnp.asarray(lens, np.int32), PS,
  )
  _, cache_d = prefill_into_slots(params_d, CFG, shard_d, jnp.asarray(tok), cache_d, jnp.arange(B, dtype=jnp.int32), jnp.asarray(lens, np.int32))
  token = jnp.asarray([[9], [11]], jnp.int32)
  positions = jnp.asarray(lens, jnp.int32)
  active = jnp.ones((B,), bool)
  gammas = jnp.asarray([2, 2], jnp.int32)
  temps = jnp.zeros((B,), jnp.float32)
  outs = {}
  for use_kernel in (False, True):
    buf, counts, _n_prop, nxt, npos, _, _ = fused_spec_paged_batch_decode(
      params, CFG, shard, params_d, CFG, shard_d, token, {k: jnp.array(v) for k, v in pool.items()},
      {k: jnp.array(v) for k, v in cache_d.items()}, jnp.asarray(bts), positions, active, gammas, temps,
      n_rounds=2, gamma_max=2, page_size=PS, key=jax.random.PRNGKey(7), use_kernel=use_kernel, interpret=use_kernel,
    )
    counts = np.asarray(counts)
    outs[use_kernel] = [np.asarray(buf)[i, : counts[i]].tolist() for i in range(B)] + [np.asarray(nxt).tolist(), np.asarray(npos).tolist()]
  assert outs[True] == outs[False], f"spec kernel A/B diverged: {outs}"


def test_adopt_guard_active_before_pool_builds(monkeypatch):
  """Review hardening: a disagg decode node can receive SendKvPages BEFORE
  its first request builds the pool. The lazily created tier resolves the
  quant mode eagerly from env/cfg, so a mismatched sender is refused while
  the tier is empty and its byte-geometry guard is still unseeded (the
  exact window the tag guard exists for)."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  params, shard = full_model_params(KEY, CFG)
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  monkeypatch.setenv("XOT_TPU_KV_QUANT", "int4")
  monkeypatch.delenv("XOT_TPU_KV_TIER", raising=False)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2)
  assert server.cache is None and server.tier is None  # nothing built yet
  hd, H = CFG.cache_k_dim, CFG.cache_kv_heads
  leaves = {
    "k": np.ones((2, 1, H, PS, hd // 2), np.int8),
    "v": np.ones((2, 1, H, PS, hd // 2), np.int8),
    "k_scale": np.ones((2, 1, H, PS, 1), np.float32),
    "v_scale": np.ones((2, 1, H, PS, 1), np.float32),
  }
  # A mismatched (int8) sender is refused up front…
  assert server.adopt_kv_wire([b"early-key"], leaves, quant="int8") == 0
  assert server.kv_quant == "int4" and server.tier is not None and server.tier.kv_quant == "int4"
  assert server.tier.host_pages == 0  # nothing seeded the byte guard
  # …and the matching mode adopts.
  assert server.adopt_kv_wire([b"early-key"], leaves, quant="int4") == 1
  server.shutdown()
