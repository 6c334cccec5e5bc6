"""Pipeline-parallel BATCHED serving (parallel/pp_batch.py): the pipelined
group schedule must be token-identical to the single-device fused batch
programs — dense slots and paged pool, prefill included — and the batch
scheduler must serve concurrent requests through it end-to-end (VERDICT r2
next-step #2: multi-stream pipeline serving)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_batch_decode,
  fused_paged_batch_decode,
  init_kv_cache,
  prefill_into_pages,
  prefill_into_slot,
)
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool
from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
from xotorch_support_jetson_tpu.parallel.pp_batch import PPBatchedServing

KEY = jax.random.PRNGKey(0)
PS = 16
MAX_SEQ = 64
PROMPTS = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]


def _cfg(flavor="llama"):
  if flavor == "gemma2":
    return tiny_test_config(n_layers=4, max_seq_len=MAX_SEQ, sliding_window=8, attn_logit_softcap=50.0, final_logit_softcap=30.0)
  if flavor == "moe":
    return tiny_test_config(n_layers=4, max_seq_len=MAX_SEQ, n_experts=4, n_active_experts=2, moe_hidden_dim=32)
  return tiny_test_config(n_layers=4, max_seq_len=MAX_SEQ)


def _pad(p):
  pad = np.zeros((1, 16 * ((len(p) + 15) // 16)), np.int32)
  pad[0, : len(p)] = p
  return jnp.asarray(pad)


def _prefill_dense(params, cfg, shard, prompts, ppb=None):
  """Prefill every prompt into a fresh slot pool (single-device or pp)."""
  B = len(prompts)
  cache = init_kv_cache(cfg, shard.n_shard_layers, B, MAX_SEQ)
  if ppb is not None:
    cache = ppb.place_cache(cache)
  firsts = []
  for r, p in enumerate(prompts):
    if ppb is not None:
      last, cache = ppb.prefill_into_slot(_pad(p), cache, r, len(p))
    else:
      last, cache = prefill_into_slot(params, cfg, shard, _pad(p), cache, jnp.int32(r), jnp.int32(len(p)))
    firsts.append(int(np.argmax(np.asarray(last)[0])))
  return cache, firsts


def _prefill_paged(params, cfg, shard, prompts, ppb=None):
  B = len(prompts)
  mp = MAX_SEQ // PS
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * mp, PS)
  if ppb is not None:
    pool = ppb.place_pool(pool)
  bt = np.zeros((B, mp), np.int32)
  firsts = []
  for r, p in enumerate(prompts):
    bt[r] = range(1 + r * mp, 1 + (r + 1) * mp)
    if ppb is not None:
      last, pool = ppb.prefill_into_pages(_pad(p), pool, bt[r], 0, len(p), PS)
    else:
      last, pool = prefill_into_pages(params, cfg, shard, _pad(p), pool, jnp.asarray(bt[r]), jnp.int32(0), jnp.int32(len(p)), PS)
    firsts.append(int(np.argmax(np.asarray(last)[0])))
  return pool, jnp.asarray(bt), firsts


@pytest.mark.parametrize("flavor", ["llama", "gemma2", "moe"])
@pytest.mark.parametrize("plan", [MeshPlan(pp=2), MeshPlan(pp=2, tp=2)], ids=["pp2", "pp2xtp2"])
def test_pp_batch_decode_matches_single_device(flavor, plan):
  cfg = _cfg(flavor)
  params, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  ppb = PPBatchedServing(build_mesh(plan), cfg, params, plan.pp)
  n_steps = 6

  cache_ref, firsts_ref = _prefill_dense(params, cfg, shard, PROMPTS)
  cache_pp, firsts_pp = _prefill_dense(params, cfg, shard, PROMPTS, ppb)
  assert firsts_pp == firsts_ref  # prefill logits agree

  tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
  pos = jnp.asarray([len(p) for p in PROMPTS], jnp.int32)
  active = jnp.asarray([True, True, True, False])
  temps = jnp.zeros((4,), jnp.float32)
  ref_toks, _, ref_pos, _ = fused_batch_decode(params, cfg, shard, tok, cache_ref, pos, active, temps, n_steps)
  pp_toks, _, pp_pos, _ = ppb.batch_decode(tok, cache_pp, pos, active, temps, jnp.full((4,), 35, jnp.int32), n_steps)
  np.testing.assert_array_equal(np.asarray(pp_toks), np.asarray(ref_toks))
  np.testing.assert_array_equal(np.asarray(pp_pos), np.asarray(ref_pos))


def test_pp_batch_decode_consecutive_chunks_stay_exact():
  """Two chained chunks (the scheduler's steady state): cache writes from the
  pipelined schedule must land exactly where the next chunk reads them."""
  cfg = _cfg()
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, "m")
  ppb = PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2)

  cache_ref, firsts = _prefill_dense(params, cfg, shard, PROMPTS)
  cache_pp, _ = _prefill_dense(params, cfg, shard, PROMPTS, ppb)
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  pos = jnp.asarray([len(p) for p in PROMPTS], jnp.int32)
  active = jnp.ones((4,), bool)
  temps = jnp.zeros((4,), jnp.float32)
  top_ks = jnp.full((4,), 35, jnp.int32)
  for _ in range(3):
    ref_toks, _, pos_ref, cache_ref = fused_batch_decode(params, cfg, shard, tok, cache_ref, pos, active, temps, 4)
    pp_toks, _, pos_pp, cache_pp = ppb.batch_decode(tok, cache_pp, pos, active, temps, top_ks, 4)
    np.testing.assert_array_equal(np.asarray(pp_toks), np.asarray(ref_toks))
    tok = jnp.asarray(np.asarray(ref_toks)[:, -1:])
    pos = pos_ref
  assert int(pos[0]) == len(PROMPTS[0]) + 12


@pytest.mark.parametrize("flavor", ["llama", "mla", "paired-heads"])
def test_pp_paged_batch_decode_matches_single_device(flavor):
  if flavor == "paired-heads":  # 4 / 2 heads of 64: pages that hold the two KV heads side by side on the lanes (ops/paged.py, ISSUE 58)
    cfg = tiny_test_config(n_layers=4, max_seq_len=MAX_SEQ, dim=256)
  elif flavor == "mla":
    cfg = tiny_test_config(
      n_layers=4, max_seq_len=MAX_SEQ, n_heads=4, n_kv_heads=4, kv_lora_rank=16,
      q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    )
  else:
    cfg = _cfg()
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  ppb = PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2)
  n_steps = 6

  pool_ref, bt, firsts_ref = _prefill_paged(params, cfg, shard, PROMPTS)
  pool_pp, _, firsts_pp = _prefill_paged(params, cfg, shard, PROMPTS, ppb)
  assert firsts_pp == firsts_ref

  tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
  pos = jnp.asarray([len(p) for p in PROMPTS], jnp.int32)
  active = jnp.asarray([True, True, False, True])
  temps = jnp.zeros((4,), jnp.float32)
  ref_toks, _, _, _ = fused_paged_batch_decode(params, cfg, shard, tok, pool_ref, bt, pos, active, temps, n_steps, page_size=PS, use_kernel=False)
  pp_toks, _, _, _ = ppb.paged_batch_decode(tok, pool_pp, bt, pos, active, temps, jnp.full((4,), 35, jnp.int32), n_steps, page_size=PS)
  np.testing.assert_array_equal(np.asarray(pp_toks), np.asarray(ref_toks))


@pytest.mark.parametrize("mla", [False, True], ids=["gqa", "mla"])
@pytest.mark.parametrize("paged", [False, True], ids=["dense-cache", "paged"])
def test_pp_batch_dense_prefix_moe_matches_single_device(paged, mla):
  """deepseek-style first_k_dense models through the batched pipeline: the
  dense prefix runs at stage 0 with a stage-owned cache — token-identical to
  the single-device fused paths (round-3 composition; previously refused).
  The mla variant is the REAL deepseek shape: MLA latent cache + dense
  prefix + MoE stack."""
  mla_kw = dict(n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16) if mla else {}
  cfg = tiny_test_config(
    n_layers=6, max_seq_len=MAX_SEQ, n_experts=4, n_active_experts=2,
    moe_hidden_dim=32, first_k_dense=2, **mla_kw,
  )
  params, shard = full_model_params(jax.random.PRNGKey(13), cfg, "m")
  ppb = PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2)
  assert ppb.n_prefix == 2
  n_steps = 6
  tok_args = (jnp.full((4,), 35, jnp.int32), n_steps)
  pos = jnp.asarray([len(p) for p in PROMPTS], jnp.int32)
  active = jnp.asarray([True, True, False, True])
  temps = jnp.zeros((4,), jnp.float32)
  if paged:
    pool_ref, bt, firsts_ref = _prefill_paged(params, cfg, shard, PROMPTS)
    pool_pp, _, firsts_pp = _prefill_paged(params, cfg, shard, PROMPTS, ppb)
    assert firsts_pp == firsts_ref
    tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
    ref_toks, _, _, pool_ref = fused_paged_batch_decode(params, cfg, shard, tok, pool_ref, bt, pos, active, temps, n_steps, page_size=PS, use_kernel=False)
    pp_toks, _, _, pool_pp = ppb.paged_batch_decode(tok, pool_pp, bt, pos, active, temps, *tok_args, page_size=PS)
  else:
    cache_ref, firsts_ref = _prefill_dense(params, cfg, shard, PROMPTS)
    cache_pp, firsts_pp = _prefill_dense(params, cfg, shard, PROMPTS, ppb)
    assert firsts_pp == firsts_ref
    tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
    ref_toks, _, _, cache_ref = fused_batch_decode(params, cfg, shard, tok, cache_ref, pos, active, temps, n_steps)
    pp_toks, _, _, cache_pp = ppb.batch_decode(tok, cache_pp, pos, active, temps, *tok_args)
  np.testing.assert_array_equal(np.asarray(pp_toks), np.asarray(ref_toks))
  # Second chunk: the prefix cache's decode-time writes (stage-owned slices)
  # must land where the next chunk reads them.
  tok2 = jnp.asarray(np.asarray(ref_toks)[:, -1:])
  pos2 = jnp.where(active, pos + n_steps, pos)
  if paged:
    ref2, _, _, _ = fused_paged_batch_decode(params, cfg, shard, tok2, pool_ref, bt, pos2, active, temps, n_steps, page_size=PS, use_kernel=False)
    pp2, _, _, _ = ppb.paged_batch_decode(tok2, pool_pp, bt, pos2, active, temps, *tok_args, page_size=PS)
  else:
    ref2, _, _, _ = fused_batch_decode(params, cfg, shard, tok2, cache_ref, pos2, active, temps, n_steps)
    pp2, _, _, _ = ppb.batch_decode(tok2, cache_pp, pos2, active, temps, *tok_args)
  np.testing.assert_array_equal(np.asarray(pp2), np.asarray(ref2))


def test_pp_batch_dense_prefix_paged_prefix_reuse_is_exact():
  """The scheduler's shared-prefix admission (prefill_into_pages with
  prefix_len > 0) through the dense-prefix pipeline: a request admitted on
  top of another's cached prompt pages produces the same last-token logits
  as the single-device path."""
  cfg = tiny_test_config(
    n_layers=6, max_seq_len=MAX_SEQ, n_experts=4, n_active_experts=2,
    moe_hidden_dim=32, first_k_dense=2,
  )
  params, shard = full_model_params(jax.random.PRNGKey(17), cfg, "m")
  ppb = PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2)
  rng = np.random.default_rng(2)
  mp = MAX_SEQ // PS
  prompt = rng.integers(0, cfg.vocab_size, size=(2 * PS + 4,)).astype(np.int32)

  def run(prefill_fn, pool):
    bt_full = np.zeros((mp,), np.int32)
    bt_full[:4] = [1, 2, 3, 4]
    pad = np.zeros((1, 48), np.int32)
    pad[0, : len(prompt)] = prompt
    last_full, pool = prefill_fn(jnp.asarray(pad), pool, jnp.asarray(bt_full), 0, len(prompt), PS)
    # Second request: same first 2 pages, different private tail.
    bt_new = np.zeros((mp,), np.int32)
    bt_new[:4] = [1, 2, 5, 6]
    suffix = np.zeros((1, 16), np.int32)
    suffix[0, :4] = prompt[2 * PS :]
    last_reuse, pool = prefill_fn(jnp.asarray(suffix), pool, jnp.asarray(bt_new), 2 * PS, len(prompt), PS)
    return np.asarray(last_full), np.asarray(last_reuse)

  pool_ref = init_paged_pool(cfg, shard.n_shard_layers, 8, PS)
  ref_fn = lambda t, pl, b, pre, pr, ps: prefill_into_pages(params, cfg, shard, t, pl, b, jnp.int32(pre), jnp.int32(pr), ps)
  ref_full, ref_reuse = run(ref_fn, pool_ref)
  pool_pp = ppb.place_pool(init_paged_pool(cfg, shard.n_shard_layers, 8, PS))
  pp_full, pp_reuse = run(ppb.prefill_into_pages, pool_pp)
  np.testing.assert_allclose(pp_full, ref_full, atol=2e-4)
  np.testing.assert_allclose(pp_reuse, ref_reuse, atol=2e-4)
  assert np.argmax(pp_reuse) == np.argmax(ref_reuse) == np.argmax(ref_full)


def test_supports_batched_allows_dense_prefix_moe_under_pp():
  """engine.supports_batched: PP composes with batching for every model
  family, dense-prefix MoE included (stage-owned prefix cache)."""
  cfg = tiny_test_config(n_layers=4, max_seq_len=MAX_SEQ, n_experts=4, n_active_experts=2, moe_hidden_dim=32, first_k_dense=2)
  params, shard = full_model_params(jax.random.PRNGKey(1), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is not None and engine._pp.n_prefix == 2
  assert engine.supports_batched()  # round 3: dense-prefix MoE composes too

  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(*((shard, cfg, params)))
  assert plain.supports_batched()


def test_batch_scheduler_serves_concurrently_over_pp(monkeypatch):
  """End-to-end: a pp=2 engine's batch scheduler (paged, the default) serves
  4 concurrent requests token-identically to solo single-device runs — the
  composition the round-2 engine refused (jax_engine get_batched_server)."""
  from tests.test_batched import _single_row_reference
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  cfg = _cfg()
  params, shard = full_model_params(jax.random.PRNGKey(5), cfg, "m")

  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is not None and engine.mesh.shape["pp"] == 2
  server = BatchedServer(engine, n_slots=3, chunk=2)  # rounds up to 4 (pp=2… still 4? 3→4)
  assert server.n_slots % 2 == 0

  n_gen = 5
  expected = [_single_row_reference(params, shard, p, n_gen - 1, cfg=cfg) for p in PROMPTS]

  async def run():
    return await asyncio.gather(
      *(
        server.submit(f"r{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
        for i, p in enumerate(PROMPTS)
      )
    )

  outs = asyncio.run(run())
  for i, out in enumerate(outs):
    assert out == expected[i], f"req {i}: {out} != {expected[i]}"


def test_chunked_prefill_over_pp(monkeypatch):
  """XOT_TPU_PREFILL_CHUNK composes with pp-batched paged serving: a long
  arrival prefills in chunks (the pp paged program natively resumes from
  prefix_lens) with decode ticks between, and output stays token-identical
  to solo greedy on the deep mesh too."""
  from tests.test_batched import _single_row_reference
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  monkeypatch.setenv("XOT_TPU_PREFILL_CHUNK", "16")
  cfg = _cfg()
  params, shard = full_model_params(jax.random.PRNGKey(23), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is not None and engine.mesh.shape["pp"] == 2

  server = BatchedServer(engine, n_slots=4, chunk=2)
  assert server.paged and server.prefill_chunk == 16

  events = []
  orig_prefill = server.ops.prefill_into_pages_many
  orig_decode = server.ops.paged_batch_decode
  server.ops.prefill_into_pages_many = lambda tokens, *a, **k: events.append("prefill") or orig_prefill(tokens, *a, **k)
  server.ops.paged_batch_decode = lambda *a, **k: events.append("decode") or orig_decode(*a, **k)

  long_prompt = [(7 * i) % 120 + 1 for i in range(48)]  # 3 chunks of 16
  short = [3, 25, 9]

  async def run():
    started = asyncio.Event()

    def emit(rid, toks, fin):
      if rid == "s":
        started.set()

    async def late_long():
      await started.wait()
      return await server.submit("L", np.asarray(long_prompt, np.int32), max_tokens=3, temp=0.0, top_k=35, eos_ids=(), emit=emit)

    return await asyncio.gather(
      server.submit("s", np.asarray(short, np.int32), max_tokens=12, temp=0.0, top_k=35, eos_ids=(), emit=emit),
      late_long(),
    )

  out_short, out_long = asyncio.run(run())
  assert out_short == _single_row_reference(params, shard, short, 11, cfg=cfg)
  assert out_long == _single_row_reference(params, shard, long_prompt, 2, cfg=cfg)
  assert events.count("prefill") >= 4, events  # short + >=3 chunks
  first, last = events.index("prefill"), len(events) - 1 - events[::-1].index("prefill")
  assert "decode" in events[first:last], events  # decode ticks BETWEEN chunks
