"""Sequence-parallel serving tests (parallel/sp_serving.py).

Correctness claim: prefill + decode with the KV cache sharded over sp (and
partial online-softmax stats merged over the axis) are TOKEN-IDENTICAL to the
single-device engine — for dense GQA and for MLA (the absorbed-attention
merge composes with sp because the per-head up-projection applies after the
cross-rank merge; this closes the round-1 "ring attention is training-only
and doesn't compose with MLA" gap for the serving side).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_decode, init_kv_cache, shard_forward
from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
from xotorch_support_jetson_tpu.parallel.sp_serving import SPServing

DENSE = tiny_test_config(n_layers=2, max_seq_len=128)
MLA = tiny_test_config(
  n_layers=2, max_seq_len=128, n_heads=4, n_kv_heads=4, kv_lora_rank=16,
  q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
)
GEMMA = tiny_test_config(
  n_layers=2, max_seq_len=128, post_norms=True, mlp_act="gelu_tanh",
  attn_logit_softcap=50.0, final_logit_softcap=30.0, query_pre_attn_scalar=24.0,
  sliding_window=4, embed_scale=8.0, tied_embedding=True,
)


def _reference(params, cfg, shard, prompt, n_steps):
  S = len(prompt)
  tokens = jnp.asarray([prompt], jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
  cache = init_kv_cache(cfg, cfg.n_layers, 1, 64)
  logits, cache = shard_forward(params, cfg, shard, tokens, positions, cache)
  first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
  toks, _ = fused_decode(params, cfg, shard, first, cache, jnp.full((1,), S, jnp.int32), n_steps)
  return int(first[0, 0]), np.asarray(toks)[0]


@pytest.mark.parametrize("cfg,sp_n", [(DENSE, 2), (DENSE, 4), (MLA, 2), (MLA, 4), (GEMMA, 2)])
def test_sp_serving_matches_single_device(cfg, sp_n):
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "tiny")
  prompt = [3, 25, 9, 77, 2]
  S = len(prompt)
  first_ref, ref = _reference(params, cfg, shard, prompt, 10)

  mesh = build_mesh(MeshPlan(sp=sp_n))
  sps = SPServing(mesh, cfg, params, sp_n, True, True)
  cache = sps.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))
  tok_pad = np.zeros((1, 8), np.int32)
  tok_pad[0, :S] = prompt
  last, cache = sps.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), S, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  assert int(first[0, 0]) == first_ref
  toks, cache = sps.fused_decode(first, cache, jnp.full((1,), S, jnp.int32), 10)
  assert np.array_equal(np.asarray(toks)[0], ref)


def test_sp_fused_generate_and_decode_step_match():
  cfg = DENSE
  params, shard = full_model_params(jax.random.PRNGKey(1), cfg, "tiny")
  prompt = [7, 1, 88, 42]
  S = len(prompt)
  first_ref, ref = _reference(params, cfg, shard, prompt, 6)

  mesh = build_mesh(MeshPlan(sp=2))
  sps = SPServing(mesh, cfg, params, 2, True, True)
  tok_pad = np.zeros((1, 8), np.int32)
  tok_pad[0, :S] = prompt

  # fused_generate (while_loop path)
  cache = sps.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))
  last, cache = sps.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), S, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  buf, n, cache = sps.fused_generate(first, cache, jnp.full((1,), S, jnp.int32), 6, eos_ids=(-1,))
  assert np.array_equal(np.asarray(buf)[0][:6], ref)

  # per-step decode path
  cache = sps.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))
  last, cache = sps.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), S, jnp.int32))
  tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  got = []
  pos = S
  for _ in range(6):
    logits, cache = sps.decode_step(tok, cache, jnp.full((1,), pos, jnp.int32))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    got.append(int(tok[0, 0]))
    pos += 1
  assert got == [int(t) for t in ref]


def test_engine_sp_mode_serves(monkeypatch):
  """XOT_TPU_SP engine mode: the engine builds SPServing and the fused
  serving path matches the plain engine."""

  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  cfg = DENSE
  params, shard = full_model_params(jax.random.PRNGKey(2), cfg, "tiny")
  _, ref = _reference(params, cfg, shard, [5, 17, 2, 99], 7)

  monkeypatch.setenv("XOT_TPU_SP", "2")
  eng = JaxShardedInferenceEngine(use_local_mesh=False)
  eng.load_test_model(shard, cfg, jax.tree.map(jnp.copy, params))
  eng._maybe_shard_over_local_mesh()
  assert eng._pp is not None and eng.params is None  # SPServing rides the mesh-serving slot
  cache = eng._pp.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))
  tok_pad = np.zeros((1, 8), np.int32)
  tok_pad[0, :4] = [5, 17, 2, 99]
  last, cache = eng._pp.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), 4, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  toks, _ = eng._pp.fused_decode(first, cache, jnp.full((1,), 4, jnp.int32), 7)
  assert np.array_equal(np.asarray(toks)[0], ref)


def test_sp_decode_spans_all_rank_chunks():
  """Decode far past rank 0's chunk (sp=4, Sloc=16, 40 steps → position 51):
  writes land on every rank and non-masked partials from all ranks merge —
  still token-identical to the single-device decode."""
  cfg = DENSE
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, "tiny")
  prompt = [9, 9, 9, 1, 42, 7, 3, 25, 100, 2, 11]
  S = len(prompt)
  _, ref = _reference(params, cfg, shard, prompt, 40)

  mesh = build_mesh(MeshPlan(sp=4))
  sps = SPServing(mesh, cfg, params, 4, True, True)
  cache = sps.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))  # Sloc = 16
  tok_pad = np.zeros((1, 16), np.int32)
  tok_pad[0, :S] = prompt
  last, cache = sps.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), S, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  toks, _ = sps.fused_decode(first, cache, jnp.full((1,), S, jnp.int32), 40)
  assert np.array_equal(np.asarray(toks)[0], ref)


@pytest.mark.parametrize("cfg,plan", [
  (DENSE, MeshPlan(sp=2, tp=2)),
  (DENSE, MeshPlan(sp=2, tp=4)),
  (MLA, MeshPlan(sp=2, tp=2)),
  (GEMMA, MeshPlan(sp=2, tp=2)),
], ids=["dense-sp2tp2", "dense-sp2tp4", "mla-sp2tp2", "gemma-sp2tp2"])
def test_sp_tp_composed_matches_and_shards_weights(cfg, plan):
  """sp x tp composition (VERDICT r2 #3): weights shard over tp (per-rank
  weight bytes ~1/tp of replicated) while the cache shards over sp — and the
  decoded tokens still match the single device exactly."""
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "tiny")
  prompt = [3, 25, 9, 77, 2]
  S = len(prompt)
  first_ref, ref = _reference(params, cfg, shard, prompt, 10)

  mesh = build_mesh(plan)
  sps = SPServing(mesh, cfg, params, plan.sp, True, True)
  # Megatron column-parallel wq: each device holds 1/tp of the leaf (and the
  # sp axis replicates it — the round-2 design held 1/1 on every rank).
  stack = sps.params["layers"]
  wq = stack["wq"] if "wq" in stack else stack["wq_b"]  # MLA: per-head up-proj is the column-parallel leaf
  assert wq.addressable_shards[0].data.nbytes == wq.nbytes // plan.tp
  # The cache shards over sp on the sequence axis.
  cache = sps.place_cache(init_kv_cache(cfg, cfg.n_layers, 1, 64))
  assert cache["k"].addressable_shards[0].data.shape[2] == 64 // plan.sp

  tok_pad = np.zeros((1, 8), np.int32)
  tok_pad[0, :S] = prompt
  last, cache = sps.prefill(jnp.asarray(tok_pad), cache, jnp.full((1,), S, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  assert int(first[0, 0]) == first_ref
  toks, cache = sps.fused_decode(first, cache, jnp.full((1,), S, jnp.int32), 10)
  assert np.array_equal(np.asarray(toks)[0], ref)


def test_sp_batched_decode_matches_single_device():
  """SP x batched composition (parallel/sp_batch.py): the slot pool's fused
  chunk decode with the cache sharded over sp is token-identical to the
  single-device fused_batch_decode — concurrent long-context streams."""
  from xotorch_support_jetson_tpu.models.decoder import fused_batch_decode, prefill_into_slot
  from xotorch_support_jetson_tpu.parallel.sp_batch import SPBatchedServing

  cfg = DENSE
  params, shard = full_model_params(jax.random.PRNGKey(21), cfg, "tiny")
  mesh = build_mesh(MeshPlan(sp=2, tp=2))
  spb = SPBatchedServing(SPServing(mesh, cfg, params, 2, True, True))

  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  B, max_seq, n_steps = 4, 64, 6
  cache_ref = init_kv_cache(cfg, cfg.n_layers, B, max_seq)
  cache_sp = spb.place_cache(init_kv_cache(cfg, cfg.n_layers, B, max_seq))
  firsts_ref, firsts_sp = [], []
  for r, p in enumerate(prompts):
    pad = np.zeros((1, 16), np.int32)
    pad[0, : len(p)] = p
    last_r, cache_ref = prefill_into_slot(params, cfg, shard, jnp.asarray(pad), cache_ref, jnp.int32(r), jnp.int32(len(p)))
    last_s, cache_sp = spb.prefill_into_slot(jnp.asarray(pad), cache_sp, r, len(p))
    firsts_ref.append(int(np.argmax(np.asarray(last_r)[0])))
    firsts_sp.append(int(np.argmax(np.asarray(last_s)[0])))
  assert firsts_sp == firsts_ref

  tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
  pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.asarray([True, True, False, True])
  temps = jnp.zeros((B,), jnp.float32)
  top_ks = jnp.full((B,), 35, jnp.int32)
  for _ in range(2):  # two chained chunks: writes land where the next reads
    ref_toks, _, pos_ref, cache_ref = fused_batch_decode(params, cfg, shard, tok, cache_ref, pos, active, temps, n_steps)
    sp_toks, _, pos_sp, cache_sp = spb.batch_decode(tok, cache_sp, pos, active, temps, top_ks, n_steps)
    np.testing.assert_array_equal(np.asarray(sp_toks), np.asarray(ref_toks))
    np.testing.assert_array_equal(np.asarray(pos_sp), np.asarray(pos_ref))
    tok = jnp.asarray(np.asarray(ref_toks)[:, -1:])
    pos = pos_ref


def test_sp_batched_through_scheduler(monkeypatch):
  """End-to-end: an XOT_TPU_SP=2 engine's batch scheduler (dense cache,
  XOT_TPU_PAGED=0) serves concurrent requests token-identically to solo
  runs. (The default paged mode composes too — tests/test_sp_paged.py.)"""
  import asyncio

  from tests.test_batched import _single_row_reference
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  monkeypatch.setenv("XOT_TPU_SP", "2")
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  cfg = DENSE
  params, shard = full_model_params(jax.random.PRNGKey(23), cfg, "tiny")
  engine = JaxShardedInferenceEngine(use_local_mesh=True)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert isinstance(engine._pp, SPServing)
  assert engine.supports_batched()
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  assert engine.supports_batched()  # striped paged pool composes with sp now
  monkeypatch.setenv("XOT_TPU_PAGED", "0")

  server = BatchedServer(engine, n_slots=4, chunk=2)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  n_gen = 5
  expected = [_single_row_reference(params, shard, p, n_gen - 1, cfg=cfg) for p in prompts]

  async def run():
    return await asyncio.gather(
      *(
        server.submit(f"sp{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
        for i, p in enumerate(prompts)
      )
    )

  outs = asyncio.run(run())
  for i, out in enumerate(outs):
    assert out == expected[i], f"req {i}: {out} != {expected[i]}"


def test_supports_batched_requires_full_model_shard(monkeypatch):
  """A ring member serving a partial layer range must NOT route into the
  batched mesh paths (they embed tokens and run the head): supports_batched
  returns False so the Node falls back to plain mesh serving."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.inference.shard import Shard

  monkeypatch.setenv("XOT_TPU_SP", "2")
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  cfg = DENSE
  params, full = full_model_params(jax.random.PRNGKey(29), cfg, "tiny")
  from xotorch_support_jetson_tpu.models.decoder import slice_shard_params

  partial = Shard("tiny", 1, cfg.n_layers - 1, cfg.n_layers)  # last but not first
  engine = JaxShardedInferenceEngine(use_local_mesh=True)
  engine.load_test_model(partial, cfg, slice_shard_params(params, cfg, full, partial))
  engine._maybe_shard_over_local_mesh()
  assert isinstance(engine._pp, SPServing) and not engine._pp.is_first
  assert not engine.supports_batched()
