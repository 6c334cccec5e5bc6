"""Pipeline-parallel SERVING equivalence: pp=2 / pp=2×tp=2 / pp=4 KV-cached
decode must match the single-device engine token-for-token (the reference's
layer-split serving — ``reference/xotorch/orchestration/node.py:424-443`` —
rendered as shard_map + ppermute stages, parallel/pp_serving.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.inference.shard import Shard
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_decode,
  init_kv_cache,
  slice_shard_params,
)
from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
from xotorch_support_jetson_tpu.parallel.pp_serving import PPServing


def _reference_tokens(cfg, params, shard, prompt, n_steps):
  """Single-device greedy generation: prefill + fused_decode."""
  from xotorch_support_jetson_tpu.inference.jax_engine import _prefill

  B, S = prompt.shape
  cache = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  lens = jnp.full((B,), S, dtype=jnp.int32)
  logits, cache = _prefill(params, cfg, shard, jnp.asarray(prompt), cache, lens)
  first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
  toks, _ = fused_decode(params, cfg, shard, first, cache, jnp.full((B,), S, jnp.int32), n_steps)
  return np.asarray(first), np.asarray(toks)


def _pp_tokens(cfg, params, shard, prompt, n_steps, plan: MeshPlan):
  mesh = build_mesh(plan)
  pp = PPServing(mesh, cfg, params, plan.pp, shard.is_first_layer, shard.is_last_layer)
  B, S = prompt.shape
  cache = pp.place_cache(init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len))
  lens = jnp.full((B,), S, dtype=jnp.int32)
  logits, cache = pp.prefill(jnp.asarray(prompt), cache, lens)
  first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
  toks, _ = pp.fused_decode(first, cache, jnp.full((B,), S, jnp.int32), n_steps)
  return np.asarray(first), np.asarray(toks)


@pytest.mark.parametrize(
  "plan,dtype",
  [
    (MeshPlan(pp=2), jnp.float32),
    (MeshPlan(pp=2, tp=2), jnp.float32),
    (MeshPlan(pp=4), jnp.float32),
    # bf16 regression: XLA's CPU backend CHECK-crashed on a bf16 psum under
    # partial-auto shard_map on a multi-axis mesh until the f32-upcast
    # workaround in _pp_tick_loop (caught driving the daemon end-to-end —
    # real checkpoints load bf16, while these tests defaulted to f32).
    (MeshPlan(pp=2), jnp.bfloat16),
  ],
  ids=["pp2", "pp2xtp2", "pp4", "pp2-bf16"],
)
def test_pp_serving_matches_single_device(plan, dtype):
  cfg = tiny_test_config(n_layers=4, dtype=dtype)
  params, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)
  n_steps = 12

  ref_first, ref_toks = _reference_tokens(cfg, params, shard, prompt, n_steps)
  pp_first, pp_toks = _pp_tokens(cfg, params, shard, prompt, n_steps, plan)

  np.testing.assert_array_equal(pp_first, ref_first)
  np.testing.assert_array_equal(pp_toks, ref_toks)


def test_pp_step_decode_and_generate_match():
  """The engine's per-step path (infer_tensor semantics: prefill +
  decode_step) and the while_loop fused_generate, both under pp=2."""
  cfg = tiny_test_config(n_layers=4)
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, "m")
  prompt = np.array([[17, 4, 99]], dtype=np.int32)
  n_steps = 6

  ref_first, ref_toks = _reference_tokens(cfg, params, shard, prompt, n_steps)

  mesh = build_mesh(MeshPlan(pp=2, tp=2))
  pp = PPServing(mesh, cfg, params, 2, True, True)
  B, S = prompt.shape
  cache = pp.place_cache(init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len))
  logits, cache = pp.prefill(jnp.asarray(prompt), cache, jnp.full((B,), S, jnp.int32))
  tok = int(np.argmax(np.asarray(logits), axis=-1)[0])
  assert tok == int(ref_first[0, 0])
  got = []
  pos = S
  for _ in range(n_steps):
    logits, cache = pp.decode_step(jnp.asarray([[tok]], dtype=jnp.int32), cache, jnp.full((B,), pos, jnp.int32))
    tok = int(np.argmax(np.asarray(logits), axis=-1)[0])
    got.append(tok)
    pos += 1
  np.testing.assert_array_equal(np.asarray([got]), ref_toks)

  # fused_generate (no EOS in range -> runs exactly n_steps)
  cache2 = pp.place_cache(init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len))
  _, cache2 = pp.prefill(jnp.asarray(prompt), cache2, jnp.full((B,), S, jnp.int32))
  buf, n, cache2 = pp.fused_generate(ref_first, cache2, jnp.full((B,), S, jnp.int32), n_steps, eos_ids=(-1,))
  np.testing.assert_array_equal(np.asarray(buf)[:, :n_steps], ref_toks)


def test_pp_partial_shard_hidden_in_out():
  """A ring node owning layers [1..2] of 4 can pp its own range: hidden-state
  in, hidden-state out must match the single-device partial-shard forward."""
  from xotorch_support_jetson_tpu.models.decoder import shard_forward

  cfg = tiny_test_config(n_layers=4)
  full_params, full_shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  sub = Shard("m", 1, 2, 4)
  sub_params = slice_shard_params(full_params, cfg, full_shard, sub)

  B, S, D = 1, 4, cfg.dim
  h_in = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (B, S, D), dtype=jnp.float32))
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

  cache = init_kv_cache(cfg, sub.n_shard_layers, B, cfg.max_seq_len)
  ref_h, _ = shard_forward(sub_params, cfg, sub, jnp.asarray(h_in), positions, cache)

  mesh = build_mesh(MeshPlan(pp=2))
  pp = PPServing(mesh, cfg, sub_params, 2, sub.is_first_layer, sub.is_last_layer)
  cache2 = pp.place_cache(init_kv_cache(cfg, sub.n_shard_layers, B, cfg.max_seq_len))
  pp_h, _ = pp.prefill(jnp.asarray(h_in), cache2, jnp.full((B,), S, jnp.int32))

  np.testing.assert_allclose(np.asarray(pp_h), np.asarray(ref_h), rtol=1e-5, atol=1e-5)


@pytest.mark.asyncio
async def test_engine_pp_mode_matches_plain_engine():
  """End-to-end engine path: XOT_TPU_PP=2 engine vs plain engine, same tokens
  through infer_tensor (prefill + 3 decode steps) and generate_oneshot."""
  cfg = tiny_test_config(n_layers=4)
  params, shard = full_model_params(jax.random.PRNGKey(21), cfg, "m")
  tokens = np.array([[3, 14, 15, 92, 65]], dtype=np.int32)

  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(shard, cfg, params)
  ref_logits, ref_state = await plain.infer_tensor("a", shard, tokens)

  pped = JaxShardedInferenceEngine(use_local_mesh=False, pp=2)
  pped.load_test_model(shard, cfg, params)
  pped._maybe_shard_over_local_mesh()
  assert pped._pp is not None and pped.mesh.shape["pp"] == 2
  pp_logits, pp_state = await pped.infer_tensor("a", shard, tokens)
  np.testing.assert_array_equal(np.argmax(pp_logits, -1), np.argmax(ref_logits, -1))

  cur = np.argmax(ref_logits, axis=-1).astype(np.int32).reshape(1, 1)
  for _ in range(3):
    ref_logits, ref_state = await plain.infer_tensor("a", shard, cur, ref_state)
    pp_logits, pp_state = await pped.infer_tensor("a", shard, cur, pp_state)
    np.testing.assert_array_equal(np.argmax(pp_logits, -1), np.argmax(ref_logits, -1))
    cur = np.argmax(ref_logits, axis=-1).astype(np.int32).reshape(1, 1)

  # generate_oneshot through the pp engine (greedy; no eos hit)
  ref_toks = await plain.generate_oneshot("a", shard, int(cur[0, 0]), 5, eos_ids=(-1,), temp=0.0)
  pp_toks = await pped.generate_oneshot("a", shard, int(cur[0, 0]), 5, eos_ids=(-1,), temp=0.0)
  assert ref_toks == pp_toks


@pytest.mark.parametrize("plan", [MeshPlan(pp=2), MeshPlan(pp=2, tp=2)])
def test_pp_serving_dense_prefix_moe_matches(plan):
  """Deepseek-style dense-prefix MoE (+MLA) through PP serving: the prefix
  runs replicated on every stage, the MoE stack pipelines — token-identical
  to the single-device engine."""

  cfg = tiny_test_config(
    n_layers=5, max_seq_len=64, n_heads=4, n_kv_heads=4,
    n_experts=4, n_active_experts=2, moe_hidden_dim=32, shared_expert_dim=32,
    first_k_dense=1,  # 1 dense prefix layer + 4 pipelined MoE layers
    kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
  )
  params, shard = full_model_params(jax.random.PRNGKey(15), cfg, "ds-pp")
  prompt = np.array([[3, 25, 9, 77]], dtype=np.int32)
  with jax.default_matmul_precision("highest"):
    ref_first, ref_toks = _reference_tokens(cfg, params, shard, prompt, 10)
    pp_first, pp_toks = _pp_tokens(cfg, params, shard, prompt, 10, plan)
  assert np.array_equal(ref_first, pp_first)
  assert np.array_equal(ref_toks, pp_toks)
