"""Parallel-layer tests on the 8-device virtual CPU mesh.

Covers the full §2.11-and-beyond matrix: TP shardings (GSPMD), pipeline
(shard_map + ppermute with microbatching), ring attention (sp), and the
composed dp×pp×sp×tp train step — all checked numerically against the
single-device decoder.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.shard import Shard
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params, shard_forward
from xotorch_support_jetson_tpu.ops.attention import gqa_attention
from xotorch_support_jetson_tpu.parallel import (
  MeshPlan,
  auto_plan,
  build_mesh,
  make_forward_fn,
  make_sharded_ring_attention,
  make_train_step,
  shard_batch,
  shard_params,
  stack_stage_params,
  unstack_stage_params,
)

CFG = tiny_test_config(n_layers=4)
KEY = jax.random.PRNGKey(0)


def _ref_logits(params, tokens):
  shard = Shard("m", 0, CFG.n_layers - 1, CFG.n_layers)
  positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
  logits, _ = shard_forward(params, CFG, shard, tokens, positions, None)
  return np.asarray(logits)


def test_auto_plan_respects_kv_heads():
  plan = auto_plan(8, n_kv_heads=2)
  assert plan.tp == 2 and plan.dp == 4
  plan = auto_plan(8, n_kv_heads=16)
  assert plan.tp == 8 and plan.dp == 1


def test_mesh_build_and_param_sharding():
  plan = MeshPlan(dp=2, tp=2, pp=2)
  mesh = build_mesh(plan)
  params, _ = full_model_params(KEY, CFG)
  sharded = shard_params(params, mesh)
  assert sharded["layers"]["wq"].sharding.spec[-1] == "tp"
  # Same values after sharding.
  np.testing.assert_array_equal(np.asarray(sharded["layers"]["wq"]), np.asarray(params["layers"]["wq"]))


def test_stack_unstack_roundtrip():
  params, _ = full_model_params(KEY, CFG)
  stacked = stack_stage_params(params["layers"], 2)
  assert stacked["wq"].shape[:2] == (2, 2)
  rt = unstack_stage_params(stacked)
  np.testing.assert_array_equal(np.asarray(rt["wq"]), np.asarray(params["layers"]["wq"]))


def test_pipeline_forward_matches_single_device():
  plan = MeshPlan(pp=4)
  mesh = build_mesh(plan)
  params, _ = full_model_params(KEY, CFG)
  tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, CFG.vocab_size, dtype=jnp.int32)

  forward = make_forward_fn(mesh, CFG, plan, n_micro=2, remat=False)
  with jax.default_matmul_precision("highest"):
    logits, _ = jax.jit(forward)(params, tokens, jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (4, 8)))
  np.testing.assert_allclose(np.asarray(logits), _ref_logits(params, tokens), rtol=2e-4, atol=2e-4)


def test_pipeline_with_tp_dp_matches():
  plan = MeshPlan(dp=2, pp=2, tp=2)
  mesh = build_mesh(plan)
  params, _ = full_model_params(KEY, CFG)
  sharded = shard_params(params, mesh)
  tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 8), 0, CFG.vocab_size, dtype=jnp.int32)

  forward = make_forward_fn(mesh, CFG, plan, n_micro=2, remat=False)
  with jax.default_matmul_precision("highest"):
    logits, _ = jax.jit(forward)(sharded, tokens, jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (4, 8)))
  np.testing.assert_allclose(np.asarray(logits), _ref_logits(params, tokens), rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_dense():
  plan = MeshPlan(sp=4)
  mesh = build_mesh(plan)
  B, S, Hq, Hkv, hd = 2, 16, 4, 2, 8
  ks = jax.random.split(jax.random.PRNGKey(7), 3)
  q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32)
  k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
  v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
  q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  kv_pos = jnp.arange(S, dtype=jnp.int32)

  dense = gqa_attention(q, k, v, q_pos, kv_pos)
  ring_fn = make_sharded_ring_attention(mesh)
  with jax.default_matmul_precision("highest"):
    ring = ring_fn(q, k, v, q_pos, kv_pos)
  np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), rtol=2e-4, atol=2e-4)


def test_ring_sp_forward_matches():
  plan = MeshPlan(sp=2, pp=2)
  mesh = build_mesh(plan)
  params, _ = full_model_params(KEY, CFG)
  tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 16), 0, CFG.vocab_size, dtype=jnp.int32)
  forward = make_forward_fn(mesh, CFG, plan, n_micro=1, ring_sp=True, remat=False)
  with jax.default_matmul_precision("highest"):
    logits, _ = jax.jit(forward)(params, tokens, jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16)))
  np.testing.assert_allclose(np.asarray(logits), _ref_logits(params, tokens), rtol=2e-4, atol=2e-4)


def test_ring_sp_forward_matches_gemma2():
  """gemma2 trains under ring sequence parallelism: the scale override,
  logit softcap, and per-layer sliding window are per-score transforms that
  commute with the ring's blockwise merge (the former NotImplementedError
  guard is gone)."""
  gcfg = tiny_test_config(
    n_layers=4, post_norms=True, mlp_act="gelu_tanh", attn_logit_softcap=50.0,
    final_logit_softcap=30.0, query_pre_attn_scalar=24.0, sliding_window=4,
    embed_scale=8.0, tied_embedding=True,
  )
  plan = MeshPlan(sp=2)
  mesh = build_mesh(plan)
  params, shard = full_model_params(jax.random.PRNGKey(17), gcfg, "g")
  tokens = jax.random.randint(jax.random.PRNGKey(19), (2, 16), 0, gcfg.vocab_size, dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
  forward = make_forward_fn(mesh, gcfg, plan, n_micro=1, ring_sp=True, remat=False)
  with jax.default_matmul_precision("highest"):
    logits, _ = jax.jit(forward)(params, tokens, positions)
    ref, _ = shard_forward(params, gcfg, shard, tokens, positions, None)
  np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=3e-4, atol=3e-4)


def test_full_train_step_dp_pp_sp_tp():
  """One composed dp×pp×sp×tp training step: runs, loss finite, params move."""

  plan = MeshPlan(dp=2, pp=2, sp=1, tp=2)
  mesh = build_mesh(plan)
  params, _ = full_model_params(KEY, CFG)
  params = shard_params(params, mesh)

  init_fn, step_fn = make_train_step(mesh, CFG, plan, n_micro=2, remat=True)
  opt_state = init_fn(params)
  B, S = 4, 8
  rng = np.random.default_rng(0)
  batch = shard_batch(
    {
      "inputs": rng.integers(0, CFG.vocab_size, (B, S)).astype(np.int32),
      "targets": rng.integers(0, CFG.vocab_size, (B, S)).astype(np.int32),
      "mask": np.ones((B, S), np.float32),
    },
    mesh,
  )
  w_before = np.asarray(jax.device_get(params["layers"]["wq"]))
  params, opt_state, loss = step_fn(params, opt_state, batch)
  loss = float(loss)
  assert np.isfinite(loss) and loss > 0
  w_after = np.asarray(jax.device_get(params["layers"]["wq"]))
  assert not np.allclose(w_before, w_after)

  # Second step reuses the compiled program and further changes the loss.
  params, opt_state, loss2 = step_fn(params, opt_state, batch)
  assert np.isfinite(float(loss2))
  assert float(loss2) != loss


def test_moe_ep_forward_matches_single_device():
  """MoE forward under dp×ep×tp == unsharded MoE forward (EP correctness)."""
  moe_cfg = tiny_test_config(
    n_layers=4, n_experts=4, n_active_experts=2, moe_hidden_dim=32,
    shared_expert_dim=32, first_k_dense=1,
  )
  params, _ = full_model_params(jax.random.PRNGKey(7), moe_cfg)
  tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 8), 0, moe_cfg.vocab_size, dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))


  shard = Shard("moe", 0, moe_cfg.n_layers - 1, moe_cfg.n_layers)
  with jax.default_matmul_precision("highest"):
    ref, _ = shard_forward(params, moe_cfg, shard, tokens, positions, None)

    plan = MeshPlan(dp=2, ep=2, tp=2)
    mesh = build_mesh(plan)
    sharded = shard_params(params, mesh)
    forward = make_forward_fn(mesh, moe_cfg, plan, n_micro=1, remat=False)
    logits, _ = jax.jit(forward)(sharded, tokens, positions)
  np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_moe_ep_train_step():
  """Composed dp×ep×tp MoE training step: loss finite, expert weights move."""
  moe_cfg = tiny_test_config(
    n_layers=2, n_experts=4, n_active_experts=2, moe_hidden_dim=32, first_k_dense=0,
  )
  plan = MeshPlan(dp=2, ep=2, tp=2)
  mesh = build_mesh(plan)
  params, _ = full_model_params(jax.random.PRNGKey(9), moe_cfg)
  params = shard_params(params, mesh)

  init_fn, step_fn = make_train_step(mesh, moe_cfg, plan, n_micro=1, remat=True)
  opt_state = init_fn(params)
  B, S = 4, 8
  rng = np.random.default_rng(1)
  batch = shard_batch(
    {
      "inputs": rng.integers(0, moe_cfg.vocab_size, (B, S)).astype(np.int32),
      "targets": rng.integers(0, moe_cfg.vocab_size, (B, S)).astype(np.int32),
      "mask": np.ones((B, S), np.float32),
    },
    mesh,
  )
  w_before = np.asarray(jax.device_get(params["moe_layers"]["w_experts_gate"]))
  params, opt_state, loss = step_fn(params, opt_state, batch)
  assert np.isfinite(float(loss))
  w_after = np.asarray(jax.device_get(params["moe_layers"]["w_experts_gate"]))
  assert not np.allclose(w_before, w_after)


def test_ring_attention_mla_unequal_v_dim_matches():
  """Ring attention with v head dim != q/k head dim (MLA's naive training
  K/V: qk 192 vs v 128 on deepseek) — closes the round-1 'ring attention
  assumes equal k/v head dims' limitation."""
  mesh = build_mesh(MeshPlan(sp=4))
  B, S, Hq, Hkv, hd, hd_v = 2, 16, 4, 2, 24, 16
  ks = jax.random.split(jax.random.PRNGKey(8), 3)
  q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32)
  k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
  v = jax.random.normal(ks[2], (B, S, Hkv, hd_v), jnp.float32)
  q_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  kv_pos = jnp.arange(S, dtype=jnp.int32)

  dense = gqa_attention(q, k, v, q_pos, kv_pos)
  ring_fn = make_sharded_ring_attention(mesh)
  with jax.default_matmul_precision("highest"):
    ring = ring_fn(q, k, v, q_pos, kv_pos)
  assert ring.shape == (B, S, Hq, hd_v)
  np.testing.assert_allclose(np.asarray(ring), np.asarray(dense), rtol=2e-4, atol=2e-4)


def test_ring_sp_forward_matches_mla():
  """Full forward with ring sp on an MLA model (naive training K/V path):
  the sp-sharded pipeline matches the dense reference."""
  mla_cfg = tiny_test_config(
    n_layers=4, n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
  )
  mesh = build_mesh(MeshPlan(sp=2, pp=2))
  params, _ = full_model_params(jax.random.PRNGKey(16), mla_cfg)
  tokens = jax.random.randint(jax.random.PRNGKey(17), (2, 16), 0, mla_cfg.vocab_size, dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
  forward = make_forward_fn(mesh, mla_cfg, MeshPlan(sp=2, pp=2), n_micro=1, ring_sp=True, remat=False)
  with jax.default_matmul_precision("highest"):
    logits, _ = jax.jit(forward)(params, tokens, positions)
  shard = Shard("mla-ring", 0, mla_cfg.n_layers - 1, mla_cfg.n_layers)
  ref, _ = shard_forward(params, mla_cfg, shard, tokens, positions, None)
  np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), rtol=2e-4, atol=2e-4)
