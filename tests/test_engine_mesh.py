"""Engine in-slice TP: sharded-over-mesh engine must match single-device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params


@pytest.mark.asyncio
async def test_engine_local_mesh_matches_single_device():
  cfg = tiny_test_config(n_layers=2)
  params, shard = full_model_params(jax.random.PRNGKey(5), cfg, "m")
  tokens = np.array([[3, 14, 15, 92]], dtype=np.int32)

  with jax.default_matmul_precision("highest"):
    plain = JaxShardedInferenceEngine(use_local_mesh=False)
    plain.load_test_model(shard, cfg, params)
    ref_logits, ref_state = await plain.infer_tensor("a", shard, tokens)

    meshed = JaxShardedInferenceEngine(use_local_mesh=True)
    meshed.load_test_model(shard, cfg, params)
    meshed._maybe_shard_over_local_mesh()
    assert meshed.mesh is not None and meshed.mesh.shape["tp"] == 4  # 4 q heads
    mesh_logits, mesh_state = await meshed.infer_tensor("a", shard, tokens)

    np.testing.assert_allclose(mesh_logits, ref_logits, rtol=2e-4, atol=2e-4)

    # One decode step on both paths.
    nxt = np.argmax(ref_logits, axis=-1).astype(np.int32).reshape(1, 1)
    ref2, _ = await plain.infer_tensor("a", shard, nxt, ref_state)
    mesh2, _ = await meshed.infer_tensor("a", shard, nxt, mesh_state)
    np.testing.assert_allclose(mesh2, ref2, rtol=2e-4, atol=2e-4)


@pytest.mark.asyncio
async def test_engine_local_mesh_moe_ep_sharding_matches():
  """MoE model through the serving mesh: the plan splits chips ep x tp,
  expert weights shard over ep (GSPMD all-to-alls), and logits match the
  single-device engine."""
  cfg = tiny_test_config(
    n_layers=2, n_experts=4, n_active_experts=2, moe_hidden_dim=32,
    shared_expert_dim=32, first_k_dense=1,
  )
  params, shard = full_model_params(jax.random.PRNGKey(9), cfg, "moe-mesh")
  tokens = np.array([[3, 14, 15, 92]], dtype=np.int32)

  with jax.default_matmul_precision("highest"):
    plain = JaxShardedInferenceEngine(use_local_mesh=False)
    plain.load_test_model(shard, cfg, params)
    ref_logits, ref_state = await plain.infer_tensor("a", shard, tokens)

    meshed = JaxShardedInferenceEngine(use_local_mesh=True)
    meshed.load_test_model(shard, cfg, params)
    meshed._maybe_shard_over_local_mesh()
    assert meshed.mesh is not None
    assert meshed.mesh.shape["ep"] == 4  # 4 experts -> ep=4 on 8 devices
    assert meshed.mesh.shape["tp"] == 2
    mesh_logits, mesh_state = await meshed.infer_tensor("a", shard, tokens)
    np.testing.assert_allclose(mesh_logits, ref_logits, rtol=2e-4, atol=2e-4)

    nxt = np.argmax(ref_logits, axis=-1).astype(np.int32).reshape(1, 1)
    ref2, _ = await plain.infer_tensor("a", shard, nxt, ref_state)
    mesh2, _ = await meshed.infer_tensor("a", shard, nxt, mesh_state)
    np.testing.assert_allclose(mesh2, ref2, rtol=2e-4, atol=2e-4)


def test_inference_plan_ep_requires_expert_divisibility():
  """A 60-expert model must not get ep=8 (60 % 8 != 0 would crash
  device_put); the plan backs off to the largest dividing power of 2."""
  from xotorch_support_jetson_tpu.parallel.mesh import inference_plan, pow2_degree

  plan = inference_plan(8, n_heads=16, n_experts=60)
  assert plan.ep == 4 and 60 % plan.ep == 0
  assert plan.tp == 2 and plan.n_devices <= 8
  assert inference_plan(8, n_heads=16, n_experts=64).ep == 8
  assert inference_plan(8, n_heads=16, n_experts=0).ep == 1
  assert pow2_degree(8, 3) == 2  # limit caps below device count
  assert pow2_degree(6, 16) == 2  # degree must divide the device count


def test_batched_decode_over_local_mesh_matches():
  """The pooled batch-decode path with GSPMD-sharded params (use_local_mesh
  TP) == the unsharded pool: the batched server composes with in-slice TP."""
  from xotorch_support_jetson_tpu.models.decoder import fused_batch_decode, init_kv_cache, prefill_into_slot
  from xotorch_support_jetson_tpu.parallel.mesh import build_mesh, inference_plan, shard_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(12), cfg, "m")
  mesh = build_mesh(inference_plan(8, n_heads=cfg.n_heads))
  sharded = shard_params(jax.tree.map(jnp.copy, params), mesh)

  prompts = [[3, 25, 9], [7, 1, 88, 42, 5]]
  outs = []
  with jax.default_matmul_precision("highest"):
    for p in (params, sharded):
      cache = init_kv_cache(cfg, cfg.n_layers, 2, 64)
      firsts = []
      for r, prompt in enumerate(prompts):
        pad = np.zeros((1, 16), np.int32)
        pad[0, : len(prompt)] = prompt
        last, cache = prefill_into_slot(p, cfg, shard, jnp.asarray(pad), cache, jnp.int32(r), jnp.int32(len(prompt)))
        firsts.append(int(np.argmax(np.asarray(last)[0])))
      tok = jnp.asarray([[f] for f in firsts], jnp.int32)
      pos = jnp.asarray([len(x) for x in prompts], jnp.int32)
      act = jnp.ones((2,), bool)
      temps = jnp.zeros((2,), jnp.float32)
      toks, _, _, _ = fused_batch_decode(p, cfg, shard, tok, cache, pos, act, temps, 10)
      outs.append((firsts, np.asarray(toks)))
  assert outs[0][0] == outs[1][0]
  assert np.array_equal(outs[0][1], outs[1][1])


@pytest.mark.parametrize(
  "kwargs,kernels",
  [({"use_local_mesh": False}, True), ({"use_local_mesh": True}, False), ({"use_local_mesh": True, "pp": 4}, False)],
  ids=["one-device", "tp-default", "pp4xtp2"],
)
def test_gspmd_partitioned_plans_switch_the_pallas_kernels_off(kwargs, kernels):
  """A Mosaic kernel cannot be partitioned automatically, so an engine whose
  serving plan leaves an axis of more than one device to GSPMD (the tp
  default, tp under pp on 8 devices) clears the config's kernel gate; a
  one-device engine keeps it. (``--pp 4`` over exactly four chips keeps it
  too: tests/test_tpu_compile.py compiles that case for a described 2x2.)"""
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, auto_partitioned

  cfg = tiny_test_config(n_layers=4)
  params, shard = full_model_params(jax.random.PRNGKey(5), cfg, "m")
  engine = JaxShardedInferenceEngine(**kwargs)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine.cfg.mosaic_kernels is kernels and engine.cfg.plain_attention is kernels
  assert not auto_partitioned(MeshPlan(pp=4), "pp") and auto_partitioned(MeshPlan(pp=2, tp=2), "pp") and not auto_partitioned(MeshPlan())
