"""int8 weight quantization (XOT_TPU_QUANT) composed with every serving mesh
mode — the production shape for the 8B-class BASELINE configs (int8 halves
the weight read; pp/sp/tp spread it across chips). Token-identical to the
single-device quantized decode in each mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_batch_decode,
  fused_decode,
  init_kv_cache,
  prefill_into_slot,
  shard_forward,
)
from xotorch_support_jetson_tpu.models.quantize import quantize_params
from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
from xotorch_support_jetson_tpu.parallel.pp_batch import PPBatchedServing
from xotorch_support_jetson_tpu.parallel.pp_serving import PPServing
from xotorch_support_jetson_tpu.parallel.sp_batch import SPBatchedServing
from xotorch_support_jetson_tpu.parallel.sp_serving import SPServing

CFG = tiny_test_config(n_layers=4, max_seq_len=128)
PROMPT = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)
N_STEPS = 8


@pytest.fixture(scope="module")
def quantized():
  params, shard = full_model_params(jax.random.PRNGKey(7), CFG, "m")
  qp = quantize_params(params)
  S = PROMPT.shape[1]
  cache = init_kv_cache(CFG, CFG.n_layers, 1, 128)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
  logits, cache = shard_forward(qp, CFG, shard, jnp.asarray(PROMPT), positions, cache)
  first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
  ref, _ = fused_decode(qp, CFG, shard, first, cache, jnp.full((1,), S, jnp.int32), N_STEPS)
  return qp, shard, int(first[0, 0]), np.asarray(ref)[0]


@pytest.mark.parametrize(
  "builder",
  [
    lambda qp: PPServing(build_mesh(MeshPlan(pp=2)), CFG, qp, 2, True, True),
    lambda qp: PPServing(build_mesh(MeshPlan(pp=2, tp=2)), CFG, qp, 2, True, True),
    lambda qp: SPServing(build_mesh(MeshPlan(sp=2, tp=2)), CFG, qp, 2, True, True),
  ],
  ids=["pp2", "pp2xtp2", "sp2xtp2"],
)
def test_int8_mesh_serving_matches_single_device(quantized, builder):
  qp, shard, first_ref, ref = quantized
  srv = builder(qp)
  S = PROMPT.shape[1]
  cache = srv.place_cache(init_kv_cache(CFG, CFG.n_layers, 1, 128))
  last, cache = srv.prefill(jnp.asarray(PROMPT), cache, jnp.full((1,), S, jnp.int32))
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  assert int(first[0, 0]) == first_ref
  toks, _ = srv.fused_decode(first, cache, jnp.full((1,), S, jnp.int32), N_STEPS)
  np.testing.assert_array_equal(np.asarray(toks)[0], ref)


@pytest.mark.parametrize("mode", ["pp", "sp"])
def test_int8_batched_mesh_serving_matches_single_device(quantized, mode):
  """int8 through the BATCHED mesh paths (dense slot cache, 2 rows)."""

  qp, shard, _, _ = quantized
  if mode == "pp":
    srv = PPBatchedServing(build_mesh(MeshPlan(pp=2)), CFG, qp, 2)
  else:
    srv = SPBatchedServing(SPServing(build_mesh(MeshPlan(sp=2, tp=2)), CFG, qp, 2, True, True))
  prompts = [[5, 9, 2, 71, 33], [7, 1, 88]]
  B = len(prompts)
  cache_ref = init_kv_cache(CFG, CFG.n_layers, B, 128)
  cache_m = srv.place_cache(init_kv_cache(CFG, CFG.n_layers, B, 128))
  firsts_ref, firsts_m = [], []
  for r, p in enumerate(prompts):
    pad = np.zeros((1, 16), np.int32)
    pad[0, : len(p)] = p
    lr, cache_ref = prefill_into_slot(qp, CFG, shard, jnp.asarray(pad), cache_ref, jnp.int32(r), jnp.int32(len(p)))
    lm, cache_m = srv.prefill_into_slot(jnp.asarray(pad), cache_m, r, len(p))
    firsts_ref.append(int(np.argmax(np.asarray(lr)[0])))
    firsts_m.append(int(np.argmax(np.asarray(lm)[0])))
  assert firsts_m == firsts_ref

  tok = jnp.asarray([[f] for f in firsts_ref], jnp.int32)
  pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.ones((B,), bool)
  temps = jnp.zeros((B,), jnp.float32)
  top_ks = jnp.full((B,), 35, jnp.int32)
  ref_toks, _, _, _ = fused_batch_decode(qp, CFG, shard, tok, cache_ref, pos, active, temps, N_STEPS)
  m_toks, _, _, _ = srv.batch_decode(tok, cache_m, pos, active, temps, top_ks, N_STEPS)
  np.testing.assert_array_equal(np.asarray(m_toks), np.asarray(ref_toks))
