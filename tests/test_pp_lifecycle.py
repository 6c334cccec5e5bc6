"""PP/SP-mode lifecycle parity (VERDICT r3 #4): the mesh-serving engine can
train, evaluate, checkpoint, attach LoRA, and run llava — the four former
XOT_TPU_PP refusals plus the vision refusals are gone.

Core claims: the pp flat-view round trip (reassemble → adopt) is exact; a
pp-mode train step computes the SAME loss and parameter update as the plain
single-device step on identical inputs; checkpoints interoperate across
modes; the llava tower runs outside the mesh and feeds merged embeddings to
the pp/sp prefill."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params

CFG = tiny_test_config(n_layers=4, max_seq_len=128)


def _pp_engine(cfg=CFG, seed=0, pp=2):
  # Engine pp mode on 8 virtual devices builds a pp×tp mesh (leftover chips
  # go to tp).
  params, shard = full_model_params(jax.random.PRNGKey(seed), cfg, "tiny")
  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=pp)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is not None
  return engine, params, shard


def _plain_engine(cfg=CFG, seed=0):
  params, shard = full_model_params(jax.random.PRNGKey(seed), cfg, "tiny")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  return engine, params, shard


def _batch(cfg=CFG, B=2, S=16, seed=3):
  rng = np.random.default_rng(seed)
  inputs = rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)
  targets = rng.integers(1, cfg.vocab_size, (B, S)).astype(np.int32)
  lengths = np.full((B,), S, np.int32)
  return inputs, targets, lengths


def _tree_allclose(a, b, atol=2e-4):
  flat_a = jax.tree_util.tree_leaves_with_path(a)
  flat_b = dict(jax.tree_util.tree_leaves_with_path(b))
  assert len(flat_a) == len(flat_b)
  for path, leaf in flat_a:
    np.testing.assert_allclose(
      np.asarray(leaf, np.float32), np.asarray(flat_b[path], np.float32), atol=atol, rtol=2e-3,
      err_msg=jax.tree_util.keystr(path),
    )


def test_pp_flat_view_roundtrip_is_exact():
  engine, params, shard = _pp_engine()
  flat = engine._flat_params_view()
  # Exact leaf equality with the original flat tree.
  for path, leaf in jax.tree_util.tree_leaves_with_path(flat):
    orig = dict(jax.tree_util.tree_leaves_with_path(params))[path]
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(orig), err_msg=jax.tree_util.keystr(path))
  # adopt → reassemble again: still exact, and serving still works.
  engine._adopt_flat_params(flat)
  flat2 = engine._flat_params_view()
  for path, leaf in jax.tree_util.tree_leaves_with_path(flat2):
    orig = dict(jax.tree_util.tree_leaves_with_path(params))[path]
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(orig), err_msg=jax.tree_util.keystr(path))


def test_pp_train_step_matches_plain_engine():
  """One engine.train step in XOT_TPU_PP=2 mode == the plain single-device
  step: same loss, same updated weights (GPipe pipeline over the serving
  mesh is the same math)."""
  pp_eng, params, shard = _pp_engine(seed=7)
  pl_eng, _, _ = _plain_engine(seed=7)
  inputs, targets, lengths = _batch()

  async def run(eng):
    losses = []
    for _ in range(2):
      losses.append(await eng.train("t", shard, inputs, targets, lengths, lr=1e-3))
    return losses

  pp_losses = asyncio.run(run(pp_eng))
  pl_losses = asyncio.run(run(pl_eng))
  np.testing.assert_allclose(pp_losses, pl_losses, rtol=2e-4, atol=2e-4)
  _tree_allclose(pp_eng._flat_params_view(), pl_eng.params)

  # eval parity too
  async def ev(eng):
    return await eng.evaluate("e", shard, inputs, targets, lengths)

  np.testing.assert_allclose(asyncio.run(ev(pp_eng)), asyncio.run(ev(pl_eng)), rtol=2e-4, atol=2e-4)


def test_pp_lora_attach_and_train():
  engine, params, shard = _pp_engine(seed=11)
  engine.attach_lora(4)
  flat = engine._flat_params_view()
  assert any("_lora_" in k for k in flat["layers"])
  inputs, targets, lengths = _batch(seed=5)

  async def run():
    return await engine.train("lt", shard, inputs, targets, lengths, lr=1e-3)

  loss = asyncio.run(run())
  assert np.isfinite(loss)
  # LoRA b starts at zero; after one step it moved, base weights did not.
  flat2 = engine._flat_params_view()
  assert float(np.abs(np.asarray(flat2["layers"]["wq_lora_b"])).max()) > 0.0
  np.testing.assert_array_equal(np.asarray(flat2["layers"]["wq"]), np.asarray(flat["layers"]["wq"]))


def test_pp_checkpoint_interops_with_plain_engine(tmp_path):
  """save in pp mode → load in plain mode (and back): identical weights."""
  pp_eng, params, shard = _pp_engine(seed=13)
  pl_eng, _, _ = _plain_engine(seed=17)  # different init

  async def run():
    await pp_eng.save_checkpoint(shard, tmp_path / "ck")
    await pl_eng.load_checkpoint(shard, tmp_path / "ck")

  asyncio.run(run())
  _tree_allclose(pl_eng.params, params, atol=1e-6)

  # And the reverse: plain save → pp load (adopts into the stage layout).
  pl2, params2, _ = _plain_engine(seed=19)

  async def run2():
    await pl2.save_checkpoint(shard, tmp_path / "ck2")
    await pp_eng.load_checkpoint(shard, tmp_path / "ck2")

  asyncio.run(run2())
  _tree_allclose(pp_eng._flat_params_view(), params2, atol=1e-6)


@pytest.mark.parametrize("mode", ["pp", "sp"])
def test_mesh_engine_serves_llava(tmp_path, mode, monkeypatch):
  """A vision model loads under XOT_TPU_PP/SP without the old refusal; the
  tower runs outside the mesh and the merged embeddings prefill through the
  mesh token-identically to the single-device path."""
  from tests.test_vision import _save_tiny_llava
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.config import load_model_config
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights
  from xotorch_support_jetson_tpu.models.vision import encode_images, merge_image_embeddings

  tokens_np, pixels_np, ref_logits = _save_tiny_llava(tmp_path)
  cfg = load_model_config(tmp_path, dtype=jnp.float32)
  shard = Shard("tiny-llava", 0, cfg.n_layers - 1, cfg.n_layers)
  params = load_shard_weights(tmp_path, cfg, shard)

  if mode == "pp":
    engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  else:
    monkeypatch.setenv("XOT_TPU_SP", "2")
    engine = JaxShardedInferenceEngine(use_local_mesh=True)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()  # must NOT raise anymore
  assert engine._pp is not None
  assert engine._vision_params and "vision" in engine._vision_params

  vp = engine._vision_leaves()
  feats = encode_images(vp["vision"], vp["projector"], cfg.vision, jnp.asarray(pixels_np))
  tokens = jnp.asarray(tokens_np, jnp.int32)
  embeds = jnp.take(engine._serving_embed(), tokens, axis=0).astype(cfg.dtype)
  merged = merge_image_embeddings(embeds, tokens, feats, cfg.image_token_id)

  from xotorch_support_jetson_tpu.inference.state import InferenceState

  state = InferenceState()
  state.prompt_len = tokens.shape[1]
  out, _ = engine._infer_tensor_sync("v1", shard, np.asarray(merged), state)
  # The engine's prefill returns last-position logits; compare to HF golden.
  np.testing.assert_allclose(np.asarray(out).reshape(-1), ref_logits[0, -1], rtol=3e-4, atol=3e-4)


def test_pp_vision_checkpoint_keeps_tower(tmp_path):
  """A mesh-mode llava checkpoint carries the vision tower + projector (the
  flat view merges the split-off leaves back), so it restores into a plain
  engine completely — and a restore into the pp engine refreshes
  _vision_params."""
  from tests.test_vision import _save_tiny_llava
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.config import load_model_config
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights

  _save_tiny_llava(tmp_path / "hf")
  cfg = load_model_config(tmp_path / "hf", dtype=jnp.float32)
  shard = Shard("tiny-llava", 0, cfg.n_layers - 1, cfg.n_layers)
  params = load_shard_weights(tmp_path / "hf", cfg, shard)

  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(shard, cfg, jax.tree.map(jnp.zeros_like, params))

  async def run():
    await engine.save_checkpoint(shard, tmp_path / "vck")
    await plain.load_checkpoint(shard, tmp_path / "vck")

  asyncio.run(run())
  assert "vision" in plain.params and "projector" in plain.params
  _tree_allclose(plain.params, params, atol=1e-6)
  # Restore back into the pp engine: the vision leaves split off again.
  plain.params = jax.tree.map(lambda x: x + 1.0, plain.params)

  async def run2():
    await plain.save_checkpoint(shard, tmp_path / "vck2")
    await engine.load_checkpoint(shard, tmp_path / "vck2")

  asyncio.run(run2())
  assert "vision" in engine._vision_params
  np.testing.assert_allclose(
    np.asarray(jax.tree_util.tree_leaves(engine._vision_params["vision"])[0]),
    np.asarray(jax.tree_util.tree_leaves(jax.tree.map(lambda x: x + 1.0, params["vision"]))[0]),
    atol=1e-6,
  )


@pytest.mark.parametrize("mode", ["pp", "sp"])
def test_mesh_engine_scores_logprobs(mode, monkeypatch):
  """score_tokens (OpenAI logprobs) works on pp/sp mesh engines through the
  flat params view — no more None for mesh serving modes — and matches the
  plain engine's numbers."""
  if mode == "pp":
    engine, params, shard = _pp_engine(seed=29)
  else:
    monkeypatch.setenv("XOT_TPU_SP", "2")
    params, shard = full_model_params(jax.random.PRNGKey(29), CFG, "tiny")
    engine = JaxShardedInferenceEngine(use_local_mesh=True)
    engine.load_test_model(shard, CFG, params)
    engine._maybe_shard_over_local_mesh()
    assert engine._pp is not None
  plain, _, _ = _plain_engine(seed=29)
  toks = np.asarray([5, 9, 2, 71, 33, 8, 14, 60], np.int32)

  async def score(eng):
    return await eng.score_tokens(shard, toks, n_scored=3, top_n=5)

  got = asyncio.run(score(engine))
  ref = asyncio.run(score(plain))
  assert got is not None and ref is not None
  for g, r in zip(got, ref):
    np.testing.assert_allclose(np.asarray(g, np.float64), np.asarray(r, np.float64), rtol=2e-4, atol=2e-4)


def test_local_mesh_engine_trains(monkeypatch):
  """The DEFAULT in-slice tp/dp GSPMD engine (use_local_mesh, no _pp) trains
  on ITS OWN mesh — the trainer used to build a fresh single-device mesh
  that conflicted with the 8-device param placement (found driving the
  train CLI on a multi-device host)."""
  params, shard = full_model_params(jax.random.PRNGKey(31), CFG, "tiny")
  engine = JaxShardedInferenceEngine(use_local_mesh=True)
  engine.load_test_model(shard, CFG, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is None and engine.mesh is not None  # local GSPMD mode
  plain, _, _ = _plain_engine(seed=31)
  inputs, targets, lengths = _batch(seed=13)

  async def run(eng):
    losses = [await eng.train("t", shard, inputs, targets, lengths, lr=1e-3) for _ in range(2)]
    losses.append(await eng.evaluate("e", shard, inputs, targets, lengths))
    return losses

  got = asyncio.run(run(engine))
  ref = asyncio.run(run(plain))
  np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_sp_train_and_checkpoint(tmp_path):
  """SP-mode engines train and checkpoint too (same mesh branch)."""

  import os

  os.environ["XOT_TPU_SP"] = "2"
  try:
    params, shard = full_model_params(jax.random.PRNGKey(23), CFG, "tiny")
    engine = JaxShardedInferenceEngine(use_local_mesh=True)
    engine.load_test_model(shard, CFG, params)
    engine._maybe_shard_over_local_mesh()
    pl_eng, _, _ = _plain_engine(seed=23)
    inputs, targets, lengths = _batch(seed=9)

    async def run(eng):
      return await eng.train("t", shard, inputs, targets, lengths, lr=1e-3)

    sp_loss = asyncio.run(run(engine))
    pl_loss = asyncio.run(run(pl_eng))
    np.testing.assert_allclose(sp_loss, pl_loss, rtol=2e-4, atol=2e-4)
    _tree_allclose(engine._flat_params_view(), pl_eng.params)

    async def ck():
      await engine.save_checkpoint(shard, tmp_path / "spck")

    asyncio.run(ck())
  finally:
    os.environ.pop("XOT_TPU_SP", None)
