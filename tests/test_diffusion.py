"""Stable-diffusion stack tests.

Parity strategy (the reference's SD path is dead code — registry entry
commented out at ``reference models.py:167-168`` — so there is no reference
behavior to mirror beyond the API surface):

- CLIP text encoder: golden vs ``transformers.CLIPTextModel`` through the
  diffusers-format loader (the same strategy as tests/test_hf_golden.py).
- Samplers: analytic — for a delta data distribution the exact eps-model is
  known in closed form, and DDIM must recover x0 exactly; v-prediction and
  Euler must agree with it.
- UNet/VAE: structural + behavioral (diffusers is not installable here):
  loader→init tree equality, cross-attention sensitivity, skip wiring,
  shape contracts, img2img determinism.
"""

import os
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.diffusion import (
  ClipTextConfig,
  add_noise,
  alphas_cumprod,
  clip_text_encode,
  ddim_step,
  ddim_timesteps,
  euler_step,
  sample_chunk,
  tiny_diffusion_config,
  unet_apply,
  vae_decode,
  vae_encode,
  vae_sample_latents,
)
from xotorch_support_jetson_tpu.models.diffusion_loader import (
  init_clip_text_params,
  init_diffusion_params,
  init_unet_params,
  init_vae_params,
)
from xotorch_support_jetson_tpu.inference.diffusion_pipeline import DiffusionPipeline


CFG = tiny_diffusion_config()
# One program a call where the functions alone dispatch (and compile) an op at a time: what these cases cost is the
# programs they compile. Same keys, same values.
init_diffusion_params = jax.jit(init_diffusion_params, static_argnums=1)
unet_apply = jax.jit(unet_apply, static_argnums=1)


@pytest.fixture(scope="module")
def params():
  return init_diffusion_params(jax.random.PRNGKey(0), CFG)


# ------------------------------------------------------------- CLIP golden


def test_clip_text_golden_vs_transformers():
  torch = pytest.importorskip("torch")
  from safetensors.torch import save_file
  from transformers import CLIPTextConfig as HFCfg, CLIPTextModel
  from xotorch_support_jetson_tpu.models.diffusion_loader import load_clip_text

  hf = HFCfg(
    vocab_size=99, hidden_size=32, intermediate_size=64, num_hidden_layers=3,
    num_attention_heads=4, max_position_embeddings=16, hidden_act="gelu",
  )
  torch.manual_seed(0)
  model = CLIPTextModel(hf).eval()
  tokens = torch.randint(0, 99, (2, 16))
  with torch.no_grad():
    ref = model(tokens).last_hidden_state.numpy()

  jcfg = ClipTextConfig(
    vocab_size=99, hidden_size=32, intermediate_size=64, n_layers=3, n_heads=4,
    max_positions=16, act="gelu",
  )
  with tempfile.TemporaryDirectory() as d:
    save_file({k: v.contiguous() for k, v in model.state_dict().items()}, os.path.join(d, "model.safetensors"))
    loaded = load_clip_text(Path(d), jcfg)
  out = np.asarray(clip_text_encode(loaded, jcfg, jnp.asarray(tokens.numpy())))
  np.testing.assert_allclose(out, ref, atol=2e-5)


def test_clip_quick_gelu_differs():
  """SD1 checkpoints use quick_gelu; the act flag must change the output."""
  cfg_g = ClipTextConfig(vocab_size=64, hidden_size=16, intermediate_size=32, n_layers=1, n_heads=2, max_positions=8, act="gelu")
  cfg_q = ClipTextConfig(**{**cfg_g.__dict__, "act": "quick_gelu"})
  p = init_clip_text_params(jax.random.PRNGKey(1), cfg_g)
  toks = jnp.asarray([[0, 5, 9, 3, 1, 1, 1, 1]])
  a = clip_text_encode(p, cfg_g, toks)
  b = clip_text_encode(p, cfg_q, toks)
  assert not np.allclose(np.asarray(a), np.asarray(b))


# --------------------------------------------------------- sampler analytic


def _delta_eps_model(x0, alphas):
  """Exact eps-predictor for a delta data distribution at x0."""

  def fn(_params, x, t, _ctx):
    a_t = alphas[t][:, None, None, None]
    return (x - jnp.sqrt(a_t) * x0) / jnp.sqrt(1.0 - a_t)

  return fn


def test_ddim_recovers_delta_x0_exactly():
  """With the exact eps model, every DDIM step lands on the exact posterior
  mean; after the final step (a_prev = 1) the sample IS x0."""
  alphas = jnp.asarray(alphas_cumprod(CFG))
  x0 = jax.random.normal(jax.random.PRNGKey(2), (1, 4, 4, 4))
  ts = np.asarray(ddim_timesteps(CFG, 10), np.int32)
  a_ts = np.asarray(alphas)[ts]
  prev = ts - CFG.num_train_timesteps // 10
  a_prevs = np.where(prev >= 0, np.asarray(alphas)[np.clip(prev, 0, None)], 1.0).astype(np.float32)

  x2 = jnp.concatenate([x0, x0], axis=0)
  latents = jax.random.normal(jax.random.PRNGKey(3), x0.shape)
  out = sample_chunk(
    {}, CFG, latents, jnp.zeros((2, 1, 1)),
    jnp.asarray(ts), jnp.asarray(a_ts), jnp.asarray(a_prevs),
    guidance=1.0, unet_fn=_delta_eps_model(x2, alphas),
  )
  np.testing.assert_allclose(np.asarray(out), np.asarray(x0), atol=1e-4)


def test_v_prediction_equals_epsilon_step():
  """The same (x, x0) expressed in both parameterizations must produce the
  same DDIM and Euler updates."""
  rng = jax.random.PRNGKey(4)
  x0 = jax.random.normal(rng, (2, 3, 3, 4))
  eps = jax.random.normal(jax.random.fold_in(rng, 1), x0.shape)
  a_t, a_prev = 0.5, 0.8
  x = add_noise(x0, eps, a_t)
  v = np.sqrt(a_t) * eps - np.sqrt(1 - a_t) * x0
  for step in (ddim_step, euler_step):
    out_eps = step(x, eps, a_t, a_prev, "epsilon")
    out_v = step(x, v, a_t, a_prev, "v_prediction")
    np.testing.assert_allclose(np.asarray(out_eps), np.asarray(out_v), atol=1e-5)


def test_euler_recovers_delta_x0():
  """Euler in sigma space also converges on the delta distribution (exact
  probability-flow line: d is constant, so one step per interval is exact)."""
  alphas = jnp.asarray(alphas_cumprod(CFG))
  x0 = jax.random.normal(jax.random.PRNGKey(5), (1, 4, 4, 4))
  ts = np.asarray(ddim_timesteps(CFG, 8), np.int32)
  a_ts = np.asarray(alphas)[ts]
  prev = ts - CFG.num_train_timesteps // 8
  a_prevs = np.where(prev >= 0, np.asarray(alphas)[np.clip(prev, 0, None)], 1.0 - 1e-7).astype(np.float32)
  latents = jax.random.normal(jax.random.PRNGKey(6), x0.shape)
  out = sample_chunk(
    {}, CFG, latents, jnp.zeros((2, 1, 1)),
    jnp.asarray(ts), jnp.asarray(a_ts), jnp.asarray(a_prevs),
    guidance=1.0, method="euler", unet_fn=_delta_eps_model(jnp.concatenate([x0, x0]), alphas),
  )
  np.testing.assert_allclose(np.asarray(out), np.asarray(x0), atol=1e-3)


def test_cfg_guidance_one_is_cond_only():
  """guidance=1 ⇒ uncond contribution cancels: out = out_cond."""
  alphas = jnp.asarray(alphas_cumprod(CFG))
  ts = np.asarray([500], np.int32)
  a = np.asarray(alphas)[ts]

  x0_cond = jnp.ones((1, 2, 2, 4))
  x0_uncond = -jnp.ones((1, 2, 2, 4))
  pair = jnp.concatenate([x0_uncond, x0_cond], axis=0)
  latents = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 2, 4))
  out_g1 = sample_chunk({}, CFG, latents, jnp.zeros((2, 1, 1)), jnp.asarray(ts), jnp.asarray(a), jnp.asarray([1.0]), guidance=1.0, unet_fn=_delta_eps_model(pair, alphas))
  out_cond_only = sample_chunk({}, CFG, latents, jnp.zeros((2, 1, 1)), jnp.asarray(ts), jnp.asarray(a), jnp.asarray([1.0]), guidance=1.0, unet_fn=_delta_eps_model(jnp.concatenate([x0_cond, x0_cond]), alphas))
  np.testing.assert_allclose(np.asarray(out_g1), np.asarray(out_cond_only), atol=1e-5)


# ------------------------------------------------------------ UNet behavior


def test_unet_shapes_and_determinism(params):
  x = jax.random.normal(jax.random.PRNGKey(8), (2, 8, 8, 4))
  t = jnp.asarray([10, 500])
  ctx = jax.random.normal(jax.random.PRNGKey(9), (2, 7, CFG.unet.cross_attention_dim))
  out = unet_apply(params["unet"], CFG.unet, x, t, ctx)
  assert out.shape == (2, 8, 8, 4)
  assert np.isfinite(np.asarray(out)).all()
  out2 = unet_apply(params["unet"], CFG.unet, x, t, ctx)
  np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_unet_cross_attention_sees_text(params):
  x = jax.random.normal(jax.random.PRNGKey(10), (1, 8, 8, 4))
  t = jnp.asarray([100])
  ctx_a = jax.random.normal(jax.random.PRNGKey(11), (1, 7, CFG.unet.cross_attention_dim))
  ctx_b = ctx_a + 1.0
  a = unet_apply(params["unet"], CFG.unet, x, t, ctx_a)
  b = unet_apply(params["unet"], CFG.unet, x, t, ctx_b)
  assert not np.allclose(np.asarray(a), np.asarray(b))


def test_unet_timestep_matters(params):
  x = jax.random.normal(jax.random.PRNGKey(12), (1, 8, 8, 4))
  ctx = jax.random.normal(jax.random.PRNGKey(13), (1, 7, CFG.unet.cross_attention_dim))
  a = unet_apply(params["unet"], CFG.unet, x, jnp.asarray([1]), ctx)
  b = unet_apply(params["unet"], CFG.unet, x, jnp.asarray([999]), ctx)
  assert not np.allclose(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ VAE behavior


def test_vae_roundtrip_shapes(params):
  img = jax.random.uniform(jax.random.PRNGKey(14), (1, 16, 16, 3), minval=-1, maxval=1)
  moments = vae_encode(params["vae"], CFG.vae, img)
  # 2 levels ⇒ one stride-2 downsample: 16 → 8 spatial, 2*latent channels
  assert moments.shape == (1, 8, 8, 2 * CFG.vae.latent_channels)
  z = vae_sample_latents(moments, jax.random.PRNGKey(15), CFG.vae.scaling_factor)
  out = vae_decode(params["vae"], CFG.vae, z)
  assert out.shape == (1, 16, 16, 3)
  assert np.isfinite(np.asarray(out)).all()


def test_vae_sample_latents_deterministic_at_zero_var():
  moments = jnp.concatenate([jnp.full((1, 2, 2, 4), 3.0), jnp.full((1, 2, 2, 4), -40.0)], axis=-1)
  z = vae_sample_latents(moments, jax.random.PRNGKey(16), 0.5)
  np.testing.assert_allclose(np.asarray(z), 1.5, atol=1e-4)  # mean*scaling, var≈0 (logvar clipped at -30)


# ----------------------------------------------------------- loader parity


def test_loader_tree_matches_init_tree():
  """A diffusers-named checkpoint written by the SHIPPING exporter
  (export_diffusers_checkpoint — the same name map the verify drill uses)
  must load back into the identical tree, values, and behavior: one name
  map, round-tripped in both directions."""
  from xotorch_support_jetson_tpu.models.diffusion_loader import (
    export_diffusers_checkpoint,
    load_unet,
    load_vae,
  )

  rng = jax.random.PRNGKey(17)
  params = init_diffusion_params(rng, CFG)

  with tempfile.TemporaryDirectory() as d:
    export_diffusers_checkpoint(Path(d), CFG, params)
    unet_l = load_unet(Path(d) / "unet", CFG.unet)
    vae_l = load_vae(Path(d) / "vae", CFG.vae)

  for orig, loaded, name in ((params["unet"], unet_l, "unet"), (params["vae"], vae_l, "vae")):
    flat_o = jax.tree_util.tree_flatten_with_path(orig)[0]
    flat_l = jax.tree_util.tree_flatten_with_path(loaded)[0]
    assert len(flat_o) == len(flat_l), name
    for (po, lo), (pl, ll) in zip(flat_o, flat_l):
      assert jax.tree_util.keystr(po) == jax.tree_util.keystr(pl), name
      np.testing.assert_allclose(np.asarray(lo), np.asarray(ll), atol=1e-6, err_msg=f"{name}{jax.tree_util.keystr(po)}")

  # the loaded tree must also RUN identically
  x = jax.random.normal(jax.random.PRNGKey(18), (1, 8, 8, 4))
  ctx = jax.random.normal(jax.random.PRNGKey(19), (1, 5, CFG.unet.cross_attention_dim))
  a = unet_apply(params["unet"], CFG.unet, x, jnp.asarray([3]), ctx)
  b = unet_apply(unet_l, CFG.unet, x, jnp.asarray([3]), ctx)
  np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_exported_checkpoint_loads_as_full_pipeline():
  """export → diffusion_config_from_dir → load_diffusion_params: the whole
  offline-checkpoint path the verify drill and the engine take."""
  from xotorch_support_jetson_tpu.models.diffusion_loader import (
    diffusion_config_from_dir,
    export_diffusers_checkpoint,
    load_diffusion_params,
  )

  params = init_diffusion_params(jax.random.PRNGKey(21), CFG)
  with tempfile.TemporaryDirectory() as d:
    export_diffusers_checkpoint(Path(d), CFG, params)
    cfg2 = diffusion_config_from_dir(Path(d))
    # the exporter writes explicit per-level head counts; the reloaded config
    # must be FUNCTIONALLY identical (same heads at every level)
    from dataclasses import replace as _dc_replace

    n_lv = len(CFG.unet.block_out_channels)
    assert [cfg2.unet.heads_at(i) for i in range(n_lv)] == [CFG.unet.heads_at(i) for i in range(n_lv)]
    assert _dc_replace(cfg2.unet, attn_heads=None, attention_head_dim=CFG.unet.attention_head_dim) == CFG.unet
    assert cfg2.vae == CFG.vae and cfg2.clip == CFG.clip
    assert cfg2.set_alpha_to_one == CFG.set_alpha_to_one and cfg2.steps_offset == CFG.steps_offset
    loaded = load_diffusion_params(Path(d), cfg2)
  pipe_a = DiffusionPipeline(CFG, params, dtype=jnp.float32)
  pipe_b = DiffusionPipeline(cfg2, loaded, dtype=jnp.float32)
  img_a = pipe_a.generate("same words", steps=4, seed=9)
  img_b = pipe_b.generate("same words", steps=4, seed=9)
  np.testing.assert_array_equal(img_a, img_b)


# -------------------------------------------------------------- pipeline


def test_pipeline_generate_and_img2img(params):
  pipe = DiffusionPipeline(CFG, params, dtype=jnp.float32, progress_chunk=3)
  prog = []
  img = pipe.generate("a red cube", steps=7, guidance=4.0, seed=1, progress_cb=lambda d, t: prog.append((d, t)))
  assert img.shape == (16, 16, 3) and img.dtype == np.uint8
  assert prog[0] == (0, 7) and prog[-1] == (7, 7)
  assert [d for d, _ in prog] == sorted(d for d, _ in prog)

  # deterministic per seed; prompt-sensitive
  img_b = pipe.generate("a red cube", steps=7, guidance=4.0, seed=1)
  np.testing.assert_array_equal(img, img_b)
  img_c = pipe.generate("a blue sphere", steps=7, guidance=4.0, seed=1)
  assert not np.array_equal(img, img_c)

  # img2img consumes the init image and differs from text-to-image
  i2i = pipe.generate("a red cube", steps=7, seed=2, init_image=img, strength=0.5)
  assert i2i.shape == (16, 16, 3)
  assert not np.array_equal(i2i, img)


def test_pipeline_euler_method(params):
  pipe = DiffusionPipeline(CFG, params, dtype=jnp.float32)
  img = pipe.generate("cube", steps=5, method="euler", seed=3)
  assert img.shape == (16, 16, 3)


def test_pipeline_snaps_offgrid_sizes(params):
  """Off-grid sizes must round to the model's pixel grid (px_multiple =
  vae_stride x unet_stride), never shape-mismatch the UNet skip concats."""
  pipe = DiffusionPipeline(CFG, params, dtype=jnp.float32)
  assert pipe.px_multiple == 4  # 2-level VAE x 2-level UNet
  img = pipe.generate("cube", steps=3, seed=1, size=(18, 18))
  assert img.shape == (20, 20, 3)
  # off-grid init image resizes internally instead of crashing
  init = np.zeros((18, 18, 3), np.uint8)
  i2i = pipe.generate("cube", steps=4, seed=1, init_image=init, strength=0.5)
  assert i2i.shape == (20, 20, 3)


def test_pipeline_cancellation(params):
  """should_cancel is polled between chunks; firing it aborts the denoise
  (the API sets it on client disconnect — the single engine worker must not
  finish a dead request)."""
  from xotorch_support_jetson_tpu.inference.diffusion_pipeline import GenerationCancelled

  pipe = DiffusionPipeline(CFG, params, dtype=jnp.float32, progress_chunk=2)
  seen = []

  def cancel_after_first_chunk():
    return len(seen) >= 2  # progress fires at 0 then after each chunk

  with pytest.raises(GenerationCancelled):
    pipe.generate("cube", steps=8, seed=1, progress_cb=lambda d, t: seen.append(d), should_cancel=cancel_after_first_chunk)
  assert seen[-1] < 8  # never ran to completion


def test_steps_offset_shifts_timesteps():
  """SD scheduler configs ship steps_offset=1 (diffusers leading spacing);
  the lowest timestep becomes offset, not 0."""
  from dataclasses import replace

  cfg1 = replace(CFG, steps_offset=1)
  ts0 = np.asarray(ddim_timesteps(CFG, 10))
  ts1 = np.asarray(ddim_timesteps(cfg1, 10))
  assert ts0[-1] == 0 and ts1[-1] == 1
  np.testing.assert_array_equal(ts1, np.clip(ts0 + 1, 0, CFG.num_train_timesteps - 1))


def test_sd_download_patterns_skip_monolithic_checkpoints():
  """The diffusers repo layout must fetch only per-component weights — not
  the multi-GB root checkpoints or .fp16 duplicates."""
  from xotorch_support_jetson_tpu.download.hf_utils import filter_repo_objects, get_allow_patterns
  from xotorch_support_jetson_tpu.inference.shard import Shard

  shard = Shard("stable-diffusion-2-1-base", 0, 30, 31)
  patterns = get_allow_patterns(None, shard)
  repo_files = [
    "model_index.json", "v2-1_512-ema-pruned.safetensors", "v2-1_512-nonema-pruned.safetensors",
    "text_encoder/config.json", "text_encoder/model.safetensors", "text_encoder/model.fp16.safetensors",
    "unet/config.json", "unet/diffusion_pytorch_model.safetensors", "unet/diffusion_pytorch_model.fp16.safetensors",
    "vae/config.json", "vae/diffusion_pytorch_model.safetensors", "vae/diffusion_pytorch_model.fp16.safetensors",
    "scheduler/scheduler_config.json", "tokenizer/vocab.json", "tokenizer/merges.txt",
  ]
  got = set(filter_repo_objects(repo_files, allow_patterns=patterns))
  assert "unet/diffusion_pytorch_model.safetensors" in got
  assert "text_encoder/model.safetensors" in got and "vae/diffusion_pytorch_model.safetensors" in got
  assert "scheduler/scheduler_config.json" in got and "tokenizer/merges.txt" in got
  assert not any("fp16" in f or f.startswith("v2-1_512") for f in got), got
  # text models keep the bare-safetensors fallback
  llama = get_allow_patterns(None, Shard("llama-3.2-1b", 0, 15, 16))
  assert "*.safetensors" in llama


def test_pipeline_n_candidates(params):
  """n>1 denoises as one batch; candidates differ (per-candidate noise) and
  n=1 output equals the first... of nothing — n=1 keeps the 3-D contract."""
  pipe = DiffusionPipeline(CFG, params, dtype=jnp.float32)
  batch = pipe.generate("cubes", steps=4, seed=11, n=3)
  assert batch.shape == (3, 16, 16, 3) and batch.dtype == np.uint8
  assert not np.array_equal(batch[0], batch[1])
  single = pipe.generate("cubes", steps=4, seed=11)
  assert single.shape == (16, 16, 3)


def test_sd1_style_geometry_runs():
  """SD1-family layout: per-level head COUNTS (attn_heads), quick_gelu CLIP,
  v-prediction scheduler — the variant axes a real 1.5 checkpoint exercises."""
  from dataclasses import replace

  base = tiny_diffusion_config()
  cfg = replace(
    base,
    clip=ClipTextConfig(**{**base.clip.__dict__, "act": "quick_gelu"}),
    unet=replace(base.unet, attn_heads=(2, 2), attention_head_dim=999),  # head counts win
    prediction_type="v_prediction",
  )
  assert cfg.unet.heads_at(0) == 2 and cfg.unet.heads_at(1) == 2
  params = init_diffusion_params(jax.random.PRNGKey(31), cfg)
  pipe = DiffusionPipeline(cfg, params, dtype=jnp.float32)
  img = pipe.generate("a cube", steps=4, seed=2)
  assert img.shape == (16, 16, 3)
  assert np.isfinite(img.astype(np.float32)).all()
