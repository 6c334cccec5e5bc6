"""``reference.py`` against the repo's decoder at a tiny size on the CPU, for
both architectures: the same seeded int8 weights through the program's
cache-less forward (f32 activations) and through the reference must agree to
float32 rounding; and the probes the tolerances rely on must move the result."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import arch  # noqa: E402
import common  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402


def _tiny(name):
  hf = common.load_config(name)
  hf.update(arch.load(hf["arch_kind"]).REHEARSE_WIDTHS)
  hf["serving_window_tokens"] = 256
  return hf


@pytest.mark.parametrize("name", ["mistral-7b-int8", "moonlight-a3b-d14"])
def test_reference_matches_the_decoder(name):
  from dataclasses import replace

  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import shard_forward

  hf = _tiny(name)
  params = weights.build_params(hf, 5)
  cfg = replace(common.model_config(hf), dtype=jnp.float32)
  params32 = jax.tree.map(lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x, params)
  tokens = np.random.default_rng(0).integers(3, hf["vocab_size"], size=40)
  shard = Shard("m", 0, cfg.n_layers - 1, cfg.n_layers)
  with jax.default_matmul_precision("highest"):
    out = shard_forward(params32, cfg, shard, jnp.asarray(tokens[None], jnp.int32), jnp.arange(40, dtype=jnp.int32)[None])
  logits = np.asarray(out[0] if isinstance(out, tuple) else out)[0]
  served = np.asarray(jax.nn.log_softmax(jnp.asarray(logits[-9:-1]), axis=-1))
  ref = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.abs(served - ref).max() < 2e-3
  probes = arch.load(hf["arch_kind"]).probes(hf)
  assert len(probes) >= 3
  for probe in probes.values():
    wrong = np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **probe))
    assert np.abs(wrong - ref).mean() > 10 * np.abs(served - ref).mean(), probe


def test_the_topic_router_keeps_the_chosen_experts_clear_of_the_rest():
  """What the Moonlight configuration's router is for: at the published
  hidden size the 6th and 7th affinities of a token are not near-tied, so
  bf16 rounding in the served path cannot swap an expert."""
  hf = _tiny("moonlight-a3b-d14")
  hf.update(hidden_size=2048, n_routed_experts=64, num_experts_per_tok=6, num_hidden_layers=2, vocab_size=4096)
  params = weights.build_params(hf, 3)
  tokens = np.random.default_rng(1).integers(3, hf["vocab_size"], size=64)
  x = params["embed"][tokens].astype(jnp.float32)
  x = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True))
  logits = np.sort(np.asarray(x @ params["moe_layers"]["w_router"][0].astype(jnp.float32)), axis=-1)[:, ::-1]
  assert ((logits[:, 5] - logits[:, 6]) > 0.1 * np.abs(logits[:, 5])).all()
  plain = dict(hf, router_topics=0)
  w = np.asarray(weights.build_params(plain, 3)["moe_layers"]["w_router"][0].astype(jnp.float32))
  near = np.sort(np.asarray(x) @ w, axis=-1)[:, ::-1]
  assert ((near[:, 5] - near[:, 6]) < 0.02 * np.abs(near[:, 5])).mean() > 0.05  # the random router: near-ties are common
