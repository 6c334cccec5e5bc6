"""Weights made on the device from ``--seed``, already in the type they are served in.

One jitted call per configuration builds every leaf: stacked ``[L, in, out]``
matrices are produced by ``lax.map`` over per-layer keys whose body draws one
f32 slab and quantises it on the spot, so the transient never exceeds one
layer's widest leaf and the peak is about the int8 model (the idea of
``bench.py build_8b_int8``, which ROADMAP D7 deletes with that file).

The layout is the one ``models/decoder.py init_shard_params`` documents and
``models/quantize.py`` produces for ``XOT_TPU_QUANT=int8``: int8 codes under
the leaf's name plus an f32 ``<name>_scale`` per output channel; norm gains,
routers and the embedding table stay bf16/f32. The quantiser here is the
benchmark's own four lines, so the yardstick does not move with the program.

A configuration file names its maker by ``arch_kind``; a new kind is a new
module ``benchmark/arch_<kind>.py`` with ``make_params(hf, seed_key)`` and is
found by name (see ``maker_for``).
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

ACT = jnp.bfloat16


def quantize_int8(w):
  """Symmetric per-output-channel int8 over the in axis (-2): w ~ q * s[..., None, :]."""
  absmax = jnp.max(jnp.abs(w), axis=-2)
  scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
  q = jnp.round(w / scale[..., None, :]).astype(jnp.int8)
  return q, scale.astype(jnp.float32)


def _normal(key, shape, std):
  return jax.random.normal(key, shape, dtype=jnp.float32) * std


def _qstack(key, n_layers: int, shape: tuple[int, ...]):
  """[L, *shape] int8 codes + [L, *shape[:-2], out] scales, one layer in flight."""
  std = 1.0 / (shape[-2] ** 0.5)
  return jax.lax.map(lambda k: quantize_int8(_normal(k, shape, std)), jax.random.split(key, n_layers))


def _put_q(stack: dict, name: str, key, n_layers: int, shape: tuple[int, ...]) -> None:
  stack[name], stack[f"{name}_scale"] = _qstack(key, n_layers, shape)


def _head_and_embed(params: dict, keys, vocab: int, dim: int, topic_of=None, topics=None, topic_gain: float = 0.0) -> None:
  embed = _normal(next(keys), (vocab, dim), 1.0)
  if topics is not None:
    embed = embed + topic_gain * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((dim,), ACT)
  params["lm_head"], params["lm_head_scale"] = quantize_int8(_normal(next(keys), (dim, vocab), 1.0 / dim**0.5))


def make_dense_gqa(hf: dict, key) -> dict:
  """Llama/Mistral-shaped decoder: GQA attention + SwiGLU, untied int8 head."""
  L, D, F, V = hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
  hd = hf.get("head_dim") or D // hf["num_attention_heads"]
  qd, kd = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
  keys = iter(jax.random.split(key, 16))
  stack = {"attn_norm": jnp.ones((L, D), ACT), "mlp_norm": jnp.ones((L, D), ACT)}
  for name, shape in (("wq", (D, qd)), ("wk", (D, kd)), ("wv", (D, kd)), ("wo", (qd, D)), ("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))):
    _put_q(stack, name, next(keys), L, shape)
  params = {"layers": stack}
  _head_and_embed(params, keys, V, D)
  return params


def _mla_leaves(hf: dict, keys, n_layers: int) -> dict:
  D, H = hf["hidden_size"], hf["num_attention_heads"]
  rank, nope, rope, vh = hf["kv_lora_rank"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
  if hf.get("q_lora_rank"):
    raise NotImplementedError("q_lora_rank: add wq_a/q_a_norm/wq_b here when a configuration needs them")
  stack = {"attn_norm": jnp.ones((n_layers, D), ACT), "mlp_norm": jnp.ones((n_layers, D), ACT), "kv_a_norm": jnp.ones((n_layers, rank), ACT)}
  for name, shape in (("wq", (D, H * (nope + rope))), ("wkv_a", (D, rank + rope)), ("wkv_b", (rank, H * (nope + vh))), ("wo", (H * vh, D))):
    _put_q(stack, name, next(keys), n_layers, shape)
  return stack


def make_mla_moe(hf: dict, key) -> dict:
  """DeepSeek-V3-shaped decoder: MLA attention, ``first_k_dense_replace`` dense
  layers, then routed experts + shared experts; the router and its selection
  bias stay f32-precise (bf16 router weights, f32 zero bias)."""
  L, D, V = hf["num_hidden_layers"], hf["hidden_size"], hf["vocab_size"]
  n_dense = min(int(hf.get("first_k_dense_replace", 0)), L)
  Lm, E, Fm, F = L - n_dense, hf["n_routed_experts"], hf["moe_intermediate_size"], hf["intermediate_size"]
  Fs = int(hf.get("n_shared_experts") or 0) * Fm
  keys = iter(jax.random.split(key, 32))
  params: dict = {}
  if n_dense:
    dense = _mla_leaves(hf, keys, n_dense)
    for name, shape in (("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))):
      _put_q(dense, name, next(keys), n_dense, shape)
    params["layers"] = dense
  moe = _mla_leaves(hf, keys, Lm)
  w_router = _normal(next(keys), (Lm, D, E), 1.0 / D**0.5)
  topics = topic_of = None
  n_topics = int(hf.get("router_topics") or 0)
  if n_topics:
    # A router that reads the token (see the configuration file's ``assumed``):
    # every token id belongs to one of ``router_topics`` topics, its embedding
    # carries the topic's +-1 direction, and each expert layer's router gives
    # each topic its own k experts a large logit. Independent experts stay
    # independent; what goes is the near-tie at the top-k boundary.
    k_t, k_a, k_m = jax.random.split(next(keys), 3)
    topics = jnp.where(jax.random.bernoulli(k_t, 0.5, (n_topics, D)), 1.0, -1.0).astype(jnp.float32)
    topic_of = jax.random.randint(k_a, (V,), 0, n_topics)
    draw = jax.random.uniform(k_m, (Lm, n_topics, E))
    kth = jax.lax.top_k(draw, int(hf["num_experts_per_tok"]))[0][..., -1:]
    owns = (draw >= kth).astype(jnp.float32)  # [Lm, topics, E], k ones a row
    w_router = w_router + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, owns)
  moe["w_router"] = w_router.astype(ACT)
  if hf.get("scoring_func") == "sigmoid" or hf.get("model_type") == "deepseek_v3":
    moe["router_bias"] = jnp.zeros((Lm, E), jnp.float32)
  for name, shape in (("w_experts_gate", (E, D, Fm)), ("w_experts_up", (E, D, Fm)), ("w_experts_down", (E, Fm, D))):
    _put_q(moe, name, next(keys), Lm, shape)
  if Fs:
    for name, shape in (("w_shared_gate", (D, Fs)), ("w_shared_up", (D, Fs)), ("w_shared_down", (Fs, D))):
      _put_q(moe, name, next(keys), Lm, shape)
  params["moe_layers"] = moe
  _head_and_embed(params, keys, V, D, topic_of, topics, float(hf.get("embed_topic_gain", 0.0)))
  return params


MAKERS = {"dense_gqa": make_dense_gqa, "mla_moe": make_mla_moe}


def maker_for(kind: str):
  if kind in MAKERS:
    return MAKERS[kind]
  return importlib.import_module(f"arch_{kind}").make_params  # dropped-in file: benchmark/arch_<kind>.py


def seed_key(seed: int):
  """A typed key of the hardware generator: drawing 7 G normals with threefry
  costs seconds of set-up in every run of every later check."""
  return jax.random.key(int(seed) % (2**63), impl="rbg")


def shape_hf(hf: dict) -> dict:
  """The hashable part of a configuration file that decides shapes."""
  return {k: v for k, v in hf.items() if isinstance(v, (int, float, str, bool, type(None)))}


def build_params(hf: dict, seed: int) -> dict:
  """Every leaf in one jitted call, on the default device."""
  make = maker_for(hf["arch_kind"])
  shapes = shape_hf(hf)
  return jax.jit(lambda k: make(shapes, k))(seed_key(seed))


def param_shapes(hf: dict) -> dict:
  make = maker_for(hf["arch_kind"])
  shapes = shape_hf(hf)
  return jax.eval_shape(lambda k: make(shapes, k), seed_key(0))
