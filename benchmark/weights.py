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

A configuration file names its maker by ``arch_kind``: ``make_params`` of
``benchmark/arch_<kind>.py`` (``arch.py``). What the makers share is here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import arch

ACT = jnp.bfloat16


def quantize_int8(w):
  """Symmetric per-output-channel int8 over the in axis (-2): w ~ q * s[..., None, :]."""
  absmax = jnp.max(jnp.abs(w), axis=-2)
  scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
  q = jnp.round(w / scale[..., None, :]).astype(jnp.int8)
  return q, scale.astype(jnp.float32)


def normal(key, shape, std):
  return jax.random.normal(key, shape, dtype=jnp.float32) * std


def _qstack(key, n_layers: int, shape: tuple[int, ...]):
  """[L, *shape] int8 codes + [L, *shape[:-2], out] scales, one layer in flight."""
  std = 1.0 / (shape[-2] ** 0.5)
  return jax.lax.map(lambda k: quantize_int8(normal(k, shape, std)), jax.random.split(key, n_layers))


def put_q(stack: dict, name: str, key, n_layers: int, shape: tuple[int, ...]) -> None:
  stack[name], stack[f"{name}_scale"] = _qstack(key, n_layers, shape)


def head_and_embed(params: dict, keys, vocab: int, dim: int, topic_of=None, topics=None, topic_gain: float = 0.0) -> None:
  embed = normal(next(keys), (vocab, dim), 1.0)
  if topics is not None:
    embed = embed + topic_gain * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((dim,), ACT)
  params["lm_head"], params["lm_head_scale"] = quantize_int8(normal(next(keys), (dim, vocab), 1.0 / dim**0.5))


def maker_for(kind: str):
  return arch.load(kind).make_params  # benchmark/arch_<kind>.py


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
