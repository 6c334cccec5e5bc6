"""The plain reference: each architecture's forward pass in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")`` — no cache,
no kernels, no batching, no program code. Written from the published
equations; each departure is noted where it is made. A kind's forward pass is
``reference_forward`` of ``benchmark/arch_<kind>.py`` (``arch.py``); what the
kinds share — dequantisation, the norm, the two rotary pairings, causal
attention, SwiGLU, the head — is here.

Weights come from the served parameters — int8 codes times their scale, one
layer at a time — so the reference never holds the model in f32 and weight
quantisation is not part of the difference it measures: what is left is the
served path's arithmetic (bf16 activations, int8 KV, kernels, the cache).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import arch

F32 = jnp.float32


def deq(stack: dict, name: str, i=None):
  """Leaf ``name`` (layer ``i`` of a stack) as float32, scale folded in."""
  w = stack[name] if i is None else stack[name][i]
  s = stack.get(f"{name}_scale")
  if s is None:
    return w.astype(F32)
  s = s if i is None else s[i]
  return w.astype(F32) * s[..., None, :].astype(F32)


def rms_norm(x, gain, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain.astype(F32)


def rope_angles(n_pos: int, rot_dim: int, theta: float):
  inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=F32) / rot_dim))
  ang = jnp.arange(n_pos, dtype=F32)[:, None] * inv[None, :]
  return jnp.cos(ang), jnp.sin(ang)  # [S, rot_dim/2]


def rope_half(x, cos, sin):
  """x [S, H, d]; channel i pairs with i + d/2."""
  x1, x2 = jnp.split(x, 2, axis=-1)
  c, s = cos[:, None, :], sin[:, None, :]
  return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_adjacent(x, cos, sin):
  """x [S, H, d]; channel 2i pairs with 2i+1 (complex multiply)."""
  a, b = x[..., 0::2], x[..., 1::2]
  c, s = cos[:, None, :], sin[:, None, :]
  return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def causal_attention(q, k, v, scale):
  """q [S,H,dq], k [S,H,dq], v [S,H,dv] -> [S,H,dv]; full causal softmax."""
  S = q.shape[0]
  scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
  mask = jnp.tril(jnp.ones((S, S), bool))
  probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
  return jnp.einsum("hqk,khd->qhd", probs, v)


def swiglu(x, w_gate, w_up, w_down):
  return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def head(params, hf, h):
  return rms_norm(h, params["final_norm"], float(hf["rms_norm_eps"])) @ deq(params, "lm_head")


def reference_logprobs(params: dict, hf: dict, tokens, n_last: int, **probe):
  """log-softmax of the reference's logits at the ``n_last`` positions that
  predict the last ``n_last`` tokens of ``tokens`` (teacher-forced), [n_last, V]."""
  with jax.default_matmul_precision("highest"):
    logits = arch.load(hf["arch_kind"]).reference_forward(params, hf, jnp.asarray(tokens, jnp.int32), **probe)
    S = logits.shape[0]
    return jax.nn.log_softmax(logits[S - 1 - n_last : S - 1], axis=-1)
