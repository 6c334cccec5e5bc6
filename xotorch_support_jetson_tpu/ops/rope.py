"""Rotary position embeddings with llama-3 frequency scaling.

Covers the RoPE variation points the reference selects per family
(``general_mha.py:33-63``: Llama3ScaledRoPE vs vanilla/qwen2 RoPE — both are
the same math, llama3 additionally rescales inv_freq). Implemented as pure
functions of positions so decode steps at arbitrary offsets need no
precomputed tables — XLA fuses the sin/cos into the attention matmuls.

Uses the HF "half-rotation" pairing (channel i pairs with i + head_dim/2),
matching safetensors checkpoints as stored — so unlike the reference we need
no q/k weight permutation at load time (cf. ``llm_utils.py:126-134``).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from ..models.config import LongRopeScaling, ModelConfig, RopeScaling, YarnScaling


def rope_inv_freq(cfg: ModelConfig):
  """[rot_dim/2] inverse frequencies, with optional llama3/yarn scaling.

  For MLA models (deepseek) only the ``qk_rope_head_dim`` channel carries
  position; dense models rotate the whole head_dim. A model whose attention
  kinds differ in shape (``cfg.attn_shapes``) gets one table a kind that has
  a rope (``AttnKind.rope``), ``{AttnKind: table}``, made once a program; the
  layer step takes its own (models/decoder.py ``_dense_qkv``).
  """
  if len(cfg.attn_shapes) > 1:
    return {k: _inv_freq(int(cfg.head_dim * k.partial_rotary_factor), k.rope_theta, k.rope_scaling, cfg.max_seq_len) for k in dict.fromkeys(cfg.layer_attn) if k is not None and k.rope}
  rot_dim = cfg.qk_rope_head_dim if cfg.is_mla else int(cfg.head_dim * cfg.partial_rotary_factor)
  return _inv_freq(rot_dim, cfg.rope_theta, cfg.rope_scaling, cfg.max_seq_len)


def _inv_freq(rot_dim: int, theta: float, scaling, max_seq_len: int) -> jnp.ndarray:
  half = rot_dim // 2
  inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
  if isinstance(scaling, YarnScaling):
    return _yarn_inv_freq(rot_dim, theta, scaling)
  if isinstance(scaling, LongRopeScaling):
    # Static short/long selection keyed to the effective max sequence (the
    # engine clamps cfg.max_seq_len to its serving cap) — see LongRopeScaling.
    ext = scaling.short_factor if max_seq_len <= scaling.original_max_position_embeddings else scaling.long_factor
    return inv_freq / jnp.asarray(ext, dtype=jnp.float32)
  if isinstance(scaling, RopeScaling):
    inv_freq = _llama3_scale(inv_freq, scaling)
  return inv_freq


def rope_attention_factor(cfg) -> float:
  """Yarn/longrope post-scaling of cos/sin (HF multiplies them by it); 1.0 otherwise. ``cfg``: a ModelConfig or one
  layer's AttnKind."""
  return cfg.rope_scaling.attention_factor if isinstance(cfg.rope_scaling, (YarnScaling, LongRopeScaling)) else 1.0


def _yarn_inv_freq(dim: int, base: float, s: YarnScaling) -> jnp.ndarray:
  """Yarn NTK-by-parts inverse frequencies (HF ``_compute_yarn_parameters``):
  interpolated (freq/factor) below the slow-rotation boundary, extrapolated
  (unscaled) above the fast one, linear ramp between."""

  def correction_dim(num_rotations: float) -> float:
    return (dim * math.log(s.original_max_position_embeddings / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

  low = correction_dim(s.beta_fast)
  high = correction_dim(s.beta_slow)
  if s.truncate:
    low, high = math.floor(low), math.ceil(high)
  low, high = max(low, 0), min(high, dim - 1)
  if low == high:
    high += 0.001  # prevent singularity

  pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
  inv_extrapolation = 1.0 / pos_freqs
  inv_interpolation = 1.0 / (s.factor * pos_freqs)
  ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
  extrapolation_factor = 1.0 - ramp
  return inv_interpolation * (1.0 - extrapolation_factor) + inv_extrapolation * extrapolation_factor


def _llama3_scale(inv_freq: jnp.ndarray, s: RopeScaling) -> jnp.ndarray:
  wavelen = 2.0 * jnp.pi / inv_freq
  low_wavelen = s.original_max_position_embeddings / s.low_freq_factor
  high_wavelen = s.original_max_position_embeddings / s.high_freq_factor
  # Long wavelengths (low freq): divide by factor. Short: keep. Middle: smooth.
  smooth = (s.original_max_position_embeddings / wavelen - s.low_freq_factor) / (s.high_freq_factor - s.low_freq_factor)
  scaled_mid = (1.0 - smooth) * inv_freq / s.factor + smooth * inv_freq
  out = jnp.where(wavelen > low_wavelen, inv_freq / s.factor, inv_freq)
  is_mid = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
  return jnp.where(is_mid, scaled_mid, out)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray, attn_factor: float = 1.0) -> jnp.ndarray:
  """Rotate ``x`` [..., S, H, head_dim] by angles from ``positions`` [..., S].

  Half-rotation convention: (x1, x2) = split(x, 2, axis=-1);
  out = (x1*cos - x2*sin, x2*cos + x1*sin). ``attn_factor`` (yarn) scales
  cos/sin. When ``inv_freq`` covers fewer than head_dim/2 frequencies
  (phi3's partial_rotary_factor) only the leading 2·|inv_freq| channels
  rotate; the tail passes through unchanged.
  """
  rot = 2 * inv_freq.shape[-1]
  tail = None
  if rot < x.shape[-1]:
    x, tail = x[..., :rot], x[..., rot:]
  angles = positions[..., :, None].astype(jnp.float32) * inv_freq[None, :]  # [..., S, half]
  cos = jnp.cos(angles)[..., None, :] * attn_factor  # [..., S, 1, half]
  sin = jnp.sin(angles)[..., None, :] * attn_factor
  x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
  out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
  return out if tail is None else jnp.concatenate([out, tail], axis=-1)


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray, attn_factor: float = 1.0) -> jnp.ndarray:
  """Rotate with deepseek's interleaved pairing: channel 2i pairs with 2i+1.

  Matches HF ``apply_rotary_emb`` for deepseek-v2/v3 (complex multiply over
  adjacent pairs; yarn's ``attn_factor`` scales freqs_cis) — checkpoints
  store q_pe/k_pe in this layout, so no load permutation is needed.
  """
  angles = positions[..., :, None].astype(jnp.float32) * inv_freq[None, :]  # [..., S, half]
  cos = jnp.cos(angles)[..., None, :] * attn_factor
  sin = jnp.sin(angles)[..., None, :] * attn_factor
  xf = x.astype(jnp.float32)
  even = xf[..., 0::2]
  odd = xf[..., 1::2]
  out_even = even * cos - odd * sin
  out_odd = even * sin + odd * cos
  out = jnp.stack([out_even, out_odd], axis=-1).reshape(x.shape)
  return out.astype(x.dtype)
