"""Token sampling: temperature + top-k + top-p, jit-friendly.

Parity with the reference's torchtune top-k/temperature sampler with seeded
generator (``sharded_inference_engine.py:67-69,208-228``, TEMP=0.6 TOP_K=35
defaults at :34-35), extended with nucleus (top-p) sampling. Fixed shapes and
a threaded PRNG key keep it compilable into the decode step.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..utils.programs import tracked_jit

DEFAULT_TEMP = 0.6
DEFAULT_TOP_K = 35
NEG_INF = -1e30


@partial(tracked_jit, "sample.logits", static_argnames=("top_k",))
def sample_logits(
  logits: jnp.ndarray,  # [B, V]
  key: jax.Array,
  temp: float = DEFAULT_TEMP,
  top_k: int = DEFAULT_TOP_K,
  top_p: float = 1.0,
) -> jnp.ndarray:
  """Returns sampled token ids [B] (int32). temp<=0 is handled by the caller
  via ``greedy``; inside jit temp is a traced float so callers pass temp>0."""
  logits = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
  if top_k and top_k > 0:
    k = min(top_k, logits.shape[-1])
    vals, idxs = jax.lax.top_k(logits, k)  # [B, k]
    vals = _apply_top_p(vals, top_p)
    choice = jax.random.categorical(key, vals, axis=-1)  # [B]
    return jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
  return jax.random.categorical(key, _apply_top_p_full(logits, top_p), axis=-1).astype(jnp.int32)


def _apply_top_p(sorted_vals: jnp.ndarray, top_p: float) -> jnp.ndarray:
  """Mask tail of descending-sorted logits whose cumulative prob exceeds top_p."""
  probs = jax.nn.softmax(sorted_vals, axis=-1)
  cum = jnp.cumsum(probs, axis=-1)
  keep = (cum - probs) < top_p  # always keep the first token
  return jnp.where(keep, sorted_vals, NEG_INF)


def _apply_top_p_full(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
  sort_idx = jnp.argsort(-logits, axis=-1)
  sorted_vals = jnp.take_along_axis(logits, sort_idx, axis=-1)
  masked = _apply_top_p(sorted_vals, top_p)
  inv = jnp.argsort(sort_idx, axis=-1)
  return jnp.take_along_axis(masked, inv, axis=-1)


@partial(tracked_jit, "sample.logits_per_row", static_argnames=("k_max",))
def sample_logits_per_row(
  logits: jnp.ndarray,  # [B, V]
  key: jax.Array,
  temps: jnp.ndarray,  # [B] f32, caller guarantees > 0
  top_ks: jnp.ndarray,  # [B] int32, clipped to [1, k_max]
  k_max: int = 64,
) -> jnp.ndarray:
  """Per-row temperature AND top-k: one compiled program for a whole slot
  pool of heterogeneous requests (inference/batch_scheduler.py). The static
  ``k_max`` caps the candidate set; each row's traced ``top_ks`` masks ranks
  beyond its own k, so per-request values neither recompile nor leak into
  other rows.

  This is the draw itself, for every row it is given: its one caller on the
  served path (models/decoder.py ``_next_token_batched``) takes it only when
  some row's temperature is positive — an all-greedy batch never ranks the
  vocabulary — and splits its key before deciding, so the key advances
  whether or not the draw is taken."""
  x = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
  k_cap = min(k_max, x.shape[-1])
  vals, idxs = jax.lax.top_k(x, k_cap)  # [B, k_cap] descending
  rank = jnp.arange(k_cap, dtype=jnp.int32)[None, :]
  keep = rank < jnp.clip(top_ks.astype(jnp.int32), 1, k_cap)[:, None]
  vals = jnp.where(keep, vals, NEG_INF)
  choice = jax.random.categorical(key, vals, axis=-1)
  return jnp.take_along_axis(idxs, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


@tracked_jit("sample.greedy")
def greedy(logits: jnp.ndarray) -> jnp.ndarray:
  return jnp.argmax(logits, axis=-1).astype(jnp.int32)
