"""Mixture-of-Experts routed FFN — GShard-style one-hot dispatch/combine.

The reference *registers* MoE models (deepseek-v3/r1/coder-v2-lite,
``models.py:69-70``) but its dense-only layer builder cannot load them
(SURVEY.md §2.11: "registry entries ≠ working support",
``general_mha.py:77-120``). This module is the TPU-native delivery of that
promise: routing + expert compute as pure einsums so the expert axis shards
over an ``ep`` mesh axis (parallel/mesh.py) and GSPMD places the
dispatch/combine all-to-alls on ICI.

Design (idiomatic TPU, not a translation of any torch MoE):

- **top-k routing** with either softmax scoring (mixtral/qwen2-moe/deepseek-v2)
  or sigmoid scoring with a selection-only correction bias (deepseek-v3),
  optionally group-limited (deepseek's device-limited routing: v2
  ``group_limited_greedy``, v3 ``noaux_tc``).
- **Capacity-based dispatch**: tokens are assigned a position inside their
  expert's buffer via a cumulative-sum rank; position ≥ capacity ⇒ the token
  drops that expert (its combine weight is zero). ``capacity_factor=None``
  means exact compute (capacity = T, nothing ever drops) — the right default
  for inference where logits must match the unrouted math.
- **Batched expert matmuls**: every expert's FFN runs as one
  ``[E, C, D] x [E, D, F]`` einsum — a single large MXU op instead of a
  Python loop over experts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def router_topk(
  logits: jnp.ndarray,  # [T, E] fp32 router logits
  k: int,
  scoring: str = "softmax",  # "softmax" | "sigmoid"
  norm_topk: bool = False,
  selection_bias: jnp.ndarray | None = None,  # [E] added for *selection only* (deepseek-v3)
  scale: float = 1.0,
  n_group: int = 1,
  topk_group: int = 1,
  group_mode: str = "none",  # "none" | "max" (deepseek-v2) | "top2sum" (deepseek-v3)
) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Select top-k experts per token. Returns (weights [T,k] fp32, idx [T,k] int32).

  Combine weights are always the *unbiased* scores gathered at the selected
  experts; ``selection_bias`` (deepseek-v3's e_score_correction_bias) only
  reorders the top-k choice. With ``group_mode`` ≠ "none" experts are split
  into ``n_group`` groups and only the top ``topk_group`` groups (by max or
  top-2-sum of member scores) are eligible — deepseek's device-limited
  routing, which bounds how many EP shards a token can touch.
  """
  logits = logits.astype(jnp.float32)
  if scoring == "sigmoid":
    scores = jax.nn.sigmoid(logits)
  else:
    scores = jax.nn.softmax(logits, axis=-1)
  sel = scores if selection_bias is None else scores + selection_bias.astype(jnp.float32)
  if group_mode != "none" and n_group > 1:
    T, E = sel.shape
    grouped = sel.reshape(T, n_group, E // n_group)
    if group_mode == "top2sum":
      group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    else:
      group_scores = jnp.max(grouped, axis=-1)
    _, gidx = jax.lax.top_k(group_scores, topk_group)  # [T, topk_group]
    gmask = jnp.sum(jax.nn.one_hot(gidx, n_group, dtype=jnp.float32), axis=1)  # [T, n_group]
    sel = jnp.where(jnp.repeat(gmask > 0, E // n_group, axis=-1), sel, 0.0)
  _, idx = jax.lax.top_k(sel, k)
  weights = jnp.take_along_axis(scores, idx, axis=-1)
  if norm_topk:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  return weights * scale, idx.astype(jnp.int32)


def expert_capacity(n_tokens: int, k: int, n_experts: int, capacity_factor: float | None) -> int:
  """Tokens each expert can hold. None ⇒ exact (capacity = T, no drops)."""
  if capacity_factor is None:
    return n_tokens
  return min(n_tokens, max(1, math.ceil(n_tokens * k / n_experts * capacity_factor)))


def dispatch_combine_masks(idx: jnp.ndarray, weights: jnp.ndarray, n_experts: int, capacity: int):
  """Build dispatch [T,E,C] (0/1) and combine [T,E,C] (weighted) tensors.

  Position-in-expert is the token's rank (token-major, slot-minor) among all
  assignments to that expert; rank ≥ capacity drops the assignment.
  """
  T, k = idx.shape
  onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # [T, k, E]
  flat = onehot.transpose(1, 0, 2).reshape(k * T, n_experts)  # slot-major blocks of token-major rows
  ranks = jnp.cumsum(flat, axis=0) - flat  # rank of each assignment within its expert
  ranks = ranks.reshape(k, T, n_experts).transpose(1, 0, 2)  # [T, k, E]
  pos = jnp.sum(ranks * onehot, axis=-1)  # [T, k] position inside the chosen expert
  keep = (pos < capacity).astype(jnp.float32)
  pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32) * keep[..., None]  # [T,k,C]
  dispatch = jnp.einsum("tke,tkc->tec", onehot, pos_oh)
  combine = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh, weights.astype(jnp.float32))
  return dispatch, combine


def _held_index(idx, held):
  """Routed expert ids → ids into the expert leaves of a shard that holds experts ``held`` = [lo, hi) of the router's
  (None: all of them, ids as they are). A choice outside the range gives an id outside the leaves': its one-hot is all
  zeros, so it is neither dispatched nor combined, and nothing stands in for what the absent expert would have added."""
  return idx if held is None else idx - held[0]


def _moe_ffn_block(x, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, capacity_factor, n_group, topk_group, group_mode, held=None):
  """One dispatch/compute/combine block over [T, D] tokens. Returns (out, aux)."""
  T, D = x.shape
  E, E_held = w_router.shape[-1], w_gate.shape[0]
  with jax.named_scope("xot.moe_router"):
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    weights, idx = router_topk(logits, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode)
    C = expert_capacity(T, k, E, capacity_factor)
    dispatch, combine = dispatch_combine_masks(_held_index(idx, held), weights, E_held, C)

  with jax.named_scope("xot.moe_experts"):
    xin = jnp.einsum("td,tec->ecd", x, dispatch.astype(x.dtype))  # [E, C, D]
    gated = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, w_gate).astype(jnp.float32)).astype(x.dtype)
    up = jnp.einsum("ecd,edf->ecf", xin, w_up)
    out = jnp.einsum("ecf,efd->ecd", gated * up, w_down)  # [E, C, D]
    out = jnp.einsum("ecd,tec->td", out.astype(jnp.float32), combine).astype(x.dtype)
  with jax.named_scope("xot.moe_router"):
    aux = load_balancing_loss(logits, idx, E)
  return out, aux


# Below this many tokens the gather path CAN replace the batched-einsum path:
# decode steps route to k experts per token, and gathering just those experts'
# weight slabs reads k·T/E of the expert bytes the einsum path streams (it
# computes every expert's capacity block — ~32x extra HBM for deepseek-v3's
# E=256, k=8 at batch 1). Exact only when nothing can drop, so it is gated on
# capacity_factor=None (the inference default). OPT-IN (XOT_TPU_MOE_GATHER=1):
# the one chip figure (stale — measured before PR 1, not reproduced) had the
# einsum path at 234 tok/s against the gather's 117 on an E=64/k=6 decode,
# despite reading 10x the bytes (ROADMAP.md A5).
from ..utils.helpers import env_flag as _env_flag

MOE_GATHER_MAX = 32 if _env_flag("XOT_TPU_MOE_GATHER") else 0


def _moe_ffn_gather(x, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode, held=None):
  """Decode-path MoE: gather the k active experts' weights per token.

  [T, D] tokens with T small; reads only the routed experts' slabs (XLA
  lowers ``jnp.take`` over the expert axis to a dynamic-gather — no full
  [E, D, F] stream). Same routing as the einsum path, no capacity concept.
  """
  T, D = x.shape
  E = w_router.shape[-1]
  with jax.named_scope("xot.moe_router"):
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    weights, idx = router_topk(logits, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode)
  with jax.named_scope("xot.moe_experts"):
    flat = idx.reshape(-1)  # [T·k]
    if held is not None:  # a choice this shard does not hold weighs nothing; its (clipped) gather is never combined
      weights = jnp.where((idx >= held[0]) & (idx < held[1]), weights, 0.0)
      flat = jnp.clip(_held_index(flat, held), 0, w_gate.shape[0] - 1)
    g = jnp.take(w_gate, flat, axis=0).reshape(T, k, D, -1)
    u = jnp.take(w_up, flat, axis=0).reshape(T, k, D, -1)
    d = jnp.take(w_down, flat, axis=0).reshape(T, k, -1, D)
    gated = jax.nn.silu(jnp.einsum("td,tjdf->tjf", x, g).astype(jnp.float32)).astype(x.dtype)
    up = jnp.einsum("td,tjdf->tjf", x, u)
    out_e = jnp.einsum("tjf,tjfd->tjd", gated * up, d)
    out = jnp.einsum("tjd,tj->td", out_e.astype(jnp.float32), weights).astype(x.dtype)
  with jax.named_scope("xot.moe_router"):
    aux = load_balancing_loss(logits, idx, E)
  return out, aux


def moe_ffn(
  x: jnp.ndarray,  # [T, D] tokens (flattened batch*seq)
  w_router: jnp.ndarray,  # [D, E]
  w_gate: jnp.ndarray,  # [E, D, F] per-expert gate proj
  w_up: jnp.ndarray,  # [E, D, F]
  w_down: jnp.ndarray,  # [E, F, D]
  k: int,
  scoring: str = "softmax",
  norm_topk: bool = False,
  selection_bias: jnp.ndarray | None = None,
  scale: float = 1.0,
  capacity_factor: float | None = None,
  chunk: int = 256,
  return_aux: bool = False,
  n_group: int = 1,
  topk_group: int = 1,
  group_mode: str = "none",
  held: tuple[int, int] | None = None,
):
  """Routed SwiGLU FFN over ``E`` experts; returns [T, D] in x.dtype
  (or ``(out, aux_loss)`` with ``return_aux``).

  ``held`` = (lo, hi): this shard's share of an expert-parallel layer. The
  router (``w_router`` [D, E], its bias, the groups, the top-k and the
  weights' normalisation over all k chosen) stays ``E`` wide; ``w_gate`` /
  ``w_up`` / ``w_down`` hold experts [lo, hi) only, and the result is their
  part of the layer's sum: what the absent experts would have added is left
  out (the shares of all the shards add up to the whole layer).


  Small token runs (decode steps; T ≤ MOE_GATHER_MAX with the exact
  ``capacity_factor=None``) take the weight-gather path — HBM reads scale
  with the ACTIVE experts, not E. Long token runs are processed in
  sequential chunks of ``chunk`` tokens so the dispatch/combine one-hots
  stay O(chunk²·E) instead of O(T²·E) — routing is per-token, so chunking
  is exact (with the default ``capacity_factor=None``, capacity per chunk =
  chunk, nothing ever drops).
  """
  T, D = x.shape

  def block(xs):
    return _moe_ffn_block(xs, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, capacity_factor, n_group, topk_group, group_mode, held)

  if T <= MOE_GATHER_MAX and capacity_factor is None:
    out, aux = _moe_ffn_gather(x, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode, held)
  elif T <= chunk:
    out, aux = block(x)
  else:
    pad = (-T) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    out_c, aux_c = jax.lax.map(block, xp.reshape(-1, chunk, D))
    out = out_c.reshape(-1, D)[:T]
    aux = jnp.mean(aux_c)  # padding rows bias aux slightly; acceptable for a regularizer
  return (out, aux) if return_aux else out


def load_balancing_loss(router_logits: jnp.ndarray, idx: jnp.ndarray, n_experts: int) -> jnp.ndarray:
  """Switch/GShard auxiliary loss: E · Σ_e (frac tokens to e) · (mean prob to e)."""
  probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]
  onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # [T, k, E]
  frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # [E]
  mean_prob = jnp.mean(probs, axis=0)  # [E]
  return n_experts * jnp.sum(frac_tokens / idx.shape[1] * mean_prob)
