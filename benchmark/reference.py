"""The plain reference: each architecture's forward pass in straightforward
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")`` — no cache,
no kernels, no batching, no program code. Written from the published
equations; each departure is noted where it is made.

  dense_gqa  Llama/Mistral: pre-norm decoder, grouped-query attention with
             rotary embeddings in the half-rotation pairing of the published
             checkpoints (channel i pairs with i + d/2), SwiGLU feed-forward.
  mla_moe    DeepSeek-V2/V3 (Moonlight): multi-head latent attention with the
             nope/rope split (DeepSeek-V2 paper, eqs. 9-19; rotary pairs are
             adjacent channels, as in the released checkpoints), the first
             ``first_k_dense_replace`` layers dense, then DeepSeek-V3's
             auxiliary-loss-free routing (paper section 2.1.2, ``noaux_tc``):
             sigmoid affinities, top-k chosen on affinity + bias, gates are the
             affinities themselves, normalised over the chosen k and scaled by
             ``routed_scaling_factor``; shared experts always on.
             Departure: the latent norm's epsilon is 1e-6 (the released
             modelling code's default for that norm), not ``rms_norm_eps``.

Weights come from the served parameters — int8 codes times their scale, one
layer at a time — so the reference never holds the model in f32 and weight
quantisation is not part of the difference it measures: what is left is the
served path's arithmetic (bf16 activations, int8 KV, kernels, the cache).
"""

from __future__ import annotations

import importlib
from functools import partial

import jax
import jax.numpy as jnp

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


# ------------------------------------------------------------------ dense_gqa


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "theta"))
def _dense_layer(h, attn_norm, mlp_norm, wq, wk, wv, wo, w_gate, w_up, w_down, *, n_heads, n_kv, eps, theta):
  S, D = h.shape
  hd = wq.shape[-1] // n_heads
  x = rms_norm(h, attn_norm, eps)
  q = (x @ wq).reshape(S, n_heads, hd)
  k = (x @ wk).reshape(S, n_kv, hd)
  v = (x @ wv).reshape(S, n_kv, hd)
  cos, sin = rope_angles(S, hd, theta)
  q, k = rope_half(q, cos, sin), rope_half(k, cos, sin)
  rep = n_heads // n_kv
  k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
  h = h + causal_attention(q, k, v, hd**-0.5).reshape(S, n_heads * hd) @ wo
  return h + swiglu(rms_norm(h, mlp_norm, eps), w_gate, w_up, w_down)


def forward_dense_gqa(params: dict, hf: dict, tokens, drop_layer: int | None = None, theta_scale: float = 1.0):
  st = params["layers"]
  h = params["embed"][tokens].astype(F32)
  for i in range(hf["num_hidden_layers"]):
    if i == drop_layer:
      continue
    h = _dense_layer(
      h, st["attn_norm"][i], st["mlp_norm"][i], *(deq(st, n, i) for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")),
      n_heads=hf["num_attention_heads"], n_kv=hf["num_key_value_heads"], eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]) * theta_scale,
    )
  return _head(params, hf, h)


def _head(params, hf, h):
  return rms_norm(h, params["final_norm"], float(hf["rms_norm_eps"])) @ deq(params, "lm_head")


# -------------------------------------------------------------------- mla_moe

LATENT_NORM_EPS = 1e-6


@partial(jax.jit, static_argnames=("n_heads", "rank", "nope", "rope", "vh", "eps", "theta"))
def _mla_attention(h, attn_norm, kv_a_norm, wq, wkv_a, wkv_b, wo, *, n_heads, rank, nope, rope, vh, eps, theta):
  S, D = h.shape
  x = rms_norm(h, attn_norm, eps)
  q = (x @ wq).reshape(S, n_heads, nope + rope)
  kv_a = x @ wkv_a
  c_kv = rms_norm(kv_a[:, :rank], kv_a_norm, LATENT_NORM_EPS)
  kv = (c_kv @ wkv_b).reshape(S, n_heads, nope + vh)
  cos, sin = rope_angles(S, rope, theta)
  q_pe = rope_adjacent(q[..., nope:], cos, sin)
  k_pe = rope_adjacent(kv_a[:, None, rank:], cos, sin)  # one rope channel shared by every head
  qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
  kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (S, n_heads, rope))], axis=-1)
  out = causal_attention(qf, kf, kv[..., nope:], (nope + rope) ** -0.5)
  return h + out.reshape(S, n_heads * vh) @ wo


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, mlp_norm, w_gate, w_up, w_down, *, eps):
  return h + swiglu(rms_norm(h, mlp_norm, eps), w_gate, w_up, w_down)


@partial(jax.jit, static_argnames=("top_k", "norm_topk", "scaling", "eps", "drop_expert", "swap_experts"))
def _moe_ffn(h, mlp_norm, w_router, router_bias, eg, eg_s, eu, eu_s, ed, ed_s, sg, su, sd, *, top_k, norm_topk, scaling, eps, drop_expert=False, swap_experts=False):
  """Every token through every expert, one expert at a time, weighted by its
  gate (zero where the expert was not chosen): the plain form of the sum over
  the chosen experts."""
  x = rms_norm(h, mlp_norm, eps)
  affinity = jax.nn.sigmoid(x @ w_router.astype(F32))  # [S, E]
  _, idx = jax.lax.top_k(affinity + router_bias.astype(F32), top_k)
  gate = jnp.take_along_axis(affinity, idx, axis=-1)
  if norm_topk:
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
  gate = gate * scaling
  if drop_expert:  # sensitivity probe only: lose each token's weakest chosen expert
    gate = gate.at[:, -1].set(0.0)
  E = w_router.shape[-1]
  dense_gate = jnp.zeros((x.shape[0], E), F32).at[jnp.arange(x.shape[0])[:, None], idx].add(gate)
  if swap_experts:  # sensitivity probe only: two experts trade places (a wrong index, a permuted dispatch) -
    # the last token's strongest expert and the first expert that token did not choose
    a = idx[-1, 0]
    b = jnp.argmax(jnp.ones((E,), F32).at[idx[-1]].set(0.0))
    ga, gb = dense_gate[:, a], dense_gate[:, b]
    dense_gate = dense_gate.at[:, a].set(gb).at[:, b].set(ga)

  def one_expert(acc, e):
    wg = eg[e].astype(F32) * eg_s[e][None, :]
    wu = eu[e].astype(F32) * eu_s[e][None, :]
    wd = ed[e].astype(F32) * ed_s[e][None, :]
    return acc + dense_gate[:, e, None] * swiglu(x, wg, wu, wd), None

  routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))
  return h + routed + swiglu(x, sg, su, sd)


def forward_mla_moe(params: dict, hf: dict, tokens, drop_layer: int | None = None, theta_scale: float = 1.0, drop_expert: bool = False, swap_experts: bool = False):
  n_dense = min(int(hf.get("first_k_dense_replace", 0)), hf["num_hidden_layers"])
  eps = float(hf["rms_norm_eps"])
  mla = dict(
    n_heads=hf["num_attention_heads"], rank=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
    vh=hf["v_head_dim"], eps=eps, theta=float(hf["rope_theta"]) * theta_scale,
  )
  h = params["embed"][tokens].astype(F32)
  for g in range(hf["num_hidden_layers"]):
    if g == drop_layer:
      continue
    st, i = (params["layers"], g) if g < n_dense else (params["moe_layers"], g - n_dense)
    h = _mla_attention(h, st["attn_norm"][i], st["kv_a_norm"][i], *(deq(st, n, i) for n in ("wq", "wkv_a", "wkv_b", "wo")), **mla)
    if g < n_dense:
      h = _dense_ffn(h, st["mlp_norm"][i], *(deq(st, n, i) for n in ("w_gate", "w_up", "w_down")), eps=eps)
    else:
      ex = [a for n in ("w_experts_gate", "w_experts_up", "w_experts_down") for a in (st[n][i], st[f"{n}_scale"][i])]
      bias = st["router_bias"][i] if "router_bias" in st else jnp.zeros((hf["n_routed_experts"],), F32)
      h = _moe_ffn(
        h, st["mlp_norm"][i], st["w_router"][i], bias, *ex, *(deq(st, n, i) for n in ("w_shared_gate", "w_shared_up", "w_shared_down")),
        top_k=hf["num_experts_per_tok"], norm_topk=bool(hf.get("norm_topk_prob", False)), scaling=float(hf.get("routed_scaling_factor", 1.0)), eps=eps,
        drop_expert=drop_expert, swap_experts=swap_experts,
      )
  return _head(params, hf, h)


FORWARDS = {"dense_gqa": forward_dense_gqa, "mla_moe": forward_mla_moe}


def forward_for(kind: str):
  if kind in FORWARDS:
    return FORWARDS[kind]
  return importlib.import_module(f"arch_{kind}").reference_forward  # dropped-in file: benchmark/arch_<kind>.py


def reference_logprobs(params: dict, hf: dict, tokens, n_last: int, **probe):
  """log-softmax of the reference's logits at the ``n_last`` positions that
  predict the last ``n_last`` tokens of ``tokens`` (teacher-forced), [n_last, V]."""
  with jax.default_matmul_precision("highest"):
    logits = forward_for(hf["arch_kind"])(params, hf, jnp.asarray(tokens, jnp.int32), **probe)
    S = logits.shape[0]
    return jax.nn.log_softmax(logits[S - 1 - n_last : S - 1], axis=-1)
