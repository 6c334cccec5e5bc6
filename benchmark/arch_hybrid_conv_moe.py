"""Kind ``hybrid_conv_moe``: an LFM2-8B-A1B-shaped decoder (``lfm2_moe``; HF ``Lfm2Moe*``). A block is
``h <- h + operator(rmsnorm(h; operator_norm))`` then ``h <- h + ffn(rmsnorm(h; ffn_norm))``, and ``layer_types``
names each block's operator:

- ``conv``, a gated short convolution: ``[B | C | x] = u W_in`` (hidden -> 3 x hidden, in that order, no bias);
  ``g = B * x``; ``c_t = sum_j w_j * g_{t-(K-1)+j}``, a causal depthwise convolution of ``conv_L_cache`` = K taps, zeros
  before the sequence, no bias and NO activation; ``out = (C * c) W_out``. All a cache keeps of it is the last K - 1
  rows of ``g`` — the gated product, not ``x``: there is no state matrix.
- ``full_attention``: grouped-query softmax attention, ``num_attention_heads`` query heads over ``num_key_value_heads``
  KV heads of hidden / heads channels, no bias; an RMSNorm over each head's channels of q and of k (one gain of the
  head's size each) BEFORE rope; rope over the whole head, half-rotation pairing, ``rope_theta``; scale 1/sqrt(head).

The first ``num_dense_layers`` blocks' FFN is a dense SwiGLU of ``intermediate_size``; every later one routes
``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of ``moe_intermediate_size``: float32 scores
``sigmoid(v W_r)``, the choice the top k of scores + ``expert_bias``, weights the chosen SCORES over (their sum +
1e-6) (``norm_topk_prob``) times ``routed_scaling_factor``; no shared expert. RMSNorm with a plain gain, eps
``norm_eps``; a final norm (the family's ``embedding_norm``) and the head TIED to the embedding table.

The reference below is float32, one token's equations at a time, no cache, nothing of the program. The leaves are the
program's (``models/decoder.py init_shard_params``): one stack a (operator, FFN) pairing — ``ssm_layers`` (conv + dense),
``ssm_moe_layers`` (conv + experts), ``moe_layers`` (attention + experts), ``layers`` (attention + dense; no published
model has one). What ``arch.py`` asks of a kind, in its order, plus ``moe_expert_bytes``, ``routed_experts``,
``router_tables``, ``hf_layer_types``, ``layer_stacks``, ``long_prompt_tokens`` and ``exact_probes``. Each reading of
a key the catalog row does not explain is in the configuration file's ``assumed``."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import experts_touched
from reference import F32, rms_norm, rope_angles, rope_half
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` knows no
  ``lfm2_moe`` (every tree before PR 57) must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models import config

  if "lfm2_moe" not in getattr(config, "MODEL_FAMILIES", {}):
    raise SystemExit("arch_kind hybrid_conv_moe: this program's config_from_hf knows no model_type 'lfm2_moe' (no gated short convolution, no recurrent layer without a state matrix): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

# The seeded weights' departures from N(0, 1/in) and unit gains (the file's ``assumed.weights`` says each at length).
# The head is the embedding table, so a table drawn N(0, 1) + a topic's direction would stand the token's own logit ~57
# deviations up and its topic's ~40: the model would repeat its last token (PR 34's finding with its tied table). The table's
# channels are therefore of two sorts: the first IN_SHARE of them are what the LAYERS read — N(0, 1) + the topic's
# direction, as the other expert files' embeddings — and the final norm's gain is 0 there, so the head does not read
# them; the rest, n of them, are what the HEAD reads — N(0, HEAD_GAIN^2 / n) (a standard deviation of 1/32 at the
# published 512: a thirtieth of the stream the blocks write there), final norm gain FINAL_GAIN — so a token's own logit
# stands ~0.4 deviations up and the logits' spread is ~1.
IN_SHARE = 0.75
HEAD_GAIN = 0.7
FINAL_GAIN = 2.0
QK_NORM_GAIN = 2.0  # both per-head norms: softmax logits spread over gain_q x gain_k = 4, attention that attends (PR 44's file, for the same reason)
# The output projections, so that a sublayer's increment stays about a quarter of the stream it joins (rms ~1.6 at the
# embedding): the conv operator's C * conv(B * x) has unit variance, an attending head's output ~0.5, a SwiGLU's hidden
# ~0.6 and four experts weighted 1/4 each ~0.3.
OUT_GAIN = {"conv": 0.4, "attention": 0.6, "dense": 0.6, "experts": 1.2}
DECOY_BIAS = -2.0  # the selection bias of a decoy expert (``_router``): under every score, so it is never chosen


def _sizes(hf: dict) -> dict:
  D, Hq, Hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
  hd = int(hf.get("head_dim") or D // Hq)
  types = hf_layer_types(hf)
  dense = int(hf["num_dense_layers"])
  return dict(
    D=D, K=int(hf["conv_L_cache"]), Hq=Hq, Hkv=Hkv, hd=hd, qd=Hq * hd, kd=Hkv * hd, F=hf["intermediate_size"], E=hf["num_experts"], k=hf["num_experts_per_tok"], Fm=hf["moe_intermediate_size"],
    V=hf["vocab_size"], decoys=int(hf.get("router_decoys") or 0), n_conv=types.count("conv"), n_attn=types.count("attention"), n_dense=min(dense, len(types)), n_moe=max(len(types) - dense, 0),
    A=int(IN_SHARE * D),
  )


def hf_layer_types(hf: dict) -> tuple:
  """"conv" | "attention" a layer. ``weights.shape_hf`` keeps scalars only, so inside a maker ``layer_types`` is gone:
  the file spells the list once more as the string ``layer_pattern`` (``c`` a conv layer, ``A`` a full-attention one),
  and a file in which the two disagree is refused."""
  names = {"c": "conv", "A": "attention"}
  if "layer_pattern" not in hf:  # (the catalog's row: the list alone)
    return tuple({"conv": "conv", "full_attention": "attention"}[t] for t in hf["layer_types"])
  pattern = str(hf["layer_pattern"])
  if set(pattern) - set(names) or len(pattern) != int(hf["num_hidden_layers"]):
    raise ValueError(f"layer_pattern {pattern!r} must name num_hidden_layers = {hf['num_hidden_layers']} layers, each 'c' or 'A'")
  out = tuple(names[letter] for letter in pattern)
  if "layer_types" in hf and tuple({"conv": "conv", "full_attention": "attention"}[t] for t in hf["layer_types"]) != out:
    raise ValueError(f"layer_pattern {pattern!r} does not spell layer_types {hf['layer_types']}")
  return out


def layer_stacks(hf: dict) -> list[tuple[str, int]]:
  """(stack, index in it) of every layer in model order, under the program's names (``ModelConfig.layer_stack``)."""
  seen, out = {}, []
  for i, mixer in enumerate(hf_layer_types(hf)):
    name = ("ssm_" if mixer == "conv" else "") + ("layers" if i < int(hf["num_dense_layers"]) else "moe_layers")
    out.append((name, seen.get(name, 0)))
    seen[name] = out[-1][1] + 1
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _conv_leaves(z: dict, keys, n: int) -> dict:
  D = z["D"]
  return {
    "ssm_norm": jnp.ones((n, D), ACT),
    "w_in": _stack(next(keys), n, (D, 3 * D), D**-0.5),  # B | C | x: both gates and the gated value unit-variance, so C * conv(B * x) is too
    "conv_w": normal(next(keys), (n, z["K"], D), z["K"] ** -0.5).astype(ACT),  # taps N(0, 1/K), as the other kinds' convolutions
    "w_out": _stack(next(keys), n, (D, D), OUT_GAIN["conv"] * D**-0.5),
  }


def _attention_leaves(z: dict, keys, n: int) -> dict:
  D, qd, kd, hd = z["D"], z["qd"], z["kd"], z["hd"]
  return {
    "attn_norm": jnp.ones((n, D), ACT),
    "wq": _stack(next(keys), n, (D, qd), D**-0.5), "wk": _stack(next(keys), n, (D, kd), D**-0.5), "wv": _stack(next(keys), n, (D, kd), D**-0.5),
    "wo": _stack(next(keys), n, (qd, D), OUT_GAIN["attention"] * qd**-0.5),
    "q_norm": jnp.full((n, hd), QK_NORM_GAIN, ACT), "k_norm": jnp.full((n, hd), QK_NORM_GAIN, ACT),
  }


def _dense_leaves(z: dict, keys, n: int) -> dict:
  D, F = z["D"], z["F"]
  return {
    "mlp_norm": jnp.ones((n, D), ACT),
    "w_gate": _stack(next(keys), n, (D, F), D**-0.5), "w_up": _stack(next(keys), n, (D, F), D**-0.5), "w_down": _stack(next(keys), n, (F, D), OUT_GAIN["dense"] * F**-0.5),
  }


def _router(hf: dict, z: dict, key, n: int, topics):
  """([n, D, E] bfloat16, the selection bias [n, E] float32, ``owns`` [n, T, E] or None). The router is an N(0, 1/D)
  part plus, for each of ``router_topics`` topics, ``router_topic_gain`` / D times the topic's direction on the columns
  of the topic's own k experts of that layer — drawn uniformly from the experts behind the first ``router_decoys`` — AND
  of (up to) two of those first ``router_decoys``, the decoys: experts whose raw score stands as high as the topic's own
  for every token of the topic, and whose selection bias, DECOY_BIAS, keeps them from ever being chosen (what an expert
  bias is for: an expert the scores would overload). With the bias the choice is the topic's own k; without it (the
  probe) the decoys stand among k + 2 saturated scores — and stand FIRST, so that where float32 rounds all of those
  sigmoids to 1 the tie goes to them (``top_k`` takes the lowest index). PR 53's file's router at this
  family's sizes, with the decoys moved to the front for that tie."""
  D, E, k, decoys = z["D"], z["E"], z["k"], z["decoys"]
  k_w, k_e, k_d = jax.random.split(key, 3)
  w = normal(k_w, (n, D, E), D**-0.5)
  bias = jnp.zeros((n, E), F32).at[:, :decoys].set(DECOY_BIAS)
  if topics is None:
    return w.astype(ACT), bias, None
  T = topics.shape[0]
  own = decoys + jax.lax.top_k(jax.random.uniform(k_e, (n, T, E - decoys)), k)[1]  # [n, T, k] the topic's experts, of those behind the decoys
  owns = jax.nn.one_hot(own, E, dtype=F32).sum(axis=2)  # [n, T, E], k ones a row
  boosted = owns
  if decoys:
    lure = jax.lax.top_k(jax.random.uniform(k_d, (n, T, decoys)), min(2, decoys))[1]
    boosted = owns + jax.nn.one_hot(lure, E, dtype=F32).sum(axis=2)
  return (w + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, boosted)).astype(ACT), bias, owns


def _expert_leaves(hf: dict, z: dict, keys, n: int, topics) -> tuple[dict, object]:
  D, E, Fm = z["D"], z["E"], z["Fm"]
  w_router, bias, owns = _router(hf, z, next(keys), n, topics)
  return {
    "mlp_norm": jnp.ones((n, D), ACT), "w_router": w_router, "router_bias": bias,
    "w_experts_gate": _stack(next(keys), n, (E, D, Fm), D**-0.5), "w_experts_up": _stack(next(keys), n, (E, D, Fm), D**-0.5),
    "w_experts_down": _stack(next(keys), n, (E, Fm, D), OUT_GAIN["experts"] * Fm**-0.5),
  }, owns


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names: one stack a (operator, FFN) pairing, each in model order; every expert
  held; the router's selection bias float32; no ``lm_head`` (the head is the table)."""
  return _make(hf, key)[0]


def router_tables(hf: dict, key) -> dict | None:
  """What the topic router reads a token by, drawn as ``make_params`` draws it from the same key: ``topic_of`` [V], each
  token id's topic, and ``owns`` [expert layers in model order, T, E], 1 where the topic owns the expert. None where the
  file states no topics."""
  return _make(hf, key)[1]


def _make(hf: dict, key) -> tuple[dict, dict | None]:
  z = _sizes(hf)
  D, A = z["D"], z["A"]
  keys = iter(jax.random.split(key, 96))
  topics = topic_of = None
  if int(hf.get("router_topics") or 0):
    k_t, k_a = jax.random.split(next(keys))
    signs = jnp.where(jax.random.bernoulli(k_t, 0.5, (int(hf["router_topics"]), D)), 1.0, -1.0).astype(F32)
    topics = signs * (jnp.arange(D) < A)  # a topic's direction stands on the channels the layers read
    topic_of = jax.random.randint(k_a, (z["V"],), 0, topics.shape[0])
  stacks = layer_stacks(hf)
  params, owns = {}, {}
  for name in dict.fromkeys(name for name, _ in stacks):
    n = sum(1 for s, _ in stacks if s == name)
    params[name] = _conv_leaves(z, keys, n) if name.startswith("ssm_") else _attention_leaves(z, keys, n)
    if name.endswith("moe_layers"):
      ffn, owns[name] = _expert_leaves(hf, z, keys, n, topics)
      params[name] |= ffn
    else:
      params[name] |= _dense_leaves(z, keys, n)
  read = normal(next(keys), (z["V"], D), 1.0)
  if topics is not None:
    read = read + float(hf["embed_topic_gain"]) * topics[topic_of]
  params["embed"] = jnp.where(jnp.arange(D) < A, read, normal(next(keys), (z["V"], D), HEAD_GAIN * (D - A) ** -0.5)).astype(ACT)
  params["final_norm"] = jnp.where(jnp.arange(D) < A, 0.0, FINAL_GAIN).astype(ACT)
  tables = None if topics is None else {"topic_of": topic_of, "owns": jnp.stack([owns[name][i] for name, i in stacks if name in owns])}
  return params, tables


# -------------------------------------------------------------- reference
# Written from the equations above, float32, one block at a time: the convolution shifted adds over a zero-padded
# sequence, the attention a full masked softmax, the experts a loop over the experts some token chose. No chunking, no
# cache, no tail, nothing of the program. One block's weights are float32 at a time, an expert's three matrices as the
# loop reaches it (the stacked leaves indexed inside the jitted loop) and the head a slab of the vocabulary at a time:
# the reference runs on the chip beside 10.8 GB of served weights.


def _round(x, dtype):
  """``x`` rounded to ``dtype``'s grid, still float32. Through ``reduce_precision``: XLA:TPU drops a float32 → bfloat16
  → float32 pair of converts as excess precision it is allowed to keep (PERF.md section 6, PR 36)."""
  if not dtype:
    return x
  info = jnp.finfo(jnp.dtype(dtype))
  return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _mm(a, b, operands: str | None):
  """``a @ b``; under the precision probe both operands are rounded to ``operands`` (a float8 type) first."""
  if operands:
    a, b = (t.astype(jnp.dtype(operands)).astype(F32) for t in (a, b))
  return a @ b


@partial(jax.jit, static_argnames=("eps", "act", "gate_after", "order", "taps_reversed", "operands"))
def _conv(h, norm, w_in, conv_w, w_out, *, eps, act=None, gate_after=False, order="BCx", taps_reversed=False, operands=None):
  """``act`` "silu": Mamba's activation behind the taps (the probe); ``gate_after``: conv(x) * B * C, both gates behind
  the taps; ``order``: which third of W_in's columns is which (published: B | C | x); ``taps_reversed``: w_0 weighs the
  newest position."""
  S = h.shape[0]
  thirds = dict(zip(order, jnp.split(_mm(rms_norm(h, norm, eps), w_in, operands), 3, axis=-1)))
  b, c, x = thirds["B"], thirds["C"], thirds["x"]
  K = conv_w.shape[0]
  taps = conv_w[::-1] if taps_reversed else conv_w
  g = x if gate_after else b * x
  gp = jnp.concatenate([jnp.zeros((K - 1, g.shape[1]), F32), g])  # zeros before the sequence
  conv = sum(taps[j] * gp[j : j + S] for j in range(K))  # c_t = sum_j w_j g_{t-(K-1)+j}
  if act == "silu":
    conv = jax.nn.silu(conv)
  return _mm(conv * b * c if gate_after else c * conv, w_out, operands)


def causal_attention(q, k, v, scale: float):
  """q [S, H, d], k / v [S, Hkv, d] → [S, H, d]: a full [S, S] masked softmax a head, one KV head's group of query heads
  at a time (4 x 1.7 k x 1.7 k scores of the teacher-forced run fit beside the model on the chip)."""
  S, H, Hkv = q.shape[0], q.shape[1], k.shape[1]
  mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

  def one_group(qkv):
    qg, kg, vg = qkv  # [S, H / Hkv, d], [S, d], [S, d]
    probs = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("qhd,kd->hqk", qg, kg) * scale, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,kd->qhd", probs, vg)

  out = jax.lax.map(one_group, (q.reshape(S, Hkv, H // Hkv, -1).transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [Hkv, S, H / Hkv, d]
  return out.transpose(1, 0, 2, 3).reshape(S, H, -1)


@partial(jax.jit, static_argnames=("Hq", "Hkv", "hd", "eps", "theta", "qk_norm", "operands"))
def _attention(h, norm, wq, wk, wv, wo, q_norm, k_norm, *, Hq, Hkv, hd, eps, theta, qk_norm=True, operands=None):
  """``qk_norm`` False: q and k go to rope as projected (the probe)."""
  S = h.shape[0]
  x = rms_norm(h, norm, eps)
  q, k, v = _mm(x, wq, operands).reshape(S, Hq, hd), _mm(x, wk, operands).reshape(S, Hkv, hd), _mm(x, wv, operands).reshape(S, Hkv, hd)
  cos, sin = rope_angles(S, hd, theta)
  if qk_norm:
    q, k = rms_norm(q, q_norm, eps), rms_norm(k, k_norm, eps)
  q, k = rope_half(q, cos, sin), rope_half(k, cos, sin)
  return _mm(causal_attention(q, k, v, hd**-0.5).reshape(S, Hq * hd), wo, operands)


@partial(jax.jit, static_argnames=("operands",))
def _dense(x, w_gate, w_up, w_down, *, operands=None):
  return _mm(jax.nn.silu(_mm(x, w_gate, operands)) * _mm(x, w_up, operands), w_down, operands)


def router_gates(x, w_router, bias, *, top_k: int, scaling: float, softmax: bool = False, normalised: bool = True, no_bias: bool = False, rounded: bool = False, swap: bool = False):
  """[S, E] gates, 0 where an expert was not chosen: scores sigmoid(x W_r) in float32, the choice the ``top_k`` largest
  of scores + bias, a gate the chosen SCORE (not the biased one) over (the chosen scores' sum + 1e-6, the published
  constant), times ``scaling``. Probes: ``softmax`` scores; ``normalised`` False: the chosen scores as they are;
  ``no_bias``; ``swap``: the last token's strongest expert and the first expert it did not choose trade places for every
  token (a wrong index, a permuted dispatch); ``rounded``: operands and logits rounded to bfloat16 (the exact probe)."""
  S, E = x.shape[0], w_router.shape[-1]
  w = w_router.astype(F32)
  logits = _round(_round(x, "bfloat16") @ _round(w, "bfloat16"), "bfloat16") if rounded else x @ w
  score = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
  idx = jax.lax.top_k(score if no_bias else score + bias.astype(F32), top_k)[1]
  gate = jnp.take_along_axis(score, idx, axis=-1)
  if normalised:
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-6)
  gates = jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], idx].add(gate * scaling)
  if swap:
    a, b = idx[-1, 0], jnp.argmax(jnp.ones((E,), F32).at[idx[-1]].set(0.0))
    ga, gb = gates[:, a], gates[:, b]
    gates = gates.at[:, a].set(gb).at[:, b].set(ga)
  return gates


@partial(jax.jit, static_argnames=("operands",))
def _experts(x, gates, gate_w, up_w, down_w, at, *, operands=None):
  """Σ_e g_e W_down_e (silu(W_gate_e x) * W_up_e x) over the experts some token chose, one expert at a time; the three
  STACKED leaves [n, E, ...] as served and ``at`` the layer, so that one expert's matrices are float32 at a time."""
  E, D, F = gate_w.shape[1:]

  def one_expert(acc, e):
    def visit(acc):
      wg = jax.lax.dynamic_slice(gate_w, (at, e, 0, 0), (1, 1, D, F))[0, 0].astype(F32)
      wu = jax.lax.dynamic_slice(up_w, (at, e, 0, 0), (1, 1, D, F))[0, 0].astype(F32)
      wd = jax.lax.dynamic_slice(down_w, (at, e, 0, 0), (1, 1, F, D))[0, 0].astype(F32)
      return acc + gates[:, e, None] * _mm(jax.nn.silu(_mm(x, wg, operands)) * _mm(x, wu, operands), wd, operands)

    return jax.lax.cond(jnp.any(gates[:, e] != 0), visit, lambda acc: acc, acc), None

  return jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))[0]


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, conv_act: str | None = None, gate_after: bool = False, order: str = "BCx", taps_reversed: bool = False,
                      qk_norm: bool = True, no_router_bias: bool = False, softmax_router: bool = False, normalised: bool = True, swap_experts: bool = False,
                      router_rounded: bool = False, operands: str | None = None, routed: list | None = None, increments: list | None = None):
  """The keywords are the probes' (``probes``, ``exact_probes``); ``drop_layer`` leaves layer number i out. No probes:
  ``routed``, a list that receives, for each expert layer in model order, [S, E] True where the router chose the expert;
  ``increments``, a list that receives (operator, rms of the stream, of the operator's increment, of the FFN's) a layer."""
  z = _sizes(hf)
  eps, theta = float(hf["norm_eps"]), float(hf["rope_theta"])
  if hf.get("rope_scaling"):
    raise ValueError("the reference implements plain rope alone (no rope_scaling, as published)")
  rms = lambda t: float(jnp.sqrt(jnp.mean(t * t)))  # noqa: E731
  h = params["embed"][tokens].astype(F32)
  for g, ((name, i), mixer) in enumerate(zip(layer_stacks(hf), hf_layer_types(hf))):
    if g == drop_layer:
      continue
    st = params[name]
    f32 = lambda *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731, B023
    if mixer == "conv":
      out = _conv(h, *f32("ssm_norm", "w_in", "conv_w", "w_out"), eps=eps, act=conv_act, gate_after=gate_after, order=order, taps_reversed=taps_reversed, operands=operands)
    else:
      out = _attention(h, *f32("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"), Hq=z["Hq"], Hkv=z["Hkv"], hd=z["hd"], eps=eps, theta=theta, qk_norm=qk_norm, operands=operands)
    mid = h + out
    x = rms_norm(mid, st["mlp_norm"][i].astype(F32), eps)
    if "w_experts_down" in st:
      gates = router_gates(x, st["w_router"][i], st["router_bias"][i], top_k=z["k"], scaling=float(hf["routed_scaling_factor"]), softmax=softmax_router, normalised=normalised,
                           no_bias=no_router_bias, rounded=router_rounded, swap=swap_experts)
      if routed is not None:
        routed.append(gates != 0)
      ffn = _experts(x, gates, st["w_experts_gate"], st["w_experts_up"], st["w_experts_down"], i, operands=operands)
    else:
      ffn = _dense(x, *f32("w_gate", "w_up", "w_down"), operands=operands)
    if increments is not None:
      increments.append((mixer, rms(h), rms(out), rms(ffn)))
    h = mid + ffn
  return _head(rms_norm(h, params["final_norm"], eps), params["embed"], operands)


def _head(x, table, operands, slabs: int = 8):
  """``x @ table.T`` [S, V], the tied head, a slab of the vocabulary at a time: the whole table in float32 is 0.54 GB,
  which the chip does not have to spare beside the served model, its pool and a layer's float32 weights."""
  step = -(-table.shape[0] // slabs)
  return jnp.concatenate([_mm(x, table[at : at + step].astype(F32).T, operands) for at in range(0, table.shape[0], step)], axis=1)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights, K/V pages and the convolution's tail in bfloat16 over sixteen blocks and
# the router, the gates' products and the tap sums in float32; the reference is float32 on the same bfloat16 weights.
# Each limit is 2 x the largest sound reading of the chip's seeds and under half of the weakest probe's smallest
# (``run.py --probe-sensitivity``); float8 matrix operands, the nearest precision below the stated one, are refused by
# ``mean_abs`` and by ``max_abs`` each. The readings are in PERF.md section 6 (PR 57).
LIMITS = {"mean_abs": 0.05, "max_abs": 0.21, "greedy_margin": 0.17}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0172-0.0244 over its eight seeds' checks at 168 positions and 0.0185-0.0187 over 4 x 4 x 160 teacher-forced steps after prompts of 773-1014 tokens (my chip runs, PR 57; PERF.md section 6 counts the seeds); float8 matmul operands read 0.146 (0.129-0.130 teacher-forced), two experts trading places 0.264 (0.30), a dropped last layer 0.30 (0.24), q and k without their norms 0.77 (0.51), every wrong reading of the convolution, the gates or the router 1.1-3.2: 0.05 is twice the largest sound reading and under half of the weakest probe's smallest - this is the limit that refuses them all",
  "max_abs": "the worst single entry: the chip read 0.046-0.081 at 168 positions and 0.097-0.099 over the 160 k entries of the teacher-forced runs, which must stay inside; float8 operands read 0.49 (0.66-0.82 teacher-forced), every wrong architecture 0.72-8.2: 0.21 is twice the largest sound reading and under half of the weakest probe's smallest",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 to 0.008 on six seeds, 0.031 and 0.086 on two, over their 8 served tokens; 0.055-0.069 at most over 4 x 4 x 160 teacher-forced decode steps (a tied table's logits stand closer than an untied head's: the second-best token is often within a rounding); the wrong architectures read 0.66-5.7 at 168 positions and 1.3-8.7 teacher-forced (float8 operands 0.26 and 0.62-0.67): a decode step that read a wrong tail, a wrong expert or a wrong page picks tokens well below the best. 0.17 is twice the largest sound reading, two thirds of float8's smallest (which mean_abs and max_abs refuse by more) and a quarter of the weakest wrong architecture's",
}


def probes(hf: dict) -> dict:
  """Wrong references the limits must refuse (``run.py --probe-sensitivity``): each reads the published keys another
  way, or computes in the precision below the stated one."""
  return {
    "drop_layer": {"drop_layer": int(hf["num_hidden_layers"]) - 1},
    "conv_with_silu": {"conv_act": "silu"},  # Mamba's convolution: an activation behind the taps
    "gate_after_taps": {"gate_after": True},  # conv(x) * B * C: both gates behind the taps, the tail then of x
    "chunk_order": {"order": "xBC"},  # W_in's thirds read x | B | C
    "taps_reversed": {"taps_reversed": True},
    "no_qk_norm": {"qk_norm": False},
    "router_without_bias": {"no_router_bias": True},
    "softmax_router": {"softmax_router": True},
    "unnormalised_topk": {"normalised": False},
    "two_experts_trade_places": {"swap_experts": True},
    # The precision below the one the configuration states (bfloat16 weights and activations): every matrix product's
    # operands rounded to float8 (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


def exact_probes(hf: dict) -> dict:
  """Wrong references that float32 arithmetic tells (the CPU tests) and bfloat16 serving over 168 positions cannot, so
  that no limit of ``correct`` is asked to refuse them: the router's operands and logits rounded to bfloat16 where the
  configuration states float32 (the chosen stand clear, and a saturated sigmoid hides the rounding)."""
  return {"router_bfloat16": {"router_rounded": True}}


def long_prompt_tokens(hf: dict) -> tuple[int, int]:
  """The prompt lengths of the teacher-forced run (``scripts/chip_teacher_forced.py``): the cell's longest prompts, so
  that the tail a prefill leaves has thousands of positions of gated products behind it before the decode steps."""
  return 768, 1024


# The first eight layers at tiny widths: c c A c c c A c — both dense layers, two attention layers with experts, four conv
# layers with experts. 8 query heads over 2 KV heads (4 a group) of 8; 16 experts top-4 of which the first 2 are decoys;
# 4 topics, so that about one topic owns an expert: at 48 read channels more owners' cross-talk stands as high as the
# topic's own score and bfloat16 flips a choice every few dozen positions (worst entry 0.7-0.85 at 8 and 16 topics,
# 0.08-0.09 at 4; CPU, PR 57).
REHEARSE_WIDTHS = {
  "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48, "num_hidden_layers": 8, "layer_pattern": "ccAcccAc",
  "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"],
  "num_attention_heads": 8, "num_key_value_heads": 2, "vocab_size": 512, "num_experts": 16, "num_experts_per_tok": 4, "router_topics": 4, "router_decoys": 2,
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _params(hf: dict) -> dict:
  """Parameters of each part of a block (my count from the file's keys); the float32 ones apart."""
  z = _sizes(hf)
  D = z["D"]
  return {
    "conv": D + D * 3 * D + z["K"] * D + D * D,  # operator norm, in_proj, taps, out_proj
    "attention": D + D * (z["qd"] + 2 * z["kd"]) + z["qd"] * D + 2 * z["hd"],  # operator norm, q k v, o, the two head norms
    "dense": D + 3 * D * z["F"],  # ffn norm, the SwiGLU's three
    "expert": 3 * D * z["Fm"],
    "moe_rest": D + D * z["E"], "moe_f32": z["E"],  # ffn norm, router; the selection bias
    "top": z["V"] * D + D,  # the table (embedding and head) and the final norm
  }


def param_count(hf: dict) -> int:
  """Every parameter of the model the file describes, block by block."""
  z, p = _sizes(hf), _params(hf)
  return z["n_conv"] * p["conv"] + z["n_attn"] * p["attention"] + z["n_dense"] * p["dense"] + z["n_moe"] * (p["moe_rest"] + p["moe_f32"] + z["E"] * p["expert"]) + p["top"]


def active_params(hf: dict) -> int:
  """What a token touches: every block's own, ``num_experts_per_tok`` experts an expert layer, the table once (as the
  head; its one embedding row's worth left out)."""
  z, p = _sizes(hf), _params(hf)
  return z["n_conv"] * p["conv"] + z["n_attn"] * p["attention"] + z["n_dense"] * p["dense"] + z["n_moe"] * (p["moe_rest"] + p["moe_f32"] + z["k"] * p["expert"]) + p["top"]


def weight_bytes(hf: dict, rows: float | None = None) -> float:
  """Every weight's bytes (``rows`` None), or those a decode step of ``rows`` rows touches: of the experts only the
  expected distinct ones; the tied table once, as the head (``flops_bytes`` adds the rows' embedding rows)."""
  z, p = _sizes(hf), _params(hf)
  touched = z["E"] if rows is None else experts_touched(hf, *routed_experts(hf)[1:], rows)
  return BF16 * (z["n_conv"] * p["conv"] + z["n_attn"] * p["attention"] + z["n_dense"] * p["dense"] + z["n_moe"] * (p["moe_rest"] + touched * p["expert"]) + p["top"]) + 4 * z["n_moe"] * p["moe_f32"]


def routed_experts(hf: dict) -> tuple[int, int, int, int]:
  """(first, counted, routed, top_k): a step's bytes count the experts a topic can own — all but the first
  ``router_decoys``, which the selection bias keeps from ever being chosen —, of which a token chooses ``top_k``."""
  z = _sizes(hf)
  return z["decoys"], z["E"] - z["decoys"], z["E"] - z["decoys"], z["k"]


def moe_expert_bytes(hf: dict, rows: float) -> float:
  """What the expert layers of one decode step of ``rows`` rows must read of the routed experts' weights: three matrices
  an expert, the distinct experts the stated router touches."""
  z = _sizes(hf)
  return z["n_moe"] * experts_touched(hf, *routed_experts(hf)[1:], rows) * _params(hf)["expert"] * BF16


def conv_tail_bytes(hf: dict, rows: float) -> float:
  """What ONE conv layer of a decode step moves of its state for ``rows`` rows: the K - 1 rows of the gated product,
  read and written, in bfloat16. (No ``ssm_state_bytes``: there is no state matrix, and a 2-row tail has no roofline.)"""
  z = _sizes(hf)
  return rows * 2 * (z["K"] - 1) * z["D"] * BF16


def step_weight_bytes(hf: dict, rows: float) -> float:
  return weight_bytes(hf, rows)


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a conv layer moves its rows' tail (read and written) whatever the context; an
  attention layer reads the K/V of every resident token (bfloat16, or int8 codes + a scale a head)."""
  z = _sizes(hf)
  per_head_side = z["hd"] + 4 if kv_quant == "int8" else BF16 * z["hd"]
  return [conv_tail_bytes(hf, rows) if t == "conv" else resident_tokens * z["Hkv"] * 2 * per_head_side for t in hf_layer_types(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  """What a token touches (``active_params``), 2 operations a parameter a row."""
  return 2.0 * rows * active_params(hf)


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"  # absent from the file: bfloat16 pages
