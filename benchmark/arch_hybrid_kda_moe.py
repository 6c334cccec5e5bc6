"""Kind ``hybrid_kda_moe``: a Ling-3.0-flash-shaped decoder (``bailing_hybrid``), cut to one chip's share of an
expert-parallel deployment. Layer ``i`` is a latent-attention (MLA) layer where ``(i + 1) % layer_group_size == 0``,
else a Kimi-Delta-Attention layer (Kimi Linear, arXiv:2510.26692): q, k, v through a causal depthwise convolution and
silu; q and k unit-norm a head; a log decay a head AND key channel, ``kda_lower_bound`` x sigmoid(W_f x + b_f); a
rank-one delta rule on a matrix state a head; a per-head norm and a head-wise sigmoid gate before the output
projection. The MLA layer is DeepSeek's (direct q, latent + one rope channel, adjacent rotary pairs) with an RMSNorm
over each query head before rope. Layers below ``first_k_dense_replace`` have a dense SwiGLU FFN, the others
DeepSeek-V3's ``noaux_tc`` router (sigmoid scores, selection on score + bias inside the best ``topk_group`` of
``n_group`` groups by their top-two sum, gates the scores normalised over the chosen and scaled) over
``num_experts_routed`` experts, of which this chip holds ``num_experts`` from ``experts_held_from`` on, plus one shared
expert: the routed part is the held experts' part of the layer's sum, in the program and here alike. Pre-norm
residual blocks. Weights and activations are bfloat16 as published; the recurrent state and every gate of it are
float32. What ``arch.py`` asks of a kind, in its order, plus ``ssm_state_bytes`` and ``moe_expert_bytes`` for the two
rooflines. Each reading of a key the catalog row does not explain is in the configuration file's ``assumed``."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import experts_touched
from reference import F32, causal_attention, rms_norm, rope_adjacent, rope_angles, swiglu
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``ModelConfig`` knows no delta-rule
  layer (every tree before PR 36) must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models.config import ModelConfig

  if "kda_lower_bound" not in getattr(ModelConfig, "__dataclass_fields__", {}):
    raise SystemExit("arch_kind hybrid_kda_moe: this program's ModelConfig has no kda_lower_bound (no Kimi-Delta-Attention layers): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

L2_EPS = 1e-6  # of q's and k's unit norm (the configuration file's ``assumed``)
LATENT_NORM_EPS = 1e-6
GATE_BIAS_MEAN, GATE_BIAS_STD = -3.0, 2.0  # b_f ~ N(-3, 2^2): see ``assumed.kda_gate_bias``


def _sizes(hf: dict) -> dict:
  D, H = hf["hidden_size"], hf["num_attention_heads"]
  hd = hf["head_dim"]
  nope, rope, vh, rank = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"], hf["kv_lora_rank"]
  types = hf_layer_types(hf)
  n_dense = min(int(hf["first_k_dense_replace"]), len(types))
  return dict(
    D=D, H=H, N=hd, P=hd, K=hf["short_conv_kernel_size"], C=3 * H * hd, F=hf["intermediate_size"], Fm=hf["moe_intermediate_size"],
    Fs=int(hf["num_shared_experts"]) * int(hf["moe_shared_expert_intermediate_size"]), V=hf["vocab_size"], E=int(hf.get("num_experts_routed") or hf["num_experts"]),
    Eh=hf["num_experts"], lo=int(hf.get("experts_held_from") or 0), k=hf["num_experts_per_tok"], nope=nope, rope=rope, vh=vh, rank=rank, qk=nope + rope,
    n_dense=n_dense, Ls=types.count("kda"), La=types.count("attention"), L=len(types),
  )


def hf_layer_types(hf: dict) -> tuple:
  group = int(hf["layer_group_size"])
  return tuple("attention" if (i + 1) % group == 0 else "kda" for i in range(int(hf["num_hidden_layers"])))


def layer_stacks(hf: dict) -> list[tuple[str, int]]:
  """(stack, index in it) of every layer in model order, under the program's names (``ModelConfig.layer_stack``)."""
  n_dense, seen, out = min(int(hf["first_k_dense_replace"]), int(hf["num_hidden_layers"])), {}, []
  for i, t in enumerate(hf_layer_types(hf)):
    name = ("ssm_" if t == "kda" else "") + ("layers" if i < n_dense else "moe_layers")
    out.append((name, seen.get(name, 0)))
    seen[name] = out[-1][1] + 1
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _kda_leaves(z: dict, keys, n: int) -> dict:
  D, H, N, P, C = z["D"], z["H"], z["N"], z["P"], z["C"]
  return {
    "ssm_norm": jnp.ones((n, D), ACT),
    "w_qkv": _stack(next(keys), n, (D, C), D**-0.5),
    "conv_w": normal(next(keys), (n, z["K"], C), z["K"] ** -0.5).astype(ACT),
    "w_f": _stack(next(keys), n, (D, H * N), D**-0.5),
    "b_f": GATE_BIAS_MEAN + GATE_BIAS_STD * normal(next(keys), (n, H * N), 1.0),
    "w_bg": _stack(next(keys), n, (D, 2 * H), D**-0.5),
    "o_norm": jnp.ones((n, P), ACT),
    "w_out": _stack(next(keys), n, (H * P, D), (H * P) ** -0.5),
    "mlp_norm": jnp.ones((n, D), ACT),
  }


def _mla_leaves(z: dict, keys, n: int) -> dict:
  D, H = z["D"], z["H"]
  return {
    "attn_norm": jnp.ones((n, D), ACT), "mlp_norm": jnp.ones((n, D), ACT), "kv_a_norm": jnp.ones((n, z["rank"]), ACT), "q_norm": jnp.ones((n, z["qk"]), ACT),
    "wq": _stack(next(keys), n, (D, H * z["qk"]), D**-0.5),
    "wkv_a": _stack(next(keys), n, (D, z["rank"] + z["rope"]), D**-0.5),
    "wkv_b": _stack(next(keys), n, (z["rank"], H * (z["nope"] + z["vh"])), z["rank"] ** -0.5),
    "wo": _stack(next(keys), n, (H * z["vh"], D), (H * z["vh"]) ** -0.5),
  }


def _router(hf: dict, z: dict, key, n: int, topics):
  """[n, D, E] bfloat16: an N(0, 1/D) part plus, for each of ``router_topics`` topics, ``router_topic_gain`` / D times
  the topic's direction on the columns of the topic's own k experts of that layer: two in each of ``topk_group``
  groups drawn from the ``n_group``, so that the group limit keeps all of them and the k-th choice stands clear of
  the (k+1)-th (the configuration file's ``assumed.router_topics``). Beside it ``owns`` [n, T, E], 1 where the topic
  owns the expert (None without topics)."""
  D, E, k, G, Gk = z["D"], z["E"], z["k"], int(hf["n_group"]), int(hf["topk_group"])
  k_w, k_g, k_e = jax.random.split(key, 3)
  w = normal(k_w, (n, D, E), D**-0.5)
  if topics is None:
    return w.astype(ACT), None
  T, per = topics.shape[0], k // Gk
  in_group = jax.lax.top_k(jax.random.uniform(k_g, (n, T, G)), Gk)[1]  # [n, T, Gk] the topic's groups
  in_expert = jax.lax.top_k(jax.random.uniform(k_e, (n, T, Gk, E // G)), per)[1]  # [n, T, Gk, per] its experts inside each
  owns = jax.nn.one_hot((in_group[..., None] * (E // G) + in_expert).reshape(n, T, k), E, dtype=F32).sum(axis=2)  # [n, T, E], k ones a row
  return (w + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, owns)).astype(ACT), owns


def _ffn_leaves(hf: dict, z: dict, keys, n: int, experts: bool, topics) -> tuple[dict, object]:
  """The stack's FFN leaves and, for an expert stack under a topic router, which experts each topic owns."""
  D = z["D"]
  if not experts:
    return {name: _stack(next(keys), n, shape, shape[0] ** -0.5) for name, shape in (("w_gate", (D, z["F"])), ("w_up", (D, z["F"])), ("w_down", (z["F"], D)))}, None
  w_router, owns = _router(hf, z, next(keys), n, topics)
  out = {"w_router": w_router, "router_bias": jnp.zeros((n, z["E"]), F32)}
  for name, shape in (("w_experts_gate", (z["Eh"], D, z["Fm"])), ("w_experts_up", (z["Eh"], D, z["Fm"])), ("w_experts_down", (z["Eh"], z["Fm"], D))):
    out[name] = _stack(next(keys), n, shape, shape[1] ** -0.5)
  for name, shape in (("w_shared_gate", (D, z["Fs"])), ("w_shared_up", (D, z["Fs"])), ("w_shared_down", (z["Fs"], D))):
    out[name] = _stack(next(keys), n, shape, shape[0] ** -0.5)
  return out, owns


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names (``models/decoder.py init_shard_params``): one stack a (mixer, FFN)
  pairing, each in model order; the expert leaves hold the ``num_experts`` experts held, the router all
  ``num_experts_routed``; the gate's bias ``b_f`` and the router's selection bias float32."""
  return _make(hf, key)[0]


def router_tables(hf: dict, key) -> dict | None:
  """What the topic router reads a token by, drawn as ``make_params`` draws it from the same key (the same function;
  under ``jit`` the compiler drops the weights): ``topic_of`` [V], each token id's topic, and ``owns``
  [expert layers in model order, T, E routed], 1 where the topic owns the expert. None where the file states no topics."""
  return _make(hf, key)[1]


def _make(hf: dict, key) -> tuple[dict, dict | None]:
  z = _sizes(hf)
  keys = iter(jax.random.split(key, 64))
  topics = topic_of = None
  if int(hf.get("router_topics") or 0):
    k_t, k_a = jax.random.split(next(keys))
    topics = jnp.where(jax.random.bernoulli(k_t, 0.5, (int(hf["router_topics"]), z["D"])), 1.0, -1.0).astype(F32)
    topic_of = jax.random.randint(k_a, (z["V"],), 0, topics.shape[0])
  counts: dict = {}
  for name, _ in layer_stacks(hf):
    counts[name] = counts.get(name, 0) + 1
  params, owns = {}, {}
  for name, n in counts.items():
    mixer = _kda_leaves(z, keys, n) if name.startswith("ssm_") else _mla_leaves(z, keys, n)
    ffn, owns[name] = _ffn_leaves(hf, z, keys, n, name.endswith("moe_layers"), topics)
    params[name] = {**mixer, **ffn}
  embed = normal(next(keys), (z["V"], z["D"]), 1.0)
  if topics is not None:
    embed = embed + float(hf["embed_topic_gain"]) * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((z["D"],), ACT)
  params["lm_head"] = normal(next(keys), (z["D"], z["V"]), z["D"] ** -0.5).astype(ACT)
  tables = None if topics is None else {"topic_of": topic_of, "owns": jnp.stack([owns[name][i] for name, i in layer_stacks(hf) if owns[name] is not None])}
  return params, tables


# -------------------------------------------------------------- reference
# Written from the equations in ISSUE 36, float32, one token at a time: the delta rule is a ``lax.scan`` over time with
# the state as mathematics has it, S [N key channels, P values] a head; the convolution four shifted adds over a
# zero-padded sequence. No chunking, no cache, nothing of the program.


def _round(x, dtype):
  """``x`` rounded to ``dtype``'s grid, still float32. Through ``reduce_precision``: XLA:TPU drops a float32 → bfloat16
  → float32 pair of converts as excess precision it is allowed to keep (the first chip run read every bfloat16 probe
  equal to the sound reference to the last digit; PERF.md section 6, PR 36)."""
  if not dtype:
    return x
  info = jnp.finfo(jnp.dtype(dtype))
  return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _mm(a, b, operands: str | None):
  """``a @ b``; under the precision probe both operands are rounded to ``operands`` (a float8 type) first."""
  if operands:
    a, b = (t.astype(jnp.dtype(operands)).astype(F32) for t in (a, b))
  return a @ b


@partial(jax.jit, static_argnames=("H", "N", "P", "eps", "lower", "no_decay", "no_delta", "beta_one", "no_gate", "state_dtype", "decay_dtype", "operands"))
def _kda(h, norm, w_qkv, conv_w, w_f, b_f, w_bg, o_norm, w_out, *, H, N, P, eps, lower, no_decay=False, no_delta=False, beta_one=False, no_gate=False, state_dtype=None, decay_dtype=None, operands=None):
  S = h.shape[0]
  x = rms_norm(h, norm, eps)
  K = conv_w.shape[0]
  pre = jnp.concatenate([jnp.zeros((K - 1, w_qkv.shape[1]), F32), _mm(x, w_qkv, operands)])  # zeros before the sequence
  qkv = jax.nn.silu(sum(conv_w[j] * pre[j : j + S] for j in range(K)))  # out_t = sum_j w_j x_{t-(K-1)+j}
  q, k, v = qkv[:, : H * N].reshape(S, H, N), qkv[:, H * N : 2 * H * N].reshape(S, H, N), qkv[:, 2 * H * N :].reshape(S, H, P)
  unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
  q, k = unit(q) / N**0.5, unit(k)
  # The precision probe of the decay: the gate's pre-activation, the log decay and the decay itself in ``decay_dtype``
  # where float32 is stated.
  g = lower * jax.nn.sigmoid(_round(_mm(x, w_f, operands) + b_f, decay_dtype)).reshape(S, H, N)
  alpha = jnp.ones_like(g) if no_decay else _round(jnp.exp(_round(g, decay_dtype)), decay_dtype)
  bg = jax.nn.sigmoid(_mm(x, w_bg, operands))
  beta, gate = (jnp.ones((S, H), F32) if beta_one else bg[:, :H]), bg[:, H:]

  def step(state, t):  # state [H, N, P]
    q_t, k_t, v_t, a_t, b_t = t
    state = a_t[:, :, None] * state  # Diag(alpha) S
    seen = jnp.zeros_like(v_t) if no_delta else jnp.einsum("hnp,hn->hp", state, k_t)  # S^T k
    state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]  # (I - beta k k^T) . + beta k v^T
    state = _round(state, state_dtype)  # a probe: the state a slot keeps between steps, stored in a coarser type than float32
    return state, jnp.einsum("hnp,hn->hp", state, q_t)  # S^T q

  _, o = jax.lax.scan(step, jnp.zeros((H, N, P), F32), (q, k, v, alpha, beta))
  o = rms_norm(o, o_norm, eps)
  if not no_gate:
    o = o * gate[:, :, None]
  return h + _mm(o.reshape(S, H * P), w_out, operands)


@partial(jax.jit, static_argnames=("n_heads", "rank", "nope", "rope", "vh", "eps", "theta", "operands"))
def _mla(h, attn_norm, kv_a_norm, q_norm, wq, wkv_a, wkv_b, wo, *, n_heads, rank, nope, rope, vh, eps, theta, operands=None):
  S = h.shape[0]
  x = rms_norm(h, attn_norm, eps)
  q = rms_norm(_mm(x, wq, operands).reshape(S, n_heads, nope + rope), q_norm, eps)  # use_qk_norm, as read (``assumed``)
  kv_a = _mm(x, wkv_a, operands)
  c_kv = rms_norm(kv_a[:, :rank], kv_a_norm, LATENT_NORM_EPS)
  kv = _mm(c_kv, wkv_b, operands).reshape(S, n_heads, nope + vh)
  cos, sin = rope_angles(S, rope, theta)
  q_pe = rope_adjacent(q[..., nope:], cos, sin)
  k_pe = rope_adjacent(kv_a[:, None, rank:], cos, sin)  # one rope channel shared by every head
  qf = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
  kf = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (S, n_heads, rope))], axis=-1)
  out = causal_attention(qf, kf, kv[..., nope:], (nope + rope) ** -0.5)
  return h + _mm(out.reshape(S, n_heads * vh), wo, operands)


def _swiglu(x, w_gate, w_up, w_down, operands):
  return swiglu(x, w_gate, w_up, w_down) if not operands else _mm(jax.nn.silu(_mm(x, w_gate, operands)) * _mm(x, w_up, operands), w_down, operands)


@partial(jax.jit, static_argnames=("eps", "operands"))
def _dense_ffn(h, mlp_norm, w_gate, w_up, w_down, *, eps, operands=None):
  return h + _swiglu(rms_norm(h, mlp_norm, eps), w_gate, w_up, w_down, operands)


def router_gates(x, w_router, router_bias, *, top_k, n_group, topk_group, scaling, group_limit=True):
  """[S, E] gates of the ``noaux_tc`` router: 0 where an expert was not chosen."""
  S, E = x.shape[0], w_router.shape[-1]
  score = jax.nn.sigmoid(x @ w_router.astype(F32))
  sel = score + router_bias.astype(F32)
  if group_limit and n_group > 1:
    group_score = jnp.sum(jax.lax.top_k(sel.reshape(S, n_group, E // n_group), 2)[0], axis=-1)
    kept = jnp.zeros((S, n_group), bool).at[jnp.arange(S)[:, None], jax.lax.top_k(group_score, topk_group)[1]].set(True)
    sel = jnp.where(jnp.repeat(kept, E // n_group, axis=-1), sel, 0.0)
  idx = jax.lax.top_k(sel, top_k)[1]
  gate = jnp.take_along_axis(score, idx, axis=-1)
  gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * scaling  # norm_topk_prob over ALL the chosen, held or not
  return jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], idx].add(gate)


@partial(jax.jit, static_argnames=("top_k", "n_group", "topk_group", "scaling", "eps", "lo", "group_limit", "drop_expert", "operands"))
def _moe_ffn(h, mlp_norm, w_router, router_bias, eg, eu, ed, sg, su, sd, *, top_k, n_group, topk_group, scaling, eps, lo, group_limit=True, drop_expert=False, operands=None):
  """Every token through every HELD expert, one at a time, weighted by its gate (0 where the expert was not chosen):
  the held experts' part of the sum over the chosen, and the shared expert. ``lo``: the router column of the first
  expert held."""
  x = rms_norm(h, mlp_norm, eps)
  gates = router_gates(x, w_router, router_bias, top_k=top_k, n_group=n_group, topk_group=topk_group, scaling=scaling, group_limit=group_limit)
  held = jax.lax.dynamic_slice_in_dim(gates, lo, eg.shape[0], axis=1)  # [S, Eh]
  if drop_expert:  # sensitivity probe only: lose each token's strongest held expert
    held = jnp.where(held == jnp.max(held, axis=-1, keepdims=True), 0.0, held)

  def one_expert(acc, e):
    return acc + held[:, e, None] * _swiglu(x, eg[e].astype(F32), eu[e].astype(F32), ed[e].astype(F32), operands), None

  routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(eg.shape[0]))
  return h + routed + _swiglu(x, sg, su, sd, operands)


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, no_decay: bool = False, no_delta: bool = False, beta_one: bool = False,
                      no_gate: bool = False, held_shift: int = 0, group_limit: bool = True, drop_expert: bool = False, theta_scale: float = 1.0,
                      state_dtype: str | None = None, decay_dtype: str | None = None, operands: str | None = None, routed: list | None = None):
  """``routed``, no probe: a list that receives, for each expert layer in model order, [S, E routed] True where the
  router chose the expert (``tools/experts_touched.py`` holds them against the topics' tables)."""
  z = _sizes(hf)
  eps = float(hf["rms_norm_eps"])
  f32 = lambda st, i, *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731
  h = params["embed"][tokens].astype(F32)
  for g, ((name, i), kind) in enumerate(zip(layer_stacks(hf), hf_layer_types(hf))):
    if g == drop_layer:
      continue
    st = params[name]
    if kind == "kda":
      h = _kda(
        h, *f32(st, i, "ssm_norm", "w_qkv", "conv_w", "w_f", "b_f", "w_bg", "o_norm", "w_out"), H=z["H"], N=z["N"], P=z["P"], eps=eps, lower=float(hf["kda_lower_bound"]),
        no_decay=no_decay, no_delta=no_delta, beta_one=beta_one, no_gate=no_gate, state_dtype=state_dtype, decay_dtype=decay_dtype, operands=operands,
      )
    else:
      h = _mla(
        h, *f32(st, i, "attn_norm", "kv_a_norm", "q_norm", "wq", "wkv_a", "wkv_b", "wo"), n_heads=z["H"], rank=z["rank"], nope=z["nope"], rope=z["rope"], vh=z["vh"], eps=eps,
        theta=float(hf["rope_theta"]) * theta_scale, operands=operands,
      )
    if "w_router" in st:
      route = dict(top_k=z["k"], n_group=int(hf["n_group"]), topk_group=int(hf["topk_group"]), scaling=float(hf["routed_scaling_factor"]))
      if routed is not None:
        routed.append(router_gates(rms_norm(h, st["mlp_norm"][i], eps), st["w_router"][i], st["router_bias"][i], group_limit=group_limit, **route) > 0)
      h = _moe_ffn(
        h, st["mlp_norm"][i], st["w_router"][i], st["router_bias"][i], st["w_experts_gate"][i], st["w_experts_up"][i], st["w_experts_down"][i],
        *f32(st, i, "w_shared_gate", "w_shared_up", "w_shared_down"), **route, eps=eps, lo=z["lo"] + held_shift, group_limit=group_limit, drop_expert=drop_expert, operands=operands,
      )
    else:
      h = _dense_ffn(h, *f32(st, i, "mlp_norm", "w_gate", "w_up", "w_down"), eps=eps, operands=operands)
  return _mm(rms_norm(h, params["final_norm"], eps), params["lm_head"].astype(F32), operands)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and the latent pages in bfloat16 over 7 layers and the recurrent state
# and its gates in float32; the reference is float32 on the same bfloat16 weights. It sits closer to its reference than
# the other kinds do (0.006-0.008 mean where they read 0.010-0.018 and 0.036-0.062): seven layers, unit-scale residual
# increments, a float32 state. Each limit is about three times the largest sound reading of thirteen seeds on the chip
# and under the smallest reading of the reference in the nearest precision below the stated one (float8 matrix
# operands); the readings of every probe, and the four that no limit refuses, are in PERF.md section 6 (PR 36).
LIMITS = {"mean_abs": 0.024, "max_abs": 0.08, "greedy_margin": 0.08}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0055-0.0080 over thirteen seeds (my chip runs, PR 36, calls A and C); float8 matmul operands read 0.054-0.070 over four seeds, one held expert lost a token 0.13-0.21, the delta term off 0.16-0.22, the last layer dropped 0.24-0.34: this is the limit that refuses them. The latent layer's rope base 100x too small reads 0.019-0.023 and is NOT refused (one layer of seven, softmax logits of unit spread)",
  "max_abs": "the worst single entry: the chip read 0.015-0.026; float8 operands read 0.14-0.21, every probe of the recurrence and of the experts above 0.36; the rope base 0.046-0.066 (not refused)",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 on twelve seeds of thirteen and 0.0094 on one (twice the worst entry bounds it: 0.05); 0.023 at most over 4 x 160 teacher-forced decode steps; float8 operands read 0.023-0.128, the probes of the recurrence 0.17-1.9: a decode step that read a wrong state or a wrong expert picks tokens well below the best",
}


def probes(hf: dict) -> dict:
  return {
    "drop_last_layer": {"drop_layer": int(hf["num_hidden_layers"]) - 1},
    "decay_off": {"no_decay": True},
    "delta_term_off": {"no_delta": True},
    "beta_one": {"beta_one": True},
    "output_gate_off": {"no_gate": True},
    "held_range_shifted_by_one": {"held_shift": 1},
    "group_limit_off": {"group_limit": False},
    "lose_one_expert_per_token": {"drop_expert": True},
    "rope_base_100x_too_small": {"theta_scale": 0.01},
    # The precision below the one the configuration states, where it states float32: the recurrent state rounded to
    # bfloat16 after every token, and the decay (its gate, its logarithm, itself) computed in bfloat16.
    "recurrent_state_bfloat16": {"state_dtype": "bfloat16"},
    "decay_bfloat16": {"decay_dtype": "bfloat16"},
    # ... and where it states bfloat16 (weights, activations): every matrix product's operands rounded to float8
    # (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


# One shortened period: a KDA layer with the dense FFN, a KDA and a latent-attention layer with experts, a KDA layer
# with experts; 8 of 32 experts held (groups 0 and 1 of 8), top 8 inside the best 4 groups.
REHEARSE_WIDTHS = {
  "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 32, "num_hidden_layers": 4, "layer_group_size": 3,
  "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512, "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
  "v_head_dim": 16, "rotary_dim": 8, "num_experts": 8, "num_experts_routed": 32, "router_topics": 16,
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _params(hf: dict) -> dict:
  """Parameters of each part (my count from the file's keys)."""
  z = _sizes(hf)
  D, H, N, P = z["D"], z["H"], z["N"], z["P"]
  return {
    "kda": D + D * z["C"] + z["K"] * z["C"] + D * H * N + D * 2 * H + P + H * P * D,  # + b_f, float32, counted apart
    "mla": D + z["rank"] + z["qk"] + D * H * z["qk"] + D * (z["rank"] + z["rope"]) + z["rank"] * H * (z["nope"] + z["vh"]) + H * z["vh"] * D,
    "dense_ffn": D + 3 * D * z["F"],
    "expert": 3 * D * z["Fm"],
    "moe_rest": D + D * z["E"] + 3 * D * z["Fs"],  # norm, router, shared expert; the selection bias (float32) apart
    "top": 2 * z["V"] * D + D,
  }


def weight_bytes(hf: dict, rows: float | None = None) -> float:
  """Every weight's bytes (``rows`` None), or those a decode step of ``rows`` rows touches: of the held experts only
  the expected distinct ones."""
  z, p = _sizes(hf), _params(hf)
  n_moe = z["L"] - z["n_dense"]
  touched = z["Eh"] if rows is None else held_experts_touched(hf, rows)
  top = p["top"] if rows is None else p["top"] - z["V"] * z["D"]  # a step reads the head whole and of the embedding its rows' rows (``flops_bytes`` adds those)
  per_param = z["Ls"] * p["kda"] + z["La"] * p["mla"] + z["n_dense"] * p["dense_ffn"] + n_moe * (p["moe_rest"] + touched * p["expert"]) + top
  return BF16 * per_param + 4 * (z["Ls"] * z["H"] * z["N"] + n_moe * z["E"])


def held_experts_touched(hf: dict, rows: float) -> float:
  """Expected distinct HELD experts of one layer that ``rows`` tokens choose, under the router the file states
  (``flops_bytes.experts_touched``): with ``router_topics`` a topic owns a held expert with probability
  ``topk_group / n_group`` (its group is one of the topic's) x ``k / topk_group`` of the group's ``E / n_group``
  (``_router``), which is k / E; without, uniform routing over all E."""
  return experts_touched(hf, *routed_experts(hf)[1:], rows)


def routed_experts(hf: dict) -> tuple[int, int, int, int]:
  """(first, counted, routed, top_k): a step's bytes count the ``counted`` experts held, from router column ``first``
  on, of the ``routed`` that a token chooses ``top_k`` of."""
  z = _sizes(hf)
  return z["lo"], z["Eh"], z["E"], z["k"]


def moe_expert_bytes(hf: dict, rows: float) -> float:
  """What the expert layers of one decode step of ``rows`` rows must read of the held routed experts' weights."""
  z = _sizes(hf)
  return (z["L"] - z["n_dense"]) * held_experts_touched(hf, rows) * _params(hf)["expert"] * BF16


def ssm_state_bytes(hf: dict, rows: float) -> float:
  """What the KDA layers of one decode step must move for ``rows`` rows: each layer reads and writes every row's
  state [H, P, N] in float32 and its ``K - 1`` convolution rows in bfloat16."""
  z = _sizes(hf)
  return z["Ls"] * rows * 2 * (z["H"] * z["P"] * z["N"] * 4 + (z["K"] - 1) * z["C"] * BF16)


def step_weight_bytes(hf: dict, rows: float) -> float:
  return weight_bytes(hf, rows)


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a KDA layer moves its rows' state (read and written) whatever the context;
  the latent-attention layer reads the bfloat16 latent and rope channel of every resident token."""
  z = _sizes(hf)
  state = ssm_state_bytes(hf, rows) / max(z["Ls"], 1)
  return [state if t == "kda" else resident_tokens * (z["rank"] + z["rope"]) * BF16 for t in hf_layer_types(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  """Everything outside the routed experts once a row, plus each row's chosen experts that are held (k x held / routed
  of them, under uniform routing) in every expert layer. 2 operations a parameter a row."""
  z, p = _sizes(hf), _params(hf)
  n_moe = z["L"] - z["n_dense"]
  outside = z["Ls"] * p["kda"] + z["La"] * p["mla"] + z["n_dense"] * p["dense_ffn"] + n_moe * p["moe_rest"] + p["top"] / 2  # the head; the embedding is a gather
  return 2.0 * rows * (outside + n_moe * z["k"] * z["Eh"] / z["E"] * p["expert"])


CACHE_TYPE_ENV = None  # the latent cache is bfloat16 whatever XOT_TPU_KV_QUANT says
