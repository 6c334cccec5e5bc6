"""Kind ``hybrid_ssm_moe``: a Nemotron-3-Nano-shaped decoder (``nemotron_h``; the family's block is the Nemotron-H
report's, arXiv:2504.03624). A block is ``h <- h + f(rmsnorm(h))`` with ONE sublayer ``f``, named by its letter in
``hybrid_override_pattern``:

- ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; a causal depthwise convolution of ``conv_kernel`` taps with bias over
  xBC, silu; ``[x | B | C]`` with ``n_groups`` groups of B and C of ``ssm_state_size`` each (head h reads group
  h // (H / G)); ``delta = softplus(dt + dt_bias)``, ``S_t = exp(-delta e^A_log) S_{t-1} + delta x_t (x) B_t``,
  ``y_t = S_t C_t + D x_t``; then the gate FIRST and the norm after it, over each group's ``d_inner / G`` channels by
  itself, ``rmsnorm_group(y * silu(z)) * w``; then ``W_out``. ``d_inner = mamba_num_heads x mamba_head_dim``.
- ``*``, attention: grouped-query softmax attention, ``num_attention_heads`` query heads over ``num_key_value_heads`` KV
  heads of ``head_dim``, scale 1/sqrt(head_dim), no bias, no window and NO position term.
- ``E``, experts: a router of ``n_routed_experts`` outputs in float32, sigmoid scores, the choice drawn from scores +
  the selection bias, ``num_experts_per_tok`` chosen, weights the chosen scores over their sum times
  ``routed_scaling_factor``; an expert is TWO matrices and no gate, ``W_down relu(W_up x)^2``; one shared expert of the
  same form at ``moe_shared_expert_intermediate_size``, added for every token.

RMSNorm with a plain gain, a final norm, an untied head. Weights and activations are bfloat16, the recurrent state, its
decay and step, and the router float32. The reference below walks the pattern ONE BLOCK A LETTER; the program reads it
as (mixer, FFN) layer steps (``models/config.py _nemotron_h_fields``), so that pairing is what the comparison tests.
The leaves are the program's (``models/decoder.py init_shard_params``): one stack a (mixer, FFN) pairing —
``ssm_moe_layers`` (an ``M`` and the ``E`` behind it), ``ssm_mixer_layers`` (an ``M`` alone), ``moe_layers`` (a ``*``
and its ``E``) —, both of an expert's matrices stored [F, D] (``w_experts_up_t``, ``w_experts_down``). What ``arch.py``
asks of a kind, in its order, plus ``ssm_state_bytes``, ``moe_expert_bytes``, ``routed_experts``, ``router_tables``,
``hf_layer_types`` (one entry a layer STEP, as the program's pool counts them), ``long_prompt_tokens`` and
``exact_probes``. Each reading of a key the catalog row does not explain is in the configuration file's ``assumed``."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import experts_touched
from reference import F32, rms_norm, rope_angles, rope_half
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` knows no
  ``nemotron_h`` (every tree before PR 53) must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models import config

  if "nemotron_h" not in getattr(config, "MODEL_FAMILIES", {}):
    raise SystemExit("arch_kind hybrid_ssm_moe: this program's config_from_hf knows no model_type 'nemotron_h' (no block of one sublayer, no grouped B/C, no ungated experts): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

# The seeded weights' departures from N(0, 1/in) and unit gains (the file's ``assumed.weights``). wq and wk are drawn
# QK_GAIN times wider, so that a head's softmax logits spread over ~4 and attention attends (no q/k norm carries a
# gain). The three output projections are drawn narrower, so that a block's increment stays about a quarter of the
# stream it joins (a unit-gain relu² expert layer adds 1.7 x a unit-rms input: six experts at 2.5 / 6 each and a shared
# one twice as wide; a stack of such blocks is its last block's output, and no probe of an earlier one moves anything).
QK_GAIN = 2.0
OUT_GAIN = {"mamba": 0.5, "attention": 0.5, "experts": 0.25}
DECOY_BIAS = -2.0  # the selection bias of a decoy expert (``_router``): under every score, so it is never chosen


def _sizes(hf: dict) -> dict:
  D, H, P, N, G = hf["hidden_size"], hf["mamba_num_heads"], hf["mamba_head_dim"], hf["ssm_state_size"], hf["n_groups"]
  hd, di = hf["head_dim"], H * P
  return dict(
    D=D, H=H, P=P, N=N, G=G, K=hf["conv_kernel"], di=di, C=di + 2 * G * N, hd=hd, Hq=hf["num_attention_heads"], Hkv=hf["num_key_value_heads"],
    qd=hf["num_attention_heads"] * hd, kd=hf["num_key_value_heads"] * hd, E=hf["n_routed_experts"], k=hf["num_experts_per_tok"], Fm=hf["moe_intermediate_size"],
    Fs=int(hf["n_shared_experts"]) * int(hf["moe_shared_expert_intermediate_size"]), V=hf["vocab_size"], decoys=int(hf.get("router_decoys") or 0),
    n_M=str(hf["hybrid_override_pattern"]).count("M"), n_A=str(hf["hybrid_override_pattern"]).count("*"), n_E=str(hf["hybrid_override_pattern"]).count("E"),
  )


def layer_steps(hf: dict) -> list[tuple[str, str]]:
  """(mixer, FFN) of every layer step, as the program reads the pattern: a mixer letter opens a step, an ``E`` right
  behind it is the step's FFN ("experts"), else it has none."""
  steps: list = []
  for letter in str(hf["hybrid_override_pattern"]):
    if letter in "M*":
      steps.append(["mamba" if letter == "M" else "attention", "none"])
    elif letter == "E" and steps and steps[-1][1] == "none":
      steps[-1][1] = "experts"
    else:
      raise ValueError(f"hybrid_override_pattern {hf['hybrid_override_pattern']!r}: the letter {letter!r} cannot be read")
  if len(str(hf["hybrid_override_pattern"])) != int(hf["num_hidden_layers"]):
    raise ValueError(f"hybrid_override_pattern {hf['hybrid_override_pattern']!r} does not name num_hidden_layers {hf['num_hidden_layers']} blocks")
  return [tuple(s) for s in steps]


def hf_layer_types(hf: dict) -> tuple:
  """"mamba" | "attention" a layer STEP: the entries of ``cache_read_bytes`` and the layers of the program's pool."""
  return tuple(m for m, _ in layer_steps(hf))


def layer_stacks(hf: dict) -> list[tuple[str, int]]:
  """(stack, index in it) of every layer step in model order, under the program's names (``ModelConfig.layer_stack``)."""
  seen, out = {}, []
  for mixer, ffn in layer_steps(hf):
    name = ("ssm_" if mixer == "mamba" else "") + {"experts": "moe_layers", "none": "mixer_layers"}[ffn]
    out.append((name, seen.get(name, 0)))
    seen[name] = out[-1][1] + 1
  return out


def blocks(hf: dict) -> list[tuple[str, str, int]]:
  """(letter, stack, index in it) of every published BLOCK in model order: an ``E`` lives in the stack of the mixer
  block ahead of it."""
  out, at = [], -1
  stacks = layer_stacks(hf)
  for letter in str(hf["hybrid_override_pattern"]):
    at += letter in "M*"
    out.append((letter, *stacks[at]))
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _mamba_leaves(z: dict, keys, n: int) -> dict:
  D, H, di, C = z["D"], z["H"], z["di"], z["C"]
  # Granite's maker's draws (mamba_ssm Mamba2.__init__): A = -U(1, 16), the step softplus(dt_bias) log-uniform in
  # [time_step_min, time_step_max] = [1e-3, 1e-1], D = 1, conv taps N(0, 1/K) — and a conv bias that is not zero
  # (use_conv_bias true), N(0, 0.1^2), so that the bias is computed and not only carried.
  dt = jnp.exp(jax.random.uniform(next(keys), (n, H), F32, jnp.log(1e-3), jnp.log(1e-1)))
  return {
    "ssm_norm": jnp.ones((n, D), ACT),
    "w_z": _stack(next(keys), n, (D, di), D**-0.5),
    "w_xbc": _stack(next(keys), n, (D, C), D**-0.5),
    "w_dt": _stack(next(keys), n, (D, H), D**-0.5),
    "conv_w": normal(next(keys), (n, z["K"], C), z["K"] ** -0.5).astype(ACT),
    "conv_b": normal(next(keys), (n, C), 0.1).astype(ACT),
    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # the inverse of softplus
    "A_log": jnp.log(jax.random.uniform(next(keys), (n, H), F32, 1.0, 16.0)),
    "D": jnp.ones((n, H), F32),
    "gate_norm": jnp.ones((n, di), ACT),
    "w_out": _stack(next(keys), n, (di, D), OUT_GAIN["mamba"] * di**-0.5),
  }


def _attention_leaves(z: dict, keys, n: int) -> dict:
  D, qd, kd = z["D"], z["qd"], z["kd"]
  return {
    "attn_norm": jnp.ones((n, D), ACT),
    "wq": _stack(next(keys), n, (D, qd), QK_GAIN * D**-0.5), "wk": _stack(next(keys), n, (D, kd), QK_GAIN * D**-0.5), "wv": _stack(next(keys), n, (D, kd), D**-0.5),
    "wo": _stack(next(keys), n, (qd, D), OUT_GAIN["attention"] * qd**-0.5),
  }


def _router(hf: dict, z: dict, key, n: int, topics):
  """([n, D, E] bfloat16, the selection bias [n, E] float32, ``owns`` [n, T, E] or None). The router is an N(0, 1/D)
  part plus, for each of ``router_topics`` topics, ``router_topic_gain`` / D times the topic's direction on the columns
  of the topic's own k experts of that layer — drawn uniformly from the first E - ``router_decoys`` — AND of two of the
  last ``router_decoys`` experts, the decoys: experts whose raw score stands as high as the topic's own for every token
  of the topic, and whose selection bias, DECOY_BIAS, keeps them from ever being chosen (what a correction bias is
  for: an expert the scores would overload). With the bias the choice is the topic's own k, clear of the (k+1)-th by the
  margin the other expert files' routers have; without it (the probe) two decoys stand among eight saturated scores. The
  file's ``assumed.router_topics`` says it at length."""
  D, E, k, decoys = z["D"], z["E"], z["k"], z["decoys"]
  k_w, k_e, k_d = jax.random.split(key, 3)
  w = normal(k_w, (n, D, E), D**-0.5)
  bias = jnp.zeros((n, E), F32).at[:, E - decoys :].set(DECOY_BIAS) if decoys else jnp.zeros((n, E), F32)
  if topics is None:
    return w.astype(ACT), bias, None
  T = topics.shape[0]
  own = jax.lax.top_k(jax.random.uniform(k_e, (n, T, E - decoys)), k)[1]  # [n, T, k] the topic's experts, of the first E - decoys
  owns = jax.nn.one_hot(own, E, dtype=F32).sum(axis=2)  # [n, T, E], k ones a row
  boosted = owns
  if decoys:
    lure = E - decoys + jax.lax.top_k(jax.random.uniform(k_d, (n, T, decoys)), min(2, decoys))[1]
    boosted = owns + jax.nn.one_hot(lure, E, dtype=F32).sum(axis=2)
  return (w + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, boosted)).astype(ACT), bias, owns


def _expert_leaves(hf: dict, z: dict, keys, n: int, topics) -> tuple[dict, object]:
  D, E, Fm, Fs = z["D"], z["E"], z["Fm"], z["Fs"]
  w_router, bias, owns = _router(hf, z, next(keys), n, topics)
  return {
    "mlp_norm": jnp.ones((n, D), ACT), "w_router": w_router, "router_bias": bias,
    "w_experts_up_t": _stack(next(keys), n, (E, Fm, D), D**-0.5),  # [F, D]: out-major, as the program stores an ungated expert's first matrix
    "w_experts_down": _stack(next(keys), n, (E, Fm, D), OUT_GAIN["experts"] * Fm**-0.5),
    "w_shared_up": _stack(next(keys), n, (D, Fs), D**-0.5),
    "w_shared_down": _stack(next(keys), n, (Fs, D), OUT_GAIN["experts"] * Fs**-0.5),
  }, owns


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names: one stack a (mixer, FFN) pairing, each in model order; every expert
  held; the state's A_log / dt_bias / D and the router's selection bias float32."""
  return _make(hf, key)[0]


def router_tables(hf: dict, key) -> dict | None:
  """What the topic router reads a token by, drawn as ``make_params`` draws it from the same key: ``topic_of`` [V], each
  token id's topic, and ``owns`` [expert layers in model order, T, E], 1 where the topic owns the expert. None where the
  file states no topics."""
  return _make(hf, key)[1]


def _make(hf: dict, key) -> tuple[dict, dict | None]:
  z = _sizes(hf)
  keys = iter(jax.random.split(key, 96))
  topics = topic_of = None
  if int(hf.get("router_topics") or 0):
    k_t, k_a = jax.random.split(next(keys))
    topics = jnp.where(jax.random.bernoulli(k_t, 0.5, (int(hf["router_topics"]), z["D"])), 1.0, -1.0).astype(F32)
    topic_of = jax.random.randint(k_a, (z["V"],), 0, topics.shape[0])
  stacks = layer_stacks(hf)
  params, owns = {}, {}
  for name in dict.fromkeys(name for name, _ in stacks):
    n = sum(1 for s, _ in stacks if s == name)
    params[name] = _mamba_leaves(z, keys, n) if name.startswith("ssm_") else _attention_leaves(z, keys, n)
    if name.endswith("moe_layers"):
      ffn, owns[name] = _expert_leaves(hf, z, keys, n, topics)
      params[name] |= ffn
  embed = normal(next(keys), (z["V"], z["D"]), 1.0)
  if topics is not None:
    embed = embed + float(hf["embed_topic_gain"]) * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((z["D"],), ACT)
  params["lm_head"] = normal(next(keys), (z["D"], z["V"]), z["D"] ** -0.5).astype(ACT)
  tables = None if topics is None else {"topic_of": topic_of, "owns": jnp.stack([owns[name][i] for name, i in stacks if name in owns])}
  return params, tables


# -------------------------------------------------------------- reference
# Written from the equations above, float32, one BLOCK a letter and one token at a time: the recurrence is a
# ``lax.scan`` over time, the convolution shifted adds over a zero-padded sequence, the attention a full masked
# softmax, the experts a loop over the experts some token chose. No chunking, no cache, no pairing, nothing of the
# program. One block's weights are float32 at a time, an expert's two matrices as the loop reaches it and the head a
# slab of the vocabulary at a time: the reference runs on the chip beside 12 GB of served weights.


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


@partial(jax.jit, static_argnames=("H", "P", "N", "G", "eps", "read_groups", "norm_groups", "norm_first", "no_conv_bias", "operands", "state_dtype"))
def _mamba(h, norm, w_z, w_xbc, w_dt, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm, w_out, *, H, P, N, G, eps, read_groups=None, norm_groups=None, norm_first=False,
           no_conv_bias=False, operands=None, state_dtype=None):
  """``read_groups``: the B/C groups the heads read (None: all G; 1: every head reads group 0 — the one-group reading);
  ``norm_groups``: the groups of the gated norm (None: G); ``norm_first``: the norm ahead of the gate."""
  S, di = h.shape[0], H * P
  u = rms_norm(h, norm, eps)
  z, xbc, dt = _mm(u, w_z, operands), _mm(u, w_xbc, operands), _mm(u, w_dt, operands)  # [z | xBC | dt] = u W_in
  K = conv_w.shape[0]
  xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])  # zeros before the sequence
  xbc = jax.nn.silu(sum(conv_w[j] * xp[j : j + S] for j in range(K)) + (0.0 if no_conv_bias else conv_b))  # out_t = sum_j w_j x_{t-(K-1)+j}
  x, b, c = xbc[:, :di].reshape(S, H, P), xbc[:, di : di + G * N].reshape(S, G, N), xbc[:, di + G * N :].reshape(S, G, N)
  of_head = jnp.arange(H) // (H // (read_groups or G))  # head h reads group h // (H / G)
  delta = jax.nn.softplus(dt + dt_bias)  # [S, H]: no clamp (the file's ``assumed.time_step``)
  decay = jnp.exp(-delta * jnp.exp(a_log))

  def step(state, t):
    x_t, b_t, c_t, delta_t, decay_t = t
    state = decay_t[:, None, None] * state + (delta_t[:, None] * x_t)[:, :, None] * b_t[of_head][:, None, :]
    state = _round(state, state_dtype)  # a probe: the state a slot keeps between steps, stored in a coarser type than float32
    return state, jnp.einsum("hpn,hn->hp", state, c_t[of_head])

  _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, delta, decay))
  y = (y + d_skip[None, :, None] * x).reshape(S, di)
  groups = norm_groups or G
  grouped_norm = lambda t: rms_norm(t.reshape(S, groups, di // groups), gate_norm.reshape(groups, di // groups), eps).reshape(S, di)  # noqa: E731
  y = grouped_norm(y) * jax.nn.silu(z) if norm_first else grouped_norm(y * jax.nn.silu(z))
  return _mm(y, w_out, operands)


def causal_attention(q, k, v, scale: float):
  """q [S, H, d], k / v [S, Hkv, d] → [S, H, d]: a full [S, S] masked softmax a head, one KV head's group of query heads
  at a time (16 x 1.7 k x 1.7 k scores of the teacher-forced run fit beside the model on the chip)."""
  S, H, Hkv = q.shape[0], q.shape[1], k.shape[1]
  mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

  def one_group(qkv):
    qg, kg, vg = qkv  # [S, H / Hkv, d], [S, d], [S, d]
    probs = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("qhd,kd->hqk", qg, kg) * scale, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,kd->qhd", probs, vg)

  out = jax.lax.map(one_group, (q.reshape(S, Hkv, H // Hkv, -1).transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [Hkv, S, H / Hkv, d]
  return out.transpose(1, 0, 2, 3).reshape(S, H, -1)


@partial(jax.jit, static_argnames=("Hq", "Hkv", "hd", "eps", "theta", "operands"))
def _attention(h, norm, wq, wk, wv, wo, *, Hq, Hkv, hd, eps, theta=None, operands=None):
  """``theta`` None: no position term (as read: the file's ``assumed.position``); a number: plain rope at that base (the probe)."""
  S = h.shape[0]
  x = rms_norm(h, norm, eps)
  q, k, v = _mm(x, wq, operands).reshape(S, Hq, hd), _mm(x, wk, operands).reshape(S, Hkv, hd), _mm(x, wv, operands).reshape(S, Hkv, hd)
  if theta is not None:
    cos, sin = rope_angles(S, hd, theta)
    q, k = rope_half(q, cos, sin), rope_half(k, cos, sin)
  return _mm(causal_attention(q, k, v, hd**-0.5).reshape(S, Hq * hd), wo, operands)


def router_gates(x, w_router, bias, *, top_k: int, scaling: float, no_bias: bool = False, rounded: bool = False):
  """[S, E] gates, 0 where an expert was not chosen: scores sigmoid(x W_r) in float32, the choice the ``top_k`` largest
  of scores + bias, a gate the chosen SCORE (not the biased one) over the chosen scores' sum, times ``scaling``.
  ``rounded``: operands and logits rounded to bfloat16 (the exact probe)."""
  S, E = x.shape[0], w_router.shape[-1]
  w = w_router.astype(F32)
  score = jax.nn.sigmoid(_round(_round(x, "bfloat16") @ _round(w, "bfloat16"), "bfloat16") if rounded else x @ w)
  idx = jax.lax.top_k(score if no_bias else score + bias.astype(F32), top_k)[1]
  gate = jnp.take_along_axis(score, idx, axis=-1)
  gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) * scaling
  return jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], idx].add(gate)


_ACTS = {"relu2": lambda t: jnp.square(jax.nn.relu(t)), "relu": jax.nn.relu, "silu": jax.nn.silu}


@partial(jax.jit, static_argnames=("act", "gated", "operands"))
def _experts(x, gates, up_t, down, at, *, act="relu2", gated=False, operands=None):
  """Σ_e g_e W_down_e act(W_up_e x) over the experts some token chose, one expert at a time; ``up_t`` / ``down`` the
  STACKED leaves [n, E, F, D] as served and ``at`` the layer, so that one expert's two matrices are float32 at a time.
  ``gated`` (the probe): a third matrix W_gate_e ~ N(0, 1/D) drawn here, silu(W_gate x) * (W_up x) between them."""
  E, F, D = up_t.shape[1:]

  def one_expert(acc, e):
    def visit(acc):
      wu = jax.lax.dynamic_slice(up_t, (at, e, 0, 0), (1, 1, F, D))[0, 0].astype(F32)
      wd = jax.lax.dynamic_slice(down, (at, e, 0, 0), (1, 1, F, D))[0, 0].astype(F32)
      if gated:
        hidden = jax.nn.silu(_mm(x, normal(jax.random.fold_in(jax.random.fold_in(jax.random.key(7), at), e), (D, F), D**-0.5), operands)) * _mm(x, wu.T, operands)
      else:
        hidden = _ACTS[act](_mm(x, wu.T, operands))
      return acc + gates[:, e, None] * _mm(hidden, wd, operands)

    return jax.lax.cond(jnp.any(gates[:, e] > 0), visit, lambda acc: acc, acc), None

  return jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(E))[0]


@partial(jax.jit, static_argnames=("act", "operands"))
def _shared(x, w_up, w_down, *, act="relu2", operands=None):
  return _mm(_ACTS[act](_mm(x, w_up, operands)), w_down, operands)


def reference_forward(params: dict, hf: dict, tokens, drop_block: int | None = None, drop_layer: int | None = None, act: str = "relu2", gated: bool = False, read_groups: int | None = None,
                      norm_groups: int | None = None, norm_first: bool = False, no_conv_bias: bool = False, ffn_under_bare_mixer: bool = False, rope: bool = False, no_router_bias: bool = False,
                      scaling: float | None = None, no_shared: bool = False, router_rounded: bool = False, state_dtype: str | None = None, operands: str | None = None,
                      routed: list | None = None, increments: list | None = None):
  """The 52-letter walk (or the file's nine), one block a letter. The keywords are the probes' (``probes``,
  ``exact_probes``): ``drop_block`` leaves block number i out, ``drop_layer`` the blocks of layer STEP number i (the
  served-kind battery's word); ``ffn_under_bare_mixer`` gives the ``M`` that stands
  ahead of a ``*`` an FFN of its own (the next ``E``'s, run a second time: the reading that every mixer is paired).
  No probes: ``routed``, a list that receives, for each ``E`` block in model order, [S, E] True where the router chose
  the expert; ``increments``, a list that receives (letter, rms of the stream, rms of the block's increment)."""
  z = _sizes(hf)
  eps = float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)))
  rms = lambda t: float(jnp.sqrt(jnp.mean(t * t)))  # noqa: E731
  walk = blocks(hf)
  dropped = {drop_block} | ({g for g, (_, name, i) in enumerate(walk) if (name, i) == layer_stacks(hf)[drop_layer]} if drop_layer is not None else set())

  def experts_block(h, st, i, note=True):
    x = rms_norm(h, st["mlp_norm"][i], eps)
    gates = router_gates(x, st["w_router"][i], st["router_bias"][i], top_k=z["k"], scaling=float(hf["routed_scaling_factor"]) if scaling is None else scaling, no_bias=no_router_bias, rounded=router_rounded)
    if routed is not None and note:
      routed.append(gates > 0)
    out = _experts(x, gates, st["w_experts_up_t"], st["w_experts_down"], i, act=act, gated=gated, operands=operands)
    return out if no_shared else out + _shared(x, st["w_shared_up"][i].astype(F32), st["w_shared_down"][i].astype(F32), act=act, operands=operands)

  h = params["embed"][tokens].astype(F32)
  for g, (letter, name, i) in enumerate(walk):
    if g in dropped:
      continue
    st = params[name]
    f32 = lambda *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731, B023
    if letter == "M":
      out = _mamba(
        h, *f32("ssm_norm", "w_z", "w_xbc", "w_dt", "conv_w", "conv_b", "dt_bias", "A_log", "D", "gate_norm", "w_out"), H=z["H"], P=z["P"], N=z["N"], G=z["G"], eps=eps,
        read_groups=read_groups, norm_groups=norm_groups, norm_first=norm_first, no_conv_bias=no_conv_bias, operands=operands, state_dtype=state_dtype,
      )
    elif letter == "*":
      out = _attention(h, *f32("attn_norm", "wq", "wk", "wv", "wo"), Hq=z["Hq"], Hkv=z["Hkv"], hd=z["hd"], eps=eps, theta=float(hf["rope_theta"]) if rope else None, operands=operands)
    else:
      out = experts_block(h, st, i)
    if increments is not None:
      increments.append((letter, rms(h), rms(out)))
    h = h + out
    if ffn_under_bare_mixer and letter == "M" and g + 1 < len(walk) and walk[g + 1][0] != "E":
      nxt = next(((n, j) for l, n, j in walk[g + 1 :] if l == "E"), None)  # the next E block's weights, a second time
      if nxt is not None:
        h = h + experts_block(h, params[nxt[0]], nxt[1], note=False)
  return _head(rms_norm(h, params["final_norm"], eps), params["lm_head"], operands)


def _head(x, w, operands, slabs: int = 8):
  """``x @ w`` [S, V], a slab of the vocabulary at a time: the whole head in float32 is 1.41 GB, which the chip does not
  have beside the served model and its pool."""
  step = -(-w.shape[1] // slabs)
  return jnp.concatenate([_mm(x, w[:, at : at + step].astype(F32), operands) for at in range(0, w.shape[1], step)], axis=1)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and K/V pages in bfloat16 over nine blocks and the recurrent state, its
# decay and the router in float32; the reference is float32 on the same bfloat16 weights. Each limit is 2 x the largest
# sound reading of the chip's seeds — ``correctness.py``'s own check at 168 positions and the teacher-forced run at
# 1.2-1.5 k positions — and under half of the reading of the reference in the nearest precision below the stated one
# (float8 matrix operands), which ``mean_abs`` and ``max_abs`` each refuse; every probe's readings are in PERF.md
# section 6 (PR 53).
LIMITS = {"mean_abs": 0.014, "max_abs": 0.058, "greedy_margin": 0.04}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0048-0.0066 over its seeds' checks at 168 positions and 0.0051 over 4 x 160 teacher-forced steps after prompts of 1176-1469 tokens (my chip runs, PR 53; PERF.md section 6 counts the seeds); float8 matmul operands read 0.056 (0.051 teacher-forced), one B/C group for eight 0.134, the norm over all 4096 channels 0.135, rope in the attention 0.146, every other wrong reading of the row's keys 0.16-0.34: this is the limit that refuses them all",
  "max_abs": "the worst single entry: the chip read 0.013-0.019 at 168 positions and 0.029 over the 40 k entries of the teacher-forced run, which must stay inside; float8 operands read 0.135 (0.25 teacher-forced), every wrong architecture 0.50-1.17",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 on most seeds and 0.0033 at most over their 8 served tokens, 0.0197 at most over 4 x 160 teacher-forced decode steps; the wrong architectures read 0.12-1.47 at 168 positions (the norm over all channels 0.024 and float8 operands 0.036, which mean_abs and max_abs refuse) and 0.51-1.56 teacher-forced (float8 0.28): a decode step that read a wrong group, a wrong expert or a wrong state picks tokens well below the best",
}


def probes(hf: dict) -> dict:
  """Wrong references the limits must refuse (``run.py --probe-sensitivity``): each reads the published keys another
  way, or computes in the precision below the stated one."""
  return {
    "drop_last_block": {"drop_block": int(hf["num_hidden_layers"]) - 1},
    "experts_gated": {"gated": True},  # silu-GLU with a third matrix: the usual expert
    "relu_for_relu2": {"act": "relu"},
    "one_bc_group": {"read_groups": 1},  # every head reads the first of the eight B/C groups: granite's mixer
    "norm_over_all_channels": {"norm_groups": 1},
    "norm_before_gate": {"norm_first": True},
    "ffn_under_the_m_before_attention": {"ffn_under_bare_mixer": True},  # every mixer paired with an FFN: the block of two sublayers
    "rope_in_attention": {"rope": True},
    "router_without_bias": {"no_router_bias": True},
    "router_without_scaling": {"scaling": 1.0},
    "shared_expert_left_out": {"no_shared": True},
    # The precision below the one the configuration states (bfloat16 weights and activations): every matrix product's
    # operands rounded to float8 (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


def exact_probes(hf: dict) -> dict:
  """Wrong references that float32 arithmetic tells (the CPU tests) and bfloat16 serving over 168 positions cannot, so
  that no limit of ``correct`` is asked to refuse them: the router's operands and logits rounded to bfloat16 where the
  configuration states float32 (the chosen stand clear, and a saturated sigmoid hides the rounding); the convolution's
  bias left out (N(0, 0.1^2) under a silu); the recurrent state rounded to bfloat16 after every token (slow heads
  decay by 1 - 1e-5 a step: hundreds of steps show it, 168 do not — granite's finding, PERF.md section 6, PR 34)."""
  return {"router_bfloat16": {"router_rounded": True}, "conv_bias_dropped": {"no_conv_bias": True}, "recurrent_state_bfloat16": {"state_dtype": "bfloat16"}}


def long_prompt_tokens(hf: dict) -> tuple[int, int]:
  """The prompt lengths of the teacher-forced run (``scripts/chip_teacher_forced.py``): eight to twelve chunks of
  ``chunk_size`` positions, so that the chunked scan's carried state crosses many boundaries before the decode steps."""
  chunk = int(hf["chunk_size"])
  return 8 * chunk, 12 * chunk


# The first nine letters at tiny widths: (M, E) (M, E) (M, —) (*, E) (M, E). 8 heads of 16 in 2 B/C groups (4 heads a
# group), a state of 16; 8 query heads over 2 KV heads (4 a group); 16 experts top-4 of which the last 2 are decoys.
REHEARSE_WIDTHS = {
  "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 64, "num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME",
  "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512, "mamba_num_heads": 8, "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2, "chunk_size": 32,
  "n_routed_experts": 16, "num_experts_per_tok": 4, "router_topics": 16, "router_decoys": 2,
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _params(hf: dict) -> dict:
  """Parameters of each kind of block (my count from the file's keys); the float32 ones apart."""
  z = _sizes(hf)
  D, H, di, C = z["D"], z["H"], z["di"], z["C"]
  return {
    "mamba": D + D * (di + C + H) + (z["K"] + 1) * C + di + di * D, "mamba_f32": 3 * H,  # norm, in_proj, conv taps + bias, gate norm, out_proj; A_log, dt_bias, D
    "attention": D + D * (z["qd"] + 2 * z["kd"]) + z["qd"] * D,
    "expert": 2 * D * z["Fm"],  # TWO matrices
    "moe_rest": D + D * z["E"] + 2 * D * z["Fs"], "moe_f32": z["E"],  # norm, router, the shared expert's two; the selection bias
    "top": 2 * z["V"] * D + D,
  }


def param_count(hf: dict) -> int:
  """Every parameter of the model the file describes, block by block."""
  z, p = _sizes(hf), _params(hf)
  return z["n_M"] * (p["mamba"] + p["mamba_f32"]) + z["n_A"] * p["attention"] + z["n_E"] * (p["moe_rest"] + p["moe_f32"] + z["E"] * p["expert"]) + p["top"]


def active_params(hf: dict) -> int:
  """What a token touches: every block's own, ``num_experts_per_tok`` experts an ``E`` block, the head, one embedding row's worth left out."""
  z, p = _sizes(hf), _params(hf)
  return z["n_M"] * (p["mamba"] + p["mamba_f32"]) + z["n_A"] * p["attention"] + z["n_E"] * (p["moe_rest"] + p["moe_f32"] + z["k"] * p["expert"]) + p["top"] - z["V"] * z["D"]


def weight_bytes(hf: dict, rows: float | None = None) -> float:
  """Every weight's bytes (``rows`` None), or those a decode step of ``rows`` rows touches: of the experts only the
  expected distinct ones."""
  z, p = _sizes(hf), _params(hf)
  touched = z["E"] if rows is None else experts_touched(hf, *routed_experts(hf)[1:], rows)
  top = p["top"] if rows is None else p["top"] - z["V"] * z["D"]  # a step reads the head whole and of the embedding its rows' rows (``flops_bytes`` adds those)
  return BF16 * (z["n_M"] * p["mamba"] + z["n_A"] * p["attention"] + z["n_E"] * (p["moe_rest"] + touched * p["expert"]) + top) + 4 * (z["n_M"] * p["mamba_f32"] + z["n_E"] * p["moe_f32"])


def routed_experts(hf: dict) -> tuple[int, int, int, int]:
  """(first, counted, routed, top_k): a step's bytes count the experts a topic can own — all but the last
  ``router_decoys``, which the selection bias keeps from ever being chosen —, of which a token chooses ``top_k``."""
  z = _sizes(hf)
  return 0, z["E"] - z["decoys"], z["E"] - z["decoys"], z["k"]


def moe_expert_bytes(hf: dict, rows: float) -> float:
  """What the ``E`` blocks of one decode step of ``rows`` rows must read of the routed experts' weights: two matrices an
  expert, the distinct experts the stated router touches."""
  z = _sizes(hf)
  return z["n_E"] * experts_touched(hf, *routed_experts(hf)[1:], rows) * _params(hf)["expert"] * BF16


def ssm_state_bytes(hf: dict, rows: float) -> float:
  """What the ``M`` blocks of one decode step must move for ``rows`` rows: each reads and writes every row's state
  [H, P, N] in float32 and its ``K - 1`` convolution rows in bfloat16."""
  z = _sizes(hf)
  return z["n_M"] * rows * 2 * (z["H"] * z["P"] * z["N"] * 4 + (z["K"] - 1) * z["C"] * BF16)


def step_weight_bytes(hf: dict, rows: float) -> float:
  return weight_bytes(hf, rows)


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer STEP, in model order: a Mamba step moves its rows' state (read and written) whatever the
  context; the attention step reads the K/V of every resident token (bfloat16, or int8 codes + a scale a head)."""
  z = _sizes(hf)
  per_head_side = z["hd"] + 4 if kv_quant == "int8" else BF16 * z["hd"]
  state = ssm_state_bytes(hf, rows) / max(z["n_M"], 1)
  return [state if t == "mamba" else resident_tokens * z["Hkv"] * 2 * per_head_side for t in hf_layer_types(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  """What a token touches (``active_params``), 2 operations a parameter a row."""
  return 2.0 * rows * active_params(hf)


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"  # absent from the file: bfloat16 pages
