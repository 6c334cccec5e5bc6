"""Kind ``hybrid_gdn``: an Olmo-Hybrid-shaped decoder (``olmo_hybrid``). ``layer_types`` names each layer's mixer:
"linear_attention" is a Gated-DeltaNet layer (Gated Delta Networks, arXiv:2412.06464, with the negative-eigenvalue
beta of arXiv:2411.12537): q, k, v through one causal depthwise convolution and silu; q and k unit-norm a head; ONE log
decay a head, ``-exp(A_log) x softplus(W_a x + dt_bias)`` — Mamba-2's gate, with no lower bound; beta =
2 x sigmoid(W_b x) where ``linear_allow_neg_eigval``; a rank-one delta rule on a rectangular matrix state a head
(``linear_value_head_dim`` values x ``linear_key_head_dim`` key channels); a per-head norm, THEN the gate silu(W_z x),
before the output projection. "full_attention" is multi-head attention with an RMSNorm over the whole q and k
projections (OLMo 2, arXiv:2501.00656) and no position term. Both kinds of layer are OLMo 2's reordered block: no norm
ahead of a sublayer, one on its output, ``h += rms(f(h))``. Every layer has a SwiGLU MLP; the head is untied. Weights
and activations are bfloat16; the recurrent state and every gate of it are float32. What ``arch.py`` asks of a kind, in
its order, plus ``ssm_state_bytes`` for the state update's roofline and ``hf_layer_types`` for the paged kernel's. Each
reading of a key the catalog row does not explain is in the configuration file's ``assumed``."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import F32, causal_attention, rms_norm, rope_angles, rope_half
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` knows no
  ``olmo_hybrid`` (every tree before PR 44) refuses the model_type by name, but only after 6.5 GB of weights are on the
  device. It must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models import config

  if "olmo_hybrid" not in getattr(config, "MODEL_FAMILIES", {}):
    raise SystemExit("arch_kind hybrid_gdn: this program's config_from_hf knows no model_type 'olmo_hybrid' (no Gated-DeltaNet layers): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

L2_EPS = 1e-6  # of q's and k's unit norm (fla's l2norm; the configuration file's ``assumed``)
ROPE_PROBE_THETA = 10000.0  # the rotary base the ``rope_on`` probe turns on: the published model has none
# The seeded weights' three departures from N(0, 1/in) and unit gains; the file's ``assumed`` says each at length.
POST_NORM_GAIN = 0.25  # every block norm's gain: a sublayer adds a quarter of the embedding's rms to the stream, not all of it
QK_NORM_GAIN = 2.0  # the gains of the whole-projection q and k norms: softmax logits of spread 4, attention that attends
HEAD_SCALE_SIGMA = 0.5  # W_q's and W_k's columns a head scaled by exp(0.5 N(0, 1)): heads of unequal norm, as trained ones are


def _sizes(hf: dict) -> dict:
  D, H = hf["hidden_size"], hf["linear_num_value_heads"]
  N, P, Ha = hf["linear_key_head_dim"], hf["linear_value_head_dim"], hf["num_attention_heads"]
  types = hf_layer_types(hf)
  return dict(
    D=D, H=H, N=N, P=P, K=hf["linear_conv_kernel_dim"], C=H * (2 * N + P), F=hf["intermediate_size"], V=hf["vocab_size"], Ha=Ha, Hkv=hf["num_key_value_heads"], hd=D // Ha,
    Ls=types.count("gdn"), La=types.count("attention"), L=len(types),
  )


def hf_layer_types(hf: dict) -> tuple:
  """"gdn" | "attention" a layer. ``weights.shape_hf`` keeps scalars only, so inside a maker ``layer_types`` is gone:
  the file then names the pattern by ``full_attention_interval`` (every so-manieth layer is a full-attention layer)."""
  every = int(hf["full_attention_interval"])
  out = tuple("attention" if (i + 1) % every == 0 else "gdn" for i in range(int(hf["num_hidden_layers"])))
  if "layer_types" in hf and tuple({"linear_attention": "gdn", "full_attention": "attention"}[t] for t in hf["layer_types"]) != out:
    raise ValueError(f"full_attention_interval {every} does not spell layer_types {hf['layer_types']}")
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float, cols=None):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time; ``cols`` [n, shape[-1]] scales each
  layer's columns."""
  cols = jnp.ones((n, shape[-1]), F32) if cols is None else cols
  return jax.lax.map(lambda kc: (normal(kc[0], shape, std) * kc[1]).astype(ACT), (jax.random.split(key, n), cols))


def _head_scales(key, n: int, heads: int, hd: int):
  """[n, heads * hd]: exp(HEAD_SCALE_SIGMA x N(0, 1)) a head, the mean square over the heads held at 1."""
  s = jnp.exp(HEAD_SCALE_SIGMA * normal(key, (n, heads), 1.0))
  return jnp.repeat(s * jax.lax.rsqrt(jnp.mean(s * s, axis=-1, keepdims=True)), hd, axis=-1)


def _mlp_leaves(stack: dict, keys, n: int, D: int, F: int) -> None:
  stack["post_mlp_norm"] = jnp.full((n, D), POST_NORM_GAIN, ACT)
  for name, shape in (("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))):
    stack[name] = _stack(next(keys), n, shape, shape[0] ** -0.5)


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names (``models/decoder.py init_shard_params``): ``layers`` [La] the
  full-attention layers, ``ssm_layers`` [Ls] the Gated-DeltaNet layers, each in model order; ``A_log`` and ``dt_bias``
  float32."""
  z = _sizes(hf)
  D, H, N, P, C, Ls, La, qd, kd = z["D"], z["H"], z["N"], z["P"], z["C"], z["Ls"], z["La"], z["Ha"] * z["hd"], z["Hkv"] * z["hd"]
  keys = iter(jax.random.split(key, 32))
  attn = {
    "q_norm": jnp.full((La, qd), QK_NORM_GAIN, ACT), "k_norm": jnp.full((La, kd), QK_NORM_GAIN, ACT), "post_attn_norm": jnp.full((La, D), POST_NORM_GAIN, ACT),
    "wq": _stack(next(keys), La, (D, qd), D**-0.5, _head_scales(next(keys), La, z["Ha"], z["hd"])),
    "wk": _stack(next(keys), La, (D, kd), D**-0.5, _head_scales(next(keys), La, z["Hkv"], z["hd"])),
    "wv": _stack(next(keys), La, (D, kd), D**-0.5),
    "wo": _stack(next(keys), La, (qd, D), qd**-0.5),
  }
  _mlp_leaves(attn, keys, La, D, z["F"])
  # The Mamba-2 initialisation of the gate (mamba_ssm Mamba2.__init__, as granite's file): A = U(1, 16), the step
  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]. Conv taps N(0, 1/K), no bias.
  dt = jnp.exp(jax.random.uniform(next(keys), (Ls, H), F32, jnp.log(1e-3), jnp.log(1e-1)))
  ssm = {
    "w_qkv": _stack(next(keys), Ls, (D, C), D**-0.5),
    "conv_w": normal(next(keys), (Ls, z["K"], C), z["K"] ** -0.5).astype(ACT),
    "w_ab": _stack(next(keys), Ls, (D, 2 * H), D**-0.5),
    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # the inverse of softplus
    "A_log": jnp.log(jax.random.uniform(next(keys), (Ls, H), F32, 1.0, 16.0)),
    "w_z": _stack(next(keys), Ls, (D, H * P), D**-0.5),
    "o_norm": jnp.ones((Ls, P), ACT),
    "w_out": _stack(next(keys), Ls, (H * P, D), (H * P) ** -0.5),
    "post_ssm_norm": jnp.full((Ls, D), POST_NORM_GAIN, ACT),
  }
  _mlp_leaves(ssm, keys, Ls, D, z["F"])
  return {
    "layers": attn, "ssm_layers": ssm,
    "embed": normal(next(keys), (z["V"], D), 1.0).astype(ACT),
    "final_norm": jnp.ones((D,), ACT),
    "lm_head": normal(next(keys), (D, z["V"]), D**-0.5).astype(ACT),
  }


# -------------------------------------------------------------- reference
# Written from the equations in ISSUE 44, float32, one token at a time: the delta rule is a ``lax.scan`` over time with
# the state as mathematics has it, S [N key channels, P values] a head; the convolution four shifted adds over a
# zero-padded sequence. No chunking, no cache, nothing of the program.


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


def _join(h, out, norm, eps, pre_norm: bool):
  """The block's residual: ``h + rms(out)``. (Under the ``pre_norm`` probe the caller normed the sublayer's input.)"""
  return h + (out if pre_norm else rms_norm(out, norm, eps))


@partial(jax.jit, static_argnames=("eps", "pre_norm", "operands"))
def _mlp(h, norm, w_gate, w_up, w_down, *, eps, pre_norm=False, operands=None):
  x = rms_norm(h, norm, eps) if pre_norm else h
  return _join(h, _mm(jax.nn.silu(_mm(x, w_gate, operands)) * _mm(x, w_up, operands), w_down, operands), norm, eps, pre_norm)


@partial(jax.jit, static_argnames=("H", "N", "P", "eps", "beta_scale", "no_decay", "no_delta", "gate_before_norm", "pre_norm", "state_dtype", "decay_dtype", "operands"))
def _gdn(h, w_qkv, conv_w, w_ab, dt_bias, a_log, w_z, o_norm, w_out, post_norm, *, H, N, P, eps, beta_scale, no_decay=False, no_delta=False, gate_before_norm=False, pre_norm=False,
         state_dtype=None, decay_dtype=None, operands=None):
  S = h.shape[0]
  x = rms_norm(h, post_norm, eps) if pre_norm else h
  K = conv_w.shape[0]
  pre = jnp.concatenate([jnp.zeros((K - 1, w_qkv.shape[1]), F32), _mm(x, w_qkv, operands)])  # zeros before the sequence
  qkv = jax.nn.silu(sum(conv_w[j] * pre[j : j + S] for j in range(K)))  # out_t = sum_j w_j x_{t-(K-1)+j}
  q, k, v = qkv[:, : H * N].reshape(S, H, N), qkv[:, H * N : 2 * H * N].reshape(S, H, N), qkv[:, 2 * H * N :].reshape(S, H, P)
  unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)  # noqa: E731
  q, k = unit(q) / N**0.5, unit(k)
  ab = _mm(x, w_ab, operands)
  # The precision probe of the decay: the gate's pre-activation, the log decay and the decay itself in ``decay_dtype``
  # where float32 is stated.
  g = -jnp.exp(a_log) * jax.nn.softplus(_round(ab[:, :H] + dt_bias, decay_dtype))  # [S, H]: one a head
  alpha = jnp.ones_like(g) if no_decay else _round(jnp.exp(_round(g, decay_dtype)), decay_dtype)
  beta = beta_scale * jax.nn.sigmoid(ab[:, H:])

  def step(state, t):  # state [H, N, P]
    q_t, k_t, v_t, a_t, b_t = t
    state = a_t[:, None, None] * state
    seen = jnp.zeros_like(v_t) if no_delta else jnp.einsum("hnp,hn->hp", state, k_t)  # S k
    state = state + b_t[:, None, None] * k_t[:, :, None] * (v_t - seen)[:, None, :]  # + beta (v - S k) (x) k
    state = _round(state, state_dtype)  # a probe: the state a slot keeps between steps, stored in a coarser type than float32
    return state, jnp.einsum("hnp,hn->hp", state, q_t)  # S q

  _, o = jax.lax.scan(step, jnp.zeros((H, N, P), F32), (q, k, v, alpha, beta))
  gate = jax.nn.silu(_mm(x, w_z, operands)).reshape(S, H, P)
  o = rms_norm(o * gate, o_norm, eps) if gate_before_norm else rms_norm(o, o_norm, eps) * gate  # the norm first, the gate after
  return _join(h, _mm(o.reshape(S, H * P), w_out, operands), post_norm, eps, pre_norm)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "theta", "per_head_qk_norm", "pre_norm", "operands"))
def _attention(h, wq, wk, wv, wo, q_norm, k_norm, post_norm, *, n_heads, n_kv, eps, theta=0.0, per_head_qk_norm=False, pre_norm=False, operands=None):
  S = h.shape[0]
  hd = wq.shape[-1] // n_heads
  x = rms_norm(h, post_norm, eps) if pre_norm else h
  q, k, v = _mm(x, wq, operands), _mm(x, wk, operands), _mm(x, wv, operands).reshape(S, n_kv, hd)
  if per_head_qk_norm:  # a probe: qwen3's norm, each head over its own channels
    q, k = rms_norm(q.reshape(S, n_heads, hd), q_norm.reshape(n_heads, hd), eps), rms_norm(k.reshape(S, n_kv, hd), k_norm.reshape(n_kv, hd), eps)
  else:  # over the whole projection, before the split into heads
    q, k = rms_norm(q, q_norm, eps).reshape(S, n_heads, hd), rms_norm(k, k_norm, eps).reshape(S, n_kv, hd)
  if theta:  # a probe: the published model has no position term
    cos, sin = rope_angles(S, hd, theta)
    q, k = rope_half(q, cos, sin), rope_half(k, cos, sin)
  rep = n_heads // n_kv
  out = causal_attention(q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), hd**-0.5)
  return _join(h, _mm(out.reshape(S, n_heads * hd), wo, operands), post_norm, eps, pre_norm)


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, no_decay: bool = False, no_delta: bool = False, beta_unscaled: bool = False,
                      gate_before_norm: bool = False, per_head_qk_norm: bool = False, pre_norm: bool = False, rope: bool = False, state_dtype: str | None = None,
                      decay_dtype: str | None = None, operands: str | None = None):
  z = _sizes(hf)
  eps = float(hf["rms_norm_eps"])
  f32 = lambda st, i, *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731
  h = params["embed"][tokens].astype(F32)
  seen = {"gdn": 0, "attention": 0}
  for g, kind in enumerate(hf_layer_types(hf)):
    i = seen[kind]
    seen[kind] += 1
    if g == drop_layer:
      continue
    if kind == "gdn":
      st = params["ssm_layers"]
      h = _gdn(
        h, *f32(st, i, "w_qkv", "conv_w", "w_ab", "dt_bias", "A_log", "w_z", "o_norm", "w_out", "post_ssm_norm"), H=z["H"], N=z["N"], P=z["P"], eps=eps,
        beta_scale=2.0 if hf.get("linear_allow_neg_eigval") and not beta_unscaled else 1.0, no_decay=no_decay, no_delta=no_delta, gate_before_norm=gate_before_norm, pre_norm=pre_norm,
        state_dtype=state_dtype, decay_dtype=decay_dtype, operands=operands,
      )
    else:
      st = params["layers"]
      h = _attention(
        h, *f32(st, i, "wq", "wk", "wv", "wo", "q_norm", "k_norm", "post_attn_norm"), n_heads=z["Ha"], n_kv=z["Hkv"], eps=eps, theta=ROPE_PROBE_THETA if rope else 0.0,
        per_head_qk_norm=per_head_qk_norm, pre_norm=pre_norm, operands=operands,
      )
    h = _mlp(h, *f32(st, i, "post_mlp_norm", "w_gate", "w_up", "w_down"), eps=eps, pre_norm=pre_norm, operands=operands)
  return _mm(rms_norm(h, params["final_norm"], eps), params["lm_head"].astype(F32), operands)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and K/V pages in bfloat16 over 12 layers and the recurrent state and its
# gates in float32; the reference is float32 on the same bfloat16 weights. Each limit lies between the largest sound
# reading of eighteen seeds on the chip and the smallest reading of the reference in the nearest precision below the
# stated one (float8 matrix operands, three seeds) — but the greedy margin's, which two tokens in a near-tie decide
# and no precision bounds; the readings of every probe are in PERF.md section 6 (PR 44).
LIMITS = {"mean_abs": 0.04, "max_abs": 0.12, "greedy_margin": 0.2}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0114-0.0167 over eighteen seeds (my chip runs, PR 44, calls A-B), 0.0133 over 4 x 160 teacher-forced decode steps; float8 matmul operands read 0.084-0.097 over three seeds, the weakest wrong architectures 0.177-0.242 (last layer dropped), 0.189-0.240 (the gate ahead of the head norm), 0.223-0.246 (beta without its 2), 0.225-0.240 (a q/k norm a head): this is the limit that refuses them all, 2.4 x the largest sound reading and under half of float8's smallest. The recurrent state or the decay in bfloat16 read 0.012-0.015, the sound path's own reading, and are NOT refused: over 168 tokens - and over 160 teacher-forced steps, 0.0139 and 0.0136 against 0.0133 - rounding a state whose heads remember tens of tokens moves no compared log-prob",
  "max_abs": "the worst single entry: the chip read 0.029-0.060 (two seeds of eighteen above 0.05); float8 operands read 0.238-0.312, every wrong architecture above 0.51: twice the largest sound reading, half of float8's smallest",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 on thirteen seeds of eighteen, 0.0024-0.0334 on four and 0.0798 on one (0.0436 at most over 4 x 160 teacher-forced steps): a near-tie of the reference's top two, which the worst compared entry does not bound (the 48 entries are not the whole vocabulary). 2.5 x the largest sound reading. float8 operands read 0.109-0.125 and are NOT refused by this limit (they are by the other two); the wrong architectures read 0.225-3.1",
}


def probes(hf: dict) -> dict:
  return {
    "drop_last_layer": {"drop_layer": int(hf["num_hidden_layers"]) - 1},
    "no_decay": {"no_decay": True},
    "no_delta": {"no_delta": True},
    "beta_unscaled": {"beta_unscaled": True},  # beta without the 2 of linear_allow_neg_eigval
    "gate_before_norm": {"gate_before_norm": True},  # Mamba-2's order, rms(o * silu(z)), where this mixer norms first
    "per_head_qk_norm": {"per_head_qk_norm": True},
    "pre_norm": {"pre_norm": True},  # h + f(rms(h)) with the same gains: the block every other family has
    "rope_on": {"rope": True},
    # The precision below the one the configuration states, where it states float32: the recurrent state rounded to
    # bfloat16 after every token, and the decay (its gate, its logarithm, itself) computed in bfloat16.
    "recurrent_state_bfloat16": {"state_dtype": "bfloat16"},
    "decay_bfloat16": {"decay_dtype": "bfloat16"},
    # ... and where it states bfloat16 (weights, activations): every matrix product's operands rounded to float8
    # (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


# Two shortened periods and one layer more: Gated-DeltaNet runs of 2 with a full-attention layer after each, then one.
# 6 heads (no power of two) of an 8 x 16 state (N != P), 6 attention heads of 16.
REHEARSE_WIDTHS = {
  "hidden_size": 96, "intermediate_size": 192, "num_hidden_layers": 7, "num_attention_heads": 6, "num_key_value_heads": 6, "vocab_size": 512,
  "linear_num_key_heads": 6, "linear_num_value_heads": 6, "linear_key_head_dim": 8, "linear_value_head_dim": 16, "full_attention_interval": 3,
  "layer_types": ["linear_attention", "linear_attention", "full_attention", "linear_attention", "linear_attention", "full_attention", "linear_attention"],
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _layer_params(hf: dict) -> tuple[int, int]:
  """(parameters of a Gated-DeltaNet layer, of a full-attention layer), each with its MLP and norms (my count from the
  file's keys); a linear layer's 2 H float32 gate parameters (A_log, dt_bias) are counted apart."""
  z = _sizes(hf)
  D, H, P, C, qd, kd = z["D"], z["H"], z["P"], z["C"], z["Ha"] * z["hd"], z["Hkv"] * z["hd"]
  mlp = 3 * D * z["F"] + D
  gdn = D * C + z["K"] * C + D * 2 * H + D * H * P + P + H * P * D + D
  attn = D * (qd + 2 * kd) + qd * D + qd + kd + D
  return gdn + mlp, attn + mlp


def weight_bytes(hf: dict, embedding: bool = True) -> int:
  """Every weight's bytes, or (``embedding`` False) those a decode step reads whole: all but the embedding table."""
  z = _sizes(hf)
  gdn, attn = _layer_params(hf)
  top = (2 if embedding else 1) * z["V"] * z["D"] + z["D"]  # the untied head (and the table); the final norm
  return BF16 * (z["Ls"] * gdn + z["La"] * attn + top) + z["Ls"] * 2 * z["H"] * 4


def ssm_state_bytes(hf: dict, rows: float) -> float:
  """What the Gated-DeltaNet layers of one decode step must move for ``rows`` rows: each layer reads and writes every
  row's state [H, P, N] in float32 — the state's own bytes, whatever face the device stores it in — and its ``K - 1``
  convolution rows in bfloat16."""
  z = _sizes(hf)
  return z["Ls"] * rows * 2 * (z["H"] * z["P"] * z["N"] * 4 + (z["K"] - 1) * z["C"] * BF16)


def step_weight_bytes(hf: dict, rows: float) -> int:
  return weight_bytes(hf, embedding=False)  # every weight but the table, whatever the batch (``flops_bytes`` adds the rows' rows of it)


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a Gated-DeltaNet layer moves its rows' state and convolution rows (read and
  written) whatever the context; a full-attention layer reads the K/V of every resident token (bfloat16, or int8 codes +
  a scale a head)."""
  z = _sizes(hf)
  per_head_side = z["hd"] + 4 if kv_quant == "int8" else BF16 * z["hd"]
  kv = resident_tokens * z["Hkv"] * 2 * per_head_side
  state = ssm_state_bytes(hf, rows) / max(z["Ls"], 1)
  return [state if t == "gdn" else kv for t in hf_layer_types(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  z = _sizes(hf)
  gdn, attn = _layer_params(hf)
  return 2.0 * rows * (z["Ls"] * gdn + z["La"] * attn + z["V"] * z["D"])  # 2 operations a parameter a row; the embedding is a gather


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"
