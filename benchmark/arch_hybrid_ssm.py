"""Kind ``hybrid_ssm``: a Granite-4.0-H-shaped decoder (HF ``GraniteMoeHybridForCausalLM``
with no routed experts). ``layer_types`` names each layer's mixer: "mamba" is a
Mamba-2 mixer with one group (Dao & Gu 2024, section 7; HF ``GraniteMoeHybridMambaLayer``),
"attention" is grouped-query attention with NO position term at all (``position_embedding_type``
"nope") and a softmax scale of ``attention_multiplier``. Every layer is followed by a SwiGLU
``shared_mlp``; every block's output is scaled by ``residual_multiplier`` before it joins the
residual; embeddings are multiplied by ``embedding_multiplier``, the head is the embedding
table (tied) and its logits are divided by ``logits_scaling``. Weights and activations are
bfloat16 as published; the recurrent state is float32. What ``arch.py`` asks of a kind, in its
order, plus ``ssm_state_bytes`` for the state update's roofline."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from reference import F32, causal_attention, deq, rms_norm, rope_angles, rope_half
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` has no
  ``layer_types`` (every tree before PR 34) reads this configuration as a llama, makes a pool for 40 attention
  layers and serves four of them. It must end here, at once and non-zero, and not with a line that says
  ``correct`` false after minutes of compiling."""
  from xotorch_support_jetson_tpu.models.config import ModelConfig

  if "layer_types" not in getattr(ModelConfig, "__dataclass_fields__", {}):
    raise SystemExit("arch_kind hybrid_ssm: this program's ModelConfig has no layer_types (no state-space layers): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

# ---------------------------------------------------------------- weights

EMBED_STD = 1.0 / 192
FINAL_NORM_GAIN = 32.0
QK_STD_GAIN = 32.0**0.5
# Why these two are not N(0, 1) and 1 (the configuration file's ``assumed`` says it at length): the head is the
# embedding table, the residual starts as 12 x the token's own row, and a block adds 0.22 x a unit-variance output.
# With an N(0, 1) table the token's own row dominates the final hidden state and its own logit stands ~45 standard
# deviations above every other: the model repeats its last token, and no comparison reads the decode path. A row of
# standard deviation 1/192 leaves the token's own logit ~2 standard deviations up (a mild bias, as tied models have),
# and a final norm gain of 32 makes the logits' spread ~1, as the other configurations' N(0, 1/in) heads give.
# QK_STD_GAIN: the published softmax scale, attention_multiplier 1/64, is an eighth of 1/sqrt(head_dim); trained q/k
# projections make up for it. With N(0, 1/in) projections every softmax logit lies within ~0.125 of the others, the
# four attention layers return the running mean of their values, and no probe of them can be told from rounding
# (rope on: 0.041 / 0.145 / 0 against the sound 0.036 / 0.146 / 0; my chip run, PR 34). N(0, 32/in) for wq and wk
# gives the logits a spread of 4: attention that attends.


def _sizes(hf: dict) -> dict:
  D, H, P, N = hf["hidden_size"], hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
  hd = hf.get("head_dim") or D // hf["num_attention_heads"]
  di = H * P
  return dict(
    D=D, H=H, P=P, N=N, K=hf["mamba_d_conv"], di=di, C=di + 2 * N, F=hf["shared_intermediate_size"], V=hf["vocab_size"], hd=hd,
    qd=hf["num_attention_heads"] * hd, kd=hf["num_key_value_heads"] * hd,
    Ls=sum(t == "mamba" for t in hf_layer_types(hf)), La=sum(t == "attention" for t in hf_layer_types(hf)),
  )


def hf_layer_types(hf: dict) -> tuple:
  """``layer_types`` as a tuple. ``weights.shape_hf`` keeps scalars only, so inside a maker the list is gone:
  the file then names the pattern by ``layer_pattern`` (state-space runs between attention layers)."""
  out = []
  for i, run in enumerate(int(r) for r in str(hf["layer_pattern"]).split(",")):
    out += (["attention"] if i else []) + ["mamba"] * run
  if "layer_types" in hf and tuple(hf["layer_types"]) != tuple(out):
    raise ValueError(f"layer_pattern {hf['layer_pattern']!r} does not spell layer_types {hf['layer_types']}")
  return tuple(out)


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _mlp_leaves(stack: dict, keys, n: int, D: int, F: int) -> None:
  stack["mlp_norm"] = jnp.ones((n, D), ACT)
  for name, shape in (("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))):
    stack[name] = _stack(next(keys), n, shape, shape[0] ** -0.5)


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names (``models/decoder.py init_shard_params``): ``layers`` [La] the
  attention layers, ``ssm_layers`` [Ls] the state-space layers, each in model order; the tied table."""
  z = _sizes(hf)
  D, H, di, C, Ls, La = z["D"], z["H"], z["di"], z["C"], z["Ls"], z["La"]
  keys = iter(jax.random.split(key, 24))
  attn = {"attn_norm": jnp.ones((La, D), ACT)}
  for name, shape in (("wq", (D, z["qd"])), ("wk", (D, z["kd"])), ("wv", (D, z["kd"])), ("wo", (z["qd"], D))):
    attn[name] = _stack(next(keys), La, shape, shape[0] ** -0.5 * (QK_STD_GAIN if name in ("wq", "wk") else 1.0))
  _mlp_leaves(attn, keys, La, D, z["F"])
  # The Mamba-2 initialisation (mamba_ssm Mamba2.__init__): A = -U(1, 16), the step softplus(dt_bias) log-uniform in
  # [1e-3, 1e-1], D = 1: states neither die nor blow up. Conv taps N(0, 1/K), no trained bias to assume but zero.
  dt = jnp.exp(jax.random.uniform(next(keys), (Ls, H), F32, jnp.log(1e-3), jnp.log(1e-1)))
  ssm = {
    "ssm_norm": jnp.ones((Ls, D), ACT),
    "w_z": _stack(next(keys), Ls, (D, di), D**-0.5),  # HF's in_proj, as the program keeps it: cut at its three outputs
    "w_xbc": _stack(next(keys), Ls, (D, C), D**-0.5),
    "w_dt": _stack(next(keys), Ls, (D, H), D**-0.5),
    "conv_w": normal(next(keys), (Ls, z["K"], C), z["K"] ** -0.5).astype(ACT),
    "conv_b": jnp.zeros((Ls, C), ACT),
    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # the inverse of softplus
    "A_log": jnp.log(jax.random.uniform(next(keys), (Ls, H), F32, 1.0, 16.0)),
    "D": jnp.ones((Ls, H), F32),
    "gate_norm": jnp.ones((Ls, di), ACT),
    "w_out": _stack(next(keys), Ls, (di, D), di**-0.5),
  }
  _mlp_leaves(ssm, keys, Ls, D, z["F"])
  return {
    "layers": attn, "ssm_layers": ssm,
    "embed": normal(next(keys), (z["V"], D), EMBED_STD).astype(ACT),
    "final_norm": jnp.full((D,), FINAL_NORM_GAIN, ACT),
  }


# -------------------------------------------------------------- reference
# Written from the published equations, float32, one token at a time: the recurrence is a ``lax.scan`` over time,
# the convolution four shifted adds over a zero-padded sequence. No chunking, no cache, nothing of the program.


def _mm(a, b, operands: str | None):
  """``a @ b``; under the precision probe both operands are rounded to ``operands`` (a float8 type) first."""
  if operands:
    a, b = (t.astype(jnp.dtype(operands)).astype(F32) for t in (a, b))
  return a @ b


@partial(jax.jit, static_argnames=("eps", "r", "operands"))
def _mlp(h, mlp_norm, w_gate, w_up, w_down, *, eps, r, operands=None):
  x = rms_norm(h, mlp_norm, eps)
  return h + r * _mm(jax.nn.silu(_mm(x, w_gate, operands)) * _mm(x, w_up, operands), w_down, operands)


@partial(jax.jit, static_argnames=("H", "P", "N", "eps", "r", "no_skip", "no_dt_bias", "reverse_conv", "operands", "state_dtype"))
def _mamba(h, norm, w_z, w_xbc, w_dt, conv_w, conv_b, dt_bias, a_log, d_skip, gate_norm, w_out, *, H, P, N, eps, r, no_skip=False, no_dt_bias=False, reverse_conv=False, operands=None, state_dtype=None):
  S, di = h.shape[0], H * P
  u = rms_norm(h, norm, eps)
  z, xbc, dt = _mm(u, w_z, operands), _mm(u, w_xbc, operands), _mm(u, w_dt, operands)  # [z | xBC | dt] = u W_in
  K = conv_w.shape[0]
  taps = conv_w[::-1] if reverse_conv else conv_w
  xp = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])  # zeros before the sequence
  xbc = jax.nn.silu(sum(taps[j] * xp[j : j + S] for j in range(K)) + conv_b)  # out_t = sum_j w_j x_{t-(K-1)+j}
  x, b, c = xbc[:, :di].reshape(S, H, P), xbc[:, di : di + N], xbc[:, di + N :]
  delta = jax.nn.softplus(dt if no_dt_bias else dt + dt_bias)  # [S, H]
  decay = jnp.exp(-delta * jnp.exp(a_log))

  def step(state, t):
    x_t, b_t, c_t, delta_t, decay_t = t
    state = decay_t[:, None, None] * state + (delta_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
    if state_dtype:  # a probe: the state a slot keeps between steps, stored in a coarser type than float32
      state = state.astype(jnp.dtype(state_dtype)).astype(F32)
    return state, state @ c_t  # [H, P]

  _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, b, c, delta, decay))
  if not no_skip:
    y = y + d_skip[None, :, None] * x
  return h + r * _mm(rms_norm(y.reshape(S, di) * jax.nn.silu(z), gate_norm, eps), w_out, operands)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "r", "scale", "theta", "operands"))
def _attention(h, norm, wq, wk, wv, wo, *, n_heads, n_kv, eps, r, scale, theta=0.0, operands=None):
  S = h.shape[0]
  hd = wq.shape[-1] // n_heads
  x = rms_norm(h, norm, eps)
  q, k, v = _mm(x, wq, operands).reshape(S, n_heads, hd), _mm(x, wk, operands).reshape(S, n_kv, hd), _mm(x, wv, operands).reshape(S, n_kv, hd)
  if theta:  # a probe: the published model has no position term
    cos, sin = rope_angles(S, hd, theta)
    q, k = rope_half(q, cos, sin), rope_half(k, cos, sin)
  rep = n_heads // n_kv
  out = causal_attention(q, jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1), scale)
  return h + r * _mm(out.reshape(S, n_heads * hd), wo, operands)


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, no_skip: bool = False, no_dt_bias: bool = False,
                      reverse_conv: bool = False, rope: bool = False, attn_scale: float | None = None, residual: float | None = None, operands: str | None = None,
                      state_dtype: str | None = None):
  z = _sizes(hf)
  eps, r = float(hf["rms_norm_eps"]), float(hf["residual_multiplier"] if residual is None else residual)
  scale = float(hf["attention_multiplier"] if attn_scale is None else attn_scale)
  h = params["embed"][tokens].astype(F32) * float(hf["embedding_multiplier"])
  seen = {"mamba": 0, "attention": 0}
  for i, kind in enumerate(hf_layer_types(hf)):
    j = seen[kind]
    seen[kind] += 1
    if i == drop_layer:
      continue
    if kind == "mamba":
      st = params["ssm_layers"]
      h = _mamba(
        h, st["ssm_norm"][j], deq(st, "w_z", j), deq(st, "w_xbc", j), deq(st, "w_dt", j), deq(st, "conv_w", j), st["conv_b"][j].astype(F32), st["dt_bias"][j], st["A_log"][j], st["D"][j],
        st["gate_norm"][j], deq(st, "w_out", j), H=z["H"], P=z["P"], N=z["N"], eps=eps, r=r, no_skip=no_skip, no_dt_bias=no_dt_bias, reverse_conv=reverse_conv, operands=operands,
        state_dtype=state_dtype,
      )
    else:
      st = params["layers"]
      h = _attention(
        h, st["attn_norm"][j], *(deq(st, n, j) for n in ("wq", "wk", "wv", "wo")), n_heads=hf["num_attention_heads"], n_kv=hf["num_key_value_heads"],
        eps=eps, r=r, scale=scale, theta=float(hf.get("rope_theta", 10000.0)) if rope else 0.0, operands=operands,
      )
    h = _mlp(h, st["mlp_norm"][j], *(deq(st, n, j) for n in ("w_gate", "w_up", "w_down")), eps=eps, r=r, operands=operands)
  return _mm(rms_norm(h, params["final_norm"], eps), params["embed"].astype(F32).T, operands) / float(hf["logits_scaling"])  # tied head


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and K/V pages in bfloat16 over 40 layers and the recurrent state in
# float32; the reference is float32 on the same bfloat16 weights. It sits two to three times further from its
# reference than the other two kinds (0.011-0.018 mean): every block's output joins a residual of magnitude ~1 scaled
# by 0.22, so the bfloat16 grid of the residual (2^-8 at 1) rounds away ~1.5 % of each of 80 increments, where a
# unit-scale increment loses under 1 %; no single stage dominates (a CPU ablation at hidden 512: any one of the
# mixer, the MLP, the head or the residual add kept in float32 takes a fifth off; PERF.md section 6, PR 34).
# Each limit lies between the largest sound reading and the smallest reading of a wrong or coarser reference.
LIMITS = {"mean_abs": 0.08, "max_abs": 0.40, "greedy_margin": 0.40}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.036-0.062 over the 34 seeds of PR 34 (PERF.md section 6 lists them; mean 0.047); the weakest probes read 0.094-0.133 (last attention layer dropped) and 0.112-0.147 (last layer dropped) over four seeds, float8 matmul operands 0.48-0.53: this is the limit that refuses a dropped layer",
  "max_abs": "the worst single entry: the chip read 0.11-0.24 (three seeds in thirty-four above 0.19); float8 operands read 1.2-1.6 and every probe of the recurrence above 3; a dropped layer reads 0.29-0.46, which this limit does not always refuse and mean_abs does",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 in half the runs, 0.026-0.154 where the reference's top two lie closer than the served path's error (twice the worst entry bounds it); float8 operands read 0.94-1.11 and every probe of the recurrence 1.2-6.8: a decode step that read a wrong state picks tokens nats below the best",
}


def probes(hf: dict) -> dict:
  types = hf_layer_types(hf)
  last_attention = max(i for i, t in enumerate(types) if t == "attention")
  return {
    "drop_last_layer": {"drop_layer": len(types) - 1},
    "drop_last_attention_layer": {"drop_layer": last_attention},
    "skip_D_dropped": {"no_skip": True},
    "dt_bias_dropped": {"no_dt_bias": True},
    "conv_taps_reversed": {"reverse_conv": True},
    "rope_on_attention_layers": {"rope": True},
    "attention_scale_inv_sqrt_head_dim": {"attn_scale": float((hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]) ** -0.5)},
    "residual_multiplier_1": {"residual": 1.0},
    # The precision below the one the configuration states: every matrix product's operands rounded to float8
    # (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
    # The precision below the one the configuration states for the recurrent state (float32): the state rounded to
    # bfloat16 after every token. KNOWN NOT TO BE REFUSED: the comparison reads 8 tokens after a 160-token prompt,
    # and rounding a state whose slow heads decay by 1 - 1.6e-5 a step shows over hundreds of steps, not 168 (a
    # served path with a bfloat16 state read 0.054 / 0.125 / 0, `correct` true, and +25 % tokens a second; my chip
    # run, PR 34). It stands here so that every reading of the probes shows how far under the limits it lies, until
    # `correctness.py` judges a long teacher-forced decode (PERF.md section 7: a `benchmark` issue that has to come
    # before any `perf_opt` that touches the state).
    "recurrent_state_bfloat16": {"state_dtype": "bfloat16"},
  }


# Two periods of a shortened pattern: state-space runs of 2 and 1 with an attention layer after each, then a run of 1.
REHEARSE_WIDTHS = {
  "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128, "num_hidden_layers": 6, "num_attention_heads": 4, "num_key_value_heads": 2,
  "vocab_size": 512, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 32,
  "layer_types": ["mamba", "mamba", "attention", "mamba", "attention", "mamba"], "layer_pattern": "2,1,1",
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _layer_params(hf: dict) -> tuple[int, int]:
  """(parameters of a state-space layer, of an attention layer), each with its MLP and norms."""
  z = _sizes(hf)
  mlp = 3 * z["D"] * z["F"] + z["D"]
  ssm = z["D"] + z["D"] * (2 * z["di"] + 2 * z["N"] + z["H"]) + (z["K"] + 1) * z["C"] + z["di"] + z["di"] * z["D"]
  attn = z["D"] + z["D"] * (z["qd"] + 2 * z["kd"]) + z["qd"] * z["D"]
  return ssm + mlp, attn + mlp


def weight_bytes(hf: dict) -> int:
  z = _sizes(hf)
  ssm, attn = _layer_params(hf)
  return BF16 * (z["Ls"] * ssm + z["La"] * attn + z["V"] * z["D"] + z["D"]) + z["Ls"] * 3 * z["H"] * 4  # A_log, dt_bias, D in float32


def ssm_state_bytes(hf: dict, rows: float) -> float:
  """What the state-space layers of one decode step must move for ``rows`` rows: each layer reads and writes
  every row's state [H, P, N] in float32 and its ``K - 1`` convolution rows in bfloat16."""
  z = _sizes(hf)
  return z["Ls"] * rows * 2 * (z["H"] * z["P"] * z["N"] * 4 + (z["K"] - 1) * z["C"] * BF16)


def step_weight_bytes(hf: dict, rows: float) -> int:
  return weight_bytes(hf)  # every weight, whatever the batch; the tied table is read once, as the head


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a state-space layer moves its rows' state (read and written) whatever the
  context; an attention layer reads the K/V of every resident token (bfloat16, or int8 codes + a scale a head)."""
  z = _sizes(hf)
  per_head_side = z["hd"] + 4 if kv_quant == "int8" else BF16 * z["hd"]
  kv = resident_tokens * hf["num_key_value_heads"] * 2 * per_head_side
  state = ssm_state_bytes(hf, rows) / max(z["Ls"], 1)
  return [state if t == "mamba" else kv for t in hf_layer_types(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  return 2.0 * rows * weight_bytes(hf) / BF16  # 2 operations a parameter a row


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"
