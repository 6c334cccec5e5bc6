"""Kind ``dense_gqa``: a Llama/Mistral-shaped decoder. Pre-norm layers,
grouped-query attention with rotary embeddings in the half-rotation pairing of
the published checkpoints (channel i pairs with i + d/2), a SwiGLU
feed-forward, an untied int8 head. What ``arch.py`` asks of a kind, in its order."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import q_bytes
from reference import F32, causal_attention, deq, head, rms_norm, rope_angles, rope_half, swiglu
from weights import ACT, head_and_embed, put_q

# ---------------------------------------------------------------- weights


def make_params(hf: dict, key) -> dict:
  L, D, F, V = hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
  hd = hf.get("head_dim") or D // hf["num_attention_heads"]
  qd, kd = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
  keys = iter(jax.random.split(key, 16))
  stack = {"attn_norm": jnp.ones((L, D), ACT), "mlp_norm": jnp.ones((L, D), ACT)}
  for name, shape in (("wq", (D, qd)), ("wk", (D, kd)), ("wv", (D, kd)), ("wo", (qd, D)), ("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))):
    put_q(stack, name, next(keys), L, shape)
  params = {"layers": stack}
  head_and_embed(params, keys, V, D)
  return params


# -------------------------------------------------------------- reference


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


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, theta_scale: float = 1.0):
  st = params["layers"]
  h = params["embed"][tokens].astype(F32)
  for i in range(hf["num_hidden_layers"]):
    if i == drop_layer:
      continue
    h = _dense_layer(
      h, st["attn_norm"][i], st["mlp_norm"][i], *(deq(st, n, i) for n in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")),
      n_heads=hf["num_attention_heads"], n_kv=hf["num_key_value_heads"], eps=float(hf["rms_norm_eps"]), theta=float(hf["rope_theta"]) * theta_scale,
    )
  return head(params, hf, h)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations in bfloat16 (8 bits of mantissa: ~0.4 % per
# operation, accumulating over 32 layers) and keys and values as int8 codes with
# one scale per token and head (~0.4 % of the largest entry); the reference is
# float32 on the same dequantised weights. Each limit is about three times what
# the chip read and under half the weakest probe.
LIMITS = {"mean_abs": 0.05, "max_abs": 0.20, "greedy_margin": 0.10}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.011-0.018 over 77 runs (PR 23-26); the weakest probe (last layer dropped) reads 0.19",
  "max_abs": "the worst single entry: the chip read 0.034-0.064; the weakest probe reads 0.57",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: the chip read <= 0.007 in 48 runs (PR 23-25) and <= 0.013 in PR 26's 29 but for two seeds at 0.034 and 0.038; a decode step that read a wrong page picks a token nats below the best (probe readings: PERF.md section 6)",
}


def probes(hf: dict) -> dict:
  return {"drop_last_layer": {"drop_layer": hf["num_hidden_layers"] - 1}, "drop_layer_1": {"drop_layer": 1}, "rope_base_100x_too_small": {"theta_scale": 0.01}}


REHEARSE_WIDTHS = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512}

# ------------------------------------------------- bytes and operations


def weight_bytes(hf: dict) -> int:
  D, F, L, V = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"]
  hd = hf.get("head_dim") or D // hf["num_attention_heads"]
  qd, kd = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
  layer = q_bytes(D, qd) + 2 * q_bytes(D, kd) + q_bytes(qd, D) + 2 * q_bytes(D, F) + q_bytes(F, D) + 2 * 2 * D
  return L * layer + q_bytes(D, V) + 2 * D


def kv_bytes_per_token_layer(hf: dict, kv_quant: str) -> int:
  """Keys and values of one cached token in one layer: int8 codes + one f32 scale per head and side, or bf16."""
  hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
  per_head_side = hd + 4 if kv_quant == "int8" else 2 * hd
  return hf["num_key_value_heads"] * 2 * per_head_side


def step_weight_bytes(hf: dict, rows: float) -> int:
  return weight_bytes(hf)  # every weight, whatever the batch


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  return [resident_tokens * kv_bytes_per_token_layer(hf, kv_quant)] * hf["num_hidden_layers"]  # full attention in every layer


def step_matmul_flops(hf: dict, rows: float) -> float:
  return 2.0 * rows * weight_bytes(hf)  # ~1 byte a parameter


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"
