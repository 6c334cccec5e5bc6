"""Operations and bytes a step must move, computed from shapes. Kept with the
benchmark so that no later change to the program can move the yardstick.

"Least bytes" means what the algorithm cannot avoid reading once per decode
step at the served types: every weight that takes part (int8 codes + f32
scales per output channel, bf16 norms and router), the embedding rows of the
batch, and the resident cache of the rows that decode. For expert layers only
the experts the batch routes to count, as the expected number of distinct
experts under uniform routing, E * (1 - (1 - k/E)^B).
"""

from __future__ import annotations


def _q(d_in: int, d_out: int) -> int:
  """An int8 [in, out] leaf with its f32 scale per output channel."""
  return d_in * d_out + 4 * d_out


def dense_gqa_weight_bytes(hf: dict) -> int:
  D, F, L, V = hf["hidden_size"], hf["intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"]
  hd = hf.get("head_dim") or D // hf["num_attention_heads"]
  qd, kd = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
  layer = _q(D, qd) + 2 * _q(D, kd) + _q(qd, D) + 2 * _q(D, F) + _q(F, D) + 2 * 2 * D
  return L * layer + _q(D, V) + 2 * D


def dense_gqa_kv_bytes_per_token(hf: dict, kv_quant: str) -> int:
  hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
  per_head_side = hd + 4 if kv_quant == "int8" else 2 * hd
  return hf["num_hidden_layers"] * hf["num_key_value_heads"] * 2 * per_head_side


def expected_distinct_experts(n_experts: int, top_k: int, tokens: float) -> float:
  return n_experts * (1.0 - (1.0 - top_k / n_experts) ** max(tokens, 0.0))


def mla_attn_weight_bytes(hf: dict) -> int:
  D, H = hf["hidden_size"], hf["num_attention_heads"]
  rank, nope, rope, vh = hf["kv_lora_rank"], hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
  return _q(D, H * (nope + rope)) + _q(D, rank + rope) + _q(rank, H * (nope + vh)) + _q(H * vh, D) + 2 * (2 * D + rank)


def mla_moe_weight_bytes(hf: dict, tokens: float, all_experts: bool = False) -> float:
  D, F, Fm, L, V, E = hf["hidden_size"], hf["intermediate_size"], hf["moe_intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"], hf["n_routed_experts"]
  n_dense = min(int(hf.get("first_k_dense_replace", 0)), L)
  Fs = int(hf.get("n_shared_experts") or 0) * Fm
  attn = mla_attn_weight_bytes(hf)
  dense_ffn = 2 * _q(D, F) + _q(F, D)
  expert = 2 * _q(D, Fm) + _q(Fm, D)
  touched = E if all_experts else expected_distinct_experts(E, hf["num_experts_per_tok"], tokens)
  moe_ffn = touched * expert + 2 * D * E + 4 * E + (2 * _q(D, Fs) + _q(Fs, D) if Fs else 0)
  return n_dense * (attn + dense_ffn) + (L - n_dense) * (attn + moe_ffn) + _q(D, V) + 2 * D


def mla_kv_bytes_per_token(hf: dict) -> int:
  return hf["num_hidden_layers"] * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * 2


def decode_step_min_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> float:
  """One decode step of ``rows`` rows over ``resident_tokens`` cached tokens in all."""
  embed_rows = rows * hf["hidden_size"] * 2
  if hf["arch_kind"] == "dense_gqa":
    return dense_gqa_weight_bytes(hf) + embed_rows + resident_tokens * dense_gqa_kv_bytes_per_token(hf, kv_quant)
  if hf["arch_kind"] == "mla_moe":
    return mla_moe_weight_bytes(hf, rows) + embed_rows + resident_tokens * mla_kv_bytes_per_token(hf)
  raise ValueError(f"no byte model for arch_kind {hf['arch_kind']!r}")


def paged_attention_min_bytes(hf: dict, resident_tokens: float, kv_quant: str) -> float:
  """One call of the paged decode kernel (one layer): codes and scales of the tokens read."""
  return resident_tokens * dense_gqa_kv_bytes_per_token(hf, kv_quant) / hf["num_hidden_layers"]


def decode_step_flops(hf: dict, rows: float) -> float:
  """Matrix-multiply operations of one decode step (2 per multiply-add), attention over the cache left out."""
  if hf["arch_kind"] == "dense_gqa":
    params = dense_gqa_weight_bytes(hf)  # ~1 byte a parameter
  else:
    D, Fm = hf["hidden_size"], hf["moe_intermediate_size"]
    params = mla_moe_weight_bytes(hf, 0) + (hf["num_hidden_layers"] - 1) * hf["num_experts_per_tok"] * 3 * D * Fm
  return 2.0 * rows * params


def roofline_seconds(flops: float, bytes_: float, peaks: dict, int8: bool = False) -> tuple[float, str]:
  """The least time the chip could take and which bound sets it."""
  t_c = flops / (peaks["int8_ops_per_s"] if int8 else peaks["bf16_flops_per_s"])
  t_m = bytes_ / peaks["hbm_bytes_per_s"]
  return (t_c, "compute") if t_c > t_m else (t_m, "memory")
