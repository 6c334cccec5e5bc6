"""Operations and bytes a step must move, computed from shapes. Kept with the
benchmark so that no later change to the program can move the yardstick.

"Least bytes" means what the algorithm cannot avoid reading once per decode
step at the served types: every weight that takes part (int8 codes + f32
scales per output channel, bf16 norms and router), the embedding rows of the
batch, and the resident cache of the rows that decode. For expert layers only
the experts the batch routes to count, as the expected number of distinct
experts under uniform routing, E * (1 - (1 - k/E)^B).

Which weights take part, what a layer reads of the cache and the matrix
operations of a step are the kind's own (``benchmark/arch_<kind>.py``:
``step_weight_bytes``, ``cache_read_bytes``, ``step_matmul_flops``); what is
summed from them, and the roofline, is here.
"""

from __future__ import annotations

import arch


def q_bytes(d_in: int, d_out: int) -> int:
  """An int8 [in, out] leaf with its f32 scale per output channel."""
  return d_in * d_out + 4 * d_out


def expected_distinct_experts(n_experts: int, top_k: int, tokens: float) -> float:
  return n_experts * (1.0 - (1.0 - top_k / n_experts) ** max(tokens, 0.0))


def decode_step_min_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> float:
  """One decode step of ``rows`` rows over ``resident_tokens`` cached tokens in all."""
  kind = arch.load(hf["arch_kind"])
  embed_rows = rows * hf["hidden_size"] * 2
  return kind.step_weight_bytes(hf, rows) + embed_rows + sum(kind.cache_read_bytes(hf, rows, resident_tokens, kv_quant))


def paged_attention_min_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> float:
  """One call of the attention kernel over the cache (one layer of one step):
  the mean over the layers, because a trace's calls are averaged over them."""
  per_layer = arch.load(hf["arch_kind"]).cache_read_bytes(hf, rows, resident_tokens, kv_quant)
  return sum(per_layer) / len(per_layer)


def decode_step_flops(hf: dict, rows: float) -> float:
  """Matrix-multiply operations of one decode step (2 per multiply-add), attention over the cache left out."""
  return arch.load(hf["arch_kind"]).step_matmul_flops(hf, rows)


def roofline_seconds(flops: float, bytes_: float, peaks: dict, int8: bool = False) -> tuple[float, str]:
  """The least time the chip could take and which bound sets it."""
  t_c = flops / (peaks["int8_ops_per_s"] if int8 else peaks["bf16_flops_per_s"])
  t_m = bytes_ / peaks["hbm_bytes_per_s"]
  return (t_c, "compute") if t_c > t_m else (t_m, "memory")
