"""Operations and bytes a step must move, computed from shapes. Kept with the
benchmark so that no later change to the program can move the yardstick.

"Least bytes" means what the algorithm cannot avoid reading once per decode
step at the served types: every weight that takes part (int8 codes + f32
scales per output channel, bf16 norms and router), the embedding rows of the
batch, and the resident cache of the rows that decode. For expert layers only
the experts the batch routes to count: the expected number of distinct experts
that the rows choose under the router the configuration file states. Where the
file states ``router_topics`` (a token's topic fixes its experts, so two rows
of one topic choose the same ones) that is ``topic_router_distinct_experts``;
where it states none, uniform and independent routing, E * (1 - (1 - k/E)^B)
(``expected_distinct_experts``). A kind with routed experts asks
``experts_touched`` and so takes whichever its file states.

Which weights take part, what a layer reads of the cache and the matrix
operations of a step are the kind's own (``benchmark/arch_<kind>.py``:
``step_weight_bytes``, ``cache_read_bytes``, ``step_matmul_flops``); what is
summed from them, and the roofline, is here.
"""

from __future__ import annotations

import math

import arch


def q_bytes(d_in: int, d_out: int) -> int:
  """An int8 [in, out] leaf with its f32 scale per output channel."""
  return d_in * d_out + 4 * d_out


def expected_distinct_experts(n_experts: int, top_k: int, tokens: float) -> float:
  return n_experts * (1.0 - (1.0 - top_k / n_experts) ** max(tokens, 0.0))


def topic_router_distinct_experts(n_experts: float, owned_p: float, topics: int, tokens: float) -> float:
  """Of ``n_experts`` experts, the expected number that ``tokens`` tokens choose when each token draws one of
  ``topics`` topics uniformly and chooses exactly its topic's experts, and each topic owns an expert with probability
  ``owned_p`` independently of the other topics (how the kinds' ``make_params`` draw a layer's ownership table). The
  expectation is over the table and over the tokens' topics: an expert that s topics own is passed over by every token
  with probability (1 - s/T)^tokens, and s is Binomial(T, p). As tokens grow it tends to n (1 - (1 - p)^T), the
  experts some topic owns, not to n: no token ever chooses the others."""
  tokens, T = max(tokens, 0.0), int(topics)
  if tokens == 0.0 or owned_p <= 0.0:
    return 0.0
  if owned_p >= 1.0:  # top_k = routed: every topic owns every expert
    return float(n_experts)
  log_binom = lambda s: math.lgamma(T + 1) - math.lgamma(s + 1) - math.lgamma(T - s + 1) + s * math.log(owned_p) + (T - s) * math.log1p(-owned_p)  # noqa: E731
  passed_over = sum(math.exp(log_binom(s)) * (1.0 - s / T) ** tokens for s in range(T + 1))
  return n_experts * max(1.0 - passed_over, 0.0)


def experts_touched(hf: dict, counted: float, routed: int, top_k: int, rows: float) -> float:
  """Of ``counted`` experts of a layer that routes ``top_k`` of ``routed`` a token (every one of them as likely to be
  chosen as any other; a kind's ``routed_experts(hf)`` names the three), the expected distinct ones that ``rows``
  tokens choose under the router that the configuration file ``hf`` states: its topic router where ``router_topics``
  is set, else uniform independent routing."""
  topics = int(hf.get("router_topics") or 0)
  if topics:
    return topic_router_distinct_experts(counted, top_k / routed, topics, rows)
  return expected_distinct_experts(routed, top_k, rows) * counted / routed


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
