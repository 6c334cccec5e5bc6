"""The distinct held experts one expert layer's decode step visited, mean over the window up to the capture (the clock's counts
``experts_visited`` / ``expert_layer_steps``, read back with every settled chunk beside ``moe_experts_visited_total``:
``half_lib.count_ratio``) - the measured count of what ``moe_experts_roofline`` takes from ``flops_bytes.experts_touched``'s
expectation (.closed). None for a program whose snapshots carry no counts, or a model without routed experts."""
import half_lib


def read(ctx):
  return half_lib.count_ratio(ctx, ("experts_visited",), ("expert_layer_steps",))
