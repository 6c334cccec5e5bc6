"""Device self time of the feed-forward block per decode step: the dense MLP (``xot.ffn``) and the routed one
(``xot.moe_router`` + ``xot.moe_experts`` + ``xot.moe_shared``), their dequantisations included (.open, .closed)."""
import span_lib


def read(ctx):
  return span_lib.decode_scope_ms(ctx, span_lib.FFN)
