"""Device self time of the experts' ROUTING per decode step (scope ``xot.moe_router``: the router's product, the top-k
choice and its weights, the auxiliary loss) from the traced interval (.closed) - the part of ``decode_ffn_device_ms``
that a model whose router reads its attention's input draws AHEAD of the attention. None where no op of the decode
programs carries the scope: a program without routed experts, or one whose tree names no such scope."""
import span_lib

SCOPE = "moe_router"


def read(ctx):
  red = span_lib.capture(ctx)
  if red is None or SCOPE not in red["scope_s"]:
    return None
  return span_lib.decode_scope_ms(ctx, (SCOPE,))
