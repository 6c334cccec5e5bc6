"""Device self time of the state-space layers' recurrence (scope ``xot.ssm``: the convolution, the state update - each
row's state read and written -, the skip and the gated norm) per decode step of the decode families, from the traced
interval (.closed). None where no op of the decode programs carries the scope: a program without state-space layers."""
import span_lib

SCOPE = "ssm"


def read(ctx):
  red = span_lib.capture(ctx)
  if red is None or SCOPE not in red["scope_s"]:
    return None
  return span_lib.decode_scope_ms(ctx, (SCOPE,))
