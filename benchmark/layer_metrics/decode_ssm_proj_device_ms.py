"""Device self time of the state-space layers' projections (scope ``xot.ssm_proj``: the norm, ``W_in`` and ``W_out``)
per decode step of the decode families, from the traced interval (.closed). None where the programs have no such scope."""
import span_lib

SCOPE = "ssm_proj"


def read(ctx):
  red = span_lib.capture(ctx)
  if red is None or SCOPE not in red["scope_s"]:
    return None
  return span_lib.decode_scope_ms(ctx, (SCOPE,))
