"""Device self time per decode step of the decode families' ops under no ``xot.`` scope: copies and loop plumbing the
compiler added. The check that the scopes cover the step (.open, .closed)."""
import span_lib


def read(ctx):
  return span_lib.decode_scope_ms(ctx, ("unscoped",))
