"""Device self time per decode step of KV quantisation and of the cache and page writes (``xot.kv_write``; in a mixed
tick also the prefill slice's page gather and scatter) (.open, .closed)."""
import span_lib


def read(ctx):
  return span_lib.decode_scope_ms(ctx, ("kv_write",))
