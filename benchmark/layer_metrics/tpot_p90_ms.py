"""90th percentile over the window's requests of the time per output token: the tail of the end-to-end
tpot_p50_ms, unbounded while a window holds some 30 requests (3 lie beyond it)."""
import layer_lib


def read(ctx):
  return layer_lib.pct([t * 1e3 for r in ctx["recs"] if (t := r.tpot()) is not None], 90)
