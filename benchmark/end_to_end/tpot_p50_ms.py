"""Time per output token, per finished request (last event - first event) / (tokens - 1), median over the window's requests."""
import layer_lib


def read(ctx):
  return layer_lib.pct([t * 1e3 for r in ctx["recs"] if (t := r.tpot()) is not None], 50)
