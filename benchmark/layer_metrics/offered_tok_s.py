"""Output tokens received inside the window over its length: below the knee
this is the offered load, not a capacity."""
import client


def read(ctx):
  return client.tokens_between(ctx["recs"], ctx["t_open"], ctx["t_close"]) / (ctx["t_close"] - ctx["t_open"])
