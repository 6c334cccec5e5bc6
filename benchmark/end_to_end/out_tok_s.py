"""Output tokens the clients received inside the window over its length (closed loop: every slot kept
full). Every token that arrived in the window counts, also those of requests sent during the ramp."""
import client


def read(ctx):
  return client.tokens_between(ctx.get("all_recs") or ctx["recs"], ctx["t_open"], ctx["t_close"]) / (ctx["t_close"] - ctx["t_open"])
