"""Process start to window open: weights, the correctness check, warm-up, the ramp; in a first run, compilation."""


def read(ctx):
  return ctx["t_open"] - ctx["t_start"]
