"""Median time from due (open loop) or send (closed loop) to the first content event, on the client's clock (.open, .closed)."""
import layer_lib


def read(ctx):
  return layer_lib.pct(layer_lib.ttft_ms(ctx), 50)
