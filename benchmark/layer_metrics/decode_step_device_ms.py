"""Device time of the decode families (plain and mixed ticks) per decode step: what a resident row waits for each token (.open, .closed)."""
import layer_lib


def read(ctx):
  return layer_lib.decode_step_device_ms(ctx)
