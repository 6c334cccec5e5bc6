"""Device milliseconds of prompt processing per thousand prompt tokens (.open, .closed)."""
import layer_lib


def read(ctx):
  return layer_lib.prefill_device_ms_per_ktok(ctx)
