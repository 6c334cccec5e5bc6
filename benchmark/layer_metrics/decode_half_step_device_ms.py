"""Device self time of one decode step WITHOUT the prompt (.closed): the ops of the decode families outside the path component
``mixed.prefill`` (``half_lib``), over executions x chunk - ``decode_step_device_ms`` less the slices that ride its mixed ticks.
None for a program without the mark or a capture without a mixed tick."""
import half_lib


def read(ctx):
  red = half_lib.capture(ctx)
  steps = half_lib.decode_steps(red, ctx["chunk"]) if red else 0
  return half_lib.half_seconds(red, "decode") * 1e3 / steps if steps else None
