"""Of the decode families' device time in the traced interval (their ``XLA Modules`` events: ``decode.paged_batch`` and
``decode.mixed_paged_batch``), the share that is a mixed tick's PREFILL HALF (the self time of the ops under the path component
``mixed.prefill``, ``half_lib``) - how much of what every decode-side reader divides by is a prompt (.closed). None for a
program without the mark or a capture without a mixed tick."""
import half_lib


def read(ctx):
  red = half_lib.capture(ctx)
  return half_lib.half_seconds(red, "prefill") / red["device_s"] if red and red["device_s"] else None
