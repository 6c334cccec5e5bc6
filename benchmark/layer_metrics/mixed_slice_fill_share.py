"""Of the padded width the mixed ticks' prefill halves ran over the window up to the capture, the share that was a real token
(the clock's counts ``slice_tokens`` / ``slice_pad_tokens``, counted at the mixed settle beside
``sched_tick_prefill_tokens_total``: ``half_lib.count_ratio``): a slice pads to a power of two, and the rest of
``mixed_prefill_device_ms_per_ktok`` is padding (.closed). None for a program whose snapshots carry no counts, or a window without a mixed tick."""
import half_lib


def read(ctx):
  return half_lib.count_ratio(ctx, ("slice_tokens",), ("slice_pad_tokens",))
