"""Device self time of a mixed tick's PREFILL HALF per thousand slice tokens, in the traced interval (.closed): the ops of
``decode.mixed_paged_batch`` whose ``op_name`` has the path component ``mixed.prefill`` (the program marks the half since
PR 55), over the ``pf_tokens`` the capture's ``xot.sched.stage`` spans say those dispatches carried (``half_lib``). What
``prefill_device_ms_per_ktok.closed`` estimates from outside by subtracting plain chunks, and cannot where a capture holds
none (a long-document cell since PR 51: every chunk there carries a slice). Padding a slice to a power of two is inside it: the time is the padded program's, the
tokens the real ones. None for a program without the mark or a capture without a mixed tick."""
import half_lib


def read(ctx):
  red = half_lib.capture(ctx)
  ktok = half_lib.slice_ktok(red) if red else None
  return half_lib.half_seconds(red, "prefill") * 1e3 / ktok if ktok else None
