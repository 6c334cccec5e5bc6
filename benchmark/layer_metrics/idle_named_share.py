"""Of the device's idle seconds between programs in the traced interval (the chip waiting for the host's next
dispatch), the share overlapped by one of the program's own host spans (``xot.sched.*``, ``xot.program:*``,
``xot.trace:*``): how much of the waiting the trace can name (.open, .closed)."""
import span_lib


def read(ctx):
  red = span_lib.capture(ctx)
  return span_lib.idle_named_share(red) if red else None
