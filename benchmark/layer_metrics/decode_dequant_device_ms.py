"""Device self time per decode step of every weight dequantisation that runs as an op of its own (``xot.dequant``,
whatever component owns it; a part of that component's time, not beside it) (.open, .closed)."""
import span_lib


def read(ctx):
  return span_lib.decode_scope_ms(ctx, ("dequant",))
