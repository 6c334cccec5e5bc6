"""Rows advanced per decode step over the window: the growth of
decode_tokens_total over the growth of decode_chunks_total times the chunk."""
import layer_lib as lib


def read(ctx):
  chunks = lib.counter_delta(ctx, "decode_chunks_total")
  return lib.counter_delta(ctx, "decode_tokens_total") / (chunks * ctx["chunk"]) if chunks else None
