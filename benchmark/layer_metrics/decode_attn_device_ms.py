"""Device self time of the attention core (scope ``xot.attn``: the Pallas paged kernel, the gather-path GQA and MLA
attention, flash prefill in a mixed tick) per decode step of the decode families, from the traced interval (.open, .closed)."""
import span_lib


def read(ctx):
  return span_lib.decode_scope_ms(ctx, ("attn",))
