"""The state update against its memory roofline: the bytes the state-space layers of one decode step must move for
the rows resident in the traced interval (``ssm_state_bytes`` of the kind's file: every layer reads and writes each
row's float32 state and its convolution rows) over the peak HBM rate, over the device self time of scope ``xot.ssm``
per step. The scope also holds the convolution, the skip and the gated norm, which move kilobytes a row: the share
is that of the whole scope. The program steps every slot row, resident or not; the bytes counted are the resident
rows', so idle slots lower the share. None where the programs have no such scope or the kind's file no such bytes."""
import arch
import layer_lib as lib
import span_lib

SCOPE = "ssm"


def read(ctx):
  red = span_lib.capture(ctx)
  state_bytes = getattr(arch.load(ctx["hf"]["arch_kind"]), "ssm_state_bytes", None)
  if red is None or SCOPE not in red["scope_s"] or state_bytes is None or not ctx.get("peaks"):
    return None
  step_ms = span_lib.decode_scope_ms(ctx, (SCOPE,))
  if not step_ms:
    return None
  rows, _tokens = lib.resident(ctx)
  return 100.0 * (state_bytes(ctx["hf"], rows) / ctx["peaks"]["hbm_bytes_per_s"]) / (step_ms / 1e3)
