"""The routed experts of the DECODE HALF against their memory roofline: ``moe_experts_roofline``'s bytes (the kind's
``moe_expert_bytes`` at the rows resident in the traced interval, ``layer_lib.resident``) over the peak HBM rate, over the
self time a step of scope ``xot.moe_experts`` OUTSIDE the path component ``mixed.prefill`` (``half_lib``) - where
``moe_experts_roofline`` divides by that scope's time in both halves of a mixed tick, a slice's expert products among them.
None for a program without the mark, a capture without a mixed tick, or a kind without such bytes."""
import arch
import half_lib
import layer_lib as lib

SCOPE = "moe_experts"


def read(ctx):
  red = half_lib.capture(ctx)
  expert_bytes = getattr(arch.load(ctx["hf"]["arch_kind"]), "moe_expert_bytes", None)
  if red is None or expert_bytes is None or not ctx.get("peaks"):
    return None
  seconds, steps = half_lib.half_seconds(red, "decode", (SCOPE,)), half_lib.decode_steps(red, ctx["chunk"])
  if not seconds or not steps:
    return None
  rows, _tokens = lib.resident(ctx)
  return 100.0 * (expert_bytes(ctx["hf"], rows) / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / steps)
