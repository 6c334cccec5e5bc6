"""The routed experts against their memory roofline: the bytes of held expert weights that the expert layers of one
decode step must read for the rows resident in the traced interval (``moe_expert_bytes`` of the kind's file: the expected
distinct held experts that the rows choose under the router the configuration file states - with ``router_topics`` a
token's topic fixes its experts, so rows of one topic share them: ``flops_bytes.experts_touched``, 60.5 of Ling's 128 at 64
rows where independent rows would touch 81.3 (PR 39) - each expert's three matrices, in every expert layer) over the peak HBM rate,
over the device self time of scope ``xot.moe_experts`` per step. The scope also holds the dispatch and the combine,
which move activations; the share is that of the whole scope. A program that streams every held expert whatever the
rows chose reads more than is counted, so the share says how far the expert layer is from touching only what it must.
None where the programs have no such scope or the kind's file no such bytes."""
import arch
import layer_lib as lib
import span_lib

SCOPE = "moe_experts"


def read(ctx):
  red = span_lib.capture(ctx)
  expert_bytes = getattr(arch.load(ctx["hf"]["arch_kind"]), "moe_expert_bytes", None)
  if red is None or SCOPE not in red["scope_s"] or expert_bytes is None or not ctx.get("peaks"):
    return None
  step_ms = span_lib.decode_scope_ms(ctx, (SCOPE,))
  if not step_ms:
    return None
  rows, _tokens = lib.resident(ctx)
  return 100.0 * (expert_bytes(ctx["hf"], rows) / ctx["peaks"]["hbm_bytes_per_s"]) / (step_ms / 1e3)
