"""The Pallas paged-decode kernel against its memory roofline in a model whose layers do not all read pages (a hybrid
of state-space and attention layers): the K/V of the cached tokens resident in the traced interval that ONE attention
layer reads, over the peak HBM rate, over the kernel's device time per call. ``paged_attn_roofline`` takes the mean of
``cache_read_bytes`` over all layers (``flops_bytes.paged_attention_min_bytes``), which for a hybrid would count the
state-space layers' state bytes as the kernel's; this reader takes the mean over the layers the kind's file calls
"attention" (``hf_layer_types``). None where the kind names no per-layer types, or the trace holds no call of the kernel
(a program that serves the model without it, or not at all)."""
import arch
import layer_lib as lib

KERNEL = "paged_decode"
KERNELS = (KERNEL,)  # op-name substrings the trace reduction should total for this reader


def read(ctx):
  k = (ctx.get("trace") or {}).get("kernels", {}).get(KERNEL)
  kind = arch.load(ctx["hf"]["arch_kind"])
  layer_types = getattr(kind, "hf_layer_types", None)
  if not k or not k["calls"] or not ctx.get("peaks") or layer_types is None:
    return None
  rows, tokens = lib.resident(ctx)
  per_layer = kind.cache_read_bytes(ctx["hf"], rows, tokens, lib.kv_quant(ctx))
  attention = [b for b, t in zip(per_layer, layer_types(ctx["hf"])) if t == "attention"]
  if not attention:
    return None
  return 100.0 * (sum(attention) / len(attention) / ctx["peaks"]["hbm_bytes_per_s"]) / (k["device_s"] / k["calls"])
