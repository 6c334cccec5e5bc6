"""The Pallas paged-decode kernel against its memory roofline: codes and scales
of the cached tokens resident in the traced interval, per call, over the peak
HBM rate, over the kernel's device time per call."""
import flops_bytes
import layer_lib as lib

KERNEL = "paged_decode"
KERNELS = (KERNEL,)  # op-name substrings the trace reduction should total for this reader


def read(ctx):
  k = (ctx.get("trace") or {}).get("kernels", {}).get(KERNEL)
  if not k or not k["calls"] or not ctx.get("peaks"):
    return None
  rows, tokens = lib.resident(ctx)
  least_s = flops_bytes.paged_attention_min_bytes(ctx["hf"], rows, tokens, lib.kv_quant(ctx)) / ctx["peaks"]["hbm_bytes_per_s"]
  return 100.0 * least_s / (k["device_s"] / k["calls"])
