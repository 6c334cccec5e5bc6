"""The Pallas paged-decode kernel's calls on WINDOW layers against their memory roofline: the K/V that one window layer
must read for the rows resident in the traced interval (the mean of ``cache_read_bytes`` over the layers the kind's
``hf_attention_kinds`` calls "window": at most a window's tokens a row, whatever the rows hold), over the peak HBM
rate, over the device time a call of the kernel's windowed form (``paged_decode_window``: the name a call with a
static window carries, ops/paged.py). A kernel that folds every resident page of a row reads far under 100 % here
(rows of ~2.8 k tokens under a window of 512: ~19 %); one that starts at the window's first page reads what the full
layers' calls read of theirs. None where the kind names no attention kinds, none of them is a window, or the trace
holds no call of that name (a program without the window operand, or one that serves the model without the kernel)."""
import arch
import layer_lib as lib

KERNEL = "paged_decode_window"
KERNELS = (KERNEL,)  # op-name substrings the trace reduction should total for this reader


def read(ctx):
  k = (ctx.get("trace") or {}).get("kernels", {}).get(KERNEL)
  kind = arch.load(ctx["hf"]["arch_kind"])
  kinds = getattr(kind, "hf_attention_kinds", None)
  if not k or not k["calls"] or not ctx.get("peaks") or kinds is None:
    return None
  rows, tokens = lib.resident(ctx)
  per_layer = kind.cache_read_bytes(ctx["hf"], rows, tokens, lib.kv_quant(ctx))
  window = [b for b, t in zip(per_layer, kinds(ctx["hf"])) if t == "window"]
  if not window:
    return None
  return 100.0 * (sum(window) / len(window) / ctx["peaks"]["hbm_bytes_per_s"]) / (k["device_s"] / k["calls"])
