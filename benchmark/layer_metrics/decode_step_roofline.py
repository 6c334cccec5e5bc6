"""The whole decode step against its memory roofline: the least bytes the step
must read (flops_bytes.decode_step_min_bytes at the rows and cached tokens
resident in the traced interval) over the peak HBM rate, over the step's device
time. A batched decode step of these models is memory-bound (16 rows x 2
operations per weight byte is far under the chip's ~240 operations per byte)."""
import flops_bytes
import layer_lib as lib


def read(ctx):
  step_ms = lib.decode_step_device_ms(ctx)
  if step_ms is None or not ctx.get("peaks"):
    return None
  rows, tokens = lib.resident(ctx)
  least_s, _bound = flops_bytes.roofline_seconds(
    flops_bytes.decode_step_flops(ctx["hf"], rows), flops_bytes.decode_step_min_bytes(ctx["hf"], rows, tokens, lib.kv_quant(ctx)), ctx["peaks"]
  )
  return 100.0 * least_s / (step_ms / 1e3)
