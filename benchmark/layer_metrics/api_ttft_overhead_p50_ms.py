"""What the API, the node and the loopback add to TTFT around the scheduler (split per cell kind in BENCHMARK.json: .open, .closed)."""
import layer_lib


def read(ctx):
  return layer_lib.api_ttft_overhead_p50_ms(ctx)
