"""How long one batched decode dispatch takes as a function of the page pool's
size — the served program (``decode.paged_batch``) called directly, no server.
A builder's probe for choosing XOT_TPU_BATCH_PAGES and for PERF.md; not a cell.

  python benchmark/tools/pool_probe.py --config mistral-7b-int8 --pages 129,257,513 --context 800
"""

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common  # noqa: E402


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--config", required=True)
  ap.add_argument("--pages", required=True)
  ap.add_argument("--context", type=int, default=800, help="cached tokens per row")
  ap.add_argument("--trace-dir", default="")
  args = ap.parse_args()
  hf = common.load_config(args.config)
  import serve

  serve.apply_serving_env(hf)
  import jax
  import jax.numpy as jnp
  import numpy as np

  import trace_reduce
  import weights
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool
  from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache

  configure_compile_cache()
  print(json.dumps({"device": str(jax.devices()[0].device_kind)}), flush=True)
  cfg = common.model_config(hf)
  shard = Shard(hf["model_id"], 0, cfg.n_layers - 1, cfg.n_layers)
  params = weights.build_params(hf, 1)
  n, ps, mp = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), 64, cfg.max_seq_len // 64
  quant = decoder.kv_quant_mode(cfg)
  for n_pages in (int(p) for p in args.pages.split(",")):
    pool = init_paged_pool(cfg, cfg.n_layers, n_pages, ps, quant=quant)
    per_row = min(-(-(args.context + 64) // ps), (n_pages - 1) // n)
    bt = np.zeros((n, mp), np.int32)
    for r in range(n):
      bt[r, :per_row] = 1 + r * per_row + np.arange(per_row)
    pos = jnp.full((n,), min(args.context, per_row * ps - 16), jnp.int32)
    tok = jnp.ones((n, 1), jnp.int32)
    times = []
    for i in range(4):
      if i == 3 and args.trace_dir:
        jax.profiler.start_trace(f"{args.trace_dir}/pages{n_pages}")
      t0 = time.perf_counter()
      toks, tok, pos, pool = decoder.fused_paged_batch_decode(params, cfg, shard, tok, pool, jnp.asarray(bt), pos, jnp.ones((n,), bool), jnp.zeros((n,), jnp.float32), 8, page_size=ps)
      jax.block_until_ready(toks)
      times.append(time.perf_counter() - t0)
      if i == 3 and args.trace_dir:
        jax.profiler.stop_trace()
        red = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(f"{args.trace_dir}/pages{n_pages}")), trace_reduce.program_families(), times[-1])
        print(json.dumps({"pages": n_pages, "busy_s": red["busy_s"], "programs": red["programs"], "device_ops": red["device_ops"]}), flush=True)
    print(json.dumps({"pages": n_pages, "pool_gb": sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool)) / 1e9, "context": int(pos[0]) - 32, "dispatch_s": times, "ms_per_step": min(times[1:]) / 8 * 1e3}), flush=True)
    del pool


if __name__ == "__main__":
  main()
