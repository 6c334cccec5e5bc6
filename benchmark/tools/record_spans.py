"""Record the small capture that ``tests/test_span_lib.py`` reads: a tiny MLA + MoE
model (int8 weights, one dense layer and one expert layer, so every component
scope occurs) served by the program's own scheduler for four requests — one
prefill group and two decode chunks — under ``jax.profiler`` with the options
``run.py`` uses. A builder's tool, run on the chip; never a cell, never a metric.

  python benchmark/tools/record_spans.py chiprun_out/record_spans

writes ``<out>/decode_scopes_spans.xplane.pb.gz`` and prints what ``span_lib``
reads from it, for the test's expected numbers.
"""

import asyncio
import gzip
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(XOT_TPU_KV_TIER="0", XOT_TPU_SPEC_BATCH="0")

import common  # noqa: E402,F401 — puts the repo root on sys.path


def main() -> None:
  out = Path(sys.argv[1])
  out.mkdir(parents=True, exist_ok=True)
  import jax
  import jax.numpy as jnp
  import numpy as np

  import span_lib
  import trace_reduce
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import ModelConfig
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
  cfg = ModelConfig(
    vocab_size=512, dim=256, n_layers=2, n_heads=4, n_kv_heads=4, hidden_dim=512, norm_eps=1e-5, rope_theta=10000.0, max_seq_len=256, dtype=jnp.bfloat16,
    kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, n_experts=8, n_active_experts=2, moe_hidden_dim=128, first_k_dense=1, shared_expert_dim=128,
  )
  params, shard = full_model_params(jax.random.PRNGKey(24), cfg, "record-spans")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, quantize_params(params))
  server = BatchedServer(engine, n_slots=4, chunk=8)
  rng = np.random.default_rng(24)

  async def batch(tag: str, sizes: list[int]) -> None:
    await asyncio.gather(*(
      server.submit(f"{tag}{i}", rng.integers(1, 512, size=n).astype(np.int32), max_tokens=17, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
      for i, n in enumerate(sizes)
    ))

  async def drive() -> None:
    await batch("warm", [40, 30, 20, 10])  # compile the group's shape and the decode chunk, untraced
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out / "trace"), profiler_options=opts)
    try:
      await batch("a", [40, 30, 20, 10])
    finally:
      jax.profiler.stop_trace()

  try:
    asyncio.run(drive())
  finally:
    server.shutdown()
  path = trace_reduce.find_xplane(str(out / "trace"))
  packed = out / "decode_scopes_spans.xplane.pb.gz"
  packed.write_bytes(gzip.compress(Path(path).read_bytes(), 9))
  red = span_lib.reduce(path, trace_reduce.program_families())
  print(json.dumps({
    "bytes": packed.stat().st_size, "decode": red["decode"], "scope_s": red["scope_s"], "dequant_s": red["dequant_s"], "in_program_gap_s": red["in_program_gap_s"],
    "gaps": len(red["gaps"]), "idle_s": sum(b - a for a, b in red["gaps"]), "idle_named_share": span_lib.idle_named_share(red),
    "sched_ms_per_tick": span_lib.phase_ms_per_tick(red), "host_spans": len(red["host"]), "op_name_collisions": red["op_name_collisions"],
  }), flush=True)


if __name__ == "__main__":
  main()
