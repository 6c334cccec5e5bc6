"""Rehearsal 3 of the on-chip-measurement guide, by hand: compile a
configuration's serving programs for a described v5e chip (no chip attached)
at the real sizes and pool. The compiler's refusal is the only usable memory
budget (PERF.md, PR 21), so this is also how XOT_TPU_BATCH_PAGES is chosen.

  JAX_PLATFORMS=cpu python benchmark/tools/aot_compile.py --config mistral-7b-int8 --pages 1025

Nothing runs: a compile that passes is not a measurement.
"""

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import common  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import weights  # noqa: E402

PS = 64


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--config", required=True)
  ap.add_argument("--pages", type=int, default=0, help="pool pages incl. the trash page; 0 = the scheduler's default")
  ap.add_argument("--programs", default="weights,decode,mixed,prefill,score")
  ap.add_argument("--prefill", default="1x2048", help="KxS_pad of the prefill program")
  ap.add_argument("--mixed-pad", type=int, default=1024)
  args = ap.parse_args()

  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding

  from xotorch_support_jetson_tpu.inference.paging import default_pool_pages, pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, paged_kernel_supported

  jax.config.update("jax_enable_compilation_cache", False)
  topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
  chip = SingleDeviceSharding(topo.devices[0])
  jax.default_backend = lambda: "tpu"  # the kernel gates ask; answered for the trace only

  hf = common.load_config(args.config)
  for k, v in hf["serving_env"].items():
    os.environ[k] = v
  cfg = common.model_config(hf)
  shard = Shard(hf["model_id"], 0, cfg.n_layers - 1, cfg.n_layers)
  n_slots = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"])
  quant = decoder.kv_quant_mode(cfg)
  n_pages = args.pages or default_pool_pages(cfg, cfg.n_layers, n_slots, cfg.max_seq_len, PS, quant) + 1
  mp = pages_to_cover(cfg.max_seq_len, PS)

  def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

  def on_chip(tree):
    return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

  params = on_chip(weights.param_shapes(hf))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS, quant=quant)))
  nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
  print(f"config={args.config} pages={n_pages} pool={nbytes(pool)/1e9:.3f} GB weights={nbytes(params)/1e9:.3f} GB kv_quant={quant!r}", flush=True)
  use_kernel = bool(paged_kernel_supported(cfg)) and quant != ""
  rows = lambda dtype: sds((n_slots,), dtype)  # noqa: E731
  key = sds((2,), jnp.uint32)

  def report(name, fn):
    t0 = time.perf_counter()
    try:
      c = fn()
      m = c.memory_analysis()
      kern = c.as_text().count("tpu_custom_call")
      print(f"OK   {name}: {time.perf_counter()-t0:.1f}s args={m.argument_size_in_bytes/1e9:.2f}G temp={m.temp_size_in_bytes/1e9:.2f}G out={m.output_size_in_bytes/1e9:.2f}G alias={m.alias_size_in_bytes/1e9:.2f}G kernels={kern}", flush=True)
    except Exception as e:  # noqa: BLE001 — the refusal is the result
      msg = str(e)
      i = msg.find("RESOURCE_EXHAUSTED")
      print(f"FAIL {name}: {time.perf_counter()-t0:.1f}s {msg[i:i+400] if i >= 0 else msg[:600]}", flush=True)

  progs = args.programs.split(",")
  if "weights" in progs:
    make = weights.maker_for(hf["arch_kind"])
    shapes = weights.shape_hf(hf)
    kshape = jax.eval_shape(lambda: weights.seed_key(0))
    report("weights", lambda: jax.jit(lambda k: make(shapes, k), out_shardings=chip).lower(jax.ShapeDtypeStruct(kshape.shape, kshape.dtype, sharding=chip)).compile())
  if "decode" in progs:
    report("decode.paged_batch", lambda: decoder._fused_paged_batch_decode_impl.xot_jitted.lower(
      params, cfg, shard, sds((n_slots, 1), jnp.int32), pool, sds((n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_),
      rows(jnp.float32), rows(jnp.int32), 8, 64, PS, use_kernel, key, None).compile())
  if "mixed" in progs and not cfg.is_mla:
    pad = args.mixed_pad
    report(f"decode.mixed_paged_batch pad={pad}", lambda: decoder._fused_mixed_paged_batch_decode_impl.xot_jitted.lower(
      params, cfg, shard, sds((n_slots, 1), jnp.int32), pool, sds((n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_),
      rows(jnp.float32), rows(jnp.int32), sds((1, pad), jnp.int32), sds((1, mp), jnp.int32), sds((1,), jnp.int32), sds((1,), jnp.int32),
      8, 64, PS, use_kernel, key, None, None).compile())
  if "prefill" in progs:
    for spec in args.prefill.split(","):
      k, s = (int(x) for x in spec.split("x"))
      w = 1
      while w < pages_to_cover(s, PS):
        w *= 2
      r = lambda dtype: sds((k,), dtype)  # noqa: E731
      report(f"prefill.pages_many_sampled K={k} S={s} window={w}", lambda: decoder.prefill_into_pages_many_sampled.xot_jitted.lower(
        params, cfg, shard, sds((k, s), jnp.int32), pool, sds((k, w), jnp.int32), r(jnp.int32), r(jnp.int32), PS,
        r(jnp.float32), r(jnp.int32), key, 64, None).compile())
  if "score" in progs:
    report("prefill.score_last S=256", lambda: decoder.score_last_tokens.xot_jitted.lower(
      params, cfg, shard, sds((1, 256), jnp.int32), sds((), jnp.int32), 32, 20).compile())


if __name__ == "__main__":
  main()
