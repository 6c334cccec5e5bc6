#!/usr/bin/env python3
"""The flash prefill kernel alone, on the chip, at the benchmark cells' shapes (ISSUE 54, "Measure first").

For each case a program of ``--calls`` chained calls of ``ops/pallas_attention.py flash_attention_prefill`` (each
call's output is the next one's queries, so none is folded away) runs under the profiler; the kernel's time is the
device duration of its own ``XLA Ops`` events (the Mosaic custom call, named ``flash_prefill`` since PR 54 and by its
scope ``xot.attn`` before), the wrapper's time the host clock over the whole program. The share of the chip's
bfloat16 peak is taken over the NEEDED products — 4 · hd · Hq flops for every (query, key) pair the causal / window
rule lets through, not for the blocks a tile walks.

  python scripts/flash_prefill_bench.py [--root DIR] [--cases name,...] [--calls 8] [--out FILE] [--check]
                                       [--tiles HEADS,BQ,BK] [--set p_terms=N] [--plan FILE]

``--root`` puts another checkout (the parent's) first on the path; ``--tiles`` forces the tile of a checkout whose
module has the rule ``_tile`` and ``--set p_terms=1`` a single bfloat16 ``p`` (for the sweep that chose the rule and
the timing ISSUE 54 asks for; the served path takes neither). ``--check`` also compares each case's output with
plain attention in float32 on the chip. One JSON line a case on stdout and in ``--out``. No chip: exits 1 at once
(a CPU time is no time).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

PEAK_BF16 = 197e12  # TPU v5e, benchmark/peaks.json

# name: (Hq, Hkv, hd, int8 codes, Sq, offset, Skv, window)
CASES = {
  "smallthinker.full.o0": (28, 4, 128, False, 2048, 0, 2048, 0),
  "smallthinker.full.o4096": (28, 4, 128, False, 2048, 4096, 8192, 0),
  "smallthinker.full.o8192": (28, 4, 128, False, 2048, 8192, 16384, 0),
  "smallthinker.win4096.o0": (28, 4, 128, False, 2048, 0, 2048, 4096),
  "smallthinker.win4096.o4096": (28, 4, 128, False, 2048, 4096, 8192, 4096),
  "smallthinker.win4096.o8192": (28, 4, 128, False, 2048, 8192, 16384, 4096),
  "laguna.full48.o0": (48, 8, 128, False, 2048, 0, 2048, 0),
  "laguna.full48.o2048": (48, 8, 128, False, 2048, 2048, 4096, 0),
  "laguna.win512x64.o0": (64, 8, 128, False, 2048, 0, 2048, 512),
  "laguna.win512x64.o2048": (64, 8, 128, False, 2048, 2048, 4096, 512),
  "mistral.int8.s512": (32, 8, 128, True, 512, 0, 1024, 0),
  "mistral.int8.s128.o512": (32, 8, 128, True, 128, 512, 1024, 0),
  "olmo.mha30.s1024": (30, 30, 128, False, 1024, 0, 2048, 0),
  "granite.hd64.s1024": (32, 8, 64, False, 1024, 0, 2048, 0),
  "nemotron.g16.s1024": (32, 2, 128, False, 1024, 0, 2048, 0),
}


def needed_pairs(sq: int, offset: int, window: int) -> int:
  return sum(min(offset + i + 1, window) if window else offset + i + 1 for i in range(sq))


def op_events(trace_dir: str) -> dict[str, list[float]]:
  """Device seconds of every ``XLA Ops`` event of the newest trace under ``trace_dir``, by the instruction's name
  without its numbering (``benchmark/trace_reduce.py op_base``'s rule)."""
  from jax.profiler import ProfileData

  path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)[-1]
  out: dict[str, list[float]] = {}
  for plane in ProfileData.from_file(path).planes:
    if not plane.name.startswith("/device:TPU:"):
      continue
    for line in plane.lines:
      if "xla ops" in line.name.lower():
        for ev in line.events:
          out.setdefault(re.sub(r"\.\d+", "", ev.name.split(" = ", 1)[0].strip().lstrip("%")), []).append(ev.duration_ns / 1e9)
  return out


def check_case(jax, jnp, out, q, k, v, scales, offset: int, window: int) -> dict:
  """The kernel's output against plain attention in float32 over the same stored values, one KV head's group at a
  time: the worst error in units of (one bfloat16 ulp of the output + 2^-16 of sum(p·|v|)) — tests/test_pallas_attention.py's
  measure, here at the cells' lengths and through Mosaic's own products."""
  sq, hq, hd = q.shape[1:]
  hkv = k.shape[2]
  lo, hi = (max(offset - window + 1, 0) if window else 0), offset + sq
  q_pos, kv_pos = offset + jnp.arange(sq)[:, None], jnp.arange(lo, hi)[None, :]
  mask = (kv_pos <= q_pos) & ((kv_pos > q_pos - window) if window else True)

  @jax.jit
  def one(qg, kh, vh, outg):
    with jax.default_matmul_precision("highest"):
      probs = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("qhd,kd->hqk", qg.astype(jnp.float32), kh) / hd**0.5, -jnp.inf), axis=-1)
      ref, spread = jnp.einsum("hqk,kd->qhd", probs, vh), jnp.einsum("hqk,kd->qhd", probs, jnp.abs(vh))
    ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(ref), 1e-30))) - 7)
    err = jnp.abs(outg.astype(jnp.float32) - ref)
    return jnp.max(err / (ulp + 2.0**-16 * spread)), jnp.max(err), jnp.mean(err > ulp)

  worst = [0.0, 0.0, 0.0]
  g = hq // hkv
  for h in range(hkv):
    kh, vh = (x[0, lo:hi, h].astype(jnp.float32) * (1.0 if s is None else s[0, lo:hi, h]) for x, s in ((k, scales[0]), (v, scales[1])))
    got = [float(x) for x in one(q[0, :, h * g : (h + 1) * g], kh, vh, out[0, :, h * g : (h + 1) * g])]
    worst = [max(a, b) for a, b in zip(worst, got)]
  return {"check_units": round(worst[0], 3), "check_max_abs": worst[1], "check_over_ulp_share": round(worst[2], 5)}


def main() -> int:
  ap = argparse.ArgumentParser()
  ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  ap.add_argument("--cases", default=",".join(CASES))
  ap.add_argument("--calls", type=int, default=8)
  ap.add_argument("--repeats", type=int, default=3)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--out", default="")
  ap.add_argument("--tiles", default="", help="HEADS,BQ,BK: force the tile (needs the module's _tile rule)")
  ap.add_argument("--label", default="")
  ap.add_argument("--plan", default="", help="file of lines 'case tiles|- p_terms=N|-': one process for a whole sweep")
  ap.add_argument("--check", action="store_true", help="compare each case's output with the masked softmax in float32 (on the chip, highest precision)")
  ap.add_argument("--set", default="", help="p_terms=N: bfloat16 terms of p in the value product (1: what most flash kernels ship; timed, not served)")
  args = ap.parse_args()
  sys.path.insert(0, args.root)

  import jax
  import jax.numpy as jnp

  if jax.default_backend() != "tpu":
    print(json.dumps({"ok": False, "why": f"no chip: backend {jax.default_backend()}"}))
    return 1
  from xotorch_support_jetson_tpu.ops import pallas_attention as pa

  default_tile, default_terms = getattr(pa, "_tile", None), getattr(pa, "P_TERMS", None)
  plan = [(name, args.tiles, args.set) for name in args.cases.split(",")]
  if args.plan:  # lines "case tiles|- set|-": a sweep in one process (each new process costs ~15 s of reaching the chip)
    plan = [tuple(x if x != "-" else "" for x in line.split()) for line in open(args.plan) if line.strip() and not line.startswith("#")]
  lines = []
  for name, tiles, sets in plan:
    jax.clear_caches()  # the switches are read while tracing
    if default_tile is not None:
      pa._tile, pa.P_TERMS = default_tile, default_terms
    if tiles:
      pa._tile = lambda *a, forced=tuple(int(x) for x in tiles.split(",")), **k: forced
    if sets:
      pa.P_TERMS = int(sets.removeprefix("p_terms="))
    hq, hkv, hd, quant, sq, offset, skv, window = CASES[name]
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    q = jax.random.normal(ks[0], (1, sq, hq, hd), jnp.bfloat16)
    if quant:
      k, v = (jax.random.randint(kk, (1, skv, hkv, hd), -127, 128, jnp.int8) for kk in ks[1:3])
      scales = tuple(jax.random.uniform(kk, (1, skv, hkv, 1), jnp.float32, 0.005, 0.02) for kk in ks[3:5])
    else:
      k, v = (jax.random.normal(kk, (1, skv, hkv, hd), jnp.bfloat16) for kk in ks[1:3])
      scales = (None, None)
    off = jnp.full((1,), offset, jnp.int32)

    def chain(q, k, v, off, scales):
      for _ in range(args.calls):
        q = pa.flash_attention_prefill(q, k, v, off, *scales, window=window)
      return q

    line = {"case": name, "label": args.label, "root": args.root, "calls": args.calls, "tiles": tiles, "set": sets}
    try:
      fn = jax.jit(chain)
      t0 = time.perf_counter()
      fn(q, k, v, off, scales).block_until_ready()
      line["compile_s"] = round(time.perf_counter() - t0, 3)
      with tempfile.TemporaryDirectory() as td:
        jax.profiler.start_trace(td)
        walls = []
        for _ in range(args.repeats):
          t0 = time.perf_counter()
          fn(q, k, v, off, scales).block_until_ready()
          walls.append(time.perf_counter() - t0)
        jax.profiler.stop_trace()
        ops = op_events(td)
      evs = sorted(ops.get("flash_prefill") or ops.get("xot.attn") or [])
      line["ops_us"] = {n: round(sum(d) / args.repeats / args.calls * 1e6, 1) for n, d in sorted(ops.items(), key=lambda x: -sum(x[1]))[:5]}  # a call's share of every op
      flops = 4 * hd * hq * needed_pairs(sq, offset, window)
      kernel_us = evs[len(evs) // 2] * 1e6 if evs else None
      if args.check:
        line.update(check_case(jax, jnp, pa.flash_attention_prefill(q, k, v, off, *scales, window=window), q, k, v, scales, offset, window))
      line.update(
        events=len(evs),
        kernel_us=kernel_us and round(kernel_us, 1),
        kernel_us_min=evs and round(evs[0] * 1e6, 1),
        wrapper_us=round(min(walls) / args.calls * 1e6, 1),
        needed_gflop=round(flops / 1e9, 2),
        peak_share=kernel_us and round(flops / (kernel_us * 1e-6) / PEAK_BF16, 4),
      )
    except Exception as e:  # noqa: BLE001 — a case Mosaic refuses is a finding, and the others still run
      line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
    print(json.dumps(line), flush=True)
    lines.append(line)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
      f.writelines(json.dumps(line) + "\n" for line in lines)
  return 0


if __name__ == "__main__":
  sys.exit(main())
