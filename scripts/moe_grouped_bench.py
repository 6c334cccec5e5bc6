#!/usr/bin/env python3
"""The grouped form of the routed experts' product alone, on the chip, at the benchmark cells' shapes (ISSUE 56,
"Measure first").

For each (case, tokens, walk) a program of ``--calls`` chained calls of ``ops/moe.py _moe_ffn_grouped`` over one
layer's stacked expert leaves (each call's output is mixed into the next one's tokens, so none is folded away; the
routing is drawn once, outside, as a layer step hands it in — what is timed is the scope ``xot.moe_experts``) runs
under the profiler. Times are device durations of the program's own ``XLA Ops`` events: the Mosaic calls by name
(``moe_gate_up`` / ``moe_up`` / ``moe_down`` on the shared walk, ``*_rows`` on the aligned one), ``device_us`` the
time in which any op of a call runs (the union of their intervals: an async ``copy-start`` / ``slice-start`` spans
the ops it rides under, so durations do not add up), ``around_us`` what of it is not a Mosaic call (the sort, the
gathers, the ``where``, the weighted sum), ``ops_us`` the largest ops that are no such window. Beside
them what the product has to do: ``bytes_us`` — the experts the rows chose read once, the tokens read and written — at
the HBM peak, ``flops_us`` — 2 · rows held · D · F a matrix — at the bfloat16 peak, and ``x_floor`` = ``device_us``
over the larger of the two.

  python scripts/moe_grouped_bench.py [--root DIR] [--cases name,...] [--tokens N,...] [--walks auto,shared,aligned:256]
                                      [--calls 4] [--out FILE] [--check]

``--root`` puts another checkout (the parent's) first on the path; a checkout without ``grouped_walk`` has one walk
and takes ``auto`` only. ``--walks``: ``auto`` is the module's own rule, ``shared`` / ``aligned[:TILE]`` force a walk
(and a tile height) for the sweep that sets the rule's thresholds; the served path takes no such switch. ``--check``
compares each forced walk's output with the first walk's of the same (case, tokens), bit for bit. One JSON line a
measurement on stdout and in ``--out``. No chip: exits 1 at once (a CPU time is no time), unless ``--rehearse``: the
same control flow at faces of 128 and a sixteenth of the tokens, the kernels interpreted, every time 0.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

PEAK_BF16, PEAK_HBM = 197e12, 819e9  # TPU v5e, benchmark/peaks.json

# name: (k, router's width E, held range or None, D, F, int8 codes, gated, a full run's tokens, a short group's)
CASES = {
  "smallthinker": (6, 64, None, 2560, 768, False, True, 2048, 256),
  "laguna": (8, 256, None, 2048, 512, False, True, 2048, 128),
  "ling": (8, 512, (0, 128), 2560, 768, False, True, 4096, 256),
  "moonlight": (6, 64, None, 2048, 1408, True, True, 4096, 64),
  "nemotron": (6, 128, None, 2688, 1856, False, False, 4096, 128),
}


def op_intervals(trace_dir: str) -> list[tuple[str, int, int]]:
  """(name without its numbering, start ns, duration ns) of every ``XLA Ops`` event of the newest trace under
  ``trace_dir`` (``flash_prefill_bench.py op_events``'s walk, with the starts)."""
  import glob
  import re

  from jax.profiler import ProfileData

  path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)[-1]
  out = []
  for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/device:TPU:"):
      for line in plane.lines:
        if "xla ops" in line.name.lower():
          out += [(re.sub(r"\.\d+", "", ev.name.split(" = ", 1)[0].strip().lstrip("%")), int(ev.start_ns), int(ev.duration_ns)) for ev in line.events]
  return out


def busy_ns(events) -> int:
  """The length of the union of the events' intervals."""
  total, end = 0, 0
  for _, start, dur in sorted(events, key=lambda e: e[1]):
    total += max(start + dur - max(start, end), 0)
    end = max(end, start + dur)
  return total


def main() -> int:
  ap = argparse.ArgumentParser()
  ap.add_argument("--root", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  ap.add_argument("--cases", default=",".join(CASES))
  ap.add_argument("--tokens", default="", help="token counts for every case (default: the case's full run and its short group)")
  ap.add_argument("--walks", default="auto")
  ap.add_argument("--calls", type=int, default=4)
  ap.add_argument("--repeats", type=int, default=3)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--out", default="")
  ap.add_argument("--label", default="")
  ap.add_argument("--check", action="store_true")
  ap.add_argument("--rehearse", action="store_true")
  args = ap.parse_args()
  sys.path.insert(0, args.root)

  import jax
  import jax.numpy as jnp
  import numpy as np

  if jax.default_backend() != "tpu" and not args.rehearse:
    print(json.dumps({"ok": False, "why": f"no chip: backend {jax.default_backend()}"}))
    return 1
  from xotorch_support_jetson_tpu.ops import moe

  moe.INTERPRET = args.rehearse
  rule = getattr(moe, "grouped_walk", None)

  def force(walk: str, tile: str):
    """``grouped_walk`` answering ``walk`` whatever the shapes, at ``tile`` rows or the height the walk has without the tall tile."""
    return lambda rows, *a, **kw: (walk, int(tile) if tile else moe.ROW_TILE if walk == "aligned" or rows >= moe.ROW_TILE else -(-rows // 16) * 16)

  walks = args.walks.split(",") if rule else ["auto"]
  lines = []
  for name in args.cases.split(","):
    k, E, held, D, F, quant, gated, full, short = CASES[name]
    if args.rehearse:
      D, F, full, short = 128, 128, full // 16, short // 16
    E_held = E if held is None else held[1] - held[0]
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    if quant:
      leaf = lambda key, *shape: jax.random.randint(key, shape, -127, 128, jnp.int8)  # noqa: E731
      scales = tuple(jax.random.uniform(kk, (1, E_held, n), jnp.float32, 0.0005, 0.002) for kk, n in zip(ks[4:7], (F, F, D)))
    else:
      leaf = lambda key, *shape: (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(jnp.bfloat16)  # noqa: E731
      scales = None
    w_up = leaf(ks[1], 1, E_held, D, F) if gated else leaf(ks[1], 1, E_held, F, D)
    w_gate = leaf(ks[0], 1, E_held, D, F) if gated else None
    w_down = leaf(ks[2], 1, E_held, F, D)
    w_router = jax.random.normal(ks[3], (D, E), jnp.float32)
    expert_bytes = (3 if gated else 2) * D * F * w_down.dtype.itemsize
    for T in [int(t) for t in args.tokens.split(",")] if args.tokens else (full, short):
      x = jax.random.normal(ks[7], (T, D), jnp.float32).astype(jnp.bfloat16)
      routed = jax.jit(lambda x: moe.route(x, w_router, k, "softmax", True))(x)
      idx = np.asarray(routed.idx)
      mine = (idx >= held[0]) & (idx < held[1]) if held else np.ones_like(idx, bool)
      rows_held, visited = int(mine.sum()), len(set(idx[mine].tolist()))
      first = None
      for walk in walks:
        jax.clear_caches()  # the rule is read while tracing
        if rule:
          moe.grouped_walk = rule
        forced, _, tile = walk.partition(":")
        if forced != "auto":
          moe.grouped_walk = force(forced, tile)

        def one(x, w_gate, w_up, w_down, scales):
          return moe._moe_ffn_grouped(x, w_router, w_gate, w_up, w_down, k, "softmax", True, None, 1.0, 1, 1, "none", held, scales, jnp.int32(0), "silu" if gated else "relu2", routed)[0]

        def chain(x, *leaves):
          for _ in range(args.calls):
            x = x + (one(x, *leaves) * 0.01).astype(x.dtype)
          return x

        line = {"case": name, "tokens": T, "walk": walk if rule else "parent", "took": list(moe.grouped_walk(T * k, E, E_held)) if rule else None, "label": args.label, "rows_held": rows_held, "visited": visited}
        try:
          fn = jax.jit(chain)
          t0 = time.perf_counter()
          fn(x, w_gate, w_up, w_down, scales).block_until_ready()
          line["compile_s"] = round(time.perf_counter() - t0, 2)
          with tempfile.TemporaryDirectory() as td:
            jax.profiler.start_trace(td)
            for _ in range(args.repeats):
              fn(x, w_gate, w_up, w_down, scales).block_until_ready()
            jax.profiler.stop_trace()
            events = op_intervals(td)
          per_call = {}
          for n, _, dur in events:
            per_call[n] = per_call.get(n, 0.0) + dur / 1e3 / args.repeats / args.calls
          kernels = {n: round(us, 1) for n, us in per_call.items() if n.startswith("moe_")}
          device_us = busy_ns(events) / 1e3 / args.repeats / args.calls
          bytes_us = (visited * expert_bytes + 2 * T * D * 2) / PEAK_HBM * 1e6
          flops_us = 2 * rows_held * D * F * (3 if gated else 2) / PEAK_BF16 * 1e6
          line.update(
            kernels_us=kernels,
            device_us=round(device_us, 1),
            around_us=round(device_us - sum(kernels.values()), 1),
            bytes_us=round(bytes_us, 1),
            flops_us=round(flops_us, 1),
            x_floor=round(device_us / max(bytes_us, flops_us), 2),
            ops_us=dict([(n, round(us, 1)) for n, us in sorted(per_call.items(), key=lambda kv: -kv[1]) if not n.endswith(("-start", "-done"))][:10]),
          )
          if args.check:
            out = np.asarray(jax.jit(one)(x, w_gate, w_up, w_down, scales).astype(jnp.float32))
            if first is None:
              first = out
            line.update(finite=bool(np.isfinite(out).all()), bit_equal_first=bool(np.array_equal(out, first)), max_abs_from_first=float(np.abs(out - first).max()))
        except Exception as e:  # noqa: BLE001 — a shape Mosaic refuses is a finding, and the others still run
          line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
        print(json.dumps(line), flush=True)
        lines.append(line)
    del w_gate, w_up, w_down
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
      f.writelines(json.dumps(line) + "\n" for line in lines)
  return 0


if __name__ == "__main__":
  sys.exit(main())
