#!/usr/bin/env python3
"""A long teacher-forced decode of a benchmark configuration on the chip, against the kind's plain reference (builder's
tool; beyond ``benchmark/correctness.py``'s 8 tokens after 160): for a configuration with per-slot recurrent state,
whose state 168 positions hardly fill, and for one with attention windows, which 168 positions never reach.

  python scripts/chip_teacher_forced.py --config ling-3.0-flash-ep4-d7 --seed 7 [--rows 4] [--steps 160] [--probes a,b]

At the published widths and the cell's pool (slots and pages of the file's ``serving_env``): ``--rows`` prompts of
500-1000 seeded tokens (600-1200 for a kind with ``long_probes``: past a window of 512 from the first decoded token on;
what the kind's ``long_prompt_tokens`` says where it says: 4160-4608 past a window of 4096)
are prefilled as one group through ``prefill.pages_many`` (pool donated), then ``--steps`` decode
steps run through ``paged_decode_forward`` over pool, state and pages, each fed the seeded next token (teacher-forced),
the other slots inactive. The steps attend, write and step the recurrent state through whatever the served decode
program does (``paged_kernel_supported``: on a TPU the Pallas paged kernel, with the layer's window, the Mosaic token write
and the state-step kernels; until PR 58 a configuration with recurrent layers kept the XLA forms here). Every step's log-softmax is compared with the float32 reference's full forward over prompt +
continuation (``jax.default_matmul_precision("highest")``, one row at a time): the largest and the mean |difference| over
the reference's 64 likeliest tokens a position, and the reference's best log-prob minus its log-prob of the program's
greedy token. ``--probes`` adds the same numbers against deliberately wrong references of the kind's ``probes`` and
``long_probes`` (``all``: every one of them; an ``exact_probes`` entry by its name, also beside ``all``). One
JSON line; exit 1 where JAX sees no TPU (``--cpu`` rehearses at the kind's tiny widths)."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "benchmark")]


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--config", required=True)
  ap.add_argument("--seed", type=int, default=7)
  ap.add_argument("--rows", type=int, default=4)
  ap.add_argument("--steps", type=int, default=160)
  ap.add_argument("--probes", default="")
  ap.add_argument("--cpu", action="store_true")
  args = ap.parse_args()
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  if args.cpu:
    os.environ["JAX_PLATFORMS"] = "cpu"

  import arch
  import common
  import weights

  hf = common.load_config(args.config)
  kind = arch.load(hf["arch_kind"])
  if args.cpu:
    hf.update(kind.REHEARSE_WIDTHS)
    hf["serving_window_tokens"] = 1024
  for k, v in hf["serving_env"].items():
    os.environ[k] = str(v)

  import jax
  import jax.numpy as jnp
  import numpy as np

  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models import decoder as dec
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, paged_kernel_supported

  dev = jax.devices()[0]
  if dev.platform != "tpu" and not args.cpu:
    print(json.dumps({"ok": False, "error": f"no TPU: {dev.platform}"}))
    return 1
  t0 = time.perf_counter()
  params = weights.build_params(hf, args.seed)
  cfg = common.model_config(hf)
  all_probes = {**kind.probes(hf), **getattr(kind, "long_probes", lambda _hf: {})(hf)}
  use_kernel = paged_kernel_supported(cfg)  # what a server resolves on this device, recurrent layers or none (the docstring)
  shard = Shard("m", 0, cfg.n_layers - 1, cfg.n_layers)
  slots, ps = (int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), 64) if not args.cpu else (8, 16)
  mp = pages_to_cover(cfg.max_seq_len, ps)
  n_pages = 1 + args.rows * mp
  pool = init_paged_pool(cfg, cfg.n_layers, n_pages, ps, n_slots=slots)
  rng = np.random.default_rng([args.seed, 11])
  lo, hi = (40, 90) if args.cpu else kind.long_prompt_tokens(hf) if hasattr(kind, "long_prompt_tokens") else (600, 1200) if hasattr(kind, "long_probes") else (500, 1000)
  lens = [int(n) for n in rng.integers(lo, hi + 1, size=args.rows)]
  seqs = [rng.integers(3, cfg.vocab_size, size=n + args.steps) for n in lens]
  use_slots = [int(s) for s in rng.choice(slots, size=args.rows, replace=False)]
  K = 1 << (args.rows - 1).bit_length()
  S = -(-max(lens) // 128) * 128
  tok, bts = np.zeros((K, S), np.int32), np.zeros((K, mp), np.int32)
  prompt_lens, slot_rows = np.ones((K,), np.int32), np.full((K,), slots, np.int32)
  tables = np.zeros((slots, mp), np.int32)
  for i, (n, seq, slot) in enumerate(zip(lens, seqs, use_slots)):
    tok[i, :n], prompt_lens[i], slot_rows[i] = seq[:n], n, slot
    tables[slot] = bts[i] = 1 + i * mp + np.arange(mp)
  last, pool = dec.prefill_into_pages_many_inplace(params, cfg, shard, jnp.asarray(tok), pool, jnp.asarray(bts), jnp.zeros((K,), jnp.int32), jnp.asarray(prompt_lens), ps, None, jnp.asarray(slot_rows))
  got = [[np.asarray(jax.nn.log_softmax(last[i].astype(jnp.float32)))] for i in range(args.rows)]

  if use_kernel:  # as a decode dispatch does, once: a latent model's rope leaf (64 lanes) is otherwise padded and cut back a layer, a step
    from xotorch_support_jetson_tpu.ops.paged import kernel_pool_form

    pool = jax.jit(kernel_pool_form, donate_argnums=0)(pool)
  step = jax.jit(lambda params, tok, pos, pool, active: dec.paged_decode_forward(params, cfg, shard, tok, pos[:, None], pool, jnp.asarray(tables), ps, use_kernel, active=active)[:2], donate_argnums=3)
  active = np.zeros((slots,), bool)
  active[use_slots] = True
  for t in range(args.steps - 1):
    tk, pos = np.zeros((slots, 1), np.int32), np.zeros((slots,), np.int32)
    for n, seq, slot in zip(lens, seqs, use_slots):
      tk[slot, 0], pos[slot] = seq[n + t], n + t
    logits, pool = step(params, jnp.asarray(tk), jnp.asarray(pos), pool, jnp.asarray(active))
    lp = np.asarray(jax.nn.log_softmax(logits[:, 0].astype(jnp.float32)))
    for i, slot in enumerate(use_slots):
      got[i].append(lp[slot])
  served_s = time.perf_counter() - t0

  def against(**probe) -> dict:
    worst, total, count, margin = 0.0, 0.0, 0, 0.0
    per_row = []
    with jax.default_matmul_precision("highest"):
      for i, (n, seq) in enumerate(zip(lens, seqs)):
        ref = np.asarray(jax.nn.log_softmax(kind.reference_forward(params, hf, jnp.asarray(seq[: n + args.steps - 1], jnp.int32), **probe)[n - 1 :], axis=-1))
        mine = np.stack(got[i])
        top = np.argsort(-ref, axis=-1)[:, :64]
        d = np.abs(np.take_along_axis(mine, top, -1) - np.take_along_axis(ref, top, -1))
        m = ref.max(-1) - np.take_along_axis(ref, mine.argmax(-1)[:, None], -1)[:, 0]
        per_row.append({"prompt": n, "max_abs": float(d.max()), "mean_abs": float(d.mean()), "greedy_margin": float(m.max()), "max_abs_last_32": float(d[-32:].max()), "mean_abs_first_32": float(d[:32].mean()), "mean_abs_last_32": float(d[-32:].mean())})
        worst, total, count, margin = max(worst, float(d.max())), total + float(d.sum()), count + d.size, max(margin, float(m.max()))
    return {"max_abs": worst, "mean_abs": total / count, "greedy_margin": margin, "rows": per_row}

  out = {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind}, "config": args.config, "seed": args.seed, "prompts": lens, "steps": args.steps, "slots": slots, "kernel": bool(use_kernel), "served_s": round(served_s, 1), "sound": against()}
  wanted = [name for p in args.probes.split(",") if p for name in (all_probes if p == "all" else [p])]
  all_probes |= getattr(kind, "exact_probes", lambda _hf: {})(hf)  # by name only: what bfloat16 serving cannot tell is no part of "all"
  out["probes"] = {name: {k: v for k, v in against(**all_probes[name]).items() if k != "rows"} for name in wanted}
  out["memory_peak_bytes"] = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
  out["total_s"] = round(time.perf_counter() - t0, 1)
  print(json.dumps(out), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
