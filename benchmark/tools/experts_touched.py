"""Which experts a decode step of a cell really touches, against the count the rooflines divide by. A builder's tool
(ISSUE 39): no cell runs it and it reports no metric.

``flops_bytes.experts_touched`` counts the expected distinct experts that the resident rows choose under the topic
router the configuration file states. That expectation stands on two assumptions: that decoded tokens spread over the
topics as uniform draws do, and that a token's topic fixes its experts in every expert layer. This tool reads both on
the served path. It brings the cell's stack up as ``run.py`` does (the file's serving environment, weights from the
seed, ``serve.Stack``), sends the cell's traffic file from its own callers with the streamed text kept, and once a
second takes the rows resident at that instant (``layer_lib.resident``'s rule: from a request's first token until its
last) with the last token each has received. From the seed and the file alone it draws ``topic_of`` and every expert
layer's ownership table as ``make_params`` drew them (the kind's ``router_tables``: the same function), and prints for
each expert layer the exact number of distinct counted experts that those rows' topics own, beside the expectation at
those rows. Then, for the second assumption, one kept request's prompt and answer go through the kind's plain
reference, and the experts its router chose at each position are held against the topic's own.

  python benchmark/tools/experts_touched.py --workload ling-3.0-flash.decode-closed-64 --seed 7 --seconds 60

The last line of stdout is one JSON object; ``--rehearse`` is the CPU mode at the rehearsal widths (control flow only).
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import arch  # noqa: E402
import client  # noqa: E402
import common  # noqa: E402
import flops_bytes  # noqa: E402
from tokenizer import token_id  # noqa: E402

FIRST_TOKENS_LIMIT_S = 900.0  # nothing is warmed: the callers' first prompts compile their prefill programs


def log(**kw) -> None:
  print(json.dumps(kw, default=str), file=sys.stderr, flush=True)


def distinct_owned(owns, topic_of, last_tokens, first: int, counted: int):
  """[expert layers] distinct counted experts that the topics of ``last_tokens`` own; ``owns`` [layers, T, E] bool."""
  topics = sorted({int(topic_of[t]) for t in last_tokens})
  return owns[:, topics, first : first + counted].any(axis=1).sum(axis=1)


async def serve_and_sample(session, stack, plan: dict, seconds: float, every: float) -> tuple[list[list[int]], list]:
  """The callers' loop with the text kept; returns the resident rows' last tokens at each sampled instant, and the
  (record, prompt) pairs of every request sent."""
  sent: list = []
  it = iter(plan["queue"])
  stop = asyncio.Event()

  async def caller() -> None:
    for r in it:
      if stop.is_set():
        return
      rec = client.Rec(client.now(), len(r["prompt"]), r["max_tokens"])
      sent.append((rec, r["prompt"]))
      await client.stream_chat(session, stack.url, stack.model_id, r["prompt"], r["max_tokens"], rec, keep_text=True)

  tasks = [asyncio.create_task(caller()) for _ in range(plan["clients"])]
  try:
    t0 = time.perf_counter()
    while len(sent) < plan["clients"] or any(rec.first is None and rec.error is None for rec, _ in sent[: plan["clients"]]):
      if time.perf_counter() - t0 > FIRST_TOKENS_LIMIT_S:
        raise RuntimeError(f"the callers' first requests had no first token after {FIRST_TOKENS_LIMIT_S:.0f} s")
      await asyncio.sleep(0.05)
    log(event="first_tokens", seconds=round(time.perf_counter() - t0, 1))
    instants = []
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
      await asyncio.sleep(every)
      instants.append([token_id(rec.text[-1].split()[-1]) for rec, _ in sent if rec.first is not None and rec.error is None and rec.tokens < rec.max_tokens])
  finally:
    stop.set()
    for t in tasks:
      t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
  return instants, sent


def routes_as_owned(kind, params, hf: dict, owns, topic_of, sent: list, n_tokens: int, first: int, counted: int) -> dict:
  """One kept request (the shortest prompt with the most text) through the plain reference: the share of (position,
  expert layer) pairs where the router chose exactly the topic's own experts, and where it chose the same COUNTED ones."""
  done = [(rec, prompt) for rec, prompt in sent if rec.error is None and rec.tokens >= 16]
  if not done:
    return {}
  rec, prompt = min(done, key=lambda rp: (len(rp[1]), -rp[0].tokens))
  tokens = np.asarray([int(t) for t in prompt] + [token_id(w) for w in " ".join(rec.text).split()], np.int64)[:n_tokens]
  routed: list = []
  kind.reference_forward(params, hf, tokens, routed=routed)
  chose = np.stack([np.asarray(r) for r in routed])  # [layers, S, E]
  own = owns[:, topic_of[tokens], :]
  held = slice(first, first + counted)
  return {
    "tokens": int(len(tokens)), "prompt_tokens": int(min(len(prompt), n_tokens)),
    "chose_the_topics_own": float((chose == own).all(axis=2).mean()), "chose_the_topics_own_counted": float((chose[..., held] == own[..., held]).all(axis=2).mean()),
    "counted_touched_by_the_router": [int(n) for n in chose[..., held].any(axis=1).sum(axis=1)], "counted_owned_by_the_topics": [int(n) for n in own[..., held].any(axis=1).sum(axis=1)],
  }


async def main_async(args) -> int:
  import run  # the cell's set-up pieces, unchanged: rehearsal widths, the device check

  spec = common.load_spec()
  cell = common.cell_of(spec, args.workload)
  hf, traffic = common.load_config(cell["config"]), common.load_traffic(cell["traffic"])
  if args.rehearse:
    os.environ["JAX_PLATFORMS"] = "cpu"
  kind = arch.load(hf["arch_kind"])
  if not hasattr(kind, "router_tables") or not int(hf.get("router_topics") or 0):
    raise SystemExit(f"{cell['config']}: no topic router to read (the kind has no router_tables, or the file states no router_topics)")
  if args.rehearse:
    run.rehearsal_shrink(hf, traffic, kind.REHEARSE_WIDTHS)

  import serve

  serve.apply_serving_env(hf)

  import aiohttp
  import jax

  import weights
  from tokenizer import WordTokenizer
  from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache

  cache_dir = configure_compile_cache()
  info, _peaks = run.device_info(int(cell["chips"]), args.rehearse)
  log(event="devices", **info, compile_cache=cache_dir, workload=args.workload, seed=args.seed, rehearsal=args.rehearse)
  gen = importlib.import_module(f"generators.{traffic['generator']}")
  vocab = int(hf["vocab_size"])
  plan = gen.plan(traffic, args.seed, float(args.seconds), vocab)
  if plan["mode"] != "closed":
    raise SystemExit("experts_touched.py reads a closed loop's resident rows; this cell's traffic is an open loop")

  shapes = weights.shape_hf(hf)
  tables = jax.jit(lambda k: kind.router_tables(shapes, k))(weights.seed_key(args.seed))
  owns, topic_of = np.asarray(tables["owns"]) > 0, np.asarray(tables["topic_of"])
  params = weights.build_params(hf, args.seed)
  jax.block_until_ready(params)
  stack = serve.Stack(hf, common.model_config(hf), params, WordTokenizer(vocab))
  stack.start()
  try:
    async with aiohttp.ClientSession(connector=aiohttp.TCPConnector(limit=0), timeout=aiohttp.ClientTimeout(total=None)) as session:
      instants, sent = await serve_and_sample(session, stack, plan, float(args.seconds), float(args.every))
    first, counted, routed, top_k = kind.routed_experts(hf)
    T = int(hf["router_topics"])
    instants = [i for i in instants if i]
    exact = np.asarray([distinct_owned(owns, topic_of, i, first, counted) for i in instants], float)  # [instants, layers]
    rows = np.asarray([len(i) for i in instants], float)
    expected = float(np.mean([flops_bytes.experts_touched(hf, counted, routed, top_k, r) for r in rows]))
    for layer in range(exact.shape[1]):
      print(json.dumps({"expert_layer": layer, "exact_mean": float(exact[:, layer].mean()), "exact_min": float(exact[:, layer].min()), "exact_max": float(exact[:, layer].max()), "expected": expected}), flush=True)
    result = {
      "workload": args.workload, "seed": args.seed, "device": info, "rehearsal": bool(args.rehearse), "instants": len(instants), "rows_mean": float(rows.mean()),
      "topics_distinct_mean": float(np.mean([len({int(topic_of[t]) for t in i}) for i in instants])), "topics_distinct_expected": float(np.mean([T * (1 - (1 - 1 / T) ** r) for r in rows])),
      "counted": counted, "exact_mean": float(exact.mean()), "expected": expected, "exact_over_expected": float(exact.mean() / expected),
      "uniform_routing_would_count": float(np.mean([flops_bytes.expected_distinct_experts(routed, top_k, r) * counted / routed for r in rows])),
      "requests_sent": len(sent), "requests_failed": sum(1 for rec, _ in sent if rec.error is not None or rec.status not in (None, 200)),
      "router": routes_as_owned(kind, params, hf, owns, topic_of, sent, int(args.route_tokens), first, counted) if args.route_tokens else {},
    }
    print(json.dumps(result), flush=True)
  finally:
    stack.stop()
  return 0


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, default=60.0, help="how long rows are sampled once every caller has its first token")
  ap.add_argument("--every", type=float, default=1.0, help="seconds between sampled instants")
  ap.add_argument("--route-tokens", type=int, default=256, help="positions of one kept request walked through the plain reference (0: skip)")
  ap.add_argument("--rehearse", action="store_true", help="CPU, tiny widths: control flow only")
  args = ap.parse_args()
  rc = asyncio.run(main_async(args))
  sys.stdout.flush()
  sys.stderr.flush()
  os._exit(rc)  # as run.py: the engine's worker threads are not daemons


if __name__ == "__main__":
  main()
