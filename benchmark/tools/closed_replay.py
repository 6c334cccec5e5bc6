"""How much of a closed-loop cell's run-to-run variation is the order of its requests?

A replay of the scheduler's tick rule on the CPU: no chip, no program code, no
device number. It answers one question only: with every timing constant held
fixed, how far does the rate move when the seed reorders the same sizes? The
constants are read off chip runs and given on the command line; the rate it
prints is a model's, so only its relative spread is of use.

The rule replayed (``padded_groups``, no mixed ticks): callers whose request
ended are admitted together before the next decode dispatch, as one prefill
program over (group size rounded to a power of two) x (longest prompt padded to
``bucket_tokens``), during which every resident row waits; a decode dispatch is
``chunk`` steps for all resident rows, and its tokens reach the clients when it
ends.

  python benchmark/tools/closed_replay.py --traffic decode-closed --config moonlight-a3b-d14 --seeds 100
"""

from __future__ import annotations

import argparse
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import common  # noqa: E402
from generators import closed  # noqa: E402
from warm import pow2  # noqa: E402


def replay(queue: list[tuple[int, int]], clients: int, ramp_s: float, seconds: float, bucket: int, chunk: int, dispatch_s: float, prefill_s: float, prefill_s_per_token: float) -> float:
  """Tokens per second the clients would count in [ramp_s, ramp_s + seconds)."""
  it = iter(queue)
  t, rows, waiting, got = 0.0, [], [next(it) for _ in range(clients)], []
  while t < ramp_s + seconds:
    if waiting:
      longest = max(-(-p // bucket) * bucket for p, _ in waiting)
      t += prefill_s + prefill_s_per_token * pow2(len(waiting)) * longest
      got += [(t, 1)] * len(waiting)
      rows += [o - 1 for _, o in waiting]
      waiting = []
    t += dispatch_s
    got += [(t, min(chunk, r)) for r in rows]
    waiting = [next(it) for r in rows if r <= chunk]
    rows = [r - chunk for r in rows if r > chunk]
  return sum(n for at, n in got if ramp_s <= at < ramp_s + seconds) / seconds


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
  ap.add_argument("--traffic", default="decode-closed")
  ap.add_argument("--config", default="moonlight-a3b-d14")
  ap.add_argument("--seeds", type=int, default=100)
  ap.add_argument("--seconds", type=float, default=51.0)
  ap.add_argument("--ramp-s", type=float, default=None, help="override the traffic file's ramp")
  ap.add_argument("--chunk", type=int, default=8, help="decode steps a dispatch (XOT_TPU_BATCH_CHUNK)")
  ap.add_argument("--dispatch-s", type=float, default=0.2296, help="one decode dispatch (chip: 8 steps of 28.2 ms + the gap)")
  ap.add_argument("--prefill-s", type=float, default=0.045, help="fixed cost of one prefill dispatch")
  ap.add_argument("--prefill-s-per-token", type=float, default=0.00026, help="per padded token (chip: 259 ms/ktok)")
  args = ap.parse_args()
  traffic = common.load_traffic(args.traffic)
  bucket = int(common.load_config(args.config)["warm_shape_rule"]["bucket_tokens"])
  ramp = float(traffic.get("ramp_s", 0)) if args.ramp_s is None else args.ramp_s
  rates = []
  for seed in range(1, args.seeds + 1):
    plan = closed.plan(traffic, seed, args.seconds + ramp, 1000)
    queue = [(len(r["prompt"]), r["max_tokens"]) for r in plan["queue"]]
    rates.append(replay(queue, plan["clients"], ramp, args.seconds, bucket, args.chunk, args.dispatch_s, args.prefill_s, args.prefill_s_per_token))
  q1, med, q3 = statistics.quantiles(rates, n=4)
  burst = plan["clients"] * args.chunk / args.seconds
  print(f"model only, {args.seeds} orders of one multiset: quartile spread {100 * (q3 - q1) / med:.2f} % of the median, standard deviation {100 * statistics.pstdev(rates) / med:.2f} %, range {100 * (max(rates) - min(rates)) / med:.2f} %; one full decode dispatch is {100 * burst / med:.2f} % of the window's tokens")


if __name__ == "__main__":
  main()
