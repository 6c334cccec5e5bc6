"""Open loop, Poisson arrivals at a fixed rate. Due times do not depend on
completions. The gaps are the mid-quantiles of the exponential distribution and
the sizes the mid-quantiles of theirs, so every seed offers the same load.

The order is the traffic file's: gaps, prompts and answers are each shuffled
once, by ``order_seed``, and a seed starts that one cycle at a position it
draws and wraps round. Every seed sends the same (gap, prompt, answer) triples
beside the same neighbours, but for the two at the wrap. (Until PR 39 the seed
shuffled all three: a statistic over a few dozen requests then follows which
long prompts the shuffle landed on which answers - the median time per token of
29 requests read 3.4 % apart on two seeds whose runs each repeat to 0.1 %.)"""

from __future__ import annotations

import math

import numpy as np

from . import sizes


def _turned(values: list, order: np.random.Generator, first: int) -> list:
  """``values`` shuffled by the file's ``order``, started at position ``first`` and wrapped round."""
  placed = [values[i] for i in order.permutation(len(values))]
  return placed[first:] + placed[:first]


def _phase(params: dict, rng, start_s: float, seconds: float, vocab: int) -> list[dict]:
  rate = float(params["rate_rps"])
  n = max(int(round(rate * seconds)), 1)
  gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
  scale = seconds / sum(gaps)  # the n arrivals span the phase exactly
  order = np.random.default_rng([int(params.get("order_seed", 0)), n])
  first = int(rng.integers(n))
  gaps, prompts, outs = (_turned(v, order, first) for v in (gaps, sizes.quantiles(params["prompt_tokens"], n), sizes.quantiles(params["output_tokens"], n)))
  t, reqs = start_s, []
  for g, p, o in zip(gaps, prompts, outs):
    reqs.append({"due_s": t, "prompt": sizes.prompt_ids(rng, p, vocab), "max_tokens": o})
    t += g * scale
  return reqs


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
  rng = np.random.default_rng([int(seed), 1])
  ramp = float(params.get("ramp_s", 0))
  return {
    "mode": "open",
    "ramp": _phase(params, rng, 0.0, ramp, vocab) if ramp > 0 else [],
    "window": _phase(params, rng, ramp, seconds, vocab),
    "ramp_s": ramp,
    "drain_s": float(params.get("drain_s", 30)),
  }


def prompt_lengths(plan: dict) -> list[int]:
  """Every prompt length the plan will send (the warm-up's list)."""
  return [len(r["prompt"]) for r in (*plan["ramp"], *plan["window"])]
