"""Open loop, Poisson arrivals at a fixed rate. Due times do not depend on
completions. The gaps are the mid-quantiles of the exponential distribution in
a seeded order, so every seed offers the same load in another order."""

from __future__ import annotations

import math

import numpy as np

from . import sizes


def _phase(params: dict, rng, start_s: float, seconds: float, vocab: int) -> list[dict]:
  rate = float(params["rate_rps"])
  n = max(int(round(rate * seconds)), 1)
  gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
  gaps = [gaps[i] for i in rng.permutation(n)]
  scale = seconds / sum(gaps)  # the n arrivals span the phase exactly
  prompts = sizes.stratified(params["prompt_tokens"], n, rng, n)
  outs = sizes.stratified(params["output_tokens"], n, rng, n)
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
