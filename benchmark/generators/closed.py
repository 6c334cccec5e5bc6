"""Closed loop: ``clients`` callers, each sends its next request when the last
one ends. The request list is one shared queue in a seeded order, stratified in
blocks of ``clients`` so that any prefix is a fair sample of the sizes."""

from __future__ import annotations

import numpy as np

from . import sizes


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
  rng = np.random.default_rng([int(seed), 2])
  clients = int(params["clients"])
  horizon = float(params.get("ramp_s", 0)) + seconds
  # Far more than the window can consume: a request lasts at least min output x ~10 ms.
  n = clients * max(int(horizon / (params["output_tokens"]["min"] * 0.01)) + 2, 4)
  prompts = sizes.stratified(params["prompt_tokens"], n, rng, clients)
  outs = sizes.stratified(params["output_tokens"], n, rng, clients)
  return {
    "mode": "closed",
    "clients": clients,
    "queue": [{"prompt": sizes.prompt_ids(rng, p, vocab), "max_tokens": o} for p, o in zip(prompts, outs)],
    "ramp_s": float(params.get("ramp_s", 0)),
  }


def prompt_lengths(plan: dict) -> list[int]:
  """Every prompt length the plan may send (the warm-up's list)."""
  return [len(r["prompt"]) for r in plan["queue"]]
