"""Sizes shared by the generators: a distribution's stratified sample (the same
multiset for every seed, in a seeded order) and the prompts themselves."""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec: dict, n: int) -> list[int]:
  """The n mid-quantiles of a size distribution, clipped to [min, max] and
  rounded to whole tokens: one fixed multiset per (spec, n). Seeds reorder it;
  they never change the work."""
  if spec["dist"] == "lognormal":
    raw = [spec["median"] * math.exp(spec["sigma"] * NormalDist().inv_cdf((i + 0.5) / n)) for i in range(n)]
  elif spec["dist"] == "fixed":
    raw = [spec["value"]] * n
  elif spec["dist"] == "uniform":
    raw = [spec["min"] + (spec["max"] - spec["min"]) * (i + 0.5) / n for i in range(n)]
  else:
    raise ValueError(f"unknown size distribution {spec['dist']!r}")
  return [min(max(int(round(x)), int(spec["min"])), int(spec["max"])) for x in raw]


def stratified(spec: dict, n: int, rng: np.random.Generator, block: int) -> list[int]:
  """n sizes in blocks of ``block`` mid-quantiles, each block shuffled, so any
  prefix of the list is a fair sample whatever the seed."""
  out: list[int] = []
  while len(out) < n:
    q = quantiles(spec, block)
    out.extend(int(q[i]) for i in rng.permutation(block))
  return out[:n]


def prompt_ids(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
  return rng.integers(3, vocab, size=n, dtype=np.int64)
