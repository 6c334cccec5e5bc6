"""What every part of the benchmark shares: where its files are, the spec, and
how a configuration file becomes the program's ``ModelConfig``."""

from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
  if p not in sys.path:
    sys.path.insert(0, p)


def load_spec() -> dict:
  return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_of(spec: dict, name: str) -> dict:
  for w in spec["workloads"]:
    if w["name"] == name:
      return w
  raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has {[w['name'] for w in spec['workloads']]}")


def load_config(name: str) -> dict:
  return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
  return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def metric_names(spec: dict, group: str, cell: str) -> list[dict]:
  """The metrics of ``group`` ("end_to_end" | "per_layer") that ``cell`` reports."""
  return [m for m in spec[group] if "workloads" not in m or cell in m["workloads"]]


def model_config(hf: dict):
  """The program's ModelConfig for a configuration file: the published keys
  through ``config_from_hf``, the serving window as the engine would clamp it
  on a checkpoint load, and no EOS (answers run to ``max_tokens``)."""
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf({k: v for k, v in hf.items() if not isinstance(v, dict) or k == "rope_scaling"})
  return replace(cfg, max_seq_len=min(int(hf["serving_window_tokens"]), cfg.max_seq_len), eos_token_ids=())
