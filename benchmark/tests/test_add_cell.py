"""Driven by data: a later PR adds a cell, a traffic mix and a per-layer metric
by adding files and entries, and edits no file. Shown on a copy: the benchmark
and the spec are copied to a scratch checkout (the program linked in), a
throw-away cell is added as one entry plus two new files, and the unchanged
``run.py`` runs it in rehearsal."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent


def test_a_cell_is_added_as_files(tmp_path):
  shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__", "tests"))
  os.symlink(ROOT / "xotorch_support_jetson_tpu", tmp_path / "xotorch_support_jetson_tpu")
  before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}

  traffic = json.loads((tmp_path / "benchmark/traffic/decode-closed.json").read_text())
  traffic.update(clients=4, ramp_s=1, warm={**traffic["warm"], "group_sizes": [1], "anchor_tokens": 200})
  (tmp_path / "benchmark/traffic/throwaway-closed4.json").write_text(json.dumps(traffic))
  (tmp_path / "benchmark/layer_metrics/throwaway_requests.py").write_text('def read(ctx):\n  return float(len(ctx["recs"]))\n')
  spec = json.loads((ROOT / "BENCHMARK.json").read_text())
  spec["workloads"].append({"name": "mistral-7b.throwaway", "config": "mistral-7b-int8", "traffic": "throwaway-closed4", "chips": 1, "why": "throw-away"})
  for m in spec["end_to_end"]:
    if m["name"] == "out_tok_s":
      m["workloads"].append("mistral-7b.throwaway")
  spec["per_layer"].append({"name": "throwaway_requests", "unit": "requests", "better": "higher", "source": "host_clock", "layer": "harness", "moves": "out_tok_s", "workloads": ["mistral-7b.throwaway"]})
  (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
  for trace, want in ((0, "rehearsal.out_tok_s"), (1, "rehearsal.throwaway_requests")):
    p = subprocess.run(
      [sys.executable, "benchmark/run.py", "--workload", "mistral-7b.throwaway", "--seed", "4", "--seconds", "3", "--trace", str(trace), "--rehearse"],
      cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0 and want in result["metrics"], result
  assert all(p.read_bytes() == b for p, b in before.items()), "an existing benchmark file was edited"
