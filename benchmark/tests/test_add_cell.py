"""Driven by data: a later PR adds a cell, a traffic mix, a per-layer metric,
a configuration and a whole architecture kind by adding files and entries, and
edits no file. Shown on a copy: the benchmark and the spec are copied to a
scratch checkout (the program linked in), the throw-away things are added as
entries plus new files, and the unchanged ``run.py`` runs them in rehearsal."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent

THROWAWAY_KIND = '''"""A throw-away third kind: the dense maker and reference under limits, probes, rehearsal widths and a byte model of its own."""
import arch_dense_gqa as dense

make_params = dense.make_params
reference_forward = dense.reference_forward
LIMITS = {"mean_abs": 0.04, "max_abs": 0.15, "greedy_margin": 0.08}
LIMITS_WHY = {name: "throw-away" for name in LIMITS}


def probes(hf):
  return {"drop_layer_0": {"drop_layer": 0}}


REHEARSE_WIDTHS = {**dense.REHEARSE_WIDTHS, "num_hidden_layers": 3}
step_weight_bytes = dense.step_weight_bytes


def cache_read_bytes(hf, rows, resident_tokens, kv_quant):
  """Every second layer reads a window of 128 tokens a row: layers that read different amounts."""
  full = dense.cache_read_bytes(hf, rows, resident_tokens, kv_quant)
  return [b if i % 2 else min(b, b / max(resident_tokens, 1) * rows * 128) for i, b in enumerate(full)]


step_matmul_flops = dense.step_matmul_flops
CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"
'''


@pytest.fixture
def checkout(tmp_path):
  """A copy of the benchmark with the program linked in; yields (path, run) and checks afterwards that no file it had was edited."""
  shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__", "tests"))
  os.symlink(ROOT / "xotorch_support_jetson_tpu", tmp_path / "xotorch_support_jetson_tpu")
  before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}

  def run(*argv, timeout=600):
    return subprocess.run([sys.executable, *argv], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=timeout)

  yield tmp_path, run
  assert all(p.read_bytes() == b for p, b in before.items()), "an existing benchmark file was edited"


def add_to_spec(tmp_path, cell: dict, config: str | None = None, per_layer: tuple = (), new_metrics: tuple = ()) -> None:
  """One cell more in the copy's BENCHMARK.json: listed under ``out_tok_s`` and the ``per_layer`` names given, with its configuration's entry and any new metric."""
  spec = json.loads((ROOT / "BENCHMARK.json").read_text())
  spec["workloads"].append({**cell, "chips": 1, "why": "throw-away"})
  if config:
    spec["configs"].append({"name": config, "source": "https://example.org/throw-away", "file": f"benchmark/configs/{config}.json", "reduced": [], "why": "throw-away"})
  for m in spec["end_to_end"] + spec["per_layer"]:
    if m["name"] in ("out_tok_s", *per_layer):
      m["workloads"].append(cell["name"])
  spec["per_layer"] += [{**m, "workloads": [cell["name"]]} for m in new_metrics]
  (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))


def throwaway_config(tmp_path, kind: str) -> None:
  """``configs/throwaway-7b.json``: Mistral's file under another kind."""
  hf = json.loads((tmp_path / "benchmark/configs/mistral-7b-int8.json").read_text())
  hf.update(arch_kind=kind, model_id="bench-throwaway")
  (tmp_path / "benchmark/configs/throwaway-7b.json").write_text(json.dumps(hf))


def small_closed_traffic(tmp_path) -> None:
  traffic = json.loads((tmp_path / "benchmark/traffic/decode-closed.json").read_text())
  traffic.update(clients=4, ramp_s=1, warm={**traffic["warm"], "group_sizes": [1], "anchor_tokens": 200})
  (tmp_path / "benchmark/traffic/throwaway-closed4.json").write_text(json.dumps(traffic))


def rehearse(run, cell: str, wants: dict) -> None:
  for trace, want in wants.items():
    p = run("benchmark/run.py", "--workload", cell, "--seed", "4", "--seconds", "3", "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0 and want in result["metrics"], result
    assert json.loads(p.stderr.strip().splitlines()[-1])["event"] == "compared"  # each number beside its limit, last on stderr


def test_a_cell_is_added_as_files(checkout):
  tmp_path, run = checkout
  small_closed_traffic(tmp_path)
  (tmp_path / "benchmark/layer_metrics/throwaway_requests.py").write_text('def read(ctx):\n  return float(len(ctx["recs"]))\n')
  metric = {"name": "throwaway_requests", "unit": "requests", "better": "higher", "source": "host_clock", "layer": "harness", "moves": "out_tok_s"}
  add_to_spec(tmp_path, {"name": "mistral-7b.throwaway", "config": "mistral-7b-int8", "traffic": "throwaway-closed4"}, new_metrics=(metric,))
  rehearse(run, "mistral-7b.throwaway", {0: "rehearsal.out_tok_s", 1: "rehearsal.throwaway_requests"})


def test_an_architecture_kind_is_added_as_files(checkout):
  """A third kind: ``arch_throwaway.py``, a configuration file that names it, a cell, ``decode_step_roofline`` and
  ``batch_rows_mean`` listed for it. The rehearsal runs its maker, its reference under its limits and its widths; its
  byte model is asked through the shared functions the roofline readers call (a CPU run has no peaks to divide by)."""
  tmp_path, run = checkout
  small_closed_traffic(tmp_path)
  (tmp_path / "benchmark/arch_throwaway.py").write_text(THROWAWAY_KIND)
  throwaway_config(tmp_path, "throwaway")
  add_to_spec(
    tmp_path, {"name": "throwaway-7b.closed4", "config": "throwaway-7b", "traffic": "throwaway-closed4"}, config="throwaway-7b",
    per_layer=("decode_step_roofline", "batch_rows_mean", "paged_attn_roofline.closed"),
  )
  rehearse(run, "throwaway-7b.closed4", {0: "rehearsal.out_tok_s", 1: "rehearsal.batch_rows_mean"})
  p = run("-c", "import sys; sys.path.insert(0, 'benchmark'); import common, flops_bytes as fb, layer_lib; hf = common.load_config('throwaway-7b'); kv = layer_lib.kv_quant({'hf': hf}); "
          "print(kv, fb.decode_step_min_bytes(hf, 16, 12800, kv), fb.paged_attention_min_bytes(hf, 16, 12800, kv), fb.decode_step_flops(hf, 16))")
  assert p.returncode == 0, p.stderr[-2000:]
  kv, step_bytes, call_bytes, flops = p.stdout.split()
  full, windowed = 12800 * 8 * 2 * 132, 16 * 128 * 8 * 2 * 132  # a layer that reads every resident token, and one that reads 128 a row
  assert kv == "int8" and float(call_bytes) == (full + windowed) / 2 and float(flops) == 227830661120.0
  assert float(step_bytes) == 7984914432.0 - 16 * (full - windowed)  # the dense kind's bytes less what the 16 windowed layers leave unread


def test_a_kind_that_lacks_a_part_is_refused_at_load_by_the_name_of_the_part(checkout):
  tmp_path, run = checkout
  (tmp_path / "benchmark/arch_throwaway.py").write_text(THROWAWAY_KIND.replace("step_matmul_flops = dense.step_matmul_flops\n", "").replace('"max_abs": 0.15, ', ""))
  throwaway_config(tmp_path, "throwaway")
  add_to_spec(tmp_path, {"name": "throwaway-7b.closed", "config": "throwaway-7b", "traffic": "decode-closed"}, config="throwaway-7b")
  p = run("benchmark/run.py", "--workload", "throwaway-7b.closed", "--seed", "4", "--seconds", "3", "--trace", "0", "--rehearse", timeout=120)
  assert p.returncode != 0 and p.stdout.strip() == ""
  assert "arch_throwaway.py lacks step_matmul_flops, LIMITS['max_abs']" in p.stderr and '"event": "weights"' not in p.stderr
  throwaway_config(tmp_path, "nowhere")
  p = run("benchmark/run.py", "--workload", "throwaway-7b.closed", "--seed", "4", "--seconds", "3", "--trace", "0", "--rehearse", timeout=120)
  assert p.returncode != 0 and p.stdout.strip() == "" and "no benchmark/arch_nowhere.py" in p.stderr
