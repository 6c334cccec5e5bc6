"""Kind ``hybrid_ssm`` and its cell (PR 34), on the CPU: the kind's file is whole, its byte model is the published
sizes' reckoning, every probe moves its reference, its readers read nothing where the program has no such scope,
and the cell's rehearsal runs to a correct line through the unchanged ``run.py``."""

import json
import os
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import arch  # noqa: E402
import common  # noqa: E402
import flops_bytes as fb  # noqa: E402
import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent.parent
CELL, CONFIG = "granite-4.0-h-micro.decode-closed-64", "granite-4.0-h-micro-bf16"


def test_the_configuration_file_holds_the_catalog_rows_keys_and_reduces_nothing():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == [] and hf["source"] == entry["source"] and hf["arch_kind"] == "hybrid_ssm"
  assert (hf["num_hidden_layers"], hf["hidden_size"], hf["vocab_size"], hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]) == (40, 2048, 100352, 64, 64, 128)
  kind = arch.load("hybrid_ssm")
  types = kind.hf_layer_types(hf)
  assert list(types) == hf["layer_types"] and types.count("mamba") == 36 and [i for i, t in enumerate(types) if t == "attention"] == [5, 15, 25, 35]
  with pytest.raises(ValueError, match="does not spell"):
    kind.hf_layer_types({**hf, "layer_pattern": "5,9,9,9,3"})
  cell = common.cell_of(spec, CELL)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "decode-closed-64", 1)
  closed, closed64 = common.load_traffic("decode-closed"), common.load_traffic("decode-closed-64")
  assert closed64["clients"] == 64 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"])
  assert all(closed64[k] == closed[k] for k in ("generator", "ramp_s", "prompt_tokens", "output_tokens")) and closed64["warm"] == {**closed["warm"], "group_sizes_why": closed64["warm"]["group_sizes_why"]}


def test_the_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 34's numbers, from the file: 3.19 G parameters = 6.38 GB, 76.4 MB of state a slot, 8 KB of K/V a token,
  and at 64 rows of ~500 tokens the state update is 59 % of the bytes a decode step must move."""
  hf, kind = common.load_config(CONFIG), arch.load("hybrid_ssm")
  assert round(kind.weight_bytes(hf) / 1e9, 2) == 6.38
  assert round(kind.ssm_state_bytes(hf, 1) / 2 / 1e6, 1) == 76.4  # read + written: twice a slot's state
  per_layer = kind.cache_read_bytes(hf, 64, 64 * 500, "")
  assert len(per_layer) == 40 and per_layer[5] == 64 * 500 * 2 * 8 * 64 * 2 and round(4 * per_layer[5] / (64 * 500)) == 8192
  assert per_layer[0] == kind.ssm_state_bytes(hf, 64) / 36
  step = fb.decode_step_min_bytes(hf, 64, 64 * 500, "")
  share16 = kind.ssm_state_bytes(hf, 16) / fb.decode_step_min_bytes(hf, 16, 16 * 500, "")
  assert 0.59 <= kind.ssm_state_bytes(hf, 64) / step < 0.60 and 0.27 <= share16 < 0.28  # (the convolution rows counted too)
  assert fb.decode_step_flops(hf, 64) == 2.0 * 64 * kind.weight_bytes(hf) / 2
  made = weights.param_shapes(hf)
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf)  # what make_params makes is what a step is said to read


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load("hybrid_ssm").REHEARSE_WIDTHS)
  return hf


def test_every_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change
  of the equations shows); on the chip the limits must refuse each (``run.py --probe-sensitivity``, PERF.md)."""
  hf = _tiny()
  kind = arch.load("hybrid_ssm")
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  names = set(kind.probes(hf))
  assert {"skip_D_dropped", "dt_bias_dropped", "conv_taps_reversed", "rope_on_attention_layers", "attention_scale_inv_sqrt_head_dim", "residual_multiplier_1"} <= names
  assert any(n.startswith("drop_") for n in names) and "recurrent_state_bfloat16" in names  # (the one the limits are known not to refuse)
  for name, kw in kind.probes(hf).items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)


@pytest.mark.parametrize("name", ["decode_ssm_device_ms", "decode_ssm_proj_device_ms", "ssm_state_roofline"])
def test_a_reader_finds_nothing_where_the_program_has_no_such_scope(name, monkeypatch):
  """On a program without state-space layers (the parent; the other kinds) the new readers return None, never a zero
  and never an error: no trace at all, and a trace whose decode programs carry other scopes only."""
  import run
  import span_lib

  reader = run.load_reader("per_layer", f"{name}.closed" if "roofline" not in name else name)
  hf = common.load_config(CONFIG)
  assert reader.read({"hf": hf, "trace": None, "chunk": 8}) is None
  other = {"scope_s": {"attn": 1.0, "ffn": 2.0}, "scoped": True, "decode": {"executions": 10.0, "device_s": 3.0}, "dequant_s": 0.0}
  monkeypatch.setattr(span_lib, "capture", lambda ctx: other)
  assert reader.read({"hf": hf, "trace": {"programs": {}}, "chunk": 8, "peaks": {"hbm_bytes_per_s": 819e9}}) is None
  with_scope = {**other, "scope_s": {"ssm": 0.8, "ssm_proj": 0.4}}
  monkeypatch.setattr(span_lib, "capture", lambda ctx: with_scope)
  ctx = {"hf": hf, "trace": {"programs": {}}, "chunk": 8, "peaks": {"hbm_bytes_per_s": 819e9}, "cap_start": 0.0, "cap_end": 1.0, "recs": []}
  got = reader.read(ctx)
  assert got == {"decode_ssm_device_ms": 10.0, "decode_ssm_proj_device_ms": 5.0, "ssm_state_roofline": 0.0}[name]  # no resident row: no bytes


def test_the_attention_layers_roofline_counts_one_attention_layers_pages():
  """``paged_attn_layers_roofline``: the kernel's bytes a call are ONE attention layer's K/V of the resident tokens
  (not the mean over all 40 layers, 36 of which move state and no page); None without the kernel in the trace (the
  parent serves no such model; a rehearsal has no device plane) and for a kind that names no per-layer types."""
  import run
  from client import Rec

  reader = run.load_reader("per_layer", "paged_attn_layers_roofline")
  assert reader.KERNELS == ("paged_decode",)
  hf = common.load_config(CONFIG)
  rec = Rec(0.0, 400, 256)
  rec.first, rec.events = 0.1, [(0.1, 1), (0.2, 99)]
  ctx = {"hf": hf, "peaks": {"hbm_bytes_per_s": 819e9}, "cap_start": 0.0, "cap_end": 1.0, "recs": [rec], "trace": {"kernels": {}}}
  assert reader.read(ctx) is None and reader.read({**ctx, "trace": None}) is None
  ctx["trace"] = {"kernels": {"paged_decode": {"calls": 4, "device_s": 4 * 1e-5}}}
  one_layer = 500 * 2 * 8 * 64 * 2  # 500 resident tokens x (K and V) x 8 heads x 64 x bfloat16
  assert reader.read(ctx) == pytest.approx(100.0 * (one_layer / 819e9) / 1e-5)
  assert reader.read({**ctx, "hf": common.load_config("mistral-7b-int8")}) is None


def test_the_cells_rehearsal_ends_correct_with_no_failed_request(tmp_path):
  """``run.py --rehearse --workload granite-4.0-h-micro.decode-closed-64``: 64 callers through the API, the scheduler,
  ``prefill.*`` and ``decode.paged_batch`` at tiny widths; the line is ``correct`` with ``failed`` 0 and holds the
  cell's per-layer names a CPU run can read."""
  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
  p = subprocess.run(
    [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3000000019", "--seconds", "4", "--trace", "1", "--rehearse"],
    cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
  )
  assert p.returncode == 0, p.stderr[-3000:]
  result = json.loads(p.stdout.strip().splitlines()[-1])
  assert result["correct"] and result["failed"] == 0 and result["attempted"] > 0, result
  assert {"rehearsal.batch_rows_mean", "rehearsal.ttft_p50_ms.closed", "rehearsal.window_compiles.closed"} <= set(result["metrics"]), result["metrics"]
  compared = json.loads(p.stderr.strip().splitlines()[-1])
  assert compared["event"] == "compared" and compared["correct"]
  assert "keep a recurrent state per slot" in p.stdout
