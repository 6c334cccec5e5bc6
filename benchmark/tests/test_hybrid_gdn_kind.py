"""Kind ``hybrid_gdn`` and its cell (PR 44), on the CPU: the kind loads whole, the configuration file holds the catalog
row's keys and states its cut, what the maker makes is what the byte model counts and both are ISSUE 44's hand counts,
every probe moves its reference, the kind is found by name alone, and the cell's rehearsal runs to a correct line
through the unchanged ``run.py``."""

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
CELL, CONFIG, KIND = "olmo-hybrid-7b.decode-closed-64", "olmo-hybrid-7b-d12", "hybrid_gdn"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
HYBRID_CELLS = ("granite-4.0-h-micro.decode-closed-64", "ling-3.0-flash.decode-closed-64")


def test_the_gdn_kind_loads_with_every_part_and_is_found_by_name_alone():
  """All of ``arch.PARTS`` and the three limits, the two parts the rooflines ask for by ``getattr``; and no shared file of
  the harness names the kind: it came as files and entries (``test_add_cell.py``'s promise)."""
  kind = arch.load(KIND)
  assert all(hasattr(kind, part) for part in arch.PARTS) and len(arch.PARTS) == 10
  assert set(kind.LIMITS) == set(kind.LIMITS_WHY) == set(arch.LIMIT_NAMES) and all(len(why) > 80 for why in kind.LIMITS_WHY.values())
  assert callable(kind.ssm_state_bytes) and callable(kind.hf_layer_types) and not hasattr(kind, "moe_expert_bytes")
  bench = ROOT / "benchmark"
  shared = [p for p in [*bench.glob("*.py"), *bench.glob("layer_metrics/*.py"), *bench.glob("end_to_end/*.py"), *bench.glob("generators/*.py"), *bench.glob("tools/*.py")] if p.name != f"arch_{KIND}.py"]
  assert len(shared) > 40 and not [p.name for p in shared if KIND in p.read_text() or "olmo" in p.read_text().lower()]


def test_the_gdn_configuration_file_states_its_cut():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", "layer_types"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"]["num_hidden_layers"] == 32 and hf["num_hidden_layers"] == 12 == len(hf["layer_types"])
  assert hf["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 3  # three WHOLE periods, in the published order
  assert "three-chip ring" in hf["stands_for"] and "12 + 12 + 8" in hf["stands_for"]
  # every reading the row does not state is written down
  assert {"torch_dtype", "block_norms", "rope_theta_null", "qk_norm", "gdn_equations", "recurrent_state", "weights", "post_norm_gain", "q_norm_k_norm_gain", "wq_wk_head_scales"} <= set(hf["assumed"])
  kind = arch.load(KIND)
  assert kind.hf_layer_types(hf) == (("gdn",) * 3 + ("attention",)) * 3 == kind.hf_layer_types(weights.shape_hf(hf))  # from the scalar keys alone, as the maker sees the file
  with pytest.raises(ValueError, match="full_attention_interval"):
    kind.hf_layer_types({**hf, "full_attention_interval": 3})
  cell = common.cell_of(spec, CELL)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "decode-closed-64", 1)
  assert common.load_traffic("decode-closed-64")["clients"] == 64 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])
  assert int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"]) >= 1217  # not below what 64 rows at the traffic's lengths hold (19 pages a row + the trash page)
  # the cell reports what the other two hybrid cells report and it can: every metric both of them list, and the paged kernel's roofline
  listed = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ())}
  both = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if all(c in m.get("workloads", ()) for c in HYBRID_CELLS)}
  assert both <= listed and {"paged_attn_layers_roofline", "ssm_state_roofline", "decode_step_roofline", "decode_ssm_device_ms.closed", "decode_ssm_proj_device_ms.closed", "out_tok_s"} <= listed
  assert "moe_experts_roofline" not in listed


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_gdn_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Olmo-Hybrid-7B")
  hf = common.load_config(CONFIG)
  assert hf["source"] == row["source_url"]
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  assert hf["published"]["num_hidden_layers"] == row["config"]["num_hidden_layers"] and row["config"]["layer_types"] == hf["layer_types"][:4] * 8
  # ... and the row maps through config_from_hf without an edit to its keys
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf(row["config"])
  assert (cfg.n_layers, cfg.recurrent_layers, cfg.recurrent_kind, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state) == (32, 24, "gdn", 30, 192, 96)


def test_the_gdn_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 44's arithmetic, from the file: a Gated-DeltaNet mixer 88.75 M parameters, an attention mixer 58.99 M, an MLP
  126.81 M; a linear layer 215.6 M, a full layer 185.8 M, embedding + head 770.7 M; 3,268 M in all = 6.54 GB; a slot's
  state 2.21 MB a layer; 64 rows' state read and written 2 x 1.274 GB + the convolution rows; 46,080 B of K/V a token
  over the three attention layers; 5.77 GB of weights a step; the new layers' weights and state 66 % of a step's least
  bytes at 64 rows of ~500 tokens, the MHA pages 15 %."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  gdn, attn = kind._layer_params(hf)
  mlp = 3 * 3840 * 11008 + 3840
  assert [round(x / 1e6, 2) for x in (gdn - mlp, attn - mlp, mlp)] == [88.75, 58.99, 126.82]  # (the two norms of a block are counted with its sublayers)
  assert [round(x / 1e6, 1) for x in (gdn, attn, 2 * 100352 * 3840 + 3840)] == [215.6, 185.8, 770.7]
  made = weights.param_shapes(hf)
  n_params = sum(x.size for x in jax.tree.leaves(made))
  assert n_params == pytest.approx(3268e6, rel=1e-3) and n_params == 9 * (gdn + 60) + 3 * attn + 2 * 100352 * 3840 + 3840
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf) and round(kind.weight_bytes(hf) / 1e9, 2) == 6.54  # what make_params makes is what is counted
  assert made["ssm_layers"]["A_log"].dtype == made["ssm_layers"]["dt_bias"].dtype == np.float32 and made["ssm_layers"]["w_qkv"].shape == (9, 3840, 11520) and made["layers"]["q_norm"].shape == (3, 3840)
  state = 30 * 192 * 96 * 4
  assert state == 2211840 and kind.ssm_state_bytes(hf, 1) == 9 * 2 * (state + 3 * 11520 * 2)
  assert round(9 * 64 * state / 1e9, 3) == 1.274 and kind.ssm_state_bytes(hf, 64) == 2 * 9 * 64 * state + 2 * 9 * 64 * 3 * 11520 * 2 and round(kind.ssm_state_bytes(hf, 64) / 1e9, 2) == 2.63
  per_layer = kind.cache_read_bytes(hf, 64, 64 * 500, "")
  assert len(per_layer) == 12 and per_layer[3] == per_layer[7] == per_layer[11] == 64 * 500 * 30 * 2 * 128 * 2 and per_layer[0] == per_layer[10] == kind.ssm_state_bytes(hf, 64) / 9
  assert sum(kind.cache_read_bytes(hf, 1, 1, "")[3::4]) == 46080 and round(3 * per_layer[3] / 1e9, 2) == 1.47
  assert kind.step_weight_bytes(hf, 64) == kind.step_weight_bytes(hf, 1) == kind.weight_bytes(hf) - 100352 * 3840 * 2 and round(kind.step_weight_bytes(hf, 64) / 1e9, 2) == 5.77
  step = fb.decode_step_min_bytes(hf, 64, 64 * 500, "")
  gdn_mixers = 9 * (gdn - mlp) * 2  # the share ISSUE 44 names "the Gated-DeltaNet layers' weights": with their MLPs, 9 x 215.6 M x 2 B
  assert round(step / 1e9, 1) == 9.9 and round(9 * gdn * 2 / 1e9, 2) == 3.88 and gdn_mixers < 9 * gdn * 2
  assert 0.65 < (9 * gdn * 2 + kind.ssm_state_bytes(hf, 64)) / step < 0.67 and 0.26 < kind.ssm_state_bytes(hf, 64) / step < 0.28 and 0.14 < 3 * per_layer[3] / step < 0.16
  assert fb.decode_step_flops(hf, 64) == 2.0 * 64 * (n_params - 9 * 60 - 100352 * 3840 - 3840) and kind.CACHE_TYPE_ENV == "XOT_TPU_KV_QUANT"
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, 64), step, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
  assert bound == "memory" and 12.0 < t * 1e3 < 12.1


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_gdn_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each (``run.py --probe-sensitivity``, PERF.md section 6). The
  rehearsal widths keep what the published ones force: N != P and a head count that is no power of two."""
  hf, kind = _tiny(), arch.load(KIND)
  assert hf["linear_key_head_dim"] != hf["linear_value_head_dim"] and hf["linear_num_value_heads"] & (hf["linear_num_value_heads"] - 1) and set(kind.hf_layer_types(hf)) == {"gdn", "attention"}
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_last_layer", "no_decay", "no_delta", "beta_unscaled", "gate_before_norm", "per_head_qk_norm", "pre_norm", "rope_on", "recurrent_state_bfloat16", "decay_bfloat16", "float8_matmul_operands"}
  assert set(kind.probes(hf)) == want
  for name, kw in kind.probes(hf).items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)


def test_the_gdn_cells_rehearsal_ends_correct_with_no_failed_request(tmp_path):
  """``run.py --rehearse --workload olmo-hybrid-7b.decode-closed-64``: 64 callers through the API, the scheduler,
  ``prefill.*`` and ``decode.paged_batch`` at tiny widths; the line is ``correct`` with ``failed`` 0 and holds the cell's
  per-layer names a CPU run can read."""
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
