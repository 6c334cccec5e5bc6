"""Kind ``swa_gqa_moe`` and its cell (PR 46), on the CPU: the kind loads whole, the configuration file holds the catalog
row's keys and states its cut, what the maker makes is what the byte model counts and both are ISSUE 46's hand counts,
every probe moves its reference, the windowed call's roofline reads what a perfect and what a window-blind kernel would
give it, and the kind and the reader came as files and entries (``test_add_cell.py``'s promise). The cell's rehearsal
(``run.py --rehearse``: 64 callers, ~4 minutes on this CPU) is run by hand, not here."""

import importlib.util
import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

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
CELL, CONFIG, KIND, TRAFFIC = "laguna-xs.2.agent-closed-64", "laguna-xs.2-d5", "swa_gqa_moe", "agent-closed-64"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
LISTS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")


def _reader(name: str):
  spec = importlib.util.spec_from_file_location(f"per_layer_{name}", ROOT / "benchmark" / "layer_metrics" / f"{name}.py")
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_the_swa_kind_loads_with_every_part_and_is_found_by_name_alone():
  """All of ``arch.PARTS`` and the three limits, and what readers and tools ask for by ``getattr``; no shared file of the
  harness names the kind or the model, and the new reader names neither: they came as files and entries."""
  kind = arch.load(KIND)
  assert all(hasattr(kind, part) for part in arch.PARTS) and len(arch.PARTS) == 10
  assert set(kind.LIMITS) == set(kind.LIMITS_WHY) == set(arch.LIMIT_NAMES) and all(len(why) > 80 for why in kind.LIMITS_WHY.values())
  assert all(callable(getattr(kind, name)) for name in ("moe_expert_bytes", "routed_experts", "router_tables", "hf_layer_types", "hf_attention_kinds", "long_probes")) and not hasattr(kind, "ssm_state_bytes")
  bench = ROOT / "benchmark"
  shared = [p for p in [*bench.glob("*.py"), *bench.glob("layer_metrics/*.py"), *bench.glob("end_to_end/*.py"), *bench.glob("generators/*.py"), *bench.glob("tools/*.py")] if p.name != f"arch_{KIND}.py"]
  assert len(shared) > 40 and not [p.name for p in shared if KIND in p.read_text() or "laguna" in p.read_text().lower()]


def test_the_swa_configuration_file_states_its_cut():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", *LISTS] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"]["num_hidden_layers"] == 40 and hf["num_hidden_layers"] == 5 == len(hf["layer_types"]) == len(hf["mlp_layer_types"]) == len(hf["num_attention_heads_per_layer"])
  assert hf["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"] and hf["mlp_layer_types"] == ["dense"] + ["sparse"] * 4 and hf["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
  assert "8-chip ring" in hf["stands_for"] and "5 layers a chip" in hf["stands_for"]
  assert {"torch_dtype", "block", "qk_norm", "gating", "router", "hidden_act", "rope", "window", "weights", "router_topics"} <= set(hf["assumed"])  # every reading the row does not state is written down
  assert "33.44 G" in hf["assumed"]["gating"] and "34.07 G" in hf["assumed"]["gating"]  # the parameter count that bears out head-wise
  kind = arch.load(KIND)
  scalars = weights.shape_hf(hf)  # as the maker sees the file: the lists and the nested ropes are gone
  assert not set(LISTS) & set(scalars) and "rope_parameters" not in scalars
  assert kind.hf_attention_kinds(hf) == kind.hf_attention_kinds(scalars) == ("full", "window", "window", "window", "full") and kind.hf_layer_types(hf) == ("attention",) * 5
  assert kind.layer_stacks(scalars) == [("layers", 0), ("window_moe_layers", 0), ("window_moe_layers", 1), ("window_moe_layers", 2), ("moe_layers", 0)]
  for key, value in (("full_attention_interval", 3), ("sliding_attention_heads", 48), ("dense_layers", 2)):
    with pytest.raises(ValueError, match=key):
      kind._sizes({**hf, key: value})
  cell = common.cell_of(spec, CELL)
  traffic = common.load_traffic(TRAFFIC)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and traffic["generator"] == "closed"
  assert traffic["clients"] == 64 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])
  assert (traffic["prompt_tokens"], traffic["output_tokens"]) == ({"dist": "lognormal", "median": 2048, "sigma": 0.5, "min": 512, "max": 4096}, {"dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 256, "max": 2048})
  assert traffic["prompt_tokens"]["min"] >= hf["sliding_window"] and traffic["warm"]["group_sizes"] == [1, 2, 4, 8] and traffic["warm"]["anchor_tokens"] == 6000 and traffic["ramp_s"] == 6
  assert 3073 <= int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"]) <= 4097 and hf["serving_window_tokens"] == 8192 and hf["warm_shape_rule"]["kind"] == "mixed_slices"
  # the cell reports what Mistral's closed cell and Ling's cell report and it can, and the two attention rooflines
  listed = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ())}
  closed = {m["name"] for m in spec["per_layer"] if "mistral-7b.decode-closed" in m.get("workloads", ()) and m["name"] != "paged_attn_roofline.closed"}
  assert closed <= listed and {"out_tok_s", "decode_step_roofline", "moe_experts_roofline", "paged_attn_layers_roofline", "paged_attn_window_roofline"} <= listed
  new = [m for m in spec["per_layer"] if m["name"] == "paged_attn_window_roofline"]
  assert new == [{"name": "paged_attn_window_roofline", "unit": "%", "better": "higher", "source": "device_trace", "layer": "kernels", "moves": "out_tok_s", "workloads": [CELL]}]
  assert spec["per_layer"][-1] == new[0] and spec["workloads"][-1]["name"] == CELL and spec["configs"][-1]["name"] == CONFIG  # additions stand at the end of their lists


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_swa_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Laguna-XS.2")
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  assert hf["source"] == row["source_url"]
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  assert all(hf[k] == row["config"][k][:5] for k in LISTS) and row["config"]["layer_types"] == hf["layer_types"][:4] * 10
  # the 40-layer row counts the row's own 33.4B, with the head-wise gate; an element-wise gate (a q-sized projection) would not
  whole = {**row["config"], "full_attention_interval": 4, "sliding_attention_heads": 64, "dense_layers": 1}
  assert round(kind.param_count(whole) / 1e9, 2) == 33.44 and row["described_as"]["params"].startswith("33.4B")
  elementwise = kind.param_count(whole) + sum(2048 * h * 128 - 2048 * h for h in row["config"]["num_attention_heads_per_layer"])
  assert round(elementwise / 1e9, 2) == 34.07
  # ... and the row maps through config_from_hf without an edit to its keys
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf(row["config"])
  assert (cfg.n_layers, cfg.n_experts, cfg.first_k_dense, len(set(cfg.layer_attn)), cfg.attn_windows[:5]) == (40, 256, 1, 2, (0, 512, 512, 512, 0))


def test_the_swa_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 46's arithmetic, from the file: a full attention mixer 29.46 M parameters, a window mixer 37.88 M, the dense
  MLP 50.33 M, a routed expert 3,145,728 (805.31 M a layer), shared expert + router + norm 3.67 M, embedding + head
  411.04 M; 3,870 M in all = 7.74 GB, to the byte what ``make_params`` makes. At 62 rows of 2.8 k tokens a decode step's
  least bytes: the experts the rows choose 5.56 GB under independent rows (~221 of 256 a layer) and 4.62 under the file's
  topics (~184), the two full layers' K/V 1.42, the three window layers' 0.39 (without the window they would read 2.13:
  17 % of a 10.0 GB step avoidable), the head 0.41."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  p = kind._params(hf)
  assert [round(p[k] / 1e6, 2) for k in ("full", "window", "dense_ffn", "moe_rest", "top")] == [29.46, 37.88, 50.33, 3.67, 411.04] and p["expert"] == 3145728 and round(256 * p["expert"] / 1e6, 2) == 805.31
  made = weights.param_shapes(hf)
  n_params = sum(x.size for x in jax.tree.leaves(made))
  assert n_params == kind.param_count(hf) == pytest.approx(3870e6, rel=1e-3)
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf) and round(kind.weight_bytes(hf) / 1e9, 2) == 7.74  # what make_params makes is what is counted
  assert made["layers"]["wq"].shape == (1, 2048, 48 * 128) and made["window_moe_layers"]["wq"].shape == (3, 2048, 64 * 128) and made["moe_layers"]["w_og"].shape == (1, 2048, 48)
  assert made["window_moe_layers"]["w_experts_gate"].shape == (3, 256, 2048, 512) and made["moe_layers"]["router_bias"].dtype == np.float32 and made["layers"]["w_gate"].shape == (1, 2048, 8192)
  rows, tokens = 62, 62 * 2800
  per_layer = kind.cache_read_bytes(hf, rows, tokens, "")
  assert per_layer == [tokens * 4096, rows * 512 * 4096, rows * 512 * 4096, rows * 512 * 4096, tokens * 4096] and kind.kv_bytes_per_token_layer(hf, "") == 4096
  assert round(2 * per_layer[0] / 1e9, 2) == 1.42 and round(3 * per_layer[1] / 1e9, 2) == 0.39 and round(3 * per_layer[0] / 1e9, 2) == 2.13
  assert kind.cache_read_bytes(hf, 4, 4 * 300, "")[1] == 4 * 300 * 4096  # rows under the window: a window layer reads what they hold
  independent = {k: v for k, v in hf.items() if k != "router_topics"}
  assert kind.routed_experts(hf) == (0, 256, 256, 8) and round(fb.experts_touched(independent, 256, 256, 8, rows)) == 220 and round(fb.experts_touched(hf, 256, 256, 8, rows)) == 184
  assert round(kind.moe_expert_bytes(independent, rows) / 1e9, 2) == 5.54 and round(kind.moe_expert_bytes(hf, rows) / 1e9, 2) == 4.62
  assert kind.moe_expert_bytes(hf, rows) == 4 * fb.experts_touched(hf, 256, 256, 8, rows) * p["expert"] * 2
  outside = kind.step_weight_bytes(hf, rows) - kind.moe_expert_bytes(hf, rows)
  assert 0.47 < (outside - 100352 * 2048 * 2) / 1e9 < 0.48 and round(100352 * 2048 * 2 / 1e9, 2) == 0.41  # projections, dense MLP, shared experts, routers; the head
  step = fb.decode_step_min_bytes(independent, rows, tokens, "")
  assert round(step / 1e9, 1) == 8.2 and 0.16 < 3 * (per_layer[0] - per_layer[1]) / (step + 3 * (per_layer[0] - per_layer[1])) < 0.18
  assert fb.decode_step_flops(hf, rows) == 2.0 * rows * (2 * p["full"] + 3 * p["window"] + p["dense_ffn"] + 4 * (p["moe_rest"] + 8 * p["expert"]) + p["top"] / 2) and kind.CACHE_TYPE_ENV == "XOT_TPU_KV_QUANT"
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, rows), step, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
  assert bound == "memory" and 10.0 < t * 1e3 < 10.2


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_swa_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each of ``probes`` (``run.py --probe-sensitivity``) and the
  teacher-forced run each of ``long_probes`` (PERF.md section 6). The rehearsal widths keep what the published ones
  force: two head counts with different group sizes, a window shorter than the prompts, a partial rotary, 16 experts
  top-4 beside a shared one."""
  hf, kind = _tiny(), arch.load(KIND)
  z = kind._sizes(hf)
  assert z["heads"] == {"full": 6, "window": 8} and z["heads"]["full"] // z["Hkv"] != z["heads"]["window"] // z["Hkv"] and z["W"] == 8 and (z["E"], z["k"]) == (16, 4) and z["Fs"]
  assert hf["rope_parameters"]["full_attention"]["partial_rotary_factor"] == 0.5 and set(kind.hf_attention_kinds(hf)) == {"full", "window"}
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_last_layer", "rope_swapped", "full_rotary", "yarn_factor_off", "heads_48_everywhere", "no_gate", "gate_sigmoid", "no_qk_norm", "router_softmax", "no_norm_topk", "no_routed_scaling", "drop_shared", "drop_expert", "float8_matmul_operands"}
  assert set(kind.probes(hf)) == want and set(kind.long_probes(hf)) == {"window_off", "window_on_full_layers", "window_1024"}
  for name, kw in {**kind.probes(hf), **kind.long_probes(hf)}.items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)
  routed: list = []
  kind.reference_forward(jax.tree.map(lambda x: x.astype(np.float32), params), hf, np.asarray(tokens), routed=routed)
  assert len(routed) == 3 and all(np.asarray(r).sum(axis=-1).tolist() == [4] * 40 for r in routed)  # four experts a token in each expert layer
  tables = jax.jit(lambda k: kind.router_tables(weights.shape_hf(hf), k))(weights.seed_key(5))
  assert tables["owns"].shape == (3, 16, 16) and np.asarray(tables["owns"]).sum(axis=-1).tolist() == [[4.0] * 16] * 3 and tables["topic_of"].shape == (512,)


def _ctx(device_s_per_call: float, kernel: str = "paged_decode_window") -> dict:
  """62 rows resident at the capture's middle, each holding 2,800 tokens; the kernel's calls at ``device_s_per_call``."""
  hf = common.load_config(CONFIG)
  recs = [SimpleNamespace(first=0.0, events=[(0.5, 300)], max_tokens=1000, prompt_tokens=2500) for _ in range(62)]
  recs.append(SimpleNamespace(first=None, events=[], max_tokens=1000, prompt_tokens=2500))  # still waiting for its first token: not resident
  return {"hf": hf, "recs": recs, "cap_start": 1.0, "cap_end": 7.0, "peaks": {"hbm_bytes_per_s": 819e9}, "trace": {"kernels": {kernel: {"device_s": 30 * device_s_per_call, "calls": 30}}}}


def test_the_windowed_calls_roofline_reads_a_perfect_and_a_window_blind_kernel():
  """On a synthetic trace: a call that takes exactly the time its window layer's bytes take at the peak rate (62 rows x
  512 tokens x 4096 B) reads 100 %; one that folds every resident page of rows of 2,800 tokens reads 512 / 2800 = 18.3 %;
  a trace without the windowed name (the parent's program, or one off the kernel) reads nothing, and neither does a
  kind that names no attention kinds. ``paged_attn_layers_roofline`` divides both calls' time by the five layers' mean."""
  reader, layers = _reader("paged_attn_window_roofline"), _reader("paged_attn_layers_roofline")
  window_s, full_s = 62 * 512 * 4096 / 819e9, 62 * 2800 * 4096 / 819e9
  assert reader.KERNELS == ("paged_decode_window",) and reader.read(_ctx(window_s)) == pytest.approx(100.0)
  assert reader.read(_ctx(full_s)) == pytest.approx(100.0 * 512 / 2800) and 18 < reader.read(_ctx(full_s)) < 19
  assert reader.read(_ctx(window_s, kernel="paged_decode")) is None and reader.read({**_ctx(window_s), "trace": None}) is None
  assert reader.read({**_ctx(window_s), "hf": common.load_config("olmo-hybrid-7b-d12")}) is None  # a kind without ``hf_attention_kinds``
  mean_s = (2 * full_s + 3 * window_s) / 5
  assert layers.KERNELS == ("paged_decode",) and layers.read(_ctx(mean_s, kernel="paged_decode")) == pytest.approx(100.0)
