"""Kind ``swa_nope_moe`` and its cell (PR 50), on the CPU: the kind loads whole, the configuration file holds the catalog
row's keys and states its cut, what the maker makes is what the byte model counts and both are ISSUE 50's hand counts
(the 9.7 GB of a step, to its parts), every probe moves its reference, the new reader reads the router's scope and
nothing where there is none, and the kind, the traffic and the reader came as files and entries (``test_add_cell.py``'s
promise). The cell's rehearsal (``run.py --rehearse``) is run by hand, not here (PERF.md section 7 says what it can show
for prompts this long)."""

import importlib.util
import json
import os
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
CELL, CONFIG, KIND, TRAFFIC = "smallthinker-21ba3b.longdoc-closed-32", "smallthinker-21ba3b-d8", "swa_nope_moe", "longdoc-closed-32"
READER = "decode_moe_router_device_ms"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
LISTS = ("rope_layout", "sliding_window_layout")


def _reader(name: str):
  spec = importlib.util.spec_from_file_location(f"per_layer_{name}", ROOT / "benchmark" / "layer_metrics" / f"{name}.py")
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_the_nope_kind_loads_with_every_part_and_is_found_by_name_alone():
  """All of ``arch.PARTS`` and the three limits, and what readers and tools ask for by ``getattr``; no shared file of the
  harness names the kind or the model, and the new reader names neither: they came as files and entries."""
  kind = arch.load(KIND)
  assert all(hasattr(kind, part) for part in arch.PARTS) and len(arch.PARTS) == 10
  assert set(kind.LIMITS) == set(kind.LIMITS_WHY) == set(arch.LIMIT_NAMES) and all(len(why) > 80 for why in kind.LIMITS_WHY.values())
  assert all(callable(getattr(kind, name)) for name in ("moe_expert_bytes", "routed_experts", "router_tables", "hf_layer_types", "hf_attention_kinds", "long_probes", "exact_probes", "long_prompt_tokens")) and not hasattr(kind, "ssm_state_bytes")
  bench = ROOT / "benchmark"
  shared = [p for p in [*bench.glob("*.py"), *bench.glob("layer_metrics/*.py"), *bench.glob("end_to_end/*.py"), *bench.glob("generators/*.py"), *bench.glob("tools/*.py")] if p.name != f"arch_{KIND}.py"]
  assert len(shared) > 40 and not [p.name for p in shared if KIND in p.read_text() or "smallthinker" in p.read_text().lower()]


def test_the_nope_configuration_file_states_its_cut_and_the_cell_its_traffic():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", *LISTS] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"]["num_hidden_layers"] == 52 and hf["num_hidden_layers"] == 8 == len(hf["rope_layout"]) == len(hf["sliding_window_layout"])
  assert hf["rope_layout"] == hf["sliding_window_layout"] == [0, 1, 1, 1, 0, 1, 1, 1]  # two whole periods
  assert "7-chip ring" in hf["stands_for"] and "8 layers a chip" in hf["stands_for"]
  assert {"model_type", "torch_dtype", "block", "router", "experts", "qk_norm", "rope", "window", "weights", "router_topics"} <= set(hf["assumed"])  # every reading the row does not state is written down
  kind = arch.load(KIND)
  scalars = weights.shape_hf(hf)  # as the maker sees the file: the lists are gone
  assert not set(LISTS) & set(scalars)
  assert kind.hf_attention_kinds(hf) == kind.hf_attention_kinds(scalars) == ("full", "window", "window", "window") * 2 and kind.hf_layer_types(hf) == ("attention",) * 8
  assert kind.layer_stacks(scalars) == [("moe_layers", 0), ("window_moe_layers", 0), ("window_moe_layers", 1), ("window_moe_layers", 2), ("moe_layers", 1), ("window_moe_layers", 3), ("window_moe_layers", 4), ("window_moe_layers", 5)]
  with pytest.raises(ValueError, match="global_attention_interval"):
    kind.hf_attention_kinds({**hf, "global_attention_interval": 2})
  cell = common.cell_of(spec, CELL)
  traffic = common.load_traffic(TRAFFIC)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and traffic["generator"] == "closed"
  assert traffic["clients"] == 32 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])
  assert (traffic["prompt_tokens"], traffic["output_tokens"]) == ({"dist": "lognormal", "median": 8192, "sigma": 0.3, "min": 4096, "max": 12288}, {"dist": "lognormal", "median": 1024, "sigma": 0.4, "min": 256, "max": 2048})
  assert traffic["prompt_tokens"]["min"] >= hf["sliding_window_size"] and traffic["warm"]["group_sizes"] == [1, 2, 4, 8] and traffic["warm"]["anchor_tokens"] >= 12000 and traffic["ramp_s"] == 6
  assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] == 14336 < hf["serving_window_tokens"] == hf["max_position_embeddings"] == 16384
  assert 4609 <= int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"]) <= 5121 and hf["serving_env"]["XOT_TPU_MIXED_BUDGET"] == "2048" == str(hf["warm_shape_rule"]["slice_tokens"]) and "XOT_TPU_PREFILL_CHUNK" not in hf["serving_env"] and hf["warm_shape_rule"]["kind"] == "mixed_slices"
  # the cell reports what Laguna's cell reports, and the router's own time
  listed = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ())}
  laguna = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if "laguna-xs.2.agent-closed-64" in m.get("workloads", ())}
  assert listed >= laguna | {f"{READER}.closed"} and {"out_tok_s", "decode_step_roofline", "moe_experts_roofline", "paged_attn_layers_roofline", "paged_attn_window_roofline"} <= listed
  new = next(m for m in spec["per_layer"] if m["name"] == f"{READER}.closed")
  assert {k: v for k, v in new.items() if k != "workloads"} == {"name": f"{READER}.closed", "unit": "ms", "better": "lower", "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"} and CELL in new["workloads"]
  # Additions stand behind what was there (PR 46's entries), wherever later PRs' stand: nothing here pins the END of a
  # list, which the next cell moves (benchmark/tests/test_swa_gqa_moe_kind.py did, and this PR's entries fail it: PERF.md section 7).
  at = lambda group, name: [m["name"] for m in spec[group]].index(name)  # noqa: E731
  assert at("per_layer", f"{READER}.closed") > at("per_layer", "paged_attn_window_roofline") and at("workloads", CELL) > at("workloads", "laguna-xs.2.agent-closed-64") and at("configs", CONFIG) > at("configs", "laguna-xs.2-d5")
  assert all("workloads" in m for m in spec["per_layer"]) and all(w["chips"] == 1 for w in spec["workloads"] if w["config"] == CONFIG)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_nope_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "SmallThinker-21BA3B-Instruct")
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  assert hf["source"] == row["source_url"]
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  assert all(hf[k] == row["config"][k][:8] for k in LISTS) and row["config"]["rope_layout"] == hf["rope_layout"][:4] * 13
  # the 52-layer row counts the published 21.5 G parameters and ~3.7 G touched a token with no shared expert and no
  # second expert tier: "21B-A3B" with the embedding left out
  whole = {**row["config"], "global_attention_interval": 4}
  p = kind._params(whole)
  assert round(kind.param_count(whole) / 1e9, 2) == 21.51 and round((52 * (p["attention"] + p["moe_rest"] + 6 * p["expert"]) + p["top"]) / 1e9, 2) == 3.72 and row["described_as"]["params"].startswith("21B-A3B")
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf({**row["config"], "model_type": "smallthinker"})
  assert (cfg.n_layers, cfg.n_experts, cfg.first_k_dense, len(set(cfg.layer_attn)), cfg.attn_windows[:5], cfg.attn_ropes[:5]) == (52, 64, 0, 2, (0, 4096, 4096, 4096, 0), (False, True, True, True, False))


def test_the_nope_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 50's arithmetic, from the file: an attention mixer 20.97 M parameters (both kinds alike), the router and the
  FFN's norm 0.17 M, a routed expert 5,898,240 (377.5 M a layer), embedding + head 777.9 M; 3,967 M in all = 7.93 GB, to
  the byte what ``make_params`` makes. At 32 rows of 9.1 k tokens a decode step's least bytes are 9.70 GB under
  independent rows: the experts the rows choose 5.78 GB (~61.3 of 64 a layer; 5.53 and ~58.6 under the file's topics),
  the six window layers' K/V 1.61 (as full layers they would read 3.58), the two global layers' 1.19, the head 0.78, the
  attention weights 0.34 — experts and the two kinds of attention 88 %; 11.8 ms at 819 GB/s."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  p = kind._params(hf)
  assert [round(p[k] / 1e6, 2) for k in ("attention", "moe_rest", "top")] == [20.97, 0.17, 777.91] and p["expert"] == 5898240 and round(64 * p["expert"] / 1e6, 1) == 377.5
  made = weights.param_shapes(hf)
  n_params = sum(x.size for x in jax.tree.leaves(made))
  assert n_params == kind.param_count(hf) == 3966937600
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf) and round(kind.weight_bytes(hf) / 1e9, 2) == 7.93  # what make_params makes is what is counted
  assert made["moe_layers"]["wq"].shape == (2, 2560, 28 * 128) and made["window_moe_layers"]["wk"].shape == (6, 2560, 4 * 128) and made["window_moe_layers"]["w_experts_gate"].shape == (6, 64, 2560, 768)
  assert made["moe_layers"]["w_router"].shape == (2, 2560, 64) and not {"w_gate", "w_shared_gate", "router_bias", "q_norm"} & set(made["moe_layers"]) and set(made) == {"moe_layers", "window_moe_layers", "embed", "final_norm", "lm_head"}
  rows, tokens = 32, 32 * 9100
  per_layer = kind.cache_read_bytes(hf, rows, tokens, "")
  assert per_layer == [tokens * 2048, *[rows * 4096 * 2048] * 3] * 2 and kind.kv_bytes_per_token_layer(hf, "") == 2048
  assert round(6 * per_layer[1] / 1e9, 2) == 1.61 and round(2 * per_layer[0] / 1e9, 2) == 1.19 and round(6 * per_layer[0] / 1e9, 2) == 3.58
  assert kind.cache_read_bytes(hf, 4, 4 * 3000, "")[1] == 4 * 3000 * 2048  # rows under the window: a window layer reads what they hold
  independent = {k: v for k, v in hf.items() if k != "router_topics"}
  assert kind.routed_experts(hf) == (0, 64, 64, 6) and round(fb.experts_touched(independent, 64, 64, 6, rows), 1) == 61.3 and round(fb.experts_touched(hf, 64, 64, 6, rows), 1) == 58.6
  assert round(kind.moe_expert_bytes(independent, rows) / 1e9, 2) == 5.78 and round(kind.moe_expert_bytes(hf, rows) / 1e9, 2) == 5.53
  assert kind.moe_expert_bytes(hf, rows) == 8 * fb.experts_touched(hf, 64, 64, 6, rows) * p["expert"] * 2
  outside = kind.step_weight_bytes(hf, rows) - kind.moe_expert_bytes(hf, rows)
  assert round(outside / 1e9, 2) == 1.12 and round(151936 * 2560 * 2 / 1e9, 2) == 0.78 and round(8 * p["attention"] * 2 / 1e9, 2) == 0.34  # head + attention weights + routers and norms
  step = fb.decode_step_min_bytes(independent, rows, tokens, "")
  assert round(step / 1e9, 2) == 9.70 and round(fb.decode_step_min_bytes(hf, rows, tokens, "") / 1e9, 2) == 9.45
  assert 0.88 < (kind.moe_expert_bytes(independent, rows) + sum(per_layer)) / step < 0.89
  assert fb.decode_step_flops(hf, rows) == 2.0 * rows * (8 * (p["attention"] + p["moe_rest"] + 6 * p["expert"]) + p["top"] / 2) and kind.CACHE_TYPE_ENV == "XOT_TPU_KV_QUANT"
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, rows), step, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
  assert bound == "memory" and 11.8 < t * 1e3 < 11.9


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_nope_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each of ``probes`` (``run.py --probe-sensitivity``) and the
  teacher-forced run each of ``long_probes`` (PERF.md section 6 says which they do). The rehearsal widths keep what the
  published ones force: an odd group of query heads a KV head, a window shorter than the prompts, global layers without
  rope around roped window layers, 16 experts top-4 in every layer and no other FFN."""
  hf, kind = _tiny(), arch.load(KIND)
  z = kind._sizes(hf)
  assert (z["H"] // z["Hkv"]) % 2 == 1 and z["W"] == 8 and (z["E"], z["k"]) == (16, 4) and kind.hf_attention_kinds(hf) == ("full", "window", "window", "full")
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_last_layer", "router_after_attention", "router_reads_raw_stream", "silu_experts", "rope_on_global_layers", "no_rope_on_window_layers", "experts_top5", "float8_matmul_operands"}
  assert set(kind.probes(hf)) == want and set(kind.long_probes(hf)) == {"window_layers_full", "window_on_global_layers"} and set(kind.exact_probes(hf)) == {"softmax_not_renormalised", "router_bfloat16", "window_7"}
  assert "window_4095" in kind.exact_probes(common.load_config(CONFIG)) and kind.long_prompt_tokens(common.load_config(CONFIG)) == (4160, 4608)
  for name, kw in {**kind.probes(hf), **kind.long_probes(hf), **kind.exact_probes(hf)}.items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)
  routed: list = []
  kind.reference_forward(jax.tree.map(lambda x: x.astype(np.float32), params), hf, np.asarray(tokens), routed=routed)
  assert len(routed) == 4 and all(np.asarray(r).sum(axis=-1).tolist() == [4] * 40 for r in routed)  # four experts a token in every layer
  tables = jax.jit(lambda k: kind.router_tables(weights.shape_hf(hf), k))(weights.seed_key(5))
  assert tables["owns"].shape == (4, 16, 16) and np.asarray(tables["owns"]).sum(axis=-1).tolist() == [[4.0] * 16] * 4 and tables["topic_of"].shape == (512,)


def test_the_routers_reader_reads_its_scope_and_nothing_where_there_is_none(monkeypatch):
  """``decode_moe_router_device_ms``: the self time of scope ``moe_router`` a decode step — executions x the chunk's
  steps — and None where the reduced capture holds no such scope (a dense model; the parent's program for this
  configuration never runs) or no capture at all: the line then leaves the metric out."""
  import span_lib

  reader = _reader(READER)
  assert reader.SCOPE == "moe_router"
  red = {"scoped": True, "decode": {"executions": 10}, "scope_s": {"moe_router": 0.016, "moe_experts": 0.4}, "dequant_s": 0.0}
  monkeypatch.setattr(span_lib, "capture", lambda ctx: red)
  assert reader.read({"chunk": 8}) == pytest.approx(0.016 * 1e3 / 80)
  monkeypatch.setattr(span_lib, "capture", lambda ctx: {**red, "scope_s": {"ffn": 0.4}})
  assert reader.read({"chunk": 8}) is None
  monkeypatch.setattr(span_lib, "capture", lambda ctx: None)
  assert reader.read({"chunk": 8}) is None
