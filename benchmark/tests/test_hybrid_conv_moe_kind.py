"""Kind ``hybrid_conv_moe`` and its cell (PR 57), on the CPU: the kind loads whole, the configuration file holds the
catalog row's keys and states its cut, what the maker makes is what the byte model counts and both are ISSUE 57's hand
counts (5,399 M = 10.80 GB, 98 KB of state a slot, 8 KB a cached token, three matrices an expert, 8,340 M published),
every probe moves its reference, and the kind and the traffic came as files and entries (``test_add_cell.py``'s promise).
The cell's rehearsal (``run.py --rehearse``, ~3 min with 128 callers) is run by hand, not here."""

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
CELL, CONFIG, KIND, TRAFFIC = "lfm2-8b-a1b.decode-closed-128", "lfm2-8b-a1b-d16", "hybrid_conv_moe", "decode-closed-128"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED = ["conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "full_attention", "conv", "conv"]  # fmt: skip
WHOLE = {"num_hidden_layers": 24, "layer_types": PUBLISHED, "layer_pattern": "ccAcccAcccAcccAcccAccAcc"}


def test_the_kind_loads_with_every_part_and_is_found_by_name_alone():
  kind = arch.load(KIND)
  assert all(hasattr(kind, part) for part in arch.PARTS)
  assert set(kind.LIMITS) == set(kind.LIMITS_WHY) == set(arch.LIMIT_NAMES) and all(len(why) > 80 for why in kind.LIMITS_WHY.values())
  assert all(callable(getattr(kind, name)) for name in ("moe_expert_bytes", "routed_experts", "router_tables", "hf_layer_types", "layer_stacks", "exact_probes", "long_prompt_tokens", "conv_tail_bytes"))
  assert not hasattr(kind, "ssm_state_bytes")  # no state matrix: ``ssm_state_roofline``'s reader finds nothing to read, and the cell is not on its list
  bench = ROOT / "benchmark"
  shared = [p for p in [*bench.glob("*.py"), *bench.glob("layer_metrics/*.py"), *bench.glob("end_to_end/*.py"), *bench.glob("generators/*.py"), *bench.glob("tools/*.py")] if not p.name.startswith("arch_")]
  assert len(shared) > 40 and not [p.name for p in shared if KIND in p.read_text() or "lfm2" in p.read_text().lower()]


def test_the_configuration_file_states_its_cut_and_the_cell_its_traffic():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", "layer_types"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"] == {"num_hidden_layers": 24, "layer_types": PUBLISHED} and (hf["num_hidden_layers"], hf["layer_types"], hf["layer_pattern"]) == (16, PUBLISHED[:16], "ccAcccAcccAcccAc")
  assert all(word in hf["stands_for"] for word in ("two-chip", "16 + 8", "all 32", "whole vocabulary", "8,340 M", "5,399 M", "10.80 GB", "tied head"))
  assert {"torch_dtype", "tied_head", "block", "short_conv", "dense_width", "attention", "router", "weights", "router_topics", "eos"} <= set(hf["assumed"])  # every reading the row does not state is written down
  kind = arch.load(KIND)
  scalars = weights.shape_hf(hf)  # as the maker sees the file: the list is gone, the pattern string stays
  assert "layer_types" not in scalars and kind.hf_layer_types(scalars) == kind.hf_layer_types(hf) == tuple("conv" if t == "conv" else "attention" for t in PUBLISHED[:16])
  assert kind.layer_stacks(scalars)[:4] == [("ssm_layers", 0), ("ssm_layers", 1), ("moe_layers", 0), ("ssm_moe_layers", 0)] and kind.layer_stacks(scalars)[-1] == ("ssm_moe_layers", 9)
  types = kind.hf_layer_types({**hf, **WHOLE})
  assert (types.count("conv"), types.count("attention")) == (18, 6)
  with pytest.raises(ValueError, match="does not spell"):
    kind.hf_layer_types({**hf, "layer_pattern": "cccAcccAcccAcccA"})
  with pytest.raises(ValueError, match="must name"):
    kind.hf_layer_types({**hf, "layer_pattern": "ccAcccAcccAcccAx"})
  cell = common.cell_of(spec, CELL)
  traffic = common.load_traffic(TRAFFIC)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and traffic["generator"] == "closed"
  assert traffic["clients"] == 128 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])
  closed_64 = common.load_traffic("decode-closed-64")
  assert (traffic["prompt_tokens"], traffic["output_tokens"]) == (closed_64["prompt_tokens"], closed_64["output_tokens"]) == ({"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64, "max": 1024}, {"dist": "lognormal", "median": 256, "sigma": 0.4, "min": 64, "max": 512})
  assert traffic["warm"]["group_sizes"] == [1, 2, 4, 8] and (traffic["warm"]["anchor_tokens"], traffic["warm"]["concurrent"], traffic["ramp_s"]) == (3000, 1, 6) and hf["warm_shape_rule"] == {"kind": "padded_groups", "bucket_tokens": 128}
  # every row at the longest PROMPT has its pages (3073, every row at its longest context, does not fit: serving_env_why)
  assert hf["serving_env"] == {"XOT_TPU_BATCHED": "1", "XOT_TPU_BATCH_SLOTS": "128", "XOT_TPU_BATCH_PAGES": str(128 * traffic["prompt_tokens"]["max"] // 64 + 1), "XOT_TPU_BATCH_MAX_QUEUE": "256", "XOT_TPU_KV_TIER": "0"}
  assert traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] == 1536 < hf["serving_window_tokens"] == 4096
  # the cell reports every metric that the eight closed cells before it all report, and the lists ISSUE 57 names beside them
  listed = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ())}
  before = [w["name"] for w in spec["workloads"][: [w["name"] for w in spec["workloads"]].index(CELL)] if common.load_traffic(w["traffic"])["generator"] == "closed"]
  every = set.intersection(*({m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if other in m.get("workloads", ())} for other in before))
  named = {"decode_ssm_device_ms.closed", "decode_ssm_proj_device_ms.closed", "paged_attn_layers_roofline", "moe_experts_roofline", "decode_moe_router_device_ms.closed", "moe_experts_visited_per_layer_step.closed",
           "prefill_wall_share.closed", "host_gap_wall_share.closed", "sched_host_ms_per_tick_window.closed", "mixed_wall_share.closed"}  # fmt: skip
  assert len(before) == 8 and listed == every | named and {"out_tok_s", "decode_step_roofline", "decode_ffn_device_ms.closed", "window_compiles.closed"} <= every
  assert not listed & {"ssm_state_roofline", "paged_attn_window_roofline", "mixed_prefill_device_ms_per_ktok.closed", "mixed_prefill_device_share.closed", "decode_half_step_device_ms.closed", "moe_experts_decode_roofline", "mixed_slice_fill_share.closed", "kv_pages_read_share.closed"}
  # Additions stand behind what was there (PR 53's entries), wherever later PRs' stand: nothing here pins the END of a list.
  at = lambda group, name: [m["name"] for m in spec[group]].index(name)  # noqa: E731
  assert at("workloads", CELL) > at("workloads", "nemotron-3-nano.reason-closed-64") and at("configs", CONFIG) > at("configs", "nemotron-3-nano-30b-a3b-d9")
  assert all(m["workloads"].index(CELL) > max(m["workloads"].index(w) for w in m["workloads"] if w in before) for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ()))
  assert all("workloads" in m for m in spec["per_layer"]) and all(w["chips"] == 1 for w in spec["workloads"] if w["config"] == CONFIG)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "LFM2-8B-A1B")
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  assert hf["source"] == row["source_url"] and row["config"]["layer_types"] == PUBLISHED
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  whole = row["config"]  # (no ``layer_pattern``: the kind reads the list)
  assert round(kind.param_count(whole) / 1e6) == 8340 and kind.active_params(whole) // 10**6 == 1557 and row["described_as"]["params"] == "8.3B-A1.5B"
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf(whole)  # the row's keys as they are: its model_type is there
  assert (cfg.n_layers, cfg.recurrent_layers, cfg.n_attn_layers, cfg.expert_layers, cfg.first_k_dense, cfg.n_experts, cfg.n_active_experts, cfg.recurrent_kind, cfg.state_matrix, cfg.tied_embedding) == (24, 18, 6, 22, 2, 32, 4, "conv", False, True)


def test_the_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 57's arithmetic, from the file: a conv operator 16.79 M parameters, an attention operator 10.49 M, a dense FFN
  44.04 M, an expert 11.01 M and an expert layer's 32 + router + bias + norm 352.39 M, the tied table 134.22 M; this stage
  5,399 M = 10.80 GB, to the byte what ``make_params`` makes. A slot's state is 98 KB of convolution rows and nothing
  else; a cached token 8 KB over the four attention layers."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  p = kind._params(hf)
  assert [round(p[k] / 1e6, 2) for k in ("conv", "attention", "dense", "expert")] == [16.79, 10.49, 44.04, 11.01] and p["expert"] == 3 * 2048 * 1792 == 11010048
  assert round((p["moe_rest"] + p["moe_f32"] + 32 * p["expert"]) / 1e6, 2) == 352.39 and round(p["top"] / 1e6, 2) == 134.22
  assert p["conv"] == 2048 * 6144 + 2048 * 2048 + 3 * 2048 + 2048 and p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2048 + 2 * 64
  made = weights.param_shapes(hf)
  assert sum(x.size for x in jax.tree.leaves(made)) == kind.param_count(hf) == 5399129024 == 12 * p["conv"] + 4 * p["attention"] + 2 * p["dense"] + 14 * (p["moe_rest"] + p["moe_f32"] + 32 * p["expert"]) + p["top"]
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf) == 10798258944 and round(kind.weight_bytes(hf) / 1e9, 2) == 10.80
  assert kind.param_count({**hf, **WHOLE}) == 8339930560 and kind.active_params({**hf, **WHOLE}) == 1557740992
  assert set(made) == {"ssm_layers", "ssm_moe_layers", "moe_layers", "embed", "final_norm"}  # no lm_head: the head is the table
  assert made["ssm_moe_layers"]["w_experts_gate"].shape == made["ssm_moe_layers"]["w_experts_up"].shape == (10, 32, 2048, 1792) and made["moe_layers"]["w_experts_down"].shape == (4, 32, 1792, 2048)
  assert made["ssm_layers"]["w_in"].shape == (2, 2048, 6144) and made["ssm_layers"]["conv_w"].shape == (2, 3, 2048) and made["ssm_layers"]["w_gate"].shape == (2, 2048, 7168) and made["moe_layers"]["q_norm"].shape == (4, 64)
  assert made["ssm_moe_layers"]["router_bias"].dtype == np.float32 and "conv_b" not in made["ssm_layers"] and "w_shared_up" not in made["moe_layers"]
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, state_leaves

  cfg = common.model_config(hf)
  program = jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0])
  assert jax.tree.map(lambda x: (x.shape, x.dtype), program) == jax.tree.map(lambda x: (x.shape, x.dtype), made)  # the benchmark's maker and the program's agree leaf for leaf
  pool = jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, 2049, 64, n_slots=128))
  state = state_leaves(pool)
  assert set(state) == {"conv"} and sum(x.size * x.dtype.itemsize for x in state.values()) == 128 * 12 * 2 * 2048 * 2 == 12582912 == 128 * 98304  # 12.6 MB: 98 KB a slot
  rows, tokens = 128, 128 * 450
  assert kind.conv_tail_bytes(hf, 1) == 2 * 2 * 2048 * 2 == 16384  # a row's two rows of one layer, read and written
  per_layer = kind.cache_read_bytes(hf, rows, tokens, "")
  assert len(per_layer) == 16 and per_layer[2] == per_layer[14] == tokens * 2 * 8 * 64 * 2 and per_layer[0] == per_layer[15] == rows * 16384 and sum(per_layer) == 4 * tokens * 2048 + 12 * rows * 16384
  assert round(4 * tokens * 2048 / 1e9, 2) == 0.47  # the K/V a step reads: ISSUE 57's ~0.5 GB
  independent = {k: v for k, v in hf.items() if k != "router_topics"}
  assert kind.routed_experts(hf) == (2, 30, 30, 4) and 29.9 < fb.experts_touched(hf, 30, 30, 4, rows) < 30 and 29.9 < fb.experts_touched(independent, 30, 30, 4, rows) <= 30 and hf["router_topics"] == 64
  assert kind.moe_expert_bytes(hf, rows) == 14 * fb.experts_touched(hf, 30, 30, 4, rows) * 11010048 * 2 and 9.2 < kind.moe_expert_bytes(hf, rows) / 1e9 < 9.25
  assert round(14 * 32 * 11010048 * 2 / 1e9, 2) == 9.87  # all 32 of 14 layers: what a router without decoys could reach
  outside = kind.step_weight_bytes(hf, rows) - kind.moe_expert_bytes(hf, rows)
  assert round(outside / 1e9, 2) == 0.93 and round(65536 * 2048 * 2 / 1e9, 2) == 0.27
  assert fb.decode_step_flops(hf, rows) == 2.0 * rows * kind.active_params(hf) and kind.CACHE_TYPE_ENV == "XOT_TPU_KV_QUANT"
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, rows), fb.decode_step_min_bytes(hf, rows, tokens, ""), {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
  assert bound == "memory" and 12.9 < t * 1e3 < 13.1


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_probe_moves_the_reference_and_the_selection_bias_decides():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each of ``probes`` (``run.py --probe-sensitivity``; PERF.md
  section 6 says which they do). The rehearsal widths keep what the published ones force: both dense layers, several
  queries a KV head, gated experts with decoys under the selection bias."""
  hf, kind = _tiny(), arch.load(KIND)
  z = kind._sizes(hf)
  assert (z["Hq"] // z["Hkv"], z["E"], z["k"], z["decoys"], z["n_dense"], z["n_moe"], z["n_conv"], z["n_attn"], z["K"]) == (4, 16, 4, 2, 2, 6, 6, 2, 3)
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_layer", "conv_with_silu", "gate_after_taps", "chunk_order", "taps_reversed", "no_qk_norm", "router_without_bias", "softmax_router", "unnormalised_topk", "two_experts_trade_places", "float8_matmul_operands"}
  assert set(kind.probes(hf)) == want and set(kind.exact_probes(hf)) == {"router_bfloat16"} and kind.long_prompt_tokens(common.load_config(CONFIG)) == (768, 1024)
  for name, kw in kind.probes(hf).items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-2, (name, moved)
  routed: list = []
  kind.reference_forward(jax.tree.map(lambda x: x.astype(np.float32), params), hf, np.asarray(tokens), routed=routed)
  assert len(routed) == 6 and all(np.asarray(r).sum(axis=-1).tolist() == [4] * 40 for r in routed)  # four experts a token in every expert layer
  assert not any(np.asarray(r)[:, :2].any() for r in routed)  # the decoys are never chosen: their selection bias stands under every score
  unbiased: list = []
  kind.reference_forward(jax.tree.map(lambda x: x.astype(np.float32), params), hf, np.asarray(tokens), no_router_bias=True, routed=unbiased)
  assert np.asarray(unbiased[0])[:, :2].mean() > 0.5  # without the bias the decoys are among the chosen: the bias decides
  tables = jax.jit(lambda k: kind.router_tables(weights.shape_hf(hf), k))(weights.seed_key(5))
  assert tables["owns"].shape == (6, 4, 16) and np.asarray(tables["owns"]).sum(axis=-1).tolist() == [[4.0] * 4] * 6 and not np.asarray(tables["owns"])[..., :2].any()
  own = np.asarray(tables["owns"])[:, np.asarray(tables["topic_of"])[tokens], :] > 0
  assert (np.stack([np.asarray(r) for r in routed]) == own).all(axis=2).mean() > 0.9  # a token's topic fixes its experts
  assert (params["ssm_moe_layers"]["router_bias"][:, :2] == kind.DECOY_BIAS).all() and not np.asarray(params["ssm_moe_layers"]["router_bias"][:, 2:]).any()
  # the tied table: the head reads the channels the final norm's gain leaves, the layers' topic directions stand on the others
  A = z["A"]
  assert not np.asarray(params["final_norm"][:A], np.float32).any() and (np.asarray(params["final_norm"][A:], np.float32) == kind.FINAL_GAIN).all() and "lm_head" not in params
  embed = np.asarray(params["embed"], np.float32)
  assert 1.5 < embed[:, :A].std() < 2.1 and 0.5 * kind.HEAD_GAIN < embed[:, A:].std() * (hf["hidden_size"] - A) ** 0.5 < 1.5 * kind.HEAD_GAIN
