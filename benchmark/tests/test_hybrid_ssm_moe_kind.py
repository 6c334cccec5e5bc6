"""Kind ``hybrid_ssm_moe`` and its cell (PR 53), on the CPU: the kind loads whole, the configuration file holds the
catalog row's keys and states its cut, what the maker makes is what the byte model counts and both are ISSUE 53's hand
counts (12.15 GB, 8.39 MB a slot, 1 KB a token, two matrices an expert, 31,578 M published), every probe moves its
reference, and the kind and the traffic came as files and entries (``test_add_cell.py``'s promise). The cell's rehearsal
(``run.py --rehearse``, ~4 min with 64 callers) is run by hand, not here."""

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
CELL, CONFIG, KIND, TRAFFIC = "nemotron-3-nano.reason-closed-64", "nemotron-3-nano-30b-a3b-d9", "hybrid_ssm_moe", "reason-closed-64"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_the_kind_loads_with_every_part_and_is_found_by_name_alone():
  kind = arch.load(KIND)
  assert all(hasattr(kind, part) for part in arch.PARTS) and len(arch.PARTS) == 10
  assert set(kind.LIMITS) == set(kind.LIMITS_WHY) == set(arch.LIMIT_NAMES) and all(len(why) > 80 for why in kind.LIMITS_WHY.values())
  assert all(callable(getattr(kind, name)) for name in ("ssm_state_bytes", "moe_expert_bytes", "routed_experts", "router_tables", "hf_layer_types", "exact_probes", "long_prompt_tokens"))
  bench = ROOT / "benchmark"
  shared = [p for p in [*bench.glob("*.py"), *bench.glob("layer_metrics/*.py"), *bench.glob("end_to_end/*.py"), *bench.glob("generators/*.py"), *bench.glob("tools/*.py")] if not p.name.startswith("arch_")]
  assert len(shared) > 40 and not [p.name for p in shared if KIND in p.read_text() or "nemotron" in p.read_text().lower()]


def test_the_configuration_file_states_its_cut_and_the_cell_its_traffic():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"] == {"num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED} and (hf["num_hidden_layers"], hf["hybrid_override_pattern"]) == (9, PUBLISHED[:9]) == (9, "MEMEM*EME")
  assert all(word in hf["stands_for"] for word in ("6-chip", "9 + 9 + 9 + 9 + 9 + 7", "all 128", "whole vocabulary", "31,578 M", "12.15 GB"))
  assert {"position", "d_inner", "gated_norm", "time_step", "mamba_draws", "block", "experts", "router", "weights", "router_topics"} <= set(hf["assumed"])  # every reading the row does not state is written down
  kind = arch.load(KIND)
  scalars = weights.shape_hf(hf)  # as the maker sees the file: the pattern is a string and stays
  assert kind.layer_steps(scalars) == [("mamba", "experts"), ("mamba", "experts"), ("mamba", "none"), ("attention", "experts"), ("mamba", "experts")] and kind.hf_layer_types(hf) == ("mamba", "mamba", "mamba", "attention", "mamba")
  assert kind.layer_stacks(scalars) == [("ssm_moe_layers", 0), ("ssm_moe_layers", 1), ("ssm_mixer_layers", 0), ("moe_layers", 0), ("ssm_moe_layers", 2)]
  assert [(letter, name) for letter, name, _ in kind.blocks(scalars)] == [("M", "ssm_moe_layers"), ("E", "ssm_moe_layers"), ("M", "ssm_moe_layers"), ("E", "ssm_moe_layers"), ("M", "ssm_mixer_layers"), ("*", "moe_layers"), ("E", "moe_layers"), ("M", "ssm_moe_layers"), ("E", "ssm_moe_layers")]
  steps = kind.layer_steps({**hf, **hf["published"]})
  assert len(steps) == 29 and (steps.count(("mamba", "experts")), steps.count(("mamba", "none")), steps.count(("attention", "experts"))) == (17, 6, 6)
  for pattern in ("EMEMEM*EM", "MEEMEM*EM", "MEMEM*EM-"):
    with pytest.raises(ValueError, match="cannot be read"):
      kind.layer_steps({**hf, "hybrid_override_pattern": pattern})
  cell = common.cell_of(spec, CELL)
  traffic = common.load_traffic(TRAFFIC)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and traffic["generator"] == "closed"
  assert traffic["clients"] == 64 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])
  assert (traffic["prompt_tokens"], traffic["output_tokens"]) == ({"dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128, "max": 2048}, {"dist": "lognormal", "median": 1536, "sigma": 0.4, "min": 768, "max": 3072})
  assert traffic["warm"]["group_sizes"] == [1, 2, 4, 8] and traffic["ramp_s"] == 6 and hf["warm_shape_rule"] == {"kind": "padded_groups", "bucket_tokens": 128}
  longest = traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
  assert longest == 5120 < hf["serving_window_tokens"] == 8192 and int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"]) == 64 * longest // 64 + 1 == 5121
  assert hf["serving_env"] == {"XOT_TPU_BATCHED": "1", "XOT_TPU_BATCH_SLOTS": "64", "XOT_TPU_BATCH_PAGES": "5121", "XOT_TPU_BATCH_MAX_QUEUE": "128", "XOT_TPU_KV_TIER": "0"}
  # the cell reports what granite's and Ling's cells both report, and the lists ISSUE 53 names beside them
  listed = {m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ())}
  both = set.intersection(*({m["name"] for group in ("end_to_end", "per_layer") for m in spec[group] if other in m.get("workloads", ())} for other in ("granite-4.0-h-micro.decode-closed-64", "ling-3.0-flash.decode-closed-64")))
  named = {"moe_experts_roofline", "paged_attn_layers_roofline", "decode_moe_router_device_ms.closed", "prefill_wall_share.closed", "host_gap_wall_share.closed", "sched_host_ms_per_tick_window.closed"}
  assert listed == both | named and {"out_tok_s", "decode_step_roofline", "ssm_state_roofline", "decode_ssm_device_ms.closed", "decode_ffn_device_ms.closed"} <= both
  # Additions stand behind what was there (PR 50's entries), wherever later PRs' stand: nothing here pins the END of a list.
  at = lambda group, name: [m["name"] for m in spec[group]].index(name)  # noqa: E731
  assert at("workloads", CELL) > at("workloads", "smallthinker-21ba3b.longdoc-closed-32") and at("configs", CONFIG) > at("configs", "smallthinker-21ba3b-d8")
  assert all(m["workloads"].index(CELL) == max(m["workloads"].index(w) for w in m["workloads"] if not w.startswith("nemotron")) + 1 for group in ("end_to_end", "per_layer") for m in spec[group] if CELL in m.get("workloads", ()))
  assert all("workloads" in m for m in spec["per_layer"]) and all(w["chips"] == 1 for w in spec["workloads"] if w["config"] == CONFIG)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  assert hf["source"] == row["source_url"] and row["config"]["hybrid_override_pattern"] == PUBLISHED
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  whole = row["config"]
  assert round(kind.param_count(whole) / 1e6) == 31578 and round(kind.active_params(whole) / 1e9, 2) == 3.23 and row["described_as"]["params"] == "31.6B-A3.2B"
  from xotorch_support_jetson_tpu.models.config import config_from_hf

  cfg = config_from_hf(whole)  # the row's keys as they are: its model_type is there
  assert (cfg.n_layers, cfg.recurrent_layers, cfg.n_attn_layers, cfg.expert_layers, cfg.ssm_groups, cfg.n_experts, cfg.n_active_experts, cfg.ffn_gated, cfg.expert_act, cfg.use_rope) == (29, 23, 6, 23, 8, 128, 6, False, "relu2", False)


def test_the_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 53's arithmetic, from the file: a Mamba-2 block 38.74 M parameters, an attention block 23.40 M, an expert
  block 1,297.5 M (128 experts of TWO matrices, 9,977,856 each, the shared one 19.96 M, the router), embedding + head +
  final norm 704.6 M; this stage 6,073 M = 12.15 GB, to the byte what ``make_params`` makes. A slot's state is 8.39 MB
  of float32 + 147 KB of convolution rows; a cached token 1 KB in the one attention block."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  p = kind._params(hf)
  assert [round((p[k] + p.get(f"{k}_f32", 0)) / 1e6, 2) for k in ("mamba", "attention")] == [38.74, 23.40] and p["expert"] == 2 * 2688 * 1856 == 9977856
  assert round((p["moe_rest"] + p["moe_f32"] + 128 * p["expert"]) / 1e6, 1) == 1297.5 and round(p["top"] / 1e6, 1) == 704.6 and round(2 * 2688 * 3712 / 1e6, 2) == 19.96
  made = weights.param_shapes(hf)
  assert sum(x.size for x in jax.tree.leaves(made)) == kind.param_count(hf) == 6072897024
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf) and round(kind.weight_bytes(hf) / 1e9, 2) == 12.15
  assert set(made) == {"ssm_moe_layers", "ssm_mixer_layers", "moe_layers", "embed", "final_norm", "lm_head"}
  assert made["ssm_moe_layers"]["w_experts_up_t"].shape == made["ssm_moe_layers"]["w_experts_down"].shape == (3, 128, 1856, 2688) and made["moe_layers"]["w_experts_up_t"].shape == (1, 128, 1856, 2688)
  assert made["ssm_moe_layers"]["w_xbc"].shape == (3, 2688, 4096 + 2 * 8 * 128) and made["ssm_mixer_layers"]["w_z"].shape == (1, 2688, 4096) and made["moe_layers"]["wk"].shape == (1, 2688, 2 * 128)
  assert not {"w_experts_gate", "w_experts_up", "w_shared_gate", "w_gate"} & set(made["ssm_moe_layers"]) and not {"mlp_norm", "w_router", "w_shared_up"} & set(made["ssm_mixer_layers"])
  assert made["ssm_moe_layers"]["router_bias"].dtype == np.float32 and made["ssm_moe_layers"]["w_shared_up"].shape == (3, 2688, 3712)
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  program = jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), common.model_config(hf))[0])
  assert jax.tree.map(lambda x: (x.shape, x.dtype), program) == jax.tree.map(lambda x: (x.shape, x.dtype), made)  # the benchmark's maker and the program's agree leaf for leaf
  rows, tokens = 64, 64 * 1800
  assert kind.ssm_state_bytes(hf, 1) / 2 == 4 * (64 * 64 * 128 * 4 + 3 * 6144 * 2) and round(4 * 64 * 64 * 128 * 4 / 1e6, 2) == 8.39
  per_layer = kind.cache_read_bytes(hf, rows, tokens, "")
  assert len(per_layer) == 5 and per_layer[3] == tokens * 1024 and per_layer[0] == per_layer[4] == kind.ssm_state_bytes(hf, rows) / 4
  assert round(kind.ssm_state_bytes(hf, rows) / 1e9, 2) == 1.09
  independent = {k: v for k, v in hf.items() if k != "router_topics"}
  assert kind.routed_experts(hf) == (0, 120, 120, 6) and 114.5 < fb.experts_touched(independent, 120, 120, 6, rows) < 116 and 114 < fb.experts_touched(hf, 120, 120, 6, rows) < 115 and hf["router_topics"] == 512  # (512 topics: the count saturates, whatever topics a window's rows hold)
  assert kind.moe_expert_bytes(hf, rows) == 4 * fb.experts_touched(hf, 120, 120, 6, rows) * 9977856 * 2 and 9.0 < kind.moe_expert_bytes(hf, rows) / 1e9 < 9.3
  assert round(4 * 128 * 9977856 * 2 / 1e9, 1) == 10.2  # all 128 of 4 blocks: what 64 independent rows could reach
  outside = kind.step_weight_bytes(hf, rows) - kind.moe_expert_bytes(hf, rows)
  assert round(outside / 1e9, 2) == 1.22 and round(131072 * 2688 * 2 / 1e9, 2) == 0.70
  assert fb.decode_step_flops(hf, rows) == 2.0 * rows * kind.active_params(hf) and kind.CACHE_TYPE_ENV == "XOT_TPU_KV_QUANT"
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, rows), fb.decode_step_min_bytes(hf, rows, tokens, ""), {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
  assert bound == "memory" and 13.5 < t * 1e3 < 14.8


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each of ``probes`` (``run.py --probe-sensitivity``; PERF.md
  section 6 says which they do). The rehearsal widths keep what the published ones force: several heads a B/C group,
  several queries a KV head, experts of two matrices with decoys under the selection bias, a step with no FFN."""
  hf, kind = _tiny(), arch.load(KIND)
  z = kind._sizes(hf)
  assert (z["G"], z["H"] // z["G"], z["Hq"] // z["Hkv"], z["E"], z["k"], z["decoys"]) == (2, 4, 4, 16, 4, 2)
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_last_block", "experts_gated", "relu_for_relu2", "one_bc_group", "norm_over_all_channels", "norm_before_gate", "ffn_under_the_m_before_attention", "rope_in_attention",
          "router_without_bias", "router_without_scaling", "shared_expert_left_out", "float8_matmul_operands"}  # fmt: skip
  assert set(kind.probes(hf)) == want and set(kind.exact_probes(hf)) == {"router_bfloat16", "conv_bias_dropped", "recurrent_state_bfloat16"} and kind.long_prompt_tokens(common.load_config(CONFIG)) == (1024, 1536)
  for name, kw in {**kind.probes(hf), **kind.exact_probes(hf)}.items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)
  routed: list = []
  kind.reference_forward(jax.tree.map(lambda x: x.astype(np.float32), params), hf, np.asarray(tokens), routed=routed)
  assert len(routed) == 4 and all(np.asarray(r).sum(axis=-1).tolist() == [4] * 40 for r in routed)  # four experts a token in every expert block
  assert not any(np.asarray(r)[:, -2:].any() for r in routed)  # the decoys are never chosen: their selection bias stands under every score
  tables = jax.jit(lambda k: kind.router_tables(weights.shape_hf(hf), k))(weights.seed_key(5))
  assert tables["owns"].shape == (4, 16, 16) and np.asarray(tables["owns"]).sum(axis=-1).tolist() == [[4.0] * 16] * 4 and not np.asarray(tables["owns"])[..., -2:].any()
  own = np.asarray(tables["owns"])[:, np.asarray(tables["topic_of"])[tokens], :] > 0
  assert (np.stack([np.asarray(r) for r in routed]) == own).all(axis=2).mean() > 0.5  # a token's topic fixes its experts (at a hidden size of 64 the topic's direction stands less clear than at 2688: tools/experts_touched.py reads the served width)
  assert (params["ssm_moe_layers"]["router_bias"][:, -2:] == kind.DECOY_BIAS).all() and not np.asarray(params["ssm_moe_layers"]["router_bias"][:, :-2]).any()
