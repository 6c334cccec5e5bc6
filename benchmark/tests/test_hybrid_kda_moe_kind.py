"""Kind ``hybrid_kda_moe`` and its cell (PR 36), on the CPU: the configuration file holds the catalog row's keys and
states its cut, the byte model is the published sizes' reckoning, every probe moves its reference, the new reader reads
nothing where the program has no such scope, and the cell's rehearsal runs to a correct line through the unchanged
``run.py``."""

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
CELL, CONFIG, KIND = "ling-3.0-flash.decode-closed-64", "ling-3.0-flash-ep4-d7", "hybrid_kda_moe"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_the_kda_moe_configuration_file_states_its_cut():
  hf, spec = common.load_config(CONFIG), common.load_spec()
  entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
  assert hf["reduced"] == entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size", "num_nextn_predict_layers"]
  assert hf["source"] == entry["source"] and hf["arch_kind"] == KIND and set(hf["reduced_why"]) == set(hf["reduced"]) == set(hf["published"])
  assert hf["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512, "vocab_size": 157184, "num_nextn_predict_layers": 1}
  assert (hf["num_hidden_layers"], hf["first_k_dense_replace"], hf["num_experts"], hf["num_experts_routed"], hf["experts_held_from"], hf["vocab_size"]) == (7, 1, 128, 512, 0, 39296)
  assert "4 chips" in hf["stands_for"] and "chip 0" in hf["stands_for"]
  # every reading of a key that ISSUE 36 names as an inference is written down
  assert {"kda_safe_gate", "use_qk_norm", "layer_pattern", "swiglu_limits", "max_window_layers", "kda_gate_bias", "router_topics", "recurrent_state", "kda_equations", "mla", "router"} <= set(hf["assumed"])
  # no width is cut, and the floors of a model_config cut hold: a whole period + the dense layer, >= 8 experts a layer, >= 1/8 of the vocabulary
  kind = arch.load(KIND)
  types = kind.hf_layer_types(hf)
  assert types == ("kda",) * 5 + ("attention", "kda") and hf["num_experts"] >= 8 and hf["vocab_size"] * 8 >= hf["published"]["vocab_size"]
  assert [name for name, _ in kind.layer_stacks(hf)] == ["ssm_layers"] + ["ssm_moe_layers"] * 4 + ["moe_layers", "ssm_moe_layers"]
  cell = common.cell_of(spec, CELL)
  assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "decode-closed-64", 1)
  assert common.load_traffic("decode-closed-64")["clients"] == 64 == int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]) and set(hf["serving_env"]) <= set(hf["serving_env_why"])


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_file_holds_every_number_of_the_catalog_row_outside_reduced():
  row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Ling-3.0-flash")
  hf = common.load_config(CONFIG)
  assert hf["source"] == row["source_url"]
  differs = [k for k, v in row["config"].items() if hf.get(k, "absent") != v]
  assert sorted(differs) == sorted(hf["reduced"]), differs
  assert all(hf["published"][k] == row["config"][k] for k in hf["reduced"])


def test_the_kda_moe_byte_model_is_the_published_sizes_reckoning():
  """ISSUE 36's arithmetic, from the file: a KDA mixer 52.6 M parameters, the MLA mixer 31.9 M, the dense FFN 47.2 M, an
  expert 5.90 M, 5.17 G in all = 10.3 GB; 12.6 MB of state and 0.44 MB of convolution rows a slot; 1152 bytes of latent
  cache a token; at 64 rows the held experts touched (60 of 128 a layer under the file's topic router, ISSUE 39; 81
  under uniform routing, which the file does not state) and the state are most of a step's least bytes."""
  hf, kind = common.load_config(CONFIG), arch.load(KIND)
  p = kind._params(hf)
  assert [round(p[k] / 1e6, 1) for k in ("kda", "mla", "dense_ffn", "expert")] == [52.6, 31.9, 47.2, 5.9]
  assert round(kind.weight_bytes(hf) / 1e9, 2) == 10.34
  made = weights.param_shapes(hf)
  assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(made)) == kind.weight_bytes(hf)  # what make_params makes is what is counted
  slot = kind.ssm_state_bytes(hf, 1) / 2
  assert round(6 * 32 * 128 * 128 * 4 / 1e6, 1) == 12.6 and round((slot - 6 * 32 * 128 * 128 * 4) / 1e6, 2) == 0.44
  per_layer = kind.cache_read_bytes(hf, 64, 64 * 500, "")
  assert len(per_layer) == 7 and per_layer[5] == 64 * 500 * 1152 and per_layer[0] == per_layer[6] == kind.ssm_state_bytes(hf, 64) / 6
  assert 60.0 < kind.held_experts_touched(hf, 64) < 61.0 and round(kind.moe_expert_bytes(hf, 64) / 1e9, 2) == 4.28
  assert kind.held_experts_touched(hf, 61.1) == pytest.approx(59.3, abs=0.1) and kind.held_experts_touched(hf, 64) == pytest.approx(60.5, abs=0.1)
  step = fb.decode_step_min_bytes(hf, 64, 64 * 500, "")
  share = (kind.moe_expert_bytes(hf, 64) + kind.ssm_state_bytes(hf, 64)) / step
  assert 0.83 < share < 0.85, share
  # rows without end reach the held experts that some topic owns, 128 (1 - (63/64)^64) = 81.3 a layer, not all 128: no token chooses the rest
  owned = 128 * (1 - (63 / 64) ** 64)
  assert kind.held_experts_touched(hf, 1e9) == pytest.approx(owned) == pytest.approx(81.28, abs=0.01)
  assert kind.step_weight_bytes(hf, 1e9) == pytest.approx(kind.weight_bytes(hf) - 39296 * 2560 * 2 - 6 * (128 - owned) * p["expert"] * 2)
  plain = {**hf, "router_topics": 0}  # a file that states no topics counts as before: uniform routing over all 512
  assert 80.5 < kind.held_experts_touched(plain, 64) < 81.5 and round(kind.moe_expert_bytes(plain, 64) / 1e9, 2) == 5.75
  assert kind.step_weight_bytes(plain, 1e9) == pytest.approx(kind.weight_bytes(plain) - 39296 * 2560 * 2)
  assert fb.decode_step_flops(plain, 64) == fb.decode_step_flops(hf, 64)  # operations follow the assignments (rows x k), not the distinct experts
  assert fb.decode_step_flops(hf, 64) > 0 and kind.CACHE_TYPE_ENV is None


def _tiny() -> dict:
  hf = common.load_config(CONFIG)
  hf.update(arch.load(KIND).REHEARSE_WIDTHS)
  return hf


def test_every_kda_moe_probe_moves_the_reference():
  """Each deliberately wrong reference differs from the plain one at the rehearsal widths (float32, so any change of the
  equations shows); on the chip the limits must refuse each (``run.py --probe-sensitivity``, PERF.md section 6)."""
  hf, kind = _tiny(), arch.load(KIND)
  params = weights.build_params(hf, 5)
  tokens = np.random.default_rng(5).integers(3, hf["vocab_size"], size=40)
  plain = np.asarray(reference.reference_logprobs(params, hf, tokens, 8))
  assert np.isfinite(plain).all()
  want = {"drop_last_layer", "decay_off", "delta_term_off", "beta_one", "output_gate_off", "held_range_shifted_by_one", "group_limit_off", "lose_one_expert_per_token",
          "rope_base_100x_too_small", "recurrent_state_bfloat16", "decay_bfloat16", "float8_matmul_operands"}  # fmt: skip
  assert set(kind.probes(hf)) == want
  for name, kw in kind.probes(hf).items():
    moved = np.abs(np.asarray(reference.reference_logprobs(params, hf, tokens, 8, **kw)) - plain).max()
    assert moved > 1e-4, (name, moved)


def test_the_topic_router_keeps_the_group_limit_clear_of_its_choices():
  """The router's reading of the token: for nearly every token the 8 experts chosen are the topic's own, two in each of
  4 groups, the eighth affinity well above the ninth; on average a quarter of the choices are held here."""
  hf, kind = _tiny(), arch.load(KIND)
  hf.update(num_experts=16, num_experts_routed=64, router_topics=16)  # 8 groups of 8
  params = jax.tree.map(lambda x: x.astype(np.float32), weights.build_params(hf, 9))
  x = params["embed"][np.random.default_rng(1).integers(3, hf["vocab_size"], size=256)]
  x = x / np.sqrt(np.mean(np.square(x), axis=-1, keepdims=True))
  st = params["ssm_moe_layers"]
  gates = np.asarray(kind.router_gates(x, st["w_router"][0], st["router_bias"][0], top_k=8, n_group=8, topk_group=4, scaling=2.5))
  chosen = gates > 0
  assert (chosen.sum(axis=1) == 8).all() and np.allclose(gates.sum(axis=1), 2.5, atol=1e-5)
  groups = chosen.reshape(256, 8, 8).sum(axis=2)
  assert (np.sort(groups, axis=1)[:, 4:] == 2).mean() > 0.9  # two in each of four groups
  assert 0.15 < chosen[:, :16].sum() / chosen.sum() < 0.35  # the share of choices held by chip 0


def test_the_topic_count_is_what_the_kinds_own_router_makes_rows_choose():
  """Ties ``held_experts_touched`` to the code that makes the weights: the kind's ``_router`` drawn with topics on
  (8 topics, 64 routed experts in 8 groups, 16 held, 8 a token: p x T = 1, as published), 64 random token ids walked
  through the reference, and in every expert layer the held experts that the reference's own router chose for any of
  them - the mixers' context and all - against the expectation; and against ``router_tables``' reading of the same
  draw. Uniform routing would touch 16.0 of the 16."""
  hf, kind = _tiny(), arch.load(KIND)
  hf.update(num_experts=16, num_experts_routed=64, router_topics=8)
  want = kind.held_experts_touched(hf, 64)
  assert want == pytest.approx(10.5, abs=0.01) and kind.held_experts_touched({**hf, "router_topics": 0}, 64) > 15.99
  touched, by_table, as_owned = [], [], []
  for seed in range(8):
    params = weights.build_params(hf, seed)
    tables = jax.jit(lambda k: kind.router_tables(weights.shape_hf(hf), k))(weights.seed_key(seed))
    owns, topic_of = np.asarray(tables["owns"]) > 0, np.asarray(tables["topic_of"])
    assert owns.shape == (3, 8, 64) and (owns.sum(axis=2) == 8).all() and topic_of.shape == (hf["vocab_size"],)
    tokens = np.random.default_rng(seed).integers(3, hf["vocab_size"], size=64)
    routed: list = []
    logits = kind.reference_forward(params, hf, tokens, routed=routed)
    assert len(routed) == 3 and np.array_equal(np.asarray(logits), np.asarray(kind.reference_forward(params, hf, tokens)))
    for layer, chose in enumerate(np.asarray(r) for r in routed):
      assert chose.shape == (64, 64) and (chose.sum(axis=1) == 8).all()
      touched.append(chose[:, :16].any(axis=0).sum())
      by_table.append(owns[layer][topic_of[tokens]][:, :16].any(axis=0).sum())
      as_owned.append((chose == owns[layer][topic_of[tokens]]).all(axis=1).mean())
  assert np.mean(as_owned) > 0.9  # a token's topic fixes its eight experts, in every expert layer
  assert np.mean(by_table) == pytest.approx(want, rel=0.1) and np.mean(touched) == pytest.approx(want, rel=0.1), (np.mean(touched), np.mean(by_table), want)
  assert np.mean(touched) < 0.8 * 16


def test_the_new_reader_finds_nothing_where_the_program_has_no_such_scope(monkeypatch):
  """``moe_experts_roofline``: None with no trace, None on a program whose decode programs carry no ``xot.moe_experts``
  (the parent on this cell; a dense model), None for a kind whose file has no ``moe_expert_bytes``; a share where both are."""
  import run
  import span_lib

  reader = run.load_reader("per_layer", "moe_experts_roofline")
  hf = common.load_config(CONFIG)
  assert reader.read({"hf": hf, "trace": None, "chunk": 8}) is None
  other = {"scope_s": {"attn": 1.0, "ffn": 2.0}, "scoped": True, "decode": {"executions": 10.0, "device_s": 3.0}, "dequant_s": 0.0}
  monkeypatch.setattr(span_lib, "capture", lambda ctx: other)
  base = {"trace": {"programs": {}}, "chunk": 8, "peaks": {"hbm_bytes_per_s": 819e9}, "cap_start": 0.0, "cap_end": 1.0, "recs": []}
  assert reader.read({**base, "hf": hf}) is None
  with_scope = {**other, "scope_s": {"moe_experts": 0.8}}
  monkeypatch.setattr(span_lib, "capture", lambda ctx: with_scope)
  assert reader.read({**base, "hf": hf}) == 0.0  # no resident row: no bytes
  assert reader.read({**base, "hf": common.load_config("moonlight-a3b-d14")}) is None  # a kind without the bytes function
  from client import Rec

  recs = []
  for _ in range(64):
    rec = Rec(0.0, 400, 256)
    rec.first, rec.events = 0.1, [(0.1, 1), (0.2, 99)]
    recs.append(rec)
  step_s = 0.8 / (10 * 8)  # scope seconds over executions x chunk
  assert reader.read({**base, "hf": hf, "recs": recs}) == pytest.approx(100.0 * (arch.load(KIND).moe_expert_bytes(hf, 64) / 819e9) / step_s)


def test_the_kda_moe_cells_rehearsal_ends_correct_with_no_failed_request(tmp_path):
  """``run.py --rehearse --workload ling-3.0-flash.decode-closed-64``: 64 callers through the API, the scheduler,
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
