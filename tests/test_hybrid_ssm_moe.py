"""Blocks of ONE sublayer, Mamba-2 with several B/C groups and a grouped gated norm, ungated relu² experts, on the served
path (ISSUE 53): NVIDIA-Nemotron-3-Nano's architecture at the benchmark's rehearsal widths — the pattern's first nine
letters ``MEMEM*EME``, which the program reads as five layer steps (M, E) (M, E) (M, —) (*, E) (M, E); 8 Mamba heads of
16 in 2 B/C groups (4 heads a group) with a state of 16; 8 query heads over 2 KV heads (4 a group) and no position
term; 16 experts top-4 of two matrices each, the last two decoys that the selection bias keeps out, plus a shared one
— against the benchmark's plain reference (``benchmark/arch_hybrid_ssm_moe.py reference_forward``: float32, one block a
LETTER, the recurrence token by token, the experts a loop over the chosen, nothing of the program), so that the pairing
itself is what every comparison tests.

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes,
the refusals, the probes — are ``tests/served_kind.py``'s battery, taken in below.
"""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import SLOTS, Kind, battery, rehearsal_of

import arch_hybrid_ssm_moe as arch_kind  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402

FILE, HF = rehearsal_of("nemotron-3-nano-30b-a3b-d9", arch_kind)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
KIND = Kind(
  name="nemotron", arch=arch_kind, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only. Measured 1.8e-6 at the worst
  # entry of logits of spread 1 (the cache-less forward; prefill and 40 decode steps 1.7e-6).
  tol=5e-5,
  # bfloat16 weights, activations and pages as served, the state, its decay and the router float32: measured 0.0054 in
  # the mean and 0.033 at the worst entry; 0.016 and 0.1 are three times the readings and a seventh and a sixth of what a
  # dropped last step reads (0.23 / 1.24).
  bf16=(0.016, 0.1),
  families=("nemotron_h", "nemotron-h"),
  pool={"k": (1, 1 + SLOTS * 8, 2, 16, 16), "v": (1, 1 + SLOTS * 8, 2, 16, 16), "ssm": (4, SLOTS, 8, 16, 16), "conv": (4, SLOTS, 3, 128 + 2 * 2 * 16)},
  scopes=frozenset({"xot.ssm", "xot.ssm_proj", "xot.moe_router", "xot.moe_experts", "xot.moe_shared"}),
  # every probe reads 0.65 (float8 operands) to 2.0 (gated experts) at the worst entry: ten thousand tolerances or more
  probe_floor=lambda name: 10000,
  state_step_form="reference",
  cases={
    "key,value,named": [
      ("hybrid_override_pattern", "MEMEM*EM-", "'-'"), ("hybrid_override_pattern", "MEEMM*EME", "cannot be read as (mixer, FFN) steps"), ("hybrid_override_pattern", "EMEMM*EME", "cannot be read as (mixer, FFN) steps"),
      ("hybrid_override_pattern", "MEMEM*EMEM", "num_hidden_layers"), ("hybrid_override_pattern", "MEMEM*EMX", "hybrid_override_pattern"),
      ("mamba_proj_bias", True, "mamba_proj_bias"), ("use_bias", True, "use_bias"), ("attention_bias", True, "attention_bias"), ("mlp_bias", True, "mlp_bias"),
      ("sliding_window", 4096, "sliding_window"), ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"), ("n_group", 4, "n_group > 1 with topk_group < n_group"),
      ("mlp_hidden_act", "silu", "mlp_hidden_act"), ("mamba_hidden_act", "relu", "mamba_hidden_act"), ("n_groups", 3, "n_groups"), ("n_routed_experts", 0, "n_routed_experts"),
    ],
  },  # fmt: skip
)  # fmt: skip
CFG, PARAMS, SHARD, TOKENS, TOL = KIND.cfg, KIND.params, KIND.shard, KIND.tokens, KIND.tol
globals().update(battery(KIND))

CATALOG_FILE = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CATALOG = pytest.mark.skipif(not CATALOG_FILE.exists(), reason="no catalog beside this checkout")
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


# ------------------------------------------------------------ the configuration


@CATALOG
def test_config_from_hf_maps_the_catalog_rows_52_letters_and_the_files_nine_with_no_edit_to_their_keys():
  """The published model whole, from the row's keys as they are: 52 blocks read as 29 layer steps — 17 (Mamba, experts),
  6 (Mamba, no FFN: the ``M`` ahead of each ``*``), 6 (attention, experts) —, 64 Mamba heads of 64 with a state of 128 in
  8 B/C groups, 32 query heads over 2 KV heads of 128 and no position term, 128 ungated relu² experts top-6 by sigmoid
  scores + a bias, renormalised and scaled by 2.5, a shared expert of 3712. And the nine-letter file is that with the
  depth cut alone."""
  row = next(json.loads(line) for line in open(CATALOG_FILE) if '"name": "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)["config"]
  whole = config_from_hf(row)
  assert whole.family == "nemotron-h" and whole.n_layers == 29 and row["hybrid_override_pattern"] == PUBLISHED and len(PUBLISHED) == row["num_hidden_layers"] == 52
  stacks = [whole.layer_stack(i) for i in range(29)]
  assert (stacks.count("ssm_moe_layers"), stacks.count("ssm_mixer_layers"), stacks.count("moe_layers")) == (17, 6, 6) and stacks[:5] == ["ssm_moe_layers", "ssm_moe_layers", "ssm_mixer_layers", "moe_layers", "ssm_moe_layers"]
  assert all(whole.layer_types[i + 1] == "attention" for i in range(28) if whole.layer_ffn[i] == "none") and whole.layer_ffn.count("none") == 6  # the FFN-less steps are the M ahead of each *
  assert (whole.recurrent_layers, whole.n_attn_layers, whole.expert_layers, whole.recurrent_kind) == (23, 6, 23, "mamba")
  assert (whole.ssm_heads, whole.ssm_head_dim, whole.ssm_state, whole.ssm_groups, whole.ssm_conv, whole.ssm_chunk, whole.ssm_inner, whole.ssm_conv_dim) == (64, 64, 128, 8, 4, 128, 4096, 6144)
  assert (whole.n_heads, whole.n_kv_heads, whole.head_dim, whole.dim, whole.vocab_size, whole.norm_eps, whole.max_seq_len) == (32, 2, 128, 2688, 131072, 1e-5, 262144)
  assert not (whole.use_rope or whole.qk_norm or whole.tied_embedding or whole.qkv_bias or whole.is_mla or whole.post_norms or whole.layer_attn) and whole.pre_norms and whole.plain_attention
  assert (whole.n_experts, whole.n_active_experts, whole.moe_hidden_dim, whole.shared_expert_dim, whole.first_k_dense, whole.experts_held) == (128, 6, 1856, 3712, 0, ())
  assert (whole.router_scoring, whole.norm_topk_prob, whole.routed_scaling_factor, whole.n_group, whole.group_mode, whole.router_input) == ("sigmoid", True, 2.5, 1, "none", "ffn")
  assert (whole.ffn_gated, whole.expert_act, whole.mlp_act) == (False, "relu2", "relu2")
  cfg = common.model_config(FILE)
  assert replace(whole, n_layers=5, layer_types=whole.layer_types[:5], layer_ffn=whole.layer_ffn[:5], max_seq_len=8192, eos_token_ids=()) == cfg  # the cut changes the depth alone
  changed = set(FILE["reduced"])
  assert changed == {"num_hidden_layers", "hybrid_override_pattern"} and FILE["hybrid_override_pattern"] == PUBLISHED[:9] and all(FILE[k] == v for k, v in row.items() if k not in changed)


def test_the_published_parameter_sum_by_shape_arithmetic_alone():
  """31,578 M parameters at the published widths and 52 letters — 23 x 1,297.5 M (E) + 23 x 38.74 M (M) + 6 x 23.40 M (*)
  + 704.6 M — from the shapes ``init_shard_params`` would make (no weight is made), which is what the benchmark's own
  count says (``arch_hybrid_ssm_moe.param_count``); the file's nine blocks are 6,073 M."""
  hf = {k: v for k, v in FILE.items() if not isinstance(v, dict)}
  for cut, want in (({"num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED}, 31577940288), ({}, 6072897024)):
    cfg = config_from_hf({**hf, **cut})
    shapes = jax.eval_shape(lambda cfg=cfg: dec.full_model_params(jax.random.PRNGKey(0), cfg)[0])
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == want == arch_kind.param_count({**hf, **cut})
  assert round(31577940288 / 1e6) == 31578 and round(arch_kind.active_params({**hf, "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED}) / 1e9, 2) == 3.23


def test_the_rehearsal_configuration_is_the_published_one_in_small():
  assert CFG.layer_types == ("mamba", "mamba", "mamba", "attention", "mamba") and CFG.layer_ffn == ("experts", "experts", "none", "experts", "experts")
  assert (CFG.ssm_groups, CFG.ssm_heads // CFG.ssm_groups, CFG.n_heads // CFG.n_kv_heads, CFG.n_experts, CFG.n_active_experts) == (2, 4, 4, 16, 4) and not CFG.ffn_gated
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"ssm_moe_layers": 3, "ssm_mixer_layers": 1, "moe_layers": 1}
  shapes = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
  assert shapes(jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == shapes(PARAMS)  # the benchmark's maker and the program's agree leaf for leaf
  assert not {"w_experts_gate", "w_experts_up", "w_shared_gate"} & set(PARAMS["ssm_moe_layers"]) and not {"mlp_norm", "w_router", "w_experts_down"} & set(PARAMS["ssm_mixer_layers"])
  runs = [(lo, hi, pool_lo) for _, lo, hi, pool_lo, _ in dec._layer_runs(PARAMS, CFG)]
  assert runs == [(0, 2, 0), (0, 1, 2), (0, 1, 0), (2, 3, 3)]  # (M,E)(M,E) | (M,—) | (*,E) | (M,E): four runs over three stacks, the Mamba steps layers 0-3 of the state leaves


# ------------------------------------------------------------ the pairing, on the whole pattern


def test_the_paired_reading_of_all_52_letters_equals_the_letter_by_letter_reference():
  """The published pattern whole at tiny widths: the program runs it as 29 (mixer, FFN) steps over three stacks, the
  reference as 52 blocks of one sublayer, and the cache-less forward's logits agree to the order of the sums."""
  hf = {**HF, "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED}
  params = jax.tree.map(lambda x: x.astype(jnp.float32), weights.build_params(hf, 3))
  cfg = config_from_hf(hf)
  assert cfg.n_layers == 29 and len(arch_kind.blocks(hf)) == 52
  tokens = TOKENS[:48]
  shard = replace(SHARD, end_layer=28, n_layers=29)
  got, _ = dec.jit_shard_forward(params, cfg, shard, jnp.asarray(tokens)[None], jnp.arange(len(tokens))[None], None)
  want = np.asarray(arch_kind.reference_forward(params, hf, jnp.asarray(tokens)))
  np.testing.assert_allclose(np.asarray(got[0]), want, atol=4 * TOL, rtol=0)


def test_every_exact_probe_moves_the_float32_reference_past_the_tolerance():
  """What bfloat16 serving over 168 positions cannot tell and float32 arithmetic does (``exact_probes``): each lies
  tolerances off the sound reference here."""
  sound = KIND.reference(TOKENS)
  for name, probe in arch_kind.exact_probes(HF).items():
    moved = float(np.abs(KIND.reference(TOKENS, **probe) - sound).max())
    assert moved > 10 * TOL, (name, moved)


# ------------------------------------------------------------ the mixer's groups


def _one_mixer(cfg, seed: int = 4):
  """One Mamba step's leaves at ``cfg``'s widths (float32, a conv bias that is not zero) and a sequence of 45 tokens."""
  p = {k: v[0] for k, v in dec.full_model_params(jax.random.PRNGKey(seed), replace(cfg, dtype=jnp.float32))[0]["ssm_mixer_layers"].items()}
  p["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), p["conv_b"].shape)
  h = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, 45, cfg.dim), jnp.float32)
  zeros = lambda cfg: (jnp.zeros((2, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32), jnp.zeros((2, cfg.ssm_conv - 1, cfg.ssm_conv_dim), jnp.float32))  # noqa: E731
  return p, h, zeros


def test_one_group_reproduces_granites_layer_bit_for_bit_and_equal_groups_are_one_group():
  """``ssm_groups`` 1 IS granite's mixer: the same leaves through ``_ssm_layer`` under a granite configuration and under
  this family's with ``n_groups`` 1 give the same bits (one code path: the one-group branch is the expressions granite
  always ran). And two groups that hold the same B and C give what one group gives, to the order of the sums — the
  grouped branch's einsums against the one-group branch's — with the norm over each half by itself the one difference,
  which a gain of one group's statistics shows."""
  bare = {**HF, "hybrid_override_pattern": "M", "num_hidden_layers": 1}
  one, two = config_from_hf({**bare, "n_groups": 1}), config_from_hf({**bare, "n_groups": 2})
  granite = config_from_hf({
    "model_type": "granitemoehybrid", "hidden_size": 64, "num_hidden_layers": 1, "layer_types": ["mamba"], "num_attention_heads": 8, "num_key_value_heads": 2, "vocab_size": 512,
    "intermediate_size": 96, "shared_intermediate_size": 96, "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 32, "torch_dtype": "float32",
    "position_embedding_type": "nope", "tie_word_embeddings": False,
  })  # fmt: skip
  p, h, zeros = _one_mixer(one)
  got = dec._ssm_layer(h, p, one, *zeros(one))
  want = dec._ssm_layer(h, p, replace(granite, ssm_chunk=one.ssm_chunk), *zeros(one))  # (no FFN leaf in ``p``: the step ends with the mixer in both)
  for a, b in zip(got, want):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
  # two groups holding the same B and C: w_xbc's and the convolution's B and C columns doubled
  di, N = one.ssm_inner, one.ssm_state
  twice = lambda t: jnp.concatenate([t[..., :di], t[..., di : di + N], t[..., di : di + N], t[..., di + N :], t[..., di + N :]], axis=-1)  # noqa: E731
  p2 = {**p, "w_xbc": twice(p["w_xbc"]), "conv_w": twice(p["conv_w"]), "conv_b": twice(p["conv_b"])}
  x, bm, cm = dec._ssm_split(jax.random.normal(jax.random.PRNGKey(9), (2, 45, two.ssm_conv_dim)), two)
  assert bm.shape == cm.shape == (2, 45, 2, 16) and x.shape == (2, 45, 8, 16)
  dt, a_log = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(10), (2, 45, 8)) - 2.0), -jnp.exp(p["A_log"])
  same = jnp.repeat(bm[:, :, :1], 2, axis=2), jnp.repeat(cm[:, :, :1], 2, axis=2)
  y2, s2 = dec._ssm_chunk_scan(x, dt, a_log, *same, zeros(two)[0], 32)
  y1, s1 = dec._ssm_chunk_scan(x, dt, a_log, bm[:, :, :1], cm[:, :, :1], zeros(one)[0], 32)
  np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=2e-5, rtol=0)
  np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5, rtol=0)
  whole, halves = dec._ssm_layer(h, p, one, *zeros(one))[0], dec._ssm_layer(h, p2, two, *zeros(two))[0]
  assert float(jnp.abs(whole - halves).max()) > 1e-3  # the norm over each group's channels by itself is not the norm over all of them


def test_a_step_with_no_ffn_ends_with_its_mixers_residual_and_counts_no_expert():
  """The (M, —) step: ``_mlp_block`` hands the stream back as it came and visits no expert; a decode chunk's visited
  count is the four expert steps' alone: 4 decode steps x 4 expert steps x the distinct experts of the four slot rows."""
  lp = {k: v[0] for k, v in PARAMS["ssm_mixer_layers"].items()}
  h = jax.random.normal(jax.random.PRNGKey(2), (2, 3, CFG.dim), jnp.float32)
  out, aux, visited = dec._mlp_block(h, lp, CFG)
  assert out is h and float(aux) == 0.0 and int(visited) == 0
  _, pool = KIND.prefill(KIND.fresh_pool(), {0: TOKENS[:20], 3: TOKENS[30:41]})
  *_, seen = KIND.decode_chunk(pool, [[TOKENS[20]], [1], [1], [TOKENS[41]]], [20, 0, 0, 11], [True, False, False, True], experts_visited=True)
  assert 4 * 4 * 4 <= int(seen) <= 4 * 4 * 16  # every slot row (the two that are not active compute too) chooses four experts in each of four expert steps, four decode steps


def test_the_counters_and_the_gauges_count_the_new_kind(served):
  """``moe_expert_layer_steps_total`` grows by FOUR expert steps a decode step (five layer steps, one of them without an
  FFN), ``moe_experts_visited_total`` with it, the gauges name relu² experts fed from their own input and the state's
  step on a CPU."""
  moved = lambda name: served.after.counter_value(name) - served.before.counter_value(name)  # noqa: E731
  assert served.server._expert_layers == 4 == CFG.expert_layers
  steps = moved("moe_expert_layer_steps_total")
  assert steps > 0 and steps % 4 == 0 and 0 < moved("moe_experts_visited_total") <= steps * 2 * 4  # at most the two slots' four experts a layer and step
  assert served.after.gauge_value("moe_expert_gate", labels={"act": "relu2"}) == 4 and served.after.gauge_value("moe_expert_gate", labels={"act": "silu"}) == 0
  assert served.after.gauge_value("moe_router_input", labels={"at": "ffn"}) == 4 and served.after.gauge_value("moe_ffn_form", labels={"form": "block"}) == 1


@pytest.mark.parametrize("config", ["mistral-7b-int8", "moonlight-a3b-d14", "granite-4.0-h-micro-bf16", "ling-3.0-flash-ep4-d7", "olmo-hybrid-7b-d12", "laguna-xs.2-d5", "smallthinker-21ba3b-d8"])
def test_the_seven_configurations_that_stood_map_to_the_modelconfig_they_mapped_to(config):
  """The per-layer FFN description, the B/C group count and the FFN's form are at their defaults for every
  configuration file the benchmark had — ``layer_ffn`` () is the ``first_k_dense`` rule, one group is granite's mixer, a
  gated FFN everyone's — so each maps to the ``ModelConfig``, the stacks and the leaves it mapped to (compared field by
  field with the parent's tree by hand: PERF.md section 6, PR 53)."""
  cfg = common.model_config(common.load_config(config))
  assert cfg.layer_ffn == () and cfg.ssm_groups == 1 and cfg.ffn_gated and cfg.expert_act in ("silu", "relu") and cfg.mlp_act == "silu"
  want = lambda i: ("experts" if cfg.n_experts and i >= cfg.first_k_dense else "dense")  # noqa: E731
  assert [cfg.ffn_kind(i) for i in range(cfg.n_layers)] == [want(i) for i in range(cfg.n_layers)] and not any(cfg.layer_stack(i).endswith("mixer_layers") for i in range(cfg.n_layers))
  assert cfg.expert_layers == (cfg.n_layers - cfg.first_k_dense if cfg.n_experts else 0)
  shapes = jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), cfg)[0])
  assert not any("w_experts_up_t" in stack for stack in shapes.values() if isinstance(stack, dict))
  assert all(("w_experts_gate" in stack) == ("w_experts_down" in stack) and ("w_gate" in stack) == ("w_down" in stack) for stack in shapes.values() if isinstance(stack, dict))
