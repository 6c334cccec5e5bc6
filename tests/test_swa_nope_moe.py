"""A router that decides ahead of the attention, ReLU-gated experts, and layers with no position term beside roped
window layers, on the served path (ISSUE 50): SmallThinker-21BA3B's architecture at the benchmark's rehearsal widths — a
global layer without rope, two roped window layers (window 8), a global layer; 6 query heads over 2 KV heads of 16
(groups of 3, odd like the published 7); in every layer 16 experts top-4 chosen by a softmax router that reads the
ATTENTION's normed input, computed from the stream after the attention's residual — against the benchmark's plain
reference (``benchmark/arch_swa_nope_moe.py reference_forward``: float32, a full [S, S] masked softmax a layer, every
expert computed densely, nothing of the program).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes —
are ``tests/served_kind.py``'s battery, taken in below; this kind runs its prefill-then-decode and its two-chunk cases
at prompts and cuts on both sides of the window, so a window layer drops keys while a global layer keeps them, with the
routing carried across the attention in every one of them.
"""

import hashlib
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import SLOTS, Kind, battery, rehearsal_of

import arch_swa_nope_moe  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import AttnKind, config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import moe  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import paged_decode_attention, paged_gqa_attention_ref  # noqa: E402
from xotorch_support_jetson_tpu.ops.rope import rope_inv_freq  # noqa: E402

FILE, HF = rehearsal_of("smallthinker-21ba3b-d8", arch_swa_nope_moe)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
W = 8  # the rehearsal's window
KIND = Kind(
  name="smallthinker", arch=arch_swa_nope_moe, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only. Measured 4e-6 at the worst
  # entry of logits of spread 1 (prefill, 20 decode steps and the cache-less forward alike).
  tol=5e-5,
  # bfloat16 weights, activations and pages as served, the router's logits float32, a prompt of 20 tokens (past the
  # window): each of the 4 layers' two blocks rounds its increment and the stream. The readings are beside the bounds in
  # ``test_the_bfloat16_path...``'s own assertion (mean, worst, a dropped layer's mean and worst); the mean is held to
  # three times its reading, the worst entry under half of the weakest wrong architecture's (a dropped layer). Measured
  # 0.0079 in the mean and 0.063 at the worst entry; a dropped layer reads 0.27 and 1.62.
  bf16=(0.025, 0.2),
  families=("smallthinker", "smallthinker"),
  pool={"k": (4, 1 + SLOTS * 16, 2, 4, 16), "v": (4, 1 + SLOTS * 16, 2, 4, 16)},  # one page leaf for both kinds of layer, in model order
  # router and experts under the ``xot.moe_*`` scopes there are (no shared expert: no ``xot.moe_shared``); every top-k
  # of the decode program but the sampler's lies under ``xot.moe_router``, one a run of layers (three runs).
  scopes=frozenset({"xot.moe_router", "xot.moe_experts"}),
  ops_under=((r"chlo\.top_k.*->.*x4x", 3, 3, r"xot\.moe_router"),),
  # the router after the attention or on the raw stream, silu experts, rope on the global layers or none on the window
  # layers, one expert fewer, a layer dropped, float8 operands; and of ``long_probes`` (64 positions under a window of 8
  # are past it) no window, a window on every layer: a thousand tolerances or more. (``exact_probes``: the case below.)
  probe_floor=lambda name: 1000,
  cases={
    # fewer tokens than the window (8), one short of it, the window exactly, one past it, past it and a page boundary inside it (pages of 4), far past it
    "prompt": [3, 7, 8, 9, 13, 30],
    "cut": [5, 12, 16],  # the cut under the window, past it inside a page, on a page's edge
    "key,value,named": [
      ("rope_layout", [0, 1, 1], "rope_layout"), ("rope_layout", [0, 1, 2, 0], "rope_layout"), ("sliding_window_layout", [0, 1, 1, 0, 0], "sliding_window_layout"),
      ("rope_layout", [0, 0, 0, 0], "differ in the window alone"), ("rope_layout", [0, 1, 0, 1], "differ in the window alone"),
      ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"), ("moe_primary_router_apply_softmax", False, "moe_primary_router_apply_softmax"),
      ("norm_topk_prob", False, "norm_topk_prob"), ("moe_num_secondary_experts", 8, "secondary"), ("moe_secondary_ffn_hidden_size", 64, "secondary"),
      ("attention_bias", True, "attention_bias"), ("sliding_window_size", 0, "sliding_window_size"), ("moe_num_primary_experts", 0, "moe_num_primary_experts"),
    ],
  },  # fmt: skip
  names={"test_prefill_then_decode_through_the_pool_equals_the_reference": "test_prefill_then_decode_through_the_pages_equals_the_reference_on_both_sides_of_the_window_with_the_routing_carried"},
  # pages of 4: a window of 8 spans two or three of them, so its first page is crossed inside it; 16 of them a row
  page_size=4, pages_per_row=16, n_tokens=64, pad=32, prompt=30, decode_steps=20, cut=16, chunked=27, tenants=(20, 6), bf16_prompt=20,
  scheduler_prompts=((0, 29), (30, 45), (50, 55)),  # two requests past the window and one under it
)  # fmt: skip
CFG, PARAMS, SHARD, TOKENS, TOL, PS = KIND.cfg, KIND.params, KIND.shard, KIND.tokens, KIND.tol, KIND.page_size
prefill, fresh_pool, reference, tables = KIND.prefill, KIND.fresh_pool, KIND.reference, lambda: KIND.tables
globals().update(battery(KIND))

CATALOG_FILE = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CATALOG = pytest.mark.skipif(not CATALOG_FILE.exists(), reason="no catalog beside this checkout")


# ------------------------------------------------------------ the configuration


def _catalog_row() -> dict:
  return next(json.loads(line) for line in open(CATALOG_FILE) if '"name": "SmallThinker-21BA3B-Instruct"' in line)["config"]


@CATALOG
def test_config_from_hf_maps_the_catalog_rows_keys_verbatim_and_the_file_is_that_cut_in_depth():
  """The published model whole, from the row's keys as they are plus the ``model_type`` it dropped: 52 layers, a global
  layer (no window, NO position term) before every three window layers (4096, plain rope at 1.5e6), 28 query heads over
  4 KV heads of 128 in both; every layer 64 experts of 768, top-6 by a softmax renormalised over the chosen, ReLU-gated,
  the router reading the attention's input; no shared expert, no dense layer and no dense FFN width. And the 8-layer
  file — with its harness scalar, which ``config_from_hf`` never reads — is that with the depth cut alone."""
  row = _catalog_row()
  whole = config_from_hf({**row, "model_type": "smallthinker"})
  full, window = AttnKind("full", 28, 0, 1500000.0, None, 1.0, False, rope=False), AttnKind("window", 28, 4096, 1500000.0, None, 1.0, False, rope=True)
  assert whole.family == "smallthinker" and whole.n_layers == 52 and whole.layer_attn == (full, window, window, window) * 13 and not whole.layer_types and not whole.recurrent_layers
  assert (whole.n_heads, whole.n_kv_heads, whole.head_dim, whole.dim, whole.hidden_dim, whole.vocab_size, whole.norm_eps, whole.max_seq_len) == (28, 4, 128, 2560, 0, 151936, 1e-6, 16384)
  assert not (whole.qk_norm or whole.tied_embedding or whole.qkv_bias or whole.is_mla or whole.post_norms) and whole.pre_norms and whole.use_rope
  assert (whole.n_experts, whole.n_active_experts, whole.moe_hidden_dim, whole.shared_expert_dim, whole.first_k_dense) == (64, 6, 768, 0, 0)
  assert (whole.router_scoring, whole.norm_topk_prob, whole.routed_scaling_factor, whole.n_group, whole.experts_held, whole.router_input, whole.expert_act) == ("softmax", True, 1.0, 1, (), "attn", "relu")
  assert [whole.layer_stack(i) for i in range(5)] == ["moe_layers", "window_moe_layers", "window_moe_layers", "window_moe_layers", "moe_layers"]
  assert whole.mixed_layers and whole.plain_attention and not whole.traced_window and whole.attn_windows == (0, 4096, 4096, 4096) * 13 and len(whole.attn_shapes) == 2
  cfg = common.model_config(FILE)
  assert replace(whole, n_layers=8, layer_attn=whole.layer_attn[:8], eos_token_ids=()) == cfg  # the cut changes the depth alone
  assert config_from_hf({k: v for k, v in FILE.items() if not isinstance(v, dict) and k != "global_attention_interval"}) == config_from_hf({k: v for k, v in FILE.items() if not isinstance(v, dict)})
  changed = set(FILE["reduced"])
  assert changed == {"num_hidden_layers", "rope_layout", "sliding_window_layout"} and all(FILE[k] == (v[:8] if isinstance(v, list) else 8) for k, v in row.items() if k in changed)
  assert all(FILE[k] == v for k, v in row.items() if k not in changed)  # every other published key as published


def test_the_rehearsal_configuration_is_the_published_one_in_small():
  assert CFG.layer_attn[0] == AttnKind("full", 6, 0, 1500000.0, None, 1.0, False, rope=False) and CFG.layer_attn[1] == AttnKind("window", 6, W, 1500000.0, None, 1.0, False, rope=True)
  assert CFG.attn_windows == (0, W, W, 0) and CFG.router_input == "attn" and CFG.expert_act == "relu" and (CFG.n_experts, CFG.n_active_experts) == (16, 4)
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"moe_layers": 2, "window_moe_layers": 2}
  shapes = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
  assert shapes(jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == shapes(PARAMS)  # the benchmark's maker and the program's agree leaf for leaf: no dense FFN leaf, no shared expert, no bias
  assert not {"w_gate", "w_up", "w_down", "w_shared_gate", "router_bias", "q_norm", "w_og"} & set(PARAMS["moe_layers"])
  tables_ = rope_inv_freq(CFG)  # one table: the window layers'; a kind without rope has none
  assert list(tables_) == [CFG.layer_attn[1]] and tables_[CFG.layer_attn[1]].shape == (8,)
  np.testing.assert_allclose(np.asarray(tables_[CFG.layer_attn[1]]), [1500000.0 ** (-2.0 * i / 16) for i in range(8)], rtol=2e-6)


def test_use_rope_false_and_a_kind_without_rope_are_one_switch():
  """granite's and Olmo's model-level ``use_rope`` False reaches a layer step as the per-layer field's value
  (``attn_kind``), and ``_dense_qkv`` reads either: a NoPE model's q and k are the projections as they are."""
  from xotorch_support_jetson_tpu.models.config import tiny_test_config

  nope, roped = tiny_test_config(use_rope=False), tiny_test_config()
  assert not nope.attn_kind(0).rope and roped.attn_kind(0).rope
  params, _ = dec.full_model_params(jax.random.PRNGKey(0), roped)
  p = {name: leaf[0] for name, leaf in params["layers"].items()}
  x, pos = jnp.asarray(np.random.default_rng(0).normal(size=(1, 5, 64)), jnp.float32), jnp.arange(5)[None] + 7
  q0, k0, _ = dec._dense_qkv(x, p, nope, pos, rope_inv_freq(nope))
  q1, k1, _ = dec._dense_qkv(x, {**p, "attn_kind": replace(roped.attn_kind(0), rope=False)}, roped, pos, {})
  q2, _, _ = dec._dense_qkv(x, p, roped, pos, rope_inv_freq(roped))
  np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
  np.testing.assert_array_equal(np.asarray(k0), np.asarray(k1))
  np.testing.assert_array_equal(np.asarray(q0), np.asarray((x @ p["wq"]).reshape(1, 5, 4, 16)))
  assert float(jnp.abs(q2 - q0).max()) > 0.1


# ------------------------------------------------------------ the routing is an operand of its own


def _experts_case(T: int, D: int = 128, F: int = 128, E: int = 8, seed: int = 0):
  rng = np.random.default_rng(seed)
  f32 = lambda *shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)  # noqa: E731
  return f32(T, D), f32(T, D), f32(D, E, scale=D**-0.5), f32(E, D, F, scale=D**-0.5), f32(E, D, F, scale=D**-0.5), f32(E, F, D, scale=F**-0.5)


def _dense_experts(y, routed, w_gate, w_up, w_down, act):
  gates = jnp.zeros((y.shape[0], w_gate.shape[0]), jnp.float32).at[jnp.arange(y.shape[0])[:, None], routed.idx].add(routed.weights)
  nonlinear = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]
  return sum(gates[:, e, None] * ((nonlinear(y @ w_gate[e]) * (y @ w_up[e])) @ w_down[e]) for e in range(w_gate.shape[0]))


@pytest.mark.parametrize("act", ["relu", "silu"])
@pytest.mark.parametrize("T", [5, 300])
def test_the_block_form_takes_a_routing_drawn_from_another_tensor_and_either_gate(T, act):
  """``moe_ffn`` handed the routing of ``x`` (the attention's input) computes the experts of ``y`` (the stream after
  it) under that choice: the dense sum over the chosen experts, ReLU- or silu-gated — one block (5 tokens) and a long
  run cut into blocks of 256 with a padded tail (300), whose routing is cut as the tokens are. Routed from ``y`` itself
  the answer differs: the operand is what decides."""
  x, y, w_router, w_gate, w_up, w_down = _experts_case(T)
  routed = moe.route(x, w_router, 3, "softmax", True)
  out, aux, visited = moe.moe_ffn(y, w_router, w_gate, w_up, w_down, k=3, norm_topk=True, act=act, routed=routed)
  np.testing.assert_allclose(np.asarray(out), np.asarray(_dense_experts(y, routed, w_gate, w_up, w_down, act)), atol=2e-5, rtol=0)
  assert np.isfinite(float(aux)) and 1 <= int(visited) <= 8 * -(-T // 256)
  own, _, _ = moe.moe_ffn(y, w_router, w_gate, w_up, w_down, k=3, norm_topk=True, act=act)
  np.testing.assert_allclose(np.asarray(own), np.asarray(_dense_experts(y, moe.route(y, w_router, 3, "softmax", True), w_gate, w_up, w_down, act)), atol=2e-5, rtol=0)
  assert float(jnp.abs(own - out).max()) > 0.05


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_grouped_form_takes_the_routing_and_the_gate_through_its_mosaic_body(act, monkeypatch):
  """The grouped form (the Mosaic bodies under ``interpret``) with the stacked leaves and a layer to take, the routing
  drawn from another tensor: the dense sum again, ReLU-gated through ``moe_gate_up``'s body; and a run of two pieces
  (``GROUPED_MAX_TOKENS`` 16) cuts the routing with the tokens."""
  monkeypatch.setattr(moe, "INTERPRET", True)
  x, y, w_router, w_gate, w_up, w_down = _experts_case(24, seed=1)
  stacked = tuple(jnp.stack([jnp.zeros_like(w), w]) for w in (w_gate, w_up, w_down))
  assert moe.ffn_form(stacked[0], stacked[2], None, True) == "grouped"
  routed = moe.route(x, w_router, 3, "softmax", True)
  want = np.asarray(_dense_experts(y, routed, w_gate, w_up, w_down, act))
  out, _, visited = moe.moe_ffn(y, w_router, *stacked, k=3, norm_topk=True, layer=1, act=act, routed=routed)
  np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=0)
  assert int(visited) == len(np.unique(np.asarray(routed.idx)))
  monkeypatch.setattr(moe, "GROUPED_MAX_TOKENS", 16)
  pieces, _, _ = moe.moe_ffn(y, w_router, *stacked, k=3, norm_topk=True, layer=1, act=act, routed=routed)
  np.testing.assert_allclose(np.asarray(pieces), want, atol=2e-5, rtol=0)
  text = str(jax.make_jaxpr(lambda t: moe.moe_ffn(t, w_router, *stacked, k=3, norm_topk=True, layer=1, act=act, routed=routed)[0])(y))
  assert text.count("pallas_call") == 4 and ("logistic" in text) == (act == "silu")


# What the three expert families of the benchmark hand ``moe_ffn`` (models/decoder.py ``_mlp_block`` from their
# configurations: Laguna sigmoid + bias, normalised, x 2.5; Ling sigmoid + bias in 8 groups of which 4, normalised,
# x 2.5, a quarter of the experts held; Moonlight sigmoid + bias, one group, normalised, x 2.446) and the sha256 of the
# jaxpr each traced to at the parent of PR 50 (b6a476b), block form and grouped form: the operand and the gate this PR
# adds go through code all three run, and their programs must not move. A change to ops/moe.py that means to move them
# re-records these (``python tests/test_swa_nope_moe.py``); a change of the jax version may reword a jaxpr, too.
# "grouped" is the shared walk — what a decode step takes, and all there was until ISSUE 56: its jaxprs, kernel bodies
# and all, are still the parent of PR 50's. "aligned" is the other walk over the same shapes (300 tokens x top 8 or 6
# over 32 experts: 56-75 rows an expert, on either side of the rule's threshold, so each walk is named here and the
# rule has a test of its own, tests/test_moe.py), recorded at PR 56.
_FAMILIES = {
  "laguna": dict(k=8, scoring="sigmoid", norm_topk=True, scale=2.5, bias=True),
  "ling": dict(k=8, scoring="sigmoid", norm_topk=True, scale=2.5, bias=True, n_group=8, topk_group=4, group_mode="top2sum", held=(8, 16)),
  "moonlight": dict(k=6, scoring="sigmoid", norm_topk=True, scale=2.446, bias=True, n_group=1, topk_group=1, group_mode="top2sum"),
}
_RECORDED = {
  ("laguna", "block"): "79cd60471d79a23a127024884fe633e89c5029e1c6da75585c913e13186afcf7",
  ("laguna", "grouped"): "848b39f0b7d9a00debd6131fb8e127e7065796b12e1c741b22d99e8c34d99ba3",
  ("ling", "block"): "0898d0689e8ab68eb927988470460dede1d783f7bc5c2b836920e618a262647b",
  ("ling", "grouped"): "d2f8a0d70f0c03f438c9b36aeaf9b7f18dd73572cca43f0a006386e15954166d",
  ("moonlight", "block"): "aa40e754dbc190ea73d29d431ca10f7ad29df5b9209c4fba1ffc1d9dd7313d61",
  ("moonlight", "grouped"): "448109871c51dabd590ddac434dd509a1c09943f5b31bea161c211afda4ae39f",
  ("laguna", "aligned"): "2a3d2301c38615eb7e2b092b2e936df9ee732adee3305deea6575e2cf20a596c",
  ("ling", "aligned"): "d071aa1cd2a324df5df0d12b35715cf9ffdaf9a95a948f8e9c27ff14cc493374",
  ("moonlight", "aligned"): "d7c5670d81181f77e5a4d97ed290e8589e46680c419412d9b58060ab094f7425",
}
_WALK = {"grouped": "shared", "aligned": "aligned"}  # the walk each recorded grouped-form jaxpr was traced on


def _family_jaxpr(family: str, form: str) -> str:
  args = dict(_FAMILIES[family])
  E, held = 32, args.get("held")
  Eh = held[1] - held[0] if held else E
  sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)  # noqa: E731
  bias = jnp.zeros((E,), jnp.float32) if args.pop("bias") else None
  lead = () if form == "block" else (2,)
  call = lambda x, w_router, w_gate, w_up, w_down: moe.moe_ffn(x, w_router, w_gate, w_up, w_down, selection_bias=bias, **args, **({} if form == "block" else {"layer": 1}))  # noqa: E731
  rule, moe.grouped_walk = moe.grouped_walk, lambda rows, *a, **kw: (_WALK.get(form), moe.ROW_TILE)
  try:
    with jax.default_matmul_precision("highest"):  # (named here, so that the text does not turn on who runs it)
      return str(jax.make_jaxpr(call)(sds(300, 128), sds(128, E), sds(*lead, Eh, 128, 256), sds(*lead, Eh, 128, 256), sds(*lead, Eh, 256, 128)))
  finally:
    moe.grouped_walk = rule


@pytest.mark.parametrize("form", ["block", "grouped", "aligned"])
@pytest.mark.parametrize("family", list(_FAMILIES))
def test_the_other_families_expert_programs_trace_to_the_jaxprs_they_had(family, form, monkeypatch):
  monkeypatch.setattr(moe, "INTERPRET", True)
  text = _family_jaxpr(family, form)
  assert ("pallas_call" in text) == (form != "block") and "logistic" in text and ("_rows" in text) == (form == "aligned")
  assert hashlib.sha256(text.encode()).hexdigest() == _RECORDED[family, form]


# ------------------------------------------------------------ the program against the reference, the routing carried


def test_the_router_after_the_attention_is_another_model():
  """The same weights with the router at the usual place (``router_input`` "ffn") or silu experts are other models: the
  program's cache-less forward then equals the reference's probe of that name and leaves the published reading by
  thousands of tolerances — the CPU tests' tolerance refuses both, as it refuses rope on a global layer and a window
  layer left full (the battery's probe case)."""
  toks, pos = jnp.asarray(TOKENS[:40])[None], jnp.arange(40)[None]
  sound = reference(TOKENS[:40])
  for change, probe in (({"router_input": "ffn"}, {"router_reads": "ffn_normed"}), ({"expert_act": "silu"}, {"act": "silu"})):
    got, _ = dec.jit_shard_forward(PARAMS, replace(CFG, **change), SHARD, toks, pos, None)
    np.testing.assert_allclose(np.asarray(got[0]), reference(TOKENS[:40], **probe), atol=TOL, rtol=0)
    assert float(np.abs(np.asarray(got[0]) - sound).max()) > 1000 * TOL


def test_a_mixed_ticks_slice_and_its_decode_half_equal_the_two_programs_apart():
  """``decode.mixed_paged_batch``: rows 0 and 3 decode a chunk of 4 steps while slot 1's prompt advances by the slice
  [12, 24) — past the window, through layers whose routing is drawn ahead of their attention in both halves of the tick.
  The decode half's tokens and the pool are what ``decode.paged_batch`` and the chunked prefill give apart, and slot 1
  then finishes as the reference."""
  toks = TOKENS[:30]
  _, pool = prefill(fresh_pool(), {0: TOKENS[:20], 3: TOKENS[30:50]}, pad_to=32)
  _, pool = prefill(pool, {1: toks[:12]}, pad_to=16)
  active, pos = np.asarray([True, False, False, True]), np.asarray([20, 0, 0, 20], np.int32)
  first = jnp.asarray([[TOKENS[20]], [0], [0], [TOKENS[50]]], jnp.int32)
  common_args = dict(temps=np.zeros((SLOTS,), np.float32), n_steps=4, page_size=PS, use_kernel=False)
  pf = np.zeros((1, 16), np.int32)
  pf[0, :12] = toks[12:24]
  mixed_toks, _, mixed_pos, mixed_pool = dec.fused_mixed_paged_batch_decode(
    PARAMS, CFG, SHARD, first, jax.tree.map(jnp.copy, pool), tables(), jnp.asarray(pos), jnp.asarray(active), pf_tokens=pf, pf_bt=tables()[1:2], pf_prefix=np.asarray([12], np.int32), pf_end=np.asarray([24], np.int32), **common_args,
  )
  _, apart = prefill(pool, {1: toks[:24]}, prefix={1: 12}, pad_to=16)
  plain_toks, _, plain_pos, apart = dec.fused_paged_batch_decode(PARAMS, CFG, SHARD, first, apart, tables(), jnp.asarray(pos), jnp.asarray(active), **common_args)
  assert np.asarray(mixed_toks).tolist() == np.asarray(plain_toks).tolist() and np.asarray(mixed_pos).tolist() == np.asarray(plain_pos).tolist() == [24, 0, 0, 24]
  assert KIND.greedy_under_the_reference(TOKENS[:21], np.asarray(mixed_toks)[0, :3])
  for name in ("k", "v"):
    np.testing.assert_allclose(np.asarray(mixed_pool[name][:, 1:]), np.asarray(apart[name][:, 1:]), atol=TOL, rtol=0)
  last, _ = prefill(mixed_pool, {1: toks}, prefix={1: 24}, pad_to=16)
  np.testing.assert_allclose(np.asarray(last[0]), reference(toks)[-1], atol=TOL, rtol=0)


def test_the_slot_cache_and_the_speculative_verify_window_equal_the_reference():
  """The paths beside the page pool's two programs: ``shard_forward`` over a slot-indexed cache prefills 20 tokens and
  decodes 20 more, and ``paged_window_forward`` (speculation's verify) scores three positions past the window through
  the pages: all are the reference's logits, the routing carried across the attention in each."""
  want = reference(TOKENS[:40])
  cache = dec.init_kv_cache(CFG, CFG.n_layers, 1, 64)
  logits, cache = dec.jit_shard_forward(PARAMS, CFG, SHARD, jnp.asarray(TOKENS[:20])[None], jnp.arange(20)[None], cache)
  np.testing.assert_allclose(np.asarray(logits[0]), want[:20], atol=TOL, rtol=0)
  for t in range(20, 40):
    logits, cache = dec.jit_shard_forward(PARAMS, CFG, SHARD, jnp.asarray([[TOKENS[t]]]), jnp.asarray([[t]]), cache)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t], atol=TOL, rtol=0, err_msg=f"slot-cache decode at position {t}")
  _, pool = prefill(fresh_pool(), {1: TOKENS[:20]}, pad_to=32)
  toks, pos = np.zeros((SLOTS, 3), np.int32), np.zeros((SLOTS, 3), np.int32)
  toks[1], pos[1] = TOKENS[20:23], [20, 21, 22]
  logits, _ = dec.paged_window_forward(PARAMS, CFG, SHARD, jnp.asarray(toks), jnp.asarray(pos), pool, jnp.asarray(np.where(np.arange(SLOTS)[:, None] == 1, tables(), 0)), PS)
  np.testing.assert_allclose(np.asarray(logits[1]), want[20:23], atol=TOL, rtol=0)


def test_the_exact_probes_move_the_reference_by_what_only_float32_tells():
  """``exact_probes``: a softmax left unrenormalised (under a topic router that sets its four experts well clear of the
  rest it loses the rest's mass alone: 0.013 in a logit), the router rounded to bfloat16 (a gate's last bits) and a
  window of 7 for 8 lie a hundred of this module's tolerances or more from the sound reference — the CPU tests refuse
  them, and no limit of the chip's ``correct`` is asked to."""
  sound, probes = reference(TOKENS), arch_swa_nope_moe.exact_probes(HF)
  assert set(probes) == {"softmax_not_renormalised", "router_bfloat16", "window_7"} and not set(probes) & (set(arch_swa_nope_moe.probes(HF)) | set(arch_swa_nope_moe.long_probes(HF)))
  for name, probe in probes.items():
    assert float(np.abs(reference(TOKENS, **probe) - sound).max()) > 100 * TOL, name


def test_the_seeded_experts_are_neither_dead_nor_exploding():
  """ReLU gates zero half an expert's units by construction: over the rehearsal's four layers the attention's and the
  experts' increments each stay a fraction of the stream (under its rms, over a hundredth of it), the stream's rms
  stays within a factor of three of the embedding's, and every token is routed to exactly four experts a layer."""
  increments, routed = [], []
  KIND.arch.reference_forward(PARAMS, HF, jnp.asarray(TOKENS[:40]), increments=increments, routed=routed)
  assert len(increments) == 4 and all(0.01 * s < a < s and 0.01 * s < e < s for s, a, e in increments), increments
  assert increments[-1][0] < 3 * increments[0][0]
  assert all(np.asarray(r).sum(axis=-1).tolist() == [4] * 40 for r in routed)


# ------------------------------------------------------------ the paged kernel at an odd group


@pytest.mark.parametrize("window", [0, 24, 64])
def test_the_paged_kernel_at_groups_of_seven_equals_the_masked_softmax(window):
  """28 query heads over 4 KV heads of 128 — groups of 7, which no other configuration has — in rows of 0, 5, 40, 64 and
  150 tokens, with and without a window (interpret mode): the kernel equals the gather reference's masked softmax."""
  rng = np.random.default_rng(7)
  hkv, hd, ps, pages, mp = 4, 128, 8, 80, 24
  lengths = np.asarray([0, 5, 40, 64, 150], np.int32)
  bt, nxt = np.zeros((len(lengths), mp), np.int32), 1
  for b, n in enumerate(-(-lengths // ps)):
    bt[b, :n], nxt = np.arange(nxt, nxt + n), nxt + n
  k, v = (jnp.asarray(rng.normal(size=(2, pages, hkv, ps, hd)), jnp.float32) for _ in range(2))
  q = jnp.asarray(rng.normal(size=(len(lengths), 28, hd)), jnp.float32)
  got = paged_decode_attention(q, k, v, jnp.asarray(bt), jnp.asarray(lengths), ps, interpret=True, layer=1, window=window)
  want = paged_gqa_attention_ref(q[:, None], k, v, jnp.asarray(bt), jnp.asarray(lengths), ps, layer=1, **({"sliding_window": window} if window else {}))[:, 0]
  np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:]), atol=2e-6, rtol=0)
  assert not np.asarray(got[0]).any()


# ------------------------------------------------------------ the scheduler


def test_the_served_gauges_say_where_the_router_reads_which_gate_and_which_layers_have_a_rope(served):
  """After the battery's interleaved requests (two past the window, one under it; mixed ticks on): the gauges say how
  many layers have a window and how wide, how many of each kind have a position term, where the router of how many
  layers reads and which gate their experts have; the page counters what the windows let the kernel read."""
  after = served.after
  assert after.gauge_value("attention_layers", labels={"kind": "full"}) == 2 and after.gauge_value("attention_layers", labels={"kind": "window"}) == 2 and after.gauge_value("attention_window_tokens") == W
  assert after.gauge_value("attention_rope_layers", labels={"rope": "none"}) == 2 and after.gauge_value("attention_rope_layers", labels={"rope": "rope"}) == 2
  assert after.gauge_value("moe_router_input", labels={"at": "attn"}) == 4 and after.gauge_value("moe_router_input", labels={"at": "ffn"}) == 0
  assert after.gauge_value("moe_expert_gate", labels={"act": "relu"}) == 4 and after.gauge_value("moe_expert_gate", labels={"act": "silu"}) == 0
  held, read = (after.counter_value(f"kv_pages_{name}_total") - served.before.counter_value(f"kv_pages_{name}_total") for name in ("resident", "read"))
  assert 0 < read < held and held % 4 == 0  # rows past the window: the window layers read 2-3 pages of the 4-9 a row holds


if __name__ == "__main__":  # re-record the families' jaxprs (see ``_RECORDED``)
  moe.INTERPRET = True
  print({key: hashlib.sha256(_family_jaxpr(*key).encode()).hexdigest() for key in _RECORDED})
