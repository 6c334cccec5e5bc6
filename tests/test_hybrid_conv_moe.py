"""Gated short-convolution layers whose whole per-slot state is the convolution's tail, beside GQA layers with per-head
q/k norms and sigmoid-routed SwiGLU experts behind two dense layers, on the served path (ISSUE 57): LFM2-8B-A1B's
architecture at the benchmark's rehearsal widths — the first eight layers ``c c A c c c A c``, 3 taps over 64 channels;
8 query heads over 2 KV heads (4 a group) of 8 with rope; a dense SwiGLU of 96 in layers 0 and 1, then 16 experts of
48 top-4, the first two decoys that the selection bias keeps out — against the benchmark's plain reference
(``benchmark/arch_hybrid_conv_moe.py reference_forward``: float32, no cache, no tail, nothing of the program).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes,
the refusals, the probes — are ``tests/served_kind.py``'s battery, taken in below. What only this kind has is here: the
pool has NO ``ssm`` leaf, the tail is of the gated product ``B * x`` and not of ``x``, prompts and chunks shorter than
the tail, a prompt longer than the prefill chunk, a preempted row's recompute, 128 slots.
"""

import asyncio
import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import SLOTS, Kind, battery, rehearsal_of, serve

import arch_hybrid_conv_moe as arch_kind  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.inference.qos import QosConfig, QosPolicy  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import RECURRENT_KINDS, STATE_MATRIX_KINDS, config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, state_leaves  # noqa: E402
from xotorch_support_jetson_tpu.utils.metrics import metrics  # noqa: E402

FILE, HF = rehearsal_of("lfm2-8b-a1b-d16", arch_kind)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
KIND = Kind(
  name="lfm2", arch=arch_kind, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only. Measured 1.4e-6 at the worst
  # entry of logits of spread 1 (the cache-less forward; prefill and 40 decode steps 1.2e-6).
  tol=5e-5,
  # bfloat16 weights, activations, pages and tails as served, the router and the gates' products float32: measured 0.0113
  # in the mean and 0.091 at the worst entry; 0.034 and 0.27 are three times the readings and under half of what a
  # dropped last layer reads (BF16_DROPPED, asserted by the case).
  bf16=(0.034, 0.27),
  families=("lfm2_moe", "lfm2-moe"),
  pool={"k": (2, 1 + SLOTS * 8, 2, 16, 8), "v": (2, 1 + SLOTS * 8, 2, 16, 8), "conv": (6, SLOTS, 2, 64)},
  scopes=frozenset({"xot.ssm", "xot.ssm_proj", "xot.moe_router", "xot.moe_experts"}),
  # every probe reads 0.25 (float8 operands) to 1.2 (unnormalised weights) at the worst entry: thousands of tolerances
  probe_floor=lambda name: 2000,
  state_step_form="no_state_matrix",
  cases={
    "key,value,named": [
      ("conv_bias", True, "conv_bias"), ("attention_bias", True, "attention_bias"), ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
      ("layer_types", ["conv"] * 7 + ["sliding_attention"], "layer_types"), ("layer_types", ["conv"] * 9, "layer_types"), ("conv_L_cache", 1, "conv_L_cache"),
    ],
  },  # fmt: skip
)  # fmt: skip
CFG, PARAMS, SHARD, TOKENS, TOL = KIND.cfg, KIND.params, KIND.shard, KIND.tokens, KIND.tol
globals().update(battery(KIND))

CATALOG_FILE = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CATALOG = pytest.mark.skipif(not CATALOG_FILE.exists(), reason="no catalog beside this checkout")


# ------------------------------------------------------------ the configuration


@CATALOG
def test_config_from_hf_maps_the_catalog_rows_keys_with_no_edit_and_the_file_is_its_first_sixteen_layers():
  """The published model whole, from the row's keys as they are: 18 gated short-convolution layers and 6 full-attention
  ones, two leading dense layers of 7168, then 32 experts of 1792 top-4 by sigmoid scores + a selection bias,
  renormalised, scaled by 1; 32 query heads over 8 KV heads of 64 with a norm a head and rope 1e6; ``norm_eps`` read,
  the head tied. And the file is that with the depth cut alone."""
  row = next(json.loads(line) for line in open(CATALOG_FILE) if '"name": "LFM2-8B-A1B"' in line)["config"]
  whole = config_from_hf(row)
  assert whole.family == "lfm2-moe" and whole.n_layers == 24 and (whole.layer_types.count("conv"), whole.layer_types.count("attention")) == (18, 6)
  assert whole.layer_types[:16] == ("conv", "conv", "attention", "conv", "conv", "conv", "attention", "conv", "conv", "conv", "attention", "conv", "conv", "conv", "attention", "conv")
  stacks = [whole.layer_stack(i) for i in range(24)]
  assert stacks[:3] == ["ssm_layers", "ssm_layers", "moe_layers"] and (stacks.count("ssm_layers"), stacks.count("ssm_moe_layers"), stacks.count("moe_layers"), stacks.count("layers")) == (2, 16, 6, 0)
  assert (whole.recurrent_layers, whole.n_attn_layers, whole.expert_layers, whole.recurrent_kind, whole.state_matrix) == (18, 6, 22, "conv", False)
  assert (whole.ssm_conv, whole.ssm_conv_dim, whole.ssm_heads, whole.ssm_head_dim, whole.ssm_state) == (3, 2048, 0, 0, 0)
  assert (whole.n_heads, whole.n_kv_heads, whole.head_dim, whole.dim, whole.hidden_dim, whole.vocab_size, whole.norm_eps, whole.rope_theta, whole.max_seq_len) == (32, 8, 64, 2048, 7168, 65536, 1e-5, 1e6, 128000)
  assert whole.qk_norm and not whole.qk_norm_whole and whole.tied_embedding and whole.use_rope and whole.rope_scaling is None and not (whole.qkv_bias or whole.is_mla or whole.post_norms or whole.layer_attn) and whole.plain_attention
  assert (whole.n_experts, whole.n_active_experts, whole.moe_hidden_dim, whole.shared_expert_dim, whole.first_k_dense, whole.experts_held) == (32, 4, 1792, 0, 2, ())
  assert (whole.router_scoring, whole.router_selection_bias, whole.norm_topk_prob, whole.routed_scaling_factor, whole.group_mode, whole.router_input, whole.ffn_gated, whole.expert_act) == ("sigmoid", True, True, 1.0, "none", "ffn", True, "silu")
  cfg = common.model_config(FILE)
  assert replace(whole, n_layers=16, layer_types=whole.layer_types[:16], max_seq_len=4096, eos_token_ids=()) == cfg  # the cut changes the depth alone
  changed = set(FILE["reduced"])
  assert changed == {"num_hidden_layers", "layer_types"} and FILE["layer_types"] == row["layer_types"][:16] and all(FILE[k] == v for k, v in row.items() if k not in changed)
  assert "tie_word_embeddings" not in row and not config_from_hf({**row, "tie_word_embeddings": False}).tied_embedding  # the family ties unless a file says otherwise


def test_the_published_parameter_sum_by_shape_arithmetic_alone():
  """8,340 M parameters at the published widths and 24 layers — 18 x 16.79 M (conv) + 6 x 10.49 M (attention) + 2 x
  44.04 M (dense) + 22 x 352.39 M (experts) + 134.22 M (the tied table) — from the shapes ``init_shard_params`` would
  make (no weight is made), which is what the benchmark's own count says; the file's sixteen layers are 5,399 M."""
  hf = {k: v for k, v in FILE.items() if not isinstance(v, dict)}
  published = {"num_hidden_layers": 24, "layer_types": FILE["published"]["layer_types"], "layer_pattern": "ccAcccAcccAcccAcccAccAcc"}
  for cut, want in ((published, 8339930560), ({}, 5399129024)):
    cfg = config_from_hf({**hf, **cut})
    shapes = jax.eval_shape(lambda cfg=cfg: dec.full_model_params(jax.random.PRNGKey(0), cfg)[0])
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == want == arch_kind.param_count({**hf, **cut})
  assert round(8339930560 / 1e6) == 8340 and round(5399129024 / 1e6) == 5399 and arch_kind.active_params({**hf, **published}) // 10**6 == 1557


def test_the_rehearsal_configuration_is_the_published_one_in_small():
  assert CFG.layer_types == ("conv", "conv", "attention", "conv", "conv", "conv", "attention", "conv") and CFG.first_k_dense == 2 and not CFG.state_matrix
  assert (CFG.n_heads // CFG.n_kv_heads, CFG.n_experts, CFG.n_active_experts, CFG.ssm_conv, CFG.ssm_conv_dim) == (4, 16, 4, 3, 64)
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"ssm_layers": 2, "moe_layers": 2, "ssm_moe_layers": 4}
  shapes = lambda tree: jax.tree.map(lambda x: x.shape, tree)  # noqa: E731
  assert shapes(jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == shapes(PARAMS)  # the benchmark's maker and the program's agree leaf for leaf
  assert "lm_head" not in PARAMS and set(PARAMS["ssm_layers"]) == {"ssm_norm", "w_in", "conv_w", "w_out", "mlp_norm", "w_gate", "w_up", "w_down"} and "conv_b" not in PARAMS["ssm_moe_layers"]
  runs = [(lo, hi, pool_lo) for _, lo, hi, pool_lo, _ in dec._layer_runs(PARAMS, CFG)]
  assert runs == [(0, 2, 0), (0, 1, 0), (0, 3, 2), (1, 2, 1), (3, 4, 5)]  # cc | A | ccc | A | c: the conv layers are layers 0-5 of the tail leaf, the attention layers 0-1 of the pages


def test_use_expert_bias_false_is_served_with_no_bias_leaf():
  cfg = config_from_hf({**HF, "use_expert_bias": False})
  assert not cfg.router_selection_bias and cfg.router_scoring == "sigmoid"
  shapes = jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), cfg)[0])
  assert "router_bias" not in shapes["ssm_moe_layers"] and "router_bias" not in shapes["moe_layers"] and "router_bias" in PARAMS["moe_layers"]
  params = {name: ({k: v for k, v in st.items() if k != "router_bias"} if isinstance(st, dict) else st) for name, st in PARAMS.items()}
  got, _ = dec.jit_shard_forward(params, cfg, SHARD, jnp.asarray(TOKENS[:40])[None], jnp.arange(40)[None], None)
  np.testing.assert_allclose(np.asarray(got[0]), KIND.reference(TOKENS[:40], no_router_bias=True), atol=TOL, rtol=0)


# ------------------------------------------------------------ one owner of "no state matrix"


def test_a_kind_with_no_state_matrix_gets_no_ssm_leaf_of_any_size_and_one_property_says_so():
  """``cfg.state_matrix`` is the one owner: the pool makes ``conv`` alone (no zero-sized ``ssm``), the gauge's form
  names it, and no module of the program but ``models/config.py`` compares a recurrent kind with "conv"."""
  pool = KIND.fresh_pool()
  assert set(state_leaves(pool)) == {"conv"} and "ssm" not in pool and pool["conv"].shape == (CFG.recurrent_layers, SLOTS, CFG.ssm_conv - 1, CFG.dim) and pool["conv"].dtype == jnp.float32
  assert set(RECURRENT_KINDS) - set(STATE_MATRIX_KINDS) == {"conv"} and ssm_ops.state_step_form(pool.get("ssm"), False, "conv") == "no_state_matrix" == ssm_ops.STATE_STEP_FORMS[-1]
  granite = config_from_hf({**common.load_config("granite-4.0-h-micro-bf16"), "num_hidden_layers": 2, "layer_types": ["mamba", "attention"]})
  assert granite.state_matrix and set(state_leaves(init_paged_pool(granite, 2, 3, 16, n_slots=2))) == {"ssm", "conv"}
  program = Path(dec.__file__).resolve().parent.parent
  asks = [str(p.relative_to(program)) for p in program.rglob("*.py") if any(word in p.read_text() for word in ('== "conv"', "== 'conv'", '!= "conv"', 'in ("conv"'))]
  assert asks == [], asks


# ------------------------------------------------------------ the tail is of B * x


def _gated_product_of_layer_0(tokens) -> np.ndarray:
  """[S, D]: ``g = B * x`` of layer 0 from the published equations, in float32 — what the tail must hold — and not x."""
  st = {k: np.asarray(v[0], np.float64) for k, v in PARAMS["ssm_layers"].items()}
  h = np.asarray(PARAMS["embed"], np.float64)[np.asarray(tokens)]
  u = h / np.sqrt((h * h).mean(-1, keepdims=True) + float(HF["norm_eps"])) * st["ssm_norm"]
  b, _c, x = np.split(u @ st["w_in"], 3, axis=-1)
  return b * x, x


def test_the_tail_a_prefill_keeps_is_of_the_gated_product_and_a_decode_through_it_pins_it():
  """A cache-less reference cannot tell a tail of ``x`` from a tail of ``B * x``: both convolve the same sequence. The
  pool can: after a prefill of n tokens, layer 0's two rows of slot 1 are g_{n-2}, g_{n-1} — and are far from x's —,
  and the decode steps that read them give the reference's logits (the battery's case, which a tail of x would fail)."""
  n = 23
  _, pool = KIND.prefill(KIND.fresh_pool(), {1: TOKENS[:n]})
  g, x = _gated_product_of_layer_0(TOKENS[:n])
  tail = np.asarray(pool["conv"][0, 1])
  np.testing.assert_allclose(tail, g[n - 2 :], atol=1e-5, rtol=0)
  assert np.abs(tail - x[n - 2 :]).max() > 0.5
  logits, pool = KIND.decode_step(pool, {1: TOKENS[n]}, {1: n})
  np.testing.assert_allclose(logits[1], KIND.reference(TOKENS[: n + 1])[n], atol=TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(pool["conv"][0, 1]), _gated_product_of_layer_0(TOKENS[: n + 1])[0][n - 1 :], atol=1e-5, rtol=0)


@pytest.mark.parametrize("n", [1, 2])
def test_a_prompt_shorter_than_the_tail_leaves_zeros_ahead_of_its_own_rows(n):
  """A prompt of 1 or 2 tokens: its tail is [0, g_0] or [g_0, g_1] — the rows before the sequence are zeros, not a
  padded position's product — and decode from it is the reference's."""
  want = KIND.reference(TOKENS[: n + 6])
  last, pool = KIND.prefill(KIND.fresh_pool(), {3: TOKENS[:n]})
  np.testing.assert_allclose(np.asarray(last[0]), want[n - 1], atol=TOL, rtol=0)
  g, _ = _gated_product_of_layer_0(TOKENS[:n])
  np.testing.assert_allclose(np.asarray(pool["conv"][0, 3]), np.concatenate([np.zeros((2 - n, CFG.dim)), g]), atol=1e-5, rtol=0)
  for t in range(n, n + 6):
    logits, pool = KIND.decode_step(pool, {3: TOKENS[t]}, {3: t})
    np.testing.assert_allclose(logits[3], want[t], atol=TOL, rtol=0, err_msg=f"decode step at position {t}")


def test_a_second_chunk_of_one_token_keeps_the_newer_row_of_the_old_tail():
  """Positions [0, 30) then the one token [30, 31): the new tail is the old tail's second row and the new token's
  product — ``_conv_tail`` cuts at the row's length inside [old tail | chunk] —, equal to the one-chunk prefill's."""
  toks = TOKENS[:31]
  whole_logits, whole = KIND.prefill(KIND.fresh_pool(), {0: toks})
  _, first = KIND.prefill(KIND.fresh_pool(), {0: toks[:30]})
  logits, pool = KIND.prefill(first, {0: toks}, prefix={0: 30})
  np.testing.assert_allclose(np.asarray(logits), np.asarray(whole_logits), atol=TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(pool["conv"]), np.asarray(whole["conv"]), atol=TOL, rtol=0)
  np.testing.assert_array_equal(np.asarray(pool["conv"][:, 0, 0]), np.asarray(first["conv"][:, 0, 1]))  # the row carried over, bit for bit


# ------------------------------------------------------------ the scheduler


def _server(monkeypatch, slots: int, **env) -> BatchedServer:
  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", str(slots))
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(KIND.page_size))
  for key, value in env.items():
    monkeypatch.setenv(key, str(value))
  return BatchedServer(KIND.engine())


def test_a_prompt_longer_than_the_prefill_chunk_is_served_as_the_reference_does(monkeypatch):
  """``XOT_TPU_PREFILL_CHUNK`` 32 and a prompt of 75 tokens: three chunks, the tail carried across both boundaries in
  the pool; the greedy answer is the reference's."""
  server = _server(monkeypatch, 2, XOT_TPU_PREFILL_CHUNK=32)
  try:
    prompt = [int(t) for t in TOKENS[:75]]
    (answer,) = serve(server, [prompt], 6)
  finally:
    server.shutdown()
  assert len(answer) == 6 and KIND.greedy_under_the_reference(prompt, answer)


def test_a_preempted_row_resumes_by_recomputing_and_answers_as_the_reference_does(monkeypatch):
  """One slot, a batch-class request decoding in it, an interactive one arrives: the row is preempted (its pages and its
  slot's tail are the next tenant's), the interactive request answers, and the batch request resumes by RECOMPUTING its
  prompt + what it had generated — no page is reused, since pages come without their tail — to the reference's greedy
  answer, every token once."""
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(KIND.page_size))
  server = BatchedServer(KIND.engine(), n_slots=1, chunk=2, qos=QosPolicy(QosConfig(aging_s=10_000.0)))
  p_batch, p_int = [int(t) for t in TOKENS[:21]], [int(t) for t in TOKENS[40:53]]
  counter = lambda name: metrics.counter_value(name)  # noqa: E731
  preempted, hits = counter("qos_preemptions_total"), counter("prefix_cache_hit_pages_total")
  streams: dict[str, list] = {}

  async def run():
    started = asyncio.Event()

    def emit(rid, toks, fin):
      streams.setdefault(rid, []).extend(toks)
      if rid == "bg" and len(streams["bg"]) >= 4:
        started.set()

    bg = asyncio.create_task(server.submit("bg", np.asarray(p_batch, np.int32), max_tokens=16, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch"))
    await asyncio.wait_for(started.wait(), timeout=120)
    out_int = await asyncio.wait_for(server.submit("vip", np.asarray(p_int, np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="interactive"), timeout=240)
    return out_int, await asyncio.wait_for(bg, timeout=240)

  try:
    out_int, out_bg = asyncio.run(run())
  finally:
    server.shutdown()
  assert counter("qos_preemptions_total") > preempted and counter("prefix_cache_hit_pages_total") == hits
  assert len(out_bg) == 16 and streams["bg"] == out_bg and KIND.greedy_under_the_reference(p_batch, out_bg)
  assert len(out_int) == 4 and KIND.greedy_under_the_reference(p_int, out_int)


def test_128_slots_serve_160_requests_as_the_reference_does_and_the_gauges_weigh_the_tail_alone(monkeypatch, capsys):
  """The cell's slot count at the rehearsal widths: 160 short requests through a ``BatchedServer`` of 128 slots (groups
  of at most 8 rows, 128 rows decoding at once, 32 requests that wait for a slot and take a predecessor's). Every answer
  is the reference's greedy one; ``recurrent_state_bytes`` is the tail alone — 128 x 6 layers x 2 rows x 64 channels x
  4 bytes — and the start-up line says the same bytes a slot."""
  server = _server(monkeypatch, 128, XOT_TPU_BATCH_MAX_QUEUE=256)
  assert server.n_slots == 128
  rng = np.random.default_rng(7)
  prompts = [[int(t) for t in rng.integers(3, CFG.vocab_size, size=int(n))] for n in rng.choice([3, 9, 17, 26, 33, 39], size=160)]  # (six lengths: the reference compiles once a length)
  try:
    answers = serve(server, prompts, 5)
    assert "ssm" not in server.cache and server.cache["conv"].shape == (6, 128, 2, 64)
  finally:
    server.shutdown()
  wrong = [i for i, (p, a) in enumerate(zip(prompts, answers)) if len(a) != 5 or not KIND.greedy_under_the_reference(p, a)]
  assert not wrong, wrong
  assert metrics.gauge_value("recurrent_state_bytes") == 128 * 6 * 2 * 64 * 4 == 393216
  assert metrics.gauge_value("recurrent_state_step", labels={"form": "no_state_matrix"}) == 1 and metrics.gauge_value("recurrent_state_step", labels={"form": "reference"}) == 0
  out = capsys.readouterr().out
  assert "6 of 8 layers keep a recurrent state per slot (128 slots of 3072 bytes, 393216 in all, beside" in out
