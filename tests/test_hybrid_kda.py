"""A hybrid of Kimi-Delta-Attention and latent-attention layers with a share of routed experts on the served path
(ISSUE 36): Ling-3.0-flash's architecture at the benchmark's rehearsal widths — a KDA layer with the dense FFN, a KDA
and an MLA layer with experts, a KDA layer with experts; 8 of 32 experts held — against the benchmark's plain
reference (``benchmark/arch_hybrid_kda_moe.py reference_forward``: float32, the delta rule token by token, the held
experts one at a time, nothing of the program).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone: the chunked scan solves a chunk's 16 updates as one triangular system where the reference makes them a
token at a time. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes —
are ``tests/served_kind.py``'s battery, taken in below under the names they have always had here.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import SLOTS, Kind, battery, rehearsal_of, serve

import arch_hybrid_kda_moe  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import moe as moe_ops  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402

FILE, HF = rehearsal_of("ling-3.0-flash-ep4-d7", arch_hybrid_kda_moe)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves (the token-topic router among them)
KIND = Kind(
  name="ling", arch=arch_hybrid_kda_moe, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only (measured 2e-6 on logits of
  # spread 1; the delta rule's triangular solve amplifies a rounding a little more than Mamba-2's plain sums do).
  tol=3e-5,
  # bfloat16 keeps 8 bits: each of the 4 layers' two blocks rounds its output to 2^-8 of a residual of magnitude ~2, and
  # the logits (spread 1) carry the sum — measured 0.0065 in the mean and 0.051 at the worst entry; 0.02 and 0.15 are three
  # times the readings, a fifteenth and a twelfth of what a dropped layer reads (0.31 / 1.78).
  bf16=(0.02, 0.15),
  families=("bailing_hybrid", "bailing-hybrid"),
  pool={"ssm": (3, SLOTS, 4, 16, 16), "conv": (3, SLOTS, 3, 3 * 4 * 16)},  # the KDA layers' state and convolution rows beside the latent pages of the one MLA layer
  # ``xot.ssm_proj`` (norm, ``w_qkv`` / ``w_f`` / ``w_bg``, ``w_out``) and ``xot.ssm`` (convolution, gates, the state's read,
  # delta step and write, head norm, output gate), the expert layer's three and the latent attention's: the readers
  # granite's cell has read this cell too, and ``moe_experts_roofline`` its own
  scopes=frozenset({"xot.ssm", "xot.ssm_proj", "xot.moe_router", "xot.moe_experts", "xot.moe_shared"}),
  # a layer dropped, the decay, the delta term, beta or the output gate off, the held range shifted, no group limit, an
  # expert lost, the rope's base: measured 17 800 tolerances or more (float8 operands 19 200), held to 5000; the state or
  # the decay rounded to bfloat16, which is rounding by design, 490 and 718, held to 150
  probe_floor=lambda name: 150 if "bfloat16" in name else 5000,
  cases={"key,value,named": [
    ("expert_swiglu_limit_list", [0, 0, 4, 0], "expert_swiglu_limit_list"), ("share_expert_swiglu_limit_list", [5, 0, 0, 0], "share_expert_swiglu_limit_list"),
    ("use_kda_lora", True, "use_kda_lora"), ("use_nGPT", True, "use_nGPT"), ("value_norm", True, "value_norm"), ("no_kda_lora", False, "no_kda_lora false"),
    ("kda_safe_gate", False, "kda_safe_gate false"), ("gated_attention_proj_granularity_type", "element_wise", "head-wise"), ("experts_held_from", 30, "experts_held_from"),
  ]},  # fmt: skip
  names={
    "test_prefill_then_decode_through_the_pool_equals_the_reference": "test_prefill_then_decode_through_pool_state_and_latent_pages_equals_the_reference",
    "test_a_padded_group_leaves_each_row_what_its_unpadded_run_does": "test_a_padded_group_leaves_each_row_the_state_of_its_unpadded_run",
    "test_a_decode_chunk_leaves_an_inactive_rows_cache_bit_for_bit": "test_a_decode_chunk_leaves_an_inactive_rows_state_bit_for_bit",
  },
)
CFG, PARAMS, SHARD, TOKENS, TOL, PS = KIND.cfg, KIND.params, KIND.shard, KIND.tokens, KIND.tol, KIND.page_size
globals().update(battery(KIND))


# ------------------------------------------------------------ (g) the configuration


def test_config_from_hf_maps_the_catalog_rows_keys():
  """The file's keys are the catalog row's; at the published widths: five KDA layers to one MLA layer, 32 heads of a
  128 x 128 state, 512 routed experts of which 128 are held, top 8 inside 4 of 8 groups, chunks of 16."""
  cfg = common.model_config(FILE)
  assert cfg.family == "bailing-hybrid" and cfg.layer_types == ("kda",) * 5 + ("attention", "kda") and (cfg.recurrent_layers, cfg.n_attn_layers, cfg.recurrent_kind) == (6, 1, "kda")
  assert [cfg.layer_stack(i) for i in range(7)] == ["ssm_layers"] + ["ssm_moe_layers"] * 4 + ["moe_layers", "ssm_moe_layers"]
  assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk, cfg.ssm_conv_dim, cfg.kda_lower_bound) == (32, 128, 128, 4, 16, 12288, -5.0)
  assert (cfg.n_experts, cfg.experts_held, cfg.n_held_experts, cfg.n_active_experts, cfg.n_group, cfg.topk_group, cfg.group_mode, cfg.router_scoring) == (512, (0, 128), 128, 8, 8, 4, "top2sum", "sigmoid")
  assert (cfg.first_k_dense, cfg.moe_hidden_dim, cfg.shared_expert_dim, cfg.hidden_dim, cfg.routed_scaling_factor, cfg.norm_topk_prob) == (1, 768, 768, 6144, 2.5, True)
  assert cfg.is_mla and cfg.mla_q_norm and (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (0, 512, 128, 64, 128)
  assert (cfg.norm_eps, cfg.rope_theta, cfg.vocab_size, cfg.tied_embedding, cfg.max_seq_len) == (1e-6, 6e6, 39296, False, 4096)
  assert CFG.layer_types == ("kda", "kda", "attention", "kda") and CFG.experts_held == (0, 8) and CFG.n_experts == 32
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"ssm_layers": 1, "ssm_moe_layers": 2, "moe_layers": 1}
  assert jax.tree.map(lambda x: x.shape, jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == jax.tree.map(lambda x: x.shape, PARAMS)  # the benchmark's maker and the program's agree leaf for leaf (shapes alone: nothing is drawn)


def test_the_published_model_whole_is_refused_for_its_clamped_layers():
  """All 42 layers: layers 34-41 clamp their SwiGLU, which is not implemented."""
  with pytest.raises(ValueError, match="swiglu_limit_list"):
    config_from_hf({**{k: v for k, v in FILE.items() if not isinstance(v, dict)}, "num_hidden_layers": 42})


# ------------------------------------------------------------ (a) the chunked delta rule


def _random_kda_inputs(B, S, seed):
  H, N, P = 3, 8, 16
  ks = jax.random.split(jax.random.PRNGKey(seed), 6)
  unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
  q, k, v = unit(jax.random.normal(ks[0], (B, S, H, N))) / N**0.5, unit(jax.random.normal(ks[1], (B, S, H, N))), jax.random.normal(ks[2], (B, S, H, P))
  g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (B, S, H, N)))  # all of (-5, 0): 16 positions of it reach e^-80
  return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H))), jax.random.normal(ks[5], (B, H, P, N))


def _token_by_token(q, k, v, g, beta, state, lens):
  """The recurrence in float64 numpy, row by row up to its length: (o [B, S, H, P] zero past the length, final states)."""
  q, k, v, g, beta, state = (np.asarray(t, np.float64) for t in (q, k, v, g, beta, state))
  out = np.zeros(v.shape)
  for b, n in enumerate(lens):
    for t in range(n):
      s = state[b] * np.exp(g[b, t])[:, None, :]  # [H, P, N]: the decay along the key channels
      u = beta[b, t][:, None] * (v[b, t] - np.einsum("hpn,hn->hp", s, k[b, t]))
      state[b] = s + u[:, :, None] * k[b, t][:, None, :]
      out[b, t] = np.einsum("hpn,hn->hp", state[b], q[b, t])
  return out, state


@pytest.mark.parametrize("length,lens", [(48, (48, 48)), (45, (45, 17)), (16, (16, 1)), (7, (7, 3)), (1, (1, 1)), (70, (70, 33))])
def test_the_chunked_delta_rule_equals_the_token_by_token_recurrence(length, lens):
  """(a) ``_kda_chunk_scan`` in chunks of 16 from a non-zero state, rows of ragged lengths (log decay and beta 0 past a
  row's length, as ``_kda_layer`` masks them): outputs up to each row's length and the state after it are the
  recurrence's, and so are ``kda_state_step``'s, one token at a time."""
  q, k, v, g, beta, s0 = _random_kda_inputs(2, length, length)
  valid = jnp.arange(length)[None, :] < jnp.asarray(lens)[:, None]
  o, state = dec._kda_chunk_scan(q, k, v, jnp.where(valid[..., None, None], g, 0.0), jnp.where(valid[..., None], beta, 0.0), s0, 16)
  want_o, want_state = _token_by_token(q, k, v, g, beta, s0, lens)
  np.testing.assert_allclose(np.where(np.asarray(valid)[..., None, None], np.asarray(o), 0.0), want_o, atol=2e-6, rtol=0)
  np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-6, rtol=0)
  leaf = jnp.stack([jnp.zeros_like(s0), s0])  # layer 1 of a leaf of two
  for t in range(length):
    leaf, y = ssm_ops.kda_state_step(leaf, 1, jnp.exp(g[:, t]), beta[:, t], k[:, t], v[:, t], q[:, t], valid[:, t])
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid[:, t])], want_o[:, t][np.asarray(valid[:, t])], atol=2e-6, rtol=0)
  np.testing.assert_allclose(np.asarray(leaf[1]), want_state, atol=2e-6, rtol=0)
  assert not np.asarray(leaf[0]).any()


def test_a_longer_chunk_would_leave_float32s_range():
  """Why ``ssm_chunk`` is 16 at a lower bound of -5: the factorised decays reach e^(5 x chunk), and float32 ends at
  e^88.7. config_from_hf sets the chunk from the bound; at 18 positions of the fastest decay the scan is not finite."""
  assert config_from_hf({**HF, "kda_lower_bound": -10}).ssm_chunk == 8 and config_from_hf({**HF, "kda_lower_bound": -1}).ssm_chunk == 80
  q, k, v, _, beta, s0 = _random_kda_inputs(1, 18, 0)
  g = jnp.full(q.shape, -4.99)
  assert np.isfinite(np.asarray(dec._kda_chunk_scan(q, k, v, g, beta, s0, 16)[0])).all()
  assert not np.isfinite(np.asarray(dec._kda_chunk_scan(q, k, v, g, beta, s0, 18)[0])).all()


# ------------------------------------------------------------ (c) the share


def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
  """(c) One expert layer of 32 routed experts, uncut, against the four chips' shares of it (8 experts each): each
  share's routed part (``moe_ffn held=(lo, hi)`` over that chip's slice of the expert leaves, the router whole), summed,
  plus the shared expert counted once, is the whole layer as the reference gives it with all 32 held. And one share
  alone is what the reference gives for that share."""
  D, E, Fm = CFG.dim, CFG.n_experts, CFG.moe_hidden_dim
  ks = jax.random.split(jax.random.PRNGKey(7), 8)
  w = lambda key, *shape: jax.random.normal(key, shape) * shape[-2] ** -0.5  # noqa: E731
  router, bias = PARAMS["ssm_moe_layers"]["w_router"][0], 0.05 * jax.random.normal(ks[0], (E,))  # the token-topic router; a selection bias that is not zero
  eg, eu, ed = w(ks[1], E, D, Fm), w(ks[2], E, D, Fm), w(ks[3], E, Fm, D)
  sg, su, sd = w(ks[4], D, Fm), w(ks[5], D, Fm), w(ks[6], Fm, D)
  x = PARAMS["embed"][TOKENS[:40]]
  h = jnp.zeros_like(x)
  moe = dict(top_k=8, n_group=CFG.n_group, topk_group=CFG.topk_group, scaling=CFG.routed_scaling_factor, eps=CFG.norm_eps)
  whole = arch_hybrid_kda_moe._moe_ffn(h + x, jnp.ones((D,)), router, bias, eg, eu, ed, sg, su, sd, lo=0, **moe) - x  # norm gain 1: the layer's input is rms(x)
  xn = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + CFG.norm_eps)
  routed = lambda lo, hi: moe_ops.moe_ffn(  # noqa: E731
    xn, router, eg[lo:hi], eu[lo:hi], ed[lo:hi], k=8, scoring="sigmoid", norm_topk=True, selection_bias=bias, scale=2.5, n_group=CFG.n_group, topk_group=CFG.topk_group,
    group_mode="top2sum", held=(lo, hi),
  )[0]
  shares = [routed(lo, lo + 8) for lo in range(0, E, 8)]
  shared = (jax.nn.silu(xn @ sg) * (xn @ su)) @ sd
  np.testing.assert_allclose(np.asarray(sum(shares) + shared), np.asarray(whole), atol=2e-5, rtol=0)
  np.testing.assert_allclose(np.asarray(sum(shares)), np.asarray(routed(0, E)), atol=2e-5, rtol=0)  # ... and to the layer that holds them all
  for lo in (0, 8):
    one = arch_hybrid_kda_moe._moe_ffn(h + x, jnp.ones((D,)), router, bias, eg[lo : lo + 8], eu[lo : lo + 8], ed[lo : lo + 8], sg, su, sd, lo=lo, **moe) - x
    np.testing.assert_allclose(np.asarray(shares[lo // 8] + shared), np.asarray(one), atol=2e-5, rtol=0)
  # a long run of tokens in blocks of 16 is the one block's result: routing is per token
  blocked = moe_ops.moe_ffn(xn, router, eg[:8], eu[:8], ed[:8], k=8, scoring="sigmoid", norm_topk=True, selection_bias=bias, scale=2.5, n_group=CFG.n_group, topk_group=CFG.topk_group,
                            group_mode="top2sum", held=(0, 8), chunk=16)[0]  # fmt: skip
  np.testing.assert_allclose(np.asarray(blocked), np.asarray(shares[0]), atol=2e-5, rtol=0)
  assert all(float(jnp.abs(s).max()) > 1e-3 for s in shares)  # every chip's share is a part of the sum
  assert float(jnp.abs(shares[0] - routed(0, E)).max()) > 1e-2  # ... and no one share is the layer


def test_latent_attention_by_query_blocks_is_the_whole_softmax():
  """What a hybrid's prefill asks of ``mla_absorbed_attention``: queries taken ``q_block`` positions at a time (so that the
  float32 scores of a whole group never exist at once) give what the one pass gives — each block's softmax is whole —
  at a length that is no multiple of the block, and at offsets into a longer window."""
  from xotorch_support_jetson_tpu.ops.attention import mla_absorbed_attention

  B, S, T, H, nope, rope, rank, vd = 2, 40, 64, 4, 16, 8, 32, 16
  ks = jax.random.split(jax.random.PRNGKey(3), 5)
  q_nope, q_pe = jax.random.normal(ks[0], (B, S, H, nope)), jax.random.normal(ks[1], (B, S, H, rope))
  ckv, kpe, w_kv_b = jax.random.normal(ks[2], (B, T, rank)), jax.random.normal(ks[3], (B, T, rope)), jax.random.normal(ks[4], (rank, H * (nope + vd))) * rank**-0.5
  positions = jnp.asarray([[0], [17]]) + jnp.arange(S)[None, :]
  whole = mla_absorbed_attention(q_nope, q_pe, ckv, kpe, w_kv_b, positions, jnp.arange(T), vd)
  for block in (16, 8, 64):
    np.testing.assert_allclose(np.asarray(mla_absorbed_attention(q_nope, q_pe, ckv, kpe, w_kv_b, positions, jnp.arange(T), vd, block)), np.asarray(whole), atol=2e-6, rtol=0)
  assert dec._HYBRID_MLA_Q_BLOCK == 256


# ------------------------------------------------------------ (e) the scheduler


def test_the_served_gauges_say_how_many_experts_are_held_of_how_many_routed(served):
  """(e) After the battery's two interleaved requests: 8 of 32 routed experts held, and the published leaf, as the
  cell's pool holds it, takes the delta rule's one-pass form where a program is told ``use_kernel``."""
  assert CFG.recurrent_layers == 3 and (served.after.gauge_value("moe_experts_routed"), served.after.gauge_value("moe_experts_held")) == (32, 8)
  leaf = jax.ShapeDtypeStruct((6, 64, 32, 128, 128), jnp.float32)
  assert ssm_ops.state_step_form(leaf, True, "kda") == "delta_one_pass" and ssm_ops.state_step_form(leaf, False, "kda") == "delta_reference"


def test_the_served_decode_counts_the_experts_its_rows_chose(monkeypatch):
  """(ISSUE 40) ``/metrics`` says which form the experts' product takes (off the TPU: the block form) and how far the
  grouped form would engage: ``moe_experts_visited_total`` over ``moe_expert_layer_steps_total`` is the mean number of
  distinct held experts a decode step chose in one expert layer — here against a count by hand of the router's own
  choices, recorded as the decode programs ran (3 rows, top 8 of 32, experts 0-7 held, 3 expert layers)."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  seen = []

  def recording(logits, k, *args, **kwargs):
    weights, idx = router_topk(logits, k, *args, **kwargs)
    if logits.shape[0] == 3:  # a decode step's rows (a prefill group is rows x padded tokens)
      jax.debug.callback(lambda chosen: seen.append(np.asarray(chosen)), idx, ordered=True)
    return weights, idx

  router_topk = moe_ops.router_topk
  monkeypatch.setattr(moe_ops, "router_topk", recording)
  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "3")  # no other test's shapes: the programs are traced here, with the recorder
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  server = BatchedServer(KIND.engine())
  visited, steps = (lambda: metrics.counter_value("moe_experts_visited_total")), (lambda: metrics.counter_value("moe_expert_layer_steps_total"))  # noqa: E731
  before = visited(), steps()
  try:
    answers = serve(server, [[int(t) for t in TOKENS[:20]], [int(t) for t in TOKENS[30:41]], [int(t) for t in TOKENS[50:77]]], 20)
  finally:
    server.shutdown()
  assert [len(a) for a in answers] == [20, 20, 20]
  assert metrics.gauge_value("moe_ffn_form", labels={"form": "block"}) == 1 and metrics.gauge_value("moe_ffn_form", labels={"form": "grouped"}) == 0
  exposition = metrics.render_prometheus()  # what ``/metrics`` serves
  assert 'xot_tpu_moe_ffn_form{form="block"} 1' in exposition and 'xot_tpu_moe_ffn_form{form="grouped"} 0' in exposition
  assert "xot_tpu_moe_experts_visited_total " in exposition and "xot_tpu_moe_expert_layer_steps_total " in exposition
  n_steps, n_visited = int(steps() - before[1]), int(visited() - before[0])
  lo, hi = CFG.experts_held
  by_hand = [len({int(e) for e in chosen.ravel() if lo <= e < hi}) for chosen in seen]
  assert server._expert_layers == 3 and n_steps >= 2 * 3 * server.chunk and n_steps % (3 * server.chunk) == 0  # whole chunks: 3 expert layers x the chunk's steps
  assert len(by_hand) >= n_steps  # (a chunk in flight at shutdown ran and is not settled)
  assert n_visited == sum(by_hand[:n_steps])
  assert 1 <= n_visited / n_steps <= 8


def test_a_lane_wide_latent_decodes_the_same_tokens_through_the_kernels_and_the_gather(monkeypatch, interpreted_paged_kernels):
  """(ISSUE 52) The hybrid with a latent the paged kernel's latent body tiles (rank 128): its decode chunk told
  ``use_kernel`` — the latent layer through ``paged_decode_latent`` and the Mosaic token write on the pool in the
  kernel's form, the KDA layers' delta step in whichever form the leaf allows, all interpreted — and told not (the
  gather reference, the XLA expression) emit the same greedy tokens, an inactive row beside them; ``mla_q_norm`` acts
  before either core."""
  from xotorch_support_jetson_tpu.ops import paged

  hf = {**HF, "kv_lora_rank": 128}
  cfg = config_from_hf(hf)
  assert cfg.mla_q_norm and paged.kernel_attends(cfg, True) and not paged.kernel_attends(CFG, True)
  params = jax.tree.map(lambda x: x.astype(jnp.float32), weights.build_params(hf, 13))
  prompts = {0: TOKENS[:37], 2: TOKENS[40:59]}
  last, pool = KIND.prefill(KIND.fresh_pool(cfg), prompts, pad_to=37, params=params, cfg=cfg)
  tok = jnp.asarray(np.argmax(np.asarray(last), axis=-1)[[0, 0, 1, 1]].astype(np.int32)[:, None])
  pos, active = jnp.asarray([37, 0, 19, 0], jnp.int32), jnp.asarray([True, False, True, False])

  def run(use_kernel):  # (the pool is donated: each run takes a copy)
    toks, *_ = dec.fused_paged_batch_decode(params, cfg, SHARD, tok, jax.tree.map(jnp.copy, pool), jnp.asarray(KIND.tables), pos, active, jnp.zeros((SLOTS,), jnp.float32), 2 * PS, page_size=PS, use_kernel=use_kernel)
    return np.asarray(toks)[[0, 2]]

  step = ssm_ops.kda_state_step
  monkeypatch.setattr(ssm_ops, "kda_state_step", lambda *a: step(*a, interpret=True))
  assert run(True).tolist() == run(False).tolist() and interpreted_paged_kernels


def test_a_lane_wide_hybrid_takes_the_grouped_form_where_told(monkeypatch):
  """(ISSUE 40) The hybrid's prefill into the pool and its decode chunk — runs of one stack's layers, the expert
  leaves held for part of the router's range — with the experts' kernels interpreted (``INTERPRET``): the
  stacked expert leaves reach ``moe_ffn`` whole with the layer's index, and logits, greedy tokens and the count of
  expert visits are the block form's."""
  hf = {**HF, "hidden_size": 128, "moe_intermediate_size": 128, "moe_shared_expert_intermediate_size": 128}
  cfg = config_from_hf(hf)
  params = jax.tree.map(lambda x: x.astype(jnp.float32), weights.build_params(hf, 12))
  assert dec._whole_expert_leaves(params, cfg) == ()
  prompts = {0: TOKENS[:37], 2: TOKENS[40:59]}

  def run(cfg):
    last, pool = KIND.prefill(KIND.fresh_pool(cfg), prompts, pad_to=37, params=params, cfg=cfg)
    tok = jnp.asarray(np.argmax(np.asarray(last), axis=-1)[[0, 0, 1, 1]].astype(np.int32)[:, None])
    pos = jnp.asarray([37, 0, 19, 0], jnp.int32)
    toks, _, _, _, visits = dec.fused_paged_batch_decode(
      params, cfg, SHARD, tok, pool, jnp.asarray(KIND.tables), pos, jnp.asarray([True, False, True, False]), jnp.zeros((SLOTS,), jnp.float32), 5, page_size=PS, use_kernel=False, experts_visited=True
    )
    return np.asarray(last), np.asarray(toks)[[0, 2]], int(visits)

  ref = run(cfg)
  monkeypatch.setattr(moe_ops, "INTERPRET", True)
  told = replace(cfg, max_seq_len=cfg.max_seq_len + 1)  # another static config: traced anew
  assert set(dec._whole_expert_leaves(params, told)) == {"w_experts_gate", "w_experts_up", "w_experts_down"}
  got = run(told)
  np.testing.assert_allclose(got[0], ref[0], rtol=0, atol=TOL)
  assert got[1].tolist() == ref[1].tolist() and got[2] == ref[2] > 0
