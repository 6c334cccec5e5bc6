"""Window and full attention layers in one model on the served path (ISSUE 46): Laguna-XS.2's architecture at the
benchmark's rehearsal widths — a full-attention layer with a dense FFN, two window layers and a full-attention layer
with experts; 6 and 8 query heads over 2 KV heads of 16 (groups of 3 and 4), a window of 8, YaRN over half a head in the
full layers and plain rope over the whole head in the window layers, a head-wise softplus gate on the attention output,
16 sigmoid-routed experts top-4 beside a shared one — against the benchmark's plain reference
(``benchmark/arch_swa_gqa_moe.py reference_forward``: float32, a full [S, S] masked softmax a layer, every expert
computed densely, nothing of the program, its rope tables included).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes —
are ``tests/served_kind.py``'s battery, taken in below under the names they have always had here; this kind runs its
prefill-then-decode and its two-chunk cases at prompts and cuts on both sides of the window.
"""

import json
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import SLOTS, Kind, battery, rehearsal_of

import arch_swa_gqa_moe  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import AttnKind, YarnScaling, config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops.attention import gqa_attention  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import paged_decode_attention  # noqa: E402
from xotorch_support_jetson_tpu.ops.pallas_attention import flash_attention_prefill  # noqa: E402
from xotorch_support_jetson_tpu.ops.rope import rope_attention_factor, rope_inv_freq  # noqa: E402
from xotorch_support_jetson_tpu.utils.metrics import metrics  # noqa: E402

FILE, HF = rehearsal_of("laguna-xs.2-d5", arch_swa_gqa_moe, nested=True)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
W = 8  # the rehearsal's window
KIND = Kind(
  name="laguna", arch=arch_swa_gqa_moe, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only. Measured 6e-6 at the worst
  # entry of logits of spread 1 (prefill, 16 decode steps and the cache-less forward alike).
  tol=5e-5,
  # bfloat16 weights, activations and pages as served, gate and router float32, a prompt of 20 tokens (past the window):
  # each of the 4 layers' two blocks rounds its increment and the stream — measured 0.025 in the mean and 0.74 at the
  # worst entry (one position's, thirty times the mean: 16 experts of width 32 leave a token's fourth and fifth router
  # scores closer than the published widths' topic router does). The mean is held to three times its reading, the worst
  # entry under half of the weakest wrong architecture's (a dropped layer: 0.34 / 2.1).
  bf16=(0.075, 1.0),
  families=("laguna", "laguna"),
  pool={"k": (4, 1 + SLOTS * 16, 2, 4, 16), "v": (4, 1 + SLOTS * 16, 2, 4, 16)},  # one page leaf for both kinds of layer, in model order
  # the gate's projection and product and the q/k norms under ``xot.attn_proj``, both kinds' cores under ``xot.attn``,
  # router, experts and shared expert under the ``xot.moe_*`` scopes there are; the gate's softplus, once a run of
  # layers, lies under ``xot.attn_proj``. (That the windowed call is named ``paged_decode_window`` in a TPU's program
  # is ``tests/test_tpu_compile_cells.py``'s to show: a CPU lowers no Mosaic call.)
  scopes=frozenset({"xot.moe_router", "xot.moe_experts", "xot.moe_shared"}),
  ops_under=((r"call @softplus", 3, 3, r"xot\.attn_proj"),),
  # each kind given the other's rope, a rope over the whole head or without YaRN in the full layers, 6 heads everywhere,
  # no gate or a sigmoid's, no q/k norm, a softmax router, gates not normalised or not scaled, the shared or a routed
  # expert lost, a layer dropped, float8 operands; and of ``long_probes`` (64 positions under a window of 8 are past it)
  # no window, a window on every layer, a window twice as wide: a thousand tolerances or more
  probe_floor=lambda name: 1000,
  cases={
    # fewer tokens than the window (8), one short of it, the window exactly, one past it, past it and a page boundary inside it (pages of 4), far past it
    "prompt": [3, 7, 8, 9, 13, 30],
    "cut": [5, 12, 16],  # the cut under the window, past it inside a page, on a page's edge
    "key,value,named": [
      ("attention_bias", True, "attention_bias"), ("moe_apply_router_weight_on_input", True, "moe_apply_router_weight_on_input"), ("gating", "elementwise", "gating"),
      ("rope_parameters", {**FILE["rope_parameters"], "sliding_attention": {"rope_type": "longrope", "rope_theta": 1e4}}, "rope_parameters.sliding_attention"),
      ("rope_parameters", {"full_attention": FILE["rope_parameters"]["full_attention"]}, "rope_parameters.sliding_attention"),
      ("layer_types", ["full_attention", "chunked_attention", "sliding_attention", "full_attention"], "layer_types"), ("layer_types", ["full_attention"] * 3, "layer_types"),
      ("num_attention_heads_per_layer", [6, 8, 8], "num_attention_heads_per_layer"), ("num_attention_heads_per_layer", [6, 8, 4, 6], "num_attention_heads_per_layer"),
      ("num_attention_heads_per_layer", [6, 7, 7, 6], "num_attention_heads_per_layer"), ("num_key_value_heads_per_layer", [2, 2, 1, 2], "KV head count"),
      ("mlp_layer_types", ["dense", "sparse", "dense", "sparse"], "mlp_layer_types"), ("mlp_layer_types", ["dense", "sparse"], "mlp_layer_types"), ("sliding_window", 0, "sliding_window"),
    ],
  },  # fmt: skip
  names={
    "test_prefill_then_decode_through_the_pool_equals_the_reference": "test_prefill_then_decode_through_the_pages_equals_the_reference_on_both_sides_of_the_window",
    "test_a_padded_group_leaves_each_row_what_its_unpadded_run_does": "test_a_padded_group_leaves_each_row_the_pages_of_its_unpadded_run",
    "test_a_decode_chunk_leaves_an_inactive_rows_cache_bit_for_bit": "test_a_decode_chunk_leaves_an_inactive_rows_pages_bit_for_bit",
  },
  # pages of 4: a window of 8 spans two or three of them, so its first page is crossed inside it; 16 of them a row
  page_size=4, pages_per_row=16, n_tokens=64, pad=32, prompt=30, decode_steps=20, cut=16, chunked=27, tenants=(20, 6), bf16_prompt=20,
  scheduler_prompts=((0, 29), (30, 45), (50, 55)),  # two requests past the window and one under it
)  # fmt: skip
CFG, PARAMS, SHARD, TOKENS, TOL, PS = KIND.cfg, KIND.params, KIND.shard, KIND.tokens, KIND.tol, KIND.page_size
prefill, fresh_pool, reference, tables = KIND.prefill, KIND.fresh_pool, KIND.reference, lambda: KIND.tables
globals().update(battery(KIND))


# ------------------------------------------------------------ the configuration


def _catalog_row() -> dict:
  return next(json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl") if '"name": "Laguna-XS.2"' in line)["config"]


CATALOG = pytest.mark.skipif(not Path("/opt/skills/guides/model-configs/architectures.jsonl").exists(), reason="no catalog beside this checkout")


@CATALOG
def test_config_from_hf_maps_the_catalog_rows_config_without_an_edit():
  """The published model whole: 40 layers, a full-attention layer of 48 query heads (YaRN over the leading 64 channels,
  factor 1.4158883083359672 on cos and sin, no window) before every three window layers of 64 (plain rope over all 128,
  window 512), all over 8 KV heads of 128, gated; layer 0 dense, 39 layers of 256 sigmoid-routed experts top-8,
  normalised and scaled by 2.5, beside a shared expert of 512. And the 5-layer file is that with the depth cut alone."""
  whole = config_from_hf(_catalog_row())
  full, window = whole.layer_attn[0], whole.layer_attn[1]
  assert whole.family == "laguna" and whole.n_layers == 40 and whole.layer_attn == (full, window, window, window) * 10 and not whole.layer_types and not whole.recurrent_layers
  assert full == AttnKind("full", 48, 0, 500000.0, YarnScaling(64.0, 64.0, 1.0, 4096, 1.4158883083359672), 0.5, True) and window == AttnKind("window", 64, 512, 10000.0, None, 1.0, True)
  assert (whole.n_kv_heads, whole.head_dim, whole.dim, whole.hidden_dim, whole.vocab_size, whole.norm_eps, whole.max_seq_len) == (8, 128, 2048, 8192, 100352, 1e-6, 262144)
  assert whole.qk_norm and not whole.qk_norm_whole and not whole.tied_embedding and not whole.qkv_bias and not whole.is_mla and whole.pre_norms and not whole.post_norms
  assert (whole.n_experts, whole.n_active_experts, whole.moe_hidden_dim, whole.shared_expert_dim, whole.first_k_dense) == (256, 8, 512, 512, 1)
  assert (whole.router_scoring, whole.norm_topk_prob, whole.routed_scaling_factor, whole.n_group, whole.experts_held, whole.shared_expert_gate) == ("sigmoid", True, 2.5, 1, (), False)
  assert [whole.layer_stack(i) for i in range(6)] == ["layers", "window_moe_layers", "window_moe_layers", "window_moe_layers", "moe_layers", "window_moe_layers"]
  assert whole.mixed_layers and whole.plain_attention and not whole.traced_window and whole.attn_windows == (0, 512, 512, 512) * 10
  cfg = common.model_config(FILE)
  assert replace(whole, n_layers=5, layer_attn=whole.layer_attn[:5], max_seq_len=cfg.max_seq_len, rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling) == cfg  # the cut changes the depth alone
  row, changed = _catalog_row(), set(FILE["reduced"])
  assert changed == {"num_hidden_layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer"} and all(FILE[k] == (v[:5] if isinstance(v, list) else 5) for k, v in row.items() if k in changed)
  assert all(FILE[k] == v for k, v in row.items() if k not in changed)  # every other published key as published, nested groups whole


def test_the_flat_spelling_of_the_ropes_is_the_nested_one():
  """``benchmark/common.py model_config`` hands ``config_from_hf`` no nested group but ``rope_scaling``: the file spells
  ``rope_parameters`` flat beside it (gemma3's keys), and both spellings are one ModelConfig, per-layer kinds and all."""
  nested = config_from_hf({k: v for k, v in FILE.items() if k not in ("rope_theta", "rope_scaling", "rope_local_base_freq")})
  flat = config_from_hf({k: v for k, v in FILE.items() if k != "rope_parameters"})
  assert nested.layer_attn == flat.layer_attn == common.model_config(FILE).layer_attn and len(set(nested.layer_attn)) == 2
  assert CFG.layer_attn[0].n_heads == 6 and CFG.layer_attn[1] == AttnKind("window", 8, W, 10000.0, None, 1.0, True) and CFG.attn_windows == (0, W, W, 0)
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"layers": 1, "window_moe_layers": 2, "moe_layers": 1}
  assert jax.tree.map(lambda x: x.shape, jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == jax.tree.map(lambda x: x.shape, PARAMS)  # the benchmark's maker and the program's agree leaf for leaf (shapes alone: nothing is drawn)
  assert PARAMS["layers"]["wq"].shape == (1, 64, 6 * 16) and PARAMS["window_moe_layers"]["wq"].shape == (2, 64, 8 * 16) and PARAMS["window_moe_layers"]["w_og"].shape == (2, 64, 8) and PARAMS["moe_layers"]["q_norm"].shape == (1, 16)

def test_gemma2s_even_layers_are_a_value_of_the_per_layer_field():
  """gemma2's rule — even layers have the window — is read off ``layer_attn`` like any other; its kinds differ in the
  window alone, so they share the one stack, the window rides the traced ``is_sliding`` flag and (with the softcap) keeps
  the model off the Pallas kernels."""
  cfg = config_from_hf({"model_type": "gemma2", "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4, "intermediate_size": 128, "sliding_window": 8, "attn_logit_softcapping": 50.0})
  assert cfg.attn_windows == (8, 0, 8, 0) and [cfg.attn_kind(i).name for i in range(4)] == ["window", "full", "window", "full"] and cfg.sliding_window == 8
  assert {cfg.layer_stack(i) for i in range(4)} == {"layers"} and not cfg.mixed_layers and cfg.traced_window and not cfg.plain_attention
  assert np.asarray(dec.sliding_flags(cfg, range(4))).tolist() == [1.0, 0.0, 1.0, 0.0]
  assert "is_sliding" in dec.full_model_params(jax.random.PRNGKey(0), cfg)[0]["layers"]
  assert not config_from_hf({"model_type": "gemma2", "vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "intermediate_size": 128}).layer_attn  # no window: nothing to describe
  assert replace(cfg, attn_logit_softcap=0.0, sliding_window=0, layer_attn=()).plain_attention  # without the traced window and the softcap it would take them


# ------------------------------------------------------------ a rope of its own for each kind


@pytest.mark.parametrize("hf", [HF, FILE], ids=["rehearsal", "published"])
def test_each_kinds_rope_table_is_the_references(hf):
  """The program's table a kind (``rope_inv_freq``: one a program) against the reference's own computation from
  ``rope_parameters``: YaRN over half a head with its factor on cos and sin, plain rope over the whole head."""
  cfg = config_from_hf(hf)
  tables_ = rope_inv_freq(cfg)
  assert set(tables_) == set(cfg.layer_attn) and len(tables_) == 2
  for k, table in tables_.items():
    inv, factor, rot = arch_swa_gqa_moe.rope_table(hf, k.name)
    assert rot == int(cfg.head_dim * k.partial_rotary_factor) == 2 * table.shape[0] and factor == rope_attention_factor(k)
    np.testing.assert_allclose(np.asarray(table), np.asarray(inv, np.float32), rtol=2e-6, atol=0)
  full, window = (rope_inv_freq(cfg)[cfg.layer_attn[i]] for i in (0, 1))
  assert full.shape[0] * 2 == cfg.head_dim // 2 and window.shape[0] * 2 == cfg.head_dim and rope_attention_factor(cfg.layer_attn[0]) == 1.4158883083359672 and rope_attention_factor(cfg.layer_attn[1]) == 1.0


# ------------------------------------------------------------ the two kernels' window operand (interpret mode)


def _paged_case(hq: int, seed: int = 0):
  rng = np.random.default_rng(seed)
  hkv, hd, ps, pages, mp = 2, 128, 8, 80, 24
  lengths = np.asarray([0, 5, 40, 64, 150], np.int32)
  bt, nxt = np.zeros((len(lengths), mp), np.int32), 1
  for b, n in enumerate(-(-lengths // ps)):
    bt[b, :n], nxt = np.arange(nxt, nxt + n), nxt + n
  k, v = (jnp.asarray(rng.normal(size=(2, pages, hkv, ps, hd)), jnp.float32) for _ in range(2))
  return jnp.asarray(rng.normal(size=(len(lengths), hq, hd)), jnp.float32), k, v, jnp.asarray(bt), jnp.asarray(lengths), ps


@pytest.mark.parametrize("tile", [None, 2])
@pytest.mark.parametrize("window", [0, 24, 40, 64])
@pytest.mark.parametrize("hq", [12, 16])
def test_the_paged_kernel_with_a_window_equals_the_masked_softmax(hq, window, tile):
  """Groups of 6 and 8 query heads a KV head; rows of 0, 5, 40, 64 and 150 tokens in pages of 8 under windows of 24, 40
  and 64 (the window's first position inside a page, on a page's edge, in the tile before the row's last, before the
  row's first token) at the served tile and at a tile of 2 pages (so that whole tiles lie before the window): the kernel
  equals ``gqa_attention``'s masked softmax (``cap_and_mask_scores``: s <= t and s > t - window), and a row of no
  tokens gets zeros."""
  from xotorch_support_jetson_tpu.ops.paged import paged_gqa_attention_ref

  q, k, v, bt, lengths, ps = _paged_case(hq)
  got = paged_decode_attention(q, k, v, bt, lengths, ps, interpret=True, layer=1, window=window, pages_per_step=tile)
  want = paged_gqa_attention_ref(q[:, None], k, v, bt, lengths, ps, layer=1, **({"sliding_window": window} if window else {}))[:, 0]
  np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want[1:]), atol=2e-6, rtol=0)
  assert not np.asarray(got[0]).any()


@pytest.mark.parametrize("quant", ["", "int8"])
def test_the_kernels_without_a_window_are_what_they_were(quant):
  """``window`` 0 hands the kernel bodies no window at all (every ``if window`` is Python's): the traced programs of a
  call that names ``window=0`` and of one that names none are the same text, quantised pages too, and so are their
  results, bit for bit."""
  q, k, v, bt, lengths, ps = _paged_case(12)
  scales = {}
  if quant:
    from xotorch_support_jetson_tpu.models.quantize import quantize_kv

    (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    scales = {"k_scale_pool": ks, "v_scale_pool": vs}
  call = lambda **kw: paged_decode_attention(q, k, v, bt, lengths, ps, interpret=True, layer=1, **scales, **kw)  # noqa: E731
  np.testing.assert_array_equal(np.asarray(call(window=0)), np.asarray(call()))
  assert str(jax.make_jaxpr(lambda: call(window=0))()) == str(jax.make_jaxpr(lambda: call())())
  assert "paged_decode_window" in str(jax.make_jaxpr(lambda: call(window=16))()) and "paged_decode_window" not in str(jax.make_jaxpr(lambda: call())())
  qf, kf, vf = (jnp.asarray(np.random.default_rng(1).normal(size=(1, 256, h, 128)), jnp.float32) for h in (4, 2, 2))
  flash = lambda **kw: flash_attention_prefill(qf, kf, vf, q_offset=0, interpret=True, **kw)  # noqa: E731
  np.testing.assert_array_equal(np.asarray(flash(window=0)), np.asarray(flash()))
  assert str(jax.make_jaxpr(lambda: flash(window=0))()) == str(jax.make_jaxpr(lambda: flash())())


@pytest.mark.parametrize("window", [0, 100, 128, 300, 640])
@pytest.mark.parametrize("hq", [12, 16])
def test_the_flash_kernel_with_a_window_equals_the_masked_softmax(hq, window):
  """Whole-prompt (offset 0) and chunked (offset 200 and 520: K blocks wholly before the window) prefill rows of 256
  queries against 1024 cache slots, groups of 6 and 8: the flash kernel equals ``gqa_attention``'s masked softmax."""
  rng = np.random.default_rng(hq + window)
  q, k, v = (jnp.asarray(rng.normal(size=(3, s, h, 128)), jnp.float32) for s, h in ((256, hq), (1024, 2), (1024, 2)))
  off = jnp.asarray([0, 200, 520], jnp.int32)
  got = flash_attention_prefill(q, k, v, q_offset=off, interpret=True, window=window)
  want = gqa_attention(q, k, v, off[:, None] + jnp.arange(256)[None], jnp.arange(1024), **({"sliding_window": window} if window else {}))
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6, rtol=0)


# ------------------------------------------------------------ pages on both sides of the window


def test_the_long_probes_are_the_windows_three_and_none_of_the_others():
  assert set(arch_swa_gqa_moe.long_probes(HF)) == {"window_off", "window_on_full_layers", "window_1024"} and not set(arch_swa_gqa_moe.long_probes(HF)) & set(arch_swa_gqa_moe.probes(HF))


def test_a_mixed_ticks_slice_and_its_decode_half_equal_the_two_programs_apart():
  """``decode.mixed_paged_batch``: rows 0 and 3 decode a chunk of 4 steps while slot 1's prompt advances by the slice
  [12, 24) — through expert layers and window layers, which no mixed tick met before. The decode half's tokens and the
  pool are what ``decode.paged_batch`` and the chunked prefill give apart, and slot 1 then finishes as the reference."""
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
  for name in ("k", "v"):
    np.testing.assert_allclose(np.asarray(mixed_pool[name][:, 1:]), np.asarray(apart[name][:, 1:]), atol=TOL, rtol=0)
  last, _ = prefill(mixed_pool, {1: toks}, prefix={1: 24}, pad_to=16)
  np.testing.assert_allclose(np.asarray(last[0]), reference(toks)[-1], atol=TOL, rtol=0)



def test_the_slot_cache_and_the_speculative_verify_window_equal_the_reference():
  """The paths beside the page pool's two programs: ``shard_forward`` over a slot-indexed cache (solo sessions,
  ``XOT_TPU_PAGED=0``) prefills 20 tokens and decodes 20 more, and ``paged_window_forward`` (speculation's verify: its
  "window" is the 3 tokens it scores, the layers' attention window rides along) scores three positions past the window
  through the pages: all are the reference's logits."""
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



# ------------------------------------------------------------ the scheduler


def test_the_served_gauges_and_page_counters_say_what_the_windows_let_the_kernel_read(served):
  """After the battery's interleaved requests (two past the window, one under it; mixed ticks on): the gauges say how many
  layers have a window and how wide, and the two page counters what the rows held against what their layers' windows let
  the kernel read."""
  server, after = served.server, served.after
  assert not CFG.recurrent_layers and after.gauge_value("attention_layers", labels={"kind": "full"}) == 2 and after.gauge_value("attention_layers", labels={"kind": "window"}) == 2 and after.gauge_value("attention_window_tokens") == W
  held, read = (after.counter_value(f"kv_pages_{name}_total") - served.before.counter_value(f"kv_pages_{name}_total") for name in ("resident", "read"))
  assert 0 < read < held and held % 4 == 0  # rows past the window: the window layers read 2-3 pages of the 4-9 a row holds
  server._windows, server.page_size = (0, W, W, 0), PS
  before = [metrics.counter_value(f"kv_pages_{name}_total") for name in ("resident", "read")]
  server._count_pages(np.asarray([29, 4, 0]), np.asarray([True, True, False]))  # lengths 30 and 5: 8 + 2 pages held a layer; a window of 8 from position 22 reads pages 5-7
  assert [metrics.counter_value(f"kv_pages_{name}_total") - b for name, b in zip(("resident", "read"), before)] == [4 * 10, 2 * 10 + 2 * (3 + 2)]
