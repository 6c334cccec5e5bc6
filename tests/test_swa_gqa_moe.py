"""Window and full attention layers in one model on the served path (ISSUE 46): Laguna-XS.2's architecture at the
benchmark's rehearsal widths — a full-attention layer with a dense FFN, two window layers and a full-attention layer
with experts; 6 and 8 query heads over 2 KV heads of 16 (groups of 3 and 4), a window of 8, YaRN over half a head in the
full layers and plain rope over the whole head in the window layers, a head-wise softplus gate on the attention output,
16 sigmoid-routed experts top-4 beside a shared one — against the benchmark's plain reference
(``benchmark/arch_swa_gqa_moe.py reference_forward``: float32, a full [S, S] masked softmax a layer, every expert
computed densely, nothing of the program, its rope tables included).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone. Logits have a spread of ~1; tolerances are absolute.
"""

import asyncio
import json
import re
import sys
from dataclasses import replace
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))

import arch_swa_gqa_moe as kind  # noqa: E402
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine  # noqa: E402
from xotorch_support_jetson_tpu.inference.shard import Shard  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import AttnKind, YarnScaling, config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops.attention import gqa_attention  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, paged_decode_attention  # noqa: E402
from xotorch_support_jetson_tpu.ops.pallas_attention import flash_attention_prefill  # noqa: E402
from xotorch_support_jetson_tpu.ops.rope import rope_attention_factor, rope_inv_freq  # noqa: E402

FILE = common.load_config("laguna-xs.2-d5")
HF = {**FILE, **kind.REHEARSE_WIDTHS, "torch_dtype": "float32", "max_position_embeddings": 256}
CFG = config_from_hf(HF)
SHARD = Shard("laguna", 0, CFG.n_layers - 1, CFG.n_layers)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
PARAMS = jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS)
W = 8  # the rehearsal's window
PS, SLOTS, MP = 4, 4, 16  # pages of 4: a window of 8 spans two or three of them, so its first page is crossed inside it
RNG = np.random.default_rng(0)
TOKENS = RNG.integers(3, CFG.vocab_size, size=64)
# The program against the reference, both float32 at "highest": orders of summation only. Measured 6e-6 at the worst
# entry of logits of spread 1 (prefill, 16 decode steps and the cache-less forward alike).
TOL = 5e-5


@pytest.fixture(autouse=True)
def highest_precision():
  with jax.default_matmul_precision("highest"):
    yield


def reference(tokens, params=PARAMS, **probe) -> np.ndarray:
  return np.asarray(kind.reference_forward(params, HF, jnp.asarray(tokens), **probe))


def fresh_pool(cfg=CFG):
  return init_paged_pool(cfg, cfg.n_layers, 1 + SLOTS * MP, PS)


def tables() -> np.ndarray:
  return np.arange(1, 1 + SLOTS * MP, dtype=np.int32).reshape(SLOTS, MP)


def prefill(pool, prompts: dict, prefix: dict | None = None, pad_to: int | None = None, pad_rows: int = 0, params=PARAMS, cfg=CFG):
  """Prefill ``{slot: tokens}`` as one group (each row from ``prefix[slot]`` on) → (last logits [K, V], pool)."""
  rows = sorted(prompts)
  prefix = prefix or {}
  K = len(rows) + pad_rows
  S = pad_to or max(len(prompts[r]) - prefix.get(r, 0) for r in rows)
  tok, bts = np.zeros((K, S), np.int32), np.zeros((K, MP), np.int32)
  prefix_lens, prompt_lens = np.zeros((K,), np.int32), np.ones((K,), np.int32)
  for i, r in enumerate(rows):
    start = prefix.get(r, 0)
    tok[i, : len(prompts[r]) - start] = prompts[r][start:]
    bts[i], prefix_lens[i], prompt_lens[i] = tables()[r], start, len(prompts[r])
  return dec.prefill_into_pages_many(params, cfg, SHARD, jnp.asarray(tok), pool, jnp.asarray(bts), jnp.asarray(prefix_lens), jnp.asarray(prompt_lens), PS)


@partial(jax.jit, static_argnums=0)
def _decode_forward(cfg, params, tok, pos, pool, active):
  return dec.paged_decode_forward(params, cfg, SHARD, tok, pos[:, None], pool, jnp.asarray(tables()), PS, False, active=active)[:2]


def decode_step(pool, tokens: dict, positions: dict, params=PARAMS, cfg=CFG):
  """One teacher-forced decode step of the rows named → (logits [SLOTS, V], pool)."""
  tok, pos, active = np.zeros((SLOTS, 1), np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
  for r, t in tokens.items():
    tok[r, 0], pos[r], active[r] = t, positions[r], True
  logits, pool = _decode_forward(cfg, params, jnp.asarray(tok), jnp.asarray(pos), pool, jnp.asarray(active))
  return np.asarray(logits[:, 0]), pool


def pages_of(pool, slot: int):
  return np.asarray(pool["k"][:, tables()[slot]]), np.asarray(pool["v"][:, tables()[slot]])


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
  assert jax.tree.map(lambda x: x.shape, dec.full_model_params(jax.random.PRNGKey(0), CFG)[0]) == jax.tree.map(lambda x: x.shape, PARAMS)  # the benchmark's maker and the program's agree leaf for leaf
  assert PARAMS["layers"]["wq"].shape == (1, 64, 6 * 16) and PARAMS["window_moe_layers"]["wq"].shape == (2, 64, 8 * 16) and PARAMS["window_moe_layers"]["w_og"].shape == (2, 64, 8) and PARAMS["moe_layers"]["q_norm"].shape == (1, 16)


@pytest.mark.parametrize("key,value,named", [
  ("attention_bias", True, "attention_bias"), ("moe_apply_router_weight_on_input", True, "moe_apply_router_weight_on_input"), ("gating", "elementwise", "gating"),
  ("rope_parameters", {**FILE["rope_parameters"], "sliding_attention": {"rope_type": "longrope", "rope_theta": 1e4}}, "rope_parameters.sliding_attention"),
  ("rope_parameters", {"full_attention": FILE["rope_parameters"]["full_attention"]}, "rope_parameters.sliding_attention"),
  ("layer_types", ["full_attention", "chunked_attention", "sliding_attention", "full_attention"], "layer_types"), ("layer_types", ["full_attention"] * 3, "layer_types"),
  ("num_attention_heads_per_layer", [6, 8, 8], "num_attention_heads_per_layer"), ("num_attention_heads_per_layer", [6, 8, 4, 6], "num_attention_heads_per_layer"),
  ("num_attention_heads_per_layer", [6, 7, 7, 6], "num_attention_heads_per_layer"), ("num_key_value_heads_per_layer", [2, 2, 1, 2], "KV head count"),
  ("mlp_layer_types", ["dense", "sparse", "dense", "sparse"], "mlp_layer_types"), ("mlp_layer_types", ["dense", "sparse"], "mlp_layer_types"), ("sliding_window", 0, "sliding_window"),
])  # fmt: skip
def test_config_from_hf_refuses_what_is_not_implemented_by_name(key, value, named):
  with pytest.raises(ValueError, match=re.escape(named)):
    config_from_hf({**HF, key: value})


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
  """No safetensors name map exists for the family: a checkpoint is refused by name, loader and exporter alike."""
  from xotorch_support_jetson_tpu.models.hf_export import export_hf_checkpoint
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights

  with pytest.raises(NotImplementedError, match="laguna"):
    load_shard_weights(tmp_path, CFG, SHARD)
  with pytest.raises(NotImplementedError, match="laguna"):
    export_hf_checkpoint(tmp_path / "out", CFG, PARAMS)
  with pytest.raises(ValueError, match="laguna"):  # MODEL_FAMILIES' error lists the new family
    config_from_hf({"model_type": "rwkv7"})


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
    inv, factor, rot = kind.rope_table(hf, k.name)
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


def test_the_cacheless_forward_equals_the_reference():
  got, _ = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray(TOKENS)[None], jnp.arange(len(TOKENS))[None])
  np.testing.assert_allclose(np.asarray(got[0]), reference(TOKENS), atol=TOL, rtol=0)


def test_every_named_probe_moves_the_reference_past_the_tolerance():
  """Each wrong reference of ``probes`` and of ``long_probes`` (64 positions under a window of 8 are past it) lies a
  thousand tolerances or more from the sound one: each kind given the other's rope, a rope over the whole head or
  without YaRN in the full layers, 6 heads everywhere, no gate or a sigmoid's, no q/k norm, a softmax router, gates
  not normalised or not scaled, the shared or a routed expert lost, a layer dropped, float8 operands; no window, a
  window on every layer, a window twice as wide."""
  sound = reference(TOKENS)
  for name, probe in {**kind.probes(HF), **kind.long_probes(HF)}.items():
    moved = float(np.abs(reference(TOKENS, **probe) - sound).max())
    assert moved > 1000 * TOL, (name, moved)
  assert set(kind.long_probes(HF)) == {"window_off", "window_on_full_layers", "window_1024"} and not set(kind.long_probes(HF)) & set(kind.probes(HF))


@pytest.mark.parametrize("prompt", [3, 7, 8, 9, 13, 30])
def test_prefill_then_decode_through_the_pages_equals_the_reference_on_both_sides_of_the_window(prompt):
  """float32: ``prompt`` tokens prefilled into slot 2 beside three padding rows — fewer than the window (8), one short
  of it, the window exactly, one past it, past it and a page boundary inside it (pages of 4), far past it — then 20
  decode steps, one token each, through the two window layers' and the two full layers' pages: every step's LOGITS are
  the reference's full forward at that position, to the order of the sums, as each row crosses the window."""
  want = reference(TOKENS[: prompt + 20])
  last, pool = prefill(fresh_pool(), {2: TOKENS[:prompt]}, pad_to=32, pad_rows=3)
  assert pool["k"].shape == (4, 1 + SLOTS * MP, 2, PS, 16) and set(pool) == {"k", "v"}  # one page leaf for both kinds, in model order
  np.testing.assert_allclose(np.asarray(last[0]), want[prompt - 1], atol=TOL, rtol=0)
  for other in (0, 1, 3):  # nothing was written for a padding row, nor for a slot no request held
    assert not pages_of(pool, other)[0].any()
  for t in range(prompt, prompt + 20):
    logits, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
    np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0, err_msg=f"decode step at position {t}")


def test_the_bfloat16_path_stays_within_bfloat16s_rounding_of_the_reference():
  """bfloat16 weights, activations and pages as served, gate and router float32: prefill (20 tokens, past the window) and
  34 decode steps against the float32 reference on the same bfloat16 weights, the logits (spread 1) of all 35
  positions. bfloat16 keeps 7 bits of mantissa: each of the 4 layers' two blocks rounds its increment and the stream —
  measured 0.025 in the mean and 0.74 at the worst entry (one position's, thirty times the mean: 16 experts of width 32
  leave a token's fourth and fifth router scores closer than the published widths' topic router does). The mean is
  held to three times its reading, the worst entry under half of the weakest wrong architecture's (a dropped layer:
  0.34 / 2.1)."""
  cfg = replace(CFG, dtype=jnp.bfloat16)
  want = reference(TOKENS[:54], params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS))
  dropped = np.abs(reference(TOKENS[:54], drop_layer=3) - want)[19:]
  last, pool = prefill(fresh_pool(cfg), {1: TOKENS[:20]}, pad_to=32, params=BF16_PARAMS, cfg=cfg)
  assert pool["k"].dtype == jnp.bfloat16
  off = [np.abs(np.asarray(last[0], np.float32) - want[19])]
  for t in range(20, 54):
    logits, pool = decode_step(pool, {1: TOKENS[t]}, {1: t}, params=BF16_PARAMS, cfg=cfg)
    off.append(np.abs(logits[1].astype(np.float32) - want[t]))
  mean, worst = float(np.mean(off)), float(np.max(off))
  assert mean < 0.075 < 0.5 * float(dropped.mean()) and worst < 1.0 < 0.5 * float(dropped.max()), (mean, worst, float(dropped.mean()), float(dropped.max()))


def test_a_padded_group_leaves_each_row_the_pages_of_its_unpadded_run():
  """Rows of 30, 13 and 2 tokens as one group padded to 32: each row's pages and last logits are what the row's own
  prefill gives alone (its experts see its own tokens, its window its own positions)."""
  prompts = {0: TOKENS[:30], 1: TOKENS[10:23], 3: TOKENS[40:42]}
  logits, grouped = prefill(fresh_pool(), prompts, pad_to=32, pad_rows=1)
  for i, (slot, toks) in enumerate(prompts.items()):
    solo_logits, solo = prefill(fresh_pool(), {slot: toks}, pad_to=None if slot == 1 else 32)
    np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(solo_logits[0]), atol=TOL, rtol=0)
    n = -(-len(toks) // PS)
    for got, want in zip(pages_of(grouped, slot), pages_of(solo, slot)):
      np.testing.assert_allclose(got[:, : n - 1], want[:, : n - 1], atol=TOL, rtol=0, err_msg=f"slot {slot}")


@pytest.mark.parametrize("cut", [5, 12, 16])
def test_a_prompt_prefilled_in_two_chunks_equals_one(cut):
  """Positions [0, cut) then [cut, 27): the second call's window layers look back over the first call's pages — the
  cut under the window, past it inside a page, on a page's edge."""
  toks = TOKENS[:27]
  whole_logits, whole = prefill(fresh_pool(), {1: toks}, pad_to=32)
  _, pool = prefill(fresh_pool(), {1: toks[:cut]}, pad_to=16)
  cut_logits, chunked = prefill(pool, {1: toks}, prefix={1: cut}, pad_to=32)
  np.testing.assert_allclose(np.asarray(cut_logits), np.asarray(whole_logits), atol=TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(cut_logits[0]), reference(toks)[-1], atol=TOL, rtol=0)
  for got, want in zip(pages_of(chunked, 1), pages_of(whole, 1)):
    np.testing.assert_allclose(got[:, :6], want[:, :6], atol=TOL, rtol=0)


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


def test_a_reused_slot_gives_its_second_tenant_the_solo_answer():
  """Slot 2 serves one request past the window (prefill + decode steps), then another, shorter than the window, from
  position 0: the second's window layers never see its predecessor's pages, and its logits are those of a pool it has to
  itself, bit for bit."""
  _, pool = prefill(fresh_pool(), {2: TOKENS[:20]}, pad_to=32)
  for t in range(20, 26):
    _, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
  second = TOKENS[40:46]
  reused_logits, reused = prefill(pool, {2: second}, pad_to=32)
  solo_logits, solo = prefill(fresh_pool(), {2: second}, pad_to=32)
  np.testing.assert_array_equal(np.asarray(reused_logits), np.asarray(solo_logits))
  a, b = decode_step(reused, {2: TOKENS[46]}, {2: 6})[0], decode_step(solo, {2: TOKENS[46]}, {2: 6})[0]
  np.testing.assert_array_equal(a[2], b[2])


def test_a_decode_chunk_leaves_an_inactive_rows_pages_bit_for_bit():
  """A chunk of 4 steps of ``decode.paged_batch`` with rows 0 and 3 active: rows 1 and 2, resident but not stepped, keep
  their pages exactly, and the active rows' tokens are the reference's greedy ones."""
  _, pool = prefill(fresh_pool(), {0: TOKENS[:20], 1: TOKENS[20:50], 2: TOKENS[50:58], 3: TOKENS[30:41]}, pad_to=32)
  before = {slot: pages_of(pool, slot) for slot in range(SLOTS)}
  active, pos = np.asarray([True, False, False, True]), np.asarray([20, 30, 8, 11], np.int32)
  first = jnp.asarray([[TOKENS[20]], [1], [1], [TOKENS[41]]], jnp.int32)
  toks, _, new_pos, pool = dec.fused_paged_batch_decode(PARAMS, CFG, SHARD, first, pool, tables(), jnp.asarray(pos), jnp.asarray(active), np.zeros((SLOTS,), np.float32), 4, page_size=PS, use_kernel=False)
  assert np.asarray(new_pos).tolist() == [24, 30, 8, 15]
  for slot in (1, 2):
    for got, want in zip(pages_of(pool, slot), before[slot]):
      np.testing.assert_array_equal(got, want)
  row0 = list(TOKENS[:21]) + [int(t) for t in np.asarray(toks)[0, :3]]
  assert [int(np.argmax(reference(np.asarray(row0))[20 + i])) for i in range(3)] == [int(t) for t in np.asarray(toks)[0, :3]]


def test_the_slot_cache_and_the_speculative_verify_window_equal_the_reference():
  """The paths beside the page pool's two programs: ``shard_forward`` over a slot-indexed cache (solo sessions,
  ``XOT_TPU_PAGED=0``) prefills 20 tokens and decodes 20 more, and ``paged_window_forward`` (speculation's verify: its
  "window" is the 3 tokens it scores, the layers' attention window rides along) scores three positions past the window
  through the pages: all are the reference's logits."""
  want = reference(TOKENS[:40])
  cache = dec.init_kv_cache(CFG, CFG.n_layers, 1, 64)
  logits, cache = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray(TOKENS[:20])[None], jnp.arange(20)[None], cache)
  np.testing.assert_allclose(np.asarray(logits[0]), want[:20], atol=TOL, rtol=0)
  for t in range(20, 40):
    logits, cache = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray([[TOKENS[t]]]), jnp.asarray([[t]]), cache)
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[t], atol=TOL, rtol=0, err_msg=f"slot-cache decode at position {t}")
  _, pool = prefill(fresh_pool(), {1: TOKENS[:20]}, pad_to=32)
  toks, pos = np.zeros((SLOTS, 3), np.int32), np.zeros((SLOTS, 3), np.int32)
  toks[1], pos[1] = TOKENS[20:23], [20, 21, 22]
  logits, _ = dec.paged_window_forward(PARAMS, CFG, SHARD, jnp.asarray(toks), jnp.asarray(pos), pool, jnp.asarray(np.where(np.arange(SLOTS)[:, None] == 1, tables(), 0)), PS)
  np.testing.assert_allclose(np.asarray(logits[1]), want[20:23], atol=TOL, rtol=0)


# ------------------------------------------------------------ the scheduler


def _serve(server, prompts, n_gen):
  async def run():
    return await asyncio.gather(*(
      server.submit(f"r{i}-{len(p)}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i, p in enumerate(prompts)
    ))

  return asyncio.run(run())


def _greedy_under_the_reference(prompt, answer) -> bool:
  logits = reference(np.asarray(list(prompt) + list(answer)))
  return [int(np.argmax(logits[len(prompt) - 1 + i])) for i in range(len(answer))] == list(answer)


def test_the_scheduler_serves_interleaved_requests_as_the_reference_does(monkeypatch):
  """Two requests past the window and one under it through ``BatchedServer`` (admission groups, decode chunks, mixed
  ticks on, the pool's pages) answer greedy-equal to the reference; the gauges say how many layers have a window and
  how wide, and the two page counters what the rows held against what their layers' windows let the kernel read."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "2")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(SHARD, CFG, PARAMS)
  server = BatchedServer(engine)
  prompts = [[int(t) for t in TOKENS[:29]], [int(t) for t in TOKENS[30:45]], [int(t) for t in TOKENS[50:55]]]
  held, read = (metrics.counter_value(f"kv_pages_{name}_total") for name in ("resident", "read"))
  try:
    answers = _serve(server, prompts, 6)
  finally:
    server.shutdown()
  assert all(len(a) == 6 and _greedy_under_the_reference(p, a) for p, a in zip(prompts, answers))
  assert not CFG.recurrent_layers and server.ops.mixed_tick_supported() and not server.ops.prefill_donates_pool  # (a CPU states no memory limit: the copying prefill)
  assert metrics.gauge_value("attention_layers", labels={"kind": "full"}) == 2 and metrics.gauge_value("attention_layers", labels={"kind": "window"}) == 2 and metrics.gauge_value("attention_window_tokens") == W
  held, read = metrics.counter_value("kv_pages_resident_total") - held, metrics.counter_value("kv_pages_read_total") - read
  assert 0 < read < held and held % 4 == 0  # rows past the window: the window layers read 2-3 pages of the 4-9 a row holds
  server._windows, server.page_size = (0, W, W, 0), PS
  before = [metrics.counter_value(f"kv_pages_{name}_total") for name in ("resident", "read")]
  server._count_pages(np.asarray([29, 4, 0]), np.asarray([True, True, False]))  # lengths 30 and 5: 8 + 2 pages held a layer; a window of 8 from position 22 reads pages 5-7
  assert [metrics.counter_value(f"kv_pages_{name}_total") - b for name, b in zip(("resident", "read"), before)] == [4 * 10, 2 * 10 + 2 * (3 + 2)]


# ------------------------------------------------------------ tracing


def test_the_scopes_reach_the_lowered_decode_program():
  """The gate's projection and product and the q/k norms under ``xot.attn_proj``, both kinds' cores under ``xot.attn``,
  router, experts and shared expert under the ``xot.moe_*`` scopes there are: the scopes are in the lowered
  ``decode.paged_batch``, and the gate's softplus of every run of layers lies under ``xot.attn_proj``. (That the windowed call is named ``paged_decode_window`` in a TPU's program is
  ``tests/test_tpu_compile.py``'s to show: a CPU lowers no Mosaic call.)"""
  args = (
    PARAMS, CFG, SHARD, jnp.ones((SLOTS, 1), jnp.int32), fresh_pool(), jnp.asarray(tables()), jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.ones((SLOTS,), bool),
    jnp.zeros((SLOTS,), jnp.float32), jnp.full((SLOTS,), 8, jnp.int32), 4, 8, PS, False, jax.random.PRNGKey(1), None,
  )
  text = dec._fused_paged_batch_decode_impl.xot_jitted.lower(*args).as_text(debug_info=True)
  scopes = set(re.findall(r"xot\.[a-z_]+", text))
  want = {"xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.ffn", "xot.moe_router", "xot.moe_experts", "xot.moe_shared", "xot.head", "xot.sample"}
  assert want <= scopes, sorted(want - scopes)
  locs = dict(re.findall(r"(#loc\d+) = loc\((.*)\)$", text, flags=re.M))

  def named(ref: str, depth: int = 0) -> str:  # a location's whole chain of names
    body = locs.get(ref, "")
    return body + "".join(named(r, depth + 1) for r in re.findall(r"#loc\d+", body)) if depth < 8 else body

  gates = [m for m in re.finditer(r"call @softplus.*loc\((#loc\d+)\)", text)]
  assert len(gates) == 3 and all("xot.attn_proj" in named(m.group(1)) for m in gates)  # the gate's softplus, once a run of layers
