"""A hybrid of Gated-DeltaNet and full-attention layers in post-norm blocks on the served path (ISSUE 44):
Olmo-Hybrid's architecture at the benchmark's rehearsal widths — Gated-DeltaNet runs of 2 with a full-attention layer
after each, then one more; 6 heads (no power of two) of a 16 x 8 state (N != P), 6 attention heads of 16 with the
RMSNorm over the whole q and k projections — against the benchmark's plain reference
(``benchmark/arch_hybrid_gdn.py reference_forward``: float32, the delta rule token by token, nothing of the program).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone: the chunked scan solves a chunk's 64 updates as one triangular system where the reference makes them a
token at a time. Logits have a spread of ~1; tolerances are absolute.
"""

import asyncio
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

import arch_hybrid_gdn as kind  # noqa: E402
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine  # noqa: E402
from xotorch_support_jetson_tpu.inference.shard import Shard  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool  # noqa: E402

FILE = common.load_config("olmo-hybrid-7b-d12")
HF = {**{k: v for k, v in FILE.items() if not isinstance(v, dict)}, **kind.REHEARSE_WIDTHS, "torch_dtype": "float32", "max_position_embeddings": 256}
CFG = config_from_hf(HF)
SHARD = Shard("olmo", 0, CFG.n_layers - 1, CFG.n_layers)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
PARAMS = jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS)
PS, SLOTS, MP = 16, 4, 8
RNG = np.random.default_rng(0)
TOKENS = RNG.integers(3, CFG.vocab_size, size=112)
# The program against the reference, both float32 at "highest": orders of summation only — the chunked scan solves 64
# updates as one triangular system where the reference makes them a token at a time. Measured 1e-5 at the worst entry
# of logits of spread 1 (the KDA hybrid's 2e-6: a head's output is normed to unit scale whatever it was, and at a
# position whose decay has just emptied the state that carries the small o = S q's rounding up to the logits).
TOL = 5e-5


@pytest.fixture(autouse=True)
def highest_precision():
  with jax.default_matmul_precision("highest"):
    yield


def reference(tokens, params=PARAMS, **probe) -> np.ndarray:
  return np.asarray(kind.reference_forward(params, HF, jnp.asarray(tokens), **probe))


def fresh_pool(cfg=CFG):
  return init_paged_pool(cfg, cfg.n_layers, 1 + SLOTS * MP, PS, n_slots=SLOTS)


def tables() -> np.ndarray:
  return np.arange(1, 1 + SLOTS * MP, dtype=np.int32).reshape(SLOTS, MP)


def prefill(pool, prompts: dict, prefix: dict | None = None, pad_to: int | None = None, pad_rows: int = 0, params=PARAMS, cfg=CFG):
  """Prefill ``{slot: tokens}`` as one group, its rows in the dict's order (each row from ``prefix[slot]`` on) → (last logits [K, V], pool)."""
  rows = list(prompts)
  prefix = prefix or {}
  K = len(rows) + pad_rows
  S = pad_to or max(len(prompts[r]) - prefix.get(r, 0) for r in rows)
  tok, bts = np.zeros((K, S), np.int32), np.zeros((K, MP), np.int32)
  prefix_lens, prompt_lens, slot_rows = np.zeros((K,), np.int32), np.ones((K,), np.int32), np.full((K,), SLOTS, np.int32)
  for i, r in enumerate(rows):
    start = prefix.get(r, 0)
    tok[i, : len(prompts[r]) - start] = prompts[r][start:]
    bts[i], prefix_lens[i], prompt_lens[i], slot_rows[i] = tables()[r], start, len(prompts[r]), r
  return dec.prefill_into_pages_many(params, cfg, SHARD, jnp.asarray(tok), pool, jnp.asarray(bts), jnp.asarray(prefix_lens), jnp.asarray(prompt_lens), PS, None, jnp.asarray(slot_rows))


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


def state_of(pool, slot: int):
  return np.asarray(pool["ssm"][:, slot]), np.asarray(pool["conv"][:, slot])


# ------------------------------------------------------------ the configuration


def _catalog_row() -> dict:
  """The model's ``config`` as the published config.json has it: the file's published keys with the two ``reduced`` ones
  put back (32 layers, the pattern eight times over)."""
  row = {k: v for k, v in FILE.items() if not isinstance(v, dict) or k == "rope_parameters"}
  return {**row, "num_hidden_layers": FILE["published"]["num_hidden_layers"], "layer_types": FILE["layer_types"][:4] * 8}


def test_config_from_hf_maps_the_catalog_rows_keys():
  """The published model whole and the 12-layer file: three Gated-DeltaNet layers to one full-attention layer, 30 heads
  of a 192 x 96 state under a scalar decay and beta in (0, 2), chunks of 64; 30 KV heads of 128 with the norm over the
  whole projections, no rope, norms after the sublayers only, an untied head."""
  whole = config_from_hf(_catalog_row())
  assert whole.family == "olmo-hybrid" and whole.layer_types == (("gdn",) * 3 + ("attention",)) * 8 and (whole.recurrent_layers, whole.n_attn_layers, whole.recurrent_kind) == (24, 8, "gdn")
  cfg = common.model_config(FILE)
  assert cfg.layer_types == whole.layer_types[:12] and (cfg.recurrent_layers, cfg.n_attn_layers) == (9, 3)
  assert replace(whole, n_layers=12, layer_types=cfg.layer_types, max_seq_len=cfg.max_seq_len) == cfg  # the cut changes the depth alone
  assert [cfg.layer_stack(i) for i in range(4)] == ["ssm_layers"] * 3 + ["layers"]
  assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv, cfg.ssm_chunk, cfg.ssm_conv_dim, cfg.gdn_beta_scale) == (30, 192, 96, 4, 64, 11520, 2.0)
  assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dim, cfg.hidden_dim, cfg.vocab_size) == (30, 30, 128, 3840, 11008, 100352)
  assert cfg.qk_norm and cfg.qk_norm_whole and not cfg.use_rope and cfg.post_norms and not cfg.pre_norms and not cfg.tied_embedding and not cfg.qkv_bias and not cfg.n_experts and not cfg.is_mla
  assert (cfg.norm_eps, cfg.max_seq_len, whole.max_seq_len) == (1e-6, 4096, 65536)
  assert config_from_hf({**HF, "linear_allow_neg_eigval": False}).gdn_beta_scale == 1.0
  assert CFG.layer_types == ("gdn", "gdn", "attention", "gdn", "gdn", "attention", "gdn") and (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state) == (6, 16, 8)
  assert {name: next(iter(st.values())).shape[0] for name, st in PARAMS.items() if isinstance(st, dict)} == {"layers": 2, "ssm_layers": 5}
  assert jax.tree.map(lambda x: x.shape, dec.full_model_params(jax.random.PRNGKey(0), CFG)[0]) == jax.tree.map(lambda x: x.shape, PARAMS)  # the benchmark's maker and the program's agree leaf for leaf
  assert not {"attn_norm", "ssm_norm", "mlp_norm"} & (set(PARAMS["layers"]) | set(PARAMS["ssm_layers"])) and PARAMS["layers"]["q_norm"].shape == (2, CFG.q_dim)


@pytest.mark.parametrize("key,value,named", [
  ("attention_bias", True, "attention_bias"), ("rope_theta", 500000.0, "rope_theta"), ("rope_parameters", {"rope_theta": 10000.0}, "rope_theta"),
  ("linear_num_key_heads", 3, "linear_num_key_heads"), ("layer_types", ["linear_attention"] * 6 + ["sliding_attention"], "layer_types"), ("layer_types", ["linear_attention"] * 3, "layer_types"),
])  # fmt: skip
def test_config_from_hf_refuses_what_is_not_implemented_by_name(key, value, named):
  with pytest.raises(ValueError, match=named):
    config_from_hf({**HF, key: value})


def test_a_checkpoint_of_the_family_is_refused_by_name(tmp_path):
  """No safetensors name map exists for the family: a checkpoint is refused by name, loader and exporter alike."""
  from xotorch_support_jetson_tpu.models.hf_export import export_hf_checkpoint
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights

  with pytest.raises(NotImplementedError, match="olmo_hybrid"):
    load_shard_weights(tmp_path, CFG, SHARD)
  with pytest.raises(NotImplementedError, match="olmo-hybrid"):
    export_hf_checkpoint(tmp_path / "out", CFG, PARAMS)
  with pytest.raises(ValueError, match="olmo_hybrid"):  # MODEL_FAMILIES' error lists the new family
    config_from_hf({"model_type": "rwkv7"})


# ------------------------------------------------------------ the chunked delta rule under a scalar decay


def _random_gdn_inputs(B, S, seed):
  H, N, P = 3, 8, 16
  ks = jax.random.split(jax.random.PRNGKey(seed), 6)
  unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
  q, k, v = unit(jax.random.normal(ks[0], (B, S, H, N))) / N**0.5, unit(jax.nn.silu(jax.random.normal(ks[1], (B, S, H, N)))), jax.random.normal(ks[2], (B, S, H, P))
  g = -8.0 * jax.nn.softplus(2.0 * jax.random.normal(ks[3], (B, S, H)) - 2.0)  # from -0.01 to -40 a position: no bound
  return q, k, v, g, 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H))), jax.random.normal(ks[5], (B, H, P, N))


def _token_by_token(q, k, v, g, beta, state, lens):
  """The recurrence in float64 numpy, row by row up to its length: (o [B, S, H, P] zero past the length, final states)."""
  q, k, v, g, beta, state = (np.asarray(t, np.float64) for t in (q, k, v, g, beta, state))
  out = np.zeros(v.shape)
  for b, n in enumerate(lens):
    for t in range(n):
      s = state[b] * np.exp(g[b, t])[:, None, None]  # [H, P, N]: one decay a head
      u = beta[b, t][:, None] * (v[b, t] - np.einsum("hpn,hn->hp", s, k[b, t]))
      state[b] = s + u[:, :, None] * k[b, t][:, None, :]
      out[b, t] = np.einsum("hpn,hn->hp", state[b], q[b, t])
  return out, state


@pytest.mark.parametrize("length,lens", [(128, (128, 128)), (130, (130, 67)), (64, (64, 1)), (45, (45, 17)), (7, (7, 3)), (1, (1, 1)), (200, (200, 65))])
def test_the_chunked_delta_rule_equals_the_token_by_token_recurrence(length, lens):
  """``_gdn_chunk_scan`` in chunks of 64 from a non-zero state — lengths on, under and across a chunk's edge, rows of
  ragged lengths (log decay and beta 0 past a row's length, as ``_gdn_layer`` masks them), keys that correlate as
  silu's outputs do, beta up to 2: outputs up to each row's length and the state after it are the recurrence's. And
  the scalar-decay step is ``kda_state_step`` with the head's decay spread over its key channels, one token at a time."""
  q, k, v, g, beta, s0 = _random_gdn_inputs(2, length, length)
  valid = jnp.arange(length)[None, :] < jnp.asarray(lens)[:, None]
  o, state = dec._gdn_chunk_scan(q, k, v, jnp.where(valid[..., None], g, 0.0), jnp.where(valid[..., None], beta, 0.0), s0, 64)
  want_o, want_state = _token_by_token(q, k, v, g, beta, s0, lens)
  np.testing.assert_allclose(np.where(np.asarray(valid)[..., None, None], np.asarray(o), 0.0), want_o, atol=5e-6, rtol=0)
  np.testing.assert_allclose(np.asarray(state), want_state, atol=5e-6, rtol=0)
  leaf = jnp.stack([jnp.zeros_like(s0), s0])  # layer 1 of a leaf of two
  for t in range(length):
    alpha = jnp.broadcast_to(jnp.exp(g[:, t])[..., None], k[:, t].shape)
    leaf, y = ssm_ops.kda_state_step(leaf, 1, alpha, beta[:, t], k[:, t], v[:, t], q[:, t], valid[:, t])
    np.testing.assert_allclose(np.asarray(y)[np.asarray(valid[:, t])], want_o[:, t][np.asarray(valid[:, t])], atol=5e-6, rtol=0)
  np.testing.assert_allclose(np.asarray(leaf[1]), want_state, atol=5e-6, rtol=0)
  assert not np.asarray(leaf[0]).any()


def test_log_decays_of_minus_30_a_position_stay_finite_and_equal_where_the_factorised_form_leaves_float32():
  """A Gated-DeltaNet gate has no lower bound. At -30 a position the pairwise form is exact (every decay but the
  diagonal's underflows to 0: each token sees its own write alone); the KDA scan's factorised e^(G_t) x e^(-G_s), given
  the same decays spread over the key channels in the same chunk of 64, is not finite from the third position on."""
  q, k, v, _, beta, s0 = _random_gdn_inputs(2, 130, 5)
  g = jnp.full(beta.shape, -30.0)
  o, state = dec._gdn_chunk_scan(q, k, v, g, beta, s0, 64)
  want_o, want_state = _token_by_token(q, k, v, g, beta, s0, (130, 130))
  assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(state)).all()
  np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-6, rtol=0)
  np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-6, rtol=0)
  factorised = dec._kda_chunk_scan(q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta, s0, 64)[0]
  assert not np.isfinite(np.asarray(factorised)).all()
  assert config_from_hf(HF).ssm_chunk == 64  # the published chunk, whatever the gate: no rule ties it to a bound


def test_the_unit_lower_inverse_by_blocks_is_the_inverse():
  """The chunk's triangular system I + Diag(beta) (K K^T) below the diagonal at 8, 16 and 64 positions, with keys that
  correlate as silu's outputs do and beta up to 2, no decay to shrink it: inverse x system is the identity."""
  mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
  for L in (8, 16, 64):
    _, k, _, _, beta, _ = _random_gdn_inputs(2, L, L)
    T = jnp.eye(L) + jnp.tril(mm("blhn,bshn->bhls", k, k), -1) * jnp.moveaxis(beta, 1, 2)[..., None]
    np.testing.assert_allclose(np.asarray(mm("...ij,...jk->...ik", dec._unit_lower_inverse(T, mm), T)), np.broadcast_to(np.eye(L), T.shape), atol=1e-5, rtol=0)


# ------------------------------------------------------------ pool, state and pages


def test_the_cacheless_forward_equals_the_reference():
  got, _ = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray(TOKENS)[None], jnp.arange(len(TOKENS))[None])
  np.testing.assert_allclose(np.asarray(got[0]), reference(TOKENS), atol=TOL, rtol=0)


def test_every_named_probe_moves_the_reference_past_the_tolerance():
  """Each wrong reference of ``probes`` — the decay or the delta term off, beta without its 2, the gate ahead of the
  head norm, a q/k norm a head, pre-norm blocks, rope on, a layer dropped — lies two hundred tolerances or more from
  the sound one; the two float32-to-bfloat16 probes and float8 operands, which are rounding by design, above forty."""
  sound = reference(TOKENS)
  for name, probe in kind.probes(HF).items():
    moved = float(np.abs(reference(TOKENS, **probe) - sound).max())
    assert moved > (40 if "float" in name else 200) * TOL, (name, moved)


def test_prefill_then_decode_through_pool_state_and_pages_equals_the_reference():
  """float32: 50 prompt tokens prefilled into slot 2 (padded to 64, beside three padding rows), then 40 decode steps,
  one token each, through the two attention layers' pages, the Gated-DeltaNet layers' rectangular state and their
  convolution rows: every step's LOGITS are the reference's full forward at that position, to the order of the sums."""
  want = reference(TOKENS[:90])
  last, pool = prefill(fresh_pool(), {2: TOKENS[:50]}, pad_to=64, pad_rows=3)
  assert pool["k"].shape == (2, 1 + SLOTS * MP, 6, PS, 16) and pool["ssm"].shape == (5, SLOTS, 6, 16, 8) and pool["conv"].shape == (5, SLOTS, 3, 6 * (8 + 8 + 16))
  np.testing.assert_allclose(np.asarray(last[0]), want[49], atol=TOL, rtol=0)
  for t in range(50, 90):
    logits, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
    np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0, err_msg=f"decode step at position {t}")
  for other in (0, 1, 3):  # nothing was written for the padding row, nor for a slot no request held
    assert not state_of(pool, other)[0].any() and not state_of(pool, other)[1].any()


def test_the_bfloat16_path_stays_within_bfloat16s_rounding_of_the_reference():
  """bfloat16 weights and activations as served, the state float32: prefill and 34 decode steps against the float32
  reference on the same bfloat16 weights, the logits (spread 1) of all 35 positions. bfloat16 keeps 7 bits of mantissa:
  each of the 7 layers' two blocks rounds its increment and the stream — measured 0.012 in the mean and 0.16 at the
  worst entry, a tenth and a quarter of what float8 operands (3 bits) read (0.12 / 0.67). 0.036 and 0.5 are three times
  the readings and under half of the weakest wrong architecture's (a dropped layer: 0.21 / 1.57)."""
  cfg = replace(CFG, dtype=jnp.bfloat16)
  want = reference(TOKENS[:90], params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS))
  dropped = np.abs(reference(TOKENS[:90], drop_layer=6) - want)[55:]
  last, pool = prefill(fresh_pool(cfg), {1: TOKENS[:56]}, pad_to=64, params=BF16_PARAMS, cfg=cfg)
  assert pool["ssm"].dtype == jnp.float32 and pool["conv"].dtype == jnp.bfloat16 and pool["k"].dtype == jnp.bfloat16
  off = [np.abs(np.asarray(last[0], np.float32) - want[55])]
  for t in range(56, 90):
    logits, pool = decode_step(pool, {1: TOKENS[t]}, {1: t}, params=BF16_PARAMS, cfg=cfg)
    off.append(np.abs(logits[1].astype(np.float32) - want[t]))
  mean, worst = float(np.mean(off)), float(np.max(off))
  assert mean < 0.036 < 0.5 * float(dropped.mean()) and worst < 0.5 < 0.5 * float(dropped.max()), (mean, worst)


def test_a_padded_group_leaves_each_row_the_state_of_its_unpadded_run():
  """Rows of 50, 33 and 2 tokens as one group padded to 64: padding has no decay and no update and is cut from the
  convolution's tail, so each slot's state is what the row's own prefill leaves alone."""
  prompts = {0: TOKENS[:50], 1: TOKENS[10:43], 3: TOKENS[60:62]}
  _, grouped = prefill(fresh_pool(), prompts, pad_to=64, pad_rows=1)
  for slot, toks in prompts.items():
    _, solo = prefill(fresh_pool(), {slot: toks}, pad_to=None if slot == 1 else 64)
    for got, want in zip(state_of(grouped, slot), state_of(solo, slot)):
      np.testing.assert_allclose(got, want, atol=TOL, rtol=0, err_msg=f"slot {slot}")


def test_a_prompt_prefilled_in_two_chunks_equals_one():
  """Positions [0, 48) then [48, 83): the second call continues from the slot's own state, convolution rows and pages."""
  toks = TOKENS[:83]
  whole_logits, whole = prefill(fresh_pool(), {1: toks}, pad_to=96)
  _, pool = prefill(fresh_pool(), {1: toks[:48]}, pad_to=64)
  cut_logits, cut = prefill(pool, {1: toks}, prefix={1: 48}, pad_to=64)
  np.testing.assert_allclose(np.asarray(cut_logits), np.asarray(whole_logits), atol=TOL, rtol=0)
  for got, want in zip(state_of(cut, 1), state_of(whole, 1)):
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(cut_logits[0]), reference(toks)[-1], atol=TOL, rtol=0)


def test_a_second_chunk_in_a_group_of_unsorted_slots_beside_a_fresh_and_a_padding_row_equals_one_chunk():
  """The one path that USES the state a prefill group reads (``fresh`` false; ``models/decoder.py _state_rows``, ISSUE
  48), and no cell of the benchmark sends it: two prompts prefilled to positions 48 and 32 as a group of slots 3, 0 and
  a padding row, then continued in ONE group whose rows name slots 3, 2, 0 — neither sorted nor adjacent; slot 2's row
  starts at position 0 — and a padding row, which names the slot past the last (its read is clamped onto slot 3's, its
  write dropped). Every row ends in the logits and the state of its one-chunk prefill and in the token-by-token
  reference's logits; slot 1, which no row names, stays zero."""
  a, b, c = TOKENS[:83], TOKENS[10:80], TOKENS[60:90]
  _, pool = prefill(fresh_pool(), {3: a[:48], 0: b[:32]}, pad_to=64, pad_rows=1)
  logits, pool = prefill(pool, {3: a, 2: c, 0: b}, prefix={3: 48, 0: 32}, pad_to=64, pad_rows=1)
  for i, (slot, toks) in enumerate({3: a, 2: c, 0: b}.items()):
    whole_logits, whole = prefill(fresh_pool(), {slot: toks}, pad_to=96)
    np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(whole_logits[0]), atol=TOL, rtol=0, err_msg=f"slot {slot}")
    np.testing.assert_allclose(np.asarray(logits[i]), reference(toks)[-1], atol=TOL, rtol=0, err_msg=f"slot {slot}")
    for got, want in zip(state_of(pool, slot), state_of(whole, slot)):
      np.testing.assert_allclose(got, want, atol=TOL, rtol=0, err_msg=f"slot {slot}")
  assert not any(leaf.any() for leaf in state_of(pool, 1))


def test_a_reused_slot_gives_its_second_tenant_the_solo_answer():
  """Slot 2 serves one request (prefill + decode steps), then another from position 0: the second sees zeros, not its
  predecessor's state, and its logits and state are those of a pool it has to itself, bit for bit."""
  _, pool = prefill(fresh_pool(), {2: TOKENS[:40]}, pad_to=64)
  for t in range(40, 46):
    _, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
  assert state_of(pool, 2)[0].any()
  second = TOKENS[50:77]
  reused_logits, reused = prefill(pool, {2: second}, pad_to=64)
  solo_logits, solo = prefill(fresh_pool(), {2: second}, pad_to=64)
  np.testing.assert_array_equal(np.asarray(reused_logits), np.asarray(solo_logits))
  for got, want in zip(state_of(reused, 2), state_of(solo, 2)):
    np.testing.assert_array_equal(got, want)


def test_a_decode_chunk_leaves_an_inactive_rows_state_bit_for_bit():
  """A chunk of 4 steps of ``decode.paged_batch`` with rows 0 and 3 active: rows 1 and 2, resident but not stepped,
  keep the state and the convolution rows exactly."""
  _, pool = prefill(fresh_pool(), {0: TOKENS[:20], 1: TOKENS[20:50], 2: TOKENS[50:58], 3: TOKENS[30:70]}, pad_to=64)
  before = {slot: state_of(pool, slot) for slot in range(SLOTS)}
  active = np.asarray([True, False, False, True])
  pos = np.asarray([20, 30, 8, 40], np.int32)
  _, _, new_pos, pool = dec.fused_paged_batch_decode(
    PARAMS, CFG, SHARD, jnp.ones((SLOTS, 1), jnp.int32), pool, tables(), jnp.asarray(pos), jnp.asarray(active), np.zeros((SLOTS,), np.float32), 4, page_size=PS, use_kernel=False,
  )
  assert np.asarray(new_pos).tolist() == [24, 30, 8, 44]
  for slot in (1, 2):
    for got, want in zip(state_of(pool, slot), before[slot]):
      np.testing.assert_array_equal(got, want)
  for slot in (0, 3):
    assert not np.array_equal(state_of(pool, slot)[0], before[slot][0]) and not np.array_equal(state_of(pool, slot)[1], before[slot][1])


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


def test_the_scheduler_serves_interleaved_requests_as_the_reference_does(monkeypatch, capsys):
  """Two requests of different lengths through ``BatchedServer`` (admission groups, decode chunks, the pool's state and
  pages) answer greedy-equal to the reference; the same long prompt again reuses no page; prefix reuse, the host tier,
  speculation and mixed ticks are off by the ONE property ``recurrent_layers``; the gauges say which rule steps the
  state and what it weighs."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "2")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(SHARD, CFG, PARAMS)
  server = BatchedServer(engine)
  long_prompt, other = [int(t) for t in TOKENS[:52]], [int(t) for t in TOKENS[60:75]]
  resets = lambda: metrics.counter_value("recurrent_state_resets_total")  # noqa: E731
  before = resets()
  try:
    first = _serve(server, [long_prompt, other], 6)
    hits = metrics.counter_value("prefix_cache_hit_pages_total")
    again = _serve(server, [long_prompt], 6)
    assert metrics.counter_value("prefix_cache_hit_pages_total") == hits and not server.allocator.cached_keys()
  finally:
    server.shutdown()
  assert again[0] == first[0] and len(first[0]) == len(first[1]) == 6
  assert _greedy_under_the_reference(long_prompt, first[0]) and _greedy_under_the_reference(other, first[1])
  assert CFG.recurrent_layers == 5 and server.tier is None and not server.spec and not server._mixed_active() and not server.ops.mixed_tick_supported() and server.ops.prefill_donates_pool
  assert resets() - before == 3
  assert metrics.gauge_value("recurrent_state_bytes") == 2 * 5 * (6 * 16 * 8 * 4 + 3 * 6 * 32 * 4)
  forms = {form: metrics.gauge_value("recurrent_state_step", labels={"form": form}) for form in ssm_ops.STATE_STEP_FORMS}
  assert forms == {"one_pass": 0, "reference": 0, "delta_one_pass": 0, "delta_reference": 1}  # a CPU: the XLA expression
  leaf = jax.ShapeDtypeStruct((9, 64, 30, 192, 96), jnp.float32)  # the published leaf: a 96-wide face is no whole lane group, which shuts out the Mamba kernel and not the delta rule's (ISSUE 45)
  assert ssm_ops.state_step_form(leaf, True, "gdn") == "delta_one_pass" and ssm_ops.state_step_form(leaf, False, "gdn") == "delta_reference" and not ssm_ops.one_pass_supported(leaf, True)
  assert capsys.readouterr().out.count("keep a recurrent state per slot") == 1


# ------------------------------------------------------------ tracing


def test_the_scopes_reach_the_lowered_decode_program():
  """``xot.ssm_proj`` (``w_qkv`` / ``w_z`` / ``w_ab``, ``w_out`` and the norm that follows it) and ``xot.ssm`` (convolution,
  gates, the state's read, delta step and write, head norm, output gate) and the attention layers' scopes are in the
  lowered ``decode.paged_batch``; every rsqrt of it — a post-norm's among them — lies under a component's scope, so
  nothing of a block joins ``decode_unscoped_device_ms``."""
  args = (
    PARAMS, CFG, SHARD, jnp.ones((SLOTS, 1), jnp.int32), fresh_pool(), jnp.asarray(tables()), jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.ones((SLOTS,), bool),
    jnp.zeros((SLOTS,), jnp.float32), jnp.full((SLOTS,), 8, jnp.int32), 4, 8, PS, False, jax.random.PRNGKey(1), None,
  )
  text = dec._fused_paged_batch_decode_impl.xot_jitted.lower(*args).as_text(debug_info=True)
  scopes = set(re.findall(r"xot\.[a-z_]+", text))
  want = {"xot.ssm", "xot.ssm_proj", "xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.ffn", "xot.head", "xot.sample"}
  assert want <= scopes, sorted(want - scopes)
  assert re.search(r'"[^"]*xot\.ssm/[^"]*dynamic_update_slice', text), "no state write under xot.ssm"
  locs = dict(re.findall(r"(#loc\d+) = loc\((.*)\)$", text, flags=re.M))

  def named(ref: str, depth: int = 0) -> str:  # a location's whole chain of names
    body = locs.get(ref, "")
    return body + "".join(named(r, depth + 1) for r in re.findall(r"#loc\d+", body)) if depth < 8 else body

  norms = [m for m in re.finditer(r"stablehlo\.rsqrt.*loc\((#loc\d+)\)", text)]
  assert len(norms) >= 6 and all(re.search(r"xot\.(ssm_proj|ssm|attn_proj|ffn|head)", named(m.group(1))) for m in norms)
