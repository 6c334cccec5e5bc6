"""A hybrid of Gated-DeltaNet and full-attention layers in post-norm blocks on the served path (ISSUE 44):
Olmo-Hybrid's architecture at the benchmark's rehearsal widths — Gated-DeltaNet runs of 2 with a full-attention layer
after each, then one more; 6 heads (no power of two) of a 16 x 8 state (N != P), 6 attention heads of 16 with the
RMSNorm over the whole q and k projections — against the benchmark's plain reference
(``benchmark/arch_hybrid_gdn.py reference_forward``: float32, the delta rule token by token, nothing of the program).

The float32 cases run at ``highest`` matmul precision, so the program and the reference differ by the order of their
sums alone: the chunked scan solves a chunk's 64 updates as one triangular system where the reference makes them a
token at a time. Logits have a spread of ~1; tolerances are absolute.

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes —
are ``tests/served_kind.py``'s battery, taken in below under the names they have always had here.
"""

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import Kind, battery, rehearsal_of

import arch_hybrid_gdn  # noqa: E402 — served_kind puts benchmark/ on the path
import common  # noqa: E402
import weights  # noqa: E402

from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402

FILE, HF = rehearsal_of("olmo-hybrid-7b-d12", arch_hybrid_gdn)
BF16_PARAMS = weights.build_params(HF, 11)  # the benchmark's own seeded weights, bfloat16 leaves
KIND = Kind(
  name="olmo", arch=arch_hybrid_gdn, hf=HF, params=jax.tree.map(lambda x: x.astype(jnp.float32), BF16_PARAMS), bf16_params=BF16_PARAMS,
  # The program against the reference, both float32 at "highest": orders of summation only — the chunked scan solves 64
  # updates as one triangular system where the reference makes them a token at a time. Measured 1e-5 at the worst entry
  # of logits of spread 1 (the KDA hybrid's 2e-6: a head's output is normed to unit scale whatever it was, and at a
  # position whose decay has just emptied the state that carries the small o = S q's rounding up to the logits).
  tol=5e-5,
  # Each of the 7 layers' two blocks rounds its increment and the stream — measured 0.012 in the mean and 0.16 at the
  # worst entry, a tenth and a quarter of what float8 operands (3 bits) read (0.12 / 0.67). 0.036 and 0.5 are three times
  # the readings and under half of the weakest wrong architecture's (a dropped layer: 0.21 / 1.57).
  bf16=(0.036, 0.5),
  families=("olmo_hybrid", "olmo-hybrid"),
  pool={"k": (2, 33, 6, 16, 16), "ssm": (5, 4, 6, 16, 8), "conv": (5, 4, 3, 6 * (8 + 8 + 16))},  # two attention layers' pages, the Gated-DeltaNet layers' rectangular state and their convolution rows
  scopes=frozenset({"xot.ssm", "xot.ssm_proj"}),  # ``xot.ssm_proj``: ``w_qkv`` / ``w_z`` / ``w_ab``, ``w_out`` and the norm that follows it; ``xot.ssm``: convolution, gates, the state's read, delta step and write, head norm, output gate
  ops_under=((r"stablehlo\.rsqrt", 6, None, r"xot\.(ssm_proj|ssm|attn_proj|ffn|head)"),),  # every rsqrt — a post-norm's among them — lies under a component's scope
  # the decay or the delta term off, beta without its 2, the gate ahead of the head norm, a q/k norm a head, pre-norm
  # blocks, rope on, a layer dropped: two hundred tolerances or more; the two float32-to-bfloat16 probes and float8
  # operands, which are rounding by design, above forty
  probe_floor=lambda name: 40 if "float" in name else 200,
  cases={"key,value,named": [
    ("attention_bias", True, "attention_bias"), ("rope_theta", 500000.0, "rope_theta"), ("rope_parameters", {"rope_theta": 10000.0}, "rope_theta"),
    ("linear_num_key_heads", 3, "linear_num_key_heads"), ("layer_types", ["linear_attention"] * 6 + ["sliding_attention"], "layer_types"), ("layer_types", ["linear_attention"] * 3, "layer_types"),
  ]},  # fmt: skip
  names={
    "test_prefill_then_decode_through_the_pool_equals_the_reference": "test_prefill_then_decode_through_pool_state_and_pages_equals_the_reference",
    "test_a_padded_group_leaves_each_row_what_its_unpadded_run_does": "test_a_padded_group_leaves_each_row_the_state_of_its_unpadded_run",
    "test_a_decode_chunk_leaves_an_inactive_rows_cache_bit_for_bit": "test_a_decode_chunk_leaves_an_inactive_rows_state_bit_for_bit",
  },
)
CFG, PARAMS = KIND.cfg, KIND.params
globals().update(battery(KIND))


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
  assert jax.tree.map(lambda x: x.shape, jax.eval_shape(lambda: dec.full_model_params(jax.random.PRNGKey(0), CFG)[0])) == jax.tree.map(lambda x: x.shape, PARAMS)  # the benchmark's maker and the program's agree leaf for leaf (shapes alone: nothing is drawn)
  assert not {"attn_norm", "ssm_norm", "mlp_norm"} & (set(PARAMS["layers"]) | set(PARAMS["ssm_layers"])) and PARAMS["layers"]["q_norm"].shape == (2, CFG.q_dim)

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


# ------------------------------------------------------------ what the served gauges say of the published leaf


def test_the_published_leaf_takes_the_delta_rules_one_pass_form_on_a_tpu():
  """The published state leaf has a 96-wide face, no whole lane group: that shuts out the Mamba kernel and not the delta
  rule's (ISSUE 45)."""
  leaf = jax.ShapeDtypeStruct((9, 64, 30, 192, 96), jnp.float32)
  assert ssm_ops.state_step_form(leaf, True, "gdn") == "delta_one_pass" and ssm_ops.state_step_form(leaf, False, "gdn") == "delta_reference" and not ssm_ops.one_pass_supported(leaf, True)
  assert CFG.recurrent_layers == 5
