"""The decode step of a state-space layer's recurrent state (ISSUE 35): the
one-pass Mosaic kernel of ``ops/ssm.py`` in interpret mode against the
reference expression that every CPU run takes, and the predicate that
chooses between them. Everything is float32; the two forms differ by the
order of one sum over N (and, on a CPU, by whether a multiply and an add were
contracted), so a state of unit scale agrees to 1e-6 and ``y`` — a sum of 128
terms of scale 1 — to 1e-5 of its own scale.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.ops import ssm

SMALL = (3, 5, 4, 8, 128)  # [Ls, B, H, P, N]: every head in one tile
CELL = (2, 2, 64, 64, 128)  # granite-4.0-h-micro's row tile [64, 64, 128]: two head blocks a row
SHAPES = pytest.mark.parametrize("shape", [SMALL, CELL], ids=["small", "cell_tile"])


def inputs(seed: int, shape, near_one: bool = False):
  """A random state and one step's operands in the served ranges: Δ = softplus(N(−2, 1)), decay exp(−Δ·A) with A in
  [1, 16] (``near_one``: within 1e-4 of 1, the slow heads), Δ·x, B and C of unit scale."""
  Ls, B, H, P, N = shape
  k = jax.random.split(jax.random.PRNGKey(seed), 6)
  leaf = jax.random.normal(k[0], shape, jnp.float32)
  dt = jax.nn.softplus(jax.random.normal(k[1], (B, H)) - 2.0)
  a = 1.0 - 1e-4 * jax.random.uniform(k[2], (B, H)) if near_one else jnp.exp(-dt * jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.77)))
  return leaf, a, dt[:, :, None] * jax.random.normal(k[3], (B, H, P)), jax.random.normal(k[4], (B, N)), jax.random.normal(k[5], (B, N))


def both(leaf, layer, a, dtx, bm, cm, active):
  want = ssm.ssm_state_step(leaf, layer, a, dtx, bm, cm, active)
  got = ssm.ssm_state_step(leaf, layer, a, dtx, bm, cm, active, use_kernel=True, interpret=True)
  return jax.tree.map(np.asarray, (got, want))


@SHAPES
def test_one_pass_equals_the_reference_expression(shape):
  leaf, a, dtx, bm, cm = inputs(0, shape)
  assert ssm.one_pass_supported(leaf, True)
  (state, y), (want_state, want_y) = both(leaf, 1, a, dtx, bm, cm, jnp.ones((shape[1],), bool))
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5 * np.abs(want_y).max())
  assert not np.array_equal(state[1], np.asarray(leaf[1]))  # (it was stepped)


@SHAPES
@pytest.mark.parametrize("layer", [0, 1])
def test_only_the_named_layer_of_the_leaf_changes(shape, layer):
  leaf, a, dtx, bm, cm = inputs(1, shape)
  (state, _), _ = both(leaf, layer, a, dtx, bm, cm, jnp.ones((shape[1],), bool))
  for other in range(shape[0]):
    assert np.array_equal(state[other], np.asarray(leaf[other])) == (other != layer)


@SHAPES
def test_an_inactive_rows_tile_is_kept_bit_for_bit(shape):
  leaf, a, dtx, bm, cm = inputs(2, shape)
  active = jnp.arange(shape[1]) != 1
  (state, y), (want_state, want_y) = both(leaf, 1, a, dtx, bm, cm, active)
  np.testing.assert_array_equal(state[1, 1], np.asarray(leaf[1, 1]))
  np.testing.assert_array_equal(want_state[1, 1], np.asarray(leaf[1, 1]))
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y[np.asarray(active)], want_y[np.asarray(active)], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())


@SHAPES
@pytest.mark.parametrize("which", ["none", "all_but_the_first", "the_last_alone", "the_first_alone", "every_other"])
def test_rows_that_stand_still_keep_every_bit_wherever_they_lie(shape, which):
  """The one-pass form does not move an inactive row's tile: its grid steps stand on a neighbour's. Whatever rows are
  inactive — all of them, a run ahead of the first active row, a run behind the last, every other one — their tiles
  come back bit for bit, the active rows' are the reference's, and no other layer is touched."""
  B = shape[1]
  rows = np.arange(B)
  active = {"none": rows < 0, "all_but_the_first": rows > 0, "the_last_alone": rows == B - 1, "the_first_alone": rows == 0, "every_other": rows % 2 == 1}[which]
  leaf, a, dtx, bm, cm = inputs(5, shape)
  (state, y), (want_state, want_y) = both(leaf, 1, a, dtx, bm, cm, jnp.asarray(active))
  np.testing.assert_array_equal(state[1][~active], np.asarray(leaf[1])[~active])
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y[active], want_y[active], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())
  assert np.array_equal(state[0], np.asarray(leaf[0])) and np.isfinite(y).all()


def test_the_forms_stay_together_over_300_steps_of_slow_decay():
  """300 consecutive steps with fresh operands and decays within 1e-4 of 1 (the heads that forget slowest, where a
  rounding would pile up): each form steps its own state; at the end they agree to 1e-5 of the state's scale."""
  shape = (1, 2, 4, 8, 128)
  leaf, *_ = inputs(3, shape)
  active = jnp.ones((shape[1],), bool)

  @jax.jit
  def run(leaf):
    def step(carry, seed):
      one, ref = carry
      _, a, dtx, bm, cm = inputs(seed, shape, near_one=True)
      one, y_one = ssm.ssm_state_step(one, 0, a, dtx, bm, cm, active, use_kernel=True, interpret=True)
      ref, y_ref = ssm.ssm_state_step(ref, 0, a, dtx, bm, cm, active)
      return (one, ref), jnp.max(jnp.abs(y_one - y_ref)) / jnp.max(jnp.abs(y_ref))
    return jax.lax.scan(step, (leaf, leaf), jnp.arange(100, 400))

  (one, ref), y_gap = run(leaf)
  scale = float(jnp.max(jnp.abs(ref)))
  assert scale > 3.0  # the state grew: decays near 1 keep what 300 increments brought
  assert float(jnp.max(jnp.abs(one - ref))) <= 1e-5 * scale
  assert float(jnp.max(y_gap)) <= 1e-5


@pytest.mark.parametrize(
  "what,shape,dtype,use_kernel,want",
  [
    ("the cell's leaf on a TPU", (36, 64, 64, 64, 128), jnp.float32, True, True),
    ("sixteen slots", (36, 16, 64, 64, 128), jnp.float32, True, True),
    ("a bfloat16 leaf", (36, 64, 64, 64, 128), jnp.bfloat16, True, False),
    ("a state narrower than the lanes", (4, 4, 8, 16, 64), jnp.float32, True, False),
    ("the rehearsal widths", (4, 4, 8, 16, 16), jnp.float32, True, False),
    ("head rows that are no whole sublane group", (4, 4, 8, 12, 128), jnp.float32, True, False),
    ("heads that do not tile", (2, 2, 67, 64, 128), jnp.float32, True, False),
    ("a program told no kernel", (36, 64, 64, 64, 128), jnp.float32, False, False),
  ],
)
def test_the_predicate_reads_the_leaf_and_what_the_program_was_told(what, shape, dtype, use_kernel, want):
  leaf = jax.ShapeDtypeStruct(shape, dtype)
  assert ssm.one_pass_supported(leaf, use_kernel) is want, what
  assert ssm.state_step_form(leaf, use_kernel) == ("one_pass" if want else "reference")


@pytest.mark.parametrize("platform,want", [("tpu", True), ("cpu", False), ("gpu", False)])
def test_the_form_follows_the_platform_through_use_kernel(platform, want):
  """``use_kernel`` is what ``fused_paged_batch_decode`` resolves for the whole program (``paged_kernel_supported``: a
  TPU); off the TPU the one-pass form is refused whatever the leaf."""
  from xotorch_support_jetson_tpu.models.config import ModelConfig
  from xotorch_support_jetson_tpu.ops.paged import paged_kernel_supported

  cfg = ModelConfig(dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=64, hidden_dim=128, vocab_size=128)
  assert ssm.one_pass_supported(jax.ShapeDtypeStruct((36, 64, 64, 64, 128), jnp.float32), paged_kernel_supported(cfg, platform)) is want


def test_a_leaf_the_kernel_does_not_tile_takes_the_reference_whatever_it_is_told():
  shape = (2, 3, 4, 16, 16)
  leaf, a, dtx, bm, cm = inputs(4, shape)
  (state, y), (want_state, want_y) = both(leaf, 0, a, dtx, bm, cm, jnp.ones((3,), bool))
  np.testing.assert_array_equal(state, want_state)
  np.testing.assert_array_equal(y, want_y)


# ------------------------------------------------------------ the delta rule
#
# ``kda_state_step`` (ISSUE 45): the one-pass Mosaic kernel ``delta_state_step`` in interpret mode against the XLA
# expression, for both kinds that step it — "kda" (a decay a key channel, a square 128 x 128 face, β in (0, 1)) and "gdn"
# (one decay a head spread over the key channels, unbounded below, a 192 x 96 face that is no whole lane group, β in
# (0, 2)). Heads are cut for speed, faces whole. The forms differ by the order of the sums over N and by which sum
# gives y (the kernel contracts the new state with q; the expression adds u (k·q) to the decayed state's contraction).

KDA_FACE = (2, 5, 4, 128, 128)  # [Ls, B, H, P, N]
GDN_FACE = (2, 5, 6, 192, 96)
DELTA = pytest.mark.parametrize("kind,shape", [("kda", KDA_FACE), ("gdn", GDN_FACE)], ids=["kda", "gdn"])
PATTERNS = {
  "all_active": [1, 1, 1, 1, 1],
  "first_row_inactive": [0, 1, 1, 1, 1],
  "runs_in_the_middle_and_at_the_end": [1, 0, 0, 1, 0],
  "none_active": [0, 0, 0, 0, 0],
}


def delta_inputs(seed: int, kind: str, shape, state_scale: float = 1.0):
  """A random state and one step's operands in the served ranges: k of unit norm, q of norm 1/sqrt(N), v of unit scale;
  "kda": log decays in (-5, 0) a key channel, β in (0, 1); "gdn": ONE log decay a head, down to -30 a step (the gate
  has no lower bound), spread over N as ``_gdn_decode_step`` spreads it, β in (0, 2)."""
  _, B, H, P, N = shape
  ks = jax.random.split(jax.random.PRNGKey(seed), 7)
  unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
  leaf = state_scale * jax.random.normal(ks[0], shape, jnp.float32)
  k, q, v = unit(jax.random.normal(ks[1], (B, H, N))), unit(jax.random.normal(ks[2], (B, H, N))) * N**-0.5, jax.random.normal(ks[3], (B, H, P))
  if kind == "kda":
    alpha, beta = jnp.exp(-5.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, H, N)))), jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
  else:
    g = -30.0 * jax.random.uniform(ks[4], (B, H)) ** 4  # most heads near 0, some down to -30
    alpha, beta = jnp.broadcast_to(jnp.exp(g)[..., None], (B, H, N)), 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
  return leaf, alpha, beta, k, v, q


def delta_both(leaf, layer, alpha, beta, k, v, q, active):
  want = ssm.kda_state_step(leaf, layer, alpha, beta, k, v, q, active)
  got = ssm.kda_state_step(leaf, layer, alpha, beta, k, v, q, active, use_kernel=True, interpret=True)
  return jax.tree.map(np.asarray, (got, want))


@DELTA
@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("tile_bytes", [1 << 20, 1 << 17], ids=["one_tile_a_row", "several_tiles_a_row"])
def test_delta_one_pass_equals_the_reference_expression(kind, shape, pattern, tile_bytes, monkeypatch):
  """``y`` and the stepped layer to the tolerance the Mamba test uses; an inactive row's state and every other layer
  bit for bit, wherever the inactive rows lie and however many tiles a row has (``_TILE_BYTES`` cut to an eighth:
  two heads of the square face a tile, one of the rectangular)."""
  monkeypatch.setattr(ssm, "_TILE_BYTES", tile_bytes)
  assert ssm.delta_one_pass_supported(jax.ShapeDtypeStruct(shape, jnp.float32), True)
  assert ssm._delta_tile(*shape[2:]) == {("kda", 1 << 20): (4, 128), ("kda", 1 << 17): (2, 128), ("gdn", 1 << 20): (6, 192), ("gdn", 1 << 17): (1, 192)}[kind, tile_bytes]
  active = np.asarray(PATTERNS[pattern], bool)
  leaf, *operands = delta_inputs(7, kind, shape)
  (state, y), (want_state, want_y) = delta_both(leaf, 1, *operands, jnp.asarray(active))
  np.testing.assert_array_equal(state[1][~active], np.asarray(leaf[1])[~active])
  np.testing.assert_array_equal(state[0], np.asarray(leaf[0]))
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y[active], want_y[active], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())
  assert np.isfinite(y).all() and (not active.any() or not np.array_equal(state[1][active], np.asarray(leaf[1])[active]))


def test_delta_one_pass_splits_value_rows_where_one_head_is_more_than_a_tile(monkeypatch):
  """Value rows are independent under the rule, so a head whose face is more than a tile is stepped in blocks of whole
  lane groups of value rows (the grid's third axis): [256, 128] at a sixteenth of ``_TILE_BYTES`` is two blocks of 128."""
  monkeypatch.setattr(ssm, "_TILE_BYTES", 1 << 16)
  shape = (2, 3, 2, 256, 128)
  assert ssm._delta_tile(*shape[2:]) == (1, 128)
  active = np.asarray([1, 0, 1], bool)
  leaf, *operands = delta_inputs(8, "kda", shape)
  (state, y), (want_state, want_y) = delta_both(leaf, 0, *operands, jnp.asarray(active))
  np.testing.assert_array_equal(state[0][~active], np.asarray(leaf[0])[~active])
  np.testing.assert_array_equal(state[1], np.asarray(leaf[1]))
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y[active], want_y[active], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())


@DELTA
def test_the_delta_forms_stay_together_over_32_steps(kind, shape):
  """32 consecutive steps with fresh operands, each form stepping its own state: at the end the states agree to 1e-5
  of the state's scale and no step's ``y`` parted by more than 1e-5 of its own — no drift beyond the reference's."""
  shape = (1, 2, *shape[2:])
  leaf, *_ = delta_inputs(11, kind, shape)
  active = jnp.ones((shape[1],), bool)

  @jax.jit
  def run(leaf):
    def step(carry, seed):
      one, ref = carry
      _, *operands = delta_inputs(seed, kind, shape)
      one, y_one = ssm.kda_state_step(one, 0, *operands, active, use_kernel=True, interpret=True)
      ref, y_ref = ssm.kda_state_step(ref, 0, *operands, active)
      return (one, ref), jnp.max(jnp.abs(y_one - y_ref)) / jnp.max(jnp.abs(y_ref))
    return jax.lax.scan(step, (leaf, leaf), jnp.arange(100, 132))

  (one, ref), y_gap = run(leaf)
  scale = float(jnp.max(jnp.abs(ref)))
  assert float(jnp.max(jnp.abs(one - ref))) <= 1e-5 * scale and float(jnp.max(y_gap)) <= 1e-5


@pytest.mark.parametrize(
  "what,kind,shape,dtype,use_kernel,want",
  [
    ("Ling's leaf on a TPU", "kda", (6, 64, 32, 128, 128), jnp.float32, True, True),
    ("Olmo's leaf on a TPU: 96 lanes, 30 heads", "gdn", (9, 64, 30, 192, 96), jnp.float32, True, True),
    ("Ling's leaf on a CPU", "kda", (6, 64, 32, 128, 128), jnp.float32, False, False),
    ("Olmo's leaf on a CPU", "gdn", (9, 64, 30, 192, 96), jnp.float32, False, False),
    ("a bfloat16 leaf", "gdn", (9, 64, 30, 192, 96), jnp.bfloat16, True, False),
    ("a 4-D leaf", "kda", (64, 32, 128, 128), jnp.float32, True, False),
    ("the rehearsal widths", "kda", (3, 4, 4, 16, 16), jnp.float32, True, True),
    ("value rows that are no whole sublane group", "gdn", (2, 4, 4, 12, 96), jnp.float32, True, False),
  ],
)
def test_the_delta_predicate_reads_the_leaf_and_what_the_program_was_told(what, kind, shape, dtype, use_kernel, want):
  leaf = jax.ShapeDtypeStruct(shape, dtype)
  assert ssm.delta_one_pass_supported(leaf, use_kernel) is want, what
  assert ssm.state_step_form(leaf, use_kernel, kind) == ("delta_one_pass" if want else "delta_reference")
  assert set(ssm.STATE_STEP_FORMS) == {"one_pass", "reference", "delta_one_pass", "delta_reference", "no_state_matrix"} and ssm.state_step_form(None, use_kernel, kind) == "no_state_matrix"  # (a pool with no ``ssm`` leaf: ISSUE 57)


def test_the_cells_tiles():
  """What a tile is at the two served faces: Ling's 16 heads (1 MB, PR 42's), Olmo's 10 heads whose 96-wide face is
  counted at the 128 lanes it lies in (0.98 MB) — and granite's Mamba tile is what it was."""
  assert ssm._delta_tile(32, 128, 128) == (16, 128) and ssm._delta_tile(30, 192, 96) == (10, 192)
  assert ssm._head_block(64, 64, 128) == 32


_LING = dict(kv_lora_rank=512, qk_rope_head_dim=64, layer_types=("kda", "attention"))


@pytest.mark.parametrize(
  "what,overrides,platform,no_flash,want",
  [
    ("a latent-attention hybrid of delta-rule layers on a TPU (Ling)", _LING, "tpu", False, True),
    ("the same on a CPU", _LING, "cpu", False, False),
    ("the same with the kernels switched off", _LING, "tpu", True, False),
    ("the same under a plan that leaves an axis to GSPMD", dict(_LING, mosaic_kernels=False), "tpu", False, False),
    ("latent attention without recurrent layers (Moonlight)", dict(kv_lora_rank=512, qk_rope_head_dim=64), "tpu", False, True),
    ("a latent the kernel's latent body does not tile, beside delta-rule layers", dict(_LING, kv_lora_rank=8), "tpu", False, False),
    ("plain attention beside Gated-DeltaNet layers (Olmo): the paged kernel's answer", dict(layer_types=("gdn", "attention")), "tpu", False, True),
    ("plain attention of a head width the paged kernel does not tile", dict(layer_types=("gdn", "attention"), head_dim=96), "tpu", False, False),
  ],
)
def test_what_a_decode_program_is_told_where_its_caller_did_not_say(what, overrides, platform, no_flash, want, monkeypatch):
  """``fused_paged_batch_decode`` resolves ``use_kernel=None`` through ``paged_kernel_supported``, and the recurrent
  layers' Mosaic state steps ride that one answer. Until ISSUE 52 latent attention took the gather whatever a program
  was told and a second resolver answered the platform alone for Ling, so that its delta step took its kernel; the
  kernel's latent body made the paged kernel's own answer true there, and the special case went."""
  from dataclasses import replace

  from xotorch_support_jetson_tpu.models.config import ModelConfig
  from xotorch_support_jetson_tpu.ops.paged import kernel_attends, paged_kernel_supported

  monkeypatch.delenv("XOT_TPU_NO_FLASH", raising=False)
  if no_flash:
    monkeypatch.setenv("XOT_TPU_NO_FLASH", "1")
  cfg = replace(ModelConfig(dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=64, hidden_dim=128, vocab_size=128), **overrides)
  assert paged_kernel_supported(cfg, platform) is want, what
  if cfg.is_mla:  # told the kernel, the layer steps attend through it exactly where the latent body tiles the model
    assert kernel_attends(cfg, True) is (cfg.kv_lora_rank % 128 == 0 and cfg.mosaic_kernels)


# ------------------------------------------------------------ B and C a head (several B/C groups, ISSUE 53)


def _per_head(t, H: int, groups: int):
  """[B, N] drawn for one group → [B, H, N] with ``groups`` different groups, each spread over its H / groups heads."""
  grouped = jnp.stack([jnp.roll(t, g, axis=-1) * (1.0 + 0.25 * g) for g in range(groups)], axis=1)  # [B, G, N]
  return jnp.repeat(grouped, H // groups, axis=1)


@SHAPES
@pytest.mark.parametrize("groups", [1, 4])
def test_b_and_c_a_head_step_both_forms_as_the_einsum_does(shape, groups):
  """``bm``, ``cm`` [B, H, N] — a model of several B/C groups, each head its group's (``models/decoder.py
  _ssm_decode_step`` spreads them) — in the reference expression and in the one-pass kernel (interpreted; its blocks of
  B and C are the decay's, [Hb, N]) against the recurrence written as einsums; an inactive row keeps its tile bit for
  bit. With one group spread over every head both forms give what the [B, N] operand gives."""
  leaf, a, dtx, bm, cm = inputs(7, shape)
  H = shape[2]
  bh, ch = _per_head(bm, H, groups), _per_head(cm, H, groups)
  active = jnp.arange(shape[1]) != 1
  (state, y), (want_state, want_y) = both(leaf, 1, a, dtx, bh, ch, active)
  new = np.asarray(a)[:, :, None, None] * np.asarray(leaf[1]) + np.einsum("bhp,bhn->bhpn", np.asarray(dtx), np.asarray(bh))
  np.testing.assert_allclose(want_state[1][np.asarray(active)], new[np.asarray(active)], rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(want_y[np.asarray(active)], np.einsum("bhpn,bhn->bhp", new, np.asarray(ch))[np.asarray(active)], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())
  np.testing.assert_allclose(state, want_state, rtol=1e-6, atol=1e-6)
  np.testing.assert_allclose(y[np.asarray(active)], want_y[np.asarray(active)], rtol=1e-5, atol=1e-5 * np.abs(want_y).max())
  np.testing.assert_array_equal(state[1, 1], np.asarray(leaf[1, 1]))
  np.testing.assert_array_equal(state[0], np.asarray(leaf[0]))
  if groups == 1:
    (one_state, one_y), _ = both(leaf, 1, a, dtx, bm, cm, active)
    np.testing.assert_array_equal(state, one_state)
    np.testing.assert_allclose(y, one_y, rtol=1e-6, atol=1e-6)
  assert ssm.state_step_form(leaf, True) == "one_pass"
