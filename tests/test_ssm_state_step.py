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
