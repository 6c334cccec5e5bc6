"""The decode step of a recurrent layer's per-slot state: one owner, two update rules, three kinds of layer.

A hybrid's page pool carries, beside its K/V pages, the leaf ``ssm``
[Ls, slots, H, P, N] in float32 (``ops/paged.py init_paged_pool``): each slot
row's state S of every state-space layer. One decode step of one layer is

  S ← a · S + (Δ·x) ⊗ B        y = S · C

per row and head, with a [B, H] the decay, Δ·x [B, H, P], B and C [B, N], all
float32. The state is nearly all the bytes of the layer's step (268 MB read
and written at 64 rows of granite-4.0-h-micro; everything else is kilobytes a
row), so what matters is how often it crosses HBM. This module holds the two
forms of that step and the one choice between them:

- the **reference** expression: XLA updates the layer's slice of the leaf in
  place in one fusion and reads the updated state a second time to contract
  it with C (PERF.md §5, PR 34; 605 µs a layer at granite's 64 rows). It is
  what every CPU run takes, and every leaf the one-pass form does not tile.
- the **one-pass** form, a Mosaic kernel: a row's [Hb, P, N] tile comes into
  VMEM once, is decayed, incremented, contracted with C and stored back to
  the place it came from (the leaf aliased input → output, the layer a
  scalar-prefetch operand: no layer is sliced out and no leaf copied): 423 µs
  a layer there, what a plain copy of the same tiles takes, and less by the
  share of rows that are not active, whose tiles it does not move at all
  (PERF.md §6, PR 35).

Both keep the state, the decay, the increment, the sum and the contraction in
float32; they differ by the order of one sum over N. ``exp`` and ``softplus``
stay with the caller (``models/decoder.py _ssm_decode_step``). Which form a
program takes is read from what it can observe — the ``use_kernel`` its
dispatch resolved (``paged_kernel_supported``: a TPU) and the leaf's shape
and dtype (``one_pass_supported``) — and set nowhere.

The second rule is the **delta rule** (``kda_state_step``) of a
Kimi-Delta-Attention layer ("kda") and of a Gated-DeltaNet layer ("gdn"), over
the same leaf with P the value channels and N the key channels of a head's
matrix state — square for "kda" (128 x 128 as published), rectangular for
"gdn" (192 x 96):

  S ← S · Diag(α)       u = β (v − S k)       S ← S + u ⊗ k       y = S q

with α [B, H, N] the decay of each key channel, β [B, H], k and q [B, H, N],
v [B, H, P]. "kda" decays every key channel by its own α and keeps β in (0, 1);
"gdn" has ONE α a head, which its caller spreads over N (``models/decoder.py
_gdn_decode_step``), and β in (0, 2): the same step, which asks nothing of
either. A face whose N is not whole lanes (gdn's 96) is stored lane-padded by
the TPU, a third more bytes than the state holds (PERF.md §6, PR 44). The rule
has the reference expression only: one pass reads the state
for the two contractions it needs of the decayed state (S·Diag(α) with k and
with q; y = (S·Diag(α)) q + u (k·q)), a second reads it again and writes the
update — two reads and a write where the least is one of each. A one-pass
Mosaic form is ROADMAP A's, with the trace's number.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES, SUBLANES = 128, 8  # a float32 vector register of the TPU: what a state tile's [P, N] face has to fill
_TILE_BYTES = 1 << 20  # of one [Hb, P, N] block: in and out, double-buffered, with the body's temporaries inside v5e's 16 MiB of scoped VMEM


def _head_block(H: int, P: int, N: int) -> int | None:
  """Heads of one tile: all of them if that fits ``_TILE_BYTES``, else the most that divide H in whole sublane
  groups (the per-head operands' blocks are [Hb, P] and [Hb, N]); None where no such number fits."""
  fits = [hb for hb in range(1, H + 1) if H % hb == 0 and (hb == H or hb % SUBLANES == 0) and hb * P * N * 4 <= _TILE_BYTES]
  return max(fits, default=None)


def one_pass_supported(ssm_leaf, use_kernel: bool) -> bool:
  """Whether the decode step of a program told ``use_kernel`` passes over this ``ssm`` leaf once (the Mosaic kernel):
  a float32 leaf [Ls, B, H, P, N] whose [P, N] face is whole vector registers and whose heads tile. Anything else —
  another dtype, a state narrower than the lanes, a program off the TPU — takes the reference expression."""
  if not use_kernel or ssm_leaf.ndim != 5 or ssm_leaf.dtype != jnp.float32:
    return False
  H, P, N = ssm_leaf.shape[2:]
  return N % LANES == 0 and P % SUBLANES == 0 and _head_block(H, P, N) is not None


STATE_STEP_FORMS = ("one_pass", "reference", "delta_reference")


def state_step_form(ssm_leaf, use_kernel: bool, kind: str = "mamba") -> str:
  """The name of the rule and form a decode program of ``kind`` layers ("mamba" | "kda" | "gdn") steps this leaf in: the
  label of the gauge ``recurrent_state_step``."""
  if kind in ("kda", "gdn"):  # the delta rule, whatever the decay's and the face's shape
    return "delta_reference"
  return "one_pass" if one_pass_supported(ssm_leaf, use_kernel) else "reference"


def ssm_state_step(ssm_leaf, layer, a, dtx, bm, cm, active, use_kernel: bool = False, interpret: bool = False):
  """One recurrence step of state-space layer ``layer`` for every slot row.

  ssm_leaf [Ls, B, H, P, N] float32, the pool's carried leaf, stepped in place at ``layer`` (a traced scalar); a
  [B, H] the decay; dtx [B, H, P] = Δ·x; bm, cm [B, N]; active [B] bool — all float32. Returns (ssm_leaf, y
  [B, H, P] float32). A row that is not ``active`` keeps its state bit for bit; its ``y`` is of no use to anyone (the
  one-pass form writes zeros there)."""
  if one_pass_supported(ssm_leaf, use_kernel):
    return _state_step_one_pass(ssm_leaf, layer, a, dtx, bm, cm, active, interpret)
  return _state_step_reference(ssm_leaf, layer, a, dtx, bm, cm, active)


def _state_step_reference(ssm_leaf, layer, a, dtx, bm, cm, active):
  ssm0 = jax.lax.dynamic_index_in_dim(ssm_leaf, layer, 0, keepdims=False).astype(jnp.float32)
  ssm = a[:, :, None, None] * ssm0 + dtx[..., None] * bm[:, None, None, :]
  y = jnp.einsum("bhpn,bn->bhp", ssm, cm)
  return jax.lax.dynamic_update_index_in_dim(ssm_leaf, jnp.where(active[:, None, None, None], ssm, ssm0).astype(ssm_leaf.dtype), layer, 0), y


def kda_state_step(ssm_leaf, layer, alpha, beta, k, v, q, active):
  """One delta-rule step of Kimi-Delta-Attention or Gated-DeltaNet layer ``layer`` for every slot row.

  ssm_leaf [Ls, B, H, P, N] float32, stepped in place at ``layer`` (a traced scalar); alpha [B, H, N] the decay of
  each key channel; beta [B, H]; k, q [B, H, N]; v [B, H, P]; active [B] bool — all float32. Returns (ssm_leaf, y
  [B, H, P] float32). A row that is not ``active`` keeps its state bit for bit."""
  s0 = jax.lax.dynamic_index_in_dim(ssm_leaf, layer, 0, keepdims=False).astype(jnp.float32)
  # Both contractions of the decayed state are sibling sums over one read of it (multiply-and-sum, so float32 on
  # the vector unit whatever the matrix unit's default precision); then y = S_new q = (S·Diag(α)) q + u (k·q), so
  # the updated state is written and never read back.
  sk = jnp.sum(s0 * (alpha * k)[:, :, None, :], axis=-1)
  sq = jnp.sum(s0 * (alpha * q)[:, :, None, :], axis=-1)
  u = beta[..., None] * (v - sk)
  y = sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
  new = s0 * alpha[:, :, None, :] + u[..., None] * k[:, :, None, :]
  return jax.lax.dynamic_update_index_in_dim(ssm_leaf, jnp.where(active[:, None, None, None], new, s0).astype(ssm_leaf.dtype), layer, 0), y


# ------------------------------------------------------- the one-pass kernel
#
# Grid (row, head block); BlockSpecs bring a row's [Hb, P, N] tile of the
# leaf's layer into VMEM and take it back to where it came from, double-
# buffered by the pipeline, so the body is the arithmetic alone — whole-tile
# expressions, nothing unrolled by hand. Every other block of the aliased
# leaf is never touched. What sets its pace is the tile's round trip: a copy
# with the same blocks and no arithmetic takes the same time (PERF.md §6,
# PR 35).
#
# A row that is not active moves nothing: its grid steps name the tile the
# step before them named (``stand``), which the pipeline neither fetches
# again nor writes back while the name stays, and their body leaves it alone
# — so the row's own tile is never in VMEM and keeps every bit, and a step
# costs what its active rows' tiles cost.


def _state_step_kernel(layer_ref, active_ref, stand_ref, a_ref, dtx_ref, b_ref, c_ref, s_ref, out_ref, y_ref):
  del layer_ref, stand_ref  # the index maps read them
  import jax.experimental.pallas as pl

  active = active_ref[pl.program_id(0)] != 0
  first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

  @pl.when(active)
  def _():
    # (the decay lies along the lanes and spreads over sublanes; Δ·x [Hb, P] goes lanes → sublanes, then along the lanes)
    new = a_ref[0][:, None, :] * s_ref[0, 0] + dtx_ref[0][:, :, None] * b_ref[0][None]
    y_ref[0] = jnp.sum(new * c_ref[0][None], axis=-1)
    out_ref[0, 0] = new

  @pl.when(jnp.logical_not(active))
  def _():
    y_ref[0] = jnp.zeros_like(y_ref[0])

  @pl.when(jnp.logical_not(active) & first)  # rows before the first active one stand on ITS first tile, or, where none is active, on tile (0, 0): until its own step (if any) it goes back as it came
  def _():
    out_ref[0, 0] = s_ref[0, 0]


def _state_step_one_pass(ssm_leaf, layer, a, dtx, bm, cm, active, interpret: bool):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  _, B, H, P, N = ssm_leaf.shape
  hb = _head_block(H, P, N)
  nh = H // hb
  # The tile (row · nh + head block) an inactive row's steps stand on: the last tile of the last active row before
  # it, which is the tile of the step before; ahead of every active row, the first active row's first tile.
  last = jax.lax.cummax(jnp.where(active, jnp.arange(B, dtype=jnp.int32), -1))
  stand = jnp.where(last >= 0, last * nh + nh - 1, jnp.argmax(active).astype(jnp.int32) * nh)

  def tile_at(b, h, layer, active, stand):
    at = jnp.where(active[b] != 0, b * nh + h, stand[b])
    return (layer[0], at // nh, at % nh, 0, 0)

  tile = pl.BlockSpec((1, 1, hb, P, N), tile_at)
  per_head = lambda width: pl.BlockSpec((1, hb, width), lambda b, h, *_: (b, h, 0))
  per_row = pl.BlockSpec((1, 1, N), lambda b, h, *_: (b, 0, 0))
  # The decay goes in spread along the lanes, [B, H, N]: as [B, H, 1] it is lane-padded in HBM and XLA relays it in a
  # copy of its own before every call (12 µs a layer); as [B, H, P] it needs a second lanes → sublanes relayout in
  # the body, which no longer hides under the tile's round trip (437 µs a layer for 423; PERF.md §6, PR 35).
  a = jnp.broadcast_to(a[:, :, None], (B, H, N))
  return pl.pallas_call(
    _state_step_kernel,
    out_shape=[jax.ShapeDtypeStruct(ssm_leaf.shape, ssm_leaf.dtype), jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
    grid_spec=pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3, grid=(B, nh), in_specs=[per_head(N), per_head(P), per_row, per_row, tile], out_specs=[tile, per_head(P)]
    ),
    input_output_aliases={7: 0},  # the leaf, after the three scalar-prefetch operands and a, dtx, bm, cm
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),  # in order: a standing step counts on the step before it
    interpret=interpret,
    name="ssm_state_step",  # neither the attention kernel's name nor the flash kernel's: the roofline readers count calls by those
  )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32), stand, a, dtx, bm[:, None, :], cm[:, None, :], ssm_leaf)
