"""The decode step of a recurrent layer's per-slot state MATRIX: one owner, two update rules, three kinds of layer
("mamba", "kda", "gdn": ``models/config.py STATE_MATRIX_KINDS``). The fourth recurrent kind, "conv" (a gated short
convolution), keeps no matrix — its pool has no ``ssm`` leaf and nothing here is called for it; ``state_step_form``
names that too, so the gauge ``recurrent_state_step`` says so.

A hybrid's page pool carries, beside its K/V pages, the leaf ``ssm``
[Ls, slots, H, P, N] in float32 (``ops/paged.py init_paged_pool``): each slot
row's state S of every state-space layer. One decode step of one layer is

  S ← a · S + (Δ·x) ⊗ B        y = S · C

per row and head, with a [B, H] the decay, Δ·x [B, H, P], B and C [B, N] — one
group, every head's — or [B, H, N], each head its group's (a model of several
B/C groups: the caller spreads a group over its heads, kilobytes a row), all
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
dispatch resolved (``ops/paged.py paged_kernel_supported``: a TPU) and the
leaf's shape and dtype (``one_pass_supported``) — and set nowhere.

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
has the same two forms, chosen the same way (``delta_one_pass_supported``):

- the **reference** expression reads the state for the two contractions it
  needs of the decayed state (S·Diag(α) with k and with q; y = (S·Diag(α)) q
  + u (k·q), so the new state is never read back), then reads it again and
  writes the update — two reads and a write.
- the **one-pass** kernel ``delta_state_step`` (PR 45) brings a row's tile
  into VMEM once, as the Mamba kernel does, and steps it there. Row p of the
  update needs row p of S alone (the prediction (S k)[p], u[p] and
  u[p] · k are row p's), so **value rows are independent** and a tile is any
  block of heads x any block of value rows with the key axis whole: the most
  heads that fit 1 MB of VMEM with every value row (16 of Ling's 32; 10 of
  Olmo's 30, a 96-wide face counted at the 128 lanes it lies in), else blocks
  of value rows of one head. A sum over a key axis of 96 lowers as it is
  (Mosaic masks the lanes past the array's edge).
"""

from __future__ import annotations

from functools import partial

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


def _delta_tile(H: int, P: int, N: int) -> tuple[int, int] | None:
  """(heads, value rows) of one tile of the delta rule's one-pass form: value rows are independent under the rule, so
  any block of heads x any block of value rows with the key axis whole is a tile. The most heads that divide H with
  every value row if that fits ``_TILE_BYTES`` — counted as the tile lies in VMEM, its key axis padded to whole lanes —
  else one head's largest block of whole lane groups of value rows (v and y go in and out [., P] along the lanes); None
  where neither fits. The per-head operands go in as [B, H/hb, hb, .], blocked on full axes: hb need not be a sublane group."""
  row = -(-N // LANES) * LANES * 4
  heads = [hb for hb in range(1, H + 1) if H % hb == 0 and hb * P * row <= _TILE_BYTES]
  if heads and P % SUBLANES == 0:
    return max(heads), P
  rows = [pb for pb in range(LANES, P, LANES) if P % pb == 0 and pb * row <= _TILE_BYTES]
  return (1, max(rows)) if rows else None


def delta_one_pass_supported(ssm_leaf, use_kernel: bool) -> bool:
  """Whether the delta-rule decode step of a program told ``use_kernel`` passes over this ``ssm`` leaf once (the Mosaic
  kernel ``delta_state_step``): a float32 leaf [Ls, B, H, P, N] that tiles. The key axis need not be whole lanes."""
  return bool(use_kernel) and ssm_leaf.ndim == 5 and ssm_leaf.dtype == jnp.float32 and _delta_tile(*ssm_leaf.shape[2:]) is not None


STATE_STEP_FORMS = ("one_pass", "reference", "delta_one_pass", "delta_reference", "no_state_matrix")


def state_step_form(ssm_leaf, use_kernel: bool, kind: str = "mamba") -> str:
  """The name of the rule and form a decode program of ``kind`` layers ("mamba" | "kda" | "gdn") steps this leaf in: the
  label of the gauge ``recurrent_state_step``. ``ssm_leaf`` None — a pool with no such leaf (``cfg.state_matrix``
  false) — is "no_state_matrix": the decode step moves the convolution's tail and nothing else."""
  if ssm_leaf is None:
    return "no_state_matrix"
  if kind in ("kda", "gdn"):  # the delta rule, whatever the decay's and the face's shape
    return "delta_one_pass" if delta_one_pass_supported(ssm_leaf, use_kernel) else "delta_reference"
  return "one_pass" if one_pass_supported(ssm_leaf, use_kernel) else "reference"


def ssm_state_step(ssm_leaf, layer, a, dtx, bm, cm, active, use_kernel: bool = False, interpret: bool = False):
  """One recurrence step of state-space layer ``layer`` for every slot row.

  ssm_leaf [Ls, B, H, P, N] float32, the pool's carried leaf, stepped in place at ``layer`` (a traced scalar); a
  [B, H] the decay; dtx [B, H, P] = Δ·x; bm, cm [B, N], every head's, or [B, H, N], a head's own (several B/C groups);
  active [B] bool — all float32. Returns (ssm_leaf, y [B, H, P] float32). A row that is not ``active`` keeps its state bit for bit; its ``y`` is of no use to anyone (the
  one-pass form writes zeros there)."""
  if one_pass_supported(ssm_leaf, use_kernel):
    return _state_step_one_pass(ssm_leaf, layer, a, dtx, bm, cm, active, interpret)
  return _state_step_reference(ssm_leaf, layer, a, dtx, bm, cm, active)


def _state_step_reference(ssm_leaf, layer, a, dtx, bm, cm, active):
  ssm0 = jax.lax.dynamic_index_in_dim(ssm_leaf, layer, 0, keepdims=False).astype(jnp.float32)
  heads = "h" if bm.ndim == 3 else ""  # B and C a head's own, or every head's
  ssm = a[:, :, None, None] * ssm0 + dtx[..., None] * bm.reshape(bm.shape[0], -1, 1, bm.shape[-1])
  y = jnp.einsum(f"bhpn,b{heads}n->bhp", ssm, cm)
  return jax.lax.dynamic_update_index_in_dim(ssm_leaf, jnp.where(active[:, None, None, None], ssm, ssm0).astype(ssm_leaf.dtype), layer, 0), y


def kda_state_step(ssm_leaf, layer, alpha, beta, k, v, q, active, use_kernel: bool = False, interpret: bool = False):
  """One delta-rule step of Kimi-Delta-Attention or Gated-DeltaNet layer ``layer`` for every slot row.

  ssm_leaf [Ls, B, H, P, N] float32, stepped in place at ``layer`` (a traced scalar); alpha [B, H, N] the decay of
  each key channel; beta [B, H]; k, q [B, H, N]; v [B, H, P]; active [B] bool — all float32. Returns (ssm_leaf, y
  [B, H, P] float32). A row that is not ``active`` keeps its state bit for bit; its ``y`` is of no use to anyone (the
  one-pass form writes zeros there)."""
  if delta_one_pass_supported(ssm_leaf, use_kernel):
    return _delta_step_one_pass(ssm_leaf, layer, alpha, beta, k, v, q, active, interpret)
  return _delta_step_reference(ssm_leaf, layer, alpha, beta, k, v, q, active)


def _delta_step_reference(ssm_leaf, layer, alpha, beta, k, v, q, active):
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


# ------------------------------------------------------ the one-pass kernels
#
# Grid (row, head block[, value-row block]); BlockSpecs bring a row's tile of
# the leaf's layer into VMEM and take it back to where it came from, double-
# buffered by the pipeline, so a body is the arithmetic alone — whole-tile
# expressions, nothing unrolled by hand. Every other block of the aliased
# leaf is never touched. What sets the pace is the tile's round trip: a copy
# with the same blocks and no arithmetic takes the same time (PERF.md §6,
# PR 35).
#
# A row that is not active moves nothing: its grid steps name the tile the
# step before them named (``stand``), which the pipeline neither fetches
# again nor writes back while the name stays, and their body leaves it alone
# — so the row's own tile is never in VMEM and keeps every bit, and a step
# costs what its active rows' tiles cost.


def _standing_tiles(active, nh: int, npb: int = 1):
  """For a grid (row, head block[, value-row block]) over nh x npb tiles a row, in row-major order: (``stand`` [B]
  int32, the leaf's index map). ``stand`` is the tile (row · n + tile of the row) an inactive row's steps stand on: the
  last tile of the last active row before it, which is the tile of the step before; ahead of every active row, the
  first active row's first tile. The index map takes the grid indices, then the scalar-prefetch operands (layer,
  active, stand), and names the block [layer, row, head block, value-row block, 0] of the leaf."""
  n = nh * npb
  last = jax.lax.cummax(jnp.where(active, jnp.arange(active.shape[0], dtype=jnp.int32), -1))
  stand = jnp.where(last >= 0, last * n + n - 1, jnp.argmax(active).astype(jnp.int32) * n)

  def tile_at(b, h, *rest):
    *pb, layer, active, stand = rest
    if not pb:  # (row, head block), value rows whole: the Mamba kernel's map as PR 35 wrote it, so that its Mosaic module stays the one granite's cell was measured with
      at = jnp.where(active[b] != 0, b * nh + h, stand[b])
      return (layer[0], at // nh, at % nh, 0, 0)
    at = jnp.where(active[b] != 0, b * n + h * npb + pb[0], stand[b])
    return (layer[0], at // n, at % n // npb, at % npb, 0)

  return stand, tile_at


def _standing_step(grid_rank: int, active_ref, s_ref, out_ref, y_ref, step):
  """What the one-pass kernels' bodies share: ``step`` on an active row's grid steps; on the others zeros for ``y``
  and the tile left alone."""
  import jax.experimental.pallas as pl

  active = active_ref[pl.program_id(0)] != 0
  first = pl.program_id(0) == 0
  for axis in range(1, grid_rank):
    first &= pl.program_id(axis) == 0
  pl.when(active)(step)

  @pl.when(jnp.logical_not(active))
  def _():
    y_ref[0] = jnp.zeros_like(y_ref[0])

  @pl.when(jnp.logical_not(active) & first)  # rows before the first active one stand on ITS first tile, or, where none is active, on tile (0, 0): until its own step (if any) it goes back as it came
  def _():
    out_ref[0, 0] = s_ref[0, 0]


def _state_step_kernel(layer_ref, active_ref, stand_ref, a_ref, dtx_ref, b_ref, c_ref, s_ref, out_ref, y_ref, per_head: bool = False):
  del layer_ref, stand_ref  # the index maps read them
  spread = (lambda ref: ref[0][:, None, :]) if per_head else (lambda ref: ref[0][None])  # B, C [Hb, N] a head, as the decay lies, or [1, N] every head's

  def step():
    # (the decay lies along the lanes and spreads over sublanes; Δ·x [Hb, P] goes lanes → sublanes, then along the lanes)
    new = a_ref[0][:, None, :] * s_ref[0, 0] + dtx_ref[0][:, :, None] * spread(b_ref)
    y_ref[0] = jnp.sum(new * spread(c_ref), axis=-1)
    out_ref[0, 0] = new

  _standing_step(2, active_ref, s_ref, out_ref, y_ref, step)


def _state_step_one_pass(ssm_leaf, layer, a, dtx, bm, cm, active, interpret: bool):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  _, B, H, P, N = ssm_leaf.shape
  hb = _head_block(H, P, N)
  stand, tile_at = _standing_tiles(active, H // hb)
  tile = pl.BlockSpec((1, 1, hb, P, N), tile_at)
  per_head = lambda width: pl.BlockSpec((1, hb, width), lambda b, h, *_: (b, h, 0))
  per_row = pl.BlockSpec((1, 1, N), lambda b, h, *_: (b, 0, 0))
  # The decay goes in spread along the lanes, [B, H, N]: as [B, H, 1] it is lane-padded in HBM and XLA relays it in a
  # copy of its own before every call (12 µs a layer); as [B, H, P] it needs a second lanes → sublanes relayout in
  # the body, which no longer hides under the tile's round trip (437 µs a layer for 423; PERF.md §6, PR 35).
  a = jnp.broadcast_to(a[:, :, None], (B, H, N))
  grouped = bm.ndim == 3  # B and C a head, [B, H, N]: blocked as the decay is; else [B, 1, N], one block a row
  bc_block, (bm, cm) = (per_head(N), (bm, cm)) if grouped else (per_row, (bm[:, None, :], cm[:, None, :]))
  return pl.pallas_call(
    partial(_state_step_kernel, per_head=True) if grouped else _state_step_kernel,
    out_shape=[jax.ShapeDtypeStruct(ssm_leaf.shape, ssm_leaf.dtype), jax.ShapeDtypeStruct((B, H, P), jnp.float32)],
    grid_spec=pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3, grid=(B, H // hb), in_specs=[per_head(N), per_head(P), bc_block, bc_block, tile], out_specs=[tile, per_head(P)]
    ),
    input_output_aliases={7: 0},  # the leaf, after the three scalar-prefetch operands and a, dtx, bm, cm
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),  # in order: a standing step counts on the step before it
    interpret=interpret,
    name="ssm_state_step",  # neither the attention kernel's name nor the flash kernel's: the roofline readers count calls by those
  )(jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32), stand, a, dtx, bm, cm, ssm_leaf)


def _onto_sublanes(v):
  """v [hb, pb] along the lanes → [hb, pb, 1] along the sublanes, where a value row of the state lies: each group of
  128 lanes spread over as many sublanes and summed through the diagonal's mask (one term a sum: exact). A plain
  ``v[:, :, None]`` is Mosaic's general relayout, which did not hide under the tile's round trip (PERF.md §6, PR 45)."""
  parts = []
  for at in range(0, v.shape[1], LANES):
    w = min(LANES, v.shape[1] - at)
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0) == jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    parts.append(jnp.sum(jnp.where(diagonal[None], jax.lax.slice_in_dim(v, at, at + w, axis=1)[:, None, :], 0.0), axis=-1, keepdims=True))
  return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _delta_step_kernel(layer_ref, active_ref, stand_ref, a_ref, b_ref, k_ref, q_ref, v_ref, s_ref, out_ref, y_ref):
  del layer_ref, stand_ref  # the index maps read them

  def step():
    # The rule as the module's head states it, every product and sum float32 on the vector unit. The key axis lies
    # along the lanes: α, k, q [hb, N] spread over a head's value rows (sublanes). The prediction S·(αk) comes out one
    # a value row (keepdims), which is where u meets the state; y = S_new q is the sum the Mamba kernel takes, whose
    # result lies along the lanes as y_ref does (the reference's y = S·(αq) + u (k·q) is the same number by another
    # order — it spares XLA a read of the new state, which here is in VMEM; PERF.md §6, PR 45).
    s0, a, k, q = s_ref[0, 0], a_ref[0, 0], k_ref[0, 0], q_ref[0, 0]
    sk = jnp.sum(s0 * (a * k)[:, None, :], axis=-1, keepdims=True)  # [hb, pb, 1]
    u = b_ref[0, 0, :, :1][:, :, None] * (_onto_sublanes(v_ref[0, 0]) - sk)
    new = s0 * a[:, None, :] + u * k[:, None, :]
    y_ref[0, 0] = jnp.sum(new * q[:, None, :], axis=-1)
    out_ref[0, 0] = new

  _standing_step(3, active_ref, s_ref, out_ref, y_ref, step)


def _delta_step_one_pass(ssm_leaf, layer, alpha, beta, k, v, q, active, interpret: bool):
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  _, B, H, P, N = ssm_leaf.shape
  hb, pb = _delta_tile(H, P, N)
  nh, npb = H // hb, P // pb
  stand, tile_at = _standing_tiles(active, nh, npb)
  tile = pl.BlockSpec((1, 1, hb, pb, N), tile_at)
  # The per-head operands go in as [B, H/hb, hb, .] so that a block is whole in its two minor axes whatever hb is (30
  # heads have no block of whole sublane groups), and β spread along the lanes as α is: [B, H, 1] would be lane-padded
  # in HBM and relaid by XLA before every call (see ``_state_step_one_pass``).
  blocked = lambda t: t.reshape(B, nh, hb, t.shape[-1])  # noqa: E731
  per_key = pl.BlockSpec((1, 1, hb, N), lambda b, h, p, *_: (b, h, 0, 0))
  per_value = pl.BlockSpec((1, 1, hb, pb), lambda b, h, p, *_: (b, h, 0, p))
  ssm_leaf, y = pl.pallas_call(
    _delta_step_kernel,
    out_shape=[jax.ShapeDtypeStruct(ssm_leaf.shape, ssm_leaf.dtype), jax.ShapeDtypeStruct((B, nh, hb, P), jnp.float32)],
    grid_spec=pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3, grid=(B, nh, npb), in_specs=[per_key, per_key, per_key, per_key, per_value, tile], out_specs=[tile, per_value]
    ),
    input_output_aliases={8: 0},  # the leaf, after the three scalar-prefetch operands and α, β, k, q, v
    compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),  # in order, as above
    interpret=interpret,
    name="delta_state_step",
  )(
    jnp.asarray(layer, jnp.int32).reshape(1), active.astype(jnp.int32), stand,
    blocked(alpha), blocked(jnp.broadcast_to(beta[:, :, None], (B, H, N))), blocked(k), blocked(q), blocked(v), ssm_leaf,
  )  # fmt: skip
  return ssm_leaf, y.reshape(B, H, P)
