"""Mixture-of-Experts routed FFN: the router, and the one owner of the form the experts' product takes.

The reference *registers* MoE models (deepseek-v3/r1/coder-v2-lite,
``models.py:69-70``) but its dense-only layer builder cannot load them
(SURVEY.md §2.11: "registry entries ≠ working support",
``general_mha.py:77-120``). This module is the TPU-native delivery of that
promise.

- **top-k routing** (``router_topk``) with either softmax scoring
  (mixtral/qwen2-moe/deepseek-v2) or sigmoid scoring with a selection-only
  correction bias (deepseek-v3), optionally group-limited (deepseek's
  device-limited routing: v2 ``group_limited_greedy``, v3 ``noaux_tc``).
  ``held`` tells a layer which experts [lo, hi) of the router's its leaves
  hold: a choice outside them adds nothing.

The routed experts' product has two forms, and ``ffn_form`` is the one place
that says which a program takes, from what the program can see — whether
anything may drop (``capacity_factor``), whether it may use Mosaic kernels
(``cfg.mosaic_kernels``, on a TPU), the leaves' dtype and whether their faces
tile. A layer loop over a cache or a pool (models/decoder.py) asks it of its
stacked expert leaves; where the answer is "grouped" it hands ``moe_ffn`` the
stack whole with the layer to take, and that is how ``moe_ffn`` knows: one
decision, made once a program. Every other caller — the cache-less forward
(which training differentiates: the kernels have no derivative), the ``--pp`` /
``--sp`` rings' own layer loops — hands a layer's leaves and gets the block form:

- the **grouped** form (``_moe_ffn_grouped``): the T·k assignments are sorted
  by expert, their token rows gathered once (activations only), and two Mosaic
  kernels after the design of megablox's ``gmm`` (gate and up with the SwiGLU
  between them in one, down in the other) walk the sorted rows a row tile at a
  time. For every visit its expert and its row tile are scalar-prefetch
  operands; visits are ordered by expert, so an expert whose group is empty is
  never read and rows behind the last held group cost nothing (the grid's
  length is the number of visits, a traced scalar). The kernels take the
  STACKED leaves [L, E, D, F] and the layer as a scalar: a layer cut out of the
  stack ahead of a custom call would be a copy of the layer's experts every
  step. int8 leaves go in as codes, are cast in VMEM and the product's rows
  multiplied by their expert's scale row. The rows go back to their tokens by
  the inverse permutation and are summed with the router's weights in float32;
  an assignment to an expert this shard does not hold weighs exactly 0 (by
  ``where``, never a product with a zero).

  **The rows are walked in one of two ways, and ``grouped_walk`` is the one
  place that says which**, for every call site from its static shapes alone —
  rows an expert, T·k over the router's width, and what fits VMEM — (PERF.md
  §5, PR 56: the kernel-alone table that set its thresholds). Both give every
  row the same bits: a row's dot products do not depend on where its tile
  starts, and the k terms are the same float32 values added in the same order.

  - the **shared** walk (a decode step, a short group: a few rows an expert;
    Mosaic calls ``moe_gate_up`` / ``moe_up`` / ``moe_down``): the sorted rows
    lie with no gap in tiles of ``ROW_TILE``, a tile that holds several
    experts' rows is visited once for each, every visit multiplies the whole
    tile and keeps its own rows over what the output block held
    (``_own_rows``); rows of experts not held sort behind the held groups,
    and a ``where`` over the products takes them and the last tile's padding
    out ahead of the gather back.
  - the **aligned** walk (a prompt's slice, a prefill group: many rows an
    expert; ``moe_gate_up_rows`` / ``moe_up_rows`` / ``moe_down_rows``): every
    held expert's group starts at a multiple of the tile's height, in row
    buffers ``E_held`` tiles longer, so a visit's tile is ONE expert's —
    Σ ⌈rows_e / tile⌉ visits, each stored whole with no look at the output
    block; rows past a group's end are computed and never read. The tile is
    the one of ``ALIGNED_TILES`` that a group of the usual length costs
    least (192 rows an expert: one visit of 224 rows, whose product is as
    long as the next expert's fetch). A choice of an expert not held has no
    row at all, and the ``where`` that takes it out stands on the rows a
    token gathers — a whole tile of sublanes a token, [T, 8, D], the
    gather's own layout — inside the weighted sum's fusion: the products
    are read once, and no pass relays them.
- the **block** form (``_moe_ffn_block``), the reference: GShard-style dense
  [T, E, C] dispatch/combine one-hots and three ``[E, C, D] x [E, D, F]``
  einsums over EVERY held expert. Position-in-expert is a cumulative-sum
  rank; position ≥ capacity ⇒ the token drops that expert (its combine weight
  is zero); ``capacity_factor=None`` means capacity = T, nothing drops. It is
  what capacity-factor routing, ``ep``-sharded GSPMD plans (the expert axis
  shards over ``ep``, parallel/mesh.py, and GSPMD places the all-to-alls),
  differentiated programs and every backend off the TPU take, in blocks of
  ``chunk`` tokens so the one-hots stay O(chunk²·E). Its leaves are one layer's,
  in the activations' dtype: the caller dequantises codes beside the call (XLA
  fuses that into the einsum's read).

Both take bf16 operands, accumulate in float32, apply the gate's nonlinearity
(``EXPERT_ACTS``: silu, relu for ReGLU experts, relu² — a static choice, from
the configuration) in float32 and combine in float32.

**An expert may have no gate** (``w_gate`` None; nemotron_h): two matrices,
``W_down act(W_up x)``, and BOTH stored [.., F, D] — the first as HF stores it,
out-major (leaf ``w_experts_up_t``): an inner width that is no whole number of
lanes (nemotron_h's 1856 = 29 x 64) then never lies along the lanes, where
XLA:TPU would store the stack column-major and copy it whole for a Mosaic call
(5.3 GB at those widths; AOT, PERF.md §6, PR 53). The block form then has two
einsums, and the grouped form's first kernel is up-only (``moe_up``: one whole
[F, D] block a visit against the rows' transposed contraction, the
nonlinearity in it), then the same ``moe_down``: a third fewer expert bytes a
visit.

**The routing is an operand of its own** (``route`` → ``Routed``): by default
``moe_ffn`` draws it from the tokens it computes from, inside ``_moe_ffn_*``;
a model whose router reads another tensor (smallthinker's reads the normed
input of the layer's ATTENTION) has its layer step call ``route`` there, ahead
of the attention, and hand ``moe_ffn`` the result (``routed``) with the
experts' own input. ``router_topk`` stays the one place a choice is drawn, under
``xot.moe_router`` wherever ``route`` is called from. Both return, beside the result, the router's auxiliary
loss and the number of distinct held experts the rows chose (what the grouped
form visits): the counters ``moe_experts_visited_total`` /
``moe_expert_layer_steps_total`` are fed from it.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


def router_topk(
  logits: jnp.ndarray,  # [T, E] fp32 router logits
  k: int,
  scoring: str = "softmax",  # "softmax" | "sigmoid"
  norm_topk: bool = False,
  selection_bias: jnp.ndarray | None = None,  # [E] added for *selection only* (deepseek-v3)
  scale: float = 1.0,
  n_group: int = 1,
  topk_group: int = 1,
  group_mode: str = "none",  # "none" | "max" (deepseek-v2) | "top2sum" (deepseek-v3)
) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Select top-k experts per token. Returns (weights [T,k] fp32, idx [T,k] int32).

  Combine weights are always the *unbiased* scores gathered at the selected
  experts; ``selection_bias`` (deepseek-v3's e_score_correction_bias) only
  reorders the top-k choice. With ``group_mode`` ≠ "none" experts are split
  into ``n_group`` groups and only the top ``topk_group`` groups (by max or
  top-2-sum of member scores) are eligible — deepseek's device-limited
  routing, which bounds how many EP shards a token can touch.
  """
  logits = logits.astype(jnp.float32)
  if scoring == "sigmoid":
    scores = jax.nn.sigmoid(logits)
  else:
    scores = jax.nn.softmax(logits, axis=-1)
  sel = scores if selection_bias is None else scores + selection_bias.astype(jnp.float32)
  if group_mode != "none" and n_group > 1:
    T, E = sel.shape
    grouped = sel.reshape(T, n_group, E // n_group)
    if group_mode == "top2sum":
      group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    else:
      group_scores = jnp.max(grouped, axis=-1)
    _, gidx = jax.lax.top_k(group_scores, topk_group)  # [T, topk_group]
    gmask = jnp.sum(jax.nn.one_hot(gidx, n_group, dtype=jnp.float32), axis=1)  # [T, n_group]
    sel = jnp.where(jnp.repeat(gmask > 0, E // n_group, axis=-1), sel, 0.0)
  _, idx = jax.lax.top_k(sel, k)
  weights = jnp.take_along_axis(scores, idx, axis=-1)
  if norm_topk:
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
  return weights * scale, idx.astype(jnp.int32)


def expert_capacity(n_tokens: int, k: int, n_experts: int, capacity_factor: float | None) -> int:
  """Tokens each expert can hold. None ⇒ exact (capacity = T, no drops)."""
  if capacity_factor is None:
    return n_tokens
  return min(n_tokens, max(1, math.ceil(n_tokens * k / n_experts * capacity_factor)))


def dispatch_combine_masks(idx: jnp.ndarray, weights: jnp.ndarray, n_experts: int, capacity: int):
  """Build dispatch [T,E,C] (0/1) and combine [T,E,C] (weighted) tensors.

  Position-in-expert is the token's rank (token-major, slot-minor) among all
  assignments to that expert; rank ≥ capacity drops the assignment.
  """
  T, k = idx.shape
  onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # [T, k, E]
  flat = onehot.transpose(1, 0, 2).reshape(k * T, n_experts)  # slot-major blocks of token-major rows
  ranks = jnp.cumsum(flat, axis=0) - flat  # rank of each assignment within its expert
  ranks = ranks.reshape(k, T, n_experts).transpose(1, 0, 2)  # [T, k, E]
  pos = jnp.sum(ranks * onehot, axis=-1)  # [T, k] position inside the chosen expert
  keep = (pos < capacity).astype(jnp.float32)
  pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32) * keep[..., None]  # [T,k,C]
  dispatch = jnp.einsum("tke,tkc->tec", onehot, pos_oh)
  combine = jnp.einsum("tke,tkc,tk->tec", onehot, pos_oh, weights.astype(jnp.float32))
  return dispatch, combine


def _held_index(idx, held):
  """Routed expert ids → ids into the expert leaves of a shard that holds experts ``held`` = [lo, hi) of the router's
  (None: all of them, ids as they are). A choice outside the range gives an id outside the leaves': its one-hot is all
  zeros, so it is neither dispatched nor combined, and nothing stands in for what the absent expert would have added."""
  return idx if held is None else idx - held[0]


class Routed(NamedTuple):
  """A layer's routing of T tokens, drawn by ``route``: the router's float32 logits [T, E] (the auxiliary loss reads
  them), the k combine weights [T, k] float32 and the chosen experts [T, k] int32."""

  logits: jnp.ndarray
  weights: jnp.ndarray
  idx: jnp.ndarray


def route(x, w_router, k, scoring="softmax", norm_topk=False, selection_bias=None, scale=1.0, n_group=1, topk_group=1, group_mode="none") -> Routed:
  """The routing of tokens ``x`` [T, D] by ``w_router`` [D, E], under ``xot.moe_router`` wherever it is called from:
  inside the two forms (the router reads the experts' input), or by a layer step ahead of its attention, whose normed
  input ``x`` then is (``moe_ffn``'s ``routed``)."""
  with jax.named_scope("xot.moe_router"):
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    return Routed(logits, *router_topk(logits, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode))


# The experts' nonlinearity, W_down(act(W_gate y) * W_up y) or, without a gate, W_down act(W_up y): the one owner of the
# choice, for both forms (the Mosaic bodies spell the same in ``_act_in_kernel``). Applied in float32.
EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu, "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _moe_ffn_block(x, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, capacity_factor, n_group, topk_group, group_mode, held=None, act="silu", routed=None):
  """One dispatch/compute/combine block over [T, D] tokens. Returns (out, aux, visited)."""
  T, D = x.shape
  E, E_held = w_router.shape[-1], w_down.shape[0]
  logits, weights, idx = routed or route(x, w_router, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode)
  with jax.named_scope("xot.moe_router"):
    C = expert_capacity(T, k, E, capacity_factor)
    dispatch, combine = dispatch_combine_masks(_held_index(idx, held), weights, E_held, C)
    visited = jnp.sum(jnp.any(dispatch > 0, axis=(0, 2)), dtype=jnp.int32)  # (an expert's first assignment has rank 0 and is never dropped)

  with jax.named_scope("xot.moe_experts"):
    xin = jnp.einsum("td,tec->ecd", x, dispatch.astype(x.dtype))  # [E, C, D]
    if w_gate is None:  # an expert of two matrices, W_down act(W_up x), the first stored [E, F, D] as the second is
      hidden = EXPERT_ACTS[act](jnp.einsum("ecd,efd->ecf", xin, w_up).astype(jnp.float32)).astype(x.dtype)
    else:
      gated = EXPERT_ACTS[act](jnp.einsum("ecd,edf->ecf", xin, w_gate).astype(jnp.float32)).astype(x.dtype)
      up = jnp.einsum("ecd,edf->ecf", xin, w_up)
      hidden = gated * up
    out = jnp.einsum("ecf,efd->ecd", hidden, w_down)  # [E, C, D]
    out = jnp.einsum("ecd,tec->td", out.astype(jnp.float32), combine).astype(x.dtype)
  with jax.named_scope("xot.moe_router"):
    aux = load_balancing_loss(logits, idx, E)
  return out, aux, visited


# ----------------------------------------------------------------- the grouped form
LANES = 128
ROW_TILE = 128  # sorted assignment rows one visit multiplies: the MXU's height; a decode step of 64 rows x 8 is four of them
_BLOCK_BYTES = 4 << 20  # of one weight block [K, tn] as stored: gate and up, double-buffered, are four of them in VMEM
_VMEM_LIMIT = 64 << 20  # v5e has 128 MiB; the default scoped limit (16 MiB) holds no whole expert
# Tokens one grouped pass takes: the [T·k, D] gathered rows and the float32 [T·k, D] products are temporaries of the
# program, ~0.2 MB a token at Ling's widths, beside a pool and weights that fill the chip. A longer run is cut by
# tokens (a Python loop: a ``lax.map`` would make the layer's expert leaves operands of a loop and copy them).
GROUPED_MAX_TOKENS = 4096
_INNER_GROUP = 16  # what an ungated expert's inner width F is a whole number of: the sublanes of a packed bfloat16 tile (F lies along them in both of its matrices)
FFN_FORMS = ("grouped", "block")  # what ``ffn_form`` answers
WALKS = ("aligned", "shared")  # what ``grouped_walk`` answers: the label of the gauge ``moe_grouped_walk``
# Rows an expert (T·k over the router's width) from which a run takes the aligned walk, the heights its tiles may have
# and what a visit costs beside its rows, in rows (an expert's first visit waits for its weights): PERF.md §5, the
# kernel-alone tables of PR 56 (scripts/moe_grouped_bench.py).
ALIGNED_MIN_ROWS = 64
ALIGNED_TILES = (96, 128, 160, 192, 224, 256)
VISIT_ROWS = 64
SUBLANES = 8  # of a float32 tile: the k products a token gathers lie along them
INTERPRET = False  # the tests' switch: a CPU takes the grouped form too, its kernels interpreted


def _on_tpu() -> bool:
  return jax.default_backend() == "tpu"


def _col_tile(K: int, N: int, itemsize: int) -> int | None:
  """Columns of one weight block [K, tn]: all N if that fits ``_BLOCK_BYTES``, else the most whole lane groups that
  divide N and fit; None where not even one lane group of K rows fits."""
  if K * N * itemsize <= _BLOCK_BYTES:
    return N
  fits = [tn for tn in range(LANES, N, LANES) if N % tn == 0 and K * tn * itemsize <= _BLOCK_BYTES]
  return max(fits, default=None)


def ffn_form(w_gate, w_down, capacity_factor, mosaic_kernels: bool, scaled: bool = False, gated: bool = True) -> str:
  """The form the routed experts' product takes for these expert leaves ([..., E, D, F] the gate's and [..., E, F, D],
  arrays or ShapeDtypeStructs; ``gated`` false: an expert without a gate, whose first matrix is stored [..., E, F, D]
  as its second is) — the label of the gauge ``moe_ffn_form``. "grouped" where nothing may drop, the program may run
  Mosaic kernels (``cfg.mosaic_kernels``, which the engine clears for a plan that leaves a mesh axis to GSPMD — a
  Mosaic call cannot be partitioned automatically — and a TPU), the leaves are bfloat16 / float32, or int8 codes with
  per-output-channel scales (``scaled``; a packed int4 leaf has half the rows and is refused), and both faces are whole
  lane groups a block of which fits VMEM. Anything else: "block"."""
  if not mosaic_kernels or not (_on_tpu() or INTERPRET) or capacity_factor is not None:
    return "block"
  (D, F), down = w_gate.shape[-2:] if gated else w_gate.shape[-2:][::-1], w_down.shape[-2:]
  if down != (F, D) or w_gate.dtype != w_down.dtype or w_gate.dtype not in ((jnp.int8,) if scaled else (jnp.bfloat16, jnp.float32)):
    return "block"
  size = jnp.dtype(w_gate.dtype).itemsize
  if gated:
    tiles = D % LANES == 0 and F % LANES == 0 and _col_tile(D, F, size) is not None and _col_tile(F, D, size) is not None
  else:
    # The first product takes an expert's [F, D] matrix as ONE block, double-buffered, in half the VMEM limit (an output
    # block [rows, part of F] would have to be whole lanes, and F need not be: 1856 = 29 x 64): 9.98 MB x 2 of 64 at
    # nemotron_h's widths. F is the second product's contraction axis and lies along sublanes in both matrices.
    tiles = not scaled and D % LANES == 0 and F % _INNER_GROUP == 0 and 2 * F * D * size <= _VMEM_LIMIT // 2 and _col_tile(F, D, size) is not None
  return "grouped" if tiles else "block"


def grouped_walk(rows: int, E: int, E_held: int, blocks: tuple = (), itemsize: int = 2) -> tuple[str, int]:
  """How the grouped form walks ``rows`` = T·k sorted assignments, from static shapes alone: (one of ``WALKS``, the row
  tile's height). ``E`` is the router's width and ``E_held`` the experts this shard holds: rows an expert are
  ``rows / E`` whatever share of them is held. ``blocks``: for each of the two products the (K, tn, count) of the
  weight blocks [K, tn] a visit multiplies its rows by — a tile taller than ``ROW_TILE`` is taken only where its rows,
  blocks and float32 products fit ``_VMEM_LIMIT`` —, ``itemsize`` the leaves'.

  - **shared** (a decode step, a short group: a few rows an expert): the rows lie sorted with no gap, a row tile of
    ``ROW_TILE`` may hold several experts' rows and is visited once for each, every visit keeping its own rows.
  - **aligned** (a prompt's slice, a prefill group: from ``ALIGNED_MIN_ROWS`` rows an expert — from half as many
    where the shard holds a quarter of the router's experts: three in four choices then get no row at all, where the
    shared walk carries every one): every held expert's group starts at a multiple of the tile's height, so a visit's
    tile is ONE expert's and is stored whole; the row buffers are ``E_held`` tiles longer. The tile is the one of
    ``ALIGNED_TILES`` that costs a group of the usual length (the mean and 1.2 standard deviations of a uniform
    router's) least, a visit counted as its rows and ``VISIT_ROWS`` more: 192 rows an expert walk ONE tile of 224, not
    two of 128 — the next expert's weights then arrive behind a product as long as their fetch —, 64 one of 96, 384
    two of 224; where its blocks fit VMEM."""
  mean = rows / E
  if mean < ALIGNED_MIN_ROWS * math.sqrt(E_held / E):
    return "shared", ROW_TILE if rows >= ROW_TILE else -(-rows // 16) * 16  # (a bfloat16 tile is 16 sublanes)
  fits = [tm for tm in ALIGNED_TILES if tm <= ROW_TILE or all(_tile_fits(tm, K, tn, count, itemsize) for K, tn, count in blocks)]
  group = mean + 1.2 * math.sqrt(mean)  # most groups are no longer
  return "aligned", min(fits, key=lambda tm: (math.ceil(group / tm) * (tm + VISIT_ROWS), -tm))


def _tile_fits(tm: int, K: int, tn: int, count: int, itemsize: int) -> bool:
  """Whether a visit of ``tm`` rows fits three quarters of ``_VMEM_LIMIT``: its rows [tm, K] and its output block
  [tm, tn] (float32 at most) and its ``count`` weight blocks [K, tn], each double-buffered, and a float32 product a
  weight block."""
  return 2 * tm * K * 4 + 2 * count * K * tn * itemsize + (2 + count) * tm * tn * 4 <= _VMEM_LIMIT * 3 // 4


_walk_sites = dict.fromkeys(WALKS, 0)


def note_walk(walk: str | None = None) -> None:
  """The gauge ``moe_grouped_walk{walk}``: call sites of the grouped form traced on each walk since the process
  started (a program's prefill half and its decode half are sites of their own). ``walk`` None: publish the counts as
  they stand (a scheduler, when its pool is made)."""
  from ..utils.metrics import metrics

  if walk is not None:
    _walk_sites[walk] += 1
  for name, sites in _walk_sites.items():
    metrics.set_gauge("moe_grouped_walk", sites, labels={"walk": name})


def _visits(sizes, m: int, tm: int):
  """The walk over sorted rows: for ``sizes`` [E] rows of each expert in turn from row 0 and row tiles of ``tm``,
  (offsets [E+1], expert of each visit [V], row tile of each visit [V], the number of visits). A visit is one
  (expert, row tile) pair with a row in common; they are ordered by expert, so those of one row tile follow each other
  and an expert with no row has none. V = m/tm + E - 1 is the most there can be; entries past the number of visits
  repeat the last real one."""
  E, tiles = sizes.shape[0], m // tm
  ends = jnp.cumsum(sizes)
  starts = ends - sizes
  of_group = jnp.where(sizes > 0, (ends + tm - 1) // tm - starts // tm, 0)
  first = jnp.cumsum(of_group) - of_group
  n = jnp.sum(of_group)
  at = jnp.minimum(jnp.arange(tiles + E - 1, dtype=jnp.int32), jnp.maximum(n - 1, 0))
  group = jnp.clip(jnp.searchsorted(first + of_group, at, side="right", method="compare_all"), 0, E - 1).astype(jnp.int32)
  tile = jnp.clip(starts[group] // tm + at - first[group], 0, tiles - 1).astype(jnp.int32)
  return jnp.concatenate([jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)]), group, tile, n.astype(jnp.int32)


def _own_rows(offsets_ref, group_ref, tile_ref, tm: int):
  """[tm, 1] mask: the rows of this visit's row tile that belong to this visit's expert."""
  import jax.experimental.pallas as pl

  v = pl.program_id(1)
  g = group_ref[v]
  rows = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
  return (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])


def _keep(out_ref, product, walk_refs, tm: int, aligned: bool):
  """What a visit stores of its float32 ``product`` [tm, tn]. On the aligned walk the tile is this expert's alone: all
  of it, with no look at the block (rows past the group's end are computed and never read). On the shared walk: its
  own rows, over what the block held."""
  if not aligned:
    product = jnp.where(_own_rows(*walk_refs, tm), product, out_ref[...].astype(jnp.float32))
  out_ref[...] = product.astype(out_ref.dtype)


def _act_in_kernel(x, act: str):
  """``EXPERT_ACTS`` as a Mosaic body spells them, on float32."""
  if act == "relu":
    return jnp.maximum(x, 0.0)
  if act == "relu2":
    return jnp.square(jnp.maximum(x, 0.0))
  return x * jax.nn.sigmoid(x)


def _gate_up_kernel(layer_ref, offsets_ref, group_ref, tile_ref, x_ref, wg_ref, wu_ref, *rest, tm: int, scaled: bool, aligned: bool, act: str = "silu"):
  del layer_ref  # the index maps read it
  (sg_ref, su_ref, out_ref) = rest if scaled else (None, None, *rest)
  x = x_ref[...]
  gate = jnp.dot(x, wg_ref[...].astype(x.dtype), preferred_element_type=jnp.float32)
  up = jnp.dot(x, wu_ref[...].astype(x.dtype), preferred_element_type=jnp.float32)
  if scaled:
    gate, up = gate * sg_ref[...], up * su_ref[...]
  _keep(out_ref, _act_in_kernel(gate, act) * up, (offsets_ref, group_ref, tile_ref), tm, aligned)


def _up_kernel(layer_ref, offsets_ref, group_ref, tile_ref, x_ref, wu_ref, out_ref, *, tm: int, scaled: bool, aligned: bool, act: str):
  """The ungated expert's first product with its nonlinearity: act(x W_upᵀ), the matrix [F, D] as stored."""
  del layer_ref, scaled  # (``ffn_form`` admits no codes here)
  x = x_ref[...]
  up = jax.lax.dot_general(x, wu_ref[...].astype(x.dtype), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
  _keep(out_ref, _act_in_kernel(up, act), (offsets_ref, group_ref, tile_ref), tm, aligned)


def _down_kernel(layer_ref, offsets_ref, group_ref, tile_ref, h_ref, wd_ref, *rest, tm: int, scaled: bool, aligned: bool):
  del layer_ref
  (sd_ref, out_ref) = rest if scaled else (None, *rest)
  h = h_ref[...]
  y = jnp.dot(h, wd_ref[...].astype(h.dtype), preferred_element_type=jnp.float32)
  if scaled:
    y = y * sd_ref[...]
  _keep(out_ref, y, (offsets_ref, group_ref, tile_ref), tm, aligned)


def _grouped_product(kernel, name: str, rows, weights, scales, layer, walk, tm: int, out_dtype, out_major: bool = False, aligned: bool = False):
  """``rows`` [M, K] against each visit's expert in the stacked ``weights`` ([L, E, K, N] each; the layer's ``scales``
  [E, 1, N] float32, or none) → [M, N]: rows no visit owns come back as the kernel found them. ``out_major``: the
  weights are stored [L, E, N, K] and a block is an expert's whole matrix. ``aligned``: the walk's tiles are one
  expert's each (``_keep``), and the call carries a name of its own, ``<name>_rows``."""
  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  offsets, group, tile, n_visits = walk
  (M, K), N = rows.shape, weights[0].shape[-2 if out_major else -1]
  tn = N if out_major else _col_tile(K, N, weights[0].dtype.itemsize) or N
  row_block = pl.BlockSpec((tm, K), lambda j, v, layer, offsets, group, tile: (tile[v], 0))
  weight_block = pl.BlockSpec((None, None, K, tn), lambda j, v, layer, offsets, group, tile: (layer[0], group[v], 0, j))
  if out_major:
    weight_block = pl.BlockSpec((None, None, N, K), lambda j, v, layer, offsets, group, tile: (layer[0], group[v], 0, 0))
  scale_block = pl.BlockSpec((None, 1, tn), lambda j, v, layer, offsets, group, tile: (group[v], 0, j))
  out_block = pl.BlockSpec((tm, tn), lambda j, v, layer, offsets, group, tile: (tile[v], j))
  return pl.pallas_call(
    partial(kernel, tm=tm, scaled=bool(scales), aligned=aligned),
    out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
    grid_spec=pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=4,
      grid=(N // tn, n_visits),  # column blocks outside: inside one, consecutive visits of a row tile keep its output block in VMEM
      in_specs=[row_block, *[weight_block] * len(weights), *[scale_block] * len(scales)],
      out_specs=out_block,
    ),
    compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
    interpret=INTERPRET,
    name=f"{name}_rows" if aligned else name,
  )(jnp.asarray(layer, jnp.int32).reshape(1), offsets, group, tile, rows, *weights, *scales)


def _sort_by_expert(expert, E_held: int, real: int):
  """``expert`` [M] (each row's held expert, ``E_held`` where none) sorted: (the sorted ids, the row each sorted place
  holds, the sorted place of each of the first ``real`` rows, the rows of each held expert [E_held])."""
  M = expert.shape[0]
  expert, order = jax.lax.sort_key_val(expert, jnp.arange(M, dtype=jnp.int32))
  place = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))[:real]
  sizes = jnp.sum(expert[:, None] == jnp.arange(E_held, dtype=jnp.int32)[None, :], axis=0, dtype=jnp.int32)
  return expert, order, place, sizes


def _shared_dispatch(expert, E_held: int, tm: int):
  """The shared walk's order of ``expert`` [M] (each assignment's held expert, ``E_held`` where it is not held): (the
  walk's tables, the assignment of every sorted row [Mp], each assignment's sorted row [M], the rows of each expert
  [E_held], the expert of every sorted row [Mp])."""
  M = expert.shape[0]
  Mp = -(-M // tm) * tm
  # (the rows that fill the last tile sort behind every held group, as an expert not held does)
  expert, order, place, sizes = _sort_by_expert(jnp.pad(expert, (0, Mp - M), constant_values=E_held), E_held, M)
  return _visits(sizes, Mp, tm), order, place, sizes, expert


def _aligned_dispatch(expert, E_held: int, tm: int):
  """The aligned walk's order of ``expert`` [M]: the sorted order with every held group moved up to the next multiple
  of ``tm``, in a buffer of ``M // tm + E_held`` tiles (the most Σ ⌈rows_e / tm⌉ can be). An assignment to an expert
  not held has no row. (the walk's tables — a visit is a tile —, the assignment of every row, each held assignment's
  row [M], the rows of each expert [E_held]). A row past its group's end names a neighbour's assignment: its products
  are finite, and nothing reads them."""
  M = expert.shape[0]
  tiles = M // tm + E_held
  _, order, place, sizes = _sort_by_expert(expert, E_held, M)
  of_group = (sizes + tm - 1) // tm
  last = jnp.cumsum(of_group)
  n = last[-1]
  shift = (last - of_group) * tm - (jnp.cumsum(sizes) - sizes)  # how far each group's rows moved up
  tile = jnp.arange(tiles, dtype=jnp.int32)
  group = jnp.clip(jnp.searchsorted(last, jnp.minimum(tile, jnp.maximum(n - 1, 0)), side="right", method="compare_all"), 0, E_held - 1).astype(jnp.int32)
  source = jnp.clip(jnp.arange(tiles * tm, dtype=jnp.int32) - jnp.repeat(shift[group], tm), 0, M - 1)  # the sorted place each row holds
  return (jnp.pad(last * tm, (1, 0)).astype(jnp.int32), group, tile, n.astype(jnp.int32)), order[source], place + jnp.pad(shift, (0, 1))[expert], sizes


def _moe_ffn_grouped(x, w_router, w_gate, w_up, w_down, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode, held=None, scales=None, layer=0, act="silu", routed=None):
  """The grouped form over [T, D] tokens (nothing can drop). Expert leaves stacked, [L, E, D, F] / [L, E, F, D]
  (``w_gate`` None: an expert without a gate, ``w_up`` stored [L, E, F, D] as ``w_down`` is), with ``layer`` a (traced)
  scalar; ``scales`` their per-output-channel scales ([L, E, F], [L, E, F], [L, E, D]) where the leaves are int8
  codes. Returns (out, aux, visited)."""
  T, D = x.shape
  E, E_held, M = w_router.shape[-1], w_down.shape[1], T * k
  F, gated, size = w_down.shape[-2], w_gate is not None, w_down.dtype.itemsize
  first = (D, _col_tile(D, F, size) or F, 2) if gated else (D, F, 1)
  walk, tm = grouped_walk(M, E, E_held, (first, (F, _col_tile(F, D, size) or D, 1)), size)
  aligned = walk == "aligned"
  note_walk(walk)
  logits, weights, idx = routed or route(x, w_router, k, scoring, norm_topk, selection_bias, scale, n_group, topk_group, group_mode)
  with jax.named_scope("xot.moe_experts"):  # the dispatch (the sort, the walk, the rows' gather), the products and the combine
    expert = _held_index(idx, held).reshape(M)
    expert = jnp.where((expert >= 0) & (expert < E_held), expert, E_held)  # an expert this shard does not hold sorts behind every held group
    if aligned:
      visits, order, back, sizes = _aligned_dispatch(expert, E_held, tm)
    else:
      visits, order, back, sizes, of_row = _shared_dispatch(expert, E_held, tm)
    visited = jnp.sum(sizes > 0, dtype=jnp.int32)
    rows = jnp.take(x, order // k, axis=0, mode="clip")  # each row's assignment's token, in the walk's order
    # (the layer's scales are cut out of their stack — kilobytes, where an expert leaf's layer is most of a GB — as
    # [E, 1, N]: a block is one expert's row)
    cut = tuple(jax.lax.dynamic_index_in_dim(s, layer, 0, keepdims=False).astype(jnp.float32)[:, None, :] for s in scales or ())
    if w_gate is None:  # an expert of two matrices, both stored [F, D]
      h = _grouped_product(partial(_up_kernel, act=act), "moe_up", rows, (w_up,), (), layer, visits, tm, x.dtype, out_major=True, aligned=aligned)
    else:
      h = _grouped_product(partial(_gate_up_kernel, act=act), "moe_gate_up", rows, (w_gate, w_up), cut[:2], layer, visits, tm, x.dtype, aligned=aligned)
    y = _grouped_product(_down_kernel, "moe_down", h, (w_down,), cut[-1:], layer, visits, tm, jnp.float32, aligned=aligned)
    # What no held expert owns — a choice of an expert not held, and on the shared walk the rows that pad the last
    # tile — holds whatever the kernels found there: it is taken out by ``where``, never multiplied by a zero. The
    # shared walk passes over every sorted row for that, gathers the assignments' rows [T·k, D] and relays them to
    # [T, k, D] — k on the sublanes, where k is no whole tile of them: a copy — for the sum. The aligned walk has no
    # row for such a choice (``back`` names a row of another's, or one nobody wrote), so its ``where`` stands on what
    # the tokens gather, inside the weighted sum's fusion, and it gathers a whole tile of sublanes a token (the slots
    # past k weigh exactly 0 too): [T, slots, D] is the gather's own layout, no copy, and the sum over the sublanes is
    # the shared walk's — the same float32 terms in the same tree, and zeros.
    w = weights.astype(jnp.float32)
    if aligned:
      slots = -(-k // SUBLANES) * SUBLANES
      pad = lambda t: jnp.pad(t.reshape(T, k), ((0, 0), (0, slots - k)))  # noqa: E731
      picked = jnp.take(y, pad(back).reshape(T * slots), axis=0, mode="clip").reshape(T, slots, D)
      out = jnp.sum(jnp.where(pad(expert < E_held)[:, :, None], picked, 0.0) * pad(w)[:, :, None], axis=1).astype(x.dtype)
    else:
      y = jnp.where((of_row < E_held)[:, None], y, 0.0)
      out = jnp.sum(jnp.take(y, back, axis=0).reshape(T, k, D) * w[:, :, None], axis=1).astype(x.dtype)
  with jax.named_scope("xot.moe_router"):
    aux = load_balancing_loss(logits, idx, E)
  return out, aux, visited


def moe_ffn(
  x: jnp.ndarray,  # [T, D] tokens (flattened batch*seq)
  w_router: jnp.ndarray,  # [D, E]
  w_gate: jnp.ndarray | None,  # [E, D, F] per-expert gate proj ([L, E, D, F] with ``layer``); None: an expert of two matrices, W_down act(W_up x)
  w_up: jnp.ndarray,  # [E, D, F]; [E, F, D] where ``w_gate`` is None
  w_down: jnp.ndarray,  # [E, F, D]
  k: int,
  scoring: str = "softmax",
  norm_topk: bool = False,
  selection_bias: jnp.ndarray | None = None,
  scale: float = 1.0,
  capacity_factor: float | None = None,
  chunk: int = 256,
  n_group: int = 1,
  topk_group: int = 1,
  group_mode: str = "none",
  held: tuple[int, int] | None = None,
  scales: tuple | None = None,
  layer=None,
  act: str = "silu",
  routed: Routed | None = None,
):
  """Routed FFN — gated (``act``: one of ``EXPERT_ACTS``; silu is SwiGLU), or two matrices an expert where ``w_gate`` is None — over ``E`` experts; returns ([T, D] in x.dtype, the router's auxiliary loss, the number of
  distinct held experts the rows chose: int32, summed over the blocks or pieces of a long run).

  ``held`` = (lo, hi): this shard's share of an expert-parallel layer. The
  router (``w_router`` [D, E], its bias, the groups, the top-k and the
  weights' normalisation over all k chosen) stays ``E`` wide; ``w_gate`` /
  ``w_up`` / ``w_down`` hold experts [lo, hi) only, and the result is their
  part of the layer's sum: what the absent experts would have added is left
  out (the shares of all the shards add up to the whole layer).

  ``layer``: the grouped form. The expert leaves are a stack's, [L, E, ...], handed over whole by a layer loop that
  asked ``ffn_form`` of them, and this is the layer to take (a traced scalar: the kernels index the stack, so that no
  layer is cut out of it); ``scales`` their scale leaves (gate, up, down) where they are int8 codes. A long run goes
  in pieces of ``GROUPED_MAX_TOKENS``.

  ``routed``: the tokens' routing where the layer step drew it elsewhere (``route``, from another tensor than ``x``:
  the model's router reads its attention's input); None: drawn here from ``x``. A long run cuts it as it cuts ``x``.

  Without ``layer``: the block form over one layer's leaves, long runs in sequential blocks of ``chunk`` tokens so the
  dispatch/combine one-hots stay O(chunk²·E) instead of O(T²·E) — routing is per-token, so cutting is exact (with
  ``capacity_factor=None``, capacity per block = chunk, nothing ever drops).
  """
  T, D = x.shape
  routing = (k, scoring, norm_topk, selection_bias, scale)
  groups = (n_group, topk_group, group_mode, held)

  if layer is not None:
    assert capacity_factor is None, "the grouped form drops nothing"
    cuts = range(0, T, GROUPED_MAX_TOKENS)
    pieces = [x[at : at + GROUPED_MAX_TOKENS] for at in cuts]
    drawn = [routed and Routed(*(t[at : at + GROUPED_MAX_TOKENS] for t in routed)) for at in cuts]
    outs, auxs, visits = zip(*(_moe_ffn_grouped(piece, w_router, w_gate, w_up, w_down, *routing, *groups, scales, layer, act, r) for piece, r in zip(pieces, drawn)))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return out, sum(a * piece.shape[0] for a, piece in zip(auxs, pieces)) / T, sum(visits)
  if T <= chunk:
    return _moe_ffn_block(x, w_router, w_gate, w_up, w_down, *routing, capacity_factor, *groups, act, routed)
  pad = (-T) % chunk
  blocks = lambda t, fill=0: (jnp.pad(t, ((0, pad), (0, 0)), constant_values=fill) if pad else t).reshape(-1, chunk, t.shape[-1])  # noqa: E731
  if routed is None:
    out_c, aux_c, visited_c = jax.lax.map(lambda xs: _moe_ffn_block(xs, w_router, w_gate, w_up, w_down, *routing, capacity_factor, *groups, act), blocks(x))
  else:  # (a padding row chooses no expert at all — an id past the last: it must not take a real token's place in an expert's capacity)
    cut = Routed(blocks(routed.logits), blocks(routed.weights), blocks(routed.idx, w_router.shape[-1]))
    out_c, aux_c, visited_c = jax.lax.map(lambda xr: _moe_ffn_block(xr[0], w_router, w_gate, w_up, w_down, *routing, capacity_factor, *groups, act, xr[1]), (blocks(x), cut))
  return out_c.reshape(-1, D)[:T], jnp.mean(aux_c), jnp.sum(visited_c)  # padding rows bias aux slightly; acceptable for a regularizer


def load_balancing_loss(router_logits: jnp.ndarray, idx: jnp.ndarray, n_experts: int) -> jnp.ndarray:
  """Switch/GShard auxiliary loss: E · Σ_e (frac tokens to e) · (mean prob to e)."""
  probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)  # [T, E]
  onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # [T, k, E]
  frac_tokens = jnp.mean(jnp.sum(onehot, axis=1), axis=0)  # [E]
  mean_prob = jnp.mean(probs, axis=0)  # [E]
  return n_experts * jnp.sum(frac_tokens / idx.shape[1] * mean_prob)
