"""Sequence-parallel CONTINUOUS-BATCHING serving: the batched slot pool with
its KV cache sharded over ``sp`` (weights over tp) — concurrent long-context
streams.

The round-3 sp × tp composition (sp_serving.py) serves ONE stream with the
cache read split across chips; this module runs the batch scheduler's slot
pool the same way: cache [L, B, S, H, hd] shards the SEQUENCE axis over sp,
every rank computes all B rows' attention over its slot range, and the
per-rank online-softmax partials merge with one pmax + two psum per layer
(sp_serving._sp_gqa_attention handles [B]-row q positions natively, so the
batched variant reuses the exact same layer step).

PAGED pool (the scheduler's DEFAULT cache mode) composes too, via
**page-slot striping**: the pool [L, P, Hkv, ps, hd] shards its PAGE-SLOT
axis (3) over sp, so every rank holds slots [r·ps/sp, (r+1)·ps/sp) of every
page. Page ids stay GLOBAL — the host allocator, block tables, and prefix
cache are completely unchanged — while each rank's cache read (the
long-context bottleneck) is 1/sp of the pool and capacity per chip scales
by sp. Decode writes land on exactly one owning rank (the others dump into
their stripe of the trash page 0); attention runs per rank over its strided
slots and the online-softmax partials merge exactly like the dense path.
This un-degrades the round-3 gap where sp + XOT_TPU_PAGED=1 silently fell
back to single-stream serving (VERDICT r3 weak #2).

No reference counterpart (one request at a time around its ring); with the
platform's cache-read wall (NOTES.md), sp is the structural long-context
answer and this makes it a multi-stream one.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.decoder import _next_token_batched, embed_tokens, head_logits
from ..ops.rope import rope_inv_freq
from .mesh import manual_axes
from ..utils.programs import tracked_jit
from .sp_serving import AXIS, SPServing, _sp_forward, _sp_layer_step


def _stripe_positions(mp: int, stripe: int, page_size: int, rank) -> jnp.ndarray:
  """Absolute position of each of this rank's gathered slots: local slot j
  of logical page m sits at m·ps + rank·stripe + (j mod stripe)."""
  j = jnp.arange(mp * stripe, dtype=jnp.int32)
  return (j // stripe) * page_size + rank * stripe + (j % stripe)


def _gather_local(pool_part: jnp.ndarray, bt: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
  """[P, Hkv, stripe, hd] × [B, mp] → this rank's position-ordered slots
  [B, mp·stripe, Hkv, hd]: ops/paged.py ``gather_pages`` of one layer's
  stripe (``kv_heads``, the model's, unpairs a leaf of paired heads: that
  module's note)."""
  from ..ops.paged import gather_pages

  return gather_pages(pool_part, bt, kv_heads=kv_heads)


def _write_token_local(pool_l: jnp.ndarray, new: jnp.ndarray, bt: jnp.ndarray, pos: jnp.ndarray, page_size: int, stripe: int, rank) -> jnp.ndarray:
  """One decode step's KV into this rank's stripe of the pool (one layer).

  pool_l [P, Hkv, stripe, hd]; new [B, Hkv, hd] (regrouped to the leaf's heads
  where those are pairs); pos [B]. The rank owning
  ``pos % ps`` writes its page; every other rank writes its stripe of the
  trash page 0 (rows own disjoint pages, so real writes never collide)."""
  from ..ops.paged import heads_as

  page = jnp.take_along_axis(bt, (pos // page_size)[:, None], axis=1)[:, 0]
  off = pos % page_size
  mine = (off // stripe) == rank
  page_eff = jnp.where(mine, page, 0)
  return pool_l.at[page_eff, :, off % stripe].set(heads_as(new, pool_l.shape[1]).astype(pool_l.dtype))


def _write_span_local(gathered: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray, kv_pos_local: jnp.ndarray) -> jnp.ndarray:
  """Prefill write: scatter ``new`` [B, Sn, H, hd] (absolute positions
  [start_b, start_b+Sn)) into the gathered local slots [B, N, H, hd] whose
  absolute positions are ``kv_pos_local`` [N] — the striped-layout analogue
  of sp_serving._write_chunk's masked position gather."""
  Sn = new.shape[1]

  def row(c, n, s):
    idx = jnp.clip(kv_pos_local - s, 0, Sn - 1)
    cand = jnp.take(n, idx, axis=0).astype(c.dtype)
    written = (kv_pos_local >= s) & (kv_pos_local < s + Sn)
    return jnp.where(written[:, None, None], cand, c)

  return jax.vmap(row)(gathered, new, start)


def _sp_paged_layer_prefill(h, p, temp, positions, kv_pos_local, inv_freq, cfg: ModelConfig):
  """One layer of striped-pool prefill against the GATHERED local slots
  (``temp`` leaf dict, [B, N, H, hd] each); per-row positions [B, S]. The
  shared sp layer skeleton with the span write + strided positions plugged
  in (scale leaves ride the same per-leaf writer)."""
  return _sp_layer_step(
    h, p, temp, positions, 0, inv_freq, cfg,
    kv_positions_local=kv_pos_local,
    write_one=lambda leaf, new, start: _write_span_local(leaf, new, start, kv_pos_local),
  )


def _sp_paged_layer_decode(h, p, pool_l, bt, positions, kv_pos_local, inv_freq, cfg: ModelConfig, page_size: int, stripe: int, rank):
  """One decode layer against this rank's stripe of the page pool
  (``pool_l`` leaf dict, [P, Hkv, stripe, hd] each): token write into the
  owning rank's stripe, gather-on-read, strided positions — same shared
  skeleton."""
  return _sp_layer_step(
    h, p, pool_l, positions, 0, inv_freq, cfg,
    kv_positions_local=kv_pos_local,
    write_one=lambda leaf, new, start: _write_token_local(leaf, new[:, 0], bt, start, page_size, stripe, rank),
    read_one=lambda leaf: _gather_local(leaf, bt, cfg.cache_kv_heads),
  )


class SPBatchedServing:
  """Compiled sp-sharded batched programs for one loaded full-model shard.

  Shares the SPServing instance's tp-placed params; exposes the operation
  set the batch scheduler uses for BOTH cache layouts: dense slots (cache
  sequence axis over sp) and the paged pool (page-slot axis striped over
  sp — see module docstring)."""

  def __init__(self, sps: SPServing):
    self._sps = sps
    self.mesh: Mesh = sps.mesh
    self.cfg: ModelConfig = sps.cfg
    self.n_ranks = sps.n_ranks
    self.params = sps.params
    self._sm = partial(jax.shard_map, mesh=self.mesh, axis_names=manual_axes(self.mesh, AXIS), check_vma=False)
    self._build()

  def place_cache(self, cache: dict) -> dict:
    return self._sps.place_cache(cache)  # same spec + divisibility check

  def place_pool(self, pool: dict) -> dict:
    """Stripe the pool's page-slot axis over sp: [L, P, Hkv, ps, hd] with
    axis 3 sharded — every rank holds ps/sp slots of EVERY page, so block
    tables and the host allocator stay global/unchanged."""
    ps = pool["k"].shape[3]
    if ps % self.n_ranks:
      raise ValueError(f"page_size {ps} not divisible by sp={self.n_ranks}")
    sharding = NamedSharding(self.mesh, P(None, None, None, AXIS, None))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), pool)

  def _build(self) -> None:
    cfg = self.cfg
    sm = self._sm
    cache_inner = P(None, None, AXIS, None, None)

    def rank_offset(cache):
      return jax.lax.axis_index(AXIS) * cache["k"].shape[2]

    def prefill_slots_sm(params, tokens, positions, cache, rows):
      sub = {k: jnp.take(v, rows, axis=1) for k, v in cache.items()}
      h0 = embed_tokens(params, cfg, tokens)
      h, sub = _sp_forward(params, h0, positions, sub, cfg, rank_offset(sub))
      cache = {k: cache[k].at[:, rows].set(sub[k]) for k in cache}
      return h, cache

    @tracked_jit("sp.prefill_slots")  # NOT donated: a failed prefill must leave the pool intact
    def _prefill_slots(params, tokens, cache, rows, prompt_lens):
      K, S = tokens.shape
      positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (K, S))
      fn = sm(prefill_slots_sm, in_specs=(P(), P(), P(), cache_inner, P()), out_specs=(P(), cache_inner))
      h, cache = fn(params, tokens, positions, cache, rows)
      idx = (prompt_lens - 1).reshape(K, 1, 1)
      last = jnp.take_along_axis(h, jnp.broadcast_to(idx, (K, 1, h.shape[-1])), axis=1)
      return head_logits(params, cfg, last)[:, 0, :], cache

    def decode_sm(n_steps: int, k_max: int):
      def fn(params, token, cache, positions, active, temps, top_ks, key):
        off = rank_offset(cache)

        def body(carry, _):
          tok, pos, cache, key = carry
          h0 = embed_tokens(params, cfg, tok)
          h, cache = _sp_forward(params, h0, pos[:, None], cache, cfg, off)
          logits = head_logits(params, cfg, h)[:, 0, :]
          nxt, key = _next_token_batched(logits, key, temps, top_ks, k_max)
          nxt = jnp.where(active, nxt, tok[:, 0])  # inactive rows hold
          pos = jnp.where(active, pos + 1, pos)
          return (nxt[:, None], pos, cache, key), nxt

        (_, pos, cache, _), toks = jax.lax.scan(body, (token, positions, cache, key), None, length=n_steps)
        return jnp.moveaxis(toks, 0, 1), pos, cache

      return fn

    @partial(tracked_jit, "sp.decode", static_argnames=("n_steps", "k_max"), donate_argnums=(2,))
    def _batch_decode(params, token, cache, positions, active, temps, top_ks, key, n_steps: int, k_max: int):
      fn = sm(
        decode_sm(n_steps, k_max),
        in_specs=(P(), P(), cache_inner, P(), P(), P(), P(), P()),
        out_specs=(P(), P(), cache_inner),
      )
      toks, pos, cache = fn(params, token, cache, positions, active, temps, top_ks, key)
      # Device-resident chain token (shared batched-ops contract): the scan
      # body holds inactive rows' tokens, so the last column is the next
      # chunk's input for every row.
      return toks, toks[:, -1:], pos, cache

    # ---- paged pool, page-slot axis striped over sp (module docstring)

    pool_inner = P(None, None, None, AXIS, None)

    def stacks_of(params):
      return [params[name] for name in ("layers", "moe_layers") if name in params]

    def paged_prefill_sm(page_size: int):
      def fn(params, tokens, positions, pool, bt_rows, prefix_lens, prompt_lens):
        from ..ops.paged import gather_row_pages, scatter_row_pages, touched_page_targets

        rank = jax.lax.axis_index(AXIS)
        stripe = pool["k"].shape[3]
        K, S = tokens.shape
        mp = bt_rows.shape[1]
        kv_pos_local = _stripe_positions(mp, stripe, page_size, rank)
        inv_freq = rope_inv_freq(cfg)
        target = touched_page_targets(bt_rows, prefix_lens, prompt_lens, page_size)
        scatter_l = lambda pool_part, t: scatter_row_pages(pool_part, t, target)  # noqa: E731

        h = embed_tokens(params, cfg, tokens)
        temp = {key: gather_row_pages(val, bt_rows, cfg.cache_kv_heads) for key, val in pool.items()}
        off = 0
        parts = []
        for stack in stacks_of(params):
          L = next(iter(stack.values())).shape[0]

          def body(carry, per_layer):
            lp, sub = per_layer
            h2, sub = _sp_paged_layer_prefill(carry, lp, sub, positions, kv_pos_local, inv_freq, cfg)
            return h2, sub

          h, new_sub = jax.lax.scan(body, h, (stack, {key: val[off : off + L] for key, val in temp.items()}))
          parts.append(new_sub)
          off += L
        new_temp = parts[0] if len(parts) == 1 else {key: jnp.concatenate([p[key] for p in parts], axis=0) for key in parts[0]}
        return h, {key: scatter_l(pool[key], new_temp[key]) for key in pool}

      return fn

    @partial(tracked_jit, "sp.prefill_pages", static_argnames=("page_size",))  # NOT donated: a failed prefill must leave the pool intact
    def _prefill_pages(params, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int):
      K, S = tokens.shape
      positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
      fn = sm(
        paged_prefill_sm(page_size),
        in_specs=(P(), P(), P(), pool_inner, P(), P(), P()),
        out_specs=(P(), pool_inner),
      )
      h, pool = fn(params, tokens, positions, pool, bt_rows, prefix_lens, prompt_lens)
      idx = (prompt_lens - prefix_lens - 1).reshape(K, 1, 1)
      last = jnp.take_along_axis(h, jnp.broadcast_to(idx, (K, 1, h.shape[-1])), axis=1)
      return head_logits(params, cfg, last)[:, 0, :], pool

    def paged_decode_sm(n_steps: int, k_max: int, page_size: int):
      def fn(params, token, pool, block_tables, positions, active, temps, top_ks, key):
        rank = jax.lax.axis_index(AXIS)
        stripe = pool["k"].shape[3]
        mp = block_tables.shape[1]
        kv_pos_local = _stripe_positions(mp, stripe, page_size, rank)
        inv_freq = rope_inv_freq(cfg)

        def step(carry, _):
          tok, pos, pool, key = carry
          # Inactive rows' held-token rewrites go to the trash page (same
          # invariant as the single-device fused_paged_batch_decode).
          bt = jnp.where(active[:, None], block_tables, 0)
          h = embed_tokens(params, cfg, tok)
          off = 0
          parts = []
          for stack in stacks_of(params):
            L = next(iter(stack.values())).shape[0]

            def body(hc, per_layer):
              lp, pool_l = per_layer
              h2, pool_l = _sp_paged_layer_decode(hc, lp, pool_l, bt, pos[:, None], kv_pos_local, inv_freq, cfg, page_size, stripe, rank)
              return h2, pool_l

            h, new_sub = jax.lax.scan(body, h, (stack, {key: val[off : off + L] for key, val in pool.items()}))
            parts.append(new_sub)
            off += L
          pool = parts[0] if len(parts) == 1 else {key: jnp.concatenate([p[key] for p in parts], axis=0) for key in parts[0]}
          logits = head_logits(params, cfg, h)[:, 0, :]
          nxt, key = _next_token_batched(logits, key, temps, top_ks, k_max)
          nxt = jnp.where(active, nxt, tok[:, 0])  # inactive rows hold
          pos = jnp.where(active, pos + 1, pos)
          return (nxt[:, None], pos, pool, key), nxt

        (_, pos, pool, _), toks = jax.lax.scan(step, (token, positions, pool, key), None, length=n_steps)
        return jnp.moveaxis(toks, 0, 1), pos, pool

      return fn

    @partial(tracked_jit, "sp.paged_decode", static_argnames=("n_steps", "k_max", "page_size"), donate_argnums=(2,))
    def _paged_batch_decode(params, token, pool, block_tables, positions, active, temps, top_ks, key, n_steps: int, k_max: int, page_size: int):
      fn = sm(
        paged_decode_sm(n_steps, k_max, page_size),
        in_specs=(P(), P(), pool_inner, P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), pool_inner),
      )
      toks, pos, pool = fn(params, token, pool, block_tables, positions, active, temps, top_ks, key)
      return toks, toks[:, -1:], pos, pool

    self._prefill_slots_fn = _prefill_slots
    self._batch_decode_fn = _batch_decode
    self._prefill_pages_fn = _prefill_pages
    self._paged_batch_decode_fn = _paged_batch_decode

  # ------------------------------------------------------------ entry points

  def prefill_into_slot(self, tokens, cache, row, prompt_len):
    """tokens [1, S_pad] int32 → (last-token logits [1, V], cache)."""
    return self.prefill_into_slots(tokens, cache, jnp.asarray([row], jnp.int32), jnp.asarray([prompt_len], jnp.int32))

  def prefill_into_slots(self, tokens, cache, rows, prompt_lens):
    """tokens [K, S_pad] int32 → (last-token logits [K, V], cache) — K
    admissions in one sp-sharded prefill dispatch."""
    return self._prefill_slots_fn(
      self.params, jnp.asarray(tokens), cache, jnp.asarray(rows, jnp.int32), jnp.asarray(prompt_lens, jnp.int32)
    )

  def batch_decode(self, token, cache, positions, active, temps, top_ks, n_steps: int, k_max: int = 64, key=None):
    if key is None:
      key = jax.random.PRNGKey(0)
    return self._batch_decode_fn(
      self.params, jnp.asarray(token), cache, jnp.asarray(positions, jnp.int32),
      jnp.asarray(active, jnp.bool_), jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32),
      key, int(n_steps), int(k_max),
    )

  def prefill_into_pages_many(self, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int):
    """K admissions into the striped pool in one sp-sharded dispatch."""
    return self._prefill_pages_fn(
      self.params, jnp.asarray(tokens), pool, jnp.asarray(bt_rows, jnp.int32),
      jnp.asarray(prefix_lens, jnp.int32), jnp.asarray(prompt_lens, jnp.int32), int(page_size),
    )

  def paged_batch_decode(self, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int = 64, page_size: int = 64, key=None):
    if key is None:
      key = jax.random.PRNGKey(0)
    return self._paged_batch_decode_fn(
      self.params, jnp.asarray(token), pool, jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(positions, jnp.int32), jnp.asarray(active, jnp.bool_), jnp.asarray(temps, jnp.float32),
      jnp.asarray(top_ks, jnp.int32), key, int(n_steps), int(k_max), int(page_size),
    )
