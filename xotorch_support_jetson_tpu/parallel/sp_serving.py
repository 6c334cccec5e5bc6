"""Sequence-parallel SERVING: the KV cache sharded over the ``sp`` mesh axis —
flash-decode across chips.

Long-context decode is bound by the cache read: at 32K context a 1B model
reads ~1 GB of KV per token on top of its ~2.5 GB of weights. Sharding the
cache over ``sp`` splits that read N ways AND multiplies cache capacity by N:
each rank attends only its slot range and the per-rank partial softmax stats
(m, l, acc) merge over ICI with one ``pmax`` + two ``psum`` per layer — the
distributed form of split-K flash-decode. The mesh is ``sp × tp``: weights
shard megatron-style over tp (shard_map is manual only over sp, so GSPMD
inserts the tp all-reduces exactly as in pp_serving's pp × tp split) and are
replicated over sp itself — sp is the *context* axis (SURVEY.md §5.7's
greenfield mandate); tp is the weight-read axis.

Same entry points as ``pp_serving.PPServing``; the engine stores either under
its mesh-serving slot (``XOT_TPU_SP=N``). Training-side sequence parallelism
(ring attention, ``parallel/ring_attention.py``) shards the *queries* too;
serving decode has one query per step, so stat-merge is the right shape.
MLA composes: the absorbed-attention scores/latent-context pairs merge
exactly the same way (the per-head up-projection is applied after the
merge). Cache layout [L, B, S, H, hd] sharded over S (axis 2).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.decoder import _dense_qkv, _mla_latents, _mla_w_kv_b, _mlp_block, _next_token, _route_ahead, embed_tokens, head_logits
from ..ops.attention import NEG_INF, cap_and_mask_scores
from ..ops.norm import rms_norm
from ..ops.rope import rope_inv_freq
from .mesh import manual_axes

AXIS = "sp"


def _merge_stats(m_loc, l_loc, acc_loc):
  """Merge per-rank online-softmax partials over the sp axis.

  m [..., 1], l [..., 1], acc [..., d] (fp32). psum in f32 (bf16 all-reduce
  trips an XLA CPU crash under partial-auto shard_map; see pp_serving)."""
  m_g = jax.lax.pmax(m_loc, AXIS)
  alpha = jnp.exp(m_loc - m_g)
  alpha = jnp.where(m_loc <= NEG_INF / 2, 0.0, alpha)  # all-masked rank contributes nothing
  l_g = jax.lax.psum(l_loc * alpha, AXIS)
  acc_g = jax.lax.psum(acc_loc * alpha, AXIS)
  return jnp.where(l_g == 0.0, 1.0, l_g), acc_g


def _partial_stats(scores):
  """scores [..., Skv] fp32 (already masked) → (m [...,1], l [...,1], p)."""
  m = jnp.max(scores, axis=-1, keepdims=True)
  p = jnp.exp(scores - m)
  p = jnp.where(m <= NEG_INF / 2, 0.0, p)
  return m, jnp.sum(p, axis=-1, keepdims=True), p


def _sp_gqa_attention(q, k_loc, v_loc, q_positions, kv_positions_local, scale=None, logit_softcap: float = 0.0, sliding_window=None, k_scale=None, v_scale=None):
  """q [B,Sq,Hq,hd]; k/v local chunk [B,Skv_loc,Hkv,hd] → merged [B,Sq,Hq,hd].
  The gemma2 options (softcap before masking, window into the mask) commute
  with the cross-rank merge — each rank's partials see the same scores a
  single device would. ``k_scale``/``v_scale`` [B,Skv_loc,Hkv,1] are this
  rank's int8-KV scales (ops/attention.py): k's applies to the local scores
  BEFORE the partial stats (so the merged softmax sees true scores), v's
  folds into the local probs — both are rank-local, so the merge itself is
  unchanged."""
  from ..ops.attention import kv_scale_to_scores

  B, Sq, Hq, hd = q.shape
  Hkv = k_loc.shape[2]
  hd_v = v_loc.shape[3]
  group = Hq // Hkv
  if scale is None:
    scale = 1.0 / float(hd) ** 0.5
  qg = q.reshape(B, Sq, Hkv, group, hd)
  scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32), k_loc.astype(jnp.float32)) * scale
  if k_scale is not None:
    scores = scores * kv_scale_to_scores(k_scale)
  scores = cap_and_mask_scores(scores, q_positions, kv_positions_local, logit_softcap, sliding_window)
  m, l, p = _partial_stats(scores)  # [B,Hkv,g,Sq,1], p [B,Hkv,g,Sq,Skv]
  if v_scale is not None:
    p = p * kv_scale_to_scores(v_scale)
  acc = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_loc.astype(jnp.float32))
  l_g, acc_g = _merge_stats(m, l, acc)
  out = acc_g / l_g  # [B, Hkv, g, Sq, hd_v] → [B, Sq, Hkv, g, hd_v]
  return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, Sq, Hq, hd_v).astype(q.dtype)


def _sp_mla_attention(q_nope, q_pe, ckv_loc, kpe_loc, w_kv_b, q_positions, kv_positions_local, v_dim: int):
  """Absorbed MLA attention with the latent cache sharded over sp.

  Scores and the latent context merge per rank; the per-head W_v
  up-projection applies AFTER the merge — so MLA composes with sp exactly
  (cf. ops/attention.py mla_absorbed_attention)."""
  B, Sq, H, nope = q_nope.shape
  rank = ckv_loc.shape[-1]
  rope = q_pe.shape[-1]
  W = w_kv_b.reshape(rank, H, nope + v_dim)
  w_k = W[..., :nope].astype(jnp.float32)
  w_v = W[..., nope:].astype(jnp.float32)
  scale = 1.0 / jnp.sqrt(jnp.asarray(nope + rope, dtype=jnp.float32))
  q_abs = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), w_k)
  scores = jnp.einsum("bshr,btr->bhst", q_abs, ckv_loc.astype(jnp.float32))
  scores = scores + jnp.einsum("bshp,btp->bhst", q_pe.astype(jnp.float32), kpe_loc.astype(jnp.float32))
  scores = scores * scale
  mask = kv_positions_local[None, None, None, :] <= q_positions[:, None, :, None]
  scores = jnp.where(mask, scores, NEG_INF)
  m, l, p = _partial_stats(scores)  # [B,H,Sq,1]
  ctx = jnp.einsum("bhst,btr->bhsr", p, ckv_loc.astype(jnp.float32))
  l_g, ctx_g = _merge_stats(m, l, ctx)
  ctx_g = jnp.moveaxis(ctx_g / l_g, 1, 2)  # [B,Sq,H,rank]
  out = jnp.einsum("bshr,rhv->bshv", ctx_g, w_v)
  return out.astype(q_nope.dtype)


def _write_chunk(cache, new, start, rank_offset):
  """Scatter ``new`` [B,Sn,H,hd] (absolute slots [start, start+Sn)) into this
  rank's chunk [B,Sloc,H,hd]. Decode (Sn==1) is an O(B) windowed write; wider
  writes (prefill) use a masked position gather over the chunk."""
  B, Sn = new.shape[0], new.shape[1]
  Sloc = cache.shape[1]
  if Sn == 1:
    def row(c, n, s):
      local = jnp.clip(s - rank_offset, 0, Sloc - 1)
      mine = (s >= rank_offset) & (s < rank_offset + Sloc)
      window = jax.lax.dynamic_slice_in_dim(c, local, 1, axis=0)
      return jax.lax.dynamic_update_slice_in_dim(c, jnp.where(mine, n.astype(c.dtype), window), local, axis=0)

    return jax.vmap(row)(cache, new, start)

  def row(c, n, s):
    absolute = rank_offset + jnp.arange(Sloc, dtype=jnp.int32)
    idx = jnp.clip(absolute - s, 0, Sn - 1)
    cand = jnp.take(n, idx, axis=0).astype(c.dtype)
    written = (absolute >= s) & (absolute < s + Sn)
    return jnp.where(written[:, None, None], cand, c)

  return jax.vmap(row)(cache, new, start)


def _sp_layer_step(h, p, kv, positions, rank_offset, inv_freq, cfg: ModelConfig, kv_positions_local=None, write_one=None, read_one=None):
  """One decoder layer with an sp-sharded cache. h replicated [B,S,D].

  ``kv`` is this layer's cache dict ({"k", "v"} [+ "k_scale"/"v_scale" int8
  KV — models/decoder.py init_kv_cache]). Default layout: leaves are this
  rank's CONTIGUOUS chunk [B,Sloc,H,hd] (slot positions ``rank_offset +
  arange``, ``_write_chunk`` writes). The striped paged layout
  (parallel/sp_batch.py) overrides the three knobs: ``kv_positions_local``
  gives each stored slot's absolute position, ``write_one(leaf, new, start)``
  scatters one leaf's new values, ``read_one(leaf)`` yields the
  position-ordered view the attention reads — so the attention/norm/MLP
  skeleton (and the int8-KV quantize-at-write) exists exactly once for both
  layouts; scale leaves ride the same writers (trailing [..., 1] axis).
  """
  B, S, D = h.shape
  if kv_positions_local is None:
    kv_positions_local = rank_offset + jnp.arange(kv["k"].shape[1], dtype=jnp.int32)
  if write_one is None:
    write_one = lambda leaf, new, start: _write_chunk(leaf, new, start, rank_offset)  # noqa: E731
  if read_one is None:
    read_one = lambda leaf: leaf  # noqa: E731
  x = rms_norm(h, p["attn_norm"], cfg.norm_eps)
  routed = _route_ahead(x, p, cfg)
  start = positions[:, 0]
  if "wkv_a" in p:
    q_nope, q_pe, c_kv, k_pe = _mla_latents(x, p, cfg, positions, inv_freq)
    kv = {"k": write_one(kv["k"], c_kv[:, :, None, :], start), "v": write_one(kv["v"], k_pe[:, :, None, :], start)}
    attn = _sp_mla_attention(
      q_nope, q_pe, read_one(kv["k"])[:, :, 0, :].astype(h.dtype), read_one(kv["v"])[:, :, 0, :].astype(h.dtype),
      _mla_w_kv_b(p, h.dtype), positions, kv_positions_local, cfg.v_head_dim,
    )
  else:
    from ..models.decoder import _attn_opts

    q, k, v = _dense_qkv(x, p, cfg, positions, inv_freq)
    if "k_scale" in kv:  # int8/int4 KV: quantize at write, codes stay the read operand
      from ..models.quantize import quantize_kv, quantize_kv_int4, unpack_int4_kv

      packed = kv["k"].shape[-1] * 2 == k.shape[-1]  # int4: halved code axis (ISSUE 11)
      quant_fn = quantize_kv_int4 if packed else quantize_kv
      kq, ks = quant_fn(k)
      vq, vs = quant_fn(v)
      kv = {
        "k": write_one(kv["k"], kq, start),
        "k_scale": write_one(kv["k_scale"], ks, start),
        "v": write_one(kv["v"], vq, start),
        "v_scale": write_one(kv["v_scale"], vs, start),
      }
      k_codes = unpack_int4_kv(read_one(kv["k"])) if packed else read_one(kv["k"])
      v_codes = unpack_int4_kv(read_one(kv["v"])) if packed else read_one(kv["v"])
      attn = _sp_gqa_attention(
        q, k_codes, v_codes, positions, kv_positions_local,
        k_scale=read_one(kv["k_scale"]), v_scale=read_one(kv["v_scale"]), **_attn_opts(cfg, p.get("is_sliding"))
      )
    else:
      kv = {"k": write_one(kv["k"], k, start), "v": write_one(kv["v"], v, start)}
      attn = _sp_gqa_attention(q, read_one(kv["k"]).astype(h.dtype), read_one(kv["v"]).astype(h.dtype), positions, kv_positions_local, **_attn_opts(cfg, p.get("is_sliding")))
  from ..models.decoder import _mm

  attn_out = _mm(attn.reshape(B, S, -1), p, "wo", cfg.quant_compute)
  if "post_attn_norm" in p:  # gemma2
    attn_out = rms_norm(attn_out, p["post_attn_norm"], cfg.norm_eps)
  h = h + attn_out
  h, *_ = _mlp_block(h, p, cfg, routed)
  return h, kv


def _sp_forward(params, h, positions, cache, cfg: ModelConfig, rank_offset):
  inv_freq = rope_inv_freq(cfg)
  parts = []
  off = 0
  stacks = [params[name] for name in ("layers", "moe_layers") if name in params]
  for stack in stacks:
    L = next(iter(stack.values())).shape[0]

    def body(carry, per_layer):
      lp, kv = per_layer
      h2, kv = _sp_layer_step(carry, lp, kv, positions, rank_offset, inv_freq, cfg)
      return h2, kv

    h, new_sub = jax.lax.scan(body, h, (stack, {key: val[off : off + L] for key, val in cache.items()}))
    parts.append(new_sub)
    off += L
  new_cache = parts[0] if len(parts) == 1 else {key: jnp.concatenate([p[key] for p in parts], axis=0) for key in parts[0]}
  return h, new_cache


class SPServing:
  """Compiled sequence-parallel serving programs for one loaded shard.

  Entry-point-compatible with ``pp_serving.PPServing`` (the engine stores
  either in its mesh-serving slot): prefill / decode_step / fused_decode /
  fused_generate / place_cache. Enable with ``XOT_TPU_SP=N``.
  """

  def __init__(self, mesh: Mesh, cfg: ModelConfig, params: dict, n_ranks: int, is_first: bool, is_last: bool):
    if n_ranks < 2:
      raise ValueError("SPServing needs sp >= 2 (use the plain engine path otherwise)")
    if AXIS not in mesh.shape or mesh.shape[AXIS] != n_ranks:
      raise ValueError(f"mesh sp axis {mesh.shape.get(AXIS)} != n_ranks {n_ranks}")
    self.mesh = mesh
    self.cfg = cfg
    self.n_ranks = n_ranks
    self.is_first = is_first
    self.is_last = is_last
    # Weights shard megatron-style over tp (GSPMD inserts the block
    # all-reduces — shard_map is manual ONLY over sp, like pp_serving's
    # pp x tp split); they are replicated over sp itself. The cache shards
    # over sp (+ kv heads over tp when divisible), so sharding a long
    # context across chips no longer multiplies the weight HBM by sp
    # (round-2 review: params were fully replicated on every sp rank).
    from .mesh import shard_params

    self.params = shard_params(params, mesh)
    heads = cfg.cache_kv_heads
    tp = "tp" if "tp" in mesh.shape and heads > 1 and heads % mesh.shape["tp"] == 0 else None
    self._cache_spec = P(None, None, AXIS, tp, None)
    self._sm = partial(jax.shard_map, mesh=mesh, axis_names=manual_axes(mesh, AXIS), check_vma=False)
    self._build()

  def place_cache(self, cache: dict) -> dict:
    if cache["k"].shape[2] % self.n_ranks:
      raise ValueError(f"cache max_seq {cache['k'].shape[2]} not divisible by sp={self.n_ranks}")
    sharding = NamedSharding(self.mesh, self._cache_spec)
    return jax.tree.map(lambda x: jax.device_put(x, sharding), cache)

  # ------------------------------------------------------------- programs

  def _build(self) -> None:
    cfg = self.cfg
    is_first, is_last = self.is_first, self.is_last
    sm = self._sm

    def rank_offset(cache):
      # Local chunk width × this rank's index = its first absolute slot.
      return jax.lax.axis_index(AXIS) * cache["k"].shape[2]

    def forward_sm(params, x, positions, cache):
      h0 = embed_tokens(params, cfg, x) if (is_first and x.ndim == 2) else x.astype(cfg.dtype)
      return _sp_forward(params, h0, positions, cache, cfg, rank_offset(cache))

    cache_inner = P(None, None, AXIS, None, None)

    @partial(jax.jit, donate_argnums=(3,))
    def _prefill(params, x, positions, cache, prompt_len):
      fn = sm(forward_sm, in_specs=(P(), P(), P(), cache_inner), out_specs=(P(), cache_inner))
      h, cache = fn(params, x, positions, cache)
      if not is_last:
        return h, cache
      B, _, Dv = h.shape[0], h.shape[1], h.shape[2]
      idx = (prompt_len - 1).reshape(B, 1, 1)
      last = jnp.take_along_axis(h, jnp.broadcast_to(idx, (B, 1, Dv)), axis=1)
      return head_logits(params, cfg, last)[:, 0, :], cache

    @partial(jax.jit, donate_argnums=(3,))
    def _decode_step(params, x, positions, cache):
      fn = sm(forward_sm, in_specs=(P(), P(), P(), cache_inner), out_specs=(P(), cache_inner))
      h, cache = fn(params, x, positions, cache)
      if not is_last:
        return h, cache
      return head_logits(params, cfg, h)[:, 0, :], cache

    def fused_decode_sm(n_steps: int, top_k: int, greedy: bool):
      def body_fn(params, token, cache, start_pos, temp, key):
        off = rank_offset(cache)

        def body(carry, _):
          tok, pos, cache, key = carry
          h0 = embed_tokens(params, cfg, tok)
          h, cache = _sp_forward(params, h0, pos[:, None], cache, cfg, off)
          logits = head_logits(params, cfg, h)[:, 0, :]
          nxt, key = _next_token(logits, key, greedy, temp, top_k)
          return (nxt[:, None], pos + 1, cache, key), nxt

        (_, _, cache, _), toks = jax.lax.scan(body, (token, start_pos, cache, key), None, length=n_steps)
        return jnp.moveaxis(toks, 0, 1), cache

      return sm(body_fn, in_specs=(P(), P(), cache_inner, P(), P(), P()), out_specs=(P(), cache_inner))

    @partial(jax.jit, static_argnames=("n_steps", "top_k", "greedy"), donate_argnums=(2,))
    def _fused_decode(params, token, cache, start_pos, temp, key, n_steps: int, top_k: int, greedy: bool):
      return fused_decode_sm(n_steps, top_k, greedy)(params, token, cache, start_pos, temp, key)

    def fused_generate_sm(max_steps: int, eos_ids: tuple, top_k: int, greedy: bool):
      def body_fn(params, token, cache, start_pos, temp, key, n_limit):
        off = rank_offset(cache)
        B = token.shape[0]
        eos = jnp.asarray(eos_ids, dtype=jnp.int32) if eos_ids else None
        limit = jnp.minimum(n_limit.astype(jnp.int32), max_steps)
        buf0 = jnp.zeros((B, max_steps), dtype=jnp.int32)
        done0 = jnp.zeros((B,), dtype=jnp.bool_)

        def cond(carry):
          _, _, _, _, _, i, done = carry
          return (i < limit) & ~jnp.all(done)

        def body(carry):
          tok, pos, cache, key, buf, i, done = carry
          h0 = embed_tokens(params, cfg, tok)
          h, cache = _sp_forward(params, h0, pos[:, None], cache, cfg, off)
          logits = head_logits(params, cfg, h)[:, 0, :]
          nxt, key = _next_token(logits, key, greedy, temp, top_k)
          buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
          if eos is not None:
            done = done | jnp.any(nxt[:, None] == eos[None, :], axis=-1)
          return (nxt[:, None], pos + 1, cache, key, buf, i + 1, done)

        _, _, cache, _, buf, n, _ = jax.lax.while_loop(cond, body, (token, start_pos, cache, key, buf0, jnp.int32(0), done0))
        return buf, n, cache

      return sm(body_fn, in_specs=(P(), P(), cache_inner, P(), P(), P(), P()), out_specs=(P(), P(), cache_inner))

    @partial(jax.jit, static_argnames=("max_steps", "eos_ids", "top_k", "greedy"), donate_argnums=(2,))
    def _fused_generate(params, token, cache, start_pos, temp, key, n_limit, max_steps: int, eos_ids: tuple, top_k: int, greedy: bool):
      return fused_generate_sm(max_steps, eos_ids, top_k, greedy)(params, token, cache, start_pos, temp, key, n_limit)

    self._prefill_fn = _prefill
    self._decode_fn = _decode_step
    self._fused_decode_fn = _fused_decode
    self._fused_generate_fn = _fused_generate

  # ------------------------------------------------------------ entry points

  def prefill(self, x, cache, prompt_len):
    """x [B,S] tokens (first shard) | [B,S,D] hidden; prompt_len [B]."""
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return self._prefill_fn(self.params, x, positions, cache, prompt_len)

  def decode_step(self, x, cache, pos):
    return self._decode_fn(self.params, x, pos.reshape(-1, 1), cache)

  def fused_decode(self, token, cache, start_pos, n_steps: int, temp: float = 0.0, top_k: int = 35, key=None):
    if not (self.is_first and self.is_last):
      raise ValueError("fused sp decode requires a full-model shard")
    if key is None:
      key = jax.random.PRNGKey(0)
    greedy = temp is None or float(temp) <= 0.0
    temp_arr = jnp.float32(1.0 if greedy else float(temp))
    return self._fused_decode_fn(self.params, token, cache, start_pos, temp_arr, key, int(n_steps), int(top_k), greedy)

  def fused_generate(self, token, cache, start_pos, max_steps: int, eos_ids: tuple = (), temp: float = 0.0, top_k: int = 35, key=None, n_limit=None):
    if not (self.is_first and self.is_last):
      raise ValueError("fused sp generate requires a full-model shard")
    if key is None:
      key = jax.random.PRNGKey(0)
    greedy = temp is None or float(temp) <= 0.0
    temp_arr = jnp.float32(1.0 if greedy else float(temp))
    limit = jnp.int32(max_steps if n_limit is None else n_limit)
    return self._fused_generate_fn(self.params, token, cache, start_pos, temp_arr, key, limit, int(max_steps), tuple(eos_ids), int(top_k), greedy)
