"""In-slice pipeline-parallel SERVING: KV-cached prefill/decode over ``pp``
mesh stages with ``shard_map`` + ``lax.ppermute``.

This delivers the reference's one headline capability — serving a model too
big for one device by layer-splitting (``reference/xotorch/orchestration/
node.py:424-443``, ``inference/shard.py:4``) — as a TPU-native program: one
host with N chips serves a model N× its single-chip HBM with activations
hopping stage→stage over ICI, never touching the host (vs the reference's
per-token gRPC protobuf laps). Composes with tensor parallelism: the mesh is
``pp × tp`` with shard_map manual ONLY over pp, so GSPMD shards each stage's
matmuls over tp and inserts the ICI all-reduces (parallel/mesh.py specs).

Schedule: a **masked-stage loop**. Each forward runs P ticks; at tick j only
stage j's compute is real — but every stage executes it (SPMD), and the
inactive stages' results are discarded by an O(B·S_written)-windowed cache
merge and a ``jnp.where`` on the activation carry. This costs zero extra
wall-clock for single-stream serving: the redundant compute runs in parallel
with the critical path on chips that would otherwise idle, so per-token time
is Σ stage times — exactly the sequential pipeline's latency — while each
stage's weights are read from ITS OWN HBM concurrently. (Decode is
weight-bandwidth-bound; P chips' HBM in parallel is the capacity win, not a
latency win — same as the reference's ring, minus the per-hop serialization.)

The cache is layer-sharded over pp (axis 0), so each stage holds only its
layer range's KV — cache capacity also scales with P.

Dense-prefix MoE models (deepseek first_k_dense) pipeline their MoE stack;
the 1-3 dense prefix layers run REPLICATED on every stage before the tick
loop (negligible compute, and it keeps the pipeline single-stack) with a
pp-replicated prefix cache.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.decoder import _layer_step, _next_token, embed_tokens, head_logits
from ..ops.rope import rope_inv_freq
from .mesh import manual_axes

_HEAD_KEYS = ("embed", "final_norm", "lm_head", "lm_head_scale")


def split_pp_params(params: dict, n_stages: int) -> tuple[str, dict, dict, int]:
  """Carve shard params into (stack_name, stage stack [P, L/P, ...], head).

  The head dict carries the embed/final-norm/lm-head leaves the pp program
  needs (replicated over pp; tp-sharded under GSPMD as usual) — plus, for
  dense-prefix MoE models (deepseek's first_k_dense), the whole PREFIX stack
  under ``"prefix_layers"``: those 1-3 layers run replicated on every stage
  before the pipeline (their compute is negligible next to the MoE stack,
  and replicating them keeps the tick loop single-stack).
  """
  stacks = [n for n in ("layers", "moe_layers") if n in params]
  head = {k: params[k] for k in _HEAD_KEYS if k in params}
  n_prefix = 0
  if len(stacks) == 2:
    head["prefix_layers"] = params["layers"]
    n_prefix = next(iter(params["layers"].values())).shape[0]
    stack_name = "moe_layers"
  elif len(stacks) == 1:
    stack_name = stacks[0]
  else:
    raise ValueError(f"pp serving: params have no layer stacks ({stacks})")
  stack = params[stack_name]
  L = next(iter(stack.values())).shape[0]
  if L % n_stages:
    raise ValueError(f"shard has {L} pipelined layers, not divisible by pp={n_stages}")
  stage_params = {k: v.reshape(n_stages, L // n_stages, *v.shape[1:]) for k, v in stack.items()}
  return stack_name, stage_params, head, n_prefix


def place_pp_params(stage_params: dict, head: dict, mesh: Mesh, stack_name: str) -> tuple[dict, dict]:
  """device_put: stage leaves [P, L/P, ...] over pp (+tp per the megatron
  specs with the stage axis prepended); head leaves per the top-level specs
  (a dense-prefix stack rides the head, replicated over pp, tp per specs)."""
  from .mesh import decoder_param_specs

  full = decoder_param_specs()
  layer_specs = full[stack_name]
  stage_placed = {
    k: jax.device_put(v, NamedSharding(mesh, P("pp", *layer_specs.get(k, P()))))
    for k, v in stage_params.items()
  }
  head_placed = {}
  for k, v in head.items():
    if k == "prefix_layers":
      pre_specs = full["layers"]
      head_placed[k] = {pk: jax.device_put(pv, NamedSharding(mesh, pre_specs.get(pk, P()))) for pk, pv in v.items()}
    else:
      head_placed[k] = jax.device_put(v, NamedSharding(mesh, full.get(k, P())))
  return stage_placed, head_placed


def pp_cache_spec(cfg: ModelConfig, mesh: Mesh) -> P:
  """[L, B, S, H, hd]: layers over pp; kv heads over tp when divisible."""
  heads = cfg.cache_kv_heads
  tp = "tp" if "tp" in mesh.shape and heads > 1 and heads % mesh.shape["tp"] == 0 else None
  return P("pp", None, None, tp, None)


def _merge_written(old: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray, width: int, active: jnp.ndarray) -> jnp.ndarray:
  """Keep ``new``'s cache writes only when ``active`` — O(B·width) work, not a
  full-cache copy. old/new [L,B,Smax,H,hd]; start [B] per-row slot offsets;
  active is a scalar (whole-batch stage mask) or [B] (per-row, pp_batch)."""
  active = jnp.broadcast_to(active, start.shape)

  def row(o, n, s, a):  # [L, Smax, H, hd]
    wn = jax.lax.dynamic_slice_in_dim(n, s, width, axis=1)
    wo = jax.lax.dynamic_slice_in_dim(o, s, width, axis=1)
    return jax.lax.dynamic_update_slice_in_dim(o, jnp.where(a, wn, wo), s, axis=1)

  return jax.vmap(row, in_axes=(1, 1, 0, 0), out_axes=1)(old, new, start, active)


def _stage_forward(stage_layers: dict, h: jnp.ndarray, positions: jnp.ndarray, cache: dict, inv_freq, cfg: ModelConfig):
  """This stage's layer range with cache (lax.scan, like shard_forward).
  Dict-generic over cache leaves, so int8-KV scale leaves ride through."""
  kv_positions = jnp.arange(cache["k"].shape[2], dtype=jnp.int32)

  def body(carry, per_layer):
    lp, kv = per_layer
    h2, kv, _ = _layer_step(carry, lp, kv, positions, kv_positions, inv_freq, cfg, True)
    return h2, kv

  return jax.lax.scan(body, h, (stage_layers, cache))


def _pp_tick_loop(stage_layers: dict, h0: jnp.ndarray, positions: jnp.ndarray, cache: dict, cfg: ModelConfig, n_stages: int, gather_pos=None):
  """The masked-stage pipeline for one forward of S tokens (see module doc).

  Inside shard_map manual-over-pp. Returns (last stage's output hidden,
  psum-broadcast to every stage so sampling/embedding stay SPMD; cache).
  With ``gather_pos`` [B] (prefill on a last shard), only the hidden row at
  position gather_pos-1 is broadcast — psumming the full [B,S,D] sequence
  would move S× more bytes over ICI than the one row the head consumes.
  """
  stage = jax.lax.axis_index("pp")
  inv_freq = rope_inv_freq(cfg)
  S = h0.shape[1]
  start = positions[:, 0]
  perm = [(i, i + 1) for i in range(n_stages - 1)]
  carry = h0
  for j in range(n_stages):
    recv = jax.lax.ppermute(carry, "pp", perm)
    my_in = jnp.where(stage == 0, h0, recv) if j == 0 else recv
    active = stage == jnp.int32(j)
    out, new_cache = _stage_forward(stage_layers, my_in, positions, cache, inv_freq, cfg)
    cache = {k: _merge_written(cache[k], new_cache[k], start, S, active) for k in cache}
    carry = jnp.where(active, out, carry)
  if gather_pos is not None:
    B, _, D = carry.shape
    idx = (gather_pos - 1).reshape(B, 1, 1)
    carry = jnp.take_along_axis(carry, jnp.broadcast_to(idx, (B, 1, D)), axis=1)
  # psum in f32: exact (only the last stage contributes non-zeros, and the
  # bf16→f32→bf16 round-trip is lossless), and it dodges an XLA CPU-backend
  # CHECK crash ("Invalid binary instruction opcode copy") on bf16
  # all-reduce under partial-auto shard_map on a multi-axis mesh.
  masked = jnp.where(stage == n_stages - 1, carry, jnp.zeros_like(carry))
  h_final = jax.lax.psum(masked.astype(jnp.float32), "pp").astype(carry.dtype)
  return h_final, cache


def _run_prefix(head: dict, h: jnp.ndarray, positions: jnp.ndarray, cache: dict, cfg: ModelConfig):
  """Dense-prefix layers (deepseek first_k_dense), REPLICATED on every stage:
  params and the ``*_pre`` cache are pp-replicated, so all ranks compute the
  same result before the masked-stage pipeline starts."""
  if "prefix_layers" not in head:
    return h, cache
  sub = {key[: -len("_pre")]: val for key, val in cache.items() if key.endswith("_pre")}
  h, pre = _stage_forward(head["prefix_layers"], h, positions, sub, rope_inv_freq(cfg), cfg)
  return h, {**cache, **{f"{key}_pre": val for key, val in pre.items()}}


def _full_forward(stage_layers: dict, head: dict, h0: jnp.ndarray, positions: jnp.ndarray, cache: dict, cfg: ModelConfig, n_stages: int, gather_pos=None):
  """Replicated dense prefix (if any) + the masked-stage pipeline."""
  h0, cache = _run_prefix(head, h0, positions, cache, cfg)
  main = {key: val for key, val in cache.items() if not key.endswith("_pre")}
  h, moe_cache = _pp_tick_loop(stage_layers, h0, positions, main, cfg, n_stages, gather_pos=gather_pos)
  return h, {**cache, **moe_cache}


class PPServing:
  """Compiled pipeline-parallel serving programs for one loaded shard.

  Built by the engine when ``XOT_TPU_PP > 1`` (jax_engine
  ``_maybe_shard_over_local_mesh``); holds the pp-placed params and exposes
  the same step/fused entry points the single-device engine uses:

    prefill(x, cache, prompt_len)        — tokens or hidden in, cache out
    decode_step(x, cache, pos)           — one token step
    fused_decode(token, cache, pos, n)   — n tokens, one compiled program
    fused_generate(token, cache, pos, …) — until EOS, one dispatch+readback

  ``is_first``/``is_last`` mirror the engine shard: a ring node serving a
  partial layer range can still pp its own range across its local chips
  (hidden in → hidden out); fused loops need the full model (is_first and
  is_last) because sampling feeds the next embed.
  """

  def __init__(self, mesh: Mesh, cfg: ModelConfig, params: dict, n_stages: int, is_first: bool, is_last: bool):
    if n_stages < 2:
      raise ValueError("PPServing needs pp >= 2 (use the plain engine path otherwise)")
    if "pp" not in mesh.shape or mesh.shape["pp"] != n_stages:
      raise ValueError(f"mesh pp axis {mesh.shape.get('pp')} != n_stages {n_stages}")
    self.mesh = mesh
    self.cfg = cfg
    self.n_stages = n_stages
    self.is_first = is_first
    self.is_last = is_last
    stack_name, stage_params, head, self.n_prefix = split_pp_params(params, n_stages)
    self._stack_name = stack_name
    self.stage_params, self.head = place_pp_params(stage_params, head, mesh, stack_name)
    self._cache_spec = pp_cache_spec(cfg, mesh)
    self._sm = partial(jax.shard_map, mesh=mesh, axis_names=manual_axes(mesh, "pp"), check_vma=False)
    self._build()

  # ------------------------------------------------ flat-params round trip
  # (PP-mode train/eval/checkpoint/LoRA — VERDICT r3 #4): the lifecycle
  # paths need the ordinary flat tree; the stage stacks merge back with the
  # LAYER axis sharded over pp (a reshape of the stage axis — each rank
  # keeps its contiguous layer block, no gather), so a 70B pipeline never
  # materializes unsharded weights.

  def reassemble_params(self) -> dict:
    """Inverse of ``split_pp_params``: flat tree with [L, ...] stacks
    (layer axis pp-sharded), dense-prefix stack back under "layers", head
    leaves at top level. Leaves stay device-resident; the merge jit is
    cached (train loops call this every step)."""
    if getattr(self, "_reassemble_fn", None) is None:
      from .mesh import decoder_param_specs

      layer_specs = decoder_param_specs()[self._stack_name]
      out_sh = {
        k: NamedSharding(self.mesh, P("pp", *tuple(layer_specs.get(k, P()))[1:]))  # flat spec minus its leading L dim
        for k in self.stage_params
      }
      self._reassemble_fn = jax.jit(
        lambda st: {k: v.reshape(v.shape[0] * v.shape[1], *v.shape[2:]) for k, v in st.items()},
        out_shardings=out_sh,
      )
    out = {self._stack_name: self._reassemble_fn(self.stage_params)}
    for k, v in self.head.items():
      if k == "prefix_layers":
        out["layers"] = v
      else:
        out[k] = v
    return out

  def adopt_params(self, params: dict) -> None:
    """Re-place an updated flat tree (post-train / checkpoint load / LoRA
    attach) into the serving layout. The compiled programs take the placed
    params as call arguments, so no recompile — but any object sharing the
    OLD stage arrays (pp_batch.from_pp_serving) must be rebuilt by the
    caller (the engine drops its batch backend)."""
    stack_name, stage_params, head, n_prefix = split_pp_params(params, self.n_stages)
    if stack_name != self._stack_name or n_prefix != self.n_prefix:
      raise ValueError(f"adopt_params structure changed: {stack_name}/{n_prefix} != {self._stack_name}/{self.n_prefix}")
    if set(stage_params) != set(self.stage_params):
      self._reassemble_fn = None  # leaf set changed (LoRA attach): new merge jit
    self.stage_params, self.head = place_pp_params(stage_params, head, self.mesh, stack_name)

  def place_cache(self, cache: dict) -> dict:
    """Engine cache [L_total, ...] → pp placement. With a dense prefix the
    first n_prefix layers split off as replicated ``*_pre`` buffers; the
    pipelined layers shard over pp."""
    # The compiled programs' cache specs were keyed at build time from
    # kv_quant_mode (env). A cache built with an explicit quant= override
    # that disagrees would die later as an opaque pytree mismatch — fail
    # here with the actual cause instead.
    if set(cache) != set(self._cache_keys):
      raise ValueError(
        f"cache leaves {sorted(cache)} != built specs {sorted(self._cache_keys)} — "
        "PPServing keys its programs off XOT_TPU_KV_QUANT at construction; allocate the cache with the same mode"
      )
    sharding = NamedSharding(self.mesh, self._cache_spec)
    if not self.n_prefix:
      return jax.tree.map(lambda x: jax.device_put(x, sharding), cache)
    repl = NamedSharding(self.mesh, P(*[None] * cache["k"].ndim))
    n = self.n_prefix
    out = {}
    for key, val in cache.items():
      out[f"{key}_pre"] = jax.device_put(val[:n], repl)
      out[key] = jax.device_put(val[n:], sharding)
    return out

  # ------------------------------------------------------------- programs

  def _build(self) -> None:
    cfg, n_stages = self.cfg, self.n_stages
    is_first, is_last = self.is_first, self.is_last
    # Per-key cache specs: pipelined layers shard over pp; a dense prefix's
    # buffers are replicated (every stage computes the prefix identically).
    # Scale keys appear when the engine allocates an int8-quantized cache
    # (models/decoder.py kv_quant_mode — env-driven, so known at build time).
    from ..models.decoder import kv_quant_mode

    cache_keys = ("k", "v", "k_scale", "v_scale") if kv_quant_mode(cfg) else ("k", "v")
    self._cache_keys = cache_keys
    cache_spec = {key: P("pp") for key in cache_keys}
    if self.n_prefix:
      cache_spec = {**cache_spec, **{f"{key}_pre": P() for key in cache_keys}}
    stage_spec = P("pp")

    def make_forward_sm(gather_last: bool):
      def forward_sm(stage_params, head, x, positions, cache, prompt_len):
        stage_layers = {k: v[0] for k, v in stage_params.items()}  # [1, L/P, ...] -> [L/P, ...]
        h0 = embed_tokens(head, cfg, x) if (is_first and x.ndim == 2) else x.astype(cfg.dtype)
        h, cache = _full_forward(stage_layers, head, h0, positions, cache, cfg, n_stages, gather_pos=prompt_len if gather_last else None)
        return h, cache

      return forward_sm

    sm = self._sm

    @partial(jax.jit, donate_argnums=(4,))
    def _prefill(stage_params, head, x, positions, cache, prompt_len):
      fn = sm(make_forward_sm(is_last), in_specs=(stage_spec, P(), P(), P(), cache_spec, P()), out_specs=(P(), cache_spec))
      h, cache = fn(stage_params, head, x, positions, cache, prompt_len)
      if not is_last:
        return h, cache
      return head_logits(head, cfg, h)[:, 0, :], cache

    @partial(jax.jit, donate_argnums=(4,))
    def _decode_step(stage_params, head, x, positions, cache):
      fn = sm(make_forward_sm(False), in_specs=(stage_spec, P(), P(), P(), cache_spec, P()), out_specs=(P(), cache_spec))
      h, cache = fn(stage_params, head, x, positions, cache, jnp.zeros((x.shape[0],), jnp.int32))
      if not is_last:
        return h, cache
      return head_logits(head, cfg, h)[:, 0, :], cache

    def fused_decode_sm(n_steps: int, top_k: int, greedy: bool):
      def body_fn(stage_params, head, token, cache, start_pos, temp, key):
        stage_layers = {k: v[0] for k, v in stage_params.items()}

        def body(carry, _):
          tok, pos, cache, key = carry
          h0 = embed_tokens(head, cfg, tok)
          h, cache = _full_forward(stage_layers, head, h0, pos[:, None], cache, cfg, n_stages)
          logits = head_logits(head, cfg, h)[:, 0, :]
          nxt, key = _next_token(logits, key, greedy, temp, top_k)
          return (nxt[:, None], pos + 1, cache, key), nxt

        (_, _, cache, _), toks = jax.lax.scan(body, (token, start_pos, cache, key), None, length=n_steps)
        return jnp.moveaxis(toks, 0, 1), cache

      return sm(body_fn, in_specs=(stage_spec, P(), P(), cache_spec, P(), P(), P()), out_specs=(P(), cache_spec))

    @partial(jax.jit, static_argnames=("n_steps", "top_k", "greedy"), donate_argnums=(3,))
    def _fused_decode(stage_params, head, token, cache, start_pos, temp, key, n_steps: int, top_k: int, greedy: bool):
      return fused_decode_sm(n_steps, top_k, greedy)(stage_params, head, token, cache, start_pos, temp, key)

    def fused_generate_sm(max_steps: int, eos_ids: tuple, top_k: int, greedy: bool):
      def body_fn(stage_params, head, token, cache, start_pos, temp, key, n_limit):
        stage_layers = {k: v[0] for k, v in stage_params.items()}
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
          h0 = embed_tokens(head, cfg, tok)
          h, cache = _full_forward(stage_layers, head, h0, pos[:, None], cache, cfg, n_stages)
          logits = head_logits(head, cfg, h)[:, 0, :]
          nxt, key = _next_token(logits, key, greedy, temp, top_k)
          buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
          if eos is not None:
            done = done | jnp.any(nxt[:, None] == eos[None, :], axis=-1)
          return (nxt[:, None], pos + 1, cache, key, buf, i + 1, done)

        _, _, cache, _, buf, n, _ = jax.lax.while_loop(cond, body, (token, start_pos, cache, key, buf0, jnp.int32(0), done0))
        return buf, n, cache

      return sm(body_fn, in_specs=(stage_spec, P(), P(), cache_spec, P(), P(), P(), P()), out_specs=(P(), P(), cache_spec))

    @partial(jax.jit, static_argnames=("max_steps", "eos_ids", "top_k", "greedy"), donate_argnums=(3,))
    def _fused_generate(stage_params, head, token, cache, start_pos, temp, key, n_limit, max_steps: int, eos_ids: tuple, top_k: int, greedy: bool):
      return fused_generate_sm(max_steps, eos_ids, top_k, greedy)(stage_params, head, token, cache, start_pos, temp, key, n_limit)

    self._prefill_fn = _prefill
    self._decode_fn = _decode_step
    self._fused_decode_fn = _fused_decode
    self._fused_generate_fn = _fused_generate

  # ------------------------------------------------------------ entry points
  # Each coarse entry records an op-level span (ISSUE 4: pp span marks in
  # the trace ring) — wall-clock of the DISPATCH (jax returns futures;
  # device time lives in the profiler), labeled with the pipeline geometry
  # so a cluster trace shows where a ring node's local pp program sat.
  # decode_step (the per-token ring hop path) stays unmarked: its spans
  # would dominate the ring buffer at one per token.

  def prefill(self, x, cache, prompt_len):
    """x [B,S] tokens (first shard) | [B,S,D] hidden; prompt_len [B]."""
    from ..orchestration.tracing import tracer

    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    with tracer.start_span("pp.dispatch.prefill", attributes={"pp": self.n_stages, "batch": int(B), "seq": int(S)}):
      return self._prefill_fn(self.stage_params, self.head, x, positions, cache, prompt_len)

  def decode_step(self, x, cache, pos):
    """x [B,1] token | [B,1,D] hidden; pos [B] absolute position."""
    return self._decode_fn(self.stage_params, self.head, x, pos.reshape(-1, 1), cache)

  def fused_decode(self, token, cache, start_pos, n_steps: int, temp: float = 0.0, top_k: int = 35, key=None):
    from ..orchestration.tracing import tracer

    if not (self.is_first and self.is_last):
      raise ValueError("fused pp decode requires a full-model shard")
    if key is None:
      key = jax.random.PRNGKey(0)
    greedy = temp is None or float(temp) <= 0.0
    temp_arr = jnp.float32(1.0 if greedy else float(temp))
    with tracer.start_span("pp.dispatch.fused_decode", attributes={"pp": self.n_stages, "batch": int(token.shape[0]), "n_steps": int(n_steps)}):
      return self._fused_decode_fn(self.stage_params, self.head, token, cache, start_pos, temp_arr, key, int(n_steps), int(top_k), greedy)

  def fused_generate(self, token, cache, start_pos, max_steps: int, eos_ids: tuple = (), temp: float = 0.0, top_k: int = 35, key=None, n_limit=None):
    from ..orchestration.tracing import tracer

    if not (self.is_first and self.is_last):
      raise ValueError("fused pp generate requires a full-model shard")
    if key is None:
      key = jax.random.PRNGKey(0)
    greedy = temp is None or float(temp) <= 0.0
    temp_arr = jnp.float32(1.0 if greedy else float(temp))
    limit = jnp.int32(max_steps if n_limit is None else n_limit)
    with tracer.start_span("pp.dispatch.fused_generate", attributes={"pp": self.n_stages, "batch": int(token.shape[0]), "max_steps": int(max_steps)}):
      return self._fused_generate_fn(
        self.stage_params, self.head, token, cache, start_pos, temp_arr, key, limit, int(max_steps), tuple(eos_ids), int(top_k), greedy
      )
