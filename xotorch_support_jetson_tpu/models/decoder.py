"""The general decoder: one functional transformer family covering
llama 3/3.1/3.2/3.3, qwen-2.5, mistral, deepseek-r1-distills and phi-family
dense checkpoints.

Role parity with the reference's ``GeneralMHA``/``ShardTransformerDecoder``
(``general_mha.py:23-142``, ``llm_utils.py:286-440``): build and run only a
shard's ``[start_layer..end_layer]`` range; accept either token ids or an
injected hidden state from the previous pipeline stage; apply final norm +
LM head only on the last shard.

TPU-first design (deliberately different from the reference's per-layer
``nn.Module`` list):

- **Stacked layer params + ``lax.scan``**: every layer leaf carries a leading
  ``[n_shard_layers, ...]`` axis and the layer stack runs as a scan, so
  compile time is O(1) in depth (an 80-layer 70B shard traces one layer) and
  the layer axis is directly shardable for pipeline stages. The layer
  parameters are the scan's ``xs``; the PAGE POOL of the paged decode
  programs is not — it rides the scan's carry, stacked, and a layer writes
  and reads it by ``(layer, page)`` (``_scan_layers_over_pool``): as
  ``xs``/``ys`` every step moved the whole pool and computed nothing with it.
- **Fixed shapes everywhere**: prefill pads to a bucket, decode is [B, 1];
  the KV cache is a preallocated slot-indexed buffer functionally updated
  with ``dynamic_update_slice`` (donated by the engine between steps).
- **No materialized masks**: attention masks derive from absolute positions
  inside the op (see ops/attention.py).
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..inference.shard import Shard
from ..utils.programs import component_scope, tracked_jit
from ..ops.attention import gqa_attention
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope, apply_rope_interleaved, rope_attention_factor, rope_inv_freq
from .config import ModelConfig
from .quantize import qdot

Params = dict

# int8 matmul compute mode (models/quantize.py): "w8a16" upcasts weights next
# to the dot; "w8a8" also dynamically quantizes activations onto the int8 MXU
# path. Static at trace time.
QUANT_COMPUTE = os.getenv("XOT_TPU_QUANT_COMPUTE", "w8a16")


def _alora_delta(x: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
  """Per-row adapter-indexed low-rank delta (the Punica BGMV idea, ISSUE 15).

  x [B,S,D]; a [n_slots, D, r] / b [n_slots, r, O] are one layer's STACKED
  LoRA factors (inference/adapters.py keeps slot 0 all-zero = base model);
  ids [B] int32 is the TRACED per-row adapter slot — adapter mix changes
  never recompile, exactly the per-row-gamma philosophy. The gather
  materializes [B, D, r] + [B, r, O] per layer (rank r is small), and the
  scale is train/lora.py's fixed alpha = 2·rank ⇒ 2."""
  a_sel = jnp.take(a, ids, axis=0)  # [B, D, r]
  b_sel = jnp.take(b, ids, axis=0)  # [B, r, O]
  h = jnp.einsum("bsd,bdr->bsr", x, a_sel)
  return (jnp.einsum("bsr,bro->bso", h, b_sel) * 2.0).astype(x.dtype)


def _mm(x: jnp.ndarray, p: Params, name: str, compute: str = "") -> jnp.ndarray:
  """x @ p[name], transparently dequantizing int8 leaves (``<name>_scale``).

  ``compute`` (normally ``cfg.quant_compute``) selects the quantized matmul
  mode per-trace; "" falls back to the process-wide XOT_TPU_QUANT_COMPUTE.
  Because cfg is a STATIC jit argument, a caller that swaps the mode via
  ``dataclasses.replace(cfg, quant_compute=...)`` gets a fresh compiled
  program — mutating the module global would silently reuse stale traces."""
  if f"{name}_scale" in p:
    return qdot(x, p[name], p[f"{name}_scale"], compute or QUANT_COMPUTE)
  return x @ p[name]


# ---------------------------------------------------------------- KV cache


def kv_quant_mode(cfg: ModelConfig, quant: str | None = None) -> str:
  """Resolve the KV-cache quantization mode: explicit arg wins, else the
  ``XOT_TPU_KV_QUANT`` env ("", "int8" or "int4"). MLA (deepseek) caches the
  latent — already 9-71× smaller than per-head K/V — and reconstructs BOTH k
  and v from it, so quantization there is all risk and little bandwidth; it
  stays in model dtype. "int4" (ISSUE 11) packs two code nibbles per byte
  along the head dim (models/quantize.py quantize_kv_int4): token-exact vs
  its OWN quantized reference, halving cache/page/host-tier/wire bytes
  again vs int8."""
  mode = os.getenv("XOT_TPU_KV_QUANT", "") if quant is None else quant
  if mode not in ("", "int8", "int4"):
    raise ValueError(f"XOT_TPU_KV_QUANT supports '', 'int8' or 'int4'; got {mode!r}")
  return "" if cfg.is_mla else mode


def pool_kv_quant(pool: Params, cfg: ModelConfig) -> str:
  """KV quant mode a cache/pool dict ENCODES ("", "int8", "int4") — the
  one place the halved-code-axis detection idiom lives for whole-pool
  callers (the fused program wrappers resolving dispatch verdicts; the
  per-layer steps detect against their activation widths instead, since a
  scanned layer slice has no cfg-relative geometry)."""
  if "k_scale" not in pool:
    return ""
  return "int4" if jnp.shape(pool["k"])[-1] * 2 == cfg.cache_k_dim else "int8"


def init_kv_cache(cfg: ModelConfig, n_shard_layers: int, batch: int, max_seq: int, dtype=None, quant: str | None = None) -> Params:
  """Slot-indexed KV cache: slot j holds the KV of absolute position j.

  Geometry comes from the config: GQA heads for dense models; for MLA
  (deepseek) the cache is the *latent* — "k" holds the shared kv latent
  (kv_lora_rank wide), "v" the MQA rope channel (qk_rope_head_dim), one
  head axis entry (see ops/attention.py mla_absorbed_attention).

  ``quant="int8"`` (default from ``XOT_TPU_KV_QUANT``; dense models only —
  see kv_quant_mode) stores int8 codes plus per-(token, head) f32 scale
  leaves ``k_scale``/``v_scale`` shaped [..., 1] — same rank and axis
  semantics as the codes, so slot/page/sp plumbing is layout-blind to them.
  ``quant="int4"`` packs two code nibbles per byte along the head dim (the
  code leaves carry a HALVED trailing axis; detection downstream compares
  it against the config's cache dims, the qdot idiom) with the same scale
  leaves.
  """
  if cfg.recurrent_layers:
    # One place for every slot-cache path (solo sessions, XOT_TPU_PAGED=0, draft caches): a state-space
    # layer's state lives beside the page pool (ops/paged.py init_paged_pool) and nowhere else.
    raise ValueError("a configuration with recurrent layers is served by the batched server over the page pool (XOT_TPU_BATCHED=1, XOT_TPU_PAGED=1); it has no slot-indexed KV cache")
  dtype = dtype or cfg.dtype
  mode = kv_quant_mode(cfg, quant)
  kd, vd = cfg.cache_k_dim, cfg.cache_v_dim
  if mode == "int4":
    if kd % 2 or vd % 2:
      raise ValueError(f"int4 KV needs even cache dims; got k={kd} v={vd}")
    kd, vd = kd // 2, vd // 2
  k_shape = (n_shard_layers, batch, max_seq, cfg.cache_kv_heads, kd)
  v_shape = (n_shard_layers, batch, max_seq, cfg.cache_kv_heads, vd)
  if mode:
    scale_shape = k_shape[:-1] + (1,)
    return {
      "k": jnp.zeros(k_shape, dtype=jnp.int8),
      "v": jnp.zeros(v_shape, dtype=jnp.int8),
      "k_scale": jnp.ones(scale_shape, dtype=jnp.float32),
      "v_scale": jnp.ones(scale_shape, dtype=jnp.float32),
    }
  return {"k": jnp.zeros(k_shape, dtype=dtype), "v": jnp.zeros(v_shape, dtype=dtype)}


@component_scope("xot.kv_write")
def _write_cache(cache: jnp.ndarray, new: jnp.ndarray, start: jnp.ndarray) -> jnp.ndarray:
  """cache [B,S,H,hd] ← new [B,Sn,H,hd] at per-row slot offsets start [B]."""

  def upd(c, n, s):
    return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

  return jax.vmap(upd)(cache, new, start)


# ---------------------------------------------------------------- init


def sliding_flags(cfg: ModelConfig, global_indices) -> jnp.ndarray:
  """Per-layer sliding-window flags [L] f32 from GLOBAL layer indices, read off the per-layer attention description
  (``cfg.attn_kind``) — the one encoding shared by init (below) and the checkpoint loader, for a model whose window
  rides a traced flag (``cfg.traced_window``: gemma2)."""
  return jnp.asarray([1.0 if cfg.attn_kind(i).window else 0.0 for i in global_indices], jnp.float32)


def init_shard_params(key: jax.Array, cfg: ModelConfig, shard: Shard, dtype=None) -> Params:
  """Random-init params for a shard (tests, dryruns, training-from-scratch).

  Layout (all layer leaves stacked on a leading [L] axis):
    embed      [V, D]            (first shard only)
    layers/attn_norm [L, D]
    layers/wq  [L, D, Hq*hd]  (+ bq [L, Hq*hd] if cfg.qkv_bias)
    layers/wk  [L, D, Hkv*hd] (+ bk)
    layers/wv  [L, D, Hkv*hd] (+ bv)
    layers/wo  [L, Hq*hd, D]
    layers/mlp_norm [L, D]
    layers/w_gate [L, D, F]   layers/w_up [L, D, F]   layers/w_down [L, F, D]
    final_norm [D]               (last shard only)
    lm_head    [D, V]            (last shard only; omitted when tied to a
                                  first-shard embed in the same params)

  A hybrid (``cfg.layer_types``) keeps each (mixer, FFN) pairing in a stack of
  its own (``cfg.layer_stack``): attention layers in ``layers`` (dense FFN) /
  ``moe_layers`` (experts), recurrent layers in ``ssm_layers`` /
  ``ssm_moe_layers``, each in model order (``_layer_runs`` interleaves them
  again). An expert stack's expert leaves hold ``cfg.n_held_experts`` experts;
  its router and bias stay ``cfg.n_experts`` wide. A "mamba" stack:
    ssm_layers/ssm_norm [Ls, D]    ssm_layers/w_z [Ls, D, di]  w_xbc [Ls, D, di + 2*N]  w_dt [Ls, D, H]
                                   (HF's one in_proj, cut at its three outputs: its 2*di + 2*N + H columns are
                                   no whole number of 128 lanes, and the TPU stores such a stack column-major
                                   and copies it whole, once a dispatch, for the dot — PERF.md §6, PR 34)
    ssm_layers/conv_w [Ls, K, di + 2*N]   conv_b [Ls, di + 2*N]
    ssm_layers/dt_bias, A_log, D [Ls, H] f32
    ssm_layers/gate_norm [Ls, di]  ssm_layers/w_out [Ls, di, D]
    + mlp_norm, w_gate, w_up, w_down as in ``layers``
  A "kda" stack (H heads, N key and P value channels a head, K taps):
    ssm_norm [L, D]   w_qkv [L, D, H*(2N+P)] (q | k | v)   conv_w [L, K, H*(2N+P)]
    w_f [L, D, H*N], b_f [L, H*N] f32 (the decay gate)     w_bg [L, D, 2H] (β | output gate, one a head)
    o_norm [L, P] (the per-head output norm)               w_out [L, H*P, D]
    + mlp_norm and the FFN's leaves (dense or expert)
  A "gdn" stack (Gated DeltaNet: H heads, N key and P value channels a head, N != P as published):
    w_qkv [L, D, H*(2N+P)]   conv_w [L, K, H*(2N+P)]   w_ab [L, D, 2H] (the decay's step a | β's b, one a head)
    A_log, dt_bias [L, H] f32   w_z [L, D, H*P] (the output gate)   o_norm [L, P]   w_out [L, H*P, D]
  A "conv" stack (lfm2_moe's gated short convolution, K taps over D channels; no state matrix):
    ssm_norm [L, D]   w_in [L, D, 3D] (B | C | x)   conv_w [L, K, D] (no bias)   w_out [L, D, D]
  Without ``cfg.pre_norms`` no stack has ``attn_norm`` / ``ssm_norm`` / ``mlp_norm``; with ``cfg.post_norms`` each has
  ``post_attn_norm`` (``post_ssm_norm`` in a recurrent stack) and ``post_mlp_norm`` [L, D].
  """
  dtype = dtype or cfg.dtype
  L = shard.n_shard_layers
  D, F, V = cfg.dim, cfg.hidden_dim, cfg.vocab_size
  Kd = cfg.kv_dim
  keys = iter(jax.random.split(key, 32))

  def w(k, *shape, scale=None):
    scale = scale if scale is not None else 1.0 / jnp.sqrt(shape[-2] if len(shape) > 1 else shape[-1])
    return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)

  def attn_leaves(L, kind=None):
    if cfg.is_mla:
      H, qk, vh = cfg.n_heads, cfg.qk_head_dim, cfg.v_head_dim
      leaves = {
        "attn_norm": jnp.ones((L, D), dtype=dtype),
        "wkv_a": w(next(keys), L, D, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_a_norm": jnp.ones((L, cfg.kv_lora_rank), dtype=dtype),
        "wkv_b": w(next(keys), L, cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + vh)),
        "wo": w(next(keys), L, H * vh, D),
        "mlp_norm": jnp.ones((L, D), dtype=dtype),
      }
      if cfg.q_lora_rank:
        leaves["wq_a"] = w(next(keys), L, D, cfg.q_lora_rank)
        leaves["q_a_norm"] = jnp.ones((L, cfg.q_lora_rank), dtype=dtype)
        leaves["wq_b"] = w(next(keys), L, cfg.q_lora_rank, H * qk)
      else:
        leaves["wq"] = w(next(keys), L, D, H * qk)
      if cfg.mla_q_norm:
        leaves["q_norm"] = jnp.ones((L, qk), dtype=dtype)
      return leaves
    Qd = (kind.n_heads if kind is not None else cfg.n_heads) * cfg.head_dim  # a layer kind's own query heads (``cfg.layer_attn``)
    leaves = {
      "attn_norm": jnp.ones((L, D), dtype=dtype),
      "wq": w(next(keys), L, D, Qd),
      "wk": w(next(keys), L, D, Kd),
      "wv": w(next(keys), L, D, Kd),
      "wo": w(next(keys), L, Qd, D),
      "mlp_norm": jnp.ones((L, D), dtype=dtype),
    }
    if kind is not None and kind.out_gate:  # the head-wise gate on the attention output: one column a query head
      leaves["w_og"] = w(next(keys), L, D, kind.n_heads)
    if cfg.qkv_bias:
      leaves["bq"] = jnp.zeros((L, Qd), dtype=dtype)
      leaves["bk"] = jnp.zeros((L, Kd), dtype=dtype)
      leaves["bv"] = jnp.zeros((L, Kd), dtype=dtype)
    if cfg.qk_norm:  # qwen3 per-head q/k RMSNorm weights [hd]; OLMo 2's over the whole projections [Qd], [Kd]
      leaves["q_norm"] = jnp.ones((L, Qd if cfg.qk_norm_whole else cfg.head_dim), dtype=dtype)
      leaves["k_norm"] = jnp.ones((L, Kd if cfg.qk_norm_whole else cfg.head_dim), dtype=dtype)
    return leaves

  def dense_ffn(L):
    """The dense FFN's leaves; an ungated one (``cfg.ffn_gated`` false) has no ``w_gate``."""
    gate = {"w_gate": w(next(keys), L, D, F)} if cfg.ffn_gated else {}
    return {**gate, "w_up": w(next(keys), L, D, F), "w_down": w(next(keys), L, F, D)}

  def dense_stack(L):
    stack = {**attn_leaves(L), **dense_ffn(L)}
    if cfg.post_norms:  # gemma2's post-attention / post-feedforward norms
      stack["post_attn_norm"] = jnp.ones((L, D), dtype=dtype)
      stack["post_mlp_norm"] = jnp.ones((L, D), dtype=dtype)
    if cfg.sliding_window:
      stack["is_sliding"] = sliding_flags(cfg, range(shard.start_layer, shard.start_layer + L))
    return stack

  def expert_ffn(Lm):
    E, Eh, Fm, Fs = cfg.n_experts, cfg.n_held_experts, cfg.moe_hidden_dim, cfg.shared_expert_dim
    moe = {"w_router": w(next(keys), Lm, D, E)}
    if cfg.ffn_gated:
      moe["w_experts_gate"] = w(next(keys), Lm, Eh, D, Fm)
      moe["w_experts_up"] = w(next(keys), Lm, Eh, D, Fm)
    else:  # an ungated expert is two matrices, both stored [Fm, D] (ops/moe.py: the inner width never along the lanes); no ``w_shared_gate`` either
      moe["w_experts_up_t"] = w(next(keys), Lm, Eh, Fm, D, scale=D**-0.5)
    moe["w_experts_down"] = w(next(keys), Lm, Eh, Fm, D)
    if cfg.router_scoring == "sigmoid" and cfg.router_selection_bias:
      moe["router_bias"] = jnp.zeros((Lm, E), dtype=jnp.float32)
    if Fs:
      if cfg.ffn_gated:
        moe["w_shared_gate"] = w(next(keys), Lm, D, Fs)
      moe["w_shared_up"] = w(next(keys), Lm, D, Fs)
      moe["w_shared_down"] = w(next(keys), Lm, Fs, D)
      if cfg.shared_expert_gate:
        moe["w_shared_expert_gate"] = w(next(keys), Lm, D, 1)
    return moe

  def mamba_leaves(Ls):
    H, di, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
    # The Mamba-2 initialisation: decays -exp(A_log) in [-16, -1], steps softplus(dt_bias) log-uniform in [1e-3, 1e-1], skip D = 1.
    dt = jnp.exp(jax.random.uniform(next(keys), (Ls, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
      "ssm_norm": jnp.ones((Ls, D), dtype=dtype),
      "w_z": w(next(keys), Ls, D, di),
      "w_xbc": w(next(keys), Ls, D, C),
      "w_dt": w(next(keys), Ls, D, H),
      "conv_w": w(next(keys), Ls, cfg.ssm_conv, C, scale=cfg.ssm_conv**-0.5),
      "conv_b": jnp.zeros((Ls, C), dtype=dtype),
      "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
      "A_log": jnp.log(jax.random.uniform(next(keys), (Ls, H), jnp.float32, 1.0, 16.0)),
      "D": jnp.ones((Ls, H), jnp.float32),
      "gate_norm": jnp.ones((Ls, di), dtype=dtype),  # (with ``cfg.ssm_groups`` groups: each group's channels normed by themselves, under their slice of this gain)
      "w_out": w(next(keys), Ls, di, D),
    }

  def kda_leaves(Ls):
    H, N, P, C = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_conv_dim
    return {
      "ssm_norm": jnp.ones((Ls, D), dtype=dtype),
      "w_qkv": w(next(keys), Ls, D, C),
      "conv_w": w(next(keys), Ls, cfg.ssm_conv, C, scale=cfg.ssm_conv**-0.5),
      "w_f": w(next(keys), Ls, D, H * N),
      # the gate's bias N(-3, 2^2) spreads the channels' decays over all of (e^kda_lower_bound, 1), most of them slow
      "b_f": 2.0 * jax.random.normal(next(keys), (Ls, H * N), jnp.float32) - 3.0,
      "w_bg": w(next(keys), Ls, D, 2 * H),
      "o_norm": jnp.ones((Ls, P), dtype=dtype),
      "w_out": w(next(keys), Ls, H * P, D),
    }

  def gdn_leaves(Ls):
    H, P, C = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_conv_dim
    dt = jnp.exp(jax.random.uniform(next(keys), (Ls, H), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))  # Mamba-2's gate and its initialisation
    return {
      "w_qkv": w(next(keys), Ls, D, C),
      "conv_w": w(next(keys), Ls, cfg.ssm_conv, C, scale=cfg.ssm_conv**-0.5),
      "w_ab": w(next(keys), Ls, D, 2 * H),
      "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
      "A_log": jnp.log(jax.random.uniform(next(keys), (Ls, H), jnp.float32, 1.0, 16.0)),
      "w_z": w(next(keys), Ls, D, H * P),
      "o_norm": jnp.ones((Ls, P), dtype=dtype),
      "w_out": w(next(keys), Ls, H * P, D),
    }

  def conv_leaves(Ls):
    return {
      "ssm_norm": jnp.ones((Ls, D), dtype=dtype),
      "w_in": w(next(keys), Ls, D, 3 * D),
      "conv_w": w(next(keys), Ls, cfg.ssm_conv, D, scale=cfg.ssm_conv**-0.5),
      "w_out": w(next(keys), Ls, D, D),
    }

  def block_norms(stack: Params, n: int, mixer: str, ffn: bool = True) -> Params:
    """A hybrid stack's mixer leaves with the block's norms: those ahead of its two sublayers dropped without
    ``cfg.pre_norms``, those after them added with ``cfg.post_norms``; a step with no FFN (``ffn`` false) has the
    mixer's alone."""
    stack = {"mlp_norm": jnp.ones((n, D), dtype=dtype), **stack}
    if not cfg.pre_norms:
      stack = {name: leaf for name, leaf in stack.items() if name not in (f"{mixer}_norm", "mlp_norm")}
    if cfg.post_norms:
      stack |= {name: jnp.ones((n, D), dtype=dtype) for name in (f"post_{mixer}_norm", "post_mlp_norm")}
    return stack if ffn else {name: leaf for name, leaf in stack.items() if name not in ("mlp_norm", "post_mlp_norm")}

  params: Params = {}
  if cfg.mixed_layers:
    if not (shard.is_first_layer and shard.is_last_layer):
      raise ValueError("a configuration whose layers differ in kind (recurrent layers, attention kinds of different shapes) is built whole: its stacks do not split by a layer range")
    keys = iter(jax.random.split(next(keys), 96))  # up to four stacks of up to 17 drawn leaves
    names = [cfg.layer_stack(i) for i in range(cfg.n_layers)]
    recurrent_leaves = {"mamba": mamba_leaves, "kda": kda_leaves, "gdn": gdn_leaves, "conv": conv_leaves}.get(cfg.recurrent_kind)
    for name in dict.fromkeys(names):  # in the order the model meets them
      n, recurrent, ffn = names.count(name), name.startswith("ssm_"), cfg.ffn_kind(names.index(name))
      kind = cfg.layer_attn[names.index(name)] if cfg.layer_attn else None  # (None at a recurrent layer too)
      mixer = block_norms(recurrent_leaves(n), n, "ssm", ffn != "none") if recurrent else block_norms(attn_leaves(n, kind), n, "attn", ffn != "none")
      params[name] = {**mixer, **{"experts": expert_ffn, "dense": dense_ffn, "none": lambda n: {}}[ffn](n)}
  elif cfg.n_experts:
    # MoE model: dense prefix (layers [0, first_k_dense) globally), MoE rest.
    n_dense = min(max(cfg.first_k_dense - shard.start_layer, 0), L)
    Lm = L - n_dense
    if n_dense:
      params["layers"] = dense_stack(n_dense)
    moe_start = shard.start_layer + n_dense
    moe = {
      **({"is_sliding": sliding_flags(cfg, range(moe_start, moe_start + Lm))} if cfg.sliding_window else {}),
      **attn_leaves(Lm),
      **expert_ffn(Lm),
    }
    params["moe_layers"] = moe
  else:
    params["layers"] = dense_stack(L)
  if shard.is_first_layer:
    params["embed"] = w(next(keys), V, D, scale=0.02)
  if shard.is_last_layer:
    params["final_norm"] = jnp.ones((D,), dtype=dtype)
    if not (cfg.tied_embedding and shard.is_first_layer):
      params["lm_head"] = w(next(keys), D, V)
  return params


# ---------------------------------------------------------------- forward

# HF deepseek fixes the latent-norm eps at 1e-6 regardless of rms_norm_eps
# (DeepseekV2RMSNorm default in q_a_layernorm/kv_a_layernorm).
_MLA_NORM_EPS = 1e-6


@component_scope("xot.attn_proj")
def _mla_latents(x, p, cfg: ModelConfig, positions, inv_freq):
  """Multi-head latent attention projections (deepseek-v2/v3).

  Parity with HF ``DeepseekV2Attention``/``DeepseekV3Attention``: queries
  optionally LoRA-compressed (wq_a/q_a_norm/wq_b; direct wq when
  cfg.q_lora_rank == 0), KV compressed to a shared ``kv_lora_rank`` latent
  plus a single MQA rope channel; rope (interleaved pairing) applies only to
  the rope parts. Returns (q_nope [B,S,H,nope], q_pe [B,S,H,rope] roped,
  c_kv [B,S,rank] normed, k_pe [B,S,rope] roped).
  """
  B, S, D = x.shape
  H, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
  # LoRA adapters attach to the per-head q up-projection (wq or wq_b) and the
  # kv up-projection wkv_b (train/lora.py maps wv→wkv_b for MLA).
  if "wq_a" in p:
    ql = rms_norm(_mm(x, p, "wq_a", cfg.quant_compute), p["q_a_norm"], _MLA_NORM_EPS)
    q = _mm(ql, p, "wq_b", cfg.quant_compute)
    if "wq_b_lora_a" in p:
      q = q + ((ql @ p["wq_b_lora_a"]) @ p["wq_b_lora_b"]) * 2.0
  else:
    q = _mm(x, p, "wq", cfg.quant_compute)
    if "wq_lora_a" in p:
      q = q + ((x @ p["wq_lora_a"]) @ p["wq_lora_b"]) * 2.0
  q = q.reshape(B, S, H, nope + rope)
  if "q_norm" in p:  # bailing_hybrid's use_qk_norm: each head's nope+rope channels normed before rope (cfg.mla_q_norm)
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
  q_nope, q_pe = q[..., :nope], q[..., nope:]

  kv_a = _mm(x, p, "wkv_a", cfg.quant_compute)  # [B, S, kv_lora_rank + rope]
  c_kv = rms_norm(kv_a[..., : cfg.kv_lora_rank], p["kv_a_norm"], _MLA_NORM_EPS)

  m = rope_attention_factor(cfg)
  q_pe = apply_rope_interleaved(q_pe, positions, inv_freq, m)
  k_pe = apply_rope_interleaved(kv_a[..., cfg.kv_lora_rank :][:, :, None, :], positions, inv_freq, m)[:, :, 0, :]
  return q_nope, q_pe, c_kv, k_pe


def _mla_w_kv_b(p, dtype):
  """The kv_b up-projection with int8/int4 scales / LoRA folded in
  ([rank, H*(nope+v)])."""
  w = p["wkv_b"]
  if "wkv_b_scale" in p:
    from .quantize import dequantize_leaf

    w = dequantize_leaf(w, p["wkv_b_scale"], p["kv_a_norm"].shape[-1], dtype)
  if "wkv_b_lora_a" in p:
    w = w.astype(dtype) + (p["wkv_b_lora_a"] @ p["wkv_b_lora_b"]).astype(dtype) * 2.0
  return w


@component_scope("xot.attn_proj")
def _mla_qkv(x, p, cfg: ModelConfig, positions, inv_freq):
  """Naive (non-absorbed) MLA q/k/v — the cache-less/training path."""
  B, S, D = x.shape
  H, nope, rope = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
  q_nope, q_pe, c_kv, k_pe = _mla_latents(x, p, cfg, positions, inv_freq)
  kv = (c_kv @ _mla_w_kv_b(p, x.dtype)).reshape(B, S, H, nope + cfg.v_head_dim)
  k_nope, v = kv[..., :nope], kv[..., nope:]
  q = jnp.concatenate([q_nope, q_pe], axis=-1)
  k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, rope))], axis=-1)
  return q, k, v


@component_scope("xot.attn_proj")
def _dense_qkv(x, p, cfg: ModelConfig, positions, inv_freq, adapter_ids=None):
  """Dense-attention q/k/v projections (+LoRA, qkv bias, rope applied).

  x [B,S,D] → q [B,S,Hq,hd], k/v [B,S,Hkv,hd]. Shared by the contiguous-cache
  layer step below and the paged decode step (``_paged_layer_step``).

  ``adapter_ids`` [B] int32 (ISSUE 15): per-row MULTI-LoRA application from
  the stacked ``*_alora_a``/``*_alora_b`` leaves (inference/adapters.py
  installs them on the LORA_TARGETS projections; slot 0 is all-zero = base).
  None skips the hook entirely — base serving never pays the gather.
  """
  B, S, _ = x.shape
  # The layer's own attention description, where the layer loop handed one over (``_scan_layers_over_pool``: a model
  # whose attention kinds differ in shape): its query heads, and its rope's table of the program's ``{kind: table}``.
  kind = p.get("attn_kind")
  n_heads, rope = (kind.n_heads, kind) if kind is not None else (cfg.n_heads, cfg)
  if isinstance(inv_freq, dict):
    inv_freq = inv_freq.get(kind)  # (a kind without rope has no table)
  q = _mm(x, p, "wq", cfg.quant_compute)
  k = _mm(x, p, "wk", cfg.quant_compute)
  v = _mm(x, p, "wv", cfg.quant_compute)
  # Keep the three flat activations as the dots made them. Left free, XLA:TPU
  # folds the head reshape below into the projection's dot, wants the weight
  # K-minor for the dot it then has, and pays for it with a relayout of the
  # stacked wq/wk once a dispatch plus a copy of the layer's slices in every
  # layer of every step — each q/k/v weight byte moved three times, 1.5 ms of
  # a 14.6 ms Mistral-7B decode step (PERF.md §6, PR 33; pinned in
  # tests/test_tpu_compile.py). Behind the barrier the layer scan's slice of
  # each leaf is read inside its dot, as it is for wo and w_gate.
  q, k, v = jax.lax.optimization_barrier((q, k, v))
  # LoRA adapters (train/lora.py): alpha = 2·rank, so the scale is always 2.
  if "wq_lora_a" in p:
    q = q + ((x @ p["wq_lora_a"]) @ p["wq_lora_b"]) * 2.0
  if "wv_lora_a" in p:
    v = v + ((x @ p["wv_lora_a"]) @ p["wv_lora_b"]) * 2.0
  if adapter_ids is not None and "wq_alora_a" in p:
    q = q + _alora_delta(x, p["wq_alora_a"], p["wq_alora_b"], adapter_ids)
  if adapter_ids is not None and "wv_alora_a" in p:
    v = v + _alora_delta(x, p["wv_alora_a"], p["wv_alora_b"], adapter_ids)
  if "bq" in p:
    q = q + p["bq"]
    k = k + p["bk"]
    v = v + p["bv"]
  if cfg.qk_norm_whole:  # OLMo 2: RMSNorm over the whole q and k projections, before the split into heads
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.norm_eps)
  q = q.reshape(B, S, n_heads, cfg.head_dim)
  k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
  v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
  if "q_norm" in p and not cfg.qk_norm_whole:  # qwen3: per-head RMSNorm on q/k before rope
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k = rms_norm(k, p["k_norm"], cfg.norm_eps)
  if cfg.attn_multiplier:
    # granite's softmax scale, as a factor on q over the cores' own 1/sqrt(hd): the attention paths and the
    # Pallas kernels keep one scale (``plain_attention`` stays true). 1/64 over 1/8 is 0.125, exact in bf16.
    q = q * jnp.asarray(cfg.attn_multiplier * cfg.head_dim**0.5, q.dtype)
  if not (cfg.use_rope and (kind is None or kind.rope)):  # "nope", the model's or this kind of layer's: no position term; causality alone orders the tokens
    return q, k, v
  m = rope_attention_factor(rope)
  q = apply_rope(q, positions, inv_freq, m)
  k = apply_rope(k, positions, inv_freq, m)
  return q, k, v


def _mlp_act(x, cfg: ModelConfig):
  if cfg.mlp_act == "gelu_tanh":  # gemma2's gelu_pytorch_tanh
    return jax.nn.gelu(x.astype(jnp.float32), approximate=True)
  if cfg.mlp_act == "relu2":  # nemotron_h's: relu(x)², in float32
    return jnp.square(jax.nn.relu(x.astype(jnp.float32)))
  return jax.nn.silu(x.astype(jnp.float32))


def _layer_window(p) -> int:
  """The static window of the layer whose parameters ``p`` are (0: none): its ``AttnKind``'s, where the layer loop
  handed one over — the Pallas kernels' operand."""
  kind = p.get("attn_kind")
  return kind.window if kind is not None else 0


def _attn_opts(cfg: ModelConfig, layer_sliding=None, kind=None) -> dict:
  """Attention kwargs of the XLA cores that a config implies: gemma2's scale override and logit softcap, and the
  layer's window — static where the layer loop handed the layer's ``AttnKind`` over (``kind``), else gemma2's, which
  rides a per-layer traced flag (``layer_sliding``: the stack's ``is_sliding`` leaf)."""
  opts: dict = {}
  if kind is not None and kind.window:
    opts["sliding_window"] = kind.window
  if cfg.query_pre_attn_scalar:
    opts["scale"] = 1.0 / cfg.query_pre_attn_scalar**0.5
  if cfg.attn_logit_softcap:
    opts["logit_softcap"] = cfg.attn_logit_softcap
  if cfg.sliding_window and layer_sliding is not None:
    # Traced per-layer window: huge (== no-op) on global-attention layers.
    opts["sliding_window"] = jnp.where(layer_sliding > 0, cfg.sliding_window, jnp.int32(2**30))
  return opts


def _residual(h, out, cfg: ModelConfig):
  """``h + out``, a block's output scaled by granite's ``residual_multiplier`` first."""
  if cfg.residual_multiplier != 1.0:  # the multiplier itself stays float32: 0.22 rounded to bfloat16 is 0.2197, in every block
    out = (out.astype(jnp.float32) * cfg.residual_multiplier).astype(out.dtype)
  return h + out


# The nonlinearity of a head-wise gate on a softmax-attention layer's output (``AttnKind.out_gate``, leaf ``w_og``): its
# one owner. (The row that brought the gate states ``gating: true`` and no function; softplus is assumed.)
_out_gate = jax.nn.softplus


@component_scope("xot.attn_proj")
def _attn_out(h, x, attn, p, cfg: ModelConfig):
  """An attention layer's tail, shared by every layer step: attn [B, S, Hq, hd] of the normed input x → the head-wise
  output gate where the layer has one (one scalar a head from x, in float32), ``wo``, the post-norm where the block has
  one, the residual."""
  B, S = h.shape[:2]
  if "w_og" in p:
    gate = _out_gate(jax.lax.dot_general(x, p["w_og"], (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32))  # [B, S, Hq]
    attn = (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)
  attn_out = _mm(attn.reshape(B, S, -1), p, "wo", cfg.quant_compute)
  if "post_attn_norm" in p:  # gemma2's post-attention layernorm; OLMo 2's only one
    attn_out = rms_norm(attn_out, p["post_attn_norm"], cfg.norm_eps)
  return _residual(h, attn_out, cfg)


def _routing_args(p, cfg: ModelConfig) -> dict:
  """How an expert layer's router draws its choice (ops/moe.py ``route`` / ``moe_ffn``), from the configuration."""
  return dict(
    k=cfg.n_active_experts, scoring=cfg.router_scoring, norm_topk=cfg.norm_topk_prob, selection_bias=p.get("router_bias"), scale=cfg.routed_scaling_factor,
    n_group=cfg.n_group, topk_group=cfg.topk_group, group_mode=cfg.group_mode,
  )


def _route_ahead(x, p, cfg: ModelConfig):
  """The layer's routing drawn from ``x`` [B, S, D], the ATTENTION's normed input, where the model's router reads it
  (``cfg.router_input`` "attn": smallthinker) — ahead of the attention, under ``xot.moe_router``; the layer step hands
  it across the attention to ``_mlp_block``, whose experts read the stream after the attention's residual. None for
  every other model and for a layer without experts: ``_mlp_block`` then routes from its own input."""
  if cfg.router_input != "attn" or "w_router" not in p:
    return None
  from ..ops.moe import route

  return route(x.reshape(-1, x.shape[-1]), p["w_router"], **_routing_args(p, cfg))


def _mlp_block(h, p, cfg: ModelConfig, routed=None):
  """Post-attention norm + FFN (dense or MoE+shared-expert), gated or — ``cfg.ffn_gated`` false — two matrices with the
  nonlinearity between them. Returns (h, aux, visited): the router's auxiliary loss and the number of distinct held
  experts the rows chose (0 and 0 for a dense FFN). ``routed``: the layer's routing where it was drawn ahead of the
  attention (``_route_ahead``). A layer step with no FFN at all (``cfg.layer_ffn`` "none": its stack holds no FFN leaf)
  is over with its mixer's residual: ``h`` comes back as it came, and no expert is visited."""
  B, S, D = h.shape
  aux, visited = jnp.float32(0.0), jnp.int32(0)
  if "w_down" not in p and "w_experts_down" not in p:
    return h, aux, visited
  with jax.named_scope("xot.ffn"):
    x = rms_norm(h, p["mlp_norm"], cfg.norm_eps) if "mlp_norm" in p else h  # (absent: a block whose norms follow its sublayers)
  if "w_experts_down" in p:  # routed MoE FFN (ops/moe.py) + optional shared expert
    from ..ops.moe import EXPERT_ACTS, moe_ffn

    names = ("w_experts_gate", "w_experts_up", "w_experts_down") if cfg.ffn_gated else ("w_experts_up_t", "w_experts_down")
    xt = x.reshape(B * S, D)
    if "expert_layer" in p:
      # The grouped form: the layer loop asked ops/moe.py ``ffn_form`` and handed the stack's expert leaves over whole
      # (``_whole_expert_leaves``) — codes as stored with their scale leaves, and the layer to take.
      experts = [p[name] for name in names]
      form = dict(layer=p["expert_layer"], scales=tuple(p[f"{name}_scale"] for name in names) if f"{names[0]}_scale" in p else None)
    else:
      # The block form, over this layer's leaves: int8/int4 expert weights are dequantized next to the einsum (XLA
      # fuses the scale multiply into the operand read — w8a16-style).
      def expert_w(name):
        if f"{name}_scale" not in p:
          return p[name]
        from .quantize import dequantize_leaf

        return dequantize_leaf(p[name], p[f"{name}_scale"], cfg.moe_hidden_dim if name == "w_experts_down" else D, h.dtype)

      with jax.named_scope("xot.moe_experts"):  # the dequantised expert slabs are the experts' cost
        experts, form = [expert_w(name) for name in names], {}
    out, aux, visited = moe_ffn(
      xt,
      p["w_router"],
      *(experts if cfg.ffn_gated else (None, *experts)),  # an ungated expert has no gate matrix
      **_routing_args(p, cfg),
      capacity_factor=cfg.moe_capacity_factor,
      **form,
      **({"held": cfg.experts_held} if cfg.experts_held else {}),
      act=cfg.expert_act,
      routed=routed,
    )
    if "w_shared_down" in p:
      with jax.named_scope("xot.moe_shared"):
        shared = EXPERT_ACTS[cfg.expert_act](_mm(xt, p, "w_shared_gate" if cfg.ffn_gated else "w_shared_up", cfg.quant_compute).astype(jnp.float32)).astype(h.dtype)
        if cfg.ffn_gated:
          shared = shared * _mm(xt, p, "w_shared_up", cfg.quant_compute)
        shared = _mm(shared, p, "w_shared_down", cfg.quant_compute)
        if "w_shared_expert_gate" in p:  # qwen2-moe sigmoid-gated shared expert
          shared = shared * jax.nn.sigmoid((xt @ p["w_shared_expert_gate"]).astype(jnp.float32)).astype(h.dtype)
        out = out + shared
    h = h + out.reshape(B, S, D)
  else:
    with jax.named_scope("xot.ffn"):
      gated = _mlp_act(_mm(x, p, "w_gate" if cfg.ffn_gated else "w_up", cfg.quant_compute), cfg).astype(h.dtype)
      if cfg.ffn_gated:
        gated = gated * _mm(x, p, "w_up", cfg.quant_compute)
      out = _mm(gated, p, "w_down", cfg.quant_compute)
      if "post_mlp_norm" in p:  # gemma2's post-feedforward layernorm; OLMo 2's only one
        out = rms_norm(out, p["post_mlp_norm"], cfg.norm_eps)
      h = _residual(h, out, cfg)
  return h, aux, visited


# ------------------------------------------------ state-space (Mamba-2) mixer
# (granitemoehybrid's "mamba" layers, HF ``GraniteMoeHybridMambaLayer``, one
# group; nemotron_h's ``M`` blocks, ``cfg.ssm_groups`` groups.) [z | xBC | dt] = u W_in (three leaves here, w_z | w_xbc | w_dt); xBC through a causal depthwise convolution
# of ``ssm_conv`` taps and silu; [x | B | C] = xBC, B and C in G groups of N
# (head h reads group h // (H / G)); per head Δ = softplus(dt +
# dt_bias), a = exp(-Δ exp(A_log)); S_t = a_t S_{t-1} + Δ_t x_t ⊗ B_t;
# y_t = S_t C_t + D x_t; out = rms_group(y ⊙ silu(z)) W_out, the norm over
# each group's di / G channels by itself (all of di with one group). What a row keeps
# between calls is S [H, P, N] in float32 and the last ``ssm_conv - 1`` rows of
# the pre-convolution xBC: the two per-slot leaves ``ssm`` and ``conv`` that
# ride beside the K/V pages in the page pool (ops/paged.py init_paged_pool).
# Prefill scans a prompt in chunks of ``ssm_chunk`` from a state and returns
# the state after the row's last real token; decode is one recurrence step,
# and who steps the ``ssm`` leaf there is ``ops/ssm.py ssm_state_step``: the
# XLA expression, or on a TPU one Mosaic kernel that passes over the leaf's
# layer once — ``_ssm_decode_step`` keeps the rest (norm, projections, the
# convolution and its ``conv`` leaf, softplus, exp, skip, gate, norm).
# Decays, steps and the state are float32, in the pool and in both forms of
# the decode step; the chunk scan's matrix products take their operands in
# the model dtype and accumulate in float32.


@component_scope("xot.ssm_proj")
def _ssm_in(h, p, cfg: ModelConfig):
  """Norm and input projection: h [B,S,D] → z [B,S,di], xBC [B,S,di+2GN], dt [B,S,H]."""
  u = rms_norm(h, p["ssm_norm"], cfg.norm_eps)
  return tuple(_mm(u, p, name, cfg.quant_compute) for name in ("w_z", "w_xbc", "w_dt"))


@component_scope("xot.ssm_proj")
def _ssm_out(h, y, p, cfg: ModelConfig):
  out = _mm(y, p, "w_out", cfg.quant_compute)
  if "post_ssm_norm" in p:  # a block whose norm follows the mixer (cfg.post_norms)
    out = rms_norm(out, p["post_ssm_norm"], cfg.norm_eps)
  return _residual(h, out, cfg)


def _ssm_conv(xbc, conv0, p, act=jax.nn.silu):
  """The causal depthwise convolution as ``K`` shifted adds, then ``act``
  (None: none — the gated short convolution's) over the float32 sum. xbc
  [B,S,C]; conv0 [B,K-1,C] the rows before it (zeros at a prompt's start).
  Returns (activated [B,S,C], the padded input [B, K-1+S, C])."""
  K, S = p["conv_w"].shape[0], xbc.shape[1]
  xp = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
  acc = p["conv_b"].astype(jnp.float32) if "conv_b" in p else 0.0  # (a "kda", "gdn" or "conv" layer's convolution has no bias)
  for j in range(K):
    acc = acc + xp[:, j : j + S].astype(jnp.float32) * p["conv_w"][j].astype(jnp.float32)
  return (acc if act is None else act(acc)).astype(xbc.dtype), xp


def _conv_tail(xp, S: int, seq_lens):
  """The rows the convolution still needs after each row's ``seq_lens`` tokens (None: all S) of its padded input
  ``xp`` [B, K-1+S, C]: the K-1 before position ``seq_lens``, so padding is cut from the tail."""
  if seq_lens is None:
    return xp[:, S:]
  return jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, xp.shape[1] - S, axis=0))(xp, seq_lens)


def _step_conv(pool: Params, xp, conv0, layer, active) -> Params:
  """``pool`` with the ``conv`` leaf of recurrent layer ``layer`` moved on by the one token of ``xp`` [B, K, C] for the
  ``active`` rows; the others keep ``conv0`` bit for bit."""
  return {**pool, "conv": jax.lax.dynamic_update_index_in_dim(pool["conv"], jnp.where(active[:, None, None], xp[:, 1:].astype(conv0.dtype), conv0), layer, 0)}


def _ssm_split(xbc, cfg: ModelConfig):
  """Activated xBC [..., di+2GN] → x [..., H, P], B [..., G, N], C [..., G, N]."""
  di, G, N = cfg.ssm_inner, cfg.ssm_groups, cfg.ssm_state
  x = xbc[..., :di].reshape(*xbc.shape[:-1], cfg.ssm_heads, cfg.ssm_head_dim)
  return x, xbc[..., di : di + G * N].reshape(*xbc.shape[:-1], G, N), xbc[..., di + G * N :].reshape(*xbc.shape[:-1], G, N)


def _ssm_gate(y, x, z, p, cfg: ModelConfig):
  """Skip, gate, then the norm over each group's ``di / G`` channels by itself (one group: over all ``di``; the gain
  is one [di] leaf either way): y, x [..., H, P] f32, z [..., di]."""
  y = y + p["D"].astype(jnp.float32)[:, None] * x
  g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
  grouped = lambda t: t.reshape(*t.shape[:-1], cfg.ssm_groups, -1)  # noqa: E731
  return rms_norm(grouped(g), grouped(p["gate_norm"]), cfg.norm_eps).reshape(z.shape).astype(z.dtype)


def _ssm_chunk_scan(x, dt, a_log, bm, cm, state, chunk: int):
  """The recurrence over a sequence, a chunk at a time (the SSD form).

  x [B,S,H,P]; dt [B,S,H] f32 (0 at a padded position: the state passes it
  unchanged); a_log [H] f32 = -exp(A_log); bm, cm [B,S,G,N], G groups of which
  head h reads group h // (H/G); state [B,H,P,N] f32. Returns (y [B,S,H,P] f32
  without the skip, state after position S-1).
  Inside a chunk of L positions y is a masked [L, L] product, as attention
  is; between chunks only the state is carried, so the [B, H, L, L] decay
  term exists for one chunk at a time. Several groups carry the group axis
  through every product, the heads as [G, H/G]; one group (granite) keeps
  the expressions without it, because the grouped ones at G = 1 are ANOTHER
  program to XLA:TPU (granite's 8 x 1024 prefill, AOT for a described v5e:
  11,590 lines of optimised text for 11,736, 1,202,173,440 B of temporaries for
  1,206,850,560, 43.83 GB accessed for 44.04, the same flops — PERF.md §6, PR 53)
  and no chip run has said which of the two is the faster."""
  B, S, H, P = x.shape
  G = bm.shape[2]
  L = min(chunk, S)
  pad = -S % L
  if G == 1:
    bm, cm = bm[:, :, 0], cm[:, :, 0]
  if pad:
    x, dt, bm, cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in (x, dt, bm, cm))
  chunks = lambda t: jnp.moveaxis(t.reshape(B, -1, L, *t.shape[2:]), 1, 0)  # noqa: E731 — [c, B, L, ...]
  tri = jnp.tril(jnp.ones((L, L), bool))
  mm = x.dtype

  def body(state, per_chunk):
    xc, dtc, bc, cc = per_chunk
    cs = jnp.cumsum(dtc * a_log, axis=1)  # [B,L,H], falling: log of the decay from the chunk's start through l
    xdt = (xc.astype(jnp.float32) * dtc[..., None]).astype(mm)
    seg = cs[:, :, None, :] - cs[:, None, :, :]  # [B,l,s,H]: log decay over (s, l]
    decay = jnp.where(tri[None, :, :, None], jnp.exp(jnp.where(tri[None, :, :, None], seg, 0.0)), 0.0)
    to_end = jnp.exp(cs[:, -1:, :] - cs)  # [B,L,H]
    if G == 1:
      cb = jnp.einsum("bln,bsn->bls", cc, bc, preferred_element_type=jnp.float32)
      y = jnp.einsum("blsh,bshp->blhp", (cb[..., None] * decay).astype(mm), xdt, preferred_element_type=jnp.float32)
      y = y + jnp.einsum("bln,bhpn->blhp", cc, state.astype(mm), preferred_element_type=jnp.float32) * jnp.exp(cs)[..., None]
      grown = jnp.einsum("bln,blhp->bhpn", bc, (xdt.astype(jnp.float32) * to_end[..., None]).astype(mm), preferred_element_type=jnp.float32)
    else:
      heads = lambda t, axis: t.reshape(*t.shape[:axis], G, H // G, *t.shape[axis + 1 :])  # noqa: E731 — the head axis as [G, H/G]
      cb = jnp.einsum("blgn,bsgn->blsg", cc, bc, preferred_element_type=jnp.float32)
      y = jnp.einsum("blsgh,bsghp->blghp", (cb[..., None] * heads(decay, 3)).astype(mm), heads(xdt, 2), preferred_element_type=jnp.float32)
      y = y + jnp.einsum("blgn,bghpn->blghp", cc, heads(state.astype(mm), 1), preferred_element_type=jnp.float32) * heads(jnp.exp(cs), 2)[..., None]
      y = y.reshape(B, L, H, P)
      grown = jnp.einsum("blgn,blghp->bghpn", bc, heads((xdt.astype(jnp.float32) * to_end[..., None]).astype(mm), 2), preferred_element_type=jnp.float32).reshape(state.shape)
    return jnp.exp(cs[:, -1, :])[:, :, None, None] * state + grown, y

  state, y = jax.lax.scan(body, state, tuple(chunks(t) for t in (x, dt, bm, cm)))
  return jnp.moveaxis(y, 0, 1).reshape(B, S + pad, H, P)[:, :S], state


def _ssm_layer(h, p, cfg: ModelConfig, ssm0, conv0, seq_lens=None):
  """One state-space layer step over a sequence — the mixer and the FFN its
  stack pairs it with, if any (``_mlp_block``): h [B,S,D], the rows' states ssm0
  [B,H,P,N] f32 and conv0 [B,K-1,C] → (h, ssm, conv) after each row's
  ``seq_lens`` tokens (None: all S). Positions past a row's length are
  padding: Δ = 0 there and the convolution's tail is cut at the length, so
  padding moves neither leaf."""
  B, S, _ = h.shape
  z, xbc, dt = _ssm_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    xbc, xp = _ssm_conv(xbc, conv0, p)
    x, bm, cm = _ssm_split(xbc, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    if seq_lens is not None:
      dt = jnp.where((jnp.arange(S, dtype=jnp.int32)[None, :] < seq_lens[:, None])[..., None], dt, 0.0)
    conv = _conv_tail(xp, S, seq_lens)
    y, ssm = _ssm_chunk_scan(x, dt, -jnp.exp(p["A_log"].astype(jnp.float32)), bm, cm, ssm0, cfg.ssm_chunk)
    y = _ssm_gate(y, x.astype(jnp.float32), z, p, cfg)
  h, *_ = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, ssm, conv.astype(conv0.dtype)


def _ssm_decode_step(h, pool, p, layer, active, cfg: ModelConfig, use_kernel: bool = False):
  """One recurrence step of one state-space layer for every slot row: h
  [B,1,D], ``pool`` the carried dict whose leaves ``ssm`` [Ls,B,H,P,N] and
  ``conv`` [Ls,B,K-1,C] are read and written in place at ``layer``. A row
  that is not ``active`` keeps both leaves bit for bit. The state's own step
  — decay, increment, contraction with C — is ``ops/ssm.py ssm_state_step``'s,
  which passes over the leaf once where ``use_kernel`` and the leaf allow.
  Returns (h, pool, the experts its FFN visited: ``_mlp_block``)."""
  from ..ops.ssm import ssm_state_step

  z, xbc, dt = _ssm_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    conv0 = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)
    xbc, xp = _ssm_conv(xbc, conv0, p)
    x, bm, cm = _ssm_split(xbc[:, 0], cfg)
    x = x.astype(jnp.float32)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # [B,H]
    a = jnp.exp(dt * -jnp.exp(p["A_log"].astype(jnp.float32)))
    # One group: B and C [B, N], every head's; several: [B, H, N], each head its group's (32 KB a row beside a 2.1 MB state).
    per_head = (lambda t: t[:, 0]) if cfg.ssm_groups == 1 else (lambda t: jnp.repeat(t, cfg.ssm_heads // cfg.ssm_groups, axis=1))
    ssm, y = ssm_state_step(pool["ssm"], layer, a, dt[:, :, None] * x, per_head(bm.astype(jnp.float32)), per_head(cm.astype(jnp.float32)), active, use_kernel)
    pool = _step_conv({**pool, "ssm": ssm}, xp, conv0, layer, active)
    y = _ssm_gate(y[:, None], x[:, None], z, p, cfg)
  h, _, visited = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, pool, visited


# ------------------------------------------- Kimi Delta Attention (KDA) mixer
# (bailing_hybrid's "kda" layers; Kimi Linear, arXiv:2510.26692.) Per head,
# with N key and P value channels:
#   [q~ | k~ | v~] = silu(conv(u W_qkv)) (a causal depthwise convolution of
#   ``ssm_conv`` taps);  q = l2norm(q~)/sqrt(N), k = l2norm(k~), v = v~;
#   g = kda_lower_bound * sigmoid(u W_f + b_f) in (kda_lower_bound, 0), the log
#   decay of each key channel, alpha = exp(g);  beta = sigmoid(u W_beta);
#   S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t;
#   out = (sigmoid(u W_g) [one a head] * rmsnorm_head(o_t)) W_out.
# The state rides the pool's ``ssm`` leaf as [H, P, N] (values x key channels:
# S^T, so the decay lies along the lanes as Mamba-2's does) in float32, the last
# ``ssm_conv - 1`` rows of the pre-convolution [q|k|v] its ``conv`` leaf. Decode
# is one delta-rule step (``ops/ssm.py kda_state_step``); prefill scans a prompt
# in chunks of ``ssm_chunk`` positions (``_kda_chunk_scan``). Gates, decays,
# norms, the state and every product with it are float32.


@component_scope("xot.ssm_proj")
def _kda_in(h, p, cfg: ModelConfig):
  """Norm and the input projections: h [B,S,D] → qkv [B,S,H(2N+P)], f [B,S,HN] (the decay gate), bg [B,S,2H] (β | output gate)."""
  u = rms_norm(h, p["ssm_norm"], cfg.norm_eps)
  return tuple(_mm(u, p, name, cfg.quant_compute) for name in ("w_qkv", "w_f", "w_bg"))


_L2_EPS = 1e-6


def _kda_gates(qkv, f, bg, p, cfg: ModelConfig):
  """Activated [q|k|v] [..., H(2N+P)], gate pre-activations f [..., HN], bg [..., 2H] → float32 q, k [..., H, N]
  (unit norm; q scaled by 1/sqrt(N)), v [..., H, P], g [..., H, N] the log decay, beta and the output gate [..., H]."""
  H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
  lead = qkv.shape[:-1]
  qkv = qkv.astype(jnp.float32)
  q, k, v = qkv[..., : H * N].reshape(*lead, H, N), qkv[..., H * N : 2 * H * N].reshape(*lead, H, N), qkv[..., 2 * H * N :].reshape(*lead, H, P)
  unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)  # noqa: E731
  g = cfg.kda_lower_bound * jax.nn.sigmoid(f.astype(jnp.float32) + p["b_f"].astype(jnp.float32)).reshape(*lead, H, N)
  bg = jax.nn.sigmoid(bg.astype(jnp.float32))
  return unit(q) * N**-0.5, unit(k), v, g, bg[..., :H], bg[..., H:]


def _kda_out(y, gate, p, cfg: ModelConfig, dtype):
  """The per-head norm of o [..., H, P] f32, the head-wise sigmoid gate [..., H], heads joined: [..., H*P] in ``dtype``."""
  y = rms_norm(y, p["o_norm"], cfg.norm_eps) * gate[..., None]
  return y.reshape(*y.shape[:-2], -1).astype(dtype)


def _kda_chunk_scan(q, k, v, g, beta, state, chunk: int):
  """The delta rule over a sequence, ``chunk`` positions at a time.

  q, k, g [B,S,H,N], v [B,S,H,P], beta [B,S,H], state [B,H,P,N], all float32;
  g = 0 and beta = 0 at a padded position (the state passes it unchanged).
  Returns (o [B,S,H,P], state after position S-1).

  Inside a chunk, with G_t the running sum of g from the chunk's start and
  S_0 the state there, the updates w_t = beta_t (v_t - S_{t-1} Diag(alpha_t) k_t)
  solve the unit lower-triangular system
    (I + Diag(beta) A) W = Diag(beta) (V - (K * e^G) S_0^T),   A[t,s] = sum_n k_t k_s e^(G_t - G_s)  (s < t),
  and o_t = S_0 (q_t * e^(G_t)) + sum_(s<=t) W_s (q_t . k_s e^(G_t - G_s)). The pairwise
  decays are factorised, e^(G_t) x e^(-G_s): |G| <= |kda_lower_bound| x chunk,
  which ``cfg.ssm_chunk`` holds under 80, so e^(-G_s) stays inside float32
  (at 17 positions of -5 it would not). The triangular inverse is the finite
  product (I - N)(I + N^2)(I + N^4)... of the nilpotent N = Diag(beta) A: matrix
  products only. Products are float32 at ``highest`` precision: the state a
  prompt leaves is what hundreds of decode steps start from."""
  B, S, H, _ = q.shape
  L = min(chunk, S)
  pad = -S % L
  if pad:
    q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in (q, k, v, g, beta))
  chunks = lambda t: jnp.moveaxis(t.reshape(B, -1, L, *t.shape[2:]), 1, 0)  # noqa: E731 — [c, B, L, ...]
  lower, eye = jnp.tril(jnp.ones((L, L), bool), -1), jnp.eye(L, dtype=jnp.float32)
  mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)

  def body(state, per_chunk):
    qc, kc, vc, gc, bc = per_chunk
    G = jnp.cumsum(gc, axis=1)  # [B,L,H,N], falling from 0
    grow, k_in = jnp.exp(G), kc * jnp.exp(-G)
    k_out, q_out = kc * grow, qc * grow
    A = jnp.where(lower, mm("blhn,bshn->bhls", k_out, k_in), 0.0) * jnp.moveaxis(bc, 1, 2)[..., None]  # N = Diag(beta) A
    inv, power = eye - A, A
    for _ in range(max(L - 1, 1).bit_length() - 1):
      power = mm("bhls,bhst->bhlt", power, power)
      inv = mm("bhls,bhst->bhlt", inv, eye + power)
    w = mm("bhls,bshp->blhp", inv, bc[..., None] * (vc - mm("blhn,bhpn->blhp", k_out, state)))
    o = mm("blhn,bhpn->blhp", q_out, state) + mm("bhls,bshp->blhp", jnp.where(lower | (eye > 0), mm("blhn,bshn->bhls", q_out, k_in), 0.0), w)
    to_end = jnp.exp(G[:, -1:] - G)  # [B,L,H,N], at most 1
    return state * grow[:, -1][:, :, None, :] + mm("bshp,bshn->bhpn", w, kc * to_end), o

  state, o = jax.lax.scan(body, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
  return jnp.moveaxis(o, 0, 1).reshape(B, S + pad, H, -1)[:, :S], state


def _kda_layer(h, p, cfg: ModelConfig, ssm0, conv0, seq_lens=None):
  """One KDA layer over a sequence, as ``_ssm_layer``: h [B,S,D], the rows' states ssm0 [B,H,P,N] f32 and conv0
  [B,K-1,C] → (h, ssm, conv) after each row's ``seq_lens`` tokens. At a padded position the log decay and beta are
  0 and the convolution's tail is cut at the length, so padding moves neither leaf."""
  S = h.shape[1]
  qkv, f, bg = _kda_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    qkv, xp = _ssm_conv(qkv, conv0, p)
    q, k, v, g, beta, gate = _kda_gates(qkv, f, bg, p, cfg)
    if seq_lens is not None:
      valid = jnp.arange(S, dtype=jnp.int32)[None, :] < seq_lens[:, None]
      g, beta = jnp.where(valid[..., None, None], g, 0.0), jnp.where(valid[..., None], beta, 0.0)
    conv = _conv_tail(xp, S, seq_lens)
    y, ssm = _kda_chunk_scan(q, k, v, g, beta, ssm0, cfg.ssm_chunk)
    y = _kda_out(y, gate, p, cfg, h.dtype)
  h, *_ = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, ssm, conv.astype(conv0.dtype)


def _kda_decode_step(h, pool, p, layer, active, cfg: ModelConfig, use_kernel: bool = False):
  """One delta-rule step of one KDA layer for every slot row, as ``_ssm_decode_step``: the leaves ``ssm`` and ``conv``
  of ``pool`` are read and written in place at ``layer``; a row that is not ``active`` keeps both bit for bit; the
  step passes over the leaf once where ``use_kernel`` and the leaf allow. Returns (h, pool, the experts its FFN visited)."""
  from ..ops.ssm import kda_state_step

  qkv, f, bg = _kda_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    conv0 = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)
    qkv, xp = _ssm_conv(qkv, conv0, p)
    q, k, v, g, beta, gate = _kda_gates(qkv[:, 0], f[:, 0], bg[:, 0], p, cfg)
    ssm, y = kda_state_step(pool["ssm"], layer, jnp.exp(g), beta, k, v, q, active, use_kernel)
    pool = _step_conv({**pool, "ssm": ssm}, xp, conv0, layer, active)
    y = _kda_out(y[:, None], gate[:, None], p, cfg, h.dtype)
  h, _, visited = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, pool, visited


# --------------------------------------------------- Gated DeltaNet (GDN) mixer
# (olmo_hybrid's "gdn" layers; Gated Delta Networks, arXiv:2412.06464, with the negative-eigenvalue β of
# arXiv:2411.12537.) Per head, with N key and P value channels (96 and 192 as published: the state is not square):
#   [q~ | k~ | v~] = silu(conv(x W_qkv));  q = l2norm(q~)/sqrt(N), k = l2norm(k~), v = v~;
#   g = -exp(A_log) * softplus(x W_a + dt_bias), ONE log decay a head with no lower bound, alpha = exp(g);
#   beta = gdn_beta_scale * sigmoid(x W_b);
#   S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T  (S [P, N]),  o_t = S_t q_t;
#   out = (rmsnorm_head(o_t) * silu(x W_z)) W_out — the norm first, the gate after.
# No norm ahead of the mixer: the block's one norm follows ``W_out`` (``_ssm_out``). The state rides the pool's ``ssm``
# leaf as [H, P, N] and is stepped by the delta rule's one owner, ``ops/ssm.py kda_state_step``, with alpha spread over
# the key channels; prefill scans ``ssm_chunk`` positions at a time (``_gdn_chunk_scan``).


@component_scope("xot.ssm_proj")
def _gdn_in(h, p, cfg: ModelConfig):
  """The input projections: h [B,S,D] → qkv [B,S,H(2N+P)], z [B,S,HP] (the output gate), ab [B,S,2H] (the decay's step | β)."""
  return tuple(_mm(h, p, name, cfg.quant_compute) for name in ("w_qkv", "w_z", "w_ab"))


def _gdn_gates(qkv, ab, p, cfg: ModelConfig):
  """Activated [q|k|v] [..., H(2N+P)] and the gates' pre-activations ab [..., 2H] → float32 q, k [..., H, N] (unit norm;
  q scaled by 1/sqrt(N)), v [..., H, P], g [..., H] the log decay (at most 0, unbounded below), beta [..., H]. (The
  split and the unit norms are ``_kda_gates``'s own lines: shared, they reorder that kind's lowered programs.)"""
  H, N, P = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
  lead = qkv.shape[:-1]
  qkv, ab = qkv.astype(jnp.float32), ab.astype(jnp.float32)
  q, k, v = qkv[..., : H * N].reshape(*lead, H, N), qkv[..., H * N : 2 * H * N].reshape(*lead, H, N), qkv[..., 2 * H * N :].reshape(*lead, H, P)
  unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)  # noqa: E731
  g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(ab[..., :H] + p["dt_bias"].astype(jnp.float32))
  return unit(q) * N**-0.5, unit(k), v, g, cfg.gdn_beta_scale * jax.nn.sigmoid(ab[..., H:])


def _gdn_out(y, z, p, cfg: ModelConfig, dtype):
  """The per-head norm of o [..., H, P] f32, then the gate silu(z) [..., H*P], heads joined: [..., H*P] in ``dtype``."""
  y = rms_norm(y, p["o_norm"], cfg.norm_eps) * jax.nn.silu(z.astype(jnp.float32)).reshape(y.shape)
  return y.reshape(z.shape).astype(dtype)


def _unit_lower_inverse(T, mm):
  """The inverse of unit lower-triangular T [..., L, L], L = 8 x a power of two, by matrix products (``mm``) alone: the
  8 x 8 diagonal blocks I + A by the finite product (I - A)(I + A^2)(I + A^4) of their nilpotent part, then pairs of
  inverted blocks a, d around T's block c merged as [[a, 0], [-d c a, d]]. (The product formula on a whole chunk of 64
  would carry powers up to A^32, whose entries reach 1e5 where the inverse's are of order 1: float32 cancels there.)"""
  L = T.shape[-1]
  blocks = lambda s: jnp.stack([T[..., i : i + s, i : i + s] for i in range(0, L, s)], axis=-3)  # noqa: E731 — [..., L/s, s, s]
  eye = jnp.eye(8, dtype=T.dtype)
  power = blocks(8) - eye
  inv = eye - power
  for _ in range(2):
    power = mm("...ij,...jk->...ik", power, power)
    inv = mm("...ij,...jk->...ik", inv, eye + power)
  s = 8
  while s < L:
    a, d = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
    c = -mm("...ij,...jk->...ik", mm("...ij,...jk->...ik", d, blocks(2 * s)[..., s:, :s]), a)
    inv = jnp.concatenate([jnp.concatenate([a, jnp.zeros_like(a)], axis=-1), jnp.concatenate([c, d], axis=-1)], axis=-2)
    s *= 2
  return inv[..., 0, :, :]


def _gdn_chunk_scan(q, k, v, g, beta, state, chunk: int):
  """The delta rule under a scalar decay over a sequence, ``chunk`` positions (8 x a power of two) at a time.

  q, k [B,S,H,N], v [B,S,H,P], g, beta [B,S,H], state [B,H,P,N], all float32; g = 0 and beta = 0 at a padded position
  (the state passes it unchanged). Returns (o [B,S,H,P], state after position S-1).

  As ``_kda_chunk_scan``, with one G_t = sum of g a head: the updates W solve the unit lower-triangular system
    (I + Diag(beta) (K K^T * D)) W = Diag(beta) (V - e^G * (K S_0^T)),   D[t,s] = e^(G_t - G_s)  (s <= t),
  and o_t = e^(G_t) S_0 q_t + sum_(s<=t) W_s (q_t . k_s) D[t,s]. The pairwise decays are taken AS differences on the
  [L, L] lower triangle — a scalar a head makes that one small matrix — so nothing exceeds 1 whatever the gate says:
  this kind's log decay has no lower bound, and the factorised form e^(G_t) x e^(-G_s) would leave float32 at the
  third position of -30. Products are float32 at ``highest`` precision, as the KDA scan's."""
  B, S, H, _ = q.shape
  L = min(chunk, max(8, 1 << (S - 1).bit_length()))
  assert L % 8 == 0 and L & (L - 1) == 0, f"a gdn chunk is 8 x a power of two positions; got {chunk}"
  pad = -S % L
  if pad:
    q, k, v, g, beta = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2)) for t in (q, k, v, g, beta))
  chunks = lambda t: jnp.moveaxis(t.reshape(B, -1, L, *t.shape[2:]), 1, 0)  # noqa: E731 — [c, B, L, ...]
  through, lower, eye = jnp.tril(jnp.ones((L, L), bool)), jnp.tril(jnp.ones((L, L), bool), -1), jnp.eye(L, dtype=jnp.float32)
  mm = partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)

  def body(state, per_chunk):
    qc, kc, vc, gc, bc = per_chunk
    G = jnp.cumsum(gc, axis=1)  # [B,L,H], falling from 0
    # log D[t,s] = g_(s+1) + ... + g_t summed AS ITSELF (a cumulative sum down each column of the strict lower triangle),
    # not G_t - G_s: a difference of two sums of hundreds carries their rounding into a decay of hundredths.
    seg = jnp.cumsum(jnp.where(lower, jnp.moveaxis(gc, 1, 2)[..., :, None], 0.0), axis=-2)  # [B,H,l,s]
    D = jnp.where(through, jnp.exp(seg), 0.0)  # at most 1
    grow = jnp.exp(G)[..., None]  # [B,L,H,1], at most 1
    T = eye + jnp.where(lower, mm("blhn,bshn->bhls", kc, kc) * D, 0.0) * jnp.moveaxis(bc, 1, 2)[..., None]
    w = mm("bhls,bshp->blhp", _unit_lower_inverse(T, mm), bc[..., None] * (vc - grow * mm("blhn,bhpn->blhp", kc, state)))
    o = grow * mm("blhn,bhpn->blhp", qc, state) + mm("bhls,bshp->blhp", mm("blhn,bshn->bhls", qc, kc) * D, w)
    to_end = jnp.moveaxis(D[:, :, -1, :], 1, 2)[..., None]  # [B,L,H,1]: from s to the chunk's end
    return state * jnp.exp(G[:, -1])[:, :, None, None] + mm("bshp,bshn->bhpn", w * to_end, kc), o

  state, o = jax.lax.scan(body, state, tuple(chunks(t) for t in (q, k, v, g, beta)))
  return jnp.moveaxis(o, 0, 1).reshape(B, S + pad, H, -1)[:, :S], state


def _gdn_layer(h, p, cfg: ModelConfig, ssm0, conv0, seq_lens=None):
  """One Gated-DeltaNet layer over a sequence, as ``_kda_layer``: h [B,S,D], the rows' states ssm0 [B,H,P,N] f32 and
  conv0 [B,K-1,C] → (h, ssm, conv) after each row's ``seq_lens`` tokens; padding moves neither leaf."""
  S = h.shape[1]
  qkv, z, ab = _gdn_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    qkv, xp = _ssm_conv(qkv, conv0, p)
    q, k, v, g, beta = _gdn_gates(qkv, ab, p, cfg)
    if seq_lens is not None:
      valid = (jnp.arange(S, dtype=jnp.int32)[None, :] < seq_lens[:, None])[..., None]
      g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    conv = _conv_tail(xp, S, seq_lens)
    y, ssm = _gdn_chunk_scan(q, k, v, g, beta, ssm0, cfg.ssm_chunk)
    y = _gdn_out(y, z, p, cfg, h.dtype)
  h, *_ = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, ssm, conv.astype(conv0.dtype)


def _gdn_decode_step(h, pool, p, layer, active, cfg: ModelConfig, use_kernel: bool = False):
  """One delta-rule step of one Gated-DeltaNet layer for every slot row, as ``_kda_decode_step``: the head's one decay
  is spread over the key channels and the step is ``ops/ssm.py kda_state_step``'s. Returns (h, pool, 0 experts visited)."""
  from ..ops.ssm import kda_state_step

  qkv, z, ab = _gdn_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    conv0 = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)
    qkv, xp = _ssm_conv(qkv, conv0, p)
    q, k, v, g, beta = _gdn_gates(qkv[:, 0], ab[:, 0], p, cfg)
    ssm, y = kda_state_step(pool["ssm"], layer, jnp.broadcast_to(jnp.exp(g)[..., None], k.shape), beta, k, v, q, active, use_kernel)
    pool = _step_conv({**pool, "ssm": ssm}, xp, conv0, layer, active)
    y = _gdn_out(y[:, None], z, p, cfg, h.dtype)
  h, _, visited = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, pool, visited


# ------------------------------------------- gated short convolution mixer
# (lfm2_moe's "conv" layers; HF ``Lfm2MoeShortConv``.) [B | C | x] = u W_in with u = rmsnorm(h), each as wide as the
# stream; g = B ⊙ x; c_t = Σ_j w_j ⊙ g_{t-(K-1)+j}, a causal depthwise convolution of ``ssm_conv`` taps with no bias
# and NO activation; out = (C ⊙ c) W_out. Gated on both sides of the taps — which is why what a row keeps between
# calls is the last ``ssm_conv - 1`` rows of the gated product g, never of x: the pool's ``conv`` leaf
# [Ls, slots, K-1, D] in the model dtype, and nothing else (``cfg.state_matrix`` false: no ``ssm`` leaf, no scan, no
# chunk). The gates' products and the tap sum are float32; g is rounded to the model dtype where it joins the tail, so
# that a decode step reads the rows a prefill would have handed it.


@component_scope("xot.ssm_proj")
def _gated_conv_in(h, p, cfg: ModelConfig):
  """Norm and input projection: h [B,S,D] → B, C, x, each [B,S,D]."""
  bcx = _mm(rms_norm(h, p["ssm_norm"], cfg.norm_eps), p, "w_in", cfg.quant_compute)
  return jnp.split(bcx, 3, axis=-1)


def _gated_conv(b, c, x, conv0, p):
  """C ⊙ conv(B ⊙ x) over a sequence from the tail ``conv0`` [B,K-1,D] → (y [B,S,D], the padded gated product
  [B, K-1+S, D], whose last rows are the next tail)."""
  g = (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(x.dtype)
  taps, gp = _ssm_conv(g, conv0, p, act=None)
  return (c.astype(jnp.float32) * taps.astype(jnp.float32)).astype(x.dtype), gp


def _gated_conv_layer(h, p, cfg: ModelConfig, ssm0, conv0, seq_lens=None):
  """One gated-short-convolution layer step over a sequence, in the recurrent kinds' one call shape: h [B,S,D], no
  state matrix (``ssm0`` None, and None comes back) and conv0 [B,K-1,D] → (h, None, conv) after each row's
  ``seq_lens`` tokens (None: all S). The tail is cut at the length, so padding moves nothing; a prompt shorter than
  the tail keeps the newest of the old rows ahead of its own."""
  b, c, x = _gated_conv_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    y, gp = _gated_conv(b, c, x, conv0, p)
    conv = _conv_tail(gp, h.shape[1], seq_lens)
  h, *_ = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, ssm0, conv.astype(conv0.dtype)


def _gated_conv_decode_step(h, pool, p, layer, active, cfg: ModelConfig, use_kernel: bool = False):
  """One token of one gated-short-convolution layer for every slot row: the pool's ``conv`` leaf [Ls,B,K-1,D] is read
  and written in place at ``layer`` — 8 KB a row at the published widths —, an inactive row's tail bit for bit
  (``_step_conv``). Returns (h, pool, the experts its FFN visited)."""
  b, c, x = _gated_conv_in(h, p, cfg)
  with jax.named_scope("xot.ssm"):
    conv0 = jax.lax.dynamic_index_in_dim(pool["conv"], layer, 0, keepdims=False)
    y, gp = _gated_conv(b, c, x, conv0, p)
    pool = _step_conv(pool, gp, conv0, layer, active)
  h, _, visited = _mlp_block(_ssm_out(h, y, p, cfg), p, cfg)
  return h, pool, visited


# A recurrent kind's two layer functions, (over a sequence, one decode step): the ONE place a kind is looked up
# (``cfg.recurrent_kind``), by ``_hybrid_layers`` and ``paged_decode_forward``.
_RECURRENT_LAYER = {
  "mamba": (_ssm_layer, _ssm_decode_step),
  "kda": (_kda_layer, _kda_decode_step),
  "gdn": (_gdn_layer, _gdn_decode_step),
  "conv": (_gated_conv_layer, _gated_conv_decode_step),
}

# A hybrid's latent-attention layers take a prefill's queries this many positions at a time (ops/attention.py
# mla_absorbed_attention ``q_block``): its pool is donated with per-slot state beside the weights, and the float32 scores
# of a whole group against the gathered window do not fit there (AOT, tests/test_tpu_compile.py; PERF.md §6, PR 36).
_HYBRID_MLA_Q_BLOCK = 256


def _state_rows(leaf, layer, slot_rows):
  """``leaf[layer, slot_rows]`` of the pool's stacked ``ssm`` leaf [L, n_slots, H, P, N] → [K, H, P, N], one
  ``dynamic_slice`` a row (K is static and small): each read touches its row's bytes where they lie. A slot past the
  last is clamped to the last, as a gather's ``mode="clip"`` does. (The gather itself, at a face that is no whole number
  of lanes — Olmo's [192, 96] —, XLA:TPU lowers by cutting the WHOLE leaf in two first: 1.7 GB copied a layer to read K
  rows of 2.2 MB. The ``conv`` leaf's rows [K-1, C] are whole lanes in every kind and its gather compiles to a gather of
  the rows; read by slices its rows reach the convolution in a layout that costs a copy of the padded sequence a layer.
  AOT for a described v5e; PERF.md §6, PR 48.)"""
  at = (0,) * (leaf.ndim - 2)
  rows = [jax.lax.dynamic_slice(leaf, (layer, slot_rows[i], *at), (1, 1, *leaf.shape[2:]))[0] for i in range(slot_rows.shape[0])]
  return jnp.concatenate(rows)


def _hybrid_layers(h, params: Params, cfg: ModelConfig, positions, carry: Params, slot_rows=None, fresh=None, seq_lens=None, adapter_ids=None):
  """The layers of a model whose layers differ in kind (``cfg.mixed_layers``) over a sequence, in the published
  order: the prefill of the paged programs, the slot-cache forward of a model without recurrent layers, and the
  cache-less forward (scoring, training).

  ``carry`` rides the layer loop as the page pool does in decode
  (``_scan_layers_over_pool``). Prefill: the rows' gathered K/V windows
  [La, K, S_tot, Hkv, hd] under the pool's page-leaf names, plus the POOL's
  own state leaves ``ssm`` (where the kind keeps a state matrix: the
  pool has the leaf) / ``conv``, read and written at (layer,
  ``slot_rows``) — a row whose ``fresh`` flag is set starts from zeros, so a
  slot's last tenant is never seen; a padding row names a slot past the last
  and its write is dropped. Cache-less: ``carry`` is empty, every row starts
  from zeros and nothing is kept. Returns (h, carry)."""
  from ..ops.paged import STATE_LEAVES

  inv_freq = rope_inv_freq(cfg)
  B = h.shape[0]
  pages = [name for name in carry if name not in STATE_LEAVES]
  kv_positions = jnp.arange(carry[pages[0]].shape[2], dtype=jnp.int32) if pages else positions[0]

  def step(h, carry, lp, layer):
    if "w_out" not in lp:  # an attention layer (dense or latent): ``layer`` counts the pool's page layers
      kv = {name: jax.lax.dynamic_index_in_dim(carry[name], layer, 0, keepdims=False) for name in pages} or None
      h, kv, _ = _layer_step(h, lp, kv, positions, kv_positions, inv_freq, cfg, bool(pages), adapter_ids=adapter_ids, mla_q_block=_HYBRID_MLA_Q_BLOCK)
      return h, {**carry, **{name: jax.lax.dynamic_update_index_in_dim(carry[name], kv[name], layer, 0) for name in pages}}
    over_sequence = _RECURRENT_LAYER[cfg.recurrent_kind][0]
    if "conv" not in carry:
      ssm0 = jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32) if cfg.state_matrix else None
      h, _, _ = over_sequence(h, lp, cfg, ssm0, jnp.zeros((B, cfg.ssm_conv - 1, cfg.ssm_conv_dim), h.dtype), seq_lens)
      return h, carry
    with jax.named_scope("xot.ssm"):  # (a kind with no state matrix: the pool has no ``ssm`` leaf, and the tail is all there is to read and write)
      ssm0 = jnp.where(fresh[:, None, None, None], 0.0, _state_rows(carry["ssm"], layer, slot_rows)).astype(jnp.float32) if "ssm" in carry else None
      conv0 = jnp.where(fresh[:, None, None], 0, carry["conv"].at[layer, slot_rows].get(mode="clip"))
    h, ssm, conv = over_sequence(h, lp, cfg, ssm0, conv0, seq_lens)
    with jax.named_scope("xot.ssm"):
      stepped = {"ssm": carry["ssm"].at[layer, slot_rows].set(ssm.astype(carry["ssm"].dtype), mode="drop")} if "ssm" in carry else {}
      carry = {**carry, **stepped, "conv": carry["conv"].at[layer, slot_rows].set(conv, mode="drop")}
    return h, carry

  # (a cache-less forward may be differentiated — training — and the experts' kernels have no derivative: it hands no
  # stack over whole, so its layers take the block form)
  return _scan_layers_over_pool(step, h, _layer_runs(params, cfg), carry, _whole_expert_leaves(params, cfg) if carry else ())


def _layer_step(h, layer_params, kv, positions, kv_positions, inv_freq, cfg: ModelConfig, use_cache: bool, attn_fn=None, adapter_ids=None, mla_q_block: int = 0):
  """One decoder layer. h [B,S,D] → (h, new_kv, aux).

  ``kv`` is this layer's cache dict ({"k", "v"} [+ "k_scale"/"v_scale" when
  int8-quantized — init_kv_cache]) or None on the cache-less path.
  ``aux`` is the MoE load-balancing loss for this layer (0.0 for dense
  layers); the training path accumulates it (parallel/train_step.py).
  ``attn_fn(q, k, v, q_pos, kv_pos)`` overrides the attention op on the
  cache-less path — used to swap in ring attention under sequence
  parallelism (parallel/ring_attention.py).
  """
  B, S, D = h.shape
  p = layer_params

  with jax.named_scope("xot.attn_proj"):
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps) if "attn_norm" in p else h
  routed = _route_ahead(x, p, cfg)
  if "wkv_a" in p and use_cache:
    # MLA with cache: write only the latent (+rope channel) and attend via
    # weight absorption (ops/attention.py mla_absorbed_attention) — the cache
    # holds rank+rope floats per token instead of H·(qk+v).
    from ..ops.attention import mla_absorbed_attention

    q_nope, q_pe, c_kv, k_pe = _mla_latents(x, p, cfg, positions, inv_freq)
    start = positions[:, 0]
    kv = {
      "k": _write_cache(kv["k"], c_kv[:, :, None, :], start),
      "v": _write_cache(kv["v"], k_pe[:, :, None, :], start),
    }
    with jax.named_scope("xot.attn"):  # the latent read and the absorbed up-projection are the core's operands
      ckv, kpe = kv["k"][:, :, 0, :].astype(h.dtype), kv["v"][:, :, 0, :].astype(h.dtype)
      w_kv_b = _mla_w_kv_b(p, h.dtype)
    attn = mla_absorbed_attention(
      q_nope,
      q_pe,
      ckv,
      kpe,
      w_kv_b,
      positions,
      kv_positions,
      cfg.v_head_dim,
      mla_q_block,
    )
  else:
    if "wkv_a" in p:  # MLA, cache-less (training): naive per-head K/V
      q, k, v = _mla_qkv(x, p, cfg, positions, inv_freq)
    else:
      q, k, v = _dense_qkv(x, p, cfg, positions, inv_freq, adapter_ids)

    if use_cache:
      start = positions[:, 0]
      from ..ops.pallas_attention import flash_attention_prefill, flash_decode_attention, flash_decode_supported, flash_supported

      if "k_scale" in kv:  # int8/int4 KV (models/quantize.py quantize_kv[_int4])
        from .quantize import quantize_kv, quantize_kv_int4, unpack_int4_kv

        packed = kv["k"].shape[-1] * 2 == k.shape[-1]  # int4: halved code axis
        quant_fn = quantize_kv_int4 if packed else quantize_kv
        kq, ks = quant_fn(k)
        vq, vs = quant_fn(v)
        kv = {
          "k": _write_cache(kv["k"], kq, start),
          "k_scale": _write_cache(kv["k_scale"], ks, start),
          "v": _write_cache(kv["v"], vq, start),
          "v_scale": _write_cache(kv["v_scale"], vs, start),
        }
        window = _layer_window(p)
        if cfg.plain_attention and S > 1 and not packed and flash_supported(q.shape, kv["k"].shape[1]):
          # Prefill: int8 codes + scales stream straight through the flash
          # kernel (per-block in-register dequant) — no materialized bf16
          # cache copy, 1 byte/element HBM traffic. (int4 takes the einsum
          # path below — the flash kernel has no nibble unpack.)
          attn = flash_attention_prefill(q, kv["k"], kv["v"], q_offset=positions[:, 0], k_scale=kv["k_scale"], v_scale=kv["v_scale"], window=window)
        else:
          # Decode reads the cache as quantized CODES — the convert (and the
          # int4 nibble unpack) fuses into the einsum, so the HBM-bound cache
          # read moves the quantized bytes only.
          k_codes = unpack_int4_kv(kv["k"]) if packed else kv["k"]
          v_codes = unpack_int4_kv(kv["v"]) if packed else kv["v"]
          attn = gqa_attention(
            q, k_codes, v_codes, positions, kv_positions, k_scale=kv["k_scale"], v_scale=kv["v_scale"], **_attn_opts(cfg, p.get("is_sliding"), p.get("attn_kind"))
          )
      else:
        kv = {"k": _write_cache(kv["k"], k, start), "v": _write_cache(kv["v"], v, start)}
        k_cache, v_cache = kv["k"], kv["v"]
        # The Pallas kernels don't implement gemma2's softcap or a window that rides a traced flag
        # (``cfg.plain_attention``); a layer kind's static window is the flash kernel's operand.
        window = _layer_window(p)
        if cfg.plain_attention and S > 1 and not cfg.is_mla and flash_supported(q.shape, k_cache.shape[1]):
          # Prefill on TPU: flash kernel against the full cache (stale slots
          # beyond the prompt are positionally masked — slot index > position).
          attn = flash_attention_prefill(q, k_cache.astype(h.dtype), v_cache.astype(h.dtype), q_offset=positions[:, 0], window=window)
        elif cfg.plain_attention and S == 1 and not window and not cfg.is_mla and flash_decode_supported(q.shape, k_cache.shape[1]):
          # Long-cache decode step via the split-K flash-decode kernel —
          # opt-in; see flash_decode_supported for the measured rationale.
          attn = flash_decode_attention(q, k_cache.astype(h.dtype), v_cache.astype(h.dtype), positions)
        else:
          attn = gqa_attention(q, k_cache.astype(h.dtype), v_cache.astype(h.dtype), positions, kv_positions, **_attn_opts(cfg, p.get("is_sliding"), p.get("attn_kind")))
    else:
      # The override (ring sp — parallel/ring_attention.py) takes the same
      # attention options as gqa_attention, so gemma2's scale/softcap/window
      # ride through either path.
      attn = (attn_fn or gqa_attention)(q, k, v, positions, positions[0], **_attn_opts(cfg, p.get("is_sliding"), p.get("attn_kind")))

  h = _attn_out(h, x, attn, p, cfg)
  h, aux, _ = _mlp_block(h, p, cfg, routed)
  return h, kv, aux


@component_scope("xot.embed")
def embed_tokens(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
  """Token ids [B,S] → embeddings [B,S,D] in model dtype."""
  h = jnp.take(params["embed"], x, axis=0).astype(cfg.dtype)
  if cfg.embed_scale != 1.0:
    # gemma scales embeddings by sqrt(dim), with HF casting the scalar to the
    # model dtype first (bf16 rounding is part of the checkpoint contract).
    h = h * jnp.asarray(cfg.embed_scale, dtype=cfg.dtype)
  return h


@component_scope("xot.head")
def head_logits(params: Params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
  """Final norm + LM head: hidden [B,S,D] → fp32 logits [B,S,V].

  Shared by the last-shard path below and the pipeline-parallel serving
  programs (parallel/pp_serving.py), which run it replicated on every stage.
  """
  h = rms_norm(h, params["final_norm"], cfg.norm_eps)
  if "lm_head_scale" in params:
    logits = qdot(h, params["lm_head"], params["lm_head_scale"], cfg.quant_compute or QUANT_COMPUTE).astype(jnp.float32)
  else:
    w_out = params.get("lm_head")
    if w_out is None:
      w_out = params["embed"].T  # tied embeddings, single-params case
    # Keep operands in model dtype on the MXU; accumulate fp32. (Casting the
    # [D,V] head to fp32 would double its HBM traffic on every decode step.)
    logits = jax.lax.dot_general(h, w_out.astype(h.dtype), (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32)
  if cfg.logits_scaling != 1.0:  # granite
    logits = logits / cfg.logits_scaling
  if cfg.final_logit_softcap:
    logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
  return logits


def shard_forward(
  params: Params,
  cfg: ModelConfig,
  shard: Shard,
  x: jnp.ndarray,  # [B,S] int tokens (first shard) | [B,S,D] hidden
  positions: jnp.ndarray,  # [B,S] absolute positions
  kv_cache: Params | None = None,
  head_pos: jnp.ndarray | None = None,  # [B] per-row S-axis index for the head
  adapter_ids: jnp.ndarray | None = None,  # [B] per-row LoRA slot (ISSUE 15)
) -> tuple[jnp.ndarray, Params | None]:
  """Run the shard's layer range. Returns (hidden|logits, updated cache).

  With a cache: queries attend to all cache slots ≤ their absolute position
  (prefill writes slots [0..S), decode writes slot p then reads ≤ p).
  Without a cache: plain causal attention within the call (training path).

  ``head_pos`` (last shard only): gather each row's hidden state at that
  S-axis index BEFORE the LM head, returning logits [B, 1, V] instead of
  [B, S, V] — a batched prefill over K rows would otherwise materialize
  K·S·V fp32 logits it immediately discards.
  """
  if x.ndim == 2:  # token ids — valid only on the first shard
    h = embed_tokens(params, cfg, x)
  else:
    h = x.astype(cfg.dtype)

  inv_freq = rope_inv_freq(cfg)
  use_cache = kv_cache is not None
  kv_positions = jnp.arange(kv_cache["k"].shape[2], dtype=jnp.int32) if use_cache else positions[0]

  # Layer stacks run in order: dense prefix ("layers", e.g. deepseek's
  # first_k_dense), then the MoE stack ("moe_layers"). Each stack is one
  # lax.scan; MoE models with no dense prefix simply have no "layers" key.
  stacks = _layer_stacks(params)

  if cfg.mixed_layers:  # layers of several kinds, in the published order
    if use_cache and cfg.recurrent_layers:  # with a cache a hybrid's forward is the paged prefill (prefill_into_pages_many)
      raise ValueError("a hybrid has no slot-cache forward: shard_forward runs it cache-less")
    h, new_cache = _hybrid_layers(h, params, cfg, positions, kv_cache or {}, adapter_ids=adapter_ids)
    new_cache = new_cache or None
  elif use_cache:
    parts = []
    off = 0
    whole = _whole_expert_leaves(params, cfg)  # (the cache-less forward below may be differentiated: block form)
    for stack in stacks:
      L = next(iter(stack.values())).shape[0]
      sliced, put = _split_whole(stack, whole)

      def body(carry, per_layer, put=put):
        h = carry
        lp, kv, at = per_layer
        h, kv, _ = _layer_step(h, put(lp, at), kv, positions, kv_positions, inv_freq, cfg, True, adapter_ids=adapter_ids)
        return h, kv

      with jax.named_scope("xot.kv_write"):  # a model of two stacks splits the cache per stack and joins it again: whole-cache copies
        sub = {key: val[off : off + L] for key, val in kv_cache.items()}
      h, new_sub = jax.lax.scan(body, h, (sliced, sub, jnp.arange(L, dtype=jnp.int32)))
      parts.append(new_sub)
      off += L
    with jax.named_scope("xot.kv_write"):
      new_cache: Params | None = parts[0] if len(parts) == 1 else {key: jnp.concatenate([p[key] for p in parts], axis=0) for key in parts[0]}
  else:

    def body(carry, lp):
      h = carry
      h, _, _ = _layer_step(h, lp, None, positions, kv_positions, inv_freq, cfg, False, adapter_ids=adapter_ids)
      return h, None

    for stack in stacks:
      h, _ = jax.lax.scan(body, h, stack)
    new_cache = None

  if shard.is_last_layer:
    if head_pos is not None:
      h = _rows_at(h, head_pos)
    return head_logits(params, cfg, h), new_cache
  return h, new_cache


def _rows_at(h: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
  """h [B,S,D] at each row's own S-axis index idx [B] → [B,1,D]."""
  B = h.shape[0]
  return jnp.take_along_axis(h, jnp.broadcast_to(idx.reshape(B, 1, 1), (B, 1, h.shape[-1])), axis=1)


# Jitted entry: cfg/shard are static (hashable frozen dataclasses).
jit_shard_forward = tracked_jit(
  "decode.shard_forward",
  lambda params, cfg, shard, x, positions, kv_cache: shard_forward(params, cfg, shard, x, positions, kv_cache),
  static_argnames=("cfg", "shard"),
)


def shard_forward_aux(
  params: Params,
  cfg: ModelConfig,
  shard: Shard,
  x: jnp.ndarray,
  positions: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Cache-less ``shard_forward`` that also returns the span's accumulated
  MoE load-balancing aux loss (0.0 for dense layers).

  The ring-training spans (train/trainer.py) use this so each span folds its
  OWN layers' aux gradient into its local update and adds ``coef·aux`` to
  the loss riding the ring reply — making ring training of MoE models
  exactly equivalent to the single-node step, which optimizes
  ``CE + moe_aux_loss_coef · Σ aux`` (parallel/train_step.py).
  """
  if len(cfg.attn_shapes) > 1:
    raise ValueError("shard_forward_aux walks the two plain stacks: a model whose attention kinds have stacks of their own is not trained over the ring")
  h = embed_tokens(params, cfg, x) if x.ndim == 2 else x.astype(cfg.dtype)
  inv_freq = rope_inv_freq(cfg)
  kv_positions = positions[0]

  def body(carry, lp):
    h, a = carry
    h, _, aux = _layer_step(h, lp, None, positions, kv_positions, inv_freq, cfg, False)
    return (h, a + aux), None

  a = jnp.float32(0.0)
  for stack in (params[name] for name in ("layers", "moe_layers") if name in params):
    (h, a), _ = jax.lax.scan(body, (h, a), stack)
  if shard.is_last_layer:
    return head_logits(params, cfg, h), a
  return h, a


@component_scope("xot.sample")
def _next_token(row, key, greedy: bool, temp, top_k: int):
  """greedy is STATIC (two compiled variants); temp is TRACED — client
  temperatures must not key the jit cache, or each distinct value would
  recompile the full decode program (a remotely triggerable compile storm)."""
  from ..ops.sampling import sample_logits

  if greedy:
    return jnp.argmax(row, axis=-1).astype(jnp.int32), key
  key, sub = jax.random.split(key)
  return sample_logits(row, sub, temp=temp, top_k=top_k), key


@partial(tracked_jit, "decode.fused", static_argnames=("cfg", "shard", "n_steps", "top_k", "greedy"), donate_argnums=(4,))
def _fused_decode_impl(params, cfg: ModelConfig, shard: Shard, token, cache, start_pos, n_steps: int, temp, top_k: int, greedy: bool, key, adapter_ids):
  def body(carry, _):
    tok, pos, cache, key = carry
    logits, cache = shard_forward(params, cfg, shard, tok, pos[:, None], cache, adapter_ids=adapter_ids)
    nxt, key = _next_token(logits[:, 0, :], key, greedy, temp, top_k)
    return (nxt[:, None], pos + 1, cache, key), nxt

  (_, _, cache, _), toks = jax.lax.scan(body, (token, start_pos, cache, key), None, length=n_steps)
  return jnp.moveaxis(toks, 0, 1), cache


def fused_decode(params, cfg: ModelConfig, shard: Shard, token, cache, start_pos, n_steps: int, temp: float = 0.0, top_k: int = 35, key=None, adapter_ids=None):
  """Generate ``n_steps`` tokens in ONE compiled program (lax.scan over steps).

  The single-node serving fast path: no host round-trip per token, cache
  donated and updated in place. token [B,1] int32; start_pos [B] int32.
  Returns (tokens [B, n_steps], cache). Requires a full-model shard.
  ``adapter_ids`` [B] selects each row's LoRA slot (ISSUE 15; None = base).
  """
  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("fused_decode requires a full-model shard")
  if key is None:
    key = jax.random.PRNGKey(0)
  greedy = temp is None or float(temp) <= 0.0
  temp_arr = jnp.float32(1.0 if greedy else float(temp))
  return _fused_decode_impl(params, cfg, shard, token, cache, start_pos, int(n_steps), temp_arr, int(top_k), greedy, key, adapter_ids)


@partial(tracked_jit, "decode.fused_generate", static_argnames=("cfg", "shard", "max_steps", "top_k", "eos_ids", "greedy"), donate_argnums=(4,))
def _fused_generate_impl(params, cfg: ModelConfig, shard: Shard, token, cache, start_pos, max_steps: int, eos_ids: tuple, temp, top_k: int, greedy: bool, key, n_limit, adapter_ids):
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
    logits, cache = shard_forward(params, cfg, shard, tok, pos[:, None], cache, adapter_ids=adapter_ids)
    nxt, key = _next_token(logits[:, 0, :], key, greedy, temp, top_k)
    buf = jax.lax.dynamic_update_slice(buf, nxt[:, None], (0, i))
    if eos is not None:
      done = done | jnp.any(nxt[:, None] == eos[None, :], axis=-1)
    return (nxt[:, None], pos + 1, cache, key, buf, i + 1, done)

  _, _, cache, _, buf, n, _ = jax.lax.while_loop(cond, body, (token, start_pos, cache, key, buf0, jnp.int32(0), done0))
  return buf, n, cache


def fused_generate(
  params,
  cfg: ModelConfig,
  shard: Shard,
  token,  # [B,1] int32 — the token that seeds generation
  cache,
  start_pos,  # [B] int32
  max_steps: int,
  eos_ids: tuple = (),
  temp: float = 0.0,
  top_k: int = 35,
  key=None,
  n_limit=None,
  adapter_ids=None,
):
  """Generate until EOS (or a step limit) in ONE compiled program.

  ``max_steps`` (static) sizes the token buffer and the compiled program;
  ``n_limit`` (traced scalar, default ``max_steps``) is the actual step cap —
  callers bucket ``max_steps`` to reuse compiled programs across requests
  without running bucket−request extra steps. ``temp`` is traced too (client
  temperatures must not key the jit cache); only greedy-vs-sampled compiles
  two variants.

  ``lax.while_loop`` exits as soon as every batch row has sampled an EOS id,
  so the host pays exactly ONE dispatch + ONE result fetch for the whole
  response, where the reference's loop (``node.py:109-147``) reads back
  every token.

  Returns (tokens [B, max_steps] int32, n_steps [] int32, cache). Rows keep
  their EOS token; positions past a row's EOS hold whatever was speculatively
  sampled before every row finished (callers trim at the first EOS).
  """
  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("fused_generate requires a full-model shard")
  if key is None:
    key = jax.random.PRNGKey(0)
  greedy = temp is None or float(temp) <= 0.0
  temp_arr = jnp.float32(1.0 if greedy else float(temp))
  limit = jnp.int32(max_steps if n_limit is None else n_limit)
  return _fused_generate_impl(
    params, cfg, shard, token, cache, start_pos, int(max_steps), tuple(eos_ids), temp_arr, int(top_k), greedy, key, limit, adapter_ids
  )


# ------------------------------------------------ speculative decoding


@partial(tracked_jit, "spec.generate", static_argnames=("cfg_t", "cfg_d", "shard_t", "shard_d", "max_steps", "gamma", "eos_ids"), donate_argnums=(6, 7))
def _fused_spec_generate_impl(
  params_t, params_d, cfg_t: ModelConfig, cfg_d: ModelConfig, shard_t: Shard, shard_d: Shard,
  cache_t, cache_d, token, start_pos, max_steps: int, gamma: int, eos_ids: tuple, n_limit,
):
  G = gamma
  eos = jnp.asarray(eos_ids, dtype=jnp.int32) if eos_ids else None
  limit = jnp.minimum(n_limit.astype(jnp.int32), max_steps)
  max_seq = cache_t["k"].shape[2]
  buf0 = jnp.zeros((max_steps + G + 1,), dtype=jnp.int32)
  idx = jnp.arange(G + 1, dtype=jnp.int32)

  def cond(carry):
    _, pos, _, _, _, n, _, done = carry
    # Room guard: one round writes target slots [pos, pos+G]; stop a round
    # early rather than run off the cache.
    return (~done) & (n < limit) & (pos + G + 1 <= max_seq)

  def body(carry):
    cur, pos, cache_t_, cache_d_, buf, n, rounds, done = carry

    # 1) Draft proposes G tokens greedily (sequential small-model steps).
    def dstep(c, _):
      tok, p, cache = c
      logits, cache = shard_forward(params_d, cfg_d, shard_d, tok, p.reshape(1, 1), cache)
      nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
      return (nxt[:, None], p + 1, cache), nxt[0]

    (_, _, cache_d_), d = jax.lax.scan(dstep, (cur, pos, cache_d_), None, length=G)  # d: [G]

    # 2) Target verifies the whole window in ONE parallel forward:
    #    tokens [cur, d_1..d_G] at positions pos..pos+G.
    window = jnp.concatenate([cur[0], d], axis=0)[None, :]  # [1, G+1]
    positions = (pos + idx)[None, :]
    logits_t, cache_t_ = shard_forward(params_t, cfg_t, shard_t, window, positions, cache_t_)
    t = jnp.argmax(logits_t[0], axis=-1).astype(jnp.int32)  # [G+1]; t[i] = target's token for position pos+i+1

    # 3) Greedy acceptance: longest prefix with d_i == t_{i-1}; then the
    #    target's own next token. Every emitted token equals what plain
    #    target-greedy would produce, so the scheme is EXACT for any draft.
    matches = (d == t[:G]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(matches))
    k = n_acc + 1  # tokens emitted this round
    emitted = jnp.where(idx < n_acc, jnp.concatenate([d, jnp.zeros((1,), jnp.int32)])[idx], t[n_acc])
    # (slots past index n_acc hold t[n_acc] too — harmless: only buf[n:n+k]
    #  counts and the next round's write at n+k overwrites the rest.)
    buf = jax.lax.dynamic_update_slice(buf, emitted, (n,))

    # 4) Draft catch-up: same window through the draft so its cache covers
    #    slot pos+G (the last proposed token's KV never lands during the
    #    sequential proposal — on full acceptance the next round would
    #    otherwise read a hole).
    _, cache_d_ = shard_forward(params_d, cfg_d, shard_d, window, positions, cache_d_)

    if eos is not None:
      hit = jnp.any((emitted[:, None] == eos[None, :]) & (idx[:, None] < k), axis=(0, 1))
      done = done | hit
    cur = t[n_acc].reshape(1, 1)
    return (cur, pos + k, cache_t_, cache_d_, buf, n + k, rounds + 1, done)

  init = (token, start_pos, cache_t, cache_d, buf0, jnp.int32(0), jnp.int32(0), jnp.bool_(False))
  _, _, cache_t, cache_d, buf, n, rounds, _ = jax.lax.while_loop(cond, body, init)
  return buf, n, rounds, cache_t, cache_d


def fused_speculative_generate(
  params_t, cfg_t: ModelConfig, shard_t: Shard,
  params_d, cfg_d: ModelConfig, shard_d: Shard,
  token,  # [1,1] int32 seed token
  cache_t, cache_d,
  start_pos,  # [] int32 scalar
  max_steps: int,
  gamma: int = 4,
  eos_ids: tuple = (),
  n_limit=None,
):
  """Greedy speculative decoding: draft + target fused in ONE while_loop.

  Each round: the draft proposes ``gamma`` tokens sequentially; the target
  scores the whole window in one parallel forward (reading its weights ONCE
  for up to gamma+1 output tokens — decode is weight-bandwidth-bound, so
  acceptance rate ≈ speedup); the longest matching prefix is accepted plus
  the target's correction token. Host pays one dispatch + one readback for
  the entire response.

  EXACT by construction: every emitted token is the target's own greedy
  choice (computed by the verification forward), so for ANY draft the output
  is identical to ``fused_generate`` at temp=0 under deterministic
  arithmetic — the draft only changes speed; the exactness tests run at f32
  matmul precision and assert token-for-token equality. One honest numerics
  caveat shared by all production speculative decoders: on bf16 hardware a
  batched (gamma+1)-token forward and a 1-token forward can reduce in
  different orders, so argmax near-ties may resolve differently than the
  sequential path — the output is still a greedy trajectory of the target
  under the verification forward's numerics. Rollback is free: rejected
  slots are position-masked until the next round's writes cover them
  (slot-indexed cache, see init_kv_cache).

  Acceptance rate ≈ speedup. With a real checkpoint and an int8
  self-draft, argmax agreement is high (peaked distributions); a
  random-weight model has near-uniform logits, so its acceptance understates
  real-model behavior.

  Returns (buf [max_steps+gamma+1], n_generated, n_rounds, cache_t,
  cache_d); trim to the first EOS within buf[:n] host-side. Acceptance rate
  = (n/n_rounds − 1)/gamma.
  """
  if not (shard_t.is_first_layer and shard_t.is_last_layer and shard_d.is_first_layer and shard_d.is_last_layer):
    raise ValueError("speculative decoding requires full-model shards")
  if token.shape[0] != 1:
    raise ValueError("speculative decoding is single-stream (B=1)")
  limit = jnp.int32(max_steps if n_limit is None else n_limit)
  return _fused_spec_generate_impl(
    params_t, params_d, cfg_t, cfg_d, shard_t, shard_d, cache_t, cache_d,
    token, jnp.int32(start_pos), int(max_steps), int(gamma), tuple(eos_ids), limit,
  )


@partial(tracked_jit, "spec.chunk", static_argnames=("cfg", "shard", "cfg_d", "shard_d", "steps", "gamma", "eos_ids"), donate_argnums=(3, 4))
def _fused_spec_chunk_impl(params_t, params_d, token, cache_t, cache_d, pos, n_limit, steps: int, gamma: int, eos_ids: tuple, cfg: ModelConfig, shard: Shard, cfg_d: ModelConfig, shard_d: Shard):
  buf, n, rounds, cache_t, cache_d = _fused_spec_generate_impl(
    params_t, params_d, cfg, cfg_d, shard, shard_d, cache_t, cache_d, token, pos, steps, gamma, eos_ids, n_limit
  )
  m = jnp.minimum(n, n_limit)
  # [m, rounds, tokens...] in ONE array: the host learns the count, the round
  # count (the acceptance-EWMA gamma policy needs it — ISSUE 7) and the
  # tokens in a single fetch instead of three.
  packed = jnp.concatenate([m[None], rounds[None], buf])
  # The chain stays ON DEVICE: seed = last emitted token, pos advances by m —
  # the next chunk can dispatch before this one is ever read back.
  seed = jnp.where(m > 0, buf[jnp.maximum(m - 1, 0)], token[0, 0]).reshape(1, 1)
  return packed, seed, pos + m, cache_t, cache_d


def fused_speculative_chunk(params_t, cfg: ModelConfig, shard: Shard, params_d, token, cache_t, cache_d, pos, steps: int, gamma: int = 4, eos_ids: tuple = (), n_limit=None, cfg_d: ModelConfig | None = None, shard_d: Shard | None = None):
  """One STREAMING speculative chunk with a device-resident chain.

  Same math as ``fused_speculative_generate`` (greedy, exact vs plain greedy
  for any draft) bounded to ``steps`` emitted tokens. Returns
  (packed [2+steps+gamma+1] int32 = [m, rounds, tokens...], seed [1,1],
  new_pos [], cache_t, cache_d) — seed/new_pos are lazy device values, so the engine can
  dispatch chunk N+1 from chunk N's outputs with no host round-trip, and the
  node's pipelined chunk loop works unchanged (jax_engine
  ``_dispatch_chunk_sync``). EOS inside the chunk shortens ``m`` via the
  while_loop's done flag; positions past ``m`` in the packed buffer are
  speculative garbage the host discards.
  """
  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("speculative decoding requires full-model shards")
  limit = jnp.int32(steps if n_limit is None else n_limit)
  return _fused_spec_chunk_impl(
    params_t, params_d, token, cache_t, cache_d, jnp.int32(pos) if not hasattr(pos, "dtype") else pos, limit, int(steps), int(gamma), tuple(eos_ids),
    cfg, shard, cfg_d or cfg, shard_d or shard,
  )


# ------------------------------------------------------- batched serving
# (inference/batch_scheduler.py): a fixed pool of batch rows ("slots"), each
# holding one request. Shapes stay static — prefill scatters one row into the
# pooled cache; decode steps ALL rows every tick (decode is weight-bandwidth
# bound, so B rows cost ≈ 1 row) with per-row positions/temperature.


@partial(tracked_jit, "prefill.slot", static_argnames=("cfg", "shard"))
def prefill_into_slot(params, cfg: ModelConfig, shard: Shard, tokens, cache, row, prompt_len):
  """Prefill one request into batch row ``row`` of the pooled cache.

  tokens [1, S_pad] int32; returns (last-token logits [1, V], cache).
  ``row`` and ``prompt_len`` are traced scalars — one compiled program
  serves every slot and prompt length within a pad bucket.

  Deliberately NOT donated: a prefill that fails on-device (e.g. activation
  OOM on a huge prompt) must leave the POOLED cache intact so the other
  rows' requests keep serving — the scheduler fails only the one request
  (batch_scheduler.py _admit). The copy costs one cache write pass.
  """
  S = tokens.shape[1]
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
  sub = {k: jax.lax.dynamic_slice_in_dim(v, row, 1, axis=1) for k, v in cache.items()}
  logits, sub = shard_forward(params, cfg, shard, tokens, positions, sub)
  cache = {k: jax.lax.dynamic_update_slice_in_dim(cache[k], sub[k], row, axis=1) for k in cache}
  idx = (prompt_len - 1).reshape(1, 1, 1)
  last = jnp.take_along_axis(logits, jnp.broadcast_to(idx, (1, 1, logits.shape[-1])), axis=1)[:, 0, :]
  return last, cache


@partial(tracked_jit, "prefill.slots", static_argnames=("cfg", "shard"))
def prefill_into_slots(params, cfg: ModelConfig, shard: Shard, tokens, cache, rows, prompt_lens, adapter_ids=None):
  """Prefill K requests into K pool rows in ONE dispatch.

  tokens [K, S_pad] int32 (each row its own prompt, zero-padded to the
  group's bucket); rows [K] int32 (distinct slot indices — padding rows may
  duplicate EACH OTHER but never a real row: scatter order between
  duplicates is undefined, and only unoccupied slots can absorb garbage);
  prompt_lens [K] int32 traced. Returns (last-token logits [K, V], cache).

  This is the admission-latency fix for concurrent arrivals: K requests
  queued together cost one weight pass instead of K serial prefill
  dispatches while the decode pool stalls (prefill is weight-bandwidth-bound
  at short prompts, so K rows cost ≈ 1). Not donated, same as
  ``prefill_into_slot``: a failed prefill must leave the pooled cache
  intact.
  """
  K, S = tokens.shape
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (K, S))
  sub = {k: jnp.take(v, rows, axis=1) for k, v in cache.items()}
  logits, sub = shard_forward(params, cfg, shard, tokens, positions, sub, head_pos=prompt_lens - 1, adapter_ids=adapter_ids)
  cache = {k: cache[k].at[:, rows].set(sub[k]) for k in cache}
  return logits[:, 0, :], cache


def prefill_into_pages_many(params, cfg: ModelConfig, shard: Shard, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int, adapter_ids=None, slot_rows=None):
  """``prefill_into_pages`` for K requests in ONE dispatch.

  tokens [K, S_pad] int32 — each row's prompt SUFFIX from its own
  ``prefix_lens[k]`` on; bt_rows [K, mp] int32 (padding rows all-zero: their
  writes land in the trash page). The caller must group rows so that
  ``prefix_lens[k] + S_pad <= max_seq`` for every row — ``_write_cache``'s
  dynamic_update_slice clamps out-of-range starts, which would shift a
  row's writes onto wrong slots (batch_scheduler groups admissions by
  this constraint). Returns (last-token logits [K, V], pool).

  A pool with per-slot state leaves (a hybrid's) is told ``slot_rows`` [K]
  int32 too: the state-space layers start row k from the state at its slot —
  from zeros where ``prefix_lens[k]`` is 0, which is what resets a slot for
  its next tenant — and leave the state after its last real token there. A
  padding row names a slot past the last; nothing is written for it.
  """
  from ..ops.paged import gather_row_pages, page_leaves, scatter_row_pages, state_leaves, touched_page_targets

  K, S = tokens.shape
  pages = page_leaves(pool)
  temp = {key: gather_row_pages(val, bt_rows, cfg.cache_kv_heads) for key, val in pages.items()}
  positions = prefix_lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
  if len(pages) < len(pool):
    h, carry = _hybrid_layers(
      embed_tokens(params, cfg, tokens), params, cfg, positions, {**temp, **state_leaves(pool)},
      slot_rows=slot_rows, fresh=prefix_lens == 0, seq_lens=prompt_lens - prefix_lens, adapter_ids=adapter_ids,
    )
    logits, temp, state = head_logits(params, cfg, _rows_at(h, prompt_lens - prefix_lens - 1)), page_leaves(carry), state_leaves(carry)
  else:
    logits, temp = shard_forward(params, cfg, shard, tokens, positions, temp, head_pos=prompt_lens - prefix_lens - 1, adapter_ids=adapter_ids)
    state = {}
  target = touched_page_targets(bt_rows, prefix_lens, prompt_lens, page_size)
  pool = {**{key: scatter_row_pages(pages[key], temp[key], target) for key in pages}, **state}
  return logits[:, 0, :], pool


_PAGES_MANY_STATIC = ("cfg", "shard", "page_size")
_pages_many = prefill_into_pages_many  # one body, one name in the trace, two jitted forms
prefill_into_pages_many = tracked_jit("prefill.pages_many", _pages_many, static_argnames=_PAGES_MANY_STATIC)
# The same program with the pool DONATED, for a pool that holds per-slot state: a second copy of that leaf
# (4.9 GB for 64 slots of granite-4.0-h-micro) does not fit beside the first, so its prefill writes in place
# and a failed one costs the pool (the scheduler then rebuilds it, as after a failed decode chunk).
prefill_into_pages_many_inplace = tracked_jit("prefill.pages_many", _pages_many, static_argnames=_PAGES_MANY_STATIC, donate_argnums=(4,))


@partial(tracked_jit, "sample.rows", static_argnames=("k_max",))
def sample_rows(logits, key, temps, top_ks, k_max: int):
  """First-token sampling for a batched admission: per-row temp/top_k over
  [K, V] logits in one device call instead of K host-side _sample_sync
  round trips (what batched admission exists to avoid).

  The UNFUSED epilogue: a second device dispatch after the prefill program.
  The fused variants below (``prefill_into_slots_sampled`` /
  ``prefill_into_pages_many_sampled``) run the IDENTICAL
  ``_next_token_batched`` on the in-program logits with the same key, so
  the sampled tokens match token-for-token — kept as the
  ``XOT_TPU_FUSED_SAMPLING=0`` A/B reference and for backends without the
  fused programs (pp/sp). As there, the draw is taken only when some row's
  temperature is positive (an all-greedy group pays the argmax alone) and the
  key is split either way, so the caller's key schedule does not depend on
  who samples."""
  tok, _ = _next_token_batched(logits, key, temps, top_ks, k_max)
  return tok


@tracked_jit("sample.merge_firsts")
def merge_first_tokens(chain, firsts, rows):
  """A prefill group's first tokens written into the decode chunk's chain token ON THE DEVICE (ISSUE 51): ``chain``
  [B, 1] is the next chunk's input token (the chunk in flight's ``next_token`` handle, or the host's tokens), ``firsts``
  [K] the group's sampled tokens, ``rows`` [K] each one's slot — the slot past the last for a row that takes no place
  in the next chunk (padding, an intermediate prefill chunk, a ``max_tokens`` of 1), which is dropped. The scheduler
  enqueues this behind the group and the next chunk behind it, so that chunk never waits for the group's readback.
  One program per group size, compiled in the dispatch that first compiles that group's prefill."""
  return chain.at[rows, 0].set(firsts.astype(chain.dtype), mode="drop")


# ------------------------------------------------ fused sampling epilogue
# (ISSUE 11): the batched admission path historically ran TWO device
# dispatches per prefill group — the prefill program, then ``sample_rows``
# over its last-token logits. The variants below fold the sampling epilogue
# into the prefill program itself (the logits never leave the device
# unsampled), so every admission (and every final prefill chunk feeding the
# PR 3 lookahead chain its seed token) costs one device dispatch fewer.
# Token-identical to prefill + ``sample_rows`` by construction: same
# ``_next_token_batched`` math, same key, same traced temps/top_ks.


@partial(tracked_jit, "prefill.slots_sampled", static_argnames=("cfg", "shard", "k_max"))
def prefill_into_slots_sampled(params, cfg: ModelConfig, shard: Shard, tokens, cache, rows, prompt_lens, temps, top_ks, key, k_max: int, adapter_ids=None):
  """``prefill_into_slots`` with the sampling epilogue fused in-program.

  Returns (first_tokens [K] int32, cache) — one dispatch where the unfused
  path took two."""
  last, cache = prefill_into_slots(params, cfg, shard, tokens, cache, rows, prompt_lens, adapter_ids)
  tok, _ = _next_token_batched(last, key, temps, top_ks, k_max)
  return tok, cache


def prefill_into_pages_many_sampled(params, cfg: ModelConfig, shard: Shard, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int, temps, top_ks, key, k_max: int, adapter_ids=None, slot_rows=None):
  """``prefill_into_pages_many`` with the sampling epilogue fused in-program
  (the paged-admission analogue of ``prefill_into_slots_sampled``)."""
  last, pool = prefill_into_pages_many(params, cfg, shard, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size, adapter_ids, slot_rows)
  tok, _ = _next_token_batched(last, key, temps, top_ks, k_max)
  return tok, pool


_pages_many_sampled = prefill_into_pages_many_sampled
prefill_into_pages_many_sampled = tracked_jit("prefill.pages_many_sampled", _pages_many_sampled, static_argnames=(*_PAGES_MANY_STATIC, "k_max"))
prefill_into_pages_many_sampled_inplace = tracked_jit("prefill.pages_many_sampled", _pages_many_sampled, static_argnames=(*_PAGES_MANY_STATIC, "k_max"), donate_argnums=(4,))


@component_scope("xot.sample")
def _next_token_batched(rows, key, temps, top_ks, k_max: int):
  """Per-row sampling: temp ≤ 0 rows greedy, others top-k at their own
  (traced) temperature and top_k (ops/sampling.py sample_logits_per_row).

  The draw (divide, ``top_k`` over the vocabulary, categorical) is taken only
  when some row's temperature is positive: a ``lax.cond`` on
  ``any(temps > 0)``, a predicate of the operand the program already has, so
  an all-greedy batch pays the argmax alone (ISSUE 47). The key advances
  either way — the split is outside the ``cond`` — so a sampling row draws
  the subkey it would have drawn had every earlier step sampled, and the
  speculative rounds keep the plain program's split-per-step schedule."""
  from ..ops.sampling import sample_logits_per_row

  greedy_rows = jnp.argmax(rows, axis=-1).astype(jnp.int32)
  key, sub = jax.random.split(key)

  def draw():
    safe_temp = jnp.where(temps > 0, temps, 1.0)
    sampled = sample_logits_per_row(rows, sub, safe_temp, top_ks, k_max=k_max)
    return jnp.where(temps > 0, sampled, greedy_rows)

  return jax.lax.cond(jnp.any(temps > 0), draw, lambda: greedy_rows), key


@partial(tracked_jit, "decode.batch", static_argnames=("cfg", "shard", "n_steps", "k_max"), donate_argnums=(4,))
def _fused_batch_decode_impl(params, cfg: ModelConfig, shard: Shard, token, cache, positions, active, temps, top_ks, n_steps: int, k_max: int, key, adapter_ids):
  def body(carry, _):
    tok, pos, cache, key = carry
    logits, new_cache = shard_forward(params, cfg, shard, tok, pos[:, None], cache, adapter_ids=adapter_ids)
    nxt, key = _next_token_batched(logits[:, 0, :], key, temps, top_ks, k_max)
    nxt = jnp.where(active, nxt, tok[:, 0])  # inactive rows hold their token
    pos = jnp.where(active, pos + 1, pos)  # ...and their position
    return (nxt[:, None], pos, new_cache, key), nxt

  (next_tok, pos, cache, _), toks = jax.lax.scan(body, (token, positions, cache, key), None, length=n_steps)
  return jnp.moveaxis(toks, 0, 1), next_tok, pos, cache


def fused_batch_decode(params, cfg: ModelConfig, shard: Shard, token, cache, positions, active, temps, n_steps: int, top_k=35, k_max: int = 64, key=None, adapter_ids=None):
  """One compiled decode chunk over the whole slot pool.

  token [B,1] int32 (each row's last token; inactive rows ignored),
  positions [B] int32, active [B] bool, temps [B] f32 (≤0 ⇒ greedy),
  top_k int or [B] int32 per-row (traced; clipped to the static ``k_max``).
  Returns (tokens [B, n_steps], next_token [B, 1], new positions [B], cache).
  ``next_token`` is the scan carry after the last step — each active row's
  final sampled token, inactive rows' held token — exactly the next chunk's
  input, as a DEVICE value: the scheduler's lookahead pipeline chains chunk
  N+1 from it without a host round trip (the host readback of ``tokens``
  streams back concurrently). Inactive rows do not advance and their cache
  rows stay untouched at their position.
  """
  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("fused_batch_decode requires a full-model shard")
  if key is None:
    key = jax.random.PRNGKey(0)
  B = token.shape[0]
  top_ks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
  return _fused_batch_decode_impl(
    params, cfg, shard, token, cache, positions, active.astype(jnp.bool_), jnp.asarray(temps, jnp.float32), top_ks, int(n_steps), int(k_max), key, adapter_ids
  )


# ------------------------------------------------------- paged serving
# (ops/paged.py + inference/batch_scheduler.py): the pooled cache above gives
# every slot max_seq tokens; the paged variants below map each row's logical
# positions onto fixed-size pages through a block table, so HBM is bounded by
# aggregate context and page-aligned prompt prefixes can be shared. Block
# tables are TRACED [B, mp] operands — one compiled program covers every
# allocation state. Rows without a request must keep their table zeroed (all
# writes land in the reserved trash page 0). The pool [L, P, Hkv, ps, hd] is
# one buffer a leaf from the donated argument to the result: the step loop
# and the layer loop carry it, a token write touches (layer, page, :, slot),
# and the attention reads its pages by (layer, page).


def _whole_expert_leaves(params: Params, cfg: ModelConfig) -> tuple:
  """The names of the stacked leaves a layer loop over ``params`` must not slice: the expert leaves (and their scale
  leaves), where the experts' product takes the grouped form (ops/moe.py ``ffn_form``, asked of the leaves as they
  are) — its kernels index the stack by layer, and a layer cut out ahead of a custom call would be a copy of the
  layer's experts, every step. () for the block form, whose einsums read a scan's slice in place. This is where a
  program's form is decided: a layer that gets these leaves whole (``_split_whole``) takes the grouped form, any other
  the block form (``_mlp_block``)."""
  from ..ops.moe import ffn_form

  for stack in (stack for name, stack in params.items() if name.endswith("moe_layers")):
    first = "w_experts_gate" if cfg.ffn_gated else "w_experts_up_t"
    if ffn_form(stack[first], stack["w_experts_down"], cfg.moe_capacity_factor, cfg.mosaic_kernels, f"{first}_scale" in stack, gated=cfg.ffn_gated) == "grouped":
      return tuple(name for name in stack if name.startswith("w_experts_"))
  return ()


def served_expert_form(params: Params | None, cfg: ModelConfig) -> str:
  """The form the experts' product takes in the programs that serve ``params`` over a cache or a pool (prefill, decode
  chunk, mixed tick): the label of the gauge ``moe_ffn_form``. Without params — a ``--pp`` / ``--sp`` ring holds its
  stages' weights itself, and its layer loops hand no stack over whole — the block form."""
  return "grouped" if params and _whole_expert_leaves(params, cfg) else "block"


def _split_whole(stack: Params, whole: tuple):
  """(the leaves a layer loop slices, ``put(lp, at)``: a layer's parameters with the ``whole`` leaves of the stack put
  beside them unsliced and ``expert_layer`` = ``at``, the layer's index in the stack, to take them at)."""
  kept = {name: stack[name] for name in whole if name in stack}
  if not kept:
    return stack, lambda lp, at: lp
  return {name: leaf for name, leaf in stack.items() if name not in kept}, lambda lp, at: {**lp, **kept, "expert_layer": at}


def _scan_layers_over_pool(step, h, stacks, pool: Params, whole: tuple = ()):
  """Run ``step(h, pool, layer_params, layer) → (h, pool)`` over the layers of
  ``stacks`` (stacked-parameter dicts, in model order) with the STACKED pool
  in the loop's carry (``h`` may be any pytree the step carries beside it).

  The pool is never a scan ``xs``/``ys``: as ``xs`` every layer's whole
  [P, Hkv, ps, hd] slice was cut out of the stacked leaf and as ``ys`` written
  back into a fresh one, every step, for every leaf (PERF.md §6, PR 29). In
  the carry, XLA has one buffer from the donated argument to the result, and
  a layer's step touches the token rows it writes and the pages it reads.
  ``layer`` is the pool's own layer index and runs on across the stacks, so a
  model of two (dense prefix + experts) indexes the one pool from both, with
  no split and no join.

  An entry of a model whose layers differ in kind (``cfg.mixed_layers``) is a
  run ``(stack, lo, hi, pool_lo, kind)`` (``_layer_runs``): layers [lo, hi) of
  a stack — ``kind`` their ``AttnKind`` where the model describes its
  attention layers one by one (None otherwise), which the step finds beside
  the layer's leaves as ``attn_kind``, static —, whose mixer's kind owns leaves
  of the pool of its own (pages for attention layers, per-slot state for recurrent layers) and
  counts its layers there from ``pool_lo`` on, so ``layer`` is the layer's
  index among the pool's layers of its kind, and the layer's parameters are
  read at their index in the stack inside the loop, as a scan reads its
  ``xs``: a slice of the stack cut out beforehand would be a copy of the run's
  weights.

  ``whole`` names leaves no layer is cut out of (``_whole_expert_leaves``): the
  step gets them as the stack has them, with the layer's index in the stack as
  ``expert_layer``."""
  first = 0
  for stack in stacks:
    if isinstance(stack, tuple):
      stack, lo, hi, pool_lo, kind = stack
      sliced, put = _split_whole(stack, whole)
      described = {} if kind is None else {"attn_kind": kind}  # static: the run's attention description beside its leaves

      def run_body(carry, at, sliced=sliced, put=put, shift=pool_lo - lo, described=described):
        lp = {name: jax.lax.dynamic_index_in_dim(leaf, at, 0, keepdims=False) for name, leaf in sliced.items()}
        return step(*carry, {**put(lp, at), **described}, at + shift if shift else at), None

      (h, pool), _ = jax.lax.scan(run_body, (h, pool), jnp.arange(lo, hi, dtype=jnp.int32))
      continue
    n = next(iter(stack.values())).shape[0]
    sliced, put = _split_whole(stack, whole)

    def body(carry, per_layer, put=put, first=first):
      lp, layer = per_layer
      return step(*carry, put(lp, layer - first), layer), None

    (h, pool), _ = jax.lax.scan(body, (h, pool), (sliced, first + jnp.arange(n, dtype=jnp.int32)))
    first += n
  return h, pool


def _layer_stacks(params: Params) -> list:
  """A full model's stacked layer parameters in model order: the dense layers, then the expert layers."""
  return [params[name] for name in ("layers", "moe_layers") if name in params]


def _layer_runs(params: Params, cfg: ModelConfig) -> list:
  """The layers of a model whose layers differ in kind (``cfg.mixed_layers``: recurrent layers beside attention
  layers, or attention kinds of different shapes) in the published order, as runs ``(stack, lo, hi, pool_lo, kind)``
  for ``_scan_layers_over_pool``: consecutive layers of one (mixer, FFN) pairing
  are layers [lo, hi) of that pairing's stack (``cfg.layer_stack``) and layers
  ``pool_lo`` on of the pool's leaves of their mixer's kind; ``kind`` is their ``AttnKind`` (``cfg.layer_attn``), or
  None. Laguna-XS.2's first stage is a full-attention layer with a dense FFN, three window layers with experts, a
  full-attention layer with experts: three stacks, three runs, one pool of five page layers. granite-4.0-h-micro
  is state-space runs of 5, 9, 9, 9 and 4 with an attention layer after each of
  the first four; Ling-3.0-flash's first stage a KDA layer with a dense FFN, four
  with experts, a latent-attention layer with experts, a KDA layer with experts.
  Any other model: its stacks, whole."""
  if not cfg.mixed_layers:
    return _layer_stacks(params)
  runs, in_stack, in_pool = [], {}, {True: 0, False: 0}
  for i in range(cfg.n_layers):
    name, recurrent = cfg.layer_stack(i), bool(cfg.layer_types) and cfg.layer_types[i] != "attention"
    at = in_stack.get(name, 0)
    if runs and runs[-1][0] == name:
      runs[-1][2] += 1
    else:
      runs.append([name, at, at + 1, in_pool[recurrent], cfg.layer_attn[i] if cfg.layer_attn else None])
    in_stack[name], in_pool[recurrent] = at + 1, in_pool[recurrent] + 1
  return [(params[name], *run) for name, *run in runs]


def _write_kv(pool: Params, k, v, layer, block_tables, pos, page_size: int, kv_quant: str, kernel: bool = False, interpret: bool = False) -> Params:
  """One token a row (k/v [B, Hkv, hd]) of one layer into the stacked pool:
  as it is for float pools, as per-(token, head) codes and scales for
  int8/int4 pages (models/quantize.py). ``kernel``: the pool is in the
  kernel's form and Mosaic writes it (ops/paged.py ``write_token_kv``)."""
  from ..ops.paged import page_leaves, write_token_kv

  new = {"k": k, "v": v}
  if kv_quant:
    from .quantize import quantize_kv, quantize_kv_int4

    quant_fn = quantize_kv_int4 if kv_quant == "int4" else quantize_kv
    (new["k"], new["k_scale"]), (new["v"], new["v_scale"]) = quant_fn(k), quant_fn(v)
  return {**pool, **write_token_kv(page_leaves(pool), new, layer, block_tables, pos, page_size, kernel, interpret)}


def _paged_layer_step(h, pool, p, layer, block_tables, positions, inv_freq, cfg: ModelConfig, page_size: int, use_kernel: bool, adapter_ids=None, kv_quant: str | None = None):
  """One decoder layer against the page pool — decode only (S == 1).

  ``pool`` is the STACKED page dict: {"k", "v"} [L, P, Hkv, ps, hd]
  (+ "k_scale"/"v_scale" [L, P, Hkv, ps, 1] when quantized; in the kernel's
  form on the kernel path — ops/paged.py ``kernel_pool_form``; float heads of
  64 stored in pairs, which only that module's accessors see: its note), ``layer``
  this layer's index into it; positions [B, 1]. ``kv_quant`` names the
  pool's mode where its shapes cannot (the kernel's form pads the code
  axis); None reads it off the stored shapes. Returns (h, pool, the experts
  its FFN visited: ``_mlp_block``).
  """
  B, S, D = h.shape
  with jax.named_scope("xot.attn_proj"):
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps) if "attn_norm" in p else h
  routed = _route_ahead(x, p, cfg)
  pos = positions[:, 0]
  lengths = pos + 1  # valid KV slots incl. the token written below
  from ..ops.paged import kernel_attends, paged_decode_attention, paged_gqa_attention_ref, paged_latent_decode_attention, paged_mla_attention_ref

  if kv_quant is None:
    kv_quant = pool_kv_quant(pool, cfg)
  kernel = kernel_attends(cfg, use_kernel)
  if "wkv_a" in p:
    # MLA: pages hold the latent ("k") and rope channel ("v"), one head entry. The kernel's latent body walks the
    # pages each row holds; the reference gathers the table's whole width.
    q_nope, q_pe, c_kv, k_pe = _mla_latents(x, p, cfg, positions, inv_freq)
    pool = _write_kv(pool, c_kv[:, 0][:, None, :], k_pe[:, 0][:, None, :], layer, block_tables, pos, page_size, "", kernel)
    with jax.named_scope("xot.attn"):  # the absorbed up-projection is the core's operand
      w_kv_b = _mla_w_kv_b(p, h.dtype)
    attend = paged_latent_decode_attention if kernel else paged_mla_attention_ref
    attn = attend(q_nope, q_pe, pool["k"], pool["v"], block_tables, lengths, w_kv_b, cfg.v_head_dim, page_size, layer=layer)
  else:
    q, k, v = _dense_qkv(x, p, cfg, positions, inv_freq, adapter_ids)
    pool = _write_kv(pool, k[:, 0], v[:, 0], layer, block_tables, pos, page_size, kv_quant, kernel)
    scales = {"k_scale_pool": pool["k_scale"], "v_scale_pool": pool["v_scale"]} if kv_quant else {}
    if kernel:
      # int8/int4-KV pages go straight through the kernel: codes + scales
      # stream per page tile with in-register dequant — the pool read stays
      # 1 byte/element (0.5 for packed int4; the gather fallback below moves
      # the same quantized bytes but materializes the gathered window).
      window = _layer_window(p)
      attn = paged_decode_attention(q[:, 0], pool["k"], pool["v"], block_tables, lengths, page_size, layer=layer, kv_quant=kv_quant, window=window, kv_heads=cfg.cache_kv_heads, **scales)[:, None]
    else:
      attn = paged_gqa_attention_ref(q, pool["k"], pool["v"], block_tables, lengths, page_size, layer=layer, **scales, **_attn_opts(cfg, p.get("is_sliding"), p.get("attn_kind")))
  h = _attn_out(h, x, attn, p, cfg)
  h, _, visited = _mlp_block(h, p, cfg, routed)
  return h, pool, visited


def paged_decode_forward(params, cfg: ModelConfig, shard: Shard, tokens, positions, pool, block_tables, page_size: int, use_kernel: bool, adapter_ids=None, kv_quant: str | None = None, active=None):
  """One decode step for all rows against the page pool.

  tokens [B, 1] int32 → (logits [B, 1, V], updated pool, experts visited). Full shard only
  (the batched server is single-node). The pool comes back in the form it
  came in (``_paged_layer_step``). A hybrid's recurrent layers step their
  per-slot state leaves of the pool instead (``_RECURRENT_LAYER``), for the
  rows ``active`` [B] names (None: all). The third result is how many
  distinct held experts the rows chose, summed over the expert layers (int32;
  what the grouped form of ops/moe.py visits; 0 without experts)."""
  h = embed_tokens(params, cfg, tokens)
  inv_freq = rope_inv_freq(cfg)
  if active is None:
    active = jnp.ones((tokens.shape[0],), jnp.bool_)

  def step(carry, pool, lp, layer):
    h, seen = carry
    if "w_out" in lp:  # a recurrent layer (an attention layer's output projection is ``wo``): ``layer`` counts the pool's state layers
      h, pool, visited = _RECURRENT_LAYER[cfg.recurrent_kind][1](h, pool, lp, layer, active, cfg, use_kernel)
    else:
      h, pool, visited = _paged_layer_step(h, pool, lp, layer, block_tables, positions, inv_freq, cfg, page_size, use_kernel, adapter_ids, kv_quant)
    return (h, seen + visited), pool

  (h, seen), pool = _scan_layers_over_pool(step, (h, jnp.int32(0)), _layer_runs(params, cfg), pool, _whole_expert_leaves(params, cfg))
  return head_logits(params, cfg, h), pool, seen


def _paged_decode_scan(params, cfg: ModelConfig, shard: Shard, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, use_kernel: bool, key, adapter_ids=None):
  """The chunked paged decode loop shared by ``fused_paged_batch_decode``
  and the mixed-tick program below — ONE definition of the per-step math, so
  the mixed tick's decode half is the plain program's decode half by
  construction (the token-identity contract of ISSUE 14). Returns (tokens,
  next token, positions, pool) and, for a model with routed experts, the
  distinct held experts visited, summed over expert layers and steps."""

  from ..ops.paged import kernel_attends, kernel_pool_form, stored_pool_form

  stored, kv_quant, kernel_form = pool, pool_kv_quant(pool, cfg), kernel_attends(cfg, use_kernel)
  if kernel_form:
    pool = kernel_pool_form(pool)  # once a dispatch, not once a layer: the steps write and read this form

  def body(carry, _):
    tok, pos, pool, key, seen = carry
    # Inactive rows would write into whatever page their table names; pin
    # their table to the trash page so held-token rewrites can't land on a
    # page another row now owns.
    bt = jnp.where(active[:, None], block_tables, 0)
    logits, pool, visited = paged_decode_forward(params, cfg, shard, tok, pos[:, None], pool, bt, page_size, use_kernel, adapter_ids, kv_quant, active)
    nxt, key = _next_token_batched(logits[:, 0, :], key, temps, top_ks, k_max)
    nxt = jnp.where(active, nxt, tok[:, 0])  # inactive rows hold their token
    pos = jnp.where(active, pos + 1, pos)  # ...and their position
    return (nxt[:, None], pos, pool, key, seen + visited), nxt

  (next_tok, pos, pool, _, seen), toks = jax.lax.scan(body, (token, positions, pool, key, jnp.int32(0)), None, length=n_steps)
  out = jnp.moveaxis(toks, 0, 1), next_tok, pos, stored_pool_form(pool, stored) if kernel_form else pool
  return (*out, seen) if cfg.n_experts else out  # a model without experts: the program keeps the four results it had


@partial(tracked_jit, "decode.paged_batch", static_argnames=("cfg", "shard", "n_steps", "k_max", "page_size", "use_kernel"), donate_argnums=(4,))
def _fused_paged_batch_decode_impl(params, cfg: ModelConfig, shard: Shard, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, use_kernel: bool, key, adapter_ids):
  return _paged_decode_scan(params, cfg, shard, token, pool, block_tables, positions, active, temps, top_ks, n_steps, k_max, page_size, use_kernel, key, adapter_ids)


def fused_paged_batch_decode(params, cfg: ModelConfig, shard: Shard, token, pool, block_tables, positions, active, temps, n_steps: int, top_k=35, k_max: int = 64, page_size: int = 64, use_kernel: bool | None = None, key=None, adapter_ids=None, experts_visited: bool = False):
  """``fused_batch_decode`` against the page pool.

  Same contract plus ``block_tables`` [B, mp] int32 — the host must have
  allocated pages covering [pos, pos + n_steps) for every active row before
  dispatch (inference/batch_scheduler.py does). Returns
  (tokens [B, n_steps], next_token [B, 1], positions [B], pool) —
  ``next_token`` is the device-resident chain input for the following chunk
  (see ``fused_batch_decode``). With ``experts_visited``, for a model with
  routed experts, a fifth: the number of distinct held experts the rows
  chose, summed over the chunk's expert layers and steps (int32 scalar; a
  model without experts keeps the four).

  ``use_kernel=None`` resolves to the Pallas kernels wherever they can run
  (ops/paged.py ``paged_kernel_supported``), the XLA forms elsewhere.
  """
  from ..ops.paged import paged_kernel_supported

  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("fused_paged_batch_decode requires a full-model shard")
  if key is None:
    key = jax.random.PRNGKey(0)
  if use_kernel is None:
    use_kernel = paged_kernel_supported(cfg)
  B = token.shape[0]
  top_ks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
  out = _fused_paged_batch_decode_impl(
    params, cfg, shard, token, pool, jnp.asarray(block_tables, jnp.int32), positions, active.astype(jnp.bool_),
    jnp.asarray(temps, jnp.float32), top_ks, int(n_steps), int(k_max), int(page_size), bool(use_kernel), key, adapter_ids,
  )
  return out if experts_visited else out[:4]


# --------------------------------------------------- mixed prefill+decode tick
# (inference/batch_scheduler.py, XOT_TPU_MIXED_TICK — ISSUE 14): the
# alternating scheduler dispatched chunked prefill and batched decode as
# strictly SEPARATE device programs, so every resident decode row idled for
# the full wall-clock of every prefill chunk (the head-of-line ITL hit the
# disagg bench quantified: mid-burst resident ITL 108 ms colocated vs 2.9 ms
# with a second node). The mixed tick removes the stall WITHOUT extra
# hardware (Sarathi-Serve / Orca style): ONE fused program per tick advances
# all resident rows by their decode chunk AND pushes one admission's prefill
# forward by a token-budgeted slice. Correct by page disjointness: the
# prefilling row's private pages are never in any decode row's block table
# (pages are private until donated at release), and shared prefix pages are
# read-only for both halves — so the decode half reads exactly the pool
# values the plain program would, and greedy decode streams are
# token-identical to the alternating baseline by construction (test-pinned).


MIXED_PREFILL_SCOPE = "mixed.prefill"  # the prefill half of a mixed tick in a device op's ``op_name``: a path component, and no ``xot.`` one


@partial(tracked_jit, "decode.mixed_paged_batch", static_argnames=("cfg", "shard", "n_steps", "k_max", "page_size", "use_kernel"), donate_argnums=(4,))
def _fused_mixed_paged_batch_decode_impl(params, cfg: ModelConfig, shard: Shard, token, pool, block_tables, positions, active, temps, top_ks, pf_tokens, pf_bt, pf_prefix, pf_end, n_steps: int, k_max: int, page_size: int, use_kernel: bool, key, adapter_ids, pf_adapter):
  from ..ops.paged import gather_row_pages, scatter_row_pages, touched_page_targets

  # Prefill half: the SAME gather → shard_forward → scatter math as
  # prefill_into_pages_many, minus the sampling epilogue — an intermediate
  # slice produces no token (the final slice, which samples, dispatches
  # through the ordinary admission path so first-token key-split semantics
  # are untouched). pf_prefix/pf_end are traced [1] scalars: slice length
  # changes within a pad bucket never recompile (the traced-budget contract).
  # The whole half lies under ONE outer scope that is no ``xot.`` component (ISSUE 55): a device op's ``op_name`` reads
  # ``…/mixed.prefill/xot.moe_experts/…``, so a reader of the profiler's trace tells the slice's work from the scan's by
  # one path component (benchmark/half_lib.py) while the component readers, which keep the ``xot.`` parts, read what they read.
  S = pf_tokens.shape[1]
  with jax.named_scope(MIXED_PREFILL_SCOPE):
    temp_c = {k: gather_row_pages(v, pf_bt, cfg.cache_kv_heads) for k, v in pool.items()}
    ppos = pf_prefix[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    _, temp_c = shard_forward(params, cfg, shard, pf_tokens, ppos, temp_c, head_pos=pf_end - pf_prefix - 1, adapter_ids=pf_adapter)
    target = touched_page_targets(pf_bt, pf_prefix, pf_end, page_size)
    pool = {k: scatter_row_pages(pool[k], temp_c[k], target) for k in pool}

  # Decode half: the plain program's scan, verbatim (_paged_decode_scan).
  return _paged_decode_scan(params, cfg, shard, token, pool, block_tables, positions, active, temps, top_ks, n_steps, k_max, page_size, use_kernel, key, adapter_ids)


def fused_mixed_paged_batch_decode(params, cfg: ModelConfig, shard: Shard, token, pool, block_tables, positions, active, temps, pf_tokens, pf_bt, pf_prefix, pf_end, n_steps: int, top_k=35, k_max: int = 64, page_size: int = 64, use_kernel: bool | None = None, key=None, adapter_ids=None, pf_adapter=None, experts_visited: bool = False):
  """``fused_paged_batch_decode`` with one admission's prefill slice fused in.

  Decode operands as in ``fused_paged_batch_decode``; the prefill slice is
  ``pf_tokens`` [1, S_pad] (the prompt's tokens from ``pf_prefix`` on,
  zero-padded), ``pf_bt`` [1, mp] (the admission's block-table row — the
  caller must have allocated pages covering ``pf_end``), and traced [1]
  scalars ``pf_prefix``/``pf_end`` bounding the slice's absolute positions
  (``pf_prefix + S_pad <= max_seq``, the scatter-clamp constraint of
  ``prefill_into_pages_many``). Returns the plain contract
  (tokens [B, n_steps], next_token [B, 1], positions [B], pool) — the slice
  emits nothing; its pages simply advance —, with ``experts_visited`` the
  decode half's count of expert visits as there. ``use_kernel=None`` resolves
  as in the plain program.
  """
  from ..ops.paged import paged_kernel_supported

  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("fused_mixed_paged_batch_decode requires a full-model shard")
  if cfg.is_mla:
    raise ValueError("fused_mixed_paged_batch_decode does not support MLA models")
  if key is None:
    key = jax.random.PRNGKey(0)
  if use_kernel is None:
    use_kernel = paged_kernel_supported(cfg)
  B = token.shape[0]
  top_ks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
  out = _fused_mixed_paged_batch_decode_impl(
    params, cfg, shard, token, pool, jnp.asarray(block_tables, jnp.int32), positions, active.astype(jnp.bool_),
    jnp.asarray(temps, jnp.float32), top_ks, jnp.asarray(pf_tokens, jnp.int32), jnp.asarray(pf_bt, jnp.int32),
    jnp.asarray(pf_prefix, jnp.int32), jnp.asarray(pf_end, jnp.int32),
    int(n_steps), int(k_max), int(page_size), bool(use_kernel), key,
    adapter_ids, None if pf_adapter is None else jnp.asarray(pf_adapter, jnp.int32),
  )
  return out if experts_visited else out[:4]


# ------------------------------------------- batched speculative serving
# (inference/batch_scheduler.py, XOT_TPU_SPEC_BATCH — ISSUE 7): draft-then-
# verify INSIDE the batched decode chunk. One chunk is ``n_rounds`` rounds;
# each round the draft proposes up to gamma_max tokens per row (sequential
# batched small-model steps against its own dense cache), the target scores
# every row's whole (gamma_max+1)-token window in ONE parallel forward, and
# each row advances by its own accepted-run length + 1 — a variable advance
# the paged pool absorbs exactly like the lookahead pipeline's drop-on-read:
# rejected tail positions hold garbage KV that the next round's window
# rewrites before anything reads it (same argument as
# fused_speculative_generate's free rollback).
#
# Per-row depth ``gammas`` [B] is TRACED: a row at gamma 0 degenerates to
# plain decode inside the same program (its window contributes exactly one
# target token per round), which is how the scheduler's acceptance-EWMA
# policy (inference/paging.py spec_adapt_gamma) lets rows where the draft
# isn't paying fall back WITHOUT dragging the batch onto a different compiled
# program. Greedy rows emit exactly the target's greedy trajectory for ANY
# draft; sampled (temp>0) rows always run gamma 0 and draw ONE sample per
# round from the verify logits' first position — with n_rounds equal to the
# plain chunk size their key-split schedule matches the plain program's
# one-split-per-step exactly.


def _paged_window_layer_step(h, pool, p, layer, block_tables, positions, inv_freq, cfg: ModelConfig, page_size: int, use_kernel: bool = False, interpret: bool = False, adapter_ids=None, kv_quant: str | None = None):
  """One decoder layer for a multi-token VERIFY window against the page pool. ("Window" here and in
  ``paged_window_forward`` is speculation's: the W tokens a round verifies — not a layer's attention window, which is
  ``AttnKind.window`` and rides along as the kernel's and the reference's operand.)

  ``pool`` is the stacked page dict and ``layer`` this layer's index into
  it, as in ``_paged_layer_step``. positions [B, W] are each row's own
  absolute window positions (rows are at different depths). Writes all W
  tokens' KV through the block tables, then attends per window position
  through the Pallas kernel where it attends (ops/paged.py
  ``kernel_attends`` — W is small and static, so the window unrolls into W
  one-query kernel launches; each query's ``lengths`` is its own
  position+1, the same mask the reference's causal window applies, and the
  batched pool read per launch is exactly a decode step's), or via the
  gather reference otherwise. Before ISSUE 11 the verify ALWAYS took the
  gather reference — batched speculation forfeited the kernel win its plain
  chunks had. MLA is unsupported here (the scheduler keeps MLA models on the
  plain chunk program in paged mode)."""
  B, W, D = h.shape
  with jax.named_scope("xot.attn_proj"):
    x = rms_norm(h, p["attn_norm"], cfg.norm_eps) if "attn_norm" in p else h
  routed = _route_ahead(x, p, cfg)
  from ..ops.paged import kernel_attends, paged_decode_attention, paged_gqa_attention_ref

  if kv_quant is None:
    kv_quant = pool_kv_quant(pool, cfg)
  q, k, v = _dense_qkv(x, p, cfg, positions, inv_freq, adapter_ids)
  lengths = positions[:, -1] + 1  # valid KV slots incl. the window's writes
  kernel = kernel_attends(cfg, use_kernel)
  window = _layer_window(p)  # the LAYER's window (``AttnKind.window``), not this step's verify window W
  for j in range(W):  # W is small (gamma_max+1) and static; per-token scales, the values a one-token-at-a-time write produces
    pool = _write_kv(pool, k[:, j], v[:, j], layer, block_tables, positions[:, j], page_size, kv_quant, kernel, interpret)
  scales = {"k_scale_pool": pool["k_scale"], "v_scale_pool": pool["v_scale"]} if kv_quant else {}
  if kernel:
    # Kernel route: one tuned-kernel launch per window position, each masked
    # by its own query's length. Token-exact against the gather route (A/B-pinned).
    attn = jnp.stack(
      [
        paged_decode_attention(q[:, j], pool["k"], pool["v"], block_tables, positions[:, j] + 1, page_size, interpret=interpret, layer=layer, kv_quant=kv_quant, window=window, kv_heads=cfg.cache_kv_heads, **scales)
        for j in range(W)
      ],
      axis=1,
    )  # [B, W, Hq, hd]
  else:  # gather route: one multi-query reference call
    attn = paged_gqa_attention_ref(q, pool["k"], pool["v"], block_tables, lengths, page_size, q_positions=positions, layer=layer, **scales, **_attn_opts(cfg, p.get("is_sliding"), p.get("attn_kind")))
  h = _attn_out(h, x, attn, p, cfg)
  h, *_ = _mlp_block(h, p, cfg, routed)
  return h, pool


def paged_window_forward(params, cfg: ModelConfig, shard: Shard, tokens, positions, pool, block_tables, page_size: int, use_kernel: bool = False, interpret: bool = False, adapter_ids=None, kv_quant: str | None = None):
  """W-token forward for every row against the page pool — the batched
  speculative VERIFY pass. tokens/positions [B, W] → (logits [B, W, V],
  updated pool, in the form it came in; the "window" is the verify's W tokens, not an attention window). Full shard only. ``use_kernel``
  routes each window position through the tuned Pallas kernel instead of
  the gather reference (``_paged_window_layer_step``; A/B-pinned token-exact)."""
  if cfg.is_mla:
    raise ValueError("paged_window_forward does not support MLA models")
  h = embed_tokens(params, cfg, tokens)
  inv_freq = rope_inv_freq(cfg)

  def step(h, pool, lp, layer):
    return _paged_window_layer_step(h, pool, lp, layer, block_tables, positions, inv_freq, cfg, page_size, use_kernel, interpret, adapter_ids, kv_quant)

  h, pool = _scan_layers_over_pool(step, h, _layer_runs(params, cfg), pool)
  return head_logits(params, cfg, h), pool


def _spec_batch_rounds(params_d, cfg_d: ModelConfig, shard_d: Shard, verify, token, carry_t, cache_d, positions, active, gammas, temps, top_ks, n_rounds: int, gamma_max: int, k_max: int, key, props=None, prop_counts=None):
  """The shared draft→verify→accept round loop of both batched spec programs.

  ``verify(window [B,W], wpos [B,W], carry_t)`` runs the target over each
  row's window and returns (logits [B,W,V], carry_t) — the dense impl closes
  over the slot cache, the paged impl over (pool, block tables). Returns
  (buf [B, n_rounds·W], counts [B], n_prop [B], next_tok [B,1],
  next_pos [B], carry_t, cache_d): row i's first counts[i] buffer slots are
  its emitted tokens, in order; slots past counts[i] are overwritten
  leftovers the host drops; n_prop[i] is the number of draft tokens actually
  proposed for row i across the chunk (the host's acceptance-EWMA
  denominator — rounds·gamma for model-drafted rows, the consumed stream
  length for host-proposed rows).

  HOST-PROPOSED rows (ISSUE 12): ``props`` [B, L] carries each row's n-gram
  reference STREAM (the continuation that followed the matched suffix
  earlier in prompt+generated history), ``prop_counts`` [B] its valid
  length (0 = no proposal: the row runs plain). A proposed row drafts the
  next G stream tokens each round for as long as it stays ON-STREAM — every
  verified token so far (accepted draft AND the target's own correction)
  continued the reference exactly — so a row tracking a long quote keeps
  full depth across all ``n_rounds`` rounds of the chunk, not just the
  first (the LLMA multi-round continuation); the first divergence drops it
  to plain for the rest of the chunk. Greedy identity holds for ANY stream
  content: the stream only ever supplies draft tokens, and the accept rule
  compares them to the target's own greedy choices.

  ``params_d is None`` compiles the DRAFT-FREE variant (n-gram/plain rows
  only): the draft proposal scan and the draft catch-up forward are absent
  from the program entirely, and ``cache_d`` passes through untouched."""
  B = token.shape[0]
  G = gamma_max
  W = G + 1
  widx = jnp.arange(W, dtype=jnp.int32)
  buf0 = jnp.zeros((B, n_rounds * W), dtype=jnp.int32)
  if props is not None and G > 0:
    # Pad so the per-round dynamic_slice window [counts, counts+G) is always
    # in range (counts can reach (n_rounds-1)·W before the last round).
    props_pad = jnp.concatenate([props.astype(jnp.int32), jnp.zeros((B, n_rounds * W + G - props.shape[1]), jnp.int32)], axis=1)

  def body(carry, _):
    tok, pos, carry_t, cache_d, buf, counts, n_prop, on_stream, key = carry

    if params_d is not None:
      # 1) Draft proposes G tokens per row, greedily (batched sequential
      #    steps — the same single-token program shape as plain decode,
      #    small model).
      def dstep(c, _):
        t, p, cd = c
        logits, cd = shard_forward(params_d, cfg_d, shard_d, t, p[:, None], cd)
        nxt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        return (nxt[:, None], p + 1, cd), nxt

      (_, _, cache_d), d = jax.lax.scan(dstep, (tok, pos, cache_d), None, length=G)
      d = jnp.moveaxis(d, 0, 1)  # [B, G]
    else:
      d = jnp.zeros((B, G), dtype=jnp.int32)

    # 1b) Host-proposed rows draft the next G tokens of their reference
    #     stream instead; once off-stream they propose nothing (geff 0) and
    #     decode plain for the rest of the chunk.
    geff = gammas
    if props is not None and G > 0:
      d_stream = jax.vmap(lambda s, o: jax.lax.dynamic_slice(s, (o,), (G,)))(props_pad, counts)
      is_prop = prop_counts > 0
      use_prop = is_prop & on_stream
      d = jnp.where(use_prop[:, None], d_stream, d)
      remaining = jnp.maximum(prop_counts - counts, 0)
      geff = jnp.where(is_prop, jnp.where(use_prop, jnp.minimum(remaining, gammas), 0), gammas)

    # 2) Target verifies every row's window [tok, d_1..d_G] in ONE forward.
    window = jnp.concatenate([tok, d], axis=1)  # [B, W]
    wpos = pos[:, None] + widx[None, :]
    logits_t, carry_t = verify(window, wpos, carry_t)
    t_greedy = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)  # [B, W]
    # One key split per ROUND — with n_rounds == the plain chunk size this is
    # the plain program's exact split-per-step schedule, so sampled rows draw
    # identical subkeys under either program.
    nxt0, key = _next_token_batched(logits_t[:, 0, :], key, temps, top_ks, k_max)

    # 3) Per-row greedy acceptance, capped at the row's own traced depth;
    #    sampled rows accept nothing (their draft run is scaffolding only).
    matches = (d == t_greedy[:, :G]).astype(jnp.int32) * (widx[None, :G] < geff[:, None]).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)  # [B]
    n_acc = jnp.where(temps > 0, 0, n_acc)
    corr = jnp.take_along_axis(t_greedy, n_acc[:, None], axis=1)[:, 0]  # target's own next token
    corr = jnp.where(temps > 0, nxt0, corr)
    d_pad = jnp.concatenate([d, jnp.zeros((B, 1), jnp.int32)], axis=1)
    emitted = jnp.where(widx[None, :] < n_acc[:, None], d_pad, corr[:, None])  # [B, W]
    # Per-row append at each row's own offset; slots past k_adv hold the
    # correction token and are overwritten by the next round's append.
    buf = jax.vmap(lambda b, e, o: jax.lax.dynamic_update_slice(b, e, (o,)))(buf, emitted, counts)

    if params_d is not None:
      # 4) Draft catch-up: the window through the draft so its cache covers
      #    every accepted position (the sequential proposal never writes the
      #    last proposed token's KV — see _fused_spec_generate_impl). Also
      #    keeps the draft warm for host-proposed rows that may switch back.
      _, cache_d = shard_forward(params_d, cfg_d, shard_d, window, wpos, cache_d)

    if props is not None and G > 0:
      # On-stream iff the whole window continued the reference: full
      # acceptance AND the correction token is the stream's next token.
      nxt_idx = jnp.clip(counts + n_acc, 0, props_pad.shape[1] - 1)
      cont = jnp.take_along_axis(props_pad, nxt_idx[:, None], axis=1)[:, 0]
      on_stream = use_prop & (n_acc == geff) & (counts + n_acc < prop_counts) & (corr == cont)

    k_adv = jnp.where(active, n_acc + 1, 0)  # inactive rows hold token & position
    n_prop = n_prop + jnp.where(active, geff, 0)
    new_tok = jnp.where(active, corr, tok[:, 0])[:, None]
    return (new_tok, pos + k_adv, carry_t, cache_d, buf, counts + k_adv, n_prop, on_stream, key), None

  counts0 = jnp.zeros((B,), dtype=jnp.int32)
  on0 = (prop_counts > 0) if props is not None else jnp.zeros((B,), jnp.bool_)
  (next_tok, next_pos, carry_t, cache_d, buf, counts, n_prop, _, _), _ = jax.lax.scan(
    body, (token, positions, carry_t, cache_d, buf0, counts0, counts0, on0, key), None, length=n_rounds
  )
  return buf, counts, n_prop, next_tok, next_pos, carry_t, cache_d


@partial(tracked_jit, "spec.batch", static_argnames=("cfg", "shard", "cfg_d", "shard_d", "n_rounds", "gamma_max", "k_max"), donate_argnums=(2, 3))
def _fused_spec_batch_decode_impl(params, params_d, cache, cache_d, token, positions, active, gammas, temps, top_ks, key, props, prop_counts, adapter_ids, cfg: ModelConfig, shard: Shard, cfg_d: ModelConfig, shard_d: Shard, n_rounds: int, gamma_max: int, k_max: int):
  def verify(window, wpos, cache):
    # The TARGET applies each row's adapter (ISSUE 15) — greedy identity vs
    # the merged solo reference holds for ANY draft because the accept rule
    # compares against the adapter-applied target's own greedy choices; the
    # draft stays base (a worse draft only lowers acceptance, never output).
    return shard_forward(params, cfg, shard, window, wpos, cache, adapter_ids=adapter_ids)

  return _spec_batch_rounds(params_d, cfg_d, shard_d, verify, token, cache, cache_d, positions, active, gammas, temps, top_ks, n_rounds, gamma_max, k_max, key, props, prop_counts)


@partial(tracked_jit, "spec.paged_batch", static_argnames=("cfg", "shard", "cfg_d", "shard_d", "n_rounds", "gamma_max", "k_max", "page_size", "use_kernel", "interpret"), donate_argnums=(2, 3))
def _fused_spec_paged_batch_decode_impl(params, params_d, pool, cache_d, token, block_tables, positions, active, gammas, temps, top_ks, key, props, prop_counts, adapter_ids, cfg: ModelConfig, shard: Shard, cfg_d: ModelConfig, shard_d: Shard, n_rounds: int, gamma_max: int, k_max: int, page_size: int, use_kernel: bool, interpret: bool):
  # Inactive rows' window writes must not land on pages another row may now
  # own: pin their tables to the trash page once (tables are chunk-constant).
  from ..ops.paged import kernel_attends, kernel_pool_form, stored_pool_form

  bt = jnp.where(active[:, None], block_tables, 0)
  stored, kv_quant, kernel_form = pool, pool_kv_quant(pool, cfg), kernel_attends(cfg, use_kernel)
  if kernel_form:
    pool = kernel_pool_form(pool)  # once a dispatch, as in _paged_decode_scan

  def verify(window, wpos, pool):
    return paged_window_forward(params, cfg, shard, window, wpos, pool, bt, page_size, use_kernel, interpret, adapter_ids, kv_quant)

  *out, pool, cache_d = _spec_batch_rounds(params_d, cfg_d, shard_d, verify, token, pool, cache_d, positions, active, gammas, temps, top_ks, n_rounds, gamma_max, k_max, key, props, prop_counts)
  return (*out, stored_pool_form(pool, stored) if kernel_form else pool, cache_d)


def _spec_batch_args(shard: Shard, token, active, gammas, temps, top_k, k_max: int, key):
  if not (shard.is_first_layer and shard.is_last_layer):
    raise ValueError("batched speculative decode requires a full-model shard")
  if key is None:
    key = jax.random.PRNGKey(0)
  B = token.shape[0]
  top_ks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (B,))
  return (
    jnp.asarray(token), jnp.asarray(active).astype(jnp.bool_), jnp.asarray(gammas, jnp.int32),
    jnp.asarray(temps, jnp.float32), top_ks, key,
  )


def _spec_props_args(props, prop_counts, B: int, n_rounds: int, gamma_max: int):
  """Normalize the host-proposal pair (ISSUE 12): both None (no n-gram rows
  this dispatch — compiles the props-free program) or a [B, ≤worst+G]
  int32 stream buffer + [B] valid counts, clipped to what the chunk can
  consume."""
  if props is None:
    return None, None
  cap = n_rounds * (gamma_max + 1) + gamma_max
  props = jnp.asarray(props, jnp.int32)[:, :cap]
  counts = jnp.minimum(jnp.asarray(prop_counts, jnp.int32), props.shape[1])
  if props.shape[0] != B:
    raise ValueError(f"props batch {props.shape[0]} != token batch {B}")
  return props, counts


def fused_spec_batch_decode(params, cfg: ModelConfig, shard: Shard, params_d, cfg_d: ModelConfig, shard_d: Shard, token, cache, cache_d, positions, active, gammas, temps, n_rounds: int, gamma_max: int, top_k=35, k_max: int = 64, key=None, props=None, prop_counts=None, adapter_ids=None):
  """``fused_batch_decode`` with draft-then-verify rounds (dense slot cache).

  token [B,1] / positions [B] / active [B] / temps [B] as in
  ``fused_batch_decode``; ``gammas`` [B] int32 is each row's traced
  speculation depth (0 ⇒ plain decode for that row), clamped to the static
  ``gamma_max``; ``cache_d`` is the draft's OWN dense slot cache (same slot
  indexing, prefilled by the scheduler at admission). Returns
  (tokens [B, n_rounds·(gamma_max+1)], counts [B], n_prop [B],
  next_token [B,1], next_positions [B], cache, cache_d) — counts[i] of row
  i's buffer slots are valid; n_prop[i] is the tokens actually drafted for
  row i (the acceptance denominator); next_token/next_positions are DEVICE
  handles so the scheduler's lookahead pipeline chains chunk N+1 without
  knowing chunk N's variable advance host-side.

  ISSUE 12: ``props``/``prop_counts`` carry per-row HOST-PROPOSED reference
  streams (inference/ngram.py) — those rows skip the draft model entirely
  and draft from their stream while it keeps verifying (see
  ``_spec_batch_rounds``). ``params_d=None`` compiles the DRAFT-FREE
  program (no draft scan, no catch-up, ``cache_d`` passes through): the
  spec path no longer requires a loaded draft pair.
  """
  token, active, gammas, temps, top_ks, key = _spec_batch_args(shard, token, active, gammas, temps, top_k, k_max, key)
  props, prop_counts = _spec_props_args(props, prop_counts, token.shape[0], int(n_rounds), int(gamma_max))
  return _fused_spec_batch_decode_impl(
    params, params_d, cache, cache_d, token, positions, active, jnp.minimum(gammas, gamma_max), temps, top_ks, key,
    props, prop_counts, adapter_ids, cfg, shard, cfg_d, shard_d, int(n_rounds), int(gamma_max), int(k_max),
  )


def fused_spec_paged_batch_decode(params, cfg: ModelConfig, shard: Shard, params_d, cfg_d: ModelConfig, shard_d: Shard, token, pool, cache_d, block_tables, positions, active, gammas, temps, n_rounds: int, gamma_max: int, top_k=35, k_max: int = 64, page_size: int = 64, use_kernel: bool | None = None, interpret: bool = False, key=None, props=None, prop_counts=None, adapter_ids=None):
  """``fused_spec_batch_decode`` against the page pool.

  Same contract plus ``block_tables`` [B, mp]: the host must have allocated
  pages covering every row's WORST-CASE advance
  ``n_rounds·(gamma_max+1)`` before dispatch
  (inference/paging.py ``spec_worst_advance`` — the gamma-deep analogue of
  the lookahead pipeline's one-extra-chunk headroom). ``use_kernel=None``
  resolves as in ``fused_paged_batch_decode`` — where the kernel attends, the
  verify window runs per-position through it instead of the gather
  reference (A/B-pinned token-exact); the draft keeps its dense slot cache
  either way. ``props``/``prop_counts``/
  ``params_d=None`` as in ``fused_spec_batch_decode`` (ISSUE 12).
  """
  from ..ops.paged import paged_kernel_supported

  if cfg.is_mla:
    raise ValueError("fused_spec_paged_batch_decode does not support MLA models (use the dense layout)")
  if use_kernel is None:
    use_kernel = paged_kernel_supported(cfg)
  token, active, gammas, temps, top_ks, key = _spec_batch_args(shard, token, active, gammas, temps, top_k, k_max, key)
  props, prop_counts = _spec_props_args(props, prop_counts, token.shape[0], int(n_rounds), int(gamma_max))
  return _fused_spec_paged_batch_decode_impl(
    params, params_d, pool, cache_d, token, jnp.asarray(block_tables, jnp.int32), positions, active,
    jnp.minimum(gammas, gamma_max), temps, top_ks, key,
    props, prop_counts, adapter_ids, cfg, shard, cfg_d, shard_d, int(n_rounds), int(gamma_max), int(k_max), int(page_size), bool(use_kernel), bool(interpret),
  )


@partial(tracked_jit, "prefill.pages", static_argnames=("cfg", "shard", "page_size"))
def prefill_into_pages(params, cfg: ModelConfig, shard: Shard, tokens, pool, bt_row, prefix_len, prompt_len, page_size: int):
  """Prefill one request's prompt SUFFIX into its pages.

  tokens [1, S_pad] int32 — the prompt tokens from ``prefix_len`` on (the
  page-aligned reused prefix is skipped: its KV is already in the shared
  pages named by ``bt_row``). bt_row [mp] int32; prefix_len/prompt_len are
  traced scalars (prompt_len is the FULL prompt length). Returns
  (last-token logits [1, V], pool).

  Strategy: gather the row's pages into a contiguous [L, 1, mp·ps, H, hd]
  cache, run the ordinary ``shard_forward`` prefill at positions
  [prefix_len, prefix_len + S_pad), then scatter the touched pages back.
  Untouched/unallocated table entries scatter into the trash page 0. Not
  donated for the same reason as ``prefill_into_slot``: a failed prefill
  must leave the shared pool intact.
  """
  from ..ops.paged import gather_row_pages, scatter_row_pages, touched_page_targets

  S = tokens.shape[1]
  bt_rows = bt_row[None]
  temp = {key: gather_row_pages(val, bt_rows, cfg.cache_kv_heads) for key, val in pool.items()}
  positions = (prefix_len + jnp.arange(S, dtype=jnp.int32))[None, :]
  logits, temp = shard_forward(params, cfg, shard, tokens, positions, temp)
  target = touched_page_targets(bt_rows, jnp.reshape(prefix_len, (1,)), jnp.reshape(prompt_len, (1,)), page_size)  # trash page for the rest
  pool = {key: scatter_row_pages(pool[key], temp[key], target) for key in pool}
  idx = (prompt_len - prefix_len - 1).reshape(1, 1, 1)
  last = jnp.take_along_axis(logits, jnp.broadcast_to(idx, (1, 1, logits.shape[-1])), axis=1)[:, 0, :]
  return last, pool


# ------------------------------------------------------------- scoring
# (OpenAI ``logprobs``): the serving fast paths return token ids only — one
# readback per response is the whole point — so logprobs are recomputed
# post-hoc in ONE parallel forward over prompt+completion, only when a client
# asks. The head runs on just the scored positions' hidden states (full-
# sequence logits would be [S, V] fp32 — ~2 GB at a 4K/128K-vocab request).


@partial(tracked_jit, "prefill.score_last", static_argnames=("cfg", "shard", "n_scored", "top_n"))
def score_last_tokens(params, cfg: ModelConfig, shard: Shard, tokens, seq_len, n_scored: int, top_n: int):
  """Logprobs of the last ``n_scored`` tokens of a [1, S_pad] sequence.

  ``seq_len`` (traced) is the real length; padding beyond it is inert under
  causal attention. Returns (chosen_logprob [n], top_ids [n, top_n],
  top_logprobs [n, top_n]) — top-k always computed (static shape); callers
  slice host-side. Full-model shards only.
  """
  h = embed_tokens(params, cfg, tokens)
  inv_freq = rope_inv_freq(cfg)
  B, S = tokens.shape
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

  def body(carry, lp):
    h, _aux = carry
    h, _, aux = _layer_step(h, lp, None, positions, positions[0], inv_freq, cfg, False)
    return (h, _aux + aux), None

  if cfg.mixed_layers:  # the same mixers (a recurrent one from a zero state), in the published order
    h, _ = _hybrid_layers(h, params, cfg, positions, {})
  else:
    for stack in _layer_stacks(params):
      (h, _), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), stack)

  # Hidden states at positions [L-n-1, L-2] predict tokens [L-n, L-1].
  # ``n_scored`` is BUCKETED by the caller (jax_engine.score_tokens) so one
  # compiled program serves every completion length in a bucket; the clip
  # keeps over-bucketed leading indices in range (their rows are garbage and
  # the caller slices them off host-side).
  idx = jnp.clip(seq_len - n_scored - 1 + jnp.arange(n_scored, dtype=jnp.int32), 0, tokens.shape[1] - 2)  # [n]
  hs = jnp.take_along_axis(h, jnp.broadcast_to(idx[None, :, None], (1, n_scored, h.shape[-1])), axis=1)
  logits = head_logits(params, cfg, hs)[0]  # [n, V]
  logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
  chosen = jnp.take_along_axis(tokens[0], idx + 1, axis=0)  # [n]
  chosen_lp = jnp.take_along_axis(logp, chosen[:, None], axis=1)[:, 0]
  top_lp, top_ids = jax.lax.top_k(logp, top_n)
  return chosen_lp, top_ids, top_lp


def full_model_params(key: jax.Array, cfg: ModelConfig, model_id: str = "model", dtype=None) -> tuple[Params, Shard]:
  shard = Shard(model_id, 0, cfg.n_layers - 1, cfg.n_layers)
  return init_shard_params(key, cfg, shard, dtype=dtype), shard


def slice_shard_params(params: Params, cfg: ModelConfig, full_shard: Shard, sub: Shard) -> Params:
  """Carve a sub-shard's params out of full-model params (tests, local PP)."""
  out: Params = {}
  stack_start = full_shard.start_layer  # global index of each stack's first layer
  for name in ("layers", "moe_layers"):
    if name not in params:
      continue
    stack = params[name]
    L = next(iter(stack.values())).shape[0]
    lo = max(sub.start_layer - stack_start, 0)
    hi = min(sub.end_layer + 1 - stack_start, L)
    if hi > lo:
      out[name] = {k: v[lo:hi] for k, v in stack.items()}
    stack_start += L
  if sub.is_first_layer:
    out["embed"] = params["embed"]
  if sub.is_last_layer:
    out["final_norm"] = params["final_norm"]
    if "lm_head" in params:
      out["lm_head"] = params["lm_head"]
    elif not sub.is_first_layer:
      out["lm_head"] = params["embed"].T
  return out
