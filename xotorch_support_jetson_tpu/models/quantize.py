"""Weight quantization for the general decoder: int8 per-output-channel.

The reference ships quantized checkpoints as separate registry entries
(``models.py:29`` llama-3.1-405b-8bit) and otherwise runs whatever dtype the
checkpoint has. Here quantization is a first-class engine mode instead:
any registry model can be loaded with ``XOT_TPU_QUANT=int8``, halving the
HBM bytes per decode step — single-token decode is bandwidth-bound on TPU,
so weight bytes ≈ decode latency.

Two compute modes for a quantized matmul (selected per-call):

- ``w8a16`` (weight-only): int8 weights are upcast next to the dot;
  activations stay bf16. Numerically safest.
- ``w8a8`` (dynamic): activations are quantized per-row symmetric to int8 on
  the fly and the dot runs int8×int8→int32 on the MXU's int8 path, then
  rescales by (row_scale × channel_scale). Half the weight traffic AND the
  int8 MXU rate; small extra quantization error on activations.

Quantized params keep the same pytree names with an added ``<name>_scale``
leaf, so sharding specs and checkpoint code treat them like any other leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.programs import component_scope

# Stacked weight leaves eligible for quantization (last two dims [in, out];
# expert leaves carry extra leading axes) plus the top-level lm_head.
# Norm gains, biases, routers, LoRA adapters and the embedding table stay in
# model dtype (embed rows are gathered, not matmul'd; quantizing it would
# also quantize a tied LM head; routers are tiny and accuracy-critical).
_MLA_LEAVES = ("wq_a", "wq_b", "wkv_a", "wkv_b")
QUANT_STACK_LEAVES = {
  "layers": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", *_MLA_LEAVES),
  "moe_layers": (
    "wq",
    "wk",
    "wv",
    "wo",
    *_MLA_LEAVES,
    "w_experts_gate",
    "w_experts_up",
    "w_experts_down",
    "w_shared_gate",
    "w_shared_up",
    "w_shared_down",
  ),
}
QUANT_TOP_LEAVES = ("lm_head",)


def quantize_weight(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Symmetric per-output-channel int8: w ≈ q * scale[..., None, :].

  w [..., in, out] → (q int8 [..., in, out], scale f32 [..., out]).
  """
  absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
  scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
  q = jnp.round(w.astype(jnp.float32) / scale[..., None, :]).astype(jnp.int8)
  return q, scale


def quantize_weight_int4(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Symmetric per-output-channel int4, PACKED two values per int8 byte
  along the IN axis (even rows in the low nibble, odd rows in the high):
  w [..., in, out] → (packed int8 [..., in/2, out], scale f32 [..., out]).

  The halved in-axis is how the quantization is detected downstream
  (``qdot`` / decoder._mm compare it against the activation width), so scale
  leaves keep the same ``<name>_scale`` name and every sharding spec /
  checkpoint path treats int4 exactly like int8.
  """
  if w.shape[-2] % 2:
    raise ValueError(f"int4 packing needs an even in-dim; got {w.shape}")
  absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
  scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
  q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[..., None, :]), -8, 7).astype(jnp.int8)
  lo = q[..., 0::2, :] & 0x0F
  hi = (q[..., 1::2, :] & 0x0F) << 4
  return (lo | hi).astype(jnp.int8), scale


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
  """packed int8 [..., in/2, out] → int8 [..., in, out] (sign-extended)."""
  lo = (packed << 4) >> 4  # arithmetic shifts on int8 sign-extend the nibble
  hi = packed >> 4
  pair = jnp.stack([lo, hi], axis=-2)  # [..., in/2, 2, out]
  return pair.reshape(*packed.shape[:-2], packed.shape[-2] * 2, packed.shape[-1])


def quantize_params(params: dict, mode: str = "int8") -> dict:
  """Quantize a shard's params in place-shape: returns a new pytree where
  each eligible leaf ``w`` becomes int8 (or packed int4) with a sibling
  ``w_scale``."""
  if mode not in ("int8", "int4"):
    raise ValueError(f"unsupported quantization mode {mode!r}")
  quant = quantize_weight if mode == "int8" else quantize_weight_int4
  out = dict(params)
  for stack_name, eligible in QUANT_STACK_LEAVES.items():
    if stack_name not in params:
      continue
    stack = dict(params[stack_name])
    for name in eligible:
      if name in stack and stack[name].dtype != jnp.int8:
        if mode == "int4" and stack[name].shape[-2] % 2:
          continue  # odd in-dim can't pack; leaf stays full precision
        q, s = quant(stack[name])
        stack[name] = q
        stack[f"{name}_scale"] = s
    out[stack_name] = stack
  for name in QUANT_TOP_LEAVES:
    if name in out and out[name].dtype != jnp.int8:
      if mode == "int4" and out[name].shape[-2] % 2:
        continue  # odd in-dim can't pack; leaf stays full precision
      q, s = quant(out[name])
      out[name] = q
      out[f"{name}_scale"] = s
  if "lm_head" not in out and "embed" in out and "final_norm" in out and not (mode == "int4" and out["embed"].shape[-1] % 2):
    # Tied embeddings: materialize a quantized copy of the head so decode
    # reads ≤1 byte/param for the [D,V] projection (the single biggest
    # weight read per token); the bf16 table stays for the embedding gather.
    q, s = quant(out["embed"].T)
    out["lm_head"] = q
    out["lm_head_scale"] = s
  return out


# ------------------------------------------------------------ int8 KV cache
#
# Long-context decode is HBM-bound on the CACHE read (measured ~35-45 GB/s
# effective at 32K on v5e — ops/pallas_attention.py flash_decode_supported),
# so halving cached bytes ≈ halving the cache-read time AND doubling paged-
# pool residency. K/V vectors quantize at cache-write time, symmetric int8
# per (token, head); the scale rides as a sibling cache leaf with a trailing
# [..., 1] axis — SAME rank/axis semantics as the codes, so every dict-
# generic cache path (slot gather/scatter, pp merge, sp striping, paged
# row gather) handles it untouched. The attention read keeps the int8 codes
# as the einsum operand (a fused convert — HBM reads stay 1 byte/element)
# and applies the scale OUTSIDE the contraction: k's scale multiplies the
# scores (it depends only on output dims), v's folds into the probs.
# See ops/attention.py gqa_attention(k_scale=, v_scale=).


@component_scope("xot.kv_write")
def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Symmetric per-(token, head) int8 for KV vectors.

  x [..., hd] → (codes int8 [..., hd], scale f32 [..., 1])."""
  xf = x.astype(jnp.float32)
  absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
  scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
  return jnp.round(xf / scale).astype(jnp.int8), scale


def dequantize_kv(codes: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
  """codes [..., hd] × scale [..., 1] → [..., hd] in ``dtype``. No serving
  path materializes dequantized K/V anymore (the flash-prefill kernel
  dequantizes per block in-register); this is the reference definition the
  fidelity tests compare against (tests/test_kv_quant.py)."""
  return (codes.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------ int4 KV cache
#
# The int4 page mode (ISSUE 11): codes pack two 4-bit values per int8 byte
# along the HEAD-DIM axis (channel 2i in the low nibble, 2i+1 in the high —
# the same nibble convention as quantize_weight_int4, but on the LAST axis
# because KV scales are per-(token, head) over the whole hd vector). The
# packed leaf keeps the codes' rank with a halved trailing dim, so every
# dict-generic cache path (slot gather/scatter, page row gather, tier
# spill/restore, the KvPageBatch wire) moves the packed bytes untouched —
# detection everywhere is the halved axis against the expected head dim,
# exactly the qdot idiom. One scale per (token, head) rides unchanged, so
# the int8 scale machinery (gqa_attention k_scale/v_scale, the kernel's
# per-column score scaling) consumes int4 codes the moment they are
# unpacked back to int8 nibble values in [-8, 7].


@component_scope("xot.kv_write")
def quantize_kv_int4(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
  """Symmetric per-(token, head) int4, packed two nibbles per byte along hd.

  x [..., hd] → (packed int8 [..., hd/2], scale f32 [..., 1])."""
  if x.shape[-1] % 2:
    raise ValueError(f"int4 KV packing needs an even head dim; got {x.shape}")
  xf = x.astype(jnp.float32)
  absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
  scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
  q = jnp.clip(jnp.round(xf / scale), -8, 7).astype(jnp.int8)
  lo = q[..., 0::2] & 0x0F
  hi = (q[..., 1::2] & 0x0F) << 4
  return (lo | hi).astype(jnp.int8), scale


def unpack_int4_kv(packed: jnp.ndarray) -> jnp.ndarray:
  """packed int8 [..., hd/2] → int8 nibble values [..., hd] (sign-extended,
  channel order restored). The unpacked array IS an int8-codes array for the
  shared scale machinery: value = code × scale."""
  lo = (packed << 4) >> 4  # arithmetic shifts on int8 sign-extend the nibble
  hi = packed >> 4
  pair = jnp.stack([lo, hi], axis=-1)  # [..., hd/2, 2]
  return pair.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def qdot(x: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray, compute: str = "w8a16") -> jnp.ndarray:
  """x [..., in] @ quantized w → [..., out] in x.dtype.

  ``w`` is int8 [in, out] or PACKED int4 [in/2, out] (detected by the
  halved in-axis; unpacked next to the dot, w4a16-style).
  ``compute='w8a8'`` additionally quantizes x per-row to int8 and runs the
  dot on the int8 MXU path with int32 accumulation (int8 layout only).
  """
  if w.shape[-2] * 2 == x.shape[-1]:  # packed int4
    if w.ndim == 2:
      from ..ops.pallas_int4 import int4_kernel_supported, int4_matmul

      x2 = x.reshape(-1, x.shape[-1])
      if int4_kernel_supported(x2.shape, w.shape):
        # In-register unpack (ops/pallas_int4.py): the packed tile is read
        # from HBM ONCE — true 0.5 bytes/param streaming, vs the two-dot
        # fallback below whose dots each re-read it (int8-equivalent
        # traffic). Opt-in via XOT_TPU_INT4_KERNEL=1.
        return int4_matmul(x2, w, scale.astype(jnp.float32)).reshape(*x.shape[:-1], w.shape[-1])
    # TWO-DOT formulation: y = x_even @ signext(packed) + x_odd @ (packed>>4).
    # Each operand is a pure shift of the packed buffer, which XLA streams
    # into the dot like int8's astype; the obvious stack/reshape interleave
    # instead MATERIALIZES the unpacked weights to HBM every step — measured
    # 26 vs 185 tok/s on the 1B geometry on v5e-1 (NOTES round-4). Traffic
    # is int8-equivalent (both dots read the packed buffer), so int4 is the
    # HBM-CAPACITY mode (weights at rest: 0.5 byte/param), not the speed
    # mode — int8 decodes ~2x faster (BASELINE.md).
    xe = x[..., 0::2]
    xo = x[..., 1::2]
    with jax.named_scope("xot.dequant"):
      lo = ((w << 4) >> 4).astype(x.dtype)
      hi = (w >> 4).astype(x.dtype)
    dn = (((x.ndim - 1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(xe, lo, dn, preferred_element_type=jnp.float32)
    acc = acc + jax.lax.dot_general(xo, hi, dn, preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)).astype(x.dtype)
  if compute == "w8a8":
    xf = x.astype(jnp.float32)
    row = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    sx = jnp.where(row > 0, row / 127.0, 1.0)
    xq = jnp.round(xf / sx).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, w, (((xq.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * sx * scale.astype(jnp.float32)).astype(x.dtype)
  with jax.named_scope("xot.dequant"):  # only the operand's conversion: where XLA does not fuse it into the dot it is its own op
    up = w.astype(x.dtype)
  acc = jax.lax.dot_general(x, up, (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
  return (acc * scale.astype(jnp.float32)).astype(x.dtype)


def is_quantized(p: dict, name: str) -> bool:
  return f"{name}_scale" in p


@component_scope("xot.dequant")
def dequantize_leaf(w: jnp.ndarray, scale: jnp.ndarray, in_dim: int, dtype) -> jnp.ndarray:
  """Materialize a quantized leaf (int8 OR packed int4, detected against the
  expected ``in_dim``) back to ``dtype`` — for the few sites that need the
  full matrix rather than a fused qdot (MLA weight absorption, MoE expert
  einsums)."""
  if w.shape[-2] * 2 == in_dim:
    w = unpack_int4(w)
  return w.astype(dtype) * scale[..., None, :].astype(dtype)
