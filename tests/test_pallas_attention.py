"""Flash-attention kernel correctness (interpret mode on CPU; the same kernel
compiles for TPU via Mosaic — tests/test_tpu_compile.py compiles it for a
described v5e and chip_smoke.py runs it on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.ops.attention import gqa_attention
from xotorch_support_jetson_tpu.ops.pallas_attention import BLOCK_K, BLOCK_Q, flash_attention_prefill, flash_supported


def _make(B=2, Sq=256, Skv=256, Hq=4, Hkv=2, hd=64, seed=0):
  ks = jax.random.split(jax.random.PRNGKey(seed), 3)
  q = jax.random.normal(ks[0], (B, Sq, Hq, hd), jnp.float32)
  k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), jnp.float32)
  v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), jnp.float32)
  return q, k, v


@pytest.mark.parametrize("Sq,Skv,offset", [(256, 256, 0), (128, 512, 0), (128, 384, 128)])
def test_flash_matches_dense(Sq, Skv, offset):
  q, k, v = _make(Sq=Sq, Skv=Skv)
  q_pos = jnp.broadcast_to(offset + jnp.arange(Sq, dtype=jnp.int32), (q.shape[0], Sq))
  kv_pos = jnp.arange(Skv, dtype=jnp.int32)
  with jax.default_matmul_precision("highest"):
    dense = gqa_attention(q, k, v, q_pos, kv_pos)
    flash = flash_attention_prefill(q, k, v, q_offset=offset, interpret=True)
  np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_flash_masks_garbage_beyond_positions():
  """Cache slots beyond the prompt hold junk; positional masking must hide it."""
  q, k, v = _make(Sq=128, Skv=256)
  # Poison slots >= 128 with huge values.
  k = k.at[:, 128:].set(1e4)
  v = v.at[:, 128:].set(1e4)
  q_pos = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32), (2, 128))
  with jax.default_matmul_precision("highest"):
    dense = gqa_attention(q, k[:, :128], v[:, :128], q_pos, jnp.arange(128, dtype=jnp.int32))
    flash = flash_attention_prefill(q, k, v, q_offset=0, interpret=True)
  np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)


def _parent_flash(q, k, v, q_offset, k_scale=None, v_scale=None, window=0):
  """The kernel as it stood before PR 54 (commit fa76edc), interpreted: one ``[128, hd]`` query tile a QUERY head,
  every operand cast to float32 ahead of both products, every K/V block of the row walked. Kept here as what
  ``test_flash_bfloat16_one_ulp_and_as_before`` compares the kernel with; nothing serves it."""
  import functools

  import jax.experimental.pallas as pl
  from jax.experimental.pallas import tpu as pltpu

  from xotorch_support_jetson_tpu.ops.pallas_attention import NEG_INF

  B, Sq, Hq, hd = q.shape
  Skv, Hkv = k.shape[1], k.shape[2]
  group, quantized = Hq // Hkv, k_scale is not None
  block_k = next((bk for bk in (2048, 1024, 512, 256, 128) if Skv % bk == 0 and (not window or bk <= 512)), 128)

  def kernel(off_ref, q_ref, k_ref, v_ref, *rest):
    ks_ref, vs_ref = rest[:2] if quantized else (None, None)
    o_ref, m_ref, l_ref, acc_ref = rest[-4:]
    b, qi, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
      m_ref[...] = jnp.full_like(m_ref, NEG_INF)
      l_ref[...] = jnp.zeros_like(l_ref)
      acc_ref[...] = jnp.zeros_like(acc_ref)

    qf = q_ref[0, 0].astype(jnp.float32)
    q_pos = off_ref[b] + qi * 128 + jax.lax.broadcasted_iota(jnp.int32, (128, 1), 0)
    start = kb * block_k
    needed = start <= off_ref[b] + (qi + 1) * 128 - 1
    if window:
      needed = jnp.logical_and(needed, start + block_k - 1 > off_ref[b] + qi * 128 - window)

    @pl.when(needed)
    def _block():
      scores = jax.lax.dot_general(qf, k_ref[0, 0].astype(jnp.float32), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * float(1.0 / hd**0.5)
      if quantized:
        scores = scores * jnp.transpose(ks_ref[0, 0], (1, 0))
      kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
      mask = kv_pos <= q_pos
      if window:
        mask = jnp.logical_and(mask, kv_pos > q_pos - window)
      scores = jnp.where(mask, scores, NEG_INF)
      m = m_ref[...]
      new_m = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
      p = jnp.where(new_m <= NEG_INF / 2, 0.0, jnp.exp(scores - new_m))
      alpha = jnp.exp(m - new_m)
      m_ref[...] = new_m
      l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
      if quantized:
        p = p * jnp.transpose(vs_ref[0, 0], (1, 0))
      acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
      l = l_ref[...]
      o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

  kv_spec = functools.partial(pl.BlockSpec, index_map=lambda b, h, i, kb: (b, h // group, kb, 0))
  q_spec = pl.BlockSpec((1, 1, 128, hd), lambda b, h, i, kb: (b, h, i, 0))
  in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), q_spec, kv_spec((1, 1, block_k, hd)), kv_spec((1, 1, block_k, hd))] + [kv_spec((1, 1, block_k, 1))] * (2 * quantized)
  operands = [jnp.moveaxis(x, 2, 1) for x in (q, k, v) + ((k_scale, v_scale) if quantized else ())]
  out = pl.pallas_call(
    kernel, out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype), grid=(B, Hq, Sq // 128, Skv // block_k), in_specs=in_specs, out_specs=q_spec,
    scratch_shapes=[pltpu.VMEM((128, 1), jnp.float32), pltpu.VMEM((128, 1), jnp.float32), pltpu.VMEM((128, hd), jnp.float32)], interpret=True,
  )(jnp.asarray(q_offset, jnp.int32), *operands)  # fmt: skip
  return jnp.moveaxis(out, 1, 2)


def _masked_softmax_f32(q, k, v, offset: int, window: int):
  """One row's plain attention in float32 over the keys its queries can see: q [Sq, H, hd] at ``offset``,
  k / v [Skv, hd] float32 → (out [Sq, H, hd], sum(p·|v|) of each output: what its terms amount to before they cancel)."""
  Sq, hd = q.shape[0], q.shape[-1]
  lo, hi = max(offset - window + 1, 0) if window else 0, offset + Sq
  q_pos, kv_pos = offset + jnp.arange(Sq)[:, None], jnp.arange(lo, hi)[None, :]
  mask = (kv_pos <= q_pos) & ((kv_pos > q_pos - window) if window else True)
  with jax.default_matmul_precision("highest"):
    scores = jnp.einsum("qhd,kd->hqk", q.astype(jnp.float32), k[lo:hi]) / np.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return np.asarray(jnp.einsum("hqk,kd->qhd", probs, v[lo:hi]), np.float64), np.asarray(jnp.einsum("hqk,kd->qhd", probs, jnp.abs(v[lo:hi])), np.float64)


# (window, the three first-row offsets: 0 / inside the window / past it, Skv): Skv is the power of two a page window
# is padded to (inference/batch_scheduler.py _page_window), past the last key any row needs.
_BF16_SPANS = {0: ((0, 256, 1408), 2048), 512: ((0, 256, 896), 2048), 4096: ((0, 2048, 4480), 8192)}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("where", [0, 1, 2], ids=["offset0", "mid-window", "past-window"])
@pytest.mark.parametrize("window", sorted(_BF16_SPANS))
@pytest.mark.parametrize("group", [1, 4, 7, 16])
def test_flash_bfloat16_one_ulp_and_as_before(group, window, where, quant):
  """bfloat16 queries against bfloat16 values or int8 codes: the kernel's products take their operands as stored
  (q·k exact, p as two bfloat16 terms) and agree with the masked softmax in float32 over the SAME stored values to
  one bfloat16 ulp of each output, and with the kernel of before PR 54 (float32 operands, a tile a query head) in
  all but 0.5 % of the elements, those by one ulp. Two rows at offsets that differ (the second 192 further, off the
  tile's grain), garbage in every slot past a row's last key, one compiled shape for a window's three offsets."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_kv

  B, Sq, hd = 2, 256, 64
  offsets, Skv = _BF16_SPANS[window]
  off = np.asarray([offsets[where], offsets[where] + 192], np.int32)
  ks = jax.random.split(jax.random.PRNGKey(1000 * group + window + where), 3)
  q = jax.random.normal(ks[0], (B, Sq, group, hd), jnp.float32).astype(jnp.bfloat16)
  k = jax.random.normal(ks[1], (B, Skv, 1, hd), jnp.float32).astype(jnp.bfloat16)
  v = jax.random.normal(ks[2], (B, Skv, 1, hd), jnp.float32).astype(jnp.bfloat16)
  stale = jnp.arange(Skv)[None, :, None, None] > jnp.asarray(off)[:, None, None, None] + Sq - 1  # slots no query of the row may see
  k, v = jnp.where(stale, 3e4, k).astype(jnp.bfloat16), jnp.where(stale, -3e4, v).astype(jnp.bfloat16)
  scales = {}
  if quant:
    (k, k_scale), (v, v_scale) = quantize_kv(k), quantize_kv(v)
    scales = {"k_scale": k_scale, "v_scale": v_scale}
    k_true, v_true = (c.astype(jnp.float32) * s for c, s in ((k, k_scale), (v, v_scale)))
  else:
    k_true, v_true = k.astype(jnp.float32), v.astype(jnp.float32)

  out = np.asarray(flash_attention_prefill(q, k, v, q_offset=jnp.asarray(off), window=window, interpret=True, **scales).astype(jnp.float32))
  before = np.asarray(_parent_flash(q, k, v, jnp.asarray(off), window=window, **scales).astype(jnp.float32))

  ref, spread = (np.stack(x) for x in zip(*(_masked_softmax_f32(q[b], k_true[b, :, 0], v_true[b, :, 0], int(off[b]), window) for b in range(B))))

  assert np.isfinite(out).all()
  # bfloat16 keeps 8 bits of a value. Where a sum of terms of both signs cancels, an output is small beside what made it
  # and no kernel (the one before neither) is within an ulp of IT: the room there is 2^-16 of sum(p·|v|) — the 16 bits
  # p enters the value product with. A single bfloat16 p misses that by two orders (2e-3 against 2e-6 read, PR 54).
  ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 1e-30))) - 7)
  cancel = 2.0**-16 * spread
  assert (np.abs(out - ref) <= ulp + cancel).all(), float(np.max(np.abs(out - ref) / (ulp + cancel)))
  differ = out != before
  assert differ.mean() <= 0.005, differ.mean()  # read: 0.22-0.26 % with two terms, 0.02-0.05 % with three, 39-43 % with one
  assert (np.abs(out - before) <= 2 * (ulp + cancel)).all()


# The benchmark cells' head shapes: (group, hd, window, Sq, Skv).
_CELL_SHAPES = [
  pytest.param(7, 128, 0, 2048, 16384, id="smallthinker-full"),
  pytest.param(7, 128, 4096, 2048, 16384, id="smallthinker-window"),
  pytest.param(6, 128, 0, 2048, 4096, id="laguna-full"),
  pytest.param(8, 128, 512, 2048, 4096, id="laguna-window"),
  pytest.param(4, 128, 0, 512, 1024, id="mistral"),
  pytest.param(1, 128, 0, 640, 2048, id="olmo-mha"),
  pytest.param(4, 64, 0, 1024, 2048, id="granite-hd64"),
  pytest.param(16, 128, 0, 1024, 2048, id="nemotron-group16"),
  pytest.param(64, 128, 0, 128, 384, id="mqa-64-heads"),
  pytest.param(8, 256, 4096, 2048, 8192, id="hd256-mqa-window"),
]


@pytest.mark.parametrize("group,hd,window,sq,skv", _CELL_SHAPES)
def test_flash_tile_rule_serves_every_shape(group, hd, window, sq, skv):
  """The tile rule reads shapes alone and always has an answer: whole heads of one KV head, a stretch of queries that
  divides Sq, a K block that divides Skv; the scores tile stays inside its VMEM share unless the tile is already the
  smallest there is, and a window layer's block stays at its window's grain."""
  from xotorch_support_jetson_tpu.ops.pallas_attention import MAX_BLOCK_K, TILE_MAX_Q, TILE_MAX_ROWS, TILE_SCORES, _tile

  heads, bq, bk = _tile(group, hd, window, sq, skv)
  assert group % heads == 0 and sq % bq == 0 and skv % bk == 0 and bq % BLOCK_Q == 0 and bk % BLOCK_K == 0
  assert heads * bq * bk <= TILE_SCORES or (bk == BLOCK_K and bq == BLOCK_Q)
  max_rows = TILE_MAX_ROWS * 128 // max(hd, 128)
  assert heads * bq <= max_rows or (bq == BLOCK_Q and heads == 1)
  assert heads == group or group * BLOCK_Q > max_rows  # one tile a KV head wherever the group's rows fit
  assert bk <= MAX_BLOCK_K and (not window or bk <= max(window, BLOCK_K)) and bq <= max(TILE_MAX_Q, BLOCK_Q)


@pytest.mark.parametrize("window,offset", [(0, 0), (0, 640), (256, 0), (256, 896)])
def test_flash_never_reads_a_block_no_tile_needs(window, offset):
  """Every K/V block outside [first, last] of every tile holds NaN: the kernel's index maps clamp into the range and
  its body skips the step, so the output is the clean cache's, bit for bit. (Before PR 54 a block past the horizon was
  fetched and skipped; one before the window's first block likewise.)"""
  from xotorch_support_jetson_tpu.ops.pallas_attention import _kv_blocks, _tile

  Sq, Skv, Hq, Hkv, hd = 256, 2048, 4, 2, 64
  q, k, v = _make(B=1, Sq=Sq, Skv=Skv, Hq=Hq, Hkv=Hkv, hd=hd, seed=3)
  heads, bq, bk = _tile(Hq // Hkv, hd, window, Sq, Skv)
  needed = np.zeros(Skv // bk, bool)
  for i in range(Sq // bq):
    first, last = (int(x) for x in _kv_blocks(jnp.int32(offset + i * bq), bq, bk, Skv // bk, window))
    needed[first : last + 1] = True
  assert not needed.all()  # the case has blocks to poison
  poison = jnp.asarray(np.repeat(~needed, bk))[None, :, None, None]
  clean = flash_attention_prefill(q, k, v, q_offset=offset, window=window, interpret=True)
  dirty = flash_attention_prefill(q, jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v), q_offset=offset, window=window, interpret=True)
  np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


def test_flash_prefill_half_specified_quant_raises():
  """Passing only one of k_scale/v_scale is a caller bug (the other leaf
  would be silently ignored / int8 codes read as values): fail loudly."""
  q, k, v = _make(Sq=128, Skv=128)
  scale = jnp.ones((2, 128, 2, 1), jnp.float32)
  with pytest.raises(ValueError, match="k_scale and v_scale"):
    flash_attention_prefill(q, k, v, k_scale=scale, interpret=True)
  with pytest.raises(ValueError, match="k_scale and v_scale"):
    flash_attention_prefill(q, k, v, v_scale=scale, interpret=True)


def test_flash_supported_gating(monkeypatch):
  assert not flash_supported((1, 100, 4, 64), 256, platform="tpu")  # Sq not blocked
  assert not flash_supported((1, 128, 4, 63), 256, platform="tpu")  # odd head dim
  assert not flash_supported((1, 128, 4, 64), 200, platform="tpu")  # kv not blocked
  assert flash_supported((1, 128, 4, 64), 256, platform="tpu")
  assert not flash_supported((1, 128, 4, 64), 256, platform="cpu")
  monkeypatch.setenv("XOT_TPU_NO_FLASH", "1")
  assert not flash_supported((1, 128, 4, 64), 256, platform="tpu")


def test_flash_decode_matches_dense_reference():
  """Flash-decode (split-K over the cache with block-diagonal queries) ==
  dense attention for ragged per-row positions, including row position 0."""
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_decode_attention

  rng = np.random.default_rng(7)
  B, Hq, Hkv, hd, Skv = 2, 8, 4, 64, 128
  q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
  k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
  for pos in ([37, 12], [127, 0]):
    q_pos = jnp.asarray(pos, jnp.int32)[:, None]
    with jax.default_matmul_precision("highest"):
      dense = gqa_attention(q, k, v, q_pos, jnp.arange(Skv, dtype=jnp.int32))
      flash = flash_decode_attention(q, k, v, q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)


def test_flash_decode_gating(monkeypatch):
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_decode_supported

  monkeypatch.setenv("XOT_TPU_FLASH_DECODE", "1")
  assert flash_decode_supported((1, 1, 32, 64), 16384, platform="tpu")
  assert not flash_decode_supported((1, 1, 32, 64), 4096, platform="tpu")  # below threshold
  assert not flash_decode_supported((1, 2, 32, 64), 16384, platform="tpu")  # not a decode step
  assert not flash_decode_supported((1, 1, 32, 64), 16384, platform="cpu")
  monkeypatch.delenv("XOT_TPU_FLASH_DECODE")
  assert not flash_decode_supported((1, 1, 32, 64), 16384, platform="tpu")  # opt-in


def test_flash_decode_multi_block_carry(monkeypatch):
  """Force multiple kv blocks so the cross-block online-softmax carry, the
  clamped DMA index, and the block-skip actually run (BLOCK_D shrunk)."""
  import xotorch_support_jetson_tpu.ops.pallas_attention as pa

  monkeypatch.setattr(pa, "BLOCK_D", 64)
  rng = np.random.default_rng(11)
  B, Hq, Hkv, hd, Skv = 2, 8, 4, 64, 256  # 4 blocks of 64
  q = jnp.asarray(rng.normal(size=(B, 1, Hq, hd)), jnp.float32)
  k = jnp.asarray(rng.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(B, Skv, Hkv, hd)), jnp.float32)
  for pos in ([255, 100], [70, 0]):  # full span / mid-block raggedness
    q_pos = jnp.asarray(pos, jnp.int32)[:, None]
    with jax.default_matmul_precision("highest"):
      dense = gqa_attention(q, k, v, q_pos, jnp.arange(Skv, dtype=jnp.int32))
      flash = pa.flash_decode_attention(q, k, v, q_pos, interpret=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense), rtol=2e-5, atol=2e-5)
