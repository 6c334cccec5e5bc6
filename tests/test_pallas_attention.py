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
