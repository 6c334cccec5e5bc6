"""The byte functions against numbers worked by hand from the published sizes."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import arch_dense_gqa as dense  # noqa: E402
import arch_mla_moe as mla  # noqa: E402
import common  # noqa: E402
import flops_bytes as fb  # noqa: E402
import pytest  # noqa: E402


def test_mistral_7b_int8_weights_and_cache():
  hf = common.load_config("mistral-7b-int8")
  # a layer: q and o 4096x4096, k and v 4096x1024, three 4096x14336 -> 218.1 M parameters; 32 layers + the 4096x32768 head
  params = 32 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336) + 4096 * 32768
  assert params == 7_113_539_584
  assert dense.weight_bytes(hf) == pytest.approx(params, rel=2e-3)  # + f32 scales and bf16 norms: under 0.2 %
  # one cached token, int8 KV: 32 layers x 8 heads x 2 sides x (128 codes + one f32 scale)
  assert sum(dense.cache_read_bytes(hf, 1, 1, "int8")) == 32 * 8 * 2 * 132 == 67_584
  assert sum(dense.cache_read_bytes(hf, 1, 1, "")) == 32 * 8 * 2 * 256
  # one call of the paged kernel reads one layer's share of it
  assert fb.paged_attention_min_bytes(hf, 16, 12800, "int8") == 12800 * 8 * 2 * 132


def test_moonlight_d14_weights_cache_and_experts():
  hf = common.load_config("moonlight-a3b-d14")
  attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048  # q, kv_a, kv_b, o = 13.76 M
  dense_layer = attn + 3 * 2048 * 11264  # 83 M
  expert = 3 * 2048 * 1408
  moe_layer = attn + 64 * expert + 3 * 2048 * 2816 + 2048 * 64  # 585 M
  total = dense_layer + 13 * moe_layer + 2048 * 163840
  assert round(dense_layer / 1e6) == 83 and round(moe_layer / 1e6) == 585
  assert mla.weight_bytes(hf, 16, all_experts=True) == pytest.approx(total, rel=3e-3)
  # 16 tokens x 6 of 64 experts touch 64 * (1 - (58/64)^16) = 50.75 distinct experts a layer
  assert fb.expected_distinct_experts(64, 6, 16) == pytest.approx(50.75, abs=0.01)
  assert mla.weight_bytes(hf, 16) == pytest.approx(total - 13 * (64 - 50.75) * expert, rel=3e-3)
  assert sum(mla.cache_read_bytes(hf, 1, 1, "")) == 14 * (512 + 64) * 2 == 16_128


def test_roofline_names_the_bound():
  peaks = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
  hf = common.load_config("mistral-7b-int8")
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, 16), fb.decode_step_min_bytes(hf, 16, 16 * 800, "int8"), peaks)
  assert bound == "memory" and t == pytest.approx((7.12e9 + 16 * 800 * 67584) / 819e9, rel=0.01)
