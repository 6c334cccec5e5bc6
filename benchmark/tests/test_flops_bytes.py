"""The byte functions against numbers worked by hand from the published sizes."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

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
  # 16 tokens x 6 of 64 experts would touch 64 * (1 - (58/64)^16) = 50.75 distinct experts a layer if every token chose for itself ...
  assert fb.expected_distinct_experts(64, 6, 16) == pytest.approx(50.75, abs=0.01)
  # ... but the file states 64 topics of 6 experts a layer, and two tokens of one topic choose the same six: 48.17 (47.03 at the 15.1 rows resident)
  assert hf["router_topics"] == 64
  assert fb.experts_touched(hf, 64, 64, 6, 16) == pytest.approx(48.17, abs=0.01) and fb.experts_touched(hf, 64, 64, 6, 15.1) == pytest.approx(47.03, abs=0.1)
  assert mla.weight_bytes(hf, 16) == pytest.approx(total - 13 * (64 - 48.17) * expert, rel=3e-3)
  # a file that states no topics counts as before (the rehearsal of a later kind; ``router_topics`` 0 or absent)
  for plain in ({**hf, "router_topics": 0}, {k: v for k, v in hf.items() if k != "router_topics"}):
    assert fb.experts_touched(plain, 64, 64, 6, 16) == fb.expected_distinct_experts(64, 6, 16)
    assert mla.weight_bytes(plain, 16) == pytest.approx(total - 13 * (64 - 50.75) * expert, rel=3e-3)
  assert sum(mla.cache_read_bytes(hf, 1, 1, "")) == 14 * (512 + 64) * 2 == 16_128


def test_roofline_names_the_bound():
  peaks = {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
  hf = common.load_config("mistral-7b-int8")
  t, bound = fb.roofline_seconds(fb.decode_step_flops(hf, 16), fb.decode_step_min_bytes(hf, 16, 16 * 800, "int8"), peaks)
  assert bound == "memory" and t == pytest.approx((7.12e9 + 16 * 800 * 67584) / 819e9, rel=0.01)


def _monte_carlo_touched(topics: int, routed: int, top_k: int, counted: int, rows: int, trials: int) -> float:
  """As the kinds' ``make_params`` draw a layer: every topic owns exactly ``top_k`` of the ``routed`` experts; each of
  ``rows`` tokens draws a topic; the distinct experts touched among the first ``counted``, averaged over tables and tokens."""
  rng = np.random.default_rng([topics, routed, top_k, counted, rows])
  total = 0
  for _ in range(trials):
    owned = np.zeros((topics, routed), bool)
    np.put_along_axis(owned, np.argpartition(rng.random((topics, routed)), top_k, axis=1)[:, :top_k], True, axis=1)
    total += owned[rng.integers(topics, size=rows), :counted].any(axis=0).sum()
  return total / trials


@pytest.mark.parametrize(
  "topics, routed, top_k, counted, rows",
  [(64, 512, 8, 128, 16), (64, 512, 8, 128, 61), (64, 512, 8, 128, 64), (64, 512, 8, 128, 512), (64, 64, 6, 64, 16)],
  ids=["ling-16-rows", "ling-61-rows", "ling-64-rows", "ling-512-rows", "moonlight-16-rows"],
)
def test_the_topic_count_is_what_drawn_tables_and_drawn_tokens_touch(topics, routed, top_k, counted, rows):
  """Ling's held experts (128 of 512 routed, 8 a token: p = 1/64) and Moonlight's (6 of 64), 64 topics: the closed sum
  against tables and tokens drawn 8000 times, within 1 %."""
  want = fb.topic_router_distinct_experts(counted, top_k / routed, topics, rows)
  assert _monte_carlo_touched(topics, routed, top_k, counted, rows, 8000) == pytest.approx(want, rel=0.01)
  assert want == fb.experts_touched({"router_topics": topics}, counted, routed, top_k, rows)


def test_the_topic_counts_limits():
  """No row, no expert; one row, its topic's own; rows without end reach the experts that SOME topic owns,
  n (1 - (1 - p)^T), not n: an expert no topic owns is never read."""
  assert fb.topic_router_distinct_experts(128, 1 / 64, 64, 0) == 0.0
  assert fb.topic_router_distinct_experts(128, 1 / 64, 64, 1) == pytest.approx(128 / 64)  # k x held / routed = 2
  owned = 128 * (1 - (63 / 64) ** 64)
  assert owned == pytest.approx(81.28, abs=0.01)
  assert fb.topic_router_distinct_experts(128, 1 / 64, 64, 1e9) == pytest.approx(owned, rel=1e-9) and owned < 128
  assert fb.topic_router_distinct_experts(128, 1 / 64, 64, 4096) == pytest.approx(owned, rel=1e-6)
  assert fb.topic_router_distinct_experts(64, 6 / 64, 64, 1e9) == pytest.approx(64 * (1 - (58 / 64) ** 64), rel=1e-9)
  # the closed form of the issue, topics drawn then experts: within 0.1 expert at the cells' rows
  for n, p, rows in ((128, 1 / 64, 61.1), (128, 1 / 64, 64), (64, 6 / 64, 15.1)):
    assert abs(fb.topic_router_distinct_experts(n, p, 64, rows) - n * (1 - (1 - p) ** (64 * (1 - (1 - 1 / 64) ** rows)))) < 0.15


@pytest.mark.parametrize("routed, top_k, counted", [(512, 8, 128), (64, 6, 64), (32, 8, 8)])
def test_the_topic_count_never_passes_the_uniform_count(routed, top_k, counted):
  """Rows of one topic share their experts, so from one row on a topic router touches at most what independent rows
  would (one row touches its k either way); with a topic a token (topics without end) the two agree; the count grows
  with the rows."""
  last = 0.0
  for rows in (0, 1, 1.5, 2, 7.3, 16, 61.1, 64, 200, 512, 5000):
    uniform = fb.expected_distinct_experts(routed, top_k, rows) * counted / routed
    for topics in (1, 4, 16, 64, 256):
      assert fb.topic_router_distinct_experts(counted, top_k / routed, topics, rows) <= uniform * (1 + 1e-9)
    got = fb.topic_router_distinct_experts(counted, top_k / routed, 64, rows)
    assert got >= last * (1 - 1e-9)
    last = got
  assert fb.topic_router_distinct_experts(counted, top_k / routed, 200000, 64) == pytest.approx(fb.expected_distinct_experts(routed, top_k, 64) * counted / routed, rel=2e-3)
