"""Tracing + metrics: live (unlike the reference's dead tracer, SURVEY §5.1).

ISSUE 2 coverage: histogram bucket math and quantile edge cases, scheduler
gauge/counter lifecycle under admit/evict/grow, decode-path attribution
(the scheduler's ``decode_path`` label), per-request stage
timelines (+ the ``/v1/requests/{id}/timeline`` endpoint and slow-request
log), the buffered-export / residual-token-group tracer fixes, cluster
snapshot merging, and a metric-name snapshot so the ``/metrics`` exposition
stays stable.
"""

import asyncio
import json

import pytest

from xotorch_support_jetson_tpu.orchestration.tracing import (
  Tracer,
  format_traceparent,
  parse_traceparent,
)
from xotorch_support_jetson_tpu.utils.metrics import Metrics


def test_traceparent_roundtrip():
  tp = format_traceparent("a" * 32, "b" * 16)
  assert parse_traceparent(tp) == ("a" * 32, "b" * 16)
  assert parse_traceparent("garbage") is None
  assert parse_traceparent(None) is None


def test_parse_traceparent_hardened():
  """Hardened parsing (ISSUE 4 satellite): any 4-dash-part string used to be
  accepted — garbage ids were silently adopted as trace identity."""
  good = f"00-{'a' * 32}-{'b' * 16}-01"
  assert parse_traceparent(good) is not None
  # Non-hex trace/span ids.
  assert parse_traceparent(f"00-{'g' * 32}-{'b' * 16}-01") is None
  assert parse_traceparent(f"00-{'a' * 32}-{'z' * 16}-01") is None
  # Uppercase hex is invalid per W3C (ids are lowercase base16).
  assert parse_traceparent(f"00-{'A' * 32}-{'b' * 16}-01") is None
  # All-zero ids are explicitly invalid.
  assert parse_traceparent(f"00-{'0' * 32}-{'b' * 16}-01") is None
  assert parse_traceparent(f"00-{'a' * 32}-{'0' * 16}-01") is None
  # Unknown/invalid version fields are rejected, not adopted.
  assert parse_traceparent(f"ff-{'a' * 32}-{'b' * 16}-01") is None
  assert parse_traceparent(f"01-{'a' * 32}-{'b' * 16}-01") is None
  assert parse_traceparent(f"xx-{'a' * 32}-{'b' * 16}-01") is None
  # Malformed flags / wrong lengths.
  assert parse_traceparent(f"00-{'a' * 32}-{'b' * 16}-zz") is None
  assert parse_traceparent(f"00-{'a' * 31}-{'b' * 16}-01") is None


def test_tracer_contexts_bounded():
  """A request cancelled/failed before end_request used to leave its
  TraceContext in the dict forever; the LRU cap bounds it (ISSUE 4
  satellite)."""
  from xotorch_support_jetson_tpu.orchestration import tracing

  t = Tracer()
  for i in range(tracing.MAX_CONTEXTS + 50):
    t.request_context(f"leak-{i}")  # never end_request'd
  assert len(t.contexts) == tracing.MAX_CONTEXTS
  assert "leak-0" not in t.contexts  # oldest evicted
  assert f"leak-{tracing.MAX_CONTEXTS + 49}" in t.contexts
  # Access refreshes recency: touching an old id keeps it past new inserts.
  t.request_context("leak-100")
  for i in range(200):
    t.request_context(f"leak2-{i}")
  assert "leak-100" in t.contexts


def test_span_lifecycle_and_token_groups():
  tracer = Tracer()
  ctx = tracer.request_context("req1")
  with tracer.start_span("request.process_prompt", "req1", {"model": "m"}) as span:
    assert span.trace_id == ctx.trace_id
  for _ in range(25):
    tracer.handle_token("req1")
  spans = tracer.recent_spans()
  names = [s["name"] for s in spans]
  assert "request.process_prompt" in names
  assert names.count("token_group") == 2  # groups of 10; 25 tokens → 2 full groups
  group = [s for s in spans if s["name"] == "token_group"][0]
  assert group["parent_id"] == ctx.request_span_id
  tracer.end_request("req1")
  assert "req1" not in tracer.contexts


def test_remote_context_joins_trace():
  tracer = Tracer()
  remote_tp = format_traceparent("c" * 32, "d" * 16)
  ctx = tracer.request_context("req2", remote_tp)
  assert ctx.trace_id == "c" * 32
  assert ctx.parent_id == "d" * 16


def test_metrics_render():
  m = Metrics()
  m.inc("requests_total")
  m.inc("requests_total", 2)
  m.set_gauge("active_sessions", 3)
  with m.timer("prefill"):
    pass
  text = m.render_prometheus()
  assert "xot_tpu_requests_total 3.0" in text
  assert "xot_tpu_active_sessions 3" in text
  assert "xot_tpu_prefill_seconds_count 1" in text


@pytest.mark.asyncio
async def test_node_generates_spans_and_metrics():
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.orchestration.tracing import tracer as global_tracer
  from xotorch_support_jetson_tpu.registry import build_base_shard
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from xotorch_support_jetson_tpu.utils.metrics import metrics as global_metrics
  from tests_support_stubs import NoDiscovery, StubServer

  node = Node("trace-node", StubServer(), DummyInferenceEngine(), NoDiscovery(), None, RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=30)
  await node.start()
  done = asyncio.Event()
  node.on_token.register("t").on_next(lambda r, toks, fin: done.set() if fin else None)
  before_tokens = global_metrics.counters["tokens_generated_total"]
  await node.process_prompt(build_base_shard("dummy", "DummyInferenceEngine"), "aaaa", "trace-req")
  await asyncio.wait_for(done.wait(), timeout=10)
  await node.stop()

  assert global_metrics.counters["tokens_generated_total"] > before_tokens
  names = [s["name"] for s in global_tracer.recent_spans(500)]
  assert "request.process_prompt" in names
  assert "token_group" in names


# ------------------------------------------------------------- histograms


def test_histogram_buckets_cumulative_exposition():
  m = Metrics()
  for v in (0.0005, 0.002, 0.02, 0.02, 0.3, 200.0):  # 200 s lands in +Inf
    m.observe_hist("ttft_seconds", v)
  text = m.render_prometheus()
  assert "# TYPE xot_tpu_ttft_seconds histogram" in text
  assert 'xot_tpu_ttft_seconds_bucket{le="0.001"} 1' in text  # cumulative
  assert 'xot_tpu_ttft_seconds_bucket{le="0.0025"} 2' in text
  assert 'xot_tpu_ttft_seconds_bucket{le="0.025"} 4' in text
  assert 'xot_tpu_ttft_seconds_bucket{le="+Inf"} 6' in text
  assert "xot_tpu_ttft_seconds_count 6" in text
  assert abs(float(text.split("xot_tpu_ttft_seconds_sum ")[1].split("\n")[0]) - 200.3425) < 1e-6


def test_histogram_quantile_edge_cases():
  m = Metrics()
  assert m.quantile("absent", 0.5) is None  # never created
  m.observe_hist("h", 0.02)
  # Single observation: every quantile lands inside its (0.01, 0.025] bucket.
  for q in (0.0, 0.5, 1.0):
    v = m.quantile("h", q)
    assert 0.01 <= v <= 0.025, (q, v)
  # +Inf landings clamp to the last finite edge (the histogram can't resolve
  # beyond its ladder).
  m2 = Metrics()
  m2.observe_hist("h", 1e9)
  assert m2.quantile("h", 0.99) == 60.0
  # Out-of-range q clamps instead of raising.
  assert m2.quantile("h", 7.0) == 60.0
  assert m2.quantile("h", -1.0) == 60.0
  # Interpolation: 100 uniform values in (0.01, 0.025] → median ≈ bucket mid.
  m3 = Metrics()
  for _ in range(100):
    m3.observe_hist("h", 0.02)
  v = m3.quantile("h", 0.5)
  assert 0.01 < v <= 0.025


def test_labeled_counters_and_gauges():
  m = Metrics()
  m.inc("decode_chunks_total", labels={"path": "kernel"})
  m.inc("decode_chunks_total", 2, labels={"path": "gather"})
  m.inc("decode_chunks_total", labels={"path": "kernel"})
  m.set_gauge("pool", 1.5, labels={"node": "a"})
  assert m.counter_value("decode_chunks_total", labels={"path": "kernel"}) == 2.0
  text = m.render_prometheus()
  assert 'xot_tpu_decode_chunks_total{path="gather"} 2.0' in text
  assert 'xot_tpu_decode_chunks_total{path="kernel"} 2.0' in text
  assert text.count("# TYPE xot_tpu_decode_chunks_total counter") == 1
  assert 'xot_tpu_pool{node="a"} 1.5' in text


def test_snapshot_merge_cluster_semantics():
  a, b = Metrics(), Metrics()
  a.inc("requests_total", 3)
  b.inc("requests_total", 4)
  a.set_gauge("scheduler_queue_depth", 2)
  b.set_gauge("scheduler_queue_depth", 5)
  a.set_gauge("page_pool_utilization", 0.9)
  b.set_gauge("page_pool_utilization", 0.4)
  a.inc("decode_chunks_total", labels={"path": "kernel"})
  b.inc("decode_chunks_total", labels={"path": "kernel"})
  for v in (0.01, 0.02):
    a.observe_hist("itl_seconds", v)
  b.observe_hist("itl_seconds", 0.04)
  a.observe_latency("req", 1.0)
  b.observe_latency("req", 3.0)
  snaps = [a.snapshot(), b.snapshot()]
  json.dumps(snaps)  # must be wire-safe (rides the opaque-status channel)
  merged = Metrics.merged(snaps)
  assert merged.counter_value("requests_total") == 7.0
  assert merged.gauges["scheduler_queue_depth"] == 7.0  # additive across nodes
  assert merged.gauges["page_pool_utilization"] == 0.9  # ratio gauges: max, not sum
  assert merged.counter_value("decode_chunks_total", labels={"path": "kernel"}) == 2.0
  assert merged.hist_count("itl_seconds") == 3
  text = merged.render_prometheus()
  assert "xot_tpu_req_seconds_count 2" in text
  assert 'xot_tpu_itl_seconds_bucket{le="+Inf"} 3' in text


def test_weighted_histogram_observation():
  """observe_hist(name, v, n=k): k identical observations in ONE lock
  acquisition — the itl_seconds path records a whole decode chunk's tokens
  this way (one call per chunk instead of a per-token Python loop)."""
  m = Metrics()
  m.observe_hist("itl_seconds", 0.02, n=5)
  m.observe_hist("itl_seconds", 0.3)  # default n=1 unchanged
  assert m.hist_count("itl_seconds") == 6
  text = m.render_prometheus()
  assert 'xot_tpu_itl_seconds_bucket{le="0.025"} 5' in text
  assert 'xot_tpu_itl_seconds_bucket{le="+Inf"} 6' in text
  assert abs(float(text.split("xot_tpu_itl_seconds_sum ")[1].split("\n")[0]) - 0.4) < 1e-9
  # Weighted quantile: 5/6 of mass in (0.01, 0.025].
  assert 0.01 < m.quantile("itl_seconds", 0.5) <= 0.025
  # n <= 0 is a no-op, not a crash (defensive for emit-empty chunks).
  m.observe_hist("itl_seconds", 1.0, n=0)
  assert m.hist_count("itl_seconds") == 6
  # Snapshot/merge round-trips weighted counts exactly.
  merged = Metrics.merged([m.snapshot(), m.snapshot()])
  assert merged.hist_count("itl_seconds") == 12


def test_labeled_histograms_render_snapshot_merge():
  """Per-peer-link RPC latency lives in LABELED histogram series
  (``peer_rpc_seconds{peer,method}``): render carries the label set next to
  ``le``, snapshot/merge round-trip per series, and label-less queries
  aggregate the family."""
  m = Metrics()
  m.observe_hist("peer_rpc_seconds", 0.02, labels={"peer": "n1", "method": "SendTensor"})
  m.observe_hist("peer_rpc_seconds", 0.02, labels={"peer": "n1", "method": "SendTensor"})
  m.observe_hist("peer_rpc_seconds", 0.3, labels={"peer": "n2", "method": "SendResult"})
  assert m.hist_count("peer_rpc_seconds", labels={"peer": "n1", "method": "SendTensor"}) == 2
  assert m.hist_count("peer_rpc_seconds") == 3  # label-less: whole family
  q = m.quantile("peer_rpc_seconds", 0.5)  # aggregate: 2/3 of mass in (0.01, 0.025]
  assert 0.01 < q <= 0.025
  assert m.quantile("peer_rpc_seconds", 0.5, labels={"peer": "n2", "method": "SendResult"}) > 0.25
  text = m.render_prometheus()
  assert text.count("# TYPE xot_tpu_peer_rpc_seconds histogram") == 1
  assert 'xot_tpu_peer_rpc_seconds_bucket{method="SendTensor",peer="n1",le="0.025"} 2' in text
  assert 'xot_tpu_peer_rpc_seconds_bucket{method="SendResult",peer="n2",le="+Inf"} 1' in text
  assert 'xot_tpu_peer_rpc_seconds_count{method="SendTensor",peer="n1"} 2' in text
  snaps = [m.snapshot(), m.snapshot()]
  json.dumps(snaps)  # wire-safe for the opaque-status channel
  merged = Metrics.merged(snaps)
  assert merged.hist_count("peer_rpc_seconds", labels={"peer": "n1", "method": "SendTensor"}) == 4
  assert merged.hist_count("peer_rpc_seconds") == 6
  # Unlabeled histograms keep their exact prior exposition shape.
  m2 = Metrics()
  m2.observe_hist("ttft_seconds", 0.02)
  assert 'xot_tpu_ttft_seconds_bucket{le="0.025"} 1' in m2.render_prometheus()


# ------------------------------------------------------ scheduler telemetry


def _tiny_batched_server(n_slots=2, chunk=2, **overrides):
  import jax

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=128, **overrides)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  return BatchedServer(engine, n_slots=n_slots, chunk=chunk)


@pytest.mark.parametrize(
  "overrides,kv_quant,k_lanes,v_lanes",
  [
    (dict(dim=2048, n_heads=32, n_kv_heads=8, hidden_dim=64), "", 1.0, 1.0),  # granite's and LFM2's attention geometry: heads of 64, stored in pairs
    (dict(dim=512, n_heads=4, n_kv_heads=2, hidden_dim=64), "", 1.0, 1.0),  # heads of 128: whole lanes as they always were
    (dict(dim=512, n_heads=4, n_kv_heads=2, hidden_dim=64), "int8", 1.0, 1.0),
    (dict(dim=256, n_heads=4, n_kv_heads=2, hidden_dim=64), "int8", 0.5, 0.5),  # int8 codes of 64: a scale a token and head, no pairs — padded once a dispatch
    (dict(dim=192, n_heads=3, n_kv_heads=3, hidden_dim=64), "", 0.5, 0.5),  # an odd head count
    (dict(dim=64, n_heads=4, n_kv_heads=4, kv_lora_rank=128, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16), "", 1.0, 0.5),  # MLA: the latent leaf whole, the rope leaf padded
  ],
  ids=["heads-of-64-in-pairs", "heads-of-128", "heads-of-128-int8", "heads-of-64-int8", "odd-heads-of-64", "mla-rope-leaf"],
)
def test_kv_page_lanes_filled_says_what_of_a_pools_rows_the_kernel_reads_is_codes(monkeypatch, overrides, kv_quant, k_lanes, v_lanes):
  """ISSUE 58: ``kv_page_lanes_filled{leaf}`` is set when the pool is made, from the leaves' own shapes (ops/paged.py
  ``code_lanes_filled``): 1 where a code leaf's row is whole lane groups — float heads of 64 are stored two a lane group
  for that —, 0.5 where a 64-wide row is padded to 128 in the decode programs' copy of the leaf."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_KV_QUANT", kv_quant)
  server = _tiny_batched_server(**overrides)
  try:
    server._ensure_cache()
    snap = Metrics.merged([metrics.snapshot()])
    assert (snap.gauge_value("kv_page_lanes_filled", labels={"leaf": "k"}), snap.gauge_value("kv_page_lanes_filled", labels={"leaf": "v"})) == (k_lanes, v_lanes)
    assert (server.cache["k"].shape[2] * 2 == server.engine.cfg.cache_kv_heads) == (overrides.get("n_kv_heads") == 8)
  finally:
    server.shutdown()


@pytest.mark.parametrize(
  "paged,kernel_can_run,label",
  [("0", True, "dense"), ("1", False, "gather"), ("1", True, "kernel")],
  ids=["dense-slots", "paged-on-cpu", "paged-where-the-kernel-runs"],
)
def test_scheduler_decode_path_label(monkeypatch, paged, kernel_can_run, label):
  """The label on ``decode_chunks_total{path=}`` / ``decode_tokens_total{path=}``
  is the layout, then what the decode programs resolve ``use_kernel=None``
  to (ops/paged.py ``paged_kernel_supported``): a CPU takes the gather."""
  monkeypatch.setenv("XOT_TPU_PAGED", paged)
  if kernel_can_run:
    monkeypatch.setattr("xotorch_support_jetson_tpu.ops.paged.paged_kernel_supported", lambda cfg, platform=None: True)
  server = _tiny_batched_server()
  assert server.decode_path == "dense"  # a bare server; resolved with the cache
  server._ensure_cache()
  assert server.decode_path == label
  server.shutdown()


def test_scheduler_gauges_counters_and_histograms(monkeypatch):
  """Admit → decode → grow → release lifecycle populates the scheduler
  telemetry: occupancy is live DURING the run, queue-wait/TTFT/ITL
  histograms fill, page grow/release counters move, and the decode-path
  chunk counter is attributed to the pool's resolved path."""
  import numpy as np

  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "8")  # force page growth mid-decode
  server = _tiny_batched_server(n_slots=2, chunk=2)
  assert server.paged
  server._ensure_cache()  # resolves decode_path (a bare server still says "dense"), so before/after read ONE label
  before = {
    "admit": gm.counter_value("scheduler_admissions_total"),
    "grow": gm.counter_value("page_grow_events_total"),
    "release": gm.counter_value("page_release_events_total"),
    "chunks": gm.counter_value("decode_chunks_total", labels={"path": server.decode_path}),
    "ttft": gm.hist_count("ttft_seconds"),
    "qwait": gm.hist_count("queue_wait_seconds"),
    "itl": gm.hist_count("itl_seconds"),
    "chunk_t": gm.hist_count("decode_chunk_seconds"),
    "host": gm.counter_value("sched_wall_seconds_total", labels={"kind": "host"}),
  }
  seen_occupancy = []

  async def run():
    def emit(rid, toks, finished):
      seen_occupancy.append(gm.gauges.get("scheduler_batch_occupancy", 0))

    await asyncio.gather(
      *(
        server.submit(f"g{i}", np.asarray([3, 25, 9 + i], np.int32), max_tokens=12, temp=0.0, top_k=35, eos_ids=(), emit=emit)
        for i in range(3)
      )
    )

  asyncio.run(run())
  assert gm.counter_value("scheduler_admissions_total") - before["admit"] == 3
  assert gm.counter_value("page_grow_events_total") > before["grow"]  # 12 tokens cross 8-token pages
  assert gm.counter_value("page_release_events_total") - before["release"] >= 3
  assert gm.counter_value("decode_chunks_total", labels={"path": server.decode_path}) > before["chunks"]
  assert gm.hist_count("ttft_seconds") - before["ttft"] == 3
  assert gm.hist_count("queue_wait_seconds") - before["qwait"] == 3
  assert gm.hist_count("itl_seconds") > before["itl"]
  assert gm.hist_count("decode_chunk_seconds") > before["chunk_t"]
  # Dispatch-boundary host gap (the loop's wall clock, kind "host"): a chained
  # lookahead dispatch adds nothing to it; a sync-boundary dispatch adds the
  # real window from the last readback to itself.
  assert gm.counter_value("sched_wall_seconds_total", labels={"kind": "host"}) > before["host"]
  assert max(seen_occupancy) >= 1  # rows were visibly resident mid-run
  # Idle again: gauges settle back to an empty pool.
  assert gm.gauges["scheduler_batch_occupancy"] == 0
  assert gm.gauges["scheduler_queue_depth"] == 0
  assert gm.gauges["page_pool_utilization"] == 0.0
  assert gm.gauges["page_pool_pages_total"] > 0
  server.shutdown()


def test_scheduler_rejection_counter(monkeypatch):
  import numpy as np

  from xotorch_support_jetson_tpu.inference.engine import ServerOverloadedError
  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  server = _tiny_batched_server()
  server.max_queue = 0
  before = gm.counter_value("scheduler_rejections_total")

  async def run():
    with pytest.raises(ServerOverloadedError):
      await server.submit("rej", np.asarray([1, 2], np.int32), max_tokens=2, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)

  asyncio.run(run())
  assert gm.counter_value("scheduler_rejections_total") == before + 1
  server.shutdown()


# --------------------------------------------------------- tracer fixes


def test_end_request_flushes_residual_token_group():
  t = Tracer()
  t.request_context("r-res")
  for _ in range(13):  # one full group of 10 + 3 residual
    t.handle_token("r-res")
  t.end_request("r-res")
  groups = [s for s in t.recent_spans() if s["name"] == "token_group"]
  assert [g["attributes"]["n_tokens"] for g in groups] == [10, 3]
  assert groups[-1]["attributes"]["total_tokens"] == 13
  # A request ending exactly on a group boundary must NOT emit an extra span.
  t2 = Tracer()
  t2.request_context("r-even")
  for _ in range(20):
    t2.handle_token("r-even")
  t2.end_request("r-even")
  groups = [s for s in t2.recent_spans() if s["name"] == "token_group"]
  assert [g["attributes"]["n_tokens"] for g in groups] == [10, 10]


def test_trace_file_export_buffered_outside_lock(tmp_path, monkeypatch):
  """Spans still reach the JSONL file — but the hot path only queues them;
  the file write happens after the tracer lock is released."""
  path = tmp_path / "trace.jsonl"
  monkeypatch.setenv("XOT_TPU_TRACE_FILE", str(path))
  t = Tracer()  # reads the env at construction
  t.request_context("r-exp")
  with t.start_span("request.x", "r-exp"):
    pass
  for _ in range(12):
    t.handle_token("r-exp")
  t.end_request("r-exp")
  lines = [json.loads(line) for line in path.read_text().splitlines()]
  names = [entry["name"] for entry in lines]
  assert "request.x" in names
  assert names.count("token_group") == 2  # 10 + residual 2
  assert not t._export_pending  # everything flushed


# ------------------------------------------------- clock-offset estimation


def test_offset_sample_symmetric_rtt_exact():
  """With a symmetric path the NTP midpoint recovers the true offset
  exactly and rtt excludes server processing time."""
  from xotorch_support_jetson_tpu.orchestration.clocksync import offset_sample

  true_offset, one_way, proc = 1_450, 50, 100
  t0 = 1_000
  t1 = t0 + one_way + true_offset
  t2 = t1 + proc
  t3 = t2 - true_offset + one_way
  off, rtt = offset_sample(t0, t1, t2, t3)
  assert off == true_offset
  assert rtt == 2 * one_way
  # Negative offsets (peer clock BEHIND ours) come out correctly signed.
  off2, _ = offset_sample(t0, t0 + one_way - 700, t0 + one_way - 700 + proc, t0 + 2 * one_way + proc)
  assert off2 == -700


def test_clock_sync_ewma_convergence_and_uncertainty():
  from xotorch_support_jetson_tpu.orchestration.clocksync import ClockSync

  cs = ClockSync()
  true_offset, one_way = 5_000_000, 40_000  # 5 ms skew, 40 µs one-way
  # First sample seeds the estimate exactly; uncertainty = rtt/2.
  t0 = 0
  est = cs.update("peer", t0, t0 + one_way + true_offset, t0 + one_way + true_offset, t0 + 2 * one_way)
  assert est.offset_ns == true_offset
  assert est.uncertainty_ns == one_way
  # Noisy samples (±alternating asymmetry) converge around the true offset.
  for i in range(60):
    noise = 25_000 if i % 2 else -25_000
    t0 = i * 1_000_000
    t1 = t0 + one_way + noise + true_offset
    t3 = t0 + 2 * one_way
    est = cs.update("peer", t0, t1, t1, t3)
  assert abs(est.offset_ns - true_offset) < 30_000  # within the noise band
  assert est.samples == 61
  assert cs.offset_ns("peer") == est.offset_ns
  assert cs.offset_ns("never-seen") is None
  assert cs.age_s("peer") is not None and cs.age_s("peer") < 5
  cs.forget("peer")
  assert cs.estimate("peer") is None


# --------------------------------------------------------------- hop spans


def test_record_hop_spans_and_timeline_attribution():
  t = Tracer()
  ctx = t.request_context("hop-req")
  from xotorch_support_jetson_tpu.orchestration.tracing import node_now_ns

  hid = t.record_hop(
    "hop-req", side="client", method="SendTensor", peer="node-b", node="node-a",
    t_start_ns=node_now_ns(), dur_ms=1.2,
    attributes={"serialize_ms": 0.3, "rpc_ms": 0.9, "payload_bytes": 4096, "ok": True},
  )
  t.record_hop(
    "hop-req", side="server", method="SendTensor", peer="ipv4:1.2.3.4", node="node-b",
    t_start_ns=node_now_ns(), dur_ms=0.6, hop_id=hid,
    attributes={"deserialize_ms": 0.2, "handler_ms": 0.6, "payload_bytes": 4096},
  )
  spans = t.recent_spans()
  client = next(s for s in spans if s["name"] == "rpc.client.SendTensor")
  server = next(s for s in spans if s["name"] == "rpc.server.SendTensor")
  assert client["span_id"] == hid and client["trace_id"] == ctx.trace_id
  assert server["parent_id"] == hid  # server hop parents to the client hop
  assert client["attributes"]["serialize_ms"] == 0.3 and client["attributes"]["payload_bytes"] == 4096
  assert server["attributes"]["handler_ms"] == 0.6
  tl = t.timeline("hop-req")
  assert [h["side"] for h in tl["hops"]] == ["client", "server"]
  assert tl["hops"][0]["hop_id"] == hid and tl["hops"][1]["hop_id"] == hid
  # Exact per-link aggregates ride alongside the capped detail.
  agg = tl["hop_agg"]["client|node-a|node-b|SendTensor"]
  assert agg["count"] == 1 and agg["rpc_ms_sum"] == 0.9 and agg["payload_bytes_sum"] == 4096


def test_hop_detail_capped_aggregates_exact():
  from xotorch_support_jetson_tpu.orchestration import tracing

  t = Tracer()
  t.request_context("hop-cap")
  n = tracing.MAX_TIMELINE_HOPS + 20
  for _ in range(n):
    t.record_hop(
      "hop-cap", side="client", method="SendResult", peer="p", node="n",
      t_start_ns=tracing.node_now_ns(), dur_ms=0.1, attributes={"rpc_ms": 0.1},
    )
  tl = t.timeline("hop-cap")
  assert len(tl["hops"]) == tracing.MAX_TIMELINE_HOPS
  assert tl["hops_dropped"] == 20
  assert tl["hop_agg"]["client|n|p|SendResult"]["count"] == n  # exact past the cap
  # The span RING rides the same cap: per-token hop spans must not cycle the
  # whole ring and bury request/pp/token-group spans.
  ring = [s for s in t.recent_spans(n + 50) if s["name"] == "rpc.client.SendResult"]
  assert len(ring) == tracing.MAX_TIMELINE_HOPS


def test_merge_cluster_timeline_offset_normalization():
  """Known injected skew: node B's clock runs 7 ms ahead. The merge must
  subtract the estimated offset so B's events/hops land where they really
  happened in A's clock domain — correctly signed, monotonic order."""
  from xotorch_support_jetson_tpu.orchestration.tracing import (
    merge_cluster_timeline, node_now_ns, set_test_skew,
  )

  set_test_skew("B", 7_000_000)
  try:
    t = Tracer()
    t.request_context("merge-req")
    t.stage("merge-req", "queued", node="A")
    hid = t.record_hop(
      "merge-req", side="client", method="SendTensor", peer="B", node="A",
      t_start_ns=node_now_ns("A"), dur_ms=1.0,
      attributes={"serialize_ms": 0.3, "rpc_ms": 0.7, "payload_bytes": 128},
    )
    t.record_hop(
      "merge-req", side="server", method="SendTensor", peer="ipv4:x", node="B",
      t_start_ns=node_now_ns("B"), dur_ms=0.5, hop_id=hid,
      attributes={"deserialize_ms": 0.1, "handler_ms": 0.5, "payload_bytes": 128},
    )
    t.stage("merge-req", "decode", node="B")
    t.end_request("merge-req")
    exp = t.timeline_export("merge-req")

    # WITHOUT the offset, B's entries sit ~7 ms in the future.
    raw = merge_cluster_timeline("A", exp, [{"node_id": "B", "fragment": exp}], {})
    raw_hop = raw["hops"][0]
    assert raw_hop["recv_at_ms"] - raw_hop["at_ms"] > 5.0

    # WITH the (exactly-known) offset the order is restored: send < recv
    # within sub-ms slop, and B's decode follows A's queued by wall time.
    merged = merge_cluster_timeline("A", exp, [{"node_id": "B", "fragment": exp}], {"B": {"offset_ns": 7_000_000}})
    assert merged["nodes"] == ["A", "B"]
    hop = merged["hops"][0]
    assert hop["from"] == "A" and hop["to"] == "B" and hop["method"] == "SendTensor"
    # Hop attribution splits: serialize / wire / deserialize / compute.
    assert hop["serialize_ms"] == 0.3
    assert hop["deserialize_ms"] == 0.1
    assert hop["wire_ms"] == pytest.approx(0.7 - 0.5)
    assert hop["compute_ms"] == pytest.approx(0.5 - 0.1)
    assert abs(hop["recv_at_ms"] - hop["at_ms"]) < 2.0  # the 7 ms skew is gone
    order = [(e["node"], e["stage"]) for e in merged["events"]]
    assert order == [("A", "queued"), ("B", "decode")]
    # Shared-tracer fragments (both "nodes" exported the same object) do
    # not duplicate events, hops, or aggregate sums.
    assert len(merged["events"]) == 2 and len(merged["hops"]) == 1
    assert merged["hop_agg"]["client|A|B|SendTensor"]["count"] == 1
    # Per-node stage rollups are present for both nodes.
    assert set(merged["stages"]) == {"A", "B"}
    # t=0 is the earliest normalized event anywhere; nothing goes negative.
    assert merged["events"][0]["at_ms"] == 0.0
    assert all(e["at_ms"] >= 0 for e in merged["events"])
    # Off-origin merge (no local fragment — e.g. the query landed on a node
    # that only saw the tail of the request): same guarantee.
    remote_only = merge_cluster_timeline("C", None, [{"node_id": "B", "fragment": exp}], {"B": {"offset_ns": 7_000_000}})
    assert min(e["at_ms"] for e in remote_only["events"]) == 0.0
    assert remote_only["total_ms"] >= 0
  finally:
    set_test_skew("B", None)


# ----------------------------------------------------------- timelines


def test_stage_timeline_shape_and_rollup():
  t = Tracer()
  t.request_context("r-tl")
  t.stage("r-tl", "queued")
  t.stage("r-tl", "admitted", {"row": 1})
  t.stage("r-tl", "prefill_chunk", {"tokens": 2048})
  t.stage("r-tl", "prefill_chunk", {"tokens": 512})
  t.stage("r-tl", "decode")
  for _ in range(5):
    t.handle_token("r-tl")
  t.end_request("r-tl")
  t.stage("r-tl", "detokenize")  # API-side, lands after the finish
  tl = t.timeline("r-tl")
  assert tl["finished"] and tl["tokens"] == 5
  assert [s["stage"] for s in tl["stages"]] == ["queued", "admitted", "prefill_chunk", "decode", "detokenize"]
  chunks = next(s for s in tl["stages"] if s["stage"] == "prefill_chunk")
  assert chunks["count"] == 2
  assert tl["total_ms"] >= 0
  assert [e["attributes"].get("tokens") for e in tl["events"] if e["stage"] == "prefill_chunk"] == [2048, 512]
  assert all(e["at_ms"] >= 0 for e in tl["events"])
  assert t.timeline("never-seen") is None


def test_timeline_lru_bounded():
  from xotorch_support_jetson_tpu.orchestration import tracing

  t = Tracer()
  for i in range(tracing.MAX_TIMELINES + 10):
    t.stage(f"r{i}", "queued")
  assert len(t.timelines) == tracing.MAX_TIMELINES
  assert t.timeline("r0") is None  # oldest evicted
  assert t.timeline(f"r{tracing.MAX_TIMELINES + 9}") is not None


def test_slow_request_log(monkeypatch, capsys):
  monkeypatch.setenv("XOT_TPU_SLOW_REQUEST_MS", "0.000001")
  t = Tracer()
  t.request_context("r-slow")
  t.stage("r-slow", "queued")
  t.stage("r-slow", "decode")
  t.handle_token("r-slow")
  t.end_request("r-slow")
  out = capsys.readouterr().out
  line = next(json.loads(entry) for entry in out.splitlines() if '"slow_request"' in entry)
  assert line["event"] == "slow_request" and line["request_id"] == "r-slow"
  assert [s["stage"] for s in line["stages"]] == ["queued", "decode"]
  assert line["tokens"] == 1
  # Below threshold: silent.
  monkeypatch.setenv("XOT_TPU_SLOW_REQUEST_MS", "1e9")
  t.request_context("r-fast")
  t.stage("r-fast", "queued")
  t.end_request("r-fast")
  assert "slow_request" not in capsys.readouterr().out


# ------------------------------------------------------- metric-name snapshot

# The serving stack's exposition contract: every name the instrumentation
# emits, frozen so dashboards/alerts don't silently break. Adding a metric
# means adding it HERE (and to the README table); renaming one is a breaking
# change and should be called out in CHANGES.md.
EXPECTED_METRIC_NAMES = {
  # counters
  "xot_tpu_requests_total",
  "xot_tpu_requests_replayed_total",
  "xot_tpu_tokens_generated_total",
  "xot_tpu_scheduler_submitted_total",
  "xot_tpu_scheduler_admissions_total",
  "xot_tpu_scheduler_rejections_total",
  "xot_tpu_scheduler_parked_total",
  "xot_tpu_scheduler_admission_failures_total",
  "xot_tpu_scheduler_preemptions_total",
  "xot_tpu_scheduler_page_starved_total",
  "xot_tpu_decode_chunks_total",
  "xot_tpu_decode_draw_skipped_chunks_total",
  "xot_tpu_decode_tokens_total",
  "xot_tpu_prefill_chunks_total",
  "xot_tpu_recurrent_state_resets_total",  # slots a prefill from position 0 started from zeros (ISSUE 34)
  "xot_tpu_prefix_cache_hit_pages_total",
  "xot_tpu_page_grow_events_total",
  "xot_tpu_page_grow_pages_total",
  "xot_tpu_page_release_events_total",
  "xot_tpu_grpc_rpcs_total",
  "xot_tpu_grpc_rpc_failures_total",
  # QoS subsystem (ISSUE 5; labeled {class} / {tenant} / {reason})
  "xot_tpu_qos_submitted_total",
  "xot_tpu_qos_shed_total",
  "xot_tpu_qos_rejected_total",
  "xot_tpu_qos_rate_limited_total",
  "xot_tpu_qos_preemptions_total",
  # Batched speculation (ISSUE 7; spec_gamma labeled {row}; since ISSUE 12
  # the token counters are labeled {proposer} and spec_proposer{row} reports
  # each row's active proposer: 0 plain / 1 n-gram / 2 model draft)
  "xot_tpu_spec_proposed_tokens_total",
  "xot_tpu_spec_accepted_tokens_total",
  "xot_tpu_spec_proposer",
  # KV memory hierarchy (ISSUE 6; registry hits labeled {scope})
  "xot_tpu_kv_tier_spilled_pages_total",
  "xot_tpu_kv_tier_spilled_bytes_total",
  "xot_tpu_kv_tier_restored_pages_total",
  "xot_tpu_kv_tier_restored_bytes_total",
  "xot_tpu_kv_tier_host_evictions_total",
  "xot_tpu_kv_prefix_registry_hits_total",
  "xot_tpu_peer_broadcast_failures_total",
  "xot_tpu_peer_rpc_bytes_sent_total",
  "xot_tpu_peer_rpc_bytes_received_total",
  "xot_tpu_peer_rpc_failures_total",
  # Fault tolerance (ISSUE 8; retries labeled {method})
  "xot_tpu_rpc_retries_total",
  "xot_tpu_drain_migrations_total",
  "xot_tpu_requests_recovered_total",
  "xot_tpu_requests_stalled_total",
  # Mixed prefill+decode ticks (ISSUE 14)
  "xot_tpu_sched_tick_prefill_tokens_total",
  "xot_tpu_sched_tick_prefill_pad_tokens_total",  # the padded width of the same slices (ISSUE 55)
  "xot_tpu_sched_ticks_total",  # one per program dispatch of the scheduler loop (ISSUE 24)
  "xot_tpu_sched_dispatches_total",  # {queue}: a dispatch enqueued behind one not yet read back, or onto an empty queue (ISSUE 51)
  "xot_tpu_sched_phase_seconds_total",  # {phase}: host seconds of a tick by phase (ISSUE 24)
  "xot_tpu_sched_wall_seconds_total",  # {kind}: the loop's wall time by what it waits for (ISSUE 41)
  # Disaggregated prefill/decode (ISSUE 10)
  "xot_tpu_kv_stream_pages_total",
  "xot_tpu_kv_stream_bytes_total",
  "xot_tpu_kv_stream_adopted_pages_total",
  "xot_tpu_disagg_handoffs_total",
  # Cluster front door (ISSUE 13; requests labeled {target}, hits {source},
  # throttles {tenant})
  "xot_tpu_router_requests_total",
  "xot_tpu_router_prefix_hits_total",
  "xot_tpu_router_failovers_total",
  "xot_tpu_router_tenant_throttled_total",
  # SLO engine + flight recorder (ISSUE 9)
  "xot_tpu_slo_requests_good_total",  # {class}
  "xot_tpu_slo_requests_bad_total",  # {class,reason}
  "xot_tpu_slo_tokens_total",  # {class,tenant}
  "xot_tpu_slo_good_tokens_total",  # {class,tenant}
  "xot_tpu_flightrec_events_total",  # {type}
  "xot_tpu_anomalies_total",  # {rule}
  "xot_tpu_incident_bundles_total",  # {trigger}
  # Device-program ledger (ISSUE 19; all labeled {family})
  "xot_tpu_program_compiles_total",
  "xot_tpu_program_steady_compiles_total",
  "xot_tpu_program_dispatch_total",
  # gauges
  "xot_tpu_scheduler_batch_occupancy",
  "xot_tpu_scheduler_queue_depth",
  "xot_tpu_scheduler_parked",
  "xot_tpu_scheduler_prefilling",
  "xot_tpu_scheduler_slots_total",
  "xot_tpu_page_pool_pages_total",
  "xot_tpu_page_pool_pages_free",
  "xot_tpu_page_pool_pages_cached",
  "xot_tpu_page_pool_utilization",
  "xot_tpu_qos_queue_depth",
  "xot_tpu_spec_gamma",
  "xot_tpu_kv_draft_bytes",
  "xot_tpu_kv_draft_slots",
  "xot_tpu_kv_draft_pages_equivalent",
  "xot_tpu_kv_tier_host_pages",
  "xot_tpu_kv_tier_host_bytes",
  "xot_tpu_kv_tier_host_utilization",
  "xot_tpu_engine_sessions",
  "xot_tpu_peer_clock_offset_ms",
  "xot_tpu_peer_clock_uncertainty_ms",
  "xot_tpu_peer_circuit_state",
  "xot_tpu_cluster_nodes_reporting",
  "xot_tpu_slo_burn_rate",  # {class,window}
  "xot_tpu_slo_attainment",  # {class}
  "xot_tpu_goodput_tok_s",  # {class}
  "xot_tpu_node_role",  # 0=both 1=prefill 2=decode (ISSUE 10)
  "xot_tpu_kv_quant_bits",  # 16=bf16 8=int8 4=int4 (ISSUE 11)
  "xot_tpu_recurrent_state_bytes",  # per-slot state beside the page pool (ISSUE 34)
  "xot_tpu_recurrent_state_step",  # {form}: 1 on the rule and form the decode programs step that state in: one_pass / reference (Mamba-2, ISSUE 35), delta_one_pass / delta_reference (KDA and Gated DeltaNet, ISSUE 36, 44, 45)
  "xot_tpu_moe_experts_routed",  # the router's width of the loaded shard's expert layers (0: dense) (ISSUE 36)
  "xot_tpu_moe_experts_held",  # how many of those experts' weights the shard holds: fewer for one chip's share of an expert-parallel deployment (ISSUE 36)
  "xot_tpu_moe_ffn_form",  # {form}: 1 on the form the routed experts' product takes in the pool's programs: grouped / block (ops/moe.py ffn_form, ISSUE 40)
  "xot_tpu_moe_grouped_walk",  # {walk}: call sites of the grouped form traced on each walk since start: aligned (tiles of one expert: a prompt's slice, a prefill group) / shared (a decode step, a short group) (ops/moe.py grouped_walk, ISSUE 56)
  "xot_tpu_moe_experts_visited_total",  # distinct held experts the decode rows chose, summed over expert layers and steps; over the next: the mean a layer and step (ISSUE 40)
  "xot_tpu_moe_expert_layer_steps_total",  # expert layers x decode steps of the settled chunks (ISSUE 40)
  "xot_tpu_attention_layers",  # {kind}: the page pool's layers whose attention sees every position (full) / its last window (window) (ISSUE 46)
  "xot_tpu_attention_window_tokens",  # that window, in tokens (0: no layer has one) (ISSUE 46)
  "xot_tpu_attention_rope_layers",  # {rope}: the page pool's layers whose q and k carry a position term (rope) / none (none) (ISSUE 50)
  "xot_tpu_moe_router_input",  # {at}: the expert layers whose router reads its experts' own input (ffn) / the attention's, drawn ahead of it (attn) (ISSUE 50)
  "xot_tpu_moe_expert_gate",  # {act}: the expert layers by their experts' gate nonlinearity: silu / relu (ops/moe.py EXPERT_ACTS, ISSUE 50)
  "xot_tpu_kv_page_lanes_filled",  # {leaf}: the share of a code leaf's row, as the decode kernel's DMA takes it, that holds codes: 1 for whole lane groups (heads of 128 / 256, paired heads of 64), 0.5 for a 64-wide row padded to 128 (ops/paged.py code_lanes_filled, ISSUE 58)
  "xot_tpu_kv_pages_resident_total",  # pages the decode rows hold in every layer that owns pages, summed a decode dispatch (ISSUE 46)
  "xot_tpu_kv_pages_read_total",  # of those, the pages the layers' windows let their attention read (ISSUE 46)
  "xot_tpu_mixed_budget_tokens",  # the tick planner's current prefill-slice budget (ISSUE 14)
  # Multi-LoRA serving (ISSUE 15; swaps labeled {direction}, requests
  # labeled {adapter} — adapter names are client-asserted, same trust note
  # as tenant keys)
  "xot_tpu_lora_adapters_resident",
  "xot_tpu_lora_host_bytes",
  "xot_tpu_lora_swaps_total",
  "xot_tpu_lora_requests_total",
  "xot_tpu_lora_swap_seconds",
  # Device-program ledger (ISSUE 19)
  "xot_tpu_programs_steady",  # 0 warming / 1 steady (post-warmup sentinel armed)
  "xot_tpu_warmup_programs",  # manifest size of the last warmup
  # histograms
  "xot_tpu_ttft_seconds",
  "xot_tpu_itl_seconds",
  "xot_tpu_qos_ttft_seconds",  # {class} (ISSUE 9 — the SLO engine's windows)
  "xot_tpu_qos_itl_seconds",  # {class}
  "xot_tpu_queue_wait_seconds",
  "xot_tpu_prefill_chunk_seconds",
  "xot_tpu_decode_chunk_seconds",
  "xot_tpu_mixed_tick_seconds",  # one fused mixed prefill+decode dispatch (ISSUE 14)
  "xot_tpu_spec_acceptance_ewma",
  "xot_tpu_kv_tier_spill_seconds",
  "xot_tpu_kv_tier_restore_seconds",
  "xot_tpu_kv_tier_restore_pages_per_op",
  "xot_tpu_kv_stream_seconds",  # {peer} (ISSUE 10 — disagg KV-page transfer)
  "xot_tpu_prefill_seconds",
  "xot_tpu_decode_step_seconds",
  # Device-program ledger (ISSUE 19; compile/device labeled {family})
  "xot_tpu_program_compile_seconds",
  "xot_tpu_program_dispatch_seconds",
  "xot_tpu_warmup_compile_seconds",
  # per-peer-link RPC attribution (ISSUE 4; labeled {peer,method} / {method})
  "xot_tpu_peer_rpc_seconds",
  "xot_tpu_peer_rpc_serialize_seconds",
  "xot_tpu_grpc_handler_seconds",
  "xot_tpu_grpc_deserialize_seconds",
}


def test_metric_name_snapshot_after_serving():
  """Drive the batched scheduler once, then assert the exposition carries
  every frozen metric name (and only well-formed xot_tpu_* families)."""
  import re

  import numpy as np

  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  server = _tiny_batched_server()

  async def run():
    await server.submit("snap", np.asarray([5, 6, 7], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)

  asyncio.run(run())
  server.shutdown()
  # Families emitted by paths this scheduler-only drive doesn't hit (node
  # ring/replay, gRPC plane, rarely-taken scheduler branches): materialize
  # them at zero so the pin covers the WHOLE documented exposition contract.
  for name in (
    "requests_total", "requests_replayed_total", "tokens_generated_total",
    "scheduler_rejections_total", "scheduler_parked_total",
    "scheduler_admission_failures_total", "scheduler_preemptions_total",
    "scheduler_page_starved_total", "prefix_cache_hit_pages_total",
    "kv_tier_spilled_pages_total", "kv_tier_spilled_bytes_total",
    "kv_tier_restored_pages_total", "kv_tier_restored_bytes_total",
    "kv_tier_host_evictions_total",
    # Event-driven pool counters: a short solo drive may finish inside its
    # initial allocation and never grow (module-order dependent — earlier
    # test modules usually materialize these into the process-global
    # registry, but the pin must hold in isolation too).
    "page_grow_events_total", "page_grow_pages_total", "page_release_events_total",
  ):
    gm.inc(name, 0)
  gm.inc("kv_prefix_registry_hits_total", 0, labels={"scope": "local"})
  gm.inc("spec_proposed_tokens_total", 0, labels={"proposer": "ngram"})
  gm.inc("spec_accepted_tokens_total", 0, labels={"proposer": "ngram"})
  gm.set_gauge("spec_gamma", 0, labels={"row": "0"})
  gm.set_gauge("spec_proposer", 0, labels={"row": "0"})
  gm.set_gauge("kv_draft_bytes", 0)
  gm.set_gauge("recurrent_state_step", 0, labels={"form": "reference"})  # set when a pool with state leaves is made (ISSUE 35)
  gm.inc("recurrent_state_resets_total", 0)  # event-driven: only a configuration with recurrent layers resets a slot's state (ISSUE 34)
  gm.set_gauge("moe_ffn_form", 0, labels={"form": "block"})  # set when a pool is made for a model with routed experts (ISSUE 40)
  gm.set_gauge("moe_grouped_walk", 0, labels={"walk": "shared"})  # set when a pool is made for a model with routed experts, and again whenever a program with the grouped form is traced (ISSUE 56)
  gm.inc("moe_experts_visited_total", 0)  # event-driven: only a model with routed experts visits any (ISSUE 40)
  gm.inc("moe_expert_layer_steps_total", 0)
  gm.set_gauge("attention_layers", 0, labels={"kind": "full"})  # set when a page pool is made (ISSUE 46)
  gm.set_gauge("attention_window_tokens", 0)
  gm.set_gauge("attention_rope_layers", 0, labels={"rope": "rope"})  # set when a page pool is made (ISSUE 50)
  gm.set_gauge("moe_router_input", 0, labels={"at": "ffn"})  # set when a pool is made for a model with routed experts (ISSUE 50)
  gm.set_gauge("moe_expert_gate", 0, labels={"act": "silu"})
  gm.set_gauge("kv_page_lanes_filled", 0, labels={"leaf": "k"})  # set when a page pool is made (ISSUE 58)
  gm.inc("kv_pages_resident_total", 0)  # counted a paged decode dispatch
  gm.inc("kv_pages_read_total", 0)
  gm.set_gauge("kv_draft_slots", 0)
  gm.set_gauge("kv_draft_pages_equivalent", 0)
  # Mixed ticks (ISSUE 14): a short solo drive never stages a chunked
  # prefill next to resident decode rows, so the mixed families stay
  # event-driven — materialize them at zero for the exposition pin.
  gm.inc("sched_tick_prefill_tokens_total", 0)
  gm.inc("sched_tick_prefill_pad_tokens_total", 0)
  gm.observe_hist("mixed_tick_seconds", 0.0)
  gm.set_gauge("mixed_budget_tokens", 0)
  # Multi-LoRA (ISSUE 15): registry families are event-driven (a solo
  # drive loads no adapter) — materialize them at zero for the pin.
  gm.set_gauge("lora_adapters_resident", 0)
  gm.set_gauge("lora_host_bytes", 0)
  gm.inc("lora_swaps_total", 0, labels={"direction": "in"})
  gm.inc("lora_requests_total", 0, labels={"adapter": "base"})
  gm.observe_hist("lora_swap_seconds", 0.0)
  from xotorch_support_jetson_tpu.utils.metrics import FRACTION_BUCKETS

  gm.observe_hist("spec_acceptance_ewma", 0.0, buckets=FRACTION_BUCKETS)
  gm.set_gauge("kv_tier_host_pages", 0)
  gm.set_gauge("kv_tier_host_bytes", 0)
  gm.set_gauge("kv_tier_host_utilization", 0.0)
  gm.observe_hist("kv_tier_spill_seconds", 0.0)
  gm.observe_hist("kv_tier_restore_seconds", 0.0)
  from xotorch_support_jetson_tpu.utils.metrics import SIZE_BUCKETS

  gm.observe_hist("kv_tier_restore_pages_per_op", 0, buckets=SIZE_BUCKETS)
  gm.inc("grpc_rpcs_total", 0, labels={"method": "SendResult"})
  gm.inc("grpc_rpc_failures_total", 0, labels={"method": "SendResult"})
  gm.inc("qos_submitted_total", 0, labels={"class": "standard"})
  gm.inc("qos_shed_total", 0, labels={"reason": "deadline"})
  gm.inc("qos_rejected_total", 0, labels={"class": "batch"})
  gm.inc("qos_rate_limited_total", 0, labels={"tenant": "default"})
  gm.inc("qos_preemptions_total", 0)
  gm.set_gauge("qos_queue_depth", 0, labels={"class": "standard"})
  gm.inc("peer_broadcast_failures_total", 0, labels={"kind": "result"})
  gm.observe_hist("prefill_seconds", 0.0)
  gm.observe_hist("decode_step_seconds", 0.0)
  gm.set_gauge("engine_sessions", 0)
  link = {"peer": "peer-0", "method": "SendTensor"}
  gm.inc("peer_rpc_bytes_sent_total", 0, labels=link)
  gm.inc("peer_rpc_bytes_received_total", 0, labels=link)
  gm.inc("peer_rpc_failures_total", 0, labels=link)
  gm.observe_hist("peer_rpc_seconds", 0.0, labels=link)
  gm.observe_hist("peer_rpc_serialize_seconds", 0.0, labels={"method": "SendTensor"})
  gm.observe_hist("grpc_handler_seconds", 0.0, labels={"method": "SendTensor"})
  gm.observe_hist("grpc_deserialize_seconds", 0.0, labels={"method": "SendTensor"})
  gm.set_gauge("peer_clock_offset_ms", 0.0, labels={"peer": "peer-0"})
  gm.set_gauge("peer_clock_uncertainty_ms", 0.0, labels={"peer": "peer-0"})
  gm.inc("rpc_retries_total", 0, labels={"method": "SendResult"})
  gm.inc("drain_migrations_total", 0)
  gm.inc("requests_recovered_total", 0)
  gm.inc("requests_stalled_total", 0)
  gm.set_gauge("peer_circuit_state", 0, labels={"peer": "peer-0"})
  # SLO engine + flight recorder (ISSUE 9): families emitted by the SLO
  # accounting hooks / tick and the recorder — materialized at zero when the
  # drive above ran with the engines quiet.
  gm.inc("slo_requests_good_total", 0, labels={"class": "standard"})
  gm.inc("slo_requests_bad_total", 0, labels={"class": "standard", "reason": "shed"})
  gm.inc("slo_tokens_total", 0, labels={"class": "standard", "tenant": "default"})
  gm.inc("slo_good_tokens_total", 0, labels={"class": "standard", "tenant": "default"})
  gm.inc("flightrec_events_total", 0, labels={"type": "admitted"})
  gm.inc("anomalies_total", 0, labels={"rule": "burn_rate"})
  gm.inc("incident_bundles_total", 0, labels={"trigger": "stall"})
  gm.set_gauge("cluster_nodes_reporting", 1)
  # Disaggregated prefill/decode (ISSUE 10): emitted by the node's KV
  # stream / handoff path and the decode-side adopt — off in this drive.
  gm.inc("kv_stream_pages_total", 0)
  gm.inc("kv_stream_bytes_total", 0)
  gm.inc("kv_stream_adopted_pages_total", 0)
  gm.inc("disagg_handoffs_total", 0)
  # Cluster front door (ISSUE 13): emitted only by a router-mode API.
  gm.inc("router_requests_total", 0, labels={"target": "replica-0"})
  gm.inc("router_prefix_hits_total", 0, labels={"source": "advert"})
  gm.inc("router_failovers_total", 0)
  gm.inc("router_tenant_throttled_total", 0, labels={"tenant": "default"})
  gm.observe_hist("kv_stream_seconds", 0.0, labels={"peer": "peer-0"})
  gm.set_gauge("node_role", 0)
  # Device-program ledger (ISSUE 19): the drive itself compiles and
  # dispatches tracked programs (program_compiles_total / dispatch /
  # compile+device seconds land naturally); the STEADY families are
  # event-driven — no warmup ran, nothing recompiled post-steady.
  gm.inc("program_steady_compiles_total", 0, labels={"family": "decode.batch"})
  gm.set_gauge("programs_steady", 0)
  gm.set_gauge("warmup_programs", 0)
  gm.observe_hist("warmup_compile_seconds", 0.0)
  gm.set_gauge("slo_burn_rate", 0.0, labels={"class": "standard", "window": "300s"})
  gm.set_gauge("slo_attainment", 1.0, labels={"class": "standard"})
  gm.set_gauge("goodput_tok_s", 0.0, labels={"class": "standard"})
  gm.observe_hist("qos_ttft_seconds", 0.0, labels={"class": "standard"})
  gm.observe_hist("qos_itl_seconds", 0.0, labels={"class": "standard"})
  text = gm.render_prometheus()
  families = set(re.findall(r"# TYPE (xot_tpu_[a-z0-9_]+) \w+", text))
  missing = EXPECTED_METRIC_NAMES - families
  assert not missing, f"exposition lost metric families: {sorted(missing)}"
  assert all(re.fullmatch(r"xot_tpu_[a-z0-9_]+", f) for f in families)


# ------------------------------------------------- cluster-wide aggregation


@pytest.mark.asyncio
async def test_cluster_metrics_pull_over_opaque_status():
  """Two nodes bridged by in-process 'peers': the API node's pull broadcast
  reaches the peer, the peer replies with its snapshot over the same opaque
  channel, and the merged exposition carries both registries."""
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from xotorch_support_jetson_tpu.utils.metrics import Metrics, metrics as gm
  from tests_support_stubs import NoDiscovery, StubServer

  def make_node(name):
    return Node(name, StubServer(), DummyInferenceEngine(), NoDiscovery(), None, RingMemoryWeightedPartitioningStrategy())

  a, b = make_node("agg-a"), make_node("agg-b")

  class BridgePeer:
    def __init__(self, me, other):
      self._me, self._other = me, other

    def id(self):
      return self._other.id

    async def send_opaque_status(self, request_id, status):
      self._other.on_opaque_status.trigger_all(request_id, status)
      await asyncio.sleep(0)  # let the receiver's created tasks run

  a.peers = [BridgePeer(a, b)]
  b.peers = [BridgePeer(b, a)]

  gm.inc("requests_total", 0)  # ensure the family exists locally
  snaps = await a.collect_cluster_metrics(timeout=2.0)
  assert len(snaps) == 1
  merged = Metrics.merged([gm.snapshot(), *snaps])
  text = merged.render_prometheus()
  assert "xot_tpu_requests_total" in text

  # No peers → instant empty pull (the API then renders local-only).
  a.peers = []
  assert await a.collect_cluster_metrics(timeout=0.1) == []


# ------------------------------------------------------------ API endpoints


async def _dummy_api():
  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from aiohttp.test_utils import TestClient, TestServer
  from tests_support_stubs import NoDiscovery, StubServer

  node = Node(
    "obs-api-node", StubServer(), DummyInferenceEngine(), NoDiscovery(), None,
    RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=16,
  )
  await node.start()
  api = ChatGPTAPI(node, "DummyInferenceEngine", response_timeout=30, default_model="dummy")
  client = TestClient(TestServer(api.app))
  await client.start_server()
  return node, api, client


@pytest.mark.asyncio
async def test_timeline_endpoint_and_metrics_scope():
  node, api, client = await _dummy_api()
  try:
    resp = await client.post(
      "/v1/chat/completions",
      json={"model": "dummy", "messages": [{"role": "user", "content": "aaaa"}], "stream": False},
    )
    assert resp.status == 200, await resp.text()
    data = await resp.json()
    request_id = data["id"].removeprefix("chatcmpl-")

    resp = await client.get(f"/v1/requests/{request_id}/timeline")
    assert resp.status == 200, await resp.text()
    tl = await resp.json()
    assert tl["request_id"] == request_id and tl["finished"]
    stages = [s["stage"] for s in tl["stages"]]
    for expected in ("queued", "admitted", "prefill_chunk", "decode", "detokenize"):
      assert expected in stages, (expected, stages)
    assert tl["total_ms"] > 0 and tl["tokens"] > 0
    assert {"stage", "count", "first_at_ms", "duration_ms"} <= set(tl["stages"][0])

    resp = await client.get("/v1/requests/not-a-request/timeline")
    assert resp.status == 404

    # /metrics local and cluster scopes both render; cluster adds the
    # reporting-node gauge even with zero peers.
    resp = await client.get("/metrics")
    assert resp.status == 200
    local_text = await resp.text()
    assert "xot_tpu_requests_total" in local_text
    resp = await client.get("/metrics?scope=cluster")
    assert resp.status == 200
    cluster_text = await resp.text()
    assert "xot_tpu_cluster_nodes_reporting 1" in cluster_text
    assert "xot_tpu_requests_total" in cluster_text
  finally:
    await client.close()
    await node.stop()


@pytest.mark.asyncio
async def test_traces_endpoint_query_hardening():
  """GET /v1/traces (ISSUE 4 satellite): non-integer n → 400 (used to crash
  the handler into a 500); huge n clamps to the ring-buffer capacity."""
  node, api, client = await _dummy_api()
  try:
    resp = await client.get("/v1/traces")
    assert resp.status == 200
    assert "spans" in await resp.json()

    for bad in ("abc", "1.5", ""):
      resp = await client.get("/v1/traces", params={"n": bad})
      assert resp.status == 400, (bad, await resp.text())

    resp = await client.get("/v1/traces", params={"n": "-3"})
    assert resp.status == 400

    from xotorch_support_jetson_tpu.orchestration.tracing import tracer

    resp = await client.get("/v1/traces", params={"n": str(10**9)})
    assert resp.status == 200
    spans = (await resp.json())["spans"]
    assert len(spans) <= tracer.spans.maxlen
  finally:
    await client.close()
    await node.stop()


@pytest.mark.asyncio
@pytest.mark.parametrize("body,python_level", [({}, 0), ({"python_tracer": True}, 1), ({"python_tracer": "yes"}, 0)])
async def test_profile_endpoint_python_tracer_is_off_unless_asked(tmp_path, monkeypatch, body, python_level):
  """An operator's capture must not slow the host it measures: the python
  tracer is off by default, the host tracer (the ``xot.*`` spans) at level 2."""
  import jax.profiler

  seen = []
  monkeypatch.setattr(jax.profiler, "start_trace", lambda out_dir, profiler_options=None: seen.append(profiler_options))
  monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
  node, api, client = await _dummy_api()
  try:
    resp = await client.post("/v1/profile", json={"duration_ms": 5, "dir": str(tmp_path / "prof"), **body})
    assert resp.status == 200, await resp.text()
    (opts,) = seen
    assert (opts.python_tracer_level, opts.host_tracer_level) == (python_level, 2)
  finally:
    await client.close()
    await node.stop()


@pytest.mark.asyncio
async def test_profile_endpoint(tmp_path, monkeypatch):
  node, api, client = await _dummy_api()
  try:
    monkeypatch.setenv("XOT_TPU_PROFILE", "0")
    resp = await client.post("/v1/profile", json={})
    assert resp.status == 403
    monkeypatch.delenv("XOT_TPU_PROFILE")

    resp = await client.post("/v1/profile", json={"duration_ms": -5})
    assert resp.status == 400

    out_dir = str(tmp_path / "prof")
    resp = await client.post("/v1/profile", json={"duration_ms": 50, "dir": out_dir})
    # 200 when jax.profiler works here; 503 is the documented no-op when the
    # backend can't trace — either way the endpoint must not 500.
    assert resp.status in (200, 503), await resp.text()
    if resp.status == 200:
      data = await resp.json()
      assert data["dir"] == out_dir
      assert data["duration_ms"] >= 50
      import os

      assert os.path.isdir(out_dir)
  finally:
    await client.close()
    await node.stop()


def test_an_ungated_expert_stack_and_a_step_without_an_ffn_are_counted_as_they_are():
  """``_note_expert_form`` (ISSUE 53): the expert layers of a decode step are the stacks' layers that hold an expert's
  DOWN matrix — an ungated expert has no gate leaf, and a layer step with no FFN at all is no expert-layer step —, so
  ``moe_expert_layer_steps_total`` grows by them alone; the gauge ``moe_expert_gate`` names relu² experts."""
  from types import SimpleNamespace

  import jax

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  cfg = config_from_hf(dict(
    model_type="nemotron_h", hidden_size=32, num_hidden_layers=7, hybrid_override_pattern="MEM*EME", num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=64, intermediate_size=32,
    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8, n_groups=2, conv_kernel=4, chunk_size=16, n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=16, n_shared_experts=1,
    moe_shared_expert_intermediate_size=16, norm_topk_prob=True, routed_scaling_factor=2.5, torch_dtype="float32", max_position_embeddings=64,
  ))  # fmt: skip
  params, _ = full_model_params(jax.random.PRNGKey(0), cfg)
  assert cfg.n_layers == 4 and cfg.expert_layers == 3 and "w_experts_gate" not in params["ssm_moe_layers"]
  stub = SimpleNamespace(engine=SimpleNamespace(cfg=cfg, params=params), _expert_layers=0)
  BatchedServer._note_expert_form(stub)
  assert stub._expert_layers == 3
  snap = Metrics.merged([metrics.snapshot()])
  assert snap.gauge_value("moe_expert_gate", labels={"act": "relu2"}) == 3 and snap.gauge_value("moe_expert_gate", labels={"act": "silu"}) == 0 and snap.gauge_value("moe_router_input", labels={"at": "ffn"}) == 3


def test_a_kind_with_no_state_matrix_is_named_by_the_gauge_weighed_by_its_tail_alone_and_logged_so(capsys, monkeypatch):
  """ISSUE 57: a server of gated-short-convolution layers (``cfg.state_matrix`` false) sets ``recurrent_state_step`` to
  the form "no_state_matrix" and every other form to 0, ``recurrent_state_bytes`` to the ``conv`` leaf's bytes — the
  pool has no ``ssm`` leaf to weigh —, and its start-up line gives the same bytes, a slot and in all. A Mamba server
  beside it still names its rule's form and weighs both leaves."""
  import jax
  import numpy as np

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.ops.ssm import STATE_STEP_FORMS
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  base = dict(hidden_size=32, num_attention_heads=4, num_key_value_heads=2, vocab_size=64, intermediate_size=32, torch_dtype="float32", max_position_embeddings=64)
  lfm2 = config_from_hf(dict(
    base, model_type="lfm2_moe", num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"], conv_L_cache=3, num_dense_layers=1, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True, norm_eps=1e-5, rope_theta=1e6,
  ))  # fmt: skip
  granite = config_from_hf(dict(
    base, model_type="granitemoehybrid", num_hidden_layers=2, layer_types=["mamba", "attention"], mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_d_conv=4, mamba_chunk_size=16,
    shared_intermediate_size=32, position_embedding_type="nope",
  ))  # fmt: skip
  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "3")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  read = {}
  for name, cfg in (("lfm2", lfm2), ("granite", granite)):
    engine = JaxShardedInferenceEngine(use_local_mesh=False)
    engine.load_test_model(Shard(name, 0, cfg.n_layers - 1, cfg.n_layers), cfg, full_model_params(jax.random.PRNGKey(0), cfg)[0])
    server = BatchedServer(engine)
    try:
      asyncio.run(server.submit(f"{name}-1", np.asarray([3, 4, 5], np.int32), max_tokens=2, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None))
      snap = Metrics.merged([metrics.snapshot()])
      read[name] = (set(server.cache), snap.gauge_value("recurrent_state_bytes"), {form: snap.gauge_value("recurrent_state_step", labels={"form": form}) for form in STATE_STEP_FORMS}, capsys.readouterr().out)
    finally:
      server.shutdown()
  leaves, weighed, forms, out = read["lfm2"]
  assert leaves == {"k", "v", "conv"} and weighed == 2 * 3 * 2 * 32 * 4 == 1536  # 2 conv layers x 3 slots x 2 rows x 32 channels, float32
  assert forms == {form: int(form == "no_state_matrix") for form in STATE_STEP_FORMS} and STATE_STEP_FORMS[-1] == "no_state_matrix"
  assert "2 of 3 layers keep a recurrent state per slot (3 slots of 512 bytes, 1536 in all, beside" in out
  leaves, weighed, forms, out = read["granite"]
  assert leaves == {"k", "v", "ssm", "conv"} and weighed == 3 * 4 * (4 * 16 * 8 + 3 * (64 + 2 * 8)) and forms == {form: int(form == "reference") for form in STATE_STEP_FORMS}
  assert f"(3 slots of {int(weighed) // 3} bytes, {int(weighed)} in all, beside" in out
