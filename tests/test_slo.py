"""SLO engine, goodput accounting, terminal-stage invariant, and cluster
SLO/bundle assembly (ISSUE 9).

Covers: objective env overrides; bucket-edge threshold semantics; the
multi-window burn-rate math against histogram fixtures; exact cluster merge
(sum of raw counts, never average of averages); scheduler goodput
accounting (within-SLO vs violating, preserved across the token paths);
the XOT_TPU_SLO=0 byte-identical off switch; the every-request-reaches-
exactly-one-terminal invariant across completion, refusal, preempt-resume,
and chaos-injected paths; and the two-node gRPC cluster SLO pull + bundle
assembly with a killed peer yielding an annotated-partial bundle without a
hang.
"""

import asyncio
import time

import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.engine import ServerOverloadedError
from xotorch_support_jetson_tpu.orchestration import slo
from xotorch_support_jetson_tpu.orchestration.flightrec import flightrec
from xotorch_support_jetson_tpu.orchestration.slo import (
  SloEngine,
  hist_over_threshold,
  merge_slo_reports,
  objectives,
  slo_engine,
)
from xotorch_support_jetson_tpu.orchestration.tracing import TERMINAL_STAGES, tracer
from xotorch_support_jetson_tpu.utils.metrics import Metrics, metrics as gm, snapshot_delta


# ------------------------------------------------------------ objectives/env


def test_objectives_defaults_and_env_overrides(monkeypatch):
  assert objectives("interactive")["ttft_p95_ms"] == 500.0
  assert objectives("batch")["availability"] == 0.99
  assert objectives("no-such-class") == objectives("standard")
  monkeypatch.setenv("XOT_TPU_SLO_INTERACTIVE_TTFT_P95_MS", "250")
  monkeypatch.setenv("XOT_TPU_SLO_INTERACTIVE_AVAILABILITY", "0.9999")
  obj = objectives("interactive")
  assert obj["ttft_p95_ms"] == 250.0 and obj["availability"] == 0.9999
  # A nonsense 1.0 target would make the budget zero — clamped below 1.
  monkeypatch.setenv("XOT_TPU_SLO_INTERACTIVE_AVAILABILITY", "1.0")
  assert objectives("interactive")["availability"] < 1.0


def test_slo_off_switch(monkeypatch):
  monkeypatch.setenv("XOT_TPU_SLO", "0")
  assert slo.slo_enabled() is False
  monkeypatch.delenv("XOT_TPU_SLO")
  assert slo.slo_enabled() is True


# ------------------------------------------------------- threshold semantics


def test_hist_over_threshold_bucket_edge_rounding():
  m = Metrics()
  m.observe_hist("h", 0.1, n=90)
  m.observe_hist("h", 1.0, n=10)
  h = m.snapshot()["histograms"]["h"]
  # Exact bucket edge: 0.5 — the 0.1s are under, the 1.0s violate.
  assert hist_over_threshold(h, 0.5) == (10, 100)
  # Non-edge threshold rounds DOWN to the last edge <= it (0.6 -> 0.5):
  # an 0.55 observation counts as violating — conservative toward alerting.
  m2 = Metrics()
  m2.observe_hist("h", 0.55, n=5)
  m2.observe_hist("h", 0.3, n=5)
  h2 = m2.snapshot()["histograms"]["h"]
  assert hist_over_threshold(h2, 0.6) == (5, 10)
  # Threshold above the ladder: only +Inf-bucket entries violate.
  assert hist_over_threshold(h, 60.0) == (0, 100)


# ------------------------------------------------------- window burn math


def _fixture_snapshot():
  """100 interactive requests: 90 TTFTs at 100 ms, 10 at 1 s (threshold
  500 ms -> 10% violations); availability 99 good / 1 bad; 1000 tokens of
  which 800 good."""
  m = Metrics()
  m.observe_hist("qos_ttft_seconds", 0.1, n=90, labels={"class": "interactive"})
  m.observe_hist("qos_ttft_seconds", 1.0, n=10, labels={"class": "interactive"})
  m.inc("slo_requests_good_total", 99, labels={"class": "interactive"})
  m.inc("slo_requests_bad_total", 1, labels={"class": "interactive", "reason": "shed"})
  m.inc("slo_tokens_total", 1000, labels={"class": "interactive", "tenant": "t1"})
  m.inc("slo_good_tokens_total", 800, labels={"class": "interactive", "tenant": "t1"})
  return m.snapshot()


def test_window_burn_rates_against_fixture():
  engine = SloEngine(tick_s=1.0, windows_s=(60.0,))
  now = time.time()
  engine._ring.append((now - 120.0, Metrics().snapshot()))  # empty base, 120 s old
  stats = engine._window_stats(now, _fixture_snapshot(), 60.0)
  w = stats["classes"]["interactive"]
  # TTFT p95 objective (500 ms): 10/100 over -> burn = 0.10 / 0.05 = 2.
  assert w["ttft"] == {"violations": 10, "total": 100, "burn_rate": pytest.approx(2.0)}
  # Availability 0.999: bad fraction 1% vs budget 0.1% -> burn 10.
  assert w["availability"]["good"] == 99 and w["availability"]["bad"] == 1
  assert w["availability"]["burn_rate"] == pytest.approx(10.0)
  # No ITL data -> burn None, never 0 (unknown != healthy).
  assert w["itl"]["burn_rate"] is None
  # Goodput rate over the REAL elapsed span (120 s), not the window label.
  assert w["goodput"]["good_tok_s"] == pytest.approx(800 / 120.0, rel=1e-3)
  # Untouched class: zero counts, burns None.
  b = stats["classes"]["batch"]
  assert b["availability"]["burn_rate"] is None and b["ttft"]["total"] == 0


def test_report_attainment_and_no_history():
  engine = SloEngine(tick_s=1.0, windows_s=(60.0,))
  # No ring at all: a young engine reports zero-traffic windows, attainment None.
  rep = engine._report_locked_free(time.time(), Metrics().snapshot())
  assert rep["classes"]["interactive"]["attainment"] is None
  now = time.time()
  engine._ring.append((now - 90.0, Metrics().snapshot()))
  rep = engine._report_locked_free(now, _fixture_snapshot())
  entry = rep["classes"]["interactive"]
  # Attainment = worst objective over the longest window: min(ttft 0.90,
  # availability 0.99) = 0.90.
  assert entry["attainment"] == pytest.approx(0.90)
  assert entry["goodput_cum"] == {"tokens": 1000, "good_tokens": 800}


def test_tick_exports_gauges_and_is_rate_limited(monkeypatch):
  monkeypatch.setenv("XOT_TPU_BUNDLE_MIN_INTERVAL_S", "999999")
  engine = SloEngine(tick_s=30.0, windows_s=(60.0,))
  engine._ring.append((time.time() - 90.0, Metrics().snapshot()))
  assert engine.maybe_tick() is True
  assert engine.maybe_tick() is False  # inside the tick interval
  text = gm.render_prometheus()
  assert 'xot_tpu_slo_burn_rate{class="interactive",window="60s"}' in text
  assert 'xot_tpu_slo_attainment{class="batch"}' in text
  assert 'xot_tpu_goodput_tok_s{class="standard"}' in text


def test_disabled_engine_reports_and_ticks_nothing(monkeypatch):
  monkeypatch.setenv("XOT_TPU_SLO", "0")
  engine = SloEngine(tick_s=0.001, windows_s=(60.0,))
  assert engine.maybe_tick() is False
  assert len(engine._ring) == 0
  assert engine.report() == {"scope": "local", "enabled": False}


# ------------------------------------------------------------- cluster merge


def _mini_report(node_id, violations, total, good, bad):
  burn = (violations / total / 0.05) if total else None
  n = good + bad
  return {
    "enabled": True,
    "node_id": node_id,
    "windows_s": [300],
    "classes": {
      "interactive": {
        "objectives": objectives("interactive"),
        "windows": {"300": {
          "elapsed_s": 300.0,
          "ttft": {"violations": violations, "total": total, "burn_rate": burn},
          "itl": {"violations": 0, "total": 0, "burn_rate": None},
          "availability": {"good": good, "bad": bad, "burn_rate": (bad / n / 0.001) if n else None},
          "goodput": {"tokens": total * 10, "good_tokens": total * 8, "good_tok_s": None},
        }},
        "goodput_cum": {"tokens": total * 10, "good_tokens": total * 8},
      }
    },
  }


def test_merge_is_exact_not_average_of_averages():
  # Node A: 10/100 over (burn 2.0). Node B: 0/900 over (burn 0.0).
  # Average of burns would say 1.0; the exact cluster burn is
  # (10/1000)/0.05 = 0.2.
  merged = merge_slo_reports([_mini_report("a", 10, 100, 99, 1), _mini_report("b", 0, 900, 900, 0)])
  w = merged["classes"]["interactive"]["windows"]["300"]
  assert w["ttft"] == {"violations": 10, "total": 1000, "burn_rate": pytest.approx(0.2)}
  assert w["availability"]["good"] == 999 and w["availability"]["bad"] == 1
  assert w["availability"]["burn_rate"] == pytest.approx(1 / 1000 / 0.001)
  assert merged["nodes"] == ["a", "b"] and merged["nodes_reporting"] == 2
  assert merged["classes"]["interactive"]["goodput_cum"] == {"tokens": 10000, "good_tokens": 8000}
  # Disabled nodes are counted but contribute nothing.
  merged2 = merge_slo_reports([_mini_report("a", 10, 100, 99, 1), {"enabled": False, "node_id": "off"}])
  assert merged2["nodes_reporting"] == 2
  assert merged2["classes"]["interactive"]["windows"]["300"]["ttft"]["total"] == 100


# ------------------------------------------------- snapshot_delta semantics


def test_snapshot_delta_semantics():
  m = Metrics()
  m.inc("c", 5)
  m.inc("lc", 2, labels={"k": "v"})
  m.set_gauge("g", 10)
  m.observe_hist("h", 0.1, n=3)
  s1 = m.snapshot()
  m.inc("c", 2)
  m.inc("lc", 1, labels={"k": "v"})
  m.set_gauge("g", 4)
  m.observe_hist("h", 0.3, n=2)
  s2 = m.snapshot()
  d = snapshot_delta(s1, s2)
  assert d["counters"]["c"] == 2.0
  assert dict((tuple(map(tuple, k)), v) for k, v in d["labeled_counters"]["lc"])[(("k", "v"),)] == 1.0
  assert d["gauges"]["g"] == 4  # gauges are levels: current value, not delta
  assert sum(d["histograms"]["h"]["counts"]) == 2
  # Shrunk counter (registry restart): floored at zero, never negative.
  assert snapshot_delta(s2, s1)["counters"]["c"] == 0.0
  # Incompatible prev ladder: cur passes through as-is.
  m3 = Metrics()
  m3.observe_hist("h", 2, n=4, buckets=(1.0, 4.0))
  d2 = snapshot_delta(s1, m3.snapshot())
  assert sum(d2["histograms"]["h"]["counts"]) == 4


# ------------------------------------------- scheduler goodput accounting


def _objectives_wide(monkeypatch):
  """CPU tiny-model runs include compile time — keep the latency objectives
  out of the way so 'good' is deterministic."""
  monkeypatch.setenv("XOT_TPU_SLO_STANDARD_TTFT_P95_MS", "600000")
  monkeypatch.setenv("XOT_TPU_SLO_STANDARD_ITL_P99_MS", "600000")


def _drive_tiny(rid, n=4):
  from tests.test_observability import _tiny_batched_server

  server = _tiny_batched_server()
  out = {}

  async def run():
    out["tokens"] = await server.submit(
      rid, np.asarray([5, 6, 7], np.int32), max_tokens=n, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None,
    )

  asyncio.run(run())
  server.shutdown()
  return out["tokens"]


def test_scheduler_goodput_within_slo(monkeypatch):
  _objectives_wide(monkeypatch)
  labels = {"class": "standard", "tenant": "default"}
  before_tok = gm.counter_value("slo_tokens_total", labels=labels)
  before_good = gm.counter_value("slo_good_tokens_total", labels=labels)
  before_ok = gm.counter_value("slo_requests_good_total", labels={"class": "standard"})
  toks = _drive_tiny("slo-good", n=4)
  assert len(toks) == 4
  assert gm.counter_value("slo_tokens_total", labels=labels) == before_tok + 4
  assert gm.counter_value("slo_good_tokens_total", labels=labels) == before_good + 4
  # Availability's GOOD event belongs to the API token choke point (the
  # layer every serving path streams through), NOT the scheduler — a
  # scheduler-only drive must not move it.
  assert gm.counter_value("slo_requests_good_total", labels={"class": "standard"}) == before_ok
  # Per-class TTFT/ITL landed in the labeled families.
  assert gm.hist_count("qos_ttft_seconds", labels={"class": "standard"}) >= 1
  assert gm.hist_count("qos_itl_seconds", labels={"class": "standard"}) >= 1


def test_scheduler_goodput_ttft_violation_counts_total_not_good(monkeypatch):
  monkeypatch.setenv("XOT_TPU_SLO_STANDARD_TTFT_P95_MS", "0.000001")
  monkeypatch.setenv("XOT_TPU_SLO_STANDARD_ITL_P99_MS", "600000")
  labels = {"class": "standard", "tenant": "default"}
  before_tok = gm.counter_value("slo_tokens_total", labels=labels)
  before_good = gm.counter_value("slo_good_tokens_total", labels=labels)
  _drive_tiny("slo-viol", n=4)
  # Delivered tokens all count; none are goodput (the request violated its
  # TTFT objective — latency is goodput's concern, not availability's).
  assert gm.counter_value("slo_tokens_total", labels=labels) == before_tok + 4
  assert gm.counter_value("slo_good_tokens_total", labels=labels) == before_good


def test_slo_off_is_byte_identical(monkeypatch):
  """The acceptance pin: XOT_TPU_SLO=0 XOT_TPU_FLIGHTREC=0 leaves the
  serving path byte-identical — same token stream, zero SLO series moved,
  zero flight events recorded."""
  reference = _drive_tiny("slo-ref", n=4)
  monkeypatch.setenv("XOT_TPU_SLO", "0")
  monkeypatch.setenv("XOT_TPU_FLIGHTREC", "0")
  before = gm.snapshot()
  ring_before = len(flightrec)
  toks = _drive_tiny("slo-off", n=4)
  delta = snapshot_delta(before, gm.snapshot())
  assert toks == reference  # serving output identical
  assert len(flightrec) == ring_before  # recorder untouched
  # NO slo/qos-class series moved: the hooks never ran.
  for name in ("slo_tokens_total", "slo_good_tokens_total", "slo_requests_good_total", "slo_requests_bad_total"):
    assert sum(v for _, v in (delta.get("labeled_counters") or {}).get(name, [])) == 0, name
  for name in ("qos_ttft_seconds", "qos_itl_seconds"):
    series = (delta.get("labeled_histograms") or {}).get(name, [])
    assert sum(sum(h["counts"]) for _, h in series) == 0, name


# --------------------------------------------------- terminal-stage invariant


def _terminal_events(rid):
  tl = tracer.timeline(rid)
  assert tl is not None, rid
  return tl, [e for e in tl["events"] if e["stage"] in TERMINAL_STAGES]


def _qos_server(**kw):
  import jax

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  return BatchedServer(engine, n_slots=1, chunk=2, qos=True, **kw)


def test_terminal_invariant_complete_via_node():
  """Normal completion through the node serving path ends terminal
  'complete' — set by end_request, exactly once."""
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.registry import build_base_shard
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from tests_support_stubs import NoDiscovery, StubServer

  async def run():
    node = Node(
      "term-node", StubServer(), DummyInferenceEngine(), NoDiscovery(), None,
      RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=50,
    )
    await node.start()
    shard = build_base_shard("dummy", "DummyInferenceEngine")
    done = asyncio.Event()
    node.on_token.register("term").on_next(lambda rid, toks, fin: done.set() if fin else None)
    await node.process_prompt(shard, "aaaa", "term-ok")
    await asyncio.wait_for(done.wait(), timeout=30)
    await node.stop()

  asyncio.run(run())
  tl, terms = _terminal_events("term-ok")
  assert tl["finished"] and tl["terminal"] == "complete"
  assert terms == []  # 'complete' is the classification, not a refusal event


@pytest.mark.parametrize("path", ["rejected", "shed_overload", "shed_deadline", "rate_limited"])
def test_terminal_invariant_refusal_paths(path, monkeypatch):
  """Every refusal path stamps EXACTLY ONE terminal refusal stage and
  finishes the timeline — the goodput/availability denominator's contract."""
  server = _qos_server(max_queue=1)
  rid = f"term-{path}"

  async def run():
    streams = {}
    resident = asyncio.Event()

    def emit(r, toks, fin):
      streams.setdefault(r, []).extend(toks)
      if r == "bg-" + path:
        resident.set()

    # A long-running resident occupies the single slot; a queued waiter
    # fills the queue for the overload paths. The resident's first token WAKES
    # this task (no poll on a timer) and it has 99 tokens to go in chunks of
    # two: with a warm compile cache 30 tokens were over inside one 10 ms poll,
    # the waiter was admitted, and the loop below never ended (the suite hung
    # at 96-99 % until its clock cut it: PR 53). A lost race now fails.
    bg = asyncio.create_task(server.submit("bg-" + path, np.asarray([3, 25, 9], np.int32), max_tokens=100, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="standard", tenant="bulk"))
    await asyncio.wait_for(resident.wait(), timeout=120)
    waiter = None
    if path in ("rejected", "shed_overload"):
      # Fill the 1-deep queue. For the shed path the waiter is strictly
      # lower priority than the arrival (it becomes the victim); for the
      # reject path it is the SAME class, so nothing outranked waits and
      # the new arrival itself is rejected.
      waiter = asyncio.create_task(server.submit("w-" + path, np.asarray([4, 4, 4], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch" if path == "shed_overload" else "interactive", tenant="bulk"))
      while server.queue.qsize() == 0:
        assert not waiter.done() and not bg.done(), "the resident left its slot before the waiter was queued"
        await asyncio.sleep(0)
    if path == "shed_deadline":
      monkeypatch.setattr(server.qos, "estimate_completion_ms", lambda **kw: 1e9)
    if path == "rate_limited":
      def deny(tenant, toks):
        raise ServerOverloadedError("rate limited (test)")
      monkeypatch.setattr(server.qos, "check_rate", deny)
    submit = server.submit(
      rid, np.asarray([9, 9, 9], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(),
      emit=emit, priority="interactive" if path in ("rejected", "shed_overload") else "standard",
      tenant="vip", deadline_ms=5.0 if path == "shed_deadline" else None,
    )
    if path == "shed_overload":
      await submit  # the interactive arrival displaces the queued batch waiter
      with pytest.raises(ServerOverloadedError):
        await waiter
    else:
      with pytest.raises(Exception):
        await submit
      if waiter is not None:
        await waiter  # the same-class waiter was NOT displaced; it completes
    await bg

  asyncio.run(run())
  server.shutdown()
  victim = {"rejected": rid, "shed_overload": "w-" + path, "shed_deadline": rid, "rate_limited": rid}[path]
  expected = {"rejected": "rejected", "shed_overload": "shed", "shed_deadline": "shed", "rate_limited": "rate_limited"}[path]
  tl, terms = _terminal_events(victim)
  assert tl["finished"] and tl["terminal"] == expected
  assert len(terms) == 1 and terms[0]["stage"] == expected


def test_terminal_invariant_preempt_resume_single_complete():
  """A preempted-then-resumed request crosses preempt/resume stages but
  still terminates EXACTLY ONCE as complete; goodput judges the FIRST
  incarnation's TTFT (slo_ttft_s survives the preemption)."""
  from xotorch_support_jetson_tpu.inference.qos import QosConfig, QosPolicy

  import jax

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  server = BatchedServer(engine, n_slots=1, chunk=2, qos=QosPolicy(QosConfig(aging_s=10_000.0)))

  async def run():
    started = asyncio.Event()
    streams = {}

    def emit(r, toks, fin):
      streams.setdefault(r, []).extend(toks)
      if r == "bg" and len(streams["bg"]) >= 4:
        started.set()

    bg = asyncio.create_task(server.submit("bg", np.asarray([3, 25, 9], np.int32), max_tokens=24, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch", tenant="bulk"))
    await asyncio.wait_for(started.wait(), timeout=60)
    await asyncio.wait_for(
      server.submit("vip", np.asarray([7, 1, 88], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="interactive", tenant="vip"),
      timeout=60,
    )
    await asyncio.wait_for(bg, timeout=60)

  asyncio.run(run())
  server.shutdown()
  # The preempted request's timeline carries the preempted stage but no
  # refusal terminal; its availability classification is 'complete'-bound
  # (end_request runs at the node/API layer — at scheduler level no refusal
  # stage may have fired).
  tl = tracer.timeline("bg")
  stages = [e["stage"] for e in tl["events"]]
  assert "preempted" in stages
  assert [e for e in tl["events"] if e["stage"] in TERMINAL_STAGES] == []
  assert tl["terminal"] is None  # the API layer's end_request classifies it
  tracer.end_request("bg")
  assert tracer.timeline("bg")["terminal"] == "complete"


def test_terminal_invariant_chaos_kill_path():
  """Chaos-injected node kill mid-decode: the replay completes the request
  token-identically (PR 8) and the terminal classification is still exactly
  one 'complete' — with the replay recorded in the flight ring (ISSUE 9:
  the forensics of the acceptance scenario)."""
  from xotorch_support_jetson_tpu.networking.faults import chaos
  from xotorch_support_jetson_tpu.networking.retry import breakers, peer_health
  from tests.test_chaos import FAULT_FREE_TOKENS, _drive_ring_request
  from tests.test_networking import _make_cluster

  chaos.clear()
  breakers.reset()
  peer_health.reset()

  async def run():
    nodes = await _make_cluster(2)
    killed = []

    def maybe_kill(collected):
      if not killed and collected:
        killed.append(True)
        chaos.kill("node1")
        asyncio.ensure_future(nodes[1].server.stop())

    try:
      collected = await _drive_ring_request(nodes, "slo-chaos-kill", on_tokens=maybe_kill)
      assert killed and collected == FAULT_FREE_TOKENS
    finally:
      chaos.clear()
      breakers.reset()
      peer_health.reset()
      for n in nodes:
        await n.stop()

  asyncio.run(run())
  tl, terms = _terminal_events("slo-chaos-kill")
  assert tl["finished"] and tl["terminal"] == "complete"
  assert terms == []
  # The flight ring holds the replay in causal order before the completion.
  evs = flightrec.query(request_id="slo-chaos-kill", limit=100)
  types = [e["type"] for e in evs]
  assert "replay" in types and "complete" in types
  assert types.index("replay") < types.index("complete")


# ------------------------------------------------------ cluster SLO + bundle


def test_cluster_slo_and_bundle_on_real_grpc_cluster(monkeypatch, tmp_path):
  """The acceptance fixture: a REAL two-node gRPC cluster. /v1/slo's
  cluster scope merges both nodes' reports pulled over the opaque-status
  channel; a bundle captures both peers' parts; killing a peer yields an
  annotated-partial bundle WITHOUT a hang."""
  monkeypatch.setenv("XOT_TPU_BUNDLE_DIR", str(tmp_path))
  from tests.test_chaos import _drive_ring_request
  from tests.test_networking import _make_cluster

  out = {}

  async def run():
    nodes = await _make_cluster(2)
    try:
      # Serve one real request over the ring so timelines/counters move.
      await _drive_ring_request(nodes, "slo-cluster-req")
      # Give the (shared, in-process) engine a window base so burn rates
      # compute over real counter deltas.
      slo_engine.reset()
      slo_engine._ring.append((time.time() - 400.0, Metrics().snapshot()))
      slo.note_good("interactive")
      slo.note_bad("interactive", "shed")
      reports = await nodes[0].collect_cluster_slo()
      out["reports"] = reports
      out["merged"] = nodes[0].merged_cluster_slo(reports)
      out["local"] = slo_engine.report(node_id="node0")
      bundle = await nodes[0].collect_cluster_bundle(reason="drill", timeout=5.0)
      out["bundle"] = bundle
      # Kill the peer: its server goes down hard.
      await nodes[1].stop()
      t0 = time.monotonic()
      out["partial"] = await nodes[0].collect_cluster_bundle(reason="dead-peer", timeout=2.0)
      out["partial_elapsed"] = time.monotonic() - t0
    finally:
      for n in nodes:
        try:
          await n.stop()
        except Exception:
          pass

  asyncio.run(run())
  # One report per peer, carrying the peer's node id.
  assert [r.get("node_id") for r in out["reports"]] == ["node1"]
  merged = out["merged"]
  assert merged["scope"] == "cluster" and merged["nodes_reporting"] == 2
  assert set(merged["nodes"]) == {"node0", "node1"}
  # Merged counts are the SUM of both nodes' raw counts (shared in-process
  # registry -> exactly 2x the local report), and the burn is recomputed
  # from the sums.
  wk = str(int(min(slo_engine.windows)))
  local_avail = out["local"]["classes"]["interactive"]["windows"][wk]["availability"]
  merged_avail = merged["classes"]["interactive"]["windows"][wk]["availability"]
  assert merged_avail["good"] == 2 * local_avail["good"]
  assert merged_avail["bad"] == 2 * local_avail["bad"]
  assert merged_avail["bad"] >= 1 and merged_avail["burn_rate"] > 0  # the availability burn is visible
  # Full bundle: both peers answered, each part carries its flight events.
  bundle = out["bundle"]
  assert bundle["nodes_reporting"] == 2 and bundle["nodes_unreachable"] == []
  assert {p.get("node_id") for p in bundle["parts"]} == {"node0", "node1"}
  assert all("events" in p and "breakers" in p for p in bundle["parts"])
  # Killed peer: annotated as unreachable, and the call stayed bounded.
  partial = out["partial"]
  unreachable = partial["nodes_unreachable"]
  assert [u["node_id"] for u in unreachable] == ["node1"] and unreachable[0]["unreachable"] is True
  assert partial["nodes_reporting"] == 1
  assert out["partial_elapsed"] < 10.0  # never waits out a dead peer


@pytest.mark.asyncio
async def test_slo_endpoint_local_and_disabled(monkeypatch):
  from aiohttp.test_utils import TestClient, TestServer

  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from tests_support_stubs import NoDiscovery, StubServer

  node = Node(
    "slo-api", StubServer(), DummyInferenceEngine(), NoDiscovery(), None,
    RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=50,
  )
  await node.start()
  api = ChatGPTAPI(node, "DummyInferenceEngine", response_timeout=30, default_model="dummy")
  client = TestClient(TestServer(api.app))
  await client.start_server()
  try:
    # A served request counts ONE availability good event at the API token
    # choke point — every serving mode streams through it (the plain/ring
    # path included, which never touches the batched scheduler's hooks).
    before_ok = gm.counter_value("slo_requests_good_total", labels={"class": "standard"})
    resp = await client.post(
      "/v1/chat/completions",
      json={"model": "dummy", "messages": [{"role": "user", "content": "aaaa"}], "stream": False},
    )
    assert resp.status == 200
    assert gm.counter_value("slo_requests_good_total", labels={"class": "standard"}) == before_ok + 1
    resp = await client.get("/v1/slo")
    data = await resp.json()
    assert resp.status == 200 and data["enabled"] is True
    assert set(data["classes"]) == {"interactive", "standard", "batch"}
    for cls in data["classes"].values():
      assert set(cls["objectives"]) == {"ttft_p95_ms", "itl_p99_ms", "availability"}
    # Cluster scope with no peers: merged shape, one reporter.
    resp = await client.get("/v1/slo?scope=cluster")
    data = await resp.json()
    assert data["scope"] == "cluster" and data["nodes_reporting"] == 1
    monkeypatch.setenv("XOT_TPU_SLO", "0")
    resp = await client.get("/v1/slo")
    data = await resp.json()
    assert resp.status == 200 and data["enabled"] is False
  finally:
    await client.close()
    await node.stop()
