"""Headline benchmark: single-chip decode throughput on the flagship model.

Runs on whatever accelerator JAX exposes (one TPU chip under the driver).
Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
supporting fields. The reference publishes no numbers (BASELINE.md), so
``vs_baseline`` is reported against the driver-recorded history when present
(BENCH_r*.json) and null otherwise.

Model: llama-3.2-1b geometry, random bf16 weights (no network egress in the
bench environment). Decode uses the fused lax.scan loop (models/decoder.py
``fused_decode``) — one compiled program for the whole token stream, KV cache
donated in place.
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

import jax

from xotorch_support_jetson_tpu.utils.helpers import apply_platform_override, configure_compile_cache, device_summary

apply_platform_override()
configure_compile_cache()

import jax.numpy as jnp
import numpy as np


def gate_headline(tok_per_s: float, serving_tok_s: float | None) -> tuple[float, bool]:
  """Sanity-gate the headline decode number against the serving-path number.

  A timing that ends before the device does produces physically impossible
  throughputs (the round-2 record claimed 79,922 tok/s for a 2.45 GB-weight
  model whose HBM roofline is ~220 tok/s). Both paths run the same
  weights-bound decode, so a headline more
  than 2x the serving number cannot be real — treat it as a timing artifact
  and report the serving number instead, flagging the trip.
  """
  if serving_tok_s and tok_per_s > 2.0 * serving_tok_s:
    return float(serving_tok_s), True
  return float(tok_per_s), False


def gate_lookahead(ratio: float | None) -> float | None:
  """Sanity-gate the lookahead/sync A/B ratio (same drift-gate pattern as
  ``gate_headline``). Overlapping host bookkeeping with device compute can
  at most hide the per-chunk host window — a ratio outside [1/3, 3] means
  one of the two back-to-back rounds hit a timing artifact (a stall, a timer
  that stopped early), not a real scheduling delta; drop it
  rather than record it."""
  if ratio is None:
    return None
  return float(ratio) if 1.0 / 3.0 <= ratio <= 3.0 else None


def gate_overload(shed_rate: float | None) -> float | None:
  """Sanity-gate the overload round's shed rate (same drift-gate pattern).
  The round offers ~2x capacity, so a healthy QoS layer sheds SOME batch
  work but nowhere near everything: a rate outside [0, 0.95] means the
  round broke (scheduler wedged and shed the world, or the counter went
  negative across a registry reset) — drop it rather than record it."""
  if shed_rate is None:
    return None
  return float(shed_rate) if 0.0 <= shed_rate <= 0.95 else None


def gate_slo(fraction: float | None) -> float | None:
  """Sanity-gate the overload round's SLO fractions (ISSUE 9: interactive
  availability attainment and the goodput ratio — same drift-gate pattern).
  Both are ratios of counter deltas from the same round, so honest values
  live in [0, 1] exactly; outside means the delta went negative across a
  registry reset or the round broke — drop it rather than record it."""
  if fraction is None:
    return None
  return float(fraction) if 0.0 <= fraction <= 1.0 else None


def gate_spec_batch(ratio: float | None) -> float | None:
  """Sanity-gate the batched-spec/plain aggregate A/B ratio (same drift-gate
  pattern as ``gate_lookahead``). Draft-then-verify multiplies tokens per
  target weight pass by at most gamma+1 (= 5 at the benched depth) and the
  acceptance-adaptive floor bounds the downside near parity, so honest
  ratios live in roughly [0.5, 5]: outside [1/3, 8] one side of the
  back-to-back A/B hit a timing artifact (a timer that stopped early, a
  stall) — drop it rather than record a fake speedup/regression."""
  if ratio is None:
    return None
  return float(ratio) if 1.0 / 3.0 <= ratio <= 8.0 else None


def gate_spec_ngram(ratio: float | None) -> float | None:
  """Drift gate for the draft-free n-gram spec/plain A/B ratio (ISSUE 12 —
  same artifact-filter shape as ``gate_spec_batch``). N-gram proposals cost
  no device work and the on-stream rounds advance up to gamma+1 tokens per
  verify at the benched depth 8, so honest ratios on the repetition-heavy
  workload live in roughly [0.5, 9]; the acceptance-EWMA floor bounds the
  downside near parity. Outside [1/3, 12] one side of the back-to-back A/B
  hit a timing artifact — drop it rather than record a fake speedup."""
  if ratio is None:
    return None
  return float(ratio) if 1.0 / 3.0 <= ratio <= 12.0 else None


def gate_paged_b48(ratio: float | None) -> float | None:
  """Drift gate for ``paged_vs_dense_ratio_b48`` (ISSUE 11: the tentpole
  gauge — target >= 0.95 with the retuned shape-aware kernel; the r5 gap
  was 0.80). Same artifact-filter shape as ``gate_lookahead``: the ratio
  compares two same-methodology aggregates, so values far outside a
  generous plausibility band are measurement artifacts (poisoned
  denominator, truncated run), not regressions worth recording. Honest
  regressions INSIDE the band (e.g. 0.7) are recorded so the drift check
  can flag them against the target."""
  if ratio is None:
    return None
  if not (0.05 <= ratio <= 2.5):
    return None
  return ratio


def gate_kv_tier(value: float | None, lo: float = 0.01, hi: float = 1000.0) -> float | None:
  """Sanity-gate the KV-tier round's numbers (same drift-gate pattern).
  Spill/restore bandwidths outside [0.01, 1000] GB/s are timing artifacts
  (a timer that stops early can report a PCIe copy at impossible rates; a
  stall can report near-zero), and the recompute/restore
  resume ratio rides the same gate with its own bounds — drop artifacts
  rather than record them."""
  if value is None:
    return None
  return float(value) if lo <= value <= hi else None


def gate_disagg(value: float | None, lo: float = 0.001, hi: float = 10000.0) -> float | None:
  """Drift gate for the disagg round's numbers (ISSUE 10): TTFT/ITL-ratio/
  GB-s values outside a generous plausibility band are timing artifacts (a
  stalled fixture or a timer that stopped early), not results — emit
  null rather than poison the tracked record. Same band-check as
  ``gate_kv_tier``, kept as a named gate so each field's bounds are pinned
  independently in test_bench_gate."""
  return gate_kv_tier(value, lo=lo, hi=hi)


def gate_router(value: float | None, lo: float = 0.001, hi: float = 1000.0) -> float | None:
  """Drift gate for the router round's numbers (ISSUE 13): the
  affine-vs-random TTFT ratio, the prefix hit rate, and the failover
  splice window each ride this band check with their own bounds (the
  ``gate_kv_tier`` pattern — values outside a generous plausibility band
  are timing artifacts, not results; honest regressions INSIDE the band
  stay recorded so drift is visible)."""
  return gate_kv_tier(value, lo=lo, hi=hi)


def gate_mixed(value: float | None, lo: float = 0.001, hi: float = 1000.0) -> float | None:
  """Drift gate for the mixed-tick round's numbers (ISSUE 14): the
  mid-burst resident ITL p50s, their mixed/alternating ratio, and the burst
  TTFT p50s each ride this band check with their own bounds (the
  ``gate_kv_tier`` pattern — values outside a generous plausibility band
  are timing artifacts, not results; honest regressions INSIDE the band
  stay recorded so drift is visible)."""
  return gate_kv_tier(value, lo=lo, hi=hi)


def gate_lora(value: float | None, lo: float = 0.001, hi: float = 1000.0) -> float | None:
  """Drift gate for the multi-LoRA round's numbers (ISSUE 15): the
  mixed-adapter vs base B=8 throughput ratio (acceptance bar ≥ 0.5 —
  adapter overhead must not halve batched throughput) and the adapter
  swap-in latency p50 each ride this band check with their own bounds
  (the ``gate_kv_tier`` pattern — values outside a generous plausibility
  band are timing artifacts, not results; honest regressions INSIDE the
  band stay recorded so drift is visible)."""
  return gate_kv_tier(value, lo=lo, hi=hi)


def gate_failover(recovery_ms: float | None, lo: float = 1.0, hi: float = 120000.0) -> float | None:
  """Sanity-gate the failover round's recovery latency (same drift-gate
  pattern). Recovery = kill-to-next-client-visible-token on the localhost
  two-node ring: the replay delay + one re-prefill, so honest values live
  in tens-of-ms to tens-of-seconds. Outside [1 ms, 120 s] the round broke
  (a token raced the kill, or the stream wedged until an outer timeout) —
  drop it rather than record it."""
  if recovery_ms is None:
    return None
  return float(recovery_ms) if lo <= recovery_ms <= hi else None


def gate_compile(value: float | None, lo: float = 0.0, hi: float = 0.0) -> float | None:
  """Drift gate for the program-ledger round (ISSUE 19). The defaults ARE
  the steady band: ``steady_state_compiles`` must be exactly 0 — the repo's
  no-recompile invariant (traced hooks, pow2 pad buckets, static switches)
  measured, not asserted — so any nonzero count is a broken round and drops
  to null, which the drift check surfaces as a missing metric.
  ``warmup_compile_s_total`` rides the same check with a generous
  plausibility band (``lo=0.0, hi=3600.0``)."""
  if value is None:
    return None
  return float(value) if lo <= value <= hi else None


def labeled_hist_delta_quantile(before: dict, after: dict, name: str, q: float, where: dict | None = None) -> float | None:
  """Quantile of a LABELED histogram family's growth between two registry
  snapshots, aggregated across every label series (the per-peer-link RPC
  histograms are ``{peer,method}``-labeled; the bench wants the p50 over the
  whole ring, not one link). ``where`` keeps only series whose label set
  contains those pairs (e.g. ``{"method": "SendResult"}``). Delta math is
  the shared ``utils/metrics.py snapshot_delta`` (ISSUE 9 satellite) — same
  measured-round isolation as the unlabeled ``_hist_delta_quantile``:
  warm-up observations don't own the tail."""
  from xotorch_support_jetson_tpu.utils.metrics import Metrics, snapshot_delta

  want = set((str(k), str(v)) for k, v in (where or {}).items())
  series = (snapshot_delta(before, after).get("labeled_histograms") or {}).get(name) or []
  buckets: list | None = None
  counts: list | None = None
  for key, h in series:
    if want and not want <= {tuple(kv) for kv in key}:
      continue
    if buckets is None:
      buckets = list(h["buckets"])
      counts = [0] * len(h["counts"])
    if list(h["buckets"]) != buckets or len(h["counts"]) != len(counts):
      continue  # foreign ladder: can't aggregate bucket-wise, skip series
    for i, c in enumerate(h["counts"]):
      counts[i] += int(c)
  if buckets is None:
    return None
  m = Metrics.merged([{"histograms": {name: {"buckets": buckets, "counts": counts, "sum": 0.0}}}])
  return m.quantile(name, q)


def bench_cross_node_hops() -> tuple[float | None, float | None]:
  """Two-node localhost gRPC ring (dummy engine): drive one request across
  the ring and report (hop_serialize_ms_p50, hop_rpc_ms_p50) from the
  per-hop histograms the data plane now records (ISSUE 4). Model compute is
  deliberately trivial — what this measures is the serialization + gRPC
  overhead per ring hop, the per-hop tax the cross-node attribution exists
  to expose."""
  import asyncio

  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.networking.discovery import Discovery
  from xotorch_support_jetson_tpu.networking.grpc.grpc_peer_handle import GRPCPeerHandle
  from xotorch_support_jetson_tpu.networking.grpc.grpc_server import GRPCServer
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.registry import build_base_shard
  from xotorch_support_jetson_tpu.topology.device_capabilities import DeviceCapabilities, DeviceFlops
  from xotorch_support_jetson_tpu.topology.partitioning import (
    RingMemoryWeightedPartitioningStrategy,
    map_partitions_to_shards,
  )
  from xotorch_support_jetson_tpu.utils.helpers import find_available_port
  from xotorch_support_jetson_tpu.utils.metrics import metrics as global_metrics

  class _Static(Discovery):
    def __init__(self, peers):
      self._peers = peers

    async def start(self):
      pass

    async def stop(self):
      pass

    async def discover_peers(self, wait_for_peers: int = 0):
      return self._peers

  caps = DeviceCapabilities(model="bench", chip="cpu", memory=1024, flops=DeviceFlops(1, 2, 4))

  async def run() -> tuple[float | None, float | None]:
    ports = [find_available_port("127.0.0.1") for _ in range(2)]
    ids = ["bench-hop-0", "bench-hop-1"]
    nodes = []
    for i in range(2):
      peers = [GRPCPeerHandle(ids[j], f"127.0.0.1:{ports[j]}", "bench", caps) for j in range(2) if j != i]
      node = Node(ids[i], None, DummyInferenceEngine(), _Static(peers), None, RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=64)
      node.server = GRPCServer(node, "127.0.0.1", ports[i])
      nodes.append(node)
    await asyncio.gather(*(n.start() for n in nodes))
    try:
      for _ in range(100):
        if all(
          len(n.topology.nodes) == 2 and len(map_partitions_to_shards(n.partitioning_strategy.partition(n.topology), 8, "dummy")) == 2
          for n in nodes
        ):
          break
        await asyncio.gather(*(n.collect_topology(set()) for n in nodes))
        await asyncio.sleep(0.05)
      shard = build_base_shard("dummy", "DummyInferenceEngine")
      done = asyncio.Event()
      nodes[0].on_token.register("bench-hop").on_next(lambda rid, toks, fin: done.set() if fin else None)
      before = global_metrics.snapshot()
      await nodes[0].process_prompt(shard, "aaaa", "bench-hop-req")
      await asyncio.wait_for(done.wait(), timeout=30)
      after = global_metrics.snapshot()
      ser = labeled_hist_delta_quantile(before, after, "peer_rpc_serialize_seconds", 0.50)
      # LEAF hop only: a ring-forwarding SendTensor's client latency includes
      # the whole awaited downstream generation (span-tree semantics), so its
      # p50 tracks generation length, not the per-hop wire tax. SendResult
      # never nests — serialize + wire + deliver is all it is.
      rpc = labeled_hist_delta_quantile(before, after, "peer_rpc_seconds", 0.50, where={"method": "SendResult"})
      return (
        round(ser * 1e3, 3) if ser is not None else None,
        round(rpc * 1e3, 3) if rpc is not None else None,
      )
    finally:
      await asyncio.gather(*(n.stop() for n in nodes), return_exceptions=True)

  return asyncio.run(run())


def bench_failover_recovery(n_drills: int = 3) -> tuple[float | None, int | None]:
  """Kill-mid-decode failover drill on the localhost two-node gRPC ring
  (ISSUE 8): per drill, stream one request across the ring, simulate the
  peer's death with the deterministic fault injector at the first
  client-visible token, and measure kill-to-next-token (the elastic replay's
  client-visible recovery window). Returns (failover_recovery_ms_p50,
  requests_lost) — a lost request is one that never reaches a finish event
  within the drill bound (the exact hang ROADMAP item 4 forbids)."""
  import asyncio

  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.networking.discovery import Discovery
  from xotorch_support_jetson_tpu.networking.faults import chaos
  from xotorch_support_jetson_tpu.networking.grpc.grpc_peer_handle import GRPCPeerHandle
  from xotorch_support_jetson_tpu.networking.grpc.grpc_server import GRPCServer
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.registry import build_base_shard
  from xotorch_support_jetson_tpu.topology.device_capabilities import DeviceCapabilities, DeviceFlops
  from xotorch_support_jetson_tpu.topology.partitioning import (
    RingMemoryWeightedPartitioningStrategy,
    map_partitions_to_shards,
  )
  from xotorch_support_jetson_tpu.utils.helpers import find_available_port

  class _Static(Discovery):
    def __init__(self, peers):
      self._peers = peers

    async def start(self):
      pass

    async def stop(self):
      pass

    async def discover_peers(self, wait_for_peers: int = 0):
      return self._peers

  caps = DeviceCapabilities(model="bench", chip="cpu", memory=1024, flops=DeviceFlops(1, 2, 4))
  old_delay = os.environ.get("XOT_TPU_RETRY_DELAY_S")
  os.environ["XOT_TPU_RETRY_DELAY_S"] = "0.2"  # drill cadence, not the 3 s prod default

  async def drill(k: int) -> tuple[float | None, bool]:
    ports = [find_available_port("127.0.0.1") for _ in range(2)]
    ids = [f"bench-fo{k}-0", f"bench-fo{k}-1"]
    nodes = []
    for i in range(2):
      peers = [GRPCPeerHandle(ids[j], f"127.0.0.1:{ports[j]}", "bench", caps) for j in range(2) if j != i]
      node = Node(ids[i], None, DummyInferenceEngine(), _Static(peers), None, RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=64)
      node.server = GRPCServer(node, "127.0.0.1", ports[i])
      nodes.append(node)
    await asyncio.gather(*(n.start() for n in nodes))
    try:
      for _ in range(100):
        if all(
          len(n.topology.nodes) == 2 and len(map_partitions_to_shards(n.partitioning_strategy.partition(n.topology), 8, "dummy")) == 2
          for n in nodes
        ):
          break
        await asyncio.gather(*(n.collect_topology(set()) for n in nodes))
        await asyncio.sleep(0.05)
      shard = build_base_shard("dummy", "DummyInferenceEngine")
      done = asyncio.Event()
      t_kill: list[float] = []
      t_recover: list[float] = []

      def on_tok(rid, toks, fin):
        now = time.perf_counter()
        if toks and not t_kill:
          chaos.kill(ids[1])  # peer dies at the first client-visible token
          t_kill.append(now)
        elif toks and t_kill and not t_recover:
          t_recover.append(now)
        if fin:
          done.set()

      nodes[0].on_token.register("bench-fo").on_next(on_tok)
      asyncio.ensure_future(nodes[0].process_prompt(shard, "aaaa", f"bench-fo-req{k}"))
      lost = False
      try:
        await asyncio.wait_for(done.wait(), timeout=60)
      except asyncio.TimeoutError:
        lost = True
      rec_ms = (t_recover[0] - t_kill[0]) * 1e3 if t_kill and t_recover else None
      return rec_ms, lost
    finally:
      chaos.revive(ids[1])
      await asyncio.gather(*(n.stop() for n in nodes), return_exceptions=True)

  try:
    recoveries: list[float] = []
    lost_total = 0
    for k in range(n_drills):
      rec_ms, lost = asyncio.run(drill(k))
      if rec_ms is not None:
        recoveries.append(rec_ms)
      lost_total += int(lost)
    p50 = float(np.percentile(np.asarray(recoveries), 50)) if recoveries else None
    return gate_failover(round(p50, 1) if p50 is not None else None), lost_total
  finally:
    if old_delay is None:
      os.environ.pop("XOT_TPU_RETRY_DELAY_S", None)
    else:
      os.environ["XOT_TPU_RETRY_DELAY_S"] = old_delay


def bench_disagg(n_burst: int = 4, n_resident_tokens: int = 96, n_burst_tokens: int = 8) -> tuple[float | None, float | None, float | None]:
  """Disaggregated prefill/decode round (ISSUE 10) on the localhost two-node
  gRPC ring with a tiny-but-real jax model: a RESIDENT decode stream runs
  while a chunked-prefill BURST arrives — the exact interference the
  colocated scheduler cannot avoid. Phase A (colocated, single node): the
  burst's prefill chunks interleave with the resident stream's decode
  chunks. Phase B (disagg: prefill node + decode node): prefill runs on
  node 0, decode on node 1, KV pages stream between them.

  Returns (disagg_ttft_ms_p50, disagg_vs_colocated_itl_p50, kv_stream_gbps):
  burst TTFT p50 under disagg, the resident stream's mid-burst ITL p50
  ratio disagg/colocated (≤1 ⇒ the decode node is undisturbed), and the
  measured KV-page transfer rate from the ``kv_stream`` timeline stages."""
  import asyncio

  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.networking.discovery import Discovery
  from xotorch_support_jetson_tpu.networking.grpc.grpc_peer_handle import GRPCPeerHandle
  from xotorch_support_jetson_tpu.networking.grpc.grpc_server import GRPCServer
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.orchestration.tracing import tracer
  from xotorch_support_jetson_tpu.topology.device_capabilities import DeviceCapabilities, DeviceFlops
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from xotorch_support_jetson_tpu.utils.helpers import find_available_port

  class _Static(Discovery):
    def __init__(self, peers):
      self._peers = peers

    async def start(self):
      pass

    async def stop(self):
      pass

    async def discover_peers(self, wait_for_peers: int = 0):
      return self._peers

  prompt = [(i % 250) + 2 for i in range(96)]

  class _Tok:
    eos_token_id = None

    def encode(self, p):
      return list(prompt)

    def decode(self, toks):
      return " ".join(map(str, toks))

  caps = DeviceCapabilities(model="bench", chip="cpu", memory=1024, flops=DeviceFlops(1, 2, 4))
  cfg = tiny_test_config(n_layers=2, max_seq_len=512)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  overrides = {
    "XOT_TPU_DISAGG": "1", "XOT_TPU_PAGE_SIZE": "16", "XOT_TPU_PREFILL_CHUNK": "32",
    "XOT_TPU_BATCH_CHUNK": "4", "XOT_TPU_BATCH_SLOTS": "6",
  }
  saved = {k: os.environ.get(k) for k in overrides}
  os.environ.update(overrides)

  async def phase(tag: str, disagg: bool) -> tuple[float | None, float | None, float | None]:
    n_nodes = 2 if disagg else 1
    ports = [find_available_port("127.0.0.1") for _ in range(n_nodes)]
    ids = [f"bench-dis-{tag}{i}" for i in range(n_nodes)]
    nodes = []
    for i in range(n_nodes):
      engine = JaxShardedInferenceEngine(use_local_mesh=False)
      engine.load_test_model(shard, cfg, params, tokenizer=_Tok())
      peers = [GRPCPeerHandle(ids[j], f"127.0.0.1:{ports[j]}", "bench", caps) for j in range(n_nodes) if j != i]
      node = Node(ids[i], None, engine, _Static(peers), None, RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=512, default_sample_temp=0.0)
      node.server = GRPCServer(node, "127.0.0.1", ports[i])
      node.disagg_role = ("prefill" if i == 0 else "decode") if disagg else "both"
      nodes.append(node)
    await asyncio.gather(*(n.start() for n in nodes))
    try:
      for _ in range(100):
        if all(len(n.topology.nodes) == n_nodes for n in nodes):
          break
        await asyncio.gather(*(n.collect_topology(set()) for n in nodes))
        await asyncio.sleep(0.05)

      arrivals: dict[str, list[float]] = {}
      done: dict[str, asyncio.Event] = {}

      def on_tok(rid, toks, fin):
        if toks:
          arrivals.setdefault(rid, []).extend([time.perf_counter()] * len(toks))
        if fin and rid in done:
          done[rid].set()

      nodes[0].on_token.register(f"bench-dis-{tag}").on_next(on_tok)

      def start_req(rid: str, max_tokens: int):
        nodes[0].set_request_options(rid, max_tokens=max_tokens, temperature=0.0)
        done[rid] = asyncio.Event()
        return asyncio.ensure_future(nodes[0]._batched_serve(shard, shard, "p", rid))

      resident = f"res-{tag}"
      t_res = start_req(resident, n_resident_tokens)
      while not arrivals.get(resident):
        await asyncio.sleep(0.005)
      t_burst_start = time.perf_counter()
      burst_ids = [f"burst-{tag}{k}" for k in range(n_burst)]
      submits = {}
      tasks = []
      for rid in burst_ids:
        submits[rid] = time.perf_counter()
        tasks.append(start_req(rid, n_burst_tokens))
      await asyncio.wait_for(asyncio.gather(*(done[r].wait() for r in burst_ids)), timeout=300)
      t_burst_end = time.perf_counter()
      await asyncio.wait_for(done[resident].wait(), timeout=300)
      await asyncio.wait_for(asyncio.gather(t_res, *tasks), timeout=300)

      # Resident ITL over the burst window only — the contended span.
      # Tokens arrive in delivery chunks (several share one timestamp), so
      # the honest per-token figure is each inter-chunk gap amortized over
      # the tokens that gap produced — p50 over those, weighted by tokens.
      ts = [t for t in arrivals.get(resident, []) if t_burst_start <= t <= t_burst_end]
      uniq, counts = (np.unique(np.asarray(ts), return_counts=True)) if ts else (np.asarray([]), np.asarray([]))
      per_tok = []
      for j in range(1, uniq.size):
        per_tok.extend([(uniq[j] - uniq[j - 1]) / counts[j] * 1e3] * int(counts[j]))
      itl_p50 = float(np.percentile(np.asarray(per_tok), 50)) if per_tok else None
      ttfts = [
        (arrivals[r][0] - submits[r]) * 1e3 for r in burst_ids if arrivals.get(r)
      ]
      ttft_p50 = float(np.percentile(np.asarray(ttfts), 50)) if ttfts else None
      gbps = None
      if disagg:
        bytes_total = 0
        ms_total = 0.0
        for rid in [resident, *burst_ids]:
          tl = tracer.timeline_export(rid) or {}
          for e in tl.get("events", []):
            if e.get("stage") == "kv_stream":
              bytes_total += int(e["attributes"].get("bytes", 0))
              ms_total += float(e["attributes"].get("ms", 0.0))
        if bytes_total and ms_total:
          gbps = bytes_total / (ms_total / 1e3) / 1e9
      if os.getenv('XOT_BENCH_DEBUG'):
        print('phase', tag, 'res_arrivals', len(arrivals.get(resident, [])), 'in_window', len(ts), 'itl', itl_p50, 'ttft', ttft_p50, 'burst_span', round(t_burst_end - t_burst_start, 3))
      return itl_p50, ttft_p50, gbps
    finally:
      for n in nodes:
        await n.stop()

  try:
    colo_itl, _colo_ttft, _ = asyncio.run(phase("c", False))
    dis_itl, dis_ttft, gbps = asyncio.run(phase("d", True))
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v
  ratio = round(dis_itl / colo_itl, 4) if (dis_itl and colo_itl) else None
  return (
    gate_disagg(round(dis_ttft, 2) if dis_ttft is not None else None, lo=0.01, hi=600000.0),
    gate_disagg(ratio, lo=0.001, hi=1000.0),
    gate_disagg(round(gbps, 4) if gbps is not None else None, lo=1e-6, hi=10000.0),
  )


def bench_mixed(n_burst: int = 4, n_resident_tokens: int = 120, n_burst_tokens: int = 8, prompt_tokens: int = 768) -> tuple:
  """Mixed prefill+decode tick round (ISSUE 14), measured on EVERY round —
  the PR 10 colocated-burst fixture minus the second node: a RESIDENT
  decode stream runs while a chunked-prefill BURST arrives, driven straight
  through the batched scheduler (the contention is a scheduler property; no
  ring needed). Phase A (alternating, ``XOT_TPU_MIXED_TICK=0``): every
  resident token waits behind whole K-batched prefill-chunk dispatches —
  the head-of-line stall PR 10 cured with a second node. Phase B (mixed):
  prefill advances by SLO-budgeted slices fused into the decode dispatches.
  The fixture sits in the COMPUTE-DOMINATED chunk regime (256-token chunks,
  3-chunk prompts) that production 2048-token chunks occupy — at toy chunk
  widths the padded prefill dispatch costs about one decode chunk and there
  is no stall to remove. Each phase runs once for compile warm-up, once
  measured.

  Returns (mixed_resident_itl_ms, alternating_resident_itl_ms,
  mixed_vs_alternating_itl, mixed_ttft_ms_p50, alternating_ttft_ms_p50,
  mixed_resident_itl_ms_p50, alternating_resident_itl_ms_p50): the
  headline ITL fields — and the gated ratio (≤0.5 is the ISSUE 14
  acceptance bar) — are the MEAN resident ITL over the burst's prefill
  span (span / tokens delivered). The mean is the stall-sensitive
  statistic here: an alternating-schedule stall STARVES the resident (it
  delivers fewer tokens, in clusters), and the per-chunk amortized p50
  mistakes that for speed — the tokens that never arrived during the
  stall simply don't appear in its distribution. The amortized p50s (the
  bench_disagg math) are still emitted for continuity. Burst TTFT p50s
  ride along (the budget policy may trade a bounded amount of TTFT for
  the ITL win; under a serialized backlog the EARLY prompts' first tokens
  arrive far sooner than the alternating all-at-once completion, so the
  p50 often improves too)."""
  import asyncio

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=1024)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  overrides = {
    "XOT_TPU_PAGE_SIZE": "16", "XOT_TPU_PREFILL_CHUNK": "256",
    "XOT_TPU_BATCH_CHUNK": "4", "XOT_TPU_BATCH_SLOTS": "6", "XOT_TPU_KV_QUANT": "int8",
  }
  saved = {k: os.environ.get(k) for k in (*overrides, "XOT_TPU_MIXED_TICK")}
  os.environ.update(overrides)

  def phase(tag: str, mixed: bool, measure: bool) -> tuple[float | None, float | None, float | None]:
    os.environ["XOT_TPU_MIXED_TICK"] = "1" if mixed else "0"
    engine = JaxShardedInferenceEngine(use_local_mesh=False)
    engine.load_test_model(shard, cfg, params)
    server = BatchedServer(engine, n_slots=6, chunk=4)
    arrivals: dict[str, list[float]] = {}

    def emit(rid, toks, fin):
      if toks:
        arrivals.setdefault(rid, []).extend([time.perf_counter()] * len(toks))

    async def run():
      resident = f"res-{tag}"
      t_res = asyncio.ensure_future(server.submit(
        resident, np.asarray([3, 25, 9], np.int32), max_tokens=n_resident_tokens,
        temp=0.0, top_k=35, eos_ids=(), emit=emit,
      ))
      while not arrivals.get(resident):
        await asyncio.sleep(0.002)
      t0 = time.perf_counter()
      submits: dict[str, float] = {}

      async def burst(k: int):
        rid = f"burst-{tag}{k}"
        # Distinct heads keep the burst prompts out of each other's prefix
        # cache — every burst pays its full chunked prefill.
        prompt = [k + 2, *(((i * 7) % 200) + 40 for i in range(prompt_tokens - 1))]
        submits[rid] = time.perf_counter()
        return await server.submit(rid, np.asarray(prompt, np.int32), max_tokens=n_burst_tokens, temp=0.0, top_k=35, eos_ids=(), emit=emit)
      await asyncio.gather(*(burst(k) for k in range(n_burst)))
      t1 = time.perf_counter()
      await t_res
      return t0, t1, submits

    try:
      t0, t1, submits = asyncio.run(asyncio.wait_for(run(), timeout=600))
    finally:
      server.shutdown()
    if not measure:
      return None, None, None
    # Resident ITL over the burst's PREFILL span (submit → last burst first
    # token): that is the contended window the two schedules differ in —
    # after every burst prompt has prefilled, both arms run identical pure
    # decode ticks, which would only dilute the A/B. (bench_disagg windows
    # to burst COMPLETION instead because disagg moves both phases off the
    # node.) Tokens arrive in delivery chunks, so each inter-chunk gap is
    # amortized over the tokens it produced, weighted by tokens.
    firsts = [arrivals[r][0] for r in submits if arrivals.get(r)]
    t_pf_end = max(firsts) if firsts else t1
    ts = [t for t in arrivals.get(f"res-{tag}", []) if t0 <= t <= t_pf_end]
    # The stall-sensitive aggregate: mean resident ITL over the span. A
    # starved resident delivers FEWER tokens — the mean charges the stall;
    # the amortized per-chunk p50 (below, the bench_disagg math) cannot.
    itl_mean = (t_pf_end - t0) / len(ts) * 1e3 if len(ts) >= 2 else None
    uniq, counts = (np.unique(np.asarray(ts), return_counts=True)) if ts else (np.asarray([]), np.asarray([]))
    per_tok = []
    for j in range(1, uniq.size):
      per_tok.extend([(uniq[j] - uniq[j - 1]) / counts[j] * 1e3] * int(counts[j]))
    itl_p50 = float(np.percentile(np.asarray(per_tok), 50)) if per_tok else None
    ttfts = [(arrivals[r][0] - t_sub) * 1e3 for r, t_sub in submits.items() if arrivals.get(r)]
    ttft_p50 = float(np.percentile(np.asarray(ttfts), 50)) if ttfts else None
    return itl_mean, itl_p50, ttft_p50

  try:
    phase("aw", False, measure=False)  # compile warm-up (plain programs)
    alt_itl, alt_p50, alt_ttft = phase("a", False, measure=True)
    phase("mw", True, measure=False)  # warm the mixed program's pad buckets
    mix_itl, mix_p50, mix_ttft = phase("m", True, measure=True)
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v
  ratio = round(mix_itl / alt_itl, 4) if (mix_itl and alt_itl) else None
  return (
    gate_mixed(round(mix_itl, 3) if mix_itl is not None else None, lo=0.001, hi=600000.0),
    gate_mixed(round(alt_itl, 3) if alt_itl is not None else None, lo=0.001, hi=600000.0),
    gate_mixed(ratio, lo=0.001, hi=1000.0),
    gate_mixed(round(mix_ttft, 2) if mix_ttft is not None else None, lo=0.01, hi=600000.0),
    gate_mixed(round(alt_ttft, 2) if alt_ttft is not None else None, lo=0.01, hi=600000.0),
    gate_mixed(round(mix_p50, 3) if mix_p50 is not None else None, lo=0.001, hi=600000.0),
    gate_mixed(round(alt_p50, 3) if alt_p50 is not None else None, lo=0.001, hi=600000.0),
  )


def bench_lora(n_rows: int = 8, n_gen: int = 33) -> tuple:
  """Batched multi-LoRA round (ISSUE 15), measured on EVERY round — the
  adapter hook is a per-row gather inside the same fused programs, so the
  CPU smoke measures a real A/B (tiny model) instead of emitting null.

  A tiny checkpoint + 2 synthetic adapters serve a MIXED B=8 batch through
  the REAL scheduler (rows alternate adapter-1 / adapter-2 / base — the
  Punica serving shape: one resident base model, every row its own
  variant) vs the SAME engine serving all-base with the hook compiled in
  never enabled (fresh engine, no registry). Also measures the adapter
  swap path: cycling more adapters than device slots forces evict+install
  rounds whose latency lands in ``lora_swap_seconds``.

  Returns (lora_mixed_batch8_vs_base8, lora_swap_ms_p50,
  lora_mixed_batch8_aggregate_tok_s, lora_base_batch8_aggregate_tok_s)."""
  import asyncio

  from xotorch_support_jetson_tpu.inference.adapters import extract_adapter
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.train.lora import add_lora
  from xotorch_support_jetson_tpu.utils.metrics import metrics as _gm

  cfg = tiny_test_config(n_layers=2, max_seq_len=512)
  params, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  rank = 4

  def synth_adapter(seed: int) -> dict:
    wl = add_lora(params, rank, jax.random.PRNGKey(seed))
    layers = dict(wl["layers"])
    for t in ("wq", "wv"):  # nonzero B so the variant actually differs from base
      b = layers[f"{t}_lora_b"]
      layers[f"{t}_lora_b"] = (jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), 99), b.shape, jnp.float32) * 0.05).astype(b.dtype)
    return extract_adapter({**wl, "layers": layers})

  saved = {k: os.environ.get(k) for k in ("XOT_TPU_PAGED", "XOT_TPU_KV_QUANT")}
  os.environ["XOT_TPU_PAGED"] = "1"
  os.environ["XOT_TPU_KV_QUANT"] = "int8"
  try:
    rng = np.random.default_rng(23)
    prompts = {f"lr{i}": rng.integers(1, cfg.vocab_size, (24,)).astype(np.int32) for i in range(n_rows)}

    def measure(engine, adapters_by_row) -> float:
      srv = BatchedServer(engine, n_slots=n_rows, chunk=8)

      async def rnd():
        total = 0

        def emit(rid, toks, finished):
          nonlocal total
          total += len(toks)

        async def one(tag):
          await asyncio.gather(*(
            srv.submit(f"{tag}{rid}", p, max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=emit,
                       adapter=adapters_by_row[i])
            for i, (rid, p) in enumerate(prompts.items())
          ))

        await one("w")  # compile warm-up (admission + chunk programs)
        total = 0
        t0 = time.perf_counter()
        await one("m")
        return total / (time.perf_counter() - t0)

      tok_s = asyncio.run(rnd())
      srv.shutdown()
      return round(tok_s, 2)

    # Base arm: NO registry — the dispatch signature (and compiled program)
    # is exactly pre-multi-LoRA serving.
    base_eng = JaxShardedInferenceEngine(use_local_mesh=False)
    base_eng.load_test_model(shard, cfg, params)
    base_tok_s = measure(base_eng, [None] * n_rows)
    base_eng = None

    # Mixed arm: registry + 2 adapters, rows alternating a1/a2/base.
    mix_eng = JaxShardedInferenceEngine(use_local_mesh=False)
    mix_eng.load_test_model(shard, cfg, params)
    reg = mix_eng.enable_multi_lora(capacity=4, rank=rank)
    if reg is None:
      return None, None, None, base_tok_s
    reg.register("bl-a1", synth_adapter(1))
    reg.register("bl-a2", synth_adapter(2))
    mixed_names = [("bl-a1", "bl-a2", None)[i % 3] for i in range(n_rows)]
    mixed_tok_s = measure(mix_eng, mixed_names)

    # Swap latency: more adapters than free slots → every acquire past
    # capacity is an LRU evict + install (the lora_swap_seconds histogram).
    for i in range(3, 9):
      reg.register(f"bl-x{i}", synth_adapter(i))
    for cycle in range(2):
      for i in range(3, 9):
        reg.acquire(f"bl-x{i}")
    swap_p50 = _gm.quantile("lora_swap_seconds", 0.5)
    swap_ms_p50 = round(swap_p50 * 1e3, 3) if swap_p50 is not None else None
    mix_eng = None

    ratio = round(mixed_tok_s / base_tok_s, 4) if (mixed_tok_s and base_tok_s) else None
    return (
      gate_lora(ratio, lo=0.001, hi=100.0),
      gate_lora(swap_ms_p50, lo=0.0001, hi=600000.0),
      gate_lora(mixed_tok_s, lo=0.001, hi=10_000_000.0),
      gate_lora(base_tok_s, lo=0.001, hi=10_000_000.0),
    )
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v


def bench_router_round(n_sessions: int = 5, sys_tokens: int = 256, n_gen: int = 6) -> tuple:
  """Cluster front door round (ISSUE 13) on a two-replica localhost fixture
  with a tiny-but-real jax checkpoint — CPU-measurable (the
  ``gate_spec_ngram`` pattern: the router is host-side HTTP + policy, so
  every round records a real A/B instead of null).

  Workload: ``n_sessions`` two-turn chats, each with its own
  ``sys_tokens``-token system prompt (the repeated-system-prompt shape).
  AFFINE arm: both turns via the router (``XOT_TPU_ROUTER=1``) — turn 2
  sticks to the replica whose KV holds turn 1. RANDOM arm: the motivating
  baseline, a client round-robining the replicas by hand — turn 2 lands on
  the OTHER replica and re-prefills. FAILOVER drill: a streamed request's
  serving replica is killed at the wire (transport abort) mid-stream; the
  measured window is kill → next client-visible token through the router's
  transparent re-submit.

  Returns (router_affine_vs_random_ttft_p50, router_prefix_hit_rate,
  router_failover_ms_p50, affine_ttft_ms_p50, random_ttft_ms_p50)."""
  import asyncio

  import aiohttp
  from aiohttp import web as aioweb

  from xotorch_support_jetson_tpu import registry as _registry
  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.networking.discovery import Discovery
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from xotorch_support_jetson_tpu.utils.helpers import find_available_port
  from xotorch_support_jetson_tpu.utils.metrics import metrics as _gm

  class _NoDisc(Discovery):
    async def start(self):
      pass

    async def stop(self):
      pass

    async def discover_peers(self, wait_for_peers: int = 0):
      return []

  class _Srv:
    async def start(self):
      pass

    async def stop(self):
      pass

  class _Tok:
    eos_token_id = None

    def encode(self, text):
      return [int(w) for w in str(text).split()]

    def decode(self, toks):
      return " ".join(str(int(t)) for t in toks)

    def apply_chat_template(self, conversation=None, tokenize=False, add_generation_prompt=True, **kw):
      return " ".join(m["content"] for m in conversation)

  model_id = "bench-router-tiny"
  cfg = tiny_test_config(n_layers=2, max_seq_len=512)
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, model_id)
  overrides = {
    "XOT_TPU_BATCHED": "1", "XOT_TPU_PAGE_SIZE": "4", "XOT_TPU_BATCH_CHUNK": "2",
    "XOT_TPU_ROUTER_STATS_TTL_S": "60", "XOT_TPU_ROUTER_AFFINITY": "1",
    "XOT_TPU_ROUTER_RETRIES": "2",
  }
  saved = {k: os.environ.get(k) for k in list(overrides) + ["XOT_TPU_ROUTER", "XOT_TPU_ROUTER_REPLICAS"]}
  os.environ.update(overrides)
  os.environ.pop("XOT_TPU_ROUTER", None)  # replicas must construct router-off
  had_card = model_id in _registry.model_cards
  _registry.model_cards[model_id] = _registry.ModelCard(model_id, cfg.n_layers, "Bench Router Tiny", "llama", {"JaxShardedInferenceEngine": "local-bench"})

  def messages(*contents):
    roles = ["system"] + ["user", "assistant"] * len(contents)
    return [{"role": r, "content": c} for r, c in zip(roles, contents)]

  def sys_prompt(tag: int) -> str:
    return " ".join(str(2 + ((tag * 37 + i) % 200)) for i in range(sys_tokens))

  async def round_():
    tok = _Tok()
    ids = ["bench-rt0", "bench-rt1"]
    nodes, runners, sites, ports, urls = [], [], [], [], []
    for i in range(2):
      engine = JaxShardedInferenceEngine(use_local_mesh=False)
      engine.load_test_model(shard, cfg, params, tokenizer=_Tok())
      node = Node(ids[i], _Srv(), engine, _NoDisc(), None, RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=200, default_sample_temp=0.0)
      await node.start()
      api = ChatGPTAPI(node, "JaxShardedInferenceEngine", response_timeout=60, default_model=model_id)
      runner = aioweb.AppRunner(api.app)
      await runner.setup()
      port = find_available_port("127.0.0.1")
      site = aioweb.TCPSite(runner, "127.0.0.1", port)
      await site.start()
      nodes.append(node)
      runners.append(runner)
      sites.append(site)
      ports.append(port)
      urls.append(f"http://127.0.0.1:{port}")
    os.environ["XOT_TPU_ROUTER"] = "1"
    os.environ["XOT_TPU_ROUTER_REPLICAS"] = ",".join(f"{i}={u}" for i, u in zip(ids, urls))
    rnode = Node("bench-router", _Srv(), DummyInferenceEngine(), _NoDisc(), None, RingMemoryWeightedPartitioningStrategy())
    await rnode.start()
    rapi = ChatGPTAPI(rnode, "JaxShardedInferenceEngine", response_timeout=60, default_model=model_id)

    async def _tokenizer(shard_):
      return tok

    rapi._tokenizer_for = _tokenizer
    rrunner = aioweb.AppRunner(rapi.app)
    await rrunner.setup()
    rport = find_available_port("127.0.0.1")
    await aioweb.TCPSite(rrunner, "127.0.0.1", rport).start()
    router_url = f"http://127.0.0.1:{rport}"

    async def stream_ttft(sess, url, body):
      """POST a streaming chat and return (ttft_ms, full_text)."""
      t0 = time.perf_counter()
      ttft = None
      acc = ""
      async with sess.post(url + "/v1/chat/completions", json={**body, "stream": True}, timeout=aiohttp.ClientTimeout(total=60)) as resp:
        assert resp.status == 200, await resp.text()
        async for line in resp.content:
          line = line.decode().strip()
          if not line.startswith("data: ") or line == "data: [DONE]":
            continue
          obj = json.loads(line[6:])
          delta = (obj.get("choices") or [{}])[0].get("delta", {}).get("content")
          if delta:
            if ttft is None:
              ttft = (time.perf_counter() - t0) * 1e3
            acc += delta
      return ttft, acc

    try:
      async with aiohttp.ClientSession() as sess:
        # Warm BOTH replicas through BOTH turn shapes (and the cached-prefix
        # prefill variant) so neither arm pays first-compile skew — the
        # affine arm runs first and would otherwise absorb every XLA
        # compile while the random arm reused them.
        for wi, u in enumerate(urls):
          w1 = {"model": model_id, "messages": messages(sys_prompt(90 + wi), "5 3"), "max_tokens": n_gen}
          _, wreply = await stream_ttft(sess, u, w1)
          w2 = {"model": model_id, "messages": messages(sys_prompt(90 + wi), "5 3", wreply, "7 7"), "max_tokens": n_gen}
          await stream_ttft(sess, u, w2)

        # AFFINE arm: two turns per session through the router.
        req0 = _gm.counter_sum("router_requests_total")
        hit0 = _gm.counter_sum("router_prefix_hits_total")
        affine: list[float] = []
        for s in range(n_sessions):
          b1 = {"model": model_id, "messages": messages(sys_prompt(s), "5 3"), "max_tokens": n_gen}
          _, reply = await stream_ttft(sess, router_url, b1)
          b2 = {"model": model_id, "messages": messages(sys_prompt(s), "5 3", reply, "7 7"), "max_tokens": n_gen}
          ttft, _ = await stream_ttft(sess, router_url, b2)
          if ttft is not None:
            affine.append(ttft)
        routed = _gm.counter_sum("router_requests_total") - req0
        hits = _gm.counter_sum("router_prefix_hits_total") - hit0
        hit_rate = round(hits / routed, 4) if routed else None

        # RANDOM arm: same router hop, affinity OFF — the load fallback's
        # round-robin sends turn 2 to the OTHER replica, which re-prefills
        # the session (fresh system prompts so nothing is pre-cached). The
        # A/B isolates the PLACEMENT policy, not the HTTP hop.
        os.environ["XOT_TPU_ROUTER_AFFINITY"] = "0"
        random_: list[float] = []
        for s in range(n_sessions):
          b1 = {"model": model_id, "messages": messages(sys_prompt(100 + s), "5 3"), "max_tokens": n_gen}
          _, reply = await stream_ttft(sess, router_url, b1)
          b2 = {"model": model_id, "messages": messages(sys_prompt(100 + s), "5 3", reply, "7 7"), "max_tokens": n_gen}
          ttft, _ = await stream_ttft(sess, router_url, b2)
          if ttft is not None:
            random_.append(ttft)
        os.environ["XOT_TPU_ROUTER_AFFINITY"] = "1"

        # FAILOVER drill: kill the serving replica mid-stream, measure the
        # client-visible splice window through the router.
        windows: list[float] = []
        for d in range(3):
          t_kill: list[float] = []
          per_target0 = {i: _gm.counter_value("router_requests_total", labels={"target": i}) for i in ids}

          async def kill_serving():
            await asyncio.sleep(0)  # let the dispatch counter settle
            per = {i: _gm.counter_value("router_requests_total", labels={"target": i}) for i in ids}
            victim = max(ids, key=lambda i: per[i] - per_target0[i])
            v = ids.index(victim)
            web_server = runners[v].server
            for proto in list(getattr(web_server, "connections", []) or []):
              tr = getattr(proto, "transport", None)
              if tr is not None:
                tr.abort()
            await sites[v].stop()
            t_kill.append(time.perf_counter())
            # Re-arm the replica for the next drill.
            sites[v] = aioweb.TCPSite(runners[v], "127.0.0.1", ports[v])
            await sites[v].start()
            view = rapi._router.policy.replicas.get(victim)
            if view is not None:
              view.t_unreachable = 0.0

          t_rec: list[float] = []
          body = {"model": model_id, "messages": messages(sys_prompt(200 + d), "9 9"), "max_tokens": 32}
          t0 = time.perf_counter()
          seen_first = False
          async with sess.post(router_url + "/v1/chat/completions", json={**body, "stream": True}, timeout=aiohttp.ClientTimeout(total=60)) as resp:
            async for line in resp.content:
              line = line.decode().strip()
              if not line.startswith("data: ") or line == "data: [DONE]":
                continue
              obj = json.loads(line[6:])
              delta = (obj.get("choices") or [{}])[0].get("delta", {}).get("content")
              if not delta:
                continue
              if not seen_first:
                seen_first = True
                await kill_serving()
              elif t_kill and not t_rec:
                t_rec.append(time.perf_counter())
          if t_kill and t_rec:
            windows.append((t_rec[0] - t_kill[0]) * 1e3)

      aff_p50 = float(np.percentile(np.asarray(affine), 50)) if affine else None
      rnd_p50 = float(np.percentile(np.asarray(random_), 50)) if random_ else None
      fo_p50 = float(np.percentile(np.asarray(windows), 50)) if windows else None
      return aff_p50, rnd_p50, hit_rate, fo_p50
    finally:
      if rapi._router is not None:
        await rapi._router.close()
      await rrunner.cleanup()
      for r in runners:
        try:
          await asyncio.wait_for(r.cleanup(), timeout=5)
        except asyncio.TimeoutError:
          pass
      for n in nodes:
        srv = getattr(n.inference_engine, "_batched_server", None)
        if srv is not None:
          srv.shutdown()
        await n.stop()
      await rnode.stop()

  try:
    aff_p50, rnd_p50, hit_rate, fo_p50 = asyncio.run(round_())
  finally:
    for k, v in saved.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v
    if not had_card:
      _registry.model_cards.pop(model_id, None)
  ratio = round(aff_p50 / rnd_p50, 4) if (aff_p50 and rnd_p50) else None
  return (
    gate_router(ratio, lo=0.001, hi=100.0),
    # lo=0.0: a measured 0.0 hit rate is an honest (bad) result that must
    # stay in the drift record — unlike the ratio, where 0 = broken input.
    gate_router(hit_rate, lo=0.0, hi=1.0),
    gate_router(round(fo_p50, 1) if fo_p50 is not None else None, lo=1.0, hi=120000.0),
    round(aff_p50, 2) if aff_p50 is not None else None,
    round(rnd_p50, 2) if rnd_p50 is not None else None,
  )


def plausible_value(rec: dict) -> float | None:
  """Extract the trustworthy headline tok/s from a recorded BENCH_r*.json line.

  A recorded ``value`` more than 2x its own ``serving_chunked_tok_s`` is a
  timing artifact (the poisoned round-2 record); fall
  back to that record's serving-path number so ``vs_baseline`` chains stay
  sane across rounds.
  """
  v = rec.get("value")
  s = rec.get("serving_chunked_tok_s")
  if not v:
    return None
  return gate_headline(float(v), float(s) if s else None)[0]


def main() -> None:
  from xotorch_support_jetson_tpu.models.config import ModelConfig
  from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_decode, init_kv_cache, shard_forward
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  device = device_summary()
  on_accel = device["platform"] != "cpu"
  if not on_accel:
    # Slated for replacement (ROADMAP.md A1/D7). Until then the absent-chip
    # branch at least says what it is: every field it prints comes from a
    # 4-layer dim-256 model on the CPU and is NOT a device metric.
    print(f"bench.py: no accelerator ({device}) — running the 4-layer CPU smoke; no field below is a device measurement", file=sys.stderr, flush=True)

  cfg = ModelConfig(
    vocab_size=128256,
    dim=2048,
    n_layers=16,
    n_heads=32,
    n_kv_heads=8,
    hidden_dim=8192,
    head_dim=64,
    rope_theta=500000.0,
    max_seq_len=2048,
    tied_embedding=True,
    dtype=jnp.bfloat16,
  )
  if not on_accel:  # keep the CPU smoke run quick
    cfg = ModelConfig(
      vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=4, hidden_dim=1024,
      rope_theta=10000.0, max_seq_len=512, tied_embedding=True, dtype=jnp.float32,
    )

  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "llama-3.2-1b")
  B, prompt_len, max_seq = 1, 128, 1024 if on_accel else 256
  n_decode = 128 if on_accel else 8

  tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (B, prompt_len)), dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(prompt_len, dtype=jnp.int32), (B, prompt_len))

  def prefill(params, tokens, cache):
    logits, cache = shard_forward(params, cfg, shard, tokens, positions, cache)
    return logits[:, -1, :], cache

  prefill_jit = jax.jit(prefill, donate_argnums=(2,))

  # Warmup / compile. All timed sections below fetch results to the host with
  # np.asarray, so the timer cannot stop before the device does (the round-2
  # headline was invalidated by a timing that did).
  cache = init_kv_cache(cfg, shard.n_shard_layers, B, max_seq)
  last, cache = prefill_jit(params, tokens, cache)
  _ = np.asarray(jnp.argmax(last, axis=-1))

  # TTFT: prefill + on-device sample + first token on the host (what a client
  # actually waits for), compiled. Median of 5 runs with the spread recorded:
  # a single-shot sample made r03 look like a +31% regression.
  ttft_samples = []
  for _ in range(5):
    cache = init_kv_cache(cfg, shard.n_shard_layers, B, max_seq)
    t0 = time.perf_counter()
    last, cache = prefill_jit(params, tokens, cache)
    _ = np.asarray(jnp.argmax(last, axis=-1))
    ttft_samples.append((time.perf_counter() - t0) * 1e3)
  ttft_ms = float(np.median(ttft_samples))
  ttft_spread_ms = float(max(ttft_samples) - min(ttft_samples))

  first_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  start_pos = jnp.full((B,), prompt_len, dtype=jnp.int32)

  # Warmup decode compile.
  toks, cache = fused_decode(params, cfg, shard, first_tok, cache, start_pos, n_decode)
  _ = np.asarray(toks)

  # Timed decode (fresh cache regions; positions continue). Full host fetch.
  # MEDIAN of 3 in-run repeats with the spread recorded (VERDICT r4 #6): the
  # single-section headline varied run to run (NOTES.md records a
  # 212.9-218.7 same-commit spread); TTFT already medians ×5.
  headline_samples = []
  start_pos2 = start_pos + n_decode
  for _ in range(3):
    t0 = time.perf_counter()
    toks, cache = fused_decode(params, cfg, shard, first_tok, cache, start_pos2, n_decode)
    _ = np.asarray(toks)
    headline_samples.append(n_decode * B / (time.perf_counter() - t0))
    start_pos2 = start_pos2 + n_decode
  tok_per_s = float(np.median(headline_samples))
  headline_spread = round(float(max(headline_samples) - min(headline_samples)), 2)

  # Program-ledger round (ISSUE 19): the warmup sections above compiled the
  # tracked decode programs — the ledger holds their compile seconds. Mark
  # steady, run a few more dispatches at already-compiled shapes (positions
  # are TRACED, so a stale start_pos is the point: mix changes must not
  # compile), and pin steady-state serving at zero recompiles. Steady is
  # then unmarked: later rounds compile NEW programs legitimately.
  from xotorch_support_jetson_tpu.utils.programs import ledger as program_ledger

  warmup_compile_s_total = round(
    sum(st["compile_s"] for st in program_ledger.snapshot()["families"].values()), 6
  )
  steady_compiles_before = program_ledger.steady_compile_count()
  program_ledger.mark_steady()
  try:
    for _ in range(3):
      toks, cache = fused_decode(params, cfg, shard, first_tok, cache, start_pos, n_decode)
      _ = np.asarray(toks)
    steady_state_compiles = program_ledger.steady_compile_count() - steady_compiles_before
  finally:
    program_ledger.unmark_steady()

  # Serving cadence: the Node's non-streaming fast path — fused_generate
  # (while_loop w/ on-device EOS) generates the whole response in ONE
  # dispatch + ONE host readback, where the chunked path reads back once
  # per chunk; this measures the amortized-to-one path end-to-end.
  from xotorch_support_jetson_tpu.models.decoder import fused_generate

  pos = int(np.asarray(start_pos2)[0]) + n_decode
  buf, n_run, cache = fused_generate(params, cfg, shard, first_tok, cache, jnp.full((B,), pos, jnp.int32), n_decode, eos_ids=(-1,))
  _ = np.asarray(buf)  # warm compile + readback path
  pos += n_decode  # eos id -1 never fires, so all n_decode steps ran
  t0 = time.perf_counter()
  buf, n_run, cache = fused_generate(params, cfg, shard, first_tok, cache, jnp.full((B,), pos, jnp.int32), n_decode, eos_ids=(-1,))
  _ = np.asarray(buf)  # single readback; count inferred host-side in the engine
  serving_tok_s = n_decode * B / (time.perf_counter() - t0)

  # int8 weight-quantized decode (XOT_TPU_QUANT=int8 engine mode): halves the
  # HBM bytes per step — the decode roofline is weight bandwidth, so this is
  # the fast serving mode (~1.5× measured on v5e).
  def _bench_quant_decode(mode: str):
    """Solo quantized decode for one XOT_TPU_QUANT mode (shared timing
    methodology: warm compile, full np.asarray host fetch, MEDIAN of 3, same
    as the headline).
    Returns (tok/s, quantized tree)."""
    qp = quantize_params(params, mode)
    qcache = init_kv_cache(cfg, shard.n_shard_layers, B, max_seq)
    qtoks, qcache = fused_decode(qp, cfg, shard, first_tok, qcache, jnp.zeros((B,), jnp.int32), n_decode)
    _ = np.asarray(qtoks)
    qpos = n_decode
    samples = []
    for _ in range(3):
      t0 = time.perf_counter()
      qtoks, qcache = fused_decode(qp, cfg, shard, first_tok, qcache, jnp.full((B,), qpos, jnp.int32), n_decode)
      _ = np.asarray(qtoks)
      samples.append(n_decode * B / (time.perf_counter() - t0))
      qpos += n_decode
    return round(float(np.median(samples)), 2), qp

  int8_tok_s = None
  int4_tok_s = None
  if on_accel:
    int8_tok_s, qp = _bench_quant_decode("int8")
    # int4 (packed w4a16, round 4): the HBM-CAPACITY mode. The two-dot qdot
    # keeps the unpack streamable but reads the packed buffer twice, so the
    # expected number is ~half of int8 (BASELINE.md) — recorded for drift,
    # not as a recommendation.
    try:
      int4_tok_s, qp4 = _bench_quant_decode("int4")
      del qp4
    except Exception:  # noqa: BLE001 — optional section
      int4_tok_s = None

  # Continuous-batching aggregate (XOT_TPU_BATCHED=1 serving mode,
  # inference/batch_scheduler.py): decode is weight-bandwidth-bound, so an
  # 8-row slot pool multiplies aggregate tokens/s ~4.5× on v5e-1.
  def _bench_batch(p, Bb: int, kv_quant: str = "", bcfg=None) -> float:
    """Bb-row batched chunk aggregate for any params pytree (bf16 / int8),
    KV-cache mode ('' bf16 / 'int8' — XOT_TPU_KV_QUANT), and optional cfg
    override (e.g. a quant_compute variant — cfg is a static jit arg, so a
    distinct cfg keys a distinct compiled program)."""
    from xotorch_support_jetson_tpu.models.decoder import fused_batch_decode

    bcfg = bcfg or cfg
    bcache = init_kv_cache(bcfg, shard.n_shard_layers, Bb, 1024, quant=kv_quant)
    btok = jnp.ones((Bb, 1), jnp.int32)
    bpos = jnp.full((Bb,), prompt_len, jnp.int32)
    bact = jnp.ones((Bb,), bool)
    btemps = jnp.zeros((Bb,), jnp.float32)
    btoks, _, bpos, bcache = fused_batch_decode(p, bcfg, shard, btok, bcache, bpos, bact, btemps, n_decode)
    _ = np.asarray(btoks)  # warm compile + honest fetch
    t0 = time.perf_counter()
    btoks, _, bpos, bcache = fused_batch_decode(p, bcfg, shard, btok, bcache, bpos, bact, btemps, n_decode)
    _ = np.asarray(btoks)
    return round(Bb * n_decode / (time.perf_counter() - t0), 2)

  batch8_tok_s = _bench_batch(params, 8) if on_accel else None
  # int8 x continuous batching: halved weight bytes per step AND the rows
  # amortizing each read (XOT_TPU_QUANT=int8 + XOT_TPU_BATCHED=1 together).
  int8_batch8_tok_s = _bench_batch(qp, 8) if on_accel else None
  # 16 rows is the measured single-chip sweet spot at int8 (round-4 probe:
  # B=8 1148, B=16 1466, B=32 1328 — beyond 16 the per-row attention reads
  # start to dominate the amortized weight stream).
  int8_batch16_tok_s = _bench_batch(qp, 16) if on_accel else None
  # int8 weights + int8 KV cache (round 5): the KV read is the other
  # bandwidth stream at batch — quantizing it too lifts the aggregate AND
  # moves the batch sweet spot: halved per-row attention reads push the
  # knee from B=16 to B=48 (median-of-3 sweep: 16→1560, 32→1841, 48→1967,
  # 64→1771, 128→1627). DENSE SLOTS ONLY — the paged pool's gather
  # indirection keeps its knee at 16. The BEST single-chip aggregate
  # config: XOT_TPU_QUANT=int8 XOT_TPU_KV_QUANT=int8 XOT_TPU_BATCHED=1
  # XOT_TPU_PAGED=0 XOT_TPU_BATCH_SLOTS=48.
  int8_int8kv_batch16_tok_s = _bench_batch(qp, 16, kv_quant="int8") if on_accel else None
  int8_int8kv_batch48_tok_s = _bench_batch(qp, 48, kv_quant="int8") if on_accel else None

  # w8a8 at batch (VERDICT r4 #7): dynamic activation quant puts the decode
  # matmuls on the MXU's int8 path — at B=16 the batch dot is big enough
  # that compute rate could matter. cfg.quant_compute is part of the STATIC
  # jit key, so this compiles its own program (no global-state hazard).
  int8_w8a8_batch16_tok_s = None
  if on_accel:
    from dataclasses import replace as _dc_replace

    try:
      int8_w8a8_batch16_tok_s = _bench_batch(qp, 16, bcfg=_dc_replace(cfg, quant_compute="w8a8"))
    except Exception:  # noqa: BLE001 — optional section
      int8_w8a8_batch16_tok_s = None

  # Long-context decode: the 1B model at a 32K-token context (cache ~1.1 GB
  # bf16 on top of 2.45 GB weights — the §5.7 long-context serving story).
  # XOT_TPU_SP shards this cache read across chips when >1 are present.
  ctx32k_tok_s = None
  int8kv_ctx32k_tok_s = None
  if on_accel:
    try:
      n32 = 64

      def _ctx32k(kv_quant: str) -> float:
        c32 = init_kv_cache(cfg, shard.n_shard_layers, B, 32768, quant=kv_quant)
        t32, c32 = fused_decode(params, cfg, shard, first_tok, c32, jnp.full((B,), 32000, jnp.int32), n32)
        _ = np.asarray(t32)
        t0 = time.perf_counter()
        t32, c32 = fused_decode(params, cfg, shard, first_tok, c32, jnp.full((B,), 32000 + n32, jnp.int32), n32)
        _ = np.asarray(t32)
        return round(n32 * B / (time.perf_counter() - t0), 2)

      ctx32k_tok_s = _ctx32k("")
      # int8 KV (round 5, XOT_TPU_KV_QUANT=int8): halves the cache-read bytes
      # against the measured pattern wall — +22% at 32K on v5e-1 (weights
      # stream bounds the rest; XOT_TPU_SP splits what remains across chips).
      int8kv_ctx32k_tok_s = _ctx32k("int8")
    except Exception:  # noqa: BLE001 — smaller-HBM devices
      pass

  # Paged-KV batched decode (XOT_TPU_PAGED serving mode, ops/paged.py):
  # concurrent rows over a shared page pool, decode attention through the
  # dispatch-table-selected path (inference/paging.py select_decode_path:
  # XLA gather at B<=16 serving shapes, the Pallas paged kernel — page-tiled
  # split-K, in-kernel int8-KV dequant — at larger batch / longer context).
  paged16_tok_s = None
  paged16_int8kv_tok_s = None
  int8_paged16_int8kv_tok_s = None
  paged48_tok_s = None
  paged48_int8kv_tok_s = None
  paged48_int4kv_tok_s = None
  int4kv_batch96_aggregate_tok_s = None
  paged_vs_dense_ratio = None
  paged_vs_dense_ratio_b48 = None
  # Chosen page-tile geometry per benched shape (ISSUE 11): pure dispatch
  # verdicts (inference/paging.py select_page_tile) — emitted on EVERY
  # round, CPU included, so a tile-table regression is diagnosable from the
  # JSON alone even when the throughput fields are null.
  from xotorch_support_jetson_tpu.inference.paging import select_page_tile

  paged_tile_b16_int8kv = select_page_tile(16, 1024, "int8")
  paged_tile_b48_int8kv = select_page_tile(48, 1024, "int8")
  paged_tile_b96_int4kv = select_page_tile(96, 1024, "int4")
  if on_accel:
    from xotorch_support_jetson_tpu.models.decoder import fused_paged_batch_decode
    from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

    def _bench_paged(p, Bp: int, kv_quant: str) -> float | None:
      """Bp-row paged aggregate for a KV quant mode ('' bf16 / 'int8' /
      'int4' packed pages) through the dispatch-selected decode path."""
      ps = 64
      mp = 1024 // ps
      try:
        pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + Bp * mp, ps, quant=kv_quant)
        bt = np.zeros((Bp, mp), np.int32)
        for r in range(Bp):
          bt[r] = range(1 + r * mp, 1 + (r + 1) * mp)
        ptok = jnp.ones((Bp, 1), jnp.int32)
        ppos = jnp.full((Bp,), prompt_len, jnp.int32)
        pact = jnp.ones((Bp,), bool)
        ptemps = jnp.zeros((Bp,), jnp.float32)
        ptoks, _, ppos2, pool = fused_paged_batch_decode(p, cfg, shard, ptok, pool, jnp.asarray(bt), ppos, pact, ptemps, n_decode, page_size=ps)
        _ = np.asarray(ptoks)
        t0 = time.perf_counter()
        ptoks, _, _, pool = fused_paged_batch_decode(p, cfg, shard, ptok, pool, jnp.asarray(bt), ppos2, pact, ptemps, n_decode, page_size=ps)
        _ = np.asarray(ptoks)
        del pool
        return round(Bp * n_decode / (time.perf_counter() - t0), 2)
      except Exception:  # noqa: BLE001 — optional section (smaller-HBM devices)
        return None

    paged16_tok_s = _bench_paged(params, 16, "")
    # int8 KV pages (XOT_TPU_KV_QUANT=int8): int8 bytes through the pool
    # read — +33% aggregate measured (probe: 1324 vs 997) AND 2x contexts
    # resident per HBM byte.
    paged16_int8kv_tok_s = _bench_paged(params, 16, "int8")
    # int8 WEIGHTS + int8-KV pages at B=16: the apples-to-apples numerator
    # for the paged-vs-dense ratio (same weight bytes as the dense
    # int8_int8kv_batch16 denominator, so the ratio isolates the PAGING
    # cost instead of conflating it with weight quantization).
    int8_paged16_int8kv_tok_s = _bench_paged(qp, 16, "int8")
    # B=48 — the dense knee (int8 weights + int8 KV, mirroring the dense
    # int8_int8kv_batch48 config): the paged-vs-dense gap is tracked at the
    # batch size where dense peaks, through the dispatch-selected kernel.
    paged48_tok_s = _bench_paged(params, 48, "")
    paged48_int8kv_tok_s = _bench_paged(qp, 48, "int8")
    # int4-KV pages (ISSUE 11): half the int8 page bytes again — the
    # capacity mode that moves the default admission knee past B=96, so
    # B=96 is where its aggregate is recorded (B=48 for the apples-to-int8
    # comparison at the dense knee).
    paged48_int4kv_tok_s = _bench_paged(qp, 48, "int4")
    int4kv_batch96_aggregate_tok_s = _bench_paged(qp, 96, "int4")
    # Paged-vs-dense efficiency ratios (ISSUE r6 tentpole gauge), int8
    # weights + int8 KV on BOTH sides: B=16 against the dense knee-study
    # number (target >= 0.90); B=48 at the batch size where dense peaks —
    # behind gate_paged_b48 since ISSUE 11 (target >= 0.95 with the
    # shape-aware kernel retune).
    if int8_paged16_int8kv_tok_s and int8_int8kv_batch16_tok_s:
      paged_vs_dense_ratio = round(int8_paged16_int8kv_tok_s / int8_int8kv_batch16_tok_s, 4)
    if paged48_int8kv_tok_s and int8_int8kv_batch48_tok_s:
      paged_vs_dense_ratio_b48 = gate_paged_b48(round(paged48_int8kv_tok_s / int8_int8kv_batch48_tok_s, 4))

  # TTFT under concurrent load: 8 requests arriving together at the REAL
  # batch scheduler (inference/batch_scheduler.py). Batched admission
  # prefills all 8 in one padded dispatch, so p50 TTFT stays ≈ the solo
  # number instead of degrading linearly in queue depth (serial admission
  # would pay 8 × prefill for the median request). Measured end-to-end:
  # submit → first emitted token, default (paged) serving mode.
  ttft_batch8_p50_ms = None
  ttft_batch8_max_ms = None
  ttft_batch8_p95_ms = None
  itl_p50_ms = None
  itl_p99_ms = None

  def _hist_delta_quantile(before: dict, after: dict, name: str, q: float) -> float | None:
    """Quantile of a histogram's growth BETWEEN two registry snapshots —
    isolates the measured round from warm-up observations (the scheduler
    records TTFT/ITL into the global registry on every round, and the warm
    round's compile time would otherwise own the tail). Delta math is the
    shared ``utils/metrics.py snapshot_delta`` (ISSUE 9 satellite)."""
    from xotorch_support_jetson_tpu.utils.metrics import Metrics, snapshot_delta

    delta = snapshot_delta(before, after)
    if name not in (delta.get("histograms") or {}):
      return None
    m = Metrics.merged([delta])
    return m.quantile(name, q)

  server = eng = None
  try:
    if not on_accel:  # scheduler covered by tests on CPU; keep the smoke quick
      raise RuntimeError("skip on cpu")
    import asyncio

    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

    eng = JaxShardedInferenceEngine(use_local_mesh=False)
    eng.load_test_model(shard, cfg, params)
    from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

    server = BatchedServer(eng, n_slots=8, chunk=8)
    rng = np.random.default_rng(7)

    def batch_prompts(tag):
      return {f"{tag}{i}": rng.integers(1, cfg.vocab_size, (96 + i,)).astype(np.int32) for i in range(8)}

    async def ttft_round(prompts):
      first_at: dict[str, float] = {}

      def emit(rid, toks, finished):
        if toks and rid not in first_at:
          first_at[rid] = time.perf_counter()

      t0 = time.perf_counter()
      await asyncio.gather(
        *(
          server.submit(rid, p, max_tokens=9, temp=0.0, top_k=35, eos_ids=(), emit=emit)
          for rid, p in prompts.items()
        )
      )
      return sorted((first_at[rid] - t0) * 1e3 for rid in prompts)

    async def ttft_bench():
      await ttft_round(batch_prompts("w"))  # warm the K=8 admission + chunk programs
      from xotorch_support_jetson_tpu.utils.metrics import metrics as global_metrics

      before = global_metrics.snapshot()
      measured = await ttft_round(batch_prompts("b"))
      return measured, before, global_metrics.snapshot()

    ttfts, snap_before, snap_after = asyncio.run(ttft_bench())
    ttft_batch8_p50_ms = round(float(np.median(ttfts)), 2)
    ttft_batch8_max_ms = round(ttfts[-1], 2)
    # Tail latency from the scheduler's own histograms (utils/metrics.py):
    # the measured round's delta only, so warm-compile samples don't own
    # the tail. These are what BENCH rounds track instead of just means.
    p95 = _hist_delta_quantile(snap_before, snap_after, "ttft_seconds", 0.95)
    ttft_batch8_p95_ms = round(p95 * 1e3, 2) if p95 is not None else None
    itl50 = _hist_delta_quantile(snap_before, snap_after, "itl_seconds", 0.50)
    itl99 = _hist_delta_quantile(snap_before, snap_after, "itl_seconds", 0.99)
    itl_p50_ms = round(itl50 * 1e3, 3) if itl50 is not None else None
    itl_p99_ms = round(itl99 * 1e3, 3) if itl99 is not None else None
  except Exception:  # noqa: BLE001 — keep the bench line printing
    pass
  finally:
    # Release the pool's HBM on BOTH paths — a leaked 8-slot paged cache
    # would starve the later spec/8B sections and corrupt their numbers.
    if server is not None:
      server.shutdown()
    server = eng = None

  # Lookahead-vs-sync A/B through the REAL scheduler at the dense B=48 knee
  # (int8 weights + int8 KV — the config behind the repo's best aggregate):
  # the one-chunk-lookahead pipeline overlaps host bookkeeping + readback
  # with the next chunk's device compute, so the ratio directly measures the
  # per-chunk host window it hides. Both modes run back-to-back on the same
  # engine/pool config; sched_host_gap_ms_p50 tracks the device-idle window
  # a dispatch had to wait for host work in the DEFAULT (lookahead) mode —
  # ~0 by construction, so upward drift is a pipeline regression.
  batch48_lookahead_vs_sync = None
  sched_host_gap_ms_p50 = None
  sched_host_gap_sync_ms_p50 = None
  lookahead48_aggregate_tok_s = None
  sync48_aggregate_tok_s = None
  # Flight-recorder overhead (ISSUE 9): the same B=48 round with the
  # recorder off (XOT_TPU_FLIGHTREC=0) pins that the hot path is unaffected
  # — the recorder only sees state transitions (~2 events/request), so the
  # on/off ratio must sit at ~1.0; events_per_sec documents the actual
  # recording rate at the knee.
  flightrec_events_per_sec = None
  flightrec_overhead_ratio = None
  la_env = {
    "XOT_TPU_PAGED": os.environ.get("XOT_TPU_PAGED"),
    "XOT_TPU_KV_QUANT": os.environ.get("XOT_TPU_KV_QUANT"),
    "XOT_TPU_FLIGHTREC": os.environ.get("XOT_TPU_FLIGHTREC"),
  }
  eng48 = server48 = None
  try:
    if not on_accel:  # A/B token-identity is pinned by tests/test_lookahead.py on CPU
      raise RuntimeError("skip on cpu")
    import asyncio

    from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
    from xotorch_support_jetson_tpu.utils.metrics import metrics as global_metrics

    os.environ["XOT_TPU_PAGED"] = "0"  # dense slots: where the B=48 knee lives
    os.environ["XOT_TPU_KV_QUANT"] = "int8"
    eng48 = JaxShardedInferenceEngine(use_local_mesh=False)
    eng48.load_test_model(shard, cfg, qp)
    rng48 = np.random.default_rng(11)
    n_la_tok = 33  # first token + 4 chunks of 8

    def _bench_sched(tag: str, lookahead: bool):
      nonlocal server48
      server48 = BatchedServer(eng48, n_slots=48, chunk=8, lookahead=lookahead)
      prompts = {f"{tag}{i}": rng48.integers(1, cfg.vocab_size, (64,)).astype(np.int32) for i in range(48)}

      async def bench_round():
        total = 0

        def emit(rid, toks, finished):
          nonlocal total
          total += len(toks)

        async def one_round():
          await asyncio.gather(
            *(
              server48.submit(rid, p, max_tokens=n_la_tok, temp=0.0, top_k=35, eos_ids=(), emit=emit)
              for rid, p in prompts.items()
            )
          )

        await one_round()  # warm the 48-row admission + chunk programs
        total = 0
        before = global_metrics.snapshot()
        seq0 = _frec.last_seq()
        t0 = time.perf_counter()
        await one_round()
        dt = time.perf_counter() - t0
        return total / dt, before, global_metrics.snapshot(), (_frec.last_seq() - seq0) / dt

      tok_s, before, after, ev_s = asyncio.run(bench_round())
      gap = _hist_delta_quantile(before, after, "sched_host_gap_seconds", 0.50)
      server48.shutdown()
      server48 = None
      return round(tok_s, 2), (round(gap * 1e3, 3) if gap is not None else None), round(ev_s, 2)

    from xotorch_support_jetson_tpu.orchestration.flightrec import flightrec as _frec

    lookahead48_aggregate_tok_s, sched_host_gap_ms_p50, flightrec_events_per_sec = _bench_sched("la", True)
    sync48_aggregate_tok_s, sched_host_gap_sync_ms_p50, _ = _bench_sched("sy", False)
    if lookahead48_aggregate_tok_s and sync48_aggregate_tok_s:
      batch48_lookahead_vs_sync = gate_lookahead(round(lookahead48_aggregate_tok_s / sync48_aggregate_tok_s, 4))
    # Recorder-off control run (same config as the lookahead run). The
    # caller's XOT_TPU_FLIGHTREC is restored by the la_env finally below,
    # raise or not.
    os.environ["XOT_TPU_FLIGHTREC"] = "0"
    frec_off_tok_s, _, _ = _bench_sched("fr", True)
    if lookahead48_aggregate_tok_s and frec_off_tok_s:
      flightrec_overhead_ratio = gate_lookahead(round(lookahead48_aggregate_tok_s / frec_off_tok_s, 4))
  except Exception:  # noqa: BLE001 — optional section: keep the bench line printing
    pass
  finally:
    if server48 is not None:
      server48.shutdown()
    server48 = eng48 = None
    for k, v in la_env.items():  # later sections read these envs (init_kv_cache)
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  # QoS overload round (ISSUE 5): offered load ≈ 2x capacity, mixed priority
  # (half interactive, half batch, distinct tenants) against the QoS-enabled
  # scheduler. Emits the shed rate (behind gate_overload) and per-class
  # first-token p99s measured CLIENT-side — the numbers the acceptance
  # criterion is judged on: interactive p99 must hold while batch sheds/
  # degrades. Null on CPU rounds (tests/test_qos.py pins the behavior there).
  overload_shed_rate = None
  ttft_ms_p99_interactive_overload = None
  ttft_ms_p99_batch_overload = None
  slo_attainment_interactive = None
  goodput_ratio = None
  ov_server = ov_eng = None
  try:
    if not on_accel:
      raise RuntimeError("skip on cpu")
    import asyncio

    from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
    from xotorch_support_jetson_tpu.inference.engine import ServerOverloadedError
    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
    from xotorch_support_jetson_tpu.utils.metrics import metrics as global_metrics, snapshot_delta as _snap_delta

    ov_eng = JaxShardedInferenceEngine(use_local_mesh=False)
    ov_eng.load_test_model(shard, cfg, qp)
    n_slots_ov = 16
    offered = 2 * n_slots_ov  # ≈ 2x capacity: every slot claimed twice over
    ov_server = BatchedServer(ov_eng, n_slots=n_slots_ov, chunk=8, max_queue=n_slots_ov, qos=True)
    rng_ov = np.random.default_rng(23)
    prompts_ov = [rng_ov.integers(1, cfg.vocab_size, (64,)).astype(np.int32) for _ in range(offered)]

    async def overload_round():
      waits = {"interactive": [], "batch": []}
      shed = 0
      firsts: dict[str, float] = {}

      def emit(rid, toks, finished):
        if toks and rid not in firsts:
          firsts[rid] = time.perf_counter()

      async def one(i: int, klass: str):
        nonlocal shed
        rid = f"ov-{klass}-{i}"
        t0 = time.perf_counter()
        try:
          await ov_server.submit(
            rid, prompts_ov[i], max_tokens=17, temp=0.0, top_k=35,
            eos_ids=(), emit=emit, priority=klass, tenant=f"tenant-{klass}",
          )
          waits[klass].append((firsts[rid] - t0) * 1e3)
        except ServerOverloadedError:
          shed += 1

      tasks = [asyncio.create_task(one(i, "batch")) for i in range(offered // 2)]
      await asyncio.sleep(0.02)  # the batch backlog forms first — worst case
      tasks += [asyncio.create_task(one(offered // 2 + i, "interactive")) for i in range(offered // 2)]
      await asyncio.gather(*tasks)
      return waits, shed

    ov_before = global_metrics.snapshot()
    waits_ov, shed_ov = asyncio.run(overload_round())
    overload_shed_rate = gate_overload(round(shed_ov / offered, 4))
    # SLO/goodput read of the same round (ISSUE 9): the engine's own window
    # math over the round's snapshot delta — interactive attainment under
    # 2x overload (the router's per-replica health signal) and the
    # goodput-to-delivered token ratio across all classes.
    from xotorch_support_jetson_tpu.orchestration import slo as _slo

    ov_delta = _snap_delta(ov_before, global_metrics.snapshot())
    att_num = _slo.counter_family(ov_delta, "slo_requests_good_total", {"class": "interactive"})
    att_den = att_num + _slo.counter_family(ov_delta, "slo_requests_bad_total", {"class": "interactive"})
    if att_den > 0:
      slo_attainment_interactive = gate_slo(round(att_num / att_den, 4))
    tok_total = _slo.counter_family(ov_delta, "slo_tokens_total")
    tok_good = _slo.counter_family(ov_delta, "slo_good_tokens_total")
    if tok_total > 0:
      goodput_ratio = gate_slo(round(tok_good / tok_total, 4))

    def p99(xs):
      # Nearest-rank p99: ceil(0.99 n) - 1. At this round's sample counts
      # (16/class) that is the max — the worst TTFT must not silently drop
      # out of the tracked record.
      if not xs:
        return None
      idx = min(len(xs) - 1, max((len(xs) * 99 + 99) // 100 - 1, 0))
      return round(sorted(xs)[idx], 2)

    ttft_ms_p99_interactive_overload = p99(waits_ov["interactive"])
    ttft_ms_p99_batch_overload = p99(waits_ov["batch"])
  except Exception:  # noqa: BLE001 — optional section: keep the bench line printing
    pass
  finally:
    if ov_server is not None:
      ov_server.shutdown()
    ov_server = ov_eng = None

  # KV tier round (ISSUE 6, behind gate_kv_tier): raw spill/restore copy
  # bandwidth over the real paged pool, open multi-turn sessions held with
  # the pool oversubscribed ~4x, and the preempt-resume recompute-vs-restore
  # A/B from the request timelines. Null on CPU rounds (tests/test_kv_tier.py
  # pins the behavior there).
  kv_spill_gbps = None
  kv_restore_gbps = None
  kv_stream_gbps_int4 = None
  open_sessions_per_node = None
  preempt_resume_ms_recompute = None
  preempt_resume_ms_restore = None
  preempt_resume_ms_recompute_vs_restore = None
  kv_eng = kv_server = None
  kv_env = {}
  try:
    if not on_accel:
      raise RuntimeError("skip on cpu")
    import asyncio

    from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
    from xotorch_support_jetson_tpu.inference.kv_tier import gather_pages, scatter_pages
    from xotorch_support_jetson_tpu.ops.paged import init_paged_pool
    from xotorch_support_jetson_tpu.orchestration.tracing import tracer

    # --- spill/restore bandwidth: 128 pages in one batched copy each way.
    kv_ps, kv_n = 64, 128
    kv_pages = list(range(1, kv_n + 1))

    def _spill_gbps(pool_q):
      """Warm + measured 128-page batched D2H over one pool; returns
      (gated GB/s, per-page bytes, host copies) — shared by the bf16 spill
      number and the int4 stream-rate number below."""
      dev, nn = gather_pages(pool_q, kv_pages)  # warm (compile + first copy)
      host = {k: np.asarray(v)[:, :nn] for k, v in dev.items()}
      pb = sum(int(np.prod(a.shape[2:])) * a.shape[0] * a.dtype.itemsize for a in host.values())
      t0 = time.perf_counter()
      dev, nn = gather_pages(pool_q, kv_pages)
      host = {k: np.asarray(v)[:, :nn] for k, v in dev.items()}
      return gate_kv_tier(round(pb * kv_n / (time.perf_counter() - t0) / 1e9, 3)), pb, host

    kv_pool = init_paged_pool(cfg, shard.n_shard_layers, 2 * kv_n + 1, kv_ps)
    kv_spill_gbps, page_bytes, host = _spill_gbps(kv_pool)
    kv_pool = scatter_pages(kv_pool, kv_pages, host)  # warm
    jax.block_until_ready(jax.tree_util.tree_leaves(kv_pool))
    t0 = time.perf_counter()
    kv_pool = scatter_pages(kv_pool, kv_pages, host)
    jax.block_until_ready(jax.tree_util.tree_leaves(kv_pool))
    kv_restore_gbps = gate_kv_tier(round(page_bytes * kv_n / (time.perf_counter() - t0) / 1e9, 3))
    del kv_pool, host

    # --- int4 page copies (ISSUE 11): the same 128-page batched D2H over a
    # PACKED int4 pool — the byte rate that bounds both the host-tier spill
    # and the SendKvPages wire payload under XOT_TPU_KV_QUANT=int4 (the
    # stream ships exactly these leaves; halved page bytes ⇒ halved
    # transfer cost at the same copy rate).
    kv_pool4 = init_paged_pool(cfg, shard.n_shard_layers, 2 * kv_n + 1, kv_ps, quant="int4")
    kv_stream_gbps_int4, _, host4 = _spill_gbps(kv_pool4)
    del kv_pool4, host4

    # --- open sessions with the pool oversubscribed ~4x: 48 two-turn chat
    # sessions on an 8-slot server whose pool holds ~1/4 of their history.
    n_sessions, n_slots_kv = 48, 8
    kv_env = {"XOT_TPU_PAGE_SIZE": os.environ.get("XOT_TPU_PAGE_SIZE"), "XOT_TPU_BATCH_PAGES": os.environ.get("XOT_TPU_BATCH_PAGES"), "XOT_TPU_KV_TIER": os.environ.get("XOT_TPU_KV_TIER")}
    os.environ["XOT_TPU_PAGE_SIZE"] = "64"
    os.environ["XOT_TPU_BATCH_PAGES"] = "37"  # ~(48 sessions x 3 pages) / 4
    os.environ.pop("XOT_TPU_KV_TIER", None)
    kv_eng = JaxShardedInferenceEngine(use_local_mesh=False)
    kv_eng.load_test_model(shard, cfg, qp)
    kv_server = BatchedServer(kv_eng, n_slots=n_slots_kv, chunk=8, max_queue=2 * n_sessions, qos=False)
    rng_kv = np.random.default_rng(31)

    async def kv_sessions():
      done = 0

      async def one(i: int):
        nonlocal done
        prompt = rng_kv.integers(1, cfg.vocab_size, (128,)).astype(np.int32).tolist()
        for turn in range(2):
          out = await kv_server.submit(f"kv-{i}-{turn}", np.asarray(prompt, np.int32), max_tokens=16, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None)
          prompt = prompt + out + [int(rng_kv.integers(1, cfg.vocab_size))]
        done += 1

      await asyncio.gather(*(one(i) for i in range(n_sessions)), return_exceptions=True)
      return done

    open_sessions_per_node = asyncio.run(kv_sessions())
    kv_server.shutdown()
    kv_server = None

    # --- preempt-resume A/B: resume gap (preempted -> next decode stage on
    # the request timeline) with the tier restoring vs recomputing prefill.
    def resume_gap_ms(tier_on: bool) -> float | None:
      if tier_on:
        os.environ.pop("XOT_TPU_KV_TIER", None)
      else:
        os.environ["XOT_TPU_KV_TIER"] = "0"
      eng = JaxShardedInferenceEngine(use_local_mesh=False)
      eng.load_test_model(shard, cfg, qp)
      server = BatchedServer(eng, n_slots=1, chunk=8, qos=True)
      rid = f"kv-pre-{tier_on}"
      prompt = rng_kv.integers(1, cfg.vocab_size, (512,)).astype(np.int32)  # prefill worth skipping

      async def drive():
        started = asyncio.Event()
        emitted = []

        def emit(r, toks, fin):
          if r == rid:
            emitted.extend(toks)
            if len(emitted) >= 8:
              started.set()

        bg = asyncio.create_task(server.submit(rid, prompt, max_tokens=64, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch"))
        await asyncio.wait_for(started.wait(), timeout=120)
        await server.submit("kv-vip", prompt[:64], max_tokens=8, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None, priority="interactive")
        await asyncio.wait_for(bg, timeout=240)

      try:
        asyncio.run(drive())
        tl = tracer.timeline(rid)
        if tl is None:
          return None
        t_pre = next((e["at_ms"] for e in tl["events"] if e["stage"] == "preempted"), None)
        if t_pre is None:
          return None
        t_dec = next((e["at_ms"] for e in tl["events"] if e["stage"] == "decode" and e["at_ms"] > t_pre), None)
        return None if t_dec is None else round(t_dec - t_pre, 2)
      finally:
        server.shutdown()

    preempt_resume_ms_restore = resume_gap_ms(True)
    preempt_resume_ms_recompute = resume_gap_ms(False)
    if preempt_resume_ms_restore and preempt_resume_ms_recompute:
      preempt_resume_ms_recompute_vs_restore = gate_kv_tier(
        round(preempt_resume_ms_recompute / preempt_resume_ms_restore, 4), lo=1.0 / 3.0, hi=100.0
      )
  except Exception:  # noqa: BLE001 — optional section: keep the bench line printing
    pass
  finally:
    if kv_server is not None:
      kv_server.shutdown()
    kv_server = kv_eng = None
    for k, v in kv_env.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  # Speculative decoding (XOT_TPU_SPEC_DECODE=int8, models/decoder.py
  # fused_speculative_generate): greedy int8 self-draft + bf16 target in one
  # while_loop. On these RANDOM weights logits are near-uniform, so the
  # measured acceptance (and hence speed) is a floor, not the real-model
  # number — reported alongside so the trade is visible.
  spec_tok_s = None
  spec_acceptance = None
  spec_vs_plain = None
  spec_peak_tok_s = None
  spec_peak_acceptance = None
  spec_peak_vs_plain = None
  if on_accel:
    from xotorch_support_jetson_tpu.models.decoder import fused_speculative_generate

    gamma = 4
    spec_prefill = jax.jit(shard_forward, static_argnames=("cfg", "shard"))

    def bench_spec(target_p, draft_p):
      """(tok_s, acceptance, vs_plain) for one target/draft pair — warm run
      + timed run over fresh prefilled caches, identical protocol for the
      floor and ceiling measurements below."""

      def caches():
        ct = init_kv_cache(cfg, shard.n_shard_layers, B, max_seq)
        cd = init_kv_cache(cfg, shard.n_shard_layers, B, max_seq)
        _, ct = spec_prefill(target_p, cfg, shard, tokens, positions, ct)
        _, cd = spec_prefill(draft_p, cfg, shard, tokens, positions, cd)
        return ct, cd

      ct, cd = caches()
      sbuf, *_ = fused_speculative_generate(target_p, cfg, shard, draft_p, cfg, shard, first_tok, ct, cd, prompt_len, n_decode, gamma=gamma, eos_ids=(-1,))
      _ = np.asarray(sbuf)
      ct, cd = caches()
      t0 = time.perf_counter()
      sbuf, sn, srounds, ct, cd = fused_speculative_generate(target_p, cfg, shard, draft_p, cfg, shard, first_tok, ct, cd, prompt_len, n_decode, gamma=gamma, eos_ids=(-1,))
      _ = np.asarray(sbuf)
      sn, srounds = int(sn), max(int(srounds), 1)
      tok_s = round(min(sn, n_decode) / (time.perf_counter() - t0), 2)
      acceptance = round((sn / srounds - 1) / gamma, 3)
      vs_plain = round(tok_s / serving_tok_s, 3) if serving_tok_s else None
      return tok_s, acceptance, vs_plain

    # FLOOR: on these RANDOM weights logits are near-uniform, so int8 noise
    # flips the draft's argmax often; the engine's load-time autocalibration
    # (XOT_TPU_SPEC_AUTOCAL) disables the mode when plain wins, so a sub-1.0
    # ratio here is a measured demotion, not a shipped regression.
    spec_tok_s, spec_acceptance, spec_vs_plain = bench_spec(params, qp)

    # CEILING: the peaked-logit synthetic model (utils/synthetic.py) drives
    # acceptance to ~1.0 — the first offline record of what speculation can
    # AT BEST deliver here (VERDICT r3 #6). Same geometry and weight bytes
    # as the headline model, so the plain serving number stays the
    # apples-to-apples denominator; real checkpoints sit between the two.
    from xotorch_support_jetson_tpu.utils.synthetic import peaked_echo_params

    pkp = peaked_echo_params(params)
    pkq = quantize_params(pkp)
    spec_peak_tok_s, spec_peak_acceptance, spec_peak_vs_plain = bench_spec(pkp, pkq)
    # Free the spec-floor HBM before the 8.5 GB 8B model loads. (The
    # self-pair's acceptance=1.0 comes from AGREEMENT — pkp and pkq compute
    # the same deterministic map whether or not it truly echoes — so damp
    # doesn't matter above; the cross pair below needs a TRUE echo and
    # builds its own draft at the measured-echoing damp.)
    del pkp, pkq, qp

  # Pipeline-parallel serving decode (parallel/pp_serving.py): only runs when
  # the host exposes >=2 accelerator chips; otherwise exercised in tests and
  # dryrun_multichip on the virtual mesh.
  pp_decode_tok_s = None
  pp_batched_tok_s = None
  if on_accel and len(jax.devices()) >= 2:
    from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
    from xotorch_support_jetson_tpu.parallel.pp_serving import PPServing

    n_dev = len(jax.devices())
    pp_deg = n_dev if cfg.n_layers % n_dev == 0 else 2
    if cfg.n_layers % pp_deg == 0:  # skip (like other optional sections) rather than abort the run
      pp = PPServing(build_mesh(MeshPlan(pp=pp_deg)), cfg, params, pp_deg, True, True)
      pcache = pp.place_cache(init_kv_cache(cfg, shard.n_shard_layers, B, max_seq))
      ptoks, pcache = pp.fused_decode(first_tok, pcache, jnp.zeros((B,), jnp.int32), n_decode)
      _ = np.asarray(ptoks)
      t0 = time.perf_counter()
      ptoks, pcache = pp.fused_decode(first_tok, pcache, jnp.full((B,), n_decode, jnp.int32), n_decode)
      _ = np.asarray(ptoks)
      pp_decode_tok_s = round(n_decode * B / (time.perf_counter() - t0), 2)
      del pcache

      # Multi-stream pipeline serving (parallel/pp_batch.py): 2·pp streams
      # overlapping across stages — the aggregate-throughput story for deep
      # pipelines (VERDICT r2 #2); target ≥ ~P× the B=1 pp number above.
      from xotorch_support_jetson_tpu.parallel.pp_batch import PPBatchedServing

      ppb = PPBatchedServing.from_pp_serving(pp)
      Bpp = 2 * pp_deg
      bcache2 = ppb.place_cache(init_kv_cache(cfg, shard.n_shard_layers, Bpp, 1024))
      btok2 = jnp.ones((Bpp, 1), jnp.int32)
      bpos2 = jnp.full((Bpp,), prompt_len, jnp.int32)
      bact2 = jnp.ones((Bpp,), bool)
      btmp2 = jnp.zeros((Bpp,), jnp.float32)
      btk2 = jnp.full((Bpp,), 35, jnp.int32)
      btoks2, _, bpos2, bcache2 = ppb.batch_decode(btok2, bcache2, bpos2, bact2, btmp2, btk2, n_decode)
      _ = np.asarray(btoks2)
      t0 = time.perf_counter()
      btoks2, _, bpos2, bcache2 = ppb.batch_decode(btok2, bcache2, bpos2, bact2, btmp2, btk2, n_decode)
      _ = np.asarray(btoks2)
      pp_batched_tok_s = round(Bpp * n_decode / (time.perf_counter() - t0), 2)
      del bcache2

  # Cross-node hop overhead (ISSUE 4): p50 serialize cost and RPC latency
  # per ring hop from the new per-peer-link histograms, measured over a real
  # two-node localhost gRPC ring. Gated like the other multichip sections —
  # null on single-node CPU rounds.
  hop_serialize_ms_p50 = None
  hop_rpc_ms_p50 = None
  if on_accel and len(jax.devices()) >= 2:
    try:
      hop_serialize_ms_p50, hop_rpc_ms_p50 = bench_cross_node_hops()
    except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
      pass

  # Failover round (ISSUE 8, behind gate_failover): kill-mid-decode on the
  # localhost two-node ring via the deterministic fault injector — emits the
  # client-visible recovery window p50 and the hard invariant requests_lost
  # (must be 0: every in-flight request completes or errors, never hangs).
  # Gated like the other multichip sections — null on single-node CPU rounds.
  failover_recovery_ms_p50 = None
  requests_lost = None
  if on_accel and len(jax.devices()) >= 2:
    try:
      failover_recovery_ms_p50, requests_lost = bench_failover_recovery()
    except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
      pass

  # Disaggregated prefill/decode round (ISSUE 10, behind gate_disagg):
  # chunked-prefill burst + resident decode on the localhost two-node ring,
  # disagg vs colocated. Null on CPU rounds like the other cluster benches —
  # the behavior (token identity, fallback, adoption) is pinned by
  # tests/test_disagg.py there; the accel round records the measured numbers.
  disagg_ttft_ms_p50 = None
  disagg_vs_colocated_itl_p50 = None
  kv_stream_gbps = None
  if on_accel:
    try:
      disagg_ttft_ms_p50, disagg_vs_colocated_itl_p50, kv_stream_gbps = bench_disagg()
    except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
      pass

  # Mixed-tick round (ISSUE 14, behind gate_mixed): colocated burst through
  # the batched scheduler (the PR 10 disagg fixture minus the second node) —
  # mid-burst resident ITL and burst TTFT, mixed vs alternating. Runs on
  # EVERY round: the contention is a scheduler property and the 108 ms
  # colocated baseline was measured on this box, so the CPU smoke records a
  # real A/B too.
  mixed_resident_itl_ms = None
  alternating_resident_itl_ms = None
  mixed_vs_alternating_itl = None
  mixed_ttft_ms_p50 = None
  alternating_ttft_ms_p50 = None
  mixed_resident_itl_ms_p50 = None
  alternating_resident_itl_ms_p50 = None
  try:
    (
      mixed_resident_itl_ms, alternating_resident_itl_ms, mixed_vs_alternating_itl,
      mixed_ttft_ms_p50, alternating_ttft_ms_p50,
      mixed_resident_itl_ms_p50, alternating_resident_itl_ms_p50,
    ) = bench_mixed()
  except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
    pass

  # Batched multi-LoRA round (ISSUE 15, behind gate_lora): mixed-adapter
  # B=8 batch through the real scheduler vs the base batch, plus the
  # adapter swap-in latency — CPU-measurable on every round (the hook is a
  # per-row gather inside the same fused programs).
  lora_mixed_batch8_vs_base8 = None
  lora_swap_ms_p50 = None
  lora_mixed_batch8_aggregate_tok_s = None
  lora_base_batch8_aggregate_tok_s = None
  try:
    (
      lora_mixed_batch8_vs_base8, lora_swap_ms_p50,
      lora_mixed_batch8_aggregate_tok_s, lora_base_batch8_aggregate_tok_s,
    ) = bench_lora()
  except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
    pass

  # Cluster front door round (ISSUE 13, behind gate_router): two-replica
  # localhost fixture with a tiny checkpoint and a repeated-system-prompt
  # two-turn workload — affine (router) vs random (hand round-robin) TTFT,
  # the routed prefix hit rate, and the kill-mid-stream failover splice
  # window. Runs on EVERY round (the router is host-side HTTP + policy —
  # CPU-measurable like gate_spec_ngram).
  router_affine_vs_random_ttft_p50 = None
  router_prefix_hit_rate = None
  router_failover_ms_p50 = None
  router_affine_ttft_ms_p50 = None
  router_random_ttft_ms_p50 = None
  try:
    (
      router_affine_vs_random_ttft_p50, router_prefix_hit_rate, router_failover_ms_p50,
      router_affine_ttft_ms_p50, router_random_ttft_ms_p50,
    ) = bench_router_round()
  except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
    pass

  # 8B-geometry int8 decode: the measurable v5e-1 stand-in for BASELINE
  # configs 2/3 (8B-class serving). bf16 8B (~16 GB) exceeds one v5e chip's
  # HBM, so weights are generated AND quantized leaf-by-leaf (the full bf16
  # model never materializes; peak = int8 model + one bf16 leaf ≈ 9 GB).
  int8_8b_tok_s = None
  spec_8b_draft1b_tok_s = None
  spec_8b_draft1b_acceptance = None
  spec_8b_draft1b_vs_plain8b = None
  # Batched speculation round (ISSUE 7): null on CPU rounds — the behavior
  # (token identity, adaptive gamma, accounting) is pinned by
  # tests/test_spec_batch.py there; the v5e round records the measured A/B.
  spec_batch8_aggregate_tok_s = None
  plain_batch8_aggregate_tok_s = None
  spec_batch8_vs_plain8 = None
  spec_acceptance_rate = None
  spec_gamma_p50 = None
  # Draft-free n-gram speculation round (ISSUE 12, behind gate_spec_ngram):
  # measured on EVERY round — the proposer is host-side and the workload
  # synthetic, so the CPU smoke run records a real A/B too (tiny model; the
  # accel round measures the 1B-geometry echo model).
  spec_ngram_batch8_aggregate_tok_s = None
  spec_ngram_plain_batch8_aggregate_tok_s = None
  spec_ngram_batch8_vs_plain8 = None
  spec_ngram_acceptance_rate = None
  spec_proposer_mix = None
  # Proposer-policy dispatch verdicts (pure host policy, non-null on CPU —
  # the paged_tile_* pattern): a policy-table regression is diagnosable
  # from the JSON alone even when the throughput fields are null.
  from xotorch_support_jetson_tpu.inference.paging import spec_reprobe_proposer, spec_select_proposer

  spec_policy_model_collapse_switches_to = spec_select_proposer("model", {"model": 0.1}, ("model", "ngram"))[0]
  spec_policy_exhausted_falls_back_to = spec_select_proposer("model", {"model": 0.1, "ngram": 0.05}, ("model", "ngram"))[0]
  spec_policy_reprobe_prefers = spec_reprobe_proposer({}, ("ngram", "model"))
  if on_accel:
    try:
      from xotorch_support_jetson_tpu.inference.shard import Shard
      from xotorch_support_jetson_tpu.models.quantize import quantize_weight

      cfg8 = ModelConfig(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, head_dim=128, rope_theta=500000.0, max_seq_len=2048,
        tied_embedding=False, dtype=jnp.bfloat16,
      )
      shard8 = Shard("llama-3.1-8b", 0, cfg8.n_layers - 1, cfg8.n_layers)

      def build_8b_int8():
        # Generate ALREADY-QUANTIZED weights: each stacked leaf is built by a
        # lax.map over layers whose body makes one [in, out] bf16 slab and
        # quantizes it in-place — the bf16/f32 transients never exceed one
        # layer's worth, so peak HBM ≈ int8 model (~8.5 GB), not bf16 (~16 GB).
        L, D, F, V = cfg8.n_layers, cfg8.dim, cfg8.hidden_dim, cfg8.vocab_size
        Qd, Kd = cfg8.q_dim, cfg8.kv_dim

        @partial(jax.jit, static_argnames=("d_in", "d_out"))
        def qstack(keys, d_in: int, d_out: int):
          def one(k):
            w = jax.random.normal(k, (d_in, d_out), dtype=jnp.float32) * (1.0 / (d_in**0.5))
            return quantize_weight(w.astype(jnp.bfloat16))

          return jax.lax.map(one, keys)

        root = jax.random.PRNGKey(1)
        names = [("wq", D, Qd), ("wk", D, Kd), ("wv", D, Kd), ("wo", Qd, D), ("w_gate", D, F), ("w_up", D, F), ("w_down", F, D)]
        stack = {"attn_norm": jnp.ones((L, D), jnp.bfloat16), "mlp_norm": jnp.ones((L, D), jnp.bfloat16)}
        for i, (name, di, do) in enumerate(names):
          q, s = qstack(jax.random.split(jax.random.fold_in(root, i), L), di, do)
          stack[name], stack[f"{name}_scale"] = q, s
        embed = (jax.random.normal(jax.random.fold_in(root, 101), (V, D), jnp.float32) * 0.02).astype(jnp.bfloat16)
        # TIED head (embed.T, quantized): same bytes/step as a random head,
        # but it makes the echo variant (spec ceiling below) actually echo —
        # logits peak at the current token through embed self-similarity.
        qh, sh = jax.jit(quantize_weight)(embed.T)
        p = {
          "layers": stack,
          "embed": embed,
          "final_norm": jnp.ones((D,), jnp.bfloat16),
          "lm_head": qh,
          "lm_head_scale": sh,
        }
        jax.block_until_ready(p["lm_head"])
        return p

      qp8 = build_8b_int8()
      c8 = init_kv_cache(cfg8, cfg8.n_layers, 1, 1024)
      t8, c8 = fused_decode(qp8, cfg8, shard8, first_tok, c8, jnp.zeros((1,), jnp.int32), n_decode)
      _ = np.asarray(t8)
      best = 0.0
      p8 = n_decode
      for _ in range(2):
        t0 = time.perf_counter()
        t8, c8 = fused_decode(qp8, cfg8, shard8, first_tok, c8, jnp.full((1,), p8, jnp.int32), n_decode)
        _ = np.asarray(t8)
        best = max(best, n_decode / (time.perf_counter() - t0))
        p8 += n_decode
      int8_8b_tok_s = round(best, 2)
      del c8, t8

      # Cross-model speculative CEILING (VERDICT r4 #3): int8 8B echo target
      # + int8 1B echo draft — the ~4× speed-ratio pair where speculation
      # mathematically wins (the self-draft's ~1.6× ratio loses even at
      # acceptance 1.0). Echo makes both models argmax the current token, so
      # acceptance ≈ 1.0: this records the MECHANICAL ceiling of
      # XOT_TPU_SPEC_DRAFT=llama-3.2-1b on an 8B target; real checkpoints
      # land between the floor (spec_vs_plain) and this.
      try:
        from xotorch_support_jetson_tpu.models.decoder import fused_speculative_generate as _spec_gen
        from xotorch_support_jetson_tpu.utils.synthetic import peaked_echo_params as _echo

        # damp=0.01 on BOTH sides: at the default 0.05 the residual noise
        # swamps embed self-similarity (measured: 32-layer target argmaxes
        # the wrong token at 0.05, clean echo at 0.01 with margin 22; the
        # 16-layer 1B needs 0.01 too — margin 15.5). The cross pair only
        # agrees when both models TRULY echo; the self-pair above hides
        # non-echoing because both sides compute the same function.
        echo8 = _echo(qp8, damp=0.01)
        draft1b = quantize_params(peaked_echo_params(params, damp=0.01))
        gamma8 = 4

        def spec8_run():
          ct = init_kv_cache(cfg8, cfg8.n_layers, 1, 1024)
          cd = init_kv_cache(cfg, cfg.n_layers, 1, 1024)
          t0 = time.perf_counter()
          buf, m, rounds, ct, cd = _spec_gen(
            echo8, cfg8, shard8, draft1b, cfg, shard, first_tok, ct, cd, 0, n_decode, gamma=gamma8, eos_ids=(-1,)
          )
          _ = np.asarray(buf)
          m, rounds = int(m), max(int(rounds), 1)
          return min(m, n_decode) / (time.perf_counter() - t0), (m / rounds - 1) / gamma8

        spec8_run()  # warm compile
        s_tok, s_acc = max(spec8_run(), spec8_run())
        spec_8b_draft1b_tok_s = round(s_tok, 2)
        spec_8b_draft1b_acceptance = round(s_acc, 3)
        spec_8b_draft1b_vs_plain8b = round(s_tok / int8_8b_tok_s, 3)

        # BATCHED speculation round (ISSUE 7, behind gate_spec_batch): the
        # same echo-8B-target/echo-1B-draft pair through the REAL batched
        # scheduler at B=8 on the serving-default layout (paged + int8-KV),
        # spec mode vs plain back-to-back — the acceptance criterion is
        # spec aggregate ≥ plain aggregate on the measured round. Also
        # records the measured acceptance rate (from the spec counters'
        # delta) and the p50 of the per-row dispatched gammas.
        sb_env = {k: os.environ.get(k) for k in ("XOT_TPU_PAGED", "XOT_TPU_KV_QUANT")}
        try:
          import asyncio as _asyncio

          from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer as _BS
          from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine as _Eng
          from xotorch_support_jetson_tpu.utils.metrics import metrics as _gm

          os.environ["XOT_TPU_PAGED"] = "1"
          os.environ["XOT_TPU_KV_QUANT"] = "int8"
          sb_eng = _Eng(use_local_mesh=False)
          sb_eng.load_test_model(shard8, cfg8, echo8)
          sb_eng._draft_params = draft1b  # cross-model 1B draft, injected
          sb_eng._draft_cfg = cfg
          sb_eng._draft_shard = shard
          sb_rng = np.random.default_rng(13)
          sb_prompts = {f"sb{i}": sb_rng.integers(1, cfg8.vocab_size, (64,)).astype(np.int32) for i in range(8)}
          sb_gammas: list[int] = []

          def _bench_spec_batch(tag: str, spec_on: bool):
            srv = _BS(sb_eng, n_slots=8, chunk=8, spec_batch=spec_on)
            if spec_on:
              orig_sp = srv.ops.spec_paged_batch_decode

              def spy(token, pool, cache_d, bt, pos, active, gammas, *a, **k):
                sb_gammas.extend(int(g) for g in np.asarray(gammas) if int(g) > 0)
                return orig_sp(token, pool, cache_d, bt, pos, active, gammas, *a, **k)

              srv.ops.spec_paged_batch_decode = spy

            async def rnd():
              total = 0

              def emit(rid, toks, finished):
                nonlocal total
                total += len(toks)

              async def one():
                await _asyncio.gather(*(
                  srv.submit(f"{tag}{rid}", p, max_tokens=33, temp=0.0, top_k=35, eos_ids=(), emit=emit)
                  for rid, p in sb_prompts.items()
                ))

              await one()  # warm the admission + chunk programs
              total = 0
              t0 = time.perf_counter()
              await one()
              return total / (time.perf_counter() - t0)

            tok_s = _asyncio.run(rnd())
            srv.shutdown()
            return round(tok_s, 2)

          # The spec token counters are {proposer}-labeled since ISSUE 12;
          # this round's drafting rides the model proposer.
          prop0 = _gm.counter_value("spec_proposed_tokens_total", labels={"proposer": "model"})
          acc0 = _gm.counter_value("spec_accepted_tokens_total", labels={"proposer": "model"})
          spec_batch8_aggregate_tok_s = _bench_spec_batch("s", True)
          prop1 = _gm.counter_value("spec_proposed_tokens_total", labels={"proposer": "model"})
          acc1 = _gm.counter_value("spec_accepted_tokens_total", labels={"proposer": "model"})
          plain_batch8_aggregate_tok_s = _bench_spec_batch("p", False)
          if prop1 > prop0:
            spec_acceptance_rate = round((acc1 - acc0) / (prop1 - prop0), 4)
          if sb_gammas:
            spec_gamma_p50 = int(np.percentile(np.asarray(sb_gammas), 50))
          if spec_batch8_aggregate_tok_s and plain_batch8_aggregate_tok_s:
            spec_batch8_vs_plain8 = gate_spec_batch(round(spec_batch8_aggregate_tok_s / plain_batch8_aggregate_tok_s, 4))
        except Exception:  # noqa: BLE001 — optional section
          pass
        finally:
          sb_eng = None
          for k, v in sb_env.items():
            if v is None:
              os.environ.pop(k, None)
            else:
              os.environ[k] = v
        del echo8, draft1b
      except Exception:  # noqa: BLE001 — optional section
        pass
      del qp8
    except Exception:  # noqa: BLE001 — smaller-HBM devices: skip, don't abort the bench
      int8_8b_tok_s = None

  # --- DRAFT-FREE n-gram speculation A/B (ISSUE 12, behind gate_spec_ngram):
  # a repetition-heavy synthetic workload (per-row periodic prompts — the
  # RAG/code-edit/multi-turn shape where prompt-lookup pays) through the REAL
  # batched scheduler at B=8 on the serving-default layout (paged + int8-KV),
  # n-gram speculation (no draft model loaded, zero draft-KV pages) vs plain
  # back-to-back. The echo model continues each row's periodic stream, so
  # suffix matches fire AND accept — the acceptance criterion is
  # spec_ngram_batch8_vs_plain8 > 1.0 with kv_draft_* gauges at 0. Runs on
  # EVERY round: the proposer is host-side, so the CPU smoke measures a real
  # (tiny-model) A/B instead of emitting null.
  ngb_env = {k: os.environ.get(k) for k in ("XOT_TPU_PAGED", "XOT_TPU_KV_QUANT", "XOT_TPU_SPEC_NGRAM")}
  try:
    import asyncio as _asyncio

    from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer as _BS
    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine as _Eng
    from xotorch_support_jetson_tpu.utils.metrics import metrics as _gm
    from xotorch_support_jetson_tpu.utils.synthetic import peaked_echo_params as _echo_p

    os.environ["XOT_TPU_PAGED"] = "1"
    os.environ["XOT_TPU_KV_QUANT"] = "int8"
    os.environ["XOT_TPU_SPEC_NGRAM"] = "1"
    ng_eng = _Eng(use_local_mesh=False)
    # damp=0.01 on accel for the same reason as the 8B echo pair above (the
    # 16-layer bf16 model only truly echoes at low damp); the tiny CPU
    # config echoes cleanly at the default.
    ng_eng.load_test_model(shard, cfg, _echo_p(params, damp=0.01) if on_accel else _echo_p(params))
    assert ng_eng._draft_params is None  # the round must be draft-free
    ng_rng = np.random.default_rng(17)
    ng_prompts = {}
    for i in range(8):
      pat = ng_rng.integers(1, cfg.vocab_size, (8,)).astype(np.int32)
      ng_prompts[f"ng{i}"] = np.tile(pat, 8)  # 64 tokens, period 8
    ng_tokens = 65 if on_accel else 33

    def _bench_spec_ngram(tag: str, spec_on: bool):
      srv = _BS(ng_eng, n_slots=8, chunk=8, spec_batch=spec_on)

      async def rnd():
        total = 0

        def emit(rid, toks, finished):
          nonlocal total
          total += len(toks)

        async def one():
          await _asyncio.gather(*(
            srv.submit(f"{tag}{rid}", p, max_tokens=ng_tokens, temp=0.0, top_k=35, eos_ids=(), emit=emit)
            for rid, p in ng_prompts.items()
          ))

        await one()  # warm the admission + chunk programs
        total = 0
        t0 = time.perf_counter()
        await one()
        return total / (time.perf_counter() - t0)

      tok_s = _asyncio.run(rnd())
      if spec_on:
        assert srv.spec and srv.draft_cache is None
      srv.shutdown()
      return round(tok_s, 2)

    def _spec_family_by_proposer(name: str) -> dict:
      return {p: _gm.counter_value(name, labels={"proposer": p}) for p in ("model", "ngram")}

    ng_prop0 = _spec_family_by_proposer("spec_proposed_tokens_total")
    ng_acc0 = _spec_family_by_proposer("spec_accepted_tokens_total")
    spec_ngram_batch8_aggregate_tok_s = _bench_spec_ngram("s", True)
    ng_prop1 = _spec_family_by_proposer("spec_proposed_tokens_total")
    ng_acc1 = _spec_family_by_proposer("spec_accepted_tokens_total")
    spec_ngram_plain_batch8_aggregate_tok_s = _bench_spec_ngram("p", False)
    d_prop = {p: ng_prop1[p] - ng_prop0[p] for p in ng_prop0}
    total_prop = sum(d_prop.values())
    if d_prop.get("ngram", 0) > 0:
      spec_ngram_acceptance_rate = round((ng_acc1["ngram"] - ng_acc0["ngram"]) / d_prop["ngram"], 4)
    if total_prop > 0:
      spec_proposer_mix = {p: round(v / total_prop, 4) for p, v in d_prop.items() if v > 0}
    if spec_ngram_batch8_aggregate_tok_s and spec_ngram_plain_batch8_aggregate_tok_s:
      spec_ngram_batch8_vs_plain8 = gate_spec_ngram(
        round(spec_ngram_batch8_aggregate_tok_s / spec_ngram_plain_batch8_aggregate_tok_s, 4)
      )
    ng_eng = None
  except Exception:  # noqa: BLE001 — optional section
    pass
  finally:
    for k, v in ngb_env.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v

  # --- stable-diffusion UNet denoise step (round 4: the image path is real —
  # models/diffusion.py). One classifier-free-guidance step at the SD2-base
  # geometry (865M-param UNet, 64x64 latents, 77x1024 text ctx, bf16): batch
  # 2 through the UNet per step, the MXU-bound core of image generation.
  sd_unet_step_ms = None
  try:
    from xotorch_support_jetson_tpu.models.diffusion import (
      DiffusionConfig,
      alphas_cumprod as sd_alphas,
      sample_chunk,
      tiny_diffusion_config,
    )
    from xotorch_support_jetson_tpu.models.diffusion_loader import init_unet_params

    sd_cfg = DiffusionConfig() if on_accel else tiny_diffusion_config()
    sd_unet = init_unet_params(jax.random.PRNGKey(11), sd_cfg.unet)
    if on_accel:
      sd_unet = jax.tree.map(lambda x: jnp.asarray(x, jnp.bfloat16), sd_unet)
    sd_lat = jnp.zeros((1, sd_cfg.sample_size, sd_cfg.sample_size, sd_cfg.unet.in_channels), jnp.bfloat16 if on_accel else jnp.float32)
    sd_ctx = jnp.zeros((2, 77 if on_accel else 8, sd_cfg.unet.cross_attention_dim), sd_lat.dtype)
    sd_a = np.asarray(sd_alphas(sd_cfg), np.float32)
    n_sd = 8 if on_accel else 2
    ts = np.linspace(900, 100, n_sd).astype(np.int32)
    sd_args = (jnp.asarray(ts), jnp.asarray(sd_a[ts]), jnp.asarray(sd_a[np.clip(ts - 50, 0, None)]))
    sd_fn = jax.jit(lambda p, lat, ctx, t, at, ap: sample_chunk(p, sd_cfg, lat, ctx, t, at, ap, guidance=7.5))
    _ = np.asarray(sd_fn(sd_unet, sd_lat, sd_ctx, *sd_args))  # compile
    t0 = time.perf_counter
    start = t0()
    _ = np.asarray(sd_fn(sd_unet, sd_lat, sd_ctx, *sd_args))
    sd_unet_step_ms = round((t0() - start) * 1000.0 / n_sd, 2)
    del sd_unet, sd_lat, sd_ctx
  except Exception:  # noqa: BLE001 — optional section: skip, don't abort the bench
    sd_unet_step_ms = None

  headline, gate_tripped = gate_headline(tok_per_s, serving_tok_s)

  vs_baseline = None
  int8_vs_prev = None
  ttft_vs_prev = None
  try:  # compare to the previous round's recorded value if the driver left one
    import glob

    hist = sorted(glob.glob("BENCH_r*.json"))
    if hist:
      prev = json.load(open(hist[-1]))
      if "parsed" in prev:  # driver wraps the JSON line under "parsed"
        prev = prev["parsed"]
      denom = plausible_value(prev) if prev.get("unit") == "tokens/s" else None
      if denom:
        vs_baseline = round(headline / denom, 4)
      prev_int8 = prev.get("int8_decode_tok_s")
      prev_serving = prev.get("serving_chunked_tok_s")
      # Same artifact filter as the headline: int8 halves the weight bytes, so
      # a recorded int8 number beyond 4x the record's own serving number is a
      # timing artifact, not a denominator.
      if prev_int8 and prev_serving and float(prev_int8) > 4.0 * float(prev_serving):
        prev_int8 = None
      if int8_tok_s and prev_int8:
        # Regression gate (VERDICT r1 weak #1): flag int8 decode drift
        # round-over-round right in the bench line.
        int8_vs_prev = round(int8_tok_s / float(prev_int8), 4)
      # TTFT drift gate (VERDICT r3 weak #6): same pattern. A recorded TTFT
      # under 40 ms for a 128-token prefill is treated as an artifact, not a
      # denominator.
      prev_ttft = prev.get("ttft_ms_prefill128")
      if prev_ttft and on_accel and float(prev_ttft) < 40.0:
        prev_ttft = None
      if prev_ttft:
        ttft_vs_prev = round(ttft_ms / float(prev_ttft), 4)
  except Exception:  # noqa: BLE001
    pass

  print(
    json.dumps(
      {
        "metric": "decode_tokens_per_sec_llama1b_bf16_1chip" if on_accel else "decode_tokens_per_sec_smoke_cpu",
        "value": round(headline, 2),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "headline_gate_tripped": gate_tripped,
        "headline_spread": headline_spread,
        "serving_chunked_tok_s": round(serving_tok_s, 2),
        "decode_tok_s_ctx32k": ctx32k_tok_s,
        "int8kv_decode_tok_s_ctx32k": int8kv_ctx32k_tok_s,
        "int8_decode_tok_s": int8_tok_s,
        "int4_decode_tok_s": int4_tok_s,
        "batch8_aggregate_tok_s": batch8_tok_s,
        "int8_batch8_aggregate_tok_s": int8_batch8_tok_s,
        "int8_batch16_aggregate_tok_s": int8_batch16_tok_s,
        "int8_int8kv_batch16_aggregate_tok_s": int8_int8kv_batch16_tok_s,
        "int8_int8kv_batch48_aggregate_tok_s": int8_int8kv_batch48_tok_s,
        "int8_w8a8_batch16_aggregate_tok_s": int8_w8a8_batch16_tok_s,
        "paged_batch16_aggregate_tok_s": paged16_tok_s,
        "paged_batch16_int8kv_aggregate_tok_s": paged16_int8kv_tok_s,
        "int8_paged_batch16_int8kv_aggregate_tok_s": int8_paged16_int8kv_tok_s,
        "paged_batch48_aggregate_tok_s": paged48_tok_s,
        "paged_batch48_int8kv_aggregate_tok_s": paged48_int8kv_tok_s,
        "paged_batch48_int4kv_aggregate_tok_s": paged48_int4kv_tok_s,
        "int4kv_batch96_aggregate_tok_s": int4kv_batch96_aggregate_tok_s,
        "paged_vs_dense_ratio": paged_vs_dense_ratio,
        "paged_vs_dense_ratio_b48": paged_vs_dense_ratio_b48,
        "paged_tile_b16_int8kv": paged_tile_b16_int8kv,
        "paged_tile_b48_int8kv": paged_tile_b48_int8kv,
        "paged_tile_b96_int4kv": paged_tile_b96_int4kv,
        "spec_decode_tok_s": spec_tok_s,
        "spec_acceptance": spec_acceptance,
        "spec_vs_plain": spec_vs_plain,
        "spec_peak_tok_s": spec_peak_tok_s,
        "spec_peak_acceptance": spec_peak_acceptance,
        "spec_peak_vs_plain": spec_peak_vs_plain,
        "int8_8b_decode_tok_s": int8_8b_tok_s,
        "spec_8b_draft1b_tok_s": spec_8b_draft1b_tok_s,
        "spec_8b_draft1b_acceptance": spec_8b_draft1b_acceptance,
        "spec_8b_draft1b_vs_plain8b": spec_8b_draft1b_vs_plain8b,
        "spec_batch8_aggregate_tok_s": spec_batch8_aggregate_tok_s,
        "plain_batch8_aggregate_tok_s": plain_batch8_aggregate_tok_s,
        "spec_batch8_vs_plain8": spec_batch8_vs_plain8,
        "spec_acceptance_rate": spec_acceptance_rate,
        "spec_gamma_p50": spec_gamma_p50,
        "spec_ngram_batch8_aggregate_tok_s": spec_ngram_batch8_aggregate_tok_s,
        "spec_ngram_plain_batch8_aggregate_tok_s": spec_ngram_plain_batch8_aggregate_tok_s,
        "spec_ngram_batch8_vs_plain8": spec_ngram_batch8_vs_plain8,
        "spec_ngram_acceptance_rate": spec_ngram_acceptance_rate,
        "spec_proposer_mix": spec_proposer_mix,
        "spec_policy_model_collapse_switches_to": spec_policy_model_collapse_switches_to,
        "spec_policy_exhausted_falls_back_to": spec_policy_exhausted_falls_back_to,
        "spec_policy_reprobe_prefers": spec_policy_reprobe_prefers,
        "sd_unet_step_ms": sd_unet_step_ms,
        "int8_vs_prev": int8_vs_prev,
        "pp_decode_tok_s": pp_decode_tok_s,
        "pp_batched_aggregate_tok_s": pp_batched_tok_s,
        "hop_serialize_ms_p50": hop_serialize_ms_p50,
        "hop_rpc_ms_p50": hop_rpc_ms_p50,
        "failover_recovery_ms_p50": failover_recovery_ms_p50,
        "requests_lost": requests_lost,
        "disagg_ttft_ms_p50": disagg_ttft_ms_p50,
        "disagg_vs_colocated_itl_p50": disagg_vs_colocated_itl_p50,
        "kv_stream_gbps": kv_stream_gbps,
        "mixed_resident_itl_ms": mixed_resident_itl_ms,
        "alternating_resident_itl_ms": alternating_resident_itl_ms,
        "mixed_resident_itl_ms_p50": mixed_resident_itl_ms_p50,
        "alternating_resident_itl_ms_p50": alternating_resident_itl_ms_p50,
        "mixed_vs_alternating_itl": mixed_vs_alternating_itl,
        "mixed_ttft_ms_p50": mixed_ttft_ms_p50,
        "alternating_ttft_ms_p50": alternating_ttft_ms_p50,
        "lora_mixed_batch8_vs_base8": lora_mixed_batch8_vs_base8,
        "lora_swap_ms_p50": lora_swap_ms_p50,
        "lora_mixed_batch8_aggregate_tok_s": lora_mixed_batch8_aggregate_tok_s,
        "lora_base_batch8_aggregate_tok_s": lora_base_batch8_aggregate_tok_s,
        "router_affine_vs_random_ttft_p50": router_affine_vs_random_ttft_p50,
        "router_prefix_hit_rate": router_prefix_hit_rate,
        "router_failover_ms_p50": router_failover_ms_p50,
        "router_affine_ttft_ms_p50": router_affine_ttft_ms_p50,
        "router_random_ttft_ms_p50": router_random_ttft_ms_p50,
        "ttft_ms_prefill128": round(ttft_ms, 2),
        "ttft_ms_spread": round(ttft_spread_ms, 2),
        "ttft_vs_prev": ttft_vs_prev,
        "ttft_ms_batch8_p50": ttft_batch8_p50_ms,
        "ttft_ms_batch8_p95": ttft_batch8_p95_ms,
        "ttft_ms_batch8_max": ttft_batch8_max_ms,
        "itl_ms_p50": itl_p50_ms,
        "itl_ms_p99": itl_p99_ms,
        "batch48_lookahead_vs_sync": batch48_lookahead_vs_sync,
        "lookahead48_aggregate_tok_s": lookahead48_aggregate_tok_s,
        "sync48_aggregate_tok_s": sync48_aggregate_tok_s,
        "sched_host_gap_ms_p50": sched_host_gap_ms_p50,
        "sched_host_gap_sync_ms_p50": sched_host_gap_sync_ms_p50,
        "overload_shed_rate": overload_shed_rate,
        "ttft_ms_p99_interactive_overload": ttft_ms_p99_interactive_overload,
        "ttft_ms_p99_batch_overload": ttft_ms_p99_batch_overload,
        "slo_attainment_interactive": slo_attainment_interactive,
        "goodput_ratio": goodput_ratio,
        "flightrec_events_per_sec": flightrec_events_per_sec,
        "flightrec_overhead_ratio": flightrec_overhead_ratio,
        "kv_spill_gbps": kv_spill_gbps,
        "kv_restore_gbps": kv_restore_gbps,
        "kv_stream_gbps_int4": kv_stream_gbps_int4,
        "open_sessions_per_node": open_sessions_per_node,
        "preempt_resume_ms_recompute": preempt_resume_ms_recompute,
        "preempt_resume_ms_restore": preempt_resume_ms_restore,
        "preempt_resume_ms_recompute_vs_restore": preempt_resume_ms_recompute_vs_restore,
        "steady_state_compiles": gate_compile(steady_state_compiles),
        "warmup_compile_s_total": gate_compile(warmup_compile_s_total, lo=0.0, hi=3600.0),
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "device": str(jax.devices()[0]),
        "n_decode": n_decode,
      }
    )
  )


if __name__ == "__main__":
  main()
