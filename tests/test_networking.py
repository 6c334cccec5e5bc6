"""Distributed-in-one-process integration tests (reference test strategy §4):
real gRPC servers + real Nodes with dummy engines on localhost — multi-node
pipeline generation without a real cluster. Plus manual-discovery hot-reload.
"""

import asyncio
import json

import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.dummy_engine import DUMMY_EOS, DummyInferenceEngine
from xotorch_support_jetson_tpu.networking.discovery import Discovery
from xotorch_support_jetson_tpu.networking.grpc.grpc_peer_handle import GRPCPeerHandle
from xotorch_support_jetson_tpu.networking.grpc.grpc_server import GRPCServer
from xotorch_support_jetson_tpu.networking.grpc.serialization import (
  proto_to_state,
  proto_to_tensor,
  state_to_proto,
  tensor_to_proto,
)
from xotorch_support_jetson_tpu.networking.manual.manual_discovery import ManualDiscovery
from xotorch_support_jetson_tpu.networking.manual.network_topology_config import NetworkTopology
from xotorch_support_jetson_tpu.orchestration.node import Node
from xotorch_support_jetson_tpu.registry import build_base_shard
from xotorch_support_jetson_tpu.inference.state import InferenceState
from xotorch_support_jetson_tpu.topology.device_capabilities import DeviceCapabilities, DeviceFlops
from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
from xotorch_support_jetson_tpu.utils.helpers import find_available_port


def test_tensor_proto_roundtrip_preserves_dtype():
  import ml_dtypes

  for dtype in (np.float32, np.int32, ml_dtypes.bfloat16):
    arr = np.arange(12, dtype=dtype).reshape(3, 4)
    rt = proto_to_tensor(tensor_to_proto(arr))
    assert rt.dtype == arr.dtype
    np.testing.assert_array_equal(np.asarray(rt, np.float64), np.asarray(arr, np.float64))
  assert proto_to_tensor(tensor_to_proto(None)) is None


def test_state_proto_roundtrip():
  state = InferenceState(tokens=np.array([[1, 2, 3]], np.int32), curr_pos=3, prompt_len=3, extras={"k": 1})
  rt = proto_to_state(state_to_proto(state))
  np.testing.assert_array_equal(rt.tokens, state.tokens)
  assert rt.curr_pos == 3 and rt.prompt_len == 3 and rt.extras == {"k": 1}


class StaticDiscovery(Discovery):
  def __init__(self, peers):
    self._peers = peers

  async def start(self):
    pass

  async def stop(self):
    pass

  async def discover_peers(self, wait_for_peers: int = 0):
    return self._peers


CAPS = DeviceCapabilities(model="test", chip="cpu", memory=1024, flops=DeviceFlops(1, 2, 4))


async def _make_cluster(n=2):
  """n Nodes with dummy engines, real gRPC servers, statically discovered."""
  ports = [find_available_port("127.0.0.1") for _ in range(n)]
  ids = [f"node{i}" for i in range(n)]
  nodes = []
  servers = []
  for i in range(n):
    peers = [GRPCPeerHandle(ids[j], f"127.0.0.1:{ports[j]}", "test", CAPS) for j in range(n) if j != i]
    node = Node(
      ids[i],
      None,  # server set below
      DummyInferenceEngine(),
      StaticDiscovery(peers),
      None,
      RingMemoryWeightedPartitioningStrategy(),
      max_generate_tokens=200,
    )
    server = GRPCServer(node, "127.0.0.1", ports[i])
    node.server = server
    nodes.append(node)
    servers.append(server)
  await asyncio.gather(*(node.start() for node in nodes))
  # Placement is eventually consistent (views converge via the 2s collection
  # loop; reference §5.3 has the same property). Wait until every node sees
  # the full membership and computes an n-way partition before using the ring.
  from xotorch_support_jetson_tpu.topology.partitioning import map_partitions_to_shards

  for _ in range(100):
    converged = True
    for node in nodes:
      parts = node.partitioning_strategy.partition(node.topology)
      shards = map_partitions_to_shards(parts, 8, "dummy")
      if len(node.topology.nodes) != n or len(shards) != n:
        converged = False
    if converged:
      break
    await asyncio.gather(*(node.collect_topology(set()) for node in nodes))
    await asyncio.sleep(0.05)
  return nodes


@pytest.mark.asyncio
async def test_two_node_grpc_pipeline_generation():
  nodes = await _make_cluster(2)
  try:
    # Both nodes see both in the topology.
    assert set(nodes[0].topology.nodes) == {"node0", "node1"}
    assert set(nodes[1].topology.nodes) == {"node0", "node1"}

    shard = build_base_shard("dummy", "DummyInferenceEngine")
    done = asyncio.Event()
    collected = []

    def on_tok(rid, tokens, finished):
      collected.extend(tokens)
      if finished:
        done.set()

    # Listen on node1 — tokens are sampled wherever the last shard lives and
    # broadcast to all peers via SendResult.
    nodes[0].on_token.register("t0").on_next(on_tok)
    await nodes[0].process_prompt(shard, "aaaa", "req-dist")
    await asyncio.wait_for(done.wait(), timeout=30)
    assert collected[-1] == DUMMY_EOS
    assert collected == list(range(5, DUMMY_EOS + 1))

    # Data-plane RPC telemetry: the ring traffic that just flowed is counted
    # per method in the metrics registry (networking/grpc/grpc_server.py).
    from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

    assert gm.counter_value("grpc_rpcs_total", labels={"method": "SendResult"}) >= 1

    # Cluster-scope aggregation over the REAL gRPC opaque-status channel:
    # each node answers the pull with its registry snapshot.
    snaps = await nodes[0].collect_cluster_metrics(timeout=5.0)
    assert len(snaps) == 1
    assert "counters" in snaps[0] and "histograms" in snaps[0]
  finally:
    for node in nodes:
      await node.stop()


@pytest.mark.asyncio
async def test_two_node_cluster_scope_timeline_with_skew():
  """ISSUE 4 acceptance: a request crosses the real two-node gRPC ring while
  node1's monotonic clock is synthetically skewed +50 ms; the HealthCheck
  clock echo estimates the offset (correctly signed), and
  ``GET /v1/requests/{id}/timeline?scope=cluster`` returns ONE merged
  timeline whose hop entries carry compute/serialize/wire/deserialize
  attribution and whose cross-node ordering is monotonic after offset
  normalization — paired hops land within the RPC window, not 50 ms out."""
  from aiohttp.test_utils import TestClient, TestServer

  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.orchestration.clocksync import clock_sync
  from xotorch_support_jetson_tpu.orchestration.tracing import set_test_skew

  SKEW_MS = 50.0
  nodes = await _make_cluster(2)
  set_test_skew("node1", int(SKEW_MS * 1e6))
  client = None
  try:
    # Fresh skewed estimates (the convergence loop above may have seeded
    # pre-skew samples through the periodic clock-sync pass).
    clock_sync.forget("node0")
    clock_sync.forget("node1")
    # One echo's offset is off by up to rtt / 2, and the marks ordered below are 1-2 ms apart: an echo taken while
    # the suite's other workers hold the cores flipped them (ROADMAP D18). The estimate is an EWMA (alpha 0.2), so it
    # is given the echoes an EWMA needs: after 16 the first one's share is 0.8^15, 3.5 %, and the rest average out.
    for _ in range(16):
      await asyncio.gather(*(peer.health_check() for peer in nodes[0].peers))
    est = clock_sync.estimate("node1")
    assert est is not None and est.samples >= 16
    assert SKEW_MS - 10 < est.offset_ns / 1e6 < SKEW_MS + 10  # correctly signed: node1 AHEAD

    shard = build_base_shard("dummy", "DummyInferenceEngine")
    done = asyncio.Event()
    nodes[0].on_token.register("tl").on_next(lambda rid, toks, fin: done.set() if fin else None)
    await nodes[0].process_prompt(shard, "aaaa", "req-cluster-tl")
    await asyncio.wait_for(done.wait(), timeout=30)

    api = ChatGPTAPI(nodes[0], "DummyInferenceEngine", response_timeout=30, default_model="dummy")
    client = TestClient(TestServer(api.app))
    await client.start_server()

    resp = await client.get("/v1/requests/req-cluster-tl/timeline", params={"scope": "cluster"})
    assert resp.status == 200, await resp.text()
    tl = await resp.json()
    assert tl["scope"] == "cluster"
    assert set(tl["nodes"]) == {"node0", "node1"}
    assert 40 < tl["offsets"]["node1"]["offset_ms"] < 60

    # Both nodes contributed events, labeled with their node id.
    event_nodes = {e["node"] for e in tl["events"]}
    assert {"node0", "node1"} <= event_nodes

    # Both directions of the ring produced PAIRED hops (client + server
    # sides matched by hop id) with the full attribution split.
    paired = [h for h in tl["hops"] if h["from"] and h["to"] and h["recv_at_ms"] is not None]
    assert any(h["from"] == "node0" and h["to"] == "node1" for h in paired)
    assert any(h["from"] == "node1" and h["to"] == "node0" for h in paired)
    for h in paired:
      assert h["serialize_ms"] is not None and h["rpc_ms"] is not None, h
      assert h["deserialize_ms"] is not None and h["handler_ms"] is not None, h
      assert h["wire_ms"] is not None and h["compute_ms"] is not None, h
      assert h["payload_bytes"] and h["payload_bytes"] > 0, h
      # Monotonic after normalization: the server-side arrival sits inside
      # the client's RPC window (± the estimate's error bound, itself ≪ the
      # injected skew). Uncorrected, one ring direction would be ~50 ms out.
      delta = h["recv_at_ms"] - h["at_ms"]
      assert -15.0 < delta < SKEW_MS / 2, (h["from"], h["to"], h["method"], delta)

    # The whole-event stream is ordered (merge sorts by normalized time) and
    # the origin's queued mark comes first.
    at = [e["at_ms"] for e in tl["events"]]
    assert at == sorted(at)
    assert tl["events"][0]["stage"] == "queued" and tl["events"][0]["node"] == "node0"

    # Local scope still serves the single-node view with hop detail.
    resp = await client.get("/v1/requests/req-cluster-tl/timeline")
    assert resp.status == 200
    local_tl = await resp.json()
    assert local_tl["hops"] and "hop_agg" in local_tl

    # Unknown request: 404 on cluster scope too.
    resp = await client.get("/v1/requests/nope/timeline", params={"scope": "cluster"})
    assert resp.status == 404
  finally:
    set_test_skew("node1", None)
    clock_sync.forget("node0")
    clock_sync.forget("node1")
    if client is not None:
      await client.close()
    for node in nodes:
      await node.stop()


@pytest.mark.asyncio
async def test_grpc_health_check_and_failure():
  nodes = await _make_cluster(2)
  try:
    peer = nodes[0].peers[0]
    assert await peer.health_check()
    # Kill node1's server: health check must fail.
    await nodes[1].server.stop()
    await peer.disconnect()
    assert not await peer.health_check()
  finally:
    await nodes[0].stop()
    await nodes[1].discovery.stop()


@pytest.mark.asyncio
async def test_manual_discovery_hot_reload(tmp_path):
  """Config edits are picked up without restart (reference :46-101)."""
  port = find_available_port("127.0.0.1")

  class _StubNode:
    pass

  node = Node(
    "peer1",
    None,
    DummyInferenceEngine(),
    StaticDiscovery([]),
    None,
    RingMemoryWeightedPartitioningStrategy(),
  )
  server = GRPCServer(node, "127.0.0.1", port)
  node.server = server
  await node.start()

  config = {"peers": {"peer1": {"address": "127.0.0.1", "port": port, "device_capabilities": CAPS.to_dict()}}}
  config_path = tmp_path / "topology.json"
  config_path.write_text(json.dumps({"peers": {}}))

  discovery = ManualDiscovery(
    str(config_path),
    "me",
    create_peer_handle=lambda pid, addr, desc, caps: GRPCPeerHandle(pid, addr, desc, caps),
    poll_interval=0.2,
  )
  await discovery.start()
  try:
    assert await discovery.discover_peers() == []
    config_path.write_text(json.dumps(config))
    for _ in range(50):
      peers = await discovery.discover_peers()
      if peers:
        break
      await asyncio.sleep(0.1)
    assert len(peers) == 1 and peers[0].id() == "peer1"

    # Remove the peer again — eviction on next poll.
    config_path.write_text(json.dumps({"peers": {}}))
    for _ in range(50):
      peers = await discovery.discover_peers()
      if not peers:
        break
      await asyncio.sleep(0.1)
    assert peers == []
  finally:
    await discovery.stop()
    await node.stop()


def test_network_topology_config_validation(tmp_path):
  bad = tmp_path / "bad.json"
  bad.write_text("{not json")
  with pytest.raises(ValueError):
    NetworkTopology.from_path(str(bad))
  missing_field = tmp_path / "missing.json"
  missing_field.write_text(json.dumps({"peers": {"a": {"address": "1.2.3.4"}}}))
  with pytest.raises(ValueError):
    NetworkTopology.from_path(str(missing_field))
  with pytest.raises(FileNotFoundError):
    NetworkTopology.from_path(str(tmp_path / "nope.json"))
