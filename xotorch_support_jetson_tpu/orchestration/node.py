"""The cluster node: request routing, ring pipeline, topology management.

Behavioral parity with reference ``orchestration/node.py`` (process_prompt
:149-208, process_inference_result :109-147, process_tensor :347-380,
forward_* :382-443, partition/shard resolution :445-460, update_peers
:462-511, collect_topology :533-566, broadcasts :580-607, periodic collection
:520-531, training ring :210-345). Notable deltas, all deliberate:

- The engine returns *already-gathered* ``[B, vocab]`` logits on the last
  shard (no padded [B,S,V] on the wire) and O(1) inference state
  (inference/state.py) — the reference reserialized the full mask per hop.
- ``engine.train/evaluate`` actually exist here (the reference called
  methods its engines never implemented — SURVEY.md §2.2).
- Placement stays deterministic-given-topology (memory-weighted ring,
  topology/partitioning.py), so peers agree without consensus.
"""

from __future__ import annotations

import asyncio
import json
import os
import time
import traceback
import uuid

import numpy as np

from ..inference import sched_admission
from ..inference.engine import InferenceEngine, RequestMigratedError
from ..inference.kv_tier import prefix_registry
from ..inference.shard import Shard
from ..inference.state import InferenceState
from ..networking.discovery import Discovery
from ..networking.peer_handle import PeerHandle
from ..networking.retry import breakers, peer_health
from ..topology.device_capabilities import UNKNOWN_DEVICE_CAPABILITIES, device_capabilities
from ..topology.partitioning import PartitioningStrategy, map_partitions_to_shards
from ..topology.topology import Topology
from ..utils.helpers import DEBUG, AsyncCallbackSystem
from ..utils.metrics import metrics
from .. import registry
from .clocksync import clock_sync
from .flightrec import assemble_local_bundle, flightrec
from .slo import merge_slo_reports, slo_enabled, slo_engine
from .tracing import merge_cluster_timeline, tracer


# How long per-request bookkeeping (cancel flags, dedup tombstones) outlives
# its request: must cover the API's response timeout (chatgpt_api.py, 900 s)
# so zombie broadcasts arriving within any live client's window stay deduped.
RESPONSE_TIMEOUT_HORIZON_S = 900.0


def _resume_tokens_of(state: InferenceState | None) -> list | None:
  """API-level resume payload (ISSUE 13): tokens a router carried over from
  a failed replica, to be absorbed into the prompt (carry semantics)."""
  if state is None:
    return None
  toks = state.extras.get("resume_tokens")
  return list(toks) if toks else None

# A held ahead-of-mark chunk waits this long for the gap to fill before the
# stream force-flushes in position order: one LOST broadcast RPC then costs a
# visible gap after a short stall instead of hanging the client forever.
GAP_FLUSH_S = 5.0

# How long a peer's "node_draining" announcement keeps it out of partition
# maps before expiring: covers the drain window with margin, and bounds the
# blast radius of a node that announced drain but then kept running (e.g. a
# cancelled shutdown, or a restart reusing the id before re-announcing).
DRAINING_TTL_S = 180.0


class Node:
  def __init__(
    self,
    _id: str,
    server,
    inference_engine: InferenceEngine,
    discovery: Discovery,
    shard_downloader,
    partitioning_strategy: PartitioningStrategy,
    max_generate_tokens: int = 10000,
    default_sample_temp: float = 0.6,
    default_sample_top_k: int = 35,
    topology_viz=None,
  ) -> None:
    self.id = _id
    self.inference_engine = inference_engine
    self.server = server
    self.discovery = discovery
    self.shard_downloader = shard_downloader
    self.partitioning_strategy = partitioning_strategy
    self.max_generate_tokens = max_generate_tokens
    self.default_sample_temp = default_sample_temp
    self.default_sample_top_k = default_sample_top_k
    self.topology_viz = topology_viz

    self.peers: list[PeerHandle] = []
    self.topology: Topology = Topology()
    self.device_capabilities = UNKNOWN_DEVICE_CAPABILITIES
    self.buffered_token_output: dict[str, tuple[list[int], bool]] = {}
    self.request_options: dict[str, dict] = {}
    self.cancelled_requests: set[str] = set()
    self._replay_attempts: dict[str, int] = {}
    self._replay_pending: set[str] = set()  # requests with a replay in flight (coalesce concurrent failure reports)
    self._replay_lifetime: dict[str, int] = {}  # total replays per request (never resets; termination backstop)
    # Client-stream replay dedup (VERDICT r2 #5): every token delivery
    # carries the absolute completion index of its first token; a receiver
    # delivers only tokens at/above its high-water mark, so a failover that
    # regenerates an already-streamed span (prompt-level replay, or a
    # zombie broadcast racing the retry) can never duplicate the client
    # transcript. ``_emitted_counts`` is the per-request high-water mark;
    # ``_completion_offset`` maps a generation node's LOCAL buffer index to
    # the absolute index (non-zero only after adopting a token-level replay
    # whose history predates this node's buffer); ``_seen_epochs`` detects a
    # bumped replay_epoch so a surviving node resets its stale local buffer.
    self._emitted_counts: dict[str, int] = {}
    self._pending_chunks: dict[str, dict[int, tuple[list[int], bool]]] = {}  # ahead-of-mark deliveries held for in-order release
    self._gap_flush_timers: dict[str, asyncio.TimerHandle] = {}  # armed gap-flush timers per request
    self._completion_offset: dict[str, int] = {}
    self._seen_epochs: dict[str, int] = {}
    self.buffered_inputs: dict[str, list] = {}
    self.checkpoints: dict[str, dict[str, int]] = {}
    self.outstanding_requests: dict[str, str] = {}
    # Ahead-of-time ring HBM validation cache: (fingerprint, problems) for
    # the last (model, partition-map) checked — a topology change (peer
    # joins/leaves, probed memory update) changes the fingerprint, so the
    # ring re-plans automatically (parallel/hbm_planner.ring_partition_fits).
    self._ring_budget_cache: tuple | None = None

    # Per-request submit time (TTFT histogram for the plain serving path; the
    # batch scheduler measures its own from submit-to-first-emit).
    self._request_t0: dict[str, float] = {}
    self._ttft_observed: set[str] = set()
    # Cluster metrics pulls in flight: nonce -> [event, snapshots, expected].
    self._metrics_waiters: dict[str, list] = {}
    # Cluster timeline pulls in flight: nonce -> [event, fragments, expected].
    self._timeline_waiters: dict[str, list] = {}
    # Cluster prefix-registry pulls in flight: nonce -> [event, replies, expected].
    self._prefix_waiters: dict[str, list] = {}
    # Cluster SLO-report pulls in flight: nonce -> [event, reports, expected].
    self._slo_waiters: dict[str, list] = {}
    # Cluster incident-bundle pulls in flight: nonce -> [event, parts, expected].
    self._bundle_waiters: dict[str, list] = {}
    # Cluster program-ledger pulls in flight: nonce -> [event, snapshots, expected].
    self._programs_waiters: dict[str, list] = {}

    # Fault-tolerance state (ISSUE 8). ``draining`` marks THIS node as
    # shutting down (no new work; resident batched rows migrate);
    # ``_draining_peers`` maps announced-draining peer ids to their expiry
    # (they drop out of partition maps so no new work lands on them);
    # ``_migrated`` holds per-request finish events for rows shipped to a
    # surviving peer; ``_recovering`` tracks requests that entered replay or
    # migration, counted as recovered when they still finish;
    # ``_batched_shards`` remembers each batched request's base shard so a
    # drain can re-route it.
    self.draining = False
    self._draining_peers: dict[str, float] = {}
    self._migrated: dict[str, asyncio.Event] = {}
    self._recovering: set[str] = set()
    self._batched_shards: dict[str, Shard] = {}
    # Disaggregated prefill/decode (ISSUE 10). ``_disagg_stats`` caches each
    # peer's latest role/capacity advert (``disagg_pull``/``disagg_stats``
    # over the opaque-status channel — the metrics_pull pattern) for the
    # placement policy; ``_disagg_waiters`` holds pulls in flight;
    # ``_kv_stream_tasks`` tracks per-request mid-prefill KV-page transfer
    # tasks so the decode handoff can flush them (adoption must precede the
    # decode node's admission); ``_kv_stream_seq`` numbers a request's
    # batches for the receive side's telemetry.
    # This node's role, initialized from XOT_TPU_ROLE (tests — and a future
    # control plane — may override per node: two in-process nodes share the
    # env).
    self.disagg_role = sched_admission.node_role()
    self._disagg_stats: dict[str, dict] = {}
    self._disagg_stats_ts: float = 0.0
    self._disagg_waiters: dict[str, list] = {}
    self._kv_stream_tasks: dict[str, list] = {}
    self._kv_stream_seq: dict[str, int] = {}
    # Monotonic time of the last peer LOSS (eviction of a removed peer).
    # The stall watchdog's fault predicate needs this to stay truthful
    # AFTER eviction: the damped eviction also forgets the dead peer's
    # breaker/health state, so without a sticky loss mark a stall detected
    # post-eviction would look "healthy" and hang to the response timeout.
    self.last_peer_loss_ts: float | None = None

    self._on_token: AsyncCallbackSystem[str, str, list, bool] = AsyncCallbackSystem()
    self._on_opaque_status: AsyncCallbackSystem[str, str, str] = AsyncCallbackSystem()
    self._on_opaque_status.register("node_status").on_next(self.on_node_status)
    self.node_download_progress: dict[str, dict] = {}
    self.topology_inference_engines_pool: list[list[str]] = []
    self._topology_task: asyncio.Task | None = None

  # ------------------------------------------------------------- lifecycle

  async def start(self, wait_for_peers: int = 0) -> None:
    self.device_capabilities = await device_capabilities()
    # Role gauge (ISSUE 10): 0 = both (colocated), 1 = prefill, 2 = decode —
    # dashboards see the disaggregation topology without scraping env vars.
    metrics.set_gauge("node_role", {"both": 0, "prefill": 1, "decode": 2}.get(self.disagg_role, 0))
    await self.server.start()
    await self.discovery.start()
    await self.update_peers(wait_for_peers)
    await self.collect_topology(set())
    if DEBUG >= 2:
      print(f"[node {self.id}] collected topology: {self.topology}")
    self._topology_task = asyncio.create_task(self.periodic_topology_collection(2.0))

  async def stop(self) -> None:
    if self._topology_task is not None:
      self._topology_task.cancel()
      try:
        await self._topology_task
      except asyncio.CancelledError:
        pass
    await self.discovery.stop()
    await self.server.stop()

  # ------------------------------------------------- graceful drain (ISSUE 8)

  async def announce_shutdown(self) -> None:
    """Tell every peer this node is draining: they drop it from partition
    maps (no new work placed here) while keeping the peer handle alive for
    in-flight traffic and migration RPCs."""
    self.draining = True
    await self.broadcast_opaque_status(
      "", json.dumps({"type": "node_draining", "node_id": self.id})
    )

  async def graceful_drain(self, drain_s: float | None = None, force: asyncio.Event | None = None) -> None:
    """SIGTERM path (main.py): stop taking new work, migrate the batched
    scheduler's resident rows to a surviving peer via ``carry_tokens``
    resume, and wait — up to the drain deadline — for outstanding work
    (local rows that could not migrate finish locally; migrated streams
    relay their remote tokens through this node's API). ``force`` (a second
    signal) aborts the wait immediately. Does NOT stop the node: the
    caller's shutdown sequence owns that."""
    if drain_s is None:
      try:
        drain_s = float(os.getenv("XOT_TPU_DRAIN_S", "20") or 20)
      except ValueError:
        drain_s = 20.0
    server = getattr(self.inference_engine, "_batched_server", None)
    if server is not None and hasattr(server, "begin_drain"):
      # Flag first (synchronous), THEN announce: the scheduler stops
      # admitting in the same event-loop turn, so no row can slip in
      # between the announcement and the drain gate. Migration is offered
      # only when a survivor exists RIGHT NOW — on a single-node deployment
      # extracting every row just to re-enqueue it locally would force a
      # pointless full re-prefill per in-flight request.
      _topo, parts = self._surviving_partitions()
      server.begin_drain(self._migrate_batched_row if parts else None, deadline_s=drain_s)
    await self.announce_shutdown()
    loop = asyncio.get_event_loop()
    deadline = loop.time() + drain_s
    while loop.time() < deadline and not (force is not None and force.is_set()):
      busy = bool(self.outstanding_requests) or bool(self._migrated)
      if server is not None and hasattr(server, "busy"):
        busy = busy or server.busy()
      if not busy:
        break
      await asyncio.sleep(0.1)

  def _surviving_partitions(self):
    """Partition map over the topology EXCLUDING this (draining) node and
    any peer that announced its own drain — where migrated work may land."""
    topo = Topology()
    for nid, caps in self.topology.nodes.items():
      if nid == self.id or self._peer_draining(nid):
        continue
      topo.update_node(nid, caps)
    if not topo.nodes:
      return None, None
    return topo, self.partitioning_strategy.partition(topo)

  async def _migrate_batched_row(self, req) -> bool:
    """Scheduler drain callback: re-submit one extracted batched row to a
    surviving peer as a ``carry_tokens`` resume over the existing gRPC path
    (``req.tokens`` is prompt ++ generated; the wire history keeps budget
    and absolute stream positions exact, so the receiver's continuation is
    token-identical and the origin's high-water dedup splices it seamlessly).
    Returns False (the row finishes locally) when no survivor is reachable."""
    request_id = req.request_id
    base_shard = self._batched_shards.get(request_id)
    if base_shard is None:
      return False
    _topo, partitions = self._surviving_partitions()
    if not partitions:
      return False
    target_id = partitions[0].node_id  # the survivors' layer-0 owner
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if peer is None:
      return False
    next_shard = map_partitions_to_shards(partitions, base_shard.n_layers, base_shard.model_id)[0]
    tokens = np.asarray(req.tokens, dtype=np.int32).reshape(1, -1)
    # The ORIGINAL prompt length keeps the receiver's max_tokens budget and
    # absolute positions exact (req.tokens already absorbed the generated
    # stream; carry_tokens is exactly that generated span).
    orig_len = int(tokens.shape[1]) - len(req.carry_tokens)
    epoch = self._seen_epochs.get(request_id, 0) + 1
    self._seen_epochs[request_id] = epoch
    state = InferenceState(
      tokens=tokens.copy(), prompt_len=int(tokens.shape[1]),
      extras={"replay_epoch": epoch, "orig_prompt_len": orig_len},
    )
    # Register the finish waiter BEFORE the forward: the remote finish
    # broadcast must not race the registration.
    self._migrated[request_id] = asyncio.Event()
    self._recovering.add(request_id)
    try:
      await peer.send_tensor(next_shard, tokens, request_id, self._stash_options(request_id, state))
    except asyncio.TimeoutError:
      # The wait expired (a deadline-capped SendTensor) but the wire may
      # have DELIVERED — the survivor could already be generating. Treating
      # this as not-delivered would re-run the row locally: two generators
      # racing the client stream (at-least-once; sampled streams corrupt).
      # Prefer at-most-once: consider it shipped — if it was truly lost,
      # the stall watchdog converts the silence into a structured
      # retryable 503 instead of a corrupted transcript.
      if DEBUG >= 1:
        print(f"[node {self.id}] drain migration of {request_id}: send timed out after delivery window; assuming shipped")
    except Exception:  # noqa: BLE001 — survivor unreachable: finish locally
      self._migrated.pop(request_id, None)
      self._recovering.discard(request_id)
      if DEBUG >= 1:
        print(f"[node {self.id}] drain migration of {request_id} to {target_id} failed")
      return False
    metrics.inc("drain_migrations_total")
    tracer.stage(request_id, "migrated", {
      "to": target_id, "carried_tokens": len(req.carry_tokens), "prompt_len": orig_len,
    }, node=self.id)
    if DEBUG >= 1:
      print(f"[node {self.id}] migrated {request_id} to {target_id} ({len(req.carry_tokens)} tokens carried)")
    return True

  def _peer_draining(self, node_id: str) -> bool:
    expiry = self._draining_peers.get(node_id)
    if expiry is None:
      return False
    if time.monotonic() > expiry:
      del self._draining_peers[node_id]
      return False
    return True

  # ------------------------------------- disaggregated prefill/decode (ISSUE 10)

  def _disagg_local_stats(self) -> dict:
    """This node's role/capacity advert for the placement policy: free
    pages + queue depth place decode work; the QoS deadline estimator's
    queue-drain number places prefill work (inference/sched_admission.py)."""
    st: dict = {"node_id": self.id, "role": self.disagg_role, "draining": bool(self.draining)}
    server = getattr(self.inference_engine, "_batched_server", None)
    if server is not None:
      alloc = getattr(server, "allocator", None)
      if alloc is not None:
        st["free_pages"] = int(alloc.n_available)
      st["queue_depth"] = int(server.queue.qsize() + len(server._parked))
      st["slots_free"] = sum(1 for s in server.slots if s is None)
      if server.qos is not None:
        est = server.qos.estimate_completion_ms(queue_depth=st["queue_depth"], n_slots=server.n_slots, max_tokens=1)
        if est is not None:
          st["est_drain_ms"] = round(float(est), 1)
    return st

  async def collect_disagg_stats(self, timeout: float = 1.0) -> dict[str, dict]:
    """Refresh the peer role/capacity cache over the opaque-status channel
    (the ``metrics_pull`` pattern: broadcast ``disagg_pull``, peers reply
    ``disagg_stats``). The broadcast is a background task — a dead peer
    must not stall placement past ``timeout`` (its stale advert ages out of
    the cache instead)."""
    if not self.peers:
      return {}
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._disagg_waiters[nonce] = waiter
    bcast = asyncio.create_task(self.broadcast_opaque_status(
      "", json.dumps({"type": "disagg_pull", "node_id": self.id, "nonce": nonce})
    ))
    try:
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # place with whatever adverts arrived
      self._disagg_stats_ts = time.monotonic()
      return dict(self._disagg_stats)
    finally:
      self._disagg_waiters.pop(nonce, None)
      bcast.cancel()

  async def _disagg_stats_fresh(self, max_age_s: float = 5.0, timeout: float = 1.0) -> dict[str, dict]:
    if self._disagg_stats and time.monotonic() - self._disagg_stats_ts <= max_age_s:
      return dict(self._disagg_stats)
    return await self.collect_disagg_stats(timeout=timeout)

  def _handle_disagg_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "disagg_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      reply = json.dumps({
        "type": "disagg_stats",
        "node_id": self.id,
        "nonce": status_data.get("nonce", ""),
        "stats": self._disagg_local_stats(),
      })
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        async def send():
          try:
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — adverts are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] disagg stats reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "disagg_stats":
      sender = status_data.get("node_id")
      if sender == self.id:
        return
      st = status_data.get("stats") or {}
      self._disagg_stats[str(sender)] = st
      waiter = self._disagg_waiters.get(status_data.get("nonce", ""))
      if waiter is not None:
        waiter[1].append((sender, st))
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  async def _disagg_decode_target(self) -> str | None:
    """Where this request decodes after its local prefill (None = here)."""
    role = self.disagg_role
    if role == "decode":
      return None  # a decode node never hands decode work away
    stats = await self._disagg_stats_fresh()
    # A crashed peer's last advert lingers in the cache (often looking BEST
    # — it was idle when it died): placement only considers peers that still
    # hold a live handle and aren't draining. Departed peers' adverts are
    # also evicted at the damped-eviction point (update_peers).
    peer_ids = {p.id() for p in self.peers}
    live = {
      nid: st for nid, st in stats.items()
      if nid in peer_ids and not st.get("draining") and not self._peer_draining(nid)
    }
    return sched_admission.choose_decode_node(live, self_id=self.id, self_role=role)

  def _wire_disagg_hooks(self, server) -> None:
    """Inject the node-layer transfer callbacks into the scheduler (the
    execution layer never imports networking): ``kv_stream`` ships one
    completed prefill chunk's pages in the background; ``kv_handoff``
    flushes the stream and re-submits the extracted row to its decode
    node."""
    if getattr(server, "kv_handoff", None) is None:
      server.kv_stream = self._disagg_kv_stream
      server.kv_handoff = self._disagg_handoff_cb

  def _disagg_kv_stream(self, request_id: str, target_id: str, keys: list, dev: dict, n: int) -> None:
    """Scheduler hook: schedule one KV-page batch transfer in the
    background (the device gather's async D2H is already in flight) so the
    transfer overlaps the remaining prefill chunks."""
    task = asyncio.ensure_future(self._disagg_send_kv(request_id, target_id, keys, dev, n, last=False))
    self._kv_stream_tasks.setdefault(request_id, []).append(task)

  async def _disagg_send_kv(self, request_id: str, target_id: str, keys: list, dev: dict, n: int, *, last: bool) -> int:
    """Materialize one gathered page batch host-side and stream it to the
    decode node in bounded ``KvPageBatch`` messages. Best-effort by
    contract: any failure just means the decode node recomputes those
    tokens' prefill."""
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if peer is None or not hasattr(peer, "send_kv_pages"):
      return 0
    server = getattr(self.inference_engine, "_batched_server", None)
    page_size = getattr(server, "page_size", 0) or 0
    loop = asyncio.get_event_loop()
    t0 = time.perf_counter()
    # np.asarray blocks until the async D2H lands — off the event loop.
    leaves = await loop.run_in_executor(None, lambda: {name: np.asarray(arr)[:, :n] for name, arr in dev.items()})
    try:
      cap = max(int(os.getenv("XOT_TPU_KV_STREAM_PAGES", "32") or 32), 1)
    except ValueError:
      cap = 32
    adopted = 0
    nbytes = 0
    try:
      for i in range(0, len(keys), cap):
        sub_keys = keys[i : i + cap]
        sub = {name: arr[:, i : i + cap] for name, arr in leaves.items()}
        nbytes += sum(a.nbytes for a in sub.values())
        seq = self._kv_stream_seq.get(request_id, 0)
        self._kv_stream_seq[request_id] = seq + 1
        adopted += await peer.send_kv_pages(
          request_id, sub_keys, sub, page_size=page_size, seq=seq, last=last and i + cap >= len(keys),
          quant=getattr(server, "kv_quant", None),
        )
    except Exception:  # noqa: BLE001 — transfer is an optimization, never a failure
      if DEBUG >= 1:
        print(f"[node {self.id}] kv stream for {request_id} to {target_id} failed mid-transfer")
      return adopted
    finally:
      dt = time.perf_counter() - t0
      if keys:
        metrics.inc("kv_stream_pages_total", len(keys))
        metrics.inc("kv_stream_bytes_total", nbytes)
        metrics.observe_hist("kv_stream_seconds", dt, labels={"peer": target_id})
        tracer.stage(request_id, "kv_stream", {
          "peer": target_id, "pages": len(keys), "bytes": nbytes,
          "ms": round(dt * 1e3, 3), "adopted": adopted, "last": last,
        }, node=self.id)
    return adopted

  async def _disagg_handoff_cb(self, req, final_kv) -> bool:
    """Scheduler handoff hook: flush the request's in-flight page batches
    (adoption must land before the decode node's admission restores), ship
    the final batch, then re-submit the extracted row to its decode node.
    False ⇒ the scheduler resumes the row locally — a dead decode target
    never strands a prefilled context."""
    request_id, target_id = req.request_id, req.disagg_target
    for t in self._kv_stream_tasks.pop(request_id, []):
      try:
        await t
      except Exception:  # noqa: BLE001 — stream batches are best-effort
        pass
    if final_kv is not None:
      keys, dev, n = final_kv
      await self._disagg_send_kv(request_id, target_id, keys, dev, n, last=True)
    return await self._disagg_dispatch(req, target_id)

  async def _disagg_dispatch(self, req, target_id: str) -> bool:
    """Hand the extracted row to its decode node over the existing gRPC
    tensor path — the drain-migration wire contract (``replay_epoch`` +
    ``orig_prompt_len`` keep budget and absolute stream positions exact)
    plus a ``disagg_decode`` marker that routes it into the decode node's
    BATCHED scheduler (process_tensor). Returns False on any dispatch
    failure: the row finishes locally via the carry_tokens resume."""
    request_id = req.request_id
    base_shard = self._batched_shards.get(request_id)
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if base_shard is None or peer is None or self._peer_draining(target_id):
      return False
    full = Shard(base_shard.model_id, 0, base_shard.n_layers - 1, base_shard.n_layers)
    tokens = np.asarray(req.tokens, dtype=np.int32).reshape(1, -1)
    orig_len = int(tokens.shape[1]) - len(req.carry_tokens)
    epoch = self._seen_epochs.get(request_id, 0) + 1
    self._seen_epochs[request_id] = epoch
    state = InferenceState(
      tokens=tokens.copy(), prompt_len=int(tokens.shape[1]),
      extras={
        "replay_epoch": epoch, "orig_prompt_len": orig_len,
        "disagg_decode": {"remaining": int(req.max_tokens), "carried": len(req.carry_tokens)},
      },
    )
    # Register the finish waiter BEFORE the forward: the remote finish
    # broadcast must not race the registration.
    self._migrated[request_id] = asyncio.Event()
    self._recovering.add(request_id)
    try:
      await peer.send_tensor(full, tokens, request_id, self._stash_options(request_id, state))
    except asyncio.TimeoutError:
      # The wait expired but the wire may have DELIVERED (the decode node
      # could already be streaming). Prefer at-most-once — same argument as
      # the drain migration: a truly lost handoff becomes the stall
      # watchdog's structured retryable 503, never two generators racing
      # the client stream.
      if DEBUG >= 1:
        print(f"[node {self.id}] disagg handoff of {request_id}: send timed out after delivery window; assuming shipped")
    except Exception:  # noqa: BLE001 — decode target unreachable: finish locally
      self._migrated.pop(request_id, None)
      self._recovering.discard(request_id)
      if DEBUG >= 1:
        print(f"[node {self.id}] disagg handoff of {request_id} to {target_id} failed; resuming locally")
      return False
    metrics.inc("disagg_handoffs_total")
    if DEBUG >= 1:
      print(f"[node {self.id}] disagg handoff: {request_id} decodes on {target_id} ({req.kv_streamed} pages streamed)")
    return True

  def handle_kv_pages(self, request_id: str, keys: list, leaves: dict, *, page_size: int, quant: str | None = None) -> int:
    """gRPC receive side: adopt streamed KV pages into the batched
    scheduler's host tier (the restore-adopt path then serves them to the
    handoff's admission as an extended prefix hit). ``quant`` is the
    sender's KV quant-mode tag (ISSUE 11) — forwarded to the adopt guard."""
    engine = self.inference_engine
    if not hasattr(engine, "get_batched_server"):
      return 0
    # No supports_batched() gate here: adoption is host-RAM only and pages
    # arrive while the engine may still hold (or be loading) a different
    # shard — the batched-capability verdict belongs to the decode handoff
    # itself, which loads the full model. (A model swap still clears the
    # tier, so pages adopted before the swap are just a recomputed prefill.)
    server = engine.get_batched_server()
    if page_size and getattr(server, "page_size", None) not in (None, page_size):
      return 0  # mismatched page geometry: refuse, the sender falls back
    return int(server.adopt_kv_wire(keys, leaves, quant=quant))

  async def _serve_disagg_decode(self, base_shard: Shard, shard: Shard, tensor: np.ndarray, request_id: str, state: InferenceState) -> None:
    """Decode-node side of a disagg handoff (ISSUE 10): submit the carried
    token history into THIS node's batched scheduler as a wire-carried
    resume. Admission finds the streamed pages in the host tier and
    restore-adopts them, so prefill here recomputes only the last partial
    page; emitted tokens broadcast with ABSOLUTE stream positions so the
    origin's high-water dedup splices the continuation exactly after the
    prefill node's first token."""
    engine = self.inference_engine
    tokens = np.asarray(tensor, dtype=np.int32).reshape(-1)
    extras = state.extras if state is not None else {}
    orig_len = int(extras.get("orig_prompt_len", tokens.shape[0]))
    carried = [int(t) for t in tokens[orig_len:]]
    info = extras.get("disagg_decode") or {}
    remaining = int(info.get("remaining", 0))
    if remaining <= 0:
      max_tokens, _, _ = self._request_limits(request_id)
      remaining = max(max_tokens - len(carried), 1)
    _, temp, top_k = self._request_limits(request_id)
    eos_ids = self._eos_token_ids(base_shard)
    self.buffered_token_output[request_id] = ([], False)
    self._ttft_observed.add(request_id)  # TTFT was the prefill node's observation
    offset = len(carried)

    def emit(rid: str, new_tokens: list, finished: bool) -> None:
      buffered, _ = self.buffered_token_output.get(rid, ([], False))
      start = offset + len(buffered)
      buffered.extend(new_tokens)
      self.buffered_token_output[rid] = (buffered, finished)
      for _ in new_tokens:
        tracer.handle_token(rid)
      metrics.inc("tokens_generated_total", len(new_tokens))
      self.trigger_on_token_callbacks(rid, list(new_tokens), finished, start_pos=start)
      asyncio.create_task(self.broadcast_result(rid, list(new_tokens), finished, start_pos=start))

    opts = self.request_options.get(request_id, {})
    try:
      await engine.get_batched_server().submit(
        request_id, tokens, max_tokens=remaining, temp=temp, top_k=top_k, eos_ids=eos_ids, emit=emit,
        priority=opts.get("priority", "standard"), tenant=opts.get("tenant", "default"),
        deadline_ms=opts.get("deadline_ms"), carry=carried, adapter=opts.get("adapter"),
      )
    finally:
      self._finish_request(request_id)

  # --------------------------------------------------------------- serving

  def set_request_options(self, request_id: str, *, stream: bool | None = None, max_tokens: int | None = None, temperature: float | None = None, top_k: int | None = None, priority: str | None = None, tenant: str | None = None, deadline_ms: float | None = None, adapter: str | None = None) -> None:
    """Per-request serving hints set by the API before ``process_prompt``.

    ``stream=False`` lets the fast decode path generate the entire response
    in one compiled program (single host round-trip) instead of streaming
    chunks; ``max_tokens``/``temperature``/``top_k`` override the node
    defaults for this request only. ``priority``/``tenant``/``deadline_ms``
    feed the batched scheduler's QoS layer and are registered in the QoS
    wire registry so data-plane RPCs carry them as ``x-qos-*`` metadata
    (inference/qos.py) — a non-head node that runs the scheduler enforces
    the same policy. ``adapter`` (ISSUE 15) selects a named multi-LoRA
    adapter and rides the same wire registry as ``x-adapter`` metadata, so
    a disagg decode node or drain survivor serves the same variant.
    """
    opts = self.request_options.setdefault(request_id, {})
    for k, v in (("stream", stream), ("max_tokens", max_tokens), ("temperature", temperature), ("top_k", top_k), ("priority", priority), ("tenant", tenant), ("deadline_ms", deadline_ms), ("adapter", adapter)):
      if v is not None:
        opts[k] = v
    if priority is not None or tenant is not None or deadline_ms is not None or adapter is not None:
      from ..inference.qos import qos_wire

      qos_wire.register(request_id, priority=priority, tenant=tenant, deadline_ms=deadline_ms, adapter=adapter, node_id=self.id)

  def _request_limits(self, request_id: str) -> tuple[int, float, int]:
    opts = self.request_options.get(request_id, {})
    max_tokens = opts.get("max_tokens")
    max_tokens = self.max_generate_tokens if max_tokens is None else min(int(max_tokens), self.max_generate_tokens)
    temp = float(opts.get("temperature", self.default_sample_temp))
    top_k = int(opts.get("top_k", self.default_sample_top_k))
    return max_tokens, temp, top_k

  def _stash_options(self, request_id: str, state: InferenceState | None) -> InferenceState | None:
    """Attach this request's serving options to the wire state so every ring
    peer (the last-shard node samples and enforces limits) sees them."""
    opts = self.request_options.get(request_id)
    if opts:
      state = state or InferenceState()
      state.extras["request_options"] = opts
    return state

  def _adopt_options(self, request_id: str, state: InferenceState | None, shard: Shard) -> None:
    # Only the last-shard node samples and enforces limits, and only it runs
    # _finish_request — adopting on middle nodes would leak one dict entry
    # per request with nothing to clean it up.
    if not shard.is_last_layer:
      return
    if state is not None and "request_options" in state.extras and request_id not in self.request_options:
      self.request_options[request_id] = dict(state.extras["request_options"])
    if state is not None:
      # A bumped replay_epoch means the stream was re-driven after a failure:
      # a SURVIVING last-layer owner must drop its stale local buffer or the
      # regenerated tokens would double-count against max_tokens (truncating
      # the transcript) and desync the absolute positions. The wire history
      # (orig_prompt_len floor in _check_finished / _completion_offset) keeps
      # budget and positions exact for token-level replays.
      epoch = int(state.extras.get("replay_epoch", 0))
      if epoch > self._seen_epochs.get(request_id, 0):
        self._seen_epochs[request_id] = epoch
        if request_id in self.buffered_token_output:
          self.buffered_token_output[request_id] = ([], False)
        self._completion_offset.pop(request_id, None)

  async def process_prompt(self, base_shard: Shard, prompt: str, request_id: str | None = None, inference_state: InferenceState | None = None, wire_concrete: bool = False):
    shard = self.get_current_shard(base_shard)
    if request_id is None:
      request_id = str(uuid.uuid4())
    start_time = time.perf_counter_ns()
    ctx = tracer.request_context(request_id)
    metrics.inc("requests_total")
    self._request_t0.setdefault(request_id, time.perf_counter())
    tracer.stage(request_id, "queued", {"node_id": self.id}, node=self.id)
    asyncio.create_task(
      self.broadcast_opaque_status(
        request_id,
        json.dumps(
          {
            "type": "node_status",
            "node_id": self.id,
            "status": "start_process_prompt",
            "base_shard": base_shard.to_dict(),
            "shard": shard.to_dict(),
            "prompt": prompt,
            "request_id": request_id,
            "traceparent": ctx.traceparent(),
          }
        ),
      )
    )
    with tracer.start_span("request.process_prompt", request_id, {"node_id": self.id, "model": base_shard.model_id}):
      result = await self._process_prompt(base_shard, prompt, request_id, inference_state, wire_concrete)
    elapsed_ns = time.perf_counter_ns() - start_time
    asyncio.create_task(
      self.broadcast_opaque_status(
        request_id,
        json.dumps(
          {
            "type": "node_status",
            "node_id": self.id,
            "status": "end_process_prompt",
            "request_id": request_id,
            "elapsed_time_ns": elapsed_ns,
          }
        ),
      )
    )
    return result

  async def process_image_prompt(
    self,
    base_shard: Shard,
    prompt: str,
    request_id: str | None = None,
    *,
    negative: str = "",
    steps: int = 30,
    guidance: float = 7.5,
    seed: int = 0,
    size: tuple[int, int] | None = None,
    init_image=None,
    strength: float = 0.8,
    progress_cb=None,
    cancel_event=None,
    n: int = 1,
  ):
    """Image generation (stable-diffusion family) → uint8 [H, W, 3].

    Role of the reference's SD special case (reference node.py:116-147,
    613-620), which steps a sampler once per ring pass through dead code.
    Here diffusion runs single-node full-model by design (the whole SD2
    pipeline fits one chip; see jax_engine._load_diffusion_sync) so the ring
    forwarding layer is bypassed: progress streams from the denoise loop's
    chunk boundaries instead of ring hops.
    """
    if request_id is None:
      request_id = str(uuid.uuid4())
    full = Shard(base_shard.model_id, 0, base_shard.n_layers - 1, base_shard.n_layers)
    metrics.inc("requests_total")
    with tracer.start_span("request.process_image_prompt", request_id, {"node_id": self.id, "model": base_shard.model_id}):
      return await self.inference_engine.generate_image(
        full, prompt, negative=negative, steps=steps, guidance=guidance,
        seed=seed, size=size, init_image=init_image, strength=strength,
        progress_cb=progress_cb, cancel_event=cancel_event, n=n,
      )

  async def _process_prompt(self, base_shard: Shard, prompt: str, request_id: str, inference_state: InferenceState | None, wire_concrete: bool = False):
    # Sender-authoritative rule (see process_tensor): a shard that arrived
    # over the wire is the sender's concrete routing decision — obey it.
    # Local callers (API/CLI) pass abstract base shards that resolve against
    # this node's topology view. The flag is explicit because a head owning
    # only layer 0 is structurally identical to the API's (0,0,n) marker.
    shard = base_shard if wire_concrete else self.get_current_shard(base_shard)
    # Ahead-of-time ring HBM budget (VERDICT r3 #3): refuse a partition map
    # that cannot hold the model BEFORE any download/load starts — the
    # reference's failure mode was an OOM mid-prefill after the full
    # download. Runs on the node the client hit, BEFORE any per-request
    # state registers (nothing to clean up on refusal), and only for LOCAL
    # callers: a wire-forwarded prompt was already validated by its sender,
    # and a head-side re-raise would surface to the client as a delayed
    # generic RPC failure instead of the typed 507.
    if not wire_concrete:
      problems = self._ring_budget_problems(base_shard)
      if problems:
        from ..parallel.hbm_planner import RingBudgetError

        raise RingBudgetError("ring cannot hold the model: " + "; ".join(problems))
    self._adopt_options(request_id, inference_state, shard)
    if (
      sched_admission.disagg_enabled()
      and os.getenv("XOT_TPU_BATCHED", "0") == "1"
      and hasattr(self.inference_engine, "get_batched_server")
      and getattr(self.inference_engine, "supports_batched", lambda: True)()
      and not (inference_state and inference_state.extras.get("images"))
    ):
      # Disaggregated serving (ISSUE 10): every node holds the FULL model
      # and the ring is a replica set routed by ROLE, not a layer split —
      # a decode-role node forwards fresh prompts to the least-loaded
      # prefill node (queue-drain estimate); prefill/both nodes serve the
      # prefill locally and the scheduler streams the KV to the placed
      # decode node. Wire-forwarded prompts (wire_concrete) are the
      # sender's placement decision — serve them here.
      full = Shard(base_shard.model_id, 0, base_shard.n_layers - 1, base_shard.n_layers)
      if not wire_concrete and self.disagg_role == "decode" and self.peers:
        stats = await self._disagg_stats_fresh()
        # N-node prefill pool (ISSUE 13): walk the ranked candidates so a
        # draining/desynced head doesn't force a colocated degrade while a
        # healthy second-choice prefill node exists.
        for target_id in sched_admission.rank_prefill_nodes(stats, self_id=self.id):
          peer = next((p for p in self.peers if p.id() == target_id), None)
          if peer is not None and not self._peer_draining(target_id):
            await peer.send_prompt(full, prompt, request_id, self._stash_options(request_id, inference_state))
            return None
        # No prefill peer reachable: degrade to serving colocated here.
      return await self._batched_serve(full, full, prompt, request_id, resume_tokens=_resume_tokens_of(inference_state))
    if not shard.is_first_layer:
      # Not the ring head: route the prompt to whichever node owns layer 0,
      # retrying once over a refreshed topology if the head just left.
      for attempt in (0, 1):
        try:
          if attempt:
            # The retry regenerates from position 0. Bump the replay epoch so
            # every surviving node resets its stale buffer for this request
            # (_adopt_options); the regenerated stream's absolute positions
            # then restart at 0 and the receivers' high-water dedup drops the
            # re-streamed prefix — no duplicated span reaches the client.
            inference_state = inference_state or InferenceState()
            inference_state.extras["replay_epoch"] = int(inference_state.extras.get("replay_epoch", 0)) + 1
          head_idx = self.get_partition_index(offset=0, owner_of_first_layer=True)
          await self.forward_prompt(base_shard, prompt, request_id, head_idx, inference_state)
          return None
        except Exception:  # noqa: BLE001
          if attempt:
            raise
          await asyncio.sleep(float(os.getenv("XOT_TPU_RETRY_DELAY_S", "3")))
          try:
            await self.update_peers()
            await self.collect_topology(set())
          except Exception:  # noqa: BLE001
            pass
      return None
    if (
      os.getenv("XOT_TPU_BATCHED", "0") == "1"
      and shard.is_last_layer
      and hasattr(self.inference_engine, "get_batched_server")
      and getattr(self.inference_engine, "supports_batched", lambda: True)()
      and not (inference_state and inference_state.extras.get("images"))
    ):
      # Continuous batching (inference/batch_scheduler.py): this node owns the
      # whole model, so concurrent requests share fused decode chunks — decode
      # is weight-bandwidth-bound, so B in-flight requests cost ≈ 1.
      return await self._batched_serve(base_shard, shard, prompt, request_id, resume_tokens=_resume_tokens_of(inference_state))
    self.outstanding_requests[request_id] = "processing"
    adapter = self.request_options.get(request_id, {}).get("adapter")
    if adapter and hasattr(self.inference_engine, "set_request_adapter"):
      # Solo/streaming parity (ISSUE 15): the engine applies the same
      # indexed adapter hook per session; raises the client-error type for
      # unknown names before any device work.
      self.inference_engine.set_request_adapter(request_id, adapter)
    tracer.stage(request_id, "admitted", {"node_id": self.id}, node=self.id)
    tracer.stage(request_id, "prefill_chunk", {"node_id": self.id}, node=self.id)
    output, state = await self.inference_engine.infer_prompt(request_id, shard, prompt, inference_state)
    await self.process_inference_result(base_shard, output, request_id, state, shard=shard)
    return output

  async def _batched_serve(self, base_shard: Shard, shard: Shard, prompt: str, request_id: str, resume_tokens: list | None = None) -> None:
    engine = self.inference_engine
    self.outstanding_requests[request_id] = "processing"
    tokens = await engine.encode(shard, prompt)
    max_tokens, temp, top_k = self._request_limits(request_id)
    eos_ids = self._eos_token_ids(base_shard)
    self.buffered_token_output[request_id] = ([], False)
    # The scheduler measures TTFT from its own submit time (also the bench
    # path with no node); pre-claim the choke-point observation so the same
    # request isn't counted twice.
    self._ttft_observed.add(request_id)
    # API-level resume (ISSUE 13): a router re-submitting a failed-over
    # request ships the tokens the client already has — the prompt absorbs
    # them (the scheduler's carry contract), emit skips them, and absolute
    # stream positions offset past them so any broadcast dedup splices.
    carried = [int(t) for t in (resume_tokens or [])]
    if carried:
      tokens = np.concatenate([np.asarray(tokens, np.int32).reshape(-1), np.asarray(carried, np.int32)])
      # The carried span was already DELIVERED to the client by whoever is
      # re-submitting (the router's failover contract) — seed the absolute-
      # position high-water there, or the dedup would hold the continuation
      # as an out-of-order chunk until the GAP_FLUSH_S timer fired.
      self._emitted_counts[request_id] = max(self._emitted_counts.get(request_id, 0), len(carried))
    offset = len(carried)

    def emit(rid: str, new_tokens: list, finished: bool) -> None:
      buffered, _ = self.buffered_token_output.get(rid, ([], False))
      start = offset + len(buffered)
      buffered.extend(new_tokens)
      self.buffered_token_output[rid] = (buffered, finished)
      for _ in new_tokens:
        tracer.handle_token(rid)
      metrics.inc("tokens_generated_total", len(new_tokens))
      self.trigger_on_token_callbacks(rid, list(new_tokens), finished, start_pos=start)
      asyncio.create_task(self.broadcast_result(rid, list(new_tokens), finished, start_pos=start))

    opts = self.request_options.get(request_id, {})
    self._batched_shards[request_id] = base_shard
    server = engine.get_batched_server()
    disagg_target = None
    if sched_admission.disagg_enabled() and self.peers and not self.draining:
      # Placement (ISSUE 10): decode node by free pages + class queue depth
      # from the peers' role/capacity adverts. None ⇒ serve colocated.
      disagg_target = await self._disagg_decode_target()
      self._wire_disagg_hooks(server)
    try:
      await server.submit(
        request_id, tokens, max_tokens=max_tokens, temp=temp, top_k=top_k, eos_ids=eos_ids, emit=emit,
        priority=opts.get("priority", "standard"), tenant=opts.get("tenant", "default"),
        deadline_ms=opts.get("deadline_ms"), disagg_target=disagg_target,
        carry=carried or None, adapter=opts.get("adapter"),
      )
    except RequestMigratedError:
      # A draining scheduler shipped the row to a surviving peer (graceful
      # drain), or a disagg placement handed it to its decode node: the
      # stream continues from there over the normal SendResult broadcast
      # path (absolute positions pick up exactly where the local rows left
      # off). Hold this handler open until the remote finish so the API's
      # generation task lifecycle stays truthful.
      await self._await_migrated(request_id)
    finally:
      self._batched_shards.pop(request_id, None)
      for t in self._kv_stream_tasks.pop(request_id, []):
        t.cancel()  # stream batches for a settled request are moot
      self._kv_stream_seq.pop(request_id, None)
      self._finish_request(request_id)

  async def _await_migrated(self, request_id: str) -> None:
    event = self._migrated.get(request_id)
    if event is None:
      return
    try:
      await asyncio.wait_for(event.wait(), timeout=RESPONSE_TIMEOUT_HORIZON_S)
    except asyncio.TimeoutError:
      pass  # the API's own response timeout already fired long before this
    finally:
      self._migrated.pop(request_id, None)

  async def process_tensor(self, base_shard: Shard, tensor: np.ndarray, request_id: str, inference_state: InferenceState | None = None, wire_concrete: bool = False):
    # Sender-authoritative routing: forward_tensor ships the CONCRETE layer
    # range it computed for us. Obey it rather than re-deriving from our own
    # topology view — during a divergence window (a node booting, a peer
    # just evicted) local re-derivation can disagree with the sender and
    # misinterpret the payload (e.g. a hidden state fed to an embedding
    # lookup). ``wire_concrete`` is set by the gRPC server and by local
    # self-forwards; plain callers resolve against the local view.
    shard = base_shard if wire_concrete else self.get_current_shard(base_shard)
    self._adopt_options(request_id, inference_state, shard)
    if (
      inference_state is not None
      and inference_state.extras.get("disagg_decode")
      and shard.is_first_layer
      and shard.is_last_layer
      and hasattr(self.inference_engine, "get_batched_server")
      and getattr(self.inference_engine, "supports_batched", lambda: True)()
    ):
      # Disagg decode handoff (ISSUE 10): route the carried history into
      # THIS node's batched scheduler. Exceptions propagate (unlike the
      # plain path below): the sender's handoff task must see the typed
      # failure and resume the row locally — a swallowed error here would
      # read as "shipped" and strand the stream until the stall watchdog.
      self.outstanding_requests[request_id] = "processing"
      await self._serve_disagg_decode(base_shard, shard, tensor, request_id, inference_state)
      return None
    try:
      self.outstanding_requests[request_id] = "processing"
      output, state = await self.inference_engine.infer_tensor(request_id, shard, tensor, inference_state)
      await self.process_inference_result(base_shard, output, request_id, state, shard=shard)
      return output
    except Exception:  # noqa: BLE001 — a failed hop must not kill the server
      self._finish_request(request_id)
      print(f"[node {self.id}] error processing tensor for {request_id}")
      traceback.print_exc()
      return None

  async def process_inference_result(self, base_shard: Shard, result, request_id: str, inference_state: InferenceState | None = None, shard: Shard | None = None):
    # ``shard`` is the range the result was actually computed for (callers
    # that obeyed a sender-authoritative wire shard pass it); routing of the
    # NEXT hop still derives from this node's current topology view.
    shard = shard or self.get_current_shard(base_shard)
    if request_id in self.cancelled_requests:
      # Client gone: stop the ring here instead of circulating to max_tokens.
      self.buffered_token_output.setdefault(request_id, ([], False))
      tokens, _ = self.buffered_token_output[request_id]
      self.buffered_token_output[request_id] = (tokens, True)
      self.trigger_on_token_callbacks(request_id, [], True)
      self._finish_request(request_id)
      return
    if shard.is_last_layer:
      # result is [B, vocab] logits: sample here, buffer, and broadcast.
      if request_id not in self.buffered_token_output:
        self.buffered_token_output[request_id] = ([], False)
      tokens, _ = self.buffered_token_output[request_id]
      _, req_temp, req_top_k = self._request_limits(request_id)
      token = await self.inference_engine.sample(result, temp=req_temp, top_k=req_top_k)
      token_int = int(np.asarray(token).reshape(-1)[0])
      tokens.append(token_int)
      tracer.handle_token(request_id)
      metrics.inc("tokens_generated_total")
      if len(tokens) == 1:
        # TTFT itself is observed at the token choke point
        # (trigger_on_token_callbacks) so it also fires on the ORIGIN node of
        # a multi-node ring, where sampling happens on a peer and tokens
        # arrive via broadcast; here we only mark the sampling node's stage.
        tracer.stage(request_id, "decode", {"first_token": token_int}, node=self.id)

      is_finished = self._check_finished(base_shard, token_int, len(tokens), inference_state, request_id)
      self.buffered_token_output[request_id] = (tokens, is_finished)
      # Absolute completion index of this token: the wire history floors it
      # when a token-level replay landed on a node whose buffer restarted
      # (the offset then maps local buffer indices to absolute positions for
      # the fast-decode loop too).
      off = self._completion_offset.get(request_id, 0)
      state = inference_state
      if state is not None and state.tokens is not None and "orig_prompt_len" in state.extras:
        hist_pos = int(np.asarray(state.tokens).shape[-1]) - int(state.extras["orig_prompt_len"])
        if hist_pos - (len(tokens) - 1) > off:
          off = hist_pos - (len(tokens) - 1)
          self._completion_offset[request_id] = off
      abs_pos = off + len(tokens) - 1
      self.trigger_on_token_callbacks(request_id, [token_int], is_finished, start_pos=abs_pos)
      asyncio.create_task(self.broadcast_result(request_id, [token_int], is_finished, start_pos=abs_pos))

      if is_finished:
        self._finish_request(request_id)
        return
      # Single-node fast path: this node owns the whole model, so decode in
      # fused chunks (one compiled program per chunk, no per-token host trip).
      if shard.is_first_layer and hasattr(self.inference_engine, "generate_chunk"):
        await self._fast_decode_loop(base_shard, shard, request_id, token_int)
        return
      # Ring wraps: sampled token goes back to the first-layer owner.
      next_token = np.asarray([[token_int]], dtype=np.int32)
      try:
        await self.forward_tensor(base_shard, next_token, request_id, self.get_partition_index(offset=1), inference_state)
      except Exception as e:  # noqa: BLE001 — next hop gone: replay over new topology
        if DEBUG >= 1:
          print(f"[node {self.id}] ring wrap hop for {request_id} failed: {e!r}")
        # The just-sampled (and already streamed) token is only appended to
        # the wire history when it reaches the head — include it here or the
        # replay would regenerate/re-emit that position.
        if inference_state is not None and inference_state.tokens is not None:
          inference_state.tokens = np.concatenate([inference_state.tokens, next_token], axis=1)
        await self._retry_request(base_shard, request_id, inference_state)
    else:
      # Middle shard: pass hidden state to the next partition.
      try:
        await self.forward_tensor(base_shard, result, request_id, self.get_partition_index(offset=1), inference_state)
      except Exception as e:  # noqa: BLE001
        if DEBUG >= 1:
          print(f"[node {self.id}] mid-ring hop for {request_id} failed: {e!r}")
        await self._retry_request(base_shard, request_id, inference_state)

  async def _retry_request(self, base_shard: Shard, request_id: str, state: InferenceState | None) -> None:
    """Elastic in-flight recovery: replay a request whose next hop died.

    The reference simply fails in-flight requests when a peer leaves
    (SURVEY.md §5.3: forward raises "peer not found"; no retry). Here the
    wire state carries the full token history (prompt + generated so far —
    inference/state.py), so after the membership loop re-derives the
    partition map the request REPLAYS as a fresh prefill of those tokens to
    the new layer-0 owner; surviving engines drop their stale per-request
    sessions via the bumped ``replay_epoch``. Tokens already streamed are
    not re-emitted — generation continues where it left off. The separate
    prompt-level retry in _process_prompt — used when the failure surfaces
    inside the initial SendPrompt RPC — regenerates from the original
    prompt; receivers drop the re-streamed prefix by absolute-position
    high-water mark (trigger_on_token_callbacks), so neither path can
    duplicate the client transcript.
    """
    # Coalesce: a mid-failover ring can report SEVERAL failures for one
    # request near-simultaneously (the wrap hop, a stale broadcast, the next
    # hop's error all landing in the same event-loop drain). Without this
    # gate each report consumed an attempt instantly — the budget burned to
    # exhaustion at t+0 and the request was declared failed while the replay
    # that would have succeeded was still sleeping (observed live in
    # scripts/failover_drill.sh).
    if request_id in self._replay_pending:
      return
    # 4 x RETRY_DELAY must outlast discovery's eviction of the dead peer (a
    # collect that still lists it re-targets the replay at the corpse; the
    # drill showed 2 attempts losing that race on slow health timeouts).
    retries = int(os.getenv("XOT_TPU_INFLIGHT_RETRIES", "4"))
    attempt = self._replay_attempts.get(request_id, 0)
    # The per-incident budget resets after a successful replay; the LIFETIME
    # cap does not — a flapping peer that accepts every replay forward but
    # fails every hop must still terminate with a finish event.
    lifetime = self._replay_lifetime.get(request_id, 0)
    if state is None or state.tokens is None or attempt >= retries or lifetime >= 4 * retries:
      # Terminal ``error`` classification (ISSUE 9): the replay budget is
      # spent and the request is being failed — the one genuinely-errored
      # terminal the goodput/availability denominators must see. Recorded
      # BEFORE _finish_request so the stage claims the terminal slot (a
      # finished timeline no longer accepts one). The class rides along so
      # an outage that only kills interactive traffic burns the
      # interactive budget, not 'standard'.
      from ..inference.qos import qos_wire

      wire = qos_wire.get(request_id) or {}
      tracer.stage(request_id, "error", {
        "reason": "replay_budget_exhausted", "attempts": attempt,
        "class": wire.get("priority") or "standard",
      }, node=self.id, terminal=True)
      self._finish_request(request_id)
      print(f"[node {self.id}] request {request_id} failed after {attempt} replay attempts")
      self.buffered_token_output.setdefault(request_id, ([], False))
      tokens, _ = self.buffered_token_output[request_id]
      self.buffered_token_output[request_id] = (tokens, True)
      self.trigger_on_token_callbacks(request_id, [], True)
      # Tell peers too: the origin (and any other counter) must see the
      # finish or its per-request dedup state would linger forever.
      asyncio.create_task(self.broadcast_result(request_id, [], True))
      return
    self._replay_attempts[request_id] = attempt + 1
    self._replay_lifetime[request_id] = lifetime + 1
    # Entered recovery: counted as recovered iff it still reaches a finish
    # event (requests_recovered_total — trigger_on_token_callbacks).
    self._recovering.add(request_id)
    # Held through sleep + forward so concurrent reports no-op; try/finally
    # because a CancelledError (our caller is often a gRPC handler whose peer
    # can drop mid-replay) must not leave the id stuck in the gate.
    self._replay_pending.add(request_id)
    if DEBUG >= 1:
      print(f"[node {self.id}] replaying {request_id} (attempt {attempt + 1}) after peer loss")
    metrics.inc("requests_replayed_total")
    flightrec.record("replay", request_id=request_id, node=self.id, attributes={"attempt": attempt + 1})
    retry_state: InferenceState | None = None
    try:
      # Let discovery evict the dead peer and the topology re-derive.
      await asyncio.sleep(float(os.getenv("XOT_TPU_RETRY_DELAY_S", "3")))
      try:
        await self.update_peers()
        await self.collect_topology(set())
      except Exception:  # noqa: BLE001 — collection is best-effort here
        pass
      tokens = np.asarray(state.tokens, dtype=np.int32).reshape(1, -1)
      # The epoch invalidates surviving engines' stale sessions and keeps
      # traveling with the state across the ring. It derives from the WIRE
      # state's epoch (not the local attempt counter): a second failure
      # detected on a *different* node must still produce a new, higher epoch
      # or survivors would keep their stale sessions. The original prompt
      # length rides along so the new last-layer owner keeps the client's
      # max_tokens budget (its local token buffer starts empty after a move).
      extras = {"replay_epoch": int(state.extras.get("replay_epoch", 0)) + 1}
      if "orig_prompt_len" in state.extras:
        extras["orig_prompt_len"] = state.extras["orig_prompt_len"]
      replay_state = InferenceState(tokens=tokens.copy(), prompt_len=tokens.shape[1], extras=extras)
      try:
        head_idx = self.get_partition_index(offset=0, owner_of_first_layer=True)
        await self.forward_tensor(base_shard, tokens, request_id, head_idx, replay_state)
      except Exception as e:  # noqa: BLE001 — recurse into the next attempt
        if DEBUG >= 1:
          print(f"[node {self.id}] replay forward for {request_id} failed: {e!r}")
        retry_state = replay_state
    finally:
      self._replay_pending.discard(request_id)
    if retry_state is not None:
      await self._retry_request(base_shard, request_id, retry_state)
    else:
      # Replay forwarded successfully: reset the budget so a LATER, separate
      # failure incident gets the full attempt count (not a lifetime cap).
      self._replay_attempts.pop(request_id, None)

  async def _fast_decode_loop(self, base_shard: Shard, shard: Shard, request_id: str, last_token: int, chunk: int | None = None) -> None:
    """Pipelined fused-chunk decode: chunk N+1 is dispatched (input token
    chained on-device) before chunk N's tokens are read back, so the host
    round-trip hides behind compute. An EOS inside chunk N wastes at most one
    speculative chunk."""
    engine = self.inference_engine
    eos_ids = self._eos_token_ids(base_shard)
    max_tokens, temp, top_k = self._request_limits(request_id)

    # Non-streaming request + oneshot-capable engine: generate the whole
    # response in ONE compiled program (a single host round trip).
    if self.request_options.get(request_id, {}).get("stream") is False and hasattr(engine, "generate_oneshot"):
      tokens, _ = self.buffered_token_output[request_id]
      off = self._completion_offset.get(request_id, 0)
      emit: list[int] = []
      start = off + len(tokens)
      remaining = max_tokens - start
      if remaining > 0:
        # generate_oneshot already trims at the first EOS.
        t_chunk = time.perf_counter()
        emit = await engine.generate_oneshot(request_id, shard, last_token, remaining, eos_ids, temp, top_k)
        chunk_dt = time.perf_counter() - t_chunk
        metrics.observe_hist("decode_chunk_seconds", chunk_dt)
        metrics.inc("decode_chunks_total", labels={"path": "dense"})
        if emit:
          metrics.inc("decode_tokens_total", len(emit), labels={"path": "dense"})
          for _ in emit:
            tracer.handle_token(request_id)
          # One weighted observation per response instead of a per-token
          # metrics-lock round trip (utils/metrics.py observe_hist n=k).
          metrics.observe_hist("itl_seconds", chunk_dt / len(emit), n=len(emit))
        metrics.inc("tokens_generated_total", len(emit))
        tokens.extend(emit)
      self.buffered_token_output[request_id] = (tokens, True)
      self.trigger_on_token_callbacks(request_id, emit, True, start_pos=start)
      asyncio.create_task(self.broadcast_result(request_id, emit, True, start_pos=start))
      self._finish_request(request_id)
      return

    if chunk is None:
      # Tokens per streamed chunk: one dispatch and one readback each.
      # Env-tunable; the default has no chip measurement behind it.
      import os as _os

      chunk = int(_os.getenv("XOT_TPU_DECODE_CHUNK", "32"))

    off = self._completion_offset.get(request_id, 0)
    pending = await engine.dispatch_chunk(request_id, shard, chunk, temp, top_k, first_token=last_token)
    while pending is not None:
      if request_id in self.cancelled_requests:
        break
      tokens, _ = self.buffered_token_output[request_id]
      remaining = max_tokens - off - len(tokens)
      # Speculatively enqueue the next chunk while we read this one.
      nxt = None
      if remaining > chunk:
        nxt = await engine.dispatch_chunk(request_id, shard, min(chunk, remaining - chunk), temp, top_k)
      t_chunk = time.perf_counter()
      new_tokens = (await engine.read_chunk(pending))[:remaining]
      chunk_dt = time.perf_counter() - t_chunk
      metrics.observe_hist("decode_chunk_seconds", chunk_dt)
      metrics.inc("decode_chunks_total", labels={"path": "dense"})

      emit: list[int] = []
      hit_eos = False
      for t in new_tokens:
        emit.append(t)
        tracer.handle_token(request_id)
        metrics.inc("tokens_generated_total")
        if t in eos_ids:
          hit_eos = True
          break
      if emit:
        metrics.inc("decode_tokens_total", len(emit), labels={"path": "dense"})
        # One weighted observation per chunk (utils/metrics.py observe_hist
        # n=k) — the per-token cost here was pure lock round trips.
        metrics.observe_hist("itl_seconds", chunk_dt / max(len(new_tokens), 1), n=len(emit))
      start = off + len(tokens)
      tokens.extend(emit)
      done = hit_eos or off + len(tokens) >= max_tokens
      self.buffered_token_output[request_id] = (tokens, done)
      if emit or done:
        self.trigger_on_token_callbacks(request_id, emit, done, start_pos=start)
        asyncio.create_task(self.broadcast_result(request_id, emit, done, start_pos=start))
      if done:
        break
      pending = nxt
      if pending is None:
        # Variable-size chunks (speculative decoding returns m <= n_steps
        # tokens) can under-deliver the speculatively-sized schedule: if
        # budget remains but nothing is in flight, dispatch a continuation
        # now (one non-overlapped dispatch only when speculation fell short).
        tokens, _ = self.buffered_token_output[request_id]
        remaining = max_tokens - off - len(tokens)
        if remaining > 0:
          pending = await engine.dispatch_chunk(request_id, shard, min(chunk, remaining), temp, top_k)

    self._finish_request(request_id)
    # Ensure listeners see a finish even on cache exhaustion.
    tokens, finished = self.buffered_token_output[request_id]
    if not finished:
      self.buffered_token_output[request_id] = (tokens, True)
      self.trigger_on_token_callbacks(request_id, [], True)
      asyncio.create_task(self.broadcast_result(request_id, [], True))

  def cancel_request(self, request_id: str) -> None:
    """Stop generating for a request (client disconnected / stream aborted).

    Takes effect at the next step/chunk boundary: the fast decode loop and
    the per-token ring check the flag, and the batched scheduler frees the
    request's slot (inference/batch_scheduler.py ``cancel``). The cancel is
    broadcast to peers so remote ring members stop too. Without this, an
    abandoned request keeps decoding to max_tokens — harmless when requests
    serialize, a slot-starvation bug under continuous batching."""
    self._cancel_locally(request_id)
    asyncio.create_task(self.broadcast_opaque_status(request_id, json.dumps({"type": "cancel_request", "request_id": request_id})))

  def _cancel_locally(self, request_id: str) -> None:
    self.cancelled_requests.add(request_id)
    server = getattr(self.inference_engine, "_batched_server", None)
    if server is not None:
      server.cancel(request_id)
    # Bound the sets: a forwarding-only node never reaches _finish_request
    # for this id, so expire the entries after the response timeout horizon.
    loop = asyncio.get_event_loop()
    loop.call_later(RESPONSE_TIMEOUT_HORIZON_S, self.cancelled_requests.discard, request_id)
    loop.call_later(RESPONSE_TIMEOUT_HORIZON_S, self._completion_offset.pop, request_id, None)
    loop.call_later(RESPONSE_TIMEOUT_HORIZON_S, self._seen_epochs.pop, request_id, None)
    self._recovering.discard(request_id)  # a cancelled request never recovers
    self._expire_dedup_state(request_id)

  def _finish_request(self, request_id: str) -> None:
    self.outstanding_requests.pop(request_id, None)
    self.request_options.pop(request_id, None)
    # The QoS wire registry entry is NOT popped here: late broadcasts may
    # still reference it, and the registry is LRU-bounded (inference/qos.py
    # MAX_WIRE_ENTRIES) so it cannot grow without bound.
    self._request_t0.pop(request_id, None)
    self._ttft_observed.discard(request_id)
    self.cancelled_requests.discard(request_id)
    self._replay_attempts.pop(request_id, None)
    self._replay_lifetime.pop(request_id, None)
    self._replay_pending.discard(request_id)
    # The recovered counter fires at the finish EVENT (trigger callbacks),
    # which precedes this cleanup on every finishing path — discarding here
    # only reaps ids whose request died without one (failed replay budget,
    # teardown), which must not accumulate forever.
    self._recovering.discard(request_id)
    self._expire_dedup_state(request_id)  # tombstoned against zombie broadcasts, not popped
    self._completion_offset.pop(request_id, None)
    self._seen_epochs.pop(request_id, None)
    tracer.end_request(request_id)
    if hasattr(self.inference_engine, "end_request"):
      self.inference_engine.end_request(request_id)

  def _check_finished(self, base_shard: Shard, token: int, n_tokens: int, state: InferenceState | None, request_id: str = "") -> bool:
    max_tokens, _, _ = self._request_limits(request_id)
    # After an elastic replay the last layer may land on a node whose local
    # token buffer is empty — the wire state's history keeps the client's
    # budget honest (generated = history beyond the ORIGINAL prompt, +1 for
    # the token just sampled).
    if state is not None and state.tokens is not None and "orig_prompt_len" in state.extras:
      n_tokens = max(n_tokens, int(np.asarray(state.tokens).shape[-1]) - int(state.extras["orig_prompt_len"]) + 1)
    if n_tokens >= max_tokens:
      return True
    eos_ids = self._eos_token_ids(base_shard)
    return token in eos_ids

  def _eos_token_ids(self, base_shard: Shard) -> set[int]:
    tokenizer = getattr(self.inference_engine, "tokenizer", None)
    ids: set[int] = set()
    if tokenizer is not None:
      eos = getattr(tokenizer, "eos_token_id", None)
      if isinstance(eos, int):
        ids.add(eos)
      elif isinstance(eos, (list, tuple)):
        ids.update(int(e) for e in eos)
    cfg = getattr(self.inference_engine, "cfg", None)
    if cfg is not None:
      ids.update(getattr(cfg, "eos_token_ids", ()))
    return ids

  # ------------------------------------------------------------ forwarding

  async def forward_prompt(self, base_shard: Shard, prompt: str, request_id: str, target_index: int, inference_state: InferenceState | None = None) -> None:
    if DEBUG >= 1:
      print(f"[node {self.id}] forwarding prompt {request_id} to partition {target_index}")
    target_id = self.partitioning_strategy.partition(self.topology)[target_index].node_id
    next_shard = self.get_current_shard(base_shard, target_index)
    inference_state = self._stash_options(request_id, inference_state)
    if target_id == self.id:
      await self.process_prompt(next_shard, prompt, request_id, inference_state, wire_concrete=True)
    else:
      peer = next((p for p in self.peers if p.id() == target_id), None)
      if peer is None:
        raise ValueError(f"peer for {target_index} not found")
      await peer.send_prompt(next_shard, prompt, request_id, inference_state)

  async def forward_tensor(self, base_shard: Shard, tensor: np.ndarray, request_id: str, target_index: int, inference_state: InferenceState | None = None) -> None:
    if DEBUG >= 2:
      print(f"[node {self.id}] forwarding tensor {tensor.shape} for {request_id} to partition {target_index}")
    target_id = self.partitioning_strategy.partition(self.topology)[target_index].node_id
    next_shard = self.get_current_shard(base_shard, target_index)
    inference_state = self._stash_options(request_id, inference_state)
    if target_id == self.id:
      await self.process_tensor(next_shard, tensor, request_id, inference_state, wire_concrete=True)
    else:
      peer = next((p for p in self.peers if p.id() == target_id), None)
      if peer is None:
        raise ValueError(f"peer for {target_index} not found")
      await peer.send_tensor(next_shard, tensor, request_id, inference_state)

  # --------------------------------------------------------------- training

  async def enqueue_example(self, base_shard: Shard, example: np.ndarray, target: np.ndarray, length: np.ndarray, train: bool = False, request_id: str | None = None) -> tuple[float, np.ndarray | None]:
    shard = self.get_current_shard(base_shard)
    if request_id is None:
      request_id = str(uuid.uuid4())
    if shard.is_first_layer:
      return await self.process_example(base_shard, example, target, length, train, request_id)
    # Route to the ring head.
    head_idx = self.get_partition_index(offset=0, owner_of_first_layer=True)
    target_id = self.partitioning_strategy.partition(self.topology)[head_idx].node_id
    peer = next((p for p in self.peers if p.id() == target_id), None)
    if peer is None:
      raise ValueError("first-layer owner not found")
    return await peer.send_example(self.get_current_shard(base_shard, head_idx), example, target, length, train, request_id)

  async def process_example(self, base_shard: Shard, example: np.ndarray, target: np.ndarray, length: np.ndarray, train: bool, request_id: str) -> tuple[float, np.ndarray | None]:
    """Run this node's span of the training ring.

    Full-model shard: one engine step. Partial shards run the ring protocol
    the reference designed but never implemented engine-side
    (``reference/orchestration/node.py:299-330``, proto ``Loss{loss,grads}``):
    activations hop forward via SendExample; each RPC *reply* carries the
    loss and d_activations back, and every span applies its own optimizer
    update — elementwise optimizers make the composite step identical to a
    single-node full-model step (tests/test_ring_training.py)."""
    shard = self.get_current_shard(base_shard)
    self.outstanding_requests[request_id] = "training" if train else "evaluating"
    try:
      if shard.is_last_layer and shard.is_first_layer:
        if train:
          loss = await self.inference_engine.train(request_id, shard, example, target, length)
        else:
          loss = await self.inference_engine.evaluate(request_id, shard, example, target, length)
        return float(loss), None
      if shard.is_last_layer:
        # Ring tail: example carries the upstream span's activations.
        loss, d_h = await self.inference_engine.last_span_step(request_id, shard, example, target, length, train)
        return float(loss), d_h
      # Head or middle span: forward own layers, hop downstream, and (when
      # training) backpropagate through the stashed VJP on the reply.
      h = await self.inference_engine.forward_span(request_id, shard, example, train)
      next_idx = self.get_partition_index(offset=1)
      next_shard = self.get_current_shard(base_shard, next_idx)
      target_id = self.partitioning_strategy.partition(self.topology)[next_idx].node_id
      peer = next((p for p in self.peers if p.id() == target_id), None)
      discard = getattr(self.inference_engine, "discard_span", lambda _rid: None)
      if peer is None:
        discard(request_id)  # drops the stashed VJP (train) and aux (both modes)
        raise ValueError(f"downstream training peer {target_id} not found")
      try:
        loss, d_out = await peer.send_example(next_shard, h, target, length, train, request_id)
      except Exception:
        discard(request_id)
        raise
      # This span's MoE load-balancing aux joins the TRAINING loss on the way
      # back — the reply then equals the single-node CE + coef*sum(aux)
      # objective (train/trainer.py ring section). Eval stays pure CE like
      # single-node make_eval_step; the stash is popped either way.
      aux = getattr(self.inference_engine, "pop_span_aux", lambda _rid: 0.0)(request_id)
      if train:
        loss = float(loss) + aux
      if not train:
        return float(loss), None
      d_in = await self.inference_engine.backward_span(request_id, shard, d_out)
      return float(loss), d_in
    finally:
      self.outstanding_requests.pop(request_id, None)

  async def score_tokens(self, base_shard: Shard, tokens, n_scored: int, top_n: int):
    """Post-hoc logprobs for the API (`logprobs` request field): one parallel
    forward over prompt+completion on THIS node. Only meaningful where the
    full model lives (single-node serving); ring deployments return None and
    the API omits logprobs (documented limitation)."""
    shard = self.get_current_shard(base_shard)
    scorer = getattr(self.inference_engine, "score_tokens", None)
    if scorer is None or not (shard.is_first_layer and shard.is_last_layer):
      return None
    return await scorer(shard, tokens, n_scored, top_n)

  async def coordinate_save(self, base_shard: Shard, iteration: int, destination: str) -> None:
    """Save this node's shard checkpoint (reference node.py:230-252)."""
    shard = self.get_current_shard(base_shard)
    model = base_shard.model_id
    self.checkpoints.setdefault(model, {})
    sid = f"{shard.start_layer}-{shard.end_layer}"
    from pathlib import Path

    path = Path(destination) / model / f"{sid}-{iteration}.ckpt"
    path.parent.mkdir(parents=True, exist_ok=True)
    await self.inference_engine.save_checkpoint(shard, path)
    self.checkpoints[model][sid] = iteration

  async def on_loss(self, loss: float) -> None:
    if DEBUG >= 1:
      print(f"[node {self.id}] received loss {loss}")

  # ------------------------------------------------------------- partitions

  def get_partition_index(self, offset: int = 0, owner_of_first_layer: bool = False) -> int:
    if not self.partitioning_strategy:
      raise ValueError("no partitioning strategy")
    partitions = self.partitioning_strategy.partition(self.topology)
    if owner_of_first_layer:
      return 0
    current = next((i for i, p in enumerate(partitions) if p.node_id == self.id), None)
    if current is None:
      raise ValueError(f"node {self.id} not in partition table")
    return (current + offset) % len(partitions)

  def get_current_shard(self, base_shard: Shard, index: int | None = None) -> Shard:
    if index is None:
      index = self.get_partition_index()
    partitions = self.partitioning_strategy.partition(self.topology)
    shards = map_partitions_to_shards(partitions, base_shard.n_layers, base_shard.model_id)
    return shards[min(index, len(shards) - 1)]

  # ------------------------------------------------- ring HBM budget (AOT)

  def _model_cfg_for_budget(self, model_id: str):
    """Best-effort model geometry WITHOUT downloading weights: the loaded
    engine's cfg, an ``XOT_TPU_MODEL_DIR`` checkpoint, or an
    already-downloaded snapshot's config.json. ``None`` (skip the ring
    check) when no local geometry exists — the engine's own ``check_plan``
    still guards its local mesh after the download."""
    eng = self.inference_engine
    cfg = getattr(eng, "cfg", None)
    eng_shard = getattr(eng, "shard", None)
    if cfg is not None and eng_shard is not None and eng_shard.model_id == model_id:
      return cfg
    from pathlib import Path

    candidates = []
    if local := os.getenv("XOT_TPU_MODEL_DIR"):
      candidates.append(Path(local))
    try:
      from ..download.downloader import get_models_dir, repo_to_dirname

      repo = registry.get_repo(model_id, type(eng).__name__)
      if repo:
        candidates.append(get_models_dir() / repo_to_dirname(repo))
    except Exception:  # noqa: BLE001
      pass
    from ..models.config import load_model_config

    for d in candidates:
      try:
        if (d / "config.json").exists():
          return load_model_config(d)
      except Exception:  # noqa: BLE001
        continue
    return None

  def _ring_budget_problems(self, base_shard: Shard) -> list[str]:
    """Validate the CURRENT multi-node partition map against each member's
    probed memory (parallel/hbm_planner.ring_partition_fits). Returns
    human-readable problems; empty when the ring fits, when this node
    serves alone (the engine's check_plan guards that path), when any
    member's memory is an un-probed placeholder (0 — never false-refuse),
    or when the model geometry is unknown locally."""
    partitions = self.partitioning_strategy.partition(self.topology)
    if len(partitions) <= 1:
      return []
    mems_mb = [int(getattr(self.topology.nodes.get(p.node_id), "memory", 0) or 0) for p in partitions]
    if any(m <= 0 for m in mems_mb):
      return []
    fingerprint = (base_shard.model_id, tuple(zip([p.node_id for p in partitions], mems_mb)))
    if self._ring_budget_cache and self._ring_budget_cache[0] == fingerprint:
      return self._ring_budget_cache[1]
    cfg = self._model_cfg_for_budget(base_shard.model_id)
    if cfg is None:
      # Unknown geometry: skip WITHOUT caching — once the config lands on
      # disk (first download), the next prompt must run the real check.
      return []
    from ..parallel.hbm_planner import ring_partition_fits

    # Map onto the checkpoint's REAL depth (the engine remaps the same way
    # when registry layer counts disagree with a local checkpoint).
    shards = map_partitions_to_shards(partitions, cfg.n_layers, base_shard.model_id)
    quant = os.getenv("XOT_TPU_QUANT") or None
    problems = ring_partition_fits(cfg, shards, [m * 1024**2 for m in mems_mb], quant=quant)
    self._ring_budget_cache = (fingerprint, problems)
    return problems

  # ------------------------------------------------------- cluster metrics

  async def collect_cluster_metrics(self, timeout: float = 2.0) -> list[dict]:
    """Pull every peer's metrics snapshot over the existing gRPC
    opaque-status channel (no new RPC): broadcast a ``metrics_pull`` with a
    nonce; each peer replies by broadcasting a ``metrics_snapshot`` carrying
    its ``utils/metrics.py snapshot()``. Returns the collected snapshots
    (possibly fewer than the peer count when some time out) — the API merges
    them with the local registry for ``/metrics?scope=cluster``."""
    if not self.peers:
      return []
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._metrics_waiters[nonce] = waiter
    try:
      await self.broadcast_opaque_status(
        "", json.dumps({"type": "metrics_pull", "node_id": self.id, "nonce": nonce})
      )
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # merge whatever arrived
      return list(waiter[1])
    finally:
      self._metrics_waiters.pop(nonce, None)

  # ------------------------------------------------------- cluster timelines

  async def collect_cluster_timeline(self, request_id: str, timeout: float = 2.0) -> list[dict]:
    """Pull every peer's timeline fragment for ``request_id`` over the
    existing gRPC opaque-status channel (mirrors ``collect_cluster_metrics``:
    broadcast a ``timeline_pull`` with a nonce; each peer replies with a
    ``timeline_fragment`` carrying its ``tracer.timeline_export`` — or None
    when it never saw the request, so the pull completes without waiting out
    the timeout). Returns ``[{"node_id", "fragment"}, ...]``."""
    if not self.peers:
      return []
    await self._seed_clock_offsets()
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._timeline_waiters[nonce] = waiter
    try:
      await self.broadcast_opaque_status(
        "", json.dumps({"type": "timeline_pull", "node_id": self.id, "nonce": nonce, "request_id": request_id})
      )
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # merge whatever arrived
      return list(waiter[1])
    finally:
      self._timeline_waiters.pop(nonce, None)

  async def _seed_clock_offsets(self, timeout: float = 2.0) -> None:
    """Make sure every peer has a usable clock-offset estimate before a
    cluster-timeline merge: peers without one (the periodic pass hasn't
    reached them, or discovery never health-checks — static test setups) get
    a burst of 3 echo samples to prime the EWMA. Bounded: the whole seeding
    is capped at ``timeout`` and a peer that fails its first check is not
    retried — a DEAD peer must not stall the observability endpoint exactly
    when the cluster is degraded (its fragment just merges with offset 0)."""
    fresh = [p for p in self.peers if clock_sync.estimate(p.id()) is None and hasattr(p, "health_check")]
    if not fresh:
      return

    async def burst(peer) -> None:
      for _ in range(3):
        if not await peer.health_check():
          return  # unreachable: don't burn the remaining samples on it

    try:
      await asyncio.wait_for(
        asyncio.gather(*(burst(p) for p in fresh), return_exceptions=True), timeout=timeout
      )
    except asyncio.TimeoutError:
      pass  # merge with whatever estimates landed

  def merged_cluster_timeline(self, request_id: str, fragments: list[dict]) -> dict | None:
    return merge_cluster_timeline(
      self.id, tracer.timeline_export(request_id), fragments, clock_sync.offsets()
    )

  def _handle_timeline_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "timeline_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      reply = json.dumps({
        "type": "timeline_fragment",
        "node_id": self.id,
        "nonce": status_data.get("nonce", ""),
        "fragment": tracer.timeline_export(status_data.get("request_id", "")),
      })
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        async def send():
          try:
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — timeline replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] timeline fragment reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "timeline_fragment":
      waiter = self._timeline_waiters.get(status_data.get("nonce", ""))
      if waiter is not None and status_data.get("node_id") != self.id:
        waiter[1].append({"node_id": status_data.get("node_id"), "fragment": status_data.get("fragment")})
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  def _handle_metrics_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "metrics_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      reply = json.dumps({
        "type": "metrics_snapshot",
        "node_id": self.id,
        "nonce": status_data.get("nonce", ""),
        "snapshot": metrics.snapshot(),
      })
      # Reply ONLY to the requester: broadcasting the full registry to every
      # peer would make one cluster scrape O(N²) snapshot deliveries.
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        async def send():
          try:
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — scrape replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] metrics snapshot reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "metrics_snapshot":
      waiter = self._metrics_waiters.get(status_data.get("nonce", ""))
      if waiter is not None and status_data.get("node_id") != self.id:
        waiter[1].append(status_data.get("snapshot") or {})
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  # ------------------------------------------------- cluster prefix registry

  async def collect_cluster_prefixes(self, timeout: float = 2.0) -> dict[str, int]:
    """Refresh the cluster prefix-registry view over the opaque-status
    channel (the ``metrics_pull`` pattern, ISSUE 6): broadcast a
    ``prefix_pull`` with a nonce; each peer replies with a ``prefix_keys``
    advertisement — the chain-key hexes its KV tiers currently hold. Replies
    REPLACE that peer's entry in ``inference/kv_tier.py prefix_registry``
    (an advert is a snapshot, not a delta), so a router — or
    ``GET /v1/kv/tier`` — can see where a prefix already sits. Returns
    ``{node_id: advertised key count}`` for the peers that answered.
    Advertised keys are placement HINTS, never dereferenced blindly."""
    if not self.peers:
      return {}
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._prefix_waiters[nonce] = waiter
    try:
      await self.broadcast_opaque_status(
        "", json.dumps({"type": "prefix_pull", "node_id": self.id, "nonce": nonce})
      )
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # record whatever arrived
      return {nid: n for nid, n in waiter[1]}
    finally:
      self._prefix_waiters.pop(nonce, None)

  def _handle_prefix_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "prefix_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      reply = json.dumps({
        "type": "prefix_keys",
        "node_id": self.id,
        "nonce": status_data.get("nonce", ""),
        "keys": prefix_registry.local_hexes(),
      })
      # Reply only to the requester (same O(N²) argument as metrics_pull).
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        async def send():
          try:
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — advert replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] prefix advert reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "prefix_keys":
      sender = status_data.get("node_id")
      if sender == self.id:
        return
      keys = status_data.get("keys") or []
      prefix_registry.update_remote(sender, keys)
      waiter = self._prefix_waiters.get(status_data.get("nonce", ""))
      if waiter is not None:
        waiter[1].append((sender, len(keys)))
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  # ----------------------------------------------- cluster SLO reports (ISSUE 9)

  async def collect_cluster_slo(self, timeout: float = 2.0) -> list[dict]:
    """Pull every peer's SLO report over the opaque-status channel (the
    ``metrics_pull`` pattern): broadcast an ``slo_pull`` with a nonce; each
    peer ticks its engine and replies with ``slo_report`` carrying the raw
    numerators/denominators, so the API can merge them EXACTLY
    (orchestration/slo.py ``merge_slo_reports``) for ``/v1/slo?scope=cluster``.
    The broadcast runs as a background task: a dead peer's send attempt must
    not stall the endpoint past ``timeout`` (its report is simply absent)."""
    if not self.peers:
      return []
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._slo_waiters[nonce] = waiter
    bcast = asyncio.create_task(self.broadcast_opaque_status(
      "", json.dumps({"type": "slo_pull", "node_id": self.id, "nonce": nonce})
    ))
    try:
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # merge whatever arrived
      return list(waiter[1])
    finally:
      self._slo_waiters.pop(nonce, None)
      bcast.cancel()

  def _handle_slo_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "slo_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      # Reply only to the requester (same O(N²) argument as metrics_pull).
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        nonce = status_data.get("nonce", "")

        async def send():
          # Tick + report deep-copy the whole registry — off the event
          # loop, same argument as the periodic tick dispatch.
          loop = asyncio.get_event_loop()

          def build() -> str:
            slo_engine.maybe_tick(node=self, loop=loop)  # fresh window ring
            return json.dumps({
              "type": "slo_report",
              "node_id": self.id,
              "nonce": nonce,
              "report": slo_engine.report(node_id=self.id),
            })

          try:
            reply = await loop.run_in_executor(None, build)
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — SLO replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] slo report reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "slo_report":
      waiter = self._slo_waiters.get(status_data.get("nonce", ""))
      if waiter is not None and status_data.get("node_id") != self.id:
        waiter[1].append(status_data.get("report") or {})
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  def merged_cluster_slo(self, peer_reports: list[dict], loop=None) -> dict:
    slo_engine.maybe_tick(node=self, loop=loop)
    return merge_slo_reports([slo_engine.report(node_id=self.id)] + peer_reports)

  # ----------------------------------------- cluster program ledger (ISSUE 19)

  async def collect_cluster_programs(self, timeout: float = 2.0) -> list[dict]:
    """Pull every peer's program-ledger snapshot over the opaque-status
    channel (the ``slo_pull`` pattern) for ``/v1/programs?scope=cluster``.
    Dead peers are annotated by absence — the endpoint merges whatever
    arrived within ``timeout`` and lists the silent peers as unreachable."""
    if not self.peers:
      return []
    nonce = uuid.uuid4().hex
    event = asyncio.Event()
    waiter = [event, [], len(self.peers)]
    self._programs_waiters[nonce] = waiter
    bcast = asyncio.create_task(self.broadcast_opaque_status(
      "", json.dumps({"type": "programs_pull", "node_id": self.id, "nonce": nonce})
    ))
    try:
      try:
        await asyncio.wait_for(event.wait(), timeout=timeout)
      except asyncio.TimeoutError:
        pass  # merge whatever arrived; silent peers annotated by the caller
      return list(waiter[1])
    finally:
      self._programs_waiters.pop(nonce, None)
      bcast.cancel()

  def _handle_programs_status(self, status_data: dict) -> None:
    from ..utils.programs import ledger

    kind = status_data.get("type")
    if kind == "programs_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        nonce = status_data.get("nonce", "")

        async def send():
          try:
            snap = ledger.snapshot()
            snap["node_id"] = self.id
            await peer.send_opaque_status("", json.dumps({
              "type": "programs_report", "node_id": self.id, "nonce": nonce, "snapshot": snap,
            }))
          except Exception:  # noqa: BLE001 — ledger replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] programs report reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "programs_report":
      waiter = self._programs_waiters.get(status_data.get("nonce", ""))
      if waiter is not None and status_data.get("node_id") != self.id:
        waiter[1].append(status_data.get("snapshot") or {})
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  # ---------------------------------------------- incident bundles (ISSUE 9)

  async def collect_cluster_bundle(self, reason: str = "manual", timeout: float = 3.0) -> dict:
    """Assemble ONE incident bundle from every reachable peer plus this node
    (``orchestration/flightrec.py assemble_local_bundle`` per node, pulled
    over the opaque-status channel). Peers that did not answer within
    ``timeout`` are ANNOTATED — ``{"node_id": ..., "unreachable": true}`` —
    never waited out: the call is bounded by construction (the broadcast is
    a background task, the waiter is a timed event), because the likeliest
    trigger is exactly a dead peer. Local assembly runs in an executor —
    the registry deep-copy must not stall the event loop's RPC handling."""
    local = await asyncio.get_event_loop().run_in_executor(
      None, lambda: assemble_local_bundle(self, reason=reason)
    )
    parts: list[dict] = []
    if self.peers:
      nonce = uuid.uuid4().hex
      event = asyncio.Event()
      waiter = [event, [], len(self.peers)]
      self._bundle_waiters[nonce] = waiter
      bcast = asyncio.create_task(self.broadcast_opaque_status(
        "", json.dumps({"type": "bundle_pull", "node_id": self.id, "nonce": nonce, "reason": reason})
      ))
      try:
        try:
          await asyncio.wait_for(event.wait(), timeout=timeout)
        except asyncio.TimeoutError:
          pass  # annotate the silent peers below
        parts = list(waiter[1])
      finally:
        self._bundle_waiters.pop(nonce, None)
        bcast.cancel()
    answered = {p.get("node_id") for p in parts}
    missing = [
      {"node_id": pid, "unreachable": True, "breaker_open": breakers.is_open(pid), "health_dead": peer_health.is_dead(pid)}
      for p in self.peers if (pid := p.id()) not in answered
    ]
    return {
      "scope": "cluster",
      "reason": reason,
      "captured_at": time.time(),
      "origin": self.id,
      "nodes_reporting": 1 + len(parts),
      "nodes_unreachable": missing,
      "parts": [local] + parts + missing,
    }

  def _handle_bundle_status(self, status_data: dict) -> None:
    kind = status_data.get("type")
    if kind == "bundle_pull":
      requester = status_data.get("node_id")
      if requester == self.id:
        return  # our own broadcast echoing back through the local trigger
      peer = next((p for p in self.peers if p.id() == requester), None)
      if peer is not None:
        nonce = status_data.get("nonce", "")
        reason = str(status_data.get("reason") or "cluster")

        async def send():
          # Bundle assembly deep-copies the registry + events + timelines
          # and JSON-serializes it — off the event loop: the pull arrives
          # exactly when the cluster is unhealthy and RPC handling matters
          # most.
          def build() -> str:
            return json.dumps({
              "type": "bundle_part",
              "node_id": self.id,
              "nonce": nonce,
              "part": assemble_local_bundle(self, reason=reason),
            })

          try:
            reply = await asyncio.get_event_loop().run_in_executor(None, build)
            await peer.send_opaque_status("", reply)
          except Exception:  # noqa: BLE001 — bundle replies are best-effort
            if DEBUG >= 1:
              print(f"[node {self.id}] bundle part reply to {requester} failed")
        asyncio.create_task(send())
    elif kind == "bundle_part":
      waiter = self._bundle_waiters.get(status_data.get("nonce", ""))
      if waiter is not None and status_data.get("node_id") != self.id:
        waiter[1].append(status_data.get("part") or {"node_id": status_data.get("node_id")})
        if len(waiter[1]) >= waiter[2]:
          waiter[0].set()

  # -------------------------------------------------------------- topology

  async def update_peers(self, wait_for_peers: int = 0) -> bool:
    next_peers = await self.discovery.discover_peers(wait_for_peers)
    for p in next_peers:
      # Stamp whose behalf these handles send on: hop telemetry labels
      # client-side spans with the ORIGIN node (discovery built the handles
      # without knowing it).
      if hasattr(p, "set_origin"):
        p.set_origin(self.id)
    current_ids = {p.id() for p in self.peers}
    next_ids = {p.id() for p in next_peers}
    peers_added = [p for p in next_peers if p.id() not in current_ids]
    peers_removed = [p for p in self.peers if p.id() not in next_ids]
    peers_updated = [p for p in next_peers if p.id() in current_ids and next(o for o in self.peers if o.id() == p.id()).addr() != p.addr()]
    peers_unchanged = [p for p in next_peers if p.id() in current_ids and next(o for o in self.peers if o.id() == p.id()).addr() == p.addr()]
    peers_to_disconnect = peers_removed + peers_updated
    peers_to_connect = peers_added + peers_updated

    async def disconnect_with_timeout(peer, timeout=5):
      # A departing (or address-changed → likely restarted) peer's clock
      # estimate is garbage for its next incarnation: perf_counter's epoch is
      # per-process, so the true offset jumps arbitrarily on restart and the
      # EWMA would converge from that huge error over dozens of samples.
      # Forget now; the next health check re-seeds from scratch.
      clock_sync.forget(peer.id())
      # Its prefix advertisement is equally stale (a restarted peer's pools
      # start empty); keep the registry's hints honest.
      prefix_registry.forget_remote(peer.id())
      # Same for the fault-tolerance state: a departed peer's circuit and
      # flap-damping counters describe the OLD incarnation — the next one
      # (possibly at a new address) starts closed/healthy. Consistent with
      # the clock-offset forget: all three happen at the damped eviction
      # point, never on a single flapped health check.
      breakers.forget(peer.id())
      peer_health.forget(peer.id())
      # Its disagg role/capacity advert is stale the same way (a restarted
      # peer's pools start empty; a crashed one must stop attracting
      # placement): forget with the rest of the per-peer state.
      self._disagg_stats.pop(peer.id(), None)
      try:
        await asyncio.wait_for(peer.disconnect(), timeout)
        return True
      except Exception:  # noqa: BLE001
        if DEBUG >= 1:
          print(f"[node {self.id}] disconnect error for {peer.id()}")
        return False

    async def connect_with_timeout(peer, timeout=5):
      try:
        await asyncio.wait_for(peer.connect(), timeout)
        return True
      except Exception:  # noqa: BLE001
        if DEBUG >= 1:
          print(f"[node {self.id}] connect error for {peer.id()}")
        return False

    await asyncio.gather(
      *(disconnect_with_timeout(p) for p in peers_to_disconnect),
      *(connect_with_timeout(p) for p in peers_to_connect),
    )
    for p in peers_added:
      # A newly (re)discovered peer is by definition serving again: clear
      # any stale drain announcement from its previous incarnation.
      self._draining_peers.pop(p.id(), None)
    if any(not self._peer_draining(p.id()) for p in peers_removed):
      # Sticky loss mark for the stall watchdog (see __init__): the dead
      # peer's breaker/health state was just forgotten with its handles.
      # Only UNPLANNED losses count — a peer that announced its drain left
      # gracefully and must not put the watchdog on a hair trigger.
      self.last_peer_loss_ts = time.monotonic()
    # Topology transitions are flight-recorder events (ISSUE 9): joins and
    # leaves — with leave cause drain vs loss — are the ring context every
    # incident reconstruction starts from.
    for p in peers_added:
      flightrec.record("topology_join", peer=p.id(), node=self.id)
    for p in peers_removed:
      flightrec.record(
        "topology_leave", peer=p.id(), node=self.id,
        cause="drain" if self._peer_draining(p.id()) else "loss",
      )
    self.peers = peers_unchanged + peers_to_connect
    return bool(peers_added or peers_removed or peers_updated)

  async def collect_topology(self, visited: set[str], max_depth: int = 4) -> Topology:
    next_topology = Topology()
    next_topology.update_node(self.id, self.device_capabilities)
    for peer in self.peers:
      # Seed each peer from the best knowledge we have: a previously merged
      # SELF-report beats the static capabilities on the discovery handle
      # (manual-config caps are placeholders; probed values must win or
      # nodes derive divergent partition maps — the ring corrupts).
      known = self.topology.nodes.get(peer.id())
      next_topology.update_node(peer.id(), known or peer.device_capabilities())
      next_topology.add_edge(self.id, peer.id(), peer.description())
    unreachable: set[str] = set()
    if max_depth > 0:
      prev_visited = set(visited)
      visited.add(self.id)
      visited.update(p.id() for p in self.peers)
      for peer in self.peers:
        if peer.id() in prev_visited:
          continue
        try:
          other = await asyncio.wait_for(peer.collect_topology(visited, max_depth - 1), timeout=5.0)
          next_topology.merge(peer.id(), other)
        except Exception as e:  # noqa: BLE001
          if DEBUG >= 1:
            print(f"[node {self.id}] error collecting topology from {peer.id()}: {e}")
          unreachable.add(peer.id())
      # A peer's merged view may carry stale hearsay about *us* (e.g. the
      # static capabilities its handle was created with); self-knowledge wins,
      # and every node applying this rule keeps partition tables convergent.
      next_topology.update_node(self.id, self.device_capabilities)
    # Evict unreachable peers AFTER all merges (another peer's hearsay would
    # otherwise resurrect a crashed node in the partition map): manual
    # discovery re-lists config peers forever, so a dead node would keep
    # owning layers and every replay would re-target it. It re-enters on the
    # next successful collect once it's actually back.
    for dead in unreachable:
      next_topology.nodes.pop(dead, None)
    # Draining peers drop out of the partition map the same way (no new
    # work lands on them) — their handles stay connected for in-flight
    # traffic and drain migrations. A peer's merged view may still carry
    # them as hearsay, so the removal runs after all merges, like eviction.
    for nid in list(self._draining_peers):
      if self._peer_draining(nid):
        next_topology.nodes.pop(nid, None)
    next_topology.active_node_id = self.topology.active_node_id or self.id
    self.topology = next_topology
    if self.topology_viz:
      self.topology_viz.update_visualization(self.topology, self.partitioning_strategy.partition(self.topology), self.id)
    return next_topology

  async def periodic_topology_collection(self, interval: float) -> None:
    while True:
      await asyncio.sleep(interval)
      try:
        did_change = await self.update_peers()
        if DEBUG >= 3:
          print(f"[node {self.id}] peers changed: {did_change}")
        # Collect EVERY cycle (reference node.py:520-531 does too), not only
        # on membership change: a view captured while a peer was still
        # booting (its collect RPC failing) would otherwise stay stale
        # forever, and two nodes with divergent views derive different
        # partition maps — the ring corrupts.
        await self.collect_topology(set())
        if did_change:
          self.select_best_inference_engine()
        await self._clock_sync_pass()
        if sched_admission.disagg_enabled() and self.peers:
          # Keep the placement cache warm so the submit path almost never
          # blocks on a pull (it still pulls on a cold/stale cache). Fire
          # and forget: one unresponsive peer keeps the waiter from
          # completing early, and its 1 s timeout must not stall the shared
          # periodic loop (clock sync + SLO tick run right after this).
          asyncio.create_task(self.collect_disagg_stats(timeout=1.0))
        if self.peers and prefix_registry.stale_remote_ids():
          # Prefix-advert staleness guard (ISSUE 13 satellite): an advert
          # past XOT_TPU_PREFIX_ADVERT_TTL_S stops steering placement
          # (``locate`` skips it) — re-pull so a live peer's advert comes
          # back fresh instead of aging out into routing blindness.
          asyncio.create_task(self.collect_cluster_prefixes(timeout=1.0))
        if slo_enabled():
          # SLO windows stay fresh without a dedicated timer (the engine
          # self-gates to its tick interval); the anomaly watchers run on
          # each tick with this node for cluster-context auto-bundles.
          # Dispatched to an executor thread: the tick deep-copies the
          # whole registry and computes every window report — tens of ms
          # on a busy node, which must not stall the event loop's RPC
          # handling (the loop rides along so watcher-triggered bundle
          # captures still schedule on it).
          loop = asyncio.get_event_loop()
          await loop.run_in_executor(None, lambda: slo_engine.maybe_tick(node=self, loop=loop))
      except Exception:  # noqa: BLE001
        if DEBUG >= 1:
          traceback.print_exc()

  async def _clock_sync_pass(self) -> None:
    """Keep per-peer clock-offset estimates fresh: health-check (the RPC
    that carries the NTP echo) any peer whose estimate is missing or older
    than ``XOT_TPU_CLOCKSYNC_INTERVAL_S`` (default 10 s). Discovery layers
    that already health-check every poll feed the estimator for free; this
    covers static/test topologies that never do."""
    try:
      interval = float(os.getenv("XOT_TPU_CLOCKSYNC_INTERVAL_S", "10"))
    except ValueError:
      interval = 10.0  # malformed knob must not kill the refresh loop
    stale = [
      p for p in self.peers
      if hasattr(p, "health_check") and ((age := clock_sync.age_s(p.id())) is None or age > interval)
    ]
    if stale:
      await asyncio.gather(*(p.health_check() for p in stale), return_exceptions=True)

  def select_best_inference_engine(self) -> None:
    """Hook for heterogeneous clusters; single-engine here (jax everywhere)."""

  # ------------------------------------------------------------- callbacks

  @property
  def on_token(self) -> AsyncCallbackSystem[str, str, list, bool]:
    return self._on_token

  @property
  def on_opaque_status(self) -> AsyncCallbackSystem[str, str, str]:
    return self._on_opaque_status

  def on_node_status(self, request_id: str, opaque_status: str) -> None:
    try:
      status_data = json.loads(opaque_status)
      status_type = status_data.get("type", "")
      if status_type == "node_status":
        # Join the originating node's trace (W3C traceparent propagation).
        if status_data.get("traceparent") and status_data.get("request_id"):
          tracer.request_context(status_data["request_id"], status_data["traceparent"])
        if status_data.get("status", "").startswith("start_"):
          self.topology.active_node_id = status_data.get("node_id")
        elif status_data.get("status", "").startswith("end_"):
          if status_data.get("node_id") == self.topology.active_node_id:
            self.topology.active_node_id = None
      elif status_type == "supported_inference_engines":
        node_id = status_data.get("node_id")
        engines = status_data.get("engines", [])
        self.topology_inference_engines_pool.append(engines)
      elif status_type == "download_progress":
        self.node_download_progress[status_data.get("node_id")] = status_data.get("progress")
      elif status_type == "cancel_request":
        # A peer's client disconnected: stop our share of the generation at
        # the next step/chunk boundary and drop the engine session.
        rid = status_data.get("request_id", "")
        if rid:
          self._cancel_locally(rid)
      elif status_type == "node_draining":
        # A peer announced graceful shutdown: keep its handle (in-flight
        # traffic and migrations still flow) but drop it from partition
        # maps so no NEW work routes there. TTL-bounded: a node that
        # announced but kept running re-enters the map after expiry.
        nid = status_data.get("node_id")
        if nid and nid != self.id:
          if nid not in self._draining_peers:
            flightrec.record("drain_announced", peer=nid, node=self.id)
          self._draining_peers[nid] = time.monotonic() + DRAINING_TTL_S
      elif status_type in ("metrics_pull", "metrics_snapshot"):
        # Cluster-wide /metrics aggregation rides the same opaque channel.
        self._handle_metrics_status(status_data)
      elif status_type in ("timeline_pull", "timeline_fragment"):
        # Cluster-scope request timelines ride it too (same pull pattern).
        self._handle_timeline_status(status_data)
      elif status_type in ("prefix_pull", "prefix_keys"):
        # Cluster prefix-registry adverts (ISSUE 6: KV memory hierarchy).
        self._handle_prefix_status(status_data)
      elif status_type in ("slo_pull", "slo_report"):
        # Cluster SLO reports ride the same pull pattern (ISSUE 9).
        self._handle_slo_status(status_data)
      elif status_type in ("disagg_pull", "disagg_stats"):
        # Disagg role/capacity adverts for placement (ISSUE 10).
        self._handle_disagg_status(status_data)
      elif status_type in ("bundle_pull", "bundle_part"):
        # Incident-bundle assembly (ISSUE 9).
        self._handle_bundle_status(status_data)
      elif status_type in ("programs_pull", "programs_report"):
        # Device-program ledger snapshots (ISSUE 19).
        self._handle_programs_status(status_data)
      if self.topology_viz:
        self.topology_viz.update_visualization(self.topology, self.partitioning_strategy.partition(self.topology), self.id)
    except Exception:  # noqa: BLE001
      if DEBUG >= 1:
        traceback.print_exc()

  def trigger_on_token_callbacks(self, request_id: str, tokens: list[int], is_finished: bool, start_pos: int | None = None) -> None:
    """Single choke point for client-facing token delivery.

    With ``start_pos`` (the absolute completion index of ``tokens[0]``),
    tokens below the request's high-water mark are dropped as replayed
    duplicates, and tokens AHEAD of it (deliveries reordered across
    channels during a failover) are held until the gap fills — the client
    transcript is always the exact in-order stream. Without a position
    (status-only events, legacy senders) tokens pass through and advance
    the mark."""
    if start_pos is not None and (tokens or is_finished):
      emitted = self._emitted_counts.get(request_id, 0)
      if start_pos > emitted:
        held = self._pending_chunks.setdefault(request_id, {})
        cur = held.get(start_pos)
        if cur is None or len(tokens) > len(cur[0]):
          # Same-start duplicates (zombie vs regenerated stream): keep the
          # longer span; OR the finish flags so neither signal is lost.
          held[start_pos] = (list(tokens), is_finished or (cur[1] if cur else False))
        elif is_finished and not cur[1]:
          held[start_pos] = (cur[0], True)
        self._arm_gap_flush(request_id)
        return
      skip = emitted - start_pos
      if skip > 0:
        tokens = tokens[skip:]
        if not tokens and not is_finished:
          return
        start_pos = emitted
      self._emitted_counts[request_id] = max(emitted, start_pos + len(tokens))
    elif tokens:
      self._emitted_counts[request_id] = self._emitted_counts.get(request_id, 0) + len(tokens)
    if tokens and request_id not in self._ttft_observed:
      # First client-visible token for a request THIS node originated (t0 is
      # only set by process_prompt): works for local sampling, the batched
      # scheduler (which pre-claims the observation), and ring deployments
      # where the first token arrives over a SendResult broadcast.
      t0 = self._request_t0.get(request_id)
      if t0 is not None:
        self._ttft_observed.add(request_id)
        metrics.observe_hist("ttft_seconds", time.perf_counter() - t0)
    self._on_token.trigger_all(request_id, tokens, is_finished)
    if is_finished:
      # A migrated row's remote finish releases its origin-side waiter; a
      # replayed/migrated request that still finished counts as recovered.
      event = self._migrated.get(request_id)
      if event is not None:
        event.set()
      if request_id in self._recovering:
        self._recovering.discard(request_id)
        metrics.inc("requests_recovered_total")
      # Keep the high-water mark as a tombstone so a straggling zombie
      # broadcast can't reset it and re-deliver the stream; it expires on
      # the response-timeout horizon (origin nodes never run
      # _finish_request for remote flows).
      self._pending_chunks.pop(request_id, None)
      self._disarm_gap_flush(request_id)
      self._expire_dedup_state(request_id)
      return
    # Deliver any held chunk that now abuts or overlaps the advanced mark
    # (recursion re-applies the duplicate trim and continues the chain).
    pend = self._pending_chunks.get(request_id)
    if pend:
      emitted = self._emitted_counts.get(request_id, 0)
      for sp in sorted(pend):
        if sp <= emitted:
          held_tokens, held_fin = pend.pop(sp)
          if not pend:
            self._pending_chunks.pop(request_id, None)
          self.trigger_on_token_callbacks(request_id, held_tokens, held_fin, start_pos=sp)
          break
    if request_id not in self._pending_chunks:
      self._disarm_gap_flush(request_id)  # all gaps filled naturally
    elif start_pos is not None and tokens:
      # Progress was made but a LATER hole still blocks held chunks: restart
      # the window so that hole gets its own full GAP_FLUSH_S, not the stale
      # remainder of the previous hole's timer.
      self._disarm_gap_flush(request_id)
      self._arm_gap_flush(request_id)

  def _expire_dedup_state(self, request_id: str) -> None:
    def clear() -> None:
      self._emitted_counts.pop(request_id, None)
      self._pending_chunks.pop(request_id, None)
      # TTFT bookkeeping rides the same horizon: an origin node that only
      # forwards never reaches _finish_request for this id.
      self._request_t0.pop(request_id, None)
      self._ttft_observed.discard(request_id)
    try:
      asyncio.get_running_loop().call_later(RESPONSE_TIMEOUT_HORIZON_S, clear)
    except RuntimeError:  # no loop (sync callers in tests): clear later is moot
      pass

  def _arm_gap_flush(self, request_id: str) -> None:
    """Bound how long held chunks wait for a gap to fill (a lost broadcast
    would otherwise stall the stream forever): after GAP_FLUSH_S, release
    everything held in position order, accepting the hole. The timer is
    cancelled when the gap fills naturally (_disarm_gap_flush) so a stale
    timer can never force-flush a LATER hole early."""
    if request_id in self._gap_flush_timers:
      return
    def flush() -> None:
      self._gap_flush_timers.pop(request_id, None)
      pend = self._pending_chunks.pop(request_id, None)
      if not pend:
        return
      for sp in sorted(pend):
        held_tokens, held_fin = pend[sp]
        self._emitted_counts[request_id] = max(self._emitted_counts.get(request_id, 0), sp)  # jump the mark over the hole
        self.trigger_on_token_callbacks(request_id, held_tokens, held_fin, start_pos=sp)
    try:
      self._gap_flush_timers[request_id] = asyncio.get_running_loop().call_later(GAP_FLUSH_S, flush)
    except RuntimeError:
      pass

  def _disarm_gap_flush(self, request_id: str) -> None:
    handle = self._gap_flush_timers.pop(request_id, None)
    if handle is not None:
      handle.cancel()

  def handle_remote_result(self, request_id: str, result, is_finished: bool, start_pos: int | None = None) -> None:
    """Results arriving over the wire (gRPC SendResult) — token lists route
    through the dedup choke point; tensor payloads pass straight through."""
    if isinstance(result, list):
      self.trigger_on_token_callbacks(request_id, result, is_finished, start_pos=start_pos)
    else:
      self._on_token.trigger_all(request_id, result, is_finished)

  async def broadcast_result(self, request_id: str, result: list[int], is_finished: bool, start_pos: int | None = None) -> None:
    async def send_result_to_peer(peer):
      try:
        await asyncio.wait_for(peer.send_result(request_id, result, is_finished, start_pos=start_pos), timeout=15.0)
      except Exception:  # noqa: BLE001
        # A lost result broadcast is what the gap-flush machinery papers
        # over — count it so stream stalls are attributable from /metrics.
        metrics.inc("peer_broadcast_failures_total", labels={"kind": "result"})
        if DEBUG >= 1:
          print(f"[node {self.id}] result broadcast to {peer.id()} failed")

    await asyncio.gather(*(send_result_to_peer(p) for p in self.peers), return_exceptions=True)

  async def broadcast_opaque_status(self, request_id: str, status: str) -> None:
    async def send_status_to_peer(peer):
      try:
        await asyncio.wait_for(peer.send_opaque_status(request_id, status), timeout=15.0)
      except Exception:  # noqa: BLE001
        metrics.inc("peer_broadcast_failures_total", labels={"kind": "status"})
        if DEBUG >= 1:
          print(f"[node {self.id}] status broadcast to {peer.id()} failed")

    await asyncio.gather(*(send_status_to_peer(p) for p in self.peers), return_exceptions=True)
    # Local callbacks fire too (the reference triggers its own handlers last).
    self._on_opaque_status.trigger_all(request_id, status)

  @property
  def current_topology(self) -> Topology:
    return self.topology
