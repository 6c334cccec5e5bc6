"""One-chunk-lookahead pipelined decode (inference/batch_scheduler.py).

The correctness contract: with ``XOT_TPU_SCHED_LOOKAHEAD`` on (the default),
the batched server's output is TOKEN-IDENTICAL to the synchronous loop —
same compiled programs, same key-split order, same sampling; only the
host/device schedule changes. A row that finishes inside an in-flight chunk
is speculatively decoded one extra chunk whose tokens are dropped on read;
pages release cleanly at the settle boundary. An admission does not drain the
pipeline (ISSUE 51): its prefill group is enqueued BEHIND the chunk in flight,
that chunk is settled under the group, and the next chunk is enqueued behind
the group with the first tokens merged into its chain token on the device. What
still settles the chunk first is what needs its settled state (the tests at the
end of this file, one a reason).
"""

import asyncio
import threading
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_batched import _single_row_reference
from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params

CFG = tiny_test_config(n_layers=2, max_seq_len=128)
KEY = jax.random.PRNGKey(0)
PROMPTS = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]


def _engine(params, shard, cfg=CFG):
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  return engine


def _serve(server, prompts, n_gen, temp=0.0, eos_ids=(), max_tokens=None):
  """Run ``prompts`` concurrently through ``server``; returns (outputs,
  per-request emitted streams)."""
  streams: dict[str, list] = {}

  async def run():
    def emit(rid, toks, finished):
      streams.setdefault(rid, []).extend(toks)

    return await asyncio.gather(
      *(
        server.submit(
          f"r{i}", np.asarray(p, np.int32),
          max_tokens=max_tokens[i] if max_tokens else n_gen,
          temp=temp, top_k=35, eos_ids=eos_ids, emit=emit,
        )
        for i, p in enumerate(prompts)
      )
    )

  outs = asyncio.run(run())
  return outs, [streams[f"r{i}"] for i in range(len(prompts))]


def _ab(engine, prompts, n_gen, *, chunk=2, n_slots=4, temp=0.0, eos_ids=(), seed=None):
  """Serve the same prompts with lookahead ON then OFF; assert identical
  outputs and streams; return the (shared) outputs."""
  outs = {}
  for mode in (True, False):
    if seed is not None:
      engine._key = jax.random.PRNGKey(seed)  # identical key schedules for the sampled A/B
    server = BatchedServer(engine, n_slots=n_slots, chunk=chunk, lookahead=mode)
    assert server.lookahead is mode
    outs[mode], streams = _serve(server, prompts, n_gen, temp=temp, eos_ids=eos_ids)
    for o, s in zip(outs[mode], streams):
      assert s == o  # emitted stream matches the resolved result
    server.shutdown()
  assert outs[True] == outs[False], f"lookahead diverged: {outs[True]} != {outs[False]}"
  return outs[True]


def test_lookahead_env_knob(monkeypatch):
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  assert BatchedServer(engine).lookahead  # default ON
  monkeypatch.setenv("XOT_TPU_SCHED_LOOKAHEAD", "0")
  assert not BatchedServer(engine).lookahead
  monkeypatch.setenv("XOT_TPU_SCHED_LOOKAHEAD", "1")
  assert BatchedServer(engine).lookahead


def test_lookahead_ab_paged_int8kv(monkeypatch):
  """A/B over the DEFAULT layout at the serving quant point: paged pool with
  int8-KV pages — token-identical to the sync loop and to solo greedy."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_KV_QUANT", "int8")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  n_gen = 6
  expected = [_single_row_reference(params, shard, p, n_gen - 1) for p in PROMPTS]
  outs = _ab(engine, PROMPTS, n_gen)
  assert outs == expected


def test_lookahead_ab_dense(monkeypatch):
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  n_gen = 6
  expected = [_single_row_reference(params, shard, p, n_gen - 1) for p in PROMPTS]
  outs = _ab(engine, PROMPTS, n_gen)
  assert outs == expected


def test_lookahead_ab_sampled_same_key_schedule(monkeypatch):
  """SAMPLED requests stay identical too: the key-split order is one split
  per dispatched chunk on the event-loop thread, and the speculative chunk
  (if any) splits only AFTER every emitted token's chunk — so reseeding the
  engine gives byte-identical sampled streams in both modes."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  outs = _ab(engine, [[5, 17, 2, 99]], 9, temp=0.8, seed=123)
  assert len(outs[0]) == 9


class _MeshStub:
  """Minimal engine facade for driving BatchedServer over a mesh backend.

  pp-only / sp-only plans run fully-manual shard_map on the CPU test mesh
  (the engine-level pp×tp / sp×tp compositions need partial-manual shard_map
  and keep their probe-skips in test_pp_batch / test_sp_paged)."""

  def __init__(self, cfg, shard):
    self.cfg = cfg
    self.max_seq_len = cfg.max_seq_len
    self._effective_shard = shard
    self._key = jax.random.PRNGKey(0)
    self._key_lock = threading.Lock()
    self.executor = ThreadPoolExecutor(max_workers=1)
    self.batch_ops = None  # wired by the test after backend construction

  def split_key(self):
    with self._key_lock:
      self._key, sub = jax.random.split(self._key)
      return sub


def test_lookahead_ab_pp2(monkeypatch):
  """pp=2 pipelined backend chains device tokens through the ring schedule:
  lookahead == sync == solo greedy (dense slot cache over the pp mesh)."""
  from xotorch_support_jetson_tpu.inference.batch_ops import PPBatchOps
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
  from xotorch_support_jetson_tpu.parallel.pp_batch import PPBatchedServing

  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  cfg = tiny_test_config(n_layers=4, max_seq_len=64)
  params, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  stub = _MeshStub(cfg, shard)
  stub.batch_ops = PPBatchOps(stub, PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2))
  n_gen = 5
  expected = [_single_row_reference(params, shard, p, n_gen - 1, cfg=cfg) for p in PROMPTS]
  outs = _ab(stub, PROMPTS, n_gen, n_slots=4)
  assert outs == expected


def test_lookahead_ab_sp2(monkeypatch):
  """sp=2 striped-pool backend: device token chaining across the sp mesh
  stays token-identical (paged pool, page-slot axis striped over sp)."""
  from xotorch_support_jetson_tpu.inference.batch_ops import SPBatchOps
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
  from xotorch_support_jetson_tpu.parallel.sp_batch import SPBatchedServing
  from xotorch_support_jetson_tpu.parallel.sp_serving import SPServing

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  cfg = tiny_test_config(n_layers=2, max_seq_len=64)
  params, shard = full_model_params(jax.random.PRNGKey(9), cfg, "m")
  stub = _MeshStub(cfg, shard)
  stub.batch_ops = SPBatchOps(stub, SPBatchedServing(SPServing(build_mesh(MeshPlan(sp=2)), cfg, params, 2, True, True)))
  n_gen = 5
  expected = [_single_row_reference(params, shard, p, n_gen - 1, cfg=cfg) for p in PROMPTS]
  outs = _ab(stub, PROMPTS, n_gen, n_slots=4)
  assert outs == expected


def test_lookahead_eos_at_chunk_boundary(monkeypatch):
  """EOS landing exactly at a chunk boundary exercises the overrun-drop
  path: the speculative chunk N+1 was already dispatched when chunk N's EOS
  is discovered; its tokens are discarded, the row releases at the N+1
  settle, and the pool ends the run fully recovered."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  solo = _single_row_reference(params, shard, [3, 25, 9], 6)
  eos = solo[2]  # prefill token + one chunk of 2 → EOS is the chunk's LAST token

  server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=True)
  dispatches = []
  orig = server.ops.paged_batch_decode
  server.ops.paged_batch_decode = lambda *a, **k: dispatches.append(1) or orig(*a, **k)

  outs, _ = _serve(server, [[3, 25, 9]], 20, eos_ids=(eos,))
  assert outs[0] == solo[:3] and outs[0][-1] == eos
  # The lookahead really did decode one speculative chunk past the EOS
  # chunk (2 decode dispatches for 1 emitted chunk) and dropped it.
  assert len(dispatches) == 2, dispatches
  assert all(s is None for s in server.slots)
  assert not server._h_occupied.any()
  # Every page recovered: free list + prefix-cache LRU cover the whole pool.
  alloc = server.allocator
  assert alloc.n_available == alloc.n_pages - 1
  server.shutdown()


def test_lookahead_cancel_mid_stream():
  """cancel() during a lookahead steady state still resolves at a dispatch
  boundary (the in-flight speculative chunk is dropped) and frees the slot
  for the next request."""
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=1, chunk=2, lookahead=True)
  solo = _single_row_reference(params, shard, [3, 25, 9], 4)

  async def run():
    started = asyncio.Event()

    def emit(rid, toks, fin):
      if rid == "long" and toks:
        started.set()

    long_task = asyncio.create_task(
      server.submit("long", np.asarray([3, 25, 9], np.int32), max_tokens=500, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    await asyncio.wait_for(started.wait(), timeout=30)
    server.cancel("long")
    out_long = await asyncio.wait_for(long_task, timeout=30)
    assert len(out_long) < 500

    out_next = await asyncio.wait_for(
      server.submit("next", np.asarray([3, 25, 9], np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None),
      timeout=30,
    )
    assert out_next == solo

  asyncio.run(run())
  server.shutdown()


def test_lookahead_page_starved_row(monkeypatch):
  """A page-starved row under the extra-chunk headroom reservation: the
  starved row skips chunks (its speculative advance included) until the
  other row's finish frees pages, then completes token-identically."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "8")
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "5")  # 4 grantable pages + trash
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=True)
  before = gm.counter_value("scheduler_page_starved_total")

  # Sized for contention: row A (6-token prompt) wants its 3rd page around
  # position 16 while row B (2-token prompt, staggered page boundaries)
  # still holds 2 of the 4 grantable pages — A starves, keeps skipping
  # chunks (speculative advance included), and resumes when B finishes.
  pa, pb = [3, 25, 9, 7, 1, 2], [9, 4]
  expected = [
    _single_row_reference(params, shard, pa, 19),
    _single_row_reference(params, shard, pb, 13),
  ]
  outs, _ = _serve(server, [pa, pb], 0, max_tokens=[20, 14])
  assert outs == expected
  assert gm.counter_value("scheduler_page_starved_total") > before
  server.shutdown()


def test_lookahead_keeps_chaining_at_saturation(monkeypatch):
  """A backlog with ZERO free slots must not drain the pipeline: admission
  cannot make progress anyway, so dispatches keep chaining (the saturated
  regime is exactly where the overlap pays). The queued request still
  admits at the first boundary after a slot frees — one chunk later at
  most."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=1, chunk=2, lookahead=True)
  solo_long = _single_row_reference(params, shard, [3, 25, 9], 40)
  solo_next = _single_row_reference(params, shard, [7, 1, 88, 42, 5], 4)

  chained_flags = []
  orig_dispatch = server._dispatch_decode

  async def spy(plan, inflight):
    rec = await orig_dispatch(plan, inflight)
    chained_flags.append(inflight is not None)  # dispatched on top of an in-flight chunk: the device never idled
    return rec

  server._dispatch_decode = spy

  async def run():
    started = asyncio.Event()

    def emit(rid, toks, fin):
      if rid == "long" and toks:
        started.set()

    long_task = asyncio.create_task(
      server.submit("long", np.asarray([3, 25, 9], np.int32), max_tokens=41, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    await asyncio.wait_for(started.wait(), timeout=30)
    # The single slot is resident: this submission queues with NO free slot.
    next_task = asyncio.create_task(
      server.submit("next", np.asarray([7, 1, 88, 42, 5], np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    return await asyncio.wait_for(long_task, timeout=60), await asyncio.wait_for(next_task, timeout=60)

  out_long, out_next = asyncio.run(run())
  assert out_long == solo_long
  assert out_next == solo_next
  # ~20 chunks for the long request: the vast majority must have dispatched
  # CHAINED despite the queued backlog (pre-fix, every dispatch after the
  # second submit degraded to synchronous).
  assert chained_flags.count(True) >= 10, chained_flags
  server.shutdown()


def test_parked_drain_gate_retries_on_availability_change(monkeypatch):
  """The drain gate retries parked requests only when page availability
  MOVED since the last admission pass — an unchanged allocator would just
  replay the pass that parked everyone (and recorded demands can go stale
  against the live prefix cache, so the retry recomputes rather than the
  gate trusting them). Steady page-bound saturation keeps chaining; every
  release/donation event buys exactly one drain."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "8")
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "6")  # 5 grantable pages
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=True)
  server._ensure_cache()
  assert server.allocator.n_available == 5

  class _Parked:
    page_demand = 3

  assert not server._parked_admissible()  # empty deque
  server._parked.append(_Parked())
  # Baseline never recorded yet: drain once.
  assert server._parked_admissible()
  server._parked_avail_seen = server.allocator.n_available  # admission pass looked
  assert not server._parked_admissible()  # nothing changed: keep chaining
  got = server.allocator.alloc(2)
  # A DECREASE (resident row growth) cannot make a parked demand coverable:
  # no drain — the gate silently re-baselines instead.
  assert not server._parked_admissible()
  server.allocator.free(got)  # a release event (increase): retry once
  assert server._parked_admissible()
  server.shutdown()


def test_lookahead_keeps_chaining_when_parked_page_bound(monkeypatch):
  """The page-bound saturated regime: a request PARKS on page scarcity while
  a slot is free. Draining cannot admit it (its demand exceeds the
  allocator's availability), so the pipeline must keep chaining; the parked
  request admits at the first boundary after the resident row's finish
  frees enough pages, and completes token-identically."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "8")
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "5")  # 4 grantable pages + trash
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=True)
  before_parked = gm.counter_value("scheduler_parked_total")

  p_long = [3, 25, 9, 7, 1, 2]  # grows to all 4 pages over 20 tokens
  p_big = [(5 * i) % 120 + 1 for i in range(17)]  # needs 3 pages at admission
  solo_long = _single_row_reference(params, shard, p_long, 19)
  solo_big = _single_row_reference(params, shard, p_big, 4)

  chained_flags = []
  orig_dispatch = server._dispatch_decode

  async def spy(plan, inflight):
    rec = await orig_dispatch(plan, inflight)
    chained_flags.append(inflight is not None)  # dispatched on top of an in-flight chunk: the device never idled
    return rec

  server._dispatch_decode = spy

  async def run():
    tokens_seen = 0
    grown = asyncio.Event()

    def emit(rid, toks, fin):
      nonlocal tokens_seen
      if rid == "long":
        tokens_seen += len(toks)
        if tokens_seen >= 6:  # long row holds >=2 pages now: 'big' must park
          grown.set()

    long_task = asyncio.create_task(
      server.submit("long", np.asarray(p_long, np.int32), max_tokens=20, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    await asyncio.wait_for(grown.wait(), timeout=30)
    big_task = asyncio.create_task(
      server.submit("big", np.asarray(p_big, np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    return await asyncio.wait_for(long_task, timeout=60), await asyncio.wait_for(big_task, timeout=60)

  out_long, out_big = asyncio.run(run())
  assert out_long == solo_long
  assert out_big == solo_big
  assert gm.counter_value("scheduler_parked_total") > before_parked  # it really parked
  # Chaining continued through the parked window (pre-fix, a parked waiter
  # with a free slot forced a synchronous settle at every boundary).
  assert chained_flags.count(True) >= 4, chained_flags
  server.shutdown()


def test_lookahead_admission_joins_at_dispatch_boundary(monkeypatch):
  """A request arriving while a lookahead chunk is in flight admits at the
  next dispatch boundary, its prefill group enqueued behind that chunk — it
  does NOT wait for the resident stream to finish (the TTFT contract)."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=True)
  solo_long = _single_row_reference(params, shard, [3, 25, 9], 39)
  solo_short = _single_row_reference(params, shard, [7, 1, 88, 42, 5], 4)

  async def run():
    started = asyncio.Event()
    finish_order = []

    def emit(rid, toks, fin):
      if rid == "long" and toks:
        started.set()
      if fin:
        finish_order.append(rid)

    long_task = asyncio.create_task(
      server.submit("long", np.asarray([3, 25, 9], np.int32), max_tokens=40, temp=0.0, top_k=35, eos_ids=(), emit=emit)
    )
    await asyncio.wait_for(started.wait(), timeout=30)  # steady lookahead now
    out_short = await asyncio.wait_for(
      server.submit("short", np.asarray([7, 1, 88, 42, 5], np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=emit),
      timeout=30,
    )
    out_long = await asyncio.wait_for(long_task, timeout=30)
    return out_short, out_long, finish_order

  out_short, out_long, finish_order = asyncio.run(run())
  assert out_short == solo_short
  assert out_long == solo_long
  # The short request joined the resident batch and finished FIRST — it was
  # admitted mid-stream, not serialized behind the long one.
  assert finish_order[0] == "short"
  server.shutdown()


# ------------------------------------------------------------------ an admission rides behind the chunk in flight (ISSUE 51)

_ENQUEUES = {
  "paged_batch_decode": "decode", "batch_decode": "decode", "mixed_paged_batch_decode": "decode", "spec_paged_batch_decode": "decode", "spec_batch_decode": "decode",
  "prefill_into_pages_many_sampled": "group", "prefill_into_pages_many": "group", "prefill_into_slots_sampled": "group", "prefill_into_slots": "group",
}


class _RecordingOps:
  """The server's ``ops`` with every program call logged as it is handed to the device: ("enqueue", "decode" |
  "group", the tick that issued it). Everything else passes through."""

  def __init__(self, server, events):
    self._ops, self._server, self._events, self.mixed_ticks = server.ops, server, events, []

  def __getattr__(self, name):
    attr = getattr(self._ops, name)
    if name not in _ENQUEUES:
      return attr

    def call(*a, **k):
      self._events.append(("enqueue", _ENQUEUES[name], self._server._tick))
      if name == "mixed_paged_batch_decode":
        self.mixed_ticks.append(self._server._tick)
      return attr(*a, **k)

    return call


def _record(server) -> list:
  """The order in which the loop enqueues programs and reads their results back: ("enqueue", kind, tick) from a
  recording ``ops``, ("readback", tick) from the ``readback`` phase that waits for that tick's program."""
  events: list = []
  server.ops = _RecordingOps(server, events)
  phase = server._phase

  @contextmanager
  def recorded(name, **args):
    if name == "readback":
      events.append(("readback", args.get("tick")))
    with phase(name, **args):
      yield

  server._phase = recorded
  return events


def _at(events, event) -> int:
  return events.index(event)


def _behind():
  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  return {q: gm.counter_value("sched_dispatches_total", labels={"queue": q}) for q in ("behind", "empty")}


async def _admit_mid_run(server, second, *, long_tokens=40, after=3, **kw):
  """A long greedy request, and ``second`` (prompt, max_tokens) submitted once the long one has streamed ``after``
  tokens, i.e. into a steady pipeline. Returns (long's output, second's output)."""
  seen = 0
  go = asyncio.Event()

  def emit(rid, toks, fin):
    nonlocal seen
    if rid == "long":
      seen += len(toks)
      if seen >= after:
        go.set()

  long_task = asyncio.create_task(server.submit("long", np.asarray([3, 25, 9], np.int32), max_tokens=long_tokens, temp=0.0, top_k=35, eos_ids=(), emit=emit))
  await asyncio.wait_for(go.wait(), timeout=60)
  prompt, max_tokens = second
  out_second = await asyncio.wait_for(server.submit("second", np.asarray(prompt, np.int32), max_tokens=max_tokens, top_k=35, eos_ids=(), emit=emit, **{"temp": 0.0, **kw}), timeout=60)
  return await asyncio.wait_for(long_task, timeout=60), out_second


def test_admission_is_enqueued_behind_the_chunk_in_flight(monkeypatch):
  """The order on the device queue, read off a recording ``ops``: the group of a request that arrives into a steady
  pipeline is enqueued BEFORE the chunk in flight is read back (so that chunk's settle runs under the group), and the
  next chunk is enqueued BEFORE the group is read back (so the group's settle runs under that chunk). The counter
  says the same from inside: those dispatches count as ``behind``."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)
  events = _record(server)
  before = _behind()
  out_long, out_second = asyncio.run(_admit_mid_run(server, ([7, 1, 88, 42, 5], 5)))
  server.shutdown()
  assert out_long == _single_row_reference(params, shard, [3, 25, 9], 39)
  assert out_second == _single_row_reference(params, shard, [7, 1, 88, 42, 5], 4)
  groups = [e for e in events if e[:2] == ("enqueue", "group")]
  assert len(groups) == 2  # the long request's own, onto an idle server, and the second's
  g = _at(events, groups[1])
  chunk_before = [e for e in events[:g] if e[:2] == ("enqueue", "decode")][-1]
  chunk_after = next(e for e in events[g:] if e[:2] == ("enqueue", "decode"))
  assert g < _at(events, ("readback", chunk_before[2])), events  # steps 1-2: the group went in behind chunk N, N is settled under it
  assert _at(events, chunk_after) < _at(events, ("readback", groups[1][2])), events  # step 3: chunk N+1 went in behind the group
  # ... and the very first admission, onto an idle server, is no different: the first chunk is enqueued behind its group
  assert _at(events, next(e for e in events if e[:2] == ("enqueue", "decode"))) < _at(events, ("readback", groups[0][2]))
  grew = {q: n - before[q] for q, n in _behind().items()}
  assert grew["behind"] + grew["empty"] == len([e for e in events if e[0] == "enqueue"])
  assert grew["empty"] <= 2 and grew["behind"] >= 18, grew  # ~20 chunks and 2 groups: all but the first ride behind something


LONG_PROMPT = [(7 * i) % 120 + 1 for i in range(50)]  # 4 slices at a prefill chunk of 16


@pytest.mark.parametrize("layout", ["dense", "paged", "mixed"])
def test_lookahead_ab_admissions_arriving_mid_run(monkeypatch, layout):
  """Greedy A/B against ``XOT_TPU_SCHED_LOOKAHEAD=0`` with requests arriving while others decode: the schedule
  differs (groups ride behind chunks, first tokens reach the next chunk on the device), the tokens do not. ``mixed``:
  the late arrival's prompt is sliced into mixed ticks, so it claims its slot without forcing a settle and only its
  final slice goes through a group."""
  monkeypatch.setenv("XOT_TPU_PAGED", "0" if layout == "dense" else "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  monkeypatch.setenv("XOT_TPU_MIXED_TICK", "1" if layout == "mixed" else "0")
  if layout == "mixed":
    monkeypatch.setenv("XOT_TPU_PREFILL_CHUNK", "16")
  second = LONG_PROMPT if layout == "mixed" else [7, 1, 88, 42, 5]
  params, shard = full_model_params(KEY, CFG)
  from xotorch_support_jetson_tpu.utils.programs import ledger

  engine = _engine(params, shard)
  outs, mixed = {}, {}
  for mode in (True, False):
    server = BatchedServer(engine, n_slots=3, chunk=2, lookahead=mode)
    events = _record(server)

    async def run(server=server):
      (out_long, out_second), out_third = await asyncio.gather(
        _admit_mid_run(server, (second, 9)),
        server.submit("third", np.asarray([100], np.int32), max_tokens=1, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None),  # one token: a row that ends at its first
      )
      return out_long, out_second, out_third

    before, mixed_before = _behind(), ledger.dispatch_count("decode.mixed_paged_batch")
    outs[mode] = asyncio.run(run())
    assert (_behind()["behind"] > before["behind"]) is mode  # lookahead off: the strictly synchronous tick, nothing rides behind anything
    assert (("enqueue", "decode") in {(e[0], e[1]) for e in events}) and any(e[:2] == ("enqueue", "group") for e in events)
    assert server.ops._ops is engine.batch_ops
    if mode and layout == "mixed":
      # the final slice's group goes in behind the chunk that carries the last intermediate slice (its end is the
      # host's own number), not a chunk later: the tick a drain at every boundary gave it
      final = [e for e in events if e[:2] == ("enqueue", "group")][-1]
      last_slice = max(t for t in server.ops.mixed_ticks if t < final[2])
      assert _at(events, final) < _at(events, ("readback", last_slice)), events
    mixed[mode] = ledger.dispatch_count("decode.mixed_paged_batch") - mixed_before
    assert all(s is None for s in server.slots) and not server._pending and server._chain is None
    server.shutdown()
  assert outs[True] == outs[False]
  assert all((n > 0) is (layout == "mixed") for n in mixed.values()), mixed
  assert list(outs[True]) == [_single_row_reference(params, shard, [3, 25, 9], 39), _single_row_reference(params, shard, second, 8), _single_row_reference(params, shard, [100], 0)]


def test_sampled_stream_admitted_behind_a_chunk_is_identical_per_request(monkeypatch):
  """A SAMPLED request admitted behind a chunk draws what it draws under the synchronous tick: the key splits stay
  on the loop thread in the order N, G, N+1, so from its group's split on it sees one split a dispatched chunk in
  either mode. (Per request: the chain is reseeded at its group's staging in both modes, since the number of splits
  BEFORE that depends on which boundary the arrival met — the module docstring's caveat.)"""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  outs = {}
  for mode in (True, False):
    server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=mode)
    stage_group = server._stage_group

    def reseeded(group, *a, stage_group=stage_group, **k):
      if any(r.req.request_id == "second" for r in group):
        engine._key = jax.random.PRNGKey(4242)
      return stage_group(group, *a, **k)

    server._stage_group = reseeded
    outs[mode] = asyncio.run(_admit_mid_run(server, ([5, 17, 2, 99], 9), temp=0.8))
    server.shutdown()
  assert outs[True] == outs[False]
  assert len(outs[True][1]) == 9 and outs[True][0] == _single_row_reference(params, shard, [3, 25, 9], 39)


# ------------------------------------------------------------------ what still settles the chunk in flight first, one test a reason


def _settled_first(events, group) -> bool:
  """Was every decode chunk enqueued before ``group`` read back before it? (The group went onto a drained pipeline.)"""
  g = _at(events, group)
  return all(("readback", e[2]) in events[:g] for e in events[:g] if e[:2] == ("enqueue", "decode"))


def test_lookahead_off_is_the_strictly_synchronous_tick(monkeypatch):
  """``XOT_TPU_SCHED_LOOKAHEAD=0``: every program is read back before the next is enqueued — the reference schedule."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=False)
  events = _record(server)
  before = _behind()
  asyncio.run(_admit_mid_run(server, ([7, 1, 88, 42, 5], 5), long_tokens=12))
  server.shutdown()
  assert [e[0] for e in events] == ["enqueue", "readback"] * (len(events) // 2), events
  assert all(a[2] == b[1] for a, b in zip(events[::2], events[1::2]))
  assert _behind()["behind"] == before["behind"]


def test_a_preemption_settles_the_chunk_in_flight_first(monkeypatch):
  """A waiter that outranks a resident row with no slot free: the victim's row is in the chunk in flight, so that
  chunk is settled before the admission pass extracts it; the preempting request's group goes onto a drained pipeline."""
  from xotorch_support_jetson_tpu.inference.qos import QosConfig, QosPolicy
  from xotorch_support_jetson_tpu.utils.metrics import metrics as gm

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=1, chunk=2, lookahead=True, qos=QosPolicy(QosConfig(aging_s=10_000.0)))
  events = _record(server)
  preempted = gm.counter_value("qos_preemptions_total")

  async def run():
    started = asyncio.Event()

    def emit(rid, toks, fin):
      if rid == "bg" and toks:
        started.set()

    bg = asyncio.create_task(server.submit("bg", np.asarray([3, 25, 9], np.int32), max_tokens=24, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch", tenant="bulk"))
    await asyncio.wait_for(started.wait(), timeout=60)
    vip = await asyncio.wait_for(server.submit("vip", np.asarray([7, 1, 88, 42, 5], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="interactive", tenant="vip"), timeout=60)
    return await asyncio.wait_for(bg, timeout=60), vip

  out_bg, out_vip = asyncio.run(run())
  server.shutdown()
  assert gm.counter_value("qos_preemptions_total") == preempted + 1
  assert out_bg == _single_row_reference(params, shard, [3, 25, 9], 23) and out_vip == _single_row_reference(params, shard, [7, 1, 88, 42, 5], 3)
  groups = [e for e in events if e[:2] == ("enqueue", "group")]
  assert len(groups) == 3 and _settled_first(events, groups[1]), events  # bg, vip (after the settle that freed bg's row), bg's resume


def test_a_parked_waiter_only_the_finishing_rows_can_cover_settles_the_chunk_first(monkeypatch):
  """A waiter parked for pages, a slot free, and page availability has moved: the pass that runs behind the chunk in
  flight still cannot cover it (the pages it needs are held by a row that ends inside that chunk, which the host does
  not know yet), so the chunk is settled and the pass repeated — the waiter joins the next chunk, as under a drain at
  every boundary, and not one later."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "8")
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "5")  # 4 grantable pages + trash
  params, shard = full_model_params(KEY, CFG)
  p_long = [3, 25, 9, 7, 1, 2]
  p_big = [(5 * i) % 120 + 1 for i in range(17)]  # needs 3 pages at admission
  solo_long = _single_row_reference(params, shard, p_long, 25)
  # the long request ends by EOS on the last token of a chunk (of 2, after the first token), which the host learns at that chunk's settle only
  last = next(i for i in range(10, 24, 2) if solo_long[i] not in solo_long[:i])
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)
  events = _record(server)
  passes = []
  admit = server._admit_pending

  async def counted(woken=None, behind=None):
    passes.append((behind is not None, len(server._parked)))
    await admit(woken, behind)

  server._admit_pending = counted

  async def run():
    seen = 0
    grown = asyncio.Event()

    def emit(rid, toks, fin):
      nonlocal seen
      if rid == "long":
        seen += len(toks)
        if seen >= 6:
          grown.set()  # the long row holds 2 pages now: 'big' must park
        if seen == last - 1 and server._parked:
          server._parked_avail_seen = -1  # what any release event does: the gate retries the parked set at the next boundary, with the EOS chunk in flight

    long_task = asyncio.create_task(server.submit("long", np.asarray(p_long, np.int32), max_tokens=40, temp=0.0, top_k=35, eos_ids=(solo_long[last],), emit=emit))
    await asyncio.wait_for(grown.wait(), timeout=60)
    big = await asyncio.wait_for(server.submit("big", np.asarray(p_big, np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=emit), timeout=60)
    return await asyncio.wait_for(long_task, timeout=60), big

  out_long, out_big = asyncio.run(run())
  server.shutdown()
  assert out_long == solo_long[: last + 1] and out_big == _single_row_reference(params, shard, p_big, 4)
  assert (True, 1) in passes  # a pass ran behind a chunk with 'big' parked, and parked it again ...
  groups = [e for e in events if e[:2] == ("enqueue", "group")]
  assert len(groups) == 2 and _settled_first(events, groups[1]), events  # ... so its group went in once that chunk was settled


def test_a_cancel_mid_prefill_settles_the_chunk_in_flight_first(monkeypatch):
  """A cancel that lands on a prompt mid-chunked-prefill: its pages are released at the admission sweep, and a mixed
  chunk in flight may be writing them — the gate asks for the settled state; the resident stream is untouched."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  monkeypatch.setenv("XOT_TPU_MIXED_TICK", "1")
  monkeypatch.setenv("XOT_TPU_PREFILL_CHUNK", "16")
  monkeypatch.setenv("XOT_TPU_MIXED_BUDGET", "8")  # many small slices: the cancel lands between two of them
  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)
  gate = server._needs_settled_state
  verdicts = []
  server._needs_settled_state = lambda: verdicts.append((gate(), bool(server._cancelled_ids))) or verdicts[-1][0]

  async def run():
    def emit(rid, toks, fin):
      if rid == "long" and server._prefilling and not server._cancelled_ids:
        server.cancel("second")  # mid-prefill: remembered in ``_cancelled_ids``, settled at the next admission sweep

    long_task = asyncio.create_task(server.submit("long", np.asarray([3, 25, 9], np.int32), max_tokens=30, temp=0.0, top_k=35, eos_ids=(), emit=emit))
    await asyncio.sleep(0)
    second = await asyncio.wait_for(server.submit("second", np.asarray(LONG_PROMPT, np.int32), max_tokens=5, temp=0.0, top_k=35, eos_ids=(), emit=emit), timeout=60)
    return await asyncio.wait_for(long_task, timeout=60), second

  out_long, out_second = asyncio.run(run())
  assert out_long == _single_row_reference(params, shard, [3, 25, 9], 29) and out_second == []
  assert (True, True) in verdicts and (True, False) not in verdicts  # it asked for the settled state exactly while the cancel was pending
  alloc = server.allocator
  assert alloc.n_available == alloc.n_pages - 1 and not server._prefilling
  server.shutdown()


def test_ngram_rows_settle_their_group_before_the_plan(monkeypatch):
  """A server that proposes from n-gram indexes: a row's index is built over its first token, and the proposals of a
  chunk key on settled history — so a group is read back before the next chunk is planned, and no chunk with n-gram
  rows is enqueued behind another. Plain chunks of the same server still chain."""
  from tests.test_spec_ngram import CFG as NGRAM_CFG
  from tests.test_spec_ngram import PROMPTS as NGRAM_PROMPTS
  from tests.test_spec_ngram import _engine as ngram_engine

  monkeypatch.setenv("XOT_TPU_SPEC_NGRAM", "1")
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  engine, params, shard = ngram_engine()
  server = BatchedServer(engine, n_slots=2, chunk=4, lookahead=True, spec_batch=True)
  events = _record(server)
  outs, _ = _serve(server, NGRAM_PROMPTS[:2], 24)
  assert server.spec_proposers == ("ngram",)
  server.shutdown()
  assert outs == [_single_row_reference(params, shard, p, 23, cfg=NGRAM_CFG) for p in NGRAM_PROMPTS[:2]]
  spec_ticks = {e[2] for e in events if e[0] == "enqueue"} - {e[2] for e in events if e[:2] == ("enqueue", "group")}
  for kind, _, tick in (e for e in events if e[0] == "enqueue"):
    if kind == "group":
      after = events[_at(events, ("enqueue", "group", tick)) + 1]
      assert after == ("readback", tick), events  # the group is settled before anything else is enqueued
  assert spec_ticks


def test_a_spec_to_plain_switch_settles_the_chunk_in_flight_first(monkeypatch):
  """The two decode programs chain by different contracts (device positions against the host's plan): when every
  row's depth has collapsed and the plain program takes over, the last speculative chunk is read back before the first
  plain one is enqueued."""
  from tests.test_spec_batch import _random_engine
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  monkeypatch.setenv("XOT_TPU_SPEC_REPROBE", "1000")
  cfg = tiny_test_config(n_layers=2, max_seq_len=512, tied_embedding=True)
  engine, params, shard = _random_engine(cfg=cfg)
  engine._draft_params = quantize_params(full_model_params(jax.random.PRNGKey(777), cfg, "m")[0])  # a draft that never agrees
  server = BatchedServer(engine, n_slots=2, chunk=4, lookahead=True, spec_batch=True)
  events: list = []
  for name in ("spec_paged_batch_decode", "paged_batch_decode"):
    def call(*a, _orig=getattr(server.ops, name), _name=name, **k):
      events.append(("enqueue", _name, server._tick))
      return _orig(*a, **k)

    setattr(server.ops, name, call)
  phase = server._phase

  @contextmanager
  def recorded(name, **args):
    if name == "readback":
      events.append(("readback", args.get("tick")))
    with phase(name, **args):
      yield

  server._phase = recorded
  outs, _ = _serve(server, [[3, 25, 9]], 60)
  server.shutdown()
  assert outs[0] == _single_row_reference(params, shard, [3, 25, 9], 59, cfg=cfg)
  first_plain = next(e for e in events if e[:2] == ("enqueue", "paged_batch_decode"))
  last_spec = [e for e in events[: _at(events, first_plain)] if e[:2] == ("enqueue", "spec_paged_batch_decode")][-1]
  assert _at(events, ("readback", last_spec[2])) < _at(events, first_plain), events
  plain = [e for e in events if e[:2] == ("enqueue", "paged_batch_decode")]
  assert any(_at(events, b) < _at(events, ("readback", a[2])) for a, b in zip(plain, plain[1:]))  # plain chunks chain again


def test_the_pass_behind_a_chunk_waits_until_late_in_that_chunk():
  """``_wait_late_into``: the admission pass that rides behind a chunk is held until a quarter of the chunk's
  expected time is left (so that it sees what arrived during the chunk, as a pass at its end did), no longer than the
  chunk runs, and not at all without an estimate."""
  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)

  class _Toks:
    done = False

    def is_ready(self):
      return self.done

  class _Chunk:
    toks = _Toks()

  async def waited(estimates, ready_after=None):
    asked = []

    def expected():
      asked.append(1)
      if ready_after is not None and len(asked) > ready_after:
        _Chunk.toks.done = True
      return estimates[min(len(asked), len(estimates)) - 1]

    server.clock.expected = expected
    _Chunk.toks.done = False
    await asyncio.wait_for(server._wait_late_into(_Chunk()), timeout=5)
    return len(asked)

  async def run():
    assert await waited([None]) == 1  # no estimate: the pass runs at once
    assert await waited([(0.02, 0.1)]) == 1  # a fifth of the chunk is left: late enough
    assert await waited([(0.08, 0.1), (0.05, 0.1), (0.024, 0.1)]) == 3  # slept twice, then a quarter or less is left
    assert await waited([(5.0, 10.0)], ready_after=2) == 3  # the estimate was long and the chunk has ended: stop waiting

  asyncio.run(run())
  server.shutdown()


@contextmanager
def _scripted_time(monkeypatch):
  """``batch_scheduler``'s clock and ``asyncio.sleep`` by script: a sleep moves the clock on by what it was asked and
  lets the loop's other tasks run. No real time passes, so a busy machine changes nothing. Yields (clock, sleeps asked)."""
  from types import SimpleNamespace

  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  clock, slept, real_sleep = [100.0], [], asyncio.sleep

  async def sleep(dt):
    slept.append(dt)
    clock[0] += dt
    await real_sleep(0)

  with monkeypatch.context() as m:
    m.setattr(bs, "time", SimpleNamespace(perf_counter=lambda: clock[0]))
    m.setattr(asyncio, "sleep", sleep)
    yield clock, slept


class _NoEstimate:
  toks = None  # ``_wait_late_into``'s first wait is over at once: no estimate, no readiness


@pytest.mark.parametrize("lumps", [1, 2, 3])
def test_the_pass_behind_a_chunk_lets_a_burst_finish_landing(lumps, monkeypatch):
  """``_wait_late_into``'s second wait: k callers at once reach the queue in lumps a few milliseconds apart, and a pass
  between two lumps would make two groups of k/2 (a k-row prefill program the benchmark's warm-up then never reaches).
  The pass runs only once the newest arrival is ``BURST_QUIET_S`` old — after the LAST lump, however many there are."""
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)
  server.clock.expected = lambda: None

  async def run():
    with _scripted_time(monkeypatch) as (clock, slept):
      due = [clock[0] + i * 0.6 * bs.BURST_QUIET_S for i in range(lumps)]  # the first lump is in the queue as the boundary looks
      landed = []

      async def land():  # a handler on the loop's thread: it runs whenever the scheduler sleeps
        while len(landed) < lumps:
          if clock[0] >= due[len(landed)]:
            landed.append(clock[0])
            server._last_arrival = clock[0]
          await asyncio.sleep(0)

      server._last_arrival = clock[0]
      landed.append(clock[0])
      lander = asyncio.create_task(land())
      await server._wait_late_into(_NoEstimate())
      passed = clock[0]
      await lander
    assert len(landed) == lumps and landed[-1] + bs.BURST_QUIET_S <= passed <= landed[-1] + bs.BURST_QUIET_S + 0.002  # every lump is in, and the pass is held no longer than that
    assert max(slept) <= 0.001 + 1e-9  # it looks again every millisecond: a lump is never slept through

  asyncio.run(run())
  server.shutdown()


def test_the_pass_behind_a_chunk_is_not_held_by_a_steady_stream_of_arrivals(monkeypatch):
  """Arrivals a millisecond apart without end: the pass gives up waiting for quiet after ``BURST_WAIT_MAX_S``; and with
  nobody queued in the last ``BURST_QUIET_S`` (a closed loop's every boundary) it does not sleep at all."""
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  params, shard = full_model_params(KEY, CFG)
  server = BatchedServer(_engine(params, shard), n_slots=2, chunk=2, lookahead=True)
  server.clock.expected = lambda: None

  async def run():
    with _scripted_time(monkeypatch) as (clock, slept):
      stop = False

      async def stream():
        while not stop:
          server._last_arrival = clock[0]
          await asyncio.sleep(0)

      streamer = asyncio.create_task(stream())
      t0 = server._last_arrival = clock[0]
      await server._wait_late_into(_NoEstimate())
      held, stop = clock[0] - t0, True
      await streamer
      assert bs.BURST_WAIT_MAX_S <= held <= bs.BURST_WAIT_MAX_S + 0.002, held

      server._last_arrival = clock[0] - 2 * bs.BURST_QUIET_S
      del slept[:]
      await server._wait_late_into(_NoEstimate())
      assert slept == []

  asyncio.run(run())
  server.shutdown()


def test_a_group_whose_shape_was_never_staged_takes_a_staged_program_of_more_rows(monkeypatch):
  """``_covered_rows``: a paged prefill group is staged at its own rows, padded to a power of two — unless that shape
  has never been staged on this server and one of more rows at the same padded length and page window has (a warm-up's
  group of four that the admission pass cut into two and two leaves [4, L] unmet and [8, L] met): then it takes that
  program, its extra rows padding rows like any, and what it emits is what the exact shape would. Only on a server that
  was declared warm (``POST /v1/warmup`` marks the ledger steady): one nobody warmed compiles the exact shape, once."""
  from xotorch_support_jetson_tpu.utils.programs import ledger

  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  monkeypatch.setattr(bs, "GROUP_SLOTS_WHOLE", 4)  # a server of more slots than that has programs of 1, 2, 4 and 8 rows and no others
  server = BatchedServer(_engine(params, shard), n_slots=8, chunk=2, lookahead=True)
  staged, real = [], server.ops.prefill_into_pages_many_sampled

  def recording(tok, *rest, **kw):
    staged.append(tuple(tok.shape))
    return real(tok, *rest, **kw)

  server.ops.prefill_into_pages_many_sampled = recording
  n_gen = 5
  expected = [_single_row_reference(params, shard, p, n_gen - 1) for p in PROMPTS]

  assert server._covered_rows(2, 32, 2) == 2 and _serve(server, PROMPTS[:1], n_gen)[0] == expected[:1]  # nothing staged yet: its own shape
  (rows, padded), window = staged[-1], next(iter(server._group_shapes))[2]
  assert rows == 1 and server._group_shapes == {(1, padded, window)}

  server._group_shapes = {(4, padded, window), (8, padded, window), (4, 2 * padded, window)}  # as after a warm-up that missed [1, L] and [2, L]
  assert not ledger.steady and server._covered_rows(2, padded, window) == 2  # nobody declared the server warm: the exact shape, compiled once
  monkeypatch.setattr(ledger, "_steady", True)
  assert (server._covered_rows(1, padded, window), server._covered_rows(2, padded, window), server._covered_rows(4, padded, window)) == (4, 4, 4)  # the fewest rows that cover
  assert server._covered_rows(8, 2 * padded, window) == 8 and server._covered_rows(2, padded, 2 * window) == 2  # nothing of more rows at that length and window: its own
  assert _serve(server, PROMPTS[:1], n_gen)[0] == expected[:1] and staged[-1] == (4, padded)  # one request, staged as the program of four; the same tokens

  server._group_shapes.add((1, padded, window))
  assert _serve(server, PROMPTS[1:2], n_gen)[0] == expected[1:2] and staged[-1] == (1, padded)  # a shape that was staged is taken as it is
  monkeypatch.setattr(bs, "GROUP_SLOTS_WHOLE", 16)
  assert server._covered_rows(2, padded, window) == 2  # a server of up to 16 slots is never asked
  server.shutdown()
