"""One-chunk-lookahead pipelined decode (inference/batch_scheduler.py).

The correctness contract: with ``XOT_TPU_SCHED_LOOKAHEAD`` on (the default),
the batched server's output is TOKEN-IDENTICAL to the synchronous loop —
same compiled programs, same key-split order, same sampling; only the
host/device schedule changes. A row that finishes inside an in-flight chunk
is speculatively decoded one extra chunk whose tokens are dropped on read;
pages release cleanly at the settle boundary; admissions never queue behind
a speculative chunk (the pipeline drains whenever anyone is waiting).
"""

import asyncio
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

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
  """A request arriving while a lookahead chunk is in flight drains the
  pipeline and admits at the next dispatch boundary — it does NOT wait for
  the resident stream to finish (the TTFT contract)."""
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
