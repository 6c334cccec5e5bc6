"""Batched prefill admission (VERDICT r3 #1): K concurrent arrivals prefill
in ONE padded dispatch instead of K serial ones, with decode progressing
between chunk boundaries — the p50-TTFT fix under load.

Covers the device programs (multi-row prefill == K single-row prefills,
dense and paged), the scheduler dispatch accounting (K queued prompts ≤ 2
prefill dispatches), admission overlapping live decode, and the
scatter-clamp grouping (a long cached prefix cannot share a dispatch with a
fresh long prompt).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  init_kv_cache,
  prefill_into_pages,
  prefill_into_pages_many,
  prefill_into_slot,
  prefill_into_slots,
)
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

CFG = tiny_test_config(n_layers=2, max_seq_len=128)
KEY = jax.random.PRNGKey(0)


def _pad(prompt, to=16):
  out = np.zeros((1, to), np.int32)
  out[0, : len(prompt)] = prompt
  return jnp.asarray(out)


def test_prefill_into_slots_matches_single_rows():
  """One K=3 dispatch == 3 single-row prefills: same cache, same logits."""
  params, shard = full_model_params(KEY, CFG)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100]]

  cache_ref = init_kv_cache(CFG, shard.n_shard_layers, 4, 64)
  lasts_ref = []
  for row, p in enumerate(prompts):
    last, cache_ref = prefill_into_slot(params, CFG, shard, _pad(p), cache_ref, jnp.int32(row), jnp.int32(len(p)))
    lasts_ref.append(np.asarray(last))

  cache_b = init_kv_cache(CFG, shard.n_shard_layers, 4, 64)
  toks = np.zeros((3, 16), np.int32)
  for i, p in enumerate(prompts):
    toks[i, : len(p)] = p
  last_b, cache_b = prefill_into_slots(
    params, CFG, shard, jnp.asarray(toks), cache_b, jnp.asarray([0, 1, 2], jnp.int32),
    jnp.asarray([len(p) for p in prompts], jnp.int32),
  )
  last_b = np.asarray(last_b)
  for i in range(3):
    np.testing.assert_allclose(last_b[i], lasts_ref[i][0], rtol=2e-5, atol=2e-5)
  for k in cache_ref:
    # Rows 0-2 written identically; row 3 untouched in both.
    np.testing.assert_array_equal(np.asarray(cache_b[k]), np.asarray(cache_ref[k]))


def test_prefill_into_pages_many_matches_single_rows():
  """Batched page prefill == per-request page prefills (distinct pages)."""
  PS = 16
  params, shard = full_model_params(KEY, CFG)
  prompts = [[3, 25, 9], list(range(40, 60)), [9, 9, 9, 1]]
  n_pages = 32
  mp = 8  # pages per row

  def bt_for(i, p):
    # Rows own disjoint page ranges (page 0 is the trash page).
    total = (len(p) + 1 + PS - 1) // PS
    bt = np.zeros((mp,), np.int32)
    bt[:total] = np.arange(1 + 4 * i, 1 + 4 * i + total)
    return bt

  pool_ref = init_paged_pool(CFG, shard.n_shard_layers, n_pages, PS)
  lasts_ref = []
  for i, p in enumerate(prompts):
    last, pool_ref = prefill_into_pages(
      params, CFG, shard, _pad(p, 32), pool_ref, jnp.asarray(bt_for(i, p)), jnp.int32(0), jnp.int32(len(p)), PS
    )
    lasts_ref.append(np.asarray(last))

  pool_b = init_paged_pool(CFG, shard.n_shard_layers, n_pages, PS)
  toks = np.zeros((3, 32), np.int32)
  bts = np.zeros((3, mp), np.int32)
  for i, p in enumerate(prompts):
    toks[i, : len(p)] = p
    bts[i] = bt_for(i, p)
  last_b, pool_b = prefill_into_pages_many(
    params, CFG, shard, jnp.asarray(toks), pool_b, jnp.asarray(bts), jnp.zeros((3,), jnp.int32),
    jnp.asarray([len(p) for p in prompts], jnp.int32), PS,
  )
  last_b = np.asarray(last_b)
  for i in range(3):
    np.testing.assert_allclose(last_b[i], lasts_ref[i][0], rtol=2e-5, atol=2e-5)
  # The rows' own pages match (up to batch-shape reduction-order jitter);
  # the trash page (0) differs by design.
  for k in ("k", "v"):
    np.testing.assert_allclose(np.asarray(pool_b[k][:, 1:]), np.asarray(pool_ref[k][:, 1:]), rtol=2e-5, atol=2e-5)


def _count_prefills(server):
  """Wrap the server's ops so every batched-prefill dispatch is recorded as
  (n_real_rows, n_occupied_slots_at_dispatch); single-row entry points are
  poisoned — the scheduler must never use them again."""
  calls = []

  def wrap(name):
    orig = getattr(server.ops, name)

    def fn(tokens, *a, **k):
      occupied = sum(s is not None for s in server.slots)
      calls.append((int(np.asarray(tokens).shape[0]), occupied))
      return orig(tokens, *a, **k)

    setattr(server.ops, name, fn)

  wrap("prefill_into_slots")
  wrap("prefill_into_pages_many")
  # Fused sampling epilogue (ISSUE 11): the default admission path now
  # dispatches the prefill+sample programs — same batched-prefill semantics,
  # counted identically. The pp/sp backends have no fused variants
  # (fused_sampling_supported() is False there) and keep the two above.
  if server.ops.fused_sampling_supported():
    wrap("prefill_into_slots_sampled")
    wrap("prefill_into_pages_many_sampled")

  def poisoned(*a, **k):
    raise AssertionError("scheduler used a single-row prefill entry point")

  server.ops.prefill_into_slot = poisoned
  server.ops.prefill_into_pages = poisoned
  return calls


def _serve(server, prompts, n_gen, streamed=None):
  async def run():
    def emit(rid, toks, finished):
      if streamed is not None:
        streamed.setdefault(rid, []).extend(toks)

    return await asyncio.gather(
      *(
        server.submit(f"r{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=emit)
        for i, p in enumerate(prompts)
      )
    )

  return asyncio.run(run())


def _solo(params, shard, prompt, n_gen, cfg=CFG):
  """Greedy solo reference with a cache big enough for long prompts."""
  from xotorch_support_jetson_tpu.models.decoder import fused_decode, shard_forward

  S = len(prompt)
  tokens = jnp.asarray([prompt], dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
  cache = init_kv_cache(cfg, shard.n_shard_layers, 1, cfg.max_seq_len)
  logits, cache = shard_forward(params, cfg, shard, tokens, positions, cache)
  first = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
  toks, _ = fused_decode(params, cfg, shard, first, cache, jnp.full((1,), S, jnp.int32), n_gen - 1, temp=0.0)
  return [int(first[0, 0])] + [int(t) for t in np.asarray(toks)[0]]


def _check_exact(params, shard, prompts, outs, n_gen, cfg=None):
  for i, p in enumerate(prompts):
    expected = _solo(params, shard, p, n_gen, cfg=cfg or CFG)
    assert outs[i] == expected, f"req {i}: {outs[i]} != {expected}"


def test_k_queued_prompts_admit_in_one_dispatch_dense(monkeypatch):
  """4 concurrent arrivals, 4 slots, dense cache: ONE prefill dispatch,
  token-identical to solo greedy."""
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  params, shard = full_model_params(KEY, CFG)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  calls = _count_prefills(server)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  outs = _serve(server, prompts, n_gen=5)
  _check_exact(params, shard, prompts, outs, 5)
  assert len(calls) <= 2, f"expected <=2 prefill dispatches for 4 queued prompts, got {calls}"
  assert sum(n for n, _ in calls) >= 4  # all four admitted through batched dispatches


def test_k_queued_prompts_admit_in_one_dispatch_paged(monkeypatch):
  """Same under the default paged pool (block tables built host-side)."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  calls = _count_prefills(server)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  streamed = {}
  outs = _serve(server, prompts, n_gen=5, streamed=streamed)
  _check_exact(params, shard, prompts, outs, 5)
  assert len(calls) <= 2, f"expected <=2 prefill dispatches for 4 queued prompts, got {calls}"
  for i in range(4):
    assert streamed[f"r{i}"] == outs[i]


def test_admission_overlaps_live_decode(monkeypatch):
  """Two requests arriving while two rows are mid-decode admit in ONE
  dispatch with the resident rows' decode progressing around it, and every
  stream stays token-identical to solo greedy."""
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  params, shard = full_model_params(KEY, CFG)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  calls = _count_prefills(server)
  first_pair = [[3, 25, 9], [7, 1, 88, 42, 5]]
  second_pair = [[100], [9, 9, 9, 1]]

  async def run():
    streamed: dict[str, list] = {}
    mid = asyncio.Event()

    def emit(rid, toks, finished):
      streamed.setdefault(rid, []).extend(toks)
      # After the first pair has produced a few tokens, release the second pair.
      if rid in ("r0", "r1") and len(streamed[rid]) >= 3:
        mid.set()

    async def late_submit(i, p):
      await mid.wait()
      return await server.submit(f"s{i}", np.asarray(p, np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit)

    outs_first, outs_second = await asyncio.gather(
      asyncio.gather(
        *(
          server.submit(f"r{i}", np.asarray(p, np.int32), max_tokens=12, temp=0.0, top_k=35, eos_ids=(), emit=emit)
          for i, p in enumerate(first_pair)
        )
      ),
      asyncio.gather(*(late_submit(i, p) for i, p in enumerate(second_pair))),
    )
    return outs_first, outs_second

  outs_first, outs_second = asyncio.run(run())
  _check_exact(params, shard, first_pair, outs_first, 12)
  _check_exact(params, shard, second_pair, outs_second, 4)
  # The second pair's dispatch happened while resident rows were mid-decode,
  # and admitted both rows at once.
  late = [c for c in calls if c[1] >= 2]
  assert late, f"no prefill dispatch overlapped live decode: {calls}"
  assert any(n >= 2 for n, _ in late), f"late arrivals were serialized: {calls}"


def test_scatter_clamp_grouping_splits_long_prefix_from_long_prompt(monkeypatch):
  """A request reusing a long cached prefix cannot pad to a fresh long
  prompt's bucket (dynamic_update_slice would clamp its writes): the
  scheduler splits them into two dispatches, outputs still exact."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  long_prompt = [(7 * i) % 120 + 1 for i in range(100)]
  other_long = [(11 * i) % 120 + 1 for i in range(100)]

  # Seed the prefix cache: run the long prompt once to completion.
  outs = _serve(server, [long_prompt], n_gen=2)
  _check_exact(params, shard, [long_prompt], outs, 2)

  calls = _count_prefills(server)
  prompts = [long_prompt, other_long]  # r0 reuses 96 cached prefix tokens
  outs = _serve(server, prompts, n_gen=3)
  _check_exact(params, shard, prompts, outs, 3)
  assert len(calls) == 2, f"expected the scatter-clamp split into 2 dispatches, got {calls}"


def test_parked_request_survives_insta_finished_batchmate(monkeypatch):
  """A request parked because its batch-mates held pages must not strand (or
  assert-crash the pool) when those mates finish AT their first token and no
  slot ever becomes occupied: the scheduler retries the parked entry with
  the pages now free (code-review r4 finding)."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  monkeypatch.setenv("XOT_TPU_BATCH_PAGES", "9")  # 1 trash + 8 usable
  params, shard = full_model_params(KEY, CFG)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  short = [3, 25, 9]  # 1 page, max_tokens=1 → finishes at its first token
  big = [(5 * i) % 120 + 1 for i in range(113)]  # needs all 8 pages

  async def run():
    return await asyncio.gather(
      server.submit("a", np.asarray(short, np.int32), max_tokens=1, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None),
      server.submit("b", np.asarray(big, np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None),
    )

  out_a, out_b = asyncio.run(run())
  assert out_a == _solo(params, shard, short, 1)
  assert out_b == _solo(params, shard, big, 4)


def test_chunked_prefill_interleaves_decode(monkeypatch):
  """A long prompt prefills in XOT_TPU_PREFILL_CHUNK-sized chunks with
  decode ticks for resident rows BETWEEN the chunks — one long arrival no
  longer stalls every stream for its whole prefill — and every output stays
  token-identical to solo greedy.

  Pinned to the ALTERNATING scheduler (`XOT_TPU_MIXED_TICK=0`): this test
  counts separate prefill/decode dispatches, which is exactly the schedule
  mixed ticks replace (ISSUE 14 — tests/test_mixed_tick.py pins the fused
  schedule's stronger bound: decode advances INSIDE every prefill tick)."""
  monkeypatch.setenv("XOT_TPU_MIXED_TICK", "0")
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  monkeypatch.setenv("XOT_TPU_PREFILL_CHUNK", "128")
  cfg = tiny_test_config(n_layers=2, max_seq_len=512)
  params, shard = full_model_params(KEY, cfg)
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=2)
  events = []  # ordered ("prefill", n_rows) / ("decode",) trace

  orig_prefill = server.ops.prefill_into_pages_many
  orig_prefill_sampled = server.ops.prefill_into_pages_many_sampled
  orig_decode = server.ops.paged_batch_decode

  def rec_prefill(tokens, *a, **k):
    events.append(("prefill", int(np.asarray(tokens).shape[0])))
    return orig_prefill(tokens, *a, **k)

  def rec_prefill_sampled(tokens, *a, **k):
    events.append(("prefill", int(np.asarray(tokens).shape[0])))
    return orig_prefill_sampled(tokens, *a, **k)

  def rec_decode(*a, **k):
    events.append(("decode",))
    return orig_decode(*a, **k)

  server.ops.prefill_into_pages_many = rec_prefill
  server.ops.prefill_into_pages_many_sampled = rec_prefill_sampled
  server.ops.paged_batch_decode = rec_decode

  long_prompt = [(7 * i) % 120 + 1 for i in range(400)]  # 4 chunks of 128
  short = [3, 25, 9]

  async def run():
    streamed: dict[str, list] = {}
    started = asyncio.Event()

    def emit(rid, toks, fin):
      streamed.setdefault(rid, []).extend(toks)
      if rid == "s0" and len(streamed[rid]) >= 2:
        started.set()

    async def late_long():
      await started.wait()  # the short stream is mid-decode when this lands
      return await server.submit("L", np.asarray(long_prompt, np.int32), max_tokens=3, temp=0.0, top_k=35, eos_ids=(), emit=emit)

    return await asyncio.gather(
      server.submit("s0", np.asarray(short, np.int32), max_tokens=30, temp=0.0, top_k=35, eos_ids=(), emit=emit),
      late_long(),
    )

  out_short, out_long = asyncio.run(run())
  assert out_short == _solo(params, shard, short, 30, cfg=cfg)
  assert out_long == _solo(params, shard, long_prompt, 3, cfg=cfg)
  # The long prompt took >= 4 prefill dispatches (400 tokens / 128-chunk) on
  # top of the short request's admission…
  p_idx = [i for i, e in enumerate(events) if e[0] == "prefill"]
  assert len(p_idx) >= 5, events
  # …and decode ticks ran BETWEEN its chunks (the stall per tick is bounded
  # by one chunk, not the whole 400-token prefill).
  long_chunks = p_idx[-4:]
  interleaved = any(("decode",) in events[a + 1 : b] for a, b in zip(long_chunks, long_chunks[1:]))
  assert interleaved, f"no decode tick between prefill chunks: {events}"


def test_pp_engine_batched_admission(monkeypatch):
  """XOT_TPU_PP=2: the pp-pipelined backend admits a burst in one dispatch
  too (dense slots), outputs exact."""
  monkeypatch.setenv("XOT_TPU_PAGED", "0")
  monkeypatch.setenv("XOT_TPU_PP", "2")
  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(KEY, cfg)
  engine = JaxShardedInferenceEngine(use_local_mesh=True, pp=2)
  engine.load_test_model(shard, cfg, params)
  engine._maybe_shard_over_local_mesh()
  assert engine._pp is not None and engine.mesh.shape["pp"] == 2

  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  server = BatchedServer(engine, n_slots=4, chunk=4)
  calls = _count_prefills(server)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]
  outs = _serve(server, prompts, n_gen=5)
  _check_exact(params, shard, prompts, outs, 5, cfg=cfg)
  assert len(calls) <= 2, f"expected <=2 prefill dispatches, got {calls}"
