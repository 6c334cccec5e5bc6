"""Device-program ledger (ISSUE 19, utils/programs.py).

The repo's core no-recompile invariant, measured: every serving-path jit
flows through ``tracked_jit``, so the ledger can pin the standing claims —
adapter mix changes (ISSUE 15), per-row spec gamma/proposer changes
(ISSUE 7/12), mixed-tick budgets within one pad bucket (ISSUE 14), and
decode-path/page-remap switches — at ZERO new compiles; a forced shape
change post-steady is detected as a ``compile`` flight event + timeline
stage; an injected storm fires ``recompile_storm`` with an auto-bundle;
``XOT_TPU_PROGRAMS=0`` is poison-pinned byte-identical; and the cluster
scope merges over the real two-node gRPC fixture with a dead peer
annotated, never waited out.
"""

import asyncio
import json
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.orchestration.flightrec import (
  AnomalyWatchers,
  bundles,
  flightrec,
)
from xotorch_support_jetson_tpu.orchestration.tracing import tracer
from xotorch_support_jetson_tpu.utils.metrics import metrics as gm
from xotorch_support_jetson_tpu.utils.programs import (
  ProgramLedger,
  describe_signature,
  dispatch_context,
  ledger,
  tracked_jit,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _clean_ledger():
  """The ledger is process-global (like the metrics registry): every test
  starts from a forgotten, non-steady state and leaves one behind."""
  ledger.reset()
  yield
  ledger.reset()
  # Compile/anomaly events this module planted must not trip the
  # recompile-storm rule in LATER test modules' AnomalyWatchers checks —
  # the flight ring is process-global too.
  flightrec.clear()


# ------------------------------------------------------------- the wrapper


def test_tracked_jit_counts_compiles_dispatches_and_signatures():
  calls = []

  @tracked_jit("test.unit")
  def f(x):
    calls.append(1)
    return x * 2

  a = jnp.ones((1, 3), jnp.float32)
  for _ in range(3):
    np.asarray(f(a))
  assert len(calls) == 1  # the body ran only while tracing
  assert ledger.compile_count("test.unit") == 1
  assert ledger.dispatch_count("test.unit") == 3
  snap = ledger.snapshot()["families"]["test.unit"]
  assert snap["signatures"] == ["float32[1,3]"]
  assert snap["compile_s"] > 0.0  # the compiling dispatch's wall time
  # A new abstract shape is a new program.
  np.asarray(f(jnp.ones((2, 5), jnp.float32)))
  assert ledger.compile_count("test.unit") == 2
  assert "float32[2,5]" in ledger.snapshot()["families"]["test.unit"]["signatures"]
  # Counters moved under the family label.
  assert gm.counter_value("program_compiles_total", labels={"family": "test.unit"}) >= 2
  assert gm.counter_value("program_dispatch_total", labels={"family": "test.unit"}) >= 4


def test_nested_tracked_programs_count_builds_but_one_dispatch():
  @tracked_jit("test.inner")
  def inner(x):
    return x + 1

  @tracked_jit("test.outer")
  def outer(x):
    return inner(x) * 3

  np.asarray(outer(jnp.ones((4,), jnp.float32)))
  # Both families' program builds are counted (the inner trace hook fired
  # inside the outer trace), but only the top-level dispatch is recorded.
  assert ledger.compile_count("test.outer") == 1
  assert ledger.compile_count("test.inner") == 1
  assert ledger.dispatch_count("test.outer") == 1
  assert ledger.dispatch_count("test.inner") == 0


def test_tracked_jit_static_argnames_still_resolve():
  @tracked_jit("test.static", static_argnames=("n",))
  def rep(x, n):
    return jnp.tile(x, n)

  out = rep(jnp.ones((2,), jnp.float32), 3)
  assert out.shape == (6,)
  assert ledger.compile_count("test.static") == 1
  rep(jnp.ones((2,), jnp.float32), 3)
  assert ledger.compile_count("test.static") == 1  # cached
  rep(jnp.ones((2,), jnp.float32), 4)  # new static value -> new program
  assert ledger.compile_count("test.static") == 2


def test_describe_signature_shapes_trees_and_caps():
  sig = describe_signature((jnp.ones((2, 3), jnp.int32), {"a": jnp.ones((4,))}, 7), {"flag": True})
  assert sig.startswith("int32[2,3], tree(1 leaves), 7, flag=True")
  long = describe_signature(tuple(jnp.ones((100 + i,)) for i in range(60)), {})
  assert len(long) <= 512 and long.endswith("...")


def test_programs_disabled_poison_pin_is_byte_identical(monkeypatch):
  @tracked_jit("test.poison")
  def f(x):
    return jnp.cumsum(x * 3 + 1)

  a = jnp.arange(8, dtype=jnp.float32)
  on = np.asarray(f(a))
  assert ledger.dispatch_count("test.poison") == 1
  monkeypatch.setenv("XOT_TPU_PROGRAMS", "0")
  before = ledger.snapshot()["totals"]
  off = np.asarray(f(a))
  assert np.array_equal(on, off)  # the jitted computation is the SAME object
  assert ledger.snapshot()["totals"] == before  # nothing recorded while off
  assert ledger.snapshot()["enabled"] is False


# ------------------------------------- compile-count pins (standing claims)


def _prefilled_row(cfg, params, shard, prompt, n_slots=1, max_seq=128):
  from xotorch_support_jetson_tpu.models.decoder import init_kv_cache, prefill_into_slot

  cache = init_kv_cache(cfg, shard.n_shard_layers, n_slots, max_seq)
  pad = np.zeros((1, 16), np.int32)
  pad[0, : len(prompt)] = prompt
  last, cache = prefill_into_slot(params, cfg, shard, jnp.asarray(pad), cache, jnp.int32(0), jnp.int32(len(prompt)))
  return cache, int(np.argmax(np.asarray(last)[0])), len(prompt)


def test_pin_per_row_spec_gamma_and_proposer_change_zero_compiles():
  """ISSUE 7/12: per-row speculation depth and the host-proposed stream are
  TRACED — adapting gamma row by row, swapping the proposed tokens, or
  turning a row's proposer off (count 0) reuses the compiled program."""
  from tests.test_paged import CFG, KEY
  from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_spec_batch_decode

  params, shard = full_model_params(KEY, CFG)
  cache, first, S = _prefilled_row(CFG, params, shard, [3, 25, 9])
  rounds, G = 2, 2
  cap = rounds * (G + 1) + G
  tok = jnp.asarray([[first]], jnp.int32)
  pos = jnp.asarray([S], jnp.int32)
  active = jnp.asarray([True])
  temps = jnp.zeros((1,), jnp.float32)

  def spec(cache, gammas, props, counts):
    out = fused_spec_batch_decode(
      params, CFG, shard, None, CFG, shard, tok, cache, None, pos, active,
      jnp.asarray(gammas, jnp.int32), temps, rounds, G, top_k=1, k_max=1,
      props=props, prop_counts=counts,
    )
    jax.block_until_ready(out[0])
    return out[5]  # the donated-and-returned target cache

  stream = np.arange(1, cap + 1, dtype=np.int32)[None, :]
  cache = spec(cache, [2], jnp.asarray(stream), jnp.asarray([cap], jnp.int32))  # warm
  base = ledger.compile_count()
  cache = spec(cache, [0], jnp.asarray(stream), jnp.asarray([cap], jnp.int32))  # gamma change
  cache = spec(cache, [1], jnp.asarray(stream[:, ::-1].copy()), jnp.asarray([3], jnp.int32))  # proposer stream change
  cache = spec(cache, [2], jnp.asarray(stream), jnp.asarray([0], jnp.int32))  # proposer off for the row
  assert ledger.compile_count() == base, (
    f"spec gamma/proposer mix change recompiled: {ledger.snapshot()['families']}"
  )


def test_pin_mixed_tick_budget_within_bucket_zero_compiles():
  """ISSUE 14: the mixed tick's prefill slice is bounded by TRACED
  ``pf_prefix``/``pf_end`` scalars — any budget within one pad bucket (the
  padded ``pf_tokens`` shape) reuses the compiled mixed program."""
  from tests.test_paged import CFG, KEY, PS, _prefill_both
  from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_mixed_paged_batch_decode

  params, shard = full_model_params(KEY, CFG)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5]]
  _dense, pool, bt, firsts = _prefill_both(params, shard, prompts, 2)
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.asarray([True, True])
  temps = jnp.zeros((2,), jnp.float32)

  def mixed(pool, pf_tokens, prefix, end):
    out = fused_mixed_paged_batch_decode(
      params, CFG, shard, tok, pool, jnp.asarray(bt), positions, active, temps,
      jnp.asarray(pf_tokens, jnp.int32), jnp.asarray(bt[:1]),
      jnp.asarray([prefix], jnp.int32), jnp.asarray([end], jnp.int32),
      n_steps=2, page_size=PS, use_kernel=False,
    )
    jax.block_until_ready(out[0])
    return out[3]

  S0 = len(prompts[0])
  slice8 = np.zeros((1, 8), np.int32)
  slice8[0, :4] = [5, 6, 7, 8]
  pool = mixed(pool, slice8, S0, S0 + 4)  # warm at the 8-token pad bucket
  base = ledger.compile_count()
  slice8b = np.zeros((1, 8), np.int32)
  slice8b[0, :2] = [9, 10]
  pool = mixed(pool, slice8b, S0 + 4, S0 + 6)  # smaller budget, same bucket
  assert ledger.compile_count() == base, (
    f"mixed budget change within one pad bucket recompiled: {ledger.snapshot()['families']}"
  )


def test_pin_decode_path_switches_and_page_remap_zero_compiles():
  """Dense and paged decode are separate (warmed) programs — alternating
  between them dispatches cached executables, and remapping the page table
  CONTENTS (migration/defrag) is traced data, never a new program."""
  from tests.test_paged import CFG, KEY, PS, _prefill_both
  from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_batch_decode, fused_paged_batch_decode

  params, shard = full_model_params(KEY, CFG)
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5]]
  dense, pool, bt, firsts = _prefill_both(params, shard, prompts, 2)
  tok = jnp.asarray([[f] for f in firsts], jnp.int32)
  positions = jnp.asarray([len(p) for p in prompts], jnp.int32)
  active = jnp.asarray([True, True])
  temps = jnp.zeros((2,), jnp.float32)

  _, _, _, dense = fused_batch_decode(params, CFG, shard, tok, dense, positions, active, temps, 2)
  _, _, _, pool = fused_paged_batch_decode(
    params, CFG, shard, tok, pool, jnp.asarray(bt), positions, active, temps, 2, page_size=PS, use_kernel=False
  )
  base = ledger.compile_count()
  for tables in (bt, bt[::-1].copy()):  # second pass: rows' pages remapped
    _, _, _, dense = fused_batch_decode(params, CFG, shard, tok, dense, positions, active, temps, 2)
    _, _, _, pool = fused_paged_batch_decode(
      params, CFG, shard, tok, pool, jnp.asarray(tables), positions, active, temps, 2, page_size=PS, use_kernel=False
    )
  assert ledger.compile_count() == base, (
    f"decode-path switch / page remap recompiled: {ledger.snapshot()['families']}"
  )


def test_pin_adapter_mix_change_zero_compiles(monkeypatch):
  """ISSUE 15: per-row adapter ids are TRACED — re-serving the same prompts
  under a DIFFERENT adapter assignment (swaps included) must dispatch the
  already-compiled programs only."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_KV_QUANT", "int8")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  from tests.test_lora_serving import PROMPTS, _engine_with_adapters
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer

  engine, _reg = _engine_with_adapters()
  server = BatchedServer(engine, n_slots=4, chunk=2)

  def serve(names):
    async def run():
      return await asyncio.gather(*(
        server.submit(
          f"mix-{names[i]}-{i}", np.asarray(p, np.int32), max_tokens=4, temp=0.0,
          top_k=35, eos_ids=(), emit=lambda *_: None, adapter=nm,
        )
        for i, (p, nm) in enumerate(zip(PROMPTS, names))
      ))

    return asyncio.run(run())

  serve(["a1", "a2", None, "a1"])  # warm: mixed batch compiles the programs
  base = ledger.compile_count()
  serve(["a2", None, "a1", "a2"])  # every row's adapter changed
  serve([None, "a1", "a2", None])
  server.shutdown()
  assert ledger.compile_count() == base, (
    f"adapter mix change recompiled: {ledger.snapshot()['families']}"
  )


# --------------------------------------------- sentinel + storm + bundles


def test_forced_shape_change_post_steady_emits_sentinel():
  flightrec.clear()

  @tracked_jit("test.sentinel")
  def f(x):
    return x * 2

  np.asarray(f(jnp.ones((2, 2), jnp.float32)))
  ledger.mark_steady(manifest=[{"family": "test.sentinel"}])
  assert ledger.steady_compile_count() == 0
  with dispatch_context(["req-recomp"], node="n0"):
    np.asarray(f(jnp.ones((3, 7), jnp.float32)))  # the shape leak
  assert ledger.steady_compile_count("test.sentinel") == 1
  assert gm.counter_value("program_steady_compiles_total", labels={"family": "test.sentinel"}) >= 1
  evs = flightrec.query(types={"compile"}, limit=10)
  assert len(evs) == 1
  ev = evs[0]
  assert ev["request_id"] == "req-recomp" and ev["cause"] == "steady_recompile"
  assert ev["attributes"]["family"] == "test.sentinel"
  assert ev["attributes"]["signature"] == "float32[3,7]"
  assert ev["attributes"]["seconds"] > 0
  # The triggering request's timeline carries a ``compile`` stage.
  tl = tracer.timeline("req-recomp")
  assert tl is not None
  stages = [e["stage"] for e in tl["events"]]
  assert "compile" in stages
  comp = next(e for e in tl["events"] if e["stage"] == "compile")
  assert comp["attributes"]["family"] == "test.sentinel"


def test_nested_recompile_is_one_sentinel_event():
  """One real recompile of a fused program rebuilds its nested kernels too —
  that must be ONE flight event (the storm threshold counts stalls)."""
  flightrec.clear()

  @tracked_jit("test.n_inner")
  def inner(x):
    return x + 1

  @tracked_jit("test.n_outer")
  def outer(x):
    return inner(x) * 3

  np.asarray(outer(jnp.ones((2,), jnp.float32)))
  ledger.mark_steady()
  np.asarray(outer(jnp.ones((5,), jnp.float32)))
  evs = flightrec.query(types={"compile"}, limit=10)
  assert len(evs) == 1
  assert evs[0]["attributes"]["family"] == "test.n_outer"
  assert evs[0]["attributes"]["nested"] == ["test.n_inner"]
  assert ledger.steady_compile_count() == 1  # outer's dispatch only


def test_recompile_storm_fires_anomaly_with_auto_bundle(tmp_path, monkeypatch):
  """The injected storm fixture: ≥3 post-steady compiles inside the window
  → one ``recompile_storm`` anomaly + a rate-limited auto-bundle on disk
  whose ``programs`` section carries the ledger snapshot."""
  monkeypatch.setenv("XOT_TPU_BUNDLE_DIR", str(tmp_path))
  flightrec.clear()
  bundles.reset()

  @tracked_jit("test.storm")
  def f(x):
    return x - 1

  np.asarray(f(jnp.ones((2,), jnp.float32)))
  ledger.mark_steady()
  for n in (3, 4, 5):  # three distinct shape leaks
    np.asarray(f(jnp.ones((n, n), jnp.float32)))
  assert len(flightrec.query(types={"compile"}, limit=10)) == 3

  fired = {}

  async def run():
    w = AnomalyWatchers()
    fired["events"] = w.check({}, 1.0)
    await asyncio.sleep(0.2)  # let the auto-capture task write

  asyncio.run(run())
  assert [e["cause"] for e in fired["events"]] == ["recompile_storm"]
  attrs = fired["events"][0]["attributes"]
  assert attrs["compiles"] == 3 and attrs["families"] == {"test.storm": 3}
  files = list(tmp_path.glob("bundle-*-anomaly-recompile_storm.json"))
  assert len(files) == 1
  saved = json.loads(files[0].read_text())
  assert saved["reason"] == "anomaly:recompile_storm"
  assert "test.storm" in saved["programs"]["families"]
  assert saved["programs"]["steady"] is True


def test_storm_threshold_env_override(monkeypatch):
  monkeypatch.setenv("XOT_TPU_ANOMALY_RECOMPILES", "5")
  monkeypatch.setattr(bundles, "auto_capture", lambda *a, **k: False)
  flightrec.clear()

  @tracked_jit("test.quiet")
  def f(x):
    return x

  np.asarray(f(jnp.ones((2,), jnp.float32)))
  ledger.mark_steady()
  for n in (3, 4, 5):
    np.asarray(f(jnp.ones((n,), jnp.float32)))
  assert AnomalyWatchers().check({}, 1.0) == []  # 3 < the raised threshold


# --------------------------------------------------- warmup + steady serving


def _tiny_server(monkeypatch, **kw):
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  from tests.test_observability import _tiny_batched_server

  return _tiny_batched_server(**kw)


def test_warmup_manifest_enumerates_active_config(monkeypatch):
  server = _tiny_server(monkeypatch)
  fams = [e["family"] for e in server.warmup_manifest()]
  assert "decode.paged_batch" in fams
  assert any(f.startswith("prefill.") for f in fams)
  assert all(e.get("why") for e in server.warmup_manifest())
  server.shutdown()


def test_warmup_marks_steady_and_serving_stays_compile_free(monkeypatch):
  """The acceptance loop: POST /v1/warmup's engine side pre-compiles the
  manifest, marks steady — and a REAL request afterwards dispatches ZERO
  compiles (the identity suites' no-recompile claim, measured live)."""
  server = _tiny_server(monkeypatch)
  out = asyncio.run(server.warmup())
  assert out["steady"] is True and out["errors"] == []
  assert ledger.steady is True
  assert ledger.snapshot()["manifest"] == out["manifest"]
  warmed = [e["family"] for e in out["manifest"] if e.get("warmed")]
  assert "decode.paged_batch" in warmed
  assert ledger.warmup_compile_s_total() > 0.0
  assert gm.gauge_value("programs_steady") == 1.0
  # The warmup pass landed in the flight ring.
  assert any(e["type"] == "warmup" for e in flightrec.recent(50))

  async def run():
    return await server.submit(
      "steady-req", np.asarray([5, 6, 7], np.int32), max_tokens=3, temp=0.0,
      top_k=35, eos_ids=(), emit=lambda *_: None,
    )

  toks = asyncio.run(run())
  server.shutdown()
  assert len(toks) == 3
  assert ledger.steady_compile_count() == 0, (
    f"steady-state serving recompiled: {ledger.snapshot()['families']}"
  )


# ----------------------------------------------------------- snapshot/merge


def test_snapshot_is_json_safe_and_totaled():
  @tracked_jit("test.snap")
  def f(x):
    return x

  np.asarray(f(jnp.ones((2,), jnp.float32)))
  snap = ledger.snapshot()
  json.dumps(snap)  # rides the opaque-status wire and bundle files
  assert snap["totals"]["compiles"] == 1 and snap["totals"]["dispatches"] == 1
  assert snap["enabled"] is True and snap["steady"] is False


def test_merge_snapshots_sums_and_ands_steady():
  a = {
    "node_id": "n0", "steady": True,
    "families": {"decode.batch": {"compiles": 2, "steady_compiles": 0, "dispatches": 10, "compile_s": 1.5, "dispatch_s": 0.25, "xla_compile_s": 1.0, "signatures": ["int32[4,1]"]}},
  }
  b = {
    "node_id": "n1", "steady": False,
    "families": {
      "decode.batch": {"compiles": 1, "steady_compiles": 1, "dispatches": 4, "compile_s": 0.5, "dispatch_s": 0.75, "xla_compile_s": 0.25, "signatures": ["int32[4,1]", "int32[8,1]"]},
      "prefill.slots": {"compiles": 1, "dispatches": 2},
    },
  }
  merged = ProgramLedger.merge_snapshots([a, b])
  assert merged["scope"] == "cluster" and merged["nodes"] == ["n0", "n1"]
  assert merged["steady"] is False  # steady only when EVERY node is
  db = merged["families"]["decode.batch"]
  assert db["compiles"] == 3 and db["dispatches"] == 14 and db["steady_compiles"] == 1
  assert db["compile_s"] == 2.0 and db["dispatch_s"] == 1.0
  assert db["signatures"] == ["int32[4,1]", "int32[8,1]"]  # deduped
  assert merged["totals"]["dispatches"] == 16
  assert ProgramLedger.merge_snapshots([])["steady"] is False


def test_active_families_since_baseline_and_wall_ts():
  @tracked_jit("test.active_a")
  def fa(x):
    return x

  @tracked_jit("test.active_b")
  def fb(x):
    return x

  np.asarray(fa(jnp.ones((2,), jnp.float32)))
  base = ledger.dispatch_counts()
  wall = time.time()
  np.asarray(fb(jnp.ones((2,), jnp.float32)))
  assert ledger.active_families(base) == ["test.active_b"]
  assert "test.active_b" in ledger.families_active_since(wall)


# ------------------------------------------------------------ API endpoints


@pytest.mark.asyncio
async def test_programs_and_warmup_endpoints_local(monkeypatch):
  from aiohttp.test_utils import TestClient, TestServer

  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from tests_support_stubs import NoDiscovery, StubServer

  node = Node(
    "prog-api", StubServer(), DummyInferenceEngine(), NoDiscovery(), None,
    RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=50,
  )
  await node.start()

  @tracked_jit("test.api")
  def f(x):
    return x + 1

  np.asarray(f(jnp.ones((2,), jnp.float32)))
  api = ChatGPTAPI(node, "DummyInferenceEngine", response_timeout=30, default_model="dummy")
  client = TestClient(TestServer(api.app))
  await client.start_server()
  try:
    resp = await client.get("/v1/programs")
    data = await resp.json()
    assert resp.status == 200
    assert data["enabled"] is True and data["steady"] is False
    assert data["node_id"] == "prog-api"
    assert data["families"]["test.api"]["compiles"] == 1
    # The dummy engine has no batched scheduler: warmup degrades to arming
    # the sentinel over an empty manifest.
    resp = await client.post("/v1/warmup")
    data = await resp.json()
    assert resp.status == 200 and data["steady"] is True and data["manifest"] == []
    resp = await client.get("/v1/programs")
    assert (await resp.json())["steady"] is True
    # Cluster scope with no peers: merged shape, nothing unreachable.
    resp = await client.get("/v1/programs?scope=cluster")
    data = await resp.json()
    assert data["scope"] == "cluster" and data["unreachable"] == []
    assert data["families"]["test.api"]["compiles"] == 1
  finally:
    await client.close()
    await node.stop()


@pytest.mark.asyncio
async def test_profile_response_carries_active_program_families(monkeypatch):
  from aiohttp.test_utils import TestClient, TestServer

  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
  from xotorch_support_jetson_tpu.inference.dummy_engine import DummyInferenceEngine
  from xotorch_support_jetson_tpu.orchestration.node import Node
  from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy
  from tests_support_stubs import NoDiscovery, StubServer

  node = Node(
    "prof-api", StubServer(), DummyInferenceEngine(), NoDiscovery(), None,
    RingMemoryWeightedPartitioningStrategy(), max_generate_tokens=50,
  )
  await node.start()
  api = ChatGPTAPI(node, "DummyInferenceEngine", response_timeout=30, default_model="dummy")
  client = TestClient(TestServer(api.app))
  await client.start_server()
  try:

    @tracked_jit("test.profiled")
    def f(x):
      return x * 2

    async def dispatch_during_capture():
      await asyncio.sleep(0.02)
      np.asarray(f(jnp.ones((3,), jnp.float32)))

    task = asyncio.ensure_future(dispatch_during_capture())
    resp = await client.post("/v1/profile", json={"duration_ms": 120})
    await task
    data = await resp.json()
    if resp.status == 200:  # CPU backends that can't trace return 503
      assert "test.profiled" in data["programs"]
    else:
      assert resp.status == 503
  finally:
    await client.close()
    await node.stop()


def test_slow_request_log_carries_program_families(monkeypatch, capsys):
  from xotorch_support_jetson_tpu.orchestration.tracing import Tracer

  @tracked_jit("test.slowline")
  def f(x):
    return x

  monkeypatch.setenv("XOT_TPU_SLOW_REQUEST_MS", "0.000001")
  t = Tracer()
  t.request_context("r-progs")
  t.stage("r-progs", "queued")
  np.asarray(f(jnp.ones((2,), jnp.float32)))  # a dispatch inside the window
  t.handle_token("r-progs")
  t.end_request("r-progs")
  line = next(
    json.loads(entry) for entry in capsys.readouterr().out.splitlines() if '"slow_request"' in entry
  )
  assert "test.slowline" in line["programs"]


# ----------------------------------------------- cluster scope (real gRPC)


def test_cluster_programs_scope_on_real_grpc_cluster():
  """GET /v1/programs?scope=cluster over a REAL two-node gRPC cluster: the
  pull broadcast reaches the peer, per-family counts merge by summing (the
  in-process fixture shares one ledger → exactly 2x), and a killed peer is
  annotated unreachable without a hang (the PR 9 bundle semantics)."""
  from aiohttp.test_utils import TestClient, TestServer

  from tests.test_networking import _make_cluster
  from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI

  @tracked_jit("test.cluster")
  def f(x):
    return x + 2

  for _ in range(2):
    np.asarray(f(jnp.ones((2, 2), jnp.float32)))
  out = {}

  async def run():
    nodes = await _make_cluster(2)
    api = ChatGPTAPI(nodes[0], "DummyInferenceEngine", response_timeout=30, default_model="dummy")
    client = TestClient(TestServer(api.app))
    await client.start_server()
    try:
      resp = await client.get("/v1/programs?scope=cluster")
      out["merged"] = await resp.json()
      out["status"] = resp.status
      await nodes[1].stop()
      t0 = time.monotonic()
      resp = await client.get("/v1/programs?scope=cluster")
      out["partial"] = await resp.json()
      out["partial_elapsed"] = time.monotonic() - t0
    finally:
      await client.close()
      for n in nodes:
        try:
          await n.stop()
        except Exception:
          pass

  asyncio.run(run())
  assert out["status"] == 200
  merged = out["merged"]
  assert merged["scope"] == "cluster" and merged["unreachable"] == []
  assert set(merged["nodes"]) == {"node0", "node1"}
  # Both nodes answered from the shared in-process ledger → exact 2x sums.
  assert merged["families"]["test.cluster"]["compiles"] == 2
  assert merged["families"]["test.cluster"]["dispatches"] == 4
  # Killed peer: annotated, never waited out.
  partial = out["partial"]
  assert partial["unreachable"] == ["node1"]
  assert set(partial["nodes"]) == {"node0"}
  assert out["partial_elapsed"] < 10.0


# ------------------------------------------------------------ the AST lint


def _checker():
  sys.path.insert(0, str(REPO / "scripts"))
  try:
    import check_tracked_jit
  finally:
    sys.path.pop(0)
  return check_tracked_jit


def test_serving_path_modules_are_ledger_tracked():
  problems = _checker().check()
  assert not problems, "tracked-jit adoption drifted:\n" + "\n".join(f"  - {p}" for p in problems)


def test_checker_catches_planted_raw_jit(tmp_path):
  """The gate bites: a copy of a constrained module growing a function-local
  aliased ``jax.jit`` (and a ``from jax import jit``) fails."""
  check_tracked_jit = _checker()
  src = (REPO / "xotorch_support_jetson_tpu" / "ops" / "sampling.py").read_text()
  planted = src + (
    "\n\ndef _smuggle(x):\n"
    "  import jax as _j\n"
    "  from jax import jit as _raw\n"
    "  return _j.jit(lambda y: y)(_raw(lambda y: y)(x))\n"
  )
  pkg = tmp_path / "xotorch_support_jetson_tpu" / "ops"
  pkg.mkdir(parents=True)
  (pkg / "sampling.py").write_text(planted)
  old_repo = check_tracked_jit.REPO
  try:
    check_tracked_jit.REPO = tmp_path
    problems = check_tracked_jit.check()
    planted_hits = [p for p in problems if "sampling.py" in p and "jit" in p]
    assert len(planted_hits) >= 2, problems  # the attribute AND the import-from
    # Every other constrained module is reported missing — reverting the
    # ledger adoption by deleting a module is drift too.
    assert any("missing" in p for p in problems)
  finally:
    check_tracked_jit.REPO = old_repo


def test_checker_cli_exit_status():
  out = subprocess.run(
    [sys.executable, str(REPO / "scripts" / "check_tracked_jit.py")],
    capture_output=True, text=True,
  )
  assert out.returncode == 0, out.stdout + out.stderr
  assert "check_tracked_jit: OK" in out.stdout
