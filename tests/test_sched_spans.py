"""Scheduler-phase spans and counters (ISSUE 24).

One tiny served run on the CPU, captured with ``jax.profiler`` at the options
``benchmark/run.py`` and ``POST /v1/profile`` use (python tracer off, host
tracer level 2): the program's own ``xot.sched.*`` / ``xot.program:*`` spans
must be in the capture, nested per thread, and the always-on counters that
share their boundaries must add up. A second run (ISSUE 55) serves a tiny model
with routed experts through MIXED ticks on a paged pool: a mixed tick's
executor-side ``stage`` span says what its prefill half carried, and every count
the clock's snapshots carry grew by what its ``/metrics`` counter grew by.
"""

import asyncio
import glob
import time

import jax
import numpy as np
import pytest

from xotorch_support_jetson_tpu.utils.metrics import metrics
from xotorch_support_jetson_tpu.utils.programs import ledger

PHASES = ("admit", "plan", "stage", "readback", "settle")
TICK_FAMILIES = ("decode.", "spec.", "prefill.")  # what the scheduler loop dispatches


def _phase_seconds() -> dict:
  return {p: metrics.counter_value("sched_phase_seconds_total", labels={"phase": p}) for p in PHASES}


def _tick_dispatches() -> int:
  return sum(n for f, n in ledger.dispatch_counts().items() if f.startswith(TICK_FAMILIES))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
  """Three requests through a two-slot server (so one queues and admits at a
  later boundary), under a capture; returns the host events and the counters' growth."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = tiny_test_config(n_layers=2, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  server = BatchedServer(engine, n_slots=2, chunk=2)

  async def drive():
    prompts = [np.asarray([5, 6, 7], np.int32), np.asarray([9, 8, 7, 6], np.int32), np.asarray([3, 1], np.int32)]
    await asyncio.gather(*(server.submit(f"r{i}", p, max_tokens=8, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i, p in enumerate(prompts)))

  trace_dir = tmp_path_factory.mktemp("capture")
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.host_tracer_level = 2
  before = {"phase": _phase_seconds(), "ticks": metrics.counter_value("sched_ticks_total"), "dispatches": _tick_dispatches()}
  t0 = time.perf_counter()
  jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
  try:
    asyncio.run(drive())
  finally:
    jax.profiler.stop_trace()
    server.shutdown()
  wall = time.perf_counter() - t0
  after = {"phase": _phase_seconds(), "ticks": metrics.counter_value("sched_ticks_total"), "dispatches": _tick_dispatches()}
  (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
  pd = jax.profiler.ProfileData.from_file(path)
  lines = {}  # thread line -> [(start_ns, end_ns, name, stats)] of the program's own spans
  for plane in pd.planes:
    if plane.name.startswith("/device:"):
      continue
    for i, line in enumerate(plane.lines):  # python threads all call their line "python"
      evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats)) for ev in line.events if ev.name.startswith("xot.")]
      if evs:
        lines[f"{plane.name}/{i}:{line.name}"] = sorted(evs, key=lambda e: (e[0], -e[1]))
  return {"lines": lines, "before": before, "after": after, "wall": wall}


def _names(served) -> set[str]:
  return {name for evs in served["lines"].values() for _, _, name, _ in evs}


def test_capture_holds_every_scheduler_phase_span(served):
  assert _names(served) >= {f"xot.sched.{p}" for p in PHASES}


def test_capture_holds_program_and_trace_spans(served):
  names = _names(served)
  assert any(n.startswith("xot.program:decode.") for n in names), sorted(names)
  assert any(n.startswith("xot.program:prefill.") for n in names)
  assert any(n.startswith("xot.trace:decode.") for n in names)  # the first dispatch traced its program


def test_spans_nest_per_thread(served):
  for line, evs in served["lines"].items():
    stack = []
    for start, end, name, _ in evs:
      while stack and stack[-1][0] <= start:
        stack.pop()
      assert not stack or end <= stack[-1][0], f"{name} crosses {stack[-1][1]} on {line}"
      stack.append((end, name))


def test_program_span_runs_inside_its_stage_span_and_carries_the_tick(served):
  """On the executor thread ``stage`` runs to the end of the dispatch (the
  engine handles arguments between the transfers and the jitted call), and the
  nested ``xot.program:*`` span marks the call itself, with the same tick."""
  seen = 0
  for evs in served["lines"].values():
    stages = [(s, e, st) for s, e, n, st in evs if n == "xot.sched.stage"]
    for s, e, name, stats in evs:
      if not name.startswith("xot.program:decode."):
        continue
      (parent,) = [st for ps, pe, st in stages if ps <= s and e <= pe]
      assert int(stats["tick"]) == int(parent["tick"]) > 0 and int(stats["rows"]) >= 1
      seen += 1
  assert seen >= 2


def test_phase_counters_grow_for_every_phase(served):
  for p in PHASES:
    assert served["after"]["phase"][p] > served["before"]["phase"][p], p


def test_ticks_equal_the_ledgers_dispatches(served):
  ticks = served["after"]["ticks"] - served["before"]["ticks"]
  assert ticks == served["after"]["dispatches"] - served["before"]["dispatches"] >= 3


def test_phases_sum_to_no_more_than_wall_time(served):
  # two threads work (the loop and the engine's executor), but they take turns: a phase on one waits for the other
  total = sum(served["after"]["phase"][p] - served["before"]["phase"][p] for p in PHASES)
  assert 0 < total <= served["wall"]


# ------------------------------------------------------------------ a mixed tick's span, and the counts (ISSUE 55)

# count on a snapshot -> its /metrics counter: one call moves both (``SchedClock.inc``)
COUNTS = {
  "dispatch_behind": ("sched_dispatches_total", {"queue": "behind"}),
  "dispatch_empty": ("sched_dispatches_total", {"queue": "empty"}),
  "slice_tokens": ("sched_tick_prefill_tokens_total", None),
  "slice_pad_tokens": ("sched_tick_prefill_pad_tokens_total", None),
  "kv_pages_read": ("kv_pages_read_total", None),
  "kv_pages_resident": ("kv_pages_resident_total", None),
  "experts_visited": ("moe_experts_visited_total", None),
  "expert_layer_steps": ("moe_expert_layer_steps_total", None),
}


def _counters() -> dict:
  return {c: metrics.counter_value(family, labels=labels) for c, (family, labels) in COUNTS.items()}


@pytest.fixture(scope="module")
def served_mixed(tmp_path_factory):
  """Two short prompts and one of 80 tokens (slices of 12 tokens, padded to 16, under a budget of 12) through a four-slot paged server
  of a model with routed experts, under a capture; returns the ``stage`` spans, the slices the mixed program was
  handed, and the counts and their counters before and after."""
  from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.orchestration.tracing import tracer

  cfg = tiny_test_config(n_layers=2, max_seq_len=128, n_experts=4, n_active_experts=2, moe_hidden_dim=32)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  prompts = [[3, 25, 9], [(i % 90) + 3 for i in range(80)], [7, 1, 88, 42, 5]]
  slices: list = []
  with pytest.MonkeyPatch.context() as env:
    for key, value in {"XOT_TPU_PAGED": "1", "XOT_TPU_PAGE_SIZE": "16", "XOT_TPU_PREFILL_CHUNK": "16", "XOT_TPU_MIXED_TICK": "1", "XOT_TPU_MIXED_BUDGET": "12"}.items():
      env.setenv(key, value)
    server = BatchedServer(engine, n_slots=4, chunk=4)
    handed = server.ops.mixed_paged_batch_decode

    def spy(*a, **kw):
      slices.append((int(kw["pf_end"][0]) - int(kw["pf_prefix"][0]), int(kw["pf_tokens"].shape[1])))
      return handed(*a, **kw)

    server.ops.mixed_paged_batch_decode = spy

    async def drive():
      await asyncio.gather(*(server.submit(f"mx{i}", np.asarray(p, np.int32), max_tokens=24, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i, p in enumerate(prompts)))

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    before = {"counts": dict(server.clock.snapshot()["counts"]), "counters": _counters()}
    trace_dir = tmp_path_factory.mktemp("capture_mixed")
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
      asyncio.run(drive())
    finally:
      jax.profiler.stop_trace()
      server.shutdown()
  after = {"counts": server.clock.snapshot()["counts"], "counters": _counters()}
  (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
  stages = [dict(ev.stats) for plane in jax.profiler.ProfileData.from_file(path).planes if not plane.name.startswith("/device:") for line in plane.lines for ev in line.events if ev.name == "xot.sched.stage"]
  return {"stages": stages, "slices": slices, "before": before, "after": after, "timeline": tracer.timeline("mx1")}


def test_a_mixed_ticks_stage_span_says_what_its_prefill_half_carried_and_a_plain_ticks_does_not(served_mixed):
  stages, slices = served_mixed["stages"], served_mixed["slices"]
  assert len(slices) >= 3 and (12, 16) in slices and all(tokens <= pad < 2 * tokens and pad & (pad - 1) == 0 for tokens, pad in slices)  # a slice pads to the next power of two
  carried = [st for st in stages if "pf_tokens" in st or "pf_pad" in st]
  assert sorted((int(st["pf_tokens"]), int(st["pf_pad"])) for st in carried) == sorted(slices)  # ONE span a mixed tick: the executor's, beside the program it hands over
  assert all(int(st["tick"]) > 0 and int(st["rows"]) >= 1 for st in carried)
  mixed_ticks = {int(st["tick"]) for st in carried}
  assert len(mixed_ticks) == len(carried)
  assert any(int(st["tick"]) not in mixed_ticks for st in stages)  # plain chunks and prefill groups ran too, and theirs carry neither argument
  assert all(set(st) == {"tick", "rows"} for st in stages if st not in carried)


@pytest.mark.parametrize("count", sorted(COUNTS))
def test_each_count_on_the_clock_grew_by_what_its_metrics_counter_grew_by(served_mixed, count):
  before, after = served_mixed["before"], served_mixed["after"]
  grew = after["counts"][count] - before["counts"].get(count, 0)
  assert grew == after["counters"][count] - before["counters"][count] > 0


def test_the_counts_are_what_the_run_did_and_ride_every_snapshot(served_mixed):
  counts, slices = served_mixed["after"]["counts"], served_mixed["slices"]
  assert counts["slice_tokens"] == sum(t for t, _ in slices) and counts["slice_pad_tokens"] == sum(p for _, p in slices)
  assert counts["kv_pages_read"] == counts["kv_pages_resident"]  # no layer of this model has a window: every resident page is read
  assert counts["expert_layer_steps"] % (2 * 4) == 0 and 0 < counts["experts_visited"] <= counts["expert_layer_steps"] * 4  # two expert layers x chunks of 4 steps; at most all four experts a layer and step
  clocked = [ev["attributes"]["clock"] for ev in served_mixed["timeline"]["events"] if "clock" in (ev.get("attributes") or {})]
  assert len(clocked) == 2 and all(set(c["counts"]) <= set(COUNTS) for c in clocked)
  first, last = clocked
  assert all(last["counts"][k] >= first["counts"].get(k, 0) for k in last["counts"]) and last["counts"]["slice_tokens"] >= first["counts"].get("slice_tokens", 0) > 0  # the long prompt's own slices settled before its first token
