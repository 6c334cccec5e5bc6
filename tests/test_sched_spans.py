"""Scheduler-phase spans and counters (ISSUE 24).

One tiny served run on the CPU, captured with ``jax.profiler`` at the options
``benchmark/run.py`` and ``POST /v1/profile`` use (python tracer off, host
tracer level 2): the program's own ``xot.sched.*`` / ``xot.program:*`` spans
must be in the capture, nested per thread, and the always-on counters that
share their boundaries must add up.
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
