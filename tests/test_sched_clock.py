"""The scheduler loop's wall clock (ISSUE 41): ``SchedClock`` partitions the
loop's wall time by what the loop waits for, the batched scheduler snapshots it
on every request's ``decode`` and ``released`` stages, and the timeline's
``resident_ms`` is the difference of the two."""

import asyncio

import jax
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.inference.qos import QosConfig, QosPolicy
from xotorch_support_jetson_tpu.inference.sched_clock import DEVICE_KINDS, KINDS, SchedClock
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params
from xotorch_support_jetson_tpu.orchestration.tracing import resident_ms, tracer
from xotorch_support_jetson_tpu.utils.metrics import metrics


class _Time:
  def __init__(self):
    self.t = 1000.0

  def __call__(self) -> float:
    return self.t


# (call, argument, seconds that pass BEFORE the call), then what the sequence should book: seconds by kind, what each ready() returns, closed device intervals
SEQUENCES = {
  "unchained": (
    [("idle_end", None, 2.0), ("dispatched", "decode", 0.25), ("ready", None, 1.0), ("dispatched", "decode", 0.5), ("ready", None, 1.0)],
    {"idle": 2.0, "host": 0.75, "decode": 2.0},
    [1.0, 1.0],
    {"decode": 2},
  ),
  "chained": (  # chunk 2 queues behind chunk 1: dispatch-to-ready, then ready-to-ready, and no host time between them
    [("idle_end", None, 0.0), ("dispatched", "decode", 0.1), ("dispatched", "decode", 0.3), ("ready", None, 0.7), ("dispatched", "decode", 0.2), ("ready", None, 0.9), ("ready", None, 1.1)],
    {"host": 0.1, "decode": 3.2},
    [1.0, 1.1, 1.1],
    {"decode": 3},
  ),
  "mixed": (  # a mixed tick chained behind a plain chunk owns the clock from the plain chunk's readback
    [("idle_end", None, 0.0), ("dispatched", "decode", 0.5), ("dispatched", "mixed", 0.5), ("ready", None, 0.5), ("ready", None, 2.0), ("dispatched", "spec", 0.25), ("ready", None, 0.75)],
    {"host": 0.75, "decode": 1.0, "mixed": 2.0, "spec": 0.75},
    [1.0, 2.0, 0.75],
    {"decode": 1, "mixed": 1, "spec": 1},
  ),
  "group-behind-a-chunk": (  # ISSUE 51: a prefill group enqueued behind chunk N, N settled under it, chunk N+1 enqueued behind the group, the group settled under N+1 — the host never holds the clock
    [("idle_end", None, 0.0), ("dispatched", "decode", 0.25), ("dispatched", "prefill", 0.5), ("ready", None, 0.75), ("dispatched", "decode", 0.5), ("ready", None, 1.5), ("dispatched", "decode", 0.25), ("ready", None, 1.0), ("ready", None, 2.0)],
    {"host": 0.25, "decode": 4.5, "prefill": 2.0},
    [1.25, 2.0, 1.25, 2.0],
    {"decode": 3, "prefill": 1},
  ),
  "withdrawn-group": (  # a group whose enqueue raised leaves the queue: behind a chunk the chunk keeps the clock, alone the host takes it back
    [("idle_end", None, 0.0), ("dispatched", "decode", 0.5), ("dispatched", "prefill", 0.25), ("withdrawn", None, 0.25), ("ready", None, 1.0), ("dispatched", "prefill", 0.5), ("withdrawn", None, 0.25), ("dispatched", "decode", 0.25), ("ready", None, 1.0)],
    {"host": 1.25, "decode": 2.5, "prefill": 0.25},
    [1.5, 1.0],
    {"decode": 2, "prefill": 1},
  ),
  "failed-prefill": (  # the failure path closes the interval as a readback does; a reset drops what was in flight
    [("idle_end", None, 1.0), ("dispatched", "prefill", 0.5), ("ready", None, 3.0), ("dispatched", "decode", 0.5), ("dispatched", "decode", 0.5), ("reset", None, 0.25), ("idle_end", None, 4.0), ("dispatched", "prefill", 0.125), ("ready", None, 1.0)],
    {"idle": 5.0, "host": 1.125, "prefill": 4.0, "decode": 0.75},
    [3.0, 1.0],
    {"prefill": 2, "decode": 1},  # the chunk queued behind the one that failed never took the clock
  ),
  "idle": (  # the wait on the queue is no host gap; idle_begin / idle_end out of turn book nothing twice
    [("idle_begin", None, 1.0), ("idle_end", None, 1.0), ("idle_end", None, 1.0), ("idle_begin", None, 1.0), ("idle_begin", None, 5.0), ("idle_end", None, 5.0), ("dispatched", "prefill", 0.5), ("ready", None, 0.5)],
    {"idle": 12.0, "host": 2.5, "prefill": 0.5},
    [0.5],
    {"prefill": 1},
  ),
}


@pytest.mark.parametrize("name", sorted(SEQUENCES))
def test_kinds_partition_the_wall_time(name):
  calls, want, want_dts, want_intervals = SEQUENCES[name]
  now = _Time()
  clock = SchedClock(now=now)
  before = {k: metrics.counter_value("sched_wall_seconds_total", labels={"kind": k}) for k in KINDS}
  dts = []
  for call, arg, wait in calls:
    now.t += wait
    got = getattr(clock, call)(*(() if arg is None else (arg,)))
    if call == "ready":
      dts.append(got)
    snap = clock.snapshot()  # a snapshot books up to now and moves nothing else: the invariant holds after every call
    assert abs(sum(snap["seconds"].values()) - (snap["t"] - clock.t_started)) < 1e-9
  now.t += 0.375  # whatever kind the sequence ends in keeps the clock
  snap = clock.snapshot()
  assert abs(sum(snap["seconds"].values()) - (now.t - 1000.0)) < 1e-9
  last = "idle" if calls[-1][0] == "reset" else "host"
  assert snap["seconds"] == pytest.approx({**dict.fromkeys(KINDS, 0.0), **want, last: want.get(last, 0.0) + 0.375})
  assert dts == pytest.approx(want_dts)
  assert {k: n for k, n in snap["intervals"].items() if k in DEVICE_KINDS and n} == want_intervals
  for k in KINDS:  # /metrics carries the same sums
    assert metrics.counter_value("sched_wall_seconds_total", labels={"kind": k}) - before[k] == pytest.approx(snap["seconds"][k])


def test_expected_is_what_the_kind_last_took_from_when_the_oldest_dispatch_got_the_clock():
  now = _Time()
  clock = SchedClock(now=now)
  clock.idle_end()
  assert clock.expected() is None  # nothing in flight
  clock.dispatched("decode")
  assert clock.expected() is None  # no decode chunk read back yet: no estimate
  now.t += 2.0
  clock.dispatched("prefill")  # behind the chunk
  assert clock.ready() == 2.0 and clock.last == {"decode": 2.0}
  assert clock.expected() is None  # the group has the clock now, and no group has been read back
  now.t += 0.5
  clock.dispatched("decode")
  assert clock.ready() == 0.5
  now.t += 0.5
  assert clock.expected() == pytest.approx((1.5, 2.0))  # the chunk got the clock at the group's readback; its kind last took 2.0
  now.t += 2.5
  assert clock.expected() == pytest.approx((-1.0, 2.0))  # overdue: the estimate is an estimate


def test_snapshot_carries_phases_ticks_and_steps():
  clock = SchedClock(now=_Time())
  clock.phase("stage", 0.5)
  clock.phase("stage", 0.25)
  clock.phase("settle", 0.125)
  clock.tick()
  clock.dispatched("decode")
  clock.ready(steps=8)
  snap = clock.snapshot()
  assert snap["phases"] == {"stage": 0.75, "settle": 0.125} and snap["ticks"] == 1 and snap["steps"] == 8
  snap["seconds"]["decode"] = -1.0  # a copy: a timeline's snapshot does not move with the clock
  assert clock.snapshot()["seconds"]["decode"] == 0.0


def test_inc_moves_a_count_and_its_metrics_counter_by_the_same_amount():
  """ISSUE 55: the scheduler's counts ride the snapshots, and one call moves a count and its ``/metrics`` counter."""
  now = _Time()
  clock = SchedClock(now=now)
  assert clock.snapshot()["counts"] == {}  # a count appears with its first increment
  before = {q: metrics.counter_value("sched_dispatches_total", labels={"queue": q}) for q in ("behind", "empty")}
  pages = metrics.counter_value("kv_pages_read_total")
  clock.inc("sched_dispatches_total", count="dispatch_empty", labels={"queue": "empty"})
  clock.inc("kv_pages_read_total", 40, count="kv_pages_read")
  first = clock.snapshot()
  now.t += 1.0
  clock.inc("sched_dispatches_total", count="dispatch_behind", labels={"queue": "behind"})
  clock.inc("sched_dispatches_total", count="dispatch_behind", labels={"queue": "behind"})
  clock.inc("kv_pages_read_total", 2, count="kv_pages_read")
  last = clock.snapshot()
  assert first["counts"] == {"dispatch_empty": 1, "kv_pages_read": 40}  # a copy: the first does not move with the clock
  assert last["counts"] == {"dispatch_empty": 1, "dispatch_behind": 2, "kv_pages_read": 42}
  assert {q: metrics.counter_value("sched_dispatches_total", labels={"queue": q}) - n for q, n in before.items()} == {"behind": 2, "empty": 1}
  assert metrics.counter_value("kv_pages_read_total") - pages == 42
  assert abs(sum(last["seconds"].values()) - (last["t"] - clock.t_started)) < 1e-9  # counting books no time


def test_resident_ms_sums_the_residencies_and_skips_an_open_one():
  def clock(decode, prefill, host, steps):
    return {"seconds": {"decode": decode, "mixed": 0.0, "spec": 0.0, "prefill": prefill, "host": host, "idle": 7.0}, "steps": steps}

  events = [
    {"stage": "queued", "attributes": {}},
    {"stage": "decode", "attributes": {"first_token": 1}},  # a node's own stage of the same name carries no clock
    {"stage": "decode", "attributes": {"clock": clock(1.0, 0.5, 0.25, 8)}},
    {"stage": "preempted", "attributes": {}},
    {"stage": "released", "attributes": {"clock": clock(1.5, 0.75, 0.5, 24)}},
    {"stage": "decode", "attributes": {"clock": clock(3.0, 1.0, 1.0, 80)}},
    {"stage": "released", "attributes": {"clock": clock(3.25, 1.0, 1.125, 88)}},
    {"stage": "decode", "attributes": {"clock": clock(9.0, 9.0, 9.0, 800)}},  # still resident: counts nothing yet
  ]
  assert resident_ms(events) == {"decode": 750.0, "mixed": 0.0, "spec": 0.0, "prefill": 250.0, "host": 375.0, "steps": 24}
  assert resident_ms([]) == {"steps": 0}  # no clock on the timeline (solo path, rings): no kinds


# ------------------------------------------------------------------ the served path


def _server(**kw) -> BatchedServer:
  cfg = tiny_test_config(n_layers=2, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "m")
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, cfg, params)
  return BatchedServer(engine, **kw)


def _hists() -> dict:
  return {h: metrics.hist_count(h) for h in ("prefill_chunk_seconds", "decode_chunk_seconds", "mixed_tick_seconds")}


def _residencies(tl: dict) -> list[tuple[dict, dict]]:
  """(decode event, released event) per residency, in order; every ``decode`` must be followed by its ``released``."""
  clocked = [ev for ev in tl["events"] if ev["stage"] in ("decode", "released") and "clock" in ev["attributes"]]
  assert [ev["stage"] for ev in clocked] == ["decode", "released"] * (len(clocked) // 2), [ev["stage"] for ev in tl["events"]]
  return list(zip(clocked[::2], clocked[1::2]))


def _assert_parts_sum_to_the_interval(tl: dict) -> None:
  parts = tl["resident_ms"]
  pairs = _residencies(tl)
  on_the_timeline = sum(b["at_ms"] - a["at_ms"] for a, b in pairs)
  assert set(parts) == {*KINDS, "steps"} - {"idle"}  # every kind the clock keeps but idle
  assert abs(sum(ms for k, ms in parts.items() if k != "steps") - on_the_timeline) < 1.0, (parts, on_the_timeline)
  for a, b in pairs:  # idle is no part of a residency: a resident row is pending work
    assert b["attributes"]["clock"]["seconds"]["idle"] == a["attributes"]["clock"]["seconds"]["idle"]


def test_a_second_request_admitted_while_the_first_decodes_shows_as_its_prefill_time():
  server = _server(n_slots=2, chunk=2)
  before = _hists()
  ticks_before = server.clock.ticks

  async def run():
    first_tokens = asyncio.Event()

    def emit(rid, toks, fin):
      if rid == "clk-a" and toks:
        first_tokens.set()

    a = asyncio.create_task(server.submit("clk-a", np.asarray([5, 6, 7], np.int32), max_tokens=24, temp=0.0, top_k=35, eos_ids=(), emit=emit))
    await asyncio.wait_for(first_tokens.wait(), timeout=60)
    await asyncio.wait_for(server.submit("clk-b", np.asarray([9, 8, 7, 6], np.int32), max_tokens=6, temp=0.0, top_k=35, eos_ids=(), emit=emit), timeout=60)
    await asyncio.wait_for(a, timeout=60)

  asyncio.run(run())
  try:
    a, b = tracer.timeline("clk-a"), tracer.timeline("clk-b")
    assert a["resident_ms"]["prefill"] > 0  # b's prompt stopped a
    assert a["resident_ms"]["steps"] == 24 and b["resident_ms"]["steps"] == 6  # the chunks of 2 steps that gave it its tokens after the first: 23 in 12, 5 in 3
    for tl in (a, b):
      assert len(_residencies(tl)) == 1
      _assert_parts_sum_to_the_interval(tl)
      assert "released" in [s["stage"] for s in tl["stages"]]  # stage_summary treats it as any stage
    # the three chunk histograms count what they counted: one observation a prefill group, one a decode or mixed chunk
    grew = {h: n - before[h] for h, n in _hists().items()}
    snap = server.clock.snapshot()
    assert grew["prefill_chunk_seconds"] == snap["intervals"]["prefill"] >= 2
    assert grew["decode_chunk_seconds"] == snap["intervals"]["decode"] >= 11
    assert grew["mixed_tick_seconds"] == snap["intervals"]["mixed"]
    assert snap["ticks"] - ticks_before == sum(snap["intervals"][k] for k in DEVICE_KINDS)
    assert abs(sum(snap["seconds"].values()) - (snap["t"] - server.clock.t_started)) < 1e-6
    assert snap["seconds"]["host"] > 0 and snap["phases"].keys() >= {"admit", "plan", "stage", "readback", "settle"}
  finally:
    server.shutdown()


def test_a_preempted_and_resumed_row_has_two_residencies():
  server = _server(n_slots=1, chunk=2, qos=QosPolicy(QosConfig(aging_s=10_000.0)))

  async def run():
    started = asyncio.Event()
    got = []

    def emit(rid, toks, fin):
      if rid == "clk-bg":
        got.extend(toks)
        if len(got) >= 4:
          started.set()

    bg = asyncio.create_task(server.submit("clk-bg", np.asarray([3, 25, 9], np.int32), max_tokens=24, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="batch", tenant="bulk"))
    await asyncio.wait_for(started.wait(), timeout=60)
    await asyncio.wait_for(server.submit("clk-vip", np.asarray([7, 1, 88, 42, 5], np.int32), max_tokens=4, temp=0.0, top_k=35, eos_ids=(), emit=emit, priority="interactive", tenant="vip"), timeout=60)
    await asyncio.wait_for(bg, timeout=60)

  asyncio.run(run())
  try:
    tl = tracer.timeline("clk-bg")
    stages = [ev["stage"] for ev in tl["events"]]
    assert "preempted" in stages and stages[stages.index("preempted") + 1] == "released"
    assert len(_residencies(tl)) == 2
    _assert_parts_sum_to_the_interval(tl)
    assert tl["resident_ms"]["steps"] % 2 == 0 and 22 <= tl["resident_ms"]["steps"] <= 26  # every token but each incarnation's first came out of a decode step
  finally:
    server.shutdown()


def test_the_wait_on_the_queue_is_one_span_that_nests_on_the_loop_thread(tmp_path):
  """``xot.sched.idle`` is the one ``TraceAnnotation`` the scheduler opens across an await: a capture at the options
  ``benchmark/run.py`` uses must hold the wait between two batches as one event, and every ``xot.*`` event of its
  thread must still nest — the clock's ``idle`` kind holds the same wait."""
  import glob

  server = _server(n_slots=2, chunk=2)

  async def batch(tag):
    await asyncio.gather(*(server.submit(f"{tag}{i}", np.asarray([5, 6, 7 + i], np.int32), max_tokens=6, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i in range(2)))

  async def drive():
    await batch("idle-warm")  # compiles; the wait that follows straddles start_trace and is lost
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
      await batch("idle-a")
      idle_before = server.clock.snapshot()["seconds"]["idle"]
      await asyncio.sleep(0.3)
      await batch("idle-b")
      return server.clock.snapshot()["seconds"]["idle"] - idle_before
    finally:
      jax.profiler.stop_trace()

  try:
    idle_on_the_clock = asyncio.run(drive())
  finally:
    server.shutdown()
  (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
  waits = []
  for plane in jax.profiler.ProfileData.from_file(path).planes:
    for line in plane.lines:
      evs = sorted(((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events if ev.name.startswith("xot.")), key=lambda e: (e[0], -e[1]))
      stack = []
      for start, end, name in evs:
        while stack and stack[-1][0] <= start:
          stack.pop()
        assert not stack or end <= stack[-1][0], f"{name} crosses {stack[-1][1]} on {line.name}"
        stack.append((end, name))
      waits += [(end - start) / 1e9 for start, end, name in evs if name == "xot.sched.idle"]
  assert len(waits) == 1 and 0.25 < waits[0] < 5.0  # the pause between the batches, whole; the straddling wait and the one still open at stop_trace are not recorded
  assert abs(idle_on_the_clock - waits[0]) < 0.05
