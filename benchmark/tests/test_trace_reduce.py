"""The trace reduction against two small recorded traces — one ``decode.paged_batch``
dispatch (8 steps, 16 rows, Mistral-7B int8, a 33-page pool) recorded on a TPU v5 lite by
``tools/pool_probe.py`` (PR 23), and ``tools/record_spans.py``'s (PR 24) — against
synthetic planes, and ``run.py``'s capture against a stubbed profiler. Neither recorded
file holds the capture's marker: both are read over their device events' own extent, or
over an interval the test names. ``data/reduce_4367f61.json`` is what the reduction of
``4367f61`` (before the interval, PR 31) returned for them."""

import asyncio
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = ("decode_paged_batch_33pages", "decode_scopes_spans")
BEFORE = json.loads((DATA / "reduce_4367f61.json").read_text())
FAMILIES = {"_fused_paged_batch_decode_impl": "decode.paged_batch"}
KERNELS = ("paged_decode", "flash")
NS_TOL = 1e-9


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
  """name -> (ProfileData, the unpacked file's path)."""
  out = {}
  for name in RECORDED:
    raw = tmp_path_factory.mktemp("trace") / f"{name}.xplane.pb"
    raw.write_bytes(gzip.decompress((DATA / f"{name}.xplane.pb.gz").read_bytes()))
    out[name] = (trace_reduce.load(str(raw)), raw)
  return out


@pytest.fixture(scope="module")
def reduced(recorded):
  return trace_reduce.reduce(recorded[RECORDED[0]][0], FAMILIES, kernels=KERNELS)


def test_busy_time_and_program(reduced):
  assert reduced["chips"] == 1
  assert reduced["busy_s"] == pytest.approx(0.24688, rel=1e-3)
  prog = reduced["programs"]["decode.paged_batch"]
  assert prog["executions"] == 1 and prog["device_s"] == pytest.approx(0.24688, rel=1e-3)
  assert 0 < reduced["busy_s"] <= reduced["window_s"]


def test_kernel_calls_and_self_times(reduced):
  k = reduced["kernels"]["paged_decode"]
  assert k["calls"] == 32 * 8  # one call a layer a step
  assert k["device_s"] == pytest.approx(0.14066, rel=1e-3)
  names = [n for n, _ in reduced["device_ops"]]
  assert names[0] == "_paged_decode_attention_impl" and "while" not in names[:3]  # the loop's own time excludes its body
  assert sum(t for _, t in reduced["device_ops"]) <= reduced["busy_s"] * 1.001


# ------------------------------------------------------------------ the interval (PR 31)


def around(red: dict, before: float, after: float) -> tuple[float, float]:
  """An interval that starts ``before`` and ends ``after`` the recorded device events' extent (seconds; negative: inside)."""
  return red["extent"][0] - before, red["extent"][1] + after


@pytest.mark.parametrize("name", RECORDED)
@pytest.mark.parametrize("margin", [None, 0.0, 0.5], ids=["no_marker", "extent", "wider"])
def test_an_interval_that_covers_the_file_reads_what_the_parent_read(recorded, name, margin):
  """Without a marker the interval is the device events' extent; that interval named, or a wider
  one, counts the same work: ``busy_s`` and, value for value, what the per-layer readers read."""
  pd = recorded[name][0]
  red = trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS)
  assert not red["marked"] and red["interval"] == red["extent"] and red["outside_s"] == 0.0
  if margin is not None:
    red = trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS, interval=around(red, margin, margin))
  was = BEFORE[name]
  assert red["busy_s"] == pytest.approx(was["busy_s"], abs=NS_TOL) and red["outside_s"] == pytest.approx(0.0, abs=NS_TOL)
  assert {k: red[k] for k in ("chips", "programs", "kernels", "device_ops")} == {k: was[k] for k in ("chips", "programs", "kernels", "device_ops")}
  assert red["window_s"] == pytest.approx(red["extent"][1] - red["extent"][0] + 2 * (margin or 0.0), abs=NS_TOL)
  assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"], abs=NS_TOL)
  if not margin:  # the gaps between operations are the parent's; a wider interval adds its two ends
    assert red["idle_gaps"] == [[k, pytest.approx(v, abs=NS_TOL)] for k, v in was["idle_gaps"]]
  else:
    assert red["idle_s"] == pytest.approx(sum(v for _, v in was["idle_gaps"]) + 2 * margin, abs=1e-6)


@pytest.mark.parametrize("name", RECORDED)
def test_operations_across_an_edge_count_for_their_part_inside(recorded, name):
  """The middle half of the file: busy <= window, busy + idle = window to a nanosecond, and the
  three pieces of the extent add up to the whole — an operation across a cut gives each side its part."""
  pd = recorded[name][0]
  whole = trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS)
  lo, hi = whole["extent"]
  quarter = (hi - lo) / 4
  pieces = [trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS, interval=iv) for iv in ((lo, lo + quarter), (lo + quarter, hi - quarter), (hi - quarter, hi))]
  middle = pieces[1]
  assert 0 < middle["busy_s"] <= middle["window_s"] == pytest.approx(2 * quarter, abs=NS_TOL)
  assert middle["outside_s"] == pytest.approx(whole["busy_s"] - middle["busy_s"], abs=NS_TOL) and middle["outside_s"] > 0
  for piece in pieces:
    assert piece["busy_s"] + piece["idle_s"] == pytest.approx(piece["window_s"], abs=NS_TOL)
    assert {k: piece[k] for k in ("programs", "kernels", "device_ops")} == {k: whole[k] for k in ("programs", "kernels", "device_ops")}  # whole events of the whole file
  assert sum(p["busy_s"] for p in pieces) == pytest.approx(whole["busy_s"], abs=NS_TOL)
  assert sum(p["idle_s"] for p in pieces) == pytest.approx(whole["idle_s"], abs=NS_TOL)


@pytest.mark.parametrize("name", RECORDED)
def test_an_interval_that_holds_no_operation_is_not_printed(recorded, name):
  import run

  pd = recorded[name][0]
  whole = trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS)
  red = trace_reduce.reduce(pd, FAMILIES, kernels=KERNELS, interval=(whole["extent"][0] - 2.0, whole["extent"][0] - 1.0))
  assert red["busy_s"] == 0.0 and red["idle_s"] == pytest.approx(1.0, abs=NS_TOL) and red["outside_s"] == pytest.approx(whole["busy_s"], abs=NS_TOL)
  assert len(red["idle_gaps"]) == 1 and red["idle_gaps"][0][1] == pytest.approx(1.0, abs=NS_TOL)
  with pytest.raises(RuntimeError, match=r"busy_s 0\.0 is not above 0 .*\"attempt\": 1"):
    run.device_seconds(red, {"event": "capture", "attempt": 1})
  assert run.device_seconds(whole, None) == {"busy_s": whole["busy_s"], "window_s": whole["window_s"]}


@pytest.mark.parametrize("reduced_as", [None, {"busy_s": float("nan"), "window_s": 6.0}, {"busy_s": 6.1, "window_s": 6.0}, {"busy_s": 1.0, "window_s": float("inf")}],
                         ids=["no_trace", "nan", "above_window", "infinite"])
def test_a_line_the_driver_would_refuse_is_refused_first(reduced_as):
  import run

  with pytest.raises(RuntimeError, match="no result line: device.busy_s"):
    run.device_seconds(reduced_as, None)


def ev(name: str, start_ms: int, end_ms: int):
  """Whole milliseconds, as the file's float nanoseconds: an event that ends where the next starts touches it exactly."""
  return NS(name=name, start_ns=float(start_ms * 10**6), duration_ns=float((end_ms - start_ms) * 10**6), stats=())


def synthetic(ops, host=(), modules=(), chips: int = 1):
  """A ``ProfileData`` look-alike: ``chips`` device planes with the same ops, one host thread."""
  device = [NS(name=f"/device:TPU:{i}", lines=[NS(name="XLA Modules", events=[ev(*m) for m in modules]), NS(name="XLA Ops", events=[ev(*o) for o in ops])]) for i in range(chips)]
  return NS(planes=[*device, NS(name="/host:CPU", lines=[NS(name="python/1", events=[ev(*h) for h in host])])])


@pytest.mark.parametrize("chips", [1, 4])
def test_a_device_busy_from_before_the_marker_to_after_it_reads_busy_equal_to_window(chips):
  """What refused PR 30: back-to-back operations from before the capture's begin to after its end.
  The parent's rule read the file's 6.3 busy seconds against a 6 s window."""
  ops = [(f"%fusion.{i} = f32[] fusion()", 900 + 12 * i, 900 + 12 * (i + 1)) for i in range(525)]  # 0.9 s .. 7.2 s
  red = trace_reduce.reduce(synthetic(ops, host=[(trace_reduce.MARK, 1000, 7000)], chips=chips), {})
  assert red["marked"] and red["interval"] == [1.0, 7.0] and red["chips"] == chips
  assert red["busy_s"] == red["window_s"] == 6.0 and red["idle_s"] == 0.0 and red["idle_gaps"] == []
  assert red["outside_s"] == pytest.approx(0.3, abs=NS_TOL) and red["extent"] == [0.9, pytest.approx(7.2)]
  assert red["device_ops"] == [["fusion", pytest.approx(6.3)]]  # the readers' sums stay those of the whole file


def test_the_marked_interval_bounds_the_gaps_and_the_marker_names_none():
  """Idle from the begin marker to the first operation and from the last to the end marker are gaps,
  named by the host span that covers them; the marker itself, which covers every gap, names none."""
  ops = [("%a = f32[] copy()", 500, 800), ("%b = f32[] fusion()", 2000, 3000), ("%c = f32[] fusion()", 3500, 4000), ("%d = f32[] copy()", 5500, 6500)]
  host = [(trace_reduce.MARK, 1000, 5000), ("xot.sched.stage", 900, 2000), ("xot.sched.readback", 4000, 5200)]  # begin -> b 1.0 s, b -> c 0.5 s, c -> end 1.0 s
  red = trace_reduce.reduce(synthetic(ops, host=host), {})
  assert (red["busy_s"], red["idle_s"], red["window_s"], red["outside_s"]) == (pytest.approx(1.5), pytest.approx(2.5), 4.0, pytest.approx(1.3))
  assert dict(map(tuple, red["idle_gaps"])) == {"python:xot.sched.stage": pytest.approx(1.0), "python:xot.sched.readback": pytest.approx(1.0), "unattributed": pytest.approx(0.5)}
  assert trace_reduce.marked_interval(synthetic(ops)) is None


def test_no_device_plane_reads_no_chip():
  red = trace_reduce.reduce(synthetic([], host=[(trace_reduce.MARK, 1000, 2000)], chips=0), {})
  assert red["chips"] == 0 and red["busy_s"] == 0.0 and red["window_s"] == 1.0 and red["extent"] is None


# ------------------------------------------------------------------ run.py capture(): an empty capture is taken again


@pytest.mark.parametrize("dispatched, kept", [([0, 0, 0, 0, 0, 5, 5], 2), ([0, 0, 0, 7, 7, 7, 7], 1), ([0] * 9, None), ([0, 0, 0, (0, 2), (0, 2), (5, 2), (5, 2)], 2)], ids=["second_kept", "first_kept", "all_empty", "prefill_only_taken_again"])
def test_a_capture_without_a_dispatch_is_taken_again(recorded, tmp_path, monkeypatch, capsys, dispatched, kept):
  """``measure`` with a stubbed profiler (each ``start_trace`` leaves the recorded file under a name of
  its own), a stubbed load generator and a scripted ``counters()``: window open, window close and two
  readings a capture, in the order of the calls. A capture over which prefills moved and no decode step
  (an open loop's lull) is taken again too: the decode readers would find nothing in it (PR 39)."""
  import jax

  import run

  calls, started = [], []
  pair = lambda v: v if isinstance(v, tuple) else (v, 0)  # noqa: E731 — (decode, prefill) dispatches so far; a bare number is decode's

  async def counters(session, url):
    calls.append(pair(dispatched[min(len(calls), len(dispatched) - 1)]))
    return {"compiles": 0, "families": {"decode.paged_batch": (0, calls[-1][0]), "prefill.pages_many": (0, calls[-1][1])}}

  def start_trace(log_dir, profiler_options=None):
    started.append(len(started) + 1)
    folder = Path(log_dir) / "plugins" / "profile" / f"attempt{started[-1]}"
    folder.mkdir(parents=True)
    (folder / "host.xplane.pb").write_bytes(recorded[RECORDED[0]][1].read_bytes())

  async def open_loop(session, url, model_id, phases, t0, drain_s):
    await asyncio.sleep(2.0)
    return []

  monkeypatch.setattr(run, "counters", counters)
  monkeypatch.setattr(run, "CAPTURE_S", 0.2)
  monkeypatch.setattr(run.client, "open_loop", open_loop)
  monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
  monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
  plan = {"mode": "open", "ramp_s": 0.0, "ramp": [], "window": [], "drain_s": 0.0}
  args = NS(seconds=2.0, trace=1)
  trace_dir = tmp_path / "bench_trace" / "cell"
  measure = run.measure(None, NS(url="http://stub", model_id="stub"), plan, args, trace_dir)
  if kept is None:
    with pytest.raises(RuntimeError, match="dispatched nothing during any of 3 captures of 0.2 s"):
      asyncio.run(measure)
    assert started == [1, 2, 3] and trace_reduce.find_xplane(str(trace_dir)) is None
    events = [json.loads(line) for line in capsys.readouterr().err.splitlines() if line.startswith('{"event": "capture"')]
    assert [(e["attempt"], e["kept"], e["dispatches"]) for e in events] == [(1, False, {}), (2, False, {}), (3, False, {})]
    return
  ctx = asyncio.run(measure)
  assert started == list(range(1, kept + 1)) and Path(trace_reduce.find_xplane(str(trace_dir))).parent.name == f"attempt{kept}"
  assert len(list(trace_dir.rglob("*.xplane.pb"))) == 1  # an empty capture's trace is removed: the readers take the newest file
  red = run.reduce_trace(trace_dir, ctx, KERNELS)
  events = [json.loads(line) for line in capsys.readouterr().err.splitlines() if line.startswith('{"event": "capture"')]
  assert [(e["attempt"], e["kept"]) for e in events] == [(n, n == kept) for n in range(1, kept + 1)]
  last = events[-1]
  assert last["dispatches"] == {"decode.paged_batch": max(pair(v)[0] for v in dispatched)} and last["offset_s"] == [ctx["cap_start"] - ctx["t_open"], ctx["cap_end"] - ctx["t_open"]]
  assert 0.9 + 0.2 * (kept - 1) <= last["offset_s"][0] < last["offset_s"][1] <= 2.0
  assert (last["marked"], last["interval"], last["extent"], last["busy_s"], last["window_s"], last["outside_s"]) == (False, red["extent"], red["extent"], red["busy_s"], red["window_s"], 0.0)
  assert run.device_seconds(red, ctx["capture"]) == {"busy_s": pytest.approx(0.24688, rel=1e-3), "window_s": red["window_s"]}


def test_names():
  assert trace_reduce.module_base("jit__fused_paged_batch_decode_impl(1528432197634081039)") == "_fused_paged_batch_decode_impl"
  assert trace_reduce.op_base("%copy.152 = s8[257,8,64,128]{3,2,1,0} copy(%x)") == "copy"
  assert trace_reduce.self_times([(0.0, 10.0, "loop"), (1.0, 3.0, "a"), (4.0, 9.0, "b"), (5.0, 6.0, "c")]) == [(3.0, "loop"), (2.0, "a"), (4.0, "b"), (1.0, "c")]


def test_program_families_finds_tracked_callables_wherever_they_live():
  """The names the reduction mapped before PR 26 (three modules' attributes, recorded from
  ``bb2de62``) map as they did; a tracked callable of another module and one made inside a
  function, as the ring's programs are, are found too; a name two families share is left out."""
  import json
  import os

  os.environ.setdefault("JAX_PLATFORMS", "cpu")
  sys.path.insert(0, str(Path(__file__).resolve().parent.parent.parent))
  from xotorch_support_jetson_tpu.models import decoder  # noqa: F401
  from xotorch_support_jetson_tpu.ops import paged, pallas_attention, sampling  # noqa: F401
  from xotorch_support_jetson_tpu.utils.programs import tracked_jit

  def build():
    @tracked_jit("test.closure_family")
    def _made_inside_a_function(x):
      return x + 1

    @tracked_jit("test.one_family")
    def _shared_name(x):
      return x

    return _made_inside_a_function, _shared_name

  def build_again():
    @tracked_jit("test.another_family")
    def _shared_name(x):
      return x

    return _shared_name

  keep = (build(), build_again())  # noqa: F841 - alive while the collector is asked
  for fn in (*keep[0], keep[1]):
    fn.__module__ = f"{trace_reduce.PROGRAM_PACKAGE}.made_up"
  got = trace_reduce.program_families()
  before = json.loads((Path(__file__).resolve().parent / "data" / "families_bb2de62.json").read_text())
  assert {k: got.get(k) for k in before} == before
  assert got["_made_inside_a_function"] == "test.closure_family"
  assert got["sample_logits"] == "sample.logits" and "sample_logits" not in before  # ops/sampling.py: not among the three modules
  assert "_shared_name" not in got
