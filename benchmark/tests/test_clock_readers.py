"""The readers of the scheduler's own wall clock (PR 41) on synthetic contexts: ``clock_lib.window_clock`` takes the
first and the last snapshot inside the window up to the capture's opening and drops the rest, every reader says None
(never 0) where a program wrote no clock or the timelines that are left begin too late, ``queue_wait_p50_ms`` starts at the admission layer's ``queued`` and not the node's — and
``trace_reduce._host_name`` names a gap by the new ``xot.sched.idle`` span with no edit of its own."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import clock_lib  # noqa: E402
import common  # noqa: E402
import pytest  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402

KINDS = ("decode", "mixed", "spec", "prefill", "host", "idle")
NEW = ("prefill_wall_share", "host_gap_wall_share", "sched_host_ms_per_tick_window", "queue_wait_p50_ms")


def _snap(t: float, **moved) -> dict:
  """A snapshot at ``t`` of a clock that started at 100: every second since then is ``decode``'s but those named."""
  seconds = {**dict.fromkeys(KINDS, 0.0), **{k: v for k, v in moved.items() if k in KINDS}}
  seconds["decode"] = t - 100.0 - sum(seconds.values())
  ticks = int(moved.get("ticks", 0))
  return {"t": t, "ticks": ticks, "steps": 8 * ticks, "seconds": seconds, "intervals": dict.fromkeys(KINDS, ticks),
          "phases": {"admit": 0.001 * ticks, "plan": 0.0005 * ticks, "stage": 0.004 * ticks, "readback": 0.5 * ticks, "settle": 0.0025 * ticks}}


def _timeline(*events) -> dict:
  return {"events": [{"stage": stage, "at_ms": at_ms, "attributes": attrs} for stage, at_ms, attrs in events]}


def _ctx(timelines: dict, sent: dict) -> dict:
  """A window [200, 251) on the client's clock; ``sent`` maps request id -> when the client sent it."""
  return {"recs": [NS(rid=rid, sent=t) for rid, t in sent.items()], "timelines": timelines, "t_open": 200.0, "t_close": 251.0}


def _read(name: str, ctx: dict):
  return run.load_reader("per_layer", f"{name}.closed").read(ctx)


@pytest.fixture
def window():
  """Three requests: ``a`` got its first token before the window opened (dropped) and left inside it, ``b`` lies
  inside, ``c`` left after it closed (dropped). Between a's release (t 310) and b's (t 350): 40 s, of which prefill
  8, host 2, idle 0, and 100 ticks."""
  timelines = {
    "a": _timeline(("queued", 0.0, {"node_id": "n"}), ("queued", 2.0, {"queue_depth": 1}), ("admitted", 12.0, {"row": 0}),
                   ("decode", 500.0, {"clock": _snap(299.0, prefill=1.0, ticks=10)}), ("released", 11_000.0, {"clock": _snap(310.0, prefill=2.0, host=1.0, idle=50.0, ticks=20)})),
    "b": _timeline(("queued", 0.0, {"node_id": "n"}), ("queued", 1.0, {"queue_depth": 3}), ("admitted", 5.0, {"row": 1}),
                   ("decode", 900.0, {"clock": _snap(320.0, prefill=4.0, host=1.5, idle=50.0, ticks=40)}), ("released", 30_000.0, {"clock": _snap(350.0, prefill=10.0, host=3.0, idle=50.0, ticks=120)})),
    "c": _timeline(("queued", 0.5, {"queue_depth": 2}), ("admitted", 30.5, {"row": 2}),
                   ("decode", 1000.0, {"clock": _snap(340.0, prefill=9.0, host=2.5, idle=50.0, ticks=100)}), ("released", 12_000.0, {"clock": _snap(365.0, prefill=30.0, host=9.0, idle=50.0, ticks=300)})),
  }
  return _ctx(timelines, {"a": 199.4, "b": 220.0, "c": 240.0})


def test_window_clock_takes_the_first_and_last_snapshot_inside_the_window(window, capsys):
  first, last = clock_lib.window_clock(window)
  assert (first["t"], last["t"]) == (310.0, 350.0)  # a's decode (client 199.4 + 0.5) and c's release (252) lie outside
  (event,) = [json.loads(line) for line in capsys.readouterr().err.splitlines() if '"wall"' in line]
  assert event["snapshots"] == 4 and event["clock_span_s"] == 40.0 and event["ticks"] == 100 and event["steps"] == 800
  assert sum(event["seconds"].values()) == pytest.approx(event["clock_span_s"])  # the kinds partition the loop's wall time
  assert event["share"] == pytest.approx({"decode": 0.75, "mixed": 0.0, "spec": 0.0, "prefill": 0.2, "host": 0.05, "idle": 0.0})
  assert event["intervals"]["prefill"] == 100 and event["phase_ms_per_tick"]["readback"] == pytest.approx(500.0)
  assert event["before_capture"]["clock_span_s"] == 40.0  # no capture marked (an untraced rehearsal): the whole window is "before" it
  assert clock_lib.window_clock(window) == (first, last)
  assert capsys.readouterr().err == ""  # logged once a run


def test_the_readers_read_the_window_up_to_the_capture_and_the_wall_event_holds_both(window, capsys):
  """A capture leaves the host slower until the window closes: what comes after its opening is another regime."""
  window["timelines"]["d"] = _timeline(("decode", 1000.0, {"clock": _snap(302.0, prefill=1.2, host=0.2, idle=50.0, ticks=12)}), ("released", 60_000.0, {"clock": _snap(361.0, prefill=20.0, host=8.0, idle=50.0, ticks=250)}))
  window["recs"].append(NS(rid="d", sent=201.0))
  window["cap_start"] = 225.0  # d's first token (client 202), a's release (210.4) and b's first token (220.9) come before it; b's release (250) after
  first, last = clock_lib.window_clock(window)
  assert (first["t"], last["t"]) == (302.0, 320.0)
  assert _read("prefill_wall_share", window) == pytest.approx(2.8 / 18.0) and _read("host_gap_wall_share", window) == pytest.approx(1.3 / 18.0)
  assert _read("sched_host_ms_per_tick_window", window) == pytest.approx(8.0)
  (event,) = [json.loads(line) for line in capsys.readouterr().err.splitlines() if '"wall"' in line]
  assert event["clock_span_s"] == 48.0 and event["ticks"] == 108  # every snapshot of the window: both regimes
  assert event["before_capture"]["clock_span_s"] == 18.0 and event["before_capture"]["ticks"] == 28
  assert event["before_capture"]["share"]["prefill"] == pytest.approx(2.8 / 18.0)


@pytest.mark.parametrize("name", NEW[:3])
def test_timelines_that_begin_as_the_capture_opens_give_no_reading(name, window, capsys):
  """More than 256 requests finished in the window and the tracer dropped the early ones (Ling's 64-caller cell): what
  is left before the capture spans 10 of its 25 seconds, under half."""
  window["cap_start"] = 225.0
  assert _read(name, window) is None
  (event,) = [json.loads(line) for line in capsys.readouterr().err.splitlines() if '"wall"' in line]
  assert event["before_capture"] is None and event["clock_span_s"] == 40.0  # the builder still sees the window that is there


def test_the_shares_leave_idle_out_of_their_base_and_the_tick_reads_the_working_phases(window):
  assert _read("prefill_wall_share", window) == pytest.approx(8.0 / 40.0)
  assert _read("host_gap_wall_share", window) == pytest.approx(2.0 / 40.0)
  assert _read("sched_host_ms_per_tick_window", window) == pytest.approx(1.0 + 0.5 + 4.0 + 2.5)  # admit + plan + stage + settle; readback is a wait
  idle = _ctx({"a": _timeline(("decode", 1000.0, {"clock": _snap(300.0, ticks=1)}), ("released", 31_000.0, {"clock": _snap(330.0, prefill=5.0, idle=10.0, ticks=11)}))}, {"a": 210.0})
  assert _read("prefill_wall_share", idle) == pytest.approx(5.0 / 20.0)  # 30 s on the clock, 10 of them waiting for arrivals


def test_queue_wait_starts_at_the_admissions_queued_not_the_nodes(window):
  assert _read("queue_wait_p50_ms", window) == pytest.approx(10.0)  # 10, 4 and 30 ms; from the node's stage it would read 12, 5 and 30
  del window["timelines"]["c"], window["timelines"]["b"]["events"][2]  # a timeline the tracer dropped; a request never admitted
  assert _read("queue_wait_p50_ms", window) == pytest.approx(10.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_clock_reads_none_not_zero(name):
  parent = _ctx({"a": _timeline(("prefill_chunk", 3.0, {"tokens": 7}), ("decode", 500.0, {"first_token": 5}))}, {"a": 210.0})
  assert _read(name, parent) is None
  assert _read(name, _ctx({}, {"a": 210.0})) is None and _read(name, {**_ctx({}, {}), "timelines": None}) is None


@pytest.mark.parametrize("name", NEW[:3])
def test_fewer_than_two_snapshots_inside_the_window_read_none(name):
  one = _ctx({"a": _timeline(("decode", 1000.0, {"clock": _snap(300.0, ticks=1)}), ("released", 60_000.0, {"clock": _snap(359.0, ticks=9)}))}, {"a": 210.0})
  assert _read(name, one) is None
  same = _ctx({"a": _timeline(("decode", 1000.0, {"clock": _snap(300.0, ticks=1)}), ("released", 1000.0, {"clock": _snap(300.0, ticks=1)}))}, {"a": 210.0})
  assert _read(name, same) is None  # a row that finished at its first token: no time between its two


def test_benchmark_json_lists_each_new_metric_for_the_cells_that_report_what_it_moves():
  spec = common.load_spec()
  by_name = {m["name"]: m for m in spec["per_layer"]}
  moved = {e["name"]: set(e["workloads"]) for e in spec["end_to_end"] if "workloads" in e}
  for name in NEW:
    for suffix, moves in ((".open", "tpot_p50_ms"), (".closed", "out_tok_s")):
      m = by_name[name + suffix]
      # Ling's window finishes ~490 requests and the tracer keeps 256: no snapshot is left from before the capture, so the clock's three leave that cell out
      lost = {"ling-3.0-flash.decode-closed-64"} if suffix == ".closed" and name != "queue_wait_p50_ms" else set()
      assert m["moves"] == moves and set(m["workloads"]) == moved[moves] - lost and m["better"] == "lower"
      assert m["layer"] == ("admission" if name == "queue_wait_p50_ms" else "scheduler")
      assert callable(run.load_reader("per_layer", m["name"]).read)


def test_host_name_names_a_gap_under_the_idle_span():
  host = sorted([(0.0, 9.0, "python:$threading.py:1 run"), (1.0, 3.0, "python:xot.sched.idle"), (3.0, 3.4, "python:xot.sched.admit"), (3.4, 3.5, "python:xot.sched.stage")])
  assert trace_reduce._host_name(host, 1.2, 2.9) == "python:xot.sched.idle"  # both cover the whole gap: the shorter names it, not the thread-long one
  spans = [e for e in host if "threading" not in e[2]]  # what a capture holds with the python tracer off, as run.py takes it
  assert trace_reduce._host_name(spans, 0.9, 3.1) == "python:xot.sched.idle"  # one span over the whole wait covers most of a gap that begins before it and ends after
  assert trace_reduce._host_name(spans, 3.42, 3.5) == "python:xot.sched.stage"
  assert trace_reduce._host_name([e for e in spans if "idle" not in e[2]], 0.9, 3.1) == "unattributed"  # what the parent's capture says of the same wait
