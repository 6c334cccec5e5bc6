"""The readers of what PR 55 added to the program, on synthetic contexts: ``half_lib.halve`` files a decode family's device
ops under (half, component) by the one path component ``mixed.prefill`` and the halves sum to the families' time; a thousand
slice tokens' time survives a capture's edges; every reader says None (never 0) for a program without the mark or whose
snapshots carry no ``counts``; the recorded capture of a program from before the mark reads as ``span_lib`` reads it; and
``BENCHMARK.json`` lists each new metric for cells that report what it moves — by presence, not by its place in a list."""

import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace as NS

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import arch  # noqa: E402
import common  # noqa: E402
import half_lib  # noqa: E402
import pytest  # noqa: E402
import run  # noqa: E402
import span_lib  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PLAIN, MIXED = "decode.paged_batch", "decode.mixed_paged_batch"
MIXED3 = {"mistral-7b.decode-closed", "laguna-xs.2.agent-closed-64", "smallthinker-21ba3b.longdoc-closed-32"}
HALF_READERS = ("mixed_prefill_device_ms_per_ktok", "mixed_prefill_device_share", "decode_half_step_device_ms", "moe_experts_decode_roofline")
COUNT_READERS = ("dispatch_behind_share", "mixed_slice_fill_share", "kv_pages_read_share", "moe_experts_visited_per_layer_step")
# name -> (layer, moves, source, better, cells it must list)
ENTRIES = {
  "mixed_prefill_device_ms_per_ktok.closed": ("fused programs", "out_tok_s", "device_trace", "lower", MIXED3),
  "mixed_prefill_device_share.closed": ("fused programs", "out_tok_s", "device_trace", "lower", MIXED3),
  "decode_half_step_device_ms.closed": ("fused programs", "out_tok_s", "device_trace", "lower", MIXED3),
  "moe_experts_decode_roofline": ("kernels", "out_tok_s", "device_trace", "higher", MIXED3 - {"mistral-7b.decode-closed"}),
  "dispatch_behind_share.open": ("scheduler", "tpot_p50_ms", "program_counter", "higher", {"mistral-7b.chat-poisson"}),
  "dispatch_behind_share.closed": ("scheduler", "out_tok_s", "program_counter", "higher", MIXED3 | {"moonlight-a3b.decode-closed"}),
  "mixed_slice_fill_share.closed": ("scheduler", "out_tok_s", "program_counter", "higher", MIXED3),
  "mixed_wall_share.open": ("scheduler", "tpot_p50_ms", "program_counter", "lower", {"mistral-7b.chat-poisson"}),
  "mixed_wall_share.closed": ("scheduler", "out_tok_s", "program_counter", "lower", MIXED3),
  "kv_pages_read_share.closed": ("scheduler", "out_tok_s", "program_counter", "lower", MIXED3 - {"mistral-7b.decode-closed"}),
  "moe_experts_visited_per_layer_step.closed": ("kernels", "out_tok_s", "program_counter", "lower", MIXED3 - {"mistral-7b.decode-closed"}),
}


# ------------------------------------------------------------------ the halves


def _capture(marked: bool = True):
  """One chip: a plain chunk [0, 1) and a mixed tick [2, 5) of 8 steps each. The mixed tick's prefill half is a layer loop
  (a ``while`` of 1.5 s that holds 0.9 s of experts and 0.4 s of attention) and a page scatter of 0.25 s; its decode half a
  scan that holds 0.8 s of experts and 0.3 s of attention. 0.05 s of the tick no op covers."""
  pre = "jit(f)/mixed.prefill" if marked else "jit(f)"
  ops = {
    "%while.1": (0.0, 1.0, 1, "jit(g)/while"), "%experts.1": (0.1, 0.7, 1, "jit(g)/while/body/xot.moe_experts/dot_general"), "%attn.1": (0.7, 0.95, 1, "jit(g)/while/body/xot.attn/pallas_call"),
    "%while.2": (2.0, 3.5, 2, f"{pre}/while"), "%experts.2": (2.1, 3.0, 2, f"{pre}/while/body/closed_call/xot.moe_experts/xot.dequant/mul"), "%attn.2": (3.0, 3.4, 2, f"{pre}/while/body/closed_call/xot.attn/dot_general"),
    "%scatter.2": (3.5, 3.75, 2, f"{pre}/xot.kv_write/scatter"),
    "%while.3": (3.8, 5.0, 2, "jit(f)/while"), "%experts.3": (3.85, 4.65, 2, "jit(f)/while/body/xot.moe_experts/dot_general"), "%attn.3": (4.65, 4.95, 2, "jit(f)/while/body/xot.attn/pallas_call"),
    "%prefill.9": (6.0, 7.0, 9, "jit(h)/mixed.prefill/xot.attn/dot_general"),  # another family's program: none of the decode families' time
  }  # fmt: skip
  modules = [(0.0, 1.0, PLAIN, 1), (2.0, 5.0, MIXED, 2)]
  events = sorted((s, e, name) for name, (s, e, _, _) in ops.items())
  names = {(program, name): op_name for name, (_, _, program, op_name) in ops.items()}
  return [(modules, events, names)]


def _stages(*slices):
  return [{"tick": 7, "rows": 3}, *({"tick": 8 + i, "rows": 3, "pf_tokens": str(t), "pf_pad": str(p)} for i, (t, p) in enumerate(slices)), {"tick": 99, "rows": 1}]


def test_halve_files_the_ops_by_half_and_component_and_the_halves_sum_to_the_families_time():
  red = half_lib.halve(_capture(), _stages((300, 512)))
  assert red["marked"] and red["executions"] == {PLAIN: 1, MIXED: 1} and red["device_s"] == pytest.approx(4.0)
  assert red["half_s"]["prefill"] == pytest.approx({"moe_experts": 0.9, "attn": 0.4, "unscoped": 0.2, "kv_write": 0.25})  # the loop's own 0.2 s is self time, and the prefill half's
  assert red["half_s"]["decode"] == pytest.approx({"moe_experts": 0.6 + 0.8, "attn": 0.25 + 0.3, "unscoped": 0.15 + 0.1})  # the plain chunk is decode, whole
  halves = sum(half_lib.half_seconds(red, h) for h in half_lib.HALVES)
  assert halves == pytest.approx(red["device_s"] - 0.05)  # all but the space between ops inside the mixed tick
  assert (red["slices"], red["pf_tokens"], red["pf_pad"]) == (1, 300, 512)
  assert half_lib.half_seconds(red, "decode", ("moe_experts",)) == pytest.approx(1.4) and half_lib.decode_steps(red, 8) == 16


def test_two_chips_read_as_their_mean():
  one, two = half_lib.halve(_capture(), _stages((300, 512))), half_lib.halve(_capture() * 2, _stages((300, 512)))
  assert two["half_s"] == one["half_s"] and two["executions"] == one["executions"] and two["device_s"] == pytest.approx(one["device_s"])


def test_a_thousand_slice_tokens_time_is_the_mean_slices_over_the_mean_slices_tokens():
  """The capture's edges: three ``stage`` spans (300, 500 and 400 tokens) and one execution — the mean slice is 400 tokens,
  so the one marked half of 1.75 s stands for 0.4 thousand tokens, not for 1.2."""
  red = half_lib.halve(_capture(), _stages((300, 512), (500, 512), (400, 512)))
  assert half_lib.slice_ktok(red) == pytest.approx(0.4)
  assert half_lib.slice_ktok(half_lib.halve(_capture(), _stages())) is None  # no span said what the slices carried


@pytest.fixture
def traced(monkeypatch):
  """``ctx`` of a traced run whose newest capture reduces to what ``set_red`` is given."""
  monkeypatch.setattr(half_lib.trace_reduce, "find_xplane", lambda _dir: "synthetic.xplane.pb")
  monkeypatch.setattr(half_lib, "_MEMO", {})
  hf = common.load_config("smallthinker-21ba3b-d8")
  recs = [NS(rid=f"r{i}", sent=100.0, first=101.0, events=[(101.0, 1), (150.0, 10)], max_tokens=64, prompt_tokens=500) for i in range(28)]  # 28 rows resident at the capture's middle
  ctx = {"trace": {"programs": {}}, "chunk": 8, "hf": hf, "peaks": {"hbm_bytes_per_s": 819e9}, "recs": recs, "cap_start": 120.0, "cap_end": 126.0}
  monkeypatch.setattr(half_lib, "reduce", lambda path, families: ctx["_red"])
  return ctx


def _read(name: str, ctx: dict):
  return run.load_reader("per_layer", f"{name}.closed").read(ctx)


def test_the_half_readers_divide_each_half_by_its_own_base_and_log_the_halves_once(traced, capsys):
  traced["_red"] = half_lib.halve(_capture(), _stages((300, 512)))
  assert _read("mixed_prefill_device_ms_per_ktok", traced) == pytest.approx(1.75e3 / 0.3)
  assert _read("mixed_prefill_device_share", traced) == pytest.approx(1.75 / 4.0)
  assert _read("decode_half_step_device_ms", traced) == pytest.approx(2.2e3 / 16)
  least_s = arch.load(traced["hf"]["arch_kind"]).moe_expert_bytes(traced["hf"], 28.0) / 819e9
  assert _read("moe_experts_decode_roofline", traced) == pytest.approx(100.0 * least_s / (1.4 / 16))
  (event,) = [json.loads(line) for line in capsys.readouterr().err.splitlines() if '"halves"' in line]  # four readers, one walk
  assert event["marked"] and event["executions"] == {PLAIN: 1, MIXED: 1} and (event["slices"], event["pf_tokens"], event["pf_pad"]) == (1, 300, 512)
  assert event["prefill_half_s"] + event["decode_half_s"] == pytest.approx(3.95) and event["halves_over_device_s"] == pytest.approx(3.95 / 4.0)
  assert event["decode_half_step_ms"]["moe_experts"] == pytest.approx(1.4e3 / 16) and event["prefill_half_ms_per_ktok"]["kv_write"] == pytest.approx(0.25e3 / 0.3)
  assert event["walk_s"] >= 0


@pytest.mark.parametrize("name", HALF_READERS)
def test_a_program_without_the_mark_reads_none_not_zero(name, traced, capsys):
  traced["_red"] = half_lib.halve(_capture(marked=False), _stages())
  assert not traced["_red"]["marked"] and half_lib.half_seconds(traced["_red"], "decode") == pytest.approx(3.95)  # everything is "decode": a zero would read as a half that costs nothing
  assert _read(name, traced) is None
  (event,) = [json.loads(line) for line in capsys.readouterr().err.splitlines() if '"halves"' in line]  # the builder still sees what the capture held
  assert not event["marked"] and event["prefill_half_ms_per_ktok"] is None
  assert _read(name, {**traced, "trace": None}) is None  # an untraced run


def test_a_marked_capture_whose_spans_carry_no_slice_gives_a_share_and_a_step_and_no_time_per_token(traced):
  traced["_red"] = half_lib.halve(_capture(), _stages())  # the parent's scheduler under the change's program: no ``pf_tokens``
  assert _read("mixed_prefill_device_ms_per_ktok", traced) is None
  assert _read("mixed_prefill_device_share", traced) == pytest.approx(1.75 / 4.0) and _read("decode_half_step_device_ms", traced) == pytest.approx(2.2e3 / 16)


def test_the_experts_reader_reads_none_for_a_kind_without_expert_bytes_or_a_capture_without_the_scope(traced):
  traced["_red"] = half_lib.halve(_capture(), _stages((300, 512)))
  assert _read("moe_experts_decode_roofline", {**traced, "hf": common.load_config("mistral-7b-int8")}) is None
  traced["_red"]["half_s"]["decode"].pop("moe_experts")
  assert _read("moe_experts_decode_roofline", traced) is None


def test_the_recorded_capture_from_before_the_mark_reads_as_span_lib_reads_it(tmp_path):
  """PR 24's capture from a TPU v5 lite (two decode chunks of a program with scopes and no mark): every op is the decode
  half's, by the components and to the seconds ``span_lib.reduce`` gives, and no ``stage`` span carries a slice."""
  raw = tmp_path / "t.xplane.pb"
  raw.write_bytes(gzip.decompress((DATA / "decode_scopes_spans.xplane.pb.gz").read_bytes()))
  families = {"_fused_paged_batch_decode_impl": PLAIN}
  red, scoped = half_lib.reduce(str(raw), families), span_lib.reduce(str(raw), families)
  assert not red["marked"] and red["half_s"]["prefill"] == {} and red["slices"] == 0
  assert red["half_s"]["decode"] == pytest.approx(scoped["scope_s"]) and red["executions"] == {PLAIN: 2}
  assert red["device_s"] == pytest.approx(scoped["decode"]["device_s"])
  assert half_lib.half_seconds(red, "decode") + scoped["in_program_gap_s"] == pytest.approx(red["device_s"], rel=1e-6)


# ------------------------------------------------------------------ the counts

KINDS = ("decode", "mixed", "spec", "prefill", "host", "idle")


def _snap(t: float, counts: dict | None, mixed: float = 0.0) -> dict:
  seconds = {**dict.fromkeys(KINDS, 0.0), "mixed": mixed, "decode": t - 100.0 - mixed}
  snap = {"t": t, "ticks": 0, "steps": 0, "seconds": seconds, "intervals": dict.fromkeys(KINDS, 0), "phases": {}}
  return snap if counts is None else {**snap, "counts": counts}


def _window(first: dict | None, last: dict | None, **kw) -> dict:
  """A window [200, 251) with one request whose two snapshots lie 40 s apart inside it."""
  events = [{"stage": "decode", "at_ms": 1000.0, "attributes": {"clock": _snap(310.0, first, **{k: v[0] for k, v in kw.items()})}}, {"stage": "released", "at_ms": 41_000.0, "attributes": {"clock": _snap(350.0, last, **{k: v[1] for k, v in kw.items()})}}]
  return {"recs": [NS(rid="a", sent=205.0)], "timelines": {"a": {"events": events}}, "t_open": 200.0, "t_close": 251.0}


FIRST = {"dispatch_behind": 90, "dispatch_empty": 10, "slice_tokens": 3000, "slice_pad_tokens": 4096, "kv_pages_read": 500, "kv_pages_resident": 800, "experts_visited": 1000, "expert_layer_steps": 64}
LAST = {"dispatch_behind": 288, "dispatch_empty": 12, "slice_tokens": 9000, "slice_pad_tokens": 12288, "kv_pages_read": 1100, "kv_pages_resident": 2000, "experts_visited": 3240, "expert_layer_steps": 192}
WANT = {"dispatch_behind_share": 198 / 200, "mixed_slice_fill_share": 6000 / 8192, "kv_pages_read_share": 600 / 1200, "moe_experts_visited_per_layer_step": 2240 / 128}


@pytest.mark.parametrize("name", COUNT_READERS)
def test_a_count_reader_divides_what_moved_between_the_two_snapshots(name):
  assert _read(name, _window(FIRST, LAST)) == pytest.approx(WANT[name])
  assert _read(name, _window({}, LAST)) == pytest.approx({"dispatch_behind_share": 288 / 300, "mixed_slice_fill_share": 9000 / 12288, "kv_pages_read_share": 1100 / 2000, "moe_experts_visited_per_layer_step": 3240 / 192}[name])  # a count appears with its first increment: one the first snapshot lacks started at 0


@pytest.mark.parametrize("name", COUNT_READERS)
def test_snapshots_without_counts_or_a_count_that_did_not_move_read_none_not_zero(name):
  assert _read(name, _window(None, None)) is None  # the parent's clock: seconds and phases, no counts
  assert _read(name, _window(None, LAST)) is None  # a server restarted onto the change mid-window is no reading either
  assert _read(name, _window(LAST, LAST)) is None  # nothing under the line moved: no mixed tick, no expert layer, no dispatch
  assert _read(name, {**_window(FIRST, LAST), "timelines": {}}) is None and _read(name, {**_window(FIRST, LAST), "cap_start": 225.0}) is None  # no pair before the capture


def test_mixed_wall_share_is_the_clocks_mixed_kind_over_its_busy_time():
  assert _read("mixed_wall_share", _window(None, None, mixed=(5.0, 25.0))) == pytest.approx(20.0 / 40.0)  # booked since PR 41: the parent reads it too
  assert _read("mixed_wall_share", {**_window(None, None), "timelines": {}}) is None
  assert run.load_reader("per_layer", "mixed_wall_share.open").read(_window(None, None, mixed=(0.0, 4.0))) == pytest.approx(0.1)


# ------------------------------------------------------------------ BENCHMARK.json


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_json_lists_the_metric_for_cells_that_report_what_it_moves(name):
  spec = common.load_spec()
  (m,) = [m for m in spec["per_layer"] if m["name"] == name]  # by presence: where in the list it stands is nobody's business
  layer, moves, source, better, must = ENTRIES[name]
  assert (m["layer"], m["moves"], m["source"], m["better"]) == (layer, moves, source, better)
  (moved,) = [set(e["workloads"]) for e in spec["end_to_end"] if e["name"] == moves]
  assert must <= set(m["workloads"]) <= moved and len(set(m["workloads"])) == len(m["workloads"])
  assert layer in {x["layer"] for x in spec["per_layer"] if x["name"] not in ENTRIES}  # a layer the benchmark already names
  assert callable(run.load_reader("per_layer", name).read)
