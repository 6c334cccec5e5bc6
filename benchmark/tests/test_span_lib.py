"""``span_lib`` against two recorded traces from a TPU v5 lite: PR 23's one
``decode.paged_batch`` dispatch of a program without scopes or spans, and PR 24's
``tools/record_spans.py`` capture (a tiny MLA + MoE model served by the
scheduler: one prefill group and two decode chunks of 8 steps)."""

import gzip
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest  # noqa: E402
import span_lib  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
FAMILIES = {"_fused_paged_batch_decode_impl": "decode.paged_batch"}


def _unpacked(tmp_path_factory, name: str) -> str:
  raw = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
  raw.write_bytes(gzip.decompress((DATA / name).read_bytes()))
  return str(raw)


@pytest.fixture(scope="module")
def unscoped_path(tmp_path_factory):
  return _unpacked(tmp_path_factory, "decode_paged_batch_33pages.xplane.pb.gz")


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
  return span_lib.reduce(_unpacked(tmp_path_factory, "decode_scopes_spans.xplane.pb.gz"), FAMILIES)


def test_metadata_walk_finds_the_op_names(unscoped_path):
  planes, collisions = span_lib.event_op_names(Path(unscoped_path).read_bytes())
  names = planes["/device:TPU:0"]
  dots = {program for (program, _), v in names.items() if v.rstrip(":").endswith("dot_general")}
  assert dots == {9613196594613691648} and collisions == 0  # the program id in ``jit__fused_paged_batch_decode_impl(9613…)``
  assert all(v.startswith("jit(_fused_paged_batch_decode_impl)/while/body/") for (program, _), v in names.items() if program in dots and v.rstrip(":").endswith("dot_general"))
  # a copy the compiler added for the loop carry is named after the bare loop: the reader's "unscoped"
  assert any(trace_reduce.op_base(k) == "copy" and v.rstrip(":").endswith("/while") for (_, k), v in names.items())


def test_a_program_without_scopes_reads_as_absent_not_as_zero(unscoped_path):
  red = span_lib.reduce(unscoped_path, FAMILIES)
  assert not red["scoped"] and set(red["scope_s"]) == {"unscoped"}
  busy = trace_reduce.reduce(trace_reduce.load(unscoped_path), FAMILIES, window_s=0.2532)["busy_s"]
  assert red["scope_s"]["unscoped"] == pytest.approx(busy, rel=1e-4)  # self times lose nothing
  assert red["decode"] == {"device_s": pytest.approx(0.24688, rel=1e-3), "executions": 1}
  assert red["host"] == [] and span_lib.idle_named_share(red) is None and span_lib.phase_ms_per_tick(red) is None


def test_component_of():
  assert span_lib.component_of("jit(f)/while/body/xot.moe_experts/xot.dequant/mul:") == ("moe_experts", True)
  assert span_lib.component_of("jit(f)/while/body/xot.attn/xot.attn/jit(g)/pallas_call:") == ("attn", False)
  assert span_lib.component_of("jit(f)/while") == ("unscoped", False) and span_lib.component_of(None) == ("unscoped", False)


def test_scoped_capture_splits_the_decode_step(scoped):
  assert scoped["scoped"] and scoped["decode"] == {"device_s": pytest.approx(675.010e-6, rel=1e-4), "executions": 2}
  s = scoped["scope_s"]
  assert set(s) == {"unscoped", "embed", "attn_proj", "kv_write", "attn", "ffn", "moe_router", "moe_experts", "moe_shared", "head", "sample"}
  assert s["attn"] == pytest.approx(77.817e-6, rel=1e-3) and s["moe_experts"] == pytest.approx(21.990e-6, rel=1e-3) and s["unscoped"] == pytest.approx(139.002e-6, rel=1e-3)
  assert scoped["dequant_s"] == pytest.approx(13.199e-6, rel=1e-3)  # a part of its owners' time, not beside it
  # the components, the unscoped rest and the gaps between ops inside the programs are the programs' whole device time
  assert sum(s.values()) + scoped["in_program_gap_s"] == pytest.approx(scoped["decode"]["device_s"], rel=1e-6)


def test_scoped_capture_names_the_idle_gaps_and_the_ticks(scoped):
  names = {name for _, _, name, _ in scoped["host"]}
  assert names >= {f"xot.sched.{p}" for p in span_lib.PHASES} | {"xot.program:decode.paged_batch", "xot.program:prefill.pages_many_sampled"}
  assert len(scoped["gaps"]) == 8 and sum(b - a for a, b in scoped["gaps"]) == pytest.approx(12.1614e-3, rel=1e-3)
  assert span_lib.idle_named_share(scoped) == pytest.approx(0.8427, abs=1e-3)
  phases = span_lib.phase_ms_per_tick(scoped)  # three ticks, each whole: one prefill group, two decode chunks
  assert phases["ticks"] == 3 and phases["stage"] == pytest.approx(3.512, rel=1e-3) and phases["readback"] == pytest.approx(0.369, rel=1e-2)
  assert sum(phases[p] for p in span_lib.WORKING_PHASES) == pytest.approx(3.856, rel=1e-3)
  assert scoped["op_name_collisions"] == 0


def test_only_whole_ticks_count_and_admit_and_plan_go_to_the_next_stage():
  host = [
    (0.000, 0.001, "xot.sched.settle", 1),  # tick 1 began before the capture: only its settle is here
    (0.002, 0.0025, "xot.sched.plan", None),  # on the way to tick 2
    (0.003, 0.004, "xot.sched.stage", 2), (0.004, 0.006, "xot.sched.stage", 2), (0.006, 0.007, "xot.program:decode.paged_batch", 2),
    (0.010, 0.020, "xot.sched.readback", 2), (0.020, 0.021, "xot.sched.settle", 2),
    (0.0215, 0.022, "xot.sched.admit", None),  # on the way to tick 3
    (0.022, 0.023, "xot.sched.stage", 3), (0.023, 0.024, "xot.sched.stage", 3),  # tick 3's settle fell after the capture
    (0.030, 0.031, "xot.sched.stage", 4),  # tick 4: the capture ended between its two stage spans
  ]  # fmt: skip
  phases = span_lib.phase_ms_per_tick({"host": host})
  assert phases == {"plan": pytest.approx(0.5), "stage": pytest.approx(3.0), "readback": pytest.approx(10.0), "settle": pytest.approx(1.0), "ticks": 1}
  assert span_lib.phase_ms_per_tick({"host": host[:2]}) is None  # no whole tick: absent, not zero


def _msg(*fields) -> bytes:
  """A protobuf message from (field number, int | bytes) pairs: what ``span_lib._fields`` reads back."""

  def varint(n: int) -> bytes:
    out = b""
    while True:
      n, b = n >> 7, n & 0x7F
      out += bytes([b | (0x80 if n else 0)])
      if not n:
        return out

  return b"".join(varint(f << 3 | (0 if isinstance(v, int) else 2)) + (varint(v) if isinstance(v, int) else varint(len(v)) + v) for f, v in fields)


def test_ops_join_by_program_and_name_and_a_collision_is_counted():
  def metadata(mid: int, name: str, program: int, op_name: str) -> bytes:
    stats = [(5, _msg((1, 1), (5, op_name.encode()))), (5, _msg((1, 2), (3, program)))]  # tf_op as a string, program_id as a uint64
    return _msg((1, mid), (2, _msg((1, mid), (2, name.encode()), *stats)))

  stat_names = [(5, _msg((1, i), (2, _msg((1, i), (2, n.encode()))))) for i, n in ((1, "tf_op"), (2, "program_id"))]
  same_text = "%fusion.7 = bf16[4,256] fusion(...)"
  plane = _msg(
    (2, b"/device:TPU:0"), *stat_names,
    (4, metadata(1, same_text, 111, "jit(decode)/while/body/xot.attn/dot_general")),
    (4, metadata(2, same_text, 222, "jit(prefill)/while/body/xot.ffn/dot_general")),  # another program, the same instruction text
    (4, metadata(3, same_text, 222, "jit(prefill)/while/body/xot.head/dot_general")),  # the same program again: cannot be told apart
  )  # fmt: skip
  planes, collisions = span_lib.event_op_names(_msg((1, plane)))
  assert collisions == 1
  assert planes["/device:TPU:0"] == {(111, same_text): "jit(decode)/while/body/xot.attn/dot_general", (222, same_text): "jit(prefill)/while/body/xot.ffn/dot_general"}
