"""The trace reduction against a small recorded trace: one ``decode.paged_batch``
dispatch (8 steps, 16 rows, Mistral-7B int8, a 33-page pool) recorded on a TPU
v5 lite by ``tools/pool_probe.py`` (PR 23)."""

import gzip
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest  # noqa: E402
import trace_reduce  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "decode_paged_batch_33pages.xplane.pb.gz"
FAMILIES = {"_fused_paged_batch_decode_impl": "decode.paged_batch"}


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
  raw = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
  raw.write_bytes(gzip.decompress(DATA.read_bytes()))
  return trace_reduce.reduce(trace_reduce.load(str(raw)), FAMILIES, window_s=0.2532, kernels=("paged_decode", "flash"))


def test_busy_time_and_program(reduced):
  assert reduced["chips"] == 1
  assert reduced["busy_s"] == pytest.approx(0.24688, rel=1e-3)
  prog = reduced["programs"]["decode.paged_batch"]
  assert prog["executions"] == 1 and prog["device_s"] == pytest.approx(0.24688, rel=1e-3)
  assert reduced["busy_s"] <= reduced["window_s"]


def test_kernel_calls_and_self_times(reduced):
  k = reduced["kernels"]["paged_decode"]
  assert k["calls"] == 32 * 8  # one call a layer a step
  assert k["device_s"] == pytest.approx(0.14066, rel=1e-3)
  names = [n for n, _ in reduced["device_ops"]]
  assert names[0] == "_paged_decode_attention_impl" and "while" not in names[:3]  # the loop's own time excludes its body
  assert sum(t for _, t in reduced["device_ops"]) <= reduced["busy_s"] * 1.001


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
