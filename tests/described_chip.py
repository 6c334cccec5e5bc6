"""A described v5e for the compile tests (``tests/test_tpu_compile*.py``): the fixture, the shapes and the readers of
an optimised HLO text they share. Not collected; a module takes the fixtures in by name
(``from described_chip import chip, _fp32_matmuls``), so each has a chip and a precision of its own.

libtpu is installed here and compiles for a chip that is described, not attached
(``jax.experimental.topologies``): what Mosaic or XLA:TPU refuses for a v5e it refuses here too, at no chip time.
Code that asks ``jax.default_backend()`` sees the CPU here, so the tests hand the jitted kernels and steps their
shapes (and ``use_kernel``) directly.
"""

import os
import pathlib
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
# libtpu lets one process at a time hold a chip and guards that with a lock
# file. No chip is attached here, and under pytest-xdist several workers
# describe the topology at once: without this all but one would skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import pytest  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

PS = 64  # the served page size


@pytest.fixture(autouse=True)
def _fp32_matmuls():
  """Overrides conftest's precision pin: the serving process sets no matmul
  precision, and these tests compile what it compiles ("highest" turns the
  flash-decode kernel's bf16 dots into fp32-precision ones Mosaic refuses)."""
  yield


@pytest.fixture(scope="module")
def chip(no_persistent_compile_cache):
  """A described v5e chip as a sharding for ShapeDtypeStructs, with the
  persistent compile cache off: an entry written for an absent chip cannot
  be read back and only warns."""
  from jax.experimental import topologies
  from jax.sharding import SingleDeviceSharding

  try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 — no libtpu in this installation: nothing to ask
    pytest.skip(f"cannot describe a v5e topology here: {e!r}")
  return SingleDeviceSharding(topo.devices[0])


def _sds(chip, shape, dtype):
  return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _rows(chip, n: int):
  """Per-row operand of a batched program: dtype → [n] shape."""
  return lambda dtype: _sds(chip, (n,), dtype)


def _compile(tracked, *args, **kwargs):
  """Lower + compile a ``tracked_jit`` program for the described chip."""
  compiled = tracked.xot_jitted.lower(*args, **kwargs).compile()
  return compiled, compiled.as_text()


def _mosaic_calls(text: str) -> list[str]:
  """The kernel names of a compiled program's Mosaic calls, one per call."""
  return [m.group(1) for line in text.splitlines() if "tpu_custom_call" in line for m in [re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)] if m]



_HLO_LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([a-z][\w\-]*)\((.*)$", re.M)  # name = result type opcode(operands...


def _takers(text: str, shape: str) -> list[tuple[str, str]]:
  """(name, opcode) of every fusion and custom call of an optimised HLO text that takes a value of ``shape`` (a
  regex) as an operand. Operands are printed by name, so the names' shapes are read first."""
  lines = _HLO_LINE.findall(text)
  shapes = {name: result for name, result, _, _ in lines}
  out = []
  for name, _, op, rest in lines:
    if op in ("fusion", "custom-call") and any(re.fullmatch(shape + r"\S*", shapes.get(operand, "")) for operand in re.findall(r"%[\w.\-]+", rest.split("), ")[0])):
      out.append((name, op))
  return out


def _materialised(text: str) -> list[tuple[str, str, str, list[str], str]]:
  """(name, result type, opcode, operand names, op_name) of every instruction of an optimised HLO text that stands in
  the entry computation, a loop's body or a called one — not inside a fusion's or a reducer's own computation, whose
  instructions produce no buffer."""
  inner = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", text)) | set(re.findall(r"to_apply=(%[\w.\-]+)", text))
  out, computation = [], None
  for line in text.splitlines():
    head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
    if head:
      computation = head.group(1)
    m = None if computation in inner else _HLO_LINE.match(line)
    if m:
      name, result, op, rest = m.groups()
      scope = re.search(r'op_name="([^"]*)"', rest)
      out.append((name, result, op, re.findall(r"%[\w.\-]+", rest.split("), ")[0]), scope.group(1) if scope else ""))
  return out


def _ling_at_the_cells_settings(chip, monkeypatch, config: str = "ling-3.0-flash-ep4-d7", **cut):
  """(hf, cfg, params, pool) of ``ling-3.0-flash.decode-closed-64`` (or of another expert configuration of the
  benchmark, ``cut`` replacing keys of its file) as shapes on the described chip, its programs told what they see on
  the chip: a TPU (``ops/moe.py ffn_form`` asks the backend, which is the CPU here)."""
  import json
  from dataclasses import replace

  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  monkeypatch.setattr(moe, "_on_tpu", lambda: True)

  hf = {**json.loads((ROOT / "benchmark" / "configs" / f"{config}.json").read_text()), **cut}
  n_slots, n_pages = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), int(hf["serving_env"].get("XOT_TPU_BATCH_PAGES", 257))
  cfg = replace(config_from_hf({k: v for k, v in hf.items() if not isinstance(v, dict)}), max_seq_len=int(hf["serving_window_tokens"]))
  on_chip = lambda tree: jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)  # noqa: E731
  params = on_chip(jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS, n_slots=n_slots)))
  return hf, cfg, params, pool
