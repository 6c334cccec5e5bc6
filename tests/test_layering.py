"""Tier-1 wiring for scripts/check_layering.py (ISSUE 10 satellite).

The scheduler split is admission/placement (inference/sched_admission.py)
vs device execution (inference/batch_scheduler.py); the split stays real
only while the admission layer never imports the execution layer (or the
networking transport). Likewise ``ops/`` and ``models/`` sit under
``inference/`` and import nothing of it but ``Shard`` (PR 32). Wired next to
tests/test_metrics_docs.py — same lexical-gate pattern, AST-based matcher."""

import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _checker():
  sys.path.insert(0, str(REPO / "scripts"))
  try:
    import check_layering
  finally:
    sys.path.pop(0)
  return check_layering


def test_admission_layer_never_imports_execution_layer():
  problems = _checker().check()
  assert not problems, "layering drifted:\n" + "\n".join(f"  - {p}" for p in problems)


def test_checker_catches_a_planted_reverse_import(tmp_path):
  """The gate actually bites: a copy of the admission module with a
  function-local, aliased, relative import of the execution module fails."""
  check_layering = _checker()
  src = (REPO / "xotorch_support_jetson_tpu" / "inference" / "sched_admission.py").read_text()
  planted = src + (
    "\n\ndef _smuggle():\n"
    "  from .batch_scheduler import BatchedServer as _B\n"
    "  return _B\n"
  )
  pkg = tmp_path / "xotorch_support_jetson_tpu" / "inference"
  pkg.mkdir(parents=True)
  (pkg / "sched_admission.py").write_text(planted)
  old_repo = check_layering.REPO
  try:
    check_layering.REPO = tmp_path
    problems = [p for p in check_layering.check() if "batch_scheduler" in p]
    assert problems, "planted reverse import was not detected"
  finally:
    check_layering.REPO = old_repo


def test_checker_catches_planted_reverse_import_in_router_policy(tmp_path):
  """ISSUE 13 satellite: the router-policy rule bites too — a copy of
  ``router_policy.py`` smuggling a function-local import of the
  device-execution scheduler fails the gate (its allowed imports of
  sched_admission/qos/kv_tier stay clean)."""
  check_layering = _checker()
  src = (REPO / "xotorch_support_jetson_tpu" / "inference" / "router_policy.py").read_text()
  planted = src + (
    "\n\ndef _smuggle():\n"
    "  from .batch_scheduler import BatchedServer as _B\n"
    "  return _B\n"
  )
  pkg = tmp_path / "xotorch_support_jetson_tpu" / "inference"
  pkg.mkdir(parents=True)
  (pkg / "sched_admission.py").write_text((REPO / "xotorch_support_jetson_tpu" / "inference" / "sched_admission.py").read_text())
  (pkg / "router_policy.py").write_text(planted)
  old_repo = check_layering.REPO
  try:
    check_layering.REPO = tmp_path
    problems = [p for p in check_layering.check() if "router_policy" in p and "batch_scheduler" in p]
    assert problems, "planted reverse import in router_policy was not detected"
  finally:
    check_layering.REPO = old_repo


def test_checker_catches_planted_reverse_import_in_adapters(tmp_path):
  """ISSUE 15 satellite: the adapter-registry rule bites — a copy of
  ``adapters.py`` smuggling a function-local import of the device-execution
  scheduler (or the networking transport) fails the gate, while its allowed
  paging/kv_tier imports stay clean."""
  check_layering = _checker()
  src = (REPO / "xotorch_support_jetson_tpu" / "inference" / "adapters.py").read_text()
  planted = src + (
    "\n\ndef _smuggle():\n"
    "  from .batch_scheduler import BatchedServer as _B\n"
    "  from ..networking import server as _S\n"
    "  return _B, _S\n"
  )
  pkg = tmp_path / "xotorch_support_jetson_tpu" / "inference"
  pkg.mkdir(parents=True)
  for name in ("sched_admission.py", "router_policy.py"):
    (pkg / name).write_text((REPO / "xotorch_support_jetson_tpu" / "inference" / name).read_text())
  (pkg / "adapters.py").write_text(planted)
  old_repo = check_layering.REPO
  try:
    check_layering.REPO = tmp_path
    problems = [p for p in check_layering.check() if "adapters" in p]
    assert any("batch_scheduler" in p for p in problems), "planted scheduler import was not detected"
    assert any("networking" in p for p in problems), "planted networking import was not detected"
  finally:
    check_layering.REPO = old_repo


@pytest.mark.parametrize("layer", ["ops", "models"])
def test_checker_catches_a_planted_import_of_the_serving_layer(tmp_path, monkeypatch, layer):
  """PR 32: nothing under ``ops/`` or ``models/`` imports ``inference/`` but
  ``inference.shard`` — a file that also reaches for the page accounting
  (function-local, aliased, relative) fails the gate, in a subdirectory
  too; its ``Shard`` import alone does not."""
  check_layering = _checker()
  assert any(rel.endswith(f"/{layer}") for rel, *_ in check_layering.RULES)
  pkg = tmp_path / "xotorch_support_jetson_tpu" / layer
  (pkg / "deeper").mkdir(parents=True)
  (pkg / "clean.py").write_text("from ..inference.shard import Shard\n")
  (pkg / "deeper" / "planted.py").write_text(
    "from ...inference.shard import Shard\n\n\ndef _smuggle():\n  from ...inference import paging as _p\n  return _p, Shard\n"
  )
  monkeypatch.setattr(check_layering, "REPO", tmp_path)
  problems = [p for p in check_layering.check() if p.startswith(f"xotorch_support_jetson_tpu/{layer}/")]
  assert problems and all("deeper/planted.py" in p and "inference" in p for p in problems), problems
  assert any("inference.paging" in p for p in problems), problems


def test_adapters_rule_is_active():
  check_layering = _checker()
  assert any("adapters" in rel for rel, *_ in check_layering.RULES)
  assert not [p for p in check_layering.check() if "adapters" in p]


def test_router_policy_rule_is_active():
  """The live module passes, and the rule set actually names it (deleting
  the rule would silently disable the gate)."""
  check_layering = _checker()
  assert any("router_policy" in rel for rel, *_ in check_layering.RULES)
  assert not [p for p in check_layering.check() if "router_policy" in p]


def test_checker_cli_exit_status():
  proc = subprocess.run(
    [sys.executable, str(REPO / "scripts" / "check_layering.py")],
    capture_output=True, text=True, timeout=60,
  )
  assert proc.returncode == 0, proc.stdout + proc.stderr
  assert "check_layering: OK" in proc.stdout
