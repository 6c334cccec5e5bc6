#!/usr/bin/env python3
"""Layering drift gate (ISSUE 10 satellite).

The batched scheduler split (``inference/sched_admission.py`` = admission/
placement policy, ``inference/batch_scheduler.py`` = device execution) is
only real while the import DIRECTION holds: execution may import admission,
but the admission/placement layer must stay expressible against any executor
— a local slot pool today, a remote decode node tomorrow — which is exactly
what disaggregation exploits. This script makes a reverse import a tier-1
failure (tests/test_layering.py runs it), the same pattern as
``check_metrics_docs.py`` for the metric docs.

Scanning is AST-based (not lexical): every ``import``/``from-import`` in the
constrained module is resolved against the rule's forbidden module names, so
aliased, relative, and function-local imports are all caught; a string
mention in a comment or docstring is not.

Exit status: 0 clean, 1 with a report of every violation.
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PACKAGE = "xotorch_support_jetson_tpu"

# (constrained module or directory, forbidden module, why, *modules beneath
# the forbidden one that are allowed all the same) — paths relative to the
# repo root; a directory constrains every .py file under it; "module"
# matching covers both absolute and relative spellings.
RULES: list[tuple[str, ...]] = [
  (
    f"{PACKAGE}/inference/sched_admission.py",
    f"{PACKAGE}.inference.batch_scheduler",
    "admission/placement must never depend on the device-execution layer (ISSUE 10 split)",
  ),
  (
    f"{PACKAGE}/inference/sched_admission.py",
    f"{PACKAGE}.networking",
    "placement policy is transport-agnostic: the node layer owns the wire",
  ),
  # Cluster router (ISSUE 13): the routing policy ranks replicas through the
  # admission/placement layer's scoring — it may import sched_admission,
  # but never the device-execution scheduler (a router owns no model and
  # must stay expressible against replicas it only knows by advert) and
  # never the transport (api/router.py owns the HTTP mechanics).
  (
    f"{PACKAGE}/inference/router_policy.py",
    f"{PACKAGE}.inference.batch_scheduler",
    "router policy scores adverts via admission/placement, never the device-execution scheduler (ISSUE 13)",
  ),
  (
    f"{PACKAGE}/inference/router_policy.py",
    f"{PACKAGE}.networking",
    "routing policy is transport-agnostic: api/router.py owns the HTTP client",
  ),
  # Multi-LoRA registry (ISSUE 15): adapters.py may import paging/kv_tier
  # (block math, tiering idioms) but never the device-execution scheduler —
  # the registry must stay expressible against any executor (the
  # sched_admission discipline) — and never the transport (the node layer
  # propagates x-adapter metadata).
  (
    f"{PACKAGE}/inference/adapters.py",
    f"{PACKAGE}.inference.batch_scheduler",
    "the adapter registry is pool policy, never device-execution (ISSUE 15)",
  ),
  (
    f"{PACKAGE}/inference/adapters.py",
    f"{PACKAGE}.networking",
    "the adapter registry is transport-agnostic: the node layer owns the x-adapter wire",
  ),
  # The kernels and the model programs sit under the serving layer (PR 32):
  # which attention core a paged program runs is ops/paged.py's to say, and
  # inference/ asks it, never the reverse. ``Shard`` is the one type both
  # sides share (spell it ``from ..inference.shard import Shard``).
  (
    f"{PACKAGE}/ops",
    f"{PACKAGE}.inference",
    "kernels and device ops know nothing of the scheduler or its policy tables",
    f"{PACKAGE}.inference.shard",
  ),
  (
    f"{PACKAGE}/models",
    f"{PACKAGE}.inference",
    "model programs know nothing of the scheduler or its policy tables",
    f"{PACKAGE}.inference.shard",
  ),
]


def _imported_modules(path: Path) -> set[str]:
  """Absolute module names imported anywhere in ``path`` (top-level or
  function-local), with relative imports resolved against the file's own
  package position inside the repo."""
  tree = ast.parse(path.read_text(), filename=str(path))
  pkg_parts = path.relative_to(REPO).with_suffix("").parts[:-1]  # containing package
  out: set[str] = set()
  for node in ast.walk(tree):
    if isinstance(node, ast.Import):
      for alias in node.names:
        out.add(alias.name)
    elif isinstance(node, ast.ImportFrom):
      if node.level:  # relative: resolve against the file's package
        base = pkg_parts[: len(pkg_parts) - (node.level - 1)]
        mod = ".".join(base + tuple((node.module or "").split("."))).rstrip(".")
      else:
        mod = node.module or ""
      out.add(mod)
      for alias in node.names:  # `from pkg import mod` also names pkg.mod
        out.add(f"{mod}.{alias.name}" if mod else alias.name)
  return out


def _within(mod: str, root: str) -> bool:
  return mod == root or mod.startswith(root + ".")


def check() -> list[str]:
  """Returns a list of human-readable violations (empty = clean)."""
  problems: list[str] = []
  for rel, forbidden, why, *allowed in RULES:
    path = REPO / rel
    if not path.exists():
      problems.append(f"{rel}: constrained module missing (split reverted?)")
      continue
    for file in sorted(path.rglob("*.py")) if path.is_dir() else [path]:
      for mod in sorted(_imported_modules(file)):
        if _within(mod, forbidden) and not any(_within(mod, a) for a in allowed):
          problems.append(f"{file.relative_to(REPO)} imports {mod} — {why}")
  return problems


def main() -> int:
  problems = check()
  if problems:
    print("check_layering: FAIL")
    for p in problems:
      print(f"  - {p}")
    return 1
  print(f"check_layering: OK ({len(RULES)} rules hold)")
  return 0


if __name__ == "__main__":
  sys.exit(main())
