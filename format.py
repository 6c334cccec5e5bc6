"""Dev formatting entry point (role of the reference's ``format.py``).

Runs yapf in-place (config: .style.yapf) over a file, a directory, or the
default source roots. Usage::

    python format.py            # whole repo source + tests
    python format.py <path>     # one file or subtree
"""

from __future__ import annotations

import shutil
import subprocess
import sys
from pathlib import Path

ROOTS = ("xotorch_support_jetson_tpu", "tests", "format.py")


def python_files(target: Path) -> list[str]:
  if target.is_file():
    return [str(target)] if target.suffix == ".py" else []
  return [str(p) for p in sorted(target.rglob("*.py"))]


def main() -> int:
  if shutil.which("yapf") is None:
    print("yapf is not installed (pip install yapf); nothing formatted", file=sys.stderr)
    return 1
  targets = [Path(sys.argv[1])] if len(sys.argv) > 1 else [Path(r) for r in ROOTS]
  files: list[str] = []
  for t in targets:
    if not t.exists():
      print(f"skipping missing {t}", file=sys.stderr)
      continue
    files.extend(python_files(t))
  if not files:
    print("no python files found", file=sys.stderr)
    return 1
  return subprocess.call(["yapf", "-i", "--style", ".style.yapf", *files])


if __name__ == "__main__":
  sys.exit(main())
