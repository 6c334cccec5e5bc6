"""The benchmark's own CPU tests (``benchmark/tests``), each case a tier-1 case.

PR 30 was refused and PR 31 written because the yardstick misread a sound
program; its tests guard every number the driver reads, so they run with the
rest. Nothing is copied: every ``test_*`` function and fixture of
``benchmark/tests/test_*.py`` is taken into this module under its own name (a
file added there is collected here with no edit), and a name two files share
is an error, not a case lost. The files put ``benchmark/`` on ``sys.path``
themselves, as they do when run by hand (``python -m pytest benchmark/tests``).
"""

import importlib
import sys
from pathlib import Path

_TESTS = Path(__file__).resolve().parent.parent / "benchmark" / "tests"


def _is_fixture(value) -> bool:
  return type(value).__name__ == "FixtureFunctionDefinition" or hasattr(value, "_pytestfixturefunction")  # pytest >= 8.4, or older


sys.path.insert(0, str(_TESTS))
try:
  for _file in sorted(_TESTS.glob("test_*.py")):
    for _name, _value in vars(importlib.import_module(_file.stem)).items():
      if (_name.startswith("test_") and callable(_value)) or _is_fixture(_value):
        if _name in globals():
          raise ImportError(f"benchmark/tests: {_name} is defined twice; {_file.name} holds the second")
        globals()[_name] = _value
finally:
  sys.path.remove(str(_TESTS))
