"""What the compile tests ask of another process: where the persistent compile cache is placed and what its key holds
(``utils/helpers.py configure_compile_cache``), and ``chip_smoke.py`` without a chip and in its CPU rehearsal. Every
case starts a Python of its own with a cache directory of its own.
"""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

# ---------------------------------------------- compile cache placement

_PLACE = "from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache as c; import jax; print(c()); print(jax.config.jax_compilation_cache_dir)"


def _placed(env: dict, cwd) -> list[str]:
  env = {**os.environ, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu", **env}
  env = {k: v for k, v in env.items() if v is not None}
  return subprocess.run([sys.executable, "-c", _PLACE], capture_output=True, text=True, timeout=120, cwd=cwd, env=env, check=True).stdout.split()


def test_compile_cache_dir_from_outside_is_left_to_jax(tmp_path):
  """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it, the code sets nothing
  (the config still holds exactly the variable's value)."""
  assert _placed({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}, tmp_path) == [str(tmp_path / "cc")] * 2


def test_compile_cache_default_dir_is_fixed_inside_the_checkout(tmp_path):
  """Unset: one fixed directory at the root of the checkout, the same from
  two processes started in different places — the path is part of the
  cache's key, so a directory that moves never hits."""
  first, second = _placed({"JAX_COMPILATION_CACHE_DIR": None}, ROOT), _placed({"JAX_COMPILATION_CACHE_DIR": None}, tmp_path)
  assert first == second == [str(ROOT / ".xot_compile_cache")] * 2


# One traced function under one component scope, compiled through the placed
# cache: argv = blank lines before the function's source, the scope's name.
_KEYED = """
import os, sys, jax, jax.numpy as jnp
from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache
configure_compile_cache()
src = "\\n" * int(sys.argv[1]) + "def f(x):\\n  with jax.named_scope('" + sys.argv[2] + "'):\\n    return jnp.dot(x, x) + 1\\n"
ns = {"jax": jax, "jnp": jnp}
exec(compile(src, "model_code.py", "exec"), ns)
jax.jit(ns["f"])(jnp.ones((8, 8))).block_until_ready()
print(sorted(n for n in os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"]) if n.startswith("jit_f-")))
"""


def test_compile_cache_key_holds_the_scope_names_and_no_source_lines(tmp_path):
  """A cached executable carries the ``xot.*`` scope names of the code that
  compiled it and the profiler reads them back, so an entry is never served
  to code that names its ops otherwise; a line that only moves (every later
  edit of a traced file) must find the entry again."""
  env = {**os.environ, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}

  def entries(*argv: str) -> list[str]:
    out = subprocess.run([sys.executable, "-c", _KEYED, *argv], capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env, check=True).stdout
    return eval(out.strip().splitlines()[-1])  # noqa: S307 — our own child's printed list

  first = entries("0", "xot.attn")
  assert len(first) == 1
  assert entries("7", "xot.attn") == first  # the same code seven lines further down: a hit
  assert len(entries("0", "xot.ffn")) == 2  # another scope name: its own entry


# ------------------------------------------------------- chip_smoke.py


def _smoke(*args, env=None, timeout=600):
  return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args], capture_output=True, text=True, timeout=timeout, cwd=ROOT, env=env)


def test_chip_smoke_without_a_tpu_fails(tmp_path):
  """No accelerator and no rehearsal option: a non-zero exit and
  ``"ok": false`` — never a CPU result under the chip's name."""
  import json

  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
  out = _smoke(env=env)
  last = json.loads(out.stdout.strip().splitlines()[-1])
  assert out.returncode != 0 and last["ok"] is False, out.stdout[-2000:] + out.stderr[-2000:]
  assert '"platform": "tpu"' not in out.stdout


def test_chip_smoke_cpu_rehearsal(tmp_path):
  """The whole control flow of ``chip_smoke.py`` — checkpoint from a seed,
  kernels against references (interpret mode), a solo daemon and a batched
  daemon each answering blocking, streamed and concurrent requests, the
  program ledger read back — at tiny width behind its explicit option."""
  import json

  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
  out = _smoke("--cpu-rehearsal", env=env)
  assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
  lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
  assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
  phases = {line["phase"]: line for line in lines if "phase" in line}
  assert {"kernels", "solo", "batched"} <= set(phases) and all(p["ok"] for p in phases.values())
  assert phases["solo"]["blocking_equals_streaming"] and phases["batched"]["blocking_equals_streaming"]
  assert '"platform": "tpu"' not in out.stdout
