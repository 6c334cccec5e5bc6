"""Medians and spreads of result lines, as the contract defines a spread: the
distance between the first and third quartile (``statistics.quantiles(n=4)``)
as a share of the median.

  python benchmark/tools/spread.py chiprun_out/set1 chiprun_out/set2
"""

import glob
import json
import statistics
import sys
from collections import defaultdict


def read(directory: str) -> dict:
  cells = defaultdict(lambda: defaultdict(list))
  for path in sorted(glob.glob(f"{directory}/*.out")):
    lines = open(path).read().strip().splitlines()
    if not lines:
      continue
    try:
      res = json.loads(lines[-1])
    except ValueError:
      continue
    cell = path.split("/")[-1].rsplit(".", 3)[0]
    for name, m in res["metrics"].items():
      cells[cell][name].append(m["value"])
    cells[cell]["_correct"].append(res["correct"])
    cells[cell]["_failed"].append(res["failed"])
    cells[cell]["_attempted"].append(res["attempted"])
    cells[cell]["_memory_peak_gb"].append(res["device"]["memory_peak_bytes"] / 1e9)
    for name, value in client_view(path[: -len(".out")] + ".err").items():
      cells[cell][name].append(value)
  return cells


def client_view(err_path: str) -> dict:
  """The client-side statistics run.py logs with every window (stderr): TTFT,
  TPOT and whole-request latency, p50 / p90 / mean, as ``view.<what>.<stat>``."""
  try:
    lines = open(err_path).read().splitlines()
  except OSError:
    return {}
  for line in reversed(lines):
    if line.startswith('{"event": "window"'):
      ev = json.loads(line)
      out = {f"view.{k}.{stat}": v for k in ("ttft_ms", "tpot_ms", "latency_ms") for stat, v in (ev.get(k) or {}).items() if v is not None}
      for k in ("share_within_limits", "window_compiles", "late_p95_ms"):
        if ev.get(k) is not None:
          out[f"view.{k}"] = float(ev[k])
      return out
  return {}


def spread(values: list[float]) -> float:
  q1, _q2, q3 = statistics.quantiles(values, n=4)
  return (q3 - q1) / statistics.median(values)


def main() -> None:
  for directory in sys.argv[1:]:
    for cell, metrics in read(directory).items():
      print(f"{directory} {cell}: runs={len(metrics['_correct'])} correct={metrics['_correct']} failed={metrics['_failed']} attempted={metrics['_attempted']}")
      for name, values in metrics.items():
        if name.startswith("_") and name != "_memory_peak_gb":
          continue
        if len(values) >= 2:
          med = statistics.median(values)
          print(f"  {name}: median={med:.4f} spread={100 * spread(values) / 1:.2f}% values={[round(v, 2) for v in values]}" if med else f"  {name}: {values}")
        else:
          print(f"  {name}: {values}")


if __name__ == "__main__":
  main()
