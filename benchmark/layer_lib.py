"""What the readers in ``layer_metrics/`` and ``end_to_end/`` share. A reader is ``read(ctx) -> number | None``;
None means "nothing to read" and the harness leaves the metric out of the line. A per-layer reader that
needs a kernel's device time names the kernel's op-name substrings in a module-level ``KERNELS`` tuple.

``ctx`` holds: ``recs`` (client records of the measured requests), ``timelines``
({request id: the program's stage timeline}), ``before``/``after`` (the
program's counters at window open and close), ``trace`` (trace_reduce.reduce's
output for the traced interval, or None), ``cap_start``/``cap_end`` (the traced
interval on the client's clock), ``hf``, ``traffic``, ``peaks``, ``mode``,
``chunk``, ``t_start``/``t_open``/``t_close``, ``late_p95_ms``, ``window_compiles``. ``timelines`` and ``trace`` exist
in a ``--trace 1`` run only.
"""

from __future__ import annotations

import numpy as np

import arch

DECODE_FAMILIES = ("decode.paged_batch", "decode.mixed_paged_batch")


def pct(values, q):
  return float(np.percentile(np.asarray(values, dtype=float), q)) if len(values) else None


def stage_at_ms(tl: dict, stage: str):
  """at_ms of the first event of ``stage`` in a timeline, or None."""
  for ev in tl.get("events", ()):
    if ev["stage"] == stage:
      return ev["at_ms"]
  return None


def counter_delta(ctx: dict, name: str) -> float:
  """Sum over label sets of a counter's growth inside the window."""
  grow = 0.0
  for key, val in ctx["after"].items():
    if isinstance(val, float) and key.split("{")[0] == f"xot_tpu_{name}":
      grow += val - ctx["before"].get(key, 0.0)
  return grow


def decode_programs(ctx: dict) -> tuple[float, int]:
  """(device seconds, executions) of the decode families in the traced interval."""
  progs = (ctx.get("trace") or {}).get("programs", {})
  return sum(progs.get(f, {}).get("device_s", 0.0) for f in DECODE_FAMILIES), sum(progs.get(f, {}).get("executions", 0) for f in DECODE_FAMILIES)


def decode_step_device_ms(ctx: dict):
  device_s, runs = decode_programs(ctx)
  return device_s / (runs * ctx["chunk"]) * 1e3 if runs else None


def resident(ctx: dict) -> tuple[float, float]:
  """(rows, cached tokens) resident at the middle of the traced interval, from
  the client's records: a request is resident from its first token to its last,
  and holds its prompt plus the tokens it has received by then."""
  mid = (ctx["cap_start"] + ctx["cap_end"]) / 2
  rows = tokens = 0
  for r in ctx.get("all_recs") or ctx["recs"]:
    if r.first is None or r.first > mid:
      continue
    got = sum(n for t, n in r.events if t <= mid)
    if got < r.max_tokens:
      rows += 1
      tokens += r.prompt_tokens + got
  return float(rows), float(tokens)


def kv_quant(ctx: dict) -> str:
  """The stored type of the cache: what the serving key the kind names says, "" where the kind names none."""
  key = arch.load(ctx["hf"]["arch_kind"]).CACHE_TYPE_ENV
  return ctx["hf"]["serving_env"].get(key, "") if key else ""


def api_ttft_overhead_p50_ms(ctx: dict):
  """Client TTFT (send to first content event) minus the program's own
  submit-to-first-token span from the request's timeline, per request, median:
  what the API, the node and the loopback add around the scheduler."""
  over = []
  for r in ctx["recs"]:
    tl = ctx["timelines"].get(r.rid)
    if tl is None or r.first is None:
      continue
    first = stage_at_ms(tl, "first_token")
    if first is None:
      first = stage_at_ms(tl, "decode")
    if first is not None:
      over.append((r.first - r.sent) * 1e3 - first)
  return pct(over, 50)


def prefill_device_ms_per_ktok(ctx: dict):
  """Device time of prompt processing per thousand prompt tokens, in the traced
  interval. Prefill runs in the ``prefill.*`` programs and, for configurations
  with mixed ticks, in the prefill half of ``decode.mixed_paged_batch``; that
  half is the mixed program's device time less what the same number of plain
  decode chunks took in the same trace (left out when the trace holds no plain
  chunk to compare with). Prompt tokens are those of the timelines'
  ``prefill_chunk`` stages that fall inside the interval."""
  trace = ctx.get("trace")
  if not trace or not trace["programs"]:
    return None
  progs = trace["programs"]
  device_s = sum(v["device_s"] for f, v in progs.items() if f.startswith("prefill.") and f != "prefill.score_last")
  mixed, plain = progs.get("decode.mixed_paged_batch"), progs.get("decode.paged_batch")
  if mixed and mixed["executions"]:
    if not plain or not plain["executions"]:
      return None
    device_s += max(mixed["device_s"] - mixed["executions"] * plain["device_s"] / plain["executions"], 0.0)
  by_rid = {r.rid: r for r in ctx.get("all_recs") or ctx["recs"]}
  tokens = 0
  for rid, tl in ctx["timelines"].items():
    r = by_rid.get(rid)
    if r is None or r.sent is None:
      continue
    for ev in tl.get("events", ()):
      if ev["stage"] == "prefill_chunk" and ctx["cap_start"] <= r.sent + ev["at_ms"] / 1e3 < ctx["cap_end"]:
        tokens += int((ev.get("attributes") or {}).get("tokens", 0))
  return device_s * 1e3 / (tokens / 1e3) if tokens else None


def ttft_ms(ctx: dict) -> list[float]:
  """Due (open loop) or send (closed loop) to the first content event, per request that got one."""
  return [(r.first - (r.due if ctx["mode"] == "open" else r.sent)) * 1e3 for r in ctx["recs"] if r.first is not None and r.error is None and r.status == 200]
