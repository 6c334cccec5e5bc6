"""One run of one cell of BENCHMARK.json.

  python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: the configuration's serving environment, weights made on the
device from the seed, ``load_test_model``, ``Node`` + the ChatGPT API on a
loopback port, the correctness check against ``reference.py``, warm-up of every
shape the traffic will use, then the measured window. The last line of stdout
is the result object the contract fixes. Everything before the window opens is
``setup_s``. ``--rehearse`` is the only CPU mode: tiny widths, every metric
name prefixed ``rehearsal.``, never a device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import shutil  # noqa: E402

import arch  # noqa: E402
import common  # noqa: E402
import client  # noqa: E402
from layer_lib import pct as percentile  # noqa: E402
from common import BENCH, ROOT  # noqa: E402

def log(**kw) -> None:
  print(json.dumps(kw, default=str), file=sys.stderr, flush=True)


def rehearsal_shrink(hf: dict, traffic: dict, widths: dict) -> None:
  hf.update(widths)
  hf["serving_window_tokens"] = 1024
  hf["serving_env"] = {**hf["serving_env"], "XOT_TPU_BATCH_PAGES": "0", "XOT_TPU_MIXED_BUDGET": "256"}
  for key in ("prompt_tokens", "output_tokens"):
    for f in ("median", "min", "max"):
      traffic[key][f] = max(int(traffic[key][f] // 4), 8)
  traffic["ramp_s"] = min(float(traffic.get("ramp_s", 0)), 2.0)
  traffic["warm"] = {**traffic["warm"], "anchor_tokens": 600}
  if "slice_tokens" in hf["warm_shape_rule"]:
    hf["warm_shape_rule"] = {**hf["warm_shape_rule"], "slice_tokens": 256}


def device_info(need_chips: int, rehearse: bool) -> tuple[dict, dict | None]:
  import jax

  devs = jax.devices()
  info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
  if rehearse:
    return info, None
  peaks = json.loads((BENCH / "peaks.json").read_text())
  if info["platform"] != "tpu" or info["count"] < need_chips:
    raise SystemExit(f"this cell needs {need_chips} TPU chip(s); JAX found {info}")
  if info["kind"] not in peaks:
    raise SystemExit(f"device kind {info['kind']!r} is not in benchmark/peaks.json: add its published peaks with their source")
  return info, peaks[info["kind"]]


def memory_peak(n: int) -> int:
  import jax

  return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()[:n])


async def get_json(session, url: str):
  async with session.get(url) as resp:
    return await resp.json() if resp.status == 200 else None


async def counters(session, url: str) -> dict:
  """The program's own counts, through its own doors: /v1/programs and /metrics."""
  progs = await get_json(session, f"{url}/v1/programs")
  out = {"compiles": progs["totals"]["compiles"], "families": {f: (v["compiles"], v["dispatches"]) for f, v in progs["families"].items()}, "signatures": {f: v.get("signatures", []) for f, v in progs["families"].items()}}
  async with session.get(f"{url}/metrics") as resp:
    for line in (await resp.text()).splitlines():
      if line.startswith("xot_tpu_") and " " in line:
        name, _, val = line.rpartition(" ")
        if name.split("{")[0] in ("xot_tpu_decode_tokens_total", "xot_tpu_decode_chunks_total", "xot_tpu_sched_tick_prefill_tokens_total", "xot_tpu_tokens_generated_total"):
          try:
            out[name] = out.get(name, 0.0) + float(val)
          except ValueError:
            pass
  return out


def family_delta(before: dict, after: dict, i: int) -> dict:
  """Per program family, how far its compiles (``i`` 0) or dispatches (1) moved between two ``counters()``; the unmoved left out."""
  return {f: a[i] - b for f, a in after["families"].items() if a[i] != (b := before["families"].get(f, (0, 0))[i])}


def compiled_between(before: dict, after: dict) -> dict:
  """Which families compiled between two ``counters()``, how often, and the newest argument signatures the program's ledger kept of each (it keeps eight)."""
  return {f: {"compiles": n, "signatures": after["signatures"].get(f, [])[-n:]} for f, n in family_delta(before, after, 0).items()}


def is_failed(mode: str, r) -> bool:
  """Errored or refused; in an open loop also unfinished when the drain limit
  ended. A closed loop's requests still in flight at window close were cut by
  the harness, not failed by the server."""
  if mode == "open":
    return not r.ok
  return r.error is not None or r.status not in (None, 200)


def knee_view(mode: str, ctx: dict, traffic: dict) -> dict:
  """What the rate sweep reads (stderr only, not a metric): the share of
  requests inside the traffic file's limits and whether a backlog grew."""
  recs, lim = ctx["recs"], traffic.get("limits")
  if mode != "open" or not lim or not recs:
    return {}
  good = sum(1 for r in recs if r.ok and (r.first - r.due) * 1e3 <= lim["ttft_ms"] and ((t := r.tpot()) is None or t * 1e3 <= lim["tpot_ms"]))
  inflight = lambda at: sum(1 for r in recs if r.due <= at and (r.last is None or r.tokens < r.max_tokens or r.last > at))  # noqa: E731
  half = (ctx["t_open"] + ctx["t_close"]) / 2
  return {"share_within_limits": good / len(recs), "inflight_mid": inflight(half), "inflight_close": inflight(ctx["t_close"]), "rate_rps": traffic["rate_rps"]}


def load_reader(group: str, name: str):
  """The reader of one metric: ``<group>/<name>.py``, or, for a name split per
  cell kind (``decode_step_device_ms.open``), the quantity's one file
  ``<group>/decode_step_device_ms.py``. ``group`` is the metric's list in
  BENCHMARK.json ("end_to_end" | "per_layer")."""
  folder = BENCH / {"per_layer": "layer_metrics"}.get(group, group)
  path = folder / f"{name}.py"
  if not path.exists() and "." in name:
    path = folder / f"{name.rsplit('.', 1)[0]}.py"
  spec = importlib.util.spec_from_file_location(f"{group}_{path.stem.replace('.', '_').replace('-', '_')}", path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def read_metrics(spec: dict, group: str, cell: str, ctx: dict, readers: dict | None = None) -> dict:
  out = {}
  for m in common.metric_names(spec, group, cell):
    got = (readers or {}).get(m["name"], None) or load_reader(group, m["name"])
    value = got.read(ctx)
    if value is not None:
      out[m["name"]] = {"value": value, "unit": m["unit"]}
  return out


def client_view(ctx: dict) -> dict:
  """Statistics of the window on the client's clock, logged with every run
  (stderr, not metrics): what a later benchmark PR needs to judge which
  user-facing numbers are steady enough to carry a bound."""
  import layer_lib

  recs, mode = ctx["recs"], ctx["mode"]
  ttft = layer_lib.ttft_ms(ctx)
  tpot = [t * 1e3 for r in recs if (t := r.tpot()) is not None]
  total = [(r.last - (r.due if mode == "open" else r.sent)) * 1e3 for r in recs if r.ok]
  stat = lambda v: {"p50": percentile(v, 50), "p90": percentile(v, 90), "mean": sum(v) / len(v)} if v else {}  # noqa: E731
  return {"ttft_ms": stat(ttft), "tpot_ms": stat(tpot), "latency_ms": stat(total), "finished": len(total)}


def request_rows(ctx: dict) -> list:
  """Every request of the window as the client saw it (stderr only): what a spread is made of, when a later PR has to look."""
  since = lambda r: r.due if ctx["mode"] == "open" else r.sent  # noqa: E731
  return [[round(since(r) - ctx["t_open"], 3), r.prompt_tokens, r.tokens, round((r.first - since(r)) * 1e3, 1) if r.first else None, round(t * 1e3, 3) if (t := r.tpot()) else None] for r in ctx["recs"]]


async def main_async(args) -> int:
  spec = common.load_spec()
  cell = common.cell_of(spec, args.workload)
  hf = common.load_config(cell["config"])
  traffic = common.load_traffic(cell["traffic"])
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  if args.rehearse:
    os.environ["JAX_PLATFORMS"] = "cpu"
  kind = arch.load(hf["arch_kind"])  # a kind's file that lacks a part is refused here, before anything is built
  if args.rehearse:
    rehearsal_shrink(hf, traffic, kind.REHEARSE_WIDTHS)

  import serve

  serve.apply_serving_env(hf)

  import jax

  from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache

  cache_dir = configure_compile_cache()
  info, peaks = device_info(int(cell["chips"]), args.rehearse)
  log(event="devices", **info, compile_cache=cache_dir, workload=args.workload, seed=args.seed, rehearsal=args.rehearse)

  import aiohttp

  import correctness
  import warm
  import weights
  from tokenizer import WordTokenizer

  t = time.perf_counter()
  params = weights.build_params(hf, args.seed)
  jax.block_until_ready(params)
  log(event="weights", seconds=round(time.perf_counter() - t, 3), bytes=sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params)))

  cfg = common.model_config(hf)
  vocab = int(hf["vocab_size"])
  stack = serve.Stack(hf, cfg, params, WordTokenizer(vocab))
  stack.start()
  rc = 0
  connector = aiohttp.TCPConnector(limit=0)
  async with aiohttp.ClientSession(connector=connector, timeout=aiohttp.ClientTimeout(total=None)) as session:
    try:
      t = time.perf_counter()
      correct, detail = await correctness.check(session, stack, hf, params, args.seed, probe=args.probe_sensitivity)
      log(event="correctness", correct=correct, seconds=round(time.perf_counter() - t, 3), **detail)

      t = time.perf_counter()
      async with session.post(f"{stack.url}/v1/warmup") as resp:
        warmed = await resp.json()
      gen = importlib.import_module(f"generators.{traffic['generator']}")
      plan = gen.plan(traffic, args.seed, float(args.seconds), vocab)
      lengths = gen.prompt_lengths(plan)
      for rate in (args.sweep.split(",") if args.sweep else ()):  # a sweep warms the shapes of every rate's plan
        lengths += gen.prompt_lengths(gen.plan({**traffic, "rate_rps": float(rate)}, args.seed, float(args.seconds), vocab))
      warm_out = await warm.run(session, stack, hf["warm_shape_rule"], traffic["warm"], vocab, args.seed, lengths)
      log(event="warmup", seconds=round(time.perf_counter() - t, 3), manifest=[(m["family"], m.get("warmed")) for m in warmed.get("manifest", [])], **warm_out)
      if args.sweep:
        rc = await sweep(session, stack, gen, traffic, args, vocab)
        stack.stop()
        return rc

      trace_dir = ROOT / "_work" / "bench_trace" / args.workload
      ctx = await measure(session, stack, plan, args, trace_dir)
      ctx.update(mode=plan["mode"], t_start=T_START, hf=hf, cfg=cfg, traffic=traffic, peaks=peaks, spec=spec, chunk=int(os.getenv("XOT_TPU_BATCH_CHUNK", "8")))
      window_compiles = ctx["after"]["compiles"] - ctx["before"]["compiles"]
      if window_compiles:
        log(event="compiled_in_window", compiles=window_compiles, families=compiled_between(ctx["before"], ctx["after"]))
      if ctx["before"]["compiles"] != ctx["start"]["compiles"]:
        log(event="compiled_in_ramp", families=compiled_between(ctx["start"], ctx["before"]))
      attempted = len(ctx["recs"])
      failed = sum(1 for r in ctx["recs"] if is_failed(plan["mode"], r))
      device = {**info, "count": int(cell["chips"]), "memory_peak_bytes": memory_peak(int(cell["chips"]))}
      result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
      ctx["window_compiles"] = window_compiles
      if args.trace:
        ctx["timelines"] = await fetch_timelines(session, stack.url, ctx["recs"])
        readers = {m["name"]: load_reader("per_layer", m["name"]) for m in common.metric_names(spec, "per_layer", args.workload)}
        kernels = tuple(sorted({k for r in readers.values() for k in getattr(r, "KERNELS", ())}))
        reduced = reduce_trace(trace_dir, ctx, kernels)
        ctx["trace"] = reduced
        metrics = read_metrics(spec, "per_layer", args.workload, ctx, readers)
        if not args.rehearse:  # a rehearsal's trace holds no device plane, and it prints no device number
          device.update(device_seconds(reduced, ctx.get("capture")))
        if reduced:
          result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
          log(event="trace", programs=reduced["programs"], kernels=reduced["kernels"])
      else:
        metrics = read_metrics(spec, "end_to_end", args.workload, ctx)
      if args.rehearse:
        metrics = {f"rehearsal.{k}": v for k, v in metrics.items()}
        result["rehearsal"] = True
      result.update(metrics=metrics, device=device)
      dispatched = family_delta(ctx["before"], ctx["after"], 1)
      ramp = {"ramp_compiles": ctx["before"]["compiles"] - ctx["start"]["compiles"], "first_tokens_s": ctx.get("first_tokens_s")}
      log(event="requests", columns=["due_s", "prompt", "answer", "ttft_ms", "tpot_ms"], rows=request_rows(ctx))
      log(event="window", attempted=attempted, failed=failed, window_compiles=window_compiles, late_p95_ms=ctx.get("late_p95_ms"), dispatches=dispatched, **ramp, **client_view(ctx), **knee_view(plan["mode"], ctx, traffic))
      log(event="compared", correct=bool(correct), **correctness.compared(detail, hf["arch_kind"]), stream_equals_blocking=[detail.get("stream_equals_blocking"), True])
      print(json.dumps(result), flush=True)
    except Exception:  # noqa: BLE001 — report, clean up, exit non-zero with no result line
      import traceback

      traceback.print_exc()
      rc = 1
  stack.stop()
  return rc


FIRST_TOKENS_LIMIT_S = 300.0  # a closed loop whose callers have no first token by then is a failed run
CAPTURE_S = 6.0  # the traced interval, mid window (at most two fifths of a short window)
CAPTURE_ATTEMPTS = 3  # captures taken, one after another, until one holds a dispatch


async def measure(session, stack, plan: dict, args, trace_dir) -> dict:
  """Ramp, then the window. An open loop's window opens ``ramp_s`` after the
  first arrival is due. A closed loop's opens ``ramp_s`` after every caller has
  its first token: the callers' first requests meet an idle scheduler, which
  admits them in whatever groups they happen to arrive in, and a group shape
  that no earlier run left in the compile cache compiles there for longer than
  a fixed ramp lasts (one run in six lost 6-13 % of its window to that; my chip
  runs, PR 23c). That belongs to set-up, and ``setup_s`` shows it."""
  seconds = float(args.seconds)
  loop = asyncio.get_running_loop()
  opens: asyncio.Future = loop.create_future()  # resolves to the window's opening time
  marks: dict = {"start": await counters(session, stack.url)}
  t0 = time.perf_counter()

  async def count_at(offset: float, key: str) -> None:
    await asyncio.sleep(max(await opens + offset - time.perf_counter(), 0))
    marks[key] = await counters(session, stack.url)

  async def capture() -> None:
    """The traced interval: a few seconds in the middle of the window, taken
    with jax.profiler in this process (the python tracer off: it slows the host).
    The interval is the host span ``trace_reduce.MARK``, opened once ``start_trace``
    has returned and closed before ``stop_trace`` is asked for; ``window_s`` and
    ``busy_s`` are read inside it, whatever the profiler records before and after.
    A capture over which no program family's dispatch count moved holds no device
    work, and one over which no decode family's moved (an open loop's lull that
    held one prefill; seed 1633000008 of PR 33) gives the decode readers nothing:
    its trace is removed and another is taken at once, while a whole one still
    fits the window; the last that fits is kept if it holds any dispatch."""
    import jax

    from trace_reduce import MARK

    length = min(CAPTURE_S, seconds * 0.4)
    t_open = await opens
    await asyncio.sleep(max(t_open + (seconds - length) / 2 - time.perf_counter(), 0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    empty = []
    while len(empty) < CAPTURE_ATTEMPTS and (not empty or time.perf_counter() + length <= t_open + seconds):
      shutil.rmtree(trace_dir, ignore_errors=True)  # find_xplane and span_lib.capture take the newest file under _work/bench_trace
      trace_dir.mkdir(parents=True, exist_ok=True)
      before = await counters(session, stack.url)
      jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
      cap_start = time.perf_counter()
      with jax.profiler.TraceAnnotation(MARK):
        await asyncio.sleep(length)
      cap_end = time.perf_counter()
      stopped = loop.run_in_executor(None, jax.profiler.stop_trace)
      after = await counters(session, stack.url)
      await stopped
      event = {"event": "capture", "attempt": len(empty) + 1, "offset_s": [cap_start - t_open, cap_end - t_open], "dispatches": family_delta(before, after, 1)}
      another = len(empty) + 1 < CAPTURE_ATTEMPTS and time.perf_counter() + length <= t_open + seconds
      if any(f.startswith("decode.") for f in event["dispatches"]) or (event["dispatches"] and not another):
        marks.update(cap_start=cap_start, cap_end=cap_end, capture=event)  # logged by reduce_trace, with what the file holds
        return
      log(**event, kept=False)
      empty.append(event["offset_s"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    raise RuntimeError(f"no capture to reduce: the program dispatched nothing during any of {len(empty)} captures of {length:g} s (or, before the last, prefills and no decode step), at {empty} s of the {seconds:g} s window")

  async def open_after_first_tokens(all_recs: list, close_at: asyncio.Future) -> None:
    n = plan["clients"]
    while len(all_recs) < n or any(r.first is None and r.error is None for r in all_recs[:n]):
      if time.perf_counter() - t0 > FIRST_TOKENS_LIMIT_S:
        err = RuntimeError(f"the callers' first requests had no first token after {FIRST_TOKENS_LIMIT_S:.0f} s")
        opens.set_exception(err)
        close_at.set_exception(err)
        return
      await asyncio.sleep(0.005)
    marks["first_tokens_s"] = time.perf_counter() - t0
    opens.set_result(time.perf_counter() + plan["ramp_s"])
    close_at.set_result(opens.result() + seconds)

  side = [asyncio.create_task(count_at(0.0, "before")), asyncio.create_task(count_at(seconds, "after"))]
  if args.trace:
    side.append(asyncio.create_task(capture()))
  try:
    if plan["mode"] == "open":
      opens.set_result(t0 + plan["ramp_s"])
      recs = await client.open_loop(session, stack.url, stack.model_id, [(plan["ramp"], False), (plan["window"], True)], t0, plan["drain_s"])
    else:
      all_recs: list = []
      close_at: asyncio.Future = loop.create_future()
      side.append(asyncio.create_task(open_after_first_tokens(all_recs, close_at)))
      await client.closed_loop(session, stack.url, stack.model_id, plan["queue"], plan["clients"], all_recs, close_at)
      recs = [r for r in all_recs if r.sent is not None and r.sent >= opens.result()]
      marks["all_recs"] = all_recs
    await asyncio.gather(*side)
  finally:
    for task in side:
      task.cancel()
    await asyncio.gather(*side, return_exceptions=True)
  t_open = opens.result()
  late = [(r.sent - r.due) * 1e3 for r in recs if r.sent is not None] if plan["mode"] == "open" else []
  return {"recs": recs, "t_open": t_open, "t_close": t_open + seconds, "late_p95_ms": percentile(late, 95) if late else None, **marks}


async def fetch_timelines(session, url: str, recs: list) -> dict:
  """Stage timelines of the measured requests (the tracer keeps the newest
  256; a window that finishes more than that loses the oldest, and the
  readers use what is there)."""
  out = {}
  for r in recs:
    if r.rid:
      tl = await get_json(session, f"{url}/v1/requests/{r.rid}/timeline")
      if tl is not None:
        out[r.rid] = tl
  return out


async def sweep(session, stack, gen, traffic: dict, args, vocab: int) -> int:
  """The builder's rate sweep (``--sweep r1,r2,...``): one set-up, then one
  ramp + window + drain per rate, each reported as a ``sweep`` event. Prints no
  result line: a check never runs it."""
  for rate in (float(r) for r in args.sweep.split(",")):
    mix = {**traffic, "rate_rps": rate}
    plan = gen.plan(mix, args.seed, float(args.seconds), vocab)
    before = await counters(session, stack.url)
    ctx = await measure(session, stack, plan, args, None)
    ctx["mode"] = plan["mode"]
    log(event="requests", rate_rps=rate, columns=["due_s", "prompt", "answer", "ttft_ms", "tpot_ms"], rows=request_rows(ctx))
    log(event="sweep", out_tok_s=client.tokens_between(ctx["recs"], ctx["t_open"], ctx["t_close"]) / float(args.seconds), dispatches=family_delta(ctx["before"], ctx["after"], 1), attempted=len(ctx["recs"]), failed=sum(1 for r in ctx["recs"] if is_failed(plan["mode"], r)), window_compiles=ctx["after"]["compiles"] - ctx["before"]["compiles"],
        late_p95_ms=ctx.get("late_p95_ms"), compiles_since_last=ctx["after"]["compiles"] - before["compiles"], **client_view(ctx), **knee_view(plan["mode"], ctx, mix))
  return 0


def reduce_trace(trace_dir, ctx: dict, kernels: tuple[str, ...]) -> dict | None:
  import trace_reduce

  path = trace_reduce.find_xplane(str(trace_dir))
  if path is None or "capture" not in ctx:
    return None
  pd = trace_reduce.load(path)
  if os.getenv("BENCH_DESCRIBE_TRACE"):
    print(trace_reduce.describe(pd), file=sys.stderr, flush=True)
  red = trace_reduce.reduce(pd, trace_reduce.program_families(), kernels=kernels)
  ctx["capture"].update(kept=True, **{k: red[k] for k in ("marked", "interval", "extent", "window_s", "busy_s", "outside_s")})
  log(**ctx["capture"])
  return red


def device_seconds(reduced: dict | None, capture: dict | None) -> dict:
  """``busy_s`` and ``window_s`` of the result line: two readings of the marked capture, so
  0 <= busy <= window by construction. A line the driver would refuse — no trace, no device
  plane, no operation inside the capture — is refused here, with what the capture was."""
  busy, window = (reduced["busy_s"], reduced["window_s"]) if reduced else (math.nan, math.nan)
  if not (math.isfinite(busy) and math.isfinite(window) and 0 < busy <= window):
    raise RuntimeError(f"no result line: device.busy_s {busy!r} is not above 0 and at most device.window_s {window!r} (no operation ran on the device inside the capture, or the trace holds no device plane); capture: {json.dumps(capture)}")
  return {"busy_s": busy, "window_s": window}


def main() -> None:
  ap = argparse.ArgumentParser(description=__doc__)
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  ap.add_argument("--rehearse", action="store_true", help="CPU, tiny widths, labelled; never a device number")
  ap.add_argument("--sweep", default=None, help="comma-separated rates: one set-up, one window per rate, a `sweep` event each, no result line (builder's tool)")
  ap.add_argument("--probe-sensitivity", action="store_true", help="also compare against deliberately wrong references (builder's tool)")
  args = ap.parse_args()
  if not (ROOT / "xotorch_support_jetson_tpu").is_dir():
    raise SystemExit("the system under test (xotorch_support_jetson_tpu/) is not in this checkout")
  rc = asyncio.run(main_async(args))
  sys.stdout.flush()
  sys.stderr.flush()
  os._exit(rc)  # the engine's worker threads are not daemons; everything is flushed and stopped


if __name__ == "__main__":
  main()
