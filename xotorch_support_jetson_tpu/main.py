"""CLI entrypoint: ``xot-tpu`` — daemon (API server), one-shot run, train, eval.

Parity with reference ``xotorch/main.py`` (flag surface :73-108, component
wiring :120-182, preemptive-load + download-broadcast callbacks :184-227,
``run`` one-shot :229-259, train/eval :287-318, daemon default :362-387,
signal handling :345-358).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys
import time
import uuid

from . import registry
from .inference.engine import get_inference_engine, inference_engine_classes
from .inference.shard import Shard
from .topology.partitioning import RingMemoryWeightedPartitioningStrategy
from .utils.helpers import DEBUG, apply_platform_override, configure_compile_cache, device_summary, find_available_port, get_or_create_node_id


def build_parser() -> argparse.ArgumentParser:
  parser = argparse.ArgumentParser(prog="xot-tpu", description="TPU-native distributed LLM inference and fine-tuning")
  parser.add_argument("command", nargs="?", choices=["run", "eval", "train", "export"], help="Command to run (default: daemon with API server)")
  parser.add_argument("model_name", nargs="?", help="Model id (see registry)")
  parser.add_argument("--default-model", type=str, default="llama-3.2-1b")
  parser.add_argument("--node-id", type=str, default=None)
  parser.add_argument("--node-host", type=str, default="0.0.0.0")
  parser.add_argument("--node-port", type=int, default=None)
  parser.add_argument("--listen-port", type=int, default=5678)
  parser.add_argument("--broadcast-port", type=int, default=5678)
  parser.add_argument("--discovery-module", type=str, choices=["udp", "manual", "none"], default="udp")
  parser.add_argument("--discovery-timeout", type=int, default=30)
  parser.add_argument("--discovery-config-path", type=str, default=None)
  parser.add_argument("--wait-for-peers", type=int, default=0)
  parser.add_argument("--chatgpt-api-port", type=int, default=52415)
  # None → the API resolves XOT_TPU_RESPONSE_TIMEOUT_S (default 900 s); an
  # explicit flag still wins over the env.
  parser.add_argument("--chatgpt-api-response-timeout", type=int, default=None)
  parser.add_argument("--max-generate-tokens", type=int, default=10000)
  parser.add_argument("--inference-engine", type=str, default="jax", choices=list(inference_engine_classes))
  parser.add_argument("--temp", "--default-temp", dest="temp", type=float, default=0.6)
  parser.add_argument("--top-k", type=int, default=35)
  parser.add_argument("--prompt", type=str, default="Who are you?")
  parser.add_argument("--system-prompt", type=str, default=None)
  parser.add_argument("--disable-tui", action="store_true")
  parser.add_argument("--chat-tui", action="store_true", help="daemon with an interactive terminal chat instead of the topology TUI")
  parser.add_argument("--run-model", type=str, default=None, help="alias for the `run MODEL` command (reference parity)")
  parser.add_argument("--models-seed-dir", type=str, default=None, help="move pre-fetched model dirs from here into the downloads home at startup")
  parser.add_argument("--interface-type-filter", type=str, default=None, help="comma-separated interface types UDP discovery may adopt peers from (e.g. Ethernet,WiFi)")
  parser.add_argument("--max-parallel-downloads", type=int, default=8)
  parser.add_argument("--data", type=str, default=None, help="dataset dir for train/eval")
  parser.add_argument("--iters", type=int, default=100)
  parser.add_argument("--batch-size", type=int, default=1)
  parser.add_argument("--seq-len", type=int, default=512)
  parser.add_argument("--lr", type=float, default=1e-5)
  # TRAINING-side LoRA attach (one adapter). For SERVING fine-tuned
  # variants, do NOT merge one checkpoint per process: point
  # XOT_TPU_LORA_DIR at a directory of adapter .npz files and the engine
  # serves EVERY variant from one resident base model (the multi-LoRA
  # registry, inference/adapters.py — select per request via the `model`
  # field / x-adapter header; see README "Multi-LoRA serving").
  parser.add_argument("--lora-rank", type=int, default=0, help=">0 enables LoRA with this rank (training; serving uses XOT_TPU_LORA_DIR + the adapter registry)")
  parser.add_argument("--save-every", type=int, default=0)
  parser.add_argument("--save-checkpoint-dir", type=str, default="checkpoints")
  parser.add_argument("--resume-checkpoint", type=str, default=None)
  parser.add_argument("--export-dir", type=str, default=None, help="output directory for the `export` command (HF-format checkpoint)")
  parser.add_argument("--export-dtype", type=str, default="float32", choices=["float32", "bfloat16"], help="tensor dtype for the `export` command")
  parser.add_argument("--allowed-node-ids", type=str, default=None, help="comma-separated")
  # Multi-host SPMD (one mesh spanning hosts over ICI/DCN): initializes
  # jax.distributed so every process sees the global device set; the in-slice
  # engine mesh and parallel/ training meshes then span all hosts. This is
  # the TPU-pod alternative to the gRPC ring (which remains the path for
  # heterogeneous/loose clusters).
  parser.add_argument("--jax-coordinator", type=str, default=None, help="host:port of process 0 (enables jax.distributed)")
  # Mesh serving modes (flag form of the XOT_TPU_PP / XOT_TPU_SP env vars —
  # the engine reads the env, so the flags just set them before it loads).
  parser.add_argument("--pp", type=int, default=None, help="serve the loaded layer range as N pipeline stages over local chips")
  parser.add_argument("--sp", type=int, default=None, help="shard the KV cache over N local chips (long-context serving)")
  parser.add_argument("--jax-num-processes", type=int, default=None)
  parser.add_argument("--jax-process-id", type=int, default=None)
  return parser


def maybe_init_jax_distributed(args) -> None:
  if not args.jax_coordinator:
    return
  import jax

  jax.distributed.initialize(
    coordinator_address=args.jax_coordinator,
    num_processes=args.jax_num_processes,
    process_id=args.jax_process_id,
  )
  if DEBUG >= 1:
    import jax as _jax

    print(f"[main] jax.distributed up: process {args.jax_process_id}/{args.jax_num_processes}, {_jax.device_count()} global devices")


def build_components(args):
  """Wire downloader → engine → discovery → Node → gRPC server → API."""
  from .api.chatgpt_api import ChatGPTAPI
  from .download.downloader import new_shard_downloader
  from .networking.grpc.grpc_peer_handle import GRPCPeerHandle
  from .networking.grpc.grpc_server import GRPCServer
  from .orchestration.node import Node

  node_id = args.node_id or get_or_create_node_id()
  node_port = args.node_port or find_available_port(args.node_host)

  downloader = new_shard_downloader(args.max_parallel_downloads)
  engine = get_inference_engine(args.inference_engine, downloader)
  engine_classname = type(engine).__name__

  def create_peer_handle(peer_id, address, description, device_capabilities):
    return GRPCPeerHandle(peer_id, address, description, device_capabilities)

  if args.discovery_module == "udp":
    from .networking.udp.udp_discovery import UDPDiscovery

    discovery = UDPDiscovery(
      node_id,
      node_port,
      args.listen_port,
      args.broadcast_port,
      create_peer_handle,
      discovery_timeout=args.discovery_timeout,
      allowed_node_ids=args.allowed_node_ids.split(",") if args.allowed_node_ids else None,
      allowed_interface_types=args.interface_type_filter.split(",") if args.interface_type_filter else None,
    )
  elif args.discovery_module == "manual":
    from .networking.manual.manual_discovery import ManualDiscovery

    if not args.discovery_config_path:
      raise ValueError("--discovery-config-path required with manual discovery")
    discovery = ManualDiscovery(args.discovery_config_path, node_id, create_peer_handle)
  else:
    from .networking.discovery import Discovery

    class _NoDiscovery(Discovery):
      async def start(self):
        pass

      async def stop(self):
        pass

      async def discover_peers(self, wait_for_peers: int = 0):
        return []

    discovery = _NoDiscovery()

  topology_viz = None
  if not args.disable_tui:
    try:
      from .viz.topology_viz import TopologyViz

      topology_viz = TopologyViz()
    except Exception:  # noqa: BLE001 — rich unavailable or no tty
      topology_viz = None

  node = Node(
    node_id,
    None,
    engine,
    discovery,
    downloader,
    RingMemoryWeightedPartitioningStrategy(),
    max_generate_tokens=args.max_generate_tokens,
    default_sample_temp=args.temp,
    default_sample_top_k=args.top_k,
    topology_viz=topology_viz,
  )
  server = GRPCServer(node, args.node_host, node_port)
  node.server = server

  api = ChatGPTAPI(
    node,
    engine_classname,
    response_timeout=args.chatgpt_api_response_timeout,
    default_model=args.default_model,
    system_prompt=args.system_prompt,
  )

  # Preemptive shard load: when any node starts a prompt, every node warms its
  # own shard of that model (reference main.py:204-215).
  def on_opaque_status(request_id: str, status: str):
    try:
      data = json.loads(status)
      if data.get("type") == "node_status" and data.get("status") == "start_process_prompt":
        base_shard = Shard.from_dict(data.get("base_shard", {}))
        from .inference import sched_admission

        if sched_admission.disagg_enabled() and os.environ.get("XOT_TPU_BATCHED", "0") == "1":
          # Disaggregated serving (ISSUE 10): every node holds the FULL
          # model — warming the ring PARTITION here would load a partial
          # shard that the first decode handoff immediately swaps out
          # (dropping the batched server and the adopted KV pages with it).
          current = Shard(base_shard.model_id, 0, base_shard.n_layers - 1, base_shard.n_layers)
        else:
          current = node.get_current_shard(base_shard)
        asyncio.create_task(engine.ensure_shard(current))
    except Exception:  # noqa: BLE001
      pass

  node.on_opaque_status.register("preload").on_next(on_opaque_status)

  # Download progress rebroadcast (throttled), reference main.py:217-227.
  last_broadcast = {}

  def on_progress(shard, event):
    now = time.time()
    if now - last_broadcast.get(shard, 0) < 0.2 and event.status != "complete":
      return
    last_broadcast[shard] = now
    asyncio.create_task(
      node.broadcast_opaque_status(
        "",
        json.dumps({"type": "download_progress", "node_id": node.id, "progress": event.to_dict()}),
      )
    )

  if downloader is not None:
    downloader.on_progress.register("broadcast").on_next(on_progress)

  return node, server, api, engine, engine_classname


async def run_model_cli(node, engine_classname: str, model_name: str, prompt: str) -> None:
  shard = registry.build_base_shard(model_name, engine_classname)
  if shard is None:
    raise SystemExit(f"Error: unsupported model '{model_name}' for engine {engine_classname}")
  from .inference.tokenizers import resolve_tokenizer

  tokenizer = await resolve_tokenizer(registry.get_repo(model_name, engine_classname))
  messages = [{"role": "user", "content": prompt}]
  templated = tokenizer.apply_chat_template(messages, tokenize=False, add_generation_prompt=True)

  request_id = str(uuid.uuid4())
  done = asyncio.Event()
  tokens_out: list[int] = []
  t_start = time.perf_counter()

  def on_token(rid, tokens, is_finished):
    if rid != request_id:
      return
    tokens_out.extend(tokens)
    text = tokenizer.decode(tokens)
    print(text, end="", flush=True)
    if is_finished:
      done.set()

  node.on_token.register("cli").on_next(on_token)
  await node.process_prompt(shard, templated, request_id)
  try:
    await asyncio.wait_for(done.wait(), timeout=300)
  except asyncio.TimeoutError:
    raise SystemExit(f"\n[timeout] no completion after 300 s ({len(tokens_out)} tokens)") from None
  elapsed = time.perf_counter() - t_start
  print(f"\n[{len(tokens_out)} tokens in {elapsed:.1f}s — {len(tokens_out)/max(elapsed,1e-9):.1f} tok/s]")


async def train_model_cli(node, engine_classname: str, args) -> None:
  from .train.driver import run_training

  await run_training(node, engine_classname, args)


async def eval_model_cli(node, engine_classname: str, args) -> None:
  from .train.driver import run_eval

  await run_eval(node, engine_classname, args)


async def export_model_cli(node, engine_classname: str, args) -> None:
  """`export MODEL --export-dir OUT [--resume-checkpoint CKPT]` — load the
  model (plus an optional trained checkpoint incl. LoRA adapters), write an
  HF-format checkpoint AutoModelForCausalLM loads directly
  (models/hf_export.py). The reference has no training→HF path at all."""
  from . import registry
  from .models.hf_export import export_hf_checkpoint

  if not args.export_dir:
    raise SystemExit("export requires --export-dir")
  model = args.model_name or args.default_model
  shard = registry.build_full_shard(model, engine_classname)
  if shard is None:
    raise SystemExit(f"unknown model {model!r} for engine {engine_classname}")
  engine = node.inference_engine
  await engine.ensure_shard(shard)
  if getattr(engine, "diffusion", None) is not None:
    raise SystemExit(f"{model!r} is an image-generation model; HF export covers text decoders only")
  if args.resume_checkpoint:
    # A LoRA-trained checkpoint carries adapter leaves the plain tree lacks;
    # attach matching adapters FIRST or load_checkpoint would silently drop
    # the fine-tune (npz restore only fills keys present in the template).
    # The rank is DETECTED from the checkpoint so forgetting --lora-rank
    # cannot lose the fine-tune; an explicit flag must agree.
    from .train.checkpoint import checkpoint_lora_rank

    detected = checkpoint_lora_rank(args.resume_checkpoint)
    if detected and args.lora_rank and args.lora_rank != detected:
      raise SystemExit(f"--lora-rank {args.lora_rank} does not match the checkpoint's adapter rank {detected}")
    rank = args.lora_rank or detected
    if rank:
      engine.attach_lora(rank)
    await engine.load_checkpoint(shard, args.resume_checkpoint)
  out = export_hf_checkpoint(args.export_dir, engine.cfg, engine.params, dtype=args.export_dtype)
  # ship the tokenizer alongside so the export is a complete HF repo
  src = getattr(engine, "_model_dir", None)
  if src is not None:
    import shutil

    for name in ("tokenizer.json", "tokenizer_config.json", "tokenizer.model", "special_tokens_map.json", "vocab.json", "merges.txt"):
      p = src / name
      if p.exists():
        shutil.copy2(p, out / name)
  print(f"exported HF checkpoint to {out}")


async def async_main(args) -> None:
  if args.models_seed_dir:
    from .download.downloader import seed_models

    try:
      await seed_models(args.models_seed_dir)
    except Exception as e:  # noqa: BLE001 — seeding is best-effort, like the reference
      print(f"error seeding models from {args.models_seed_dir}: {e}")
  node, server, api, engine, engine_classname = build_components(args)
  await node.start(wait_for_peers=args.wait_for_peers)

  loop = asyncio.get_event_loop()
  stop_event = asyncio.Event()
  force_event = asyncio.Event()  # second signal: skip the graceful drain

  def shutdown():
    if stop_event.is_set():
      # Second SIGINT/SIGTERM: the operator wants out NOW — abort the
      # drain wait and fall through to the hard stop.
      force_event.set()
    stop_event.set()

  for sig in (signal.SIGINT, signal.SIGTERM):
    try:
      loop.add_signal_handler(sig, shutdown)
    except NotImplementedError:
      pass

  try:
    if args.command == "run" or (args.command is None and args.run_model):
      model = args.model_name or args.run_model or args.default_model
      await run_model_cli(node, engine_classname, model, args.prompt)
    elif args.command == "train":
      await train_model_cli(node, engine_classname, args)
    elif args.command == "eval":
      await eval_model_cli(node, engine_classname, args)
    elif args.command == "export":
      await export_model_cli(node, engine_classname, args)
    elif args.chat_tui:
      # Interactive terminal chat against this daemon (reference --chat-tui):
      # the API still serves alongside the REPL. SIGINT/SIGTERM must still
      # stop the process (the loop-level handler swallows KeyboardInterrupt,
      # so the REPL task races stop_event instead of relying on it).
      from .viz.chat_tui import run_chat_tui

      runner = await api.run(port=args.chatgpt_api_port)
      tui = asyncio.ensure_future(run_chat_tui(node, engine_classname, args.default_model))
      stopper = asyncio.ensure_future(stop_event.wait())
      try:
        await asyncio.wait({tui, stopper}, return_when=asyncio.FIRST_COMPLETED)
      finally:
        for t in (tui, stopper):
          if not t.done():
            t.cancel()
        await runner.cleanup()
    else:
      runner = await api.run(port=args.chatgpt_api_port)
      await stop_event.wait()
      # Graceful drain (ISSUE 8): announce shutdown so peers stop routing
      # new work here, migrate resident batched rows to a surviving peer
      # (carry_tokens resume), and wait out in-flight streams up to
      # XOT_TPU_DRAIN_S. A second signal (force_event) aborts the wait.
      try:
        await node.graceful_drain(force=force_event)
      except Exception:  # noqa: BLE001 — drain is best-effort; stop regardless
        if DEBUG >= 1:
          import traceback

          traceback.print_exc()
      await runner.cleanup()
  finally:
    await node.stop()


def run() -> None:
  args = build_parser().parse_args()
  if args.pp:
    os.environ["XOT_TPU_PP"] = str(args.pp)
  if args.sp:
    os.environ["XOT_TPU_SP"] = str(args.sp)
  # The engine serves in exactly one mesh mode; a silent pick would leave the
  # operator believing both splits are active. Check the EFFECTIVE settings —
  # the flags are just aliases for the env vars, which may also be exported.
  if int(os.environ.get("XOT_TPU_PP", "0") or 0) > 1 and int(os.environ.get("XOT_TPU_SP", "0") or 0) > 1:
    print("error: --pp/XOT_TPU_PP and --sp/XOT_TPU_SP are mutually exclusive serving modes", file=sys.stderr)
    sys.exit(2)
  apply_platform_override()
  cache_dir = configure_compile_cache()
  maybe_init_jax_distributed(args)
  # Logged once, before anything is loaded: the first device query is also
  # where a host whose accelerator cannot be reached fails, loudly.
  print(json.dumps({"event": "devices", **device_summary(), "compile_cache": cache_dir}), flush=True)
  try:
    asyncio.run(async_main(args))
  except KeyboardInterrupt:
    print("\nshutting down")


if __name__ == "__main__":
  run()
