#!/usr/bin/env python3
"""Standing proof that the serving path runs on the chip.

Drives the repo's main path once, through the entry points a user calls —
daemon → ChatGPT-compatible API → Node → JaxShardedInferenceEngine → the
fused programs of models/decoder.py — at the full published width and depth
of Llama-3.2-1B, with random weights made from ``--seed``:

  python chip_smoke.py               one chip: kernels, solo daemon, batched daemon
  python chip_smoke.py --chips 4     four chips: one device vs --pp 4 vs the tp default
  python chip_smoke.py --cpu-rehearsal [--chips 4]
                                     the same control flow at tiny width on the CPU

One process per chip: this parent never imports JAX. Every phase is a child
process, started after the previous one has exited and released the device.
Each phase prints one JSON line; the last line of stdout is the verdict,
``{"ok": true, "device": {"platform", "kind", "count"}}``, with the device as
the *serving* process reported it. No accelerator (and no ``--cpu-rehearsal``)
is a failure: ``"ok": false`` and a non-zero exit.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
WORK = ROOT / "_work" / "chip_smoke"  # git-ignored; checkpoint, logs, daemon home
MODEL = "llama-3.2-1b"  # the registry's --default-model

# config.json as published for meta-llama/Llama-3.2-1B-Instruct (the registry
# card's repo, unsloth/Llama-3.2-1B-Instruct, mirrors it): full width AND depth.
LLAMA_32_1B = {
  "architectures": ["LlamaForCausalLM"],
  "attention_bias": False,
  "bos_token_id": 128000,
  "eos_token_id": [128001, 128008, 128009],
  "head_dim": 64,
  "hidden_act": "silu",
  "hidden_size": 2048,
  "initializer_range": 0.02,
  "intermediate_size": 8192,
  "max_position_embeddings": 131072,
  "mlp_bias": False,
  "model_type": "llama",
  "num_attention_heads": 32,
  "num_hidden_layers": 16,
  "num_key_value_heads": 8,
  "rms_norm_eps": 1e-05,
  "rope_scaling": {"factor": 32.0, "high_freq_factor": 4.0, "low_freq_factor": 1.0, "original_max_position_embeddings": 8192, "rope_type": "llama3"},
  "rope_theta": 500000.0,
  "tie_word_embeddings": True,
  "torch_dtype": "bfloat16",
  "vocab_size": 128256,
}
# --cpu-rehearsal only: same family and code path, toy width, 4 layers so --pp 4 splits.
TINY = {
  **LLAMA_32_1B,
  "bos_token_id": 1,
  "eos_token_id": [2],
  "head_dim": 16,
  "hidden_size": 64,
  "initializer_range": 0.25,  # at this width 0.02 leaves the layers silent and greedy decoding echoes its prompt
  "intermediate_size": 128,
  "max_position_embeddings": 512,
  "num_attention_heads": 4,
  "num_hidden_layers": 4,
  "num_key_value_heads": 4,
  "rope_scaling": None,
  "rope_theta": 10000.0,
  "torch_dtype": "float32",
  "vocab_size": 512,
}

WORDS = "hello world how are you today the quick brown fox tell me a story about tpus what is your name".split()
PROMPT = "hello world how are you today"
CONCURRENT_PROMPTS = [PROMPT, "the quick brown fox", "tell me a story about tpus", "what is your name"]
MAX_TOKENS = 16

# Families /v1/programs must list after each phase: each entry is a prefix,
# or a tuple of prefixes of which one must match. The kernel families (ops.*)
# exist only where a dispatch table picked the Pallas kernel, so a kernel that
# gave way to its XLA/gather reference fails the phase. The batched decode
# chunk is ``spec.paged_batch`` instead of ``decode.paged_batch`` whenever the
# draft-free n-gram proposer (on by default) found a repeat to draft from.
SOLO_FAMILIES = ("decode.fused_generate", "decode.fused")
BATCHED_FAMILIES = (("decode.paged_batch", "spec.paged_batch"), "prefill.pages_many")
TPU_SOLO_KERNELS = ("ops.flash_prefill",)
TPU_BATCHED_KERNELS = ("ops.flash_prefill", "ops.paged_attention")


def emit(**fields) -> None:
  print(json.dumps(fields), flush=True)


# ------------------------------------------------------------- checkpoint


def _tensor_plan(cfg: dict) -> list[tuple[str, tuple[int, ...], bool]]:
  """(HF tensor name, shape, is_norm) in file order — the key space
  models/loader.py maps; tied embedding, so no lm_head."""
  d, f, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
  q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
  plan = [("model.embed_tokens.weight", (cfg["vocab_size"], d), False)]
  for i in range(cfg["num_hidden_layers"]):
    pre = f"model.layers.{i}"
    plan += [
      (f"{pre}.input_layernorm.weight", (d,), True),
      (f"{pre}.self_attn.q_proj.weight", (q, d), False),
      (f"{pre}.self_attn.k_proj.weight", (kv, d), False),
      (f"{pre}.self_attn.v_proj.weight", (kv, d), False),
      (f"{pre}.self_attn.o_proj.weight", (d, q), False),
      (f"{pre}.post_attention_layernorm.weight", (d,), True),
      (f"{pre}.mlp.gate_proj.weight", (f, d), False),
      (f"{pre}.mlp.up_proj.weight", (f, d), False),
      (f"{pre}.mlp.down_proj.weight", (d, f), False),
    ]
  plan.append(("model.norm.weight", (d,), True))
  return plan


def _encode(x: np.ndarray, dtype: str) -> bytes:
  if dtype == "F32":
    return x.astype("<f4").tobytes()
  bits = x.astype("<f4").view(np.uint32)  # bf16, round to nearest even
  return ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16).astype("<u2").tobytes()


def write_weights(path: Path, cfg: dict, seed: int) -> int:
  """Random safetensors checkpoint straight from numpy (no torch, no JAX):
  N(0, initializer_range) matrices, unit norms. Returns bytes written."""
  dtype = {"bfloat16": "BF16", "float32": "F32"}[cfg["torch_dtype"]]
  width = 2 if dtype == "BF16" else 4
  header, offset = {}, 0
  plan = _tensor_plan(cfg)
  for name, shape, _ in plan:
    n = int(np.prod(shape)) * width
    header[name] = {"dtype": dtype, "shape": list(shape), "data_offsets": [offset, offset + n]}
    offset += n
  head = json.dumps(header, separators=(",", ":")).encode()
  head += b" " * (-len(head) % 8)
  rng = np.random.default_rng(seed)
  with open(path, "wb") as f:
    f.write(len(head).to_bytes(8, "little"))
    f.write(head)
    for _, shape, is_norm in plan:
      if is_norm:
        f.write(_encode(np.ones(shape, np.float32), dtype))
        continue
      rows = max(1, (1 << 24) // shape[1])  # ~16M elements at a time
      for r in range(0, shape[0], rows):
        block = rng.standard_normal((min(rows, shape[0] - r), shape[1]), dtype=np.float32)
        f.write(_encode(block * np.float32(cfg["initializer_range"]), dtype))
  return 8 + len(head) + offset


def write_tokenizer(path: Path, cfg: dict) -> None:
  """A word-level tokenizer covering EVERY id of the model's vocabulary, so
  any generated token decodes to a distinct word and equal text means equal
  tokens. Real words first, ``t<id>`` fillers after, bos/eos at the
  config's ids."""
  from tokenizers import Tokenizer, models, pre_tokenizers

  specials = {cfg["bos_token_id"]: "<s>", cfg["eos_token_id"][0]: "</s>"}
  ids = [i for i in range(cfg["vocab_size"]) if i not in specials]
  names = ["<unk>", *dict.fromkeys(WORDS)]
  vocab = {tok: i for i, tok in specials.items()} | dict(zip(names, ids)) | {f"t{i}": i for i in ids[len(names) :]}
  tok = Tokenizer(models.WordLevel(vocab=vocab, unk_token="<unk>"))
  tok.pre_tokenizer = pre_tokenizers.Whitespace()
  tok.save(str(path / "tokenizer.json"))
  (path / "tokenizer_config.json").write_text(
    json.dumps(
      {
        "tokenizer_class": "PreTrainedTokenizerFast",
        "unk_token": "<unk>",
        "bos_token": "<s>",
        "eos_token": "</s>",
        "clean_up_tokenization_spaces": False,
        "chat_template": "{% for m in messages %}{{ m['content'] }} {% endfor %}",
      }
    )
  )


def checkpoint_path(rehearsal: bool, seed: int) -> Path:
  return WORK / f"ckpt-{'tiny' if rehearsal else MODEL}-seed{seed}"


def ensure_checkpoint(rehearsal: bool, seed: int) -> None:
  """Build the checkpoint of ``checkpoint_path`` unless a finished one is
  there already (the marker is written last). Called once a child has shown
  there is a device to serve it on, so a run without one fails in seconds."""
  path = checkpoint_path(rehearsal, seed)
  marker = path / "complete.json"
  if marker.exists():
    return
  cfg = TINY if rehearsal else LLAMA_32_1B
  t0 = time.perf_counter()
  path.mkdir(parents=True, exist_ok=True)
  (path / "config.json").write_text(json.dumps(cfg))
  n_bytes = write_weights(path / "model.safetensors", cfg, seed)
  write_tokenizer(path, cfg)
  marker.write_text(json.dumps({"seed": seed, "weight_bytes": n_bytes}))
  emit(phase="checkpoint", ok=True, path=str(path.relative_to(ROOT)), seed=seed, weight_bytes=n_bytes, wall_s=round(time.perf_counter() - t0, 2), weights=f"random N(0, {cfg['initializer_range']}), unit norms")


# ------------------------------------------------------------ child phases


def child_env(rehearsal: bool, chips: int = 1, **extra: str) -> dict:
  env = dict(os.environ)
  env.update(PYTHONUNBUFFERED="1", HF_HUB_OFFLINE="1", XOT_TPU_UUID="chip-smoke", XOT_TPU_HOME=str(WORK / "home"))
  if rehearsal:  # the CPU backend, with as many virtual devices as the run has chips
    flags = [f for f in env.get("XLA_FLAGS", "").split() if "xla_force_host_platform_device_count" not in f]
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags + [f"--xla_force_host_platform_device_count={chips}"]))
  env.update(extra)
  return env


def stop(proc: subprocess.Popen, grace_s: float = 60.0) -> None:
  """SIGTERM the child's process group (the daemon drains and exits), then
  SIGKILL what is left. Returns only once the group is gone, so the next
  phase finds the chip free."""
  if proc.poll() is None:
    os.killpg(proc.pid, signal.SIGTERM)
    try:
      proc.wait(grace_s)
    except subprocess.TimeoutExpired:
      pass
  try:
    os.killpg(proc.pid, signal.SIGKILL)
  except ProcessLookupError:
    pass
  proc.wait()


def run_kernels_phase(rehearsal: bool) -> dict:
  """Each Pallas kernel of the serving path against its XLA reference on
  random inputs at Llama-3.2-1B head shapes, in a child of its own."""
  t0 = time.perf_counter()
  cmd = [sys.executable, str(Path(__file__).resolve()), "--child-kernels"] + (["--cpu-rehearsal"] if rehearsal else [])
  proc = subprocess.Popen(cmd, env=child_env(rehearsal), cwd=ROOT, stdout=subprocess.PIPE, text=True, start_new_session=True)
  try:
    out, _ = proc.communicate(timeout=600)
  finally:
    stop(proc, grace_s=5.0)
  if proc.returncode != 0:
    raise RuntimeError(f"kernels child exited {proc.returncode}: {out[-2000:]}")
  result = json.loads(out.strip().splitlines()[-1])
  result["wall_s"] = round(time.perf_counter() - t0, 2)
  return result


def kernels_child(rehearsal: bool) -> None:
  """Runs in the child: the only code of this file that imports JAX."""
  sys.path.insert(0, str(ROOT))
  from xotorch_support_jetson_tpu.utils.helpers import apply_platform_override, configure_compile_cache, device_summary

  apply_platform_override()
  configure_compile_cache()
  import jax
  import jax.numpy as jnp

  from xotorch_support_jetson_tpu.models.quantize import quantize_kv, quantize_kv_int4
  from xotorch_support_jetson_tpu.ops.attention import gqa_attention
  from xotorch_support_jetson_tpu.ops.paged import PAGE_TILE, paged_decode_attention, paged_gqa_attention_ref
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_attention_prefill

  device = device_summary()
  if device["platform"] != "tpu" and not rehearsal:
    emit(phase="kernels", ok=False, device=device, error="no TPU: the kernels only compile for one")
    sys.exit(1)
  interpret = device["platform"] != "tpu"
  # Llama-3.2-1B heads (32/8 x 64), 64-token pages, 1k context, the smoke's 16 rows.
  B, Hq, Hkv, hd, ps, mp, skv = (2, 4, 2, 64, 8, 4, 256) if interpret else (16, 32, 8, 64, 64, 16, 1024)
  rng = np.random.default_rng(0)
  dt = jnp.float32 if interpret else jnp.bfloat16
  n_pages = B * mp + 1
  q = jnp.asarray(rng.normal(size=(B, Hq, hd)), dt)
  k = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, hd)), jnp.float32)
  v = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, hd)), jnp.float32)
  tables = jnp.asarray(1 + rng.permutation(B * mp).reshape(B, mp), jnp.int32)
  lengths = jnp.asarray(rng.integers(1, mp * ps + 1, size=(B,)), jnp.int32)
  cases = []

  def check(name: str, got, want, atol: float) -> None:
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    cases.append({"kernel": name, "max_abs_err": err, "ref_max_abs": float(np.max(np.abs(want))), "ok": bool(np.isfinite(got).all() and err <= atol)})

  def reference(fn, *args, **kwargs):
    with jax.default_matmul_precision("highest"):  # the kernels run as served: no precision set
      return fn(*args, **kwargs)

  atol = 1e-4 if interpret else 3e-2  # bf16 operands on the chip
  pairs = lambda x: jnp.concatenate([x[:, 0::2], x[:, 1::2]], axis=-1)  # noqa: E731 — [P, Hkv/2, ps, 128]: how the served pool stores float heads of 64 (ops/paged.py, the module note)
  for quant, pools in (("", (k.astype(dt), v.astype(dt))), ("bf16-pairs", (k.astype(dt), v.astype(dt))), ("int8", quantize_kv(k) + quantize_kv(v)), ("int4", quantize_kv_int4(k) + quantize_kv_int4(v))):
    if quant in ("int8", "int4"):
      kc, ks, vc, vs = pools
      scales = {"k_scale_pool": ks, "v_scale_pool": vs}
    else:
      (kc, vc), scales = pools, {}
    tile = min(PAGE_TILE, mp)
    stored = pairs if quant == "bf16-pairs" else lambda x: x  # the reference reads the pool a head a row, the kernel as served
    got = paged_decode_attention(q, stored(kc), stored(vc), tables, lengths, ps, pages_per_step=tile, interpret=interpret, **scales)
    want = reference(paged_gqa_attention_ref, q[:, None], kc, vc, tables, lengths, ps, **scales)[:, 0]
    check(f"ops.paged_attention[{quant or 'bf16'},G={tile}]", got, want, atol)
  # Flash prefill: one PREFILL_BUCKET of queries against a longer cache.
  sq = 128
  qf = jnp.asarray(rng.normal(size=(1, sq, Hq, hd)), dt)
  kf = jnp.asarray(rng.normal(size=(1, skv, Hkv, hd)), jnp.float32)
  vf = jnp.asarray(rng.normal(size=(1, skv, Hkv, hd)), jnp.float32)
  q_pos = jnp.arange(sq, dtype=jnp.int32)[None, :]
  kv_pos = jnp.arange(skv, dtype=jnp.int32)
  check("ops.flash_prefill[bf16]", flash_attention_prefill(qf, kf.astype(dt), vf.astype(dt), q_offset=0, interpret=interpret), reference(gqa_attention, qf, kf.astype(dt), vf.astype(dt), q_pos, kv_pos), atol)
  (kq, ksc), (vq, vsc) = quantize_kv(kf), quantize_kv(vf)
  check(
    "ops.flash_prefill[int8]",
    flash_attention_prefill(qf, kq, vq, q_offset=0, k_scale=ksc, v_scale=vsc, interpret=interpret),
    reference(gqa_attention, qf, kq, vq, q_pos, kv_pos, k_scale=ksc, v_scale=vsc),
    atol,
  )
  ok = all(c["ok"] for c in cases)
  emit(phase="kernels", ok=ok, device=device, interpret=interpret, cases=cases)
  sys.exit(0 if ok else 1)


# ----------------------------------------------------------------- serving


def free_port() -> int:
  with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    return s.getsockname()[1]


def http_open(url: str, body: dict | None = None, timeout: float = 900.0):
  """GET, or POST ``body`` as JSON; the open response."""
  data = None if body is None else json.dumps(body).encode()
  return urllib.request.urlopen(urllib.request.Request(url, data=data, headers={"Content-Type": "application/json"}), timeout=timeout)


def http_json(url: str, body: dict | None = None, timeout: float = 900.0) -> dict:
  try:
    with http_open(url, body, timeout) as resp:
      return json.loads(resp.read())
  except urllib.error.HTTPError as e:  # keep what the server said about it
    raise RuntimeError(f"{url}: HTTP {e.code}: {e.read().decode(errors='replace')[:1000]}") from None


def chat(base: str, prompt: str, stream: bool) -> dict:
  """One /v1/chat/completions request → {"text", "finish_reason", "tokens"}.
  Greedy by the daemon's ``--temp 0.0``; a streamed answer is the
  concatenation of its deltas and counts one token per content chunk."""
  body = {"model": MODEL, "messages": [{"role": "user", "content": prompt}], "stream": stream, "max_tokens": MAX_TOKENS}
  if not stream:
    data = http_json(f"{base}/v1/chat/completions", body)
    choice = data["choices"][0]
    return {"text": choice["message"]["content"], "finish_reason": choice["finish_reason"], "tokens": data["usage"]["completion_tokens"]}
  text, finish = "", None
  with http_open(f"{base}/v1/chat/completions", body) as resp:
    for raw in resp:
      line = raw.decode().strip()
      if not line.startswith("data: ") or line == "data: [DONE]":
        continue
      choice = json.loads(line[6:])["choices"][0]
      text += choice["delta"].get("content") or ""
      finish = choice.get("finish_reason") or finish
  return {"text": text, "finish_reason": finish, "tokens": len(text.split())}


def check_answer(label: str, answer: dict) -> None:
  if answer["tokens"] <= 0 or not answer["text"].strip():
    raise RuntimeError(f"{label}: no tokens in {answer!r}")
  if answer["finish_reason"] not in ("stop", "length"):
    raise RuntimeError(f"{label}: finish_reason {answer['finish_reason']!r}")


def serve_phase(name: str, seed: int, rehearsal: bool, expect_chips: int, env: dict | None = None, flags: tuple[str, ...] = (), families: tuple = (), tpu_kernels: tuple = ()) -> dict:
  """Start the daemon as the README does, answer one blocking, one
  streamed and four concurrent requests, read the program ledger, stop the
  daemon. Raises on the first thing that is not right."""
  port = free_port()
  base = f"http://127.0.0.1:{port}"
  log_path = WORK / f"{name}.log"
  cmd = [
    sys.executable, "-m", "xotorch_support_jetson_tpu.main", "--discovery-module", "none", "--disable-tui",
    "--chatgpt-api-port", str(port), "--node-port", str(free_port()), "--default-model", MODEL,
    "--temp", "0.0", "--max-generate-tokens", str(MAX_TOKENS), *flags,
  ]  # fmt: skip
  t_start = time.perf_counter()
  with open(log_path, "w") as log:
    proc = subprocess.Popen(cmd, env=child_env(rehearsal, expect_chips, XOT_TPU_MODEL_DIR=str(checkpoint_path(rehearsal, seed)), **(env or {})), cwd=ROOT, stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
  try:
    while True:  # healthcheck answers once the API is up; the model loads on the first request
      if proc.poll() is not None:
        raise RuntimeError(f"{name}: daemon exited {proc.returncode} at start-up:\n{log_path.read_text()[-3000:]}")
      try:
        http_json(f"{base}/healthcheck", timeout=2.0)
        break
      except OSError:
        if time.perf_counter() - t_start > 300:
          raise RuntimeError(f"{name}: daemon not healthy after 300 s:\n{log_path.read_text()[-3000:]}") from None
        time.sleep(0.5)
    device = http_json(f"{base}/v1/programs")["devices"]
    facts = {"platform": device[0]["platform"], "kind": device[0]["kind"], "count": len(device)}
    if facts["platform"] != "tpu" and not rehearsal:
      raise RuntimeError(f"{name}: the serving process runs on {facts}, not on a TPU")
    if facts["count"] != expect_chips:
      raise RuntimeError(f"{name}: the serving process sees {facts['count']} devices, this run is for {expect_chips}")
    start_s = time.perf_counter() - t_start
    ensure_checkpoint(rehearsal, seed)  # the daemon reads XOT_TPU_MODEL_DIR at the first request
    t_up = time.perf_counter()

    blocking = chat(base, PROMPT, stream=False)  # weight load + cold compiles land here
    t_first = time.perf_counter()
    check_answer(f"{name}/blocking", blocking)
    streamed = chat(base, PROMPT, stream=True)
    check_answer(f"{name}/streaming", streamed)
    if streamed["text"].split() != blocking["text"].split():
      raise RuntimeError(f"{name}: greedy blocking and streamed answers differ:\n  {blocking['text']!r}\n  {streamed['text']!r}")

    answers: list = [None] * len(CONCURRENT_PROMPTS)

    def ask(i: int) -> None:
      try:
        answers[i] = chat(base, CONCURRENT_PROMPTS[i], stream=bool(i % 2))
      except Exception as e:  # noqa: BLE001 — re-raised below, in the main thread
        answers[i] = e

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(len(answers))]
    for t in threads:
      t.start()
    for t in threads:
      t.join(900)
    for i, a in enumerate(answers):
      if not isinstance(a, dict):
        raise RuntimeError(f"{name}/concurrent[{i}]: {a!r}")
      check_answer(f"{name}/concurrent[{i}]", a)
    t_served = time.perf_counter()

    programs = http_json(f"{base}/v1/programs")
    built = {f: st for f, st in programs["families"].items() if st["compiles"] > 0}
    want = families + (tpu_kernels if facts["platform"] == "tpu" else ())
    missing = [w for w in want if not any(f.startswith(w) for f in built)]  # str.startswith takes a tuple too
    if missing:
      raise RuntimeError(f"{name}: /v1/programs lists no build of {missing}; built: {sorted(built)}")
    return {
      "phase": name,
      "ok": True,
      "device": facts,
      "start_s": round(start_s, 2),
      "first_request_s": round(t_first - t_up, 2),  # weight load + the cold compiles
      "serve_s": round(t_served - t_first, 2),
      "tokens": blocking["tokens"] + streamed["tokens"] + sum(a["tokens"] for a in answers),
      "finish_reasons": sorted({blocking["finish_reason"], streamed["finish_reason"], *(a["finish_reason"] for a in answers)}),
      "blocking_equals_streaming": True,
      "concurrent_equals_blocking": answers[0]["text"].split() == blocking["text"].split(),
      "answers": [blocking["text"], *(a["text"] for a in answers)],
      "families_built": sorted(built),
      "compiles": programs["totals"]["compiles"],
      "compile_s": round(sum(st["compile_s"] for st in built.values()), 2),
      "xla_compile_s": round(sum(st["xla_compile_s"] for st in programs["families"].values()), 2),
      "devices": programs["devices"],
    }
  except BaseException:
    stop(proc)
    print(f"[chip_smoke] {name} failed; the daemon's log ends:\n{log_path.read_text()[-6000:]}", file=sys.stderr, flush=True)
    raise
  finally:
    stop(proc)


def agreement(a: list[str], b: list[str]) -> float:
  """Share of generated tokens on which two runs of the same requests agree
  (position by position, over all answers)."""
  pairs = [(x, y) for ta, tb in zip(a, b) for x, y in zip(ta.split(), tb.split())]
  total = sum(max(len(ta.split()), len(tb.split())) for ta, tb in zip(a, b))
  return sum(x == y for x, y in pairs) / max(total, 1)


def report(phase: dict) -> dict:
  """One line per phase; of the answers the line carries the first."""
  emit(**{k: v for k, v in phase.items() if k != "answers"}, answer=phase["answers"][0])
  return phase


def one_chip(seed: int, rehearsal: bool) -> dict:
  emit(**run_kernels_phase(rehearsal))
  report(serve_phase("solo", seed, rehearsal, 1, families=SOLO_FAMILIES, tpu_kernels=TPU_SOLO_KERNELS))
  # The documented serving settings: at the bare batched defaults (4 slots,
  # bf16 KV) the dispatch table answers "gather" and the paged kernel would
  # never be built.
  batched = serve_phase(
    "batched", seed, rehearsal, 1, env={"XOT_TPU_BATCHED": "1", "XOT_TPU_BATCH_SLOTS": "16", "XOT_TPU_KV_QUANT": "int8"},
    families=BATCHED_FAMILIES, tpu_kernels=TPU_BATCHED_KERNELS,
  )  # fmt: skip
  return report(batched)["device"]


def four_chips(seed: int, rehearsal: bool) -> dict:
  """Only what exists across chips, and what it is compared with."""
  one = report(serve_phase("one_device", seed, rehearsal, 4, env={"XOT_TPU_LOCAL_MESH": "0"}, families=SOLO_FAMILIES))
  pp = serve_phase("pp4", seed, rehearsal, 4, flags=("--pp", "4"))
  pp["equals_one_device"] = [a.split() for a in pp["answers"]] == [a.split() for a in one["answers"]]
  report(pp)
  tp = serve_phase("tp_default", seed, rehearsal, 4, families=SOLO_FAMILIES)
  tp["agreement_with_one_device"] = round(agreement(tp["answers"], one["answers"]), 4)
  report(tp)
  if not pp["equals_one_device"]:
    raise RuntimeError(f"pp4 answers differ from the one-device answers:\n  {pp['answers']}\n  {one['answers']}")
  # Weights and cache must really be spread: every chip holds a fair part of
  # the weights under pp and tp, and one chip holds them all without a mesh.
  if not rehearsal:  # the CPU backend reports no memory_stats
    weight_bytes = json.loads((checkpoint_path(rehearsal, seed) / "complete.json").read_text())["weight_bytes"]
    share = weight_bytes // 8
    for phase in (pp, tp):
      held = [d["bytes_in_use"] for d in phase["devices"]]
      if min(held) < share:
        raise RuntimeError(f"{phase['phase']}: a chip holds under 1/8 of the weights: bytes_in_use={held}")
    held = [d["bytes_in_use"] for d in one["devices"]]
    if held[0] < weight_bytes or max(held[1:]) > share:
      raise RuntimeError(f"one_device: expected everything on device 0: bytes_in_use={held}")
  return tp["device"]


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
  ap.add_argument("--chips", type=int, choices=(1, 4), default=1, help="4: only the cross-chip phases (one device, --pp 4, tp default)")
  ap.add_argument("--seed", type=int, default=0, help="seed of the random checkpoint")
  ap.add_argument("--cpu-rehearsal", action="store_true", help="tiny width on the CPU backend; never a chip result")
  ap.add_argument("--child-kernels", action="store_true", help=argparse.SUPPRESS)
  args = ap.parse_args()
  if args.child_kernels:
    kernels_child(args.cpu_rehearsal)
    return 0
  t0 = time.perf_counter()
  WORK.mkdir(parents=True, exist_ok=True)
  try:
    device = (one_chip if args.chips == 1 else four_chips)(args.seed, args.cpu_rehearsal)
  except Exception as e:  # noqa: BLE001 — any failed phase fails the run, with its reason as the verdict
    emit(ok=False, error=f"{type(e).__name__}: {e}", wall_s=round(time.perf_counter() - t0, 2))
    return 1
  emit(phase="total", ok=True, wall_s=round(time.perf_counter() - t0, 2))
  emit(ok=True, device=device)
  return 0


if __name__ == "__main__":
  sys.exit(main())
