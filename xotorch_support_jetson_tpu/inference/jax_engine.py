"""The JAX/TPU inference engine.

Role parity with reference ``inference/torch/sharded_inference_engine.py``
(``TorchDynamicShardInferenceEngine``): device-resident sharded model,
encode/sample/infer_tensor/decode contract, per-request sessions, all heavy
work serialized on one executor thread off the event loop (:46). Designed
differently where TPU demands it:

- **Static shapes.** The reference grows tokens/masks per step in Python
  (``:291-298,356-359``); here prefill pads to a bucket and decode is a
  fixed ``[B,1]`` jitted step, so XLA compiles each shape exactly once.
- **Slot-indexed donated KV cache.** Preallocated once per request at a
  fixed ``max_seq``; the cache pytree is donated into each jitted call so
  decode updates happen in-place in HBM (no per-request ``setup_caches``
  and no "drop the whole model on OOM" recovery, cf. ``:85-106,330-334`` —
  memory is budgeted ahead of time).
- **Wire state is O(1).** Only tokens + positions travel between pipeline
  peers (see inference/state.py); last-shard output is the already-gathered
  ``[B, vocab]`` logits row, not the padded ``[B, S, V]`` tensor.
"""

from __future__ import annotations

import asyncio
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..models.decoder import init_kv_cache, shard_forward
from ..utils.helpers import DEBUG
from ..utils.metrics import metrics
from .engine import InferenceEngine
from .shard import Shard
from .state import InferenceState

DEFAULT_MAX_SEQ = int(os.getenv("XOT_TPU_MAX_SEQ", "4096"))
PREFILL_BUCKET = 128


def _round_up(n: int, multiple: int) -> int:
  return ((n + multiple - 1) // multiple) * multiple


def _tokenizer_fingerprint(d: Path) -> dict[str, str] | None:
  """Best-effort tokenizer identity for a checkpoint dir: per-artifact
  digests over the VOCABULARY files (tokenizer.json / sentencepiece model /
  vocab+merges). Kept per-file so two dirs compare only on the artifacts
  BOTH ship — identical tokenizers serialized with different artifact sets
  (e.g. tokenizer.json alone vs +tokenizer.model) must not read as a
  mismatch. ``tokenizer_config.json`` is deliberately excluded —
  chat-template and padding metadata differ across same-tokenizer model
  families. None when no artifact exists (nothing to compare)."""
  import hashlib

  digests = {}
  for name in ("tokenizer.json", "tokenizer.model", "vocab.json", "merges.txt"):
    f = d / name
    if f.is_file():
      digests[name] = hashlib.blake2b(f.read_bytes(), digest_size=16).hexdigest()
  return digests or None


def _tokenizers_differ(fp_a: dict[str, str] | None, fp_b: dict[str, str] | None) -> bool:
  """True only when some artifact PRESENT IN BOTH checkpoints differs."""
  if not fp_a or not fp_b:
    return False
  common = fp_a.keys() & fp_b.keys()
  return bool(common) and any(fp_a[n] != fp_b[n] for n in common)


# --- jitted steps (cfg/shard static; cache donated so decode is in-place) ---


@partial(jax.jit, static_argnames=("cfg", "shard"), donate_argnums=(4,))
def _prefill(params, cfg, shard, x, kv_cache, prompt_len, adapter_ids=None):
  B = x.shape[0]
  S = x.shape[1]
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  out, kv_cache = shard_forward(params, cfg, shard, x, positions, kv_cache, adapter_ids=adapter_ids)
  if shard.is_last_layer:
    idx = (prompt_len - 1).reshape(B, 1, 1)
    out = jnp.take_along_axis(out, jnp.broadcast_to(idx, (B, 1, out.shape[-1])), axis=1)[:, 0, :]
  return out, kv_cache


@partial(jax.jit, static_argnames=("cfg", "shard"), donate_argnums=(4,))
def _decode_step(params, cfg, shard, x, kv_cache, pos, adapter_ids=None):
  B = x.shape[0]
  positions = pos.reshape(B, 1)
  out, kv_cache = shard_forward(params, cfg, shard, x, positions, kv_cache, adapter_ids=adapter_ids)
  if shard.is_last_layer:
    out = out[:, 0, :]
  return out, kv_cache


class _Session:
  __slots__ = (
    "kv_cache", "curr_pos", "prompt_len", "max_seq", "next_token_dev", "epoch", "prompt_np", "draft_cache",
    "spec_seed_dev", "spec_pos_dev", "spec_known_pos", "spec_inflight_slots",
    "ngram_index", "ngram_unread", "ngram_ewma", "ngram_gamma", "adapter_slot",
  )

  def __init__(self, kv_cache, max_seq: int, epoch: int = 0) -> None:
    self.kv_cache = kv_cache
    self.curr_pos = 0
    self.prompt_len = 0
    self.max_seq = max_seq
    self.next_token_dev = None  # [B,1] device array chaining fused chunks
    self.epoch = epoch  # replay epoch (elastic recovery, node._retry_request)
    self.prompt_np = None  # prompt token ids (speculative draft prefill)
    self.draft_cache = None  # lazily-built draft KV cache (speculative mode)
    # Streaming speculative chain (models/decoder.py fused_speculative_chunk):
    # seed token and position stay ON DEVICE so chunk N+1 dispatches from
    # chunk N's lazy outputs with no host round-trip. The host tracks a
    # CONFIRMED position (updated as chunks are read) plus the summed
    # worst-case slot consumption of dispatched-but-unread chunks (each
    # chunk's own steps+gamma+1 — buckets can differ per chunk) for
    # conservative cache-room checks.
    self.spec_seed_dev = None
    self.spec_pos_dev = None
    self.spec_known_pos = 0
    self.spec_inflight_slots = 0
    # Draft-free n-gram chain (ISSUE 12): the suffix index over this
    # session's prompt+generated history (inference/ngram.py), and whether
    # an n-gram chunk is dispatched-but-unread. Unlike the draft spec chain,
    # n-gram chunks can NEVER pipeline: the next proposal keys on the tokens
    # this one emits, so the engine answers the node's speculative
    # dispatch-ahead with None and the chunk loop degrades to synchronous.
    # The acceptance EWMA and live depth are PER SESSION (unlike the model
    # draft's engine-level pair): n-gram acceptance is a property of the
    # TEXT being generated, not of the model — one non-repetitive response
    # must not collapse speculation for the repetitive session that follows
    # (the batched path's per-slot state makes the same choice). -1 depth =
    # not initialized yet (set from the engine cap at chain start).
    self.ngram_index = None
    self.ngram_unread = False
    self.ngram_ewma = None
    self.ngram_gamma = -1
    # Multi-LoRA (ISSUE 15): this session's pinned adapter slot (0 = base).
    # Solo sessions apply the SAME indexed hook as the batched rows
    # (adapter_ids=[slot] through _prefill/fused_decode/fused_generate);
    # spec/n-gram chunk modes step aside for adapter sessions — their
    # programs verify against the base target.
    self.adapter_slot = 0


class JaxShardedInferenceEngine(InferenceEngine):
  """In-slice parallel by default: when the host exposes multiple chips, the
  engine shards its shard's params megatron-style over a local tp×dp mesh
  (parallel/mesh.py) and jit/GSPMD inserts the ICI collectives. The cluster
  ring (orchestration) and the in-slice mesh compose: each ring node runs its
  layer range across all of its own chips.
  """

  can_generate_images = True

  def __init__(self, shard_downloader=None, max_seq_len: int | None = None, seed: int = 0, use_local_mesh: bool | None = None, quant: str | None = None, pp: int | None = None, spec_decode: str | None = None):
    super().__init__()
    self.shard_downloader = shard_downloader
    self.shard: Shard | None = None
    self.params = None
    self.cfg = None
    self.tokenizer = None
    self.max_seq_len = max_seq_len or DEFAULT_MAX_SEQ
    # Whether the serving cap was chosen by the operator (constructor arg or
    # XOT_TPU_MAX_SEQ) vs defaulted — longrope models default their cap to the
    # pre-scaling original context for exact HF short-context parity.
    self._max_seq_explicit = max_seq_len is not None or os.getenv("XOT_TPU_MAX_SEQ") is not None
    # XOT_TPU_QUANT=int8 loads ANY registry model weight-quantized (decode is
    # HBM-bound: ~half the weight bytes ≈ ~half the per-token latency). The
    # reference instead ships separate -8bit checkpoints (models.py:29).
    self.quant = quant if quant is not None else (os.getenv("XOT_TPU_QUANT") or None)
    # XOT_TPU_SPEC_DECODE=int8: greedy speculative decoding with a
    # self-speculative int8 draft (models/decoder.py
    # fused_speculative_generate) on the non-streaming fast path. Exact:
    # output is token-identical to plain greedy.
    self.spec_decode = spec_decode if spec_decode is not None else (os.getenv("XOT_TPU_SPEC_DECODE") or None)
    self.spec_gamma = int(os.getenv("XOT_TPU_SPEC_GAMMA", "4"))
    # Acceptance-adaptive depth (ISSUE 7): the LIVE gamma starts at
    # spec_gamma and walks the policy table (inference/paging.py
    # spec_adapt_gamma) on every measured chunk/oneshot acceptance — floor 0
    # means the solo spec path hands the stream to plain decode instead of
    # losing to it (the 149-vs-212 tok/s inversion becomes a fallback), and
    # a gamma-1 probe runs every XOT_TPU_SPEC_REPROBE plain dispatches so a
    # draft that starts paying again re-earns its depth.
    self._spec_ewma = None
    self._spec_gamma_live = self.spec_gamma
    self._spec_plain_streak = 0
    self._spec_reprobe = int(os.getenv("XOT_TPU_SPEC_REPROBE", "64"))
    # Draft-free n-gram proposer (ISSUE 12): with XOT_TPU_SPEC_DECODE set
    # but NO draft pair loaded (XOT_TPU_SPEC_DECODE=ngram, or a draft whose
    # checkpoint/vocab check failed), streaming chunks speculate from the
    # session's own prompt+generated history (inference/ngram.py) — same
    # accept rule, zero draft weights, zero draft KV. The EWMA/depth state
    # lives on the SESSION (n-gram acceptance is a property of the text,
    # not the model); only the knobs are engine-level.
    from .ngram import ngram_enabled, ngram_knobs

    self._spec_ngram_on = ngram_enabled()
    self.spec_ngram_n, self.spec_ngram_max = ngram_knobs()
    self._draft_params = None
    # Multi-LoRA serving (ISSUE 15): the adapter registry built by
    # enable_multi_lora (None = base-only serving). Model swaps reset it —
    # its geometry/install hook target one params tree's stacked leaves.
    self.adapter_registry = None
    # Cross-model draft (XOT_TPU_SPEC_DRAFT=<registry-id-or-dir>): a second,
    # SMALLER model drafts for the target. None ⇒ int8 self-draft (same cfg).
    self._draft_cfg = None
    self._draft_shard = None
    self.use_local_mesh = use_local_mesh if use_local_mesh is not None else os.getenv("XOT_TPU_LOCAL_MESH", "1") == "1"
    # XOT_TPU_PP=N serves the loaded layer range as N pipeline stages over the
    # local chips (parallel/pp_serving.py) — the in-slice rendering of the
    # reference's layer-split serving; remaining chips go to tp.
    self.pp = pp if pp is not None else int(os.getenv("XOT_TPU_PP", "0") or 0)
    self._pp = None
    self._batch_ops = None
    self.diffusion = None  # DiffusionPipeline when an SD card is loaded
    self.mesh = None
    self.sessions: dict[str, _Session] = {}
    # One worker thread serializes all device work off the asyncio loop —
    # same concurrency discipline as the reference engine (:46).
    self.executor = ThreadPoolExecutor(max_workers=1)
    self._seed = seed
    self._key = None
    # Guards the PRNG chain's read-split-write. Device work serializes on the
    # one executor thread, but key SPLITS are pure host state: the batch
    # scheduler splits on the event-loop thread before dispatch (so the
    # lookahead pipeline never touches the chain from the worker thread),
    # while single-stream paths split wherever their sync helper runs — the
    # lock makes any interleaving of the two yield distinct subkeys.
    self._key_lock = threading.Lock()
    self._shard_lock = asyncio.Lock()

  def split_key(self):
    """Split the engine PRNG chain and return a fresh subkey (thread-safe).

    Every consumer of ``self._key`` must go through here — a bare
    ``self._key, sub = jax.random.split(self._key)`` from two threads can
    read the same chain state and hand two dispatches the SAME subkey
    (identical samples for different requests)."""
    with self._key_lock:
      if self._key is None:
        self._key = jax.random.PRNGKey(self._seed)
      self._key, sub = jax.random.split(self._key)
      return sub

  # ---------------------------------------------------------------- loading

  async def ensure_shard(self, shard: Shard) -> None:
    async with self._shard_lock:
      if self.shard == shard:
        return
      if self.shard_downloader is None:
        raise RuntimeError("no shard downloader configured and shard not preloaded; use load_test_model() for tests")
      model_dir = await self.shard_downloader.ensure_shard(shard, type(self).__name__)
      await asyncio.get_event_loop().run_in_executor(self.executor, self._load_shard_sync, shard, model_dir)
      await self._load_tokenizer(shard)

  def _load_shard_sync(self, shard: Shard, model_dir) -> None:
    from ..models.config import load_model_config
    from ..models.loader import load_shard_weights

    # A model swap invalidates the adapter registry: its geometry/install
    # hook target the OLD params' stacked leaves (XOT_TPU_LORA_DIR
    # re-enables against the new model below).
    self.adapter_registry = None

    # Diffusers-format checkpoints carry model_index.json at the root; they
    # take the image-generation path (the reference's SD special case,
    # reference node.py:116, is dead code — this one runs).
    if (Path(model_dir) / "model_index.json").exists():
      self._load_diffusion_sync(shard, model_dir)
      return
    self.diffusion = None

    cfg = load_model_config(model_dir)
    # Clamp the config's max_seq_len to the engine's serving cap: cache
    # allocation uses it, and longrope (phi-3/4) selects its short vs long
    # frequency factors from it (ops/rope.py) — a cap within the original
    # context keeps exact HF short-context rope parity.
    from dataclasses import replace as _dc_replace

    cfg = _dc_replace(cfg, max_seq_len=self._serving_cap(cfg))
    # Registry layer counts can disagree with an arbitrary local checkpoint
    # (XOT_TPU_MODEL_DIR override): remap the shard's layer fractions onto the
    # checkpoint's real depth.
    eff = shard
    if cfg.n_layers != shard.n_layers:
      start = round(shard.start_layer * cfg.n_layers / shard.n_layers)
      end = round((shard.end_layer + 1) * cfg.n_layers / shard.n_layers) - 1
      eff = Shard(shard.model_id, start, max(start, end), cfg.n_layers)
    # Ahead-of-time HBM budget (SURVEY §7): refuse BEFORE reading weights if
    # this (remapped) shard cannot fit the local chips under the plan the
    # engine will actually build (_planned_mesh — single source of truth).
    self._check_hbm_budget(self._planned_mesh(cfg), cfg=cfg, shard=eff)
    self.params = load_shard_weights(model_dir, cfg, eff)
    if self.quant:
      from ..models.quantize import quantize_params

      self.params = quantize_params(self.params, self.quant)
    self.cfg = cfg
    self._note_experts()
    self.shard = shard
    self._effective_shard = eff
    self._vision_params = None  # set by _split_vision_params in mesh modes
    self._train_state = None  # model-specific jits/opt state (train/trainer.py)
    self._mesh_eval_fn = None
    self._maybe_shard_over_local_mesh()
    # Build the draft AFTER mesh placement so the int8 copy derives from the
    # already-sharded params (its leaves inherit their shardings).
    self._maybe_build_draft()
    self.sessions.clear()
    self._drop_batched_server()  # pooled cache is model-specific
    self._key = jax.random.PRNGKey(self._seed)
    self._model_dir = Path(model_dir)
    self._maybe_load_adapter_dir()
    if DEBUG >= 1:
      print(f"[jax_engine] loaded {shard} from {model_dir}" + (f" over mesh {self.mesh.shape}" if self.mesh else ""))

  def _maybe_load_adapter_dir(self) -> None:
    """``XOT_TPU_LORA_DIR``: enable multi-LoRA at model load and register
    every ``*.npz`` adapter checkpoint in the directory (name = file stem,
    train/lora.py leaf format — see inference/adapters.py). Best-effort: a
    bad adapter file is skipped with a warning, never a failed model load;
    mesh/MLA configurations (which refuse enable_multi_lora) just log."""
    lora_dir = os.getenv("XOT_TPU_LORA_DIR")
    if not lora_dir or getattr(self, "adapter_registry", None) is not None:
      return
    if not (self._effective_shard.is_first_layer and self._effective_shard.is_last_layer):
      return  # partial ring shards serve hidden states; no adapter hook
    try:
      reg = self.enable_multi_lora()
    except (RuntimeError, ValueError) as e:
      print(f"[jax_engine] XOT_TPU_LORA_DIR set but multi-LoRA unavailable: {e}")
      return
    if reg is None:
      return  # XOT_TPU_LORA=0
    for path in sorted(Path(lora_dir).glob("*.npz")):
      try:
        reg.register(path.stem, path=str(path))
      except Exception as e:  # noqa: BLE001 — one bad adapter must not sink the load
        print(f"[jax_engine] skipping adapter {path.name}: {e}")

  def _maybe_build_draft(self, calibrate: bool = True) -> None:
    """Speculative draft. Two modes (VERDICT r4 #3):

    - ``XOT_TPU_SPEC_DRAFT=<registry-id-or-dir>``: a second, SMALLER model
      (int8-quantized at load) drafts for the target — the configuration
      where speculation mathematically wins (the 1B draft decodes ~4× faster
      than the 8B target; the measured self-draft ratio is only ~1.6×).
      Compatibility checks at load: vocab SIZE equality always, plus
      tokenizer-artifact identity when both checkpoints carry tokenizer
      files. Equal-sized but differently-TOKENIZING pairs with no artifacts
      to compare slip through — greedy verification keeps the output exact
      regardless; acceptance just collapses.
    - otherwise (``XOT_TPU_SPEC_DECODE=int8`` alone): the int8 self-draft.

    Requires a full-model shard (sampling feeds the next embed).
    ``calibrate=False`` (test-model injection) skips the load-time A/B so
    tests exercise the speculative path deterministically."""
    self._draft_params = None
    self._draft_cfg = None
    self._draft_shard = None
    # A new draft is a new acceptance distribution: reset the adaptive state.
    # (The n-gram state needs no reset here — it lives per session, and a
    # model swap drops every session with the cache it invalidates.)
    self._spec_ewma = None
    self._spec_gamma_live = self.spec_gamma
    self._spec_plain_streak = 0
    eff = getattr(self, "_effective_shard", None)
    if self.spec_decode != "int8" or eff is None or not (eff.is_first_layer and eff.is_last_layer) or self.params is None:
      return
    draft_spec = os.getenv("XOT_TPU_SPEC_DRAFT")
    if draft_spec:
      self._build_cross_draft(draft_spec)
    else:
      if self.quant:  # self-draft would equal the target — no speedup, skip
        return
      from ..models.quantize import quantize_params

      self._draft_params = quantize_params(self.params)
    if self._draft_params is not None and calibrate:
      self._maybe_calibrate_spec()

  def _build_cross_draft(self, spec: str) -> None:
    """Load the cross-model draft named by ``XOT_TPU_SPEC_DRAFT`` — a local
    checkpoint dir or a registry id whose snapshot is already downloaded
    (the engine never downloads synchronously at load; run the model once or
    pre-seed XOT_HOME/downloads)."""
    from ..models.config import load_model_config
    from ..models.loader import load_shard_weights
    from ..models.quantize import quantize_params

    d = Path(spec)
    if not (d / "config.json").exists():
      from ..download.downloader import get_models_dir, repo_to_dirname
      from ..registry import get_repo

      repo = get_repo(spec, self.__class__.__name__)
      if repo:
        cand = get_models_dir() / repo_to_dirname(repo)
        if (cand / "config.json").exists():
          d = cand
    if not (d / "config.json").exists():
      print(f"[jax_engine] XOT_TPU_SPEC_DRAFT={spec!r}: no local checkpoint found; speculative draft disabled (download the draft model first)")
      return
    cfg_d = load_model_config(d, dtype=self.cfg.dtype)
    if cfg_d.vocab_size != self.cfg.vocab_size:
      print(
        f"[jax_engine] XOT_TPU_SPEC_DRAFT={spec!r}: draft vocab {cfg_d.vocab_size} != target {self.cfg.vocab_size} — "
        "draft tokens are target-vocab ids, so this pair cannot speculate; draft disabled"
      )
      return
    # Vocab-size equality is a weak tokenizer-identity proxy: when both
    # checkpoints carry tokenizer artifacts, compare them too — a draft that
    # tokenizes DIFFERENTLY proposes wrong ids (greedy verify stays exact;
    # acceptance silently collapses to ~0, i.e. pure slowdown).
    target_dir = getattr(self, "_model_dir", None)
    fp_t = _tokenizer_fingerprint(Path(target_dir)) if target_dir else None
    fp_d = _tokenizer_fingerprint(d)
    if _tokenizers_differ(fp_t, fp_d):
      print(
        f"[jax_engine] XOT_TPU_SPEC_DRAFT={spec!r}: draft tokenizer artifacts differ from the target's "
        "(same vocab size, different vocabulary) — the draft would propose wrong ids; draft disabled"
      )
      return
    shard_d = Shard(spec, 0, cfg_d.n_layers - 1, cfg_d.n_layers)
    # int8 draft: drafting is decode-bound like everything else — the whole
    # point of the small model is fewer bytes per proposed token.
    draft = quantize_params(load_shard_weights(d, cfg_d, shard_d))
    if self.mesh is not None and self._pp is None:
      # The self-draft inherits shardings from the already-placed target;
      # a cross-model draft is loaded fresh and must be placed itself. The
      # target-generic specs can be indivisible for the draft's geometry
      # (head/hidden axes vs mesh tp) — that must DEGRADE like every other
      # _build_cross_draft failure mode, not abort the engine load: fall
      # back to a replicated draft (drafting is small-model decode; the
      # replicated copy costs HBM, not correctness).
      from ..parallel.mesh import shard_params

      try:
        draft = shard_params(draft, self.mesh)
      except Exception as e:  # noqa: BLE001
        print(f"[jax_engine] XOT_TPU_SPEC_DRAFT={spec!r}: draft sharding failed ({e!r}); keeping the draft replicated")
    self._draft_params = draft
    self._draft_cfg = cfg_d
    self._draft_shard = shard_d
    if DEBUG >= 1:
      print(f"[jax_engine] cross-model speculative draft: {spec} ({cfg_d.n_layers}L dim={cfg_d.dim}, int8) drafting for {self.shard.model_id}")

  def _maybe_calibrate_spec(self) -> None:
    """Gate speculative decoding on MEASURED benefit (VERDICT r2 #4): low
    acceptance (poorly-quantizing or random-like weights) makes speculation
    strictly slower than plain decode, so the mode must not advertise itself
    on hope. A quick on-device A/B at load disables it with a log line when
    plain wins. Decode is weight-bandwidth-bound, so a SMALL calibration
    cache (tiny compiles, tiny HBM) still measures the serving-relevant
    ratio; caches go through _place_cache so multi-chip layouts time the
    real sharded execution. Skipped on CPU (tests/dev) and via
    XOT_TPU_SPEC_AUTOCAL=0; the demotion clears only the per-MODEL draft,
    so the next loaded model recalibrates."""
    if jax.devices()[0].platform == "cpu" or os.getenv("XOT_TPU_SPEC_AUTOCAL", "1") in ("0", "false"):
      return
    import time as _time

    from ..models.decoder import fused_decode, fused_speculative_generate

    eff = self._effective_shard
    cfg = self.cfg
    n = 64
    max_seq = min(256, self.max_seq_len, cfg.max_seq_len)
    tok = jnp.ones((1, 1), jnp.int32)

    def time_plain() -> float:
      cache = self._place_cache(init_kv_cache(cfg, eff.n_shard_layers, 1, max_seq))
      toks, cache = fused_decode(self.params, cfg, eff, tok, cache, jnp.zeros((1,), jnp.int32), n)
      _ = np.asarray(toks)  # warm compile + honest fetch
      best = 0.0
      for start in (n, 2 * n):  # best-of-2: one readback's jitter must not decide the verdict
        t0 = _time.perf_counter()
        toks, cache = fused_decode(self.params, cfg, eff, tok, cache, jnp.full((1,), start, jnp.int32), n)
        _ = np.asarray(toks)
        best = max(best, n / (_time.perf_counter() - t0))
      return best

    def time_spec() -> float:
      cfg_d = self._draft_cfg or cfg
      shard_d = self._draft_shard or eff

      def run() -> float:
        ct = self._place_cache(init_kv_cache(cfg, eff.n_shard_layers, 1, max_seq))
        cd = self._place_cache(init_kv_cache(cfg_d, shard_d.n_shard_layers, 1, max_seq), cfg=cfg_d)
        t0 = _time.perf_counter()
        buf, m, rounds, ct, cd = fused_speculative_generate(
          self.params, cfg, eff, self._draft_params, cfg_d, shard_d, tok, ct, cd, 0, n, gamma=self.spec_gamma, eos_ids=(-1,)
        )
        _ = np.asarray(buf)
        return min(int(np.asarray(m)), n) / (_time.perf_counter() - t0)

      run()  # warm compile
      return max(run(), run())

    try:
      plain_tok_s, spec_tok_s = time_plain(), time_spec()
    except Exception as e:  # noqa: BLE001 — calibration must never block serving
      print(f"[jax_engine] spec calibration FAILED on {jax.devices()[0].device_kind} ({e!r}); speculative mode stays on, unmeasured")
      return
    if spec_tok_s < 0.95 * plain_tok_s:
      print(
        f"[jax_engine] speculative decode DISABLED for this model: measured {spec_tok_s:.1f} tok/s vs plain "
        f"{plain_tok_s:.1f} (low draft acceptance); set XOT_TPU_SPEC_AUTOCAL=0 to force it"
      )
      self._draft_params = None
    elif DEBUG >= 1:
      print(f"[jax_engine] speculative decode kept: {spec_tok_s:.1f} vs plain {plain_tok_s:.1f} tok/s")

  def _serving_cap(self, cfg) -> int:
    """The effective serving max_seq_len for a loaded config.

    Longrope (phi-3/4) selects short vs long frequency factors from this cap
    (ops/rope.py, static per loaded model): unless the operator chose a cap
    explicitly, default it to the pre-scaling original context so the common
    short-context case keeps exact HF parity; raising XOT_TPU_MAX_SEQ above
    original_max_position_embeddings opts into the long factors.
    """
    cap = min(self.max_seq_len, cfg.max_seq_len)
    if not self._max_seq_explicit:
      from ..models.config import LongRopeScaling

      if isinstance(cfg.rope_scaling, LongRopeScaling):
        cap = min(cap, cfg.rope_scaling.original_max_position_embeddings)
    return cap

  def _planned_mesh(self, cfg=None):
    """The serving plan this engine will build for the loaded model — the
    SINGLE source of truth shared by the pre-load HBM check and
    _maybe_shard_over_local_mesh (so the validated plan is the built plan)."""
    from ..parallel.mesh import MeshPlan, inference_plan, pow2_degree

    cfg = cfg or self.cfg
    n = len(jax.devices())
    sp = int(os.getenv("XOT_TPU_SP", "0") or 0)
    if sp > 1:
      return MeshPlan(sp=sp, tp=pow2_degree(max(n // sp, 1), cfg.n_heads))
    if self.pp > 1:
      return MeshPlan(pp=self.pp, tp=pow2_degree(max(n // self.pp, 1), cfg.n_heads))
    if self.use_local_mesh and n > 1:
      return inference_plan(n, n_heads=cfg.n_heads, n_experts=cfg.n_experts or 0)
    return MeshPlan()

  def _check_hbm_budget(self, plan, cfg=None, shard=None) -> None:
    """Refuse a serving plan that cannot fit BEFORE any compile (SURVEY §7
    ahead-of-time budgeting; the reference dropped the model after the OOM).
    No-op when the backend doesn't report HBM (CPU/virtual meshes) or when
    disabled via XOT_TPU_HBM_CHECK=0."""
    if os.getenv("XOT_TPU_HBM_CHECK", "1") in ("0", "false"):
      return
    from ..parallel.hbm_planner import check_plan, device_hbm_bytes

    hbm = device_hbm_bytes()
    if hbm is None:
      return
    cfg = cfg or self.cfg
    shard = shard or getattr(self, "_effective_shard", self.shard)
    max_seq = min(self.max_seq_len, cfg.max_seq_len)
    check_plan(cfg, plan, len(jax.devices()), hbm, batch=1, max_seq=max_seq, quant=self.quant, shard=shard)
    if DEBUG >= 1:
      print(f"[jax_engine] HBM budget ok for plan {plan.describe()}")

  def _split_vision_params(self) -> None:
    """Keep the llava tower + projector OUT of a serving-mesh layout (they
    are tiny next to the decoder and run once per request): the multimodal
    path encodes images with them eagerly and hands the merged embeddings
    to the mesh prefill as hidden input — this is what lifts the former
    PP/SP vision refusals (VERDICT r3 #4)."""
    if self.cfg.vision is None or self.params is None:
      return
    self._vision_params = {k: self.params[k] for k in ("vision", "projector") if k in self.params}
    self.params = {k: v for k, v in self.params.items() if k not in ("vision", "projector")}

  def _vision_leaves(self) -> dict:
    vp = getattr(self, "_vision_params", None)
    if vp:
      return vp
    return {"vision": self.params["vision"], "projector": self.params["projector"]}

  def _serving_embed(self):
    """The embedding table wherever the serving mode placed it."""
    if self._pp is None:
      return self.params["embed"]
    from ..parallel.pp_serving import PPServing

    return self._pp.head["embed"] if isinstance(self._pp, PPServing) else self._pp.params["embed"]

  def _maybe_shard_over_local_mesh(self) -> None:
    sp = int(os.getenv("XOT_TPU_SP", "0") or 0)
    if sp > 1:
      # Sequence-parallel serving: the KV cache shards over sp — the
      # long-context mode (cache read splits sp ways, capacity × sp).
      # Entry-point-compatible with PPServing, so it rides the same slot.
      from ..parallel.mesh import MeshPlan, build_mesh
      from ..parallel.sp_serving import SPServing

      n = len(jax.devices())
      if n < sp:
        raise ValueError(f"XOT_TPU_SP={sp} but only {n} local devices")
      self._split_vision_params()
      if min(self.max_seq_len, self.cfg.max_seq_len) % sp:
        raise ValueError(f"serving max_seq must be divisible by XOT_TPU_SP={sp}")
      from ..parallel.mesh import pow2_degree

      # Leftover chips go to tp: weights shard megatron-style over tp while
      # the cache shards over sp, so long context stops paying sp x the
      # weight HBM (VERDICT r2 weak #3).
      plan = self._planned_mesh()
      self._check_hbm_budget(plan)
      self.mesh = build_mesh(plan)
      self._gate_kernels_for(plan, manual="sp")
      eff = getattr(self, "_effective_shard", self.shard)
      self._pp = SPServing(self.mesh, self.cfg, self.params, sp, eff.is_first_layer, eff.is_last_layer)
      self.params = None
      self._draft_params = None
      return
    if self.pp > 1:
      from ..parallel.mesh import MeshPlan, build_mesh
      from ..parallel.pp_serving import PPServing

      n = len(jax.devices())
      if n < self.pp:
        raise ValueError(f"XOT_TPU_PP={self.pp} but only {n} local devices")
      self._split_vision_params()
      from ..parallel.mesh import pow2_degree

      plan = self._planned_mesh()
      self._check_hbm_budget(plan)
      self.mesh = build_mesh(plan)
      self._gate_kernels_for(plan, manual="pp")
      eff = getattr(self, "_effective_shard", self.shard)
      self._pp = PPServing(self.mesh, self.cfg, self.params, self.pp, eff.is_first_layer, eff.is_last_layer)
      # The pp-placed stage/head copies are the serving params; drop the
      # original so a >1-chip model doesn't also hold a full-size copy.
      self.params = None
      self._draft_params = None  # speculative decode is not composed with pp
      return
    if not self.use_local_mesh or len(jax.devices()) <= 1:
      return
    from ..parallel.mesh import build_mesh, inference_plan, shard_params

    plan = self._planned_mesh()
    self._check_hbm_budget(plan)
    self.mesh = build_mesh(plan)
    self._gate_kernels_for(plan)
    self.params = shard_params(self.params, self.mesh)

  def _gate_kernels_for(self, plan, manual: str | None = None) -> None:
    """A serving plan that leaves an axis of more than one device to GSPMD
    (tp, ep — every axis but the shard_map's ``manual`` one) cannot hold
    Mosaic kernels: clear the config's gate, so every program of this load
    takes the XLA attention paths, and say so once. ``--pp N`` / ``--sp N``
    over exactly N chips keep the kernels (manual throughout)."""
    from ..parallel.mesh import auto_partitioned

    if auto_partitioned(plan, manual):
      from dataclasses import replace

      self.cfg = replace(self.cfg, mosaic_kernels=False)
      print(f"[jax_engine] serving plan {plan.describe()} is GSPMD-partitioned: Pallas kernels off, XLA attention paths on")

  def _place_cache(self, cache, cfg=None):
    """Mesh-place a KV cache. ``cfg`` defaults to the target model's; the
    cross-model draft passes its OWN cfg — its kv-head count decides whether
    the head axis can shard over tp (a 2-head draft under tp=4 must stay
    replicated even when the 8-head target shards)."""
    if self._pp is not None:
      return self._pp.place_cache(cache)
    if self.mesh is None:
      return cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    heads = (cfg or self.cfg).cache_kv_heads  # MLA latent cache has a size-1 head axis
    tp = "tp" if heads > 1 and heads % self.mesh.shape["tp"] == 0 else None
    spec = NamedSharding(self.mesh, P(None, None, None, tp, None))
    return jax.tree.map(lambda x: jax.device_put(x, spec), cache)

  async def _load_tokenizer(self, shard: Shard) -> None:
    if self.diffusion is not None:  # CLIP tokenizer already loaded from disk
      return
    from .. import registry
    from .tokenizers import resolve_tokenizer

    repo = registry.get_repo(shard.model_id, type(self).__name__) or shard.model_id
    local = getattr(self, "_model_dir", None)
    prefer_processor = self.cfg is not None and self.cfg.vision is not None
    self.tokenizer = await resolve_tokenizer(repo, local, prefer_processor=prefer_processor)

  def _note_experts(self) -> None:
    """The expert layers' two counts as the loaded shard has them: the router's width, and how many of those experts'
    weights are here (fewer where the shard is one chip's share of an expert-parallel deployment; 0 and 0: dense)."""
    metrics.set_gauge("moe_experts_routed", self.cfg.n_experts)
    metrics.set_gauge("moe_experts_held", self.cfg.n_held_experts)

  def load_test_model(self, shard: Shard, cfg, params, tokenizer=None) -> None:
    """Directly inject a model (unit tests / local pipeline composition)."""
    self.adapter_registry = None  # stale geometry: re-enable against the new params
    self.shard = shard
    self._effective_shard = shard
    self.cfg = cfg
    self._note_experts()
    self.params = params
    self.tokenizer = tokenizer
    self._vision_params = None
    self._train_state = None
    self._mesh_eval_fn = None
    self._maybe_build_draft(calibrate=False)  # tests must exercise the spec path deterministically
    self.sessions.clear()
    self._key = jax.random.PRNGKey(self._seed)

  # ------------------------------------------------------- image generation

  def _load_diffusion_sync(self, shard: Shard, model_dir) -> None:
    """Load a diffusers-format checkpoint as a DiffusionPipeline.

    Diffusion serving is deliberately single-device full-model: SD2's
    ~2.6 GB of bf16 weights fit any TPU chip, and the denoising loop is
    compute-bound MXU work — ring-sharding the UNet (what the reference's
    dead 31-"layer" split would have done, reference models.py:168) buys
    nothing on this hardware. Scale throughput with data parallelism
    (one request per node) instead.
    """
    from ..models.diffusion_loader import diffusion_config_from_dir, load_diffusion_params
    from .diffusion_pipeline import DiffusionPipeline

    model_dir = Path(model_dir)
    cfg = diffusion_config_from_dir(model_dir)
    params = load_diffusion_params(model_dir, cfg)
    tokenizer = None
    if (model_dir / "tokenizer").exists():
      from transformers import AutoTokenizer

      tokenizer = AutoTokenizer.from_pretrained(str(model_dir / "tokenizer"))
    self.diffusion = DiffusionPipeline(cfg, params, tokenizer)
    self.tokenizer = tokenizer
    # Release EVERY piece of the previous text model's device state (same
    # set as clear_model) — a stale int8 draft / PPServing-held sharded
    # params / jitted eval closure would pin HBM under the diffusion weights.
    self.params = None
    self.cfg = None
    self._draft_params = None
    self._vision_params = None
    self._train_state = None
    self._mesh_eval_fn = None
    self.mesh = None
    self._pp = None
    self._batch_ops = None
    self.shard = shard
    self._effective_shard = shard
    self._model_dir = model_dir
    self.sessions.clear()
    self._drop_batched_server()
    if DEBUG >= 1:
      print(f"[jax_engine] loaded diffusion pipeline {shard.model_id} from {model_dir}")

  def load_test_diffusion(self, shard: Shard, cfg, params, tokenizer=None) -> None:
    """Directly inject a diffusion model (unit tests)."""
    import jax.numpy as jnp

    from .diffusion_pipeline import DiffusionPipeline

    self.diffusion = DiffusionPipeline(cfg, params, tokenizer, dtype=jnp.float32)
    self.tokenizer = tokenizer
    self.params = None
    self.cfg = None
    self.shard = shard
    self._effective_shard = shard

  async def generate_image(
    self,
    shard: Shard,
    prompt: str,
    negative: str = "",
    steps: int = 30,
    guidance: float = 7.5,
    seed: int = 0,
    size: tuple[int, int] | None = None,
    init_image: np.ndarray | None = None,
    strength: float = 0.8,
    progress_cb=None,
    cancel_event=None,
    n: int = 1,
  ) -> np.ndarray:
    """Text→image (or img2img) on the loaded diffusion pipeline.

    Runs on the engine's single worker thread like all device work; the
    progress callback is marshalled back onto the event loop.
    ``cancel_event`` (threading.Event) aborts between denoise chunks —
    asyncio cancellation cannot interrupt the worker thread, so a dead
    client's request must be stopped cooperatively.
    """
    await self.ensure_shard(shard)
    # Snapshot: a concurrent text-model load on the worker thread may null
    # self.diffusion between this check and the executor slot.
    pipeline = self.diffusion
    if pipeline is None:
      raise NotImplementedError(f"{shard.model_id} is not an image-generation model")
    loop = asyncio.get_event_loop()
    cb = None
    if progress_cb is not None:
      def cb(done, total):  # noqa: E306 — worker-thread → loop marshal
        loop.call_soon_threadsafe(progress_cb, done, total)
    return await loop.run_in_executor(
      self.executor,
      lambda: pipeline.generate(
        prompt, negative=negative, steps=steps, guidance=guidance, seed=seed,
        size=size, init_image=init_image, strength=strength, progress_cb=cb,
        should_cancel=cancel_event.is_set if cancel_event is not None else None,
        n=n,
      ),
    )

  # ---------------------------------------------------------------- contract

  async def encode(self, shard: Shard, prompt: str) -> np.ndarray:
    await self.ensure_shard(shard)
    if self.diffusion is not None:
      raise NotImplementedError(f"{shard.model_id} is an image-generation model; use /v1/image/generations")
    ids = self.tokenizer.encode(prompt)
    return np.asarray(ids, dtype=np.int32)

  async def decode(self, shard: Shard, tokens: np.ndarray) -> str:
    await self.ensure_shard(shard)
    return self.tokenizer.decode(np.asarray(tokens).reshape(-1).tolist())

  async def sample(self, x: np.ndarray, temp: float = 0.6, top_k: int = 35) -> np.ndarray:
    return await asyncio.get_event_loop().run_in_executor(self.executor, self._sample_sync, x, temp, top_k)

  def _sample_sync(self, x: np.ndarray, temp: float, top_k: int) -> np.ndarray:
    from ..ops.sampling import greedy, sample_logits

    logits = jnp.asarray(x)
    if logits.ndim == 3:  # tolerate [B,S,V] callers: sample the last row
      logits = logits[:, -1, :]
    if temp <= 0:
      return np.asarray(greedy(logits))
    sub = self.split_key()
    return np.asarray(sample_logits(logits, sub, temp=temp, top_k=top_k))

  async def infer_prompt(
    self,
    request_id: str,
    shard: Shard,
    prompt: str,
    inference_state: InferenceState | None = None,
  ) -> tuple[np.ndarray, InferenceState]:
    """Adds the llava vision path on top of the base encode→infer_tensor:
    when the request carries images (state.extras["images"], base64 — set by
    the API) and the loaded model has a vision tower, the prompt's <image>
    placeholders are expanded by the HF processor, the CLIP tower + projector
    run on-device, and the patch features are merged into the token
    embeddings before prefill (models/vision.py)."""
    images = (inference_state.extras.pop("images", None) if inference_state and inference_state.extras else None)
    await self.ensure_shard(shard)
    if images and self.cfg is not None and self.cfg.vision is not None and shard.is_first_layer:
      return await asyncio.get_event_loop().run_in_executor(
        self.executor, self._infer_prompt_multimodal_sync, request_id, shard, prompt, images, inference_state or InferenceState()
      )
    return await super().infer_prompt(request_id, shard, prompt, inference_state)

  def _infer_prompt_multimodal_sync(self, request_id, shard, prompt, images_b64, state):
    import base64
    import io

    from PIL import Image

    from ..models.vision import encode_images, merge_image_embeddings

    pil_images = [Image.open(io.BytesIO(base64.b64decode(b))).convert("RGB") for b in images_b64]
    # The resolved "tokenizer" for llava repos is the AutoProcessor
    # (inference/tokenizers.py) — it expands each <image> into n_patches
    # placeholder ids and normalizes pixels to the CLIP layout.
    proc = self.tokenizer
    try:
      out = proc(text=prompt, images=pil_images, return_tensors="np")
    except StopIteration:
      # HF processors raise bare StopIteration on a placeholder/image count
      # mismatch — inside run_in_executor that surfaces as an opaque
      # RuntimeError; turn it into an actionable client error instead.
      raise ValueError(
        f"prompt has more <image> placeholders than attached images ({len(pil_images)}); "
        "the API inserts one per image_url part — don't also write <image> in the text"
      ) from None
    tokens = np.asarray(out["input_ids"], dtype=np.int32)
    pixel_values = np.asarray(out["pixel_values"], dtype=np.float32)
    B, S = tokens.shape

    vp = self._vision_leaves()
    if pixel_values.ndim == 5:
      # llava-next anyres: [n_images, tiles, 3, H, W] + per-image original
      # sizes. Each image's tiles batch through the tower in one dispatch;
      # packing (spatial re-assembly + unpad + newline) is host bookkeeping
      # (models/vision.py pack_anyres_features).
      from ..models.vision import anyres_grid_shape, pack_anyres_features

      image_sizes = np.asarray(out["image_sizes"], dtype=np.int64)
      newline = vp["projector"]["image_newline"]
      packed = []
      for i in range(pixel_values.shape[0]):
        osize = (int(image_sizes[i][0]), int(image_sizes[i][1]))
        gh, gw = anyres_grid_shape(osize, self.cfg.vision.grid_pinpoints, self.cfg.vision.image_size)
        tiles = jnp.asarray(pixel_values[i, : 1 + gh * gw])
        tile_feats = encode_images(vp["vision"], vp["projector"], self.cfg.vision, tiles)
        packed.append(pack_anyres_features(tile_feats, osize, self.cfg.vision, newline))
      feats = jnp.concatenate(packed, axis=0)[None]  # [1, total, D]
    else:
      feats = encode_images(vp["vision"], vp["projector"], self.cfg.vision, jnp.asarray(pixel_values))
    pad_to = min(_round_up(S, PREFILL_BUCKET), min(self.max_seq_len, self.cfg.max_seq_len))
    tok_pad = np.zeros((B, pad_to), dtype=np.int32)
    tok_pad[:, :S] = tokens
    embeds = jnp.take(self._serving_embed(), jnp.asarray(tok_pad), axis=0).astype(self.cfg.dtype)
    merged = merge_image_embeddings(embeds, jnp.asarray(tok_pad), feats, self.cfg.image_token_id)

    state.prompt_len = S
    out_np, state = self._infer_tensor_sync(request_id, shard, np.asarray(merged), state)
    state.tokens = tokens  # the hidden-input path doesn't record token ids
    return out_np, state

  async def infer_tensor(
    self,
    request_id: str,
    shard: Shard,
    input_data: np.ndarray,
    inference_state: InferenceState | None = None,
  ) -> tuple[np.ndarray, InferenceState]:
    await self.ensure_shard(shard)
    return await asyncio.get_event_loop().run_in_executor(
      self.executor, self._infer_tensor_sync, request_id, shard, input_data, inference_state
    )

  def _infer_tensor_sync(self, request_id, shard, input_data, state):
    import time as _time

    t0 = _time.perf_counter()
    shard = getattr(self, "_effective_shard", shard)
    state = state or InferenceState()
    # In-flight replay after a peer loss (orchestration/node.py
    # _retry_request): a bumped replay_epoch invalidates any stale session so
    # the replayed token history prefills from scratch. The epoch is READ,
    # not consumed — it must keep traveling with the state to every
    # surviving downstream node on the ring.
    epoch = int(state.extras.get("replay_epoch", 0))
    x = np.asarray(input_data)
    is_tokens = x.ndim == 2 and np.issubdtype(x.dtype, np.integer)
    B = x.shape[0]

    session = self.sessions.get(request_id)
    if session is not None and session.epoch != epoch:
      session = None
      self.sessions.pop(request_id, None)
    if session is None:
      max_seq = min(self.max_seq_len, self.cfg.max_seq_len)
      cache = self._place_cache(init_kv_cache(self.cfg, shard.n_shard_layers, B, max_seq))
      session = self.sessions[request_id] = _Session(cache, max_seq, epoch)
      session.adapter_slot = self._acquire_session_slot(request_id)

    prefilling = session.curr_pos == 0
    if prefilling:
      prompt_len = state.prompt_len or x.shape[1]
      if prompt_len + 1 > session.max_seq:
        from .engine import PromptTooLongError

        self.sessions.pop(request_id, None)
        raise PromptTooLongError(f"prompt of {prompt_len} tokens exceeds the {session.max_seq}-token context window")
      # Remember the FIRST prefill's prompt length for the request lifetime:
      # a replay prefills the whole token history, and the max_tokens budget
      # must still count from the original prompt (node._check_finished).
      state.extras.setdefault("orig_prompt_len", int(prompt_len))
      if is_tokens:
        state.tokens = x.astype(np.int32)
        state.prompt_len = prompt_len
        session.prompt_np = x.astype(np.int32)  # draft prefill (speculative mode)
        pad_to = min(_round_up(x.shape[1], PREFILL_BUCKET), session.max_seq)
        x_in = np.zeros((B, pad_to), dtype=np.int32)
        x_in[:, : x.shape[1]] = x
      else:
        x_in = x  # hidden states arrive already padded by the first shard
      lens = jnp.full((B,), prompt_len, dtype=jnp.int32)
      if self._pp is not None:
        out, session.kv_cache = self._pp.prefill(jnp.asarray(x_in), session.kv_cache, lens)
      else:
        out, session.kv_cache = _prefill(self.params, self.cfg, shard, jnp.asarray(x_in), session.kv_cache, lens, self._session_adapter_ids(session, B))
      session.curr_pos = session.prompt_len = prompt_len
    else:
      if session.curr_pos >= session.max_seq:
        raise RuntimeError(f"KV cache exhausted at {session.max_seq} positions for request {request_id}")
      if is_tokens:
        x_step = x[:, -1:].astype(np.int32)  # the freshly sampled token
        if state.tokens is not None:
          state.tokens = np.concatenate([state.tokens, x_step], axis=1)
      else:
        x_step = x
      pos = jnp.full((B,), session.curr_pos, dtype=jnp.int32)
      if self._pp is not None:
        out, session.kv_cache = self._pp.decode_step(jnp.asarray(x_step), session.kv_cache, pos)
      else:
        out, session.kv_cache = _decode_step(self.params, self.cfg, shard, jnp.asarray(x_step), session.kv_cache, pos, self._session_adapter_ids(session, B))
      session.curr_pos += 1

    state.curr_pos = session.curr_pos
    out_np = np.asarray(out)
    # Engine-step telemetry: the host fetch above makes the timing honest
    # (dispatch alone would measure queueing, not compute).
    metrics.observe_hist("prefill_seconds" if prefilling else "decode_step_seconds", _time.perf_counter() - t0)
    metrics.set_gauge("engine_sessions", len(self.sessions))
    return out_np, state

  async def generate_chunk(self, request_id: str, shard: Shard, last_token: int, n_steps: int, temp: float = 0.6, top_k: int = 35) -> list[int]:
    """Generate ``n_steps`` tokens in one compiled program (fused lax.scan)."""
    handle = await self.dispatch_chunk(request_id, shard, n_steps, temp, top_k, first_token=last_token)
    return await self.read_chunk(handle)

  async def dispatch_chunk(self, request_id: str, shard: Shard, n_steps: int, temp: float = 0.6, top_k: int = 35, first_token: int | None = None):
    """Enqueue one fused decode chunk; returns a device handle immediately.

    The chunk's input token is either ``first_token`` (host int, first chunk
    after prefill) or the previous chunk's last token, which stays ON DEVICE
    (``session.next_token_dev``) — so the Node can dispatch chunk N+1 before
    reading chunk N and hide the host round trip behind compute.
    Returns None if the KV cache is exhausted.
    """
    await self.ensure_shard(shard)
    return await asyncio.get_event_loop().run_in_executor(
      self.executor, self._dispatch_chunk_sync, request_id, shard, n_steps, temp, top_k, first_token
    )

  def _spec_chunk_eligible(self, session, temp, first_token) -> bool:
    """Streaming speculative chain: greedy single-stream requests with the
    int8 self-draft, entered right after prefill and continued on-device."""
    if self._draft_params is None or (temp is not None and float(temp) > 0.0):
      return False
    if getattr(session, "adapter_slot", 0):
      return False  # spec verifies the BASE target; adapter sessions decode plain
    if session.spec_seed_dev is not None:
      return True  # chain already active
    return (
      first_token is not None
      and session.prompt_np is not None
      and session.prompt_np.shape[0] == 1
      and session.curr_pos == session.prompt_len  # fresh after prefill
    )

  def _spec_gamma_for_dispatch(self) -> int:
    """The adaptive solo-path depth for the NEXT spec dispatch: the live
    gamma, or a gamma-1 probe once the plain streak earns one, else 0
    (= take the plain path; XOT_TPU_SPEC_DECODE must never decode slower
    than plain — the acceptance-EWMA floor, ISSUE 7)."""
    g = self._spec_gamma_live
    if g > 0:
      return g
    if self._spec_reprobe > 0 and self._spec_plain_streak >= self._spec_reprobe:
      return 1
    return 0

  def _note_spec_acceptance(self, emitted: int, rounds: int, gamma: int) -> None:
    """Fold one spec call's measured acceptance into the engine EWMA and
    re-run the depth policy (inference/paging.py)."""
    from .paging import ewma_update, spec_adapt_gamma
    from ..utils.metrics import FRACTION_BUCKETS

    if rounds <= 0 or gamma <= 0:
      return
    acc = (emitted / rounds - 1.0) / gamma
    self._spec_ewma = ewma_update(self._spec_ewma, acc)
    self._spec_gamma_live = spec_adapt_gamma(self._spec_ewma, gamma, self.spec_gamma)
    self._spec_plain_streak = 0
    metrics.observe_hist("spec_acceptance_ewma", self._spec_ewma, buckets=FRACTION_BUCKETS)

  def _ngram_chunk_eligible(self, session, temp, first_token) -> bool:
    """Draft-free n-gram chain (ISSUE 12): greedy single-stream requests
    with XOT_TPU_SPEC_DECODE set but NO draft pair loaded — the solo spec
    path no longer requires a draft checkpoint. Entered right after prefill
    like the draft chain; continues while the session's index is alive."""
    if self._draft_params is not None or not self.spec_decode or not self._spec_ngram_on:
      return False
    if getattr(session, "adapter_slot", 0):
      return False  # n-gram chunks verify the BASE target; adapter sessions decode plain
    if temp is not None and float(temp) > 0.0:
      return False
    if session.ngram_index is not None or session.ngram_unread:
      return True  # chain active
    return (
      first_token is not None
      and session.prompt_np is not None
      and session.prompt_np.shape[0] == 1
      and session.curr_pos == session.prompt_len  # fresh after prefill
    )

  def _ngram_gamma_for_dispatch(self, session) -> int:
    """The SESSION's adaptive n-gram depth for the next chunk. Every fresh
    session opens at the full cap — proposals cost nothing to attempt, and
    the previous response's text says nothing about this one's — and the
    session's own measured acceptance walks it down from there (the batched
    path's per-slot fresh start, same reasoning)."""
    if session.ngram_gamma < 0:
      session.ngram_gamma = self.spec_ngram_max
    return session.ngram_gamma

  def _note_ngram_acceptance(self, session, accepted: int, proposed: int) -> None:
    """Fold one n-gram chunk's measured acceptance into the SESSION's EWMA
    and re-run the depth policy (same shape as ``_note_spec_acceptance``,
    per-session state — ISSUE 12)."""
    from .paging import ewma_update, spec_adapt_gamma
    from ..utils.metrics import FRACTION_BUCKETS

    if proposed <= 0:
      return
    # Counters record the device work unconditionally (the batched settle
    # does too); only the EWMA needs a live session — a request cancelled
    # between dispatch and read still drafted/verified those tokens.
    metrics.inc("spec_proposed_tokens_total", proposed, labels={"proposer": "ngram"})
    metrics.inc("spec_accepted_tokens_total", accepted, labels={"proposer": "ngram"})
    if session is None:
      return
    session.ngram_ewma = ewma_update(session.ngram_ewma, accepted / proposed)
    session.ngram_gamma = spec_adapt_gamma(session.ngram_ewma, max(session.ngram_gamma, 1), self.spec_ngram_max)
    metrics.observe_hist("spec_acceptance_ewma", session.ngram_ewma, buckets=FRACTION_BUCKETS)

  def _note_ngram_miss(self, session) -> None:
    """A suffix lookup found nothing: zero-acceptance EWMA observation, so
    a session over non-repetitive text converges back to the (pipelined)
    plain path instead of holding the chunk loop synchronous forever."""
    from .paging import ewma_update, spec_adapt_gamma

    session.ngram_ewma = ewma_update(session.ngram_ewma, 0.0)
    session.ngram_gamma = spec_adapt_gamma(session.ngram_ewma, session.ngram_gamma, self.spec_ngram_max)

  def _dispatch_ngram_chunk_sync(self, request_id, shard, first_token, steps: int, gamma: int):
    """One draft-free speculative chunk (models/decoder.py
    ``fused_spec_batch_decode`` with ``params_d=None``, B=1): the host
    proposes the continuation that followed the current suffix earlier in
    prompt+generated history, the target verifies the whole window, and the
    session's dense cache absorbs the variable advance. Returns the packed
    handle, or None to hand THIS dispatch to the plain path (no proposal
    and depth at the floor, or the near-window band).

    The chain is strictly sequential: host history must cover a chunk's
    emitted tokens before the next proposal — ``read_chunk`` confirms the
    position, extends the index, and clears ``ngram_unread``."""
    from ..models.decoder import fused_spec_batch_decode

    session = self.sessions[request_id]
    if session.ngram_index is None:
      from .ngram import NgramIndex

      idx = NgramIndex(self.spec_ngram_n)
      idx.extend(session.prompt_np[0])
      idx.extend([int(first_token)])
      session.ngram_index = idx
      token = jnp.full((1, 1), int(first_token), dtype=jnp.int32)
    else:
      token = session.next_token_dev
      if token is None:
        session.ngram_index = None  # chain broken (plain re-seeds exactly)
        return None
    G = self.spec_ngram_max
    rounds = max(steps // (G + 1), 1)
    stream = session.ngram_index.propose(rounds * (G + 1) + G)
    if len(stream) == 0:
      self._note_ngram_miss(session)
      if session.ngram_gamma <= 0:
        session.ngram_index = None  # depth floor: plain serves the rest
        return None
      # Tracking-only chunk (gamma_max=0 compiles to a plain-equivalent
      # program that still reports counts): history stays live so the next
      # repetitive region can propose again.
      rounds, G, g_eff = steps, 0, 0
      props = prop_counts = None
    else:
      g_eff = min(gamma, len(stream))
      props = jnp.asarray(np.asarray(stream)[None, :], jnp.int32)
      prop_counts = jnp.asarray([len(stream)], jnp.int32)
    worst = rounds * (G + 1)
    if session.curr_pos + worst + 1 > session.max_seq:
      session.ngram_index = None  # near the cache end: plain trims exactly
      return None
    pos = jnp.full((1,), session.curr_pos, dtype=jnp.int32)
    buf, counts, n_prop, seed, _new_pos, session.kv_cache, _cd = fused_spec_batch_decode(
      self.params, self.cfg, shard, None, self.cfg, shard,
      token, session.kv_cache, None, pos, jnp.ones((1,), jnp.bool_), jnp.asarray([g_eff], jnp.int32),
      jnp.zeros((1,), jnp.float32), rounds, G, top_k=1, k_max=1, key=None,
      props=props, prop_counts=prop_counts,
    )
    packed = jnp.concatenate([counts, n_prop, buf[0]])
    session.next_token_dev = seed
    session.ngram_unread = True
    try:
      packed.copy_to_host_async()
    except AttributeError:
      pass
    return ("ngram", request_id, rounds, packed)

  def _dispatch_spec_chunk_sync(self, request_id, shard, n_steps, first_token, steps: int, gamma: int):
    """One streaming speculative chunk (models/decoder.py
    fused_speculative_chunk). The seed token and position ride the DEVICE
    chain, so the node's pipelined dispatch (enqueue N+1 before reading N)
    works without a host round-trip. EOS handling stays host-side exactly
    like plain chunks (the node trims and stops)."""
    from ..models.decoder import fused_speculative_chunk

    session = self.sessions[request_id]
    if session.spec_seed_dev is None:
      self._ensure_draft_cache(session, shard)
      session.spec_known_pos = session.curr_pos
      token = jnp.full((1, 1), int(first_token), dtype=jnp.int32)
      pos = jnp.int32(session.curr_pos)
    else:
      token = session.spec_seed_dev
      pos = session.spec_pos_dev
    worst = steps + gamma + 1
    packed, seed, new_pos, session.kv_cache, session.draft_cache = fused_speculative_chunk(
      self.params, self.cfg, shard, self._draft_params, token, session.kv_cache, session.draft_cache,
      pos, steps, gamma=gamma, n_limit=min(n_steps, steps),
      cfg_d=self._draft_cfg, shard_d=self._draft_shard,
    )
    session.spec_seed_dev = seed
    session.spec_pos_dev = new_pos
    session.spec_inflight_slots += worst
    session.next_token_dev = None  # plain chain broken while spec is active
    # Double-buffered readback (NOTES r2 item 3): enqueue the device->host
    # copy NOW, behind the compute — read_chunk's fetch then completes
    # immediately instead of starting the copy after the chunk.
    try:
      packed.copy_to_host_async()
    except AttributeError:  # backend without async copies
      pass
    return ("spec", request_id, worst, gamma, packed)

  def _dispatch_chunk_sync(self, request_id, shard, n_steps, temp, top_k, first_token):
    shard = getattr(self, "_effective_shard", shard)
    session = self.sessions[request_id]
    if self._pp is None and self._spec_chunk_eligible(session, temp, first_token):
      G = self._spec_gamma_for_dispatch()
      steps = min(1 << (max(n_steps, 1) - 1).bit_length(), 256)  # bucketed compile size
      # Conservative room bound: confirmed position + every unread chunk's
      # own worst case + this chunk's worst case. Before the chain starts
      # the confirmed position is simply curr_pos.
      base = session.spec_known_pos if session.spec_seed_dev is not None else session.curr_pos
      if G > 0 and base + session.spec_inflight_slots + (steps + G + 1) + 1 <= session.max_seq:
        return self._dispatch_spec_chunk_sync(request_id, shard, n_steps, first_token, steps, G)
      if G == 0:
        # Adaptive floor: the draft isn't paying — this dispatch takes the
        # plain path (never slower than plain decode), and the streak counts
        # toward the next gamma-1 probe.
        self._spec_plain_streak += 1
      if session.spec_seed_dev is not None:
        # Near the cache end: sync the exact chain position once and hand the
        # stream to the plain path, which trims precisely at max_seq. Stale
        # spec handles read after this point must not touch the bookkeeping
        # (read_chunk checks spec_seed_dev) — the synced position already
        # includes every dispatched chunk.
        session.curr_pos = int(np.asarray(session.spec_pos_dev))
        session.spec_known_pos = session.curr_pos
        session.next_token_dev = session.spec_seed_dev
        session.spec_seed_dev = None
        session.spec_pos_dev = None
        session.spec_inflight_slots = 0
    elif self._pp is None and self._ngram_chunk_eligible(session, temp, first_token):
      # Draft-free n-gram chain (ISSUE 12). An unread n-gram chunk answers
      # the node's dispatch-ahead with None — the chunk loop's
      # under-delivery fallback then re-dispatches after reading, which is
      # exactly the synchronous cadence host proposals require.
      if session.ngram_unread:
        return None
      G = self._ngram_gamma_for_dispatch(session)
      if G > 0:
        steps = min(1 << (max(n_steps, 1) - 1).bit_length(), 256)
        handle = self._dispatch_ngram_chunk_sync(request_id, shard, first_token, steps, G)
        if handle is not None:
          return handle
      else:
        session.ngram_index = None  # session at the depth floor: plain takes over
    return self._dispatch_plain_chunk_sync(request_id, shard, n_steps, temp, top_k, first_token)

  def _dispatch_plain_chunk_sync(self, request_id, shard, n_steps, temp, top_k, first_token):
    from ..models.decoder import fused_decode

    session = self.sessions[request_id]
    n_steps = min(n_steps, session.max_seq - session.curr_pos)
    if n_steps <= 0:
      return None
    B = session.kv_cache["k"].shape[1]
    if first_token is not None:
      token = jnp.full((B, 1), int(first_token), dtype=jnp.int32)
    else:
      token = session.next_token_dev
      if token is None:
        raise RuntimeError(f"no chained token for request {request_id}; pass first_token after prefill")
    start_pos = jnp.full((B,), session.curr_pos, dtype=jnp.int32)
    sub = self.split_key()
    if self._pp is not None:
      toks, session.kv_cache = self._pp.fused_decode(token, session.kv_cache, start_pos, n_steps, temp=float(temp), top_k=int(top_k), key=sub)
    else:
      toks, session.kv_cache = fused_decode(
        self.params, self.cfg, shard, token, session.kv_cache, start_pos, n_steps,
        temp=float(temp), top_k=int(top_k), key=sub,
        adapter_ids=self._session_adapter_ids(session, B),
      )
    session.next_token_dev = toks[:, -1:]
    session.curr_pos += n_steps
    try:
      toks.copy_to_host_async()  # overlap the readback with the next chunk's compute
    except AttributeError:
      pass
    return toks

  async def generate_oneshot(
    self,
    request_id: str,
    shard: Shard,
    first_token: int,
    max_steps: int,
    eos_ids=(),
    temp: float = 0.6,
    top_k: int = 35,
  ) -> list[int]:
    """Generate a whole response (until EOS) in one compiled program.

    One dispatch + one host readback total (vs one per chunk) — the blocking
    completion fast path. Returns the generated tokens trimmed at the first
    EOS.
    """
    await self.ensure_shard(shard)
    return await asyncio.get_event_loop().run_in_executor(
      self.executor, self._generate_oneshot_sync, request_id, shard, first_token, max_steps, eos_ids, temp, top_k
    )

  def _generate_oneshot_sync(self, request_id, shard, first_token, max_steps, eos_ids, temp, top_k):
    from ..models.decoder import fused_generate

    shard = getattr(self, "_effective_shard", shard)
    session = self.sessions[request_id]
    room = session.max_seq - session.curr_pos
    if room <= 0:
      return []
    spec_gamma = self._spec_gamma_for_dispatch() if self._draft_params is not None else 0
    if (
      self._draft_params is not None
      and not getattr(session, "adapter_slot", 0)  # spec verifies the BASE target; adapter sessions stay plain
      and (temp is None or float(temp) <= 0.0)
      and session.prompt_np is not None
      and session.curr_pos == session.prompt_len  # fresh after prefill (no chunk history to replay into the draft)
      and session.prompt_np.shape[0] == 1
      # Spec rounds need gamma+1 slots of headroom; near the cache end the
      # plain path can still emit the final tokens — use it so a
      # context-limited response is never cut gamma+1 tokens short.
      and max_steps <= room - spec_gamma - 1
    ):
      if spec_gamma > 0:
        return self._generate_speculative_sync(request_id, shard, first_token, max_steps, eos_ids, spec_gamma)
      # Acceptance-EWMA floor (ISSUE 7): the draft isn't paying — plain
      # decode, counting toward the next gamma-1 probe.
      self._spec_plain_streak += 1
    # Bucket the COMPILED step count (power-of-two, capped by cache room) so
    # varying max_tokens requests reuse a handful of compiled programs; the
    # actual step cap travels as a traced scalar, so no extra steps run.
    limit = max(1, min(max_steps, room))
    steps = min(1 << (limit - 1).bit_length(), room)
    B = session.kv_cache["k"].shape[1]
    token = jnp.full((B, 1), int(first_token), dtype=jnp.int32)
    start_pos = jnp.full((B,), session.curr_pos, dtype=jnp.int32)
    sub = self.split_key()
    eos = tuple(sorted(int(e) for e in eos_ids))
    if self._pp is not None:
      buf, _n, session.kv_cache = self._pp.fused_generate(
        token, session.kv_cache, start_pos, steps, eos_ids=eos, temp=float(temp), top_k=int(top_k), key=sub, n_limit=limit
      )
    else:
      buf, _n, session.kv_cache = fused_generate(
        self.params, self.cfg, shard, token, session.kv_cache, start_pos, steps,
        eos_ids=eos, temp=float(temp), top_k=int(top_k), key=sub, n_limit=limit,
        adapter_ids=self._session_adapter_ids(session, B),
      )
    # ONE host readback: the step count is recovered from the first EOS hit
    # (the while_loop stops right after writing it), not fetched separately.
    row = np.asarray(buf)[0]
    n = limit
    if eos:
      hits = np.nonzero(np.isin(row[:limit], eos))[0]
      if hits.size:
        n = int(hits[0]) + 1
    toks = [int(t) for t in row[:n]]
    session.curr_pos += n
    session.next_token_dev = None  # chain broken: next chunk must re-seed
    return toks

  def _ensure_draft_cache(self, session, shard) -> None:
    """Draft prefill over the prompt (the draft never saw it): pad like the
    target prefill so the compiled program is shared across prompts."""
    from ..models.decoder import init_kv_cache

    if session.draft_cache is not None:
      return
    cfg_d = self._draft_cfg or self.cfg
    shard_d = self._draft_shard or shard
    B, S = session.prompt_np.shape
    cache = init_kv_cache(cfg_d, shard_d.n_shard_layers, B, session.max_seq)
    pad_to = min(_round_up(S, PREFILL_BUCKET), session.max_seq)
    x_in = np.zeros((B, pad_to), dtype=np.int32)
    x_in[:, :S] = session.prompt_np
    lens = jnp.full((B,), S, dtype=jnp.int32)
    _, session.draft_cache = _prefill(self._draft_params, cfg_d, shard_d, jnp.asarray(x_in), self._place_cache(cache, cfg=cfg_d), lens)

  def _generate_speculative_sync(self, request_id, shard, first_token, max_steps, eos_ids, gamma: int | None = None):
    """Greedy speculative oneshot: int8 self-draft + bf16 target fused in one
    while_loop program (models/decoder.py fused_speculative_generate).
    Output is exactly the plain-greedy tokens; only the speed differs."""
    from ..models.decoder import fused_speculative_generate

    gamma = self.spec_gamma if gamma is None else gamma
    session = self.sessions[request_id]
    room = session.max_seq - session.curr_pos
    limit = min(max_steps, room - gamma - 1)  # caller guarantees > 0
    steps = min(1 << (limit - 1).bit_length(), room - gamma - 1)
    self._ensure_draft_cache(session, shard)
    token = jnp.full((1, 1), int(first_token), dtype=jnp.int32)
    eos = tuple(sorted(int(e) for e in eos_ids))
    buf, n, rounds, session.kv_cache, session.draft_cache = fused_speculative_generate(
      self.params, self.cfg, shard, self._draft_params, self._draft_cfg or self.cfg, self._draft_shard or shard,
      token, session.kv_cache, session.draft_cache, session.curr_pos,
      steps, gamma=gamma, eos_ids=eos, n_limit=limit,
    )
    row = np.asarray(buf)
    self._note_spec_acceptance(int(n), int(rounds), gamma)
    n = min(int(n), limit)
    if eos:
      hits = np.nonzero(np.isin(row[:n], eos))[0]
      if hits.size:
        n = int(hits[0]) + 1
    toks = [int(t) for t in row[:n]]
    session.curr_pos += n
    session.next_token_dev = None
    return toks

  async def read_chunk(self, handle) -> list[int]:
    if handle is None:
      return []

    def read():
      if isinstance(handle, tuple) and handle[0] == "ngram":
        # Packed draft-free n-gram chunk: [m, n_prop, tokens...] in one
        # fetch (ISSUE 12). Confirms the chain position, extends the
        # suffix index with the emitted tokens (the next proposal keys on
        # them), and feeds the measured acceptance into the n-gram EWMA.
        _, request_id, rounds, packed = handle
        row = np.asarray(packed)
        m, n_prop = int(row[0]), int(row[1])
        session = self.sessions.get(request_id)
        self._note_ngram_acceptance(session, max(m - rounds, 0), n_prop)
        toks = [int(t) for t in row[2 : 2 + m]]
        if session is not None:
          session.ngram_unread = False
          session.curr_pos += m
          if session.ngram_index is not None:
            session.ngram_index.extend(toks)
        return toks
      if isinstance(handle, tuple) and handle[0] == "spec":
        # Packed speculative chunk: [m, rounds, tokens...] in one fetch.
        # Confirm the chain position host-side (the room bound tightens back
        # up) — but ONLY while the chain is still active: after the
        # near-cache-end handoff curr_pos is already exact (it includes this
        # chunk), and a stale update would desync it from the device. The
        # round count feeds the acceptance EWMA that adapts the NEXT chunk's
        # gamma (ISSUE 7).
        _, request_id, worst, gamma, packed = handle
        row = np.asarray(packed)
        m = int(row[0])
        self._note_spec_acceptance(m, int(row[1]), gamma)
        session = self.sessions.get(request_id)
        if session is not None and session.spec_seed_dev is not None:
          session.spec_known_pos += m
          session.spec_inflight_slots = max(session.spec_inflight_slots - worst, 0)
          session.curr_pos = session.spec_known_pos
        return [int(t) for t in row[2 : 2 + m]]
      return [int(t) for t in np.asarray(handle)[0]]

    return await asyncio.get_event_loop().run_in_executor(self.executor, read)

  def supports_batched(self) -> bool:
    """Whether batched serving can run for the loaded model + serving mesh.

    The Node falls back to the plain serving path when False. PP composes
    fully (dense-prefix MoE included — parallel/pp_batch.py). SP composes
    for both cache layouts (parallel/sp_batch.py): dense slots shard the
    sequence axis, and the DEFAULT paged pool stripes its page-slot axis
    over sp — the one divisibility requirement is page_size % sp == 0
    (default 64 divides every power-of-two sp)."""
    # Every batched path embeds tokens and runs the head, so a multi-node
    # ring member serving a PARTIAL layer range must fall back to the plain
    # serving path (which supports hidden-in/hidden-out shards) — with or
    # without a local mesh.
    eff = getattr(self, "_effective_shard", None)
    if eff is not None and not (eff.is_first_layer and eff.is_last_layer):
      return False
    if self._pp is None:
      return True
    from ..parallel.pp_serving import PPServing
    from ..parallel.sp_serving import SPServing

    if isinstance(self._pp, PPServing):
      return True
    if not isinstance(self._pp, SPServing):
      return False
    if os.getenv("XOT_TPU_PAGED", "1") in ("0", "false"):
      return True
    page_size = int(os.getenv("XOT_TPU_PAGE_SIZE", "64"))
    return page_size % self._pp.n_ranks == 0

  @property
  def batch_ops(self):
    """Device-op backend for the batch scheduler (inference/batch_ops.py):
    single-device fused programs, or pp-pipelined variants in XOT_TPU_PP mode
    (B streams overlap across stages — parallel/pp_batch.py)."""
    ops = getattr(self, "_batch_ops", None)
    if ops is None:
      from ..parallel.pp_serving import PPServing
      from .batch_ops import DecoderBatchOps, PPBatchOps

      if isinstance(self._pp, PPServing):
        from ..parallel.pp_batch import PPBatchedServing

        ops = PPBatchOps(self, PPBatchedServing.from_pp_serving(self._pp))
      elif self._pp is not None:
        from ..parallel.sp_batch import SPBatchedServing
        from .batch_ops import SPBatchOps

        ops = SPBatchOps(self, SPBatchedServing(self._pp))
      else:
        ops = DecoderBatchOps(self)
      self._batch_ops = ops
    return ops

  def get_batched_server(self):
    """Lazy continuous-batching scheduler (inference/batch_scheduler.py);
    one per loaded model — the pooled KV cache is model-specific."""
    if getattr(self, "_batched_server", None) is None:
      from .batch_scheduler import BatchedServer

      self._batched_server = BatchedServer(self)
    return self._batched_server

  def _drop_batched_server(self) -> None:
    """Stop the old pool loop so its HBM cache actually frees (model swap).
    The KV tier's host store clears with it (server.shutdown) and the local
    prefix advertisement is withdrawn: the same token chains will hold a
    DIFFERENT model's KV bytes after the swap, so both the host entries and
    the cluster-visible hints are stale."""
    server = getattr(self, "_batched_server", None)
    if server is not None:
      server.shutdown()
      from .kv_tier import prefix_registry

      prefix_registry.clear_local()
    self._batched_server = None
    self._batch_ops = None  # backend is model/mesh-specific

  async def clear_session(self) -> None:
    self.sessions.clear()

  async def clear_model(self) -> None:
    """Drop the loaded model and all sessions, freeing HBM.

    Role of the reference's OOM-recovery ``clear_model``
    (``sharded_inference_engine.py:85-106``) — but here it's an explicit
    management operation (model-switch, DELETE /models), not a crash handler:
    HBM is budgeted ahead of time by the static cache allocation.
    """
    self.params = None
    self.adapter_registry = None
    self.shard = None
    self._effective_shard = None
    self.cfg = None
    self.tokenizer = None
    self.mesh = None
    self._pp = None
    self._batch_ops = None
    self._vision_params = None
    self._train_state = None
    self._mesh_eval_fn = None
    self.sessions.clear()
    self._drop_batched_server()

  def end_request(self, request_id: str) -> None:
    self.sessions.pop(request_id, None)
    metrics.set_gauge("engine_sessions", len(self.sessions))

  # ---------------------------------------------------------------- training
  # (implemented in train/trainer.py and bound here so `xot-tpu train` works;
  #  see engine.py module docstring re the reference's missing train/evaluate)

  async def train(self, request_id, shard, inputs, targets, lengths, loss="ce", opt="adamw", lr=1e-5):
    # Works in every serving mode: plain/tp engines step their flat params;
    # pp/sp mesh engines run the SAME distributed step over the serving mesh
    # (pp routes through the GPipe pipeline — train/trainer.py mesh branch).
    from ..train.trainer import engine_train_step

    return await asyncio.get_event_loop().run_in_executor(
      self.executor, engine_train_step, self, shard, inputs, targets, lengths, loss, opt, lr
    )

  async def evaluate(self, request_id, shard, inputs, targets, lengths, loss="ce"):
    from ..train.trainer import engine_eval_step

    return await asyncio.get_event_loop().run_in_executor(self.executor, engine_eval_step, self, shard, inputs, targets, lengths, loss)

  def _flat_params_view(self, include_vision: bool = False):
    """The flat param tree regardless of serving mode. PP stage stacks
    reassemble with the layer axis still pp-sharded (no gather —
    parallel/pp_serving.reassemble_params); sp/tp params are already flat.

    ``include_vision`` merges the mesh-mode split-off llava tower/projector
    back in — checkpointing needs the COMPLETE tree so mesh and plain
    checkpoints interoperate; the train path must NOT include them (unused
    leaves would still collect optimizer moments and adamw weight decay)."""
    if self._pp is None:
      flat = self.params
    else:
      from ..parallel.pp_serving import PPServing

      flat = self._pp.reassemble_params() if isinstance(self._pp, PPServing) else self._pp.params
    vp = getattr(self, "_vision_params", None)
    if include_vision and vp:
      flat = {**flat, **vp}
    return flat

  def _adopt_flat_params(self, params) -> None:
    """Install an updated flat tree (train step / checkpoint load / LoRA
    attach) into the active layout and drop weight-derived state: live KV
    sessions and the batched pool backend (pp_batch/sp_batch share the old
    arrays). A tree carrying vision leaves (a full-checkpoint restore in a
    mesh mode) splits them back off first. The cached train state is NOT
    reset here — a train loop adopts every step and must keep its optimizer
    momentum; structure-changing callers (attach_lora, load_checkpoint)
    reset it themselves."""
    if self._pp is not None and any(k in params for k in ("vision", "projector")):
      self._vision_params = {k: params[k] for k in ("vision", "projector") if k in params}
      params = {k: v for k, v in params.items() if k not in ("vision", "projector")}
    if self._pp is None:
      self.params = params
    else:
      from ..parallel.pp_serving import PPServing

      if isinstance(self._pp, PPServing):
        self._pp.adopt_params(params)
      else:
        self._pp.params = params
    self.sessions.clear()
    self._drop_batched_server()

  def attach_lora(self, rank: int, key=None) -> None:
    """Attach LoRA adapters to the loaded model in ANY serving mode (the
    train CLI's --lora-rank path; train/lora.py add_lora).

    This is the TRAINING attach (one adapter, unmerged leaves). For
    SERVING many adapters at once, use ``enable_multi_lora`` + the adapter
    registry (ISSUE 15) instead of merging one checkpoint per process."""
    from ..train.lora import add_lora

    key = jax.random.PRNGKey(0) if key is None else key
    self._adopt_flat_params(add_lora(self._flat_params_view(), rank, key))
    self._train_state = None  # param structure changed: new opt state + jits

  # ------------------------------------------------- multi-LoRA (ISSUE 15)

  def enable_multi_lora(self, capacity: int | None = None, rank: int | None = None, host_budget_bytes: int | None = None):
    """Turn on batched multi-LoRA serving: install all-zero STACKED adapter
    leaves ``{wq,wv}_alora_{a,b} [L, n_slots, ...]`` on the LORA_TARGETS
    projections (slot 0 stays zero = base model) and build the
    ``inference/adapters.py`` registry over them. Returns the registry, or
    None when ``XOT_TPU_LORA=0`` (byte-identical base serving — the hook is
    never traced). Capacity rounds UP to a power of two: slot count and
    rank are compiled shapes, so adapter loads/evictions afterwards are
    pure content swaps — never a recompile.

    Single-device fused path only (the same reach as the fused batched
    programs); MLA models are refused (their LoRA targets map onto the
    latent up-projections the per-row hook does not cover)."""
    from .adapters import ADAPTER_TARGETS, AdapterRegistry, lora_capacity, lora_enabled, lora_rank, round_pow2

    if not lora_enabled():
      return None
    existing = getattr(self, "adapter_registry", None)
    if existing is not None:
      return existing
    if self.cfg is None or self.params is None:
      raise RuntimeError("load a model before enabling multi-LoRA")
    if self.cfg.is_mla:
      raise ValueError("multi-LoRA serving does not support MLA models (wq/wv targets map onto latent projections)")
    if self._pp is not None or self.mesh is not None:
      raise ValueError("multi-LoRA serving requires the single-device fused path (no pp/sp/tp serving mesh)")
    cap = round_pow2(capacity) if capacity else lora_capacity()
    rank = int(rank or lora_rank())
    params = dict(self.params)
    geometry: dict = {}
    for stack in ("layers", "moe_layers"):
      if stack not in params:
        continue
      layers = dict(params[stack])
      geo: dict = {}
      for t in ADAPTER_TARGETS:
        w = layers.get(t)
        if w is None:
          continue
        L, d_in, d_out = int(w.shape[0]), int(w.shape[1]), int(w.shape[2])
        geo[t] = (L, d_in, d_out)
        layers[f"{t}_alora_a"] = jnp.zeros((L, cap, d_in, rank), self.cfg.dtype)
        layers[f"{t}_alora_b"] = jnp.zeros((L, cap, rank, d_out), self.cfg.dtype)
      if geo:
        params[stack] = layers
        geometry[stack] = geo
    if not geometry:
      raise ValueError("the loaded model has no LoRA target projections (wq/wv)")
    self.params = params
    self.adapter_registry = AdapterRegistry(
      geometry=geometry, rank=rank, capacity=cap, install=self._install_adapter_slot,
      host_budget_bytes=host_budget_bytes,
    )
    self._session_adapters: dict[str, str] = {}
    # Param structure changed: the pooled caches and every compiled serving
    # program re-trace against the new pytree.
    self.sessions.clear()
    self._drop_batched_server()
    return self.adapter_registry

  def _install_adapter_slot(self, slot: int, arrays: dict) -> None:
    """Registry install hook: functionally write one adapter's (rank-padded)
    factors into device slot ``slot`` of the stacked leaves. Content-only —
    shapes never change, so no compiled program invalidates; in-flight
    dispatches captured the previous leaf buffers (the leaves are never
    donated) and the next dispatch reads the fresh ones."""
    params = dict(self.params)
    for stack, per in arrays.items():
      layers = dict(params[stack])
      for t, (a, b) in per.items():
        la, lb = layers[f"{t}_alora_a"], layers[f"{t}_alora_b"]
        layers[f"{t}_alora_a"] = la.at[:, slot].set(jnp.asarray(a, la.dtype))
        layers[f"{t}_alora_b"] = lb.at[:, slot].set(jnp.asarray(b, lb.dtype))
      params[stack] = layers
    self.params = params

  def set_request_adapter(self, request_id: str, name: str | None) -> None:
    """Select a named adapter for a request served on the SOLO path (the
    batched scheduler takes the name through ``submit(adapter=...)``
    instead). Validated against the registry up front — an unknown name
    must 400 at the API, not fail mid-prefill."""
    if not name:
      return
    from .adapters import check_known

    check_known(getattr(self, "adapter_registry", None), name)
    adapters = getattr(self, "_session_adapters", None)
    if adapters is None:
      adapters = self._session_adapters = {}
    adapters[request_id] = name
    while len(adapters) > 1024:  # client-driven keyspace: stay bounded
      adapters.pop(next(iter(adapters)))

  def _acquire_session_slot(self, request_id: str) -> int:
    """Resolve (and pin) the solo session's adapter slot at session-creation
    time; 0 = base. Dead solo pins (sessions dropped without an unpin —
    replay-epoch invalidation, clear_session) are swept here, so a leaked
    pin can never permanently shrink the evictable slot set."""
    reg = getattr(self, "adapter_registry", None)
    name = getattr(self, "_session_adapters", {}).get(request_id)
    if reg is None or name is None:
      return 0
    for holder in reg.pinned_holders():
      if isinstance(holder, tuple) and holder[0] == "solo" and holder[1] != request_id and holder[1] not in self.sessions:
        reg.unpin(holder)
    return reg.acquire(name, holder=("solo", request_id))

  def _session_adapter_ids(self, session, B: int):
    if not getattr(session, "adapter_slot", 0):
      return None
    return jnp.full((B,), int(session.adapter_slot), dtype=jnp.int32)

  async def score_tokens(self, shard: Shard, tokens, n_scored: int, top_n: int):
    """Post-hoc logprobs for the last ``n_scored`` tokens (OpenAI logprobs).

    One cache-less parallel forward over prompt+completion
    (models/decoder.py score_last_tokens). Returns (chosen_logprobs [n],
    top_ids [n, top_n], top_logprobs [n, top_n]) as numpy, or None when this
    engine can't score (partial ring shards lack the head). Mesh serving
    modes score through the flat params view (pp stage stacks reassemble
    with the layer axis still sharded)."""
    if self.cfg is None or (self._pp is None and self.params is None):
      return None
    eff = self._effective_shard
    if eff is None or not (eff.is_first_layer and eff.is_last_layer):
      return None
    from ..models.decoder import score_last_tokens

    toks = np.asarray(tokens, dtype=np.int32).reshape(-1)
    S = int(toks.shape[0])
    if n_scored <= 0 or n_scored >= S:
      return None
    pad_to = _round_up(S, PREFILL_BUCKET)
    buf = np.zeros((1, pad_to), dtype=np.int32)
    buf[0, :S] = toks
    # n_scored and top_n are STATIC to the compiled program — bucket both so
    # per-request completion lengths / top-N choices don't each trigger a
    # full-forward recompile; the excess rows/columns slice off below.
    n_bucket = min(_round_up(int(n_scored), 32), pad_to - 1)

    def run():
      # The flat view (and its first-call reassemble jit on pp meshes) is
      # device work — it belongs on the engine's single executor thread.
      params = self._flat_params_view()
      out = score_last_tokens(params, self.cfg, eff, jnp.asarray(buf), jnp.int32(S), n_bucket, 20)
      chosen_lp, top_ids, top_lp = (np.asarray(x) for x in out)
      n, t = int(n_scored), max(int(top_n), 1)
      return chosen_lp[-n:], top_ids[-n:, :t], top_lp[-n:, :t]

    return await asyncio.get_event_loop().run_in_executor(self.executor, run)

  # Ring pipeline training (train/trainer.py ring section): partial-shard
  # spans — forward ships activations, backward applies this span's update.

  async def forward_span(self, request_id, shard, x, train: bool):
    from ..train.trainer import engine_forward_span

    return await asyncio.get_event_loop().run_in_executor(self.executor, engine_forward_span, self, shard, x, request_id, train)

  async def backward_span(self, request_id, shard, d_out, opt="adamw", lr=1e-5):
    from ..train.trainer import engine_backward_span

    return await asyncio.get_event_loop().run_in_executor(self.executor, engine_backward_span, self, shard, d_out, request_id, opt, lr)

  async def last_span_step(self, request_id, shard, h, targets, lengths, train: bool, opt="adamw", lr=1e-5):
    from ..train.trainer import engine_last_span_step

    return await asyncio.get_event_loop().run_in_executor(
      self.executor, engine_last_span_step, self, shard, h, targets, lengths, train, opt, lr
    )

  def discard_span(self, request_id) -> None:
    from ..train.trainer import engine_discard_span

    engine_discard_span(self, request_id)

  def pop_span_aux(self, request_id) -> float:
    """This span's coef-scaled MoE aux loss (0.0 for dense models): the Node
    adds it to the loss riding the ring reply so the reported training loss
    equals the single-node CE + moe_aux_loss_coef * sum(aux) objective."""
    from ..train.trainer import engine_pop_span_aux

    return engine_pop_span_aux(self, request_id)

  async def save_checkpoint(self, shard: Shard, path: str | Path) -> None:
    # PP mode saves the REASSEMBLED flat tree, so a pipeline-trained
    # checkpoint restores into any serving mode (and vice versa).
    from ..train.checkpoint import save_params

    def run():
      save_params(self._flat_params_view(include_vision=True), path)

    await asyncio.get_event_loop().run_in_executor(self.executor, run)

  async def load_checkpoint(self, shard: Shard, path: str | Path) -> None:
    from ..train.checkpoint import load_params

    def run():
      loaded = load_params(path, self._flat_params_view(include_vision=True))
      self._adopt_flat_params(loaded)  # drops stale KV sessions + batch pool
      self._train_state = None  # resumed opt state must not mix with the old

    await asyncio.get_event_loop().run_in_executor(self.executor, run)
