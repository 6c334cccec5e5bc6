"""Backend indirection for the continuous-batching scheduler.

``BatchedServer`` (batch_scheduler.py) drives five device operations: cache
and page-pool creation, slot/page prefill, and the fused chunk decode. This
module provides them behind one small interface so the SAME scheduler loop
serves both layouts. The decode ops share one contract across every backend:
``(tokens [B, chunk], next_token [B, 1], positions [B], cache)`` — the
``next_token`` handle stays ON DEVICE so the scheduler's one-chunk-lookahead
pipeline can dispatch chunk N+1 from chunk N's outputs while chunk N's
tokens stream back to the host:

- ``DecoderBatchOps`` — the single-device path (models/decoder.py fused
  programs), used whenever the engine runs without a serving mesh.
- ``PPBatchOps`` — the pp-pipelined path (parallel/pp_batch.py): cache
  sharded over pipeline stages, B streams overlapping across stages. Slots
  are rounded UP to a multiple of pp so the rows split into equal groups.

The engine picks one in ``JaxShardedInferenceEngine.batch_ops``.

Since ISSUE 6 the contract also carries the KV memory hierarchy's page
copies: ``read_pages`` starts a batched device→host gather of pool pages
(async D2H — the host tier's spill path) and ``write_pages`` scatters host
page data back into freshly allocated pages (the restore path). Both are
generic over the pool's dict-of-leaves layout (inference/kv_tier.py
``gather_pages``/``scatter_pages``), so the pp/sp placed pools inherit them
— the page axis is global across every backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class _PageCopyMixin:
  """Spill/restore page copies shared by every backend: the pool leaves all
  keep the page axis at position 1 regardless of placement."""

  def read_pages(self, pool, pages):
    from .kv_tier import gather_pages

    return gather_pages(pool, pages)

  def write_pages(self, pool, pages, data):
    from .kv_tier import scatter_pages

    return scatter_pages(pool, pages, data)

  def fused_sampling_supported(self) -> bool:
    """Whether this backend has the fused prefill+sampling programs
    (ISSUE 11). Default False: the pp/sp mesh backends still prefill and
    sample in two dispatches (their placed programs have no sampling
    epilogue yet) — the scheduler falls back to ``sample_rows``."""
    return False

  def mixed_tick_supported(self) -> bool:
    """Whether this backend has the mixed prefill+decode tick program
    (ISSUE 14). Default False: the pp/sp mesh backends keep the alternating
    prefill-dispatch / decode-dispatch schedule — the scheduler falls back
    automatically."""
    return False

  def lora_supported(self) -> bool:
    """Whether this backend's programs take the per-row ``adapter_ids``
    operand (ISSUE 15). Default False: the pp/sp mesh backends have no
    adapter integration — ``enable_multi_lora`` refuses mesh serving
    anyway, and the scheduler only threads ids when this is True."""
    return False


class DecoderBatchOps(_PageCopyMixin):
  """Single-device batched serving ops (the default).

  Since ISSUE 7 this is also the one backend that supports BATCHED
  SPECULATIVE decoding: when the engine carries a draft
  (``XOT_TPU_SPEC_DECODE=int8`` self-draft or ``XOT_TPU_SPEC_DRAFT`` cross
  model), ``spec_batch_decode``/``spec_paged_batch_decode`` run the
  draft-then-verify chunk (models/decoder.py) with the draft's own dense
  slot cache created/prefilled through ``init_draft_cache`` /
  ``prefill_draft_into_slots``. The pp/sp mesh backends report
  ``spec_supported() == False`` — their pipelined programs have no draft
  integration yet — and the scheduler falls back to plain chunks there."""

  def __init__(self, engine):
    self.engine = engine
    # A pool with per-slot recurrent state is written in place by its prefill too: a second copy of the state
    # (what a program that leaves its argument intact returns) does not fit beside the first. A failed prefill
    # then costs the pool, as a failed decode chunk does. Chosen with every load (the ops are rebuilt), and once more
    # where the pool is made (``init_pool``: a pool too large to hold twice).
    cfg = getattr(engine, "cfg", None)
    self._prefill_in_place(bool(cfg is not None and cfg.recurrent_layers))

  def _prefill_in_place(self, donates: bool) -> None:
    from ..models import decoder

    self.prefill_donates_pool = donates
    self._pages_many = decoder.prefill_into_pages_many_inplace if donates else decoder.prefill_into_pages_many
    self._pages_many_sampled = decoder.prefill_into_pages_many_sampled_inplace if donates else decoder.prefill_into_pages_many_sampled

  def round_slots(self, n: int) -> int:
    return n

  # ------------------------------------------------- batched speculation

  def spec_supported(self) -> bool:
    return getattr(self.engine, "_draft_params", None) is not None

  def spec_ngram_supported(self) -> bool:
    """Whether the DRAFT-FREE spec programs can run here (ISSUE 12): the
    fused spec programs need a full-model single-device backend, which is
    exactly what this class is — no draft model required. The pp/sp mesh
    backends have no spec integration at all (the mixin default)."""
    return True

  def draft_geometry(self):
    """(cfg_d, shard_d) of the draft — the target's own for a self-draft."""
    eng = self.engine
    return (getattr(eng, "_draft_cfg", None) or eng.cfg), (getattr(eng, "_draft_shard", None) or eng._effective_shard)

  def init_draft_cache(self, n_slots: int, max_seq: int):
    from ..models.decoder import init_kv_cache

    cfg_d, shard_d = self.draft_geometry()
    # The draft cache stays in model dtype regardless of XOT_TPU_KV_QUANT:
    # it is already small (the whole point of the draft), and quantizing it
    # would put int8 rounding between the draft's proposals and the target's
    # verification for no meaningful HBM win.
    cache = init_kv_cache(cfg_d, shard_d.n_shard_layers, n_slots, max_seq, quant="")
    place = getattr(self.engine, "_place_cache", None)
    return place(cache, cfg=cfg_d) if place is not None else cache

  def prefill_draft_into_slots(self, tokens, cache_d, rows, prompt_lens):
    from ..models.decoder import prefill_into_slots

    eng = self.engine
    cfg_d, shard_d = self.draft_geometry()
    _, cache_d = prefill_into_slots(
      eng._draft_params, cfg_d, shard_d, tokens, cache_d, jnp.asarray(rows, jnp.int32), jnp.asarray(prompt_lens, jnp.int32)
    )
    return cache_d

  def spec_batch_decode(self, token, cache, cache_d, positions, active, gammas, temps, top_ks, n_rounds: int, gamma_max: int, k_max: int, key, props=None, prop_counts=None, adapter_ids=None):
    from ..models.decoder import fused_spec_batch_decode

    eng = self.engine
    cfg_d, shard_d = self.draft_geometry()
    # cache_d=None dispatches the DRAFT-FREE program (ISSUE 12): the
    # scheduler passes it when no model-drafted row is in the chunk, so
    # n-gram-only dispatches never pay the draft rounds (and draft-free
    # engines have no draft params to pass at all).
    params_d = getattr(eng, "_draft_params", None) if cache_d is not None else None
    return fused_spec_batch_decode(
      eng.params, eng.cfg, eng._effective_shard, params_d, cfg_d, shard_d,
      token, cache, cache_d, positions, active, gammas, temps, n_rounds, gamma_max,
      top_k=top_ks, k_max=k_max, key=key, props=props, prop_counts=prop_counts, adapter_ids=adapter_ids,
    )

  def spec_paged_batch_decode(self, token, pool, cache_d, block_tables, positions, active, gammas, temps, top_ks, n_rounds: int, gamma_max: int, k_max: int, page_size: int, key, props=None, prop_counts=None, adapter_ids=None):
    from ..models.decoder import fused_spec_paged_batch_decode

    eng = self.engine
    cfg_d, shard_d = self.draft_geometry()
    params_d = getattr(eng, "_draft_params", None) if cache_d is not None else None
    return fused_spec_paged_batch_decode(
      eng.params, eng.cfg, eng._effective_shard, params_d, cfg_d, shard_d,
      token, pool, cache_d, block_tables, positions, active, gammas, temps, n_rounds, gamma_max,
      top_k=top_ks, k_max=k_max, page_size=page_size, key=key, props=props, prop_counts=prop_counts, adapter_ids=adapter_ids,
    )

  def lora_supported(self) -> bool:
    """Multi-LoRA (ISSUE 15): this single-device backend threads the traced
    per-row adapter index through every fused program once the engine has
    built its registry (jax_engine.enable_multi_lora)."""
    return getattr(self.engine, "adapter_registry", None) is not None

  def init_cache(self, n_slots: int, max_seq: int):
    from ..models.decoder import init_kv_cache

    eng = self.engine
    return init_kv_cache(eng.cfg, eng._effective_shard.n_shard_layers, n_slots, max_seq)

  def init_pool(self, n_pages: int, page_size: int, n_slots: int = 0):
    """``n_slots``: the rows whose recurrent state rides in the pool (a configuration with recurrent layers)."""
    from ..ops.paged import init_paged_pool

    eng = self.engine
    pool = init_paged_pool(eng.cfg, eng._effective_shard.n_shard_layers, n_pages, page_size, n_slots=n_slots)
    # A pool of more than a quarter of the device's memory is written in place by its prefill too, whatever it holds:
    # the program that leaves its argument intact returns a second copy, and beside the weights and a prefill group's
    # temporaries that copy has no room (64 contexts of Laguna-XS.2's first stage: 5.4 GB of pages beside 7.7 GB of
    # weights). Asked of the device once a pool; a CPU states no limit and keeps the copying program.
    limit = 0 if self.prefill_donates_pool else (jax.local_devices()[0].memory_stats() or {}).get("bytes_limit") or 0
    if limit and 4 * sum(leaf.size * leaf.dtype.itemsize for leaf in pool.values()) > limit:
      self._prefill_in_place(True)
    return pool

  def prefill_into_slots(self, tokens, cache, rows, prompt_lens, adapter_ids=None):
    from ..models.decoder import prefill_into_slots

    eng = self.engine
    return prefill_into_slots(
      eng.params, eng.cfg, eng._effective_shard, tokens, cache, jnp.asarray(rows, jnp.int32), jnp.asarray(prompt_lens, jnp.int32), adapter_ids
    )

  def prefill_into_pages_many(self, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int, adapter_ids=None, slot_rows=None):
    """``slot_rows`` [K]: each row's slot, for a pool that holds per-slot state — whose program is the one
    that writes the pool in place (``prefill_donates_pool``)."""
    eng = self.engine
    return self._pages_many(
      eng.params, eng.cfg, eng._effective_shard, tokens, pool, jnp.asarray(bt_rows, jnp.int32),
      jnp.asarray(prefix_lens, jnp.int32), jnp.asarray(prompt_lens, jnp.int32), int(page_size), adapter_ids,
      slot_rows if slot_rows is None else jnp.asarray(slot_rows, jnp.int32),
    )

  # ------------------------------------------- fused sampling epilogue
  # (ISSUE 11): prefill + first-token sampling in ONE dispatch. Only this
  # single-device backend has the fused programs; pp/sp report
  # fused_sampling_supported() == False and keep the two-dispatch path.

  def fused_sampling_supported(self) -> bool:
    return True

  def prefill_into_slots_sampled(self, tokens, cache, rows, prompt_lens, temps, top_ks, k_max: int, key, adapter_ids=None):
    from ..models.decoder import prefill_into_slots_sampled

    eng = self.engine
    return prefill_into_slots_sampled(
      eng.params, eng.cfg, eng._effective_shard, tokens, cache, jnp.asarray(rows, jnp.int32),
      jnp.asarray(prompt_lens, jnp.int32), jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32), key, int(k_max), adapter_ids,
    )

  def prefill_into_pages_many_sampled(self, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int, temps, top_ks, k_max: int, key, adapter_ids=None, slot_rows=None):
    eng = self.engine
    return self._pages_many_sampled(
      eng.params, eng.cfg, eng._effective_shard, tokens, pool, jnp.asarray(bt_rows, jnp.int32),
      jnp.asarray(prefix_lens, jnp.int32), jnp.asarray(prompt_lens, jnp.int32), int(page_size),
      jnp.asarray(temps, jnp.float32), jnp.asarray(top_ks, jnp.int32), key, int(k_max), adapter_ids,
      slot_rows if slot_rows is None else jnp.asarray(slot_rows, jnp.int32),
    )

  def batch_decode(self, token, cache, positions, active, temps, top_ks, n_steps: int, k_max: int, key, adapter_ids=None):
    from ..models.decoder import fused_batch_decode

    eng = self.engine
    return fused_batch_decode(
      eng.params, eng.cfg, eng._effective_shard, token, cache, positions, active, temps, n_steps,
      top_k=top_ks, k_max=k_max, key=key, adapter_ids=adapter_ids,
    )

  def paged_batch_decode(self, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, key, adapter_ids=None):
    from ..models.decoder import fused_paged_batch_decode

    eng = self.engine
    return fused_paged_batch_decode(
      eng.params, eng.cfg, eng._effective_shard, token, pool, block_tables, positions, active, temps, n_steps,
      top_k=top_ks, k_max=k_max, page_size=page_size, key=key, adapter_ids=adapter_ids,
      experts_visited=True,  # a fifth result: the chunk's count of expert visits, read back beside its tokens
    )

  # ------------------------------------------------- mixed tick (ISSUE 14)

  def mixed_tick_supported(self) -> bool:
    """The mixed prefill+decode program needs the full-model single-device
    fused path (same reach as the spec programs); MLA models stay on the
    alternating schedule (no paged multi-token prefill composition), and so
    does a configuration with recurrent layers (a slice would have to leave
    its state where the decode half of the same program cannot touch it)."""
    return not self.engine.cfg.is_mla and not self.engine.cfg.recurrent_layers

  def mixed_paged_batch_decode(self, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, key, pf_tokens, pf_bt, pf_prefix, pf_end, adapter_ids=None, pf_adapter=None):
    from ..models.decoder import fused_mixed_paged_batch_decode

    eng = self.engine
    return fused_mixed_paged_batch_decode(
      eng.params, eng.cfg, eng._effective_shard, token, pool, block_tables, positions, active, temps,
      pf_tokens, pf_bt, pf_prefix, pf_end, n_steps,
      top_k=top_ks, k_max=k_max, page_size=page_size, key=key, adapter_ids=adapter_ids, pf_adapter=pf_adapter,
      experts_visited=True,
    )


class PPBatchOps(_PageCopyMixin):
  """Batched serving over the pp pipeline (parallel/pp_batch.py)."""

  def __init__(self, engine, pp_batched):
    self.engine = engine
    self.pp = pp_batched

  def spec_supported(self) -> bool:
    return False  # no draft integration in the pipelined programs (yet)

  def round_slots(self, n: int) -> int:
    p = self.pp.n_stages
    return ((max(n, p) + p - 1) // p) * p

  def init_cache(self, n_slots: int, max_seq: int):
    from ..models.decoder import init_kv_cache

    eng = self.engine
    return self.pp.place_cache(init_kv_cache(eng.cfg, eng._effective_shard.n_shard_layers, n_slots, max_seq))

  def init_pool(self, n_pages: int, page_size: int):
    from ..ops.paged import init_paged_pool

    eng = self.engine
    return self.pp.place_pool(init_paged_pool(eng.cfg, eng._effective_shard.n_shard_layers, n_pages, page_size))

  def prefill_into_slots(self, tokens, cache, rows, prompt_lens):
    return self.pp.prefill_into_slots(tokens, cache, rows, prompt_lens)

  def prefill_into_pages_many(self, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int):
    return self.pp.prefill_into_pages_many(tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size)

  def batch_decode(self, token, cache, positions, active, temps, top_ks, n_steps: int, k_max: int, key):
    return self.pp.batch_decode(token, cache, positions, active, temps, top_ks, n_steps, k_max=k_max, key=key)

  def paged_batch_decode(self, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, key):
    return self.pp.paged_batch_decode(
      token, pool, block_tables, positions, active, temps, top_ks, n_steps, k_max=k_max, page_size=page_size, key=key
    )


class SPBatchOps(_PageCopyMixin):
  """Batched serving over the sp x tp mesh (parallel/sp_batch.py): dense
  slot cache (sequence axis over sp) or the default paged pool (page-slot
  axis striped over sp — global page ids, host allocator unchanged)."""

  def __init__(self, engine, sp_batched):
    self.engine = engine
    self.sp = sp_batched

  def spec_supported(self) -> bool:
    return False  # no draft integration over the sp mesh (yet)

  def round_slots(self, n: int) -> int:
    return n

  def init_cache(self, n_slots: int, max_seq: int):
    from ..models.decoder import init_kv_cache

    eng = self.engine
    return self.sp.place_cache(init_kv_cache(eng.cfg, eng._effective_shard.n_shard_layers, n_slots, max_seq))

  def init_pool(self, n_pages: int, page_size: int):
    from ..ops.paged import init_paged_pool

    eng = self.engine
    return self.sp.place_pool(init_paged_pool(eng.cfg, eng._effective_shard.n_shard_layers, n_pages, page_size))

  def prefill_into_slots(self, tokens, cache, rows, prompt_lens):
    return self.sp.prefill_into_slots(tokens, cache, rows, prompt_lens)

  def prefill_into_pages_many(self, tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size: int):
    return self.sp.prefill_into_pages_many(tokens, pool, bt_rows, prefix_lens, prompt_lens, page_size)

  def batch_decode(self, token, cache, positions, active, temps, top_ks, n_steps: int, k_max: int, key):
    return self.sp.batch_decode(token, cache, positions, active, temps, top_ks, n_steps, k_max=k_max, key=key)

  def paged_batch_decode(self, token, pool, block_tables, positions, active, temps, top_ks, n_steps: int, k_max: int, page_size: int, key):
    return self.sp.paged_batch_decode(
      token, pool, block_tables, positions, active, temps, top_ks, n_steps, k_max=k_max, page_size=page_size, key=key
    )
