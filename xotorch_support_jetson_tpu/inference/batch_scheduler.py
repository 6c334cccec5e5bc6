"""Continuous batching for single-node serving: a fixed pool of batch rows
("slots"), each holding one in-flight request.

The reference serves strictly one token step at a time per request around the
ring (``node.py:109-147``) — concurrent requests serialize. On TPU, decode is
weight-bandwidth-bound: stepping B rows costs almost exactly the same HBM
traffic as stepping one, so batching B concurrent requests multiplies
aggregate tokens/s by ~B. This scheduler keeps XLA happy with fully static
shapes:

- ONE pooled KV cache ``[L, n_slots, max_seq, H, hd]`` allocated up front;
- admission is BATCHED: all requests admissible at a chunk boundary prefill
  in ONE padded dispatch (``models/decoder.py prefill_into_slots`` /
  ``prefill_into_pages_many`` — row indices and prompt lengths are traced,
  so one compiled program per (row-bucket, pad-bucket) serves every
  combination). K concurrent arrivals cost ≈ one prefill's wall-clock
  instead of K serial dispatches — the p50-TTFT fix under load;
- long prompts prefill in CHUNKS (paged mode, ``XOT_TPU_PREFILL_CHUNK``
  tokens per tick, default 2048) with decode ticks interleaved, so one 32K
  arrival cannot stall every resident stream for its whole prefill — the
  paged prefill program natively resumes from a per-row prefix offset;
- decode runs ``fused_batch_decode`` chunks over ALL rows every tick with
  per-row positions/temperature/active mask — one compiled program total;
- admission happens between chunks: new requests claim free slots and
  prefill while other rows keep their state (their next chunk resumes from
  host-tracked positions);
- the decode loop is a ONE-CHUNK-LOOKAHEAD pipeline (default; escape hatch
  ``XOT_TPU_SCHED_LOOKAHEAD=0``): chunk N+1 dispatches immediately from
  chunk N's *device-resident* chain token (the fused programs return the
  next input token as a device handle — no host round trip), while chunk
  N's token buffer streams back via ``copy_to_host_async`` and the host
  does emit/EOS/stop/metrics bookkeeping concurrently. Correctness is by
  DROP-ON-READ: a row that finishes (EOS, max_tokens, cancel) inside chunk
  N was speculatively decoded one extra chunk — the host discards the
  overrun tokens and releases the row at the N+1 settle; page growth runs
  against dispatch-time positions, so a row always holds one extra chunk of
  page headroom and the speculative chunk can never overflow a block table.
  Membership changes (admission prefills, slot frees, preemption) happen
  only at dispatch boundaries, and an ADMISSION DOES NOT DRAIN the pipeline
  (ISSUE 51): at a boundary with chunk N in flight the admission pass runs
  while N computes — late in N's time (``_wait_late_into``), so that it
  sees what arrived during N as a pass at N's end did; everything it reads
  is host state that is exact while N flies (free slots, the free list,
  the queue) — and its prefill group G is staged and enqueued BEHIND N. The device executes one stream in order:
  G's pool operand is N's result, a device future, and its pages come off
  the free list, which holds no page whose garbage writes by N anyone can
  read (positions mask them, and G runs after N). N is then settled UNDER
  G; chunk N+1 is planned with G's rows already in their slots and
  enqueued behind G, their first tokens merged into its chain token ON THE
  DEVICE (``models/decoder.py merge_first_tokens``, enqueued with the
  group), so it waits for no readback either; and G is settled (first-token
  emit, EOS / ``max_tokens`` 1 / raced cancel) UNDER N+1. A row that ends
  at its first token is in N+1 all the same and is dropped on read like any
  overrun; a ``max_tokens`` of 1 is known at the plan and left inactive.
  Whatever N's settle frees is offered in a second pass before N+1 is
  planned, so no row joins a chunk later than under a drain at every
  boundary. A waiter whose prompt will be sliced into mixed ticks only
  claims its slot and pages in the pass and forces nothing. N is settled
  FIRST only where what comes next needs its settled state
  (``_needs_settled_state``, ``_group_settles_first`` and the checks after
  the plan in ``_run``): a QoS preemption, a graceful drain or migration
  and a disaggregation hand-off (they extract rows a chunk in flight
  holds), a parked waiter whose demand only N's finishing rows can cover
  (the pass behind N parks it again; N is settled and the pass repeated), a
  cancel on a prompt mid-prefill, a spec ↔ plain switch and n-gram rows
  (host proposals key on settled history), and
  ``XOT_TPU_SCHED_LOOKAHEAD=0``, the strictly synchronous tick that stays
  the reference schedule. A backlog with zero free slots chains chunk to
  chunk as before. ``sched_dispatches_total{queue="behind"|"empty"}`` says
  how often a dispatch rode behind one not yet read back.
  Greedy traffic is token-identical to the synchronous loop
  by construction (same compiled programs, same sampling; only the
  host/device schedule changes), and each SAMPLED request's stream is
  identical too — the key-split order is one split per dispatch on
  the event-loop thread, in the order N, G, N+1, and a speculative chunk's extra split happens only
  AFTER every emitted token of the finishing request. The one honest caveat:
  that extra split shifts the engine's key chain, so sampled requests
  arriving AFTER an EOS-triggered speculative chunk draw different (equally
  valid) subkeys than they would under ``XOT_TPU_SCHED_LOOKAHEAD=0`` — A/B
  comparisons of sampled traffic are per-request, not cross-request.

Speculative decoding is a FIRST-CLASS SCHEDULER MODE (``XOT_TPU_SPEC_BATCH``,
default auto — ISSUE 7): each decode tick dispatches a draft-then-verify
chunk (``models/decoder.py fused_spec_[paged_]batch_decode``): ``chunk``
rounds in which a proposer drafts up to gamma tokens per row, ONE batched
target forward verifies every row's window, and per-row accept/reject
becomes a variable advance on the paged pool — rejected tails are garbage
the next round's writes cover before any read (the same drop-on-read
argument as the lookahead pipeline). Since ISSUE 12 the PROPOSER is itself a
per-row adaptive choice: a loaded draft model ("model" —
``XOT_TPU_SPEC_DECODE=int8`` / ``XOT_TPU_SPEC_DRAFT``), the row's own
prompt-lookup suffix index ("ngram" — inference/ngram.py, zero device work,
zero KV pages, ``XOT_TPU_SPEC_NGRAM[_N/_MAX]`` knobs), or plain (gamma 0
inside the same program) — so ``auto`` speculates DRAFT-FREE when no draft
is configured. N-gram rows draft from a host-proposed reference stream that
keeps proposing round after round while the target stays on it (the LLMA
multi-round continuation); proposals key on SETTLED history, so chunks with
n-gram rows dispatch synchronously (the pipeline drains first). Depth is
adaptive PER ROW per proposer: an acceptance EWMA walks each row's gamma
through the policy table (inference/paging.py ``spec_adapt_gamma``; floor 0
→ ``spec_select_proposer`` probes the next proposer or parks the row on
plain; n-gram lookup misses charge the same zero observation so
non-repetitive rows stop paying the pipeline drain), interactive-class rows
demote later (accepted runs directly cut their ITL), and when every row sits
at gamma 0 the scheduler dispatches the PLAIN chunk program (re-probing
every ``XOT_TPU_SPEC_REPROBE`` plain chunks, each row on its best-ranked
proposer). Page growth and the context-window gate run against the chunk's
WORST-CASE advance (``spec_worst_advance`` — gamma-deep speculative
headroom); within ``spec_worst_advance`` tokens of the context window the
batch falls back to plain chunks so the window-end cutoff keeps plain-mode
chunk granularity. A loaded draft's dense slot cache rides next to the
target pool (prefilled at admission), and its HBM bytes enter the
pool-sizing block math so enabling speculation cannot oversubscribe
admission (``kv_draft_*`` gauges); DRAFT-FREE speculation holds no device
state — the gauges read 0, the page budget stays whole, and n-gram-only
chunks compile the draft-free program even when a draft is loaded. Greedy
streams are token-identical to the plain program by construction; sampled
rows always run gamma 0 and draw one sample per round (same key-split
schedule as plain chunks). ``XOT_TPU_SPEC_BATCH=0`` restores the plain
program byte-for-byte.

Admission runs through the QoS layer (inference/qos.py, ``XOT_TPU_QOS``,
default on): priority classes with anti-starvation aging, weighted-fair
tenant selection, per-tenant token-bucket rate limits, deadline-aware
shedding, and an overload policy that sheds/preempts ``batch`` work before
rejecting ``interactive`` requests — preempted rows re-enqueue and RESUME
token-identically (their prompt absorbs the tokens generated so far).
``XOT_TPU_QOS=0`` restores the plain FIFO ``asyncio.Queue`` byte-for-byte.

The page pool carries a KV MEMORY HIERARCHY (inference/kv_tier.py,
``XOT_TPU_KV_TIER``, default on): pages evicted from the device prefix-cache
LRU spill to a byte-budgeted host-RAM tier (batched gather +
``copy_to_host_async``) instead of vanishing, and admission restores
host-resident chain runs into fresh device pages — extending the device
prefix hit without recomputing those tokens' prefill. Release paths donate a
row's GENERATED pages too (under chain keys extended over the absorbed
stream), so a preempted row's resume and an idle multi-turn session's next
turn both find their whole history as a reusable prefix: preempt-resume
becomes transfer-cost instead of recompute-cost, and parked sessions survive
pool pressure host-side. ``XOT_TPU_KV_TIER=0`` restores the single-tier
behavior byte-for-byte (``_Request.carry_tokens`` recompute stays the
correctness fallback either way).

This module is the DEVICE-EXECUTION half of the scheduler (ISSUE 10 split):
the slot pool, the paged cache, dispatch/settle, and the lookahead pipeline.
Everything that happens BEFORE a request touches the device — the queue, the
QoS refusal ladder, parking, and the disaggregation placement policy — lives
in ``inference/sched_admission.py`` (``AdmissionControl``), which never
imports this module (``scripts/check_layering.py`` enforces the direction).

DISAGGREGATED PREFILL/DECODE (ISSUE 10, ``XOT_TPU_DISAGG=1`` +
``XOT_TPU_ROLE``): a request placed for remote decode (``_Request.
disagg_target``) prefills here as usual — chunked, into the paged pool —
while each completed chunk's full int8-KV pages stream to the decode node
over the gRPC tensor path (``kv_stream`` hook; the transfer overlaps the
remaining prefill chunks). After the final chunk samples the first token,
the row is EXTRACTED exactly like a drain migration (pages donated under
extended chain keys, prompt absorbs the token, ``carry_tokens`` carries the
emitted span) and handed to the decode node (``kv_handoff`` hook →
orchestration/node.py), whose admission finds the streamed pages in its
host tier and restore-adopts them — prefill there recomputes only the last
partial page. A dead decode target falls back to the local
``carry_tokens`` resume via the same ``_settle_migration`` path drain uses:
a prefilled context is never stranded. ``XOT_TPU_DISAGG=0`` (and unset) is
byte-identical to the colocated scheduler (test-pinned).

Enable with ``XOT_TPU_BATCHED=1`` (orchestration/node.py routes single-node
full-shard prompts here). ``XOT_TPU_BATCH_SLOTS`` (default 4) and
``XOT_TPU_BATCH_CHUNK`` (default 8) size the pool and the emission cadence.
"""

from __future__ import annotations

import asyncio
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..orchestration import slo
from ..orchestration.tracing import TERMINAL_STAGES, tracer
from ..utils.helpers import DEBUG
from ..utils.metrics import FRACTION_BUCKETS, metrics
from ..utils.programs import dispatch_context, ledger
from .engine import PromptTooLongError, RequestMigratedError, ServerOverloadedError
from .qos import DeadlineUnmeetableError
from .sched_admission import AdmissionControl, _Request
from .sched_clock import SchedClock

__all__ = ["BatchedServer", "_Request"]

PREFILL_BUCKET = 128

# spec_proposer{row} gauge encoding (ISSUE 12) — same 0/1/2 style as the
# node_role gauge: 0 = plain decode, 1 = n-gram prompt-lookup, 2 = model
# draft. Documented in the README metric table.
PROPOSER_CODE = {"plain": 0, "ngram": 1, "model": 2}


def _round_up(n: int, multiple: int) -> int:
  return ((n + multiple - 1) // multiple) * multiple


@dataclass
class _Ready:
  """A host-prepared admission awaiting its batched prefill dispatch (or,
  mid-chunked-prefill, its NEXT chunk dispatch — ``prefix_len`` advances to
  the end of each completed chunk)."""

  req: _Request
  row: int
  pad_to: int  # this request's own padded suffix length (current chunk)
  prefix_len: int = 0
  shared_pages: list = field(default_factory=list)
  new_pages: list = field(default_factory=list)
  chain_keys: list = field(default_factory=list)
  chunk_end: int = 0  # 0 = the dispatch covers the full prompt; else the chunk's end position


@dataclass
class _Slot:
  req: _Request
  pos: int  # next cache slot to write (== tokens absorbed)
  generated: int = 0
  last_token: int = 0
  finished: bool = False
  cancelled: bool = False
  out_tokens: list = field(default_factory=list)
  # Paged mode (inference/paging.py): reused read-only prefix pages, then the
  # request's private pages, in logical order; chain keys for every FULL
  # prompt page (private ones are donated to the prefix cache on finish).
  shared_pages: list = field(default_factory=list)
  pages: list = field(default_factory=list)
  chain_keys: list = field(default_factory=list)
  # Batched speculation (ISSUE 7/12): this row's current draft depth, its
  # active PROPOSER ("model" draft / "ngram" prompt-lookup / "plain"), the
  # per-proposer acceptance EWMAs that drive both choices
  # (inference/paging.py spec_adapt_gamma + spec_select_proposer), and the
  # row's own n-gram suffix index over prompt+generated history
  # (inference/ngram.py — None when the n-gram family is off or the row is
  # sampled).
  spec_gamma: int = 0
  spec_proposer: str = "plain"
  spec_ewmas: dict = field(default_factory=dict)
  ngram: object = None
  # perf_counter at the first emitted token (ISSUE 9): with the finish time
  # it yields the request's realized mean inter-token latency for goodput's
  # within-SLO check.
  t_first: float = 0.0
  # The row has its place and its prefill group is enqueued, but its first token is still on the device (ISSUE 51):
  # the group's settle reads it, emits it and resolves what it ends (``_confirm_admission``).
  first_pending: bool = False


@dataclass
class _Plan:
  """Dispatch-time snapshot for one decode chunk: who steps, who is
  page-starved, and each row's dispatch position (confirmed position plus
  the in-flight chunk's speculative advance under lookahead)."""

  rows: list  # [(row, _Slot)] resident at dispatch
  active: np.ndarray  # [B] bool
  starved: set  # rows resident but skipped this chunk (page-starved)
  positions: np.ndarray  # [B] int32 dispatch positions
  deadlocked: bool = False  # every resident row starved, nothing finishing
  gmax: int = 0  # >0: dispatch the SPEC program at this depth cap (ISSUE 7)
  # Mixed tick (ISSUE 14): (ready, start, end) — fuse this admission's
  # prefill slice [start, end) into the decode dispatch. None = plain tick.
  mixed: tuple | None = None


@dataclass
class _Chunk:
  """One dispatched decode chunk, possibly still executing on device.

  Holds what the settle pass needs: the device token buffer (its host copy
  already streaming back via ``copy_to_host_async``), the device-resident
  chain token that seeds the NEXT dispatch (never read back), and the
  dispatch-time plan so host bookkeeping runs against the state the compiled
  program actually saw — not against state that moved while it flew."""

  toks: object  # device [B, chunk] int32 ([B, rounds·(gamma_max+1)] for spec chunks)
  next_tok: object  # device [B, 1] int32 — chunk N+1's input token handle
  rows: list  # [(row, _Slot)] resident at dispatch
  active: np.ndarray  # [B] bool — rows that stepped in this chunk
  starved: frozenset
  tick: int = 0  # the scheduler tick that issued it (its ``xot.*`` spans and the ``decode_chunk`` stage carry the same number)
  # Batched speculation (ISSUE 7): variable-advance chunks. ``worst`` is the
  # chunk's worst-case per-row advance (== chunk for plain chunks) — what
  # the NEXT plan must assume while this chunk flies; ``counts``/``pos_dev``
  # are the device handles of the real per-row advance (settle reads counts;
  # a chained spec dispatch consumes pos_dev without a host round trip).
  spec: bool = False
  worst: int = 0
  rounds: int = 0
  counts: object = None  # device [B] int32 — valid tokens per row
  pos_dev: object = None  # device [B] int32 — post-chunk positions
  gammas: np.ndarray | None = None  # [B] dispatched depths (metrics/EWMA)
  # ISSUE 12: per-row proposer attribution for the settle's accounting —
  # which proposer drafted each row this chunk, and the device handle of the
  # per-row drafted-token totals (the acceptance-EWMA denominator; model
  # rows draft rounds·gamma, n-gram rows their consumed stream length).
  proposers: list | None = None  # [n_slots] "model"|"ngram"|"plain"
  n_prop: object = None  # device [B] int32 — tokens drafted per row
  # Mixed tick (ISSUE 14): the admission whose prefill slice rode this
  # dispatch. Its ``prefix_len`` advances to ``mixed_end`` at the settle, or
  # at the boundary before it when the loop looks for what to enqueue
  # behind this chunk (``_run``; ISSUE 51): the end is the host's own
  # number, and whatever reads the slice's pages runs after this chunk.
  mixed_ready: object = None  # _Ready | None
  mixed_start: int = 0
  mixed_end: int = 0
  mixed_pad: int = 0  # the padded slice the program ran (a power of two >= mixed_end - mixed_start)
  # Device int32 scalar: the distinct held experts the chunk's rows chose, summed over its expert layers and steps
  # (ops/moe.py): read back with the tokens, it feeds ``moe_experts_visited_total``. None: a program that does not count.
  experts_visited: object = None
  # No row of the dispatch's ``temps`` operand was positive: the program's own predicate (models/decoder.py
  # ``_next_token_batched``) was false at every step and no draw was taken (``decode_draw_skipped_chunks_total``).
  draw_skipped: bool = False


@dataclass
class _Group:
  """One dispatched prefill group, possibly still executing on the device: what its settle needs. The rows that
  sample were given their slots at the enqueue (``installed``), so that the next decode chunk could be planned and
  enqueued behind the group; their first tokens are read back here, after it."""

  members: list  # [_Ready]
  firsts: object  # device [K] int32: the sampled first tokens (garbage for a row of an intermediate chunk)
  tick: int
  installed: dict  # row -> _Slot, first token pending
  # Settled before the next chunk is planned, not under it: a member is handed to a decode node at its first token
  # (the hand-off extracts the row, which no chunk in flight may hold) or an n-gram index must see that token first.
  sync: bool = False


# A server of more than ``GROUP_SLOTS_WHOLE`` slots holds a prefill group to ``GROUP_ROWS`` rows, so that its prefill
# programs are those of 1, 2, 4 and 8 rows and no others; one of up to 16 slots never has a ready list split for its
# size, and groups as it always did (``_dispatch_groups``).
GROUP_SLOTS_WHOLE = 16
GROUP_ROWS = 8
# The admission pass behind a chunk lets a burst of arrivals finish landing (``_wait_late_into``): it runs once the
# newest arrival is ``BURST_QUIET_S`` old, and no later than ``BURST_WAIT_MAX_S`` after it could first have run. On the
# chip's host eight callers at once reach the queue over 8-13 ms with up to 6.7 ms between two of them, four over 2.5-5
# ms (PERF.md §6, PR 53's review round): the quiet is twice the widest gap seen.
BURST_QUIET_S = 0.012
BURST_WAIT_MAX_S = 0.04


class BatchedServer:
  """Owns the slot pool and the decode loop for one engine."""

  def __init__(self, engine, n_slots: int | None = None, chunk: int | None = None, top_k: int | None = None, max_queue: int | None = None, lookahead: bool | None = None, qos: "QosPolicy | bool | None" = None, spec_batch: bool | None = None):
    self.engine = engine
    # Device ops go through the engine's backend (inference/batch_ops.py):
    # single-device fused programs, or the pp-pipelined variants when the
    # engine serves over a pipeline mesh (slots round up to a multiple of pp).
    self.ops = engine.batch_ops
    self.n_slots = self.ops.round_slots(n_slots or int(os.getenv("XOT_TPU_BATCH_SLOTS", "4")))
    self.chunk = chunk or int(os.getenv("XOT_TPU_BATCH_CHUNK", "8"))
    self._expert_layers = 0  # expert layers a decode step passes: set with the pool (``_note_expert_form``)
    self._last_arrival = 0.0  # when the newest request was queued (``submit``): what ``_wait_late_into`` lets settle
    self._group_shapes: set[tuple[int, int, int]] = set()  # (rows, padded length, pages a row) of the paged prefill groups staged here: their programs exist (``_covered_rows``)
    # Per-request top_k IS honored (traced per row, like temperature —
    # ops/sampling.py sample_logits_per_row); only the candidate-set cap
    # ``k_max`` is static in the compiled program. Requests asking for more
    # than k_max candidates are clipped.
    self.k_max = top_k or int(os.getenv("XOT_TPU_BATCH_TOP_K_MAX", "64"))
    # Admission & placement layer (inference/sched_admission.py, ISSUE 10
    # split): owns the queue, the QoS refusal ladder, parking, and the
    # disagg placement policy. This execution layer drains it at dispatch
    # boundaries; the reverse import direction is lint-forbidden.
    self.admission = AdmissionControl(
      n_slots=self.n_slots,
      max_queue=max_queue if max_queue is not None else int(os.getenv("XOT_TPU_BATCH_MAX_QUEUE", "64")),
      qos=qos,
    )
    # Paged KV cache (default): positions map onto fixed-size pages through
    # per-row block tables (ops/paged.py), so HBM is bounded by aggregate
    # context — XOT_TPU_BATCH_PAGES sizes the pool (default: the dense
    # layout's HBM budget in PAGES, which under int8-KV quantization is 2x
    # the dense slot count's worth of contexts; see _ensure_cache) — and
    # page-aligned prompt prefixes dedup across requests. XOT_TPU_PAGED=0
    # restores the dense slot-per-max_seq cache.
    self.paged = os.getenv("XOT_TPU_PAGED", "1") not in ("0", "false")
    self.page_size = int(os.getenv("XOT_TPU_PAGE_SIZE", "64"))
    # Chunked prefill (paged mode): a prompt longer than this many tokens
    # prefills in chunks with DECODE TICKS interleaved between them, so one
    # very long arrival cannot stall every resident stream for its whole
    # prefill (the paged prefill program natively resumes from a per-row
    # prefix offset). 0 disables; dense mode always prefills whole (its
    # program has no resume offset — and it is the opt-in layout).
    self.prefill_chunk = int(os.getenv("XOT_TPU_PREFILL_CHUNK", "2048"))
    # Mixed prefill+decode ticks (ISSUE 14): while decode rows are resident,
    # a chunked prefill advances by a token-BUDGETED slice fused INTO the
    # batched decode dispatch (models/decoder.py
    # fused_mixed_paged_batch_decode) instead of stalling every resident
    # stream for a whole alternating prefill chunk. The budget is
    # SLO-driven (inference/paging.py select_mixed_budget: shrinks as the
    # interactive ITL burn rises, grows to XOT_TPU_PREFILL_CHUNK when
    # idle; XOT_TPU_MIXED_BUDGET force-pins). The FINAL slice — the one
    # that samples the first token — always dispatches through the
    # ordinary admission path, so first-token key-split semantics are
    # untouched. XOT_TPU_MIXED_TICK=0 restores the strictly alternating
    # schedule byte-for-byte (test-pinned).
    from .paging import mixed_tick_enabled

    self.mixed = mixed_tick_enabled()
    # Boundary-pass counter: identifies which _admit_pending pass an
    # admission belongs to (the deadline estimator's measured-drain EWMA
    # groups intra-pass admissions — wall-clock can't, since one pass's
    # _prepare calls may each do milliseconds of host-tier restore work).
    self._admit_pass = 0
    self._prefilling: list[_Ready] = []  # admissions mid-chunked-prefill (rows reserved)
    self.allocator = None
    self.block_tables = None
    self.cache = None
    # KV memory hierarchy (inference/kv_tier.py): host-RAM second tier under
    # the page pool. Created with the pool in _ensure_cache (paged mode +
    # XOT_TPU_KV_TIER, default on) and KEPT across cache rebuilds after a
    # device failure — host entries are content-addressed copies, still
    # valid against a fresh pool. Cleared at shutdown: a model swap changes
    # the KV content behind the same token chains.
    self.tier = None
    self.decode_path = "dense"  # resolved per pool config in _ensure_cache
    self.kv_quant = None  # resolved with the cache (None = not built yet)
    # Fused sampling epilogue (ISSUE 11): prefill + first-token sampling in
    # ONE device dispatch when the backend has the fused programs.
    # XOT_TPU_FUSED_SAMPLING=0 restores the two-dispatch path (the
    # token-identity A/B reference).
    self.fused_sampling = (
      os.getenv("XOT_TPU_FUSED_SAMPLING", "1") not in ("0", "false")
      and getattr(self.ops, "fused_sampling_supported", lambda: False)()
    )
    # Batched speculation (ISSUE 7, module docstring). ``spec_batch=None``
    # resolves from XOT_TPU_SPEC_BATCH (default auto: on exactly when the
    # engine carries a draft and the backend supports it); the final verdict
    # lands in ``self.spec`` at cache-build time — the draft cache's HBM
    # must enter the pool-sizing math before the pool exists.
    self._spec_batch_arg = spec_batch
    self.spec = False
    self.draft_cache = None
    self.spec_gamma_max = int(os.getenv("XOT_TPU_SPEC_BATCH_GAMMA", "0") or 0) or int(getattr(engine, "spec_gamma", 4))
    # Plain chunks between gamma-1 re-probes once every row has collapsed to
    # plain decode (0 disables re-probing).
    self.spec_reprobe = int(os.getenv("XOT_TPU_SPEC_REPROBE", "32"))
    self._spec_plain_chunks = 0
    # Draft-free proposers (ISSUE 12): which proposer families this server
    # can offer ("model" = loaded draft, "ngram" = the prompt-lookup index).
    # Resolved with the spec verdict at cache-build time; the n-gram knobs
    # are read here so one server's dispatches are self-consistent.
    from .ngram import ngram_knobs

    self.spec_proposers: tuple = ()
    self.spec_ngram_n, self.spec_ngram_max = ngram_knobs()
    # Host proposals staged by _spec_intent for the NEXT dispatch (row ->
    # int32 reference stream). Only ever populated with the pipeline
    # drained: n-gram proposals key on settled history, so a chunk with
    # n-gram rows always dispatches synchronously.
    self._spec_props: dict | None = None
    self._spec_needs_host = False
    self.max_seq = 0
    self.slots: list[_Slot | None] = [None] * self.n_slots
    self._loop_task: asyncio.Task | None = None
    # Disaggregated serving hooks (ISSUE 10), injected by the node layer:
    # ``kv_stream(request_id, target, keys, dev_leaves, n)`` schedules a
    # background KV-page transfer of one completed prefill chunk's pages;
    # ``kv_handoff(req, final_kv) -> awaitable[bool]`` flushes the last
    # pages and re-submits the extracted row to the decode node. Both None
    # (and every disagg branch dead) unless the node wired them.
    self.kv_stream = None
    self.kv_handoff = None
    # One-chunk-lookahead pipelined decode (module docstring): dispatch chunk
    # N+1 from chunk N's device-resident chain token while N's tokens stream
    # back and the host post-processes. XOT_TPU_SCHED_LOOKAHEAD=0 restores
    # the strictly synchronous tick (dispatch → readback → bookkeeping).
    if lookahead is None:
      lookahead = os.getenv("XOT_TPU_SCHED_LOOKAHEAD", "1") not in ("0", "false")
    self.lookahead = bool(lookahead)
    # Persistent per-row dispatch arrays, updated incrementally on admission
    # / advance / release — the dispatch path no longer rebuilds them from a
    # Python loop over every slot each tick.
    self._h_tokens = np.zeros((self.n_slots, 1), dtype=np.int32)
    self._h_positions = np.zeros((self.n_slots,), dtype=np.int32)
    self._h_temps = np.zeros((self.n_slots,), dtype=np.float32)
    self._h_top_ks = np.ones((self.n_slots,), dtype=np.int32)
    self._h_generated = np.zeros((self.n_slots,), dtype=np.int64)
    self._h_max_tokens = np.zeros((self.n_slots,), dtype=np.int64)
    self._h_occupied = np.zeros((self.n_slots,), dtype=bool)
    # Multi-LoRA (ISSUE 15): each row's device adapter slot (0 = base) —
    # the traced [B] index the fused programs gather per-row factors with.
    self._h_adapters = np.zeros((self.n_slots,), dtype=np.int32)
    # Page availability as of the last admission pass: the lookahead drain
    # gate retries parked requests only when this moves (_parked_admissible).
    self._parked_avail_seen: int = -1
    # The loop's wall clock by what it waits for (sched_clock.py): the one
    # subtraction per readback that feeds prefill_chunk_seconds /
    # decode_chunk_seconds / mixed_tick_seconds (device time, ready-to-ready
    # while the pipeline is full) and sched_wall_seconds_total{kind}; its
    # snapshots ride every request's ``decode`` and ``released`` stages.
    self.clock = SchedClock()
    # Ticks issued: one per program dispatch of the loop (plain, mixed, spec
    # or prefill group) — ``sched_ticks_total``, and the number its spans carry.
    self._tick = 0
    # Prefill groups enqueued and not yet settled, oldest first (ISSUE 51); empty at the top of every loop pass.
    self._pending: list[_Group] = []
    # The next decode chunk's input token with the pending groups' first tokens already merged in, on the device
    # (``merge_first_tokens``); None: the chunk takes the in-flight chunk's handle or the host's tokens as it always did.
    self._chain = None
    # Graceful drain (ISSUE 8): once draining, submit() refuses new work
    # (typed "draining" 429) and the loop's next dispatch boundary offers
    # every resident row to the migration callback exactly once; rows the
    # callback declines (or attempted past the drain deadline) re-enqueue
    # and finish locally via the carry_tokens resume machinery.
    self.draining = False
    self._migrate_cb = None
    self._drain_deadline = 0.0
    self._drain_attempted: set[str] = set()

  # --------------------------------------------- admission-layer delegation
  #
  # The queue-side state lives in the admission layer (ISSUE 10 split);
  # these views keep the execution code — and a decade of tests poking
  # ``server._parked`` — reading the same live objects.

  @property
  def qos(self):
    return self.admission.qos

  @property
  def queue(self):
    return self.admission.queue

  @property
  def max_queue(self) -> int:
    return self.admission.max_queue

  @max_queue.setter
  def max_queue(self, v: int) -> None:
    self.admission.max_queue = v

  @property
  def _parked(self):
    return self.admission.parked

  @property
  def _queued(self):
    return self.admission.queued

  @property
  def _cancelled_ids(self):
    return self.admission.cancelled_ids

  @property
  def _admitting(self):
    return self.admission.admitting

  def _queue_depth_ahead(self, ticket) -> int:
    return self.admission.queue_depth_ahead(ticket)

  # ------------------------------------------------------------- public API

  async def submit(self, request_id: str, tokens: np.ndarray, *, max_tokens: int, temp: float, top_k: int, eos_ids, emit, priority: str = "standard", tenant: str = "default", deadline_ms: float | None = None, carry: list | None = None, disagg_target: str | None = None, adapter: str | None = None) -> list:
    """Enqueue a request; resolves when it finishes. Tokens stream out via
    ``emit(request_id, new_tokens, finished)`` as chunks complete.

    ``priority`` / ``tenant`` / ``deadline_ms`` feed the QoS layer (rate
    limiting, deadline shedding, fair selection); all three are ignored when
    QoS is disabled. ``carry`` (ISSUE 10) marks a WIRE-CARRIED resume: the
    trailing ``len(carry)`` tokens of ``tokens`` were already streamed to
    the client by another node (the prefill node's first token), so emit
    skips them, ``max_tokens`` is the REMAINING budget, and no queue-wait/
    TTFT is re-observed here. ``disagg_target`` marks the request for
    remote decode after its local prefill (placement decided by the node —
    inference/sched_admission.py)."""
    tokens = np.asarray(tokens, dtype=np.int32).reshape(-1)
    ticket = self.admission.admit(
      request_id, int(tokens.shape[0]), int(max_tokens), priority, tenant, deadline_ms, draining=self.draining,
    )
    req = _Request(
      request_id=request_id,
      tokens=tokens,
      max_tokens=int(max_tokens),
      temp=float(temp),
      top_k=int(top_k),
      eos_ids=tuple(int(e) for e in eos_ids),
      emit=emit,
      future=asyncio.get_event_loop().create_future(),
      t_submit=0.0 if carry else time.perf_counter(),
      qos=ticket,
      disagg_target=disagg_target,
      adapter=adapter or None,
    )
    if carry:
      req.carry_tokens = list(carry)
    await self.admission.enqueue(req)
    self._last_arrival = time.perf_counter()
    self._update_gauges()
    if self._loop_task is None or self._loop_task.done():
      self._loop_task = asyncio.create_task(self._run())
    return await req.future

  def _preempt_victim_for(self, req) -> int | None:
    """Row of the resident slot a waiting ``req`` may preempt: the
    lowest-priority resident strictly below the waiter's class, tie-broken
    by most generated (the most over-budget row gives its slot back first).
    None when preemption is off or nothing outranked is resident."""
    if self.qos is None or not self.qos.cfg.preempt or req is None:
      return None
    ticket = getattr(req, "qos", None)
    if ticket is None:
      return None
    best = None
    for i, s in enumerate(self.slots):
      if s is None or s.finished or s.cancelled or s.first_pending:  # (a row whose first token is still on the device cannot be extracted)
        continue
      if s.pos + 1 >= self.max_seq:
        # The row is at the context window: it finishes imminently (freeing
        # the slot anyway), and its resume prompt could not re-admit.
        continue
      st = s.req.qos
      srank = st.rank if st is not None else 1
      if srank <= ticket.rank:
        continue
      key = (srank, s.generated)
      if best is None or key > best[0]:
        best = (key, i)
    return best[1] if best is not None else None

  def _extract_row(self, row: int, *, keep_kv: bool) -> "_Request":
    """Pull a resident row out of the pool for a carry_tokens resume
    (preemption or drain migration): its pages release now — donated under
    extended chain keys when ``keep_kv`` so the resume is transfer-cost —
    its prompt absorbs the tokens generated so far, and ``carry_tokens``
    carries the emitted span. The token-absorption/budget bookkeeping here
    is what makes every resume token-identical; both callers run only at a
    dispatch boundary with the pipeline drained, so no in-flight chunk
    references the row."""
    s = self.slots[row]
    req = s.req
    self._note_released(s)  # a resumed row has one residency per incarnation
    self._release_pages(s, extend=keep_kv)
    self.slots[row] = None
    self._clear_row(row)
    new_toks = s.out_tokens[len(req.carry_tokens):]
    if new_toks:
      req.tokens = np.concatenate([req.tokens, np.asarray(new_toks, np.int32)])
    req.carry_tokens = list(s.out_tokens)
    req.max_tokens -= s.generated
    req.t_submit = 0.0  # queue-wait/TTFT were already observed at first admission
    return req

  def _requeue_resumed(self, req: "_Request") -> None:
    """Re-enqueue an extracted row for a LOCAL resume (the policy —
    front-of-lane, aging restart — lives in the admission layer)."""
    self.admission.requeue_resumed(req)

  def _preempt_resume(self, row: int) -> None:
    """Preempt a resident row for higher-priority work and RE-ENQUEUE it
    (park-style, not a failure): the resumed prefill continues the stream
    token-identically (greedy: same logits from the recomputed cache)."""
    s = self.slots[row]
    metrics.inc("qos_preemptions_total")
    # With the KV tier on, the victim's pages — prompt AND generated — are
    # donated under extended chain keys: its resume finds the whole stream
    # as a reusable prefix (device-cached now, host-spilled under pressure)
    # and prefill recomputes only the last partial page. Resume becomes
    # transfer-cost instead of recompute-cost; carry_tokens stays the
    # fallback when every copy has been evicted.
    keep_kv = self.tier is not None and self.qos.cfg.preempt_spill
    tracer.stage(s.req.request_id, "preempted", {"row": row, "generated": s.generated, "resume": True, "kv": "tiered" if keep_kv else "recompute"})
    self._requeue_resumed(self._extract_row(row, keep_kv=keep_kv))

  def cancel(self, request_id: str) -> None:
    """Stop a request (client gone): its slot frees at the next chunk
    boundary; a queued request finishes at admission (looked up via the
    ``_queued`` side table — asyncio.Queue has no public scan API and its
    ``_queue`` deque is an implementation detail); a cancel racing a request
    that is mid-admission (between the queue and its slot, inside _admit's
    prefill) is remembered via ``_cancelled_ids``. Cancels for ids the
    scheduler has never seen are ignored — an unconditional record would
    grow without bound (every disconnect reaches here, including requests
    that never entered the pool)."""
    for slot in self.slots:
      if slot is not None and slot.req.request_id == request_id:
        slot.cancelled = True
        return
    for r in self._prefilling:
      if r.req.request_id == request_id:
        # Mid-chunked-prefill: settled (pages released) at the next tick's
        # continuation sweep in _admit_pending.
        self._cancelled_ids.add(request_id)
        return
    queued = self._queued.get(request_id)
    if queued is not None and not queued.future.done():
      queued.max_tokens = 0  # admitted-then-finished immediately
      # Poke the lookahead drain gate: a cancelled PARKED request must
      # settle at the next boundary's admission pass, not wait for the next
      # page-availability increase (which under saturation can be a whole
      # resident generation away).
      self._parked_avail_seen = -1
      return
    if request_id in self._admitting:
      self._cancelled_ids.add(request_id)

  def begin_drain(self, migrate=None, deadline_s: float = 20.0) -> None:
    """Enter graceful drain (ISSUE 8): stop admitting NEW work and, at the
    next dispatch boundary, offer each resident row to ``migrate`` — an
    async callback ``(req) -> bool`` that ships the row's ``carry_tokens``
    resume to a surviving peer (orchestration/node.py
    ``_migrate_batched_row``). Rows declined (no survivor, RPC failure, or
    past ``deadline_s``) re-enqueue and finish locally."""
    self.draining = True
    self._migrate_cb = migrate
    self._drain_deadline = time.perf_counter() + max(float(deadline_s), 0.0)
    self._parked_avail_seen = -1  # poke the lookahead drain gate

  def busy(self) -> bool:
    """Any work still resident, queued, parked, or mid-prefill? (The drain
    wait in ``Node.graceful_drain`` polls this.)"""
    return (
      any(s is not None for s in self.slots)
      or not self.queue.empty()
      or bool(self._parked)
      or bool(self._prefilling)
    )

  def _drain_pending(self) -> bool:
    return (
      self.draining
      and self._migrate_cb is not None
      and time.perf_counter() < self._drain_deadline
      and any(
        s is not None and not s.finished and not s.cancelled and s.req.request_id not in self._drain_attempted
        for s in self.slots
      )
    )

  async def _drain_migrate(self) -> None:
    """Offer every live resident row to the migration callback, once each.
    Runs only at a dispatch boundary with the pipeline drained (exactly the
    preemption contract), so no in-flight chunk references an extracted
    row. Extraction mirrors ``_preempt_resume``: pages release (donated
    under extended chain keys when the KV tier is on), the prompt absorbs
    the generated stream, and ``carry_tokens`` carries the emitted span —
    so whether the row ships out or re-enqueues locally, its continuation
    is token-identical."""
    for row, s in enumerate(list(self.slots)):
      if s is None or s.finished or s.cancelled:
        continue
      if s.req.request_id in self._drain_attempted or time.perf_counter() >= self._drain_deadline:
        continue
      self._drain_attempted.add(s.req.request_id)
      tracer.stage(s.req.request_id, "drain", {"row": row, "generated": s.generated})
      keep_kv = self.tier is not None and (self.qos is None or self.qos.cfg.preempt_spill)
      req = self._extract_row(row, keep_kv=keep_kv)
      # The migration RPC (send_tensor) resolves only when the SURVIVOR
      # finishes the whole continuation (ring span-tree semantics), so it
      # must not block this loop — remaining rows keep decoding while the
      # shipped row runs remotely. The extracted row is already safe to
      # hand off: no in-flight chunk references it.
      task = asyncio.ensure_future(self._migrate_cb(req))
      task.add_done_callback(lambda t, req=req: self._settle_migration(t, req))
    self._update_gauges()

  def _settle_migration(self, task, req: _Request) -> None:
    migrated = False
    if not task.cancelled():  # a cancelled migration (teardown) resumes locally too
      try:
        migrated = bool(task.result())
      except Exception:  # noqa: BLE001 — a failed migration finishes locally
        migrated = False
    if migrated:
      if not req.future.done():
        req.future.set_exception(RequestMigratedError(req.request_id))
      return
    if req.future.done():
      return  # torn down while the migration was in flight
    # No survivor took it: resume locally (carry_tokens recompute). A failed
    # DISAGG handoff pins the request local for good — re-placing it at the
    # resume's admission would retry the dead decode target once per
    # generated token (ISSUE 10 failure semantics: fall back, don't flap).
    req.disagg_target = None
    self._requeue_resumed(req)
    self._parked_avail_seen = -1  # poke the lookahead drain gate

  def shutdown(self) -> None:
    """Stop the decode loop and drop the pooled cache (model unload/reload).

    Thread-safe: callable from the engine's executor thread — the task
    cancel is marshalled onto the loop that owns it."""
    task = self._loop_task
    self._loop_task = None
    self.cache = None
    self.draft_cache = None
    if self.tier is not None:
      # A model swap invalidates the host tier's CONTENT (chain keys hash
      # token ids, not weights — the same chain under a new model must not
      # restore the old model's KV bytes).
      self.tier.clear()
    if task is not None and not task.done():
      task.get_loop().call_soon_threadsafe(task.cancel)

  # ------------------------------------------------------- kv tier plumbing

  def _tier_read(self, pages: list[int]):
    """Spill-side device read for the tier (batched gather + async D2H).
    None when the pool is already torn down (shutdown racing an eviction) —
    the tier degrades to plain eviction."""
    if self.cache is None:
      return None, 0
    return self.ops.read_pages(self.cache, pages)

  def _tier_write(self, pages: list[int], data: dict) -> None:
    """Restore-side device write: scatter host page data into freshly
    allocated pages. Donates the pool leaves — runs in the admission pass,
    on the loop thread while the executor is idle; with a chunk in flight
    the pool is that chunk's result and the write queues behind it, exactly
    like the prefill group that follows."""
    if self.cache is None:
      raise RuntimeError("page pool torn down under a restore")
    self.cache = self.ops.write_pages(self.cache, pages, data)

  def _stage_spill(self, request_id: str) -> None:
    """Attribute the tier's most recent eviction-spill burst to the request
    whose allocation forced it (the D2H sits in THAT request's latency)."""
    if self.tier is None:
      return
    last = self.tier.take_last_spill()
    if last is not None:
      tracer.stage(request_id, "spilled", last)

  # ------------------------------------------------- multi-LoRA (ISSUE 15)

  def _lora_active(self) -> bool:
    """Adapter-aware serving applies: the engine built its registry
    (jax_engine.enable_multi_lora) AND this backend's fused programs take
    the per-row index (DecoderBatchOps only — pp/sp keep base serving)."""
    return (
      getattr(self.ops, "lora_supported", lambda: False)()
      and getattr(self.engine, "adapter_registry", None) is not None
    )

  def _lora_acquire(self, req: _Request) -> None:
    """Resolve (and pin) the request's named adapter to a device slot at
    admission — a cold adapter is a host-restore or checkpoint load (a
    SWAP, measured in lora_swap_seconds), never a recompile. Unknown names
    raise the client-error type; a fully pinned slot set raises the
    retryable overload type. Both surface through _prepare's failure path
    (pages released, future failed) without touching the pool."""
    if not req.adapter:
      req.adapter_slot = 0
      return
    from .adapters import check_known

    reg = getattr(self.engine, "adapter_registry", None) if self._lora_active() else None
    check_known(reg, req.adapter)
    req.adapter_slot = reg.acquire(req.adapter, holder=req.request_id)

  def _lora_unpin(self, req: _Request | None) -> None:
    """Drop the request's slot pin (idempotent) — called from every path a
    row leaves the pool through (finish, cancel, extract, teardown), so the
    registry's LRU can never reassign a slot a resident row still indexes,
    and a departed row can never pin one forever."""
    if req is None or not getattr(req, "adapter", None):
      return
    reg = getattr(self.engine, "adapter_registry", None)
    if reg is not None:
      reg.unpin(req.request_id)

  # ---------------------------------------------------------------- loop

  def _ensure_cache(self):
    if self.cache is not None:
      return
    eng = self.engine
    from ..models.decoder import kv_quant_mode

    kv_quant = kv_quant_mode(eng.cfg)
    self.max_seq = min(eng.max_seq_len, eng.cfg.max_seq_len)
    # Batched speculation verdict (module docstring): needs the resolved
    # layout (the paged program excludes MLA) and must land BEFORE pool
    # sizing so the draft cache's bytes can enter the page budget.
    mode = os.getenv("XOT_TPU_SPEC_BATCH", "auto")
    want = self._spec_batch_arg if self._spec_batch_arg is not None else mode not in ("0", "false")
    # Proposer families (ISSUE 12): a loaded draft model offers "model";
    # the n-gram index offers "ngram" on any backend with the fused spec
    # programs — so XOT_TPU_SPEC_BATCH=auto speculates DRAFT-FREE when no
    # draft checkpoint is configured.
    from .ngram import ngram_enabled

    proposers = []
    if getattr(self.ops, "spec_supported", lambda: False)():
      proposers.append("model")
    if ngram_enabled() and getattr(self.ops, "spec_ngram_supported", lambda: False)():
      proposers.append("ngram")
    # A configuration with recurrent layers keeps, beside its pages, a per-slot state that pages do not carry
    # (cfg.recurrent_layers, the one property read here and at the three gates below): until that state can be
    # snapshotted and rolled back, whatever reuses, moves or rewinds pages without it is off.
    recurrent = bool(eng.cfg.recurrent_layers)
    if recurrent and not self.paged:
      raise ValueError("a configuration with recurrent layers is served over the page pool: unset XOT_TPU_PAGED=0")
    self.spec = bool(want) and bool(proposers) and not (self.paged and eng.cfg.is_mla) and not recurrent
    self.spec_proposers = tuple(proposers) if self.spec else ()
    draft_pages_equiv = 0
    if self.spec and "model" in self.spec_proposers:
      from .paging import kv_cache_bytes

      cfg_d, shard_d = self.ops.draft_geometry()
      draft_bytes = kv_cache_bytes(cfg_d, shard_d.n_shard_layers, self.n_slots * self.max_seq, "")
      page_bytes = max(kv_cache_bytes(eng.cfg, eng._effective_shard.n_shard_layers, self.page_size, kv_quant), 1)
      draft_pages_equiv = -(-draft_bytes // page_bytes)  # ceil
      metrics.set_gauge("kv_draft_bytes", draft_bytes)
      metrics.set_gauge("kv_draft_slots", self.n_slots)
      metrics.set_gauge("kv_draft_pages_equivalent", draft_pages_equiv)
    elif self.spec:
      # Draft-free speculation (ISSUE 12 satellite): the n-gram proposer
      # holds no device state — the draft gauges must READ ZERO and the
      # page budget below stays whole (nothing to deduct back from
      # admission).
      metrics.set_gauge("kv_draft_bytes", 0)
      metrics.set_gauge("kv_draft_slots", 0)
      metrics.set_gauge("kv_draft_pages_equivalent", 0)
    if self.paged:
      from .paging import PageAllocator, default_pool_pages, kv_cache_bytes, pages_to_cover

      ps = self.page_size
      self.pages_per_row = pages_to_cover(self.max_seq, ps)
      # Default pool size: the dense bf16 layout's HBM budget in PAGES of
      # the actual quant mode (inference/paging.py default_pool_pages — the
      # draft accounting below and the capacity tests pin the same block
      # math), so admission at large batch is bounded by paged block math
      # instead of dense-slot math.
      per_dense = default_pool_pages(eng.cfg, eng._effective_shard.n_shard_layers, self.n_slots, self.max_seq, ps, kv_quant)
      if draft_pages_equiv:
        # Draft-KV accounting (ISSUE 7): the draft cache rides in the SAME
        # HBM budget, so its page-equivalent comes out of the default pool —
        # enabling speculation cannot oversubscribe admission. Floored at
        # one row's window so a tiny test budget still serves; an explicit
        # XOT_TPU_BATCH_PAGES is the operator's own bookkeeping.
        per_dense = max(per_dense - draft_pages_equiv, self.pages_per_row + 1)
      if self._lora_active():
        # Adapter-stack accounting (ISSUE 15): the registry's pre-allocated
        # slot capacity rides in the same HBM budget — the adapter analogue
        # of the draft deduction (inference/paging.py lora_pages_equivalent),
        # with the same one-row floor.
        from .paging import lora_pages_equivalent

        page_bytes = max(kv_cache_bytes(eng.cfg, eng._effective_shard.n_shard_layers, ps, kv_quant), 1)
        lora_pages = lora_pages_equivalent(self.engine.adapter_registry.device_bytes(), page_bytes)
        if lora_pages:
          per_dense = max(per_dense - lora_pages, self.pages_per_row + 1)
      n_pages = int(os.getenv("XOT_TPU_BATCH_PAGES", "0")) or per_dense + 1
      self.allocator = PageAllocator(n_pages, ps)
      self.block_tables = np.zeros((self.n_slots, self.pages_per_row), dtype=np.int32)
      self.cache = self.ops.init_pool(n_pages, ps, **({"n_slots": self.n_slots} if recurrent else {}))
      metrics.set_gauge("page_pool_pages_total", n_pages - 1)  # page 0 = trash page
      from ..ops.paged import code_lanes_filled, paged_kernel_supported, state_leaves
      from ..ops.ssm import STATE_STEP_FORMS, state_step_form

      for name in ("k", "v"):  # what the decode kernel's DMAs carry of each code leaf's rows: under 1, the rest is padding made once a dispatch
        metrics.set_gauge("kv_page_lanes_filled", code_lanes_filled(self.cache[name]), labels={"leaf": name})

      state_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in state_leaves(self.cache).values())
      metrics.set_gauge("recurrent_state_bytes", state_bytes)
      if recurrent:  # which form the decode programs of this pool step the state in: what they will observe, asked once
        form = state_step_form(self.cache.get("ssm"), paged_kernel_supported(eng.cfg), eng.cfg.recurrent_kind)
        for name in STATE_STEP_FORMS:
          metrics.set_gauge("recurrent_state_step", int(name == form), labels={"form": name})
      self._note_expert_form()
      # The layers that own pages, by what their attention reads of them (``cfg.attn_windows``): a window layer's pages
      # stay resident for the whole context and the kernel reads the window's only (``_count_pages``).
      self._windows = eng.cfg.attn_windows
      for kind, n in (("full", sum(1 for w in self._windows if not w)), ("window", sum(1 for w in self._windows if w))):
        metrics.set_gauge("attention_layers", n, labels={"kind": kind})
      metrics.set_gauge("attention_window_tokens", max(self._windows, default=0))
      # ... and by whether their q and k carry a position term (``AttnKind.rope``; a model-level "nope" is every layer's)
      ropes = eng.cfg.attn_ropes
      for rope, n in (("rope", sum(ropes)), ("none", len(ropes) - sum(ropes))):
        metrics.set_gauge("attention_rope_layers", n, labels={"rope": rope})
      from .kv_tier import KvTierManager, kv_tier_enabled

      if recurrent:
        self.tier = None
        print(
          f"[batch_scheduler] {eng.cfg.recurrent_layers} of {eng.cfg.n_layers} layers keep a recurrent state per slot "
          f"({self.n_slots} slots of {state_bytes // self.n_slots} bytes, {state_bytes} in all, beside {n_pages - 1} pages of the {eng.cfg.n_attn_layers} attention layers): "
          "prefix reuse, the host KV tier, speculation and mixed ticks are off; a preempted row resumes by recomputing"
        )
      elif self.tier is None and kv_tier_enabled():
        self.tier = KvTierManager.from_env(page_size=ps, read_pages=self._tier_read, write_pages=self._tier_write)
      if self.tier is not None:
        # Rewire onto the (possibly rebuilt) allocator: device evictions
        # spill their pages host-side before the free list reuses them.
        self.allocator.spill_hook = self.tier.spill
        # The wire quant tag the adopt guard checks (ISSUE 11): a peer
        # streaming a different KV quant mode is refused up front.
        self.tier.kv_quant = kv_quant
    else:
      self.cache = self.ops.init_cache(self.n_slots, self.max_seq)
    if self.spec and "model" in self.spec_proposers:
      self.draft_cache = self.ops.init_draft_cache(self.n_slots, self.max_seq)
    # Decode-path attribution label for this pool's compiled chunk program:
    # what fused_paged_batch_decode resolves use_kernel=None to.
    from ..ops.paged import paged_kernel_supported

    self.kv_quant = kv_quant
    self.decode_path = "dense" if not self.paged else "kernel" if paged_kernel_supported(eng.cfg) else "gather"
    metrics.set_gauge("kv_quant_bits", {"": 16, "int8": 8, "int4": 4}[kv_quant])
    self._update_gauges()

  def _update_gauges(self) -> None:
    """Scheduler health gauges — refreshed at every loop boundary (cheap:
    a handful of dict writes)."""
    metrics.set_gauge("scheduler_batch_occupancy", sum(1 for s in self.slots if s is not None))
    metrics.set_gauge("scheduler_queue_depth", self.queue.qsize() + len(self._parked))
    metrics.set_gauge("scheduler_parked", len(self._parked))
    metrics.set_gauge("scheduler_prefilling", len(self._prefilling))
    metrics.set_gauge("scheduler_slots_total", self.n_slots)
    if self.paged and self.allocator is not None:
      total = max(self.allocator.n_pages - 1, 1)
      metrics.set_gauge("page_pool_pages_free", self.allocator.n_free)
      metrics.set_gauge("page_pool_pages_cached", self.allocator.n_available - self.allocator.n_free)
      metrics.set_gauge("page_pool_utilization", round(1.0 - self.allocator.n_available / total, 6))
    if self.qos is not None:
      for cls, depth in self.queue.class_depths().items():
        metrics.set_gauge("qos_queue_depth", depth, labels={"class": cls})

  def _next_tick(self) -> int:
    self._tick += 1
    metrics.inc("sched_ticks_total")
    self.clock.tick()
    return self._tick

  @contextmanager
  def _phase(self, name: str, **args):
    """One scheduler phase (admit | plan | stage | readback | settle) on two
    clocks (ISSUE 24): a ``xot.sched.<name>`` span in the profiler's trace, where
    it shares the device ops' clock and names the idle gap it overlaps, and the
    always-on ``sched_phase_seconds_total{phase}`` (host ``perf_counter``; free of
    the profiler, what an operator scrapes; the clock's snapshots carry the same
    sums). A ``TraceAnnotation`` belongs to one thread and must nest there: wrap
    synchronous sections only, never an ``await``. The one exception is
    ``xot.sched.idle`` around the loop's wait on its queue (``_run``): this task
    alone opens a span across an await, it resumes on the thread it left, and
    whatever else runs on that thread meanwhile is a synchronous section that
    begins after the span opened and ends before it closes, so the events still
    nest. ``stage`` on the executor thread runs to the end of the dispatch, so when
    a dispatch compiles its seconds hold the compile (``program_compile_seconds``).
    ``args`` land in the trace beside the device ops: ``tick`` and ``rows`` on every
    tick's spans, and on a MIXED tick's executor-side ``stage`` also ``pf_tokens`` (the
    slice's real tokens) and ``pf_pad`` (the padded slice its program runs) — what the
    ops under the ``mixed.prefill`` path component of that dispatch carried (ISSUE 55;
    a plain or speculative tick passes neither)."""
    t0 = time.perf_counter()
    try:
      with jax.profiler.TraceAnnotation(f"xot.sched.{name}", **args):
        yield
    finally:
      dt = time.perf_counter() - t0
      metrics.inc("sched_phase_seconds_total", dt, labels={"phase": name})
      self.clock.phase(name, dt)

  @staticmethod
  def _attributed(run, request_ids, tick: int):
    """Wrap an executor ``run`` closure in the program-ledger dispatch
    context (ISSUE 19): a compile happens synchronously inside the jitted
    call on the executor thread, so a thread-local set here is visible to
    ``tracked_jit`` — a post-steady recompile can then name the request(s)
    whose dispatch it stalled (flight ``compile`` event + timeline stage),
    and the dispatch's ``xot.program:*`` span the tick that issued it."""

    def wrapped():
      with dispatch_context(request_ids, tick=tick):
        return run()

    return wrapped

  # ------------------------------------------------ warmup manifest (ISSUE 19)

  def warmup_manifest(self) -> list[dict]:
    """The device-program families this config is expected to compile —
    keyed off the ACTIVE facets: batched backend (single-device vs pp/sp),
    paged vs dense KV, fused-sampling epilogue, spec / mixed / LoRA on or
    off. Pad buckets multiply *shapes within* a family, not families, so
    the manifest enumerates families and the warmup drives representative
    shapes through them."""
    ops_name = type(self.ops).__name__
    fams: list[dict] = []

    def add(family: str, why: str) -> None:
      fams.append({"family": family, "why": why})

    if ops_name == "PPBatchOps":
      add("pp.prefill_pages" if self.paged else "pp.prefill_slots", "pipeline-parallel batched prefill")
      add("pp.paged_decode" if self.paged else "pp.decode", "pipeline-parallel chunked decode")
    elif ops_name == "SPBatchOps":
      add("sp.prefill_pages" if self.paged else "sp.prefill_slots", "sequence-parallel batched prefill")
      add("sp.paged_decode" if self.paged else "sp.decode", "sequence-parallel chunked decode")
    else:
      if self.paged:
        add("prefill.pages_many_sampled" if self.fused_sampling else "prefill.pages_many", "paged batched prefill")
      else:
        add("prefill.slots_sampled" if self.fused_sampling else "prefill.slots", "dense batched prefill")
      if not self.fused_sampling:
        add("sample.rows", "unfused first-token sampling epilogue")
      if self.spec:
        add("spec.paged_batch" if self.paged else "spec.batch", "batched speculative decode (greedy rows)")
      if self.paged:
        add("decode.paged_batch", "paged batched decode")
        if self.mixed:
          add("decode.mixed_paged_batch", "mixed prefill+decode tick")
      else:
        add("decode.batch", "dense batched decode")
    return fams

  async def warmup(self) -> dict:
    """Pre-compile the manifest off the serving path (POST /v1/warmup):
    drive tiny synthetic requests through the REAL submit path — the same
    programs, shapes bucketed the same way — then mark the ledger steady so
    any later compile is a sentinel event. Best-effort: families the
    synthetic traffic cannot reach (e.g. the mixed tick needs a prefill
    arriving mid-decode) are reported ``warmed: false``."""
    manifest = self.warmup_manifest()
    before = ledger.dispatch_counts()
    before_s = {f["family"]: ledger.compile_count(f["family"]) for f in manifest}
    t0 = time.perf_counter()
    errors: list[str] = []

    def sink(_rid, _toks, _fin) -> None:
      return None

    async def one(tag: str, temp: float) -> None:
      try:
        await self.submit(
          f"_warmup-{tag}-{id(self):x}", np.ones((4,), dtype=np.int32),
          max_tokens=max(int(self.chunk), 1) + 1, temp=temp, top_k=5 if temp > 0 else 0,
          eos_ids=(), emit=sink,
        )
      except Exception as e:  # noqa: BLE001 — warmup must never take the API down
        errors.append(f"{tag}: {e!r}")

    await one("sampled", 0.7)
    if self.spec:
      # Spec programs only dispatch for greedy rows.
      await one("greedy", 0.0)
    total_s = time.perf_counter() - t0
    after = ledger.dispatch_counts()
    per_family_s: dict[str, float] = {}
    for entry in manifest:
      fam = entry["family"]
      entry["warmed"] = after.get(fam, 0) > before.get(fam, 0) or ledger.compile_count(fam) > before_s.get(fam, 0)
      snap_fam = ledger.snapshot()["families"].get(fam)
      if snap_fam:
        per_family_s[fam] = snap_fam["compile_s"]
    ledger.note_warmup(manifest, per_family_s, total_s)
    ledger.mark_steady(manifest)
    try:
      from ..orchestration.flightrec import flightrec

      flightrec.record("warmup", cause="v1_warmup", attributes={
        "families": [e["family"] for e in manifest],
        "warmed": [e["family"] for e in manifest if e.get("warmed")],
        "total_s": round(total_s, 6),
        "errors": errors,
      })
    except Exception:  # noqa: BLE001
      pass
    return {"manifest": manifest, "warmup_s": round(total_s, 6), "steady": True, "errors": errors}

  def stats_snapshot(self) -> dict:
    """Live capacity/pressure aggregates for this scheduler — the payload a
    replica advertises at ``GET /v1/router/stats`` (ISSUE 13). Read from
    the live objects, not the process-global gauges, so multiple servers in
    one process (tests, benches) each report their OWN state."""
    busy = sum(1 for s in self.slots if s is not None)
    depths = self.queue.class_depths() if self.qos is not None else {}
    waiting = self.admission.waiting()
    st = {
      "slots_total": self.n_slots,
      "slots_busy": busy,
      "slots_free": self.n_slots - busy,
      "queue_depth": dict(depths),
      "queue_depth_total": waiting,
      "prefilling": len(self._prefilling),
      "parked": len(self._parked),
      "page_size": self.page_size,
      "draining": bool(self.draining),
    }
    if self.allocator is not None:
      st["total_pages"] = max(self.allocator.n_pages - 1, 0)  # page 0 is the trash page
      st["free_pages"] = self.allocator.n_available
    if self._lora_active():
      # Router ADAPTER-affinity rung (ISSUE 15): which adapters are
      # DEVICE-resident here right now — a hit means zero swap, a miss a
      # host-restore/load, never a recompile. The full REGISTERED list
      # rides along for the front door's model-field alias check: a
      # registered-but-cold adapter must still resolve (and 400 only when
      # truly unknown), not silently serve base.
      st["lora_adapters"] = self.engine.adapter_registry.resident_names()
      st["lora_adapters_known"] = self.engine.adapter_registry.names()
    if self.qos is not None:
      est = self.qos.estimate_completion_ms(queue_depth=waiting, n_slots=self.n_slots, max_tokens=1)
      if est is not None:
        st["est_drain_ms"] = round(float(est), 1)
    return st

  def prefix_hexes(self, limit: int = 512) -> list[str]:
    """Chain-key hexes THIS server can actually serve as a prefix hit —
    device prefix cache first (newest donations first), then host-tier
    entries. Per-server state (unlike the process-global
    ``kv_tier.prefix_registry``), so a prefix-affinity router polling
    several replicas in one process sees who truly holds what."""
    keys: list[bytes] = []
    seen: set[bytes] = set()
    if self.allocator is not None:
      for k in self.allocator.cached_keys():
        if k not in seen:
          seen.add(k)
          keys.append(k)
    if self.tier is not None:
      for k in self.tier.host_keys():
        if k not in seen:
          seen.add(k)
          keys.append(k)
    return [k.hex() for k in keys[:limit]]

  def _page_window(self, end_pos: int) -> int:
    """Block-table width for a prefill dispatch covering ``[0, end_pos)``:
    pages needed, rounded UP to a power of two (bounds the compiled-shape
    count at log2(pages_per_row)) and clamped to the row maximum. The ONE
    bucketing both the alternating group dispatch and the mixed-tick slice
    staging use — the two paths' compiled-program shapes must stay in
    lockstep."""
    from .paging import pages_to_cover

    need = pages_to_cover(end_pos, self.page_size)
    mp_used = 1
    while mp_used < need:
      mp_used *= 2
    return min(mp_used, self.pages_per_row)

  def _group_rows(self, end_pos: int) -> int:
    """Most rows of a prefill group that covers ``[0, end_pos)`` on a server
    of more than ``GROUP_SLOTS_WHOLE`` slots: ``GROUP_ROWS`` while its page
    window is no wider than that of one chunk from position 0, and half as
    many for each doubling past it (a later chunk of long prompts). The
    program gathers every row's window of every attention layer before the
    first layer runs, so the group of ``GROUP_ROWS`` first chunks is the most
    K/V any group holds beside its activations. (8 rows x 4096 tokens of
    Olmo-Hybrid's 30 KV heads in three layers are 1.4 GB, twice what 8 first
    chunks gather, and XLA:TPU refuses that program beside the cell's pool
    by 52 MB; ISSUE 48.) A power of two, so the groups' programs stay those
    of 1, 2, 4 and 8 rows."""
    rows = GROUP_ROWS
    if self.paged and self.prefill_chunk > 0:
      while rows > 1 and rows * self._page_window(end_pos) > GROUP_ROWS * self._page_window(self.prefill_chunk):
        rows //= 2
    return rows

  def _free_slot(self, taken: frozenset | set = frozenset()) -> int | None:
    """The first row no request holds. A prompt mid-chunked-prefill holds its row without a slot: while it waits
    in ``_prefilling``, and while its chunk rides a pending group (``_collect_admissions`` swaps ``_prefilling``
    out and names those rows in ``taken``)."""
    held = {r.row for r in self._prefilling}  # (both lists are empty at most boundaries)
    held.update(r.row for g in self._pending for r in g.members if r.chunk_end)
    for i, s in enumerate(self.slots):
      if s is None and i not in taken and i not in held:
        return i
    return None

  def _prepare(self, req: _Request, row: int, *, reserve: int = 0, others_active: bool = False) -> tuple[str, _Ready | None]:
    """Host-side admission of one request: validate and allocate pages.

    Returns ``("ready", _Ready)`` when the request awaits the batched
    prefill dispatch; ``("done", None)`` when it settled synchronously (its
    future is resolved — cancelled while queued, or failed validation: a
    failed request never blocks the pool); ``("park", None)`` when pages
    are scarce while other requests hold them (``req.page_demand`` set for
    reserve accounting; re-registered in ``_queued`` NOW so a cancel landing
    before the re-park still finds it). ``reserve`` pages are kept back for
    earlier parked requests; ``others_active`` extends the "pages will
    recycle" test to admissions prepared in this same round but not yet
    dispatched."""
    self._queued.pop(req.request_id, None)
    shared_pages: list = []
    new_pages: list | None = None
    try:
      if req.max_tokens <= 0:  # cancelled while queued (or degenerate request)
        req.emit(req.request_id, [], True)
        if not req.future.done():
          req.future.set_result([])
        return "done", None
      if self.qos is not None and req.qos is not None and not req.carry_tokens and self.qos.deadline_expired(req.qos):
        # The deadline lapsed while the request waited: shed it at the slot
        # boundary instead of spending a prefill on a response its client
        # has already given up on. A preempted-and-resumed request (carry
        # tokens) is exempt — its client is already mid-stream, and a shed
        # here would break the resume guarantee.
        self.qos.refund(req.qos.tenant, int(req.tokens.shape[0]))  # never ran
        metrics.inc("qos_shed_total", labels={"reason": "deadline"})
        tracer.stage(req.request_id, "shed", {"reason": "deadline_expired", "class": req.qos.priority, "tenant": req.qos.tenant}, terminal=True)
        raise DeadlineUnmeetableError(
          f"deadline {req.qos.deadline_ms:.0f} ms expired while queued",
          retry_after_ms=self.qos.retry_after_ms(self.queue.qsize() + len(self._parked), self.n_slots),
        )
      S = int(req.tokens.shape[0])
      if S + 1 >= self.max_seq:
        if req.carry_tokens:
          # A resumed row whose absorbed stream reached the context window:
          # finish with what it already streamed (a "length" finish) — never
          # a client-error 400 for a request that was validly admitted.
          req.emit(req.request_id, [], True)
          if not req.future.done():
            req.future.set_result(list(req.carry_tokens))
          return "done", None
        # A too-long prompt is a client error, not an empty completion.
        raise PromptTooLongError(f"prompt of {S} tokens exceeds the {self.max_seq}-token context window")

      if not self.paged:
        # pad_to is computed per dispatch by _chunk_ready (the single source
        # of truth — chunking advances it as prefix_len grows).
        self._lora_acquire(req)
        self._note_admitted(req, row)
        return "ready", _Ready(req=req, row=row, pad_to=0)

      ps = self.page_size
      chain_keys = self.allocator.chain_keys(req.tokens, ps)
      # Reuse at most (S-1)//ps pages: at least one suffix token must run
      # through prefill to produce the last-position logits.
      # (No reuse for a configuration with recurrent layers: cached pages come without the state they belong to.)
      shared_pages = [] if self.engine.cfg.recurrent_layers else self.allocator.lookup_prefix(chain_keys[: (S - 1) // ps])
      prefix_len = len(shared_pages) * ps
      from .paging import pages_to_cover

      total = pages_to_cover(S + 1, ps)  # cover positions [0, S] (first generated token)
      need = total - len(shared_pages)
      new_pages = None if self.allocator.n_available - need < reserve else self.allocator.alloc(need)
      if new_pages is None:
        for p in shared_pages:
          self.allocator.release(p)
        shared_pages = []  # already released — the except handler must not release again
        if others_active or any(s is not None for s in self.slots):
          # Other requests are draining pages — park to retry at the next
          # chunk boundary, keeping arrival order.
          req.page_demand = need
          self._queued[req.request_id] = req
          if not req.t_parked:
            req.t_parked = time.perf_counter()
          metrics.inc("scheduler_parked_total")
          tracer.stage(req.request_id, "parked", {"page_demand": need})
          return "park", None
        raise ServerOverloadedError(f"prompt of {S} tokens cannot fit the page pool even when idle")
      self._stage_spill(req.request_id)  # evictions this alloc forced: D2H in THIS admission's latency
      new_pages = list(new_pages)
      if self.tier is not None and new_pages:
        # Host-tier restore: extend the device prefix hit with the longest
        # HOST-resident chain run — the leading fresh pages become restore
        # targets (written + adopted as cached read-only prefix pages, COW:
        # the host copies are retained) and prefill skips those tokens too.
        # A failed restore is only a missed optimization: the pages stay
        # private and prefill recomputes them (the correctness fallback).
        run = self.tier.host_run(chain_keys, len(shared_pages), (S - 1) // ps)
        # Pages evict in chain order, so a chain's SUFFIX can outlive its
        # evicted prefix in the device LRU: stop the run at the first key
        # still device-cached — adopt_restored requires the key be absent,
        # and those tokens recompute through prefill (re-linking the chain
        # for the next admission to hit whole).
        for j, key in enumerate(run):
          if self.allocator.is_cached(key):
            run = run[:j]
            break
        if run:
          dest = new_pages[: len(run)]
          try:
            self.tier.restore_into(run, dest, request_id=req.request_id)
          except Exception:  # noqa: BLE001
            pass
          else:
            for key, page in zip(run, dest):
              self.allocator.adopt_restored(key, page)
            shared_pages = shared_pages + dest
            del new_pages[: len(run)]
            prefix_len = len(shared_pages) * ps
        from .kv_tier import prefix_registry

        nxt = len(shared_pages)
        if nxt < (S - 1) // ps and prefix_registry.locate(chain_keys[nxt]):
          # Neither tier holds the next link locally, but a peer advertises
          # it: the hit a prefix-affinity router would have exploited.
          metrics.inc("kv_prefix_registry_hits_total", labels={"scope": "remote"})
      if shared_pages:
        metrics.inc("prefix_cache_hit_pages_total", len(shared_pages))
      self._lora_acquire(req)  # pin the adapter slot; failures release pages below
      self._note_admitted(req, row, shared=len(shared_pages), fresh=len(new_pages))
      return "ready", _Ready(
        req=req, row=row, pad_to=0, prefix_len=prefix_len, shared_pages=shared_pages,
        new_pages=new_pages, chain_keys=chain_keys,
      )
    except Exception as e:  # noqa: BLE001
      for p in shared_pages:
        self.allocator.release(p)
      if new_pages:
        # Still-private fresh pages (adopted restore targets have already
        # moved into shared_pages and released above): return them, or a
        # failed admission would shrink the pool permanently.
        self.allocator.free(new_pages)
      if not req.future.done():
        req.future.set_exception(e)
      if not isinstance(e, DeadlineUnmeetableError):
        # Deadline sheds are intentional QoS outcomes (already counted in
        # qos_shed_total); the failure counter must keep isolating real
        # admission errors (too-long prompts, page-pool exhaustion).
        metrics.inc("scheduler_admission_failures_total")
      self._cancelled_ids.discard(req.request_id)  # a raced cancel is moot now
      return "done", None

  def _note_admitted(self, req: _Request, row: int, shared: int = 0, fresh: int = 0) -> None:
    metrics.inc("scheduler_admissions_total")
    if self.qos is not None:
      # Measured admission cadence for the deadline estimator (ISSUE 14
      # satellite): only gaps taken while work was still waiting count, and
      # the pass id groups this boundary's batch into ONE observation.
      self.qos.note_admission(waiting=self.admission.waiting(), pass_id=self._admit_pass)
    if req.t_submit:
      metrics.observe_hist("queue_wait_seconds", time.perf_counter() - req.t_submit)
    if req.t_parked:
      # The page-starvation wait ends here: the timeline pairs this with the
      # first ``parked`` stage so /v1/requests/{id}/timeline answers "why
      # was this request slow" with the measured starvation span.
      tracer.stage(req.request_id, "unparked", {"waited_ms": round((time.perf_counter() - req.t_parked) * 1e3, 3)})
      req.t_parked = 0.0
    attrs = {"row": row, "shared_pages": shared, "new_pages": fresh}
    if req.qos is not None:
      attrs["class"] = req.qos.priority
      attrs["tenant"] = req.qos.tenant
    tracer.stage(req.request_id, "admitted", attrs)

  async def _admit_pending(self, woken: _Request | None = None, behind: _Chunk | None = None) -> None:
    """One admission pass: what it admits is staged and ENQUEUED (``_pending``), not awaited. ``behind`` is the decode
    chunk in flight, if any: the groups then queue behind it on the device."""
    with self._phase("admit"):
      ready = self._collect_admissions(woken, inflight=behind is not None)
    if ready:
      await self._dispatch(ready, behind)

  def _collect_admissions(self, woken: _Request | None, inflight: bool = False) -> list[_Ready]:
    """Collect every admissible request — parked (page-starved) first, in
    arrival order, then the queue — for ``_admit_pending`` to prefill in ONE batched dispatch
    (more only when the scatter-clamp grouping splits; see ``_dispatch``).
    ``woken`` is a request the idle wait already popped from the queue — it
    admits first. Every still-unmet parked request's page demand accumulates
    into ``reserve``: younger requests may only admit out of the surplus
    beyond it, so freed pages accumulate toward the parked requests instead
    of being consumed by later small prompts.

    ``inflight``: a decode chunk is on the device (ISSUE 51). Everything read here is host state that is exact all
    the same — free slots, the free list (the chunk's own growth was allocated at its dispatch), the queue — and
    whatever this pass sends to the device (a tier restore, the group) queues behind the chunk. The one thing it
    may not do is preempt: a victim's row is in that chunk. The loop sees the waiter that wants one
    (``_preempt_possible``), settles the chunk and passes again."""
    self._admit_pass += 1  # one boundary pass = one drain-cadence observation
    ready: list[_Ready] = []
    taken: set[int] = set()
    reserve = 0
    # Chunked-prefill continuations go FIRST: their rows/pages are already
    # committed, and each tick advances every in-flight prefill by one chunk
    # (a cancel that landed between chunks settles the request here).
    prefilling, self._prefilling = self._prefilling, []
    for r in prefilling:
      if r.req.request_id in self._cancelled_ids:
        self._cancelled_ids.discard(r.req.request_id)
        self._release_ready_pages(r)
        r.req.emit(r.req.request_id, [], True)
        if not r.req.future.done():
          r.req.future.set_result([])
        continue
      ready.append(r)
      taken.add(r.row)  # _prefilling was just emptied; keep the row reserved
    if woken is not None and (row := self._free_slot(taken)) is not None:
      status, r = self._prepare(woken, row)
      if status == "park":
        self._parked.append(woken)
      elif r is not None:
        ready.append(r)
        taken.add(row)
    scan = 0  # parked entries stay IN the deque while being retried, so a
    # teardown (_fail_all) or a concurrent submit's backpressure check
    # during the dispatch await still sees them; drop only on admission.
    while scan < len(self._parked) and (row := self._free_slot(taken)) is not None:
      req = self._parked[scan]
      status, r = self._prepare(req, row, reserve=reserve, others_active=bool(ready))
      if status == "park":
        reserve += req.page_demand
        scan += 1
        continue
      del self._parked[scan]
      if r is not None:
        ready.append(r)
        taken.add(row)
    if not inflight and self.qos is not None and not self.queue.empty() and self._free_slot(taken) is None:
      # Overload policy: a waiting request that outranks a resident row
      # preempts it (the row re-enqueues and resumes token-identically)
      # instead of queueing behind it — batch rows yield before interactive
      # work is rejected. One victim per boundary bounds the churn.
      victim = self._preempt_victim_for(self.queue.peek())
      if victim is not None:
        self._preempt_resume(victim)
    while (row := self._free_slot(taken)) is not None and not self.queue.empty():
      req = self.queue.get_nowait()
      status, r = self._prepare(req, row, reserve=reserve, others_active=bool(ready))
      if status == "park":
        self._parked.append(req)  # _prepare re-registered it in _queued
        break
      if r is not None:
        ready.append(r)
        taken.add(row)
    if self.allocator is not None:
      # Baseline for the lookahead drain gate: parked retries wait for the
      # NEXT availability change instead of replaying this pass's verdict.
      self._parked_avail_seen = self.allocator.n_available
    if ready and self._mixed_active() and any(s is not None for s in self.slots):
      # Mixed ticks (ISSUE 14): admissions whose remaining prompt exceeds
      # the per-tick budget don't dispatch an alternating prefill chunk —
      # they stage into ``_prefilling`` (rows/pages already committed) and
      # the tick planner fuses budgeted slices into the decode dispatches.
      # Final-slice-ready entries (and everything when no decode row is
      # resident) dispatch below as before.
      # Backlog counts every candidate this pass could stage: the budget
      # must see the pass's FULL depth, or the first deferral would be
      # sized for a backlog of one.
      budget = self._mixed_budget(backlog=max(len(ready), 1))
      still: list[_Ready] = []
      for r in ready:
        if self._mixed_defer(r, budget):
          self._prefilling.append(r)
        else:
          still.append(r)
      ready = still
    return ready

  def _chunk_ready(self, r: _Ready) -> None:
    """Set this dispatch's padded span (the ONE source of pad_to), capping
    long prompts to a chunk (paged mode): cover [prefix_len, chunk_end)
    only; the admission loop re-dispatches the rest next tick, with decode
    chunks interleaved. The pad stays inside the row's logical window —
    dynamic_update_slice CLAMPS out-of-range starts, which would silently
    corrupt slot 0 (_dispatch_groups enforces the same bound per group)."""
    S = int(r.req.tokens.shape[0])
    cap = self.prefill_chunk
    if not self.paged or cap <= 0 or S - r.prefix_len <= cap:
      r.chunk_end = 0
      r.pad_to = min(_round_up(max(S - r.prefix_len, 1), PREFILL_BUCKET), self.max_seq - r.prefix_len)
      return
    r.chunk_end = r.prefix_len + cap
    r.pad_to = min(_round_up(cap, PREFILL_BUCKET), self.max_seq - r.prefix_len)

  def _release_ready_pages(self, r: _Ready) -> None:
    """Free a not-yet-finished admission's pages (cancel or failure)."""
    self._lora_unpin(r.req)
    for p in r.shared_pages:
      self.allocator.release(p)
    if r.new_pages:
      self.allocator.free(r.new_pages)
    r.shared_pages, r.new_pages = [], []

  def _dispatch_groups(self, ready: list[_Ready]) -> list[list[_Ready]]:
    """Split admissions so every row in a group satisfies
    ``prefix_len + S_pad <= max_seq`` (the scatter-clamp constraint: a row
    reusing a long cached prefix cannot share a dispatch with a fresh long
    prompt). Groups are seeded longest-first, so each group's S_pad is its
    first member's pad_to; in practice one group.

    On a server of more than ``GROUP_SLOTS_WHOLE`` slots a group also holds
    at most ``GROUP_ROWS`` rows. A group is one program per (rows padded to
    a power of two, longest member), and a program first met in service
    stops every row for its compile (~10 s each at 64 slots): more rows are
    met only when a lump of callers ends together, once in minutes (PR 34).
    It also bounds the activations (8 rows x ``XOT_TPU_PREFILL_CHUNK``) and,
    by ``_group_rows``, the K/V windows the program gathers beside them.
    A server of up to 16 slots is never asked, so it groups as before."""
    groups: list[list[_Ready]] = []
    for r in sorted(ready, key=lambda x: x.pad_to, reverse=True):
      for g in groups:
        whole = self.n_slots <= GROUP_SLOTS_WHOLE or len(g) < self._group_rows(max(r.prefix_len, *(m.prefix_len for m in g)) + g[0].pad_to)
        if r.prefix_len + g[0].pad_to <= self.max_seq and whole:
          g.append(r)
          break
      else:
        groups.append([r])
    return groups

  async def _dispatch(self, ready: list[_Ready], behind: _Chunk | None = None) -> None:
    """Stage K prepared admissions and ENQUEUE their prefill, one device dispatch per group; the first tokens are
    read back, emitted and resolved at each group's settle (``_settle_groups``). All-or-nothing per group: a
    device failure fails every request in the group, releases their pages, and the pool keeps serving."""
    for r in ready:
      self._chunk_ready(r)  # cap long prompts to one prefill chunk per tick
      self._admitting.add(r.req.request_id)
    try:
      for group in self._dispatch_groups(ready):
        await self._enqueue_group(group, {r.row for r in ready}, behind)
    except BaseException as e:  # loop teardown mid-dispatch (CancelledError):
      # device errors are handled per group — only make sure no admitted
      # request's future leaks unresolved before the task dies. Their
      # adapter pins release too: these entries are in neither slots nor
      # _prefilling, so _fail_all's sweep would miss them and the pin would
      # outlive the server (the registry is engine-lifetime).
      for r in ready:
        self._admitting.discard(r.req.request_id)
        self._lora_unpin(r.req)
        if not r.req.future.done():
          r.req.future.set_exception(RuntimeError(f"batched server shut down mid-admission: {e!r}"))
      raise

  def _group_lora_kw(self, group: list[_Ready], n_rows: int) -> dict:
    """Per-row adapter slots for one prefill group (padding rows = base 0);
    empty when multi-LoRA is off so the dispatch signature — and therefore
    the compiled program — is byte-identical to pre-ISSUE-15 serving."""
    if not self._lora_active():
      return {}
    ad = np.zeros((n_rows,), dtype=np.int32)
    for i, r in enumerate(group):
      ad[i] = getattr(r.req, "adapter_slot", 0)
    return {"adapter_ids": jnp.asarray(ad)}

  def _row_bucket(self, K: int) -> int:
    """Round the admission batch up to a power of two (capped at n_slots) so
    a handful of compiled programs covers every batch size."""
    kpad = 1
    while kpad < K:
      kpad *= 2
    return max(min(kpad, self.n_slots), K)

  def _covered_rows(self, kpad: int, S_pad: int, pages: int) -> int:
    """The rows a paged prefill group of ``kpad`` rows is staged at. ``kpad`` itself — unless the server is one whose
    prefill programs are those of 1, 2, 4 and 8 rows and no others (more than ``GROUP_SLOTS_WHOLE`` slots) and has been
    warmed (``POST /v1/warmup`` marked the ledger steady: from then on a compile in service is a fault, the recompile
    sentinel's), no group of that shape has been staged on it, and one of MORE rows at the same padded length and
    page window has: then that one's, the fewest there are. A program first met in service stops every row for its
    compile (~10 s at 64 slots), and whatever was meant to meet it first — a warm-up's group of four that the admission
    pass cut into two and two (PR 53: [4, 640], [4, 896], [4, 1024] over two rounds) — may not have; the program of
    eight rows that stands ready costs such a group some tens of milliseconds of padding rows instead, every time that
    shape comes (which is why a server nobody declared warm compiles the exact shape, once, as it always did). Shapes
    are met smallest first wherever they are met in order, so a warm-up that missed nothing never takes this turn."""
    if self.n_slots <= GROUP_SLOTS_WHOLE or not ledger.steady or (kpad, S_pad, pages) in self._group_shapes:
      return kpad
    return min((k for k, s, p in self._group_shapes if k > kpad and (s, p) == (S_pad, pages)), default=kpad)

  def _stage_group(self, group: list[_Ready], all_rows: set[int], tick: int, chain_base=None):
    """Host operands of one prefill group and the ``run()`` closure that
    stages them on the executor thread and ENQUEUES the program: it returns
    the device handle of the first tokens without waiting for them
    (``_settle_group`` reads them back). ``chain_base`` not None: the closure
    also writes those tokens into the next decode chunk's chain token, on the
    device (``self._chain``; ``chain_base`` is what the chain starts from when
    no earlier group of this boundary has started it)."""
    eng = self.engine
    K = len(group)
    S_pad = max(r.pad_to for r in group)
    kpad = self._row_bucket(K)
    if self.paged:
      # The window must cover each row's PADDED write reach (the program
      # writes S_pad slots from prefix_len; pad garbage scatters to trash),
      # which the scatter-clamp grouping already bounds to max_seq.
      mp_used = self._page_window(max(int(r.prefix_len) for r in group) + S_pad)
      kpad = self._covered_rows(kpad, S_pad, mp_used)
      self._group_shapes.add((kpad, S_pad, mp_used))
    else:
      # Dense padding rows scatter garbage into a real slot, so each needs a
      # DISTINCT spare free slot (never a slot another admission owns —
      # scatter order between duplicate rows is undefined). Without enough
      # spares the batch stays exact-K: one more compiled variant, rare.
      spare = [i for i, s in enumerate(self.slots) if s is None and i not in all_rows]
      kpad = K + min(kpad - K, len(spare))
    n_rows = kpad
    tok = np.zeros((n_rows, S_pad), dtype=np.int32)
    prompt_lens = np.ones((n_rows,), dtype=np.int32)
    temps = np.zeros((n_rows,), dtype=np.float32)
    top_ks = np.ones((n_rows,), dtype=np.int32)
    for i, r in enumerate(group):
      # A chunked prefill covers [prefix_len, chunk_end) only; the final
      # chunk (chunk_end == 0) runs to the prompt's end and samples.
      end = r.chunk_end or int(r.req.tokens.shape[0])
      tok[i, : end - r.prefix_len] = r.req.tokens[r.prefix_len : end]
      prompt_lens[i] = end
      temps[i] = r.req.temp
      top_ks[i] = min(r.req.top_k, self.k_max)
    # Each sampling row's slot, for the merge of its first token into the chain token; the slot past the last
    # (dropped) for padding and for a row of an intermediate chunk, which samples nothing.
    merge_rows = np.full((n_rows,), self.n_slots, dtype=np.int32)
    merge_rows[:K] = [self.n_slots if r.chunk_end else r.row for r in group]

    def merge(firsts):
      if chain_base is not None:
        from ..models.decoder import merge_first_tokens

        self._chain = merge_first_tokens(jnp.asarray(self._chain if self._chain is not None else chain_base), firsts, merge_rows)
      return firsts

    if self.paged:
      # Truncate the gathered page window to this dispatch's span: the
      # prefill only reads/writes pages covering [0, max prompt_lens), so
      # gathering each row's full max_seq window would multiply KV-pool
      # copy traffic — by the chunk count for chunked prefills, and by
      # window/prompt for ordinary short-prompt admissions. Power-of-two
      # bucketing bounds the compiled-shape count at log2(pages_per_row).
      bts = np.zeros((n_rows, mp_used), dtype=np.int32)
      prefix_lens = np.zeros((n_rows,), dtype=np.int32)
      for i, r in enumerate(group):
        row_pages = (r.shared_pages + r.new_pages)[:mp_used]
        bts[i, : len(row_pages)] = row_pages
        prefix_lens[i] = r.prefix_len
      # Padding rows: all-zero block table (writes land in the trash page),
      # prefix 0, prompt_len 1.
      prompt_lens[K:] = 1
      state_kw = {}
      if eng.cfg.recurrent_layers:
        # The rows' slots, for the state-space layers' per-slot state: a padding row names the slot past the last,
        # so nothing is written for it. A prefill from position 0 starts its slot from zeros: the reset.
        slot_rows = np.full((n_rows,), self.n_slots, dtype=np.int32)
        slot_rows[:K] = [r.row for r in group]
        state_kw = {"slot_rows": slot_rows}
        metrics.inc("recurrent_state_resets_total", sum(1 for r in group if r.prefix_len == 0))

      # Key split on the EVENT-LOOP thread, before the dispatch crosses to
      # the executor: the worker thread never touches the engine's PRNG
      # chain, so concurrent single-stream requests (and the lookahead
      # pipeline) can't interleave splits (engine.split_key is locked too).
      sub = eng.split_key()
      draft_job = self._draft_prefill_job(group)
      lora_kw = self._group_lora_kw(group, n_rows)

      def run():
        # Fused sampling epilogue (ISSUE 11): prefill + first-token
        # sampling in ONE device dispatch — same _next_token_batched math
        # on the same key, so the unfused path below is token-identical
        # (A/B-pinned; XOT_TPU_FUSED_SAMPLING=0 restores it).
        with self._phase("stage", tick=tick, rows=K):
          if self.fused_sampling:
            firsts, self.cache = self.ops.prefill_into_pages_many_sampled(
              jnp.asarray(tok), self.cache, bts, prefix_lens, prompt_lens, self.page_size,
              temps, top_ks, self.k_max, sub, **lora_kw, **state_kw,
            )
            if draft_job is not None:
              draft_job()
          else:
            from ..models.decoder import sample_rows

            last, self.cache = self.ops.prefill_into_pages_many(
              jnp.asarray(tok), self.cache, bts, prefix_lens, prompt_lens, self.page_size, **lora_kw, **state_kw
            )
            if draft_job is not None:
              draft_job()
            firsts = sample_rows(last, sub, jnp.asarray(temps), jnp.asarray(top_ks), self.k_max)
          return merge(firsts)

    else:
      rows = np.asarray([r.row for r in group] + spare[: n_rows - K], dtype=np.int32)
      sub = eng.split_key()  # loop-thread split; the executor only runs device work
      draft_job = self._draft_prefill_job(group)
      lora_kw = self._group_lora_kw(group, n_rows)

      def run():
        # Prefill AND first-token sampling stay on the engine executor — the
        # single thread that serializes all device work.
        with self._phase("stage", tick=tick, rows=K):
          if self.fused_sampling:
            firsts, self.cache = self.ops.prefill_into_slots_sampled(
              jnp.asarray(tok), self.cache, rows, prompt_lens, temps, top_ks, self.k_max, sub, **lora_kw,
            )
            if draft_job is not None:
              draft_job()
          else:
            from ..models.decoder import sample_rows

            last, self.cache = self.ops.prefill_into_slots(jnp.asarray(tok), self.cache, rows, prompt_lens, **lora_kw)
            if draft_job is not None:
              draft_job()
            firsts = sample_rows(last, sub, jnp.asarray(temps), jnp.asarray(top_ks), self.k_max)
          return merge(firsts)

    # Stage marks go down BEFORE the dispatch so the timeline's
    # prefill_chunk duration covers the device work, not the gap after it.
    for r in group:
      end = r.chunk_end or int(r.req.tokens.shape[0])
      tracer.stage(r.req.request_id, "prefill_chunk", {"tokens": end - r.prefix_len, "batched_with": K - 1})
    return run

  def _note_dispatch(self, kind: str) -> None:
    """One program handed to the device: onto an empty queue, or BEHIND one the loop has not read back yet
    (``sched_dispatches_total{queue}`` and, on the clock's snapshots, ``dispatch_behind`` / ``dispatch_empty``; the
    ``behind`` share is how often the host's work rode under the device's). The clock's interval of the host ends
    here unless it is chained."""
    queue = "behind" if self.clock.queued else "empty"
    self.clock.inc("sched_dispatches_total", count=f"dispatch_{queue}", labels={"queue": queue})
    self.clock.dispatched(kind)

  def _group_settles_first(self, group: list[_Ready]) -> bool:
    """Must this group be settled BEFORE the next chunk is planned (and not under it)? Without the lookahead
    always: the synchronous tick is the reference schedule. With it, when a member's first token is needed on the
    host at once: a request placed for remote decode is extracted at its first token (no chunk may hold the row), and
    a server that proposes from n-gram indexes builds the row's index over that token before the plan asks it."""
    if not self.lookahead or "ngram" in self.spec_proposers:
      return True
    return self.kv_handoff is not None and self.paged and any(r.req.disagg_target for r in group)

  async def _enqueue_group(self, group: list[_Ready], all_rows: set[int], behind: _Chunk | None) -> None:
    """Stage one prefill group and enqueue it — behind the decode chunk in flight, if there is one: its pool operand
    is that chunk's result, a device future, so the runtime orders them. Nothing is awaited but the enqueue. The rows
    that sample take their slots NOW (``_install_admission``), so the next chunk's plan holds them."""
    eng = self.engine
    tick = self._next_tick()
    sync = self._group_settles_first(group)
    chain_base = None if sync else behind.next_tok if behind is not None else self._h_tokens
    with self._phase("stage", tick=tick, rows=len(group)):
      run = self._stage_group(group, all_rows, tick, chain_base)
    self._note_dispatch("prefill")
    try:
      firsts = await asyncio.get_event_loop().run_in_executor(
        eng.executor, self._attributed(run, [r.req.request_id for r in group], tick)
      )
    except Exception as e:  # noqa: BLE001
      self.clock.withdrawn()  # it never reached the device: what is in flight keeps the clock
      self._fail_group(group, {}, e)
      if getattr(self.ops, "prefill_donates_pool", False):
        raise  # the pool went into the failed call: the loop drops it and fails the rest, as after a failed decode chunk
      return
    installed = {r.row: self._install_admission(r) for r in group if not r.chunk_end}
    self._pending.append(_Group(members=group, firsts=firsts, tick=tick, installed=installed, sync=sync))

  def _fail_group(self, members: list[_Ready], installed: dict, e: Exception) -> None:
    """Fail every request of a prefill group and give its pages back (``installed``: row -> slot of the members
    that had taken their slots)."""
    for r in members:
      self._admitting.discard(r.req.request_id)
      slot = installed.get(r.row)
      if slot is None:
        self._release_ready_pages(r)
      else:  # the slot owns the pages since the install (the next chunk's plan may have grown them): all freed, none donated
        self._lora_unpin(r.req)
        for p in slot.shared_pages:
          self.allocator.release(p)
        if slot.pages:
          self.allocator.free(slot.pages)
        slot.shared_pages, slot.pages, slot.finished = [], [], True
        if self.slots[r.row] is slot:
          self.slots[r.row] = None
          self._clear_row(r.row)
      if not r.req.future.done():
        r.req.future.set_exception(e)
      self._cancelled_ids.discard(r.req.request_id)

  async def _settle_groups(self) -> None:
    """Read back and settle every pending group, oldest first (the device's order, and the clock's)."""
    self._chain = None  # whoever needed it has taken it (``_stage_decode``); from here the host's tokens hold the firsts
    while self._pending:
      await self._settle_group(self._pending[0])
      self._pending.pop(0)  # only now: a teardown inside the await still finds the group (``_fail_all``)

  async def _settle_group(self, g: _Group) -> None:
    """One prefill group's first tokens read back, and the host half of its admission: the emit of each first
    token, what that token ends, the next chunk of a long prompt. Under the lookahead this runs while the decode chunk
    enqueued behind the group computes; a row that ended at its first token is in that chunk all the same, and is
    dropped on read at the chunk's settle like any overrun."""

    def fetch():
      with self._phase("readback", tick=g.tick):  # the first tokens: waits for the prefill program
        return np.asarray(g.firsts)

    try:
      firsts = await asyncio.get_event_loop().run_in_executor(self.engine.executor, fetch)
    except Exception as e:  # noqa: BLE001
      self._fail_group(g.members, g.installed, e)
      if getattr(self.ops, "prefill_donates_pool", False):
        raise
      return
    finally:
      # Device idle from here until the next dispatch (unless one is queued behind) — closed on the failure
      # path too, or a failed prefill's interval would run on into the host's.
      prefill_dt = self.clock.ready()
      for r in g.members:
        self._admitting.discard(r.req.request_id)
    with self._phase("settle", tick=g.tick):
      metrics.observe_hist("prefill_chunk_seconds", prefill_dt)
      metrics.inc("prefill_chunks_total")
      for i, r in enumerate(g.members):
        if r.chunk_end:  # intermediate chunk: advance and re-queue; no sample
          r.prefix_len = r.chunk_end
          if r.req.disagg_target and self.kv_stream is not None and self.paged:
            # Disagg overlap (ISSUE 10): the chunk just written is final —
            # stream its full pages to the decode node NOW, while the
            # remaining prefill chunks still run, so the decode node's first
            # token never waits for the whole context to cross the wire.
            self._disagg_stream_chunk(r)
          self._prefilling.append(r)
          continue
        self._confirm_admission(r.row, g.installed[r.row], int(firsts[i]))

  def _draft_prefill_job(self, group: list[_Ready]):
    """Host-side prep of the draft prefill that rides the SAME executor
    dispatch as the target prefill (ISSUE 7): final-chunk admissions prefill
    their FULL prompt into the draft's dense slot cache in one padded
    forward. The draft has no prefix cache — it recomputes reused-prefix
    tokens too, which a ~4x-faster draft affords — and chunked long prompts
    draft-prefill ONCE, at the final chunk, rather than per chunk. Greedy
    identity never depends on this cache (verification is exact for any
    draft state); it only sets the acceptance rate."""
    if not self.spec or self.draft_cache is None:
      return None
    final = [r for r in group if not r.chunk_end and r.req.temp <= 0.0]
    if not final:
      return None
    d_pad = min(_round_up(max(int(r.req.tokens.shape[0]) for r in final), PREFILL_BUCKET), self.max_seq)
    dtok = np.zeros((len(final), d_pad), dtype=np.int32)
    dlens = np.ones((len(final),), dtype=np.int32)
    drows = np.asarray([r.row for r in final], dtype=np.int32)
    for i, r in enumerate(final):
      S = int(r.req.tokens.shape[0])
      dtok[i, :S] = r.req.tokens
      dlens[i] = S

    def job():
      self.draft_cache = self.ops.prefill_draft_into_slots(jnp.asarray(dtok), self.draft_cache, drows, dlens)

    return job

  def _install_admission(self, r: _Ready) -> _Slot:
    """Give an admission whose prefill group is enqueued its slot: everything the next chunk's plan and dispatch
    read of a row but its first token, which is still on the device and reaches that chunk there
    (``merge_first_tokens``). From here the slot owns the pages."""
    req = r.req
    slot = _Slot(
      req=req, pos=int(req.tokens.shape[0]), generated=1, first_pending=True,
      shared_pages=r.shared_pages, pages=list(r.new_pages), chain_keys=r.chain_keys,
    )
    if req.carry_tokens:
      # Resumed after a QoS preemption: the finish paths report carry + new
      # (``generated``/``max_tokens`` already net out the carried span).
      slot.out_tokens.extend(req.carry_tokens)
    if self.spec and req.temp <= 0.0:
      # Starting depth by QoS class (module docstring): interactive and
      # standard rows open at full depth — an accepted run directly cuts
      # their ITL — while batch-class rows start shallow and must EARN depth
      # through the acceptance EWMA (they only care about throughput, where
      # a mispredicting deep draft costs most). Sampled rows stay at 0.
      # Starting PROPOSER (ISSUE 12): the loaded draft keeps PR 7's behavior
      # when present; draft-free servers open on the n-gram proposer at its
      # own depth cap (proposals are free — a row only pays when a suffix
      # match actually fires). Per-row convergence from here is the policy's
      # job (spec_adapt_gamma + spec_select_proposer at every settle).
      cls = req.qos.priority if req.qos is not None else "standard"
      if "model" in self.spec_proposers:
        slot.spec_proposer = "model"
        slot.spec_gamma = max(self.spec_gamma_max // 2, 1) if cls == "batch" else self.spec_gamma_max
      else:
        slot.spec_proposer = "ngram"
        slot.spec_gamma = max(self.spec_ngram_max // 2, 1) if cls == "batch" else self.spec_ngram_max
    self.slots[r.row] = slot
    self._h_occupied[r.row] = True
    self._h_tokens[r.row, 0] = 0  # until the settle: the chunk behind the group takes the token from the device
    self._h_positions[r.row] = slot.pos
    self._h_temps[r.row] = req.temp
    self._h_top_ks[r.row] = min(req.top_k, self.k_max)
    self._h_generated[r.row] = slot.generated
    self._h_max_tokens[r.row] = req.max_tokens
    self._h_adapters[r.row] = getattr(req, "adapter_slot", 0)
    if self.paged:
      self.block_tables[r.row, :] = 0
      n = len(slot.shared_pages) + len(slot.pages)
      self.block_tables[r.row, :n] = slot.shared_pages + slot.pages
    return slot

  def _confirm_admission(self, row: int, slot: _Slot, first: int) -> None:
    """The first token is on the host: emit it and resolve what it ends (EOS, a ``max_tokens`` of 1, a raced
    cancel). A row that ends here may already be in the chunk enqueued behind its group; that chunk's settle drops it
    on read, and its pages go back only to dispatches the device runs after that chunk."""
    req = slot.req
    slot.first_pending = False
    slot.last_token = first
    slot.out_tokens.append(first)
    slot.t_first = time.perf_counter()
    if req.t_submit:
      ttft = slot.t_first - req.t_submit
      metrics.observe_hist("ttft_seconds", ttft)
      req.slo_ttft_s = ttft
      # Per-class TTFT (ISSUE 9): the SLO engine's burn-rate windows need
      # the class dimension the unlabeled histogram can't carry; a separate
      # family keeps the existing exposition and bench deltas untouched.
      slo.observe_ttft(self._slo_class(req), ttft)
    cancelled = slot.cancelled or req.request_id in self._cancelled_ids  # raced during prefill
    finished = cancelled or first in req.eos_ids or slot.generated >= req.max_tokens
    slot.finished = finished
    tracer.stage(req.request_id, "decode", {"first_token": int(first), "clock": self.clock.snapshot()})
    req.emit(req.request_id, [] if cancelled else [first], finished)
    if not cancelled:
      slo.note_tokens(self._slo_class(req), self._slo_tenant(req), 1)
    if finished:
      self._cancelled_ids.discard(req.request_id)
      self._release_pages(slot)
      self._note_complete(slot)
      if not req.future.done():
        req.future.set_result(slot.out_tokens)
      if self.slots[row] is slot:
        self.slots[row] = None
        self._clear_row(row)
      return
    self._h_tokens[row, 0] = first
    if self.spec and req.temp <= 0.0 and "ngram" in self.spec_proposers:
      from .ngram import NgramIndex

      slot.ngram = NgramIndex(self.spec_ngram_n)
      slot.ngram.extend(req.tokens)
      slot.ngram.extend([first])
    if req.disagg_target and self.kv_handoff is not None and self.paged:
      # Disaggregated decode (ISSUE 10): prefill is done and the first
      # token is sampled — hand the row to its decode node instead of
      # decoding here. Runs with nothing in flight (``_group_settles_first``),
      # so extraction is exactly the drain-migration contract.
      self._disagg_handoff(row)

  # ------------------------------------------------- disaggregation (ISSUE 10)

  def _disagg_read_pages(self, keys: list, pages: list):
    """Start a batched device→host read of full KV pages for the wire (the
    tier-spill gather path: fresh buffers, async D2H already in flight).
    Returns ``(keys, dev_leaves, n)`` or None on any failure — the stream
    is best-effort; a missed batch just means the decode node recomputes
    those tokens' prefill (the correctness fallback)."""
    if not keys or self.cache is None:
      return None
    try:
      dev, n = self.ops.read_pages(self.cache, pages)
    except Exception:  # noqa: BLE001 — transfer is an optimization, never a failure
      if DEBUG >= 1:
        import traceback

        print("[sched] disagg page read failed; decode node will recompute")
        traceback.print_exc()
      return None
    if dev is None:
      return None
    return list(keys), dev, n

  def _disagg_stream_chunk(self, r: _Ready) -> None:
    """Ship the full pages a completed (non-final) prefill chunk produced —
    called between chunks, so the transfer overlaps the rest of prefill."""
    full = min(r.prefix_len // self.page_size, len(r.chain_keys))
    if full <= r.req.kv_streamed:
      return
    batch = self._disagg_read_pages(
      r.chain_keys[r.req.kv_streamed:full], (r.shared_pages + r.new_pages)[r.req.kv_streamed:full],
    )
    if batch is None:
      return
    r.req.kv_streamed = full
    self.kv_stream(r.req.request_id, r.req.disagg_target, *batch)

  def _disagg_handoff(self, row: int) -> None:
    """Extract a freshly prefilled row and dispatch it to its decode node:
    read the not-yet-streamed full pages (the final flush rides WITH the
    handoff so adoption always precedes the decode node's admission),
    extract via the drain-migration mechanics (pages donated under chain
    keys — the local fallback resume stays transfer-cost), and resolve the
    handoff like a migration: success ⇒ the submit future gets
    ``RequestMigratedError`` and the stream continues from the decode node;
    failure ⇒ the row re-enqueues locally and a prefilled context is never
    stranded (ISSUE 10 failure semantics)."""
    s = self.slots[row]
    req = s.req
    full = min(s.pos // self.page_size, len(s.chain_keys))
    final_kv = None
    if full > req.kv_streamed:
      final_kv = self._disagg_read_pages(
        s.chain_keys[req.kv_streamed:full], (s.shared_pages + s.pages)[req.kv_streamed:full],
      )
      if final_kv is not None:
        req.kv_streamed = full
    tracer.stage(req.request_id, "disagg_handoff", {
      "row": row, "target": req.disagg_target, "pages_streamed": req.kv_streamed,
    })
    ex = self._extract_row(row, keep_kv=self.tier is not None)
    task = asyncio.ensure_future(self.kv_handoff(ex, final_kv))
    task.add_done_callback(lambda t, ex=ex: self._settle_migration(t, ex))
    self._update_gauges()

  def adopt_kv_wire(self, keys: list, leaves: dict, quant: str | None = None) -> int:
    """Decode-node receive side (ISSUE 10): adopt streamed KV pages into
    the host tier — the existing restore path then extends admission's
    device prefix hit with them, COW semantics and all. The tier is created
    lazily (pages can arrive before this node's first request builds the
    pool); a non-paged or tier-disabled scheduler adopts nothing (the
    handoff still lands and prefill recomputes — correctness never depends
    on the transfer). ``quant`` is the sender's KV quant-mode tag (ISSUE
    11) — a mismatch with this pool's mode refuses the batch BEFORE the
    tier's byte-geometry guard could be seeded with foreign-layout pages."""
    if not self.paged or self.engine.cfg.recurrent_layers:
      return 0
    if self.tier is None:
      from .kv_tier import KvTierManager, kv_tier_enabled

      if not kv_tier_enabled():
        return 0
      self.tier = KvTierManager.from_env(page_size=self.page_size, read_pages=self._tier_read, write_pages=self._tier_write)
      if self.kv_quant is None:
        # Pages can arrive BEFORE this node's first request builds the pool
        # (the disagg receive side) — resolve the mode the pool WILL use
        # eagerly (pure env/cfg), or the adopt guard would wave a mismatched
        # sender through exactly when the tier is empty and its
        # byte-geometry guard is still unseeded.
        from ..models.decoder import kv_quant_mode

        try:
          self.kv_quant = kv_quant_mode(self.engine.cfg)
        except Exception:  # noqa: BLE001 — engine without a cfg yet: guard stays inactive
          pass
      self.tier.kv_quant = self.kv_quant
      if self.allocator is not None:
        self.allocator.spill_hook = self.tier.spill
    return self.tier.adopt_wire(keys, leaves, quant=quant)

  @staticmethod
  def _slo_class(req: _Request) -> str:
    return req.qos.priority if req.qos is not None else "standard"

  @staticmethod
  def _slo_tenant(req: _Request) -> str:
    return req.qos.tenant if req.qos is not None else "default"

  def _note_released(self, slot: _Slot) -> None:
    """The row leaves its slot — finished, cancelled, preempted or drained. The
    timeline's ``released`` stage carries the loop's clock as the row's
    ``decode`` stage did at its first token: their difference is where this
    residency's wall time went (``resident_ms`` on the timeline)."""
    tracer.stage(slot.req.request_id, "released", {"generated": slot.generated, "clock": self.clock.snapshot()})

  def _note_complete(self, slot: _Slot) -> None:
    """The completion choke point: every finish passes here."""
    self._note_released(slot)
    self._slo_note_complete(slot)

  def _slo_note_complete(self, slot: _Slot) -> None:
    """Goodput accounting at the completion choke points (ISSUE 9): a
    finished request's tokens count as goodput only when BOTH realized
    latencies met the class objectives. ``slo_ttft_s`` survives
    preempt-resume, so the judged TTFT is the one the client saw.
    (Availability's GOOD event is counted once per client request at the
    API token choke point — the layer every serving path streams through —
    not here: the scheduler is one serving mode of several.)"""
    if not slo.slo_enabled():
      return
    req = slot.req
    cls, tenant = self._slo_class(req), self._slo_tenant(req)
    if tracer.terminal_of(req.request_id) in TERMINAL_STAGES:
      # A refusal terminal (e.g. the API stall watchdog's 'stalled')
      # already counted this request bad; a later local recovery finishing
      # the row must not put its tokens in goodput — the client's stream
      # ended in the 503.
      return
    n = len(slot.out_tokens)
    # Realized mean ITL over THIS incarnation's tokens only: t_first is the
    # resumed incarnation's first token, so dividing by the carried span
    # would bias a preempt-resumed request's ITL low by exactly the carry
    # factor and overstate goodput on preemption-heavy overload.
    n_new = n - len(req.carry_tokens)
    itl_s = None
    if slot.t_first and n_new > 1:
      itl_s = max(time.perf_counter() - slot.t_first, 0.0) / (n_new - 1)
    if slo.within_slo(cls, req.slo_ttft_s, itl_s):
      slo.note_good_tokens(cls, tenant, n)

  def _release_pages(self, slot: _Slot, extend: bool | None = None) -> None:
    """Return a finished slot's pages: shared prefix refs drop; private FULL
    prompt pages are donated to the prefix cache; the rest (partial prompt
    tail + generated positions) free immediately.

    Under the KV tier (``extend`` defaults to tier-enabled), the donation
    also covers the row's GENERATED full pages: chain keys extend over the
    absorbed stream (prompt ++ new tokens — O(new tokens), the running hash
    carries forward), so a preempted row's resume and a multi-turn session's
    next turn find the whole history as a reusable prefix, device-side now
    and host-side after LRU pressure spills it."""
    self._lora_unpin(slot.req)  # the row is leaving the pool in every caller
    if not self.paged:
      return
    for p in slot.shared_pages:
      self.allocator.release(p)
    n_shared = len(slot.shared_pages)
    keys = [] if self.engine.cfg.recurrent_layers else slot.chain_keys  # pages without their state are no prefix: all freed
    if extend is None:
      extend = self.tier is not None
    if extend and slot.pos // self.page_size > len(keys):
      from .paging import PageAllocator

      new_toks = slot.out_tokens[len(slot.req.carry_tokens):]
      absorbed = np.concatenate([slot.req.tokens, np.asarray(new_toks, np.int64)]) if new_toks else slot.req.tokens
      # Positions [0, pos) are exactly the written KV of absorbed[:pos]; only
      # FULL pages (pos // page_size) are donatable.
      keys = PageAllocator.chain_keys_extend(keys, absorbed[: (slot.pos // self.page_size) * self.page_size], self.page_size)
    n_donatable = len(keys)
    to_free = []
    donated = []
    for i, p in enumerate(slot.pages):
      logical = n_shared + i
      if logical < n_donatable and self.allocator.insert_cached(keys[logical], p):
        donated.append(keys[logical])
        continue
      to_free.append(p)
    self.allocator.free(to_free)
    if donated and self.tier is not None:
      from .kv_tier import prefix_registry

      prefix_registry.note(donated)  # cluster-visible: this node now holds these chains
    if slot.shared_pages or slot.pages:
      metrics.inc("page_release_events_total")
    slot.shared_pages, slot.pages = [], []

  def _clear_row(self, row: int) -> None:
    """Reset a freed row's block-table entry and its persistent dispatch
    arrays (the single release hook — results walk, preemption, teardown)."""
    if self.paged and self.block_tables is not None:
      self.block_tables[row, :] = 0
    if self.spec:
      metrics.set_gauge("spec_gamma", 0, labels={"row": str(row)})
      metrics.set_gauge("spec_proposer", 0, labels={"row": str(row)})
    self._h_occupied[row] = False
    self._h_tokens[row, 0] = 0
    self._h_positions[row] = 0
    self._h_temps[row] = 0.0
    self._h_top_ks[row] = 1
    self._h_generated[row] = 0
    self._h_max_tokens[row] = 0
    self._h_adapters[row] = 0

  def _grow_pages(self, row: int, slot: _Slot, pos: int, headroom: int | None = None) -> bool:
    """Ensure ``slot`` has pages covering the chunk dispatched at ``pos``.

    ``pos`` is the DISPATCH-time position — under lookahead it already
    includes the in-flight chunk's speculative advance, so growth reserves
    one extra chunk of headroom ahead of the confirmed position and the
    speculative chunk can never overflow the block table
    (inference/paging.py ``pages_to_cover``). ``headroom`` overrides the
    plain chunk size for spec-batch dispatches: their worst-case advance is
    ``spec_worst_advance(chunk, gamma_max)`` — gamma-deep speculative
    headroom (ISSUE 7)."""
    from .paging import pages_to_cover

    needed = pages_to_cover(pos + (headroom if headroom is not None else self.chunk), self.page_size)
    have = len(slot.shared_pages) + len(slot.pages)
    if needed <= have:
      return True
    got = self.allocator.alloc(needed - have)
    if got is None:
      return False
    self._stage_spill(slot.req.request_id)  # evictions this growth forced
    metrics.inc("page_grow_events_total")
    metrics.inc("page_grow_pages_total", len(got))
    self.block_tables[row, have : have + len(got)] = got
    slot.pages.extend(got)
    return True

  def _parked_admissible(self) -> bool:
    """Should the pipeline drain for the parked (page-starved) set? True
    when page availability CHANGED since the last admission pass looked.

    Every event that can make a parked request admissible moves
    ``n_available`` — a finishing row frees its tail pages, donated prompt
    pages land in the evictable LRU, shared-prefix refs drop — while an
    UNCHANGED allocator would just replay the pass that parked everyone
    (recorded demands can go stale against the live prefix cache, so the
    retry recomputes them rather than trusting them here). Only INCREASES
    count: a decrease (a resident row growing into a page) cannot make a
    parked demand coverable, so it just moves the baseline — without that,
    every page-boundary crossing by a resident row would buy a futile
    synchronous boundary. Cost model: one drain per release/donation event,
    and steady page-bound saturation keeps the pipeline chaining."""
    if not self._parked:
      return False
    if self.allocator is None:
      return True
    avail = self.allocator.n_available
    if avail > self._parked_avail_seen:
      return True
    self._parked_avail_seen = avail  # shrunk: re-baseline, keep chaining
    return False

  # ------------------------------------------------- mixed ticks (ISSUE 14)

  def _mixed_active(self) -> bool:
    """Mixed prefill+decode ticks apply: knob on, paged layout (the prefill
    program's per-row prefix-offset resume is what a slice IS), chunking on,
    and a backend with the fused mixed program (pp/sp fall back to the
    alternating schedule)."""
    return (
      self.mixed
      and self.paged
      and self.prefill_chunk > 0
      and getattr(self.ops, "mixed_tick_supported", lambda: False)()
    )

  def _itl_burn(self) -> float | None:
    """Interactive-class fast-window ITL burn — the budget policy's input.
    The SLO tick's gauge when it has run; before the first tick, a proxy
    judged directly from the live ``qos_itl_seconds{class=interactive}``
    histogram against the class objective (p50 at the p99 objective reads
    as burn 1.0 — conservative toward shrinking the slice). None = no ITL
    signal at all."""
    if not slo.slo_enabled():
      return None
    fast = int(min(slo.slo_windows_s()))
    b = metrics.gauge_value("slo_burn_rate", labels={"class": "interactive", "window": f"{fast}s"})
    if b is not None:
      return float(b)
    itl = metrics.quantile("qos_itl_seconds", 0.5, labels={"class": "interactive"})
    if itl is None:
      return None
    obj_ms = slo.objectives("interactive")["itl_p99_ms"]
    return (itl * 1e3) / max(obj_ms, 1e-9)

  def _mixed_budget(self, backlog: int | None = None) -> int:
    from .paging import select_mixed_budget

    residents = sum(1 for s in self.slots if s is not None)
    budget = select_mixed_budget(
      self.prefill_chunk, self._itl_burn(), residents,
      backlog=backlog if backlog is not None else max(len(self._prefilling), 1),
    )
    metrics.set_gauge("mixed_budget_tokens", budget)
    return budget

  @staticmethod
  def _mixed_final_cap(budget: int) -> int:
    """Largest remaining suffix the FINAL (sampling) dispatch may cover.
    The final runs ALONE at a boundary — a pure prefill stall — so its size
    is bounded by one pad bucket, not the (possibly much larger) slice
    budget: mixed ticks keep slicing until the remainder fits a single
    PREFILL_BUCKET-wide dispatch. When the budget is already below the
    bucket the budget bounds it (small-chunk configs are unchanged)."""
    return min(budget, PREFILL_BUCKET)

  def _mixed_defer(self, r: _Ready, budget: int) -> bool:
    """Should this admission's next prefill advance ride mixed ticks
    instead of an alternating prefill dispatch? Yes while decode rows are
    resident (there is someone to stall) and the remaining suffix exceeds
    the final cap (the final, sampling slice always dispatches through the
    ordinary admission path)."""
    if not self._mixed_active() or r.req.request_id in self._cancelled_ids:
      return False
    if not any(s is not None for s in self.slots):
      return False  # nothing to mix with: the alternating dispatch stalls no one
    return int(r.req.tokens.shape[0]) - r.prefix_len > self._mixed_final_cap(budget)

  def _mixed_intent(self, inflight: _Chunk | None, budget: int | None = None) -> tuple | None:
    """(ready, start, end) of the prefill slice the NEXT decode dispatch
    should fuse in, or None for a plain tick. One admission per tick (the
    head of ``_prefilling`` — arrival order); a chained dispatch continues
    from the IN-FLIGHT slice's end (the advance is host-deterministic, so
    mixed chunks chain exactly like plain lookahead chunks). ``budget`` is
    the loop iteration's single policy verdict — recomputing here could
    disagree with the boundary gate's read within one tick."""
    if not self._mixed_active() or not self._prefilling:
      return None
    if not any(s is not None for s in self.slots):
      return None
    r = self._prefilling[0]
    if r.req.request_id in self._cancelled_ids:
      return None  # force a boundary: the admission sweep settles the cancel
    start = r.prefix_len
    if inflight is not None and inflight.mixed_ready is r:
      start = inflight.mixed_end  # the in-flight slice hasn't settled yet
    if budget is None:
      budget = self._mixed_budget()
    final_cap = self._mixed_final_cap(budget)
    remaining = int(r.req.tokens.shape[0]) - start
    if remaining <= final_cap:
      return None  # final slice: the boundary dispatch prefills + samples it
    # Never leave a final larger than the cap: the last slice shrinks so
    # the sampling dispatch stays one pad bucket wide.
    slice_len = min(budget, remaining - final_cap)
    # Keep the padded dispatch shape a POWER OF TWO inside the scatter-clamp
    # bound (prefix + pad <= max_seq): near the window end the slice shrinks
    # rather than the pad clamping to an arbitrary width — a non-pow2
    # [1, pad] shape would trace a fresh XLA compile per near-window slice,
    # exactly the recompile the traced budget exists to avoid.
    pad = 1
    while pad < slice_len:
      pad *= 2
    while pad > self.max_seq - start and pad > 1:
      pad //= 2
    slice_len = max(min(slice_len, pad), 1)
    return (r, start, start + slice_len)

  def _prefill_boundary_needed(self, budget: int | None = None) -> bool:
    """Does a mid-flight chunked prefill need a SYNCHRONOUS boundary
    (settle + ``_admit_pending`` dispatch)? Always under the alternating
    scheduler (the historical behavior); under mixed ticks only when an
    entry is final-slice-ready (its sampling dispatch runs through the
    admission path), cancelled, or no decode row is resident to mix with.
    ``budget`` shares the loop iteration's verdict with ``_mixed_intent``."""
    if not self._prefilling:
      return False
    if not self._mixed_active() or not any(s is not None for s in self.slots):
      return True
    if budget is None:
      budget = self._mixed_budget()
    final_cap = self._mixed_final_cap(budget)
    for r in self._prefilling:
      if r.req.request_id in self._cancelled_ids:
        return True
      if int(r.req.tokens.shape[0]) - r.prefix_len <= final_cap:
        return True
    return False

  def _plan_chunk(self, inflight: _Chunk | None, gmax: int = 0) -> _Plan:
    """Snapshot the next chunk's dispatch state: CONFIRMED slot state plus
    the (single) in-flight chunk's speculative advance.

    Mirrors the synchronous tick's per-row gating. Cancelled rows and rows
    without cache room deactivate (they settle as empty finishes at this
    chunk's boundary); page-starved rows skip the chunk but stay resident
    (other rows' finishes free pages). Under lookahead only, a row whose
    in-flight chunk deterministically reaches max_tokens is excluded
    outright: an active row advances a full chunk unless EOS lands first,
    and either way the IN-FLIGHT settle resolves it before this chunk's
    settle runs — this chunk would only decode droppable overrun for it.

    Spec-batch interplay (ISSUE 7): an in-flight SPEC chunk's advance is
    variable, so the plan assumes its WORST case for positions/page-growth —
    and skips the max_tokens exclusion entirely (worst-case ``generated``
    could exclude a row that won't actually finish, which would truncate its
    stream). ``gmax > 0`` means THIS dispatch will be a spec chunk: growth
    reserves ``spec_worst_advance(chunk, gmax)`` tokens of page headroom."""
    from .paging import spec_worst_advance

    spec = inflight.active if inflight is not None else None
    headroom = spec_worst_advance(self.chunk, gmax) if gmax > 0 else self.chunk
    positions = self._h_positions.copy()
    generated = self._h_generated.copy()
    if spec is not None:
      positions[spec] += inflight.worst
      if not inflight.spec:
        generated[spec] += inflight.worst
    active = self._h_occupied.copy()
    starved: set[int] = set()
    rows: list = []
    finishing = 0
    for i, s in enumerate(self.slots):
      if s is None:
        continue
      rows.append((i, s))
      if spec is not None and not inflight.spec and spec[i] and generated[i] >= self._h_max_tokens[i]:
        active[i] = False  # finishes at the in-flight settle; drop-on-read covers the rest
      elif s.first_pending and generated[i] >= self._h_max_tokens[i]:
        active[i] = False  # a ``max_tokens`` of 1: its first token, still on the device, is its last; its group's settle ends it
        finishing += 1
      elif s.cancelled or int(positions[i]) + self.chunk >= self.max_seq:
        active[i] = False
        finishing += 1
      elif self.paged and not self._grow_pages(i, s, int(positions[i]), headroom):
        active[i] = False
        starved.add(i)  # counted at dispatch — a discarded plan is re-planned, not a second starvation
    deadlocked = inflight is None and bool(starved) and not active.any() and finishing == 0
    return _Plan(rows=rows, active=active, starved=starved, positions=positions, deadlocked=deadlocked, gmax=gmax)

  def _note_ngram_miss(self, row: int, slot: _Slot) -> None:
    """Charge a proposal MISS (no suffix match in the row's history) to the
    n-gram EWMA as a zero-acceptance observation. A miss costs no device
    work, but a row holding n-gram depth forces synchronous dispatch (host
    proposals need settled history), so rows whose text never matches must
    converge back to plain and let the pipeline chain — while a row with an
    established high EWMA rides the hysteresis band through brief
    non-repetitive gaps."""
    from .paging import ewma_update, spec_adapt_gamma, spec_select_proposer

    ewma = ewma_update(slot.spec_ewmas.get("ngram"), 0.0)
    slot.spec_ewmas["ngram"] = ewma
    prio = slot.req.qos.priority if slot.req.qos is not None else "standard"
    slot.spec_gamma = spec_adapt_gamma(ewma, slot.spec_gamma, self.spec_ngram_max, prio)
    if slot.spec_gamma == 0:
      slot.spec_proposer, slot.spec_gamma = spec_select_proposer("ngram", slot.spec_ewmas, self.spec_proposers, prio)
    metrics.set_gauge("spec_proposer", PROPOSER_CODE[slot.spec_proposer], labels={"row": str(row)})

  def _spec_intent(self, inflight: _Chunk | None) -> int:
    """gamma_max for the NEXT decode chunk; 0 ⇒ dispatch the plain program.

    Plain wins when: speculation is off, no greedy row proposes (every
    depth collapsed to 0 — the acceptance-EWMA floor), or any live row sits
    within the chunk's worst-case advance of the context window (the plain
    program's window-end cutoff keeps chunk granularity there — identity
    over the band). When every depth is 0, one probe chunk runs every
    ``spec_reprobe`` plain chunks so a proposer that STARTS paying again
    (e.g. the stream left a pathological region) can re-earn its depth —
    each row probes whichever proposer the policy ranks best for it
    (inference/paging.py ``spec_reprobe_proposer``).

    ISSUE 12: rows on the N-GRAM proposer draft from settled host history,
    so when any such row holds depth while a chunk is in flight this
    returns with ``_spec_needs_host`` set and the loop settles first; with
    the pipeline drained the proposals are computed here (one suffix lookup
    per row) and staged in ``_spec_props`` for the dispatch. A lookup MISS
    contributes no depth this chunk and charges the miss policy
    (``_note_ngram_miss``)."""
    self._spec_props = None
    self._spec_needs_host = False
    if not self.spec:
      return 0
    from .paging import spec_reprobe_proposer, spec_worst_advance

    live = [(i, s) for i, s in enumerate(self.slots) if s is not None and not s.finished and not s.cancelled]
    greedy = [(i, s) for i, s in live if s.req.temp <= 0.0]
    if not greedy:
      return 0
    model_ok = self.draft_cache is not None
    if all(s.spec_gamma <= 0 or (s.spec_proposer == "model" and not model_ok) for _, s in greedy):
      if self.spec_reprobe <= 0 or self._spec_plain_chunks < self.spec_reprobe:
        return 0
      for i, s in greedy:  # probe round: shallowest depth, best proposer per row
        prop = spec_reprobe_proposer(s.spec_ewmas, self.spec_proposers if model_ok else tuple(p for p in self.spec_proposers if p != "model"))
        if prop is None:
          continue
        s.spec_proposer, s.spec_gamma = prop, 1
        metrics.set_gauge("spec_proposer", PROPOSER_CODE[prop], labels={"row": str(i)})
      self._spec_plain_chunks = 0
    if inflight is not None and any(s.spec_proposer == "ngram" and s.spec_gamma > 0 and s.ngram is not None for _, s in greedy):
      # Host proposals need settled history: ask the loop to drain first.
      self._spec_needs_host = True
      return max(s.spec_gamma for _, s in greedy)
    gmax = 0
    props: dict[int, np.ndarray] = {}
    stream_cap = spec_worst_advance(self.chunk, self.spec_ngram_max)
    for i, s in greedy:
      if s.spec_gamma <= 0:
        continue
      if s.spec_proposer == "ngram":
        if s.ngram is None:
          continue
        cand = s.ngram.propose(stream_cap)
        if len(cand) == 0:
          self._note_ngram_miss(i, s)
          continue
        props[i] = cand
        gmax = max(gmax, min(s.spec_gamma, len(cand)))
      elif model_ok:
        gmax = max(gmax, s.spec_gamma)
    if gmax == 0:
      return 0
    worst = spec_worst_advance(self.chunk, gmax)
    adv = inflight.worst if inflight is not None else 0
    for i, s in live:
      pos = int(self._h_positions[i]) + (adv if (inflight is not None and inflight.active[i]) else 0)
      if pos + worst >= self.max_seq:
        return 0  # near-window band: plain chunks carry the row to its end
    self._spec_props = props or None
    return gmax

  def _preempt_starved(self, plan: _Plan) -> None:
    """Every resident row is starved (none can run, and no finishing row is
    about to free pages at the next settle): fail the youngest so the others
    make progress."""
    victim = min(plan.starved, key=lambda i: self.slots[i].generated)
    s = self.slots[victim]
    metrics.inc("scheduler_preemptions_total")
    tracer.stage(s.req.request_id, "preempted", {"generated": s.generated})
    self._note_released(s)
    self._release_pages(s)
    self.slots[victim] = None
    self._clear_row(victim)
    if not s.req.future.done():
      s.req.future.set_exception(ServerOverloadedError("page pool exhausted with no runnable rows"))

  def _stage_decode(self, plan: _Plan, inflight: _Chunk | None, tick: int):
    """The synchronous half of one decode dispatch, on the event-loop thread:
    builds the chunk's host operands and returns ``(run, request ids, the
    host-side fields of its _Chunk)``. ``run()`` goes to the executor thread,
    moves the operands to the device, enqueues the compiled program plus the
    async device→host copy and returns its device handles WITHOUT waiting
    for results — the device runs while the host loops back to settle the
    previous chunk (``_dispatch_decode`` awaits it and makes the ``_Chunk``).

    ``plan.gmax > 0`` dispatches the SPEC program (``chunk`` draft/verify
    rounds, per-row depths from the slots, variable advance — ISSUE 7). A
    chained spec dispatch consumes the in-flight chunk's device position
    handle: the host cannot know a spec chunk's variable advance until its
    settle, so the chain rides device-resident positions exactly like the
    token."""
    from .paging import spec_worst_advance

    eng = self.engine
    gmax = plan.gmax
    spec = gmax > 0
    # Chained dispatch: the input token is the in-flight chunk's
    # device-resident next-token handle (no host round trip); a sync
    # dispatch (pipeline empty) uses the persistent host arrays. The key
    # split happens HERE on the event-loop thread — the executor thread
    # never touches the engine's PRNG chain.
    # Behind a prefill group still in flight it is the chain token that group's first tokens were merged
    # into, on the device (``_stage_group``): the chunk waits for no readback of theirs either.
    tokens = inflight.next_tok if inflight is not None else self._h_tokens
    if self._chain is not None:
      tokens, self._chain = self._chain, None
    positions, active = plan.positions, plan.active
    if spec and inflight is not None:
      positions = inflight.pos_dev  # true device positions; plan's copy is worst-case
    temps, top_ks = self._h_temps, self._h_top_ks
    gammas = None
    proposers = None
    props_arr = prop_counts = None
    use_draft = False
    if spec:
      props_map, self._spec_props = self._spec_props, None
      gammas = np.zeros((self.n_slots,), dtype=np.int32)
      proposers = ["plain"] * self.n_slots
      if props_map:
        stream_w = spec_worst_advance(self.chunk, gmax) + gmax
        props_arr = np.zeros((self.n_slots, stream_w), dtype=np.int32)
        prop_counts = np.zeros((self.n_slots,), dtype=np.int32)
      for i, s in plan.rows:
        if not (plan.active[i] and s.req.temp <= 0.0):
          continue
        if s.spec_proposer == "ngram":
          if props_map and i in props_map:
            stream = props_map[i][:stream_w]
            props_arr[i, : len(stream)] = stream
            prop_counts[i] = len(stream)
            gammas[i] = min(s.spec_gamma, gmax)
            proposers[i] = "ngram"
        elif s.spec_proposer == "model" and self.draft_cache is not None and s.spec_gamma > 0:
          gammas[i] = min(s.spec_gamma, gmax)
          proposers[i] = "model"
          use_draft = True
      self._spec_plain_chunks = 0
    elif self.spec:
      self._spec_plain_chunks += 1
    worst = spec_worst_advance(self.chunk, gmax) if spec else self.chunk
    if self.paged and not spec:  # (a chained speculative chunk's positions are on the device)
      self._count_pages(positions, active)
    # Mixed tick (ISSUE 14): stage the prefill slice's host operands. The
    # slice pads to a power of two (one compiled program per pad bucket —
    # the traced prefix/end mean slice-length changes within a bucket never
    # recompile) and its page window pow2-buckets like _dispatch_group's.
    pf_tokens = pf_bt = pf_prefix = pf_end = None
    mixed_r = None
    m_start = m_end = pad = 0
    stage_args = dict(tick=tick, rows=int(active.sum()))
    if plan.mixed is not None and not spec:
      mixed_r, m_start, m_end = plan.mixed
      s_slice = m_end - m_start
      # The planner already shrank the slice so this pow2 pad fits the
      # scatter-clamp bound (prefix + pad <= max_seq) — see _mixed_intent.
      pad = 1
      while pad < s_slice:
        pad *= 2
      pf_tokens = np.zeros((1, pad), dtype=np.int32)
      pf_tokens[0, :s_slice] = mixed_r.req.tokens[m_start:m_end]
      mp_used = self._page_window(m_start + pad)
      pf_bt = np.zeros((1, mp_used), dtype=np.int32)
      row_pages = (mixed_r.shared_pages + mixed_r.new_pages)[:mp_used]
      pf_bt[0, : len(row_pages)] = row_pages
      pf_prefix = np.asarray([m_start], dtype=np.int32)
      pf_end = np.asarray([m_end], dtype=np.int32)
      stage_args.update(pf_tokens=int(s_slice), pf_pad=pad)  # what this dispatch's prefill half carries, on the device ops' clock
      tracer.stage(mixed_r.req.request_id, "prefill_chunk", {
        "tokens": s_slice, "mixed": True, "batched_with": int(plan.active.sum()),
      })
    sub = eng.split_key()
    lora_kw = {"adapter_ids": jnp.asarray(self._h_adapters)} if self._lora_active() else {}

    def run():
      # ``stage`` on this thread runs to the end of the dispatch: the operands'
      # transfers, the engine's own argument handling and the jitted call, which
      # the nested ``xot.program:<family>`` span marks (measured, PR 24: the call
      # is 0.5-1 ms of it, the engine's handling before it 1.4-2.7 ms).
      with self._phase("stage", **stage_args):
        counts = pos_dev = n_prop = None
        seen = ()  # a solo engine's plain and mixed programs also return their count of expert visits (a pp / sp ring's do not)
        # The draft cache rides the dispatch only when a MODEL-drafted row is
        # in it (ISSUE 12): n-gram/plain-only chunks compile the draft-free
        # program — no draft rounds, no donated draft cache (it stays valid
        # for a later model re-probe; staleness only lowers that probe's
        # acceptance, never correctness).
        cd = self.draft_cache if (spec and use_draft) else None
        pr = jnp.asarray(props_arr) if (spec and props_arr is not None) else None
        pc = jnp.asarray(prop_counts) if (spec and prop_counts is not None) else None
        if spec and self.paged:
          toks, counts, n_prop, next_tok, pos_dev, self.cache, cd = self.ops.spec_paged_batch_decode(
            jnp.asarray(tokens), self.cache, cd, jnp.asarray(self.block_tables), jnp.asarray(positions),
            jnp.asarray(active), jnp.asarray(gammas), jnp.asarray(temps), self._h_top_ks, self.chunk, gmax,
            k_max=self.k_max, page_size=self.page_size, key=sub, props=pr, prop_counts=pc, **lora_kw,
          )
        elif spec:
          toks, counts, n_prop, next_tok, pos_dev, self.cache, cd = self.ops.spec_batch_decode(
            jnp.asarray(tokens), self.cache, cd, jnp.asarray(positions), jnp.asarray(active),
            jnp.asarray(gammas), jnp.asarray(temps), self._h_top_ks, self.chunk, gmax, k_max=self.k_max, key=sub,
            props=pr, prop_counts=pc, **lora_kw,
          )
        elif pf_tokens is not None:
          # Mixed tick: one dispatch advances every decode row by its chunk
          # AND the staged admission's prefill by its budgeted slice (the
          # slice carries ITS OWN adapter index — pf_adapter — so a mixed
          # tick's prefill half applies the admission's adapter per-row too).
          toks, next_tok, _pos, self.cache, *seen = self.ops.mixed_paged_batch_decode(
            jnp.asarray(tokens), self.cache, jnp.asarray(self.block_tables), jnp.asarray(positions),
            jnp.asarray(active), jnp.asarray(temps), jnp.asarray(top_ks), self.chunk,
            k_max=self.k_max, page_size=self.page_size, key=sub,
            pf_tokens=pf_tokens, pf_bt=pf_bt, pf_prefix=pf_prefix, pf_end=pf_end,
            **({**lora_kw, "pf_adapter": np.asarray([getattr(mixed_r.req, "adapter_slot", 0)], np.int32)} if lora_kw else {}),
          )
        elif self.paged:
          toks, next_tok, _pos, self.cache, *seen = self.ops.paged_batch_decode(
            jnp.asarray(tokens), self.cache, jnp.asarray(self.block_tables), jnp.asarray(positions),
            jnp.asarray(active), jnp.asarray(temps), jnp.asarray(top_ks), self.chunk,
            k_max=self.k_max, page_size=self.page_size, key=sub, **lora_kw,
          )
        else:
          toks, next_tok, _pos, self.cache = self.ops.batch_decode(
            jnp.asarray(tokens), self.cache, jnp.asarray(positions), jnp.asarray(active),
            jnp.asarray(temps), jnp.asarray(top_ks), self.chunk, k_max=self.k_max, key=sub, **lora_kw,
          )
        if spec and use_draft:
          self.draft_cache = cd
        try:
          toks.copy_to_host_async()  # the readback overlaps the next chunk's compute
          if counts is not None:
            counts.copy_to_host_async()
          if n_prop is not None:
            n_prop.copy_to_host_async()
        except AttributeError:  # backend without async copies
          pass
        return toks, next_tok, counts, pos_dev, n_prop, seen[0] if seen else None

    if plan.starved:
      metrics.inc("scheduler_page_starved_total", len(plan.starved))
    # The host's interval ends here unless this chunk is chained: the device
    # then already runs its predecessor and this one queues behind it.
    self._note_dispatch("spec" if spec else "mixed" if mixed_r is not None else "decode")
    rids = [s.req.request_id for i, s in plan.rows if plan.active[i]]
    if mixed_r is not None:
      rids.append(mixed_r.req.request_id)
    return run, rids, dict(
      rows=plan.rows, active=plan.active,
      starved=frozenset(plan.starved),
      spec=spec, worst=worst, rounds=self.chunk if spec else 0, gammas=gammas,
      proposers=proposers,
      mixed_ready=mixed_r, mixed_start=m_start, mixed_end=m_end, mixed_pad=pad,
      draw_skipped=not (temps > 0).any(),
    )

  def _note_expert_form(self) -> None:
    """Which form the routed experts' product takes in this pool's programs (models/decoder.py ``served_expert_form``:
    the layer loops' own decision, asked once), and how many expert layers a decode step passes. A pipelined or
    sequence-parallel ring holds the weights itself (``engine.params`` is None): block form, and its programs do not
    count their visits."""
    from ..models.decoder import served_expert_form
    from ..ops.moe import EXPERT_ACTS, FFN_FORMS, note_walk

    cfg, params = self.engine.cfg, getattr(self.engine, "params", None)
    self._expert_layers = sum(stack["w_experts_down"].shape[0] for stack in (params or {}).values() if isinstance(stack, dict) and "w_experts_down" in stack)  # the stacks THIS engine holds (a shard of the layers counts its own; gated or not, an expert has a down matrix)
    if not cfg.n_experts:
      return
    form = served_expert_form(params, cfg)
    for name in FFN_FORMS:
      metrics.set_gauge("moe_ffn_form", int(name == form), labels={"form": name})
    note_walk()  # ``moe_grouped_walk{walk}`` as it stands: the grouped form's call sites move it as they are traced
    # The expert layers by where their router reads (``cfg.router_input``: its experts' own input, or the attention's,
    # drawn ahead of it) and by their experts' gate (``cfg.expert_act``): one value a model today, a count so that a
    # model of two says so.
    n_expert_layers = self._expert_layers or cfg.expert_layers
    for at in ("ffn", "attn"):
      metrics.set_gauge("moe_router_input", n_expert_layers * int(at == cfg.router_input), labels={"at": at})
    for act in EXPERT_ACTS:
      metrics.set_gauge("moe_expert_gate", n_expert_layers * int(act == cfg.expert_act), labels={"act": act})

  def _count_pages(self, positions, active) -> None:
    """One decode dispatch's pages, from the rows' lengths on the host: ``kv_pages_resident_total`` — what the active
    rows hold, in every layer that owns pages — and ``kv_pages_read_total`` — what those layers' attention reads of
    them at the chunk's first step: every page in a layer without a window, the pages from the one that holds
    position ``length - window`` on in a layer with one. Their quotient is what the window returns in bandwidth, and
    what a pool whose window layers held a window only would return in bytes."""
    lengths = np.asarray(positions)[np.asarray(active, bool)].astype(np.int64) + 1
    held = -(-lengths // self.page_size)
    read = sum((held - np.maximum(lengths - w, 0) // self.page_size if w else held).sum() for w in self._windows)
    self.clock.inc("kv_pages_resident_total", int(held.sum()) * len(self._windows), count="kv_pages_resident")
    self.clock.inc("kv_pages_read_total", int(read), count="kv_pages_read")

  async def _dispatch_decode(self, plan: _Plan, inflight: _Chunk | None) -> _Chunk:
    tick = self._next_tick()
    with self._phase("stage", tick=tick, rows=int(plan.active.sum())):
      run, rids, record = self._stage_decode(plan, inflight, tick)
    toks, next_tok, counts, pos_dev, n_prop, experts_visited = await asyncio.get_event_loop().run_in_executor(
      self.engine.executor, self._attributed(run, rids, tick)
    )
    return _Chunk(toks=toks, next_tok=next_tok, counts=counts, pos_dev=pos_dev, n_prop=n_prop, experts_visited=experts_visited, tick=tick, **record)

  def _note_spec_settle(self, row: int, slot: _Slot, record: _Chunk, avail: int, emitted: int, proposed: int) -> None:
    """Per-row spec-chunk bookkeeping at the settle: per-proposer acceptance
    counters, the EWMA → depth policy step, proposer switching at the depth
    floor (ISSUE 12: ``spec_select_proposer`` — each row converges to
    model-draft / n-gram / plain, whichever pays), the per-row depth and
    proposer gauges, and the timeline decode stage carrying the chunk's
    accepted-run total."""
    from .paging import ewma_update, spec_adapt_gamma, spec_select_proposer

    g = int(record.gammas[row]) if record.gammas is not None else 0
    prop = record.proposers[row] if record.proposers is not None else ("model" if g > 0 else "plain")
    accepted = max(avail - record.rounds, 0)
    metrics.inc("spec_accepted_tokens_total", accepted, labels={"proposer": prop})
    ewma = None
    if g > 0 and proposed > 0:
      metrics.inc("spec_proposed_tokens_total", proposed, labels={"proposer": prop})
      acc = accepted / float(proposed)
      ewma = ewma_update(slot.spec_ewmas.get(prop), acc)
      slot.spec_ewmas[prop] = ewma
      prio = slot.req.qos.priority if slot.req.qos is not None else "standard"
      cap = self.spec_ngram_max if prop == "ngram" else self.spec_gamma_max
      slot.spec_gamma = spec_adapt_gamma(ewma, g, cap, prio)
      if slot.spec_gamma == 0:
        # Depth floor on the current proposer: the selection policy probes
        # the next candidate (or parks the row on plain until a re-probe).
        slot.spec_proposer, slot.spec_gamma = spec_select_proposer(prop, slot.spec_ewmas, self.spec_proposers, prio)
      metrics.observe_hist("spec_acceptance_ewma", ewma, buckets=FRACTION_BUCKETS)
    metrics.set_gauge("spec_gamma", slot.spec_gamma, labels={"row": str(row)})
    metrics.set_gauge("spec_proposer", PROPOSER_CODE[slot.spec_proposer], labels={"row": str(row)})
    tracer.stage(slot.req.request_id, "decode_chunk", {
      "tokens": emitted, "accepted": accepted, "gamma": g, "rounds": record.rounds, "proposer": prop,
      "ewma": round(ewma, 4) if ewma is not None else None, "tick": record.tick,
    })

  async def _settle(self, record: _Chunk) -> None:
    """Read one chunk's tokens back and run the host bookkeeping the
    synchronous loop did inline: emit, EOS/max_tokens/cancel finishes, page
    release, metrics. Under lookahead this runs while the NEXT chunk
    computes on device. Rows that already finished at an earlier settle
    (while this chunk was speculatively in flight) are DROPPED-ON-READ:
    their tokens in this buffer are overrun garbage and are never emitted;
    their pages were released at the earlier settle and can only be
    re-granted to dispatches that execute AFTER this chunk on the single
    device stream, so the garbage writes are always overwritten or
    positionally masked before anyone reads them.

    Spec chunks (ISSUE 7) settle with a VARIABLE advance: the counts vector
    says how many of each row's buffer slots are real; the emit walk below
    is otherwise identical (EOS/max_tokens cut inside an accepted run the
    same way they cut inside a plain chunk), and each row's measured
    acceptance drives its EWMA → next-depth policy here, at the settle."""
    eng = self.engine

    counted = record.experts_visited is not None  # a program that counts its expert visits: a solo engine's, of a model with experts

    def fetch():
      with self._phase("readback", tick=record.tick):  # waits for the chunk's program
        return (
          np.asarray(record.toks),
          np.asarray(record.counts) if record.counts is not None else None,
          np.asarray(record.n_prop) if record.n_prop is not None else None,
          int(record.experts_visited) if counted else 0,
        )

    rows_host, counts_host, n_prop_host, visited = await asyncio.get_event_loop().run_in_executor(eng.executor, fetch)
    with self._phase("settle", tick=record.tick):
      if counted:
        # Their quotient is the mean number of distinct held experts a decode step visits in one expert layer: how
        # far the grouped form (``moe_ffn_form``) engages — it reads those and no other.
        self.clock.inc("moe_experts_visited_total", visited, count="experts_visited")
        self.clock.inc("moe_expert_layer_steps_total", self._expert_layers * self.chunk, count="expert_layer_steps")
      self._settle_host(record, rows_host, counts_host, n_prop_host)

  def _settle_host(self, record: _Chunk, rows_host, counts_host, n_prop_host) -> None:
    """The settle's host half, once the chunk's tokens are on the host: timing
    attribution, the emit walk, finishes, page release."""
    # Device-time attribution (sched_clock.py): while the pipeline is full
    # the device runs chunks back-to-back, so per-chunk device time is
    # READY-TO-READY (== dispatch-to-dispatch in steady state); the first
    # chunk after a boundary times dispatch-to-ready, exactly like the
    # synchronous loop. Either way the host bookkeeping below is NOT
    # serially attributed.
    chunk_dt = max(self.clock.ready(steps=self.chunk), 1e-9)
    if record.mixed_ready is not None:
      # Mixed-tick settle (ISSUE 14): the fused dispatch's prefill slice is
      # confirmed — advance the admission's prefix (max-guarded: a settle
      # never rewinds past a later chained slice) and attribute the
      # dispatch to its OWN latency family: one fused program is neither a
      # pure prefill chunk nor a pure decode chunk, so it must not skew
      # either existing histogram (the attribution-split satellite).
      r = record.mixed_ready
      r.prefix_len = max(r.prefix_len, record.mixed_end)
      metrics.observe_hist("mixed_tick_seconds", chunk_dt)
      self.clock.inc("sched_tick_prefill_tokens_total", record.mixed_end - record.mixed_start, count="slice_tokens")
      self.clock.inc("sched_tick_prefill_pad_tokens_total", record.mixed_pad, count="slice_pad_tokens")  # the padded width the program ran: tokens / pad is the slices' fill
      if r.req.disagg_target and self.kv_stream is not None and self.paged:
        # Disagg overlap rides mixed ticks too: ship the slice's completed
        # full pages while the remaining prefill advances.
        self._disagg_stream_chunk(r)
    path = {"path": "spec" if record.spec else self.decode_path}
    if record.active.any():
      # Per-chunk decode-path attribution: the dispatch table's real-world
      # mix, observable at /metrics instead of only in offline bench JSON.
      if record.mixed_ready is None:
        metrics.observe_hist("decode_chunk_seconds", chunk_dt)
      metrics.inc("decode_chunks_total", labels=path)
      if record.draw_skipped:
        # The hit share of the program's draw predicate: skipped ÷ decode_chunks_total, path by path.
        metrics.inc("decode_draw_skipped_chunks_total", labels=path)

    for i, slot in record.rows:
      if slot.finished or self.slots[i] is not slot:
        continue  # drop-on-read: overrun tokens of a row settled earlier
      req = slot.req
      if i in record.starved:  # skipped this chunk; retried at the next dispatch
        continue
      if not record.active[i]:  # cache exhausted or cancelled at dispatch
        slot.finished = True
        self._cancelled_ids.discard(req.request_id)
        self._release_pages(slot)
        self._note_complete(slot)
        req.emit(req.request_id, [], True)
        if not req.future.done():
          req.future.set_result(slot.out_tokens)
        self.slots[i] = None
        self._clear_row(i)
        continue
      avail = int(counts_host[i]) if record.spec else rows_host.shape[1]
      emit: list[int] = []
      done = False
      for t in rows_host[i][:avail]:
        t = int(t)
        emit.append(t)
        slot.generated += 1
        if t in req.eos_ids or slot.generated >= req.max_tokens:
          done = True
          break
      if record.spec:
        self._note_spec_settle(i, slot, record, avail, len(emit), int(n_prop_host[i]) if n_prop_host is not None else 0)
      if slot.ngram is not None and emit:
        # O(1)-per-token index update: the row's suffix history now covers
        # everything the next chunk's proposal may key on.
        slot.ngram.extend(emit)
      slot.out_tokens.extend(emit)
      slot.pos += len(emit)
      slot.last_token = emit[-1] if emit else slot.last_token
      self._h_positions[i] = slot.pos
      self._h_generated[i] = slot.generated
      self._h_tokens[i, 0] = slot.last_token
      if emit:
        # Same path label as this chunk's decode_chunks_total increment, so
        # the two per-path series stay ratio-able (tokens per chunk).
        metrics.inc("decode_tokens_total", len(emit), labels=path)
        # Inter-token latency: the chunk's wall-clock amortized over its
        # tokens — ONE weighted observation (utils/metrics.py observe_hist
        # n=k) instead of k lock round trips.
        metrics.observe_hist("itl_seconds", chunk_dt / len(emit), n=len(emit))
        # Per-class ITL + the goodput denominator (ISSUE 9): same weighted
        # observation, one extra lock acquisition per chunk; no-ops with
        # XOT_TPU_SLO=0.
        slo.observe_itl(self._slo_class(req), chunk_dt / len(emit), n=len(emit))
        slo.note_tokens(self._slo_class(req), self._slo_tenant(req), len(emit))
      req.emit(req.request_id, emit, done)
      if done:
        slot.finished = True
        self._cancelled_ids.discard(req.request_id)
        self._release_pages(slot)
        self._note_complete(slot)
        if not req.future.done():
          req.future.set_result(slot.out_tokens)
        self.slots[i] = None
        self._clear_row(i)
    self._update_gauges()

  def _preempt_possible(self) -> bool:
    """A waiting request outranks a resident row and no slot is free: the next admission pass would preempt."""
    return self.qos is not None and self._free_slot() is None and not self.queue.empty() and self._preempt_victim_for(self.queue.peek()) is not None

  def _admission_waiting(self, mixed_budget: int | None = None) -> bool:
    """Would an admission pass at this boundary have anything to do? A waiter and a free slot (a parked one only
    when page availability has grown since the last pass looked: ``_parked_admissible``), a waiter that may
    preempt, or a chunked prefill whose next dispatch goes through the admission path (``_prefill_boundary_needed``)."""
    if self._free_slot() is not None and (not self.queue.empty() or self._parked_admissible()):
      return True
    return self._preempt_possible() or self._prefill_boundary_needed(mixed_budget)

  async def _wait_late_into(self, chunk: _Chunk) -> None:
    """Hold the admission pass that runs behind ``chunk`` until LATE in the chunk's device time: a pass at the
    boundary's start would see only what arrived while the last group was settled, and a burst of arrivals still
    landing (k callers at once, each a handler on this loop's thread) would be cut in two — two small groups where a
    pass at the chunk's end made one, and prefill shapes the warm-up never reached. So the loop sleeps — the
    thread serves the arriving requests meanwhile — until a quarter of the chunk's expected time is left
    (``SchedClock.expected``: what its kind last took, from when it got the device), which is several times what the
    pass and its staging cost the host, and no longer than the chunk runs: it wakes every 5 ms at most and stops
    when the chunk's tokens are ready. No estimate yet (the first chunk of a kind): no wait.

    Then, whatever the chunk: a burst that began to land just before that point is let finish. k callers at once
    reach the queue in lumps some milliseconds apart (seen as 4 + 4 and 2 + 2 in a rehearsal of the benchmark's warm-up,
    gaps of up to 6.7 ms on the chip's host, PR 53), and a pass between the lumps made two
    groups of k/2 — the one cut that leaves the k-row program uncompiled. The pass therefore waits until the newest
    arrival is ``BURST_QUIET_S`` old, at most ``BURST_WAIT_MAX_S`` in all (a steady stream cannot hold it), a
    millisecond a sleep; where nobody arrived in the last ``BURST_QUIET_S`` — every boundary of a closed loop in its
    steady state, whose callers come back early in a chunk — it costs nothing."""
    ready = getattr(chunk.toks, "is_ready", None)
    while (est := self.clock.expected()) is not None and est[0] > 0.25 * est[1] and not (ready is not None and ready()):
      await asyncio.sleep(min(est[0] - 0.25 * est[1], 0.005))
    give_up = time.perf_counter() + BURST_WAIT_MAX_S
    while (now := time.perf_counter()) < min(self._last_arrival + BURST_QUIET_S, give_up):
      await asyncio.sleep(min(self._last_arrival + BURST_QUIET_S - now, 0.001))

  def _needs_settled_state(self) -> bool:
    """With a chunk in flight: does what comes next need that chunk SETTLED first? (All of it is host state the loop
    can read at the boundary.) The strictly synchronous tick (``XOT_TPU_SCHED_LOOKAHEAD=0``, the reference
    schedule); a graceful drain and a QoS preemption, which extract resident rows the chunk still holds; a cancel
    that landed on a prompt mid-prefill, whose pages a mixed chunk in flight may be writing; and a chunked prefill
    left with no decode row resident, whose next chunk starts from a prefix the chunk in flight may still advance."""
    if not self.lookahead or self._drain_pending() or self._preempt_possible():
      return True
    if not self._prefilling:
      return False
    if any(r.req.request_id in self._cancelled_ids for r in self._prefilling):
      return True
    return self._mixed_active() and not any(s is not None for s in self.slots)

  async def _run(self) -> None:
    self.clock.idle_end()  # a request is pending, or this loop would not have been started
    self._ensure_cache()
    inflight: _Chunk | None = None
    try:
      while True:
        # One mixed-budget verdict per loop iteration: the boundary gate,
        # the tick planner, and the admission sweep must agree within a
        # tick (and the policy read — gauge/histogram walk — runs once).
        with self._phase("plan"):
          mixed_budget = self._mixed_budget() if (self._prefilling and self._mixed_active()) else None
        if inflight is not None:
          # A boundary with chunk N on the device (ISSUE 51). The device executes one stream in order, so what
          # the loop enqueues now runs after N whatever the host does meanwhile, and the host's work rides under
          # the device's instead of in front of it:
          #   - nothing waits and nothing needs N settled: chunk N+1 is chained from N's device-resident token
          #     and N is settled under it (below, as ever; a backlog with no free slot chains the same way);
          #   - someone can ADMIT: the pass runs late in N's device time (``_wait_late_into``: so that it sees what
          #     arrived during N, as a pass at N's end did), against host state that is exact while N flies, and its
          #     prefill group G is enqueued BEHIND N (its pool operand is N's result, a device future; its pages
          #     come off the free list, which holds no page a write of N can still be read from). N is then
          #     settled under G, N+1 is planned with G's rows in their slots and enqueued behind G with their
          #     first tokens merged into its chain token on the device, and G is settled under N+1. A waiter
          #     whose prompt will be sliced into mixed ticks only claims its slot and pages in that pass and
          #     forces nothing;
          #   - what comes next needs N's settled state (``_needs_settled_state``): N is settled first, and the
          #     pass after it is the synchronous one.
          # No row joins a chunk later than under a drain at every boundary: whatever N's settle frees (slots,
          # pages a parked waiter was short of) is offered in a second pass before N+1 is planned.
          if inflight.mixed_ready is not None:
            # The slice that rides N ends where the host said it would, and whatever reads those pages next runs
            # after N: the prompt's prefix is taken as advanced NOW (N's settle would do it, too late for this
            # boundary), so a prompt whose last intermediate slice is in N sends its final slice's group behind N
            # and the next waiter's first slice rides N+1 — the tick a drain at every boundary gave them.
            inflight.mixed_ready.prefix_len = max(inflight.mixed_ready.prefix_len, inflight.mixed_end)
          if self._needs_settled_state():
            await self._settle(inflight)
            inflight = None
            continue
          if self._admission_waiting(mixed_budget):
            await self._wait_late_into(inflight)
            if self._needs_settled_state():
              continue  # what the wait let in (a cancel, a drain, a waiter that may preempt) asks for N settled: the top of the loop does it
            await self._admit_pending(behind=inflight)
            if self._pending or self._preempt_possible() or (self._parked and self._free_slot() is not None):
              # Groups ride behind N, or the pass left someone only N's settle can help (a parked waiter short
              # of pages that N's finishing rows hold, a waiter that may preempt): settle N, then pass again.
              await self._settle(inflight)
              inflight = None
              if self._admission_waiting():
                await self._admit_pending()
              self._update_gauges()
              if not self._pending:
                continue  # nothing enqueued after all: the synchronous pass below owns the idle wait
        else:
          if self._drain_pending():
            # Graceful drain: the pipeline is drained (no in-flight chunk),
            # so resident rows can be extracted and offered for migration
            # exactly like a preemption boundary.
            await self._drain_migrate()
          # Admission: every admissible request — parked (page-starved)
          # first, in arrival order, then the queue — prefills in ONE
          # batched dispatch between decode chunks.
          await self._admit_pending()
          self._update_gauges()
          if not self._pending and all(s is None for s in self.slots):
            if self._prefilling:
              # A chunked prefill is mid-flight with no resident decoders:
              # loop straight back to dispatch its next chunk.
              continue
            if self._parked:
              # A ready batch that insta-finished (eos or max_tokens at its
              # first token, a raced cancel, or a failed dispatch) can leave
              # entries parked behind it with every slot free — their park
              # was justified by ``others_active=ready`` pages that are now
              # released. Retry immediately: with nothing in flight each one
              # either admits or fails honestly as overloaded (every pass
              # resolves at least one request, so this cannot spin).
              continue
            # Idle: block on the queue (the task persists — no exit/restart
            # race). The woken request and anything else that queued while
            # idle admit together in one batched dispatch.
            self.clock.idle_begin()  # idle by design, not a host gap
            try:
              with jax.profiler.TraceAnnotation("xot.sched.idle"):  # the one span across an await: _phase's docstring says why it nests
                req = await self.queue.get()
            finally:
              self.clock.idle_end()
            await self._admit_pending(woken=req)
            if not self._pending:
              continue
        if any(g.sync for g in self._pending):
          # The first tokens are needed on the host before the plan (``_group_settles_first``): the old order.
          await self._settle_groups()
          if all(s is None for s in self.slots):
            continue

        with self._phase("plan"):
          if mixed_budget is None and self._prefilling and self._mixed_active():
            # The admission pass above just staged a prefill: pick up the
            # verdict for this iteration's planner.
            mixed_budget = self._mixed_budget()
          mixed = self._mixed_intent(inflight, mixed_budget)
          if mixed is not None:
            # Spec rows fall back to plain chunks during a mixed tick (the
            # mixed program composes with the PLAIN decode scan only); the
            # settle semantics are exactly the existing spec↔plain switch —
            # an in-flight spec chunk settles below before the mixed dispatch.
            self._spec_props = None
            self._spec_needs_host = False
            gmax = 0
          else:
            gmax = self._spec_intent(inflight)
        if inflight is not None and (inflight.spec != (gmax > 0) or self._spec_needs_host):
          # Program-type switch (spec↔plain): a chained dispatch would need
          # the other program's chain contract (device positions vs host
          # plan) — settle the in-flight chunk and dispatch synchronously.
          # N-gram rows holding depth settle the same way (ISSUE 12): their
          # proposals key on the suffix of SETTLED history, so a chunk with
          # host proposals never chains — the intent recomputes them against
          # the drained state on the next pass.
          await self._settle(inflight)
          inflight = None
          continue
        with self._phase("plan"):
          plan = self._plan_chunk(inflight, gmax)
        plan.mixed = mixed
        if inflight is not None and (not plan.rows or not plan.active.any()):
          # Nothing would step — a membership change is imminent (every row
          # finishing, starved, or already resolved by the in-flight
          # settle): settle instead of spending a dead speculative chunk.
          await self._settle(inflight)
          inflight = None
          continue
        if self._pending and not plan.active.any():
          # Nothing would step behind the groups either (an intermediate chunk with no decode row resident, rows
          # of one token each): settle them and look again.
          await self._settle_groups()
          continue
        if plan.deadlocked:
          self._preempt_starved(plan)
          continue
        prev, inflight = inflight, await self._dispatch_decode(plan, inflight)
        if prev is not None:
          # Settle chunk N while chunk N+1 computes: the host readback of N
          # (already streaming via copy_to_host_async) plus all bookkeeping
          # overlaps device work instead of serializing in front of it.
          await self._settle(prev)
        # ... and the groups this chunk was enqueued behind (``prev`` is then None: N was settled under them).
        await self._settle_groups()
    except asyncio.CancelledError:
      self._fail_all(RuntimeError("batched server shut down"))
      raise
    except Exception as e:  # noqa: BLE001 — fail every in-flight request loudly
      if DEBUG >= 1:
        import traceback

        traceback.print_exc()
      # The fused calls donate the cache: after a mid-call failure the
      # buffers may be consumed — drop it so the next submit reallocates.
      # The draft cache is donated by the spec programs the same way.
      self.cache = None
      self.draft_cache = None
      self._fail_all(e)

  def _fail_all(self, exc: Exception) -> None:
    for i, slot in enumerate(self.slots):
      if slot is not None:
        self._lora_unpin(slot.req)
        if not slot.req.future.done():
          slot.req.future.set_exception(exc)
      self.slots[i] = None
      self._clear_row(i)  # the single release hook resets every dispatch array
    self.clock.reset()
    self._chain = None
    while self._pending:  # groups enqueued and not settled: a sampling member is in ``slots`` (failed above), an intermediate one nowhere else
      for r in self._pending.pop().members:
        self._lora_unpin(r.req)
        if not r.req.future.done():
          r.req.future.set_exception(exc)
    while self._prefilling:
      r = self._prefilling.pop()
      self._lora_unpin(r.req)
      if not r.req.future.done():
        r.req.future.set_exception(exc)
    self.admission.fail_queued(exc)
