"""Host-side page accounting for the paged KV cache (ops/paged.py).

The device never sees this: pages are allocated/freed/shared here and the
resulting block tables ride into the compiled decode program as traced
operands. Two pieces:

- ``PageAllocator`` — a free list over pages ``1..n_pages-1`` (page 0 is the
  device-side trash page and is never handed out).
- an integrated prefix cache: finished requests donate their prompt's FULL
  pages keyed by the exact token chain that produced them; a new request
  reuses the longest page-aligned prefix already resident, skipping both the
  HBM and the prefill FLOPs for those tokens. Reused pages are read-only by
  construction (decode writes only at positions ≥ its own prompt length,
  which land in the request's private tail pages). Cached pages with no
  active readers sit in an LRU and are evicted when the free list runs dry.

Chain keys are content-addressed: key i is a 128-bit blake2b digest of
(key i-1, page i's token ids). The running hash carries FORWARD — both
within one ``chain_keys`` call and across calls via ``chain_keys_extend``
(a slot extending its prompt keys over generated tokens at release hashes
only the NEW pages) — so building all keys is O(prompt) and extending is
O(new tokens), where rehashing key i from scratch would walk i pages:
O(pages² · page_size) per admission at 32K contexts. (The first design used
nested tuples of token ids for literal exactness. At 128 bits a spurious
collision needs ~2⁶⁴ distinct pages; git-style content addressing, accepted
as exact — and pinned key-equal to the from-scratch scheme in
tests/test_kv_tier.py.)

The allocator also carries the KV memory hierarchy's device-side hooks
(inference/kv_tier.py, ISSUE 6): ``spill_hook`` receives every batch of
LRU-evicted cached pages BEFORE their device pages are reused (the host
tier copies them out), and ``adopt_restored`` registers a freshly written
restore page as a cached, refcounted prefix page.

No reference counterpart (the reference's cache is dense per-request,
``SURVEY.md §5.7``); the design is the vLLM paged-KV idea rebuilt for static
XLA shapes.
"""

from __future__ import annotations

import hashlib
import os
from collections import OrderedDict

import numpy as np

# ------------------------------------------- per-row speculation policy
#
# A PER-ROW policy (ISSUE 7): which speculation depth wins is a function of the
# measured acceptance, so neither "always speculate" nor "never" is
# hardwired — each batch row carries an acceptance EWMA and its gamma walks
# this table every chunk. Provenance for the thresholds: with an ~4x-faster
# draft (the 8B/1B pair) a round costs ≈ gamma/4 + 1 target-equivalents and
# yields 1 + acc·gamma tokens, so break-even acceptance sits near 0.25-0.35
# across gamma 1-4; the solo-path inversion the ISSUE cites (149 vs 212
# tok/s) was measured at 0.64 acceptance with the ~1.6x self-draft — hence
# demote below ~0.30 and deepen only above ~0.55, with hysteresis between.
# Interactive-class rows use a LOWER demote bar: an accepted run directly
# cuts their inter-token latency, so speculation stays worth keeping even
# when throughput-neutral (the QoS interaction ISSUE 7 names).
#
# Rows are (min_ewma, action); first row whose bound covers the EWMA wins.
_SPEC_GAMMA_TABLE = (
  (0.55, "promote"),  # draft paying well: deepen by 1 toward gamma_max
  (0.30, "hold"),  # marginal: keep the current depth (hysteresis band)
  (0.0, "demote"),  # not paying: halve toward the floor
)
_SPEC_DEMOTE_FLOOR = {"interactive": 0.15}  # class-specific demote override


def spec_adapt_gamma(ewma: float | None, gamma: int, gamma_max: int, priority: str = "standard") -> int:
  """Next chunk's speculation depth for one row, from its acceptance EWMA.

  Floor 0 = plain decode: the row stops proposing entirely (its window
  degenerates to one target token per round) instead of dragging the batch.
  Re-promotion from 0 is the CALLER's probe (the scheduler re-probes idle
  rows at gamma 1 every ``XOT_TPU_SPEC_REPROBE`` plain chunks) — the policy
  itself never resurrects a depth it has no fresh measurement for."""
  if ewma is None or gamma <= 0:
    return max(min(gamma, gamma_max), 0)
  demote_bar = _SPEC_DEMOTE_FLOOR.get(priority, _SPEC_GAMMA_TABLE[1][0])
  for bound, action in _SPEC_GAMMA_TABLE:
    if ewma >= bound:
      if action == "promote":
        return min(gamma + 1, gamma_max)
      if action == "hold" or (action == "demote" and ewma >= demote_bar):
        return min(gamma, gamma_max)
      return gamma // 2
  return gamma // 2


# Proposer preference order for probes/switches (ISSUE 12): the n-gram
# proposer costs nothing to try (host dict lookups; a miss never dispatches),
# so it is probed before the model draft, whose rounds cost real device work.
SPEC_PROPOSERS = ("ngram", "model")


def spec_select_proposer(current: str, ewmas: dict, available: tuple, priority: str = "standard") -> tuple[str, int]:
  """Next proposer for a row whose depth policy just landed at gamma 0 on
  ``current`` (ISSUE 12: the proposer itself is the per-row adaptive choice).

  ``ewmas`` maps proposer name -> that proposer's acceptance EWMA for THIS
  row (None/absent = never measured). Returns ``(proposer, gamma)``: an
  untried alternative is probed at depth 1 (the same shallow probe the
  re-probe path uses), a measured alternative re-probes only if its EWMA
  still clears the row's demote bar (no point bouncing between two proposers
  that both measured dead), and ``("plain", 0)`` otherwise — the row decodes
  plain until the scheduler's re-probe cadence resurrects one."""
  demote_bar = _SPEC_DEMOTE_FLOOR.get(priority, _SPEC_GAMMA_TABLE[1][0])
  for cand in SPEC_PROPOSERS:
    if cand == current or cand not in available:
      continue
    e = ewmas.get(cand)
    if e is None or e >= demote_bar:
      return cand, 1
  return "plain", 0


def spec_reprobe_proposer(ewmas: dict, available: tuple) -> str | None:
  """Which proposer a re-probe round should try for one row: unmeasured
  proposers win (cheap discovery, n-gram first per SPEC_PROPOSERS), then the
  best measured EWMA. None when nothing is available."""
  best, best_e = None, -1.0
  for cand in SPEC_PROPOSERS:
    if cand not in available:
      continue
    e = ewmas.get(cand)
    if e is None:
      return cand
    if e > best_e:
      best, best_e = cand, e
  return best


# ------------------------------------------------- mixed-tick budget policy
#
# ISSUE 14: one scheduler tick can fuse a token-budgeted PREFILL SLICE into
# the batched decode dispatch (models/decoder.py
# ``fused_mixed_paged_batch_decode``), so resident decode rows never stall
# for a full prefill chunk. How many prefill tokens one tick should carry is
# the same kind of measured trade as the decode-path table above: every
# slice token adds latency to EVERY resident row's next token, while smaller
# slices stretch the prefilling request's TTFT across more ticks. The policy
# is SLO-driven — the interactive fast-window burn rate (orchestration/slo.py,
# computed from the live ``qos_itl_seconds{class}`` histograms) says whether
# resident ITL is actually suffering:
#
# Rows are (min_burn, fraction-of-cap); first row whose bound covers the
# burn wins. ``burn=None`` means no ITL signal at all; with resident decode
# rows that is "healthy until proven otherwise" (the half-cap hedge), and
# with NO residents there is nothing to protect — the slice grows to the
# full ``XOT_TPU_PREFILL_CHUNK`` cap (TTFT-optimal, exactly the alternating
# chunk).

_MIXED_BUDGET_TABLE = (
  (4.0, 1 / 16),  # ITL budget burning >=4x: minimum forward progress only
  (2.0, 1 / 8),
  (1.0, 1 / 4),  # burning at exactly budget: quarter-chunk slices
  (0.0, 1 / 2),  # healthy (or unmeasured) with residents: half-chunk hedge
)


def mixed_tick_enabled() -> bool:
  """``XOT_TPU_MIXED_TICK`` (default on): fuse chunked prefill into the
  batched decode dispatch. ``0`` restores the strictly alternating
  prefill-tick / decode-tick scheduler byte-for-byte (test-pinned)."""
  return os.getenv("XOT_TPU_MIXED_TICK", "1") not in ("0", "false")


def select_mixed_budget(cap: int, burn: float | None, residents: int = 1, backlog: int = 1, floor: int = 16) -> int:
  """Prefill-token budget for one mixed tick at a (cap, burn, residents,
  backlog) point. ``cap`` is ``XOT_TPU_PREFILL_CHUNK`` (the alternating
  chunk — the budget's ceiling and the idle verdict); ``burn`` the
  interactive class's fast-window ITL burn rate (None = no signal);
  ``residents`` how many decode rows the slice would delay; ``backlog`` how
  many admissions are mid-prefill. A deeper backlog GROWS the slice toward
  the cap while ITL is not actually burning (burn < 1): slicing smaller
  never reduces the TOTAL stall the backlog imposes on residents — the same
  prefill tokens cross the device either way, small slices only smooth it —
  while TTFT for the queued prompts degrades linearly with the tick count.
  Under measured burn the table's shrink wins unscaled: smoothing is
  exactly what a burning ITL objective buys with the TTFT trade.
  ``XOT_TPU_MIXED_BUDGET`` (tokens) force-pins the verdict, clamped to
  [1, cap] — the operator's escape hatch."""
  cap = max(int(cap), 1)
  forced = int(os.getenv("XOT_TPU_MIXED_BUDGET", "0") or 0)
  if forced > 0:
    return max(min(forced, cap), 1)
  if residents <= 0:
    return cap  # idle: nothing to protect, prefill at full chunk
  frac = _MIXED_BUDGET_TABLE[-1][1]
  if burn is not None:
    for bound, f in _MIXED_BUDGET_TABLE:
      if burn >= bound:
        frac = f
        break
  budget = int(cap * frac)
  if (burn is None or burn < 1.0) and backlog > 1:
    budget = min(budget * int(backlog), cap)
  return max(min(budget, cap), min(floor, cap))


def spec_worst_advance(n_rounds: int, gamma_max: int) -> int:
  """Worst-case tokens one spec chunk advances a row: every round fully
  accepted. The scheduler's page growth and context-window band gate both
  run against this (gamma-deep speculative headroom, the analogue of the
  lookahead pipeline's one-extra-chunk reservation)."""
  return int(n_rounds) * (int(gamma_max) + 1)


def ewma_update(prev: float | None, obs: float, alpha: float = 0.3) -> float:
  """One acceptance-EWMA step (first observation seeds the average)."""
  obs = min(max(float(obs), 0.0), 1.0)
  return obs if prev is None else (1.0 - alpha) * float(prev) + alpha * obs


def kv_cache_bytes(cfg, n_layers: int, n_tokens: int, quant: str = "") -> int:
  """HBM bytes of ``n_tokens`` cached positions under ``quant`` — the block
  math shared by the scheduler's pool sizing and the draft-cache accounting
  (ISSUE 7: enabling speculation must not oversubscribe admission). int4
  packs two code nibbles per byte (half the code bytes of int8); both
  quantized modes pay one f32 scale per (token, head) per side."""
  import jax.numpy as jnp

  heads = cfg.cache_kv_heads
  per_side = cfg.cache_k_dim + cfg.cache_v_dim
  if quant == "int4":
    per_token = heads * (per_side // 2 + 2 * 4)
  elif quant:
    # int8 codes (1 byte/element) + one f32 scale per (token, head) per side.
    per_token = heads * (per_side + 2 * 4)
  else:
    per_token = heads * per_side * jnp.dtype(cfg.dtype).itemsize
  return int(n_layers) * int(n_tokens) * int(per_token)


def default_pool_pages(cfg, n_layers: int, n_slots: int, max_seq: int, page_size: int, quant: str = "") -> int:
  """Pages (trash page excluded) of the scheduler's DEFAULT pool: the dense
  bf16 layout's HBM budget — ``n_slots`` windows of ``max_seq`` tokens at 2
  bytes/element, whatever ``cfg.dtype`` is (test configs run f32 params; the
  budget story and the pinned capacity tests are the production bf16 one) —
  re-expressed in pages of the ACTUAL quant mode. An int8-KV token costs hd
  code bytes + 4 scale bytes per head per side against 2·hd bf16 bytes, so
  the same budget holds 2·hd/(hd+4) ≈ 1.88x (hd=64) the pages; int4 packs
  two nibbles per byte → ≈ 3.6x (ISSUE 11: a pool sized from the dense-48
  budget covers 96 full windows under int4)."""
  per_dense = int(n_slots) * pages_to_cover(max_seq, page_size)
  if not quant:
    return per_dense
  dense_budget = int(n_layers) * per_dense * int(page_size) * cfg.cache_kv_heads * (cfg.cache_k_dim + cfg.cache_v_dim) * 2
  return dense_budget // max(kv_cache_bytes(cfg, n_layers, page_size, quant), 1)


def lora_device_bytes(n_layers: int, d_in: int, d_out: int, rank: int, n_slots: int, itemsize: int = 4) -> int:
  """HBM bytes of ONE target projection's stacked LoRA slot factors
  (ISSUE 15): ``A [L, n_slots, d_in, r]`` + ``B [L, n_slots, r, d_out]``.
  The adapter analogue of the draft-cache block math — the registry's
  capacity is pre-allocated, so enabling multi-LoRA deducts this from the
  default page budget up front and can never oversubscribe admission."""
  return int(n_layers) * int(n_slots) * int(rank) * (int(d_in) + int(d_out)) * int(itemsize)


def lora_pages_equivalent(device_bytes: int, page_bytes: int) -> int:
  """Adapter-stack bytes expressed in pages of the serving pool (ceil) —
  what the scheduler subtracts from the default pool size, mirroring the
  draft-KV deduction (ISSUE 7)."""
  return -(-int(device_bytes) // max(int(page_bytes), 1))


def pages_to_cover(end_pos: int, page_size: int) -> int:
  """Pages a row needs so every position in ``[0, end_pos)`` maps to an
  allocated block-table entry.

  The scheduler's growth check runs this against the row's DISPATCH-time
  position — under the lookahead pipeline that position already includes the
  in-flight chunk's speculative advance, so a row always holds one extra
  chunk of page headroom and the speculative chunk can never overflow its
  block table (batch_scheduler.py ``_grow_pages``)."""
  return max((int(end_pos) + page_size - 1) // page_size, 0)


class PageAllocator:
  """Free-list + refcounted prefix cache over a fixed page pool."""

  def __init__(self, n_pages: int, page_size: int):
    self.n_pages = n_pages
    self.page_size = page_size
    self._free: list[int] = list(range(n_pages - 1, 0, -1))  # pop() -> low ids first
    self._refs: dict[int, int] = {}  # page -> active readers (cached pages only)
    self._by_key: dict[bytes, int] = {}  # chain key -> cached page
    self._key_of: dict[int, bytes] = {}  # cached page -> chain key
    self._lru: OrderedDict[int, None] = OrderedDict()  # refcount-0 cached pages
    # KV tier spill hook (inference/kv_tier.py): called with the full batch
    # of (chain_key, page) pairs an eviction run frees, BEFORE the pages
    # return to the free list — the host tier's chance to copy them out.
    self.spill_hook = None

  # ------------------------------------------------------------- allocation

  @property
  def n_free(self) -> int:
    """Pages available without evicting (the LRU adds to this on demand)."""
    return len(self._free)

  @property
  def n_available(self) -> int:
    return len(self._free) + len(self._lru)

  def cached_keys(self) -> list[bytes]:
    """Chain keys currently device-cached (shared prefix pages), newest
    first — the device half of this node's prefix advertisement (the host
    half lives in the KV tier). Insertion order approximates recency:
    donations append as requests finish."""
    return list(reversed(self._by_key))

  def alloc(self, n: int) -> list[int] | None:
    """n fresh private pages, evicting idle cached pages if needed; None if
    even eviction can't cover it (caller backpressures). Evictions run as
    ONE batch so the spill hook's device gather + D2H is a single copy op,
    not per-page round trips."""
    if n > self.n_available:
      return None
    if len(self._free) < n:
      self._evict(n - len(self._free))
    return [self._free.pop() for _ in range(n)]

  def free(self, pages: list[int]) -> None:
    """Return PRIVATE (never-cached) pages to the free list."""
    for p in pages:
      assert p not in self._key_of, f"page {p} is cached; use release()"
      self._free.append(p)

  def _evict(self, n: int) -> None:
    batch: list[tuple[bytes, int]] = []
    for _ in range(n):
      page, _ = self._lru.popitem(last=False)
      key = self._key_of.pop(page)
      del self._by_key[key]
      self._refs.pop(page, None)
      batch.append((key, page))
    if self.spill_hook is not None:
      # The hook's gather is enqueued on the device stream BEFORE any later
      # dispatch can reuse these pages, so the host copy reads valid data.
      self.spill_hook(batch)
    self._free.extend(p for _, p in batch)

  # ----------------------------------------------------------- prefix cache

  @staticmethod
  def chain_keys(tokens, page_size: int) -> list[bytes]:
    """Cumulative content keys for each FULL page of ``tokens``."""
    return PageAllocator.chain_keys_extend([], tokens, page_size)

  @staticmethod
  def chain_keys_extend(prev_keys: list[bytes], tokens, page_size: int) -> list[bytes]:
    """Extend an existing chain-key list over a LONGER token sequence,
    carrying the running hash forward from ``prev_keys[-1]`` — O(new
    tokens), not O(sequence). ``prev_keys`` must be the chain for
    ``tokens[: len(prev_keys) * page_size]`` (the caller's slot keys always
    are: same prompt, new suffix). The release path uses this to donate a
    finished/preempted row's GENERATED pages under content keys without
    rehashing its whole absorbed prompt."""
    arr = np.asarray(tokens, dtype=np.int64)  # normalize dtype: same ids -> same bytes
    keys = list(prev_keys)
    prev = keys[-1] if keys else b""
    for i in range(len(keys), len(arr) // page_size):
      prev = hashlib.blake2b(prev + arr[i * page_size : (i + 1) * page_size].tobytes(), digest_size=16).digest()
      keys.append(prev)
    return keys

  def lookup_prefix(self, keys: list[bytes]) -> list[int]:
    """Longest cached prefix; bumps each hit's refcount (caller must
    ``release`` every returned page exactly once)."""
    pages: list[int] = []
    for key in keys:
      page = self._by_key.get(key)
      if page is None:
        break
      self._refs[page] = self._refs.get(page, 0) + 1
      self._lru.pop(page, None)
      pages.append(page)
    return pages

  def release(self, page: int) -> None:
    """Drop one reader of a cached page; idle pages become evictable."""
    self._refs[page] -= 1
    if self._refs[page] <= 0:
      self._refs.pop(page)
      self._lru[page] = None

  def insert_cached(self, key: bytes, page: int) -> bool:
    """Donate a private page to the cache (refcount 0, evictable). Returns
    False (page NOT adopted — caller should ``free`` it) when the chain is
    already cached."""
    if key in self._by_key:
      return False
    self._by_key[key] = page
    self._key_of[page] = key
    self._lru[page] = None
    return True

  def is_cached(self, key: bytes) -> bool:
    """Whether ``key``'s page is device-cached (referenced or idle-LRU).
    The host-restore path uses this to stop a restore run at the first key
    still resident: a chain's suffix can outlive its evicted prefix in the
    LRU, and ``adopt_restored`` requires the key to be absent."""
    return key in self._by_key

  def adopt_restored(self, key: bytes, page: int) -> None:
    """Register a host-tier restore target as a CACHED page with one active
    reader (the restoring request — it must ``release`` it exactly once,
    like any ``lookup_prefix`` hit). The page was just allocated private and
    written with the key's content, so concurrent requests sharing the
    prefix dedup onto it immediately."""
    assert key not in self._by_key, "restore raced an identical cached chain"
    self._by_key[key] = page
    self._key_of[page] = key
    self._refs[page] = 1

  def audit(self) -> dict:
    """Internal-consistency check + accounting snapshot for the invariant
    tests (ISSUE 6 satellite): every pool page is in EXACTLY one of {free,
    cached-idle (LRU), cached-referenced, caller-held private}; the first
    three are visible here, so ``free + cached == n_pages - 1 - in_use``
    must hold for the caller's private count."""
    free = set(self._free)
    assert len(free) == len(self._free), "double-freed page on the free list"
    cached = set(self._key_of)
    assert not (free & cached), f"pages both free and cached: {sorted(free & cached)}"
    lru = set(self._lru)
    reffed = set(self._refs)
    assert lru <= cached and reffed <= cached, "ref/LRU entry for a non-cached page"
    assert not (lru & reffed), "cached page both idle and referenced"
    assert lru | reffed == cached, "cached page neither idle nor referenced"
    assert all(n > 0 for n in self._refs.values()), "non-positive refcount survived release"
    assert len(self._by_key) == len(self._key_of), "key<->page maps diverged"
    assert 0 not in free | cached, "trash page 0 escaped into the pool"
    return {"free": len(free), "cached": len(cached), "lru": len(lru), "referenced": len(reffed)}
