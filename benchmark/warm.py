"""Warm-up: every prefill shape the planned requests will use, before the window opens.

The scheduler compiles one program per shape of prompt processing, and which
prompt lengths share a shape is its rule, not the traffic's. That rule is
restated here, from the numbers a configuration file gives under ``warm_shape_rule``,
so that the warm-up can send one prompt per shape instead of one per length:

  mixed_slices   (configurations with mixed ticks) While decode rows are
                 resident a prompt longer than ``bucket_tokens`` is cut into
                 slices of ``slice_tokens``, the last one shortened to leave
                 exactly ``bucket_tokens``; a slice is one program per
                 (length padded to a power of two, pages covering its end
                 rounded to a power of two). The last ``bucket_tokens`` - or a
                 whole prompt that short - go through the group dispatch: one
                 program per (group size rounded to a power of two, pages
                 covering the prompt rounded to a power of two).
                 When NO decode row is resident (``idle_chunk_tokens`` in the
                 rule; warmed where the traffic file's ``warm`` block says
                 ``idle``: a server that has drained its queue, as an open loop
                 below its knee does many times a window) nothing is sliced:
                 the group dispatch takes the whole prompt in chunks of
                 ``idle_chunk_tokens``, each one program per (length padded
                 to ``bucket_tokens``, pages covering its padded end rounded
                 to a power of two) - ``idle_shapes_of``.
                 ``final_escorts`` in the ``warm`` block lists how many short prompts wait beside each distinct final
                 window (1: a group of two, 3: of four; one where the file
                 lists a group size above one and says nothing else).
  padded_groups  (no mixed ticks) A group of k admissions is one program per
                 (k rounded to a power of two, longest prompt padded to
                 ``bucket_tokens``).

The idle shapes are sent first, one prompt at a time to a server with nothing
resident. Then one long "anchor" request is held resident, so the paths are the
ones a busy window takes. The lengths sent are the planned requests' own (the
seed reorders one fixed multiset, so they are the same in every run); the rule
only says which of them repeat a shape. If the rule changes in the program,
``window_compiles`` reads above 0 and the run says which family compiled."""

from __future__ import annotations

import asyncio
import time

import numpy as np

import client
from generators import sizes


def pow2(n: int) -> int:
  p = 1
  while p < n:
    p *= 2
  return p


def shapes_of(length: int, rule: dict) -> list[tuple]:
  """The compiled prefill shapes one prompt of ``length`` tokens runs through alone."""
  bucket = int(rule["bucket_tokens"])
  if rule["kind"] == "padded_groups":
    return [("group", -(-length // bucket) * bucket)]
  page, budget = int(rule["page_tokens"]), int(rule["slice_tokens"])
  out, start = [], 0
  while length - start > bucket:
    pad = pow2(min(budget, length - start - bucket))
    out.append(("slice", pad, pow2(-(-(start + pad) // page))))
    start += min(budget, length - start - bucket)
  return out + [("final", pow2(-(-max(length, bucket) // page)))]


def idle_shapes_of(length: int, rule: dict) -> list[tuple]:
  """The compiled prefill shapes one prompt of ``length`` tokens runs through alone when no decode row is resident."""
  chunk = int(rule.get("idle_chunk_tokens", 0))
  if rule["kind"] != "mixed_slices" or chunk <= 0:
    return []  # one path, busy or idle: ``shapes_of`` has it
  bucket, page = int(rule["bucket_tokens"]), int(rule["page_tokens"])
  out, start = [], 0
  while start < length:
    pad = -(-min(chunk, length - start) // bucket) * bucket
    out.append(("idle", pad, pow2(-(-(start + pad) // page))))
    start += chunk
  return out


def cover(lengths: list[int], rule: dict, shapes=shapes_of) -> tuple[list[int], list[int]]:
  """(one length per shape not yet covered, one length per distinct final shape)."""
  seen, firsts, finals = set(), [], {}
  for n in sorted(set(lengths)):
    got = shapes(n, rule)
    if not got:
      continue
    if any(s not in seen for s in got):
      firsts.append(n)
      seen.update(got)
    finals.setdefault(got[-1], n)
  return firsts, sorted(finals.values())


async def _send(session, stack, rng, vocab: int, length: int, max_tokens: int = 2) -> client.Rec:
  rec = client.Rec(0.0, length, max_tokens)
  return await client.stream_chat(session, stack.url, stack.model_id, sizes.prompt_ids(rng, length, vocab), max_tokens, rec)


async def _escorted(session, stack, rng, vocab: int, length: int, short: int, escorts: int = 1) -> list[client.Rec]:
  """One prompt with ``escorts`` short ones always waiting beside it, so that
  its final dispatch is a group of ``escorts`` + 1."""
  main = asyncio.create_task(_send(session, stack, rng, vocab, length))
  recs = []

  async def escort() -> None:
    while not main.done():
      recs.append(await _send(session, stack, rng, vocab, short, 1))

  await asyncio.gather(*(escort() for _ in range(escorts)))
  return [await main, *recs]


async def run(session, stack, rule: dict, warm: dict, vocab: int, seed: int, lengths: list[int]) -> dict:
  """``rule``: the configuration's ``warm_shape_rule``; ``warm``: the traffic file's
  ``warm`` block; ``lengths``: the prompt lengths of the planned requests (ramp and window)."""
  rng = np.random.default_rng([int(seed), 3])
  firsts, finals = cover(lengths, rule)
  idle = cover(lengths, rule, idle_shapes_of)[0] if warm.get("idle") else []  # a closed loop's server is never idle inside its window
  t0 = time.perf_counter()
  recs: list[client.Rec] = [await _send(session, stack, rng, vocab, n) for n in idle]  # one at a time: each meets a server with nothing resident
  idle_s = time.perf_counter() - t0
  together = max(int(warm.get("concurrent", 1)), 1)  # how many warm groups are in flight at once
  anchor_rec = client.Rec(0.0, min(lengths), int(warm.get("anchor_tokens", 2048)))
  anchor = asyncio.create_task(client.stream_chat(session, stack.url, stack.model_id, sizes.prompt_ids(rng, min(lengths), vocab), anchor_rec.max_tokens, anchor_rec))
  while anchor_rec.first is None and not anchor.done():
    await asyncio.sleep(0.01)
  try:
    if rule["kind"] == "mixed_slices":
      # Only one prompt is sliced per tick, so groups larger than one form at the final dispatch alone.
      for i in range(0, len(firsts), together):
        recs += await asyncio.gather(*(_send(session, stack, rng, vocab, n) for n in firsts[i : i + together]))
      short = min(min(lengths), int(rule["bucket_tokens"]))
      for escorts in warm.get("final_escorts", [1] if max(warm.get("group_sizes", [1])) > 1 else []):
        for n in finals:
          recs += await _escorted(session, stack, rng, vocab, n, short, escorts)
    else:
      for k in warm.get("group_sizes", [1]):
        for i in range(0, len(firsts), together):
          recs += await asyncio.gather(*(_send(session, stack, rng, vocab, n) for n in firsts[i : i + together] for _ in range(k)))
  finally:
    anchor.cancel()
    await asyncio.gather(anchor, return_exceptions=True)
  return {"warm_requests": len(recs), "warm_failed": sum(not r.ok for r in recs), "warm_idle": idle, "warm_idle_s": round(idle_s, 3), "warm_lengths": firsts, "warm_finals": finals, "anchor_tokens_seen": anchor_rec.tokens}
