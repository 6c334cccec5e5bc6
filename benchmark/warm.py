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
  padded_groups  (no mixed ticks) A group of k admissions is one program per
                 (k rounded to a power of two, longest prompt padded to
                 ``bucket_tokens``).

One long "anchor" request is held resident throughout, so the paths are the
ones the window takes. The lengths sent are the planned requests' own (the
seed reorders one fixed multiset, so they are the same in every run); the rule
only says which of them repeat a shape. If the rule changes in the program,
``window_compiles`` reads above 0 and the run says which family compiled."""

from __future__ import annotations

import asyncio

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


def cover(lengths: list[int], rule: dict) -> tuple[list[int], list[int]]:
  """(one length per shape not yet covered, one length per distinct final shape)."""
  seen, firsts, finals = set(), [], {}
  for n in sorted(set(lengths)):
    got = shapes_of(n, rule)
    if any(s not in seen for s in got):
      firsts.append(n)
      seen.update(got)
    finals.setdefault(got[-1], n)
  return firsts, sorted(finals.values())


async def _send(session, stack, rng, vocab: int, length: int, max_tokens: int = 2) -> client.Rec:
  rec = client.Rec(0.0, length, max_tokens)
  return await client.stream_chat(session, stack.url, stack.model_id, sizes.prompt_ids(rng, length, vocab), max_tokens, rec)


async def _escorted(session, stack, rng, vocab: int, length: int, short: int) -> list[client.Rec]:
  """One prompt with a short one always waiting beside it, so that its final
  dispatch is a group of two."""
  main = asyncio.create_task(_send(session, stack, rng, vocab, length))
  recs = []
  while not main.done():
    recs.append(await _send(session, stack, rng, vocab, short, 1))
  return [await main, *recs]


async def run(session, stack, rule: dict, warm: dict, vocab: int, seed: int, lengths: list[int]) -> dict:
  """``rule``: the configuration's ``warm_shape_rule``; ``warm``: the traffic file's
  ``warm`` block; ``lengths``: the prompt lengths of the planned requests (ramp and window)."""
  rng = np.random.default_rng([int(seed), 3])
  firsts, finals = cover(lengths, rule)
  together = max(int(warm.get("concurrent", 1)), 1)  # how many warm groups are in flight at once
  anchor_rec = client.Rec(0.0, min(lengths), int(warm.get("anchor_tokens", 2048)))
  anchor = asyncio.create_task(client.stream_chat(session, stack.url, stack.model_id, sizes.prompt_ids(rng, min(lengths), vocab), anchor_rec.max_tokens, anchor_rec))
  while anchor_rec.first is None and not anchor.done():
    await asyncio.sleep(0.01)
  recs: list[client.Rec] = []
  try:
    if rule["kind"] == "mixed_slices":
      # Only one prompt is sliced per tick, so groups larger than one form at the final dispatch alone.
      for i in range(0, len(firsts), together):
        recs += await asyncio.gather(*(_send(session, stack, rng, vocab, n) for n in firsts[i : i + together]))
      short = min(min(lengths), int(rule["bucket_tokens"]))
      if max(warm.get("group_sizes", [1])) > 1:
        for n in finals:
          recs += await _escorted(session, stack, rng, vocab, n, short)
    else:
      for k in warm.get("group_sizes", [1]):
        for i in range(0, len(firsts), together):
          recs += await asyncio.gather(*(_send(session, stack, rng, vocab, n) for n in firsts[i : i + together] for _ in range(k)))
  finally:
    anchor.cancel()
    await asyncio.gather(anchor, return_exceptions=True)
  return {"warm_requests": len(recs), "warm_failed": sum(not r.ok for r in recs), "warm_lengths": firsts, "warm_finals": finals, "anchor_tokens_seen": anchor_rec.tokens}
