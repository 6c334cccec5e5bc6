"""The load generator's client side: one streamed chat completion over HTTP,
stamped by the client's clock, and the open and closed loops that replay a
generator's plan. Tokens are counted from the events (one word = one token
with ``tokenizer.py``), never assumed from ``max_tokens``."""

from __future__ import annotations

import asyncio
import json
import time

from tokenizer import text_of

now = time.perf_counter


class Rec:
  """One request as the client saw it (seconds on the client's clock)."""

  __slots__ = ("due", "sent", "first", "last", "tokens", "events", "status", "error", "rid", "prompt_tokens", "max_tokens", "text")

  def __init__(self, due: float, prompt_tokens: int, max_tokens: int):
    self.due, self.prompt_tokens, self.max_tokens = due, prompt_tokens, max_tokens
    self.sent = self.first = self.last = None
    self.tokens, self.events, self.status, self.error, self.rid, self.text = 0, [], None, None, None, []

  @property
  def ok(self) -> bool:
    return self.status == 200 and self.error is None and self.tokens == self.max_tokens

  def tpot(self) -> float | None:
    return (self.last - self.first) / (self.tokens - 1) if self.ok and self.tokens > 1 else None


def tokens_between(recs: list, start: float, end: float) -> int:
  """Tokens the client received in [start, end), over all of ``recs``."""
  return sum(n for r in recs for t, n in r.events if start <= t < end)


async def stream_chat(session, url: str, model: str, prompt_ids, max_tokens: int, rec: Rec, keep_text: bool = False) -> Rec:
  body = {"model": model, "messages": [{"role": "user", "content": text_of(prompt_ids)}], "stream": True, "temperature": 0, "max_tokens": int(max_tokens)}
  rec.sent = now()
  try:
    async with session.post(f"{url}/v1/chat/completions", json=body) as resp:
      rec.status = resp.status
      if resp.status != 200:
        rec.error = (await resp.text())[:300]
        return rec
      async for raw in resp.content:
        if not raw.startswith(b"data: "):
          continue
        t = now()
        payload = raw[6:].strip()
        if payload == b"[DONE]":
          break
        chunk = json.loads(payload)
        if "error" in chunk:
          rec.error = str(chunk["error"])[:300]
          break
        if rec.rid is None:
          rec.rid = chunk.get("id", "").removeprefix("chatcmpl-")
        for choice in chunk.get("choices", ()):
          delta = choice.get("delta", {}).get("content")
          if delta:
            n = len(delta.split())
            if rec.first is None:
              rec.first = t
            rec.last = t
            rec.tokens += n
            rec.events.append((t, n))
            if keep_text:
              rec.text.append(delta)
  except asyncio.CancelledError:
    raise
  except Exception as e:  # noqa: BLE001 — a failed request is a counted result, not a crash
    rec.error = repr(e)[:300]
  return rec


async def open_loop(session, url: str, model: str, phases: list[tuple[list[dict], bool]], t0: float, drain_s: float) -> list[Rec]:
  """Send each request at ``t0 + due_s`` whatever the earlier ones are doing.
  ``phases`` is [(requests, measured)]; returns the measured records. Unfinished
  requests are cancelled ``drain_s`` after the last arrival and count as failed."""
  measured: list[Rec] = []
  tasks: list[asyncio.Task] = []
  last_due = t0
  for reqs, keep in phases:
    for r in reqs:
      due = t0 + r["due_s"]
      last_due = max(last_due, due)
      delay = due - now()
      if delay > 0:
        await asyncio.sleep(delay)
      rec = Rec(due, len(r["prompt"]), r["max_tokens"])
      tasks.append(asyncio.create_task(stream_chat(session, url, model, r["prompt"], r["max_tokens"], rec)))
      if keep:
        measured.append(rec)
  _done, pending = await asyncio.wait(tasks, timeout=max(last_due + drain_s - now(), 0.1)) if tasks else (set(), set())
  for t in pending:
    t.cancel()
  await asyncio.gather(*tasks, return_exceptions=True)
  return measured


async def closed_loop(session, url: str, model: str, queue: list[dict], clients: int, recs: list[Rec], close_at: asyncio.Future) -> list[Rec]:
  """``clients`` callers share ``queue`` in order; each sends its next request
  when its last one ends, and appends its record to ``recs``. ``close_at``
  resolves to the window's closing time once the caller knows it (run.py opens
  the window only when every caller has its first token). In-flight requests
  are cancelled at the close (the tokens they delivered inside the window still count)."""
  it = iter(queue)

  async def caller() -> None:
    for r in it:
      if close_at.done() and now() >= close_at.result():
        return
      rec = Rec(now(), len(r["prompt"]), r["max_tokens"])
      recs.append(rec)
      await stream_chat(session, url, model, r["prompt"], r["max_tokens"], rec)

  tasks = [asyncio.create_task(caller()) for _ in range(clients)]
  try:
    t_close = await close_at
    await asyncio.wait(tasks, timeout=max(t_close - now(), 0.0))
  finally:
    for t in tasks:
      t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
  return recs
