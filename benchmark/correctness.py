"""What decides ``correct``: the served path against ``reference.py`` at the
published widths, on the device the cell runs on, in set-up.

One seeded prompt of PROMPT_TOKENS tokens is served through
``/v1/chat/completions`` (blocking, greedy, ``logprobs`` with 5 alternatives,
N_GEN tokens). The reference runs teacher-forced over prompt + answer, and

  1. every served log-prob (the chosen token's and the 5 alternatives', which
     the API scores with the program's own parallel forward) is compared with
     the reference's log-softmax at the same position and token;
  2. every served token (picked by prefill + the batched decode steps through
     the page pool) must be within GREEDY_MARGIN of the reference's best
     log-prob at its position. Logits, not sampled tokens: with random weights
     the top two are often closer than the arithmetic's rounding;
  3. a streamed and a blocking answer to one prompt of SHORT_TOKENS tokens
     must be equal. That prompt is shorter than a page, so the second request
     shares no cached page with the first: a second request with the long
     prompt takes the prefix-cache path (its last tokens prefilled over int8
     pages the first request wrote), whose greedy answer can differ in the
     last bit. What that path does is logged beside the verdict
     (``prefix_hit_first_token_margin``), not judged.

What each part covers. The log-probs of (1) come from the API's scoring
forward (``prefill.score_last``: the same weights, attention and expert math,
one parallel pass, no page pool). The page pool and the batched decode steps -
the path the cells measure - are judged by (2) and (3) only: a decode step
that picked a wrong expert or read a wrong page chooses a token the reference
puts nats below its best.

Limits and why. The three numbers compared — the mean |served - reference|
log-prob over the compared entries, the worst single entry, and the reference's
best log-prob minus its log-prob of the served token — are held to the limits
of the configuration's kind: ``LIMITS`` in ``benchmark/arch_<kind>.py``, with the
readings each was set from in ``LIMITS_WHY`` beside them (numbers in PERF.md):
about three times what the chip read over the seeds of the proving runs, and
under half what the weakest probe of the kind's ``probes`` reads.
``--probe-sensitivity`` runs the probes."""

from __future__ import annotations

import numpy as np

import arch
from tokenizer import text_of, token_id

PROMPT_TOKENS = 160
N_GEN = 8
TOP = 5
SHORT_TOKENS = 48

def check_prompt(seed: int, vocab: int) -> np.ndarray:
  return np.random.default_rng([int(seed), 7]).integers(3, vocab, size=PROMPT_TOKENS, dtype=np.int64)


async def served_logprobs(session, url: str, model: str, prompt_ids) -> tuple[list[int], list[list[tuple[int, float]]], str]:
  body = {
    "model": model, "messages": [{"role": "user", "content": text_of(prompt_ids)}], "stream": False, "temperature": 0,
    "max_tokens": N_GEN, "logprobs": True, "top_logprobs": TOP,
  }
  async with session.post(f"{url}/v1/chat/completions", json=body) as resp:
    data = await resp.json()
    if resp.status != 200:
      raise RuntimeError(f"correctness request refused: {resp.status} {data}")
  choice = data["choices"][0]
  if not choice.get("logprobs"):
    raise RuntimeError("the API returned no logprobs (score_tokens unavailable on this serving plan)")
  gen, entries = [], []
  for item in choice["logprobs"]["content"]:
    gen.append(token_id(item["token"]))
    row = [(token_id(item["token"]), float(item["logprob"]))]
    row += [(token_id(t["token"]), float(t["logprob"])) for t in item["top_logprobs"]]
    entries.append(row)
  return gen, entries, choice["message"]["content"]


def compare(entries, gen, ref_lp: np.ndarray) -> dict:
  diffs = [abs(lp - float(ref_lp[j, tok])) for j, row in enumerate(entries) for tok, lp in row]
  margins = [float(ref_lp[j].max() - ref_lp[j, tok]) for j, tok in enumerate(gen)]
  return {"mean_abs": float(np.mean(diffs)), "max_abs": float(np.max(diffs)), "greedy_margin": float(np.max(margins)), "entries": len(diffs)}


def verdict(c: dict, kind: str) -> bool:
  return all(c[name] <= limit for name, limit in arch.load(kind).LIMITS.items())


def compared(c: dict, kind: str) -> dict:
  """Each number compared beside its limit: {name: [value, limit]}."""
  return {name: [c.get(name), limit] for name, limit in arch.load(kind).LIMITS.items()}


async def check(session, stack, hf: dict, params, seed: int, probe: bool = False) -> tuple[bool, dict]:
  import client
  import reference

  vocab = int(hf["vocab_size"])
  prompt = check_prompt(seed, vocab)
  gen, entries, blocking_text = await served_logprobs(session, stack.url, stack.model_id, prompt)
  if len(gen) != N_GEN:
    return False, {"error": f"asked for {N_GEN} tokens, got {len(gen)}"}
  tokens = np.concatenate([prompt, np.asarray(gen, np.int64)])
  ref_lp = np.asarray(reference.reference_logprobs(params, hf, tokens, N_GEN))
  out = compare(entries, gen, ref_lp)
  # The long prompt again, streamed: the prefix-cache path. Logged, not judged.
  rec = await client.stream_chat(session, stack.url, stack.model_id, prompt, N_GEN, client.Rec(0.0, len(prompt), N_GEN), keep_text=True)
  again = [token_id(w) for w in "".join(rec.text).split()]
  if again:
    out["prefix_hit_first_token_margin"] = float(ref_lp[0].max() - ref_lp[0, again[0]])
    out["prefix_hit_equals_first_answer"] = again == gen
  short = check_prompt(seed + 1, vocab)[:SHORT_TOKENS]
  _g, _e, short_blocking = await served_logprobs(session, stack.url, stack.model_id, short)
  rec = await client.stream_chat(session, stack.url, stack.model_id, short, N_GEN, client.Rec(0.0, len(short), N_GEN), keep_text=True)
  out["stream_equals_blocking"] = rec.ok and "".join(rec.text).split() == short_blocking.split()
  if not out["stream_equals_blocking"]:
    out["stream_mismatch"] = {"blocking": short_blocking, "streamed": "".join(rec.text), "status": rec.status, "error": rec.error}
  if probe:
    out["sensitivity"] = sensitivity(params, hf, tokens, entries, gen)
  return verdict(out, hf["arch_kind"]) and out["stream_equals_blocking"], out


def sensitivity(params, hf: dict, tokens, entries, gen) -> dict:
  """The same comparison against deliberately wrong references: what the
  tolerances must still refuse."""
  import reference

  probes = arch.load(hf["arch_kind"]).probes(hf)
  return {name: compare(entries, gen, np.asarray(reference.reference_logprobs(params, hf, tokens, N_GEN, **kw))) for name, kw in probes.items()}
