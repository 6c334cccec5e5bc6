"""The load generators' schedules as a function of the seed."""

import asyncio
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import client  # noqa: E402
import common  # noqa: E402
from generators import closed, open_poisson, sizes  # noqa: E402


def _open(seed, rate=4.0, seconds=20):
  t = common.load_traffic("chat-poisson")
  t["rate_rps"] = rate
  return open_poisson.plan(t, seed, seconds, 32768)


def test_same_seed_same_schedule():
  a, b = _open(2**31 + 5), _open(2**31 + 5)
  assert [(r["due_s"], r["max_tokens"], r["prompt"].tolist()) for r in a["window"]] == [(r["due_s"], r["max_tokens"], r["prompt"].tolist()) for r in b["window"]]


def test_seeds_reorder_the_same_work():
  a, b = _open(1), _open(2)
  assert Counter(len(r["prompt"]) for r in a["window"]) == Counter(len(r["prompt"]) for r in b["window"])
  assert Counter(r["max_tokens"] for r in a["window"]) == Counter(r["max_tokens"] for r in b["window"])
  assert [r["due_s"] for r in a["window"]] != [r["due_s"] for r in b["window"]]
  assert len(a["window"]) == 80 and a["ramp_s"] <= a["window"][0]["due_s"] and a["window"][-1]["due_s"] < a["ramp_s"] + 20


def test_a_seed_turns_the_file_s_one_cycle():
  """A seed starts the traffic file's one cycle of (gap, prompt, answer) triples at another place: the same neighbours, but at the wrap."""
  t = common.load_traffic("chat-poisson")
  plans = [open_poisson.plan(t, seed, 51, 32768)["window"] for seed in (1, 2, 2**31 + 7)]
  triples = [[(round(b["due_s"] - a["due_s"], 9), len(a["prompt"]), a["max_tokens"]) for a, b in zip(w, w[1:])] for w in plans]
  for other in triples[1:]:
    at = [(p, m) for _, p, m in other].index(triples[0][0][1:])
    assert len(set(other) & set(triples[0])) >= len(other) - 1 and other[at] == triples[0][0]
  assert triples[0] != triples[1] and [r["prompt"].tolist() for r in plans[0]] != [r["prompt"].tolist() for r in plans[1]]
  other_cycle = open_poisson.plan({**t, "order_seed": 1}, 1, 51, 32768)["window"]
  assert Counter(len(r["prompt"]) for r in other_cycle) == Counter(len(r["prompt"]) for r in plans[0]) and Counter(r["max_tokens"] for r in other_cycle) == Counter(r["max_tokens"] for r in plans[0])
  assert [len(r["prompt"]) for r in other_cycle] != [len(r["prompt"]) for r in plans[0]]


def test_lengths_are_the_stated_distribution_unrounded():
  t = common.load_traffic("chat-poisson")
  q = sizes.quantiles(t["prompt_tokens"], 10001)
  assert min(q) == 32 and max(q) == 3072 and q[5000] == 512 and len({n % 128 for n in q}) > 100
  assert 0.15 < sum(n < 256 for n in q) / len(q) < 0.25  # the short prompts are there
  c = sizes.quantiles(common.load_traffic("decode-closed")["prompt_tokens"], 1001)
  assert min(c) == 64 and max(c) == 1024 and c[500] == 256


def test_warm_up_covers_every_shape_of_the_planned_lengths():
  import warm

  rule = common.load_config("mistral-7b-int8")["warm_shape_rule"]
  # Worked by hand from the scheduler's rule: 2748 tokens = slices [0,1024) [1024,2048) [2048,2620) + the last 128.
  assert warm.shapes_of(2748, rule) == [("slice", 1024, 16), ("slice", 1024, 32), ("slice", 1024, 64), ("final", 64)]
  assert warm.shapes_of(141, rule) == [("slice", 16, 1), ("final", 4)] and warm.shapes_of(95, rule) == [("final", 2)]
  assert warm.shapes_of(1152, rule) == [("slice", 1024, 16), ("final", 32)] and warm.shapes_of(1153, rule) == [("slice", 1024, 16), ("slice", 1, 32), ("final", 32)]
  lengths = open_poisson.prompt_lengths(_open(3, rate=0.55, seconds=51))
  firsts, finals = warm.cover(lengths, rule)
  want = {s for n in lengths for s in warm.shapes_of(n, rule)}
  assert {s for n in firsts for s in warm.shapes_of(n, rule)} == want and len(firsts) < len(set(lengths))
  assert {warm.shapes_of(n, rule)[-1] for n in finals} == {s for s in want if s[0] == "final"}
  groups = common.load_config("moonlight-a3b-d14")["warm_shape_rule"]
  assert warm.shapes_of(129, groups) == [("group", 256)] and warm.shapes_of(1024, groups) == [("group", 1024)]


def test_warm_up_covers_the_idle_server_s_shapes():
  """With no decode row resident the scheduler slices nothing: `_chunk_ready` takes the prompt in chunks of
  XOT_TPU_PREFILL_CHUNK padded to PREFILL_BUCKET, `_page_window` over the padded end (worked by hand)."""
  import warm

  rule = common.load_config("mistral-7b-int8")["warm_shape_rule"]
  assert warm.idle_shapes_of(95, rule) == [("idle", 128, 2)] and warm.idle_shapes_of(2048, rule) == [("idle", 2048, 32)]
  assert warm.idle_shapes_of(2049, rule) == [("idle", 2048, 32), ("idle", 128, 64)] and warm.idle_shapes_of(2748, rule) == [("idle", 2048, 32), ("idle", 768, 64)]
  lengths = open_poisson.prompt_lengths(_open(3, rate=0.56, seconds=51))
  idle = warm.cover(lengths, rule, warm.idle_shapes_of)[0]
  assert {s for n in idle for s in warm.idle_shapes_of(n, rule)} == {s for n in lengths for s in warm.idle_shapes_of(n, rule)} and len(idle) <= len({-(-n // 128) for n in lengths})
  groups = common.load_config("moonlight-a3b-d14")["warm_shape_rule"]  # no mixed ticks: one path, busy or idle
  assert warm.idle_shapes_of(700, groups) == [] and warm.cover(lengths, groups, warm.idle_shapes_of) == ([], [])


def test_closed_queue_is_stratified():
  q = closed.plan(common.load_traffic("decode-closed"), 9, 20, 1000)["queue"]
  first, second = q[:16], q[16:32]
  assert Counter(len(r["prompt"]) for r in first) == Counter(len(r["prompt"]) for r in second)


def test_open_loop_due_times_do_not_wait_for_completions(monkeypatch):
  """A server that never answers must still be sent every request on time."""
  sent = []

  async def never(session, url, model, prompt, max_tokens, rec, keep_text=False):
    rec.sent = client.now()
    sent.append(rec)
    await asyncio.sleep(3600)

  monkeypatch.setattr(client, "stream_chat", never)
  reqs = [{"due_s": 0.02 * i, "prompt": [1], "max_tokens": 1} for i in range(10)]

  async def go():
    return await client.open_loop(None, "", "", [(reqs, True)], client.now(), drain_s=0.05)

  recs = asyncio.run(go())
  assert len(recs) == len(sent) == 10
  assert max(r.sent - r.due for r in recs) < 0.02


def test_replay_rate_moves_with_the_order_of_one_multiset():
  """tools/closed_replay.py: seeds reorder the same sizes, and the replayed rate moves by a few percent at most."""
  sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
  import closed_replay

  def rate(queue):
    return closed_replay.replay(queue, 16, 6.0, 51.0, 128, 8, 0.23, 0.045, 0.00026)

  plans = [closed.plan(common.load_traffic("decode-closed"), seed, 57, 1000)["queue"] for seed in (1, 2, 3, 4)]
  rates = {rate([(len(r["prompt"]), r["max_tokens"]) for r in q]) for q in plans}
  assert len(rates) > 1 and max(rates) / min(rates) < 1.05


def test_closed_loop_keeps_sending_until_the_close_it_is_given(monkeypatch):
  """The closing time arrives while the callers run (run.py sets it once every caller has a first token)."""

  async def quick(session, url, model, prompt, max_tokens, rec, keep_text=False):
    rec.sent = rec.first = client.now()
    await asyncio.sleep(0.005)

  monkeypatch.setattr(client, "stream_chat", quick)
  queue = [{"prompt": [1], "max_tokens": 1}] * 1000

  async def go():
    recs, close_at = [], asyncio.get_running_loop().create_future()

    async def decide():
      while len(recs) < 4:
        await asyncio.sleep(0.001)
      close_at.set_result(client.now() + 0.05)

    decider = asyncio.create_task(decide())
    await client.closed_loop(None, "", "", queue, 4, recs, close_at)
    await decider
    return recs, close_at.result()

  recs, t_close = asyncio.run(go())
  assert 8 < len(recs) < 1000 and max(r.sent for r in recs) < t_close + 0.02
