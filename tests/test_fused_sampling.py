"""Fused sampling epilogue (ISSUE 11): prefill + first-token sampling in ONE
device dispatch.

Contract: with ``XOT_TPU_FUSED_SAMPLING`` on (the default), the batched
scheduler's admissions run the fused prefill programs
(``prefill_into_{slots,pages_many}_sampled``) and never dispatch the
separate ``sample_rows`` epilogue — one device dispatch fewer per prefill
group (dispatch-count spy) — while the emitted streams stay TOKEN-IDENTICAL
to the unfused two-dispatch path, for greedy and seeded-sampled traffic,
lookahead on AND off (same ``_next_token_batched`` math on the same key).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xotorch_support_jetson_tpu.models.decoder as decoder_mod
from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params

CFG = tiny_test_config(n_layers=2, max_seq_len=128)
KEY = jax.random.PRNGKey(0)
PROMPTS = [[3, 25, 9], [7, 1, 88, 42, 5], [100], [9, 9, 9, 1]]


def _engine(params, shard, seed=0):
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(shard, CFG, params)
  engine._key = jax.random.PRNGKey(seed)  # identical key schedules across A/B runs
  return engine


def _serve(server, prompts, n_gen, temp=0.0):
  streams: dict[str, list] = {}

  async def run():
    def emit(rid, toks, finished):
      streams.setdefault(rid, []).extend(toks)

    return await asyncio.gather(
      *(
        server.submit(f"r{i}", np.asarray(p, np.int32), max_tokens=n_gen, temp=temp, top_k=35, eos_ids=(), emit=emit)
        for i, p in enumerate(prompts)
      )
    )

  outs = asyncio.run(run())
  return outs, [streams[f"r{i}"] for i in range(len(prompts))]


class _DispatchSpy:
  """Counts the scheduler's per-admission device dispatches: prefill-program
  calls (fused or not) and separate sample_rows epilogue calls."""

  def __init__(self, server, monkeypatch):
    self.prefills = 0
    self.samples = 0
    ops = server.ops
    for name in ("prefill_into_slots", "prefill_into_pages_many", "prefill_into_slots_sampled", "prefill_into_pages_many_sampled"):
      if not hasattr(ops, name):
        continue
      orig = getattr(ops, name)

      def counted(*a, _orig=orig, **kw):
        self.prefills += 1
        return _orig(*a, **kw)

      monkeypatch.setattr(ops, name, counted)
    orig_sample = decoder_mod.sample_rows

    def counted_sample(*a, **kw):
      self.samples += 1
      return orig_sample(*a, **kw)

    monkeypatch.setattr(decoder_mod, "sample_rows", counted_sample)


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("lookahead", [True, False])
def test_fused_sampling_identity_and_dispatch_count(monkeypatch, paged, lookahead):
  """Greedy A/B: fused == unfused token-for-token on both layouts, both
  scheduler modes; the spy proves the fused run made ZERO sample_rows
  dispatches (one fewer device dispatch per prefill group) while the
  unfused run made one per group."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1" if paged else "0")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  n_gen = 6
  outs = {}
  for fused in (True, False):
    monkeypatch.setenv("XOT_TPU_FUSED_SAMPLING", "1" if fused else "0")
    server = BatchedServer(_engine(params, shard), n_slots=4, chunk=2, lookahead=lookahead)
    assert server.fused_sampling is fused
    spy = _DispatchSpy(server, monkeypatch)
    outs[fused], streams = _serve(server, PROMPTS, n_gen)
    for o, s in zip(outs[fused], streams):
      assert s == o
    assert spy.prefills >= 1
    if fused:
      assert spy.samples == 0, "fused mode must never dispatch the separate sampling epilogue"
    else:
      assert spy.samples >= 1, "unfused mode samples in a second dispatch per group"
      assert spy.samples <= spy.prefills
    server.shutdown()
  assert outs[True] == outs[False], f"fused sampling diverged: {outs[True]} != {outs[False]}"


@pytest.mark.parametrize("lookahead", [True, False])
def test_fused_sampling_seeded_sampled_identity(monkeypatch, lookahead):
  """Seeded SAMPLED traffic (temp > 0): the fused program consumes the same
  event-loop key split as the unfused sample_rows call, so re-seeding the
  engine gives byte-identical sampled streams either way."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  outs = {}
  for fused in (True, False):
    monkeypatch.setenv("XOT_TPU_FUSED_SAMPLING", "1" if fused else "0")
    server = BatchedServer(_engine(params, shard, seed=123), n_slots=2, chunk=2, lookahead=lookahead)
    outs[fused], _ = _serve(server, [[5, 17, 2, 99]], 9, temp=0.8)
    server.shutdown()
  assert len(outs[True][0]) == 9
  assert outs[True] == outs[False], f"seeded sampled A/B diverged: {outs}"


def test_fused_sampling_unsupported_backend_falls_back(monkeypatch):
  """A backend without the fused programs (pp/sp report
  fused_sampling_supported() == False) keeps the two-dispatch path even
  with the env knob on."""
  params, shard = full_model_params(KEY, CFG)
  engine = _engine(params, shard)
  monkeypatch.setenv("XOT_TPU_FUSED_SAMPLING", "1")
  monkeypatch.setattr(type(engine.batch_ops), "fused_sampling_supported", lambda self: False)
  server = BatchedServer(engine, n_slots=2, chunk=2)
  assert server.fused_sampling is False
  outs, _ = _serve(server, [[3, 25, 9]], 3)
  assert len(outs[0]) == 3
  server.shutdown()


# ------------------------------------------------ the draw is taken only when a row asks for it (ISSUE 47)
# ``_next_token_batched`` ranks the vocabulary under a ``lax.cond`` on ``any(temps > 0)``. The same work done less
# often, not another result: tokens AND the key chain equal the unconditional form's, for every mix of rows.


def _unconditional_next_token(rows, key, temps, top_ks, k_max):
  """The form this repo had up to PR 46: the draw for every row at every step, selected away where temp <= 0."""
  from xotorch_support_jetson_tpu.ops.sampling import sample_logits_per_row

  greedy_rows = jnp.argmax(rows, axis=-1).astype(jnp.int32)
  key, sub = jax.random.split(key)
  safe_temp = jnp.where(temps > 0, temps, 1.0)
  sampled = sample_logits_per_row(rows, sub, safe_temp, top_ks, k_max=k_max)
  return jnp.where(temps > 0, sampled, greedy_rows), key


_TEMPS = {
  "all_greedy": [0.0, 0.0, -1.0, 0.0, 0.0, 0.0],
  "all_sampling": [0.7, 1.3, 0.2, 0.9, 2.0, 0.6],
  "one_sampling_row_among_greedy": [0.0, 0.0, 0.0, 0.8, 0.0, 0.0],
}
_TOP_KS = [1, 35, 3, 64, 200, 7]  # per row; 200 is clipped to k_max


@pytest.mark.parametrize("mix", list(_TEMPS))
def test_next_token_equals_the_unconditional_form_in_tokens_and_key(mix):
  temps, top_ks = jnp.asarray(_TEMPS[mix], jnp.float32), jnp.asarray(_TOP_KS, jnp.int32)
  new, old = jax.jit(decoder_mod._next_token_batched, static_argnums=4), jax.jit(_unconditional_next_token, static_argnums=4)
  key_new = key_old = jax.random.PRNGKey(20261002)
  for step in range(4):  # a chain of steps: a later step's draw sees the key the earlier steps left
    rows = jax.random.normal(jax.random.PRNGKey(step), (len(_TOP_KS), 997), jnp.float32) * 3.0
    tok_new, key_new = new(rows, key_new, temps, top_ks, 64)
    tok_old, key_old = old(rows, key_old, temps, top_ks, 64)
    np.testing.assert_array_equal(np.asarray(tok_new), np.asarray(tok_old))
    np.testing.assert_array_equal(np.asarray(key_new), np.asarray(key_old))
    assert tok_new.dtype == jnp.int32
  greedy = np.asarray(_TEMPS[mix]) <= 0
  np.testing.assert_array_equal(np.asarray(tok_new)[greedy], np.asarray(jnp.argmax(rows, axis=-1))[greedy])


def _eqns(jaxpr, inside_cond=False):
  """Every equation under ``jaxpr`` with whether a ``cond`` encloses it, through nested jits and scans."""
  for eqn in jaxpr.eqns:
    yield eqn, inside_cond
    for sub in jax.core.jaxprs_in_params(eqn.params):
      yield from _eqns(sub, inside_cond or eqn.primitive.name == "cond")


def test_greedy_branch_ranks_nothing_and_the_key_splits_outside_the_cond():
  B, V = 4, 512
  jaxpr = jax.make_jaxpr(lambda r, k, t, tk: decoder_mod._next_token_batched(r, k, t, tk, 64))(
    jnp.zeros((B, V), jnp.float32), jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32), jnp.ones((B,), jnp.int32)
  ).jaxpr
  everything = list(_eqns(jaxpr))
  conds = [eqn for eqn, _ in everything if eqn.primitive.name == "cond"]
  assert len(conds) == 1
  greedy_branch, draw_branch = (br.jaxpr for br in conds[0].params["branches"])  # index 0: the predicate is false

  def names(j):
    return [eqn.primitive.name for eqn, _ in _eqns(j)]

  wide = [eqn for eqn, _ in _eqns(greedy_branch) if any(getattr(v.aval, "shape", ()) == (B, V) for v in (*eqn.invars, *eqn.outvars))]
  assert not wide, f"the greedy branch touches the [B, V] operand: {wide}"
  assert not {"top_k", "random_bits", "div", "sort"} & set(names(greedy_branch))
  assert {"top_k", "random_bits", "div"} <= set(names(draw_branch))  # today's body, whole, in the other branch
  splits = [inside for eqn, inside in everything if eqn.primitive.name == "random_split"]
  assert splits == [False], "one split a step, outside the cond: the key advances whether or not the draw is taken"
  argmaxes = [inside for eqn, inside in everything if eqn.primitive.name == "argmax"]
  assert argmaxes[0] is False  # both outcomes need it


def _chunks():
  """(decode chunks, those of them that skipped the draw), over every path label."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  return tuple(
    sum(metrics.counter_value(name, labels={"path": path}) for path in ("dense", "gather", "kernel", "spec"))
    for name in ("decode_chunks_total", "decode_draw_skipped_chunks_total")
  )


def _serve_joining(server, greedy, sampler, join_after=3):
  """``greedy`` = (prompt, n) is resident first; ``sampler`` = (prompt, n, temp) is submitted once the greedy request
  has streamed ``join_after`` tokens and, being shorter, leaves before it ends. Either may be None (the other alone)."""
  streams: dict[str, list] = {"g": [], "s": []}

  async def run():
    pending = []

    def submit(rid, prompt, n, temp):
      pending.append(asyncio.ensure_future(server.submit(rid, np.asarray(prompt, np.int32), max_tokens=n, temp=temp, top_k=5, eos_ids=(), emit=emit)))

    def emit(rid, toks, finished):
      before = len(streams[rid])
      streams[rid].extend(toks)
      if rid == "g" and sampler is not None and before < join_after <= len(streams[rid]):
        submit("s", *sampler)

    if greedy is not None:
      submit("g", *greedy, 0.0)
    else:
      submit("s", *sampler)
    while pending:
      await pending.pop(0)

  asyncio.run(run())
  return streams["g"], streams["s"]


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("lookahead", [True, False])
def test_a_sampling_row_joins_and_leaves_a_greedy_batch(monkeypatch, paged, lookahead):
  """Served streams across the predicate's flips. The greedy request's stream is the one it has alone, whoever
  joins; the sampling request's stream is the one it has alone in the same row on the same key schedule (every
  dispatch handed one subkey, so that the schedule does not depend on how many dispatches came before it); and
  ``decode_draw_skipped_chunks_total`` follows ``decode_chunks_total`` exactly while no resident row samples, and
  stands still while one does."""
  monkeypatch.setenv("XOT_TPU_PAGED", "1" if paged else "0")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", "16")
  params, shard = full_model_params(KEY, CFG)
  greedy, sampler = ([3, 25, 9, 41], 24), ([7, 1, 88, 42, 5], 8, 0.9)

  def run(greedy, sampler, row=None):
    engine = _engine(params, shard)
    monkeypatch.setattr(engine, "split_key", lambda: jax.random.PRNGKey(47))
    server = BatchedServer(engine, n_slots=2, chunk=2, lookahead=lookahead)
    if row is not None:  # alone, in the row it had beside the greedy request
      monkeypatch.setattr(server, "_free_slot", lambda taken=frozenset(): None if row in taken or server.slots[row] is not None else row)
    before = _chunks()
    streams = _serve_joining(server, greedy, sampler)
    server.shutdown()
    return streams, tuple(int(b - a) for a, b in zip(before, _chunks()))

  (g_alone, _), (chunks, skipped) = run(greedy, None)
  assert len(g_alone) == 24 and chunks == skipped >= 11  # greedy-only traffic: every chunk skips the draw
  (_, s_alone), (chunks, skipped) = run(None, sampler, row=1)
  assert len(s_alone) == 8 and chunks >= 3 and skipped == 0  # a sampling row is resident throughout
  (s_greedy, _), _ = run((sampler[0], sampler[1]), None)
  assert s_alone != s_greedy  # it does draw: the comparison below is not one of argmaxes
  (g_joint, s_joint), (chunks, skipped) = run(greedy, sampler)
  assert g_joint == g_alone, "a greedy stream moved when a sampling row joined its batch"
  assert s_joint == s_alone, "a sampling row's stream depends on the greedy rows beside it"
  assert 0 < skipped < chunks  # before it joined and after it left, and not in between
