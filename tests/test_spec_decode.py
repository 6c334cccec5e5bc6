"""Fused speculative decoding exactness: for ANY draft, output must be
token-identical to plain greedy fused_generate (models/decoder.py
fused_speculative_generate — every emitted token is the target's own greedy
choice by construction)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_generate,
  fused_speculative_generate,
  init_kv_cache,
  shard_forward,
)


def _greedy_reference(cfg, params, shard, prompt, max_steps, eos_ids):
  B, S = prompt.shape
  cache = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  logits, cache = shard_forward(params, cfg, shard, jnp.asarray(prompt), positions, cache)
  first = jnp.argmax(logits[:, S - 1, :], axis=-1).astype(jnp.int32)[:, None]
  buf, n, _ = fused_generate(params, cfg, shard, first, cache, jnp.full((B,), S, jnp.int32), max_steps, eos_ids=eos_ids)
  row = np.asarray(buf)[0]
  out = [int(first[0, 0])]
  for tok in row[:max_steps]:
    out.append(int(tok))
    if int(tok) in eos_ids:
      break
  return out


def _spec_tokens(cfg_t, params_t, shard_t, cfg_d, params_d, shard_d, prompt, max_steps, eos_ids, gamma):
  B, S = prompt.shape
  cache_t = init_kv_cache(cfg_t, shard_t.n_shard_layers, B, cfg_t.max_seq_len)
  cache_d = init_kv_cache(cfg_d, shard_d.n_shard_layers, B, cfg_d.max_seq_len)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  logits, cache_t = shard_forward(params_t, cfg_t, shard_t, jnp.asarray(prompt), positions, cache_t)
  _, cache_d = shard_forward(params_d, cfg_d, shard_d, jnp.asarray(prompt), positions, cache_d)
  first = jnp.argmax(logits[:, S - 1, :], axis=-1).astype(jnp.int32)[:, None]
  buf, n, _rounds, _, _ = fused_speculative_generate(
    params_t, cfg_t, shard_t, params_d, cfg_d, shard_d, first, cache_t, cache_d,
    jnp.int32(S), max_steps, gamma=gamma, eos_ids=eos_ids,
  )
  row = np.asarray(buf)[: int(n)]
  out = [int(first[0, 0])]
  for tok in row:
    out.append(int(tok))
    if int(tok) in eos_ids:
      break
    if len(out) - 1 >= max_steps:
      break
  return out


@pytest.mark.parametrize("gamma", [1, 3, 4])
def test_spec_decode_same_draft_is_exact(gamma):
  """draft == target: full acceptance, identical output."""
  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  prompt = np.array([[5, 9, 2, 71]], dtype=np.int32)
  ref = _greedy_reference(cfg, params, shard, prompt, 24, eos_ids=(-1,))
  got = _spec_tokens(cfg, params, shard, cfg, params, shard, prompt, 24, (-1,), gamma)
  assert got[: len(ref)] == ref


@pytest.mark.parametrize("gamma", [2, 4])
def test_spec_decode_unrelated_draft_is_exact(gamma):
  """A completely different (random) draft must STILL yield the target's
  exact greedy output — the draft can only change speed, never tokens."""
  cfg_t = tiny_test_config(n_layers=4, max_seq_len=128)
  params_t, shard_t = full_model_params(jax.random.PRNGKey(7), cfg_t, "m")
  cfg_d = tiny_test_config(n_layers=2, dim=32, hidden_dim=64, n_heads=2, n_kv_heads=1, max_seq_len=128)
  params_d, shard_d = full_model_params(jax.random.PRNGKey(99), cfg_d, "d")
  prompt = np.array([[5, 9, 2, 71]], dtype=np.int32)
  ref = _greedy_reference(cfg_t, params_t, shard_t, prompt, 20, eos_ids=(-1,))
  got = _spec_tokens(cfg_t, params_t, shard_t, cfg_d, params_d, shard_d, prompt, 20, (-1,), gamma)
  assert got[: len(ref)] == ref


def test_peaked_echo_model_hits_high_acceptance_and_stays_exact():
  """The peaked-logit synthetic model (utils/synthetic.py): the int8
  self-draft reaches near-full acceptance — the speculative win is
  measurable OFFLINE — while the output stays token-identical to plain
  greedy.

  The acceptance assertion is a BUILD-VARIANCE CAPABILITY PROBE (ISSUE 7),
  not a loosened constant: the echo margin rides on int8-rounding noise and
  the backend's reduction order, so the test first MEASURES this build's
  draft/target argmax agreement along the greedy trajectory
  (spec_agreement_bitmap), replays the speculative accept rule on that
  bitmap (simulate_spec_acceptance), and pins the fused program to its own
  build's expectation — a program regression can no longer hide inside a
  hand-widened threshold, while genuine build variance passes by
  construction. The probe itself keeps a floor: if THIS build's agreement
  collapses, the ceiling construction has regressed."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_params
  from xotorch_support_jetson_tpu.utils.synthetic import peaked_echo_params, simulate_spec_acceptance, spec_agreement_bitmap

  cfg = tiny_test_config(n_layers=4, max_seq_len=128, tied_embedding=True)
  base, shard = full_model_params(jax.random.PRNGKey(7), cfg, "m")
  params = peaked_echo_params(base)
  qp = quantize_params(params)
  gamma, max_steps = 4, 24
  prompt = np.array([[5, 9, 2, 71]], dtype=np.int32)
  # Probe trajectory runs gamma past max_steps: the fused loop's final round
  # emits its full accepted run beyond the limit, and the replay needs those
  # agreement bits to predict n/rounds exactly.
  probe_traj = _greedy_reference(cfg, params, shard, prompt, max_steps + gamma + 1, eos_ids=(-1,))[1:]
  ref = _greedy_reference(cfg, params, shard, prompt, max_steps, eos_ids=(-1,))

  B, S = prompt.shape
  cache_t = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  cache_d = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  logits, cache_t = shard_forward(params, cfg, shard, jnp.asarray(prompt), positions, cache_t)
  _, cache_d = shard_forward(qp, cfg, shard, jnp.asarray(prompt), positions, cache_d)
  first = jnp.argmax(logits[:, S - 1, :], axis=-1).astype(jnp.int32)[:, None]
  buf, n, rounds, _, _ = fused_speculative_generate(
    params, cfg, shard, qp, cfg, shard, first, cache_t, cache_d, jnp.int32(S), max_steps, gamma=gamma, eos_ids=(-1,)
  )
  got = [int(first[0, 0])] + [int(t) for t in np.asarray(buf)[: int(n)]][:max_steps]
  assert got[: len(ref)] == ref
  acceptance = (int(n) / max(int(rounds), 1) - 1) / gamma

  # The trajectory the fused loop verifies against starts at `first`; the
  # bitmap covers the draft's agreement on every step after it.
  bits = spec_agreement_bitmap(params, cfg, shard, qp, cfg, shard, prompt, probe_traj)
  predicted = simulate_spec_acceptance(bits, gamma, max_steps)
  # Exact replay up to window-forward vs step-forward argmax near-ties
  # (the one numerics caveat fused_speculative_generate documents): allow a
  # one-flip margin, nothing more.
  assert abs(acceptance - predicted) <= 1.5 / max_steps, (
    f"measured acceptance {acceptance:.3f} diverged from this build's probed expectation {predicted:.3f}"
  )
  # Construction floor: the ECHO ceiling itself must still be a ceiling on
  # this build (worst measured build variance to date: 0.83).
  assert predicted >= 0.5, f"echo construction regressed: probed agreement predicts only {predicted:.3f}"


@pytest.mark.asyncio
async def test_engine_spec_decode_matches_plain_oneshot():
  """XOT_TPU_SPEC_DECODE=int8 engine path (prefill + generate_oneshot) must
  produce the exact plain-greedy token stream."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)

  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(shard, cfg, params)
  logits, _ = await plain.infer_tensor("a", shard, prompt)
  first = int(np.argmax(logits, -1)[0])
  ref = await plain.generate_oneshot("a", shard, first, 20, eos_ids=(-1,), temp=0.0)

  spec = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  assert spec._draft_params is not None
  logits2, _ = await spec.infer_tensor("a", shard, prompt)
  assert int(np.argmax(logits2, -1)[0]) == first
  got = await spec.generate_oneshot("a", shard, first, 20, eos_ids=(-1,), temp=0.0)
  assert got == ref


def test_spec_decode_eos_trim_matches_reference():
  """EOS produced mid-round ends generation at the same token as plain
  greedy (use an eos id that actually occurs in the reference output)."""
  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, "m")
  prompt = np.array([[17, 4, 99]], dtype=np.int32)
  probe = _greedy_reference(cfg, params, shard, prompt, 16, eos_ids=(-1,))
  eos = probe[len(probe) // 2]  # a token we know greedy decoding emits
  ref = _greedy_reference(cfg, params, shard, prompt, 16, eos_ids=(eos,))
  cfg_d = tiny_test_config(n_layers=2, dim=32, hidden_dim=64, n_heads=2, n_kv_heads=1, max_seq_len=128)
  params_d, shard_d = full_model_params(jax.random.PRNGKey(42), cfg_d, "d")
  got = _spec_tokens(cfg, params, shard, cfg_d, params_d, shard_d, prompt, 16, (eos,), 3)
  assert got == ref


def test_spec_chunk_chain_is_exact():
  """Streaming speculative chunks (models/decoder.py fused_speculative_chunk)
  chained through the DEVICE-side seed/pos must reproduce plain greedy
  token-for-token across chunk boundaries, for any draft."""
  from xotorch_support_jetson_tpu.models.decoder import fused_speculative_chunk

  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  params_d = jax.tree.map(lambda x: x, full_model_params(jax.random.PRNGKey(77), cfg, "m")[0])  # unrelated draft
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)
  ref = _greedy_reference(cfg, params, shard, prompt, 24, eos_ids=(-1,))

  B, S = prompt.shape
  cache_t = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  cache_d = init_kv_cache(cfg, shard.n_shard_layers, B, cfg.max_seq_len)
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  logits, cache_t = shard_forward(params, cfg, shard, jnp.asarray(prompt), positions, cache_t)
  _, cache_d = shard_forward(params_d, cfg, shard, jnp.asarray(prompt), positions, cache_d)
  token = jnp.argmax(logits[:, S - 1, :], axis=-1).astype(jnp.int32)[:, None]
  got = [int(token[0, 0])]
  pos = jnp.int32(S)
  for _ in range(4):  # 4 chunks of 6 = ref's 24 steps
    packed, token, pos, cache_t, cache_d = fused_speculative_chunk(
      params, cfg, shard, params_d, token, cache_t, cache_d, pos, steps=8, gamma=3, n_limit=6
    )
    row = np.asarray(packed)
    m, rounds = int(row[0]), int(row[1])
    assert 1 <= m <= 6
    assert 1 <= rounds <= m  # each round emits at least one token
    got.extend(int(t) for t in row[2 : 2 + m])
  assert got == ref[: len(got)]
  assert len(got) >= 1 + 4 * 1


@pytest.mark.asyncio
async def test_engine_streaming_spec_chunks_match_plain():
  """The engine's pipelined chunk path under XOT_TPU_SPEC_DECODE=int8:
  dispatch N+1 before reading N (exactly like the node's loop), tokens must
  equal the plain engine's chunked stream."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)

  async def drive_to_exhaustion(engine, rid, chunk):
    """The node's pipelined loop (dispatch N+1 before reading N) until the
    engine refuses for lack of cache room."""
    logits, _ = await engine.infer_tensor(rid, shard, prompt)
    first = int(np.argmax(logits, -1)[0])
    out = [first]
    pending = await engine.dispatch_chunk(rid, shard, chunk, 0.0, 35, first_token=first)
    while pending is not None:
      nxt = await engine.dispatch_chunk(rid, shard, chunk, 0.0, 35)
      out.extend(await engine.read_chunk(pending))
      pending = nxt
    return out

  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(shard, cfg, params)
  ref = await drive_to_exhaustion(plain, "a", 8)

  spec = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  # First dispatch must actually take the spec path.
  logits2, _ = await spec.infer_tensor("probe", shard, prompt)
  h = spec._dispatch_chunk_sync("probe", shard, 8, 0.0, 35, int(np.argmax(logits2, -1)[0]))
  assert isinstance(h, tuple) and h[0] == "spec"

  # FULL stream to cache exhaustion, including the near-cache-end handoff to
  # the plain path with an unread (possibly truncated) spec chunk in flight:
  # the whole stream must be token-identical to the plain engine's, and both
  # must fill the cache to the same final position.
  got = await drive_to_exhaustion(spec, "b", 8)
  assert got == ref
  assert spec.sessions["b"].curr_pos == plain.sessions["a"].curr_pos <= cfg.max_seq_len

  # Mixed chunk sizes (the node shrinks n_steps near the token budget):
  # larger unread buckets must be accounted at THEIR size, not the current one.
  spec2 = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec2.load_test_model(shard, cfg, params)
  logits3, _ = await spec2.infer_tensor("c", shard, prompt)
  first3 = int(np.argmax(logits3, -1)[0])
  got2 = [first3]
  sizes = [16, 16, 4, 4, 2, 8, 16, 2]
  pending = await spec2.dispatch_chunk("c", shard, sizes[0], 0.0, 35, first_token=first3)
  i = 1
  while pending is not None:
    nxt = await spec2.dispatch_chunk("c", shard, sizes[i % len(sizes)], 0.0, 35)
    i += 1
    got2.extend(await spec2.read_chunk(pending))
    pending = nxt
  assert got2 == ref[: len(got2)]
  assert spec2.sessions["c"].curr_pos <= cfg.max_seq_len


@pytest.mark.asyncio
async def test_engine_cross_model_draft_matches_plain(tmp_path, monkeypatch):
  """XOT_TPU_SPEC_DRAFT=<dir> (VERDICT r4 #3): a SMALLER on-disk checkpoint
  drafts for the injected target — output must be the target's exact plain
  greedy stream (the draft only changes speed), and the engine must record
  the draft's own cfg/shard (its cache has the draft's geometry)."""
  from tests.test_hf_golden import _save_tiny_hf

  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  _save_tiny_hf(tmp_path, "llama")  # 2-layer dim-64 vocab-128 draft on disk
  cfg = tiny_test_config(n_layers=4, vocab_size=128, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)

  plain = JaxShardedInferenceEngine(use_local_mesh=False)
  plain.load_test_model(shard, cfg, params)
  logits, _ = await plain.infer_tensor("a", shard, prompt)
  first = int(np.argmax(logits, -1)[0])
  ref = await plain.generate_oneshot("a", shard, first, 20, eos_ids=(-1,), temp=0.0)

  monkeypatch.setenv("XOT_TPU_SPEC_DRAFT", str(tmp_path))
  spec = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  assert spec._draft_params is not None, "cross-model draft failed to load"
  assert spec._draft_cfg is not None and spec._draft_cfg.n_layers != cfg.n_layers
  logits2, _ = await spec.infer_tensor("a", shard, prompt)
  assert int(np.argmax(logits2, -1)[0]) == first
  got = await spec.generate_oneshot("a", shard, first, 20, eos_ids=(-1,), temp=0.0)
  assert got == ref


def test_engine_cross_model_draft_refuses_vocab_mismatch(tmp_path, monkeypatch):
  """A draft whose vocab differs from the target's proposes ids the target
  cannot verify — the engine must refuse it at load, not mistranslate."""
  from tests.test_hf_golden import _save_tiny_hf

  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  _save_tiny_hf(tmp_path, "llama")  # vocab 128
  cfg = tiny_test_config(n_layers=4, vocab_size=256, max_seq_len=128)  # vocab 256 target
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")

  monkeypatch.setenv("XOT_TPU_SPEC_DRAFT", str(tmp_path))
  spec = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  assert spec._draft_params is None, "vocab-mismatched draft must be refused"


@pytest.mark.asyncio
async def test_solo_adaptive_gamma_collapses_to_plain_on_bad_draft():
  """ISSUE 7 satellite: an adversarial (near-zero-acceptance) draft must
  drive the solo path's acceptance EWMA down until gamma hits 0 — from then
  on dispatches take the PLAIN chunk program (XOT_TPU_SPEC_DECODE can never
  keep decoding slower than plain decode), and the stream stays exactly the
  plain greedy stream throughout the transition."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  cfg = tiny_test_config(n_layers=4, max_seq_len=512)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)

  plain = JaxShardedInferenceEngine(use_local_mesh=False, max_seq_len=512)
  plain.load_test_model(shard, cfg, params)
  logits, _ = await plain.infer_tensor("a", shard, prompt)
  first = int(np.argmax(logits, -1)[0])
  ref = [first]
  pending = await plain.dispatch_chunk("a", shard, 8, 0.0, 35, first_token=first)
  for _ in range(20):
    nxt = await plain.dispatch_chunk("a", shard, 8, 0.0, 35)
    ref.extend(await plain.read_chunk(pending))
    pending = nxt
    if pending is None:
      break

  spec = JaxShardedInferenceEngine(use_local_mesh=False, max_seq_len=512, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  # Adversarial draft: unrelated random weights — argmax agreement ~1/vocab.
  spec._draft_params = full_model_params(jax.random.PRNGKey(777), cfg, "m")[0]
  assert spec._spec_gamma_live == spec.spec_gamma
  logits2, _ = await spec.infer_tensor("b", shard, prompt)
  assert int(np.argmax(logits2, -1)[0]) == first
  got = [first]
  kinds = []
  pending = await spec.dispatch_chunk("b", shard, 8, 0.0, 35, first_token=first)
  for _ in range(20):
    kinds.append("spec" if isinstance(pending, tuple) else "plain")
    nxt = await spec.dispatch_chunk("b", shard, 8, 0.0, 35)
    got.extend(await spec.read_chunk(pending))
    pending = nxt
    if pending is None:
      break
  assert got == ref[: len(got)]
  assert spec._spec_gamma_live == 0, f"gamma never collapsed (ewma {spec._spec_ewma})"
  # The transition really happened: spec chunks first, plain chunks after.
  assert kinds[0] == "spec" and kinds[-1] == "plain", kinds
  assert kinds.index("plain") == len(kinds) - kinds[::-1].count("plain"), f"plain/spec interleaved after collapse: {kinds}"


@pytest.mark.asyncio
async def test_solo_adaptive_gamma_reprobes_after_plain_streak(monkeypatch):
  """Once collapsed to plain, the engine re-probes at gamma 1 after
  XOT_TPU_SPEC_REPROBE plain dispatches — a draft that starts paying again
  (here: the real self-draft swapped back in) re-earns its depth."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  monkeypatch.setenv("XOT_TPU_SPEC_REPROBE", "3")
  cfg = tiny_test_config(n_layers=4, max_seq_len=512)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  prompt = np.array([[5, 9, 2, 71, 33]], dtype=np.int32)

  eng = JaxShardedInferenceEngine(use_local_mesh=False, max_seq_len=512, spec_decode="int8")
  eng.load_test_model(shard, cfg, params)
  eng._spec_gamma_live = 0  # collapsed earlier (simulated)
  # Spec entry happens fresh-after-prefill (the draft cache is prompt-deep),
  # so the plain streak accrues per REQUEST; after three plain requests the
  # fourth probes at gamma 1 and the healthy self-draft re-earns its depth.
  kinds = []
  for i in range(5):
    rid = f"r{i}"
    logits, _ = await eng.infer_tensor(rid, shard, prompt)
    first = int(np.argmax(logits, -1)[0])
    h = await eng.dispatch_chunk(rid, shard, 4, 0.0, 35, first_token=first)
    kinds.append("spec" if isinstance(h, tuple) else "plain")
    await eng.read_chunk(h)
    eng.end_request(rid)
  assert kinds[:3] == ["plain", "plain", "plain"], kinds
  assert "spec" in kinds[3:], kinds
  assert eng._spec_gamma_live >= 1


def test_engine_cross_model_draft_missing_dir_disables(monkeypatch):
  """A draft spec that resolves to no local checkpoint disables speculation
  with a log line — never a crash, never a surprise network download."""
  from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine

  cfg = tiny_test_config(n_layers=4, max_seq_len=128)
  params, shard = full_model_params(jax.random.PRNGKey(11), cfg, "m")
  monkeypatch.setenv("XOT_TPU_SPEC_DRAFT", "no-such-model-anywhere")
  spec = JaxShardedInferenceEngine(use_local_mesh=False, spec_decode="int8")
  spec.load_test_model(shard, cfg, params)
  assert spec._draft_params is None
