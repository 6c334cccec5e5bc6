"""A hybrid of state-space and attention layers on the served path (ISSUE 34):
granite-4.0-h-micro's architecture at the benchmark's rehearsal widths — runs
of 2, 1 and 1 Mamba-2 layers with a position-free GQA layer after each of the
first two — against the benchmark's plain reference
(``benchmark/arch_hybrid_ssm.py reference_forward``: float32, the recurrence
token by token, nothing of the program).

Everything runs in float32 at ``highest`` matmul precision, so the program and
the reference differ by the order of their sums alone: the chunked scan adds a
chunk's contributions as one matrix product where the reference adds them a
token at a time. Logits here have a spread of ~0.2; tolerances are absolute.
"""

import asyncio
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))

import arch_hybrid_ssm as kind  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool  # noqa: E402

HF = {
  "model_type": "granitemoehybrid", "attention_multiplier": 0.25, "embedding_multiplier": 12, "logits_scaling": 8, "residual_multiplier": 0.22,
  "position_embedding_type": "nope", "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
  "num_local_experts": 0, "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": True, "max_position_embeddings": 256, "torch_dtype": "float32",
  **kind.REHEARSE_WIDTHS,
}
CFG = config_from_hf(HF)
PARAMS, SHARD = dec.full_model_params(jax.random.PRNGKey(0), CFG)
PARAMS["ssm_layers"]["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), PARAMS["ssm_layers"]["conv_b"].shape)  # a bias that is not zero
# The same architecture with a state of 128 lanes (16 heads x 8 x 128): the widths the one-pass form of the state's
# decode step tiles (ops/ssm.py one_pass_supported), which the rehearsal's state of 16 is not.
HF_LANES = {**HF, "mamba_n_heads": 16, "mamba_d_head": 8, "mamba_d_state": 128}
CFG_LANES = config_from_hf(HF_LANES)
PARAMS_LANES = dec.full_model_params(jax.random.PRNGKey(0), CFG_LANES)[0]
PS, SLOTS, MP = 16, 4, 8
RNG = np.random.default_rng(0)
TOKENS = RNG.integers(3, CFG.vocab_size, size=96)
# The program against the reference, both float32 at "highest": orders of summation only (measured 4e-7 to 8e-7).
TOL = 5e-6


@pytest.fixture(autouse=True)
def highest_precision():
  with jax.default_matmul_precision("highest"):
    yield


@pytest.fixture(params=["reference", "one_pass"])
def state_step(request, monkeypatch):
  """The form a decode program steps the recurrent state in. ``one_pass``: the module's model is the 128-lane one and
  ``ssm_state_step`` is told what a TPU's program would be — the Mosaic kernel, here in interpret mode — while the
  attention layers stay on the gather path (their kernel has tests of its own). A new configuration is a new static
  argument, so no program traced for the other form is met again."""
  if request.param == "reference":
    yield request.param
    return
  real, traced = ssm_ops.ssm_state_step, []
  for name, value in (("HF", HF_LANES), ("CFG", CFG_LANES), ("PARAMS", PARAMS_LANES)):
    monkeypatch.setattr(sys.modules[__name__], name, value)
  monkeypatch.setattr(ssm_ops, "ssm_state_step", lambda *args, **_: traced.append(1) or real(*args[:7], use_kernel=True, interpret=True))
  assert ssm_ops.one_pass_supported(fresh_pool()["ssm"], True)
  yield request.param
  assert traced, "the decode program of this case was not traced with the one-pass form"


def reference(tokens) -> np.ndarray:
  return np.asarray(kind.reference_forward(PARAMS, HF, jnp.asarray(tokens)))


def fresh_pool():
  return init_paged_pool(CFG, CFG.n_layers, 1 + SLOTS * MP, PS, n_slots=SLOTS)


def tables() -> np.ndarray:
  return np.arange(1, 1 + SLOTS * MP, dtype=np.int32).reshape(SLOTS, MP)


def prefill(pool, prompts: dict, prefix: dict | None = None, pad_to: int | None = None, pad_rows: int = 0):
  """Prefill ``{slot: tokens}`` as one group, its rows in the dict's order (each row from ``prefix[slot]`` on) → (last logits [K, V], pool)."""
  rows = list(prompts)
  prefix = prefix or {}
  K = len(rows) + pad_rows
  S = pad_to or max(len(prompts[r]) - prefix.get(r, 0) for r in rows)
  tok, bts = np.zeros((K, S), np.int32), np.zeros((K, MP), np.int32)
  prefix_lens, prompt_lens, slot_rows = np.zeros((K,), np.int32), np.ones((K,), np.int32), np.full((K,), SLOTS, np.int32)
  for i, r in enumerate(rows):
    start = prefix.get(r, 0)
    tok[i, : len(prompts[r]) - start] = prompts[r][start:]
    bts[i], prefix_lens[i], prompt_lens[i], slot_rows[i] = tables()[r], start, len(prompts[r]), r
  return dec.prefill_into_pages_many(PARAMS, CFG, SHARD, jnp.asarray(tok), pool, jnp.asarray(bts), jnp.asarray(prefix_lens), jnp.asarray(prompt_lens), PS, None, jnp.asarray(slot_rows))


@partial(jax.jit, static_argnums=0)
def _decode_forward(cfg, params, tok, pos, pool, active):
  return dec.paged_decode_forward(params, cfg, SHARD, tok, pos[:, None], pool, jnp.asarray(tables()), PS, False, active=active)[:2]  # (the third result counts expert visits)


def decode_step(pool, tokens: dict, positions: dict):
  """One teacher-forced decode step of the rows named → (logits [SLOTS, V], pool)."""
  tok, pos, active = np.zeros((SLOTS, 1), np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
  for r, t in tokens.items():
    tok[r, 0], pos[r], active[r] = t, positions[r], True
  logits, pool = _decode_forward(CFG, PARAMS, jnp.asarray(tok), jnp.asarray(pos), pool, jnp.asarray(active))
  return np.asarray(logits[:, 0]), pool


def state_of(pool, slot: int):
  return np.asarray(pool["ssm"][:, slot]), np.asarray(pool["conv"][:, slot])


def test_config_from_hf_reads_the_hybrid_fields():
  assert CFG.family == "granite-hybrid" and CFG.layer_types == tuple(HF["layer_types"]) and CFG.recurrent_layers == 4 and CFG.n_attn_layers == 2
  assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state, CFG.ssm_conv, CFG.ssm_chunk) == (8, 16, 16, 4, 32)
  assert (CFG.embed_scale, CFG.residual_multiplier, CFG.logits_scaling, CFG.attn_multiplier, CFG.use_rope, CFG.tied_embedding) == (12.0, 0.22, 8.0, 0.25, False, True)
  assert CFG.plain_attention  # the attention multiplier is folded into q: the Pallas kernels keep their one scale
  assert {k: v.shape[0] for k, v in (("layers", PARAMS["layers"]["wq"]), ("ssm_layers", PARAMS["ssm_layers"]["w_xbc"]))} == {"layers": 2, "ssm_layers": 4}


@pytest.mark.parametrize("what", ["model_type", "experts", "groups", "layer_types"])
def test_config_from_hf_refuses_what_it_does_not_know(what):
  """An unknown ``model_type`` is no longer served as a llama without a word (ROADMAP M9); an absent one stays llama.
  Of a granitemoehybrid config, what the decoder does not implement is refused by name."""
  bad = {
    "model_type": {"model_type": "rwkv7", "vocab_size": 8, "hidden_size": 8, "num_hidden_layers": 1, "num_attention_heads": 1, "intermediate_size": 8},
    "experts": {**HF, "num_local_experts": 4},
    "groups": {**HF, "mamba_n_groups": 2},
    "layer_types": {**HF, "layer_types": ["mamba"] * 5},
  }[what]
  with pytest.raises(ValueError, match={"model_type": "unknown model_type 'rwkv7'", "experts": "num_local_experts", "groups": "mamba_n_groups", "layer_types": "layer_types"}[what]):
    config_from_hf(bad)
  bare = {k: v for k, v in bad.items() if k != "model_type"} if what == "model_type" else None
  assert bare is None or config_from_hf(bare).family == "llama"


@pytest.mark.parametrize("length", [32, 64, 96, 45, 7, 1])
def test_a_chunked_mixer_equals_the_token_by_token_reference(length):
  """(a) One state-space layer, chunks of 32: lengths that are and are not multiples of the chunk, and shorter than
  the convolution. The whole layer (mixer and its MLP) against the reference's ``_mamba`` + ``_mlp``."""
  st = {k: v[1] for k, v in PARAMS["ssm_layers"].items()}
  h = jax.random.normal(jax.random.PRNGKey(length), (length, CFG.dim), jnp.float32)
  zeros = jnp.zeros((1, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state)), jnp.zeros((1, CFG.ssm_conv - 1, CFG.ssm_conv_dim))
  got, ssm, conv = dec._ssm_layer(h[None], st, CFG, *zeros)
  want = kind._mamba(
    h, st["ssm_norm"], st["w_z"], st["w_xbc"], st["w_dt"], st["conv_w"], st["conv_b"], st["dt_bias"], st["A_log"], st["D"], st["gate_norm"], st["w_out"],
    H=CFG.ssm_heads, P=CFG.ssm_head_dim, N=CFG.ssm_state, eps=CFG.norm_eps, r=CFG.residual_multiplier,
  )
  want = kind._mlp(want, st["mlp_norm"], st["w_gate"], st["w_up"], st["w_down"], eps=CFG.norm_eps, r=CFG.residual_multiplier)
  np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=TOL, rtol=0)
  assert ssm.shape == (1, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state) and conv.shape == (1, CFG.ssm_conv - 1, CFG.ssm_conv_dim)


def test_the_cacheless_forward_equals_the_reference():
  got, _ = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray(TOKENS)[None], jnp.arange(len(TOKENS))[None])
  np.testing.assert_allclose(np.asarray(got[0]), reference(TOKENS), atol=TOL, rtol=0)


def test_prefill_then_decode_through_pool_and_state_equals_the_reference(state_step):
  """(b) 50 prompt tokens prefilled into slot 2 (padded to 64, beside three padding rows), then 30 decode steps, one
  token each, through pages and state: every step's logits are the reference's full forward at that position — in
  either form of the state's decode step."""
  want = reference(TOKENS[:80])
  last, pool = prefill(fresh_pool(), {2: TOKENS[:50]}, pad_to=64, pad_rows=3)
  np.testing.assert_allclose(np.asarray(last[0]), want[49], atol=TOL, rtol=0)
  for t in range(50, 80):
    logits, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
    np.testing.assert_allclose(logits[2], want[t], atol=TOL, rtol=0, err_msg=f"decode step at position {t}")
  for other in (0, 1, 3):  # nothing was written for the padding row, nor for a slot no request held
    assert not state_of(pool, other)[0].any() and not state_of(pool, other)[1].any()


def test_a_padded_group_leaves_each_row_the_state_of_its_unpadded_run():
  """(c) Rows of 50, 33 and 2 tokens as one group padded to 64: padding has a step of 0 and is cut from the
  convolution's tail, so each slot's state is what the row's own prefill leaves alone — unpadded for the row of 33
  (its own program), the other two alone at the same padded length (one program for both: a compile less)."""
  prompts = {0: TOKENS[:50], 1: TOKENS[10:43], 3: TOKENS[60:62]}
  _, grouped = prefill(fresh_pool(), prompts, pad_to=64, pad_rows=1)
  for slot, toks in prompts.items():
    _, solo = prefill(fresh_pool(), {slot: toks}, pad_to=None if slot == 1 else 64)
    for got, want in zip(state_of(grouped, slot), state_of(solo, slot)):
      np.testing.assert_allclose(got, want, atol=TOL, rtol=0, err_msg=f"slot {slot}")


def test_a_prompt_prefilled_in_two_chunks_equals_one():
  """(d) Positions [0, 48) then [48, 83): the second call continues from the slot's own state and pages."""
  toks = TOKENS[:83]
  whole_logits, whole = prefill(fresh_pool(), {1: toks}, pad_to=96)
  _, pool = prefill(fresh_pool(), {1: toks[:48]}, pad_to=64)
  cut_logits, cut = prefill(pool, {1: toks}, prefix={1: 48}, pad_to=64)
  np.testing.assert_allclose(np.asarray(cut_logits), np.asarray(whole_logits), atol=TOL, rtol=0)
  for got, want in zip(state_of(cut, 1), state_of(whole, 1)):
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
  np.testing.assert_allclose(np.asarray(cut_logits[0]), reference(toks)[-1], atol=TOL, rtol=0)


def test_a_second_chunk_in_a_group_of_unsorted_slots_beside_a_fresh_and_a_padding_row_equals_one_chunk():
  """The one path that USES the state a prefill group reads (``fresh`` false; ``models/decoder.py _state_rows``, ISSUE
  48), and no cell of the benchmark sends it: two prompts prefilled to positions 48 and 32 as a group of slots 3, 0 and
  a padding row, then continued in ONE group whose rows name slots 3, 2, 0 — neither sorted nor adjacent; slot 2's row
  starts at position 0 — and a padding row, which names the slot past the last (its read is clamped onto slot 3's, its
  write dropped). Every row ends in the logits and the state of its one-chunk prefill and in the token-by-token
  reference's logits; slot 1, which no row names, stays zero."""
  a, b, c = TOKENS[:83], TOKENS[10:80], TOKENS[60:90]
  _, pool = prefill(fresh_pool(), {3: a[:48], 0: b[:32]}, pad_to=64, pad_rows=1)
  logits, pool = prefill(pool, {3: a, 2: c, 0: b}, prefix={3: 48, 0: 32}, pad_to=64, pad_rows=1)
  for i, (slot, toks) in enumerate({3: a, 2: c, 0: b}.items()):
    whole_logits, whole = prefill(fresh_pool(), {slot: toks}, pad_to=96)
    np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(whole_logits[0]), atol=TOL, rtol=0, err_msg=f"slot {slot}")
    np.testing.assert_allclose(np.asarray(logits[i]), reference(toks)[-1], atol=TOL, rtol=0, err_msg=f"slot {slot}")
    for got, want in zip(state_of(pool, slot), state_of(whole, slot)):
      np.testing.assert_allclose(got, want, atol=TOL, rtol=0, err_msg=f"slot {slot}")
  assert not any(leaf.any() for leaf in state_of(pool, 1))


def test_a_reused_slot_gives_its_second_tenant_the_solo_answer():
  """(e) Slot 2 serves one request (prefill + decode steps), then another from position 0: the second sees zeros, not
  its predecessor's state, and its logits and state are those of a pool it has to itself."""
  _, pool = prefill(fresh_pool(), {2: TOKENS[:40]}, pad_to=64)
  for t in range(40, 46):
    _, pool = decode_step(pool, {2: TOKENS[t]}, {2: t})
  second = TOKENS[50:77]
  reused_logits, reused = prefill(pool, {2: second}, pad_to=64)
  solo_logits, solo = prefill(fresh_pool(), {2: second}, pad_to=64)
  np.testing.assert_array_equal(np.asarray(reused_logits), np.asarray(solo_logits))
  for got, want in zip(state_of(reused, 2), state_of(solo, 2)):
    np.testing.assert_array_equal(got, want)


def test_a_decode_chunk_leaves_an_inactive_rows_state_bit_for_bit(state_step):
  """(e) A chunk of 4 steps of ``decode.paged_batch`` with rows 0 and 3 active: rows 1 and 2, resident but not
  stepped (a row mid-prefill, a starved row), keep both state leaves exactly — in either form of the state's step."""
  _, pool = prefill(fresh_pool(), {0: TOKENS[:20], 1: TOKENS[20:50], 2: TOKENS[50:58], 3: TOKENS[30:70]}, pad_to=64)
  before = {slot: state_of(pool, slot) for slot in range(SLOTS)}
  active = np.asarray([True, False, False, True])
  pos = np.asarray([20, 30, 8, 40], np.int32)
  _, _, new_pos, pool = dec.fused_paged_batch_decode(
    PARAMS, CFG, SHARD, jnp.ones((SLOTS, 1), jnp.int32), pool, tables(), jnp.asarray(pos), jnp.asarray(active), np.zeros((SLOTS,), np.float32), 4, page_size=PS, use_kernel=False,
  )
  assert np.asarray(new_pos).tolist() == [24, 30, 8, 44]
  for slot in (1, 2):
    for got, want in zip(state_of(pool, slot), before[slot]):
      np.testing.assert_array_equal(got, want)
  for slot in (0, 3):
    assert not np.array_equal(state_of(pool, slot)[0], before[slot][0])


# ------------------------------------------------------------ the scheduler


def _engine():
  engine = JaxShardedInferenceEngine(use_local_mesh=False)
  engine.load_test_model(SHARD, CFG, PARAMS)
  return engine


def _serve(server, prompts, n_gen):
  async def run():
    return await asyncio.gather(*(
      server.submit(f"r{i}-{len(p)}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i, p in enumerate(prompts)
    ))

  return asyncio.run(run())


def _greedy_under_the_reference(prompt, answer) -> bool:
  """Whether ``answer`` is the reference's greedy continuation of ``prompt``: each of its tokens is the reference's best
  after everything before it (one forward over prompt + answer: by induction the same as generating token by token)."""
  logits = reference(np.asarray(list(prompt) + list(answer)))
  return [int(np.argmax(logits[len(prompt) - 1 + i])) for i in range(len(answer))] == list(answer)


def test_the_scheduler_turns_off_what_pages_alone_cannot_carry(monkeypatch, capsys):
  """(f) For a configuration with recurrent layers prefix reuse, the host tier, speculation and mixed ticks are off,
  each at its one gate, with one log line; the same long prompt sent twice (three pages of 16 each time: the second
  would reuse the first's pages in any other model) answers twice alike, and as the reference does; a slot's state is
  reset once an admission and the gauge holds the state's bytes."""
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "2")
  monkeypatch.setenv("XOT_TPU_PAGE_SIZE", str(PS))
  server = BatchedServer(_engine())
  long_prompt, other = [int(t) for t in TOKENS[:52]], [int(t) for t in TOKENS[60:75]]
  resets = lambda: metrics.counter_value("recurrent_state_resets_total")  # noqa: E731
  before = resets()
  try:
    first = _serve(server, [long_prompt, other], 6)
    hits = metrics.counter_value("prefix_cache_hit_pages_total")
    again = _serve(server, [long_prompt], 6)
    assert metrics.counter_value("prefix_cache_hit_pages_total") == hits and not server.allocator.cached_keys()
  finally:
    server.shutdown()
  assert again[0] == first[0] and len(first[0]) == len(first[1]) == 6
  assert _greedy_under_the_reference(long_prompt, first[0]) and _greedy_under_the_reference(other, first[1])
  assert server.tier is None and not server.spec and not server._mixed_active() and not server.ops.mixed_tick_supported()
  assert resets() - before == 3
  state_bytes = SLOTS // 2 * CFG.recurrent_layers * (CFG.ssm_heads * CFG.ssm_head_dim * CFG.ssm_state * 4 + (CFG.ssm_conv - 1) * CFG.ssm_conv_dim * 4)
  assert metrics.gauge_value("recurrent_state_bytes") == state_bytes
  out = capsys.readouterr().out
  assert out.count("keep a recurrent state per slot") == 1 and "prefix reuse, the host KV tier, speculation and mixed ticks are off" in out


def test_a_prefill_group_holds_at_most_eight_rows_on_a_server_of_many_slots(monkeypatch):
  """What 64 callers meet that 16 never did: on a server of more than 16 slots a ready list of more than 8 rows is
  split into groups of at most 8, longest first, so that its prefill programs are those of 1, 2, 4 and 8 rows (what a
  warm-up sends) and a lump of callers compiles nothing in service; a server of up to 16 slots groups as before."""
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "12")
  server = BatchedServer(_engine())
  server.max_seq, server.pages_per_row = 256, 4
  ready = [bs._Ready(req=None, row=i, pad_to=pad) for i, pad in enumerate([32, 128, 64, 64, 128, 32, 64, 32, 32, 64, 32])]
  assert [len(g) for g in server._dispatch_groups(ready)] == [11]  # 12 slots: whole
  monkeypatch.setattr(bs, "GROUP_SLOTS_WHOLE", 4)  # (a server of more slots than that, without building 17 of them)
  groups = server._dispatch_groups(ready)
  assert [len(g) for g in groups] == [8, 3] and [g[0].pad_to for g in groups] == [128, 32]
  assert sorted(r.row for g in groups for r in g) == list(range(11))
  assert [len(g) for g in server._dispatch_groups(ready[:8])] == [8]


@pytest.mark.parametrize(
  "max_seq,chunk,rows,sizes",
  [
    (4096, 2048, [(0, 2048)] * 8, [8]),  # eight first chunks: the widest group there is, a window of 32 pages
    (4096, 2048, [(2048, 1024)] * 8, [4, 4]),  # eight second chunks end past 2048 tokens: a window of 64 pages, four rows
    (4096, 2048, [(0, 1024)] * 5 + [(2048, 1024)] * 3, [5, 3]),  # a later chunk would widen the window of all five
    (4096, 2048, [(0, 1024)] * 3 + [(2048, 1024)] * 3, [4, 2]),  # ... and joins three, whose window it doubles
    (8192, 2048, [(4096, 256)] * 5, [2, 2, 1]),  # a final bucket after 4096 tokens: a window of 128 pages, two rows
    (4096, 0, [(2048, 1024)] * 8, [8]),  # no chunking: a first group is as wide as a row, nothing to hold a later one to
  ],
  ids=["first_chunks", "second_chunks", "second_beside_five_first", "second_beside_three_first", "window_of_128_pages", "unchunked"],
)
def test_a_prefill_group_of_a_wider_page_window_holds_fewer_rows(monkeypatch, max_seq, chunk, rows, sizes):
  """A group's program gathers each row's page window of every attention layer, so on a server of more than 16 slots
  no group's windows together are wider than those of eight first chunks (8 x ``XOT_TPU_PREFILL_CHUNK``): a group
  that ends further on — later chunks of long prompts — holds 4, 2 or 1 rows (``_group_rows``; ISSUE 48: eight rows x
  4096 tokens is the group XLA:TPU refuses beside Olmo-Hybrid's pool). Counted on the padded rows, a power of two."""
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "12")
  monkeypatch.setenv("XOT_TPU_PREFILL_CHUNK", str(chunk))
  monkeypatch.setattr(bs, "GROUP_SLOTS_WHOLE", 4)
  server = BatchedServer(_engine())
  server.max_seq, server.pages_per_row = max_seq, max_seq // server.page_size
  groups = server._dispatch_groups([bs._Ready(req=None, row=i, pad_to=pad, prefix_len=prefix) for i, (prefix, pad) in enumerate(rows)])
  assert [len(g) for g in groups] == sizes and sorted(r.row for g in groups for r in g) == list(range(len(rows)))
  for g in groups:
    window = server._page_window(max(r.prefix_len for r in g) + g[0].pad_to)
    assert not chunk or server._row_bucket(len(g)) * window <= bs.GROUP_ROWS * server._page_window(chunk)
  monkeypatch.setattr(bs, "GROUP_SLOTS_WHOLE", 16)  # a server of up to 16 slots is never asked
  assert [len(g) for g in server._dispatch_groups([bs._Ready(req=None, row=i, pad_to=pad, prefix_len=prefix) for i, (prefix, pad) in enumerate(rows)])] == [len(rows)]


def test_the_slot_cache_paths_refuse_a_recurrent_configuration():
  with pytest.raises(ValueError, match="recurrent layers is served by the batched server"):
    dec.init_kv_cache(CFG, CFG.n_layers, 1, 64)


def test_hf_itself_loads_the_export_and_agrees(tmp_path):
  """Golden, through ``transformers``' own ``GraniteMoeHybridForCausalLM``: the exported checkpoint's logits are this
  decoder's and the benchmark reference's (so the equations are HF's, not a shared misreading), and the loader reads
  the export back leaf for leaf — ``in_proj`` cut in three, ``conv1d`` taps-major, ``shared_mlp.input_linear`` in two."""
  torch = pytest.importorskip("torch")
  transformers = pytest.importorskip("transformers")
  if not hasattr(transformers, "GraniteMoeHybridForCausalLM"):
    pytest.skip("this transformers has no GraniteMoeHybridForCausalLM")
  torch.set_num_threads(1)  # a tiny model: the suite's other workers have the cores
  from xotorch_support_jetson_tpu.models.config import load_model_config
  from xotorch_support_jetson_tpu.models.hf_export import export_hf_checkpoint
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights

  out = export_hf_checkpoint(tmp_path / "granite", CFG, PARAMS)
  model = transformers.AutoModelForCausalLM.from_pretrained(str(out), torch_dtype=torch.float32).eval()
  toks = TOKENS[:45]
  with torch.no_grad():
    theirs = model(torch.tensor(toks)[None]).logits[0].numpy()
  ours, _ = dec.shard_forward(PARAMS, CFG, SHARD, jnp.asarray(toks)[None], jnp.arange(len(toks))[None])
  np.testing.assert_allclose(np.asarray(ours[0]), theirs, atol=TOL, rtol=0)
  np.testing.assert_allclose(reference(toks), theirs, atol=TOL, rtol=0)
  cfg = load_model_config(out)
  assert (cfg.layer_types, cfg.ssm_heads, cfg.ssm_state, cfg.residual_multiplier, cfg.attn_multiplier, cfg.use_rope) == (CFG.layer_types, 8, 16, 0.22, 0.25, False)
  loaded = load_shard_weights(out, cfg, SHARD)
  assert jax.tree.structure(loaded) == jax.tree.structure(PARAMS)
  for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(PARAMS)):
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_state_space_scopes_reach_the_lowered_decode_program():
  """(g) ``xot.ssm_proj`` (norm, ``w_z``/``w_xbc``/``w_dt``, ``w_out``) and ``xot.ssm`` (convolution, the state's read,
  update and write, skip, gated norm) are in the lowered ``decode.paged_batch`` beside the scopes every model has:
  ``benchmark/span_lib.py`` splits a decode step's device time by them. Lowered, not compiled (as the kernel-path case
  of ``tests/test_named_scopes.py``): the locations carry the name stack, and this file stays cheap."""
  import re

  args = (
    PARAMS, CFG, SHARD, jnp.ones((SLOTS, 1), jnp.int32), fresh_pool(), jnp.asarray(tables()), jnp.asarray([3, 5, 7, 9], jnp.int32), jnp.ones((SLOTS,), bool),
    jnp.zeros((SLOTS,), jnp.float32), jnp.full((SLOTS,), 8, jnp.int32), 4, 8, PS, False, jax.random.PRNGKey(1), None,
  )
  text = dec._fused_paged_batch_decode_impl.xot_jitted.lower(*args).as_text(debug_info=True)
  scopes = set(re.findall(r"xot\.[a-z_]+", text))
  assert {"xot.ssm", "xot.ssm_proj", "xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.ffn", "xot.head", "xot.sample"} <= scopes, sorted(scopes)
  # the state's write at (layer) sits under xot.ssm, not under the page writes' scope
  assert re.search(r'"[^"]*xot\.ssm/[^"]*dynamic_update_slice', text), "no state write under xot.ssm"
