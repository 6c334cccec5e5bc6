"""The served-kind battery: what "supported on the served path" means for a model kind, written once.

A kind is an architecture the batched server serves from the page pool — a hybrid of recurrent and attention
layers, window beside full attention — with a plain reference of its own in ``benchmark/arch_<kind>.py`` (float32,
token by token, nothing of the program). Its module (``tests/test_hybrid_ssm.py``, ``test_hybrid_kda.py``,
``test_hybrid_gdn.py``, ``test_swa_gqa_moe.py``) holds a ``KIND`` — what differs — and the tests only it has, and
takes this battery in under the names its cases have always had::

    KIND = Kind(name="olmo", arch=arch_hybrid_gdn, hf=HF, params=PARAMS, bf16_params=BF16_PARAMS, tol=5e-5, ...)
    globals().update(battery(KIND))

so every kind runs every case, a case added here runs for all of them with no edit to their modules, and
``--dist loadfile`` still gives each kind a worker of its own. This module is not collected
(``tests/conftest.py`` registers it for assertion rewriting).

One token stream, one page table, ONE prefill bucket (``Kind.pad``) and groups of one or four rows serve every case
whose assertion does not turn on them, so a module compiles each program once. The float32 cases run at ``highest``
matmul precision: the program and the reference differ by the order of their sums alone, and tolerances are absolute
on logits of spread ~1 (granite's ~0.2).
"""

import asyncio
import contextlib
import io
import re
import sys
from dataclasses import dataclass, field, replace
from functools import cached_property, partial
from pathlib import Path
from types import ModuleType

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "benchmark"))

import common  # noqa: E402

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine  # noqa: E402
from xotorch_support_jetson_tpu.inference.shard import Shard  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool, state_leaves  # noqa: E402
from xotorch_support_jetson_tpu.utils.metrics import Metrics, metrics  # noqa: E402

SLOTS = 4
EVERY_MODELS_SCOPES = frozenset({"xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.ffn", "xot.head", "xot.sample"})


def rehearsal_of(config: str, arch: ModuleType, nested: bool = False) -> tuple[dict, dict]:
  """(the benchmark's configuration file, its HF config at the kind's rehearsal widths in float32). ``nested`` keeps
  the file's nested groups (a kind whose ropes are spelled in one)."""
  file = common.load_config(config)
  flat = file if nested else {k: v for k, v in file.items() if not isinstance(v, dict)}
  return file, {**flat, **arch.REHEARSE_WIDTHS, "torch_dtype": "float32", "max_position_embeddings": 256}


@dataclass(frozen=True)
class Kind:
  """What differs between the kinds. The defaults are the three hybrids'."""

  name: str  # the Shard's model id
  arch: ModuleType  # benchmark/arch_<kind>.py: reference_forward, probes, REHEARSE_WIDTHS
  hf: dict  # the HF config at the rehearsal widths
  params: dict  # float32 leaves
  tol: float  # the program against the reference, both float32 at "highest"; the kind's module says what was measured
  bf16_params: dict | None = None  # the same weights as served
  bf16: tuple[float, float] = (0.0, 0.0)  # the bfloat16 path's mean and worst entry off the reference, each bound beside its reading in the module
  families: tuple[str, str] = ("", "")  # (model_type, family) as the loader's and the exporter's refusals name them
  pool: dict = field(default_factory=dict)  # leaf name → shape of a fresh pool, as literals
  scopes: frozenset = frozenset()  # the component scopes of the lowered decode program beyond every model's
  ops_under: tuple = ()  # (op pattern, least count, most count or None, scopes pattern): each such op of the lowered decode program lies under one of the scopes
  probe_floor: object = None  # probe name → how many tolerances a wrong reference lies off, at the least
  state_step_form: str = "delta_reference"  # which gauge of ``recurrent_state_step`` a CPU server sets
  cases: dict = field(default_factory=dict)  # argument names → the values a case that takes them runs at, where a kind has more than the one
  without: dict = field(default_factory=dict)  # battery case → why this kind does not run it
  names: dict = field(default_factory=dict)  # battery case → the name it has in this kind's module
  # --- what every case is cut to
  page_size: int = 16
  pages_per_row: int = 8
  n_tokens: int = 112
  pad: int = 64  # the one prefill bucket
  prompt: int = 50  # a prompt under the bucket
  decode_steps: int = 40
  cut: int = 48  # a prompt of ``chunked`` tokens prefilled as [0, cut) and [cut, chunked)
  chunked: int = 61
  tenants: tuple = (40, 27)  # a slot's first tenant (decoded 6 steps on) and its second
  bf16_prompt: int = 56
  scheduler_prompts: tuple = ((0, 52), (60, 75))  # slices of the token stream

  @cached_property
  def cfg(self):
    return config_from_hf(self.hf)

  @cached_property
  def shard(self) -> Shard:
    return Shard(self.name, 0, self.cfg.n_layers - 1, self.cfg.n_layers)

  @cached_property
  def tokens(self) -> np.ndarray:
    return np.random.default_rng(0).integers(3, self.cfg.vocab_size, size=self.n_tokens)

  @cached_property
  def tables(self) -> np.ndarray:
    return np.arange(1, 1 + SLOTS * self.pages_per_row, dtype=np.int32).reshape(SLOTS, self.pages_per_row)

  def reference(self, tokens, params=None, **probe) -> np.ndarray:
    return np.asarray(self.arch.reference_forward(self.params if params is None else params, self.hf, jnp.asarray(tokens), **probe))

  def greedy_under_the_reference(self, prompt, answer) -> bool:
    """Whether ``answer`` is the reference's greedy continuation of ``prompt``: each of its tokens is the reference's best
    after everything before it (one forward over prompt + answer: by induction the same as generating token by token)."""
    logits = self.reference(np.asarray(list(prompt) + list(answer)))
    return [int(np.argmax(logits[len(prompt) - 1 + i])) for i in range(len(answer))] == [int(t) for t in answer]

  def as_served(self):
    """(the configuration, the weights) in bfloat16."""
    return replace(self.cfg, dtype=jnp.bfloat16), self.bf16_params

  def fresh_pool(self, cfg=None):
    cfg = cfg or self.cfg
    return init_paged_pool(cfg, cfg.n_layers, 1 + SLOTS * self.pages_per_row, self.page_size, n_slots=SLOTS)

  def prefill(self, pool, prompts: dict, prefix: dict | None = None, pad_to: int | None = None, pad_rows: int = 0, params=None, cfg=None):
    """Prefill ``{slot: tokens}`` as one group padded to the bucket, its rows in the dict's order (each row from
    ``prefix[slot]`` on) and ``pad_rows`` padding rows after them → (last logits [K, V], pool)."""
    rows, prefix = list(prompts), prefix or {}
    K, S, MP = len(rows) + pad_rows, pad_to or self.pad, self.pages_per_row
    tok, bts = np.zeros((K, S), np.int32), np.zeros((K, MP), np.int32)
    prefix_lens, prompt_lens, slot_rows = np.zeros((K,), np.int32), np.ones((K,), np.int32), np.full((K,), SLOTS, np.int32)
    for i, r in enumerate(rows):
      start = prefix.get(r, 0)
      tok[i, : len(prompts[r]) - start] = prompts[r][start:]
      bts[i], prefix_lens[i], prompt_lens[i], slot_rows[i] = self.tables[r], start, len(prompts[r]), r
    return dec.prefill_into_pages_many(
      self.params if params is None else params, cfg or self.cfg, self.shard, jnp.asarray(tok), pool, jnp.asarray(bts), jnp.asarray(prefix_lens), jnp.asarray(prompt_lens),
      self.page_size, None, jnp.asarray(slot_rows),
    )

  def decode_step(self, pool, tokens: dict, positions: dict, params=None, cfg=None):
    """One teacher-forced decode step of the rows named → (logits [SLOTS, V], pool)."""
    tok, pos, active = np.zeros((SLOTS, 1), np.int32), np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), bool)
    for r, t in tokens.items():
      tok[r, 0], pos[r], active[r] = t, positions[r], True
    logits, pool = _decode_forward(
      cfg or self.cfg, self.shard, self.page_size, self.params if params is None else params, jnp.asarray(tok), jnp.asarray(pos), pool, jnp.asarray(self.tables), jnp.asarray(active)
    )
    return np.asarray(logits[:, 0]), pool

  def decode_chunk(self, pool, first, positions, active, n_steps: int = 4, **kwargs):
    """``decode.paged_batch``: ``n_steps`` greedy steps of the active rows → what ``fused_paged_batch_decode`` returns."""
    return dec.fused_paged_batch_decode(
      self.params, self.cfg, self.shard, jnp.asarray(first, jnp.int32), pool, self.tables, jnp.asarray(positions, jnp.int32), jnp.asarray(active), np.zeros((SLOTS,), np.float32), n_steps,
      page_size=self.page_size, use_kernel=False, **kwargs,
    )

  def cache_of(self, pool, slot: int, length: int | None = None) -> tuple:
    """What the pool holds of a slot: its recurrent-state leaves where the pool has them, else its K and V pages — the
    whole pages of the first ``length`` tokens (a page half full holds what padding wrote), or all of the row's."""
    state = state_leaves(pool)
    if state:
      return tuple(np.asarray(leaf[:, slot]) for leaf in state.values())
    pages = self.tables[slot][: self.pages_per_row if length is None else length // self.page_size]
    return np.asarray(pool["k"][:, pages]), np.asarray(pool["v"][:, pages])

  def engine(self) -> JaxShardedInferenceEngine:
    engine = JaxShardedInferenceEngine(use_local_mesh=False)
    engine.load_test_model(self.shard, self.cfg, self.params)
    return engine

  def lowered_decode_program(self) -> str:
    """``decode.paged_batch`` lowered, not compiled: the locations carry the name stack, and a module stays cheap."""
    rows = lambda value, dtype: jnp.full((SLOTS,), value, dtype)  # noqa: E731
    args = (
      self.params, self.cfg, self.shard, jnp.ones((SLOTS, 1), jnp.int32), self.fresh_pool(), jnp.asarray(self.tables), jnp.asarray([3, 5, 7, 9], jnp.int32), rows(True, bool),
      rows(0, jnp.float32), rows(8, jnp.int32), 4, 8, self.page_size, False, jax.random.PRNGKey(1), None,
    )
    return dec._fused_paged_batch_decode_impl.xot_jitted.lower(*args).as_text(debug_info=True)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _decode_forward(cfg, shard, page_size, params, tok, pos, pool, tables, active):
  return dec.paged_decode_forward(params, cfg, shard, tok, pos[:, None], pool, tables, page_size, False, active=active)[:2]  # (the third result counts expert visits)


def serve(server, prompts, n_gen: int):
  async def run():
    return await asyncio.gather(*(
      server.submit(f"r{i}-{len(p)}", np.asarray(p, np.int32), max_tokens=n_gen, temp=0.0, top_k=35, eos_ids=(), emit=lambda *_: None) for i, p in enumerate(prompts)
    ))

  return asyncio.run(run())


def _named(locs: dict, ref: str, depth: int = 0) -> str:
  """A location's whole chain of names in a lowered text."""
  body = locs.get(ref, "")
  return body + "".join(_named(locs, r, depth + 1) for r in re.findall(r"#loc\d+", body)) if depth < 8 else body


# ------------------------------------------------------------ what a kind's module is given


@pytest.fixture(autouse=True)
def highest_precision():
  with jax.default_matmul_precision("highest"):
    yield


@pytest.fixture(scope="module")
def kind(request) -> Kind:
  return request.module.KIND


@pytest.fixture
def stepping_kind(kind) -> Kind:
  """The kind whose decode program a case steps: the module's, but in a module that runs those cases in more than one
  form of the state's step (granite's: the one-pass kernel interpreted, at a state of 128 lanes)."""
  return kind


@pytest.fixture(scope="module")
def prompt(kind) -> int:
  return kind.prompt


@pytest.fixture(scope="module")
def cut(kind) -> int:
  return kind.cut


def pytest_generate_tests(metafunc):
  """A kind's own values for a case's arguments (``Kind.cases``): its refusals; prompts and cuts on both sides of a window."""
  for names, values in metafunc.module.KIND.cases.items():
    if set(names.split(",")) <= set(metafunc.fixturenames):
      metafunc.parametrize(names, values)


@dataclass
class Served:
  """Two interleaved requests and the first of them again through a ``BatchedServer`` of two slots, and the registry
  before and after."""

  server: BatchedServer
  prompts: list
  answers: list
  again: list
  before: Metrics
  after: Metrics
  cached: list  # the prefix cache's keys after the last answer
  out: str  # what the server printed


@pytest.fixture(scope="module")
def served(kind) -> Served:
  prompts = [[int(t) for t in kind.tokens[a:b]] for a, b in kind.scheduler_prompts]
  with pytest.MonkeyPatch.context() as patch, contextlib.redirect_stdout(io.StringIO()) as out:
    patch.setenv("XOT_TPU_BATCH_SLOTS", "2")
    patch.setenv("XOT_TPU_PAGE_SIZE", str(kind.page_size))
    server = BatchedServer(kind.engine())
    before = Metrics.merged([metrics.snapshot()])
    try:
      answers = serve(server, prompts, 6)
      again = serve(server, prompts[:1], 6)[0]
      cached = server.allocator.cached_keys()
    finally:
      server.shutdown()
  return Served(server, prompts, answers, again, before, Metrics.merged([metrics.snapshot()]), cached, out.getvalue())


# ------------------------------------------------------------ the battery


def test_a_checkpoint_of_the_family_is_refused_by_name(kind, tmp_path):
  """No safetensors name map exists for the family: a checkpoint is refused by name, loader and exporter alike."""
  from xotorch_support_jetson_tpu.models.hf_export import export_hf_checkpoint
  from xotorch_support_jetson_tpu.models.loader import load_shard_weights

  model_type, family = kind.families
  with pytest.raises(NotImplementedError, match=model_type):
    load_shard_weights(tmp_path, kind.cfg, kind.shard)
  with pytest.raises(NotImplementedError, match=family):
    export_hf_checkpoint(tmp_path / "out", kind.cfg, kind.params)
  with pytest.raises(ValueError, match=model_type):  # MODEL_FAMILIES' error lists the family
    config_from_hf({"model_type": "rwkv7"})


def test_config_from_hf_refuses_what_is_not_implemented_by_name(kind, key, value, named):
  with pytest.raises(ValueError, match=re.escape(named)):
    config_from_hf({**kind.hf, key: value})


def test_the_cacheless_forward_equals_the_reference(kind):
  got, _ = dec.jit_shard_forward(kind.params, kind.cfg, kind.shard, jnp.asarray(kind.tokens)[None], jnp.arange(len(kind.tokens))[None], None)  # the served entry: one program
  np.testing.assert_allclose(np.asarray(got[0]), kind.reference(kind.tokens), atol=kind.tol, rtol=0)


def test_every_named_probe_moves_the_reference_past_the_tolerance(kind):
  """Each wrong reference of the kind's ``probes`` (and of its ``long_probes``, where the token stream is long enough to
  tell them) lies ``Kind.probe_floor`` tolerances or more from the sound one — the kind's module says which is which."""
  sound = kind.reference(kind.tokens)
  for name, probe in {**kind.arch.probes(kind.hf), **getattr(kind.arch, "long_probes", lambda hf: {})(kind.hf)}.items():
    moved = float(np.abs(kind.reference(kind.tokens, **probe) - sound).max())
    assert moved > kind.probe_floor(name) * kind.tol, (name, moved)


def test_prefill_then_decode_through_the_pool_equals_the_reference(stepping_kind, prompt):
  """float32: ``prompt`` tokens prefilled into slot 2 beside three padding rows, then ``decode_steps`` decode steps, one
  token each, through whatever the pool holds for the kind — pages, latent pages, recurrent state and convolution rows:
  every step's LOGITS are the reference's full forward at that position, to the order of the sums."""
  kind, T = stepping_kind, stepping_kind.tokens
  want = kind.reference(T[: prompt + kind.decode_steps])
  last, pool = kind.prefill(kind.fresh_pool(), {2: T[:prompt]}, pad_rows=3)
  assert {name: pool[name].shape for name in kind.pool} == kind.pool
  np.testing.assert_allclose(np.asarray(last[0]), want[prompt - 1], atol=kind.tol, rtol=0)
  for other in (0, 1, 3):  # nothing was written for a padding row, nor for a slot no request held
    assert not any(leaf.any() for leaf in kind.cache_of(pool, other))
  for t in range(prompt, prompt + kind.decode_steps):
    logits, pool = kind.decode_step(pool, {2: T[t]}, {2: t})
    np.testing.assert_allclose(logits[2], want[t], atol=kind.tol, rtol=0, err_msg=f"decode step at position {t}")
  assert not any(np.asarray(leaf[:, other]).any() for leaf in state_leaves(pool).values() for other in (0, 1, 3))  # ... and no state by a step that row did not take


def test_the_bfloat16_path_stays_within_bfloat16s_rounding_of_the_reference(kind):
  """bfloat16 weights, activations and pages as served, the recurrent state float32: prefill and 34 decode steps against
  the float32 reference on the same bfloat16 weights, the logits of all 35 positions. bfloat16 keeps 7 bits of
  mantissa: each layer's two blocks round their increment and the stream. The mean and the worst entry are held to the
  kind's ``bf16`` bounds — some three times what its module says was measured — and each bound is under half of what
  the weakest wrong architecture, a dropped last layer, reads."""
  cfg, params = kind.as_served()
  T, n, end = kind.tokens, kind.bf16_prompt, kind.bf16_prompt + 34
  rounded = jax.tree.map(lambda x: x.astype(jnp.float32), params)
  want = kind.reference(T[:end], params=rounded)
  dropped = np.abs(kind.reference(T[:end], params=rounded, drop_layer=kind.cfg.n_layers - 1) - want)[n - 1 :]
  last, pool = kind.prefill(kind.fresh_pool(cfg), {1: T[:n]}, params=params, cfg=cfg)
  assert all(leaf.dtype == (jnp.float32 if name == "ssm" else jnp.bfloat16) for name, leaf in pool.items())
  off = [np.abs(np.asarray(last[0], np.float32) - want[n - 1])]
  for t in range(n, end):
    logits, pool = kind.decode_step(pool, {1: T[t]}, {1: t}, params=params, cfg=cfg)
    off.append(np.abs(logits[1].astype(np.float32) - want[t]))
  mean, worst = float(np.mean(off)), float(np.max(off))
  assert mean < kind.bf16[0] < 0.5 * float(dropped.mean()) and worst < kind.bf16[1] < 0.5 * float(dropped.max()), (mean, worst, float(dropped.mean()), float(dropped.max()))


def test_a_padded_group_leaves_each_row_what_its_unpadded_run_does(kind):
  """Rows of ``prompt``, ``prompt - 17`` and 2 tokens as one group padded to the bucket beside a padding row: padding has
  no decay and no update, is cut from the convolution's tail and sees no other row's experts or window, so each row's
  last logits and what the pool holds of its slot are what the row's own prefill leaves alone — unpadded for the row of
  ``prompt - 17`` (its own program), the other two alone at the bucket (one program for both: a compile less)."""
  T, n = kind.tokens, kind.prompt
  prompts = {0: T[:n], 1: T[10 : n - 7], 3: T[n + 10 : n + 12]}
  logits, grouped = kind.prefill(kind.fresh_pool(), prompts, pad_rows=1)
  for i, (slot, toks) in enumerate(prompts.items()):
    solo_logits, solo = kind.prefill(kind.fresh_pool(), {slot: toks}, pad_to=len(toks) if slot == 1 else None)
    np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(solo_logits[0]), atol=kind.tol, rtol=0, err_msg=f"slot {slot}")
    for got, want in zip(kind.cache_of(grouped, slot, len(toks)), kind.cache_of(solo, slot, len(toks))):
      np.testing.assert_allclose(got, want, atol=kind.tol, rtol=0, err_msg=f"slot {slot}")


def test_a_prompt_prefilled_in_two_chunks_equals_one(kind, cut):
  """Positions [0, cut) then [cut, chunked): the second call continues from the slot's own state, convolution rows and
  pages (a window layer looks back over the first call's)."""
  toks = kind.tokens[: kind.chunked]
  whole_logits, whole = kind.prefill(kind.fresh_pool(), {1: toks})
  _, pool = kind.prefill(kind.fresh_pool(), {1: toks[:cut]})
  cut_logits, chunked = kind.prefill(pool, {1: toks}, prefix={1: cut})
  np.testing.assert_allclose(np.asarray(cut_logits), np.asarray(whole_logits), atol=kind.tol, rtol=0)
  np.testing.assert_allclose(np.asarray(cut_logits[0]), kind.reference(toks)[-1], atol=kind.tol, rtol=0)
  for got, want in zip(kind.cache_of(chunked, 1, len(toks)), kind.cache_of(whole, 1, len(toks))):
    np.testing.assert_allclose(got, want, atol=kind.tol, rtol=0)


def test_a_second_chunk_in_a_group_of_unsorted_slots_beside_a_fresh_and_a_padding_row_equals_one_chunk(kind):
  """The one path that USES the state a prefill group reads (``fresh`` false; ``models/decoder.py _state_rows``, ISSUE
  48), and no cell of the benchmark sends it: two prompts prefilled to ``cut`` and two thirds of it as a group of
  slots 3, 0 and two padding rows, then continued in ONE group whose rows name slots 3, 2, 0 — neither sorted nor
  adjacent; slot 2's row starts at position 0 — and a padding row, which names the slot past the last (its read is
  clamped onto slot 3's, its write dropped). Every row ends in the logits and the cache of its one-chunk prefill and in
  the token-by-token reference's logits; slot 1, which no row names, stays zero."""
  T, n, at = kind.tokens, kind.chunked, {3: kind.cut, 0: kind.cut - kind.cut // 3}
  a, b, c = T[:n], T[10 : n - 1], T[n - 1 : n + 29]
  _, pool = kind.prefill(kind.fresh_pool(), {3: a[: at[3]], 0: b[: at[0]]}, pad_rows=2)
  logits, pool = kind.prefill(pool, {3: a, 2: c, 0: b}, prefix=at, pad_rows=1)
  for i, (slot, toks) in enumerate({3: a, 2: c, 0: b}.items()):
    whole_logits, whole = kind.prefill(kind.fresh_pool(), {slot: toks})
    np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(whole_logits[0]), atol=kind.tol, rtol=0, err_msg=f"slot {slot}")
    np.testing.assert_allclose(np.asarray(logits[i]), kind.reference(toks)[-1], atol=kind.tol, rtol=0, err_msg=f"slot {slot}")
    for got, want in zip(kind.cache_of(pool, slot, len(toks)), kind.cache_of(whole, slot, len(toks))):
      np.testing.assert_allclose(got, want, atol=kind.tol, rtol=0, err_msg=f"slot {slot}")
  assert not any(leaf.any() for leaf in kind.cache_of(pool, 1))


def test_a_reused_slot_gives_its_second_tenant_the_solo_answer(kind):
  """Slot 2 serves one request (prefill + 6 decode steps), then another from position 0: the second sees zeros, not its
  predecessor's state, and no page of its predecessor's through a window — its logits, its cache and its next decode
  step are those of a pool it has to itself, bit for bit."""
  T, (first, second) = kind.tokens, kind.tenants
  _, pool = kind.prefill(kind.fresh_pool(), {2: T[:first]})
  for t in range(first, first + 6):
    _, pool = kind.decode_step(pool, {2: T[t]}, {2: t})
  assert kind.cache_of(pool, 2)[0].any()
  toks = T[first + 10 : first + 10 + second]
  reused_logits, reused = kind.prefill(pool, {2: toks})
  solo_logits, solo = kind.prefill(kind.fresh_pool(), {2: toks})
  np.testing.assert_array_equal(np.asarray(reused_logits), np.asarray(solo_logits))
  for got, want in zip(kind.cache_of(reused, 2, second), kind.cache_of(solo, 2, second)):
    np.testing.assert_array_equal(got, want)
  np.testing.assert_array_equal(kind.decode_step(reused, {2: T[0]}, {2: second})[0][2], kind.decode_step(solo, {2: T[0]}, {2: second})[0][2])


def test_a_decode_chunk_leaves_an_inactive_rows_cache_bit_for_bit(stepping_kind):
  """A chunk of 4 steps of ``decode.paged_batch`` with rows 0 and 3 active: rows 1 and 2, resident but not stepped (a
  row mid-prefill, a starved row), keep their state, their convolution rows and their pages exactly; the active rows'
  change, and row 0's tokens are the reference's greedy ones."""
  kind, T = stepping_kind, stepping_kind.tokens
  _, pool = kind.prefill(kind.fresh_pool(), {0: T[:20], 1: T[20:50], 2: T[50:58], 3: T[30:41]})
  before = {slot: kind.cache_of(pool, slot) for slot in range(SLOTS)}
  toks, _, new_pos, pool = kind.decode_chunk(pool, [[T[20]], [1], [1], [T[41]]], [20, 30, 8, 11], [True, False, False, True])[:4]
  assert np.asarray(new_pos).tolist() == [24, 30, 8, 15]
  for slot in (1, 2):
    for got, want in zip(kind.cache_of(pool, slot), before[slot]):
      np.testing.assert_array_equal(got, want)
  for slot in (0, 3):
    assert not any(np.array_equal(got, was) for got, was in zip(kind.cache_of(pool, slot), before[slot]))
  assert kind.greedy_under_the_reference(T[:21], np.asarray(toks)[0, :3])


def test_the_scheduler_serves_interleaved_requests_as_the_reference_does(kind, served):
  """Requests of different lengths through ``BatchedServer`` (admission groups, decode chunks, the pool's state and
  pages) answer greedy-equal to the reference, and the first of them again as it did. Where the configuration has
  recurrent layers, that ONE property turns prefix reuse (the same long prompt again reuses no page), the host tier,
  speculation and mixed ticks off, each at its one gate, with one log line; a slot's state is reset once an admission,
  and the gauges say what the state weighs and which rule steps it. Where it has none, mixed ticks stay on."""
  cfg, server = kind.cfg, served.server
  assert all(len(a) == 6 and kind.greedy_under_the_reference(p, a) for p, a in zip(served.prompts, served.answers))
  assert served.again == served.answers[0]
  moved = lambda name: served.after.counter_value(name) - served.before.counter_value(name)  # noqa: E731
  if not cfg.recurrent_layers:
    assert server.ops.mixed_tick_supported() and not server.ops.prefill_donates_pool  # (a CPU states no memory limit: the copying prefill)
    return
  assert not moved("prefix_cache_hit_pages_total") and not served.cached
  assert server.tier is None and not server.spec and not server._mixed_active() and not server.ops.mixed_tick_supported() and server.ops.prefill_donates_pool
  assert moved("recurrent_state_resets_total") == len(served.prompts) + 1
  matrix = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state if cfg.state_matrix else 0  # (a kind whose whole state is its convolution's tail: no ``ssm`` leaf weighs in)
  assert served.after.gauge_value("recurrent_state_bytes") == 2 * cfg.recurrent_layers * 4 * (matrix + (cfg.ssm_conv - 1) * cfg.ssm_conv_dim)
  assert re.search(rf"\(2 slots of {cfg.recurrent_layers * 4 * (matrix + (cfg.ssm_conv - 1) * cfg.ssm_conv_dim)} bytes", served.out)  # the start-up line and the gauge agree
  forms = {form: served.after.gauge_value("recurrent_state_step", labels={"form": form}) for form in ssm_ops.STATE_STEP_FORMS}
  assert forms == {form: int(form == kind.state_step_form) for form in ssm_ops.STATE_STEP_FORMS}  # a CPU: the XLA expression
  assert served.out.count("keep a recurrent state per slot") == 1 and "prefix reuse, the host KV tier, speculation and mixed ticks are off" in served.out


def test_the_scopes_reach_the_lowered_decode_program(kind):
  """Every model's component scopes and the kind's own (``Kind.scopes``) are in the lowered ``decode.paged_batch``:
  ``benchmark/span_lib.py`` splits a decode step's device time by them. A recurrent state's write at (layer) sits under
  ``xot.ssm``, not under the page writes' scope; and each op the kind names (``Kind.ops_under``: a post-norm's rsqrt,
  a gate's softplus) lies under a component's scope, so nothing of a block joins ``decode_unscoped_device_ms``."""
  text = kind.lowered_decode_program()
  scopes = set(re.findall(r"xot\.[a-z_]+", text))
  assert EVERY_MODELS_SCOPES | kind.scopes <= scopes, sorted((EVERY_MODELS_SCOPES | kind.scopes) - scopes)
  if kind.cfg.recurrent_layers:
    assert re.search(r'"[^"]*xot\.ssm/[^"]*dynamic_update_slice', text), "no state write under xot.ssm"
  locs = dict(re.findall(r"(#loc\d+) = loc\((.*)\)$", text, flags=re.M))
  for op, least, most, under in kind.ops_under:
    found = list(re.finditer(op + r".*loc\((#loc\d+)\)", text))
    assert least <= len(found) <= (most or len(found)) and all(re.search(under, _named(locs, m.group(1))) for m in found), (op, len(found))


_FIXTURES = {"highest_precision": highest_precision, "kind": kind, "stepping_kind": stepping_kind, "prompt": prompt, "cut": cut, "served": served, "pytest_generate_tests": pytest_generate_tests}
_CASES = {name: value for name, value in list(globals().items()) if name.startswith("test_")}


def battery(kind: Kind) -> dict:
  """The fixtures and the cases for a kind's module to take in: every case but those the kind says it is ``without``
  (and why), each under the name ``Kind.names`` gives it there, else its own."""
  unknown = (set(kind.without) | set(kind.names)) - set(_CASES)
  assert not unknown, f"{kind.name}: no such case of the battery: {sorted(unknown)}"
  return {**_FIXTURES, **{kind.names.get(name, name): case for name, case in _CASES.items() if name not in kind.without}}
