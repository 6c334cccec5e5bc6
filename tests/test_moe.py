"""MoE op + MoE-decoder tests.

The reference cannot load any of its registered MoE models (SURVEY.md §2.11 —
dense-only builder, ``general_mha.py:77-120``); these tests cover the real MoE
support this framework adds: routing math, capacity-based dispatch, the
deepseek-style dense-prefix decoder, and the sharding-equivalence contract
(full model == composed layer-range shards) across the dense/MoE boundary.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.inference.shard import Shard
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  init_kv_cache,
  shard_forward,
  slice_shard_params,
)
from xotorch_support_jetson_tpu.ops.moe import (
  dispatch_combine_masks,
  expert_capacity,
  moe_ffn,
  router_topk,
)


def _moe_cfg(**over):
  defaults = dict(
    n_experts=4,
    n_active_experts=2,
    moe_hidden_dim=32,
    first_k_dense=1,
    n_layers=4,
  )
  defaults.update(over)
  return tiny_test_config(**defaults)


def test_router_topk_softmax_norm():
  logits = jnp.asarray([[1.0, 3.0, 2.0, -1.0]])
  w, idx = router_topk(logits, k=2, scoring="softmax", norm_topk=True)
  assert idx.tolist() == [[1, 2]]
  np.testing.assert_allclose(np.sum(np.asarray(w), axis=-1), 1.0, rtol=1e-6)


def test_router_sigmoid_selection_bias_reorders_but_does_not_weight():
  logits = jnp.asarray([[0.0, 0.1, 0.2, 0.3]])
  bias = jnp.asarray([10.0, 0.0, 0.0, 0.0])  # force expert 0 into the top-k
  w, idx = router_topk(logits, k=2, scoring="sigmoid", selection_bias=bias)
  assert 0 in idx.tolist()[0]
  # combine weight for expert 0 is its *unbiased* sigmoid score
  pos = idx.tolist()[0].index(0)
  np.testing.assert_allclose(np.asarray(w)[0, pos], 1 / (1 + np.exp(0.0)), rtol=1e-6)


def test_dispatch_exact_capacity_no_drops():
  T, E, k = 6, 4, 2
  key = jax.random.PRNGKey(0)
  logits = jax.random.normal(key, (T, E))
  w, idx = router_topk(logits, k)
  C = expert_capacity(T, k, E, None)
  assert C == T
  dispatch, combine = dispatch_combine_masks(idx, w, E, C)
  # every assignment lands: total dispatched slots == T*k
  assert float(jnp.sum(dispatch)) == T * k
  # combine weights sum per token to the router weights' sum
  np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))), np.asarray(jnp.sum(w, axis=-1)), rtol=1e-5)


def test_capacity_one_drops_overflow():
  # All tokens pick expert 0 ⇒ capacity 1 keeps exactly one assignment.
  idx = jnp.zeros((5, 1), dtype=jnp.int32)
  w = jnp.ones((5, 1))
  dispatch, _ = dispatch_combine_masks(idx, w, n_experts=2, capacity=1)
  assert float(jnp.sum(dispatch)) == 1.0


def test_moe_ffn_matches_per_token_loop():
  """Capacity einsum == naive gather loop (the definition of routed FFN)."""
  T, D, E, F, k = 5, 8, 4, 16, 2
  key = jax.random.PRNGKey(1)
  ks = jax.random.split(key, 5)
  x = jax.random.normal(ks[0], (T, D), dtype=jnp.float32)
  w_router = jax.random.normal(ks[1], (D, E)) * 0.1
  w_gate = jax.random.normal(ks[2], (E, D, F)) * 0.1
  w_up = jax.random.normal(ks[3], (E, D, F)) * 0.1
  w_down = jax.random.normal(ks[4], (E, F, D)) * 0.1

  out, _aux, visited = moe_ffn(x, w_router, w_gate, w_up, w_down, k=k)
  assert 1 <= int(visited) <= E

  weights, idx = router_topk(x @ w_router, k)
  expected = np.zeros((T, D), np.float32)
  for t in range(T):
    for j in range(k):
      e = int(idx[t, j])
      h = np.asarray(x[t]) @ np.asarray(w_gate[e])
      act = h / (1 + np.exp(-h)) * (np.asarray(x[t]) @ np.asarray(w_up[e]))
      expected[t] += float(weights[t, j]) * (act @ np.asarray(w_down[e]))
  np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-4, atol=1e-5)


# ``shard_forward`` as one program a call: eager, it dispatches (and compiles) an op at a time. For the decoder cases
# below, none of which patches what a trace reads; the grouped form's cases further down call the function itself.
forward = jax.jit(shard_forward, static_argnums=(1, 2))


def test_moe_decoder_forward_and_decode():
  """Dense-prefix + MoE stacks: prefill-with-cache then one decode step."""
  cfg = _moe_cfg(shared_expert_dim=32, shared_expert_gate=True)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg, "moe-test")
  assert params["layers"]["wq"].shape[0] == 1  # dense prefix
  assert params["moe_layers"]["w_experts_gate"].shape[:2] == (3, 4)

  B, S = 2, 6
  tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
  cache = init_kv_cache(cfg, shard.n_shard_layers, B, 16)
  logits, cache = forward(params, cfg, shard, tokens, positions, cache)
  assert logits.shape == (B, S, cfg.vocab_size)

  nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
  logits2, _ = forward(params, cfg, shard, nxt, jnp.full((B, 1), S, jnp.int32), cache)
  assert logits2.shape == (B, 1, cfg.vocab_size)
  assert np.all(np.isfinite(np.asarray(logits2, dtype=np.float32)))


def test_moe_sharding_equivalence_across_boundary():
  """Full MoE model == composed shards split *at* the dense/MoE boundary
  and also mid-MoE (reference's core numerical contract,
  inference/test_inference_engine.py:12-47)."""
  cfg = _moe_cfg()
  params, full = full_model_params(jax.random.PRNGKey(2), cfg, "moe-test")
  B, S = 1, 5
  tokens = jnp.arange(S, dtype=jnp.int32)[None, :]
  positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

  full_logits, _ = forward(params, cfg, full, tokens, positions, None)

  for split in (1, 2):  # layer boundary: at the dense/MoE edge and mid-MoE
    a = Shard("moe-test", 0, split - 1, cfg.n_layers)
    b = Shard("moe-test", split, cfg.n_layers - 1, cfg.n_layers)
    pa = slice_shard_params(params, cfg, full, a)
    pb = slice_shard_params(params, cfg, full, b)
    hidden, _ = forward(pa, cfg, a, tokens, positions, None)
    logits, _ = forward(pb, cfg, b, hidden, positions, None)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits), rtol=2e-5, atol=2e-5)


def test_moe_sigmoid_router_decoder():
  """deepseek-v3 style: sigmoid scoring + selection bias + scaling factor."""
  cfg = _moe_cfg(router_scoring="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.5, first_k_dense=0)
  params, shard = full_model_params(jax.random.PRNGKey(3), cfg, "v3-test")
  assert "layers" not in params and "router_bias" in params["moe_layers"]
  tokens = jnp.asarray([[1, 2, 3]], dtype=jnp.int32)
  positions = jnp.asarray([[0, 1, 2]], dtype=jnp.int32)
  logits, _ = forward(params, cfg, shard, tokens, positions, None)
  assert np.all(np.isfinite(np.asarray(logits, dtype=np.float32)))


def test_moe_quantized_forward_close_to_fp():
  """XOT_TPU_QUANT=int8 path: expert weights quantize and the forward stays close."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  cfg = _moe_cfg(shared_expert_dim=32)
  params, shard = full_model_params(jax.random.PRNGKey(4), cfg, "moe-q")
  qp = quantize_params(params)
  assert qp["moe_layers"]["w_experts_gate"].dtype == jnp.int8
  assert qp["layers"]["w_gate"].dtype == jnp.int8
  assert "w_router" not in [k for k in qp["moe_layers"] if qp["moe_layers"][k].dtype == jnp.int8]

  tokens = jnp.asarray([[1, 2, 3, 4]], dtype=jnp.int32)
  positions = jnp.asarray([[0, 1, 2, 3]], dtype=jnp.int32)
  ref, _ = forward(params, cfg, shard, tokens, positions, None)
  out, _ = forward(qp, cfg, shard, tokens, positions, None)
  # int8 weight error is small at tiny scale; just require close correlation
  ref, out = np.asarray(ref, np.float32).ravel(), np.asarray(out, np.float32).ravel()
  corr = np.corrcoef(ref, out)[0, 1]
  assert corr > 0.99, f"quantized forward diverged (corr={corr})"


def test_moe_aux_loss_surfaces_in_forward():
  """make_forward_fn returns aux > 0 for MoE models and 0 for dense ones."""
  from xotorch_support_jetson_tpu.parallel import MeshPlan, build_mesh, make_forward_fn

  mesh = build_mesh(MeshPlan())
  tokens = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (1, 8))

  moe_cfg = _moe_cfg(first_k_dense=0)
  params, _ = full_model_params(jax.random.PRNGKey(5), moe_cfg)
  _, aux = make_forward_fn(mesh, moe_cfg, MeshPlan(), remat=False)(params, tokens, positions)
  assert float(aux) > 0.0

  dense_cfg = tiny_test_config(n_layers=2)
  dparams, _ = full_model_params(jax.random.PRNGKey(6), dense_cfg)
  _, daux = make_forward_fn(mesh, dense_cfg, MeshPlan(), remat=False)(dparams, tokens, positions)
  assert float(daux) == 0.0


def test_moe_chunked_dispatch_matches_single_block():
  """Chunked exact dispatch (T > chunk) == one-shot dispatch."""
  T, D, E, F, k = 40, 8, 4, 16, 2
  ks = jax.random.split(jax.random.PRNGKey(11), 5)
  x = jax.random.normal(ks[0], (T, D), dtype=jnp.float32)
  w_router = jax.random.normal(ks[1], (D, E)) * 0.1
  w_gate = jax.random.normal(ks[2], (E, D, F)) * 0.1
  w_up = jax.random.normal(ks[3], (E, D, F)) * 0.1
  w_down = jax.random.normal(ks[4], (E, F, D)) * 0.1
  one = moe_ffn(x, w_router, w_gate, w_up, w_down, k=k, chunk=64)[0]
  chunked = moe_ffn(x, w_router, w_gate, w_up, w_down, k=k, chunk=16)[0]
  np.testing.assert_allclose(np.asarray(chunked), np.asarray(one), rtol=1e-5, atol=1e-6)


def test_mla_decode_cache_matches_full_forward():
  """MLA (deepseek) KV-cache path: prefill + one decode step == cache-less
  forward on the extended sequence (k/v cache widths differ under MLA)."""
  cfg = tiny_test_config(
    n_layers=2,
    n_heads=4,
    n_kv_heads=4,
    kv_lora_rank=16,
    q_lora_rank=24,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    n_experts=4,
    n_active_experts=2,
    moe_hidden_dim=32,
    shared_expert_dim=32,
    first_k_dense=1,
  )
  # Latent cache: "k" holds the kv latent (rank), "v" the rope channel.
  assert cfg.is_mla and cfg.cache_kv_heads == 1
  assert cfg.cache_k_dim == cfg.kv_lora_rank and cfg.cache_v_dim == cfg.qk_rope_head_dim
  params, shard = full_model_params(jax.random.PRNGKey(12), cfg, "mla-test")

  S = 6
  tokens = jnp.arange(1, S + 2, dtype=jnp.int32)[None, :]  # S+1 tokens
  positions = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32), (1, S + 1))
  full_logits, _ = forward(params, cfg, shard, tokens, positions, None)

  cache = init_kv_cache(cfg, shard.n_shard_layers, 1, 16)
  _, cache = forward(params, cfg, shard, tokens[:, :S], positions[:, :S], cache)
  step_logits, _ = forward(params, cfg, shard, tokens[:, S:], positions[:, S:], cache)
  np.testing.assert_allclose(np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, S]), rtol=2e-4, atol=2e-4)


def test_mla_lora_adapters_are_live():
  """add_lora on an MLA model attaches to wq_b/wkv_b and affects the forward."""
  from xotorch_support_jetson_tpu.train.lora import add_lora, merge_lora

  cfg = tiny_test_config(
    n_layers=2, n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
  )
  params, shard = full_model_params(jax.random.PRNGKey(13), cfg, "mla-lora")
  lp = add_lora(params, rank=4, key=jax.random.PRNGKey(14))
  assert "wq_b_lora_a" in lp["layers"] and "wkv_b_lora_a" in lp["layers"]

  tokens = jnp.asarray([[1, 2, 3, 4]], dtype=jnp.int32)
  positions = jnp.asarray([[0, 1, 2, 3]], dtype=jnp.int32)
  base, _ = forward(params, cfg, shard, tokens, positions, None)
  zeroed, _ = forward(lp, cfg, shard, tokens, positions, None)
  np.testing.assert_allclose(np.asarray(zeroed), np.asarray(base), rtol=1e-6)  # B=0 ⇒ no-op

  # Non-zero B must change the output — proves the decoder actually applies
  # the adapters on the MLA path (a silent no-op would pass the line above).
  lp["layers"]["wq_b_lora_b"] = jnp.ones_like(lp["layers"]["wq_b_lora_b"]) * 0.05
  bumped, _ = forward(lp, cfg, shard, tokens, positions, None)
  assert not np.allclose(np.asarray(bumped), np.asarray(base))

  # merge_lora folds the delta and drops the adapter leaves.
  merged = merge_lora(lp, rank=4)
  assert "wq_b_lora_a" not in merged["layers"]
  folded, _ = forward(merged, cfg, shard, tokens, positions, None)
  np.testing.assert_allclose(np.asarray(folded), np.asarray(bumped), rtol=2e-4, atol=2e-5)


ROUTINGS = [
  dict(scoring="softmax", norm_topk=False),  # mixtral
  dict(scoring="softmax", norm_topk=True),  # qwen2-moe
  dict(scoring="softmax", norm_topk=True, n_group=4, topk_group=2, group_mode="max", scale=2.0),  # deepseek-v2
  dict(scoring="sigmoid", norm_topk=True, n_group=4, topk_group=2, group_mode="top2sum", scale=2.5),  # deepseek-v3
]


def _experts(rng, E, E_held, D, F, dtype=jnp.float32, layers=None):
  lead = () if layers is None else (layers,)
  w = lambda *shape: jnp.asarray(rng.normal(size=lead + shape) * 0.1, dtype)  # noqa: E731
  return jnp.asarray(rng.normal(size=(D, E)), jnp.float32), w(E_held, D, F), w(E_held, D, F), w(E_held, F, D)


@pytest.fixture
def interpreted(monkeypatch):
  """The tests' switch (``ops/moe.py INTERPRET``): this CPU takes the grouped form, its kernels interpreted."""
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(moe, "INTERPRET", True)


def _force_walk(monkeypatch, walk: str, tile: int | None = None):
  """``ops/moe.py grouped_walk`` answering ``walk`` whatever the shapes (the rule itself is
  ``test_the_walk_is_read_from_static_shapes``'s), at ``tile`` rows or the shared walk's height — ONE height for both
  walks, so that this CPU's dot, whose sums follow the tile's height as the MXU's do not, gives them the same bits."""
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(moe, "grouped_walk", lambda rows, *a, **kw: (walk, tile or (moe.ROW_TILE if rows >= moe.ROW_TILE else -(-rows // 16) * 16)))


@pytest.fixture(params=["shared", "aligned"])
def walk(request, monkeypatch):
  """Both walks of the grouped form, whatever the shapes."""
  _force_walk(monkeypatch, request.param)
  return request.param


def _both_forms(x, w_router, w_gate, w_up, w_down, k, held=None, scales=None, layer=None, **routing):
  """(block form, grouped form with its kernels interpreted) of one layer: each (out, aux, visited). The grouped form
  takes the leaves as a stack (of one layer, where ``layer`` is None) — codes with their ``scales`` —, the block form
  that layer's leaves, dequantised."""
  from xotorch_support_jetson_tpu.models.quantize import dequantize_leaf
  from xotorch_support_jetson_tpu.ops.moe import _moe_ffn_block, _moe_ffn_grouped

  full = dict(scoring="softmax", norm_topk=False, selection_bias=None, scale=1.0, n_group=1, topk_group=1, group_mode="none")
  full.update(routing)
  stack = [w_gate, w_up, w_down]
  if layer is None:
    stack, scales, layer = [w[None] for w in stack], scales and tuple(s[None] for s in scales), 0
  cut = lambda w: jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)  # noqa: E731
  one = [cut(w) for w in stack]
  if scales:
    one = [dequantize_leaf(w, cut(s), w.shape[-2], x.dtype) for w, s in zip(one, scales)]
  ref = _moe_ffn_block(x, w_router, *one, k, capacity_factor=None, held=held, **full)
  got = _moe_ffn_grouped(x, w_router, *stack, k, held=held, scales=scales, layer=layer, **full)
  return ref, got


def _assert_same(ref, got, rtol=1e-5, atol=1e-5):
  np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=rtol, atol=atol)
  np.testing.assert_allclose(float(got[1]), float(ref[1]), rtol=1e-5)
  assert int(got[2]) == int(ref[2])


@pytest.mark.parametrize("kwargs", ROUTINGS)
def test_moe_grouped_form_matches_block_form(kwargs, interpreted, walk):
  """The grouped form (sorted assignments, two Mosaic kernels, interpreted here) computes the same outputs, auxiliary
  loss and count of experts visited as the dispatch/combine einsums, for every routing variant."""
  rng = np.random.default_rng(17)
  E, D, F, k = 8, 16, 24, 3
  w_router, w_gate, w_up, w_down = _experts(rng, E, E, D, F)
  bias = jnp.asarray(rng.normal(size=(E,)) * 0.1, jnp.float32) if kwargs["scoring"] == "sigmoid" else None
  for T in (1, 2, 4):
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    _assert_same(*_both_forms(x, w_router, w_gate, w_up, w_down, k, selection_bias=bias, **kwargs))


@pytest.mark.parametrize(
  "what,T,E,k,held",
  [
    ("one token: a row tile of 16 with 13 rows of padding behind 3 groups", 1, 8, 3, None),
    ("a decode step of 64 rows: four row tiles, every expert's group inside or across them", 64, 16, 8, None),
    ("T·k no multiple of the tile: 201 rows, 55 of padding", 67, 8, 3, None),
    ("two experts: each group spans several row tiles", 200, 2, 2, None),
    ("empty groups: 4 assignments over 32 experts", 2, 32, 2, None),
    ("a held range with choices outside it, some rows with none inside", 40, 16, 2, (4, 9)),
    ("a held range nobody chose: no visit at all", 3, 16, 1, (15, 16)),
    ("unequal groups: one expert takes every token's first choice", 96, 8, 2, None),
    ("a group of exactly one tile: 128 tokens, one choice, one expert", 128, 4, 1, None),
    ("a group one row over a tile: 129 rows", 129, 4, 1, None),
    ("most choices not held: 2 of 32 experts", 80, 32, 4, (30, 32)),
  ],
)
def test_moe_grouped_form_over_tiles_groups_and_held_ranges(what, T, E, k, held, interpreted, walk):
  rng = np.random.default_rng(T * 31 + E)
  D, F = 16, 24
  E_held = E if held is None else held[1] - held[0]
  w_router, w_gate, w_up, w_down = _experts(rng, E, E_held, D, F)
  if what.startswith("a held range nobody"):
    w_router = w_router.at[:, 15].set(-w_router[:, :15].sum(axis=1))  # expert 15 scores under every other where they score high
  x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
  if what.startswith(("unequal groups", "a group of exactly", "a group one row")):
    x = jnp.abs(x)
    w_router = w_router.at[:, 1].set(10.0)  # positive tokens: expert 1 scores over every other, for every token
  ref, got = _both_forms(x, w_router, w_gate, w_up, w_down, k, held=held, norm_topk=True)
  _assert_same(ref, got)
  if what.startswith("a group"):
    assert int(got[2]) == 1
  if what.startswith("empty groups"):
    assert int(got[2]) <= 4
  if held is not None:
    whole = _both_forms(x, w_router, *_experts(rng, E, E, D, F)[1:], k, norm_topk=True)[0]
    assert int(got[2]) <= min(E_held, int(whole[2]))


def test_moe_grouped_form_leaves_garbage_rows_out_by_where(interpreted, walk):
  """Rows of an expert not held hold whatever the kernels found (here: NaN from an uninitialised output block): the
  result has none of it."""
  rng = np.random.default_rng(5)
  w_router, w_gate, w_up, w_down = _experts(rng, 16, 4, 16, 24)
  x = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
  ref, got = _both_forms(x, w_router, w_gate, w_up, w_down, 2, held=(0, 4))
  assert np.all(np.isfinite(np.asarray(got[0])))
  _assert_same(ref, got)


@pytest.mark.parametrize("T", [1, 16, 130])
def test_moe_grouped_form_takes_int8_codes_and_a_stacks_layer(T, interpreted, walk):
  """int8 leaves go into the kernels as codes, their per-output-channel scales multiply the products' rows; the leaves
  are a stack's and the layer a traced scalar. Against the block form over the dequantised layer."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_weight

  rng = np.random.default_rng(T)
  L, E, D, F, k = 3, 8, 32, 64, 2
  w_router, *ws = _experts(rng, E, E, D, F, layers=L)
  (g, sg), (u, su), (d, sd) = (quantize_weight(w) for w in ws)
  assert g.dtype == jnp.int8 and sg.shape == (L, E, F) and sd.shape == (L, E, D)
  x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
  for layer in (0, 2):
    ref, got = jax.jit(lambda layer: _both_forms(x, w_router, g, u, d, k, scales=(sg, su, sd), layer=layer, norm_topk=True))(jnp.int32(layer))
    _assert_same(ref, got, rtol=2e-5, atol=2e-5)
    alone = _both_forms(x, w_router, g[layer], u[layer], d[layer], k, scales=(sg[layer], su[layer], sd[layer]), norm_topk=True)[1]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(alone[0]), rtol=1e-6, atol=1e-6)


def test_moe_ffn_cuts_a_long_run_into_grouped_pieces(monkeypatch, interpreted, walk):
  """A run longer than ``GROUPED_MAX_TOKENS`` goes through the grouped form in pieces, by tokens: same result as the
  block form's blocks; the count of visits is summed over the pieces. ``moe_ffn`` takes the grouped form where it is
  handed a stack and a layer, the block form where it is handed a layer's leaves."""
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(moe, "GROUPED_MAX_TOKENS", 32)
  rng = np.random.default_rng(3)
  E, D, F, k, T = 8, 128, 128, 2, 80
  w_router, w_gate, w_up, w_down = _experts(rng, E, E, D, F, layers=2)
  x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
  assert moe.ffn_form(w_gate, w_down, None, True) == "grouped"
  kernels = lambda layer: str(jax.make_jaxpr(lambda x: moe.moe_ffn(x, w_router, *layer, k=k))(x)).count("pallas_call")  # noqa: E731
  assert kernels((w_gate[1], w_up[1], w_down[1])) == 0
  assert str(jax.make_jaxpr(lambda x: moe.moe_ffn(x, w_router, w_gate, w_up, w_down, k=k, layer=1))(x)).count("pallas_call") == 2 * 3  # three pieces
  got = moe.moe_ffn(x, w_router, w_gate, w_up, w_down, k=k, layer=1)
  ref = moe.moe_ffn(x, w_router, w_gate[1], w_up[1], w_down[1], k=k, chunk=32)
  np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), rtol=1e-5, atol=1e-5)
  pieces = [moe._moe_ffn_block(x[at : at + 32], w_router, w_gate[1], w_up[1], w_down[1], k, "softmax", False, None, 1.0, None, 1, 1, "none") for at in (0, 32, 64)]
  assert int(got[2]) == int(ref[2]) == sum(int(p[2]) for p in pieces)
  np.testing.assert_allclose(float(got[1]), sum(float(p[1]) * n for p, n in zip(pieces, (32, 32, 16))) / T, rtol=1e-5)


@pytest.mark.parametrize(
  "what,T,E,k,held,form",
  [
    ("unequal groups over three tiles", 100, 8, 3, None, "gated"),
    ("an expert with no row: 3 tokens over 16", 3, 16, 2, None, "gated"),
    ("a group of exactly one tile and one a row over", 257, 2, 1, None, "gated"),
    ("most choices not held", 80, 32, 4, (30, 32), "gated"),
    ("int8 codes with scales", 70, 8, 2, None, "int8"),
    ("the ungated form", 70, 8, 3, None, "ungated"),
    ("the routing handed in", 70, 8, 3, None, "routed"),
  ],
)
def test_the_aligned_walk_gives_the_shared_walks_rows_bit_for_bit(what, T, E, k, held, form, interpreted, monkeypatch):
  """Where a row lies in its tile changes nothing of its dot products, and the combine adds the same k float32 terms
  in the same order with exactly 0 for a choice not held: the two walks agree in every bit, at one tile height (a
  CPU's dot sums by the tile's height; the chip's check at the tall tile is ``scripts/moe_grouped_bench.py --check``)
  — and the aligned walk at a tile of its own (32 rows) agrees with the block form."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_weight
  from xotorch_support_jetson_tpu.ops import moe

  rng = np.random.default_rng(56)
  D, F = 32, 64
  E_held = E if held is None else held[1] - held[0]
  w_router, *ws = _experts(rng, E, E_held, D, F, layers=2)
  x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
  if what.startswith("a group of exactly"):  # a routing handed in: 128 tokens to expert 0, 129 to expert 1
    routed = moe.Routed(jnp.zeros((T, E)), jnp.ones((T, 1)), (jnp.arange(T) >= 128).astype(jnp.int32)[:, None])
  else:
    routed = moe.route(x, w_router, k, "softmax", True) if form == "routed" else None
  scales, gate = None, ws[0]
  if form == "int8":
    (gate, sg), (up, su), (down, sd) = (quantize_weight(w) for w in ws)
    ws, scales = [gate, up, down], (sg, su, sd)
  if form == "ungated":
    gate, ws = None, [None, jnp.swapaxes(ws[1], -1, -2), ws[2]]
  run = lambda: moe._moe_ffn_grouped(x, w_router, *ws, k, "softmax", True, None, 1.0, 1, 1, "none", held, scales, jnp.int32(1), "silu" if gate is not None else "relu2", routed)  # noqa: E731
  got = {}
  for walk in moe.WALKS:
    _force_walk(monkeypatch, walk)
    got[walk] = run()
  np.testing.assert_array_equal(np.asarray(got["aligned"][0]), np.asarray(got["shared"][0]))
  assert np.asarray(got["shared"][0]).any() and int(got["aligned"][2]) == int(got["shared"][2]) and float(got["aligned"][1]) == float(got["shared"][1])
  _force_walk(monkeypatch, "aligned", 32)
  np.testing.assert_allclose(np.asarray(run()[0]), np.asarray(got["shared"][0]), rtol=2e-5, atol=2e-5)


def test_the_aligned_walk_names_its_calls_and_passes_over_no_product_twice(interpreted, monkeypatch):
  """The aligned walk's Mosaic calls carry names of their own, its kernels read no output block and hold no ``where``,
  and no op but the gather that picks each token's k rows takes the float32 products [rows, D]; the gauge counts the
  call sites traced on each walk."""
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.utils.metrics import metrics

  rng = np.random.default_rng(7)
  w_router, w_gate, w_up, w_down = _experts(rng, 8, 8, 128, 128, layers=1)
  x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
  traced, before = {}, {walk: metrics.gauge_value("moe_grouped_walk", labels={"walk": walk}) or 0 for walk in moe.WALKS}
  for walk in moe.WALKS:
    _force_walk(monkeypatch, walk)
    traced[walk] = jax.make_jaxpr(lambda x: moe.moe_ffn(x, w_router, w_gate, w_up, w_down, k=2, layer=0))(x)
    assert metrics.gauge_value("moe_grouped_walk", labels={"walk": walk}) == before[walk] + 1

  def eqns(jaxpr):  # every equation, those of nested programs too (a ``jnp.take`` is a ``pjit`` of a ``gather``), a kernel's body apart
    for eqn in jaxpr.eqns:
      inner = [] if eqn.primitive.name == "pallas_call" else [v for v in eqn.params.values() if hasattr(v, "jaxpr") or hasattr(v, "eqns")]
      yield from ([eqn] if not inner else (e for v in inner for e in eqns(getattr(v, "jaxpr", v))))

  names = lambda walk: sorted(e.params["name"] for e in eqns(traced[walk].jaxpr) if e.primitive.name == "pallas_call")  # noqa: E731
  assert names("aligned") == ["moe_down_rows", "moe_gate_up_rows"] and names("shared") == ["moe_down", "moe_gate_up"]
  products = ((64 * 2 // 128 + 8) * 128, 128)  # the aligned buffer: M // tm + E_held tiles of float32 rows
  takers = [e.primitive.name for e in eqns(traced["aligned"].jaxpr) if e.primitive.name != "pallas_call" and any(getattr(v.aval, "shape", None) == products and v.aval.dtype == jnp.float32 for v in e.invars)]
  assert takers == ["gather"], takers
  for e in eqns(traced["aligned"].jaxpr):
    if e.primitive.name == "pallas_call":
      assert "select_n" not in str(e.params["jaxpr"]) and len(e.params["jaxpr"].eqns) < 16, e.params["name"]


EXPERT_CELLS = [  # (cell, decode rows, k, router's width, experts held, a full slice's or prefill piece's tokens, its tile)
  ("smallthinker", 32, 6, 64, 64, 2048, 224),  # 192 rows an expert: one tile of 224, not two of 128
  ("laguna", 64, 8, 256, 256, 2048, 96),  # 64 rows an expert
  ("ling", 64, 8, 512, 128, 8 * 512, 96),  # 64 rows an expert of the router's 512, whatever share is held
  ("moonlight", 16, 6, 64, 64, 4096, 224),  # 384 rows an expert: two tiles of 224
  ("nemotron", 64, 6, 128, 128, 4096, 224),  # 192
]


@pytest.mark.parametrize("cell,rows,k,E,E_held,full,tile", EXPERT_CELLS)
def test_the_walk_is_read_from_static_shapes(cell, rows, k, E, E_held, full, tile):
  """``grouped_walk`` — the one place the walk is chosen, a pure function of static shapes: shared at every decode shape
  of the five expert cells (16 rows up to the cell's slots) and at a short group, aligned at each cell's full slice or
  prefill piece, at the tile a group of the usual length costs least, where VMEM holds it; the threshold reads the
  share of the router's experts the shard holds."""
  import math

  from xotorch_support_jetson_tpu.ops import moe

  for T in sorted({16, 32, rows, 256}):
    walk, tm = moe.grouped_walk(T * k, E, E_held)
    assert walk == "shared" and tm == (moe.ROW_TILE if T * k >= moe.ROW_TILE else -(-T * k // 16) * 16), (cell, T)
  assert moe.grouped_walk(full * k, E, E_held) == ("aligned", tile), cell
  assert moe.grouped_walk(moe.GROUPED_MAX_TOKENS * k, E, E_held)[0] == "aligned"
  short = math.ceil(moe.ALIGNED_MIN_ROWS * math.sqrt(E_held / E) * E)  # Ling's quarter: from 32 rows an expert
  assert moe.grouped_walk(short - 1, E, E_held) == ("shared", moe.ROW_TILE) and moe.grouped_walk(short, E, E_held) == ("aligned", 96)
  big = ((8192, 8192, 2), (8192, 8192, 1))  # blocks no tile taller than the shared walk's fits beside
  assert moe.grouped_walk(192 * E, E, E) == ("aligned", 224) and moe.grouped_walk(192 * E, E, E, big, 2) == ("aligned", moe.ROW_TILE)
  assert set(moe.WALKS) == {"aligned", "shared"} and moe.ROW_TILE in moe.ALIGNED_TILES and all(tm % 16 == 0 for tm in moe.ALIGNED_TILES)


BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


@pytest.mark.parametrize(
  "what,gate,down,dtype,capacity_factor,mosaic_kernels,scaled,want",
  [
    ("Ling's held experts on a TPU", (6, 128, 2560, 768), (6, 128, 768, 2560), BF16, None, True, False, "grouped"),
    ("Moonlight's int8 codes with scales", (13, 64, 2048, 1408), (13, 64, 1408, 2048), I8, None, True, True, "grouped"),
    ("one layer's float32 leaves", (8, 128, 256), (8, 256, 128), F32, None, True, False, "grouped"),
    ("mixtral's experts: a column block of 512 of the 14336", (32, 8, 4096, 14336), (32, 8, 14336, 4096), BF16, None, True, False, "grouped"),
    ("a capacity factor: assignments may drop", (6, 128, 2560, 768), (6, 128, 768, 2560), BF16, 1.25, True, False, "block"),
    ("a plan that leaves a mesh axis to GSPMD: no Mosaic kernel", (6, 128, 2560, 768), (6, 128, 768, 2560), BF16, None, False, False, "block"),
    ("int8 codes without scales", (13, 64, 2048, 1408), (13, 64, 1408, 2048), I8, None, True, False, "block"),
    ("packed int4: half the rows", (13, 64, 1024, 1408), (13, 64, 704, 2048), I8, None, True, True, "block"),
    ("the tests' widths: no whole lane group", (4, 64, 32), (4, 32, 64), F32, None, True, False, "block"),
    ("bfloat16 leaves with scales", (4, 128, 128), (4, 128, 128), BF16, None, True, True, "block"),
  ],
)
def test_the_expert_form_is_read_from_what_the_program_sees(what, gate, down, dtype, capacity_factor, mosaic_kernels, scaled, want, monkeypatch):
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  w_gate, w_down = jax.ShapeDtypeStruct(gate, dtype), jax.ShapeDtypeStruct(down, dtype)
  assert moe.ffn_form(w_gate, w_down, capacity_factor, mosaic_kernels, scaled) == want, what
  assert want in moe.FFN_FORMS


@pytest.mark.parametrize("platform,mosaic_kernels,want", [("tpu", True, "grouped"), ("tpu", False, "block"), ("cpu", True, "block"), ("gpu", True, "block")])
def test_the_expert_form_follows_the_platform_and_the_plan(platform, mosaic_kernels, want, monkeypatch):
  """The experts' kernels are for a TPU, and for a plan with no mesh axis left to GSPMD (the engine clears
  ``mosaic_kernels`` for such a plan): the layer loops and the gauge ask with the config's flag."""
  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(jax, "default_backend", lambda: platform)
  cfg = _moe_cfg(dim=128, moe_hidden_dim=128, mosaic_kernels=mosaic_kernels)
  leaf = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
  stack = {"w_experts_gate": leaf(3, 4, 128, 128), "w_experts_up": leaf(3, 4, 128, 128), "w_experts_down": leaf(3, 4, 128, 128), "w_router": leaf(3, 128, 4)}
  assert moe.ffn_form(stack["w_experts_gate"], stack["w_experts_down"], cfg.moe_capacity_factor, cfg.mosaic_kernels) == want
  assert decoder.served_expert_form({"moe_layers": stack}, cfg) == want
  assert decoder._whole_expert_leaves({"moe_layers": stack}, cfg) == (("w_experts_gate", "w_experts_up", "w_experts_down") if want == "grouped" else ())
  assert decoder.served_expert_form(None, cfg) == "block"  # a ring holds its weights itself: its loops hand no stack over


def test_nothing_of_the_gather_path_is_left():
  import os

  from xotorch_support_jetson_tpu.ops import moe

  assert not hasattr(moe, "_moe_ffn_gather") and not hasattr(moe, "MOE_GATHER_MAX")
  assert "XOT_TPU_MOE_GATHER" not in open(moe.__file__).read() and "XOT_TPU_MOE_GATHER" not in os.environ


def _lane_wide_moe(quant: bool):
  """A tiny MoE decoder whose expert faces are whole lane groups (so the predicate takes the grouped form once the
  program is told it may), its weights, and prompts."""
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  cfg = _moe_cfg(dim=128, moe_hidden_dim=128, n_experts=8, n_active_experts=2, shared_expert_dim=32, max_seq_len=64 + int(quant))
  params, shard = full_model_params(jax.random.PRNGKey(21), cfg, "moe-lanes")
  return cfg, (quantize_params(params) if quant else params), shard


@pytest.mark.parametrize("quant", [False, True], ids=["float32", "int8"])
def test_moe_decoder_programs_take_the_grouped_form_where_told(quant, monkeypatch):
  """The prefill over a cache and the paged decode chunk of a dense-prefix + experts model, once with the block form
  (what a CPU resolves) and once with the experts' kernels interpreted (``INTERPRET``): the same logits, the same
  greedy tokens, the same count of expert visits — and the layer loops hand the stacked expert leaves over whole."""
  from dataclasses import replace

  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  cfg, params, shard = _lane_wide_moe(quant)
  assert decoder._whole_expert_leaves(params, cfg) == ()
  tokens = jnp.asarray([[5, 9, 2, 7, 1, 3, 8, 4], [11, 12, 13, 14, 15, 16, 17, 18]], dtype=jnp.int32)
  positions = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32), (2, 8))
  PS = 8
  bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

  def run(cfg):
    logits, _ = shard_forward(params, cfg, shard, tokens, positions, init_kv_cache(cfg, cfg.n_layers, 2, 16))
    pool = init_paged_pool(cfg, cfg.n_layers, 5, PS)
    toks, _, _, _, seen = decoder.fused_paged_batch_decode(
      params, cfg, shard, tokens[:, :1], pool, bt, jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool), jnp.zeros((2,), jnp.float32), 4, page_size=PS, use_kernel=False, experts_visited=True
    )
    return np.asarray(logits, np.float32), np.asarray(toks), int(seen)

  ref_logits, ref_toks, ref_seen = run(cfg)
  monkeypatch.setattr(moe, "INTERPRET", True)
  told = replace(cfg, max_seq_len=cfg.max_seq_len + 2)  # another static config: the programs are traced anew
  whole = decoder._whole_expert_leaves(params, told)
  assert set(whole) == {name for name in params["moe_layers"] if name.startswith("w_experts_")} and len(whole) == (6 if quant else 3)
  got_logits, got_toks, got_seen = run(told)
  np.testing.assert_allclose(got_logits, ref_logits, rtol=2e-4, atol=2e-4)
  assert got_toks.tolist() == ref_toks.tolist()
  assert got_seen == ref_seen and 3 * 4 * 2 <= got_seen <= 3 * 4 * 4  # 3 expert layers x 4 steps, 2 rows x top 2


def test_a_differentiated_forward_keeps_the_block_form(monkeypatch):
  """Training and the cache-less forward hand no stack over whole: where the served programs take the grouped form
  (here: interpreted), ``shard_forward`` without a cache and ``shard_forward_aux`` under ``jax.grad`` trace no kernel
  — a ``pallas_call`` with scalar prefetch has no derivative — and give the block form's numbers."""
  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.ops import moe

  cfg, params, shard = _lane_wide_moe(False)
  tokens = jnp.asarray([[5, 9, 2, 7, 1, 3, 8, 4]], dtype=jnp.int32)
  positions = jnp.arange(8, dtype=jnp.int32)[None]

  def loss(params):
    logits, aux = decoder.shard_forward_aux(params, cfg, shard, tokens, positions)
    return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)) + 0.01 * aux

  ref_loss, ref_grad = jax.value_and_grad(loss)(params)
  ref_logits = shard_forward(params, cfg, shard, tokens, positions)[0]
  monkeypatch.setattr(moe, "INTERPRET", True)
  assert decoder.served_expert_form(params, cfg) == "grouped"
  assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(loss))(params))
  assert "pallas_call" not in str(jax.make_jaxpr(lambda p: shard_forward(p, cfg, shard, tokens, positions)[0])(params))
  got_loss, got_grad = jax.value_and_grad(loss)(params)
  assert float(got_loss) == float(ref_loss)
  np.testing.assert_array_equal(np.asarray(got_grad["moe_layers"]["w_experts_down"]), np.asarray(ref_grad["moe_layers"]["w_experts_down"]))
  assert float(jnp.abs(got_grad["moe_layers"]["w_experts_down"]).max()) > 0
  np.testing.assert_array_equal(np.asarray(shard_forward(params, cfg, shard, tokens, positions)[0]), np.asarray(ref_logits))


# ------------------------------------------------------------ an expert of two matrices (ISSUE 53)


def _ungated(rng, E, D, F, dtype=jnp.float32):
  """(router [D, E], up stored [E, F, D] as the program keeps an ungated expert's first matrix, down [E, F, D])."""
  w = lambda *shape: jnp.asarray(rng.normal(size=shape) * 0.1, dtype)  # noqa: E731
  return jnp.asarray(rng.normal(size=(D, E)), jnp.float32), w(E, F, D), w(E, F, D)


def _ungated_forms(x, w_router, w_up_t, w_down, k, act="relu2", routed=None, **routing):
  from xotorch_support_jetson_tpu.ops.moe import _moe_ffn_block, _moe_ffn_grouped

  full = dict(scoring="sigmoid", norm_topk=True, selection_bias=None, scale=2.5, n_group=1, topk_group=1, group_mode="none")
  full.update(routing)
  ref = _moe_ffn_block(x, w_router, None, w_up_t, w_down, k, capacity_factor=None, act=act, routed=routed, **full)
  got = _moe_ffn_grouped(x, w_router, None, w_up_t[None], w_down[None], k, layer=0, act=act, routed=routed, **full)
  return ref, got


@pytest.mark.parametrize("act", ["relu2", "relu", "silu"])
@pytest.mark.parametrize("T", [1, 5, 40])
def test_an_ungated_experts_two_forms_equal_a_per_token_loop(act, T, interpreted, walk):
  """``w_gate`` None: W_down act(W_up x) with both matrices stored [F, D]. The block form's two einsums, the grouped
  form's ``moe_up`` (a transposed contraction against the whole [F, D] block, the nonlinearity in the kernel) and
  ``moe_down``, and a loop over each token's chosen experts in numpy agree; relu² squares in float32. F = 24 is no
  whole number of lanes, as the published 1856 is none."""
  from xotorch_support_jetson_tpu.ops.moe import EXPERT_ACTS, router_topk

  rng = np.random.default_rng(53)
  E, D, F, k = 8, 16, 24, 3
  w_router, w_up_t, w_down = _ungated(rng, E, D, F)
  bias = jnp.asarray(rng.normal(size=(E,)) * 0.1, jnp.float32)
  x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
  ref, got = _ungated_forms(x, w_router, w_up_t, w_down, k, act=act, selection_bias=bias)
  _assert_same(ref, got)
  weights, idx = router_topk(x @ w_router, k, "sigmoid", True, bias, 2.5)
  want = np.zeros((T, D), np.float32)
  for t in range(T):
    for w, e in zip(np.asarray(weights[t]), np.asarray(idx[t])):
      want[t] += w * np.asarray(EXPERT_ACTS[act](x[t] @ w_up_t[e].T) @ w_down[e])
  np.testing.assert_allclose(np.asarray(ref[0]), want, rtol=1e-5, atol=1e-5)
  assert act != "relu2" or np.allclose(np.asarray(EXPERT_ACTS["relu2"](jnp.asarray([-2.0, 3.0]))), [0.0, 9.0])


def test_an_ungated_expert_layers_padded_row_chooses_no_expert(interpreted, walk):
  """A routing drawn elsewhere whose last row chose no expert at all (an id past the last: the padding of a long run's
  last block) adds nothing for that row and visits no expert for it, in both forms."""
  from xotorch_support_jetson_tpu.ops.moe import Routed, route

  rng = np.random.default_rng(54)
  E, D, F, k = 8, 16, 24, 2
  w_router, w_up_t, w_down = _ungated(rng, E, D, F)
  x = jnp.asarray(rng.normal(size=(3, D)), jnp.float32)
  drawn = route(x, w_router, k, "sigmoid", True, None, 2.5)
  padded = Routed(drawn.logits, drawn.weights, drawn.idx.at[2].set(E))
  ref, got = _ungated_forms(x, w_router, w_up_t, w_down, k, routed=padded)
  _assert_same(ref, got)
  assert not np.asarray(ref[0][2]).any() and not np.asarray(got[0][2]).any() and np.asarray(ref[0][:2]).any()
  assert int(got[2]) == len(set(np.asarray(drawn.idx[:2]).ravel().tolist()))


@pytest.mark.parametrize(
  "what,first,down,dtype,scaled,want",
  [
    ("nemotron_h's experts: both matrices [1856, 2688], the first ONE 9.98 MB block a visit", (3, 128, 1856, 2688), (3, 128, 1856, 2688), BF16, False, "grouped"),
    ("an inner width that is no whole number of packed sublanes", (3, 8, 1848, 2688), (3, 8, 1848, 2688), BF16, False, "block"),
    ("a first matrix that does not fit VMEM twice over", (3, 8, 4096, 4096), (3, 8, 4096, 4096), BF16, False, "block"),
    ("int8 codes: the up-only kernel takes none", (3, 8, 1856, 2688), (3, 8, 1856, 2688), I8, True, "block"),
    ("the tests' widths: D no whole lane group", (4, 24, 64), (4, 24, 64), F32, False, "block"),
  ],
)
def test_an_ungated_experts_form_is_read_from_its_two_f_by_d_leaves(what, first, down, dtype, scaled, want, monkeypatch):
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  assert moe.ffn_form(jax.ShapeDtypeStruct(first, dtype), jax.ShapeDtypeStruct(down, dtype), None, True, scaled, gated=False) == want, what
