"""The q/k/v projections keep their flat activations behind a barrier (ISSUE 33).

``models/decoder.py _dense_qkv`` makes three dots on ``wq``/``wk``/``wv`` and
holds their outputs behind ``jax.lax.optimization_barrier`` before the head
reshape. On the chip that one line is worth 1.5 ms of a 14.6 ms Mistral-7B
decode step: without it XLA:TPU folds the reshape into the dot and relays the
weights to suit (PERF.md §6, PR 33; the TPU compiler's verdict is pinned in
``tests/test_tpu_compile.py``). What is pinned here, on the CPU:

- the barrier changes no value: the projection with it equals the projection
  without it bit for bit — bf16, int8 (w8a16 and w8a8) and packed int4
  weights, GQA groups of 1, 4 and 7, head widths 64 and 128, with biases,
  ``q_norm``, a LoRA and per-row multi-LoRA deltas on ``q`` and ``v``;
- it changes no gradient (training runs the same function);
- every traced program that projects q/k/v holds it once per layer body, on
  the three flat activations, and joins no weight-sized arrays; an MLA
  program, which projects through latents, holds none;
- under a ``tp`` mesh (KV heads divisible by ``tp`` or not) the paged decode
  yields the unsharded run's tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_paged_pool_inplace import _bits, _eqns
from xotorch_support_jetson_tpu.models import decoder
from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import full_model_params, fused_paged_batch_decode, prefill_into_pages_many, shard_forward
from xotorch_support_jetson_tpu.models.quantize import quantize_params
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool
from xotorch_support_jetson_tpu.ops.rope import rope_inv_freq

KEY = jax.random.PRNGKey(33)
MLA = dict(n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
MOE = dict(n_experts=4, n_active_experts=2, moe_hidden_dim=32)


@pytest.fixture
def no_barrier(monkeypatch):
  """Within the test, programs traced anew hold no barrier."""
  monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)


# ------------------------------------------------------------ no value moves


def _layer_inputs(cfg, mode: str, rank: int = 4, slots: int = 3):
  """One layer's parameters — weights in ``mode``, biases, q/k norms, a LoRA
  and a stack of per-row adapters on ``wq`` and ``wv`` — and an activation."""
  rng = np.random.default_rng(33)
  D = cfg.dim
  normal = lambda *shape, scale=1.0: jnp.asarray(rng.normal(size=shape) * scale, cfg.dtype)  # noqa: E731
  p = {"q_norm": jnp.asarray(rng.uniform(0.5, 1.5, cfg.head_dim), cfg.dtype), "k_norm": jnp.asarray(rng.uniform(0.5, 1.5, cfg.head_dim), cfg.dtype)}
  stack = {name: normal(1, D, n, scale=D**-0.5) for name, n in (("wq", cfg.q_dim), ("wk", cfg.kv_dim), ("wv", cfg.kv_dim))}
  if mode != "bf16":
    stack = quantize_params({"layers": stack}, mode)["layers"]
  p.update({k: v[0] for k, v in stack.items()})
  for name, n in (("wq", cfg.q_dim), ("wk", cfg.kv_dim), ("wv", cfg.kv_dim)):
    p["b" + name[1]] = normal(n)
  for t, n in (("wq", cfg.q_dim), ("wv", cfg.kv_dim)):
    p[f"{t}_lora_a"], p[f"{t}_lora_b"] = normal(D, rank, scale=0.1), normal(rank, n, scale=0.1)
    p[f"{t}_alora_a"] = normal(slots, D, rank, scale=0.1).at[0].set(0)
    p[f"{t}_alora_b"] = normal(slots, rank, n, scale=0.1).at[0].set(0)
  return p, normal(3, 5, D)


@pytest.mark.parametrize("mode", ["bf16", "int8-w8a16", "int8-w8a8", "int4"])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("heads", [(4, 4), (8, 2), (7, 1)], ids=["G1", "G4", "G7"])
def test_barrier_moves_no_bit_of_the_projection(heads, hd, mode, monkeypatch):
  quant, _, compute = mode.partition("-")
  cfg = tiny_test_config(n_heads=heads[0], n_kv_heads=heads[1], head_dim=hd, dim=128, n_layers=1, dtype=jnp.bfloat16, qkv_bias=True, qk_norm=True, quant_compute=compute or "w8a16")
  positions = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32)[None], (3, 5))
  ids = jnp.asarray([2, 0, 1], jnp.int32)
  p, x = _layer_inputs(cfg, quant)
  assert ("wq_scale" in p) == (quant != "bf16") and p["wq"].shape[0] == (cfg.dim // 2 if quant == "int4" else cfg.dim)

  def project(p):
    # a fresh function each time: jit must trace anew to see the patched barrier
    return jax.jit(lambda p, x: decoder._dense_qkv(x, p, cfg, positions, rope_inv_freq(cfg), ids))(p, x)

  got = project(p)
  base = project({k: v for k, v in p.items() if "lora" not in k})
  monkeypatch.setattr(jax.lax, "optimization_barrier", lambda t: t)
  want = project(p)
  for name, w, g, n in zip("qkv", want, got, (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)):
    assert g.shape == (3, 5, n, hd) and w.dtype == g.dtype
    assert float(jnp.max(jnp.abs(w.astype(jnp.float32)))) > 1.0, name  # not a comparison of zeros
    assert np.array_equal(_bits(w), _bits(g)), f"{name}: {int(np.sum(_bits(w) != _bits(g)))} of {w.size} elements differ"
  assert not np.array_equal(_bits(base[0]), _bits(got[0])) and not np.array_equal(_bits(base[2]), _bits(got[2]))  # the adapters add to q and v behind the barrier
  assert np.array_equal(_bits(base[1]), _bits(got[1]))  # and not to k


def _loss_and_grads(cfg, params, remat: bool):
  """Loss and gradients through training's layer path (``parallel/pipeline.py run_layer_stack``)."""
  from xotorch_support_jetson_tpu.parallel.pipeline import run_layer_stack

  h = jax.random.normal(KEY, (2, 6, cfg.dim), cfg.dtype)
  positions = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (2, 6))
  loss = lambda layers: jnp.mean(run_layer_stack(layers, h, positions, rope_inv_freq(cfg), cfg, remat=remat).astype(jnp.float32) ** 2)  # noqa: E731
  return jax.jit(jax.value_and_grad(loss))(params["layers"])


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_barrier_moves_no_gradient(remat, monkeypatch):
  """Training differentiates ``_dense_qkv`` (``train/``): loss and every
  leaf's gradient are what they are without the barrier."""
  cfg = tiny_test_config(n_layers=2, qkv_bias=True)
  params, _ = full_model_params(KEY, cfg, "m")
  loss, grads = _loss_and_grads(cfg, params, remat)
  monkeypatch.setattr(jax.lax, "optimization_barrier", lambda t: t)
  want_loss, want = _loss_and_grads(cfg, params, remat)
  assert float(loss) == float(want_loss)
  for name in ("wq", "wk", "wv", "bq", "bk", "bv", "wo", "attn_norm"):
    g = np.asarray(grads[name])
    assert np.any(g != 0), name
    np.testing.assert_array_equal(g, np.asarray(want[name]), err_msg=name)


# ------------------------------------------------------------ the traced programs


def _paged_args(cfg, shard, params, B=3, ps=16, mp=4):
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * mp, ps, quant="int8")
  bt = jnp.arange(1, 1 + B * mp, dtype=jnp.int32).reshape(B, mp)
  rows = lambda dtype, fill=0: jnp.full((B,), fill, dtype)  # noqa: E731
  return (params, cfg, shard, jnp.ones((B, 1), jnp.int32), pool, bt, rows(jnp.int32, 5), rows(bool, True), rows(jnp.float32), rows(jnp.int32, 8), 4, 8, ps, False, jax.random.PRNGKey(0), None)


def _traced(fn, args, kwargs=None):
  """(dot counts by weight shape, barrier operand shapes, weight-sized concatenations) of a traced program."""
  static = tuple(i for i, a in enumerate(args) if not hasattr(a, "shape") and not isinstance(a, dict) and a is not None)
  jaxpr = jax.make_jaxpr(fn, static_argnums=static)(*args, **(kwargs or {})).jaxpr
  dots, barriers, joined = {}, [], []
  for eqn in _eqns(jaxpr):
    if eqn.primitive.name == "dot_general":
      rhs = tuple(eqn.invars[1].aval.shape)
      dots[rhs] = dots.get(rhs, 0) + 1
    elif eqn.primitive.name == "optimization_barrier":
      barriers.append([tuple(v.aval.shape) for v in eqn.invars])
    elif eqn.primitive.name == "concatenate":
      joined.append(tuple(eqn.outvars[0].aval.shape))
  return dots, barriers, joined


def _odd_cfg(**kw):
  """A dense-GQA config in which every projection of a layer has a shape of its own."""
  cfg = tiny_test_config(n_layers=3, n_heads=4, n_kv_heads=1, head_dim=32, hidden_dim=160, max_seq_len=64, **kw)
  D, Qd, Kd = cfg.dim, cfg.q_dim, cfg.kv_dim
  assert len({(D, Qd), (D, Kd), (Qd, D), (D, cfg.hidden_dim), (cfg.hidden_dim, D)}) == 5
  return cfg


@pytest.mark.parametrize("program", ["decode.paged_batch", "decode.mixed_paged_batch", "forward"])
def test_programs_hold_the_barrier_once_a_layer_body(program):
  """The layer body is traced once (a scan): three q/k/v dots, ONE barrier on
  their three flat activations, and no concatenation as large as a weight."""
  cfg = _odd_cfg()
  D, Qd, Kd = cfg.dim, cfg.q_dim, cfg.kv_dim
  params, shard = full_model_params(KEY, cfg, "m")
  params = quantize_params(params)
  if program == "forward":
    tokens = jnp.ones((2, 6), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), tokens.shape)
    dots, barriers, joined = _traced(lambda p, t, pos: shard_forward(p, cfg, shard, t, pos, None), (params, tokens, positions))
    rows = [(2, 6)]
  else:
    args = _paged_args(cfg, shard, params)
    if program == "decode.paged_batch":
      fn, rows = decoder._fused_paged_batch_decode_impl.xot_jitted, [(3, 1)]
    else:
      pad = 16
      args = args[:10] + (jnp.ones((1, pad), jnp.int32), jnp.arange(1, 5, dtype=jnp.int32)[None], jnp.zeros((1,), jnp.int32), jnp.full((1,), 5, jnp.int32)) + args[10:] + (None,)
      fn, rows = decoder._fused_mixed_paged_batch_decode_impl.xot_jitted, [(3, 1), (1, pad)]  # the decode rows' body and the prefill slice's
    dots, barriers, joined = _traced(fn, args)
  assert dots.get((D, Qd)) == len(rows) and dots.get((D, Kd)) == 2 * len(rows), dots
  assert sorted(barriers) == sorted([(*r, Qd), (*r, Kd), (*r, Kd)] for r in rows), barriers
  assert not [s for s in joined if len(s) >= 2 and s[-2] == D], joined  # nothing joins arrays of a weight's shape [.., D, N]


def test_an_mla_program_holds_no_barrier():
  """MLA projects through latents (``_mla_latents``), never ``_dense_qkv``:
  its programs are what they were (on the chip: Moonlight's optimised HLO
  equals the parent's, PERF.md §6)."""
  cfg = tiny_test_config(n_layers=3, first_k_dense=1, **MLA, **MOE)
  params, shard = full_model_params(KEY, cfg, "m")
  _, barriers, _ = _traced(decoder._fused_paged_batch_decode_impl.xot_jitted, _paged_args(cfg, shard, quantize_params(params)))
  assert barriers == []


def test_without_the_barrier_the_counter_counts(no_barrier):
  cfg = _odd_cfg(norm_eps=3.3e-5)  # a config of its own: no other test may be handed this barrier-less trace from jit's cache
  params, shard = full_model_params(KEY, cfg, "m")
  dots, barriers, _ = _traced(decoder._fused_paged_batch_decode_impl.xot_jitted, _paged_args(cfg, shard, quantize_params(params)))
  assert barriers == [] and dots.get((cfg.dim, cfg.q_dim)) == 1


# ------------------------------------------------------------ under a tp mesh


def _greedy_paged(params, cfg, shard, n_steps: int = 6):
  """Three prompts prefilled into pages, then ``decode.paged_batch``."""
  ps, mp = 16, 4
  prompts = [[3, 25, 9], [7, 1, 88, 42, 5], [100, 4]]
  B = len(prompts)
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * mp, ps, quant="int8")
  bt = jnp.arange(1, 1 + B * mp, dtype=jnp.int32).reshape(B, mp)
  toks = np.zeros((B, 16), np.int32)
  for r, p in enumerate(prompts):
    toks[r, : len(p)] = p
  lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
  last, pool = prefill_into_pages_many(params, cfg, shard, jnp.asarray(toks), pool, bt, jnp.zeros((B,), jnp.int32), lens, ps)
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  out, _, _, _ = fused_paged_batch_decode(params, cfg, shard, first, pool, bt, lens, jnp.ones((B,), bool), jnp.zeros((B,), jnp.float32), n_steps, page_size=ps, use_kernel=False)
  return np.concatenate([np.asarray(first), np.asarray(out)], axis=1)


@pytest.mark.parametrize("tp,kv_heads", [(2, 4), (4, 2)], ids=["tp2-divides-Hkv", "tp4-over-2-kv-heads"])
def test_tp_mesh_decodes_the_unsharded_tokens(tp, kv_heads):
  """GSPMD partitions through the barrier: ``wq``/``wk``/``wv`` column-sharded
  over ``tp`` — whole KV heads a device, or (4 ways over 2) split heads."""
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh, shard_params
  from xotorch_support_jetson_tpu.utils.synthetic import peaked_echo_params

  cfg = tiny_test_config(n_layers=2, n_heads=8, n_kv_heads=kv_heads, dim=128, qkv_bias=True)
  params, shard = full_model_params(KEY, cfg, "m")
  params = quantize_params(peaked_echo_params(params))
  want = _greedy_paged(params, cfg, shard)
  mesh = build_mesh(MeshPlan(tp=tp), jax.devices()[:tp])
  placed = shard_params(params, mesh)
  assert placed["layers"]["wk"].sharding.spec[-1] == "tp"
  with mesh:
    got = _greedy_paged(placed, cfg, shard)
  assert np.array_equal(got, want), (got, want)
