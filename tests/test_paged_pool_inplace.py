"""The page pool is a loop carry, written in place and addressed by (layer, page) (ISSUE 29).

Before PR 29 the stacked pool ``[L, P, Hkv, ps, hd]`` went through the layer
loop as a scan's ``xs``/``ys``: every decode step sliced each layer's whole
pool out and wrote the whole layer back. Now the layer loop carries the
stacked pool, a token write touches ``(layer, page, :, slot)`` alone, and the
attention reads its pages by ``(layer, page)``. Three claims:

- the bytes are the parent's: after N steps every pool leaf equals, bit for
  bit, what the tree of PR 26 wrote, and so do the tokens
  (``tests/data/paged_pool_pr26.npz``, recorded from that tree with
  ``python tests/test_paged_pool_inplace.py --record`` before anything moved);
- the Pallas kernel on a stacked pool with a layer scalar reads that layer;
- the traced ``decode.paged_batch`` holds no pool-shaped scan ``xs``/``ys``,
  no ``concatenate`` of pool leaves and no slice of a whole layer's pool, so
  the old form cannot come back unnoticed.
"""

import os
import pathlib
import sys
from unittest import mock

if __name__ == "__main__":  # record mode runs outside pytest: the suite's platform and device count, set before jax loads
  import conftest  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import (
  full_model_params,
  fused_mixed_paged_batch_decode,
  fused_paged_batch_decode,
  paged_window_forward,
  prefill_into_pages_many,
)
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

RECORDED = pathlib.Path(__file__).parent / "data" / "paged_pool_pr26.npz"
KEY = jax.random.PRNGKey(29)
PS = 16
MAX_SEQ = 64
MP = MAX_SEQ // PS
PROMPTS = [[3, 25, 9], [7, 1, 88, 42, 5] + [11] * 14, [100, 4]]  # the middle row's decode crosses a page boundary
MLA = dict(n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
MOE = dict(n_experts=4, n_active_experts=2, moe_hidden_dim=32)


def _prefilled(cfg, quant, n_pages=None):
  """Three rows with their prompts prefilled into private pages 1.. of a fresh pool."""
  params, shard = full_model_params(KEY, cfg, "m")
  B = len(PROMPTS)
  pool = init_paged_pool(cfg, shard.n_shard_layers, n_pages or 1 + B * MP, PS, quant=quant)
  bt = np.arange(1, 1 + B * MP, dtype=np.int32).reshape(B, MP)
  toks = np.zeros((B, 32), np.int32)
  lens = np.asarray([len(p) for p in PROMPTS], np.int32)
  for r, p in enumerate(PROMPTS):
    toks[r, : len(p)] = p
  last, pool = prefill_into_pages_many(params, cfg, shard, jnp.asarray(toks), pool, jnp.asarray(bt), jnp.zeros((B,), jnp.int32), jnp.asarray(lens), PS)
  first = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
  return params, shard, pool, jnp.asarray(bt), first, jnp.asarray(lens)


def _bits(x) -> np.ndarray:
  """An array as numpy can store and compare it: bf16 (no numpy dtype of its own) as its 16-bit patterns."""
  x = np.asarray(x)
  return x.view(np.uint16) if x.dtype == jnp.bfloat16 else x


def _leaves(pool) -> dict:
  return {f"pool.{name}": _bits(leaf) for name, leaf in pool.items()}


def _decode(cfg, quant, active=(True, True, True)):
  """Two dispatches of four greedy steps through ``decode.paged_batch`` (the gather path: the CPU's)."""
  params, shard, pool, bt, tok, pos = _prefilled(cfg, quant)
  active = jnp.asarray(active)
  temps = jnp.zeros((len(PROMPTS),), jnp.float32)
  out = []
  for _ in range(2):
    toks, tok, pos, pool = fused_paged_batch_decode(params, cfg, shard, tok, pool, bt, pos, active, temps, 4, page_size=PS, use_kernel=False)
    out.append(np.asarray(toks))
  return {"tokens": np.concatenate(out, axis=1), "positions": np.asarray(pos), **_leaves(pool)}


def _mixed(cfg, quant):
  """``decode.mixed_paged_batch``: rows 0 and 1 decode while row 2's pages take a prefill slice."""
  params, shard, pool, bt, tok, pos = _prefilled(cfg, quant)
  pf = np.zeros((1, 16), np.int32)
  pf[0, :9] = [5, 6, 7, 8, 9, 10, 11, 12, 13]
  toks, tok, pos, pool = fused_mixed_paged_batch_decode(
    params, cfg, shard, tok, pool, bt, pos, jnp.asarray([True, True, False]), jnp.zeros((3,), jnp.float32),
    jnp.asarray(pf), bt[2:3], jnp.asarray([2], jnp.int32), jnp.asarray([11], jnp.int32), 4, page_size=PS, use_kernel=False,
  )
  return {"tokens": np.asarray(toks), "positions": np.asarray(pos), **_leaves(pool)}


def _window(cfg, quant):
  """The batched speculation verify: a three-token window a row, written and attended in one forward."""
  params, shard, pool, bt, tok, pos = _prefilled(cfg, quant)
  window = jnp.concatenate([tok, jnp.asarray([[17, 40], [2, 91], [64, 8]], jnp.int32)], axis=1)
  wpos = pos[:, None] + jnp.arange(3, dtype=jnp.int32)[None, :]
  logits, pool = paged_window_forward(params, cfg, shard, window, wpos, pool, bt, PS, use_kernel=False)
  return {"tokens": np.asarray(jnp.argmax(logits, axis=-1)), **_leaves(pool)}


def _pp_batch(cfg, quant):
  """``pp.paged_decode`` over two stages of a dense-prefix model: the prefix scan and the stage scan."""
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, build_mesh
  from xotorch_support_jetson_tpu.parallel.pp_batch import PPBatchedServing

  prompts = PROMPTS + [[9, 9, 9, 1]]
  params, shard = full_model_params(KEY, cfg, "m")
  with mock.patch.dict(os.environ, {"XOT_TPU_KV_QUANT": quant}):  # the stage programs are keyed off it at construction
    ppb = PPBatchedServing(build_mesh(MeshPlan(pp=2)), cfg, params, 2)
  pool = ppb.place_pool(init_paged_pool(cfg, shard.n_shard_layers, 1 + len(prompts) * MP, PS, quant=quant))
  bt = np.arange(1, 1 + len(prompts) * MP, dtype=np.int32).reshape(len(prompts), MP)
  firsts = []
  for r, p in enumerate(prompts):
    pad = np.zeros((1, 32), np.int32)
    pad[0, : len(p)] = p
    last, pool = ppb.prefill_into_pages(jnp.asarray(pad), pool, bt[r], 0, len(p), PS)
    firsts.append(int(np.argmax(np.asarray(last)[0])))
  tok = jnp.asarray(firsts, jnp.int32)[:, None]
  pos = jnp.asarray([len(p) for p in prompts], jnp.int32)
  toks, _, pos, pool = ppb.paged_batch_decode(tok, pool, jnp.asarray(bt), pos, jnp.asarray([True, True, False, True]), jnp.zeros((4,), jnp.float32), jnp.full((4,), 35, jnp.int32), 5, page_size=PS)
  return {"tokens": np.asarray(toks), "positions": np.asarray(pos), **_leaves(pool)}


def _cfg(**overrides):
  return tiny_test_config(**{"n_layers": 3, "max_seq_len": MAX_SEQ, **overrides})


CASES = {
  "decode-bf16": lambda: _decode(_cfg(dtype=jnp.bfloat16), ""),
  "decode-f32": lambda: _decode(_cfg(), ""),
  "decode-int8": lambda: _decode(_cfg(), "int8"),
  "decode-int4": lambda: _decode(_cfg(), "int4"),
  "decode-mla-two-stacks": lambda: _decode(_cfg(first_k_dense=1, **MLA, **MOE), ""),
  "decode-gqa-two-stacks-int8": lambda: _decode(_cfg(first_k_dense=2, **MOE), "int8"),
  "decode-gemma2-window-softcap": lambda: _decode(_cfg(sliding_window=4, attn_logit_softcap=50.0, final_logit_softcap=30.0), "int8"),
  "decode-inactive-row-int8": lambda: _decode(_cfg(), "int8", active=(True, False, True)),
  "mixed-int8": lambda: _mixed(_cfg(), "int8"),
  "mixed-bf16": lambda: _mixed(_cfg(dtype=jnp.bfloat16), ""),
  "window-int8": lambda: _window(_cfg(), "int8"),
  "window-int4": lambda: _window(_cfg(), "int4"),
  "window-f32": lambda: _window(_cfg(), ""),
  "pp-batch-dense-prefix": lambda: _pp_batch(_cfg(n_layers=6, first_k_dense=2, **MOE), ""),
  "pp-batch-int8": lambda: _pp_batch(_cfg(n_layers=4), "int8"),
}


@pytest.fixture(scope="module")
def recorded():
  with np.load(RECORDED) as data:
    return {name: data[name] for name in data.files}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pool_and_tokens_equal_the_parents(case, recorded):
  got = CASES[case]()
  want = {name.split("/", 1)[1]: value for name, value in recorded.items() if name.startswith(case + "/")}
  assert sorted(got) == sorted(want)
  for name in sorted(got):
    assert got[name].dtype == want[name].dtype and got[name].shape == want[name].shape, name
    # Bit for bit: a float leaf is compared as its bytes, so that -0.0 is not 0.0.
    same = np.array_equal(got[name].view(np.uint8), want[name].view(np.uint8))
    assert same, f"{case}: {name} differs from what PR 26's tree wrote in {int(np.sum(got[name] != want[name]))} of {got[name].size} elements"


# ------------------------------------------------------------ the kernels on a stacked pool


def _stacked_pools(quant: str, L=5, P=9, Hkv=2, hd=32, seed=7):
  """Random stacked leaves (every layer different) and a new token a row, in the pool's dtypes. ``"pairs"``: float
  heads of 64 as the pool stores them since ISSUE 58, two a lane group ([L, P, Hkv/2, PS, 128]); the token's K/V come
  as the layer step hands them, [B, Hkv, 64]."""
  rng = np.random.default_rng(seed)
  if quant == "pairs":
    val = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pool = {"k": val(L, P, Hkv // 2, PS, 128), "v": val(L, P, Hkv // 2, PS, 128)}
    return pool, {"k": val(3, Hkv, 64), "v": val(3, Hkv, 64)}, jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32), rng
  kd = hd // 2 if quant == "int4" else hd
  if quant:
    code = lambda *shape: jnp.asarray(rng.integers(-128, 128, size=shape), jnp.int8)  # noqa: E731
    scale = lambda *shape: jnp.asarray(rng.uniform(0.01, 0.1, size=shape), jnp.float32)  # noqa: E731
    pool = {"k": code(L, P, Hkv, PS, kd), "v": code(L, P, Hkv, PS, kd), "k_scale": scale(L, P, Hkv, PS, 1), "v_scale": scale(L, P, Hkv, PS, 1)}
    new = {"k": code(3, Hkv, kd), "v": code(3, Hkv, kd), "k_scale": scale(3, Hkv, 1), "v_scale": scale(3, Hkv, 1)}
  else:
    val = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    pool = {"k": val(L, P, Hkv, PS, kd), "v": val(L, P, Hkv, PS, kd)}
    new = {"k": val(3, Hkv, kd), "v": val(3, Hkv, kd)}
  bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 0]], jnp.int32)
  return pool, new, bt, rng


def _unpaired(leaf):
  """A leaf of paired heads [..., Hkv/2, PS, 128] a head a row, [..., Hkv, PS, 64], written out by hand."""
  return jnp.stack([leaf[..., :64], leaf[..., 64:]], axis=-3).reshape(*leaf.shape[:-3], 2 * leaf.shape[-3], leaf.shape[-2], 64)


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("quant", ["", "int8", "int4", "pairs"])
def test_kernel_on_a_stacked_pool_reads_its_layer(quant, layer):
  """``paged_decode_attention`` (interpret mode) on the stacked leaves with a
  layer scalar == the gather reference on that layer's slice alone."""
  from xotorch_support_jetson_tpu.ops.paged import paged_decode_attention, paged_gqa_attention_ref

  pool, new, bt, rng = _stacked_pools(quant)
  q = jnp.asarray(rng.normal(size=(3, 4, new["k"].shape[-1] * (2 if quant == "int4" else 1))), jnp.float32)
  lengths = jnp.asarray([2 * PS + 3, PS, 3 * PS], jnp.int32)
  scales = {"k_scale_pool": pool["k_scale"], "v_scale_pool": pool["v_scale"]} if "k_scale" in pool else {}
  got = paged_decode_attention(q, pool["k"], pool["v"], bt, lengths, PS, layer=jnp.int32(layer), pages_per_step=2, interpret=True, **scales)
  one = {name: leaf[layer] for name, leaf in scales.items()}
  want = paged_gqa_attention_ref(q[:, None], pool["k"][layer], pool["v"][layer], bt, lengths, PS, **one)[:, 0]
  stacked_ref = paged_gqa_attention_ref(q[:, None], pool["k"], pool["v"], bt, lengths, PS, layer=jnp.int32(layer), **scales)[:, 0]
  assert np.array_equal(np.asarray(stacked_ref), np.asarray(want))  # the reference by (layer, page) is the reference on the slice
  np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
  if quant == "pairs":  # ... which reads the pairs as the heads they are: the same bits as off the layer stored a head a row
    assert np.array_equal(np.asarray(want), np.asarray(paged_gqa_attention_ref(q[:, None], _unpaired(pool["k"][layer]), _unpaired(pool["v"][layer]), bt, lengths, PS)[:, 0]))


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("quant", ["", "int8", "int4", "pairs"])
def test_token_write_kernel_equals_the_scatter(quant, layer):
  """The Mosaic token write (interpret mode), on the pool in the kernel's
  form, puts the bytes the XLA scatter puts, in that layer only; a row on
  the trash page is skipped (nothing reads what it would write). A pool of
  paired heads is its own kernel form, and both writes put each KV head's 64
  channels in its half of the pair's row."""
  from xotorch_support_jetson_tpu.ops.paged import kernel_pool_form, stored_pool_form, write_token_kv

  pool, new, bt, _ = _stacked_pools(quant)
  bt = bt.at[1].set(0)  # an inactive row: its table is pinned to the trash page
  pos = jnp.asarray([2 * PS + 5, 7, PS - 1], jnp.int32)
  want = write_token_kv(pool, new, jnp.int32(layer), bt, pos, PS)
  kernel_form = kernel_pool_form(pool)
  assert all(leaf.shape[-1] % 128 == 0 for leaf in kernel_form.values()) and kernel_form["k"].ndim == 5
  if quant == "pairs":
    assert all(np.array_equal(kernel_form[name], pool[name]) for name in pool)
    at = (layer, np.asarray([bt[0, 2], bt[2, 0]]), slice(None), np.asarray([5, PS - 1]))  # where rows 0 and 2 write: (page, every head, slot)
    assert np.array_equal(np.asarray(_unpaired(want["k"]))[at], np.asarray(new["k"])[[0, 2]])
  got = stored_pool_form(write_token_kv(kernel_form, new, jnp.int32(layer), bt, pos, PS, kernel=True, interpret=True), pool)
  for name in pool:
    w, g, before = (np.array(x[name]) for x in (want, got, pool))
    assert np.array_equal(g[layer, 0], before[layer, 0]) and not np.array_equal(w[layer, 0], before[layer, 0])  # the scatter dumps the inactive row there
    w[layer, 0] = before[layer, 0]
    assert np.array_equal(w.view(np.uint8), g.view(np.uint8)), name
    others = [i for i in range(w.shape[0]) if i != layer]
    assert np.array_equal(g[others], before[others]), name


# ------------------------------------------------------------ the traced programs' structure


def _eqns(jaxpr):
  """Every equation of a jaxpr and of the jaxprs its equations hold (scan, while, cond, pjit, closed_call, custom_*)."""
  for eqn in jaxpr.eqns:
    yield eqn
    for value in eqn.params.values():
      for sub in value if isinstance(value, (tuple, list)) else (value,):
        inner = getattr(sub, "jaxpr", sub)
        if hasattr(inner, "eqns"):
          yield from _eqns(inner)


def _traced(program: str):
  """(jaxpr, pool) of a paged program on a tiny model whose pool shapes nothing else has."""
  from xotorch_support_jetson_tpu.models import decoder

  kernel = program.endswith("/paired-heads-kernel")  # told the kernels: a trace holds the Mosaic calls and runs nothing
  if program == "decode.paged_batch/mla-two-stacks":
    cfg, quant = _cfg(first_k_dense=1, **MLA, **MOE), ""
  elif kernel:
    cfg, quant = _cfg(dim=256), ""  # heads of 64, bfloat16-or-float pages: the pool holds them in pairs (ISSUE 58)
  else:
    cfg, quant = _cfg(), "int8"
  params, shard = full_model_params(KEY, cfg, "m")
  B = 3
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * MP, PS, quant=quant)
  bt = jnp.arange(1, 1 + B * MP, dtype=jnp.int32).reshape(B, MP)
  rows = lambda dtype, fill=0: jnp.full((B,), fill, dtype)  # noqa: E731
  tok, key = jnp.ones((B, 1), jnp.int32), jax.random.PRNGKey(0)
  if program.startswith("decode.paged_batch"):
    fn, args = decoder._fused_paged_batch_decode_impl.xot_jitted, (params, cfg, shard, tok, pool, bt, rows(jnp.int32, 5), rows(bool, True), rows(jnp.float32), rows(jnp.int32, 8), 4, 8, PS, kernel, key, None)
  elif program.startswith("decode.mixed_paged_batch"):
    fn = decoder._fused_mixed_paged_batch_decode_impl.xot_jitted
    args = (params, cfg, shard, tok, pool, bt, rows(jnp.int32, 5), rows(bool, True), rows(jnp.float32), rows(jnp.int32, 8), jnp.ones((1, 16), jnp.int32), bt[2:3], jnp.asarray([0]), jnp.asarray([9]), 4, 8, PS, kernel, key, None, None)
  else:  # the verify window, alone
    fn = jax.jit(lambda params, window, wpos, pool: paged_window_forward(params, cfg, shard, window, wpos, pool, bt, PS, use_kernel=kernel))
    args = (params, jnp.ones((B, 3), jnp.int32), 5 + jnp.arange(3, dtype=jnp.int32)[None, :] + jnp.zeros((B, 1), jnp.int32), pool)
  return jax.make_jaxpr(fn, static_argnums=tuple(i for i, a in enumerate(args) if not hasattr(a, "shape") and not isinstance(a, dict) and a is not None))(*args).jaxpr, pool


_PAIRED = ["decode.paged_batch/paired-heads-kernel", "decode.mixed_paged_batch/paired-heads-kernel", "paged_window_forward/paired-heads-kernel"]


@pytest.mark.parametrize("program", ["decode.paged_batch/dense-int8", "decode.paged_batch/mla-two-stacks", "decode.mixed_paged_batch", "paged_window_forward", *_PAIRED])
def test_no_layer_of_the_pool_is_sliced_out_or_joined(program):
  """The pool is a loop carry addressed by (layer, page): in the traced
  program no scan takes or makes a pool-shaped ``xs``/``ys``, nothing
  concatenates pool leaves, and no slice or gather yields a whole layer's
  pool — the forms PR 29 removed (each cost a copy that follows the pool's
  size, every step)."""
  jaxpr, pool = _traced(program)
  layer_pools = {leaf.shape[1:] for leaf in pool.values()}  # one layer's [P, Hkv, ps, hd]
  if program in _PAIRED:
    # Heads of 64 in pairs are whole lanes: the kernel path's programs — the plain chunk, the mixed tick, speculation's
    # verify — make no other form of the pool (until ISSUE 58 a padded copy each of K and V once a dispatch, cut back
    # at its end), so no pad, slice or transpose of theirs takes or makes a stacked leaf.
    assert {leaf.shape for leaf in pool.values()} == {(3, 13, 1, PS, 128)}
    whole = lambda v: tuple(getattr(v.aval, "shape", ()))[1:] in layer_pools  # noqa: E731
    moved = [eqn for eqn in _eqns(jaxpr) if eqn.primitive.name in ("pad", "slice", "transpose", "copy", "reshape") and any(whole(v) for v in (*eqn.invars, *eqn.outvars) if hasattr(v, "aval"))]
    assert not moved, moved
    assert {"kv_token_write"} <= {eqn.params.get("name") for eqn in _eqns(jaxpr) if eqn.primitive.name == "pallas_call"}

  def of_pool(aval) -> bool:
    shape = tuple(getattr(aval, "shape", ()))
    return shape[-4:] in layer_pools and len(shape) in (4, 5)

  scans = carried = 0
  for eqn in _eqns(jaxpr):
    name = eqn.primitive.name
    if name == "scan":
      scans += 1
      n_fixed = eqn.params["num_consts"] + eqn.params["num_carry"]
      xs, ys = eqn.invars[n_fixed:], eqn.outvars[eqn.params["num_carry"] :]
      assert not [v.aval for v in (*xs, *ys) if of_pool(v.aval)], f"a scan of {program} takes or makes the pool as xs/ys"
      carried += any(of_pool(v.aval) for v in eqn.invars[eqn.params["num_consts"] : n_fixed])
    elif name == "concatenate":
      assert not of_pool(eqn.outvars[0].aval), f"{program} concatenates pool leaves"
    elif name in ("dynamic_slice", "slice", "gather", "squeeze", "dynamic_update_slice") and any(of_pool(v.aval) for v in eqn.invars[:1]):
      out = tuple(eqn.outvars[0].aval.shape)
      assert name == "dynamic_update_slice" or out[-4:] not in layer_pools, f"{program}: {name} yields a whole layer's pool {out}"
      assert name != "dynamic_update_slice" or tuple(eqn.invars[1].aval.shape)[-4:] not in layer_pools, f"{program}: a whole layer is written back"
  assert scans >= 1 and carried >= 1  # the layer loop is there, with the pool in its carry


if __name__ == "__main__":  # python tests/test_paged_pool_inplace.py --record [out.npz], on the tree whose bytes are to be pinned
  assert sys.argv[1] == "--record"
  out = {}
  with jax.default_matmul_precision("highest"):  # conftest's autouse fixture
    for case in sorted(CASES):
      for name, value in CASES[case]().items():
        out[f"{case}/{name}"] = value
      print(case, {k.split("/", 1)[1]: v.shape for k, v in out.items() if k.startswith(case + "/")}, flush=True)
  path = pathlib.Path(sys.argv[2]) if len(sys.argv) > 2 else RECORDED
  path.parent.mkdir(exist_ok=True)
  np.savez_compressed(path, **out)
