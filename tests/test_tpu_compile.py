"""Ask the TPU's compiler before asking the chip.

libtpu is installed here and compiles for a chip that is described, not
attached (``jax.experimental.topologies``): what Mosaic or XLA:TPU refuses
for a v5e it refuses here too, at no chip time — a slice off the tiling, a
vector op v5e lacks (the int4-KV nibble unpack's int8 shift was one), more
VMEM than a kernel may take, a step program that does not fit 16 GB of HBM.
Interpret-mode tests cannot see any of these. Every kernel a dispatch table
can select on a TPU is compiled at Llama-3.2-1B / 8B head shapes, and the
whole batched decode step at Llama-3.2-1B width with the pool the scheduler
sizes for ``chip_smoke.py``'s settings. Nothing runs: a compile that passes
says nothing about results or times.

Code that asks ``jax.default_backend()`` sees the CPU here, so the tests
hand the jitted kernels and steps their shapes (and ``use_kernel``) directly.
"""

import math
import os
import pathlib
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
# libtpu lets one process at a time hold a chip and guards that with a lock
# file. No chip is attached here, and under pytest-xdist several workers
# describe the topology at once: without this all but one would skip.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

HQ, HKV, PS = 32, 8, 64  # Llama-3.2-1B (hd 64) and Llama-3.1-8B (hd 128) share 32/8 heads


@pytest.fixture(autouse=True)
def _fp32_matmuls():
  """Overrides conftest's precision pin: the serving process sets no matmul
  precision, and these tests compile what it compiles ("highest" turns the
  flash-decode kernel's bf16 dots into fp32-precision ones Mosaic refuses)."""
  yield


@pytest.fixture(scope="module")
def chip():
  """A described v5e chip as a sharding for ShapeDtypeStructs, with the
  persistent compile cache off: an entry written for an absent chip cannot
  be read back and only warns."""
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  from jax.sharding import SingleDeviceSharding

  try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 — no libtpu in this installation: nothing to ask
    pytest.skip(f"cannot describe a v5e topology here: {e!r}")
  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", was)
  compilation_cache.reset_cache()


def _sds(chip, shape, dtype):
  return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _rows(chip, n: int):
  """Per-row operand of a batched program: dtype → [n] shape."""
  return lambda dtype: _sds(chip, (n,), dtype)


def _compile(tracked, *args, **kwargs):
  """Lower + compile a ``tracked_jit`` program for the described chip."""
  compiled = tracked.xot_jitted.lower(*args, **kwargs).compile()
  return compiled, compiled.as_text()


def _mosaic_calls(text: str) -> list[str]:
  """The kernel names of a compiled program's Mosaic calls, one per call."""
  return [m.group(1) for line in text.splitlines() if "tpu_custom_call" in line for m in [re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)] if m]


def _compile_paged_kernel(chip, quant: str, batch: int, tile: int, hd: int, mp: int, n_pages: int, hq: int = HQ) -> str:
  from xotorch_support_jetson_tpu.ops.paged import _paged_decode_attention_impl

  kd = hd // 2 if quant == "int4" else hd
  code = jnp.int8 if quant else jnp.bfloat16
  layers = 2  # the stacked leaves, read at a layer scalar
  pool = _sds(chip, (layers, n_pages, HKV, PS, kd), code)
  scales = [_sds(chip, (layers, n_pages, HKV, PS, 1), jnp.float32)] * 2 if quant else []
  _, text = _compile(
    _paged_decode_attention_impl,
    _sds(chip, (batch, hq, hd), jnp.bfloat16), _sds(chip, (batch, mp), jnp.int32), _sds(chip, (batch,), jnp.int32), _sds(chip, (1,), jnp.int32), pool, pool, *scales,
    page_size=PS, pages_per_step=tile, kv_quant=quant, interpret=False,
  )  # fmt: skip
  return text


# Every KV mode at the served tile (ops/paged.py PAGE_TILE) and 16/48/96 rows.
# A tile is two VMEM slots of G whole pages (all kv heads).
@pytest.mark.parametrize("hd", [64, 128, 256])
@pytest.mark.parametrize("batch", [16, 48, 96])
@pytest.mark.parametrize("quant", ["", "int8", "int4"])
def test_paged_decode_kernel_compiles_for_v5e(chip, quant, batch, hd):
  from xotorch_support_jetson_tpu.ops.paged import _page_tile

  mp = 16  # 1k context
  assert "tpu_custom_call" in _compile_paged_kernel(chip, quant, batch, _page_tile(mp), hd, mp, batch * mp + 1)


@pytest.mark.parametrize(
  "quant,batch,tile,hd,mp,n_pages,hq",
  [
    pytest.param("int8", 16, 8, 128, 64, 257, 32, id="mistral-7b-served"),  # the benchmark's cell: 32/8 heads, 4096-token window, 257 pages
    pytest.param("", 96, 16, 256, 128, 1025, 16, id="hd256-bf16-widest-tile"),  # 16 MiB of tile buffers: past the default scoped VMEM
  ],
)
def test_paged_decode_kernel_compiles_at_served_shapes(chip, quant, batch, tile, hd, mp, n_pages, hq):
  assert "tpu_custom_call" in _compile_paged_kernel(chip, quant, batch, tile, hd, mp, n_pages, hq)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("quant", ["", "int8", "int4"])
def test_token_write_kernel_compiles_for_v5e(chip, quant, hd):
  """The kernel path's token write (``write_token_kv`` ``kernel=True``) on a
  stacked pool in the kernel's form, every pool dtype and code width: groups
  of 8/16/32 slots by DMA, the select in 32 bits, the pool aliased through."""
  from xotorch_support_jetson_tpu.ops.paged import kernel_pool_form, stored_pool_form, write_token_kv

  layers, n_pages, batch, mp = 4, 65, 16, 16
  kd = hd // 2 if quant == "int4" else hd
  code = jnp.int8 if quant else jnp.bfloat16
  pool = {"k": _sds(chip, (layers, n_pages, HKV, PS, kd), code), "v": _sds(chip, (layers, n_pages, HKV, PS, kd), code)}
  new = {"k": _sds(chip, (batch, HKV, kd), code), "v": _sds(chip, (batch, HKV, kd), code)}
  if quant:
    pool.update({name: _sds(chip, (layers, n_pages, HKV, PS, 1), jnp.float32) for name in ("k_scale", "v_scale")})
    new.update({name: _sds(chip, (batch, HKV, 1), jnp.float32) for name in ("k_scale", "v_scale")})

  def write(pool, new, layer, bt, pos):
    return stored_pool_form(write_token_kv(kernel_pool_form(pool), new, layer, bt, pos, PS, kernel=True), pool)

  text = jax.jit(write, donate_argnums=0).lower(pool, new, _sds(chip, (), jnp.int32), _sds(chip, (batch, mp), jnp.int32), _sds(chip, (batch,), jnp.int32)).compile().as_text()
  assert "tpu_custom_call" in text and "kv_token_write" in text


@pytest.mark.parametrize("quant", ["", "int8"])
def test_flash_prefill_compiles_for_v5e(chip, quant):
  """One PREFILL_BUCKET of queries against the default 4096-slot cache."""
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_attention_prefill

  sq, skv, hd = 128, 4096, 64
  kv = _sds(chip, (1, skv, HKV, hd), jnp.int8 if quant else jnp.bfloat16)
  scale = _sds(chip, (1, skv, HKV, 1), jnp.float32) if quant else None
  _, text = _compile(flash_attention_prefill, _sds(chip, (1, sq, HQ, hd), jnp.bfloat16), kv, kv, _sds(chip, (1,), jnp.int32), scale, scale, interpret=False)
  assert "tpu_custom_call" in text


def test_flash_decode_32k_compiles_for_v5e(chip):
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_decode_attention

  kv = _sds(chip, (1, 32768, HKV, 64), jnp.bfloat16)
  _, text = _compile(flash_decode_attention, _sds(chip, (1, 1, HQ, 64), jnp.bfloat16), kv, kv, _sds(chip, (1, 1), jnp.int32), interpret=False)
  assert "tpu_custom_call" in text


def test_int4_matmul_compiles_for_v5e(chip):
  """A decode-sized w4a16 matmul at Llama-3.2-1B's MLP width."""
  from xotorch_support_jetson_tpu.ops.pallas_int4 import int4_matmul

  _, text = _compile(int4_matmul, _sds(chip, (16, 2048), jnp.bfloat16), _sds(chip, (1024, 8192), jnp.int8), _sds(chip, (8192,), jnp.float32), interpret=False)
  assert "tpu_custom_call" in text


# ------------------------------------------------- whole step programs


@pytest.fixture(scope="module")
def llama_1b(chip):
  """Llama-3.2-1B at full width and depth, as shapes: the published config
  ``chip_smoke.py`` serves, under the engine's default 4096-token cap."""
  from dataclasses import replace

  from chip_smoke import LLAMA_32_1B, MODEL
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import full_model_params

  cfg = replace(config_from_hf(LLAMA_32_1B), max_seq_len=4096)
  shapes = jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg, MODEL)[0])
  params = jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), shapes)
  return cfg, Shard(MODEL, 0, cfg.n_layers - 1, cfg.n_layers), params


def _smoke_pool(chip, cfg, n_slots: int, quant: str, scale_pages: int = 1):
  """The pool ``BatchedServer._ensure_cache`` sizes by default for these
  settings (inference/paging.py default_pool_pages), as shapes."""
  from xotorch_support_jetson_tpu.inference.paging import default_pool_pages, pages_to_cover
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  n_pages = scale_pages * default_pool_pages(cfg, cfg.n_layers, n_slots, cfg.max_seq_len, PS, quant) + 1
  pool = jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS, quant=quant))
  return jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), pool), pages_to_cover(cfg.max_seq_len, PS)


def _decode_step_args(chip, llama_1b, n_slots: int, quant: str, scale_pages: int = 1):
  cfg, shard, params = llama_1b
  pool, mp = _smoke_pool(chip, cfg, n_slots, quant, scale_pages)
  rows = _rows(chip, n_slots)
  return (
    params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool, _sds(chip, (n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_),
    rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True, _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip


def test_paged_batch_step_at_smoke_settings_fits_v5e(chip, llama_1b):
  """``decode.paged_batch`` as ``chip_smoke.py``'s batched phase dispatches
  it — 16 slots, int8 KV, the scheduler's own pool, the kernel path — is
  accepted by XLA:TPU, which refuses a program whose arguments and
  temporaries exceed the chip's HBM (next test). ``memory_analysis()`` is
  printed for the record (see PERF.md: its ``temp_size`` over-counts)."""
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl

  args = _decode_step_args(chip, llama_1b, 16, "int8")
  cfg, pool = args[1], args[4]
  compiled, text = _compile(_fused_paged_batch_decode_impl, *args)
  assert "tpu_custom_call" in text, "the step must hold the Pallas paged kernel, not the gather reference"
  # The kernel takes the scales with tokens on lanes. Asked for as the pool stores them, [P, Hkv, ps, 1]
  # row-major, each leaf was copied every layer into a layout that pads the trailing 1 to 128 lanes.
  padded_scale_copy = re.search(rf"f32\[\d+,{HKV},{PS},1\]\{{3,2,1,0[^}}]*\}} copy\(", text)
  assert padded_scale_copy is None, padded_scale_copy.group(0)
  # The pool is one buffer a leaf from the donated argument to the result (ISSUE 29): inside the step loop no
  # instruction copies, slices or rewrites a stacked code leaf — the token write and the attention are Mosaic
  # calls that address it by (layer, page), and only the write's name lacks what the roofline reader counts.
  n_pages = pool["k"].shape[1]
  whole_leaf = rf"= s8\[{cfg.n_layers},{n_pages},{HKV},{PS},\d+\]\S* (copy|fusion|dynamic-update-slice|copy-start|scatter)\("
  # (Llama-3.2-1B's 64-wide code leaves are padded to whole lanes and back once a dispatch, outside the loops.)
  touched = [line[:160] for line in text.splitlines() if re.search(whole_leaf, line) and "/while/body" in line]
  assert not touched, touched
  kernels = re.findall(r"%(\S+) = [^\n]*? custom-call\([^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*op_name=\"([^\"]*)\"", text)
  assert sorted("paged_decode" in name for name, _ in kernels) == [False, True], kernels
  for name, op_name in kernels:
    assert ("xot.attn/" if "paged_decode" in name else "xot.kv_write/") in op_name, (name, op_name)
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch B=16 int8: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes < 16 * 1024**3


@pytest.mark.parametrize("weights", ["bf16", "int8"])
def test_qkv_weights_are_read_where_they_lie(chip, llama_1b, weights):
  """``decode.paged_batch``: XLA:TPU reads each layer's slice of the stacked
  ``wq``/``wk``/``wv`` inside the fusion that holds its dot, as it reads
  ``wo`` and ``w_gate``. Without the barrier in ``_dense_qkv`` it folds the
  head reshape into the projection's dot, wants the weight K-minor for the
  dot it then has, relays two of the three stacks to a ``{1,2,0}`` layout
  once a dispatch and copies the layer's slices out of them in every layer
  of every step (``constant_dynamic-slice_fusion`` of a ``[1, D, N]`` array)
  — and does the same to one merged leaf (AOT, PR 33; PERF.md §6)."""
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl
  from xotorch_support_jetson_tpu.models.quantize import quantize_params

  args = list(_decode_step_args(chip, llama_1b, 16, "int8"))
  cfg = args[1]
  if weights == "int8":
    args[0] = jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), jax.eval_shape(quantize_params, args[0]))
  code = "s8" if weights == "int8" else "bf16"
  L, D = cfg.n_layers, cfg.dim
  widths = "|".join(str(n) for n in sorted({cfg.q_dim, cfg.kv_dim}))
  assert all(args[0]["layers"][n].shape == (L, D, w) for n, w in (("wq", cfg.q_dim), ("wk", cfg.kv_dim), ("wv", cfg.kv_dim)))
  compiled, text = _compile(_fused_paged_batch_decode_impl, *args)
  relaid = [line.strip()[:140] for line in text.splitlines() if re.search(rf"= {code}\[({L}|1),{D},({widths})\]\S* (copy|copy-start)\(", line) or re.search(rf"{code}\[({L}|1),{D},({widths})\]\{{1,2,0", line)]
  assert not relaid, relaid
  rebuilt = [line.strip()[:140] for line in text.splitlines() if re.search(rf"= {code}\[(1,)?{D},({widths})\]\S* fusion\(", line) and "constant_dynamic-slice_fusion" in line]
  assert not rebuilt, rebuilt
  dots = [block for block in text.split("\n\n") if re.match(rf"%\S+ \([^\n]*{code}\[{L},{D},({widths})\]", block) and " convolution(" in block]
  assert len(dots) >= 1, "no fusion takes a stacked q/k/v leaf and holds its dot"
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch B=16 {weights}: temp={mem.temp_size_in_bytes}")


_HLO_LINE = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([a-z][\w\-]*)\((.*)$", re.M)  # name = result type opcode(operands...


def _takers(text: str, shape: str) -> list[tuple[str, str]]:
  """(name, opcode) of every fusion and custom call of an optimised HLO text that takes a value of ``shape`` (a
  regex) as an operand. Operands are printed by name, so the names' shapes are read first."""
  lines = _HLO_LINE.findall(text)
  shapes = {name: result for name, result, _, _ in lines}
  out = []
  for name, _, op, rest in lines:
    if op in ("fusion", "custom-call") and any(re.fullmatch(shape + r"\S*", shapes.get(operand, "")) for operand in re.findall(r"%[\w.\-]+", rest.split("), ")[0])):
      out.append((name, op))
  return out


def _materialised(text: str) -> list[tuple[str, str, str, list[str], str]]:
  """(name, result type, opcode, operand names, op_name) of every instruction of an optimised HLO text that stands in
  the entry computation, a loop's body or a called one — not inside a fusion's or a reducer's own computation, whose
  instructions produce no buffer."""
  inner = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", text)) | set(re.findall(r"to_apply=(%[\w.\-]+)", text))
  out, computation = [], None
  for line in text.splitlines():
    head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$", line)
    if head:
      computation = head.group(1)
    m = None if computation in inner else _HLO_LINE.match(line)
    if m:
      name, result, op, rest = m.groups()
      scope = re.search(r'op_name="([^"]*)"', rest)
      out.append((name, result, op, re.findall(r"%[\w.\-]+", rest.split("), ")[0]), scope.group(1) if scope else ""))
  return out


@pytest.mark.parametrize("slots", [64, 16])
def test_state_step_kernel_compiles_for_v5e(chip, slots):
  """``ops/ssm.py``'s one-pass form alone at granite-4.0-h-micro's leaf (ISSUE 35): the stacked leaf aliased input →
  output and read at a layer scalar, [32, 64, 128] tiles in and out of VMEM inside the default scoped limit, the
  lanes→sublanes relayout of Δ·x, the lane sums and the index map of rows that stand still as Mosaic lowers them for
  a v5e. Nothing copies the leaf."""
  from xotorch_support_jetson_tpu.ops.ssm import one_pass_supported, ssm_state_step

  leaf = _sds(chip, (36, slots, 64, 64, 128), jnp.float32)
  assert one_pass_supported(leaf, True)
  step = jax.jit(lambda *args: ssm_state_step(*args, use_kernel=True), donate_argnums=0)
  per_head, per_row = _sds(chip, (slots, 64), jnp.float32), _sds(chip, (slots, 128), jnp.float32)
  compiled = step.lower(leaf, _sds(chip, (), jnp.int32), per_head, _sds(chip, (slots, 64, 64), jnp.float32), per_row, per_row, _sds(chip, (slots,), jnp.bool_)).compile()
  text = compiled.as_text()
  assert text.count("custom_call_target=\"tpu_custom_call\"") == 1 and "ssm_state_step" in text
  assert not re.search(rf"= f32\[36,{slots},64,64,128\]\S* (copy|copy-start|transpose)\(", text)
  assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20  # y and the relaid decay: no second state


def test_hybrid_decode_step_at_the_cells_settings_fits_v5e(chip):
  """granite-4.0-h-micro whole, as ``granite-4.0-h-micro.decode-closed-64`` serves it (ISSUE 34): 64 slots, 1537
  pages, bf16, the kernel path. ``decode.paged_batch`` is accepted by XLA:TPU beside 6.4 GB of weights, 4.9 GB of
  recurrent state and 0.8 GB of pages. The state leaf is one buffer from the donated argument to the result: no
  instruction copies it (a copy is a second 4.8 GB, and PR 29's finding over again); it is read at (layer) and
  written back by the Mosaic call ``ssm_state_step``, which aliases it (PR 35; until then by fusions the compiler
  aliased to it). No stacked state-space projection is relaid or copied: HF's one ``in_proj`` of 8512 columns is no
  whole number of lanes, the TPU kept that stack column-major and copied all 1.26 GB of it once a dispatch for the dot, so it is three leaves (AOT, PR 34; PERF.md section 6)."""
  import json

  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl, full_model_params
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  from dataclasses import replace

  hf = json.loads((ROOT / "benchmark" / "configs" / "granite-4.0-h-micro-bf16.json").read_text())
  n_slots, n_pages = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"])
  cfg = replace(config_from_hf({k: v for k, v in hf.items() if not isinstance(v, dict)}), max_seq_len=int(hf["serving_window_tokens"]))
  on_chip = lambda tree: jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)  # noqa: E731
  params = on_chip(jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS, n_slots=n_slots)))
  assert pool["k"].shape == (4, n_pages, 8, PS, 64) and pool["ssm"].shape == (36, n_slots, 64, 64, 128) and pool["conv"].shape == (36, n_slots, 3, 4352)
  rows = _rows(chip, n_slots)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, Shard("granite", 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (n_slots, 1), jnp.int32), pool,
    _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  kernels = re.findall(r"custom_call_target=\"tpu_custom_call\"", text)
  # the paged kernel and the token write, once for each of the four attention layers' loops, and the state step
  # (ops/ssm.py, the one-pass form), once for each of the five state-space runs'
  assert len(kernels) == 13, len(kernels)
  # Each run reads the state's tiles in ONE instruction, the kernel: no fusion takes the leaf or a layer of it (the
  # reference expression compiles to two a run — the in-place update and the contraction's second read; PERF.md §6, PR 35).
  takers = _takers(text, r"f32\[(36,|1,)?64,64,64,128\]")
  assert len(takers) == 5 and all(op == "custom-call" and name.startswith("%ssm_state_step") for name, op in takers), takers
  state = r"f32\[36,64,64,64,128\]"
  copied = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= {state}\S* (copy|copy-start|transpose)\(", line)]
  assert not copied, copied
  relaid = [line.strip()[:160] for line in text.splitlines() if re.search(r"= bf16\[36,(2048|4096|8192),\d+\]\S* (copy|copy-start)\(", line) and "[36,2048,64]" not in line]
  assert not relaid, relaid  # (w_dt's 64 columns, 9 MB, are the one stack the TPU still relays)
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch granite B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize(
  "what,T,L,E,held,D,F,k,dtype",
  [
    ("Ling's decode step: 64 rows x top 8 of 512, 128 held, whole-expert blocks", 64, 6, 512, (0, 128), 2560, 768, 8, jnp.bfloat16),
    ("Moonlight's decode step: int8 codes cast in VMEM, a scale row an expert", 16, 13, 64, None, 2048, 1408, 6, jnp.int8),
    ("mixtral's widths: an expert's matrix is cut into column blocks of 512", 16, 2, 8, None, 4096, 14336, 2, jnp.bfloat16),
  ],
)
def test_expert_kernels_compile_for_v5e(chip, what, T, L, E, held, D, F, k, dtype, monkeypatch):
  """``ops/moe.py``'s grouped form alone (ISSUE 40): ``moe_gate_up`` and ``moe_down`` as Mosaic lowers them for a v5e —
  a dynamic grid of visits, whole-expert (or column) blocks of the STACKED leaves at a layer scalar inside a 64 MiB
  VMEM limit, the int8 cast — and nothing copies or cuts an expert leaf."""
  from xotorch_support_jetson_tpu.ops import moe

  E_held = E if held is None else held[1] - held[0]
  scaled = dtype == jnp.int8
  leaves = [_sds(chip, (L, E_held, D, F), dtype), _sds(chip, (L, E_held, D, F), dtype), _sds(chip, (L, E_held, F, D), dtype)]
  scales = [_sds(chip, (L, E_held, F), jnp.float32), _sds(chip, (L, E_held, F), jnp.float32), _sds(chip, (L, E_held, D), jnp.float32)] if scaled else []
  monkeypatch.setattr(moe, "_on_tpu", lambda: True)  # (the backend here is the CPU)
  assert moe.ffn_form(leaves[0], leaves[2], None, True, scaled) == "grouped"

  def layer(x, w_router, layer, w_gate, w_up, w_down, *scales):
    return moe.moe_ffn(x, w_router, w_gate, w_up, w_down, k=k, held=held, scales=scales or None, layer=layer)

  compiled = jax.jit(layer).lower(_sds(chip, (T, D), jnp.bfloat16), _sds(chip, (D, E), jnp.float32), _sds(chip, (), jnp.int32), *leaves, *scales).compile()
  text = compiled.as_text()
  calls = _mosaic_calls(text)
  assert sorted(calls) == ["moe_down", "moe_gate_up"], calls
  stack = rf"{'s8' if scaled else 'bf16'}\[{L},{E_held},({D},{F}|{F},{D})\]"
  assert {op for _, op in _takers(text, stack)} == {"custom-call"}
  assert compiled.memory_analysis().temp_size_in_bytes < 64e6, what


def test_training_a_lane_wide_moe_lowers_for_v5e(chip, monkeypatch):
  """``jax.grad`` of ``shard_forward_aux`` (train/trainer.py, parallel/train_step.py) for a MoE whose expert faces are
  whole lane groups — the served programs of the same weights take the grouped form on this chip — lowers and compiles
  for a v5e: the cache-less forward hands no stack over whole, so its experts are the block form's einsums, which have
  a derivative (a ``pallas_call`` with scalar prefetch and a dynamic grid has none: ``_pallas_call_jvp_rule`` raises
  at trace time). The cache-less ``shard_forward`` holds no kernel either."""
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models import decoder
  from xotorch_support_jetson_tpu.models.config import tiny_test_config
  from xotorch_support_jetson_tpu.ops import moe

  monkeypatch.setattr(moe, "_on_tpu", lambda: True)  # (the backend here is the CPU)
  cfg = tiny_test_config(dim=128, moe_hidden_dim=128, n_experts=8, n_active_experts=2, first_k_dense=1, n_layers=3, shared_expert_dim=128, dtype=jnp.bfloat16)
  shard = Shard("moe-lanes", 0, cfg.n_layers - 1, cfg.n_layers)
  params = jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), jax.eval_shape(lambda: decoder.full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  assert decoder.served_expert_form(params, cfg) == "grouped"
  tokens, positions = _sds(chip, (2, 64), jnp.int32), _sds(chip, (2, 64), jnp.int32)

  def loss(params, tokens, positions):
    logits, aux = decoder.shard_forward_aux(params, cfg, shard, tokens, positions)
    return jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)) + 0.01 * aux

  text = jax.jit(jax.grad(loss)).lower(params, tokens, positions).compile().as_text()
  assert "tpu_custom_call" not in text
  text = jax.jit(lambda p, t, pos: decoder.shard_forward(p, cfg, shard, t, pos)[0]).lower(params, tokens, positions).compile().as_text()
  assert "tpu_custom_call" not in text
  # ... and the same weights over a cache do take the kernels
  cache = jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), jax.eval_shape(lambda: decoder.init_kv_cache(cfg, cfg.n_layers, 2, 128)))
  text = jax.jit(lambda p, t, pos, c: decoder.shard_forward(p, cfg, shard, t, pos, c)[0]).lower(params, tokens, positions, cache).compile().as_text()
  assert {m for m in re.findall(r'/(\w+)/pallas_call"', text)} >= {"moe_gate_up", "moe_down"}


def _ling_at_the_cells_settings(chip, monkeypatch, config: str = "ling-3.0-flash-ep4-d7", **cut):
  """(hf, cfg, params, pool) of ``ling-3.0-flash.decode-closed-64`` (or of another expert configuration of the
  benchmark, ``cut`` replacing keys of its file) as shapes on the described chip, its programs told what they see on
  the chip: a TPU (``ops/moe.py ffn_form`` asks the backend, which is the CPU here)."""
  import json
  from dataclasses import replace

  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import full_model_params
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  monkeypatch.setattr(moe, "_on_tpu", lambda: True)

  hf = {**json.loads((ROOT / "benchmark" / "configs" / f"{config}.json").read_text()), **cut}
  n_slots, n_pages = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), int(hf["serving_env"].get("XOT_TPU_BATCH_PAGES", 257))
  cfg = replace(config_from_hf({k: v for k, v in hf.items() if not isinstance(v, dict)}), max_seq_len=int(hf["serving_window_tokens"]))
  on_chip = lambda tree: jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)  # noqa: E731
  params = on_chip(jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS, n_slots=n_slots)))
  return hf, cfg, params, pool


def test_kda_hybrid_decode_step_at_the_cells_settings_fits_v5e(chip, monkeypatch):
  """Ling-3.0-flash's first stage at one chip's share, as ``ling-3.0-flash.decode-closed-64`` serves it (ISSUE 36): 64
  slots, 1537 latent pages of ONE attention layer, bf16, 128 of 512 experts held. ``decode.paged_batch`` is accepted by
  XLA:TPU beside 10.3 GB of weights, 0.81 GB of float32 matrix state and 0.11 GB of pages. The state leaf is one buffer
  from the donated argument to the result: no instruction copies it or a layer of it; it is read at (layer) and written
  back by the Mosaic call ``delta_state_step`` (ISSUE 45: one call in each of the three runs of KDA layers, the leaf
  aliased through it; until then by two fusions of the XLA expression), which no fusion shares it with. The other Mosaic calls
  are the experts' two (ISSUE 40: ``moe_gate_up``, ``moe_down``, in both stacks' loops — MLA takes the gather path
  though the program is told ``use_kernel``, which is what ``decode_kernels_supported`` resolves for it on a TPU), and
  they take the STACKED expert leaves: no stacked expert leaf, and no layer of one, is copied, cut out or relaid (a copy
  of a stack is 3.8 GB, of a layer 0.75 GB a step)."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl
  from xotorch_support_jetson_tpu.ops.paged import decode_kernels_supported, paged_kernel_supported

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch)
  assert decode_kernels_supported(cfg, "tpu") and not paged_kernel_supported(cfg, "tpu") and not decode_kernels_supported(cfg, "cpu")
  n_slots = pool["ssm"].shape[1]
  assert pool["k"].shape == (1, 1537, 1, PS, 512) and pool["v"].shape == (1, 1537, 1, PS, 64) and pool["ssm"].shape == (6, 64, 32, 128, 128) and pool["conv"].shape == (6, 64, 3, 12288)
  assert params["ssm_moe_layers"]["w_experts_gate"].shape == (5, 128, 2560, 768) and params["moe_layers"]["w_router"].shape == (1, 2560, 512)
  rows = _rows(chip, n_slots)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, Shard("ling", 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (n_slots, 1), jnp.int32), pool,
    _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  calls = _mosaic_calls(text)
  # (the six KDA layers are three runs: the dense first layer, and the expert layers on either side of the latent layer)
  assert sorted(set(calls)) == ["delta_state_step", "moe_down", "moe_gate_up"] and calls.count("delta_state_step") == 3, calls
  state_takers = _takers(text, r"f32\[(6,|1,)?64,32,128,128\]")
  assert len(state_takers) == 3 and all(op == "custom-call" and name.startswith("%delta_state_step") for name, op in state_takers), state_takers
  assert not re.search(r"= f32\[6,64,32,128,128\]\S* dynamic-update-slice\(", text)
  stack = r"bf16\[(5|1),128,(2560,768|768,2560)\]"
  takers = {op for shape in (stack,) for _, op in _takers(text, shape)}
  assert takers == {"custom-call"}, takers  # the kernels alone take the stacks: no fusion cuts a layer out of one
  state = r"f32\[(6,)?64,32,128,128\]"
  copied = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= {state}\S* (copy|copy-start|transpose)\(", line)]
  assert not copied, copied
  experts = [line.strip()[:160] for line in text.splitlines() if re.search(r"= bf16\[(5,|1,)?128,(2560,768|768,2560)\]\S* (copy|copy-start|transpose)\(", line)]
  assert not experts, experts
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch ling B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= 6 * 64 * 32 * 128 * 128 * 4  # the pool is donated: the state is updated where it lies
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("config", ["moonlight-a3b-d14", "ling-3.0-flash-ep4-d7"])
def test_latent_attention_decode_step_holds_no_paged_decode_call(chip, monkeypatch, config):
  """The two latent-attention configurations of the benchmark, ASKED for the kernel (``use_kernel`` True): their
  ``decode.paged_batch`` holds no Mosaic call named ``paged_decode`` — ``kernel_attends`` sends MLA to the XLA gather
  whatever it was told —, so a change to the paged-decode kernel cannot move their cells (ISSUE 43). Moonlight is
  cut to its dense layer and two expert layers, in bf16: which attention core a program takes does not depend on depth."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl

  hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, config, **({} if config.startswith("ling") else {"num_hidden_layers": 3}))
  n_slots = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"])
  assert cfg.is_mla
  rows = _rows(chip, n_slots)
  _, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, Shard(config, 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (n_slots, 1), jnp.int32), pool,
    _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  calls = _mosaic_calls(text)
  assert calls and not [name for name in calls if "paged_decode" in name], calls  # (the experts' two kernels are there)


def test_kda_hybrid_prefill_group_at_the_cells_longest_fits_v5e(chip, monkeypatch):
  """The largest prefill program the cell meets — a group of 8 rows padded to 1024 tokens, ``prefill.pages_many_sampled``
  with the pool donated — fits beside the weights and the state: the chunked delta rule's float32 operands, the expert
  layer's sorted rows and products of 4096 tokens a piece (ISSUE 40: two pieces here, 32,768 assignments each) and the
  latent attention's scores of 256 queries at a time (whole, they are 4 GB twice over and the compiler refuses the
  program) are its temporaries. No piece copies an expert leaf or a layer of one."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import prefill_into_pages_many_sampled_inplace

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch)
  K, S = 8, 1024
  rows = _rows(chip, K)
  compiled, text = _compile(
    prefill_into_pages_many_sampled_inplace, params, cfg, Shard("ling", 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (K, S), jnp.int32), pool,
    _sds(chip, (K, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS, rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None, rows(jnp.int32),
  )  # fmt: skip
  experts = [line.strip()[:160] for line in text.splitlines() if re.search(r"= bf16\[(5,|1,)?128,(2560,768|768,2560)\]\S* (copy|copy-start|transpose|fusion|dynamic-slice)\(", line)]
  assert not experts, experts
  assert text.count('custom_call_target="tpu_custom_call"') >= 8  # (gate/up, down) x 2 pieces x 2 stacks' loops
  mem = compiled.memory_analysis()
  print(f"prefill.pages_many_sampled ling K=8 S=1024: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_gdn_hybrid_decode_step_and_longest_prefill_at_the_cells_settings_fit_v5e(chip, monkeypatch):
  """Olmo-Hybrid's first twelve layers as ``olmo-hybrid-7b.decode-closed-64`` serves them (ISSUE 44): 64 slots of a
  [30, 192, 96] float32 state in 9 layers, 1537 pages of 30 KV heads x 64 x 128 bf16 in 3. ``decode.paged_batch`` told
  ``use_kernel`` is accepted by XLA:TPU beside 6.54 GB of weights: its Mosaic calls are the paged kernel's, the token
  write's and the delta step's (ISSUE 45: ``delta_state_step``, one call in the loop of each of the three runs of
  Gated-DeltaNet layers, tiles of 10 heads whose 96-wide face lies in 128 lanes of VMEM; the leaf is aliased through
  it and no fusion takes the leaf or a layer of it — the XLA expression compiled to two a run), the paged kernel takes
  Mistral's tile of 8 pages (two slots of 8 pages of K and of V are 15.7 MB of VMEM, inside the limit the call asks
  for), no instruction copies the state leaf or a layer of it, and the compiler's argument bytes are what they were
  before the step had a kernel (12.81 GB). And the largest prefill groups a server of this pool dispatches fit beside
  them with the pool donated — larger than any the cell meets (8 rows padded to 1024 tokens from position 0, a page
  table of 16): eight first chunks (8 x ``XOT_TPU_PREFILL_CHUNK`` = 2048 tokens, a table of 32 pages) and, at the
  widest table a row can have (64 pages: a group that ends past 2048 tokens, the later chunks of long prompts), the
  four rows the scheduler holds such a group to (``BatchedServer._group_rows``). The chunked delta rule's float32
  operands at 64 positions a chunk and the rows' gathered K/V windows — every attention layer's, 240 MB a layer each
  of K and of V at 8 x 4096 tokens — are the temporaries: 3.48 and 2.55 GB. Eight rows at a table of 64 are what
  ``_group_rows`` exists for: XLA:TPU refuses that program by 52 MB at 8 x 1024 ("Used 15.80G of 15.75G", 1.17 GB of
  it the heap's fragmentation), where until ISSUE 48 it fitted as a rematerialised program (79 instructions named
  ``remat``, 4.62 GB of temporaries, 1.2 GB of them the two cuts of the WHOLE state leaf that the rows' state read was
  lowered to once a Gated-DeltaNet layer — ``test_hybrid_prefill_group_reads_the_admitted_rows_state_where_it_lies``;
  PERF.md §7)."""
  from types import SimpleNamespace

  from xotorch_support_jetson_tpu.inference.batch_scheduler import GROUP_ROWS, BatchedServer
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl, prefill_into_pages_many_sampled_inplace
  from xotorch_support_jetson_tpu.ops.paged import PAGE_TILE, _page_tile

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, "olmo-hybrid-7b-d12")
  n_slots, n_pages = pool["ssm"].shape[1], pool["k"].shape[1]
  assert pool["k"].shape == pool["v"].shape == (3, n_pages, 30, PS, 128) and n_pages >= 1217 and pool["ssm"].shape == (9, 64, 30, 192, 96) and pool["conv"].shape == (9, 64, 3, 11520)
  shard, mp = Shard("olmo", 0, cfg.n_layers - 1, cfg.n_layers), pages_to_cover(cfg.max_seq_len, PS)
  rows = _rows(chip, n_slots)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool,
    _sds(chip, (n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
  # the paged kernel and the token write, once for each of the three attention layers' loops, and the delta step, once for each of the three runs of Gated-DeltaNet layers between them
  assert len(kernels) == 9 and [sum(name in line for line in kernels) for name in ("paged_decode", "kv_token_write", "delta_state_step")] == [3, 3, 3], [line.strip()[:120] for line in kernels]
  state_takers = _takers(text, r"f32\[(9,|1,)?64,30,192,96\]")
  assert len(state_takers) == 3 and all(op == "custom-call" and name.startswith("%delta_state_step") for name, op in state_takers), state_takers
  assert not re.search(r"= f32\[9,64,30,192,96\]\S* dynamic-update-slice\(", text)
  assert _page_tile(mp) == PAGE_TILE == 8  # Mistral's and granite's tile, at a page seven times theirs
  state = r"f32\[(9,)?64,30,192,96\]"
  copied = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= {state}\S* (copy|copy-start|transpose)\(", line)]
  assert not copied, copied
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch olmo B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= 9 * 64 * 30 * 192 * 96 * 4  # the pool is donated: the state is updated where it lies
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9 and abs(mem.argument_size_in_bytes - 12.81e9) < 0.01e9
  sched = SimpleNamespace(paged=True, prefill_chunk=2048, page_size=PS, pages_per_row=mp)  # what the rule reads of a server (XOT_TPU_PREFILL_CHUNK's default: the cell sets none)
  sched._page_window = lambda end_pos: BatchedServer._page_window(sched, end_pos)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend: head size 128 takes the kernel on the chip
  S = sched.prefill_chunk
  for table in (sched._page_window(S), mp):
    K = BatchedServer._group_rows(sched, table * PS)
    assert (K, table) in ((GROUP_ROWS, 32), (GROUP_ROWS // 2, 64))
    rows = _rows(chip, K)
    compiled, text = _compile(
      prefill_into_pages_many_sampled_inplace, params, cfg, shard, _sds(chip, (K, S), jnp.int32), pool,
      _sds(chip, (K, table), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS, rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None, rows(jnp.int32),
    )  # fmt: skip
    mem = compiled.memory_analysis()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3  # the flash kernel in each of the three attention layers' loops
    print(f"prefill.pages_many_sampled olmo K={K} S={S} table={table}: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
    # That it compiled is the fit: XLA:TPU refuses 8 x 1024 at a table of 64 from 1700 pages on at the parent ("Used 16.98G of 15.75G"; PERF.md §6, PR 44) and at this pool now.
    # Its temporaries are not a sum to hold against the chip: they overlap the donated pool's buffers (arguments + temp is 16.3 GB for the eight first chunks).
    assert mem.alias_size_in_bytes >= 9 * 64 * 30 * 192 * 96 * 4 and mem.argument_size_in_bytes < 13.0e9


@pytest.mark.parametrize("K,S", [(2, 640), (8, 1024)], ids=["typical_group_2x640", "largest_group_8x1024"])
@pytest.mark.parametrize("config", ["olmo-hybrid-7b-d12", "granite-4.0-h-micro-bf16", "ling-3.0-flash-ep4-d7"])
def test_hybrid_prefill_group_reads_the_admitted_rows_state_where_it_lies(chip, monkeypatch, config, K, S):
  """The three recurrent kinds' prefill program at their cells' pools (64 slots; ISSUE 48), for a typical group and the
  largest one, with the page window the scheduler hands such a group (``_page_window``: 16 pages): the only instructions
  that produce a tensor of an eighth of the ``ssm`` leaf or more out of the leaf are the ``xot.ssm/scatter`` fusions,
  which write the rows' states back into the donated leaf in place — no ``slice``, ``copy``, ``gather`` of it. The
  read of the K admitted rows is K ``dynamic-slice``s of one [H, P, N] row each (``models/decoder.py _state_rows``).
  As a gather (``.at[layer, slot_rows].get``) XLA:TPU cut Olmo's WHOLE leaf in two first, once a Gated-DeltaNet layer —
  ``mini-gather-slice`` f32[9,64,30,128,96] + [9,64,30,64,96], 1.27 GB read and written, 12.8 M of the layer body's
  19.3 M estimated cycles, 1.2 GB of the program's 1.49 GB of temporaries at 2 x 640 (0.29 GB now) — because a face
  192 x 96 is no whole number of lanes; granite's [64, 128] and Ling's [128, 128] already compiled to a row loop."""
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import prefill_into_pages_many_sampled_inplace

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, config)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend
  assert pool["ssm"].shape[1] == 64 and pool["k"].shape[1] == 1537
  rows, window = _rows(chip, K), 1 << (-(-S // PS) - 1).bit_length()
  compiled, text = _compile(
    prefill_into_pages_many_sampled_inplace, params, cfg, Shard(config, 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (K, S), jnp.int32), pool,
    _sds(chip, (K, window), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS, rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None, rows(jnp.int32),
  )  # fmt: skip
  leaf = pool["ssm"]
  of_leaf = f"f32[{','.join(map(str, leaf.shape))}]"
  produced = _materialised(text)
  types = {name: result for name, result, *_ in produced}
  large = []
  for name, result, op, operands, scope in produced:
    dims = re.fullmatch(r"\w+\[([\d,]*)\]\S*", result)  # (a tuple — a loop, an asynchronous start — is what its done or its elements are)
    if op in ("parameter", "get-tuple-element", "bitcast") or not dims or 8 * math.prod(int(d) for d in dims.group(1).split(",") if d) < leaf.size:
      continue
    if result.startswith(of_leaf) or any(of_leaf in types.get(operand, "") for operand in operands):
      large.append((name, result.split("{")[0], op, scope[-40:]))
  assert large and all(op == "fusion" and result == of_leaf and scope.endswith("/xot.ssm/scatter") for _, result, op, scope in large), large
  mem = compiled.memory_analysis()
  print(f"prefill.pages_many_sampled {config} K={K} S={S}: temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes} leaf-sized={[name for name, *_ in large]}")
  assert mem.alias_size_in_bytes >= leaf.size * 4  # the pool is donated: the state is written where it lies
  if config.startswith("olmo") and K == 2:
    assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes


def test_swa_gqa_moe_decode_step_mixed_tick_and_longest_prefill_at_the_cells_settings_fit_v5e(chip, monkeypatch):
  """Laguna-XS.2's first stage as ``laguna-xs.2.agent-closed-64`` serves it (ISSUE 46): 4097 pages of 8 KV heads x 64 x
  128 bf16 in 5 layers (5.37 GB) beside 7.74 GB of weights with every expert held. ``decode.paged_batch`` told
  ``use_kernel`` is accepted by XLA:TPU (arguments 13.11 GB): its three runs of layers — a full layer, three window
  layers, a full layer — each hold one call of the paged kernel, the window layers' under its own name
  ``paged_decode_window`` (groups of 8 query heads a KV head, the full layers' 6: both new to the kernel, at Mistral's
  tile of 8 pages) inside ``xot.attn``, one token write each, and the two expert runs the grouped expert products
  inside ``xot.moe_experts``. The mixed tick with a slice padded to 2048 (the file's budget) fits beside them, and so does the largest
  prefill the ramp meets — a group of 8 rows of 2048 tokens over a 64-page window, the pool donated (a pool this large
  is written in place: inference/batch_ops.py ``init_pool``) — through the flash kernel in all three runs, the window
  layers' with their window."""
  import json

  sys.path.insert(0, str(ROOT / "benchmark"))
  import common

  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_mixed_paged_batch_decode_impl, _fused_paged_batch_decode_impl, full_model_params, prefill_into_pages_many_sampled_inplace
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.ops.paged import PAGE_TILE, _page_tile, init_paged_pool

  monkeypatch.setattr(moe, "_on_tpu", lambda: True)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend
  hf = json.loads((ROOT / "benchmark" / "configs" / "laguna-xs.2-d5.json").read_text())
  cfg = common.model_config(hf)
  n_slots, n_pages = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"])
  on_chip = lambda tree: jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)  # noqa: E731
  params = on_chip(jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS)))
  assert pool["k"].shape == pool["v"].shape == (5, n_pages, 8, PS, 128) and set(pool) == {"k", "v"} and n_pages >= 3073
  assert {name: st["wq"].shape for name, st in params.items() if isinstance(st, dict)} == {"layers": (1, 2048, 6144), "window_moe_layers": (3, 2048, 8192), "moe_layers": (1, 2048, 6144)}
  shard, mp = Shard("laguna", 0, cfg.n_layers - 1, cfg.n_layers), pages_to_cover(cfg.max_seq_len, PS)
  rows, key = _rows(chip, n_slots), _sds(chip, (2,), jnp.uint32)
  decode_args = (params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool, _sds(chip, (n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32))
  compiled, text = _compile(_fused_paged_batch_decode_impl, *decode_args, 8, 64, PS, True, key, None)
  kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
  count = lambda call: sum(f"{call}/pallas_call" in line for line in kernels)  # noqa: E731  (the call's own path, not its operands' names)
  calls = ("jit(_paged_decode_attention_impl)", "jit(_paged_decode_attention_impl)/paged_decode_window", "xot.kv_write/kv_token_write", "xot.moe_experts/moe_gate_up", "xot.moe_experts/moe_down")
  assert len(kernels) == 10 and [count(call) for call in calls] == [2, 1, 3, 2, 2], [line.strip()[-300:] for line in kernels]
  assert all("/xot.attn/jit(_paged_decode_attention_impl)" in line for line in kernels if "_paged_decode_attention_impl" in line)
  assert _page_tile(mp) == PAGE_TILE == 8
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch laguna B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= 2 * 5 * n_pages * 8 * PS * 128 * 2 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9 and abs(mem.argument_size_in_bytes - (7.74e9 + 2 * 5 * n_pages * 8 * PS * 128 * 2)) < 0.01e9
  one = lambda dtype: _sds(chip, (1,), dtype)  # noqa: E731
  compiled, text = _compile(_fused_mixed_paged_batch_decode_impl, *decode_args, _sds(chip, (1, 2048), jnp.int32), _sds(chip, (1, 64), jnp.int32), one(jnp.int32), one(jnp.int32), 8, 64, PS, True, key, None, None)
  mem = compiled.memory_analysis()
  print(f"decode.mixed_paged_batch laguna pad=2048: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert text.count("paged_decode_window") >= 1 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
  K, S, window = 8, 2048, 64
  rows = _rows(chip, K)
  compiled, text = _compile(
    prefill_into_pages_many_sampled_inplace, params, cfg, shard, _sds(chip, (K, S), jnp.int32), pool,
    _sds(chip, (K, window), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS, rows(jnp.float32), rows(jnp.int32), key, 64, None,
  )  # fmt: skip
  mem = compiled.memory_analysis()
  kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
  flash = [line for line in kernels if "xot.moe_experts/moe_" not in line]
  assert len(flash) == 3 and all("flash_attention_prefill" in line for line in flash), [line.strip()[-300:] for line in flash]  # the flash kernel in each of the three runs
  print(f"prefill.pages_many_sampled laguna K=8 S=2048: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  # That it compiled is the fit (its temporaries overlap the donated pool's buffers: PERF.md section 6, PR 44).
  assert mem.alias_size_in_bytes >= 2 * 5 * n_pages * 8 * PS * 128 * 2 and mem.argument_size_in_bytes < 13.2e9


def test_compiler_refuses_a_pool_beyond_the_chip(chip, llama_1b):
  """What makes the test above a fit check: the same step over three times
  the pool is refused at compile time, not at run time."""
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl

  with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
    _compile(_fused_paged_batch_decode_impl, *_decode_step_args(chip, llama_1b, 16, "int8", scale_pages=3))


def test_spec_window_kernel_arm_compiles_for_v5e(chip, llama_1b):
  """``spec.paged_batch`` draft-free (the n-gram default): the verify
  window's kernel arm launches the paged kernel once per window position
  (``paged_window_forward``), here gamma 2 → three launches a round."""
  from xotorch_support_jetson_tpu.models.decoder import _fused_spec_paged_batch_decode_impl

  cfg, shard, params = llama_1b
  n, rounds, gamma = 16, 8, 2
  pool, mp = _smoke_pool(chip, cfg, n, "int8")
  rows = _rows(chip, n)
  _, text = _compile(
    _fused_spec_paged_batch_decode_impl,
    params, None, pool, None, _sds(chip, (n, 1), jnp.int32), _sds(chip, (n, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.int32),
    rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), _sds(chip, (n, rounds * (gamma + 1) + gamma), jnp.int32), rows(jnp.int32), None,
    cfg, shard, None, None, rounds, gamma, 64, PS, True, False,
  )  # fmt: skip
  assert text.count("tpu_custom_call") >= gamma + 1


def test_batched_prefill_takes_the_flash_kernel_on_tpu(chip, llama_1b, monkeypatch):
  """``prefill.pages_many_sampled`` for one PREFILL_BUCKET-wide admission.
  The flash gate asks ``jax.default_backend()``; answered "tpu" for the
  trace, the program must come out holding the kernel."""
  from xotorch_support_jetson_tpu.models.decoder import prefill_into_pages_many_sampled

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  cfg, shard, params = llama_1b
  pool, _ = _smoke_pool(chip, cfg, 16, "int8")
  k = 4
  rows = _rows(chip, k)
  _, text = _compile(
    prefill_into_pages_many_sampled,
    params, cfg, shard, _sds(chip, (k, 128), jnp.int32), pool, _sds(chip, (k, 2), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS,
    rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None,
  )  # fmt: skip
  assert "tpu_custom_call" in text


# ------------------------------------------------- four chips (v5e 2x2)
#
# A Mosaic kernel cannot be partitioned automatically: lowered anywhere but a
# one-device program or a region that is manual over every mesh axis, jax
# refuses it ("wrap the call in a shard_map"). On the chip the flash gate
# answers yes, so both multi-chip serving modes died at their first prefill
# until PR 21; CPU meshes never take the kernel and never saw it.


@pytest.fixture(scope="module")
def four_chips(chip):
  from jax.experimental import topologies

  return list(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices)


def _on(mesh, tree, specs):
  from jax.sharding import NamedSharding, PartitionSpec as P

  return jax.tree.map(lambda leaf, spec: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)), tree, specs, is_leaf=lambda x: isinstance(x, P))


def test_tp_default_prefill_compiles_without_kernels(four_chips, llama_1b, monkeypatch):
  """The no-flag default on a four-chip host is a tp mesh under GSPMD: the
  engine clears ``cfg.mosaic_kernels`` for it, and the prefill compiles on
  the XLA attention path. With the gate left open the same lowering is
  refused — which is what the chip did."""
  from dataclasses import replace

  from jax.sharding import PartitionSpec as P

  from xotorch_support_jetson_tpu.inference.jax_engine import _prefill
  from xotorch_support_jetson_tpu.models.decoder import init_kv_cache
  from xotorch_support_jetson_tpu.parallel.mesh import auto_partitioned, build_mesh, inference_plan, specs_for_params

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  cfg, shard, params = llama_1b
  plan = inference_plan(4, n_heads=cfg.n_heads)
  assert plan.tp == 4 and auto_partitioned(plan)
  mesh = build_mesh(plan, devices=four_chips)
  params = _on(mesh, params, specs_for_params(params, False))
  cache = jax.eval_shape(lambda: init_kv_cache(cfg, cfg.n_layers, 1, cfg.max_seq_len))
  cache = _on(mesh, cache, {k: P(None, None, None, "tp", None) for k in cache})
  tokens, lens = _on(mesh, jax.ShapeDtypeStruct((1, 128), jnp.int32), P()), _on(mesh, jax.ShapeDtypeStruct((1,), jnp.int32), P())
  text = _prefill.lower(params, replace(cfg, mosaic_kernels=False), shard, tokens, cache, lens).compile().as_text()
  assert "tpu_custom_call" not in text
  with pytest.raises(NotImplementedError, match="cannot be automatically partitioned"):
    _prefill.lower(params, cfg, shard, tokens, cache, lens)


def test_pp4_prefill_keeps_the_flash_kernel(four_chips, llama_1b, monkeypatch):
  """``--pp 4`` over four chips: every other mesh axis has size 1, so the
  stage shard_map is manual throughout (parallel/mesh.py manual_axes) and the
  flash kernel stays in — the layer-split ring computes what one device does."""
  from functools import partial

  from jax.sharding import PartitionSpec as P

  from xotorch_support_jetson_tpu.models.decoder import init_kv_cache
  from xotorch_support_jetson_tpu.parallel import pp_serving
  from xotorch_support_jetson_tpu.parallel.mesh import MeshPlan, auto_partitioned, build_mesh, decoder_param_specs, manual_axes

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  cfg, _, params = llama_1b
  plan = MeshPlan(pp=4)
  assert not auto_partitioned(plan, "pp")
  mesh = build_mesh(plan, devices=four_chips)
  assert manual_axes(mesh, "pp") == frozenset(mesh.axis_names)
  # PPServing's constructor places real arrays; build its programs around shapes instead.
  srv = object.__new__(pp_serving.PPServing)
  srv.mesh, srv.cfg, srv.n_stages, srv.is_first, srv.is_last, srv.n_prefix = mesh, cfg, 4, True, True, 0
  srv._sm = partial(jax.shard_map, mesh=mesh, axis_names=manual_axes(mesh, "pp"), check_vma=False)
  srv._build()
  specs = decoder_param_specs()
  stage = {k: jax.ShapeDtypeStruct((4, v.shape[0] // 4, *v.shape[1:]), v.dtype) for k, v in params["layers"].items()}
  stage = _on(mesh, stage, {k: P("pp", *specs["layers"].get(k, P())) for k in stage})
  head = {k: v for k, v in params.items() if k != "layers"}
  head = _on(mesh, head, {k: specs.get(k, P()) for k in head})
  cache = jax.eval_shape(lambda: init_kv_cache(cfg, cfg.n_layers, 1, cfg.max_seq_len))
  cache = _on(mesh, cache, {k: pp_serving.pp_cache_spec(cfg, mesh) for k in cache})
  tokens, lens = _on(mesh, jax.ShapeDtypeStruct((1, 128), jnp.int32), P()), _on(mesh, jax.ShapeDtypeStruct((1,), jnp.int32), P())
  text = srv._prefill_fn.lower(stage, head, tokens, tokens, cache, lens).compile().as_text()
  assert "tpu_custom_call" in text


# ---------------------------------------------- compile cache placement

_PLACE = "from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache as c; import jax; print(c()); print(jax.config.jax_compilation_cache_dir)"


def _placed(env: dict, cwd) -> list[str]:
  env = {**os.environ, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu", **env}
  env = {k: v for k, v in env.items() if v is not None}
  return subprocess.run([sys.executable, "-c", _PLACE], capture_output=True, text=True, timeout=120, cwd=cwd, env=env, check=True).stdout.split()


def test_compile_cache_dir_from_outside_is_left_to_jax(tmp_path):
  """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it, the code sets nothing
  (the config still holds exactly the variable's value)."""
  assert _placed({"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}, tmp_path) == [str(tmp_path / "cc")] * 2


def test_compile_cache_default_dir_is_fixed_inside_the_checkout(tmp_path):
  """Unset: one fixed directory at the root of the checkout, the same from
  two processes started in different places — the path is part of the
  cache's key, so a directory that moves never hits."""
  first, second = _placed({"JAX_COMPILATION_CACHE_DIR": None}, ROOT), _placed({"JAX_COMPILATION_CACHE_DIR": None}, tmp_path)
  assert first == second == [str(ROOT / ".xot_compile_cache")] * 2


# One traced function under one component scope, compiled through the placed
# cache: argv = blank lines before the function's source, the scope's name.
_KEYED = """
import os, sys, jax, jax.numpy as jnp
from xotorch_support_jetson_tpu.utils.helpers import configure_compile_cache
configure_compile_cache()
src = "\\n" * int(sys.argv[1]) + "def f(x):\\n  with jax.named_scope('" + sys.argv[2] + "'):\\n    return jnp.dot(x, x) + 1\\n"
ns = {"jax": jax, "jnp": jnp}
exec(compile(src, "model_code.py", "exec"), ns)
jax.jit(ns["f"])(jnp.ones((8, 8))).block_until_ready()
print(sorted(n for n in os.listdir(os.environ["JAX_COMPILATION_CACHE_DIR"]) if n.startswith("jit_f-")))
"""


def test_compile_cache_key_holds_the_scope_names_and_no_source_lines(tmp_path):
  """A cached executable carries the ``xot.*`` scope names of the code that
  compiled it and the profiler reads them back, so an entry is never served
  to code that names its ops otherwise; a line that only moves (every later
  edit of a traced file) must find the entry again."""
  env = {**os.environ, "PYTHONPATH": str(ROOT), "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}

  def entries(*argv: str) -> list[str]:
    out = subprocess.run([sys.executable, "-c", _KEYED, *argv], capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env, check=True).stdout
    return eval(out.strip().splitlines()[-1])  # noqa: S307 — our own child's printed list

  first = entries("0", "xot.attn")
  assert len(first) == 1
  assert entries("7", "xot.attn") == first  # the same code seven lines further down: a hit
  assert len(entries("0", "xot.ffn")) == 2  # another scope name: its own entry


# ------------------------------------------------------- chip_smoke.py


def _smoke(*args, env=None, timeout=600):
  return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"), *args], capture_output=True, text=True, timeout=timeout, cwd=ROOT, env=env)


def test_chip_smoke_without_a_tpu_fails(tmp_path):
  """No accelerator and no rehearsal option: a non-zero exit and
  ``"ok": false`` — never a CPU result under the chip's name."""
  import json

  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
  out = _smoke(env=env)
  last = json.loads(out.stdout.strip().splitlines()[-1])
  assert out.returncode != 0 and last["ok"] is False, out.stdout[-2000:] + out.stderr[-2000:]
  assert '"platform": "tpu"' not in out.stdout


def test_chip_smoke_cpu_rehearsal(tmp_path):
  """The whole control flow of ``chip_smoke.py`` — checkpoint from a seed,
  kernels against references (interpret mode), a solo daemon and a batched
  daemon each answering blocking, streamed and concurrent requests, the
  program ledger read back — at tiny width behind its explicit option."""
  import json

  env = {**os.environ, "JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
  out = _smoke("--cpu-rehearsal", env=env)
  assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
  lines = [json.loads(line) for line in out.stdout.strip().splitlines()]
  assert lines[-1] == {"ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
  phases = {line["phase"]: line for line in lines if "phase" in line}
  assert {"kernels", "solo", "batched"} <= set(phases) and all(p["ok"] for p in phases.values())
  assert phases["solo"]["blocking_equals_streaming"] and phases["batched"]["blocking_equals_streaming"]
  assert '"platform": "tpu"' not in out.stdout
