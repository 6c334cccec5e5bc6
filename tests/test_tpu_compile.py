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

import re

import jax
import jax.numpy as jnp
import pytest
from described_chip import PS, _compile, _fp32_matmuls, _mosaic_calls, _rows, _sds, _takers, chip  # noqa: F401 — the fixtures are taken in by name

HQ, HKV = 32, 8  # Llama-3.2-1B (hd 64) and Llama-3.1-8B (hd 128) share 32/8 heads


def _compile_paged_kernel(chip, quant: str, batch: int, tile: int, hd: int, mp: int, n_pages: int, hq: int = HQ) -> str:
  from xotorch_support_jetson_tpu.ops.paged import _paged_decode_attention_impl

  paired = quant == "pairs"  # bfloat16 heads of 64 as the pool stores them since ISSUE 58: two a lane group
  quant = "" if paired else quant
  kd = hd // 2 if quant == "int4" else hd
  code = jnp.int8 if quant else jnp.bfloat16
  layers = 2  # the stacked leaves, read at a layer scalar
  leaf = (layers, n_pages, HKV // 2, PS, 2 * hd) if paired else (layers, n_pages, HKV, PS, kd)
  pool = _sds(chip, leaf, code)
  scales = [_sds(chip, (layers, n_pages, HKV, PS, 1), jnp.float32)] * 2 if quant else []
  _, text = _compile(
    _paged_decode_attention_impl,
    _sds(chip, (batch, hq, hd), jnp.bfloat16), _sds(chip, (batch, mp), jnp.int32), _sds(chip, (batch,), jnp.int32), _sds(chip, (1,), jnp.int32), pool, pool, *scales,
    page_size=PS, pages_per_step=tile, kv_quant=quant, interpret=False, **({"paired": True} if paired else {}),
  )  # fmt: skip
  if leaf[-1] % 128 == 0:
    # Code leaves of whole lanes — heads of 128 and 256 as they always were, heads of 64 in pairs — reach the Mosaic call
    # as the operands they are: the call takes two values of the stored shape, and nothing pads or copies one first.
    stored = f"{'s8' if quant else 'bf16'}[{','.join(map(str, leaf))}]"
    call = next(line for line in text.splitlines() if "tpu_custom_call" in line)
    assert call.count(stored) == 2 and not re.search(rf"= {re.escape(stored)}\S* (pad|copy|transpose|slice)\(", text), call[:400]
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
    pytest.param("pairs", 128, 8, 64, 64, 2049, 32, id="lfm2-8b-a1b-served"),  # 32 / 8 heads of 64 in pairs: to the kernel 4 heads of 128 under groups of 8 (ISSUE 58)
    pytest.param("pairs", 64, 8, 64, 32, 1537, 32, id="granite-4.0-h-micro-served"),
  ],
)
def test_paged_decode_kernel_compiles_at_served_shapes(chip, quant, batch, tile, hd, mp, n_pages, hq):
  assert "tpu_custom_call" in _compile_paged_kernel(chip, quant, batch, tile, hd, mp, n_pages, hq)


@pytest.mark.parametrize(
  "batch,heads,layers,n_pages,rope_lanes",
  [
    pytest.param(16, 16, 14, 1025, 128, id="moonlight-a3b-served"),  # the benchmark's cells: rank 512, a table of 64 pages; the rope leaf in the kernel's form
    pytest.param(64, 32, 1, 1537, 128, id="ling-3.0-flash-served"),
    pytest.param(16, 16, 14, 1025, 64, id="moonlight-a3b-stored-leaves"),  # a direct caller's stored leaf: padded per call
  ],
)
def test_paged_decode_latent_body_compiles_at_served_shapes(chip, batch, heads, layers, n_pages, rope_lanes):
  """The kernel's latent body (absorbed MLA, ISSUE 52) at both latent cells' shapes and the served tile: q_abs ‖ q_pe
  [B, H, 512 + 128] float32, the stacked latent and rope leaves in HBM, read at a layer scalar."""
  from xotorch_support_jetson_tpu.ops.paged import PAGE_TILE, _paged_decode_attention_impl

  _, text = _compile(
    _paged_decode_attention_impl,
    _sds(chip, (batch, heads, 640), jnp.float32), _sds(chip, (batch, 64), jnp.int32), _sds(chip, (batch,), jnp.int32), _sds(chip, (1,), jnp.int32),
    _sds(chip, (layers, n_pages, 1, PS, 512), jnp.bfloat16), _sds(chip, (layers, n_pages, 1, PS, rope_lanes), jnp.bfloat16),
    page_size=PS, pages_per_step=PAGE_TILE, kv_quant="", interpret=False, latent_scale=float(192**-0.5),
  )  # fmt: skip
  assert _mosaic_calls(text) == ["paged_decode_latent"]


@pytest.mark.parametrize("quant,hd", [(q, hd) for hd in (64, 128) for q in ("", "int8", "int4")] + [("pairs", 64)])
def test_token_write_kernel_compiles_for_v5e(chip, quant, hd):
  """The kernel path's token write (``write_token_kv`` ``kernel=True``) on a
  stacked pool in the kernel's form, every pool dtype and code width: groups
  of 8/16/32 slots by DMA, the select in 32 bits, the pool aliased through.
  ``pairs``: bfloat16 heads of 64 stored two a lane group (ISSUE 58) — the
  token's [B, Hkv, 64] goes in as [B, Hkv/2, 128], an hd-128 model's write,
  and the program pads and cuts nothing."""
  from xotorch_support_jetson_tpu.ops.paged import kernel_pool_form, stored_pool_form, write_token_kv

  layers, n_pages, batch, mp = 4, 65, 16, 16
  paired, quant = quant == "pairs", "" if quant == "pairs" else quant
  kd = hd // 2 if quant == "int4" else hd
  code = jnp.int8 if quant else jnp.bfloat16
  leaf = (layers, n_pages, HKV // 2, PS, 2 * kd) if paired else (layers, n_pages, HKV, PS, kd)
  pool = {"k": _sds(chip, leaf, code), "v": _sds(chip, leaf, code)}
  new = {"k": _sds(chip, (batch, HKV, kd), code), "v": _sds(chip, (batch, HKV, kd), code)}
  if quant:
    pool.update({name: _sds(chip, (layers, n_pages, HKV, PS, 1), jnp.float32) for name in ("k_scale", "v_scale")})
    new.update({name: _sds(chip, (batch, HKV, 1), jnp.float32) for name in ("k_scale", "v_scale")})

  def write(pool, new, layer, bt, pos):
    return stored_pool_form(write_token_kv(kernel_pool_form(pool), new, layer, bt, pos, PS, kernel=True), pool)

  text = jax.jit(write, donate_argnums=0).lower(pool, new, _sds(chip, (), jnp.int32), _sds(chip, (batch, mp), jnp.int32), _sds(chip, (batch,), jnp.int32)).compile().as_text()
  assert "tpu_custom_call" in text and "kv_token_write" in text
  if paired:
    moved = re.findall(r"[^\n]*= bf16\[[\d,]+\]\S* (?:pad|slice|copy)\([^\n]*", text)  # of K/V, the token's rows or a leaf
    assert not moved, [line[:200] for line in moved[:3]]


# The flash prefill kernel at every benchmark cell's head shapes (hq, hkv, hd, int8 codes, windows): one PREFILL_BUCKET of
# queries and a mixed tick's 2048-token slice, against the longest page window the cell's prompts reach (a power of two).
_FLASH_CELLS = [
  pytest.param(32, 8, 64, "", (0,), 4096, id="llama-1b-default-cache"),
  pytest.param(32, 8, 64, "int8", (0,), 4096, id="llama-1b-int8"),
  pytest.param(28, 4, 128, "", (0, 4096), 16384, id="smallthinker"),
  pytest.param(48, 8, 128, "", (0,), 4096, id="laguna-full"),
  pytest.param(64, 8, 128, "", (512,), 4096, id="laguna-window"),
  pytest.param(32, 8, 128, "int8", (0,), 4096, id="mistral-int8"),
  pytest.param(30, 30, 128, "", (0,), 2048, id="olmo-mha"),
  pytest.param(32, 8, 64, "", (0,), 2048, id="granite-hd64"),
  pytest.param(32, 2, 128, "", (0,), 2048, id="nemotron-group16"),
  pytest.param(8, 1, 256, "", (4096,), 8192, id="hd256-mqa-window"),  # no cell: the widest head ``flash_supported`` admits, whose tiles the rule halves
]


@pytest.mark.parametrize("sq", [128, 2048])
@pytest.mark.parametrize("hq,hkv,hd,quant,windows,skv", _FLASH_CELLS)
def test_flash_prefill_compiles_for_v5e(chip, hq, hkv, hd, quant, windows, skv, sq):
  """Mosaic's word on the tile rule's VMEM and tiling (``ops/pallas_attention.py _tile``), with no chip: the group's
  heads folded into one product's rows, the stored-dtype operands, the scalar-prefetched offsets in the index maps.
  The call carries its own name, which is what a trace lists it under."""
  from xotorch_support_jetson_tpu.ops.pallas_attention import flash_attention_prefill

  kv = _sds(chip, (1, skv, hkv, hd), jnp.int8 if quant else jnp.bfloat16)
  scale = _sds(chip, (1, skv, hkv, 1), jnp.float32) if quant else None
  for window in windows:
    _, text = _compile(flash_attention_prefill, _sds(chip, (1, sq, hq, hd), jnp.bfloat16), kv, kv, _sds(chip, (1,), jnp.int32), scale, scale, interpret=False, window=window)
    assert _mosaic_calls(text) == ["flash_prefill"], window
    assert re.search(rf"%flash_prefill[.\d]* = bf16\[1,{hq},{sq},{hd}\]\S* custom-call", text), window


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


@pytest.mark.parametrize(
  "what,T,L,E,held,D,F,k,dtype,gated,tile",
  [
    ("SmallThinker's slice: 192 rows an expert, one tile of 224", 2048, 8, 64, None, 2560, 768, 6, jnp.bfloat16, True, 224),
    ("Laguna's slice: 64 rows an expert", 2048, 5, 256, None, 2048, 512, 8, jnp.bfloat16, True, 96),
    ("Ling's prefill group: 64 rows an expert of the router's 512, a quarter of them held", 4096, 6, 512, (0, 128), 2560, 768, 8, jnp.bfloat16, True, 96),
    ("Moonlight's prefill group: int8 codes, 384 rows an expert: two tiles of 224", 4096, 13, 64, None, 2048, 1408, 6, jnp.int8, True, 224),
    ("Nemotron's prefill group: ungated, one [1856, 2688] block a visit", 4096, 4, 128, None, 2688, 1856, 6, jnp.bfloat16, False, 224),
  ],
)
def test_a_prompts_expert_product_walks_aligned_tiles_on_v5e(chip, what, T, L, E, held, D, F, k, dtype, gated, tile, monkeypatch):
  """A run of many rows an expert (ISSUE 56) as Mosaic and XLA:TPU lower it for a v5e at the five expert cells' full
  slice or prefill piece: the aligned walk's calls under their own names, at the tile ``grouped_walk`` reads from the
  shapes, inside the 64 MiB VMEM limit; the expert stacks go through the Mosaic calls only (no copy, no cut, no
  relayout); ONE fusion takes the float32 products (the gather of the rows a token's choices name, a whole tile of
  sublanes a token, which the weighted sum reads with no relayout). The temporaries' growth over the shared walk's, stated: at most the ``E_held`` tiles
  the three row buffers are longer by — rows x ((D + F) activations + D float32) — and in fact about half of it, the
  buffers not all living at once (126 MB at SmallThinker's slice, 264 MB at Nemotron's piece of 4096 tokens)."""
  from xotorch_support_jetson_tpu.ops import moe

  E_held = E if held is None else held[1] - held[0]
  scaled = dtype == jnp.int8
  first = [_sds(chip, (L, E_held, D, F), dtype)] * 2 if gated else [_sds(chip, (L, E_held, F, D), dtype)]
  leaves = [*first, _sds(chip, (L, E_held, F, D), dtype)]
  scales = [_sds(chip, (L, E_held, F), jnp.float32), _sds(chip, (L, E_held, F), jnp.float32), _sds(chip, (L, E_held, D), jnp.float32)] if scaled else []
  monkeypatch.setattr(moe, "_on_tpu", lambda: True)  # (the backend here is the CPU)
  assert moe.ffn_form(leaves[0], leaves[-1], None, True, scaled, gated=gated) == "grouped"
  rule = moe.grouped_walk

  def compiled(walk):
    def layer(x, w_router, layer, *rest):  # (a function of its own a walk: a trace is cached by the function)
      ws, sc = rest[: len(leaves)], rest[len(leaves) :]
      return moe.moe_ffn(x, w_router, *(ws if gated else (None, *ws)), k=k, held=held, scales=sc or None, layer=layer, act="silu" if gated else "relu2")

    if walk == "shared":
      monkeypatch.setattr(moe, "grouped_walk", lambda rows, *a, **kw: ("shared", moe.ROW_TILE))
    else:
      seen = []
      monkeypatch.setattr(moe, "grouped_walk", lambda *a, **kw: seen.append(rule(*a, **kw)) or seen[-1])
    out = jax.jit(layer).lower(_sds(chip, (T, D), jnp.bfloat16), _sds(chip, (D, E), jnp.float32), _sds(chip, (), jnp.int32), *leaves, *scales).compile()
    assert walk == "shared" or seen == [("aligned", tile)], seen
    return out

  aligned = compiled("aligned")
  text = aligned.as_text()
  assert sorted(_mosaic_calls(text)) == ["moe_down_rows", "moe_gate_up_rows" if gated else "moe_up_rows"], _mosaic_calls(text)
  stack = rf"{'s8' if scaled else 'bf16'}\[{L},{E_held},({D},{F}|{F},{D})\]"
  assert {op for _, op in _takers(text, stack)} == {"custom-call"}
  rows = (T * k // tile + E_held) * tile
  takers = _takers(text, rf"f32\[{rows},{D}\]")
  assert [op for _, op in takers] == ["fusion"] * (2 if rows == T * 8 else 1), takers  # ONE taker: the gather of the rows a token's choices name (at Moonlight's shapes what it gathers has the products' shape, and a taker of its own)
  assert not re.search(rf"= f32\[{T},8,{D}\]\S* (copy|reshape|transpose)\(", text)  # which are a whole tile of sublanes a token: no relayout ahead of the sum
  grown = E_held * tile * ((D + F) * 2 + D * 4)
  temp, before = aligned.memory_analysis().temp_size_in_bytes, compiled("shared").memory_analysis().temp_size_in_bytes
  print(f"{what}: temporaries {before} shared -> {temp} aligned ({rows} rows for {T * k} assignments; stated growth {grown})")
  assert 0 < temp - before < grown, (what, before, temp, grown)


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
