"""A cell's whole program at its settings, compiled for a described v5e (``tests/described_chip.py``): the decode step,
the longest prefill group and the mixed tick of each benchmark configuration that has a kind of its own, as shapes at
the published widths with the cell's slots and pages. What XLA:TPU refuses for 16 GB of HBM it refuses here, at no
chip time; what it copies or relays of a stacked leaf is read off the optimised text. Nothing runs. These are the
suite's longest compiles (30-110 s each), which is why they have a module, and so a worker, of their own.
"""

import math
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from described_chip import PS, ROOT, _compile, _fp32_matmuls, _ling_at_the_cells_settings, _materialised, _mosaic_calls, _rows, _sds, _takers, chip  # noqa: F401 — the fixtures are taken in by name


def _pool_sized(text: str, leaf) -> list[str]:
  """The instructions of an optimised HLO text that PRODUCE a value with as many elements as a K/V pool leaf (or its
  lane-padded double) by a ``pad``, ``slice``, ``copy`` or ``transpose``: what making the kernel's form of a pool of
  64-channel heads once a dispatch cost until ISSUE 58 (a padded copy each of K and V, a relaid copy of V, two cuts back)."""
  out = []
  for line in text.splitlines():
    m = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]+)\]\S* (pad|slice|copy|copy-start|transpose)\(", line)
    if m and math.prod(int(d) for d in m.group(1).split(",")) in (leaf.size, 2 * leaf.size):
      out.append(line.strip()[:160])
  return out


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
  assert pool["k"].shape == pool["v"].shape == (4, n_pages, 4, PS, 128) and pool["ssm"].shape == (36, n_slots, 64, 64, 128) and pool["conv"].shape == (36, n_slots, 3, 4352)
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
  # The pages' 8 KV heads of 64 are stored in pairs on the lanes (ops/paged.py, the module note, ISSUE 58): the stored
  # pool is the kernel's form, and nothing pads, cuts, copies or relays a K/V leaf (until then 9 % of the cell's step).
  assert not _pool_sized(text, pool["k"]), _pool_sized(text, pool["k"])
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch granite B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_kda_hybrid_decode_step_at_the_cells_settings_fits_v5e(chip, monkeypatch):
  """Ling-3.0-flash's first stage at one chip's share, as ``ling-3.0-flash.decode-closed-64`` serves it (ISSUE 36): 64
  slots, 1537 latent pages of ONE attention layer, bf16, 128 of 512 experts held. ``decode.paged_batch`` is accepted by
  XLA:TPU beside 10.3 GB of weights, 0.81 GB of float32 matrix state and 0.11 GB of pages. The state leaf is one buffer
  from the donated argument to the result: no instruction copies it or a layer of it; it is read at (layer) and written
  back by the Mosaic call ``delta_state_step`` (ISSUE 45: one call in each of the three runs of KDA layers, the leaf
  aliased through it; until then by two fusions of the XLA expression), which no fusion shares it with. The other Mosaic calls
  are the experts' two (ISSUE 40: ``moe_gate_up``, ``moe_down``, in both stacks' loops) and, since ISSUE 52, the latent
  layer's two — ``paged_decode_latent`` and ``kv_token_write``, which ``paged_kernel_supported`` resolves for it on a
  TPU (``test_latent_attention_decode_step_walks_its_rows_pages_in_the_kernel``); the experts' take the STACKED expert leaves: no stacked expert leaf, and no layer of one, is copied, cut out or relaid (a copy
  of a stack is 3.8 GB, of a layer 0.75 GB a step)."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl
  from xotorch_support_jetson_tpu.ops.paged import paged_kernel_supported

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch)
  assert paged_kernel_supported(cfg, "tpu") and not paged_kernel_supported(cfg, "cpu")
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
  assert sorted(set(calls)) == ["delta_state_step", "kv_token_write", "moe_down", "moe_gate_up", "paged_decode_latent"] and calls.count("delta_state_step") == 3, calls
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
def test_latent_attention_decode_step_walks_its_rows_pages_in_the_kernel(chip, monkeypatch, config):
  """The two latent-attention configurations of the benchmark at their cells' settings, told the kernel (``use_kernel``
  True, what ``paged_kernel_supported`` resolves for them on a TPU since ISSUE 52; until then this test held the
  opposite — ``kernel_attends`` sent MLA to the XLA gather whatever it was told, which read every row's whole
  4096-token table twice a layer, in float32). Their ``decode.paged_batch`` holds the kernel's latent body
  ``paged_decode_latent`` and the Mosaic token write once a loop of latent layers, inside ``xot.attn`` and
  ``xot.kv_write``; nothing anywhere in the optimised text has a gathered window's shape ([slots, 4096, ·] or
  [slots, 64 pages, ·]) or is a float32 latent; the latent leaf is one buffer from the donated argument to the result,
  taken by the two Mosaic calls alone; the rope leaf (64 lanes stored, and stored by the TPU with the pages along the
  lanes) is brought to the kernel's form once a dispatch — a copy and a pad before the step loop, a copy and a slice
  after it, none inside — and the program fits 15.75 GB. So a change to the paged-decode kernel's walk DOES move these
  two cells now. Moonlight is cut to its dense layer and two expert layers, in bf16: which attention core a program
  takes does not depend on depth (the whole cell's program, 14 layers in int8, compiled by hand: PERF.md §6, PR 52)."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl
  from xotorch_support_jetson_tpu.ops.paged import kernel_attends, paged_kernel_supported

  # Moonlight's file names no pool: the scheduler's default for it is a table a slot, 16 x 64 pages and the trash page
  # (a rope leaf of a few MB the compiler would keep in VMEM, in pieces: not the cell's program).
  cut = {} if config.startswith("ling") else {"num_hidden_layers": 3, "serving_env": {"XOT_TPU_BATCH_SLOTS": "16", "XOT_TPU_BATCH_PAGES": "1025"}}
  hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, config, **cut)
  n_slots = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"])
  assert cfg.is_mla and paged_kernel_supported(cfg, "tpu") and kernel_attends(cfg, True) and not paged_kernel_supported(cfg, "cpu")
  L, P = pool["k"].shape[:2]
  assert pool["k"].shape == (L, P, 1, PS, 512) and pool["v"].shape == (L, P, 1, PS, 64) and cfg.max_seq_len == 4096
  rows = _rows(chip, n_slots)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, Shard(config, 0, cfg.n_layers - 1, cfg.n_layers), _sds(chip, (n_slots, 1), jnp.int32), pool,
    _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  calls = _mosaic_calls(text)
  loops = 1 if config.startswith("ling") else 2  # Ling's one latent layer; Moonlight's dense layer and its expert layers
  assert calls.count("paged_decode_latent") == calls.count("kv_token_write") == loops and not [name for name in calls if name.startswith("paged_decode") and name != "paged_decode_latent"], calls
  kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
  assert all("/xot.attn/jit(_paged_decode_attention_impl)/paged_decode_latent/pallas_call" in line for line in kernels if "paged_decode_latent" in line)
  gathered = re.findall(rf"(?:bf16|f32)\[{n_slots},(?:4096|64,1,64|64,64,1|64,64),(?:512|64|128)\]", text)
  assert not gathered, gathered[:4]
  assert not re.search(r"f32\[[\d,]+,(?:64|4096),512\]", text)  # no latent page, and no window of them, in float32
  produced = _materialised(text)
  latent, rope = f"bf16[{L},{P},1,{PS},512]", (f"bf16[{L},{P},1,{PS},64]", f"bf16[{L},{P},1,{PS},128]")
  assert {op for _, result, op, *_ in produced if result.startswith(latent)} <= {"parameter", "get-tuple-element"}
  takers = _takers(text, re.escape(latent))
  assert len(takers) == 2 * loops and all(op == "custom-call" for _, op in takers), takers
  moved = [(op, scope) for _, result, op, _, scope in produced if result.startswith(rope) and op not in ("parameter", "get-tuple-element", "copy-start", "copy-done")]
  assert sorted(op for op, _ in moved) == ["copy", "copy", "pad", "slice"] and not [scope for _, scope in moved if "/while/" in scope], moved
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch {config} B={n_slots}: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= L * P * PS * 576 * 2  # the pool is donated: the pages are written where they lie
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


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
  assert {name for name in _mosaic_calls(text) if name.startswith("moe_")} == {"moe_gate_up_rows", "moe_down_rows"}  # 64 rows an expert of the router's 512: the aligned walk (ISSUE 56)
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


def test_swa_nope_moe_decode_step_mixed_tick_and_longest_prefill_at_the_cells_settings_fit_v5e(chip, monkeypatch):
  """SmallThinker-21BA3B's first stage as ``smallthinker-21ba3b.longdoc-closed-32`` serves it (ISSUE 50): the file's
  pages of 4 KV heads x 64 x 128 bf16 in 8 layers beside 7.93 GB of weights with every expert held, block tables of 256
  pages a row (the model's whole context, 16384). ``decode.paged_batch`` told ``use_kernel`` is accepted by XLA:TPU: its
  four runs of layers — a global layer, three window layers, a global layer, three window layers — each hold one call
  of the paged kernel at groups of SEVEN query heads a KV head (which no other configuration has), the window layers'
  under its own name ``paged_decode_window`` (a window of 64 pages), one token write each, and the grouped expert
  products, ReLU-gated, inside ``xot.moe_experts``; the router's top-k lies AHEAD of the run's attention call in the
  program's text as it does in the model. The mixed tick with a slice padded to 2048 (the file's budget) over a 256-page
  window fits beside them, and so do the largest prefill groups the ramp meets (below), the pool donated."""
  import json

  sys.path.insert(0, str(ROOT / "benchmark"))
  import common

  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_mixed_paged_batch_decode_impl, _fused_paged_batch_decode_impl, full_model_params, prefill_into_pages_many_sampled_inplace
  from xotorch_support_jetson_tpu.ops import moe
  from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

  monkeypatch.setattr(moe, "_on_tpu", lambda: True)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend
  hf = json.loads((ROOT / "benchmark" / "configs" / "smallthinker-21ba3b-d8.json").read_text())
  cfg = common.model_config(hf)
  n_slots, n_pages = int(hf["serving_env"]["XOT_TPU_BATCH_SLOTS"]), int(hf["serving_env"]["XOT_TPU_BATCH_PAGES"])
  on_chip = lambda tree: jax.tree.map(lambda x: _sds(chip, x.shape, x.dtype), tree)  # noqa: E731
  params = on_chip(jax.eval_shape(lambda: full_model_params(jax.random.PRNGKey(0), cfg)[0]))
  pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, cfg.n_layers, n_pages, PS)))
  pool_bytes = 2 * 8 * n_pages * 4 * PS * 128 * 2
  assert pool["k"].shape == pool["v"].shape == (8, n_pages, 4, PS, 128) and set(pool) == {"k", "v"} and 4609 <= n_pages <= 5121 and n_slots == 32
  assert {name: (st["wq"].shape, st["w_experts_gate"].shape) for name, st in params.items() if isinstance(st, dict)} == {
    "moe_layers": ((2, 2560, 3584), (2, 64, 2560, 768)), "window_moe_layers": ((6, 2560, 3584), (6, 64, 2560, 768)),
  }
  shard, mp = Shard("smallthinker", 0, cfg.n_layers - 1, cfg.n_layers), pages_to_cover(cfg.max_seq_len, PS)
  assert mp == 256
  rows, key = _rows(chip, n_slots), _sds(chip, (2,), jnp.uint32)
  decode_args = (params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool, _sds(chip, (n_slots, mp), jnp.int32), rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32))
  compiled, text = _compile(_fused_paged_batch_decode_impl, *decode_args, 8, 64, PS, True, key, None)
  kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
  count = lambda call: sum(f"{call}/pallas_call" in line for line in kernels)  # noqa: E731  (the call's own path, not its operands' names)
  calls = ("jit(_paged_decode_attention_impl)", "jit(_paged_decode_attention_impl)/paged_decode_window", "xot.kv_write/kv_token_write", "xot.moe_experts/moe_gate_up", "xot.moe_experts/moe_down")
  assert len(kernels) == 16 and [count(call) for call in calls] == [2, 2, 4, 4, 4], [line.strip()[-300:] for line in kernels]
  assert all("/xot.attn/jit(_paged_decode_attention_impl)" in line for line in kernels if "_paged_decode_attention_impl" in line)
  assert "xot.moe_router" in text
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch smallthinker B=32 pages={n_pages}: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= pool_bytes and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9 and abs(mem.argument_size_in_bytes - (7.93e9 + pool_bytes)) < 0.01e9
  one = lambda dtype: _sds(chip, (1,), dtype)  # noqa: E731
  compiled, text = _compile(_fused_mixed_paged_batch_decode_impl, *decode_args, _sds(chip, (1, 2048), jnp.int32), _sds(chip, (1, mp), jnp.int32), one(jnp.int32), one(jnp.int32), 8, 64, PS, True, key, None, None)
  mem = compiled.memory_analysis()
  print(f"decode.mixed_paged_batch smallthinker pad=2048 window={mp}: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert text.count("paged_decode_window") >= 1 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
  # A mixed tick's two halves walk their rows each its own way (ISSUE 56, ``ops/moe.py grouped_walk``): the decode
  # half's 32 rows the shared walk, as ``decode.paged_batch`` above, the slice's 2048 tokens tiles of ONE expert.
  names = _mosaic_calls(text)
  assert [names.count(name) for name in ("moe_gate_up", "moe_down", "moe_gate_up_rows", "moe_down_rows")] == [4, 4, 4, 4], names
  # The prefill groups the ramp meets, the pool donated: ``_group_rows`` holds rows x page window to 8 first chunks' (256
  # pages in all), so 8 rows of a first chunk over 32 pages and ONE row of a 12 k-token prompt's last chunk over the
  # whole 256 are the most K/V a group gathers beside its activations (8 rows over 256 pages would be 4.2 GB of
  # temporaries, which XLA:TPU refuses beside this pool) — through the flash kernel in all four runs of layers. (With
  # the chunk at 4096, measured and set aside, 8 rows x 4096 over 64 pages take 2.89 GB of temporaries and still fit.)
  for K, S, window in ((8, 2048, 32), (1, 2048, mp)):
    rows = _rows(chip, K)
    compiled, text = _compile(
      prefill_into_pages_many_sampled_inplace, params, cfg, shard, _sds(chip, (K, S), jnp.int32), pool,
      _sds(chip, (K, window), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS, rows(jnp.float32), rows(jnp.int32), key, 64, None,
    )  # fmt: skip
    mem = compiled.memory_analysis()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    flash = [line for line in kernels if "xot.moe_experts/moe_" not in line]
    assert len(flash) == 4 and all("flash_attention_prefill" in line for line in flash), [line.strip()[-300:] for line in flash]
    assert {name for name in _mosaic_calls(text) if name.startswith("moe_")} == {"moe_gate_up_rows", "moe_down_rows"}
    print(f"prefill.pages_many_sampled smallthinker K={K} S={S} window={window}: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
    # That it compiled is the fit (its temporaries overlap the donated pool's buffers: PERF.md section 6, PR 44).
    assert mem.alias_size_in_bytes >= pool_bytes and mem.argument_size_in_bytes < 13.4e9


def test_hybrid_ssm_moe_decode_step_and_longest_prefill_at_the_cells_settings_fit_v5e(chip, monkeypatch):
  """Nemotron-3-Nano's first stage as ``nemotron-3-nano.reason-closed-64`` serves it (ISSUE 53): 64 slots, 5121 pages of
  ONE attention layer (2 KV heads of 128, 16 queries a KV head — the widest group in the benchmark; the paged kernel,
  the token write and the flash prefill needed no tile rule), bf16, all 128 ungated experts of 4 expert blocks held:
  12.15 GB of weights, 0.55 GB of state, 0.34 GB of pages. ``decode.paged_batch``'s Mosaic calls are the grouped state
  step (``ssm_state_step`` with B and C a head, one call in each of the three runs of Mamba steps, the leaf aliased
  through it), the ungated experts' two (``moe_up``, whose block is an expert's whole [1856, 2688] matrix as stored, and
  ``moe_down``) and the attention layer's two. No stacked expert leaf, and no layer of one, is copied, cut out or relaid
  — an up matrix stored [2688, 1856] is: XLA:TPU keeps a stack whose minor axis is no whole number of lanes
  column-major and copies all 5.3 GB of it for the Mosaic call (AOT, PR 53), which is why both matrices of an ungated
  expert are stored [F, D]. The largest prefill program the cell meets, a group of 8 rows padded to 2048 tokens with the
  pool donated, fits beside them."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl, prefill_into_pages_many_sampled_inplace, served_expert_form
  from xotorch_support_jetson_tpu.ops.paged import paged_kernel_supported
  from xotorch_support_jetson_tpu.ops.ssm import state_step_form

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, "nemotron-3-nano-30b-a3b-d9")
  assert paged_kernel_supported(cfg, "tpu") and served_expert_form(params, cfg) == "grouped" and state_step_form(pool["ssm"], True) == "one_pass"
  n_slots = pool["ssm"].shape[1]
  assert pool["k"].shape == (1, 5121, 2, PS, 128) and pool["ssm"].shape == (4, 64, 64, 64, 128) and pool["conv"].shape == (4, 64, 3, 6144)
  assert params["ssm_moe_layers"]["w_experts_up_t"].shape == (3, 128, 1856, 2688) and params["moe_layers"]["w_experts_down"].shape == (1, 128, 1856, 2688) and "w_router" not in params["ssm_mixer_layers"]
  shard, rows = Shard("nemotron", 0, cfg.n_layers - 1, cfg.n_layers), _rows(chip, n_slots)
  window = _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool, window, rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  calls = _mosaic_calls(text)
  # (the paged kernel's call carries no name of its own: counted with the rest — three state steps, three runs of
  # expert steps with two calls each, the attention step's kernel and token write)
  assert sorted(set(calls)) == ["kv_token_write", "moe_down", "moe_up", "ssm_state_step"] and calls.count("ssm_state_step") == 3 and text.count('custom_call_target="tpu_custom_call"') == 3 + 3 * 2 + 2, calls
  experts = r"bf16\[(3,|1,)?128,1856,2688\]"
  assert {op for _, op in _takers(text, experts)} == {"custom-call"}  # the kernels alone take the stacks: no fusion cuts a layer out of one
  state = r"f32\[(4,)?64,64,64,128\]"
  copied = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= ({state}|{experts})\S* (copy|copy-start|transpose)\(", line)]
  assert not copied, copied
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch nemotron B=64: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= 4 * 64 * 64 * 64 * 128 * 4 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9

  K, S = 8, 2048
  rows = _rows(chip, K)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend (through XLA's attention the group's scores alone are 4 GB)
  compiled, text = _compile(
    prefill_into_pages_many_sampled_inplace, params, cfg, shard, _sds(chip, (K, S), jnp.int32), pool, _sds(chip, (K, 32), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS,
    rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None, rows(jnp.int32),
  )  # fmt: skip
  moved = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= {experts}\S* (copy|copy-start|transpose|fusion|dynamic-slice)\(", line)]
  assert not moved, moved
  assert {name for name in _mosaic_calls(text) if name.startswith("moe_")} == {"moe_up_rows", "moe_down_rows"} and "flash_attention_prefill" in text, _mosaic_calls(text)  # pieces of 4096 tokens, 192 rows an expert: the aligned walk (ISSUE 56)
  mem = compiled.memory_analysis()
  print(f"prefill.pages_many_sampled nemotron K=8 S=2048: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


def test_hybrid_conv_moe_decode_step_and_a_prefill_group_at_the_cells_settings_fit_v5e(chip, monkeypatch):
  """LFM2-8B-A1B's first stage as ``lfm2-8b-a1b.decode-closed-128`` serves it (ISSUE 57): 128 slots — twice any other
  cell's —, 2049 pages of 4 attention layers (8 KV heads of 64, 4 queries a KV head: granite's geometry), bf16, all 32
  SwiGLU experts of 14 expert layers held: 10.80 GB of weights, 1.07 GB of pages — stored two KV heads a lane group,
  [4, P, 4, 64, 128] (ops/paged.py, the module note, ISSUE 58), which IS the kernel's form: no instruction of the decode
  program produces a value of a K/V leaf's size, and 3073 pages, every row at its longest context, fit beside the
  weights (refused by 460 MiB while the form was made once a dispatch) — and 12.6 MB of state — the pool has a
  ``conv`` leaf [12, 128, 2, 2048] and NO ``ssm`` leaf, so the decode program has no state-step call at all. Its Mosaic
  calls are the gated experts' two (``moe_gate_up``, ``moe_down``: the shared walk, 17 rows an expert) in each run of
  expert layers and the attention layers' two. No ``copy(`` of a stacked expert leaf's shape, and no layer cut out of
  one, stands ahead of the Mosaic calls; the tail's update is a dynamic-update-slice of the leaf in place, and the leaf
  is copied twice a dispatch (relaid in, relaid out), by no layer. The largest prefill program the cell meets, a group of 8 rows padded to 1024 tokens with the pool donated, fits
  beside the weights."""
  from xotorch_support_jetson_tpu.inference.paging import pages_to_cover
  from xotorch_support_jetson_tpu.inference.shard import Shard
  from xotorch_support_jetson_tpu.models.decoder import _fused_paged_batch_decode_impl, prefill_into_pages_many_sampled_inplace, served_expert_form
  from xotorch_support_jetson_tpu.ops.paged import paged_kernel_supported
  from xotorch_support_jetson_tpu.ops.ssm import state_step_form

  _hf, cfg, params, pool = _ling_at_the_cells_settings(chip, monkeypatch, "lfm2-8b-a1b-d16")
  assert paged_kernel_supported(cfg, "tpu") and served_expert_form(params, cfg) == "grouped" and not cfg.state_matrix and state_step_form(pool.get("ssm"), True, cfg.recurrent_kind) == "no_state_matrix"
  assert set(pool) == {"k", "v", "conv"} and pool["k"].shape == pool["v"].shape == (4, 2049, 4, PS, 128) and pool["conv"].shape == (12, 128, 2, 2048) and pool["conv"].dtype == jnp.bfloat16
  n_slots = pool["conv"].shape[1]
  assert params["ssm_moe_layers"]["w_experts_gate"].shape == (10, 32, 2048, 1792) and params["moe_layers"]["w_experts_down"].shape == (4, 32, 1792, 2048) and params["ssm_layers"]["w_up"].shape == (2, 2048, 7168) and "lm_head" not in params
  shard, rows = Shard("lfm2", 0, cfg.n_layers - 1, cfg.n_layers), _rows(chip, n_slots)
  window = _sds(chip, (n_slots, pages_to_cover(cfg.max_seq_len, PS)), jnp.int32)
  compiled, text = _compile(
    _fused_paged_batch_decode_impl, params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), pool, window, rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )  # fmt: skip
  calls = _mosaic_calls(text)
  assert sorted(set(calls)) == ["kv_token_write", "moe_down", "moe_gate_up"], calls  # no ``ssm_state_step`` / ``delta_state_step`` among them: there is no state matrix to step
  experts = r"bf16\[(10,|4,)?32,(2048,1792|1792,2048)\]"
  assert {op for _, op in _takers(text, experts)} == {"custom-call"}  # the kernels alone take the stacks: no fusion cuts a layer out of one
  tail = r"bf16\[(12,)?128,2,2048\]"
  copied = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= ({tail}|{experts})\S* (copy|copy-start|transpose)\(", line)]
  # No expert stack is copied. The tail leaf is relaid once on the way in and once on the way out of a DISPATCH (12.6 MB
  # each way for eight steps: XLA:TPU keeps the leaf in another layout inside the loops than the one it is handed in) and
  # by no layer and no step: PERF.md section 7, From PR 57.
  assert len(copied) == 2 and all(re.search(rf"= {tail}", line) for line in copied) and "copy(%pool__conv__" in copied[0], copied
  assert not _pool_sized(text, pool["k"]), _pool_sized(text, pool["k"])
  mem = compiled.memory_analysis()
  print(f"decode.paged_batch lfm2 B=128: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.alias_size_in_bytes >= 12 * 128 * 2 * 2048 * 2 and mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
  roomy = {name: _sds(chip, (4, 3073, *leaf.shape[2:]), leaf.dtype) if name in "kv" else leaf for name, leaf in pool.items()}
  mem = _compile(
    _fused_paged_batch_decode_impl, params, cfg, shard, _sds(chip, (n_slots, 1), jnp.int32), roomy, window, rows(jnp.int32), rows(jnp.bool_), rows(jnp.float32), rows(jnp.int32), 8, 64, PS, True,
    _sds(chip, (2,), jnp.uint32), None,
  )[0].memory_analysis()  # fmt: skip
  print(f"decode.paged_batch lfm2 B=128, 3073 pages: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9

  K, S = 8, 1024
  rows = _rows(chip, K)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash gate asks the backend
  compiled, text = _compile(
    prefill_into_pages_many_sampled_inplace, params, cfg, shard, _sds(chip, (K, S), jnp.int32), pool, _sds(chip, (K, 16), jnp.int32), rows(jnp.int32), rows(jnp.int32), PS,
    rows(jnp.float32), rows(jnp.int32), _sds(chip, (2,), jnp.uint32), 64, None, rows(jnp.int32),
  )  # fmt: skip
  moved = [line.strip()[:160] for line in text.splitlines() if re.search(rf"= {experts}\S* (copy|copy-start|transpose|fusion|dynamic-slice)\(", line)]
  assert not moved, moved
  assert {name for name in _mosaic_calls(text) if name.startswith("moe_")} == {"moe_gate_up_rows", "moe_down_rows"} and "flash_attention_prefill" in text, _mosaic_calls(text)  # 8192 tokens x 4 / 32: 1024 rows an expert, the aligned walk
  mem = compiled.memory_analysis()
  print(f"prefill.pages_many_sampled lfm2 K=8 S=1024: arguments={mem.argument_size_in_bytes} temp={mem.temp_size_in_bytes} alias={mem.alias_size_in_bytes}")
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
