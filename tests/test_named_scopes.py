"""The model-component scopes of the fused programs (ISSUE 24).

``jax.named_scope`` names are HLO metadata: the profiler's trace shows them as
each device op's ``op_name``, which is how ``benchmark/span_lib.py`` splits a
decode step's device time by component. Two claims, both on the CPU compile of
the tiny ``decode.paged_batch``: every name of the vocabulary reaches the
compiled program, and the optimised program is the same with and without them.
The flavour ``mixed`` is ``decode.mixed_paged_batch`` (ISSUE 55): the same two
claims, and its prefill half under the one outer scope ``mixed.prefill``.
"""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xotorch_support_jetson_tpu.models.config import tiny_test_config
from xotorch_support_jetson_tpu.models.decoder import MIXED_PREFILL_SCOPE, _fused_mixed_paged_batch_decode_impl, _fused_paged_batch_decode_impl, full_model_params
from xotorch_support_jetson_tpu.models.quantize import quantize_params
from xotorch_support_jetson_tpu.ops.paged import init_paged_pool

# One program is compiled here under two sets of scope names, and op metadata is no part of the suite's
# persistent-cache key (tests/conftest.py): a hit would hand the second compile the first one's names.
pytestmark = pytest.mark.usefixtures("no_persistent_compile_cache")

PS = 16
COMMON = {"xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.dequant", "xot.head", "xot.sample"}
CONFIGS = {
  "dense": (dict(n_layers=2, max_seq_len=128), "int8", COMMON | {"xot.ffn"}),
  "mixed": (dict(n_layers=2, max_seq_len=128), "int8", COMMON | {"xot.ffn"}),  # the dense model's mixed tick: a slice of 11 tokens padded to 16 beside the two decode rows
  "mla_moe": (
    dict(
      n_layers=2, max_seq_len=128, n_heads=4, n_kv_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
      n_experts=4, n_active_experts=2, moe_hidden_dim=32, first_k_dense=1, shared_expert_dim=32,
    ),
    None,
    COMMON | {"xot.ffn", "xot.moe_router", "xot.moe_experts", "xot.moe_shared"},
  ),
}


def _traced(flavor: str):
  """``decode.paged_batch`` (``decode.mixed_paged_batch`` for the flavour ``mixed``) for a tiny int8 model, traced and not yet lowered."""
  overrides, kv_quant, _ = CONFIGS[flavor]
  cfg = tiny_test_config(**overrides)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg)
  params = quantize_params(params)
  B, mp = 2, 128 // PS
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * mp + 2, PS, quant=kv_quant)
  bt = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
  decode = (params, cfg, shard, jnp.ones((B, 1), jnp.int32), pool, bt, jnp.asarray([3, 5], jnp.int32), jnp.ones((B,), bool), jnp.zeros((B,), jnp.float32), jnp.full((B,), 8, jnp.int32))
  static = (4, 8, PS, False, jax.random.PRNGKey(1), None)
  if flavor != "mixed":
    return _fused_paged_batch_decode_impl.xot_jitted.trace(*decode, *static)
  pf_bt = jnp.asarray([[1 + B * mp, 2 + B * mp]], jnp.int32)  # the admission's own two pages: positions 4..14 of its prompt
  return _fused_mixed_paged_batch_decode_impl.xot_jitted.trace(*decode, jnp.ones((1, 16), jnp.int32), pf_bt, jnp.asarray([4], jnp.int32), jnp.asarray([15], jnp.int32), *static, None)


def _lowered(flavor: str):
  return _traced(flavor).lower()


@functools.cache
def _compiled(flavor: str) -> str:
  """The optimised text of ``_lowered(flavor)`` with its scopes: both tests read it, one compile."""
  return _lowered(flavor).compile().as_text()


def _op_names(text: str) -> list[str]:
  return re.findall(r'op_name="([^"]*)"', text)


def _scopes_in(text: str) -> set[str]:
  return set(re.findall(r"xot\.[a-z_]+", " ".join(_op_names(text))))


_METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
_STACK_TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ [\"{].*)\n", re.M)


_NUMBERED = re.compile(r"[A-Za-z_][\w-]*\.\d+")


def _program(text: str) -> str:
  """Optimised HLO text without what only describes its source: per-instruction
  metadata, the stack-frame tables, and the instruction names, which become
  serials in order of appearance. The scopes change how many instructions the
  unoptimised module holds, hence every later serial; and a ``conditional``'s
  branch computation (``_next_token_batched``'s draw, ISSUE 47) names its
  parameter after the innermost scope — ``xot_sample`` with, the function's
  own name without. What an instruction does is in its text, not in its name."""
  seen: dict[str, str] = {}

  def renumber(m):
    return seen.setdefault(m.group(0), f"#{len(seen)}")

  return _NUMBERED.sub(renumber, _STACK_TABLES.sub("", _METADATA.sub("", text)))


@pytest.mark.parametrize("flavor", sorted(CONFIGS))
def test_every_scope_reaches_the_compiled_decode_program(flavor):
  text = _compiled(flavor)
  assert _scopes_in(text) >= CONFIGS[flavor][2], sorted(CONFIGS[flavor][2] - _scopes_in(text))
  # nested: a dequantisation names its component first, so a reader can split it from the einsum beside it
  assert re.search(r'op_name="[^"]*xot\.(attn_proj|ffn|moe_experts|moe_shared|head|attn)/[^"]*xot\.dequant', text)


@pytest.mark.parametrize("flavor", sorted(CONFIGS))
def test_scopes_do_not_change_the_optimised_program(flavor, monkeypatch):
  with_scopes = _compiled(flavor)
  monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
  jax.clear_caches()  # the traced jaxpr carries the name stack
  try:
    lowered = _lowered(flavor)
  finally:
    monkeypatch.undo()
    jax.clear_caches()
  without = lowered.compile().as_text()
  assert not _scopes_in(without) and MIXED_PREFILL_SCOPE not in " ".join(_op_names(without))
  assert _program(with_scopes) == _program(without)


def test_a_mixed_ticks_prefill_half_lies_under_one_outer_mark_and_its_scan_under_none():
  """``decode.mixed_paged_batch`` is two halves in sequence (ISSUE 55): every equation of the slice's gather, forward
  and scatter carries ``mixed.prefill`` as the OUTERMOST part of its name stack, ahead of any ``xot.`` component —
  ``benchmark/span_lib.component_of`` keeps the ``xot.`` parts and reads what it read, ``benchmark/half_lib.py`` splits
  by the one part — and the decode scan, which is the plain program's, carries it nowhere. In the compiled program
  the slice's ops read ``…/mixed.prefill/…xot.<component>…`` and the scan's ops, ``xot.sample`` among them, do not."""
  eqns = _traced("mixed").jaxpr.eqns
  stacks = [str(e.source_info.name_stack) for e in eqns]
  scan = max(i for i, e in enumerate(eqns) if e.primitive.name == "scan")  # the decode chunk's steps: the last loop of the program
  assert all(s.split("/")[0] == MIXED_PREFILL_SCOPE for s in stacks[:scan]) and scan > 10
  assert not any(MIXED_PREFILL_SCOPE in s for s in stacks[scan:])  # the scan's own equations name their stacks from the scan inwards
  names = [n.split("/") for n in _op_names(_compiled("mixed"))]
  marked = [n for n in names if MIXED_PREFILL_SCOPE in n]
  plain = [n for n in names if MIXED_PREFILL_SCOPE not in n]
  scoped = lambda group: {p for n in group for p in n if p.startswith("xot.")}  # noqa: E731
  assert scoped(marked) >= {"xot.embed", "xot.attn_proj", "xot.kv_write", "xot.attn", "xot.ffn", "xot.dequant"}
  assert scoped(plain) >= COMMON | {"xot.ffn"} and "xot.sample" not in scoped(marked)  # an intermediate slice samples nothing
  assert all(n.index(MIXED_PREFILL_SCOPE) < min(i for i, p in enumerate(n) if p.startswith("xot.")) for n in marked if scoped([n]))


def test_kernel_path_scopes_the_token_write_and_the_attention_kernel():
  """On the kernel path both halves of a layer's cache traffic are Mosaic
  calls (ISSUE 29): the token write under ``xot.kv_write`` — and not under a
  name the roofline reader counts as the attention kernel — and the paged
  kernel under ``xot.attn``. Lowered for the TPU platform on the CPU: the
  Mosaic lowering needs no chip, and the locations carry the name stack."""
  cfg = tiny_test_config(n_layers=2, max_seq_len=128, dim=512)  # head_dim 128: a pool leaf of whole lanes
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg)
  B, mp = 2, 128 // PS
  pool = init_paged_pool(cfg, shard.n_shard_layers, 1 + B * mp, PS, quant="int8")
  bt = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
  args = (
    params, cfg, shard, jnp.ones((B, 1), jnp.int32), pool, bt, jnp.asarray([3, 5], jnp.int32), jnp.ones((B,), bool),
    jnp.zeros((B,), jnp.float32), jnp.full((B,), 8, jnp.int32), 4, 8, PS, True, jax.random.PRNGKey(1), None,
  )
  text = _fused_paged_batch_decode_impl.xot_jitted.trace(*args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
  locs = dict(re.findall(r"(#loc\d+) = loc\((.*)\)", text))
  kernels = [locs[ref] for ref in re.findall(r"stablehlo\.custom_call @tpu_custom_call.*?loc\((#loc\d+)\)", text)]
  writes = [where for where in kernels if "kv_token_write" in where]
  assert len(kernels) == 2 and len(writes) == 1, kernels  # one write call and one attention call in the layer loop's body
  assert writes[0].startswith('"xot.kv_write/kv_token_write') and "paged_decode" not in writes[0]
  attention = [locs[ref] for ref in re.findall(r"call @_paged_decode_attention_impl.*?loc\((#loc\d+)\)", text)]
  assert len(attention) == 1 and "xot.attn/" in attention[0], attention


@pytest.mark.parametrize("router_input", ["ffn", "attn"])
def test_the_routing_is_drawn_under_its_scope_on_the_side_of_the_attention_the_model_says(router_input):
  """``xot.moe_router`` is emitted where the routing is DRAWN (ops/moe.py ``route``): after the attention's scopes for a
  router that reads its experts' input, ahead of them for one that reads the attention's (``cfg.router_input`` "attn",
  ISSUE 50) — in the paged decode layer step as traced, equation by equation. Either way the one top-k of the layer lies
  under the scope, and the experts' product follows the attention."""
  from xotorch_support_jetson_tpu.models.decoder import _paged_layer_step
  from xotorch_support_jetson_tpu.ops.rope import rope_inv_freq

  cfg = tiny_test_config(n_layers=1, max_seq_len=128, n_experts=4, n_active_experts=2, moe_hidden_dim=32, router_input=router_input)
  params, shard = full_model_params(jax.random.PRNGKey(0), cfg)
  lp = {name: leaf[0] for name, leaf in params["moe_layers"].items()}
  B, mp = 2, 128 // PS
  pool = init_paged_pool(cfg, 1, 1 + B * mp, PS)
  bt = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
  step = lambda h, pool: _paged_layer_step(h, pool, lp, 0, bt, jnp.asarray([[3], [5]], jnp.int32), rope_inv_freq(cfg), cfg, PS, False)  # noqa: E731
  eqns = jax.make_jaxpr(step)(jnp.ones((B, 1, cfg.dim), jnp.float32), pool).jaxpr.eqns
  stacks = [str(e.source_info.name_stack) for e in eqns]
  at = lambda scope: [i for i, s in enumerate(stacks) if scope in s.split("/")]  # noqa: E731
  router, attn, experts = at("xot.moe_router"), at("xot.attn"), at("xot.moe_experts")
  assert router and attn and experts and min(experts) > max(attn)
  top_k = [i for i, e in enumerate(eqns) if e.primitive.name == "top_k"]
  assert len(top_k) == 1 and top_k[0] in router
  assert (top_k[0] < min(attn)) == (router_input == "attn") and (min(router) < min(attn)) == (router_input == "attn")


def _unscoped_primitives(jaxpr, prefix: str = "") -> set:
  """The primitives of a traced program's equations — those of its loops', branches' and calls' bodies too — that no
  ``xot.<component>`` scope names: what a device trace files under ``decode_unscoped_device_ms``."""
  out = set()
  for eqn in jaxpr.eqns:
    stack = f"{prefix}/{eqn.source_info.name_stack}"
    bodies = [b for v in eqn.params.values() for b in (v if isinstance(v, (tuple, list)) else (v,)) if hasattr(b, "eqns") or hasattr(getattr(b, "jaxpr", None), "eqns")]
    for body in bodies:
      out |= _unscoped_primitives(getattr(body, "jaxpr", body), stack)
    if not bodies and "xot." not in stack:
      out.add(eqn.primitive.name)
  return out


def test_blocks_of_one_sublayer_leave_no_op_unscoped_that_granites_programs_do_not():
  """nemotron_h's decode step and prefill group (ISSUE 53) — a step with no FFN, B and C by group and the grouped gated
  norm (``xot.ssm``), ungated experts (``xot.moe_experts``), an ungated shared expert (``xot.moe_shared``) — traced
  equation by equation beside granite's: every primitive that stands outside the ``xot.`` scopes in the new kind's
  programs stands outside them in granite's too (the layer loops' own index arithmetic) or in a gated-expert model's
  (the reshape of the tokens to [B·S, D] around ``moe_ffn``, which moves nothing), so ``decode_unscoped_device_ms.closed``
  reads for it what it reads for granite and Ling: the loops' plumbing, no part of a block."""
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import paged_decode_forward, prefill_into_pages_many

  mamba = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, vocab_size=256, intermediate_size=96, max_position_embeddings=128, torch_dtype="float32")
  granite = config_from_hf(dict(
    mamba, model_type="granitemoehybrid", num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=16, shared_intermediate_size=96, position_embedding_type="nope",
  ))  # fmt: skip
  nemotron = config_from_hf(dict(
    mamba, model_type="nemotron_h", num_hidden_layers=5, hybrid_override_pattern="MEM*E", mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
    head_dim=16, n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1, moe_shared_expert_intermediate_size=48, norm_topk_prob=True, routed_scaling_factor=2.5,
  ))  # fmt: skip
  assert nemotron.layer_ffn == ("experts", "none", "experts") and nemotron.ssm_groups == 2 and not nemotron.ffn_gated

  def programs(cfg):
    params, shard = full_model_params(jax.random.PRNGKey(0), cfg)
    B, mp = 2, 128 // PS
    pool = init_paged_pool(cfg, cfg.n_layers, 1 + B * mp, PS, n_slots=B)
    bt = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
    decode = jax.make_jaxpr(lambda pool: paged_decode_forward(params, cfg, shard, jnp.ones((B, 1), jnp.int32), jnp.asarray([[3], [5]], jnp.int32), pool, bt, PS, False))(pool)
    lens = jnp.asarray([20, 32], jnp.int32)
    prefill = jax.make_jaxpr(lambda pool: prefill_into_pages_many.xot_jitted.__wrapped__(params, cfg, shard, jnp.ones((B, 32), jnp.int32), pool, bt, jnp.zeros((B,), jnp.int32), lens, PS, None, jnp.arange(B, dtype=jnp.int32)))(pool)
    return _unscoped_primitives(decode.jaxpr), _unscoped_primitives(prefill.jaxpr)

  gated = tiny_test_config(n_layers=2, max_seq_len=128, n_experts=4, n_active_experts=2, moe_hidden_dim=32, shared_expert_dim=32)
  (decode, prefill), (granite_decode, granite_prefill), (gated_decode, _) = programs(nemotron), programs(granite), programs(gated)
  assert decode <= granite_decode | gated_decode, sorted(decode - granite_decode - gated_decode)
  assert prefill <= granite_prefill | gated_decode, sorted(prefill - granite_prefill - gated_decode)


def test_a_gated_short_convolution_files_its_norm_and_projections_under_ssm_proj_and_everything_else_under_ssm():
  """lfm2_moe's decode step and prefill group (ISSUE 57). The kind writes under the scopes the other recurrent kinds
  write under, so ``decode_ssm_proj_device_ms.closed`` and ``decode_ssm_device_ms.closed`` read it with no new reader:
  ``xot.ssm_proj`` holds the operator norm and the two projections (W_in, W_out: its only matrix products beside the
  FFN's), ``xot.ssm`` the two gates, the taps, and the tail's read and write (the decode step's dynamic_update_slice of
  the ``conv`` leaf; the prefill's scatter at the group's slots). No primitive stands outside the ``xot.`` scopes that
  does not in granite's programs or in a gated-expert model's."""
  from xotorch_support_jetson_tpu.models.config import config_from_hf
  from xotorch_support_jetson_tpu.models.decoder import _gated_conv_decode_step, paged_decode_forward, prefill_into_pages_many

  base = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, vocab_size=256, intermediate_size=96, max_position_embeddings=128, torch_dtype="float32")
  granite = config_from_hf(dict(
    base, model_type="granitemoehybrid", num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4,
    mamba_chunk_size=16, shared_intermediate_size=96, position_embedding_type="nope",
  ))  # fmt: skip
  lfm2 = config_from_hf(dict(
    base, model_type="lfm2_moe", num_hidden_layers=4, layer_types=["conv", "conv", "full_attention", "conv"], conv_L_cache=3, num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True, norm_eps=1e-5, rope_theta=1e6,
  ))  # fmt: skip
  assert lfm2.layer_types == ("conv", "conv", "attention", "conv") and not lfm2.state_matrix and [lfm2.layer_stack(i) for i in range(4)] == ["ssm_layers", "ssm_moe_layers", "moe_layers", "ssm_moe_layers"]

  def programs(cfg):
    params, shard = full_model_params(jax.random.PRNGKey(0), cfg)
    B, mp = 2, 128 // PS
    pool = init_paged_pool(cfg, cfg.n_layers, 1 + B * mp, PS, n_slots=B)
    bt = jnp.asarray(np.arange(1, 1 + B * mp, dtype=np.int32).reshape(B, mp))
    decode = jax.make_jaxpr(lambda pool: paged_decode_forward(params, cfg, shard, jnp.ones((B, 1), jnp.int32), jnp.asarray([[3], [5]], jnp.int32), pool, bt, PS, False))(pool)
    lens = jnp.asarray([20, 32], jnp.int32)
    prefill = jax.make_jaxpr(lambda pool: prefill_into_pages_many.xot_jitted.__wrapped__(params, cfg, shard, jnp.ones((B, 32), jnp.int32), pool, bt, jnp.zeros((B,), jnp.int32), lens, PS, None, jnp.arange(B, dtype=jnp.int32)))(pool)
    return _unscoped_primitives(decode.jaxpr), _unscoped_primitives(prefill.jaxpr)

  gated = tiny_test_config(n_layers=2, max_seq_len=128, n_experts=4, n_active_experts=2, moe_hidden_dim=32, shared_expert_dim=32)
  (decode, prefill), (granite_decode, granite_prefill), (gated_decode, _) = programs(lfm2), programs(granite), programs(gated)
  assert decode <= granite_decode | gated_decode, sorted(decode - granite_decode - gated_decode)
  assert prefill <= granite_prefill | gated_decode, sorted(prefill - granite_prefill - gated_decode)

  # one conv layer's decode step, equation by equation: which scope each primitive of the operator stands under
  params, _ = full_model_params(jax.random.PRNGKey(0), lfm2)
  lp = {name: leaf[0] for name, leaf in params["ssm_layers"].items() if name in ("ssm_norm", "w_in", "conv_w", "w_out")}  # (no FFN leaf: the step ends with the operator)
  pool = init_paged_pool(lfm2, lfm2.n_layers, 3, PS, n_slots=2)
  eqns = jax.make_jaxpr(lambda h, pool: _gated_conv_decode_step(h, pool, lp, 1, jnp.asarray([True, False]), lfm2))(jnp.ones((2, 1, 64), jnp.float32), pool).jaxpr.eqns
  under = lambda prim: {next((part for part in str(e.source_info.name_stack).split("/") if part.startswith("xot.")), "") for e in eqns if e.primitive.name == prim}  # noqa: E731
  assert under("dot_general") == {"xot.ssm_proj"} and under("dynamic_update_slice") == {"xot.ssm"} and under("dynamic_slice") == {"xot.ssm"}
  assert "xot.ssm" in under("mul") and not under("logistic") and not under("exp")  # gates and taps are products and sums: the operator has no activation
  assert all(any(part.startswith("xot.") for part in str(e.source_info.name_stack).split("/")) for e in eqns), [e.primitive.name for e in eqns if "xot." not in str(e.source_info.name_stack)]
