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

The cases every served kind has — prefill, decode, padding, chunking, slot reuse, bfloat16, the scheduler, the scopes —
are ``tests/served_kind.py``'s battery, taken in below under the names they have always had here.
"""

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from served_kind import Kind, battery

import arch_hybrid_ssm  # noqa: E402 — served_kind puts benchmark/ on the path

from xotorch_support_jetson_tpu.inference.batch_scheduler import BatchedServer  # noqa: E402
from xotorch_support_jetson_tpu.models import decoder as dec  # noqa: E402
from xotorch_support_jetson_tpu.models.config import config_from_hf  # noqa: E402
from xotorch_support_jetson_tpu.ops import ssm as ssm_ops  # noqa: E402

HF = {
  "model_type": "granitemoehybrid", "attention_multiplier": 0.25, "embedding_multiplier": 12, "logits_scaling": 8, "residual_multiplier": 0.22,
  "position_embedding_type": "nope", "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1, "mamba_conv_bias": True, "mamba_proj_bias": False,
  "num_local_experts": 0, "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": True, "max_position_embeddings": 256, "torch_dtype": "float32",
  **arch_hybrid_ssm.REHEARSE_WIDTHS,
}  # fmt: skip


def _params(hf: dict) -> dict:
  params = dec.full_model_params(jax.random.PRNGKey(0), config_from_hf(hf))[0]
  params["ssm_layers"]["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1), params["ssm_layers"]["conv_b"].shape)  # a bias that is not zero
  return params


PARAMS = _params(HF)
KIND = Kind(
  name="granite", arch=arch_hybrid_ssm, hf=HF, params=PARAMS, bf16_params=jax.tree.map(lambda x: x.astype(jnp.bfloat16), PARAMS),
  tol=5e-6,  # the program against the reference, both float32 at "highest": orders of summation only (measured 4e-7 to 8e-7)
  # bfloat16 weights and activations, the state float32: measured 0.0002 in the mean and 0.0013 at the worst entry of
  # logits of spread 0.2; 0.0006 and 0.004 are three times the readings and a fifth of half a dropped layer's (0.0069 / 0.040)
  bf16=(0.0006, 0.004),
  scopes=frozenset({"xot.ssm", "xot.ssm_proj"}),  # ``xot.ssm_proj``: norm, ``w_z``/``w_xbc``/``w_dt``, ``w_out``; ``xot.ssm``: convolution, the state's read, update and write, skip, gated norm
  state_step_form="reference",
  cases={"key,value,named": [("num_local_experts", 4, "num_local_experts"), ("mamba_n_groups", 2, "mamba_n_groups"), ("layer_types", ["mamba"] * 5, "layer_types")]},
  without={
    "test_every_named_probe_moves_the_reference_past_the_tolerance": "at these widths attention_multiplier 0.25 IS 1 / sqrt(head_dim 16), so the probe attention_scale_inv_sqrt_head_dim moves nothing; "
    "benchmark/tests/test_hybrid_ssm_kind.py holds the kind's probes",
    "test_a_checkpoint_of_the_family_is_refused_by_name": "the family has a safetensors name map: test_hf_itself_loads_the_export_and_agrees loads and exports one",
  },
  names={
    "test_prefill_then_decode_through_the_pool_equals_the_reference": "test_prefill_then_decode_through_pool_and_state_equals_the_reference",
    "test_a_padded_group_leaves_each_row_what_its_unpadded_run_does": "test_a_padded_group_leaves_each_row_the_state_of_its_unpadded_run",
    "test_a_decode_chunk_leaves_an_inactive_rows_cache_bit_for_bit": "test_a_decode_chunk_leaves_an_inactive_rows_state_bit_for_bit",
    "test_the_scopes_reach_the_lowered_decode_program": "test_the_state_space_scopes_reach_the_lowered_decode_program",
  },
)
# The same architecture with a state of 128 lanes (16 heads x 8 x 128): the widths the one-pass form of the state's
# decode step tiles (ops/ssm.py one_pass_supported), which the rehearsal's state of 16 is not.
HF_LANES = {**HF, "mamba_n_heads": 16, "mamba_d_head": 8, "mamba_d_state": 128}
CFG, SHARD, TOKENS, TOL = KIND.cfg, KIND.shard, KIND.tokens, KIND.tol
globals().update(battery(KIND))


@functools.cache
def _lanes() -> Kind:
  """The kind at the 128-lane widths, its weights drawn when a case first asks (every worker imports this module)."""
  return replace(KIND, hf=HF_LANES, params=_params(HF_LANES), bf16_params=None)


@pytest.fixture(params=["reference", "one_pass"])
def stepping_kind(request, monkeypatch):
  """The form a decode program steps the recurrent state in. ``one_pass``: the case's model is the 128-lane one and
  ``ssm_state_step`` is told what a TPU's program would be — the Mosaic kernel, here in interpret mode — while the
  attention layers stay on the gather path (their kernel has tests of its own). A new configuration is a new static
  argument, so no program traced for the other form is met again."""
  if request.param == "reference":
    yield KIND
    return
  lanes = _lanes()
  real, traced = ssm_ops.ssm_state_step, []
  monkeypatch.setattr(ssm_ops, "ssm_state_step", lambda *args, **_: traced.append(1) or real(*args[:7], use_kernel=True, interpret=True))
  assert ssm_ops.one_pass_supported(lanes.fresh_pool()["ssm"], True)
  yield lanes
  assert traced, "the decode program of this case was not traced with the one-pass form"


def test_config_from_hf_reads_the_hybrid_fields():
  assert CFG.family == "granite-hybrid" and CFG.layer_types == tuple(HF["layer_types"]) and CFG.recurrent_layers == 4 and CFG.n_attn_layers == 2
  assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state, CFG.ssm_conv, CFG.ssm_chunk) == (8, 16, 16, 4, 32)
  assert (CFG.embed_scale, CFG.residual_multiplier, CFG.logits_scaling, CFG.attn_multiplier, CFG.use_rope, CFG.tied_embedding) == (12.0, 0.22, 8.0, 0.25, False, True)
  assert CFG.plain_attention  # the attention multiplier is folded into q: the Pallas kernels keep their one scale
  assert {k: v.shape[0] for k, v in (("layers", PARAMS["layers"]["wq"]), ("ssm_layers", PARAMS["ssm_layers"]["w_xbc"]))} == {"layers": 2, "ssm_layers": 4}


def test_an_unknown_model_type_is_refused_and_an_absent_one_stays_llama():
  """An unknown ``model_type`` is no longer served as a llama without a word (ROADMAP M9); an absent one stays llama.
  (Of a granitemoehybrid config, what the decoder does not implement is refused by name: the battery's refusals.)"""
  bare = {"vocab_size": 8, "hidden_size": 8, "num_hidden_layers": 1, "num_attention_heads": 1, "intermediate_size": 8}
  with pytest.raises(ValueError, match="unknown model_type 'rwkv7'"):
    config_from_hf({**bare, "model_type": "rwkv7"})
  assert config_from_hf(bare).family == "llama"


@pytest.mark.parametrize("length", [32, 64, 96, 45, 7, 1])
def test_a_chunked_mixer_equals_the_token_by_token_reference(length):
  """(a) One state-space layer, chunks of 32: lengths that are and are not multiples of the chunk, and shorter than
  the convolution. The whole layer (mixer and its MLP) against the reference's ``_mamba`` + ``_mlp``."""
  st = {k: v[1] for k, v in PARAMS["ssm_layers"].items()}
  h = jax.random.normal(jax.random.PRNGKey(length), (length, CFG.dim), jnp.float32)
  zeros = jnp.zeros((1, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state)), jnp.zeros((1, CFG.ssm_conv - 1, CFG.ssm_conv_dim))
  got, ssm, conv = dec._ssm_layer(h[None], st, CFG, *zeros)
  want = arch_hybrid_ssm._mamba(
    h, st["ssm_norm"], st["w_z"], st["w_xbc"], st["w_dt"], st["conv_w"], st["conv_b"], st["dt_bias"], st["A_log"], st["D"], st["gate_norm"], st["w_out"],
    H=CFG.ssm_heads, P=CFG.ssm_head_dim, N=CFG.ssm_state, eps=CFG.norm_eps, r=CFG.residual_multiplier,
  )
  want = arch_hybrid_ssm._mlp(want, st["mlp_norm"], st["w_gate"], st["w_up"], st["w_down"], eps=CFG.norm_eps, r=CFG.residual_multiplier)
  np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=TOL, rtol=0)
  assert ssm.shape == (1, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state) and conv.shape == (1, CFG.ssm_conv - 1, CFG.ssm_conv_dim)


# ------------------------------------------------------------ the scheduler


def test_the_scheduler_turns_off_what_pages_alone_cannot_carry(served):
  """(f) What the battery's scheduler case holds for every recurrent kind — prefix reuse, the host tier, speculation and
  mixed ticks off by the one property, one log line — shows here: the long prompt spans more than three pages of 16, so
  its second sending would reuse the first's pages in any other model, and it reuses none."""
  assert len(served.prompts[0]) > 3 * KIND.page_size and not served.cached
  assert served.after.counter_value("prefix_cache_hit_pages_total") == served.before.counter_value("prefix_cache_hit_pages_total")


def test_a_prefill_group_holds_at_most_eight_rows_on_a_server_of_many_slots(monkeypatch):
  """What 64 callers meet that 16 never did: on a server of more than 16 slots a ready list of more than 8 rows is
  split into groups of at most 8, longest first, so that its prefill programs are those of 1, 2, 4 and 8 rows (what a
  warm-up sends) and a lump of callers compiles nothing in service; a server of up to 16 slots groups as before."""
  from xotorch_support_jetson_tpu.inference import batch_scheduler as bs

  monkeypatch.setenv("XOT_TPU_BATCH_SLOTS", "12")
  server = BatchedServer(KIND.engine())
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
  server = BatchedServer(KIND.engine())
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
  np.testing.assert_allclose(KIND.reference(toks), theirs, atol=TOL, rtol=0)
  cfg = load_model_config(out)
  assert (cfg.layer_types, cfg.ssm_heads, cfg.ssm_state, cfg.residual_multiplier, cfg.attn_multiplier, cfg.use_rope) == (CFG.layer_types, 8, 16, 0.22, 0.25, False)
  loaded = load_shard_weights(out, cfg, SHARD)
  assert jax.tree.structure(loaded) == jax.tree.structure(PARAMS)
  for got, want in zip(jax.tree.leaves(loaded), jax.tree.leaves(PARAMS)):
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
