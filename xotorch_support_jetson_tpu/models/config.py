"""Model configuration: the variation points of the llama/qwen/mistral/phi
decoder family, plus the HF ``config.json`` → internal mapping.

Capability parity with reference ``inference/torch/models/llm_utils.py:22-77``
(``load_model_config``) and ``general_mha.py:33-63`` (per-family RoPE flavor,
qkv bias, tied-embedding selection). Unlike the reference — which sniffs model
*names* to decide tied embeddings (``general_mha.py:43-57``) — tying is taken
from ``config.json``'s ``tie_word_embeddings`` with a family default.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Any

import jax.numpy as jnp


@dataclass(frozen=True)
class RopeScaling:
  """Llama-3 style frequency scaling (rope_type='llama3' in HF configs)."""

  factor: float = 8.0
  low_freq_factor: float = 1.0
  high_freq_factor: float = 4.0
  original_max_position_embeddings: int = 8192
  rope_type: str = "llama3"


@dataclass(frozen=True)
class YarnScaling:
  """Yarn frequency scaling (rope_type='yarn'; deepseek-v2/v3 checkpoints).

  ``attention_factor`` is resolved at parse time (HF `_compute_yarn_parameters`:
  explicit value, else mscale/mscale_all_dim ratio, else 0.1·ln(factor)+1) and
  multiplies cos/sin at application."""

  factor: float = 1.0
  beta_fast: float = 32.0
  beta_slow: float = 1.0
  original_max_position_embeddings: int = 4096
  attention_factor: float = 1.0
  truncate: bool = True
  rope_type: str = "yarn"


@dataclass(frozen=True)
class LongRopeScaling:
  """Phi-3/phi-4 'longrope': per-frequency factors with a sqrt attention
  scale. HF switches short→long factors dynamically when the sequence
  exceeds the original context; with static shapes the choice here keys off
  the model's effective max_seq_len (the engine clamps it to the serving
  cap, inference/jax_engine.py) — exact HF parity whenever the cap fits the
  original context, consistently long-factor beyond it."""

  short_factor: tuple[float, ...]
  long_factor: tuple[float, ...]
  original_max_position_embeddings: int
  attention_factor: float = 1.0
  rope_type: str = "longrope"


@dataclass(frozen=True)
class AttnKind:
  """One attention layer's description, where a model's attention layers are not all alike: the ONE owner of which
  layers have a window, how many query heads, which rope — or none — and a gate or none (``ModelConfig.layer_attn``
  holds one a layer; the KV heads and the head size are the model's). ``name`` is "full" or "window" and prefixes the
  layer's stack (``ModelConfig.layer_stack``); ``window`` 0 is none, else a query at t sees the keys in (t - window, t];
  ``out_gate``: one scalar a head, softplus(x W_og) in float32, multiplies the head's output ahead of ``wo``;
  ``rope`` False: the kind's q and k go to the attention as projected, with no position term (smallthinker's global
  layers beside its roped window layers; a model none of whose layers has one says so once, ``ModelConfig.use_rope``)."""

  name: str
  n_heads: int
  window: int = 0
  rope_theta: float = 10000.0
  rope_scaling: RopeScaling | YarnScaling | LongRopeScaling | None = None
  partial_rotary_factor: float = 1.0
  out_gate: bool = False
  rope: bool = True

  @property
  def shape(self) -> tuple:
    """What two kinds must share to share a stack of parameters: everything but the window."""
    return (self.n_heads, self.rope_theta, self.rope_scaling, self.partial_rotary_factor, self.out_gate, self.rope)


@dataclass(frozen=True)
class ModelConfig:
  vocab_size: int
  dim: int  # embedding/residual width
  n_layers: int
  n_heads: int
  n_kv_heads: int
  hidden_dim: int  # MLP intermediate width
  head_dim: int = 0  # 0 → dim // n_heads
  norm_eps: float = 1e-5
  rope_theta: float = 500000.0
  rope_scaling: RopeScaling | YarnScaling | LongRopeScaling | None = None
  max_seq_len: int = 8192
  qkv_bias: bool = False  # qwen2 uses attention biases
  qk_norm: bool = False  # qwen3: per-head RMSNorm on q and k before rope
  qk_norm_whole: bool = False  # OLMo 2 / olmo_hybrid: that RMSNorm over the WHOLE q and k projections, before the split into heads
  attn_out_bias: bool = False
  partial_rotary_factor: float = 1.0  # phi3/phi-4: rope only the leading channels
  tied_embedding: bool = False
  family: str = "llama"
  dtype: Any = jnp.bfloat16
  # Quantized-matmul compute mode for int8 weights ("w8a16" | "w8a8"); ""
  # defers to the process-wide XOT_TPU_QUANT_COMPUTE. Lives on the config —
  # a STATIC jit argument — so swapping modes via dataclasses.replace keys a
  # fresh compiled program (models/decoder.py _mm).
  quant_compute: str = ""
  eos_token_ids: tuple[int, ...] = ()
  # bos/pad ids ride along so hf_export can reproduce the source config
  # verbatim — dropping them lets transformers re-apply architecture defaults
  # (e.g. Phi3Config's pad_token_id=32000) that can be out of vocab range.
  bos_token_id: int | None = None
  pad_token_id: int | None = None
  # --- MoE (ops/moe.py). n_experts == 0 ⇒ dense model; first_k_dense layers
  # stay dense even in an MoE model (deepseek puts layer 0 dense).
  n_experts: int = 0
  n_active_experts: int = 0  # top-k routed experts per token
  moe_hidden_dim: int = 0  # per-routed-expert intermediate width
  shared_expert_dim: int = 0  # total shared-expert intermediate width (0 ⇒ none)
  shared_expert_gate: bool = False  # qwen2-moe: sigmoid gate on the shared expert
  first_k_dense: int = 0
  router_scoring: str = "softmax"  # "softmax" | "sigmoid" (deepseek-v3)
  router_selection_bias: bool = True  # a sigmoid router's stacks hold the leaf ``router_bias``, added for the CHOICE only; False (lfm2_moe ``use_expert_bias`` false): no such leaf
  norm_topk_prob: bool = False
  routed_scaling_factor: float = 1.0
  moe_capacity_factor: float | None = None  # None ⇒ exact compute (no token drops)
  moe_aux_loss_coef: float = 0.0  # load-balancing loss weight in training
  # Where an expert layer's router reads: "ffn", the experts' own normed input, or "attn" (smallthinker), the normed
  # input of the layer's ATTENTION — the choice is drawn ahead of the attention and carried across it to the experts,
  # which read the stream after the attention's residual (models/decoder.py ``_route_ahead``).
  router_input: str = "ffn"
  expert_act: str = "silu"  # the experts' nonlinearity (routed and shared), one of ops/moe.py ``EXPERT_ACTS``: "silu" (SwiGLU) | "relu" (ReGLU) | "relu2" (relu(x)²)
  # The FFN's form, dense, routed and shared alike: True, W_down(act(W_gate x) * W_up x); False (nemotron_h), two matrices
  # and no gate, W_down act(W_up x) — the stacks then hold no ``w_gate`` / ``w_experts_gate`` / ``w_shared_gate`` leaf.
  ffn_gated: bool = True
  # Which FFN each layer step has, "dense" | "experts" | "none", one a layer; () ⇒ the ``first_k_dense`` rule (experts
  # from that layer on in a model with experts, else dense). "none": the step ends with its mixer's residual — a
  # published block of ONE sublayer whose successor is another mixer (nemotron_h's ``M`` ahead of a ``*``).
  layer_ffn: tuple = ()
  # Group-limited routing (deepseek): experts are grouped; only experts in the
  # top ``topk_group`` groups are eligible. Group score = max expert score
  # (v2 "group_limited_greedy") or sum of top-2 (v3 "noaux_tc").
  n_group: int = 1
  topk_group: int = 1
  group_mode: str = "none"  # "none" | "max" | "top2sum"
  # --- MLA (multi-head latent attention, deepseek-v2/v3). kv_lora_rank > 0
  # switches the attention block to MLA: queries optionally LoRA-compressed
  # (q_lora_rank, 0 ⇒ direct q_proj), KV always compressed to a shared latent
  # + a small MQA rope channel. Rope applies only to the *_rope parts, with
  # deepseek's interleaved pairing (ops/rope.py apply_rope_interleaved).
  q_lora_rank: int = 0
  kv_lora_rank: int = 0
  qk_nope_head_dim: int = 0
  qk_rope_head_dim: int = 0
  v_head_dim: int = 0
  # --- gemma2: pre+post norms around each block, GeGLU (tanh-gelu) MLP,
  # tanh softcapping on attention scores and final logits, sqrt(dim) embed
  # scaling, attention scale from query_pre_attn_scalar, and alternating
  # sliding-window attention (even layers sliding in HF's Gemma2).
  post_norms: bool = False
  # False: no norm ahead of a sublayer. With ``post_norms`` that is OLMo 2's reordered block, h += norm(f(h)).
  pre_norms: bool = True
  mlp_act: str = "silu"  # the dense FFN's: "silu" | "gelu_tanh" | "relu2"
  attn_logit_softcap: float = 0.0  # 0 ⇒ off
  final_logit_softcap: float = 0.0
  query_pre_attn_scalar: float = 0.0  # 0 ⇒ scale by 1/sqrt(qk head dim)
  # gemma2's window size (0: none). WHICH layers have it is ``layer_attn``'s to say (filled in from this key by gemma2's
  # rule where nothing else describes the layers: ``__post_init__``); in a model whose kinds differ in nothing else
  # (gemma2) they share one stack and the window rides a traced per-layer flag (``is_sliding``).
  sliding_window: int = 0
  # One ``AttnKind`` a layer (None at a recurrent layer) where a model's attention layers are not all alike; () ⇒ every
  # attention layer is the model-level fields' (``attn_kind``). Kinds that differ in more than their window (laguna:
  # 48 query heads, YaRN over half a head and no window / 64 heads, plain rope and a window of 512; smallthinker: no
  # position term and no window / plain rope and a window of 4096, 28 heads both) keep a stack of parameters each
  # (``layer_stack``), run in the published order (``mixed_layers``), and their window is a static operand of the
  # attention kernels.
  layer_attn: tuple = ()
  embed_scale: float = 1.0  # gemma multiplies embeddings by sqrt(dim)
  # --- vision (llava): CLIP tower + projector config (models/vision.py) and
  # the placeholder token id the HF processor expands per image patch.
  vision: Any = None  # VisionConfig | None (Any keeps this module torch/vision-free)
  image_token_id: int = -1
  # --- hybrids of recurrent and attention layers: ``layer_types`` names every
  # layer's mixer in model order (a kind of RECURRENT_KINDS | "attention";
  # empty ⇒ all attention). A recurrent layer keeps a per-slot state beside
  # the page pool instead of K/V pages; a model's recurrent layers are all of
  # one kind, each with ``ssm_heads`` heads, a causal depthwise convolution of
  # ``ssm_conv`` taps and a chunked prefill of ``ssm_chunk`` positions
  # (models/decoder.py). Four kinds; three keep a state matrix (``state_matrix``), stepped by two update rules (ops/ssm.py):
  # - "mamba" (granitemoehybrid, nemotron_h): a Mamba-2 mixer; a head's state
  #   is [``ssm_head_dim`` channels x ``ssm_state``], decayed by one scalar;
  #   B and C come in ``ssm_groups`` groups (head h reads group
  #   h // (heads / groups)) and the gated norm runs over each group's
  #   channels by itself (1, granite: one B, one C, one norm over all).
  # - "kda" (bailing_hybrid): Kimi Delta Attention; a head's state is a matrix
  #   [``ssm_head_dim`` values x ``ssm_state`` key channels], square there,
  #   decayed per key channel and corrected by a rank-one delta rule with beta
  #   in (0, 1). The log decay is ``kda_lower_bound`` x a sigmoid, and
  #   ``ssm_chunk`` is held to the positions of it that stay inside float32's
  #   range: that kind's chunked prefill factorises the pairwise decays.
  # - "gdn" (olmo_hybrid): Gated DeltaNet; the same delta rule on a
  #   rectangular matrix (192 values x 96 key channels as published), decayed
  #   by ONE scalar a head whose log, -exp(A_log) softplus(a + dt_bias), has no
  #   lower bound, beta in (0, ``gdn_beta_scale``). Its chunked prefill takes
  #   the pairwise decays exp(G_t - G_s) as they are, never above 1, so its
  #   ``ssm_chunk`` (64, the published chunk) needs no rule.
  # - "conv" (lfm2_moe): a gated short convolution, out = W_out(C ⊙ conv(B ⊙ x)) with [B | C | x] = u W_in over
  #   ``ssm_conv_dim`` = dim channels, ``ssm_conv`` taps, no bias and no activation. NO state matrix: all a slot keeps
  #   is the convolution's tail, ``ssm_conv - 1`` rows of the gated product B ⊙ x (``ssm_heads`` / ``ssm_head_dim`` /
  #   ``ssm_state`` stay 0, the pool has no ``ssm`` leaf, and there is no scan, so no ``ssm_chunk``).
  # The stacked parameters are named by (mixer, FFN)
  # pairing (``layer_stack``): ``layers`` / ``moe_layers`` the attention layers
  # with a dense / an expert FFN, ``ssm_layers`` / ``ssm_moe_layers`` the
  # recurrent ones, ``ssm_mixer_layers`` those with no FFN at all; attention layers of a second shape (``layer_attn``: other
  # query heads, another rope, a gate) take stacks under their kind's name
  # (``window_moe_layers``). The page pool keeps pages for the attention layers
  # only — one K/V leaf for every attention kind: the KV heads and the head size
  # are the model's —, with per-slot state leaves beside them (ops/paged.py
  # init_paged_pool).
  layer_types: tuple[str, ...] = ()
  ssm_heads: int = 0
  ssm_head_dim: int = 0
  ssm_state: int = 0
  ssm_conv: int = 0
  ssm_chunk: int = 256
  ssm_groups: int = 1  # "mamba": groups of B and C (and of the gated norm)
  kda_lower_bound: float = 0.0  # "kda": the log decay of a key channel lies in (kda_lower_bound, 0)
  gdn_beta_scale: float = 1.0  # "gdn": beta = gdn_beta_scale x sigmoid; 2 where the transition may have negative eigenvalues
  # bailing_hybrid's ``use_qk_norm`` as read for its MLA layers: an RMSNorm over each query head's nope+rope channels
  # before rope, beside the latent's own norm (leaf ``q_norm``).
  mla_q_norm: bool = False
  # An expert layer told which experts it holds (one chip's share of an expert-parallel deployment): the router, its
  # bias, the groups and the top-k stay ``n_experts`` wide; the expert leaves hold experts [lo, hi) and the layer
  # computes their part of the result alone (ops/moe.py moe_ffn ``held``). () ⇒ all of them.
  experts_held: tuple[int, int] | tuple[()] = ()
  # Granite's four multipliers: ``embed_scale`` above is its
  # embedding_multiplier; every block's output is scaled by
  # ``residual_multiplier`` before it joins the residual; logits are divided
  # by ``logits_scaling``; ``attn_multiplier`` (0 ⇒ 1/sqrt(head_dim)) is the
  # softmax scale, folded into q (decoder.py _dense_qkv) so the attention
  # cores and kernels keep their one scale.
  residual_multiplier: float = 1.0
  logits_scaling: float = 1.0
  attn_multiplier: float = 0.0
  use_rope: bool = True  # False: no position term in ANY attention layer ("nope"); some layers only: ``AttnKind.rope``
  # Cleared by the engine (never by a user) when the serving plan leaves a
  # mesh axis of more than one device to GSPMD: a Mosaic kernel cannot be
  # partitioned automatically ("wrap the call in a shard_map"), so programs
  # that span such an axis take the XLA attention paths. Static like the rest
  # of the config, so the choice keys the compiled programs.
  mosaic_kernels: bool = True

  def attn_kind(self, layer_idx: int) -> AttnKind:
    """Layer ``layer_idx``'s attention description: its ``layer_attn`` entry, else the model-level fields'."""
    if self.layer_attn:
      return self.layer_attn[layer_idx]
    return AttnKind("full", self.n_heads, 0, self.rope_theta, self.rope_scaling, self.partial_rotary_factor, rope=self.use_rope)

  @property
  def attn_shapes(self) -> tuple:
    """The distinct ``AttnKind.shape`` of the attention layers, in the order the model meets them: one stack of
    parameters each (the first keeps the plain names ``layers`` / ``moe_layers``)."""
    return tuple(dict.fromkeys(k.shape for k in self.layer_attn if k is not None))

  @property
  def traced_window(self) -> bool:
    """Whether some stack holds layers with a window beside layers without (gemma2: all its layers are one stack):
    the window then rides a traced per-layer flag, which no Pallas kernel takes."""
    return len({(k.shape, k.window) for k in self.layer_attn if k is not None}) > len(self.attn_shapes)

  @property
  def mixed_layers(self) -> bool:
    """Whether the layers live in more stacks than the two plain ones, to be run in the published order
    (models/decoder.py ``_layer_runs``): a hybrid with recurrent layers, or attention kinds of different shapes."""
    return self.recurrent_layers > 0 or len(self.attn_shapes) > 1

  @property
  def plain_attention(self) -> bool:
    """No softcap, no scale override, no window that rides a traced flag (a window that is static per stack IS the
    kernels' operand) and no automatically partitioned mesh axis — the single gate for the Pallas kernels, which
    implement none of the former and cannot be lowered under the latter."""
    return self.mosaic_kernels and not self.attn_logit_softcap and not self.traced_window and not self.query_pre_attn_scalar

  @property
  def is_mla(self) -> bool:
    return self.kv_lora_rank > 0

  @property
  def recurrent_layers(self) -> int:
    """How many layers keep a per-slot recurrent state instead of K/V pages.
    The ONE property the scheduler's gates read: above 0, pages alone are not
    a request's state, so whatever reuses or moves pages without it (prefix
    reuse, the host tier, speculation, mixed ticks) is off."""
    return sum(1 for t in self.layer_types if t in RECURRENT_KINDS)

  @property
  def recurrent_kind(self) -> str:
    """The one kind of this model's recurrent layers ("" where it has none)."""
    return next((t for t in self.layer_types if t in RECURRENT_KINDS), "")

  @property
  def state_matrix(self) -> bool:
    """Whether the recurrent layers keep a per-slot state MATRIX (the pool's float32 ``ssm`` leaf) beside their
    convolution's tail (``conv``): every kind but "conv", whose whole state is the tail. The one owner of that fact —
    the pool (ops/paged.py ``init_paged_pool``), the layer loops (models/decoder.py) and the gauge (ops/ssm.py
    ``state_step_form``) read it; nobody compares kinds."""
    return self.recurrent_kind in STATE_MATRIX_KINDS

  @property
  def n_held_experts(self) -> int:
    """Experts whose weights this shard holds: the expert axis of the expert leaves."""
    return self.experts_held[1] - self.experts_held[0] if self.experts_held else self.n_experts

  def ffn_kind(self, layer_idx: int) -> str:
    """Layer ``layer_idx``'s FFN, "dense" | "experts" | "none": its ``layer_ffn`` entry, else the ``first_k_dense`` rule."""
    if self.layer_ffn:
      return self.layer_ffn[layer_idx]
    return "experts" if self.n_experts and layer_idx >= self.first_k_dense else "dense"

  @property
  def expert_layers(self) -> int:
    """How many layer steps route experts."""
    return sum(1 for i in range(self.n_layers) if self.ffn_kind(i) == "experts")

  def layer_stack(self, layer_idx: int) -> str:
    """The stacked-parameter dict layer ``layer_idx`` lives in, by its (mixer, FFN) pairing; an attention kind whose
    shape is not the model's first (``attn_shapes``) prefixes its stacks with its name (``window_moe_layers``); a layer
    step with no FFN lives in ``mixer_layers``."""
    recurrent = bool(self.layer_types) and self.layer_types[layer_idx] in RECURRENT_KINDS
    kind = None if recurrent or not self.layer_attn else self.layer_attn[layer_idx]
    prefix = "ssm_" if recurrent else f"{kind.name}_" if kind is not None and kind.shape != self.attn_shapes[0] else ""
    return prefix + {"experts": "moe_layers", "dense": "layers", "none": "mixer_layers"}[self.ffn_kind(layer_idx)]

  @property
  def attn_windows(self) -> tuple:
    """The window (0: none) of every layer that owns K/V pages, in the order of the page pool's layer axis."""
    return tuple(self.attn_kind(i).window for i in range(self.n_layers) if not (self.layer_types and self.layer_types[i] in RECURRENT_KINDS))

  @property
  def attn_ropes(self) -> tuple:
    """Whether q and k carry a position term, for every layer that owns K/V pages, in the order of ``attn_windows``."""
    return tuple(self.attn_kind(i).rope for i in range(self.n_layers) if not (self.layer_types and self.layer_types[i] in RECURRENT_KINDS))

  @property
  def n_attn_layers(self) -> int:
    """Layers that own K/V pages: the page pool's layer axis."""
    return self.n_layers - self.recurrent_layers

  @property
  def ssm_inner(self) -> int:
    return self.ssm_heads * self.ssm_head_dim

  @property
  def ssm_conv_dim(self) -> int:
    """Channels the convolution runs over: x and every group's B and C ("mamba"); every head's q, k and v ("kda", "gdn");
    the gated product B ⊙ x, as wide as the stream ("conv")."""
    if not self.state_matrix:
      return self.dim
    if self.recurrent_kind in ("kda", "gdn"):
      return self.ssm_heads * (2 * self.ssm_state + self.ssm_head_dim)
    return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

  @property
  def qk_head_dim(self) -> int:
    return self.qk_nope_head_dim + self.qk_rope_head_dim if self.is_mla else self.head_dim

  # KV-cache geometry (models/decoder.py init_kv_cache): MLA caches the
  # *latent* (shared kv latent in the "k" buffer, rope channel in the "v"
  # buffer — rank+rope floats per token instead of per-head K/V; the kv_b
  # up-projection is absorbed into attention, ops/attention.py
  # mla_absorbed_attention). Dense models cache GQA heads.
  @property
  def cache_kv_heads(self) -> int:
    return 1 if self.is_mla else self.n_kv_heads

  @property
  def cache_k_dim(self) -> int:
    return self.kv_lora_rank if self.is_mla else self.head_dim

  @property
  def cache_v_dim(self) -> int:
    return self.qk_rope_head_dim if self.is_mla else self.head_dim

  def __post_init__(self):
    if self.head_dim == 0:
      object.__setattr__(self, "head_dim", self.dim // self.n_heads)
    if self.sliding_window and not self.layer_attn:
      # A model-level window with no per-layer description is HF Gemma2's: the even-indexed layers have it. Said
      # here once, as a value of the per-layer field, which is what everything else reads.
      rope = (self.rope_theta, self.rope_scaling, self.partial_rotary_factor)
      object.__setattr__(self, "layer_attn", tuple(AttnKind("full" if i % 2 else "window", self.n_heads, 0 if i % 2 else self.sliding_window, *rope) for i in range(self.n_layers)))

  @property
  def q_dim(self) -> int:
    return self.n_heads * self.head_dim

  @property
  def kv_dim(self) -> int:
    return self.n_kv_heads * self.head_dim

  def with_layers(self, n_layers: int) -> "ModelConfig":
    return replace(self, n_layers=n_layers)


STATE_MATRIX_KINDS = ("mamba", "kda", "gdn")  # the recurrent kinds that keep a state matrix a slot (``ModelConfig.state_matrix``)
RECURRENT_KINDS = (*STATE_MATRIX_KINDS, "conv")  # the ``layer_types`` whose layers keep a per-slot state (``ModelConfig.recurrent_layers``)

# HF ``model_type`` (or, with its underscores dropped, the ``architectures`` entry) -> family; first match wins, so
# a longer name stands before the one it contains. The one list of what ``config_from_hf`` knows.
MODEL_FAMILIES = {
  "qwen3_moe": "qwen3-moe", "qwen3": "qwen3", "qwen2_moe": "qwen2-moe", "qwen2": "qwen2", "mixtral": "mixtral", "mistral": "mistral", "phi3": "phi3",
  "deepseek_v3": "deepseek-v3", "deepseek_v2": "deepseek-v2", "gemma2": "gemma2", "granitemoehybrid": "granite-hybrid", "bailing_hybrid": "bailing-hybrid", "olmo_hybrid": "olmo-hybrid", "laguna": "laguna", "smallthinker": "smallthinker", "nemotron_h": "nemotron-h", "lfm2_moe": "lfm2-moe", "llama": "llama",
}


def _rope_scaling_from(rs, hf: dict):
  """An HF ``rope_scaling`` / ``rope_parameters`` block → RopeScaling | YarnScaling | LongRopeScaling | None (a block
  of another ``rope_type``, "default" among them: None)."""
  rope_scaling = None
  if isinstance(rs, dict):
    rope_type = rs.get("rope_type", rs.get("type", ""))
    if rope_type == "llama3":
      rope_scaling = RopeScaling(
        factor=float(rs.get("factor", 8.0)),
        low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
        high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
        original_max_position_embeddings=int(rs.get("original_max_position_embeddings", 8192)),
      )
    elif rope_type == "yarn":
      import math

      factor = float(rs.get("factor", 1.0))
      attention_factor = rs.get("attention_factor")
      if attention_factor is None:
        mscale, mscale_all = rs.get("mscale"), rs.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
          return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0

        if mscale and mscale_all:
          attention_factor = get_mscale(factor, float(mscale)) / get_mscale(factor, float(mscale_all))
        else:
          attention_factor = get_mscale(factor)
      rope_scaling = YarnScaling(
        factor=factor,
        beta_fast=float(rs.get("beta_fast") or 32),
        beta_slow=float(rs.get("beta_slow") or 1),
        original_max_position_embeddings=int(rs.get("original_max_position_embeddings") or hf.get("max_position_embeddings", 4096)),
        attention_factor=float(attention_factor),
        truncate=bool(rs.get("truncate", True)),
      )
    elif rope_type == "longrope":
      import math

      orig = int(hf.get("original_max_position_embeddings") or hf.get("max_position_embeddings", 4096))
      attention_factor = rs.get("attention_factor")
      if attention_factor is None:
        factor = rs.get("factor")
        if hf.get("original_max_position_embeddings"):
          factor = hf.get("max_position_embeddings", orig) / orig
        attention_factor = 1.0 if not factor or factor <= 1.0 else math.sqrt(1 + math.log(factor) / math.log(orig))
      rope_scaling = LongRopeScaling(
        short_factor=tuple(float(x) for x in rs["short_factor"]),
        long_factor=tuple(float(x) for x in rs["long_factor"]),
        original_max_position_embeddings=orig,
        attention_factor=float(attention_factor),
      )
  return rope_scaling


def config_from_hf(hf: dict, dtype=None) -> ModelConfig:
  """Map an HF ``config.json`` dict to ModelConfig.

  Handles the same key space the reference maps
  (``llm_utils.py:30-77``): llama/qwen2/mistral/phi3 config.json layouts,
  including llama3 rope_scaling blocks and explicit ``head_dim`` overrides
  (needed e.g. for Llama-3.2 where head_dim * n_heads != hidden_size is
  false but qwen3-style configs carry it explicitly).
  """
  vision_cfg = None
  image_token_id = -1
  if "text_config" in hf and isinstance(hf["text_config"], dict):
    # Vision-language checkpoints (llava) nest the decoder config; the text
    # path runs on the nested config, and the vision tower/projector configs
    # are carried alongside (models/vision.py — a real tower, beyond the
    # reference's registry entry + API image remapping, chatgpt_api.py:97-128).
    top = hf
    merged = dict(hf["text_config"])
    merged.setdefault("vocab_size", top.get("vocab_size", merged.get("vocab_size")))
    hf = merged
    image_token_id = int(top.get("image_token_index", -1))
    if isinstance(top.get("vision_config"), dict):
      from .vision import vision_config_from_hf

      vision_cfg = vision_config_from_hf(top["vision_config"], int(hf["hidden_size"]), top)
  arch = (hf.get("architectures") or [""])[0].lower()
  model_type = hf.get("model_type", "").lower()
  family = next((fam for key, fam in MODEL_FAMILIES.items() if key in model_type or key.replace("_", "") in arch), None)
  if family is None:
    if model_type:
      # An unknown architecture is not a llama: serving it as one answers with noise and no error.
      raise ValueError(f"config_from_hf: unknown model_type {hf.get('model_type')!r} (known: {', '.join(MODEL_FAMILIES)})")
    family = "llama"  # an absent model_type stays llama (bare test configs)

  rope_scaling = _rope_scaling_from(hf.get("rope_scaling"), hf)

  eos = hf.get("eos_token_id", [])
  if isinstance(eos, int):
    eos = [eos]

  # transformers ≥4.56 writes "dtype"; older checkpoints carry "torch_dtype"
  torch_dtype = str(hf.get("torch_dtype") or hf.get("dtype") or "bfloat16")
  dtype_map = {"bfloat16": jnp.bfloat16, "float16": jnp.bfloat16, "float32": jnp.float32}

  # MoE key space: mixtral (num_local_experts, expert width = intermediate_size),
  # qwen2-moe (num_experts, moe_intermediate_size, gated shared expert),
  # deepseek-v2/v3 (n_routed_experts, n_shared_experts, first_k_dense_replace,
  # sigmoid scoring + routed_scaling_factor on v3).
  moe: dict[str, Any] = {}
  n_experts = int(hf.get("num_local_experts") or hf.get("num_experts") or hf.get("n_routed_experts") or hf.get("moe_num_primary_experts") or 0)
  if n_experts:
    moe_hidden = int(hf.get("moe_intermediate_size") or hf.get("moe_ffn_hidden_size") or hf["intermediate_size"])
    n_shared = int(hf.get("n_shared_experts") or 0)
    shared_dim = n_shared * moe_hidden
    if family in ("qwen2-moe", "laguna"):
      shared_dim = int(hf.get("shared_expert_intermediate_size") or 0)
    if family == "bailing-hybrid":
      shared_dim = int(hf.get("num_shared_experts") or 0) * int(hf.get("moe_shared_expert_intermediate_size") or moe_hidden)
    if family == "nemotron-h":
      shared_dim = n_shared * int(hf.get("moe_shared_expert_intermediate_size") or moe_hidden)
    # deepseek group-limited routing: v3 is always sigmoid + top-2-sum group
    # scores (HF DeepseekV3TopkRouter); v2 keys it on topk_method.
    # (laguna's row names no score function: deepseek-v3's router, whose 256 / top-8 / 2.5 its keys repeat, is assumed)
    # (lfm2_moe's row names none either: the family's router is sigmoid scores under ``use_expert_bias``)
    scoring = "sigmoid" if (hf.get("scoring_func") == "sigmoid" or family in ("deepseek-v3", "laguna", "nemotron-h", "lfm2-moe")) else "softmax"
    if family == "deepseek-v3" or hf.get("topk_method") == "noaux_tc":
      group_mode = "top2sum"
    elif hf.get("topk_method") == "group_limited_greedy":
      group_mode = "max"
    else:
      group_mode = "none"
    # A deployment's share (one chip of an expert-parallel group): the count above is of the experts HELD, from
    # ``experts_held_from`` on, and ``num_experts_routed`` is the router's width, the published count.
    routed, held = int(hf.get("num_experts_routed") or n_experts), ()
    if routed != n_experts:
      lo = int(hf.get("experts_held_from") or 0)
      if not 0 <= lo <= routed - n_experts:
        raise ValueError(f"experts_held_from {lo}: {n_experts} held experts do not lie inside the {routed} routed")
      held, n_experts = (lo, lo + n_experts), routed
    moe = dict(
      experts_held=held,
      n_experts=n_experts,
      n_active_experts=int(hf.get("num_experts_per_tok") or hf.get("moe_num_active_primary_experts") or 2),
      moe_hidden_dim=moe_hidden,
      shared_expert_dim=shared_dim,
      shared_expert_gate=family == "qwen2-moe",
      first_k_dense=_leading_dense(hf) if "mlp_layer_types" in hf else int(hf.get("first_k_dense_replace", hf.get("num_dense_layers", 0))),  # (lfm2_moe's name for it)
      router_scoring=scoring,
      norm_topk_prob=bool(hf.get("norm_topk_prob", family in ("mixtral", "laguna", "smallthinker"))),
      routed_scaling_factor=float(hf.get("routed_scaling_factor", hf.get("moe_routed_scaling_factor", 1.0))),
      moe_aux_loss_coef=float(hf.get("router_aux_loss_coef", hf.get("aux_loss_alpha", 0.001))),
      n_group=int(hf.get("n_group") or 1),
      topk_group=int(hf.get("topk_group") or 1),
      group_mode=group_mode,
    )

  mla: dict[str, Any] = {}
  if hf.get("kv_lora_rank"):
    mla = dict(
      q_lora_rank=int(hf.get("q_lora_rank") or 0),
      kv_lora_rank=int(hf["kv_lora_rank"]),
      qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
      qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
      v_head_dim=int(hf["v_head_dim"]),
    )

  gemma: dict[str, Any] = {}
  if family == "gemma2":
    import math

    gemma = dict(
      post_norms=True,
      mlp_act="gelu_tanh",
      attn_logit_softcap=float(hf.get("attn_logit_softcapping") or 0.0),
      final_logit_softcap=float(hf.get("final_logit_softcapping") or 0.0),
      query_pre_attn_scalar=float(hf.get("query_pre_attn_scalar") or 0.0),
      sliding_window=int(hf.get("sliding_window") or 0),
      embed_scale=math.sqrt(float(hf["hidden_size"])),
    )

  hybrid: dict[str, Any] = {}
  if family == "granite-hybrid":
    hybrid = _granite_hybrid_fields(hf)
  if family == "bailing-hybrid":
    hybrid = _bailing_hybrid_fields(hf)
  if family == "olmo-hybrid":
    hybrid = _olmo_hybrid_fields(hf)
  if family == "laguna":
    hybrid = _laguna_fields(hf)
  if family == "smallthinker":
    hybrid = _smallthinker_fields(hf)
  if family == "nemotron-h":
    hybrid = _nemotron_h_fields(hf)
  if family == "lfm2-moe":
    hybrid = _lfm2_moe_fields(hf)

  n_heads = int(hf["num_attention_heads"])
  hybrid.setdefault("n_layers", int(hf["num_hidden_layers"]))  # (nemotron_h counts its layer STEPS: two published blocks can be one)
  return ModelConfig(
    vocab_size=int(hf["vocab_size"]),
    dim=int(hf["hidden_size"]),
    n_heads=n_heads,
    n_kv_heads=int(hf.get("num_key_value_heads", n_heads)),
    # (smallthinker: every layer an expert layer, no dense FFN width at all)
    hidden_dim=int(hf.get("shared_intermediate_size") or hf["intermediate_size"]) if family == "granite-hybrid" else int(hf.get("intermediate_size") or 0) if family == "smallthinker" else int(hf["intermediate_size"]),
    head_dim=int(hf.get("head_dim") or 0),
    norm_eps=float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5)) if family in ("nemotron-h", "lfm2-moe") else hf.get("rms_norm_eps", 1e-5)),  # (lfm2_moe: ``norm_eps`` alone)
    rope_theta=float(hf.get("rope_theta") or 10000.0),
    rope_scaling=rope_scaling,
    max_seq_len=int(hf.get("max_position_embeddings", 8192)),
    qkv_bias=family in ("qwen2", "qwen2-moe") or bool(hf.get("attention_bias", False)),
    qk_norm=family in ("qwen3", "qwen3-moe", "olmo-hybrid", "laguna", "lfm2-moe"),  # (laguna: assumed; its row has no key for or against)
    partial_rotary_factor=float(hf.get("partial_rotary_factor", 1.0)),
    tied_embedding=bool(hf.get("tie_word_embeddings", family in ("gemma2", "granite-hybrid", "lfm2-moe") or (family == "qwen2" and int(hf["hidden_size"]) < 2048))),
    family=family,
    dtype=dtype or dtype_map.get(torch_dtype, jnp.bfloat16),
    eos_token_ids=tuple(int(e) for e in eos),
    bos_token_id=None if hf.get("bos_token_id") is None else int(hf["bos_token_id"]),
    pad_token_id=None if hf.get("pad_token_id") is None else int(hf["pad_token_id"]),
    vision=vision_cfg,
    image_token_id=image_token_id,
    **moe,
    **mla,
    **gemma,
    **hybrid,
  )


def _granite_hybrid_fields(hf: dict) -> dict:
  """``GraniteMoeHybridConfig`` → the hybrid fields of ModelConfig. What the
  decoder does not implement is refused here, by name, not served wrong."""
  n_layers = int(hf["num_hidden_layers"])
  layer_types = tuple(hf.get("layer_types") or ("attention",) * n_layers)
  if len(layer_types) != n_layers or set(layer_types) - {"mamba", "attention"}:
    raise ValueError(f"granitemoehybrid: layer_types must name {n_layers} layers, each 'mamba' or 'attention'; got {layer_types}")
  if int(hf.get("num_local_experts") or 0):
    raise ValueError("granitemoehybrid with routed experts (num_local_experts > 0) is not supported: only the shared MLP is")
  if int(hf.get("mamba_n_groups", 1)) != 1:
    raise ValueError("granitemoehybrid: mamba_n_groups != 1 is not supported")
  if bool(hf.get("mamba_proj_bias", False)):
    raise ValueError("granitemoehybrid: mamba_proj_bias is not supported")
  pos = hf.get("position_embedding_type", "rope")
  if pos not in ("nope", "rope"):
    raise ValueError(f"granitemoehybrid: position_embedding_type {pos!r} is not supported")
  heads, head_dim = int(hf["mamba_n_heads"]), int(hf["mamba_d_head"])
  if heads * head_dim != int(hf.get("mamba_expand", 2)) * int(hf["hidden_size"]):
    raise ValueError("granitemoehybrid: mamba_n_heads * mamba_d_head must equal mamba_expand * hidden_size")
  return dict(
    layer_types=layer_types,
    ssm_heads=heads,
    ssm_head_dim=head_dim,
    ssm_state=int(hf["mamba_d_state"]),
    ssm_conv=int(hf["mamba_d_conv"]),
    ssm_chunk=int(hf.get("mamba_chunk_size", 256)),
    embed_scale=float(hf.get("embedding_multiplier", 1.0)),
    residual_multiplier=float(hf.get("residual_multiplier", 1.0)),
    logits_scaling=float(hf.get("logits_scaling", 1.0)),
    attn_multiplier=float(hf.get("attention_multiplier") or 0.0),
    use_rope=pos == "rope",
  )


def _bailing_hybrid_fields(hf: dict) -> dict:
  """``bailing_hybrid`` (Ling-3.0) → the hybrid fields of ModelConfig: layer ``i`` is an MLA layer where
  ``(i + 1) % layer_group_size == 0``, else a Kimi-Delta-Attention layer. What the decoder does not implement is
  refused here, by name, not served wrong. The multi-token-prediction module (``num_nextn_predict_layers``) is a draft
  head outside the forward pass and ``max_window_layers`` has no ``use_sliding_window`` beside it: neither is read."""
  n_layers, group = int(hf["num_hidden_layers"]), int(hf.get("layer_group_size") or 1)
  for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
    if any(float(v) for v in (hf.get(key) or ())[:n_layers]):
      raise ValueError(f"bailing_hybrid: a non-zero {key} in the layers kept (a clamp on the SwiGLU) is not supported")
  on = [key for key in ("use_kda_lora", "use_nGPT", "value_norm", "up_proj_norm", "scale_router_input", "use_mla_nope", "use_bias", "use_qkv_bias") if hf.get(key)]
  off = [key for key in ("no_kda_lora", "kda_safe_gate", "linear_silu", "rope_interleave", "moe_router_enable_expert_bias") if not hf.get(key, True)]
  if on or off:
    raise ValueError(f"bailing_hybrid: {', '.join(on + [f'{key} false' for key in off])} is not supported")
  if hf.get("gated_attention_proj_granularity_type", "head_wise") != "head_wise" or int(hf.get("group_norm_size") or 1) != 1:
    raise ValueError("bailing_hybrid: only a head-wise output gate (gated_attention_proj_granularity_type 'head_wise', group_norm_size 1) is supported")
  heads, head_dim = int(hf["num_attention_heads"]), int(hf.get("head_dim") or int(hf["hidden_size"]) // int(hf["num_attention_heads"]))
  if int(hf.get("num_kv_heads_for_linear_attn") or 0) not in (0, heads):
    raise ValueError("bailing_hybrid: num_kv_heads_for_linear_attn other than 0 or num_attention_heads is not supported")
  lower = float(hf.get("kda_lower_bound") or 0.0)
  if not lower < 0:
    raise ValueError("bailing_hybrid: kda_lower_bound must be below 0 (the log decay of a key channel lies between it and 0)")
  return dict(
    layer_types=tuple("attention" if (i + 1) % group == 0 else "kda" for i in range(n_layers)),
    ssm_heads=heads,
    ssm_head_dim=head_dim,
    ssm_state=head_dim,
    ssm_conv=int(hf.get("short_conv_kernel_size") or 4),
    # "kda" alone: its scan factorises the pairwise decays, and exp(±lower·chunk) has to stay inside float32
    # (e^80 = 5.5e34): 16 positions at -5. ("gdn" exponentiates the differences themselves, at most 0: no rule.)
    ssm_chunk=max(int(80.0 / -lower), 1),
    kda_lower_bound=lower,
    mla_q_norm=bool(hf.get("use_qk_norm", False)),
  )


def _olmo_hybrid_fields(hf: dict) -> dict:
  """``olmo_hybrid`` (Olmo-Hybrid) → the hybrid fields of ModelConfig: ``layer_types`` names each layer
  "linear_attention" (a Gated-DeltaNet layer, kind "gdn") or "full_attention"; OLMo 2's reordered block (a norm on each
  sublayer's output, none ahead of it) and its RMSNorm over the whole q and k projections; no position term in the
  attention layers (``rope_parameters.rope_theta`` null). What the decoder does not implement is refused here, by name."""
  n_layers = int(hf["num_hidden_layers"])
  names = {"linear_attention": "gdn", "full_attention": "attention"}
  layer_types = tuple(hf.get("layer_types") or ())
  if len(layer_types) != n_layers or set(layer_types) - set(names):
    raise ValueError(f"olmo_hybrid: layer_types must name {n_layers} layers, each 'linear_attention' or 'full_attention'; got {layer_types}")
  if hf.get("attention_bias"):
    raise ValueError("olmo_hybrid: attention_bias true is not supported")
  if hf.get("rope_theta") is not None or (hf.get("rope_parameters") or {}).get("rope_theta") is not None or hf.get("rope_scaling"):
    raise ValueError("olmo_hybrid: a rope_theta that is not null (rotary attention layers beside the recurrent ones) is not supported")
  heads = int(hf["linear_num_value_heads"])
  if int(hf.get("linear_num_key_heads") or heads) != heads:
    raise ValueError("olmo_hybrid: linear_num_key_heads other than linear_num_value_heads (grouped keys) is not supported")
  return dict(
    layer_types=tuple(names[t] for t in layer_types),
    ssm_heads=heads,
    ssm_head_dim=int(hf["linear_value_head_dim"]),
    ssm_state=int(hf["linear_key_head_dim"]),
    ssm_conv=int(hf.get("linear_conv_kernel_dim") or 4),
    ssm_chunk=64,  # Gated DeltaNet's published chunk
    gdn_beta_scale=2.0 if hf.get("linear_allow_neg_eigval") else 1.0,
    qk_norm_whole=True,
    pre_norms=False,
    post_norms=True,
    use_rope=False,
  )


def _leading_dense(hf: dict) -> int:
  """``mlp_layer_types`` ("dense" | "sparse" a layer) → how many leading layers have a dense FFN; a dense layer after a
  sparse one has no place in the two FFN stacks and is refused."""
  types = list(hf["mlp_layer_types"])
  n_dense = next((i for i, t in enumerate(types) if t != "dense"), len(types))
  if len(types) != int(hf["num_hidden_layers"]) or set(types) - {"dense", "sparse"} or "dense" in types[n_dense:]:
    raise ValueError(f"mlp_layer_types must name {hf['num_hidden_layers']} layers, 'dense' ones first and then 'sparse' ones; got {types}")
  return n_dense


def _laguna_fields(hf: dict) -> dict:
  """``laguna`` (Laguna-XS.2) → ``layer_attn``: ``layer_types`` names each layer "full_attention" or
  "sliding_attention" (window ``sliding_window``), ``num_attention_heads_per_layer`` its query heads (over the model's
  one KV head count and head size), ``rope_parameters`` a rope for each of the two kinds (or its flat spelling, below), ``gating`` a head-wise gate
  on the attention output (softplus: models/decoder.py ``_attn_out``). What the decoder does not implement is refused
  here, by name."""
  n_layers = int(hf["num_hidden_layers"])
  names = {"full_attention": "full", "sliding_attention": "window"}
  layer_types = list(hf.get("layer_types") or ())
  heads = list(hf.get("num_attention_heads_per_layer") or [int(hf["num_attention_heads"])] * n_layers)
  if len(layer_types) != n_layers or set(layer_types) - set(names):
    raise ValueError(f"laguna: layer_types must name {n_layers} layers, each 'full_attention' or 'sliding_attention'; got {layer_types}")
  if len(heads) != n_layers:
    raise ValueError(f"laguna: num_attention_heads_per_layer must have {n_layers} entries; got {len(heads)}")
  if hf.get("attention_bias"):
    raise ValueError("laguna: attention_bias true is not supported")
  if hf.get("moe_apply_router_weight_on_input"):
    raise ValueError("laguna: moe_apply_router_weight_on_input true (the router's weight on an expert's input) is not supported")
  if isinstance(hf.get("num_key_value_heads_per_layer"), (list, tuple)) and len(set(hf["num_key_value_heads_per_layer"])) > 1:
    raise ValueError("laguna: a KV head count that differs by layer (num_key_value_heads_per_layer) is not supported: the page pool has one K/V leaf")
  if hf.get("gating") not in (None, False, True):
    raise ValueError(f"laguna: gating {hf['gating']!r} is not supported (true: one softplus scalar a head on the attention output; false: none)")
  window = int(hf.get("sliding_window") or 0)
  if "sliding_attention" in layer_types and window <= 0:
    raise ValueError("laguna: sliding_attention layers need a sliding_window above 0")
  ropes, by_kind = {}, hf.get("rope_parameters")
  if not isinstance(by_kind, dict):
    # The flat spelling, under gemma3's keys, for a reader that has dropped the file's nested groups (the benchmark's
    # ``common.model_config`` keeps no dict but ``rope_scaling``): the full layers' rope as ``rope_theta`` +
    # ``rope_scaling`` + the top-level ``partial_rotary_factor``, the window layers' as ``rope_local_base_freq``,
    # plain and over the whole head.
    by_kind = {
      "full_attention": {"rope_type": "default", **(hf.get("rope_scaling") or {}), "rope_theta": hf.get("rope_theta"), "partial_rotary_factor": hf.get("partial_rotary_factor", 1.0)},
      "sliding_attention": {"rope_type": "default", "rope_theta": hf.get("rope_local_base_freq"), "partial_rotary_factor": 1.0},
    }
  for t in dict.fromkeys(layer_types):
    rp = by_kind.get(t)
    if not isinstance(rp, dict) or rp.get("rope_type", "default") not in ("default", "yarn") or rp.get("rope_theta") is None:
      raise ValueError(f"laguna: rope_parameters.{t} must be a block of rope_type 'default' or 'yarn' with a rope_theta; got {rp!r}")
    ropes[t] = (float(rp["rope_theta"]), _rope_scaling_from(rp, hf), float(rp.get("partial_rotary_factor", 1.0)))
  per_kind = {}
  for t, h in zip(layer_types, heads):
    if per_kind.setdefault(t, int(h)) != int(h) or int(h) % int(hf["num_key_value_heads"]):
      raise ValueError(f"laguna: num_attention_heads_per_layer must give every {t} layer one head count, a multiple of num_key_value_heads; got {heads}")
  kinds = {t: AttnKind(names[t], per_kind[t], window if t == "sliding_attention" else 0, *ropes[t], out_gate=bool(hf.get("gating"))) for t in per_kind}
  return dict(layer_attn=tuple(kinds[t] for t in layer_types))


def _smallthinker_fields(hf: dict) -> dict:
  """``smallthinker`` (SmallThinker-21BA3B) → ``layer_attn`` and where the router reads: ``sliding_window_layout`` names
  each layer 1 (a window of ``sliding_window_size``) or 0 (none), ``rope_layout`` 1 (plain rope at ``rope_theta`` over
  the whole head) or 0 (no position term) — two fields of the kind, so the lists need not agree; every layer routes
  ``moe_num_active_primary_experts`` of ``moe_num_primary_experts`` ReLU-gated experts of ``moe_ffn_hidden_size`` by a
  softmax over the chosen, from a router that reads the attention's normed input, with no shared expert and no dense
  FFN. What the decoder does not implement is refused here, by name."""
  n_layers = int(hf["num_hidden_layers"])
  layouts = {key: list(hf.get(key) or [0 if key == "sliding_window_layout" else 1] * n_layers) for key in ("rope_layout", "sliding_window_layout")}
  for key, layout in layouts.items():
    if len(layout) != n_layers or set(layout) - {0, 1}:
      raise ValueError(f"smallthinker: {key} must name {n_layers} layers, each 0 or 1; got {layout}")
  if hf.get("rope_scaling"):
    raise ValueError("smallthinker: a rope_scaling that is not null is not supported")
  if not hf.get("moe_primary_router_apply_softmax", False):
    raise ValueError("smallthinker: moe_primary_router_apply_softmax false (sigmoid scores on the chosen experts) is not supported")
  if not hf.get("norm_topk_prob", True):
    raise ValueError("smallthinker: norm_topk_prob false is not supported: the softmax is taken over the chosen experts")
  if not int(hf.get("moe_num_primary_experts") or 0):
    raise ValueError("smallthinker: moe_num_primary_experts must be above 0: every layer is an expert layer, the model has no dense FFN")
  secondary = sorted(key for key in hf if "secondary" in key and hf[key])
  if secondary:
    raise ValueError(f"smallthinker: a secondary expert tier ({', '.join(secondary)}) is not supported")
  if hf.get("attention_bias"):
    raise ValueError("smallthinker: attention_bias true is not supported")
  window = int(hf.get("sliding_window_size") or 0)
  if 1 in layouts["sliding_window_layout"] and window <= 0:
    raise ValueError("smallthinker: window layers (sliding_window_layout 1) need a sliding_window_size above 0")
  heads, theta = int(hf["num_attention_heads"]), float(hf.get("rope_theta") or 10000.0)
  kind = lambda roped, windowed: AttnKind("window" if windowed else "full", heads, window if windowed else 0, theta, None, 1.0, rope=bool(roped))  # noqa: E731
  layer_attn = tuple(kind(r, w) for r, w in zip(layouts["rope_layout"], layouts["sliding_window_layout"]))
  # Each kind of layer needs a stack of its own, named by its window: kinds that differ in the window alone would share
  # one and the window ride a traced flag (gemma2's way, which no Pallas kernel takes and this family has no leaf for).
  kinds = list(dict.fromkeys(layer_attn))
  if len({k.shape for k in kinds}) < len(kinds) or len({k.name for k in kinds[1:]}) < len(kinds[1:]):
    raise ValueError(f"smallthinker: rope_layout {layouts['rope_layout']} and sliding_window_layout {layouts['sliding_window_layout']} give layers that differ in the window alone, or two kinds of one name: not supported")
  return dict(layer_attn=layer_attn, router_input="attn", expert_act="relu")


def _nemotron_h_fields(hf: dict) -> dict:
  """``nemotron_h`` (Nemotron-H, arXiv:2504.03624; Nemotron-3-Nano) → the hybrid fields of ModelConfig. A published block
  is ``h + f(rmsnorm(h))`` with ONE sublayer ``f``, named by its letter in ``hybrid_override_pattern``: ``M`` a Mamba-2
  mixer (``mamba_num_heads`` heads of ``mamba_head_dim``, ``n_groups`` groups of B and C of ``ssm_state_size``, the gate
  ahead of a norm over each group's channels), ``*`` grouped-query attention with no position term, ``E`` the routed
  experts (sigmoid scores, a selection bias, the chosen scores over their sum times ``routed_scaling_factor``; two
  matrices and no gate an expert, relu² between them; one shared expert of the same form). Two consecutive blocks — a
  mixer, then ``E`` — are exactly the (mixer, FFN) step the decoder runs, so the pattern is read as layer STEPS:
  a mixer letter opens one and an ``E`` right behind it is its FFN, else it has none (``layer_ffn`` "none");
  ``n_layers`` counts the steps (29 for the published 52 letters). What cannot be read so, and what the decoder does
  not implement, is refused here, by name."""
  pattern = str(hf.get("hybrid_override_pattern") or "")
  if len(pattern) != int(hf["num_hidden_layers"]) or set(pattern) - set("ME*-"):
    raise ValueError(f"nemotron_h: hybrid_override_pattern must name num_hidden_layers = {hf['num_hidden_layers']} blocks, each 'M', 'E', '*' or '-'; got {pattern!r}")
  if "-" in pattern:
    raise ValueError("nemotron_h: the letter '-' in hybrid_override_pattern (a dense MLP block) is not supported")
  mixers, ffns = [], []
  for at, letter in enumerate(pattern):
    if letter in "M*":
      mixers.append("mamba" if letter == "M" else "attention")
      ffns.append("none")
    elif not ffns or ffns[-1] != "none":
      raise ValueError(f"nemotron_h: hybrid_override_pattern {pattern!r} cannot be read as (mixer, FFN) steps: the 'E' at block {at} has no mixer block right ahead of it")
    else:
      ffns[-1] = "experts"
  on = [key for key in ("mamba_proj_bias", "use_bias", "attention_bias", "mlp_bias", "sliding_window", "rope_scaling", "residual_in_fp32") if hf.get(key)]
  if on:
    raise ValueError(f"nemotron_h: {', '.join(on)} is not supported")
  if int(hf.get("n_group") or 1) > 1 and int(hf.get("topk_group") or 1) < int(hf["n_group"]):
    raise ValueError("nemotron_h: n_group > 1 with topk_group < n_group (group-limited routing) is not supported")
  for key, only in (("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu")):
    if hf.get(key, only) != only:
      raise ValueError(f"nemotron_h: {key} {hf[key]!r} is not supported (only {only!r})")
  if "experts" in ffns and not int(hf.get("n_routed_experts") or 0):
    raise ValueError("nemotron_h: an 'E' block needs n_routed_experts above 0")
  heads, groups = int(hf["mamba_num_heads"]), int(hf.get("n_groups") or 1)
  if heads % groups:
    raise ValueError(f"nemotron_h: mamba_num_heads {heads} is no multiple of n_groups {groups}")
  return dict(
    n_layers=len(mixers),
    layer_types=tuple(mixers),
    layer_ffn=tuple(ffns),
    ssm_heads=heads,
    ssm_head_dim=int(hf["mamba_head_dim"]),  # d_inner = heads x head_dim; ``expand`` is not read
    ssm_state=int(hf["ssm_state_size"]),
    ssm_conv=int(hf.get("conv_kernel") or 4),
    ssm_chunk=int(hf.get("chunk_size") or 128),
    ssm_groups=groups,
    ffn_gated=False,
    expert_act="relu2",
    mlp_act="relu2",
    use_rope=False,  # the family's attention applies no position term: the Mamba layers carry position (``rope_theta`` stands unread)
  )


def _lfm2_moe_fields(hf: dict) -> dict:
  """``lfm2_moe`` (LFM2-8B-A1B) → the hybrid fields of ModelConfig: ``layer_types`` names each layer "conv" (a gated short
  convolution of ``conv_L_cache`` taps over ``hidden_size`` channels, kind "conv": no state matrix, ``ssm_heads`` /
  ``ssm_head_dim`` / ``ssm_state`` 0) or "full_attention" (GQA with an RMSNorm over each head's q and k before rope);
  ``num_dense_layers`` leading layers have a dense SwiGLU of ``intermediate_size``, the rest ``num_experts`` experts of
  ``moe_intermediate_size`` under a sigmoid router whose choice adds ``expert_bias`` (``use_expert_bias``; false: no
  bias leaf); the norms' epsilon is ``norm_eps`` and the head is tied (``config_from_hf`` reads both by family). What
  the decoder does not implement is refused here, by name."""
  n_layers = int(hf["num_hidden_layers"])
  names = {"conv": "conv", "full_attention": "attention"}
  layer_types = tuple(hf.get("layer_types") or ())
  if len(layer_types) != n_layers or set(layer_types) - set(names):
    raise ValueError(f"lfm2_moe: layer_types must name num_hidden_layers = {n_layers} layers, each 'conv' or 'full_attention'; got {layer_types}")
  on = [key for key in ("conv_bias", "attention_bias", "rope_scaling") if hf.get(key)]
  if on:
    raise ValueError(f"lfm2_moe: {', '.join(on)} is not supported")
  if int(hf.get("conv_L_cache") or 0) < 2:
    raise ValueError("lfm2_moe: conv_L_cache (the short convolution's taps) must be 2 or more")
  return dict(
    layer_types=tuple(names[t] for t in layer_types),
    ssm_conv=int(hf["conv_L_cache"]),
    router_selection_bias=bool(hf.get("use_expert_bias", True)),
  )


def load_model_config(model_dir: str | Path, dtype=None) -> ModelConfig:
  with open(Path(model_dir) / "config.json") as f:
    return config_from_hf(json.load(f), dtype=dtype)


def tiny_test_config(**overrides) -> ModelConfig:
  """A small config for unit tests (CPU-fast, GQA + all variation points on)."""
  defaults = dict(
    vocab_size=256,
    dim=64,
    n_layers=4,
    n_heads=4,
    n_kv_heads=2,
    hidden_dim=128,
    norm_eps=1e-5,
    rope_theta=10000.0,
    max_seq_len=128,
    dtype=jnp.float32,
  )
  defaults.update(overrides)
  return ModelConfig(**defaults)
