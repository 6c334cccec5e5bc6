"""Export decoder params back to HF-transformers format (inverse of loader.py).

The reference fine-tunes through torchtune but has no path from its training
state back to a standard HF checkpoint; here ``export_hf_checkpoint`` writes
``config.json`` + ``model.safetensors`` that ``AutoModelForCausalLM`` loads
directly — train or LoRA-tune on TPU with this framework, then serve the
result anywhere. Golden round trip is verified THROUGH HF itself
(tests/test_hf_export.py: load → export → HF forward == original HF forward).

Scope: the dense decoder families whose load maps are bijective —
llama (incl. llama3 rope scaling), qwen2 (attention biases), qwen3
(per-head q/k RMSNorm), mistral, gemma2 (zero-centered norms re-centered,
four-norm layout, softcaps). MoE / MLA / fused-projection (phi3) exports
are refused with a clear message. LoRA adapters (train/lora.py), if present
in the tree, are merged into the base projections (w + 2·A@B — alpha=2·rank
so the scale is always 2, matching models/decoder.py's forward).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from .config import ModelConfig, RopeScaling

_MODEL_TYPE = {
  "llama": "llama",
  "qwen2": "qwen2",
  "qwen3": "qwen3",
  "mistral": "mistral",
  "gemma2": "gemma2",
  "phi3": "phi3",  # fused qkv / gate_up re-fused on write
  "mixtral": "mixtral",  # expert stacks unstacked to per-expert names
  "qwen2-moe": "qwen2_moe",
  "granite-hybrid": "granitemoehybrid",  # two stacks re-interleaved by layer_types
}


def _np32(x) -> np.ndarray:
  return np.asarray(x, dtype=np.float32)


def _lin(w) -> np.ndarray:
  """Our [in, out] → torch Linear [out, in]."""
  return np.ascontiguousarray(_np32(w).T)


def export_hf_checkpoint(out_dir: str | Path, cfg: ModelConfig, params: dict, dtype: str = "float32") -> Path:
  """Write an HF-loadable checkpoint; returns the directory.

  ``params`` is a FULL-model tree (embed + all layers + final_norm [+
  lm_head]) in the decoder layout (stacked [L, ...] leaves).
  """
  if cfg.family not in _MODEL_TYPE:
    raise NotImplementedError(f"HF export supports {sorted(_MODEL_TYPE)}; {cfg.family!r} (MLA layouts, and the hybrids with no safetensors name map) is not exportable")
  if cfg.is_mla:
    raise NotImplementedError("HF export of MLA (deepseek) trees is not supported")
  if cfg.vision is not None:
    raise NotImplementedError("HF export of vision (llava) trees is not supported — the tower/projector would be silently dropped")
  if not isinstance(params, dict) or "embed" not in params or "final_norm" not in params:
    raise ValueError("export needs a FULL model tree (first+last shard params); mesh serving modes (pp/sp) hold params elsewhere — export from a plain load")
  for stack_key in ("layers", "moe_layers", "ssm_layers"):
    if any(k.endswith("_scale") for k in params.get(stack_key, {})):
      raise NotImplementedError("params are int8/int4-quantized (XOT_TPU_QUANT); export from an unquantized load — casting quantized codes to float would silently corrupt the checkpoint")

  # LoRA adapters fold into the base weights through THE training/decode
  # merge (train/lora.py — one scale definition), not a local copy.
  if any(k.endswith("_lora_a") for k in params.get("layers", {})):
    from ..train.lora import merge_lora

    rank = next(v for k, v in params["layers"].items() if k.endswith("_lora_a")).shape[-1]
    params = merge_lora(params, rank)

  gemma = cfg.post_norms  # zero-centered norms were re-centered (+1) at load
  out_dir = Path(out_dir)
  out_dir.mkdir(parents=True, exist_ok=True)

  def norm(w) -> np.ndarray:
    w = _np32(w)
    return np.ascontiguousarray(w - 1.0 if gemma else w)

  phi3 = cfg.family == "phi3"
  sd: dict[str, np.ndarray] = {"model.embed_tokens.weight": _np32(params["embed"])}
  # MoE stacks live under "moe_layers" (dense-prefix models) or "layers".
  stacks = [params[k] for k in ("layers", "moe_layers") if k in params]
  if cfg.recurrent_layers:
    stacks = []
    _granite_hybrid_layers(sd, cfg, params)
  i = -1
  for stack in stacks:
    L = stack["attn_norm"].shape[0]
    for li in range(L):
      i += 1
      p = {k: v[li] for k, v in stack.items()}
      pre = f"model.layers.{i}"
      sd[f"{pre}.input_layernorm.weight"] = norm(p["attn_norm"])
      if phi3:  # fused projections, as the HF checkpoint stores them
        sd[f"{pre}.self_attn.qkv_proj.weight"] = np.concatenate([_lin(p["wq"]), _lin(p["wk"]), _lin(p["wv"])], axis=0)
      else:
        sd[f"{pre}.self_attn.q_proj.weight"] = _lin(p["wq"])
        sd[f"{pre}.self_attn.k_proj.weight"] = _lin(p["wk"])
        sd[f"{pre}.self_attn.v_proj.weight"] = _lin(p["wv"])
      sd[f"{pre}.self_attn.o_proj.weight"] = _lin(p["wo"])
      if "bq" in p:
        sd[f"{pre}.self_attn.q_proj.bias"] = _np32(p["bq"])
        sd[f"{pre}.self_attn.k_proj.bias"] = _np32(p["bk"])
        sd[f"{pre}.self_attn.v_proj.bias"] = _np32(p["bv"])
      if "q_norm" in p:  # qwen3 per-head q/k RMSNorm
        sd[f"{pre}.self_attn.q_norm.weight"] = _np32(p["q_norm"])
        sd[f"{pre}.self_attn.k_norm.weight"] = _np32(p["k_norm"])
      if gemma:  # four-norm layout
        sd[f"{pre}.post_attention_layernorm.weight"] = norm(p["post_attn_norm"])
        sd[f"{pre}.pre_feedforward_layernorm.weight"] = norm(p["mlp_norm"])
        sd[f"{pre}.post_feedforward_layernorm.weight"] = norm(p["post_mlp_norm"])
      else:
        sd[f"{pre}.post_attention_layernorm.weight"] = norm(p["mlp_norm"])
      if "w_experts_gate" in p:  # routed MoE: unstack experts to HF names
        E = p["w_experts_gate"].shape[0]
        if cfg.family == "mixtral":
          sd[f"{pre}.block_sparse_moe.gate.weight"] = _lin(p["w_router"])
          for e in range(E):
            sd[f"{pre}.block_sparse_moe.experts.{e}.w1.weight"] = _lin(p["w_experts_gate"][e])
            sd[f"{pre}.block_sparse_moe.experts.{e}.w3.weight"] = _lin(p["w_experts_up"][e])
            sd[f"{pre}.block_sparse_moe.experts.{e}.w2.weight"] = _lin(p["w_experts_down"][e])
        else:  # qwen2-moe
          sd[f"{pre}.mlp.gate.weight"] = _lin(p["w_router"])
          for e in range(E):
            sd[f"{pre}.mlp.experts.{e}.gate_proj.weight"] = _lin(p["w_experts_gate"][e])
            sd[f"{pre}.mlp.experts.{e}.up_proj.weight"] = _lin(p["w_experts_up"][e])
            sd[f"{pre}.mlp.experts.{e}.down_proj.weight"] = _lin(p["w_experts_down"][e])
          if "w_shared_gate" in p:
            sd[f"{pre}.mlp.shared_expert.gate_proj.weight"] = _lin(p["w_shared_gate"])
            sd[f"{pre}.mlp.shared_expert.up_proj.weight"] = _lin(p["w_shared_up"])
            sd[f"{pre}.mlp.shared_expert.down_proj.weight"] = _lin(p["w_shared_down"])
          if "w_shared_expert_gate" in p:
            sd[f"{pre}.mlp.shared_expert_gate.weight"] = _lin(p["w_shared_expert_gate"])
      elif phi3:
        sd[f"{pre}.mlp.gate_up_proj.weight"] = np.concatenate([_lin(p["w_gate"]), _lin(p["w_up"])], axis=0)
        sd[f"{pre}.mlp.down_proj.weight"] = _lin(p["w_down"])
      else:
        sd[f"{pre}.mlp.gate_proj.weight"] = _lin(p["w_gate"])
        sd[f"{pre}.mlp.up_proj.weight"] = _lin(p["w_up"])
        sd[f"{pre}.mlp.down_proj.weight"] = _lin(p["w_down"])
  sd["model.norm.weight"] = norm(params["final_norm"])
  tied = "lm_head" not in params
  if not tied:
    sd["lm_head.weight"] = np.ascontiguousarray(_np32(params["lm_head"]).T)

  import torch
  from safetensors.torch import save_file

  torch_dtype = {"float32": torch.float32, "bfloat16": torch.bfloat16}[dtype]
  save_file({k: torch.from_numpy(np.ascontiguousarray(v).copy()).to(torch_dtype) for k, v in sd.items()}, str(out_dir / "model.safetensors"))

  hf_cfg: dict = {
    "architectures": [_arch(cfg.family)],
    "model_type": _MODEL_TYPE[cfg.family],
    "vocab_size": cfg.vocab_size,
    "hidden_size": cfg.dim,
    "intermediate_size": cfg.hidden_dim,
    "num_hidden_layers": cfg.n_layers,
    "num_attention_heads": cfg.n_heads,
    "num_key_value_heads": cfg.n_kv_heads,
    "head_dim": cfg.head_dim,
    "rms_norm_eps": cfg.norm_eps,
    "rope_theta": cfg.rope_theta,
    "max_position_embeddings": cfg.max_seq_len,
    "tie_word_embeddings": tied,
    # without this, architectures defaulting to bias=False would silently
    # drop the exported q/k/v bias tensors at from_pretrained
    "attention_bias": bool(cfg.qkv_bias),
    "torch_dtype": dtype,  # legacy key; transformers ≥4.56 reads "dtype"
    "dtype": dtype,
  }
  if cfg.partial_rotary_factor != 1.0:  # phi3/phi-4: rope only leading channels
    hf_cfg["partial_rotary_factor"] = cfg.partial_rotary_factor
  if cfg.eos_token_ids:
    hf_cfg["eos_token_id"] = list(cfg.eos_token_ids) if len(cfg.eos_token_ids) > 1 else cfg.eos_token_ids[0]
  # Carry the source's bos/pad ids verbatim. Omitting them lets transformers
  # re-apply architecture defaults on import — Phi3Config defaults
  # pad_token_id=32000, which crashes nn.Embedding for any smaller vocab.
  if cfg.bos_token_id is not None:
    hf_cfg["bos_token_id"] = cfg.bos_token_id
  if cfg.pad_token_id is not None:
    hf_cfg["pad_token_id"] = cfg.pad_token_id
  if isinstance(cfg.rope_scaling, RopeScaling):
    hf_cfg["rope_scaling"] = {
      "rope_type": "llama3",
      "factor": cfg.rope_scaling.factor,
      "low_freq_factor": cfg.rope_scaling.low_freq_factor,
      "high_freq_factor": cfg.rope_scaling.high_freq_factor,
      "original_max_position_embeddings": cfg.rope_scaling.original_max_position_embeddings,
    }
  if gemma:
    hf_cfg.update(
      attn_logit_softcapping=cfg.attn_logit_softcap or None,
      final_logit_softcapping=cfg.final_logit_softcap or None,
      query_pre_attn_scalar=cfg.query_pre_attn_scalar or cfg.head_dim,
      sliding_window=cfg.sliding_window or None,
      hidden_act="gelu_pytorch_tanh",
      hidden_activation="gelu_pytorch_tanh",
    )
  if cfg.family == "granite-hybrid":
    hf_cfg.update(
      layer_types=list(cfg.layer_types), shared_intermediate_size=cfg.hidden_dim, num_local_experts=0, num_experts_per_tok=0,
      mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim, mamba_d_state=cfg.ssm_state, mamba_d_conv=cfg.ssm_conv, mamba_chunk_size=cfg.ssm_chunk,
      mamba_expand=cfg.ssm_inner // cfg.dim, mamba_n_groups=1, mamba_conv_bias=True, mamba_proj_bias=False,
      embedding_multiplier=cfg.embed_scale, residual_multiplier=cfg.residual_multiplier, logits_scaling=cfg.logits_scaling,
      attention_multiplier=cfg.attn_multiplier or cfg.head_dim**-0.5, position_embedding_type="rope" if cfg.use_rope else "nope",
    )
    del hf_cfg["head_dim"]  # GraniteMoeHybridConfig derives it from hidden_size // num_attention_heads
  if cfg.n_experts:
    hf_cfg.update(num_experts_per_tok=cfg.n_active_experts, norm_topk_prob=cfg.norm_topk_prob)
    if cfg.family == "mixtral":
      hf_cfg["num_local_experts"] = cfg.n_experts
    else:  # qwen2-moe
      hf_cfg.update(
        num_experts=cfg.n_experts,
        moe_intermediate_size=cfg.moe_hidden_dim,
        shared_expert_intermediate_size=cfg.shared_expert_dim,
        decoder_sparse_step=1,
        mlp_only_layers=[],
      )
  (out_dir / "config.json").write_text(json.dumps(hf_cfg, indent=2))
  return out_dir


def _granite_hybrid_layers(sd: dict, cfg: ModelConfig, params: dict) -> None:
  """``GraniteMoeHybridForCausalLM``'s per-layer names from the two stacks, in ``layer_types`` order (the
  inverse of loader.py's split): the mixer by kind, then the fused SwiGLU ``shared_mlp`` every layer has."""
  seen = {"mamba": 0, "attention": 0}
  for i, kind in enumerate(cfg.layer_types):
    stack = params["ssm_layers" if kind == "mamba" else "layers"]
    p = {k: v[seen[kind]] for k, v in stack.items()}
    seen[kind] += 1
    pre = f"model.layers.{i}"
    if kind == "mamba":
      sd[f"{pre}.input_layernorm.weight"] = _np32(p["ssm_norm"])
      sd[f"{pre}.mamba.in_proj.weight"] = np.concatenate([_lin(p["w_z"]), _lin(p["w_xbc"]), _lin(p["w_dt"])], axis=0)
      sd[f"{pre}.mamba.conv1d.weight"] = np.ascontiguousarray(_np32(p["conv_w"]).T[:, None, :])  # [K, C] → [C, 1, K]
      sd[f"{pre}.mamba.conv1d.bias"] = _np32(p["conv_b"])
      for name in ("dt_bias", "A_log", "D"):
        sd[f"{pre}.mamba.{name}"] = _np32(p[name])
      sd[f"{pre}.mamba.norm.weight"] = _np32(p["gate_norm"])
      sd[f"{pre}.mamba.out_proj.weight"] = _lin(p["w_out"])
    else:
      sd[f"{pre}.input_layernorm.weight"] = _np32(p["attn_norm"])
      for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj")):
        sd[f"{pre}.self_attn.{theirs}.weight"] = _lin(p[ours])
    sd[f"{pre}.post_attention_layernorm.weight"] = _np32(p["mlp_norm"])
    sd[f"{pre}.shared_mlp.input_linear.weight"] = np.concatenate([_lin(p["w_gate"]), _lin(p["w_up"])], axis=0)
    sd[f"{pre}.shared_mlp.output_linear.weight"] = _lin(p["w_down"])


def _arch(family: str) -> str:
  return {
    "granite-hybrid": "GraniteMoeHybridForCausalLM",
    "llama": "LlamaForCausalLM",
    "qwen2": "Qwen2ForCausalLM",
    "qwen3": "Qwen3ForCausalLM",
    "mistral": "MistralForCausalLM",
    "gemma2": "Gemma2ForCausalLM",
    "phi3": "Phi3ForCausalLM",
    "mixtral": "MixtralForCausalLM",
    "qwen2-moe": "Qwen2MoeForCausalLM",
  }[family]
