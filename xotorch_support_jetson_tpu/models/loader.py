"""HF safetensors → decoder pytree weight loading, shard-aware.

Capability parity with reference ``llm_utils.py:97-284``
(``load_model_weights_torchtune``: per-layer regex renames :181-246, q/k
permutation :126-134, embed/norm/lm_head mapping :249-269, ``check_weights``
validator :80-95). Differences by design:

- **No q/k permutation.** The reference permutes q/k because torchtune uses
  interleaved RoPE pairing; our RoPE (ops/rope.py) uses the HF half-rotation
  convention, so checkpoints load as stored.
- **Stacked layers.** Per-layer tensors are stacked into ``[L, ...]`` leaves
  to feed ``lax.scan`` (models/decoder.py) — the loader is where the AoS→SoA
  transpose happens, once, at load time.
- **Shard-aware file selection.** Only safetensors files containing the
  shard's layer range are opened (same contract as the reference's
  weight-map-based download filtering, ``new_shard_download.py:181-194``).
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from ..inference.shard import Shard
from ..utils.helpers import DEBUG
from .config import ModelConfig
from .decoder import Params

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

# HF per-layer suffix → (our key, transpose?)
_LAYER_MAP: dict[str, tuple[str, bool]] = {
  "input_layernorm.weight": ("attn_norm", False),
  "self_attn.q_proj.weight": ("wq", True),
  "self_attn.k_proj.weight": ("wk", True),
  "self_attn.v_proj.weight": ("wv", True),
  "self_attn.o_proj.weight": ("wo", True),
  "self_attn.q_proj.bias": ("bq", False),
  "self_attn.k_proj.bias": ("bk", False),
  "self_attn.v_proj.bias": ("bv", False),
  # qwen3: per-head RMSNorm on q/k (weights [head_dim], applied before rope)
  "self_attn.q_norm.weight": ("q_norm", False),
  "self_attn.k_norm.weight": ("k_norm", False),
  # MLA projections (deepseek-v2/v3, HF DeepseekV2Attention): q optionally
  # LoRA-compressed; KV compressed to a latent + MQA rope channel.
  "self_attn.q_a_proj.weight": ("wq_a", True),
  "self_attn.q_a_layernorm.weight": ("q_a_norm", False),
  "self_attn.q_b_proj.weight": ("wq_b", True),
  "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", True),
  "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
  "self_attn.kv_b_proj.weight": ("wkv_b", True),
  "post_attention_layernorm.weight": ("mlp_norm", False),
  # gemma2's four-norm layout: input_layernorm/post_attention_layernorm wrap
  # attention (the latter remapped to post_attn_norm below when
  # cfg.post_norms), pre/post_feedforward_layernorm wrap the MLP.
  "pre_feedforward_layernorm.weight": ("mlp_norm", False),
  "post_feedforward_layernorm.weight": ("post_mlp_norm", False),
  "mlp.gate_proj.weight": ("w_gate", True),
  "mlp.up_proj.weight": ("w_up", True),
  "mlp.down_proj.weight": ("w_down", True),
  # MoE routers / shared experts (mixtral, qwen2-moe, deepseek-v2/v3; the
  # reference registers these models but cannot load them — SURVEY.md §2.11).
  "block_sparse_moe.gate.weight": ("w_router", True),
  "mlp.gate.weight": ("w_router", True),
  "mlp.gate.e_score_correction_bias": ("router_bias", False),
  "mlp.shared_expert.gate_proj.weight": ("w_shared_gate", True),
  "mlp.shared_expert.up_proj.weight": ("w_shared_up", True),
  "mlp.shared_expert.down_proj.weight": ("w_shared_down", True),
  "mlp.shared_experts.gate_proj.weight": ("w_shared_gate", True),
  "mlp.shared_experts.up_proj.weight": ("w_shared_up", True),
  "mlp.shared_experts.down_proj.weight": ("w_shared_down", True),
  "mlp.shared_expert_gate.weight": ("w_shared_expert_gate", True),
  # granitemoehybrid (HF GraniteMoeHybridMambaLayer / GraniteMoeHybridMLP): a "mamba" layer's input_layernorm
  # is renamed ``ssm_norm`` below; in_proj, conv1d.weight and the fused shared_mlp.input_linear are handled by name.
  "mamba.out_proj.weight": ("w_out", True),
  "mamba.conv1d.bias": ("conv_b", False),
  "mamba.dt_bias": ("dt_bias", False),
  "mamba.A_log": ("A_log", False),
  "mamba.D": ("D", False),
  "mamba.norm.weight": ("gate_norm", False),
  "shared_mlp.output_linear.weight": ("w_down", True),
}
_F32_KEYS = ("router_bias", "dt_bias", "A_log", "D")  # small per-layer vectors whose precision the model leans on

# Per-expert projections: `{block_sparse_moe|mlp}.experts.{e}.{proj}.weight`,
# stacked into [E, D, F] / [E, F, D] leaves (mixtral names w1/w3/w2).
_EXPERT_RE = re.compile(r"^(?:block_sparse_moe|mlp)\.experts\.(\d+)\.(w1|w2|w3|gate_proj|up_proj|down_proj)\.weight$")
_EXPERT_KEY = {
  "w1": "w_experts_gate",
  "gate_proj": "w_experts_gate",
  "w3": "w_experts_up",
  "up_proj": "w_experts_up",
  "w2": "w_experts_down",
  "down_proj": "w_experts_down",
}

# Vision tower (llava: CLIP ViT, HF `vision_tower.vision_model.*`) per-layer
# suffix → (our key, transpose?). Non-layer tensors handled by name below.
_VISION_LAYER_MAP = {
  "layer_norm1.weight": ("ln1_scale", False),
  "layer_norm1.bias": ("ln1_bias", False),
  "self_attn.q_proj.weight": ("wq", True),
  "self_attn.q_proj.bias": ("bq", False),
  "self_attn.k_proj.weight": ("wk", True),
  "self_attn.k_proj.bias": ("bk", False),
  "self_attn.v_proj.weight": ("wv", True),
  "self_attn.v_proj.bias": ("bv", False),
  "self_attn.out_proj.weight": ("wo", True),
  "self_attn.out_proj.bias": ("bo", False),
  "layer_norm2.weight": ("ln2_scale", False),
  "layer_norm2.bias": ("ln2_bias", False),
  "mlp.fc1.weight": ("fc1", True),
  "mlp.fc1.bias": ("bfc1", False),
  "mlp.fc2.weight": ("fc2", True),
  "mlp.fc2.bias": ("bfc2", False),
}
_VISION_TOP_MAP = {
  "vision_tower.vision_model.embeddings.class_embedding": ("class_embed", False),
  "vision_tower.vision_model.embeddings.patch_embedding.weight": ("patch_embed", False),
  "vision_tower.vision_model.embeddings.position_embedding.weight": ("pos_embed", False),
  "vision_tower.vision_model.pre_layrnorm.weight": ("pre_ln_scale", False),  # HF's typo, as stored
  "vision_tower.vision_model.pre_layrnorm.bias": ("pre_ln_bias", False),
}
_PROJECTOR_MAP = {
  "multi_modal_projector.linear_1.weight": ("w1", True),
  "multi_modal_projector.linear_1.bias": ("b1", False),
  "multi_modal_projector.linear_2.weight": ("w2", True),
  "multi_modal_projector.linear_2.bias": ("b2", False),
}
_VISION_LAYER_RE = re.compile(r"^vision_tower\.vision_model\.encoder\.layers\.(\d+)\.(.+)$")


def _normalize_name(name: str) -> str:
  """llava checkpoints prefix the text decoder as ``language_model.`` —
  strip it so the standard maps apply."""
  if name.startswith("language_model."):
    return name[len("language_model.") :]
  return name


def _to_numpy(tensor) -> np.ndarray:
  """safetensors tensor (possibly torch bf16) → numpy (ml_dtypes bf16 ok)."""
  if isinstance(tensor, np.ndarray):
    return tensor
  import ml_dtypes
  import torch

  if tensor.dtype == torch.bfloat16:
    return tensor.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
  return tensor.numpy()


def _weight_files_for_shard(model_dir: Path, shard: Shard) -> list[Path]:
  """Resolve which .safetensors files hold this shard's tensors."""
  index_path = model_dir / "model.safetensors.index.json"
  if not index_path.exists():
    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
      raise FileNotFoundError(f"no safetensors files under {model_dir}")
    return files
  with open(index_path) as f:
    weight_map: dict[str, str] = json.load(f)["weight_map"]
  needed: set[str] = set()
  for raw_name, fname in weight_map.items():
    name = _normalize_name(raw_name)
    m = _LAYER_RE.match(name)
    if m:
      if shard.start_layer <= int(m.group(1)) <= shard.end_layer:
        needed.add(fname)
    elif name.startswith("model.embed_tokens") and (shard.is_first_layer or shard.is_last_layer):
      needed.add(fname)
    elif (name.startswith("model.norm") or name.startswith("lm_head")) and shard.is_last_layer:
      needed.add(fname)
    elif (raw_name.startswith(("vision_tower.", "multi_modal_projector.")) or raw_name == "image_newline") and shard.is_first_layer:
      needed.add(fname)
  return [model_dir / f for f in sorted(needed)]


_NO_NAME_MAP = {"bailing-hybrid": "bailing_hybrid (Ling-3.0)", "olmo-hybrid": "olmo_hybrid (Olmo-Hybrid)", "laguna": "laguna (Laguna-XS.2)", "smallthinker": "smallthinker (SmallThinker-21BA3B)", "nemotron-h": "nemotron_h (Nemotron-3-Nano)", "lfm2-moe": "lfm2_moe (LFM2-8B-A1B)"}  # family -> the model_type refused by name


def load_shard_weights(model_dir: str | Path, cfg: ModelConfig, shard: Shard) -> Params:
  """Load a shard's params from HF safetensors into the decoder layout."""
  from safetensors import safe_open

  if cfg.family in _NO_NAME_MAP:
    # No safetensors index of these families was at hand to take the tensor names from: the decoder serves the
    # architecture from a parameter tree (tests, the benchmark's seeded weights), not from a checkpoint.
    raise NotImplementedError(f"{_NO_NAME_MAP[cfg.family]} checkpoints cannot be loaded: this loader has no safetensors name map for the family")
  model_dir = Path(model_dir)
  per_layer: dict[int, dict[str, np.ndarray]] = {i: {} for i in range(shard.start_layer, shard.end_layer + 1)}
  top: dict[str, np.ndarray] = {}
  vision_layers: dict[str, dict[int, np.ndarray]] = {}
  vision_top: dict[str, np.ndarray] = {}
  projector: dict[str, np.ndarray] = {}

  for file in _weight_files_for_shard(model_dir, shard):
    with safe_open(str(file), framework="pt") as f:
      for raw_name in f.keys():
        name = _normalize_name(raw_name)
        if raw_name == "image_newline":  # llava-next: learned row terminator
          if shard.is_first_layer and cfg.vision is not None:
            projector["image_newline"] = _to_numpy(f.get_tensor(raw_name))
          continue
        if raw_name.startswith(("vision_tower.", "multi_modal_projector.")):
          # llava vision tower + projector ride with the FIRST shard (the
          # node that embeds the prompt also embeds the images).
          if not (shard.is_first_layer and cfg.vision is not None):
            continue
          vm = _VISION_LAYER_RE.match(raw_name)
          if vm and vm.group(2) in _VISION_LAYER_MAP:
            key, tr = _VISION_LAYER_MAP[vm.group(2)]
            arr = _to_numpy(f.get_tensor(raw_name))
            vision_layers.setdefault(key, {})[int(vm.group(1))] = arr.T if tr else arr
          elif raw_name in _VISION_TOP_MAP:
            key, tr = _VISION_TOP_MAP[raw_name]
            vision_top[key] = _to_numpy(f.get_tensor(raw_name))
          elif raw_name in _PROJECTOR_MAP:
            key, tr = _PROJECTOR_MAP[raw_name]
            arr = _to_numpy(f.get_tensor(raw_name))
            projector[key] = arr.T if tr else arr
          continue
        m = _LAYER_RE.match(name)
        if m:
          layer_idx = int(m.group(1))
          if not (shard.start_layer <= layer_idx <= shard.end_layer):
            continue
          suffix = m.group(2)
          mapped = _LAYER_MAP.get(suffix)
          if mapped is not None:
            key, transpose = mapped
            if cfg.post_norms and suffix == "post_attention_layernorm.weight":
              key = "post_attn_norm"  # gemma2: this norm follows attention
            arr = _to_numpy(f.get_tensor(raw_name))
            per_layer[layer_idx][key] = arr.T if transpose else arr
            continue
          if suffix == "self_attn.qkv_proj.weight":  # phi3: fused [q+k+v, D]
            arr = _to_numpy(f.get_tensor(raw_name))
            qd, kd = cfg.q_dim, cfg.kv_dim
            per_layer[layer_idx]["wq"] = arr[:qd].T
            per_layer[layer_idx]["wk"] = arr[qd : qd + kd].T
            per_layer[layer_idx]["wv"] = arr[qd + kd :].T
            continue
          if suffix in ("mlp.gate_up_proj.weight", "shared_mlp.input_linear.weight"):  # phi3, granite: fused [2F, D]
            arr = _to_numpy(f.get_tensor(raw_name))
            per_layer[layer_idx]["w_gate"] = arr[: cfg.hidden_dim].T
            per_layer[layer_idx]["w_up"] = arr[cfg.hidden_dim :].T
            continue
          if suffix == "mamba.in_proj.weight":  # [di + (di + 2N) + H, D], cut at its three outputs z | xBC | dt
            arr, di, C = _to_numpy(f.get_tensor(raw_name)), cfg.ssm_inner, cfg.ssm_conv_dim
            per_layer[layer_idx].update(w_z=arr[:di].T, w_xbc=arr[di : di + C].T, w_dt=arr[di + C :].T)
            continue
          if suffix == "mamba.conv1d.weight":  # depthwise [C, 1, K] → taps-major [K, C]
            per_layer[layer_idx]["conv_w"] = _to_numpy(f.get_tensor(raw_name))[:, 0, :].T
            continue
          em = _EXPERT_RE.match(suffix)
          if em is not None:
            key = _EXPERT_KEY[em.group(2)]
            per_layer[layer_idx].setdefault(key, {})[int(em.group(1))] = _to_numpy(f.get_tensor(raw_name)).T
            continue
          if DEBUG >= 3:
            print(f"[loader] skipping unmapped tensor {name}")
        elif name == "model.embed_tokens.weight":
          if shard.is_first_layer or (shard.is_last_layer and cfg.tied_embedding):
            top["embed_tokens"] = _to_numpy(f.get_tensor(raw_name))
        elif name == "model.norm.weight" and shard.is_last_layer:
          top["final_norm"] = _to_numpy(f.get_tensor(raw_name))
        elif name == "lm_head.weight" and shard.is_last_layer:
          top["lm_head"] = _to_numpy(f.get_tensor(raw_name)).T

  # Stack per-layer dicts (AoS) into [L, ...] leaves (SoA) for lax.scan —
  # a dense-prefix stack ("layers") and, for MoE models, an MoE stack
  # ("moe_layers") with per-expert leaves stacked on an extra [E] axis.
  first_k = cfg.first_k_dense if cfg.n_experts else shard.n_layers
  all_idx = range(shard.start_layer, shard.end_layer + 1)
  groups = [("layers", [i for i in all_idx if i < first_k]), ("moe_layers", [i for i in all_idx if i >= first_k])]
  if cfg.recurrent_layers:
    # A hybrid's two stacks, each in model order (models/decoder.py _layer_runs interleaves them again).
    if not (shard.is_first_layer and shard.is_last_layer):
      raise ValueError("a configuration with recurrent layers loads whole: its two stacks do not split by a layer range")
    groups = [("layers", [i for i in all_idx if cfg.layer_types[i] == "attention"]), ("ssm_layers", [i for i in all_idx if cfg.layer_types[i] == "mamba"])]
    for i in groups[1][1]:
      per_layer[i]["ssm_norm"] = per_layer[i].pop("attn_norm")

  _norm_keys = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")

  def as_leaf(t, key: str):
    if isinstance(t, dict):  # experts: {e → [D,F]} → [E, D, F]
      if sorted(t) != list(range(len(t))):
        raise ValueError(f"{key}: missing expert tensors (have {sorted(t)})")
      t = np.stack([t[e] for e in range(len(t))])
    dtype = jnp.float32 if key in _F32_KEYS else cfg.dtype
    if cfg.post_norms and key in _norm_keys:
      # gemma stores zero-centered norm weights; HF computes x*(1+w.float())
      # in fp32, so the gain must stay fp32 — a bf16(1+w) round-trip loses
      # any |w| < 2^-8 entirely (rms_norm upcasts, so fp32 gains are exact).
      t = np.asarray(t, dtype=np.float32) + 1.0
      dtype = jnp.float32
    return jnp.asarray(np.ascontiguousarray(t), dtype=dtype)

  params: Params = {}
  for stack_name, indices in groups:
    if not indices:
      continue
    layer_keys = sorted(per_layer[indices[0]].keys())
    for idx in indices:
      missing = set(layer_keys) - set(per_layer[idx])
      if missing:
        raise ValueError(f"layer {idx}: missing tensors {sorted(missing)}")
    params[stack_name] = {key: jnp.stack([as_leaf(per_layer[i][key], key) for i in indices]) for key in layer_keys}
    if cfg.sliding_window:
      # Per-layer sliding flag from the GLOBAL layer index, riding EVERY
      # stack so the lax.scan sees it as a traced per-layer scalar.
      from .decoder import sliding_flags

      params[stack_name]["is_sliding"] = sliding_flags(cfg, indices)
  if shard.is_first_layer:
    params["embed"] = jnp.asarray(top["embed_tokens"], dtype=cfg.dtype)
    if vision_layers:  # llava: vision tower + projector ride with shard 0
      L = cfg.vision.n_layers
      for key, by_idx in vision_layers.items():
        if sorted(by_idx) != list(range(L)):
          raise ValueError(f"vision/{key}: missing layers (have {sorted(by_idx)})")
      params["vision"] = {
        **{k: jnp.asarray(v, dtype=cfg.dtype) for k, v in vision_top.items()},
        "layers": {key: jnp.stack([jnp.asarray(by_idx[i], dtype=cfg.dtype) for i in range(L)]) for key, by_idx in vision_layers.items()},
      }
      params["projector"] = {k: jnp.asarray(v, dtype=cfg.dtype) for k, v in projector.items()}
  if shard.is_last_layer:
    fn = top["final_norm"]
    if cfg.post_norms:  # gemma zero-centered gain; fp32 like the layer norms
      params["final_norm"] = jnp.asarray(np.asarray(fn, dtype=np.float32) + 1.0, dtype=jnp.float32)
    else:
      params["final_norm"] = jnp.asarray(fn, dtype=cfg.dtype)
    if "lm_head" in top:
      params["lm_head"] = jnp.asarray(top["lm_head"], dtype=cfg.dtype)
    elif cfg.tied_embedding:
      if not shard.is_first_layer:
        params["lm_head"] = jnp.asarray(top["embed_tokens"], dtype=cfg.dtype).T
      # first+last single shard: decoder falls back to embed.T
    else:
      raise ValueError("last shard: no lm_head weight and embeddings not tied")
  check_shard_params(params, cfg, shard)
  return params


def check_shard_params(params: Params, cfg: ModelConfig, shard: Shard) -> None:
  """Shape validator (role of reference ``check_weights``, llm_utils.py:80-95)."""
  L = shard.n_shard_layers
  if cfg.n_experts:
    n_dense = sum(1 for i in range(shard.start_layer, shard.end_layer + 1) if i < cfg.first_k_dense)
  else:
    n_dense = L

  def attn_expect(L):
    if cfg.is_mla:
      H = cfg.n_heads
      exp = {
        "attn_norm": (L, cfg.dim),
        "wkv_a": (L, cfg.dim, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_a_norm": (L, cfg.kv_lora_rank),
        "wkv_b": (L, cfg.kv_lora_rank, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (L, H * cfg.v_head_dim, cfg.dim),
        "mlp_norm": (L, cfg.dim),
      }
      if cfg.q_lora_rank:
        exp.update({
          "wq_a": (L, cfg.dim, cfg.q_lora_rank),
          "q_a_norm": (L, cfg.q_lora_rank),
          "wq_b": (L, cfg.q_lora_rank, H * cfg.qk_head_dim),
        })
      else:
        exp["wq"] = (L, cfg.dim, H * cfg.qk_head_dim)
      return exp
    exp = {
      "attn_norm": (L, cfg.dim),
      "wq": (L, cfg.dim, cfg.q_dim),
      "wk": (L, cfg.dim, cfg.kv_dim),
      "wv": (L, cfg.dim, cfg.kv_dim),
      "wo": (L, cfg.q_dim, cfg.dim),
      "mlp_norm": (L, cfg.dim),
    }
    if cfg.qkv_bias:
      exp.update({"bq": (L, cfg.q_dim), "bk": (L, cfg.kv_dim), "bv": (L, cfg.kv_dim)})
    if cfg.qk_norm:  # qwen3: the decoder gates on key presence, so a missing
      # q/k norm must fail HERE, not silently skip the norm
      exp["q_norm"] = (L, cfg.head_dim)
      exp["k_norm"] = (L, cfg.head_dim)
    if cfg.post_norms:  # gemma2: the decoder gates on key presence, so a
      # missing post-norm must fail HERE, not silently skip the norm.
      exp["post_attn_norm"] = (L, cfg.dim)
      exp["post_mlp_norm"] = (L, cfg.dim)
    if cfg.sliding_window:
      exp["is_sliding"] = (L,)
    return exp

  checks: dict[str, dict] = {}
  if cfg.recurrent_layers:  # a hybrid: "layers" holds its attention layers only, "ssm_layers" the rest
    n_dense, L, Ls = cfg.n_attn_layers, cfg.n_attn_layers, cfg.recurrent_layers
    H, di, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
    checks["ssm_layers"] = {
      "ssm_norm": (Ls, cfg.dim), "w_z": (Ls, cfg.dim, di), "w_xbc": (Ls, cfg.dim, C), "w_dt": (Ls, cfg.dim, H), "conv_w": (Ls, cfg.ssm_conv, C), "conv_b": (Ls, C),
      "dt_bias": (Ls, H), "A_log": (Ls, H), "D": (Ls, H), "gate_norm": (Ls, di), "w_out": (Ls, di, cfg.dim), "mlp_norm": (Ls, cfg.dim),
      "w_gate": (Ls, cfg.dim, cfg.hidden_dim), "w_up": (Ls, cfg.dim, cfg.hidden_dim), "w_down": (Ls, cfg.hidden_dim, cfg.dim),
    }
  if n_dense:
    checks["layers"] = {
      **attn_expect(n_dense),
      "w_gate": (n_dense, cfg.dim, cfg.hidden_dim),
      "w_up": (n_dense, cfg.dim, cfg.hidden_dim),
      "w_down": (n_dense, cfg.hidden_dim, cfg.dim),
    }
  if L - n_dense:
    Lm, E, Fm, Fs = L - n_dense, cfg.n_experts, cfg.moe_hidden_dim, cfg.shared_expert_dim
    moe_exp = {
      **attn_expect(Lm),
      "w_router": (Lm, cfg.dim, E),
      "w_experts_gate": (Lm, E, cfg.dim, Fm),
      "w_experts_up": (Lm, E, cfg.dim, Fm),
      "w_experts_down": (Lm, E, Fm, cfg.dim),
    }
    if Fs:
      moe_exp.update({
        "w_shared_gate": (Lm, cfg.dim, Fs),
        "w_shared_up": (Lm, cfg.dim, Fs),
        "w_shared_down": (Lm, Fs, cfg.dim),
      })
      if cfg.shared_expert_gate:
        moe_exp["w_shared_expert_gate"] = (Lm, cfg.dim, 1)
    checks["moe_layers"] = moe_exp
  for stack_name, expect in checks.items():
    stack = params.get(stack_name, {})
    for key, shape in expect.items():
      if key not in stack:
        raise ValueError(f"{stack_name}/{key}: missing")
      actual = tuple(stack[key].shape)
      if actual != shape:
        raise ValueError(f"{stack_name}/{key}: expected {shape}, got {actual}")
  if shard.is_first_layer and tuple(params["embed"].shape) != (cfg.vocab_size, cfg.dim):
    raise ValueError(f"embed: expected {(cfg.vocab_size, cfg.dim)}, got {params['embed'].shape}")
  if shard.is_last_layer and "lm_head" in params and tuple(params["lm_head"].shape) != (cfg.dim, cfg.vocab_size):
    raise ValueError(f"lm_head: expected {(cfg.dim, cfg.vocab_size)}, got {params['lm_head'].shape}")
