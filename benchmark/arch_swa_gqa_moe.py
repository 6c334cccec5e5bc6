"""Kind ``swa_gqa_moe``: a Laguna-XS.2-shaped decoder (``laguna``). Every layer is grouped-query softmax attention
over ``num_key_value_heads`` KV heads of ``head_dim``, but the layers are of two kinds (``layer_types``), each with its own
query-head count (``num_attention_heads_per_layer``) and its own rope (``rope_parameters``): a "full_attention" layer
sees every earlier position, rotates the leading ``partial_rotary_factor`` of each head by YaRN-scaled frequencies and
scales cos and sin by the YaRN attention factor; a "sliding_attention" layer sees its last ``sliding_window`` positions
(a query at t the keys in (t - window, t]) under plain rope over the whole head. Per-head RMSNorm on q and k before
rope; a head-wise gate on the attention output, softplus(W_g x), one scalar a head, ahead of the output projection.
Layer 0's FFN is a dense SwiGLU; the others route 8 of 256 experts by DeepSeek-V3's router without groups (sigmoid
scores, selection on score + bias, gates normalised over the chosen and scaled by 2.5) beside one shared expert.
Pre-norm residual blocks, an untied head. Weights and activations are bfloat16, the gate and the router float32. What
``arch.py`` asks of a kind, in its order, plus ``moe_expert_bytes`` for the experts' roofline, ``hf_layer_types`` for the
paged kernel's (every layer "attention"), ``hf_attention_kinds`` ("full" | "window" a layer) for the windowed call's
own roofline, and ``long_probes``: the wrong references a window can only show past 512 positions
(``scripts/chip_teacher_forced.py``). Each reading of a key the catalog row does not state is in the file's ``assumed``."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import experts_touched
from reference import F32, rms_norm, rope_half, swiglu
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` knows no
  ``laguna`` (every tree before PR 46) must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models import config

  if "laguna" not in getattr(config, "MODEL_FAMILIES", {}):
    raise SystemExit("arch_kind swa_gqa_moe: this program's config_from_hf knows no model_type 'laguna' (no per-layer attention kinds): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

# The seeded weights' one departure from N(0, 1/in) and unit gains (the file's ``assumed.weights``): the gains of the
# per-head q and k norms, so that softmax logits spread over ~4 and attention attends (at unit gains a head's scores are
# N(0, 1) and every softmax is nearly flat: no probe of a rope or of a window would move anything).
QK_NORM_GAIN = 2.0
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def hf_attention_kinds(hf: dict) -> tuple:
  """"full" | "window" a layer. ``weights.shape_hf`` keeps scalars only, so inside a maker the per-layer lists are gone:
  the file then names the pattern by ``full_attention_interval`` (layer i is a full-attention layer where
  i % interval == 0) — held to the lists wherever they are there."""
  every = int(hf["full_attention_interval"])
  out = tuple("full" if i % every == 0 else "window" for i in range(int(hf["num_hidden_layers"])))
  if "layer_types" in hf and tuple(KINDS[t] for t in hf["layer_types"]) != out:
    raise ValueError(f"full_attention_interval {every} does not spell layer_types {hf['layer_types']}")
  return out


def hf_layer_types(hf: dict) -> tuple:
  """Every layer reads K/V pages: all "attention", the word ``paged_attn_layers_roofline`` selects by."""
  return ("attention",) * int(hf["num_hidden_layers"])


def _heads(hf: dict) -> dict:
  """Query heads of each kind: ``num_attention_heads`` the full layers', ``sliding_attention_heads`` the window layers'
  (the file's scalar for ``num_attention_heads_per_layer``, held to the list where it is there)."""
  out = {"full": int(hf["num_attention_heads"]), "window": int(hf["sliding_attention_heads"])}
  if "num_attention_heads_per_layer" in hf and tuple(out[k] for k in hf_attention_kinds(hf)) != tuple(hf["num_attention_heads_per_layer"]):
    raise ValueError(f"num_attention_heads / sliding_attention_heads do not spell num_attention_heads_per_layer {hf['num_attention_heads_per_layer']}")
  return out


def _sizes(hf: dict) -> dict:
  n_dense = int(hf["dense_layers"])
  if "mlp_layer_types" in hf and tuple(hf["mlp_layer_types"]) != ("dense",) * n_dense + ("sparse",) * (int(hf["num_hidden_layers"]) - n_dense):
    raise ValueError(f"dense_layers {n_dense} does not spell mlp_layer_types {hf['mlp_layer_types']}")
  return dict(
    D=hf["hidden_size"], Hkv=hf["num_key_value_heads"], hd=hf["head_dim"], F=hf["intermediate_size"], Fm=hf["moe_intermediate_size"], Fs=hf["shared_expert_intermediate_size"],
    V=hf["vocab_size"], E=hf["num_experts"], k=hf["num_experts_per_tok"], W=int(hf["sliding_window"]), L=int(hf["num_hidden_layers"]), n_dense=n_dense, heads=_heads(hf),
  )


def layer_stacks(hf: dict) -> list[tuple[str, int]]:
  """(stack, index in it) of every layer in model order, under the program's names (``ModelConfig.layer_stack``): the
  model's first kind (full) keeps the plain names, the window layers' stacks carry their kind's."""
  n_dense, seen, out = int(hf["dense_layers"]), {}, []
  for i, kind in enumerate(hf_attention_kinds(hf)):
    name = ("" if kind == "full" else "window_") + ("layers" if i < n_dense else "moe_layers")
    out.append((name, seen.get(name, 0)))
    seen[name] = out[-1][1] + 1
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _attn_leaves(z: dict, keys, n: int, heads: int) -> dict:
  D, qd, kd = z["D"], heads * z["hd"], z["Hkv"] * z["hd"]
  return {
    "attn_norm": jnp.ones((n, D), ACT), "mlp_norm": jnp.ones((n, D), ACT),
    "q_norm": jnp.full((n, z["hd"]), QK_NORM_GAIN, ACT), "k_norm": jnp.full((n, z["hd"]), QK_NORM_GAIN, ACT),
    "wq": _stack(next(keys), n, (D, qd), D**-0.5), "wk": _stack(next(keys), n, (D, kd), D**-0.5), "wv": _stack(next(keys), n, (D, kd), D**-0.5),
    "w_og": _stack(next(keys), n, (D, heads), D**-0.5),
    "wo": _stack(next(keys), n, (qd, D), qd**-0.5),
  }


def _router(hf: dict, z: dict, key, n: int, topics):
  """[n, D, E] bfloat16: an N(0, 1/D) part plus, for each of ``router_topics`` topics, ``router_topic_gain`` / D times
  the topic's direction on the columns of the topic's own k experts of that layer, drawn uniformly from the E (no
  groups), so that the k-th choice stands clear of the (k+1)-th (the file's ``assumed.router_topics``). Beside it
  ``owns`` [n, T, E], 1 where the topic owns the expert (None without topics)."""
  D, E, k = z["D"], z["E"], z["k"]
  k_w, k_e = jax.random.split(key)
  w = normal(k_w, (n, D, E), D**-0.5)
  if topics is None:
    return w.astype(ACT), None
  own = jax.lax.top_k(jax.random.uniform(k_e, (n, topics.shape[0], E)), k)[1]  # [n, T, k] the topic's experts
  owns = jax.nn.one_hot(own, E, dtype=F32).sum(axis=2)  # [n, T, E], k ones a row
  return (w + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, owns)).astype(ACT), owns


def _ffn_leaves(hf: dict, z: dict, keys, n: int, experts: bool, topics) -> tuple[dict, object]:
  D = z["D"]
  if not experts:
    return {name: _stack(next(keys), n, shape, shape[0] ** -0.5) for name, shape in (("w_gate", (D, z["F"])), ("w_up", (D, z["F"])), ("w_down", (z["F"], D)))}, None
  w_router, owns = _router(hf, z, next(keys), n, topics)
  out = {"w_router": w_router, "router_bias": jnp.zeros((n, z["E"]), F32)}
  for name, shape in (("w_experts_gate", (z["E"], D, z["Fm"])), ("w_experts_up", (z["E"], D, z["Fm"])), ("w_experts_down", (z["E"], z["Fm"], D))):
    out[name] = _stack(next(keys), n, shape, shape[1] ** -0.5)
  for name, shape in (("w_shared_gate", (D, z["Fs"])), ("w_shared_up", (D, z["Fs"])), ("w_shared_down", (z["Fs"], D))):
    out[name] = _stack(next(keys), n, shape, shape[0] ** -0.5)
  return out, owns


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names (``models/decoder.py init_shard_params``): one stack an (attention kind,
  FFN) pairing, each in model order; every expert held; the router's selection bias float32 (zeros: a trained quantity)."""
  return _make(hf, key)[0]


def router_tables(hf: dict, key) -> dict | None:
  """What the topic router reads a token by, drawn as ``make_params`` draws it from the same key: ``topic_of`` [V], each
  token id's topic, and ``owns`` [expert layers in model order, T, E], 1 where the topic owns the expert. None where the
  file states no topics."""
  return _make(hf, key)[1]


def _make(hf: dict, key) -> tuple[dict, dict | None]:
  z = _sizes(hf)
  keys = iter(jax.random.split(key, 64))
  topics = topic_of = None
  if int(hf.get("router_topics") or 0):
    k_t, k_a = jax.random.split(next(keys))
    topics = jnp.where(jax.random.bernoulli(k_t, 0.5, (int(hf["router_topics"]), z["D"])), 1.0, -1.0).astype(F32)
    topic_of = jax.random.randint(k_a, (z["V"],), 0, topics.shape[0])
  stacks, kinds = layer_stacks(hf), hf_attention_kinds(hf)
  counts = {}
  for (name, _), kind in zip(stacks, kinds):
    counts[name] = (counts.get(name, (0, kind))[0] + 1, kind)
  params, owns = {}, {}
  for name, (n, kind) in counts.items():
    ffn, owns[name] = _ffn_leaves(hf, z, keys, n, name.endswith("moe_layers"), topics)
    params[name] = {**_attn_leaves(z, keys, n, z["heads"][kind]), **ffn}
  embed = normal(next(keys), (z["V"], z["D"]), 1.0)
  if topics is not None:
    embed = embed + float(hf["embed_topic_gain"]) * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((z["D"],), ACT)
  params["lm_head"] = normal(next(keys), (z["D"], z["V"]), z["D"] ** -0.5).astype(ACT)
  tables = None if topics is None else {"topic_of": topic_of, "owns": jnp.stack([owns[name][i] for name, i in stacks if owns[name] is not None])}
  return params, tables


# -------------------------------------------------------------- reference
# Written from the equations in ISSUE 46, float32: a full [S, S] masked softmax a layer, every expert computed densely
# and weighted by its gate (0 where it was not chosen), no cache, no kernels, nothing of the program — the rope tables
# and the YaRN ramp included, which are computed here from the published keys.


def rope_table(hf: dict, kind: str, rot: int | None = None, yarn: bool | None = None) -> tuple:
  """(inverse frequencies [rot/2], the factor on cos and sin, the rotated channels) of ``kind``'s layers, from
  ``rope_parameters`` — ``rot`` / ``yarn`` override the block's own (the probes). YaRN (arXiv:2309.00071, as
  transformers' ``_compute_yarn_parameters``): frequency i of rot/2 is interpolated (divided by ``factor``) where it
  turns fewer than ``beta_slow`` times over the original context, kept where it turns more than ``beta_fast`` times,
  and blended linearly between, the two bounds rounded outwards to whole channel indices."""
  rp = hf["rope_parameters"][{v: k for k, v in KINDS.items()}[kind]]
  rot = int(hf["head_dim"] * float(rp.get("partial_rotary_factor", 1.0))) if rot is None else rot
  theta = float(rp["rope_theta"])
  inv = [theta ** (-2.0 * i / rot) for i in range(rot // 2)]
  if rp.get("rope_type") != "yarn" or yarn is False:
    return tuple(inv), 1.0, rot
  orig, factor = float(rp["original_max_position_embeddings"]), float(rp["factor"])
  turns_at = lambda turns: rot * math.log(orig / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))  # noqa: E731  the channel index that turns ``turns`` times over the original context
  low, high = max(math.floor(turns_at(float(rp["beta_fast"]))), 0), min(math.ceil(turns_at(float(rp["beta_slow"]))), rot - 1)
  high = high + 0.001 if high == low else high
  keep = [1.0 - min(max((i - low) / (high - low), 0.0), 1.0) for i in range(rot // 2)]  # 1: extrapolated (kept), 0: interpolated
  return tuple(f * w + f / factor * (1.0 - w) for f, w in zip(inv, keep)), float(rp.get("attention_factor") or 0.1 * math.log(factor) + 1.0), rot


def causal_attention(q, k, v, scale: float, window: int = 0):
  """q [S, H, d], k / v [S, Hkv, d] → [S, H, d]: a full [S, S] softmax a head, each query head with its group's KV
  head; a query at t sees key s iff s <= t and, with a window, s > t - window."""
  S, H = q.shape[:2]
  k, v = (jnp.repeat(t, H // t.shape[1], axis=1) for t in (k, v))
  t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
  mask = (s <= t) & ((s > t - window) if window else True)
  probs = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("qhd,khd->hqk", q, k) * scale, -jnp.inf), axis=-1)
  return jnp.einsum("hqk,khd->qhd", probs, v)


def _mm(a, b, operands: str | None):
  """``a @ b``; under the precision probe both operands are rounded to ``operands`` (a float8 type) first."""
  if operands:
    a, b = (t.astype(jnp.dtype(operands)).astype(F32) for t in (a, b))
  return a @ b


@partial(jax.jit, static_argnames=("H", "Hkv", "hd", "eps", "window", "rope", "gate", "qk_norm", "operands"))
def _attention(h, attn_norm, q_norm, k_norm, wq, wk, wv, w_og, wo, *, H, Hkv, hd, eps, window, rope, gate="softplus", qk_norm=True, operands=None):
  """``rope``: (inverse frequencies, factor, rotated channels) — ``rope_table``'s. ``H`` under the leaves' own count
  takes their leading heads (the ``heads_48_everywhere`` probe)."""
  S = h.shape[0]
  x = rms_norm(h, attn_norm, eps)
  q, k, v = _mm(x, wq, operands).reshape(S, -1, hd)[:, :H], _mm(x, wk, operands).reshape(S, Hkv, hd), _mm(x, wv, operands).reshape(S, Hkv, hd)
  if qk_norm:
    q, k = rms_norm(q, q_norm, eps), rms_norm(k, k_norm, eps)
  inv, factor, rot = rope
  ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(inv, F32)[None, :]
  cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
  turn = lambda t: jnp.concatenate([rope_half(t[..., :rot], cos, sin), t[..., rot:]], axis=-1)  # noqa: E731  the leading ``rot`` channels rotate, the rest pass
  o = causal_attention(turn(q), turn(k), v, hd**-0.5, window)
  if gate:
    g = _mm(x, w_og, operands)[:, :H]
    o = o * (jax.nn.softplus(g) if gate == "softplus" else jax.nn.sigmoid(g))[:, :, None]
  return h + _mm(o.reshape(S, H * hd), wo[: H * hd], operands)


def _swiglu(x, w_gate, w_up, w_down, operands):
  return swiglu(x, w_gate, w_up, w_down) if not operands else _mm(jax.nn.silu(_mm(x, w_gate, operands)) * _mm(x, w_up, operands), w_down, operands)


@partial(jax.jit, static_argnames=("eps", "operands"))
def _dense_ffn(h, mlp_norm, w_gate, w_up, w_down, *, eps, operands=None):
  return h + _swiglu(rms_norm(h, mlp_norm, eps), w_gate, w_up, w_down, operands)


def router_gates(x, w_router, router_bias, *, top_k, scaling, softmax=False, norm_topk=True):
  """[S, E] gates: 0 where an expert was not chosen. Scores sigmoid(W_r x) in float32; the ``top_k`` of largest score +
  bias are chosen; a gate is the score (not the biased one), over the chosen ones' sum, x ``scaling``."""
  S, E = x.shape[0], w_router.shape[-1]
  logits = x @ w_router.astype(F32)
  score = jax.nn.softmax(logits, axis=-1) if softmax else jax.nn.sigmoid(logits)
  idx = jax.lax.top_k(score + router_bias.astype(F32), top_k)[1]
  gate = jnp.take_along_axis(score, idx, axis=-1)
  if norm_topk:
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
  return jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], idx].add(gate * scaling)


@partial(jax.jit, static_argnames=("top_k", "scaling", "eps", "softmax", "norm_topk", "drop_shared", "drop_expert", "operands"))
def _moe_ffn(h, mlp_norm, w_router, router_bias, eg, eu, ed, sg, su, sd, *, top_k, scaling, eps, softmax=False, norm_topk=True, drop_shared=False, drop_expert=False, operands=None):
  """Every token through every expert, one expert at a time, weighted by its gate, and the shared expert, ungated."""
  x = rms_norm(h, mlp_norm, eps)
  gates = router_gates(x, w_router, router_bias, top_k=top_k, scaling=scaling, softmax=softmax, norm_topk=norm_topk)
  if drop_expert:  # sensitivity probe only: lose each token's strongest expert
    gates = jnp.where(gates == jnp.max(gates, axis=-1, keepdims=True), 0.0, gates)

  def one_expert(acc, e):
    return acc + gates[:, e, None] * _swiglu(x, eg[e].astype(F32), eu[e].astype(F32), ed[e].astype(F32), operands), None

  routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), jnp.arange(eg.shape[0]))
  return h + routed + (0.0 if drop_shared else _swiglu(x, sg, su, sd, operands))


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, rope_swapped: bool = False, full_rotary: bool = False, yarn_off: bool = False, heads_48: bool = False,
                      gate: str | None = "softplus", qk_norm: bool = True, softmax: bool = False, norm_topk: bool = True, scaling: bool = True, drop_shared: bool = False,
                      drop_expert: bool = False, window_of: dict | None = None, operands: str | None = None, routed: list | None = None):
  """``window_of`` {"full": w, "window": w} overrides each kind's window (the long probes); ``routed``, no probe: a list
  that receives, for each expert layer in model order, [S, E] True where the router chose the expert."""
  z = _sizes(hf)
  eps = float(hf["rms_norm_eps"])
  windows = {"full": 0, "window": z["W"], **(window_of or {})}
  ropes = {kind: rope_table(hf, kind, rot=z["hd"] if full_rotary and kind == "full" else None, yarn=False if yarn_off else None) for kind in ("full", "window")}
  if rope_swapped:
    ropes = {"full": ropes["window"], "window": ropes["full"]}
  f32 = lambda st, i, *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731
  h = params["embed"][tokens].astype(F32)
  for g, ((name, i), kind) in enumerate(zip(layer_stacks(hf), hf_attention_kinds(hf))):
    if g == drop_layer:
      continue
    st = params[name]
    h = _attention(
      h, *f32(st, i, "attn_norm", "q_norm", "k_norm", "wq", "wk", "wv", "w_og", "wo"), H=min(z["heads"].values()) if heads_48 else z["heads"][kind], Hkv=z["Hkv"], hd=z["hd"], eps=eps,
      window=int(windows[kind]), rope=ropes[kind], gate=gate, qk_norm=qk_norm, operands=operands,
    )
    if "w_router" in st:
      route = dict(top_k=z["k"], scaling=float(hf["moe_routed_scaling_factor"]) if scaling else 1.0, softmax=softmax, norm_topk=norm_topk)
      if routed is not None:
        routed.append(router_gates(rms_norm(h, st["mlp_norm"][i].astype(F32), eps), st["w_router"][i], st["router_bias"][i], **route) > 0)
      h = _moe_ffn(
        h, st["mlp_norm"][i], st["w_router"][i], st["router_bias"][i], st["w_experts_gate"][i], st["w_experts_up"][i], st["w_experts_down"][i],
        *f32(st, i, "w_shared_gate", "w_shared_up", "w_shared_down"), **route, eps=eps, drop_shared=drop_shared, drop_expert=drop_expert, operands=operands,
      )
    else:
      h = _dense_ffn(h, *f32(st, i, "mlp_norm", "w_gate", "w_up", "w_down"), eps=eps, operands=operands)
  return _mm(rms_norm(h, params["final_norm"], eps), params["lm_head"].astype(F32), operands)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and K/V pages in bfloat16 over 5 layers and the gate and the router in
# float32; the reference is float32 on the same bfloat16 weights. Each limit lies between the largest sound reading of
# the chip's seeds — ``correctness.py``'s own check at 168 positions and the teacher-forced run at 846-1,327 — and the
# reading of the reference in the nearest precision below the stated one (float8 matrix operands), with room on both
# sides; every probe's readings are in PERF.md section 6 (PR 46).
LIMITS = {"mean_abs": 0.05, "max_abs": 0.17, "greedy_margin": 0.15}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0096-0.0210 over 28 runs at 168 positions and 0.0156 over 4 x 160 teacher-forced steps past the window (my chip runs, PR 46); float8 matmul operands read 0.117 (0.129 teacher-forced), a routed expert lost a token 0.24, a layer dropped 0.43, each wrong window 0.42-0.60 past the window: this is the limit that refuses them all",
  "max_abs": "the worst single entry: the chip read 0.026-0.065 at 168 positions and 0.100 over the 41 k entries of the teacher-forced run, which must stay inside; float8 operands read 0.30 (0.72 teacher-forced), every wrong architecture above 0.8",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0-0.052 over the seeds' 8 served tokens and 0.041 at most over 4 x 160 teacher-forced decode steps through the Pallas kernels; float8 operands read 0.29 (0.62 teacher-forced), a lost expert 0.44, the wrong windows 1.9-2.7: a decode step that read a wrong page or a wrong expert picks tokens well below the best",
}


def probes(hf: dict) -> dict:
  """Wrong references that 168 positions can show (``run.py --probe-sensitivity``). A window of 512 masks nothing
  there: its probes are ``long_probes``."""
  return {
    "drop_last_layer": {"drop_layer": int(hf["num_hidden_layers"]) - 1},
    "rope_swapped": {"rope_swapped": True},  # each kind given the other's table
    "full_rotary": {"full_rotary": True},  # the full layers' rope over the whole head, not its leading half
    "yarn_factor_off": {"yarn_off": True},  # plain frequencies and no factor on cos and sin in the full layers
    "heads_48_everywhere": {"heads_48": True},  # the window layers cut to the full layers' head count
    "no_gate": {"gate": None},
    "gate_sigmoid": {"gate": "sigmoid"},
    "no_qk_norm": {"qk_norm": False},
    "router_softmax": {"softmax": True},
    "no_norm_topk": {"norm_topk": False},
    "no_routed_scaling": {"scaling": False},
    "drop_shared": {"drop_shared": True},
    "drop_expert": {"drop_expert": True},
    # The precision below the one the configuration states (bfloat16 weights and activations): every matrix product's
    # operands rounded to float8 (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


def long_probes(hf: dict) -> dict:
  """Wrong references that only a context past the window shows (``scripts/chip_teacher_forced.py``, 760-1,360
  positions): no window anywhere, a window on every layer, a window of twice the size."""
  w = int(hf["sliding_window"])
  return {"window_off": {"window_of": {"window": 0}}, "window_on_full_layers": {"window_of": {"full": w}}, "window_1024": {"window_of": {"window": 2 * w}}}


# A full layer with the dense FFN, two window layers and a full layer with experts: two head counts with groups of 3 and
# 4 query heads a KV head, a window (8) shorter than the rehearsal's prompts, a rope over half a head, 16 experts top-4.
REHEARSE_WIDTHS = {
  "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "num_hidden_layers": 4, "full_attention_interval": 3,
  "num_attention_heads": 6, "sliding_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512, "num_experts": 16, "num_experts_per_tok": 4,
  "sliding_window": 8, "router_topics": 16, "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "full_attention"],
  "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"], "num_attention_heads_per_layer": [6, 8, 8, 6],
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _params(hf: dict) -> dict:
  """Parameters of each part (my count from the file's keys)."""
  z = _sizes(hf)
  D, hd, kd = z["D"], z["hd"], z["Hkv"] * z["hd"]
  mixer = lambda H: D + 2 * hd + D * H * hd + 2 * D * kd + D * H + H * hd * D  # noqa: E731  norm, q/k norms, wq, wk + wv, gate, wo
  return {
    "full": mixer(z["heads"]["full"]), "window": mixer(z["heads"]["window"]),
    "dense_ffn": D + 3 * D * z["F"],
    "expert": 3 * D * z["Fm"],
    "moe_rest": D + D * z["E"] + 3 * D * z["Fs"],  # norm, router, shared expert; the selection bias (float32) apart
    "top": 2 * z["V"] * D + D,
  }


def param_count(hf: dict) -> int:
  """Every parameter of the model the file describes (the selection bias included)."""
  z, p = _sizes(hf), _params(hf)
  n_moe = z["L"] - z["n_dense"]
  return sum(p[k] for k in hf_attention_kinds(hf)) + z["n_dense"] * p["dense_ffn"] + n_moe * (p["moe_rest"] + z["E"] + z["E"] * p["expert"]) + p["top"]


def weight_bytes(hf: dict, rows: float | None = None) -> float:
  """Every weight's bytes (``rows`` None), or those a decode step of ``rows`` rows touches: of the experts only the
  expected distinct ones."""
  z, p = _sizes(hf), _params(hf)
  n_moe = z["L"] - z["n_dense"]
  touched = z["E"] if rows is None else experts_touched(hf, *routed_experts(hf)[1:], rows)
  top = p["top"] if rows is None else p["top"] - z["V"] * z["D"]  # a step reads the head whole and of the embedding its rows' rows (``flops_bytes`` adds those)
  per_param = sum(p[k] for k in hf_attention_kinds(hf)) + z["n_dense"] * p["dense_ffn"] + n_moe * (p["moe_rest"] + touched * p["expert"]) + top
  return BF16 * per_param + 4 * n_moe * z["E"]


def routed_experts(hf: dict) -> tuple[int, int, int, int]:
  """(first, counted, routed, top_k): a step's bytes count every expert (all are held), of which a token chooses ``top_k``."""
  z = _sizes(hf)
  return 0, z["E"], z["E"], z["k"]


def moe_expert_bytes(hf: dict, rows: float) -> float:
  """What the expert layers of one decode step of ``rows`` rows must read of the routed experts' weights."""
  z = _sizes(hf)
  return (z["L"] - z["n_dense"]) * experts_touched(hf, *routed_experts(hf)[1:], rows) * _params(hf)["expert"] * BF16


def step_weight_bytes(hf: dict, rows: float) -> float:
  return weight_bytes(hf, rows)


def kv_bytes_per_token_layer(hf: dict, kv_quant: str) -> int:
  """Keys and values of one cached token in one layer: bfloat16, or int8 codes + one f32 scale per head and side."""
  per_head_side = hf["head_dim"] + 4 if kv_quant == "int8" else 2 * hf["head_dim"]
  return hf["num_key_value_heads"] * 2 * per_head_side


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a full layer reads every resident token's K/V, a window layer at most its
  window's of every row (exact where every row holds at least a window, as every row of ``agent-closed-64`` does from
  its first decoded token on)."""
  per_token = kv_bytes_per_token_layer(hf, kv_quant)
  return [(resident_tokens if kind == "full" else min(resident_tokens, rows * int(hf["sliding_window"]))) * per_token for kind in hf_attention_kinds(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  """Everything outside the routed experts once a row, plus each row's k chosen experts in every expert layer. 2
  operations a parameter a row."""
  z, p = _sizes(hf), _params(hf)
  n_moe = z["L"] - z["n_dense"]
  outside = sum(p[k] for k in hf_attention_kinds(hf)) + z["n_dense"] * p["dense_ffn"] + n_moe * p["moe_rest"] + p["top"] / 2  # the head; the embedding is a gather
  return 2.0 * rows * (outside + n_moe * z["k"] * p["expert"])


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"  # absent from the file: bfloat16 pages
