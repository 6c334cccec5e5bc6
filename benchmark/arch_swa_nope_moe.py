"""Kind ``swa_nope_moe``: a SmallThinker-21BA3B-shaped decoder (``smallthinker``). Every layer is grouped-query softmax
attention of ``num_attention_heads`` query heads over ``num_key_value_heads`` KV heads of ``head_dim`` and a routed
expert FFN, and the layers are of two kinds: a global layer (``rope_layout`` 0, ``sliding_window_layout`` 0) sees every
earlier position and has NO position term — q and k go to the softmax as projected; a window layer (1, 1) sees its last
``sliding_window_size`` positions (a query at t the keys in (t - window, t]) under plain rope at ``rope_theta`` over the
whole head. **The router reads the ATTENTION's normed input** and draws its choice before the attention runs: the
``moe_num_active_primary_experts`` largest of ``x W_r``, weighted by a softmax over the chosen; the experts then read the
stream after the attention's residual, normed, and are ReLU-gated: W_down(relu(W_gate y) * (W_up y)). No shared expert,
no dense FFN anywhere, no q/k norm, no bias, pre-norm residual blocks, an untied head. Weights and activations are
bfloat16, the router's logits float32. What ``arch.py`` asks of a kind, in its order, plus ``moe_expert_bytes`` for the
experts' roofline, ``hf_layer_types`` for the paged kernel's (every layer "attention"), ``hf_attention_kinds`` ("full" |
"window" a layer) for the windowed call's own roofline, ``long_probes``: the wrong references a window can only show
past ``sliding_window_size`` positions (``scripts/chip_teacher_forced.py``, at ``long_prompt_tokens``), and
``exact_probes``: those that only float32 arithmetic tells. Each reading of a key the catalog row does
not state is in the file's ``assumed``."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from flops_bytes import experts_touched
from reference import F32, rms_norm, rope_half
from weights import ACT, normal


def _refuse_a_program_without_the_kind() -> None:
  """Asked once, as the kind is loaded and before a weight is made: a program whose ``config_from_hf`` knows no
  ``smallthinker`` (every tree before PR 50) must end the cell here, at once and non-zero."""
  from xotorch_support_jetson_tpu.models import config

  if "smallthinker" not in getattr(config, "MODEL_FAMILIES", {}):
    raise SystemExit("arch_kind swa_nope_moe: this program's config_from_hf knows no model_type 'smallthinker' (no router ahead of the attention, no ReLU-gated experts, no layer kind without rope): it cannot serve the configuration")


_refuse_a_program_without_the_kind()

# The seeded weights' one departure from N(0, 1/in) and unit gains (the file's ``assumed.weights``): wq and wk are drawn
# QK_GAIN times wider, so that a head's softmax logits spread over ~4 and attention attends (the model has no q/k norm to
# carry a gain; at N(0, 1/in) a head's scores are N(0, 1), every softmax is nearly flat and no probe of a rope or of a
# window would move anything).
QK_GAIN = 2.0


def hf_attention_kinds(hf: dict) -> tuple:
  """"full" (global, no position term) | "window" (roped) a layer. ``weights.shape_hf`` keeps scalars only, so inside a
  maker the two layout lists are gone: the file then names the pattern by ``global_attention_interval`` (layer i is a
  global layer where i % interval == 0) — held to both lists wherever they are there."""
  every = int(hf["global_attention_interval"])
  out = tuple("full" if i % every == 0 else "window" for i in range(int(hf["num_hidden_layers"])))
  for key in ("rope_layout", "sliding_window_layout"):
    if key in hf and tuple("window" if v else "full" for v in hf[key]) != out:
      raise ValueError(f"global_attention_interval {every} does not spell {key} {hf[key]}")
  return out


def hf_layer_types(hf: dict) -> tuple:
  """Every layer reads K/V pages: all "attention", the word ``paged_attn_layers_roofline`` selects by."""
  return ("attention",) * int(hf["num_hidden_layers"])


def _sizes(hf: dict) -> dict:
  return dict(
    D=hf["hidden_size"], H=hf["num_attention_heads"], Hkv=hf["num_key_value_heads"], hd=hf["head_dim"], Fm=hf["moe_ffn_hidden_size"], V=hf["vocab_size"],
    E=hf["moe_num_primary_experts"], k=hf["moe_num_active_primary_experts"], W=int(hf["sliding_window_size"]), L=int(hf["num_hidden_layers"]),
  )


def layer_stacks(hf: dict) -> list[tuple[str, int]]:
  """(stack, index in it) of every layer in model order, under the program's names (``ModelConfig.layer_stack``): the
  model's first kind (global) keeps the plain name, the window layers' stack carries its kind's. Every layer has experts."""
  seen, out = {}, []
  for kind in hf_attention_kinds(hf):
    name = ("" if kind == "full" else "window_") + "moe_layers"
    out.append((name, seen.get(name, 0)))
    seen[name] = out[-1][1] + 1
  return out


# ---------------------------------------------------------------- weights


def _stack(key, n: int, shape: tuple, std: float):
  """[n, *shape] in the served type, one layer's float32 slab in flight at a time."""
  return jax.lax.map(lambda k: normal(k, shape, std).astype(ACT), jax.random.split(key, n))


def _router(hf: dict, z: dict, key, n: int, topics):
  """[n, D, E] bfloat16: an N(0, 1/D) part plus, for each of ``router_topics`` topics, ``router_topic_gain`` / D times
  the topic's direction on the columns of the topic's own k experts of that layer, drawn uniformly from the E, so that
  the k-th choice stands clear of the (k+1)-th (the file's ``assumed.router_topics``). Beside it ``owns`` [n, T, E], 1
  where the topic owns the expert (None without topics)."""
  D, E, k = z["D"], z["E"], z["k"]
  k_w, k_e = jax.random.split(key)
  w = normal(k_w, (n, D, E), D**-0.5)
  if topics is None:
    return w.astype(ACT), None
  own = jax.lax.top_k(jax.random.uniform(k_e, (n, topics.shape[0], E)), k)[1]  # [n, T, k] the topic's experts
  owns = jax.nn.one_hot(own, E, dtype=F32).sum(axis=2)  # [n, T, E], k ones a row
  return (w + (float(hf["router_topic_gain"]) / D) * jnp.einsum("td,lte->lde", topics, owns)).astype(ACT), owns


def _layer_leaves(hf: dict, z: dict, keys, n: int, topics) -> tuple[dict, object]:
  D, qd, kd = z["D"], z["H"] * z["hd"], z["Hkv"] * z["hd"]
  w_router, owns = _router(hf, z, next(keys), n, topics)
  out = {
    "attn_norm": jnp.ones((n, D), ACT), "mlp_norm": jnp.ones((n, D), ACT),
    "wq": _stack(next(keys), n, (D, qd), QK_GAIN * D**-0.5), "wk": _stack(next(keys), n, (D, kd), QK_GAIN * D**-0.5), "wv": _stack(next(keys), n, (D, kd), D**-0.5),
    "wo": _stack(next(keys), n, (qd, D), qd**-0.5),
    "w_router": w_router,
  }
  for name, shape in (("w_experts_gate", (z["E"], D, z["Fm"])), ("w_experts_up", (z["E"], D, z["Fm"])), ("w_experts_down", (z["E"], z["Fm"], D))):
    out[name] = _stack(next(keys), n, shape, shape[1] ** -0.5)
  return out, owns


def make_params(hf: dict, key) -> dict:
  """bfloat16 leaves under the program's names (``models/decoder.py init_shard_params``): one stack a kind of layer,
  each in model order; every expert held; no dense FFN leaf, no shared expert, no selection bias (a softmax router)."""
  return _make(hf, key)[0]


def router_tables(hf: dict, key) -> dict | None:
  """What the topic router reads a token by, drawn as ``make_params`` draws it from the same key: ``topic_of`` [V], each
  token id's topic, and ``owns`` [layers in model order, T, E], 1 where the topic owns the expert. None where the file
  states no topics."""
  return _make(hf, key)[1]


def _make(hf: dict, key) -> tuple[dict, dict | None]:
  z = _sizes(hf)
  keys = iter(jax.random.split(key, 32))
  topics = topic_of = None
  if int(hf.get("router_topics") or 0):
    k_t, k_a = jax.random.split(next(keys))
    topics = jnp.where(jax.random.bernoulli(k_t, 0.5, (int(hf["router_topics"]), z["D"])), 1.0, -1.0).astype(F32)
    topic_of = jax.random.randint(k_a, (z["V"],), 0, topics.shape[0])
  stacks = layer_stacks(hf)
  params, owns = {}, {}
  for name in dict.fromkeys(name for name, _ in stacks):
    params[name], owns[name] = _layer_leaves(hf, z, keys, sum(1 for s, _ in stacks if s == name), topics)
  embed = normal(next(keys), (z["V"], z["D"]), 1.0)
  if topics is not None:
    embed = embed + float(hf["embed_topic_gain"]) * topics[topic_of]
  params["embed"] = embed.astype(ACT)
  params["final_norm"] = jnp.ones((z["D"],), ACT)
  params["lm_head"] = normal(next(keys), (z["D"], z["V"]), z["D"] ** -0.5).astype(ACT)
  tables = None if topics is None else {"topic_of": topic_of, "owns": jnp.stack([owns[name][i] for name, i in stacks])}
  return params, tables


# -------------------------------------------------------------- reference
# Written from the equations in ISSUE 50, float32: a full [S, S] masked softmax a layer, every expert computed densely
# and weighted by its gate (0 where it was not chosen), no cache, no kernels, nothing of the program.


def causal_attention(q, k, v, scale: float, window: int = 0):
  """q [S, H, d], k / v [S, Hkv, d] → [S, H, d]: a full [S, S] softmax a head, each query head with its group's KV
  head; a query at t sees key s iff s <= t and, with a window, s > t - window. One KV head's group of query heads at a
  time, so that a context past the published window (28 x 4.7 k x 4.7 k scores) fits beside the model on the chip."""
  S, H, Hkv = q.shape[0], q.shape[1], k.shape[1]
  t, s = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
  mask = (s <= t) & ((s > t - window) if window else True)

  def one_group(qkv):
    qg, kg, vg = qkv  # [S, H / Hkv, d], [S, d], [S, d]
    probs = jax.nn.softmax(jnp.where(mask[None], jnp.einsum("qhd,kd->hqk", qg, kg) * scale, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,kd->qhd", probs, vg)

  out = jax.lax.map(one_group, (q.reshape(S, Hkv, H // Hkv, -1).transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [Hkv, S, H / Hkv, d]
  return out.transpose(1, 0, 2, 3).reshape(S, H, -1)


def _mm(a, b, operands: str | None):
  """``a @ b``; under the precision probe both operands are rounded to ``operands`` (a float8 type) first."""
  if operands:
    a, b = (t.astype(jnp.dtype(operands)).astype(F32) for t in (a, b))
  return a @ b


def _bf16(x):
  """``x`` rounded to bfloat16's 8 bits of significand, still float32."""
  return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def router_gates(x, w_router, *, top_k: int, renormalise: bool = True, rounded: bool = False):
  """[S, E] gates, 0 where an expert was not chosen: logits ``x W_r`` in float32, the ``top_k`` largest chosen, a gate
  the softmax over the CHOSEN logits (the same as a softmax over all E renormalised over the chosen); ``renormalise``
  False leaves the softmax over all E as it is at the chosen. ``rounded``: operands and logits rounded to bfloat16."""
  S, E = x.shape[0], w_router.shape[-1]
  w = w_router.astype(F32)
  logits = _bf16(_bf16(x) @ _bf16(w)) if rounded else x @ w
  top, idx = jax.lax.top_k(logits, top_k)
  gate = jax.nn.softmax(top, axis=-1) if renormalise else jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
  return jnp.zeros((S, E), F32).at[jnp.arange(S)[:, None], idx].add(gate)


@partial(jax.jit, static_argnames=("H", "Hkv", "hd", "window", "theta", "operands"))
def _attention(x, wq, wk, wv, wo, *, H, Hkv, hd, window, theta, operands=None):
  """The attention's increment from its normed input ``x`` [S, D]. ``theta`` None: no position term."""
  S = x.shape[0]
  q, k, v = _mm(x, wq, operands).reshape(S, H, hd), _mm(x, wk, operands).reshape(S, Hkv, hd), _mm(x, wv, operands).reshape(S, Hkv, hd)
  if theta is not None:
    ang = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray([theta ** (-2.0 * i / hd) for i in range(hd // 2)], F32)[None, :]
    q, k = rope_half(q, jnp.cos(ang), jnp.sin(ang)), rope_half(k, jnp.cos(ang), jnp.sin(ang))
  return _mm(causal_attention(q, k, v, hd**-0.5, window).reshape(S, H * hd), wo, operands)


@partial(jax.jit, static_argnames=("act", "operands"))
def _experts(y, gates, eg, eu, ed, *, act="relu", operands=None):
  """Every token through every expert, one expert at a time, weighted by its gate: Σ_e g_e W_down_e(act(W_gate_e y) * W_up_e y)."""
  nonlinear = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]

  def one_expert(acc, e):
    return acc + gates[:, e, None] * _mm(nonlinear(_mm(y, eg[e].astype(F32), operands)) * _mm(y, eu[e].astype(F32), operands), ed[e].astype(F32), operands), None

  return jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(eg.shape[0]))[0]


def reference_forward(params: dict, hf: dict, tokens, drop_layer: int | None = None, router_reads: str = "attn_normed", act: str = "relu", rope_of: dict | None = None,
                      window_of: dict | None = None, renormalise: bool = True, top_k: int | None = None, router_rounded: bool = False, operands: str | None = None,
                      routed: list | None = None, increments: list | None = None):
  """``router_reads``: "attn_normed" (the published place: rms(h; w_in), what the attention reads) | "attn_raw" (the raw
  stream h) | "ffn_normed" (rms(h'; w_post), the usual place, after the attention). ``rope_of`` / ``window_of``
  {"full": ..., "window": ...} override each kind's rope (True | False) and window (the probes). No probes: ``routed``,
  a list that receives, for each layer in model order, [S, E] True where the router chose the expert; ``increments``, a
  list that receives (rms of the stream, of the attention's increment, of the experts') a layer."""
  z = _sizes(hf)
  eps, theta = float(hf["rms_norm_eps"]), float(hf["rope_theta"])
  if hf.get("rope_scaling"):
    raise ValueError("the reference implements plain rope alone (rope_scaling null, as published)")
  roped = {"full": False, "window": True, **(rope_of or {})}
  windows = {"full": 0, "window": z["W"], **(window_of or {})}
  k = z["k"] if top_k is None else top_k
  rms = lambda t: float(jnp.sqrt(jnp.mean(t * t)))  # noqa: E731
  h = params["embed"][tokens].astype(F32)
  for g, ((name, i), kind) in enumerate(zip(layer_stacks(hf), hf_attention_kinds(hf))):
    if g == drop_layer:
      continue
    st = params[name]
    f32 = lambda *names: tuple(st[n][i].astype(F32) for n in names)  # noqa: E731, B023
    x = rms_norm(h, st["attn_norm"][i], eps)
    attn = _attention(x, *f32("wq", "wk", "wv", "wo"), H=z["H"], Hkv=z["Hkv"], hd=z["hd"], window=int(windows[kind]), theta=theta if roped[kind] else None, operands=operands)
    after = h + attn
    y = rms_norm(after, st["mlp_norm"][i], eps)
    gates = router_gates({"attn_normed": x, "attn_raw": h, "ffn_normed": y}[router_reads], st["w_router"][i], top_k=k, renormalise=renormalise, rounded=router_rounded)
    if routed is not None:
      routed.append(gates > 0)
    out = _experts(y, gates, st["w_experts_gate"][i], st["w_experts_up"][i], st["w_experts_down"][i], act=act, operands=operands)
    if increments is not None:
      increments.append((rms(h), rms(attn), rms(out)))
    h = after + out
  return _head(rms_norm(h, params["final_norm"], eps), params["lm_head"], operands)


def _head(x, w, operands, slabs: int = 8):
  """``x @ w`` [S, V], a slab of the vocabulary at a time: the whole head in float32 is 1.56 GB (and as much again in
  the products' operand splits), which the chip does not have beside the served model and its pool."""
  step = -(-w.shape[1] // slabs)
  return jnp.concatenate([_mm(x, w[:, at : at + step].astype(F32), operands) for at in range(0, w.shape[1], step)], axis=1)


# ------------------------------------------------- the limits of `correct`

# The served path keeps activations, weights and K/V pages in bfloat16 over 8 layers and the router's logits in float32;
# the reference is float32 on the same bfloat16 weights. Each limit lies between the largest sound reading of the chip's
# seeds — ``correctness.py``'s own check at 168 positions and the teacher-forced run at 4.3-4.6 k — and the reading of the
# reference in the nearest precision below the stated one (float8 matrix operands), 2.0-2.1 x the first and under half
# the second; every probe's readings are in PERF.md section 6 (PR 50).
LIMITS = {"mean_abs": 0.05, "max_abs": 0.17, "greedy_margin": 0.15}
LIMITS_WHY = {
  "mean_abs": "mean |served - reference| log-prob over the 48 compared entries: the chip read 0.0156-0.0235 over its 22 seeds' checks at 168 positions and 0.0165 over 2 x 160 teacher-forced steps past position 4096 (my chip runs, PR 50; PERF.md section 6 counts the seeds); float8 matmul operands read 0.180 (0.135 teacher-forced), the weakest of a token's six experts lost 0.065 (0.060), the router after the attention 0.47 (0.24), silu experts 0.42 (0.32), each wrong window 0.09-0.18 past the window: this is the limit that refuses them all",
  "max_abs": "the worst single entry: the chip read 0.045-0.084 at 168 positions and 0.086 over the 40 k entries of the teacher-forced run, which must stay inside; float8 operands read 0.46 (0.74 teacher-forced), a lost expert 0.21 (0.36), every wrong architecture above 0.65",
  "greedy_margin": "the reference's best log-prob minus its log-prob of the served token: 0 on seventeen seeds of 22 and 0.029 at most over their 8 served tokens and 0.0755 at most over 2 x 160 teacher-forced decode steps through the Pallas kernels; float8 operands read 0.31 (0.59 teacher-forced), a lost expert 0.18 (0.26), a routing drawn after the attention 1.17: a decode step that read a wrong page, a wrong expert or a routing drawn from the wrong tensor picks tokens well below the best",
}


def probes(hf: dict) -> dict:
  """Wrong references that 168 positions can show and the limits refuse (``run.py --probe-sensitivity``). A window of
  4096 masks nothing there: its probes are ``long_probes``; what only float32 arithmetic can tell is ``exact_probes``."""
  return {
    "drop_last_layer": {"drop_layer": int(hf["num_hidden_layers"]) - 1},
    "router_after_attention": {"router_reads": "ffn_normed"},  # the usual place: the router reads the experts' own input
    "router_reads_raw_stream": {"router_reads": "attn_raw"},  # the stream itself, not its norm (the file's ``assumed.router``)
    "silu_experts": {"act": "silu"},
    "rope_on_global_layers": {"rope_of": {"full": True}},
    "no_rope_on_window_layers": {"rope_of": {"window": False}},
    "experts_top5": {"top_k": int(hf["moe_num_active_primary_experts"]) - 1},  # the weakest of the chosen lost: a tenth of the experts' weight
    # The precision below the one the configuration states (bfloat16 weights and activations): every matrix product's
    # operands rounded to float8 (e4m3, 3 bits of mantissa where bfloat16 keeps 7). A served path that computed so must not pass.
    "float8_matmul_operands": {"operands": "float8_e4m3fn"},
  }


def long_probes(hf: dict) -> dict:
  """Wrong references that only a context past the window shows (``scripts/chip_teacher_forced.py``, positions past
  ``sliding_window_size``): no window anywhere, a window on every layer."""
  return {"window_layers_full": {"window_of": {"window": 0}}, "window_on_global_layers": {"window_of": {"full": int(hf["sliding_window_size"])}}}


def exact_probes(hf: dict) -> dict:
  """Wrong references that float32 arithmetic tells (the CPU tests, at a few hundred of their tolerances) and bfloat16
  serving cannot, so that no limit of ``correct`` is asked to refuse them (their readings on the chip: PERF.md section
  6, PR 50): the softmax left as it is over all the experts — under a router that sets its chosen well clear of the rest,
  the rest's mass is all that renormalising returns; the router's operands and logits rounded to bfloat16, where the
  configuration states float32 — the served path rounds everything else so; a window of one key fewer — one key of a
  row's 4096."""
  w = int(hf["sliding_window_size"])
  return {"softmax_not_renormalised": {"renormalise": False}, "router_bfloat16": {"router_rounded": True}, f"window_{w - 1}": {"window_of": {"window": w - 1}}}


def long_prompt_tokens(hf: dict) -> tuple[int, int]:
  """The prompt lengths of the teacher-forced run (``scripts/chip_teacher_forced.py``): past the window from the first
  decoded token on, so that the window's edge, the global layers at depth and the carried routing are all compared."""
  w = int(hf["sliding_window_size"])
  return w + w // 64, w + w // 8


# Two global layers without a position term around two roped window layers: 6 query heads over 2 KV heads (groups of 3,
# odd like the published 7), a window (8) shorter than the rehearsal's prompts, 16 experts top-4, every layer routed.
REHEARSE_WIDTHS = {
  "hidden_size": 64, "moe_ffn_hidden_size": 32, "num_hidden_layers": 4, "global_attention_interval": 3, "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
  "vocab_size": 512, "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 4, "sliding_window_size": 8, "router_topics": 16,
  "rope_layout": [0, 1, 1, 0], "sliding_window_layout": [0, 1, 1, 0],
}

# ------------------------------------------------- bytes and operations

BF16 = 2


def _params(hf: dict) -> dict:
  """Parameters of each part (my count from the file's keys)."""
  z = _sizes(hf)
  D, qd, kd = z["D"], z["H"] * z["hd"], z["Hkv"] * z["hd"]
  return {
    "attention": D + 2 * D * qd + 2 * D * kd,  # norm, wq + wo, wk + wv: both kinds alike
    "expert": 3 * D * z["Fm"],
    "moe_rest": D + D * z["E"],  # norm, router
    "top": 2 * z["V"] * D + D,
  }


def param_count(hf: dict) -> int:
  """Every parameter of the model the file describes."""
  z, p = _sizes(hf), _params(hf)
  return z["L"] * (p["attention"] + p["moe_rest"] + z["E"] * p["expert"]) + p["top"]


def weight_bytes(hf: dict, rows: float | None = None) -> float:
  """Every weight's bytes (``rows`` None), or those a decode step of ``rows`` rows touches: of the experts only the
  expected distinct ones."""
  z, p = _sizes(hf), _params(hf)
  touched = z["E"] if rows is None else experts_touched(hf, *routed_experts(hf)[1:], rows)
  top = p["top"] if rows is None else p["top"] - z["V"] * z["D"]  # a step reads the head whole and of the embedding its rows' rows (``flops_bytes`` adds those)
  return BF16 * (z["L"] * (p["attention"] + p["moe_rest"] + touched * p["expert"]) + top)


def routed_experts(hf: dict) -> tuple[int, int, int, int]:
  """(first, counted, routed, top_k): a step's bytes count every expert (all are held), of which a token chooses ``top_k``."""
  z = _sizes(hf)
  return 0, z["E"], z["E"], z["k"]


def moe_expert_bytes(hf: dict, rows: float) -> float:
  """What the expert layers (every layer) of one decode step of ``rows`` rows must read of the routed experts' weights."""
  z = _sizes(hf)
  return z["L"] * experts_touched(hf, *routed_experts(hf)[1:], rows) * _params(hf)["expert"] * BF16


def step_weight_bytes(hf: dict, rows: float) -> float:
  return weight_bytes(hf, rows)


def kv_bytes_per_token_layer(hf: dict, kv_quant: str) -> int:
  """Keys and values of one cached token in one layer: bfloat16, or int8 codes + one f32 scale per head and side."""
  per_head_side = hf["head_dim"] + 4 if kv_quant == "int8" else 2 * hf["head_dim"]
  return hf["num_key_value_heads"] * 2 * per_head_side


def cache_read_bytes(hf: dict, rows: float, resident_tokens: float, kv_quant: str) -> list[float]:
  """One entry a layer, in model order: a global layer reads every resident token's K/V, a window layer at most its
  window's of every row (exact where every row holds at least a window, as every row of ``longdoc-closed-32`` does from
  its first decoded token on)."""
  per_token = kv_bytes_per_token_layer(hf, kv_quant)
  return [(resident_tokens if kind == "full" else min(resident_tokens, rows * int(hf["sliding_window_size"]))) * per_token for kind in hf_attention_kinds(hf)]


def step_matmul_flops(hf: dict, rows: float) -> float:
  """Everything outside the routed experts once a row, plus each row's k chosen experts in every layer. 2 operations a
  parameter a row."""
  z, p = _sizes(hf), _params(hf)
  outside = z["L"] * (p["attention"] + p["moe_rest"]) + p["top"] / 2  # the head; the embedding is a gather
  return 2.0 * rows * (outside + z["L"] * z["k"] * p["expert"])


CACHE_TYPE_ENV = "XOT_TPU_KV_QUANT"  # absent from the file: bfloat16 pages
