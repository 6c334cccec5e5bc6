"""OpenAI-compatible HTTP API.

Endpoint parity with reference ``api/chatgpt_api.py`` (routes :208-234,
streaming/blocking completions :317-443, token queues :194-198,585, prompt
build w/ chat template + tools :131-150, finish_reason logic :383,430-436,
``gpt-*`` aliasing :322, timeout middleware :246-253, CORS, static web chat).
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import math
import os
import time
import uuid
from pathlib import Path

from aiohttp import web

from .. import registry
from ..inference.engine import RequestStalledError
from ..inference.qos import PRIORITY_CLASSES
from ..inference.shard import Shard
from ..inference.tokenizers import resolve_tokenizer
from ..utils.helpers import DEBUG, AsyncCallbackSystem, PrefixDict, device_memory
from ..utils.metrics import metrics


class Message:
  def __init__(self, role: str, content, tools=None):
    self.role = role
    self.content = content
    self.tools = tools

  def to_dict(self) -> dict:
    data = {"role": self.role, "content": self.content}
    if self.tools:
      data["tools"] = self.tools
    return data


class ChatCompletionRequest:
  def __init__(self, model: str, messages: list[Message], temperature: float | None = None, tools=None, max_tokens=None, stream=False, stop=(), logprobs=False, top_logprobs=0):
    self.model = model
    self.messages = messages
    self.temperature = temperature
    self.tools = tools
    self.max_tokens = max_tokens
    self.stream = stream
    self.stop = tuple(stop)
    self.logprobs = bool(logprobs)
    self.top_logprobs = int(top_logprobs)


def find_stop(text: str, stops: tuple) -> tuple[int | None, int]:
  """Stop-string scan over accumulated response text.

  Returns (cut, safe_len): ``cut`` is the index of the earliest stop-string
  occurrence (None if absent); ``safe_len`` is how much of ``text`` can be
  emitted now without risking that a later chunk completes a stop string
  across the boundary (the longest text suffix that is a proper prefix of
  any stop string is held back).
  """
  cut = None
  for s in stops:
    i = text.find(s)
    if i != -1:
      cut = i if cut is None else min(cut, i)
  if cut is not None:
    return cut, cut
  hold = 0
  for s in stops:
    for l in range(min(len(s) - 1, len(text)), 0, -1):
      if text.endswith(s[:l]):
        hold = max(hold, l)
        break
  return None, len(text) - hold


def remap_messages(messages: list[Message], vision: bool = False) -> tuple[list[Message], list[str]]:
  """Flatten multimodal content blocks. With ``vision`` (the serving model
  has a tower, models/vision.py) each data-URL image becomes an ``<image>``
  placeholder (the llava processor expands it to patch tokens) and its
  base64 payload is collected for the engine; for text-only models images
  are dropped cleanly, leaving no placeholder in the prompt. Role of
  reference ``chatgpt_api.py:97-128`` — but backed by a real vision path."""
  remapped = []
  images: list[str] = []
  for message in messages:
    if isinstance(message.content, list):
      parts = []
      for part in message.content:
        if not isinstance(part, dict):
          continue
        if part.get("type") == "text":
          parts.append(part.get("text", ""))
        elif part.get("type") == "image_url" and vision:
          url = (part.get("image_url") or {}).get("url", "")
          if url.startswith("data:") and "," in url:
            images.append(url.split(",", 1)[1])
            parts.append("<image>")
      remapped.append(Message(message.role, " ".join(parts), message.tools))
    else:
      remapped.append(message)
  return remapped, images


def build_prompt(tokenizer, _messages: list[Message], tools=None, vision: bool = False) -> tuple[str, list[str]]:
  messages, images = remap_messages(_messages, vision=vision)
  chat_template_args = {
    "conversation": [m.to_dict() for m in messages],
    "tokenize": False,
    "add_generation_prompt": True,
  }
  if tools:
    chat_template_args["tools"] = tools
  try:
    return tokenizer.apply_chat_template(**chat_template_args), images
  except TypeError:
    # Tokenizers without `conversation=` kwarg naming.
    args = dict(chat_template_args)
    conv = args.pop("conversation")
    return tokenizer.apply_chat_template(conv, **args), images


def parse_message(data: dict) -> Message:
  if "role" not in data or "content" not in data:
    raise ValueError(f"Invalid message: {data}. Must have 'role' and 'content'")
  return Message(data["role"], data["content"], data.get("tools"))


def parse_chat_request(data: dict, default_model: str) -> ChatCompletionRequest:
  if not data.get("messages"):
    raise ValueError("'messages' must be a non-empty list")
  max_tokens = data.get("max_tokens")
  if max_tokens is not None and (not isinstance(max_tokens, int) or isinstance(max_tokens, bool) or max_tokens < 1):
    raise ValueError("'max_tokens' must be a positive integer")
  temperature = data.get("temperature")
  if temperature is not None and (not isinstance(temperature, (int, float)) or isinstance(temperature, bool) or not 0 <= temperature <= 2):
    raise ValueError("'temperature' must be a number in [0, 2]")
  stop = data.get("stop")
  if stop is None:
    stop = ()
  elif isinstance(stop, str):
    stop = (stop,)
  elif isinstance(stop, list) and all(isinstance(s, str) and s for s in stop) and len(stop) <= 4:
    stop = tuple(stop)
  else:
    raise ValueError("'stop' must be a non-empty string or a list of up to 4 non-empty strings")
  model = data.get("model", default_model)
  if model and model.startswith("gpt-"):  # alias ChatGPT client defaults
    model = default_model
  if model not in registry.model_cards:
    if DEBUG >= 1:
      print(f"[api] unknown model {model}; defaulting to {default_model}")
    model = default_model
  logprobs = data.get("logprobs", False)
  if not isinstance(logprobs, bool):
    raise ValueError("'logprobs' must be a boolean")
  top_logprobs = data.get("top_logprobs", 0) or 0
  if not isinstance(top_logprobs, int) or isinstance(top_logprobs, bool) or not 0 <= top_logprobs <= 20:
    raise ValueError("'top_logprobs' must be an integer in [0, 20]")
  if top_logprobs and not logprobs:
    raise ValueError("'top_logprobs' requires 'logprobs': true")
  if logprobs and data.get("stream"):
    # Logprobs are recomputed post-hoc in one parallel forward (the fused
    # decode loops return token ids only); a stream has no final message to
    # attach them to.
    raise ValueError("'logprobs' is not supported with 'stream': true")
  return ChatCompletionRequest(
    model,
    [parse_message(m) for m in data["messages"]],
    # None = "not specified" → the node's configured default applies; an
    # unconditional 0.6 here would override the daemon's --temp flag.
    temperature,
    data.get("tools"),
    max_tokens,
    data.get("stream", False),
    stop,
    logprobs,
    top_logprobs,
  )


def _align_logprobs(tokenizer, all_tokens: list, eos_set, text: str, prompt_len: int, stop_cut: bool) -> tuple[list, list, list]:
  """Token strings / text offsets / kept indices for /v1/completions logprobs.

  OpenAI contract: the arrays align with the RETURNED text — no entries for
  EOS tokens (the text omits them) or tokens starting past a stop-string
  cut; ``keep`` indexes the surviving positions in ``all_tokens`` so the
  caller can subset the scores. Fast path: when per-token decodes concatenate
  to the joint decode, offsets are cumulative per-token lengths (O(tokens)).
  Fallback (byte-level BPE splitting a multi-byte char across tokens decodes
  to U+FFFD per token but one char jointly): joint prefix decodes, O(tokens²)
  — callers run this off the event loop.
  """
  ids = [int(t) for t in all_tokens if t not in eos_set]
  positions = [i for i, t in enumerate(all_tokens) if t not in eos_set]
  pieces = [tokenizer.decode([t]) for t in ids]
  joint = tokenizer.decode(ids)
  if "".join(pieces) == joint:
    prefix_lens = []
    acc = 0
    for p in pieces:
      prefix_lens.append(acc)
      acc += len(p)
  else:
    prefix_lens = [len(tokenizer.decode(ids[:j])) for j in range(len(ids))]
  toks, offsets, keep = [], [], []
  for j, (i, piece) in enumerate(zip(positions, pieces)):
    start = prefix_lens[j]
    if stop_cut and start >= len(text):  # starts past the cut
      break
    toks.append(piece)
    offsets.append(prompt_len + min(start, len(text)))
    keep.append(i)
  return toks, offsets, keep


def parse_qos_fields(data: dict, headers) -> tuple[str | None, str | None, float | None]:
  """(priority, tenant, deadline_ms) from OpenAI-compatible extra body
  fields (``priority``, ``deadline_ms``, ``tenant``) or headers
  (``x-priority``, ``x-deadline-ms``, ``x-tenant-id``). A client that sets
  neither gets all-None (the node's defaults apply). Tenant identity falls
  back to a hash of the Authorization header (per-API-key buckets without
  ever logging the key). Raises ``ValueError`` on malformed values — a typo
  must be a 400, not a silently-dropped QoS hint.

  TRUST MODEL: this API performs no authentication, so every tenant key —
  explicit or Authorization-derived — is client-asserted. Per-tenant rate
  limits and fairness are meaningful only behind a gateway that pins the
  tenant identity (strips/sets ``x-tenant-id`` itself); an unauthenticated
  client can rotate keys to dodge its bucket. The per-tenant state is
  LRU-bounded (qos.py MAX_TENANTS) so key rotation cannot grow memory."""
  priority = data.get("priority")
  if priority is None:
    priority = headers.get("x-priority")
  if priority is not None:
    priority = str(priority).lower()
    if priority not in PRIORITY_CLASSES:
      raise ValueError(f"'priority' must be one of {list(PRIORITY_CLASSES)}")
  deadline = data.get("deadline_ms")
  if deadline is None:
    deadline = headers.get("x-deadline-ms")
  if deadline is not None:
    if isinstance(deadline, bool):
      raise ValueError("'deadline_ms' must be a positive number")
    try:
      deadline = float(deadline)
    except (TypeError, ValueError):
      raise ValueError("'deadline_ms' must be a positive number") from None
    if not deadline > 0:
      raise ValueError("'deadline_ms' must be a positive number")
  tenant = data.get("tenant")
  if tenant is None:
    tenant = headers.get("x-tenant-id")
  if tenant is None:
    auth = headers.get("authorization")
    if auth:
      tenant = "key-" + hashlib.sha256(auth.encode()).hexdigest()[:12]
  if tenant is not None:
    tenant = str(tenant)[:64]
    if not tenant:
      tenant = None
  return priority, tenant, deadline


def parse_adapter_field(data: dict, headers, tenant: str | None, known=None) -> str | None:
  """Multi-LoRA adapter selection (ISSUE 15), first hit wins: the
  ``x-adapter`` header; an OpenAI-compatible ``model`` field that names a
  REGISTERED adapter (``known(name)`` — only a known name can alias the
  model field, so ordinary model ids keep their meaning); the tenant's
  default from ``XOT_TPU_LORA_TENANTS``. None = base model. TRUST: adapter
  names are client-asserted, exactly like tenant keys — pin the header at a
  gateway for real per-tenant adapter policy."""
  name = headers.get("x-adapter")
  if name:
    return str(name)[:128]
  model = data.get("model")
  if model and known is not None and known(str(model)):
    return str(model)
  if tenant:
    from ..inference.adapters import lora_tenant_map

    return lora_tenant_map().get(tenant)
  return None


def overloaded_response(e: Exception) -> web.Response:
  """ServerOverloadedError (and its QoS subclasses) → structured 429: a JSON
  body clients can back off on (``{"error": {"type", "message",
  "retry_after_ms"}}``) plus a standard ``Retry-After`` header derived from
  the measured drain rate. 503 stays reserved for genuine internal
  failures (e.g. profiler unavailable) — overload is a client-retryable
  condition, not a server fault."""
  retry_ms = getattr(e, "retry_after_ms", None)
  body = {"error": {"message": str(e), "type": getattr(e, "error_type", "overloaded")}}
  headers = {}
  if retry_ms is not None:
    body["error"]["retry_after_ms"] = round(float(retry_ms), 1)
    headers["Retry-After"] = str(max(1, math.ceil(float(retry_ms) / 1e3)))
  return web.json_response(body, status=429, headers=headers)


def stalled_response(e: Exception) -> web.Response:
  """RequestStalledError → structured, RETRYABLE 503 (the stall watchdog's
  contract, ISSUE 8): same typed-error shape as the QoS 429s, plus the
  tokens generated so far so a client or router can re-submit with resume
  semantics (``carry_tokens``-style continuation) instead of starting over.
  503 — the server is at fault (a dead/open-circuit hop), unlike the
  client-retryable overload 429s."""
  body = {
    "error": {
      "message": str(e),
      "type": getattr(e, "error_type", "upstream_stalled"),
      "retryable": True,
      "tokens": [int(t) for t in (getattr(e, "tokens", None) or [])],
    }
  }
  return web.json_response(body, status=503, headers={"Retry-After": "1"})


def completion_chunk(request_id: str, model: str, created: int, content: str | None, finish_reason: str | None) -> dict:
  delta = {} if content is None else {"role": "assistant", "content": content}
  return {
    "id": f"chatcmpl-{request_id}",
    "object": "chat.completion.chunk",
    "created": created,
    "model": model,
    "system_fingerprint": "xot_tpu_0.1.0",
    "choices": [{"index": 0, "delta": delta, "logprobs": None, "finish_reason": finish_reason}],
  }


class ChatGPTAPI:
  def __init__(self, node, inference_engine_classname: str, response_timeout: float | None = None, on_chat_completion_request=None, default_model: str | None = None, system_prompt: str | None = None):
    self.node = node
    self.inference_engine_classname = inference_engine_classname
    if response_timeout is None:
      # Env-configurable (was a hardcoded 900 s): the deployment's SLO, not
      # a code constant. Malformed or non-positive values fall back rather
      # than crash (0 would make every wait_for raise instantly).
      try:
        response_timeout = float(os.getenv("XOT_TPU_RESPONSE_TIMEOUT_S", "900") or 900)
      except ValueError:
        response_timeout = 900.0
      if response_timeout <= 0:
        response_timeout = 900.0
    self.response_timeout = response_timeout
    # Per-request ABSOLUTE deadlines (event-loop clock): a request carrying
    # ``deadline_ms`` is budgeted end-to-end — every wait gets only the
    # REMAINING budget, so a deadlined request can't hold a token queue
    # open past its SLO by making per-chunk progress.
    self._request_deadlines: dict[str, float] = {}
    # Stall watchdog (ISSUE 8): event-loop time of each request's last token
    # progress. No progress for XOT_TPU_STALL_S while an upstream hop is
    # dead or open-circuit ⇒ structured retryable 503 instead of waiting
    # out the full response timeout.
    self._last_progress: dict[str, float] = {}
    self.on_chat_completion_request = on_chat_completion_request
    self.default_model = default_model or "llama-3.2-1b"
    self.system_prompt = system_prompt

    self.app = web.Application(client_max_size=1024**3)  # 100MB+ for image payloads
    self.prev_token_lens: dict[str, int] = {}
    self.stream_tasks: dict[str, asyncio.Task] = {}
    self.token_queues: dict[str, asyncio.Queue] = {}

    # Token events from the node (local or broadcast from the sampling peer).
    self.node.on_token.register("chatgpt-api-token-handler").on_next(
      lambda req_id, tokens, is_finished: asyncio.create_task(self.handle_tokens(req_id, tokens, is_finished))
    )

    cors_middleware = self._make_cors_middleware()
    timeout_middleware = self._make_timeout_middleware()
    self.app.middlewares.extend([cors_middleware, timeout_middleware])

    # Cluster front door (ISSUE 13): XOT_TPU_ROUTER=1 + XOT_TPU_ROUTER_REPLICAS
    # turn this API into a prefix-affine multi-replica router that owns no
    # model. None (the default) keeps the request path byte-identical: one
    # ``is None`` check per chat request (test-pinned).
    from .router import build_router

    self._router = build_router(self)

    r = self.app.router
    r.add_post("/v1/chat/completions", self.handle_post_chat_completions)
    r.add_post("/chat/completions", self.handle_post_chat_completions)
    r.add_post("/v1/completions", self.handle_post_completions)
    r.add_post("/completions", self.handle_post_completions)
    r.add_post("/v1/chat/token/encode", self.handle_post_chat_token_encode)
    r.add_post("/chat/token/encode", self.handle_post_chat_token_encode)
    r.add_get("/v1/models", self.handle_get_models)
    r.add_get("/models", self.handle_get_models)
    r.add_get("/initial_models", self.handle_get_initial_models)
    r.add_get("/modelpool", self.handle_model_support)
    r.add_get("/healthcheck", self.handle_healthcheck)
    r.add_get("/metrics", self.handle_metrics)
    r.add_get("/v1/traces", self.handle_traces)
    r.add_get("/v1/requests/{request_id}/timeline", self.handle_request_timeline)
    r.add_get("/v1/kv/tier", self.handle_kv_tier)
    r.add_get("/v1/adapters", self.handle_adapters)
    r.add_get("/v1/disagg", self.handle_disagg)
    r.add_get("/v1/slo", self.handle_slo)
    r.add_get("/v1/programs", self.handle_programs)
    r.add_post("/v1/warmup", self.handle_warmup)
    r.add_get("/v1/router", self.handle_router_state)
    r.add_get("/v1/router/stats", self.handle_router_stats)
    r.add_get("/v1/events", self.handle_events)
    r.add_post("/v1/debug/bundle", self.handle_debug_bundle)
    r.add_post("/v1/profile", self.handle_profile)
    self._profiling = False  # one jax.profiler capture at a time
    r.add_get("/v1/topology", self.handle_get_topology)
    r.add_get("/topology", self.handle_get_topology)
    r.add_get("/v1/download/progress", self.handle_get_download_progress)
    r.add_post("/download", self.handle_post_download)
    r.add_delete("/models/{model_name}", self.handle_delete_model)
    r.add_post("/v1/image/generations", self.handle_image_generations)
    r.add_post("/v1/images/generations", self.handle_openai_image_generations)  # OpenAI Images API shape
    r.add_post("/quit", self.handle_quit)

    from ..utils.helpers import XOT_HOME

    self.images_dir = XOT_HOME / "images"
    self.images_dir.mkdir(parents=True, exist_ok=True)
    r.add_static("/images/", self.images_dir, name="static_images")

    static_dir = Path(__file__).parent.parent / "tinychat"
    if static_dir.exists():
      r.add_get("/", self.handle_root)
      r.add_static("/", static_dir, name="static")

  # ------------------------------------------------------------ middleware

  def _make_cors_middleware(self):
    @web.middleware
    async def cors(request, handler):
      if request.method == "OPTIONS":
        response = web.Response()
      else:
        try:
          response = await handler(request)
        except web.HTTPException as e:
          response = e
      response.headers["Access-Control-Allow-Origin"] = "*"
      response.headers["Access-Control-Allow-Methods"] = "GET, POST, DELETE, OPTIONS"
      response.headers["Access-Control-Allow-Headers"] = "Content-Type, Authorization"
      return response

    return cors

  def _make_timeout_middleware(self):
    @web.middleware
    async def timeout(request, handler):
      # The image handler manages its own per-wait stall timeout (the
      # reference likewise gives images a 10x budget, chatgpt_api.py:529);
      # wrapping the whole stream in wait_for would kill healthy long
      # generations after 200 headers are out.
      if request.path.endswith(("/image/generations", "/images/generations")):
        return await handler(request)
      try:
        return await asyncio.wait_for(handler(request), timeout=self.response_timeout)
      except asyncio.TimeoutError:
        return web.json_response({"detail": "Request timed out"}, status=408)

    return timeout

  # --------------------------------------------------------------- handlers

  async def handle_root(self, request):
    return web.FileResponse(Path(__file__).parent.parent / "tinychat" / "index.html")

  async def handle_healthcheck(self, request):
    return web.json_response({"status": "ok"})

  async def handle_metrics(self, request):
    from ..utils.metrics import Metrics, metrics

    if request.query.get("scope") == "cluster":
      # Merge every peer's snapshot (pulled over the gRPC opaque-status
      # channel) with the local registry: one exposition for the whole ring.
      collect = getattr(self.node, "collect_cluster_metrics", None)
      snapshots = [metrics.snapshot()]
      n_peers = 0
      if collect is not None:
        try:
          peer_snaps = await collect()
          n_peers = len(peer_snaps)
          snapshots.extend(peer_snaps)
        except Exception:  # noqa: BLE001 — cluster scrape degrades to local
          if DEBUG >= 1:
            import traceback

            traceback.print_exc()
      merged = Metrics.merged(snapshots)
      merged.set_gauge("cluster_nodes_reporting", 1 + n_peers)
      return web.Response(text=merged.render_prometheus(), content_type="text/plain")
    return web.Response(text=metrics.render_prometheus(), content_type="text/plain")

  async def handle_request_timeline(self, request):
    """GET /v1/requests/{id}/timeline — the request's stage breakdown
    (queued → admitted → prefill chunks → decode → detokenize) from the
    tracer's bounded timeline LRU. 404 once the entry has aged out.

    ``?scope=cluster`` (ISSUE 4): pull every peer's timeline fragment over
    the gRPC opaque-status channel, normalize remote timestamps with the
    NTP-style per-peer clock offsets, and merge into ONE hop-annotated
    timeline — each hop split into serialize / wire / deserialize / compute,
    so "which hop — compute, serialization, or wire?" is answerable for a
    request that crossed the ring."""
    from ..orchestration.tracing import tracer

    request_id = request.match_info.get("request_id", "")
    if request.query.get("scope") == "cluster":
      fragments = []
      try:
        fragments = await self.node.collect_cluster_timeline(request_id)
      except Exception:  # noqa: BLE001 — cluster pull degrades to local-only
        if DEBUG >= 1:
          import traceback

          traceback.print_exc()
      merged = self.node.merged_cluster_timeline(request_id, fragments)
      if merged is None:
        return web.json_response({"detail": f"no timeline for request {request_id}"}, status=404)
      return web.json_response(merged)
    tl = tracer.timeline(request_id)
    if tl is None:
      return web.json_response({"detail": f"no timeline for request {request_id}"}, status=404)
    return web.json_response(tl)

  async def handle_kv_tier(self, request):
    """GET /v1/kv/tier — the KV memory hierarchy's state (ISSUE 6): host
    tier occupancy/budget, spill/restore totals, and the cluster prefix
    registry (local advertised keys + each peer's advert size). This is how
    session park/resume is surfaced: a parked multi-turn session's pages
    show up as host-tier bytes here and as ``parked``/``unparked``/
    ``spilled``/``restored`` stages on its request timelines.

    ``?scope=cluster`` additionally refreshes the peer advertisements over
    the gRPC opaque-status channel before reporting (best-effort: an
    unreachable peer just keeps its last advert)."""
    from ..inference.kv_tier import kv_tier_enabled, prefix_registry
    from ..utils.metrics import metrics

    if request.query.get("scope") == "cluster":
      collect = getattr(self.node, "collect_cluster_prefixes", None)
      if collect is not None:
        try:
          await collect()
        except Exception:  # noqa: BLE001 — cluster refresh degrades to cached view
          if DEBUG >= 1:
            import traceback

            traceback.print_exc()
    tier = getattr(getattr(self.node.inference_engine, "_batched_server", None), "tier", None)
    body = {
      "enabled": kv_tier_enabled(),
      "host": tier.stats() if tier is not None else {
        # No live scheduler on this node (or tiering off): report the gauge
        # view so the endpoint stays truthful instead of 404ing.
        "host_pages": metrics.gauges.get("kv_tier_host_pages", 0),
        "host_bytes": metrics.gauges.get("kv_tier_host_bytes", 0),
      },
      "spilled_pages_total": metrics.counter_value("kv_tier_spilled_pages_total"),
      "restored_pages_total": metrics.counter_value("kv_tier_restored_pages_total"),
      "prefix_registry": prefix_registry.snapshot(),
    }
    return web.json_response(body)

  async def handle_adapters(self, request):
    """GET /v1/adapters — multi-LoRA registry introspection (ISSUE 15):
    every registered adapter with its device slot / host residency / pin
    count, plus the capacity and byte budgets. ``{"enabled": false}`` when
    multi-LoRA serving is off."""
    reg = getattr(getattr(self.node, "inference_engine", None), "adapter_registry", None)
    if reg is None:
      return web.json_response({"enabled": False, "detail": "multi-LoRA serving off (XOT_TPU_LORA=0 or no adapters loaded)"})
    return web.json_response({"enabled": True, **reg.snapshot()})

  def _adapter_known(self, name: str) -> bool:
    """Is ``name`` a registered adapter — locally, or (router mode) on any
    replica's latest advert? Used for the model-field alias, so an ordinary
    model id can never be misread as an adapter. Replicas advertise BOTH
    lists: ``lora_adapters_known`` (every registered name — what the alias
    must match, or a registered-but-cold adapter would silently serve base)
    and ``lora_adapters`` (device-resident — the affinity rung's subset)."""
    reg = getattr(getattr(self.node, "inference_engine", None), "adapter_registry", None)
    if reg is not None and reg.known(name):
      return True
    if self._router is not None:
      for v in self._router.policy.replicas.values():
        st = v.stats
        if name in (st.get("lora_adapters_known") or ()) or name in (st.get("lora_adapters") or ()):
          return True
    return False

  def _resolve_adapter(self, data: dict, headers, tenant: str | None) -> str | None:
    """Per-request adapter name (or None), validated locally when this node
    serves the model itself. In router mode the name forwards unvalidated —
    the serving replica enforces its own registry and the 400 relays."""
    from ..inference.adapters import check_known

    name = parse_adapter_field(data, headers, tenant, known=self._adapter_known)
    if name is None or self._router is not None:
      return name
    check_known(getattr(getattr(self.node, "inference_engine", None), "adapter_registry", None), name)
    return name

  async def handle_disagg(self, request):
    """GET /v1/disagg — disaggregated-serving state (ISSUE 10): this node's
    role, whether disagg is enabled, the cached peer role/capacity adverts
    the placement policy reads, and the transfer/handoff totals.

    ``?scope=cluster`` refreshes the peer adverts over the gRPC
    opaque-status channel first (best-effort, like ``/v1/kv/tier``)."""
    from ..inference import sched_admission
    from ..utils.metrics import metrics

    if request.query.get("scope") == "cluster":
      collect = getattr(self.node, "collect_disagg_stats", None)
      if collect is not None:
        try:
          await collect()
        except Exception:  # noqa: BLE001 — refresh degrades to the cached view
          if DEBUG >= 1:
            import traceback

            traceback.print_exc()
    body = {
      "enabled": sched_admission.disagg_enabled(),
      "role": getattr(self.node, "disagg_role", sched_admission.node_role()),
      "local": self.node._disagg_local_stats() if hasattr(self.node, "_disagg_local_stats") else {},
      "peers": dict(getattr(self.node, "_disagg_stats", {})),
      "handoffs_total": metrics.counter_value("disagg_handoffs_total"),
      "kv_stream_pages_total": metrics.counter_value("kv_stream_pages_total"),
      "kv_stream_bytes_total": metrics.counter_value("kv_stream_bytes_total"),
      "kv_stream_adopted_pages_total": metrics.counter_value("kv_stream_adopted_pages_total"),
    }
    return web.json_response(body)

  async def handle_slo(self, request):
    """GET /v1/slo — the SLO engine's report (ISSUE 9): per-class objectives,
    multi-window burn rates, availability, and goodput, every rate carried
    with its raw numerator/denominator. ``?scope=cluster`` pulls each peer's
    report over the gRPC opaque-status channel (``slo_pull``, the
    ``metrics_pull`` pattern) and merges by summing the raw counts — the
    cluster burn is exact, never an average of averages. 200 with
    ``{"enabled": false}`` when ``XOT_TPU_SLO=0``."""
    from ..orchestration.slo import slo_enabled, slo_engine

    if not slo_enabled():
      return web.json_response({"enabled": False, "detail": "SLO engine disabled (XOT_TPU_SLO=0)"})
    loop = asyncio.get_event_loop()
    if request.query.get("scope") == "cluster":
      peer_reports = []
      collect = getattr(self.node, "collect_cluster_slo", None)
      if collect is not None:
        try:
          peer_reports = await collect()
        except Exception:  # noqa: BLE001 — cluster pull degrades to local
          if DEBUG >= 1:
            import traceback

            traceback.print_exc()
      # Tick/report/merge deep-copy the registry — off the event loop (the
      # loop rides along so a watcher-triggered bundle capture can still
      # schedule on it).
      merged = await loop.run_in_executor(None, self.node.merged_cluster_slo, peer_reports, loop)
      return web.json_response(merged)

    def local_report():
      slo_engine.maybe_tick(node=self.node, loop=loop)
      return slo_engine.report(node_id=getattr(self.node, "id", None))

    return web.json_response(await loop.run_in_executor(None, local_report))

  async def handle_programs(self, request):
    """GET /v1/programs — the device-program ledger (ISSUE 19): per-family
    compile/dispatch counts, compile seconds (wall + the backend's own where
    jax.monitoring reports it), the triggering abstract shape signatures,
    the warmup manifest, and the steady flag. ``?scope=cluster`` pulls each
    peer's snapshot over the gRPC opaque-status channel (``programs_pull``,
    the ``slo_pull`` pattern) and merges by summing per-family counts —
    silent peers are annotated unreachable, never waited out."""
    from ..utils.programs import ProgramLedger, ledger

    local = ledger.snapshot()
    local["node_id"] = getattr(self.node, "id", None)
    local["devices"] = device_memory()
    if request.query.get("scope") != "cluster":
      return web.json_response(local)
    peer_snaps: list[dict] = []
    collect = getattr(self.node, "collect_cluster_programs", None)
    if collect is not None:
      try:
        peer_snaps = await collect()
      except Exception:  # noqa: BLE001 — cluster pull degrades to local
        if DEBUG >= 1:
          import traceback

          traceback.print_exc()
    merged = ProgramLedger.merge_snapshots([local] + peer_snaps)
    answered = {s.get("node_id") for s in peer_snaps}
    merged["unreachable"] = [
      pid for p in getattr(self.node, "peers", []) if (pid := p.id()) not in answered
    ]
    return web.json_response(merged)

  async def handle_warmup(self, request):
    """POST /v1/warmup — pre-compile the expected program set OFF the
    serving path (ISSUE 19): the batched scheduler enumerates its warmup
    manifest for the active config (backend, paged/dense, kv-quant,
    spec/mixed/LoRA), drives representative synthetic requests through the
    real submit path, then marks the ledger STEADY — from that point every
    compile is a recompile-sentinel event. A COLD batched-capable engine
    (fresh daemon, nothing served yet) first loads the default model's
    shard — the whole point of calling warmup before traffic is that the
    load+compile burst happens here, not inside the first request. Degrades
    gracefully when no batched scheduler exists (dummy engine / non-batched
    backend): the ledger is marked steady over an empty manifest so the
    sentinel still arms."""
    from ..utils.programs import ledger

    engine = getattr(self.node, "inference_engine", None)
    server = None
    if engine is not None and getattr(engine, "supports_batched", None):
      try:
        if getattr(engine, "shard", None) is None and self.default_model:
          shard = registry.build_base_shard(self.default_model, self.inference_engine_classname)
          if shard is not None:
            await engine.ensure_shard(shard)
        if getattr(engine, "shard", None) is not None and engine.supports_batched():
          server = engine.get_batched_server()
      except Exception:  # noqa: BLE001 — a cold engine warms up empty
        server = None
    if server is None:
      ledger.mark_steady(manifest=[])
      return web.json_response({"manifest": [], "warmup_s": 0.0, "steady": True, "detail": "no batched scheduler; ledger marked steady over an empty manifest"})
    out = await server.warmup()
    return web.json_response(out)

  async def handle_router_stats(self, request):
    """GET /v1/router/stats — the replica-side advert a cluster router
    polls (ISSUE 13): this node's live capacity/pressure aggregates (the
    same numbers ``/metrics`` exports, read from the live scheduler so
    multiple servers in one process stay distinct), the PR 5 deadline
    estimator's queue-drain number, the latency medians, the fast-window
    SLO burn per class, and the node's prefix advertisement (the chain-key
    hexes whose KV this node can serve as a prefix hit). Served by every
    node — cheap, no cluster fan-out."""
    from ..inference import sched_admission
    from ..inference.kv_tier import prefix_registry

    node = self.node
    st: dict = {
      "node_id": getattr(node, "id", None),
      "role": getattr(node, "disagg_role", sched_admission.node_role()),
      "draining": bool(getattr(node, "draining", False)),
    }
    engine = getattr(node, "inference_engine", None)
    shard = getattr(engine, "shard", None)
    if shard is not None:
      st["model"] = shard.model_id
    server = getattr(engine, "_batched_server", None)
    if server is not None:
      st.update(server.stats_snapshot())
      st["prefix_keys"] = server.prefix_hexes()
    else:
      # No live scheduler (cold node / non-batched engine): advertise what
      # the process-global registry knows so the endpoint stays truthful.
      st["prefix_keys"] = prefix_registry.local_hexes(limit=512)
    for name, q in (("ttft_p50_ms", "ttft_seconds"), ("itl_p50_ms", "itl_seconds")):
      v = metrics.quantile(q, 0.5)
      if v is not None:
        st[name] = round(v * 1e3, 3)
    burn = {}
    from ..inference.qos import PRIORITY_CLASSES
    from ..orchestration.slo import slo_enabled, slo_windows_s

    if slo_enabled():
      fast = f"{int(slo_windows_s()[0])}s"
      for cls in PRIORITY_CLASSES:
        v = metrics.gauge_value("slo_burn_rate", labels={"class": cls, "window": fast})
        if v is not None:
          burn[cls] = v
    st["slo_burn_fast"] = burn
    return web.json_response(st)

  async def handle_router_state(self, request):
    """GET /v1/router — router-mode introspection: replica views (stats
    age, advert freshness, load score), session-affinity occupancy, and
    the routing counters. ``{"enabled": false}`` on a non-router node."""
    if self._router is None:
      return web.json_response({"enabled": False, "detail": "router mode off (XOT_TPU_ROUTER=0 or no replicas)"})
    body = {
      "enabled": True,
      **self._router.policy.snapshot(),
      "requests_total": metrics.counter_sum("router_requests_total"),
      "prefix_hits_total": metrics.counter_sum("router_prefix_hits_total"),
      "failovers_total": metrics.counter_value("router_failovers_total"),
      "tenant_throttled_total": metrics.counter_sum("router_tenant_throttled_total"),
    }
    return web.json_response(body)

  async def handle_events(self, request):
    """GET /v1/events — query the flight recorder's wide-event ring
    (ISSUE 9). Filters: ``?type=a,b`` (comma-separated event types),
    ``?request_id=``, ``?peer=``, ``?since_s=`` (wall-clock age),
    ``?min_seq=``, ``?n=`` (newest N matches, default 256, clamped to the
    ring capacity). Events return oldest-first — causal order."""
    from ..orchestration.flightrec import flightrec

    if not flightrec.enabled:
      return web.json_response({"enabled": False, "detail": "flight recorder disabled (XOT_TPU_FLIGHTREC=0)"})
    types = None
    if request.query.get("type"):
      types = {t.strip() for t in request.query["type"].split(",") if t.strip()}
    try:
      n = int(request.query.get("n", "256"))
      since_s = float(request.query["since_s"]) if "since_s" in request.query else None
      min_seq = int(request.query["min_seq"]) if "min_seq" in request.query else None
      if n < 0 or (since_s is not None and since_s < 0):
        raise ValueError
    except (TypeError, ValueError):
      return web.json_response({"error": "'n'/'min_seq' must be integers, 'since_s' a non-negative number"}, status=400)
    events = flightrec.query(
      types=types,
      request_id=request.query.get("request_id"),
      peer=request.query.get("peer"),
      since_s=since_s,
      min_seq=min_seq,
      limit=min(n, flightrec.capacity),
    )
    return web.json_response({"enabled": True, "capacity": flightrec.capacity, "last_seq": flightrec.last_seq(), "events": events})

  async def handle_debug_bundle(self, request):
    """POST /v1/debug/bundle — one-call incident bundle (ISSUE 9): metric
    snapshots, recent flight events, breaker/health/clock state, active
    chaos schedule, in-flight timelines, and a config/env fingerprint from
    EVERY reachable peer (opaque-status pull; dead peers annotated, never
    waited out). Body (all optional): ``{"scope": "cluster"|"local",
    "reason": str, "save": bool}`` — ``save`` also writes the artifact to
    the bundle directory and returns its path."""
    from ..orchestration.flightrec import assemble_local_bundle, bundles

    try:
      data = await request.json()
    except Exception:  # noqa: BLE001 — empty body is fine
      data = {}
    reason = str(data.get("reason") or "manual")[:128]
    scope = str(data.get("scope") or "cluster")
    if scope == "cluster" and hasattr(self.node, "collect_cluster_bundle"):
      bundle = await self.node.collect_cluster_bundle(reason=reason)
    else:
      bundle = await asyncio.get_event_loop().run_in_executor(
        None, lambda: assemble_local_bundle(self.node, reason=reason)
      )
    metrics.inc("incident_bundles_total", labels={"trigger": "api"})
    if data.get("save"):
      path = bundles.write(bundle, reason)
      bundle["saved_to"] = path
    from ..orchestration.flightrec import flightrec

    flightrec.record("bundle_captured", cause=reason, attributes={"via": "api", "path": bundle.get("saved_to")})
    return web.json_response(bundle)

  async def handle_profile(self, request):
    """POST /v1/profile — on-demand jax.profiler capture to a directory.

    Body: {"duration_ms": float (default 1000, capped 60000)} or
    {"steps": int} — a step capture runs until ``steps`` more decode chunks
    complete (the engine-wide ``decode_chunks_total`` counters advance) or
    the duration cap elapses. ``dir`` overrides the output directory
    (default ``$XOT_TPU_PROFILE_DIR`` or XOT_HOME/profiles/<ts>). The
    python tracer is off unless ``python_tracer`` is true: the program's own
    ``xot.sched.*`` / ``xot.program:*`` spans (host tracer level 2) name the
    host's time without slowing the host that is being measured. Guarded:
    one capture at a time (409), and a clean 503 no-op when the profiler is
    unavailable on this backend. Disable the endpoint entirely with
    XOT_TPU_PROFILE=0.
    """
    import os as _os

    from ..utils.metrics import metrics

    if _os.getenv("XOT_TPU_PROFILE", "1") in ("0", "false"):
      return web.json_response({"detail": "profiling disabled (XOT_TPU_PROFILE=0)"}, status=403)
    try:
      data = await request.json()
    except Exception:  # noqa: BLE001 — empty body is fine
      data = {}
    try:
      steps = int(data.get("steps", 0))
      # A step-bounded capture without an explicit duration gets the full
      # 60 s deadline — the 1 s default would silently end a quiet node's
      # capture with ~0 steps; duration_ms stays the hard cap either way.
      default_ms = 60000.0 if steps > 0 else 1000.0
      duration_ms = min(float(data.get("duration_ms", default_ms)), 60000.0)
      if duration_ms <= 0 or steps < 0:
        raise ValueError
    except (TypeError, ValueError):
      return web.json_response({"error": "'duration_ms' must be > 0 and 'steps' >= 0"}, status=400)
    if self._profiling:
      return web.json_response({"detail": "a profile capture is already running"}, status=409)
    from ..utils.helpers import XOT_HOME

    out_dir = str(data.get("dir") or _os.getenv("XOT_TPU_PROFILE_DIR") or (XOT_HOME / "profiles" / f"trace-{int(time.time())}"))
    try:
      import jax.profiler as jax_profiler

      Path(out_dir).mkdir(parents=True, exist_ok=True)
      opts = jax_profiler.ProfileOptions()
      opts.python_tracer_level = 1 if data.get("python_tracer") is True else 0
      opts.host_tracer_level = 2
      jax_profiler.start_trace(out_dir, profiler_options=opts)
    except Exception as e:  # noqa: BLE001 — profiler unavailable: no-op, not a crash
      return web.json_response({"detail": f"profiler unavailable: {e}"}, status=503)
    from ..orchestration.flightrec import flightrec

    flightrec.record("profile_capture", attributes={"dir": out_dir, "duration_ms": duration_ms, "steps": steps})
    from ..utils.programs import ledger as program_ledger

    # Dispatch-count baseline: the response names the program families that
    # actually ran inside the captured window, so the trace joins against
    # the ledger (ISSUE 19).
    programs_base = program_ledger.dispatch_counts()
    self._profiling = True
    t0 = time.perf_counter()
    steps_seen = 0
    try:
      def chunk_total() -> float:
        return sum(
          metrics.counter_value("decode_chunks_total", labels={"path": p})
          for p in ("dense", "gather", "kernel")
        )

      if steps > 0:
        base = chunk_total()
        deadline = t0 + duration_ms / 1e3
        while time.perf_counter() < deadline:
          steps_seen = int(chunk_total() - base)
          if steps_seen >= steps:
            break
          await asyncio.sleep(0.02)
      else:
        await asyncio.sleep(duration_ms / 1e3)
    finally:
      self._profiling = False
      try:
        jax_profiler.stop_trace()
      except Exception:  # noqa: BLE001
        pass
    return web.json_response({
      "dir": out_dir,
      "duration_ms": round((time.perf_counter() - t0) * 1e3, 3),
      "steps_requested": steps,
      "steps_captured": steps_seen,
      "programs": program_ledger.active_families(programs_base),
    })

  async def handle_traces(self, request):
    """GET /v1/traces?n=N — recent spans. Hardened (ISSUE 4 satellite): a
    non-integer ``n`` is a 400, not a handler crash, and ``n`` clamps to the
    span ring-buffer capacity (asking for a million spans returns the whole
    buffer, it doesn't allocate for the ask)."""
    from ..orchestration.tracing import tracer

    try:
      n = int(request.query.get("n", "100"))
    except (TypeError, ValueError):
      return web.json_response({"error": "'n' must be an integer"}, status=400)
    if n < 0:
      return web.json_response({"error": "'n' must be >= 0"}, status=400)
    n = min(n, tracer.spans.maxlen or n)
    return web.json_response({"spans": tracer.recent_spans(n)})

  async def handle_quit(self, request):
    response = web.json_response({"detail": "Quit signal received"}, status=200)
    await response.prepare(request)
    await response.write_eof()
    import os
    import signal

    os.kill(os.getpid(), signal.SIGINT)
    return response

  async def handle_get_models(self, request):
    from ..download.downloader import get_models_dir, repo_to_dirname

    models_dir = get_models_dir()

    def has_local_weights(card) -> bool:
      repo = card.repo_for(self.inference_engine_classname)
      d = models_dir / repo_to_dirname(repo)
      return d.is_dir() and any(d.glob("*.safetensors"))

    models = [
      {
        "id": model_id,
        "object": "model",
        "owned_by": "xot_tpu",
        "ready": True,
        "name": card.pretty,
        "downloaded": has_local_weights(card),
      }
      for model_id, card in registry.model_cards.items()
      if card.repo_for(self.inference_engine_classname)
    ]
    return web.json_response({"object": "list", "data": models})

  async def handle_get_initial_models(self, request):
    model_data = {
      model_id: {
        "name": card.pretty,
        "downloaded": None,
        "download_percentage": None,
        "total_size": None,
        "total_downloaded": None,
        "loading": False,
      }
      for model_id, card in registry.model_cards.items()
      if card.repo_for(self.inference_engine_classname)
    }
    return web.json_response(model_data)

  async def handle_model_support(self, request):
    response = web.StreamResponse(status=200, headers={"Content-Type": "text/event-stream", "Cache-Control": "no-cache", "Connection": "keep-alive"})
    await response.prepare(request)
    for model_id, card in registry.model_cards.items():
      if not card.repo_for(self.inference_engine_classname):
        continue
      payload = {"model": model_id, "name": card.pretty, "downloaded": None, "download_percentage": None}
      await response.write(f"data: {json.dumps(payload)}\n\n".encode())
    await response.write(b"data: [DONE]\n\n")
    await response.write_eof()
    return response

  async def handle_get_topology(self, request):
    topology = self.node.current_topology
    return web.json_response(topology.to_json() if topology else {})

  async def handle_get_download_progress(self, request):
    progress_data = {}
    for node_id, progress in self.node.node_download_progress.items():
      progress_data[str(node_id)] = progress
    return web.json_response(progress_data)

  async def handle_post_download(self, request):
    data = await request.json()
    model_id = data.get("model")
    shard = registry.build_full_shard(model_id, self.inference_engine_classname)
    if shard is None:
      return web.json_response({"error": f"Invalid model: {model_id}"}, status=400)
    if self.node.shard_downloader is None:
      return web.json_response({"error": "no downloader configured"}, status=400)
    asyncio.create_task(self.node.shard_downloader.ensure_shard(shard, self.inference_engine_classname))
    return web.json_response({"status": f"Download started for {model_id}"})

  async def handle_delete_model(self, request):
    model_name = request.match_info.get("model_name")
    from ..download.downloader import delete_model

    if await delete_model(model_name, self.inference_engine_classname):
      return web.json_response({"status": f"Model {model_name} deleted"})
    return web.json_response({"detail": f"Model {model_name} not found"}, status=404)

  async def handle_post_completions(self, request):
    """Legacy text completions (`/v1/completions`): the prompt runs RAW — no
    chat template — through the same generation machinery. Supports
    max_tokens/temperature/stop/stream/echo and OpenAI's integer ``logprobs``
    (top-N per generated token, recomputed post-hoc; single-node serving)."""
    try:
      data = await request.json()
    except Exception:  # noqa: BLE001
      return web.json_response({"error": "invalid JSON body"}, status=400)
    prompt = data.get("prompt")
    if isinstance(prompt, list):
      if len(prompt) != 1 or not isinstance(prompt[0], str):
        return web.json_response({"error": "'prompt' must be a string (or a single-element list of one)"}, status=400)
      prompt = prompt[0]
    if not isinstance(prompt, str) or not prompt:
      return web.json_response({"error": "'prompt' must be a non-empty string"}, status=400)
    logprobs_n = data.get("logprobs")
    if logprobs_n is not None and (not isinstance(logprobs_n, int) or isinstance(logprobs_n, bool) or not 0 <= logprobs_n <= 20):
      return web.json_response({"error": "'logprobs' must be an integer in [0, 20]"}, status=400)
    if logprobs_n and data.get("stream"):
      return web.json_response({"error": "'logprobs' is not supported with 'stream': true"}, status=400)
    try:
      # Reuse the chat validation for the shared fields.
      base = parse_chat_request({**data, "messages": [{"role": "user", "content": prompt}], "logprobs": False, "top_logprobs": 0}, self.default_model)
      qos_priority, qos_tenant, qos_deadline_ms = parse_qos_fields(data, request.headers)
      adapter = self._resolve_adapter(data, request.headers, qos_tenant)
    except ValueError as e:
      # UnknownAdapterError subclasses ValueError: both are client errors.
      return web.json_response({"error": str(e)}, status=400)
    shard = registry.build_base_shard(base.model, self.inference_engine_classname)
    if shard is None:
      return web.json_response({"detail": f"Unsupported model: {base.model}"}, status=400)
    tokenizer = await self._tokenizer_for(shard)
    request_id = str(uuid.uuid4())
    created = int(time.time())
    self.token_queues[request_id] = asyncio.Queue()
    self._last_progress[request_id] = asyncio.get_event_loop().time()  # stall clock starts now
    if qos_deadline_ms is not None:
      self._request_deadlines[request_id] = asyncio.get_event_loop().time() + min(self.response_timeout, qos_deadline_ms / 1e3)
    if hasattr(self.node, "set_request_options"):
      self.node.set_request_options(
        request_id, stream=bool(base.stream), max_tokens=base.max_tokens, temperature=base.temperature,
        priority=qos_priority, tenant=qos_tenant, deadline_ms=qos_deadline_ms, adapter=adapter,
      )
    prompt_ids = list(tokenizer.encode(prompt)) if hasattr(tokenizer, "encode") else []
    eos = getattr(tokenizer, "eos_token_id", None)
    eos_set = {eos} if isinstance(eos, int) else set(eos or [])
    from ..inference.adapters import UnknownAdapterError
    from ..inference.engine import PromptTooLongError, ServerOverloadedError
    from ..parallel.hbm_planner import RingBudgetError

    def completion_body(text: str, finish_reason, logprobs_obj=None, n_gen: int = 0) -> dict:
      return {
        "id": f"cmpl-{request_id}",
        "object": "text_completion",
        "created": created,
        "model": base.model,
        "system_fingerprint": "xot_tpu_0.1.0",
        "choices": [{"index": 0, "text": text, "logprobs": logprobs_obj, "finish_reason": finish_reason}],
        "usage": {"prompt_tokens": len(prompt_ids), "completion_tokens": n_gen, "total_tokens": len(prompt_ids) + n_gen},
      }

    try:
      if base.stream:
        gen_task = asyncio.create_task(self.node.process_prompt(shard, prompt, request_id))
        try:
          return await self._stream_completions_response(request, base, request_id, tokenizer, created, gen_task)
        finally:
          if not gen_task.done():
            cancel = getattr(self.node, "cancel_request", None)
            if cancel is not None:
              cancel(request_id)
          try:
            await asyncio.wait_for(asyncio.shield(gen_task), timeout=30)
          except Exception:  # noqa: BLE001
            pass
      try:
        await self._await_generation(request_id, asyncio.create_task(self.node.process_prompt(shard, prompt, request_id)))
      except (asyncio.TimeoutError, RequestStalledError):
        cancel = getattr(self.node, "cancel_request", None)
        if cancel is not None:
          cancel(request_id)
        raise
      all_tokens = await self._collect_all_tokens(request_id)
      text = tokenizer.decode([t for t in all_tokens if t not in eos_set])
      finish_reason = self._finish_reason(tokenizer, all_tokens[-1] if all_tokens else -1, True, False)
      stop_cut = False
      if base.stop:
        cut, _ = find_stop(text, base.stop)
        if cut is not None:
          text = text[:cut]
          finish_reason = "stop"
          stop_cut = True
      logprobs_obj = None
      if logprobs_n:
        scored = await self._score_logprobs(shard, prompt_ids, all_tokens, logprobs_n)
        if scored is not None:
          chosen_lp, top_ids, top_lp = scored
          # Alignment runs in an executor: the exact fallback is O(tokens²)
          # decode work that must not stall the event loop.
          toks, offsets, keep = await asyncio.get_event_loop().run_in_executor(
            None, _align_logprobs, tokenizer, all_tokens, eos_set, text, len(prompt), stop_cut
          )
          logprobs_obj = {
            "tokens": toks,
            "token_logprobs": [float(chosen_lp[i]) for i in keep],
            "top_logprobs": [
              {tokenizer.decode([int(tid)]): float(tlp) for tid, tlp in zip(top_ids[i][:logprobs_n], top_lp[i][:logprobs_n])}
              for i in keep
            ],
            "text_offset": offsets,
          }
      if data.get("echo"):
        text = prompt + text
      return web.json_response(completion_body(text, finish_reason, logprobs_obj, len(all_tokens)))
    except asyncio.TimeoutError:
      return web.json_response({"detail": "Response generation timed out"}, status=408)
    except RequestStalledError as e:
      cancel = getattr(self.node, "cancel_request", None)
      if cancel is not None:
        cancel(request_id)
      return stalled_response(e)
    except PromptTooLongError as e:
      return web.json_response({"error": {"message": str(e), "type": "invalid_request_error", "code": "context_length_exceeded"}}, status=400)
    except UnknownAdapterError as e:
      return web.json_response({"error": {"message": str(e), "type": "invalid_request_error", "code": "unknown_adapter"}}, status=400)
    except ServerOverloadedError as e:
      return overloaded_response(e)
    except RingBudgetError as e:
      # Ahead-of-time refusal (node.py): the current ring cannot hold the
      # model — nothing was downloaded or loaded.
      return web.json_response({"error": {"message": str(e), "type": "insufficient_resources"}}, status=507)
    except Exception as e:  # noqa: BLE001
      if DEBUG >= 1:
        import traceback

        traceback.print_exc()
      return web.json_response({"detail": f"Error processing prompt: {e}"}, status=500)
    finally:
      self.token_queues.pop(request_id, None)
      self._request_deadlines.pop(request_id, None)
      self._last_progress.pop(request_id, None)
      getattr(self.node, "request_options", {}).pop(request_id, None)

  async def _stream_completions_response(self, request, base, request_id, tokenizer, created, gen_task):
    """SSE for /v1/completions: the shared token loop with text_completion
    chunk shapes."""

    def chunk(text: str, reason) -> dict:
      return {
        "id": f"cmpl-{request_id}",
        "object": "text_completion",
        "created": created,
        "model": base.model,
        "choices": [{"index": 0, "text": text, "logprobs": None, "finish_reason": reason}],
      }

    return await self._run_sse_stream(
      request, request_id, tokenizer, base.stop, gen_task,
      lambda delta: chunk(delta, None),
      lambda reason: chunk("", reason),
    )

  async def handle_image_generations(self, request):
    """POST /v1/image/generations — streaming progress + saved-PNG URL.

    Surface parity with the reference handler (chatgpt_api.py:445-535):
    same request fields (model, prompt, image_url for img2img), same
    octet-stream of JSON lines ({"progress": ...} then {"images": [{url,
    content_type}]}), same images static mount. Difference: this one
    actually generates (the reference's SD registry entry is commented out,
    reference models.py:167-168, so its path is unreachable). Extra fields
    beyond the reference: negative_prompt, steps, guidance, seed, size,
    strength.
    """
    data, shard, err = await self._image_request_prologue(request)
    if err is not None:
      return err
    prompt = data.get("prompt", "")

    init_image = None
    image_url = data.get("image_url") or ""
    if image_url:
      try:
        init_image = self._decode_image_b64(image_url)
      except Exception as e:  # noqa: BLE001
        return web.json_response({"error": f"invalid image_url: {e}"}, status=400)

    # Coerce every numeric field BEFORE the 200 headers go out — malformed
    # input must be a clean 400, not a truncated stream.
    try:
      gen_kwargs = dict(
        negative=str(data.get("negative_prompt", "")),
        steps=int(data.get("steps", 30)),
        guidance=float(data.get("guidance", 7.5)),
        seed=int(data.get("seed", 0)),
        size=tuple(int(v) for v in data["size"]) if data.get("size") else None,
        strength=float(data.get("strength", 0.8)),
        n=int(data.get("n", 1)),
      )
      if not 1 <= gen_kwargs["n"] <= 4:
        raise ValueError("n must be in [1, 4]")
      if gen_kwargs["size"] is not None:
        if len(gen_kwargs["size"]) != 2:
          raise ValueError("size must be [height, width]")
        if not all(8 <= v <= 2048 for v in gen_kwargs["size"]):
          raise ValueError("size dims must be in [8, 2048]")
      if not 1 <= gen_kwargs["steps"] <= 1000:
        raise ValueError("steps must be in [1, 1000]")
    except (TypeError, ValueError) as e:
      return web.json_response({"error": f"invalid parameters: {e}"}, status=400)

    request_id = str(uuid.uuid4())
    response = web.StreamResponse(
      status=200, reason="OK",
      headers={"Content-Type": "application/octet-stream", "Cache-Control": "no-cache"},
    )
    await response.prepare(request)

    progress_q: asyncio.Queue = asyncio.Queue()

    def on_progress(done: int, total: int) -> None:
      progress_q.put_nowait((done, total))

    import threading

    # Client-disconnect cancellation: asyncio cancel can't interrupt the
    # engine's worker thread, so the pipeline polls this event between
    # denoise chunks (same contract as chat streaming's disconnect path).
    cancel_event = threading.Event()
    gen = asyncio.create_task(
      self.node.process_image_prompt(
        shard, prompt, request_id, init_image=init_image, progress_cb=on_progress,
        cancel_event=cancel_event, **gen_kwargs,
      )
    )
    get_q = None  # tracked outside the loop so EVERY exit path can cancel it
    try:
      while True:
        get_q = asyncio.create_task(progress_q.get())
        finished, _ = await asyncio.wait({gen, get_q}, return_when=asyncio.FIRST_COMPLETED, timeout=self.response_timeout)
        if get_q in finished:
          done, total = get_q.result()
          pct = int(100 * done / max(total, 1))
          bar = "-" * max(pct // 2 - 1, 0) + ">" + " " * (50 - max(pct // 2, 1))
          await response.write(
            json.dumps({"progress": f"Progress: [{bar}] {pct}% ({done}/{total})", "step": done, "total_steps": total}).encode() + b"\n"
          )
          continue
        get_q.cancel()
        if gen in finished:
          break
        cancel_event.set()
        gen.cancel()
        await asyncio.gather(gen, return_exceptions=True)
        await response.write(json.dumps({"error": "image generation timed out"}).encode() + b"\n")
        await response.write_eof()
        return response

      image = gen.result()  # uint8 [H, W, 3] (or [n, H, W, 3] when n > 1)
      urls = await self._save_images(request, request_id, image)
      await response.write(json.dumps({"images": [{"url": u, "content_type": "image/png"} for u in urls]}).encode() + b"\n")
      await response.write_eof()
      return response
    except asyncio.CancelledError:
      # aiohttp cancels the handler task on client disconnect —
      # CancelledError is a BaseException, so the generic branch below never
      # sees it. Stop the denoise (the worker polls cancel_event between
      # chunks), retrieve the task outcome, and let the cancellation
      # propagate as aiohttp expects.
      cancel_event.set()
      gen.cancel()
      await asyncio.gather(gen, return_exceptions=True)
      raise
    except Exception as e:  # noqa: BLE001 — incl. client-disconnect write errors
      # Stop the denoise loop: the worker thread polls cancel_event between
      # chunks; the abandoned task's outcome is retrieved so it never logs
      # as an un-awaited exception.
      cancel_event.set()
      gen.cancel()
      await asyncio.gather(gen, return_exceptions=True)
      if DEBUG >= 2:
        import traceback

        traceback.print_exc()
      try:
        await response.write(json.dumps({"error": str(e)}).encode() + b"\n")
        await response.write_eof()
      except (ConnectionError, RuntimeError):
        pass  # client is gone; nothing to tell them
      return response
    finally:
      # The pending progress_q.get() would otherwise linger un-awaited and
      # log "Task was destroyed but it is pending!" on every disconnect.
      if get_q is not None and not get_q.done():
        get_q.cancel()

  async def _image_request_prologue(self, request, allow_default_model: bool = False):
    """Shared body-read + model/engine validation for both image routes.

    → (data, shard, None) on success, (None, None, web.Response) on refusal.
    The body read is bounded even though the timeout middleware exempts
    these routes (a slow-loris client must not hold the connection forever).
    ``allow_default_model`` (the OpenAI alias, where model is optional)
    falls back to the first SD registry card; the reference-shaped streaming
    route keeps its explicit-model 400.
    """
    try:
      data = await asyncio.wait_for(request.json(), timeout=30)
    except asyncio.TimeoutError:
      return None, None, web.json_response({"error": "request body read timed out"}, status=408)
    except Exception:  # noqa: BLE001 — same contract as the chat endpoints
      return None, None, web.json_response({"error": "invalid JSON body"}, status=400)
    model = data.get("model", "")
    if not model and allow_default_model:
      model = next((m for m in registry.model_cards if registry.get_family(m) == "stable-diffusion"), "")
      data = {**data, "model": model}
    if registry.get_family(model) != "stable-diffusion":
      return None, None, web.json_response({"error": f"Unsupported model for image generation: {model}"}, status=400)
    if not getattr(self.node.inference_engine, "can_generate_images", False):
      return None, None, web.json_response({"detail": "image generation models are not supported by this engine"}, status=501)
    shard = registry.build_base_shard(model, self.inference_engine_classname)
    if shard is None:
      return None, None, web.json_response({"error": f"Unsupported model: {model} with engine {self.inference_engine_classname}"}, status=400)
    return data, shard, None

  async def _save_images(self, request, request_id: str, image) -> list[str]:
    """uint8 [H,W,3] or [n,H,W,3] → saved PNGs under /images/, absolute URLs."""
    from PIL import Image

    batch = image if image.ndim == 4 else image[None]
    base = f"{request.scheme}://{request.host}"
    urls = []
    for i, arr in enumerate(batch):
      path = self.images_dir / (f"{request_id}.png" if len(batch) == 1 else f"{request_id}-{i}.png")
      await asyncio.get_event_loop().run_in_executor(None, lambda a=arr, p=path: Image.fromarray(a).save(p))
      urls.append(base + str(request.app.router["static_images"].url_for(filename=path.name)))
    return urls

  async def handle_openai_image_generations(self, request):
    """POST /v1/images/generations — the OpenAI Images API shape (note the
    plural): blocking JSON {created, data: [{url} | {b64_json}]}. The
    reference only has the singular streaming route; this alias exists so
    OpenAI image clients work unmodified. Supports prompt, n (1-4), size
    ("512x512"), response_format ("url" | "b64_json"), and model (defaults
    to the first stable-diffusion registry card)."""
    data, shard, err = await self._image_request_prologue(request, allow_default_model=True)
    if err is not None:
      return err
    try:
      n = int(data.get("n", 1))
      if not 1 <= n <= 4:
        raise ValueError("n must be in [1, 4]")
      size = None
      if data.get("size"):
        w, h = (int(v) for v in str(data["size"]).lower().split("x"))
        if not (8 <= w <= 2048 and 8 <= h <= 2048):
          raise ValueError("size dims must be in [8, 2048]")
        size = (h, w)
      steps = int(data.get("steps", 30))
      if not 1 <= steps <= 1000:
        raise ValueError("steps must be in [1, 1000]")
      seed = int(data.get("seed", 0))
      negative = str(data.get("negative_prompt", ""))
      response_format = str(data.get("response_format", "url"))
      if response_format not in ("url", "b64_json"):
        raise ValueError("response_format must be 'url' or 'b64_json'")
    except (TypeError, ValueError) as e:
      return web.json_response({"error": f"invalid parameters: {e}"}, status=400)

    request_id = str(uuid.uuid4())
    import threading

    cancel_event = threading.Event()
    try:
      # 10x budget like the reference's image wait (chatgpt_api.py:529);
      # on timeout OR client disconnect the denoise loop is cooperatively
      # cancelled so the single engine worker doesn't keep burning for a
      # dead request.
      image = await asyncio.wait_for(
        self.node.process_image_prompt(
          shard, str(data.get("prompt", "")), request_id,
          negative=negative, steps=steps, seed=seed, size=size, n=n,
          cancel_event=cancel_event,
        ),
        timeout=self.response_timeout * 10,
      )
    except asyncio.TimeoutError:
      cancel_event.set()
      return web.json_response({"error": "image generation timed out"}, status=408)
    except asyncio.CancelledError:
      cancel_event.set()
      raise
    except NotImplementedError as e:
      return web.json_response({"error": str(e)}, status=501)
    except Exception as e:  # noqa: BLE001
      if DEBUG >= 2:
        import traceback

        traceback.print_exc()
      return web.json_response({"error": str(e)}, status=500)

    if response_format == "b64_json":
      def encode_all(batch):
        import base64
        import io

        from PIL import Image

        out = []
        for arr in batch:
          buf = io.BytesIO()
          Image.fromarray(arr).save(buf, format="PNG")
          out.append({"b64_json": base64.b64encode(buf.getvalue()).decode()})
        return out

      batch = image if image.ndim == 4 else image[None]
      entries = await asyncio.get_event_loop().run_in_executor(None, encode_all, batch)
    else:
      urls = await self._save_images(request, request_id, image)
      entries = [{"url": u} for u in urls]
    return web.json_response({"created": int(time.time()), "data": entries})

  @staticmethod
  def _decode_image_b64(image_url: str):
    """data-URL or raw base64 → uint8 RGB array, dims floored to /8. The
    pipeline itself snaps to the loaded model's exact pixel grid
    (DiffusionPipeline.px_multiple) before encoding; this host-side floor
    just keeps absurd sizes from shipping to the device."""
    import base64
    import io

    import numpy as np
    from PIL import Image

    payload = image_url.split(",", 1)[1] if image_url.startswith("data:") else image_url
    img = Image.open(io.BytesIO(base64.b64decode(payload))).convert("RGB")
    w, h = img.size
    if max(w, h) > 2048:  # cap like explicit sizes — one request must not OOM the worker
      scale = 2048 / max(w, h)
      w, h = max(int(w * scale), 8), max(int(h * scale), 8)
    w8, h8 = max(w // 8 * 8, 8), max(h // 8 * 8, 8)
    if (w8, h8) != img.size:
      img = img.resize((w8, h8))
    return np.asarray(img, dtype=np.uint8)

  async def handle_post_chat_token_encode(self, request):
    data = await request.json()
    model = data.get("model", self.default_model)
    if model.startswith("gpt-"):
      model = self.default_model
    shard = registry.build_base_shard(model, self.inference_engine_classname)
    if shard is None:
      return web.json_response({"error": f"Unsupported model: {model}"}, status=400)
    messages = [parse_message(m) for m in data.get("messages", [])]
    tokenizer = await self._tokenizer_for(shard)
    prompt, _images = build_prompt(tokenizer, messages, data.get("tools"))
    tokens = tokenizer.encode(prompt)
    return web.json_response({"length": len(prompt), "num_tokens": len(tokens), "encoded_tokens": [int(t) for t in tokens], "encoded_prompt": prompt})

  async def _tokenizer_for(self, shard: Shard):
    engine_tok = getattr(self.node.inference_engine, "tokenizer", None)
    loaded_shard = getattr(self.node.inference_engine, "shard", None)
    if engine_tok is not None and loaded_shard is not None and loaded_shard.model_id == shard.model_id:
      return engine_tok
    repo = registry.get_repo(shard.model_id, self.inference_engine_classname)
    if repo == "dummy":  # the dummy engine's tokenizer never lives on the hub
      return engine_tok
    return await resolve_tokenizer(repo)

  async def handle_tokens(self, request_id: str, tokens: list[int], is_finished: bool) -> None:
    queue = self.token_queues.get(request_id)
    if queue is not None:
      if tokens or is_finished:
        self._last_progress[request_id] = asyncio.get_event_loop().time()
      if is_finished:
        # Availability GOOD event (ISSUE 9), exactly once per client
        # request at the one layer EVERY serving path streams through
        # (batched scheduler, plain path, ring) — finish events arrive
        # once (the node's dedup tombstones duplicates). A request whose
        # timeline already claimed a refusal terminal was counted bad.
        from ..orchestration.slo import note_good, slo_enabled
        from ..orchestration.tracing import TERMINAL_STAGES, tracer as _tracer

        if slo_enabled() and _tracer.terminal_of(request_id) not in TERMINAL_STAGES:
          from ..inference.qos import qos_wire

          wire = qos_wire.get(request_id) or {}
          note_good(wire.get("priority") or "standard")
      await queue.put((tokens, is_finished))

  # --------------------------------------------------- stall watchdog (ISSUE 8)

  @staticmethod
  def _stall_after_s() -> float:
    """XOT_TPU_STALL_S (default 120 s; <= 0 disables). Read per check so
    operators (and tests) can retune a live server."""
    try:
      return float(os.getenv("XOT_TPU_STALL_S", "120") or 120)
    except ValueError:
      return 120.0

  def _stall_poll_s(self) -> float:
    """Wait-slice so detection lands within the 2x-stall-bound contract:
    at most stall/4 (floored at 50 ms), capped at the historical 1 s poll."""
    stall = self._stall_after_s()
    if stall <= 0:
      return 1.0
    return min(1.0, max(stall / 4.0, 0.05))

  def _upstream_faulty(self) -> bool:
    """Is any serving hop dead or open-circuit — or was a peer lost
    UNPLANNED recently? A healthy-but-slow model must never trip the
    watchdog; only a faulted upstream does. The predicate is node-scope,
    not per-request-path: on a ring every peer IS on the serving path, and
    the one conservative consequence — a request starving >stall_s while
    the cluster carries a genuinely faulted peer gets a RETRYABLE 503
    instead of more waiting — is an acceptable trade for never missing a
    real post-eviction stall. The sticky loss mark matters
    because the damped eviction forgets the dead peer's breaker/health
    state: a stall detected after eviction would otherwise look healthy
    and hang to the full response timeout. The loss window is bounded
    (2x the stall bound, >= 300 s: eviction takes ~15-30 s and the stall
    itself >= XOT_TPU_STALL_S, so the mark is always still warm when a
    loss-caused stall fires) — a long-ago loss must not convert every
    later slow request into a 503."""
    from ..networking.retry import breakers, peer_health

    loss_ts = getattr(self.node, "last_peer_loss_ts", None)
    if loss_ts is not None and time.monotonic() - loss_ts < max(self._stall_after_s() * 2, 300.0):
      return True
    for p in getattr(self.node, "peers", None) or []:
      try:
        pid = p.id()
      except Exception:  # noqa: BLE001 — a broken handle is itself a faulty hop
        return True
      if breakers.is_open(pid) or peer_health.is_dead(pid):
        return True
    return False

  def _check_stall(self, request_id: str) -> None:
    """Raise ``RequestStalledError`` (carrying every token the client has
    not yet been handed) when the request made no progress for the stall
    bound AND an upstream hop is faulted."""
    stall = self._stall_after_s()
    if stall <= 0:
      return
    now = asyncio.get_event_loop().time()
    last = self._last_progress.get(request_id)
    if last is None or now - last <= stall or not self._upstream_faulty():
      return
    pending: list[int] = []
    queue = self.token_queues.get(request_id)
    if queue is not None:
      while not queue.empty():  # undelivered chunks ride the 503 body
        toks, _fin = queue.get_nowait()
        pending.extend(toks)
    from ..inference.qos import qos_wire
    from ..orchestration.flightrec import bundles
    from ..orchestration.tracing import tracer

    metrics.inc("requests_stalled_total")
    wire = qos_wire.get(request_id) or {}
    tracer.stage(request_id, "stalled", {
      "stall_s": stall, "class": wire.get("priority") or "standard",
    }, terminal=True)
    # Auto-capture (ISSUE 9): the stall fires exactly when the failure's
    # context is freshest — grab a rate-limited incident bundle (cluster
    # scope, dead peers annotated) so the post-mortem starts from data,
    # not reconstruction. Scheduled as a task; never delays the 503.
    bundles.auto_capture("stall", node=self.node)
    raise RequestStalledError(
      f"no token progress for {stall:.0f}s with a dead or open-circuit upstream hop",
      tokens=pending,
    )

  async def _collect_all_tokens(self, request_id: str) -> list[int]:
    """Drain the request's token queue to the finish event (the blocking
    handlers' shared loop). A stall mid-drain re-raises with every token
    the client never got spliced into the 503's resume payload."""
    all_tokens: list[int] = []
    try:
      while True:
        tokens, is_finished = await self._next_tokens(request_id, None)
        all_tokens.extend(tokens)
        if is_finished:
          return all_tokens
    except RequestStalledError as e:
      e.tokens = all_tokens + e.tokens  # everything the client never got
      raise

  async def _await_generation(self, request_id: str, task) -> None:
    """Await a (shielded) generation task under the response timeout AND
    the stall watchdog: the blocking path's equivalent of ``_next_tokens``'
    poll loop — without it a ring stall would hang until the full response
    timeout, exactly the failure mode ROADMAP item 4 forbids."""
    deadline = asyncio.get_event_loop().time() + self._timeout_for(request_id)
    while True:
      remaining = deadline - asyncio.get_event_loop().time()
      if remaining <= 0:
        raise asyncio.TimeoutError
      try:
        return await asyncio.wait_for(asyncio.shield(task), timeout=min(self._stall_poll_s(), remaining))
      except asyncio.TimeoutError:
        self._check_stall(request_id)

  async def handle_post_chat_completions(self, request):
    try:
      data = await request.json()
    except Exception:  # noqa: BLE001 — malformed body is a client error
      return web.json_response({"error": "invalid JSON body"}, status=400)
    if DEBUG >= 2:
      print(f"[api] chat completions request: {data}")
    from ..inference.adapters import UnknownAdapterError

    try:
      chat_request = parse_chat_request(data, self.default_model)
      qos_priority, qos_tenant, qos_deadline_ms = parse_qos_fields(data, request.headers)
      adapter = self._resolve_adapter(data, request.headers, qos_tenant)
    except UnknownAdapterError as e:
      return web.json_response({"error": {"message": str(e), "type": "invalid_request_error", "code": "unknown_adapter"}}, status=400)
    except ValueError as e:
      return web.json_response({"error": str(e)}, status=400)

    shard = registry.build_base_shard(chat_request.model, self.inference_engine_classname)
    if shard is None:
      supported = registry.get_supported_models([[self.inference_engine_classname]])
      return web.json_response(
        {"detail": f"Unsupported model: {chat_request.model} with engine {self.inference_engine_classname}. Supported: {supported}"},
        status=400,
      )

    if self.system_prompt and not any(m.role == "system" for m in chat_request.messages):
      chat_request.messages.insert(0, Message("system", self.system_prompt))

    tokenizer = await self._tokenizer_for(shard)
    card = registry.model_cards.get(chat_request.model)
    vision = card is not None and card.family == "llava"
    # Local-checkpoint override (XOT_TPU_MODEL_DIR) can serve a vision model
    # under any id — trust the loaded engine config when present.
    engine_cfg = getattr(self.node.inference_engine, "cfg", None)
    vision = vision or getattr(engine_cfg, "vision", None) is not None
    prompt, images = build_prompt(tokenizer, chat_request.messages, chat_request.tools, vision=vision)
    request_id = str(uuid.uuid4())
    if self.on_chat_completion_request:
      try:
        self.on_chat_completion_request(request_id, chat_request, prompt)
      except Exception:  # noqa: BLE001
        pass

    self.token_queues[request_id] = asyncio.Queue()
    self._last_progress[request_id] = asyncio.get_event_loop().time()  # stall clock starts now
    created = int(time.time())
    if qos_deadline_ms is not None:
      self._request_deadlines[request_id] = asyncio.get_event_loop().time() + min(self.response_timeout, qos_deadline_ms / 1e3)
    if hasattr(self.node, "set_request_options"):
      # Serving hints: a non-streaming request lets the node generate the
      # whole response in one compiled program (single device round-trip).
      # QoS identity (priority/tenant/deadline) rides along for the batched
      # scheduler's admission/fairness policy and the gRPC metadata path.
      self.node.set_request_options(
        request_id,
        stream=bool(chat_request.stream),
        max_tokens=chat_request.max_tokens,
        temperature=chat_request.temperature,
        priority=qos_priority,
        tenant=qos_tenant,
        deadline_ms=qos_deadline_ms,
        adapter=adapter,
      )
    # Resume semantics (ISSUE 13): ``resume_tokens`` marks a re-submitted
    # continuation — the batched scheduler absorbs the carried tokens into
    # the prompt (the PR 8 carry-resume mechanics) and emits only NEW
    # tokens, so a router can splice an invisible failover. Requires the
    # batched scheduler (the only path with carry semantics).
    resume_tokens = data.get("resume_tokens")
    if resume_tokens is not None:
      if not isinstance(resume_tokens, list) or not all(isinstance(t, int) and not isinstance(t, bool) for t in resume_tokens):
        return web.json_response({"error": "'resume_tokens' must be a list of integers"}, status=400)
      # Router mode relays the carry to a replica (which enforces its own
      # scheduler support); only LOCAL serving needs the batched scheduler.
      if self._router is None and (os.getenv("XOT_TPU_BATCHED", "0") != "1" or not hasattr(self.node.inference_engine, "get_batched_server")):
        return web.json_response({"error": "'resume_tokens' requires the batched scheduler (XOT_TPU_BATCHED=1)"}, status=400)
    initial_state = None
    if images or resume_tokens:
      from ..inference.state import InferenceState

      extras = {}
      if images:
        extras["images"] = images
      if resume_tokens:
        extras["resume_tokens"] = [int(t) for t in resume_tokens]
      initial_state = InferenceState(extras=extras)
    # Truthful usage accounting (the reference reports none at all). Encoding
    # the prompt again costs one BPE pass — only pay it when usage will
    # actually be reported (blocking always; streaming only on request).
    stream_options = data.get("stream_options")
    if stream_options is not None and not isinstance(stream_options, dict):
      return web.json_response({"error": "'stream_options' must be an object"}, status=400)
    include_usage = bool((stream_options or {}).get("include_usage"))
    need_usage = not chat_request.stream or include_usage
    # Router mode always encodes (the affinity hash needs the ids) and
    # derives usage from that one pass — don't pay a second BPE here.
    prompt_tokens = len(tokenizer.encode(prompt)) if need_usage and self._router is None and hasattr(tokenizer, "encode") else 0
    from ..inference.engine import PromptTooLongError, ServerOverloadedError
    from ..parallel.hbm_planner import RingBudgetError
    from .router import RouterUpstreamHTTPError

    try:
      if self._router is not None:
        # Router mode (ISSUE 13): this node owns no model — the request is
        # dispatched to a full-model replica chosen by the prefix-affinity
        # ladder, with cluster-scoped tenant limits and invisible failover.
        # The typed refusals surface through the same ladder below.
        if chat_request.logprobs:
          return web.json_response({"error": "'logprobs' is not supported through the router"}, status=400)
        if images:
          # Falling through would serve locally on a model-less node; an
          # explicit refusal beats a confusing 500 (same shape as logprobs).
          return web.json_response({"error": "image content is not supported through the router"}, status=400)
        return await self._router.serve_chat(
          request, data, chat_request, request_id, tokenizer, prompt, created,
          (qos_priority, qos_tenant, qos_deadline_ms), include_usage, adapter=adapter,
        )
      if chat_request.stream:
        # Generation runs CONCURRENTLY with the SSE stream: tokens flow to
        # the client as they arrive (TTFT = prefill, not full generation),
        # and a client disconnect cancels the in-flight generation (frees
        # its batch slot / decode loop) instead of running to max_tokens.
        gen_task = asyncio.create_task(self.node.process_prompt(shard, prompt, request_id, inference_state=initial_state))
        try:
          if data.get("token_stream"):
            # Internal router protocol: raw token-id batches, no
            # detokenization — the ROUTER decodes the merged stream once.
            return await self._stream_token_response(request, request_id, gen_task)
          return await self._stream_response(request, chat_request, request_id, tokenizer, created, gen_task, prompt_tokens, include_usage)
        finally:
          if not gen_task.done():
            cancel = getattr(self.node, "cancel_request", None)
            if cancel is not None:
              cancel(request_id)
          try:
            await asyncio.wait_for(asyncio.shield(gen_task), timeout=30)
          except Exception:  # noqa: BLE001 — surfaced via the stream already
            pass
      try:
        await self._await_generation(
          request_id, asyncio.create_task(self.node.process_prompt(shard, prompt, request_id, inference_state=initial_state))
        )
      except (asyncio.TimeoutError, RequestStalledError):
        # The shielded generation would otherwise keep decoding (and keep its
        # batch slot) until max_tokens after the client got its 408/503.
        cancel = getattr(self.node, "cancel_request", None)
        if cancel is not None:
          cancel(request_id)
        raise
      prompt_ids = list(tokenizer.encode(prompt)) if chat_request.logprobs and hasattr(tokenizer, "encode") else None
      return await self._blocking_response(chat_request, request_id, tokenizer, created, prompt_tokens, shard=shard, prompt_ids=prompt_ids)
    except asyncio.TimeoutError:
      return web.json_response({"detail": "Response generation timed out"}, status=408)
    except RequestStalledError as e:
      # Stall watchdog (ISSUE 8): structured retryable 503 carrying the
      # tokens generated so far — the client can re-submit with resume
      # semantics instead of replaying the whole generation.
      return stalled_response(e)
    except PromptTooLongError as e:
      return web.json_response({"error": {"message": str(e), "type": "invalid_request_error", "code": "context_length_exceeded"}}, status=400)
    except UnknownAdapterError as e:
      return web.json_response({"error": {"message": str(e), "type": "invalid_request_error", "code": "unknown_adapter"}}, status=400)
    except ServerOverloadedError as e:
      # Overload / rate-limit / deadline-shed: structured 429 + Retry-After
      # (the QoS subclasses carry retry_after_ms from the drain estimate —
      # or, through the router, the CLUSTER retry horizon).
      return overloaded_response(e)
    except RouterUpstreamHTTPError as e:
      # A replica refused with a non-retryable status: relay it verbatim —
      # the router adds no failure modes of its own to client errors.
      return web.json_response(e.body, status=e.status)
    except RingBudgetError as e:
      # Ahead-of-time refusal (node.py): the current ring cannot hold the
      # model — nothing was downloaded or loaded.
      return web.json_response({"error": {"message": str(e), "type": "insufficient_resources"}}, status=507)
    except Exception as e:  # noqa: BLE001
      if DEBUG >= 1:
        import traceback

        traceback.print_exc()
      return web.json_response({"detail": f"Error processing prompt: {e}"}, status=500)
    finally:
      self.token_queues.pop(request_id, None)
      self._request_deadlines.pop(request_id, None)
      self._last_progress.pop(request_id, None)
      # On multi-node rings the finishing node cleans its own copy; the
      # API-attached node must drop its entry here or it leaks per request.
      getattr(self.node, "request_options", {}).pop(request_id, None)

  def _finish_reason(self, tokenizer, last_token: int, is_finished: bool, hit_max: bool) -> str | None:
    if not is_finished:
      return None
    eos = getattr(tokenizer, "eos_token_id", None)
    eos_set = {eos} if isinstance(eos, int) else set(eos or [])
    return "stop" if last_token in eos_set else "length"

  def _timeout_for(self, request_id: str) -> float:
    """Effective timeout for one WAIT of this request: the configured
    ``response_timeout``, capped by the REMAINING end-to-end budget when
    the request carries a ``deadline_ms`` (anchored at request start — a
    generation making slow per-chunk progress still times out at its SLO
    instead of resetting the clock every chunk)."""
    deadline = self._request_deadlines.get(request_id)
    if deadline is None:
      return self.response_timeout
    return min(self.response_timeout, max(deadline - asyncio.get_event_loop().time(), 0.0))

  async def _next_tokens(self, request_id, gen_task):
    """Next (tokens, finished) from the queue; surfaces a generation failure
    promptly instead of waiting out the full response timeout."""
    queue = self.token_queues[request_id]
    deadline = asyncio.get_event_loop().time() + self._timeout_for(request_id)
    while True:
      remaining = deadline - asyncio.get_event_loop().time()
      if remaining <= 0:
        raise asyncio.TimeoutError
      try:
        return await asyncio.wait_for(queue.get(), timeout=min(self._stall_poll_s(), remaining))
      except asyncio.TimeoutError:
        if gen_task is not None and gen_task.done() and gen_task.exception() is not None:
          raise gen_task.exception()
        self._check_stall(request_id)

  async def _run_sse_stream(self, request, request_id, tokenizer, stops, gen_task, make_delta_chunk, make_finish_chunk, make_trailer_chunk=None):
    """The one SSE token loop both endpoints share: incremental
    detokenization (decode the full token list each time and emit the text
    suffix — per-token decode drops BPE leading spaces), stop-string
    hold-back, finish_reason from the RAW final token batch, and in-band
    error reporting once the response is committed. The chunk shapes
    (chat.completion.chunk vs text_completion) come from the callbacks;
    ``make_trailer_chunk(n_completion)`` may add one final chunk (usage).
    """
    # Fetch the FIRST token batch before committing the SSE response: errors
    # knowable at admission (PromptTooLongError, ServerOverloadedError, a
    # pre-first-token timeout) propagate to the handler and get their proper
    # 400/429/408 status instead of a 200 stream with an in-band error.
    tokens, is_finished = await self._next_tokens(request_id, gen_task)
    from ..orchestration.tracing import tracer

    response = web.StreamResponse(
      status=200,
      reason="OK",
      headers={"Content-Type": "text/event-stream", "Cache-Control": "no-cache"},
    )
    await response.prepare(request)
    eos = getattr(tokenizer, "eos_token_id", None)
    eos_set = {eos} if isinstance(eos, int) else set(eos or [])
    all_tokens: list[int] = []
    n_completion = 0
    emitted_text = ""

    async def emit(chunk: dict) -> None:
      await response.write(f"data: {json.dumps(chunk)}\n\n".encode())

    try:
      while True:
        n_completion += len(tokens)
        all_tokens.extend(t for t in tokens if t not in eos_set)
        full_text = tokenizer.decode(all_tokens) if all_tokens else ""
        cut = None
        safe_len = len(full_text)
        if stops:
          cut, safe_len = find_stop(full_text, stops)
          if cut is not None:
            full_text = full_text[:cut]
            safe_len = cut
          elif is_finished:
            safe_len = len(full_text)  # flush any held-back stop-prefix suffix
        delta = full_text[len(emitted_text):safe_len]
        if delta:
          emitted_text = full_text[:safe_len]
          await emit(make_delta_chunk(delta))
        if cut is not None:
          # Stop string hit: end the stream (the handler's finally cancels
          # the still-running generation) — finish_reason "stop" per OpenAI.
          await emit(make_finish_chunk("stop"))
          break
        if is_finished:
          # Reason from the RAW final batch: an EOS-terminated stream is
          # "stop" even though EOS tokens never enter all_tokens.
          await emit(make_finish_chunk(self._finish_reason(tokenizer, tokens[-1] if tokens else -1, True, False)))
          break
        tokens, is_finished = await self._next_tokens(request_id, gen_task)
      # Detokenization was incremental (interleaved with decode); mark the
      # stage at stream end so the timeline doesn't attribute decode time to
      # it (the duration-to-next-event rollup would otherwise absorb the
      # whole stream into "detokenize").
      tracer.stage(request_id, "detokenize", {"streaming": True, "tokens": n_completion})
      if make_trailer_chunk is not None:
        trailer = make_trailer_chunk(n_completion)
        if trailer is not None:
          await emit(trailer)
    except Exception as e:  # noqa: BLE001
      # The SSE response is already committed (prepare() ran; bytes may be
      # out) — aiohttp cannot send a second response on this connection, so
      # report the failure IN-BAND as an SSE error event and end the stream
      # cleanly instead of returning a fresh json_response the client would
      # never parse.
      detail = "Response generation timed out" if isinstance(e, asyncio.TimeoutError) else f"Error processing prompt: {e}"
      err_obj: dict = {"message": detail}
      if isinstance(e, RequestStalledError):
        # Stall watchdog mid-stream: the same typed retryable contract as
        # the 503, in-band. ``tokens`` = everything already streamed plus
        # anything the watchdog drained, so a router can resume exactly.
        err_obj.update({
          "type": getattr(e, "error_type", "upstream_stalled"),
          "retryable": True,
          "tokens": [int(t) for t in all_tokens + (getattr(e, "tokens", None) or [])],
        })
      if DEBUG >= 1 and not isinstance(e, (asyncio.TimeoutError, RequestStalledError)):
        import traceback

        traceback.print_exc()
      try:
        await response.write(f"data: {json.dumps({'error': err_obj})}\n\n".encode())
      except ConnectionResetError:
        return response  # client already gone
    await response.write(b"data: [DONE]\n\n")
    await response.write_eof()
    return response

  async def _stream_token_response(self, request, request_id, gen_task):
    """Internal token-stream SSE (ISSUE 13): raw token-id batches for a
    cluster router — ``data: {"tokens": [...], "finished": bool}`` events,
    ``data: [DONE]`` terminator. No detokenization, no stop strings (the
    router owns both over the merged stream). Errors knowable before the
    first batch propagate as proper HTTP statuses; a mid-stream stall
    reports IN-BAND with the retryable contract, ``tokens`` carrying only
    the UNDELIVERED batches (the router tracks what it already received)."""
    tokens, is_finished = await self._next_tokens(request_id, gen_task)
    response = web.StreamResponse(
      status=200, reason="OK",
      headers={"Content-Type": "text/event-stream", "Cache-Control": "no-cache"},
    )
    await response.prepare(request)
    try:
      while True:
        await response.write(f"data: {json.dumps({'tokens': [int(t) for t in tokens], 'finished': bool(is_finished)})}\n\n".encode())
        if is_finished:
          break
        tokens, is_finished = await self._next_tokens(request_id, gen_task)
    except Exception as e:  # noqa: BLE001 — response committed: report in-band
      err_obj: dict = {"message": "Response generation timed out" if isinstance(e, asyncio.TimeoutError) else f"Error processing prompt: {e}"}
      if isinstance(e, RequestStalledError):
        err_obj.update({
          "type": getattr(e, "error_type", "upstream_stalled"),
          "retryable": True,
          "tokens": [int(t) for t in (getattr(e, "tokens", None) or [])],
        })
      if DEBUG >= 1 and not isinstance(e, (asyncio.TimeoutError, RequestStalledError)):
        import traceback

        traceback.print_exc()
      try:
        await response.write(f"data: {json.dumps({'error': err_obj})}\n\n".encode())
      except ConnectionResetError:
        return response  # client already gone
    await response.write(b"data: [DONE]\n\n")
    await response.write_eof()
    return response

  async def _stream_response(self, request, chat_request, request_id, tokenizer, created, gen_task=None, prompt_tokens: int = 0, include_usage: bool = False):
    def make_trailer(n_completion: int) -> dict | None:
      if not include_usage:  # OpenAI stream_options.include_usage: final usage-only chunk
        return None
      usage_chunk = completion_chunk(request_id, chat_request.model, created, None, None)
      usage_chunk["choices"] = []
      usage_chunk["usage"] = {"prompt_tokens": prompt_tokens, "completion_tokens": n_completion, "total_tokens": prompt_tokens + n_completion}
      return usage_chunk

    return await self._run_sse_stream(
      request, request_id, tokenizer, chat_request.stop, gen_task,
      lambda delta: completion_chunk(request_id, chat_request.model, created, delta, None),
      lambda reason: completion_chunk(request_id, chat_request.model, created, None, reason),
      make_trailer,
    )

  async def _score_logprobs(self, shard, prompt_ids, gen_tokens, top_n: int):
    """(chosen_lp, top_ids, top_lp) for the generated tokens, or None where
    scoring is unavailable (ring/mesh serving)."""
    if not prompt_ids or not gen_tokens:
      return None
    scorer = getattr(self.node, "score_tokens", None)
    if scorer is None:
      return None
    try:
      return await scorer(shard, list(prompt_ids) + list(gen_tokens), len(gen_tokens), max(top_n, 1))
    except Exception:  # noqa: BLE001 — logprobs are best-effort decoration
      if DEBUG >= 1:
        import traceback

        traceback.print_exc()
      return None

  def _chat_logprobs(self, tokenizer, token_ids, scored, top_n: int) -> dict | None:
    if scored is None:
      return None
    chosen_lp, top_ids, top_lp = scored

    def tok_entry(tid: int, lp: float) -> dict:
      s = tokenizer.decode([int(tid)])
      return {"token": s, "logprob": float(lp), "bytes": list(s.encode())}

    content = []
    for i, t in enumerate(token_ids):
      entry = tok_entry(t, chosen_lp[i])
      entry["top_logprobs"] = [tok_entry(int(tid), float(tlp)) for tid, tlp in zip(top_ids[i][:top_n], top_lp[i][:top_n])]
      content.append(entry)
    return {"content": content, "refusal": None}

  async def _blocking_response(self, chat_request, request_id, tokenizer, created, prompt_tokens: int = 0, shard=None, prompt_ids=None):
    eos = getattr(tokenizer, "eos_token_id", None)
    eos_set = {eos} if isinstance(eos, int) else set(eos or [])
    all_tokens = await self._collect_all_tokens(request_id)
    # Generation already completed (the handler awaits process_prompt before
    # calling here), so stop strings are a single post-hoc scan + truncation.
    from ..orchestration.tracing import tracer

    tracer.stage(request_id, "detokenize", {"tokens": len(all_tokens)})
    content = tokenizer.decode([t for t in all_tokens if t not in eos_set])
    finish_reason = self._finish_reason(tokenizer, all_tokens[-1] if all_tokens else -1, True, False)
    if chat_request.stop:
      cut, _safe = find_stop(content, chat_request.stop)
      if cut is not None:
        content = content[:cut]
        finish_reason = "stop"
    logprobs_obj = None
    if chat_request.logprobs:
      # Post-hoc scoring covers every generated token (including a trailing
      # EOS and any tokens past a stop-string cut — token/text boundaries
      # don't align under truncation).
      scored = await self._score_logprobs(shard, prompt_ids, all_tokens, chat_request.top_logprobs)
      logprobs_obj = self._chat_logprobs(tokenizer, all_tokens, scored, chat_request.top_logprobs)
    return web.json_response(
      {
        "id": f"chatcmpl-{request_id}",
        "object": "chat.completion",
        "created": created,
        "model": chat_request.model,
        "system_fingerprint": "xot_tpu_0.1.0",
        "choices": [
          {
            "index": 0,
            "message": {"role": "assistant", "content": content},
            "logprobs": logprobs_obj,
            "finish_reason": finish_reason,
          }
        ],
        "usage": {"prompt_tokens": prompt_tokens, "completion_tokens": len(all_tokens), "total_tokens": prompt_tokens + len(all_tokens)},
      }
    )

  async def run(self, host: str = "0.0.0.0", port: int = 52415):
    runner = web.AppRunner(self.app)
    await runner.setup()
    site = web.TCPSite(runner, host, port)
    await site.start()
    if DEBUG >= 0:
      print(f"[api] ChatGPT-compatible API on http://{host}:{port}")
    return runner
