"""Cross-cutting helpers: debug flags, async callback fan-out, small net/id utils.

Capability parity with reference ``xotorch/helpers.py`` (DEBUG env levels
:19-21, AsyncCallbackSystem :104-149, port/node-id/interface utilities
:234-315), re-implemented for this framework. The callback system is the one
piece of the reference design that is transport- and engine-agnostic and was
explicitly worth keeping (SURVEY.md §7 design translation table).
"""

from __future__ import annotations

import asyncio
import os
import random
import socket
import uuid
from pathlib import Path
from typing import Any, Callable, Generic, TypeVar, TypeVarTuple, Unpack

DEBUG = int(os.getenv("DEBUG", "0"))
DEBUG_DISCOVERY = int(os.getenv("DEBUG_DISCOVERY", "0"))


def env_flag(name: str, default: bool = False) -> bool:
  """Boolean env var: unset → default; '', '0', 'false', 'no', 'off' (any
  case) → False; anything else ('1', 'true', 'yes', ...) → True."""
  val = os.getenv(name)
  if val is None:
    return default
  return val.strip().lower() not in ("", "0", "false", "no", "off")


def env_float(name: str, default: float) -> float:
  """Float env var: unset, empty, or malformed → default (a typo'd knob
  degrades to the shipped behavior, never crashes a policy read). The one
  shared parser behind the retry/SLO/anomaly knobs."""
  try:
    return float(os.getenv(name, "") or default)
  except ValueError:
    return default


def apply_platform_override() -> None:
  """The one rule for which device JAX uses: ``XOT_TPU_PLATFORM`` (else
  ``JAX_PLATFORMS``) when set, otherwise JAX's own choice. Parity with the
  reference's TORCH_DEVICE knob (sharded_inference_engine.py:58-65). Entry
  points call this before touching devices; nothing else in the package
  picks or changes the platform."""
  platform = os.getenv("XOT_TPU_PLATFORM") or os.getenv("JAX_PLATFORMS")
  if platform:
    import jax

    jax.config.update("jax_platforms", platform)


# Default home of JAX's persistent compilation cache: one fixed directory at
# the root of the checkout (git-ignored). The path is part of the cache key,
# so it must not move between runs — never a temp dir, a pid or a timestamp.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xot_compile_cache"


def configure_compile_cache() -> str:
  """Place JAX's persistent compilation cache; returns the directory in use.

  ``JAX_COMPILATION_CACHE_DIR`` set → JAX reads it itself and no directory is
  set in code; unset → ``COMPILE_CACHE_DIR``. Every entry point calls this next
  to ``apply_platform_override`` so a daemon, the bench and ``chip_smoke.py``
  children all share one cache (a 16-layer model's programs compile once per
  checkout instead of once per process)."""
  import jax

  if os.getenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS") is None:
    # JAX keeps only programs that took over a second to compile; a cold
    # daemon also builds dozens of smaller ones, every start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  # A cached executable carries the ``op_name`` of every op as the code that
  # compiled it named them, and JAX strips such metadata from the key: a profiler
  # capture then names device ops by another version's ``xot.*`` component
  # scopes, or by none (seen on the chip, PR 24: the parent commit's run traced
  # with this one's scopes). So the names go into the key — and only the names:
  # with no traceback in the locations a line that moves in a traced file changes
  # no key (nor a Pallas kernel's serialized body), a renamed scope does.
  jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
  jax.config.update("jax_traceback_in_locations_limit", 0)
  placed = os.getenv("JAX_COMPILATION_CACHE_DIR")
  if placed:
    return placed
  jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
  return str(COMPILE_CACHE_DIR)


def device_summary() -> dict:
  """Platform, kind and count of the devices JAX gave this process — what an
  entry point logs once at start, and what ``chip_smoke.py`` reports."""
  import jax

  devices = jax.devices()
  return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def device_memory() -> list[dict]:
  """Per local device, what the runtime says it holds: ``bytes_in_use``,
  ``peak_bytes_in_use`` and ``bytes_limit`` from ``memory_stats()`` (all
  None on a backend that reports none, as the CPU's) — how ``/v1/programs``
  shows which chips a serving plan really put weights and cache on."""
  import jax

  out = []
  for d in jax.local_devices():
    stats = d.memory_stats() or {}
    out.append({"id": d.id, "platform": d.platform, "kind": d.device_kind, **{k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}})
  return out


XOT_HOME = Path(os.getenv("XOT_TPU_HOME", Path.home() / ".cache" / "xot_tpu"))

T = TypeVar("T")
Ts = TypeVarTuple("Ts")


class AsyncCallback(Generic[Unpack[Ts]]):
  """A single awaitable callback channel.

  ``wait(check, timeout)`` blocks until a ``trigger`` whose args satisfy
  ``check``; ``on_next`` registers a synchronous observer for every trigger.
  """

  def __init__(self) -> None:
    self.condition: asyncio.Condition = asyncio.Condition()
    self.result: tuple[Unpack[Ts]] | None = None
    self.observers: list[Callable[[Unpack[Ts]], None]] = []

  async def wait(self, check_condition: Callable[[Unpack[Ts]], bool], timeout: float | None = None) -> tuple[Unpack[Ts]]:
    async with self.condition:
      await asyncio.wait_for(
        self.condition.wait_for(lambda: self.result is not None and check_condition(*self.result)),
        timeout,
      )
      assert self.result is not None
      return self.result

  def on_next(self, callback: Callable[[Unpack[Ts]], None]) -> None:
    self.observers.append(callback)

  def set(self, *args: Unpack[Ts]) -> None:
    self.result = args
    for observer in self.observers:
      observer(*args)
    loop = asyncio.get_event_loop()
    loop.create_task(self._notify())

  async def _notify(self) -> None:
    async with self.condition:
      self.condition.notify_all()


class AsyncCallbackSystem(Generic[T, Unpack[Ts]]):
  """Keyed registry of AsyncCallbacks with broadcast trigger."""

  def __init__(self) -> None:
    self.callbacks: dict[T, AsyncCallback[Unpack[Ts]]] = {}

  def register(self, name: T) -> AsyncCallback[Unpack[Ts]]:
    if name not in self.callbacks:
      self.callbacks[name] = AsyncCallback[Unpack[Ts]]()
    return self.callbacks[name]

  def deregister(self, name: T) -> None:
    self.callbacks.pop(name, None)

  def trigger(self, name: T, *args: Unpack[Ts]) -> None:
    if name in self.callbacks:
      self.callbacks[name].set(*args)

  def trigger_all(self, *args: Unpack[Ts]) -> None:
    for callback in list(self.callbacks.values()):
      callback.set(*args)


K = TypeVar("K")
V = TypeVar("V")


class PrefixDict(Generic[K, V]):
  """Dict queried by key prefix (used for request-id lookups in the API)."""

  def __init__(self) -> None:
    self.items: dict[K, V] = {}

  def __setitem__(self, key: K, value: V) -> None:
    self.items[key] = value

  def __getitem__(self, key: K) -> V:
    return self.items[key]

  def __contains__(self, key: K) -> bool:
    return key in self.items

  def items_with_prefix(self, prefix: str) -> list[tuple[K, V]]:
    return [(k, v) for k, v in self.items.items() if str(k).startswith(prefix)]

  def find_prefix(self, argument: str) -> list[tuple[K, V]]:
    return [(k, v) for k, v in self.items.items() if argument.startswith(str(k))]

  def find_longest_prefix(self, argument: str) -> tuple[K, V] | None:
    matches = self.find_prefix(argument)
    if not matches:
      return None
    return max(matches, key=lambda kv: len(str(kv[0])))


def find_available_port(host: str = "", min_port: int = 49152, max_port: int = 65535) -> int:
  """Pick a free TCP port by bind-probing random candidates."""
  for _ in range(100):
    port = random.randint(min_port, max_port)
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
      try:
        s.bind((host, port))
        return port
      except OSError:
        continue
  raise RuntimeError("no available port found")


def get_or_create_node_id() -> str:
  """Stable node identity persisted under the framework cache dir.

  Honors ``XOT_TPU_UUID`` for tests/deployments that pin identity (reference
  honors ``XOT_UUID``, ``helpers.py:360``).
  """
  if env_id := os.getenv("XOT_TPU_UUID"):
    return env_id
  id_file = XOT_HOME / ".node_id"
  try:
    if id_file.is_file():
      stored = id_file.read_text().strip()
      if stored:
        return stored
    node_id = str(uuid.uuid4())
    id_file.parent.mkdir(parents=True, exist_ok=True)
    id_file.write_text(node_id)
    return node_id
  except OSError:
    return str(uuid.uuid4())


def pretty_print_bytes(size_in_bytes: float) -> str:
  for unit, divisor in (("TB", 1024**4), ("GB", 1024**3), ("MB", 1024**2), ("KB", 1024)):
    if size_in_bytes >= divisor:
      return f"{size_in_bytes / divisor:.2f} {unit}"
  return f"{size_in_bytes:.0f} B"


def pretty_print_bytes_per_second(bytes_per_second: float) -> str:
  return f"{pretty_print_bytes(bytes_per_second)}/s"


# Interface-type priority for discovery: when the same peer is reachable over
# multiple links prefer the fastest (reference scores Thunderbolt > Ethernet >
# WiFi, ``helpers.py:284-315``). On TPU hosts the analogous ranking is
# ICI-attached (same slice) > DCN/Ethernet > WiFi > other.
INTERFACE_PRIORITY = {
  "ici": 50,
  "thunderbolt": 40,
  "ethernet": 30,
  "wifi": 20,
  "other": 10,
  "loopback": 5,
}


def get_interface_priority_and_type(interface_name: str) -> tuple[int, str]:
  name = interface_name.lower()
  if name.startswith("lo"):
    return INTERFACE_PRIORITY["loopback"], "loopback"
  if name.startswith(("eth", "en", "eno", "ens", "enp")):
    return INTERFACE_PRIORITY["ethernet"], "ethernet"
  if name.startswith(("wlan", "wl", "wifi")):
    return INTERFACE_PRIORITY["wifi"], "wifi"
  if "thunderbolt" in name or name.startswith("tb"):
    return INTERFACE_PRIORITY["thunderbolt"], "thunderbolt"
  return INTERFACE_PRIORITY["other"], "other"


def get_all_ip_addresses_and_interfaces() -> list[tuple[str, str]]:
  """Best-effort enumeration of (ip, interface) pairs without psutil."""
  results: list[tuple[str, str]] = []
  try:
    import socket as _socket

    hostname = _socket.gethostname()
    for info in _socket.getaddrinfo(hostname, None, _socket.AF_INET):
      ip = info[4][0]
      if ip and not ip.startswith("127."):
        results.append((ip, "ethernet"))
  except OSError:
    pass
  # Fallback: UDP-connect trick for the primary outbound interface.
  if not results:
    try:
      with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
        s.connect(("8.8.8.8", 80))
        results.append((s.getsockname()[0], "ethernet"))
    except OSError:
      pass
  if not results:
    results.append(("127.0.0.1", "loopback"))
  return list(dict.fromkeys(results))
