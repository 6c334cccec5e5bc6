"""The system under test, in this process: engine, ``Node`` and the aiohttp
application of ``api/chatgpt_api.py`` on a loopback port, as
``tests/test_e2e_serving.py::serving_stack`` builds them. One process, because
a chip belongs to one process and the profiler can only trace the process
that holds it. The server has a thread and an event loop of its own: the
scheduler waits for the device on its loop's thread, and a load generator on
the same loop would be stalled with it and read as late (seen on the chip,
PR 23). Nothing in the program is patched, wrapped or switched beyond the
serving environment the configuration file states."""

from __future__ import annotations

import asyncio
import os
import socket
import threading


class _NoDiscovery:
  async def start(self):
    pass

  async def stop(self):
    pass

  async def discover_peers(self, wait_for_peers: int = 0):
    return []


class _NoServer:
  async def start(self):
    pass

  async def stop(self):
    pass


def free_port() -> int:
  with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    return s.getsockname()[1]


def apply_serving_env(hf: dict) -> None:
  """Deployment settings a user would set, from the configuration file. Set
  before the program's modules are imported: several read them at import."""
  for k, v in hf["serving_env"].items():
    os.environ[k] = str(v)


class Stack:
  """Engine + node + API for one configuration; ``await start()`` then talk to
  ``self.url`` over HTTP."""

  def __init__(self, hf: dict, cfg, params, tokenizer):
    from xotorch_support_jetson_tpu import registry
    from xotorch_support_jetson_tpu.api.chatgpt_api import ChatGPTAPI
    from xotorch_support_jetson_tpu.inference.jax_engine import JaxShardedInferenceEngine
    from xotorch_support_jetson_tpu.inference.shard import Shard
    from xotorch_support_jetson_tpu.orchestration.node import Node
    from xotorch_support_jetson_tpu.topology.partitioning import RingMemoryWeightedPartitioningStrategy

    self.model_id = hf["model_id"]
    # The cut model is registered under its own id from the benchmark's files
    # (model_cards is a dict; registry.py is not edited).
    registry.model_cards[self.model_id] = registry.ModelCard(
      self.model_id, cfg.n_layers, hf.get("stands_for", self.model_id)[:60], hf["registry_family"], {registry.JAX_ENGINE: self.model_id}
    )
    self.engine = JaxShardedInferenceEngine(None, max_seq_len=cfg.max_seq_len, use_local_mesh=False)
    self.engine.load_test_model(Shard(self.model_id, 0, cfg.n_layers - 1, cfg.n_layers), cfg, params, tokenizer=tokenizer)
    self.node = Node(
      "bench-node", _NoServer(), self.engine, _NoDiscovery(), None, RingMemoryWeightedPartitioningStrategy(),
      max_generate_tokens=4096, default_sample_temp=0.0,
    )
    self.api = ChatGPTAPI(self.node, "JaxShardedInferenceEngine", response_timeout=300, default_model=self.model_id)
    self.port = free_port()
    self.url = f"http://127.0.0.1:{self.port}"
    self._runner = None

  async def _serve(self) -> None:
    from aiohttp import web

    await self.node.start()
    self._runner = web.AppRunner(self.api.app)
    await self._runner.setup()
    await web.TCPSite(self._runner, "127.0.0.1", self.port).start()

  async def _unserve(self) -> None:
    if self._runner is not None:
      await self._runner.cleanup()
    await self.node.stop()

  def start(self) -> None:
    """Serve from a thread of the server's own; returns once it listens."""
    self._loop = asyncio.new_event_loop()
    self._thread = threading.Thread(target=self._loop.run_forever, name="bench-server", daemon=True)
    self._thread.start()
    asyncio.run_coroutine_threadsafe(self._serve(), self._loop).result(timeout=120)

  def stop(self) -> None:
    asyncio.run_coroutine_threadsafe(self._unserve(), self._loop).result(timeout=60)
    self._loop.call_soon_threadsafe(self._loop.stop)
    self._thread.join(timeout=30)
