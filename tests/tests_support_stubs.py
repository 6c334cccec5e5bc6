"""Shared test stubs (importable from any test module)."""

from xotorch_support_jetson_tpu.networking.discovery import Discovery


class NoDiscovery(Discovery):
  async def start(self):
    pass

  async def stop(self):
    pass

  async def discover_peers(self, wait_for_peers: int = 0):
    return []


class StubServer:
  async def start(self):
    pass

  async def stop(self):
    pass
