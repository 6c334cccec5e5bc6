"""Device capability probes (topology/device_capabilities.py).

The heterogeneous parsing helpers are pure functions (reference parity:
``device_capabilities.py:166-384`` probes Apple/CUDA/Jetson) — tested here
without the hardware; the live probe path is exercised for whatever this CI
host actually is (CPU or TPU)."""

from xotorch_support_jetson_tpu.topology.device_capabilities import (
  DeviceCapabilities,
  apple_caps_from,
  cuda_caps_from,
  device_capabilities_sync,
  jetson_caps_from,
)


def test_cuda_caps_lookup_and_scaling():
  caps = cuda_caps_from("NVIDIA GeForce RTX 4090", 24 * 1024**3, n_devices=2)
  assert caps.memory == 2 * 24 * 1024
  assert caps.flops.fp16 == 2 * 165.2
  assert "2x" in caps.model
  unknown = cuda_caps_from("NVIDIA Mystery GPU", 8 * 1024**3)
  assert unknown.flops.fp16 == 0 and unknown.memory == 8 * 1024


def test_jetson_caps_from_meminfo():
  meminfo = "MemTotal:       32412345 kB\nMemFree:        100 kB\n"
  caps = jetson_caps_from("Jetson AGX Orin Developer Kit", meminfo)
  assert caps.memory == 32412345 // 1024
  assert caps.flops.int8 == 170.0  # matched "jetson agx orin"


def test_apple_caps_lookup_prefers_most_specific():
  pro = apple_caps_from("Apple M2 Pro", 16 * 1024)
  base = apple_caps_from("Apple M2", 8 * 1024)
  assert pro.flops.fp16 == 13.6 and base.flops.fp16 == 7.2  # "m2 pro" != "m2"


def test_live_probe_returns_something_sane():
  caps = device_capabilities_sync()
  assert isinstance(caps, DeviceCapabilities)
  assert caps.memory > 0
  assert caps.chip
  # Round-trips through the wire dict format.
  assert DeviceCapabilities.from_dict(caps.to_dict()).memory == caps.memory


def test_tpu_kind_lookup_is_exact_or_an_error():
  """The chip's ``device_kind`` ("TPU v5 lite" on a v5e) resolves to its
  published peaks; a kind the table does not hold raises instead of
  reporting a zero-FLOP host."""
  import pytest

  from xotorch_support_jetson_tpu.topology.device_capabilities import _lookup_tpu_flops

  assert _lookup_tpu_flops("TPU v5 lite").fp16 == 197.0
  assert _lookup_tpu_flops("TPU v5p").fp16 == 459.0
  with pytest.raises(ValueError, match="unknown TPU device_kind"):
    _lookup_tpu_flops("TPU v99")
