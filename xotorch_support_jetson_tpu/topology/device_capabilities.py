"""Device capability probing — TPU-native.

Capability parity with reference ``xotorch/topology/device_capabilities.py``
(pydantic ``DeviceCapabilities`` model :35-49, hardcoded ``CHIP_FLOPS`` table
:54-163, per-OS async probes :166-384). The reference probes Apple silicon,
CUDA GPUs and Jetson boards; here the first-class citizen is the TPU: chip
kind, count, and per-chip HBM come from live JAX runtime metadata
(``jax.devices()``, ``device.memory_stats()``), with a small public-spec
TFLOPS table for capability *estimates* (used only for placement weighting
and viz, never for correctness). A host with no accelerator is described
from ``os.sysconf``.
"""

from __future__ import annotations

import os
from dataclasses import asdict, dataclass

from ..utils.helpers import DEBUG, device_memory

TFLOPS = 1.0


@dataclass(frozen=True)
class DeviceFlops:
  # units: TFLOPS
  fp32: float
  fp16: float
  int8: float

  def to_dict(self) -> dict:
    return asdict(self)


@dataclass
class DeviceCapabilities:
  model: str
  chip: str
  memory: int  # MB
  flops: DeviceFlops

  def __str__(self) -> str:
    return f"Model: {self.model}. Chip: {self.chip}. Memory: {self.memory}MB. Flops: fp32 {self.flops.fp32:.2f} TFLOPS, fp16 {self.flops.fp16:.2f} TFLOPS, int8 {self.flops.int8:.2f} TFLOPS"

  def model_dump(self) -> dict:
    return {"model": self.model, "chip": self.chip, "memory": self.memory, "flops": self.flops.to_dict()}

  def to_dict(self) -> dict:
    return self.model_dump()

  @classmethod
  def from_dict(cls, data: dict) -> "DeviceCapabilities":
    flops = data.get("flops", {})
    if isinstance(flops, DeviceFlops):
      pass
    else:
      flops = DeviceFlops(fp32=flops.get("fp32", 0), fp16=flops.get("fp16", 0), int8=flops.get("int8", 0))
    return cls(model=data.get("model", "Unknown"), chip=data.get("chip", "Unknown"), memory=data.get("memory", 0), flops=flops)


UNKNOWN_DEVICE_CAPABILITIES = DeviceCapabilities(model="Unknown Model", chip="Unknown Chip", memory=0, flops=DeviceFlops(fp32=0, fp16=0, int8=0))

# Public-spec peak compute per TPU chip generation (bf16 dense, int8 where
# published). Estimates for placement weighting only — analogous in role to
# the reference's CHIP_FLOPS table (device_capabilities.py:54-163) but keyed
# on jax device_kind strings instead of GPU marketing names.
TPU_CHIP_FLOPS: dict[str, DeviceFlops] = {
  "tpu v2": DeviceFlops(fp32=11.5, fp16=23.0, int8=46.0),
  "tpu v3": DeviceFlops(fp32=61.5, fp16=123.0, int8=246.0),
  "tpu v4": DeviceFlops(fp32=137.5, fp16=275.0, int8=275.0),
  "tpu v5 lite": DeviceFlops(fp32=98.5, fp16=197.0, int8=394.0),
  "tpu v5e": DeviceFlops(fp32=98.5, fp16=197.0, int8=394.0),
  "tpu v5": DeviceFlops(fp32=229.5, fp16=459.0, int8=918.0),
  "tpu v5p": DeviceFlops(fp32=229.5, fp16=459.0, int8=918.0),
  "tpu v6 lite": DeviceFlops(fp32=459.0, fp16=918.0, int8=1836.0),
  "tpu v6e": DeviceFlops(fp32=459.0, fp16=918.0, int8=1836.0),
  "tpu7x": DeviceFlops(fp32=1153.0, fp16=2307.0, int8=4614.0),
}

def _lookup_tpu_flops(device_kind: str) -> DeviceFlops:
  """Peak-compute estimate for a TPU ``device_kind``. An unknown kind is an
  error, not a zero: a ring would silently weight this host as having no
  compute at all."""
  kind = device_kind.lower().strip()
  for key in sorted(TPU_CHIP_FLOPS, key=len, reverse=True):
    if kind.startswith(key) or key in kind:
      return TPU_CHIP_FLOPS[key]
  raise ValueError(f"unknown TPU device_kind {device_kind!r}: add it to TPU_CHIP_FLOPS (topology/device_capabilities.py)")


def _host_memory_mb() -> int:
  try:
    pages = os.sysconf("SC_PHYS_PAGES")
    page_size = os.sysconf("SC_PAGE_SIZE")
    return int(pages * page_size / (1024 * 1024))
  except (ValueError, OSError):
    return 0


def _tpu_device_capabilities() -> DeviceCapabilities | None:
  """Capabilities of the local TPU chips; None when JAX runs on anything
  else (the later probes then describe the host). On a TPU nothing is
  assumed: the kind must be in the table and the runtime must report the
  chip's ``bytes_limit``."""
  devices = [d for d in device_memory() if d["platform"] == "tpu"]
  if not devices:
    return None
  kind = devices[0]["kind"]
  flops = _lookup_tpu_flops(kind)
  if not devices[0]["bytes_limit"]:
    raise RuntimeError(f"TPU {kind!r} reports no bytes_limit in memory_stats()")
  per_chip_mb = int(devices[0]["bytes_limit"] / (1024 * 1024))
  n = len(devices)
  return DeviceCapabilities(
    model=f"TPU host ({n}x {kind})",
    chip=kind,
    memory=per_chip_mb * n,
    flops=DeviceFlops(fp32=flops.fp32 * n, fp16=flops.fp16 * n, int8=flops.int8 * n),
  )


# --------------------------------------------- heterogeneous peers
#
# The gRPC ring admits non-TPU peers (the reference's whole deployment
# story); memory-weighted partitioning then needs THEIR capabilities too, or
# a mixed ring mis-weights every layer split. Public-spec estimates for the
# common chips (fp16 dense TFLOPS; role-parity with the reference's
# CHIP_FLOPS table, independently keyed/valued) + thin probes with the
# parsing split into pure functions so they're testable without hardware.

GPU_CHIP_FLOPS: dict[str, DeviceFlops] = {
  "nvidia h100": DeviceFlops(fp32=67.0, fp16=989.0, int8=1979.0),
  "nvidia a100": DeviceFlops(fp32=19.5, fp16=312.0, int8=624.0),
  "nvidia geforce rtx 4090": DeviceFlops(fp32=82.6, fp16=165.2, int8=660.6),
  "nvidia geforce rtx 4080": DeviceFlops(fp32=48.7, fp16=97.5, int8=390.0),
  "nvidia geforce rtx 3090": DeviceFlops(fp32=35.6, fp16=71.0, int8=284.0),
  "nvidia geforce rtx 3080": DeviceFlops(fp32=29.8, fp16=59.5, int8=238.0),
  "jetson agx orin": DeviceFlops(fp32=5.3, fp16=10.6, int8=170.0),
  "jetson orin nano": DeviceFlops(fp32=1.3, fp16=2.6, int8=20.0),
  "jetson": DeviceFlops(fp32=1.0, fp16=2.0, int8=10.0),  # unlisted-board floor
}

APPLE_CHIP_FLOPS: dict[str, DeviceFlops] = {
  "apple m1": DeviceFlops(fp32=2.6, fp16=5.2, int8=10.4),
  "apple m1 pro": DeviceFlops(fp32=5.2, fp16=10.4, int8=20.8),
  "apple m1 max": DeviceFlops(fp32=10.4, fp16=20.8, int8=41.6),
  "apple m2": DeviceFlops(fp32=3.6, fp16=7.2, int8=14.4),
  "apple m2 pro": DeviceFlops(fp32=6.8, fp16=13.6, int8=27.2),
  "apple m2 max": DeviceFlops(fp32=13.5, fp16=27.0, int8=54.0),
  "apple m3": DeviceFlops(fp32=4.1, fp16=8.2, int8=16.4),
  "apple m3 pro": DeviceFlops(fp32=7.4, fp16=14.8, int8=29.6),
  "apple m3 max": DeviceFlops(fp32=16.3, fp16=32.6, int8=65.2),
  "apple m4": DeviceFlops(fp32=4.6, fp16=9.2, int8=18.4),
}


def _match_flops(table: dict[str, DeviceFlops], name: str) -> DeviceFlops:
  name = name.lower().strip()
  for key in sorted(table, key=len, reverse=True):  # most specific first
    if key in name:
      return table[key]
  return DeviceFlops(fp32=0, fp16=0, int8=0)


def cuda_caps_from(name: str, total_memory_bytes: int, n_devices: int = 1) -> DeviceCapabilities:
  flops = _match_flops(GPU_CHIP_FLOPS, name)
  return DeviceCapabilities(
    model=f"GPU host ({n_devices}x {name})",
    chip=name,
    memory=int(total_memory_bytes / (1024 * 1024)) * n_devices,
    flops=DeviceFlops(fp32=flops.fp32 * n_devices, fp16=flops.fp16 * n_devices, int8=flops.int8 * n_devices),
  )


def jetson_caps_from(model: str, meminfo: str) -> DeviceCapabilities:
  """Jetson boards share system RAM with the GPU — memory comes from
  /proc/meminfo MemTotal (the reference special-cases this the same way)."""
  mem_mb = 0
  for line in meminfo.splitlines():
    if line.startswith("MemTotal:"):
      mem_mb = int(line.split()[1]) // 1024
      break
  return DeviceCapabilities(model=model, chip=model.lower(), memory=mem_mb, flops=_match_flops(GPU_CHIP_FLOPS, model))


def apple_caps_from(chip: str, memory_mb: int) -> DeviceCapabilities:
  return DeviceCapabilities(model=f"Apple ({chip})", chip=chip, memory=memory_mb, flops=_match_flops(APPLE_CHIP_FLOPS, chip))


def _jetson_device_capabilities() -> DeviceCapabilities | None:
  try:
    if not os.path.exists("/etc/nv_tegra_release"):
      return None
    model = "Jetson"
    try:
      with open("/proc/device-tree/model") as f:
        model = f.read().strip("\x00 \n")
    except OSError:
      pass
    with open("/proc/meminfo") as f:
      return jetson_caps_from(model, f.read())
  except Exception:  # noqa: BLE001
    return None


def _cuda_device_capabilities() -> DeviceCapabilities | None:
  try:
    import torch

    if not torch.cuda.is_available():
      return None
    props = torch.cuda.get_device_properties(0)
    return cuda_caps_from(props.name, props.total_memory, torch.cuda.device_count())
  except Exception:  # noqa: BLE001 — torch absent or CUDA runtime broken
    return None


def _apple_device_capabilities() -> DeviceCapabilities | None:
  import platform

  if platform.system() != "Darwin":
    return None
  try:
    import subprocess

    chip = subprocess.run(["sysctl", "-n", "machdep.cpu.brand_string"], capture_output=True, text=True, timeout=5).stdout.strip()
    mem = int(subprocess.run(["sysctl", "-n", "hw.memsize"], capture_output=True, text=True, timeout=5).stdout.strip()) // (1024 * 1024)
    caps = apple_caps_from(chip, mem)
    if caps.flops.fp16 == 0:
      return None  # Intel Mac / unknown chip: fall through to the CPU estimate
    return caps
  except Exception:  # noqa: BLE001
    return None


def _probe() -> DeviceCapabilities:
  caps = None
  for probe in (_tpu_device_capabilities, _jetson_device_capabilities, _cuda_device_capabilities, _apple_device_capabilities):
    caps = probe()
    if caps is not None:
      break
  if caps is None:
    caps = DeviceCapabilities(
      model=f"CPU host ({os.uname().machine})" if hasattr(os, "uname") else "CPU host",
      chip="cpu",
      memory=_host_memory_mb(),
      flops=DeviceFlops(fp32=0.1, fp16=0.1, int8=0.2),
    )
  # Test/drill override: report a fixed memory (MB) regardless of the probe —
  # lets a drill stand up a deliberately undersized ring member to exercise
  # the ahead-of-time ring HBM refusal (scripts/ring_budget_drill.sh).
  override = os.getenv("XOT_TPU_MEMORY_MB")
  if override:
    caps = DeviceCapabilities(model=caps.model, chip=caps.chip, memory=int(override), flops=caps.flops)
  return caps


async def device_capabilities() -> DeviceCapabilities:
  """Probe this host's accelerator (TPU → Jetson → CUDA → Apple → CPU)."""
  caps = _probe()
  if DEBUG >= 2:
    print(f"[device_capabilities] {caps}")
  return caps


def device_capabilities_sync() -> DeviceCapabilities:
  return _probe()
