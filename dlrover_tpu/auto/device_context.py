"""Device context: what hardware is this search/job running on (AT8).

Parity reference: atorch/atorch/auto/device_context.py:1-203
(get_device_context — node num, nproc, GPU memory and flops feeding the
acceleration engine).

TPU shape: one cached snapshot of the accelerator fleet (platform,
chip generation, per-chip HBM and peak bf16 FLOP/s from the device
kind) plus host resources — the single source the strategy ranker
(auto/accelerate.py) and the planner share instead of each keeping
its own chip table.
"""

import dataclasses
import functools
import os
from typing import Optional, Sequence

import jax

from dlrover_tpu.common.log import default_logger as logger

#: peak dense bf16 TFLOP/s per chip by TPU generation. v5e: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s per chip); the other rows are the same pages' figures for
#: their generations
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5e": 197.0,
    "v5lite": 197.0,  # device_kind "TPU v5 lite"
    "v5p": 459.0,
    "v6e": 918.0,
    "v6": 918.0,
}

#: HBM bytes per chip by generation (same source)
HBM_BYTES = {
    "v4": 32e9,
    "v5e": 16e9,
    "v5lite": 16e9,
    "v5p": 95e9,
    "v6e": 32e9,
    "v6": 32e9,
}

#: what a device that is NOT a TPU (the CPU test mesh, fake devices in
#: strategy-search tests) is sized as, so the search has numbers to
#: rank with. No TPU run reaches these: an unknown TPU kind raises.
NON_TPU_STAND_IN_PEAK = 459.0e12
NON_TPU_STAND_IN_HBM = 95e9


def _kind_key(device) -> Optional[str]:
    kind = getattr(device, "device_kind", "").lower().replace(" ", "")
    for key in PEAK_TFLOPS:
        if key in kind:
            return key
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"TPU device kind {device.device_kind!r} is not in the "
            "peak table (auto/device_context.py); add its published "
            "figures instead of guessing"
        )
    return None


def peak_flops_per_chip(device) -> float:
    key = _kind_key(device)
    return PEAK_TFLOPS[key] * 1e12 if key else NON_TPU_STAND_IN_PEAK


def hbm_bytes_per_chip(device) -> float:
    key = _kind_key(device)
    return HBM_BYTES[key] if key else NON_TPU_STAND_IN_HBM


@dataclasses.dataclass(frozen=True)
class DeviceContext:
    """Snapshot of the fleet the strategy search targets."""

    platform: str
    device_kind: str
    num_devices: int
    num_hosts: int
    devices_per_host: int
    hbm_bytes: float  # per device
    peak_flops: float  # per device, dense bf16
    host_cpu_count: int
    host_memory_mb: int

    @property
    def total_hbm_bytes(self) -> float:
        return self.hbm_bytes * self.num_devices

    @property
    def total_peak_flops(self) -> float:
        return self.peak_flops * self.num_devices


def build_device_context(
    devices: Optional[Sequence] = None,
) -> DeviceContext:
    devices = list(devices if devices is not None else jax.devices())
    dev = devices[0]
    num_hosts = len({d.process_index for d in devices}) or 1
    try:
        import psutil  # pragma: no cover - optional

        host_mem_mb = int(psutil.virtual_memory().total / 2**20)
    except Exception:
        host_mem_mb = int(
            os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
            / 2**20
        )
    ctx = DeviceContext(
        platform=dev.platform,
        device_kind=getattr(dev, "device_kind", dev.platform),
        num_devices=len(devices),
        num_hosts=num_hosts,
        devices_per_host=len(devices) // num_hosts,
        hbm_bytes=hbm_bytes_per_chip(dev),
        peak_flops=peak_flops_per_chip(dev),
        host_cpu_count=os.cpu_count() or 1,
        host_memory_mb=host_mem_mb,
    )
    logger.info("Device context: %s", ctx)
    return ctx


@functools.lru_cache(maxsize=1)
def get_device_context() -> DeviceContext:
    """Cached context for the default jax.devices() fleet."""
    return build_device_context()
