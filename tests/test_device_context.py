"""Device context tests (AT8 parity: auto/device_context.py)."""

import jax
import pytest

from dlrover_tpu.auto.device_context import (
    DeviceContext,
    build_device_context,
    hbm_bytes_per_chip,
    peak_flops_per_chip,
)


class FakeDev:
    def __init__(self, kind, platform="tpu", process_index=0):
        self.device_kind = kind
        self.platform = platform
        self.process_index = process_index


def test_chip_tables():
    assert peak_flops_per_chip(FakeDev("TPU v5 lite")) == 197.0e12
    assert hbm_bytes_per_chip(FakeDev("TPU v5 lite")) == 16e9
    assert peak_flops_per_chip(FakeDev("TPU v5p")) == 459.0e12
    assert hbm_bytes_per_chip(FakeDev("TPU v4")) == 32e9
    # an unknown TPU is an error, not a v5p
    with pytest.raises(ValueError, match="v9 mega"):
        peak_flops_per_chip(FakeDev("TPU v9 mega"))


def test_build_context_counts_hosts():
    devs = [FakeDev("TPU v5e", process_index=i // 4) for i in range(8)]
    ctx = build_device_context(devs)
    assert ctx.num_devices == 8
    assert ctx.num_hosts == 2
    assert ctx.devices_per_host == 4
    assert ctx.total_hbm_bytes == 8 * 16e9
    assert ctx.host_cpu_count >= 1
    assert ctx.host_memory_mb > 0


def test_build_context_real_devices():
    ctx = build_device_context(jax.devices())
    assert isinstance(ctx, DeviceContext)
    assert ctx.num_devices == len(jax.devices())
