"""Native shm ring + coworker dataloader tests.

Parity coverage for atorch's shm data-path tests (data/shm_context.py)."""

import multiprocessing as mp
import os
import time

import numpy as np
import pytest

from dlrover_tpu.data.shm_dataloader import DevicePrefetch, ShmDataLoader
from dlrover_tpu.data.shm_ring import RingClosed, ShmRing


def _name(tag):
    return f"/dlrover_test_{tag}_{os.getpid()}"


def test_ring_roundtrip_bytes():
    ring = ShmRing(_name("rt"), slot_bytes=1 << 16, num_slots=4)
    try:
        ring.push_bytes(b"hello tpu")
        assert len(ring) == 1
        assert ring.pop_bytes() == b"hello tpu"
        assert len(ring) == 0
    finally:
        ring.destroy()


def test_ring_numpy_framing_no_pickle():
    ring = ShmRing(_name("np"), slot_bytes=1 << 20, num_slots=4)
    try:
        x = np.arange(1000, dtype=np.float32).reshape(10, 100)
        y = np.arange(10, dtype=np.int64)
        ring.push((x, y))
        rx, ry = ring.pop()
        np.testing.assert_array_equal(rx, x)
        np.testing.assert_array_equal(ry, y)
        # arbitrary pytrees fall back to pickle
        ring.push({"a": x, "b": [1, 2]})
        out = ring.pop()
        np.testing.assert_array_equal(out["a"], x)
    finally:
        ring.destroy()


def test_ring_capacity_blocks_and_times_out():
    ring = ShmRing(_name("cap"), slot_bytes=1 << 10, num_slots=2)
    try:
        ring.push_bytes(b"a")
        ring.push_bytes(b"b")
        with pytest.raises(TimeoutError):
            ring.push_bytes(b"c", timeout_ms=200)
        assert ring.pop_bytes() == b"a"
        ring.push_bytes(b"c", timeout_ms=200)  # space freed
    finally:
        ring.destroy()


def test_ring_oversize_payload_rejected():
    ring = ShmRing(_name("big"), slot_bytes=64, num_slots=2)
    try:
        with pytest.raises(ValueError):
            ring.push_bytes(b"x" * 100)
    finally:
        ring.destroy()


def test_close_drains_then_raises():
    ring = ShmRing(_name("close"), slot_bytes=1 << 10, num_slots=4)
    try:
        ring.push_bytes(b"last")
        ring.close()
        assert ring.pop_bytes() == b"last"
        with pytest.raises(RingClosed):
            ring.pop_bytes(timeout_ms=1000)
    finally:
        ring.destroy()


def _producer_proc(name):
    ring = ShmRing.attach(name)
    for i in range(20):
        ring.push(np.full((4, 4), i, dtype=np.int32))


def test_cross_process_transport():
    name = _name("xproc")
    ring = ShmRing(name, slot_bytes=1 << 20, num_slots=4)
    try:
        ctx = mp.get_context("spawn")
        p = ctx.Process(target=_producer_proc, args=(name,))
        p.start()
        got = [int(ring.pop(timeout_ms=30_000)[0, 0]) for _ in range(20)]
        p.join(timeout=10)
        assert got == list(range(20))
    finally:
        ring.destroy()


def _batches():
    for i in range(12):
        yield np.full((2, 3), i, dtype=np.float32)


def test_shm_dataloader_end_to_end():
    loader = ShmDataLoader(_batches, num_workers=2,
                           slot_bytes=1 << 20, num_slots=4)
    try:
        seen = sorted(int(b[0, 0]) for b in loader)
        assert seen == list(range(12))
    finally:
        loader.shutdown()


def test_device_prefetch_preserves_order():
    prefetched = list(DevicePrefetch(_batches(), depth=3))
    assert [int(np.asarray(b)[0, 0]) for b in prefetched] == list(
        range(12)
    )


def test_push_waits_on_while_told_to():
    """A ring still full when a push's wait ends is a consumer busy
    elsewhere (a step program compiles for minutes): the coworkers'
    push asks whether to wait that long again, and gives up only when
    told (PR 34: the producers of a 16k-token cell died after 60 s of
    a 100 s set-up)."""
    ring = ShmRing(_name("wait"), slot_bytes=1024, num_slots=1)
    try:
        ring.push(np.zeros(4, np.int32))
        asked = []

        def keep_waiting():
            asked.append(len(asked))
            if len(asked) == 2:
                ring.pop()  # the consumer comes back
            return True

        ring.push(np.ones(4, np.int32), timeout_ms=50,
                  keep_waiting=keep_waiting)
        assert asked == [0, 1]
        np.testing.assert_array_equal(ring.pop(), np.ones(4, np.int32))
        ring.push(np.zeros(4, np.int32))
        with pytest.raises(TimeoutError):
            ring.push(np.ones(4, np.int32), timeout_ms=50,
                      keep_waiting=lambda: False)
        with pytest.raises(TimeoutError):
            ring.push(np.ones(4, np.int32), timeout_ms=50)
    finally:
        ring.destroy()
