"""The trace reduction on hand-made cases and on a recorded
excerpt of a chip trace."""

import gzip
import json
import os

import pytest

from yardstick import reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_subtract():
    merged = reduce.union([(0, 2), (1, 3), (5, 6)])
    assert merged == [(0, 3), (5, 6)]
    assert reduce.total(merged) == 4
    assert reduce.subtract([(0, 10)], merged) == [(3, 5), (6, 10)]
    assert reduce.subtract([(0, 3), (4, 8)], [(2, 5)]) == [
        (0, 2), (5, 8)]


def test_leaves_drop_the_event_that_holds_others():
    events = [
        ("while.1", 0.0, 10.0),   # spans its body
        ("fusion.1", 0.0, 4.0),
        ("fusion.2", 5.0, 4.0),
        ("copy.3", 11.0, 1.0),
    ]
    assert [n for n, _, _ in reduce.leaves(events)] == [
        "fusion.1", "fusion.2", "copy.3"]


def test_collective_names():
    for name in ("all-gather-start.3", "all-reduce.12",
                 "%reduce-scatter.1", "collective-permute-done",
                 "all-to-all.7"):
        assert reduce.is_collective(name), name
    for name in ("fusion.3", "all-gather-fusion", "copy.1"):
        assert not reduce.is_collective(name), name


def two_devices():
    """Two chips, two steps in a window of 10 s each.

    chip 0: compute 0-4, all-gather 4-5 (exposed, 1 s), compute 5-8,
            idle 8-9 (the host fetching a batch), compute 9-10
    chip 1: compute 0-4, all-gather 3-6 (3 s, of which 3-4 is hidden
            behind compute: 2 s exposed), compute 6-10
    """
    return {
        "devices": {
            "/device:TPU:0": [
                ("fusion.1", 0.0, 4.0), ("all-gather.1", 4.0, 1.0),
                ("fusion.2", 5.0, 3.0), ("fusion.1", 9.0, 1.0),
            ],
            "/device:TPU:1": [
                ("fusion.1", 0.0, 4.0), ("all-gather.1", 3.0, 3.0),
                ("fusion.2", 6.0, 4.0),
            ],
        },
        "host": [
            ("yardstick.step", 0.0, 10.0),
            ("yardstick.next_batch", 7.9, 1.2),
            ("yardstick.dispatch", 9.1, 0.1),
        ],
    }


def test_two_device_case_by_hand():
    got = reduce.reduce(two_devices(), steps=2)
    assert got["devices"] == 2 and got["steps"] == 2
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx((9.0 + 10.0) / 2)
    assert got["collective_exposed_s"] == pytest.approx((1.0 + 2.0) / 2)
    ops = {name: (t, n) for name, t, n in got["ops"]}
    assert ops["fusion.1"] == (pytest.approx(4.5), 1)
    assert ops["all-gather.1"] == (pytest.approx(2.0), 1)
    assert got["ops"][0][0] == "fusion.1"  # by time
    assert got["idle_gaps"] == [
        ["yardstick.next_batch", pytest.approx(1.0)]]


def test_gap_labels():
    host = two_devices()["host"]
    assert reduce.label_gap((8.0, 9.0), host) == "yardstick.next_batch"
    assert reduce.label_gap((9.05, 9.2), host) == "yardstick.dispatch"
    # only the step annotation reaches it
    assert reduce.label_gap((2.0, 3.0), host) == "yardstick.step"
    assert reduce.label_gap((20.0, 21.0), host) == "host:unannotated"


def test_no_device_plane_gives_nothing():
    assert reduce.reduce({"devices": {}, "host": []}, steps=3) is None


def test_find_xplane_says_what_is_missing(tmp_path):
    with pytest.raises(FileNotFoundError):
        reduce.find_xplane(str(tmp_path))


@pytest.fixture(scope="module")
def excerpt():
    path = os.path.join(HERE, "data", "trace_excerpt_v5e.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_step(excerpt):
    """One step of ``mistral-7b-l4.steady`` as the chip recorded it
    (PR 25): 864 events, of which two ``while`` loops span 768."""
    events = excerpt["devices"]["/device:TPU:0"]
    assert len(events) == 864
    kept = reduce.leaves(events)
    assert not [n for n, _, _ in kept if n.startswith("while")]
    got = reduce.reduce(excerpt, steps=1)
    assert got["window_s"] == pytest.approx(0.600793742, abs=1e-9)
    # the two loops alone are 0.502 s: counted once, not twice
    assert got["busy_s"] == pytest.approx(0.600783727, abs=1e-8)
    assert got["busy_s"] <= got["window_s"]
    assert got["collective_exposed_s"] == 0.0
    # forward, dq and dkv kernels, once a layer (4 layers)
    flash = [row for row in got["ops"]
             if row[0].startswith("flash_attention")]
    assert sorted(n for _, _, n in flash) == [4, 4, 4]
    assert sum(t for _, t, _ in flash) == pytest.approx(
        0.062519806, abs=1e-8)
    assert got["idle_gaps"][0][0] == "yardstick.wait"


def test_recorded_step_through_the_metric_readers(excerpt):
    from yardstick import cells
    from yardstick.layer_metrics import (
        attn_kernel_ms, attn_roofline_pct, device_idle_pct)

    trace = reduce.reduce(excerpt, steps=1)
    _, config, traffic = cells.load_cell("mistral-7b-l4.steady")
    run = {"trace": trace, "config": config, "traffic": traffic,
           "cell": {"chips": 1}, "peak": cells.peak_of("TPU v5 lite")}
    assert attn_kernel_ms.read(run) == pytest.approx(62.519806)
    # 7 x 4 x 3 x 32 x 4096^2 x 128 operations at 197 TFLOP/s are
    # 29.30 ms: the least the chip could take, compute-bound
    least, bound = attn_roofline_pct.least_seconds(run)
    assert (round(least * 1e3, 2), bound) == (29.3, "compute")
    assert attn_roofline_pct.read(run) == pytest.approx(
        100 * least / 0.062519806)
    assert 0 <= device_idle_pct.read(run) < 0.01


def test_short_name():
    text = ("%fusion.341 = bf16[3,4096,4096]{2,1,0:T(8,128)(2,1)} "
            "fusion(bf16[4096,14336]{1,0} %x), kind=kOutput")
    assert reduce.short_name(text) == "fusion.341 bf16[3,4096,4096]"
    text = ("%flash_attention.29 = (bf16[24,4,4096,128]{3,2,1,0}, "
            "f32[24,4,1,4096]{3,2,1,0}) custom-call(...)")
    assert reduce.short_name(text) == (
        "flash_attention.29 (bf16[24,4,4096,128]")
    assert reduce.short_name("all-gather-start.3") == (
        "all-gather-start.3")
    assert reduce.is_collective(reduce.short_name(
        "%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) "
        "all-gather-start(%p)"))
