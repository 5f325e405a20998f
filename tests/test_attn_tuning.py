"""Persistent kernel autotuner (ops/tuning.py).

Runs entirely on CPU (interpret mode): the measure path is stubbed
where a test needs to prove it does or does not run, so no TPU is
required for full coverage of the cache-key, persistence, and
fallback contracts.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.ops import tuning
from dlrover_tpu.ops.attention import flash_attention, mha_reference
from dlrover_tpu.trainer import profiler


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    # the tuning cache lives in tuning/ below the compile cache dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    d = str(tmp_path / "tuning")
    tuning.reset_cache_memo()
    yield d
    tuning.reset_cache_memo()


def _key(**over):
    base = dict(
        kernel="flash_attention", seq=2048, head_dim=64, gqa_group=8,
        dtype="bfloat16", causal=True, device_kind="TPU v5e",
    )
    base.update(over)
    return tuning.TuningKey(**base)


# ------------------------------------------------------------------ keys


def test_cache_key_roundtrip():
    key = _key()
    assert tuning.TuningKey.from_dict(key.to_dict()) == key
    # json round-trip (what the cache file stores)
    assert tuning.TuningKey.from_dict(
        json.loads(json.dumps(key.to_dict()))
    ) == key


def test_cache_key_filename_stable_and_distinct():
    a, b = _key(), _key()
    assert a.filename() == b.filename()
    assert _key(seq=4096).filename() != a.filename()
    assert _key(causal=False).filename() != a.filename()
    assert _key(device_kind="TPU v4").filename() != a.filename()
    # filesystem-safe despite spaces in device_kind
    assert "/" not in a.filename() and " " not in a.filename()


def test_heuristic_matches_pre_tuning_logic():
    # g=1: full 1024x1024; g=8: q rows capped at 128
    assert tuning.heuristic_blocks(2048, 1) == (1024, 1024)
    assert tuning.heuristic_blocks(2048, 8) == (128, 1024)
    # caller cap below the 128 minimum -> no candidates -> XLA path
    assert tuning.heuristic_blocks(2048, 1, block_q=64) is None
    # nothing divides a non-pow2-multiple seq
    assert tuning.heuristic_blocks(100, 1) is None


def test_candidate_grid_heuristic_first():
    grid = tuning.candidate_grid(2048, 8)
    assert grid[0] == tuning.heuristic_blocks(2048, 8)
    assert len(set(grid)) == len(grid)


# ------------------------------------------------------- persistence


def test_store_lookup_roundtrip(cache_dir):
    cache = tuning.get_cache()
    key = _key()
    assert cache.lookup(key) is None
    cache.store(key, (256, 512), measured_ms=1.25)
    assert cache.lookup(key) == (256, 512)
    # a FRESH handle (restarted worker) reads it from disk
    fresh = tuning.TuningCache(cache.path)
    assert fresh.lookup(key) == (256, 512)
    assert fresh.entries() == 1


def test_corrupt_entry_is_a_miss_not_an_error(cache_dir):
    cache = tuning.get_cache()
    key = _key()
    path = os.path.join(cache.path, key.filename())
    with open(path, "w") as f:
        f.write("{not json")
    assert cache.lookup(key) is None  # no raise
    # schema-mismatched and block-invalid entries also miss
    for bad in (
        {"version": 99, "key": key.to_dict(), "block_q": 128,
         "block_k": 128},
        {"version": 1, "key": key.to_dict(), "block_q": 999,
         "block_k": 128},
        {"version": 1, "key": _key(seq=4096).to_dict(),
         "block_q": 128, "block_k": 128},
    ):
        with open(path, "w") as f:
            json.dump(bad, f)
        assert tuning.TuningCache(cache.path).lookup(key) is None


def test_corrupt_entry_falls_back_to_heuristic(cache_dir, monkeypatch):
    """get_blocks over a corrupt entry: no raise, the sweep runs
    again, and when only the prior could be timed it comes back."""
    key_file = _key(device_kind="cpu", dtype="float32")
    cache = tuning.get_cache()
    with open(os.path.join(cache.path, key_file.filename()), "w") as f:
        f.write("garbage")
    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: True)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [type("D", (), {"device_kind": "cpu"})()],
    )
    monkeypatch.setattr(
        tuning, "measure_candidates",
        lambda key, cands: [(*cands[0], 1.0)],  # only the prior timed
    )
    blocks = tuning.get_blocks(
        seq=2048, head_dim=64, group=8, dtype="float32", causal=True
    )
    assert blocks == tuning.heuristic_blocks(2048, 8)


def test_untrusted_dir_degrades_to_memory_only(tmp_path, monkeypatch):
    d = tmp_path / "loose"
    d.mkdir()
    real_stat = os.stat

    class FakeStat:
        def __init__(self, st):
            self.st_uid = st.st_uid + 1  # someone else's dir
            self.st_mode = st.st_mode

    monkeypatch.setattr(
        os, "stat",
        lambda p, *a, **k: FakeStat(real_stat(p, *a, **k))
        if str(p) == str(d) else real_stat(p, *a, **k),
    )
    tuning.reset_cache_memo()
    cache = tuning.get_cache(str(d))
    assert cache.path is None  # refused, no persistence
    key = _key()
    cache.store(key, (128, 128))
    assert cache.lookup(key) == (128, 128)  # memory still works
    assert not list(d.iterdir())
    tuning.reset_cache_memo()


def test_adopted_loose_dir_is_tightened(tmp_path):
    from dlrover_tpu.common.cachedir import ensure_private_dir

    d = str(tmp_path / "world_readable")
    os.makedirs(d, mode=0o755)
    os.chmod(d, 0o755)  # defeat umask
    assert ensure_private_dir(d) == d
    assert (os.stat(d).st_mode & 0o777) == 0o700


# ------------------------------------------------------------ get_blocks


def test_cpu_path_never_measures(cache_dir, monkeypatch):
    """Off-TPU the autotuner must do ZERO timing runs and return the
    exact heuristic answer (the bitwise-identity contract)."""

    def boom(*a, **k):
        raise AssertionError("measure path entered on CPU")

    monkeypatch.setattr(tuning, "measure_candidates", boom)
    monkeypatch.setattr(tuning, "timeit", boom)
    blocks = tuning.get_blocks(
        seq=2048, head_dim=64, group=8, dtype="bfloat16", causal=True
    )
    assert blocks == tuning.heuristic_blocks(2048, 8)
    # and the full attention op still matches the XLA reference
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(flash_attention(q, k, v)),
        np.asarray(mha_reference(q, k, v)),
    )


def test_persisted_winner_honored_without_remeasure(cache_dir,
                                                   monkeypatch):
    """First call measures and persists; a second construction (fresh
    in-memory state, same host dir) reads the winner from disk and the
    measure path is NOT re-entered."""
    calls = []

    def fake_measure(key, cands):
        calls.append(key)
        return [(bq, bk, 1.0 + i) for i, (bq, bk) in enumerate(cands)]

    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: True)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [type("D", (), {"device_kind": "TPU v5e"})()],
    )
    monkeypatch.setattr(tuning, "measure_candidates", fake_measure)

    kwargs = dict(
        seq=2048, head_dim=64, group=8, dtype="bfloat16", causal=True
    )
    first = tuning.get_blocks(**kwargs)
    assert len(calls) == 1
    # fake timings make the first candidate (the heuristic) fastest
    assert first == tuning.candidate_grid(2048, 8)[0]
    assert tuning.get_cache().entries() == 1

    # simulate a restarted worker: drop ALL in-process state
    tuning.reset_cache_memo()
    second = tuning.get_blocks(**kwargs)
    assert second == first
    assert len(calls) == 1, "measure path re-entered despite cache"
    sel = tuning.last_selection()
    assert sel["source"] == "cache"
    assert (sel["block_q"], sel["block_k"]) == first


def test_winner_is_fastest_candidate(cache_dir, monkeypatch):
    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: True)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [type("D", (), {"device_kind": "TPU v5e"})()],
    )
    grid = tuning.candidate_grid(1024, 1)
    want = grid[len(grid) // 2]

    def fake_measure(key, cands):
        return [
            (bq, bk, 0.5 if (bq, bk) == want else 2.0)
            for bq, bk in cands
        ]

    monkeypatch.setattr(tuning, "measure_candidates", fake_measure)
    got = tuning.get_blocks(
        seq=1024, head_dim=64, group=1, dtype="bfloat16", causal=True
    )
    assert got == want
    assert tuning.last_selection()["source"] == "measured"


def test_tuning_event_reaches_profiler(cache_dir, monkeypatch):
    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: True)
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: [type("D", (), {"device_kind": "TPU v5e"})()],
    )
    monkeypatch.setattr(
        tuning, "measure_candidates",
        lambda key, cands: [(bq, bk, 1.0) for bq, bk in cands],
    )
    before = len(profiler.tuning_events())
    tuning.get_blocks(
        seq=512, head_dim=128, group=2, dtype="float32", causal=False
    )
    events = profiler.tuning_events()
    assert len(events) == before + 1
    evt = events[-1]
    assert evt["kernel"] == "flash_attention"
    assert evt["seq"] == 512 and evt["source"] == "measured"


def test_caller_caps_join_the_filter(cache_dir):
    # an explicit cap below every valid block -> None (XLA fallback)
    assert tuning.get_blocks(
        seq=2048, head_dim=64, group=1, dtype="bfloat16", causal=True,
        block_q=32,
    ) is None


# ------------------------------------------- measuring from inside a trace


def _tpu_like(monkeypatch):
    """Measurement forced on, on the interpret-mode kernel."""
    monkeypatch.setattr(tuning, "_measurement_enabled", lambda: True)
    real = tuning.measure_candidates
    monkeypatch.setattr(
        tuning, "measure_candidates",
        lambda key, cands: real(key, cands[:1], n=1, warmup=1),
    )


def test_sweep_reached_from_inside_jit_measures_outside_the_trace(
    cache_dir, monkeypatch
):
    """flash_attention is jitted and calls get_blocks in its body, so
    the sweep always starts inside a trace; its inputs and timings
    must be concrete all the same, and the winner must say so."""
    _tpu_like(monkeypatch)
    seen = []
    real_timeit = tuning.timeit

    def spy(fn, *args, **kw):
        seen.append([type(a).__name__ for a in args])
        t = real_timeit(fn, *args, **kw)
        assert isinstance(t, float) and t > 0
        return t

    monkeypatch.setattr(tuning, "timeit", spy)

    @jax.jit
    def step(x):
        blocks = tuning.get_blocks(
            seq=128, head_dim=64, group=1, dtype="float32",
            causal=True,
        )
        assert blocks == (128, 128)
        return x * 2

    jax.grad(lambda x: step(x).sum())(jnp.ones(4))
    assert seen and all(
        "Tracer" not in name for names in seen for name in names
    )
    sel = tuning.last_selection()
    assert sel["source"] == "measured" and sel["seq"] == 128
    assert len(os.listdir(cache_dir)) == 1


def test_sweep_with_no_survivor_raises_and_stores_nothing(
    cache_dir, monkeypatch
):
    _tpu_like(monkeypatch)

    def broken(fn, *args, **kw):
        raise RuntimeError("Mosaic says no")

    monkeypatch.setattr(tuning, "timeit", broken)
    with pytest.raises(RuntimeError, match="timed none"):
        tuning.get_blocks(
            seq=128, head_dim=64, group=1, dtype="float32",
            causal=True,
        )
    cache = tuning.get_cache()
    assert cache.entries() == 0 and not cache._mem
