"""Zero-stall checkpoint pipeline (ISSUE 3).

The save path must cost the train thread only staging dispatch
(serialization happens behind the step loop), the persist tier must be
BOUNDED (a slow store can pin at most queue_depth archives, overflow is
counted, forced saves back-pressure instead of dropping), and close()
must never orphan an in-flight save. The Orbax branch must consume the
host snapshot captured at save() time — never touch live device state
on the background thread (donation may have invalidated it).
"""

import io
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlrover_tpu.telemetry as T
from dlrover_tpu.telemetry import EventJournal
from dlrover_tpu.trainer import ckpt_store
from dlrover_tpu.trainer.checkpoint import (
    FlashCheckpointer,
    _local_shards,
    _materialize_staged,
    _stage_local_shards,
)


@pytest.fixture(autouse=True)
def fresh_telemetry():
    reg = T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))
    yield reg
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))


def _state():
    return {
        "params": {
            "w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
            "b": jnp.ones((6,), jnp.bfloat16),
        },
        "step": jnp.asarray(7),
    }


class SlowStore(ckpt_store.LocalFsStore):
    """LocalFsStore whose shard uploads take ``delay`` seconds, with
    concurrency accounting: the bounded pipeline must never run more
    than one upload at a time."""

    def __init__(self, root, delay=0.15):
        super().__init__(root)
        self.delay = delay
        self.active = 0
        self.max_active = 0
        self.puts = 0
        self.started = threading.Event()  # an upload has begun
        self._lock = threading.Lock()

    def _track(self):
        class _Ctx:
            def __enter__(ctx):
                with self._lock:
                    self.active += 1
                    self.max_active = max(self.max_active, self.active)
                    self.puts += 1
                self.started.set()
                time.sleep(self.delay)
                return ctx

            def __exit__(ctx, *exc):
                with self._lock:
                    self.active -= 1
                return False

        return _Ctx()

    def put(self, key, data):
        if "/proc-" in key:
            with self._track():
                return super().put(key, data)
        return super().put(key, data)

    def put_stream(self, key, fileobj, size=None):
        if "/proc-" in key:
            with self._track():
                return super().put_stream(key, fileobj, size=size)
        return super().put_stream(key, fileobj, size=size)


def _ckpt(tmp_path, store=None, **kw):
    kw.setdefault("use_orbax", False)
    ckpt = FlashCheckpointer(
        persist_dir=str(tmp_path / "persist"),
        ram_dir=str(tmp_path / "ram"),
        **kw,
    )
    if store is not None:
        ckpt._store = store
    return ckpt


# ----------------------------------------------------------- streaming codec


def test_streaming_archive_roundtrip_via_file(tmp_path):
    """snapshot_to_file -> snapshot_from_file round-trips the full
    leaf menagerie (sharded f32, bf16 extension dtype, scalars)."""
    state = _state()
    snap = _local_shards(state)
    path = tmp_path / "arch.ckpt"
    with open(path, "wb") as f:
        nbytes = ckpt_store.snapshot_to_file(snap, 11, f)
    assert nbytes == os.path.getsize(path) > 0
    with open(path, "rb") as f:
        got, step = ckpt_store.snapshot_from_file(f, target=state)
    assert step == 11
    np.testing.assert_array_equal(
        got["params"]["w"]["shards"][0][1],
        np.asarray(state["params"]["w"]),
    )
    # bf16 rode the encodings table, not a void dtype
    b = got["params"]["b"]
    assert b["dtype"] == "bfloat16"
    assert b["shards"][0][1].dtype.name == "bfloat16"
    # scalar shard survived with shape () (regression: the streaming
    # writer must not promote 0-d members to 1-d)
    assert got["step"]["shards"][0][1].shape == ()


def test_streaming_and_bytes_codecs_are_interchangeable():
    state = _state()
    snap = _local_shards(state)
    data = ckpt_store.snapshot_to_bytes(snap, 3)
    buf = io.BytesIO()
    ckpt_store.snapshot_to_file(snap, 3, buf)
    # one archive, two readers
    got_a, _ = ckpt_store.snapshot_from_bytes(buf.getvalue())
    got_b, _ = ckpt_store.snapshot_from_bytes(data)
    np.testing.assert_array_equal(
        got_a["params"]["w"]["shards"][0][1],
        got_b["params"]["w"]["shards"][0][1],
    )


def test_streaming_reader_rejects_corrupt_archives(tmp_path):
    path = tmp_path / "bad.ckpt"
    path.write_bytes(b"definitely not a zip archive")
    with open(path, "rb") as f:
        with pytest.raises(ckpt_store.ArchiveError):
            ckpt_store.snapshot_from_file(f)
    # truncated real archive is rejected too, never executed
    snap = _local_shards(_state())
    data = ckpt_store.snapshot_to_bytes(snap, 1)
    with pytest.raises(ckpt_store.ArchiveError):
        ckpt_store.snapshot_from_file(io.BytesIO(data[: len(data) // 2]))


def test_store_put_stream_and_open_read_roundtrip(tmp_path):
    store = ckpt_store.LocalFsStore(str(tmp_path))
    payload = os.urandom(1 << 16)
    store.put_stream("step-1/proc-0.a0.ckpt", io.BytesIO(payload))
    with store.open_read("step-1/proc-0.a0.ckpt") as f:
        assert f.read() == payload
    with pytest.raises(KeyError):
        store.open_read("missing-key")
    # base-class default path (exercised via a minimal store)
    class Mem(ckpt_store.ObjectStore):
        def __init__(self):
            self.d = {}

        def put(self, key, data):
            self.d[key] = data

        def get(self, key):
            try:
                return self.d[key]
            except KeyError:
                raise KeyError(key)

        def list(self, prefix=""):
            return sorted(k for k in self.d if k.startswith(prefix))

        def delete(self, key):
            self.d.pop(key, None)

    mem = Mem()
    mem.put_stream("k", io.BytesIO(b"xyz"))
    assert mem.open_read("k").read() == b"xyz"


# ------------------------------------------------------------- stall contract


def test_save_returns_before_serialization_completes(tmp_path,
                                                     monkeypatch):
    """The stall regression: save() must hand off BEFORE the archive
    is serialized — the train thread pays staging dispatch only."""
    serialize_started = threading.Event()
    release = threading.Event()
    real = ckpt_store.snapshot_to_file

    def gated(snapshot, step, fileobj, **kw):
        serialize_started.set()
        assert release.wait(10.0), "test deadlock"
        return real(snapshot, step, fileobj, **kw)

    monkeypatch.setattr(ckpt_store, "snapshot_to_file", gated)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    state = _state()
    t0 = time.perf_counter()
    stall_ms = ckpt.save(21, state)
    returned_in = (time.perf_counter() - t0) * 1e3
    # save() came back while the serializer is still gated
    assert serialize_started.wait(5.0)
    assert not release.is_set()
    assert stall_ms < 1000.0 and returned_in < 1000.0
    release.set()
    ckpt.wait()
    restored, step = ckpt.restore(target=state)
    assert step == 21
    np.testing.assert_array_equal(
        np.asarray(restored["params"]["w"]),
        np.asarray(state["params"]["w"]),
    )
    ckpt.close()
    # stall histogram observed the save
    reg = T.default_registry()
    hist = reg.get("dlrover_checkpoint_save_stall_seconds")
    assert hist is not None and hist._default_child().count >= 1


def test_wait_staged_marks_donation_safe_point(tmp_path, monkeypatch):
    """After wait_staged() the staged snapshot owns host memory: the
    source device buffers can be deleted (donation) without corrupting
    the save."""
    gate = threading.Event()
    real = ckpt_store.snapshot_to_file

    def slow(snapshot, step, fileobj, **kw):
        assert gate.wait(10.0)
        return real(snapshot, step, fileobj, **kw)

    monkeypatch.setattr(ckpt_store, "snapshot_to_file", slow)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    state = {"w": jnp.arange(64, dtype=jnp.float32)}
    expect = np.asarray(state["w"]).copy()
    ckpt.save(5, state)
    assert ckpt.wait_staged(10.0)
    state["w"].delete()  # the donation hazard, made explicit
    gate.set()
    ckpt.wait()
    target = {"w": jnp.zeros(64, dtype=jnp.float32)}
    restored, step = ckpt.restore(target=target)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(restored["w"]), expect)
    ckpt.close()


def test_durable_save_lands_on_tmpfs_before_returning(tmp_path):
    """durable=True: the RAM archive survives an immediate hard kill —
    the file must exist the moment save() returns."""
    ckpt = _ckpt(tmp_path, persist_interval=0)
    state = _state()
    ckpt.save(30, state, durable=True)
    assert os.path.exists(ckpt._ram_path(30))
    ckpt.close()


def test_durable_drain_excluded_from_stall_histogram(tmp_path,
                                                     monkeypatch):
    """durable=True blocks for the serializer drain, but the stall
    histogram is the staging-only zero-stall budget — the drain must
    not skew it (alerting keys off the ~25ms back-pressure buckets).
    The return value still reports the full train-thread cost."""
    real = ckpt_store.snapshot_to_file

    def slow(snapshot, step, fileobj, **kw):
        time.sleep(0.3)
        return real(snapshot, step, fileobj, **kw)

    monkeypatch.setattr(ckpt_store, "snapshot_to_file", slow)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    ret = ckpt.save(9, _state(), durable=True)
    assert ret >= 300.0  # the drain is the caller's visible cost
    hist = T.default_registry().get(
        "dlrover_checkpoint_save_stall_seconds"
    )
    child = hist._default_child()
    assert child.count == 1
    assert child.sum < 0.25  # the 0.3s serialize drain stayed out
    ckpt.close()


def test_stage_then_materialize_owns_memory():
    staged = _stage_local_shards({"w": jnp.arange(8.0)})
    snap = _materialize_staged(staged)
    arr = snap["w"]["shards"][0][1]
    assert isinstance(arr, np.ndarray)
    # owned: mutating the materialized copy can't be a view of the
    # live device buffer (CPU backend would otherwise alias it)
    assert arr.base is None or arr.flags["OWNDATA"]


def test_sync_stage_mode_materializes_on_the_caller(tmp_path):
    ckpt = _ckpt(tmp_path, persist_interval=0, stage="sync")
    state = {"w": jnp.arange(16.0)}
    ckpt.save(3, state)
    # sync staging: host copies owned before save() returned
    assert ckpt.wait_staged(0.0)
    state["w"].delete()
    ckpt.wait()
    restored, step = ckpt.restore(
        target={"w": jnp.zeros(16, jnp.float32)}
    )
    assert step == 3
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.arange(16.0)
    )
    ckpt.close()


def test_orbax_branch_persists_staged_snapshot_not_live_state(tmp_path):
    """checkpoint.py:283 bugfix: the Orbax persist must consume host
    data captured at save() time. With donation, the train loop may
    invalidate the state buffers before the background persist runs —
    device_get(state) there reads deleted arrays."""

    class FakeManager:
        def __init__(self):
            self.saved = {}
            self.entered = threading.Event()
            self.release = threading.Event()

        def save(self, step, args=None):
            self.entered.set()
            assert self.release.wait(10.0)
            self.saved[step] = args

        def wait_until_finished(self):
            pass

        def close(self):
            pass

    ckpt = _ckpt(tmp_path, persist_interval=1)
    mgr = FakeManager()
    ckpt._manager = mgr
    ckpt._store = None
    state = {"w": jnp.arange(32, dtype=jnp.float32)}
    expect = np.asarray(state["w"]).copy()
    ckpt.save(9, state, force_persist=True)
    assert ckpt.wait_staged(10.0)
    # donation: the live buffers die while the persist is in flight
    state["w"].delete()
    assert mgr.entered.wait(10.0)
    mgr.release.set()
    ckpt.wait()
    saved = mgr.saved[9]
    # StandardSave(ref) or the raw tree, depending on orbax presence;
    # unwrap defensively
    tree = getattr(saved, "item", saved)
    np.testing.assert_array_equal(np.asarray(tree["w"]), expect)
    ckpt._manager = None  # close() must not touch the fake again
    ckpt.close()


# ------------------------------------------------------ bounded persist queue


def test_persist_queue_overflow_skips_oldest_and_counts(tmp_path):
    store = SlowStore(str(tmp_path / "bucket"), delay=0.25)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=1, queue_depth=2,
    )
    state = _state()
    for s in range(1, 7):
        ckpt.save(s, state)
    ckpt.wait()
    ckpt.close()
    # bounded: never more than one concurrent upload (single worker),
    # and some persists were skipped under the slow store
    assert store.max_active == 1
    committed = ckpt_store.committed_steps(store)
    assert committed, "no step ever committed"
    assert committed[-1] == 6, "the NEWEST save must survive the skips"
    skipped = T.default_registry().get(
        "dlrover_checkpoint_persist_skipped_total"
    )
    assert skipped is not None
    total_skipped = sum(
        child._value for _, child in skipped._snapshot()
    )
    assert total_skipped >= 1
    assert total_skipped + store.puts == 6
    # the journal carries the same story
    assert T.default_journal().events("checkpoint.persist_skipped")


def test_inflight_never_exceeds_queue_depth(tmp_path):
    store = SlowStore(str(tmp_path / "bucket"), delay=0.1)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=1, queue_depth=2,
    )
    state = _state()
    peak = 0
    for s in range(1, 8):
        ckpt.save(s, state)
        ckpt._drain_saves()  # queue observed between uploads
        peak = max(peak, ckpt._persistq.inflight())
    assert peak <= 2
    ckpt.wait()
    assert ckpt._persistq.inflight() == 0
    ckpt.close()


def test_force_persist_backpressures_instead_of_skipping(tmp_path):
    store = SlowStore(str(tmp_path / "bucket"), delay=0.15)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=0, queue_depth=1,
    )
    state = _state()
    for s in (1, 2, 3):
        ckpt.save(s, state, force_persist=True)
    ckpt.wait()
    ckpt.close()
    # every forced save was uploaded (none dropped by the bound)
    assert store.puts == 3
    assert ckpt_store.committed_steps(store) == [1, 2, 3]


def test_wait_joins_all_inflight_persists_not_just_last(tmp_path):
    """The old code joined only the LAST persist thread; close() could
    orphan an uncommitted save."""
    store = SlowStore(str(tmp_path / "bucket"), delay=0.2)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=0, queue_depth=4,
    )
    state = _state()
    ckpt.save(10, state, force_persist=True)
    ckpt.save(20, state, force_persist=True)
    ckpt.close()  # wait + shutdown: both persists must have landed
    assert ckpt_store.committed_steps(store) == [10, 20]


def test_same_step_resave_supersedes_queued_predecessor(tmp_path):
    store = SlowStore(str(tmp_path / "bucket"), delay=0.2)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=1, queue_depth=3,
    )
    state = _state()
    ckpt.save(5, state)
    ckpt.save(5, state)  # same step again: supersede, don't race
    ckpt.wait()
    ckpt.close()
    assert ckpt_store.committed_steps(store) == [5]
    # at most 2 uploads ever ran (first may have started), never 2
    # concurrently for one step
    assert store.max_active == 1


def test_ram_gc_spares_files_pinned_by_pending_persist(tmp_path):
    store = SlowStore(str(tmp_path / "bucket"), delay=0.3)
    ckpt = _ckpt(
        tmp_path, store=store, persist_interval=1, queue_depth=2,
        max_ram_keep=1,
    )
    state = _state()
    ckpt.save(1, state)  # persist of step 1 starts (slow)
    # ... and has started: still queued when 2 and 3 arrive, step 1 is
    # the oldest entry and the bounded queue drops it (newest wins)
    assert store.started.wait(30)
    for s in (2, 3):
        ckpt.save(s, state)  # gc would love to remove step-1's file
    ckpt.wait()
    ckpt.close()
    # the persist of step 1 read a live file: it committed correctly
    assert 1 in ckpt_store.committed_steps(store)
    restored = ckpt_store.read_step(store, 1, 0)
    got, step = ckpt_store.snapshot_from_bytes(restored, target=state)
    assert step == 1


def test_ram_write_failure_still_persists_due_save(tmp_path,
                                                   monkeypatch):
    """A RAM-tier write failure must not silently drop a due persist
    (forced persists are documented as never skipped): the worker
    falls back to building the archive in memory from the snapshot
    materialized at save() time."""
    ckpt = _ckpt(tmp_path, persist_interval=1)
    state = _state()

    def boom(step, snapshot):
        raise OSError("tmpfs full")

    monkeypatch.setattr(ckpt, "_write_ram", boom)
    ckpt.save(4, state, force_persist=True)
    ckpt.wait()
    ckpt.close()
    assert ckpt_store.committed_steps(ckpt._store) == [4]
    data = ckpt_store.read_step(ckpt._store, 4, 0)
    got, step = ckpt_store.snapshot_from_bytes(data, target=state)
    assert step == 4
    np.testing.assert_array_equal(
        got["params"]["w"]["shards"][0][1],
        np.asarray(state["params"]["w"]),
    )


def test_stage_failure_counts_lost_persist(tmp_path, monkeypatch):
    """When staging itself fails there is nothing to persist — the
    loss must be observable (persist_skipped{reason=stage_failed} +
    journal), never just a log line a failover drill can't see."""
    import dlrover_tpu.trainer.checkpoint as ckpt_mod

    def boom(staged):
        raise RuntimeError("D2H failed")

    monkeypatch.setattr(ckpt_mod, "_materialize_staged", boom)
    ckpt = _ckpt(tmp_path, persist_interval=1)
    ckpt.save(2, _state(), force_persist=True)
    ckpt.wait()
    ckpt.close()
    skipped = T.default_registry().get(
        "dlrover_checkpoint_persist_skipped_total"
    )
    assert skipped is not None
    assert sum(c._value for _, c in skipped._snapshot()) >= 1
    evts = T.default_journal().events("checkpoint.persist_skipped")
    assert any(e["data"].get("reason") == "stage_failed" for e in evts)


# --------------------------------------------------------------- elastic tie


def test_elastic_trainer_save_cadence(tmp_path):
    import optax

    from dlrover_tpu.trainer.elastic import ElasticTrainer

    trainer = ElasticTrainer(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
        optax.sgd(0.1), max_nodes=1, cur_nodes=1,
    )
    ckpt = _ckpt(tmp_path, persist_interval=0)
    trainer.attach_checkpointer(ckpt, save_interval=2)
    state = {"w": jnp.ones((3, 1))}
    stalls = []
    for _ in range(4):
        trainer.report_step()
        stalls.append(trainer.maybe_checkpoint(state))
    # cadence 2: steps 2 and 4 saved, steps 1 and 3 skipped
    assert [s is not None for s in stalls] == [
        False, True, False, True,
    ]
    assert ckpt.latest_step() == 4
    ckpt.close()
    # detached trainer is a no-op
    trainer2 = ElasticTrainer(
        lambda p, b: 0.0, optax.sgd(0.1), max_nodes=1, cur_nodes=1,
    )
    assert trainer2.maybe_checkpoint(state) is None


def test_elastic_train_step_calls_wait_staged_when_attached():
    """ElasticTrainer's jitted step donates (params, opt_state): with
    a checkpointer attached, every train_step dispatch must hit the
    donation sync point first (docs/CHECKPOINT.md contract)."""
    import optax

    from dlrover_tpu.trainer.elastic import ElasticTrainer

    class SpyCkpt:
        def __init__(self):
            self.waits = 0

        def wait_staged(self, timeout=None):
            self.waits += 1
            return True

    optimizer = optax.sgd(0.1)
    trainer = ElasticTrainer(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
        optimizer, max_nodes=1, cur_nodes=1,
    )
    params = {"w": jnp.ones((3, 1))}
    opt_state = optimizer.init(params)
    batches = (jnp.ones((1, 4, 3)), jnp.zeros((1, 4, 1)))
    # unattached: no sync point, the step runs as-is
    params, opt_state, _ = trainer.train_step(params, opt_state, batches)
    spy = SpyCkpt()
    trainer.attach_checkpointer(spy, save_interval=1)
    for _ in range(2):
        params, opt_state, _ = trainer.train_step(
            params, opt_state, batches
        )
    assert spy.waits == 2
    # profiler path still reaches the shared jit cache
    assert hasattr(trainer.train_step, "lower")


def test_elastic_train_step_blocks_until_staging_materializes(
        tmp_path, monkeypatch):
    """The donation race end-to-end: an async save's device handles
    are still un-materialized when the next (donating) step would
    dispatch — the wrapped train_step must block until the serializer
    owns host copies, and the checkpoint must restore the pre-step
    values."""
    import optax

    import dlrover_tpu.trainer.checkpoint as ckpt_mod
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    optimizer = optax.sgd(0.1)
    trainer = ElasticTrainer(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
        optimizer, max_nodes=1, cur_nodes=1,
    )
    params = {"w": jnp.ones((3, 1))}
    opt_state = optimizer.init(params)
    batches = (jnp.ones((1, 4, 3)), jnp.zeros((1, 4, 1)))
    # warm the jit cache so the blocking assertion below never
    # measures compile time
    params, opt_state, _ = trainer.train_step(params, opt_state, batches)

    entered = threading.Event()
    release = threading.Event()
    real = ckpt_mod._materialize_staged

    def gated(staged):
        entered.set()
        assert release.wait(10.0), "test deadlock"
        return real(staged)

    monkeypatch.setattr(ckpt_mod, "_materialize_staged", gated)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    trainer.attach_checkpointer(ckpt, save_interval=1)
    expect = np.asarray(params["w"]).copy()
    trainer.report_step()
    assert trainer.maybe_checkpoint((params, opt_state)) is not None
    assert entered.wait(5.0)

    done = threading.Event()

    def run():
        out = trainer.train_step(params, opt_state, batches)
        jax.block_until_ready(out[:2])
        done.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # the donating dispatch is gated on staging materialization
    assert not done.wait(0.5)
    release.set()
    assert done.wait(10.0)
    ckpt.wait()
    restored, step = ckpt.restore()
    assert step == 1
    np.testing.assert_array_equal(
        np.asarray(restored[0]["w"]), expect
    )
    ckpt.close()


# ------------------------------------- what a save costs the step loop


@pytest.fixture
def traced():
    """The span sites on, the ring empty; off and empty afterwards."""
    from dlrover_tpu.telemetry import tracing

    tracing.disable()
    tracing.clear()
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.clear()


def _spans(tracing, name):
    return [r for r in tracing.tail(4096) if r["name"] == name]


def _gate(monkeypatch, module, name):
    """Hold every call of ``module.name`` until ``release`` is set;
    ``entered`` says that one stands there."""
    entered, release = threading.Event(), threading.Event()
    real = getattr(module, name)

    def gated(*args, **kw):
        entered.set()
        assert release.wait(10.0), "test deadlock"
        return real(*args, **kw)

    monkeypatch.setattr(module, name, gated)
    return entered, release


def _wait_staged_histogram():
    hist = T.default_registry().get(
        "dlrover_checkpoint_wait_staged_seconds"
    )
    return None if hist is None else hist._default_child()


def test_wait_staged_writes_nothing_when_nothing_is_in_flight(
        tmp_path, traced):
    ckpt = _ckpt(tmp_path, persist_interval=0)
    assert ckpt.wait_staged() is True  # no save yet
    ckpt.save(3, _state())
    ckpt.wait()  # the copies are long on the host
    for _ in range(3):  # a loop that asks before every dispatch
        assert ckpt.wait_staged() is True
    ckpt.close()
    assert _spans(traced, "ckpt.stage")  # the sites are on
    assert _spans(traced, "ckpt.wait_staged") == []
    assert _wait_staged_histogram() is None


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_wait_staged_that_waits_is_one_span_on_the_callers_thread(
        tmp_path, monkeypatch, traced, on):
    import dlrover_tpu.trainer.checkpoint as ckpt_mod

    if not on:
        traced.disable()
    entered, release = _gate(monkeypatch, ckpt_mod, "_materialize_staged")
    ckpt = _ckpt(tmp_path, persist_interval=0)
    ckpt.save(11, _state())
    assert entered.wait(5.0)
    got = []
    loop = threading.Thread(
        target=lambda: got.append(ckpt.wait_staged(10.0)), name="loop"
    )
    loop.start()
    time.sleep(0.05)
    assert loop.is_alive()  # it stands in the wait
    release.set()
    loop.join(10.0)
    assert got == [True]
    ckpt.wait()
    ckpt.close()
    waits = _spans(traced, "ckpt.wait_staged")
    if on:
        (wait,) = waits
        assert wait["attrs"] == {"step": 11}
        assert wait["thread"] == "loop"
        assert wait["dur"] >= 0.04
        # the lane's materialize ends where the wait does
        (mat,) = _spans(traced, "ckpt.write.materialize")
        assert mat["thread"] == "ckpt-serialize"
        assert abs(mat["ts"] + mat["dur"]
                   - wait["ts"] - wait["dur"]) < 0.05
    else:
        assert waits == []
    # the histogram is an operator's, tracing or not: one wait, and
    # the staging-only stall beside it left as it was
    child = _wait_staged_histogram()
    assert child.count == 1 and child.sum >= 0.04
    stall = T.default_registry().get(
        "dlrover_checkpoint_save_stall_seconds"
    )._default_child()
    assert stall.count == 1 and stall.sum < 0.04


def test_wait_staged_that_times_out_says_so_and_is_a_span(
        tmp_path, monkeypatch, traced):
    import dlrover_tpu.trainer.checkpoint as ckpt_mod

    entered, release = _gate(monkeypatch, ckpt_mod, "_materialize_staged")
    ckpt = _ckpt(tmp_path, persist_interval=0)
    ckpt.save(12, _state())
    assert entered.wait(5.0)
    assert ckpt.wait_staged(0.02) is False
    release.set()
    ckpt.wait()
    ckpt.close()
    (wait,) = _spans(traced, "ckpt.wait_staged")
    assert wait["attrs"] == {"step": 12} and wait["dur"] >= 0.02


def test_submit_wait_only_under_back_pressure(tmp_path, monkeypatch,
                                              traced):
    """``ckpt.stage`` is dispatch; where the lane's one pending slot
    was taken, the wait for it is ``ckpt.submit_wait`` inside it."""
    entered, release = _gate(monkeypatch, ckpt_store, "snapshot_to_file")
    ckpt = _ckpt(tmp_path, persist_interval=0)
    ckpt.save(1, _state())
    assert entered.wait(5.0)  # the lane is busy with step 1
    ckpt.save(2, _state())  # takes the pending slot: no wait
    assert _spans(traced, "ckpt.submit_wait") == []
    third = threading.Thread(
        target=lambda: ckpt.save(3, _state()), name="loop"
    )
    third.start()
    time.sleep(0.05)
    assert third.is_alive()  # behind step 2
    release.set()
    third.join(10.0)
    assert not third.is_alive()
    ckpt.wait()
    ckpt.close()
    (wait,) = _spans(traced, "ckpt.submit_wait")
    assert wait["attrs"] == {"step": 3, "behind": 2}
    assert wait["thread"] == "loop" and wait["dur"] >= 0.04
    stages = {r["attrs"]["step"]: r for r in _spans(traced, "ckpt.stage")}
    assert sorted(stages) == [1, 2, 3]
    assert stages[3]["ts"] <= wait["ts"]
    assert stages[3]["dur"] >= wait["dur"]
    assert stages[1]["dur"] < 0.04 and stages[2]["dur"] < 0.04


def test_stage_span_says_how_much_was_staged(tmp_path, traced):
    ckpt = _ckpt(tmp_path, persist_interval=0)
    state = _state()
    ckpt.save(4, state)
    ckpt.wait()
    ckpt.close()
    (stage,) = _spans(traced, "ckpt.stage")
    assert stage["attrs"] == {
        "step": 4, "shards": 3,
        "bytes": sum(x.nbytes for x in jax.tree.leaves(state)),
    }
    (mat,) = _spans(traced, "ckpt.write.materialize")
    assert mat["attrs"]["bytes"] == stage["attrs"]["bytes"]


LANE_SPANS = ("ckpt.serialize", "ckpt.write.materialize",
              "ckpt.write.encode", "ckpt.write.io", "ckpt.write.digest",
              "ckpt.persist")


@pytest.mark.parametrize("name", LANE_SPANS)
def test_lane_span_carries_its_threads_cpu_time(tmp_path, traced, name):
    ckpt = _ckpt(tmp_path, persist_interval=1)
    ckpt.save(1, _state())
    ckpt.wait()
    ckpt.close()
    found = _spans(traced, name)
    assert found
    for rec in found:
        assert rec["thread"] in ("ckpt-serialize", "ckpt-persist")
        # the thread's own clock: never more than the wall's, but for
        # the two clocks' grain
        assert 0.0 <= rec["attrs"]["cpu_s"] <= rec["dur"] + 0.02
    # the train thread's slice asks for none
    assert "cpu_s" not in _spans(traced, "ckpt.stage")[0]["attrs"]


def test_a_lane_that_waits_reads_little_cpu(tmp_path, monkeypatch,
                                            traced):
    """``cpu_s`` near zero where the pass waited: here on a gate, on
    the chip on the device-to-host copies."""
    import dlrover_tpu.trainer.checkpoint as ckpt_mod

    real = ckpt_mod._materialize_staged

    def slow(staged):
        time.sleep(0.2)
        return real(staged)

    monkeypatch.setattr(ckpt_mod, "_materialize_staged", slow)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    ckpt.save(1, _state())
    ckpt.wait()
    ckpt.close()
    (mat,) = _spans(traced, "ckpt.write.materialize")
    assert mat["dur"] >= 0.2 and mat["attrs"]["cpu_s"] < 0.1
    (whole,) = _spans(traced, "ckpt.serialize")
    assert whole["attrs"]["cpu_s"] < whole["dur"] - 0.1


def test_no_thread_clock_is_read_with_tracing_off(tmp_path,
                                                  monkeypatch):
    from dlrover_tpu.telemetry import tracing

    assert not tracing.enabled()
    calls = []
    monkeypatch.setattr(
        time, "thread_time", lambda: calls.append(1) or 0.0
    )
    ckpt = _ckpt(tmp_path, persist_interval=1)
    ckpt.save(1, _state())
    ckpt.wait_staged()
    ckpt.wait()
    restored, step = ckpt.restore(target=_state())
    ckpt.close()
    assert step == 1 and calls == []


def test_elastic_train_steps_wait_is_the_checkpointers_span(
        tmp_path, monkeypatch, traced):
    """One wait, one name, wherever it is called from."""
    import optax

    import dlrover_tpu.trainer.checkpoint as ckpt_mod
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    optimizer = optax.sgd(0.1)
    trainer = ElasticTrainer(
        lambda p, b: jnp.mean((b[0] @ p["w"] - b[1]) ** 2),
        optimizer, max_nodes=1, cur_nodes=1,
    )
    params = {"w": jnp.ones((3, 1))}
    opt_state = optimizer.init(params)
    batches = (jnp.ones((1, 4, 3)), jnp.zeros((1, 4, 1)))
    params, opt_state, _ = trainer.train_step(params, opt_state, batches)
    real = ckpt_mod._materialize_staged

    def slow(staged):
        time.sleep(0.1)
        return real(staged)

    monkeypatch.setattr(ckpt_mod, "_materialize_staged", slow)
    ckpt = _ckpt(tmp_path, persist_interval=0)
    trainer.attach_checkpointer(ckpt, save_interval=1)
    trainer.report_step()
    assert trainer.maybe_checkpoint((params, opt_state)) is not None
    for _ in range(2):  # the first waits, the second finds it done
        params, opt_state, _ = trainer.train_step(
            params, opt_state, batches
        )
    ckpt.wait()
    ckpt.close()
    (wait,) = _spans(traced, "ckpt.wait_staged")
    assert wait["thread"] == threading.current_thread().name
    assert 0.05 <= wait["dur"]
    # the warm-up's and the two after the save
    assert len(_spans(traced, "train.dispatch")) == 3
    assert _spans(traced, "train.wait_staged") == []
