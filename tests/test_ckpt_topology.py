"""Sharded checkpoint plane (format v2): topology-elastic drills.

The virtual-host pattern from the other drill suites: one real JAX
process with 8 forced CPU devices, carved into logical processes via
``proc_of_device``, one FlashCheckpointer per logical process sharing a
single LocalFs object store. Saves under one topology (pp2xtp2,
4-process dp, 2-process world) must restore bit-identical under
another (dp over all devices, halved/doubled worlds), every shard
digest-verified on fetch, with the exactly-once sampler ledger carried
across the resize.
"""

import io
import json
import os
import subprocess
import sys
import time
import zipfile

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu import telemetry as T
from dlrover_tpu.checkpoint import manifest as mf
from dlrover_tpu.telemetry.journal import EventJournal
from dlrover_tpu.trainer import ckpt_store
from dlrover_tpu.trainer.checkpoint import FlashCheckpointer
from dlrover_tpu.trainer.sampler import ElasticDistributedSampler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


@pytest.fixture(autouse=True)
def fresh_defaults():
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))
    yield
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))


def events(kind):
    return T.default_journal().events(kind)


def _state(mesh, spec):
    """A model-ish pytree: one sharded weight, one replicated bias,
    and the exactly-once sampler ledger as a py leaf."""
    sampler = ElasticDistributedSampler(
        dataset_size=1000, num_replicas=4, rank=0, shuffle=False
    )
    sampler.completed_num = 637  # mid-epoch progress to carry over
    return {
        "w": jax.device_put(
            np.arange(64, dtype=np.float32).reshape(8, 8),
            NamedSharding(mesh, spec),
        ),
        "b": jax.device_put(
            np.linspace(-1, 1, 8, dtype=np.float32),
            NamedSharding(mesh, P(None)),
        ),
        "ledger": sampler.state_dict(),
        "step_count": 7,
    }


def _fleet(tmp_path, n_procs, devs_per_proc, tag=""):
    """One virtual checkpointer per logical process over a shared
    store."""
    return [
        FlashCheckpointer(
            persist_dir=str(tmp_path / f"store{tag}"),
            ram_dir=str(tmp_path / f"ram{tag}{p}"),
            persist_interval=1, use_orbax=False,
            process_index=p, n_processes=n_procs,
            proc_of_device=lambda d: d.id // devs_per_proc,
            commit_timeout=60,
        )
        for p in range(n_procs)
    ]


def _save_all(ckpts, step, state, durable=False):
    for c in ckpts:
        c.save(step, state, force_persist=True, durable=durable)
    for c in ckpts:
        c.wait()


def _close_all(ckpts):
    for c in ckpts:
        c.close()


def _zeros_like(state, mesh, spec):
    out = dict(state)
    out["w"] = jax.device_put(
        np.zeros((8, 8), np.float32), NamedSharding(mesh, spec)
    )
    out["b"] = jax.device_put(
        np.zeros(8, np.float32), NamedSharding(mesh, P(None))
    )
    out["ledger"] = {"epoch": -1, "completed_num": -1}
    out["step_count"] = -1
    return out


# ------------------------------------------------- pp2xtp2 -> dp drill


def test_pp_tp_save_restores_under_dp(tmp_path):
    """The ISSUE acceptance drill: save under pp2xtp2 (4 virtual
    hosts), restore under a pure-dp layout by a fresh single-process
    checkpointer that never saw the save topology — bit-identical,
    every shard digest-verified, topology journaled."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(2, 4), ("pp", "tp"))
    state = _state(mesh, P("pp", "tp"))
    want = np.asarray(state["w"])
    ckpts = _fleet(tmp_path, 4, 2)
    _save_all(ckpts, 30, state)
    _close_all(ckpts)

    man = ckpt_store.step_manifest(
        ckpt_store.get_store(str(tmp_path / "store")), 30
    )
    assert man["format"] == 2
    assert man["topology"]["n_processes"] == 4
    # every globally-named shard has exactly one located member
    for loc in man["locations"].values():
        assert loc["sha256"]

    mesh_dp = Mesh(np.array(devs), ("dp",))
    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram-new"),
        persist_interval=0, use_orbax=False,
        process_index=0, n_processes=1,
    )
    target = _zeros_like(state, mesh_dp, P("dp"))
    got, step = r.restore(target=target, step=30)
    r.close()

    assert step == 30
    assert np.array_equal(np.asarray(got["w"]), want)
    assert np.array_equal(np.asarray(got["b"]), np.asarray(state["b"]))
    assert got["ledger"] == {"epoch": 0, "completed_num": 637}
    assert got["step_count"] == 7
    ev = events("ckpt.topology_restore")
    assert ev and ev[-1]["data"]["saved_processes"] == 4


# ------------------------------------------------------- world resize


def test_world_resize_4_to_2_preserves_ledger(tmp_path):
    devs = jax.devices()
    mesh4 = Mesh(np.array(devs).reshape(4, 2), ("dp", "tp"))
    state = _state(mesh4, P("dp", "tp"))
    want = np.asarray(state["w"])
    _save_all(ckpts := _fleet(tmp_path, 4, 2), 40, state)
    _close_all(ckpts)

    # the world halves: 2 logical processes, 4 devices each
    mesh2 = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    r = _fleet(tmp_path, 2, 4, tag="n")[0]
    r._store = ckpt_store.get_store(str(tmp_path / "store"))
    got, step = r.restore(
        target=_zeros_like(state, mesh2, P("dp", "tp")), step=40
    )
    r.close()
    assert step == 40
    assert np.array_equal(np.asarray(got["w"]), want)

    # exactly-once: the ledger resumes mid-epoch in the new world
    # with no shard replayed and none skipped
    s2 = ElasticDistributedSampler(
        dataset_size=1000, num_replicas=2, rank=0, shuffle=False
    )
    s2.load_state_dict(got["ledger"], num_replicas=2, rank=0)
    assert s2.completed_num == 637
    assert s2.epoch == 0


def test_world_resize_2_to_4(tmp_path):
    devs = jax.devices()
    mesh2 = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    state = _state(mesh2, P(None, "tp"))  # dp-replicated weight
    want = np.asarray(state["w"])
    _save_all(ckpts := _fleet(tmp_path, 2, 4), 50, state)
    _close_all(ckpts)

    mesh4 = Mesh(np.array(devs).reshape(4, 2), ("dp", "tp"))
    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram-up0"),
        persist_interval=0, use_orbax=False,
        process_index=0, n_processes=4,
        proc_of_device=lambda d: d.id // 2,
    )
    got, step = r.restore(
        target=_zeros_like(state, mesh4, P(None, "tp")), step=50
    )
    r.close()
    assert step == 50
    assert np.array_equal(np.asarray(got["w"]), want)
    assert got["ledger"] == {"epoch": 0, "completed_num": 637}


# ------------------------------------------------- dedup + owner election


def test_replicated_save_dedups_to_owned_shards(tmp_path):
    """A dp-replicated save must persist each logical shard once, from
    its crc32-elected owner — aggregate store bytes stop scaling with
    the replica count."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(4, 2), ("dp", "tp"))
    state = _state(mesh, P(None, "tp"))  # 4-way replicated
    _save_all(ckpts := _fleet(tmp_path, 4, 2), 60, state)
    _close_all(ckpts)

    man = ckpt_store.step_manifest(
        ckpt_store.get_store(str(tmp_path / "store")), 60
    )
    # w is tp-sharded in 2 domains, each replicated across all 4
    # procs; the location table names each domain exactly once
    wleaf = next(
        l for l in man["leaves"]
        if l["path"][-1].get("k") == "w"
    )
    assert len(wleaf["domains"]) == 2
    for d in wleaf["domains"]:
        assert sorted(d["replicas"]) == [0, 1, 2, 3]
        assert d["owner"] == mf.elect_owner(
            mf.shard_key(mf.path_key(wleaf["path"]), d["idx"]),
            d["replicas"],
        )
    dedup = events("ckpt.dedup")
    assert len(dedup) == 4  # every host journaled its subset
    owned = sum(e["data"]["members_owned"] for e in dedup)
    full = sum(e["data"]["members_full"] for e in dedup)
    assert owned < full  # replicas actually dropped members


def test_owner_election_deterministic_and_spread():
    replicas = [0, 1, 2, 3]
    owners = [
        mf.elect_owner(f"leaf-{i}|[[0,8]]", replicas)
        for i in range(200)
    ]
    assert owners == [
        mf.elect_owner(f"leaf-{i}|[[0,8]]", replicas)
        for i in range(200)
    ]
    counts = {p: owners.count(p) for p in replicas}
    assert all(c > 0 for c in counts.values())  # no pile-up on rank 0
    # order of the replica list must not matter
    assert mf.elect_owner("k", [3, 1, 0, 2]) == mf.elect_owner(
        "k", [0, 1, 2, 3]
    )


# ------------------------------------------- sentinel taint + drain save


def test_sentinel_taint_skipped_over_v2(tmp_path):
    """A step saved inside an anomaly window (clean_fn False) is
    tainted at commit and the rollback walk-down lands on the older
    clean step — unchanged semantics over the sharded format."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    state = _state(mesh, P("dp", "tp"))
    ckpts = _fleet(tmp_path, 2, 4)
    verdict = {"clean": True}
    for c in ckpts:
        c.set_clean_fn(lambda: verdict["clean"])
    _save_all(ckpts, 70, state)
    verdict["clean"] = False
    bad = dict(state, step_count=666)
    _save_all(ckpts, 80, bad)
    _close_all(ckpts)

    store = ckpt_store.get_store(str(tmp_path / "store"))
    assert ckpt_store.step_last_good(store, 80) is False
    assert ckpt_store.step_last_good(store, 70) is True

    # the rollback restorer: a fresh single-process world (the taint
    # walk-down is the solo path; multi-process worlds agree via the
    # consensus collectives) reading the same store
    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram-rb0"),
        persist_interval=0, use_orbax=False,
        process_index=0, n_processes=1,
    )
    got, step = r.restore(
        target=_zeros_like(state, mesh, P("dp", "tp"))
    )
    r.close()
    assert step == 70
    assert got["step_count"] == 7  # not the tainted 666


def test_durable_emergency_save_restores_after_kill(tmp_path):
    """The preemption-drain emergency save (durable=True) over the
    sharded format: both hosts' notice-window saves are on tmpfs when
    save() returns (no wait, no close — a hard kill follows); the
    relaunched host reassembles the step from its own surviving RAM
    archive plus the survivor's peer tier, never touching the store."""
    from dlrover_tpu.checkpoint.peer import PeerRegistry
    from dlrover_tpu.telemetry.http import MetricsServer

    class _KV:
        def __init__(self):
            self.kv = {}

        def kv_store_set(self, k, v):
            self.kv[k] = v

        def kv_store_get(self, k):
            return self.kv.get(k, b"")

        def kv_store_keys(self, prefix=""):
            return sorted(k for k in self.kv if k.startswith(prefix))

        def kv_store_delete(self, k):
            self.kv.pop(k, None)

    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    state = _state(mesh, P("dp", "tp"))
    kv = _KV()
    ckpts, servers = [], []
    for p in range(2):
        c = FlashCheckpointer(
            persist_dir=str(tmp_path / "store"),
            ram_dir=str(tmp_path / f"ram{p}"),
            persist_interval=0, use_orbax=False,
            process_index=p, n_processes=2,
            proc_of_device=lambda d: d.id // 4,
        )
        srv = MetricsServer(
            port=0, shard_provider=c.shard_provider()
        ).start()
        c._peer_registry = PeerRegistry(
            kv, p, f"http://127.0.0.1:{srv.port}"
        )
        ckpts.append(c)
        servers.append(srv)
    for c in ckpts:
        c.save(90, state, durable=True)  # returns only once on tmpfs
    # the archives must already be durable — no wait()/close() flush
    for p in range(2):
        assert os.path.exists(tmp_path / f"ram{p}" / f"step-90-proc-{p}")
    deadline = time.monotonic() + 10
    while (len(kv.kv_store_keys("ckpt/peer/90/")) < 2
           and time.monotonic() < deadline):
        time.sleep(0.02)  # advertisement rides the background lane

    # host 0 is hard-killed and relaunched over the same tmpfs
    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram0"),
        persist_interval=0, use_orbax=False,
        process_index=0, n_processes=2,
        proc_of_device=lambda d: d.id // 4,
        peer_registry=PeerRegistry(kv, 0, "http://127.0.0.1:1"),
    )
    got, step = r.restore(
        target=_zeros_like(state, mesh, P("dp", "tp")), step=90
    )
    r.close()
    for c in ckpts:
        c.close()
    for s in servers:
        s.stop()
    assert step == 90
    assert np.array_equal(np.asarray(got["w"]), np.asarray(state["w"]))
    tr = events("ckpt.topology_restore")[-1]["data"]
    assert tr["local"] >= 1 and tr["peer"] >= 1 and tr["store"] == 0


# ------------------------------------------------- digest verification


def _corrupt_one_member(path):
    """Flip payload bytes of one npy member inside a RAM archive,
    keeping the zip well-formed (the digest must catch it)."""
    with zipfile.ZipFile(path) as z:
        members = {n: z.read(n) for n in z.namelist()}
    victim = next(
        n for n in members if n.endswith(".npy") and n != "manifest.json"
    )
    raw = bytearray(members[victim])
    raw[-1] ^= 0xFF
    members[victim] = bytes(raw)
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_STORED) as z:
        for n, data in members.items():
            z.writestr(n, data)
    with open(path, "wb") as f:
        f.write(buf.getvalue())
    return victim


def test_digest_mismatch_refetches_from_next_tier(tmp_path):
    """A corrupted local shard fails its sha256 on fetch; the loader
    journals the fallback and re-fetches that shard from the store —
    the restore still lands bit-identical."""
    devs = jax.devices()
    mesh = Mesh(np.array(devs).reshape(2, 4), ("dp", "tp"))
    state = _state(mesh, P("dp", "tp"))
    want = np.asarray(state["w"])
    _save_all(ckpts := _fleet(tmp_path, 2, 4), 100, state)
    _close_all(ckpts)

    victim = _corrupt_one_member(
        str(tmp_path / "ram0" / "step-100-proc-0")
    )
    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram0"),
        persist_interval=0, use_orbax=False,
        process_index=0, n_processes=2,
        proc_of_device=lambda d: d.id // 4,
    )
    got, step = r.restore(
        target=_zeros_like(state, mesh, P("dp", "tp")), step=100
    )
    r.close()
    assert step == 100
    assert np.array_equal(np.asarray(got["w"]), want)

    fb = [
        e for e in events("checkpoint.restore_fallback")
        if e["data"].get("reason") == "digest_mismatch"
    ]
    assert fb, "digest mismatch must journal a restore_fallback"
    rf = events("ckpt.shard_refetch")
    assert rf and rf[-1]["data"]["failed_tier"] == "local"
    assert victim  # the corrupted member really existed


# ------------------------------------------------- other format versions


def test_a_v1_archive_is_refused_by_name(tmp_path, monkeypatch):
    """An archive that states format version 1 is input from outside
    the program: it is refused with an error naming the file and the
    version, and the walk-down restores the v2 step beside it."""
    state = {"w": np.arange(12, dtype=np.float32), "n": 3}
    c = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram"),
        persist_interval=1, use_orbax=False,
    )
    c.save(100, state, force_persist=True)
    c.wait()
    monkeypatch.setattr(ckpt_store, "_FORMAT_VERSION", 1)
    c.save(110, {"w": state["w"] + 1, "n": 4}, force_persist=True)
    c.wait()
    c.close()
    monkeypatch.undo()

    store = ckpt_store.get_store(str(tmp_path / "store"))
    v1_key = ckpt_store.step_key(110, 0)
    with store.open_read(v1_key) as f:
        with pytest.raises(ckpt_store.ArchiveError) as err:
            ckpt_store.read_manifest(f)
    assert v1_key in str(err.value) and "version 1" in str(err.value)
    with store.open_read(v1_key) as f:
        with pytest.raises(ckpt_store.ArchiveError, match="version 1"):
            ckpt_store.snapshot_from_file(f)

    r = FlashCheckpointer(
        persist_dir=str(tmp_path / "store"),
        ram_dir=str(tmp_path / "ram-new"),
        persist_interval=0, use_orbax=False,
    )
    got, step = r.restore()
    r.close()
    assert step == 100
    assert np.array_equal(got["w"], state["w"])
    refused = [
        e["data"] for e in events("checkpoint.restore_fallback")
        if e["data"]["step"] == 110
    ]
    assert refused and refused[-1]["reason"] == "archive_error"
    assert "version 1" in refused[-1]["error"]


# ------------------------------------------------------------ bench smoke


def test_ckpt_topology_bench_smoke():
    """The topology bench's tier-1 smoke tier: dedup_factor from 4
    replicating virtual hosts clears the 3.5x acceptance bar, the
    cross-topology restore is bit-identical, and the kill-a-host phase
    reassembles entirely from the peer tier."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DLROVER_TPU_METRICS_PORT="off")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "ckpt_topology.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=180, env=env,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["ok"] is True
    assert result["dedup_factor"] >= 3.5
    assert result["reshard_identical"] is True
    assert result["peer_identical"] is True
    assert result["peer_hit_ratio"] >= 0.99
    assert result["bytes_written_per_host"] > 0
    assert result["restore_ms"] > 0
