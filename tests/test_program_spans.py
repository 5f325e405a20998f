"""ISSUE 26: the spans inside the program that ``setup_s`` and a
restart are made of. Every test runs in this process on the CPU."""

import pathlib
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.telemetry import tracing


@pytest.fixture
def traced():
    """Tracing on (ring only) for one test, the ring its own."""
    tracing.disable()
    tracing.clear()
    tracing.set_step(-1)
    tracing.enable()
    yield tracing
    tracing.disable()
    tracing.clear()
    tracing.set_step(-1)


def _named(records, name):
    return [r for r in records if r["name"] == name]


def _children(records, parent):
    return [r for r in records if r.get("parent") == parent["span"]]


def _descendants(records, parent):
    out = []
    for child in _children(records, parent):
        out += [child] + _descendants(records, child)
    return out


# ----------------------------------------------------------- self_times


def test_self_times_on_a_hand_made_tree():
    recs = [
        {"name": "root", "span": "r", "ts": 10.0, "dur": 10.0},
        # two children that overlap each other for a second
        {"name": "a", "span": "a", "parent": "r", "ts": 11.0, "dur": 3.0},
        {"name": "b", "span": "b", "parent": "r", "ts": 13.0, "dur": 3.0},
        # a child that outlives its parent is cut to it
        {"name": "c", "span": "c", "parent": "r", "ts": 19.0, "dur": 5.0},
        {"name": "leaf", "span": "l", "parent": "a", "ts": 11.5,
         "dur": 1.0},
        # no id: left out; its parent is not among the records
        {"name": "anon", "parent": "gone", "ts": 0.0, "dur": 1.0},
    ]
    own = tracing.self_times(recs)
    assert own == pytest.approx(
        {"r": 10.0 - 5.0 - 1.0, "a": 2.0, "b": 3.0, "c": 5.0, "l": 1.0}
    )


# ------------------------------------------------- checkpoint, both ways


def _sharded_state():
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("x", "y"))
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
    return {
        "w": put(jnp.arange(64 * 32, dtype=jnp.float32).reshape(64, 32),
                 P("x", "y")),
        "h": put(jnp.ones((16, 8), jnp.bfloat16), P(None, "y")),
        "b": put(jnp.arange(8, dtype=jnp.float32), P()),
    }


def test_ram_save_and_restore_are_itemised(tmp_path, traced):
    from dlrover_tpu.trainer.checkpoint import FlashCheckpointer

    state = _sharded_state()
    size = sum(x.nbytes for x in jax.tree.leaves(state))
    ckpt = FlashCheckpointer(
        persist_dir=str(tmp_path / "persist"),
        ram_dir=str(tmp_path / "ram"),
        persist_interval=0, use_orbax=False,
    )
    try:
        ckpt.save(3, state, durable=True)
        recs = traced.tail(4096)
        (serialize,) = _named(recs, "ckpt.serialize")
        under = _children(recs, serialize)
        assert {r["name"] for r in under} == {
            "ckpt.write.materialize", "ckpt.write.encode",
            "ckpt.write.io",
        }
        (materialize,) = _named(recs, "ckpt.write.materialize")
        # every device holds its shard: replicated ones are staged a
        # device, written once
        assert materialize["attrs"]["bytes"] >= size
        io = _named(recs, "ckpt.write.io")
        digests = _named(recs, "ckpt.write.digest")
        assert len(io) == len(digests) == len(
            _named(recs, "ckpt.write.encode")
        )
        assert {d["parent"] for d in digests} == {r["span"] for r in io}
        assert all(d["dur"] <= p["dur"] for d, p in zip(digests, io))

        traced.clear()
        restored, step = ckpt.restore(target=state)
        assert step == 3
        for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        recs = traced.tail(4096)
        (restore,) = _named(recs, "ckpt.restore")
        assert restore["attrs"] == {
            "step": 3, "tier": "ram", "leaves": 3, "bytes": size,
        }
        below = _descendants(recs, restore)
        names = {r["name"] for r in below}
        # ``assemble`` is there although the layout did not change:
        # the archive's normalised indices (0:n) are looked up by the
        # sharding's own slices (None:None), which misses wherever a
        # dimension is not sharded (PERF.md section 5)
        assert names == {
            "ckpt.restore.select", "ckpt.restore.digest",
            "ckpt.restore.fetch", "ckpt.restore.decode",
            "ckpt.restore.assemble", "ckpt.restore.device_put",
        }
        # the children cover the restore: what is left is its own
        own = tracing.self_times(recs)[restore["span"]]
        covered = restore["dur"] - own
        assert covered == pytest.approx(
            sum(r["dur"] for r in _children(recs, restore)), rel=0.05
        )
        assert covered > 0.5 * restore["dur"]
        # bytes ride on the spans: what went to the devices is the
        # state, shard by shard; what was read is every saved member
        puts = _named(below, "ckpt.restore.device_put")
        assert sum(r["attrs"]["bytes"] for r in puts) == sum(
            s.data.nbytes for x in jax.tree.leaves(state)
            for s in x.addressable_shards
        )
        fetched = sum(
            r["attrs"]["bytes"] for r in _named(below, "ckpt.restore.fetch")
        )
        assert fetched == materialize["attrs"]["bytes"] - 3 * 8 * 4 - (
            16 * 8 * 2  # replicas of "b" and "h" are written once
        )
        # only the extension dtype needs decoding: the halves of "h"
        assert [
            r["attrs"]["bytes"]
            for r in _named(below, "ckpt.restore.decode")
        ] == [16 * 4 * 2] * 2
    finally:
        ckpt.close()


def test_v2_loader_restore_is_itemised(tmp_path, traced):
    """The layout-free path (another topology, peers, the store)
    carries the same names, assembled domains included."""
    from dlrover_tpu.checkpoint import loader
    from dlrover_tpu.trainer import ckpt_store
    from dlrover_tpu.trainer.checkpoint import (
        _materialize_staged,
        _stage_local_shards,
    )

    state = _sharded_state()
    topology = {"n_processes": 1, "process_index": 0}
    path = tmp_path / "step-1"
    with open(path, "wb") as f:
        ckpt_store.snapshot_to_file(
            _materialize_staged(
                _stage_local_shards(state, topology=topology)
            ), 1, f, topology=topology,
        )
    # restore under another layout: every needed domain is assembled
    mesh = Mesh(np.array(jax.devices()[:2]), ("z",))
    target = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(
                mesh, P("z") if x.ndim == 2 else P()),
        ), state,
    )
    traced.clear()
    with open(path, "rb") as f:
        catalog = loader.StepCatalog.from_archive_manifest(
            ckpt_store.read_manifest(f)
        )
        source = loader.LocalArchiveSource(f)
        restored, _, stats = loader.restore_from_catalog(
            catalog, target, [source]
        )
        source.close()
    np.testing.assert_array_equal(
        np.asarray(restored["w"]), np.asarray(state["w"])
    )
    recs = traced.tail(4096)
    fetches = _named(recs, "ckpt.restore.fetch")
    assert sum(r["attrs"]["bytes"] for r in fetches) == stats["bytes"]
    assert {r["attrs"]["tier"] for r in fetches} == {"local"}
    assert len(_named(recs, "ckpt.restore.digest")) == len(fetches)
    assert len(_named(recs, "ckpt.restore.decode")) == len(fetches)
    assembled = _named(recs, "ckpt.restore.assemble")
    # the fetches an assembly makes are its children (a member fetched
    # for an earlier domain comes from the fetcher's memo instead)
    assert {c["name"] for a in assembled for c in _children(recs, a)} == {
        "ckpt.restore.fetch", "ckpt.restore.digest",
        "ckpt.restore.decode",
    }
    puts = _named(recs, "ckpt.restore.device_put")
    assert sum(r["attrs"]["bytes"] for r in puts) == sum(
        s.data.nbytes for x in jax.tree.leaves(restored)
        for s in x.addressable_shards
    )


# ------------------------------------------------------ process bootstrap


def test_init_from_env_opens_the_backend_inside_a_span(
        traced, tmp_path, monkeypatch):
    from dlrover_tpu.trainer.distributed import init_from_env

    # as given where the job set it: this process then keeps the
    # (absent) cache jax read at import, and names no directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    env = init_from_env()
    assert not env.is_distributed
    recs = traced.tail(4096)
    (opened,) = _named(recs, "boot.backend_open")
    assert opened["attrs"] == {
        "platform": "cpu", "device_count": len(jax.devices()),
    }
    assert _named(recs, "boot.compile_cache_setup")
    # one process: nothing to initialise across processes
    assert not _named(recs, "boot.distributed_init")


def test_compiles_become_spans_tagged_with_the_step(traced):
    from dlrover_tpu.trainer import compile_cache

    compile_cache.trace_compiles()
    compile_cache.trace_compiles()  # one listener, however often
    traced.set_step(7)

    @jax.jit
    def f(x):
        return jnp.tanh(x) * 3 + 1

    x = jnp.arange(12.0)
    f(x).block_until_ready()
    recs = traced.tail(4096)
    mine = [r for r in recs
            if r["attrs"].get("fun_name") in ("f", "jit(f)")]
    assert [r["name"] for r in mine].count("xla.backend_compile") == 1
    assert {"xla.trace", "xla.lower"} <= {r["name"] for r in mine}
    assert all(r["step"] == 7 for r in mine)
    assert all(
        r["attrs"]["event"].startswith("/jax/core/compile/") for r in mine
    )
    # the second call runs what the first compiled
    traced.clear()
    traced.set_step(8)
    f(x).block_until_ready()
    assert not [r for r in traced.tail(4096)
                if r["name"].startswith("xla.")]


def test_a_span_in_a_jax_process_is_a_profiler_annotation(
        traced, monkeypatch):
    """One clock with the device trace: a live span enters a
    ``TraceAnnotation`` of its name where jax is imported, and none
    where it is not."""
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "_annotation_cls", Annotation)
    with tracing.span("data.stage"):
        pass
    assert seen == [("enter", "data.stage"), ("exit", "data.stage")]
    # a process that never imported jax (launcher, agent) enters none
    monkeypatch.setattr(tracing, "_annotation_cls", None)
    monkeypatch.delitem(sys.modules, "jax")
    with tracing.span("agent.spawn"):
        pass
    assert len(seen) == 2
    assert tracing._annotation_cls is None


# ------------------------------------------------------------------ agent


class _StubClient:
    """What a restart needs of the master, answered in place."""

    master_addr = "stub:0"

    def __init__(self):
        self.failures = []

    def report_failure(self, message, level, restart_count):
        self.failures.append((message, restart_count))

    def report_rdzv_params(self, *params):
        pass

    def join_rendezvous(self, node_rank, local_world_size, name):
        return 1

    def get_comm_world(self, name, node_rank):
        return 1, 0, {0: 1}

    def kv_store_set(self, key, value):
        pass


def test_agent_restart_is_a_span_with_its_steps(traced, monkeypatch):
    from dlrover_tpu.agent.elastic.training import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
        RunResult,
        WorkerState,
    )

    monkeypatch.setenv("DLROVER_TPU_METRICS_PORT", "off")
    client = _StubClient()
    agent = ElasticTrainingAgent(
        ElasticLaunchConfig(entrypoint="sleep", args=["30"]), client
    )
    try:
        agent._initialize_workers()
        first = agent._proc
        traced.clear()
        agent._restart_workers(
            "process_failure", failed=RunResult(WorkerState.FAILED, 17),
            rc=17,
        )
        assert first.poll() is not None and agent._proc is not first
        assert client.failures == [
            ("training process exited rc=17", 1)
        ]
        recs = traced.tail(4096)
        (restart,) = _named(recs, "agent.restart")
        assert restart["attrs"] == {"reason": "process_failure"}
        steps = sorted(_children(recs, restart), key=lambda r: r["ts"])
        assert [r["name"] for r in steps] == [
            "agent.report_failure", "agent.kill_group",
            "agent.rendezvous", "agent.spawn",
        ]
        assert steps[0]["attrs"] == {"rc": 17}
        assert steps[2]["attrs"] == {"round": 1, "world": 1}
        assert steps[3]["attrs"] == {
            "restart_count": 1, "pid": agent._proc.pid,
        }
        # a worker found dead is stamped where the agent learns of it
        agent._proc.kill()
        agent._proc.wait()
        traced.clear()
        assert agent._monitor_workers().state == WorkerState.FAILED
        (seen,) = _named(traced.tail(4096), "agent.exit_detected")
        assert seen["dur"] == 0.0
        assert seen["attrs"] == {"rc": -9, "restart_count": 2}
    finally:
        agent.stop()


# ------------------------------------------------ data plane, supervision


def test_the_fill_thread_and_the_step_report_are_spans(traced):
    import threading

    from dlrover_tpu.data.shm_dataloader import DevicePrefetch
    from dlrover_tpu.trainer.elastic import ElasticTrainer

    def slow():
        for i in range(3):
            time.sleep(0.02)
            yield np.full((2,), i, np.float32)

    got = [int(b[0]) for b in DevicePrefetch(slow(), depth=1)]
    assert got == [0, 1, 2]
    recs = traced.tail(4096)
    # three batches and the end of the stream
    assert len(_named(recs, "data.fetch")) == 4
    assert len(_named(recs, "data.stage")) == 3
    assert max(r["dur"] for r in _named(recs, "data.fetch")) > 0.01
    # the consumer's side of next(batch) crosses no span site: a live
    # span there cost the yardstick's data_wait_ms, which times the
    # same wait from outside, 0.07-0.35 ms of its 0.08 on the chip
    assert threading.get_ident() not in {
        r["tid"] for r in recs if r["name"].startswith("data.")}

    reporter = ElasticTrainer(
        lambda p, b: 0.0, optax.identity(), max_nodes=1, cur_nodes=1,
    )
    traced.clear()
    reporter.report_step(5)
    (report,) = _named(traced.tail(4096), "train.report_step")
    assert report["step"] == 5


# ---------------------------------------------------------- tracing off


#: every ``tracing.span`` site this issue added
NEW_SITES = [
    "launch.run", "launch.master_start", "agent.rendezvous",
    "agent.spawn", "agent.restart", "agent.report_failure",
    "agent.kill_group", "boot.compile_cache_setup",
    "boot.distributed_init", "boot.backend_open",
    "boot.master_client", "boot.data_plane", "ckpt.restore",
    "ckpt.restore.select", "ckpt.restore.fetch",
    "ckpt.restore.digest", "ckpt.restore.decode",
    "ckpt.restore.assemble", "ckpt.restore.device_put",
    "ckpt.write.materialize", "ckpt.write.encode", "ckpt.write.io",
    "train.report_step",
]


@pytest.mark.parametrize("name", NEW_SITES)
def test_with_tracing_off_a_site_gets_the_shared_noop(name):
    """One global read a site: the name is in the program as a span
    site, and with tracing off the site is handed the one no-op."""
    tracing.disable()
    root = pathlib.Path(tracing.__file__).resolve().parents[1]
    sites = [
        p for p in root.rglob("*.py")
        if re.search(
            r"tracing\.span\(\s*\"" + re.escape(name) + "\"", p.read_text()
        )
    ]
    assert sites, f"no span site named {name}"
    assert tracing.span(name, {"bytes": 1}) is tracing._NOOP
    before = len(tracing.tail(4096))
    with tracing.span(name):
        tracing.add_span(name, time.time(), 0.0)
    assert len(tracing.tail(4096)) == before


# ------------------------------------------------------------ named scopes


def test_train_step_names_loss_and_optimizer():
    from dlrover_tpu.models import make_trainer_for
    from dlrover_tpu.models.llama import LlamaConfig
    from dlrover_tpu.parallel.mesh import create_mesh

    mesh = create_mesh([("data", 1), ("fsdp", 1)],
                       devices=jax.devices()[:1])
    trainer = make_trainer_for(
        LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_layers=1, num_heads=2, num_kv_heads=1, max_seq_len=16,
        ),
        mesh, strategy="fsdp", optimizer=optax.adamw(1e-3),
    )
    params, opt_state = trainer.abstract_state()
    batch = jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)
    text = trainer.train_step.lower(
        params, opt_state, (batch, batch)
    ).as_text(debug_info=True)
    assert "/loss/" in text
    assert "/optimizer/" in text
