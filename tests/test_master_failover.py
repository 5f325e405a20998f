"""Master failover: durable job-state journal + master-kill chaos drill.

Fast unit path: the ledger/journal round-trip (DatasetShardCheckpoint
detail fields, keep_doing restore semantics, MasterStateJournal
persistence, rendezvous round monotonicity, speed-monitor restore) runs
in-process with no subprocesses.

E2e drill (``test_master_kill_drill``): a real master subprocess serves
two real worker subprocesses; ``DLROVER_FAULT_INJECT=master_crash@4``
kills the master mid-epoch (rc 28); a second master starts against the
same ``--state_dir`` and port; both workers reconnect (connection
supervisor), the job finishes, and the test asserts exactly-once shard
delivery, a monotonic rendezvous round, and the
``master.restored`` / ``agent.master_lost`` / ``agent.master_reconnected``
journal events.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from dlrover_tpu.common.constants import NodeType, RendezvousName, TaskType
from dlrover_tpu.fault_tolerance.injection import MASTER_CRASH_EXIT_CODE
from dlrover_tpu.master.elastic_training.rdzv_manager import (
    ElasticTrainingRendezvousManager,
)
from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
from dlrover_tpu.master.shard.base_dataset_manager import (
    DatasetShardCheckpoint,
)
from dlrover_tpu.master.shard.dataset_splitter import new_dataset_splitter
from dlrover_tpu.master.shard.task_manager import TaskManager
from dlrover_tpu.master.state_journal import (
    MasterStateJournal,
    build_master_state_journal,
)
from dlrover_tpu.util.state_store import build_state_store

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- unit path


def test_checkpoint_detail_roundtrip():
    ckpt = DatasetShardCheckpoint(
        dataset_name="ds",
        todo=[[0, 10], [10, 20]],
        doing=[[20, 30]],
        epoch=1,
        todo_ids=[3, 4],
        doing_detail=[[2, 1, 20, 30, 7]],
        next_task_id=5,
        completed_step=2,
    )
    restored = DatasetShardCheckpoint.from_json(ckpt.to_json())
    assert restored.todo_ids == [3, 4]
    assert restored.doing_detail == [[2, 1, 20, 30, 7]]
    assert restored.next_task_id == 5
    assert restored.completed_step == 2


def test_checkpoint_legacy_json_still_loads():
    # a pre-journal checkpoint has none of the detail fields
    legacy = json.dumps({
        "dataset_name": "ds", "todo": [[0, 10]], "doing": [[10, 20]],
        "epoch": 1,
    })
    ckpt = DatasetShardCheckpoint.from_json(legacy)
    assert ckpt.doing_detail is None
    assert ckpt.next_task_id == 0


def _new_journaled_task_manager(state_dir, params):
    journal = build_master_state_journal("drill-job", state_dir=state_dir)
    tm = TaskManager()
    tm.attach_state_journal(journal)
    splitter = new_dataset_splitter(
        shuffle=params["shuffle"],
        shard_size=params["batch_size"]
        * params["num_minibatches_per_shard"],
        dataset_size=params["dataset_size"],
        num_epochs=params["num_epochs"],
        dataset_name=params["dataset_name"],
    )
    tm.new_dataset(
        batch_size=params["batch_size"],
        dataset_size=params["dataset_size"],
        dataset_name=params["dataset_name"],
        dataset_splitter=splitter,
        task_type=TaskType.TRAINING,
        params=params,
    )
    return journal, tm


PARAMS = dict(
    batch_size=4, num_epochs=1, dataset_size=32, shuffle=False,
    num_minibatches_per_shard=1, dataset_name="drill-ds",
    task_type=TaskType.TRAINING, storage_type="table",
)


def test_ledger_roundtrip_exactly_once(tmp_path):
    """The fast path of the master-kill drill: every shard-state
    mutation is journaled, and a fresh TaskManager restored with
    keep_doing=True accepts the surviving workers' in-flight completion
    reports instead of re-dispatching their shards."""
    state_dir = str(tmp_path)
    _, tm = _new_journaled_task_manager(state_dir, PARAMS)

    t0 = tm.get_dataset_task(NodeType.WORKER, 0, "drill-ds")
    t1 = tm.get_dataset_task(NodeType.WORKER, 1, "drill-ds")
    t2 = tm.get_dataset_task(NodeType.WORKER, 0, "drill-ds")
    assert tm.report_dataset_task("drill-ds", t0.task_id, True)
    consumed = [(t0.shard.start, t0.shard.end)]

    # "master crash": rebuild master-side state from the journal alone,
    # the way dist_master._restore_state does
    journal2 = build_master_state_journal("drill-job", state_dir=state_dir)
    assert journal2.has_state()
    assert journal2.saved_datasets() == ["drill-ds"]
    params, ckpt = journal2.load_dataset("drill-ds")
    assert params["batch_size"] == 4
    _, tm2 = _new_journaled_task_manager(state_dir, params)
    assert tm2.restore_dataset_from_checkpoint(ckpt, keep_doing=True)

    # in-flight completions are accepted under their ORIGINAL task ids
    assert tm2.report_dataset_task("drill-ds", t1.task_id, True)
    assert tm2.report_dataset_task("drill-ds", t2.task_id, True)
    consumed += [(t1.shard.start, t1.shard.end),
                 (t2.shard.start, t2.shard.end)]

    # drain the rest: the union must cover the dataset exactly once
    while True:
        t = tm2.get_dataset_task(NodeType.WORKER, 0, "drill-ds")
        if t.task_id < 0:
            break
        consumed.append((t.shard.start, t.shard.end))
        assert tm2.report_dataset_task("drill-ds", t.task_id, True)
    ranges = sorted(consumed)
    assert ranges[0][0] == 0 and ranges[-1][1] == 32
    for (_, end), (start, _) in zip(ranges, ranges[1:]):
        assert end == start, f"gap/overlap in {ranges}"
    assert tm2.finished()


def test_keep_doing_false_requeues_in_flight(tmp_path):
    """The legacy worker-driven restore still requeues doing shards."""
    state_dir = str(tmp_path)
    _, tm = _new_journaled_task_manager(state_dir, PARAMS)
    t0 = tm.get_dataset_task(NodeType.WORKER, 0, "drill-ds")
    ckpt = tm.get_dataset_checkpoint("drill-ds").to_json()
    _, tm2 = _new_journaled_task_manager(str(tmp_path / "b"), PARAMS)
    assert tm2.restore_dataset_from_checkpoint(ckpt, keep_doing=False)
    # the in-flight shard went back to todo: its old id is unknown
    assert not tm2.report_dataset_task("drill-ds", t0.task_id, True)


def test_journal_kv_rdzv_speed_roundtrip(tmp_path):
    store = build_state_store("file", str(tmp_path))
    journal = MasterStateJournal(store, "job/with spaces")
    assert not journal.has_state()
    journal.save_kv({"a": b"\x00\xffbin", "b": b"text"})
    journal.save_rdzv_round(RendezvousName.TRAINING, 7)
    journal.save_global_step(42, batch_feed=True)
    journal.mark_started()
    assert journal.has_state()

    reopened = MasterStateJournal(
        build_state_store("file", str(tmp_path)), "job/with spaces"
    )
    assert reopened.load_kv() == {"a": b"\x00\xffbin", "b": b"text"}
    assert reopened.load_rdzv_rounds() == {RendezvousName.TRAINING: 7}
    assert reopened.load_global_step() == (42, True)
    reopened.clear()
    assert not reopened.has_state()


def test_fresh_wipes_prior_state(tmp_path):
    journal = build_master_state_journal("j", state_dir=str(tmp_path))
    journal.save_global_step(9)
    fresh = build_master_state_journal(
        "j", state_dir=str(tmp_path), fresh=True
    )
    assert fresh.load_global_step() == (0, False)
    assert build_master_state_journal("j") is None  # no dir, no env


def test_rdzv_round_restore_is_monotonic():
    mgr = ElasticTrainingRendezvousManager()
    mgr.restore_round(5)
    assert mgr._rdzv_round == 5
    mgr.restore_round(3)  # a stale journal can never regress the round
    assert mgr._rdzv_round == 5


def test_speed_monitor_restore():
    sm = SpeedMonitor()
    sm.restore_global_step(40)
    assert sm.completed_global_step >= 40
    sm_batch = SpeedMonitor()
    sm_batch.restore_global_step(17, batch_feed=True)
    assert sm_batch._batches_done == 17


# ----------------------------------------------------------------- e2e drill


def _drill_env(tmp, journal_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), REPO) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("DLROVER_FAULT_INJECT", None)
    env["DLROVER_TPU_JOURNAL"] = journal_path
    env["DLROVER_TPU_LOG_LEVEL"] = "INFO"
    return env


def _spawn_master(tmp, env, state_dir, port, tag):
    cmd = [
        sys.executable, "-m", "dlrover_tpu.master.main",
        "--platform", "process", "--node_num", "0",
        "--job_name", "failover-drill", "--port", str(port),
        "--state_dir", state_dir,
        "--autoscale_interval", "600", "--check_interval", "0.2",
    ]
    return subprocess.Popen(
        cmd, cwd=REPO, env=env,
        stdout=open(os.path.join(tmp, f"master-{tag}.out"), "w"),
        stderr=open(os.path.join(tmp, f"master-{tag}.err"), "w"),
        start_new_session=True,
    )


def _master_port(tmp, tag, proc, timeout=30):
    path = os.path.join(tmp, f"master-{tag}.out")
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            for line in open(path):
                if line.startswith("DLROVER_TPU_MASTER_PORT="):
                    return int(line.strip().split("=", 1)[1])
        assert proc.poll() is None, _tail(tmp, f"master-{tag}.err")
        time.sleep(0.2)
    raise AssertionError(
        f"master-{tag} never printed its port; "
        + _tail(tmp, f"master-{tag}.err")
    )


def _tail(tmp, name, n=3000):
    path = os.path.join(tmp, name)
    try:
        return f"{name}: " + open(path).read()[-n:]
    except OSError:
        return f"{name}: <missing>"


def _wait(proc, timeout, what, tmp, logs):
    try:
        return proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise AssertionError(
            f"{what} did not exit in {timeout}s; "
            + " | ".join(_tail(tmp, l) for l in logs)
        )


def _killpg(proc, sig=signal.SIGKILL):
    try:
        os.killpg(os.getpgid(proc.pid), sig)
    except (ProcessLookupError, PermissionError):
        pass


def test_master_kill_drill(tmp_path):
    tmp = str(tmp_path)
    state_dir = os.path.join(tmp, "state")
    journal_path = os.path.join(tmp, "journal.jsonl")
    env = _drill_env(tmp, journal_path)
    # bound the lost-reply window: a shard whose dispatch reply died
    # with the master is requeued by the watchdog within ~21s
    master_env = dict(env, DLROVER_TPU_CTX_TASK_PROCESS_TIMEOUT="20")
    worker_env = dict(env, DLROVER_TPU_MASTER_RECONNECT_TIMEOUT="90")

    procs = []
    try:
        m1 = _spawn_master(
            tmp, dict(master_env, DLROVER_FAULT_INJECT="master_crash@4"),
            state_dir, 0, "1",
        )
        procs.append(m1)
        port = _master_port(tmp, "1", m1)

        workers = []
        for node_id in (0, 1):
            w = subprocess.Popen(
                [sys.executable,
                 os.path.join(REPO, "tests", "_master_failover_worker.py"),
                 "--master_addr", f"localhost:{port}",
                 "--node_id", str(node_id),
                 "--out", os.path.join(tmp, f"worker-{node_id}.txt")],
                cwd=REPO, env=worker_env,
                stdout=open(os.path.join(tmp, f"worker-{node_id}.out"), "w"),
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
            workers.append(w)
            procs.append(w)

        # phase 1: the injector kills master #1 once the reported global
        # step reaches 4 — rc 28, distinct from worker/job failures
        rc1 = _wait(m1, 120, "master #1 (crash expected)", tmp,
                    ["master-1.err", "worker-0.out", "worker-1.out"])
        assert rc1 == MASTER_CRASH_EXIT_CODE, (
            f"master #1 exited rc={rc1}, wanted injected crash "
            f"rc={MASTER_CRASH_EXIT_CODE}; " + _tail(tmp, "master-1.err")
        )

        # phase 2: restart against the same state dir and port, no
        # injection — workers must reconnect without being restarted
        m2 = _spawn_master(tmp, master_env, state_dir, port, "2")
        procs.append(m2)

        for node_id, w in enumerate(workers):
            rc = _wait(w, 120, f"worker {node_id}", tmp,
                       ["worker-0.out", "worker-1.out", "master-2.err"])
            assert rc == 0, (
                f"worker {node_id} exited rc={rc}; "
                + _tail(tmp, f"worker-{node_id}.out")
            )
        # the master exits 0 (SUCCEEDED) once the dataset completes
        rc2 = _wait(m2, 60, "master #2", tmp, ["master-2.err"])
        assert rc2 == 0, _tail(tmp, "master-2.err")
    finally:
        for p in procs:
            _killpg(p, signal.SIGTERM)
        time.sleep(0.5)
        for p in procs:
            _killpg(p)

    # ---- exactly-once shard delivery across the restart -------------
    ranges = []
    rounds = {}
    for node_id in (0, 1):
        lines = open(os.path.join(tmp, f"worker-{node_id}.txt")).read()
        assert "DONE" in lines, lines
        for line in lines.splitlines():
            parts = line.split()
            if parts[0] == "SHARD":
                ranges.append((int(parts[1]), int(parts[2])))
            elif parts[0] in ("ROUND1", "ROUND2"):
                rounds[(node_id, parts[0])] = int(parts[1])
    ranges.sort()
    assert ranges[0][0] == 0 and ranges[-1][1] == 96, ranges
    for (_, end), (start, _) in zip(ranges, ranges[1:]):
        assert end == start, f"shard gap/overlap at {start}: {ranges}"
    # both workers consumed a share (the crash didn't serialize the job)
    assert len(ranges) == 96 // 4

    # ---- monotonic rendezvous rounds across the restart --------------
    for node_id in (0, 1):
        assert rounds[(node_id, "ROUND2")] > rounds[(node_id, "ROUND1")], (
            rounds
        )

    # ---- failover observability (telemetry journal) ------------------
    from dlrover_tpu.telemetry.journal import read_journal

    events = read_journal(journal_path)
    kinds = [e.get("kind") for e in events]
    assert "fault.injected" in kinds
    assert "master.restored" in kinds
    assert kinds.count("agent.master_lost") >= 2  # one per worker
    assert kinds.count("agent.master_reconnected") >= 2
    restored = next(e for e in events if e["kind"] == "master.restored")
    assert restored["data"]["datasets"] == ["failover-drill"]
    # step persists are rate-limited to ~1/s, so the restored step may
    # trail the crash step — it only needs to be monotonic, not exact
    assert restored["data"]["global_step"] >= 1
