"""Elastic agent tests against an in-process master with loopback gRPC.

Mirrors reference dlrover/python/tests/test_elastic_training_agent.py:
agents constructed with explicit node ranks against a real local master.
"""

import os
import sys
import tempfile
import threading
import time

import pytest

from dlrover_tpu.agent.elastic.training import (
    ElasticLaunchConfig,
    ElasticTrainingAgent,
    MasterRendezvousHandler,
    WorkerState,
)
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.sharding.client import (
    IndexShardingClient,
    ShardingClient,
)
from dlrover_tpu.common.constants import NodeType
from dlrover_tpu.master.local_master import LocalJobMaster


@pytest.fixture()
def master():
    m = LocalJobMaster(port=0)
    m.prepare()
    yield m
    m.stop()


def _client(master, node_id):
    return MasterClient(master.addr, node_id=node_id,
                        node_type=NodeType.WORKER)


def test_sharding_client_batch_done(master):
    c = _client(master, 0)
    sc = ShardingClient(
        dataset_name="d", batch_size=4, num_epochs=1, dataset_size=16,
        num_minibatches_per_shard=2, master_client=c,
    )
    shard = sc.fetch_shard()
    assert shard is not None
    assert shard.end - shard.start == 8
    assert not sc.report_batch_done()  # 1 of 2 minibatches
    assert sc.report_batch_done()  # task complete -> reported
    sc.fetch_shard()
    sc.report_batch_done()
    sc.report_batch_done()
    assert sc.fetch_shard() is None  # exhausted
    assert master.task_manager.finished()


def test_index_sharding_client(master):
    c = _client(master, 0)
    sc = IndexShardingClient(
        dataset_name="idx", batch_size=4, num_epochs=1, dataset_size=10,
        num_minibatches_per_shard=1, master_client=c,
    )
    seen = []
    while True:
        idx = sc.fetch_sample_index()
        if idx is None:
            break
        seen.append(idx)
    assert sorted(seen) == list(range(10))
    sc.stop()


def test_rendezvous_handler_two_nodes(master):
    c0, c1 = _client(master, 0), _client(master, 1)
    c0.report_rdzv_params(min_nodes=2, max_nodes=2, waiting_timeout=1.0,
                          node_unit=1)
    results = {}

    def join(rank, client):
        h = MasterRendezvousHandler(client, rank, local_world_size=1,
                                    join_timeout=30)
        results[rank] = h.next_rendezvous()

    threads = [
        threading.Thread(target=join, args=(r, c))
        for r, c in ((0, c0), (1, c1))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=40)
    assert set(results) == {0, 1}
    _, world0, pid0, nproc0, coord0 = results[0]
    _, world1, pid1, nproc1, coord1 = results[1]
    assert world0 == world1 == {0: 1, 1: 1}
    assert (pid0, pid1) == (0, 1)
    assert nproc0 == nproc1 == 2
    assert coord0 == coord1  # both learned rank0's coordinator
    assert ":" in coord0


def _write_script(tmpdir, body: str) -> str:
    path = os.path.join(tmpdir, "entry.py")
    with open(path, "w") as f:
        f.write(body)
    return path


def test_agent_runs_process_to_success(master):
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "out.txt")
        script = _write_script(
            tmp,
            "import os\n"
            f"open({out!r}, 'w').write(os.environ['DLROVER_TPU_PROCESS_ID'])\n",
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, node_rank=0, monitor_interval=0.2,
            entrypoint=script,
        )
        c = _client(master, 0)
        c.report_rdzv_params(1, 1, 0.5, 1)
        agent = ElasticTrainingAgent(config, c)
        result = agent.run()
        assert result.state == WorkerState.SUCCEEDED
        assert open(out).read() == "0"


def test_agent_restarts_failed_process(master):
    """First run fails, second (after restart) succeeds."""
    with tempfile.TemporaryDirectory() as tmp:
        flag = os.path.join(tmp, "flag")
        script = _write_script(
            tmp,
            "import os, sys\n"
            f"if not os.path.exists({flag!r}):\n"
            f"    open({flag!r}, 'w').close()\n"
            "    sys.exit(3)\n",
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, node_rank=0, monitor_interval=0.2,
            max_restarts=2, entrypoint=script,
        )
        c = _client(master, 0)
        c.report_rdzv_params(1, 1, 0.5, 1)
        agent = ElasticTrainingAgent(config, c)
        result = agent.run()
        assert result.state == WorkerState.SUCCEEDED
        assert agent._restart_count == 2


def test_agent_gives_up_after_max_restarts(master):
    with tempfile.TemporaryDirectory() as tmp:
        script = _write_script(tmp, "import sys; sys.exit(7)\n")
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, node_rank=0, monitor_interval=0.2,
            max_restarts=1, entrypoint=script,
        )
        c = _client(master, 0)
        c.report_rdzv_params(1, 1, 0.5, 1)
        agent = ElasticTrainingAgent(config, c)
        result = agent.run()
        assert result.state == WorkerState.FAILED
        assert result.return_code == 7


def test_agent_restarts_on_membership_change(master):
    """A new node joining triggers re-rendezvous of the running agent
    (scale-up without job restart)."""
    with tempfile.TemporaryDirectory() as tmp:
        script = _write_script(tmp, "import time; time.sleep(30)\n")
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=2, node_rank=0, monitor_interval=0.2,
            rdzv_timeout=0.5, entrypoint=script,
        )
        # the agent re-reports its config's rdzv params on every join
        # (HA master restarts relearn them), so the config carries the
        # short timeout rather than a one-shot report here
        c0 = _client(master, 0)
        agent = ElasticTrainingAgent(config, c0)
        t = threading.Thread(target=agent.run, daemon=True)
        t.start()
        # wait for the first world (only node 0)
        deadline = time.time() + 20
        while agent._restart_count == 0 and time.time() < deadline:
            time.sleep(0.1)
        assert agent._restart_count == 1
        first_proc = agent._proc

        # second node appears
        c1 = _client(master, 1)
        h1 = MasterRendezvousHandler(c1, 1, 1, join_timeout=30)
        joined = {}

        def join_second():
            joined["res"] = h1.next_rendezvous()

        t2 = threading.Thread(target=join_second, daemon=True)
        t2.start()
        # agent should notice, kill the old proc, and re-rendezvous
        t2.join(timeout=30)
        assert "res" in joined
        _, world, _, nproc, _ = joined["res"]
        assert world == {0: 1, 1: 1}
        deadline = time.time() + 10
        while agent._restart_count < 2 and time.time() < deadline:
            time.sleep(0.1)
        assert agent._restart_count == 2
        assert first_proc.poll() is not None  # old process was stopped
        agent.stop()
        t.join(timeout=10)


_IMPORT_ONLY = """
import importlib.util, sys
import dlrover_tpu.master.main
import dlrover_tpu.trainer.elastic_run
import dlrover_tpu.agent.elastic.training
import dlrover_tpu.data.shm_dataloader
import dlrover_tpu.data.elastic_shm
# the example as a coworker sees it: imported, not run
spec = importlib.util.spec_from_file_location("__mp_main__", sys.argv[1])
spec.loader.exec_module(importlib.util.module_from_spec(spec))
from jax._src import xla_bridge
print("BACKENDS", sorted(xla_bridge._backends))
"""


def test_importing_the_launcher_path_initialises_no_backend():
    """One process for each chip: launcher, master, agent and the
    spawned coworkers (which re-import the example) may import jax but
    must never open a backend — the worker needs the chip."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _IMPORT_ONLY,
         os.path.join(repo, "examples", "llama_train.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo),
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKENDS []" in out.stdout, out.stdout[-500:]


def test_restart_waits_out_the_dead_workers_children(master):
    """A worker that dies on its own leaves its coworker children
    behind (own session group); the next incarnation must not start
    while one of them still runs."""
    with tempfile.TemporaryDirectory() as tmp:
        pidfile = os.path.join(tmp, "child.pid")
        script = os.path.join(tmp, "w.py")
        with open(script, "w") as f:
            f.write(
                "import os, subprocess, sys, time\n"
                "if os.environ['DLROVER_TPU_RESTART_COUNT'] == '0':\n"
                "    c = subprocess.Popen([sys.executable, '-c',\n"
                "        'import time; time.sleep(600)'])\n"
                f"    open({pidfile!r}, 'w').write(str(c.pid))\n"
                "    os._exit(17)\n"
                f"pid = int(open({pidfile!r}).read())\n"
                "try:\n"
                "    os.kill(pid, 0)\n"
                "    state = open(f'/proc/{pid}/stat').read()"
                ".rsplit(')', 1)[1].split()[0]\n"
                "    sys.exit(0 if state in 'ZX' else 3)\n"
                "except ProcessLookupError:\n"
                "    sys.exit(0)\n"
            )
        client = _client(master, 0)
        client.report_rdzv_params(1, 1, 0.5, 1)
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, node_rank=0, max_restarts=1,
            monitor_interval=0.2, entrypoint=script,
        )
        result = ElasticTrainingAgent(config, client).run()
        assert result.state == WorkerState.SUCCEEDED, result
