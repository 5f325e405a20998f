"""Evaluator side-job role (VERDICT r2 Missing #5 / M6 role depth):
spec-declared eval replicas provisioned next to the worker fleet, a
checkpoint-watching eval loop, and eval results flowing into the
master's custom-metric stats channel. Parity role:
dlrover/python/master/node/worker.py:32 EvaluatorManager + the
estimator evaluator replica."""

import jax.numpy as jnp
import numpy as np

from dlrover_tpu.common.constants import NodeType
from dlrover_tpu.common.node import Node, NodeResource
from dlrover_tpu.master.scaler.base_scaler import ScalePlan, Scaler
from dlrover_tpu.scheduler.job_spec import JobArgs
from dlrover_tpu.trainer.checkpoint import FlashCheckpointer
from dlrover_tpu.trainer.evaluator import CheckpointEvaluator


class RecordingScaler(Scaler):
    def __init__(self):
        super().__init__("j")
        self.launched = []

    def supports_role(self, node_type):
        return True  # test double: every role has an entrypoint

    def scale(self, plan: ScalePlan):
        self.launched.extend(plan.launch_nodes)


def test_spec_declares_evaluator_role(tmp_path):
    spec = tmp_path / "job.yaml"
    spec.write_text("""
apiVersion: dlrover-tpu/v1
kind: ElasticTpuJob
metadata: {name: evaljob}
spec:
  platform: process
  worker:
    replicas: 2
  evaluator:
    replicas: 1
    command: [python, eval.py]
    env: {EVAL_SPLIT: validation}
    resource: {cpu: 4, memory: 8Gi}
""")
    args = JobArgs.from_file(str(spec))
    assert args.evaluator_num == 1
    assert args.evaluator_command == ["python", "eval.py"]
    assert args.evaluator_env == {"EVAL_SPLIT": "validation"}
    assert args.evaluator_resource.memory == 8192


def test_job_manager_provisions_evaluators():
    from dlrover_tpu.master.node.dist_job_manager import (
        DistributedJobManager,
    )

    args = JobArgs(
        job_name="j", node_num=2,
        node_resource=NodeResource(cpu=1),
        evaluator_num=1,
        evaluator_resource=NodeResource(cpu=4),
    )
    scaler = RecordingScaler()
    jm = DistributedJobManager(job_args=args, scaler=scaler)
    jm.start()
    try:
        workers = [
            n for n in scaler.launched if n.type == NodeType.WORKER
        ]
        evals = [
            n for n in scaler.launched if n.type == NodeType.EVALUATOR
        ]
        assert len(workers) == 2
        assert len(evals) == 1
        assert not evals[0].critical
        # evaluators never gate job completion (workers-only check)
        assert not jm.all_workers_exited()
    finally:
        jm.stop()


def test_evaluator_failure_relaunches_without_touching_workers():
    from dlrover_tpu.common.constants import (
        NodeEventType,
        NodeExitReason,
        NodeStatus,
    )
    from dlrover_tpu.master.node.dist_job_manager import (
        DistributedJobManager,
    )
    from dlrover_tpu.master.watcher.base_watcher import NodeEvent

    args = JobArgs(
        job_name="j", node_num=1, evaluator_num=1,
        node_resource=NodeResource(cpu=1),
    )
    scaler = RecordingScaler()
    jm = DistributedJobManager(job_args=args, scaler=scaler)
    jm.start()
    try:
        ev = next(
            n for n in scaler.launched
            if n.type == NodeType.EVALUATOR
        )
        dead = Node(NodeType.EVALUATOR, ev.id, name=ev.name,
                    status=NodeStatus.FAILED)
        dead.set_exit_reason(NodeExitReason.KILLED)
        jm.process_event(NodeEvent(NodeEventType.MODIFIED, dead))
        emgr = jm._node_managers[NodeType.EVALUATOR]
        relaunched = [
            n for n in emgr.nodes.values() if not n.is_released
        ]
        assert len(relaunched) == 1
        assert relaunched[0].id != ev.id
        # the worker fleet is untouched
        wmgr = jm._node_managers[NodeType.WORKER]
        assert len(wmgr.unfinished_nodes()) == 1
        assert not jm.is_job_failed()
    finally:
        jm.stop()


def test_checkpoint_evaluator_loop(tmp_path):
    ckpt = FlashCheckpointer(
        persist_dir=str(tmp_path / "persist"),
        ram_dir=str(tmp_path / "ram"),
        persist_interval=0, use_orbax=False,
    )
    reported = []
    evaluated = []

    def eval_fn(state, step):
        evaluated.append(step)
        return {"loss": float(jnp.sum(state["w"]))}

    evaluator = CheckpointEvaluator(
        ckpt, eval_fn,
        report_fn=lambda step, res: reported.append((step, res)),
        poll_interval=0.01,
    )
    assert evaluator.poll_once() is None  # nothing saved yet
    ckpt.save(5, {"w": jnp.ones((4,))})
    ckpt.wait()
    res = evaluator.poll_once()
    assert res == {"loss": 4.0}
    assert evaluator.poll_once() is None  # same step: not re-evaluated
    ckpt.save(10, {"w": jnp.full((4,), 2.0)})
    ckpt.wait()
    n = evaluator.run(max_evals=1, deadline=None)
    assert n == 1
    assert evaluated == [5, 10]
    assert reported[0][0] == 5
    assert reported[1] == (10, {"loss": 8.0})


def test_eval_results_reach_master_stats(tmp_path):
    """End-to-end over the wire: evaluator -> report_custom_data RPC ->
    job collector custom metrics."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.master.servicer import create_master_service
    from dlrover_tpu.master.stats.job_collector import (
        JobMetricCollector,
    )
    from dlrover_tpu.master.stats.reporter import JobMeta

    collector = JobMetricCollector(JobMeta(name="j"))
    server, servicer = create_master_service(
        0, job_metric_collector=collector
    )
    server.start()
    try:
        client = MasterClient(
            f"localhost:{server.port}", 0, NodeType.EVALUATOR
        )
        client.report_custom_data({"eval_step": 5, "eval_loss": 1.5})
        assert collector._custom["eval_loss"] == 1.5
        assert collector._custom["eval_step"] == 5
    finally:
        server.stop()


def test_process_scaler_uses_per_role_command(tmp_path):
    import time

    from dlrover_tpu.master.scaler.process_scaler import ProcessScaler

    out = tmp_path / "role.txt"
    scaler = ProcessScaler(
        "j", "localhost:1",
        command=["python", "-c",
                 f"open(r'{out}', 'a').write('worker\\n')"],
        commands={"evaluator": [
            "python", "-c",
            f"open(r'{out}', 'a').write('evaluator\\n')",
        ]},
    )
    try:
        plan = ScalePlan()
        w = Node(NodeType.WORKER, 0, rank_index=0)
        e = Node(NodeType.EVALUATOR, 0, rank_index=0)
        w.config_resource = e.config_resource = NodeResource()
        plan.launch_nodes += [w, e]
        scaler.scale(plan)
        deadline = time.time() + 20
        while time.time() < deadline:
            lines = sorted(
                out.read_text().split()
            ) if out.exists() else []
            if lines == ["evaluator", "worker"]:
                break
            time.sleep(0.2)
        assert sorted(out.read_text().split()) == [
            "evaluator", "worker",
        ]
    finally:
        scaler.stop()


import pytest


@pytest.mark.drill
def test_evaluator_e2e_with_training_job(tmp_path):
    """Full job: master (process platform) supervising one training
    worker AND one evaluator replica; the evaluator must produce eval
    rows from the worker's flash checkpoints while training runs."""
    import os
    import signal
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tmp = str(tmp_path)
    ckpt = os.path.join(tmp, "ckpt")
    eval_out = os.path.join(tmp, "eval.txt")
    progress = os.path.join(tmp, "progress.txt")
    spec = os.path.join(tmp, "job.yaml")
    with open(spec, "w") as f:
        f.write(f"""
apiVersion: dlrover-tpu/v1
kind: ElasticTpuJob
metadata: {{name: eval-e2e}}
spec:
  platform: process
  worker:
    replicas: 1
    env: {{JAX_PLATFORMS: cpu}}
    command:
      - {sys.executable}
      - -m
      - dlrover_tpu.trainer.elastic_run
      - --nnodes
      - "1:1"
      - --monitor_interval
      - "0.3"
      - {os.path.join(repo, 'examples', 'dist_train.py')}
      - --
      - --steps
      - "120"
      - --step-time
      - "0.1"
      - --ckpt-dir
      - {ckpt}
      - --progress
      - {progress}
  evaluator:
    replicas: 1
    env: {{JAX_PLATFORMS: cpu}}
    command:
      - {sys.executable}
      - {os.path.join(repo, 'examples', 'eval_loop.py')}
      - --ckpt-dir
      - {ckpt}
      - --poll
      - "0.5"
      - --max-evals
      - "2"
      - --out
      - {eval_out}
""")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), repo) if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    master = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.master.main",
         "--job_spec", spec, "--port", "0"],
        cwd=repo, env=env,
        stdout=open(os.path.join(tmp, "m.out"), "w"),
        stderr=open(os.path.join(tmp, "m.err"), "w"),
        start_new_session=True,
    )
    try:
        deadline = time.time() + 180
        rows = []
        while time.time() < deadline:
            if os.path.exists(eval_out):
                rows = [
                    ln for ln in open(eval_out).read().splitlines()
                    if "," in ln
                ]
                if len(rows) >= 2:
                    break
            assert master.poll() is None, (
                open(os.path.join(tmp, "m.err")).read()[-2000:]
            )
            time.sleep(0.5)
        assert len(rows) >= 2, (
            f"evaluator produced {rows}; master.err: "
            + open(os.path.join(tmp, "m.err")).read()[-2000:]
        )
        # rows are "step,loss" with increasing steps and finite loss
        steps = [int(r.split(",")[0]) for r in rows]
        losses = [float(r.split(",")[1]) for r in rows]
        assert steps == sorted(steps) and steps[0] > 0
        assert all(np.isfinite(v) for v in losses)
    finally:
        try:
            os.killpg(os.getpgid(master.pid), signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
        time.sleep(1)
        try:
            os.killpg(os.getpgid(master.pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def test_unsupported_platform_skips_evaluator_role():
    """A scaler with no evaluator entrypoint (GKE/TPU-VM without a
    per-role command) must skip the role with a warning, never launch
    the training workload under the evaluator label."""
    from dlrover_tpu.master.node.dist_job_manager import (
        DistributedJobManager,
    )

    class WorkerOnlyScaler(RecordingScaler):
        def supports_role(self, node_type):
            return node_type == NodeType.WORKER

    args = JobArgs(
        job_name="j", node_num=1, evaluator_num=1,
        node_resource=NodeResource(cpu=1),
    )
    scaler = WorkerOnlyScaler()
    jm = DistributedJobManager(job_args=args, scaler=scaler)
    jm.start()
    try:
        assert all(
            n.type == NodeType.WORKER for n in scaler.launched
        )
    finally:
        jm.stop()


def test_process_scaler_fails_roles_without_command(tmp_path):
    """A non-worker node with no per-role command fails FATAL instead
    of silently running the training command as a rogue trainer."""
    from dlrover_tpu.common.constants import NodeExitReason
    from dlrover_tpu.master.scaler.process_scaler import ProcessScaler

    scaler = ProcessScaler(
        "j", "localhost:1", command=["python", "-c", "pass"],
    )
    try:
        assert not scaler.supports_role(NodeType.EVALUATOR)
        node = Node(NodeType.EVALUATOR, 0, rank_index=0)
        node.config_resource = NodeResource()
        plan = ScalePlan()
        plan.launch_nodes.append(node)
        scaler.scale(plan)
        failed = scaler.watcher._nodes[(NodeType.EVALUATOR, 0)]
        assert failed.exit_reason == NodeExitReason.FATAL_ERROR
    finally:
        scaler.stop()
