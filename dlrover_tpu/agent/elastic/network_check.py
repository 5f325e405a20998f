"""Pre-flight network/accelerator health check.

Parity reference: dlrover/python/elastic_agent/torch/training.py:579
(NetworkCheckElasticAgent) + dlrover/trainer/torch/run_network_check.py:24.

TPU shape: each pair of hosts rendezvouses under the NETWORK_CHECK name and
runs an all-gather probe. On a real multi-host slice the probe is a
``jax.distributed`` + ``jax.lax.all_gather`` round over ICI/DCN; the
single-host fallback exercises chip compute (a matmul) so a sick accelerator
still fails its round. Two rounds: round 0 pairs neighbours, round 1 pairs
each abnormal node with a known-good partner to localize the fault.
"""

import subprocess
import sys
import time
from typing import Optional

from dlrover_tpu.agent.elastic.training import (
    ElasticLaunchConfig,
    MasterRendezvousHandler,
)
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.common.log import default_logger as logger

CHECK_ROUNDS = 2

_PROBE_SCRIPT = r"""
import os, time
import jax
import jax.numpy as jnp

# fault-injection hook (drill grammar, tests/test_four_node_drill.py):
# "rank:seconds[:gate_file][,rank:seconds[:gate_file]]" delays THIS
# node's probe so the master records it as a straggler
# (rdzv_manager.get_straggler_nodes). With a gate_file, the delay only
# applies while that file exists — lets a soak drill turn a straggler
# ON mid-run instead of from the first rendezvous.
_delay_spec = os.environ.get("DLROVER_TPU_PROBE_DELAY", "")
_own_rank = os.environ.get("DLROVER_TPU_NODE_RANK", "")
for _part in _delay_spec.split(","):
    _fields = _part.split(":")
    if len(_fields) < 2:
        continue
    _r, _secs = _fields[0], _fields[1]
    _gate = _fields[2] if len(_fields) > 2 else ""
    try:
        _delay = float(_secs)
    except ValueError:
        continue  # malformed entry must not fail the probe itself
    if _r and _r == _own_rank and (
        not _gate or os.path.exists(_gate)
    ):
        time.sleep(_delay)

coordinator = os.environ.get("{COORD}")
num_processes = int(os.environ.get("{NPROC}", "1"))
process_id = int(os.environ.get("{PID}", "0"))
if num_processes > 1:
    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # same contract as trainer/distributed.py
        jax.config.update(
            "jax_cpu_collectives_implementation", "gloo"
        )
    jax.distributed.initialize(coordinator, num_processes, process_id)
    x = jnp.ones((1024 * 1024,), dtype=jnp.float32)
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    import numpy as np
    devs = np.array(jax.devices())
    mesh = Mesh(devs, ("d",))
    y = jax.jit(
        lambda a: jax.lax.psum(a, "d"),
        in_shardings=NamedSharding(mesh, P()),
        out_shardings=NamedSharding(mesh, P()),
    )  # noqa
    # all-gather-equivalent probe over the full world
    xs = jax.device_put(x, NamedSharding(mesh, P()))
    s = jax.jit(jnp.sum)(xs)
    s.block_until_ready()
else:
    # single-node: exercise local chip(s) with a matmul probe
    a = jnp.ones((2048, 2048), dtype=jnp.bfloat16)
    (a @ a).block_until_ready()
print("NETWORK_CHECK_OK", flush=True)
"""


class NetworkCheckElasticAgent:
    """Runs CHECK_ROUNDS probe rounds and reports statuses to the master."""

    def __init__(self, config: ElasticLaunchConfig, master_client,
                 probe_timeout: float = 180.0):
        self._config = config
        self._client = master_client
        self._probe_timeout = probe_timeout

    def run(self) -> bool:
        success = False
        for r in range(CHECK_ROUNDS):
            handler = MasterRendezvousHandler(
                self._client, self._config.node_rank,
                self._config.nproc_per_node,
                rdzv_name=RendezvousName.NETWORK_CHECK,
                rdzv_params=(
                    self._config.min_nodes, self._config.max_nodes,
                    self._config.rdzv_timeout, self._config.node_unit,
                ),
            )
            rdzv_round, world, process_id, num_processes, coordinator = (
                handler.next_rendezvous()
            )
            start = time.time()
            normal = self._run_probe(coordinator, process_id, num_processes)
            elapsed = time.time() - start
            self._client.report_node_check_status(
                rdzv_round, normal, elapsed
            )
            # wait for all peers to report, then ask the verdict
            reason = ""
            deadline = time.time() + 60
            while time.time() < deadline:
                success, reason = self._client.network_check_success()
                if success or (reason and reason != "waiting_node"):
                    break
                time.sleep(1)
            # even on a green verdict, ALL rounds run: the probe is
            # collective, so one round cannot tell a straggler from the
            # group members it slowed — the re-paired second round
            # provides the evidence the master's straggler localization
            # intersects (rdzv_manager.get_straggler_nodes)
            if not success:
                logger.warning(
                    "Network check round %d failed (%s)", r, reason
                )
        if success:
            return True
        fault_nodes = self._client.get_fault_nodes()
        if self._config.node_rank in fault_nodes:
            logger.error("This node localized as faulty: %s", fault_nodes)
            return False
        return success

    def _run_probe(self, coordinator: str, process_id: int,
                   num_processes: int) -> bool:
        script = _PROBE_SCRIPT.format(
            COORD=NodeEnv.COORDINATOR_ADDR,
            NPROC=NodeEnv.NUM_PROCESSES,
            PID=NodeEnv.PROCESS_ID,
        )
        import os

        env = dict(os.environ)
        env[NodeEnv.COORDINATOR_ADDR] = coordinator
        env[NodeEnv.PROCESS_ID] = str(process_id)
        env[NodeEnv.NUM_PROCESSES] = str(num_processes)
        try:
            out = subprocess.run(
                [sys.executable, "-c", script],
                env=env, timeout=self._probe_timeout,
                capture_output=True, text=True,
            )
            ok = out.returncode == 0 and "NETWORK_CHECK_OK" in out.stdout
            if not ok:
                logger.warning(
                    "Probe failed rc=%s stderr=%s",
                    out.returncode, out.stderr[-500:],
                )
            return ok
        except subprocess.TimeoutExpired:
            logger.warning("Probe timed out after %ss", self._probe_timeout)
            return False
