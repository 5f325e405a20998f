"""Training-process bootstrap: env contract -> jax.distributed.

The agent (agent/elastic/training.py) fills the NodeEnv vars after each
rendezvous; the training process calls ``init_from_env()`` first thing and
JAX forms the mesh over the surviving topology. This replaces the reference's
``dist.init_process_group(NCCL)`` bootstrap (its MasterKVStore/TCPStore role
is played by the coordinator election in the agent).
"""

import os
from dataclasses import dataclass

from dlrover_tpu.common.constants import NodeEnv
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.log import set_process_index
from dlrover_tpu.telemetry import record, tracing


@dataclass
class DistributedEnv:
    coordinator_addr: str
    process_id: int
    num_processes: int
    node_rank: int
    node_num: int
    restart_count: int
    master_addr: str

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1


def read_dist_env() -> DistributedEnv:
    return DistributedEnv(
        coordinator_addr=os.getenv(NodeEnv.COORDINATOR_ADDR, ""),
        process_id=int(os.getenv(NodeEnv.PROCESS_ID, "0")),
        num_processes=int(os.getenv(NodeEnv.NUM_PROCESSES, "1")),
        node_rank=int(os.getenv(NodeEnv.NODE_RANK, "0")),
        node_num=int(os.getenv(NodeEnv.NODE_NUM, "1")),
        restart_count=int(os.getenv(NodeEnv.RESTART_COUNT, "0")),
        master_addr=os.getenv(NodeEnv.MASTER_ADDR, ""),
    )


def init_from_env(timeout_s: int = 300) -> DistributedEnv:
    """Initialize jax.distributed from the agent-provided env (no-op for a
    single process), then open the backend: the first ``jax.devices()``
    call of the process is made here, where ``boot.backend_open`` can
    time it. Every caller made that call right after this one.

    ``DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT`` (seconds) bounds how long a
    process blocks on collectives with a dead peer before the runtime
    kills it so the agent can re-rendezvous. The default (45s, vs jax's
    100s) keeps dead-peer detection inside the north-star <60s recovery
    budget.
    """
    env = read_dist_env()
    # before any jit: a restarted process re-traces the same program,
    # and the persistent cache turns its re-compile into a disk read
    # (the warm half of the <60s failover budget — compile_cache.py)
    from dlrover_tpu.trainer.compile_cache import (
        setup_compilation_cache,
    )

    import jax

    with tracing.span("boot.compile_cache_setup"):
        setup_compilation_cache()
    if env.is_distributed and env.coordinator_addr:
        # decided from the env, NOT jax.default_backend(): touching a
        # backend before jax.distributed.initialize() would create a
        # single-process client and the world would silently not form
        if os.getenv("JAX_PLATFORMS", "").startswith("cpu"):
            # cross-process CPU collectives (the multi-host test fabric;
            # TPU uses ICI/DCN natively)
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo"
            )
        hb_timeout = int(float(
            os.getenv("DLROVER_TPU_DIST_HEARTBEAT_TIMEOUT", "45")
        ))
        logger.info(
            "jax.distributed.initialize(%s, num_processes=%d, "
            "process_id=%d, heartbeat_timeout=%ds)",
            env.coordinator_addr, env.num_processes, env.process_id,
            hb_timeout,
        )
        with tracing.span("boot.distributed_init", {
            "num_processes": env.num_processes,
        }):
            jax.distributed.initialize(
                coordinator_address=env.coordinator_addr,
                num_processes=env.num_processes,
                process_id=env.process_id,
                initialization_timeout=timeout_s,
                heartbeat_timeout_seconds=hb_timeout,
            )
    attrs = {}
    with tracing.span("boot.backend_open", attrs):
        devices = jax.devices()
        attrs["platform"] = devices[0].platform
        attrs["device_count"] = len(devices)
    # the authoritative index is now known: tag log lines and the
    # journal envelope with it (common/log.py), then journal the init
    # so restarts are attributable on the timeline
    set_process_index(env.process_id)
    record(
        "distributed.init", process_id=env.process_id,
        num_processes=env.num_processes, node_rank=env.node_rank,
        restart_count=env.restart_count,
        coordinator=env.coordinator_addr,
    )
    return env
