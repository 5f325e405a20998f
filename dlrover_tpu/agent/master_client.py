"""Agent-side client for every master RPC, behind reconnect supervision.

Parity reference: dlrover/python/elastic_agent/master_client.py:51
(MasterClient, build_master_client:466, GlobalMasterClient:479). Adds a
LocalMasterClient fallback that serves the sharding protocol in-process
when no master address is configured (reference LocalDataset behavior).

The reference retried every RPC blindly (retry_grpc_request: 10x6s,
masking app errors and giving up mid-master-reschedule). Here every
public RPC runs under a ConnectionSupervisor instead:

* errors are CLASSIFIED — only connection-level failures (UNAVAILABLE /
  DEADLINE_EXCEEDED / socket errors) enter the reconnect loop;
  application errors surface to the caller immediately;
* reconnects back off with decorrelated jitter up to a hard deadline
  (``DLROVER_TPU_MASTER_RECONNECT_TIMEOUT``, default 600 s — generous
  enough to cover a master pod reschedule);
* recovery is probed with a raw ping, then registered re-hello hooks
  run BEFORE the original call retries (re-register the node,
  re-report dataset params) so the restarted master has the context
  the retried RPC assumes;
* the outage is observable: ``agent.master_lost`` /
  ``agent.master_reconnected`` journal events and a reconnect-attempts
  counter.
"""

import functools
import os
import random
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

import grpc

from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeEnv, RendezvousName, TaskType
from dlrover_tpu.common.grpc_utils import GenericRpcClient
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import counter, fleet, record, tracing

#: hard reconnect deadline (seconds) — how long a worker rides out a
#: master outage before giving up. Default covers a pod reschedule plus
#: image pull with room to spare.
ENV_RECONNECT_TIMEOUT = "DLROVER_TPU_MASTER_RECONNECT_TIMEOUT"
DEFAULT_RECONNECT_TIMEOUT = 600.0

#: decorrelated-jitter backoff bounds for the reconnect probe loop
BACKOFF_BASE = 0.25
BACKOFF_CAP = 15.0

#: relay-tier failover (ISSUE 16): when the client's primary address is
#: an aggregator relay and it stays unreachable this long, the
#: supervisor re-points the channel at the fallback (direct-master)
#: address and keeps probing — the relay tier degrades to PR 12's
#: direct fan-in, it never partitions agents from the master.
ENV_RELAY_FAILOVER = "DLROVER_TPU_RELAY_FAILOVER_S"
DEFAULT_RELAY_FAILOVER = 10.0

#: public MasterClient methods deliberately NOT supervised (the AST lint
#: in tests/test_reconnect_supervisor.py enforces this list is the only
#: gap): ``ping`` IS the supervisor's liveness probe and its contract is
#: an immediate True/False — blocking it for the reconnect deadline
#: would deadlock the probe and stall every caller that just wants a
#: health answer.
UNSUPERVISED_RPCS = ("ping",)


class MasterLostError(ConnectionError):
    """The master stayed unreachable past the reconnect deadline."""


def is_connection_error(exc: BaseException) -> bool:
    """Connection-level (reconnect-worthy) vs application error.

    The generic RPC server aborts INVALID_ARGUMENT on wire errors and
    INTERNAL on handler exceptions (common/grpc_utils.py) — those are
    the remote code talking and must surface immediately. A dead or
    rescheduling master manifests as UNAVAILABLE / DEADLINE_EXCEEDED or
    a raw socket error; a master that dies (os._exit on an injected
    crash, OOM-kill) with our unary call in flight surfaces as
    CANCELLED from the peer — nothing in this codebase cancels calls
    client-side, so CANCELLED is also the master going away."""
    if isinstance(exc, grpc.RpcError):
        code = getattr(exc, "code", lambda: None)()
        return code in (
            grpc.StatusCode.UNAVAILABLE,
            grpc.StatusCode.DEADLINE_EXCEEDED,
            grpc.StatusCode.CANCELLED,
        )
    return isinstance(exc, (ConnectionError, OSError))


class ConnectionSupervisor:
    """Shared reconnect state machine for one MasterClient.

    Any number of threads (heartbeat, shard prefetch, rendezvous
    polling) may hit the outage concurrently; the first records
    ``agent.master_lost``, exactly one at a time probes the master, and
    the winning probe runs the re-hello hooks once before any supervised
    call retries."""

    def __init__(self, client: GenericRpcClient, node_desc: str = "",
                 reconnect_timeout: Optional[float] = None,
                 fallback_addr: Optional[str] = None,
                 failover_after: Optional[float] = None):
        self._client = client
        self._node_desc = node_desc
        if reconnect_timeout is None:
            reconnect_timeout = float(
                os.getenv(ENV_RECONNECT_TIMEOUT, "")
                or DEFAULT_RECONNECT_TIMEOUT
            )
        self.reconnect_timeout = reconnect_timeout
        self._backoff_cap = BACKOFF_CAP
        # relay -> direct-master failover: when set, an outage longer
        # than failover_after re-points the channel at fallback_addr
        # (once); the normal probe/re-hello machinery then reconnects
        self._fallback_addr = fallback_addr
        if failover_after is None:
            failover_after = float(
                os.getenv(ENV_RELAY_FAILOVER, "")
                or DEFAULT_RELAY_FAILOVER
            )
        self._failover_after = failover_after
        self._failed_over = False
        self._reset_pending = False
        self._hooks: Dict[str, Callable[[], None]] = {}
        self._state_lock = threading.Lock()
        self._connected = True
        self._lost_at = 0.0
        self._local = threading.local()

    # ------------------------------------------------------------- hooks

    def add_hook(self, name: str, fn: Callable[[], None]):
        """Register an idempotent re-hello, run (in registration order)
        after every reconnect BEFORE supervised calls retry. Hooks may
        freely call supervised RPCs — supervision is bypassed inside."""
        with self._state_lock:
            self._hooks[name] = fn

    def remove_hook(self, name: str):
        with self._state_lock:
            self._hooks.pop(name, None)

    # -------------------------------------------------------------- core

    def call(self, method: str, fn: Callable):
        if getattr(self._local, "bypass", False):
            return fn()
        deadline = None
        sleep = BACKOFF_BASE
        attempts = 0
        first_error: Optional[BaseException] = None
        while True:
            try:
                return fn()
            except Exception as e:
                if not is_connection_error(e):
                    raise
                now = time.monotonic()
                if deadline is None:
                    deadline = now + self.reconnect_timeout
                    first_error = e
                    self._note_lost(method, e)
                # probe-and-backoff until reconnected or out of time;
                # fn() only retries AFTER a successful probe ran the
                # re-hello hooks (the retried call may assume them)
                while True:
                    if time.monotonic() >= deadline:
                        raise MasterLostError(
                            f"master unreachable for "
                            f"{self.reconnect_timeout:.0f}s "
                            f"({attempts} reconnect attempts) during "
                            f"RPC {method}"
                        ) from first_error
                    attempts += 1
                    counter(
                        "dlrover_agent_master_reconnect_attempts_total",
                        "Reconnect probes sent while the master was "
                        "unreachable",
                    ).inc()
                    # decorrelated jitter: spreads a whole fleet's
                    # probes instead of synchronized thundering herds
                    sleep = min(
                        self._backoff_cap,
                        random.uniform(BACKOFF_BASE, sleep * 3),
                    )
                    time.sleep(
                        max(0.02, min(sleep,
                                      deadline - time.monotonic()))
                    )
                    if self._try_reconnect():
                        break

    # ----------------------------------------------------------- plumbing

    def _raw_ping(self) -> bool:
        try:
            res = self._client.call("ping", comm.BaseRequest())
            return bool(getattr(res, "success", True))
        except Exception:
            return False

    def _note_lost(self, method: str, exc: BaseException):
        with self._state_lock:
            if not self._connected:
                return
            self._connected = False
            self._lost_at = time.time()
        logger.warning(
            "Master connection lost during RPC %s: %s — entering "
            "reconnect supervision (deadline %.0fs)",
            method, exc, self.reconnect_timeout,
        )
        record(
            "agent.master_lost", method=method, error=str(exc)[:200],
            node=self._node_desc,
        )

    def _maybe_fail_over(self):
        """Relay tier: after ``_failover_after`` seconds of outage,
        re-point the channel at the direct-master fallback (once). The
        channel swap happens OUTSIDE the state lock — it closes a gRPC
        channel — and the racing probe that follows is idempotent."""
        with self._state_lock:
            if (self._fallback_addr is None or self._failed_over
                    or self._connected
                    or time.time() - self._lost_at
                    < self._failover_after):
                return
            self._failed_over = True
            fallback = self._fallback_addr
        logger.warning(
            "relay at %s unreachable for %.1fs — failing over to "
            "master at %s", self._client.addr,
            self._failover_after, fallback,
        )
        record(
            "relay.failover",
            node=self._node_desc,
            relay_addr=self._client.addr,
            master_addr=fallback,
            after_s=self._failover_after,
        )
        counter(
            "dlrover_relay_failovers_total",
            "relay -> direct-master failovers taken by this process",
        ).inc()
        self._client.reset(fallback)

    def _try_reconnect(self) -> bool:
        """Probe the master; on success run re-hello hooks and flip back
        to connected. Serialized: concurrent stranded threads wait on
        the lock and see _connected already True."""
        self._maybe_fail_over()
        if self._reset_pending:
            # A channel that watched its server die can wedge in
            # TRANSIENT_FAILURE far past any configured backoff: a
            # fresh channel (and raw TCP) reaches the restarted master
            # instantly while this one keeps failing every RPC without
            # dialing. After a failed probe, re-dial on a brand-new
            # channel. Outside the state lock — reset() closes a gRPC
            # channel; the flag race is benign (an extra reset just
            # recreates an idle channel).
            self._reset_pending = False
            reset = getattr(self._client, "reset", None)
            if reset is not None:
                reset(self._client.addr)
        with self._state_lock:
            if self._connected:
                return True
            if not self._raw_ping():
                self._reset_pending = True
                return False
            self._local.bypass = True
            try:
                for name, hook in list(self._hooks.items()):
                    try:
                        hook()
                    except Exception as e:
                        logger.warning(
                            "re-hello hook %s failed after "
                            "reconnect: %s", name, e,
                        )
            finally:
                self._local.bypass = False
            outage = time.time() - self._lost_at
            self._connected = True
        logger.info(
            "Master reconnected after %.1fs outage; re-hello hooks "
            "done", outage,
        )
        record(
            "agent.master_reconnected",
            outage_seconds=round(outage, 3), node=self._node_desc,
        )
        return True


def supervised_rpc(func):
    """Route a MasterClient RPC method through its ConnectionSupervisor
    (classification + reconnect + re-hello; see module docstring)."""

    @functools.wraps(func)
    def wrapped(self, *args, **kwargs):
        return self._supervisor.call(
            func.__name__, lambda: func(self, *args, **kwargs)
        )

    wrapped._supervised_rpc = True
    return wrapped


class MasterClient:
    """One client instance per agent/worker process."""

    def __init__(self, master_addr: str, node_id: int, node_type: str,
                 timeout: float = 30.0,
                 reconnect_timeout: Optional[float] = None,
                 fallback_addr: Optional[str] = None,
                 failover_after: Optional[float] = None):
        """``master_addr`` may be an aggregator relay (ISSUE 16); then
        ``fallback_addr`` is the real master and the supervisor fails
        over relay -> direct after ``failover_after`` seconds of
        outage."""
        self._client = GenericRpcClient(master_addr, timeout=timeout)
        self._node_id = node_id
        self._node_type = node_type
        self.master_addr = master_addr
        self._supervisor = ConnectionSupervisor(
            self._client,
            node_desc=f"{node_type}-{node_id}",
            reconnect_timeout=reconnect_timeout,
            fallback_addr=fallback_addr,
            failover_after=failover_after,
        )

    def add_reconnect_hook(self, name: str, fn: Callable[[], None]):
        """Register an idempotent re-hello run after every reconnect
        (e.g. re-register this node, re-report dataset params)."""
        self._supervisor.add_hook(name, fn)

    def remove_reconnect_hook(self, name: str):
        self._supervisor.remove_hook(name)

    def _call(self, method: str, message):
        t0 = time.perf_counter()
        try:
            return self._client.call(method, message)
        finally:
            # fleet roll-up (ISSUE 17): RPC latency rides the digest
            # instead of requiring a per-agent scrape
            fleet.observe("rpc", time.perf_counter() - t0)

    def _fill(self, req: comm.BaseRequest):
        req.node_id = self._node_id
        req.node_type = self._node_type
        return req

    # ------------------------------------------------------------ sharding

    @supervised_rpc
    def report_dataset_shard_params(
        self, batch_size: int, num_epochs: int, dataset_size: int,
        shuffle: bool, num_minibatches_per_shard: int, dataset_name: str,
        task_type: str = TaskType.TRAINING, storage_type: str = "table",
    ):
        req = self._fill(comm.DatasetShardParams(
            batch_size=batch_size, num_epochs=num_epochs,
            dataset_size=dataset_size, shuffle=shuffle,
            num_minibatches_per_shard=num_minibatches_per_shard,
            dataset_name=dataset_name, task_type=task_type,
            storage_type=storage_type,
        ))
        return self._call("report_dataset_shard_params", req)

    @supervised_rpc
    def get_task(self, dataset_name: str,
                 incarnation: int = -1) -> comm.Task:
        req = self._fill(comm.TaskRequest(
            dataset_name=dataset_name, incarnation=incarnation,
        ))
        return self._call("get_task", req)

    @supervised_rpc
    def get_tasks(self, dataset_name: str, max_tasks: int = 1,
                  incarnation: int = -1) -> List[comm.Task]:
        """Batched dispatch: up to ``max_tasks`` shards in one
        round-trip. A master that predates this RPC rejects the unknown
        message type/method with an application error (not a connection
        error) — callers catch that and fall back to :meth:`get_task`."""
        req = self._fill(comm.TaskBatchRequest(
            dataset_name=dataset_name, incarnation=incarnation,
            max_tasks=max_tasks,
        ))
        return self._call("get_tasks", req).tasks

    @supervised_rpc
    def report_task_result(self, dataset_name: str, task_id: int,
                           err_message: str = ""):
        req = self._fill(comm.TaskResult(
            dataset_name=dataset_name, task_id=task_id,
            err_message=err_message,
        ))
        return self._call("report_task_result", req)

    @supervised_rpc
    def get_shard_checkpoint(self, dataset_name: str) -> str:
        req = self._fill(
            comm.ShardCheckpointRequest(dataset_name=dataset_name)
        )
        res = self._call("get_shard_checkpoint", req)
        return res.content

    @supervised_rpc
    def report_shard_checkpoint(self, content: str):
        return self._call(
            "report_shard_checkpoint", comm.ShardCheckpoint(content=content)
        )

    @supervised_rpc
    def get_dataset_epoch(self, dataset_name: str) -> int:
        req = self._fill(comm.DatasetEpochRequest(dataset_name=dataset_name))
        return self._call("get_dataset_epoch", req).epoch

    # ---------------------------------------------------------- rendezvous

    @supervised_rpc
    def report_rdzv_params(self, min_nodes: int, max_nodes: int,
                           waiting_timeout: float, node_unit: int,
                           join_timeout: float = 600.0):
        req = self._fill(comm.RendezvousParams(
            min_nodes=min_nodes, max_nodes=max_nodes,
            waiting_timeout=waiting_timeout, node_unit=node_unit,
            joint_timeout=join_timeout,
        ))
        return self._call("report_rdzv_params", req)

    @supervised_rpc
    def join_rendezvous(self, node_rank: int, local_world_size: int,
                        rdzv_name: str = RendezvousName.TRAINING) -> int:
        req = comm.JoinRendezvousRequest(
            node_id=node_rank, node_type=self._node_type,
            local_world_size=local_world_size, rdzv_name=rdzv_name,
        )
        return self._call("join_rendezvous", req).round

    @supervised_rpc
    def get_comm_world(
        self, rdzv_name: str, node_rank: int
    ):
        req = comm.CommWorldRequest(
            node_id=node_rank, rdzv_name=rdzv_name
        )
        res = self._call("get_comm_world", req)
        return res.rdzv_round, res.group, res.world

    @supervised_rpc
    def num_nodes_waiting(
        self, rdzv_name: str = RendezvousName.TRAINING
    ) -> int:
        req = self._fill(comm.WaitingNodeNumRequest(rdzv_name=rdzv_name))
        try:
            return self._call("num_nodes_waiting", req).waiting_num
        except Exception as e:
            # connection loss must reach the supervisor (it owns the
            # reconnect loop); only APP errors degrade to "0 waiting"
            if is_connection_error(e):
                raise
            logger.warning("num_nodes_waiting failed: %s", e)
            return 0

    @supervised_rpc
    def report_node_check_status(self, rdzv_round: int, normal: bool,
                                 elapsed_time: float):
        req = self._fill(comm.NodeCheckStatus(
            rdzv_round=rdzv_round, normal=normal, elapsed_time=elapsed_time,
        ))
        return self._call("report_node_check_status", req)

    @supervised_rpc
    def network_check_success(self):
        req = self._fill(comm.NetworkReadyRequest())
        res = self._call("network_check_success", req)
        return res.success, res.reason

    @supervised_rpc
    def get_fault_nodes(self) -> List[int]:
        return self._call("get_fault_nodes", self._fill(comm.BaseRequest()))

    @supervised_rpc
    def get_straggler_nodes(self) -> List[int]:
        return self._call(
            "get_straggler_nodes", self._fill(comm.BaseRequest())
        )

    # ------------------------------------------------------------- kv store

    @supervised_rpc
    def kv_store_set(self, key: str, value: bytes):
        return self._call(
            "kv_store_set", comm.KVStoreSetRequest(key=key, value=value)
        )

    @supervised_rpc
    def kv_store_get(self, key: str) -> bytes:
        return self._call(
            "kv_store_get", comm.KVStoreGetRequest(key=key)
        ).value

    @supervised_rpc
    def kv_store_keys(self, prefix: str = ""):
        return self._call(
            "kv_store_keys", comm.KVStoreKeysRequest(prefix=prefix)
        ).keys

    @supervised_rpc
    def kv_store_add(self, key: str, amount: int) -> int:
        return self._call(
            "kv_store_add", comm.KVStoreAddRequest(key=key, amount=amount)
        ).value

    # ---------------------------------------------------------- node status

    @supervised_rpc
    def update_node_status(self, status: str, exit_reason: str = "",
                           restart_count: int = 0):
        req = self._fill(comm.NodeStatusRequest(
            status=status, exit_reason=exit_reason,
            restart_count=restart_count,
        ))
        return self._call("update_node_status", req)

    @supervised_rpc
    def update_node_address(self, address: str):
        req = self._fill(comm.NodeAddressRequest(address=address))
        return self._call("update_node_address", req)

    @supervised_rpc
    def report_heartbeat(self) -> str:
        req = self._fill(comm.HeartBeat(timestamp=time.time()))
        return self._call("report_heartbeat", req).action

    @supervised_rpc
    def report_node_status(self, report: comm.NodeStatusReport):
        """The coalesced fan-in rpc (agent/status_reporter.py builds
        the delta payload): heartbeat + changed sections in one call.
        Returns the :class:`~dlrover_tpu.common.comm.NodeStatusAck`, or
        ``None`` when the master predates the RPC — the reporter then
        degrades to the per-rpc paths for the rest of this process."""
        try:
            return self._call("report_node_status", self._fill(report))
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_node_status unsupported: %s", e)
            record("report.rpc_fallback", rpc="report_node_status",
                   error=str(e)[:200])
            return None

    @supervised_rpc
    def report_relay_batch(self, batch: comm.RelayBatchReport):
        """An aggregator relay's coalesced upstream interval
        (agent/relay.py): its agents' re-delta'd reports in one call.
        Returns the :class:`~dlrover_tpu.common.comm.RelayBatchAck`, or
        ``None`` when the master predates the RPC — the relay then
        degrades to forwarding per-agent ``report_node_status`` calls."""
        try:
            return self._call("report_relay_batch", self._fill(batch))
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_relay_batch unsupported: %s", e)
            record("report.rpc_fallback", rpc="report_relay_batch",
                   error=str(e)[:200])
            return None

    @supervised_rpc
    def report_failure(self, error_data: str, level: str,
                       restart_count: int = 0):
        req = self._fill(comm.NodeFailure(
            error_data=error_data, level=level, restart_count=restart_count,
        ))
        try:
            return self._call("report_failure", req)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_failure failed: %s", e)

    @supervised_rpc
    def report_preemption(self, reason: str = "",
                          notice_budget_s: float = 0.0,
                          deadline_ts: float = 0.0,
                          restart_count: int = 0):
        """Drain step 1 (fault_tolerance/drain.py): announce the
        reclaim notice so the master marks this node PREEMPTED, evicts
        it from rendezvous, and relaunches budget-free. A master that
        predates this RPC rejects the unknown message with an
        application error — the drain proceeds without it (the
        heartbeat watchdog still notices the death)."""
        req = self._fill(comm.PreemptionNotice(
            reason=reason, notice_budget_s=notice_budget_s,
            deadline_ts=deadline_ts, restart_count=restart_count,
        ))
        try:
            return self._call("report_preemption", req)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_preemption unsupported: %s", e)
            record("preempt.rpc_fallback", rpc="report_preemption",
                   error=str(e)[:200])
            return None

    @supervised_rpc
    def report_anomaly(self, kind: str, step: int, value: float = 0.0,
                       zscore: float = 0.0, host: str = "",
                       last_good_step: int = -1,
                       restart_count: int = 0):
        """Sentinel trip (fault_tolerance/sentinel.py): report a
        silent-corruption signal and receive the master's verdict — a
        coordinated rollback order, "none" (duplicate of an in-flight
        rollback), or "job_failed" once the rollback budget is spent.
        A master predating this RPC rejects the unknown message with an
        application error; the sentinel then runs uncoordinated (its
        local anomaly window still keeps poisoned saves untagged)."""
        req = self._fill(comm.AnomalyReport(
            kind=kind, step=step, value=value, zscore=zscore,
            host=host or socket.gethostname(),
            last_good_step=last_good_step, restart_count=restart_count,
        ))
        try:
            return self._call("report_anomaly", req)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_anomaly unsupported: %s", e)
            record("anomaly.rpc_fallback", rpc="report_anomaly",
                   error=str(e)[:200])
            return None

    @supervised_rpc
    def report_reshard(self, order_id: int, phase: str,
                       detail: str = ""):
        """Mesh-transition progress (reshard/transition.py): this
        survivor reached ``phase`` of transition order ``order_id``.
        The coordinator answers ok/stale/abort. A master predating the
        RPC rejects the unknown message with an application error —
        the worker then treats the transition as unsupervised and
        falls back to restart-the-world (None return)."""
        req = self._fill(comm.ReshardReport(
            order_id=order_id, phase=phase, detail=detail,
        ))
        try:
            return self._call("report_reshard", req)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("report_reshard unsupported: %s", e)
            record("anomaly.rpc_fallback", rpc="report_reshard",
                   error=str(e)[:200])
            return None

    @supervised_rpc
    def relinquish_shards(self, dataset_name: str = "") -> int:
        """Drain step 3: return this node's in-flight shards to the
        todo queue immediately (empty name = every dataset). Returns
        the number requeued, or -1 when the master predates the RPC —
        the task-timeout watchdog covers that case, just slower."""
        req = self._fill(
            comm.RelinquishShardsRequest(dataset_name=dataset_name)
        )
        try:
            return int(self._call("relinquish_shards", req).requeued)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("relinquish_shards unsupported: %s", e)
            record("preempt.rpc_fallback", rpc="relinquish_shards",
                   error=str(e)[:200])
            return -1

    @supervised_rpc
    def report_used_resource(self, cpu_percent: float, memory_mb: int,
                             tpu_stats: Optional[List[Dict]] = None):
        req = self._fill(comm.ResourceStats(
            cpu_percent=cpu_percent, memory_mb=memory_mb,
            tpu_stats=tpu_stats or [],
        ))
        return self._call("report_used_resource", req)

    @supervised_rpc
    def query_running_nodes(self) -> List[Dict]:
        req = self._fill(comm.RunningNodesRequest())
        return self._call("query_running_nodes", req).nodes

    @supervised_rpc
    def request_scale(self, node_num: int) -> bool:
        """Operator-requested manual scaling (parity: manualScaling)."""
        req = self._fill(comm.ScaleRequest(node_num=node_num))
        resp = self._call("request_scale", req)
        return bool(getattr(resp, "success", False))

    # -------------------------------------------------------------- serving

    @supervised_rpc
    def serve_submit(self, payload: bytes, req_id: str = "",
                     tenant: str = "", priority: int = 0):
        """Admit one inference request; returns (accepted, req_id,
        reason). Reasons are explicit backpressure — the caller owns
        the retry policy. ``tenant``/``priority`` buy fair queuing on
        the sharded router plane (ISSUE 20); the defaults keep the old
        wire byte-identical."""
        req = self._fill(comm.ServeSubmit(
            req_id=req_id, payload=payload,
            tenant=tenant, priority=priority,
        ))
        res = self._call("serve_submit", req)
        return bool(res.accepted), res.req_id, res.reason

    @supervised_rpc
    def serve_poll(self, req_id: str):
        """Fetch the stored response for a request id; returns
        (done, payload, worker_id, latency_s)."""
        res = self._call(
            "serve_poll", self._fill(comm.ServePoll(req_id=req_id))
        )
        return bool(res.done), res.payload, res.worker_id, res.latency_s

    @supervised_rpc
    def serve_lease(self, max_requests: int = 1, incarnation: int = -1):
        """Pull the next micro-batch of requests; returns
        ([(req_id, payload), ...], sealed). Empty + sealed=True is the
        end-of-stream signal."""
        req = self._fill(comm.ServeLeaseRequest(
            max_requests=max_requests, incarnation=incarnation,
        ))
        res = self._call("serve_lease", req)
        return (
            [(r.req_id, r.payload) for r in res.requests],
            bool(res.sealed),
        )

    @supervised_rpc
    def serve_complete(self, req_id: str, payload: bytes) -> bool:
        """Report one response; False when the master rejected it
        (duplicate, or the request was redelivered after this worker's
        lease timed out) — the worker must NOT count it as its own."""
        req = self._fill(comm.ServeComplete(req_id=req_id, payload=payload))
        res = self._call("serve_complete", req)
        return bool(getattr(res, "success", False))

    @supervised_rpc
    def serve_relinquish(self) -> int:
        """Replica rotation: return this worker's unprocessed leases to
        the queue immediately. Returns the number requeued, or -1 when
        the master predates the serving RPCs — the lease-timeout
        watchdog covers that case, just slower."""
        req = self._fill(comm.ServeRelinquishRequest())
        try:
            return int(self._call("serve_relinquish", req).requeued)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("serve_relinquish unsupported: %s", e)
            record("serve.rpc_fallback", rpc="serve_relinquish",
                   error=str(e)[:200])
            return -1

    @supervised_rpc
    def serve_seal(self):
        """Declare end-of-stream: no more submissions; workers exit
        once the queue drains."""
        return self._call(
            "serve_seal", self._fill(comm.ServeSealRequest())
        )

    @supervised_rpc
    def serve_stats(self) -> Optional[Dict]:
        """Router stats (queue depth, p50/p99 latency, counters) for
        autoscaling and load generators; None when the master has no
        serving tier."""
        req = self._fill(comm.ServeStatsRequest())
        try:
            res = self._call("serve_stats", req)
        except Exception as e:
            if is_connection_error(e):
                raise
            logger.warning("serve_stats unsupported: %s", e)
            record("serve.rpc_fallback", rpc="serve_stats",
                   error=str(e)[:200])
            return None
        # mirror every wire field (the router's stats() and ServeStats
        # are kept key-identical by test_router_stats_match_serve_stats
        # _wire_fields) so new stats — shard/tenant/GC counters —
        # propagate without touching this client
        return {
            name: getattr(res, name, field.default)
            for name, field in comm.ServeStats.__dataclass_fields__.items()
        }

    # -------------------------------------------------------------- metrics

    @supervised_rpc
    def report_global_step(self, step: int,
                           timestamp: Optional[float] = None):
        # piggyback the goodput ledger when this process armed one
        # (telemetry/goodput.py) — empty fields otherwise, so the wire
        # message is unchanged for ledger-less processes
        from dlrover_tpu.telemetry import goodput

        req = self._fill(comm.GlobalStep(
            timestamp=timestamp or time.time(), step=step,
            pid=os.getpid(), **goodput.report_fields(),
        ))
        return self._call("report_global_step", req)

    @supervised_rpc
    def report_goodput(self, final: bool = False):
        """Push the full ledger snapshot outside the step cadence
        (periodic agent heartbeats, and once with ``final=True`` at
        process exit so the master closes the incarnation). No-op
        without an armed ledger."""
        from dlrover_tpu.telemetry import goodput

        fields = goodput.report_fields()
        if not fields:
            return None
        req = self._fill(comm.GoodputReport(
            pid=os.getpid(), host=socket.gethostname(),
            final=final, **fields,
        ))
        return self._call("report_goodput", req)

    @supervised_rpc
    def report_custom_data(self, data: Dict):
        """Free-form metrics into the stats pipeline (evaluator
        results; parity: report_customized_data)."""
        req = self._fill(comm.CustomData(data=dict(data)))
        return self._call("report_custom_data", req)

    @supervised_rpc
    def report_model_info(self, param_count: int, flops_per_step: float,
                          batch_size: int, seq_len: int = 0,
                          extra: Optional[Dict] = None):
        req = self._fill(comm.ModelInfo(
            param_count=param_count, flops_per_step=flops_per_step,
            batch_size=batch_size, seq_len=seq_len, extra=extra or {},
        ))
        return self._call("report_model_info", req)

    # ----------------------------------------------------------------- sync

    @supervised_rpc
    def join_sync(self, sync_name: str) -> bool:
        req = self._fill(comm.SyncJoin(sync_name=sync_name))
        return self._call("join_sync", req).success

    @supervised_rpc
    def sync_finished(self, sync_name: str) -> bool:
        req = self._fill(comm.SyncFinish(sync_name=sync_name))
        return self._call("sync_finished", req).success

    @supervised_rpc
    def barrier(self, barrier_name: str, notify: bool = False) -> bool:
        req = self._fill(comm.SyncBarrier(
            barrier_name=barrier_name, notify=notify,
        ))
        return self._call("barrier", req).success

    @supervised_rpc
    def get_elastic_run_config(self) -> Dict[str, str]:
        req = self._fill(comm.ElasticRunConfigRequest())
        return self._call("get_elastic_run_config", req).configs

    def ping(self) -> bool:
        try:
            return self._call("ping", comm.BaseRequest()).success
        except Exception:
            return False

    def close(self):
        self._client.close()


class LocalMasterClient:
    """Masterless fallback serving the sharding protocol in-process
    (parity: master_client.py LocalDataset path)."""

    def __init__(self, node_id: int = 0,
                 node_type: str = "worker"):
        from dlrover_tpu.master.shard.task_manager import TaskManager

        self._node_id = node_id
        self._node_type = node_type
        self._task_manager = TaskManager()
        self._kv: Dict[str, bytes] = {}
        self._router = None

    def report_dataset_shard_params(self, batch_size, num_epochs,
                                    dataset_size, shuffle,
                                    num_minibatches_per_shard, dataset_name,
                                    task_type=TaskType.TRAINING,
                                    storage_type="table"):
        splitter = __import__(
            "dlrover_tpu.master.shard.dataset_splitter",
            fromlist=["new_dataset_splitter"],
        ).new_dataset_splitter(
            shuffle=shuffle,
            shard_size=batch_size * num_minibatches_per_shard,
            dataset_size=dataset_size, num_epochs=num_epochs,
            dataset_name=dataset_name, storage_type=storage_type,
        )
        self._task_manager.new_dataset(
            batch_size, dataset_size, dataset_name, splitter, task_type
        )

    # signature in lockstep with MasterClient.get_task: ShardingClient
    # calls either through the same code path. The rpc.* span mirrors
    # the remote servicer's handle() so a trace reads the same shape
    # whether the master is local or remote — and since the "RPC" is a
    # plain call, the caller's trace context flows through the shared
    # contextvar with no metadata plumbing at all.
    def get_task(self, dataset_name: str,
                 incarnation: int = -1) -> comm.Task:
        with tracing.span("rpc.get_task"):
            task = self._task_manager.get_dataset_task(
                self._node_type, self._node_id, dataset_name,
                incarnation=incarnation,
            )
        return comm.Task(
            task_id=task.task_id, task_type=task.task_type,
            shard=comm.Shard(
                name=task.shard.name, start=task.shard.start,
                end=task.shard.end, record_indices=task.shard.record_indices,
            ),
        )

    def get_tasks(self, dataset_name: str, max_tasks: int = 1,
                  incarnation: int = -1) -> List[comm.Task]:
        with tracing.span("rpc.get_tasks"):
            tasks = self._task_manager.get_dataset_tasks(
                self._node_type, self._node_id, dataset_name,
                max_tasks=max_tasks, incarnation=incarnation,
            )
        return [
            comm.Task(
                task_id=t.task_id, task_type=t.task_type,
                shard=comm.Shard(
                    name=t.shard.name, start=t.shard.start,
                    end=t.shard.end,
                    record_indices=t.shard.record_indices,
                ),
            )
            for t in tasks
        ]

    def report_task_result(self, dataset_name, task_id, err_message=""):
        with tracing.span("rpc.report_task_result"):
            accepted = self._task_manager.report_dataset_task(
                dataset_name, task_id, not err_message
            )
        return comm.Response(success=bool(accepted))

    def get_dataset_epoch(self, dataset_name: str) -> int:
        return self._task_manager.get_dataset_epoch(dataset_name)

    def get_shard_checkpoint(self, dataset_name: str) -> str:
        ckpt = self._task_manager.get_dataset_checkpoint(dataset_name)
        return ckpt.to_json() if ckpt else ""

    def report_shard_checkpoint(self, content: str):
        self._task_manager.restore_dataset_from_checkpoint(content)

    def kv_store_set(self, key, value):
        self._kv[key] = value

    def kv_store_get(self, key):
        return self._kv.get(key, b"")

    def kv_store_keys(self, prefix=""):
        return sorted(k for k in self._kv if k.startswith(prefix))

    def kv_store_delete(self, key):
        self._kv.pop(key, None)

    def report_global_step(self, step, timestamp=None):
        pass

    def report_goodput(self, final=False):
        pass

    def report_preemption(self, reason="", notice_budget_s=0.0,
                          deadline_ts=0.0, restart_count=0):
        pass

    def report_anomaly(self, kind, step, value=0.0, zscore=0.0,
                       host="", last_good_step=-1, restart_count=0):
        # masterless: no one to coordinate a rollback with; the
        # sentinel's local anomaly window is the whole story
        return None

    def report_reshard(self, order_id, phase, detail=""):
        # masterless: a single process has no mesh to transition
        return None

    def relinquish_shards(self, dataset_name=""):
        self._task_manager.recover_tasks(self._node_type, self._node_id)
        return 0

    def report_custom_data(self, data):
        pass

    def report_heartbeat(self):
        return ""

    def report_node_status(self, report):
        # masterless: ack everything so the reporter idles quietly
        return comm.NodeStatusAck(accepted=True, acked_seq=report.seq)

    # masterless serving: the request plane lives in-process, so a
    # single-host ``examples/serve.py`` run needs no master at all
    def _serve_router(self):
        if self._router is None:
            from dlrover_tpu.serving.router import RequestRouter

            self._router = RequestRouter()
            self._router.start()
        return self._router

    def serve_submit(self, payload: bytes, req_id: str = "",
                     tenant: str = "", priority: int = 0):
        return self._serve_router().submit(
            payload, req_id=req_id, tenant=tenant, priority=priority
        )

    def serve_poll(self, req_id: str):
        return self._serve_router().poll(req_id)

    def serve_lease(self, max_requests: int = 1, incarnation: int = -1):
        return self._serve_router().lease(
            self._node_type, self._node_id,
            max_requests=max_requests, incarnation=incarnation,
        )

    def serve_complete(self, req_id: str, payload: bytes) -> bool:
        return self._serve_router().complete(
            self._node_type, self._node_id, req_id, payload
        )

    def serve_relinquish(self) -> int:
        return self._serve_router().relinquish(
            self._node_type, self._node_id
        )

    def serve_seal(self):
        self._serve_router().seal()

    def serve_stats(self):
        return self._serve_router().stats()


_master_client = None


def build_master_client(master_addr: Optional[str] = None,
                        node_id: Optional[int] = None,
                        node_type: Optional[str] = None,
                        timeout: float = 30.0):
    """Build a (cached) master client from args or env
    (parity: master_client.py:466)."""
    global _master_client
    master_addr = master_addr or os.getenv(NodeEnv.MASTER_ADDR, "")
    if node_id is None:
        node_id = int(os.getenv(NodeEnv.NODE_ID, "0"))
    if node_type is None:
        node_type = os.getenv(NodeEnv.NODE_TYPE, "worker")
    with tracing.span("boot.master_client"):
        if master_addr:
            _master_client = MasterClient(
                master_addr, node_id, node_type, timeout
            )
        else:
            _master_client = LocalMasterClient(node_id, node_type)
    return _master_client


def get_master_client():
    global _master_client
    if _master_client is None:
        _master_client = build_master_client()
    return _master_client
