"""Sharded ingest plane + aggregator relay tier (ISSUE 16).

Four layers, tested where each contract lives:

* :class:`ReporterLedger` — the per-reporter exactly-once bookkeeping:
  resync on unknown/new-incarnation deltas, immediate eviction on
  ``final``, stale-first eviction at the cap (the satellite bugfix:
  the ledger used to grow forever);
* :class:`IngestPlane` — node-id sharding, the split admission budget,
  and the PR 12 shed/retry contract surviving the shard refactor;
* the AsyncRpcServer front end — hot handlers on the event loop, cold
  RPCs on the bounded thread pool, both over a real gRPC channel;
* the relay tier — downstream termination + upstream re-delta against
  a real master, and the failover drill: kill the relay mid-interval,
  the agent's ConnectionSupervisor fails over to the direct master
  address, and NO interval is dropped or double-applied (master ledger
  seq == the agent's last acked seq).
"""

import os
import time

import pytest

from dlrover_tpu.agent.status_reporter import DeltaTracker
from dlrover_tpu.common import comm
from dlrover_tpu.common.constants import NodeStatus, NodeType
from dlrover_tpu.common.node import Node
from dlrover_tpu.master.ingest import IngestPlane, ReporterLedger
from dlrover_tpu.master.node.dist_job_manager import DistributedJobManager
from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor
from dlrover_tpu.master.servicer import create_master_service
from dlrover_tpu.telemetry.journal import (
    EventJournal,
    default_journal,
    set_default_journal,
)


@pytest.fixture(autouse=True)
def _fresh_event_journal():
    set_default_journal(EventJournal())
    yield
    set_default_journal(EventJournal())


GP = {
    "goodput_phases": {"init": 45.0, "training": 120.0},
    "goodput_elapsed_s": 170.0,
    "goodput_start_ts": 1000.0,
    "goodput_phase": "training",
}


def _compose(tracker, node_id=0, **kw):
    kw.setdefault("step", 100)
    kw.setdefault("pid", 4242)
    kw.setdefault("goodput_fields", dict(GP))
    kw.setdefault("resource", (50.0, 4096))
    kw.setdefault("host", f"host-{node_id}")
    rep = tracker.compose(time.time(), **kw)
    rep.node_id, rep.node_type = node_id, NodeType.WORKER
    return rep


def _job_manager(agents=4):
    speed = SpeedMonitor()
    jm = DistributedJobManager(speed_monitor=speed,
                               heartbeat_timeout=3600.0)
    jm._node_managers[NodeType.WORKER].update_nodes({
        i: Node(NodeType.WORKER, i, status=NodeStatus.RUNNING)
        for i in range(agents)
    })
    return jm, speed


# ------------------------------------------------------------- ledger


def test_ledger_resync_semantics():
    led = ReporterLedger(cap=64)
    key = (NodeType.WORKER, 1)
    # full first contact: no resync needed
    assert led.observe(key, 0, 1, True, 1.0) is False
    # known incarnation, delta: flows
    assert led.observe(key, 0, 2, False, 2.0) is False
    # unknown reporter delta: the ledger has no baseline
    assert led.observe((NodeType.WORKER, 2), 0, 5, False, 3.0) is True
    # incarnation flip WITHOUT a full report: the old baseline
    # describes a dead process
    assert led.observe(key, 1, 1, False, 4.0) is True
    # ...and once a full report lands, deltas flow again
    assert led.observe(key, 1, 2, True, 5.0) is False
    assert led.observe(key, 1, 3, False, 6.0) is False
    assert led.get(key) == (1, 3)


def test_ledger_final_evicts_immediately():
    led = ReporterLedger(cap=64)
    key = (NodeType.WORKER, 3)
    led.observe(key, 0, 1, True, 1.0)
    assert led.evict(key) is True
    assert led.evict(key) is False  # already gone
    assert led.evictions == 1
    assert led.get(key) is None
    # the next delta from a reborn process resyncs
    assert led.observe(key, 0, 2, False, 2.0) is True


def test_ledger_cap_evicts_stalest_first():
    led = ReporterLedger(cap=2)
    a, b, c = [(NodeType.WORKER, i) for i in range(3)]
    led.observe(a, 0, 1, True, 1.0)  # stalest
    led.observe(b, 0, 1, True, 2.0)
    led.observe(c, 0, 1, True, 3.0)  # over cap: evicts a
    assert led.evictions == 1
    assert len(led) == 2
    assert led.get(a) is None
    assert led.get(b) == (0, 1) and led.get(c) == (0, 1)
    # the evicted-but-alive reporter self-heals through resync
    assert led.observe(a, 0, 2, False, 4.0) is True


# -------------------------------------------------------------- plane


def test_plane_splits_admission_budget_across_shards():
    plane = IngestPlane(shards=4, inflight_limit=8, retry_after=0.02,
                        ledger_cap=400)
    try:
        assert len(plane.shards) == 4
        shard = plane.shard_of(NodeType.WORKER, 0)
        # routing is stable
        assert plane.shard_of(NodeType.WORKER, 0) is shard
        # 8 // 4 = 2 slots per shard, no cross-shard borrowing
        assert shard.try_admit() and shard.try_admit()
        assert not shard.try_admit()
        ack = plane.shed_ack(shard)
        assert not ack.accepted and ack.retry_after_s == 0.02
        shed = default_journal().events("control.load_shed")
        assert shed and shed[-1]["data"]["shard"] == shard.index
        shard.release()
        shard.release()
        assert shard.try_admit()
        shard.release()
    finally:
        plane.close()


def test_plane_limit_zero_sheds_everything_then_recovers():
    plane = IngestPlane(shards=4, inflight_limit=48, retry_after=0.02,
                        ledger_cap=400)
    applied = []
    try:
        tracker = DeltaTracker(incarnation=0)
        rep = _compose(tracker, node_id=1)
        plane.inflight_limit = 0
        shed = plane.report(rep, lambda r: applied.append(r.seq) or "")
        assert not shed.accepted and shed.retry_after_s > 0
        assert applied == []  # shed never applies nor advances ledger
        assert (NodeType.WORKER, 1) not in plane.reporters()
        plane.inflight_limit = 48
        ack = plane.report(rep, lambda r: applied.append(r.seq) or "")
        assert ack.accepted and ack.acked_seq == rep.seq
        assert applied == [rep.seq]
        assert plane.reporters()[(NodeType.WORKER, 1)] == (0, rep.seq)
    finally:
        plane.close()


def test_plane_exactly_once_across_shards_and_final_evicts():
    plane = IngestPlane(shards=4, inflight_limit=48, retry_after=0.02,
                        ledger_cap=400)
    try:
        trackers = {a: DeltaTracker(incarnation=0) for a in range(8)}
        for a, tr in trackers.items():
            rep = _compose(tr, node_id=a)
            ack = plane.report(rep, lambda r: "")
            tr.commit(rep)
            assert ack.accepted and not ack.resync
        view = plane.reporters()
        assert {k[1] for k in view} == set(range(8))
        assert all(v == (0, 1) for v in view.values())
        # deltas land on their own shard's ledger slice
        for a, tr in trackers.items():
            rep = _compose(tr, node_id=a, step=101)
            plane.report(rep, lambda r: "")
        assert all(v == (0, 2) for v in plane.reporters().values())
        # a final report (process exit) evicts its entry immediately
        bye = _compose(trackers[3], node_id=3, step=102, final=True)
        ack = plane.report(bye, lambda r: "")
        assert ack.accepted
        assert (NodeType.WORKER, 3) not in plane.reporters()
        assert plane.evictions() == 1
    finally:
        plane.close()


def test_resync_after_master_restart_across_shards():
    """A restarted master (fresh IngestPlane) has no baselines: every
    agent's next DELTA must come back resync=True so the tracker
    resends full — on every shard, not just shard 0."""
    old = IngestPlane(shards=4, inflight_limit=48, ledger_cap=400)
    trackers = {a: DeltaTracker(incarnation=0) for a in range(8)}
    try:
        for a, tr in trackers.items():
            rep = _compose(tr, node_id=a)
            old.report(rep, lambda r: "")
            tr.commit(rep)
    finally:
        old.close()

    reborn = IngestPlane(shards=4, inflight_limit=48, ledger_cap=400)
    try:
        for a, tr in trackers.items():
            delta = _compose(tr, node_id=a, step=101)
            assert not delta.full
            ack = reborn.report(delta, lambda r: "")
            assert ack.accepted and ack.resync
            tr.commit(delta)
            tr.request_full()  # what the agent-side resync hook does
            full = _compose(tr, node_id=a, step=102)
            assert full.full
            ack = reborn.report(full, lambda r: "")
            assert ack.accepted and not ack.resync
            tr.commit(full)
        assert all(
            v == (0, 3) for v in reborn.reporters().values()
        )
    finally:
        reborn.close()


# ------------------------------------------------- async front end


def test_async_server_hot_and_cold_lanes():
    """The event-loop server dispatches hot methods on the loop (async
    handler) and everything else on the bounded pool (sync handler),
    over a real gRPC channel."""
    from dlrover_tpu.common.grpc_utils import (
        AsyncRpcServer,
        GenericRpcClient,
    )

    calls = []

    def cold(method, message):
        calls.append(("cold", method))
        return comm.Response(success=True)

    async def hot(message):
        calls.append(("hot", message.node_id))
        return comm.NodeStatusAck(accepted=True, acked_seq=message.seq)

    server = AsyncRpcServer(
        cold, port=0, hot_handlers={"report_node_status": hot}
    )
    assert server.port > 0  # port known BEFORE start (dist_master)
    server.start()
    cli = GenericRpcClient(f"localhost:{server.port}", timeout=10.0)
    try:
        resp = cli.call("ping", comm.HeartBeat(
            node_id=0, node_type=NodeType.WORKER, timestamp=1.0,
        ))
        assert resp.success
        rep = comm.NodeStatusReport(timestamp=1.0, seq=5)
        rep.node_id, rep.node_type = 7, NodeType.WORKER
        ack = cli.call("report_node_status", rep)
        assert ack.accepted and ack.acked_seq == 5
        assert ("cold", "ping") in calls
        assert ("hot", 7) in calls
    finally:
        cli.close()
        server.stop(grace=0.2)


def test_the_master_server_is_the_async_one_whatever_the_env(monkeypatch):
    """One server for the master: the variable that used to choose the
    threaded one is read by nothing."""
    from dlrover_tpu.common.grpc_utils import AsyncRpcServer

    monkeypatch.setenv("DLROVER_TPU_ASYNC_INGEST", "0")
    jm, speed = _job_manager(2)
    server, _ = create_master_service(
        0, job_manager=jm, speed_monitor=speed
    )
    try:
        assert isinstance(server, AsyncRpcServer)
    finally:
        server.stop(grace=0.2)


# ---------------------------------------------------------- relay tier


def _master_service(agents=4):
    jm, speed = _job_manager(agents)
    server, servicer = create_master_service(
        0, job_manager=jm, speed_monitor=speed
    )
    server.start()
    return server, servicer


def test_relay_terminates_redeltas_and_forwards():
    """Downstream: the relay acks like a master (immediate, resync
    semantics). Upstream: it forwards ONE coalesced batch per interval
    whose sub-reports are RE-DELTA'D against the master-acked baseline
    and keep the original agent identity."""
    from dlrover_tpu.agent.relay import AggregatorRelay

    server, servicer = _master_service()
    relay = AggregatorRelay(
        f"localhost:{server.port}", relay_id=0, interval=30.0,
    )
    batches = []
    orig = relay._upstream.report_relay_batch
    relay._upstream.report_relay_batch = (
        lambda b: (batches.append(b), orig(b))[1]
    )
    try:
        t0 = DeltaTracker(incarnation=0)
        t1 = DeltaTracker(incarnation=0)
        for node_id, tr in ((0, t0), (1, t1)):
            rep = _compose(tr, node_id=node_id)
            ack = relay.handle("report_node_status", rep)
            assert ack.accepted and ack.acked_seq == rep.seq
            assert not ack.resync
            tr.commit(rep)
        relay._forward_once()  # the interval tick, deterministically
        assert relay.forwarded_batches == 1
        assert relay.forwarded_reports == 2
        assert len(batches[0].reports) == 2
        # the master's ledger is keyed by ORIGINAL agent, seq from the
        # relay's own upstream tracker stream
        view = servicer._reporters
        assert view[(NodeType.WORKER, 0)] == (0, 1)
        assert view[(NodeType.WORKER, 1)] == (0, 1)
        chain = relay.delivery_snapshot()
        assert chain[(NodeType.WORKER, 0)] == {
            "downstream_seq": 1, "upstream_seq": 1,
        }

        # second interval: only agent 0 reports, only its step moved —
        # the upstream sub-report is a DELTA carrying just the step
        rep = _compose(t0, node_id=0, step=101)
        assert relay.handle("report_node_status", rep).accepted
        t0.commit(rep)
        relay._forward_once()
        assert len(batches[1].reports) == 1  # agent 1 was not fresh
        fwd = batches[1].reports[0]
        assert (fwd.node_type, fwd.node_id) == (NodeType.WORKER, 0)
        assert not fwd.full and fwd.has_step and fwd.step == 101
        assert not fwd.has_goodput and not fwd.has_resource
        assert servicer._reporters[(NodeType.WORKER, 0)] == (0, 2)

        # a final report retires the agent end to end: relay slot,
        # relay ledger, and the master's ledger entry
        bye = _compose(t1, node_id=1, step=200, final=True)
        assert relay.handle("report_node_status", bye).accepted
        relay._forward_once()
        assert (NodeType.WORKER, 1) not in relay._slots
        assert (NodeType.WORKER, 1) not in servicer._reporters
    finally:
        relay._upstream.report_relay_batch = orig
        relay.stop(flush=False, grace=0.0)
        server.stop(grace=0.2)
        servicer.close()


def test_relay_restart_resyncs_agent():
    """A reborn relay has no baseline for its agents: a DELTA report
    must be acked resync=True — the agent cannot tell a relay restart
    from a master restart."""
    from dlrover_tpu.agent.relay import AggregatorRelay

    server, servicer = _master_service()
    relay = AggregatorRelay(
        f"localhost:{server.port}", relay_id=1, interval=30.0,
    )
    try:
        tracker = DeltaTracker(incarnation=0)
        rep = _compose(tracker, node_id=2)
        assert not relay.handle("report_node_status", rep).resync
        tracker.commit(rep)

        reborn = AggregatorRelay(
            f"localhost:{server.port}", relay_id=1, interval=30.0,
        )
        try:
            delta = _compose(tracker, node_id=2, step=101)
            assert not delta.full
            ack = reborn.handle("report_node_status", delta)
            assert ack.accepted and ack.resync
            tracker.commit(delta)
            tracker.request_full()
            full = _compose(tracker, node_id=2, step=102)
            ack = reborn.handle("report_node_status", full)
            assert ack.accepted and not ack.resync
        finally:
            reborn.stop(flush=False, grace=0.0)
    finally:
        relay.stop(flush=False, grace=0.0)
        server.stop(grace=0.2)
        servicer.close()


def test_relay_failover_drill():
    """Kill the relay mid-interval: the agent's ConnectionSupervisor
    fails over to the direct master address after
    DLROVER_TPU_RELAY_FAILOVER_S and the report stream continues —
    zero dropped, zero duplicated intervals (the master's ledger ends
    at EXACTLY the agent's last acked seq), with the failover
    journaled."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.relay import AggregatorRelay

    server, servicer = _master_service()
    master_addr = f"localhost:{server.port}"
    # interval long enough that nothing auto-forwards: the kill drops
    # relay-acked-but-unforwarded state, the worst case for delivery
    relay = AggregatorRelay(master_addr, relay_id=0, interval=30.0)
    relay.start()
    cli = MasterClient(
        f"localhost:{relay.port}", node_id=0, node_type=NodeType.WORKER,
        timeout=10.0, fallback_addr=master_addr, failover_after=0.5,
    )
    tracker = DeltaTracker(incarnation=0)
    cli.add_reconnect_hook("report-resync", tracker.request_full)
    try:
        acked = []
        for i in range(6):
            rep = _compose(tracker, node_id=0, step=100 + i)
            ack = cli.report_node_status(rep)
            assert ack is not None and ack.accepted, f"interval {i}"
            tracker.commit(rep)
            acked.append(rep.seq)
            if ack.resync:
                tracker.request_full()
            if i == 2:
                relay.kill()  # mid-interval: acked seqs 1-3 unflushed
        # the supervisor failed over relay -> direct and journaled it
        assert default_journal().events("relay.failover")
        # two-hop exactly-once: the master's ledger entry is the
        # agent's LAST acked seq — nothing dropped, nothing replayed
        assert servicer._reporters[(NodeType.WORKER, 0)] == (
            0, acked[-1],
        )
        # post-failover the master forced a resync (it never saw the
        # relay-terminated intervals), so full state was re-delivered
        assert tracker._seq == acked[-1]
    finally:
        cli.close()
        server.stop(grace=0.2)
        servicer.close()
