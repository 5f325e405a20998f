"""Unified telemetry: registry, journal, exposition, dump CLI, and the
instrumentation wired into servicer / event queue."""

import json
import re
import urllib.request

import pytest

from dlrover_tpu import telemetry as T
from dlrover_tpu.telemetry.http import MetricsServer
from dlrover_tpu.telemetry.journal import EventJournal, read_journal
from dlrover_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def fresh_defaults():
    """Isolate the process-wide registry/journal per test."""
    reg = T.set_default_registry(None)
    jr = T.set_default_journal(EventJournal(None))
    yield reg, jr
    T.set_default_registry(None)
    T.set_default_journal(EventJournal(None))


# ---------------------------------------------------------------- registry


def test_counter_gauge_lifecycle():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g", "a gauge")
    g.set(7)
    g.dec(2)
    assert g.value == 5


def test_labels_are_distinct_series():
    reg = MetricsRegistry()
    c = reg.counter("rpc_total", "by method", ["method"])
    c.labels(method="a").inc()
    c.labels(method="b").inc(4)
    assert c.labels(method="a").value == 1
    assert c.labels(method="b").value == 4
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    # a metric with declared labels refuses label-less use
    with pytest.raises(ValueError):
        c.inc()


def test_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("same", "x")
    b = reg.counter("same", "x")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("same", "x")
    with pytest.raises(ValueError):
        reg.counter("same", "x", ["extra"])


def test_histogram_buckets_cumulative_and_sum():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "x", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h._default_child().snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(56.05)
    assert dict(
        (b, c) for b, c in snap["buckets"]
    ) == {0.1: 1, 1.0: 3, 10.0: 4}  # cumulative; +Inf == count


def test_prometheus_text_format_validity():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", ["method"]).labels(
        method='get"task\n'
    ).inc()
    reg.gauge("up", "liveness").set(1)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.5, 5.0))
    h.observe(0.2)
    h.observe(7.0)
    text = reg.to_prometheus_text()
    assert text.endswith("\n")
    # every non-comment line is `name{labels} value`
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [^ ]+$'
    )
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:]", line), line
        else:
            assert sample.match(line), line
    # label escaping: quote and newline survive round-trippably
    assert r'method="get\"task\n"' in text
    # histogram exposition triplet with cumulative +Inf (the 7.0
    # observation exceeds every finite bucket and lands only in +Inf)
    assert 'lat_seconds_bucket{le="0.5"} 1' in text
    assert 'lat_seconds_bucket{le="5"} 1' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_sum" in text and "lat_seconds_count 2" in text
    assert "# TYPE lat_seconds histogram" in text


def test_registry_json_dump():
    reg = MetricsRegistry()
    reg.counter("c_total", "x", ["k"]).labels(k="v").inc(2)
    reg.histogram("h", "x", buckets=(1.0,)).observe(0.5)
    d = json.loads(reg.to_json())
    assert d["c_total"]["kind"] == "counter"
    assert d["c_total"]["series"]["k=v"] == 2
    assert d["h"]["series"][""]["count"] == 1


# ----------------------------------------------------------------- journal


def test_journal_seq_monotonic_and_file_roundtrip(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path)
    j.record("rendezvous.complete", round=1, nodes=[0, 1])
    j.record("checkpoint.save", tier="ram", step=10)
    j.record("checkpoint.restore", tier="ram", step=10)
    seqs = [e["seq"] for e in j.events()]
    assert seqs == [1, 2, 3]
    evts = read_journal(path)
    assert [e["kind"] for e in evts] == [
        "rendezvous.complete", "checkpoint.save", "checkpoint.restore",
    ]
    for e in evts:
        assert {"seq", "ts", "host", "pid", "kind"} <= set(e)


def test_journal_kind_prefix_filter_and_payload_isolation():
    j = EventJournal(None)
    # payload keys that LOOK like envelope keys (a `seq` that is a
    # sequence LENGTH) stay in data, never shadow the envelope
    j.record("checkpoint.save", step=1, seq=999, ts=-5.0, pid=-1)
    j.record("checkpoint.restore", step=2)
    j.record("checkpointing", step=3)  # not a dotted child
    evs = j.events("checkpoint")
    assert [e["kind"] for e in evs] == [
        "checkpoint.save", "checkpoint.restore",
    ]
    assert evs[0]["seq"] == 1
    assert evs[0]["data"]["seq"] == 999 and evs[0]["data"]["step"] == 1


def test_journal_ring_bounded():
    j = EventJournal(None, capacity=5)
    for i in range(12):
        j.record("k", i=i)
    evs = j.events()
    assert len(evs) == 5
    assert [e["data"]["i"] for e in evs] == list(range(7, 12))
    assert evs[-1]["seq"] == 12  # seq keeps counting past eviction


def test_read_journal_skips_torn_lines(tmp_path):
    path = tmp_path / "j.jsonl"
    good = json.dumps({"seq": 1, "ts": 2.0, "kind": "a"})
    path.write_text(good + "\n{torn wri\n")
    evts = read_journal(str(path))
    assert len(evts) == 1 and evts[0]["kind"] == "a"


def test_journal_rotation_caps_disk_contiguous_tail(tmp_path):
    """ISSUE 17: with DLROVER_TPU_JOURNAL_MAX_MB set, the journal
    rotates to ``<path>.1`` at the cap. Disk stays bounded (current +
    one predecessor), the stitched read_journal() view keeps a
    CONTIGUOUS tail of the newest events (rotation drops oldest-first,
    never punches holes), and each rotation journals itself."""
    import os

    path = str(tmp_path / "j.jsonl")
    cap = 2000
    j = EventJournal(path, max_bytes=cap)
    for i in range(40):
        j.record("checkpoint.save", step=i, i=i)
    evts = read_journal(path)
    iv = [e["data"]["i"] for e in evts if e["kind"] == "checkpoint.save"]
    assert iv, "stitched view lost everything"
    assert iv == list(range(iv[0], 40)), (
        "rotation must keep a contiguous tail, got holes: %r" % (iv,)
    )
    assert iv[-1] == 39  # the newest event always survives
    rotated = [e for e in evts if e["kind"] == "journal.rotated"]
    assert rotated, "no journal.rotated marker in the stitched view"
    for e in rotated:
        assert e["data"]["rotated_to"] == path + ".1"
        assert e["data"]["max_bytes"] == cap
    disk = os.path.getsize(path)
    old = path + ".1"
    if os.path.exists(old):
        disk += os.path.getsize(old)
    assert disk < 3 * cap, f"disk {disk}B exceeds 3x the {cap}B cap"
    # the in-memory ring is unaffected by file rotation
    assert len(j.events("checkpoint.save")) == 40


def test_journal_resync_follows_sibling_rotation(tmp_path):
    """Two processes share one journal path; when a sibling rotates the
    file out from under us, the periodic fstat/inode resync reopens the
    live path instead of appending forever to the renamed ``.1``."""
    import os

    from dlrover_tpu.telemetry import journal as journal_mod

    path = str(tmp_path / "shared.jsonl")
    j = EventJournal(path, max_bytes=0)  # this writer never rotates
    j.record("checkpoint.save", i=-1)
    # a sibling process rotates the file away
    os.replace(path, path + ".1")
    for i in range(journal_mod._RESYNC_EVERY + 2):
        j.record("checkpoint.save", i=i)
    # post-resync events landed in the RECREATED live file itself
    # (read_journal would stitch the .1 back in and hide a regression)
    assert os.path.exists(path)
    with open(path) as f:
        live_is = [json.loads(line)["data"]["i"] for line in f]
    assert live_is and live_is[-1] == journal_mod._RESYNC_EVERY + 1
    assert -1 not in live_is  # pre-rotation events stayed in the .1


def test_read_journal_survives_rotation_mid_stitch(tmp_path,
                                                   monkeypatch):
    """ISSUE 19 satellite bugfix: a rotation landing BETWEEN the two
    opens of one stitching pass used to silently drop the rotated
    tail — the pass saw no ``.1`` yet, then opened the already-rotated
    (fresh, near-empty) live file. read_journal now re-stats ``.1``
    after the pass and retries once on an inode change."""
    import os

    from dlrover_tpu.telemetry import journal as journal_mod

    path = str(tmp_path / "j.jsonl")
    with open(path, "w") as f:
        for i in range(10):
            f.write(json.dumps(
                {"seq": i + 1, "ts": float(i), "kind": "checkpoint.save",
                 "data": {"i": i}}
            ) + "\n")

    real_open = journal_mod._open_for_read
    raced = {"done": False}

    def racing_open(p):
        if p == path and not raced["done"]:
            # the sibling writer rotates at the worst moment: after
            # this pass found no ".1", before it opens the live file
            raced["done"] = True
            os.replace(path, path + ".1")
            with open(path, "w") as f:
                f.write(json.dumps(
                    {"seq": 11, "ts": 10.0, "kind": "checkpoint.save",
                     "data": {"i": 10}}
                ) + "\n")
        return real_open(p)

    monkeypatch.setattr(journal_mod, "_open_for_read", racing_open)
    evts = read_journal(path)
    # nothing dropped: the pre-rotation tail AND the post-rotation
    # event both survive, in timeline order
    assert [e["data"]["i"] for e in evts] == list(range(11))


def test_journal_envelope_stamps_job_id(monkeypatch):
    """ISSUE 19: with DLROVER_TPU_JOB_ID set to a non-default job, the
    envelope gains a ``job`` field; the default job's envelopes stay
    byte-identical to the pre-job shape (no key at all)."""
    from dlrover_tpu.telemetry import journal as journal_mod

    monkeypatch.setenv(journal_mod.ENV_JOB_ID, "tenant-a")
    assert journal_mod.current_job_id() == "tenant-a"
    j = EventJournal(None)
    assert j.record("checkpoint.save", step=1)["job"] == "tenant-a"
    # "default" (explicit or unset) never stamps the key
    for raw in ("default", ""):
        monkeypatch.setenv(journal_mod.ENV_JOB_ID, raw)
        assert journal_mod.current_job_id() == "default"
        j = EventJournal(None)
        assert "job" not in j.record("checkpoint.save", step=1)


def test_default_journal_env_configured(tmp_path, monkeypatch):
    path = str(tmp_path / "env.jsonl")
    monkeypatch.setenv("DLROVER_TPU_JOURNAL", path)
    jr = T.set_default_journal(None)  # re-read env
    assert jr.path == path
    T.record("fault.injected", fault="crash", step=3)
    assert read_journal(path)[0]["data"]["fault"] == "crash"


# -------------------------------------------------------------- exposition


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.read().decode()


def test_http_metrics_and_journal_endpoint():
    T.counter("dlrover_up_total", "x").inc()
    T.record("rendezvous.complete", round=1)
    T.record("checkpoint.save", step=5)
    srv = MetricsServer(host="127.0.0.1").start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = _get(f"{base}/metrics")
        assert "# TYPE dlrover_up_total counter" in text
        assert "dlrover_up_total 1" in text
        tail = json.loads(_get(f"{base}/journal"))
        assert [e["kind"] for e in tail] == [
            "rendezvous.complete", "checkpoint.save",
        ]
        only = json.loads(_get(f"{base}/journal?kind=checkpoint&n=10"))
        assert [e["kind"] for e in only] == ["checkpoint.save"]
        assert _get(f"{base}/healthz").strip() == "ok"
        d = json.loads(_get(f"{base}/metrics.json"))
        assert d["dlrover_up_total"]["series"][""] == 1
    finally:
        srv.stop()


def test_start_metrics_server_env_off(monkeypatch):
    from dlrover_tpu.telemetry.http import start_metrics_server

    monkeypatch.setenv("DLROVER_TPU_METRICS_PORT", "off")
    assert start_metrics_server() is None


# ------------------------------------------------------------------- dump


def test_dump_cli_renders_timeline(tmp_path, capsys):
    from dlrover_tpu.telemetry import dump

    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path)
    j.record("rendezvous.complete", round=1, duration_s=2.5)
    j.record("checkpoint.save", tier="ram", step=100)
    rc = dump.main([path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "rendezvous.complete" in out and "round=1" in out
    assert "checkpoint.save" in out and "tier=ram" in out
    # the second line carries a +delta to the first
    assert "+0." in out.splitlines()[1]
    rc = dump.main([path, "--kind", "checkpoint", "--json"])
    out = capsys.readouterr().out.strip()
    assert rc == 0
    assert json.loads(out)["kind"] == "checkpoint.save"


def test_dump_cli_missing_file():
    from dlrover_tpu.telemetry import dump

    assert dump.main(["/nonexistent/journal.jsonl"]) == 2


# ------------------------------------------------- wired instrumentation


def test_servicer_rpc_metrics():
    from dlrover_tpu.common import comm
    from dlrover_tpu.master.servicer import MasterServicer

    servicer = MasterServicer()
    servicer.handle("ping", comm.BaseRequest())
    servicer.handle("ping", comm.BaseRequest())
    with pytest.raises(ValueError):
        servicer.handle("no_such_rpc", None)
    reg = T.default_registry()
    req = reg.get("dlrover_rpc_requests_total")
    assert req.labels(method="ping").value == 2
    lat = reg.get("dlrover_rpc_latency_seconds")
    assert lat.labels(method="ping").count == 2
    errs = reg.get("dlrover_rpc_errors_total")
    assert errs.labels(method="no_such_rpc").value == 1
    text = reg.to_prometheus_text()
    assert 'dlrover_rpc_latency_seconds_bucket{method="ping",le="+Inf"} 2' in text


def test_rdzv_round_emits_round_event_and_metrics():
    from dlrover_tpu.master.elastic_training.rdzv_manager import (
        ElasticTrainingRendezvousManager,
    )

    mgr = ElasticTrainingRendezvousManager()
    mgr.update_rdzv_params(2, 2, 0.1, 1)
    mgr.join_rendezvous(0, 1)
    mgr.join_rendezvous(1, 1)
    _, _, world = mgr.get_comm_world(0)
    assert world == {0: 1, 1: 1}
    evs = T.default_journal().events("rendezvous.complete")
    assert len(evs) == 1
    assert evs[0]["data"]["round"] == 1
    assert evs[0]["data"]["nodes"] == [0, 1]
    reg = T.default_registry()
    assert reg.get("dlrover_rdzv_rounds_total").labels(
        name="training"
    ).value == 1
    assert reg.get("dlrover_rdzv_world_size").labels(
        name="training"
    ).value == 2


def test_event_queue_counts_dropped_oldest():
    from dlrover_tpu.util.event_queue import EventQueue

    q = EventQueue(max_size=3)
    for i in range(5):
        q.put(i)
    # oldest dropped, newest kept, drops counted
    assert q.dropped == 2
    assert len(q) == 3
    assert [q.get(timeout=0.01) for _ in range(3)] == [2, 3, 4]
    assert q.get(timeout=0.01) is None
    assert T.default_registry().get(
        "dlrover_event_queue_dropped_total"
    ).value == 2


def test_hang_detector_journals_stall():
    from dlrover_tpu.fault_tolerance.hanging_detector import (
        HangingDetector,
    )

    reports = []
    det = HangingDetector(
        report_fn=reports.append, min_timeout=0.05, multiplier=2.0
    )
    det.record_step(1)
    import time as _t

    _t.sleep(0.12)
    det._check_once()
    assert len(reports) == 1
    evs = T.default_journal().events("hang.detected")
    assert len(evs) == 1 and evs[0]["data"]["step"] == 1
    assert T.default_registry().get(
        "dlrover_hang_stalls_total"
    ).value == 1


def test_speed_monitor_sets_gauges():
    import time as _t

    from dlrover_tpu.master.monitor.speed_monitor import SpeedMonitor

    sm = SpeedMonitor()
    sm.add_running_worker("worker", 0)
    sm.add_running_worker("worker", 1)
    now = _t.time()
    sm.collect_global_step(10, now - 10)
    sm.collect_global_step(30, now)
    reg = T.default_registry()
    assert reg.get("dlrover_training_workers").value == 2
    assert reg.get("dlrover_training_global_step").value == 30
    assert reg.get(
        "dlrover_training_steps_per_second"
    ).value == pytest.approx(2.0, rel=0.01)


def test_local_master_serves_metrics_endpoint():
    """Acceptance: GET /metrics on a live master returns valid
    Prometheus text including RPC latency histograms and steps/s."""
    import time as _t

    from dlrover_tpu.common import comm
    from dlrover_tpu.master.local_master import LocalJobMaster

    master = LocalJobMaster(port=0)
    master.prepare()
    try:
        assert master.metrics_port > 0
        master.servicer.handle("ping", comm.BaseRequest())
        master.speed_monitor.add_running_worker("worker", 0)
        now = _t.time()
        master.servicer.handle(
            "report_global_step",
            comm.GlobalStep(step=5, timestamp=now - 1),
        )
        master.servicer.handle(
            "report_global_step",
            comm.GlobalStep(step=10, timestamp=now),
        )
        text = _get(
            f"http://127.0.0.1:{master.metrics_port}/metrics"
        )
        assert "# TYPE dlrover_rpc_latency_seconds histogram" in text
        assert (
            'dlrover_rpc_latency_seconds_count{method="ping"} 1'
            in text
        )
        assert (
            'dlrover_rpc_requests_total{method="report_global_step"} 2'
            in text
        )
        assert "dlrover_training_steps_per_second 5" in text
        assert "dlrover_training_workers 1" in text
    finally:
        master.stop()


def test_elastic_agent_serves_metrics_endpoint():
    """Acceptance: the agent exposes the same /metrics surface as the
    master (per-host scrape point)."""
    from dlrover_tpu.agent.elastic.training import (
        ElasticLaunchConfig,
        ElasticTrainingAgent,
    )

    T.counter("dlrover_agent_probe_total", "x").inc()
    agent = ElasticTrainingAgent(
        ElasticLaunchConfig(entrypoint="true"), master_client=None
    )
    try:
        assert agent._metrics_server is not None
        port = agent._metrics_server.port
        text = _get(f"http://127.0.0.1:{port}/metrics")
        assert "# TYPE dlrover_agent_probe_total counter" in text
    finally:
        agent.stop()
    assert agent._metrics_server is None
