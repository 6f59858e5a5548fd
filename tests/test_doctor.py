"""hvd-doctor incident drills: seeded chaos scenarios must yield a
verdict naming the injected root cause.

Each drill runs a real failure through real component paths (SimCluster
shard protocol, the KVServer's epoch fence, the replicated KV's
elections, the serve router/admission planes) with the event journal
enabled, then asks :mod:`horovod_tpu.obs.doctor` to diagnose the
artifacts. The assertion is exact: a verdict that names the wrong cause
is a test failure, not a partial credit."""

import json
import subprocess
import sys

import pytest

from horovod_tpu.common import journal
from horovod_tpu.obs import doctor

import chaos


@pytest.fixture
def journal_dir(tmp_path, monkeypatch):
    d = tmp_path / "journal"
    monkeypatch.setenv("HOROVOD_JOURNAL_DIR", str(d))
    journal._reset_for_tests()
    yield d
    journal._reset_for_tests()


def _diagnose(journal_dir, **kw):
    journal._reset_for_tests()  # flush/close this process's writer
    ctx = doctor.build_timeline(journal_dir, **kw)
    return doctor.diagnose(ctx)


def _causes(verdict):
    return [i["cause"] for i in verdict["incidents"]]


# ---------------------------------------------------------------------------
# the drill matrix (ISSUE 20: >= 6 seeded scenarios)
# ---------------------------------------------------------------------------

def _kill_in_simcluster(journal_dir):
    with chaos.SimCluster(world=4, n_params=600) as c:
        c.run_steps(2, commit_every=1)
        c.kill(2)
        c.resize()
        c.run_steps(1)


def _kill_in_64_rank_journal_with_shed_storm(journal_dir):
    """A written 64-rank driver journal (spawns, step times, one exit
    with -9, the resize after it) beside a serve-plane journal of 200
    cache-exhaustion sheds: the louder incident is not the cause."""
    wd = journal.JournalWriter(journal_dir, host="driver0", pid=1)
    wd.append("driver", "resize", generation=1, slots=64, hosts=8,
              first=True)
    for r in range(64):
        wd.append("driver", "worker_spawn", rank=r, generation=1,
                  host=f"h{r // 8}", local_rank=r % 8)
    for step in range(50):
        for r in range(0, 64, 16):
            wd.append("driver", "step_time", rank=r, step=step,
                      step_time_sec=0.1)
    wd.append("driver", "worker_exit", generation=1, reason="failure",
              exit_code=-9, host="h3", local_rank=2)
    wd.append("driver", "resize", generation=2, slots=63, hosts=8)
    ws = journal.JournalWriter(journal_dir, host="serve0", pid=2)
    for i in range(200):
        ws.append("serve", "shed", reason="kv cache blocks exhausted",
                  trace_id=f"t{i}")
    wd.close()
    ws.close()


@pytest.mark.parametrize("kill", [_kill_in_simcluster,
                                  _kill_in_64_rank_journal_with_shed_storm])
def test_drill_worker_sigkill_mid_step(journal_dir, kill):
    """Drill 1: a worker is SIGKILLed mid-step (no drain). The doctor
    must name the dead rank, not the resize that cleaned up after it,
    nor a shed storm that wrote fifty times as many events."""
    kill(journal_dir)
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "dead_rank", _causes(v)
    inc = v["incidents"][0]
    assert inc["evidence"], "verdict must cite evidence event ids"
    assert "exit" in inc["root_cause"] and "-9" in inc["root_cause"]


def test_drill_drain_race(journal_dir):
    """Drill 2: the preemption notice lands but the host is reaped
    before the handoff completes — a drain that lost its race, distinct
    from a plain dead rank."""
    with chaos.SimCluster(world=4, n_params=600) as c:
        c.run_steps(2, commit_every=1)
        c.kill_during_drain(1)
        c.resize()
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "drain_race", _causes(v)
    assert "dead_rank" not in _causes(v), \
        "a raced drain must not double-report as an unexplained death"


def test_drill_stale_epoch_rival_driver(journal_dir, tmp_path):
    """Drill 3: a fenced-out rival driver keeps mutating through the
    real KVServer epoch fence — every 409 lands in the journal and the
    doctor calls the split-brain attempt."""
    from horovod_tpu.runner.http_kv import KVClient, StaleEpochError
    cp = chaos.ControlPlane(str(tmp_path / "kv"))
    try:
        KVClient("127.0.0.1", cp.port, epoch=7).put_json(
            "soak/current", {"v": 1})
        rival = KVClient("127.0.0.1", cp.port, epoch=3)
        for _ in range(2):
            with pytest.raises(StaleEpochError):
                rival.put_json("soak/rogue", {"v": 2}, attempts=1)
    finally:
        cp.close()
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "split_brain_attempt", _causes(v)
    assert "fencing held" in v["incidents"][0]["blast_radius"]


def test_drill_kv_leader_kill_mid_resize(journal_dir, tmp_path):
    """Drill 4: the replicated KV leader is SIGKILLed while an autoscale
    decision sits between decide and ack. The replicas' real elections
    journal from their subprocesses (they inherit HOROVOD_JOURNAL_DIR);
    the doctor must name the failover and flag the in-flight resize."""
    journal.emit("autoscaler", "autoscale_decide", control_epoch=1,
                 seq=4, action="up", victim=None, reason="slo_breach",
                 fleet=4)  # decided, never acked: the mid-resize window
    cp = chaos.ReplicatedControlPlane(str(tmp_path / "kv"),
                                      lease_seconds=0.3)
    try:
        cp.client.put_json("soak/a", {"v": 1}, deadline=20.0)
        lid = cp.kill_leader()
        cp.await_leader_other_than(lid, timeout=30.0)
    finally:
        cp.close()
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "kv_leader_failover", _causes(v)
    inc = v["incidents"][0]
    assert inc["detail"]["resize_in_flight"] is True
    assert "mid-resize" in inc["title"]


def test_drill_partition_heal(journal_dir):
    """Drill 5: serve discovery partitions from the KV and heals. The
    doctor must report a healed partition (low severity), not an open
    outage."""
    from horovod_tpu.serve.router import RequestRouter
    from horovod_tpu.common import kv_keys

    table = {kv_keys.serve_targets(): {
        "workers": [{"id": "w0", "addr": "127.0.0.1", "port": 19990}],
        "generation": 1}}
    r = RequestRouter()
    assert r.refresh_from_kv(table.get)
    for _ in range(3):  # the partition: discovery unreachable
        assert not r.refresh_from_kv(
            lambda key: (_ for _ in ()).throw(ConnectionError("part")))
    assert r.refresh_from_kv(table.get)  # heal
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "partition_healed", _causes(v)
    assert "partition" not in _causes(v)[1:], \
        "healed partition must not also report as unhealed"


def test_drill_flash_crowd_shed_storm(journal_dir):
    """Drill 6: a flash crowd slams a full queue; the admission plane
    sheds a storm of requests through the real frontend check."""
    from horovod_tpu.serve.admission import AdmissionController
    from horovod_tpu.serve.frontend import ServeFrontend
    from horovod_tpu.serve.router import RequestRouter
    fe = ServeFrontend(
        router=RequestRouter(),
        admission=AdmissionController(
            classes={"batch": 0, "interactive": 1}, tenant_qps=0.0))
    for i in range(14):
        shed = fe._admission_check(
            {"priority": "batch", "trace": {"id": f"t{i}"}},
            queue_fill=0.97)
        assert shed is not None and shed[0] == 429
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "shed_storm", _causes(v)
    assert v["incidents"][0]["detail"]["sheds"] >= 14


def test_drill_unhealed_partition_distinct(journal_dir):
    """Negative control for drill 5: the same partition WITHOUT the heal
    must escalate to the unhealed (higher-severity) verdict."""
    from horovod_tpu.serve.router import RequestRouter
    from horovod_tpu.common import kv_keys
    table = {kv_keys.serve_targets(): {
        "workers": [{"id": "w0", "addr": "127.0.0.1", "port": 19990}],
        "generation": 1}}
    r = RequestRouter()
    assert r.refresh_from_kv(table.get)
    assert not r.refresh_from_kv(lambda key: None)
    v = _diagnose(journal_dir)
    assert v["top_cause"] == "partition", _causes(v)


def test_healthy_journal_yields_no_incidents(journal_dir):
    journal.emit("driver", "resize", control_epoch=1, generation=1,
                 slots=4, hosts=2, first=True)
    journal.emit("driver", "worker_spawn", control_epoch=1, generation=1)
    v = _diagnose(journal_dir)
    assert v["incident_count"] == 0 and v["top_cause"] is None


# ---------------------------------------------------------------------------
# ordering + CLI + exports
# ---------------------------------------------------------------------------

def test_timeline_orders_by_epoch_before_wall_clock():
    """A stale-epoch writer with a FUTURE wall clock must still sort
    before the successor epoch's events — fenced order beats clocks."""
    events = [
        {"id": "b", "writer": "w2", "seq": 1, "control_epoch": 5,
         "t_wall": 100.0, "event": "new"},
        {"id": "a", "writer": "w1", "seq": 1, "control_epoch": 4,
         "t_wall": 900.0, "event": "stale"},  # skewed clock, old epoch
    ]
    ordered = doctor.order_events(events)
    assert [e["id"] for e in ordered] == ["a", "b"]


def test_timeline_carries_epoch_forward_within_writer():
    events = [
        {"id": "e1", "writer": "w1", "seq": 1, "control_epoch": 9,
         "t_wall": 1.0, "event": "claim"},
        {"id": "e2", "writer": "w1", "seq": 2, "t_wall": 2.0,
         "event": "unfenced-rides-fence"},
        {"id": "x", "writer": "w0", "seq": 1, "control_epoch": 2,
         "t_wall": 50.0, "event": "older-epoch"},
    ]
    ordered = doctor.order_events(events)
    assert [e["id"] for e in ordered] == ["x", "e1", "e2"]


def test_doctor_cli_json_and_verdict_file(journal_dir, tmp_path, capsys):
    journal.emit("driver", "worker_exit", generation=1, reason="failure",
                 exit_code=-9, host="h0", local_rank=0)
    journal._reset_for_tests()
    rc = doctor.main([str(journal_dir), "--json"])
    assert rc == 0
    v = json.loads(capsys.readouterr().out)
    assert v["top_cause"] == "dead_rank"
    # the persisted verdict (what hvd-top banners)
    persisted = doctor.read_verdict_file(journal_dir)
    assert persisted and persisted["incident_count"] == 1
    assert doctor.main([str(journal_dir), "--fail-on-incident"]) == 1


def test_doctor_cli_subprocess_smoke(journal_dir, tmp_path):
    """The `python -m horovod_tpu.obs.doctor` front door (hvd-doctor,
    `make doctor`) in a clean interpreter, Perfetto export included."""
    journal.emit("serve", "shed", reason="q full", trace_id="t0")
    journal._reset_for_tests()
    out = tmp_path / "timeline.json"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.obs.doctor",
         str(journal_dir), "--perfetto", str(out)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "hvd-doctor verdict" in proc.stdout
    trace = json.loads(out.read_text())
    assert any(e.get("name", "").startswith("serve:shed")
               for e in trace["traceEvents"])


def test_perfetto_export_fuses_flight_and_journal(journal_dir, tmp_path):
    journal.emit("driver", "resize", generation=1, slots=2, hosts=1)
    journal._reset_for_tests()
    fdir = tmp_path / "flight"
    fdir.mkdir()
    (fdir / "flight_rank0.json").write_text(json.dumps({
        "rank": 0, "size": 1, "origin_unix_us": 0, "dump_unix_us": 10_000,
        "trigger": "test", "reason": "",
        "events": [{"phase": "ENQ", "name": "grad", "ts_us": 1.0},
                   {"phase": "DONE", "name": "grad", "ts_us": 5.0}]}))
    ctx = doctor.build_timeline(journal_dir, flight_dir=fdir)
    out = tmp_path / "fused.json"
    doctor.export_perfetto(ctx, out)
    trace = json.loads(out.read_text())
    names = {e.get("name") for e in trace["traceEvents"]}
    assert "driver:resize" in names
    assert any("flight rank 0" in str(e.get("args", {}).get("name", ""))
               for e in trace["traceEvents"]
               if e.get("ph") == "M") or "grad" in names


# ---------------------------------------------------------------------------
# hvd-top doctor banner (satellite: verdict age + incident count)
# ---------------------------------------------------------------------------

def test_top_banner_reflects_verdict(journal_dir):
    from horovod_tpu.obs import top
    journal.emit("driver", "worker_exit", generation=1, reason="failure",
                 exit_code=-9, host="h0", local_rank=0)
    v = _diagnose(journal_dir)
    doctor.write_verdict_file(v, journal_dir)
    line = top.render_doctor_banner(journal_dir)
    assert "1 incident" in line and "dead_rank" in line
    assert "old" in line  # the verdict age marker


def test_top_banner_healthy_and_absent(journal_dir):
    from horovod_tpu.obs import top
    assert top.render_doctor_banner(journal_dir) is None  # no verdict yet
    journal.emit("driver", "resize", generation=1, slots=2, hosts=1)
    v = _diagnose(journal_dir)
    doctor.write_verdict_file(v, journal_dir)
    assert "healthy" in top.render_doctor_banner(journal_dir)


def test_top_once_subprocess_shows_doctor_banner(journal_dir, tmp_path,
                                                 monkeypatch):
    """`hvd-top --once` in a clean interpreter with HOROVOD_JOURNAL_DIR
    set: the banner leads with the newest verdict."""
    import os
    from horovod_tpu.metrics import MetricsExporter, record_step
    from horovod_tpu.metrics.registry import MetricsRegistry
    journal.emit("driver", "worker_exit", generation=1, reason="failure",
                 exit_code=-9, host="h0", local_rank=0)
    v = _diagnose(journal_dir)
    doctor.write_verdict_file(v, journal_dir)
    reg = MetricsRegistry()
    record_step("jax", 0.1, registry=reg)
    exp = MetricsExporter(reg, port=0, labels={"rank": "0"}).start()
    try:
        env = dict(os.environ, HOROVOD_JOURNAL_DIR=str(journal_dir))
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.obs.top", "--once",
             "--targets", f"127.0.0.1:{exp.port}"],
            capture_output=True, text=True, timeout=60, env=env)
    finally:
        exp.stop()
    assert proc.returncode == 0, proc.stderr
    assert "doctor:" in proc.stdout and "dead_rank" in proc.stdout


# ---------------------------------------------------------------------------
# regression pins: journaled events still reach their legacy surfaces
# ---------------------------------------------------------------------------

def test_journaled_drain_still_reaches_kv(journal_dir, tmp_path,
                                          monkeypatch):
    """The preemption announce now ALSO journals — the KV record the
    driver consumes must stay byte-for-byte what it always was."""
    from horovod_tpu.runner.elastic import preempt
    from horovod_tpu.runner.elastic import worker as elastic_worker
    from horovod_tpu.runner.http_kv import KVClient
    cp = chaos.ControlPlane(str(tmp_path / "kv"))
    try:
        client = KVClient("127.0.0.1", cp.port)
        monkeypatch.setattr(elastic_worker, "is_elastic_worker",
                            lambda: True)
        monkeypatch.setattr(elastic_worker, "_slot", lambda: ("h0", 1))
        monkeypatch.setattr(elastic_worker, "current_generation",
                            lambda: 3)
        monkeypatch.setattr(elastic_worker, "kv_client", lambda: client)
        preempt._announce()
        rec = cp.kv.get_json(preempt.drain_key("h0", 1))
        assert rec and int(rec["generation"]) == 3 and "ts" in rec
    finally:
        cp.close()
    events = journal.load_events(journal_dir)
    assert any(e["event"] == "drain_announce" and
               e["generation"] == 3 for e in events)


def test_journaled_straggler_still_logs_and_publishes(journal_dir):
    """The driver's straggler relay keeps its structured log line and
    its straggler_events list (the surfaces older tooling consumes)
    while also journaling."""
    import logging
    import threading
    from horovod_tpu.metrics.straggler import StragglerDetector
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    class _KV:
        def put_json(self, *a, **k):
            pass

    drv = ElasticDriver.__new__(ElasticDriver)
    drv._straggler = StragglerDetector(k=1.0, windows=1,
                                       min_rel_skew=0.0)
    drv._lock = threading.Lock()
    drv._generation = 2
    drv._epoch = 1
    drv.straggler_events = []
    drv._logger = logging.getLogger("horovod_tpu.elastic.driver")
    drv._log = lambda msg: None
    drv._kv = _KV()
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    cap = _Capture(level=logging.WARNING)
    drv._logger.addHandler(cap)
    try:
        drv._ingest_step_times({0: 0.1, 1: 0.1, 2: 0.1, 3: 2.0})
    finally:
        drv._logger.removeHandler(cap)
    assert drv.straggler_events and \
        drv.straggler_events[0]["rank"] == 3
    # the structured log line older tooling greps is still emitted
    assert any("straggler detected" in m for m in records)
    events = journal.load_events(journal_dir)
    assert any(e["event"] == "straggler" and e["rank"] == 3
               for e in events)
