"""Control-plane high availability (ISSUE 10): durable KV, driver crash
recovery, epoch fencing, headless workers, and the supervised restart.

Fast tests drive the machinery in-process (port-0 servers, injected
spawns, pid-level liveness) the way the rest of the elastic suite does;
the driver-restart smoke spawns a real supervised launcher with no-jax
workers (KV handshake + heartbeats only) so kill→respawn→adopt runs end
to end in seconds. The full training acceptance (SIGKILL the driver mid
ZeRO training, then kill a worker under the recovered driver) is
slow-marked — ``make soak`` territory.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

import chaos
from horovod_tpu.runner.http_kv import KVClient, KVServer, StaleEpochError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_worker_state():
    from horovod_tpu.runner.elastic import headless
    from horovod_tpu.runner.elastic import worker as elastic_worker
    elastic_worker._reset_epoch_for_tests()
    headless._reset_for_tests()
    yield
    elastic_worker._reset_epoch_for_tests()
    headless._reset_for_tests()


# ---------------------------------------------------------------------------
# durable KV: WAL + snapshot + replay


def test_wal_roundtrip_across_restart(tmp_path):
    d = str(tmp_path / "kv")
    kv = KVServer(kv_dir=d).start()
    assert kv.epoch == 1 and not kv.recovered
    kv.put_json("a/b", {"x": 1})
    kv.put_json("a/c", {"x": 2})
    kv.delete("a/c")
    KVClient("127.0.0.1", kv.port).put_json("h/1", {"ts": 9})
    kv.delete_prefix("h/")
    kv.stop()

    kv2 = KVServer(kv_dir=d).start()
    try:
        assert kv2.recovered and kv2.epoch == 2
        assert kv2.get_json("a/b") == {"x": 1}
        assert kv2.get_json("a/c") is None
        assert kv2.get_json("h/1") is None
        assert kv2.keys("a/") == ["a/b"]
    finally:
        kv2.stop()


def test_wal_compaction_keeps_full_state(tmp_path):
    d = str(tmp_path / "kv")
    kv = KVServer(kv_dir=d, snapshot_bytes=2048).start()
    for i in range(60):
        kv.put_json(f"k{i}", {"payload": "x" * 64, "i": i})
    kv.stop()
    assert os.path.exists(os.path.join(d, "snapshot.json"))
    # compaction reset the WAL below the threshold
    assert os.path.getsize(os.path.join(d, "wal.log")) < 2048

    kv2 = KVServer(kv_dir=d, snapshot_bytes=2048).start()
    try:
        assert len(kv2.keys("k")) == 60
        assert kv2.get_json("k59")["i"] == 59
    finally:
        kv2.stop()


def _durable_with_keys(d, n=6):
    kv = KVServer(kv_dir=str(d)).start()
    for i in range(n):
        kv.put_json(f"k{i}", {"i": i})
    kv.stop()
    return os.path.join(str(d), "wal.log")


def test_wal_truncated_tail_recovers_to_last_complete_record(tmp_path):
    wal = _durable_with_keys(tmp_path)
    with open(wal, "r+b") as f:
        f.truncate(os.path.getsize(wal) - 5)  # rip the last record's tail
    kv = KVServer(kv_dir=str(tmp_path)).start()
    try:
        assert sorted(kv.keys()) == [f"k{i}" for i in range(5)]
        # and the store stays appendable: the garbage tail was truncated
        kv.put_json("k9", {"i": 9})
    finally:
        kv.stop()
    kv2 = KVServer(kv_dir=str(tmp_path)).start()
    try:
        assert "k9" in kv2.keys() and "k4" in kv2.keys()
    finally:
        kv2.stop()


def test_wal_bitflip_crc_recovers_prefix(tmp_path):
    wal = _durable_with_keys(tmp_path)
    with open(wal, "rb") as f:
        data = bytearray(f.read())
    # flip a payload byte inside the 3rd record: replay must stop at the
    # last record whose CRC still verifies, not refuse to start
    off, rec = 0, 0
    while rec < 2:
        off += 8 + int.from_bytes(data[off:off + 4], "little")
        rec += 1
    data[off + 12] ^= 0xFF
    with open(wal, "wb") as f:
        f.write(data)
    kv = KVServer(kv_dir=str(tmp_path)).start()
    try:
        assert sorted(kv.keys()) == ["k0", "k1"]
    finally:
        kv.stop()


def test_empty_snapshot_degrades_to_wal_replay(tmp_path):
    _durable_with_keys(tmp_path)
    open(os.path.join(str(tmp_path), "snapshot.json"), "w").close()
    kv = KVServer(kv_dir=str(tmp_path)).start()
    try:
        assert sorted(kv.keys()) == [f"k{i}" for i in range(6)]
    finally:
        kv.stop()


def test_kv_replay_metrics_exported(tmp_path):
    from horovod_tpu.metrics import get_registry, snapshot_value
    _durable_with_keys(tmp_path)
    kv = KVServer(kv_dir=str(tmp_path)).start()
    try:
        snap = get_registry().snapshot()
        assert snapshot_value(snap, "hvd_kv_replay_seconds") is not None
        assert snapshot_value(snap, "hvd_kv_wal_bytes") == kv.wal_bytes > 0
    finally:
        kv.stop()


# ---------------------------------------------------------------------------
# epoch fencing: KV server side + worker side


def test_kv_fences_stale_epoch_and_adopts_newer(tmp_path):
    kv = KVServer(kv_dir=str(tmp_path)).start()
    try:
        base = kv.epoch
        stale = KVClient("127.0.0.1", kv.port, epoch=base - 1)
        with pytest.raises(StaleEpochError) as ei:
            stale.put_json("notify", {"generation": 99})
        assert ei.value.current == base and ei.value.offered == base - 1
        assert kv.get_json("notify") is None
        with pytest.raises(StaleEpochError):
            stale.delete("anything")
        # in-process claims are fenced identically (a stale driver object)
        with pytest.raises(StaleEpochError):
            kv.put_json("notify", {"generation": 99}, epoch=base - 1)
        # a NEWER claim (the respawned driver) advances and persists
        KVClient("127.0.0.1", kv.port, epoch=base + 3).put_json(
            "notify", {"generation": 100})
        assert kv.epoch == base + 3
        assert kv.get_json("notify") == {"generation": 100}
    finally:
        kv.stop()
    kv2 = KVServer(kv_dir=str(tmp_path)).start()
    try:
        assert kv2.epoch == 5  # adopted epoch persisted, +1 on restart
    finally:
        kv2.stop()


def test_worker_rejects_stale_epoch_commands(monkeypatch):
    import logging

    from horovod_tpu.runner.elastic import worker as elastic_worker
    kv = KVServer().start()
    monkeypatch.setenv("HOROVOD_ELASTIC", "1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(kv.port))
    monkeypatch.setenv("HOROVOD_ELASTIC_GENERATION", "4")
    monkeypatch.setenv("HOROVOD_CONTROL_EPOCH", "5")
    messages = []

    class Capture(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    handler = Capture()
    logging.getLogger("horovod_tpu.elastic.worker").addHandler(handler)
    try:
        # a lingering pre-crash driver (epoch 3) announces a resize: the
        # worker must not reset out of a healthy generation for it
        kv.put_json("notify", {"generation": 9, "epoch": 3})
        assert elastic_worker.poll_notification() is None
        assert any("stale_epoch_rejected" in m and
                   '"offered": 3' in m and
                   '"current": 5' in m for m in messages)
        # the current driver (epoch 6) is obeyed and raises the floor
        kv.put_json("notify", {"generation": 9, "epoch": 6})
        assert elastic_worker.poll_notification() == 9
        kv.put_json("notify", {"generation": 10, "epoch": 5})
        assert elastic_worker.poll_notification() is None
        # epoch-less records (pre-ISSUE-10 driver) stay accepted
        kv.put_json("notify", {"generation": 11})
        assert elastic_worker.poll_notification() == 11
    finally:
        logging.getLogger("horovod_tpu.elastic.worker").removeHandler(
            handler)
        kv.stop()


# ---------------------------------------------------------------------------
# KVClient total-deadline budget (satellite)


class _HungServer:
    """Accepts connections and never responds — the wedge-shaped failure
    per-attempt retries alone cannot bound."""

    def __enter__(self):
        self._sock = socket.socket()
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(16)
        self.port = self._sock.getsockname()[1]
        self._conns = []

        def accept_loop():
            while True:
                try:
                    conn, _ = self._sock.accept()
                    self._conns.append(conn)  # hold open, say nothing
                except OSError:
                    return

        threading.Thread(target=accept_loop, daemon=True).start()
        return self

    def __exit__(self, *exc):
        for c in self._conns:
            try:
                c.close()
            except OSError:
                pass
        self._sock.close()
        return False


def test_kv_client_deadline_bounds_hung_server():
    with _HungServer() as srv:
        client = KVClient("127.0.0.1", srv.port)
        t0 = time.monotonic()
        with pytest.raises(Exception):
            client.put_json("k", {"v": 1}, timeout=30.0, attempts=5,
                            deadline=1.0)
        assert time.monotonic() - t0 < 5.0, \
            "deadline did not bound the hung-server PUT"


def test_kv_client_get_timeout_bounds_hung_server():
    with _HungServer() as srv:
        client = KVClient("127.0.0.1", srv.port)
        t0 = time.monotonic()
        assert client.get_json("k", timeout=1.0) is None
        assert time.monotonic() - t0 < 5.0


# ---------------------------------------------------------------------------
# headless mode: outage accounting, deferred writes, deadline


def test_headless_queue_and_replay(monkeypatch):
    from horovod_tpu.metrics import get_registry, snapshot_value
    from horovod_tpu.runner.elastic import headless
    headless.note_failure()
    assert headless.is_headless()
    headless.queue_write("drain/h/0", {"generation": 1})
    headless.queue_write("shard_handoff/w4/2", {"world": 4})
    assert headless.pending_writes() == 2
    time.sleep(0.05)
    assert snapshot_value(get_registry().snapshot(),
                          headless.UNREACHABLE_SECONDS) >= 0.0
    assert headless.unreachable_seconds() > 0
    kv = KVServer().start()
    try:
        headless.note_success(KVClient("127.0.0.1", kv.port))
        assert not headless.is_headless()
        assert headless.pending_writes() == 0
        # replayed in order, nothing lost
        assert kv.get_json("drain/h/0") == {"generation": 1}
        assert kv.get_json("shard_handoff/w4/2") == {"world": 4}
        assert snapshot_value(get_registry().snapshot(),
                              headless.UNREACHABLE_SECONDS) == 0.0
    finally:
        kv.stop()


def test_headless_deadline_fires_abort_hook(monkeypatch):
    from horovod_tpu.runner.elastic import headless
    monkeypatch.setenv("HOROVOD_HEADLESS_DEADLINE_SECONDS", "0.05")
    fired = []
    headless.set_abort_hook(lambda outage: fired.append(outage))
    headless.note_failure()
    assert not fired, "deadline fired before it elapsed"
    time.sleep(0.1)
    headless.note_failure()
    assert fired and fired[0] > 0.05


def test_preempt_announce_queued_during_outage(monkeypatch):
    """A drain announcement that cannot land (driver mid-restart) is
    queued, not dropped — and replayed verbatim on reconnect."""
    from horovod_tpu.runner.elastic import headless, preempt
    monkeypatch.setenv("HOROVOD_ELASTIC", "1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", "1")  # nothing there
    monkeypatch.setenv("HOROVOD_HOSTNAME", "hostX")
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "3")
    preempt._announce()
    assert headless.pending_writes() == 1
    kv = KVServer().start()
    try:
        headless.note_success(KVClient("127.0.0.1", kv.port))
        announced = kv.get_json(preempt.drain_key("hostX", "3"))
        assert announced and "generation" in announced
    finally:
        kv.stop()


# ---------------------------------------------------------------------------
# driver crash recovery (in-process, injected spawns + real pids)


class _SpawnCounter:
    """FakeWorker-style spawn handle that records every spawn."""

    spawned = []

    def __init__(self, hostname, rank, command, env):
        self.hostname = hostname
        self.rank = rank
        self.env = env
        self.exit_code = None
        _SpawnCounter.spawned.append(self)

    def poll(self):
        return self.exit_code

    def terminate(self):
        self.exit_code = 0 if self.exit_code is None else self.exit_code

    kill = terminate

    def wait(self, timeout=None):
        return self.exit_code


def _mkdriver(tmp_path, monkeypatch, **kw):
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    return ElasticDriver(FixedHostDiscovery({"localhost": 2}),
                         min_np=2, max_np=2, command=["true"],
                         spawn_worker=_SpawnCounter,
                         kv_dir=str(tmp_path / "kv"), **kw)


def test_driver_recovery_adopts_live_workers(tmp_path, monkeypatch):
    """Driver #2 over the same KV dir restores the generation, adopts
    the still-beating workers instead of respawning them (no double
    spawn), outranks #1's epoch, and publishes the bumped epoch."""
    from horovod_tpu.runner.elastic.worker import heartbeat_key
    monkeypatch.setenv("HOROVOD_DRIVER_RECOVERY_WAIT_SECONDS", "2.0")
    _SpawnCounter.spawned = []
    d1 = _mkdriver(tmp_path, monkeypatch)
    epoch1 = d1.epoch
    d1._hosts.refresh()
    d1._rebalance(first=True)
    assert len(_SpawnCounter.spawned) == 2
    # the workers' heartbeats: our own (live) pid on localhost
    for host, slot in d1._expected_slots:
        d1._kv.put_json(heartbeat_key(host, slot),
                        {"pid": os.getpid(), "rank": slot,
                         "generation": 0, "ts": time.time()})
    slots1 = list(d1._expected_slots)
    d1._shutdown.set()
    d1._kv.stop()  # the "crash" (WAL is per-mutation, nothing to flush)

    spawned_before = len(_SpawnCounter.spawned)
    d2 = _mkdriver(tmp_path, monkeypatch)
    try:
        assert d2._kv.recovered and d2.epoch == epoch1 + 1
        assert d2._recover() is True
        assert d2.generation == 0
        assert d2._expected_slots == slots1
        # adopted, not respawned
        assert len(_SpawnCounter.spawned) == spawned_before
        assert all(getattr(w, "adopted", False)
                   for w in d2._workers.values())
        assert len(d2._workers) == 2
        assert not d2._rebalance_needed.is_set()
        assert d2._kv.get_json("control_epoch")["epoch"] == d2.epoch
        # worker-state/go records survived the crash
        assert d2._kv.get_json("generation")["generation"] == 0
    finally:
        d2._shutdown.set()
        d2._kv.stop()


def test_recovered_driver_respawns_after_adopted_worker_dies(
        tmp_path, monkeypatch):
    """The PR 4/9 failure path still works under a recovered driver: an
    adopted worker whose pid dies is reaped as a failure and the
    rebalance respawns the slot at a fresh generation."""
    from horovod_tpu.runner.elastic.worker import heartbeat_key
    monkeypatch.setenv("HOROVOD_DRIVER_RECOVERY_WAIT_SECONDS", "2.0")
    _SpawnCounter.spawned = []
    d1 = _mkdriver(tmp_path, monkeypatch)
    d1._hosts.refresh()
    d1._rebalance(first=True)
    # one live worker (this test process), one already-dead pid
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()
    (h0, s0), (h1, s1) = d1._expected_slots
    d1._kv.put_json(heartbeat_key(h0, s0),
                    {"pid": os.getpid(), "rank": 0, "ts": time.time()})
    d1._kv.put_json(heartbeat_key(h1, s1),
                    {"pid": dead.pid, "rank": 1, "ts": time.time()})
    d1._shutdown.set()
    d1._kv.stop()

    d2 = _mkdriver(tmp_path, monkeypatch)
    try:
        assert d2._recover() is True
        assert len(d2._workers) == 2
        spawned_before = len(_SpawnCounter.spawned)
        d2._reap_workers()  # the dead pid is a failure...
        assert d2._rebalance_needed.is_set()
        assert d2._host_failures.get(h1, 0) >= 1
        d2._hosts.refresh()
        d2._rebalance()  # ...and the next generation respawns the slot
        assert d2.generation == 1
        assert len(_SpawnCounter.spawned) == spawned_before + 1
    finally:
        d2._shutdown.set()
        d2._kv.stop()


def test_stale_driver_mutation_fenced_after_recovery(tmp_path,
                                                     monkeypatch):
    """Split-brain pin: after recovery, a lingering driver #1 (old epoch)
    trying to publish a resize is rejected by the KV server."""
    _SpawnCounter.spawned = []
    d1 = _mkdriver(tmp_path, monkeypatch)
    epoch1 = d1.epoch
    d1._hosts.refresh()
    d1._rebalance(first=True)
    d1._shutdown.set()
    d1._kv.stop()

    d2 = _mkdriver(tmp_path, monkeypatch)
    try:
        # driver #1's ghost comes back and issues a command over HTTP
        ghost = KVClient("127.0.0.1", d2._kv.port, epoch=epoch1)
        with pytest.raises(StaleEpochError):
            ghost.put_json("notify", {"generation": 99, "epoch": epoch1})
        # d2's own command path still works
        d2._publish("notify", {"generation": 1})
        assert d2._kv.get_json("notify")["epoch"] == d2.epoch
    finally:
        d2._shutdown.set()
        d2._kv.stop()


# ---------------------------------------------------------------------------
# serving router + hvd-top under discovery loss


def test_router_serves_stale_table_when_discovery_disappears():
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.serve.router import RequestRouter
    router = RequestRouter(retry_limit=1, registry=MetricsRegistry())
    assert router.refresh_from_kv(lambda k: {
        "generation": 3,
        "workers": [{"id": "w0", "addr": "127.0.0.1", "port": 1234}]})
    assert not router.discovery_stale
    # discovery dies (driver down): table kept, stale-marked, requests
    # still route to the last-known worker
    assert not router.refresh_from_kv(lambda k: None)
    assert router.discovery_stale
    info = router.stale_info()
    assert info["discovery_stale"] and info["workers"] == 1
    assert info["discovery_age_seconds"] >= 0
    resp = router.submit("r1", {"p": 1}, lambda w, p: {"status": "ok"})
    assert resp == {"status": "ok"}
    # a KV getter that RAISES (connection reset) is an outage too
    def boom(key):
        raise ConnectionError("kv gone")
    assert not router.refresh_from_kv(boom)
    # the driver returns: table refreshes, stale flag clears
    assert router.refresh_from_kv(lambda k: {
        "generation": 4,
        "workers": [{"id": "w0", "addr": "127.0.0.1", "port": 1234}]})
    assert not router.discovery_stale


def test_frontend_stats_surface_discovery_staleness():
    import urllib.request
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.serve.frontend import ServeFrontend
    from horovod_tpu.serve.router import RequestRouter
    reg = MetricsRegistry()
    router = RequestRouter(retry_limit=0, registry=reg)
    router.refresh_from_kv(lambda k: {"generation": 1, "workers": []})
    router.refresh_from_kv(lambda k: None)  # outage
    fe = ServeFrontend(router=router, registry=reg, port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{fe.port}/stats", timeout=5) as r:
            stats = json.loads(r.read())
        assert stats["router"]["discovery_stale"] is True
        assert stats["router"]["generation"] == 1
    finally:
        fe.stop()


class _StubMetricsServer:
    """A restartable /metrics.json endpoint (fixed port across restarts,
    like a worker exporter surviving a driver outage from hvd-top's
    point of view the scrape itself fails while the network blips)."""

    def __init__(self, port=0):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        snap = {"labels": {"rank": "0"},
                "metrics": [{"name": "hvd_engine_queue_depth",
                             "samples": [{"labels": {}, "value": 3}]}]}
        body = json.dumps(snap).encode()

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), H)
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()


def test_hvd_top_stale_banner_and_recovery():
    from horovod_tpu.obs.top import TopState
    srv = _StubMetricsServer()
    state = TopState([{"addr": "127.0.0.1", "port": srv.port}])
    rows, unreachable = state.refresh(window=False)
    assert rows and state.stale_age_seconds is None
    srv.stop()  # the outage: nothing answers
    rows, unreachable = state.refresh(window=False)
    assert rows, "outage must re-show the last good rows, not blank"
    assert unreachable == 1
    assert state.stale_age_seconds is not None
    text = state.render(rows, unreachable, "title")
    assert "STALE DATA" in text and "driver/KV down" in text
    # recovery: the endpoint returns (same port) and the banner clears
    srv2 = _StubMetricsServer(port=srv.port)
    try:
        rows, unreachable = state.refresh(window=False)
        assert rows and state.stale_age_seconds is None
        assert "STALE" not in state.render(rows, unreachable, "t")
    finally:
        srv2.stop()


def test_hvd_top_once_exits_nonzero_with_clear_message(capsys):
    from horovod_tpu.obs import top
    # a port nothing listens on
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    rc = top.main(["--once", "--targets", f"127.0.0.1:{port}"])
    assert rc == 1
    err = capsys.readouterr().err
    assert "driver/KV" in err and "unreachable" in err


# ---------------------------------------------------------------------------
# driver-restart smoke (fast tier): subprocess kill + respawn < 30s.
# Workers here are KV-handshake-only (no jax, no engine) so the whole
# supervised launch boots in ~a second.


SMOKE_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
from horovod_tpu.runner.elastic import worker as ew
gen = ew.rendezvous(timeout=60.0)
ew.start_heartbeat(0.2)
deadline = time.monotonic() + float(os.environ.get("WORK_SECONDS", "6"))
step = 0
while time.monotonic() < deadline:
    print(f"smoke-step pid={{os.getpid()}} "
          f"rank={{os.environ['HOROVOD_RANK']}} step={{step}} "
          f"t={{time.monotonic():.2f}}", flush=True)
    step += 1
    time.sleep(0.2)
ew.record_state(ew.current_generation(), ew.SUCCESS)
print(f"smoke-done pid={{os.getpid()}}", flush=True)
"""


def _launch_supervised(tmp_path, script_body, extra_env, np_=2):
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text(f"localhost:{np_}\n")
    discovery = tmp_path / "discover.sh"
    discovery.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    discovery.chmod(0o755)
    worker = tmp_path / "cp_worker.py"
    worker.write_text(textwrap.dedent(script_body).format(repo=REPO))
    env = dict(os.environ,
               HOROVOD_KV_DIR=str(tmp_path / "kvdir"),
               HOROVOD_DRIVER_RESTART_BACKOFF_SECONDS="0.2",
               # a cap, not a sleep: the recovered driver goes on as soon
               # as every slot's heartbeat is in. 3 s was too few for a
               # 0.5 s-deadline beat under six-way load (adopted 0 of 2,
               # both slots spawned twice: PR 28's whole run)
               HOROVOD_DRIVER_RECOVERY_WAIT_SECONDS="10.0",
               JAX_PLATFORMS="cpu", **extra_env)
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", str(np_), "--max-np", str(np_),
         "--host-discovery-script", str(discovery), "--verbose",
         "--", sys.executable, str(worker.resolve())],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    return proc, worker


def _read_until(proc, needle, timeout, lines):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and proc.poll() is None:
        line = proc.stdout.readline().decode(errors="replace")
        lines.append(line)
        if needle in line:
            return True
    return False


def test_driver_restart_smoke_subprocess(tmp_path):
    """SIGKILL the supervised driver while (engine-less) workers are
    stepping: the supervisor respawns it, the KV rehydrates from the
    WAL, the driver adopts the SAME worker pids (no double spawn), and
    the job completes rc 0. The workers step for 20 s so that they outlive
    the outage on a loaded machine too: under six xdist workers the
    supervisor has needed 6-14 s to bring the driver back, and a worker
    that finishes while nobody takes its SUCCESS record counts as failed
    and is spawned again."""
    proc, _ = _launch_supervised(tmp_path, SMOKE_WORKER,
                                 {"WORK_SECONDS": "20"})
    lines = []

    def stepping_pids(text_lines):
        return {line.split("pid=")[1].split()[0]
                for line in text_lines if "smoke-step" in line}

    # kill once BOTH workers are stepping: what a worker prints while no
    # driver is there to forward it never reaches this pipe, so a worker
    # that had not printed yet would be missing from the pid set below
    while len(stepping_pids(lines)) < 2:
        assert _read_until(proc, "smoke-step", 30, lines), "".join(lines)

    killed = chaos.kill_workers("elastic.supervisor --driver",
                                sig=signal.SIGKILL, under=proc.pid)
    assert killed, "driver process not found"
    try:
        out, _ = proc.communicate(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    assert "driver crashed" in text, text           # supervisor saw it
    # recovery ran and ended on the event, both heartbeats in, not on its
    # clock
    recovered = [json.loads(line[line.index("{"):])
                 for line in text.splitlines()
                 if "driver_recovered: {" in line]
    assert recovered and recovered[0]["adopted"] == \
        recovered[0]["expected"] == 2, text
    # both workers finished, and no worker was double-spawned: the pid
    # set across the whole run is exactly the two originals
    pids = stepping_pids(text.splitlines())
    assert len(pids) == 2, text
    done = [line for line in text.splitlines() if "smoke-done" in line]
    assert len(done) == 2, text
    assert {line.split("pid=")[1].split()[0] for line in done} == pids
    # and the worker logs survived in the durable dir
    logs = os.listdir(os.path.join(str(tmp_path / "kvdir"), "logs"))
    assert len(logs) == 2
    # the surviving WAL — a REAL driver-crash-and-recovery trace — must
    # replay clean against the protocol specs' rules (hvd-check
    # conformance: typed key registry, epoch monotonicity, go-barrier
    # ordering)
    from horovod_tpu.verify import conformance
    divergences = conformance.check_kv_wal(str(tmp_path / "kvdir"))
    assert divergences == [], divergences


# ---------------------------------------------------------------------------
# full acceptance (slow): SIGKILL the driver mid ZeRO training; workers
# never pause; a subsequent worker kill still runs blacklist→resize→
# recovery under the recovered driver.


ACCEPT_TRAIN = """
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import horovod_tpu as hvd_top
import horovod_tpu.jax as hvd
from horovod_tpu.jax import elastic
from horovod_tpu.parallel import zero

hvd_top.init()
P, BLOCK = 800, 64
world = hvd_top.size()
shard = zero._group_leaves([np.zeros(P, np.float32)], world, BLOCK)[0].shard
state = elastic.ShardedState(
    template=[np.zeros(P, np.float32)],
    sharded={{"opt": {{"m": np.zeros(shard, np.float32)}}}},
    block_size=BLOCK,
    params=np.zeros(P, np.float32), step=0)
TOTAL = int(os.environ.get("TOTAL_STEPS", "40"))

@elastic.run
def train(state):
    while state.step < TOTAL:
        out = np.asarray(hvd.allreduce(
            np.ones(2, np.float32), op=hvd.Sum,
            name=f"batch.{{state.step}}"))
        assert np.allclose(out, hvd_top.size()), (out, hvd_top.size())
        state.step += 1
        print(f"aprogress rank={{hvd_top.rank()}} step={{state.step}} "
              f"t={{time.monotonic():.2f}} "
              f"gen={{os.environ.get('HOROVOD_ELASTIC_GENERATION')}}",
              flush=True)
        state.commit()
        time.sleep(0.05)
    return state.step

steps = train(state)
print(f"accept-done rank={{hvd_top.rank()}} steps={{steps}}", flush=True)
hvd_top.shutdown()
"""


@pytest.mark.slow
def test_driver_kill_mid_training_acceptance(tmp_path):
    """ISSUE 10 acceptance: SIGKILL the driver mid-training → workers
    keep stepping through the outage (step timestamps in the durable
    worker logs never gap past a few heartbeat intervals), the
    supervisor respawns the driver, the KV rehydrates, and a subsequent
    worker SIGKILL still triggers the full PR 4/9 blacklist → resize →
    recovery path under the recovered driver."""
    proc, worker = _launch_supervised(
        tmp_path, ACCEPT_TRAIN,
        {"TOTAL_STEPS": "400",  # must outlive both chaos phases: a job
         # that *finishes* during the outage is a different scenario
         "HOROVOD_CONTROLLER_TIMEOUT_SECONDS": "10",
         "HOROVOD_FAILURES_TO_BLACKLIST": "1",
         "HOROVOD_BLACKLIST_COOLDOWN_SECONDS": "2",
         "HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS": "0.1"})
    lines = []
    assert _read_until(proc, "step=5 ", 120, lines), "".join(lines)

    # --- phase 1: kill the control plane, not the workers
    killed = chaos.kill_workers("elastic.supervisor --driver",
                                sig=signal.SIGKILL, under=proc.pid)
    assert killed, "driver process not found"
    kill1_t = time.monotonic()
    assert _read_until(proc, "driver_recovered", 60, lines), \
        "".join(lines)
    # workers kept stepping while the driver was dead
    assert _read_until(proc, "aprogress", 30, lines), "".join(lines)

    # --- phase 2: kill a WORKER under the recovered driver
    killed = chaos.kill_workers("cp_worker.py", sig=signal.SIGKILL,
                                count=1)
    assert killed, "no worker found to kill"
    try:
        out, _ = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    assert "blacklisting localhost" in text, text
    assert "accept-done" in text, text

    # per-rank step sequences never decrease (live resume), and the
    # durable worker logs prove stepping never paused much longer than a
    # heartbeat interval around the driver kill
    per_rank = {}
    for line in text.splitlines():
        if "aprogress" in line and "step=" in line:
            r = int(line.split("rank=")[1].split()[0])
            s = int(line.split("step=")[1].split()[0])
            assert s >= per_rank.get(r, 0), \
                f"rank {r} rolled back to step {s}:\n{text}"
            per_rank[r] = s
    assert per_rank and max(per_rank.values()) == 400, per_rank
    log_dir = os.path.join(str(tmp_path / "kvdir"), "logs")
    gap_ok = False
    for name in os.listdir(log_dir):
        ts = [float(line.split("t=")[1].split()[0])
              for line in open(os.path.join(log_dir, name))
              if "aprogress" in line and "t=" in line]
        # only the driver-kill window matters; resize pauses (phase 2)
        # are the PR 4/9 path and legitimately longer
        window = [t for t in ts if kill1_t - 3 <= t <= kill1_t + 6]
        if len(window) >= 2:
            gaps = [b - a for a, b in zip(window, window[1:])]
            assert max(gaps) < 3.0, \
                f"{name}: stepping paused {max(gaps):.1f}s at driver kill"
            gap_ok = True
    assert gap_ok, "no worker log covered the driver-kill window"


# ---------------------------------------------------------------------------
# ISSUE 19: the replicated control plane under the supervised launcher —
# SIGKILL the KV *leaseholder* (not the driver) and the job must ride
# the election.


def _check_replica_wals(base_dir: str, replicas: int = 3):
    from horovod_tpu.runner.replica_kv import replica_dir
    from horovod_tpu.verify import conformance
    for i in range(replicas):
        d = replica_dir(base_dir, i)
        divergences = conformance.check_kv_wal(d)
        assert divergences == [], (i, divergences)


def test_kv_leader_kill_smoke_subprocess(tmp_path):
    """Supervised launch with ``HOROVOD_KV_REPLICAS=3``: SIGKILL the KV
    leaseholder while engine-less workers step. A follower must win the
    election, the supervisor respawns the dead replica, worker
    heartbeats and the final SUCCESS records ride the failover client,
    and the job completes rc 0 with conformance-clean per-shard WALs on
    every replica."""
    proc, _ = _launch_supervised(tmp_path, SMOKE_WORKER,
                                 {"WORK_SECONDS": "8",
                                  "HOROVOD_KV_REPLICAS": "3",
                                  "HOROVOD_KV_LEASE_SECONDS": "0.5"})
    lines = []
    assert _read_until(proc, "smoke-step", 45, lines), "".join(lines)
    _pid, lid = chaos.kill_kv_leader(under=proc.pid)
    try:
        out, _ = proc.communicate(timeout=90)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    assert "kv_replica_respawn" in text, text  # supervisor healed fleet
    assert "elected leader" in text, text      # a follower took over
    done = [line for line in text.splitlines() if "smoke-done" in line]
    assert len(done) == 2, text
    assert f'"replica": {lid}' in text, text   # the leader was the victim
    _check_replica_wals(str(tmp_path / "kvdir"))


ACCEPT_KV_TRAIN = ACCEPT_TRAIN


@pytest.mark.slow
def test_kv_leader_kill_mid_training_acceptance(tmp_path):
    """ISSUE 19 acceptance: SIGKILL the KV leaseholder mid-ZeRO-training
    under a 3-replica control plane. A follower is elected (epoch bump),
    training and heartbeats continue through the failover, and a
    subsequent worker SIGKILL still drives the full blacklist → resize →
    recovery path against the replica set — zero acked-write loss (the
    recovered protocol state is exactly what the resize needs), zero
    split-brain (conformance-clean, epoch-monotone WALs everywhere)."""
    proc, worker = _launch_supervised(
        tmp_path, ACCEPT_KV_TRAIN,
        {"TOTAL_STEPS": "400",
         "HOROVOD_KV_REPLICAS": "3",
         "HOROVOD_KV_LEASE_SECONDS": "0.5",
         "HOROVOD_CONTROLLER_TIMEOUT_SECONDS": "10",
         "HOROVOD_FAILURES_TO_BLACKLIST": "1",
         "HOROVOD_BLACKLIST_COOLDOWN_SECONDS": "2",
         "HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS": "0.1"})
    lines = []
    assert _read_until(proc, "step=5 ", 120, lines), "".join(lines)

    # --- phase 1: kill the KV LEASEHOLDER, not the driver, not a worker
    _pid, lid = chaos.kill_kv_leader(under=proc.pid)
    assert _read_until(proc, "elected leader", 60, lines), "".join(lines)
    # training never stopped while the election ran
    assert _read_until(proc, "aprogress", 30, lines), "".join(lines)

    # --- phase 2: a worker dies — the elastic resize must complete
    # against the post-failover replica set
    killed = chaos.kill_workers("cp_worker.py", sig=signal.SIGKILL,
                                count=1)
    assert killed, "no worker found to kill"
    try:
        out, _ = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    assert "blacklisting localhost" in text, text
    assert "accept-done" in text, text
    assert "kv_replica_respawn" in text, text
    assert f'"replica": {lid}' in text, text
    # per-rank step sequences never decrease: no acked protocol state
    # (generation, go-barrier, worker records) was lost to the failover
    per_rank = {}
    for line in text.splitlines():
        if "aprogress" in line and "step=" in line:
            r = int(line.split("rank=")[1].split()[0])
            s = int(line.split("step=")[1].split()[0])
            assert s >= per_rank.get(r, 0), \
                f"rank {r} rolled back to step {s}:\n{text}"
            per_rank[r] = s
    assert per_rank and max(per_rank.values()) == 400, per_rank
    _check_replica_wals(str(tmp_path / "kvdir"))
