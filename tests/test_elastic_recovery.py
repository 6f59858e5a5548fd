"""Self-healing elastic recovery: blacklist cooldown, bounded retries,
rendezvous-KV retry paths, and the host-update notification contract.

The driver-level tests run the real ElasticDriver state machine with an
injected spawn strategy (fake worker handles) — every transition is driven
explicitly, no subprocesses, no sleeps-as-synchronization (the only waiting
is a poll for a real cooldown interval to elapse). The subprocess test at
the end is the full acceptance path: kill a worker, watch the driver
blacklist its host, the cooldown re-admit it, and the job finish at a later
generation.
"""

import os
import re
import subprocess
import sys
import time

import pytest

import chaos
from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.runner.elastic.discovery import (
    FixedHostDiscovery,
    HostManager,
)
from horovod_tpu.runner.http_kv import KVClient, http_get_with_retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# host-update notifications (satellite: generation=None regression)


@pytest.fixture
def _notification_env(monkeypatch):
    from horovod_tpu.jax import elastic
    # drain anything a previous test left behind
    while not elastic._notification_queue.empty():
        elastic._notification_queue.get_nowait()
    monkeypatch.setattr(elastic, "_current_generation", lambda: 5)
    yield elastic
    while not elastic._notification_queue.empty():
        elastic._notification_queue.get_nowait()


def test_notify_none_generation_always_newer(_notification_env):
    """generation=None means "always newer": it must fire the interrupt
    regardless of the worker's current generation, and must never hit the
    integer staleness comparison."""
    elastic = _notification_env
    elastic.notify_hosts_updated(generation=None)
    with pytest.raises(HostsUpdatedInterrupt):
        elastic._check_host_updates()


def test_notify_stale_generation_filtered(_notification_env):
    elastic = _notification_env
    elastic.notify_hosts_updated(generation=3)  # worker is already at 5
    elastic._check_host_updates()  # no interrupt


def test_notify_mixed_none_and_stale(_notification_env):
    """A stale integer notification and a None notification together: the
    None one wins (interrupt), the stale one is ignored — and skip_sync
    aggregates across the accepted updates only."""
    elastic = _notification_env
    elastic.notify_hosts_updated(skip_sync=True, generation=3)
    elastic.notify_hosts_updated(skip_sync=False, generation=None)
    with pytest.raises(HostsUpdatedInterrupt) as exc:
        elastic._check_host_updates()
    assert exc.value.skip_sync is False


# ---------------------------------------------------------------------------
# bounded elastic retries


def test_elastic_run_bounded_retries(monkeypatch):
    """HOROVOD_ELASTIC_MAX_RETRIES bounds the HorovodInternalError retry
    loop: after N recoveries the error propagates instead of looping
    forever against a cluster that will never heal."""
    from horovod_tpu.jax import elastic
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RETRIES", "3")
    monkeypatch.setenv("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS", "0.01")
    calls = {"n": 0, "resets": 0}
    monkeypatch.setattr(elastic, "_reset", lambda: calls.__setitem__(
        "resets", calls["resets"] + 1))
    monkeypatch.setattr(elastic, "start_notification_poller", lambda: None)

    state = elastic.State(step=0)
    monkeypatch.setattr(state, "sync", lambda: None)

    @elastic.run
    def always_fails(state):
        calls["n"] += 1
        raise HorovodInternalError("peer keeps dying")

    with pytest.raises(HorovodInternalError, match="peer keeps dying"):
        always_fails(state)
    # initial attempt + 3 retries, and the 4th failure propagated without
    # another reset
    assert calls["n"] == 4, calls
    assert calls["resets"] == 3, calls


def test_elastic_run_recovers_within_budget(monkeypatch):
    """Failures below the bound still recover exactly as before."""
    from horovod_tpu.jax import elastic
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RETRIES", "5")
    monkeypatch.setenv("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS", "0.01")
    monkeypatch.setattr(elastic, "_reset", lambda: None)
    monkeypatch.setattr(elastic, "start_notification_poller", lambda: None)
    state = elastic.State(step=0)
    monkeypatch.setattr(state, "sync", lambda: None)
    attempts = {"n": 0}

    @elastic.run
    def flaky(state):
        attempts["n"] += 1
        if attempts["n"] <= 2:
            raise HorovodInternalError("transient")
        return "done"

    assert flaky(state) == "done"
    assert attempts["n"] == 3


# ---------------------------------------------------------------------------
# KV retry paths (satellite: flaky-server tests)


def test_http_get_with_retry_flaky_server():
    """The first two connections are dropped cold; the third succeeds —
    one transient ECONNRESET/REFUSED must not abort a scrape."""
    with chaos.FlakyHTTPServer(fail_first=2, body=b'{"ok": true}') as srv:
        body = http_get_with_retry(
            f"http://127.0.0.1:{srv.port}/metrics.json",
            timeout=2.0, attempts=3, backoff=0.01)
        assert body == b'{"ok": true}'
        assert srv.requests_seen == 3


def test_http_get_with_retry_exhausts():
    with chaos.FlakyHTTPServer(fail_first=10) as srv:
        with pytest.raises(Exception):
            http_get_with_retry(f"http://127.0.0.1:{srv.port}/x",
                                timeout=1.0, attempts=3, backoff=0.01)
        assert srv.requests_seen == 3


def test_kv_put_retries_flaky_server():
    """KVClient.put_json (READY records, reset requests) retries through
    transient connection failures instead of failing the rendezvous."""
    with chaos.FlakyHTTPServer(fail_first=2, body=b"{}") as srv:
        client = KVClient("127.0.0.1", srv.port)
        client.put_json("worker_state/g0/host/0", {"state": "READY"},
                        timeout=2.0, backoff=0.01)
        assert srv.requests_seen == 3


# ---------------------------------------------------------------------------
# blacklist cooldown (HostManager unit + driver state machine)


def test_host_manager_cooldown_readmits():
    disc = FixedHostDiscovery({"hostA": 1, "hostB": 1})
    mgr = HostManager(disc, cooldown=0.3)
    mgr.refresh()
    assert set(mgr.current) == {"hostA", "hostB"}
    mgr.blacklist("hostB")
    mgr.refresh()
    assert set(mgr.current) == {"hostA"}
    assert mgr.is_blacklisted("hostB")
    # poll (not a blind sleep) until the cooldown re-admits the host
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        mgr.refresh()
        if "hostB" in mgr.current:
            break
        time.sleep(0.02)
    assert set(mgr.current) == {"hostA", "hostB"}
    assert not mgr.is_blacklisted("hostB")


def test_host_manager_permanent_without_cooldown():
    mgr = HostManager(FixedHostDiscovery({"h": 1}), cooldown=0)
    mgr.blacklist("h")
    mgr.refresh()
    assert mgr.current == {}
    assert mgr.is_blacklisted("h")


class FakeWorker:
    """Injected spawn handle: the driver's full reap/blacklist/respawn path
    runs against these instead of subprocesses."""

    spawned = []

    def __init__(self, hostname, rank, command, env):
        self.hostname = hostname
        self.rank = rank
        self.env = env
        self.exit_code = None
        FakeWorker.spawned.append(self)

    def poll(self):
        return self.exit_code

    def terminate(self):
        self.exit_code = 0 if self.exit_code is None else self.exit_code

    def kill(self):
        self.terminate()

    def wait(self, timeout=None):
        return self.exit_code


def test_driver_blacklist_cooldown_rejoin(monkeypatch):
    """Acceptance (d), state-machine form: a worker failure blacklists its
    host (threshold 1), the next rebalance excludes it, the cooldown
    re-admits it, and a later generation respawns a worker there — all
    driven deterministically through the real ElasticDriver."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "1")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN_SECONDS", "0.3")
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 1, "hostB": 1})
    driver = ElasticDriver(disc, min_np=1, max_np=2,
                           command=["true"], spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        assert driver.generation == 0
        assert {w.hostname for w in FakeWorker.spawned} == \
            {"hostA", "hostB"}

        # hostB's worker dies → threshold 1 → blacklisted immediately
        next(w for w in FakeWorker.spawned
             if w.hostname == "hostB").exit_code = 1
        driver._reap_workers()
        assert driver._hosts.is_blacklisted("hostB")
        assert driver._rebalance_needed.is_set()

        # the next generation runs without hostB
        driver._hosts.refresh()
        driver._rebalance()
        assert driver.generation == 1
        assert all(h == "hostA" for h, _ in driver._expected_slots)

        # cooldown elapses → refresh re-admits hostB (polled, not slept)
        deadline = time.monotonic() + 5.0
        readmitted = False
        while time.monotonic() < deadline:
            if driver._hosts.refresh() and "hostB" in driver._hosts.current:
                readmitted = True
                break
            time.sleep(0.02)
        assert readmitted, "cooldown never re-admitted hostB"
        assert not driver._hosts.is_blacklisted("hostB")

        # and the following generation schedules hostB again
        spawned_before = len(FakeWorker.spawned)
        driver._rebalance()
        assert driver.generation == 2
        assert {h for h, _ in driver._expected_slots} == {"hostA", "hostB"}
        new = FakeWorker.spawned[spawned_before:]
        assert any(w.hostname == "hostB" for w in new), \
            "no worker respawned on the re-admitted host"
        assert any(w.env.get("HOROVOD_ELASTIC_GENERATION") == "2"
                   for w in new)
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_resets_cluster_health_on_generation_change():
    """ISSUE 7 satellite bugfix: after a resize the rank→host mapping
    shifts, so pre-resize straggler streaks / scrape baselines would be
    charged to whichever rank inherited the number. A rebalance must
    start every detector window clean — driven through the real
    ElasticDriver + real StragglerDetector."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 2})
    driver = ElasticDriver(disc, min_np=1, max_np=2,
                           command=["true"], spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        # one window short of flagging rank 1 (windows defaults to 3)
        for _ in range(driver._straggler.windows - 1):
            driver._ingest_step_times({0: 0.1, 1: 0.9, 2: 0.1})
        assert driver._straggler._streak.get(1, 0) == \
            driver._straggler.windows - 1
        assert driver._straggler.last_scores
        driver._metrics_prev[("hostA", 0)] = (10, 1.0)
        driver._anomaly_prev[("hostA", 0)] = 3.0

        driver._rebalance()  # resize: everything rolling must clear

        assert driver._straggler._streak == {}
        assert driver._straggler.last_scores == {}
        assert driver._straggler.flagged == set()
        assert driver._metrics_prev == {}
        assert driver._anomaly_prev == {}
        # the stale streak may not carry over: the same skew pattern needs
        # the full `windows` count again before flagging
        events = []
        for _ in range(driver._straggler.windows - 1):
            events += driver._ingest_step_times({0: 0.1, 1: 0.9, 2: 0.1}) \
                or []
        assert not driver.straggler_events, \
            "pre-resize samples leaked into the new generation"
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_straggler_detector_reset_zeroes_gauges():
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.metrics.straggler import StragglerDetector
    from horovod_tpu.metrics import snapshot_value

    reg = MetricsRegistry()
    det = StragglerDetector(k=3.0, windows=1, registry=reg)
    det.update({0: 0.1, 1: 0.1, 2: 0.9})
    assert det.flagged == {2}
    assert snapshot_value(reg.snapshot(), "hvd_straggler_flagged",
                          rank="2") == 1.0
    det.reset()
    assert det.flagged == set() and det.last_scores == {}
    assert snapshot_value(reg.snapshot(), "hvd_straggler_flagged",
                          rank="2") == 0.0
    assert snapshot_value(reg.snapshot(), "hvd_straggler_score",
                          rank="2") == 0.0


def test_driver_clean_generation_clears_failure_counts(monkeypatch):
    """One failure (below threshold 2) followed by a clean generation must
    not leave the host one strike from blacklisting forever."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "2")
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 1})
    driver = ElasticDriver(disc, min_np=1, max_np=1,
                           command=["true"], spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        FakeWorker.spawned[0].exit_code = 1
        driver._reap_workers()
        assert driver._host_failures.get("hostA") == 1
        # a clean generation: every expected slot records READY → the real
        # go-barrier loop publishes go AND clears the failure count
        import threading
        barrier = threading.Thread(target=driver._go_barrier_loop,
                                   daemon=True)
        barrier.start()
        gen = driver.generation
        for host, slot in driver._expected_slots:
            driver._registry.record(gen, host, slot, "READY")
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                driver._kv.get_json(f"go/g{gen}") is None:
            time.sleep(0.02)
        assert driver._kv.get_json(f"go/g{gen}") is not None, \
            "go barrier never released"
        assert "hostA" not in driver._host_failures
        driver._shutdown.set()
        barrier.join(timeout=5)
    finally:
        driver._shutdown.set()
        driver._kv.stop()


# ---------------------------------------------------------------------------
# ISSUE 9 satellite: a failure mid-sync during a resize restarts the sync
# instead of burning a steady-state retry


def test_elastic_run_mid_sync_failure_not_double_charged(monkeypatch):
    """A peer dying while the resize sync is in flight is part of the SAME
    incident, not a fresh one: the sync restarts against the next topology
    without consuming the bounded retry budget. Before the fix this
    sequence (2 training failures + 1 mid-sync failure on a budget of 2)
    exhausted the budget and propagated."""
    from horovod_tpu.jax import elastic
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RETRIES", "2")
    monkeypatch.setenv("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS", "0")
    monkeypatch.setattr(elastic, "_reset", lambda: None)
    monkeypatch.setattr(elastic, "start_notification_poller", lambda: None)
    state = elastic.State(step=0)
    seq = {"sync": 0, "func": 0}

    def sync():
        seq["sync"] += 1
        if seq["sync"] == 2:  # the re-sync right after the first failure
            raise HorovodInternalError("peer died mid-resize-sync")

    monkeypatch.setattr(state, "sync", sync)

    @elastic.run
    def train(state):
        seq["func"] += 1
        if seq["func"] <= 2:
            raise HorovodInternalError("boom")
        return "done"

    assert train(state) == "done"
    # the 2 training failures consumed exactly the budget of 2; the
    # mid-sync failure triggered a sync restart, not a retry charge
    assert seq["func"] == 3, seq
    assert seq["sync"] == 4, seq


def test_elastic_run_sync_failures_still_bounded(monkeypatch):
    """The sync-restart path must not loop forever against a cluster that
    can never complete a resize: consecutive sync failures are bounded by
    the same HOROVOD_ELASTIC_MAX_RETRIES."""
    from horovod_tpu.jax import elastic
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RETRIES", "2")
    monkeypatch.setenv("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS", "0")
    monkeypatch.setattr(elastic, "_reset", lambda: None)
    monkeypatch.setattr(elastic, "start_notification_poller", lambda: None)
    state = elastic.State(step=0)
    calls = {"sync": 0}

    def sync():
        calls["sync"] += 1
        raise HorovodInternalError("resize never completes")

    monkeypatch.setattr(state, "sync", sync)

    @elastic.run
    def train(state):
        raise AssertionError("training must never start")

    with pytest.raises(HorovodInternalError, match="never completes"):
        train(state)
    assert calls["sync"] == 3  # initial + 2 bounded restarts


def test_recovery_metrics_recorded(monkeypatch):
    """hvd_elastic_recovery_seconds / hvd_elastic_recoveries_total are
    recorded by the retry loop when a failure heals."""
    from horovod_tpu.jax import elastic
    from horovod_tpu.metrics import get_registry, snapshot_value
    monkeypatch.setenv("HOROVOD_ELASTIC_MAX_RETRIES", "5")
    monkeypatch.setenv("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS", "0")
    monkeypatch.setattr(elastic, "_reset", lambda: None)
    monkeypatch.setattr(elastic, "start_notification_poller", lambda: None)
    state = elastic.State(step=0)
    monkeypatch.setattr(state, "sync", lambda: None)
    before = snapshot_value(get_registry().snapshot(),
                            elastic.RECOVERIES_TOTAL) or 0.0
    attempts = {"n": 0}

    @elastic.run
    def flaky(state):
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise HorovodInternalError("transient")
        return "ok"

    assert flaky(state) == "ok"
    snap = get_registry().snapshot()
    assert snapshot_value(snap, elastic.RECOVERIES_TOTAL) == before + 1
    from horovod_tpu.metrics import snapshot_histogram
    hist = snapshot_histogram(snap, elastic.RECOVERY_SECONDS)
    assert hist and hist["count"] >= 1


# ---------------------------------------------------------------------------
# ISSUE 9 satellite: exit-by-drain is not a crash (driver regression)


def test_driver_drain_exit_not_blacklisted(monkeypatch):
    """A worker that announced a preemption drain and then exited —
    whatever the exit code — must not count toward
    HOROVOD_FAILURES_TO_BLACKLIST, must not trigger the flight-dump
    post-mortem, and must schedule a proactive resize that excludes the
    doomed host."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "1")
    monkeypatch.setenv("HOROVOD_PREEMPT_COOLDOWN_SECONDS", "0.3")
    from horovod_tpu.runner.elastic import preempt
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 1, "hostB": 1})
    driver = ElasticDriver(disc, min_np=1, max_np=2,
                           command=["true"], spawn_worker=FakeWorker)
    dumps = []
    monkeypatch.setattr(driver, "_collect_flight_dumps",
                        lambda failed: dumps.append(failed))
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)

        # hostB's worker announces a drain, then exits like a SIGTERM'd
        # process (143)
        driver._kv.put_json(preempt.drain_key("hostB", 0),
                            {"generation": 0, "ts": time.time()})
        driver._check_drains()
        assert ("hostB", 0) in driver._draining
        assert driver.drain_events and \
            driver.drain_events[0]["host"] == "hostB"
        assert driver._rebalance_needed.is_set()
        assert driver._hosts.is_draining("hostB")
        assert not driver._hosts.is_blacklisted("hostB")

        next(w for w in FakeWorker.spawned
             if w.hostname == "hostB").exit_code = 143
        driver._reap_workers()
        # threshold is 1: ANY failure charge would have blacklisted
        assert not driver._hosts.is_blacklisted("hostB")
        assert driver._host_failures.get("hostB") is None
        assert not dumps, "drain exit triggered a flight-dump post-mortem"

        # the proactive resize runs without the draining host
        driver._hosts.refresh()
        driver._rebalance()
        assert all(h == "hostA" for h, _ in driver._expected_slots)

        # after the drain cooldown the host (or its replacement) rejoins
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            driver._hosts.refresh()
            if "hostB" in driver._hosts.current:
                break
            time.sleep(0.02)
        assert "hostB" in driver._hosts.current
        driver._rebalance()
        assert {h for h, _ in driver._expected_slots} == \
            {"hostA", "hostB"}
        # the rejoin cleared the drain record and its KV key
        assert ("hostB", 0) not in driver._draining
        assert driver._kv.get_json(preempt.drain_key("hostB", 0)) is None
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_crash_still_blacklists_and_dumps(monkeypatch):
    """Control for the drain regression: an UNANNOUNCED failure keeps the
    crash semantics — failure strike, blacklist at threshold, flight-dump
    collection attempted."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "1")
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 1, "hostB": 1})
    driver = ElasticDriver(disc, min_np=1, max_np=2,
                           command=["true"], spawn_worker=FakeWorker)
    dumps = []
    monkeypatch.setattr(driver, "_collect_flight_dumps",
                        lambda failed: dumps.append(failed))
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        next(w for w in FakeWorker.spawned
             if w.hostname == "hostB").exit_code = 1
        driver._reap_workers()
        assert driver._hosts.is_blacklisted("hostB")
        assert dumps and dumps[0][0][0] == ("hostB", 0)
    finally:
        driver._shutdown.set()
        driver._kv.stop()


# ---------------------------------------------------------------------------
# ISSUE 9 satellite: the driver state machine beyond 8 ranks


@pytest.mark.parametrize("world", [16, 64])
def test_driver_kill_blacklist_cooldown_rejoin_large_world(world,
                                                           monkeypatch):
    """kill → blacklist → cooldown → rejoin through the real ElasticDriver
    at 16 and 64 slots (everything before this PR ran at 4-8): three hosts
    die, get blacklisted, the job rebalances to the smaller world, the
    cooldown re-admits them, and the next generation is whole again."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "1")
    monkeypatch.setenv("HOROVOD_BLACKLIST_COOLDOWN_SECONDS", "0.3")
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    FakeWorker.spawned = []
    hosts = {f"h{i:03d}": 1 for i in range(world)}
    disc = FixedHostDiscovery(hosts)
    driver = ElasticDriver(disc, min_np=world // 2, max_np=world,
                           command=["true"], spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        assert len(driver._expected_slots) == world

        victims = [f"h{i:03d}" for i in (1, world // 2, world - 1)]
        for w in FakeWorker.spawned:
            if w.hostname in victims:
                w.exit_code = 1
        driver._reap_workers()
        for v in victims:
            assert driver._hosts.is_blacklisted(v), v

        driver._hosts.refresh()
        driver._rebalance()
        gen_small = driver.generation
        assert len(driver._expected_slots) == world - len(victims)
        assert not ({h for h, _ in driver._expected_slots} & set(victims))

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            driver._hosts.refresh()
            if all(v in driver._hosts.current for v in victims):
                break
            time.sleep(0.02)
        assert all(v in driver._hosts.current for v in victims)

        spawned_before = len(FakeWorker.spawned)
        driver._rebalance()
        assert driver.generation == gen_small + 1
        assert len(driver._expected_slots) == world
        respawned = {w.hostname for w in FakeWorker.spawned[spawned_before:]}
        assert set(victims) <= respawned
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_respawned_worker_success_not_misread_as_drain():
    """A predecessor's DRAINED registry record must not be charged to the
    worker that later re-occupies the slot: its successful exit-0 is job
    completion, not a drain (observed live: the respawned worker finished
    the whole job and the stale g0 record turned that into a pointless
    re-drain + respawn loop)."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.registration import DRAINED

    FakeWorker.spawned = []
    disc = FixedHostDiscovery({"hostA": 1})
    driver = ElasticDriver(disc, min_np=1, max_np=1,
                           command=["true"], spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)   # gen 0
        # the gen-0 occupant drains away properly
        driver._registry.record(0, "hostA", 0, DRAINED)
        driver._workers.clear()
        driver._rebalance()             # gen 1 respawns the slot
        assert driver.generation == 1
        FakeWorker.spawned[-1].exit_code = 0
        driver._reap_workers()
        # completion, not drain: the g0 DRAINED record predates spawn
        assert driver._result == 0
        assert driver._shutdown.is_set()
        assert ("hostA", 0) not in driver._draining
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_stop_workers_escalates_to_kill():
    """Teardown must SIGKILL a worker that survives the SIGTERM grace:
    elastic workers treat SIGTERM as a preemption notice (drain at the
    next commit), so a worker wedged in a peerless collective would
    otherwise be orphaned on the host."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    class Stubborn:
        killed = False

        def __init__(self, *a):
            pass

        def poll(self):
            return 137 if self.killed else None

        def terminate(self):
            pass  # swallowed — the preempt handler defers the exit

        def wait(self, timeout=None):
            return self.poll()

        def kill(self):
            self.killed = True

    driver = ElasticDriver(FixedHostDiscovery({"h": 1}), min_np=1,
                           max_np=1, command=["true"],
                           spawn_worker=Stubborn)
    try:
        w = Stubborn()
        driver._workers[("h", 0)] = w
        driver._stop_workers(grace=0.1)
        assert w.killed
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_fetch_handoff_rejects_stale_payload(_preempt_env):
    """An hours-old handoff key (e.g. one a crashed consumer failed to
    GC) must not outrank a fresh buddy replica: fetch rejects payloads
    older than the drain cooldown."""
    import numpy as np
    preempt, kv = _preempt_env
    stacks = {"combined": {"float32": np.ones((1, 64), np.float32)}}
    assert preempt.publish_handoff(4, 2, stacks)
    assert preempt.fetch_handoff(4, 2) is not None
    # age the payload past the TTL in place
    payload = kv.get_json(preempt.handoff_key(4, 2))
    payload["ts"] = time.time() - 7200
    kv.put_json(preempt.handoff_key(4, 2), payload)
    assert preempt.fetch_handoff(4, 2) is None


# ---------------------------------------------------------------------------
# preemption drain: worker-side protocol units


@pytest.fixture
def _preempt_env(monkeypatch):
    from horovod_tpu.runner.elastic import preempt
    from horovod_tpu.runner.http_kv import KVServer
    preempt._reset_for_tests()
    kv = KVServer().start()
    monkeypatch.setenv("HOROVOD_ELASTIC", "1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
    monkeypatch.setenv("HOROVOD_RENDEZVOUS_PORT", str(kv.port))
    monkeypatch.setenv("HOROVOD_HOSTNAME", "testhost")
    monkeypatch.setenv("HOROVOD_LOCAL_RANK", "0")
    monkeypatch.setenv("HOROVOD_ELASTIC_GENERATION", "3")
    yield preempt, kv
    kv.stop()
    preempt._reset_for_tests()


def test_preempt_notice_announces_drain(_preempt_env):
    preempt, kv = _preempt_env
    assert not preempt.preempt_requested()
    preempt.request_preemption()
    assert preempt.preempt_requested()
    deadline = time.monotonic() + 5.0
    info = None
    while time.monotonic() < deadline and info is None:
        info = kv.get_json(preempt.drain_key("testhost", "0"))
        time.sleep(0.02)
    assert info and info["generation"] == 3


def test_commit_boundary_drains_and_hands_off(_preempt_env):
    """A pending preemption notice takes effect at commit(): the live
    shard lands on the KV (decodable, bit-exact) and DRAINED is recorded
    before the clean exit."""
    import numpy as np
    from horovod_tpu.jax import elastic
    from horovod_tpu.runner.elastic import worker as elastic_worker
    preempt, kv = _preempt_env

    recorded = []
    orig_record = elastic_worker.record_state
    elastic_worker.record_state = \
        lambda gen, state, client=None: recorded.append((gen, state))
    try:
        tmpl = [np.arange(500, dtype=np.float32)]
        m = np.arange(512, dtype=np.float32)
        state = elastic.ShardedState(
            template=tmpl, sharded={"opt": {"m": m}}, step=7)
        preempt.request_preemption()
        with pytest.raises(SystemExit) as exc:
            state.commit()
        assert exc.value.code == 0
        assert recorded == [(3, elastic_worker.DRAINED)]
        payload = kv.get_json(preempt.handoff_key(1, 0))
        assert payload and payload["world"] == 1
        stacks = preempt.decode_shard_stacks(payload["stacks"])
        np.testing.assert_array_equal(stacks["combined"]["float32"][0], m)
    finally:
        elastic_worker.record_state = orig_record


def test_handoff_codec_roundtrip_int8():
    import numpy as np
    from horovod_tpu.runner.elastic import preempt
    rng = np.random.RandomState(0)
    stacks = {"combined": {"float32": rng.randn(2, 512).astype(np.float32),
                           "int32": rng.randint(0, 9, (1, 256),
                                                dtype=np.int32)}}
    enc = preempt.encode_shard_stacks(stacks, quantized=True)
    dec = preempt.decode_shard_stacks(enc)
    f = stacks["combined"]["float32"]
    scale = np.abs(f).max()
    assert np.abs(dec["combined"]["float32"] - f).max() <= \
        scale / 127.0 + 1e-6
    np.testing.assert_array_equal(dec["combined"]["int32"],
                                  stacks["combined"]["int32"])
    # raw codec is bit-exact
    dec2 = preempt.decode_shard_stacks(
        preempt.encode_shard_stacks(stacks, quantized=False))
    np.testing.assert_array_equal(dec2["combined"]["float32"], f)


# ---------------------------------------------------------------------------
# partition tolerance (chaos harness: SIGSTOP = partitioned rank)


PARTITION_WORKER = """
import os, sys, time
sys.path.insert(0, {repo!r})
from horovod_tpu.engine import EngineSession, OP_ALLREDUCE

rank = int(os.environ["HOROVOD_RANK"])
size = int(os.environ["HOROVOD_SIZE"])
port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
s = EngineSession(rank=rank, size=size, transport="tcp",
                  addr="127.0.0.1", port=port, timeout_sec=30.0)
for step in range(30):
    h = s.enqueue(f"p{{step}}", OP_ALLREDUCE, "float32", [8])
    s.wait(h, timeout=25.0)
    print(f"partition-progress rank={{rank}} step={{step}}", flush=True)
s.shutdown()
print(f"partition worker {{rank}} OK", flush=True)
"""


def test_partition_heals_without_abort(tmp_path):
    """A short network partition (SIGSTOP'd rank, sockets open but silent)
    must NOT trigger the fast abort — it is indistinguishable from a slow
    rank and heals when traffic resumes. Detection stays reserved for real
    teardown (closed sockets / abort frames)."""
    import textwrap
    size = 2
    from horovod_tpu.runner.launch import free_ports
    port = free_ports(1)[0]
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(PARTITION_WORKER).format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port))
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    # wait for real progress, then partition rank 1 for a second mid-run
    # (generous deadline: jax import under CI load dominates)
    deadline = time.monotonic() + 240
    saw_progress = False
    while time.monotonic() < deadline:
        line = procs[1].stdout.readline().decode()
        if "partition-progress rank=1 step=3" in line:
            saw_progress = True
            break
        if line == "" and procs[1].poll() is not None:
            break  # EOF: drained every buffered line and the rank exited
    assert saw_progress, "rank 1 never progressed"
    chaos.stall(procs[1].pid, 1.0)
    outs = [p.communicate(timeout=180)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    assert f"partition worker 1 OK" in outs[1]


# ---------------------------------------------------------------------------
# full subprocess acceptance (d): kill → blacklist → cooldown → rejoin


ELASTIC_TRAIN = """
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import horovod_tpu as hvd_top
import horovod_tpu.jax as hvd
from horovod_tpu.jax import elastic

hvd_top.init()
state = elastic.State(step=0)
TOTAL = int(os.environ.get("TOTAL_STEPS", "25"))

@elastic.run
def train(state):
    while state.step < TOTAL:
        out = np.asarray(hvd.allreduce(
            np.ones(2, np.float32), op=hvd.Sum,
            name=f"batch.{{state.step}}"))
        assert np.allclose(out, hvd_top.size()), (out, hvd_top.size())
        print(f"progress rank={{hvd_top.rank()}} step={{state.step}} "
              f"gen={{os.environ.get('HOROVOD_ELASTIC_GENERATION')}}",
              flush=True)
        state.step += 1
        state.commit()
        time.sleep(0.05)
    return state.step

steps = train(state)
print(f"worker-done rank={{hvd_top.rank()}} steps={{steps}} "
      f"gen={{os.environ.get('HOROVOD_ELASTIC_GENERATION')}}", flush=True)
hvd_top.shutdown()
"""


SHARDED_TRAIN = """
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
TOTAL = int(os.environ.get("TOTAL_STEPS", "25"))

@elastic.run
def train(state):
    while state.step < TOTAL:
        out = np.asarray(hvd.allreduce(
            np.ones(2, np.float32), op=hvd.Sum,
            name=f"batch.{{state.step}}"))
        assert np.allclose(out, hvd_top.size()), (out, hvd_top.size())
        state.step += 1
        print(f"sprogress rank={{hvd_top.rank()}} step={{state.step}} "
              f"gen={{os.environ.get('HOROVOD_ELASTIC_GENERATION')}}",
              flush=True)
        state.commit()
        time.sleep(0.05)
    return state.step

steps = train(state)
print(f"sharded-done rank={{hvd_top.rank()}} steps={{steps}}", flush=True)
hvd_top.shutdown()
"""


@pytest.mark.slow
def test_preempt_drain_subprocess_no_blacklist_live_resume(tmp_path):
    """End-to-end preemption drain over real processes: SIGTERM one
    elastic worker mid-training → it announces the drain, finishes its
    step, hands off its live ZeRO shard, and exits 0; the driver treats
    it as a drain (no blacklist), holds the host out for the drain
    cooldown, and the post-cooldown generation resumes from the LIVE step
    — the printed step sequence never goes backward."""
    import signal as _signal
    import textwrap
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text("localhost:2\n")
    discovery = tmp_path / "discover.sh"
    discovery.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    discovery.chmod(0o755)
    train = tmp_path / "train_drain.py"
    train.write_text(textwrap.dedent(SHARDED_TRAIN).format(repo=REPO))

    env = dict(os.environ, TOTAL_STEPS="25",
               HOROVOD_CONTROLLER_TIMEOUT_SECONDS="10",
               HOROVOD_FAILURES_TO_BLACKLIST="1",
               HOROVOD_PREEMPT_COOLDOWN_SECONDS="2",
               HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS="0.1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", "2", "--max-np", "2",
         "--host-discovery-script", str(discovery), "--verbose",
         "--", sys.executable, str(train.resolve())],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    lines = []
    deadline = time.monotonic() + 120
    progressed = False
    while time.monotonic() < deadline and proc.poll() is None:
        line = proc.stdout.readline().decode(errors="replace")
        lines.append(line)
        if "step=3" in line:
            progressed = True
            break
    assert progressed, "".join(lines)
    # the preemption notice: SIGTERM, not SIGKILL
    killed = chaos.kill_workers("train_drain.py", sig=_signal.SIGTERM,
                                count=1)
    assert killed, "no worker found to notify"

    try:
        out, _ = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    # drain, not crash: announced, and never blacklisted
    assert "drain announced" in text, text
    assert "blacklisting" not in text, text
    assert "sharded-done" in text, text
    # live resume: per-rank step sequences never decrease across the
    # generation change (a rollback to the commit would repeat steps)
    per_rank = {}
    for line in text.splitlines():
        if "sprogress" in line and "step=" in line:
            r = int(line.split("rank=")[1].split()[0])
            s = int(line.split("step=")[1].split()[0])
            assert s >= per_rank.get(r, 0), \
                f"rank {r} rolled back to step {s}:\n{text}"
            per_rank[r] = s
    assert per_rank and max(per_rank.values()) == 25, per_rank


def test_elastic_blacklist_cooldown_rejoin_subprocess(tmp_path):
    """Acceptance (d), end to end: kill one worker → the driver blacklists
    its host (threshold 1) → with every host blacklisted the job waits →
    the cooldown re-admits the host → workers rejoin at a later generation
    → training completes with committed state intact."""
    import textwrap
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text("localhost:2\n")
    discovery = tmp_path / "discover.sh"
    discovery.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    discovery.chmod(0o755)
    train = tmp_path / "train_cooldown.py"
    train.write_text(textwrap.dedent(ELASTIC_TRAIN).format(repo=REPO))

    env = dict(os.environ, TOTAL_STEPS="25",
               HOROVOD_CONTROLLER_TIMEOUT_SECONDS="10",
               HOROVOD_FAILURES_TO_BLACKLIST="1",
               HOROVOD_BLACKLIST_COOLDOWN_SECONDS="2",
               HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS="0.1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", "2", "--max-np", "2",
         "--host-discovery-script", str(discovery), "--verbose",
         "--", sys.executable, str(train.resolve())],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)

    lines = []
    deadline = time.monotonic() + 120
    progressed = False
    while time.monotonic() < deadline and proc.poll() is None:
        line = proc.stdout.readline().decode(errors="replace")
        lines.append(line)
        if "step=2" in line:
            progressed = True
            break
    assert progressed, "".join(lines)
    killed = chaos.kill_workers("train_cooldown.py", count=1)
    assert killed, "no worker found to kill"

    try:
        out, _ = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    text = "".join(lines) + out.decode(errors="replace")
    assert proc.returncode == 0, text
    assert "blacklisting localhost" in text, text
    done = [line for line in text.splitlines() if "worker-done" in line]
    assert done, text
    # the job finished in a generation AFTER the one that was running when
    # the host was blacklisted — i.e. the host re-joined post-cooldown
    # (the driver writes to the same pipe: its "[elastic-driver]" can follow
    # a worker's number without a space, so read the digits alone)
    def number(line, key):
        return int(re.search(key + r"=(\d+)", line).group(1))

    final_gens = [number(line, "gen") for line in done]
    assert all(g >= 1 for g in final_gens), text
    # committed state survived: nobody restarted from step 0 post-rejoin
    post = [number(line, "step")
            for line in text.splitlines()
            if "progress" in line and "gen=" in line and
            number(line, "gen") >= 1]
    assert post and min(post) > 0, text
