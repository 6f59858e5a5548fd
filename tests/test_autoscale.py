"""Traffic-driven autoscaler (ISSUE 15): policy hysteresis/cooldowns/
clamps, the epoch-claimed KV decision machine + driver-recovery resume,
SLO-aware admission (priority classes, tenant quotas), the router's
immediate drain announce, the driver's scale-up/drain actuation (FakeWorker
leg, chaos compose), and the slow-marked closed-loop smoke."""

import threading
import time

import pytest

from horovod_tpu.common import kv_keys
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.runner.elastic.autoscaler import (ACK, DECIDE, DOWN,
                                                   DRAIN, HOLD, RESIZE, UP,
                                                   Autoscaler,
                                                   AutoscalePolicy,
                                                   Decision, WorkerSLO,
                                                   autoscale_status,
                                                   slo_headroom,
                                                   worker_slo_from_snapshot)
from horovod_tpu.serve.admission import (AdmissionController, TokenBucket,
                                         parse_priority_classes)


def _slo(key, qd=0.0, p99=10.0, inflight=0.0):
    return WorkerSLO(key, qd, p99, None, inflight)


HOT = [_slo("h/0", qd=20, p99=900.0, inflight=5)]
IDLE2 = [_slo("a/0"), _slo("b/0")]


def _policy(**kw):
    base = dict(min_workers=1, max_workers=3, queue_bound=8,
                p99_bound_ms=500.0, idle_occupancy=0.25, up_windows=2,
                down_windows=2, up_cooldown=0.0, down_cooldown=0.0)
    base.update(kw)
    return AutoscalePolicy(**base)


class FakeOps:
    def __init__(self):
        self.ups = 0
        self.drains = []

    def scale_up(self):
        self.ups += 1

    def start_drain(self, key):
        self.drains.append(key)


class DictKV(dict):
    """put_json/get_json surface recording the claimed epoch per write."""

    def __init__(self):
        super().__init__()
        self.epochs = {}

    def put_json(self, key, value, epoch=None):
        self[key] = value
        self.epochs[key] = epoch

    def get_json(self, key):
        return self.get(key)


# ---------------------------------------------------------------------------
# policy: hysteresis, cooldowns, clamps, victim selection


def test_one_window_spike_never_decides():
    pol = _policy()
    assert pol.update(HOT) == "breach"
    assert pol.decide(HOT).action == HOLD
    assert pol.update(IDLE2) == "idle"  # streak broken
    assert pol.hot_streak == 0
    assert pol.update(HOT) == "breach"
    assert pol.decide(HOT).action == HOLD


def test_sustained_breach_scales_up_and_resets_streaks():
    pol = _policy()
    pol.update(HOT), pol.update(HOT)
    d = pol.decide(HOT)
    assert d.action == UP and "breached" in d.reason
    assert pol.hot_streak == 0 and pol.idle_streak == 0


def test_sustained_idle_scales_down_least_loaded():
    pol = _policy(idle_occupancy=0.5)
    fleet = [_slo("a/0", inflight=1), _slo("b/0", inflight=0)]
    pol.update(fleet), pol.update(fleet)
    d = pol.decide(fleet)
    assert d.action == DOWN and d.victim == "b/0"


def test_clamps_hold_at_bounds():
    pol = _policy(max_workers=1)
    pol.update(HOT), pol.update(HOT)
    assert pol.decide(HOT).action == HOLD
    pol2 = _policy(min_workers=2)
    pol2.update(IDLE2), pol2.update(IDLE2)
    assert pol2.decide(IDLE2).action == HOLD


def test_per_direction_cooldowns():
    pol = _policy(up_cooldown=3600.0)
    pol.update(HOT), pol.update(HOT)
    assert pol.decide(HOT, now=time.monotonic()).action == UP
    pol.update(HOT), pol.update(HOT)
    d = pol.decide(HOT, now=time.monotonic())
    assert d.action == HOLD and "cooling" in d.reason
    # the down direction has its own clock: an up decision does not
    # consume the down budget
    pol.update(IDLE2), pol.update(IDLE2)
    assert pol.decide(IDLE2, now=time.monotonic()).action == DOWN


def test_victim_selection_excludes_draining():
    fleet = [_slo("a/0", inflight=0), _slo("b/0", inflight=3)]
    assert AutoscalePolicy.pick_victim(fleet, draining=["a/0"]) == "b/0"
    assert AutoscalePolicy.pick_victim(fleet, draining=["a/0", "b/0"]) \
        is None


def test_victim_selection_prefers_host_top_slot():
    """The elastic assignment packs local_ranks contiguously per host,
    so only a host's highest occupied slot is actually sheddable —
    draining a lower one would evict a different, healthy worker."""
    fleet = [_slo("A/0", inflight=0), _slo("A/1", inflight=5),
             _slo("B/0", inflight=1)]
    # A/0 is least loaded but NOT sheddable; among {A/1, B/0} -> B/0
    assert AutoscalePolicy.pick_victim(fleet) == "B/0"
    assert AutoscalePolicy.pick_victim(
        [_slo("A/0"), _slo("A/1")]) == "A/1"
    # flat ids (the fleet sim) are all sheddable
    assert AutoscalePolicy.pick_victim(
        [_slo("w0", inflight=3), _slo("w1", inflight=0)]) == "w1"


def test_classify_breach_uses_shared_headroom_formula():
    pol = _policy()
    assert pol.classify([_slo("h/0", qd=9, p99=10.0)]) == "breach"
    assert pol.classify([_slo("h/0", qd=1, p99=900.0)]) == "breach"
    assert pol.classify([_slo("h/0", qd=1, p99=10.0, inflight=2)]) == "ok"
    assert slo_headroom(8, 0.0, 8, 500.0) == 0.0
    assert slo_headroom(0, 0.0, 8, 500.0) == 1.0
    assert slo_headroom(16, 0.0, 8, 500.0) == -1.0


def test_worker_slo_from_snapshot_requires_serving_metrics():
    reg = MetricsRegistry()
    reg.gauge("hvd_engine_queue_depth").set(3)  # training-only rank
    assert worker_slo_from_snapshot("h/0", reg.snapshot()) is None
    reg.gauge("hvd_serve_queue_depth").set(5)
    reg.gauge("hvd_serve_inflight").set(2)
    slo = worker_slo_from_snapshot("h/0", reg.snapshot())
    assert slo.queue_depth == 5 and slo.inflight == 2


# ---------------------------------------------------------------------------
# the KV decision machine: decide -> drain -> resize -> ack, epoch claims,
# recovery resume


def _scaler(kv=None, epoch=5, **pol_kw):
    return Autoscaler(FakeOps(), kv=kv, epoch=epoch, policy=_policy(
        **pol_kw), registry=MetricsRegistry())


def test_up_decision_record_walks_decide_resize_ack():
    kv = DictKV()
    a = _scaler(kv)
    a.tick(HOT), a.tick(HOT)
    rec = kv.get_json(kv_keys.autoscale_decision())
    assert rec["action"] == UP and rec["state"] == RESIZE
    assert rec["epoch"] == 5 and kv.epochs[kv_keys.autoscale_decision()] == 5
    assert a.fleet_ops.ups == 1
    # a new worker joins -> ack + audit record
    a.tick(HOT + [_slo("h/new")])
    rec = kv.get_json(kv_keys.autoscale_decision())
    assert rec["state"] == ACK
    assert kv.get_json(kv_keys.autoscale_event(1))["action"] == UP
    assert a.pending is None


def test_up_ack_tolerates_concurrent_kill():
    """Completion is 'a NEW worker joined', not an absolute size — a kill
    during the resize must not wedge the decision open forever."""
    a = _scaler(DictKV())
    a.tick(HOT), a.tick(HOT)
    assert a.pending["state"] == RESIZE
    # the original worker dies; only the joiner remains (size unchanged)
    a.tick([_slo("h/new", qd=20, p99=900.0)])
    assert a.pending is None and a.decisions[-1]["action"] == UP


def test_down_decision_walks_decide_drain_resize_ack():
    kv = DictKV()
    a = _scaler(kv)
    a.tick(IDLE2), a.tick(IDLE2)
    rec = kv.get_json(kv_keys.autoscale_decision())
    assert rec["action"] == DOWN and rec["state"] == DRAIN
    assert a.fleet_ops.drains == ["a/0"]
    # victim leaves the accepting fleet but is still draining -> resize
    a.tick([_slo("b/0")], draining=["a/0"])
    assert kv.get_json(kv_keys.autoscale_decision())["state"] == RESIZE
    # drain fully clears -> ack
    a.tick([_slo("b/0")], draining=[])
    assert kv.get_json(kv_keys.autoscale_decision())["state"] == ACK
    assert [d["action"] for d in a.decisions] == [DOWN]


def test_no_new_decision_while_one_is_in_flight():
    a = _scaler(DictKV())
    a.tick(IDLE2), a.tick(IDLE2)
    assert a.pending["action"] == DOWN
    # keep observing idle far past the hysteresis bar: still one drain
    for _ in range(6):
        a.tick(IDLE2, draining=["a/0"])
    assert a.fleet_ops.drains == ["a/0"]
    assert len([d for d in a.decisions]) == 0  # still un-acked


def test_recovery_resumes_instead_of_redeciding():
    kv = DictKV()
    a = _scaler(kv, epoch=5)
    a.tick(IDLE2), a.tick(IDLE2)
    assert kv.get_json(kv_keys.autoscale_decision())["state"] == DRAIN
    # driver crash; a recovered driver (epoch 6) adopts the record
    b = _scaler(kv, epoch=6)
    rec = b.recover()
    assert rec["resumed"] and rec["state"] == DRAIN and rec["epoch"] == 6
    assert kv.epochs[kv_keys.autoscale_decision()] == 6  # re-claimed
    # it does NOT re-decide (no second drain), it finishes the first
    b.tick(IDLE2, draining=["a/0"])   # hysteresis would justify another
    assert b.fleet_ops.drains == []   # resumed, not re-issued
    b.tick([_slo("b/0")], draining=["a/0"])
    b.tick([_slo("b/0")], draining=[])
    assert b.decisions[-1]["state"] == ACK


def test_recovery_of_acked_record_is_a_noop():
    kv = DictKV()
    kv.put_json(kv_keys.autoscale_decision(),
                {"seq": 3, "action": UP, "state": ACK, "epoch": 2},
                epoch=2)
    b = _scaler(kv, epoch=4)
    assert b.recover() is None and b.pending is None
    assert b._seq == 3  # seq continues, never reuses an audit slot


def test_recovery_resumes_from_decide_state():
    """Crash between the decide write and the first act: the recovered
    driver re-issues the action idempotently."""
    kv = DictKV()
    kv.put_json(kv_keys.autoscale_decision(),
                {"seq": 1, "action": DOWN, "victim": "a/0",
                 "state": DECIDE, "epoch": 1}, epoch=1)
    b = _scaler(kv, epoch=2)
    assert b.recover()["state"] == DECIDE
    b.tick(IDLE2, draining=[])
    assert b.fleet_ops.drains == ["a/0"]
    assert kv.get_json(kv_keys.autoscale_decision())["state"] == DRAIN


def test_stuck_decision_times_out_loudly():
    a = Autoscaler(FakeOps(), kv=DictKV(), epoch=1, policy=_policy(),
                   registry=MetricsRegistry(), pending_timeout=0.0)
    a.tick(HOT), a.tick(HOT)
    a.tick(HOT)  # target never joins; the timeout abandons the record
    assert a.pending is None
    assert a.decisions[-1]["outcome"] == "timeout"


def test_autoscale_status_reports_age():
    kv = DictKV()
    kv.put_json(kv_keys.autoscale_decision(),
                {"seq": 2, "action": UP, "state": ACK,
                 "ts": time.time() - 10}, epoch=1)
    st = autoscale_status(kv.get_json)
    assert st["action"] == UP and 9 <= st["age_seconds"] <= 60
    assert autoscale_status(lambda k: None) is None


# ---------------------------------------------------------------------------
# admission: priority classes + tenant token buckets


def test_priority_class_parsing():
    assert parse_priority_classes("batch,standard,premium") == {
        "batch": 0, "standard": 1, "premium": 2}
    assert parse_priority_classes(" a , ,b,a ") == {"a": 0, "b": 1}
    assert parse_priority_classes("") == {"standard": 0}


def test_lowest_class_shed_first_under_pressure():
    ac = AdmissionController(registry=MetricsRegistry())
    # thresholds: batch 1/3, standard 2/3, premium 1.0
    assert ac.admit({"priority": "batch"}, 0.2).ok
    assert not ac.admit({"priority": "batch"}, 0.4).ok
    assert ac.admit({"priority": "standard"}, 0.4).ok
    assert not ac.admit({"priority": "standard"}, 0.7).ok
    assert ac.admit({"priority": "premium"}, 0.99).ok
    counters = ac.counters()
    assert counters["shed"]["batch"] == 1
    assert counters["admitted"]["premium"] == 1


def test_unknown_class_is_lowest_missing_is_highest():
    ac = AdmissionController(registry=MetricsRegistry())
    assert ac.resolve_class({"priority": "typo'd"}) == "batch"
    assert ac.resolve_class({}) == "premium"  # back-compat: only the
    # bounded queue itself sheds unclassified traffic
    assert ac.admit({}, 0.99).ok


def test_tenant_token_bucket_429_with_retry_after():
    ac = AdmissionController(tenant_qps=2.0, tenant_burst=1.0,
                             registry=MetricsRegistry())
    assert ac.admit({"tenant": "t1"}, 0.0).ok
    verdict = ac.admit({"tenant": "t1"}, 0.0)
    assert not verdict.ok and "quota" in verdict.reason
    assert 0 < verdict.retry_after_seconds <= 0.5  # 1/rate
    # tenants are isolated; tenant-less requests share no bucket
    assert ac.admit({"tenant": "t2"}, 0.0).ok
    assert ac.admit({}, 0.0).ok
    assert ac.counters()["quota_shed"] == 1


def test_tenant_bucket_map_is_bounded():
    """A client rotating tenant ids cannot grow the ingress hot path
    without bound: idle (burst-full) buckets are evicted at the cap; a
    recently-active tenant (tokens still spent) survives the pass."""
    ac = AdmissionController(tenant_qps=1e6, tenant_burst=2.0,
                             registry=MetricsRegistry())
    ac.MAX_TRACKED_TENANTS = 8
    # pin one ACTIVE tenant: zero refill rate, tokens below burst
    busy = ac._buckets["busy"] = TokenBucket(rate=0.0, burst=5.0)
    busy.tokens = 1.0
    for i in range(50):
        assert ac.admit({"tenant": f"rotating-{i}"}, 0.0).ok
    assert len(ac._buckets) <= 8
    assert "busy" in ac._buckets
    # slow-refill regime (nothing ever full): the oldest-insertion
    # backstop still bounds the map
    ac2 = AdmissionController(tenant_qps=0.001, tenant_burst=5.0,
                              registry=MetricsRegistry())
    ac2.MAX_TRACKED_TENANTS = 4
    for i in range(20):
        ac2.admit({"tenant": f"r{i}"}, 0.0)
    assert len(ac2._buckets) <= 4


def test_token_bucket_refills():
    b = TokenBucket(rate=10.0, burst=1.0)
    t0 = b._last
    assert b.take(now=t0) == 0.0
    assert b.take(now=t0) > 0
    assert b.take(now=t0 + 0.2) == 0.0  # refilled, capped at burst


def test_frontend_shed_returns_429_with_retry_hint():
    from horovod_tpu.serve.batcher import ContinuousBatcher
    from horovod_tpu.serve.frontend import ServeFrontend
    reg = MetricsRegistry()
    batcher = ContinuousBatcher(queue_depth=4, registry=reg)
    frontend = ServeFrontend(
        batcher=batcher, registry=reg,
        admission=AdmissionController(registry=reg)).start()
    # no serving loop: fill the queue by hand to 50%
    batcher.submit([1, 2]), batcher.submit([3, 4])
    code, payload = frontend.handle_generate(
        {"tokens": [1], "priority": "batch"})
    assert code == 429 and payload["status"] == "rejected"
    assert payload["retry_after_seconds"] > 0
    assert payload["priority_class"] == "batch"
    frontend.stop()


def test_frontend_quota_applies_in_routed_mode():
    from horovod_tpu.serve.frontend import ServeFrontend
    from horovod_tpu.serve.router import RequestRouter
    reg = MetricsRegistry()
    frontend = ServeFrontend(
        router=RequestRouter(retry_limit=0, registry=reg), registry=reg,
        admission=AdmissionController(tenant_qps=1.0, tenant_burst=1.0,
                                      registry=reg)).start()
    code, _ = frontend.handle_generate({"tokens": [1], "tenant": "t"})
    assert code != 429  # admitted (then 503: no workers registered)
    code, payload = frontend.handle_generate({"tokens": [1],
                                              "tenant": "t"})
    assert code == 429 and "quota" in payload["error"]
    frontend.stop()


# ---------------------------------------------------------------------------
# router satellite: drain announce stops NEW placements immediately


def test_router_drain_announce_blocks_new_placements():
    """Regression pin: zero requests routed to a worker after its
    draining announce, even though it is still in the table."""
    from horovod_tpu.serve.router import RequestRouter
    router = RequestRouter(retry_limit=0, registry=MetricsRegistry())
    router.update_workers(
        [{"id": "a", "addr": "x", "port": 1},
         {"id": "b", "addr": "x", "port": 2}], generation=1)
    # the scale-down announce: same table, entry flagged draining
    router.update_workers(
        [{"id": "a", "addr": "x", "port": 1, "draining": True},
         {"id": "b", "addr": "x", "port": 2}], generation=2)
    placed = []

    def send(worker, payload):
        placed.append(worker.id)
        return {"status": "ok"}

    for i in range(8):
        router.submit(f"r{i}", {}, send)
    assert placed == ["b"] * 8
    ws = {w["id"]: w for w in router.workers()}
    assert ws["a"]["state"] == "draining"
    # re-registration without the flag (scale-up reusing the slot)
    # restores placements
    router.update_workers(
        [{"id": "a", "addr": "x", "port": 1},
         {"id": "b", "addr": "x", "port": 2}], generation=3)
    router.submit("r9", {}, send)
    assert "a" in placed or placed[-1] == "b"  # a accepting again
    assert {w["id"]: w["state"] for w in router.workers()}["a"] == "up"


# ---------------------------------------------------------------------------
# driver actuation: FakeWorker leg (scale-up, admin drain, chaos compose)


class FakeWorker:
    spawned = []

    def __init__(self, hostname, rank, command, env):
        self.hostname = hostname
        self.rank = rank
        self.env = env
        self.exit_code = None
        self.terminated = False
        FakeWorker.spawned.append(self)

    def poll(self):
        return self.exit_code

    def terminate(self):
        self.terminated = True
        self.exit_code = 0 if self.exit_code is None else self.exit_code

    def kill(self):
        self.terminate()

    def wait(self, timeout=None):
        return self.exit_code


def _driver(monkeypatch, hosts, min_np=1, max_np=4):
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    monkeypatch.setenv("HOROVOD_AUTOSCALE", "1")
    FakeWorker.spawned = []
    driver = ElasticDriver(FixedHostDiscovery(hosts), min_np=min_np,
                           max_np=max_np, command=["true"],
                           spawn_worker=FakeWorker)
    driver._hosts.refresh()
    return driver


def test_driver_autoscaled_job_starts_at_the_floor(monkeypatch):
    driver = _driver(monkeypatch, {"hostA": 2, "hostB": 2}, min_np=1,
                     max_np=4)
    try:
        driver._rebalance(first=True)
        assert len(driver._expected_slots) == 1
        assert driver.target_np == 1
        driver.request_scale_up()
        assert driver.target_np == 2
        driver._rebalance()
        assert len(driver._expected_slots) == 2
        assert len([w for w in FakeWorker.spawned
                    if w.poll() is None]) == 2
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_administrative_drain_is_clean_and_host_stays(monkeypatch):
    """Scale-down drains via SIGTERM (never a kill), the exit is clean
    (no failure strike, no blacklist), and the HOST stays eligible — a
    later scale-up respawns the slot."""
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1}, min_np=1,
                     max_np=2)
    try:
        driver.request_scale_up()
        driver._rebalance(first=True)
        assert len(driver._expected_slots) == 2
        victim = driver._expected_slots[-1]
        assert driver.administrative_drain(victim)
        w = next(w for w in FakeWorker.spawned
                 if w.hostname == victim[0])
        assert w.terminated and w.exit_code == 0
        assert driver.target_np == 1
        driver._reap_workers()
        # clean departure: no failure strike, nothing blacklisted, and
        # the admin-drain records are cleared
        assert driver._host_failures == {}
        assert not driver._hosts.is_blacklisted(victim[0])
        assert victim not in driver._draining
        assert victim not in driver._admin_drains
        driver._rebalance()
        assert len(driver._expected_slots) == 1
        # the host was only slot-shed, not held out: scale-up re-admits
        driver.request_scale_up()
        driver._rebalance()
        assert {h for h, _ in driver._expected_slots} == \
            {"hostA", "hostB"}
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_rebalance_drops_the_draining_slot_not_another(monkeypatch):
    """While the drain is still in flight, the next topology excludes
    exactly the victim's slot."""
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1}, min_np=1,
                     max_np=2)
    try:
        driver.request_scale_up()
        driver._rebalance(first=True)
        victim = ("hostB", 0) if ("hostB", 0) in driver._expected_slots \
            else ("hostA", 0)
        driver.administrative_drain(victim)
        driver._rebalance()  # drain NOT yet reaped
        assert victim not in driver._expected_slots
        assert len(driver._expected_slots) == 1
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_chaos_kill_during_autoscale_drain_composes(monkeypatch):
    """The ISSUE 15 chaos satellite, FakeWorker leg: SIGKILL worker B
    while the autoscaler is already draining worker A for scale-down.
    The drain stays clean (no strike for A), the kill is charged to B's
    host only, and the single following rebalance both removes A's slot
    and respawns B — no double-resize, no lost drain."""
    monkeypatch.setenv("HOROVOD_FAILURES_TO_BLACKLIST", "3")
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1, "hostC": 1},
                     min_np=1, max_np=3)
    try:
        driver.request_scale_up()
        driver.request_scale_up()
        driver._rebalance(first=True)
        assert len(driver._expected_slots) == 3
        slots = dict.fromkeys(h for h, _ in driver._expected_slots)
        assert set(slots) == {"hostA", "hostB", "hostC"}
        gen_before = driver.generation
        # the autoscaler drains A...
        assert driver.administrative_drain(("hostA", 0))
        # ...and B is SIGKILLed before the drain is even reaped
        killer_victim = next(w for w in FakeWorker.spawned
                             if w.hostname == "hostB")
        killer_victim.exit_code = 137
        driver._reap_workers()
        # drain clean, kill charged — and only the kill
        assert driver._host_failures == {"hostB": 1}
        assert not driver._hosts.is_blacklisted("hostB")
        assert ("hostA", 0) not in driver._draining  # reaped + cleared
        assert driver._rebalance_needed.is_set()
        spawned_before = len(FakeWorker.spawned)
        driver._hosts.refresh()
        driver._rebalance()  # ONE rebalance composes both events
        assert driver.generation == gen_before + 1
        # A's slot is gone (target dropped to 2), B's slot respawned
        hosts_now = {h for h, _ in driver._expected_slots}
        assert hosts_now == {"hostB", "hostC"}
        respawned = [w.hostname
                     for w in FakeWorker.spawned[spawned_before:]]
        assert respawned == ["hostB"]
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_never_delivers_a_second_notice(monkeypatch):
    """A victim that already announced its own (spot) drain must not get
    the scale-down SIGTERM — a repeated preemption notice force-exits
    immediately (preempt.py), dropping acked requests. Covers both the
    scanned case (key in _draining) and the race where the announce
    landed after this heartbeat's drain scan (KV last-chance check)."""
    from horovod_tpu.runner.elastic.preempt import drain_key
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1, "hostC": 1},
                     min_np=1, max_np=3)
    try:
        driver.request_scale_up()
        driver.request_scale_up()
        driver._rebalance(first=True)
        target_before = driver.target_np
        # case 1: the drain scan already registered the spot drain
        v1 = driver._expected_slots[0]
        driver._draining.add(v1)
        w1 = next(w for w in FakeWorker.spawned if w.hostname == v1[0])
        assert not driver.administrative_drain(v1)
        assert not w1.terminated
        # case 2: the announce landed in the KV after the scan
        v2 = driver._expected_slots[1]
        driver._kv.put_json(drain_key(*v2), {"ts": time.time()})
        w2 = next(w for w in FakeWorker.spawned if w.hostname == v2[0])
        assert not driver.administrative_drain(v2)
        assert not w2.terminated
        assert driver.target_np == target_before  # nothing accounted
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_resume_admin_drain_accounting(monkeypatch):
    """A recovered driver resuming a DOWN decision re-applies the
    scale-down's driver-side accounting exactly once — the resumed
    record's re-issued administrative_drain must not double-decrement."""
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1}, min_np=1,
                     max_np=2)
    try:
        driver.request_scale_up()
        driver._rebalance(first=True)
        victim = driver._expected_slots[-1]
        driver._resume_admin_drain(f"{victim[0]}/{victim[1]}")
        assert driver.target_np == 1
        assert victim in driver._admin_drains
        # the resumed DECIDE record re-issues the drain: idempotent
        assert driver.administrative_drain(victim)
        assert driver.target_np == 1
        # a victim outside the recovered topology is a no-op (the
        # pre-crash rebalance already removed the slot)
        driver._resume_admin_drain("hostX/0")
        assert driver.target_np == 1
        assert ("hostX", 0) not in driver._admin_drains
    finally:
        driver._shutdown.set()
        driver._kv.stop()


def test_driver_serve_targets_carries_draining_flag(monkeypatch):
    """The announce path end to end: an admin drain flips the victim's
    serve_targets entry to draining on the very next scrape, so routers
    stop placing before the worker leaves the table."""
    driver = _driver(monkeypatch, {"hostA": 1, "hostB": 1}, min_np=1,
                     max_np=2)
    try:
        driver.request_scale_up()
        driver._rebalance(first=True)
        for host, lr in driver._expected_slots:
            driver._kv.put_json(kv_keys.serve_addr(host, lr),
                                {"id": f"{host}/{lr}", "addr": "127.0.0.1",
                                 "port": 1234, "rank": 0})
        driver._scrape_worker_metrics()
        table = driver._kv.get_json(kv_keys.serve_targets())
        assert not any(e.get("draining") for e in table["workers"])
        victim = driver._expected_slots[-1]
        driver.administrative_drain(victim)
        driver._scrape_worker_metrics()
        table = driver._kv.get_json(kv_keys.serve_targets())
        flagged = {e["id"]: bool(e.get("draining"))
                   for e in table["workers"]}
        assert flagged[f"{victim[0]}/{victim[1]}"] is True
        assert sum(flagged.values()) == 1
    finally:
        driver._shutdown.set()
        driver._kv.stop()


# ---------------------------------------------------------------------------
# the closed loop (slow: ~30s of real load + drains)


@pytest.mark.slow
@pytest.mark.parametrize("trace,chaos_kill", [("flash", True),
                                              ("diurnal", False)])
def test_autoscale_smoke_flash_crowd_with_chaos_kill(trace, chaos_kill):
    """The Makefile autoscale-smoke acceptance as a pytest leg: flash
    crowd -> scale-up (chaos kill mid-resize, re-routed, zero loss) ->
    recede -> drain-based scale-down, no flapping, p99 within bound; and
    the diurnal staircase, which no chaos disturbs, held to the same."""
    from horovod_tpu.serve.autoscale_smoke import run_smoke
    r = run_smoke(trace=trace, chaos_kill=chaos_kill, seconds_scale=2.0)
    assert r["accepted_loss"] == 0
    assert r["scale_up_seen"] and r["scale_down_seen"]
    assert r["no_flap"]
    assert r["p99_within_bound"], r["max_p99_ms"]
    assert r["fleet_max"] >= 2
    if chaos_kill:
        assert r["chaos"]["killed"] is not None
        assert r["rerouted"] >= 0


def test_autoscale_smoke_module_is_wired():
    """Fast-tier pin: the smoke's fleet plumbing works without load —
    spawn, drain announce (router stops placing), removal."""
    from horovod_tpu.serve.autoscale_smoke import SimFleet
    fleet = SimFleet(service_ms=1.0, spawn_delay=0.0)
    try:
        fleet._add_worker()
        fleet._add_worker()
        assert sorted(fleet.accepting_ids()) == ["w0", "w1"]
        r = fleet.submit({"tokens": [1, 2, 3], "max_new_tokens": 2})
        assert r["status"] == "ok"
        fleet.start_drain("w0")
        deadline = time.monotonic() + 10
        while fleet.draining_keys() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fleet.accepting_ids() == ["w1"]
        assert fleet.submit({"tokens": [1], "max_new_tokens": 2})[
            "status"] == "ok"
        assert fleet.lost_requests() == 0
    finally:
        fleet.close()
