"""Serving-plane tests: continuous batching, routing, drain-on-death, and
the engine's low-latency (serving-mode) collective path.

Tier-1 discipline: every HTTP server binds port 0, subprocess tests are
deadline-bounded, and sustained-load soaks are ``slow``-marked. Each test
that counts metrics uses its own MetricsRegistry so parallel test history
can't leak across assertions.
"""

import json
import os
import sys
import threading
import time
import urllib.request
import uuid
from urllib import error as urlerror

import numpy as np
import pytest

from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.serve.batcher import (AdmissionRejected, ContinuousBatcher,
                                       bucket_for, bucket_plan,
                                       default_buckets)
from horovod_tpu.serve.executor import ServingLoop, make_toy_step
from horovod_tpu.serve.frontend import ServeFrontend, serving_stats
from horovod_tpu.serve.router import (NoWorkersError, RequestRouter,
                                      post_json)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stack(step_fn=None, **kw):
    """Fresh batcher + serving loop on an isolated registry."""
    reg = MetricsRegistry()
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("default_deadline_ms", 2000.0)
    kw.setdefault("max_len", 128)
    batcher = ContinuousBatcher(registry=reg, **kw)
    loop = ServingLoop(step_fn or make_toy_step(), batcher, registry=reg)
    return reg, batcher, loop


def _toy_reference(tokens, n_new, vocab=256):
    """The toy model's expected greedy continuation."""
    seq = list(tokens)
    out = []
    for _ in range(n_new):
        nxt = (sum(seq) + len(seq)) % vocab
        out.append(nxt)
        seq.append(nxt)
    return out


# ---------------------------------------------------------------------------
# bucketing


def test_default_buckets_and_bucket_for():
    buckets = default_buckets(max_len=256, min_bucket=32)
    assert buckets == (32, 64, 128, 256)
    assert bucket_for(1, buckets) == 32
    assert bucket_for(32, buckets) == 32
    assert bucket_for(33, buckets) == 64
    assert bucket_for(256, buckets) == 256
    with pytest.raises(AdmissionRejected):
        bucket_for(257, buckets)


def test_bucket_plan_reuses_flash_length_router(monkeypatch):
    """The per-bucket attention route is the PR-2 length router's
    crossover: buckets below HOROVOD_FLASH_MIN_SEQ plan the XLA kernel,
    the rest flash — and moving the env knob moves the plan."""
    monkeypatch.setenv("HOROVOD_FLASH_MIN_SEQ", "128")
    plan = {p["bucket"]: p["attention_kernel"]
            for p in bucket_plan(default_buckets(256, 32))}
    assert plan == {32: "xla", 64: "xla", 128: "flash", 256: "flash"}
    monkeypatch.setenv("HOROVOD_FLASH_MIN_SEQ", "1024")
    plan = {p["bucket"]: p["attention_kernel"]
            for p in bucket_plan(default_buckets(256, 32))}
    assert set(plan.values()) == {"xla"}


# ---------------------------------------------------------------------------
# batcher: deadlines, backpressure, scheduling


def test_queued_deadline_expires_without_execution():
    reg, batcher, _ = _stack()  # no loop running: requests sit queued
    req = batcher.submit([1, 2, 3], max_new_tokens=4, deadline_ms=10.0)
    time.sleep(0.05)
    assert batcher.fill([]) == []  # expired at scheduling time, never admitted
    assert req.status == "expired"
    assert req.generated == []
    from horovod_tpu.metrics import snapshot_value
    assert snapshot_value(reg.snapshot(), "hvd_serve_requests_total",
                          status="expired") == 1


@pytest.mark.parametrize("loop_running", [False, True])
def test_backpressure_rejects_when_queue_full(loop_running):
    """A burst of 60 far outruns the server. With nobody draining the
    queue everything past its depth is refused; with the loop running the
    refusals are the whole of the damage: every admitted request still
    completes with the right tokens."""
    from horovod_tpu.metrics import snapshot_value
    toy = make_toy_step()

    def slow_step(tokens, lengths):
        time.sleep(0.005)  # the burst arrives inside one step
        return toy(tokens, lengths)

    reg, batcher, loop = _stack(slow_step, queue_depth=3,
                                default_deadline_ms=30000.0)
    if loop_running:
        loop.start()
    admitted, rejected = [], 0
    try:
        for i in range(60):
            try:
                admitted.append(batcher.submit([i, 1], max_new_tokens=2))
            except AdmissionRejected:
                rejected += 1
        if loop_running:
            for r in admitted:
                assert r.wait(30.0) and r.status == "ok", r.status
    finally:
        loop.stop()
    snap = reg.snapshot()
    assert rejected > 0 and admitted
    assert snapshot_value(snap, "hvd_serve_requests_total",
                          status="rejected") == rejected
    if loop_running:
        assert [r.generated for r in admitted] == \
            [_toy_reference(r.tokens[:2], 2) for r in admitted]
    else:
        assert len(admitted) == 3
        assert snapshot_value(snap, "hvd_serve_queue_depth") == 3


def test_explicit_zero_budget_is_not_the_default_cap():
    """max_new_tokens=0 is a tiny request (floored to 1 token), NOT a
    fall-through to the 32-token default cap (falsy-zero regression)."""
    _, batcher, _ = _stack()
    req = batcher.submit([1, 2, 3], max_new_tokens=0)
    assert req.max_new_tokens == 1


def test_single_bucket_batches():
    """fill() never mixes buckets: a 32-bucket and a 128-bucket request
    are scheduled in separate batches, in arrival order per bucket."""
    _, batcher, _ = _stack(max_len=128)
    small = batcher.submit([1] * 4, max_new_tokens=4)        # bucket 32
    big = batcher.submit([1] * 100, max_new_tokens=4)        # bucket 128
    small2 = batcher.submit([2] * 5, max_new_tokens=4)       # bucket 32
    batch1 = batcher.fill([])
    assert {r.id for r in batch1} == {small.id, small2.id}
    for r in batch1:
        batcher.complete(r, "ok")
    batch2 = batcher.fill([])
    assert [r.id for r in batch2] == [big.id]


def test_decode_completes_and_matches_toy_reference():
    _, batcher, loop = _stack()
    loop.start()
    try:
        reqs = [batcher.submit([i, i + 1, i + 2], max_new_tokens=5)
                for i in range(3)]
        for r in reqs:
            assert r.wait(10.0), r.status
            assert r.status == "ok"
        for i, r in enumerate(reqs):
            assert r.generated == _toy_reference([i, i + 1, i + 2], 5)
    finally:
        loop.stop()


def test_continuous_batching_admits_into_inflight_batch():
    """A request submitted while a batch is mid-generation joins it at a
    step boundary (occupancy reaches 2) instead of waiting for a drain."""
    reg, batcher, _ = _stack()
    step_base = make_toy_step()

    def slow_step(tokens, lengths):
        time.sleep(0.02)
        return step_base(tokens, lengths)

    loop = ServingLoop(slow_step, batcher, registry=reg).start()
    try:
        first = batcher.submit([1, 2], max_new_tokens=30)
        time.sleep(0.06)  # a few steps in flight
        second = batcher.submit([3, 4], max_new_tokens=2)
        assert second.wait(10.0) and second.status == "ok"
        assert not first.done  # joined and finished while first still ran
        assert first.wait(10.0) and first.status == "ok"
        from horovod_tpu.metrics import snapshot_histogram
        occ = snapshot_histogram(reg.snapshot(), "hvd_serve_batch_occupancy")
        # some steps carried both requests (occupancy bucket > 1)
        assert sum(occ["counts"][1:]) > 0, occ
    finally:
        loop.stop()


def test_mid_generation_deadline_returns_partial():
    _, batcher, _ = _stack()
    step_base = make_toy_step()

    def slow_step(tokens, lengths):
        time.sleep(0.03)
        return step_base(tokens, lengths)

    reg2 = MetricsRegistry()
    loop = ServingLoop(slow_step, batcher, registry=reg2).start()
    try:
        req = batcher.submit([5, 6], max_new_tokens=64, deadline_ms=120.0)
        assert req.wait(10.0)
        assert req.status == "expired"
        assert 0 < len(req.generated) < 64  # partial output, not dropped
    finally:
        loop.stop()


# ---------------------------------------------------------------------------
# TP inference executor (8 virtual devices via conftest)


def test_tp_lm_int8_activations_match_fp32_argmax():
    from horovod_tpu.serve.executor import make_tp_lm_step
    step_f, info_f = make_tp_lm_step(compression=None, vocab=64, hidden=32,
                                     mlp_dim=64, layers=2)
    step_q, info_q = make_tp_lm_step(compression="int8", vocab=64,
                                     hidden=32, mlp_dim=64, layers=2)
    rng = np.random.RandomState(0)
    tokens = np.zeros((4, 16), np.int32)
    lengths = np.ones(4, np.int32)
    for i in range(4):
        n = rng.randint(1, 12)
        tokens[i, :n] = rng.randint(0, 64, n)
        lengths[i] = n
    a, b = step_f(tokens, lengths), step_q(tokens, lengths)
    # int8 activation quantization perturbs logits by ~max|block|/127 —
    # far below the argmax margins of this model
    assert np.array_equal(a, b), (a, b)
    assert info_q["compression"] == "int8"
    assert info_f["compression"] == "none"


def test_activation_wire_report_savings():
    from horovod_tpu.serve.executor import activation_wire_report
    rep = activation_wire_report(hidden=256, layers=4, world=8)
    # fp32: 2*(7/8)*4 B/elem; int8: 2*(7/8)*(1+4/256) B/elem -> ~3.94x
    assert rep["fp32_bytes_per_token"] == int(2 * 7 / 8 * 4 * 1024)
    assert 3.8 < rep["int8_savings_x"] < 4.0
    from horovod_tpu.parallel.tp import tp_activation_wire_bytes
    assert tp_activation_wire_bytes(100, 1, None) == 0  # single rank: free


def test_serving_loop_executor_failure_fails_requests_loudly():
    reg, batcher, _ = _stack()

    def broken_step(tokens, lengths):
        raise RuntimeError("kaboom")

    loop = ServingLoop(broken_step, batcher, registry=reg).start()
    try:
        req = batcher.submit([1], max_new_tokens=2)
        assert req.wait(10.0)
        assert req.status == "failed"
        assert "kaboom" in req.error
    finally:
        loop.stop()


# ---------------------------------------------------------------------------
# engine low-latency path (serving mode)


def _eager_group(n, serving_mode, monkeypatch):
    from horovod_tpu.engine.bindings import EngineSession
    from horovod_tpu.common.eager import EagerExecutor
    monkeypatch.setenv("HOROVOD_SERVING_MODE", "1" if serving_mode else "0")
    group = f"serve-{uuid.uuid4().hex[:8]}"
    sessions = [EngineSession(rank=r, size=n, transport="loopback",
                              group=group, cycle_time_ms=1.0,
                              stall_warning_sec=60.0)
                for r in range(n)]
    return sessions, [EagerExecutor(s) for s in sessions]


def _destroy(sessions):
    for s in sessions:
        s._lib.hvdtpu_shutdown(s._session)
    for s in sessions:
        s.destroy()


def _run_pairs(sessions, execs, iters, small_n=64, big_n=65536):
    """Each rank submits (small, big) fp32 allreduces per iteration;
    returns ({name: result}, [(small_done, big_done) times on rank 0])."""
    from horovod_tpu.engine.bindings import OP_ALLREDUCE
    from horovod_tpu.common.reduce_ops import Sum
    results = {}
    times = []
    lock = threading.Lock()
    barrier = threading.Barrier(len(sessions))

    def run(rank, s, ex):
        rng = np.random.RandomState(100 + rank)
        for i in range(iters):
            small = rng.randn(small_n).astype(np.float32)
            big = rng.randn(big_n).astype(np.float32)
            barrier.wait()
            hs = ex.submit(f"small.{i}", OP_ALLREDUCE, small, reduce_op=Sum)
            hb = ex.submit(f"big.{i}", OP_ALLREDUCE, big, reduce_op=Sum)
            s.wait(hs, timeout=30.0)
            t_small = time.perf_counter()
            rs = ex.take_result(f"small.{i}")
            s.wait(hb, timeout=30.0)
            t_big = time.perf_counter()
            rb = ex.take_result(f"big.{i}")
            if rank == 0:
                with lock:
                    results[f"small.{i}"] = rs
                    results[f"big.{i}"] = rb
                    times.append((t_small, t_big))

    threads = [threading.Thread(target=run, args=(r, s, e), daemon=True)
               for r, (s, e) in enumerate(zip(sessions, execs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return results, times


def test_low_latency_path_bit_exact_vs_fused(monkeypatch):
    """Acceptance: serving-mode (express lane) allreduce results are
    bit-exact against the fused path on identical inputs — the express
    lane reorders execution, it must not touch the math."""
    iters = 4
    sessions, execs = _eager_group(2, False, monkeypatch)
    try:
        fused, _ = _run_pairs(sessions, execs, iters)
    finally:
        _destroy(sessions)
    sessions, execs = _eager_group(2, True, monkeypatch)
    try:
        express, _ = _run_pairs(sessions, execs, iters)
        counters = sessions[0].metrics()["counters"]
        # every small tensor rode the express lane
        assert counters["low_latency_responses"] >= iters
        # flight-recorder coverage: inference-regime collectives are in
        # the black box like any training collective
        dump = sessions[0].flight_dump()
        names = {e.get("name") for e in dump["events"]}
        assert any(n and n.startswith("small.") for n in names)
    finally:
        _destroy(sessions)
    assert fused.keys() == express.keys()
    for name in fused:
        assert np.array_equal(fused[name], express[name]), name


def test_serving_mode_small_completes_ahead_of_bulk(monkeypatch):
    """The cost-cliff regression: with serving mode on, a sub-threshold
    allreduce submitted alongside a bulk one completes ahead of it (the
    express response executes first), so it no longer pays the fused
    batch's exec time."""
    iters = 6
    sessions, execs = _eager_group(2, True, monkeypatch)
    try:
        _, times = _run_pairs(sessions, execs, iters, big_n=1 << 21)
        counters = sessions[0].metrics()["counters"]
    finally:
        _destroy(sessions)
    assert counters["low_latency_responses"] >= iters
    assert counters["fused_responses"] == 0
    # small strictly precedes big on every iteration
    assert all(ts <= tb for ts, tb in times), times


@pytest.mark.parametrize("serving_mode,answered", [(True, 6), (False, 0)],
                         ids=["serving", "fused"])
def test_express_lane_answers_every_small_allreduce_in_serving_mode_only(
        serving_mode, answered):
    """The small-tensor microbench, a mode a run: the counters say the
    express lane answered all six small allreduces in serving mode and none
    in fused mode."""
    from horovod_tpu.serve.loadgen import small_allreduce_latency
    run = small_allreduce_latency(serving_mode, iters=6, big_elems=1 << 20)
    assert run["low_latency_responses"] == answered
    assert run["p50_ms"] is not None


# ---------------------------------------------------------------------------
# router


def _entries(*specs):
    return [{"id": i, "addr": "127.0.0.1", "port": p, "rank": r}
            for i, p, r in specs]


def test_router_least_loaded_and_reroute_on_death():
    reg = MetricsRegistry()
    router = RequestRouter(retry_limit=2, registry=reg)
    router.update_workers(_entries(("a", 1001, 0), ("b", 1002, 1)), 0)
    dead = {"a"}
    served = []

    def send(worker, payload):
        if worker.id in dead:
            raise ConnectionRefusedError("worker gone")
        served.append(worker.id)
        return {"status": "ok", "id": payload["id"]}

    out = router.submit("r1", {"id": "r1"}, send)
    assert out["status"] == "ok"
    assert served == ["b"]  # a died, b absorbed the re-route
    from horovod_tpu.metrics import snapshot_value
    snap = reg.snapshot()
    assert snapshot_value(snap, "hvd_serve_rerouted_total") == 1
    assert snapshot_value(snap, "hvd_serve_lost_total") == 0
    states = {w["id"]: w["state"] for w in router.workers()}
    assert states["a"] == "dead" and states["b"] == "up"


def test_router_exhausted_retries_is_loud_not_silent():
    reg = MetricsRegistry()
    router = RequestRouter(retry_limit=1, registry=reg)
    router.update_workers(_entries(("a", 1001, 0)), 0)

    def send(worker, payload):
        raise ConnectionResetError("down")

    with pytest.raises(NoWorkersError):
        router.submit("r1", {"id": "r1"}, send)
    from horovod_tpu.metrics import snapshot_value
    assert snapshot_value(reg.snapshot(), "hvd_serve_lost_total") == 1


def test_router_generation_change_drains_and_reroutes():
    router = RequestRouter(retry_limit=1, registry=MetricsRegistry())
    router.update_workers(_entries(("a", 1001, 0), ("b", 1002, 1)), 0)
    wa = router.pick()  # least-loaded, tie by id -> a
    assert wa.id == "a"
    router.assign(wa, "req-a")
    # generation change: a is gone from the topology, c joined
    router.update_workers(_entries(("b", 1002, 1), ("c", 1003, 2)), 1)
    states = {w["id"]: w["state"] for w in router.workers()}
    assert states["a"] == "draining"
    # draining workers take no new traffic
    assert {router.pick().id for _ in range(4)} <= {"b", "c"}
    # its in-flight request finishes on the departing worker, then the
    # worker leaves the table entirely
    router.complete(wa, "req-a")
    assert "a" not in {w["id"] for w in router.workers()}
    assert router.generation == 1


def test_router_reregistered_worker_resumes():
    router = RequestRouter(registry=MetricsRegistry())
    router.update_workers(_entries(("a", 1001, 0), ("b", 1002, 1)), 0)
    router.update_workers(_entries(("b", 1002, 1)), 1)  # a drains
    router.update_workers(_entries(("a", 1001, 0), ("b", 1002, 1)), 2)
    states = {w["id"]: w["state"] for w in router.workers()}
    assert states["a"] == "up"  # rejoined the rotation


def test_router_stale_gen0_record_cannot_revive_corpse():
    """A dead worker's own stale KV record — explicit generation 0, the
    falsy one — must not resurrect it when the table moves to a later
    generation; only a strictly newer *registration* revives the id."""
    router = RequestRouter(registry=MetricsRegistry())
    e = dict(_entries(("a", 1001, 0))[0], generation=0)
    router.update_workers([e], 0)
    router.fail_worker("a")
    # the driver republishes the stale gen-0 record under table gen 1
    router.update_workers([e], 1)
    assert {w["id"]: w["state"] for w in router.workers()}["a"] == "dead"
    # the respawned slot re-registers under generation 1: revived
    router.update_workers([dict(e, generation=1)], 1)
    assert {w["id"]: w["state"] for w in router.workers()}["a"] == "up"


def test_router_refresh_from_kv():
    from horovod_tpu.runner.http_kv import KVServer
    kv = KVServer().start()
    try:
        router = RequestRouter(registry=MetricsRegistry())
        kv.put_json("serve_targets",
                    {"generation": 3,
                     "workers": _entries(("x", 1009, 0))})
        router.refresh_from_kv(kv.get_json)
        assert router.generation == 3
        assert [w["id"] for w in router.workers()] == ["x"]
    finally:
        kv.stop()


# ---------------------------------------------------------------------------
# frontend


def _http(url, payload=None, timeout=10.0):
    if payload is None:
        req = url
    else:
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(), method="POST",
            headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urlerror.HTTPError as e:
        return e.code, json.loads(e.read())


def test_frontend_local_roundtrip_reject_and_drain():
    reg, batcher, loop = _stack(queue_depth=2)
    loop.start()
    fe = ServeFrontend(batcher=batcher, port=0, addr="127.0.0.1",
                       registry=reg).start()
    base = f"http://127.0.0.1:{fe.port}"
    try:
        code, out = _http(base + "/v1/generate",
                          {"tokens": [1, 2, 3], "max_new_tokens": 3})
        assert code == 200 and out["status"] == "ok"
        assert out["tokens"] == _toy_reference([1, 2, 3], 3)
        code, health = _http(base + "/healthz")
        assert code == 200 and health["status"] == "ok"
        code, stats = _http(base + "/stats")
        assert code == 200 and stats["requests_ok"] == 1
        assert stats["latency_p50_ms"] is not None
        # drain flips health to 503 and rejects new work
        fe.set_draining(True)
        code, health = _http(base + "/healthz")
        assert code == 503 and health["status"] == "draining"
        code, out = _http(base + "/v1/generate", {"tokens": [1]})
        assert code == 503
    finally:
        fe.stop()
        loop.stop()


def test_routed_frontend_end_to_end_with_drain_on_death():
    """Cluster shape in one process: two local worker stacks behind an
    ingress router frontend. Killing one worker's HTTP server mid-run
    re-routes to the survivor; nothing accepted is lost."""
    workers = []
    for _ in range(2):
        reg, batcher, loop = _stack()
        loop.start()
        fe = ServeFrontend(batcher=batcher, port=0, addr="127.0.0.1",
                           registry=reg).start()
        workers.append((batcher, loop, fe))
    reg_r = MetricsRegistry()
    router = RequestRouter(retry_limit=2, registry=reg_r)
    router.update_workers(
        [{"id": f"w{i}", "addr": "127.0.0.1", "port": w[2].port, "rank": i}
         for i, w in enumerate(workers)], 0)
    ingress = ServeFrontend(router=router, port=0, addr="127.0.0.1",
                            registry=reg_r).start()
    base = f"http://127.0.0.1:{ingress.port}"
    try:
        oks = 0
        for i in range(6):
            code, out = _http(base + "/v1/generate",
                              {"tokens": [i], "max_new_tokens": 2,
                               "id": f"req{i}"})
            assert code == 200 and out["status"] == "ok", out
            oks += 1
            if i == 2:  # kill worker 0's HTTP server mid-load
                workers[0][2].stop()
                workers[0][1].stop()
        assert oks == 6
        from horovod_tpu.metrics import snapshot_value
        assert snapshot_value(reg_r.snapshot(),
                              "hvd_serve_lost_total") in (None, 0.0)
        states = {w["id"]: w["state"] for w in router.workers()}
        assert states.get("w0", "dead") == "dead"
    finally:
        ingress.stop()
        for _, loop, fe in workers[1:]:
            fe.stop()
            loop.stop()


def test_serving_stats_summary():
    reg, batcher, loop = _stack()
    loop.start()
    try:
        for i in range(3):
            r = batcher.submit([i, i], max_new_tokens=2)
            assert r.wait(10.0)
        stats = serving_stats(reg.snapshot())
        assert stats["requests_ok"] == 3
        assert stats["tokens_out"] == 6
        assert stats["latency_p99_ms"] is not None
        assert stats["batch_occupancy_mean"] is not None
    finally:
        loop.stop()


# ---------------------------------------------------------------------------
# serve worker drain + driver serve_targets aggregation


def test_serve_worker_drains_instead_of_dropping():
    from horovod_tpu.serve.worker import ServeWorker
    step_base = make_toy_step()

    def slow_step(tokens, lengths):
        time.sleep(0.02)
        return step_base(tokens, lengths)

    w = ServeWorker(step_fn=slow_step)
    w.start()
    base = f"http://127.0.0.1:{w.frontend.port}"
    results = {}

    def client(i):
        results[i] = _http(base + "/v1/generate",
                           {"tokens": [i], "max_new_tokens": 8,
                            "deadline_ms": 5000})

    threads = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.08)  # requests admitted and mid-generation
    assert w.drain(timeout=15.0)
    for t in threads:
        t.join(timeout=15.0)
    try:
        # every accepted request completed despite the drain
        assert all(code == 200 and out["status"] == "ok"
                   for code, out in results.values()), results
        code, health = _http(base + "/healthz")
        assert code == 503
    finally:
        w.stop()


def test_driver_aggregates_serve_targets():
    """The driver's heartbeat publishes worker serve endpoints as one
    ``serve_targets`` key — the router's discovery input."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery

    class FakeWorker:
        def __init__(self, hostname, rank, command, env):
            pass

        def poll(self):
            return None

        def terminate(self):
            pass

    driver = ElasticDriver(FixedHostDiscovery({"hostA": 2}), min_np=1,
                           max_np=2, command=["true"],
                           spawn_worker=FakeWorker)
    try:
        driver._hosts.refresh()
        driver._rebalance(first=True)
        driver._kv.put_json("serve_addr/hostA/0",
                            {"id": "hostA/0", "addr": "hostA", "port": 7001,
                             "rank": 0, "generation": 0})
        driver._kv.put_json("serve_addr/hostA/1",
                            {"id": "hostA/1", "addr": "hostA", "port": 7002,
                             "rank": 1, "generation": 0})
        driver._scrape_worker_metrics()
        info = driver._kv.get_json("serve_targets")
        assert info["generation"] == 0
        assert {w["id"] for w in info["workers"]} == {"hostA/0", "hostA/1"}
        router = RequestRouter(registry=MetricsRegistry())
        router.refresh_from_kv(driver._kv.get_json)
        assert len(router.workers()) == 2
    finally:
        driver._shutdown.set()
        driver._kv.stop()


# ---------------------------------------------------------------------------
# fault injection: kill a rank mid-load (die action + elastic driver)


def _wait_for_new_generation(driver, router, timeout=60.0):
    """Block until the driver has moved on from generation 0 and two
    workers have registered in the new generation. The router's table
    moves to the new generation before they have: until then it still
    lists the dead worker's old address as "up"."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if driver.generation >= 1:
            router.refresh_from_kv(driver._kv.get_json)
            up = [w for w in router.workers()
                  if w["state"] == "up" and w["generation"] >= 1]
            if len(up) >= 2 and router.generation >= 1:
                return
        time.sleep(0.25)
    pytest.fail(f"no recovery: generation={driver.generation}, "
                f"workers={router.workers()}")


def test_kill_rank_mid_load_drains_and_reroutes(tmp_path):
    """The serving-plane incident drill: two elastic serve workers under
    the real driver; rank 1's engine heartbeat dies mid-run via the
    HOROVOD_FAULT_SPEC ``die`` action (a real exit(137) at an exact frame
    boundary). The router must re-route around the death with zero lost
    accepted requests (bounded error budget below covers requests that
    race the brief pre-detection window), and the driver must respawn the
    slot into a new generation whose worker re-registers."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.exec_utils import WorkerProcess

    injected = {"done": False}

    def spawn(hostname, rank, command, env):
        env = dict(env)
        env["PYTHONPATH"] = REPO
        if rank == 1 and not injected["done"]:
            injected["done"] = True
            # die mid control-channel traffic (~4 s of 5 ms cycles in),
            # which lands squarely inside the load window below
            env["HOROVOD_FAULT_SPEC"] = "control.send:die@frame=800"
        return WorkerProcess(hostname, rank, command, env)

    driver = ElasticDriver(
        FixedHostDiscovery({"localhost": 2}), min_np=2, max_np=2,
        command=[sys.executable, "-m", "horovod_tpu.serve.worker"],
        extra_env={"HOROVOD_SERVE_PORT": "0", "HOROVOD_CYCLE_TIME": "5",
                   "JAX_PLATFORMS": "cpu"},
        spawn_worker=spawn)
    result = {}
    runner = threading.Thread(
        target=lambda: result.update(rc=driver.run(start_timeout=60)),
        daemon=True)
    runner.start()

    reg = MetricsRegistry()
    router = RequestRouter(retry_limit=3, registry=reg)
    outcomes = {"ok": 0, "other": 0}
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            router.refresh_from_kv(driver._kv.get_json)
            if len([w for w in router.workers()
                    if w["state"] == "up"]) >= 2:
                break
            time.sleep(0.25)
        else:
            pytest.fail("serve workers never registered")

        def send(worker, payload):
            return post_json(worker.addr, worker.port, "/v1/generate",
                             payload, timeout=15.0)

        i = 0
        while i < 60:
            i += 1
            router.refresh_from_kv(driver._kv.get_json)
            try:
                out = router.submit(
                    f"req{i}", {"tokens": [i % 7, 3], "max_new_tokens": 2,
                                "deadline_ms": 5000, "id": f"req{i}"},
                    send)
                outcomes["ok" if out.get("status") == "ok"
                         else "other"] += 1
            except NoWorkersError:
                outcomes["other"] += 1
            # pace the load so the death + recovery land mid-stream
            time.sleep(0.15)

        # the driver re-routed: a new generation exists and its workers
        # re-registered (respawned rank included)
        _wait_for_new_generation(driver, router)

        from horovod_tpu.metrics import snapshot_value
        snap = reg.snapshot()
        # the no-silent-loss contract: nothing exhausted its retries
        assert (snapshot_value(snap, "hvd_serve_lost_total") or 0) == 0
        # bounded error budget: the kill may eat the requests that raced
        # the detection window, nothing more
        assert outcomes["other"] <= 5, outcomes
        assert outcomes["ok"] >= 55, outcomes
    finally:
        driver._kv.put_json("serve_stop", {"ts": time.time()})
        runner.join(timeout=90)
        if runner.is_alive():
            driver._shutdown.set()
            runner.join(timeout=30)
    assert result.get("rc") == 0, result


# ---------------------------------------------------------------------------
# serving fast path: paged KV cache + prefix reuse + speculative decode


def _fast_stack(draft=None, spec_k=None, spec_sync=None, pool_blocks=64,
                block_tokens=8, **kw):
    """Fresh toy stack behind a block-paged cache, isolated registry."""
    from horovod_tpu.serve.executor import make_toy_cached_step
    from horovod_tpu.serve.kv_cache import PagedKVCache
    reg = MetricsRegistry()
    cache = PagedKVCache(block_tokens=block_tokens,
                         pool_blocks=pool_blocks, registry=reg)
    kw.setdefault("max_batch", 4)
    kw.setdefault("queue_depth", 8)
    kw.setdefault("default_deadline_ms", 2000.0)
    kw.setdefault("max_len", 128)
    batcher = ContinuousBatcher(registry=reg, cache=cache, **kw)
    loop = ServingLoop(make_toy_step(), batcher, registry=reg,
                       cached_step=make_toy_cached_step(),
                       draft_step=draft, spec_k=spec_k,
                       spec_sync=spec_sync)
    return reg, batcher, loop


def test_cached_decode_matches_toy_reference():
    """The fast path changes the cost model (O(1)/token vs O(L)), never
    the tokens."""
    _, batcher, loop = _fast_stack()
    loop.start()
    try:
        reqs = [batcher.submit([i, i + 1, i + 2], max_new_tokens=5)
                for i in range(3)]
        for i, r in enumerate(reqs):
            assert r.wait(10.0) and r.status == "ok"
            assert r.generated == _toy_reference([i, i + 1, i + 2], 5)
    finally:
        loop.stop()
    assert batcher.cache.balanced()


def test_queued_expired_never_allocates_cache_blocks():
    """Expiry-split regression, queued half: a request that dies in the
    queue charged capacity but provably never bound a physical block."""
    from horovod_tpu.metrics import snapshot_value
    reg, batcher, _ = _fast_stack()  # loop not started: stays queued
    req = batcher.submit([1, 2, 3], max_new_tokens=4, deadline_ms=10.0)
    assert req.lease.charged > 0 and req.lease.bound == 0
    time.sleep(0.05)
    assert batcher.fill([]) == []  # expired at scheduling time
    assert req.status == "expired" and req.generated == []
    assert req.lease.bound == 0  # the invariant release() enforces
    st = batcher.cache.stats()
    assert st["free"] == st["pool_blocks"] and batcher.cache.balanced()
    assert snapshot_value(reg.snapshot(),
                          "hvd_serve_cache_blocks_used") == 0


def test_running_expired_frees_exactly_the_charge():
    """Expiry-split regression, running half: a mid-generation expiry
    returns partial output AND its full block charge at that same step
    boundary."""
    from horovod_tpu.metrics import snapshot_value
    from horovod_tpu.serve.executor import CachedStep, make_toy_cached_step
    base = make_toy_cached_step()

    class Slow(CachedStep):
        state_dim = base.state_dim

        def advance(self, *a):
            time.sleep(0.03)
            return base.advance(*a)

    from horovod_tpu.serve.kv_cache import PagedKVCache
    reg = MetricsRegistry()
    cache = PagedKVCache(block_tokens=8, pool_blocks=64, registry=reg)
    batcher = ContinuousBatcher(max_batch=4, queue_depth=8, max_len=128,
                                default_deadline_ms=2000.0, registry=reg,
                                cache=cache)
    loop = ServingLoop(make_toy_step(), batcher, registry=reg,
                       cached_step=Slow()).start()
    try:
        req = batcher.submit([5, 6], max_new_tokens=32, deadline_ms=120.0)
        charged = req.lease.charged
        assert charged == 5  # ceil((2 + 32) / 8): the worst case upfront
        assert req.wait(10.0) and req.status == "expired"
        assert 0 < len(req.generated) < 32  # partial output returned
    finally:
        loop.stop()
    assert req.lease.closed and req.lease.charged == 0
    st = cache.stats()
    assert st["free"] == st["pool_blocks"], st  # the charge came back
    assert cache.balanced()
    assert snapshot_value(reg.snapshot(),
                          "hvd_serve_cache_blocks_used") == 0


def test_cache_churn_1k_requests_no_leak():
    """1k requests of mixed fate — ok, queued-expired, running-expired,
    rejected — leave the pool exactly conserved: every non-shared block
    back in the free list, used gauge == resident shared blocks."""
    from horovod_tpu.metrics import snapshot_value
    reg, batcher, loop = _fast_stack(queue_depth=64, pool_blocks=96,
                                     default_deadline_ms=500.0)
    loop.start()
    prefixes = [[t] * 24 for t in (3, 5, 7)]  # 3 shared tenant prompts
    outcomes = {"submitted": 0, "rejected": 0}
    reqs = []
    try:
        for i in range(1000):
            tokens = prefixes[i % 3] + [i % 251]
            ddl = 0.5 if i % 7 == 0 else 500.0  # ~14% expire somewhere
            try:
                reqs.append(batcher.submit(tokens, max_new_tokens=4,
                                           deadline_ms=ddl))
                outcomes["submitted"] += 1
            except AdmissionRejected:
                outcomes["rejected"] += 1
            if i % 50 == 49:  # let the loop breathe; keeps some bursts
                for r in reqs[-20:]:
                    r.wait(5.0)
        for r in reqs:
            assert r.wait(10.0), r.status
    finally:
        loop.drain(timeout=10.0)
        loop.stop()
    assert outcomes["submitted"] >= 900  # the churn actually churned
    assert all(r.status in ("ok", "expired") for r in reqs)
    assert any(r.status == "expired" for r in reqs)
    cache = batcher.cache
    assert cache.balanced(), cache.stats()
    st = cache.stats()
    # nothing private leaked: all non-resident-shared capacity is free
    assert st["charged"] == 0
    assert st["free"] + st["shared_resident"] == st["pool_blocks"]
    snap = reg.snapshot()
    assert snapshot_value(snap, "hvd_serve_cache_blocks_used") == \
        st["shared_resident"]
    # the shared tenant prompts actually got reused
    assert (snapshot_value(snap, "hvd_serve_cache_reuse_total") or 0) > 0


def test_cache_exhaustion_is_admission_backpressure():
    """A pool too small for the request is a 429 at submit, before the
    queue — never an OOM later."""
    from horovod_tpu.metrics import snapshot_value
    reg, batcher, _ = _fast_stack(pool_blocks=2, block_tokens=8)
    with pytest.raises(AdmissionRejected, match="exhausted"):
        batcher.submit(list(range(20)), max_new_tokens=20)  # needs 5
    snap = reg.snapshot()
    assert snapshot_value(snap, "hvd_serve_requests_total",
                          status="rejected") == 1
    assert snapshot_value(snap, "hvd_serve_cache_exhausted_total") == 1
    assert batcher.cache.balanced()


def test_prefix_reuse_skips_prefill_compute():
    """Second request with the same prompt resumes from the published
    checkpoint: hits > 0, prefill tokens saved, and the tokens still
    match the reference exactly."""
    from horovod_tpu.metrics import snapshot_value
    reg, batcher, loop = _fast_stack(block_tokens=8)
    prompt = [9] * 20  # 2 full blocks + partial
    loop.start()
    try:
        first = batcher.submit(prompt, max_new_tokens=4)
        assert first.wait(10.0) and first.status == "ok"
        second = batcher.submit(prompt, max_new_tokens=4)
        assert second.wait(10.0) and second.status == "ok"
    finally:
        loop.stop()
    assert first.generated == second.generated == \
        _toy_reference(prompt, 4)
    snap = reg.snapshot()
    assert (snapshot_value(snap, "hvd_serve_cache_hits_total") or 0) > 0
    assert (snapshot_value(
        snap, "hvd_serve_cache_prefill_tokens_saved_total") or 0) >= 16
    assert batcher.cache.balanced()


def test_spec_decode_token_identical_toy_with_rejects():
    """Speculative decoding with a deliberately-wrong draft: the reject
    path engages (accepted < proposed) and the output is still
    token-identical to the non-speculative greedy reference."""
    from horovod_tpu.metrics import snapshot_value
    from horovod_tpu.serve.executor import make_toy_draft_step
    reg, batcher, loop = _fast_stack(
        draft=make_toy_draft_step(wrong_every=3), spec_k=4)
    loop.start()
    try:
        reqs = [batcher.submit([i + 1, 2 * i], max_new_tokens=12)
                for i in range(4)]
        for i, r in enumerate(reqs):
            assert r.wait(10.0) and r.status == "ok"
            assert r.generated == _toy_reference([i + 1, 2 * i], 12)
    finally:
        loop.stop()
    snap = reg.snapshot()
    proposed = snapshot_value(snap, "hvd_serve_spec_proposed_total")
    accepted = snapshot_value(snap, "hvd_serve_spec_accepted_total")
    assert proposed and accepted  # speculation ran and accepted some
    assert accepted < proposed    # ... and the reject path was exercised
    assert batcher.cache.balanced()


def test_spec_decode_token_identical_rnn_vs_plain_step():
    """The acceptance pin on a real recurrent LM: cached + speculative
    greedy decode emits exactly the plain recompute StepFn's tokens."""
    from horovod_tpu.serve.executor import make_rnn_lm_step
    from horovod_tpu.serve.kv_cache import PagedKVCache
    step_fn, cached, draft, _ = make_rnn_lm_step(hidden=32, vocab=64,
                                                 seed=1)
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9]]

    def decode(fast):
        reg = MetricsRegistry()
        cache = PagedKVCache(block_tokens=8, pool_blocks=64,
                             registry=reg) if fast else None
        batcher = ContinuousBatcher(max_batch=4, queue_depth=8,
                                    max_len=64, registry=reg, cache=cache,
                                    default_deadline_ms=5000.0)
        loop = ServingLoop(step_fn, batcher, registry=reg,
                           cached_step=cached if fast else None,
                           draft_step=draft if fast else None,
                           spec_k=4).start()
        try:
            reqs = [batcher.submit(p, max_new_tokens=10) for p in prompts]
            for r in reqs:
                assert r.wait(20.0) and r.status == "ok"
            return [r.generated for r in reqs]
        finally:
            loop.stop()

    assert decode(True) == decode(False)


def test_spec_accept_sync_rides_express_lane(monkeypatch):
    """The accept/reject exchange is 4 bytes per slot — deep under the
    low-latency threshold — so with serving mode on it takes the express
    lane on a REAL engine session, never the fusion buffer."""
    from horovod_tpu.common.reduce_ops import Sum
    from horovod_tpu.engine.bindings import OP_ALLREDUCE
    from horovod_tpu.serve.executor import make_toy_draft_step
    sessions, execs = _eager_group(2, True, monkeypatch)
    seq = {"n": 0}

    def spec_sync(accepts):
        buf = np.asarray(accepts, np.float32)
        assert buf.nbytes <= 4096  # express-lane eligible by size
        name = f"spec.accept.{seq['n']}"
        seq["n"] += 1
        hs = [ex.submit(name, OP_ALLREDUCE, buf.copy(), reduce_op=Sum)
              for ex in execs]
        for s, h in zip(sessions, hs):
            s.wait(h, timeout=30.0)
        for ex in execs:
            ex.take_result(name)
        return accepts

    try:
        _, batcher, loop = _fast_stack(
            draft=make_toy_draft_step(wrong_every=3), spec_k=4,
            spec_sync=spec_sync)
        loop.start()
        try:
            reqs = [batcher.submit([i, i + 2], max_new_tokens=8)
                    for i in range(3)]
            for i, r in enumerate(reqs):
                assert r.wait(20.0) and r.status == "ok"
                assert r.generated == _toy_reference([i, i + 2], 8)
        finally:
            loop.stop()
        counters = sessions[0].metrics()["counters"]
    finally:
        _destroy(sessions)
    assert seq["n"] > 0  # syncs actually happened
    assert counters["low_latency_responses"] >= seq["n"]
    assert counters.get("fused_responses", 0) == 0


def test_kill_worker_mid_decode_with_shared_prefixes(tmp_path):
    """The fast-path incident drill (ISSUE 16 satellite): chaos-kill one
    of two serve workers mid-decode while shared-prefix requests are in
    flight. The router re-routes with zero accepted-request loss and the
    survivor's cache pool accounting still balances."""
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.exec_utils import WorkerProcess
    from horovod_tpu.serve.loadgen import shared_prefix_trace

    trace = shared_prefix_trace(seed=3, requests=48, tenants=2,
                                prefix_len=48, tail_len=8,
                                max_new_tokens=4, vocab=128)
    injected = {"done": False}

    def spawn(hostname, rank, command, env):
        env = dict(env)
        env["PYTHONPATH"] = REPO
        if rank == 1 and not injected["done"]:
            injected["done"] = True
            env["HOROVOD_FAULT_SPEC"] = "control.send:die@frame=800"
        return WorkerProcess(hostname, rank, command, env)

    driver = ElasticDriver(
        FixedHostDiscovery({"localhost": 2}), min_np=2, max_np=2,
        command=[sys.executable, "-m", "horovod_tpu.serve.worker"],
        extra_env={"HOROVOD_SERVE_PORT": "0", "HOROVOD_CYCLE_TIME": "5",
                   "JAX_PLATFORMS": "cpu"},
        spawn_worker=spawn)
    result = {}
    runner = threading.Thread(
        target=lambda: result.update(rc=driver.run(start_timeout=60)),
        daemon=True)
    runner.start()

    reg = MetricsRegistry()
    router = RequestRouter(retry_limit=3, registry=reg)
    outcomes = {"ok": 0, "other": 0}
    try:
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            router.refresh_from_kv(driver._kv.get_json)
            if len([w for w in router.workers()
                    if w["state"] == "up"]) >= 2:
                break
            time.sleep(0.25)
        else:
            pytest.fail("serve workers never registered")

        def send(worker, payload):
            return post_json(worker.addr, worker.port, "/v1/generate",
                             payload, timeout=15.0)

        for i, item in enumerate(trace):
            router.refresh_from_kv(driver._kv.get_json)
            payload = {"tokens": item["tokens"],
                       "max_new_tokens": item["max_new_tokens"],
                       "deadline_ms": 5000, "id": f"sp{i}"}
            try:
                out = router.submit(f"sp{i}", payload, send)
                outcomes["ok" if out.get("status") == "ok"
                         else "other"] += 1
            except NoWorkersError:
                outcomes["other"] += 1
            time.sleep(0.15)  # staggered: reuse hits after first publish

        _wait_for_new_generation(driver, router)

        from horovod_tpu.metrics import snapshot_value
        assert (snapshot_value(reg.snapshot(),
                               "hvd_serve_lost_total") or 0) == 0
        assert outcomes["other"] <= 5, outcomes
        assert outcomes["ok"] >= len(trace) - 5, outcomes

        # the survivors' cache accounting balances, and at least one of
        # them actually shared prefixes across the in-flight requests
        stats = []
        for w in (w for w in router.workers() if w["state"] == "up"):
            code, st = _http(f"http://{w['addr']}:{w['port']}/stats")
            assert code == 200
            stats.append(st["cache"])
        assert stats and all(s["pool_balanced"] for s in stats), stats
        assert any(s["reuse"] > 0 for s in stats), stats
    finally:
        driver._kv.put_json("serve_stop", {"ts": time.time()})
        runner.join(timeout=90)
        if runner.is_alive():
            driver._shutdown.set()
            runner.join(timeout=30)
    assert result.get("rc") == 0, result


# ---------------------------------------------------------------------------
# sustained-load soak (slow)


@pytest.mark.slow
def test_sustained_load_soak():
    """20 s of steady offered load on the local stack: no failures, no
    unbounded queue, p99 under the deadline."""
    from horovod_tpu.serve import loadgen
    reg, batcher, loop = _stack(max_batch=8, queue_depth=32,
                                default_deadline_ms=2000.0)
    loop.start()

    def submit(payload):
        try:
            req = batcher.submit(payload["tokens"],
                                 max_new_tokens=payload["max_new_tokens"])
        except AdmissionRejected:
            return {"status": "rejected"}
        req.wait(10.0)
        return req.result()

    try:
        window = loadgen.run_load(
            submit, offered_qps=50.0, duration_sec=20.0,
            make_payload=lambda i: {"tokens": [i % 17, 1, 2],
                                    "max_new_tokens": 4})
    finally:
        loop.drain(10.0)
        loop.stop()
    assert window["failed"] == 0
    assert window["completed_ok"] > 0
    assert window["p99_ms"] is not None and window["p99_ms"] < 2000.0
