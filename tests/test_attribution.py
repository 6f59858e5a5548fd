"""Step-time attribution engine (ISSUE 7).

Covers the decomposition model (compute / exposed-comm / stall / host sum
to the step exactly; overlapped-vs-exposed split against the enqueue
phase), cross-rank critical-path analysis over CYCLE-aligned clocks, the
live attributor (engine STEP marks, rolling anomaly detection, automatic
flight dumps), and the BENCH ``step_attribution`` block with its <1%
overhead budget.
"""

import json
import time
import uuid

import pytest

from horovod_tpu.engine import OP_ALLREDUCE, EngineSession
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.obs import attribution as attr_mod
from horovod_tpu.obs.attribution import (
    StepAttributor,
    attribute,
    decompose_rank,
    step_windows,
)

# ---------------------------------------------------------------------------
# synthetic dump builders


def _ev(i, phase, name="", ts=0.0, aux=0, cycle=-1):
    return {"i": i, "phase": phase, "name": name, "ts_us": float(ts),
            "aux": aux, "cycle": cycle}


def _dump(events, rank=0, origin_us=1_000_000):
    return {"rank": rank, "size": 1, "origin_unix_us": origin_us,
            "events": events}


def test_step_windows_pair_by_id_and_skip_unmatched():
    d = _dump([
        _ev(0, "STEP_BEGIN", aux=1, ts=100),
        _ev(1, "STEP_END", aux=1, ts=600),
        _ev(2, "STEP_END", aux=7, ts=700),    # BEGIN fell off the ring
        _ev(3, "STEP_BEGIN", aux=2, ts=800),  # still running at dump time
    ])
    ws = step_windows(d)
    assert [w["step"] for w in ws] == [1]
    assert ws[0]["begin_us"] == 100 and ws[0]["end_us"] == 600


def test_decomposition_buckets_sum_to_step_exactly():
    # enqueue at 100 and 300 (compute until 300); g1 execs 150..250
    # (fully overlapped), g2 execs 500..900 (fully exposed); negotiation
    # wait for g2 spans 300..500 (stall); tail remainder 900..1000 = host.
    d = _dump([
        _ev(0, "STEP_BEGIN", aux=5, ts=0),
        _ev(1, "ENQUEUE", "g1", ts=100),
        _ev(2, "EXEC", "g1", ts=150),
        _ev(3, "DONE", "g1", ts=250, aux=100),
        _ev(4, "ENQUEUE", "g2", ts=300),
        _ev(5, "EXEC", "g2", ts=500),
        _ev(6, "DONE", "g2", ts=900, aux=400),
        _ev(7, "STEP_END", aux=5, ts=1000),
    ])
    (dec,) = decompose_rank(d)
    assert dec["step"] == 5
    assert dec["step_s"] == pytest.approx(1000e-6)
    assert dec["compute_s"] == pytest.approx(300e-6)
    assert dec["exposed_comm_s"] == pytest.approx(400e-6)
    assert dec["overlapped_comm_s"] == pytest.approx(100e-6)
    assert dec["stall_s"] == pytest.approx(200e-6)
    assert dec["host_s"] == pytest.approx(100e-6)
    assert dec["compute_s"] + dec["exposed_comm_s"] + dec["stall_s"] + \
        dec["host_s"] == pytest.approx(dec["step_s"])
    assert dec["gating_tensor"] == "g2"
    assert dec["collectives"] == 2


def test_decomposition_exec_reconstructed_from_done_aux():
    # The EXEC event fell off the ring: DONE's aux (exec span, us) must
    # reconstruct the span so exposed comm is still priced.
    d = _dump([
        _ev(0, "STEP_BEGIN", aux=1, ts=0),
        _ev(1, "ENQUEUE", "g", ts=100),
        _ev(2, "DONE", "g", ts=700, aux=500),   # exec began at 200
        _ev(3, "STEP_END", aux=1, ts=800),
    ])
    (dec,) = decompose_rank(d)
    assert dec["exposed_comm_s"] == pytest.approx(500e-6)
    assert dec["compute_s"] == pytest.approx(100e-6)


def test_pure_compute_step_decomposes_as_compute():
    # No engine-visible collectives (XLA owns the overlap in-jit): the
    # whole window is honest compute, nothing invented.
    d = _dump([
        _ev(0, "STEP_BEGIN", aux=1, ts=0),
        _ev(1, "STEP_END", aux=1, ts=1000),
    ])
    (dec,) = decompose_rank(d)
    assert dec["compute_s"] == pytest.approx(1000e-6)
    assert dec["exposed_comm_s"] == 0.0 and dec["stall_s"] == 0.0
    assert dec["gating_tensor"] is None


def test_cross_rank_critical_path_uses_aligned_clocks():
    # Same wall-clock behavior on both ranks, but rank 1's steady clock
    # started 10_000us later (smaller local timestamps). Shared CYCLE
    # anchors realign; rank 1 actually finishes the step 300us after
    # rank 0, so it is the critical rank and its last DONE is gating.
    r0 = _dump([
        _ev(0, "CYCLE", ts=10_000, cycle=1),
        _ev(1, "STEP_BEGIN", aux=1, ts=10_100),
        _ev(2, "ENQUEUE", "grad", ts=10_200),
        _ev(3, "EXEC", "grad", ts=10_300),
        _ev(4, "DONE", "grad", ts=10_600, aux=300),
        _ev(5, "STEP_END", aux=1, ts=10_700),
    ], rank=0)
    r1 = _dump([
        _ev(0, "CYCLE", ts=0, cycle=1),
        _ev(1, "STEP_BEGIN", aux=1, ts=100),
        _ev(2, "ENQUEUE", "grad", ts=200),
        _ev(3, "EXEC", "grad", ts=300),
        _ev(4, "DONE", "grad", ts=900, aux=600),
        _ev(5, "STEP_END", aux=1, ts=1000),
    ], rank=1)
    rec = attribute({0: r0, 1: r1})
    assert rec["clock_offsets_us"][1] == pytest.approx(10_000, abs=1)
    (step,) = rec["steps"]
    assert step["critical_rank"] == 1
    assert step["gating_tensor"] == "grad"
    assert step["step_skew_us"] == pytest.approx(300, abs=1)
    s = rec["summary"]
    assert s["steps"] == 1
    assert s["critical_rank_counts"] == {1: 1}
    assert s["gating_tensor_counts"] == {"grad": 1}
    fracs = (s["compute_frac"] + s["exposed_comm_frac"] + s["stall_frac"]
             + s["host_frac"])
    assert fracs == pytest.approx(1.0, abs=1e-3)


def test_summary_empty_steps():
    s = attr_mod.summarize([])
    assert s["steps"] == 0 and s["compute_frac"] is None


# ---------------------------------------------------------------------------
# live engine integration (STEP marks through the real flight ring)


def _make_group(n):
    group = f"attr-{uuid.uuid4().hex[:8]}"
    return [EngineSession(rank=r, size=n, transport="loopback", group=group,
                          cycle_time_ms=1.0, stall_warning_sec=60.0)
            for r in range(n)]


def _destroy(sessions):
    for s in sessions:
        s._lib.hvdtpu_shutdown(s._session)
    for s in sessions:
        s.destroy()


def test_engine_step_marks_bracket_collectives():
    """step_begin/end land STEP events in the flight ring; the window
    around a real allreduce decomposes with >=1 collective and a DONE
    event carrying the exec span in aux."""
    ss = _make_group(2)
    try:
        def execute(resp):
            time.sleep(0.002)  # a visible exec span for the DONE aux
            return 0

        for s in ss:
            s.set_execute_callback(execute)
        for s in ss:
            s.step_begin(3)
        hs = [s.enqueue("t0", OP_ALLREDUCE, "float32", [64]) for s in ss]
        for s, h in zip(ss, hs):
            s.wait(h, timeout=10.0)
        for s in ss:
            s.step_end(3)
        dump = ss[0].flight_dump()
        phases = {e["phase"] for e in dump["events"]}
        assert {"STEP_BEGIN", "STEP_END"} <= phases
        marks = [e for e in dump["events"]
                 if e["phase"].startswith("STEP")]
        assert all(e["aux"] == 3 for e in marks)
        dones = [e for e in dump["events"] if e["phase"] == "DONE"]
        assert dones and any(e["aux"] > 0 for e in dones), \
            "DONE events should carry the exec-callback span in aux"
        (dec,) = decompose_rank(dump)
        assert dec["step"] == 3 and dec["collectives"] >= 1
        assert dec["step_s"] > 0
        # engine-side counter for the frontend marks
        assert ss[0].metrics()["counters"]["steps_marked"] == 1
    finally:
        _destroy(ss)


def test_cross_rank_attribute_from_live_dumps():
    ss = _make_group(2)
    try:
        for sid in (1, 2):
            for s in ss:
                s.step_begin(sid)
            hs = [s.enqueue(f"g{sid}", OP_ALLREDUCE, "float32", [32])
                  for s in ss]
            for s, h in zip(ss, hs):
                s.wait(h, timeout=10.0)
            for s in ss:
                s.step_end(sid)
        rec = attribute({r: ss[r].flight_dump() for r in range(2)})
        assert rec["summary"]["steps"] == 2
        for step in rec["steps"]:
            assert step["critical_rank"] in (0, 1)
            assert set(step["ranks"]) == {0, 1}
    finally:
        _destroy(ss)


# ---------------------------------------------------------------------------
# live attributor: anomaly detection + flight dumps + gauges


class FakeEngine:
    """step/flight surface of EngineSession without an engine."""

    def __init__(self, dump=None):
        self.begins, self.ends, self.dump_dirs = [], [], []
        self._dump = dump or {}

    def step_begin(self, sid):
        self.begins.append(sid)

    def step_end(self, sid):
        self.ends.append(sid)

    def flight_dump(self, dir=None):
        if dir is not None:
            self.dump_dirs.append(dir)
        return self._dump


def _attributor(engine=None, **kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("k", 4.0)
    kw.setdefault("window", 16)
    kw.setdefault("refresh_every", 0)
    kw.setdefault("flight_dir", "")
    if engine is not None:
        kw.setdefault("engine", engine)
    else:
        kw.setdefault("use_engine", False)
    return StepAttributor(**kw)


def test_anomaly_needs_warmup():
    # too few samples: even a huge spike stays silent (mean/sigma over a
    # handful of warmup steps is noise, not a baseline)
    a = _attributor()
    for _ in range(attr_mod.MIN_ANOMALY_SAMPLES - 1):
        assert a.observe(50.0) is None


def test_anomaly_fires_after_warmup():
    a = _attributor()
    for _ in range(attr_mod.MIN_ANOMALY_SAMPLES):
        assert a.observe(0.1) is None
    ev = a.observe(1.0)
    assert ev is not None and ev["event"] == "step_anomaly"
    assert ev["stddevs"] >= 4.0
    assert a.anomalies[-1] is ev


def test_uniform_steps_never_flag_micro_jitter():
    a = _attributor()
    for i in range(200):
        assert a.observe(0.1 + 1e-5 * (i % 3)) is None, i


def test_anomaly_counter_and_gauge_exported():
    reg = MetricsRegistry()
    a = _attributor(registry=reg)
    for _ in range(16):
        a.observe(0.1)
    a.observe(5.0)
    from horovod_tpu.metrics import snapshot_value
    snap = reg.snapshot()
    assert snapshot_value(snap, "hvd_step_anomaly_total") == 1.0
    assert snapshot_value(snap, "hvd_step_seconds_last") == \
        pytest.approx(5.0)


def test_anomaly_triggers_automatic_flight_dump(tmp_path):
    eng = FakeEngine()
    a = _attributor(engine=eng, flight_dir=str(tmp_path))
    for i in range(16):
        sid = a.next_step()
        a.step_begin(sid)
        a.step_end(sid, 0.1)
    sid = a.next_step()
    a.step_begin(sid)
    ev = a.step_end(sid, 3.0)
    assert ev is not None
    assert eng.dump_dirs == [str(tmp_path)], \
        "spike evidence must hit disk before the ring wraps"
    # engine marks bracketed every step
    assert eng.begins == eng.ends == list(range(1, 18))


def test_refresh_decomposition_exports_gauges():
    dump = _dump([
        _ev(0, "STEP_BEGIN", aux=1, ts=0),
        _ev(1, "ENQUEUE", "g", ts=200),
        _ev(2, "EXEC", "g", ts=300),
        _ev(3, "DONE", "g", ts=800, aux=500),
        _ev(4, "STEP_END", aux=1, ts=1000),
    ])
    reg = MetricsRegistry()
    a = _attributor(engine=FakeEngine(dump), registry=reg)
    dec = a.refresh_decomposition()
    assert dec is not None and a.last_decomposition is dec
    from horovod_tpu.metrics import snapshot_value
    snap = reg.snapshot()
    assert snapshot_value(snap, "hvd_step_compute_seconds") == \
        pytest.approx(200e-6)
    assert snapshot_value(snap, "hvd_step_exposed_comm_seconds") == \
        pytest.approx(500e-6)
    assert snapshot_value(snap, "hvd_step_exposed_comm_ratio") == \
        pytest.approx(0.5)


def test_periodic_refresh_driven_by_step_end():
    dump = _dump([
        _ev(0, "STEP_BEGIN", aux=1, ts=0),
        _ev(1, "STEP_END", aux=1, ts=1000),
    ])
    eng = FakeEngine(dump)
    a = _attributor(engine=eng, refresh_every=4)
    for _ in range(8):
        sid = a.next_step()
        a.step_begin(sid)
        a.step_end(sid, 0.1)
    # refreshes at steps 4 and 8 run off the training thread — poll
    deadline = time.monotonic() + 5.0
    while a.last_decomposition is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert a.last_decomposition is not None
    assert len(eng.dump_dirs) == 0  # no anomaly dumps along the way


def test_get_attributor_disabled_by_env(monkeypatch):
    monkeypatch.setenv("HOROVOD_STEP_ATTRIBUTION", "0")
    assert attr_mod.get_attributor() is None


def test_frontend_step_timer_feeds_attributor(monkeypatch):
    """timed_step brackets every invocation with engine marks and feeds
    the rolling window — the wrapper is the production entry point."""
    from horovod_tpu import metrics as hvd_metrics
    eng = FakeEngine()
    a = _attributor(engine=eng)
    monkeypatch.setattr(hvd_metrics, "_get_attributor", lambda: a)
    calls = []
    wrapped = hvd_metrics.timed_step(lambda x: calls.append(x), "jax")
    for i in range(3):
        wrapped(i)
    assert calls == [0, 1, 2]
    assert eng.begins == eng.ends == [1, 2, 3]
    assert len(a._window) == 3
