"""Tiered telemetry plane tests (ISSUE 18 pillar 1): merge semantics,
the per-host aggregator, and the driver-side ``TieredScrape`` failure
modes that ``ScrapeSpec`` models — aggregator death mid-soak falls back
to the direct scrape with no lost or double-counted increments, stale
``/agg.json`` payloads are rejected, and a generation change resets the
shared baselines exactly once (the PR-7 stale-baseline bug class, now
exercised *through the tier*).

The slow leg is the 1024-rank scrape soak over a durable KV: every run
doubles as a conformance oracle (`make soak` exports the WAL, `make
conformance` replays the aggregator families' writes against the typed
key registry and the generation/epoch monotonicity rules).
"""

import threading
import time

import pytest

from horovod_tpu.common import kv_keys
from horovod_tpu.metrics import (STEP_SECONDS, record_step, snapshot_value,
                                 step_stats)
from horovod_tpu.metrics.aggregator import (HostAggregator, TieredScrape,
                                            counter_totals, merge_snapshots)
from horovod_tpu.metrics.exporter import MetricsExporter
from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.metrics.straggler import StragglerDetector

ANOM = "hvd_step_anomaly_total"


# ---------------------------------------------------------------------------
# merge semantics


def _snap(rank, anom=0.0, steps=(), queue=None):
    reg = MetricsRegistry()
    if anom:
        reg.counter(ANOM).inc(anom)
    for s in steps:
        record_step("jax", s, registry=reg)
    if queue is not None:
        reg.gauge("hvd_serve_queue_depth").set(queue)
    return rank, reg.snapshot()


def test_merge_sums_counters_adds_buckets_vectors_gauges():
    merged = merge_snapshots([_snap(0, anom=3, steps=[0.1, 0.2], queue=4),
                              _snap(1, anom=2, steps=[0.3], queue=7)])
    # counters: one summed sample
    assert snapshot_value(merged, ANOM) == 5
    # histograms: bucket-wise added — count/sum are the union of windows
    assert step_stats(merged) == (3, pytest.approx(0.6))
    # gauges: per-rank vector, never summed (a summed straggler score or
    # queue depth per rank would be meaningless to the detector)
    gauge = next(m for m in merged["metrics"]
                 if m["name"] == "hvd_serve_queue_depth")
    by_rank = {s["labels"]["rank"]: s["value"] for s in gauge["samples"]}
    assert by_rank == {"0": 4.0, "1": 7.0}


@pytest.mark.parametrize("regroup,whole_merge_identical", [
    (lambda snaps: list(reversed(snaps)), True),
    # the tiered path: each host merges its own ranks and the driver
    # merges the hosts (what `/agg.json` serves, keyed by host index);
    # gauges then carry a host's label, so only the counters compare
    (lambda snaps: [(0, merge_snapshots(snaps[:2])),
                    (1, merge_snapshots(snaps[2:]))], False),
], ids=["reversed", "two_hosts_of_two"])
def test_merge_is_deterministic_under_input_order(regroup,
                                                  whole_merge_identical):
    import json
    snaps = [_snap(r, anom=r + 1, steps=[0.1 * (r + 1)]) for r in range(4)]
    direct = merge_snapshots(snaps)
    again = merge_snapshots(regroup(snaps))
    # counter totals are byte-identical whichever way the ranks were
    # grouped: a tiered scrape reports what a direct one would
    assert json.dumps(counter_totals(direct), sort_keys=True) == \
        json.dumps(counter_totals(again), sort_keys=True)
    assert counter_totals(direct)[ANOM] == 1 + 2 + 3 + 4
    if whole_merge_identical:  # sorted-rank accumulation
        assert json.dumps(direct, sort_keys=True) == \
            json.dumps(again, sort_keys=True)


# ---------------------------------------------------------------------------
# one simulated host behind real HTTP


class _Host:
    """N ranks with live exporters + a HostAggregator served as /agg.json
    on its own exporter, discovered through a dict-backed KV — the exact
    shape TieredScrape sees in production, minus the driver."""

    def __init__(self, n_ranks=2, host="h0"):
        self.host = host
        self.kv = {}
        self.regs = []
        self.exporters = []
        self.targets = []
        for lr in range(n_ranks):
            reg = MetricsRegistry()
            exp = MetricsExporter(reg, labels={"rank": str(lr)}).start()
            self.regs.append(reg)
            self.exporters.append(exp)
            self.targets.append({"rank": lr, "local_rank": lr,
                                 "addr": "127.0.0.1", "port": exp.port})
            self.kv[kv_keys.metrics_addr(host, lr)] = {
                "addr": "127.0.0.1", "port": exp.port, "rank": lr}
        self.agg = HostAggregator(self.targets, host=host)
        self.agg_exp = MetricsExporter(MetricsRegistry(),
                                       aggregator=self.agg).start()
        self.kv[kv_keys.agg_addr(host)] = {"addr": "127.0.0.1",
                                           "port": self.agg_exp.port}
        self.slots = [(host, lr) for lr in range(n_ranks)]

    def restart_agg(self):
        """A replacement aggregator process: same HostAggregator state
        machine, new port, endpoint re-published to the KV."""
        self.agg_exp = MetricsExporter(MetricsRegistry(),
                                       aggregator=self.agg).start()
        self.kv[kv_keys.agg_addr(self.host)] = {
            "addr": "127.0.0.1", "port": self.agg_exp.port}

    def close(self):
        for e in self.exporters + [self.agg_exp]:
            try:
                e.stop()
            except Exception:  # noqa: BLE001 — already-killed exporters
                pass


@pytest.fixture
def sim_host():
    h = _Host()
    try:
        yield h
    finally:
        h.close()


def test_host_aggregator_survives_a_dead_rank(sim_host):
    """A single unreachable rank is absent from the window, counted in
    scrape_errors — it must not poison the host's aggregate (the driver's
    fallback handles whole-host outages, not single-rank blips)."""
    sim_host.regs[0].counter(ANOM).inc(4)
    sim_host.exporters[1].stop()
    payload = sim_host.agg.refresh()
    assert payload["scrape_errors"] == 1
    assert set(payload["ranks"]) == {"0"}
    assert snapshot_value(payload["merged"], ANOM) == 4
    # the served view stamps its age on the serving host's clock
    served = sim_host.agg.payload()
    assert 0 <= served["age_seconds"] < 5


def test_tiered_heartbeat_consumes_fresh_aggregator(sim_host):
    ts = TieredScrape(sim_host.kv.get)
    prev, aprev = {}, {}
    for lr, reg in enumerate(sim_host.regs):
        record_step("jax", 0.1 * (lr + 1), registry=reg)
    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)
    assert res.agg_hosts == ["h0"] and res.fallback_hosts == []
    assert res.anomalies == []  # baseline-establish window emits nothing
    assert [t["host"] for t in res.agg_targets] == ["h0"]
    # second window: per-rank mean step time from the histogram delta
    for lr, reg in enumerate(sim_host.regs):
        record_step("jax", 0.1 * (lr + 1), registry=reg)
    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)
    assert res.times == {0: pytest.approx(0.1), 1: pytest.approx(0.2)}


def test_driver_beating_faster_than_aggregator_stays_on_agg_path(sim_host):
    """Regression: /agg.json rounds age_seconds to 1ms at serve time, so
    re-deriving the SAME window's sample time across driver beats jitters
    slightly. Without the window-floor slack every beat after the first
    rejected its own floor and silently fell back to the O(N) direct
    scrape — defeating the tier exactly when the driver heartbeats faster
    than the aggregator refreshes."""
    ts = TieredScrape(sim_host.kv.get)
    sim_host.agg.refresh()  # ONE aggregation window...
    prev, aprev = {}, {}
    for _ in range(3):  # ...consumed by three driver beats
        res = ts.heartbeat(sim_host.slots, prev, aprev)
        assert res.agg_hosts == ["h0"], \
            "same-window re-consume fell back to the direct scrape"


def test_agg_killed_mid_soak_no_lost_or_double_counted_increments(sim_host):
    """The chaos leg: the aggregator dies between publishes, the driver
    falls back to direct scrape, the aggregator comes back — and across
    both path switches every anomaly increment is counted exactly once
    (`ScrapeSpec.no_double_count` with the fault budget spent)."""
    ts = TieredScrape(sim_host.kv.get)
    prev, aprev = {}, {}
    counted = 0.0
    r0 = sim_host.regs[0].counter(ANOM)
    r1 = sim_host.regs[1].counter(ANOM)

    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)   # establish
    assert res.agg_hosts == ["h0"] and not res.anomalies

    r0.inc(2)
    r1.inc(1)
    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)   # agg path
    counted += sum(d for _, _, d in res.anomalies)
    assert counted == 3

    r0.inc(1)
    sim_host.agg_exp.stop()                           # the kill
    res = ts.heartbeat(sim_host.slots, prev, aprev)   # direct fallback
    assert res.fallback_hosts == ["h0"] and res.agg_hosts == []
    deltas = [d for _, _, d in res.anomalies]
    assert deltas == [1.0], \
        f"fallback lost or double-counted increments: {deltas}"
    counted += sum(deltas)

    r1.inc(2)
    sim_host.restart_agg()                            # the comeback
    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)   # agg path again
    assert res.agg_hosts == ["h0"]
    counted += sum(d for _, _, d in res.anomalies)
    assert counted == 6.0  # == every increment since establish, once each


def test_stale_agg_payload_falls_back(sim_host):
    ts = TieredScrape(sim_host.kv.get, stale_seconds=0.05)
    sim_host.agg.refresh()
    time.sleep(0.12)  # the payload ages past the bound, ranks stay live
    res = ts.heartbeat(sim_host.slots, {}, {})
    assert res.fallback_hosts == ["h0"] and res.agg_hosts == []
    assert res.agg_targets == []  # a stale aggregator is not advertised


def test_age_fresh_but_pre_floor_window_is_rejected(sim_host):
    """An /agg.json window that PREDATES telemetry already consumed via
    the direct path is rejected even though its age passes the staleness
    bound — consuming it would regress the shared baselines and the next
    window would re-count the difference (ScrapeSpec mutant
    ``scrape_consume_stale_window``)."""

    class _FrozenAgg:
        def __init__(self, inner_payload):
            self._p = inner_payload

        def payload(self):
            return dict(self._p, age_seconds=5.0)  # fresh per the 10s bound

        def stop(self):
            pass

    sim_host.agg.refresh()
    frozen = _FrozenAgg(sim_host.agg.payload())
    sim_host.agg_exp.stop()
    sim_host.agg_exp = MetricsExporter(MetricsRegistry(),
                                       aggregator=frozen).start()
    sim_host.kv[kv_keys.agg_addr("h0")] = {"addr": "127.0.0.1",
                                           "port": sim_host.agg_exp.port}
    ts = TieredScrape(sim_host.kv.get)
    prev, aprev = {}, {}
    del sim_host.kv[kv_keys.agg_addr("h0")]
    res = ts.heartbeat(sim_host.slots, prev, aprev)  # direct: floor = now
    assert res.fallback_hosts == ["h0"]
    sim_host.kv[kv_keys.agg_addr("h0")] = {"addr": "127.0.0.1",
                                           "port": sim_host.agg_exp.port}
    res = ts.heartbeat(sim_host.slots, prev, aprev)
    assert res.fallback_hosts == ["h0"], \
        "an aggregation window older than already-consumed telemetry " \
        "was accepted"


def test_generation_change_resets_baselines_exactly_once(sim_host):
    """The PR-7 stale-baseline bug, now through the tier: after a resize
    a restarted rank restarts its counters at 0. With the reset (baseline
    maps cleared + TieredScrape.reset(), what the driver does on every
    generation change) post-restart increments are counted; without it
    they are silently swallowed until the new counter climbs past the
    pre-restart baseline."""
    ts = TieredScrape(sim_host.kv.get)
    prev, aprev = {}, {}
    sim_host.regs[0].counter(ANOM).inc(5)
    sim_host.agg.refresh()
    ts.heartbeat(sim_host.slots, prev, aprev)          # establish at 5
    assert aprev[("h0", 0)] == 5.0

    # the "restart": both ranks come back with fresh registries (counters
    # re-registered at zero) on the same endpoints
    stale_aprev = dict(aprev)  # what a reset-skipping driver would keep
    for lr in range(2):
        reg = MetricsRegistry()
        reg.counter(ANOM)
        sim_host.exporters[lr].registry = reg
        sim_host.regs[lr] = reg
    prev.clear()
    aprev.clear()
    ts.reset()                                          # the driver's reset
    sim_host.agg.refresh()

    res = ts.heartbeat(sim_host.slots, prev, aprev)     # re-establish at 0
    assert res.anomalies == []
    sim_host.regs[0].counter(ANOM).inc(3)
    sim_host.agg.refresh()
    res = ts.heartbeat(sim_host.slots, prev, aprev)
    assert [d for _, _, d in res.anomalies] == [3.0]    # counted

    # contrast — the bug: stale baselines swallow the same increments
    ts_buggy = TieredScrape(sim_host.kv.get)
    res = ts_buggy.heartbeat(sim_host.slots, {}, stale_aprev)
    assert res.anomalies == [], \
        "3 fresh increments vs the stale baseline of 5 should be " \
        "(wrongly) invisible — the regression this test pins down"


def test_straggler_detector_over_tier_resets_on_generation_change():
    """Satellite 1: the detector consumes the tier's per-rank window
    means, and its reset() on a generation change prevents a pre-resize
    streak from flagging whichever rank inherited the number."""
    # the detector needs a few peers for a meaningful median: 4 ranks
    host = _Host(n_ranks=4, host="h0")
    try:
        ts = TieredScrape(host.kv.get)
        prev, aprev = {}, {}
        det_reset = StragglerDetector(k=2.0, windows=3, min_rel_skew=0.05)
        det_stale = StragglerDetector(k=2.0, windows=3, min_rel_skew=0.05)

        def window(times_by_lr):
            for lr, t in times_by_lr.items():
                record_step("jax", t, registry=host.regs[lr])
            host.agg.refresh()
            return ts.heartbeat(host.slots, prev, aprev).times

        window({lr: 0.1 for lr in range(4)})            # establish
        events = []
        for _ in range(2):                               # rank 3 slow twice
            t = window({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.5})
            events += det_reset.update(t) + det_stale.update(t)
        assert events == []                              # streak 2 < 3

        det_reset.reset()                                # generation change
        prev.clear()
        aprev.clear()
        ts.reset()
        window({lr: 0.1 for lr in range(4)})            # re-establish
        t = window({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.5})    # new machine, slow
        assert det_reset.update(t) == [], \
            "one slow window after a resize flagged on inherited history"
        assert [e["rank"] for e in det_stale.update(t)] == [3], \
            "control: without reset the stale streak does (wrongly) flag"
    finally:
        host.close()


# ---------------------------------------------------------------------------
# the 1024-rank scrape soak (slow; `make soak` exports its WAL for
# `make conformance` to replay)


@pytest.mark.slow
def test_scrape_soak_1024_ranks_wal_conformance(tmp_path):
    """32 hosts x 32 ranks with live exporters and aggregators over a
    DURABLE KV: six driver heartbeats mixing aggregator kills, a
    generation change, and anomaly increments. Asserts (a) exact
    increment accounting across every path switch at fleet scale, (b)
    the tier stays O(hosts) — >= 29/32 hosts consumed via /agg.json on
    every steady beat — and (c) the KV write-ahead log replays clean
    against the conformance rules (typed families, epoch claims,
    agg_targets generation monotonicity)."""
    from horovod_tpu.runner.http_kv import KVServer
    from horovod_tpu.verify import conformance

    n_hosts, per_host = 32, 32
    kv_dir = str(tmp_path / "kv")
    kv = KVServer(kv_dir=kv_dir).start()
    exporters, hosts = [], []
    regs = {}
    try:
        for h in range(n_hosts):
            host = f"host{h:02d}"
            targets = []
            for lr in range(per_host):
                rank = h * per_host + lr
                reg = MetricsRegistry()
                reg.counter(ANOM)  # registered at 0, like a real worker
                record_step("jax", 0.1, registry=reg)
                exp = MetricsExporter(reg,
                                      labels={"rank": str(rank)}).start()
                exporters.append(exp)
                regs[(host, lr)] = reg
                targets.append({"rank": rank, "local_rank": lr,
                                "addr": "127.0.0.1", "port": exp.port})
                kv.put_json(kv_keys.metrics_addr(host, lr),
                            {"addr": "127.0.0.1", "port": exp.port,
                             "rank": rank})
            agg = HostAggregator(targets, host=host)
            agg.refresh()
            agg_exp = MetricsExporter(MetricsRegistry(),
                                      aggregator=agg).start()
            exporters.append(agg_exp)
            hosts.append((host, agg, agg_exp))
            kv.put_json(kv_keys.agg_addr(host),
                        {"addr": "127.0.0.1", "port": agg_exp.port})

        slots = [(host, lr) for host, _, _ in hosts
                 for lr in range(per_host)]
        ts = TieredScrape(kv.get_json)
        prev, aprev = {}, {}
        dead = set()
        gen = 1
        injected = counted = 0.0

        def inc_round(n):
            nonlocal injected
            for (host, lr), reg in list(regs.items())[::7][:n]:
                reg.counter(ANOM).inc(1)
                injected += 1

        def refresh_live():
            live = [a for host, a, _ in hosts if host not in dead]
            threads = [threading.Thread(target=a.refresh) for a in live]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        for beat in range(6):
            if beat == 2:  # chaos: three aggregators die mid-soak
                for host, _, agg_exp in hosts[:3]:
                    agg_exp.stop()
                    dead.add(host)
            if beat == 4:  # generation change: the one-shot reset
                gen = 2
                prev.clear()
                aprev.clear()
                ts.reset()
            elif beat > 0:
                inc_round(100)
            refresh_live()
            res = ts.heartbeat(slots, prev, aprev)
            counted += sum(d for _, _, d in res.anomalies)
            if beat == 4:
                assert res.anomalies == []  # establish window, no deltas
            if beat >= 2:
                assert sorted(res.fallback_hosts) == sorted(dead)
            assert len(res.agg_hosts) == n_hosts - len(dead)
            # the driver-shaped publishes the conformance replay audits
            kv.put_json(kv_keys.metrics_targets(), res.targets,
                        epoch=kv.epoch)
            kv.put_json(kv_keys.agg_targets(),
                        {"generation": gen, "epoch": kv.epoch,
                         "hosts": res.agg_targets}, epoch=kv.epoch)
        assert counted == injected, \
            f"lost/double-counted increments: {counted} != {injected}"
        assert injected >= 300
    finally:
        kv.stop()
        stops = [threading.Thread(target=e.stop) for e in exporters]
        for t in stops:
            t.start()
        for t in stops:
            t.join()
    # every soak run doubles as a conformance oracle (chaos-soak idiom):
    # export the WAL for `make conformance`, then replay it here too
    conformance.copy_soak_artifacts(kv_dir=kv_dir)
    divergences = conformance.check_kv_wal(kv_dir)
    assert divergences == [], divergences
