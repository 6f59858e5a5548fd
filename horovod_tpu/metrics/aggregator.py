"""Per-host telemetry aggregation (the tiered scrape plane, ISSUE 18).

Every telemetry consumer — the elastic driver's heartbeat scrape,
straggler detection, the autoscaler's SLO loop, ``hvd-top`` — used to
read one ``/metrics.json`` per rank: O(N) HTTP round-trips per heartbeat,
the thing ROADMAP open item 3 names as breaking first at 1024 ranks.
This module is the middle tier that makes all of them O(hosts):

- :func:`merge_snapshots` — deterministic merge of co-located ranks'
  registry snapshots. **Counters are summed** (sorted-rank order, so two
  merges of the same inputs are byte-identical), **fixed-bucket
  histograms are bucket-wise added** (same bounds; differing bounds stay
  separate samples), and **gauges are kept as per-rank vectors** (each
  sample gains a ``rank`` label) — a summed queue depth is meaningful,
  a summed straggler score is not.
- :class:`HostAggregator` — hosted by local_rank 0's
  :class:`~horovod_tpu.metrics.exporter.MetricsExporter`: a background
  thread scrapes the co-located ranks' ``/metrics.json`` and publishes
  the merged view plus compact per-rank vectors (step stats, anomaly
  counters, serving SLO samples) as ``/agg.json``.
- :class:`TieredScrape` — the driver side of the tier, factored out of
  ``ElasticDriver._scrape_worker_metrics`` so tests drive the exact
  production consume path without a
  live driver. Per heartbeat each host is consumed through **exactly
  one** path: the aggregator when its ``/agg.json`` is fresh, the
  per-rank direct scrape otherwise (aggregator dead/stale) — never
  both, or counter deltas would double-count (``ScrapeSpec``'s
  ``no_double_count`` invariant, seeded mutant
  ``scrape_double_count_on_fallback``).

Staleness contract: ``/agg.json`` carries ``age_seconds`` computed on
the serving host (no cross-host clock skew); the driver falls back to
direct scrape past ``HOROVOD_AGG_STALE_SECONDS`` — the same bound
``hvd-top`` uses for its ``STALE DATA`` banner over aggregated rows.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from horovod_tpu.common.env_registry import env_float
from horovod_tpu.metrics import snapshot_value, step_stats
from horovod_tpu.runner.http_kv import http_get_with_retry


def _label_key(labels: Dict[str, str]) -> Tuple:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def merge_snapshots(snaps: List[Tuple[int, dict]]) -> dict:
    """Merge per-rank registry snapshots into one host-level snapshot of
    the same ``{"metrics": [{name, kind, samples}]}`` shape (so
    ``snapshot_value``/``snapshot_histogram``/``histogram_quantile`` read
    it unchanged).

    ``snaps`` is ``[(rank, snapshot), ...]``; ranks are processed in
    sorted order so the float accumulation is deterministic and two
    merges of the same inputs serialize byte-identically.
    """
    counters: Dict[Tuple, dict] = {}
    hists: Dict[Tuple, dict] = {}
    gauges: List[Tuple[Tuple, dict]] = []
    kinds: Dict[str, str] = {}
    order: List[str] = []
    for rank, snap in sorted(snaps, key=lambda rs: int(rs[0])):
        for m in snap.get("metrics", []):
            name, kind = m.get("name"), m.get("kind", "counter")
            if name not in kinds:
                kinds[name] = kind
                order.append(name)
            for s in m.get("samples", []):
                labels = dict(s.get("labels", {}))
                if kind == "gauge":
                    # per-rank vector: straggler-relevant gauges must not
                    # collapse (a summed score is meaningless); consumers
                    # select with rank=<r> or average over the vector
                    labels.setdefault("rank", str(rank))
                    gauges.append(((name, _label_key(labels)),
                                   {"labels": labels,
                                    "value": float(s.get("value", 0.0))}))
                elif "counts" in s:
                    key = (name, _label_key(labels),
                           tuple(s.get("bounds", [])))
                    cur = hists.get(key)
                    if cur is None:
                        hists[key] = {
                            "labels": labels,
                            "bounds": list(s.get("bounds", [])),
                            "counts": list(s.get("counts", [])),
                            "sum": float(s.get("sum", 0.0)),
                            "count": int(s.get("count", 0))}
                    else:
                        cur["counts"] = [a + b for a, b in
                                         zip(cur["counts"], s["counts"])]
                        cur["sum"] += float(s.get("sum", 0.0))
                        cur["count"] += int(s.get("count", 0))
                else:
                    key = (name, _label_key(labels))
                    cur = counters.get(key)
                    if cur is None:
                        counters[key] = {"labels": labels,
                                         "value": float(s.get("value", 0.0))}
                    else:
                        cur["value"] += float(s.get("value", 0.0))
    metrics = []
    for name in order:
        kind = kinds[name]
        if kind == "gauge":
            samples = [s for (n, _), s in gauges if n == name]
        elif any(k[0] == name for k in hists):
            samples = [s for k, s in hists.items() if k[0] == name]
        else:
            samples = [s for k, s in counters.items() if k[0] == name]
        metrics.append({"name": name, "kind": kind, "samples": samples})
    return {"metrics": metrics}


def counter_totals(snapshot: dict) -> Dict[str, float]:
    """{family name -> summed value} for every counter family in a
    snapshot — the quantity ``tests/test_telemetry_tier.py``
    asserts byte-identical between the direct and tiered scrape paths."""
    out: Dict[str, float] = {}
    for m in snapshot.get("metrics", []):
        if m.get("kind") != "counter":
            continue
        total = 0.0
        for s in m.get("samples", []):
            if "value" in s:
                total += float(s["value"])
        out[m["name"]] = total
    return out


def _rank_vector(rank: int, local_rank, target: dict, snap: dict) -> dict:
    """The compact per-rank record the driver consumes from /agg.json:
    exactly what its straggler/anomaly/autoscaler paths read per rank."""
    from horovod_tpu.runner.elastic.autoscaler import worker_slo_from_snapshot
    vec = {
        "rank": int(rank),
        "local_rank": local_rank,
        "addr": target.get("addr"),
        "port": target.get("port"),
        "step": None,
        "anomalies": snapshot_value(snap, "hvd_step_anomaly_total"),
        "slo": None,
    }
    stats = step_stats(snap)
    if stats is not None:
        vec["step"] = [int(stats[0]), float(stats[1])]
    slo = worker_slo_from_snapshot(f"{target.get('host', '?')}/{local_rank}",
                                  snap)
    if slo is not None:
        vec["slo"] = slo._asdict()
    return vec


class HostAggregator:
    """Scrapes co-located ranks' ``/metrics.json`` and holds the merged
    ``/agg.json`` payload. Hosted by local_rank 0's exporter; pure HTTP
    client + JSON merge, no registry access of its own.

    ``targets``: list of ``{"rank", "local_rank", "addr", "port"}`` or a
    callable returning one (re-evaluated every refresh, so KV-discovered
    co-located ranks can come and go with elastic resizes).
    """

    def __init__(self, targets, host: str = "",
                 interval: Optional[float] = None,
                 timeout: float = 1.0):
        self._targets = targets
        self.host = host
        self.interval = interval if interval is not None else \
            env_float("HOROVOD_AGG_INTERVAL_SECONDS")
        self.timeout = timeout
        self._lock = threading.Lock()
        self._payload: Optional[dict] = None
        self._refreshed_mono: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.scrape_errors = 0

    # -- scrape + merge ------------------------------------------------------

    def _resolve_targets(self) -> List[dict]:
        t = self._targets() if callable(self._targets) else self._targets
        return list(t or [])

    def refresh(self) -> dict:
        """One aggregation pass: scrape every co-located rank, merge, and
        install the new payload. Unreachable ranks are simply absent from
        this window (the driver's fallback handles a whole-host outage;
        a single dead rank must not poison its host's aggregate)."""
        snaps: List[Tuple[int, dict]] = []
        ranks: Dict[str, dict] = {}
        errors = 0
        for t in self._resolve_targets():
            url = f"http://{t['addr']}:{t['port']}/metrics.json"
            try:
                snap = json.loads(http_get_with_retry(
                    url, timeout=self.timeout, attempts=1))
            except Exception:  # noqa: BLE001 — rank mid-restart
                errors += 1
                continue
            rank = int(t.get("rank", snap.get("labels", {}).get("rank", -1)))
            snaps.append((rank, snap))
            ranks[str(t.get("local_rank", rank))] = _rank_vector(
                rank, t.get("local_rank", rank), t, snap)
        payload = {
            "host": self.host,
            "ts": time.time(),
            "ranks": ranks,
            "merged": merge_snapshots(snaps),
            "scrape_errors": errors,
        }
        with self._lock:
            self._payload = payload
            self._refreshed_mono = time.monotonic()
            self.scrape_errors = errors
        return payload

    def payload(self) -> Optional[dict]:
        """The latest aggregate with its serve-time ``age_seconds``
        (computed on this host's monotonic clock — the staleness check
        never depends on cross-host clock sync). None before the first
        refresh completes."""
        with self._lock:
            if self._payload is None:
                return None
            out = dict(self._payload)
            out["age_seconds"] = round(
                time.monotonic() - self._refreshed_mono, 3)
        return out

    # -- background loop -----------------------------------------------------

    def start(self) -> "HostAggregator":
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="hvd-agg")
        self._thread.start()
        return self

    def _run(self):
        while not self._stop.is_set():
            try:
                self.refresh()
            except Exception:  # noqa: BLE001 — aggregation must never
                pass  # take down the worker hosting it
            self._stop.wait(self.interval)

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)


# ===========================================================================
# Driver-side consumption (the tiered heartbeat)
# ===========================================================================

class ScrapeResult(NamedTuple):
    """One heartbeat's consumed telemetry, path bookkeeping included."""
    times: Dict[int, float]            # rank -> window mean step seconds
    targets: List[dict]                # per-rank metrics endpoints
    agg_targets: List[dict]            # live per-host aggregator endpoints
    anomalies: List[Tuple[Tuple[str, int], dict, float]]
    slos: List                         # WorkerSLO samples (autoscaler input)
    agg_hosts: List[str]               # hosts consumed via the aggregator
    fallback_hosts: List[str]          # hosts consumed via direct scrape


# Window-floor comparison slack (seconds). The payload's age is rounded
# to 1ms at serve time and both clock reads carry scheduling jitter, so
# re-deriving the SAME aggregation window's sample time across two
# heartbeats wobbles by a few ms — without slack, a driver beating
# faster than the aggregator refreshes would reject its own floor and
# fall back to the O(N) direct scrape every other beat. A real stale
# window is at least one refresh interval (1s default) behind; 50ms
# cleanly separates the two.
_WINDOW_SLACK_SECONDS = 0.05


class TieredScrape:
    """The driver's per-heartbeat scrape over the aggregator tier.

    For each host: read ``agg_addr/<host>`` from the KV, fetch
    ``/agg.json``, and consume the per-rank vectors when the payload is
    fresh; otherwise fall back to the per-rank direct scrape via
    ``metrics_addr/<host>/<slot>``. A host goes through exactly one path
    per heartbeat, and both paths diff against the SAME baseline maps
    (owned by the caller — the driver clears them on every generation
    change, exactly once, which is ``ScrapeSpec``'s
    ``baseline_reset_on_generation`` invariant)."""

    def __init__(self, kv_get_json: Callable[[str], Optional[dict]],
                 stale_seconds: Optional[float] = None,
                 timeout: float = 1.0, attempts: int = 2):
        self._kv_get = kv_get_json
        self.stale_seconds = stale_seconds if stale_seconds is not None \
            else env_float("HOROVOD_AGG_STALE_SECONDS")
        self.timeout = timeout
        self.attempts = attempts
        # per-host consume-window floor (driver monotonic clock): the
        # effective sample time of the newest telemetry already consumed
        # for the host. An agg payload whose scrape PREDATES this floor
        # is rejected even if age-fresh — consuming it would regress the
        # shared baselines below values a direct scrape already
        # installed, and the next window would re-count the difference
        # (double-counting via both paths across heartbeats; ScrapeSpec
        # mutant ``scrape_consume_stale_window``).
        self._window_floor: Dict[str, float] = {}

    def reset(self):
        """Forget consume-window floors (driver generation change — the
        caller clears the baseline maps at the same point)."""
        self._window_floor.clear()

    def _fetch_agg(self, host: str) -> Optional[dict]:
        from horovod_tpu.common import kv_keys
        info = self._kv_get(kv_keys.agg_addr(host))
        if not isinstance(info, dict) or not info.get("addr") \
                or not info.get("port"):
            return None
        try:
            url = f"http://{info['addr']}:{info['port']}/agg.json"
            payload = json.loads(http_get_with_retry(
                url, timeout=self.timeout, attempts=self.attempts,
                backoff=0.05))
        except Exception:  # noqa: BLE001 — aggregator dead: fall back
            return None
        if not isinstance(payload, dict) or "ranks" not in payload:
            return None
        age = payload.get("age_seconds")
        if age is None or float(age) > self.stale_seconds:
            return None  # stale aggregate: the fallback path owns this host
        # window-ordering guard: the payload's effective sample time on
        # OUR clock (age is a host-monotonic duration, so subtracting it
        # from our monotonic now involves no cross-host clock sync)
        sample_mono = time.monotonic() - float(age)
        if sample_mono < self._window_floor.get(host, float("-inf")) \
                - _WINDOW_SLACK_SECONDS:
            return None  # age-fresh but older than what we consumed
        payload["_addr"] = info["addr"]
        payload["_port"] = info["port"]
        payload["_sample_mono"] = sample_mono
        return payload

    def heartbeat(self, slots: List[Tuple[str, int]],
                  metrics_prev: Dict[Tuple[str, int], tuple],
                  anomaly_prev: Dict[Tuple[str, int], float],
                  want_slo: bool = False) -> ScrapeResult:
        """Consume one heartbeat window for ``slots`` (host, local_rank
        pairs), diffing step/anomaly counters into the caller-owned
        baseline maps."""
        from horovod_tpu.common import kv_keys
        times: Dict[int, float] = {}
        targets: List[dict] = []
        agg_targets: List[dict] = []
        anomalies: List[Tuple[Tuple[str, int], dict, float]] = []
        slos: List = []
        agg_hosts: List[str] = []
        fallback_hosts: List[str] = []

        by_host: Dict[str, List[int]] = {}
        for host, lr in slots:
            by_host.setdefault(host, []).append(lr)

        for host in sorted(by_host):
            payload = self._fetch_agg(host)
            if payload is not None:
                self._window_floor[host] = max(
                    self._window_floor.get(host, float("-inf")),
                    payload["_sample_mono"])
                agg_hosts.append(host)
                agg_targets.append({"host": host, "addr": payload["_addr"],
                                    "port": payload["_port"],
                                    "age_seconds": payload.get(
                                        "age_seconds")})
                ranks = payload.get("ranks", {})
                for lr in by_host[host]:
                    vec = ranks.get(str(lr))
                    if not isinstance(vec, dict):
                        continue  # rank missed this aggregation window
                    self._consume_rank(
                        host, lr, vec, metrics_prev, anomaly_prev,
                        times, targets, anomalies, slos, want_slo)
                continue
            # fallback: aggregator dead or stale — direct per-rank scrape,
            # never in the same heartbeat as an agg consume of this host
            fallback_hosts.append(host)
            self._window_floor[host] = time.monotonic()
            for lr in by_host[host]:
                info = self._kv_get(kv_keys.metrics_addr(host, lr))
                if not isinstance(info, dict) or not info.get("addr") \
                        or not info.get("port"):
                    continue
                try:
                    url = (f"http://{info['addr']}:{info['port']}"
                           f"/metrics.json")
                    snap = json.loads(http_get_with_retry(
                        url, timeout=self.timeout, attempts=self.attempts,
                        backoff=0.05))
                except Exception:  # noqa: BLE001 — worker mid-restart
                    continue
                vec = _rank_vector(int(info.get("rank", -1)), lr,
                                   {"addr": info["addr"],
                                    "port": info["port"], "host": host},
                                   snap)
                self._consume_rank(
                    host, lr, vec, metrics_prev, anomaly_prev,
                    times, targets, anomalies, slos, want_slo)
        return ScrapeResult(times, targets, agg_targets, anomalies, slos,
                            agg_hosts, fallback_hosts)

    @staticmethod
    def _consume_rank(host, lr, vec, metrics_prev, anomaly_prev,
                      times, targets, anomalies, slos, want_slo):
        """Diff one rank's vector against the shared baselines — the one
        consume path both tiers funnel through, so a rank can never be
        double-counted within a heartbeat and counter totals stay
        monotonic across an aggregator death + fallback (the baselines
        survive the path switch)."""
        key = (host, lr)
        if vec.get("addr") and vec.get("port"):
            targets.append({"addr": vec["addr"], "port": vec["port"],
                            "rank": vec.get("rank")})
        count = vec.get("anomalies")
        if count is not None:
            prev_count = anomaly_prev.get(key)
            anomaly_prev[key] = float(count)
            if prev_count is not None and count > prev_count:
                anomalies.append((key, {"rank": vec.get("rank")},
                                  float(count) - prev_count))
        if want_slo and isinstance(vec.get("slo"), dict):
            from horovod_tpu.runner.elastic.autoscaler import WorkerSLO
            try:
                slos.append(WorkerSLO(**vec["slo"]))
            except TypeError:
                pass  # vector from a different version: skip, don't crash
        step = vec.get("step")
        if not step:
            return
        stats = (int(step[0]), float(step[1]))
        prev = metrics_prev.get(key)
        metrics_prev[key] = stats
        if prev is not None and stats[0] > prev[0]:
            times[int(vec.get("rank", -1))] = \
                (stats[1] - prev[1]) / (stats[0] - prev[0])
