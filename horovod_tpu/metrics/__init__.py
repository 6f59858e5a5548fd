"""Runtime metrics & cluster health telemetry.

The *monitoring* layer of the observability stack (the PR-2 profiler is
the *attribution* layer): live numeric telemetry from the running engine
and the Python hot paths, exported per worker in Prometheus text format
and aggregated by the elastic driver into straggler events.

Data flow (docs/DESIGN.md "Observability"):

    C++ MetricsStore ──hvdtpu_metrics_snapshot──▶ Session.metrics()
                                                     │ engine_collector
    Python hot paths ──registry instruments──▶ MetricsRegistry
                                                     │ prom.render
                         HOROVOD_METRICS_PORT ──▶ /metrics (per worker)
                                                     │ heartbeat scrape
                         elastic driver ──▶ step-time skew ──▶ straggler
                                                               events
"""

from __future__ import annotations

import time
from typing import Optional

from horovod_tpu.metrics.exporter import (  # noqa: F401
    MetricsExporter,
    start_exporter_from_env,
)
from horovod_tpu.metrics.registry import (  # noqa: F401
    DEFAULT_SIZE_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    HistogramValue,
    Metric,
    MetricsRegistry,
    engine_collector,
    get_registry,
)
from horovod_tpu.metrics import compile_log
from horovod_tpu.metrics.straggler import StragglerDetector  # noqa: F401
from horovod_tpu.profiler.annotate import (
    STEP_DISPATCH_SPAN,
    host_annotation,
    step_annotation,
)

# Family names shared by every frontend step timer (keras callback, torch
# optimizer, the jax make_train_step wrapper) — the driver's straggler
# detection sums across frameworks, so they must agree.
STEP_SECONDS = "hvd_frontend_step_seconds"
STEPS_TOTAL = "hvd_frontend_steps_total"


def _get_attributor():
    """The step attributor behind the frontend timers, or None when
    disabled (HOROVOD_STEP_ATTRIBUTION=0). Late import: obs.attribution
    imports this package."""
    from horovod_tpu.obs.attribution import get_attributor
    return get_attributor()


class _TimedStep:
    """Wraps a (jitted) step callable: records wall time per invocation
    into the shared step-time histogram while forwarding everything else
    (``.lower``, AOT attributes) to the wrapped function.

    Also the frontend half of step-time attribution: each invocation is
    bracketed with engine STEP_BEGIN/STEP_END flight marks and fed to the
    rolling anomaly detector (horovod_tpu.obs.attribution) — one lock-free
    engine record each side plus a deque append, cheap enough for every
    step.

    While a ``jax.profiler`` trace is being collected each invocation also
    writes an ``hvd.step`` span (``step_num`` = this wrapper's call count)
    holding an ``hvd.step.dispatch`` span around the wrapped call; with no
    trace running both are an object and a flag test.

    The compile log (:mod:`horovod_tpu.metrics.compile_log`) learns from
    here which functions are steps (``framework="jax"`` is
    ``dp._jit_step``'s alone) and which call is open on this thread, so a
    program compiled inside a later call is a recompile that names its
    step."""

    def __init__(self, fn, framework: str):
        self._fn = fn
        self.framework = framework
        self._hist = get_registry().histogram(STEP_SECONDS,
                                              framework=framework)
        self._steps = get_registry().counter(STEPS_TOTAL,
                                             framework=framework)
        # there from the start, so that 0 recompiles reads 0 and not absent
        get_registry().counter(compile_log.RECOMPILES_TOTAL,
                               framework=framework)
        if framework == "jax":
            compile_log.step_function(getattr(fn, "__name__", None))
        self._attr = None
        self._attr_resolved = False
        self._calls = 0  # step_num of the next hvd.step span

    @property
    def step_num(self) -> int:
        """Number of the call that is open (the last one, between calls)."""
        return self._calls - 1

    def __call__(self, *args, **kwargs):
        if not self._attr_resolved:
            # resolved on first step, not at wrap time: the attributor
            # needs the engine session, which init() creates later
            self._attr = _get_attributor()
            self._attr_resolved = True
        attr = self._attr
        # the wrapper's own spans, on the profiler's clock beside the device
        # planes: hvd.step around everything, hvd.step.dispatch around the
        # wrapped call; the difference is what this wrapper costs
        with step_annotation(self._calls):
            self._calls += 1
            sid = attr.next_step() if attr is not None else 0
            if attr is not None:
                attr.step_begin(sid)
            t0 = time.perf_counter()
            compile_log.OPEN.step = self
            try:
                with host_annotation(STEP_DISPATCH_SPAN):
                    out = self._fn(*args, **kwargs)
            finally:
                compile_log.OPEN.step = None
            dt = time.perf_counter() - t0
            self._hist.observe(dt)
            self._steps.inc()
            if attr is not None:
                attr.step_end(sid, dt)
        return out

    def __getattr__(self, item):
        # Never forward private/dunder probes: pickle and copy interrogate
        # __setstate__/__reduce__ before __init__ has run, and forwarding
        # would re-enter this method on the missing _fn (RecursionError).
        if item.startswith("_"):
            raise AttributeError(item)
        return getattr(object.__getattribute__(self, "_fn"), item)


def timed_step(fn, framework: str):
    """Instrument a train-step callable with the shared step timer.

    Note the async-dispatch caveat: under jax the recorded time is the
    dispatch+donation wall time of the call, which converges to the true
    step time in any steady-state loop (the next dispatch blocks on the
    previous step's donated buffers)."""
    return _TimedStep(fn, framework)


def record_step(framework: str, seconds: float,
                registry: Optional[MetricsRegistry] = None):
    """Record one frontend step duration (used by frontends that own their
    own timing, e.g. the torch optimizer and the keras callback).

    On the default registry the duration also feeds the step attributor's
    rolling anomaly detector; these frontends can't bracket the step with
    engine marks (they time after the fact), so they get anomaly events
    and gauges but no flight-ring windows."""
    reg = registry if registry is not None else get_registry()
    reg.histogram(STEP_SECONDS, framework=framework).observe(seconds)
    reg.counter(STEPS_TOTAL, framework=framework).inc()
    if registry is None:
        attr = _get_attributor()
        if attr is not None:
            attr.observe(seconds)


def snapshot_value(snapshot: dict, name: str, **labels) -> Optional[float]:
    """Scalar value of a counter/gauge family in a ``/metrics.json``
    snapshot (summed over samples matching ``labels`` — the families
    ``hvd-top`` and the driver read carry one sample each). None when the
    family is absent or no sample matches."""
    total, found = 0.0, False
    want = {str(k): str(v) for k, v in labels.items()}
    for m in snapshot.get("metrics", []):
        if m.get("name") != name:
            continue
        for s in m.get("samples", []):
            if "value" not in s:
                continue  # histogram family under a scalar lookup
            got = s.get("labels", {})
            if all(got.get(k) == v for k, v in want.items()):
                total += float(s["value"])
                found = True
    return total if found else None


def snapshot_histogram(snapshot: dict, name: str, **labels) -> Optional[dict]:
    """Merged histogram of a family in a ``/metrics.json`` snapshot:
    ``{"bounds": [...], "counts": [...], "sum": s, "count": n}`` with
    per-bucket (non-cumulative) counts, summed over samples matching
    ``labels``. None when absent/empty. Samples must share bucket bounds
    (true for every family one process exports)."""
    want = {str(k): str(v) for k, v in labels.items()}
    merged: Optional[dict] = None
    for m in snapshot.get("metrics", []):
        if m.get("name") != name:
            continue
        for s in m.get("samples", []):
            if "counts" not in s:
                continue
            got = s.get("labels", {})
            if not all(got.get(k) == v for k, v in want.items()):
                continue
            if merged is None:
                merged = {"bounds": list(s["bounds"]),
                          "counts": list(s["counts"]),
                          "sum": float(s.get("sum", 0.0)),
                          "count": int(s.get("count", 0))}
            elif list(s["bounds"]) == merged["bounds"]:
                merged["counts"] = [a + b for a, b in
                                    zip(merged["counts"], s["counts"])]
                merged["sum"] += float(s.get("sum", 0.0))
                merged["count"] += int(s.get("count", 0))
    return merged if merged and merged["count"] else None


def histogram_quantile(hist: dict, q: float) -> Optional[float]:
    """Estimate the ``q``-quantile (0..1) of a merged histogram
    (:func:`snapshot_histogram` shape) by linear interpolation inside the
    landing bucket — the standard Prometheus ``histogram_quantile``
    estimate. The overflow bucket clamps to its lower bound (no upper edge
    to interpolate toward). None for an empty histogram."""
    if not hist or not hist.get("count"):
        return None
    bounds, counts = hist["bounds"], hist["counts"]
    target = q * hist["count"]
    cum = 0.0
    for i, c in enumerate(counts):
        if cum + c >= target and c > 0:
            lo = bounds[i - 1] if i > 0 else 0.0
            if i >= len(bounds):
                return float(bounds[-1]) if bounds else None
            hi = bounds[i]
            frac = (target - cum) / c
            return lo + (hi - lo) * frac
        cum += c
    return float(bounds[-1]) if bounds else None


def step_stats(snapshot: dict) -> Optional[tuple]:
    """(count, sum_seconds) of the step-time histogram across frameworks
    from a ``/metrics.json`` snapshot — what the driver diffs per window.
    None when the worker has recorded no steps yet."""
    total_count, total_sum = 0, 0.0
    for m in snapshot.get("metrics", []):
        if m.get("name") != STEP_SECONDS:
            continue
        for s in m.get("samples", []):
            total_count += int(s.get("count", 0))
            total_sum += float(s.get("sum", 0.0))
    return (total_count, total_sum) if total_count else None

