"""Observability satellites: machine-readable stall reports on every rank
(fault-injection: one rank withholds a tensor), the ABI guard, the
unified HOROVOD_LOG_LEVEL knob for the Python layers (incl. per-rank log
tagging), per-rank straggler-score gauges on /metrics, and the
MetricAverageCallback cross-rank mean (2-rank subprocess run)."""

import importlib.util
import os
import socket
import subprocess
import sys
import textwrap
import time
import uuid

import pytest

from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.engine import OP_ALLREDUCE, EngineSession, bindings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# stall report fault injection


def test_stall_report_names_missing_rank_on_all_ranks():
    """Rank 3 withholds a tensor the other ranks submitted: every rank —
    not just the coordinator — observes a machine-readable report naming
    rank 3 as missing (reference test_stall.py only ever sees rank-0 log
    text; the report here is broadcast)."""
    n = 4
    group = f"stall-{uuid.uuid4().hex[:8]}"
    sessions = [EngineSession(rank=r, size=n, transport="loopback",
                              group=group, cycle_time_ms=1.0,
                              stall_warning_sec=0.3)
                for r in range(n)]
    try:
        handles = [s.enqueue("withheld", OP_ALLREDUCE, "float32", [4])
                   for s in sessions[:3]]
        deadline = time.monotonic() + 10.0
        reports = {}
        while time.monotonic() < deadline and len(reports) < n:
            for r, s in enumerate(sessions):
                if r not in reports:
                    rep = s.stall_report()
                    if rep:
                        reports[r] = rep
            time.sleep(0.05)
        assert len(reports) == n, f"ranks with a report: {sorted(reports)}"
        for r, rep in reports.items():
            stalled = {e["tensor"]: e for e in rep["stalled"]}
            assert "withheld" in stalled, (r, rep)
            assert stalled["withheld"]["missing"] == [3], (r, rep)
            assert stalled["withheld"]["ready"] == [0, 1, 2], (r, rep)
        # engine counters observed the stall (coordinator-side scan)
        c = sessions[0].metrics()["counters"]
        assert c["stall_warnings"] >= 1
        assert c["stalled_tensors"] >= 1
        # unblock: the withholding rank finally submits; everyone completes
        handles.append(sessions[3].enqueue("withheld", OP_ALLREDUCE,
                                           "float32", [4]))
        for s, h in zip(sessions[:3] + sessions[3:], handles):
            s.wait(h, timeout=10.0)
    finally:
        for s in sessions:
            s._lib.hvdtpu_shutdown(s._session)
        for s in sessions:
            s.destroy()


def test_stall_report_empty_before_any_warning():
    group = f"nostall-{uuid.uuid4().hex[:8]}"
    sessions = [EngineSession(rank=r, size=2, transport="loopback",
                              group=group, cycle_time_ms=1.0)
                for r in range(2)]
    try:
        assert sessions[0].stall_report() is None
        assert sessions[1].stall_report() is None
    finally:
        for s in sessions:
            s._lib.hvdtpu_shutdown(s._session)
        for s in sessions:
            s.destroy()


# ---------------------------------------------------------------------------
# ABI guard


def test_abi_version_is_10():
    # 9 → 10: topology-aware data plane — hvdtpu_create_session gains
    # host_id (launcher locality map), hvdtpu_set_tuned_params gains the
    # cycle-fenced routing knobs (ring_threshold_bytes / hierarchical /
    # small_tensor_algo), hvdtpu_data_algo_ops added
    lib = bindings.load_library()
    assert bindings.ABI_VERSION == 10
    assert lib.hvdtpu_abi_version() == 10


def test_stale_library_refused(monkeypatch):
    """bindings must refuse a .so whose ABI doesn't match — simulated by
    bumping the expected version and forcing a fresh load."""
    monkeypatch.setattr(bindings, "ABI_VERSION", 999)
    monkeypatch.setattr(bindings, "_lib", None)
    with pytest.raises(HorovodInternalError, match="ABI"):
        bindings.load_library()
    # monkeypatch teardown restores the real _lib and version


# ---------------------------------------------------------------------------
# unified logging knob


def test_python_logging_honors_horovod_log_level(monkeypatch):
    import logging

    from horovod_tpu.common import hvd_logging

    monkeypatch.setenv("HOROVOD_LOG_LEVEL", "debug")
    logger = hvd_logging.setup_python_logging(force=True)
    assert logger.level == logging.DEBUG
    monkeypatch.setenv("HOROVOD_LOG_LEVEL", "error")
    assert hvd_logging.setup_python_logging(force=True).level == \
        logging.ERROR
    monkeypatch.delenv("HOROVOD_LOG_LEVEL")
    assert hvd_logging.setup_python_logging(force=True).level == \
        logging.WARNING
    # timestamp knob switches the formatter
    monkeypatch.setenv("HOROVOD_LOG_TIMESTAMP", "1")
    logger = hvd_logging.setup_python_logging(force=True)
    assert "%(asctime)s" in logger.handlers[0].formatter._fmt
    monkeypatch.setenv("HOROVOD_LOG_TIMESTAMP", "0")
    hvd_logging.setup_python_logging(force=True)


def test_log_records_carry_rank_after_init(monkeypatch, capsys):
    """Satellite: once init() has stamped the rank context, every record
    emitted through common/hvd_logging carries rank/local_rank so
    multi-rank logs interleave legibly; before that, nothing changes."""
    import logging

    from horovod_tpu.common import hvd_logging

    monkeypatch.setattr(hvd_logging, "_rank_context",
                        {"rank": None, "local_rank": None})
    logger = hvd_logging.setup_python_logging(force=True)
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(self.format(record))

    cap = Capture()
    cap.setFormatter(logger.handlers[0].formatter)
    cap.addFilter(hvd_logging._RankContextFilter())
    logger.addHandler(cap)
    try:
        log = hvd_logging.get_logger("test")
        log.warning("before-init line")
        assert "rank=" not in records[-1]
        assert records[-1].startswith("[hvdtpu ")
        # what basics.init() does after resolving the topology
        hvd_logging.set_rank_context(3, 1)
        log.warning("after-init line")
        assert "rank=3 local=1" in records[-1], records[-1]
    finally:
        logger.removeHandler(cap)
        hvd_logging.setup_python_logging(force=True)


# ---------------------------------------------------------------------------
# per-rank straggler scores as /metrics gauges


def test_straggler_scores_exported_as_gauges():
    """Satellite: the StragglerDetector's per-rank scores are live gauges
    on /metrics(.json), not just logged events — scraped here through a
    real exporter on an ephemeral port."""
    import json as json_mod
    import urllib.request

    from horovod_tpu.metrics import MetricsExporter, MetricsRegistry
    from horovod_tpu.metrics.straggler import StragglerDetector

    reg = MetricsRegistry()
    det = StragglerDetector(k=2.0, windows=2, registry=reg)
    # rank 2 is 3x slower than its peers for two consecutive windows
    events = []
    for _ in range(2):
        events += det.update({0: 1.0, 1: 1.01, 2: 3.0, 3: 0.99})
    assert [e["rank"] for e in events] == [2]

    exporter = MetricsExporter(reg, port=0).start()
    try:
        snap = json_mod.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics.json",
            timeout=5).read().decode())
        fams = {m["name"]: m for m in snap["metrics"]}
        assert "hvd_straggler_score" in fams
        scores = {s["labels"]["rank"]: s["value"]
                  for s in fams["hvd_straggler_score"]["samples"]}
        assert set(scores) == {"0", "1", "2", "3"}
        assert scores["2"] > 2.0  # far beyond the k=2 threshold
        assert all(abs(scores[r]) < 2.0 for r in ("0", "1", "3"))
        flagged = {s["labels"]["rank"]: s["value"]
                   for s in fams["hvd_straggler_flagged"]["samples"]}
        assert flagged["2"] == 1.0
        assert flagged["0"] == 0.0
        # the text endpoint renders the same family for Prometheus
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/metrics",
            timeout=5).read().decode()
        assert 'hvd_straggler_score{rank="2"}' in text
    finally:
        exporter.stop()
    # recovery clears the flag gauge on the next window
    det.update({0: 1.0, 1: 1.01, 2: 1.0, 3: 0.99})
    assert reg.gauge("hvd_straggler_flagged", rank="2").value == 0.0
    # a departed rank's gauges are zeroed, not served stale forever
    det.update({0: 1.0, 1: 1.01, 3: 5.0})
    assert reg.gauge("hvd_straggler_score", rank="2").value == 0.0
    assert reg.gauge("hvd_straggler_flagged", rank="2").value == 0.0


# ---------------------------------------------------------------------------
# MetricAverageCallback: true cross-rank mean on 2 ranks


_AVG_WORKER = textwrap.dedent("""
    import os, sys
    os.environ.setdefault("KERAS_BACKEND", "jax")
    sys.path.insert(0, {repo!r})
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()
    assert hvd.size() == 2

    from horovod_tpu.keras.callbacks import (MetricAverageCallback,
                                             _averageable_keys)

    # filtering contract: numeric scalars in, lr/strings/bools out
    logs = {{"loss": 1.0 + rank, "acc": np.float32(rank),
             "lr": 0.1 * (rank + 1), "wd_lr": 0.5, "note": "text",
             "flag": True, "vec": np.ones(3)}}
    assert _averageable_keys(logs) == ["acc", "loss"], \\
        _averageable_keys(logs)

    cb = MetricAverageCallback()
    cb.on_epoch_end(0, logs)
    # true cross-rank means: loss = (1.0 + 2.0)/2, acc = (0 + 1)/2
    assert abs(logs["loss"] - 1.5) < 1e-6, logs
    assert abs(logs["acc"] - 0.5) < 1e-6, logs
    # untouched: lr-style, strings, bools, non-scalars
    assert logs["lr"] == 0.1 * (rank + 1), logs
    assert logs["wd_lr"] == 0.5 and logs["note"] == "text"
    assert logs["flag"] is True and logs["vec"].shape == (3,)

    hvd.shutdown()
    print(f"metric-avg worker {{rank}} OK")
""")


@pytest.mark.skipif(importlib.util.find_spec("keras") is None,
                    reason="keras not installed")
def test_metric_average_callback_two_ranks(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "avg_worker.py"
    script.write_text(_AVG_WORKER.format(repo=REPO))
    procs = []
    for r in range(2):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE="2",
                   HOROVOD_LOCAL_RANK=str(r), HOROVOD_LOCAL_SIZE="2",
                   HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                   HOROVOD_CONTROLLER_PORT=str(port),
                   JAX_PLATFORMS="cpu", KERAS_BACKEND="jax")
        procs.append(subprocess.Popen([sys.executable, str(script)],
                                      env=env, stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=180)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"metric-avg worker {r} OK" in out
