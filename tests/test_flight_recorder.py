"""Collective flight recorder, desync detection, and the cross-rank
post-mortem analyzer (ISSUE 5).

Acceptance matrix: (a) with HOROVOD_FAULT_SPEC killing one rank
mid-collective, every surviving rank writes a flight dump on abort and the
analyzer names the dead rank and the in-flight tensor; (b) a deliberate
shape mismatch raises an error naming the offending rank and both
signatures within one coordination cycle; (c) hvd.stall_report() and the
flight dump agree on the same stall. Plus: dump triggers (on-demand API,
stall report, SIGUSR2), clock alignment, Perfetto emission, and the
recorder's own microbench.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import uuid

import pytest

from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.engine import OP_ALLREDUCE, EngineSession, bindings
from horovod_tpu.profiler import flight

from conftest import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_group(n, **kwargs):
    group = f"fr-{uuid.uuid4().hex[:8]}"
    kwargs.setdefault("cycle_time_ms", 1.0)
    kwargs.setdefault("stall_warning_sec", 60.0)
    return [EngineSession(rank=r, size=n, transport="loopback", group=group,
                          **kwargs) for r in range(n)]


def destroy_all(sessions):
    for s in sessions:
        s._lib.hvdtpu_shutdown(s._session)
    for s in sessions:
        s.destroy()


# ---------------------------------------------------------------------------
# recorder basics + on-demand dump


def test_flight_dump_records_collective_lifecycle(tmp_path):
    """A completed allreduce leaves the full ENQUEUE → NEGOTIATE → FUSE →
    EXEC → DONE lifecycle in every rank's dump; the on-demand API writes
    one file per rank."""
    sessions = make_group(2)
    try:
        handles = [s.enqueue("lifecycle", OP_ALLREDUCE, "float32", [8])
                   for s in sessions]
        for s, h in zip(sessions, handles):
            s.wait(h, timeout=10.0)
        for r, s in enumerate(sessions):
            dump = s.flight_dump(str(tmp_path))
            assert dump["rank"] == r and dump["size"] == 2
            assert dump["trigger"] == "api"
            phases = {e["phase"] for e in dump["events"]
                      if e["name"] == "lifecycle"}
            assert phases == {"ENQUEUE", "NEGOTIATE", "FUSE", "EXEC",
                              "DONE"}, phases
            done = [e for e in dump["events"]
                    if e["name"] == "lifecycle" and e["phase"] == "DONE"]
            assert done[0]["status"] == 0
            assert done[0]["bytes"] == 8 * 4
            assert (tmp_path / f"flight_rank{r}.json").exists()
        # both ranks recorded CYCLE anchors for the analyzer's alignment
        d0 = json.loads((tmp_path / "flight_rank0.json").read_text())
        assert any(e["phase"] == "CYCLE" for e in d0["events"])
        # hashes of the same tensor agree across ranks
        d1 = json.loads((tmp_path / "flight_rank1.json").read_text())

        def h(d):
            return {e["hash"] for e in d["events"]
                    if e["name"] == "lifecycle"}
        assert h(d0) == h(d1) and len(h(d0)) == 1
    finally:
        destroy_all(sessions)


def test_recorder_disabled_by_size_zero(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT_RECORDER_SIZE", "0")
    sessions = make_group(2, )
    try:
        handles = [s.enqueue("off", OP_ALLREDUCE, "float32", [4])
                   for s in sessions]
        for s, h in zip(sessions, handles):
            s.wait(h, timeout=10.0)
        dump = sessions[0].flight_dump()
        assert dump["capacity"] == 0 and dump["events"] == []
    finally:
        destroy_all(sessions)


def test_bench_flight_record_microbench():
    on = bindings.bench_flight_record(50_000)
    off = bindings.bench_flight_record(50_000, enabled=False)
    assert on > 0.0 and off >= 0.0
    # only sanity: a record costs well under a microsecond on any
    # plausible machine
    assert on < 25_000.0, f"Record() costs {on:.0f}ns?!"


# ---------------------------------------------------------------------------
# desync detection (acceptance b)


def test_shape_mismatch_names_rank_and_signatures():
    """Rank 1 submits a different shape for the same tensor: both ranks
    fail within one coordination cycle with an error naming the offending
    rank and BOTH signature hashes — instead of hanging or reducing
    garbage."""
    sessions = make_group(2)
    try:
        h0 = sessions[0].enqueue("mismatch", OP_ALLREDUCE, "float32", [4])
        h1 = sessions[1].enqueue("mismatch", OP_ALLREDUCE, "float32", [8])
        t0 = time.monotonic()
        with pytest.raises(HorovodInternalError) as ei:
            sessions[0].wait(h0, timeout=10.0)
        elapsed = time.monotonic() - t0
        msg = str(ei.value)
        assert "Mismatched" in msg and "mismatch" in msg
        assert "rank 0" in msg and "rank 1" in msg
        assert "signatures:" in msg and "0x" in msg, msg
        # the two signatures differ in the message
        import re
        sigs = re.findall(r"0x([0-9a-f]{16})", msg)
        assert len(sigs) == 2 and sigs[0] != sigs[1], msg
        assert elapsed < 5.0, f"desync took {elapsed:.1f}s to surface"
        with pytest.raises(HorovodInternalError, match="signatures:"):
            sessions[1].wait(h1, timeout=10.0)
        # the rejection is black-boxed as a DESYNC event on both ranks
        for s in sessions:
            dump = s.flight_dump()
            assert any(e["phase"] == "DESYNC" and e["name"] == "mismatch"
                       for e in dump["events"]), dump["events"][-5:]
        # ...and the session survives (ERROR response, not an abort)
        ok = [s.enqueue("after", OP_ALLREDUCE, "float32", [4])
              for s in sessions]
        for s, h in zip(sessions, ok):
            s.wait(h, timeout=10.0)
    finally:
        destroy_all(sessions)


def test_analyzer_flags_cross_rank_signature_mismatch(tmp_path):
    """The analyzer independently cross-checks the per-rank signatures
    (ENQUEUE events carry them), so a desync is visible even in dumps
    from a hung job that never produced the ERROR response."""
    sessions = make_group(2)
    try:
        sessions[0].enqueue("sig", OP_ALLREDUCE, "float32", [4])
        sessions[1].enqueue("sig", OP_ALLREDUCE, "int32", [4])
        # don't wait for the error — dump immediately (the hung-job shape)
        for s in sessions:
            s.flight_dump(str(tmp_path))
        verdict = flight.analyze(flight.load_dumps(tmp_path))
        assert verdict["desync"], verdict
        mism = verdict["desync"][0]
        assert mism["tensor"] == "sig"
        if "signatures" in mism:
            assert mism["signatures"][0] != mism["signatures"][1]
        assert any("sig" in line for line in verdict["lines"])
    finally:
        destroy_all(sessions)


# ---------------------------------------------------------------------------
# stall ↔ flight-recorder agreement (satellite) + the stall dump trigger


def test_stall_report_agrees_with_flight_dump(tmp_path, monkeypatch):
    """The same injected stall (rank 3 withholds a tensor the others
    submitted) seen by both systems: hvd.stall_report() names the missing
    rank, and the flight dumps show the tensor in flight on exactly the
    ranks the report lists as ready — with the stall itself triggering
    the automatic dump to HOROVOD_FLIGHT_DIR."""
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", str(tmp_path))
    n = 4
    sessions = make_group(n, stall_warning_sec=0.3)
    try:
        handles = [s.enqueue("withheld", OP_ALLREDUCE, "float32", [4])
                   for s in sessions[:3]]
        # the stall scan fires on the coordinator, the report is broadcast,
        # and every rank auto-dumps on observing it
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all((tmp_path / f"flight_rank{r}.json").exists()
                   for r in range(n)):
                break
            time.sleep(0.05)
        report = sessions[0].stall_report()
        assert report is not None
        stalled = {e["tensor"]: e for e in report["stalled"]}
        assert stalled["withheld"]["missing"] == [3]
        assert stalled["withheld"]["ready"] == [0, 1, 2]

        dumps = flight.load_dumps(tmp_path)
        assert sorted(dumps) == [0, 1, 2, 3]
        assert dumps[0]["trigger"] == "stall"
        # agreement: ENQUEUE exists exactly on the report's ready ranks
        enq = {r for r, d in dumps.items()
               if any(e["name"] == "withheld" and e["phase"] == "ENQUEUE"
                      for e in d["events"])}
        assert enq == set(stalled["withheld"]["ready"])
        verdict = flight.analyze(dumps)
        inflight = {i["tensor"]: i for i in verdict["in_flight"]}
        assert "withheld" in inflight
        assert inflight["withheld"]["ranks_waiting"] == [0, 1, 2]
        assert inflight["withheld"]["ranks_without_it"] == [3]

        # unblock and finish clean
        handles.append(sessions[3].enqueue("withheld", OP_ALLREDUCE,
                                           "float32", [4]))
        for s, h in zip(sessions[:3] + sessions[3:], handles):
            s.wait(h, timeout=10.0)
    finally:
        destroy_all(sessions)


def test_sigusr2_triggers_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", str(tmp_path))
    sessions = make_group(2)
    try:
        handles = [s.enqueue("sig2", OP_ALLREDUCE, "float32", [4])
                   for s in sessions]
        for s, h in zip(sessions, handles):
            s.wait(h, timeout=10.0)
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if all((tmp_path / f"flight_rank{r}.json").exists()
                   for r in range(2)):
                break
            time.sleep(0.05)
        dumps = flight.load_dumps(tmp_path)
        assert sorted(dumps) == [0, 1]
        assert dumps[0]["trigger"] == "sigusr2"
    finally:
        destroy_all(sessions)


# ---------------------------------------------------------------------------
# analyzer unit coverage: clock alignment + perfetto emission


def _mk_dump(rank, size, events, origin_us=0):
    return {"rank": rank, "size": size, "capacity": 64,
            "origin_unix_us": origin_us, "trigger": "api", "reason": "",
            "dump_unix_us": time.time() * 1e6,  # fresh for driver filter
            "events": events}


def _ev(i, ts, phase, name="t", cycle=-1, status=0, aux=0):
    return {"i": i, "ts_us": ts, "phase": phase, "name": name,
            "hash": "00", "cycle": cycle, "op": 0, "dtype": 7, "bytes": 4,
            "status": status, "aux": aux}


def test_align_clocks_uses_cycle_anchors():
    """Rank 1's steady clock started 5s later; the shared cycle anchors
    recover the offset exactly (origins deliberately lie)."""
    d0 = _mk_dump(0, 2, [_ev(0, 1000, "CYCLE", name="", cycle=1),
                         _ev(1, 2000, "CYCLE", name="", cycle=2),
                         _ev(2, 3000, "CYCLE", name="", cycle=3)])
    d1 = _mk_dump(1, 2, [_ev(0, 1000 - 5_000_000, "CYCLE", name="",
                             cycle=1),
                         _ev(1, 2000 - 5_000_000, "CYCLE", name="",
                             cycle=2),
                         _ev(2, 3000 - 5_000_000, "CYCLE", name="",
                             cycle=3)])
    offsets = flight.align_clocks({0: d0, 1: d1})
    assert offsets[0] == 0.0
    assert offsets[1] == pytest.approx(5_000_000, abs=1)


def test_analyzer_names_dead_rank_and_in_flight_tensor_synthetic():
    d0 = _mk_dump(0, 3, [_ev(0, 10, "ENQUEUE", "grad"),
                         _ev(1, 20, "NEGOTIATE", "grad")])
    d1 = _mk_dump(1, 3, [_ev(0, 11, "ENQUEUE", "grad")])
    verdict = flight.analyze({0: d0, 1: d1})
    assert verdict["dead_ranks"] == [2]
    assert verdict["in_flight"][0]["tensor"] == "grad"
    assert 2 in verdict["in_flight"][0]["ranks_without_it"]
    text = "\n".join(verdict["lines"])
    assert "[2]" in text and "grad" in text


def test_rejected_duplicate_submit_does_not_read_as_pending():
    """A synchronously rejected duplicate submit opens and closes (DONE,
    rank-local cycle -1) while the original is still in flight — the
    duplicate's terminal event must not orphan the original, and the
    verdict must not call the tensor forever-pending."""
    d0 = _mk_dump(0, 1, [
        _ev(0, 10, "ENQUEUE", "grad"),
        _ev(1, 11, "ENQUEUE", "grad"),         # duplicate submit
        _ev(2, 12, "DONE", "grad", status=3),  # rejected, cycle=-1
        _ev(3, 20, "NEGOTIATE", "grad"),
        _ev(4, 30, "FUSE", "grad", cycle=5),
        _ev(5, 31, "EXEC", "grad", cycle=5),
        _ev(6, 40, "DONE", "grad", cycle=5),   # original completes
    ])
    verdict = flight.analyze({0: d0})
    assert not any(i["ranks_waiting"] for i in verdict["in_flight"]), verdict


def test_perfetto_emission(tmp_path):
    d0 = _mk_dump(0, 1, [_ev(0, 10, "ENQUEUE", "g"),
                         _ev(1, 20, "NEGOTIATE", "g"),
                         _ev(2, 30, "FUSE", "g"),
                         _ev(3, 31, "EXEC", "g"),
                         _ev(4, 40, "DONE", "g")])
    out = tmp_path / "trace.json"
    trace = flight.to_perfetto({0: d0}, out_path=str(out))
    assert out.exists()
    names = [e.get("name") for e in trace["traceEvents"]]
    assert "QUEUE" in names and "EXEC" in names
    # lane metadata names the rank's process group
    assert any(e.get("ph") == "M" and
               e.get("args", {}).get("name") == "hvd flight rank 0"
               for e in trace["traceEvents"])


# ---------------------------------------------------------------------------
# elastic driver collects survivor dumps and logs the verdict


def test_elastic_driver_collects_dumps_on_worker_failure(tmp_path):
    """On a worker failure with HOROVOD_FLIGHT_DIR set, the driver runs
    the analyzer over the survivors' dumps and keeps/logs the verdict —
    driven through the real _collect_flight_dumps hook, no processes."""
    from horovod_tpu.runner.elastic.discovery import FixedHostDiscovery
    from horovod_tpu.runner.elastic.driver import ElasticDriver

    for r in (0, 1):  # survivors of a 3-rank job; rank 2 died
        (tmp_path / f"flight_rank{r}.json").write_text(json.dumps(
            _mk_dump(r, 3, [_ev(0, 10 + r, "ENQUEUE", "grad")])))
    driver = ElasticDriver(
        FixedHostDiscovery({"localhost": 3}), min_np=3, max_np=3,
        command=["true"],
        extra_env={"HOROVOD_FLIGHT_DIR": str(tmp_path)})
    try:
        driver._collect_flight_dumps([(("localhost", 2), 137)])
        assert len(driver.flight_verdicts) == 1
        verdict = driver.flight_verdicts[0]
        assert verdict["dead_ranks"] == [2]
        text = "\n".join(verdict["lines"])
        assert "grad" in text and "[2]" in text
    finally:
        driver._kv.stop()


# ---------------------------------------------------------------------------
# acceptance (a): injected peer death → survivor dumps + analyzer verdict


DEATH_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import EngineSession, OP_ALLREDUCE, bindings
    from horovod_tpu.common.exceptions import HorovodInternalError

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=30.0)
    lib = bindings.load_library()

    def cb(resp):
        buf = np.ones(4, np.float32)
        return lib.hvdtpu_data_allreduce(
            s._session, buf.ctypes.data, 4,
            bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)

    s.set_execute_callback(cb)
    # rank 2's injector kills the process mid-send of its third data
    # frame (HOROVOD_FAULT_SPEC data.send:die@frame=2) — steps 0/1
    # complete, step2 is the in-flight collective at death
    for step in range(5):
        h = s.enqueue(f"step{{step}}", OP_ALLREDUCE, "float32", [4])
        try:
            s.wait(h, timeout=25.0)
        except HorovodInternalError:
            break
    s.destroy()
    print(f"flight worker {{rank}} done", flush=True)
""")


def test_peer_death_writes_survivor_dumps_and_analyzer_names_it(tmp_path):
    """Acceptance (a): rank 2 dies mid-collective (HOROVOD_FAULT_SPEC);
    every SURVIVING rank writes a flight dump on the abort, and the
    analyzer names the dead rank and the in-flight tensor."""
    size = 3
    port = free_port()
    flight_dir = tmp_path / "dumps"
    flight_dir.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(DEATH_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_FLIGHT_DIR=str(flight_dir),
                   HOROVOD_CYCLE_TIME="5")
        if r == 2:
            env["HOROVOD_FAULT_SPEC"] = "data.send:die@frame=2"
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=180)[0].decode() for p in procs]
    assert procs[2].returncode == 137, f"rank 2 did not die:\n{outs[2]}"
    for r in (0, 1):
        assert procs[r].returncode == 0, f"rank {r} failed:\n{outs[r]}"
        path = flight_dir / f"flight_rank{r}.json"
        assert path.exists(), \
            f"survivor {r} wrote no dump; contents: " \
            f"{os.listdir(flight_dir)}\n{outs[r]}"
        dump = json.loads(path.read_text())
        assert dump["trigger"] == "abort"
    assert not (flight_dir / "flight_rank2.json").exists()

    dumps = flight.load_dumps(flight_dir)
    verdict = flight.analyze(dumps)
    assert verdict["dead_ranks"] == [2]
    problem = {i["tensor"] for i in verdict["in_flight"]}
    assert "step2" in problem, verdict
    text = "\n".join(verdict["lines"])
    assert "step2" in text and "[2]" in text

    # the CLI prints the same verdict (console entry point's target)
    cli = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.profiler.flight",
         str(flight_dir)],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, PYTHONPATH=REPO))
    assert cli.returncode == 0, cli.stderr
    assert "step2" in cli.stdout and "[2]" in cli.stdout
