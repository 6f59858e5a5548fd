"""Fault-injection and fast-abort recovery tests.

The ISSUE-4 acceptance matrix: (a) an injected peer death mid-collective
fails every survivor within a bounded wall clock (fast abort), never the
30s controller timeout; (b) an injected corrupt frame is caught by the
CRC32C framing check and surfaces Status::Corrupted with the tensor name;
(c) a connect storm is absorbed by bounded exponential-backoff retries;
plus the wait-timeout handle contract and the fault-spec grammar itself.
All injection is seeded/deterministic via HOROVOD_FAULT_SPEC — no
sleeps-as-synchronization.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time
import uuid

import numpy as np
import pytest

from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    WaitTimeout,
)
from horovod_tpu.engine import OP_ALLREDUCE, EngineSession, bindings

from conftest import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_group(n, **kwargs):
    group = f"ft-{uuid.uuid4().hex[:8]}"
    kwargs.setdefault("cycle_time_ms", 1.0)
    kwargs.setdefault("stall_warning_sec", 60.0)
    return [EngineSession(rank=r, size=n, transport="loopback", group=group,
                          **kwargs) for r in range(n)]


def destroy_all(sessions):
    for s in sessions:
        s._lib.hvdtpu_shutdown(s._session)
    for s in sessions:
        s.destroy()


@pytest.fixture(autouse=True)
def _clear_fault_spec():
    """Injection state is process-global; never leak a spec across tests."""
    yield
    bindings.set_fault_spec("")


# ---------------------------------------------------------------------------
# fault-spec grammar


def test_fault_spec_grammar():
    # the ISSUE's own example must parse
    bindings.set_fault_spec(
        "ring_send:drop@frame=7;recv:delay_ms=500@prob=0.1;"
        "frame:corrupt@frame=12")
    # channel scoping, rank conditions, counts
    bindings.set_fault_spec(
        "data.send:corrupt@frame=0,rank=1;control.connect:fail@count=3")
    bindings.set_fault_spec("")  # empty disables


@pytest.mark.parametrize("bad", [
    "nonsense",
    "send:explode",
    "send:drop@frame=x",
    "bogus_point:drop",
    "send:delay_ms=-5",
    "send:drop@prob=1.5",
])
def test_fault_spec_rejects_malformed(bad):
    with pytest.raises(ValueError, match="HOROVOD_FAULT_SPEC"):
        bindings.set_fault_spec(bad)


def test_malformed_env_spec_refuses_session(monkeypatch):
    """A session must refuse to start on a bad spec — silently running a
    chaos test with no chaos is the worst failure mode."""
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "send:explode")
    with pytest.raises(HorovodInternalError, match="HOROVOD_FAULT_SPEC"):
        EngineSession(rank=0, size=1, transport="loopback",
                      group=f"bad-{uuid.uuid4().hex[:6]}")


# ---------------------------------------------------------------------------
# fast abort (in-process)


def test_abort_fails_stalled_collective_fast():
    """hvdtpu_abort on one rank fails a *stalled* collective on another
    rank within one coordination cycle — not after the 30s controller
    timeout (the loopback default)."""
    sessions = make_group(4)
    try:
        # only rank 0 submits: without the abort this would hang forever
        h = sessions[0].enqueue("stalled", OP_ALLREDUCE, "float32", [4])
        t0 = time.monotonic()
        sessions[2].abort("deliberate chaos")
        with pytest.raises(HorovodInternalError, match="deliberate chaos"):
            sessions[0].wait(h, timeout=20.0)
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, f"abort took {elapsed:.1f}s — not fast"
        # the abort is observable in the metrics of both the aborter and
        # the ranks it tore down
        assert sessions[2].metrics()["counters"]["aborts"] >= 1
        assert sessions[0].metrics()["counters"]["aborts"] >= 1
        assert not sessions[0].healthy
    finally:
        for s in sessions:
            s.destroy()


def test_data_plane_failure_aborts_peers():
    """A data-plane failure on ONE rank (its callback fails) tears the
    whole session down: peers whose callbacks succeeded still learn of the
    failure via the abort flag instead of deadlocking on the next op."""
    sessions = make_group(3)
    try:
        def make_cb(rank):
            def cb(resp):
                return 3 if rank == 1 else 0
            return cb

        for r, s in enumerate(sessions):
            s.set_execute_callback(make_cb(r))
        handles = [s.enqueue("dp", OP_ALLREDUCE, "float32", [4])
                   for s in sessions]
        # rank 1's own handle carries the data-plane error with tensor name
        with pytest.raises(HorovodInternalError, match=r"dp"):
            sessions[1].wait(handles[1], timeout=10.0)
        # every rank becomes unhealthy within a few cycles (poll, no sleep
        # synchronization)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and any(
                s.healthy for s in sessions):
            time.sleep(0.01)
        assert not any(s.healthy for s in sessions)
    finally:
        for s in sessions:
            s.destroy()


def test_loopback_injected_drop_unblocks_both_ranks():
    """An injected data-plane drop on rank 1 fails rank 1 with the
    injection Status AND unblocks rank 0 (hub abort = closed-socket
    analog), with the injection visible in engine metrics."""
    bindings.set_fault_spec("data.send:drop@frame=0,rank=1")
    sessions = make_group(2)
    lib = bindings.load_library()
    try:
        rcs = {}

        def run(r):
            buf = np.ones(8, np.float32)
            rcs[r] = lib.hvdtpu_data_allreduce(
                sessions[r]._session, buf.ctypes.data, 8,
                bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)

        threads = [threading.Thread(target=run, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
        assert rcs == {0: 3, 1: 3}, rcs  # ABORTED on both
        assert sessions[1].metrics()["counters"]["faults_injected"] >= 1
    finally:
        bindings.set_fault_spec("")
        for s in sessions:
            s.destroy()


# ---------------------------------------------------------------------------
# Session.wait timeout contract (satellite)


def test_wait_timeout_leaves_handle_pending():
    """WaitTimeout is not a failure: the op stays in flight, the handle
    stays live, and a later wait on the SAME handle succeeds once the
    stragglers arrive."""
    sessions = make_group(3)
    try:
        h0 = sessions[0].enqueue("late", OP_ALLREDUCE, "float32", [4])
        with pytest.raises(WaitTimeout):
            sessions[0].wait(h0, timeout=0.2)
        # handle is still pollable (a dead handle would error)
        done, err = sessions[0].poll(h0)
        assert not done and err == ""
        # the stragglers submit; the same handle now completes
        others = [s.enqueue("late", OP_ALLREDUCE, "float32", [4])
                  for s in sessions[1:]]
        sessions[0].wait(h0, timeout=10.0)
        for s, h in zip(sessions[1:], others):
            s.wait(h, timeout=10.0)
        # session unharmed: the timeout must not have aborted anything
        assert all(s.healthy for s in sessions)
        hs = [s.enqueue("after", OP_ALLREDUCE, "float32", [4])
              for s in sessions]
        for s, h in zip(sessions, hs):
            s.wait(h, timeout=10.0)
    finally:
        destroy_all(sessions)


# ---------------------------------------------------------------------------
# connect backoff


def test_connect_retries_exhausted_fails_fast(monkeypatch):
    """Bounded retries: with nothing listening and
    HOROVOD_CONNECT_RETRIES=3 the session fails after 3 attempts with a
    clear message, instead of spinning to the full timeout."""
    monkeypatch.setenv("HOROVOD_CONNECT_RETRIES", "3")
    monkeypatch.setenv("HOROVOD_CONNECT_BACKOFF_MS", "5")
    t0 = time.monotonic()
    with pytest.raises(HorovodInternalError,
                       match="exhausted 3 connect attempts"):
        EngineSession(rank=1, size=2, transport="tcp", addr="127.0.0.1",
                      port=free_port(), timeout_sec=30.0)
    assert time.monotonic() - t0 < 10.0


STORM_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    from horovod_tpu.engine import EngineSession, OP_ALLREDUCE

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=60.0)
    h = s.enqueue("storm", OP_ALLREDUCE, "float32", [8])
    s.wait(h, timeout=30.0)
    c = s.metrics()["counters"]
    if rank == 1:
        # the injector failed the first 3 connect attempts; backoff
        # retries absorbed the storm and the job still came up
        assert c["connect_retries"] >= 3, c
        assert c["faults_injected"] >= 3, c
    s.shutdown()
    print(f"storm worker {{rank}} OK")
""")


def test_connect_storm_backoff_recovers(tmp_path):
    """Acceptance (c): N injected connect failures, then backoff retries
    succeed — the job comes up and the retry count is observable."""
    size = 2
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(STORM_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_CONNECT_BACKOFF_MS="5")
        if r == 1:
            env["HOROVOD_FAULT_SPEC"] = "connect:fail@count=3"
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"storm worker {r} OK" in out


# ---------------------------------------------------------------------------
# peer death mid-collective → fast abort (acceptance a)


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

    # steps 0 and 1 succeed on every rank; rank 2's injector kills the
    # process mid-send of its THIRD data frame (HOROVOD_FAULT_SPEC
    # data.send:die@frame=2) — a real death in the middle of step 2
    for step in range(5):
        h = s.enqueue(f"step{{step}}", OP_ALLREDUCE, "float32", [4])
        t0 = time.monotonic()
        try:
            s.wait(h, timeout=29.0)
            assert step < 2 or rank == 2, f"step {{step}} should have failed"
        except HorovodInternalError as e:
            elapsed = time.monotonic() - t0
            assert step >= 2, (step, e)
            # fast abort: bounded wall clock, nowhere near the 30s
            # controller timeout
            assert elapsed < 10.0, f"took {{elapsed:.1f}}s: {{e}}"
            print(f"survivor rank={{rank}} failed step {{step}} in "
                  f"{{elapsed:.2f}}s: OK", flush=True)
            break
    else:
        raise AssertionError("never saw the failure")
    assert s.metrics()["counters"]["aborts"] >= 1
    print(f"death worker {{rank}} OK", flush=True)
""")


def test_peer_death_mid_collective_fast_abort(tmp_path):
    """Acceptance (a): rank 2 dies mid-collective (injected, exact frame);
    every survivor raises HorovodInternalError in bounded wall clock —
    fast abort, not the 30s timeout."""
    size = 3
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(DEATH_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
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
        assert f"death worker {r} OK" in outs[r]
        assert f"survivor rank={r}" in outs[r]


# ---------------------------------------------------------------------------
# corrupt frame → CRC detection (acceptance b)


CRC_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import EngineSession, OP_ALLREDUCE, bindings
    from horovod_tpu.common.exceptions import (
        HorovodCorruptedError, HorovodInternalError)

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
    # rank 1's first data frame is sent with a deliberately broken CRC
    h = s.enqueue("crc_tensor", OP_ALLREDUCE, "float32", [4])
    try:
        s.wait(h, timeout=25.0)
        raise AssertionError("corruption not detected")
    except HorovodCorruptedError as e:
        # the receiving rank pins the strong contract: Status::Corrupted
        # (its own exception class), CRC named, tensor named
        assert rank == 0, f"unexpected detector rank {{rank}}: {{e}}"
        assert "CRC32C" in str(e), e
        assert "crc_tensor" in str(e), e
        assert s.metrics()["counters"]["crc_failures"] >= 1
        print(f"crc worker {{rank}} DETECTED", flush=True)
    except HorovodInternalError as e:
        # peers are torn down by the fast abort
        assert rank != 0, e
        print(f"crc worker {{rank}} aborted: OK", flush=True)
    print(f"crc worker {{rank}} OK", flush=True)
""")


def test_corrupt_frame_detected_by_crc(tmp_path):
    """Acceptance (b): an injected corrupt frame is rejected by the CRC32C
    framing check and surfaces Status::Corrupted carrying the tensor name;
    the other rank is released by the fast abort."""
    size = 2
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(CRC_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port))
        if r == 1:
            env["HOROVOD_FAULT_SPEC"] = "data.send:corrupt@frame=0"
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"crc worker {r} OK" in out
    assert "crc worker 0 DETECTED" in outs[0]


# ---------------------------------------------------------------------------
# TSan build (CI/tooling satellite) — slow, not in the tier-1 shard


@pytest.mark.slow
def test_tsan_allreduce_loop_no_races():
    """4-rank allreduce loop + concurrent metrics polling + a mid-flight
    abort, under the -fsanitize=thread build (pure-C++ harness so every
    frame is instrumented): the engine's relaxed-atomic metrics and the new
    abort flag must be clean under TSan, not just code review."""
    engine_dir = os.path.join(REPO, "horovod_tpu", "engine")
    build = subprocess.run(["make", "-C", engine_dir, "tsan"],
                           capture_output=True, text=True)
    assert build.returncode == 0, build.stdout + build.stderr
    env = dict(os.environ, TSAN_OPTIONS="exitcode=66 halt_on_error=0")
    proc = subprocess.run(
        [os.path.join(engine_dir, "build-tsan", "tsan_harness")], env=env,
        capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert "WARNING: ThreadSanitizer" not in out, out
    assert proc.returncode == 0, out
    assert "tsan workload OK" in out
