"""Topology-aware data plane (ISSUE 14): hierarchical allreduce,
recursive-doubling small-tensor route, and cycle-fenced routing knobs.

Loopback sessions simulate multi-host grouping by passing distinct
``host_id`` values per in-process rank (the launcher's
HOROVOD_CROSS_RANK contract); the bit-exactness matrix pins
star == recursive-doubling == hierarchical for every dtype because all
three share ONE canonical reduction order (per-host partials in local
rank order, hosts folded in host-id order — data_plane.cc
CanonicalReduce). The fault legs pin the ADVICE round-5 residue class:
every new wire format validates received payload sizes before use, and a
mid-phase death fast-aborts every rank within one cycle with the tensor
named.
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

from horovod_tpu.common.eager import EagerExecutor
from horovod_tpu.common.exceptions import HorovodInternalError
from horovod_tpu.engine import EngineSession, OP_ALLREDUCE

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_all(workers, fn):
    results = [None] * len(workers)
    errors = [None] * len(workers)

    def work(r):
        try:
            results[r] = fn(r, workers[r])
        except Exception as e:  # noqa: BLE001
            errors[r] = e

    threads = [threading.Thread(target=work, args=(r,))
               for r in range(len(workers))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e
    return results


def make_group(n, host_ids=None, env=None, monkeypatch=None, **kwargs):
    """N loopback sessions with optional simulated host grouping."""
    if env:
        assert monkeypatch is not None
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    group = f"topo-{uuid.uuid4().hex[:8]}"
    kwargs.setdefault("cycle_time_ms", 1.0)
    sessions = [
        EngineSession(rank=r, size=n, transport="loopback", group=group,
                      host_id=(host_ids[r] if host_ids else None), **kwargs)
        for r in range(n)
    ]
    if env:
        for k in env:
            monkeypatch.delenv(k)
    return sessions


def destroy_all(sessions):
    for s in sessions:
        s._lib.hvdtpu_shutdown(s._session)
    for s in sessions:
        s.destroy()


def allreduce_once(sessions, arrays, name="t", timeout=30.0):
    executors = [EagerExecutor(s) for s in sessions]

    def fn(r, ex):
        h = ex.submit(name, OP_ALLREDUCE, arrays[r])
        ex.session.wait(h, timeout=timeout)
        return ex.take_result(name)

    return run_all(executors, fn)


def _data(n_ranks, num_elements, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == "int8":
        # small magnitudes: the SUM of 8 ranks must not wrap
        return [rng.integers(-10, 10, num_elements).astype(np.int8)
                for _ in range(n_ranks)]
    if dtype == "bfloat16":
        import jax.numpy as jnp
        return [np.asarray(jnp.asarray(
            rng.standard_normal(num_elements), jnp.bfloat16))
            for _ in range(n_ranks)]
    return [rng.standard_normal(num_elements).astype(dtype)
            for _ in range(n_ranks)]


# ---------------------------------------------------------------------------
# recursive-doubling small-tensor route: bit-exact vs star, engages


# ragged sizes cross the chunking edge cases (0-length chunks, remainder
# spread); 8 = power of two, 5/6 exercise the fold-in pre/post step
@pytest.mark.parametrize("n_ranks", [5, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_recursive_doubling_bit_exact_vs_star(monkeypatch, n_ranks, dtype):
    for num_elements in (1, 7, 300):
        arrays = _data(n_ranks, num_elements, dtype, seed=num_elements)
        s_star = make_group(n_ranks)
        star = allreduce_once(s_star, arrays)
        assert all(s.data_algo_ops("rd") == 0 for s in s_star)
        destroy_all(s_star)

        s_rd = make_group(n_ranks, monkeypatch=monkeypatch,
                          env={"HOROVOD_SMALL_TENSOR_ALGO": "rd"})
        rd = allreduce_once(s_rd, arrays)
        # the route engaged (payload < express-lane class = 4096 default)
        assert all(s.data_algo_ops("rd") == 1 for s in s_rd)
        destroy_all(s_rd)
        for r in range(n_ranks):
            assert star[r].tobytes() == rd[r].tobytes(), \
                f"rd != star bitwise (rank {r}, {dtype}, {num_elements})"


def test_recursive_doubling_above_lane_falls_back_to_star(monkeypatch):
    """Payloads at/above the express-lane class keep their bulk route —
    rd is the LATENCY class's algorithm only."""
    arrays = _data(4, 2048, "float32")  # 8 KiB > 4 KiB default lane
    sessions = make_group(4, monkeypatch=monkeypatch,
                          env={"HOROVOD_SMALL_TENSOR_ALGO": "rd"})
    allreduce_once(sessions, arrays)
    assert all(s.data_algo_ops("rd") == 0 for s in sessions)
    destroy_all(sessions)


# ---------------------------------------------------------------------------
# hierarchical allreduce: bit-exact vs canonical star, uneven local sizes


@pytest.mark.parametrize("host_ids", [
    [0, 0, 0, 0, 1, 1, 1, 1],     # even 4+4
    [0, 0, 0, 1, 1, 1, 1, 1],     # uneven 3+5 (the ISSUE's split)
    [0, 1, 0, 1, 0, 1, 0, 1],     # cyclic placement (non-contiguous)
    [0, 0, 0, 1, 1, 1, 2, 2],     # three hosts (non-pow2 leader count)
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_hierarchical_bit_exact_vs_star(monkeypatch, host_ids, dtype):
    n = len(host_ids)
    for num_elements in (5, 1000, 30000):
        arrays = _data(n, num_elements, dtype, seed=num_elements)
        # flat star forced (huge ring threshold) WITH the locality map:
        # the canonical host-grouped reduction order both paths share
        s_star = make_group(
            n, host_ids=host_ids, monkeypatch=monkeypatch,
            env={"HOROVOD_RING_THRESHOLD_BYTES": str(1 << 30)})
        star = allreduce_once(s_star, arrays)
        destroy_all(s_star)

        s_h = make_group(n, host_ids=host_ids, monkeypatch=monkeypatch,
                         env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"})
        hier = allreduce_once(s_h, arrays)
        # hierarchy serves the bandwidth class; payloads under the
        # express-lane boundary (4 KiB default) keep the latency route
        engaged = arrays[0].nbytes >= 4096
        assert all(s.data_algo_ops("hier") == (1 if engaged else 0)
                   for s in s_h)
        destroy_all(s_h)
        for r in range(n):
            assert star[r].tobytes() == hier[r].tobytes(), \
                f"hier != star bitwise (rank {r}, {dtype}, " \
                f"{num_elements}, hosts {host_ids})"


def test_hierarchical_leader_ring_regime_bit_exact(monkeypatch):
    """Above the ring threshold the leaders' allgather phase switches to
    the ring schedule — same canonical result."""
    host_ids = [0, 0, 1, 1, 2, 2]
    arrays = _data(6, 70000, "float32")  # 280 KB >= 64 KiB threshold
    s_star = make_group(
        6, host_ids=host_ids, monkeypatch=monkeypatch,
        env={"HOROVOD_RING_THRESHOLD_BYTES": str(1 << 30)})
    star = allreduce_once(s_star, arrays)
    destroy_all(s_star)
    s_h = make_group(6, host_ids=host_ids, monkeypatch=monkeypatch,
                     env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
                          "HOROVOD_RING_THRESHOLD_BYTES": str(64 << 10)})
    hier = allreduce_once(s_h, arrays)
    assert all(s.data_algo_ops("hier") == 1 for s in s_h)
    destroy_all(s_h)
    for r in range(6):
        assert star[r].tobytes() == hier[r].tobytes()


def test_hierarchical_without_locality_map_stays_flat(monkeypatch):
    """HOROVOD_HIERARCHICAL_ALLREDUCE without host ids must not change
    routing (no locality map -> flat plane, existing jobs untouched)."""
    arrays = _data(4, 30000, "float32")
    sessions = make_group(4, monkeypatch=monkeypatch,
                          env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"})
    out = allreduce_once(sessions, arrays)
    assert all(s.data_algo_ops("hier") == 0 for s in sessions)
    expected = np.sum(np.stack(arrays), axis=0)
    np.testing.assert_allclose(out[0], expected, rtol=1e-5)
    destroy_all(sessions)


# ---------------------------------------------------------------------------
# inter-host wire-byte accounting (the hierarchy's acceptance metric)


def _interhost_bytes(sessions):
    return sum(s.metrics()["counters"]["data_interhost_bytes"]
               for s in sessions)


def test_hierarchical_cuts_interhost_bytes_vs_flat_ring(monkeypatch):
    """8 ranks / 2 simulated hosts, 1 MiB payload: the hierarchical
    route's measured inter-host bytes vs the topology-blind flat ring's.
    Cyclic placement (ranks alternating hosts — what a topology-blind
    ring cannot avoid paying for) shows the full fan-in cut; even the
    friendly block placement still wins."""
    n, elements = 8, 1 << 18  # 1 MiB fp32
    arrays = _data(n, elements, "float32")
    cyclic = [r % 2 for r in range(n)]
    s_ring = make_group(n, host_ids=cyclic, monkeypatch=monkeypatch,
                        env={"HOROVOD_RING_THRESHOLD_BYTES": str(1 << 10)})
    allreduce_once(s_ring, arrays)
    assert all(s.data_algo_ops("ring") == 1 for s in s_ring)
    ring_inter = _interhost_bytes(s_ring)
    destroy_all(s_ring)

    s_h = make_group(n, host_ids=cyclic, monkeypatch=monkeypatch,
                     env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"})
    allreduce_once(s_h, arrays)
    hier_inter = _interhost_bytes(s_h)
    destroy_all(s_h)

    assert ring_inter > 0 and hier_inter > 0
    # acceptance bound: <= 0.30x the flat ring under cyclic placement
    assert hier_inter <= 0.30 * ring_inter, (hier_inter, ring_inter)
    # and the absolute model: leaders exchange ~2n total across hosts
    assert hier_inter <= 2.5 * elements * 4


# ---------------------------------------------------------------------------
# cycle-fenced routing knobs (TunedParams ABI 10)


def test_routing_knobs_ride_tuned_params_broadcast(monkeypatch):
    """ring_threshold / hierarchical / small_tensor_algo pushed at
    runtime land on every rank at one cycle boundary and actually change
    routing — the previously documented 'raw hvdtpu_data_* not
    cycle-fenced' limitation is gone."""
    monkeypatch.setenv("HOROVOD_TUNE", "1")
    host_ids = [0, 0, 1, 1]
    sessions = make_group(4, host_ids=host_ids)
    monkeypatch.delenv("HOROVOD_TUNE")
    try:
        arrays = _data(4, 300, "float32")
        allreduce_once(sessions, arrays, name="pre")
        assert all(s.data_algo_ops("rd") == 0 and
                   s.data_algo_ops("hier") == 0 for s in sessions)

        sessions[0].set_tuned_params(small_tensor_algo="rd",
                                     hierarchical=True)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            snaps = [s.tuned_params() for s in sessions]
            if all(sn["small_tensor_algo"] == 1 and sn["hierarchical"] == 1
                   for sn in snaps):
                break
            time.sleep(0.02)
        else:
            raise AssertionError(f"routing push never propagated: {snaps}")

        # small payload -> rd; bulk payload -> hierarchical
        allreduce_once(sessions, arrays, name="small")
        big = _data(4, 30000, "float32")
        allreduce_once(sessions, big, name="big")
        assert all(s.data_algo_ops("rd") == 1 for s in sessions)
        assert all(s.data_algo_ops("hier") == 1 for s in sessions)

        # ring threshold is tunable too: drop it under the small payload
        sessions[0].set_tuned_params(small_tensor_algo="star",
                                     hierarchical=False,
                                     ring_threshold_bytes=256)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if all(s.tuned_params()["ring_threshold_bytes"] == 256
                   for s in sessions):
                break
            time.sleep(0.02)
        rings_before = [s.data_algo_ops("ring") for s in sessions]
        allreduce_once(sessions, arrays, name="post")
        assert all(s.data_algo_ops("ring") == b + 1
                   for s, b in zip(sessions, rings_before))
    finally:
        destroy_all(sessions)


def test_routing_push_refused_without_sync(monkeypatch):
    """Multi-rank routing pushes without the standing broadcast channel
    must refuse loudly — a silently rank-local ring threshold is exactly
    the divergence class the fence exists to prevent (see the
    tune_env_divergent_routing hvd-check mutant)."""
    monkeypatch.delenv("HOROVOD_TUNE", raising=False)
    monkeypatch.delenv("HOROVOD_AUTOTUNE", raising=False)
    sessions = make_group(2)
    try:
        with pytest.raises(HorovodInternalError, match="HOROVOD_TUNE"):
            sessions[0].set_tuned_params(ring_threshold_bytes=4096)
    finally:
        destroy_all(sessions)


def test_small_tensor_algo_env_validated(monkeypatch):
    """A typo'd HOROVOD_SMALL_TENSOR_ALGO refuses session creation
    instead of silently running star."""
    monkeypatch.setenv("HOROVOD_SMALL_TENSOR_ALGO", "ringdouble")
    with pytest.raises(HorovodInternalError,
                       match="SMALL_TENSOR_ALGO"):
        make_group(1)


# ---------------------------------------------------------------------------
# wire-format validation (ADVICE round-5 residue class): one negative
# test per new exchange format — a truncated payload must fail the op
# with the size named, never hand the reducer garbage


def _expect_wire_failure(monkeypatch, env, host_ids, num_elements,
                         match):
    """Engine-path negative leg: the poisoned exchange must fail the op
    on every rank (the detecting rank's validation error fast-aborts the
    rest — nobody consumes the short buffer), with the tensor AND the
    size-validation specifics named in at least one rank's error."""
    n = len(host_ids) if host_ids else 4
    sessions = make_group(n, host_ids=host_ids, monkeypatch=monkeypatch,
                          env=env)
    executors = [EagerExecutor(s) for s in sessions]
    arrays = _data(n, num_elements, "float32")

    def fn(r, ex):
        h = ex.submit("poisoned", OP_ALLREDUCE, arrays[r])
        try:
            ex.session.wait(h, timeout=20.0)
            return None
        except HorovodInternalError as e:
            return str(e)

    errs = run_all(executors, fn)
    destroy_all(sessions)
    assert all(errs), f"some rank consumed the poisoned payload: {errs}"
    assert any(match in e for e in errs), errs
    assert any("poisoned" in e for e in errs), errs


def test_rd_bundle_truncation_detected(monkeypatch):
    _expect_wire_failure(
        monkeypatch,
        env={"HOROVOD_SMALL_TENSOR_ALGO": "rd",
             "HOROVOD_DATA_FAULT_INJECT": "truncate_rd_bundle"},
        host_ids=None, num_elements=64,
        match="size mismatch")


def test_hier_chunk_truncation_detected(monkeypatch):
    _expect_wire_failure(
        monkeypatch,
        env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
             "HOROVOD_DATA_FAULT_INJECT": "truncate_hier_chunk"},
        host_ids=[0, 0, 1, 1], num_elements=30000,
        match="size mismatch")


def test_hier_allgather_bundle_truncation_detected(monkeypatch):
    _expect_wire_failure(
        monkeypatch,
        env={"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
             "HOROVOD_DATA_FAULT_INJECT": "truncate_hier_allgather"},
        host_ids=[0, 0, 1, 1], num_elements=30000,
        match="bundle corrupt entry")


# ---------------------------------------------------------------------------
# fault legs: death mid-phase fast-aborts every rank within one cycle


FAULT_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import EngineSession, OP_ALLREDUCE, bindings
    from horovod_tpu.common.exceptions import HorovodInternalError

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    host_id = int(os.environ["SIM_HOST_ID"])
    elements = int(os.environ["SIM_ELEMENTS"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, host_id=host_id,
                      timeout_sec=30.0)
    lib = bindings.load_library()

    def cb(resp):
        buf = np.ones(elements, np.float32)
        return lib.hvdtpu_data_allreduce(
            s._session, buf.ctypes.data, elements,
            bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)

    s.set_execute_callback(cb)
    h = s.enqueue("doomed_tensor", OP_ALLREDUCE, "float32", [elements])
    t0 = time.monotonic()
    try:
        s.wait(h, timeout=29.0)
        raise AssertionError("collective should have failed")
    except HorovodInternalError as e:
        elapsed = time.monotonic() - t0
        # fast abort: bounded wall clock, nowhere near the 30s timeout,
        # and the doomed tensor is named in the failure
        assert elapsed < 10.0, f"took {{elapsed:.1f}}s: {{e}}"
        assert "doomed_tensor" in str(e), e
        print(f"survivor rank={{rank}} aborted in {{elapsed:.2f}}s OK",
              flush=True)
""")


def _run_fault_leg(tmp_path, extra_env, dead_rank, fault_spec,
                   elements):
    import socket
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    size = 4
    script = tmp_path / "worker.py"
    script.write_text(FAULT_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_CYCLE_TIME="5",
                   SIM_HOST_ID=str(r // 2), SIM_ELEMENTS=str(elements),
                   **extra_env)
        if r == dead_rank:
            env["HOROVOD_FAULT_SPEC"] = fault_spec
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=180)[0].decode() for p in procs]
    assert procs[dead_rank].returncode == 137, \
        f"rank {dead_rank} did not die:\n{outs[dead_rank]}"
    for r in range(size):
        if r == dead_rank:
            continue
        assert procs[r].returncode == 0, f"rank {r} failed:\n{outs[r]}"
        assert f"survivor rank={r} aborted" in outs[r], outs[r]


def test_die_mid_hierarchical_phase_fast_aborts(tmp_path):
    """die@frame on the pairwise mesh mid-hierarchical-phase: every
    surviving rank fails the collective within bounded wall clock (one
    cycle + abort fan-out, not the 30s transport timeout) with the
    tensor named."""
    _run_fault_leg(tmp_path,
                   {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"},
                   dead_rank=2, fault_spec="data.peer_send:die@frame=1",
                   elements=30000)


def test_die_mid_doubling_step_fast_aborts(tmp_path):
    """die@frame mid-doubling-step: same fast-abort contract on the
    latency route. frame=0 puts the death inside the first distance-1
    exchange, so the dist-2 partners are left waiting on a peer that
    will never connect — the accept loop's abort-frame polling is what
    bounds them."""
    _run_fault_leg(tmp_path,
                   {"HOROVOD_SMALL_TENSOR_ALGO": "rd"},
                   dead_rank=1, fault_spec="data.peer_send:die@frame=0",
                   elements=64)
