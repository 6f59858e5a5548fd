"""Multi-process engine coordination over the TCP transport — the analog of
the reference's real-multi-process parallel tests (SURVEY §4: multiple
processes on one machine, env-var rank injection)."""

import os
import subprocess
import sys
import textwrap

import pytest

from conftest import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {repo!r})
    from horovod_tpu.engine import EngineSession, OP_ALLREDUCE, OP_ALLGATHER
    from horovod_tpu.common.exceptions import HorovodInternalError

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=20.0)
    seen = []
    s.set_execute_callback(lambda r: (seen.append(r), 0)[1])

    # out-of-order submission across processes
    names = [f"t{{i}}" for i in range(4)]
    order = names[rank:] + names[:rank]
    handles = [s.enqueue(n, OP_ALLREDUCE, "float32", [8]) for n in order]
    for h in handles:
        s.wait(h, timeout=20.0)

    # allgather with per-rank sizes
    h = s.enqueue("ag", OP_ALLGATHER, "float32", [rank + 1, 2])
    s.wait(h, timeout=20.0)
    sizes = [r["sizes"] for r in seen if r["type"] == "ALLGATHER"]
    assert sizes and sizes[0] == [1, 2, 3], sizes

    # mismatch detection across processes
    shape = [4] if rank != 1 else [5]
    h = s.enqueue("bad", OP_ALLREDUCE, "float32", shape)
    try:
        s.wait(h, timeout=20.0)
        raise AssertionError("mismatch not detected")
    except HorovodInternalError as e:
        assert "ismatch" in str(e), e

    s.shutdown()
    print(f"worker {{rank}} OK")
""")


def test_tcp_three_process_coordination(tmp_path):
    size = 3
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port))
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=90)
        outs.append(out.decode())
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"worker {r} OK" in out


RING_WORKER = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import bindings
    from horovod_tpu.engine.bindings import EngineSession

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=60.0)
    lib = bindings.load_library()

    # large allreduce: forced onto the ring (threshold lowered via env)
    n = 1 << 22  # 16 MB of float32
    buf = np.full(n, float(rank + 1), np.float32)
    rc = lib.hvdtpu_data_allreduce(s._session, buf.ctypes.data, n,
                                   bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)
    assert rc == 0, rc
    assert np.allclose(buf, sum(range(1, size + 1))), buf[:4]

    # uneven element count (pad-free chunking) + MAX kind
    n2 = 4099
    buf2 = np.arange(n2, dtype=np.float32) + 1000.0 * rank
    rc = lib.hvdtpu_data_allreduce(s._session, buf2.ctypes.data, n2,
                                   bindings.DTYPE_IDS["float32"], 3, 1.0, 1.0)
    assert rc == 0, rc
    assert np.allclose(buf2, np.arange(n2) + 1000.0 * (size - 1)), buf2[:4]

    # large bcast from a non-zero root rides the pipelined ring
    buf3 = np.full(1 << 20, float(rank), np.float32)
    rc = lib.hvdtpu_data_bcast(s._session, buf3.ctypes.data, buf3.nbytes, 2)
    assert rc == 0, rc
    assert np.allclose(buf3, 2.0), buf3[:4]

    assert s.data_ring_ops() == 3, s.data_ring_ops()
    s.shutdown()
    print(f"ring worker {{rank}} OK")
""")


GATHER_WORKER = textwrap.dedent("""
    import ctypes, os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import bindings
    from horovod_tpu.engine.bindings import EngineSession

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=60.0)
    lib = bindings.load_library()

    # variable-size allgatherv, large enough for the ring: no rank-0 relay
    n = (rank + 1) * 1024
    buf = np.full(n, float(rank), np.float32)
    rank_bytes = (ctypes.c_int64 * size)()
    total = lib.hvdtpu_data_allgatherv(s._session, buf.ctypes.data,
                                       buf.nbytes, rank_bytes)
    assert total == sum((r + 1) * 4096 for r in range(size)), total
    assert list(rank_bytes) == [(r + 1) * 4096 for r in range(size)]
    out = np.empty(total // 4, np.float32)
    lib.hvdtpu_data_fetch(s._session, out.ctypes.data, total)
    off = 0
    for r in range(size):
        cnt = (r + 1) * 1024
        assert np.all(out[off:off + cnt] == float(r)), (r, out[off:off + 4])
        off += cnt
    assert s.data_ring_ops() == 1, s.data_ring_ops()

    # variable-split alltoallv on the ring: chunk (src -> dst) has value
    # src*10+dst and per-dst length (dst+1)*256 floats
    sends = [(d + 1) * 256 for d in range(size)]
    data = np.concatenate([np.full((d + 1) * 256, rank * 10 + d, np.float32)
                           for d in range(size)])
    send_b = (ctypes.c_int64 * size)(*[c * 4 for c in sends])
    recv_b = (ctypes.c_int64 * size)()
    total = lib.hvdtpu_data_alltoallv(s._session, data.ctypes.data, send_b,
                                      size, recv_b)
    assert total == size * (rank + 1) * 1024, total
    assert list(recv_b) == [(rank + 1) * 1024] * size
    out = np.empty(total // 4, np.float32)
    lib.hvdtpu_data_fetch(s._session, out.ctypes.data, total)
    off = 0
    for src in range(size):
        cnt = (rank + 1) * 256
        assert np.all(out[off:off + cnt] == float(src * 10 + rank)), src
        off += cnt
    assert s.data_ring_ops() == 2, s.data_ring_ops()

    # small payloads stay on the low-latency star (counter unchanged)
    tiny = np.full(4, float(rank), np.float32)
    total = lib.hvdtpu_data_allgatherv(s._session, tiny.ctypes.data,
                                       tiny.nbytes, rank_bytes)
    assert total == 16 * size, total
    assert s.data_ring_ops() == 2, s.data_ring_ops()

    s.shutdown()
    print(f"gather worker {{rank}} OK")
""")


def test_tcp_ring_allgatherv_alltoallv_8ranks(tmp_path):
    """Large eager allgatherv/alltoallv take ring paths at 8 ranks — rank 0
    no longer relays O(world*bytes) (VERDICT r4 item 6; reference analog:
    gloo ring selection, ops/gloo_operations.cc)."""
    size = 8
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(GATHER_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_RING_THRESHOLD_BYTES="4096")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"gather worker {r} OK" in out


FAULT_WORKER = textwrap.dedent("""
    import ctypes, os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    from horovod_tpu.engine import bindings
    from horovod_tpu.engine.bindings import EngineSession

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    port = int(os.environ["HOROVOD_CONTROLLER_PORT"])
    mode = os.environ["FAULT_MODE"]
    s = EngineSession(rank=rank, size=size, transport="tcp",
                      addr="127.0.0.1", port=port, timeout_sec=60.0)
    lib = bindings.load_library()

    if mode == "star_allgatherv":
        # small payload -> star path; rank 0 drops a byte of the packed
        # broadcast (HOROVOD_DATA_FAULT_INJECT) -> every rank must see the
        # size-validation error, not a silent short buffer
        buf = np.full((rank + 1) * 8, float(rank), np.float32)
        rank_bytes = (ctypes.c_int64 * size)()
        total = lib.hvdtpu_data_allgatherv(s._session, buf.ctypes.data,
                                           buf.nbytes, rank_bytes)
        assert total < 0, f"truncated allgatherv not detected: {{total}}"
    else:
        # large payload -> ring path; every rank truncates its outgoing
        # bundle on hop 0 -> corrupt-entry validation must fire everywhere
        sends = [2048 for _ in range(size)]
        data = np.full(sum(sends), float(rank), np.float32)
        send_b = (ctypes.c_int64 * size)(*[c * 4 for c in sends])
        recv_b = (ctypes.c_int64 * size)()
        total = lib.hvdtpu_data_alltoallv(s._session, data.ctypes.data,
                                          send_b, size, recv_b)
        assert total < 0, f"corrupt alltoallv bundle not detected: {{total}}"

    s.shutdown()
    print(f"fault worker {{rank}} OK")
""")


@pytest.mark.parametrize("mode,fault,size", [
    ("star_allgatherv", "truncate_star_allgatherv", 3),
    ("ring_alltoallv", "truncate_ring_alltoallv", 4),
])
def test_data_plane_corruption_detected(tmp_path, mode, fault, size):
    """Negative path for the round-5 advisor findings: a truncated star
    Allgatherv broadcast and a corrupt RingAlltoallv bundle must surface as
    errors on every rank instead of handing callers bad offsets."""
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(FAULT_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_RING_THRESHOLD_BYTES="4096",
                   HOROVOD_DATA_FAULT_INJECT=fault,
                   FAULT_MODE=mode)
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"fault worker {r} OK" in out


def test_tcp_ring_data_plane(tmp_path):
    """Large payloads take the O(bytes)-per-rank ring path: numerics for
    sum/max/bcast plus the ring-ops counter proving the star was bypassed
    (VERDICT r3 item 6; reference analog: gloo ring ops)."""
    size = 4
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(RING_WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_CONTROLLER_PORT=str(port),
                   HOROVOD_RING_THRESHOLD_BYTES="4096")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"ring worker {r} OK" in out
