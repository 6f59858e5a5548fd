"""The full public Horovod-parity surface across real processes:
hvd.init() from the launcher env contract, eager collectives, object
broadcast, join, shutdown (reference analog: any test/parallel/* run under
horovodrun)."""

import os
import subprocess
import sys
import textwrap

from conftest import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    import horovod_tpu as hvd_top
    import horovod_tpu.jax as hvd

    hvd_top.init()
    rank, size = hvd_top.rank(), hvd_top.size()
    assert size == 3

    # eager allreduce through the top-level API
    out = np.asarray(hvd.allreduce(np.full((4,), float(rank), np.float32),
                                   op=hvd.Sum))
    assert np.allclose(out, 0.0 + 1.0 + 2.0), out

    # grouped
    outs = hvd.grouped_allreduce(
        [np.full((2,), float(rank), np.float32),
         np.full((3,), float(rank * 2), np.float32)], op=hvd.Average)
    assert np.allclose(np.asarray(outs[0]), 1.0), outs[0]
    assert np.allclose(np.asarray(outs[1]), 2.0), outs[1]

    # object transport
    obj = hvd.broadcast_object({{"lr": 0.1, "epoch": 3}}, root_rank=0)
    assert obj == {{"lr": 0.1, "epoch": 3}}
    gathered = hvd.allgather_object(("rank", rank))
    assert gathered == [("rank", r) for r in range(3)], gathered

    # parameters
    params = {{"w": np.full((3,), float(rank), np.float32)}}
    params = hvd.broadcast_parameters(params, root_rank=1)
    assert np.allclose(np.asarray(params["w"]), 1.0)

    # metrics-style allreduce with average kwarg (legacy parity)
    m = hvd.allreduce(np.asarray([float(rank)], np.float32), average=True)
    assert np.allclose(np.asarray(m), 1.0)

    # join: uneven final batches
    if rank != 2:
        out = np.asarray(hvd.allreduce(
            np.full((2,), 1.0, np.float32), op=hvd.Sum, name="tail"))
        assert np.allclose(out, 2.0), out  # rank 2 contributed zeros
    hvd.join()

    hvd_top.shutdown()
    print(f"public-api worker {{rank}} OK")
""")


def test_public_api_three_processes(tmp_path):
    size = 3
    port = free_port()
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO))
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_LOCAL_RANK=str(r), HOROVOD_LOCAL_SIZE=str(size),
                   HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                   HOROVOD_CONTROLLER_PORT=str(port),
                   JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=120)
        outs.append(out.decode())
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"public-api worker {r} OK" in out


SUBSET_WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    import horovod_tpu as hvd_top
    import horovod_tpu.jax as hvd

    global_rank = int(os.environ["HOROVOD_RANK"])
    hvd_top.init(comm=[0, 2])
    if global_rank in (0, 2):
        # members re-rank into the subset
        assert hvd_top.size() == 2, hvd_top.size()
        assert hvd_top.rank() == (0 if global_rank == 0 else 1)
        out = np.asarray(hvd.allreduce(
            np.asarray([float(global_rank + 1)], np.float32), op=hvd.Sum))
        assert np.allclose(out, 4.0), out  # 1 + 3: rank 1 excluded
        g = hvd.allgather_object(global_rank)
        assert g == [0, 2], g
    else:
        # non-member: size-1 singleton, local semantics
        assert hvd_top.size() == 1, hvd_top.size()
        out = np.asarray(hvd.allreduce(
            np.asarray([5.0], np.float32), op=hvd.Sum))
        assert np.allclose(out, 5.0), out
    hvd_top.shutdown()
    print(f"subset worker {{global_rank}} OK")
""")


def test_subset_communicator(tmp_path):
    """hvd.init(comm=[0, 2]) on a 3-process world: members form a size-2
    job with re-ranked collectives, the excluded rank runs size-1
    (reference: operations.cc:712-714, controller.h:112-117)."""
    script = tmp_path / "subset.py"
    script.write_text(SUBSET_WORKER.format(repo=REPO))
    # no rendezvous KV here: the subset's engine takes the controller port
    # plus 2 and plus 3 (basics.py, the arithmetic fallback)
    port = free_port(span=4)
    procs = []
    for r in range(3):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE="3",
                   HOROVOD_LOCAL_RANK=str(r), HOROVOD_LOCAL_SIZE="3",
                   HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                   HOROVOD_CONTROLLER_PORT=str(port),
                   JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=120)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"subset worker {r} OK" in out
