"""Eager collectives on a jax.distributed multi-process SPMD job.

VERDICT round-3 item 3: the engine's host-TCP controller must coexist with
a jax.distributed job — broadcast_object / State.sync must move data across
processes rather than silently returning local results (the reference's
gloo controller likewise runs alongside NCCL, gloo_context.cc:136-147).
"""

import os
import subprocess
import sys
import textwrap

from conftest import free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(
        coordinator_address="127.0.0.1:" + os.environ["JAXD_PORT"],
        num_processes=2,
        process_id=int(os.environ["HOROVOD_RANK"]),
        local_device_ids=[int(os.environ["HOROVOD_RANK"])])

    import numpy as np
    import horovod_tpu as hvd_top
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax.elastic import State

    hvd_top.init()
    rank, size = hvd_top.rank(), hvd_top.size()
    assert size == 2 and jax.process_count() == 2

    # the engine must have booted despite jax.distributed being live
    from horovod_tpu.common import basics
    assert basics._context().engine is not None, "engine not started"

    # broadcast_object crosses processes
    obj = hvd.broadcast_object({{"seed": 1234 + rank}}, root_rank=0)
    assert obj == {{"seed": 1234}}, obj

    # eager allreduce crosses processes
    out = np.asarray(hvd.allreduce(
        np.full((3,), float(rank + 1), np.float32), op=hvd.Sum))
    assert np.allclose(out, 3.0), out

    # elastic State.sync broadcasts committed state from rank 0
    s = State(step=100 * (rank + 1), note=f"from-{{rank}}")
    s.sync()
    assert s.step == 100 and s.note == "from-0", (s.step, s.note)

    hvd_top.shutdown()
    print(f"spmd eager worker {{rank}} OK")
""")


def test_spmd_job_eager_ops(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER.format(repo=REPO))
    ctrl_port, jaxd_port = free_port(), free_port()
    procs = []
    for r in range(2):
        env = dict(os.environ,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE="2",
                   HOROVOD_LOCAL_RANK=str(r), HOROVOD_LOCAL_SIZE="2",
                   HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                   HOROVOD_CONTROLLER_PORT=str(ctrl_port),
                   JAXD_PORT=str(jaxd_port))
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=240)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"spmd eager worker {r} OK" in out
