"""Elastic training end-to-end on localhost.

Reference analog: test/integration/test_elastic_torch.py +
elastic_common.py — a discovery script backed by a file the test mutates
mid-run; asserts training survives host additions and worker failures with
state intact.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ELASTIC_TRAIN = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, {repo!r})
    import numpy as np
    import horovod_tpu as hvd_top
    import horovod_tpu.jax as hvd
    from horovod_tpu.jax import elastic

    hvd_top.init()
    state = elastic.State(step=0)
    TOTAL = int(os.environ.get("TOTAL_STEPS", "30"))

    @elastic.run
    def train(state):
        while state.step < TOTAL:
            out = np.asarray(hvd.allreduce(
                np.ones(2, np.float32), op=hvd.Sum,
                name=f"batch.{{state.step}}"))
            assert np.allclose(out, hvd_top.size()), (out, hvd_top.size())
            print(f"progress rank={{hvd_top.rank()}} step={{state.step}} "
                  f"size={{hvd_top.size()}}", flush=True)
            state.step += 1
            state.commit()
            time.sleep(0.05)
        return state.step

    steps = train(state)
    print(f"worker-done rank={{hvd_top.rank()}} steps={{steps}} "
          f"size={{hvd_top.size()}}", flush=True)
    hvd_top.shutdown()
""")


class _StreamingJob:
    """Launcher subprocess with live output capture, so mid-run events
    (host add, worker kill) trigger on observed progress instead of racing
    a fixed sleep against JAX import time."""

    def __init__(self, proc):
        self.proc = proc
        self.lines = []
        self._cond = threading.Condition()
        self._thread = threading.Thread(target=self._pump, daemon=True)
        self._thread.start()

    def _pump(self):
        for line in self.proc.stdout:
            with self._cond:
                self.lines.append(line.decode(errors="replace"))
                self._cond.notify_all()

    def wait_for_line(self, needle: str, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        scanned = 0
        with self._cond:
            while True:
                for line in self.lines[scanned:]:
                    if needle in line:
                        return True
                scanned = len(self.lines)
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self.proc.poll() is not None:
                    return False
                self._cond.wait(timeout=min(remaining, 1.0))

    def finish(self, timeout: float) -> str:
        self.proc.wait(timeout=timeout)
        self._thread.join(timeout=10)
        return "".join(self.lines)


def _startup_deadline(base: float = 90.0) -> float:
    """Deadline for the first observed training progress, scaled by host
    load. The fixed 90s wait flaked on fully loaded 2-core boxes (CHANGES
    PR 11): JAX import + engine build + two worker spawns compete with
    the rest of the test suite for the cores, so the wall-clock budget
    must grow with oversubscription. Scale by load-per-core, clamped to
    [base, 4x base] so a pathological load average can't hide a real
    hang."""
    try:
        per_core = os.getloadavg()[0] / max(1, os.cpu_count() or 1)
    except OSError:
        per_core = 1.0
    return min(base * 4.0, base * max(1.0, per_core))


def _launch_elastic(tmp_path, hosts_file_content, min_np, max_np,
                    total_steps=30):
    hosts_file = tmp_path / "hosts.txt"
    hosts_file.write_text(hosts_file_content)
    discovery = tmp_path / "discover.sh"
    discovery.write_text(f"#!/bin/sh\ncat {hosts_file}\n")
    discovery.chmod(0o755)
    train = tmp_path / "train.py"
    train.write_text(ELASTIC_TRAIN.format(repo=REPO))

    env = dict(os.environ, TOTAL_STEPS=str(total_steps),
               HOROVOD_CONTROLLER_TIMEOUT_SECONDS="10",
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.launch",
         "--min-np", str(min_np), "--max-np", str(max_np),
         "--host-discovery-script", str(discovery), "--verbose",
         "--", sys.executable, str(train.resolve())],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    return _StreamingJob(proc), hosts_file


def test_elastic_scale_up(tmp_path):
    """Start with 2 slots, add a third mid-run: workers reset, the new
    worker syncs committed state, training finishes at size 3."""
    job, hosts_file = _launch_elastic(tmp_path, "localhost:2\n",
                                      min_np=2, max_np=3, total_steps=40)
    # split assertion: startup (JAX import + spawn, the load-sensitive
    # part) is budgeted separately from reaching step 2, so a timeout
    # names which phase actually stalled
    assert job.wait_for_line("progress", timeout=_startup_deadline()), \
        "workers never made progress:\n" + "".join(job.lines)
    assert job.wait_for_line("step=2 size=2",
                             timeout=_startup_deadline(30.0)), \
        "".join(job.lines)
    hosts_file.write_text("localhost:3\n")
    text = job.finish(timeout=180)
    assert job.proc.returncode == 0, text
    assert "size=2" in text, text
    assert "size=3" in text, f"never scaled up:\n{text}"
    done = [line for line in text.splitlines() if "worker-done" in line]
    assert any("size=3" in line for line in done), text
    # the late-joining worker must resume from committed step, not step 0:
    # after scale-up no step may repeat from 0 for rank 2
    rank2_steps = [int(line.split("step=")[1].split()[0])
                   for line in text.splitlines()
                   if "progress rank=2" in line]
    assert rank2_steps, f"rank 2 never made progress:\n{text}"
    assert rank2_steps[0] > 0, (
        f"new worker restarted from step 0:\n{text}")


def test_elastic_worker_failure_recovers(tmp_path):
    """Kill one worker mid-run: peers restore committed state, the driver
    respawns the slot, training completes."""
    job, hosts_file = _launch_elastic(tmp_path, "localhost:2\n",
                                      min_np=2, max_np=2, total_steps=40)
    assert job.wait_for_line("step=2 size=2",
                             timeout=_startup_deadline()), \
        "".join(job.lines)
    # find a worker: children of launcher running train.py
    out = subprocess.run(
        ["pgrep", "-f", "train.py"], capture_output=True, text=True)
    pids = [int(p) for p in out.stdout.split()]
    assert pids, "did not find a worker to kill"
    os.kill(pids[-1], 9)
    text = job.finish(timeout=180)
    assert job.proc.returncode == 0, text
    assert "worker-done" in text, text


def test_elastic_scale_down(tmp_path):
    """Remove a host (slot) from discovery mid-run: the dropped worker
    exits cleanly, survivors re-rendezvous at the smaller world and finish
    (reference: elastic_common.py:35-62 drives both directions)."""
    job, hosts_file = _launch_elastic(tmp_path, "localhost:3\n",
                                      min_np=2, max_np=3, total_steps=40)
    assert job.wait_for_line("step=2 size=3",
                             timeout=_startup_deadline()), \
        "".join(job.lines)
    hosts_file.write_text("localhost:2\n")
    text = job.finish(timeout=180)
    assert job.proc.returncode == 0, text
    assert "size=3" in text, text
    done = [line for line in text.splitlines() if "worker-done" in line]
    assert done and all("size=2" in line for line in done), \
        f"job did not finish at the reduced size:\n{text}"
    # progress must continue (not restart) across the shrink
    steps_at_2 = [int(line.split("step=")[1].split()[0])
                  for line in text.splitlines()
                  if "progress" in line and "size=2" in line]
    assert steps_at_2 and min(steps_at_2) > 0, \
        f"survivors restarted from step 0:\n{text}"
