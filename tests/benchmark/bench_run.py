"""Run ``benchmark/run.py`` as the driver does, but here: a process of its
own on the CPU backend."""

import json
import os
import subprocess
import sys

import bench_paths

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(*args, devices=1, script=None, cwd=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=bench_paths.REPO)
    script = script or os.path.join(bench_paths.BENCH, "run.py")
    return subprocess.run([sys.executable, script, *args],
                          cwd=cwd or bench_paths.REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def result_line(done):
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    for line in lines:  # every line is one JSON object
        json.loads(line)
    return json.loads(lines[-1]), [json.loads(line) for line in lines[:-1]]


def check_rehearsal_result(result, chips, metrics):
    assert RESULT_KEYS <= set(result)
    assert result["rehearsal"] is True  # marked: no result
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    # each number compared beside its limit, last on the line (a rehearsal's
    # two steps of warm-up need not lower the loss: ``correct`` may be false)
    assert list(result)[-1] == "compared"
    assert {"loss_relative_error", "gradient_relative_l2_error",
            "warmup_loss_last_less_step_0", "required_kernels_missing",
            "flash_kernels_not_asked_for", "programs_in_windows",
            "losses_not_finite"} <= set(result["compared"])
    assert result["compared"]["required_kernels_missing"] == [0, 0]
    assert result["compared"]["flash_kernels_not_asked_for"] == [0, 0]
    for pair in result["compared"].values():
        assert len(pair) == 2
