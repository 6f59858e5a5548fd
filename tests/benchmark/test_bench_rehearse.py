"""``run.py --rehearse`` end to end for the one-chip cells, at the files'
rehearse sizes on the CPU: the whole path (``hvd.init()``, weights and batch
from the seed, the float32 reference check, AOT compile, warm-up, timed
blocks, the result line), and what a run without a TPU does."""

import os
import shutil
import subprocess
import sys

import pytest

import bench_paths
from bench_run import check_rehearsal_result, result_line, run_cell


@pytest.mark.parametrize("workload,rate", [
    ("resnet50-b256", "images_per_s_per_chip"),
    ("gpt2s-t512", "tokens_per_s_per_chip"),
])
def test_rehearsal_reports_the_end_to_end_metrics(workload, rate):
    result, earlier = result_line(run_cell(
        "--workload", workload, "--rehearse", "--seconds", "1", "--seed",
        "5", "--trace", "0"))
    check_rehearsal_result(result, 1, {rate, "peak_hbm_gb", "setup_s"})
    checks = next(e for e in earlier if "checks" in e)
    assert checks["programs_in_windows"] == 0
    assert checks["checks"]["warmup_loss"] and checks["checks"]["finite"]
    assert set(checks["setup_split"]) == {
        "batch_s", "reference_check_s", "weights_s", "lower_and_compile_s",
        "warmup_s"}
    reference = next(e for e in earlier
                     if e.get("check") == "float32 reference")
    assert reference["loss_relative_error"] <= reference["loss_rtol"]
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    assert held == {"check": held["check"], "ok": True, "required": {},
                    "missing": {}, "not_asked_for": {},
                    "tpu_custom_calls": {}}


def test_traced_rehearsal_reports_what_needs_no_device_plane():
    """The flash path (seq 1024, kernels interpreted). The CPU's trace has
    no device plane, so the readers of the device trace return nothing and
    are left out; the counters and host clocks are there."""
    result, earlier = result_line(run_cell(
        "--workload", "gpt2s-t8192", "--rehearse", "--seconds", "1",
        "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})
    assert result["metrics"]["programs_after_warmup"]["value"] == 0.0
    assert "breakdown" not in result
    traced = next(e for e in earlier if "traced_blocks" in e)
    assert traced["device_planes"] == 0 and traced["host_spans"] > 0
    facts = earlier[0]
    assert facts["attention"] == "flash" and facts["hidden"] == 768
    # the job asks for the three flash kernels; interpreted on the CPU they
    # are no ``tpu_custom_call``, and a rehearsal passes the check all the same
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    assert held["ok"] is True and held["tpu_custom_calls"] == {}
    assert set(held["required"]) == {"_fwd_kernel", "_bwd_dq_kernel",
                                     "_bwd_dkv_kernel"}
    assert set(held["missing"]) == set(held["required"])
    assert held["not_asked_for"] == {}


def test_without_a_tpu_it_fails_and_prints_no_result():
    done = run_cell("--workload", "gpt2s-t512", "--seconds", "1")
    assert done.returncode != 0
    assert done.stdout == "", done.stdout
    assert "no TPU" in done.stderr


def test_fewer_devices_than_the_cell_asks_for_is_an_error():
    done = run_cell("--workload", "gpt2s-t1024-dp4", "--rehearse",
                    "--seconds", "1", devices=2)
    assert done.returncode != 0
    assert done.stdout == "", done.stdout
    assert "needs 4 chip(s)" in done.stderr


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds only ``BENCHMARK.json`` and the files under
    ``paths``: there is no system to measure."""
    shutil.copytree(bench_paths.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    shutil.copy(os.path.join(bench_paths.REPO, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2s-t512",
         "--rehearse", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == "", done.stdout
    assert "horovod_tpu" in done.stderr
