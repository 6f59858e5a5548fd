"""chip_smoke.py's contract, as far as a sandbox without a chip can hold it
to: no TPU means a non-zero exit and no result line, a phase that raises
prints nothing, and the rehearsal (tiny sizes, CPU) runs every phase to its
end without ever printing ``"ok": true``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(*args, devices=1, cwd=REPO, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, SCRIPT, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_without_a_tpu_it_fails_and_prints_no_result():
    out = _run()
    assert out.returncode != 0
    assert out.stdout == "", out.stdout
    assert "no TPU" in out.stderr


def test_device_count_must_match_the_option():
    out = _run("--rehearse", "--chips", "4")
    assert out.returncode != 0
    assert out.stdout == "", out.stdout
    assert "--chips 4" in out.stderr


def test_a_phase_that_raises_prints_nothing(capsys):
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    smoke = chip_smoke.Smoke(chip_smoke.TINY, seed=0, rehearse=True)
    with pytest.raises(RuntimeError, match="boom"):
        with smoke.phase("doomed") as checked:
            checked["reached"] = True
            raise RuntimeError("boom")
    assert capsys.readouterr().out == ""
    with smoke.phase("fine") as checked:
        checked["reached"] = True
    line = json.loads(capsys.readouterr().out)
    assert line["phase"] == "fine" and line["checked"] == {"reached": True}
    assert {"seconds", "compile_seconds", "programs_compiled", "cache_hits",
            "cache_misses"} <= set(line)


@pytest.mark.slow  # about a minute each: full-width models and an engine build
@pytest.mark.parametrize("chips,phases", [
    (1, ["device", "resnet50", "gpt2_small_flash", "flash_vs_reference",
         "engine"]),
    (4, ["device", "gpt2_small_one_chip_reference", "gpt2_small_dp4",
         "gpt2_small_zero1", "resnet50_dp4"]),
])
def test_rehearsal_runs_every_phase(chips, phases):
    out = _run("--rehearse", "--chips", str(chips), devices=chips)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert [line["phase"] for line in lines[:-1]] == phases
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    assert '"ok": true' not in out.stdout
