"""Launcher tests.

Reference analog: test/single/test_run.py (host parsing + assignment
against expected topologies, launcher arg handling) and
test/integration/test_static_run.py (real localhost jobs end-to-end).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.runner import hosts as hosts_lib
from horovod_tpu.runner.launch import make_parser, run_commandline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# host assignment units (reference: test_run.py test_get_host_assignments)


def test_parse_hosts():
    hosts = hosts_lib.parse_hosts("a:2,b:4,c")
    assert [(h.hostname, h.slots) for h in hosts] == \
        [("a", 2), ("b", 4), ("c", 1)]


def test_host_assignment_topology():
    hosts = hosts_lib.parse_hosts("a:2,b:2")
    slots = hosts_lib.get_host_assignments(hosts, 4)
    assert [(s.rank, s.hostname, s.local_rank, s.cross_rank)
            for s in slots] == [
        (0, "a", 0, 0), (1, "a", 1, 0), (2, "b", 0, 1), (3, "b", 1, 1)]
    for s in slots:
        assert s.size == 4
        assert s.local_size == 2
        assert s.cross_size == 2


def test_host_assignment_uneven():
    hosts = hosts_lib.parse_hosts("a:3,b:1")
    slots = hosts_lib.get_host_assignments(hosts, 4)
    a_slots = [s for s in slots if s.hostname == "a"]
    b_slots = [s for s in slots if s.hostname == "b"]
    assert len(a_slots) == 3 and a_slots[0].local_size == 3
    assert len(b_slots) == 1 and b_slots[0].local_size == 1
    # local_rank 0 exists on both hosts; local ranks 1,2 only on a
    assert a_slots[0].cross_size == 2
    assert a_slots[1].cross_size == 1


def test_host_assignment_insufficient_slots():
    with pytest.raises(ValueError, match="slots"):
        hosts_lib.get_host_assignments(hosts_lib.parse_hosts("a:2"), 4)


def test_env_contract():
    slots = hosts_lib.get_host_assignments(
        hosts_lib.parse_hosts("localhost:2"), 2)
    env = slots[1].to_env()
    assert env["HOROVOD_RANK"] == "1"
    assert env["HOROVOD_SIZE"] == "2"
    assert env["HOROVOD_LOCAL_RANK"] == "1"


def test_parser_maps_engine_knobs():
    args = make_parser().parse_args(
        ["-np", "2", "--fusion-threshold-mb", "32", "--cycle-time-ms", "5",
         "--timeline-filename", "/tmp/t.json", "--", "python", "x.py"])
    from horovod_tpu.runner.launch import _engine_env
    env = _engine_env(args)
    assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
    assert float(env["HOROVOD_CYCLE_TIME"]) == 5.0
    assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"


# ---------------------------------------------------------------------------
# integration: real localhost static runs


TRAIN = textwrap.dedent("""
    import sys
    sys.path.insert(0, {repo!r})
    import numpy as np
    import horovod_tpu as hvd_top
    import horovod_tpu.jax as hvd
    hvd_top.init()
    out = np.asarray(hvd.allreduce(
        np.full((2,), float(hvd_top.rank()), np.float32), op=hvd.Sum))
    assert np.allclose(out, sum(range(hvd_top.size()))), out
    cfg = hvd.broadcast_object({{"seed": 42}} if hvd_top.rank() == 0 else None)
    assert cfg == {{"seed": 42}}
    print(f"static-worker {{hvd_top.rank()}}/{{hvd_top.size()}} OK")
    hvd_top.shutdown()
""")


def test_static_launch_three_workers(tmp_path, capfd):
    script = tmp_path / "train.py"
    script.write_text(TRAIN.format(repo=REPO))
    rc = run_commandline(["-np", "3", "--", sys.executable, str(script)])
    out = capfd.readouterr().out
    assert rc == 0, out
    for r in range(3):
        assert f"static-worker {r}/3 OK" in out


def test_static_launch_failure_propagates(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        if int(os.environ["HOROVOD_RANK"]) == 1:
            sys.exit(7)
        time.sleep(60)  # must be terminated by the launcher, not finish
    """))
    import time
    t0 = time.monotonic()
    rc = run_commandline(["-np", "3", "--", sys.executable, str(script)])
    assert rc == 7
    assert time.monotonic() - t0 < 50, "launcher did not fail fast"


def test_cli_requires_command():
    with pytest.raises(SystemExit):
        run_commandline(["-np", "2"])


def test_programmatic_run_returns_per_rank_results():
    """horovod_tpu.run(fn, np=N) executes fn on N coordinated processes and
    returns rank-ordered results (reference:
    test/integration/test_interactiverun.py:94)."""
    import horovod_tpu

    def fn(scale):
        import numpy as np
        import horovod_tpu as hvd
        import horovod_tpu.jax as hvd_jax
        hvd.init()
        total = float(np.asarray(hvd_jax.allreduce(
            np.asarray([float(hvd.rank())], np.float32), op=hvd_jax.Sum))[0])
        out = (hvd.rank(), hvd.size(), total * scale)
        hvd.shutdown()
        return out

    results = horovod_tpu.run(fn, args=(2.0,), np=3)
    assert results == [(r, 3, 6.0) for r in range(3)], results


def test_programmatic_run_propagates_failure():
    import pytest
    import horovod_tpu

    def boom():
        raise RuntimeError("worker exploded")

    with pytest.raises(RuntimeError, match="exit code"):
        horovod_tpu.run(boom, np=2)


def test_programmatic_run_start_timeout():
    """The liveness hook aborts a job whose workers never start (the
    mechanism behind run()'s start_timeout) instead of hanging forever."""
    import sys
    import time
    from horovod_tpu.runner import launch as launch_lib

    argv = ["-np", "1", "-H", "localhost:1", "--",
            sys.executable, "-c", "import time; time.sleep(120)"]
    parsed = launch_lib.make_parser().parse_args(argv)
    parsed.command = argv[-3:]

    t0 = time.monotonic()

    def never_started():
        if time.monotonic() - t0 > 2.0:
            return "ranks [0] did not start within 2.0s"
        return None

    rc = launch_lib.run_static(parsed, liveness_check=never_started)
    assert rc == 1
    assert time.monotonic() - t0 < 30, "liveness abort did not bound the job"


def test_programmatic_run_with_subset_comm():
    """init(comm=...) under the real launcher negotiates subset ports
    through the rendezvous KV (no arithmetic-offset collisions)."""
    import horovod_tpu

    def fn():
        import numpy as np
        import horovod_tpu as hvd
        import horovod_tpu.jax as hvd_jax
        hvd.init(comm=[0, 1])
        out = float(np.asarray(hvd_jax.allreduce(
            np.asarray([1.0], np.float32), op=hvd_jax.Sum))[0])
        r = (hvd.rank(), hvd.size(), out)
        hvd.shutdown()
        return r

    results = horovod_tpu.run(fn, np=3)
    assert sorted(results) == [(0, 1, 1.0), (0, 2, 2.0), (1, 2, 2.0)], results


def test_check_build_reports_capabilities(capsys):
    """--check-build prints the availability matrix and exits 0
    (reference: launch.py:110-146,255)."""
    rc = run_commandline(["--check-build"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Available Frameworks" in out
    assert "[X] JAX" in out
    assert "[X] native engine" in out


def test_config_file_defaults_and_cli_precedence(tmp_path):
    """YAML --config-file fills defaults; explicit CLI flags beat the file
    (reference: launch.py:293,513-517 + config_parser schema)."""
    from horovod_tpu.runner.launch import apply_config_file

    cfg = tmp_path / "hvd.yaml"
    cfg.write_text(textwrap.dedent("""
        params:
          fusion_threshold_mb: 32
          cycle_time_ms: 7.5
          hierarchical_allreduce: true
        autotune:
          enabled: true
          log_file: /tmp/at.csv
        timeline:
          filename: /tmp/tl.json
          mark_cycles: true
        stall_check:
          enabled: false
          warning_time_seconds: 42
    """))
    parser = make_parser()
    apply_config_file(parser, str(cfg))
    # config fills in unset args...
    args = parser.parse_args(["-np", "2", "cmd"])
    assert args.fusion_threshold_mb == 32
    assert args.cycle_time_ms == 7.5
    assert args.hierarchical_allreduce is True
    assert args.autotune is True
    assert args.autotune_log == "/tmp/at.csv"
    assert args.timeline_filename == "/tmp/tl.json"
    assert args.timeline_mark_cycles is True
    assert args.no_stall_check is True
    assert args.stall_check_time_seconds == 42
    # ...but explicit CLI flags win over the file
    args = parser.parse_args(["-np", "2", "--fusion-threshold-mb", "64",
                              "cmd"])
    assert args.fusion_threshold_mb == 64


def test_ssh_reachability_local_and_cache(tmp_path, monkeypatch):
    """Local hostnames skip the probe; successes are cached with a
    staleness window (reference: launch.py:57-107 + cache.use_cache)."""
    from horovod_tpu.runner import launch as launch_lib

    monkeypatch.setattr(launch_lib, "SSH_CACHE_FILE",
                        str(tmp_path / "cache.json"))
    assert launch_lib.check_hosts_ssh(["localhost", "127.0.0.1"]) == []

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)
        class R:
            returncode = 0
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert launch_lib.check_hosts_ssh(["fakehost-a"]) == []
    assert len(calls) == 1
    # second call hits the cache — no new probe
    assert launch_lib.check_hosts_ssh(["fakehost-a"]) == []
    assert len(calls) == 1


def test_ssh_cache_prunes_stale_and_keys_by_user(tmp_path, monkeypatch):
    """ADVICE r5: entries older than the staleness window are dropped on
    store (the file cannot grow unboundedly), and the key carries the
    effective ssh user so one credential set's success is not trusted for
    another."""
    import json
    import time as time_lib
    from horovod_tpu.runner import launch as launch_lib

    cache_file = tmp_path / "cache.json"
    monkeypatch.setattr(launch_lib, "SSH_CACHE_FILE", str(cache_file))
    now = time_lib.time()
    stale_key = launch_lib._ssh_cache_key("old-host", None)
    cache_file.write_text(json.dumps({
        stale_key: now - launch_lib.SSH_CACHE_STALENESS_S - 10}))

    def fake_run(cmd, **kw):
        class R:
            returncode = 0
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert launch_lib.check_hosts_ssh(["fakehost-b"]) == []
    stored = json.loads(cache_file.read_text())
    assert stale_key not in stored, "stale entry survived the store"
    fresh_key = launch_lib._ssh_cache_key("fakehost-b", None)
    assert fresh_key in stored
    # the key is user-qualified: an explicit user@host maps to its own entry
    assert launch_lib._ssh_cache_key("alice@h", 2222).startswith("alice@")
    assert launch_lib._ssh_cache_key("alice@h", 2222) != \
        launch_lib._ssh_cache_key("bob@h", 2222)


def test_ssh_unreachable_host_fails_launch(tmp_path, monkeypatch):
    from horovod_tpu.runner import launch as launch_lib

    monkeypatch.setattr(launch_lib, "SSH_CACHE_FILE",
                        str(tmp_path / "cache.json"))

    def fake_run(cmd, **kw):
        class R:
            returncode = 255
        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setattr(launch_lib, "SSH_ATTEMPTS", 1)
    bad = launch_lib.check_hosts_ssh(["no-such-host-xyz"])
    assert bad == ["no-such-host-xyz"]
