"""``hvdrun-tpu`` — the launcher CLI.

Reference analog: horovod/runner/launch.py (argparse surface mapping engine
knobs to env, :734-758 static-vs-elastic dispatch) + gloo_run.py
(rendezvous server, host assignment, per-slot env, worker spawn,
:226-271,187-211).

Static flow: allocate controller+data ports, start the rendezvous KV,
publish per-slot topology, spawn one worker per slot with the
``HOROVOD_*`` env contract, fail fast if any worker fails.
"""

from __future__ import annotations

import argparse
import os
import socket
import sys
import time
from typing import List, Optional

from horovod_tpu.runner import hosts as hosts_lib
from horovod_tpu.runner.exec_utils import WorkerProcess, is_local
from horovod_tpu.runner.http_kv import KVServer

# ssh reachability results are cached here and trusted for this long
# (reference: launch.py CACHE_FOLDER + CACHE_STALENESS_THRESHOLD_MINUTES)
SSH_CACHE_FILE = os.path.join(os.path.expanduser("~"), ".horovod_tpu",
                              "ssh_reachability.json")
SSH_CACHE_STALENESS_S = 60 * 60
SSH_ATTEMPTS = 3
SSH_CONNECT_TIMEOUT_S = 10


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("0.0.0.0", 0))
        return s.getsockname()[1]


def free_ports(n: int) -> List[int]:
    """Allocate ``n`` distinct free ports, holding all the sockets bound
    simultaneously — sequential free_port() calls can hand back the same
    port twice once the first socket is closed."""
    socks = []
    try:
        for _ in range(n):
            s = socket.socket()
            s.bind(("0.0.0.0", 0))
            socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def check_build(verbose: bool = False) -> str:
    """Summarize what this installation can do — frameworks, controllers,
    and TPU features (reference: launch.py:110-146 check_build; the
    controller/ops sections are re-interpreted for the TPU stack)."""
    import importlib.util as iu

    def have(mod):
        try:
            return iu.find_spec(mod) is not None
        except (ImportError, ValueError):
            return False

    try:
        from horovod_tpu.engine import bindings
        bindings.load_library()
        engine_ok = True
    except Exception:  # noqa: BLE001 — any load failure means "not built"
        engine_ok = False
    try:
        from horovod_tpu import __version__ as version
    except ImportError:
        version = "dev"

    def mark(v):
        return "X" if v else " "

    lines = [
        f"horovod_tpu v{version}:",
        "",
        "Available Frameworks:",
        f"    [{mark(have('jax'))}] JAX",
        f"    [{mark(have('tensorflow'))}] TensorFlow",
        f"    [{mark(have('torch'))}] PyTorch",
        f"    [{mark(have('keras'))}] Keras",
        "",
        "Available Controllers:",
        f"    [{mark(engine_ok)}] native engine (TCP / loopback)",
        "",
        "Available Tensor Operations:",
        f"    [{mark(have('jax'))}] XLA collectives (ICI/DCN)",
        f"    [{mark(engine_ok)}] host data plane (ring + star)",
        f"    [{mark(have('jax'))}] Pallas flash attention",
        "",
        "Available Integrations:",
        f"    [{mark(have('pyspark'))}] Spark",
        f"    [{mark(have('ray'))}] Ray",
    ]
    out = "\n".join(lines)
    if verbose and not engine_ok:
        out += ("\n\nnative engine unavailable: build it with "
                "`make -C horovod_tpu/engine`")
    return out


# YAML --config-file sections -> argparse dest names (reference schema:
# runner/common/util/config_parser.py set_args_from_config)
_CONFIG_SCHEMA = {
    "params": {
        "fusion_threshold_mb": "fusion_threshold_mb",
        "cycle_time_ms": "cycle_time_ms",
        "cache_capacity": "cache_capacity",
        "hierarchical_allreduce": "hierarchical_allreduce",
    },
    "autotune": {
        "enabled": "autotune",
        "log_file": "autotune_log",
        "warmup_samples": "autotune_warmup_samples",
        "steps_per_sample": "autotune_steps",
        "sample_cycles": "autotune_sample_cycles",
    },
    "timeline": {
        "filename": "timeline_filename",
        "mark_cycles": "timeline_mark_cycles",
    },
    "stall_check": {
        "warning_time_seconds": "stall_check_time_seconds",
        "shutdown_time_seconds": "stall_shutdown_time_seconds",
    },
}


def apply_config_file(parser: argparse.ArgumentParser, path: str) -> None:
    """Fold a YAML config into the parser's defaults, so explicit CLI flags
    win over the file and the file wins over built-in defaults (reference:
    launch.py:293,513-517; the reference's position-relative override order
    is simplified to CLI-beats-config)."""
    import yaml  # declared dependency (pyproject.toml)

    with open(path) as f:
        config = yaml.safe_load(f) or {}
    defaults = {}
    for section, mapping in _CONFIG_SCHEMA.items():
        values = config.get(section) or {}
        for key, dest in mapping.items():
            if key in values and values[key] is not None:
                defaults[dest] = values[key]
    stall = config.get("stall_check") or {}
    if "enabled" in stall:
        defaults["no_stall_check"] = not stall["enabled"]
    parser.set_defaults(**defaults)


def _load_ssh_cache() -> dict:
    import json
    try:
        with open(SSH_CACHE_FILE) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _effective_ssh_user(host: str) -> str:
    """The user ssh will authenticate as for ``host``: an explicit
    ``user@host`` prefix wins, else the invoking user. Folding this into the
    cache key keeps a success for one credential set from being trusted for
    another."""
    if "@" in host:
        return host.split("@", 1)[0]
    import getpass
    try:
        return getpass.getuser()
    except Exception:
        return os.environ.get("USER", "?")


def _ssh_cache_key(host: str, ssh_port) -> str:
    return f"{_effective_ssh_user(host)}@{host}:{ssh_port or 22}"


def _store_ssh_cache(cache: dict, now: Optional[float] = None) -> None:
    import json
    if now is not None:
        # Prune entries past the staleness window on every store — they can
        # never satisfy a lookup again, and without pruning the file grows
        # with every host/credential combination ever probed.
        cache = {k: t for k, t in cache.items()
                 if now - t < SSH_CACHE_STALENESS_S}
    try:
        os.makedirs(os.path.dirname(SSH_CACHE_FILE), exist_ok=True)
        with open(SSH_CACHE_FILE, "w") as f:
            json.dump(cache, f)
    except OSError:
        pass  # cache is an optimization; never fail the launch over it


def check_hosts_ssh(hostnames, ssh_port=None) -> List[str]:
    """Return the subset of remote hosts that are NOT ssh-reachable.
    Successes are cached for SSH_CACHE_STALENESS_S so repeated launches
    skip the probe (reference: launch.py:57-107
    _check_all_hosts_ssh_successful + cache.use_cache)."""
    import subprocess
    from concurrent.futures import ThreadPoolExecutor
    remote = [h for h in hostnames if not is_local(h)]
    if not remote:
        return []
    cache = _load_ssh_cache()
    now = time.time()

    def probe(host) -> bool:
        # BatchMode + closed stdin: a host behind password/interactive auth
        # must fail the probe immediately, not hang on a prompt
        cmd = ["ssh", "-o", "StrictHostKeyChecking=no",
               "-o", "BatchMode=yes",
               "-o", f"ConnectTimeout={SSH_CONNECT_TIMEOUT_S}"]
        if ssh_port:
            cmd += ["-p", str(ssh_port)]
        cmd += [host, "true"]
        for _ in range(SSH_ATTEMPTS):
            try:
                if subprocess.run(cmd, capture_output=True,
                                  stdin=subprocess.DEVNULL,
                                  timeout=SSH_CONNECT_TIMEOUT_S + 5
                                  ).returncode == 0:
                    return True
            except (subprocess.TimeoutExpired, OSError):
                pass
        return False

    to_probe = [h for h in sorted(set(remote))
                if now - cache.get(_ssh_cache_key(h, ssh_port), 0)
                >= SSH_CACHE_STALENESS_S]
    bad = []
    if to_probe:
        # concurrent probes: a fleet with several dead hosts must fail in
        # one probe-timeout, not one per host (reference: launch.py:93-95
        # execute_function_multithreaded)
        with ThreadPoolExecutor(max_workers=min(32, len(to_probe))) as ex:
            for host, ok in zip(to_probe, ex.map(probe, to_probe)):
                if ok:
                    # only successes are cached, like the reference
                    cache[_ssh_cache_key(host, ssh_port)] = now
                else:
                    bad.append(host)
    _store_ssh_cache(cache, now=now)
    return bad


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun-tpu",
        description="Launch a horovod_tpu distributed job")
    p.add_argument("-np", "--num-proc", type=int, default=None,
                   help="number of worker processes")
    p.add_argument("-H", "--hosts", default=None,
                   help='host slots, e.g. "localhost:4,host2:4"')
    p.add_argument("--ssh-port", type=int, default=None)
    p.add_argument("-cb", "--check-build", action="store_true",
                   help="print available frameworks/controllers/features "
                        "and exit")
    p.add_argument("--config-file", default=None,
                   help="YAML runtime config; explicit CLI flags override "
                        "it, it overrides built-in defaults")
    # elastic (reference: launch.py elastic group)
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None,
                   help="script printing 'host:slots' lines; polled for "
                        "elastic membership changes")
    p.add_argument("--reset-limit", type=int, default=None,
                   help="max elastic resets before aborting")
    # engine knobs → env (reference: config_parser mapping)
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true")
    p.add_argument("--stall-check-time-seconds", type=float, default=None)
    p.add_argument("--stall-shutdown-time-seconds", type=float, default=None)
    p.add_argument("--no-stall-check", action="store_true")
    p.add_argument("--hierarchical-allreduce", action="store_true",
                   help="two-level topology-aware allreduce "
                        "(HOROVOD_HIERARCHICAL_ALLREDUCE): in-jit, "
                        "reduce-scatter over the fast (ICI) mesh axes + "
                        "cross-slice allreduce + all-gather back; on the "
                        "host data plane, intra-host reduce-scatter -> "
                        "inter-host allreduce among local leaders -> "
                        "intra-host allgather (the engine groups ranks by "
                        "the HOROVOD_CROSS_RANK host index this launcher "
                        "exports per slot)")
    p.add_argument("--small-tensor-algo", choices=("star", "rd"),
                   default=None,
                   help="host data-plane route for sub-express-lane "
                        "allreduces (HOROVOD_SMALL_TENSOR_ALGO): 'star' "
                        "(rank-0 hub) or 'rd' (log2(p) recursive "
                        "doubling, no hub hotspot)")
    p.add_argument("--autotune", action="store_true",
                   help="enable online Bayesian tuning of cycle time / "
                        "fusion threshold / cache (HOROVOD_AUTOTUNE)")
    p.add_argument("--autotune-log", default=None,
                   help="CSV file recording autotune samples "
                        "(HOROVOD_AUTOTUNE_LOG)")
    p.add_argument("--autotune-warmup-samples", type=int, default=None)
    p.add_argument("--autotune-steps", type=int, default=None)
    p.add_argument("--autotune-sample-cycles", type=int, default=None)
    p.add_argument("--start-timeout", type=float, default=120.0)
    p.add_argument("--verbose", action="store_true")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command")
    return p


def _engine_env(args) -> dict:
    env = {}
    if args.fusion_threshold_mb is not None:
        env["HOROVOD_FUSION_THRESHOLD"] = str(
            int(args.fusion_threshold_mb * 1024 * 1024))
    if args.cycle_time_ms is not None:
        env["HOROVOD_CYCLE_TIME"] = str(args.cycle_time_ms)
    if args.cache_capacity is not None:
        env["HOROVOD_CACHE_CAPACITY"] = str(args.cache_capacity)
    if args.timeline_filename:
        env["HOROVOD_TIMELINE"] = args.timeline_filename
    if args.timeline_mark_cycles:
        env["HOROVOD_TIMELINE_MARK_CYCLES"] = "1"
    if args.stall_check_time_seconds is not None:
        env["HOROVOD_STALL_CHECK_TIME_SECONDS"] = str(
            args.stall_check_time_seconds)
    if args.stall_shutdown_time_seconds is not None:
        env["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = str(
            args.stall_shutdown_time_seconds)
    if args.no_stall_check:
        env["HOROVOD_STALL_CHECK_DISABLE"] = "1"
    if args.hierarchical_allreduce:
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    if args.small_tensor_algo is not None:
        env["HOROVOD_SMALL_TENSOR_ALGO"] = args.small_tensor_algo
    if args.autotune:
        env["HOROVOD_AUTOTUNE"] = "1"
    if args.autotune_log:
        env["HOROVOD_AUTOTUNE_LOG"] = args.autotune_log
    if args.autotune_warmup_samples is not None:
        env["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] = \
            str(args.autotune_warmup_samples)
    if args.autotune_steps is not None:
        env["HOROVOD_AUTOTUNE_STEPS"] = str(args.autotune_steps)
    if args.autotune_sample_cycles is not None:
        env["HOROVOD_AUTOTUNE_SAMPLE_CYCLES"] = \
            str(args.autotune_sample_cycles)
    return env


def publish_assignments(kv: KVServer, slots, controller_addr: str,
                        controller_port: int, data_port: int,
                        generation: int = 0, epoch: int = 0):
    """Publish per-slot topology under a generation scope (reference:
    rendezvous GET_RANK_AND_SIZE scope, runner/elastic/rendezvous.py).
    ``epoch`` is the publishing driver's control epoch — embedded so
    workers can fence a lingering pre-crash driver's stale topology."""
    from horovod_tpu.common import kv_keys
    for s in slots:
        kv.put_json(
            kv_keys.rank_and_size(generation, s.hostname, s.local_rank),
            {"rank": s.rank, "size": s.size,
             "local_rank": s.local_rank, "local_size": s.local_size,
             "cross_rank": s.cross_rank, "cross_size": s.cross_size,
             "controller_addr": controller_addr,
             "controller_port": controller_port,
             "controller_data_port": data_port,
             "epoch": epoch}, epoch=epoch)
    kv.put_json(kv_keys.generation(),
                {"generation": generation, "epoch": epoch},
                epoch=epoch)


def launcher_addr(hostnames) -> str:
    """Address workers use to reach the launcher's rendezvous KV server.

    The KV server runs in the *launcher* process — not on the first slot's
    host — so multi-host jobs must be given the launcher's reachable address,
    not the controller's. Resolved via the UDP-connect trick toward a worker
    host (reference: the driver-service NIC probe picks a routable interface,
    runner/driver/driver_service.py:162-258 — getfqdn() is often
    unresolvable or loopback-mapped from remote hosts)."""
    remote = [h for h in hostnames if h not in ("localhost", "127.0.0.1")]
    if not remote:
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect((remote[0], 9))  # no traffic sent; just routes
        return s.getsockname()[0]
    except OSError:
        return socket.getfqdn()
    finally:
        s.close()


def worker_env(slot, controller_addr, controller_port, data_port,
               kv_port, extra, elastic=False, generation=0,
               rendezvous_addr=None, epoch=0) -> dict:
    env = slot.to_env()
    env.update(extra)
    env.update({
        "HOROVOD_CONTROLLER_ADDR": controller_addr,
        "HOROVOD_CONTROLLER_PORT": str(controller_port),
        "HOROVOD_CONTROLLER_DATA_PORT": str(data_port),
        "HOROVOD_RENDEZVOUS_ADDR": rendezvous_addr or controller_addr,
        "HOROVOD_RENDEZVOUS_PORT": str(kv_port),
    })
    if elastic:
        env["HOROVOD_ELASTIC"] = "1"
        env["HOROVOD_ELASTIC_GENERATION"] = str(generation)
        env["HOROVOD_CONTROL_EPOCH"] = str(epoch)
    # replicated control plane: hand workers the full replica endpoint
    # list so their KV clients fail over instead of pinning one endpoint
    from horovod_tpu.common.env_registry import env_str
    replica_eps = env_str("HOROVOD_KV_REPLICA_ENDPOINTS")
    if replica_eps:
        env["HOROVOD_KV_REPLICA_ENDPOINTS"] = replica_eps
    # No JAX_PLATFORMS default: workers auto-detect their accelerator, and
    # only an explicit launcher-side setting is forwarded (same rule as
    # cluster_job.ClusterJobSpec.worker_env).
    if "JAX_PLATFORMS" in os.environ:
        env.setdefault("JAX_PLATFORMS", os.environ["JAX_PLATFORMS"])
    return env


def run_static(args, liveness_check=None, kv=None) -> int:
    """``kv``: optionally a caller-owned (started) KVServer — the caller
    reads worker-published keys (task results) after this returns, and
    owns stop()."""
    host_string = args.hosts or f"localhost:{args.num_proc}"
    host_list = hosts_lib.parse_hosts(host_string)
    np_ = args.num_proc or sum(h.slots for h in host_list)
    slots = hosts_lib.get_host_assignments(host_list, np_)

    bad = check_hosts_ssh({s.hostname for s in slots},
                          getattr(args, "ssh_port", None))
    if bad:
        sys.stderr.write(
            f"[launcher] hosts not ssh-reachable: {', '.join(bad)}\n")
        return 1

    controller_addr = slots[0].hostname if slots[0].hostname != "localhost" \
        else "127.0.0.1"
    controller_port, data_port = free_ports(2)
    own_kv = kv is None
    if own_kv:
        kv = KVServer().start()
    try:
        publish_assignments(kv, slots, controller_addr, controller_port,
                            data_port)
        extra = _engine_env(args)
        rdv_addr = launcher_addr([s.hostname for s in slots])
        workers: List[WorkerProcess] = []
        for s in slots:
            env = worker_env(s, controller_addr, controller_port, data_port,
                             kv.port, extra, rendezvous_addr=rdv_addr)
            workers.append(WorkerProcess(s.hostname, s.rank, args.command,
                                         env))
        return _wait_all(workers, liveness_check)
    finally:
        if own_kv:
            kv.stop()


def _terminate_all(workers):
    """SIGTERM + bounded wait, escalating to SIGKILL for processes that
    trap the signal — the abort paths must return, not raise."""
    workers = list(workers)
    for w in workers:
        w.terminate()
    for w in workers:
        try:
            w.wait(timeout=10)
        except Exception:  # noqa: BLE001 — TimeoutExpired etc.
            w.kill()


def _wait_all(workers: List[WorkerProcess], liveness_check=None) -> int:
    """Fail fast: first non-zero exit kills the rest (reference:
    gloo_run terminate-on-failure). ``liveness_check()`` (if given) runs
    every poll; a non-None error string aborts the job — the programmatic
    run() uses it to enforce start_timeout."""
    rc = 0
    pending = {w.rank: w for w in workers}
    try:
        while pending:
            if liveness_check is not None:
                err = liveness_check()
                if err is not None:
                    sys.stderr.write(f"[launcher] {err}; terminating job\n")
                    _terminate_all(pending.values())
                    return 1
            for rank, w in list(pending.items()):
                code = w.poll()
                if code is None:
                    continue
                del pending[rank]
                if code != 0:
                    sys.stderr.write(
                        f"[launcher] worker rank {rank} on {w.hostname} "
                        f"exited with code {code}; terminating job\n")
                    rc = code
                    _terminate_all(pending.values())
                    return rc
            time.sleep(0.1)
    except KeyboardInterrupt:
        for w in pending.values():
            w.terminate()
        rc = 130
    return rc


def run_elastic(args) -> int:
    from horovod_tpu.common.env_registry import env_bool, env_str
    # Durable control plane: with HOROVOD_KV_DIR set the driver runs as a
    # supervised subprocess — a crashed/killed driver is respawned and
    # rehydrates from the WAL while workers keep training headless.
    if env_str("HOROVOD_KV_DIR") and env_bool("HOROVOD_DRIVER_SUPERVISE"):
        from horovod_tpu.runner.elastic.supervisor import run_supervised
        return run_supervised(args)
    from horovod_tpu.runner.elastic.driver import ElasticDriver
    from horovod_tpu.runner.elastic.discovery import HostDiscoveryScript
    min_np = args.min_np or args.num_proc
    max_np = args.max_np or args.num_proc or min_np
    discovery = HostDiscoveryScript(args.host_discovery_script)
    driver = ElasticDriver(
        discovery=discovery, min_np=min_np, max_np=max_np,
        command=args.command, extra_env=_engine_env(args),
        reset_limit=args.reset_limit, verbose=args.verbose)
    return driver.run(start_timeout=args.start_timeout)


def run_commandline(argv: Optional[List[str]] = None) -> int:
    # Launcher-side logging honors the same HOROVOD_LOG_LEVEL /
    # HOROVOD_LOG_TIMESTAMP knobs as the engine and workers (satellite:
    # one knob set for the whole stack — table in docs/DESIGN.md).
    from horovod_tpu.common.hvd_logging import setup_python_logging
    setup_python_logging()
    parser = make_parser()
    args = parser.parse_args(argv)
    if args.check_build:
        print(check_build(args.verbose))
        return 0
    if args.config_file:
        # re-parse with the file folded into defaults: CLI flags win over
        # the file, the file wins over built-in defaults
        apply_config_file(parser, args.config_file)
        args = parser.parse_args(argv)
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        make_parser().error("no training command given")
    elastic = args.host_discovery_script is not None or \
        (args.min_np is not None or args.max_np is not None)
    if elastic and not args.host_discovery_script:
        make_parser().error("elastic mode requires --host-discovery-script")
    if not elastic and not (args.num_proc or args.hosts):
        make_parser().error("specify -np and/or -H")
    return run_elastic(args) if elastic else run_static(args)


def main():
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
