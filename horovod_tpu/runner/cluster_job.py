"""Executor-backed job orchestration core.

Reference analog: the pieces horovod/spark/runner.py:195-302 and
horovod/ray/runner.py:45-235 share — allocate the coordination endpoints on
the driver, hand every remote task the env contract, run the user function
on all tasks simultaneously, collect per-rank results.

The cluster schedulers themselves (Spark barrier stage, Ray actors) only
provide "run this closure on N tasks at once"; everything framework-
specific lives here so the spark/ray layers stay thin adapters and the
orchestration is testable with a local-process backend.
"""

from __future__ import annotations

import os
import socket
import uuid
from typing import Any, Callable, Dict, List, Optional, Tuple

from horovod_tpu.runner.launch import free_ports, launcher_addr


def default_driver_addr() -> str:
    """Address remote tasks can use to reach a KV server bound on this
    (driver) host: the default-route interface's IP via the UDP-connect
    trick (no traffic sent); on air-gapped boxes with no default route,
    the hostname's resolved address; loopback as the last resort.
    Reference analog: the driver-service NIC probe picking a routable
    interface (runner/driver/driver_service.py:162-258)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 9))
        return s.getsockname()[0]
    except OSError:
        try:
            ip = socket.gethostbyname(socket.gethostname())
            if not ip.startswith("127."):
                return ip
        except OSError:
            pass
        return "127.0.0.1"
    finally:
        s.close()


def _self_addr_toward(peer_addr: str) -> str:
    """This host's address as seen on the route toward ``peer_addr``."""
    if peer_addr in ("127.0.0.1", "localhost", "::1"):
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect((peer_addr, 9))
        return s.getsockname()[0]
    except OSError:
        return socket.getfqdn()
    finally:
        s.close()


class ClusterJobSpec:
    """Endpoints + per-rank env for one executor-backed job.

    Two endpoint modes:
    - ``rendezvous=(kv_addr, kv_port)``: dynamic — the rank-0 *task*
      allocates the controller/data ports on its own host at startup and
      publishes them (plus its routable address) through the driver's KV;
      other tasks poll. This avoids the driver-side free_port() TOCTOU
      (the driver may not even share a host with rank 0 under Spark/Ray)
      and needs no placement knowledge up front.
    - explicit ``controller_addr``: static — the driver allocates ports and
      bakes them into the env (single-host or caller-managed placement).
    """

    def __init__(self, num_proc: int,
                 controller_addr: Optional[str] = None,
                 extra_env: Optional[Dict[str, str]] = None,
                 rendezvous: Optional[Tuple[str, int]] = None):
        if num_proc < 1:
            raise ValueError(f"num_proc must be >= 1, got {num_proc}")
        self.num_proc = num_proc
        self.rendezvous = rendezvous
        self.job_id = uuid.uuid4().hex[:12]
        if rendezvous is not None and controller_addr is None:
            self.controller_addr = None
            self.controller_port = None
            self.data_port = None
        else:
            # Rank 0's engine binds the controller port on ITS host.
            # 127.0.0.1 is only correct when every task shares the driver's
            # host — warn rather than let remote workers spin on loopback.
            if controller_addr is None and num_proc > 1:
                import warnings
                warnings.warn(
                    "ClusterJobSpec without controller_addr or rendezvous "
                    "assumes all tasks run on the driver's host "
                    "(127.0.0.1); pass rendezvous=(kv_addr, kv_port) for "
                    "multi-node schedulers")
            self.controller_addr = controller_addr or launcher_addr([])
            self.controller_port, self.data_port = free_ports(2)
        self.extra_env = dict(extra_env or {})

    def worker_env(self, rank: int, local_rank: Optional[int] = None,
                   local_size: Optional[int] = None) -> Dict[str, str]:
        """Env for one task. Without explicit placement info the spec's
        single-host assumption applies (local == global); schedulers that
        know node placement (reference RayExecutor groups workers by node
        IP) should pass real local_rank/local_size."""
        if local_rank is None:
            local_rank = rank
        if local_size is None:
            local_size = self.num_proc
        env = dict(self.extra_env)
        env.update({
            "HOROVOD_RANK": str(rank),
            "HOROVOD_SIZE": str(self.num_proc),
            "HOROVOD_LOCAL_RANK": str(local_rank),
            "HOROVOD_LOCAL_SIZE": str(local_size),
        })
        if self.controller_addr is not None:
            env.update({
                "HOROVOD_CONTROLLER_ADDR": self.controller_addr,
                "HOROVOD_CONTROLLER_PORT": str(self.controller_port),
                "HOROVOD_CONTROLLER_DATA_PORT": str(self.data_port),
            })
        if self.rendezvous is not None:
            env.update({
                "HOROVOD_RENDEZVOUS_ADDR": self.rendezvous[0],
                "HOROVOD_RENDEZVOUS_PORT": str(self.rendezvous[1]),
                "HOROVOD_CLUSTER_JOB": self.job_id,
            })
        # Deliberately no JAX_PLATFORMS default: on a TPU pod the workers
        # must auto-detect their accelerator; only an explicit driver
        # setting (or extra_env) is forwarded.
        if "JAX_PLATFORMS" in os.environ:
            env.setdefault("JAX_PLATFORMS", os.environ["JAX_PLATFORMS"])
        return env


def _negotiate_controller(env: Dict[str, str]) -> Dict[str, str]:
    """Task-side endpoint negotiation (dynamic mode): rank 0 allocates the
    controller/data ports on its own host — where its engine will bind
    moments later — and publishes them; everyone else polls. Returns the
    controller env entries."""
    from horovod_tpu.runner.http_kv import (KVClient,
                                            replica_endpoints_from_env)
    kv_addr = env["HOROVOD_RENDEZVOUS_ADDR"]
    client = KVClient(kv_addr, int(env["HOROVOD_RENDEZVOUS_PORT"]),
                      endpoints=replica_endpoints_from_env())
    # the round scopes the key per execution: long-lived actor pools
    # (RayExecutor) negotiate afresh on every run(), and ranks >0 must not
    # read a previous run's — now closed — endpoint
    rnd = env.get("HOROVOD_CLUSTER_ROUND", "0")
    from horovod_tpu.common import kv_keys
    key = kv_keys.cluster_controller(env["HOROVOD_CLUSTER_JOB"], rnd)
    if int(env["HOROVOD_RANK"]) == 0:
        port, data_port = free_ports(2)
        info = {"addr": _self_addr_toward(kv_addr), "port": port,
                "data_port": data_port}
        client.put_json(key, info)
    else:
        info = client.get_json(key, timeout=120.0)
        if info is None:
            raise RuntimeError(
                "rank 0 never published the controller endpoint "
                f"(KV {kv_addr}, job {env['HOROVOD_CLUSTER_JOB']})")
    return {
        "HOROVOD_CONTROLLER_ADDR": str(info["addr"]),
        "HOROVOD_CONTROLLER_PORT": str(info["port"]),
        "HOROVOD_CONTROLLER_DATA_PORT": str(info["data_port"]),
    }


def task_body(spec_env: Dict[str, str], fn: Callable, args: tuple,
              kwargs: dict) -> Any:
    """Runs inside the remote task: apply the env contract, execute, and
    return the result (the scheduler ships it back)."""
    spec_env = dict(spec_env)
    if ("HOROVOD_CONTROLLER_PORT" not in spec_env and
            "HOROVOD_CLUSTER_JOB" in spec_env):
        spec_env.update(_negotiate_controller(spec_env))
    os.environ.update(spec_env)
    # executors recycle processes: a previous job's context must not leak
    from horovod_tpu.common import basics
    basics.shutdown()
    return fn(*args, **kwargs)


def run_local_processes(spec: ClusterJobSpec, fn: Callable, args: tuple,
                        kwargs: dict, timeout: float = 300.0) -> List[Any]:
    """Local-process backend: the test double for a cluster scheduler, and
    a working fallback when neither Spark nor Ray is around. Semantics
    match the real backends: N simultaneous tasks, env contract applied,
    per-rank results in rank order."""
    import cloudpickle
    import subprocess
    import sys
    import tempfile

    with tempfile.TemporaryDirectory(prefix="hvdtpu_cluster_") as td:
        payload = os.path.join(td, "task.pkl")
        with open(payload, "wb") as f:
            cloudpickle.dump((fn, args, kwargs), f)
        script = os.path.join(td, "task.py")
        with open(script, "w") as f:
            f.write(
                "import sys, os, cloudpickle\n"
                f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))!r})\n"  # noqa: E501
                "from horovod_tpu.runner import cluster_job\n"
                f"fn, args, kwargs = cloudpickle.load(open({payload!r}, 'rb'))\n"  # noqa: E501
                "rank = int(sys.argv[1])\n"
                # route through task_body so dynamic-endpoint negotiation
                # runs exactly as it would under a real scheduler
                "result = cluster_job.task_body(dict(os.environ), fn, args, kwargs)\n"  # noqa: E501
                f"cloudpickle.dump(result, open(os.path.join({td!r}, f'r{{rank}}.pkl'), 'wb'))\n")  # noqa: E501
        procs = []
        try:
            for r in range(spec.num_proc):
                env = dict(os.environ)
                env.update(spec.worker_env(r))
                procs.append(subprocess.Popen(
                    [sys.executable, script, str(r)], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
            import time
            deadline = time.monotonic() + timeout
            outs = []
            for p in procs:
                left = max(1.0, deadline - time.monotonic())
                outs.append(p.communicate(timeout=left)[0].decode())
        finally:
            # a stuck or failed rank must not leave peers blocked in
            # rendezvous holding the ports
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for r, (p, out) in enumerate(zip(procs, outs)):
            if p.returncode != 0:
                raise RuntimeError(f"task rank {r} failed:\n{out}")
        results = []
        for r in range(spec.num_proc):
            with open(os.path.join(td, f"r{r}.pkl"), "rb") as f:
                results.append(cloudpickle.load(f))
        return results
