"""Chaos harness for subprocess fault-tolerance tests + the simulated
elastic cluster.

Deterministic building blocks the recovery tests compose: kill a worker by
command-line pattern, freeze a process (a simulated network partition / KV
stall — SIGSTOP leaves its sockets open but unresponsive, exactly what a
partitioned peer looks like), and a flaky HTTP server that refuses the
first N connections (the retry-path fixture).

ISSUE 9 adds the **simulated elastic cluster** (:class:`SimCluster` +
:func:`sim_world`): N in-process "ranks", each a thread holding a real
``elastic.ShardedState``, wired together by an in-memory collective bus
that stands in for the engine's eager data plane. Every protocol layer the
real path runs — descriptor allgather, reshard-plan alltoall, buddy
replication at commit, drain handoff, replicated broadcast from the
most-advanced holder — executes the REAL code; only the wire is simulated.
That is what lets the chaos soak run at 64 ranks inside one pytest worker
while everything subprocess-based tops out at 4-8.

Not a test module (no ``test_`` prefix): imported by
tests/test_fault_tolerance.py, tests/test_elastic_recovery.py, and
tests/test_chaos_soak.py.
"""

from __future__ import annotations

import contextlib
import os
import signal
import subprocess
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

from horovod_tpu.common import journal


def _descends_from(pid: int, ancestor: int) -> bool:
    """Whether ``ancestor`` is ``pid`` or one of its live ancestors."""
    while pid > 1:
        if pid == ancestor:
            return True
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().rsplit(")", 1)[1].split()[1])  # ppid
        except (OSError, ValueError, IndexError):
            return False
    return False


def find_worker_pids(pattern: str, under: Optional[int] = None) -> List[int]:
    """PIDs of live processes whose command line matches ``pattern``
    (pgrep -f semantics). ``under`` keeps only descendants of that pid: a
    pattern such as the driver's or a KV replica's also matches what a test
    in another xdist worker has started, and a kill must not reach it."""
    out = subprocess.run(["pgrep", "-f", pattern], capture_output=True,
                         text=True)
    pids = [int(p) for p in out.stdout.split()]
    return [p for p in pids if under is None or _descends_from(p, under)]


def kill_workers(pattern: str, sig: int = signal.SIGKILL,
                 count: Optional[int] = None,
                 under: Optional[int] = None) -> List[int]:
    """Kill up to ``count`` (default: all) processes matching ``pattern``
    (below ``under``, if given). Returns the PIDs actually signalled."""
    pids = find_worker_pids(pattern, under)
    if count is not None:
        pids = pids[-count:]
    killed = []
    for pid in pids:
        try:
            os.kill(pid, sig)
            killed.append(pid)
        except ProcessLookupError:
            pass
    return killed


class Partition:
    """Freeze a process for the scope of the context (SIGSTOP/SIGCONT).

    From its peers' point of view the process is network-partitioned: its
    sockets stay open but nothing flows — the shape of failure that
    timeouts and stall detection exist for. Works on a worker (partitioned
    rank) or on the launcher (stalled rendezvous KV)."""

    def __init__(self, pid: int):
        self.pid = pid

    def __enter__(self):
        os.kill(self.pid, signal.SIGSTOP)
        return self

    def __exit__(self, *exc):
        try:
            os.kill(self.pid, signal.SIGCONT)
        except ProcessLookupError:
            pass
        return False


def stall(pid: int, seconds: float):
    """Partition a process for a fixed duration, then heal it."""
    with Partition(pid):
        time.sleep(seconds)


class FlakyHTTPServer:
    """HTTP server that drops the first ``fail_first`` connections cold
    (the client sees a reset — the transient-failure class retries must
    absorb), then serves ``body`` with status 200. ``requests_seen`` counts
    every attempt, so tests assert the retry actually happened."""

    def __init__(self, fail_first: int, body: bytes = b"{}"):
        self.fail_first = fail_first
        self.body = body
        self.requests_seen = 0
        self._lock = threading.Lock()
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):
                pass

            def _handle(self):
                with server._lock:
                    server.requests_seen += 1
                    n = server.requests_seen
                if n <= server.fail_first:
                    # slam the connection shut mid-request: the client gets
                    # a reset/RemoteDisconnected, not an HTTP status
                    self.connection.close()
                    return
                data = server.body
                self.send_response(200)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            do_GET = _handle
            do_PUT = _handle

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def __enter__(self):
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
        return False


class ControlPlane:
    """A durable rendezvous KV with a kill/restart surface — the chaos
    soak's control-plane sidecar (ISSUE 10).

    ``kill()`` drops the server the way a SIGKILLed driver does (no
    snapshot, no graceful anything beyond what the per-record WAL flush
    already guaranteed); ``restart()`` brings a fresh incarnation up over
    the same directory and the same port, replaying the WAL and bumping
    the persistent control epoch — exactly what a supervisor-respawned
    driver's KV does. ``store()`` snapshots the visible state so tests
    can assert byte-identical recovery."""

    def __init__(self, kv_dir: str, port: int = 0):
        from horovod_tpu.runner.http_kv import KVServer
        self.kv_dir = kv_dir
        self.kv = KVServer(port=port, kv_dir=kv_dir).start()
        self.port = self.kv.port
        self.epochs = [self.kv.epoch]

    def kill(self):
        # KVServer's durability is synchronous (append+flush per
        # mutation), so a hard driver kill and a socket close lose the
        # same amount: nothing that was acknowledged.
        self.kv.stop()

    def restart(self):
        from horovod_tpu.runner.http_kv import KVServer
        self.kv = KVServer(port=self.port, kv_dir=self.kv_dir).start()
        self.epochs.append(self.kv.epoch)
        return self.kv

    def kill_and_restart(self):
        self.kill()
        return self.restart()

    def store(self) -> Dict[str, object]:
        return {k: self.kv.get_json(k) for k in self.kv.keys()}

    def close(self):
        try:
            self.kv.stop()
        except Exception:  # noqa: BLE001 — already dead is fine
            pass


def kv_replica_procs(under: Optional[int] = None) -> Dict[int, List[str]]:
    """PID -> argv for every live ``replica_kv`` subprocess below ``under``
    (the chaos surface for supervised runs: argv carries ``--id`` and the
    full ``--endpoints`` list, so tests can find the leader from outside)."""
    out: Dict[int, List[str]] = {}
    for pid in find_worker_pids("horovod_tpu.runner.replica_kv", under):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out[pid] = f.read().decode().split("\x00")
        except OSError:
            continue
    return out


def kill_kv_leader(timeout: float = 30.0, sig: int = signal.SIGKILL,
                   under: Optional[int] = None):
    """SIGKILL the KV replica subprocess currently holding the lease, among
    the replicas below ``under`` (the test's own launcher). Returns
    ``(pid, replica_id)``; asserts a replica fleet exists."""
    from horovod_tpu.runner.replica_kv import wait_for_leader
    procs = kv_replica_procs(under)
    endpoints = None
    for argv in procs.values():
        if "--endpoints" in argv:
            endpoints = argv[argv.index("--endpoints") + 1].split(",")
            break
    assert endpoints, "no replica_kv subprocess found"
    st = wait_for_leader(endpoints, timeout=timeout)
    assert st is not None, "no KV leader reachable"
    lid = int(st["id"])
    for pid, argv in procs.items():
        if "--id" in argv and int(argv[argv.index("--id") + 1]) == lid:
            os.kill(pid, sig)
            return pid, lid
    raise AssertionError(f"leader replica {lid} has no live process")


class ReplicatedControlPlane:
    """N ``replica_kv`` subprocesses + a failover client — the
    replicated analog of :class:`ControlPlane` (ISSUE 19).

    ``kill_leader()`` SIGKILLs the leaseholder's process (a follower
    must win the next election and bump the epoch); ``partition_leader``
    SIGSTOPs it for the scope of the returned context — its sockets stay
    open but silent, the classic split-brain probe — and SIGCONTs on
    exit, after which the deposed leader must rejoin as a follower and
    resync to byte-identical state."""

    def __init__(self, base_dir: str, replicas: int = 3,
                 lease_seconds: float = 0.4):
        from horovod_tpu.runner import replica_kv
        from horovod_tpu.runner.http_kv import KVClient
        from horovod_tpu.runner.launch import free_port
        self._rk = replica_kv
        self.base_dir = base_dir
        self.lease = lease_seconds
        self.endpoints = [f"127.0.0.1:{free_port()}"
                          for _ in range(replicas)]
        self.procs = {
            i: replica_kv.spawn_replica(i, self.endpoints, base_dir,
                                        lease_seconds=lease_seconds)
            for i in range(replicas)}
        st = replica_kv.wait_for_leader(self.endpoints, timeout=30.0)
        assert st is not None, "no KV leader elected at bootstrap"
        self.epochs = [int(st["epoch"])]
        host, _, port = self.endpoints[0].rpartition(":")
        self.client = KVClient(host, int(port), endpoints=self.endpoints)

    def leader(self, timeout: float = 30.0) -> dict:
        st = self._rk.wait_for_leader(self.endpoints, timeout=timeout)
        assert st is not None, "no KV leader emerged"
        return st

    def await_leader_other_than(self, old_id: int,
                                timeout: float = 30.0) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = self._rk.wait_for_leader(
                self.endpoints, timeout=max(0.5, deadline -
                                            time.monotonic()))
            if st is not None and int(st["id"]) != old_id:
                self.epochs.append(int(st["epoch"]))
                return st
            time.sleep(0.1)
        raise AssertionError(
            f"no leader other than replica {old_id} within {timeout}s")

    def kill_leader(self) -> int:
        lid = int(self.leader()["id"])
        self.procs[lid].kill()
        self.procs[lid].wait()
        return lid

    def respawn(self, replica_id: int):
        self.procs[replica_id] = self._rk.spawn_replica(
            replica_id, self.endpoints, self.base_dir,
            lease_seconds=self.lease)

    @contextlib.contextmanager
    def partition_leader(self):
        lid = int(self.leader()["id"])
        with Partition(self.procs[lid].pid):
            yield lid

    def statuses(self) -> Dict[str, Optional[dict]]:
        return self._rk.replica_statuses(self.endpoints)

    def store_hashes(self, settle: float = 0.0) -> Dict[int, str]:
        """``replica_id -> store_hash`` for live replicas; with
        ``settle`` polls until every live replica reports the same hash
        (resync convergence) or the deadline passes."""
        deadline = time.monotonic() + settle
        while True:
            live = [st for st in self.statuses().values() if st]
            hashes = {int(st["id"]): st["store_hash"] for st in live}
            converged = (len(live) == len(self.endpoints)
                         and len(set(hashes.values())) <= 1)
            if converged or time.monotonic() > deadline:
                return hashes
            time.sleep(0.1)

    def replica_dirs(self) -> List[str]:
        return [self._rk.replica_dir(self.base_dir, i)
                for i in range(len(self.endpoints))]

    def close(self):
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
        for p in self.procs.values():
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass


# ===========================================================================
# Simulated elastic cluster (ISSUE 9): real ShardedState protocol over an
# in-memory collective bus, at world sizes subprocesses can't reach.


class _Bus:
    """One resize/training phase's collective rendezvous: every member
    thread deposits its payload under a shared op name and blocks until
    the full membership has contributed — the in-memory analog of the
    engine's negotiate-then-execute cycle. Op names must be unique within
    a phase (true of the real protocol's names too)."""

    def __init__(self, world: int, timeout: float = 60.0):
        self.world = world
        self.timeout = timeout
        self._cv = threading.Condition()
        self._rounds: Dict[str, dict] = {}
        self.tls = threading.local()

    def rank(self) -> int:
        return self.tls.rank

    def gather(self, name: str, payload) -> Dict[int, object]:
        rank = self.rank()
        with self._cv:
            r = self._rounds.setdefault(name, {"in": {}})
            assert rank not in r["in"], f"op {name} reused by rank {rank}"
            r["in"][rank] = payload
            self._cv.notify_all()
            ok = self._cv.wait_for(
                lambda: len(r["in"]) == self.world, timeout=self.timeout)
            if not ok:
                raise TimeoutError(
                    f"bus op {name}: {len(r['in'])}/{self.world} arrived")
            return dict(r["in"])


@contextlib.contextmanager
def sim_world(bus_ref: dict):
    """Patch the elastic state's collective/topology surface onto the sim
    bus. ``bus_ref['bus']`` is swapped per phase; member threads carry
    their rank in the bus TLS. Everything else — plan math, pack/unpack,
    buddy bookkeeping, source assignment — is the real code."""
    import copy as _copy

    import numpy as np

    from horovod_tpu.common import basics
    from horovod_tpu.jax import elastic, functions
    from horovod_tpu.runner.elastic import preempt
    from horovod_tpu.runner.elastic import worker as elastic_worker

    handoffs: Dict[tuple, dict] = {}  # (world, old_rank) -> stacks

    def bus():
        return bus_ref["bus"]

    def _seq_name(prefix):
        tls = bus().tls
        n = getattr(tls, "seq", 0)
        tls.seq = n + 1
        return f"{prefix}#{n}"

    def allgather_object(obj, name=None):
        got = bus().gather(name or _seq_name("ag"), obj)
        return [got[r] for r in sorted(got)]

    def broadcast_object(obj, root_rank=0, name=None):
        got = bus().gather(name or _seq_name("bo"), obj)
        return _copy.deepcopy(got[root_rank])

    def broadcast_parameters(params, root_rank=0):
        import jax
        got = bus().gather(_seq_name("bp"), params)
        return jax.tree_util.tree_map(
            lambda x: np.asarray(x).copy(), got[root_rank])

    def ragged_alltoall(payload, splits, name):
        got = bus().gather(name, (np.asarray(payload, np.uint8),
                                  list(splits)))
        me = bus().rank()
        out = []
        for src in sorted(got):
            buf, sp = got[src]
            off = sum(sp[:me])
            out.append(buf[off:off + sp[me]].copy())
        return out

    def fetch_handoff(world, old_rank, client=None):
        return handoffs.get((world, old_rank))

    orig = {
        "size": basics.size, "rank": basics.rank,
        "single": basics._single_process,
        "init": basics.is_initialized,
        "ago": functions.allgather_object,
        "bco": functions.broadcast_object,
        "bcp": functions.broadcast_parameters,
        "a2a": elastic._ragged_alltoall,
        "fh": preempt.fetch_handoff,
        "iew": elastic_worker.is_elastic_worker,
    }
    basics.size = lambda: bus().world
    basics.rank = lambda: bus().rank()
    basics._single_process = lambda: bus().world == 1
    basics.is_initialized = lambda: True
    functions.allgather_object = allgather_object
    functions.broadcast_object = broadcast_object
    functions.broadcast_parameters = broadcast_parameters
    elastic._ragged_alltoall = ragged_alltoall
    preempt.fetch_handoff = fetch_handoff
    elastic_worker.is_elastic_worker = lambda: True
    try:
        yield handoffs
    finally:
        basics.size = orig["size"]
        basics.rank = orig["rank"]
        basics._single_process = orig["single"]
        basics.is_initialized = orig["init"]
        functions.allgather_object = orig["ago"]
        functions.broadcast_object = orig["bco"]
        functions.broadcast_parameters = orig["bcp"]
        elastic._ragged_alltoall = orig["a2a"]
        preempt.fetch_handoff = orig["fh"]
        elastic_worker.is_elastic_worker = orig["iew"]


class SimWorker:
    """One simulated rank: a real ShardedState plus the deterministic toy
    training rule the golden model replays."""

    def __init__(self, cluster, fresh_world: int):
        import numpy as np

        from horovod_tpu.jax import elastic
        c = cluster
        shard = c.shard_len(fresh_world)
        self.state = elastic.ShardedState(
            template=[np.zeros(c.n_params, np.float32)],
            sharded={"opt": {"m": np.zeros(shard, np.float32),
                             "v": np.zeros(shard, np.float32)}},
            block_size=c.block_size,
            params=np.zeros(c.n_params, np.float32),
            step=0)
        self.cluster = c

    def train_step(self, rank: int, world: int):
        """One deterministic step (identical math to the golden model):
        replicated params follow the full gradient, the sharded moments
        integrate only this rank's slice of it."""
        import numpy as np
        c = self.cluster
        g = c.step_grad(self.state.step)
        self.state.params = self.state.params - c.lr * g
        gp = np.zeros(c.padded_len(world), np.float32)
        gp[:c.n_params] = g
        shard = c.shard_len(world)
        lo = rank * shard
        self.state.opt = {
            "m": 0.9 * self.state.opt["m"] + gp[lo:lo + shard],
            "v": 0.99 * self.state.opt["v"] + gp[lo:lo + shard] ** 2,
        }
        self.state.step += 1


class SimCluster:
    """N simulated elastic ranks + a golden single-copy replica.

    Drives the real ShardedState protocol through phases:

    - ``run_steps(k, commit_every)`` — lockstep toy training (threads; the
      buddy replication at commit is a real bus collective),
    - ``kill(i)`` / ``drain(i)`` / ``rejoin(n)`` / ``partition()`` —
      membership events,
    - ``resize()`` — the generation change: every member syncs, shards
      transfer live, and the golden model says exactly what every byte
      must now be.

    Assertions available after any resize: ``check_consistency()``
    verifies step counters (live resume — never the last commit), params
    (exact), and moments (live for survivors/drains, committed for
    buddy-recovered kills, zero for truly lost ranges).
    """

    def __init__(self, world: int, n_params: int = 3000,
                 block_size: int = 64, lr: float = 0.05, seed: int = 0):
        import numpy as np
        self.n_params = n_params
        self.block_size = block_size
        self.lr = lr
        self._rng = np.random.RandomState(seed)
        self.members: List[SimWorker] = []
        self.bus_ref: dict = {}
        self._grad_cache: Dict[int, object] = {}
        # golden replica (padded to the widest layout ever needed is not
        # required: moments are tracked at full unpadded length)
        self.g_params = np.zeros(n_params, np.float32)
        self.g_m = np.zeros(n_params, np.float32)
        self.g_v = np.zeros(n_params, np.float32)
        self.g_step = 0
        self.committed_m = self.g_m.copy()
        self.committed_v = self.g_v.copy()
        # ranges whose moments were truly lost (kill without buddy) as
        # (lo, hi) — folded into the golden model as zeros at resize
        self.lost_ranges: List[tuple] = []
        self._pending_kills: List[tuple] = []
        self.last_resize_stats: dict = {}
        self._ctx = None
        self.generation = 0
        self.handoffs: Dict[tuple, dict] = {}
        with self._phase(world):
            for r in range(world):
                self.bus_ref["bus"].tls.rank = r
                self.members.append(SimWorker(self, world))
        self.resize()  # round 0: identity sync, everyone fresh

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- geometry / golden math ---------------------------------------------

    def shard_len(self, world: int) -> int:
        from horovod_tpu.parallel import zero
        import numpy as np
        g = zero._group_leaves([np.zeros(self.n_params, np.float32)],
                               world, self.block_size)[0]
        return g.shard

    def padded_len(self, world: int) -> int:
        return self.shard_len(world) * world

    def step_grad(self, step):
        import numpy as np
        s = int(step)
        if s not in self._grad_cache:
            self._grad_cache[s] = np.random.RandomState(
                1000 + s).randn(self.n_params).astype(np.float32)
        return self._grad_cache[s]

    @contextlib.contextmanager
    def _phase(self, world: int):
        self.bus_ref["bus"] = _Bus(world)
        if self._ctx is None:
            self._ctx = sim_world(self.bus_ref)
            self.handoffs = self._ctx.__enter__()
        yield

    def close(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def _run_members(self, fn):
        """Run ``fn(idx, member)`` on every member concurrently (the bus
        collectives need all of them in flight)."""
        errs = []

        def runner(i, m):
            self.bus_ref["bus"].tls.rank = i
            self.bus_ref["bus"].tls.seq = 0
            try:
                fn(i, m)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errs.append((i, e))

        threads = [threading.Thread(target=runner, args=(i, m), daemon=True)
                   for i, m in enumerate(self.members)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        alive = [t for t in threads if t.is_alive()]
        if alive:
            raise TimeoutError(f"{len(alive)} sim members hung")
        if errs:
            raise errs[0][1]

    # -- phases --------------------------------------------------------------

    def run_steps(self, k: int, commit_every: int = 0):
        """k lockstep steps on every member; with ``commit_every`` the
        members commit() (buddy replication collective) on that cadence,
        and the golden committed snapshot advances with them."""
        world = len(self.members)
        with self._phase(world):
            def body(i, m):
                for s in range(k):
                    m.train_step(i, world)
                    if commit_every and (s + 1) % commit_every == 0:
                        m.state.commit()
            self._run_members(body)
        for s in range(k):
            g = self.step_grad(self.g_step)
            self.g_params = self.g_params - self.lr * g
            self.g_m = 0.9 * self.g_m + g
            self.g_v = 0.99 * self.g_v + g * g
            self.g_step += 1
            if commit_every and (s + 1) % commit_every == 0:
                self.committed_m = self.g_m.copy()
                self.committed_v = self.g_v.copy()

    def commit_all(self):
        world = len(self.members)
        with self._phase(world):
            self._run_members(lambda i, m: m.state.commit())
        self.committed_m = self.g_m.copy()
        self.committed_v = self.g_v.copy()

    def kill(self, idx: int):
        """Hard kill (no notice): the member's live shard dies with it.
        Its committed state survives only on its ring buddy — whether that
        buddy is still alive is judged at resize time (the buddy may die
        in the same incident)."""
        victim = self.members[idx]
        old_rank = victim.state._old_rank
        self._pending_kills.append((victim.state._world, old_rank))
        del self.members[idx]
        journal.emit("driver", "worker_exit", generation=self.generation,
                     reason="failure", exit_code=-9,
                     host=f"sim{old_rank}", local_rank=old_rank)

    def drain(self, idx: int):
        """Preemption notice: the member hands off its LIVE shard (the
        real handoff payload) and departs cleanly."""
        victim = self.members[idx]
        world, old_rank, payload = victim.state.shard_handoff_payload()
        journal.emit("worker", "drain_announce",
                     generation=self.generation,
                     host=f"sim{old_rank}", local_rank=old_rank)
        if payload:
            self.handoffs[(world, old_rank)] = {
                "combined": payload["combined"]}
        del self.members[idx]
        journal.emit("driver", "worker_exit", generation=self.generation,
                     reason="drained", exit_code=0,
                     host=f"sim{old_rank}", local_rank=old_rank)

    def kill_during_drain(self, idx: int):
        """The drain race: the preemption notice lands (the drain is
        announced in the journal and to the driver) but the host is
        reaped before the live-shard handoff completes — exactly a too-
        short preemption window. The shard falls back to its ring
        buddy's committed copy, like a plain kill."""
        victim = self.members[idx]
        old_rank = victim.state._old_rank
        journal.emit("worker", "drain_announce",
                     generation=self.generation,
                     host=f"sim{old_rank}", local_rank=old_rank)
        self.kill(idx)

    def rejoin(self, n: int = 1):
        """n fresh joiners (new hosts after a cooldown / replacement spot
        capacity): constructed at the post-join world size, round 0."""
        new_world = len(self.members) + n
        self.bus_ref["bus"] = _Bus(new_world)
        for _ in range(n):
            self.bus_ref["bus"].tls.rank = len(self.members)
            self.members.append(SimWorker(self, new_world))

    def resize(self) -> float:
        """The generation change: every member ShardedState.sync()s over
        the current membership. Folds pending kill losses into the golden
        model (committed values where a buddy replica or handoff serves
        the dead shard, zeros where nothing does) so later training
        continues from the exact state the cluster actually holds.
        Returns the wall-clock recovery time."""
        for old_world, old_rank in self._pending_kills:
            shard = self.shard_len(old_world)
            lo = old_rank * shard
            hi = min(lo + shard, self.n_params)
            if lo >= hi:
                continue
            recovered = (old_world, old_rank) in self.handoffs or any(
                (m.state._buddy or {}).get("of") == old_rank and
                (m.state._buddy or {}).get("world") == old_world
                for m in self.members)
            if recovered:
                self.g_m[lo:hi] = self.committed_m[lo:hi]
                self.g_v[lo:hi] = self.committed_v[lo:hi]
            else:
                self.g_m[lo:hi] = 0.0
                self.g_v[lo:hi] = 0.0
                self.lost_ranges.append((lo, hi))
        self._pending_kills = []
        world = len(self.members)
        t0 = time.monotonic()
        with self._phase(world):
            self._run_members(lambda i, m: m.state.sync())
        dt = time.monotonic() - t0
        self.generation += 1
        journal.emit("driver", "resize", generation=self.generation,
                     slots=world, hosts=world,
                     first=(self.generation == 1))
        self.last_resize_stats = {"recovery_seconds": dt, "world": world}
        return dt

    def partition_and_heal(self):
        """A transient partition: every rank aborts mid-step, nobody dies,
        membership is unchanged — the resize must take the identity fast
        path (no shard movement) and lose nothing."""
        return self.resize()

    # -- assertions -----------------------------------------------------------

    def reconstruct(self):
        """Reassemble the full (m, v, params, step) view from the
        members' shards."""
        import numpy as np
        world = len(self.members)
        shard = self.shard_len(world)
        m_full = np.zeros(self.padded_len(world), np.float32)
        v_full = np.zeros(self.padded_len(world), np.float32)
        for m in self.members:
            r = m.state._old_rank
            m_full[r * shard:(r + 1) * shard] = m.state.opt["m"]
            v_full[r * shard:(r + 1) * shard] = m.state.opt["v"]
        return (m_full[:self.n_params], v_full[:self.n_params],
                self.members[0].state.params, self.members[0].state.step)

    def check_consistency(self):
        """Assert the reassembled cluster state matches the golden model:
        live step (never a rollback), exact params, moments per the loss
        matrix (resize() already folded kill losses into the golden)."""
        import numpy as np
        m_full, v_full, params, step = self.reconstruct()
        assert int(step) == self.g_step, \
            f"step rolled back: {step} != live {self.g_step}"
        np.testing.assert_allclose(params, self.g_params, rtol=0, atol=0)
        np.testing.assert_allclose(m_full, self.g_m, rtol=0, atol=1e-6)
        np.testing.assert_allclose(v_full, self.g_v, rtol=0, atol=1e-6)
        for m in self.members:
            assert int(m.state.step) == self.g_step
