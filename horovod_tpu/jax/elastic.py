"""Elastic training state: commit / restore / sync + the retry loop.

Reference analog: horovod/common/elastic.py (State :33-105, run wrapper
:147-168) and horovod/torch/elastic/state.py (TorchState handlers). The
semantics carried over exactly:

- ``State.commit()``  — checkpoint in memory + check for pending host
  updates (raises HostsUpdatedInterrupt at a safe point).
- ``State.restore()`` — roll back to the last commit after a failure.
- ``State.sync()``    — broadcast state from a rank that has it (rank 0)
  after a re-initialization.
- ``run(fn)``         — retry loop: HorovodInternalError → restore + reinit;
  HostsUpdatedInterrupt → reinit, keep state.

Checkpoint-free resize (:class:`ShardedState`): the reference semantics
assume REPLICATED state — broadcast-from-rank-0 restores any worker. Under
ZeRO-1 (parallel/zero.py, arXiv:2004.13336) no single rank holds the full
optimizer state, so a resize must instead re-partition the live shards:
``ShardedState.sync()`` gathers per-rank layout descriptors, computes the
old-shards→new-shards transfer plan (``zero.reshard_plan``), and executes
it over the eager ragged alltoall — int8-compressed when
``HOROVOD_RESHARD_COMPRESSION=int8``. Training resumes from the LIVE step
(no rollback to the last ``commit()``); a hard-killed rank's shard is
recovered from its drain handoff or its ring-buddy's committed replica.
"""

from __future__ import annotations

import copy
import queue
import time as _time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from horovod_tpu.common import basics
from horovod_tpu.common.env_registry import env_float, env_int, env_str
from horovod_tpu.common.exceptions import (
    HorovodInternalError,
    HostsUpdatedInterrupt,
)
from horovod_tpu.common.hvd_logging import get_logger

# Prometheus families of the elastic recovery path (exported through the
# standard per-worker registry; the chaos soak asserts on these exact
# names).
RECOVERY_SECONDS = "hvd_elastic_recovery_seconds"
RECOVERIES_TOTAL = "hvd_elastic_recoveries_total"
RESIZE_BYTES = "hvd_resize_bytes"
RESIZE_SECONDS = "hvd_resize_seconds"

_logger = get_logger("elastic")

# Host-update notifications (pushed by the runner's worker notification
# client, reference: runner/elastic/worker.py:84-110). Each entry is
# (generation, skip_sync): a notification only fires an interrupt if its
# generation is newer than the one this worker last rendezvoused into, so a
# freshly spawned worker never interrupts on the announcement of its own
# birth generation.
_notification_queue: "queue.Queue[tuple]" = queue.Queue()


def notify_hosts_updated(skip_sync: bool = False, generation: int = None):
    _notification_queue.put((generation, skip_sync))


def _current_generation() -> int:
    from horovod_tpu.runner.elastic import worker as elastic_worker
    return elastic_worker.current_generation()


def _check_host_updates():
    updated = False
    skip_sync = True
    cur = _current_generation()
    while True:
        try:
            gen, s = _notification_queue.get_nowait()
        except queue.Empty:
            break
        # generation=None means "always newer" (a caller without generation
        # tracking forcing a re-rendezvous) — it must never enter the
        # integer comparison below, only explicit generations are
        # staleness-filtered.
        if gen is not None and gen <= cur:
            continue  # stale: we already rendezvoused past this generation
        updated = True
        skip_sync = skip_sync and s
    if updated:
        raise HostsUpdatedInterrupt(skip_sync)


class State:
    """In-memory checkpoint of training state (reference:
    common/elastic.py:33-105)."""

    def __init__(self, **kwargs):
        self._saved: Dict[str, Any] = {}
        for k, v in kwargs.items():
            setattr(self, k, v)
        self._tracked = list(kwargs.keys())
        self.commit_no_check()

    def _capture(self) -> Dict[str, Any]:
        out = {}
        for k in self._tracked:
            v = getattr(self, k)
            if isinstance(v, (jax.Array,)):
                out[k] = v  # immutable; keep the reference
            elif _is_pytree_of_arrays(v):
                out[k] = v
            else:
                out[k] = copy.deepcopy(v)
        return out

    def commit_no_check(self):
        self._saved = self._capture()

    def commit(self):
        """Save + surface pending host updates (reference:
        elastic.py:60-76)."""
        self.commit_no_check()
        self.check_host_updates()

    def check_host_updates(self):
        # A pending preemption notice drains here — the commit boundary is
        # the safe point where live state is self-consistent (the in-flight
        # step has finished; reference: spot eviction warnings).
        from horovod_tpu.runner.elastic import preempt
        if preempt.preempt_requested():
            preempt.finalize_drain(self)
        _check_host_updates()

    def restore(self):
        """Roll back to the last commit (reference: elastic.py:78-84)."""
        for k, v in self._saved.items():
            setattr(self, k, v)

    def sync(self):
        """Broadcast committed state from rank 0 (reference:
        elastic.py:86-105 + torch/elastic/state.py handlers)."""
        from horovod_tpu.jax import functions
        if basics._single_process():
            return  # single process: broadcast-from-0 is the identity
        for k in self._tracked:
            v = getattr(self, k)
            if isinstance(v, jax.Array) or _is_pytree_of_arrays(v):
                if not _fully_addressable(v):
                    # globally-sharded SPMD arrays can't stage to host here
                    # (and are consistent by construction under SPMD) —
                    # skip rather than crash the elastic retry loop
                    continue
                setattr(self, k, functions.broadcast_parameters(v, 0))
            else:
                setattr(self, k, functions.broadcast_object(
                    v, 0, name=f"elastic_state.{k}"))
        self.commit_no_check()

    def on_reset(self):
        """Hook called after re-initialization (reference: State.on_reset)."""

    def on_hosts_updated(self):
        """Hook when a host-change notification arrives."""


def _fully_addressable(v) -> bool:
    for leaf in jax.tree_util.tree_leaves(v):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            return False
    return True


def _is_pytree_of_arrays(v) -> bool:
    if isinstance(v, (dict, list, tuple)):
        leaves = jax.tree_util.tree_leaves(v)
        return bool(leaves) and all(
            isinstance(x, (jax.Array,)) or hasattr(x, "shape")
            for x in leaves)
    return False


class _TemplateLeaf:
    """Lightweight stand-in for a params leaf: just the geometry
    ``zero._group_leaves`` reads (shape/size/dtype) — the template can be
    kept without pinning the real arrays."""

    __slots__ = ("shape", "dtype", "size")

    def __init__(self, leaf):
        self.shape = tuple(leaf.shape)
        self.dtype = leaf.dtype
        self.size = 1
        for d in self.shape:
            self.size *= int(d)


class ShardedState(State):
    """Elastic state whose ``sharded`` entries live on the ZeRO-1
    flat-shard layout and survive resizes by LIVE re-sharding.

    ``template`` is the replicated params pytree whose per-dtype group
    geometry (zero._group_leaves) defines the shard layout; ``sharded``
    maps entry names to pytrees whose 1/N-shard leaves (size ==
    group.shard for the current world, dtype == group dtype) are
    re-partitioned on a generation change. Leaves that don't match a shard
    (optimizer step counts, scalars) and all regular ``**kwargs`` entries
    stay replicated and broadcast from the most-advanced holder — NOT
    blindly rank 0, which may be a fresh joiner after a resize.

    Loss matrix on resize:

    - scale up / scale down (no death): every old shard has a live holder
      → zero loss, resume at the live step.
    - preemption drain: the departing rank's live shard rides the KV
      handoff (runner/elastic/preempt.py) → zero loss.
    - hard kill: the dead shard restores from its ring buddy's replica as
      of the last ``commit()`` (HOROVOD_ELASTIC_SHARD_REDUNDANCY=1, the
      default — each commit ships the committed shard to rank+1); with
      redundancy off that 1/N moment slice resumes fresh (zeros), logged
      loudly. Params and the step counter are replicated, so training
      itself never rolls back.
    """

    #: run() consults this: shard-aware states resume from LIVE state
    #: after a failure instead of restore()-ing to the last commit.
    live_resume = True

    def __init__(self, template, sharded: Optional[Dict[str, Any]] = None,
                 block_size: int = None, progress_key: str = "step",
                 **kwargs):
        from horovod_tpu.parallel import zero
        self._block_size = block_size or zero.LANE
        self._template = [_TemplateLeaf(l)
                          for l in jax.tree_util.tree_leaves(template)]
        if not self._template:
            raise ValueError("ShardedState needs a non-empty template")
        self._sharded_names = list((sharded or {}).keys())
        self._progress_key = progress_key
        self._world = basics.size() if basics.is_initialized() else 1
        self._old_rank = basics.rank() if basics.is_initialized() else 0
        self._round = 0        # resize rounds completed (collective names)
        self._commit_no = 0    # commits within the current round
        self._buddy = None     # {"of": old_rank, "world": w, "stacks": {}}
        self._handoffs = {}    # old_rank -> {group: [rows, shard]} (sync)
        super().__init__(**dict(kwargs, **(sharded or {})))

    # -- shard layout helpers ------------------------------------------------

    def _groups(self, world: int):
        from horovod_tpu.parallel import zero
        return zero._group_leaves(self._template, world, self._block_size)

    def _classify(self, name: str, world: int):
        """(treedef, leaves, mapping): mapping[i] is the group key when
        leaf i is that group's 1/N shard, else None (replicated)."""
        import jax.numpy as jnp
        by_dtype = {str(jnp.dtype(g.dtype)): g for g in self._groups(world)}
        leaves, treedef = jax.tree_util.tree_flatten(getattr(self, name))
        mapping = []
        for leaf in leaves:
            key = None
            if hasattr(leaf, "dtype") and hasattr(leaf, "shape"):
                g = by_dtype.get(str(jnp.dtype(leaf.dtype)))
                size = int(np.prod(leaf.shape)) if len(leaf.shape) else 1
                # only effectively-1-D leaves ([shard] or [1, shard]) are
                # shards — the last dim is what a resize re-scales
                lead = int(np.prod(leaf.shape[:-1])) \
                    if len(leaf.shape) > 1 else 1
                if g is not None and size == g.shard and lead == 1:
                    key = g.key
            mapping.append(key)
        return treedef, leaves, mapping

    def _combined_stacks(self, world: int):
        """Stack every sharded leaf into per-group ``[rows, shard]``
        arrays, rows in (entry name, leaf index) order — the canonical
        layout the transfer, the buddy replica, and the handoff all share
        (every rank derives it identically from the template)."""
        stacks: Dict[str, list] = {}
        for name in self._sharded_names:
            _, leaves, mapping = self._classify(name, world)
            for leaf, key in zip(leaves, mapping):
                if key is not None:
                    stacks.setdefault(key, []).append(
                        np.asarray(leaf).ravel())
        return {k: np.stack(v) for k, v in stacks.items()}

    def _rows_by_group(self, world: int) -> Dict[str, int]:
        rows: Dict[str, int] = {}
        for name in self._sharded_names:
            _, _, mapping = self._classify(name, world)
            for key in mapping:
                if key is not None:
                    rows[key] = rows.get(key, 0) + 1
        return rows

    def _apply_stacks(self, stacks: Dict[str, np.ndarray]):
        """Scatter re-sharded ``[rows, new_shard]`` stacks back into the
        tracked attrs (row order mirrors _combined_stacks). Classifies at
        ``self._world`` — the layout the CURRENT leaves are sized for —
        so callers must apply before updating the world."""
        import jax.numpy as jnp
        cursor = {k: 0 for k in stacks}
        for name in self._sharded_names:
            treedef, leaves, mapping = self._classify(name, self._world)
            out = []
            for leaf, key in zip(leaves, mapping):
                if key is None:
                    out.append(leaf)
                    continue
                row = stacks[key][cursor[key]]
                cursor[key] += 1
                shape = tuple(leaf.shape[:-1]) + (row.size,)
                out.append(jnp.asarray(row.reshape(shape),
                                       dtype=leaf.dtype))
            setattr(self, name,
                    jax.tree_util.tree_unflatten(treedef, out))

    def shard_handoff_payload(self):
        """(world, old_rank, {"combined": stacks}) for the drain handoff
        (runner/elastic/preempt.py)."""
        if not self._sharded_names:
            return self._world, self._old_rank, {}
        return self._world, self._old_rank, {
            "combined": self._combined_stacks(self._world)}

    # -- commit: buddy redundancy -------------------------------------------

    def commit(self):
        self.commit_no_check()
        # a peer dying during the replica shift raises
        # HorovodInternalError into the normal elastic recovery path
        self._replicate_to_buddy()
        self.check_host_updates()

    def _replicate_to_buddy(self):
        """Ship the just-committed shard stacks to the ring buddy
        (old_rank + 1): a single hard kill between commits then loses no
        COMMITTED state — the buddy serves the dead shard at the next
        resize. One ragged alltoall of 1/N of the state per commit."""
        if env_int("HOROVOD_ELASTIC_SHARD_REDUNDANCY") <= 0:
            return
        if not self._sharded_names or basics._single_process():
            return
        world = basics.size()
        if world < 2 or self._world != world:
            return  # layout mid-transition; the sync will rebuild it
        stacks = self._combined_stacks(world)
        groups = [g for g in self._groups(world) if g.key in stacks]
        payload = np.frombuffer(
            b"".join(np.ascontiguousarray(stacks[g.key]).tobytes()
                     for g in groups), np.uint8)
        splits = [0] * world
        splits[(self._old_rank + 1) % world] = payload.size
        self._commit_no += 1
        received = _ragged_alltoall(
            payload, splits,
            name=f"elastic.buddy.r{self._round}.{self._commit_no}")
        buf = received[(self._old_rank - 1) % world]
        parsed, off = {}, 0
        import jax.numpy as jnp
        rows = self._rows_by_group(world)
        for g in groups:
            nbytes = rows[g.key] * g.shard * jnp.dtype(g.dtype).itemsize
            parsed[g.key] = np.frombuffer(
                buf[off:off + nbytes].tobytes(),
                jnp.dtype(g.dtype)).reshape(rows[g.key], g.shard).copy()
            off += nbytes
        self._buddy = {"of": (self._old_rank - 1) % world,
                       "world": world, "stacks": parsed}

    # -- sync: live re-sharding ---------------------------------------------

    def sync(self):
        """Shard-aware sync. Replicated entries broadcast from the
        most-advanced holder; sharded entries ride the old→new transfer
        plan. Records ``hvd_resize_{bytes,seconds}``."""
        from horovod_tpu.jax import functions
        from horovod_tpu.metrics import get_registry
        from horovod_tpu.parallel import zero
        if basics._single_process():
            # Scale-to-one is still a resize: the lone survivor holds only
            # its own 1/N shard, so the full state is rebuilt locally from
            # it plus whatever the departed ranks left behind (KV
            # handoffs, the ring-buddy replica) — no peers to ask.
            if self._sharded_names and self._world and self._world > 1:
                self._reshard_local_to_one()
            self._world, self._old_rank = 1, 0
            self.commit_no_check()
            return
        t0 = _time.perf_counter()
        new_world, new_rank = basics.size(), basics.rank()
        progress = _as_float(getattr(self, self._progress_key, 0))
        desc = {
            "new_rank": new_rank,
            "world": self._world,
            "old_rank": self._old_rank,
            "round": self._round,
            "progress": progress,
            "buddy_of": (self._buddy or {}).get("of"),
            "buddy_world": (self._buddy or {}).get("world"),
        }
        descs = functions.allgather_object(desc, name="elastic.shard.desc")
        round_id = max(int(d["round"]) for d in descs) + 1
        # Authoritative holders: the ranks that have actually trained —
        # highest round first (fresh joiners re-initialize at round 0),
        # then highest progress (a rank whose step failed mid-collective
        # is one step behind the survivors that completed it).
        max_round = max(int(d["round"]) for d in descs)
        trained = [d for d in descs if int(d["round"]) == max_round]
        best = max(d["progress"] for d in trained)
        root = min(d["new_rank"] for d in trained
                   if d["progress"] >= best)
        old_world = trained[0]["world"]
        wire_bytes = 0
        if self._sharded_names:
            identity = all(d["world"] == new_world and
                           d["old_rank"] == d["new_rank"] for d in trained)
            if not identity or self._needs_fill(trained, old_world):
                wire_bytes = self._reshard(descs, trained, old_world,
                                           new_world, new_rank, zero)
        self._world, self._old_rank = new_world, new_rank
        # The round advances as soon as this rank's SHARDS are on the new
        # layout — before the replicated broadcast. A peer dying during
        # that last phase then retries with this rank still counted as
        # trained (its live shard is valid); advancing the round last
        # would demote it to fresh-joiner and discard the data.
        self._round = round_id
        # Replicated entries (and non-shard leaves of sharded entries)
        # come from the most-advanced trained rank — after the world
        # update, so classification sees the just-resharded leaf sizes.
        self._broadcast_replicated(functions, root)
        self._commit_no = 0
        self._handoffs = {}
        elapsed = _time.perf_counter() - t0
        reg = get_registry()
        reg.counter(RESIZE_BYTES,
                    "wire bytes moved by live shard re-sharding").inc(
                        wire_bytes)
        reg.histogram(RESIZE_SECONDS,
                      "wall seconds of the shard-aware sync").observe(
                          elapsed)
        self.commit_no_check()

    def _needs_fill(self, trained, old_world: int) -> bool:
        held = {d["old_rank"] for d in trained if d["world"] == old_world}
        return len(held) < old_world

    def _reshard(self, descs, trained, old_world, new_world, new_rank,
                 zero) -> int:
        from horovod_tpu.jax import functions
        survivors = {d["old_rank"]: d["new_rank"] for d in trained
                     if d["world"] == old_world}
        missing = sorted(set(range(old_world)) - set(survivors))
        sources = dict(survivors)
        i_survive = self._old_rank in survivors and \
            survivors[self._old_rank] == new_rank and \
            self._world == old_world
        if missing:
            sources.update(self._assign_lost_sources(
                functions, descs, missing, old_world, new_rank))
        still_lost = [r for r in missing if r not in sources]
        if still_lost:
            _logger.warning(
                "resize %d->%d: no live shard, handoff, or buddy replica "
                "for old rank(s) %s — that moment slice resumes fresh",
                old_world, new_world, still_lost)
        plan = zero.reshard_plan(self._template, old_world, new_world,
                                 self._block_size)
        # Row counts are structural (which leaves are shards never
        # changes), but classification only succeeds against the world
        # the CURRENT leaves are sized for — always self._world. Using
        # new_world here broke trained-but-demoted survivors (a partial
        # mid-reshard failure leaves their leaves on a stale layout that
        # matches neither world's shard size).
        rows = self._rows_by_group(self._world)
        own = self._combined_stacks(self._world) if i_survive else {}
        buddy = self._buddy if (self._buddy and
                                self._buddy.get("world") == old_world) \
            else None

        def lookup(group_key, old_rank):
            if i_survive and old_rank == self._old_rank:
                return own[group_key]
            if old_rank in self._handoffs:
                return self._handoffs[old_rank][group_key]
            if buddy and buddy["of"] == old_rank:
                return buddy["stacks"][group_key]
            raise KeyError(f"no shard source for old rank {old_rank}")

        quantized = env_str("HOROVOD_RESHARD_COMPRESSION") == "int8"
        tag = f"elastic.reshard.r{self._round_tag(descs)}"
        new_stacks, stats = zero.reshard(
            plan, new_rank, sources, lookup, rows,
            lambda bufs: _ragged_alltoall(
                np.concatenate(bufs) if sum(b.size for b in bufs)
                else np.zeros(0, np.uint8),
                [int(b.size) for b in bufs], name=tag),
            quantized=quantized)
        self._apply_stacks(new_stacks)
        self._buddy = None  # stale layout; next commit rebuilds it
        self._gc_handoffs(old_world)
        return int(stats["wire_bytes_sent"])

    def _gc_handoffs(self, old_world: int):
        """Delete consumed drain-handoff KV payloads. Without this a
        later resize could resurrect a stale handoff in preference to a
        fresh buddy replica (fetch_handoff's TTL is the backstop)."""
        if not self._handoffs:
            return
        try:
            from horovod_tpu.runner.elastic import preempt
            from horovod_tpu.runner.elastic import worker as elastic_worker
            client = elastic_worker.kv_client()
            for r in list(self._handoffs):
                client.delete(preempt.handoff_key(old_world, r))
        except Exception:  # noqa: BLE001 — GC is best-effort
            pass

    def _reshard_local_to_one(self):
        from horovod_tpu.parallel import zero
        from horovod_tpu.runner.elastic import preempt
        old_world = self._world
        plan = zero.reshard_plan(self._template, old_world, 1,
                                 self._block_size)
        own = self._combined_stacks(old_world)
        rows = self._rows_by_group(old_world)
        buddy = self._buddy if (self._buddy and
                                self._buddy.get("world") == old_world) \
            else None
        sources = {self._old_rank: 0}
        for r in range(old_world):
            if r == self._old_rank:
                continue
            stacks = preempt.fetch_handoff(old_world, r)
            if stacks and "combined" in stacks:
                self._handoffs[r] = stacks["combined"]
                sources[r] = 0
            elif buddy and buddy["of"] == r:
                sources[r] = 0
        missing = [r for r in range(old_world) if r not in sources]
        if missing:
            _logger.warning(
                "scale to 1: no handoff or replica for old rank(s) %s — "
                "those moment slices resume fresh", missing)

        def lookup(group_key, old_rank):
            if old_rank == self._old_rank:
                return own[group_key]
            if old_rank in self._handoffs:
                return self._handoffs[old_rank][group_key]
            return buddy["stacks"][group_key]

        new_stacks, _ = zero.reshard(
            plan, 0, sources, lookup, rows,
            lambda bufs: [bufs[0]], quantized=False)
        self._apply_stacks(new_stacks)
        self._buddy = None
        self._gc_handoffs(old_world)
        self._handoffs = {}

    def _round_tag(self, descs) -> str:
        # collective names must agree across ranks: derive from gathered
        # state, never local counters (a joiner's counter starts at 0)
        return str(max(int(d["round"]) for d in descs))

    def _assign_lost_sources(self, functions, descs, missing, old_world,
                             new_rank):
        """Second descriptor round: who can serve the dead ranks' shards?
        The lowest trained rank pulls KV handoffs (a drained worker's live
        shard beats any replica); buddies offer their committed copies.
        Deterministic preference: handoff > buddy, then lowest rank."""
        from horovod_tpu.runner.elastic import preempt
        from horovod_tpu.runner.elastic import worker as elastic_worker
        offers = {}
        fetch_rank = min(d["new_rank"] for d in descs
                         if d["world"] == old_world and
                         int(d["round"]) == max(int(x["round"])
                                                for x in descs))
        if new_rank == fetch_rank and elastic_worker.is_elastic_worker():
            for r in missing:
                stacks = preempt.fetch_handoff(old_world, r)
                if stacks and "combined" in stacks:
                    self._handoffs[r] = stacks["combined"]
                    offers[r] = "handoff"
        if self._buddy and self._buddy.get("world") == old_world and \
                self._buddy.get("of") in missing:
            offers.setdefault(self._buddy["of"], "buddy")
        gathered = functions.allgather_object(
            {"new_rank": new_rank, "offers": offers},
            name="elastic.shard.offers")
        assigned = {}
        for r in missing:
            candidates = [(0 if g["offers"].get(r) == "handoff" else 1,
                           g["new_rank"])
                          for g in gathered if r in g["offers"]]
            if candidates:
                assigned[r] = min(candidates)[1]
        return assigned

    def _broadcast_replicated(self, functions, root: int):
        shard_names = set(self._sharded_names)
        for k in self._tracked:
            if k in shard_names:
                # non-shard leaves (step counts etc.) of sharded entries
                treedef, leaves, mapping = self._classify(k, self._world)
                idx = [i for i, key in enumerate(mapping) if key is None]
                if not idx:
                    continue
                synced = functions.broadcast_object(
                    [np.asarray(leaves[i])
                     if isinstance(leaves[i], jax.Array) else leaves[i]
                     for i in idx], root,
                    name=f"elastic.shard.repl.{k}")
                value = getattr(self, k)
                leaves2, treedef2 = jax.tree_util.tree_flatten(value)
                for i, v in zip(idx, synced):
                    leaves2[i] = v
                setattr(self, k,
                        jax.tree_util.tree_unflatten(treedef2, leaves2))
                continue
            v = getattr(self, k)
            if isinstance(v, jax.Array) or _is_pytree_of_arrays(v):
                if not _fully_addressable(v):
                    continue
                setattr(self, k, functions.broadcast_parameters(v, root))
            else:
                setattr(self, k, functions.broadcast_object(
                    v, root, name=f"elastic_state.{k}"))


def _as_float(v) -> float:
    try:
        return float(np.asarray(v).reshape(-1)[0]) if hasattr(v, "shape") \
            else float(v)
    except (TypeError, ValueError):
        return 0.0


def _ragged_alltoall(payload: np.ndarray, splits, name: str):
    """Eager byte alltoall returning one buffer per peer rank."""
    from horovod_tpu.common import eager
    h = eager.alltoall_async(np.ascontiguousarray(payload, np.uint8)
                             if payload.size else np.zeros(0, np.uint8),
                             splits=list(splits), name=name)
    out = eager.synchronize(h)
    out = np.asarray(out, np.uint8).ravel() if out is not None \
        else np.zeros(0, np.uint8)
    recv = h.aux.get("recv_splits")
    if recv is None:
        recv = [out.size]
    res, off = [], 0
    for s in np.asarray(recv).ravel():
        res.append(out[off:off + int(s)])
        off += int(s)
    while len(res) < len(splits):
        res.append(np.zeros(0, np.uint8))
    return res


# Failures further apart than this are independent incidents, not one
# unhealed outage: the retry counter resets so HOROVOD_ELASTIC_MAX_RETRIES
# bounds *consecutive* recoveries rather than a long job's lifetime total.
_RETRY_WINDOW_SECONDS = 600.0


def run(func: Callable) -> Callable:
    """Elastic retry wrapper (reference: common/elastic.py:147-168).

    ``func(state, *args, **kwargs)``; on HorovodInternalError the last
    committed state is restored, the framework re-initialized, state
    re-synced; on HostsUpdatedInterrupt training resumes with current state
    after re-initialization.

    Failure retries are bounded: after HOROVOD_ELASTIC_MAX_RETRIES
    consecutive HorovodInternalError recoveries (default 100; 0 =
    unbounded, the reference's behavior; the counter resets after a
    failure-free ``_RETRY_WINDOW_SECONDS`` stretch) the error propagates
    instead of looping forever against a cluster that will never heal.
    Each failed round backs off exponentially (base
    HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS, default 0.5s, capped at 30s,
    jittered) so a flapping peer isn't hammered by synchronized re-inits.
    Host-update interrupts are normal scaling events and are neither
    counted nor delayed.
    """

    def wrapper(state: State, *args, **kwargs):
        import random
        import time
        from horovod_tpu.metrics import get_registry
        from horovod_tpu.runner.elastic import preempt
        from horovod_tpu.runner.elastic import worker as elastic_worker
        start_notification_poller()
        if elastic_worker.is_elastic_worker():
            # spot/preemptible pools: an eviction warning drains instead
            # of crashing (runner/elastic/preempt.py)
            preempt.install_preempt_handler()
            # KV liveness heartbeat: driver-recovery adoption + bounded
            # headless mode during control-plane outages
            elastic_worker.start_heartbeat()
        max_retries = env_int("HOROVOD_ELASTIC_MAX_RETRIES")
        backoff_base = env_float("HOROVOD_ELASTIC_RETRY_BACKOFF_SECONDS")
        failures = 0
        sync_failures = 0
        last_failure = None
        skip_sync = False
        recovery_started = None  # monotonic ts of the incident being healed
        try:
            while True:
                # Sync-first, including the very first iteration: a
                # freshly spawned worker receives the committed state
                # before its first training collective (reference:
                # common/elastic.py run_fn). sync() itself runs
                # collectives, so it has its OWN retry scope OUTSIDE the
                # training one: a peer dying mid-sync means the resize was
                # interrupted — the sync restarts against the next
                # topology without burning a steady-state retry (the
                # bounded budget targets failures of *training*, not
                # failures of the recovery from a failure — double-
                # charging made a flaky resize exhaust the budget at half
                # the intended incident count). Consecutive sync failures
                # are still bounded by the same limit so a cluster that
                # can never complete a resize fails loudly.
                if not skip_sync:
                    try:
                        state.sync()
                    except HorovodInternalError:
                        sync_failures += 1
                        if max_retries > 0 and sync_failures > max_retries:
                            raise  # outermost handler records FAILURE
                        if backoff_base > 0:
                            time.sleep(min(
                                5.0, backoff_base *
                                (0.5 + random.random() / 2)))
                        _reset()
                        state.on_reset()
                        continue
                sync_failures = 0
                try:
                    if recovery_started is not None:
                        dt = time.monotonic() - recovery_started
                        recovery_started = None
                        reg = get_registry()
                        reg.histogram(
                            RECOVERY_SECONDS,
                            "failure/resize detection to training "
                            "resumption").observe(dt)
                        reg.counter(RECOVERIES_TOTAL,
                                    "completed elastic recoveries").inc()
                    result = func(state, *args, **kwargs)
                    _record_final_state(success=True)
                    return result
                except HorovodInternalError:
                    now = time.monotonic()
                    if recovery_started is None:
                        recovery_started = now
                    # a long healthy stretch since the previous failure
                    # means the cluster recovered — the bound targets
                    # *consecutive* failures (a job that won't heal), not
                    # unrelated transients spread over a job's lifetime
                    if last_failure is not None and \
                            now - last_failure > _RETRY_WINDOW_SECONDS:
                        failures = 0
                    last_failure = now
                    failures += 1
                    if max_retries > 0 and failures > max_retries:
                        _record_final_state(success=False)
                        raise
                    if backoff_base > 0:
                        delay = min(30.0,
                                    backoff_base * (2 ** min(failures - 1,
                                                             6)))
                        time.sleep(delay * (0.5 + random.random() / 2))
                    # Shard-aware states resume from LIVE state: the next
                    # sync() re-partitions the surviving shards, so rolling
                    # back to the last commit would discard healthy
                    # progress (the ISSUE-9 checkpoint-free contract).
                    # Classic replicated State keeps the reference
                    # restore-to-commit semantics.
                    if not getattr(state, "live_resume", False):
                        state.restore()
                    skip_sync = False
                except HostsUpdatedInterrupt as e:
                    if recovery_started is None:
                        recovery_started = time.monotonic()
                    skip_sync = e.skip_sync
                _reset()
                state.on_reset()
        except SystemExit:
            raise  # clean slot removal / drain, not a failure
        except BaseException:
            # fatal user/framework error: tell the driver's registry so a
            # generation waiting on this slot's READY rebalances immediately
            # instead of sitting out the go-barrier timeout
            _record_final_state(success=False)
            raise

    return wrapper


def _record_final_state(success: bool):
    """Best-effort SUCCESS/FAILURE record for the driver's registry
    (reference: runner/elastic/registration.py SUCCESS/FAILURE records)."""
    from horovod_tpu.runner.elastic import worker as elastic_worker
    if not elastic_worker.is_elastic_worker():
        return
    try:
        # Generous retry budget: an exit code satisfies the driver that
        # spawned us, but a driver *recovered mid-outage* only has this
        # record to tell a clean completion from a crash — wait out a
        # driver-restart window before giving up.
        elastic_worker.record_state(
            elastic_worker.current_generation(),
            elastic_worker.SUCCESS if success else elastic_worker.FAILURE,
            attempts=10, deadline=12.0)
    except Exception:  # noqa: BLE001 — the driver also watches exit codes
        pass


def _reset():
    """Shutdown + re-init (reference: torch/elastic/__init__.py:46+ —
    shutdown, re-rendezvous, init). The re-rendezvous (generation query +
    READY/go barrier, reference gloo_context.cc:154-200) happens inside
    ``init()`` for elastic workers, so the driver hands this process its new
    rank/size/controller endpoint before the engine boots.

    A reset always requires a *strictly newer* generation: the one we are
    leaving may still be current (its go released) yet contain a dead peer.
    Engine boot failures retry with another fresh generation — a peer may
    die mid-re-init too."""
    from horovod_tpu.runner.elastic import worker as elastic_worker
    last_exc = None
    for _ in range(3):
        if elastic_worker.is_elastic_worker():
            elastic_worker.request_new_generation()
        basics.shutdown()
        try:
            basics.init()
            return
        except SystemExit:
            raise
        except RuntimeError as e:
            last_exc = e
    raise last_exc


_poller_started = False


def start_notification_poller(interval: float = 1.0):
    """Background thread surfacing driver membership-change notifications
    (reference: WorkerNotificationService/Client,
    runner/elastic/worker.py:31-110 — here a poll of the rendezvous
    ``notify`` key instead of a push socket). Stale announcements — at or
    below the generation this worker already rendezvoused into — are
    filtered both here and at the interrupt point."""
    global _poller_started
    import threading
    from horovod_tpu.runner.elastic import worker as elastic_worker
    if _poller_started or not elastic_worker.is_elastic_worker():
        return
    _poller_started = True

    def poll_loop():
        import time
        last_notified = -1
        while True:
            gen = elastic_worker.poll_notification()
            if gen is not None and gen > last_notified:
                last_notified = gen
                notify_hosts_updated(generation=gen)
            time.sleep(interval)

    threading.Thread(target=poll_loop, daemon=True).start()
