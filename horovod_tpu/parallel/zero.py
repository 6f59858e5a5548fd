"""Cross-replica sharded weight update — ZeRO stage 1 for the DP hot path.

Reference technique: Xu et al., *Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training* (arXiv:2004.13336). The replicated
data-parallel step allreduces the full gradient and then performs the SAME
optimizer update on every replica — N-way redundant compute and N full
copies of the optimizer state. This module replaces that with:

    reduce-scatter(grads) → optimizer update on the local 1/N shard
    → all-gather(param updates) → apply to the replicated params

Per-replica optimizer state (Adam moments, momentum, ...) shrinks by 1/N and
the weight-update FLOPs shrink by 1/N; wire bytes are unchanged for fp32
(reduce-scatter + all-gather ≈ allreduce on a ring) and drop ~4x when the
int8 quantized collectives ride both phases (EQuARX, arXiv:2506.17615).

Layout: gradient/param leaves are grouped per dtype class (the same grouping
:mod:`horovod_tpu.ops.fusion` uses, so each phase is ONE collective per
dtype), flattened, zero-padded to a multiple of ``axis_size * block_size``
and partitioned contiguously across the mesh axes. Optimizer state lives on
that flat-shard layout: globally a ``[N, shard]`` array sharded on dim 0
(each device materializes only its ``[1, shard]`` slice); locally, inside
``shard_map``, the leading stacked dim is squeezed away before the update.

Constraint: the wrapped optax transformation must be ELEMENTWISE
(sgd/momentum/adam/adamw/rmsprop...). Transforms that couple elements
globally — ``clip_by_global_norm`` & co — would see only the local shard's
norm; compose them outside the sharded update or keep the replicated path.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel import collectives
from horovod_tpu.parallel.collectives import Average, Op, Sum
from horovod_tpu.profiler.annotate import step_phase

# Flat groups are padded to a multiple of axis_size * LANE so the layout is
# identical whether or not the int8 path (which quantizes LANE-sized blocks)
# is active — opt state initialized without compression stays valid with it.
LANE = 256


class _DtypeGroup(NamedTuple):
    key: str                 # stable dict key, e.g. "float32"
    dtype: Any
    indices: Tuple[int, ...]  # leaf positions in tree_flatten order
    sizes: Tuple[int, ...]    # leaf element counts
    shapes: Tuple[Tuple[int, ...], ...]
    padded: int              # flat length after zero-padding
    shard: int               # padded // n_shards


def _group_leaves(leaves, n_shards: int, block_size: int = LANE, *,
                  indices: Optional[Sequence[int]] = None,
                  leaf_align: int = 1,
                  key_prefix: str = "") -> Tuple[_DtypeGroup, ...]:
    """Stable per-dtype grouping of a leaf list (first-appearance order,
    mirroring ops/fusion.py), with the ZeRO partition geometry attached.

    ``indices`` restricts the grouping to a leaf subset (the bucketed
    pipeline groups per bucket); ``leaf_align`` pads every leaf to a
    multiple of it inside the flat layout (the bucketed int8 path aligns
    leaves to the quantization block so block cohorts never span leaves —
    that is what makes the quantized result invariant to the bucket
    partition)."""
    order: dict = {}
    for i in (range(len(leaves)) if indices is None else indices):
        order.setdefault(jnp.dtype(leaves[i].dtype), []).append(i)
    groups = []
    lane = n_shards * block_size
    for dtype, idxs in order.items():
        sizes = tuple(int(leaves[i].size) for i in idxs)
        total = sum(sz + (-sz) % leaf_align for sz in sizes)
        padded = total + (-total) % lane
        groups.append(_DtypeGroup(
            key=key_prefix + str(dtype), dtype=dtype, indices=tuple(idxs),
            sizes=sizes,
            shapes=tuple(tuple(leaves[i].shape) for i in idxs),
            padded=padded, shard=padded // n_shards))
    return tuple(groups)


def bucket_groups(leaves, n_shards: int, bucket_bytes: int,
                  block_size: int = LANE) -> Tuple[_DtypeGroup, ...]:
    """Flat groups for the bucketed ZeRO-1 pipeline: one group per
    (bucket, dtype) in bucket order (reverse flatten order — the order
    backward produces the grads), every leaf block-aligned. Pure function
    of (leaf shapes, bucket_bytes, n_shards) — the train step and
    :func:`sharded_opt_init` derive the identical geometry from it."""
    from horovod_tpu.parallel.bucketing import plan_buckets
    groups = []
    for b in plan_buckets(leaves, bucket_bytes):
        groups.extend(_group_leaves(
            leaves, n_shards, block_size, indices=b.indices,
            leaf_align=block_size, key_prefix=f"b{b.index:04d}/"))
    return tuple(groups)


def _flatten_group(leaves, group: _DtypeGroup,
                   leaf_align: int = 1) -> jax.Array:
    parts = []
    for i in group.indices:
        v = leaves[i].ravel()
        pad = (-v.size) % leaf_align
        parts.append(jnp.pad(v, (0, pad)) if pad else v)
    flat = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    pad = group.padded - flat.size
    return jnp.pad(flat, (0, pad)) if pad else flat


def _unflatten_group(flat: jax.Array, group: _DtypeGroup,
                     leaf_align: int = 1) -> list:
    out, offset = [], 0
    for sz, shape in zip(group.sizes, group.shapes):
        out.append(flat[offset:offset + sz].reshape(shape))
        offset += sz + (-sz) % leaf_align
    return out


def _local_shard(flat: jax.Array, rank, shard: int) -> jax.Array:
    return lax.dynamic_slice(flat, (rank * shard,), (shard,))


def _check_op(op: Op) -> None:
    if op not in (Average, Sum):
        raise ValueError(
            f"sharded_update supports Sum/Average gradient reduction, got "
            f"{op} — Adasum/Min/Max/Product have no reduce-scatter form")


def apply_sharded_update(optimizer,
                         grads,
                         opt_state,
                         params,
                         *,
                         axes=("data",),
                         op: Op = Average,
                         compression=None,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         block_size: int = LANE,
                         bucket_bytes: Optional[int] = None):
    """One ZeRO-1 step. Call INSIDE ``shard_map`` over ``axes``.

    ``params`` arrive replicated, ``opt_state`` leaves carry a leading
    stacked dim of 1 (the local slice of the globally ``[N, ...]``-sharded
    state — see :func:`sharded_opt_init`). ``compression`` follows the dp
    conventions: None, a dtype-cast Compressor (fp16/bf16 wire), or a
    quantized Compressor (int8 blocks on both phases). Returns
    ``(new_params, new_opt_state)`` with the same layouts.

    ``bucket_bytes`` (env default ``HOROVOD_BUCKET_BYTES``; 0 = off)
    switches the exchange to size-bounded buckets in backward-ready order:
    one reduce-scatter / all-gather pair per (bucket, dtype) group instead
    of one per dtype, so each bucket's wire time only depends on its own
    leaves and XLA can overlap it with the rest of backward
    (:mod:`horovod_tpu.parallel.bucketing`). The optimizer state must then
    come from ``sharded_opt_init(..., bucket_bytes=...)`` with the SAME
    bound — the flat-shard geometry is a pure function of it.
    """
    _check_op(op)
    from horovod_tpu.jax.compression import Compression
    from horovod_tpu.parallel.bucketing import resolve_bucket_bytes
    if compression is Compression.none:
        compression = None
    quantized = bool(getattr(compression, "quantized", False))
    if quantized:
        block_size = getattr(compression, "block_size", block_size)
    bucket_bytes = resolve_bucket_bytes(bucket_bytes)

    n = collectives.axis_size(axes)
    rank = collectives.axis_rank(axes)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    p_leaves = jax.tree_util.tree_leaves(params)
    if len(p_leaves) != len(leaves):
        raise ValueError("params/grads trees differ in structure")
    if bucket_bytes > 0:
        groups = bucket_groups(leaves, n, bucket_bytes, block_size)
        leaf_align = block_size
    else:
        groups = _group_leaves(leaves, n, block_size)
        leaf_align = 1

    g_shards, p_shards = {}, {}
    for group in groups:
        with step_phase("grad_exchange"):
            gflat = _flatten_group(leaves, group, leaf_align)
            gflat = collectives._scale(gflat, prescale_factor)
            if quantized:
                shard = collectives.quantized_reducescatter(
                    gflat, op=op, axis=axes, block_size=block_size)
                shard = shard.astype(group.dtype)
            elif compression is not None:
                wire, ctx = compression.compress(gflat)
                shard = collectives.reducescatter(wire, op=op, axis=axes)
                shard = compression.decompress(shard, ctx)
            else:
                shard = collectives.reducescatter(gflat, op=op, axis=axes)
            g_shards[group.key] = collectives._scale(shard, postscale_factor)
        with step_phase("optimizer_update"):
            pflat = _flatten_group(p_leaves, group, leaf_align)
            p_shards[group.key] = _local_shard(pflat, rank, group.shard)

    with step_phase("optimizer_update"):
        local_state = jax.tree_util.tree_map(lambda s: jnp.squeeze(s, 0),
                                             opt_state)
        updates, new_state = optimizer.update(g_shards, local_state,
                                              p_shards)
        new_state = jax.tree_util.tree_map(lambda s: s[None], new_state)

    update_leaves = [None] * len(leaves)
    with step_phase("param_gather"):
        for group in groups:
            u = updates[group.key]
            if quantized:
                full = collectives.quantized_allgather(
                    u, axis=axes, block_size=block_size).astype(group.dtype)
            elif compression is not None:
                # dtype-cast compression rides BOTH phases (as
                # collective_bytes_per_step counts it)
                wire, ctx = compression.compress(u)
                full = lax.all_gather(wire, axes, axis=0, tiled=True)
                full = compression.decompress(full, ctx)
            else:
                full = lax.all_gather(u, axes, axis=0, tiled=True)
            for i, leaf in zip(group.indices,
                               _unflatten_group(full, group, leaf_align)):
                update_leaves[i] = leaf
        updates_tree = jax.tree_util.tree_unflatten(treedef, update_leaves)
    with step_phase("optimizer_update"):
        new_params = optax.apply_updates(params, updates_tree)
    return new_params, new_state


def _local_init(optimizer, params, axes, block_size, bucket_bytes=0):
    n = collectives.axis_size(axes)
    rank = collectives.axis_rank(axes)
    leaves = jax.tree_util.tree_leaves(params)
    if bucket_bytes > 0:
        groups = bucket_groups(leaves, n, bucket_bytes, block_size)
        leaf_align = block_size
    else:
        groups = _group_leaves(leaves, n, block_size)
        leaf_align = 1
    p_shards = {}
    for group in groups:
        pflat = _flatten_group(leaves, group, leaf_align)
        p_shards[group.key] = _local_shard(pflat, rank, group.shard)
    state = optimizer.init(p_shards)
    return jax.tree_util.tree_map(lambda s: s[None], state)


def sharded_opt_init(optimizer,
                     params,
                     mesh: Mesh,
                     axes: Sequence[str] = ("data", "fsdp"),
                     block_size: int = LANE,
                     bucket_bytes: Optional[int] = None):
    """Initialize the sharded optimizer state on the mesh.

    The replicated-path idiom ``dp.replicate(opt.init(params), mesh)``
    materializes N full copies of the state; this builds the ZeRO layout
    instead — every state leaf becomes ``[N, shard]`` sharded over ``axes``
    on dim 0, so each device holds 1/N of the bytes. Feed the result to a
    ``make_train_step(..., sharded_update=True)`` step.

    ``bucket_bytes`` must match the step's bucket bound (both default to
    ``HOROVOD_BUCKET_BYTES``): the bucketed pipeline lays the state out per
    (bucket, dtype) group, and the two sides derive the geometry from the
    same :func:`bucket_groups` plan."""
    axes = tuple(a for a in axes if a in mesh.shape)
    from horovod_tpu.parallel.bucketing import resolve_bucket_bytes
    local = functools.partial(_local_init, optimizer, axes=axes,
                              block_size=block_size,
                              bucket_bytes=resolve_bucket_bytes(bucket_bytes))
    mapped = jax.shard_map(local, mesh=mesh, in_specs=(P(),),
                           out_specs=P(axes), check_vma=False)
    return jax.jit(mapped)(params)


# ---------------------------------------------------------------------------
# Checkpoint-free elastic resize: old-shards -> new-shards transfer plan.
#
# On a topology generation change the world size moves N_old -> N_new, so the
# ZeRO flat-group geometry changes (padded length is a multiple of
# world * block_size) and every rank's contiguous shard boundary moves. The
# optimizer state is NOT replicated — no rank can broadcast it — so a resize
# re-partitions the live shards instead: `reshard_plan` computes the exact
# (src old rank, dst new rank, offset, length) segment set, and `reshard`
# executes it over an injected exchange (the eager ragged alltoall in
# production, an in-memory exchange in the chaos simulator). Only real
# elements move; padding is reconstructed as zeros on the receiver.


class ShardSegment(NamedTuple):
    """One contiguous transfer: ``length`` elements of group ``group`` that
    live at ``src_offset`` in old rank ``src``'s shard and land at
    ``dst_offset`` in new rank ``dst``'s shard."""
    group: str
    src: int
    dst: int
    src_offset: int
    dst_offset: int
    length: int


class ReshardPlan(NamedTuple):
    old_world: int
    new_world: int
    block_size: int
    old_groups: Tuple[_DtypeGroup, ...]
    new_groups: Tuple[_DtypeGroup, ...]
    segments: Tuple[ShardSegment, ...]

    def _ordered(self, segs):
        order = {g.key: i for i, g in enumerate(self.old_groups)}
        return tuple(sorted(
            segs, key=lambda s: (order[s.group], s.src, s.src_offset)))

    def segments_for_pair(self, serving: int, dst: int,
                          sources) -> Tuple[ShardSegment, ...]:
        """The segments rank ``serving`` transmits to ``dst`` under the
        runtime source assignment ``sources`` (old rank -> serving new
        rank), in the canonical pack order both sides derive
        independently."""
        return self._ordered(
            s for s in self.segments
            if s.dst == dst and sources.get(s.src) == serving)

    def group(self, key: str) -> _DtypeGroup:
        for g in self.old_groups:
            if g.key == key:
                return g
        raise KeyError(key)

    def new_group(self, key: str) -> _DtypeGroup:
        for g in self.new_groups:
            if g.key == key:
                return g
        raise KeyError(key)

    def element_bytes(self, segs) -> int:
        groups = {g.key: jnp.dtype(g.dtype).itemsize for g in self.old_groups}
        return sum(s.length * groups[s.group] for s in segs)


def reshard_plan(template, old_world: int, new_world: int,
                 block_size: int = LANE) -> ReshardPlan:
    """Old-shards -> new-shards transfer plan for a resize.

    ``template`` is the replicated params pytree (or leaf list) whose
    per-dtype flat-group geometry defines the shard layout at BOTH world
    sizes — the state itself never needs to be materialized to plan. Pure
    function of (template shapes, old_world, new_world): every rank computes
    the identical plan locally, nothing is negotiated.

    Segments cover exactly the REAL elements (the group's unpadded total) of
    every new shard; the zero padding that squares the new layout off to a
    multiple of ``new_world * block_size`` is recreated locally. Segments
    with ``src == dst`` are local copies and cost no wire bytes.
    """
    if old_world < 1 or new_world < 1:
        raise ValueError(
            f"world sizes must be >= 1, got {old_world} -> {new_world}")
    leaves = jax.tree_util.tree_leaves(template)
    if not leaves:
        raise ValueError("reshard_plan needs a non-empty template")
    old_groups = _group_leaves(leaves, old_world, block_size)
    new_groups = _group_leaves(leaves, new_world, block_size)
    segments = []
    for og, ng in zip(old_groups, new_groups):
        total = sum(og.sizes)  # real elements; the rest is padding
        for dst in range(new_world):
            lo = dst * ng.shard
            hi = min(lo + ng.shard, total)
            src = lo // og.shard if og.shard else 0
            while lo < hi:
                src_hi = min((src + 1) * og.shard, total)
                take = min(hi, src_hi) - lo
                if take > 0:
                    segments.append(ShardSegment(
                        group=og.key, src=src, dst=dst,
                        src_offset=lo - src * og.shard,
                        dst_offset=lo - dst * ng.shard, length=take))
                lo += max(take, 0)
                src += 1
    return ReshardPlan(old_world=old_world, new_world=new_world,
                       block_size=block_size, old_groups=old_groups,
                       new_groups=new_groups, segments=tuple(segments))


# -- host-side int8 block codec (the PR-1 EQuARX wire format, numpy form) --
# The resize path moves concrete host buffers through the eager data plane,
# so the quantized wire rides a numpy implementation of the same
# block-scaled int8 scheme the in-jit quantized collectives use: one fp32
# absmax scale per `block_size` elements, values rounded into [-127, 127].


def quantize_blocks_np(arr, block_size: int = LANE):
    """``arr`` (1-D float) -> (int8 values, fp32 per-block scales)."""
    import numpy as np
    flat = np.asarray(arr, dtype=np.float32).ravel()
    pad = (-flat.size) % block_size
    padded = np.pad(flat, (0, pad)) if pad else flat
    blocks = padded.reshape(-1, block_size)
    scales = np.abs(blocks).max(axis=1).astype(np.float32)
    denom = np.where(scales > 0, scales, 1.0)
    q = np.clip(np.rint(blocks / denom[:, None] * 127.0), -127, 127)
    return q.astype(np.int8).reshape(-1)[:flat.size], scales


def dequantize_blocks_np(q, scales, dtype, block_size: int = LANE):
    import numpy as np
    q = np.asarray(q, dtype=np.int8).ravel()
    pad = (-q.size) % block_size
    padded = np.pad(q, (0, pad)) if pad else q
    blocks = padded.astype(np.float32).reshape(-1, block_size)
    out = blocks * (np.asarray(scales, np.float32)[:, None] / 127.0)
    return out.reshape(-1)[:q.size].astype(dtype)


def _seg_wire_nbytes(plan: ReshardPlan, seg: ShardSegment,
                     rows: int, quantized: bool) -> int:
    dtype = jnp.dtype(plan.group(seg.group).dtype)
    if quantized and dtype.kind == "f":
        n_blocks = -(-seg.length // plan.block_size)
        return rows * (seg.length + 4 * n_blocks)
    return rows * seg.length * dtype.itemsize


def pack_segments(plan: ReshardPlan, segs, shard_lookup,
                  quantized: bool = False):
    """Serialize ``segs`` (canonical order) into one uint8 wire buffer.

    ``shard_lookup(group_key, old_rank)`` returns that old rank's shard as a
    ``[rows, shard]`` float/int array — ``rows`` is the number of state
    leaves sharing the group's geometry (Adam: mu and nu = 2 rows). With
    ``quantized`` each float row-segment is block-int8 coded (scales then
    values); integer groups always travel raw."""
    import numpy as np
    parts = []
    for seg in segs:
        shard = np.asarray(shard_lookup(seg.group, seg.src))
        if shard.ndim == 1:
            shard = shard[None, :]
        chunk = shard[:, seg.src_offset:seg.src_offset + seg.length]
        dtype = jnp.dtype(plan.group(seg.group).dtype)
        if quantized and dtype.kind == "f":
            for row in chunk:
                q, scales = quantize_blocks_np(row, plan.block_size)
                parts.append(scales.tobytes())
                parts.append(q.tobytes())
        else:
            parts.append(np.ascontiguousarray(
                chunk.astype(dtype)).tobytes())
    return np.frombuffer(b"".join(parts), np.uint8).copy()


def unpack_segments(plan: ReshardPlan, segs, buf, sink,
                    quantized: bool = False):
    """Inverse of :func:`pack_segments`: scatter the wire buffer into the
    receiver's new shards via ``sink(group_key, dst_offset, [rows, length]
    array)``. Row counts must match what the sender packed — both sides
    derive them from the same state template."""
    import numpy as np
    buf = np.asarray(buf, np.uint8)
    off = 0
    for seg in segs:
        dtype = jnp.dtype(plan.group(seg.group).dtype)
        rows = sink(seg.group, None, None)  # row-count query
        if quantized and dtype.kind == "f":
            n_blocks = -(-seg.length // plan.block_size)
            out = np.empty((rows, seg.length), dtype)
            for r in range(rows):
                scales = np.frombuffer(
                    buf[off:off + 4 * n_blocks].tobytes(), np.float32)
                off += 4 * n_blocks
                q = np.frombuffer(
                    buf[off:off + seg.length].tobytes(), np.int8)
                off += seg.length
                out[r] = dequantize_blocks_np(q, scales, dtype,
                                              plan.block_size)
        else:
            nbytes = rows * seg.length * dtype.itemsize
            out = np.frombuffer(buf[off:off + nbytes].tobytes(),
                                dtype).reshape(rows, seg.length)
            off += nbytes
        sink(seg.group, seg.dst_offset, out)
    return off


def reshard(plan: ReshardPlan, my_rank: int, sources, shards, rows_by_group,
            exchange, quantized: bool = False):
    """Execute ``plan`` for new rank ``my_rank``.

    - ``sources``: old rank -> serving NEW rank. A survivor serves its own
      old shard; a drained rank's handoff or a buddy replica is served by
      whichever rank holds it; old ranks absent from the map are LOST — the
      receiver zero-fills their ranges (fresh-moment resume for that slice).
    - ``shards``: ``(group_key, old_rank) -> [rows, shard]`` lookup valid
      for every old rank assigned to ``my_rank``.
    - ``rows_by_group``: group_key -> state rows sharing the geometry.
    - ``exchange(send_bufs) -> recv_bufs``: ragged uint8 alltoall, one
      buffer per new rank (index = peer's new rank).

    Returns ``(new_shards, stats)`` where ``new_shards[group] `` is a
    zero-initialized ``[rows, new_shard]`` array with every served segment
    scattered in, and ``stats`` accounts wire/local bytes and lost
    elements."""
    import numpy as np
    send_bufs = []
    for dst in range(plan.new_world):
        segs = plan.segments_for_pair(my_rank, dst, sources)
        send_bufs.append(pack_segments(plan, segs, shards, quantized)
                         if segs else np.empty(0, np.uint8))
    recv_bufs = exchange(send_bufs)
    new_shards = {}
    for g in plan.new_groups:
        rows = int(rows_by_group.get(g.key, 1))
        new_shards[g.key] = np.zeros((rows, g.shard),
                                     jnp.dtype(g.dtype))
    lost = 0
    for seg in plan.segments:
        if seg.dst == my_rank and seg.src not in sources:
            lost += seg.length
    for serving in range(plan.new_world):
        segs = plan.segments_for_pair(serving, my_rank, sources)
        if not segs:
            continue

        def sink(key, dst_offset, chunk,
                 _rows=rows_by_group, _out=new_shards):
            if dst_offset is None:
                return int(_rows.get(key, 1))
            _out[key][:, dst_offset:dst_offset + chunk.shape[1]] = chunk
            return None

        unpack_segments(plan, segs, recv_bufs[serving], sink, quantized)
    wire = sum(int(b.size) for i, b in enumerate(send_bufs) if i != my_rank)
    stats = {
        "wire_bytes_sent": wire,
        "local_bytes": int(send_bufs[my_rank].size)
        if my_rank < len(send_bufs) else 0,
        "lost_elements": lost,
        "quantized": bool(quantized),
    }
    return new_shards, stats


def reshard_wire_bytes(plan: ReshardPlan, sources, rows_by_group,
                       quantized: bool = False) -> int:
    """Total cross-rank wire bytes the plan moves under ``sources`` (the
    sum every rank's ``stats['wire_bytes_sent']`` would report) — the
    metrics' accounting shares this one formula with the executor."""
    total = 0
    for seg in plan.segments:
        serving = sources.get(seg.src)
        if serving is None or serving == seg.dst:
            continue
        rows = int(rows_by_group.get(seg.group, 1))
        total += _seg_wire_nbytes(plan, seg, rows, quantized)
    return total


def optimizer_state_bytes(params, n_shards: int, state_factor: float = 2.0,
                          block_size: int = LANE) -> dict:
    """Memory math for the docs: replicated vs sharded optimizer-state
    bytes per replica. ``state_factor`` = state floats per param (2.0 for
    Adam m+v, 1.0 for momentum)."""
    leaves = jax.tree_util.tree_leaves(params)
    total = sum(
        int(l.size) * jnp.dtype(l.dtype).itemsize for l in leaves)
    padded = sum(g.padded * jnp.dtype(g.dtype).itemsize
                 for g in _group_leaves(leaves, n_shards, block_size))
    return {
        "replicated": int(total * state_factor),
        "sharded": int(padded * state_factor / n_shards),
    }


def collective_bytes_per_step(n_params: int,
                              n_shards: int,
                              *,
                              mode: str = "allreduce",
                              wire_bytes_per_elem: float = 4.0,
                              block_size: int = LANE,
                              scale_bytes: float = 4.0) -> int:
    """Ring-cost wire bytes each replica moves per step for the gradient
    exchange: the one formula the docs' figures and the tests share.

    Ring allreduce moves ``2 * (N-1)/N * payload`` per replica
    (reduce-scatter + all-gather); the sharded pipeline moves the same two
    phases explicitly, so fp32 bytes match — the sharded win at equal
    precision is state memory and update FLOPs. Quantized payloads add one
    fp32 scale per ``block_size`` elements on each phase.

    ``mode`` ∈ {"allreduce", "sharded"}; ``wire_bytes_per_elem``: 4.0 fp32,
    2.0 bf16/fp16, 1.0 int8.
    """
    if mode not in ("allreduce", "sharded"):
        raise ValueError(f"unknown mode {mode!r}")
    ring = 2.0 * (n_shards - 1) / max(n_shards, 1)
    payload = n_params * wire_bytes_per_elem
    if wire_bytes_per_elem == 1.0:  # int8 blocks carry fp32 scales
        payload += n_params / block_size * scale_bytes
    return int(ring * payload)
