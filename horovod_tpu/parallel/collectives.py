"""In-program collective primitives over named mesh axes.

This is the TPU data plane: where the reference dispatches to
NCCL/MPI/Gloo/oneCCL library calls on raw buffers (reference:
horovod/common/ops/nccl_operations.cc:126-184, mpi_operations.cc,
gloo_operations.cc), a TPU program expresses collectives *inside* the compiled
computation and XLA lowers them onto ICI. These functions are meant to be used
under ``jax.shard_map`` / ``pjit`` with a mesh from
:mod:`horovod_tpu.parallel.mesh`.

API parity (reference: horovod/torch/mpi_ops.py, horovod/tensorflow/mpi_ops.py):
allreduce / grouped_allreduce / allgather / broadcast / alltoall (+
reducescatter and barrier, which the reference composes internally), each with
``op`` ∈ {Average, Sum, Adasum, Min, Max, Product} and
prescale/postscale factors (reference: horovod/common/message.h Request
prescale_factor/postscale_factor).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common.reduce_ops import (  # noqa: F401  (re-exported)
    Adasum, Average, Max, Min, Op, Product, Sum,
)
from horovod_tpu.profiler.annotate import collective_scope

# Default axis: data parallelism — the reference's only axis (SURVEY §2.8).
DEFAULT_AXIS = "data"


def _count_trace(kind: str):
    """Monitoring: count collective *insertions* at trace time. In-jit
    collectives execute inside the compiled program where no Python runs,
    so the honest live signal is how many of each kind each (re)trace
    emits — a retrace storm or an unexpected collective mix shows up here
    (runtime bytes/latency live in the device trace, profiler layer)."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter("hvd_injit_collective_traces_total",
                           kind=kind).inc()


def _scale(x, factor):
    if factor is None or factor == 1.0:
        return x
    # Match reference semantics: scaling happens in the tensor's dtype for
    # integral types, fp32 accumulation for fp16 (common/ops ScaleBuffer).
    if jnp.issubdtype(x.dtype, jnp.integer):
        return (x * factor).astype(x.dtype)
    return (x.astype(jnp.float32) * factor).astype(x.dtype) \
        if x.dtype in (jnp.float16, jnp.bfloat16) else x * factor


def _axes(axis) -> tuple:
    return tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)


def axis_size(axis=DEFAULT_AXIS) -> int:
    """Total extent across one or several named axes (static)."""
    n = 1
    for a in _axes(axis):
        n *= lax.axis_size(a)
    return n


def axis_rank(axis=DEFAULT_AXIS) -> jax.Array:
    """Linearized index across one or several named axes (row-major in the
    order given)."""
    idx = jnp.zeros((), jnp.int32)
    for a in _axes(axis):
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def allreduce(x: jax.Array,
              op: Op = Average,
              axis=DEFAULT_AXIS,
              prescale_factor: float = 1.0,
              postscale_factor: float = 1.0,
              accumulate_in_fp32: bool = True) -> jax.Array:
    """Reduce ``x`` across ``axis`` (reference: EnqueueTensorAllreduce,
    horovod/common/operations.cc:902 → NCCLAllreduce::Execute).

    ``accumulate_in_fp32=False`` keeps low-precision inputs in their dtype on
    the wire — the point of fp16/bf16 compression (half the ICI bytes);
    compressed paths set it."""
    _count_trace(f"allreduce_{op.value}")
    with collective_scope(f"hvd_allreduce_{op.value}"):
        return _allreduce(x, op, axis, prescale_factor, postscale_factor,
                          accumulate_in_fp32)


def _allreduce(x, op, axis, prescale_factor, postscale_factor,
               accumulate_in_fp32):
    x = _scale(x, prescale_factor)
    if op in (Average, Sum):
        # Default: sum in fp32 for low-precision inputs — same accumulation
        # contract as the reference's fp16 AVX kernels summing into fp32
        # (common/half.cc).
        orig_dtype = x.dtype
        if accumulate_in_fp32 and orig_dtype in (jnp.float16, jnp.bfloat16):
            x = x.astype(jnp.float32)
        out = lax.psum(x, axis)
        if op is Average:
            out = out / axis_size(axis)
        out = out.astype(orig_dtype)
    elif op is Min:
        out = lax.pmin(x, axis)
    elif op is Max:
        out = lax.pmax(x, axis)
    elif op is Product:
        # No native pprod: gather then reduce locally (XLA fuses the reduce).
        out = jnp.prod(lax.all_gather(x, axis, axis=0), axis=0)
    elif op is Adasum:
        from horovod_tpu.parallel.adasum import adasum_allreduce
        out = adasum_allreduce(x, axis)
    else:
        raise ValueError(f"unknown op {op}")
    return _scale(out, postscale_factor)


def wire_allreduce(x: jax.Array,
                   op: Op = Average,
                   axis=DEFAULT_AXIS,
                   prescale_factor: float = 1.0,
                   postscale_factor: float = 1.0,
                   compression=None,
                   hierarchical: bool = False) -> jax.Array:
    """Allreduce ``x`` in ``compression``'s wire dtype: cast, reduce, cast
    back. ``compression`` is a cast compressor (fp16/bf16) or None;
    block-quantized payloads are not psum-reducible and go through
    :func:`quantized_allreduce`. A cast payload stays in its dtype on the
    wire; without one, 16-bit inputs accumulate in fp32. ``hierarchical``
    takes the first of ``axis`` as the slow outer level."""
    ctx = None
    if compression is not None:
        x, ctx = compression.compress(x)
    kwargs = dict(op=op, prescale_factor=prescale_factor,
                  postscale_factor=postscale_factor,
                  accumulate_in_fp32=compression is None)
    if hierarchical:
        axes = _axes(axis)
        out = hierarchical_allreduce(x, outer_axis=axes[0],
                                     inner_axis=axes[1:], **kwargs)
    else:
        out = allreduce(x, axis=axis, **kwargs)
    if compression is not None:
        out = compression.decompress(out, ctx)
    return out


def allreduce_tree(tree, **kwargs):
    """The gradient exchange of both frontends' plain path:
    :func:`wire_allreduce` on every leaf of ``tree``, leaf by leaf.

    No flat buffer: each leaf keeps the shape and tiled layout the backward
    pass gave it, and XLA's all-reduce combiner groups the collectives;
    over a group of one nothing is left of them. What packing by hand cost
    on the chip is in :mod:`horovod_tpu.ops.fusion`. Every leaf's
    collective carries the ``hvd_allreduce_*`` scope, and
    ``hvd_injit_collective_traces_total`` counts one per leaf. On several
    TPU chips the combiner's variadic all-reduces block the chip; a leaf it
    leaves alone is cut up and rides inside the optimizer update, by the
    compile options the step carries
    (:data:`horovod_tpu.parallel.dp.ASYNC_EXCHANGE_COMPILER_OPTIONS`)."""
    return jax.tree_util.tree_map(
        functools.partial(wire_allreduce, **kwargs), tree)


def grouped_allreduce(xs: Sequence[jax.Array],
                      op: Op = Average,
                      axis=DEFAULT_AXIS,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0) -> list:
    """Allreduce a group as one fused collective.

    The reference fuses grouped entries through the fusion buffer as an atomic
    unit (reference: GroupTable, horovod/common/operations.cc:1008-1015). Here
    we concatenate flattened tensors per dtype-class into a single psum — one
    ICI collective instead of len(xs).

    Adasum is NOT elementwise-fusable (its coefficients are per-tensor dot
    products); it routes to the packed-exchange group variant that keeps
    per-tensor coefficients (reference: adasum.h fused-buffer offsets).
    """
    xs = list(xs)
    if op is Adasum:
        from horovod_tpu.parallel.adasum import adasum_allreduce_group
        xs = [_scale(x, prescale_factor) for x in xs]
        outs = adasum_allreduce_group(xs, axis)
        return [_scale(o, postscale_factor) for o in outs]
    from horovod_tpu.ops.fusion import fused_apply
    fn = functools.partial(allreduce, op=op, axis=axis,
                           prescale_factor=prescale_factor,
                           postscale_factor=postscale_factor)
    return fused_apply(fn, xs)


def hierarchical_allreduce(x: jax.Array,
                           op: Op = Average,
                           outer_axis="data",
                           inner_axis=("fsdp",),
                           prescale_factor: float = 1.0,
                           postscale_factor: float = 1.0,
                           accumulate_in_fp32: bool = True) -> jax.Array:
    """Two-level allreduce: reduce-scatter over the fast ``inner_axis``
    (intra-slice ICI), allreduce the 1/inner-sized shards over the slow
    ``outer_axis`` (cross-slice DCN), then all-gather over ``inner_axis``.

    Reference analog: NCCLHierarchicalAllreduce
    (ops/nccl_operations.cc:186-398 — NCCL ReduceScatter intra-node, MPI
    allreduce across nodes on rank-0 GPUs, NCCL Allgather back) and the
    HOROVOD_HIERARCHICAL_ALLREDUCE knob (operations.cc:470-494). The TPU
    form needs no staging through host rank-0: every device keeps a shard,
    so the DCN phase moves 1/inner of the bytes and is itself parallel
    across the slice's devices.

    Mesh contract: ``outer_axis`` is the axis whose links are slow (cross
    -slice DCN), ``inner_axis`` the fast intra-slice axes — AXIS_ORDER
    already places slow axes first (parallel/mesh.py).
    """
    if op not in (Average, Sum):
        # min/max/product have no reduce-scatter form; the flat path is
        # correct and these are off the hot path
        return allreduce(x, op=op,
                         axis=(*_axes(outer_axis), *_axes(inner_axis)),
                         prescale_factor=prescale_factor,
                         postscale_factor=postscale_factor,
                         accumulate_in_fp32=accumulate_in_fp32)
    _count_trace(f"hierarchical_allreduce_{op.value}")
    with collective_scope(f"hvd_hierarchical_allreduce_{op.value}"):
        return _hierarchical_allreduce(
            x, op, outer_axis, inner_axis, prescale_factor,
            postscale_factor, accumulate_in_fp32)


def _hierarchical_allreduce(x, op, outer_axis, inner_axis, prescale_factor,
                            postscale_factor, accumulate_in_fp32):
    x = _scale(x, prescale_factor)
    orig_dtype = x.dtype
    orig_shape = x.shape
    if accumulate_in_fp32 and orig_dtype in (jnp.float16, jnp.bfloat16):
        x = x.astype(jnp.float32)
    inner = _axes(inner_axis)
    n_inner = axis_size(inner)
    flat = x.reshape(-1)
    pad = (-flat.size) % n_inner
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = lax.psum_scatter(flat, inner, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, outer_axis)
    out = lax.all_gather(shard, inner, axis=0, tiled=True)
    if pad:
        out = out[:flat.size - pad]
    out = out.reshape(orig_shape)
    if op is Average:
        out = out / (axis_size(outer_axis) * n_inner)
    return _scale(out.astype(orig_dtype), postscale_factor)


def allgather(x: jax.Array, axis=DEFAULT_AXIS) -> jax.Array:
    """Concatenate ``x`` from every rank along dim 0 (reference:
    EnqueueTensorAllgather, horovod/common/operations.cc:1027; output
    allocation logic collective_operations.h:95-170).

    Inside a compiled program shapes are static, so this is the equal-shape
    case; ragged first dims (reference controller.cc:576-648 computes
    per-rank sizes) are handled by the eager engine path via padding
    (horovod_tpu.jax.mpi_ops).
    """
    _count_trace("allgather")
    with collective_scope("hvd_allgather"):
        return lax.all_gather(x, axis, axis=0, tiled=True)


def broadcast(x: jax.Array, root_rank: int, axis=DEFAULT_AXIS) -> jax.Array:
    """Every rank receives root's value (reference: EnqueueTensorBroadcast,
    operations.cc:1062). Implemented as a masked psum — a single collective,
    no gather of all shards."""
    _count_trace("broadcast")
    with collective_scope("hvd_broadcast"):
        idx = axis_rank(axis)
        orig_dtype = x.dtype
        xf = x.astype(jnp.float32) \
            if orig_dtype in (jnp.float16, jnp.bfloat16, jnp.bool_) else x
        masked = jnp.where(idx == root_rank, xf, jnp.zeros_like(xf))
        out = lax.psum(masked, axis)
        return out.astype(orig_dtype)


def alltoall(x: jax.Array,
             axis=DEFAULT_AXIS,
             split_axis: int = 0,
             concat_axis: int = 0) -> jax.Array:
    """Scatter equal slices of ``x`` to every rank and gather their slices
    (reference: EnqueueTensorAlltoall, operations.cc:1101; even-split case of
    MPI_Alltoallv). Ragged splits go through the eager engine path."""
    _count_trace("alltoall")
    with collective_scope("hvd_alltoall"):
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def reducescatter(x: jax.Array, op: Op = Average, axis=DEFAULT_AXIS) -> jax.Array:
    """Reduce-scatter along dim 0. The reference uses this as a building block
    (NCCLHierarchicalAllreduce's intra-node phase,
    ops/nccl_operations.cc:186-398); we expose it first-class because
    psum_scatter is the natural TPU gradient-sharding primitive."""
    if op not in (Average, Sum):
        raise ValueError(f"reducescatter supports Sum/Average, got {op}")
    _count_trace(f"reducescatter_{op.value}")
    with collective_scope(f"hvd_reducescatter_{op.value}"):
        out = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
        if op is Average:
            out = (out.astype(jnp.float32) / axis_size(axis)).astype(x.dtype)
        return out


def quantized_reducescatter(x: jax.Array,
                            op: Op = Average,
                            axis=DEFAULT_AXIS,
                            block_size: int = 256) -> jax.Array:
    """Reduce-scatter with an int8 wire format (EQuARX, arXiv:2506.17615).

    ``x`` is a 1-D array with ``x.size % (axis_size * block_size) == 0``.
    Each rank block-quantizes its n rows, exchanges them with a single int8
    ``all_to_all`` (plus one fp32 scale per block — 4/block_size overhead),
    then dequantizes and reduces its own chunk locally in fp32. Wire bytes:
    ~1/4 of the fp32 psum_scatter. Returns the local fp32 shard of size
    ``x.size / axis_size``.
    """
    from horovod_tpu.jax.compression import (block_dequantize_rows,
                                             block_quantize_rows)
    if op not in (Average, Sum):
        raise ValueError(f"quantized_reducescatter supports Sum/Average, "
                         f"got {op}")
    _count_trace(f"quantized_reducescatter_{op.value}")
    with collective_scope(f"hvd_quantized_reducescatter_{op.value}"):
        n = axis_size(axis)
        rows = x.reshape(n, -1)
        payload, scales = block_quantize_rows(rows, block_size)
        # Row d goes to rank d; we receive rank s's row-for-us as row s.
        payload = lax.all_to_all(payload, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        scales = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0,
                                tiled=True)
        out = jnp.sum(block_dequantize_rows(payload, scales, block_size),
                      axis=0)
        if op is Average:
            out = out / n
        return out


def quantized_allgather(x: jax.Array,
                        axis=DEFAULT_AXIS,
                        block_size: int = 256) -> jax.Array:
    """All-gather a 1-D shard (``x.size % block_size == 0``) as int8 blocks +
    fp32 scales; returns the concatenated fp32 array (rank order, dim 0)."""
    from horovod_tpu.jax.compression import (block_dequantize_rows,
                                             block_quantize_rows)
    _count_trace("quantized_allgather")
    with collective_scope("hvd_quantized_allgather"):
        payload, scales = block_quantize_rows(x.reshape(1, -1), block_size)
        payload = lax.all_gather(payload, axis, axis=0, tiled=False)
        scales = lax.all_gather(scales, axis, axis=0, tiled=False)
        n = payload.shape[0]
        out = block_dequantize_rows(payload.reshape(n, -1),
                                    scales.reshape(n, -1), block_size)
        return out.reshape(-1)


def quantized_allreduce(x: jax.Array,
                        op: Op = Average,
                        axis=DEFAULT_AXIS,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0,
                        block_size: int = 256) -> jax.Array:
    """Allreduce with int8 on the wire both ways: quantized reduce-scatter,
    then quantized all-gather of the reduced shards — the EQuARX composition.
    Accuracy: two quantize/dequantize round trips, so elementwise error is
    bounded by ~max|block|/127; use for gradients, not for state that must
    stay bit-exact across replicas (every rank applies the SAME dequantized
    result, so replica consistency itself is preserved)."""
    if op not in (Average, Sum):
        raise ValueError(f"quantized_allreduce supports Sum/Average, got {op}")
    x = _scale(x, prescale_factor)
    orig_dtype, orig_shape = x.dtype, x.shape
    n = axis_size(axis)
    flat = x.reshape(-1)
    pad = (-flat.size) % (n * block_size)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    shard = quantized_reducescatter(flat, op=op, axis=axis,
                                    block_size=block_size)
    out = quantized_allgather(shard, axis=axis, block_size=block_size)
    if pad:
        out = out[:flat.size - pad]
    out = out.reshape(orig_shape).astype(orig_dtype)
    return _scale(out, postscale_factor)


def barrier(axis=DEFAULT_AXIS) -> None:
    """Synchronization point (reference: controller Barrier,
    controller.h:158). In a compiled SPMD program a tiny psum serves as a
    cross-replica fence."""
    lax.psum(jnp.zeros((), jnp.float32), axis)


def ppermute(x: jax.Array, perm, axis=DEFAULT_AXIS) -> jax.Array:
    """Point-to-point ring/permutation exchange — the ICI-native primitive
    ring attention and Adasum's recursive exchanges build on."""
    return lax.ppermute(x, axis, perm)
