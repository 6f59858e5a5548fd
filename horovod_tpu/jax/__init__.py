"""The JAX user frontend — analog of the reference's ``horovod.torch`` /
``horovod.tensorflow`` packages (reference: horovod/torch/__init__.py,
horovod/tensorflow/__init__.py:568-742).

The reference wraps an imperative optimizer and hooks per-parameter gradient
callbacks; the optax analog wraps a GradientTransformation so the
gradient allreduce happens inside the one compiled train step.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.common.basics import (  # noqa: F401
    cross_rank, cross_size, init, is_initialized, local_rank, local_size,
    mesh, num_replicas, rank, shutdown, size, start_timeline, stop_timeline,
)
from horovod_tpu.jax.compression import Compression  # noqa: F401
from horovod_tpu.ops.fusion import fused_apply_tree
from horovod_tpu.parallel import collectives
from horovod_tpu.parallel.collectives import (  # noqa: F401
    Adasum, Average, Max, Min, Op, Product, Sum,
    reducescatter,
)
# Smart-dispatch collective ops: in-jit tracers → XLA/ICI collectives;
# concrete arrays → engine-coordinated eager path (reference surface:
# horovod/torch/mpi_ops.py).
from horovod_tpu.jax.mpi_ops import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    grouped_allreduce,
    grouped_allreduce_async,
    join,
    poll,
    synchronize,
)
from horovod_tpu.jax.functions import (  # noqa: F401
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
)
from horovod_tpu.jax.sync_batch_norm import SyncBatchNorm  # noqa: F401
from horovod_tpu.jax import elastic  # noqa: F401
from horovod_tpu.parallel.dp import (  # noqa: F401
    DP_AXES,
    make_eval_step,
    make_stateful_train_step,
    make_train_step,
)


class _DistOptState(NamedTuple):
    count: jax.Array          # microsteps since last boundary
    accum: Any                # local gradient accumulator (bpps > 1) or ()
    inner: Any                # wrapped transformation state


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *,
                         op: Op = Average,
                         axis=DP_AXES,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         average_aggregated_gradients: bool = True,
                         gradient_predivide_factor: float = 1.0,
                         ) -> optax.GradientTransformation:
    """Wrap an optax transformation with cross-replica gradient reduction.

    Parity with reference DistributedOptimizer knobs
    (horovod/torch/optimizer.py:443-508): ``op``, ``compression``,
    ``backward_passes_per_step`` (local aggregation, fewer collectives),
    ``gradient_predivide_factor`` (splits the averaging divisor across
    pre/post scaling, reference torch/__init__.py). Use inside shard_map /
    a mesh context — the reduction is ``lax.psum`` over the DP axes, leaf by
    leaf (:func:`collectives.allreduce_tree`, as ``make_train_step``); XLA
    combines the collectives.
    """
    if gradient_predivide_factor != 1.0 and op is not Average:
        raise ValueError("gradient_predivide_factor supported only with Average")
    if compression is None:
        compression = Compression.none
    bpps = int(backward_passes_per_step)
    if bpps < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    def _reduce(tree):
        ax = _axes_in_scope(axis)
        if op is Adasum:
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            outs = collectives.grouped_allreduce(leaves, op=op, axis=ax)
            return jax.tree_util.tree_unflatten(treedef, outs)
        # Average = sum * (1/size); gradient_predivide_factor splits the
        # divisor around the wire.
        scaled = dict(op=op) if gradient_predivide_factor == 1.0 else dict(
            op=Sum, prescale_factor=1.0 / gradient_predivide_factor,
            postscale_factor=gradient_predivide_factor
            / collectives.axis_size(ax))
        if getattr(compression, "quantized", False):
            # int8 block payloads are not psum-reducible — ride the
            # dequantize-reduce-requantize collective, on the flat
            # buffer its block cohorts need.
            return fused_apply_tree(
                lambda v: collectives.quantized_allreduce(
                    v, axis=ax, block_size=compression.block_size,
                    **scaled), tree)
        # The plain path is dp.make_train_step's: leaf by leaf.
        return collectives.allreduce_tree(
            tree, axis=ax, **scaled,
            compression=None if compression is Compression.none
            else compression)

    def _axes_in_scope(ax):
        # Filter requested axes down to those bound in the current trace so
        # the same optimizer works under any mesh shape.
        names = ax if isinstance(ax, (tuple, list)) else (ax,)
        bound = []
        for name in names:
            try:
                jax.lax.axis_size(name)
            except Exception:
                continue
            bound.append(name)
        if not bound:
            raise RuntimeError(
                f"DistributedOptimizer: none of axes {names} are bound; call "
                "the update inside shard_map over the mesh")
        return tuple(bound)

    def init_fn(params):
        accum = () if bpps == 1 else jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p), params)
        return _DistOptState(jnp.zeros((), jnp.int32), accum,
                             optimizer.init(params))

    def update_fn(grads, state, params=None):
        if bpps == 1:
            updates, inner = optimizer.update(_reduce(grads), state.inner, params)
            return updates, _DistOptState(state.count + 1, (), inner)

        accum = jax.tree_util.tree_map(lambda a, g: a + g, state.accum, grads)
        count = state.count + 1
        is_boundary = (count % bpps) == 0

        def boundary(args):
            accum, inner = args
            scale = (1.0 / bpps) if average_aggregated_gradients else 1.0
            g = jax.tree_util.tree_map(lambda a: a * scale, accum)
            updates, new_inner = optimizer.update(_reduce(g), inner, params)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return updates, zeroed, new_inner

        def skip(args):
            accum, inner = args
            updates = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return updates, accum, inner

        updates, accum, inner = jax.lax.cond(
            is_boundary, boundary, skip, (accum, state.inner))
        return updates, _DistOptState(count, accum, inner)

    return optax.GradientTransformation(init_fn, update_fn)


def broadcast_parameters(params, root_rank: int = 0, axis=DP_AXES):
    """Tree broadcast from ``root_rank`` (reference:
    horovod/torch/functions.py:29-112 broadcast_parameters).

    Inside a trace: fused per-dtype XLA collectives over ``axis``. On
    concrete values: the engine-coordinated eager path (cross-process)."""
    leaves = jax.tree_util.tree_leaves(params)
    if leaves and isinstance(leaves[0], jax.core.Tracer):
        return fused_apply_tree(
            lambda v: collectives.broadcast(v, root_rank, axis), params)
    from horovod_tpu.jax import functions
    return functions.broadcast_parameters(params, root_rank)


def metric_average(value, axis=DP_AXES, name: Optional[str] = None):
    """Average a scalar metric across replicas (reference: the
    ``metric_average`` pattern in examples/pytorch/pytorch_mnist.py and
    MetricAverageCallback, horovod/_keras/callbacks.py:48-88).

    Smart-dispatched: tracers inside shard_map use the in-jit ``lax.psum``
    collective; concrete host values (the eager post-epoch pattern) go
    through the engine-coordinated eager allreduce."""
    value = jnp.asarray(value)
    if isinstance(value, jax.core.Tracer):
        return collectives.allreduce(value, op=Average, axis=axis)
    from horovod_tpu.jax import mpi_ops
    return mpi_ops.allreduce(value, op=Average, axis=axis,
                             name=name or "metric_average")
