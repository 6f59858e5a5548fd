"""In-program tensor fusion: many small collectives → one big one.

The reference's fusion buffer memcpys small tensors into a persistent 128 MB
device buffer, runs one collective, and unpacks
(reference: horovod/common/fusion_buffer_manager.cc,
ops/collective_operations.h:65-86, threshold set at operations.cc:444).

Under XLA the packing is easy to express (concatenate flattened tensors per
dtype inside the traced program) but it is not free, and the compiler does
not need it. On a TPU v5e the ``ravel``/``concatenate``/slice/``reshape`` of
GPT-2 small's 196 gradient leaves (0.498 GB of fp32) ran as 65 relayouts of
tiled, weight-shaped arrays plus the in-place ``dynamic-update-slice``s of
the flat buffer: 10.6-10.9 ms of a step on ONE chip, where the all-reduce
itself compiles to nothing, and 12.0 ms beside an 8.75 ms collective on
four (PERF.md §5-6, PR 24). Given the leaves, XLA's all-reduce combiner
groups them into a few variadic all-reduces in their own layouts. So the
gradient exchange's plain path reduces leaf by leaf
(:func:`horovod_tpu.parallel.collectives.allreduce_tree`, PR 25) and does
not come here.

Who still wants the flat buffer, and why:

- the int8 wire format (``dp._make_grad_allreduce``, ``DistributedOptimizer``):
  quantization blocks and the reduce-scatter's rows are cut from one flat,
  aligned payload, so the result depends on the layout;
- ``collectives.grouped_allreduce``: the reference's contract that a group
  is reduced as one unit;
- ``broadcast_parameters``: a masked psum per leaf at start-up, off the
  step's path, where one collective per dtype is the simpler program;
- :mod:`horovod_tpu.parallel.bucketing`, which packs per (bucket, dtype) in
  the same way to bound each collective's size.
"""

from __future__ import annotations

from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp


def fused_apply(fn: Callable[[jax.Array], jax.Array],
                xs: Sequence[jax.Array]) -> List[jax.Array]:
    """Apply an elementwise-collective ``fn`` to all of ``xs`` fused per dtype.

    ``fn`` must be shape-preserving and elementwise-independent (allreduce
    variants are; allgather/alltoall are not — those fuse at the engine level
    instead)."""
    xs = list(xs)
    if not xs:
        return []
    if len(xs) == 1:
        return [fn(xs[0])]

    # Stable grouping by dtype, mirroring the reference's per-(device,dtype)
    # fusion constraint (controller.cc FuseResponses requires matching types).
    groups: dict = {}
    for i, x in enumerate(xs):
        groups.setdefault(jnp.dtype(x.dtype), []).append(i)

    out: List = [None] * len(xs)
    for dtype, idxs in groups.items():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = fn(xs[i])
            continue
        flat = [xs[i].ravel() for i in idxs]
        sizes = [f.size for f in flat]
        fused = jnp.concatenate(flat)
        reduced = fn(fused)
        offset = 0
        for i, sz in zip(idxs, sizes):
            out[i] = reduced[offset:offset + sz].reshape(xs[i].shape)
            offset += sz
    return out


def fused_apply_tree(fn: Callable[[jax.Array], jax.Array], tree):
    """Tree-structured variant: fuse every leaf of a pytree (a grads pytree),
    preserving structure — the DistributedOptimizer hot path."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(treedef, fused_apply(fn, leaves))
