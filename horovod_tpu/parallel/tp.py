"""Tensor parallelism: Megatron-style column/row-parallel linear algebra
over the ``model`` mesh axis.

The reference has no TP (SURVEY §2.8: ABSENT — no layer sharding
anywhere); on TPU it is the natural second axis after data. The classic
pairing, re-derived on XLA collectives:

- **column-parallel** ``y = x @ W``: W is split on its *output* dim, each
  rank computes its slice of y, no communication (the following row
  parallel op consumes the split activations directly).
- **row-parallel** ``y = x @ W``: W is split on its *input* dim and x
  arrives already split (the column output); partial products ``psum``
  over the ``model`` axis.

One ``psum`` per column→row pair — the Megatron MLP/attention recipe.
Weights live pre-sharded per rank (shape ``[d, h/n]`` / ``[h/n, d]``
inside shard_map); shard with ``PartitionSpec`` on the host side.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tp(x: jax.Array, axis: str) -> jax.Array:
    """Megatron's ``f`` operator: identity forward, psum backward — wraps a
    replicated activation entering a column-parallel layer so its gradient
    sums every rank's contribution. (Raw autodiff through shard_map's psum
    would double-count: psum's transpose is psum, and the replicated
    cotangent would pick up a factor of the axis size.)"""
    return x


copy_to_tp.defvjp(lambda x, axis: (x, None),
                  lambda axis, _, g: (lax.psum(g, axis),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tp(x: jax.Array, axis: str) -> jax.Array:
    """Megatron's ``g`` operator: psum forward, identity backward — the
    row-parallel output reduction whose cotangent is already replicated."""
    return lax.psum(x, axis)


reduce_from_tp.defvjp(lambda x, axis: (lax.psum(x, axis), None),
                      lambda axis, _, g: (g,))


def column_parallel(x: jax.Array, w_shard: jax.Array,
                    b_shard: Optional[jax.Array] = None,
                    axis: str = "model") -> jax.Array:
    """``x @ W`` with W column-sharded: returns this rank's output slice
    ``[..., h/n]``. No forward communication (the input gradient psums)."""
    y = jnp.einsum("...d,dh->...h", copy_to_tp(x, axis), w_shard)
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel(x_shard: jax.Array, w_shard: jax.Array,
                 b: Optional[jax.Array] = None,
                 axis: str = "model") -> jax.Array:
    """``x @ W`` with W row-sharded and x already split on its last dim:
    partial products summed over ``axis`` (one psum). ``b`` is the full
    (replicated) bias, added once after the reduction."""
    y = reduce_from_tp(jnp.einsum("...h,hd->...d", x_shard, w_shard), axis)
    if b is not None:
        y = y + b
    return y


def tp_mlp(x: jax.Array, w_in_shard: jax.Array, w_out_shard: jax.Array,
           activation: Callable = jax.nn.gelu,
           axis: str = "model") -> jax.Array:
    """The Megatron two-layer MLP: column-parallel up-projection, nonlinear
    elementwise on the shard, row-parallel down-projection — exactly one
    psum for the whole block."""
    h = activation(column_parallel(x, w_in_shard, axis=axis))
    return row_parallel(h, w_out_shard, axis=axis)


# ---------------------------------------------------------------------------
# Inference path: forward-only TP with compressed activation collectives.
#
# Training reserved the int8 quantized collectives (EQuARX,
# arXiv:2506.17615) for gradients; serving applies them to *activations* —
# the row-parallel partial-product reduction is the only wire traffic of a
# Megatron block, and at decode batch sizes it is latency- not
# bandwidth-bound, so quartering its bytes shrinks the exposed-comm tail
# directly. Forward-only: no custom_vjp wrappers (quantization is not
# usefully differentiable, and serving never runs backward).


def row_parallel_inference(x_shard: jax.Array, w_shard: jax.Array,
                           b: Optional[jax.Array] = None,
                           axis: str = "model",
                           compression=None) -> jax.Array:
    """Forward-only :func:`row_parallel` whose reduction can ride the int8
    quantized wire. ``compression`` follows the
    :class:`horovod_tpu.jax.compression.Compression` convention: a
    compressor with ``quantized = True`` routes the partial-product sum
    through ``quantized_allreduce`` (dequantize-reduce-requantize); anything
    else is a plain psum. Bias is replicated, added after the reduction."""
    from horovod_tpu.common.reduce_ops import Sum
    from horovod_tpu.parallel.collectives import quantized_allreduce
    y = jnp.einsum("...h,hd->...d", x_shard, w_shard)
    if compression is not None and getattr(compression, "quantized", False):
        y = quantized_allreduce(
            y, op=Sum, axis=axis,
            block_size=getattr(compression, "block_size", 256))
    else:
        y = lax.psum(y, axis)
    if b is not None:
        y = y + b
    return y


def tp_mlp_inference(x: jax.Array, w_in_shard: jax.Array,
                     w_out_shard: jax.Array,
                     activation: Callable = jax.nn.gelu,
                     axis: str = "model",
                     compression=None) -> jax.Array:
    """Forward-only :func:`tp_mlp` with a selectable activation wire format
    for its single reduction (the serving executor's building block)."""
    h = activation(jnp.einsum("...d,dh->...h", x, w_in_shard))
    return row_parallel_inference(h, w_out_shard, axis=axis,
                                  compression=compression)


def tp_activation_wire_bytes(n_elements: int, world: int,
                             compression=None,
                             wire_bytes_per_elem: float = 4.0) -> int:
    """Ring-allreduce wire bytes per rank for one activation reduction of
    ``n_elements`` — the int8-vs-fp32 savings ``make_tp_lm_step`` reports.
    fp32 psum moves ``2*(world-1)/world * 4`` bytes/element (reduce-scatter
    + all-gather phases); the quantized path moves int8 payloads plus one
    fp32 scale per block on each phase."""
    if world <= 1:
        return 0
    phase = 2.0 * (world - 1) / world
    if compression is not None and getattr(compression, "quantized", False):
        block = getattr(compression, "block_size", 256)
        per_elem = 1.0 + 4.0 / block
    else:
        per_elem = wire_bytes_per_elem
    return int(phase * per_elem * n_elements)
