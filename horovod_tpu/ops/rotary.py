"""Rotary position embedding (rotate-half) as one operator with its own
backward, written for the chip.

The rotation of ``x`` [B, T, H, D] at positions ``0..T-1`` pairs column ``i``
of a head with column ``i + D/2`` and turns the pair by the angle
``a = t * theta ** (-2i / D)``:

    out = x * [cos a | cos a] + swap_halves(x) * [-sin a | sin a]

with ``swap_halves`` the head's two halves exchanged. The angles, the two
``[T, D]`` tables, the products and the sum are float32 and the result is
cast to ``x.dtype`` once (the dense models' dtype policy,
``models/olmoe.py``); the swap moves values and rounds nothing, so it is made
in ``x.dtype``.

*Its own backward* (``jax.custom_vjp``): the cotangent of a rotation by ``a``
is the rotation of the incoming cotangent by ``-a``, the same formula with
the sine's sign turned. Nothing is a residual: no float32 copy of q or k is
kept or recomputed for the backward, and the transpose of a split and a join
of float32 halves (pads and adds) is in no program.

*By the head's width and the direction alone* (``PERF.md`` §6, PR 51, has the
measurements; no option, no model's name):

- Heads that fill whole 128-lane registers (``D`` a multiple of 128), forward:
  a Pallas kernel over ``[H, B, T, D]``, heads outermost, which is how the
  projections' products lie on the chip and how the flash kernels take their
  operands, so the transposes around the call are free. A head's tile is
  whole registers and ``swap_halves`` one rotation of the lanes
  (``pltpu.roll``): no split and no join of the lane axis. All the arrays of
  a call (q and k) go through ONE kernel call, a grid step a batch row and a
  tile of positions with every head of both, against the same two tables.
  Called through ``kernel_call.on_this_platform`` and jitted: lowered once a
  shape a process, not once a layer.
- The same heads, backward: the formula in ``jax.numpy`` (``jnp.roll`` of the
  cotangent in its own dtype). A kernel's operands are row-major by rule,
  and what consumes the backward's result (a per-head norm's backward, the
  projections' two transposed products) wants other layouts: with a kernel
  there the compiler paid more in float32 copies under ``attn_qk_norm`` than
  the kernel saved, while the ``jax.numpy`` backward fuses with its
  neighbours.
- Narrower heads (a head of 64 is half a register), both ways: the formula
  in ``jax.numpy`` with the halves swapped by a product with a ``D x D``
  permutation matrix: one 1 a column, so the product moves values and rounds
  nothing, and the matrix unit, idle in this pass, does the lane shuffle the
  vector unit is slow at. In the cells it measured ahead of ``jnp.roll``, of
  the split and join under the same ``custom_vjp`` and of the parent, and
  ``jnp.roll`` there made one cell's step slower while its attention got
  cheaper (what the compiler did to the layers around it).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import on_this_platform

F32 = jnp.float32
LANES = 128
# Positions of a kernel's block; a block of q is H x TILE x D.
TILE = 256
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=48 << 20)


def _count_call(head_dim: int):
    """Monitoring, at trace time as ``hvd_flash_calls_total`` is: the kernel
    calls just traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_rotary_calls_total",
        "rotary kernel calls traced (the forward pass of one call site), "
        "by head width", head_dim=str(head_dim)).inc()


def tables(t: int, d: int, theta: float, sign: int
           ) -> Tuple[jax.Array, jax.Array]:
    """``[cos | cos]`` and ``sign * [-sin | sin]`` of positions ``0..t-1`` for
    heads of ``d``, float32 ``[t, d]``. Column ``j`` holds the angle of its
    pair, ``t * theta ** (-(2 (j mod d/2)) / d)``: the values of
    ``theta ** (-arange(0, d, 2) / d)`` twice over, written without a join."""
    column = jnp.arange(d, dtype=jnp.int32)
    pair = (column % (d // 2)).astype(F32) * 2
    angles = jnp.arange(t, dtype=F32)[:, None] * (theta ** (-pair / d))[None]
    sin = jnp.sin(angles)
    first = (column < d // 2)[None]
    return jnp.cos(angles), jnp.where(first == (sign > 0), -sin, sin)


def _rotary_kernel(cos_ref, sin_ref, *refs):
    """Blocks [H_i, 1, rows, D] of each array of the call against the
    tables' [rows, D]; ``refs`` are the inputs, then the outputs."""
    cos, sin = cos_ref[...], sin_ref[...]
    count = len(refs) // 2
    for x_ref, o_ref in zip(refs[:count], refs[count:]):
        for head in range(x_ref.shape[0]):
            x = x_ref[head, 0].astype(F32)
            swapped = pltpu.roll(x, x.shape[-1] // 2, 1)
            o_ref[head, 0] = (x * cos + swapped * sin).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rotary_call(cos, sin, *xs, interpret: bool):
    """``xs`` [H_i, B, T, D] each; ``cos``, ``sin`` [T, D]."""
    _, batch, t, d = xs[0].shape
    tile = min(t, TILE)
    table = pl.BlockSpec((tile, d), lambda b, i: (i, 0))
    heads = [pl.BlockSpec((x.shape[0], 1, tile, d), lambda b, i: (0, b, i, 0))
             for x in xs]
    return pl.pallas_call(
        _rotary_kernel, grid=(batch, pl.cdiv(t, tile)),
        in_specs=[table, table] + heads, out_specs=heads,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs],
        compiler_params=_PARAMS, interpret=interpret)(cos, sin, *xs)


def _turn(xs: Tuple[jax.Array, ...], theta: float, sign: int
          ) -> Tuple[jax.Array, ...]:
    """Each of ``xs`` [B, T, H_i, D] turned by ``sign`` times its positions'
    angles."""
    t, d = xs[0].shape[1], xs[0].shape[-1]
    cos, sin = tables(t, d, theta, sign)
    if sign > 0 and d % LANES == 0:
        _count_call(d)
        out = on_this_platform(
            _rotary_call, cos, sin, *(x.transpose(2, 0, 1, 3) for x in xs))
        return tuple(o.transpose(1, 2, 0, 3) for o in out)
    cos, sin = cos[:, None, :], sin[:, None, :]
    if d % LANES:
        # column j of x @ swap is x's column j -+ d/2: one 1 a column, so
        # the product moves values and rounds nothing
        swap = jnp.roll(jnp.eye(d, dtype=xs[0].dtype), d // 2, axis=1)
        return tuple(
            (x.astype(F32) * cos + jax.lax.dot_general(
                x, swap, (((3,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=F32) * sin).astype(x.dtype)
            for x in xs)
    return tuple(
        (x.astype(F32) * cos
         + jnp.roll(x, d // 2, axis=-1).astype(F32) * sin).astype(x.dtype)
        for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rotary(xs, theta):
    return _turn(xs, theta, 1)


def _rotary_fwd(xs, theta):
    return _turn(xs, theta, 1), None


def _rotary_bwd(theta, _, cotangents):
    return (_turn(tuple(cotangents), theta, -1),)


_rotary.defvjp(_rotary_fwd, _rotary_bwd)


def rotary(x: Union[jax.Array, Sequence[jax.Array]], theta: float):
    """Rotate-half rotary embedding of ``x`` [B, T, H, D] at positions
    ``0..T-1``, angles and rotation in float32, the result in ``x.dtype``;
    or of several such arrays that share ``B``, ``T`` and ``D`` (q and k of a
    call: one pass, one pair of tables), returned as a tuple. The backward is
    the operator's own: the same rotation by the negated angles."""
    if not isinstance(x, (tuple, list)):
        return rotary((x,), theta)[0]
    lead = x[0].shape
    for other in x[1:]:
        if other.shape[:2] + other.shape[3:] != lead[:2] + lead[3:]:
            raise ValueError(
                f"arrays of one rotary call share batch, positions and head "
                f"width: {lead} against {other.shape}")
    return _rotary(tuple(x), float(theta))
