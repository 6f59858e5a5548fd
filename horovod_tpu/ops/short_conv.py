"""The gated short convolution of an LFM2 ``conv`` layer: the part between
its two projections, one pass over HBM a direction.

``models/lfm2.py``'s operator is ``out_proj(C * conv(B * u))`` with
``[B | C | u] = in_proj(x)``; this module is the middle, a position and
channel at a time:

    g_t = B_t * u_t
    c_t = sum_{j=0..K-1} w[j] * g_{t-K+1+j}        zeros before t = 0
    y_t = C_t * c_t

depthwise (a channel meets no other), causal over ``K`` taps (``conv_L_cache``
= 3: a position sees itself and the two before it), no bias and **no
activation** (``ops/ssm_ends.causal_conv_silu``, the nearest writing here,
has both and no gate on either side, and works with positions along the
lanes: nothing is shared with it). A few operations an element, so what it
costs is the bytes it moves: ``[T, 3d]`` read and ``[T, d]`` written forward,
``[T, 3d]`` and ``dy`` read and ``[T, 3d]`` written backward.

Two writings. :func:`gated_short_conv_plain` is plain ``jax.numpy`` whose
gradient is autodiff's: what the kernels are held to, reached by tests only.
Left to the compiler's fusions it moved more than twice a pass's bytes in
``lfm2-t16384`` (10.9 ms a step under ``shortconv_mix`` where the bytes ask
for 4.9, and as much again inside the out-projection's fusions, which took
the second gate and its float32 operand: ``PERF.md`` §6, PR 44).
:func:`gated_short_conv` is what the model calls, on every backend
(``kernel_call.on_this_platform``): a ``jax.custom_vjp`` that saves ``bcu``
and the taps alone and whose two passes are Pallas kernels, each reading
its operands once and writing its results once.

*Positions stay where the projections leave them*: ``bcu`` is ``[B, T, 3d]``
with the channels along the lanes, and a kernel's block is ``TILE`` whole
rows of it (all three runs of a position side by side, so one operand and
one result serve the three), worked ``CHUNK`` lanes at a time. No transpose
and no padded copy of the ``[T, 3d]`` array. A shift along the sequence is a
roll down a tile's rows; the rows that come from outside the tile are read
from a second, ``HALO``-row block of the same array before it (backward:
after it too, of ``bcu`` and of ``dy``), zeros past either end of the
sequence, so no tile waits for another and both grid axes are parallel.
Backward a tile forms ``g`` and the conv again from ``bcu`` (nothing else is
saved), ``dw`` adds up down a tile's rows in float32 and is written once a
tile; the caller adds the tiles and the batch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import on_this_platform

F32 = jnp.float32
# Positions of a tile (and the shorter ones a short sequence is cut into),
# the rows of the block read before and after it (a packed bf16 register's
# height), and the lanes worked at a time.
TILES = (256, 128, 64, 32, 16)
HALO = 16
CHUNKS = (512, 256, 128)
# a backward tile holds 256 x 6144 of bcu and of its gradient and 256 x 2048
# of dy, each twice for the pipeline (14 MiB), and its float32 temporaries
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=64 << 20)


def _count_pass(direction: str):
    """Monitoring, at trace time as ``hvd_ssm_end_calls_total`` is: the
    passes of the middle that were just traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_shortconv_passes_total",
        "passes of a gated short convolution traced, by direction",
        direction=direction).inc()


# -- the plain writing --------------------------------------------------------

def _runs(bcu, d: int):
    if bcu.shape[-1] != 3 * d:
        raise ValueError(f"[B | C | u] of {bcu.shape[-1]} channels for taps "
                         f"over {d}")
    return (bcu[..., i * d:(i + 1) * d].astype(F32) for i in range(3))


def gated_short_conv_plain(bcu: jax.Array, w: jax.Array, dtype) -> jax.Array:
    """``C * conv(B * u)``: ``bcu`` [B, T, 3d] holds the runs ``B``, ``C``
    and ``u`` in that order along its last axis, ``w`` [K, d] the taps
    (float32), last tap on the position itself. The gates and the taps' sum
    run in float32; returns [B, T, d] in ``dtype``."""
    taps, d = w.shape
    t = bcu.shape[1]
    b_run, c_run, u_run = _runs(bcu, d)
    g = jnp.pad(b_run * u_run, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(w[j].astype(F32) * g[:, j:j + t] for j in range(taps))
    return (c_run * conv).astype(dtype)


# -- the kernels --------------------------------------------------------------

def _row(v, n: int):
    """Row ``n`` of ``v`` [rows, lanes] as [1, lanes]: a masked sum down the
    rows, which needs no slice inside a register."""
    rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    return jnp.sum(jnp.where(rows == n, v, 0.0), axis=0, keepdims=True)


def _shifted(v, outside, back: int):
    """``v`` [rows, lanes] moved ``back`` rows down (``back`` < 0: up): row
    ``r`` holds ``v[r - back]``, and where that lies outside the tile the
    row of ``outside`` [HALO, lanes] that continues it: its last rows before
    the tile, its first rows after."""
    if not back:
        return v
    count = v.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    out = pltpu.roll(v, back % count, 0)
    for n in range(abs(back)):
        at, source = (n, outside.shape[0] - back + n) if back > 0 else \
            (count + back + n, n)
        out = jnp.where(rows == at, _row(outside, source), out)
    return out


def _tile_runs(ref, d: int, at: int, width: int):
    """The float32 ``B``, ``C`` and ``u`` of a block [1, rows, 3d] at lanes
    ``at .. at + width`` of each run."""
    return (ref[0, :, run * d + at:run * d + at + width].astype(F32)
            for run in range(3))


def _gates_before(bcu_ref, before_ref, d, at, width):
    """Of a tile's chunk: ``B``, ``C``, ``u``, ``g = B u`` and ``g`` of the
    ``HALO`` positions before the tile, zeros before the sequence."""
    b_run, c_run, u_run = _tile_runs(bcu_ref, d, at, width)
    b_before, _, u_before = _tile_runs(before_ref, d, at, width)
    before = jnp.where(pl.program_id(1) == 0, 0.0, b_before * u_before)
    return b_run, c_run, u_run, b_run * u_run, before


def _mix_fwd_kernel(bcu_ref, before_ref, w_ref, y_ref, *, chunk: int):
    taps, d = w_ref.shape
    for at in range(0, d, chunk):
        _, c_run, _, g, before = _gates_before(bcu_ref, before_ref, d, at,
                                               chunk)
        conv = sum(w_ref[j:j + 1, at:at + chunk]
                   * _shifted(g, before, taps - 1 - j) for j in range(taps))
        y_ref[0, :, at:at + chunk] = (c_run * conv).astype(y_ref.dtype)


def _mix_bwd_kernel(bcu_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                    w_ref, dbcu_ref, dw_ref, *, chunk: int):
    taps, d = w_ref.shape
    last = pl.program_id(1) == pl.num_programs(1) - 1
    for at in range(0, d, chunk):
        lanes = slice(at, at + chunk)
        b_run, c_run, u_run, g, before = _gates_before(
            bcu_ref, before_ref, d, at, chunk)
        g_back = [_shifted(g, before, taps - 1 - j) for j in range(taps)]
        w = [w_ref[j:j + 1, lanes] for j in range(taps)]
        dy = dy_ref[0, :, lanes].astype(F32)
        dconv = dy * c_run
        _, c_after, _ = _tile_runs(after_ref, d, at, chunk)
        after = jnp.where(last, 0.0,
                          dy_after_ref[0, :, lanes].astype(F32) * c_after)
        # g_t feeds the conv at t .. t+K-1 through taps K-1 .. 0
        dg = sum(w[j] * _shifted(dconv, after, j - (taps - 1))
                 for j in range(taps))
        for run, value in enumerate((
                dg * u_run, dy * sum(w[j] * g_back[j] for j in range(taps)),
                dg * b_run)):
            dbcu_ref[0, :, run * d + at:run * d + at + chunk] = \
                value.astype(dbcu_ref.dtype)
        for j in range(taps):
            dw_ref[0, 0, j:j + 1, lanes] = jnp.sum(
                dconv * g_back[j], axis=0, keepdims=True)


def _largest(options, n: int, otherwise: int) -> int:
    return next((o for o in options if n % o == 0), otherwise)


def _specs(bcu, d: int):
    """The grid over ``bcu`` [B, T, 3d] and the specs of a tile of it, of the
    ``HALO`` rows before and after a tile, and the same three of a [B, T, d]
    array."""
    batch, t, _ = bcu.shape
    tile = _largest(TILES, t, 0)
    if not tile:
        raise ValueError(f"a sequence of {t} positions is no whole tiles "
                         f"of {TILES[-1]}")
    halos, ahead = t // HALO, tile // HALO

    def rows_of(width):
        return (pl.BlockSpec((1, tile, width), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, HALO, width), lambda b, i: (
                    b, jnp.maximum(i * ahead - 1, 0), 0)),
                pl.BlockSpec((1, HALO, width), lambda b, i: (
                    b, jnp.minimum((i + 1) * ahead, halos - 1), 0)))
    return (batch, t // tile), rows_of(3 * d), rows_of(d)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _mix_forward_call(bcu, w, *, dtype, interpret):
    taps, d = w.shape
    grid, (wide, wide_before, _), (narrow, _, _) = _specs(bcu, d)
    return pl.pallas_call(
        functools.partial(_mix_fwd_kernel, chunk=_largest(CHUNKS, d, d)),
        grid=grid,
        in_specs=[wide, wide_before,
                  pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct(bcu.shape[:2] + (d,), dtype),
        compiler_params=_PARAMS, interpret=interpret,
    )(bcu, bcu, w)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _mix_backward_call(bcu, dy, w, *, interpret):
    """``dbcu`` [B, T, 3d] and the taps' gradient [B, tiles, K, d], float32,
    summed down each tile."""
    taps, d = w.shape
    grid, (wide, wide_before, wide_after), (narrow, _, narrow_after) = \
        _specs(bcu, d)
    return pl.pallas_call(
        functools.partial(_mix_bwd_kernel, chunk=_largest(CHUNKS, d, d)),
        grid=grid,
        in_specs=[wide, wide_before, wide_after, narrow, narrow_after,
                  pl.BlockSpec((taps, d), lambda b, i: (0, 0))],
        out_specs=[wide, pl.BlockSpec((1, 1, taps, d),
                                      lambda b, i: (b, i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((*grid, taps, d), F32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(bcu, bcu, bcu, dy, dy, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mix(bcu, w, dtype):
    _count_pass("fwd")
    return on_this_platform(
        functools.partial(_mix_forward_call, dtype=dtype), bcu, w)


def _mix_fwd(bcu, w, dtype):
    return _mix(bcu, w, dtype), (bcu, w)


def _mix_bwd(dtype, saved, dy):
    # traced under the call site's scope, like the forward
    bcu, w = saved
    _count_pass("bwd")
    dbcu, dw = on_this_platform(_mix_backward_call, bcu, dy, w)
    return dbcu, dw.sum((0, 1)).astype(w.dtype)


_mix.defvjp(_mix_fwd, _mix_bwd)


def gated_short_conv(bcu: jax.Array, w: jax.Array, dtype=None) -> jax.Array:
    """:func:`gated_short_conv_plain` as one kernel a pass: ``bcu``
    [B, T, 3d], ``w`` [K, d] float32 -> [B, T, d] in ``dtype`` (``bcu``'s).
    Each pass reads ``bcu`` (and ``dy``) once and writes its result once;
    the taps' gradient is summed in float32 on chip. Saves its inputs
    alone. A sequence that is no whole tiles of ``HALO`` positions is padded
    to them behind its end, which no earlier position sees."""
    taps, d = w.shape
    if bcu.shape[-1] != 3 * d or taps > HALO:
        raise ValueError(f"[B | C | u] of {bcu.shape[-1]} channels for "
                         f"{taps} taps over {d} (at most {HALO} taps)")
    t = bcu.shape[1]
    short = -t % HALO
    if short:
        bcu = jnp.pad(bcu, ((0, 0), (0, short), (0, 0)))
    y = _mix(bcu, w.astype(F32), jnp.dtype(dtype or bcu.dtype))
    return y[:, :t] if short else y
