"""The two element-wise ends of a Mamba-2 mixer, one pass over HBM each.

Before the scan (``ops/ssd.py``) a mixer runs a depthwise causal conv and a
silu over ``[x | B | C]``; after it, a gate and an RMSNorm over groups of
channels (``models/nemotron_h.py``, steps 2 and 6 of its mixer):

    conv:       y_t[c] = silu(b[c] + sum_j w[j, c] x_{t-K+1+j}[c])
    gated norm: out = RMSNorm_groups(y * silu(z)) * scale

Both are a few operations an element, so what they cost is the bytes they
move. Left to autodiff and the compiler's fusions they moved three times
what a pass needs (``PERF.md`` §6, PR 42): the conv's transpose wrote one
cotangent a tap and a padded copy, the norm wrote its gated product and its
statistics, broadcast to every channel, in float32; and a ``slice`` copied
the conv's channels out of the in-projection's output and the scan's x, B
and C out of the conv's. Here each stage is a ``jax.custom_vjp`` whose
passes are Pallas kernels: a stage saves its inputs alone and every pass
reads its operands once and writes its results once, the float32 sums and
statistics staying on chip.

Two writings of each, as ``ops/ssd.py`` has of the scan.
:func:`conv_silu_plain` and :func:`gated_norm_plain` are plain ``jax.numpy``
that autodiff takes the gradient of: what the kernels are held to, reached
by tests only. :func:`causal_conv_silu` and :func:`gated_group_norm` are
what the mixer calls, on every backend (``kernel_call.on_this_platform``:
Mosaic where the program is lowered for a TPU, the same kernels in interpret
mode elsewhere).

*Positions along the lanes*, as the scan's kernels have them and for the
same reason: XLA keeps the mixer's activations with a position minor, so
``[B, T, C] -> [B, C, T]`` is a bitcast and a kernel beside the projections
costs them no layout change. On a tile ``[channels, L]`` a value a channel
(a tap's weight, the bias, the norm's scale) is a column, a group's
statistics are sums down a tile's rows, and a shift along the sequence is a
lane roll whose vacated lanes come from the columns beside the tile.

*In place.* A stage's input is a run of channels of a wider array (the
in-projection's output ``[z | x | B | C | dt]``), and a slice of it would be
copied for a kernel. So a call takes the whole array and where its run
starts (``at``), and its block indices start there. The gradient still
flows through a slice (:func:`_run_of`), which no kernel reads and nothing
therefore copies: its transpose is the ``pad`` that XLA fuses into the
in-projection's backward matmuls. The conv is depthwise, so its runs (x, B,
C) are calls of their own: each writes the array the scan takes and reads
the scan's gradient for it as it comes; backward the calls fill one ``dx``
between them (the later ones alias the first one's result), so the
in-projection's backward reads one array for the conv, not one a run (three
more operands cost its two matmul fusions 1.2 ms a layer).

*The conv's kernels*, grid ``(batch, channel tile, position tile)``, a tile
64 channels by up to the whole sequence. A grid step walks its tile 16
channels (a packed bf16 register's height) by a slab of lanes at a time, so
that a slab's taps, pre-activation and gradients lie in registers: the
kernels are paced by the vector unit (a dozen and two dozen bundles a
float32 register forward and backward where the bytes allow six and eight).
The columns before a slab come from ``x`` itself, or from a second, 128-lane
block of it before the tile (zeros before the sequence). Backward, ``dx_t``
reads ``dpre`` at ``t .. t+K-1``: slabs and tiles are visited **last to
first** and the first columns of ``dpre`` are carried, slab to slab in
registers, tile to tile in VMEM. ``dpre x_{t-K+1+j}`` and ``dpre`` add up
along a slab in registers and over a sequence's tiles in a float32 block
``[K+1, channels, 128]`` that is written once; the caller adds its lanes and
the batch.

*The norm's kernels* hold one group's channels a tile, so a position's mean
square is a sum down the tile; a tile is worked in slabs of lanes that keep
the float32 temporaries small. The forward writes the normalised, scaled
product alone; the backward forms it again from ``y`` and ``z`` and writes
``dy``, ``dz`` and, a channel and lane, ``dout n`` summed over a sequence's
tiles (the scale's gradient once the caller has added the lanes up).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import GRID_ORDER, on_this_platform
from horovod_tpu.ops.ssd import positions_last

LANES = 128
# The conv works short, long tiles (no channel of it meets another):
# channels and positions of a tile, and the positions of it worked at a time
# (16 channels of them). Positions count two-byte elements (``_lanes``).
# Measured on the v5e at 8192 positions (PERF.md §6, PR 42): a slab of 2048
# ran 0.32 ms forward and 0.63 backward over 4096 channels, one of 1024
# 0.40 and 0.82; 32 to 128 channels a tile and 2048 to 8192 positions alike.
CONV_TILE = (64, 8192, 2048)
# The norm's tile is a group's channels high: its positions, and those of a
# slab. 512 positions a tile ran 0.35 / 0.59 ms forward / backward, 1024
# 0.33 / 0.56; 2048 does not fit the backward's five tiles in VMEM.
NORM_TILE = (1024, 512)


def _count_call(stage: str, direction: str):
    """Monitoring, at trace time as ``hvd_ssd_chunks_total`` is: the passes
    of a stage that were just traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_ssm_end_calls_total",
        "passes of a mixer's conv and gated norm traced, by stage and "
        "direction", stage=stage, direction=direction).inc()


# -- the plain writings -------------------------------------------------------

def conv_silu_plain(x, w, b, dtype):
    """``silu(b + sum_j w[j] x_{t-K+1+j})``: ``x`` [B, T, C], ``w`` [K, C],
    ``b`` [C]; zeros before ``t = 0``, the sum in float32."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = b + sum(w[j] * padded[:, j:j + t].astype(jnp.float32)
                for j in range(k))
    return jax.nn.silu(y).astype(dtype)


def gated_norm_plain(y, z, scale, groups: int, eps: float, dtype):
    """``RMSNorm_groups(y * silu(z)) * scale``: ``y``, ``z`` [..., C], the
    gate first, float32 statistics over each of ``groups`` runs of
    channels."""
    channels = y.shape[-1]
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(*y.shape[:-1], groups, channels // groups)
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(y.shape) * scale).astype(dtype)


# -- the kernels --------------------------------------------------------------

def _sigmoid(x):
    """``1 / (1 + exp(-x))`` as ``(1 + tanh(x / 2)) / 2``: one transcendental
    and no division, which on the TPU's vector unit is a dozen operations
    an element fewer (these kernels are paced by them)."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _fold(v, width: int):
    """[rows, L] added up to [rows, width]: the lanes laid over one another,
    a vector add each."""
    return sum(v[:, at:at + width] for at in range(0, v.shape[1], width))


def _taps(x, before, k: int):
    """``x_{t-K+1+j}`` for each tap ``j``, [rows, L] each: ``x`` behind the
    ``width`` columns before it, rolled along the lanes."""
    width = before.shape[1]
    joined = jnp.concatenate([before, x], axis=1)
    return [(pltpu.roll(joined, s, 1) if s else joined)[:, width:]
            for s in range(k - 1, -1, -1)]


def _conv_loops(x_ref, before_ref, w_ref, b_ref, zeros_before, slab: int,
                first_to_last: bool, rows_carry, of_slab):
    """The two loops of a conv kernel's grid step: down the tile's rows 16
    at a time (a packed bf16 register's height) and along its lanes in
    slabs, so that what a slab needs lies in registers. ``of_slab(rows,
    lanes, taps, pre, w, carry) -> carry`` works one slab from its taps
    (:func:`_taps`) and pre-activation; ``rows_carry(rows) -> (carry,
    done(carry))`` makes a row group's carry and takes it back."""
    f32 = jnp.float32
    k = w_ref.shape[1]
    height, length = x_ref.shape[1:]
    width = before_ref.shape[2]
    group, slabs = math.gcd(height, 16), length // slab

    def of_rows(g, _):
        rows = pl.ds(pl.multiple_of(g * group, group), group)
        w, b = w_ref[rows, :], b_ref[rows, :]
        edge = before_ref[0, rows, :].astype(f32)
        edge = jnp.where(zeros_before, jnp.zeros_like(edge), edge)

        def one(i, carry):
            lane0 = (i if first_to_last else slabs - 1 - i) * slab
            lanes = pl.ds(pl.multiple_of(lane0, slab), slab)
            x = x_ref[0, rows, lanes].astype(f32)
            behind = x_ref[0, rows, pl.ds(pl.multiple_of(
                jnp.maximum(lane0 - width, 0), width), width)].astype(f32)
            taps = _taps(x, jnp.where(lane0 == 0, edge, behind), k)
            pre = b + sum(w[:, j:j + 1] * taps[j] for j in range(k))
            return of_slab(rows, lanes, taps, pre, w, carry)
        carry, done = rows_carry(rows)
        done(lax.fori_loop(0, slabs, one, carry))
        return 0
    lax.fori_loop(0, height // group, of_rows, 0)


def _conv_fwd_kernel(x_ref, before_ref, w_ref, b_ref, out_ref, *, slab: int):
    """One tile ``[channels, L]`` of ``silu(b + sum_j w[j] x_{t-K+1+j})``.
    ``before_ref`` is the block of ``x`` that ends where the tile starts
    (zeros before the sequence)."""
    def of_slab(rows, lanes, taps, pre, w, carry):
        out_ref[0, rows, lanes] = (pre * _sigmoid(pre)) \
            .astype(out_ref.dtype)
        return carry
    _conv_loops(x_ref, before_ref, w_ref, b_ref, pl.program_id(2) == 0,
                slab, True, lambda rows: (0, lambda carry: None), of_slab)


def _conv_bwd_kernel(x_ref, before_ref, dy_ref, w_ref, b_ref, *rest,
                     slab: int):
    """One tile of the conv's transpose, a sequence's tiles and a tile's
    slabs visited last to first: ``dx_t = sum_j w[j] dpre_{t+K-1-j}`` reads
    the first columns of ``dpre`` of what comes after, carried from slab to
    slab in registers and from tile to tile in ``after_ref`` (float32
    scratch). ``sums_ref`` [K+1, channels, width] gathers ``dpre
    x_{t-K+1+j}`` (tap ``j``) and ``dpre`` (the bias) over the sequence.
    Before the results ``rest`` may hold the array ``dx_ref`` is a run of,
    left where it is (what other calls wrote of it stays)."""
    dx_ref, sums_ref, after_ref = rest[-3:]
    f32 = jnp.float32
    k = w_ref.shape[1]
    width = after_ref.shape[1]
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)          # a sequence's last tile: nothing after it
    def _():
        after_ref[...] = jnp.zeros_like(after_ref)
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def of_slab(rows, lanes, taps, pre, w, carry):
        after, sums = carry
        sig = _sigmoid(pre)
        dpre = dy_ref[0, rows, lanes].astype(f32) * (
            sig * (1.0 + pre * (1.0 - sig)))
        joined = jnp.concatenate([dpre, after], axis=1)
        total = slab + width
        dx = dpre * w[:, k - 1:k] + sum(
            w[:, j:j + 1]
            * pltpu.roll(joined, total - (k - 1 - j), 1)[:, :slab]
            for j in range(k - 1))
        dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
        return dpre[:, :width], tuple(
            total_j + _fold(pull, width) for total_j, pull in zip(
                sums, [dpre * tap for tap in taps] + [dpre]))

    def rows_carry(rows):
        def done(carry):
            after_ref[rows, :] = carry[0]
            for j, total_j in enumerate(carry[1]):
                sums_ref[0, j, rows, :] += total_j
        zeros = jnp.zeros((rows.size, width), f32)
        return (after_ref[rows, :], (zeros,) * (k + 1)), done
    _conv_loops(x_ref, before_ref, w_ref, b_ref, step == steps - 1, slab,
                False, rows_carry, of_slab)


def _gated(y, z, eps: float):
    """``y``, ``z`` [channels, L] as float32, ``sigmoid(z)`` and the
    normalised gated product ``n`` with its ``rsqrt`` [1, L]: a group's
    channels are the rows."""
    f32 = jnp.float32
    y, z = y.astype(f32), z.astype(f32)
    sig = _sigmoid(z)
    gated = y * (z * sig)
    inverse = lax.rsqrt(jnp.mean(gated * gated, axis=0, keepdims=True) + eps)
    return y, z, sig, gated * inverse, inverse


def _by_slab(length: int, slab: int, work):
    """``work(lanes)`` for each run of ``slab`` lanes of a tile: a tile is as
    long as its transfers like, a slab as short as its float32 temporaries
    allow."""
    def one(i, _):
        work(pl.ds(pl.multiple_of(i * slab, slab), slab))
        return 0
    lax.fori_loop(0, length // slab, one, 0)


def _norm_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, *, eps: float,
                     slab: int):
    def work(lanes):
        *_, normed, _ = _gated(y_ref[0, :, lanes], z_ref[0, :, lanes], eps)
        out_ref[0, :, lanes] = (normed * scale_ref[...]) \
            .astype(out_ref.dtype)
    _by_slab(out_ref.shape[2], slab, work)


def _norm_bwd_kernel(dout_ref, y_ref, z_ref, scale_ref, dy_ref, dz_ref,
                     dscale_ref, *, eps: float, slab: int):
    """The transpose for one group and tile: with ``n = g r`` the
    normalised product, ``dg = r (dn - n mean(dn n))`` down the group's
    rows, then the gate's two sides. ``dscale_ref`` [channels, width] adds
    up ``dout n`` over a sequence's tiles."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def work(lanes):
        y, z, sig, normed, inverse = _gated(
            y_ref[0, :, lanes], z_ref[0, :, lanes], eps)
        dout = dout_ref[0, :, lanes].astype(jnp.float32)
        pull = dout * normed
        dscale_ref[0] += _fold(pull, dscale_ref.shape[2])
        dnormed = dout * scale_ref[...]
        dgated = inverse * (dnormed - normed * jnp.mean(
            pull * scale_ref[...], axis=0, keepdims=True))
        dy_ref[0, :, lanes] = (dgated * (z * sig)).astype(dy_ref.dtype)
        dz_ref[0, :, lanes] = (dgated * y * (
            sig * (1.0 + z * (1.0 - sig)))).astype(dz_ref.dtype)
    _by_slab(dy_ref.shape[2], slab, work)


# -- the calls ----------------------------------------------------------------

def _lanes(length: int, most: int, dtype=jnp.bfloat16) -> tuple:
    """(positions of a tile, columns kept of a neighbouring tile or of a
    sum): tiles of whole 128-lane registers where the sequence divides into
    them, else the whole sequence one tile. ``most`` counts two-byte
    elements: a tile of a wider ``dtype`` is as many bytes, not as many
    positions (VMEM holds each twice)."""
    lanes = math.gcd(length, most * 2 // jnp.dtype(dtype).itemsize)
    return (lanes, LANES) if lanes % LANES == 0 else (length, length)


def _columns(v):
    """A value a channel ([C] or [K, C]) as float32 columns [C, K]: down a
    tile's rows, one lane each."""
    return v.astype(jnp.float32).reshape(-1, v.shape[-1]).T


def _conv_specs(x, w, at: int, tile, into: int = 0):
    """Of a conv call on the channels ``at .. at + C`` of ``x`` [B, W, T]
    (``w`` [C, K]): the grid; the specs of a tile of ``x``, of the block of
    ``x`` that ends where the tile starts, of a tile of an array [B, C, T],
    of a tile of the channels ``into .. into + C`` of a wider result and
    of a tile's columns; rows and lanes of a tile; the lanes of a slab.
    ``back`` turns the position tiles around."""
    batch, _, length = x.shape
    channels = w.shape[0]
    rows = math.gcd(math.gcd(math.gcd(channels, at), into), tile[0])
    (lanes, width), first = _lanes(length, tile[1], x.dtype), at // rows
    if w.shape[1] - 1 > width:
        raise ValueError(f"a conv over {w.shape[1]} positions reaches past "
                         f"the {width} columns kept of a neighbouring tile")
    steps = length // lanes

    def specs(back: bool):
        def tile_of(s):
            return steps - 1 - s if back else s
        return (
            pl.BlockSpec((1, rows, lanes),
                         lambda i, j, s: (i, first + j, tile_of(s))),
            pl.BlockSpec((1, rows, width), lambda i, j, s: (
                i, first + j,
                jnp.maximum(tile_of(s) * (lanes // width) - 1, 0))),
            pl.BlockSpec((1, rows, lanes),
                         lambda i, j, s: (i, j, tile_of(s))),
            pl.BlockSpec((1, rows, lanes),
                         lambda i, j, s: (i, into // rows + j, tile_of(s))),
            lambda k: pl.BlockSpec((rows, k), lambda i, j, s: (j, 0)))
    return (batch, channels // rows, steps), specs, rows, width, \
        _lanes(lanes, tile[2])[0]


@functools.partial(jax.jit, static_argnames=("at", "dtype", "tile",
                                             "interpret"))
def _conv_forward_call(x, w, b, *, at, dtype, tile, interpret):
    """``silu(conv)`` [B, C, T] of the channels ``at .. at + C`` of ``x``
    [B, W, T]; ``w`` [C, K], ``b`` [C, 1]."""
    grid, specs, _, _, slab = _conv_specs(x, w, at, tile)
    wide, before, narrow, _, columns = specs(back=False)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, slab=slab), grid=grid,
        in_specs=[wide, before, columns(w.shape[1]), columns(1)],
        out_specs=narrow,
        out_shape=jax.ShapeDtypeStruct(
            (x.shape[0], w.shape[0], x.shape[2]), dtype),
        compiler_params=GRID_ORDER, interpret=interpret,
    )(x, x, w, b)


@functools.partial(jax.jit, static_argnames=("at", "place", "tile",
                                             "interpret"))
def _conv_backward_call(x, dy, w, b, *whole, at, place, tile, interpret):
    """Of ``dy`` [B, C, T] and the channels ``at .. at + C`` of ``x`` [B, W,
    T]: ``dx`` as the channels ``place[0] .. place[0] + C`` of an array [B,
    ``place[1]``, T], and the sums [B, K+1, C, width]. ``whole`` is that
    array as an earlier call left it (the calls of a conv's runs fill one
    array between them: one operand of the in-projection's backward, not
    one a run) or nothing, and then the other channels are not written."""
    grid, specs, rows, width, slab = _conv_specs(x, w, at, tile, place[0])
    wide, before, narrow, placed, columns = specs(back=True)
    k = w.shape[1]
    return pl.pallas_call(
        functools.partial(_conv_bwd_kernel, slab=slab), grid=grid,
        in_specs=[wide, before, narrow, columns(k), columns(1)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(whole),
        out_specs=[placed, pl.BlockSpec((1, k + 1, rows, width),
                                        lambda i, j, s: (i, 0, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((dy.shape[0], place[1],
                                         dy.shape[2]), x.dtype),
                   jax.ShapeDtypeStruct((dy.shape[0], k + 1, dy.shape[1],
                                         width), jnp.float32)],
        input_output_aliases={5: 0} if whole else {},
        scratch_shapes=[pltpu.VMEM((rows, width), jnp.float32)],
        compiler_params=GRID_ORDER, interpret=interpret,
    )(x, x, dy, w, b, *whole)


def _norm_specs(y, groups: int, at: int, tile):
    """Of the norm's calls on ``y`` [B, C, T] and the channels ``at .. at +
    C`` of a ``z`` [B, W, T]: the grid, the specs of a tile of ``y``, of
    ``z`` and of a group's columns, the lanes a sum over positions keeps,
    the lanes of a slab. A group's channels a tile."""
    batch, channels, length = y.shape
    rows, (lanes, width) = channels // groups, _lanes(length, tile[0],
                                                      y.dtype)
    first = at // rows
    return ((batch, groups, length // lanes),
            pl.BlockSpec((1, rows, lanes), lambda i, j, s: (i, j, s)),
            pl.BlockSpec((1, rows, lanes),
                         lambda i, j, s: (i, first + j, s)),
            pl.BlockSpec((rows, 1), lambda i, j, s: (j, 0)), width,
            _lanes(lanes, tile[1])[0])


@functools.partial(jax.jit, static_argnames=(
    "groups", "eps", "at", "dtype", "tile", "interpret"))
def _norm_forward_call(y, z, scale, *, groups, eps, at, dtype, tile,
                       interpret):
    grid, by_tile, of_z, by_channel, _, slab = _norm_specs(y, groups, at,
                                                           tile)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps, slab=slab), grid=grid,
        in_specs=[by_tile, of_z, by_channel], out_specs=by_tile,
        out_shape=jax.ShapeDtypeStruct(y.shape, dtype),
        compiler_params=GRID_ORDER, interpret=interpret,
    )(y, z, scale)


@functools.partial(jax.jit, static_argnames=(
    "groups", "eps", "at", "tile", "interpret"))
def _norm_backward_call(dout, y, z, scale, *, groups, eps, at, tile,
                        interpret):
    """``dy``, ``dz`` [B, C, T] and ``dout n`` [B, C, width] summed over
    each sequence's tiles."""
    grid, by_tile, of_z, by_channel, width, slab = _norm_specs(
        y, groups, at, tile)
    batch, channels, _ = y.shape
    return pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps, slab=slab), grid=grid,
        in_specs=[by_tile, by_tile, of_z, by_channel],
        out_specs=[by_tile, by_tile,
                   pl.BlockSpec((1, channels // groups, width),
                                lambda i, j, s: (i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   jax.ShapeDtypeStruct((batch, channels, width),
                                        jnp.float32)],
        compiler_params=GRID_ORDER, interpret=interpret,
    )(dout, y, z, scale)


def _run_of(wide, at: int, channels: int):
    """The channels ``at .. at + channels`` of ``wide`` [B, T, W] twice:
    as a slice, which carries the gradient and which no kernel reads (so
    nothing copies it), and ``wide`` itself without a gradient, which the
    kernels address at ``at``."""
    if not 0 <= at <= wide.shape[-1] - channels:
        raise ValueError(f"channels {at} .. {at + channels} are not in an "
                         f"array of {wide.shape[-1]}")
    return lax.slice_in_dim(wide, at, at + channels, axis=-1), \
        lax.stop_gradient(wide)


# -- the conv -----------------------------------------------------------------

def _runs(widths, w, b):
    """For runs of ``widths`` channels side by side: (where a run starts,
    its taps and bias as columns)."""
    starts = [sum(widths[:i]) for i in range(len(widths))]
    return [(s, _columns(w[:, s:s + n]), _columns(b[s:s + n]))
            for s, n in zip(starts, widths)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv(run, wide, w, b, at, dtype, widths):
    _count_call("conv", "fwd")
    return tuple(positions_last(on_this_platform(
        functools.partial(_conv_forward_call, at=at + start, dtype=dtype,
                          tile=CONV_TILE), positions_last(wide), taps, bias))
        for start, taps, bias in _runs(widths, w, b))


def _conv_fwd(run, wide, w, b, at, dtype, widths):
    return _conv(run, wide, w, b, at, dtype, widths), (wide, w, b)


def _conv_bwd(at, dtype, widths, saved, dys):
    # traced under the call site's scope, like the forward
    wide, w, b = saved
    _count_call("conv", "bwd")
    whole, sums = (), []
    for (start, taps, bias), dy in zip(_runs(widths, w, b), dys):
        dx, of_run = on_this_platform(
            functools.partial(_conv_backward_call, at=at + start,
                              place=(start, sum(widths)), tile=CONV_TILE),
            positions_last(wide), positions_last(dy), taps, bias, *whole)
        whole = (dx,)
        sums.append(of_run.sum((0, 3)))
    sums = jnp.concatenate(sums, axis=1)
    return positions_last(dx), jnp.zeros_like(wide), \
        sums[:-1].astype(w.dtype), sums[-1].astype(b.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, w, b, dtype=None, at: int = 0, widths=None):
    """:func:`conv_silu_plain` over the channels ``at .. at + C`` of ``x``
    [B, T, W] (``w`` [K, C], ``b`` [C]; ``W`` may be wider than ``C``: pass
    the whole of an array and where the conv's run starts, since a slice of
    it would be copied for a kernel). With ``widths`` the result comes as
    one array a run of that many channels, each written by a call of its
    own (the arrays a scan takes; a slice of one result would be copied
    too), and the backward calls write one ``dx`` between them. Each pass
    reads ``x`` (and ``dy``) once and writes its result once; ``dw`` and
    ``db`` are summed in float32 on chip. Saves its inputs alone."""
    channels = w.shape[1]
    if widths is not None and sum(widths) != channels:
        raise ValueError(f"runs of {widths} channels are not the conv's "
                         f"{channels}")
    outs = _conv(*_run_of(x, at, channels), w, b, at,
                 jnp.dtype(dtype or x.dtype), tuple(widths or (channels,)))
    return list(outs) if widths else outs[0]


# -- the gated norm -----------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _norm(y, run, wide, scale, groups, eps, at, dtype):
    _count_call("gate_norm", "fwd")
    return positions_last(on_this_platform(
        functools.partial(_norm_forward_call, groups=groups, eps=eps, at=at,
                          dtype=dtype, tile=NORM_TILE),
        positions_last(y), positions_last(wide), _columns(scale)))


def _norm_fwd(y, run, wide, scale, groups, eps, at, dtype):
    return _norm(y, run, wide, scale, groups, eps, at, dtype), \
        (y, wide, scale)


def _norm_bwd(groups, eps, at, dtype, saved, dout):
    y, wide, scale = saved
    _count_call("gate_norm", "bwd")
    dy, dz, pulls = on_this_platform(
        functools.partial(_norm_backward_call, groups=groups, eps=eps,
                          at=at, tile=NORM_TILE),
        *map(positions_last, (dout, y, wide)), _columns(scale))
    return positions_last(dy), positions_last(dz), \
        jnp.zeros_like(wide), pulls.sum((0, 2)).astype(scale.dtype)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_group_norm(y, z, scale, groups: int, eps: float = 1e-5,
                     dtype=None, at: int = 0):
    """:func:`gated_norm_plain` of ``y`` [B, T, C] and the channels ``at ..
    at + C`` of ``z`` [B, T, W] (as :func:`causal_conv_silu` takes its
    ``x``) as two kernels: each pass reads its operands once and writes its
    results once; neither the gated product nor a statistic reaches HBM.
    Saves its inputs alone."""
    channels = y.shape[-1]
    if channels % groups or at % (channels // groups):
        raise ValueError(
            f"{channels} channels from {at} on do not lie in {groups} "
            "groups of their own")
    return _norm(y, *_run_of(z, at, channels), scale, groups, float(eps),
                 at, jnp.dtype(dtype or y.dtype))
