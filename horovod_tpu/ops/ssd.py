"""The state-space-dual scan of Mamba-2, chunked: the first recurrence here.

A Mamba-2 head carries a state ``S`` in R^(P x N) along the sequence
(arXiv:2405.21060; step 5 of ``models/nemotron_h.py``'s mixer):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

with ``S_{-1} = 0``, ``A < 0`` a scalar a head, ``dt_t > 0`` a scalar a head
and position, ``x_t`` in R^P, and ``B_t``, ``C_t`` in R^N shared by the heads
of a group. Run position by position that is ``T`` dependent steps of
element-wise work. :func:`ssd_chunked` is section 6 of the paper instead: the
sequence in chunks of ``L`` positions, and with ``a`` the running sum of
``dt A`` inside a chunk,

- inside a chunk the masked products ``(M o C B^T) (dt x)``,
  ``M_ts = exp(a_t - a_s)`` for ``s <= t`` and 0 above the diagonal;
- a chunk's own end state ``sum_s exp(a_L - a_s) dt_s x_s (x) B_s``;
- the states carried from chunk to chunk, a ``lax.scan`` over the ``T / L``
  chunks: ``S <- exp(a_L) S + (the chunk's own)``;
- what the state a chunk starts from gives each of its positions,
  ``exp(a_t) S C_t``.

Three of the four are matrix products (bf16 inputs where the caller's are,
float32 accumulation); the carry is ``T / L`` steps. Every exponent is a
difference ``a_t - a_s`` with ``s <= t`` of a sum of non-positive terms,
masked *before* ``exp``: nothing is exponentiated that can be positive,
whatever ``dt`` is. ``dt``, ``A``, the running sums, the decays and the
carried state are float32 whatever the inputs' dtype (the repo's dtype
policy; ``tests/test_ssd.py`` shows a bf16 running sum failing the tolerance
the policy holds).

Two writings of it. :func:`ssd_chunked` is plain ``jax.numpy`` that autodiff
takes the gradient of: four ``einsum``s and a ``lax.scan``, whose ``[L, L]``
decays and per-chunk states XLA writes to HBM. It is what the kernels are
held to and is reached by tests only. :func:`ssd_scan` is what the mixer
calls, on every backend: two Pallas kernels under a ``jax.custom_vjp``
(called by ``ops/kernel_call.py``'s rule: Mosaic where the program is lowered
for a TPU, the same kernels in interpret mode elsewhere, so nothing chooses
between paths).

*Positions along the lanes.* The kernels take ``x``, ``B`` and ``C`` as
``[B, H P, T]`` and ``[B, G N, T]`` and work on tiles ``[channels, L]``: a
value a position (``dt``, every decay) is then a row that spreads down a
tile's rows for nothing, and a head's channel sums are sums down rows. It is
also the layout XLA gives the mixer's activations when left alone (a
position minor): the transposes around the calls are bitcasts, and the
in-projection and the gated norm keep the programs they had beside
:func:`ssd_chunked` (tiles ``[L, channels]`` forced a row-major layout back
through the conv and cost the projections 13 ms a step, ``PERF.md`` §6, PR
33).

*The forward kernel* (``_ssd_fwd_kernel``), grid ``(batch, group, chunk)``,
the chunk axis sequential. A program holds one group's ``B_c``, ``C_c``
``[N, L]`` and forms ``C B^T`` ``[L, L]`` once for the group's ``R`` heads;
``a`` comes in from XLA (float32 ``[B, G, R, T]``), as does ``dt``. The heads
are worked a *slab* at a time: as many as fill 128 rows with their ``P``
channels (two heads of 64), so a slab's products fill the MXU's width; a
head's own ``[L, L]`` product runs over the slab's full height and a select
keeps its rows. A head: ``M = exp(mask(a_t - a_s))`` (the one value a
position that has to lie along rows, ``a_t``, is a column of the transposed
``[R, L]`` tile), ``(dt x) (C B^T o M)^T``, plus ``exp(a_t) S C`` from the
carried state and ``D x``; then ``S <- exp(a_L) S + (exp(a_L - a_s) dt_s
x_s) B``. ``S`` is a float32 VMEM scratch ``[R P, N]`` (256 KB at 8 x 64 x
128) that lives across the chunk axis and is zeroed at chunk 0. Neither
``M`` nor any per-chunk product reaches HBM. For the backward pass the
forward writes the state each chunk ends with in ``x``'s dtype (the dtype
its products read it in; the carry itself stays float32): 67 MB a layer in
bf16 at the benchmark's size, written once.

*The backward kernel* (``_ssd_bwd_kernel``) is the transpose over the chunks
last to first, carrying the end state's gradient ``[R P, N]`` in float32
scratch. It forms ``M`` again from ``a`` and writes ``dx``, ``dB`` and
``dC`` (summed over the group's heads inside the program), and a head and
position what ``dt`` gets through ``dt x`` and ``a`` through the decays;
the running sum's own transpose (``d dt``, ``dA``) is XLA's, through
:func:`_running_sum_last` like the forward's, and ``dD`` is summed outside
from a ``[R P, L]`` tile a group.

Shapes: ``x`` [B, T, H, P]; ``dt`` [B, T, H] (already positive: the caller
applies its softplus); ``A`` [H] (negative); ``B``, ``C`` [B, T, G, N] with
``H`` a multiple of ``G`` (head ``h`` reads group ``h // (H / G)``); ``D``
[H]. ``T`` must be a multiple of ``chunk``: another length is an error, not
padding. :func:`ssd_chunked` returns ``y`` [B, T, H, P] in ``x``'s dtype and
the states at each chunk's end, float32 [B, T / chunk, H, P, N] (the last is
the state after the whole sequence); :func:`ssd_scan` returns ``y``, and the
states too where asked.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import (GRID_ORDER, NEG_INF, NN, NT, TN, dot,
                                         on_this_platform, scalar_spec)
from horovod_tpu.profiler.annotate import ssm_scope


def _running_sum_last(a: jax.Array) -> jax.Array:
    """The inclusive sum of ``dt A`` along a chunk's positions (the last
    axis), in float32. Apart so that a test can show what a bf16 sum
    costs."""
    return jnp.cumsum(a.astype(jnp.float32), axis=-1)


def _count_chunks(chunks: int):
    """Monitoring, at trace time as ``hvd_flash_block_visits`` is: the
    (batch, head, chunk) triples of what was just traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_ssd_chunks_total",
        "chunks of the state-space-dual scan traced (chunks x heads x "
        "batch)").inc(chunks)


def _checked_shapes(x, dt, A, B, C, D, chunk: int) -> tuple:
    """(B, T, H, P, G, N) of a call, or the error that says what is wrong
    with it; counts the call's chunks."""
    b, t, h, p = x.shape
    g, n = B.shape[-2:]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the "
                         f"scan's chunk ({chunk})")
    if h % g:
        raise ValueError(f"{h} heads cannot share {g} groups of B and C")
    if dt.shape != (b, t, h) or C.shape != B.shape or \
            B.shape[:2] != (b, t) or A.shape != (h,) or D.shape != (h,):
        raise ValueError(
            f"x {x.shape} wants dt [B, T, H], A and D [H], B and C "
            f"[B, T, G, N]; got {dt.shape}, {A.shape}, {D.shape}, "
            f"{B.shape}, {C.shape}")
    _count_chunks(b * h * (t // chunk))
    return b, t, h, p, g, n


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, chunk: int = 128
                ) -> Tuple[jax.Array, jax.Array]:
    b, t, h, p, g, n = _checked_shapes(x, dt, A, B, C, D, chunk)
    nc, r = t // chunk, h // g
    f32 = jnp.float32

    with ssm_scope("ssm_scan"):
        # [B, chunks, L, ...]; heads as (group, head in group). The
        # [L, L] matrices keep their two positions last, heads before them
        xc = x.reshape(b, nc, chunk, g, r, p)
        Bc = B.reshape(b, nc, chunk, g, n)
        Cc = C.reshape(b, nc, chunk, g, n)
        dtc = dt.astype(f32).reshape(b, nc, chunk, g, r) \
            .transpose(0, 1, 3, 4, 2)                     # [B, c, G, R, L]
        a = _running_sum_last(dtc * A.astype(f32).reshape(g, r, 1))
        a_end = a[..., -1]                                # [B, c, G, R]

        def by_position(v):  # [B, c, G, R, L] -> [B, c, L, G, R, 1]
            return v.transpose(0, 1, 4, 2, 3)[..., None]

        # inside a chunk: (M o C B^T)(dt x); the mask comes before exp
        pos = jnp.arange(chunk)
        below = pos[:, None] >= pos[None, :]
        diff = a[..., :, None] - a[..., None, :]          # [.., t, s]
        decay = jnp.exp(jnp.where(below, diff, -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc,
                        preferred_element_type=f32)
        m = (cb[:, :, :, None] * decay * dtc[..., None, :]).astype(x.dtype)
        y = jnp.einsum("bcgrts,bcsgrp->bctgrp", m, xc,
                       preferred_element_type=f32)

        # a chunk's own end state: sum_s exp(a_L - a_s) dt_s x_s (x) B_s
        to_end = by_position(jnp.exp(a_end[..., None] - a) * dtc)
        own = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                         xc * to_end.astype(x.dtype), Bc,
                         preferred_element_type=f32)

        # carried from chunk to chunk, in float32
        def carry(state, chunk_terms):
            decay_c, own_c = chunk_terms
            state = state * decay_c[..., None, None] + own_c
            return state, state
        _, ends = lax.scan(
            carry, jnp.zeros((b, g, r, p, n), f32),
            (jnp.exp(a_end).swapaxes(0, 1), own.swapaxes(0, 1)))
        ends = ends.swapaxes(0, 1)                        # [B, c, G, R, P, N]
        starts = jnp.concatenate(
            [jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1)

        # what the state a chunk starts from gives its positions
        y += jnp.einsum("bctgn,bcgrpn->bctgrp", Cc, starts.astype(x.dtype),
                        preferred_element_type=f32) * by_position(jnp.exp(a))
        y += xc.astype(f32) * D.astype(f32).reshape(g, r, 1)
    return (y.reshape(b, t, h, p).astype(x.dtype),
            ends.reshape(b, nc, h, p, n))


# -- the kernels --------------------------------------------------------------

def _heads_per_slab(r: int, p: int) -> int:
    """Heads of a group worked together: as many as fill the MXU's 128
    rows with their ``P`` channels."""
    hs = max(1, min(r, 128 // p))
    while r % hs:
        hs -= 1
    return hs


class _Slab:
    """The heads ``first .. first + hs`` of a group, one under the other
    down the rows of a [hs P, L] tile (a channel a row, a position a lane).
    ``spread`` gives every row of a head that head's value a position (a
    row of [R, L]) or its one value (a row of [R, 1]); ``scalars`` the same
    from SMEM; ``pick`` keeps of each head's full-height result the rows
    that are its own; ``only`` zeroes the other heads' rows; ``summed``
    adds up each head's rows."""

    def __init__(self, first: int, hs: int, p: int):
        self.first, self.hs, self.p = first, hs, p
        self.height = hs * p
        self.head = lax.broadcasted_iota(
            jnp.int32, (self.height, 1), 0) // p

    def _by_head(self, value):
        out = value(0)
        for j in range(1, self.hs):
            out = jnp.where(self.head == j, value(j), out)
        return out

    def spread(self, rows):
        return self._by_head(
            lambda j: rows[self.first + j:self.first + j + 1, :])

    def scalars(self, ref, base):
        return self._by_head(lambda j: ref[base + self.first + j])

    def pick(self, results):
        return self._by_head(lambda j: results[j])

    def only(self, j, tile):
        if self.hs == 1:
            return tile
        return jnp.where(self.head == j, tile, jnp.zeros_like(tile))

    def summed(self, j, tile):
        return jnp.sum(tile[j * self.p:(j + 1) * self.p], axis=0,
                       keepdims=True)


def _chunk_terms(dt_ref, a_ref):
    """A chunk's ``dt`` and ``a`` as they come ([R, L], a head's positions
    along the lanes), ``a`` also with the position along the rows ([L, R]),
    and the three decays that are a value a head and position: from the
    chunk's start ``exp(a_t)``, to its end ``exp(a_L - a_s)``, over the
    whole of it ``exp(a_L)`` ([R, 1])."""
    dt, a = dt_ref[0, 0], a_ref[0, 0]
    a_end = a[:, -1:]
    return dt, a, a.T, jnp.exp(a), jnp.exp(a_end - a), jnp.exp(a_end)


def _masked_decay(a_cols, a_rows, head):
    """``M_ts = exp(a_t - a_s)`` for ``s <= t`` and 0 above the diagonal,
    [L, L] float32: the mask comes before ``exp``."""
    length = a_cols.shape[0]
    below = (lax.broadcasted_iota(jnp.int32, (length, length), 0)
             >= lax.broadcasted_iota(jnp.int32, (length, length), 1))
    diff = a_cols[:, head:head + 1] - a_rows[head:head + 1, :]
    return jnp.exp(jnp.where(below, diff, NEG_INF))


def _ssd_fwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, *rest,
                    r: int, p: int, hs: int, save_ends: bool):
    """One chunk of one group, every tile with its positions along the
    lanes: ``C B^T`` once for the group's ``R`` heads, then slab by slab the
    masked products, what the carried state gives the chunk's positions,
    ``D x``, and the state carried on in ``state_ref`` (float32 [R P, N],
    alive across the chunk axis)."""
    ends_ref, state_ref = rest if save_ends else (None,) + rest
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    b, c = b_ref[0], c_ref[0]                         # [N, L]
    cb = dot(c, b, TN)                                # [L (t), L (s)]
    dt_rows, a_rows, a_cols, from_start, to_end, whole = _chunk_terms(
        dt_ref, a_ref)
    for first in range(0, r, hs):
        slab = _Slab(first, hs, p)
        at = slice(first * p, first * p + slab.height)
        x = x_ref[0, at, :].astype(jnp.float32)       # [hs P, L]
        xdt = x * slab.spread(dt_rows)
        xdt_in = xdt.astype(dtype)
        state = state_ref[at, :]                      # [hs P, N]
        y = slab.pick([
            dot(xdt_in, (cb * _masked_decay(a_cols, a_rows, first + j))
                .astype(dtype), NT) for j in range(hs)])
        y += slab.spread(from_start) * dot(state.astype(dtype), c, NN)
        y += slab.scalars(d_ref, pl.program_id(1) * r) * x
        y_ref[0, at, :] = y.astype(y_ref.dtype)
        state = slab.spread(whole) * state + dot(
            (xdt * slab.spread(to_end)).astype(dtype), b, NT)
        state_ref[at, :] = state
        if save_ends:
            ends_ref[0, 0, at, :] = state.astype(ends_ref.dtype)


def _ssd_bwd_kernel(d_ref, x_ref, dy_ref, b_ref, c_ref, dt_ref, a_ref,
                    start_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref,
                    dd_ref, grad_ref, da_cols_ref, *, r: int, p: int,
                    hs: int):
    """The forward's transpose for one chunk of one group, the chunks
    visited last to first: ``grad_ref`` (float32 [R P, N]) carries the
    gradient of the state a chunk ends with. The masked decays are formed
    again from ``a``; the state the chunk starts from is the forward's
    saved one. Writes ``dx``, ``dB`` and ``dC`` (summed over the group's
    heads here) and, a head and position, ``ddt``: what ``dt`` gets through
    ``dt x`` (the caller adds what it gets through ``a``), and ``da``.
    A decay ``exp(a_t - a_s)`` gives ``a_t`` and takes from ``a_s`` the same
    amount, and the running sum's transpose adds those up again, so both
    sides are sums of ONE float32 table (``dm o m`` along its rows and down
    its columns; the end state's pull ``exp(a_L - a_s) dt_s x_s . G B_s`` a
    position and its total at ``a_L``): rounded apart they would not cancel.
    ``dd`` adds up ``dy x`` a channel and lane over the chunks."""
    length = x_ref.shape[2]
    dtype = x_ref.dtype
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)   # the last chunk: nothing comes after
    def _():
        grad_ref[...] = jnp.zeros_like(grad_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    first_chunk = pl.program_id(2) == pl.num_programs(2) - 1
    b, c = b_ref[0], c_ref[0]                          # [N, L]
    cb = dot(c, b, TN)
    dt_rows, a_rows, a_cols, from_start, to_end, whole = _chunk_terms(
        dt_ref, a_ref)
    last_position = lax.broadcasted_iota(
        jnp.int32, (1, length), 1) == length - 1

    dcb = jnp.zeros((length, length), f32)
    db = jnp.zeros(db_ref.shape[1:], f32)              # [N, L]
    dc = jnp.zeros(dc_ref.shape[1:], f32)
    for first in range(0, r, hs):
        slab = _Slab(first, hs, p)
        at = slice(first * p, first * p + slab.height)
        x = x_ref[0, at, :].astype(f32)                # [hs P, L]
        dy_in = dy_ref[0, at, :]
        dy = dy_in.astype(f32)
        dt = slab.spread(dt_rows)
        xdt = x * dt
        xdt_in = xdt.astype(dtype)
        start = start_ref[0, 0, at, :]                 # [hs P, N]
        start = jnp.where(first_chunk, jnp.zeros_like(start), start)
        grad = grad_ref[at, :]                         # float32
        grad_in = grad.astype(dtype)

        back, da_to = [], []
        for j in range(hs):
            decay = _masked_decay(a_cols, a_rows, first + j)
            m = cb * decay
            # dy_t . dt_s x_s over the head's channels
            dm = dot(slab.only(j, dy).astype(dtype), xdt_in, TN)
            dcb += dm * decay
            pull = dm * m          # what exp(a_t - a_s) passes to a_t, a_s
            da_to.append(jnp.sum(pull, axis=1, keepdims=True))
            da_ref[0, 0, first + j:first + j + 1, :] = -jnp.sum(
                pull, axis=0, keepdims=True)
            back.append(dot(dy_in, m.astype(dtype), NN))
        # dx before its dt: the chunk's own pairs, then the end state's side
        to_state = slab.spread(to_end)
        from_grad = dot(grad_in, b, NN)               # G B_s, [hs P, L]
        dx = slab.pick(back) + to_state * from_grad
        dx_ref[0, at, :] = (dt * dx + slab.scalars(
            d_ref, pl.program_id(1) * r) * dy).astype(dx_ref.dtype)
        dd_ref[0, 0, at, :] += dy * x
        reach = dy * slab.spread(from_start)           # dy_t exp(a_t)
        reach_in = reach.astype(dtype)
        carried = xdt * to_state                       # what the state took
        dc += dot(start, reach_in, TN)
        db += dot(grad_in, carried.astype(dtype), TN)
        # a_t: what the start state gave position t, less what the end
        # state took of it; a_L: all that the end state is made of
        end_pull = carried * from_grad
        through_a = reach * dot(start, c, NN) - end_pull
        through_dt = x * dx
        start_pull = grad * start.astype(f32)          # [hs P, N]
        for j in range(hs):
            row = slice(first + j, first + j + 1)
            ddt_ref[0, 0, row, :] = slab.summed(j, through_dt)
            at_end = jnp.sum(slab.summed(j, end_pull), axis=1,
                             keepdims=True) + whole[row, :] * jnp.sum(
                slab.summed(j, start_pull), axis=1, keepdims=True)
            da_ref[0, 0, row, :] += slab.summed(j, through_a) + jnp.where(
                last_position, at_end, 0.0)
            da_cols_ref[:, row] = da_to[j]
        grad_ref[at, :] = slab.spread(whole) * grad + dot(reach_in, c, NT)

    dcb_in = dcb.astype(dtype)
    dc_ref[0] = (dc + dot(b, dcb_in, NT)).astype(dc_ref.dtype)
    db_ref[0] = (db + dot(c, dcb_in, NN)).astype(db_ref.dtype)
    da_ref[0, 0] += da_cols_ref[...].T


# -- the calls ----------------------------------------------------------------

def _rows_of_decay(dt, A, g: int, chunk: int):
    """``dt`` and the running sum ``a`` of ``dt A`` inside each chunk, both
    float32 [B, G, R, T]: a head's positions along the last axis, as the
    kernels read them."""
    b, t, h = dt.shape
    r = h // g
    dt_rows = dt.astype(jnp.float32).reshape(b, t // chunk, chunk, g, r) \
        .transpose(0, 3, 4, 1, 2)                         # [B, G, R, c, L]
    a = _running_sum_last(
        dt_rows * A.astype(jnp.float32).reshape(g, r, 1, 1))
    return dt_rows.reshape(b, g, r, t), a.reshape(b, g, r, t)


def _sizes(x, B, dt_rows, chunk: int) -> tuple:
    """(B, G, R, P, N, chunks) of the kernels' arguments: ``x`` [B, H P, T],
    ``B`` [B, G N, T], ``dt_rows`` [B, G, R, T]."""
    b, hp, t = x.shape
    g, r = dt_rows.shape[1:3]
    return b, g, r, hp // (g * r), B.shape[1] // g, t // chunk


@functools.partial(jax.jit,
                   static_argnames=("chunk", "ends_dtype", "interpret"))
def _forward_call(D, x, B, C, dt_rows, a_rows, *, chunk, ends_dtype,
                  interpret):
    """``y`` [B, H P, T] and, where ``ends_dtype`` says so, the state each
    chunk ends with [B, T / chunk, H P, N]."""
    b, g, r, p, n, nc = _sizes(x, B, dt_rows, chunk)
    save_ends = ends_dtype is not None
    by_chunk = pl.BlockSpec((1, r * p, chunk), lambda i, j, c: (i, j, c))
    by_chunk_n = pl.BlockSpec((1, n, chunk), lambda i, j, c: (i, j, c))
    a_head = pl.BlockSpec((1, 1, r, chunk), lambda i, j, c: (i, j, 0, c))
    out_specs, out_shape = [by_chunk], [jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype)]
    if save_ends:
        out_specs.append(pl.BlockSpec((1, 1, r * p, n),
                                      lambda i, j, c: (i, c, j, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b, nc, g * r * p, n), ends_dtype))
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, r=r, p=p,
                          hs=_heads_per_slab(r, p), save_ends=save_ends),
        grid=(b, g, nc),
        in_specs=[scalar_spec(), by_chunk, by_chunk_n, by_chunk_n, a_head,
                  a_head],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r * p, n), jnp.float32)],
        compiler_params=GRID_ORDER, interpret=interpret,
    )(D, x, B, C, dt_rows, a_rows)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward_call(D, x, dy, B, C, dt_rows, a_rows, ends, *, chunk,
                   interpret):
    b, g, r, p, n, nc = _sizes(x, B, dt_rows, chunk)

    def back(c):  # the chunks last to first
        return nc - 1 - c
    by_chunk = pl.BlockSpec((1, r * p, chunk),
                            lambda i, j, c: (i, j, back(c)))
    by_chunk_n = pl.BlockSpec((1, n, chunk), lambda i, j, c: (i, j, back(c)))
    a_head = pl.BlockSpec((1, 1, r, chunk),
                          lambda i, j, c: (i, j, 0, back(c)))
    state = (1, 1, r * p, n)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, r=r, p=p,
                          hs=_heads_per_slab(r, p)),
        grid=(b, g, nc),
        in_specs=[
            scalar_spec(), by_chunk, by_chunk, by_chunk_n, by_chunk_n, a_head,
            a_head,
            # the state a chunk starts from is the one before it ended with
            pl.BlockSpec(state, lambda i, j, c: (
                i, jnp.maximum(back(c) - 1, 0), j, 0)),
        ],
        out_specs=[by_chunk, by_chunk_n, by_chunk_n, a_head, a_head,
                   pl.BlockSpec((1, 1, r * p, chunk),
                                lambda i, j, c: (i, j, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(B.shape, B.dtype),
            jax.ShapeDtypeStruct(C.shape, C.dtype),
            jax.ShapeDtypeStruct(dt_rows.shape, f32),
            jax.ShapeDtypeStruct(a_rows.shape, f32),
            jax.ShapeDtypeStruct((b, g, r * p, chunk), f32),
        ],
        scratch_shapes=[pltpu.VMEM((r * p, n), f32),
                        pltpu.VMEM((chunk, r), f32)],
        compiler_params=GRID_ORDER, interpret=interpret,
    )(D, x, dy, B, C, dt_rows, a_rows, ends)


def positions_last(v):
    """[B, T, heads or groups, width] as [B, heads width, T]: the kernels'
    tiles hold a position a lane."""
    b, t = v.shape[:2]
    return v.reshape(b, t, -1).swapaxes(1, 2)


def _positions_first(v, shape):
    """The way back: [B, heads width, T] as ``shape`` [B, T, heads, width]."""
    return v.swapaxes(1, 2).reshape(shape)


def _scan_forward(x, dt, A, B, C, D, chunk, ends_dtype):
    dt_rows, a_rows = _rows_of_decay(dt, A, B.shape[2], chunk)
    return on_this_platform(
        functools.partial(_forward_call, chunk=chunk, ends_dtype=ends_dtype),
        D.astype(jnp.float32), *map(positions_last, (x, B, C)), dt_rows,
        a_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, A, B, C, D, chunk):
    return _positions_first(
        _scan_forward(x, dt, A, B, C, D, chunk, None)[0], x.shape)


def _scan_fwd(x, dt, A, B, C, D, chunk):
    y, ends = _scan_forward(x, dt, A, B, C, D, chunk, x.dtype)
    return _positions_first(y, x.shape), (x, dt, A, B, C, D, ends)


def _scan_bwd(chunk, saved, dy):
    # traced under the call site's ``ssm_scan`` scope, like the forward
    x, dt, A, B, C, D, ends = saved
    b, _, h, p = x.shape
    (dt_rows, a_rows), through_decay = jax.vjp(
        lambda dt, A: _rows_of_decay(dt, A, B.shape[2], chunk), dt, A)
    dx, dB, dC, ddt_rows, da_rows, dd = on_this_platform(
        functools.partial(_backward_call, chunk=chunk),
        D.astype(jnp.float32), *map(positions_last, (
            x, dy.astype(x.dtype), B, C)), dt_rows, a_rows, ends)
    ddt, dA = through_decay((ddt_rows, da_rows))
    dD = dd.reshape(b, h, -1).sum((0, 2)).astype(D.dtype)
    return _positions_first(dx, x.shape), ddt, dA, \
        _positions_first(dB, B.shape), _positions_first(dC, C.shape), dD


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, D: jax.Array, chunk: int = 128,
             return_states: bool = False):
    """:func:`ssd_chunked` as the Pallas kernels: the same arguments, the
    same ``y``, differentiable in all six inputs. ``return_states=True``
    also returns the state at each chunk's end, float32 [B, T / chunk, H,
    P, N], from a second forward pass that writes them; no gradient flows
    through the states."""
    b, t, h, p, g, n = _checked_shapes(x, dt, A, B, C, D, chunk)
    with ssm_scope("ssm_scan"):
        y = _scan(x, dt, A, B, C, D, chunk)
        if not return_states:
            return y
        ends = _scan_forward(*map(lax.stop_gradient, (x, dt, A, B, C, D)),
                             chunk, jnp.float32)[1]
    return y, ends.reshape(b, t // chunk, h, p, n)
