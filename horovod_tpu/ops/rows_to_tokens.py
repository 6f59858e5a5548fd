"""Rows added back into their tokens without a scatter-add.

    out[tokens[r]] += weight[r] * rows[r]        (float32, every r that counts)

XLA's ``scatter-add`` takes this one row at a time, each a dependent
read-modify-write through HBM: 18 432 rows of 2560 into a float32
``[16 384, 2560]`` took 8.17 ms, 0.44 us a row, where the bytes ask for
half a millisecond (``PERF.md`` §5, PR 38). :func:`add_rows_at_tokens` takes
a narrower contract, the one ``parallel/ep._walk`` has to give: the rows come
in ``slots`` equal parts and **inside a slot the tokens ascend** (a stable
sort by expert keeps an expert's pairs in token order), a row that goes
nowhere carrying a token of ``T`` or more, after the slot's others. The rows
of one slot that belong to one *block* of ``block_tokens`` tokens are then a
contiguous run, and a token block's result can be built where it is fast to
add to: in VMEM.

One kernel, ``_add_rows_kernel``, over a list of *jobs* made from the tokens
by comparisons and cumulative sums (no sort): a job is one chunk of
``chunk_rows`` whole rows of a slot and the part ``[r0, r1)`` of it that
lies in one token block. The jobs are in token-block order and the list is
a scalar-prefetch operand that the index maps read, so the pipeline fetches
a job's chunk while the one before is added, keeps a token block's float32
``[block_tokens, d]`` in VMEM across that block's jobs and writes it once,
after its last. Inside a job the chunk is widened to float32 and its rows
are added one by one at ``out[token - block's first]``, a row's token and
weight read from SMEM: the arithmetic of the scatter-add it replaces
(float32 weights, float32 sums, nothing rounded to the rows' dtype), with a
token's terms summed slot by slot. Every token block has a job, with or
without rows, so every block of the result is written. ``out`` is aliased to
the result; ``fresh`` (a traced flag) says that ``out`` is all zeros, and
the kernel then writes the blocks without reading them.

The tokens, the weights and the job list lie in scalar memory whole: 8
bytes a row and 16 a job (165 KB for a tile of 18 432 rows and 1088 jobs) of
the v5e's 1 MiB, which a tile of some 100 000 rows would fill (the compiler
says so: a described compile of 147 456 rows ran out by 273 KB).

The list has a static length that holds any routing (a slot's chunks, and
one more job for every (token block, slot) since a chunk that straddles
blocks is fetched for each); the jobs past the last real one repeat its
block and chunk with no row, so nothing is fetched or written for them.

The kernel is called by ``ops/kernel_call.py``'s rule: lowered for the TPU,
in interpret mode elsewhere, and under ``jax.jit`` so it is traced once a
shape.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import on_this_platform

BLOCK_TOKENS = 256  # a token block's float32 rows: 2.6 MB of VMEM at d 2560
CHUNK_ROWS = 32  # rows a job fetches: a run is 12-24 rows under balance


def block_tokens_of(tokens: int) -> int:
    """Tokens of a block of the result: ``BLOCK_TOKENS``, or the largest
    power of two under it that divides ``tokens``."""
    return math.gcd(tokens, BLOCK_TOKENS)


def chunk_rows_of(slot: int) -> int:
    """Rows of a job's chunk, dividing a slot as :func:`block_tokens_of`."""
    return math.gcd(slot, CHUNK_ROWS)


def jobs_built(tokens: int, rows: int, slots: int) -> int:
    """The static length of the job list: every chunk of every slot, and a
    job more for each (token block, slot)."""
    return slots * (rows // slots // chunk_rows_of(rows // slots)
                    + tokens // block_tokens_of(tokens))


def _jobs_of(tokens, n_tokens: int, slots: int):
    """(block, chunk, r0, r1, jobs): int32 [J] each of :func:`jobs_built`'s
    J and the number of real jobs. Job j adds rows ``[r0, r1)`` of chunk
    ``chunk[j]`` (of ``chunk_rows`` rows, counted over all the rows) into
    token block ``block[j]``; jobs are in block order, every block has one,
    and those past the real ones repeat the last with ``r0 = r1 = 0``."""
    slot = tokens.shape[0] // slots
    bt, cr = block_tokens_of(n_tokens), chunk_rows_of(slot)
    blocks, chunks = n_tokens // bt, slot // cr
    # rows of a slot whose token lies before a block's first: the slot's
    # run for block b is [bounds[b], bounds[b + 1])
    bounds = jnp.sum(
        tokens.reshape(slots, slot, 1) < jnp.arange(blocks + 1) * bt,
        axis=1, dtype=jnp.int32).T  # [blocks + 1, slots]
    lo, hi = bounds[:-1].reshape(-1), bounds[1:].reshape(-1)
    first = jnp.minimum(lax.div(lo, cr), chunks - 1)  # a run's first chunk
    n = jnp.where(hi > lo, lax.div(hi + (cr - 1), cr) - first, 0)
    # a block none of whose slots has a row still has a job: slot 0's
    n = jnp.where((jnp.arange(blocks * slots) % slots == 0), jnp.maximum(
        n, 1), n)
    stops = jnp.cumsum(n)
    j = jnp.arange(jobs_built(n_tokens, tokens.shape[0], slots))
    real = j < stops[-1]
    j = jnp.minimum(j, stops[-1] - 1)
    run = jnp.sum(stops <= j[:, None], axis=1, dtype=jnp.int32)
    chunk = first[run] + j - (stops[run] - n[run])  # within the slot
    rows_from = chunk * cr
    r0 = jnp.where(real, jnp.clip(lo[run] - rows_from, 0, cr), 0)
    r1 = jnp.where(real, jnp.clip(hi[run] - rows_from, 0, cr), 0)
    return lax.div(run, slots), lax.rem(run, slots) * chunks + chunk, \
        r0, r1, stops[-1]


def _add_rows_kernel(block_ref, chunk_ref, r0_ref, r1_ref, fresh_ref,
                     token_ref, weight_ref, rows_ref, held_ref, out_ref,
                     wide_ref):
    j = pl.program_id(0)
    block = block_ref[j]
    opens = jnp.logical_or(j == 0, block != block_ref[jnp.maximum(j - 1, 0)])

    @pl.when(jnp.logical_and(opens, fresh_ref[0] != 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(jnp.logical_and(opens, fresh_ref[0] == 0))
    def _():
        out_ref[...] = held_ref[...]

    r0, r1 = r0_ref[j], r1_ref[j]

    @pl.when(r1 > r0)
    def _():
        wide_ref[...] = rows_ref[...].astype(jnp.float32)
        first_row = chunk_ref[j] * rows_ref.shape[0]
        first_token = block * out_ref.shape[0]

        def add(r, _):
            at = pl.ds(token_ref[first_row + r] - first_token, 1)
            out_ref[at, :] += weight_ref[first_row + r] * \
                wide_ref[pl.ds(r, 1), :]
        lax.fori_loop(r0, r1, add, None)


@functools.partial(jax.jit, static_argnames=("slots", "interpret"))
def _add_rows_call(out, rows, weight, tokens, fresh, *, slots, interpret):
    n_tokens, d = out.shape
    bt = block_tokens_of(n_tokens)
    cr = chunk_rows_of(rows.shape[0] // slots)
    block, chunk, r0, r1, _ = _jobs_of(tokens, n_tokens, slots)

    def held_block(j, block, chunk, r0, r1, fresh, *_):
        # a fresh result is not read: one block, fetched once
        return jnp.where(fresh[0] != 0, 0, block[j]), 0
    return pl.pallas_call(
        _add_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(block.shape[0],),
            in_specs=[
                pl.BlockSpec((cr, d), lambda j, block, chunk, *_: (
                    chunk[j], 0)),
                pl.BlockSpec((bt, d), held_block)],
            out_specs=pl.BlockSpec((bt, d), lambda j, block, *_: (
                block[j], 0)),
            scratch_shapes=[pltpu.VMEM((cr, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(out.shape, jnp.float32),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(100 << 20, (16 << 20) + int(1.25 * (
                4 * bt * d * 4 + cr * d * (2 * rows.dtype.itemsize + 4))))),
        interpret=interpret,
    )(block, chunk, r0, r1, fresh.astype(jnp.int32).reshape(1), tokens,
      weight, rows, out)


def add_rows_at_tokens(out, rows, weight, tokens, slots: int, fresh=False):
    """``out`` [T, d] float32 with ``weight[r] * rows[r]`` added, in
    float32, to row ``tokens[r]`` for every r with ``tokens[r] < T``.

    ``rows`` [R, d] in any float dtype, ``weight`` [R] float32, ``tokens``
    [R] int32; the rows are ``slots`` equal parts and inside each the tokens
    ascend, strictly below ``T`` (no token twice in a slot) and then any
    number of rows at ``T`` or more, which go nowhere. ``fresh``: ``out`` is
    all zeros (a traced bool), so its blocks are written without being
    read. ``T`` and ``R / slots`` decide the block and chunk sizes
    (:func:`block_tokens_of`, :func:`chunk_rows_of`); on the TPU ``T`` is a
    multiple of 8 and ``R / slots`` of 16, ``d`` of 128."""
    return on_this_platform(
        functools.partial(_add_rows_call, slots=slots), out, rows,
        weight.astype(jnp.float32), tokens, jnp.asarray(fresh))
