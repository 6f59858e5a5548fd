"""A grouped matmul whose groups start on row blocks, paced by its rows.

``jax.lax.ragged_dot`` multiplies runs of rows by one matrix a run, the runs
wherever their sizes put them. The TPU compiler's kernel for it is paced by
the (group, 512-row tile) pairs it visits and not by the rows (``PERF.md``
§6, PRs 31, 34 and 36: 3.1-3.3 ms for a 1.4 ms product over 64 groups of
about 1024 rows of 2048 x 1024, 2.0 ms for 0.2 ms over eight groups of
280-500 rows of 2688 x 1856). :func:`grouped_matmul` takes the other
contract: the rows come in equal *blocks* and **every block belongs to one
group**, named by a table,

    out[rows of block b] = a[rows of block b] @ w[group_of_block[b]]

so the product is a tiled matmul whose weight block is chosen a row block.
Which blocks are worked is a second table: grid step ``j`` works on block
``block_of_step[j]``, for the first ``live`` steps, so the blocks that hold
something need not be a prefix of the blocks there are. The two tables and
the count of *live* steps are scalar-prefetch operands: the index maps read
them, so consecutive steps of one group keep its matrix in VMEM (a block
index that does not change is not fetched again), and a block that no live
step names is not computed, fetched or written: the rows of such a block
hold whatever the buffer held, as ``ragged_dot`` leaves rows in no group,
and a caller reads the rows of live blocks only. Who lays rows out like
that, both in ``parallel/ep.moe_dropless``: a full load, which starts each
expert's pairs on a row block of 128 (the live blocks are a prefix and the
steps name the blocks in order), and a share's walk, whose tile is a slot
of whole row blocks a held expert (each slot's live blocks are a prefix of
the slot: the steps name them slot after slot, compacted).

Two kernels under one ``jax.custom_vjp``:

- ``_gmm_kernel``, grid (column tiles of the result, row blocks): one
  ``[block_rows, k] x [k, n]`` product a step, float32 accumulation on the
  MXU, the contraction held whole, the result's columns whole too where the
  group's matrix fits a few MiB of VMEM twice (2048 x 1024 in bf16 does),
  else in tiles of 128s. ``transposed=True`` multiplies by ``w[g]^T`` (the
  matrices as ``[groups, n, k]``): the product towards the rows is this
  kernel over the same matrices, read the other way.
- ``_gmm_dw_kernel``, the transpose towards the matrices, ``dw[g] =
  a_g^T @ dout_g``: grid (row tiles of ``dw[g]``, row blocks), the row
  blocks sequential; a float32 VMEM tile is started at a group's first
  block and written out, in the matrices' dtype, at its last. The blocks of
  a group must be consecutive steps. A group with no live block is never
  visited; the wrapper gives it zeros. (Two and four blocks a step, a run
  of one group's blocks inside a window with the window's other rows
  selected out, were built and measured on the chip in PR 36: the same
  2.85-2.93 ms a call as one block a step, with forty more lines.)

Operands in the dtype they come in (bf16 in the training step), products
accumulated in float32, results in ``a``'s dtype and ``dw`` in ``w``'s: the
arithmetic of ``ragged_dot`` and its transposes. The kernels are called by
``ops/kernel_call.py``'s rule (Mosaic where the program is lowered for a TPU,
interpret mode elsewhere, under ``jax.jit``: nothing chooses between paths, a
compile for a described chip holds the kernels, and a kernel is traced once
a process and variant).

Shapes: ``a`` [rows, k] with ``rows`` a multiple of the number of blocks;
``w`` [groups, k, n] (``transposed``: [groups, n, k]); ``group_of_block``
int32 [blocks], every entry a group's index, live or not; ``block_of_step``
int32 [blocks], the block a grid step works on, read for the live steps
only; ``live`` int32 [1], the steps from the first on that are computed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import NN, NT, TN, dot, on_this_platform

_MATRIX_BYTES = 12 << 20  # a group's matrix block in VMEM, held twice
_ACCUMULATOR_BYTES = 8 << 20  # the float32 tile of dw[g] in VMEM


def _tile_of(width: int, tiles: int) -> int:
    """``width`` cut into ``tiles`` pieces of whole 128s (the last may run
    short), or whole."""
    if tiles <= 1:
        return width
    return min(width, -(-width // (128 * tiles)) * 128)


def _compiler_params(*block_bytes: int) -> pltpu.CompilerParams:
    """The row blocks in order (``dw``'s accumulator lives across them),
    and room for the blocks named (the caller counts those of the pipeline
    twice) beside the compiler's own temporaries."""
    need = int(1.25 * sum(block_bytes)) + (8 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=min(max(need, 32 << 20), 100 << 20))


def _block_of(step, block_ref, live_ref):
    """The row block of a grid step. A step past the live count maps where
    the last live one did, so the pipeline fetches and writes nothing for
    it."""
    return block_ref[jnp.minimum(step, jnp.maximum(live_ref[0] - 1, 0))]


def _gmm_kernel(group_ref, block_ref, live_ref, a_ref, w_ref, out_ref, *,
                transposed):
    @pl.when(pl.program_id(1) < live_ref[0])
    def _():
        out_ref[...] = dot(a_ref[...], w_ref[0],
                           NT if transposed else NN).astype(out_ref.dtype)


def _gmm_dw_kernel(group_ref, block_ref, live_ref, a_ref, d_ref, out_ref,
                   acc_ref):
    b, steps = pl.program_id(1), pl.num_programs(1)
    live = live_ref[0]

    @pl.when(b < live)
    def _():
        group = group_ref[block_ref[b]]
        first = (b == 0) | (
            group_ref[block_ref[jnp.maximum(b - 1, 0)]] != group)
        last = (b == live - 1) | (
            group_ref[block_ref[jnp.minimum(b + 1, steps - 1)]] != group)
        # a group's first block starts the sum: no pass that zeroes the tile
        acc_ref[...] = jnp.where(first, 0.0, acc_ref[...]) \
            + dot(a_ref[...], d_ref[...], TN)

        @pl.when(last)
        def _():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_rows", "transposed", "interpret"))
def _gmm_call(group_of_block, block_of_step, live, a, w, *, block_rows,
              transposed, interpret):
    rows, k = a.shape
    n = w.shape[1] if transposed else w.shape[2]
    tn = _tile_of(n, -(-k * n * w.dtype.itemsize // _MATRIX_BYTES))

    def a_block(j, b, group, block, live):
        return _block_of(b, block, live), 0

    def w_block(j, b, group, block, live):
        g = group[_block_of(b, block, live)]
        return (g, j, 0) if transposed else (g, 0, j)

    def out_block(j, b, group, block, live):
        return _block_of(b, block, live), j
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), rows // block_rows),
            in_specs=[
                pl.BlockSpec((block_rows, k), a_block),
                pl.BlockSpec((1, tn, k) if transposed else (1, k, tn),
                             w_block)],
            out_specs=pl.BlockSpec((block_rows, tn), out_block)),
        out_shape=jax.ShapeDtypeStruct((rows, n), a.dtype),
        compiler_params=_compiler_params(
            2 * k * tn * w.dtype.itemsize,
            2 * block_rows * k * a.dtype.itemsize,
            2 * block_rows * tn * a.dtype.itemsize, 2 * block_rows * tn * 4),
        interpret=interpret,
    )(group_of_block, block_of_step, live, a, w)


@functools.partial(jax.jit, static_argnames=(
    "groups", "dtype", "block_rows", "interpret"))
def _gmm_dw_call(group_of_block, block_of_step, live, a, d, *, groups, dtype,
                 block_rows, interpret):
    """``dw[g] = a_g^T @ d_g`` [groups, k, n]; the matrix of a group with no
    live block is not written."""
    rows, k = a.shape
    n = d.shape[1]
    tk = _tile_of(k, -(-k * n * 4 // _ACCUMULATOR_BYTES))

    def out_block(j, b, group, block, live):
        return group[_block_of(b, block, live)], j, 0
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(k, tk), rows // block_rows),
            in_specs=[
                pl.BlockSpec((block_rows, tk),
                             lambda j, b, group, block, live: (
                                 _block_of(b, block, live), j)),
                pl.BlockSpec((block_rows, n),
                             lambda j, b, group, block, live: (
                                 _block_of(b, block, live), 0))],
            out_specs=pl.BlockSpec((1, tk, n), out_block),
            scratch_shapes=[pltpu.VMEM((tk, n), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
        compiler_params=_compiler_params(
            # the tile, a step's product and their sum, in float32
            3 * tk * n * 4, 2 * tk * n * jnp.dtype(dtype).itemsize,
            2 * block_rows * (tk + n) * a.dtype.itemsize),
        interpret=interpret,
    )(group_of_block, block_of_step, live, a, d)


def _towards_the_matrices(a, d, group_of_block, block_of_step, live, groups,
                          dtype):
    """``dw[g] = a_g^T @ d_g`` over the live blocks, zeros for a group that
    has none (a selection XLA fuses into whatever reads the gradient)."""
    blocks = group_of_block.shape[0]
    dw = on_this_platform(
        functools.partial(_gmm_dw_call, groups=groups, dtype=dtype,
                          block_rows=a.shape[0] // blocks),
        group_of_block, block_of_step, live, a, d)
    visited = jnp.any(
        (group_of_block[block_of_step] == jnp.arange(groups)[:, None])
        & (jnp.arange(blocks) < live[0]), axis=1)
    return jnp.where(visited[:, None, None], dw, jnp.zeros((), dw.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def grouped_matmul(a, w, group_of_block, block_of_step, live,
                   transposed=False):
    """``out[rows of block b] = a[rows of block b] @ w[group_of_block[b]]``
    (``transposed``: ``@ w[...]^T``) for the blocks ``b`` that the first
    ``live[0]`` entries of ``block_of_step`` name, of the
    ``len(group_of_block)`` equal row blocks of ``a``; the rows of the
    others are not written (module text). Differentiable in ``a`` (the
    gradient's rows outside the live blocks are not written either) and in
    ``w``."""
    return on_this_platform(
        functools.partial(_gmm_call, transposed=transposed,
                          block_rows=a.shape[0] // group_of_block.shape[0]),
        group_of_block, block_of_step, live, a, w)


def _grouped_matmul_fwd(a, w, group_of_block, block_of_step, live,
                        transposed):
    return grouped_matmul(a, w, group_of_block, block_of_step, live,
                          transposed), (a, w, group_of_block, block_of_step,
                                        live)


def _grouped_matmul_bwd(transposed, saved, d_out):
    a, w, group_of_block, block_of_step, live = saved
    d_out = d_out.astype(a.dtype)
    d_a = grouped_matmul(d_out, w, group_of_block, block_of_step, live,
                         not transposed)
    left, right = (d_out, a) if transposed else (a, d_out)
    d_w = _towards_the_matrices(left, right, group_of_block, block_of_step,
                                live, w.shape[0], w.dtype)
    return d_a, d_w, None, None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
