"""``ops/grouped_matmul.grouped_matmul`` against a float32 ``einsum`` a
group: the output, the gradient to the rows and the gradient to the
matrices, the kernels in interpret mode on the CPU (the TPU lowering of the
same kernels is ``tests/test_tpu_compile.py``'s)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.grouped_matmul import grouped_matmul

R = 8  # rows of a block


def _layout(sizes, built_blocks):
    """Each group's rows from a row block on: (block-to-group table padded
    to ``built_blocks`` with the last group's index, live blocks [1], [rows]
    bool: a row of a group, [rows] the group of each row)."""
    sizes = np.asarray(sizes)
    blocks = -(-sizes // R)
    table = np.repeat(np.arange(len(sizes)), blocks)
    assert len(table) <= built_blocks
    table = np.concatenate([table, np.full(built_blocks - len(table),
                                           len(sizes) - 1)]).astype(np.int32)
    real = np.zeros(built_blocks * R, bool)
    start = 0
    for size, n in zip(sizes, blocks):
        real[start:start + size] = True
        start += n * R
    return jnp.asarray(table), jnp.asarray([blocks.sum()], jnp.int32), \
        real, np.repeat(table, R)


def _operands(sizes, built_blocks, k, n, dtype, transposed=False, seed=0):
    table, live, real, group_of_row = _layout(sizes, built_blocks)
    rng = np.random.RandomState(seed)
    a = np.where(real[:, None], rng.randn(len(real), k), 0.0)
    w = rng.randn(len(sizes), *((n, k) if transposed else (k, n))) * 0.2
    return jnp.asarray(a, dtype), jnp.asarray(w, dtype), table, live, \
        real[:, None], group_of_row


def _product(table, live, real, transposed=False, steps=None):
    """The rows of no group zeroed on the way in and out: the kernel
    leaves rows outside the live blocks unwritten (``ep.moe_dropless``
    gathers only the rows that hold a pair, and zeroes the others'
    gradient). ``steps``: the blocks by grid step, in order without it (a
    full load's: the live blocks are a prefix)."""
    if steps is None:
        steps = jnp.arange(table.shape[0], dtype=jnp.int32)

    def product(a, w):
        a = jnp.where(real, a, jnp.zeros((), a.dtype))
        out = grouped_matmul(a, w, table, steps, live, transposed)
        return jnp.where(real, out, jnp.zeros((), out.dtype))
    return product


def _reference(real, group_of_row, transposed=False):
    """A float32 einsum with each row's own matrix."""
    def product(a, w):
        w = w.astype(jnp.float32)[group_of_row]
        out = jnp.einsum("rk,rnk->rn" if transposed else "rk,rkn->rn",
                         a.astype(jnp.float32), w)
        return jnp.where(real, out, 0.0)
    return product


def _value_and_grads(product, a, w, seed=1):
    """(out, d_rows, d_matrices) under one seeded cotangent, a number of
    the rows' dtype whatever the product's."""
    out, pull = jax.vjp(product, a, w)
    cotangent = np.random.RandomState(seed).randn(*out.shape)
    return (out,) + pull(jnp.asarray(cotangent, a.dtype).astype(out.dtype))


CASES = {
    "uneven": ([13, 5, 16, 9], 9),
    "an-expert-with-no-pair": ([13, 0, 16, 5], 7),
    "no-pair-first-and-last": ([0, 11, 3, 0], 5),
    "exact-multiples-of-a-block": ([8, 16, 24, 8], 7),
    "one-expert-holds-every-row": ([0, 0, 40, 0], 5),
    "live-blocks-fewer-than-built": ([3, 9, 1, 2], 12),
    "no-live-block": ([0, 0, 0, 0], 3),
}


@pytest.mark.parametrize("transposed", [False, True], ids=["w", "w-T"])
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_matmul_and_its_gradients_match_an_einsum_a_group(
        case, transposed):
    """float32 operands: the output, the gradient to the rows and to the
    matrices are the einsum's to float32's rounding, whatever the groups'
    sizes; a group with no live block gets a zero gradient."""
    sizes, built = CASES[case]
    a, w, table, live, real, group_of_row = _operands(
        sizes, built, 24, 40, jnp.float32, transposed)
    got = _product(table, live, real, transposed)
    want = _reference(real, group_of_row, transposed)
    for name, g, v in zip(("out", "d_rows", "d_matrices"),
                          _value_and_grads(jax.jit(got), a, w),
                          _value_and_grads(want, a, w)):
        assert g.shape == v.shape and g.dtype == v.dtype, name
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
    d_matrices = np.asarray(_value_and_grads(jax.jit(got), a, w)[2])
    for g, size in enumerate(sizes):
        assert (np.abs(d_matrices[g]).sum() > 0) == (size > 0), g


@pytest.mark.parametrize("k,n", [(256, 128), (128, 256), (232, 336),
                                 (336, 232), (128, 232)],
                         ids=["olmoe-up", "olmoe-down", "232-in", "232-out",
                              "232-out-128-in"])
def test_widths_of_olmoe_and_widths_that_are_no_multiple_of_128(k, n):
    """OLMoE's 2048 x 1024 and 1024 x 2048 an eighth the size (whole 128s
    either way), and Nemotron-H's 1856 = 14.5 x 128 likewise: a contraction
    and a result width that do not cut into 128s (232 = 1.8125 x 128), both
    ways and towards the matrices."""
    a, w, table, live, real, group_of_row = _operands(
        [9, 16, 2], 5, k, n, jnp.float32)
    got = _value_and_grads(jax.jit(_product(table, live, real)), a, w)
    want = _value_and_grads(_reference(real, group_of_row), a, w)
    for name, g, v in zip(("out", "d_rows", "d_matrices"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_column_tiles_of_a_matrix_too_large_to_hold_whole(monkeypatch):
    """A matrix over the kernels' VMEM room is worked in column tiles of
    whole 128s, the last one short: the same numbers."""
    from horovod_tpu.ops import grouped_matmul as gm
    a, w, table, live, real, group_of_row = _operands(
        [9, 16, 2], 5, 160, 328, jnp.float32)
    want = _value_and_grads(_reference(real, group_of_row), a, w)
    monkeypatch.setattr(gm, "_MATRIX_BYTES", 160 * 328 * 4 // 3 + 1)
    monkeypatch.setattr(gm, "_ACCUMULATOR_BYTES", 160 * 328 * 4 // 2 + 1)
    assert gm._tile_of(328, 3) == 128 and gm._tile_of(160, 2) == 128
    got = _value_and_grads(_product(table, live, real), a, w)
    for name, g, v in zip(("out", "d_rows", "d_matrices"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-5,
                                   atol=2e-5, err_msg=name)


def test_bf16_operands_are_within_bf16_rounding_of_the_float32_sum():
    """bf16 rows and matrices, products summed in float32: each result is
    the float32 einsum of the same bf16 numbers, rounded to bf16 once (rows
    in the rows' dtype, the matrices' gradient in the matrices')."""
    a, w, table, live, real, group_of_row = _operands(
        [13, 0, 16, 5], 7, 256, 232, jnp.bfloat16)
    got = _value_and_grads(jax.jit(_product(table, live, real)), a, w)
    want = _value_and_grads(_reference(real, group_of_row), a, w)
    for name, g, v in zip(("out", "d_rows", "d_matrices"), got, want):
        assert g.dtype == jnp.bfloat16, name
        g, v = np.asarray(g, np.float32), np.asarray(v, np.float32)
        # one rounding to bf16's 8 bits of the float32 sum, and the float32
        # sum's own order
        np.testing.assert_allclose(g, v, rtol=2 ** -8, atol=1e-3 * np.abs(
            v).max(), err_msg=name)


def test_rows_past_the_live_blocks_are_not_computed():
    """The contract ``ep.moe_dropless`` gathers around: rows past the live count
    hold whatever the buffer held (in interpret mode: not the product),
    the live ones the product."""
    a, w, table, live, real, group_of_row = _operands(
        [8, 8], 4, 16, 24, jnp.float32)
    a = a.at[16:].set(1.0)  # rows of blocks that are built and not live
    out = np.asarray(grouped_matmul(a, w, table, jnp.arange(4), live))
    want = np.einsum("rk,rkn->rn", np.asarray(a), np.asarray(w)[group_of_row])
    np.testing.assert_allclose(out[:16], want[:16], rtol=1e-5, atol=1e-5)
    assert not np.allclose(out[16:], want[16:], rtol=1e-2)


# -- live blocks that are no prefix: a slot of whole blocks a group ------------

def _slots(sizes, blocks_a_slot):
    """Group ``g``'s rows from block ``g * blocks_a_slot`` on, as a tile of
    ``ep._walk`` lays them: (block-to-group table, the live blocks slot
    after slot then any block at all, their count [1], [rows] bool: a row
    of a group, [rows] the group of each row)."""
    groups = len(sizes)
    table = np.repeat(np.arange(groups), blocks_a_slot).astype(np.int32)
    live = [g * blocks_a_slot + b for g, size in enumerate(sizes)
            for b in range(-(-size // R))]
    steps = np.asarray(live + [0] * (len(table) - len(live)), np.int32)
    real = np.zeros(len(table) * R, bool)
    for g, size in enumerate(sizes):
        assert size <= blocks_a_slot * R
        real[g * blocks_a_slot * R:g * blocks_a_slot * R + size] = True
    return jnp.asarray(table), jnp.asarray(steps), \
        jnp.asarray([len(live)], jnp.int32), real, np.repeat(table, R)


SLOT_CASES = {
    "every-slot-two-thirds-full": [16, 13, 16, 11],
    "a-slot-with-no-live-block": [24, 0, 9, 3],
    "the-first-and-last-slots-empty": [0, 17, 8, 0],
    "every-slot-full": [24, 24, 24, 24],
    "no-live-block": [0, 0, 0, 0],
    "one-row": [0, 0, 1, 0],
}


@pytest.mark.parametrize("transposed", [False, True], ids=["w", "w-T"])
@pytest.mark.parametrize("case", list(SLOT_CASES))
def test_live_blocks_named_by_a_list_match_an_einsum_a_group(case,
                                                             transposed):
    """The same kernels under a share's layout: four slots of three blocks,
    the blocks that hold a row named by a compacted list (each slot's own
    prefix). Output and both gradients are the einsum's; a block the list
    does not name is not computed; a group with none gets zeros."""
    sizes = SLOT_CASES[case]
    table, steps, live, real, group_of_row = _slots(sizes, 3)
    rng = np.random.RandomState(2)
    k, n = (40, 24) if transposed else (24, 40)
    a = jnp.asarray(np.where(real[:, None], rng.randn(len(real), k), 0.0),
                    jnp.float32)
    w = jnp.asarray(rng.randn(len(sizes), 24, 40) * 0.2, jnp.float32)
    real = real[:, None]
    got = _value_and_grads(jax.jit(_product(table, live, real, transposed,
                                            steps)), a, w)
    want = _value_and_grads(_reference(real, group_of_row, transposed), a, w)
    for name, g, v in zip(("out", "d_rows", "d_matrices"), got, want):
        assert g.shape == v.shape and g.dtype == v.dtype, name
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=1e-5,
                                   atol=1e-5, err_msg=name)
    for g, size in enumerate(sizes):
        assert (np.abs(np.asarray(got[2])[g]).sum() > 0) == (size > 0), g
    # a block outside the list holds what the buffer held, not the product
    ones = jnp.ones_like(a)
    out = np.asarray(grouped_matmul(ones, w, table, steps, live, transposed))
    full = np.asarray(_reference(np.ones_like(real), group_of_row,
                                 transposed)(ones, w))
    block_live = np.zeros(len(table), bool)
    block_live[np.asarray(steps)[:int(live[0])]] = True
    row_live = np.repeat(block_live, R)
    np.testing.assert_allclose(out[row_live], full[row_live], rtol=1e-5,
                               atol=1e-5)
    if (~row_live).any():
        assert not np.allclose(out[~row_live], full[~row_live], rtol=1e-2)
