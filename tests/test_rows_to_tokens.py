"""``ops/rows_to_tokens.add_rows_at_tokens`` against the scatter-add it
replaces, ``out.at[tokens].add(weight * rows)`` in float32: the weighted
rows of a share's walk on their way back (forward) and the rows' gradient
(``d_x``: every weight one), the kernel in interpret mode on the CPU (its
TPU lowering is ``tests/test_tpu_compile.py``'s)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import rows_to_tokens as rt


def _operands(tokens, d, held, dtype, seed=0):
    """``held`` [slots][n] ascending tokens a slot -> (tokens [R] with ``T``
    past a slot's last, rows [R, d], weight [R]); every slot of the longest
    one's rows in whole chunks of 8."""
    rng = np.random.RandomState(seed)
    slot = max(8, -(-max(len(h) for h in held) // 8) * 8)
    at = np.full((len(held), slot), tokens, np.int32)
    for e, h in enumerate(held):
        at[e, :len(h)] = h
    rows = rng.randn(at.size, d)
    return jnp.asarray(at.reshape(-1)), jnp.asarray(rows, dtype), \
        jnp.asarray(rng.rand(at.size) + 0.5, jnp.float32)


def _scatter_add(out, rows, weight, tokens):
    return out.at[tokens].add(weight[:, None] * rows.astype(jnp.float32),
                              mode="drop")


def _some(rng, tokens, n):
    return np.sort(rng.choice(tokens, n, replace=False))


def _cases():
    """name -> (T, d, [slots][n] tokens)."""
    rng = np.random.RandomState(7)
    every = np.arange(64)
    return {
        "a-balanced-load": (64, 16, [_some(rng, 64, 24) for _ in range(4)]),
        # one held expert is sent every token: its slot is full
        "one-expert-sent-every-token": (64, 16, [every, [], [], []]),
        # a token's k choices all held: it is in every slot
        "tokens-in-every-slot": (64, 16, [every[::3]] * 4),
        "a-held-expert-sent-nothing": (
            64, 16, [_some(rng, 64, 20), [], _some(rng, 64, 31), []]),
        "no-pair-at-all": (64, 16, [[], [], [], []]),
        # a slot's pairs end on a chunk's edge, one past it, one short
        "pairs-end-on-a-chunks-edge": (64, 16, [every[:16], every[:8]]),
        "pairs-end-one-past-an-edge": (64, 16, [every[:17], every[:9]]),
        "pairs-end-one-short-of-an-edge": (64, 16, [every[:15], every[:7]]),
        # a run ends on a token block's last token and starts on its first
        "runs-end-on-a-blocks-edge": (
            512, 16, [[255, 256], [0, 255], [256, 511], [254, 257]]),
        "one-token-block-holds-every-row": (
            512, 16, [np.arange(256, 300), np.arange(257, 290)]),
        "the-first-and-last-token-alone": (512, 16, [[0], [511]]),
        # the two cells' shapes scaled down: eight slots, a slot 1.5 x the
        # pairs of a balanced router, d an even and an odd count of 128s
        "smallthinker-scaled-d-of-two-128s": (
            1024, 256, [_some(rng, 1024, n) for n in
                        (96, 101, 88, 93, 97, 144, 90, 99)]),
        "nemotron-scaled-d-of-three-128s": (
            512, 384, [_some(rng, 512, n) for n in
                       (24, 19, 40, 25, 22, 0, 27, 31)]),
    }


CASES = _cases()


@pytest.mark.parametrize("direction", ["forward", "d_x"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_go_back_to_their_tokens_as_the_scatter_add_did(case,
                                                             direction):
    """Into zeros (``fresh``): forward the router weights times bf16 rows,
    ``d_x`` float32 rows under weights all one. A token's terms are summed
    slot by slot where the scatter summed them row by row: equal to
    float32's last bits."""
    tokens, d, held = CASES[case]
    dtype = jnp.bfloat16 if direction == "forward" else jnp.float32
    at, rows, weight = _operands(tokens, d, held, dtype)
    if direction == "d_x":
        weight = jnp.ones_like(weight)
    zeros = jnp.zeros((tokens, d), jnp.float32)
    got = jax.jit(lambda *a: rt.add_rows_at_tokens(
        *a, slots=len(held), fresh=True))(zeros, rows, weight, at)
    want = _scatter_add(zeros, rows, weight, at)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    untouched = np.setdiff1d(np.arange(tokens),
                             np.concatenate([np.asarray(h, int)
                                             for h in held]))
    assert not np.asarray(got)[untouched].any()


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_second_tile_adds_to_what_the_first_left(case):
    """Not ``fresh``: the result so far is read, block by block, and the
    rows are added to it; a block with no row comes back as it was."""
    tokens, d, held = CASES[case]
    at, rows, weight = _operands(tokens, d, held, jnp.bfloat16, seed=1)
    so_far = jnp.asarray(np.random.RandomState(2).randn(tokens, d),
                         jnp.float32)
    got = jax.jit(lambda *a: rt.add_rows_at_tokens(
        *a, slots=len(held)))(so_far, rows, weight, at)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_scatter_add(so_far, rows, weight, at)),
        rtol=1e-6, atol=1e-6)


def test_fresh_is_data_and_a_loop_carries_the_result():
    """``fresh`` traced, as ``ep._walk`` gives it (``i == 0`` of a loop whose
    trip count is data): three tiles accumulate into one result, and what
    the carry held before the first is never read."""
    tokens, d, held = CASES["a-balanced-load"]
    tiles = [_operands(tokens, d, held, jnp.bfloat16, seed=s)
             for s in range(3)]
    at, rows, weight = (jnp.stack(a) for a in zip(*tiles))
    garbage = jnp.full((tokens, d), jnp.nan, jnp.float32)

    def walk(carry, live):
        return jax.lax.fori_loop(0, live, lambda i, out: (
            rt.add_rows_at_tokens(out, rows[i], weight[i], at[i],
                                  len(held), fresh=i == 0)), carry)
    got = jax.jit(walk)(garbage, 3)
    want = jnp.zeros((tokens, d), jnp.float32)
    for a, r, w in tiles:
        want = _scatter_add(want, r, w, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    assert np.isnan(np.asarray(jax.jit(walk)(garbage, 0))).all()


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_job_list_holds_any_routing(case):
    """The list's static length is enough, every token block has a job, the
    jobs are in block order, and the rows they name are each placed once:
    exactly the rows whose token is below ``T``."""
    tokens, _, held = CASES[case]
    at = np.asarray(_operands(tokens, 1, held, jnp.float32)[0])
    slots, slot = len(held), at.size // len(held)
    block, chunk, r0, r1, jobs = map(np.asarray, rt._jobs_of(
        jnp.asarray(at), tokens, slots))
    bt, cr = rt.block_tokens_of(tokens), rt.chunk_rows_of(slot)
    assert len(block) == rt.jobs_built(tokens, at.size, slots) >= jobs
    assert (np.diff(block) >= 0).all()
    assert set(block[:jobs]) == set(range(tokens // bt))
    assert not (r1 - r0)[jobs:].any()
    assert (block[jobs:] == block[jobs - 1]).all()
    assert (chunk[jobs:] == chunk[jobs - 1]).all()
    placed = np.concatenate([
        np.arange(c * cr + a, c * cr + b)
        for c, a, b in zip(chunk, r0, r1)] + [np.arange(0)]).astype(int)
    assert sorted(placed) == sorted(np.flatnonzero(at < tokens))
    assert (at[placed] // bt == np.repeat(block, r1 - r0)).all()


def test_block_and_chunk_sizes_follow_the_shapes():
    """Both cells: token blocks of 256, chunks of 32 rows; a tiny layer
    the largest powers of two that divide it."""
    assert rt.block_tokens_of(16384) == rt.block_tokens_of(8192) == 256
    assert rt.chunk_rows_of(2304) == rt.chunk_rows_of(640) == 32
    assert rt.block_tokens_of(96) == 32 and rt.chunk_rows_of(40) == 8
    # 8 slots x (72 chunks + 64 token blocks), 8 x (20 + 32)
    assert rt.jobs_built(16384, 18432, 8) == 1088
    assert rt.jobs_built(8192, 5120, 8) == 416
