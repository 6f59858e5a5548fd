"""The flash kernels' block mask: the causal edge rounded to blocks of G
positions, and the two streams of a block-diffusion pass
(``blockdiff_attention``: the kernels under both edges, merged by
``merge_attention`` with ``block_diagonal_attention`` of the noised stream).
Interpret mode on CPU; ``flash_cases.py`` holds the references."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import (assert_close, block_edge, dense_masked,
                         out_and_grads, qkv)
from horovod_tpu.ops.flash_attention import flash_attention, xla_attention


@functools.lru_cache(maxsize=None)
def _block_mask_case(group, edge, head_dim, seq):
    """The inputs of a (mask, head width, length) of the grid below and what
    every tile size is held to there: output, lse and gradients under the
    dense mask built by hand, and ``xla_attention``'s under the same
    ``block_mask``."""
    q, k, v = qkv(31, (1, seq, 2, head_dim), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(32).randn(*q.shape),
                       jnp.float32)
    seen = block_edge(seq, group, edge)
    dense = lambda q, k, v: dense_masked(q, k, v, seen)[0]  # noqa: E731
    xla = functools.partial(xla_attention, causal=True,
                            block_mask=(group, edge))
    return (q, k, v), dout, dense_masked(q, k, v, seen)[1], \
        out_and_grads(dense, q, k, v, dout), out_and_grads(xla, q, k, v, dout)


# L = 192 is no multiple of the preferred tile (512, or 128): tiles of 64
@pytest.mark.parametrize("seq,blocks", [(192, (64, 64)), (256, (64, 128)),
                                        (256, (128, 64))],
                         ids=["192_at_64", "256_64x128", "256_128x64"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("edge", ["le", "lt"])
@pytest.mark.parametrize("group", [1, 4, 16])
def test_flash_block_mask_forward_and_grad(group, edge, head_dim, seq,
                                           blocks):
    """Forward, lse and the three gradients under ``block_mask=(G, edge)``
    in interpret mode and through ``xla_attention`` against a dense mask
    built by hand from block indices; under ``"lt"`` the rows of block 0
    see no key: output 0, lse NEG_INF, no gradient."""
    (q, k, v), dout, want_lse, (want_o, want), (xla_o, xla_grads) = \
        _block_mask_case(group, edge, head_dim, seq)

    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1],
                              block_mask=(group, edge), return_lse=True)
    (o, lse), got = out_and_grads(flash, q, k, v, dout)
    assert_close(o, want_o, jnp.float32)
    assert_close(lse, want_lse, jnp.float32)
    assert_close(xla_o, want_o, jnp.float32)
    if edge == "lt":
        np.testing.assert_array_equal(np.asarray(o[:, :group]), 0)
        assert np.all(np.asarray(lse[..., :group]) < -1e29)
    for grads in (got, xla_grads):
        for g, w in zip(grads, want):
            assert_close(g, w, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_block_mask_of_one_is_the_causal_call_bit_for_bit(dtype):
    """``b(k) <= b(q)`` at G = 1 is ``k <= q``: the same kernels' bodies
    under another name, and not a bit of the output or of a gradient
    moves."""
    q, k, v = qkv(33, (1, 256, 2, 64), dtype)

    def both(**mask):
        attend = functools.partial(flash_attention, causal=True,
                                   interpret=True, block_q=64, block_k=128,
                                   **mask)
        return attend(q, k, v), jax.jit(jax.grad(
            lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(q, k, v)
    for got, want in zip(jax.tree_util.tree_leaves(both(block_mask=(1, "le"))),
                         jax.tree_util.tree_leaves(both())):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_block_mask_needs_causal_no_window_no_offsets_and_whole_blocks():
    from horovod_tpu.ops.flash_attention import attention
    x = jnp.zeros((1, 128, 1, 8), jnp.float32)
    bad = [dict(causal=False, block_mask=(4, "le")),
           dict(causal=True, window=8, block_mask=(4, "le")),
           dict(causal=True, block_mask=(0, "le")),
           dict(causal=True, block_mask=(4, "eq"))]
    for attend in (flash_attention, xla_attention, attention):
        for kwargs in bad:
            with pytest.raises(ValueError, match="block_mask"):
                attend(x, x, x, **kwargs)
    # the ring's offsets: the mask counts positions from 0 on both sides
    for offsets in (dict(q_offset=64.0), dict(k_offset=jnp.float32(64))):
        with pytest.raises(ValueError, match="block_mask"):
            flash_attention(x, x, x, causal=True, interpret=True,
                            block_mask=(4, "le"), **offsets)
    with pytest.raises(ValueError, match="do not divide the tiles"):
        flash_attention(x, x, x, causal=True, interpret=True, block_q=64,
                        block_k=64, block_mask=(3, "le"))


BLOCK_PLANS = [  # t, block_q, block_k, G
    (512, 128, 128, 1), (512, 128, 128, 4), (512, 64, 128, 16),
    (512, 128, 64, 64), (512, 128, 128, 128), (192, 96, 96, 4),
    (256, 64, 64, 32)]


@pytest.mark.parametrize("edge", ["le", "lt"])
@pytest.mark.parametrize("t,block_q,block_k,group", BLOCK_PLANS)
def test_block_mask_block_plan_matches_brute_force(t, block_q, block_k,
                                                   group, edge):
    """Every tile classified from the mask itself; the three kinds sum to
    the grid, and a skipped tile is one the kernels' loops never reach
    (their bounds are the plan's: a call over zeros whose skipped keys are
    NaN stays finite)."""
    from horovod_tpu.ops.flash_attention import block_plan
    seen = np.asarray(block_edge(t, group, edge)).reshape(
        t // block_q, block_q, t // block_k, block_k)
    want = {"interior": int(seen.all((1, 3)).sum()),
            "skipped": int((~seen.any((1, 3))).sum())}
    want["diagonal"] = seen.shape[0] * seen.shape[2] - sum(want.values())
    got = block_plan(t, t, block_q, block_k, True, block_mask=(group, edge))
    assert got == want and sum(got.values()) == (t // block_q) * (t // block_k)
    if group == 1 and edge == "le":
        assert got == block_plan(t, t, block_q, block_k, True)


def test_blockdiff_block_visits_counted_under_their_own_kinds():
    """8192 data tokens in blocks of 4 at tiles of 512, the SDAR cell's
    layer: of a (batch, head)'s [2L, 2L] grid of 1024 tiles, 240 lie past
    the rounded edge and 512 under the noised stream's keys: 73.4% never
    loaded. The causal and the window kinds do not move."""
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.ops.flash_attention import (blockdiff_attention,
                                                 blockdiff_block_plan)
    plan = blockdiff_block_plan(8192, 512, 512, 4)
    assert plan == {"interior": 240, "diagonal": 32, "skipped": 240,
                    "noised_keys": 512}
    assert sum(plan.values()) == 32 * 32

    def visits(prefix):
        return {kind: get_registry().counter(
            "hvd_flash_block_visits", kind=prefix + kind).value
            for kind in ("interior", "diagonal", "skipped", "noised_keys")}
    q = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    others, before = (visits(""), visits("window_")), visits("blockdiff_")
    jax.eval_shape(functools.partial(blockdiff_attention, group=4,
                                     interpret=True), q, kv, kv)
    after = visits("blockdiff_")
    assert {k: after[k] - before[k] for k in after} == {
        k: 4 * n for k, n in plan.items()}
    assert (visits(""), visits("window_")) == others


def _hand_blockdiff_mask(seq, group):
    """The block-diffusion mask built by hand, pair by pair: streams and
    block indices, no arithmetic shared with ``blockdiff_mask``."""
    seen = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            q_noised, k_noised = i < seq, j < seq
            bq, bk = (i % seq) // group, (j % seq) // group
            if q_noised:
                seen[i, j] = bk == bq if k_noised else bk < bq
            else:
                seen[i, j] = not k_noised and bk <= bq
    return seen


def _dense_blockdiff(q, k, v, seq, group):
    return dense_masked(q, k, v,
                         jnp.asarray(_hand_blockdiff_mask(seq, group)))[0]


@pytest.mark.parametrize("path", ["kernels", "xla"])
@pytest.mark.parametrize("group", [1, 4, 16])
def test_blockdiff_attention_forward_and_grad(group, path):
    """Both streams of a sequence in one call, 4 query heads on 2 key
    heads, L = 192 (three tiles of 64): the kernels under
    the two block masks merged with a noised block on itself, and the one
    dense pass below the crossover, against the mask written out by hand;
    output and all three gradients. A noised row of block 0 sees its own
    block alone."""
    from horovod_tpu.ops import flash_attention as fa
    seq = 192
    rng = np.random.RandomState(41)
    q = jnp.asarray(rng.randn(1, 2 * seq, 4, 32), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, 2 * seq, 2, 32), jnp.float32)
            for _ in range(2))
    dout = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    attend = functools.partial(fa.blockdiff_attention, group=group)
    if path == "kernels":
        attend = functools.partial(attend, min_flash_seq=0, interpret=True,
                                   block_q=64, block_k=64)
    dense = functools.partial(_dense_blockdiff, seq=seq, group=group)
    np.testing.assert_array_equal(np.asarray(fa.blockdiff_mask(seq, group)),
                                  _hand_blockdiff_mask(seq, group))
    got, got_grads = out_and_grads(attend, q, k, v, dout)
    want, want_grads = out_and_grads(dense, q, k, v, dout)
    assert_close(got, want, jnp.float32)
    own = dense_masked(q[:, :group], k[:, :group], v[:, :group],
                        jnp.ones((group, group), bool))[0]
    assert_close(got[:, :group], own, jnp.float32)
    for g, w in zip(got_grads, want_grads):
        assert_close(g, w, jnp.float32)


def test_blockdiff_mask_by_hand():
    """L = 8, G = 4: the four quadrants written out."""
    from horovod_tpu.ops.flash_attention import blockdiff_mask
    one, none = np.ones((4, 4), bool), np.zeros((4, 4), bool)
    want = np.block([[one, none, none, none],     # xt block 0: itself
                     [none, one, one, none],      # xt block 1: itself, x0 0
                     [none, none, one, none],     # x0 block 0
                     [none, none, one, one]])     # x0 block 1: x0 0 and 1
    np.testing.assert_array_equal(np.asarray(blockdiff_mask(8, 4)), want)
    assert int(np.asarray(blockdiff_mask(64, 4)).sum()) == 64 * 64 + 64 * 4


def test_block_diagonal_attention_is_each_block_on_itself():
    from horovod_tpu.ops.flash_attention import block_diagonal_attention
    rng = np.random.RandomState(43)
    q = jnp.asarray(rng.randn(2, 32, 4, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 32, 2, 16), jnp.float32)
            for _ in range(2))
    blocks = np.arange(32) // 8
    want_o, want_lse = dense_masked(
        q, k, v, jnp.asarray(blocks[:, None] == blocks[None, :]))
    o, lse = block_diagonal_attention(q, k, v, 8)
    assert o.shape == q.shape and lse.shape == (2, 4, 32)
    assert_close(o, want_o, jnp.float32)
    assert_close(lse, want_lse, jnp.float32)
    with pytest.raises(ValueError, match="blocks of 5"):
        block_diagonal_attention(q, k, v, 5)


def test_merge_attention_combines_disjoint_key_sets():
    """merge_attention(o1, lse1, o2, lse2) over a key split equals attention
    over the full key set."""
    from horovod_tpu.ops.flash_attention import merge_attention
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 32, 2, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 128, 2, 16), jnp.float32)
            for _ in range(2))
    o1, l1 = flash_attention(q, k[:, :64], v[:, :64], interpret=True,
                             return_lse=True)
    o2, l2 = flash_attention(q, k[:, 64:], v[:, 64:], interpret=True,
                             return_lse=True)
    got, _ = merge_attention(o1, l1, o2, l2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(16)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
