"""The flash kernels' window: ``0 <= q_pos - k_pos < W``, beside the causal
mask (interpret mode on CPU; ``flash_cases.py`` holds the references)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import (RING_SHARDS, assert_close, block_visits,
                         dense_causal, dense_window, out_and_grads, qkv)
from horovod_tpu.ops.flash_attention import flash_attention, xla_attention

# T = 512 at blocks of 128: below a block, no multiple of it, a multiple,
# one key, and a window that reaches past the sequence (plain causal)
WINDOWS = {"below_a_block": 40, "no_multiple": 200, "a_multiple": 256,
           "one_key": 1, "all_of_it": 512, "past_the_end": 1000}


@functools.lru_cache(maxsize=None)
def _window_case(w, head_dim):
    """The inputs of a (window, head width) of the grid below and what every
    tile size is held to there: the explicit mask's output, and
    ``xla_attention``'s output and gradients under the same window."""
    q, k, v = qkv(21, (1, 512, 2, head_dim), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(22).randn(*q.shape),
                       jnp.float32)
    xla = functools.partial(xla_attention, causal=True, window=w)
    return (q, k, v), dout, dense_window(q, k, v, w)[0], \
        out_and_grads(xla, q, k, v, dout)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
@pytest.mark.parametrize("window", list(WINDOWS))
def test_flash_window_forward_and_grad(window, blocks, head_dim):
    """Forward and the three gradients under the window against
    ``xla_attention`` with the same window and against the explicit mask;
    both orientations of the dk/dv sums (heads of 64 and of 128)."""
    w = WINDOWS[window]
    (q, k, v), dout, want_o, (xla_o, want) = _window_case(w, head_dim)
    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1], window=w)
    o, got = out_and_grads(flash, q, k, v, dout)
    assert_close(o, want_o, jnp.float32)
    assert_close(xla_o, want_o, jnp.float32)
    if w >= 512:  # the window holds every key: the causal mask
        assert_close(o, dense_causal(q, k, v)[0], jnp.float32)
    for g, wanted in zip(got, want):
        assert_close(g, wanted, jnp.float32)


@pytest.mark.parametrize("shard", list(RING_SHARDS))
def test_flash_window_with_traced_offsets(shard):
    """The window is in global positions, so a sequence shard's offsets
    move it like the causal edge: (o, lse) and gradients under jit with
    traced offsets, with a cotangent on lse. A k shard far enough before
    the q shard lies wholly behind the window: every row is dead."""
    window = 300
    q, k, v = qkv(23, (1, 256, 2, 64), jnp.float32)
    rng = np.random.RandomState(24)
    dout = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    dlse = jnp.asarray(rng.randn(1, 2, 256), jnp.float32)

    def loss(attend, q, k, v, q_off, k_off):
        o, lse = attend(q, k, v, q_off, k_off)
        return jnp.sum(o * dout) + jnp.sum(lse * dlse), (o, lse)

    def flash(q, k, v, q_off, k_off):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               block_q=64, block_k=128, q_offset=q_off,
                               k_offset=k_off, return_lse=True,
                               window=window)

    def dense(q, k, v, q_off, k_off):
        return dense_window(q, k, v, window, q_off, k_off)

    offsets = [jnp.float32(x) for x in RING_SHARDS[shard]]
    grad = lambda attend: jax.jit(jax.grad(  # noqa: E731
        functools.partial(loss, attend), argnums=(0, 1, 2), has_aux=True))
    got, (o, lse) = grad(flash)(q, k, v, *offsets)
    want, (o_ref, lse_ref) = grad(dense)(q, k, v, *offsets)
    assert_close(o, o_ref, jnp.float32)
    assert_close(lse, lse_ref, jnp.float32)
    for g, w in zip(got, want):
        assert_close(g, w, jnp.float32)
    if shard == "before":  # 512 ahead of every key, window 300
        assert not np.asarray(o)[:, 44:].any()


def test_window_needs_a_causal_mask_and_a_key():
    from horovod_tpu.ops.flash_attention import attention
    x = jnp.zeros((1, 128, 2, 32), jnp.float32)
    for attend in (functools.partial(flash_attention, interpret=True),
                   xla_attention, attention):
        with pytest.raises(ValueError, match="causal=True"):
            attend(x, x, x, causal=False, window=64)
        with pytest.raises(ValueError, match="at least 1 key"):
            attend(x, x, x, causal=True, window=0)


WINDOW_PLANS = [  # tq, tk, block_q, block_k, q_offset, k_offset, window
    (512, 512, 128, 128, 0, 0, 40), (512, 512, 128, 128, 0, 0, 200),
    (512, 512, 128, 128, 0, 0, 256), (512, 512, 64, 128, 0, 0, 1),
    (512, 512, 128, 64, 0, 0, 129), (512, 512, 128, 128, 0, 0, 512),
    (512, 512, 128, 128, 0, 0, 4096), (256, 256, 64, 128, 256, 0, 300),
    (256, 256, 128, 128, 512, 0, 300), (256, 256, 128, 128, 0, 512, 300),
    (256, 512, 128, 64, -64, 0, 100), (256, 128, 128, 128, 100, 37, 90),
]


@pytest.mark.parametrize(
    "tq,tk,block_q,block_k,q_offset,k_offset,window", WINDOW_PLANS)
def test_window_block_plan_matches_brute_force(tq, tk, block_q, block_k,
                                               q_offset, k_offset, window):
    """Every block classified from the mask itself: all true interior, all
    false and in the future skipped, all false and behind the window
    skipped_behind, crossed by the causal edge diagonal, else crossed by
    the far edge alone: window_edge. Together they are the grid."""
    from horovod_tpu.ops.flash_attention import block_plan
    ahead = (q_offset + np.arange(tq)[:, None]
             - k_offset - np.arange(tk)[None, :])

    def tiles(mask):
        return mask.reshape(tq // block_q, block_q, tk // block_k, block_k)
    seen, future = tiles((ahead >= 0) & (ahead < window)), tiles(ahead < 0)
    none = ~seen.any((1, 3))
    want = {"interior": int(seen.all((1, 3)).sum()),
            "skipped": int((none & future.all((1, 3))).sum()),
            "skipped_behind": int((none & ~future.any((1, 3))).sum()),
            "diagonal": int((~none & future.any((1, 3))).sum())}
    want["window_edge"] = none.size - sum(want.values())
    got = block_plan(tq, tk, block_q, block_k, True, q_offset, k_offset,
                     window=window)
    assert got == want and sum(got.values()) == none.size
    if window >= tq + abs(q_offset - k_offset) + tk:  # no far edge in reach
        causal = block_plan(tq, tk, block_q, block_k, True, q_offset,
                            k_offset)
        assert {k: got[k] for k in causal} == causal


def test_window_block_visits_counted_under_their_own_kinds():
    """16 384 tokens under a window of 4096 at blocks of 512, the new
    cell's window layers: of a (batch, head)'s 1024 blocks 772 are never
    loaded. The causal kinds do not move: a reader of the window's share
    and one of the causal calls' never mix."""
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.ops.flash_attention import block_plan
    plan = block_plan(16384, 16384, 512, 512, True, window=4096)
    assert plan == {"interior": 196, "diagonal": 32, "window_edge": 24,
                    "skipped": 496, "skipped_behind": 276}

    def window_visits():
        return {kind: get_registry().counter(
            "hvd_flash_block_visits", kind="window_" + kind).value
            for kind in ("interior", "diagonal", "edge", "skipped",
                         "skipped_behind")}
    x = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    causal_before, before = block_visits(), window_visits()
    jax.eval_shape(functools.partial(flash_attention, causal=True,
                                     interpret=True, window=4096), x, x, x)
    after = window_visits()
    assert {k: after[k] - before[k] for k in after} == {
        "interior": 2 * 196, "diagonal": 2 * 32, "edge": 2 * 24,
        "skipped": 2 * 496, "skipped_behind": 2 * 276}
    assert block_visits() == causal_before
