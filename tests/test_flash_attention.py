"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import flash_attention

B, T, H, D = 2, 256, 4, 64


def dense(q, k, v, causal):
    s = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_flash_matches_dense(causal, blocks):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    got = np.asarray(flash_attention(q, k, v, causal=causal,
                                     block_q=blocks[0], block_k=blocks[1],
                                     interpret=True))
    want = dense(np.asarray(q), np.asarray(k), np.asarray(v), causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def dense_jax(q, k, v, causal, t=None):
    t = t if t is not None else T
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """The custom-VJP backward kernels (dq, dk/dv) match autodiff through
    the dense formulation (reference parity: training usability of the
    flagship kernel)."""
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(2, 128, 2, 32), jnp.float32)
               for _ in range(3))
    dout = jnp.asarray(rng.randn(2, 128, 2, 32), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True, block_q=64,
                                       block_k=64) * dout)

    def loss_dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
        if causal:
            mask = jnp.tril(jnp.ones((128, 128), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd",
                                  jax.nn.softmax(s, -1), v) * dout)

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-3, atol=2e-4)


def test_flash_lse_value_and_gradient():
    """return_lse gives log-sum-exp rows, and the lse output itself is
    differentiable (needed by ring-attention merges)."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
               for _ in range(3))
    _, lse = flash_attention(q, k, v, interpret=True, return_lse=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
    want = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-4, atol=1e-5)

    wl = jnp.asarray(rng.randn(2, 2, 64), jnp.float32)
    g1 = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, interpret=True, return_lse=True)[1] * wl),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(jax.scipy.special.logsumexp(
        jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32), axis=-1) * wl),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_global_offsets_shift_causal_mask():
    """q_offset/k_offset move the causal mask to global coordinates — the
    contract ring attention relies on for sequence-sharded blocks."""
    rng = np.random.RandomState(4)
    k, v = (jnp.asarray(rng.randn(2, 128, 2, 32), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          q_offset=64.0, k_offset=0.0)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32)
    qp = 64 + jnp.arange(64)[:, None]
    kp = jnp.arange(128)[None, :]
    s = jnp.where((qp >= kp)[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    # a block entirely in the future produces lse=-inf and zero output,
    # making downstream merges a no-op
    o, lse = flash_attention(q, k, v, causal=True, interpret=True,
                             q_offset=-1000.0, return_lse=True)
    assert np.all(np.asarray(lse) < -1e29)
    np.testing.assert_array_equal(np.asarray(o), 0)


# ---------------------------------------------------------------------------
# Interior, diagonal and skipped tiles, both orientations of the score tile


def _dense_causal(q, k, v, q_off=0, k_off=0, window=None):
    """(o, lse) of causal attention at global positions, in float32; a row
    that sees no key gives o = 0 and lse = NEG_INF, as the kernel does.
    With a ``window`` the explicit mask ``0 <= q_pos - k_pos < window``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    ahead = (q_off + jnp.arange(q.shape[1])[:, None]
             - k_off - jnp.arange(k.shape[1])[None, :])
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    live = seen.any(-1)[None, None, :]
    s = jnp.where(seen[None, None], s, -1e30)
    p = jnp.where(live[..., None], jax.nn.softmax(s, -1), 0.0)
    lse = jnp.where(live, jax.scipy.special.logsumexp(s, axis=-1), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def _qkv(seed, shape, dtype):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


def _assert_close(got, want, dtype):
    tol = dict(rtol=2e-3, atol=2e-4) if dtype == jnp.float32 else \
        dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_flash_causal_grid_forward_and_grad(blocks, head_dim, dtype):
    """T = 512 at blocks of 128 is a 4 x 4 grid: interior, diagonal and
    skipped tiles all occur, also where block_q != block_k. Heads of 64
    take the scale on the operand (a power of two) and sum dk/dv
    transposed, heads of 128 take it on the scores and sum them plain."""
    q, k, v = _qkv(7, (1, 512, 2, head_dim), dtype)
    dout = jnp.asarray(np.random.RandomState(8).randn(*q.shape), dtype)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32) * dout)

    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1])
    dense = lambda q, k, v: _dense_causal(q, k, v)[0]  # noqa: E731
    _assert_close(flash(q, k, v), dense(q, k, v), dtype)
    got = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        _assert_close(g, w, dtype)


# ring attention's three cases, and a shard boundary inside a block
RING_SHARDS = {"before": (512, 0), "across": (256, 256), "after": (0, 512),
               "across_inside_a_block": (96, 0)}


@pytest.mark.parametrize("blocks", [(128, 128), (64, 128)])
@pytest.mark.parametrize("shard", list(RING_SHARDS))
def test_flash_traced_offsets_value_and_grad(shard, blocks):
    """Under jit with traced offsets, a k shard wholly before the q shard
    (every tile interior), across the diagonal and wholly after it (every
    tile skipped): (o, lse) and the gradients, with a cotangent on lse."""
    q, k, v = _qkv(11, (1, 256, 2, 64), jnp.float32)
    rng = np.random.RandomState(12)
    dout = jnp.asarray(rng.randn(*q.shape), jnp.float32)
    dlse = jnp.asarray(rng.randn(1, 2, 256), jnp.float32)

    def loss(attend, q, k, v, q_off, k_off):
        o, lse = attend(q, k, v, q_off, k_off)
        return jnp.sum(o * dout) + jnp.sum(lse * dlse), (o, lse)

    def flash(q, k, v, q_off, k_off):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               block_q=blocks[0], block_k=blocks[1],
                               q_offset=q_off, k_offset=k_off,
                               return_lse=True)

    offsets = [jnp.float32(x) for x in RING_SHARDS[shard]]
    grad = lambda attend: jax.jit(jax.grad(  # noqa: E731
        functools.partial(loss, attend), argnums=(0, 1, 2), has_aux=True))
    got, (o, lse) = grad(flash)(q, k, v, *offsets)
    want, (o_ref, lse_ref) = grad(_dense_causal)(q, k, v, *offsets)
    _assert_close(o, o_ref, jnp.float32)
    _assert_close(lse, lse_ref, jnp.float32)
    for g, w in zip(got, want):
        _assert_close(g, w, jnp.float32)
    if shard == "after":
        assert not np.asarray(o).any() and np.all(np.asarray(lse) < -1e29)
        assert not any(np.asarray(g).any() for g in got)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 64)])
def test_flash_fully_masked_rows_stay_zero(blocks):
    """Rows that see no key (here the first 64, by a negative q offset) sit
    in tiles whose other rows are live: their output and dq are exactly
    zero and they add nothing to dk/dv, with no select over the tile."""
    q, k, v = _qkv(13, (1, 256, 2, 64), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(14).randn(*q.shape),
                       jnp.float32)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * dout)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=True, block_q=blocks[0],
        block_k=blocks[1], q_offset=-64.0)
    dense = lambda q, k, v: _dense_causal(q, k, v, q_off=-64)[0]  # noqa: E731
    o = np.asarray(flash(q, k, v))
    assert not o[:, :64].any() and o[:, 64:].any()
    got = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, dense), argnums=(0, 1, 2))(q, k, v)
    assert not np.asarray(got[0])[:, :64].any()
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        _assert_close(g, w, jnp.float32)


PLANS = [  # tq, tk, block_q, block_k, q_offset, k_offset
    (512, 512, 128, 128, 0, 0), (512, 512, 64, 128, 0, 0),
    (512, 512, 128, 64, 0, 0), (256, 256, 128, 128, 512, 0),
    (256, 256, 128, 128, 0, 512), (256, 256, 64, 128, 256, 256),
    (256, 256, 64, 128, 96, 0), (256, 512, 128, 64, -64, 0),
    (256, 128, 128, 128, 100, 37),
]


@pytest.mark.parametrize("tq,tk,block_q,block_k,q_offset,k_offset", PLANS)
def test_block_plan_matches_brute_force(tq, tk, block_q, block_k, q_offset,
                                        k_offset):
    """A block is interior where the mask is all true, skipped where it is
    all false, diagonal otherwise; together they are the grid."""
    from horovod_tpu.ops.flash_attention import block_plan
    seen = (q_offset + np.arange(tq)[:, None]
            >= k_offset + np.arange(tk)[None, :])
    tiles = seen.reshape(tq // block_q, block_q, tk // block_k, block_k)
    want = {"interior": int(tiles.all((1, 3)).sum()),
            "skipped": int((~tiles.any((1, 3))).sum())}
    want["diagonal"] = tiles.shape[0] * tiles.shape[2] - sum(want.values())
    assert block_plan(tq, tk, block_q, block_k, True, q_offset,
                      k_offset) == want
    assert block_plan(tq, tk, block_q, block_k, False) == {
        "interior": tiles.shape[0] * tiles.shape[2], "diagonal": 0,
        "skipped": 0}


def _block_visits():
    from horovod_tpu.metrics.registry import get_registry
    return {kind: get_registry().counter("hvd_flash_block_visits",
                                         kind=kind).value
            for kind in ("interior", "diagonal", "skipped")}


def test_block_visits_counted_at_trace_time():
    """gpt2s-t8192's shape: 120 / 16 / 120 visits a (batch, head) at blocks
    of 512, recorded when the call is traced, times batch * heads."""
    x = jax.ShapeDtypeStruct((2, 8192, 3, 64), jnp.bfloat16)
    before = _block_visits()
    jax.eval_shape(functools.partial(flash_attention, causal=True,
                                     interpret=True), x, x, x)
    after = _block_visits()
    assert {k: after[k] - before[k] for k in after} == {
        "interior": 6 * 120, "diagonal": 6 * 16, "skipped": 6 * 120}


def test_block_visits_not_counted_for_traced_offsets():
    """Ring attention's offsets are traced: which body a tile takes is
    decided on the chip, and the counter says nothing."""
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    off = jax.ShapeDtypeStruct((), jnp.float32)
    before = _block_visits()
    jax.eval_shape(lambda q, o: flash_attention(
        q, q, q, causal=True, interpret=True, q_offset=o, k_offset=o),
        x, off)
    assert _block_visits() == before


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


def test_flash_bf16_runs():
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 256, 2, 64), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_flash_rejects_degenerate_block_divisor():
    """A prime sequence length above the block size fails with padding
    advice instead of compiling a pathological 1-wide grid. (Lengths at or
    below the block size are always fine: the whole sequence is one
    block.)"""
    q = jnp.zeros((1, 1021, 2, 32), jnp.float32)  # prime
    with pytest.raises(ValueError, match="pad the"):
        flash_attention(q, q, q, interpret=True)
    # sub-block odd length: single block, no error
    small = jnp.zeros((1, 254, 2, 32), jnp.float32)
    out = flash_attention(small, small, small, interpret=True)
    assert out.shape == small.shape


def test_flash_rejects_mask_with_flash_model():
    """EncoderBlock(use_flash=True) refuses an explicit mask — only full
    bidirectional or causal are kernel-supported."""
    import flax.linen as nn
    from horovod_tpu.models.transformer import EncoderBlock

    block = EncoderBlock(hidden=32, heads=4, mlp_dim=64,
                         dtype=jnp.float32, use_flash=True)
    x = jnp.zeros((1, 16, 32), jnp.float32)
    mask = nn.make_causal_mask(jnp.ones((1, 16)))
    with pytest.raises(ValueError, match="mask"):
        block.init(jax.random.key(0), x, mask=mask)


# ---------------------------------------------------------------------------
# The window: 0 <= q_pos - k_pos < W, beside the causal mask


def _dense_window(q, k, v, window, q_off=0, k_off=0):
    return _dense_causal(q, k, v, q_off, k_off, window)


# T = 512 at blocks of 128: below a block, no multiple of it, a multiple,
# one key, and a window that reaches past the sequence (plain causal)
WINDOWS = {"below_a_block": 40, "no_multiple": 200, "a_multiple": 256,
           "one_key": 1, "all_of_it": 512, "past_the_end": 1000}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
@pytest.mark.parametrize("window", list(WINDOWS))
def test_flash_window_forward_and_grad(window, blocks, head_dim):
    """Forward and the three gradients under the window against
    ``xla_attention`` with the same window and against the explicit mask;
    both orientations of the dk/dv sums (heads of 64 and of 128)."""
    from horovod_tpu.ops.flash_attention import xla_attention
    w = WINDOWS[window]
    q, k, v = _qkv(21, (1, 512, 2, head_dim), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(22).randn(*q.shape),
                       jnp.float32)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * dout)

    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1], window=w)
    xla = functools.partial(xla_attention, causal=True, window=w)
    want_o = _dense_window(q, k, v, w)[0]
    _assert_close(flash(q, k, v), want_o, jnp.float32)
    _assert_close(xla(q, k, v), want_o, jnp.float32)
    if w >= 512:  # the window holds every key: the causal mask
        _assert_close(flash(q, k, v), _dense_causal(q, k, v)[0],
                      jnp.float32)
    got = jax.grad(functools.partial(loss, flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, xla), argnums=(0, 1, 2))(q, k, v)
    for g, wanted in zip(got, want):
        _assert_close(g, wanted, jnp.float32)


@pytest.mark.parametrize("shard", list(RING_SHARDS))
def test_flash_window_with_traced_offsets(shard):
    """The window is in global positions, so a sequence shard's offsets
    move it like the causal edge: (o, lse) and gradients under jit with
    traced offsets, with a cotangent on lse. A k shard far enough before
    the q shard lies wholly behind the window: every row is dead."""
    window = 300
    q, k, v = _qkv(23, (1, 256, 2, 64), jnp.float32)
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
        return _dense_window(q, k, v, window, q_off, k_off)

    offsets = [jnp.float32(x) for x in RING_SHARDS[shard]]
    grad = lambda attend: jax.jit(jax.grad(  # noqa: E731
        functools.partial(loss, attend), argnums=(0, 1, 2), has_aux=True))
    got, (o, lse) = grad(flash)(q, k, v, *offsets)
    want, (o_ref, lse_ref) = grad(dense)(q, k, v, *offsets)
    _assert_close(o, o_ref, jnp.float32)
    _assert_close(lse, lse_ref, jnp.float32)
    for g, w in zip(got, want):
        _assert_close(g, w, jnp.float32)
    if shard == "before":  # 512 ahead of every key, window 300
        assert not np.asarray(o)[:, 44:].any()


def test_window_needs_a_causal_mask_and_a_key():
    from horovod_tpu.ops.flash_attention import attention, xla_attention
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
    causal_before, before = _block_visits(), window_visits()
    jax.eval_shape(functools.partial(flash_attention, causal=True,
                                     interpret=True, window=4096), x, x, x)
    after = window_visits()
    assert {k: after[k] - before[k] for k in after} == {
        "interior": 2 * 196, "diagonal": 2 * 32, "edge": 2 * 24,
        "skipped": 2 * 496, "skipped_behind": 2 * 276}
    assert _block_visits() == causal_before


def test_attention_router_honours_the_window_on_both_sides():
    """Below the crossover XLA attention, at it the kernels: the same
    window either way, and neither the causal result."""
    from horovod_tpu.ops import flash_attention as fa
    q, k, v = _qkv(25, (1, 256, 2, 32), jnp.float32)
    want = _dense_window(q, k, v, 100)[0]
    short = fa.attention(q, k, v, causal=True, window=100)
    long = fa.attention(q, k, v, causal=True, window=100, min_flash_seq=256,
                        interpret=True, block_q=64, block_k=64)
    _assert_close(short, want, jnp.float32)
    _assert_close(long, want, jnp.float32)
    assert not np.allclose(np.asarray(short),
                           np.asarray(_dense_causal(q, k, v)[0]), atol=1e-3)


# ---------------------------------------------------------------------------
# Short-sequence auto-routing (ops/flash_attention.attention)


def test_attention_router_short_sequence_takes_xla_path(monkeypatch):
    """Below the crossover the router must return the XLA path's result
    bit-for-bit (same computation, no Pallas kernel involved)."""
    from horovod_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
               for _ in range(3))
    called = {"flash": 0}
    real_flash = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: called.__setitem__(
                            "flash", called["flash"] + 1) or
                        real_flash(*a, **kw))
    out = fa.attention(q, k, v, causal=True)  # 128 < default 1024
    assert called["flash"] == 0
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(fa.xla_attention(q, k, v, causal=True)))


def test_attention_router_long_sequence_takes_flash_path(monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 2, 32), jnp.float32)
               for _ in range(3))
    called = {"flash": 0}
    real_flash = fa.flash_attention
    monkeypatch.setattr(fa, "flash_attention",
                        lambda *a, **kw: called.__setitem__(
                            "flash", called["flash"] + 1) or
                        real_flash(*a, **kw, interpret=True))
    out = fa.attention(q, k, v, causal=False, min_flash_seq=256)
    assert called["flash"] == 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa.xla_attention(q, k, v)),
        rtol=2e-4, atol=2e-5)


def test_attention_router_env_override(monkeypatch):
    from horovod_tpu.ops import flash_attention as fa

    # the ambient env may legitimately set the knob — clear it first
    monkeypatch.delenv("HOROVOD_FLASH_MIN_SEQ", raising=False)
    assert fa.flash_min_seq() == fa.DEFAULT_FLASH_MIN_SEQ
    monkeypatch.setenv("HOROVOD_FLASH_MIN_SEQ", "64")
    assert fa.flash_min_seq() == 64


def test_xla_attention_matches_dense_reference():
    from horovod_tpu.ops.flash_attention import xla_attention

    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(B, T, H, D), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        got = np.asarray(xla_attention(q, k, v, causal=causal))
        want = dense(np.asarray(q), np.asarray(k), np.asarray(v), causal)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="self-attention"):
        xla_attention(q, k[:, :128], v[:, :128], causal=True)


def test_bert_short_seq_uses_router(monkeypatch):
    """BertBase(use_flash=True) at seq 128 must not invoke the Pallas
    kernel (an earlier chip run, no longer on file, had flash 16% slower
    there)."""
    from horovod_tpu.models.transformer import BertEncoder
    from horovod_tpu.ops import flash_attention as fa

    def boom(*a, **kw):
        raise AssertionError("flash kernel must not run at seq 128")

    monkeypatch.setattr(fa, "flash_attention", boom)
    model = BertEncoder(max_len=128, use_flash=True, layers=1, hidden=64,
                        heads=2, mlp_dim=128, vocab=100)
    tokens = jnp.zeros((2, 128), jnp.int32)
    variables = model.init(jax.random.key(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 128, 100)


# ---------------------------------------------------------------------------
# The block mask: the causal edge rounded to blocks of G positions, and the
# two streams of a block-diffusion pass


def _dense_masked(q, k, v, seen):
    """(o, lse) under an explicit boolean mask [Tq, Tk], in float32, key
    heads repeated; a row that sees no key gives o = 0 and lse = NEG_INF."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    live = seen.any(-1)[None, None, :]
    s = jnp.where(seen[None, None], s, -1e30)
    p = jnp.where(live[..., None], jax.nn.softmax(s, -1), 0.0)
    lse = jnp.where(live, jax.scipy.special.logsumexp(s, axis=-1), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def _block_edge(t, group, edge):
    """The mask by hand: block indices compared, not positions."""
    b = np.arange(t) // group
    return jnp.asarray(b[None, :] <= b[:, None] if edge == "le"
                       else b[None, :] < b[:, None])


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
    from horovod_tpu.ops.flash_attention import xla_attention
    q, k, v = _qkv(31, (1, seq, 2, head_dim), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(32).randn(*q.shape),
                       jnp.float32)
    seen = _block_edge(seq, group, edge)

    def loss(attend, q, k, v):
        return jnp.sum(attend(q, k, v) * dout)
    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1],
                              block_mask=(group, edge))
    xla = functools.partial(xla_attention, causal=True,
                            block_mask=(group, edge))
    dense = lambda q, k, v: _dense_masked(q, k, v, seen)[0]  # noqa: E731
    want_o, want_lse = _dense_masked(q, k, v, seen)
    o, lse = flash(q, k, v, return_lse=True)
    _assert_close(o, want_o, jnp.float32)
    _assert_close(lse, want_lse, jnp.float32)
    _assert_close(xla(q, k, v), want_o, jnp.float32)
    if edge == "lt":
        np.testing.assert_array_equal(np.asarray(o[:, :group]), 0)
        assert np.all(np.asarray(lse[..., :group]) < -1e29)
    want = jax.grad(functools.partial(loss, dense), argnums=(0, 1, 2))(q, k, v)
    for attend in (flash, xla):
        got = jax.grad(functools.partial(loss, attend),
                       argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            _assert_close(g, w, jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_block_mask_of_one_is_the_causal_call_bit_for_bit(dtype):
    """``b(k) <= b(q)`` at G = 1 is ``k <= q``: the same kernels' bodies
    under another name, and not a bit of the output or of a gradient
    moves."""
    q, k, v = _qkv(33, (1, 256, 2, 64), dtype)

    def both(**mask):
        attend = functools.partial(flash_attention, causal=True,
                                   interpret=True, block_q=64, block_k=128,
                                   **mask)
        return attend(q, k, v), jax.grad(
            lambda q, k, v: attend(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(jax.tree_util.tree_leaves(both(block_mask=(1, "le"))),
                         jax.tree_util.tree_leaves(both())):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


def test_block_mask_needs_causal_no_window_no_offsets_and_whole_blocks():
    from horovod_tpu.ops.flash_attention import attention, xla_attention
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
    seen = np.asarray(_block_edge(t, group, edge)).reshape(
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
    return _dense_masked(q, k, v,
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
    got, want = attend(q, k, v), dense(q, k, v)
    _assert_close(got, want, jnp.float32)
    own = _dense_masked(q[:, :group], k[:, :group], v[:, :group],
                        jnp.ones((group, group), bool))[0]
    _assert_close(got[:, :group], own, jnp.float32)

    def loss(f, q, k, v):
        return jnp.sum(f(q, k, v) * dout)
    got = jax.grad(functools.partial(loss, attend), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        _assert_close(g, w, jnp.float32)


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
    want_o, want_lse = _dense_masked(
        q, k, v, jnp.asarray(blocks[:, None] == blocks[None, :]))
    o, lse = block_diagonal_attention(q, k, v, 8)
    assert o.shape == q.shape and lse.shape == (2, 4, 32)
    _assert_close(o, want_o, jnp.float32)
    _assert_close(lse, want_lse, jnp.float32)
    with pytest.raises(ValueError, match="blocks of 5"):
        block_diagonal_attention(q, k, v, 5)


# ---------------------------------------------------------------------------
# Grouped-query attention: k and v at their own heads, a key head read once
# for its group, nothing repeated in HBM


def _calls(group):
    from horovod_tpu.metrics.registry import get_registry
    return get_registry().counter("hvd_flash_calls_total",
                                  kv_group=str(group)).value


# the mask's arguments, the shard's offsets (traced, under jit) or None,
# the dtype
GROUPED_CASES = {
    "causal": (dict(), None, jnp.float32),
    "causal_bf16": (dict(), None, jnp.bfloat16),
    "causal_offsets": (dict(), (96.0, 0.0), jnp.float32),
    "window": (dict(window=100), None, jnp.float32),
    "window_offsets": (dict(window=300), (256.0, 256.0), jnp.float32),
    "block_le": (dict(block_mask=(4, "le")), None, jnp.float32),
    "block_lt": (dict(block_mask=(4, "lt")), None, jnp.float32),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group,kv_heads", [(1, 2), (4, 2), (7, 2), (16, 1)],
                         ids=["mha", "group4", "group7", "group16"])
def test_flash_grouped_heads_match_repeated_keys(group, kv_heads, head_dim,
                                                 case):
    """``kv_heads`` key heads for ``group`` times as many query heads, both
    head widths (the two orientations of the dk/dv sums): (o, lse) and dq of
    the call on k and v at their own heads equal, bit for bit, those of the
    same call on ``_repeat_kv``-repeated keys (the program every call was
    before), a cotangent on lse too; dk and dv are that call's, one a query
    head, summed over each group in float32 (the repeat's own gradient would
    add them up in the arrays' dtype); and, where no shard offsets are in
    play, all agree with ``xla_attention``'s."""
    from horovod_tpu.ops import flash_attention as fa
    mask, offsets, dtype = GROUPED_CASES[case]
    heads, seq, d = group * kv_heads, 256, head_dim
    rng = np.random.RandomState(41)
    q = jnp.asarray(rng.randn(2, seq, heads, d), dtype)
    k, v = (jnp.asarray(rng.randn(2, seq, kv_heads, d), dtype)
            for _ in range(2))
    dout = jnp.asarray(rng.randn(*q.shape), dtype)
    dlse = jnp.asarray(rng.randn(2, heads, seq), jnp.float32)
    offsets = tuple(jnp.float32(x) for x in offsets or ())

    def loss(q, k, v, *offs):
        o, lse = flash_attention(
            q, k, v, causal=True, interpret=True, block_q=128, block_k=64,
            return_lse=True, **dict(zip(("q_offset", "k_offset"), offs)),
            **mask)
        # a dead row's lse is NEG_INF: keep it out of the sum's rounding
        live = jnp.where(lse > -1e29, lse, 0.0)
        return (jnp.sum(o.astype(jnp.float32) * dout)
                + jnp.sum(live * dlse)), (o, lse)

    def run(k, v):
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(
            q, k, v, *offsets)

    def summed(x):  # a query head's dk or dv: float32 over each group
        return jnp.sum(x.astype(jnp.float32).reshape(
            2, seq, kv_heads, group, d), axis=3).astype(dtype)

    before = _calls(group)
    got, (o, lse) = run(k, v)
    assert _calls(group) == before + 1
    want, (o_rep, lse_rep) = run(*fa._repeat_kv(q, k, v))  # kv_group="1"
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_rep))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_rep))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w, like in zip(got[1:], want[1:], (k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        _assert_close(g, summed(w), dtype)
    if offsets or dtype != jnp.float32:
        return

    def xla_loss(q, k, v):
        o = fa.xla_attention(q, *fa._repeat_kv(q, k, v), causal=True, **mask)
        return jnp.sum(o.astype(jnp.float32) * dout), o

    def flash_loss(q, k, v):  # without the lse's cotangent, like XLA's
        o = flash_attention(q, k, v, causal=True, interpret=True,
                            block_q=128, block_k=64, **mask)
        return jnp.sum(o.astype(jnp.float32) * dout), o
    want, o_xla = jax.grad(xla_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    got, o = jax.grad(flash_loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    _assert_close(o, o_xla, dtype)
    for g, w in zip(got, want):
        _assert_close(g, w, dtype)


def test_flash_refuses_heads_that_are_no_groups():
    q = jnp.zeros((1, 128, 6, 64))
    for kv_heads, v_heads in ((4, 4), (2, 3)):
        with pytest.raises(ValueError, match="no multiple"):
            flash_attention(q, jnp.zeros((1, 128, kv_heads, 64)),
                            jnp.zeros((1, 128, v_heads, 64)), interpret=True)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, but
    for the kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_attention_hands_the_kernels_k_and_v_at_their_own_heads(head_dim):
    """The mechanism itself, SmallThinker's heads (4 key heads for 28): in
    the gradient through ``attention`` all three kernel calls take k and v
    as ``[B * 4, T, D]`` beside q's ``[B * 28, T, D]``, the dk/dv call
    writes ``[B * 28, T, D]``, nothing writes an array the size of k out
    once a query head, no transpose is of a k or v repeated to q's size
    (those of q's size are q, do, o and the results'), and the call counts
    under ``kv_group="7"``."""
    from horovod_tpu.ops.flash_attention import attention
    seq, heads, kv_heads = 256, 28, 4
    q = jnp.zeros((1, seq, heads, head_dim), jnp.bfloat16)
    k = v = jnp.zeros((1, seq, kv_heads, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        o = attention(q, k, v, causal=True, min_flash_seq=seq, interpret=True)
        return jnp.sum(o.astype(jnp.float32))
    before = _calls(7)
    eqns = list(_eqns(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert _calls(7) == before + 1
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    for call in calls:  # q_off, k_off, q, k, v, ...
        assert [x.aval.shape for x in call.invars[2:5]] == [
            (heads, seq, head_dim)] + 2 * [(kv_heads, seq, head_dim)]
        # a grid row finds its key head by a division; everything else by
        # the index maps a call has always had, which compute nothing
        assert [len(m.index_map_jaxpr.jaxpr.eqns) > 0 for m in
                call.params["grid_mapping"].block_mappings[2:5]] == \
            [False, True, True]
    assert [x.aval.shape for x in calls[2].outvars] == \
        2 * [(heads, seq, head_dim)]

    def sized(e, n):
        return e.invars and getattr(e.invars[0].aval, "size", 0) == n
    assert not [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                and sized(e, k.size)]
    # q forward; q, do, o backward; o, dq back: and none of k's or v's
    assert len([e for e in eqns if e.primitive.name == "transpose"
                and sized(e, q.size)]) == 6


@pytest.mark.parametrize("head_dim", [64, 128])
def test_equal_heads_call_the_kernels_as_they_always_were(head_dim):
    """With as many key heads as query heads (the GPT cells, OLMoE) no index
    map of any of the three calls computes anything: block ``(bh, i, 0)`` or
    ``(bh, 0, 0)``, the program those cells had; and no sum follows the
    dk/dv call."""
    x = jnp.zeros((2, 256, 3, head_dim), jnp.bfloat16)
    eqns = list(_eqns(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    for call in calls:
        assert not any(m.index_map_jaxpr.jaxpr.eqns for m in
                       call.params["grid_mapping"].block_mappings)
    after = eqns[eqns.index(calls[2]) + 1:]
    assert "reduce_sum" not in [e.primitive.name for e in after]
