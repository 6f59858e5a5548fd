"""Pallas flash attention vs dense reference (interpret mode on CPU): the
causal kernels and their block plan. The window is ``test_flash_window.py``'s,
the block mask and the two streams ``test_flash_block_mask.py``'s, grouped
heads ``test_flash_grouped_heads.py``'s, the router
``test_attention_router.py``'s; ``flash_cases.py`` holds what they share."""

import collections
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import (B, D, H, RING_SHARDS, T, assert_close, block_visits,
                         dense, dense_causal, out_and_grads, qkv)
from horovod_tpu.ops.flash_attention import flash_attention


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

    got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
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
    g1 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, interpret=True, return_lse=True)[1] * wl),
        argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(jax.scipy.special.logsumexp(
        jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(32), axis=-1) * wl),
        argnums=(0, 1, 2)))(q, k, v)
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


@functools.lru_cache(maxsize=None)
def _causal_grid_case(head_dim, dtype):
    """The inputs of a (head width, dtype) of the grid below and what every
    tile size is held to there: the dense output and gradients."""
    q, k, v = qkv(7, (1, 512, 2, head_dim), dtype)
    dout = jnp.asarray(np.random.RandomState(8).randn(*q.shape), dtype)
    want = out_and_grads(lambda q, k, v: dense_causal(q, k, v)[0],
                         q, k, v, dout)
    return (q, k, v), dout, want


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
def test_flash_causal_grid_forward_and_grad(blocks, head_dim, dtype):
    """T = 512 at blocks of 128 is a 4 x 4 grid: interior, diagonal and
    skipped tiles all occur, also where block_q != block_k. Heads of 64
    take the scale on the operand (a power of two) and sum dk/dv
    transposed, heads of 128 take it on the scores and sum them plain."""
    qkv_, dout, (want_o, want) = _causal_grid_case(head_dim, dtype)
    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=blocks[0], block_k=blocks[1])
    o, got = out_and_grads(flash, *qkv_, dout)
    assert_close(o, want_o, dtype)
    for g, w in zip(got, want):
        assert g.dtype == dtype
        assert_close(g, w, dtype)




@pytest.mark.parametrize("blocks", [(128, 128), (64, 128)])
@pytest.mark.parametrize("shard", list(RING_SHARDS))
def test_flash_traced_offsets_value_and_grad(shard, blocks):
    """Under jit with traced offsets, a k shard wholly before the q shard
    (every tile interior), across the diagonal and wholly after it (every
    tile skipped): (o, lse) and the gradients, with a cotangent on lse."""
    q, k, v = qkv(11, (1, 256, 2, 64), jnp.float32)
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
    want, (o_ref, lse_ref) = grad(dense_causal)(q, k, v, *offsets)
    assert_close(o, o_ref, jnp.float32)
    assert_close(lse, lse_ref, jnp.float32)
    for g, w in zip(got, want):
        assert_close(g, w, jnp.float32)
    if shard == "after":
        assert not np.asarray(o).any() and np.all(np.asarray(lse) < -1e29)
        assert not any(np.asarray(g).any() for g in got)


@pytest.mark.parametrize("blocks", [(128, 128), (128, 64)])
def test_flash_fully_masked_rows_stay_zero(blocks):
    """Rows that see no key (here the first 64, by a negative q offset) sit
    in tiles whose other rows are live: their output and dq are exactly
    zero and they add nothing to dk/dv, with no select over the tile."""
    q, k, v = qkv(13, (1, 256, 2, 64), jnp.float32)
    dout = jnp.asarray(np.random.RandomState(14).randn(*q.shape),
                       jnp.float32)

    flash = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=True, block_q=blocks[0],
        block_k=blocks[1], q_offset=-64.0)
    dense = lambda q, k, v: dense_causal(q, k, v, q_off=-64)[0]  # noqa: E731
    o, got = out_and_grads(flash, q, k, v, dout)
    o = np.asarray(o)
    assert not o[:, :64].any() and o[:, 64:].any()
    _, want = out_and_grads(dense, q, k, v, dout)
    assert not np.asarray(got[0])[:, :64].any()
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        assert_close(g, w, jnp.float32)


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


def block_visits():
    from horovod_tpu.metrics.registry import get_registry
    return {kind: get_registry().counter("hvd_flash_block_visits",
                                         kind=kind).value
            for kind in ("interior", "diagonal", "skipped")}


def test_block_visits_counted_at_trace_time():
    """gpt2s-t8192's shape: 120 / 16 / 120 visits a (batch, head) at blocks
    of 512, recorded when the call is traced, times batch * heads."""
    x = jax.ShapeDtypeStruct((2, 8192, 3, 64), jnp.bfloat16)
    before = block_visits()
    jax.eval_shape(functools.partial(flash_attention, causal=True,
                                     interpret=True), x, x, x)
    after = block_visits()
    assert {k: after[k] - before[k] for k in after} == {
        "interior": 6 * 120, "diagonal": 6 * 16, "skipped": 6 * 120}


def test_block_visits_not_counted_for_traced_offsets():
    """Ring attention's offsets are traced: which body a tile takes is
    decided on the chip, and the counter says nothing."""
    x = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.float32)
    off = jax.ShapeDtypeStruct((), jnp.float32)
    before = block_visits()
    jax.eval_shape(lambda q, o: flash_attention(
        q, q, q, causal=True, interpret=True, q_offset=o, k_offset=o),
        x, off)
    assert block_visits() == before


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


def test_a_kernel_body_is_traced_once_a_signature(monkeypatch):
    """Two layers' calls of one signature in one program, forward and
    gradient, enter each kernel's body once, and a second program of the
    process not again: the calls proper are under ``jax.jit``
    (``ops/kernel_call.py``). Another signature (a window) once more. Where
    ``interpret`` is left to the platform lowered for, both branches of the
    rule are traced, once each."""
    from horovod_tpu.ops import flash_attention as fa
    entered = collections.Counter()
    for name in ("_fwd_body", "_bwd_dq_body", "_bwd_dkv_body"):
        def counted(*refs, _name=name, _body=getattr(fa, name), **static):
            entered[_name] += 1
            _body(*refs, **static)
        monkeypatch.setattr(fa, name, counted)

    def trace(x, **how):
        def two_layers(q, k, v):
            attend = functools.partial(flash_attention, causal=True, **how)
            return jnp.sum(attend(attend(q, k, v), k, v))
        jax.make_jaxpr(jax.grad(two_layers, argnums=(0, 1, 2)))(x, x, x)

    x = jnp.zeros((1, 384, 3, 32))  # a shape no other test of this file has
    trace(x, interpret=True)
    assert entered == dict.fromkeys(
        ("_fwd_body", "_bwd_dq_body", "_bwd_dkv_body"), 1)
    trace(x, interpret=True)
    assert set(entered.values()) == {1}
    trace(x, interpret=True, window=100)
    assert set(entered.values()) == {2}
    trace(jnp.zeros((1, 384, 5, 32)))  # Mosaic's branch and interpret mode's
    assert set(entered.values()) == {4}
