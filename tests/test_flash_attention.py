"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

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
