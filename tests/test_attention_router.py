"""Short-sequence auto-routing (``ops/flash_attention.attention``): XLA dot
attention below the crossover (``HOROVOD_FLASH_MIN_SEQ``), the flash kernels
at it, the same mask either way; and ``xla_attention`` against the dense
reference."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import (B, D, H, T, assert_close, dense, dense_causal,
                         dense_window, qkv)


def test_attention_router_honours_the_window_on_both_sides():
    """Below the crossover XLA attention, at it the kernels: the same
    window either way, and neither the causal result."""
    from horovod_tpu.ops import flash_attention as fa
    q, k, v = qkv(25, (1, 256, 2, 32), jnp.float32)
    want = dense_window(q, k, v, 100)[0]
    short = fa.attention(q, k, v, causal=True, window=100)
    long = fa.attention(q, k, v, causal=True, window=100, min_flash_seq=256,
                        interpret=True, block_q=64, block_k=64)
    assert_close(short, want, jnp.float32)
    assert_close(long, want, jnp.float32)
    assert not np.allclose(np.asarray(short),
                           np.asarray(dense_causal(q, k, v)[0]), atol=1e-3)


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
    kernel: 128 is under the router's threshold."""
    from horovod_tpu.models.transformer import BertEncoder
    from horovod_tpu.ops import flash_attention as fa

    def boom(*a, **kw):
        raise AssertionError("flash kernel must not run at seq 128")

    monkeypatch.setattr(fa, "flash_attention", boom)
    model = BertEncoder(max_len=128, use_flash=True, layers=1, hidden=64,
                        heads=2, mlp_dim=128, vocab=100)
    tokens = jnp.zeros((2, 128), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    logits = jax.jit(model.apply)(variables, tokens)
    assert logits.shape == (2, 128, 100)
