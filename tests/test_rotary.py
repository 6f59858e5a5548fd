"""``ops/rotary.rotary``: one rotary operator with its own backward, held to
the plain ``jax.numpy`` rotation the five models used to share
(``models/olmoe.rotary`` until PR 51), which is written out here and nowhere
else: values to float32 rounding, the gradient of a scalar of the output to
the reference's autodiff gradient, over both head widths the models have
(128: a whole 128-lane register; 64: half of one), one, four and 32 heads,
float32 and bf16, and a length that is no whole tile of the kernel."""

import functools
import re

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import rotary as rot
from horovod_tpu.ops.rotary import rotary

THETA = 1e4
# one tile and a 44-row rest; a tile is 256 rows
LENGTH = rot.TILE + 44
SHAPES = [(batch, LENGTH, heads, width) for batch, heads, width in (
    (1, 1, 128), (2, 4, 128), (1, 32, 128),
    (1, 1, 64), (2, 4, 64), (1, 32, 64))]
DTYPES = (jnp.float32, jnp.bfloat16)


def reference(x, theta):
    """Rotate-half rotary embedding of [B, T, H, D] at positions 0..T-1,
    angles and rotation in float32: split, four products, join."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _close(got, want, dtype):
    """Equal to float32 rounding: the two writings add the same two float32
    products, fused or not, so a float32 result may differ in its last
    place and a bf16 one where that tips its rounding."""
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)
        assert (got != want).mean() < 1e-3


@functools.lru_cache(maxsize=None)
def _arrays(shape, dtype):
    keys = jax.random.split(jax.random.key(sum(shape)), 2)
    return (jax.random.normal(keys[0], shape, dtype),
            jax.random.normal(keys[1], shape, jnp.float32))


def _out_and_grad(fn, x, weight):
    def scalar(x):
        return jnp.sum(fn(x, THETA).astype(jnp.float32) * weight)
    return jax.jit(lambda x: (fn(x, THETA), jax.grad(scalar)(x)))(x)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_values_and_gradient_are_the_plain_rotation_s(shape, dtype):
    x, weight = _arrays(shape, dtype)
    out, grad = _out_and_grad(rotary, x, weight)
    want, want_grad = _out_and_grad(reference, x, weight)
    assert out.dtype == grad.dtype == dtype and out.shape == shape
    _close(out, want, dtype)
    _close(grad, want_grad, dtype)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_position_0_is_the_identity(shape):
    x, _ = _arrays(shape, jnp.bfloat16)
    out = jax.jit(functools.partial(rotary, theta=THETA))(x)
    assert jnp.array_equal(out[:, 0], x[:, 0])
    assert not jnp.array_equal(out[:, 1], x[:, 1])


@pytest.mark.parametrize("width", (128, 64))
def test_q_and_k_of_one_call_are_each_array_s_own_rotation(width):
    """Arrays that share batch, positions and head width go through one
    call (at 128 one kernel call); each comes back as alone, in its
    place."""
    q, weight = _arrays((2, LENGTH, 4, width), jnp.bfloat16)
    k = q[:, :, :2] * 0.5

    def both(q, k):
        out = rotary((q, k), THETA)
        return out, jax.grad(lambda q, k: sum(
            jnp.sum(o.astype(jnp.float32) * weight[:, :, :o.shape[2]])
            for o in rotary((q, k), THETA)), argnums=(0, 1))(q, k)
    together = zip(*jax.jit(both)(q, k))
    for (out, grad), x, w in zip(together, (q, k), (weight, weight[:, :, :2])):
        alone, alone_grad = _out_and_grad(rotary, x, w)
        assert jnp.array_equal(out, alone)
        assert jnp.array_equal(grad, alone_grad)
    with pytest.raises(ValueError, match="share batch, positions"):
        rotary((q, k[:, :-1]), THETA)
    with pytest.raises(ValueError, match="share batch, positions"):
        rotary((q, k[..., :width // 2]), THETA)


@pytest.mark.parametrize("width", (64, 16))
def test_narrow_heads_swap_their_halves_by_a_permutation_s_product(width):
    """Heads narrower than a 128-lane register: the same formula under the
    same ``custom_vjp``, the halves swapped by a product with a ``D x D``
    permutation (one 1 a column: it moves values and rounds nothing), so no
    half is cut or joined, forward or backward, and no kernel is called."""
    x, weight = _arrays((2, LENGTH, 4, width), jnp.bfloat16)
    for got, want in zip(_out_and_grad(rotary, x, weight),
                         _out_and_grad(reference, x, weight)):
        _close(got, want, jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        rotary(x, THETA).astype(jnp.float32) * weight)))(x))
    assert "pallas_call" not in jaxpr
    # the one join is the permutation's own, a [D, D] constant
    assert all(f"[{width},{width}] = concatenate" in line
               for line in jaxpr.splitlines() if "concatenate" in line)
    assert jaxpr.count("dot_general") == 2  # forward, backward


@pytest.mark.parametrize("heads,width", [(1, 128), (4, 128), (4, 64)])
def test_backward_is_the_operator_s_own_and_keeps_nothing_of_x(heads, width,
                                                               capsys):
    """The backward is the rotation by the negated angles: it needs no
    residual, so nothing of ``x`` (no float32 copy, no half) is saved for
    it, under ``jax.checkpoint`` with ``policy=None`` or without."""
    x, weight = _arrays((1, LENGTH, heads, width), jnp.bfloat16)

    def scalar(x):
        return jnp.sum(rotary(x, THETA).astype(jnp.float32) * weight)
    jax.ad_checkpoint.print_saved_residuals(scalar, x)
    kept = re.findall(rf"(\w+)\[1,{LENGTH},\d+,\d+\]",
                      capsys.readouterr().out)
    # the weight of the scalar is the one array of x's length it keeps
    assert kept == ["f32"]
    plain = jax.jit(jax.grad(scalar))(x)
    again = jax.jit(jax.grad(jax.checkpoint(scalar, policy=None)))(x)
    _close(plain, again, jnp.bfloat16)
    # turned forward by a and the cotangent by -a: a rotation's transpose
    wide = x.astype(jnp.float32)
    turn = functools.partial(rotary, theta=THETA)
    back = jax.jit(lambda x: jax.vjp(turn, x)[1](turn(x))[0])(wide)
    np.testing.assert_allclose(np.asarray(back), np.asarray(wide),
                               atol=2e-5, rtol=1e-5)


def test_traces_once_under_jit():
    """Called twice at one shape the jitted operator is traced once, and a
    kernel's call lowered once a shape: the second call finds the first."""
    x, _ = _arrays((1, LENGTH, 4, 128), jnp.bfloat16)
    traces = []

    @jax.jit
    def turned(x):
        traces.append(1)
        return rotary(x, THETA)
    first, second = turned(x), turned(x + 1)
    assert len(traces) == 1 and first.shape == second.shape


@pytest.mark.parametrize("shape,counted", [
    ((1, 64, 4, 128), 1), ((2, 64, 1, 128), 1), ((1, 64, 2, 64), 0),
    ((1, 64, 2, 16), 0)], ids=str)
def test_counter_counts_the_kernel_s_calls_by_head_width(shape, counted):
    """``hvd_rotary_calls_total{head_dim}``: a kernel call traced, which is
    the forward pass of heads that fill whole registers; the backward and
    narrower heads take the ``jax.numpy`` body and count none."""
    from horovod_tpu.metrics.registry import get_registry
    counter = get_registry().counter(
        "hvd_rotary_calls_total",
        "rotary kernel calls traced (the forward pass of one call site), "
        "by head width", head_dim=str(shape[-1]))
    x = jnp.ones(shape, jnp.bfloat16)
    before = counter.value
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(
        rotary(x, THETA).astype(jnp.float32))))(x)
    assert counter.value - before == counted
    assert ("pallas_call" in str(jaxpr)) == bool(counted)
