"""``ops/ssd.ssd_chunked`` against the recurrence it stands for, position by
position in plain float32 (``lax.scan`` over time): outputs,
the gradients of every input, the carried states; and what the float32
policy of the running sums buys under bf16 inputs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.ops import ssd

BATCH, T, H, P, G, N = 2, 64, 4, 8, 2, 16


def recurrence(x, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t,
    one position a step. Returns (y, the state after every position
    [B, T, H, P, N])."""
    h, g = x.shape[2], B.shape[2]
    Bh, Ch = (jnp.repeat(v, h // g, axis=2) for v in (B, C))  # [B, T, H, N]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * A)[..., None, None] * state + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t)
                       + D[:, None] * x_t, state)
    zeros = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, (y, states) = jax.lax.scan(
        step, zeros, tuple(v.swapaxes(0, 1) for v in (x, dt, Bh, Ch)))
    return y.swapaxes(0, 1), states.swapaxes(0, 1)


def inputs(seed, dtype=jnp.float32, dt_scale=1.0):
    rng = np.random.RandomState(seed)
    x = rng.randn(BATCH, T, H, P)
    dt = np.log1p(np.exp(rng.randn(BATCH, T, H) - 1.0)) * dt_scale
    A = -np.exp(rng.uniform(0.0, np.log(4.0), H))
    B, C = rng.randn(2, BATCH, T, G, N) * 0.5
    D = rng.randn(H)
    cast = {"x": dtype, "dt": jnp.float32, "A": jnp.float32, "B": dtype,
            "C": dtype, "D": jnp.float32}
    return {k: jnp.asarray(v, cast[k]) for k, v in
            dict(x=x, dt=dt, A=A, B=B, C=C, D=D).items()}


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("chunk", [8, 32], ids=["chunk8", "chunk32"])
def test_outputs_and_states_match_the_recurrence(chunk):
    """Four heads share two groups of B and C; the state at each chunk's
    end is the recurrence's state at that position."""
    args = inputs(0)
    y, ends = jax.jit(lambda a: ssd.ssd_chunked(**a, chunk=chunk))(args)
    want_y, want_states = recurrence(**args)
    assert y.shape == (BATCH, T, H, P) and y.dtype == jnp.float32
    assert ends.shape == (BATCH, T // chunk, H, P, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ends), np.asarray(want_states[:, chunk - 1::chunk]),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 32], ids=["chunk8", "chunk32"])
def test_gradients_of_every_input_match_the_recurrence(chunk):
    args = inputs(1)
    weight = jnp.asarray(np.random.RandomState(2).randn(BATCH, T, H, P),
                         jnp.float32)

    def loss(scan, a):
        return jnp.sum(jnp.tanh(scan(**a)[0]) * weight)
    got = jax.jit(jax.grad(lambda a: loss(
        lambda **kw: ssd.ssd_chunked(**kw, chunk=chunk), a)))(args)
    want = jax.grad(lambda a: loss(recurrence, a))(args)
    for name in ("x", "dt", "A", "B", "C", "D"):
        assert float(jnp.abs(want[name]).sum()) > 0
        assert relative_l2(got[name], want[name]) < 2e-5, name


def test_long_steps_never_overflow():
    """dt A of -300 a position: every decay the chunk forms is exp of a
    masked, non-positive difference, so nothing overflows and the result is
    the recurrence's (each state all but forgotten by the next position)."""
    args = inputs(3, dt_scale=400.0)
    y, ends = ssd.ssd_chunked(**args, chunk=16)
    want_y, _ = recurrence(**args)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(ends)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-5)
    grads = jax.grad(lambda a: jnp.sum(ssd.ssd_chunked(**a, chunk=16)[0]))(
        args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


# Under the policy (bf16 x, B, C and matmul inputs; float32 dt, A, running
# sums, decays and carried state) the output differs from the float32
# recurrence by the rounding of its matmul inputs: 2**-9 relative a value,
# incoherent over the sum: 1.7e-3 to 2.2e-3 over seeds 4-8 here. A running
# sum kept in bf16 loses 2**-9 of a sum that reaches -40 over a chunk of 64,
# errors of up to 0.1 in an exponent: 1.2e-2 to 3.7e-2 over the same seeds.
BF16_POLICY_REL_L2 = 4e-3


def test_bf16_inputs_hold_the_policy_and_a_bf16_running_sum_fails_it(
        monkeypatch):
    args = inputs(4, jnp.bfloat16)
    exact = {k: v.astype(jnp.float32) for k, v in args.items()}
    want, _ = recurrence(**exact)
    y, ends = ssd.ssd_chunked(**args, chunk=64)
    assert y.dtype == jnp.bfloat16 and ends.dtype == jnp.float32
    held = relative_l2(y.astype(jnp.float32), want)
    assert held < BF16_POLICY_REL_L2, held

    monkeypatch.setattr(
        ssd, "_running_sum_last",
        lambda a: jnp.cumsum(a.astype(jnp.bfloat16), axis=-1)
        .astype(jnp.float32))
    y, _ = ssd.ssd_chunked(**args, chunk=64)
    broken = relative_l2(y.astype(jnp.float32), want)
    assert broken > 2 * BF16_POLICY_REL_L2, broken


def test_a_length_that_is_no_multiple_of_the_chunk_is_an_error():
    args = inputs(5)
    with pytest.raises(ValueError, match="not a multiple"):
        ssd.ssd_chunked(**args, chunk=48)
    with pytest.raises(ValueError, match="cannot share"):
        ssd.ssd_chunked(**{**args, "B": args["B"][:, :, :1].repeat(3, 2),
                           "C": args["C"][:, :, :1].repeat(3, 2)}, chunk=8)


def test_chunks_are_counted_at_trace_time():
    counter = get_registry().counter(
        "hvd_ssd_chunks_total",
        "chunks of the state-space-dual scan traced (chunks x heads x "
        "batch)")
    before = counter.value
    jax.jit(lambda a: ssd.ssd_chunked(**a, chunk=16)).lower(inputs(6))
    assert counter.value - before == BATCH * H * (T // 16)
