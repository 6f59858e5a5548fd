"""``ops/ssd``'s two writings of the chunked scan, ``ssd_chunked`` (plain
``jax.numpy``) and ``ssd_scan`` (the Pallas kernels, here in interpret
mode), against the recurrence they stand for, position by position in plain
float32 (``lax.scan`` over time), and against each other: outputs, the
gradients of every input, the carried states; and what the float32 policy of
the running sums buys under bf16 inputs."""

import functools

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


def chunked(chunk, **args):
    return ssd.ssd_chunked(**args, chunk=chunk)


def kernels(chunk, **args):
    return ssd.ssd_scan(**args, chunk=chunk, return_states=True)


PATHS = pytest.mark.parametrize("scan", [chunked, kernels],
                                ids=["chunked", "kernels"])
CHUNKS = pytest.mark.parametrize("chunk", [8, 32], ids=["chunk8", "chunk32"])
INPUTS = ("x", "dt", "A", "B", "C", "D")


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@PATHS
@CHUNKS
def test_outputs_and_states_match_the_recurrence(chunk, scan):
    """Four heads share two groups of B and C; the state at each chunk's
    end is the recurrence's state at that position."""
    args = inputs(0)
    y, ends = jax.jit(lambda a: scan(chunk, **a))(args)
    want_y, want_states = recurrence(**args)
    assert y.shape == (BATCH, T, H, P) and y.dtype == jnp.float32
    assert ends.shape == (BATCH, T // chunk, H, P, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ends), np.asarray(want_states[:, chunk - 1::chunk]),
        rtol=2e-4, atol=2e-5)


@functools.lru_cache(maxsize=None)
def gradients(scan, chunk=None):
    """The gradient of one scalar of ``y`` in every input, by ``scan`` (the
    recurrence where ``chunk`` is None): computed once, read a case an
    input."""
    args = inputs(1)
    weight = jnp.asarray(np.random.RandomState(2).randn(BATCH, T, H, P),
                         jnp.float32)
    run = scan if chunk is None else functools.partial(scan, chunk)
    return jax.jit(jax.grad(lambda a: jnp.sum(
        jnp.tanh(run(**a)[0]) * weight)))(args)


@pytest.mark.parametrize("name", INPUTS)
@PATHS
@CHUNKS
def test_gradient_matches_the_recurrence(chunk, scan, name):
    want = gradients(recurrence)[name]
    assert float(jnp.abs(want).sum()) > 0
    assert relative_l2(gradients(scan, chunk)[name], want) < 2e-5


@pytest.mark.parametrize("name", INPUTS)
@CHUNKS
def test_kernels_gradient_matches_the_chunked_reference(chunk, name):
    """The kernels' hand-written transpose against autodiff through the
    einsums of what they are held to."""
    assert relative_l2(gradients(kernels, chunk)[name],
                       gradients(chunked, chunk)[name]) < 2e-5


@CHUNKS
def test_kernels_match_the_chunked_reference(chunk):
    args = inputs(7)
    y, ends = jax.jit(lambda a: kernels(chunk, **a))(args)
    want_y, want_ends = chunked(chunk, **args)
    assert y.shape == want_y.shape and ends.shape == want_ends.shape
    assert y.dtype == want_y.dtype and ends.dtype == want_ends.dtype
    assert relative_l2(y, want_y) < 1e-6
    assert relative_l2(ends, want_ends) < 1e-6


def test_kernels_hold_no_gradient_through_the_returned_states():
    args = inputs(8)
    grads = jax.jit(jax.grad(lambda a: jnp.sum(kernels(8, **a)[1])))(args)
    assert all(not np.asarray(g).any() for g in grads.values())


@PATHS
def test_long_steps_never_overflow(scan):
    """dt A of -300 a position: every decay the chunk forms is exp of a
    masked, non-positive difference, so nothing overflows and the result is
    the recurrence's (each state all but forgotten by the next position)."""
    args = inputs(3, dt_scale=400.0)
    y, ends = jax.jit(lambda a: scan(16, **a))(args)
    want_y, _ = recurrence(**args)
    assert np.isfinite(np.asarray(y)).all()
    assert np.isfinite(np.asarray(ends)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=2e-4, atol=2e-5)
    grads = jax.jit(jax.grad(lambda a: jnp.sum(scan(16, **a)[0])))(args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())


# Under the policy (bf16 x, B, C and matmul inputs; float32 dt, A, running
# sums, decays and carried state) the output differs from the float32
# recurrence by the rounding of its matmul inputs: 2**-9 relative a value,
# incoherent over the sum: 1.7e-3 to 2.2e-3 over seeds 4-8 here. A running
# sum kept in bf16 loses 2**-9 of a sum that reaches -40 over a chunk of 64,
# errors of up to 0.1 in an exponent: 1.2e-2 to 3.7e-2 over the same seeds.
BF16_POLICY_REL_L2 = 4e-3


@PATHS
def test_bf16_inputs_hold_the_policy_and_a_bf16_running_sum_fails_it(
        scan, monkeypatch):
    """Either writing takes its running sums from ``_running_sum_last``:
    planted there, a bf16 sum fails the kernels as it fails the einsums."""
    args = inputs(4, jnp.bfloat16)
    exact = {k: v.astype(jnp.float32) for k, v in args.items()}
    want, _ = recurrence(**exact)
    y, ends = scan(64, **args)
    assert y.dtype == jnp.bfloat16 and ends.dtype == jnp.float32
    held = relative_l2(y.astype(jnp.float32), want)
    assert held < BF16_POLICY_REL_L2, held

    monkeypatch.setattr(
        ssd, "_running_sum_last",
        lambda a: jnp.cumsum(a.astype(jnp.bfloat16), axis=-1)
        .astype(jnp.float32))
    y, _ = scan(64, **args)
    broken = relative_l2(y.astype(jnp.float32), want)
    assert broken > 2 * BF16_POLICY_REL_L2, broken


@PATHS
def test_a_length_that_is_no_multiple_of_the_chunk_is_an_error(scan):
    args = inputs(5)
    with pytest.raises(ValueError, match="not a multiple"):
        scan(48, **args)
    with pytest.raises(ValueError, match="cannot share"):
        scan(8, **{**args, "B": args["B"][:, :, :1].repeat(3, 2),
                   "C": args["C"][:, :, :1].repeat(3, 2)})


@pytest.mark.parametrize("heads,width,slab", [
    (8, 64, 2), (2, 8, 2), (8, 128, 1), (3, 64, 1), (4, 32, 4), (6, 32, 3)])
def test_heads_fill_128_rows_a_slab(heads, width, slab):
    """Two heads of 64 channels one under the other; a count that does not
    divide the group falls back to the largest that does."""
    assert ssd._heads_per_slab(heads, width) == slab


@pytest.mark.parametrize("per_group,width", [(1, 16), (3, 8), (4, 4)])
def test_kernels_work_any_heads_a_slab(per_group, width):
    """One head a slab (no tile is shared), three and four: the slab's
    selects against the einsums, outputs and every gradient."""
    rng = np.random.RandomState(9)
    heads, groups, state, length = 2 * per_group, 2, 8, 32
    args = {
        "x": rng.randn(1, length, heads, width),
        "dt": np.log1p(np.exp(rng.randn(1, length, heads) - 1.0)),
        "A": -np.exp(rng.uniform(0.0, 1.0, heads)),
        "B": rng.randn(1, length, groups, state) * 0.5,
        "C": rng.randn(1, length, groups, state) * 0.5,
        "D": rng.randn(heads)}
    args = {k: jnp.asarray(v, jnp.float32) for k, v in args.items()}
    assert ssd._heads_per_slab(per_group, width) == per_group

    def loss(scan, a):
        y, ends = scan(8, **a)
        return jnp.sum(jnp.sin(y)), (y, ends)
    (got, want) = (jax.jit(jax.grad(functools.partial(loss, scan),
                                    has_aux=True))(args)
                   for scan in (kernels, chunked))
    for name in INPUTS:
        assert relative_l2(got[0][name], want[0][name]) < 2e-5, name
    assert relative_l2(got[1][0], want[1][0]) < 1e-6
    assert relative_l2(got[1][1], want[1][1]) < 1e-6


@PATHS
def test_chunks_are_counted_at_trace_time(scan):
    counter = get_registry().counter(
        "hvd_ssd_chunks_total",
        "chunks of the state-space-dual scan traced (chunks x heads x "
        "batch)")
    before = counter.value
    jax.jit(lambda a: scan(16, **a)).lower(inputs(6))
    assert counter.value - before == BATCH * H * (T // 16)


# A decay exp(a_t - a_s) gives a_t what it takes from a_s, and the running
# sum's transpose adds the two up again: rounded apart (one side from bf16
# products, the other from float32) they stop cancelling, and dA read 11%,
# d dt 5% here where the einsums' autodiff reads 0.36%, 0.23%. 64 heads as
# published, so A reaches -64; chunks of 128.
BF16_GRADIENT_REL_L2 = 6e-3


@PATHS
def test_bf16_gradients_hold_the_policy(scan):
    rng = np.random.RandomState(10)
    heads, width, groups, state, length = 64, 8, 2, 16, 256
    args = {
        "x": rng.randn(1, length, heads, width),
        "dt": np.log1p(np.exp(rng.randn(1, length, heads) - 3.0)),
        "A": -np.arange(1.0, heads + 1),
        "B": rng.randn(1, length, groups, state) * 0.3,
        "C": rng.randn(1, length, groups, state) * 0.3,
        "D": np.ones(heads)}
    exact = {k: jnp.asarray(v, jnp.float32) for k, v in args.items()}
    rounded = {k: v.astype(jnp.bfloat16) if k in "xBC" else v
               for k, v in exact.items()}
    weight = jnp.asarray(rng.randn(1, length, heads, width), jnp.float32)

    def gradient(run, a):
        return jax.grad(lambda a: jnp.sum(
            run(128, **a)[0].astype(jnp.float32) * weight))(a)
    want = jax.jit(functools.partial(gradient, chunked))(exact)
    got = jax.jit(functools.partial(gradient, scan))(rounded)
    for name in INPUTS:
        assert got[name].dtype == rounded[name].dtype
        assert relative_l2(got[name].astype(jnp.float32), want[name]) \
            < BF16_GRADIENT_REL_L2, name
