"""``ops/ssm_ends.py``: the mixer's conv and gated norm as hand-written
passes (Pallas kernels in interpret mode here) against the plain
``jax.numpy`` writings autodiff takes the gradient of: each stage's forward
and each of its gradients, in float32 (tight) and under the bf16 policy;
zeros before a sequence and nothing carried from one sequence into the
next; a sequence of several tiles and of one; ``K`` 2 and 4, groups 1 and
8; channels read where they lie in a wider array; the counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.ops import ssm_ends as se

F32, BF16 = jnp.float32, jnp.bfloat16
# (batch, positions, channels, K, groups): several tiles of the conv's 8192
# positions and of the norm's 512; one tile of each; K and groups small
SHAPES = {
    "tiles-K4-G8": (2, 16384, 16, 4, 8),
    "tile-K2-G1": (2, 64, 24, 2, 1),
    "norm-tiles-K4-G2": (1, 1536, 32, 4, 2),
}
CONV_LEAVES, NORM_LEAVES = ("dx", "dw", "db"), ("dy", "dz", "dscale")


def _distance(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _limit(dtype, summed: bool):
    """A float32 pass agrees to rounding; under bf16 an element is a bf16
    ulp or two off (the kernels round where the plain writing rounds, their
    sums run in another order), a float32 sum over positions far less."""
    if dtype == F32:
        return 2e-5
    return 1e-4 if summed else 1.6e-2


def _inputs(shape, dtype, wider: int = 0):
    batch, length, channels, k, _ = SHAPES[shape]
    keys = jax.random.split(jax.random.key(7), 7)
    return dict(
        x=jax.random.normal(keys[0], (batch, length, channels + wider),
                            dtype),
        w=jax.random.uniform(keys[1], (k, channels), F32, -0.5, 0.5),
        b=jax.random.uniform(keys[2], (channels,), F32, -0.5, 0.5),
        y=jax.random.normal(keys[3], (batch, length, channels), dtype),
        scale=1.0 + 0.1 * jax.random.normal(keys[4], (channels,), F32),
        cotangent=jax.random.normal(keys[5], (batch, length, channels),
                                    dtype))


def _weighted(fn, cotangent):
    """A scalar whose gradient is ``fn``'s transpose at ``cotangent``."""
    return lambda *args: jnp.sum(fn(*args).astype(F32)
                                 * cotangent.astype(F32))


@pytest.fixture(scope="module")
def passes():
    """``passes(stage, shape, dtype)``: (the kernels', the plain writing's)
    forward result and gradients, each pair computed once for the cases
    that read it."""
    done = {}

    def both(stage, shape, dtype):
        key = stage, shape, dtype
        if key not in done:
            v = _inputs(shape, dtype)
            if stage == "conv":
                args = (v["x"], v["w"], v["b"])
                writings = [lambda x, w, b, fn=fn: fn(x, w, b, dtype)
                            for fn in (se.causal_conv_silu,
                                       se.conv_silu_plain)]
            else:
                groups = SHAPES[shape][4]
                args = (v["y"], v["x"], v["scale"])
                writings = [
                    lambda y, z, s, fn=fn: fn(y, z, s, groups, 1e-5, dtype)
                    for fn in (se.gated_group_norm, se.gated_norm_plain)]
            # the result and its gradients from one compiled program each
            done[key] = [jax.jit(lambda *a, fn=fn: (fn(*a), jax.grad(
                _weighted(fn, v["cotangent"]), argnums=(0, 1, 2))(*a)))(*args)
                for fn in writings]
        return done[key]
    return both


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_conv_forward_is_the_plain_writing(passes, shape, dtype):
    (got, _), (want, _) = passes("conv", shape, dtype)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    assert _distance(got, want) < _limit(dtype, False)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("leaf", range(3), ids=CONV_LEAVES)
def test_conv_gradient_is_the_plain_writings(passes, leaf, shape, dtype):
    """dx in the activations' dtype, dw [K, C] and db [C] float32 sums over
    positions and batch."""
    (_, got), (_, want) = passes("conv", shape, dtype)
    assert got[leaf].dtype == want[leaf].dtype
    assert got[leaf].shape == want[leaf].shape
    assert _distance(got[leaf], want[leaf]) < _limit(dtype, leaf > 0)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_norm_forward_is_the_plain_writing(passes, shape, dtype):
    (got, _), (want, _) = passes("norm", shape, dtype)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    assert _distance(got, want) < _limit(dtype, False)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("leaf", range(3), ids=NORM_LEAVES)
def test_norm_gradient_is_the_plain_writings(passes, leaf, shape, dtype):
    (_, got), (_, want) = passes("norm", shape, dtype)
    assert got[leaf].dtype == want[leaf].dtype
    assert got[leaf].shape == want[leaf].shape
    assert _distance(got[leaf], want[leaf]) < _limit(dtype, leaf == 2)


@pytest.mark.parametrize("k", [2, 4])
def test_the_first_positions_see_zeros_and_no_sequence_sees_another(k):
    """With a weight on the oldest tap alone, ``y_t = silu(x_{t-K+1})``:
    the first ``K-1`` positions of EVERY sequence are ``silu(0) = 0``
    whatever the batch holds before them, forward; backward, ``dx`` of a
    sequence's last ``K-1`` positions is 0 (nothing after them reads
    them), in the first sequence too, whose successor's cotangent is
    large."""
    batch, length, channels = 2, 16384, 16
    x = 1.0 + jax.random.uniform(jax.random.key(0),
                                 (batch, length, channels), F32)
    w = jnp.zeros((k, channels), F32).at[0].set(1.0)
    b = jnp.zeros((channels,), F32)
    y = se.causal_conv_silu(x, w, b)
    np.testing.assert_array_equal(np.asarray(y[:, :k - 1]), 0.0)
    np.testing.assert_allclose(np.asarray(y[:, k - 1:]),
                               np.asarray(jax.nn.silu(x[:, :length - k + 1])),
                               rtol=1e-5)
    dx = jax.jit(jax.grad(
        lambda x: jnp.sum(se.causal_conv_silu(x, w, b) * 1e3)))(x)
    np.testing.assert_array_equal(np.asarray(dx[:, length - k + 1:]), 0.0)
    assert float(jnp.abs(dx[:, :length - k + 1]).min()) > 0.0


def test_channels_are_read_where_they_lie_in_a_wider_array():
    """``at``: the conv's and the gate's channels as a run of a wider array
    (the in-projection's output); the gradient comes back in the run's
    place and is zero beside it."""
    shape, dtype, at, wider = "norm-tiles-K4-G2", F32, 64, 96
    v = _inputs(shape, dtype, wider)
    channels, groups = SHAPES[shape][2], SHAPES[shape][4]
    run = slice(at, at + channels)

    got = jax.jit(jax.value_and_grad(_weighted(
        lambda x: se.causal_conv_silu(x, v["w"], v["b"], at=at),
        v["cotangent"])))(v["x"])
    want = jax.jit(jax.value_and_grad(_weighted(
        lambda x: se.conv_silu_plain(x[..., run], v["w"], v["b"], dtype),
        v["cotangent"])))(v["x"])
    assert _distance(got[0], want[0]) < 2e-5
    assert _distance(got[1], want[1]) < 2e-5
    assert not np.asarray(got[1][..., :at]).any()
    assert not np.asarray(got[1][..., at + channels:]).any()

    got = jax.jit(jax.grad(_weighted(lambda y, z: se.gated_group_norm(
        y, z, v["scale"], groups, at=at), v["cotangent"]),
        argnums=(0, 1)))(v["y"], v["x"])
    want = jax.jit(jax.grad(_weighted(lambda y, z: se.gated_norm_plain(
        y, z[..., run], v["scale"], groups, 1e-5, dtype), v["cotangent"]),
        argnums=(0, 1)))(v["y"], v["x"])
    for g, w_ in zip(got, want):
        assert g.shape == w_.shape and _distance(g, w_) < 2e-5
    assert not np.asarray(got[1][..., :at]).any()


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
def test_runs_of_the_conv_are_arrays_of_their_own_and_one_gradient(dtype):
    """``widths``: one result a run (x, B and C of a mixer), each what the
    conv over all the channels holds there, bit for bit; backward, the
    runs' calls fill one ``dx`` between them, and ``dw``, ``db`` are the
    whole conv's."""
    shape, at, widths = "norm-tiles-K4-G2", 64, (16, 8, 8)
    v = _inputs(shape, dtype, wider=96)
    cotangent = v["cotangent"]

    def whole(x, w, b):
        return se.causal_conv_silu(x, w, b, at=at)

    def in_runs(x, w, b):
        runs = se.causal_conv_silu(x, w, b, at=at, widths=widths)
        assert [r.shape[-1] for r in runs] == list(widths)
        return jnp.concatenate(runs, axis=-1)
    args = (v["x"], v["w"], v["b"])
    np.testing.assert_array_equal(np.asarray(in_runs(*args), np.float32),
                                  np.asarray(whole(*args), np.float32))
    got = jax.jit(jax.grad(_weighted(in_runs, cotangent),
                           argnums=(0, 1, 2)))(*args)
    want = jax.jit(jax.grad(_weighted(whole, cotangent),
                            argnums=(0, 1, 2)))(*args)
    for g, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w_, np.float32))
    with pytest.raises(ValueError, match="are not the conv's"):
        se.causal_conv_silu(*args, at=at, widths=(16, 8))


def test_what_the_kernels_cannot_tile_is_an_error():
    y = jnp.zeros((1, 64, 24), F32)
    with pytest.raises(ValueError, match="groups"):
        se.gated_group_norm(y, y, jnp.ones((24,)), groups=5)
    with pytest.raises(ValueError, match="groups"):  # a run inside a group
        se.gated_group_norm(y[..., :16], y, jnp.ones((16,)), groups=2, at=4)
    with pytest.raises(ValueError, match="are not in"):
        se.causal_conv_silu(y, jnp.ones((4, 16)), jnp.ones((16,)), at=16)


def test_counter_says_stage_and_direction():
    """One count a pass traced: a gradient traces a stage's forward and
    its backward."""
    def counter(stage, direction):
        return get_registry().counter(
            "hvd_ssm_end_calls_total", stage=stage, direction=direction)
    passes = [(stage, direction) for stage in ("conv", "gate_norm")
              for direction in ("fwd", "bwd")]
    before = {key: counter(*key).value for key in passes}
    # shapes no other case traces, so nothing is served from a cache
    x = jnp.ones((1, 96, 8), F32)
    w, b, scale = jnp.ones((3, 8)), jnp.zeros((8,)), jnp.ones((8,))
    jax.grad(lambda x: jnp.sum(se.causal_conv_silu(x, w, b)))(x)
    assert counter("gate_norm", "fwd").value == before["gate_norm", "fwd"]
    jax.grad(lambda x: jnp.sum(se.gated_group_norm(x, x, scale, 2)))(x)
    for key in passes:
        assert counter(*key).value - before[key] >= 1, key
