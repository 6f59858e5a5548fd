"""The head-and-loss operator (``ops/head_loss.py``) against a float32
``jax.numpy`` reference on tiny shapes: both doors' value and gradients, a
matrix held ``[V, d]`` or ``[d, V]``, weights that mask rows, one chunk,
several, and a last chunk that is not whole, a cotangent other than 1, under
``jit`` and as ``value_and_grad(..., has_aux=True)``; the primal without
gradients; no float32 array of all rows by the vocabulary in a door-A
gradient's jaxpr; the counter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.ops import head_loss as hl

D, V = 32, 256
# rows a chunk at V = 256 under the patched CHUNK_BYTES of ``chunks_of_16``
ROWS = 16
LAYOUTS = {"tied_Vd": (V, D), "untied_dV": (D, V)}
# T: one chunk (short of a whole one too), several, a last chunk not whole
LENGTHS = {"one_chunk": 16, "one_short_chunk": 12, "four_chunks": 64,
           "ragged_last_chunk": 50}


@pytest.fixture
def chunks_of_16(monkeypatch):
    monkeypatch.setattr(hl, "CHUNK_BYTES", 4 * V * ROWS)
    assert hl.chunk_rows(V) == ROWS


@functools.lru_cache(maxsize=None)
def _inputs(t: int, layout: str, masked: bool):
    keys = jax.random.split(jax.random.key(t), 4)
    h = jax.random.normal(keys[0], (t, D), jnp.float32).astype(jnp.bfloat16)
    w = 0.3 * jax.random.normal(keys[1], LAYOUTS[layout], jnp.float32)
    labels = jax.random.randint(keys[2], (t,), 0, V)
    weights = jax.random.uniform(keys[3], (t,), jnp.float32, 0.5, 2.0)
    if masked:
        weights = jnp.where(jnp.arange(t) % 3 == 1, 0.0, weights)
    return h, w, labels, weights


def _reference_logits(h, w):
    """float32 products of the values the operator multiplies: the hidden
    state and the matrix as bf16."""
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(h.astype(jnp.float32), w.T if w.shape[1] == D else w,
                   precision=jax.lax.Precision.HIGHEST)


def _reference(logits, labels, weights):
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * ce)


def _close(got, want, rel):
    """Within ``rel`` of the reference's largest entry: the operator's
    gradients pass through bf16 where autodiff's products read them so."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= rel * np.abs(want).max()


@pytest.mark.parametrize("masked", [False, True], ids=["weighted", "masked"])
@pytest.mark.parametrize("length", list(LENGTHS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_door_a_is_the_reference_in_value_and_three_gradients(
        chunks_of_16, layout, length, masked):
    """``grad`` of ``3 * loss`` under ``jit``: a cotangent other than 1
    reaches ``dh``, ``dw`` and the weights' gradient."""
    t = LENGTHS[length]
    h, w, labels, weights = _inputs(t, layout, masked)

    def ours(h, w, weights):
        return 3.0 * hl.head_cross_entropy(h, w, labels, weights)

    def theirs(h, w, weights):
        return 3.0 * _reference(_reference_logits(h, w), labels, weights)
    value, grads = jax.jit(jax.value_and_grad(ours, (0, 1, 2)))(h, w, weights)
    want, want_grads = jax.jit(jax.value_and_grad(theirs, (0, 1, 2)))(
        h, w, weights)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    assert [g.dtype for g in grads] == [h.dtype, w.dtype, weights.dtype]
    assert grads[1].shape == LAYOUTS[layout]
    _close(grads[0], want_grads[0], 2.0 ** -7)  # a bf16 result
    _close(grads[1], want_grads[1], 2.0 ** -7)  # bf16 logits' gradient
    _close(grads[2], want_grads[2], 1e-5)
    if masked:
        dead = np.asarray(weights) == 0.0
        assert not np.asarray(grads[0], np.float32)[dead].any()


@pytest.mark.parametrize("length", list(LENGTHS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_door_a_without_gradients_is_the_same_value(
        chunks_of_16, layout, length):
    h, w, labels, weights = _inputs(LENGTHS[length], layout, True)
    primal = jax.jit(hl.head_cross_entropy)(h, w, labels, weights)
    with_gradients = jax.jit(jax.value_and_grad(hl.head_cross_entropy))(
        h, w, labels, weights)[0]
    want = _reference(_reference_logits(h, w), labels, weights)
    np.testing.assert_allclose(primal, want, rtol=1e-5)
    np.testing.assert_allclose(with_gradients, want, rtol=1e-5)
    # no product of the backward pass where no gradient is wanted
    jaxpr = jax.make_jaxpr(hl.head_cross_entropy)(h, w, labels, weights)
    assert _products(jaxpr.jaxpr) == -(-LENGTHS[length] // ROWS)


@pytest.mark.parametrize("door", ["A", "B"])
def test_a_door_inside_value_and_grad_with_aux(chunks_of_16, door):
    """As a loss function hands it to ``dp.make_train_step``: a mean beside
    auxiliary outputs, parameters in a tree."""
    h, w, labels, weights = _inputs(50, "untied_dV", True)

    def loss_fn(params):
        if door == "A":
            total = hl.head_cross_entropy(h, params["head"]["kernel"],
                                          labels, weights)
        else:
            logits = jnp.dot(h, params["head"]["kernel"].astype(h.dtype),
                             preferred_element_type=jnp.float32)
            total = hl.cross_entropy(logits, labels, weights)
        return total / labels.size, {"rows": labels.size}

    def reference_fn(params):
        return _reference(_reference_logits(h, params["head"]["kernel"]),
                          labels, weights) / labels.size
    params = {"head": {"kernel": w}}
    (value, aux), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params)
    want, want_grads = jax.value_and_grad(reference_fn)(params)
    assert aux == {"rows": 50}
    np.testing.assert_allclose(value, want, rtol=1e-5)
    _close(grads["head"]["kernel"], want_grads["head"]["kernel"], 2.0 ** -7)


@pytest.mark.parametrize("masked", [False, True], ids=["weighted", "masked"])
@pytest.mark.parametrize("shape", [(50,), (2, 25)], ids=["rows", "batch_rows"])
def test_door_b_is_the_reference_in_value_and_two_gradients(shape, masked):
    h, w, labels, weights = _inputs(50, "tied_Vd", masked)
    logits = _reference_logits(h, w).reshape(*shape, V)
    labels, weights = labels.reshape(shape), weights.reshape(shape)

    def ours(logits, weights):
        return 3.0 * hl.cross_entropy(logits, labels, weights)

    def theirs(logits, weights):
        return 3.0 * _reference(logits, labels, weights)
    value, grads = jax.jit(jax.value_and_grad(ours, (0, 1)))(logits, weights)
    want, want_grads = jax.jit(jax.value_and_grad(theirs, (0, 1)))(
        logits, weights)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    assert grads[0].dtype == jnp.float32
    _close(grads[0], want_grads[0], 1e-5)
    _close(grads[1], want_grads[1], 1e-5)
    np.testing.assert_allclose(
        jax.jit(hl.cross_entropy)(logits, labels, weights) * 3.0, want,
        rtol=1e-5)


def _all_equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_equations(sub)


def _products(jaxpr) -> int:
    return sum(eqn.primitive.name == "dot_general"
               for eqn in _all_equations(jaxpr))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_a_door_a_gradient_holds_no_float32_array_of_all_rows(
        chunks_of_16, layout):
    """Four chunks: every array by the vocabulary in the gradient's jaxpr is
    a chunk's (the matrix and its gradient apart), and a chunk holds three
    products, not four."""
    t = LENGTHS["four_chunks"]
    h, w, labels, weights = _inputs(t, layout, False)
    jaxpr = jax.make_jaxpr(jax.grad(hl.head_cross_entropy, (0, 1)))(
        h, w, labels, weights).jaxpr
    by_vocab = [v.aval for eqn in _all_equations(jaxpr) for v in eqn.outvars
                if V in v.aval.shape and v.aval.shape != LAYOUTS[layout]]
    assert any(aval.dtype == jnp.float32 for aval in by_vocab)
    for aval in by_vocab:
        assert aval.size <= ROWS * V, aval
    assert _products(jaxpr) == 3 * (t // ROWS)
    # door B's gradient does hold one, which is what door A is for
    logits = _reference_logits(h, w)
    whole = jax.make_jaxpr(jax.grad(hl.cross_entropy))(
        logits, labels, weights).jaxpr
    assert any(v.aval.shape == (t, V) for eqn in _all_equations(whole)
               for v in eqn.outvars)


@pytest.mark.parametrize("vocab,rows", [(16384, 8192), (16160, 8192),
                                        (18992, 4096), (25024, 4096),
                                        (50304, 2048), (1 << 30, 8)])
def test_rows_a_chunk_follow_from_the_vocabulary(vocab, rows):
    assert hl.chunk_rows(vocab) == rows
    assert 4 * vocab * rows <= hl.CHUNK_BYTES or rows == 8


def test_a_square_matrix_says_which_shapes_it_takes():
    h = jnp.zeros((8, D), jnp.bfloat16)
    with pytest.raises(ValueError, match="vocab"):
        hl.head_cross_entropy(h, jnp.zeros((D, D)), jnp.zeros(8, jnp.int32),
                              jnp.ones(8))
    with pytest.raises(ValueError, match="vocab"):
        hl.head_cross_entropy(h, jnp.zeros((V, D + 1)),
                              jnp.zeros(8, jnp.int32), jnp.ones(8))


def _calls(door, chunks):
    return get_registry().counter(
        "hvd_head_loss_calls_total", "", door=door, chunks=str(chunks)).value


@pytest.mark.parametrize("door,length,chunks", [
    ("A", "four_chunks", 4), ("A", "ragged_last_chunk", 4),
    ("A", "one_short_chunk", 1), ("B", "four_chunks", 1)])
def test_the_counter_says_door_and_chunks_once_a_traced_call(
        chunks_of_16, door, length, chunks):
    h, w, labels, weights = _inputs(LENGTHS[length], "tied_Vd", False)
    if door == "A":
        step = jax.jit(jax.grad(hl.head_cross_entropy))
        args = (h, w, labels, weights)
    else:
        step = jax.jit(jax.grad(hl.cross_entropy))
        args = (_reference_logits(h, w), labels, weights)
    before = _calls(door, chunks)
    step(*args)
    assert _calls(door, chunks) == before + 1
    step(*args)  # compiled: not traced again
    assert _calls(door, chunks) == before + 1


def _decoders():
    from horovod_tpu.models import (JoyaiFlashTiny, Lfm2Tiny, NemotronHTiny,
                                    TrinityTiny)
    return {
        "lfm2": (Lfm2Tiny(), ("embed_tokens", "embedding")),
        "trinity": (TrinityTiny(), ("lm_head", "kernel")),
        "nemotron_h": (NemotronHTiny(), ("LmHead", "kernel")),
        "joyai_flash": (JoyaiFlashTiny(), ("lm_head", "kernel")),
    }


@pytest.mark.parametrize("name", ["lfm2", "trinity", "nemotron_h",
                                  "joyai_flash"])
def test_a_decoder_stops_before_its_head_and_still_returns_logits(name):
    """``head=False`` hands back the normed hidden state the logits are the
    product of, with the matrix where the loss looks for it; called as
    before the decoder returns float32 logits."""
    model, (module, leaf) = _decoders()[name]
    tokens = jax.random.randint(jax.random.key(3), (2, 32), 0, model.vocab)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    logits = jax.jit(model.apply)(variables, tokens)
    hidden = jax.jit(functools.partial(model.apply, head=False))(
        variables, tokens)
    w = variables["params"][module][leaf]
    if w.shape[0] == model.vocab:
        w = w.T
    pairs = zip(logits, hidden) if name == "joyai_flash" \
        else [(logits, hidden)]
    for got, state in pairs:
        assert got.dtype == jnp.float32 and state.dtype == model.dtype
        assert state.shape == (2, 32, model.hidden)
        want = jnp.dot(state, w.astype(model.dtype),
                       preferred_element_type=jnp.float32)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("model,door,calls", [
    ("lfm2", "A", 1), ("trinity", "A", 1), ("nemotron_h", "A", 1),
    ("joyai_flash", "A", 2), ("smallthinker", "B", 1), ("sdar", "B", 1),
    ("olmoe", "B", 0)])
def test_each_loss_under_models_takes_its_door(model, door, calls):
    """A loss that runs the model itself takes door A (Joyai's for both of
    its heads), one that is handed logits door B; ``olmoe_loss`` keeps its
    own writing. At a test's widths a head is one chunk."""
    from test_attn_parts import MODELS
    params, loss = MODELS[model][0]()
    before = {d: _calls(d, 1) for d in "AB"}
    jax.jit(jax.grad(loss)).lower(params)
    traced = {d: _calls(d, 1) - before[d] for d in "AB"}
    assert traced == {"A": 0, "B": 0, door: calls}
