"""Trinity's training pass through the normal path, on the CPU at a small
size: the program (``models/trinity.py``: output-gated grouped-query attention
with per-head q/k norm, sliding layers with rotary beside full layers without
positions, four norms a layer, dense and sparse feed-forwards, a share of
sigmoid-and-bias SwiGLU experts beside a shared one) against the plain
float32 reference that ``benchmark/configs/trinity-mini.py`` keeps, in
float32 and under the bf16 policy; the gate, the post-norms, the positions,
the embedding's scale and each layer's call by hand; the four shares of one
sparse layer against the uncut layer; the bias rule through
``dp.make_stateful_train_step`` on four virtual devices; the published
geometry."""

import functools
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (TrinityDecoder, TrinityMini, TrinityTiny,
                                trinity, trinity_loss)
from horovod_tpu.parallel import dp, ep, mesh as mesh_lib
from horovod_tpu.profiler import annotate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "trinity-mini")

SLIDING, FULL = "sliding_attention", "full_attention"
# the cell's stack (a leading dense sliding layer and one period of sparse
# ones) at hidden 64, 4 heads of 16 on 2 key heads, a window shorter than
# every test's sequence; 4 of 16 experts held from 4 on
LAYER_TYPES = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
RATE = 1e-3
SIZES = dict(layer_types=LAYER_TYPES, num_dense_layers=1, vocab=512,
             hidden=64, heads=4, kv_heads=2, head_dim=16, dense_dim=128,
             experts=16, experts_per_token=2, expert_dim=32, window=40,
             load_balance_coeff=RATE, experts_held=(4, 4))
REFERENCE = dict(layer_types=LAYER_TYPES, num_dense_layers=1, held=(4, 4),
                 eps=1e-5, theta=1e4, window=40, scale=2.826, rate=RATE,
                 heads=4, kv_heads=2, head_dim=16, experts_per_token=2)


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_trinity_mini", CONFIG + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not want.any():  # a held expert no row chose: no gradient either side
        return float(np.linalg.norm(got))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@functools.lru_cache(maxsize=None)
def _made(dtype, batch, seq, seed, kw):
    model = TrinityDecoder(dtype=dtype, **{**SIZES, **dict(kw)})
    tokens = jax.random.randint(jax.random.key(seed + 100), (batch, seq), 0,
                                model.vocab, jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(seed), tokens)
    # a state that is not the first step's: a bias that moves choices and a
    # load the rule reads
    keys = iter(jax.random.split(jax.random.key(seed + 200), 64))
    state = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (0.05 * jax.random.normal(next(keys), leaf.shape)
                            if path[-1].key == "expert_bias" else
                            jax.random.randint(next(keys), leaf.shape, 0, 50)
                            .astype(jnp.float32)),
        variables["router_state"])
    # norm scales away from 1, so that a norm left out or misplaced shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (leaf + 0.2 * jax.random.normal(next(keys),
                                                           leaf.shape)
                            if path[-1].key == "scale" else leaf),
        variables["params"])
    return model, params, state, {
        "tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def make(dtype, batch, seq, seed=0, **kw):
    """(model, float32 parameters, a router state, the batch). Made once a
    module for the same arguments: tests share the arrays, and change none
    in place."""
    return _made(dtype, batch, seq, seed, tuple(sorted(kw.items())))


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, state, data):
    def loss_fn(p):
        return trinity_loss(model, p, state, data["tokens"], data["labels"])
    return jax.value_and_grad(loss_fn, has_aux=True)(params)


def program(model, params, state, data):
    """(loss, new state, aux, gradients) of the model's own loss: compiled
    once a model (a flax module hashes by its fields) and batch shape."""
    (loss, (new_state, aux)), grads = _program(model, params, state, data)
    return loss, new_state, aux, grads


@functools.partial(jax.jit, static_argnums=0)
def logits_of(model, params, state, tokens):
    """A training call's logits: the bias rule applied first."""
    return model.apply({"params": params, "router_state": state}, tokens,
                       mutable=["router_state"])[0]


@functools.lru_cache(maxsize=None)
def _reference(reference_forward, batch, seq, kw):
    _, params, state, data = make(jnp.float32, batch, seq)

    def loss_fn(p):
        loss, *rest = reference_forward(p, state, data,
                                        **{**REFERENCE, **dict(kw)})
        return loss, rest
    (loss, (new_state, chosen, stream)), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, new_state, chosen, grads, stream


def reference(config_module, batch, seq, **kw):
    """(loss, new state, chosen experts, gradients, the final norm's output)
    of the configuration's float32 reference on ``make(jnp.float32, batch,
    seq)``'s parameters, state and batch: run once a module for a size."""
    return _reference(config_module.reference_forward, batch, seq,
                      tuple(sorted(kw.items())))


# -- (a) float32 against float32 -------------------------------------------------

@pytest.mark.parametrize("batch,seq,remat", [
    (2, 128, ""), (1, 512, "blocks"), (1, 512, "blocks_keep_attention"),
])
def test_float32_program_matches_the_reference(config_module, batch, seq,
                                               remat):
    """Both kinds of layer and both feed-forwards in the stack, the window
    shorter than the sequence: the loss, the logits, every leaf's gradient,
    the new state (the bias after the rule, this step's load) and the
    experts every token chose."""
    model, params, state, data = make(jnp.float32, batch, seq, remat=remat)
    loss, new_state, aux, grads = program(model, params, state, data)
    want, want_state, chosen, want_grads, stream = reference(
        config_module, batch, seq)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    want_logits = np.asarray(stream, np.float64) @ np.asarray(
        params["lm_head"]["kernel"], np.float64)
    np.testing.assert_allclose(
        logits_of(model, params, state, data["tokens"]), want_logits,
        rtol=2e-4, atol=2e-5)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 2e-4, errors
    for got, wanted in zip(jax.tree_util.tree_leaves(new_state),
                           jax.tree_util.tree_leaves(want_state)):
        np.testing.assert_allclose(got, wanted, atol=1e-7)
    assert set(new_state) == {f"TrinityBlock_{i}" for i in (1, 2, 3, 4)}
    loads = np.stack([np.bincount(np.asarray(c).ravel(), minlength=16)
                      for c in chosen])
    np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]), loads)
    assert loads.sum() == 4 * 2 * batch * seq


@pytest.mark.parametrize("what,kw", [
    ("another order of layers", dict(layer_types=(SLIDING, FULL, SLIDING,
                                                  SLIDING, SLIDING))),
    ("every layer sliding", dict(layer_types=(SLIDING,) * 5)),
    ("another window", dict(window=41)),
    ("no gate", dict(gated=False)),
    ("no post-norms", dict(post_norms=False)),
    ("an unscaled embedding", dict(embedding_scale=1.0)),
    ("a router on the layer's input", dict(router_reads="layer_input")),
    ("a router on the un-normed stream", dict(router_reads="stream")),
])
def test_another_model_reads_apart(config_module, what, kw):
    """The reference told what the model is NOT computes something else, far
    outside what (a) allows (the final norm's output, which (a) holds to the
    program's logits): the lists, the window, the gate, the post-norms, the
    embedding's scale and the stream a router reads are read, not
    assumed."""
    _, params, state, data = make(jnp.float32, 2, 128)
    other = jax.jit(functools.partial(  # forward alone: no gradient is read
        config_module.reference_forward, **{**REFERENCE, **kw}))(
            params, state, data)[3]
    assert relative_l2(other, reference(config_module, 2, 128)[4]) > 1e-2, \
        what


# -- (b) the gate, the post-norms, the positions, by hand -----------------------------

def _attention_alone(dtype=jnp.float32, window=None, theta=None):
    module = trinity.TrinityAttention(4, 2, 16, theta, window, 1e-5, dtype)
    x = jax.random.normal(jax.random.key(1), (2, 48, 64))
    params = jax.jit(module.init)(jax.random.key(2), x)["params"]
    return module, params, x


@pytest.mark.parametrize("window,theta", [(None, None), (20, 1e4)])
def test_a_zero_gate_halves_the_attention_output(config_module, window,
                                                 theta):
    """``sigmoid(0) = 1/2``: with ``gate_proj`` zero the operator gives half
    of what the ungated operator gives; with the gate as initialised it
    gives the reference's gated output, which is neither."""
    module, params, x = _attention_alone(window=window, theta=theta)
    sizes = dict(heads=4, kv_heads=2, head_dim=16, theta=theta or 1.0,
                 window=window or 0, sliding=window is not None, eps=1e-5,
                 bits=None)
    with jax.default_matmul_precision("highest"):
        ungated = config_module._attention(x, params, gated=False, **sizes)
        gated = config_module._attention(x, params, **sizes)
        zero = {**params, "gate_proj": {
            "kernel": jnp.zeros_like(params["gate_proj"]["kernel"])}}
        apply = jax.jit(module.apply)
        np.testing.assert_allclose(apply({"params": zero}, x), 0.5 * ungated,
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(apply({"params": params}, x), gated,
                                   rtol=1e-4, atol=1e-6)
    assert relative_l2(gated, 0.5 * ungated) > 0.01


def test_the_gate_is_a_sigmoid_of_the_inputs_own_projection():
    """By hand on one position: the operator's output is ``(o *
    sigmoid(x W_g)) W_o`` with ``o`` what the operator gives under a gate of
    ones (``W_g`` zero gives 1/2 everywhere, so ``o W_o`` is twice that)."""
    module, params, x = _attention_alone()
    zero = {**params, "gate_proj": {
        "kernel": jnp.zeros_like(params["gate_proj"]["kernel"])}}
    w_o = np.asarray(params["o_proj"]["kernel"], np.float64)
    with jax.default_matmul_precision("highest"):
        apply = jax.jit(module.apply)
        got = np.asarray(apply({"params": params}, x), np.float64)
        half = np.asarray(apply({"params": zero}, x), np.float64)
    o = 2 * half @ np.linalg.pinv(w_o)  # o_proj is square and invertible
    g = np.asarray(x, np.float64) @ np.asarray(
        params["gate_proj"]["kernel"], np.float64)
    np.testing.assert_allclose((o / (1 + np.exp(-g))) @ w_o, got,
                               rtol=1e-3, atol=1e-6)


def test_a_branch_is_normed_before_it_joins_the_stream():
    """RMSNorm forgets its input's scale: ``o_proj`` and every ``down``
    matrix times 3 leave the logits where they were (to what ``eps`` adds:
    the matrices start at 500 times their initial width, where a branch's
    mean square is far above 1e-5), because each branch's output is normed
    before it is added; a model whose branches joined un-normed would
    move."""
    model, params, state, data = make(jnp.float32, 2, 128)

    def branches_times(by):
        return jax.tree_util.tree_map_with_path(
            lambda path, leaf: by * leaf if any(
                getattr(k, "key", None) in ("o_proj", "down_proj", "down")
                for k in path) else leaf, params)
    got = logits_of(model, branches_times(1500.0), state, data["tokens"])
    want = logits_of(model, branches_times(500.0), state, data["tokens"])
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    assert float(jnp.abs(want).max()) > 0.1


def test_by_hand_the_four_norms_of_a_block():
    """One dense block by hand from its parts: ``a = x + post_attention(
    attn(input(x)))``, ``out = a + post_mlp(mlp(pre_mlp(a)))``, every norm
    weight x ``x / rms(x)``."""
    model = TrinityTiny(layer_types=(FULL,), num_dense_layers=1,
                        dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(3), (1, 32), 0, 256)
    variables = jax.jit(model.init)(jax.random.key(4), tokens)
    block = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * 1.3 if path[-1].key == "scale" else leaf,
        variables["params"]["TrinityBlock_0"])
    x = jax.random.normal(jax.random.key(5), (1, 32, 32))

    def norm(name, y):
        y = np.asarray(y, np.float64)
        return y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(block[name]["scale"], np.float64)
    attn = trinity.TrinityAttention(4, 2, 8, None, None, 1e-5, jnp.float32)
    mlp = trinity.swiglu(64, jnp.float32, None)
    with jax.default_matmul_precision("highest"):
        a = np.asarray(x, np.float64) + norm(
            "post_attention_layernorm", jax.jit(attn.apply)(
                {"params": block["TrinityAttention_0"]},
                jnp.asarray(norm("input_layernorm", x), jnp.float32)))
        want = a + norm("post_mlp_layernorm", jax.jit(mlp.apply)(
            {"params": block["mlp"]},
            jnp.asarray(norm("pre_mlp_layernorm", a), jnp.float32)))
        got = jax.jit(trinity.TrinityBlock(
            functools.partial(trinity.TrinityAttention, 4, 2, 8, None, None,
                              1e-5, jnp.float32),
            functools.partial(trinity.swiglu, 64, jnp.float32, "mlp"),
            1e-5, jnp.float32).apply)(
                                  {"params": block}, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind,moves", [(FULL, False), (SLIDING, True)])
def test_only_a_sliding_layer_knows_positions(kind, moves):
    """One layer, the window past the sequence so that both kinds see the
    causal mask: the last position of a full layer attends to a SET (the
    tokens before it in any order give the same logits there), of a sliding
    layer to a sequence (rotary)."""
    model = TrinityTiny(layer_types=(kind,), num_dense_layers=1, window=64,
                        dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(6), (1, 32), 0, 256)
    params = jax.jit(model.init)(jax.random.key(7), tokens)["params"]
    shuffled = jnp.concatenate(
        [jax.random.permutation(jax.random.key(8), tokens[:, :-1], axis=1),
         tokens[:, -1:]], axis=1)
    with jax.default_matmul_precision("highest"):
        a = jax.jit(model.apply)({"params": params}, tokens)[0, -1]
        b = jax.jit(model.apply)({"params": params}, shuffled)[0, -1]
    assert (relative_l2(b, a) > 1e-3) == moves
    if not moves:
        np.testing.assert_allclose(b, a, rtol=1e-4, atol=1e-5)


def test_the_embedding_is_scaled_by_the_root_of_the_width():
    """With every branch silenced (``o_proj`` and ``down_proj`` zero: a
    normed zero is zero) the logits are ``norm(Emb(t) sqrt(d)) W_head``;
    ``eps`` tells the scale: at ``Emb`` rows of rms 1e-3 the norm's
    denominator is ``sqrt(d 1e-6 + 1e-5)`` and not ``sqrt(1e-6 + 1e-5)``."""
    model = TrinityTiny(layer_types=(FULL,), num_dense_layers=1,
                        dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(9), (1, 16), 0, 256)
    params = jax.jit(model.init)(jax.random.key(10), tokens)["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if any(
            getattr(k, "key", None) in ("o_proj", "down_proj")
            for k in path) else leaf, params)
    rows = 1e-3 * jnp.sign(params["embed_tokens"]["embedding"])
    params = {**params, "embed_tokens": {"embedding": rows}}
    x = np.asarray(rows, np.float64)[np.asarray(tokens)] * 32 ** 0.5
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) @ np.asarray(
        params["lm_head"]["kernel"], np.float64)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.apply)({"params": params}, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
    unscaled = x / 32 ** 0.5
    unscaled = unscaled / np.sqrt((unscaled ** 2).mean(-1, keepdims=True)
                                  + 1e-5)
    assert relative_l2(unscaled @ np.asarray(params["lm_head"]["kernel"]),
                       want) > 0.1


def test_each_layers_call_has_its_mask_and_its_scope(monkeypatch):
    """``layer_types`` decides each layer's call: a sliding layer calls
    ``attention(causal=True, window=W)`` under ``attn_window`` with rotary
    before it, a full layer ``attention(causal=True, window=None)`` under
    ``attn_full`` with none; q in 4 heads, k and v in their own 2; the gate
    and the post-norms under their names in every layer."""
    calls = []
    real = trinity.attention

    def recording(q, k, v, **kw):
        calls.append((q.shape, k.shape, v.shape, kw))
        return real(q, k, v, **kw)
    monkeypatch.setattr(trinity, "attention", recording)
    model = TrinityDecoder(**SIZES)
    tokens = jnp.zeros((1, 64), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), tokens)
    calls.clear()
    text = jax.jit(lambda v: model.apply(v, tokens)).lower(
        variables).as_text(debug_info=True)
    assert [c[3] for c in calls] == [
        dict(causal=True, window=40 if kind == SLIDING else None)
        for kind in LAYER_TYPES]
    assert all(c[:3] == ((1, 64, 4, 16), (1, 64, 2, 16), (1, 64, 2, 16))
               for c in calls)
    for i, kind in enumerate(LAYER_TYPES):
        module = f"TrinityBlock_{i}/TrinityAttention_0/"
        mine, other = ("attn_window", "attn_full") if kind == SLIDING \
            else ("attn_full", "attn_window")
        assert module + mine in text and module + other not in text
        assert (module + mine + "/attn_rope" in text) == (kind == SLIDING)
        for scope in ("attn_qkv_proj", "attn_qk_norm", "attn_out_proj",
                      *annotate.OUTGATE_SCOPES):
            assert module + scope in text, (i, scope)
        for scope in annotate.POSTNORM_SCOPES:
            assert f"TrinityBlock_{i}/{scope}" in text, (i, scope)
    # the gate's projection is the one product under its name; its sigmoid
    # and product hold none
    assert sum("dot_general" in line and "outgate_proj" in line
               for line in text.splitlines()) == len(LAYER_TYPES)
    mul = [line for line in text.splitlines() if "outgate_mul" in line]
    assert any("logistic" in line for line in mul)
    assert not any("dot_general" in line for line in mul)
    for scope in ("head_logits", "moe_shared", "moe_router", "moe_experts"):
        assert scope in text
    with pytest.raises(ValueError, match="unknown output-gate scope"):
        annotate.outgate_scope("outgate_sigmoid")
    with pytest.raises(ValueError, match="unknown post-norm scope"):
        annotate.postnorm_scope("postnorm_mlp")


def test_per_head_qk_norm_is_before_rotary_and_leaves_v_alone():
    """q and k are normed over each head's own 16 values with one weight
    vector for all heads: a q_proj kernel times 5 changes nothing (the norm
    forgets it), a v_proj kernel times 5 changes the output five-fold up to
    the gate (v is not normed)."""
    module, params, x = _attention_alone(window=20, theta=1e4)

    def scaled(name, by):
        return {**params, name: {"kernel": by * params[name]["kernel"]}}
    apply = jax.jit(module.apply)
    with jax.default_matmul_precision("highest"):
        want = apply({"params": params}, x)
        np.testing.assert_allclose(
            apply({"params": scaled("q_proj", 5.0)}, x), want,
            rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(
            apply({"params": scaled("k_proj", 5.0)}, x), want,
            rtol=1e-3, atol=1e-5)
        np.testing.assert_allclose(
            apply({"params": scaled("v_proj", 5.0)}, x), 5 * want,
            rtol=1e-4, atol=1e-5)
    assert params["q_norm"]["scale"].shape == (16,) == \
        params["k_norm"]["scale"].shape


# -- (c) the routing, and the share tied to the model -----------------------------------

def test_the_bias_moves_the_choice_and_not_the_weights(config_module):
    """Sigmoid scores; a bias large enough to force two experts into every
    token's choice changes which experts are chosen, and the weights are
    still the chosen experts' scores over their sum times ``route_scale``:
    the bias is in neither. The program's router and the reference's
    agree."""
    x = jax.random.normal(jax.random.key(7), (40, 64))
    w = 0.5 * jax.random.normal(jax.random.key(8), (64, 16))
    zero = jnp.zeros((16,))
    forced = zero.at[jnp.asarray([3, 11])].set(10.0)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)), np.float64)
    for bias, want_chosen in ((zero, None), (forced, {3, 11})):
        weights, experts, _, _ = ep.route_sigmoid_topk(x, w, bias, k=2,
                                                       scale=2.826)
        experts = np.asarray(experts)
        if want_chosen:
            assert all(set(row) == want_chosen for row in experts)
        else:
            np.testing.assert_array_equal(
                np.sort(experts, -1), np.sort(np.argsort(-scores, -1)[:, :2],
                                              -1))
        picked = np.take_along_axis(scores, experts, -1)
        np.testing.assert_allclose(
            weights, 2.826 * picked / picked.sum(-1, keepdims=True),
            rtol=1e-5)
        dense, chosen, load = config_module._routing(x, w, bias, 2, 2.826)
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(experts, -1))
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(dense), experts, -1), weights,
            rtol=1e-5)
        assert float(load.sum()) == 2 * 40


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_the_four_shares_of_a_sparse_layer_add_up_to_the_uncut_reference(
        config_module, kind):
    """One sparse layer cut as the deployment cuts it: each of four chips
    holds 4 of the 16 experts (one router over all 16, its own rows of the
    stacked matrices), every chip computes attention, the norms and the
    shared expert alike. The chips' routed parts (each chip's feed-forward
    less the shared expert), summed, with the shared expert counted ONCE,
    are the uncut layer's feed-forward; normed and added to the stream as
    the layer does, they are the uncut reference's layer."""
    sizes = dict(hidden=64, heads=4, kv_heads=2, head_dim=16, experts=16,
                 experts_per_token=4, expert_dim=16, window=24)
    whole = TrinityDecoder(layer_types=(kind,), num_dense_layers=0, vocab=64,
                           dtype=jnp.float32, **sizes)
    tokens = jax.random.randint(jax.random.key(9), (1, 64), 0, 64)
    variables = jax.jit(whole.init)(jax.random.key(10), tokens)
    layer = variables["params"]["TrinityBlock_0"]
    state = variables["router_state"]["TrinityBlock_0"]
    x = variables["params"]["embed_tokens"]["embedding"][tokens] * 8.0
    moe = layer["TrinityMoE_0"]

    def holding(first, count):
        return {**layer, "TrinityMoE_0": {**moe, "experts": {
            name: w[first:first + count]
            for name, w in moe["experts"].items()}}}
    captured = ("TrinityMoE_0", "shared_experts", "post_attention_layernorm")

    @functools.partial(jax.jit, static_argnums=0)  # eager, a share is 15 s
    def share(first):
        return whole.clone(experts_held=(first, 4)).apply(
            {"params": {**variables["params"],
                        "TrinityBlock_0": holding(first, 4)},
             "router_state": variables["router_state"]},
            tokens, mutable=["router_state", "intermediates"],
            capture_intermediates=lambda m, _: m.name in captured)[1]
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(functools.partial(
            config_module._layer, sliding=kind == SLIDING, sparse=True,
            window=24, theta=1e4, held=(0, 16), eps=1e-5, scale=2.826,
            rate=0.0, heads=4, kv_heads=2, head_dim=16, experts_per_token=4,
            bits=None, router_bits=None))(x, layer, state)[0]
        routed = 0.0
        for first in (0, 4, 8, 12):
            new = share(first)
            block = new["intermediates"]["TrinityBlock_0"]
            feed_forward = block["TrinityMoE_0"]["__call__"][0]
            shared = block["TrinityMoE_0"]["shared_experts"]["__call__"][0]
            routed += feed_forward - shared  # this chip's experts' part
            load = new["router_state"]["TrinityBlock_0"]["TrinityMoE_0"][
                "router"]["load"]
            assert float(load.sum()) == 4 * 64  # the router over all 16
        a = x + block["post_attention_layernorm"]["__call__"][0]
        total = routed + shared  # every chip's shared expert is the same one
        scale = layer["post_mlp_layernorm"]["scale"]
        normed = total * jax.lax.rsqrt(
            (total * total).mean(-1, keepdims=True) + 1e-5) * scale
        np.testing.assert_allclose(a + normed, uncut, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(routed).max()) > 1e-4  # the experts matter
    assert float(jnp.abs(shared).max()) > 1e-4  # and the shared one


# -- (d) the policy ------------------------------------------------------------------------

def test_bf16_policy_stays_near_the_reference(config_module):
    """bf16 activations against float32: the loss to 2**-9, the leaves off
    the routers' path to 12%, those on it (near-ties move rows between
    experts: the experts, the routers and the norms around them) to 40%;
    parameters and their gradients stay float32."""
    model, params, state, data = make(jnp.bfloat16, 2, 128)
    loss, _, _, grads = program(model, params, state, data)
    want, _, _, want_grads, _ = reference(config_module, 2, 128)
    assert float(loss) == pytest.approx(float(want), rel=2.0 ** -9)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    for path, error in jax.tree_util.tree_flatten_with_path(errors)[0]:
        keys = [getattr(k, "key", None) for k in path]
        on_routers_path = "TrinityMoE_0" in keys or (
            keys[0] != "TrinityBlock_0" and
            keys[1] in ("pre_mlp_layernorm", "post_mlp_layernorm"))
        assert error < (0.40 if on_routers_path else 0.12), (path, error)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


def test_the_lowered_control_is_not_the_reference(config_module):
    """The control (every product's inputs at 3 mantissa bits, the router's
    at 7) differs from the reference on the leaves off the routers' path by
    more than the bf16 program does."""
    want, _, _, want_grads, _ = reference(config_module, 2, 128)
    low, _, _, low_grads, _ = reference(config_module, 2, 128, lowered=True)
    errors = jax.tree_util.tree_map(relative_l2, low_grads, want_grads)
    assert min(jax.tree_util.tree_leaves(errors["embed_tokens"])) > 0.02
    for name, part in (("TrinityBlock_0", "TrinityAttention_0"),
                       ("TrinityBlock_0", "mlp"),
                       ("TrinityBlock_2", "TrinityAttention_0"),
                       ("TrinityBlock_4", "TrinityAttention_0")):
        assert min(jax.tree_util.tree_leaves(errors[name][part])) > 0.02, \
            (name, part, errors[name][part])
    assert float(low) != float(want)


# -- (e) through dp.make_stateful_train_step -------------------------------------------

def test_bias_rule_over_two_steps_through_the_stateful_step(devices):
    """Four devices, each its own batch, nothing in ``dp.py`` told about the
    model: after a step every router's bias has moved by the config's own
    1e-3 towards the experts the *mean* load of the previous step left
    short, the state holds this step's mean load, parameters are identical
    on the four chips and every leaf trained; the model's parts under their
    scopes inside ``phase_forward_backward``."""
    model = TrinityDecoder(**{**SIZES, "experts_held": None})
    tokens = jax.random.randint(jax.random.key(3), (8, 64), 0, model.vocab)
    variables = jax.jit(model.init)(jax.random.key(4), tokens[:1])
    params, state = variables["params"], variables["router_state"]
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_fn(p, s, b, rng):
        return trinity_loss(model, p, s, b["tokens"], b["labels"])
    optimizer = optax.adamw(1e-3, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = mesh_lib.data_parallel_mesh(devices[:4])
    step = dp.make_stateful_train_step(loss_fn, optimizer, mesh,
                                       donate=False)
    first = params
    params = dp.replicate(params, mesh)
    opt_state = dp.replicate(optimizer.init(params), mesh)
    state = dp.replicate(state, mesh)
    sharded = dp.shard_batch(batch, mesh)
    text = step.lower(params, opt_state, state, sharded,
                      jax.random.key(0)).as_text(debug_info=True)
    for scope in (*annotate.OUTGATE_SCOPES, *annotate.POSTNORM_SCOPES,
                  "attn_full", "attn_window", "moe_router", "moe_experts",
                  "moe_shared", "head_logits", "head_loss"):
        assert re.search(rf'phase_forward_backward/[^"]*{scope}', text), scope

    def routers(tree):
        return [{k: np.asarray(v) for k, v in
                 tree[f"TrinityBlock_{i}"]["TrinityMoE_0"]["router"].items()}
                for i in (1, 2, 3, 4)]
    losses = []
    for i in range(2):
        before = routers(state)
        out = step(params, opt_state, state, sharded, jax.random.key(0))
        params, opt_state, state = out.params, out.opt_state, out.model_state
        losses.append(float(out.loss))
        for was, now in zip(before, routers(state)):
            load = was["load"]
            np.testing.assert_allclose(
                now["expert_bias"], was["expert_bias"]
                + RATE * np.sign(load.mean() - load), atol=1e-7)
            # the mean over four devices of 2 x 2 x 64 pairs each
            assert now["load"].sum() == pytest.approx(2 * tokens.size / 4)
        if i == 0:
            assert all((b["expert_bias"] == 0).all() and
                       (b["load"] == 0).all() for b in before)
    assert np.abs(routers(state)[0]["expert_bias"]).max() == \
        pytest.approx(RATE)
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(np.asarray(out.aux["expert_tokens"][0]),
                                  routers(state)[0]["load"])
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, first)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (f) the configuration ---------------------------------------------------------------

def test_an_unknown_layer_type_or_policy_is_refused(config_module):
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat 'attention' is none of"):
        TrinityTiny(remat="attention").init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="names \\['conv'\\]"):
        TrinityTiny(layer_types=(SLIDING, "conv")).init(
            jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="names \\[\\]"):
        TrinityTiny(layer_types=()).init(jax.random.key(0), tokens)
    # a list that does not name each of num_layers layers once
    config = json.load(open(CONFIG + ".json"))
    uneven = {**config, "layer_types": config["layer_types"][:-1]}
    with pytest.raises(ValueError, match="has not num_layers = 5 entries"):
        config_module.build(uneven, {"seq_len": 16384, "per_chip_batch": 1})


def test_configuration_is_at_the_published_widths(config_module):
    config = json.load(open(CONFIG + ".json"))
    assert config["reduced"] == ["num_layers", "layer_types",
                                 "num_dense_layers", "num_experts",
                                 "vocab_size"]
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key  # nothing else differs
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["num_shared_experts"],
            config["sliding_window"], config["rope_theta"],
            config["rms_norm_eps"], config["route_scale"],
            config["load_balance_coeff"]) == (
        2048, 32, 4, 128, 6144, 1024, 8, 1, 2048, 10000, 1e-5, 2.826, 1e-3)
    assert published["num_hidden_layers"] == 32 and config["num_layers"] == 5
    # published layers 1-5: one leading dense layer and one whole period
    assert config["layer_types"] == published["layer_types"][1:6] == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert published["layer_types"] == [SLIDING, SLIDING, SLIDING, FULL] * 8
    assert published["num_dense_layers"] == 2 and \
        config["num_dense_layers"] == 1
    assert config["vocab_size"] * 8 == published["vocab_size"] == 200192
    assert config["experts_held"] == {"first": 0, "of": 128} and \
        published["num_experts"] == 128 and config["num_experts"] == 16
    assert config["source"].endswith("arcee-ai/Trinity-Mini/blob/main/"
                                     "config.json")
    for key in ("from_the_modelling_code", "output_gate", "qk_norm",
                "post_norms", "positions", "embedding_scale", "experts",
                "expert_bias_rule", "initializer_range", "initialisation",
                "optimizer", "loss", "weights"):
        assert key in config["assumed"], key
    assert config["assumed"]["from_the_modelling_code"].startswith(
        "From the public modelling code and Arcee's report, not from keys")
    assert "8 chips share each layer" in config["deployment"]["chips"]
    for key in ("chips", "bytes_per_parameter", "parameters", "distortion"):
        assert key in config["deployment"], key
    job = config_module.build(config, {"seq_len": 16384,
                                       "per_chip_batch": 1})
    params, state = jax.eval_shape(job.init, jax.random.key(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counted = config["deployment"]["parameters"]
    assert count == counted["what_runs"] == 705473792  # to the unit
    assert counted["what_runs"] == counted["leading_dense_layer"] \
        + 4 * counted["sparse_layer_here"] + counted["embedding_slice"] \
        + counted["head_slice"] + counted["final_norm"]
    assert counted["one_sparse_layer_whole"] == 839131392  # 13.4 GB at 16 B
    assert counted["published_whole"] == 26123970560
    assert set(state) == {f"TrinityBlock_{i}" for i in (1, 2, 3, 4)}
    assert "initializer" not in config  # one value in use: a constant
    assert job.facts["post_norm_start"] == 0.01 == \
        config_module.POST_NORM_START
    assert job.stateful and job.flash_call == (1, 16384, 32, 128, True) \
        and job.flash_layers == 1
    assert job.facts["window_call"] == [1, 16384, 32, 128, 2048]
    assert (job.facts["full_layers"], job.facts["window_layers"]) == (1, 4)
    kinds = {path[-2] if path[-1] in ("kernel", "scale", "weight",
                                      "embedding") else path[-1]
             for path in job.check_leaves}
    assert {"gate_proj", "q_norm", "k_norm", "post_attention_layernorm",
            "post_mlp_layernorm", "router", "down", "embed_tokens",
            "lm_head"} <= kinds
    # the issue's count: a token costs 2.44 GFLOP trained
    forward = job.facts["forward_mflops_per_token"]
    assert 3 * sum(forward.values()) == pytest.approx(2440, abs=5)
    assert job.model_flops_per_item == pytest.approx(
        3e6 * sum(forward.values()))
    shares = {k: round(100 * v / sum(forward.values()), 1)
              for k, v in forward.items()}
    assert shares["full_attention"] == pytest.approx(16.5, abs=0.1)
    assert shares["window_attention"] == pytest.approx(15.5, abs=0.1)
    assert shares["gate_projections"] == pytest.approx(10.3, abs=0.1)
    assert shares["head"] == pytest.approx(12.6, abs=0.1)
    # 16 slots of 1024 expected rows, a slot half as long again
    assert ep.share_slot_rows(8 * 16384, 128) == 1536
    assert ep.share_product((2048, 1024) * 2 + (1024, 2048)) == "blocks"


def test_the_cell_starts_its_post_norms_small_and_nothing_else(
        config_module):
    """``cell_start``: the two norms of a BRANCH's output start at the
    configuration module's ``POST_NORM_START``, in every block; every
    other leaf is the model's own initialisation, bit for bit."""
    model = TrinityDecoder(**SIZES)
    params = jax.jit(model.init)(jax.random.key(0),
                                 jnp.zeros((1, 16), jnp.int32))["params"]
    started = config_module.cell_start(params)
    flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    small = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(started)[0]:
        keys = [k.key for k in path]
        if keys[-2] in ("post_attention_layernorm", "post_mlp_layernorm"):
            np.testing.assert_allclose(leaf, 0.01)
            small += 1
        else:
            assert leaf is flat[path], keys
    assert small == 2 * len(LAYER_TYPES)
    assert float(started["TrinityBlock_0"]["input_layernorm"]["scale"][0]) \
        == 1.0 == float(started["norm"]["scale"][0])


def test_published_geometry_of_the_model():
    """The full published stack builds from the same module: 32 layers by
    the published list, 2 dense, 128 experts and a shared one, 200 192 rows;
    26.12 B parameters, about 3 B of them active a token."""
    model = TrinityMini()
    assert model.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 8
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = shapes["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == \
        26123970560
    assert "mlp" in params["TrinityBlock_1"] and \
        "TrinityMoE_0" in params["TrinityBlock_2"]
    moe = params["TrinityBlock_2"]["TrinityMoE_0"]
    assert moe["experts"]["gate"].shape == (128, 2048, 1024)
    assert moe["shared_experts"]["down_proj"]["kernel"].shape == (1024, 2048)
    assert moe["router"]["weight"].shape == (2048, 128)
    attention = params["TrinityBlock_3"]["TrinityAttention_0"]
    assert attention["gate_proj"]["kernel"].shape == (2048, 4096)
    assert attention["k_proj"]["kernel"].shape == (2048, 512)
    assert len(shapes["router_state"]) == 30
    # a token's active parameters: the embedding is a gather, eight experts
    active = 26123970560 - 30 * 120 * 6291456 - 200192 * 2048
    assert 2.9e9 < active < 3.2e9
