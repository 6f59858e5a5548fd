"""LFM2's training pass through the normal path, on the CPU at a small size:
the program (``models/lfm2.py``: gated short-convolution and attention
operators chosen by one list, dense and sparse feed-forwards by another, a
share of sigmoid-and-bias SwiGLU experts, the embedding's slice as the head)
against the plain float32 reference that ``benchmark/configs/lfm2-8b-a1b.py``
keeps, in float32 and under the bf16 policy; the short convolution, the
per-head norm and the routing by hand; the four shares of one sparse layer
against the uncut layer; the two parts of the tied embedding's gradient; the
bias rule through ``dp.make_stateful_train_step`` on four virtual devices;
the published geometry."""

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

from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.models import (Lfm2_8B_A1B, Lfm2MoeDecoder, Lfm2Tiny,
                                lfm2_loss)
from horovod_tpu.models import lfm2
from horovod_tpu.ops.short_conv import (gated_short_conv,
                                        gated_short_conv_plain)
from horovod_tpu.parallel import dp, ep, mesh as mesh_lib
from horovod_tpu.profiler import annotate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "lfm2-8b-a1b")

# the cell's stack (a leading dense conv layer and one period of sparse
# ones) at hidden 64, 4 heads of 16 on 2 key heads; 4 of 16 experts held
# from 4 on: the rehearsal's widths
LAYER_TYPES = ("conv", "full_attention", "conv", "conv", "conv")
SIZES = dict(layer_types=LAYER_TYPES, num_dense_layers=1, vocab=512,
             hidden=64, heads=4, kv_heads=2, head_dim=16, dense_dim=128,
             experts=16, experts_per_token=2, expert_dim=32, rope_theta=1e6,
             bias_update_rate=3e-3, experts_held=(4, 4))
REFERENCE = dict(layer_types=LAYER_TYPES, num_dense=1, held=(4, 4), eps=1e-5,
                 theta=1e6, scale=1.0, rate=3e-3, heads=4, kv_heads=2,
                 head_dim=16, experts_per_token=2)
RATE = 3e-3


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_lfm2_8b_a1b", CONFIG + ".py")
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
    model = Lfm2MoeDecoder(dtype=dtype, **{**SIZES, **dict(kw)})
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
    return model, variables["params"], state, {
        "tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def make(dtype, batch, seq, seed=0, **kw):
    """(model, float32 parameters, a router state, the batch). Made once a
    module for the same arguments: tests share the arrays, and change none
    in place."""
    return _made(dtype, batch, seq, seed, tuple(sorted(kw.items())))


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, state, data):
    def loss_fn(p):
        return lfm2_loss(model, p, state, data["tokens"], data["labels"])
    return jax.value_and_grad(loss_fn, has_aux=True)(params)


def program(model, params, state, data):
    """(loss, new state, aux, gradients) of the model's own loss: compiled
    once a model (a flax module hashes by its fields) and batch shape."""
    (loss, (new_state, aux)), grads = _program(model, params, state, data)
    return loss, new_state, aux, grads


@functools.lru_cache(maxsize=None)
def _reference(reference_forward, batch, seq, kw):
    _, params, state, data = make(jnp.float32, batch, seq)

    def loss_fn(p):
        loss, new_state, chosen = reference_forward(
            p, state, data, **{**REFERENCE, **dict(kw)})
        return loss, (new_state, chosen)
    (loss, (new_state, chosen)), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, new_state, chosen, grads


def reference(config_module, batch, seq, **kw):
    """(loss, new state, chosen experts, gradients) of the configuration's
    float32 reference on ``make(jnp.float32, batch, seq)``'s parameters,
    state and batch: run once a module for a size."""
    return _reference(config_module.reference_forward, batch, seq,
                      tuple(sorted(kw.items())))


# -- (a) float32 against float32 -------------------------------------------------

@pytest.mark.parametrize("batch,seq,remat", [
    (2, 128, ""), (1, 512, "blocks"), (1, 512, "blocks_keep_attention"),
])
def test_float32_program_matches_the_reference(config_module, batch, seq,
                                               remat):
    """Both operators and both feed-forwards in the stack: the loss, every
    leaf's gradient, the new state (the bias after the rule, this step's
    load) and the experts every token chose."""
    model, params, state, data = make(jnp.float32, batch, seq, remat=remat)
    loss, new_state, aux, grads = program(model, params, state, data)
    want, want_state, chosen, want_grads = reference(config_module, batch,
                                                     seq)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 2e-4, errors
    for got, wanted in zip(jax.tree_util.tree_leaves(new_state),
                           jax.tree_util.tree_leaves(want_state)):
        np.testing.assert_allclose(got, wanted, atol=1e-7)
    assert set(new_state) == {f"Lfm2Block_{i}" for i in (1, 2, 3, 4)}
    loads = np.stack([np.bincount(np.asarray(c).ravel(), minlength=16)
                      for c in chosen])
    np.testing.assert_array_equal(np.asarray(aux["expert_tokens"]), loads)
    assert loads.sum() == 4 * 2 * batch * seq


def test_another_stack_is_another_model(config_module):
    """The reference told another ``layer_types`` or another count of dense
    layers computes something else: the lists are read, not assumed."""
    model, params, state, data = make(jnp.float32, 2, 128)
    loss = float(program(model, params, state, data)[0])
    assert loss == pytest.approx(float(reference(config_module, 2, 128)[0]),
                                 rel=2e-6)
    swapped = make(jnp.float32, 2, 128, layer_types=(
        "conv", "conv", "full_attention", "conv", "conv"))
    # the same shapes, so the same parameters will not do: count instead
    kinds = {name: sorted(block) for name, block in swapped[1].items()
             if name.startswith("Lfm2Block_")}
    assert "Lfm2Attention_0" in kinds["Lfm2Block_2"] and \
        "Lfm2ShortConv_0" in kinds["Lfm2Block_1"]
    two_dense = make(jnp.float32, 2, 128, num_dense_layers=2)
    assert "Lfm2Mlp_0" in two_dense[1]["Lfm2Block_1"] and \
        set(two_dense[2]) == {"Lfm2Block_2", "Lfm2Block_3", "Lfm2Block_4"}


# -- (b) the gated short convolution by hand ---------------------------------------

def _conv_by_hand(bcu, w):
    """Loops over positions and taps, float64."""
    bcu, w = np.asarray(bcu, np.float64), np.asarray(w, np.float64)
    d = w.shape[1]
    b_run, c_run, u_run = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    g = b_run * u_run
    out = np.zeros_like(g)
    for t in range(g.shape[1]):
        for j in range(w.shape[0]):
            at = t - (w.shape[0] - 1) + j
            if at >= 0:
                out[:, t] += w[j] * g[:, at]
    return c_run * out


WRITINGS = pytest.mark.parametrize(
    "conv", [gated_short_conv_plain, gated_short_conv],
    ids=["plain", "kernels"])


@WRITINGS
@pytest.mark.parametrize("dtype,taps,seq", [
    (jnp.float32, 3, 37), (jnp.bfloat16, 3, 37), (jnp.float32, 4, 16),
])
def test_short_convolution_by_hand(conv, dtype, taps, seq):
    """``C * conv(B * u)`` against loops, either writing; a length that is no
    multiple of anything; float32 gates and sum from a bf16 input."""
    bcu = jax.random.normal(jax.random.key(0), (2, seq, 3 * 8)).astype(dtype)
    w = jax.random.uniform(jax.random.key(1), (taps, 8), jnp.float32, -1, 1)
    got = jax.jit(conv, static_argnums=2)(bcu, w, dtype)
    assert got.dtype == dtype and got.shape == (2, seq, 8)
    want = _conv_by_hand(bcu.astype(jnp.float32), w)
    np.testing.assert_allclose(
        np.asarray(got.astype(jnp.float32)), want,
        rtol=2.0 ** -7 if dtype == jnp.bfloat16 else 1e-5, atol=1e-6)


@WRITINGS
@pytest.mark.parametrize("at", [0, 1, 5, 11, 15, 16, 31])
def test_a_token_sees_itself_and_two_before_it_and_nothing_later(conv, at):
    """One position of ``B * u`` set: the output is the taps, last tap first,
    at that position and the two after it, cut off at the sequence's end;
    before the sequence are zeros, so position 0 reads the last tap alone.
    The kernels cut 32 positions into two tiles of 16: a position at a
    tile's end lands in the next one."""
    seq, d = 32, 4
    b_run = jnp.zeros((1, seq, d)).at[0, at].set(1.0)
    bcu = jnp.concatenate([b_run, jnp.ones((1, seq, d)),
                           jnp.ones((1, seq, d))], axis=-1)
    w = jnp.asarray([[0.5] * d, [2.0] * d, [3.0] * d])
    got = np.asarray(conv(bcu, w, jnp.float32))[0, :, 0]
    want = np.zeros(seq)
    for ahead, tap in enumerate((3.0, 2.0, 0.5)):
        if at + ahead < seq:
            want[at + ahead] = tap
    np.testing.assert_array_equal(got, want)
    # the gate after: C scales the output where it lands, B and u where read
    scaled = bcu.at[0, :, d:2 * d].set(2.0)
    np.testing.assert_array_equal(
        np.asarray(conv(scaled, w, jnp.float32))[0, :, 0], 2 * want)


@WRITINGS
def test_short_convolution_gradients_by_hand(conv):
    """autodiff's gradients of the plain writing, and the backward kernel's,
    against the formulas:
    ``dC = dy * c``, ``dg_t = sum_j w[j] (dy C)_{t+K-1-j}``, ``dB = dg u``,
    ``du = dg B``, ``dw[j] = sum_t (dy C)_t g_{t-K+1+j}``."""
    seq, d, taps = 41, 3, 3  # the kernels: three tiles of 16, 7 of padding
    bcu = jax.random.normal(jax.random.key(2), (1, seq, 3 * d))
    w = jax.random.normal(jax.random.key(3), (taps, d))
    dy = jax.random.normal(jax.random.key(4), (1, seq, d))
    got_bcu, got_w = jax.grad(
        lambda bcu, w: jnp.sum(dy * conv(bcu, w, jnp.float32)),
        argnums=(0, 1))(bcu, w)
    x, wn, dyn = (np.asarray(a, np.float64) for a in (bcu, w, dy))
    b_run, c_run, u_run = x[..., :d], x[..., d:2 * d], x[..., 2 * d:]
    g = b_run * u_run
    conv = _conv_by_hand(np.concatenate(
        [b_run, np.ones_like(c_run), u_run], -1), wn)
    dc = dyn * c_run
    dg, dw = np.zeros_like(g), np.zeros_like(wn)
    for t in range(seq):
        for j in range(taps):
            at = t - (taps - 1) + j
            if at >= 0:
                dg[:, at] += wn[j] * dc[:, t]
                dw[j] += (dc[:, t] * g[:, at]).sum(0)
    want = np.concatenate([dg * u_run, dyn * conv, dg * b_run], -1)
    np.testing.assert_allclose(got_bcu, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_w, dw, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype,seq,d", [
    (jnp.float32, 512, 256), (jnp.bfloat16, 512, 256),
    (jnp.float32, 300, 40), (jnp.bfloat16, 300, 40),
    (jnp.float32, 528, 640), (jnp.bfloat16, 1040, 128),
])
def test_the_kernels_are_the_plain_writing(dtype, seq, d):
    """Forward and all gradients: whole tiles of 256 positions; a length
    that is no multiple of the 16-row tile (300: padded behind its end); 33
    and 65 tiles of 16 (528, 1040); channels worked in chunks of 256, 128
    and in one piece of 40 and of 640 = 5 x 128."""
    bcu = jax.random.normal(jax.random.key(0), (2, seq, 3 * d)).astype(dtype)
    w = jax.random.uniform(jax.random.key(1), (3, d), jnp.float32, -1, 1)
    dy = jax.random.normal(jax.random.key(2), (2, seq, d)).astype(dtype)

    @functools.partial(jax.jit, static_argnums=0)
    def out_and_grads(conv, bcu, w):
        y, pull = jax.vjp(lambda bcu, w: conv(bcu, w, dtype), bcu, w)
        return (y,) + pull(dy)
    got = out_and_grads(gated_short_conv, bcu, w)
    want = out_and_grads(gated_short_conv_plain, bcu, w)
    for a, b, limit in zip(got, want, (
            2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6,
            2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6, 1e-5)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert relative_l2(a, b) < limit
    assert got[0].dtype == dtype and got[2].dtype == jnp.float32


def test_counter_says_the_direction_and_a_wrong_width_is_refused():
    """One count a pass traced: a gradient traces the forward and the
    backward."""
    def counter(direction):
        return get_registry().counter("hvd_shortconv_passes_total",
                                      direction=direction)
    before = {d: counter(d).value for d in ("fwd", "bwd")}
    bcu, w = jnp.ones((1, 7, 15)), jnp.ones((3, 5))  # no other case's shapes
    jax.jit(gated_short_conv, static_argnums=2)(bcu, w, jnp.float32)
    assert counter("fwd").value - before["fwd"] == 1
    assert counter("bwd").value == before["bwd"]
    jax.grad(lambda x: jnp.sum(gated_short_conv(x, w, jnp.float32)))(bcu)
    assert counter("fwd").value - before["fwd"] == 2
    assert counter("bwd").value - before["bwd"] == 1
    for conv in (gated_short_conv, gated_short_conv_plain):
        with pytest.raises(ValueError, match="taps over 4"):
            conv(bcu, jnp.ones((3, 4)), jnp.float32)
    with pytest.raises(ValueError, match="17 taps"):
        gated_short_conv(jnp.ones((1, 32, 15)), jnp.ones((17, 5)))


def test_the_operator_runs_under_its_three_scopes():
    """``shortconv_mix`` holds the gates and the taps and no product; the
    projections carry their own scope; an unknown name is refused."""
    model = Lfm2Tiny(layer_types=("conv",), num_dense_layers=1)
    tokens = jnp.zeros((1, 16), jnp.int32)
    variables = model.init(jax.random.key(0), tokens)
    text = jax.jit(lambda v: model.apply(v, tokens)).lower(
        variables).as_text(debug_info=True)
    lines = text.splitlines()
    for scope in annotate.SHORTCONV_SCOPES:
        assert any(scope in line for line in lines), scope
    mix = [line for line in lines if "shortconv_mix" in line]
    assert mix and not any("dot_general" in line for line in mix)
    assert sum("dot_general" in line and "shortconv_in_proj" in line
               for line in lines) == 1
    assert sum("dot_general" in line and "shortconv_out_proj" in line
               for line in lines) == 1
    with pytest.raises(ValueError, match="unknown short-convolution scope"):
        annotate.shortconv_scope("shortconv_conv")


# -- (c) per-head norm and rotary, routing, by hand -----------------------------------

def test_per_head_qk_norm_then_rotary_by_hand():
    """q and k are normed over each head's own 16 values with one weight
    vector for all heads, THEN turned: against numpy on the projections."""
    heads, kv_heads, head_dim, theta, seq = 4, 2, 16, 1e4, 24
    module = lfm2.Lfm2Attention(heads, kv_heads, head_dim, theta,
                                dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(5), (1, seq, 64))
    params = module.init(jax.random.key(6), x)["params"]
    scales = {name: 1.0 + 0.5 * jax.random.normal(jax.random.key(i), (16,))
              for i, name in enumerate(("q_layernorm", "k_layernorm"))}
    params = {**params, **{name: {"scale": s} for name, s in scales.items()}}
    got = np.asarray(module.apply({"params": params}, x), np.float64)[0]
    xn = np.asarray(x, np.float64)[0]
    proj = {name: xn @ np.asarray(params[name]["kernel"], np.float64)
            for name in ("q_proj", "k_proj", "v_proj")}

    def normed_turned(y, n, scale):
        y = y.reshape(seq, n, head_dim)
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(scale, np.float64)
        angles = np.arange(seq)[:, None] * theta ** (
            -np.arange(0, head_dim, 2) / head_dim)
        cos, sin = np.cos(angles)[:, None], np.sin(angles)[:, None]
        a, b = y[..., :head_dim // 2], y[..., head_dim // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    q = normed_turned(proj["q_proj"], heads, scales["q_layernorm"])
    k = normed_turned(proj["k_proj"], kv_heads, scales["k_layernorm"])
    v = proj["v_proj"].reshape(seq, kv_heads, head_dim)
    out = np.zeros((seq, heads, head_dim))
    for h in range(heads):
        s = q[:, h] @ k[:, h // 2].T / np.sqrt(head_dim)
        s = np.where(np.tril(np.ones((seq, seq), bool)), s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, h // 2]
    want = out.reshape(seq, -1) @ np.asarray(params["out_proj"]["kernel"],
                                             np.float64)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_the_bias_moves_the_choice_and_not_the_weights(config_module):
    """Sigmoid scores; a bias large enough to force two experts into every
    token's choice changes which experts are chosen, and the weights are
    still the chosen experts' scores over their sum: the bias is in neither.
    The program's router and the reference's agree."""
    x = jax.random.normal(jax.random.key(7), (40, 64))
    w = 0.5 * jax.random.normal(jax.random.key(8), (64, 16))
    zero = jnp.zeros((16,))
    forced = zero.at[jnp.asarray([3, 11])].set(10.0)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)), np.float64)
    for bias, want_chosen in ((zero, None), (forced, {3, 11})):
        weights, experts, _, _ = ep.route_sigmoid_topk(x, w, bias, k=2)
        experts = np.asarray(experts)
        if want_chosen:
            assert all(set(row) == want_chosen for row in experts)
        else:
            np.testing.assert_array_equal(
                np.sort(experts, -1), np.sort(np.argsort(-scores, -1)[:, :2],
                                              -1))
        picked = np.take_along_axis(scores, experts, -1)
        np.testing.assert_allclose(
            weights, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
        dense, chosen, load = config_module._routing(x, w, bias, 2, 1.0)
        np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                      np.sort(experts, -1))
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(dense), experts, -1), weights,
            rtol=1e-5)
        assert float(load.sum()) == 2 * 40


# -- (d) the share and the tied slice, tied to the model -------------------------------

@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_the_four_shares_of_a_sparse_layer_add_up_to_the_uncut_reference(
        config_module, kind):
    """One sparse layer cut as the deployment cuts it: each of four chips
    holds 8 of the 32 experts (one router over all 32, its own rows of the
    stacked matrices), every chip computes the operator alike, and the
    chips' expert parts add up to the uncut layer's expert sum: the
    program's output on share ``s`` minus what the operator and the
    residual give alone, summed over ``s``, against the reference holding
    all 32."""
    sizes = dict(hidden=64, heads=4, kv_heads=2, head_dim=16, experts=32,
                 experts_per_token=4, expert_dim=16, rope_theta=1e6)
    whole = Lfm2MoeDecoder(layer_types=(kind,), num_dense_layers=0, vocab=64,
                           dtype=jnp.float32, **sizes)
    tokens = jax.random.randint(jax.random.key(9), (1, 64), 0, 64)
    variables = whole.init(jax.random.key(10), tokens)
    layer = variables["params"]["Lfm2Block_0"]
    state = variables["router_state"]["Lfm2Block_0"]
    x = variables["params"]["embed_tokens"]["embedding"][tokens]
    reference_sizes = dict(
        conv=kind == "conv", sparse=True, eps=1e-5, theta=1e6, scale=1.0,
        rate=0.0, heads=4, kv_heads=2, head_dim=16, experts_per_token=4,
        bits=None, router_bits=None)
    moe = layer["Lfm2SparseMoe_0"]

    def holding(first, count):
        return {**layer, "Lfm2SparseMoe_0": {
            "gate": moe["gate"], "experts": {
                name: w[first:first + count]
                for name, w in moe["experts"].items()}}}
    with jax.default_matmul_precision("highest"):
        uncut = config_module._layer(x, layer, state, held=(0, 32),
                                     **reference_sizes)[0]
        # what the operator and the residual give alone: a share of nothing
        alone = config_module._layer(x, holding(0, 0), state, held=(0, 0),
                                     **reference_sizes)[0]
        total = jnp.zeros_like(uncut)
        for first in (0, 8, 16, 24):
            model = whole.clone(experts_held=(first, 8))
            out, new = model.apply(
                {"params": {**variables["params"],
                            "Lfm2Block_0": holding(first, 8)},
                 "router_state": variables["router_state"]},
                tokens, mutable=["router_state", "intermediates"],
                capture_intermediates=lambda m, _: m.name == "Lfm2Block_0")
            block_out = new["intermediates"]["Lfm2Block_0"]["__call__"][0]
            total += block_out - alone
            load = new["router_state"]["Lfm2Block_0"]["Lfm2SparseMoe_0"][
                "gate"]["load"]
            assert float(load.sum()) == 4 * 64  # the router over all 32
        np.testing.assert_allclose(total, uncut - alone, rtol=2e-4,
                                   atol=2e-6)
    assert float(jnp.abs(uncut - alone).max()) > 1e-4  # the experts matter


def test_the_tied_embeddings_gradient_is_the_gathers_plus_the_heads(
        config_module):
    """With a head of its own beside the embedding (what the model is not)
    the reference gives the gather's part and the head product's part; the
    program's one gradient is their sum, and neither part is nothing: a row
    no token of the batch holds has the head's part alone."""
    model, params, state, data = make(jnp.float32, 2, 128)
    grads = program(model, params, state, data)[3]
    embedding = params["embed_tokens"]["embedding"]

    def untied(p, head):
        return config_module.reference_forward(p, state, data, head=head,
                                               **REFERENCE)[0]
    by_gather, by_head = jax.jit(jax.grad(untied, argnums=(0, 1)))(
        params, embedding)
    by_gather = np.asarray(by_gather["embed_tokens"]["embedding"])
    by_head = np.asarray(by_head)
    assert relative_l2(grads["embed_tokens"]["embedding"],
                       by_gather + by_head) < 2e-4
    absent = np.setdiff1d(np.arange(model.vocab),
                          np.asarray(data["tokens"]).ravel())
    assert len(absent) > 50 and not by_gather[absent].any()
    assert np.abs(by_head[absent]).max() > 0 and np.abs(by_gather).max() > 0
    assert "LmHead" not in params and len(params) == 5 + 2


# -- (e) the bf16 policy -------------------------------------------------------------

def test_bf16_policy_stays_near_the_reference(config_module):
    """bf16 activations against float32: the loss to 2**-10, the leaves off
    the routers' path to 6%, those on it (near-ties move rows between
    experts: the experts, the routers and the norm whose output a router
    reads) to 30%; parameters and their gradients stay float32."""
    model, params, state, data = make(jnp.bfloat16, 2, 128)
    loss, _, _, grads = program(model, params, state, data)
    want, _, _, want_grads = reference(config_module, 2, 128)
    assert float(loss) == pytest.approx(float(want), rel=2.0 ** -10)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    for path, error in jax.tree_util.tree_flatten_with_path(errors)[0]:
        keys = [getattr(k, "key", None) for k in path]
        on_routers_path = "Lfm2SparseMoe_0" in keys or (
            "ffn_norm" in keys and keys[0] != "Lfm2Block_0")
        assert error < (0.30 if on_routers_path else 0.06), (path, error)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


def test_the_lowered_control_is_not_the_reference(config_module):
    """The control (every product's inputs at 3 mantissa bits, the router's
    at 7) differs from the reference on the leaves off the routers' path by
    more than the bf16 program does."""
    want, _, _, want_grads = reference(config_module, 2, 128)
    low, _, _, low_grads = reference(config_module, 2, 128, lowered=True)
    errors = jax.tree_util.tree_map(relative_l2, low_grads, want_grads)
    assert min(jax.tree_util.tree_leaves(errors["embed_tokens"])) > 0.02
    for name, part in (("Lfm2Block_0", "Lfm2ShortConv_0"),
                       ("Lfm2Block_0", "Lfm2Mlp_0"),
                       ("Lfm2Block_1", "Lfm2Attention_0"),
                       ("Lfm2Block_4", "Lfm2ShortConv_0")):
        assert min(jax.tree_util.tree_leaves(errors[name][part])) > 0.02, \
            (name, part, errors[name][part])
    assert float(low) != float(want)


# -- (f) through dp.make_stateful_train_step -------------------------------------------

def test_bias_rule_over_two_steps_through_the_stateful_step(devices):
    """Four devices, each its own batch, nothing in ``dp.py`` told about the
    model: after a step every router's bias has moved by the rate towards
    the experts the *mean* load of the previous step left short, the state
    holds this step's mean load, parameters are identical on the four chips
    and every leaf trained; the operators under their scopes inside
    ``phase_forward_backward``."""
    model = Lfm2MoeDecoder(**{**SIZES, "experts_held": None})
    tokens = jax.random.randint(jax.random.key(3), (8, 64), 0, model.vocab)
    variables = model.init(jax.random.key(4), tokens[:1])
    params, state = variables["params"], variables["router_state"]
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_fn(p, s, b, rng):
        return lfm2_loss(model, p, s, b["tokens"], b["labels"])
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
    for scope in (*annotate.SHORTCONV_SCOPES, "attn_full", "moe_router",
                  "moe_experts"):
        assert re.search(rf'phase_forward_backward/[^"]*{scope}', text), scope

    def gates(tree):
        return [{k: np.asarray(v) for k, v in
                 tree[f"Lfm2Block_{i}"]["Lfm2SparseMoe_0"]["gate"].items()}
                for i in (1, 2, 3, 4)]
    losses = []
    for i in range(2):
        before = gates(state)
        out = step(params, opt_state, state, sharded, jax.random.key(0))
        params, opt_state, state = out.params, out.opt_state, out.model_state
        losses.append(float(out.loss))
        for was, now in zip(before, gates(state)):
            load = was["load"]
            np.testing.assert_allclose(
                now["expert_bias"], was["expert_bias"]
                + RATE * np.sign(load.mean() - load), atol=1e-7)
            # the mean over four devices of 2 x 2 x 64 pairs each
            assert now["load"].sum() == pytest.approx(2 * tokens.size / 4)
        if i == 0:
            assert all((b["expert_bias"] == 0).all() and
                       (b["load"] == 0).all() for b in before)
    assert np.abs(gates(state)[0]["expert_bias"]).max() == pytest.approx(RATE)
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(np.asarray(out.aux["expert_tokens"][0]),
                                  gates(state)[0]["load"])
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, first)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (g) the configuration ---------------------------------------------------------------

def test_an_unknown_operator_or_policy_is_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat 'attention' is none of"):
        Lfm2Tiny(remat="attention").init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="names \\['sliding_attention'\\]"):
        Lfm2Tiny(layer_types=("conv", "sliding_attention")).init(
            jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="names \\[\\]"):
        Lfm2Tiny(layer_types=()).init(jax.random.key(0), tokens)


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
            config["conv_L_cache"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["rope_theta"], config["norm_eps"]) == (
        2048, 32, 8, 64, 3, 7168, 1792, 4, 1000000, 1e-5)
    assert published["num_hidden_layers"] == 24 and config["num_layers"] == 5
    # published layers 1-5: one leading dense layer and one whole period
    assert config["layer_types"] == published["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert published["num_dense_layers"] == 2 and \
        config["num_dense_layers"] == 1
    assert config["vocab_size"] * 4 == published["vocab_size"] == 65536
    assert config["experts_held"] == {"first": 0, "of": 32} and \
        published["num_experts"] == 32 and config["num_experts"] == 8
    assert config["tie_word_embeddings"] is True
    for key in ("tie_word_embeddings", "head_dim", "norm_topk_epsilon",
                "expert_bias_rule", "optimizer", "initialisation", "loss",
                "weights"):
        assert key in config["assumed"], key
    for key in ("chips", "bytes_per_parameter", "parameters", "distortion"):
        assert key in config["deployment"], key
    job = config_module.build(config, {"seq_len": 16384,
                                       "per_chip_batch": 1})
    params, state = jax.eval_shape(job.init, jax.random.key(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counted = config["deployment"]["parameters"]
    assert count == counted["what_runs"] == 507820160  # to the unit
    assert counted["what_runs"] == counted["leading_dense_layer"] \
        + counted["sparse_attention_layer_here"] \
        + 3 * counted["sparse_conv_layer_here"] \
        + counted["embedding_slice_tied"] + counted["final_norm"]
    assert set(state) == {f"Lfm2Block_{i}" for i in (1, 2, 3, 4)}
    assert job.stateful and job.flash_call == (1, 16384, 32, 64, True) \
        and job.flash_layers == 1
    assert job.facts["tied_head"] and job.facts["shortconv_layers"] == 4
    # the issue's count: a token costs 466 MFLOP forward
    forward = job.facts["forward_mflops_per_token"]
    assert sum(forward.values()) == pytest.approx(466.1, abs=0.1)
    assert job.model_flops_per_item == pytest.approx(
        3e6 * sum(forward.values()))
    shares = {k: round(100 * v / sum(forward.values()))
              for k, v in forward.items()}
    assert shares == {"shortconv": 29, "attention": 19, "dense": 19,
                      "experts": 19, "head": 14}
    assert ep.share_slot_rows(4 * 16384, 32) == 3072


def test_published_geometry_of_the_model():
    """The full published stack builds from the same module: 24 layers by
    the published lists, 32 experts, 65 536 rows; 8.3 B parameters."""
    model = Lfm2_8B_A1B()
    assert model.layer_types.count("full_attention") == 6 and \
        model.layer_types.count("conv") == 18 and \
        [i for i, kind in enumerate(model.layer_types)
         if kind == "full_attention"] == [2, 6, 10, 14, 18, 21]
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = shapes["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == \
        8339929856
    assert "Lfm2Mlp_0" in params["Lfm2Block_1"] and \
        "Lfm2SparseMoe_0" in params["Lfm2Block_2"]
    assert params["Lfm2Block_2"]["Lfm2SparseMoe_0"]["experts"]["w1"].shape \
        == (32, 2048, 1792)
    assert params["Lfm2Block_0"]["Lfm2ShortConv_0"]["conv"].shape == \
        (3, 2048)
    assert len(shapes["router_state"]) == 22
    # a token's active parameters: the embedding is a gather, four experts
    active = 8339929856 - 22 * 28 * 11010048 - 65536 * 2048
    assert 1.4e9 < active < 1.6e9
