"""``models/nemotron_h.py`` (Nemotron-H: Mamba-2 mixers, a sigmoid-routed
expert share with a shared expert, grouped-query attention without
positions) against the plain float32 reference of
``benchmark/configs/nemotron-3-nano-30b-a3b.py``, at sizes a CPU runs:
every kind of layer alone, the whole decoder (loss, gradients, the returned
state), the router on equal inputs, the bias rule through
``dp.make_stateful_train_step``, the pattern string, and the key heads."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import (Nemotron3Nano30B, NemotronHDecoder,
                                NemotronHTiny, nemotron_h_loss)
from horovod_tpu.models import nemotron_h
from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.parallel import dp, ep

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HELD = (2, 4)
SEQ = 64
SIZES = dict(mamba_heads=4, mamba_head_dim=8, state=8, groups=2, heads=4,
             kv_heads=2, head_dim=8, experts_per_token=2)
RATE = 1e-3
MATRIX_SCALE = 8.0


@pytest.fixture(scope="module")
def config_module():
    """The benchmark's configuration file: the reference lives there."""
    import sys
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    path = os.path.join(REPO, "benchmark", "configs",
                        "nemotron-3-nano-30b-a3b.py")
    spec = importlib.util.spec_from_file_location("bench_nemotron", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def make(pattern, seed=0, batch=2, dtype=jnp.float32, **kw):
    model = NemotronHTiny(pattern=pattern, experts_held=HELD, dtype=dtype,
                          **kw)
    k_init, k_tokens, k_load = jax.random.split(jax.random.key(seed), 3)
    tokens = jax.random.randint(k_tokens, (batch, SEQ), 0, model.vocab)
    variables = model.init(k_init, tokens)
    state = variables.get("router_state", {})
    # a previous step's load and biases a few steps old, so that the rule
    # has something to move
    leaves, tree = jax.tree_util.tree_flatten_with_path(state)
    keys = jax.random.split(k_load, len(leaves))
    state = jax.tree_util.tree_unflatten(tree, [
        jnp.round(jax.random.uniform(k, x.shape) * 20)
        * (1.0 if "load" in jax.tree_util.keystr(path) else RATE)
        for k, (path, x) in zip(keys, leaves)])
    data = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    # projections and router logits of spread ~1, as 2688 terms of 0.02
    # give: at 32 terms the conv's bias would drown the tokens and every
    # token would choose the same experts
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * MATRIX_SCALE if x.ndim >= 2
        and "conv1d" not in jax.tree_util.keystr(path) else x,
        variables["params"])
    return model, params, state, data


def reference(config_module, pattern, params, state, data):
    fn = functools.partial(
        config_module.reference_forward, pattern=pattern, held=HELD,
        eps=1e-5, scale=2.5, rate=RATE, **SIZES)
    (loss, (new_state, chosen)), grads = jax.jit(jax.value_and_grad(
        lambda p: (lambda out: (out[0], out[1:]))(fn(p, state, data)),
        has_aux=True))(params)
    return loss, new_state, chosen, grads


def program(model, params, state, data):
    (loss, (new_state, aux)), grads = jax.jit(jax.value_and_grad(
        lambda p: nemotron_h_loss(model, p, state, data["tokens"],
                                  data["labels"]), has_aux=True))(params)
    return loss, new_state, aux, grads


# -- (a) each kind of layer, and the decoder, in float32 --------------------------

@pytest.mark.parametrize("pattern,remat", [
    ("M", ""), ("E", ""), ("*", ""), ("MEM*EME", ""), ("ME*E", "ME*")],
    ids=["mamba2", "experts", "attention", "decoder", "decoder-remat"])
def test_float32_program_matches_the_reference(config_module, pattern,
                                               remat):
    """With float32 activations the program and the reference compute the
    same function and choose the same experts: loss, every gradient leaf,
    the new biases and the loads."""
    model, params, state, data = make(pattern, remat=remat)
    loss, new_state, aux, grads = program(model, params, state, data)
    want, want_state, chosen, want_grads = reference(
        config_module, pattern, params, state, data)
    assert abs(float(loss) - float(want)) <= 2e-5 * abs(float(want))
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 2e-3, errors
    if "E" in pattern:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=1e-7),
            new_state, want_state)
        loads = np.asarray(aux["expert_tokens"])
        assert loads.shape == (pattern.count("E"), model.experts)
        assert (loads.sum(axis=-1)
                == model.experts_per_token * data["tokens"].size).all()
        counts = np.stack([np.bincount(np.asarray(c).ravel(),
                                       minlength=model.experts)
                           for c in chosen])
        np.testing.assert_array_equal(loads, counts)
    else:
        assert new_state == {} and want_state == {}


def test_bf16_policy_stays_near_the_reference(config_module):
    """bf16 activations against float32 where no router's discontinuity
    lies on the path (mixers and attention): the loss within 2**-10, every
    gradient leaf within 3%. With expert layers a near-tie sends a token
    elsewhere and every leaf upstream sees it; that comparison is the
    chip's, at the published widths."""
    pattern = "M*M"
    model, params, state, data = make(pattern, dtype=jnp.bfloat16, batch=4)
    loss, _, _, grads = program(model, params, state, data)
    want, _, _, want_grads = reference(config_module, pattern, params, state,
                                       data)
    assert abs(float(loss) - float(want)) <= 2.0 ** -10 * abs(float(want))
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 0.03, errors


# -- (b) the router on equal inputs -------------------------------------------------

def test_router_choices_and_weights_by_hand():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(32, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 12), jnp.float32)
    zero = jnp.zeros((12,), jnp.float32)
    weights, chosen, scores, logits = ep.route_sigmoid_topk(x, w, zero, 6,
                                                            2.5)
    want_scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                        @ np.asarray(w, np.float64))))
    np.testing.assert_allclose(np.asarray(scores), want_scores, rtol=1e-5)
    want_chosen = np.argsort(-want_scores, axis=-1)[:, :6]
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(want_chosen, -1))
    picked = np.take_along_axis(want_scores, np.asarray(chosen), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(64, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 12) * 0.1, jnp.float32)
    zero = jnp.zeros((12,), jnp.float32)
    plain = ep.route_sigmoid_topk(x, w, zero, 3, 2.5)
    # a bias of 10 puts expert 7 into every token's choice ...
    lifted = ep.route_sigmoid_topk(x, w, zero.at[7].set(10.0), 3, 2.5)
    assert (np.asarray(lifted[1]) == 7).any(axis=-1).all()
    assert not (np.asarray(plain[1]) == 7).any(axis=-1).all()
    # ... and its weight is still its score without the bias, renormalised
    scores = np.asarray(lifted[2])
    np.testing.assert_array_equal(scores, np.asarray(plain[2]))
    picked = np.take_along_axis(scores, np.asarray(lifted[1]), axis=-1)
    np.testing.assert_allclose(
        np.asarray(lifted[0]), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    # no gradient reaches the bias
    grad = jax.grad(lambda b: ep.route_sigmoid_topk(x, w, b, 3, 2.5)[0].sum()
                    )(zero.at[7].set(10.0))
    assert float(jnp.abs(grad).sum()) == 0.0


def test_bf16_router_scores_choose_other_experts():
    """One precision below what the model states for the router: on equal
    inputs a float32 router picks what an exact one picks, a bf16 one does
    not. The chip's reference check cannot see this (it compares the loss
    and picked gradients); this test is the guard."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4096, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 128) * 0.1, jnp.float32)
    zero = jnp.zeros((128,), jnp.float32)
    exact = np.argsort(-(np.asarray(x, np.float64)
                         @ np.asarray(w, np.float64)), axis=-1)[:, :6]
    chosen = np.asarray(ep.route_sigmoid_topk(x, w, zero, 6)[1])
    assert (np.sort(chosen, -1) == np.sort(exact, -1)).all()
    rounded = np.asarray(ep.route_sigmoid_topk(
        x.astype(jnp.bfloat16).astype(jnp.float32),
        w.astype(jnp.bfloat16).astype(jnp.float32), zero, 6)[1])
    differing = (np.sort(rounded, -1) != np.sort(exact, -1)).any(-1).mean()
    assert differing > 0.005, differing


# -- (c) the bias rule through dp.make_stateful_train_step --------------------------

def test_bias_rule_over_three_steps_through_the_stateful_step(devices):
    """Four devices, each its own batch: after a step every router's bias
    has moved by the rate towards the experts the *mean* load of the
    previous step left short, and the state holds this step's mean load."""
    hvd.init(devices=devices[:4])
    mesh = hvd.mesh()
    model = NemotronHTiny(pattern="ME*E")
    tokens = jax.random.randint(jax.random.key(3), (8, SEQ), 0, model.vocab)
    variables = model.init(jax.random.key(4), tokens[:1])
    params, state = variables["params"], variables["router_state"]
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    def loss_fn(p, s, b, rng):
        return nemotron_h_loss(model, p, s, b["tokens"], b["labels"])
    optimizer = optax.adamw(1e-3)
    step = dp.make_stateful_train_step(loss_fn, optimizer, mesh,
                                       donate=False)
    params = dp.replicate(params, mesh)
    opt_state = dp.replicate(optimizer.init(params), mesh)
    state = dp.replicate(state, mesh)
    sharded = dp.shard_batch(batch, mesh)
    gates = [("NemotronHBlock_1", "NemotronHMoE_0", "gate"),
             ("NemotronHBlock_3", "NemotronHMoE_0", "gate")]

    def gate(tree, path):
        for key in path:
            tree = tree[key]
        return {k: np.asarray(v) for k, v in tree.items()}
    losses = []
    for i in range(3):
        before = [gate(state, g) for g in gates]
        out = step(params, opt_state, state, sharded, jax.random.key(0))
        params, opt_state, state = out.params, out.opt_state, out.model_state
        losses.append(float(out.loss))
        for was, path in zip(before, gates):
            now = gate(state, path)
            load = was["load"]
            np.testing.assert_allclose(
                now["e_score_correction_bias"],
                was["e_score_correction_bias"]
                + RATE * np.sign(load.mean() - load), atol=1e-7)
            # the mean over four devices of 2 x 2 x 64 pairs each
            assert now["load"].sum() == pytest.approx(
                model.experts_per_token * tokens.size / 4)
        if i == 0:
            assert all((b["e_score_correction_bias"] == 0).all()
                       and (b["load"] == 0).all() for b in before)
    assert np.abs(gate(state, gates[0])["e_score_correction_bias"]).max() \
        == pytest.approx(2 * RATE)
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(
        np.asarray(out.aux["expert_tokens"][0]),
        gate(state, gates[0])["load"])
    hvd.shutdown()


def test_a_walked_share_trains_as_the_one_tile_program_does(devices,
                                                            monkeypatch):
    """A recomputed decoder whose expert layers hold 2 of 8 experts, through
    ``dp.make_stateful_train_step``: with the share walked in three tiles,
    two steps give the losses, the parameters and the router state that the
    same steps give with the tile forced to all ``k T`` pairs (one tile: the
    program before the walk), to float32 rounding."""
    from horovod_tpu.metrics.registry import get_registry
    hvd.init(devices=devices[:2])
    mesh = hvd.mesh()
    model = NemotronHTiny(pattern="ME*E", remat="ME", experts_held=(2, 2),
                          dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(5), (4, SEQ), 0, model.vocab)
    variables = model.init(jax.random.key(6), tokens[:1])
    batch = dp.shard_batch(
        {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}, mesh)
    optimizer = optax.adamw(1e-2)
    built = get_registry().counter("hvd_moe_share_tiles_total", kind="built")

    def loss_fn(p, s, b, rng):
        return nemotron_h_loss(model, p, s, b["tokens"], b["labels"])

    def two_steps():
        step = dp.make_stateful_train_step(loss_fn, optimizer, mesh,
                                           donate=False)
        params = dp.replicate(variables["params"], mesh)
        opt_state = dp.replicate(optimizer.init(params), mesh)
        state = dp.replicate(variables["router_state"], mesh)
        losses = []
        for _ in range(2):
            out = step(params, opt_state, state, batch, jax.random.key(0))
            params, opt_state, state = \
                out.params, out.opt_state, out.model_state
            losses.append(float(out.loss))
        return losses, params, state, out.aux["expert_tokens"]

    k_t = model.experts_per_token * 2 * SEQ  # a device's pairs
    monkeypatch.setattr(ep, "SHARE_BLOCK_ROWS", 8)
    assert ep.share_tile_rows(k_t, 2, model.experts) < k_t  # walked
    before = built.value
    walked = two_steps()
    assert built.value > before
    monkeypatch.setattr(ep, "SHARE_TILE_HEADROOM", float(model.experts))
    assert ep.share_tile_rows(k_t, 2, model.experts) == k_t
    before = built.value
    one_tile = two_steps()
    assert built.value == before
    np.testing.assert_allclose(walked[0], one_tile[0], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(walked[3]),
                                  np.asarray(one_tile[3]))
    assert np.asarray(walked[3])[:, 2:4].sum() > 0
    errors = jax.tree_util.tree_map(relative_l2, walked[1:3], one_tile[1:3])
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-5, errors
    hvd.shutdown()


def test_evaluation_uses_the_bias_as_it_stands():
    model, params, state, data = make("E")
    logits = model.apply({"params": params, "router_state": state},
                         data["tokens"])
    assert logits.shape == data["tokens"].shape + (model.vocab,)
    assert logits.dtype == jnp.float32


# -- (d) the pattern, the names, the key heads ----------------------------------------

def test_the_pattern_builds_the_kinds_in_order():
    pattern = "MEM*E"
    model = NemotronHTiny(pattern=pattern)
    params = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, SEQ), jnp.int32)
    )["params"]
    kinds = {"M": "NemotronHMamba2Mixer_0", "E": "NemotronHMoE_0",
             "*": "NemotronHAttention_0"}
    for i, kind in enumerate(pattern):
        assert set(params[f"NemotronHBlock_{i}"]) == {"norm", kinds[kind]}
    mixer = params["NemotronHBlock_0"]["NemotronHMamba2Mixer_0"]
    assert set(mixer) == {"in_proj", "conv1d", "A_log", "D", "dt_bias",
                          "norm", "out_proj"}
    assert set(params["NemotronHBlock_3"]["NemotronHAttention_0"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(params["NemotronHBlock_1"]["NemotronHMoE_0"]) == {
        "gate", "experts", "shared_experts"}
    with pytest.raises(ValueError, match="a layer is one of"):
        NemotronHTiny(pattern="MXE").init(jax.random.key(0),
                                         jnp.zeros((1, SEQ), jnp.int32))
    with pytest.raises(ValueError, match="not a multiple"):
        NemotronHTiny(pattern="M").init(jax.random.key(0),
                                        jnp.zeros((1, SEQ + 1), jnp.int32))


def test_initialisation_of_the_mixer():
    model, params, _, _ = make("M")
    mixer = params["NemotronHBlock_0"]["NemotronHMamba2Mixer_0"]
    np.testing.assert_allclose(np.exp(np.asarray(mixer["A_log"])),
                               np.arange(1, 5), rtol=1e-6)
    assert (np.asarray(mixer["D"]) == 1).all()
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))  # softplus back
    assert (dt >= 1e-3 * (1 - 1e-5)).all() and (dt <= 0.1 * (1 + 1e-5)).all()


@pytest.mark.parametrize("seq", [64, 1024], ids=["xla", "flash"])
def test_two_key_heads_equal_explicitly_repeated_heads(seq):
    """Both paths of the router; the gradient of a key head is the sum over
    its group of query heads."""
    rng = np.random.RandomState(seq)
    q = jnp.asarray(rng.randn(1, seq, 8, 16), jnp.float32)
    k, v = (jnp.asarray(rng.randn(1, seq, 2, 16), jnp.float32)
            for _ in range(2))

    def grouped(q, k, v):
        return attention(q, k, v, causal=True)

    def repeated(q, k, v):
        return attention(q, jnp.repeat(k, 4, axis=2),
                         jnp.repeat(v, 4, axis=2), causal=True)
    np.testing.assert_array_equal(np.asarray(grouped(q, k, v)),
                                  np.asarray(repeated(q, k, v)))
    got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.tanh(grouped(*a))),
                           argnums=(1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.tanh(repeated(*a))),
                            argnums=(1, 2)))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == (1, seq, 2, 16)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6)
    with pytest.raises(ValueError, match="no multiple"):
        attention(q, k[:, :, :1].repeat(3, 2), v[:, :, :1].repeat(3, 2))


def test_published_geometry_of_the_model():
    """The preset at the published sizes: 52 layers, 23 Mamba-2, 23 expert,
    6 attention; 31.6 B parameters by shapes alone."""
    model = Nemotron3Nano30B()
    assert len(model.pattern) == 52
    assert [model.pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert model.pattern[:9] == NemotronHDecoder().pattern
    variables = jax.eval_shape(model.init, jax.random.key(0),
                               jnp.zeros((1, 128), jnp.int32))
    count = sum(int(np.prod(x.shape)) for x in
                jax.tree_util.tree_leaves(variables["params"]))
    per = {"M": 38744896, "*": 23399040,
           "E": 20302592 - 128 + 128 * 9977856}
    assert count == sum(per[k] for k in model.pattern) \
        + 2 * 131072 * 2688 + 2688
    assert 31.5e9 < count < 31.7e9
    assert nemotron_h.ROUTER_STATE in variables
