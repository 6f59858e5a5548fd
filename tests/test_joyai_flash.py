"""JoyAI-LLM-Flash's training pass through the normal path, on the CPU at a
small size: the program (``models/joyai_flash.py``: latent attention with
q/k wider than v, a dense and sparse SwiGLU feed-forwards, a share of
sigmoid-and-bias experts beside a shared one, the multi-token-prediction
module on the main model's embedding and head) against the plain float32
reference that ``benchmark/configs/joyai-llm-flash.py`` keeps, in float32 and
under the bf16 policy; latent attention, the flash kernels at 192/128, the
module and the routing by hand; the sixteen shares of one sparse layer
against the uncut layer; the two uses' parts of the embedding's and the
head's gradients; the bias rule through ``dp.make_stateful_train_step`` on
four virtual devices; the published geometry."""

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

from flash_cases import assert_close, kernel_functions, out_and_grads
from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.models import (JoyaiFlashDecoder, JoyaiFlashTiny,
                                JoyaiLlmFlash, joyai_flash_loss)
from horovod_tpu.models import joyai_flash
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import dp, ep, mesh as mesh_lib
from horovod_tpu.profiler import annotate

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "joyai-llm-flash")

# the cell's stack cut to the rehearsal's: the dense layer, two sparse
# layers and the module at hidden 64, 4 heads of 16 + 8 against values of 16;
# 4 of 16 experts held from 4 on
SIZES = dict(num_layers=3, first_k_dense=1, mtp_layers=1, vocab=512,
             hidden=64, heads=4, q_lora_rank=48, kv_lora_rank=32,
             qk_nope_dim=16, qk_rope_dim=8, v_dim=16, dense_dim=128,
             experts=16, experts_per_token=2, expert_dim=32, rope_theta=32e6,
             bias_update_rate=3e-3, experts_held=(4, 4))
REFERENCE = dict(num_layers=3, first_k_dense=1, mtp_layers=1, mtp_lambda=0.3,
                 held=(4, 4), eps=1e-6, theta=32e6, scale=2.5, rate=3e-3,
                 heads=4, kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                 v_dim=16, experts_per_token=2)
RATE = 3e-3


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_joyai_llm_flash", CONFIG + ".py")
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
    model = JoyaiFlashDecoder(dtype=dtype, **{**SIZES, **dict(kw)})
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
    return model, variables["params"], state, {"tokens": tokens}


def make(dtype, batch, seq, seed=0, **kw):
    """(model, float32 parameters, a router state, the batch). Made once a
    module for the same arguments: tests share the arrays, and change none
    in place."""
    return _made(dtype, batch, seq, seed, tuple(sorted(kw.items())))


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, state, data):
    def loss_fn(p):
        return joyai_flash_loss(model, p, state, data["tokens"])
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
        loss, new_state, chosen, parts = reference_forward(
            p, state, data, **{**REFERENCE, **dict(kw)})
        return loss, (new_state, parts)
    (loss, (new_state, parts)), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, new_state, parts, grads


def reference(config_module, batch, seq, **kw):
    return _reference(config_module.reference_forward, batch, seq,
                      tuple(sorted(kw.items())))


# -- (a) the float32 program against the reference -------------------------------

@pytest.mark.parametrize("batch,seq,remat", [
    (2, 128, ""), (2, 128, "blocks_keep_attention")],
    ids=["kept", "recomputed"])
def test_float32_program_matches_the_reference(config_module, batch, seq,
                                               remat):
    """The dense layer, two sparse layers and the module in one stack: both
    losses, every leaf's gradient, and the state the step returns."""
    model, params, state, data = make(jnp.float32, batch, seq, remat=remat)
    loss, new_state, aux, grads = program(model, params, state, data)
    want, want_state, (want_next, want_mtp), want_grads = reference(
        config_module, batch, seq)
    assert float(loss) == pytest.approx(float(want), rel=2e-5)
    assert float(aux["next_token_loss"]) == pytest.approx(float(want_next),
                                                          rel=2e-5)
    assert float(aux["mtp_loss"]) == pytest.approx(float(want_mtp), rel=2e-5)
    assert float(loss) == pytest.approx(
        float(aux["next_token_loss"]) + 0.3 * float(aux["mtp_loss"]),
        rel=1e-6)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves(errors))
    assert worst < 2e-3, errors
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(want_grads)
    for got, ref in zip(jax.tree_util.tree_leaves(new_state),
                        jax.tree_util.tree_leaves(want_state)):
        np.testing.assert_allclose(got, ref, atol=1e-6)
    # three sparse layers' loads, the module's last, each over all 16
    assert aux["expert_tokens"].shape == (3, 16)
    np.testing.assert_array_equal(
        aux["expert_tokens"][2],
        new_state["JoyaiMtp_0"]["JoyaiBlock_0"]["JoyaiMoE_0"]["gate"]["load"])
    assert float(aux["expert_tokens"].sum()) == 3 * 2 * batch * seq


# -- (b) latent attention by hand -------------------------------------------------------

ATTENTION = dict(heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_dim=16,
                 qk_rope_dim=8, v_dim=16, rope_theta=32e6)


@functools.lru_cache(maxsize=None)
def _attention_made(seq=32):
    module = joyai_flash.JoyaiLatentAttention(dtype=jnp.float32, **ATTENTION)
    x = jax.random.normal(jax.random.key(1), (2, seq, 64))
    return module, module.init(jax.random.key(2), x), x


def test_rotary_turns_the_pairs_2i_and_2i_plus_1(config_module):
    """Element ``2i`` with element ``2i + 1``, by ``t theta^(-2i/D)``. The
    program's projection hands the product its rotary columns with the pairs'
    first members before their second (the matrix as published, its columns
    reordered on the way) and turns halves; the reference turns the pairs in
    place: a dot product of two turned vectors is the same either way."""
    theta, d, own = 32e6, 8, 4
    dense = joyai_flash._pairs_as_halves(2 * (own + d), own + d, d,
                                         jnp.float32, "w")
    c = jnp.asarray(np.random.RandomState(0).randn(2, 5, 6), jnp.float32)
    variables = dense.init(jax.random.key(0), c)
    kernel = np.asarray(variables["params"]["kernel"])  # [6, 2 x (4 | 8)]
    assert kernel.shape == (6, 24)
    got = np.asarray(dense.apply(variables, c)).reshape(2, 5, 2, own + d)
    plain = (np.asarray(c) @ kernel).reshape(2, 5, 2, own + d)
    np.testing.assert_allclose(got[..., :own], plain[..., :own], rtol=1e-6)
    np.testing.assert_allclose(got[..., own:own + d // 2],
                               plain[..., own::2], rtol=1e-6)
    np.testing.assert_allclose(got[..., own + d // 2:],
                               plain[..., own + 1::2], rtol=1e-6)
    # the halves turned by the program are the pairs turned by hand
    x, turned = plain[..., own:], np.asarray(joyai_flash.rotary(
        jnp.asarray(got[..., own:]), theta))
    for t in range(5):
        for i in range(d // 2):
            angle = t * theta ** (-2 * i / d)
            a, b = x[:, t, :, 2 * i], x[:, t, :, 2 * i + 1]
            np.testing.assert_allclose(
                turned[:, t, :, i], a * np.cos(angle) - b * np.sin(angle),
                rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(
                turned[:, t, :, d // 2 + i],
                b * np.cos(angle) + a * np.sin(angle), rtol=1e-4, atol=1e-5)
    in_place = np.asarray(config_module._rotate_pairs(jnp.asarray(x), theta))
    np.testing.assert_allclose(
        (turned[:, :, 0] * turned[:, :, 1]).sum(-1),
        (in_place[:, :, 0] * in_place[:, :, 1]).sum(-1), rtol=1e-4, atol=1e-5)
    # position 0 is not turned at all
    np.testing.assert_allclose(in_place[:, 0], x[:, 0], rtol=1e-6)


def test_every_heads_key_ends_in_the_same_rotated_key(monkeypatch):
    """What reaches the attention call: q and k of 16 + 8 in four heads, v
    of 16; every head's key ends in the ONE rotary key, turned; causal; the
    scale is ``(16 + 8) ** -0.5`` and not the values' ``16 ** -0.5``."""
    module, variables, x = _attention_made()
    seen = {}

    def spy(q, k, v, **kw):
        seen.update(q=q, k=k, v=v, **kw)
        return fa.attention(q, k, v, **kw)
    monkeypatch.setattr(joyai_flash, "attention", spy)
    module.apply(variables, x)
    assert seen["q"].shape == seen["k"].shape == (2, 32, 4, 24)
    assert seen["v"].shape == (2, 32, 4, 16)
    assert seen["causal"] is True
    assert seen["sm_scale"] == pytest.approx(24 ** -0.5)
    shared = np.asarray(seen["k"][..., 16:])
    for head in range(1, 4):
        np.testing.assert_array_equal(shared[:, :, head], shared[:, :, 0])
    latent = x @ variables["params"]["kv_a_proj_with_mqa"]["kernel"]
    halves = jnp.concatenate([latent[..., 32::2], latent[..., 33::2]], -1)
    np.testing.assert_allclose(
        shared[:, :, :1], joyai_flash.rotary(halves[:, :, None, :], 32e6),
        rtol=1e-4, atol=1e-5)
    # the heads' own parts differ
    assert np.abs(np.asarray(seen["k"][:, :, 0, :16]
                             - seen["k"][:, :, 1, :16])).max() > 1e-3


def test_latent_attention_is_the_references(config_module):
    """The module against the reference's operator on the same weights: the
    inner norms, the split, rotary, the scale ``24 ** -0.5``."""
    module, variables, x = _attention_made()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(module.apply)(variables, x)
        want = config_module._latent_attention(
            x, variables["params"], heads=4, kv_lora_rank=32, qk_nope_dim=16,
            qk_rope_dim=8, v_dim=16, theta=32e6, eps=1e-6, bits=None)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("at", [0, 7, 31])
def test_a_position_sees_itself_and_what_is_before_it(at):
    module, variables, x = _attention_made()
    apply = jax.jit(module.apply)
    base = apply(variables, x)
    moved = apply(variables, x.at[:, at].add(1.0))
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 2)) > 1e-7
    assert not changed[:at].any() and changed[at:].all()


# -- (c) the kernels at q/k of 192 and v of 128 -------------------------------------------

LATENT_NAMES = ("_fwd_latent_kernel", "_bwd_dq_latent_kernel",
                "_bwd_dkv_latent_kernel")
CAUSAL_NAMES = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")


def _qkv(dtype, seq=384, heads=2, qk=192, v=128):
    rng = np.random.RandomState(3)
    return (jnp.asarray(rng.randn(1, seq, heads, qk), dtype),
            jnp.asarray(rng.randn(1, seq, heads, qk), dtype),
            jnp.asarray(rng.randn(1, seq, heads, v), dtype),
            jnp.asarray(rng.randn(1, seq, heads, v), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_the_kernels_at_192_and_128_are_xla_attention(dtype):
    """Forward and all three gradients in interpret mode, 384 positions in
    tiles of 192 (no multiple of the 256 asked for), the scale
    ``192 ** -0.5`` (no power of two: it multiplies the float32 scores)."""
    q, k, v, dout = _qkv(dtype)
    flash = functools.partial(fa.flash_attention, causal=True, block_q=256,
                              block_k=256, interpret=True)
    got, got_grads = out_and_grads(flash, q, k, v, dout)
    want, want_grads = out_and_grads(
        functools.partial(fa.xla_attention, causal=True), q, k, v, dout)
    assert got.shape == (1, 384, 2, 128) and got.dtype == dtype
    assert_close(got, want, dtype)
    for a, b, like in zip(got_grads, want_grads, (q, k, v)):
        assert a.shape == like.shape and a.dtype == dtype
        assert_close(a, b, dtype)


def test_unequal_widths_run_under_the_latent_names_and_counters():
    """Such a call is traced through the latent kernels' functions, its
    blocks counted under ``latent_*`` kinds and the call under
    ``hvd_latent_calls_total``; a call of equal widths keeps the names and
    kinds it had, and a window's or a block mask's name wins."""
    q, k, v, _ = _qkv(jnp.float32)

    def names(v, **mask):
        def loss(q, k, v):
            return fa.flash_attention(q, k, v, causal=True, interpret=True,
                                      **mask).sum()
        return sorted(kernel_functions(jax.grad(loss, argnums=(0, 1, 2)),
                                       q, k, v))
    registry = get_registry()

    def visits(kind):
        return registry.counter("hvd_flash_block_visits", kind=kind).value
    calls = registry.counter("hvd_latent_calls_total", qk_dim="192",
                             v_dim="128")
    before = calls.value, visits("latent_diagonal"), visits("diagonal")
    assert names(v) == sorted(LATENT_NAMES)
    assert calls.value == before[0] + 1
    # 384 positions in one tile of 384: 2 heads x 1 diagonal block
    assert visits("latent_diagonal") == before[1] + 2
    assert visits("diagonal") == before[2]
    assert names(k) == sorted(CAUSAL_NAMES)
    assert calls.value == before[0] + 1 and \
        visits("diagonal") == before[2] + 2
    assert names(v, window=64) == sorted(
        name.replace("_kernel", "_window_kernel") for name in CAUSAL_NAMES)
    assert calls.value == before[0] + 1


def test_the_router_takes_xla_below_the_crossover_at_unequal_widths():
    q, k, v, _ = _qkv(jnp.float32, seq=128)
    got = jax.jit(functools.partial(fa.attention, causal=True))(q, k, v)
    want = jax.jit(functools.partial(fa.xla_attention, causal=True))(q, k, v)
    np.testing.assert_array_equal(got, want)
    assert got.shape == (1, 128, 2, 128)


# -- (d) the multi-token-prediction module by hand ---------------------------------------

@functools.lru_cache(maxsize=None)
def _both_logits(at=None):
    model, params, state, data = make(jnp.float32, 1, 64, experts_held=None)
    tokens = data["tokens"]
    if at is not None:
        tokens = tokens.at[0, at].set((tokens[0, at] + 1) % model.vocab)
    (logits, mtp_logits), _ = jax.jit(
        lambda p, s, t: model.apply({"params": p, "router_state": s}, t,
                                    mutable=["router_state"]))(
        params, state, tokens)
    return np.asarray(logits), np.asarray(mtp_logits)


@pytest.mark.parametrize("at", [2, 17, 40])
def test_the_modules_logits_see_the_next_token_and_nothing_after(at):
    """``logits'_i`` is of ``t_0 .. t_{i+1}``: it moves when ``t_{i+1}``
    does and not when ``t_{i+2}`` or anything after it does; the main
    model's ``logits_i`` is of ``t_0 .. t_i``."""
    (base, base_mtp), (moved, moved_mtp) = _both_logits(), _both_logits(at)
    main = np.abs(moved - base).max(axis=(0, 2)) > 1e-7
    module = np.abs(moved_mtp - base_mtp).max(axis=(0, 2)) > 1e-7
    assert not main[:at].any() and main[at]
    # positions i <= at - 2 have t_{i+1} before ``at``
    assert not module[:at - 1].any() and module[at - 1] and module[at]


def test_lambda_zero_is_the_stack_without_the_module():
    model, params, state, data = make(jnp.float32, 2, 128, mtp_lambda=0.0,
                                      num_layers=2)
    loss, _, aux, grads = program(model, params, state, data)
    bare = model.clone(mtp_layers=0)
    without = {k: v for k, v in params.items() if k != "JoyaiMtp_0"}
    bare_state = {k: v for k, v in state.items() if k != "JoyaiMtp_0"}
    want, _, bare_aux, want_grads = program(bare, without, bare_state, data)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert "mtp_loss" not in bare_aux and float(aux["mtp_loss"]) > 0
    assert not any(np.asarray(g).any() for g in
                   jax.tree_util.tree_leaves(grads["JoyaiMtp_0"]))
    for key in without:
        assert max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            relative_l2, grads[key], want_grads[key]))) < 1e-5, key


def test_the_shared_embedding_and_head_add_up_their_two_uses(config_module):
    """With an embedding and a head of the module's own beside the main
    model's (what the model is not) the reference gives each use's part; the
    program's one gradient of each is their sum, and neither part is
    nothing."""
    model, params, state, data = make(jnp.float32, 2, 128)
    grads = program(model, params, state, data)[3]

    def apart(p, copies):
        return config_module.reference_forward(p, state, data, copies=copies,
                                               **REFERENCE)[0]
    first, second = jax.jit(jax.grad(apart, argnums=(0, 1)))(
        params, {"embedding": params["embed_tokens"]["embedding"],
                 "head": params["lm_head"]["kernel"]})
    for got, one, two in (
            (grads["embed_tokens"]["embedding"],
             first["embed_tokens"]["embedding"], second["embedding"]),
            (grads["lm_head"]["kernel"], first["lm_head"]["kernel"],
             second["head"])):
        one, two = np.asarray(one), np.asarray(two)
        assert relative_l2(got, one + two) < 2e-4
        assert np.abs(one).max() > 0 and np.abs(two).max() > 0
        assert relative_l2(got, one) > 0.05  # the second use is no rounding
    assert len(params) == 3 + 1 + 3  # blocks, the module, embed/norm/head


# -- (e) the routing equation --------------------------------------------------------------

def test_the_routing_equation_at_top_8_scaled_by_two_and_a_half(
        config_module):
    """``s = sigmoid(x W)``; the eight largest of ``s + b``; weights ``s`` at
    the chosen over their sum, times 2.5: the bias is in the choice alone.
    The program's router and the reference's agree with it by hand."""
    x = jax.random.normal(jax.random.key(7), (40, 64))
    w = 0.5 * jax.random.normal(jax.random.key(8), (64, 256))
    bias = 0.3 * jax.random.normal(jax.random.key(9), (256,))
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST)), np.float64)
    want_chosen = np.sort(np.argsort(-(scores + np.asarray(bias)), -1)[:, :8],
                          -1)
    weights, experts, _, _ = ep.route_sigmoid_topk(x, w, bias, k=8, scale=2.5)
    experts = np.asarray(experts)
    np.testing.assert_array_equal(np.sort(experts, -1), want_chosen)
    picked = np.take_along_axis(scores, experts, -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        dense, chosen, load = config_module._routing(x, w, bias, 8, 2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  want_chosen)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(dense), experts, -1), weights,
        rtol=1e-5)
    assert float(load.sum()) == 8 * 40
    # without the bias other experts are chosen: it does move the choice
    unbiased = np.sort(np.argsort(-scores, -1)[:, :8], -1)
    assert (unbiased != want_chosen).any()


# -- (f) the share, tied to the model ----------------------------------------------------

def test_the_sixteen_shares_of_a_sparse_layer_add_up_to_the_uncut_reference(
        config_module):
    """One sparse feed-forward cut as the deployment cuts it: each of
    sixteen chips holds 16 of the 256 experts (one router over all 256, its
    own rows of the stacked matrices) and computes the shared expert alike.
    The chips' routed parts (a chip's output less the shared expert's),
    summed, with the shared expert counted once, are the uncut reference
    with all 256. A share is a program of its own (``held`` is static), so
    three are run as they are, ``held=(0, 16)``, ``(112, 16)``, ``(240,
    16)``, and all sixteen as the first one's program on the layer with its
    experts renumbered so that the share's come first (the router's columns
    and the bias rolled alike): the same choices under other numbers, which
    the three show to be the same rows."""
    hidden, width, tokens = 32, 16, 64
    whole = joyai_flash.JoyaiMoE(256, 8, width, width, 2.5, 0.0,
                                 dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(9), (1, tokens, hidden))
    variables = whole.init(jax.random.key(10), x)
    moe = jax.tree_util.tree_map(
        lambda leaf: 4.0 * leaf, variables["params"])  # experts that matter
    bias = 0.05 * jax.random.normal(jax.random.key(11), (256,))
    state = {"gate": {"expert_bias": bias, "load": jnp.zeros((256,))}}

    @functools.partial(jax.jit, static_argnums=0)
    def on_share(first, params, state):
        return whole.clone(experts_held=(first, 16)).apply(
            {"params": params, "router_state": state}, x,
            mutable=["router_state"])

    def renumbered(first):
        """The layer with expert ``first + j`` called ``j``."""
        return ({**moe, "gate": {"weight": jnp.roll(
            moe["gate"]["weight"], -first, axis=1)}},
            {"gate": {"expert_bias": jnp.roll(bias, -first),
                      "load": state["gate"]["load"]}})

    def held(params, first):
        return {**params, "experts": {
            name: w[first:first + 16]
            for name, w in moe["experts"].items()}}
    with jax.default_matmul_precision("highest"):
        dense, _, load = config_module._routing(
            x, moe["gate"]["weight"], bias, 8, 2.5)
        shared = config_module._feed_forward(x, moe["shared_experts"],
                                             bits=None)
        uncut = config_module._experts(x, moe["experts"], dense, (0, 256),
                                       None) + shared
        total = jnp.zeros_like(uncut)
        for first in range(0, 256, 16):
            params, rolled = renumbered(first)
            out, new = on_share(0, held(params, first), rolled)
            total += out - shared
            # the router over all 256, whatever is held
            np.testing.assert_array_equal(
                jnp.roll(new["router_state"]["gate"]["load"], first), load)
            if first in (112, 240):  # the share under its own numbers
                own, own_new = on_share(first, held(moe, first), state)
                np.testing.assert_allclose(own, out, rtol=1e-6, atol=1e-7)
                np.testing.assert_array_equal(
                    own_new["router_state"]["gate"]["load"], load)
        np.testing.assert_allclose(total + shared, uncut, rtol=2e-4,
                                   atol=2e-6)
    assert float(load.sum()) == 8 * tokens
    assert float(jnp.abs(uncut - shared).max()) > 1e-3  # the experts matter


# -- (g) the bf16 policy ---------------------------------------------------------------------

def _on_routers_path(keys):
    return "JoyaiMoE_0" in keys or "post_attention_layernorm" in keys


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
        assert error < (0.30 if _on_routers_path(keys) else 0.06), \
            (path, error)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


def test_the_lowered_control_is_not_the_reference(config_module):
    """The control (every product's inputs at 3 mantissa bits, the router's
    at 7) differs from the reference on the leaves off the routers' path by
    more than the bf16 program does."""
    want, _, _, want_grads = reference(config_module, 2, 128)
    low, _, _, low_grads = reference(config_module, 2, 128, lowered=True)
    errors = jax.tree_util.tree_map(relative_l2, low_grads, want_grads)
    for part in (errors["embed_tokens"], errors["lm_head"],
                 errors["JoyaiBlock_0"]["JoyaiLatentAttention_0"],
                 errors["JoyaiBlock_0"]["mlp"],
                 errors["JoyaiBlock_2"]["JoyaiLatentAttention_0"],
                 errors["JoyaiMtp_0"]["eh_proj"]):
        assert min(jax.tree_util.tree_leaves(part)) > 0.02, part
    assert float(low) != float(want)


# -- (h) through dp.make_stateful_train_step ----------------------------------------------

def test_bias_rule_over_two_steps_through_the_stateful_step(devices):
    """Four devices, each its own batch, nothing in ``dp.py`` told about the
    model: after a step every router's bias, the module's among them, has
    moved by the rate towards the experts the *mean* load of the previous
    step left short, the state holds this step's mean load, parameters are
    identical on the four chips and every leaf trained; the operator and the
    module under their scopes inside ``phase_forward_backward``."""
    model = JoyaiFlashDecoder(**{**SIZES, "num_layers": 2,
                                 "experts_held": None})
    tokens = jax.random.randint(jax.random.key(3), (8, 64), 0, model.vocab)
    variables = model.init(jax.random.key(4), tokens[:1])
    params, state = variables["params"], variables["router_state"]

    def loss_fn(p, s, b, rng):
        return joyai_flash_loss(model, p, s, b["tokens"])
    optimizer = optax.adamw(1e-3, b1=0.9, b2=0.95, weight_decay=0.1)
    mesh = mesh_lib.data_parallel_mesh(devices[:4])
    step = dp.make_stateful_train_step(loss_fn, optimizer, mesh,
                                       donate=False)
    first = params
    params = dp.replicate(params, mesh)
    opt_state = dp.replicate(optimizer.init(params), mesh)
    state = dp.replicate(state, mesh)
    sharded = dp.shard_batch({"tokens": tokens}, mesh)
    text = step.lower(params, opt_state, state, sharded,
                      jax.random.key(0)).as_text(debug_info=True)
    for scope in (*annotate.MLA_SCOPES, *annotate.MTP_SCOPES, "attn_latent",
                  "moe_router", "moe_experts", "moe_shared"):
        assert re.search(rf'phase_forward_backward/[^"]*{scope}', text), scope
    # the module's block carries the operator's scopes inside its own
    assert re.search(r'mtp_block/[^"]*mla_q_proj', text)

    def gates(tree):
        return [{k: np.asarray(v) for k, v in layer["JoyaiMoE_0"]["gate"]
                 .items()} for layer in (
            tree["JoyaiBlock_1"], tree["JoyaiMtp_0"]["JoyaiBlock_0"])]
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
    assert np.abs(gates(state)[1]["expert_bias"]).max() == pytest.approx(RATE)
    assert losses[-1] < losses[0]
    np.testing.assert_array_equal(np.asarray(out.aux["expert_tokens"][1]),
                                  gates(state)[1]["load"])
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, first)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (i) the configuration -----------------------------------------------------------------

def test_scopes_and_an_unknown_policy_or_module_count_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat 'attention' is none of"):
        JoyaiFlashTiny(remat="attention").init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="mtp_layers 2"):
        JoyaiFlashTiny(mtp_layers=2).init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="unknown latent-attention scope"):
        annotate.mla_scope("mla_everything")
    with pytest.raises(ValueError, match="unknown multi-token-prediction"):
        annotate.mtp_scope("mtp_everything")
    assert annotate.MLA_SCOPES == ("mla_q_proj", "mla_kv_proj", "mla_rope",
                                   "mla_out_proj")
    assert annotate.MTP_SCOPES == ("mtp_merge", "mtp_block", "mtp_head")
    assert "attn_latent" in annotate.ATTN_SCOPES
    tiny = JoyaiFlashTiny()
    assert tiny.qk_nope_dim + tiny.qk_rope_dim != tiny.v_dim
    assert tiny.num_layers == 3 and tiny.mtp_layers == 1
    modules = get_registry().counter("hvd_mtp_modules_total")
    before = modules.value
    jax.eval_shape(tiny.init, jax.random.key(0), tokens)
    assert modules.value == before + 1
    jax.eval_shape(tiny.clone(mtp_layers=0).init, jax.random.key(0), tokens)
    assert modules.value == before + 1


def test_configuration_is_at_the_published_widths(config_module):
    config = json.load(open(CONFIG + ".json"))
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    published = config["published"]
    if os.path.exists(catalog):  # the catalog's row, where it is at hand
        row = next(json.loads(line) for line in open(catalog)
                   if '"JoyAI-LLM-Flash"' in line)
        assert published == row["config"]
        assert config["source"] == row["source_url"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key  # nothing else differs
    assert (config["hidden_size"], config["num_attention_heads"],
            config["q_lora_rank"], config["kv_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_shared_experts"], config["routed_scaling_factor"],
            config["rope_theta"], config["rms_norm_eps"],
            config["num_nextn_predict_layers"]) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 8, 1, 2.5, 32000000,
        1e-6, 1)
    assert published["num_hidden_layers"] == config["num_hidden_layers"] \
        == 40 and config["num_layers"] == 5
    assert config["first_k_dense_replace"] == 1
    assert config["vocab_size"] * 8 == published["vocab_size"] == 129280
    assert config["experts_held"] == {"first": 0, "of": 256} and \
        published["n_routed_experts"] == 256 and \
        config["n_routed_experts"] == 16
    assert config["mtp_lambda"] == 0.3 and config["bias_update_rate"] == 3e-3
    for key in ("multi_token_prediction", "initializer_range",
                "norm_topk_epsilon", "expert_bias_rule", "optimizer", "loss",
                "weights"):
        assert key in config["assumed"], key
    for key in ("chips", "bytes_per_parameter", "parameters", "distortion"):
        assert key in config["deployment"], key
    assert "16 chips share each layer" in config["deployment"]["chips"]
    job = config_module.build(config, {"seq_len": 8192, "per_chip_batch": 1})
    params, state = jax.eval_shape(job.init, jax.random.key(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(params))
    counted = config["deployment"]["parameters"]
    assert count == counted["what_runs"] == 680439808  # to the unit
    assert counted["what_runs"] == counted["leading_dense_layer"] \
        + 4 * counted["sparse_layer_here"] \
        + counted["mtp_module_with_its_layer_here"] \
        + counted["embedding_slice"] + counted["head_slice"] \
        + counted["final_norm"]
    assert counted["sparse_layer_here"] == \
        counted["one_latent_attention_operator"] \
        + counted["one_layer_norms"] + counted["one_layer_router"] \
        + counted["shared_expert"] + counted["one_layer_held_experts"]
    assert set(state) == {f"JoyaiBlock_{i}" for i in (1, 2, 3, 4)} \
        | {"JoyaiMtp_0"}
    assert job.stateful and job.flash_call is None and job.flash_layers == 0
    assert job.facts["latent_call"] == [1, 8192, 32, 192, 128]
    assert job.facts["latent_layers"] == job.facts["layers"] == 6
    # the issue's count: a token costs 1.133 GFLOP forward
    forward = job.facts["forward_mflops_per_token"]
    assert sum(forward.values()) == pytest.approx(1132.8, abs=0.1)
    assert job.model_flops_per_item == pytest.approx(
        3e6 * sum(forward.values()))
    shares = {k: round(100 * v / sum(forward.values()))
              for k, v in forward.items()}
    assert shares == {"latent_attention": 72, "dense": 8, "experts": 7,
                      "mtp_merge": 1, "head": 12}
    assert ep.share_slot_rows(8 * 8192, 256) == 384
    assert ep.share_product((2048, 768)) == "slots"


def test_published_geometry_of_the_model():
    """The full published stack builds from the same module: 40 layers, the
    first dense, 256 experts, 129 280 rows, the module: 50.19 B parameters."""
    model = JoyaiLlmFlash()
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))
    params = shapes["params"]

    def count(tree):
        return sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count(params) == 50190481408
    assert count(params) - count(params["JoyaiMtp_0"]) == 48942532608
    assert "mlp" in params["JoyaiBlock_0"] and \
        "JoyaiMoE_0" in params["JoyaiBlock_1"]
    assert count(params["JoyaiBlock_0"]) == 70391808
    assert count(params["JoyaiBlock_39"]) == 1239554048
    assert count(params["JoyaiBlock_1"]["JoyaiLatentAttention_0"]) == 26347520
    assert params["JoyaiBlock_1"]["JoyaiMoE_0"]["experts"]["w1"].shape == \
        (256, 2048, 768)
    assert params["JoyaiMtp_0"]["eh_proj"]["kernel"].shape == (4096, 2048)
    assert count(params["JoyaiMtp_0"]) == 8394752 + 1239554048
    assert params["lm_head"]["kernel"].shape == (2048, 129280)
    assert len(shapes["router_state"]) == 39 + 1
    # a token's active parameters: the embedding is a gather, 8 of 256
    # experts a sparse layer, the module left out
    active = 48942532608 - 39 * 248 * 4718592 - 129280 * 2048
    assert 2.6e9 < active < 3.1e9
