"""SmallThinker through the normal path, on the CPU at a small size: the
program (``models/smallthinker.py``: window and causal attention side by
side, a routing made of the layer's input, a share of ReGLU experts) against
the plain float32 reference that ``benchmark/configs/smallthinker-21b-a3b.py``
keeps, in float32 and under the bf16 policy; the two per-layer flags; one
``dp.make_train_step`` on four virtual devices; the published geometry."""

import functools
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (SmallThinker21BA3B, SmallThinkerDecoder,
                                SmallThinkerTiny, smallthinker_loss)
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import dp, mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "smallthinker-21b-a3b")

# one period at hidden 64; a window shorter than the sequences below and no
# multiple of a block; 4 of 16 experts held from 4 on: the rehearsal's size
SIZES = dict(vocab=512, hidden=64, heads=4, kv_heads=2, head_dim=16,
             experts=16, experts_per_token=2, expert_dim=32,
             rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
             window=200, rope_theta=1.5e6, experts_held=(4, 4))
REFERENCE = dict(rope_layout=(0, 1, 1, 1), window_layout=(0, 1, 1, 1),
                 window=200, theta=1.5e6, held=(4, 4), eps=1e-6, heads=4,
                 kv_heads=2, head_dim=16, experts_per_token=2)


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_smallthinker_21b_a3b", CONFIG + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@functools.lru_cache(maxsize=None)
def _made(dtype, batch, seq, seed, router_scale, kw):
    model = SmallThinkerDecoder(dtype=dtype, **{**SIZES, **dict(kw)})
    tokens = jax.random.randint(jax.random.key(seed + 100), (batch, seq), 0,
                                SIZES["vocab"], jnp.int32)
    params = jax.jit(model.init)(jax.random.key(seed), tokens)["params"]
    for i in range(len(model.rope_layout)):
        params[f"SmallThinkerBlock_{i}"]["primary_router"]["weight"] *= \
            router_scale
    return model, params, {"tokens": tokens,
                           "labels": jnp.roll(tokens, -1, axis=1)}


def make(dtype, batch, seq, seed=0, router_scale=1.0, **kw):
    """(model, float32 parameters, batch). ``router_scale`` widens the
    routers' logits to the spread they have at the published width. Made
    once a module for the same arguments: tests share the arrays, and
    change none in place."""
    return _made(dtype, batch, seq, seed, router_scale,
                 tuple(sorted(kw.items())))


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, batch):
    def loss_fn(p):
        logits, stats = model.apply({"params": p}, batch["tokens"])
        return smallthinker_loss(logits, batch["labels"], stats)
    return jax.value_and_grad(loss_fn, has_aux=True)(params)


def program(model, params, batch):
    """(loss, aux, gradients) of the model's own loss: compiled once a
    model (a flax module hashes by its fields) and batch shape."""
    (loss, aux), grads = _program(model, params, batch)
    return loss, aux, grads


@functools.lru_cache(maxsize=None)
def _reference(reference_forward, batch, seq, router_scale, kw):
    _, params, data = make(jnp.float32, batch, seq,
                           router_scale=router_scale)

    def loss_fn(p):
        return reference_forward(p, data, **{**REFERENCE, **dict(kw)})
    (loss, chosen), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, chosen, grads


def reference(config_module, batch, seq, router_scale=1.0, **kw):
    """(loss, chosen experts, gradients) of the configuration's float32
    reference on ``make(jnp.float32, batch, seq, router_scale=...)``'s
    parameters and batch (the parameters are float32 whatever a model's
    ``dtype``, and do not depend on its recomputation policy): run once a
    module for a size."""
    return _reference(config_module.reference_forward, batch, seq,
                      router_scale, tuple(sorted(kw.items())))


# -- (a) float32 against float32 -------------------------------------------------

@pytest.mark.parametrize("batch,seq,remat", [
    (2, 256, ""), (1, 1024, ""), (1, 1024, "blocks"),
    (1, 1024, "blocks_keep_attention")],
    ids=["xla_attention", "flash_interpreted", "flash_recomputed",
         "flash_recomputed_keeping_attention"])
def test_float32_program_matches_the_reference(config_module, batch, seq,
                                               remat):
    """The same equations in the same precision: logits' loss to 1e-5,
    every gradient leaf to 1e-4 relative L2, and the same load on every
    expert of every layer. At 1024 the router sends attention to the
    kernels, interpreted here: the causal ones on layer 0, the window ones
    on layers 1-3; a recomputation policy changes nothing."""
    model, params, data = make(jnp.float32, batch, seq, remat=remat)
    loss, aux, grads = program(model, params, data)
    want, chosen, want_grads = reference(config_module, batch, seq)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors
    load = np.stack([np.bincount(np.asarray(c).reshape(-1), minlength=16)
                     for c in chosen])
    np.testing.assert_array_equal(aux["expert_tokens"], load)
    assert (load.sum(axis=-1) == 2 * batch * seq).all()  # nothing dropped


def test_logits_match_the_reference_layer_by_layer_flags(config_module):
    """Rotary and the window only where the layouts say: a program whose
    layouts differ from the reference's in one flag of one layer is another
    function, by far more than rounding."""
    _, params, data = make(jnp.float32, 1, 256)
    want = reference(config_module, 1, 256)[2]

    def worst_leaf(**kw):
        model = SmallThinkerDecoder(dtype=jnp.float32, **{**SIZES, **kw})
        return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            relative_l2, program(model, params, data)[2], want)))
    for kw in (dict(rope_layout=(1, 1, 1, 1)), dict(rope_layout=(0, 0, 1, 1)),
               dict(sliding_window_layout=(1, 1, 1, 1)),
               dict(sliding_window_layout=(0, 0, 1, 1)), dict(window=199)):
        assert worst_leaf(**kw) > 0.01, kw  # (a window off by one key: 2%)
    assert worst_leaf() < 1e-4


def test_attention_calls_carry_their_layers_mask_and_scope(monkeypatch):
    """Layer 0: no window, no rotary (q is the projection itself); layers
    1-3: the window, under ``attn_window``; query heads 4 on key heads 2."""
    from horovod_tpu.models import smallthinker
    calls = []

    def spy(q, k, v, causal, window):
        calls.append((q.shape, k.shape, causal, window))
        return fa.attention(q, k, v, causal=causal, window=window)
    monkeypatch.setattr(smallthinker, "attention", spy)
    model, params, data = make(jnp.float32, 1, 256)
    calls.clear()  # the trace of init
    text = jax.jit(lambda p: model.apply({"params": p}, data["tokens"])[0]) \
        .lower(params).as_text(debug_info=True)
    assert calls == [((1, 256, 4, 16), (1, 256, 2, 16), True, w)
                     for w in (None, 200, 200, 200)]
    assert "SmallThinkerBlock_0/SmallThinkerAttention_0/attn_full" in text
    assert "SmallThinkerBlock_0/SmallThinkerAttention_0/attn_window" \
        not in text
    for i in (1, 2, 3):
        assert f"SmallThinkerBlock_{i}/SmallThinkerAttention_0/attn_window" \
            in text
    # the router runs before attention, on the block's input
    assert text.index("SmallThinkerBlock_0/primary_router/moe_router") < \
        text.index("SmallThinkerBlock_0/SmallThinkerAttention_0")


def test_a_router_after_attention_is_another_model(config_module):
    """The reference with its router on the stream the experts see is
    another function than the model (and than the reference proper): a
    quarter of the tokens and more go to other experts, and every leaf the
    chip's check compares on the routers' path differs by more than that
    check's limit."""
    model, params, data = make(jnp.float32, 2, 256, router_scale=8.0)
    _, _, grads = program(model, params, data)
    want, chosen, want_grads = reference(config_module, 2, 256, 8.0)
    _, late_chosen, late_grads = reference(
        config_module, 2, 256, 8.0, router_reads="after_attention")
    moved = np.mean([(np.sort(a, -1) != np.sort(b, -1)).any(-1).mean()
                     for a, b in zip(chosen, late_chosen)])
    assert moved > 0.25
    tolerance = config_module.TOLERANCE
    for block in ("SmallThinkerBlock_0", "SmallThinkerBlock_3"):
        for path in (("primary_router", "weight"), ("experts", "up")):
            under, limit = tolerance.gradient_limit((block,) + path)
            assert under in ("primary_router", "experts")
            leaf = [g[block][path[0]][path[1]]
                    for g in (grads, want_grads, late_grads)]
            assert relative_l2(leaf[0], leaf[1]) < 1e-4
            assert relative_l2(leaf[0], leaf[2]) > limit, (block, path)


# -- (b) the bf16 policy ---------------------------------------------------------

def test_bf16_policy_stays_near_the_reference(config_module):
    """bf16 activations against float32: the loss to 2**-12, the leaves off
    the routers' path to 8 roundings, those on it (near-ties move rows
    between experts) to 15%; the router's logits stay float32: its
    parameter's gradient is float32 and the weights sum to one."""
    model, params, data = make(jnp.bfloat16, 2, 256, router_scale=8.0)
    loss, _, grads = program(model, params, data)
    want, _, want_grads = reference(config_module, 2, 256, 8.0)
    assert float(loss) == pytest.approx(float(want), rel=2.0 ** -10)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    flat = jax.tree_util.tree_flatten_with_path(errors)[0]
    for path, error in flat:
        on_routers_path = any(getattr(k, "key", None) in (
            "primary_router", "experts") for k in path)
        assert error < (0.2 if on_routers_path else 0.06), (path, error)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


# -- (c) through dp.make_train_step ------------------------------------------------

def test_one_dp_step_on_four_devices(devices):
    """Parameters identical on all four, ``expert_tokens`` summed over the
    mesh, the loss the mean of the shards' (a mean over tokens: the whole
    batch's), and every leaf trained."""
    model, params, data = make(jnp.float32, 8, 128)
    optimizer = optax.adamw(1e-3, b1=0.9, b2=0.95, weight_decay=0.1)

    def loss_fn(p, batch, rng):
        logits, stats = model.apply({"params": p}, batch["tokens"])
        return smallthinker_loss(logits, batch["labels"], stats)

    mesh = mesh_lib.data_parallel_mesh(devices[:4])
    step = dp.make_train_step(loss_fn, optimizer, mesh, donate=False)
    out = step(dp.replicate(params, mesh),
               dp.replicate(optimizer.init(params), mesh),
               dp.shard_batch(data, mesh), jax.random.key(3))
    whole_loss, whole = jax.jit(loss_fn)(params, data, None)
    assert float(out.loss) == pytest.approx(float(whole_loss), rel=1e-5)
    np.testing.assert_array_equal(out.aux["expert_tokens"],
                                  whole["expert_tokens"])
    assert int(np.asarray(out.aux["expert_tokens"]).sum()) == 4 * 2 * 8 * 128
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, params)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (d) the configuration --------------------------------------------------------

def test_an_unknown_policy_or_uneven_layouts_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat 'attention' is none of"):
        SmallThinkerTiny(remat="attention").init(jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="name each layer once"):
        SmallThinkerTiny(rope_layout=(0, 1)).init(jax.random.key(0), tokens)


def test_configuration_is_at_the_published_widths(config_module):
    config = json.load(open(CONFIG + ".json"))
    assert config["reduced"] == [
        "num_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"]
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key  # nothing else differs
    assert published["num_hidden_layers"] == 52 and config["num_layers"] == 8
    assert config["rope_layout"] == published["rope_layout"][:8] == \
        [0, 1, 1, 1, 0, 1, 1, 1] == config["sliding_window_layout"]
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["experts_held"] == {"first": 0, "of": 64} and \
        published["moe_num_primary_experts"] == 64
    job = config_module.build(config, {"seq_len": 16384,
                                       "per_chip_batch": 1})
    facts = job.facts
    assert (facts["hidden"], facts["heads"], facts["kv_heads"],
            facts["head_dim"], facts["experts"], facts["experts_per_token"],
            facts["expert_dim"], facts["window"], facts["vocab"]) == \
        (2560, 28, 4, 128, 64, 6, 768, 4096, 18992)
    assert job.flash_call == (1, 16384, 28, 128, True)
    assert job.flash_layers == facts["full_layers"] == 2
    assert facts["window_layers"] == 6
    assert facts["window_call"] == [1, 16384, 28, 128, 4096]
    shapes = jax.eval_shape(job.init, jax.random.key(0))[0]
    sizes = jax.tree_util.tree_map(lambda x: int(np.prod(x.shape)), shapes)
    parameters = config["deployment"]["parameters"]
    assert sum(jax.tree_util.tree_leaves(sizes)) == \
        parameters["what_runs"] == 643852800
    block = sizes["SmallThinkerBlock_5"]
    assert sum(jax.tree_util.tree_leaves(block)) == \
        parameters["one_layer_here"] == 68326400
    assert sum(jax.tree_util.tree_leaves(
        block["SmallThinkerAttention_0"])) == \
        parameters["one_layer_attention"] == 20971520
    assert block["primary_router"]["weight"] == 2560 * 64
    assert sum(jax.tree_util.tree_leaves(block["experts"])) == \
        8 * parameters["one_routed_expert"] == 8 * 3 * 2560 * 768
    assert parameters["one_layer_whole"] == 20971520 + 163840 + 5120 + \
        64 * 5898240
    assert sizes["LmHead"]["kernel"] == sizes["Embed_0"]["embedding"] == \
        2560 * 18992  # two tables: the head is not tied
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(shapes))
    for path in job.check_leaves:
        leaf = shapes
        for key in path:
            leaf = leaf[key]


def test_published_geometry_of_the_model():
    model = SmallThinker21BA3B()
    assert (model.hidden, model.heads, model.kv_heads, model.head_dim,
            model.experts, model.experts_per_token, model.expert_dim,
            model.vocab, model.window, model.rope_theta, model.eps) == \
        (2560, 28, 4, 128, 64, 6, 768, 151936, 4096, 1.5e6, 1e-6)
    assert len(model.rope_layout) == 52 == len(model.sliding_window_layout)
    assert model.rope_layout == model.sliding_window_layout == \
        (0, 1, 1, 1) * 13
