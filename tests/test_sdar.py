"""SDAR's block-diffusion training pass through the normal path, on the CPU
at a small size: the program (``models/sdar.py``: two streams of a sequence
as ``2L`` rows, the block-diffusion mask, per-head q/k norm, a share of
SwiGLU experts) against the plain float32 reference that
``benchmark/configs/sdar-30b-a3b.py`` keeps, in float32 and under the bf16
policy; the noise and the loss by hand; the eight shares of one layer against
the uncut layer; one ``dp.make_train_step`` on four virtual devices; the
published geometry."""

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

from horovod_tpu.models import (Sdar30BA3B, SdarMoeDecoder, SdarTiny,
                                sdar_loss, sdar_noise)
from horovod_tpu.models import sdar
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import dp, mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "sdar-30b-a3b")

# two layers at hidden 64, q wider than the hidden size (8 heads of 16 on 2
# key heads); 4 of 16 experts held from 4 on: the rehearsal's size
SIZES = dict(vocab=512, layers=2, hidden=64, heads=8, kv_heads=2,
             head_dim=16, experts=16, experts_per_token=2, expert_dim=32,
             block_length=4, rope_theta=1e6, experts_held=(4, 4))
REFERENCE = dict(block=4, theta=1e6, held=(4, 4), eps=1e-6, heads=8,
                 kv_heads=2, head_dim=16, experts_per_token=2)
MASK_ID = SIZES["vocab"] - 1


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "bench_sdar_30b_a3b", CONFIG + ".py")
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
    model = SdarMoeDecoder(dtype=dtype, **{**SIZES, **dict(kw)})
    x0 = jax.random.randint(jax.random.key(seed + 100), (batch, seq), 0,
                            MASK_ID, jnp.int32)
    data = {"x0": x0, **sdar_noise(jax.random.key(seed + 200), x0,
                                   model.block_length, MASK_ID)}
    params = jax.jit(model.init)(jax.random.key(seed), data["xt"],
                                 x0)["params"]
    return model, params, data


def make(dtype, batch, seq, seed=0, **kw):
    """(model, float32 parameters, the batch with its noise). Made once a
    module for the same arguments: tests share the arrays, and change none
    in place."""
    model, params, data = _made(dtype, batch, seq, seed,
                                tuple(sorted(kw.items())))
    return model, params, dict(data)


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, data):
    def loss_fn(p):
        logits, stats = model.apply({"params": p}, data["xt"], data["x0"])
        return sdar_loss(logits, data, stats)
    return jax.value_and_grad(loss_fn, has_aux=True)(params)


def program(model, params, data):
    """(loss, aux, gradients) of the model's own loss: compiled once a
    model (a flax module hashes by its fields) and batch shape."""
    (loss, aux), grads = _program(model, params, data)
    return loss, aux, grads


@functools.lru_cache(maxsize=None)
def _reference(reference_forward, batch, seq, kw):
    _, params, data = make(jnp.float32, batch, seq)

    def loss_fn(p):
        return reference_forward(p, data, **{**REFERENCE, **dict(kw)})
    (loss, chosen), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, chosen, grads


def reference(config_module, batch, seq, **kw):
    """(loss, chosen experts, gradients) of the configuration's float32
    reference on ``make(jnp.float32, batch, seq)``'s parameters and batch
    (the parameters are float32 whatever a model's ``dtype``, and do not
    depend on its recomputation policy): run once a module for a size."""
    return _reference(config_module.reference_forward, batch, seq,
                      tuple(sorted(kw.items())))


# -- (a) float32 against float32 -------------------------------------------------

@pytest.mark.parametrize("batch,seq,remat", [
    (2, 256, ""), (1, 1024, ""), (1, 1024, "blocks"),
    (1, 1024, "blocks_keep_attention")],
    ids=["xla_attention", "flash_interpreted", "flash_recomputed",
         "flash_recomputed_keeping_attention"])
def test_float32_program_matches_the_reference(config_module, batch, seq,
                                               remat):
    """The same equations in the same precision: the loss to 1e-5, every
    gradient leaf to 1e-4 relative L2, and the
    same load on every expert of every layer, from both streams' rows. At
    1024 tokens attention is the kernels under the two block masks,
    interpreted here, merged with a noised block on itself; a recomputation
    policy changes nothing."""
    model, params, data = make(jnp.float32, batch, seq, remat=remat)
    loss, aux, grads = program(model, params, data)
    want, chosen, want_grads = reference(config_module, batch, seq)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors
    load = np.stack([np.bincount(np.asarray(c).reshape(-1), minlength=16)
                     for c in chosen])
    np.testing.assert_array_equal(aux["expert_tokens"], load)
    assert (load.sum(axis=-1) == 2 * 2 * batch * seq).all()  # 2L rows, top-2
    assert int(aux["masked_tokens"]) == int(np.asarray(data["masked"]).sum())


def test_another_block_length_or_a_shifted_stream_is_another_model(
        config_module):
    """A program whose mask is cut in other blocks than the reference's, or
    that is handed the streams the other way round, is another function by
    far more than rounding."""
    model, params, data = make(jnp.float32, 1, 256)
    want = reference(config_module, 1, 256)[2]

    def worst_leaf(model, data):
        return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            relative_l2, program(model, params, data)[2], want)))
    assert worst_leaf(model, data) < 1e-4
    assert worst_leaf(model.clone(block_length=8), data) > 0.01
    assert worst_leaf(model.clone(block_length=2), data) > 0.01
    swapped = {**data, "xt": data["x0"], "x0": data["xt"]}
    assert worst_leaf(model, swapped) > 0.01


def test_attention_is_told_the_streams_and_nothing_else_is(monkeypatch):
    """One call a layer of ``blockdiff_attention`` over 2L rows, 8 query
    heads on 2 key heads, under ``attn_blockdiff``; the head runs over the
    noised rows alone."""
    calls = []

    def spy(q, k, v, group):
        calls.append((q.shape, k.shape, v.shape, group))
        return fa.blockdiff_attention(q, k, v, group)
    monkeypatch.setattr(sdar, "blockdiff_attention", spy)
    model, params, data = make(jnp.float32, 1, 64)
    calls.clear()  # the trace of init
    lowered = jax.jit(lambda p: model.apply(
        {"params": p}, data["xt"], data["x0"])[0]).lower(params)
    assert calls == 2 * [
        ((1, 128, 8, 16), (1, 128, 2, 16), (1, 128, 2, 16), 4)]
    text = lowered.as_text(debug_info=True)
    for i in (0, 1):
        assert f"SdarBlock_{i}/SdarAttention_0/attn_blockdiff" in text
        assert f"SdarBlock_{i}/SdarSparseMoe_0/moe_experts" in text
    assert jax.eval_shape(lambda p: model.apply(
        {"params": p}, data["xt"], data["x0"])[0], params).shape == \
        (1, 64, SIZES["vocab"])


def test_rotary_is_at_the_position_in_the_sequence_not_in_the_array(
        monkeypatch):
    """Row ``i`` of either stream is turned by the angle of position ``i``:
    what ``SdarAttention`` hands attention for equal rows of the two streams
    is equal, which it would not be if the angle were that of the row's
    place among the 2L (``olmoe.rotary`` over the whole array)."""
    from horovod_tpu.models.olmoe import rotary
    seen = []

    def spy(q, k, v, group):
        seen.append((q, k))
        return fa.blockdiff_attention(q, k, v, group)
    monkeypatch.setattr(sdar, "blockdiff_attention", spy)
    model, params, data = make(jnp.float32, 1, 64, layers=1)
    seen.clear()
    model.apply({"params": params}, data["x0"], data["x0"])  # equal streams
    (q, k), = seen
    for rows in (q, k):
        np.testing.assert_allclose(rows[:, :64], rows[:, 64:], rtol=1e-6)
    unturned = jnp.ones((1, 128, 2, 16), jnp.float32)
    over_array = rotary(unturned, 1e6)
    assert float(jnp.abs(over_array[:, 64:] - over_array[:, :64]).max()) > 0.1
    by_stream = rotary(unturned.reshape(2, 64, 2, 16), 1e6)
    np.testing.assert_array_equal(by_stream[0], by_stream[1])


def test_per_head_qk_norm_by_hand():
    """``q_norm`` runs over each head's own 16 values with one weight
    vector of 16 (not over the projection's 128): scaling one head's slice
    of ``q_proj`` changes nothing, scaling ``q_norm`` scales every head's
    scores."""
    model, params, data = make(jnp.float32, 1, 64, layers=1)
    attention = params["SdarBlock_0"]["SdarAttention_0"]
    assert attention["q_norm"]["scale"].shape == (16,)
    assert attention["k_norm"]["scale"].shape == (16,)
    logits = jax.jit(lambda p: model.apply({"params": p}, data["xt"],
                                           data["x0"])[0])
    want = logits(params)
    scaled = jax.tree_util.tree_map(lambda x: x, params)
    kernel = scaled["SdarBlock_0"]["SdarAttention_0"]["q_proj"]["kernel"]
    scaled["SdarBlock_0"]["SdarAttention_0"]["q_proj"]["kernel"] = \
        kernel.at[..., 16:32].multiply(3.0)  # head 1's 16 columns
    np.testing.assert_allclose(logits(scaled), want, rtol=2e-4, atol=2e-5)
    # a norm over the whole projection would let head 1's scale leak into
    # the other heads' statistics
    q = jnp.arange(1.0, 33.0).reshape(1, 1, 2, 16)
    normed = sdar.nn.RMSNorm(epsilon=1e-6).apply(
        {"params": {"scale": jnp.full((16,), 2.0)}}, q)
    by_hand = 2.0 * np.asarray(q) / np.sqrt(
        (np.asarray(q) ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(normed, by_hand, rtol=1e-6)


# -- (b) the noise and the loss, by hand -------------------------------------------

def test_noise_masks_levels_and_weights_by_hand():
    """A level a block, shared by its four positions, within [1e-3, 1];
    ``weight`` its reciprocal; ``xt`` the mask token exactly where
    ``masked``; the share of masked positions in a block follows its level
    (a block at t > 0.9 is nearly all mask, one at t < 0.1 nearly none)."""
    x0 = jax.random.randint(jax.random.key(1), (4, 4096), 0, MASK_ID)
    noise = sdar_noise(jax.random.key(2), x0, 4, MASK_ID)
    assert set(noise) == {"xt", "masked", "weight"}
    level = 1.0 / np.asarray(noise["weight"])
    blocks = level.reshape(4, 1024, 4)
    assert (blocks == blocks[..., :1]).all()  # one level a block
    assert blocks.min() >= 1e-3 and blocks.max() <= 1.0
    assert abs(blocks[..., 0].mean() - 0.5005) < 0.02  # uniform
    masked = np.asarray(noise["masked"])
    np.testing.assert_array_equal(
        np.asarray(noise["xt"]), np.where(masked, MASK_ID, np.asarray(x0)))
    assert masked[level > 0.9].mean() > 0.9
    assert masked[level < 0.1].mean() < 0.1
    assert abs(masked.mean() - 0.5) < 0.02
    # E[masked / t] = 1: the weighted count of masked positions is L
    assert abs((masked / level).mean() - 1.0) < 0.1
    assert noise["xt"].dtype == x0.dtype and masked.dtype == bool
    # the same key, the same noise
    again = sdar_noise(jax.random.key(2), x0, 4, MASK_ID)
    np.testing.assert_array_equal(again["xt"], noise["xt"])
    with pytest.raises(ValueError, match="whole blocks"):
        sdar_noise(jax.random.key(2), x0[:, :4094], 4, MASK_ID)


def test_loss_by_hand():
    """(1 / (B L)) x the sum over the masked positions of (1 / t) CE."""
    from horovod_tpu.parallel import ep
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(2, 8, 5), jnp.float32)
    x0 = jnp.asarray(rng.randint(0, 5, (2, 8)))
    masked = jnp.asarray(rng.rand(2, 8) < 0.5)
    weight = jnp.asarray(1.0 / rng.uniform(0.1, 1.0, (2, 8)), jnp.float32)
    stats = ep.MoeStats(jnp.ones((3, 4), jnp.int32), jnp.zeros((3, 4)),
                        jnp.zeros((3,)))
    loss, aux = sdar_loss(logits, {"x0": x0, "masked": masked,
                                   "weight": weight}, stats)
    want = 0.0
    for b in range(2):
        for i in range(8):
            if masked[b, i]:
                row = np.asarray(logits[b, i], np.float64)
                ce = np.log(np.exp(row).sum()) - row[int(x0[b, i])]
                want += float(weight[b, i]) * ce
    assert float(loss) == pytest.approx(want / 16, rel=1e-5)
    assert set(aux) == {"expert_tokens", "masked_tokens"}
    assert int(aux["masked_tokens"]) == int(np.asarray(masked).sum())
    assert aux["expert_tokens"].shape == (3, 4)
    # no gradient reaches a position that is not masked
    grad = jax.grad(lambda x: sdar_loss(x, {"x0": x0, "masked": masked,
                                            "weight": weight}, stats)[0])(
        logits)
    assert np.asarray(grad)[~np.asarray(masked)].max() == 0.0
    assert np.abs(np.asarray(grad)[np.asarray(masked)]).min() > 0.0


# -- (c) the share tied to the model -------------------------------------------------

@pytest.mark.parametrize("shares", [8, 2])
def test_the_shares_of_one_layer_add_up_to_the_uncut_reference(config_module,
                                                               shares):
    """One layer cut as the deployment cuts it: each of ``shares`` chips
    holds 16 / shares experts (one router over all 16, its own rows of the
    stacked matrices), every chip computes attention alike, and the chips'
    expert parts add up to the uncut layer's expert sum: the program's
    output on share ``s`` minus the residual stream, summed over ``s``,
    against the reference holding all 16."""
    held = 16 // shares
    whole, params, data = make(jnp.float32, 1, 64, layers=1,
                               experts_held=None)
    rows = jnp.concatenate([data["xt"], data["x0"]], axis=1)
    x = params["Embed_0"]["embedding"][rows]
    layer = params["SdarBlock_0"]
    sizes = {k: v for k, v in REFERENCE.items() if k != "held"}
    uncut = config_module._layer(x, layer, seq=64, held=(0, 16), bits=None,
                                 router_bits=None, **sizes)[0]
    # what attention and the residual give alone: a share holding nothing
    no_experts = config_module._layer(
        x, {**layer, "SdarSparseMoe_0": {
            **layer["SdarSparseMoe_0"],
            **{name: layer["SdarSparseMoe_0"][name][:0]
               for name in ("gate_proj", "up_proj", "down_proj")}}},
        seq=64, held=(0, 0), bits=None, router_bits=None, **sizes)[0]
    block = sdar.SdarBlock(
        heads=8, kv_heads=2, head_dim=16, experts=16, experts_per_token=2,
        expert_dim=32, block_length=4, rope_theta=1e6, dtype=jnp.float32)
    total = jnp.zeros_like(uncut)
    for s in range(shares):
        moe = layer["SdarSparseMoe_0"]
        share = {**layer, "SdarSparseMoe_0": {
            "router": moe["router"],
            **{name: moe[name][s * held:(s + 1) * held]
               for name in ("gate_proj", "up_proj", "down_proj")}}}
        out, stats = block.clone(experts_held=(s * held, held)).apply(
            {"params": share}, x)
        total += out - no_experts
        assert int(stats.expert_tokens.sum()) == 2 * 128
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(total, uncut - no_experts, rtol=2e-4,
                                   atol=2e-6)
    assert float(jnp.abs(uncut - no_experts).max()) > 1e-4  # experts matter


# -- (d) the bf16 policy -------------------------------------------------------------

def test_bf16_policy_stays_near_the_reference(config_module):
    """bf16 activations against float32: the loss to 2**-10, the leaves off
    the routers' path to 6%, those on it (near-ties move rows between
    experts) to 25%; parameters and their gradients stay float32."""
    model, params, data = make(jnp.bfloat16, 2, 256)
    loss, _, grads = program(model, params, data)
    want, _, want_grads = reference(config_module, 2, 256)
    assert float(loss) == pytest.approx(float(want), rel=2.0 ** -10)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    for path, error in jax.tree_util.tree_flatten_with_path(errors)[0]:
        on_routers_path = any(getattr(k, "key", None) == "SdarSparseMoe_0"
                              for k in path)
        assert error < (0.25 if on_routers_path else 0.06), (path, error)
    assert all(g.dtype == jnp.float32
               for g in jax.tree_util.tree_leaves(grads))


def test_the_mask_tokens_row_sums_its_gradient_in_float32():
    """Every row of the noised stream holds the mask token, 2048 rows on one
    row of the embedding: the bf16 program's gradient of that row stays
    within 1% of the float32 program's (0.55%; a bf16 gather's transpose
    adds the rows up in bf16 and reads 4.2% off)."""
    grads = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        model, params, data = make(dtype, 2, 1024)
        data = {**data, "xt": jnp.full_like(data["xt"], MASK_ID),
                "masked": jnp.ones_like(data["masked"])}
        grads[dtype] = program(model, params, data)[2]["Embed_0"][
            "embedding"][MASK_ID]
    assert grads[jnp.bfloat16].dtype == jnp.float32
    assert relative_l2(grads[jnp.bfloat16], grads[jnp.float32]) < 1e-2


def test_the_lowered_control_is_not_the_reference(config_module):
    """The control (every product's inputs at 3 mantissa bits, the router's
    at 7) differs from the reference on every leaf by more than the bf16
    program does on the leaves off the routers' path."""
    want, _, want_grads = reference(config_module, 1, 256)
    low, _, low_grads = reference(config_module, 1, 256, lowered=True)
    errors = jax.tree_util.tree_map(relative_l2, low_grads, want_grads)
    for name in ("Embed_0", "LmHead"):
        assert min(jax.tree_util.tree_leaves(errors[name])) > 0.02, errors
    for i in (0, 1):
        attention = errors[f"SdarBlock_{i}"]["SdarAttention_0"]
        assert min(jax.tree_util.tree_leaves(attention)) > 0.02, errors
    assert float(low) != float(want)


# -- (e) through dp.make_train_step ----------------------------------------------------

def test_one_dp_step_on_four_devices(devices):
    """The noise drawn inside the loss function from the step's key, as a
    user trains: parameters identical on all four chips, ``expert_tokens``
    and ``masked_tokens`` summed over the mesh, every leaf trained; the
    noising and the loss under their scopes inside
    ``phase_forward_backward``."""
    model, params, data = make(jnp.float32, 8, 64)
    optimizer = optax.adamw(1e-3, b1=0.9, b2=0.95, weight_decay=0.1)

    def loss_fn(p, batch, rng):
        noised = {"x0": batch["x0"], **sdar_noise(
            rng, batch["x0"], model.block_length, MASK_ID)}
        logits, stats = model.apply({"params": p}, noised["xt"],
                                    noised["x0"])
        return sdar_loss(logits, noised, stats)

    mesh = mesh_lib.data_parallel_mesh(devices[:4])
    step = dp.make_train_step(loss_fn, optimizer, mesh, donate=False)
    args = (dp.replicate(params, mesh),
            dp.replicate(optimizer.init(params), mesh),
            dp.shard_batch({"x0": data["x0"]}, mesh), jax.random.key(3))
    text = step.lower(*args).as_text(debug_info=True)
    for scope in ("diffusion_noise", "diffusion_loss", "attn_blockdiff"):
        assert re.search(rf'phase_forward_backward/[^"]*{scope}', text), scope
    out = step(*args)
    assert np.isfinite(float(out.loss))
    assert int(np.asarray(out.aux["expert_tokens"]).sum()) == \
        2 * 2 * 2 * 8 * 64  # layers x top-2 x 2 streams x tokens
    assert 0 < int(out.aux["masked_tokens"]) < 8 * 64
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, params)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (f) the configuration ---------------------------------------------------------------

def test_an_unknown_policy_or_uneven_streams_are_refused():
    tokens = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat 'attention' is none of"):
        SdarTiny(remat="attention").init(jax.random.key(0), tokens, tokens)
    with pytest.raises(ValueError, match="whole blocks of 4"):
        SdarTiny().init(jax.random.key(0), tokens[:, :14], tokens[:, :14])
    with pytest.raises(ValueError, match="one batch"):
        SdarTiny().init(jax.random.key(0), tokens, tokens[:, :8])


def test_configuration_is_at_the_published_widths(config_module):
    config = json.load(open(CONFIG + ".json"))
    assert config["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key  # nothing else differs
    layers = config["num_layers"]
    assert published["num_hidden_layers"] == 48 and 4 <= layers <= 6
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["experts_held"] == {"first": 0, "of": 128} and \
        published["num_experts"] == 128 and config["num_experts"] == 16
    assert config["block_length"] == 4
    assert config["mask_token_id"] == config["vocab_size"] - 1
    for key in ("block_length", "noise_schedule", "no_shift",
                "mask_token_id", "loss", "optimizer", "initialisation",
                "weights", "batch"):
        assert key in config["assumed"], key
    job = config_module.build(config, {"seq_len": 8192, "per_chip_batch": 1})
    facts = job.facts
    assert (facts["hidden"], facts["heads"], facts["kv_heads"],
            facts["head_dim"], facts["experts"], facts["experts_per_token"],
            facts["expert_dim"], facts["vocab"], facts["block_length"]) == \
        (2048, 32, 4, 128, 128, 8, 768, 18992, 4)
    assert job.flash_call is None and job.flash_layers == 0
    assert facts["blockdiff_call"] == [1, 8192, 32, 128, 4]
    assert job.items_per_example == 8192 and facts["rows_per_layer"] == 16384
    shapes = jax.eval_shape(job.init, jax.random.key(0))[0]
    sizes = jax.tree_util.tree_map(lambda x: int(np.prod(x.shape)), shapes)
    parameters = config["deployment"]["parameters"]
    assert parameters["one_layer_here"] == 94638336 == \
        18874368 + 256 + 4096 + 262144 + 16 * 4718592
    assert parameters["one_layer_whole"] == 18874368 + 256 + 4096 + 262144 \
        + 128 * 4718592
    assert sum(jax.tree_util.tree_leaves(sizes)) == \
        parameters["what_runs"] == layers * 94638336 + 2 * 18992 * 2048 + 2048
    if layers == 6:
        assert parameters["what_runs"] == 645623296
    block = sizes[f"SdarBlock_{layers - 1}"]
    assert sum(jax.tree_util.tree_leaves(block)) == 94638336
    assert sum(jax.tree_util.tree_leaves(block["SdarAttention_0"])) == \
        parameters["one_layer_attention"] + parameters["one_layer_qk_norm"]
    assert block["SdarSparseMoe_0"]["router"] == 2048 * 128
    assert block["SdarSparseMoe_0"]["gate_proj"] == 16 * 2048 * 768 == \
        16 * parameters["one_routed_expert"] // 3
    assert sizes["LmHead"]["kernel"] == sizes["Embed_0"]["embedding"] == \
        2048 * 18992  # two tables: the head is not tied
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(shapes))
    for path in job.check_leaves:
        leaf = shapes
        for key in path:
            leaf = leaf[key]
    batch = jax.eval_shape(lambda k: job.make_batch(k, 1), jax.random.key(0))
    assert set(batch) == {"x0", "xt", "masked", "weight"}
    assert all(x.shape == (1, 8192) for x in batch.values())


def test_the_cells_start_sends_the_masked_rows_past_the_held_experts(
        config_module):
    """``cell_start`` by hand, at the rehearsal's size: ``o_proj`` and
    ``down_proj`` 1e-4 wide where they were 0.02, every other leaf as the
    model made it, the mask token's row at the embedding's own size; and
    with that start no row that holds the mask token chooses a held expert
    in any layer, while the data tokens' rows reach every held expert."""
    config = json.load(open(CONFIG + ".json"))
    config.update(config.pop("rehearse"))
    job = config_module.build(config, {"seq_len": 256, "per_chip_batch": 2})
    model = SdarMoeDecoder(
        vocab=512, layers=2, hidden=64, heads=8, kv_heads=2, head_dim=16,
        experts=16, experts_per_token=2, expert_dim=32, block_length=4,
        rope_theta=1e6, experts_held=(4, 4), remat="blocks_keep_attention")
    tokens = jnp.zeros((1, 256), jnp.int32)
    plain = model.init(jax.random.key(7), tokens, tokens)["params"]
    params, state = job.init(jax.random.key(7))
    assert state is None
    for i in (0, 1):
        block, was = params[f"SdarBlock_{i}"], plain[f"SdarBlock_{i}"]
        np.testing.assert_allclose(
            block["SdarAttention_0"]["o_proj"]["kernel"],
            was["SdarAttention_0"]["o_proj"]["kernel"] * 5e-3, rtol=1e-6)
        np.testing.assert_allclose(block["SdarSparseMoe_0"]["down_proj"],
                                   was["SdarSparseMoe_0"]["down_proj"] * 5e-3,
                                   rtol=1e-6)
        for name in ("q_proj", "k_proj", "v_proj"):
            np.testing.assert_array_equal(
                block["SdarAttention_0"][name]["kernel"],
                was["SdarAttention_0"][name]["kernel"])
        np.testing.assert_array_equal(block["SdarSparseMoe_0"]["router"],
                                      was["SdarSparseMoe_0"]["router"])
    table, was = params["Embed_0"]["embedding"], plain["Embed_0"]["embedding"]
    np.testing.assert_array_equal(table[:511], was[:511])
    assert float(jnp.sqrt(jnp.mean(table[511] ** 2))) == pytest.approx(0.02)
    for i in (0, 1):  # the mask row's logits at the held experts: far below
        logits = np.asarray(table[511] / 0.02
                            @ params[f"SdarBlock_{i}"]["SdarSparseMoe_0"][
                                "router"])
        others = np.delete(logits, range(4, 8))
        assert logits[4:8].max() < others.mean() - 1.5 * others.std()
        assert logits[4:8].max() < np.sort(logits)[-2]
    batch = job.make_batch(jax.random.key(8), 2)
    logits, stats = jax.jit(lambda p: model.apply(
        {"params": p}, batch["xt"], batch["x0"]))(params)
    masked = int(np.asarray(batch["masked"]).sum())
    load = np.asarray(stats.expert_tokens)
    # every masked row chose the same two experts, neither of them held
    assert (np.sort(load, axis=1)[:, -2:] >= masked).all()
    assert (load[:, 4:8] < masked).all() and (load[:, 4:8] > 0).all()
    pairs = 2 * (2 * 2 * 256 - masked)  # a layer's, of the data tokens' rows
    assert (load[:, 4:8].sum(axis=1) > 0.15 * pairs).all()  # 4 of 16: 0.25
    assert (load[:, 4:8].sum(axis=1) < 0.35 * pairs).all()


def test_published_geometry_of_the_model():
    model = Sdar30BA3B()
    assert (model.layers, model.hidden, model.heads, model.kv_heads,
            model.head_dim, model.experts, model.experts_per_token,
            model.expert_dim, model.vocab, model.rope_theta, model.eps,
            model.block_length) == \
        (48, 2048, 32, 4, 128, 128, 8, 768, 151936, 1e6, 1e-6, 4)
    assert model.heads * model.head_dim == 2 * model.hidden  # q is wider
