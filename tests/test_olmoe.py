"""OLMoE through the normal path, on the CPU at a small size: the program
(``models/olmoe.py`` over ``parallel/ep.moe_topk``) against the plain float32
reference that ``benchmark/configs/olmoe-1b-7b.py`` keeps, in float32 and
under the bf16 policy; one ``dp.make_train_step`` on four virtual devices;
the configuration's FLOP count by hand."""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import Olmoe1B7B, OlmoeDecoder, olmoe_loss
from horovod_tpu.parallel import dp, ep, mesh as mesh_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CONFIG = os.path.join(BENCH, "configs", "olmoe-1b-7b")

# 2 layers of hidden 64, 8 experts, top-2: the rehearsal's size
SIZES = dict(vocab=512, layers=2, hidden=64, heads=4, experts=8,
             experts_per_token=2, expert_dim=32)
K = SIZES["experts_per_token"]
REFERENCE = dict(layers=2, heads=4, k=K, eps=1e-5, theta=10000.0,
                 balance_coef=0.01, z_coef=0.001)


@pytest.fixture(scope="module")
def config_module():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location("bench_olmoe_1b_7b",
                                                  CONFIG + ".py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def relative_l2(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def make(dtype, batch, seq, seed=0, router_scale=1.0):
    """(model, float32 parameters, batch). ``router_scale`` widens the
    routers' logits to the spread they have at the published width (0.9:
    2048 terms of 0.02), which 64 terms of 0.02 do not reach."""
    model = OlmoeDecoder(dtype=dtype, **SIZES)
    tokens = jax.random.randint(jax.random.key(seed + 100), (batch, seq), 0,
                                SIZES["vocab"], jnp.int32)
    params = jax.jit(model.init)(jax.random.key(seed), tokens)["params"]
    for i in range(SIZES["layers"]):
        params[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"]["router"] *= router_scale
    return model, params, {"tokens": tokens,
                           "labels": jnp.roll(tokens, -1, axis=1)}


def program(model, params, batch):
    """(loss, aux, gradients, the experts each token chose [layers, T, k],
    the activations each router saw [layers, T, hidden])."""
    def loss_fn(p):
        (logits, stats), seen = model.apply(
            {"params": p}, batch["tokens"], capture_intermediates=lambda
            module, _: module.name == "post_attention_layernorm")
        loss, aux = olmoe_loss(logits, batch["labels"], stats, K)
        # what each router saw: the second norm's output of its layer
        seen = jnp.stack([
            seen["intermediates"][f"OlmoeBlock_{i}"]["post_attention_layernorm"]
            ["__call__"][0].reshape(-1, SIZES["hidden"])
            for i in range(SIZES["layers"])])
        chosen = jnp.stack([
            ep.route_topk(x, p[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"]
                          ["router"], K)[1]
            for i, x in enumerate(seen)])
        return loss, (aux, chosen, seen)
    (loss, (aux, chosen, seen)), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, aux, grads, chosen, seen


def reference(config_module, params, batch):
    def loss_fn(p):
        return config_module.reference_forward(p, batch, **REFERENCE)
    (loss, routing), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    return loss, routing, grads


def differing_share(chosen, want):
    """Share of the (token, slot) choices whose expert the other side did
    not choose for that token."""
    chosen, want = np.asarray(chosen), np.asarray(want)
    found = (chosen[..., :, None] == want[..., None, :]).any(axis=-1)
    return float(1.0 - found.mean())


# -- (a) float32 against float32 -------------------------------------------------

@pytest.mark.parametrize("batch,seq", [(2, 128), (1, 1024)],
                         ids=["xla_attention", "flash_interpreted"])
def test_float32_program_matches_the_reference(config_module, batch, seq):
    """The same equations in the same precision: the loss to 1e-5, every
    gradient leaf to 1e-4 relative L2, and the same experts for every
    token. At 1024 the router sends attention to the flash kernels,
    interpreted here."""
    model, params, data = make(jnp.float32, batch, seq)
    loss, aux, grads, chosen, _ = program(model, params, data)
    want, routing, want_grads = reference(config_module, params, data)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    assert max(jax.tree_util.tree_leaves(errors)) < 1e-4, errors
    np.testing.assert_array_equal(np.sort(chosen, axis=-1),
                                  np.sort(routing["chosen"], axis=-1))
    np.testing.assert_array_equal(aux["expert_tokens"],
                                  routing["expert_tokens"])
    assert (np.asarray(aux["expert_tokens"]).sum(axis=-1)
            == K * batch * seq).all()  # nothing dropped


# -- (b) the bf16 policy ---------------------------------------------------------

# bf16 keeps 8 significand bits, so an activation is off by up to 2**-9
# relative, and after the few roundings between the embedding and a router
# so is the logit it makes. Where a token's k-th and (k+1)-th logits lie
# closer than that, program and reference pick different experts for the
# slot, and the token's output changes by one expert's weighted
# contribution, not by a rounding: 0.19% of the slots here (readings of
# this test: loss 1.8e-6, expert leaves 4.3%, other leaves 1.2%). A gradient
# leaf of the experts is a sum of incoherent per-token terms, so it moves by
# about the square root of twice the share of rows that moved: that, not a
# rounding, is the limit's size. The loss is a mean over thousands of tokens.
# Router logits in bf16 (one precision below the float32 the policy states)
# add two roundings to the four or so the activations already carry: end to
# end that is 1.2 times the differing share (0.23%) and 4.7% on the expert
# leaves, which no limit on the gradients can tell from the policy. What
# tells them apart is the router on equal inputs: on the activations the
# program's own router saw, a float32 router picks the experts an exact one
# picks, every one, and a bf16 router does not.
LOSS_RTOL = 2.0 ** -12
EXPERT_GRAD_REL_L2 = 0.08
OTHER_GRAD_REL_L2 = 8 * 2.0 ** -8
DIFFERING_SHARE_MAX = 0.004
ROUTER_SCALE = 5.6  # logits of spread 0.9, as 2048 terms of 0.02 give


def route_in_bf16(x, w_router, k):
    logits = jnp.dot(x.astype(jnp.bfloat16),
                     w_router.astype(jnp.bfloat16)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    return weights, experts.astype(jnp.int32), probs, logits


def exact_choices(seen, params):
    """The experts a float64 router picks on the activations ``seen``
    [layers, T, hidden]."""
    out = []
    for i, x in enumerate(np.asarray(seen, np.float64)):
        logits = x @ np.asarray(
            params[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"]["router"],
            np.float64)
        out.append(np.argsort(-logits, axis=-1)[:, :K])
    return np.stack(out)


def held(params, found, wanted) -> dict:
    """Which of the policy's limits hold, and the readings."""
    loss, _, grads, chosen, seen = found
    want, routing, want_grads = wanted
    errors = jax.tree_util.tree_map(relative_l2, grads, want_grads)
    layers = [f"OlmoeBlock_{i}" for i in range(SIZES["layers"])]
    on_path = max(max(jax.tree_util.tree_leaves(
        errors[layer]["OlmoeSparseMoe_0"])) for layer in layers)
    for layer in layers:
        del errors[layer]["OlmoeSparseMoe_0"]
    readings = {
        "loss": abs(float(loss) - float(want)) / abs(float(want)),
        "expert_gradients": on_path,
        "other_gradients": max(jax.tree_util.tree_leaves(errors)),
        "differing_share": differing_share(chosen, routing["chosen"]),
        "differing_share_on_equal_inputs": differing_share(
            chosen, exact_choices(seen, params))}
    limits = {"loss": LOSS_RTOL, "expert_gradients": EXPERT_GRAD_REL_L2,
              "other_gradients": OTHER_GRAD_REL_L2,
              "differing_share": DIFFERING_SHARE_MAX,
              "differing_share_on_equal_inputs": 0.0}
    return {"readings": readings,
            "held": {k: readings[k] <= limits[k] for k in limits}}


@pytest.fixture(scope="module")
def bf16_case(config_module):
    model, params, data = make(jnp.bfloat16, 32, 512,
                               router_scale=ROUTER_SCALE)
    return model, params, data, reference(config_module, params, data)


def test_bf16_policy_holds_against_the_reference(bf16_case, record_property):
    model, params, data, wanted = bf16_case
    found = program(model, params, data)
    report = held(params, found, wanted)
    record_property("readings", json.dumps(report["readings"]))
    print("bf16 policy against float32:", report["readings"])
    assert all(report["held"].values()), report
    assert (np.asarray(found[1]["expert_tokens"]).sum(axis=-1)
            == K * 32 * 512).all()


def test_bf16_router_logits_fail_the_policy_limits(bf16_case, monkeypatch,
                                                   record_property):
    """One precision below what the configuration states for the router."""
    model, params, data, wanted = bf16_case
    monkeypatch.setattr(ep, "route_topk", route_in_bf16)
    report = held(params, program(model, params, data), wanted)
    record_property("readings", json.dumps(report["readings"]))
    print("bf16 router logits against float32:", report["readings"])
    assert not report["held"]["differing_share_on_equal_inputs"], report
    assert report["readings"]["differing_share_on_equal_inputs"] > 2e-4


# -- (d) through dp.make_train_step ------------------------------------------------

def test_one_dp_step_on_four_devices(devices):
    """Parameters identical on all four, ``expert_tokens`` summed over the
    mesh, and the loss the one-device loss of the same batch: its
    cross-entropy and z-loss are means over tokens, so the whole batch on
    one device gives them; the load-balancing term is a product of two
    batch means, so the mesh gives the mean of the shards' own."""
    model, params, data = make(jnp.float32, 8, 128)
    optimizer = optax.adamw(4e-4, b1=0.9, b2=0.95, weight_decay=0.1)

    def loss_fn(p, batch, rng):
        logits, stats = model.apply({"params": p}, batch["tokens"])
        return olmoe_loss(logits, batch["labels"], stats, K)

    mesh = mesh_lib.data_parallel_mesh(devices[:4])
    step = dp.make_train_step(loss_fn, optimizer, mesh, donate=False)
    out = step(dp.replicate(params, mesh),
               dp.replicate(optimizer.init(params), mesh),
               dp.shard_batch(data, mesh), jax.random.key(3))

    one = jax.jit(loss_fn)
    whole_loss, whole = one(params, data, None)
    shards = [one(params, jax.tree_util.tree_map(
        lambda x, i=i: x[2 * i:2 * i + 2], data), None) for i in range(4)]
    assert float(out.loss) == pytest.approx(
        np.mean([float(loss) for loss, _ in shards]), rel=1e-6)
    np.testing.assert_array_equal(out.aux["expert_tokens"],
                                  whole["expert_tokens"])
    assert int(np.asarray(out.aux["expert_tokens"]).sum()) == \
        SIZES["layers"] * K * 8 * 128
    assert float(out.aux["router_z_loss"]) == pytest.approx(
        float(whole["router_z_loss"]), rel=1e-5)
    assert float(out.aux["load_balancing_loss"]) == pytest.approx(
        np.mean([float(aux["load_balancing_loss"]) for _, aux in shards]),
        rel=1e-6)
    cross_entropy = float(out.loss) - 0.01 * float(
        out.aux["load_balancing_loss"]) - 0.001 * float(
            out.aux["router_z_loss"])
    whole_cross_entropy = float(whole_loss) - 0.01 * float(
        whole["load_balancing_loss"]) - 0.001 * float(whole["router_z_loss"])
    assert cross_entropy == pytest.approx(whole_cross_entropy, rel=1e-5)
    for leaf in jax.tree_util.tree_leaves(out.params):
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 4
        assert all((c == copies[0]).all() for c in copies[1:])
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), out.params, params)
    assert min(jax.tree_util.tree_leaves(moved)) > 0  # every leaf trained


# -- (e) the configuration --------------------------------------------------------

def test_flop_count_by_hand(config_module):
    # forward MFLOP a token: 8 experts x 3 matrices of 2048 x 1024, q, k, v
    # and out, the router, the causal scores at 4096, the untied head
    experts = 8 * 3 * 2048 * 1024 * 2
    projections = 4 * 2048 * 2048 * 2
    router = 2048 * 64 * 2
    scores = 2 * 2 * (4096 * 4097 // 2) * 2048 / 4096
    head = 2048 * 50304 * 2
    assert experts == 100663296 and projections == 33554432
    assert scores == 16781312 and head == 206045184
    want = 3 * (experts + projections + router + scores + head)
    got = config_module.olmoe_train_flops_per_token(
        1, 2048, 1024, 64, 8, 50304, 4096)
    assert got == pytest.approx(want) and got == pytest.approx(1.0719e9,
                                                               rel=1e-4)
    assert config_module.moe_train_flops_per_token(2048, 1024, 8) == \
        6 * 8 * 3 * 2048 * 1024
    # 16 layers: the head is 8% of the work, one layer here 58%
    whole = config_module.olmoe_train_flops_per_token(
        16, 2048, 1024, 64, 8, 50304, 4096)
    assert 3 * head / whole == pytest.approx(0.077, abs=0.005)
    assert 3 * head / got == pytest.approx(0.58, abs=0.01)


def test_configuration_is_at_the_published_widths(config_module):
    config = json.load(open(CONFIG + ".json"))
    assert config["reduced"] == ["num_layers"] and config["num_layers"] == 1
    assert config["published"]["num_hidden_layers"] == 16
    for key, value in config["published"].items():
        assert config[key] == value, key  # nothing else differs
    job = config_module.build(config, {"seq_len": 4096, "per_chip_batch": 2})
    assert job.facts["hidden"] == 2048 and job.facts["heads"] == 16
    assert job.facts["head_dim"] == 128 and job.facts["experts"] == 64
    assert job.facts["expert_dim"] == 1024 and job.facts["vocab"] == 50304
    assert job.facts["experts_per_token"] == 8
    assert job.facts["tied_head"] is False and job.facts["layers"] == 1
    assert job.flash_call == (2, 4096, 16, 128, True)
    assert job.flash_layers == 1
    shapes = jax.eval_shape(job.init, jax.random.key(0))[0]
    sizes = jax.tree_util.tree_map(lambda x: int(np.prod(x.shape)), shapes)
    assert sizes["LmHead"]["kernel"] == sizes["Embed_0"]["embedding"] == \
        2048 * 50304  # two tables: the head is not tied
    assert sum(jax.tree_util.tree_leaves(sizes)) == 625616896
    assert sum(jax.tree_util.tree_leaves(
        sizes["OlmoeBlock_0"]["OlmoeSparseMoe_0"])) == \
        64 * 3 * 2048 * 1024 + 2048 * 64
    assert all(x.dtype == jnp.float32
               for x in jax.tree_util.tree_leaves(shapes))


def test_published_geometry_of_the_model():
    model = Olmoe1B7B()
    assert (model.layers, model.hidden, model.heads, model.experts,
            model.experts_per_token, model.expert_dim, model.vocab) == \
        (16, 2048, 16, 64, 8, 1024, 50304)
