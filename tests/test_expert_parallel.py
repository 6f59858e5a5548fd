"""The two layers of ``parallel/ep.py`` against dense single-device
references: the top-1 capacity-routed exchange over the ``expert`` axis
(SURVEY §2.8: EP over the alltoall primitive — the layer the reference
lacks), and below it the dropless top-k layer that trains (``moe_topk``;
top-1 is ``k = 1``), whole and cut into shares (``moe_dropless`` with
``held``)."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import mesh as mesh_lib
from horovod_tpu.parallel.ep import (load_balancing_loss, moe_dropless,
                                     moe_layer, moe_topk, relu2_expert,
                                     route_sigmoid_topk, route_topk,
                                     top1_dispatch)

N = 8  # expert-axis extent
D, H = 16, 32
E_LOC = 2
E_TOTAL = N * E_LOC


@pytest.fixture
def ep_mesh():
    return mesh_lib.build_mesh(mesh_lib.MeshSpec(data=1, expert=N))


def _weights(seed=0):
    rng = np.random.RandomState(seed)
    w_gate = rng.randn(D, E_TOTAL).astype(np.float32)
    w_in = (rng.randn(E_TOTAL, D, H) * 0.2).astype(np.float32)
    w_out = (rng.randn(E_TOTAL, H, D) * 0.2).astype(np.float32)
    return w_gate, w_in, w_out


def _dense_moe(x, w_gate, w_in, w_out):
    """Every expert computed for every token; top-1 select (no capacity)."""
    gates = jax.nn.softmax(x @ w_gate, axis=-1)
    idx = jnp.argmax(gates, axis=-1)
    prob = jnp.max(gates, axis=-1)
    h = jax.nn.gelu(jnp.einsum("td,edh->teh", x, w_in))
    all_out = jnp.einsum("teh,ehd->ted", h, w_out)
    sel = jnp.take_along_axis(all_out, idx[:, None, None], axis=1)[:, 0]
    return sel * prob[:, None]


def test_top1_dispatch_positions_and_capacity():
    gates = jnp.asarray([[0.9, 0.1], [0.8, 0.2], [0.7, 0.3], [0.2, 0.8]],
                        jnp.float32)
    dispatch, combine = top1_dispatch(gates, capacity=2)
    # tokens 0,1 land in expert 0 slots 0,1; token 2 (slot 2) is dropped;
    # token 3 lands in expert 1 slot 0
    assert dispatch[0, 0, 0] == 1 and dispatch[1, 0, 1] == 1
    assert float(jnp.sum(dispatch[2])) == 0.0
    assert dispatch[3, 1, 0] == 1
    np.testing.assert_allclose(float(jnp.sum(combine[0])), 0.9, rtol=1e-6)


def test_moe_layer_matches_dense_reference(ep_mesh):
    """With enough capacity nothing drops, and the expert-parallel layer
    (alltoall dispatch over 8 ranks, expert-sharded weights) equals the
    dense computation."""
    w_gate, w_in, w_out = _weights()
    rng = np.random.RandomState(1)
    t_loc = 16
    x = jnp.asarray(rng.randn(N, t_loc, D), jnp.float32)  # per-rank tokens

    def local(x_shard, w_gate, w_in_shard, w_out_shard):
        return moe_layer(x_shard[0], w_gate, w_in_shard, w_out_shard,
                         capacity_factor=float(E_TOTAL))[None]

    mapped = jax.shard_map(
        local, mesh=ep_mesh,
        in_specs=(P("expert"), P(), P("expert"), P("expert")),
        out_specs=P("expert"), check_vma=False)
    got = jax.jit(mapped)(x, jnp.asarray(w_gate), jnp.asarray(w_in),
                          jnp.asarray(w_out))
    for r in range(N):
        want = _dense_moe(jnp.asarray(x[r]), jnp.asarray(w_gate),
                          jnp.asarray(w_in), jnp.asarray(w_out))
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_moe_layer_drops_over_capacity_gracefully(ep_mesh):
    """Starved capacity: outputs stay finite and dropped tokens are exactly
    zero (GShard semantics), never NaN."""
    w_gate, w_in, w_out = _weights(2)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(N, 32, D), jnp.float32)

    def local(x_shard, w_gate, w_in_shard, w_out_shard):
        return moe_layer(x_shard[0], w_gate, w_in_shard, w_out_shard,
                         capacity_factor=0.25)[None]

    mapped = jax.shard_map(
        local, mesh=ep_mesh,
        in_specs=(P("expert"), P(), P("expert"), P("expert")),
        out_specs=P("expert"), check_vma=False)
    got = np.asarray(jax.jit(mapped)(x, jnp.asarray(w_gate),
                                     jnp.asarray(w_in), jnp.asarray(w_out)))
    assert np.isfinite(got).all()
    # with capacity ~ T/4E many tokens must drop -> some all-zero rows
    zero_rows = (np.abs(got).sum(axis=-1) == 0).sum()
    assert zero_rows > 0


def test_moe_layer_differentiable(ep_mesh):
    """Gradients flow to gate and expert weights through the alltoall."""
    w_gate, w_in, w_out = _weights(4)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(N, 8, D), jnp.float32)

    def loss(w_gate, w_in, w_out, x_shard):
        out = moe_layer(x_shard[0], w_gate, w_in, w_out,
                        capacity_factor=4.0)
        return jnp.sum(out ** 2)

    def local(w_gate, w_in_shard, w_out_shard, x_shard):
        g = jax.grad(loss, argnums=(0, 1, 2))(w_gate, w_in_shard,
                                              w_out_shard, x_shard)
        return (jax.lax.psum(g[0], "expert"), g[1], g[2])

    mapped = jax.shard_map(
        local, mesh=ep_mesh,
        in_specs=(P(), P("expert"), P("expert"), P("expert")),
        out_specs=(P(), P("expert"), P("expert")), check_vma=False)
    gg, gi, go = jax.jit(mapped)(jnp.asarray(w_gate), jnp.asarray(w_in),
                                 jnp.asarray(w_out), x)
    for g in (gg, gi, go):
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).sum()) > 0


def test_moe_layer_rejects_wrong_gate_width(ep_mesh):
    """A gate routing to the wrong expert count fails loudly, not with a
    silent shape broadcast."""
    _, w_in, w_out = _weights()
    x = jnp.zeros((8, 4, D), jnp.float32)
    bad_gate = jnp.zeros((D, E_TOTAL + 1), jnp.float32)

    def local(xs, wg, wi, wo):
        return moe_layer(xs[0], wg, wi, wo)[None]

    with pytest.raises(ValueError, match="routes to"):
        jax.shard_map(
            local, mesh=ep_mesh,
            in_specs=(P("expert"), P(), P("expert"), P("expert")),
            out_specs=P("expert"), check_vma=False)(
                x, bad_gate, jnp.asarray(w_in), jnp.asarray(w_out))


# -- the dropless top-k layer (moe_topk): what trains -------------------------

E, F, T = 16, 24, 96  # experts, expert width, tokens


def _gated_weights(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(w, jnp.float32) for w in (
        rng.randn(D, E),                     # router
        rng.randn(E, D, F) * 0.2, rng.randn(E, D, F) * 0.2,  # gate, up
        rng.randn(E, F, D) * 0.2))           # down


def _dense_gated(x, w_router, w_gate, w_up, w_down, k):
    """Every expert for every token, masked by the top-k choice: no sort,
    no grouped matmul, no capacity."""
    probs = jax.nn.softmax(x @ w_router, axis=-1)
    weights, chosen = jax.lax.top_k(probs, k)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    hidden = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) * \
        jnp.einsum("td,edf->tef", x, w_up)
    return jnp.einsum("te,tef,efd->td", gate, hidden, w_down)


@pytest.mark.parametrize("k", [1, 2, 8], ids=["top1", "top2", "top8"])
def test_moe_topk_matches_dense_reference(k):
    """Top-1 is ``k = 1``; nothing is dropped at any k."""
    weights = _gated_weights(k)
    x = jnp.asarray(np.random.RandomState(10 + k).randn(T, D), jnp.float32)
    got, stats = jax.jit(lambda x, *w: moe_topk(x, *w, k))(x, *weights)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense_gated(x, *weights, k)),
                               rtol=2e-4, atol=2e-5)
    assert int(stats.expert_tokens.sum()) == k * T
    assert stats.expert_tokens.dtype == jnp.int32
    np.testing.assert_allclose(float(stats.router_prob_mean.sum()), 1.0,
                               rtol=1e-5)


@pytest.mark.parametrize("k", [1, 8], ids=["top1", "top8"])
def test_moe_topk_keeps_every_pair_under_the_worst_imbalance(k):
    """An adversarial router sends every token to the same k experts: a
    capacity would drop all but a few; here the counts sum to k T, the other
    experts get nothing, and the output is the dense reference's."""
    w_router, w_gate, w_up, w_down = _gated_weights(3)
    x = np.random.RandomState(4).randn(T, D).astype(np.float32)
    x[:, 0] = 1.0  # a constant feature the router keys on
    favoured = np.array([3, 5, 6, 7, 9, 12, 13, 15][:k])
    bias = np.zeros((D, E), np.float32)
    bias[0, favoured] = 50.0
    w_router = w_router * 0.01 + bias
    x = jnp.asarray(x)
    got, stats = jax.jit(lambda x, *w: moe_topk(x, *w, k))(
        x, w_router, w_gate, w_up, w_down)
    counts = np.asarray(stats.expert_tokens)
    assert counts.sum() == k * T
    assert (counts[favoured] == T).all()
    assert np.delete(counts, favoured).sum() == 0
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(_dense_gated(x, w_router, w_gate, w_up, w_down, k)),
        rtol=2e-4, atol=2e-5)


def test_moe_topk_gradients_match_dense_reference():
    """Through the sort, both permutations (whose backward passes are
    gathers, not scatter-adds) and the grouped matmuls."""
    weights = _gated_weights(6)
    x = jnp.asarray(np.random.RandomState(7).randn(T, D), jnp.float32)

    def loss(layer, x, *w):
        return jnp.sum(jnp.tanh(layer(x, *w)) ** 2)
    got = jax.jit(jax.grad(
        lambda x, *w: loss(lambda *a: moe_topk(*a, 4)[0], x, *w),
        argnums=(0, 1, 2, 3, 4)))(x, *weights)
    want = jax.grad(
        lambda x, *w: loss(lambda *a: _dense_gated(*a, 4), x, *w),
        argnums=(0, 1, 2, 3, 4))(x, *weights)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).sum()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-5)


def test_moe_topk_rejects_mismatched_expert_counts():
    w_router, w_gate, w_up, w_down = _gated_weights()
    with pytest.raises(ValueError, match="routes to"):
        moe_topk(jnp.zeros((4, D)), w_router[:, :E - 1], w_gate, w_up,
                 w_down, 2)


def test_router_weights_are_not_renormalised_and_losses_by_hand():
    w_router = _gated_weights(8)[0]
    x = jnp.asarray(np.random.RandomState(9).randn(T, D), jnp.float32)
    weights, chosen, probs, logits = route_topk(x, w_router, 4)
    assert chosen.shape == (T, 4) and chosen.dtype == jnp.int32
    np.testing.assert_allclose(
        np.asarray(weights),
        np.take_along_axis(np.asarray(probs), np.asarray(chosen), axis=-1))
    assert float(weights.sum(axis=-1).max()) < 1.0  # the softmax's own
    # a uniform router: every expert gets k T / E pairs at probability 1 / E
    uniform = load_balancing_loss(jnp.full((2, E), 4 * T // E),
                                  jnp.full((2, E), 1.0 / E), 4)
    assert float(uniform) == pytest.approx(4.0)
    # everything to 4 experts at probability 1/4 each: E / k times as much
    counts = jnp.zeros((E,), jnp.int32).at[:4].set(T)
    collapsed = load_balancing_loss(counts, counts / (4.0 * T), 4)
    assert float(collapsed) == pytest.approx(E / 4 * 4.0)


# -- a share of the experts (moe_dropless with held): what one chip of an
# expert-parallel deployment computes ------------------------------------------

K_SHARE = 4
SHARED = 40  # the shared expert's width


def _share_weights(seed=0):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(v, jnp.float32) for k, v in dict(
        router=rng.randn(D, E) * 0.5, bias=rng.randn(E) * 0.05,
        up=rng.randn(E, D, F) * 0.2, down=rng.randn(E, F, D) * 0.2,
        shared_up=rng.randn(D, SHARED) * 0.2,
        shared_down=rng.randn(SHARED, D) * 0.2).items()}


def _relu2(x, w_up, w_down):
    return jnp.square(jnp.maximum(x @ w_up, 0.0)) @ w_down


def _uncut_layer(x, w):
    """The whole layer, dense: every expert for every token, masked by the
    choice (top k of sigmoid + bias; weights without the bias, renormalised,
    x 2.5), plus the shared expert once."""
    scores = jax.nn.sigmoid(x @ w["router"])
    chosen = jax.lax.top_k(scores + w["bias"], K_SHARE)[1]
    picked = (chosen[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jnp.where(picked, scores, 0.0)
    gate = 2.5 * gate / gate.sum(-1, keepdims=True)
    hidden = jnp.square(jnp.maximum(
        jnp.einsum("td,edf->tef", x, w["up"]), 0.0))
    return jnp.einsum("te,tef,efd->td", gate, hidden, w["down"]) + \
        _relu2(x, w["shared_up"], w["shared_down"])


def _share(x, w, first, count):
    """One chip's routed part: the experts first .. first + count - 1."""
    route = functools.partial(route_sigmoid_topk, w_router=w["router"],
                              bias=w["bias"], k=K_SHARE, scale=2.5)
    return moe_dropless(
        x, route, relu2_expert,
        (w["up"][first:first + count], w["down"][first:first + count]),
        held=(first, count))


@pytest.mark.parametrize("shares", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """16 experts cut into 1, 2, 4 and 8 shares: the shares' routed parts
    plus the shared expert counted once are the uncut reference's whole
    layer, outputs and the gradients of tokens and router; each share's
    counts are over all 16 experts and sum to k T."""
    w = _share_weights(shares)
    x = jnp.asarray(np.random.RandomState(20 + shares).randn(T, D),
                    jnp.float32)
    count = E // shares

    def cut_layer(x, w):
        parts = [_share(x, w, first, count)
                 for first in range(0, E, count)]
        for _, stats in parts:
            assert stats.expert_tokens.shape == (E,)
        return sum(out for out, _ in parts) + \
            _relu2(x, w["shared_up"], w["shared_down"]), \
            [stats.expert_tokens for _, stats in parts]
    got, counts = jax.jit(cut_layer)(x, w)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_uncut_layer(x, w)),
                               rtol=2e-4, atol=2e-5)
    for c in counts:
        assert int(c.sum()) == K_SHARE * T
        np.testing.assert_array_equal(np.asarray(c), np.asarray(counts[0]))

    def loss(layer, x, w):
        return jnp.sum(jnp.tanh(layer(x, w)) ** 2)
    got = jax.jit(jax.grad(lambda x, w: loss(
        lambda *a: cut_layer(*a)[0], x, w), argnums=(0, 1)))(x, w)
    want = jax.grad(lambda x, w: loss(_uncut_layer, x, w),
                    argnums=(0, 1))(x, w)
    assert float(jnp.abs(got[1]["bias"]).sum()) == 0.0  # no gradient
    for g, v in zip(jax.tree_util.tree_leaves((got[0], {
            k: a for k, a in got[1].items() if k != "bias"})),
            jax.tree_util.tree_leaves((want[0], {
                k: a for k, a in want[1].items() if k != "bias"}))):
        assert float(jnp.abs(v).sum()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-3,
                                   atol=2e-5)


def test_a_share_that_is_sent_no_pair_gives_zeros_and_zero_gradients():
    """A bias of -10 on the held experts: no token chooses them. The share's
    output is zero, and so is every gradient it returns."""
    w = _share_weights(5)
    w["bias"] = w["bias"].at[4:8].set(-10.0)
    x = jnp.asarray(np.random.RandomState(6).randn(T, D), jnp.float32)
    out, stats = jax.jit(lambda x, w: _share(x, w, 4, 4))(x, w)
    assert np.asarray(stats.expert_tokens)[4:8].sum() == 0
    assert int(stats.expert_tokens.sum()) == K_SHARE * T
    assert (np.asarray(out) == 0).all()
    grads = jax.jit(jax.grad(
        lambda x, w: jnp.sum(_share(x, w, 4, 4)[0] + 1.0) ** 2,
        argnums=(0, 1)))(x, w)
    for g in jax.tree_util.tree_leaves(grads):
        assert np.isfinite(np.asarray(g)).all()
        assert (np.asarray(g) == 0).all()


def test_a_share_drops_nothing_when_every_pair_goes_to_one_held_expert():
    """The router pushed to send every token's first choice to expert 6,
    which this share holds: all T pairs are computed, as the dense
    reference's share."""
    w = _share_weights(7)
    w["bias"] = w["bias"].at[6].set(10.0)
    x = jnp.asarray(np.random.RandomState(8).randn(T, D), jnp.float32)
    out, stats = jax.jit(lambda x, w: _share(x, w, 4, 4))(x, w)
    counts = np.asarray(stats.expert_tokens)
    assert counts[6] == T and counts.sum() == K_SHARE * T
    scores = jax.nn.sigmoid(x @ w["router"])
    chosen = jax.lax.top_k(scores + w["bias"], K_SHARE)[1]
    picked = (chosen[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jnp.where(picked, scores, 0.0)
    gate = 2.5 * gate / gate.sum(-1, keepdims=True)
    hidden = jnp.square(jnp.maximum(
        jnp.einsum("td,edf->tef", x, w["up"][4:8]), 0.0))
    want = jnp.einsum("te,tef,efd->td", gate[:, 4:8], hidden, w["down"][4:8])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_a_share_rejects_weights_that_do_not_lead_with_its_count():
    w = _share_weights()
    route = functools.partial(route_sigmoid_topk, w_router=w["router"],
                              bias=w["bias"], k=K_SHARE)
    x = jnp.zeros((4, D))
    with pytest.raises(ValueError, match="holds 4 from 4 on"):
        moe_dropless(x, route, relu2_expert, (w["up"][:3], w["down"][:3]),
                     held=(4, 4))
    with pytest.raises(ValueError, match="routes to 16"):
        moe_dropless(x, route, relu2_expert, (w["up"][:8], w["down"][:8]),
                     held=(12, 8))
