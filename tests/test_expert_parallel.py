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
                                     moe_layer, moe_routing, moe_topk,
                                     reglu_expert, relu2_expert,
                                     route_sigmoid_topk, route_topk,
                                     route_topk_softmax, swiglu_expert,
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
    want = jax.jit(jax.grad(
        lambda x, *w: loss(lambda *a: _dense_gated(*a, 4), x, *w),
        argnums=(0, 1, 2, 3, 4)))(x, *weights)
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
    want = jax.jit(jax.grad(lambda x, w: loss(_uncut_layer, x, w),
                            argnums=(0, 1)))(x, w)
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


# -- a share's walk over its sorted pairs in static tiles ----------------------

@pytest.fixture
def small_tiles(monkeypatch):
    """Row blocks of 8, so that a tiny layer walks tiles of four slots of
    40 rows in threes: blocks of 128 would make 384 pairs one tile. And the
    tests' expert matrices (16 x 24) wide enough for the kernels over live
    blocks, as 1024 is at blocks of 128."""
    from horovod_tpu.parallel import ep
    monkeypatch.setattr(ep, "SHARE_BLOCK_ROWS", 8)
    monkeypatch.setattr(ep, "SHARE_BLOCKS_MIN_WIDTH", 16)
    return ep


@pytest.fixture(params=["blocks", "slots"])
def small_tiles_each_way(request, small_tiles, monkeypatch):
    """``small_tiles`` with the walk's product taken each way. The tests'
    expert matrices (16 x 24) are whole row blocks of 8, so the rule
    (``ep.share_product``) takes the grouped-matmul kernels over the live
    blocks; ``slots`` steers it to the batched product, as widths that are
    not whole blocks do (Nemotron-H's 2688 x 1856 at blocks of 128)."""
    assert small_tiles.share_product((D, F, F, D)) == "blocks"
    assert small_tiles.share_product((D, F + 4)) == "slots"  # no whole 8s
    assert small_tiles.share_product((D, 8)) == "slots"  # too narrow
    if request.param == "slots":
        monkeypatch.setattr(small_tiles, "share_product",
                            lambda widths: "slots")
    return small_tiles


FIRST, COUNT = 4, 4
K_T = K_SHARE * T
# a slot: 1.5 x the 24 pairs a balanced router sends one of 16 experts, in
# whole blocks of 8; a tile: a slot for each of the 4 held experts
SLOT = 40
TILE = COUNT * SLOT


def _choices(sizes):
    """[T, k] experts by which held expert ``FIRST + e`` is sent exactly
    ``sizes[e]`` pairs (slot ``e`` of the first ``sizes[e]`` tokens), a
    token's k all different; every other pair goes elsewhere."""
    elsewhere = np.array([e for e in range(E)
                          if not FIRST <= e < FIRST + COUNT])
    token, slot = np.divmod(np.arange(K_T), K_SHARE)
    held = token < np.asarray(sizes)[slot]
    return jnp.asarray(np.where(held, FIRST + slot, elsewhere[slot])
                       .reshape(T, K_SHARE), jnp.int32)


def _routed_as_told(x, w_router, experts):
    """A router whose choice is given and whose weights are the chosen
    experts' sigmoid scores."""
    logits = x @ w_router
    scores = jax.nn.sigmoid(logits)
    return jnp.take_along_axis(scores, experts, axis=-1), experts, scores, \
        logits


def _told_share(x, w, experts, first=FIRST, count=COUNT):
    route = functools.partial(_routed_as_told, w_router=w["router"],
                              experts=experts)
    return moe_dropless(
        x, route, relu2_expert,
        (w["up"][first:first + count], w["down"][first:first + count]),
        held=(first, count))


def _told_dense(x, w, experts, first=FIRST, count=COUNT):
    scores = jax.nn.sigmoid(x @ w["router"])
    picked = (experts[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jnp.where(picked, scores, 0.0)[:, first:first + count]
    hidden = jnp.square(jnp.maximum(jnp.einsum(
        "td,edf->tef", x, w["up"][first:first + count]), 0.0))
    return jnp.einsum("te,tef,efd->td", gate, hidden,
                      w["down"][first:first + count])


def _walked_share_against_dense(ep, sizes, first, count, tile, tiles):
    """Output and gradients (tokens, router, both expert matrices) of the
    share ``(first, count)`` under ``_choices(sizes)`` against the dense
    reference's; ``tiles`` = (live, built) by ``share_tiles``."""
    assert ep.share_tile_rows(K_T, count, E) == tile
    w = _share_weights(sum(sizes))
    x = jnp.asarray(np.random.RandomState(30).randn(T, D), jnp.float32)
    experts = _choices(sizes)
    share = functools.partial(_told_share, first=first, count=count)
    dense = functools.partial(_told_dense, first=first, count=count)
    out, stats = jax.jit(share)(x, w, experts)
    n_held = int(stats.expert_tokens[first:first + count].sum())
    assert n_held == sum(sizes[first - FIRST:first - FIRST + count])
    assert ep.share_tiles(stats.expert_tokens, (first, count), K_SHARE,
                          T) == tiles
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense(x, w, experts)),
                               rtol=2e-4, atol=2e-5)

    def loss(layer, x, w):
        return jnp.sum(jnp.tanh(layer(x, w, experts)) ** 2)
    got = jax.jit(jax.grad(lambda x, w: loss(
        lambda *a: share(*a)[0], x, w), argnums=(0, 1)))(x, w)
    want = jax.jit(jax.grad(lambda x, w: loss(dense, x, w),
                            argnums=(0, 1)))(x, w)
    for name, g, v in [("x", got[0], want[0])] + [
            (key, got[1][key], want[1][key])
            for key in ("router", "up", "down")]:
        assert np.isfinite(np.asarray(g)).all(), name
        assert (float(jnp.abs(v).sum()) > 0) == (n_held > 0), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-3,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("sizes,live", [
    ([0, 0, 0, 0], 0), ([39, 12, 39, 0], 1), ([40, 40, 40, 40], 1),
    ([41, 3, 40, 17], 2), ([96, 81, 5, 80], 3), ([96, 96, 96, 96], 3),
    ([39, 39, 39, 39], 1), ([7, 0, 1, 0], 1), ([3, 0, 90, 0], 3)],
    ids=["none", "a-row-short-of-a-slot", "every-slot-full",
         "a-slot-and-a-row", "three-tiles", "every-pair",
         "each-a-row-short-of-a-slot", "two-experts-with-no-pair",
         "one-expert-in-three-tiles-beside-empty-slots"])
def test_a_walked_share_is_exact_wherever_the_held_pairs_end(small_tiles,
                                                             sizes, live):
    """Three tiles of 160 rows (four slots of 40) over 384 sorted pairs.
    Slot ``e`` of tile ``i`` holds pairs ``[40 i, 40 (i + 1))`` of held
    expert ``e``, so the live tiles are those the fullest expert reaches:
    its pairs ending before, on and after a slot's edge, nowhere, and every
    pair there is; every expert a row short of its slot, experts with no
    pair between experts with few, one expert far ahead of the others. The
    output and the gradients of tokens, router and both expert matrices are
    the dense reference's."""
    assert -(-max(sizes) // SLOT) == live
    _walked_share_against_dense(small_tiles, sizes, FIRST, COUNT, TILE,
                                (live, 3))


@pytest.mark.parametrize("pairs,live", [(96, 3), (41, 2), (40, 1)],
                         ids=["three-tiles", "a-tile-and-a-row", "a-tile"])
def test_a_walked_share_of_one_expert_with_more_than_a_tile(
        small_tiles_each_way, pairs, live):
    """A share of one expert walks tiles of its one slot of 40 rows: it is
    sent three tiles full, a tile and a row, a tile; every pair is
    computed, by the kernels over the slot's live blocks and by the batched
    product over the slot."""
    sizes = [0, 0, pairs, 0]
    _walked_share_against_dense(small_tiles_each_way, sizes, FIRST + 2, 1,
                                SLOT, (live, 3))


def _told_gated_share(x, weights, experts):
    """The share ``(FIRST, COUNT)`` of a gated layer (three matrices an
    expert) with the choice given, the weights the chosen experts' softmax
    probabilities."""
    w_router, *expert_weights = weights

    def route(x):
        logits = x @ w_router
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.take_along_axis(probs, experts, axis=-1), experts, \
            probs, logits
    return moe_dropless(x, route, swiglu_expert,
                        [w[FIRST:FIRST + COUNT] for w in expert_weights],
                        held=(FIRST, COUNT))[0]


def _told_dense_gated_share(x, weights, experts):
    w_router, w_gate, w_up, w_down = (
        w if i == 0 else w[FIRST:FIRST + COUNT]
        for i, w in enumerate(weights))
    picked = (experts[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jnp.where(picked, jax.nn.softmax(x @ w_router, axis=-1), 0.0)
    hidden = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) * \
        jnp.einsum("td,edf->tef", x, w_up)
    return jnp.einsum("te,tef,efd->td", gate[:, FIRST:FIRST + COUNT],
                      hidden, w_down)


def _unwritten_is_nan(grouped_matmul):
    """``ops/grouped_matmul.grouped_matmul`` with NaN in every row of its
    result, and of the gradient towards the rows, that lies in a block no
    live step names: the kernels write nothing there, and on the chip the
    buffer may hold anything."""
    def dead(out, group_of_block, block_of_step, live):
        blocks = group_of_block.shape[0]
        named = jnp.zeros(blocks, bool).at[block_of_step].max(
            jnp.arange(blocks) < live[0])
        return jnp.where(jnp.repeat(named, out.shape[0] // blocks)[:, None],
                         out, jnp.nan)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
    def product(a, w, group_of_block, block_of_step, live, transposed=False):
        return dead(grouped_matmul(a, w, group_of_block, block_of_step, live,
                                   transposed),
                    group_of_block, block_of_step, live)

    def forward(a, w, *tables_and_transposed):
        return product(a, w, *tables_and_transposed), \
            (a, w) + tables_and_transposed[:3]

    def backward(transposed, saved, d_out):
        a, w, *tables = saved
        d_a, d_w = jax.vjp(lambda a, w: grouped_matmul(
            a, w, *tables, transposed), a, w)[1](d_out)
        return dead(d_a, *tables), d_w, None, None, None
    product.defvjp(forward, backward)
    return product


BLOCK_PRODUCT_CASES = {
    # name: (pairs of held experts FIRST .. FIRST + 3, live tiles, NaN)
    "a-balanced-load": ([24, 24, 24, 24], 1, False),
    "one-expert-sent-more-than-a-slot": ([24, 47, 20, 24], 2, False),
    "an-expert-sent-nothing": ([24, 0, 30, 7], 1, False),
    "counts-that-end-inside-on-and-a-row-past-a-block":
        ([19, 16, 5, 33], 1, False),
    "unwritten-rows-hold-nan": ([24, 0, 47, 13], 2, True),
}


@pytest.mark.parametrize("case", list(BLOCK_PRODUCT_CASES))
def test_the_walks_block_product_is_its_batched_product_and_the_reference(
        small_tiles, monkeypatch, case):
    """A gated share (three matrices an expert, as LFM2's, SmallThinker's
    and SDAR's) walked with the kernels over each slot's live row blocks,
    walked with the batched product over whole slots, and the dense
    reference: the output and the gradients of tokens, router and all three
    matrices agree, under a balanced load (three of a slot's five blocks
    live), one held expert sent more than a slot (a second tile in which
    one slot alone has a live block), a held expert sent nothing (a slot
    with no live block: its matrices' gradients exactly zero), counts that
    end inside a block, on its edge and a row past it, and with NaN in
    every row the kernels leave unwritten, forward and backward: nothing a
    dead block holds reaches the output or any gradient."""
    ep = small_tiles
    sizes, live, unwritten_is_nan = BLOCK_PRODUCT_CASES[case]
    assert -(-max(sizes) // SLOT) == live
    weights = _gated_weights(sum(sizes))
    x = jnp.asarray(np.random.RandomState(50).randn(T, D), jnp.float32)
    experts = _choices(sizes)

    def out_and_grads(layer):
        def loss(x, weights):
            out = layer(x, weights, experts)
            return jnp.sum(jnp.tanh(out) ** 2), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(x, weights)
        return (out, grads[0]) + tuple(grads[1])
    assert ep.share_product(ep._widths_of(weights[1:])) == "blocks"
    if unwritten_is_nan:
        monkeypatch.setattr(ep, "grouped_matmul",
                            _unwritten_is_nan(ep.grouped_matmul))
    by_blocks = out_and_grads(_told_gated_share)
    monkeypatch.setattr(ep, "share_product", lambda widths: "slots")
    by_slots = out_and_grads(_told_gated_share)
    dense = out_and_grads(_told_dense_gated_share)
    for name, blocks, slots, want in zip(
            ("out", "x", "router", "gate", "up", "down"), by_blocks,
            by_slots, dense):
        assert np.isfinite(np.asarray(blocks)).all(), name
        assert float(jnp.abs(want).sum()) > 0, name
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(slots),
                                   rtol=1e-5, atol=1e-6, err_msg=name)
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(want),
                                   rtol=2e-3, atol=2e-5, err_msg=name)
    for name, g in zip(("gate", "up", "down"), by_blocks[3:]):
        unused = np.abs(np.asarray(g)).reshape(E, -1).sum(axis=1) == 0
        np.testing.assert_array_equal(
            unused[FIRST:FIRST + COUNT], np.asarray(sizes) == 0,
            err_msg=name)


def test_a_balanced_load_at_lfm2s_shapes_computes_its_rows_in_blocks():
    """LFM2's layer as ``lfm2-t16384`` holds it (16 384 tokens, top-4 of 32
    experts of 2048 x 1792, 8 held; traced, not run): a slot is 3072 rows
    and the walk is built with the kernels (``share_product``, counted by
    ``hvd_moe_share_product_total{path="blocks"}``); Nemotron-H's 2688 x
    1856 is built with the batched product (``path="slots"``), as experts
    under 1024 wide are. From a
    balanced load ``share_tiles`` counts the held pairs in whole blocks of
    128: computed / held under 1.1 where whole slots were 1.5."""
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.parallel import ep

    def counted(*names):
        return [get_registry().counter(name, **labels).value for name, labels
                in zip(names[::2], names[1::2])]
    product = "hvd_moe_share_product_total"
    rows = "hvd_moe_share_rows_total"

    def traced(tokens, d, f, n_experts, k, shapes, expert):
        route = functools.partial(route_sigmoid_topk, k=k,
                                  bias=jnp.zeros(n_experts))
        jax.eval_shape(
            lambda x, router, *held: moe_dropless(
                x, functools.partial(route, w_router=router), expert, held,
                held=(0, 8)),
            jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((d, n_experts), jnp.float32),
            *(jax.ShapeDtypeStruct((8,) + shape, jnp.bfloat16)
              for shape in shapes))
    before = counted(product, dict(path="blocks"), product,
                     dict(path="slots"))
    traced(16384, 2048, 1792, 32, 4,
           [(2048, 1792), (2048, 1792), (1792, 2048)], swiglu_expert)
    assert counted(product, dict(path="blocks"), product,
                   dict(path="slots")) == [before[0] + 1, before[1]]
    traced(8192, 2688, 1856, 128, 6, [(2688, 1856), (1856, 2688)],
           relu2_expert)
    assert counted(product, dict(path="blocks"), product,
                   dict(path="slots")) == [before[0] + 1, before[1] + 1]
    # whole 128s, but under 1024 wide: SmallThinker's and SDAR's experts
    assert ep.share_product((2560, 768, 768, 2560)) == "slots"
    assert ep.share_product((2048, 768)) == "slots"
    assert ep.share_product((1024, 1024)) == "blocks"
    assert ep.share_slot_rows(4 * 16384, 32) == 3072
    # the step-0 load of ``lfm2-t16384``'s first sparse layer on the chip
    # (``PERF.md`` §6, PR 44): held experts are sent 1802-2365 pairs
    load = np.full(32, 2048)
    load[:8] = [1802, 2365, 2048, 1983, 2126, 2200, 1900, 2047]
    def recorded(widths):
        kinds = (rows, dict(kind="held"), rows, dict(kind="computed"))
        before = counted(*kinds)
        assert ep.share_tiles(load, (0, 8), 4, 16384, record=True,
                              widths=widths) == (1, 6)
        return [now - was for now, was in zip(counted(*kinds), before)]
    held, computed = recorded((2048, 1792))
    assert held == load[:8].sum() and computed % 128 == 0
    assert 1.0 <= computed / held < 1.1
    # the same load under the batched product: every slot of the one tile
    assert recorded((2688, 1856)) == [held, 8 * 3072]


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_the_walked_shares_add_up_to_the_uncut_layer(small_tiles, shares):
    """The shares of ``test_the_shares_add_up_to_the_uncut_layer`` again,
    each now a walk of up to three tiles of eight, four or two slots."""
    assert small_tiles.share_tile_rows(K_T, E // shares, E) \
        == E // shares * SLOT < K_T
    assert small_tiles.share_tiles(np.zeros(E), (0, E // shares), K_SHARE,
                                   T) == (0, 3)
    test_the_shares_add_up_to_the_uncut_layer(shares)


@pytest.mark.parametrize("case", ["no-pair", "one-expert"])
def test_a_walked_share_at_the_ends_of_imbalance(small_tiles, case):
    """No pair at all, and every token's first choice to one held expert:
    the two older tests, walked."""
    if case == "no-pair":
        test_a_share_that_is_sent_no_pair_gives_zeros_and_zero_gradients()
    else:
        test_a_share_drops_nothing_when_every_pair_goes_to_one_held_expert()


def test_a_full_load_is_one_tile_and_traces_to_the_kernels_alone():
    """``held=None`` is the one-tile case with no loop and no scatter:
    ``moe_topk``'s jaxpr, forward and with its gradients, holds the repo's
    grouped matmul three times forward and nine times with the gradients
    (the three, towards the rows, towards the matrices; each traced for the
    TPU and for interpret mode, ``lax.platform_dependent``'s two branches)
    and no ``ragged_dot_general``; it is to the letter the program PR 36
    wrote with PR 47's one operand more a kernel call, the blocks by grid
    step, here an ``iota`` (sha256 of its text)."""
    import hashlib
    from horovod_tpu.parallel import ep
    assert ep.share_tile_rows(8 * 8192, 64, 64) == 8 * 8192
    weights = _gated_weights()
    x = jnp.zeros((T, D), jnp.float32)
    forward = str(jax.make_jaxpr(lambda *a: moe_topk(*a, 4))(x, *weights))
    backward = str(jax.make_jaxpr(jax.grad(
        lambda *a: moe_topk(*a, 4)[0].sum(), argnums=(0, 1, 2, 3, 4)))(
            x, *weights))
    for word in ("while", "scatter", "ragged_dot_general"):
        assert word not in forward and word not in backward, word
    assert forward.count("name=_gmm_call") == 2 * 3
    assert "name=_gmm_dw_call" not in forward
    assert backward.count("name=_gmm_call") == 2 * 6
    assert backward.count("name=_gmm_dw_call") == 2 * 3
    assert hashlib.sha256(forward.encode()).hexdigest() == \
        "76a335968c66e24fac1fdbeb10897d36a1d59db75a5461ef96209d57d80327db"
    assert hashlib.sha256(backward.encode()).hexdigest() == \
        "dc552f5fa5275fab110cf054ff799b46d9f7d0c0256fa52b3dd6620142931431"


# -- a full load: every expert's pairs from a row block on ----------------------

def _told_topk(x, weights, experts):
    """``moe_dropless`` over every expert with the choice given, the
    weights the chosen experts' softmax probabilities."""
    w_router, *expert_weights = weights

    def route(x):
        logits = x @ w_router
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.take_along_axis(probs, experts, axis=-1), experts, \
            probs, logits
    return moe_dropless(x, route, swiglu_expert, expert_weights)


def _told_dense_gated(x, weights, experts):
    w_router, w_gate, w_up, w_down = weights
    picked = (experts[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jnp.where(picked, jax.nn.softmax(x @ w_router, axis=-1), 0.0)
    hidden = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) * \
        jnp.einsum("td,edf->tef", x, w_up)
    return jnp.einsum("te,tef,efd->td", gate, hidden, w_down)


@pytest.mark.parametrize("sizes", [
    [7, 8, 9, 0], [1, 16, 17, 95], [96, 0, 96, 0], [8, 8, 8, 8],
    [0, 0, 0, 0]],
    ids=["a-row-short-of-a-block-on-it-and-past-it", "one-pair-and-two-blocks",
         "every-token-or-none", "whole-blocks", "half-the-experts-unused"])
def test_a_full_load_is_exact_wherever_an_experts_pairs_end(small_tiles,
                                                            sizes):
    """Row blocks of 8 over 384 pairs of 16 experts. Expert ``4 + e`` is
    sent ``sizes[e]`` pairs and expert ``e`` the other ``96 - sizes[e]`` of
    slot ``e``; experts 8 to 15 none: an expert's pairs end a row short of
    a block, on it, a row past it and nowhere, one expert is sent every
    token, experts with no pair lie between experts with many. The output
    and the gradients of tokens, router and all three expert matrices are
    the dense reference's, and an expert with no pair gets exactly zero in
    every matrix: nothing reaches a weight through a row of padding."""
    weights = _gated_weights(sum(sizes))
    x = jnp.asarray(np.random.RandomState(40).randn(T, D), jnp.float32)
    experts = _choices(sizes)
    out, stats = jax.jit(_told_topk)(x, weights, experts)
    counts = np.asarray(stats.expert_tokens)
    assert list(counts[4:8]) == sizes and counts.sum() == K_T
    live, built = small_tiles.grouped_blocks(counts, K_SHARE, T)
    assert live == sum(-(-n // 8) for n in counts) <= built == K_T // 8 + E
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_told_dense_gated(x, weights, experts)),
        rtol=2e-4, atol=2e-5)

    def loss(layer, x, weights):
        return jnp.sum(jnp.tanh(layer(x, weights, experts)) ** 2)
    got = jax.jit(jax.grad(lambda x, w: loss(
        lambda *a: _told_topk(*a)[0], x, w), argnums=(0, 1)))(x, weights)
    want = jax.jit(jax.grad(lambda x, w: loss(_told_dense_gated, x, w),
                            argnums=(0, 1)))(x, weights)
    for name, g, v in zip(("x", "router", "gate", "up", "down"),
                          (got[0],) + tuple(got[1]),
                          (want[0],) + tuple(want[1])):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-3,
                                   atol=2e-5, err_msg=name)
    for name, g in zip(("gate", "up", "down"), got[1][1:]):
        unused = np.abs(np.asarray(g)).reshape(E, -1).sum(axis=1) == 0
        np.testing.assert_array_equal(unused, counts == 0, err_msg=name)


def test_rows_of_padding_gather_in_bounds_and_return_zero_gradients(
        small_tiles):
    """The layout by hand: counts 3, 0, 9, 8 in blocks of 8 lie at rows
    0-2, 8-16, 24-31 of 4 live blocks (of 7 built: 20 pairs in 3 blocks and
    one an expert); the block-to-expert table skips the expert with no pair
    and ends with the last expert's index; a row of padding names a pair in
    bounds; and the gradient that returns to the blocks from the pairs is
    exactly zero on every row that holds no pair."""
    ep = small_tiles
    keys = jnp.asarray([2, 3, 0, 2, 2, 3, 0, 2, 3, 3, 2, 2, 0, 3, 2, 3, 2,
                        3, 2, 3], jnp.int32)
    sizes = jnp.asarray([3, 0, 9, 8], jnp.int32)
    order = jnp.argsort(keys, stable=True)
    blocks = ep._blocks_of(sizes, keys, order, jnp.argsort(order), False)
    assert blocks.held is None and int(blocks.live[0]) == 4
    assert list(np.asarray(blocks.group_of_block)) == [0, 2, 2, 3, 3, 3, 3]
    real = np.asarray(blocks.real)
    assert real.shape == (7 * 8,)
    assert sorted(np.flatnonzero(real)) == [0, 1, 2] + list(range(8, 17)) \
        + list(range(24, 32))
    pair_of_row = np.asarray(blocks.pair_of_row)
    assert pair_of_row.min() >= 0 and pair_of_row.max() < 20
    row_of_pair = np.asarray(blocks.row_of_pair)
    np.testing.assert_array_equal(pair_of_row[row_of_pair], np.arange(20))
    np.testing.assert_array_equal(np.sort(row_of_pair), np.flatnonzero(real))
    rows = jnp.asarray(np.random.RandomState(0).randn(56, 5), jnp.float32)
    pairs, pull = jax.vjp(lambda r: ep._rows_from_blocks(r, blocks), rows)
    np.testing.assert_array_equal(np.asarray(pairs),
                                  np.asarray(rows)[row_of_pair])
    back = np.asarray(pull(jnp.ones_like(pairs))[0])
    np.testing.assert_array_equal(
        back, np.broadcast_to(np.where(real[:, None], 1.0, 0.0), back.shape))
    x = jnp.asarray(np.random.RandomState(1).randn(10, 5), jnp.float32)
    rows, pull = jax.vjp(lambda x: ep._rows_to_blocks(x, blocks, 2), x)
    np.testing.assert_array_equal(np.asarray(rows)[row_of_pair],
                                  np.asarray(x)[np.arange(20) // 2])
    # a token's gradient is the sum of its k pairs' rows; padding adds none
    back = np.asarray(pull(jnp.broadcast_to(
        jnp.where(blocks.real[:, None], 1.0, 9.0), rows.shape))[0])
    np.testing.assert_array_equal(back, np.full((10, 5), 2.0))


def _walk_operands(ep, x, experts, weights, first, count, n_experts):
    """What ``moe_dropless`` hands ``ep._walk``: the stable sort of the held
    keys, its inverse, the held counts, the tile."""
    k = experts.shape[-1]
    keys = ep._held_first(experts.reshape(-1), first, count)
    order = jnp.argsort(keys, stable=True)
    sizes = jnp.sum(keys[:, None] == jnp.arange(count), axis=0,
                    dtype=jnp.int32)
    return order, jnp.argsort(order), weights, sizes, \
        ep.share_tile_rows(k * x.shape[0], count, n_experts)


def _walk_by_scatter_add(ep, x, order, weights, sizes, expert_weights,
                         expert, tile):
    """The walk as it was before PR 39, every built tile written out: a
    tile's weighted rows return by ``jnp.zeros(...).at[tokens].add(...)`` in
    float32, and ``d_x`` is JAX's own transpose of the gather: a
    scatter-add, in float32 too since the rows are gathered from ``x`` in
    float32 and only then take the experts' dtype."""
    dtype, x = x.dtype, x.astype(jnp.float32)
    at = ep._share_tiles_of(tile, x, order, weights, sizes, expert,
                            expert_weights)[1]
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(-(-x.shape[0] // (tile // sizes.shape[0]))):
        tokens, back, _, weight, rows, experts = at(i)
        # a row that is no pair goes nowhere and nothing returns through
        # it: under the kernels over live blocks its rows of every product
        # may never have been written (interpret mode leaves them NaN)
        is_pair = (back < x.shape[0])[:, None]
        rows = experts(jnp.where(is_pair, rows, 0.0).astype(dtype),
                       *expert_weights)
        out = out + jnp.zeros(x.shape, jnp.float32).at[tokens].add(
            jnp.where(is_pair, weight[:, None] * rows.astype(jnp.float32),
                      0.0))
    return out


def _walk_against_scatter_add(ep, x, order, inverse, weights, sizes,
                              expert_weights, expert, tile, any_pair):
    """The tokens' gradient under one seeded cotangent and the output of
    ``ep._walk``, each against :func:`_walk_by_scatter_add`'s, float32."""
    def walked(x):
        return ep._walk(x, order, inverse, weights, sizes, expert_weights,
                        expert, tile)

    def scattered(x):
        return _walk_by_scatter_add(ep, x, order, weights, sizes,
                                    expert_weights, expert, tile)
    cotangent = jnp.asarray(np.random.RandomState(8).randn(*x.shape),
                            jnp.float32)
    for got, want in zip(*(
            jax.jit(lambda x, f=f: jax.vjp(f, x)[1](cotangent) + (f(x),))(x)
            for f in (walked, scattered))):
        assert got.dtype == want.dtype == jnp.float32
        assert (float(jnp.abs(want).sum()) > 0) == any_pair
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


WAY_BACK_CASES = {
    # name: per-expert pairs of ``_choices`` (FIRST .. FIRST + 3 held)
    "a-balanced-load": [24, 24, 24, 24],
    "one-held-expert-sent-every-token": [96, 0, 0, 0],
    "every-token-holds-k-held-experts": [96, 96, 96, 96],
    "a-held-expert-sent-nothing": [30, 0, 41, 7],
    "pairs-end-on-a-slots-edge": [40, 80, 40, 40],
    "pairs-end-one-past-a-slots-edge": [41, 81, 1, 40],
    "pairs-end-on-a-token-blocks-edge": [32, 64, 33, 31],
}


@pytest.mark.parametrize("case", sorted(WAY_BACK_CASES))
def test_the_walks_way_back_is_the_scatter_adds(small_tiles, case):
    """``ep._walk``, forward and the tokens' gradient, against the same
    walk with its rows returned by ``at[tokens].add`` in float32: a balanced
    load, one held expert sent every token (every tile live, each adding to
    the one before), every token sending all its k pairs to held experts
    (k rows a token), a held expert with no pair, pairs that end on and one
    past a slot's edge (40) and a token block's (32)."""
    ep, sizes = small_tiles, WAY_BACK_CASES[case]
    w = _share_weights(5)
    x = jnp.asarray(np.random.RandomState(6).randn(T, D), jnp.float32)
    experts = _choices(sizes)
    weights = jnp.take_along_axis(jax.nn.sigmoid(x @ w["router"]), experts,
                                  axis=-1)
    order, inverse, weights, held_sizes, tile = _walk_operands(
        ep, x, experts, weights, FIRST, COUNT, E)
    assert tile == TILE and list(held_sizes) == sizes
    held = (w["up"][FIRST:FIRST + COUNT], w["down"][FIRST:FIRST + COUNT])

    _walk_against_scatter_add(ep, x, order, inverse, weights, held_sizes,
                              held, relu2_expert, tile, sum(sizes) > 0)


@pytest.mark.parametrize("cell", ["smallthinker", "nemotron", "lfm2"])
def test_the_way_back_at_both_cells_shapes_scaled_down(cell):
    """Eight of the router's experts held, row blocks of 128 as built:
    SmallThinker's layer (top-6 of 64 experts, ReGLU, the softmax of the
    chosen logits) at a hidden size of two 128s and Nemotron-H's (top-6 of
    128, relu^2, sigmoid scores) at three, both with the batched product
    over slots as in their cells (``ep.share_product``: experts too narrow,
    or no whole 128s); and, since PR 47, LFM2's (top-4 of 32, SwiGLU,
    sigmoid scores) with experts 1024 x 1024, the narrowest that take the
    kernels over live blocks, nothing patched. The output and the tokens'
    gradient of the walk against the scatter-add's (in float32: two
    programs in bf16 differ by where the compiler rounds, not by the way
    back; ``tests/test_rows_to_tokens.py`` returns bf16 rows)."""
    from horovod_tpu.parallel import ep
    rng = np.random.RandomState(11)
    k = 6
    if cell == "smallthinker":
        tokens, d, f, n_experts = 512, 256, 64, 64
        expert, shapes = reglu_expert, [(d, f), (d, f), (f, d)]
        route = functools.partial(route_topk_softmax, k=6)
    elif cell == "lfm2":
        tokens, d, f, n_experts, k = 512, 1024, 1024, 32, 4
        expert, shapes = swiglu_expert, [(d, f), (d, f), (f, d)]
        route = functools.partial(route_sigmoid_topk, k=4,
                                  bias=jnp.zeros(n_experts))
    else:
        tokens, d, f, n_experts = 1024, 384, 48, 128
        expert, shapes = relu2_expert, [(d, f), (f, d)]
        route = functools.partial(route_sigmoid_topk, k=6, scale=2.5,
                                  bias=jnp.zeros(n_experts))
    x = jnp.asarray(rng.randn(tokens, d), jnp.float32)
    router = jnp.asarray(rng.randn(d, n_experts) * 0.1, jnp.float32)
    held = tuple(jnp.asarray(rng.randn(8, *shape) * 0.1, jnp.float32)
                 for shape in shapes)
    weights, experts, _, _ = route(x, router)
    order, inverse, weights, sizes, tile = _walk_operands(
        ep, x, experts, weights, 0, 8, n_experts)
    assert tile == 8 * 128 < k * tokens and int(sizes.max()) <= 128
    assert ep.share_product(ep._widths_of(held)) == \
        ("blocks" if cell == "lfm2" else "slots")

    _walk_against_scatter_add(ep, x, order, inverse, weights, sizes, held,
                              expert, tile, True)


def _primitives(jaxpr, seen=None):
    """{primitive name: [eqn, ...]} of a jaxpr and all it encloses, a Pallas
    kernel's body left out (its loops are the kernel's, not the walk's)."""
    seen = {} if seen is None else seen
    for eqn in jaxpr.eqns:
        seen.setdefault(eqn.primitive.name, []).append(eqn)
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, seen)
    return seen


def test_a_walk_has_each_grouped_matmul_once_a_direction(
        small_tiles_each_way):
    """Three tiles and still two grouped matmuls forward and six in the
    backward walk (the two again, and their four transposes): the walk is a
    loop, not an unrolling, and has no fallback of its own. Its product is
    ``ep.share_product``'s and no other: the grouped-matmul kernels over
    the tile's live row blocks (``_gmm_call`` for the two and towards the
    rows, ``_gmm_dw_call`` towards the matrices, and no ``dot_general`` with
    an expert a batch entry), or one batched ``dot_general`` over the
    tile's slots (and no grouped-matmul kernel); no ``ragged_dot`` is left
    in it. **No row goes back to its token by a scatter**: the way back is
    ``ops/rows_to_tokens``'s kernel, once forward (the weighted rows) and
    once backward (the rows' gradient). Every kernel call is lowered for the
    TPU and in interpret mode elsewhere (a ``cond`` on the platform, traced
    both ways), and the one scatter left is the backward walk's, of a
    scalar a pair."""
    by_blocks = small_tiles_each_way.share_product((D, F)) == "blocks"
    batched = "([0], [0]))"  # dot_generals with an expert a batch entry
    w = _share_weights()
    x = jnp.zeros((T, D), jnp.float32)
    forward = jax.make_jaxpr(lambda x, w: _share(x, w, 4, 4))(x, w)
    both = jax.make_jaxpr(jax.grad(
        lambda x, w: _share(x, w, 4, 4)[0].sum(), argnums=(0, 1)))(x, w)
    for jaxpr, products, towards_matrices in ((forward, 2, 0), (both, 6, 2)):
        text = str(jaxpr)
        assert text.count(batched) == (
            0 if by_blocks else products + towards_matrices)
        assert text.count("name=_gmm_call") == \
            (2 * products if by_blocks else 0)
        assert text.count("name=_gmm_dw_call") == \
            (2 * towards_matrices if by_blocks else 0)
        assert "ragged_dot_general" not in text
    for jaxpr, loops, ways_back, kernels in ((forward, 1, 1, 2),
                                             (both, 2, 2, 8)):
        seen = _primitives(jaxpr.jaxpr)
        calls = ways_back + (kernels if by_blocks else 0)
        assert len(seen["while"]) == loops
        assert len(seen["cond"]) == calls  # the platform's, no other
        assert sorted(eqn.params["interpret"] for eqn in
                      seen["pallas_call"]) == [False] * calls + [True] * calls
        assert "scatter-add" not in seen and "scatter_add" not in seen
        scattered = [eqn.outvars[0].aval.shape
                     for eqn in seen.get("scatter", [])]
        assert scattered == ([(K_T,)] if jaxpr is both else [])


def test_share_tile_rule_and_live_tiles_by_hand():
    """A slot is 1.5 x the pairs a balanced router sends one expert, in
    whole row blocks of 128; a tile a slot a held expert, at most all
    pairs; ``share_tiles`` counts the tiles the fullest held expert's pairs
    reach into."""
    from horovod_tpu.parallel import ep
    k, tokens = 6, 8192
    k_t = k * tokens
    assert ep.SHARE_BLOCK_ROWS == 128
    assert ep.share_slot_rows(k_t, 128) == 640      # 1.5 x 384 in 128s
    assert ep.share_tile_rows(k_t, 8, 128) == 5120
    assert ep.share_tile_rows(k_t, 16, 128) == 10240
    assert ep.share_tile_rows(k_t, 128, 128) == k_t
    assert ep.share_tile_rows(k_t, 96, 128) == k_t  # 1.5 x 3/4: all
    assert ep.share_tile_rows(384, 4, 16) == 384    # four blocks hold all
    assert ep.share_tile_rows(4096, 1, 128) == 128  # never under a block
    load = np.zeros(128)
    load[8:] = (k_t - 3000) / 120
    # one held expert's pairs: 13 tiles take all 8192 tokens
    for held_rows, live in [(0, 0), (1, 1), (640, 1), (641, 2), (3000, 5),
                            (tokens, 13)]:
        load[:8] = 0
        load[3] = held_rows
        assert ep.share_tiles(load, (0, 8), k, tokens) == (live, 13), \
            held_rows
    # the fullest expert alone counts: 5118 pairs in eight slots of 640
    load[:8] = [640, 639, 640, 640, 640, 640, 639, 640]
    assert ep.share_tiles(load, (0, 8), k, tokens) == (1, 13)
    load[5] = 641
    assert ep.share_tiles(load, (0, 8), k, tokens) == (2, 13)
    # a share that does not start at 0, a load as a device array; 384
    # pairs are one tile, the program below the walk
    load = jnp.zeros(16).at[4:8].set(jnp.asarray([100., 0., 45., 0.]))
    assert ep.share_tiles(load, (4, 4), K_SHARE, T) == (1, 1)
    assert ep.share_tiles(load, (8, 4), K_SHARE, T) == (0, 1)


@pytest.mark.parametrize("sizes,fetched", [
    ([39, 12, 39, 0], 8 * (7 + 4 + 7)), ([0, 0, 0, 0], 0),
    ([96, 81, 5, 80], 8 * (7 + 7 + 4 + 7 + 7 + 1 + 3 + 7 + 7)),
    ([1, 0, 0, 2], 8 * (1 + 2))],
    ids=["a-tile", "no-pair", "three-tiles", "three-pairs"])
def test_the_way_back_counts_the_rows_it_fetches(small_tiles_each_way,
                                                 sizes, fetched):
    """``hvd_moe_share_rows_total{kind="computed"}``: the held pairs in
    whole row blocks of 8 under the kernels, every slot of the live tiles
    under the batched product (``widths`` says which, as in the layer).
    ``{kind="fetched"}``, from the load alone: a
    slot's ``n`` rows in a live tile are ``ceil(n / 8)`` chunks of 8 rows,
    and a chunk is fetched again for each of the 3 token blocks of 32 it
    holds rows of, ``min(n, 3) - 1`` more at most. The jobs
    ``ops/rows_to_tokens`` makes of the same routing (expert ``e``'s pairs
    are the first ``sizes[e]`` tokens' here) fetch no more than that."""
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.ops import rows_to_tokens as rt

    def rows(kind):
        return get_registry().counter("hvd_moe_share_rows_total",
                                      kind=kind).value
    load = np.zeros(E)
    load[FIRST:FIRST + COUNT] = sizes
    before = {kind: rows(kind) for kind in ("held", "computed", "fetched")}
    small_tiles = small_tiles_each_way
    live, _ = small_tiles.share_tiles(load, (FIRST, COUNT), K_SHARE, T,
                                      record=True, widths=(D, F))
    assert rows("held") == before["held"] + sum(sizes)
    assert rows("computed") == before["computed"] + (
        sum(8 * -(-n // 8) for n in sizes)
        if small_tiles.share_product((D, F)) == "blocks" else live * TILE)
    assert rows("fetched") == before["fetched"] + fetched
    assert rt.chunk_rows_of(SLOT) == 8 and rt.block_tokens_of(T) == 32
    jobs = 0
    for i in range(live):
        at = np.full((COUNT, SLOT), T, np.int32)
        for e, n in enumerate(sizes):
            mine = np.arange(i * SLOT, min(n, (i + 1) * SLOT))
            at[e, :len(mine)] = mine
        _, _, r0, r1, _ = rt._jobs_of(jnp.asarray(at.reshape(-1)), T, COUNT)
        jobs += int(np.sum(np.asarray(r1) > np.asarray(r0)))
    assert 8 * jobs <= fetched and (jobs > 0) == (sum(sizes) > 0)


def test_the_walk_counts_its_tiles_in_the_registry(small_tiles):
    """At trace time the tiles a layer call was built with and the tile's
    rows; the live ones only when a caller reads a load back and asks."""
    from horovod_tpu.metrics.registry import get_registry

    def counter(kind):
        return get_registry().counter("hvd_moe_share_tiles_total", kind=kind)
    built, live = counter("built").value, counter("live").value
    w = _share_weights()
    x = jnp.asarray(np.random.RandomState(3).randn(T, D), jnp.float32)
    _, stats = jax.jit(lambda x, w: _share(x, w, 4, 4))(x, w)
    assert counter("built").value == built + 3
    assert get_registry().gauge("hvd_moe_share_tile_rows").value == TILE
    assert counter("live").value == live  # nothing is read inside a step
    def rows(kind):
        return get_registry().counter("hvd_moe_share_rows_total", kind=kind)
    held_rows, computed = rows("held").value, rows("computed").value
    tiles = small_tiles.share_tiles(stats.expert_tokens, (4, 4), K_SHARE,
                                    T, record=True)
    assert tiles[1] == 3 and counter("live").value == live + tiles[0]
    # the held experts' pairs, and the row blocks of 8 that hold them:
    # their ratio is what a slot's last block costs the grouped matmuls
    sizes = np.asarray(stats.expert_tokens)[4:8]
    assert rows("held").value == held_rows + sizes.sum() > held_rows
    assert tiles[0] == -(-sizes.max() // SLOT)
    assert rows("computed").value == computed + sum(
        8 * -(-n // 8) for n in sizes)
    # a full load builds no walk: it counts its row blocks instead
    def grouped(name, kind):
        return get_registry().counter(f"hvd_moe_grouped_{name}_total",
                                      kind=kind)
    before = {key: grouped(*key).value for key in (
        ("blocks", "built"), ("blocks", "live"), ("rows", "held"),
        ("rows", "computed"))}
    _, stats = jax.jit(lambda x, *w: moe_topk(x, *w, 2))(
        x, *_gated_weights())
    assert counter("built").value == built + 3
    blocks_built = 2 * T // 8 + E  # the pairs in blocks of 8, one an expert
    assert grouped("blocks", "built").value == \
        before["blocks", "built"] + blocks_built
    assert grouped("blocks", "live").value == before["blocks", "live"]
    load = np.asarray(stats.expert_tokens)
    live = sum(-(-n // 8) for n in load)
    assert small_tiles.grouped_blocks(load, 2, T, record=True) == \
        (live, blocks_built)
    assert grouped("blocks", "live").value == before["blocks", "live"] + live
    assert grouped("rows", "held").value == before["rows", "held"] + 2 * T
    # computed / held: what starting every expert on a block costs
    assert grouped("rows", "computed").value == \
        before["rows", "computed"] + 8 * live
    assert 2 * T <= 8 * live < 2 * T + 8 * E


# -- a SmallThinker layer: top k of the logits then their softmax, ReGLU
# experts, and a routing made from other rows than the experts' ----------------

K_ST = 3


def test_route_topk_softmax_weights_sum_to_one_over_the_chosen_logits():
    """The choice is the k largest logits, the weights their softmax (they
    sum to one: a renormalisation after it is the identity), in float32;
    the scores handed to ``MoeStats`` are the softmax over all E. The
    gradient reaches the router through the chosen logits alone."""
    rng = np.random.RandomState(60)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    w_router = jnp.asarray(rng.randn(D, E), jnp.float32)
    weights, experts, scores, logits = route_topk_softmax(x, w_router, K_ST)
    want_logits = np.asarray(x, np.float64) @ np.asarray(w_router, np.float64)
    np.testing.assert_allclose(np.asarray(logits), want_logits, rtol=1e-5,
                               atol=1e-5)
    want_experts = np.argsort(-want_logits, axis=-1)[:, :K_ST]
    np.testing.assert_array_equal(np.asarray(experts), want_experts)
    chosen = np.take_along_axis(want_logits, want_experts, axis=-1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, rtol=1e-6)
    assert weights.dtype == scores.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(scores),
                               np.asarray(jax.nn.softmax(logits, -1)))
    # not route_topk's weights: those are the softmax over all E
    assert not np.allclose(np.asarray(weights),
                           np.asarray(route_topk(x, w_router, K_ST)[0]))

    def first_weight(w_router):
        return route_topk_softmax(x, w_router, K_ST)[0][:, 0].sum()
    grad = np.asarray(jax.grad(first_weight)(w_router))
    picked = np.zeros((T, E), bool)
    np.put_along_axis(picked, want_experts, True, axis=-1)
    never = ~picked.any(axis=0)  # experts no token chose: no gradient
    assert never.any() or T >= E  # (with T >> E every expert is chosen)
    assert not grad[:, never].any() and grad[:, ~never].any()


def test_reglu_expert_is_relu_gate_times_up():
    rng = np.random.RandomState(61)
    rows, w_gate, w_up, w_down = (
        jnp.asarray(a, jnp.float32) for a in (
            rng.randn(T, D), rng.randn(D, F), rng.randn(D, F),
            rng.randn(F, D)))
    got = reglu_expert(jnp.dot, rows, w_gate, w_up, w_down)
    want = (np.maximum(rows @ w_gate, 0.0) * (rows @ w_up)) @ w_down
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert not np.allclose(np.asarray(got), np.asarray(
        swiglu_expert(jnp.dot, rows, w_gate, w_up, w_down)), atol=1e-3)


def _smallthinker_weights(seed=0):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(v, jnp.float32) for k, v in dict(
        router=rng.randn(D, E) * 0.5, gate=rng.randn(E, D, F) * 0.2,
        up=rng.randn(E, D, F) * 0.2, down=rng.randn(E, F, D) * 0.2).items()}


def _uncut_smallthinker_layer(r, x, w):
    """Dense: the router reads ``r``, every expert computes every row of
    ``x``, masked by the choice and weighed by the softmax of the chosen
    logits."""
    logits = r @ w["router"]
    chosen = jax.lax.top_k(logits, K_ST)[1]
    picked = (chosen[:, :, None] == jnp.arange(E)).any(axis=1)
    gate = jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1)
    hidden = jnp.maximum(jnp.einsum("td,edf->tef", x, w["gate"]), 0.0) * \
        jnp.einsum("td,edf->tef", x, w["up"])
    return jnp.einsum("te,tef,efd->td", gate, hidden, w["down"])


def _smallthinker_share(r, x, w, first, count):
    routing = moe_routing(functools.partial(
        route_topk_softmax, w_router=w["router"], k=K_ST), r)
    return moe_dropless(
        x, routing, reglu_expert,
        tuple(w[name][first:first + count]
              for name in ("gate", "up", "down")), held=(first, count))


@pytest.mark.parametrize("shares", [1, 2, 8])
def test_the_shares_of_a_smallthinker_layer_add_up_to_the_uncut_layer(
        shares):
    """The router reads other rows (``r``: the layer's input) than the
    experts (``x``: the stream after attention). 16 experts in 1, 2 and 8
    shares: the shares' parts add up to the dense layer, outputs and the
    gradients of both kinds of rows, the router and the experts; a routing
    from the experts' own rows is another layer."""
    w = _smallthinker_weights(shares)
    rng = np.random.RandomState(70 + shares)
    r, x = (jnp.asarray(rng.randn(T, D), jnp.float32) for _ in range(2))
    count = E // shares

    def cut_layer(r, x, w):
        parts = [_smallthinker_share(r, x, w, first, count)
                 for first in range(0, E, count)]
        return sum(out for out, _ in parts), parts[0][1]
    got, stats = jax.jit(cut_layer)(r, x, w)
    want = _uncut_smallthinker_layer(r, x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert int(stats.expert_tokens.sum()) == K_ST * T
    own_rows = _uncut_smallthinker_layer(x, x, w)
    assert float(jnp.abs(want - own_rows).max()) > 0.1

    def loss(layer, r, x, w):
        return jnp.sum(jnp.tanh(layer(r, x, w)) ** 2)
    got = jax.jit(jax.grad(lambda *a: loss(
        lambda *b: cut_layer(*b)[0], *a), argnums=(0, 1, 2)))(r, x, w)
    want = jax.jit(jax.grad(lambda *a: loss(_uncut_smallthinker_layer, *a),
                            argnums=(0, 1, 2)))(r, x, w)
    for g, v in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(v).sum()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(v), rtol=2e-3,
                                   atol=2e-5)


def test_a_routing_of_other_tokens_is_refused():
    w = _smallthinker_weights()
    routing = moe_routing(functools.partial(
        route_topk_softmax, w_router=w["router"], k=K_ST),
        jnp.zeros((T // 2, D), jnp.float32))
    with pytest.raises(ValueError, match="a routing of 48 tokens for 96"):
        moe_dropless(jnp.zeros((T, D), jnp.float32), routing, reglu_expert,
                     (w["gate"], w["up"], w["down"]))
