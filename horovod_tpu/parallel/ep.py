"""Mixture-of-Experts layers: a dropless top-k expert layer for one chip's
tokens, and the older top-1 exchange over the ``expert`` mesh axis.

The reference has no MoE — SURVEY §2.8 records EP as ABSENT, with its
alltoall primitive (operations.cc:1101-1162) named as the building block an
expert-parallel layer needs.

**:func:`moe_dropless` — what trains** (``models/olmoe.py`` through
:func:`moe_topk`, ``models/nemotron_h.py`` directly; data-parallel over
chips). Top-k routing that drops nothing, with static shapes and no
``[T, E, C]`` one-hot. The layer is given its two model-specific halves as
functions, not as flags:

- **a router**, ``route(x) -> (weights [T, k], experts [T, k], scores
  [T, E], logits [T, E])``, in float32 over *all* ``E`` experts:
  :func:`route_topk` is OLMoE's (softmax, the k largest probabilities, not
  renormalised); :func:`route_sigmoid_topk` is the DeepSeek-V3 / Nemotron-H
  one (sigmoid scores, the choice made on ``scores + bias`` where ``bias`` is
  a correction no gradient reaches, the weights the chosen *scores* without
  the bias, renormalised and scaled);
- **an expert**, ``expert(dot, rows, *weights) -> rows``, written over the
  grouped matmul ``dot(rows, w)`` the layer hands it: :func:`swiglu_expert`
  (``w_down(silu(w_gate x) * w_up x)``, three matrices),
  :func:`relu2_expert` (``w_down relu(w_up x)^2``, two).

The layer's own four parts are ``jax.named_scope``s
(``profiler/annotate.MOE_SCOPES``), so a device trace says what each
operation was:

- ``moe_router``: the router, and the per-expert pair counts;
- ``moe_dispatch``: the ``k T`` (token, slot) pairs are sorted by expert
  (a stable argsort), and the tokens' rows are gathered into that order;
- ``moe_experts``: one grouped matmul per projection over the sorted rows
  (``jax.lax.ragged_dot``, which the TPU compiler lowers to its own Mosaic
  kernel);
- ``moe_combine``: the rows are gathered back into token order and summed
  with their router weights.

Both permutations are bijections of the ``k T`` rows and their backward
passes are the inverse gathers: nothing on this path is a scatter-add. The
layer returns :class:`MoeStats`: the per-expert pair counts over all ``E``
(they sum to ``k T``: no capacity, no drop, under any imbalance) and what
auxiliary losses need (:func:`load_balancing_loss`, the router z-loss).

**A share** (``held = (first, count)``): the layer holds ``count`` of the
router's ``E`` experts, ``first .. first + count - 1``, as one chip of an
expert-parallel deployment does, and the expert weights it is given have
``count`` leading rows. It still routes over all ``E`` and computes
*exactly* the held experts' part of the sum: the held experts' pairs sort
first, in expert order, the group sizes are the held experts' counts, and
the rows past their sum belong to no group. ``ragged_dot`` promises nothing
about such rows (the TPU's kernel spends no time on them and leaves them
unwritten: whatever the buffer held, NaNs in a training step), so the
layer's ``dot`` zeroes them by row index on the way in and on the way out:
zero output, zero gradient. What the absent experts
would have added is left out; the shares of a layer add up to the whole
layer (``tests/test_expert_parallel.py``). On one chip a share runs without
its exchange, and nothing here stands in for the absent chips.

**:func:`moe_layer` — the exchange over the ``expert`` axis** (unit tests and
the CPU dry run only; on no measured path). GShard-style top-1 routing into
fixed-capacity ``[experts, capacity, d]`` buffers built from dense
``[T, E, C]`` one-hots (:func:`top1_dispatch`; tokens past capacity are
dropped), one ``lax.all_to_all`` each way, two-matrix experts sharded over
the axis. Composing the dropless layer's routing and shares with that
exchange is ROADMAP Reach 1's four-chip cell.

Shapes of :func:`moe_topk`: tokens ``[T, d]``; ``w_router`` ``[d, E]``;
``w_gate``, ``w_up`` ``[E, d, f]``; ``w_down`` ``[E, f, d]``. Of
:func:`moe_layer` (inside shard_map): tokens ``[T_local, d]``; w_gate
``[d, E_total]`` (replicated); w_in ``[E_local, d, hidden]``, w_out
``[E_local, hidden, d]`` (sharded over ``expert``).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel import collectives
from horovod_tpu.profiler.annotate import moe_scope


class MoeStats(NamedTuple):
    """What one :func:`moe_topk` call says about its routing."""
    expert_tokens: jax.Array     # int32 [E]: (token, slot) pairs per expert
    router_prob_mean: jax.Array  # float32 [E]: mean softmax probability
    router_z_loss: jax.Array     # float32 []: mean of logsumexp(logits)^2


def route_topk(x: jax.Array, w_router: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Softmax router in float32. x: [T, d]; w_router: [d, E]. Returns
    (weights [T, k] float32, experts [T, k] int32, probabilities [T, E],
    logits [T, E]); the k weights are the softmax's own values and are not
    renormalised. The product runs at the highest precision: on a TPU a
    float32 dot at the default one rounds both sides to bf16, and a logit
    off by 2**-9 changes which expert is the k-th. The weights are read out
    of the probabilities through the choice's one-hot mask, so their
    backward pass is a product and not ``top_k``'s scatter."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    experts = lax.top_k(lax.stop_gradient(probs), k)[1].astype(jnp.int32)
    weights = jnp.sum(jnp.where(_chosen_mask(experts, probs.shape[-1]),
                                probs[:, None, :], 0.0), axis=-1)
    return weights, experts, probs, logits


def route_sigmoid_topk(x: jax.Array, w_router: jax.Array, bias: jax.Array,
                       k: int, scale: float = 1.0
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sigmoid router with a correction bias (DeepSeek-V3, arXiv:2412.19437;
    ``NemotronHTopkRouter``), in float32 at the highest matmul precision as
    :func:`route_topk`. x: [T, d]; w_router: [d, E]; bias: [E]. The choice
    is the top k of ``sigmoid(x w) + bias``; the bias moves the choice alone:
    the weights are the chosen experts' scores *without* it, divided by
    their sum + 1e-20 and multiplied by ``scale``, and no gradient reaches
    the bias. Returns (weights [T, k], experts [T, k] int32, scores [T, E],
    logits [T, E])."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    experts = lax.top_k(lax.stop_gradient(scores + bias), k)[1] \
        .astype(jnp.int32)
    chosen = jnp.sum(jnp.where(_chosen_mask(experts, scores.shape[-1]),
                               scores[:, None, :], 0.0), axis=-1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return weights, experts, scores, logits


def _chosen_mask(experts: jax.Array, n_experts: int) -> jax.Array:
    """[T, k, E] bool: slot j of token t chose expert e."""
    return experts[:, :, None] == jnp.arange(n_experts, dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_sorted(x, order, inverse, k):
    """Row i of the result is token ``order[i] // k``: the (token, slot)
    pairs in expert order. ``inverse`` undoes ``order``."""
    return jnp.take(x, lax.div(order, k), axis=0)


def _gather_sorted_fwd(x, order, inverse, k):
    return _gather_sorted(x, order, inverse, k), (order, inverse)


def _gather_sorted_bwd(k, saved, g):
    # back in (token, slot) order a token's k rows lie side by side
    order, inverse = saved
    g = jnp.take(g, inverse, axis=0)
    return g.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_gather_sorted.defvjp(_gather_sorted_fwd, _gather_sorted_bwd)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a bijection ``perm``, whose backward pass is the
    gather by ``inverse`` (autodiff would write a scatter-add)."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inverse):
    return _permute(x, perm, inverse), (perm, inverse)


def _permute_bwd(saved, g):
    perm, inverse = saved
    return jnp.take(g, inverse, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def swiglu_expert(dot, rows, w_gate, w_up, w_down):
    """``w_down(silu(w_gate x) * w_up x)``: OLMoE's gated expert."""
    return dot(jax.nn.silu(dot(rows, w_gate)) * dot(rows, w_up), w_down)


def relu2_expert(dot, rows, w_up, w_down):
    """``w_down relu(w_up x)^2``: Nemotron-H's expert, two matrices and no
    gate."""
    return dot(jnp.square(jax.nn.relu(dot(rows, w_up))), w_down)


def _held_first(experts: jax.Array, first: int, count: int) -> jax.Array:
    """Sort keys of a share: a held expert's index among the held, and
    ``count`` for every pair of an expert that lives elsewhere."""
    local = experts - first
    return jnp.where((local >= 0) & (local < count), local, count)


def moe_dropless(x: jax.Array, route: Callable, expert: Callable,
                 expert_weights: Sequence[jax.Array],
                 held: Optional[Tuple[int, int]] = None
                 ) -> Tuple[jax.Array, MoeStats]:
    """One dropless top-k expert layer over the tokens it is given.

    x: [T, d] in the compute dtype. ``route(x)`` is the router over all E
    experts and ``expert(dot, rows, *expert_weights)`` one expert's function
    over the grouped matmul ``dot`` (the module text has both contracts);
    ``expert_weights`` lead with the experts held here: all E, or with
    ``held = (first, count)`` the ``count`` from ``first`` on. Returns
    ([T, d] in x's dtype: the held experts' part of the weighted sum,
    :class:`MoeStats` over all E). Every (token, slot) pair of a held expert
    is computed, whatever the imbalance; ``stats.expert_tokens`` sums to
    ``k T``.
    """
    t, d = x.shape
    with moe_scope("moe_router"):
        weights, experts, scores, logits = route(x)
        k, n_experts = experts.shape[-1], scores.shape[-1]
        stats = MoeStats(
            expert_tokens=jnp.sum(_chosen_mask(experts, n_experts),
                                  axis=(0, 1), dtype=jnp.int32),
            router_prob_mean=scores.mean(axis=0),
            router_z_loss=jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2))
    first, count = held if held is not None else (0, n_experts)
    if not 0 <= first <= first + count <= n_experts or any(
            w.shape[0] != count for w in expert_weights):
        raise ValueError(
            f"the router routes to {n_experts} experts and the layer holds "
            f"{count} from {first} on, but the expert weights lead with "
            f"{[w.shape[0] for w in expert_weights]}")
    with moe_scope("moe_dispatch"):
        # pair t * k + slot; stable, so an expert's rows keep token order
        keys = experts.reshape(-1)
        if held is not None:
            keys = _held_first(keys, first, count)
        order = jnp.argsort(keys, stable=True)
        inverse = jnp.argsort(order)
        rows = _gather_sorted(x, order, inverse, k)
    with moe_scope("moe_experts"):
        sizes = stats.expert_tokens[first:first + count]
        if held is None:
            def dot(a, w):
                return lax.ragged_dot(a, w, sizes)
        else:
            # the rows past the held experts' pairs are in no group
            in_a_group = (jnp.arange(k * t) < sizes.sum())[:, None]

            def dot(a, w):
                a = jnp.where(in_a_group, a, jnp.zeros((), a.dtype))
                out = lax.ragged_dot(a, w, sizes)
                return jnp.where(in_a_group, out, jnp.zeros((), out.dtype))
        rows = expert(dot, rows, *expert_weights)
    with moe_scope("moe_combine"):
        rows = _permute(rows, inverse, order).reshape(t, k, d)
        out = jnp.einsum("tk,tkd->td", weights, rows,
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), stats


def moe_topk(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
             w_up: jax.Array, w_down: jax.Array, k: int
             ) -> Tuple[jax.Array, MoeStats]:
    """OLMoE's layer: :func:`moe_dropless` under :func:`route_topk` with
    :func:`swiglu_expert`, every expert held.

    x: [T, d] in the compute dtype; w_router: [d, E] (used in float32);
    w_gate, w_up: [E, d, f]; w_down: [E, f, d], in the compute dtype.
    """
    return moe_dropless(
        x, functools.partial(route_topk, w_router=w_router, k=k),
        swiglu_expert, (w_gate, w_up, w_down))


def load_balancing_loss(expert_tokens: jax.Array,
                        router_prob_mean: jax.Array, k: int) -> jax.Array:
    """``load_balancing_loss_func`` of transformers' ``modeling_olmoe.py``:
    the layers' tokens taken together, E x sum over experts of (pairs sent
    to the expert / tokens) x (mean router probability). [layers, E] each
    (or [E] for one layer); k, a uniform router's value, is its least."""
    counts = jnp.atleast_2d(expert_tokens).astype(jnp.float32)
    probs = jnp.atleast_2d(router_prob_mean)
    n_experts = counts.shape[-1]
    tokens = counts.sum() / k  # over all layers
    return n_experts * jnp.sum(counts.sum(axis=0) / tokens
                               * probs.mean(axis=0))


def top1_dispatch(gates: jax.Array, capacity: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Build dispatch/combine tensors for top-1 routing.

    gates: [T, E] softmax router probabilities. Returns
    (dispatch [T, E, C] one-hot, combine [T, E, C] = dispatch * gate_prob).
    Token t goes to expert argmax(gates[t]) at slot ``position-in-expert``;
    tokens whose slot >= capacity are dropped (all-zero rows).
    """
    t, e = gates.shape
    expert_idx = jnp.argmax(gates, axis=-1)  # [T]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [T, E]
    # 0-based position of each token within its expert's arrival order
    # (cumsum counts the token itself, so subtract the onehot back out)
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [T, E]
    slot = jnp.sum(pos, axis=-1)  # [T]
    keep = slot < capacity
    dispatch = (jax.nn.one_hot(expert_idx, e)[:, :, None] *
                jax.nn.one_hot(jnp.where(keep, slot, capacity), capacity + 1,
                               dtype=gates.dtype)[:, None, :capacity])
    prob = jnp.max(gates, axis=-1)  # [T]
    combine = dispatch * prob[:, None, None]
    return dispatch, combine


def moe_layer(x: jax.Array, w_gate: jax.Array, w_in: jax.Array,
              w_out: jax.Array, axis: str = "expert",
              capacity_factor: float = 1.25,
              activation=jax.nn.gelu) -> jax.Array:
    """One expert-parallel MoE feed-forward layer (call under shard_map).

    x: [T_local, d]; w_gate: [d, E_total] replicated; w_in/w_out:
    [E_local, d, h] / [E_local, h, d] sharded over ``axis``. Returns
    [T_local, d] — each token's output is its top-1 expert's MLP output
    scaled by the gate probability (dropped tokens produce zeros, as in
    GShard/Switch).
    """
    n_ep = lax.axis_size(axis)
    t_loc, d = x.shape
    e_loc = w_in.shape[0]
    e_total = n_ep * e_loc
    if w_gate.shape[-1] != e_total:
        raise ValueError(
            f"w_gate routes to {w_gate.shape[-1]} experts but the mesh "
            f"provides {n_ep} ranks x {e_loc} local = {e_total}")
    # per (source rank, expert) capacity
    capacity = max(1, int(capacity_factor * t_loc / e_total))

    xf = x.astype(jnp.float32)
    gates = jax.nn.softmax(xf @ w_gate.astype(jnp.float32), axis=-1)
    dispatch, combine = top1_dispatch(gates, capacity)  # [T, E, C]

    # gather tokens into expert buffers: [E_total, C, d]
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xf)
    # exchange over the expert axis: split the expert dim across ranks,
    # concat the arrivals — each rank ends with its local experts' tokens
    # from every source rank: [n_ep * E_local, C, d] -> regroup to
    # [E_local, n_ep * C, d]
    expert_in = collectives.alltoall(expert_in, axis)
    expert_in = expert_in.reshape(n_ep, e_loc, capacity, d) \
        .transpose(1, 0, 2, 3).reshape(e_loc, n_ep * capacity, d)

    # local expert MLPs (batched einsum over the expert dim — one big MXU
    # matmul per projection, no Python loop)
    h = jnp.einsum("esd,edh->esh", expert_in, w_in.astype(jnp.float32))
    h = activation(h)
    expert_out = jnp.einsum("esh,ehd->esd", h, w_out.astype(jnp.float32))

    # reverse exchange: back to [E_total, C, d] on the source ranks
    expert_out = expert_out.reshape(e_loc, n_ep, capacity, d) \
        .transpose(1, 0, 2, 3).reshape(e_total, capacity, d)
    expert_out = collectives.alltoall(expert_out, axis)

    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.astype(x.dtype)
