"""Mixture-of-Experts layers: a dropless top-k expert layer for one chip's
tokens, and the older top-1 exchange over the ``expert`` mesh axis.

The reference has no MoE — SURVEY §2.8 records EP as ABSENT, with its
alltoall primitive (operations.cc:1101-1162) named as the building block an
expert-parallel layer needs.

**:func:`moe_topk` — what trains** (``models/olmoe.py``; every expert on the
chip, data-parallel over chips). Top-k routing that drops nothing, with
static shapes and no ``[T, E, C]`` one-hot:

- ``moe_router``: ``logits = x @ w_router`` and the softmax in float32 over
  all experts, ``top_k`` weights and indices, not renormalised
  (:func:`route_topk`);
- ``moe_dispatch``: the ``k T`` (token, slot) pairs are sorted by expert
  (a stable argsort of the expert indices), the per-expert group sizes are
  counted, and the tokens' rows are gathered into that order;
- ``moe_experts``: one grouped matmul per projection over the sorted rows
  (``jax.lax.ragged_dot``, which the TPU compiler lowers to its own Mosaic
  kernel), expert = ``w_down(silu(w_gate x) * w_up x)``;
- ``moe_combine``: the rows are gathered back into token order and summed
  with their router weights.

Those four names are ``jax.named_scope``s (``profiler/annotate.MOE_SCOPES``),
so a device trace says what each operation of the layer was. Both
permutations are bijections of the ``k T`` rows and their backward passes
are the inverse gathers: nothing on this path is a scatter-add. The layer
returns :class:`MoeStats`: the per-expert pair counts (they sum to ``k T``:
no capacity, no drop, under any imbalance) and what the auxiliary losses
need (:func:`load_balancing_loss`, the router z-loss).

**:func:`moe_layer` — the exchange over the ``expert`` axis** (unit tests and
the CPU dry run only; on no measured path). GShard-style top-1 routing into
fixed-capacity ``[experts, capacity, d]`` buffers built from dense
``[T, E, C]`` one-hots (:func:`top1_dispatch`; tokens past capacity are
dropped), one ``lax.all_to_all`` each way, two-matrix experts sharded over
the axis. Composing :func:`moe_topk`'s routing with that exchange is
ROADMAP Reach 1's four-chip cell.

Shapes of :func:`moe_topk`: tokens ``[T, d]``; ``w_router`` ``[d, E]``;
``w_gate``, ``w_up`` ``[E, d, f]``; ``w_down`` ``[E, f, d]``. Of
:func:`moe_layer` (inside shard_map): tokens ``[T_local, d]``; w_gate
``[d, E_total]`` (replicated); w_in ``[E_local, d, hidden]``, w_out
``[E_local, hidden, d]`` (sharded over ``expert``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

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


def moe_topk(x: jax.Array, w_router: jax.Array, w_gate: jax.Array,
             w_up: jax.Array, w_down: jax.Array, k: int
             ) -> Tuple[jax.Array, MoeStats]:
    """One dropless top-k expert layer over the tokens it is given.

    x: [T, d] in the compute dtype; w_router: [d, E] (used in float32);
    w_gate, w_up: [E, d, f]; w_down: [E, f, d], in the compute dtype.
    Returns ([T, d] in x's dtype, :class:`MoeStats`). Every (token, slot)
    pair is computed, whatever the imbalance: ``stats.expert_tokens`` sums
    to ``k T``.
    """
    t, d = x.shape
    n_experts = w_router.shape[-1]
    if not (w_gate.shape[0] == w_up.shape[0] == w_down.shape[0]
            == n_experts):
        raise ValueError(
            f"w_router routes to {n_experts} experts but the expert "
            f"weights hold {w_gate.shape[0]}, {w_up.shape[0]} and "
            f"{w_down.shape[0]}")
    with moe_scope("moe_router"):
        weights, experts, probs, logits = route_topk(x, w_router, k)
        stats = MoeStats(
            expert_tokens=jnp.sum(_chosen_mask(experts, n_experts),
                                  axis=(0, 1), dtype=jnp.int32),
            router_prob_mean=probs.mean(axis=0),
            router_z_loss=jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2))
    with moe_scope("moe_dispatch"):
        # pair t * k + slot; stable, so an expert's rows keep token order
        order = jnp.argsort(experts.reshape(-1), stable=True)
        inverse = jnp.argsort(order)
        rows = _gather_sorted(x, order, inverse, k)
    with moe_scope("moe_experts"):
        sizes = stats.expert_tokens
        hidden = jax.nn.silu(lax.ragged_dot(rows, w_gate, sizes)) * \
            lax.ragged_dot(rows, w_up, sizes)
        rows = lax.ragged_dot(hidden, w_down, sizes)
    with moe_scope("moe_combine"):
        rows = _permute(rows, inverse, order).reshape(t, k, d)
        out = jnp.einsum("tk,tkd->td", weights, rows,
                         preferred_element_type=jnp.float32)
    return out.astype(x.dtype), stats


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
