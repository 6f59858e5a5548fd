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
  the bias, renormalised and scaled); :func:`route_topk_softmax` takes the k
  largest *logits* and then the softmax of those k (SmallThinker). The
  router's rows need not be the experts': :func:`moe_routing` makes the
  :class:`Routing` from any rows of the same tokens (a router that reads the
  layer's input before attention) and the layer takes it in the router's
  place;
- **an expert**, ``expert(dot, rows, *weights) -> rows``, written over the
  grouped matmul ``dot(rows, w)`` the layer hands it: :func:`swiglu_expert`
  (``w_down(silu(w_gate x) * w_up x)``, three matrices),
  :func:`reglu_expert` (the same with ``relu`` for ``silu``),
  :func:`relu2_expert` (``w_down relu(w_up x)^2``, two).

The layer's own four parts are ``jax.named_scope``s
(``profiler/annotate.MOE_SCOPES``), so a device trace says what each
operation was:

- ``moe_router``: the router, and the per-expert pair counts;
- ``moe_dispatch``: the ``k T`` (token, slot) pairs are sorted by expert
  (a stable argsort), and the tokens' rows are gathered into that order;
- ``moe_experts``: one grouped matmul per projection over the sorted rows
  (:func:`~horovod_tpu.ops.grouped_matmul.grouped_matmul`, the repo's own
  Pallas kernels over the row blocks of 128 that hold a pair, for a full
  load and for a share's walk whose expert matrices are whole 128s and at
  least 1024 wide; a batched ``dot_general`` over slots, an expert a batch
  entry, for every other walk: :func:`share_product`);
- ``moe_combine``: the rows are gathered back into token order and summed
  with their router weights, in float32.

**A full load** (``held=None``: every expert here). The sorted pairs are
laid out so that **each expert's pairs start on a multiple of
``SHARE_BLOCK_ROWS`` = 128 rows** (:func:`_blocks_of`): a pair's row is its
sorted position plus the padding of the experts before its own, arithmetic
on the per-expert counts (a cumulative sum of the counts rounded up to
128s) and no second sort. The rows are a static ``k T`` in whole blocks
plus a block an expert (:func:`grouped_blocks_built`: 65 536 + 64 x 128 =
73 728 rows, 576 blocks, for top-8 of 64 experts over 8192 tokens), enough
for any routing, one expert sent every token included; the blocks that hold
a pair are a prefix of them (about 544 under a balanced router: an expert's
last block is half slack on average), and their count and the
block-to-expert table are data, handed to the kernels as scalar-prefetch
operands. Every row block is thus one expert's and the product over the
blocks is a tiled matmul whose matrix is chosen a block: its time follows
the rows (the TPU compiler's kernel for ``jax.lax``'s ragged dot is paced by
the (group, 512-row tile) pairs it visits and ran these products at 42-45%
of the chip's peak;
``PERF.md`` §6, PRs 31, 34 and 36). Rows move by gathers alone: dispatch
gathers ``x[token of a row]`` (a row of padding gathers some token in
bounds and is computed like any other in a live block; a block past the
live ones is neither fetched, computed nor written), combine gathers the
pairs' rows from where they lie, and both backward passes are the inverse
gathers, with a select that gives the rows that hold no pair a zero
gradient, so nothing they hold reaches a weight's gradient (zero times a
finite row). Nothing on that path is a scatter-add. The layer returns
:class:`MoeStats`: the per-expert pair counts over all ``E`` (they sum to
``k T``: no capacity, no drop, under any imbalance) and what auxiliary losses
need (:func:`load_balancing_loss`, the router z-loss). At trace time
``hvd_moe_grouped_blocks_total{kind="built"}`` counts the blocks a layer
call is built with; how many were live and what the padding cost
(``hvd_moe_grouped_rows_total{kind="held"|"computed"}``) is data, read back
outside the step: :func:`grouped_blocks` over the load.

**Slots are a share's layout; row blocks are what is computed, where the
experts are wide enough** (PR 47; before it: "why a full load takes the
kernel and a share keeps slots"). A share's tile gives every held expert a
slot of 1.5 x a balanced router's pairs, so that one tile holds any step a
bias rule or an auxiliary loss keeps near balance: a third to a half of a
tile's rows hold no pair (computed / held 1.5 in ``lfm2-t16384``, 1.49 in
``smallthinker-t16384``, 2.0 in ``sdar-t8192-bd4``, 1.65 in
``nemotron3n-t8192``). The slack stays where it costs little, in the layout
(a gather's indices, the way back's runs), and can leave the products: a
slot is whole row blocks of 128, block ``b`` of a tile is held expert ``b //
(S / 128)``'s, and the blocks of slot ``e`` that hold a pair of tile ``i``
are its first ``ceil(clip(count_e - i S, 0, S) / 128)``. That is a full
load's contract with one thing more, that the live blocks are not a prefix
of the tile, so the kernels take the list of live blocks by grid step (each
slot's prefix after the other, a cumulative sum over the slots) beside the
block-to-expert table, and both loads run **one kernel body** (a full
load's list names the blocks in order). :func:`share_product` decides which
walks take it, from the expert matrices' static widths and nothing else:
whole 128s and at least 1024 wide both ways (LFM2's 2048 x 1792; computed /
held 1.5 -> 1.04). What the chip said (``PERF.md`` §6, PR 47; PR 34 before
it). Alone, a tile's experts forward and backward are faster by blocks at
every width in whole 128s (12.2 -> 11.3 ms in ``lfm2-t16384``, 6.1 -> 5.3
in ``sdar-t8192-bd4``, 5.5 -> 5.4 in ``smallthinker-t16384``) and slower at
Nemotron-H's 2688 x 1856 (2.4 -> 2.9, as PR 34 found), and by less than
the rows say: XLA's batched product runs at about 90% of the chip's peak on
every row it is given (``moe_experts_mfu`` counts three passes where the
walk, which recomputes, runs four), the kernels at 80-86% at LFM2's widths.
In the step the rest of the walk decides: XLA fuses the element-wise passes
between the products (the gate, the router weights on the output's
gradient, a row's sum, the sum of a matrix's gradient over tiles) into the
batched products and cannot fuse them into a kernel call, 0.3-0.6 GB a
layer that cross HBM on their own. At 1792 wide the rows skipped pay for
that (``lfm2-t16384`` +1.35%); at 768 they do not (``smallthinker-t16384``
-0.6%, ``sdar-t8192-bd4`` +0.25% with 0.5% more memory and a longer
set-up), so those walks, like Nemotron-H's, keep XLA's batched product over
whole slots. A full load has no slack to give slots: 64 experts x 1.5
headroom is +50% rows through every gather and every product, where
starting each expert on a block of 128 costs 64 half blocks, +6%
(``PERF.md`` §6, PR 36). Walk or one tile is the static shape
``share_tile_rows(k T, count, E) < k T``; which product a walk takes is
counted at trace time, ``hvd_moe_share_product_total{path="blocks"
|"slots"}``.

**A share** (``held = (first, count)``): the layer holds ``count`` of the
router's ``E`` experts, ``first .. first + count - 1``, as one chip of an
expert-parallel deployment does, and the expert weights it is given have
``count`` leading rows. It still routes over all ``E`` and computes
*exactly* the held experts' part of the sum; what the absent experts would
have added is left out, and the shares of a layer add up to the whole layer
(``tests/test_expert_parallel.py``). On one chip a share runs without its
exchange, and nothing here stands in for the absent chips.

The held experts' pairs sort *first*, in expert order, so they are a prefix
of the sorted pairs whose length, the sum of the held experts' counts, the
layer has. A share touches only that prefix: it **walks the pairs in static
tiles** (:func:`_walk`) in which **every held expert has a slot of its own**:
tile ``i`` is ``count`` slots of ``S`` rows, and slot ``e`` holds pairs
``[i S, (i + 1) S)`` of held expert ``e`` (a row's sorted position is its
expert's first pair plus its place, arithmetic on the tile's indices from
the held counts alone, :func:`_share_tiles_of`). Every row block of a tile
is thus one expert's, at a place known when the program is built, and the
grouped matmul over a tile is :func:`share_product`'s: the kernels over the
tile's live row blocks, or XLA's own batched product ``[count, S, k] x
[count, k, n]``, an expert a batch entry; either's time follows its rows
(the compiler's ragged dot is paced by the (group, 512-row tile) pairs it
visits: 2.0 ms a call for 0.2 ms of arithmetic at Nemotron-H's
widths, where the batched product takes 0.3; ``PERF.md`` §6, PRs 31 and
34). ``S`` is :func:`share_slot_rows`: 1.5 x the ``k T / E`` pairs a
balanced router sends one expert, in whole row blocks of 128, and the tile
:func:`share_tile_rows` = ``count S``, computed from ``k``, ``T``, ``count``
and ``E`` alone (640 and 5120 of 49 152 rows for 8 of 128 experts under
top-6 of 8192 tokens). The walk works in the tiles the *fullest* held
expert's pairs reach into: on a balanced step one tile is live and the
rest cost a loop's exit. No capacity and no drop: an expert sent more than
a slot makes the next tile live, and if one expert is sent every token
every tile is. Everything done to rows happens inside the walk, a tile at
a time: the gather of the tile's tokens' rows (``moe_dispatch``), the
expert function over the batched product (``moe_experts``), and **the way
back** (``moe_combine``): the rows times their router weights, added into a
float32 ``[T, d]`` at their tokens by
:func:`~horovod_tpu.ops.rows_to_tokens.add_rows_at_tokens`. A tile's rows
are a sparse subset of the ``[T, k]`` slots, so there is no bijection to
invert, and XLA's ``scatter-add`` takes them a row at a time (8.2-8.6 ms
for a tile of 18 432 rows of 2560, 23 GB/s, sixteen times a step of
SmallThinker's cell; one scatter a slot told its indices are sorted and
unique took nine times that; ``PERF.md`` §6, PRs 38 and 39). But the sort
is stable, so **inside a slot the pairs' tokens ascend**, and the rows of
one slot that fall in one block of 256 tokens are a contiguous run: the
kernel builds a token block's result in VMEM from those runs, fetched in
chunks of 32 rows, and writes it once, the first live tile without reading
what was there (1.08 ms for the same tile, 0.66 into a fresh result; the
bytes ask for 0.4-0.6). **A row that holds no pair** gathers some token in
bounds, carries router weight 0 and goes back to no token. In a live block
(and everywhere under the batched product, which writes every row) it is
computed like any other: it adds nothing to the output, and its rows of
every gradient are zero because the gradient that reaches them is (zero
times a finite row). In a block no live step names, nothing is fetched,
computed or written, forward or backward: its rows of each product's result
hold whatever the buffer held, NaN for all anyone knows, and three guards
keep that from anything. (1) *The way back stops at the count*: a row that
is no pair carries token ``T``, so
:func:`~horovod_tpu.ops.rows_to_tokens.add_rows_at_tokens` puts it in no
run, forward (the weighted rows) and backward (the rows' gradient): no ``0 *
NaN``, the row is never read as a number. The router weights' gradient, a
row's sum scattered by sorted position, drops the same rows by their
position ``k T``. (2) *The same list skips them in the next product*: what
a dead block holds after ``silu(gate) * up`` is the third product's operand
in blocks that product does not visit either, and the matrices' gradients
(``_gmm_dw_kernel``) sum over live blocks only; an expert with no live
block gets zeros by a select on the list. (3) *A live block's tail* is
guard (1)'s weight 0 and today's padding: computed, finite, zero gradient.
``tests/test_expert_parallel.py`` fills every unwritten row with NaN, both
directions, and finds the output and every gradient finite and equal.

The walk is a ``jax.custom_vjp`` over two loops whose trip count is read
from the data, one forward and one backward, so each grouped matmul is in
the program once a direction whatever the number of tiles, and a tile that
is not live costs nothing at all. It keeps no activation: the backward loop
gathers a live tile's rows and takes them through the experts again (under
a recomputed block that is the block's one recomputation: the recomputed
forward walk's result is needed by nothing and the compiler removes it),
gathers the tile's rows of the output's gradient, adds the rows' gradients
into a float32 ``[T, d]`` at their tokens (``moe_dispatch``: the forward's
way back again, every weight one), and sums the expert weights' gradients
over the live tiles in their own dtype (one live tile: exact; float32 sums
were a gigabyte of the step's temporaries; inside a tile the kernel sums an
expert's live blocks in a float32 VMEM tile, as the batched product sums a
slot in float32). The one scatter left in the
walk is of a scalar a pair (the router weights' gradient by sorted
position). At trace time
``hvd_moe_share_tiles_total{kind="built"}`` counts the tiles a layer call
can come to and ``hvd_moe_share_tile_rows`` holds a tile's rows; which
tiles were live, how many rows the held experts were sent and how many the
products computed for them (the live row blocks, or whole tiles under the
batched product) and the way back fetched to place them
(``hvd_moe_share_rows_total{kind="held"|"computed"|"fetched"}``) is data,
read back outside the step: :func:`share_tiles` over the load a model's
state carries. A share whose rule gives a tile of all ``k T`` pairs (its slots
reach them all; tests only) is the program below the walk too: the held
experts' pairs in row blocks, nothing laid out for a pair of an expert held
elsewhere, whose row in (token, slot) order is zero by a select.

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

from horovod_tpu.ops.grouped_matmul import grouped_matmul
from horovod_tpu.ops.rows_to_tokens import (add_rows_at_tokens,
                                            block_tokens_of, chunk_rows_of)
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


def route_topk_softmax(x: jax.Array, w_router: jax.Array, k: int
                       ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top k of the logits, then the softmax of those k
    (``moe_primary_router_apply_softmax`` of SmallThinker: the weights sum to
    one, so a ``norm_topk_prob`` after it is the identity), in float32 at the
    highest matmul precision as :func:`route_topk`, the chosen logits read
    through the choice's one-hot mask as there. x: [T, d]; w_router: [d, E].
    Returns (weights [T, k], experts [T, k] int32, the softmax over all E
    [T, E], which is what :class:`MoeStats` averages and not what weighs the
    experts, logits [T, E])."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    experts = lax.top_k(lax.stop_gradient(logits), k)[1].astype(jnp.int32)
    chosen = jnp.sum(jnp.where(_chosen_mask(experts, logits.shape[-1]),
                               logits[:, None, :], 0.0), axis=-1)
    return jax.nn.softmax(chosen, axis=-1), experts, \
        jax.nn.softmax(logits, axis=-1), logits


def _chosen_mask(experts: jax.Array, n_experts: int) -> jax.Array:
    """[T, k, E] bool: slot j of token t chose expert e."""
    return experts[:, :, None] == jnp.arange(n_experts, dtype=jnp.int32)


def swiglu_expert(dot, rows, w_gate, w_up, w_down):
    """``w_down(silu(w_gate x) * w_up x)``: OLMoE's gated expert."""
    return dot(jax.nn.silu(dot(rows, w_gate)) * dot(rows, w_up), w_down)


def reglu_expert(dot, rows, w_gate, w_up, w_down):
    """``w_down(relu(w_gate x) * w_up x)``: SmallThinker's gated expert."""
    return dot(jax.nn.relu(dot(rows, w_gate)) * dot(rows, w_up), w_down)


def relu2_expert(dot, rows, w_up, w_down):
    """``w_down relu(w_up x)^2``: Nemotron-H's expert, two matrices and no
    gate."""
    return dot(jnp.square(jax.nn.relu(dot(rows, w_up))), w_down)


def _held_first(experts: jax.Array, first: int, count: int) -> jax.Array:
    """Sort keys of a share: a held expert's index among the held, and
    ``count`` for every pair of an expert that lives elsewhere."""
    local = experts - first
    return jnp.where((local >= 0) & (local < count), local, count)


# -- a share's walk over its sorted pairs -------------------------------------

SHARE_TILE_HEADROOM = 1.5  # a slot, over the pairs a balanced router sends
SHARE_BLOCK_ROWS = 128  # a slot is whole row blocks of the matrix unit
# the narrowest expert matrix whose walk takes the kernels over live blocks
# (measured on the chip: 1792 wins, 768 loses; between them not measured)
SHARE_BLOCKS_MIN_WIDTH = 1024


def share_slot_rows(k_t: int, n_experts: int) -> int:
    """Rows a tile of the walk gives each held expert: ``SHARE_TILE_HEADROOM``
    x the ``k T / E`` pairs a balanced router sends one expert, in whole
    ``SHARE_BLOCK_ROWS``."""
    balanced = int(SHARE_TILE_HEADROOM * k_t / n_experts)
    return max(1, -(-balanced // SHARE_BLOCK_ROWS)) * SHARE_BLOCK_ROWS


def share_tile_rows(k_t: int, count: int, n_experts: int) -> int:
    """Rows of one tile of the walk: a slot of :func:`share_slot_rows` for
    each of the ``count`` held experts, computed from ``k``, ``T``,
    ``count`` and ``E`` alone; never more than the ``k T`` pairs there are
    (a full load is one tile: no walk, no slots)."""
    return min(k_t, count * share_slot_rows(k_t, n_experts))


def _widths_of(expert_weights) -> Tuple[int, ...]:
    """Both widths of every expert matrix ``[count, k, n]``."""
    return tuple(n for w in expert_weights for n in w.shape[1:])


def share_product(widths: Sequence[int]) -> str:
    """What multiplies a tile's rows in a share's walk, decided here and
    nowhere else, by the expert matrices' widths (static shapes):
    ``"blocks"``, :func:`~horovod_tpu.ops.grouped_matmul.grouped_matmul`
    over the row blocks that hold a pair, where every width is whole
    ``SHARE_BLOCK_ROWS`` and at least ``SHARE_BLOCKS_MIN_WIDTH``
    (LFM2's 2048 x 1792); else ``"slots"``, XLA's batched product over every
    row of every slot (SmallThinker's 2560 x 768 and SDAR's 2048 x 768, too
    narrow for the rows skipped to pay for the element-wise passes the
    batched product fuses and a kernel call does not; Nemotron-H's 2688 x
    1856, no whole 128s: ``PERF.md`` §6, PRs 34 and 47)."""
    return "blocks" if all(
        n % SHARE_BLOCK_ROWS == 0 and n >= SHARE_BLOCKS_MIN_WIDTH
        for n in widths) else "slots"


def _share_tiles_counter(kind: str):
    from horovod_tpu.metrics.registry import get_registry
    return get_registry().counter(
        "hvd_moe_share_tiles_total",
        "tiles of a share's walk over its sorted pairs: built into a layer "
        "call (at trace time), live in a step (read back from its load)",
        kind=kind)


def _count_built_tiles(tiles: int, rows: int, product: str):
    """Monitoring, at trace time as ``hvd_ssd_chunks_total`` is."""
    from horovod_tpu.metrics.registry import get_registry
    _share_tiles_counter("built").inc(tiles)
    get_registry().gauge(
        "hvd_moe_share_tile_rows",
        "rows of one tile of the share's walk traced last").set(rows)
    get_registry().counter(
        "hvd_moe_share_product_total",
        "walks of a share built (at trace time) with the grouped-matmul "
        "kernels over a tile's live row blocks, or with the batched product "
        "over its slots: share_product of the expert matrices' widths",
        path=product).inc()


_SHARE_ROWS = (
    "hvd_moe_share_rows_total",
    "rows of a share's walk in the steps read back: the held experts' "
    "pairs, the rows that the grouped matmuls computed for them (the live "
    "row blocks, or under the batched product the live tiles whole), and "
    "the rows of the chunks the way back to the tokens fetched to place "
    "them (at most: every run taken to straddle a chunk)")


def share_tiles(load, held: Tuple[int, int], k: int, tokens: int,
                record: bool = False, widths: Sequence[int] = ()
                ) -> Tuple[int, int]:
    """(live, built): of the ``built`` tiles a share's walk can come to
    (one held expert sent every one of the ``tokens``), the ``live`` ones a
    step that routed this ``load`` worked in: as many as the fullest held
    expert's pairs fill slots. Host side, outside the step: ``load`` is one
    expert layer's pairs per expert over all E (``MoeStats.expert_tokens``,
    or the ``load`` a model's ``router_state`` carries to the next step) of
    a top-``k`` router over ``tokens`` tokens; ``widths`` are the expert
    matrices' widths, which say as in the layer what multiplies a tile's
    rows (:func:`share_product`). ``record`` adds ``live`` to
    ``hvd_moe_share_tiles_total{kind="live"}``, and to
    ``hvd_moe_share_rows_total`` the held experts' pairs (``kind="held"``),
    the rows the products computed for them (``kind="computed"``: each held
    expert's pairs in whole row blocks of ``SHARE_BLOCK_ROWS``, or under
    the batched product whole tiles, every slot of them) and the rows the
    way back fetches to place them, a direction (``kind="fetched"``, the
    walk only: a slot's rows in a tile come in whole chunks of
    ``chunk_rows_of(slot)``, and a chunk is fetched once for each token
    block it holds rows of, so at most one more chunk for each of the
    ``tokens / block_tokens_of(tokens)`` blocks the rows can lie in;
    fetched / held is what the way back reads for a row it places)."""
    first, count = held
    rows = share_tile_rows(k * tokens, count, len(load))
    held_rows = [int(n) for n in load[first:first + count]]
    counted = {"held": sum(held_rows)}
    in_blocks = SHARE_BLOCK_ROWS * grouped_blocks(load, k, tokens, held)[0]
    if rows < k * tokens:
        slot = share_slot_rows(k * tokens, len(load))
        live, built = -(-max(held_rows) // slot), -(-tokens // slot)
        counted["computed"] = in_blocks \
            if share_product(widths) == "blocks" else live * rows
        chunk, blocks = chunk_rows_of(slot), tokens // block_tokens_of(tokens)
        in_tiles = [min(slot, n - i * slot) for n in held_rows
                    for i in range(-(-n // slot))]
        counted["fetched"] = chunk * sum(
            -(-n // chunk) + min(n, blocks) - 1 for n in in_tiles)
    else:  # the one-tile program below the walk: whole row blocks
        live, built = -(-sum(held_rows) // rows), 1
        counted["computed"] = in_blocks
    if record:
        from horovod_tpu.metrics.registry import get_registry
        _share_tiles_counter("live").inc(live)
        for kind, n in counted.items():
            get_registry().counter(*_SHARE_ROWS, kind=kind).inc(n)
    return live, built


def _rows_of(x: jax.Array, index: jax.Array) -> jax.Array:
    """``x[index]`` for indices that a sort of the pairs made: in bounds."""
    return x.at[index].get(mode="promise_in_bounds")


def _run_of(steps: int, ends: jax.Array) -> jax.Array:
    """int32 [steps]: which of the consecutive runs that end before
    ``ends`` [runs] (a cumulative sum) each of ``steps`` positions lies in;
    the last run for a position past them all."""
    return jnp.minimum(ends.shape[0] - 1, jnp.sum(
        jnp.arange(steps)[:, None] >= ends, axis=1, dtype=jnp.int32))


def _share_tiles_of(tile, x, order, weights, sizes, expert, expert_weights):
    """What both directions of a share's walk read: (the number of tiles
    the fullest held expert's pairs reach into, the function of a tile's
    index that gives (its tokens, the token a row goes back to (``T`` for a
    row that is no pair: nowhere), its rows' sorted positions (``k T`` for
    such a row), its rows' router weights, its tokens' rows, the experts as
    a function of (rows, *expert_weights))). A tile is one slot of
    ``tile / count`` rows a held expert: slot ``e`` of tile ``i`` holds
    expert ``e``'s pairs ``[i S, (i + 1) S)``, and the tokens of a slot's
    pairs ascend (the sort is stable). The grouped matmul over a tile is
    :func:`share_product`'s: the kernels over the row blocks that hold a
    pair, or one batched product ``[count, S, k] x [count, k, n]`` over
    every row. A row past its expert's last pair gathers some token in
    bounds and carries weight 0; if its block holds no pair its rows of
    every product are never written."""
    k, count = weights.shape[-1], sizes.shape[0]
    by_blocks = share_product(_widths_of(expert_weights)) == "blocks"
    slot = tile // count
    pairs_in_all = order.shape[0]
    by_pair = weights.reshape(-1)

    def by_row(of_expert):
        """[count] -> [tile]: every row of a slot has its expert's value."""
        return jnp.broadcast_to(of_expert[:, None], (count, slot)).reshape(-1)
    within = jnp.tile(jnp.arange(slot), count)  # a row's place in its slot
    held_pairs, first_pair = by_row(sizes), by_row(jnp.cumsum(sizes) - sizes)

    def over_slots(a, w):
        out = jnp.einsum("esk,ekn->esn", a.reshape(count, slot, -1), w,
                         preferred_element_type=jnp.float32)
        return out.astype(a.dtype).reshape(tile, -1)

    def over_live_blocks(i):
        """The tile as row blocks of ``SHARE_BLOCK_ROWS``, slot ``e``'s all
        held expert ``e``'s: the product over those that hold a pair of
        tile ``i``, each slot's first ``ceil(pairs of it in the tile / a
        block's rows)``, named slot after slot (a cumulative sum over the
        slots, no sort)."""
        rows, in_slot = SHARE_BLOCK_ROWS, slot // SHARE_BLOCK_ROWS
        live = lax.div(jnp.clip(sizes - i * slot, 0, slot) + (rows - 1), rows)
        ends = jnp.cumsum(live)
        step = jnp.arange(count * in_slot, dtype=jnp.int32)
        slot_of_step = _run_of(count * in_slot, ends)
        block_of_step = jnp.minimum(  # past the live steps: never read
            count * in_slot - 1, slot_of_step * in_slot + step
            - _rows_of(ends - live, slot_of_step))
        return lambda a, w: grouped_matmul(
            a, w, lax.div(step, in_slot), block_of_step, ends[-1:])

    def at(i):
        nth = i * slot + within  # which of its expert's pairs a row holds
        real = nth < held_pairs
        position = first_pair + nth
        pairs = _rows_of(order, jnp.minimum(position, pairs_in_all - 1))
        tokens = lax.div(pairs, k)
        with moe_scope("moe_dispatch"):
            rows = _rows_of(x, tokens)
        dot = over_live_blocks(i) if by_blocks else over_slots

        def experts(rows, *expert_weights):
            with moe_scope("moe_experts"):
                return expert(dot, rows, *expert_weights)
        return tokens, jnp.where(real, tokens, x.shape[0]), \
            jnp.where(real, position, pairs_in_all), \
            jnp.where(real, _rows_of(by_pair, pairs), 0.0), rows, experts
    return lax.div(jnp.max(sizes) + (slot - 1), slot), at


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _walk(x, order, inverse, weights, sizes, expert_weights, expert, tile):
    """A share's dispatch, experts and combine as one walk over the sorted
    pairs in tiles of ``tile`` rows, of which only those the fullest held
    expert's pairs reach into do anything: [T, d] float32, the held
    experts' part of the weighted sum. Two loops with a trip count read
    from the data, one forward and one backward, so each grouped matmul is
    in the program once per direction, whatever the number of tiles."""
    live, at = _share_tiles_of(tile, x, order, weights, sizes, expert,
                               expert_weights)

    def one_tile(i, out):
        _, back, _, weight, rows, experts = at(i)
        rows = experts(rows, *expert_weights)
        with moe_scope("moe_combine"):
            return add_rows_at_tokens(out, rows, weight, back,
                                      sizes.shape[0], fresh=i == 0)
    return lax.fori_loop(0, live, one_tile, jnp.zeros(x.shape, jnp.float32))


def _walk_fwd(x, order, inverse, weights, sizes, expert_weights, expert,
              tile):
    return _walk(x, order, inverse, weights, sizes, expert_weights, expert,
                 tile), (x, order, inverse, weights, sizes, expert_weights)


def _walk_bwd(expert, tile, saved, g):
    """The tiles of the forward walk, live ones only. The walk keeps no
    activation: a tile's rows are gathered and taken through the experts
    again (under a recomputed block that is the one recomputation: the
    forward walk's result is not needed there, and goes). What the tiles
    add to the tokens' gradient is summed in float32, to the expert
    weights' in the weights' dtype: exact with one live tile (float32 sums
    were a gigabyte of the step's temporaries at Nemotron-H's widths)."""
    x, order, inverse, weights, sizes, expert_weights = saved
    live, at = _share_tiles_of(tile, x, order, weights, sizes, expert,
                               expert_weights)

    def one_tile(i, carry):
        d_x, d_by_pair, d_experts = carry
        tokens, back, position, weight, rows, experts = at(i)
        rows, pull = jax.vjp(experts, rows, *expert_weights)
        with moe_scope("moe_combine"):
            g_rows = _rows_of(g, tokens)
            d_by_pair = d_by_pair.at[position].set(
                jnp.sum(g_rows * rows.astype(jnp.float32), axis=-1),
                mode="drop")
            d_rows = (weight[:, None] * g_rows).astype(rows.dtype)
        d_rows, *d_tile = pull(d_rows)
        with moe_scope("moe_experts"):
            d_experts = tuple(a + d for a, d in zip(d_experts, d_tile))
        with moe_scope("moe_dispatch"):
            d_x = add_rows_at_tokens(d_x, d_rows, jnp.ones_like(weight),
                                     back, sizes.shape[0], fresh=i == 0)
        return d_x, d_by_pair, d_experts

    d_x, d_by_pair, d_experts = lax.fori_loop(0, live, one_tile, (
        jnp.zeros(x.shape, jnp.float32),
        jnp.zeros(order.shape[0], jnp.float32),  # by sorted position
        tuple(jnp.zeros_like(w) for w in expert_weights)))
    with moe_scope("moe_combine"):
        d_weights = _rows_of(d_by_pair, inverse).reshape(weights.shape)
    return d_x.astype(x.dtype), None, None, d_weights, None, d_experts


_walk.defvjp(_walk_fwd, _walk_bwd)


# -- below the walk: every expert's pairs from a row block on -------------------

def grouped_blocks_built(k_t: int, count: int) -> int:
    """Row blocks that hold all ``k T`` pairs with each of ``count`` experts'
    pairs started on a block, whatever the routing: the pairs in whole
    blocks and a block of padding an expert."""
    return -(-k_t // SHARE_BLOCK_ROWS) + count


_GROUPED_COUNTERS = {
    "blocks": ("hvd_moe_grouped_blocks_total",
               "row blocks of the grouped matmuls below the walk: built into "
               "a layer call (at trace time), live in a step (read back from "
               "its load)"),
    "rows": ("hvd_moe_grouped_rows_total",
             "rows of the grouped matmuls below the walk in the steps read "
             "back: the held experts' pairs, and the rows of the live blocks "
             "that the kernels computed for them"),
}


def _grouped_counter(name: str, kind: str):
    from horovod_tpu.metrics.registry import get_registry
    return get_registry().counter(*_GROUPED_COUNTERS[name], kind=kind)


def grouped_blocks(load, k: int, tokens: int,
                   held: Optional[Tuple[int, int]] = None,
                   record: bool = False) -> Tuple[int, int]:
    """(live, built): of the ``built`` row blocks the program below the walk
    is compiled with, the ``live`` ones a step that routed this ``load``
    computed: each held expert's pairs in whole blocks. Host side, outside
    the step, as :func:`share_tiles`: ``load`` is one expert layer's pairs
    per expert over all E of a top-``k`` router over ``tokens`` tokens,
    ``held`` the share (every expert without it). ``record`` adds ``live``
    to ``hvd_moe_grouped_blocks_total{kind="live"}``, and to
    ``hvd_moe_grouped_rows_total`` the held experts' pairs
    (``kind="held"``) and the rows of the live blocks (``kind="computed"``):
    computed / held is what starting every expert on a block costs."""
    first, count = held if held is not None else (0, len(load))
    held_rows = [int(n) for n in load[first:first + count]]
    live = sum(-(-n // SHARE_BLOCK_ROWS) for n in held_rows)
    if record:
        _grouped_counter("blocks", "live").inc(live)
        _grouped_counter("rows", "held").inc(sum(held_rows))
        _grouped_counter("rows", "computed").inc(live * SHARE_BLOCK_ROWS)
    return live, grouped_blocks_built(k * tokens, count)


class _Blocks(NamedTuple):
    """Where the pairs lie when each held expert's start on a row block."""
    group_of_block: jax.Array  # int32 [blocks]: the held expert of a block
    block_of_step: jax.Array   # int32 [blocks]: the blocks in order (those
    #                            that hold a pair are a prefix)
    live: jax.Array            # int32 [1]: the blocks that hold a pair
    pair_of_row: jax.Array     # int32 [rows]: the (token, slot) pair of a
    #                            row; of a row of padding, some pair
    real: jax.Array            # bool [rows]: the row holds a pair
    row_of_pair: jax.Array     # int32 [k T]: the row of a (token, slot) pair
    held: Optional[jax.Array]  # bool [k T]: the pair's expert is held here;
    #                            None where every expert is


def _blocks_of(sizes, keys, order, inverse, is_share: bool) -> _Blocks:
    """The sorted pairs with each held expert's pairs started on a multiple
    of ``SHARE_BLOCK_ROWS`` rows: a pair's row is its sorted position plus
    the padding of the experts before its own, arithmetic on the held
    counts ``sizes`` and no second sort. ``keys`` [k T] are the pairs' sort
    keys (a held expert's index among the held, ``len(sizes)`` for an
    expert held elsewhere), ``order`` their stable argsort, ``inverse``
    its inverse. The blocks that hold a pair are a prefix of
    :func:`grouped_blocks_built`'s, a static number."""
    count, block, pairs = sizes.shape[0], SHARE_BLOCK_ROWS, order.shape[0]
    built = grouped_blocks_built(pairs, count)
    blocks = lax.div(sizes + (block - 1), block)
    block_ends, pair_ends = jnp.cumsum(blocks), jnp.cumsum(sizes)
    # rows of padding before an expert's first pair
    padding = (block_ends - blocks) * block - (pair_ends - sizes)
    group_of_block = _run_of(built, block_ends)

    def by_row(of_expert):
        """[count] -> [rows]: every row of a block has its expert's value."""
        return jnp.broadcast_to(_rows_of(of_expert, group_of_block)[:, None],
                                (built, block)).reshape(-1)
    # a row past its expert's last pair, or of a block past the live ones
    # (whose table entry is the last expert), lies at or past that
    # expert's last sorted position
    position = jnp.arange(built * block) - by_row(padding)
    return _Blocks(
        group_of_block, jnp.arange(built, dtype=jnp.int32), block_ends[-1:],
        real=position < by_row(pair_ends),
        pair_of_row=_rows_of(order, jnp.minimum(position, pairs - 1)),
        row_of_pair=inverse + jnp.sum(jnp.where(
            keys[:, None] == jnp.arange(count), padding, 0), axis=1),
        held=keys < count if is_share else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_to_blocks(x, blocks: _Blocks, k):
    """Row r of the result is the token of pair ``blocks.pair_of_row[r]``
    (a row of padding: some token in bounds). The backward pass is the
    gather of the pairs' rows, a token's k side by side, summed."""
    return _rows_of(x, lax.div(blocks.pair_of_row, k))


def _rows_to_blocks_fwd(x, blocks, k):
    return _rows_to_blocks(x, blocks, k), blocks


def _pairs_of(rows, blocks: _Blocks):
    """The rows of the (token, slot) pairs, zeros for a pair whose expert
    is held elsewhere."""
    rows = _rows_of(rows, blocks.row_of_pair)
    if blocks.held is None:
        return rows
    return jnp.where(blocks.held[:, None], rows, jnp.zeros((), rows.dtype))


def _rows_to_blocks_bwd(k, blocks, g):
    g = _pairs_of(g, blocks)
    return g.reshape(-1, k, g.shape[-1]).sum(axis=1), None


_rows_to_blocks.defvjp(_rows_to_blocks_fwd, _rows_to_blocks_bwd)


@jax.custom_vjp
def _rows_from_blocks(rows, blocks: _Blocks):
    """The rows in (token, slot) order. The backward pass is the inverse
    gather, with zeros for the rows that hold no pair: nothing reaches a
    weight's gradient through a row of padding."""
    return _pairs_of(rows, blocks)


def _rows_from_blocks_fwd(rows, blocks):
    return _rows_from_blocks(rows, blocks), blocks


def _rows_from_blocks_bwd(blocks, g):
    g = _rows_of(g, blocks.pair_of_row)
    return jnp.where(blocks.real[:, None], g, jnp.zeros((), g.dtype)), None


_rows_from_blocks.defvjp(_rows_from_blocks_fwd, _rows_from_blocks_bwd)


class Routing(NamedTuple):
    """A router's choice for T tokens, made by :func:`moe_routing`."""
    weights: jax.Array  # float32 [T, k]
    experts: jax.Array  # int32 [T, k]
    stats: MoeStats


def moe_routing(route: Callable, x: jax.Array) -> Routing:
    """``route(x)`` and the per-expert counts, under ``moe_router``.
    :func:`moe_dropless` calls it on the experts' own rows; a model whose
    router reads other rows of the same tokens (the layer's input, before
    attention) calls it there and hands the layer the result."""
    with moe_scope("moe_router"):
        weights, experts, scores, logits = route(x)
        return Routing(weights, experts, MoeStats(
            expert_tokens=jnp.sum(_chosen_mask(experts, scores.shape[-1]),
                                  axis=(0, 1), dtype=jnp.int32),
            router_prob_mean=scores.mean(axis=0),
            router_z_loss=jnp.mean(
                jax.nn.logsumexp(logits, axis=-1) ** 2)))


def moe_dropless(x: jax.Array, route, expert: Callable,
                 expert_weights: Sequence[jax.Array],
                 held: Optional[Tuple[int, int]] = None
                 ) -> Tuple[jax.Array, MoeStats]:
    """One dropless top-k expert layer over the tokens it is given.

    x: [T, d] in the compute dtype. ``route(x)`` is the router over all E
    experts, or ``route`` is the :class:`Routing` of these T tokens that
    :func:`moe_routing` made from other rows, and
    ``expert(dot, rows, *expert_weights)`` one expert's function
    over the grouped matmul ``dot`` (the module text has both contracts);
    ``expert_weights`` lead with the experts held here: all E, or with
    ``held = (first, count)`` the ``count`` from ``first`` on. Returns
    ([T, d] in x's dtype: the held experts' part of the weighted sum,
    :class:`MoeStats` over all E). Every (token, slot) pair of a held expert
    is computed, whatever the imbalance; ``stats.expert_tokens`` sums to
    ``k T``.

    The sorted pairs are worked in tiles of :func:`share_tile_rows` rows, a
    number computed from ``k``, ``T``, ``count`` and ``E`` alone. A full
    load is one tile of ``k T`` rows, and that is the program below the
    walk: the rows gathered to where each expert's pairs start on a row
    block (:func:`_blocks_of`), one
    :func:`~horovod_tpu.ops.grouped_matmul.grouped_matmul` per projection
    over the live blocks, the pairs' rows gathered back (module text, "A
    full load"). A share of fewer experts is :func:`_walk`: a tile is one
    slot of :func:`share_slot_rows` rows a held expert, the product over it
    :func:`share_product`'s (the same kernels over the slots' live row
    blocks where the expert matrices are whole 128s and wide enough, else
    one batched ``dot_general`` over the slots), nothing is done for a tile
    past the fullest held expert's last pair, and a live tile's weighted
    rows are added to their tokens in float32 by
    :func:`~horovod_tpu.ops.rows_to_tokens.add_rows_at_tokens`, token block
    by token block in VMEM and not by a scatter (module text, "A share").
    """
    t, d = x.shape
    weights, experts, stats = route if isinstance(route, Routing) else \
        moe_routing(route, x)
    if experts.shape[0] != t:
        raise ValueError(f"a routing of {experts.shape[0]} tokens for "
                         f"{t} rows")
    k, n_experts = experts.shape[-1], stats.expert_tokens.shape[0]
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
    sizes = stats.expert_tokens[first:first + count]
    tile = share_tile_rows(k * t, count, n_experts)
    if tile < k * t:
        _count_built_tiles(-(-t // (tile // count)), tile,  # T in slots
                           share_product(_widths_of(expert_weights)))
        out = _walk(x, order, inverse, weights, sizes, tuple(expert_weights),
                    expert, tile)
        return out.astype(x.dtype), stats
    with moe_scope("moe_dispatch"):
        blocks = _blocks_of(sizes, keys, order, inverse, held is not None)
        rows = _rows_to_blocks(x, blocks, k)
    _grouped_counter("blocks", "built").inc(blocks.group_of_block.shape[0])
    with moe_scope("moe_experts"):
        def dot(a, w):
            return grouped_matmul(a, w, blocks.group_of_block,
                                  blocks.block_of_step, blocks.live)
        rows = expert(dot, rows, *expert_weights)
    with moe_scope("moe_combine"):
        rows = _rows_from_blocks(rows, blocks).reshape(t, k, d)
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
