"""The head and its cross-entropy as one operator with its own backward,
over chunks of rows, written for the chip.

A language model's last step is a product and an element-wise function of
its result: ``logits = h w`` ([T, d] by [d, V], float32 out of the
accumulators) and ``sum_i weights_i (logsumexp(logits_i) -
logits_i[label_i])``. Written through autodiff (a log-softmax, a gather, in
backward a scatter into a second ``[T, V]`` array and a softmax computed
again) the float32 ``[T, V]`` arrays stand whole in HBM and are read a dozen
times; the compiler cannot fold that back into two passes (``PERF.md`` §6,
PR 53). Here the gradient is written by hand: autodiff transposes no
element-wise chain over a ``[T, V]`` array.

*The row function* (:func:`_rows`, plain ``jax.numpy`` on a block of rows):
from float32 logits, labels and float32 row weights it gives each row's
cross-entropy and the logits' gradient ``weights_i (exp(logits_i - lse_i) -
[v == label_i])``. The label's logit is read through a comparison with an
iota, never a gather, and its gradient is a subtraction in the softmax's own
pass, never a scatter: one reading pass for the statistics, one pass that
writes the gradient.

*Door A*, :func:`head_cross_entropy`, for a loss that holds the hidden state
and the head's matrix: a ``jax.custom_vjp`` whose forward rule walks the rows
in chunks (a static loop). A chunk computes its logits, the row function, and
at once the two products of the backward pass, ``dh = dlogits w`` and ``dw +=
dlogits^T h`` (a float32 sum), so the rule returns the scalar and keeps ``dh``
[T, d] and ``dw`` alone; the backward rule scales them by the incoming
cotangent. Three products a chunk and no logits computed twice. The logits'
gradient is the products' input dtype (bf16): the dtype autodiff's two
transposed products read it in (the compiled steps before this operator cast
the float32 gradient to bf16 in front of both). The matrix is taken as the
model holds it, ``[V, d]`` (a tied embedding) or ``[d, V]`` (an ``nn.Dense``
kernel), with no transposed copy, and its gradient comes back in the same
shape. Without a gradient wanted the same chunks compute the loss alone.

*Door B*, :func:`cross_entropy`, for a loss that is handed float32 logits:
the row function over the whole array through a ``jax.custom_vjp``. The
gradient is written in the forward rule, float32 as the cotangent of float32
logits is (the operator cannot see which dtype the caller's head multiplies
in, and a float32 program has to stay one); the backward rule scales it.

*A barrier, and why.* XLA sees one graph and fuses the gradient's pass into
the products that read it; a product whose result is wanted late (``dw``, at
the update) it then postpones, and holds the float32 logits for it through
the whole backward pass: what this operator exists to end. So a chunk of door
A ends in ``optimization_barrier((dh_rows, dw))`` (``PERF.md`` §6, PR 53:
without it ``nemotron3n-t8192`` kept both chunks' logits across its backward
pass and ran 0.56% slower). Door B's products are its caller's and autodiff's:
there the compiler does as it likes.

Which door a loss takes follows from what its caller holds. Rows a chunk
follow from the vocabulary (:func:`chunk_rows`): a chunk's float32 logits are
at most :data:`CHUNK_BYTES`, because ``dw``'s float32 sum is read and written
once a chunk, which speaks against small chunks, and a chunk's logits are
what the operator adds to the step's peak, which speaks against large ones. A
head small enough for one chunk runs the same code with a trip count of one.

The scopes: the three products are ``head_logits``, everything else
``head_loss`` (``profiler/annotate.HEAD_SCOPES``), so a trace's
``head_loss_ms`` reads the same work as before; ``scopes`` puts another
family's name there (a multi-token-prediction module's ``mtp_head``). Door B
writes none: its caller does (``head_loss``; a diffusion objective's
``diffusion_loss``).
"""

from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

from horovod_tpu.profiler.annotate import head_scope

F32 = jnp.float32
# The most a chunk's float32 logits take. In the cells (``PERF.md`` §6, PR 53)
# half of this read the same rate in three and 1.2% less in a fourth, whose
# 8192 rows it cut in two; a quarter was slower in the operator alone.
CHUNK_BYTES = 1 << 29
# (what names the products, what names everything else)
Scopes = Tuple[Callable, Callable]
HEAD: Scopes = (functools.partial(head_scope, "head_logits"),
                functools.partial(head_scope, "head_loss"))


def chunk_rows(vocab: int) -> int:
    """Rows a chunk of door A: the largest power of two whose float32 logits
    over ``vocab`` columns fit :data:`CHUNK_BYTES`, and a sublane tile (8)
    at least."""
    rows = max(CHUNK_BYTES // (4 * vocab), 8)
    return 1 << (rows.bit_length() - 1)


def _count_call(door: str, chunks: int):
    """Monitoring, at trace time as ``hvd_flash_calls_total`` is: one count a
    traced call, so a described compile of a step says which door its loss
    took and in how many chunks."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_head_loss_calls_total",
        "head-and-loss operator calls traced, by door (A: hidden state and "
        "matrix, over chunks of rows; B: logits) and by chunks",
        door=door, chunks=str(chunks)).inc()


def _row_losses(logits, labels):
    """(``logsumexp(logits_i) - logits_i[label_i]``, ``lse``, the mask of
    the labels' columns); the label's logit through the mask."""
    hit = labels[..., None] == jnp.arange(logits.shape[-1],
                                          dtype=labels.dtype)
    peak = jnp.max(logits, axis=-1, keepdims=True)
    lse = peak[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - peak), axis=-1))
    return lse - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1), lse, hit


def _rows(logits, labels, weights, dtype):
    """The row function: (every row's cross-entropy, float32; the gradient
    of ``sum(weights * cross-entropy)`` in the logits, as ``dtype``)."""
    ce, lse, hit = _row_losses(logits, labels)
    softmax = jnp.exp(logits - lse[..., None])
    return ce, (weights[..., None] * (softmax - hit)).astype(dtype)


def _vocab_axis(h, w) -> int:
    d = h.shape[-1]
    if w.ndim != 2 or d not in w.shape or w.shape[0] == w.shape[1]:
        raise ValueError(
            f"a head's matrix is [vocab, {d}] or [{d}, vocab] with vocab "
            f"other than {d}; got {w.shape}")
    return 0 if w.shape[1] == d else 1


def head_cross_entropy(h: jax.Array, w: jax.Array, labels: jax.Array,
                       weights: jax.Array, scopes: Scopes = HEAD
                       ) -> jax.Array:
    """Door A: ``sum_i weights_i CE(h_i w, labels_i)``, a float32 scalar,
    from the hidden state ``h`` [T, d] (the products' input dtype), the
    head's matrix ``w`` as the model holds it ([V, d] or [d, V]; cast to
    ``h.dtype`` for the products, its gradient in its own dtype), integer
    ``labels`` [T] and float32 ``weights`` [T] (a mask, a schedule's weight;
    a mean divides the result by its own denominator). Differentiable in
    ``h``, ``w`` and ``weights``."""
    t = h.shape[0]
    vocab_axis = _vocab_axis(h, w)
    rows = chunk_rows(w.shape[vocab_axis])
    chunks = [slice(start, min(start + rows, t))
              for start in range(0, t, rows)]
    _count_call("A", len(chunks))
    logits_scope, loss_scope = scopes
    h_w = (((1,), (1 - vocab_axis,)), ((), ()))
    dlogits_w = (((1,), (vocab_axis,)), ((), ()))
    over_rows = (((0,), (0,)), ((), ()))

    def product(a, b, dims):
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        return jax.lax.dot_general(a, b, dims, preferred_element_type=F32)

    @jax.custom_vjp
    def operator(h, w, labels, weights):
        """No gradient wanted: the loss alone, a product a chunk."""
        with logits_scope():
            w_in = w.astype(h.dtype)
        ce = []
        for chunk in chunks:
            with logits_scope():
                logits = product(h[chunk], w_in, h_w)
            with loss_scope():
                ce.append(_row_losses(logits, labels[chunk])[0])
        with loss_scope():
            return jnp.sum(weights * jnp.concatenate(ce))

    def forward(h, w, labels, weights):
        with logits_scope():
            w_in = w.astype(h.dtype)
        dw = jnp.zeros(w.shape, F32)
        ce, dh = [], []
        for chunk in chunks:
            with logits_scope():
                logits = product(h[chunk], w_in, h_w)
            with loss_scope():
                ce_rows, dlogits = _rows(logits, labels[chunk],
                                         weights[chunk], h.dtype)
            with logits_scope():
                dh_rows = product(dlogits, w_in, dlogits_w)
                dw += product(h[chunk], dlogits, over_rows) if vocab_axis \
                    else product(dlogits, h[chunk], over_rows)
                # a chunk ends here: left free, the compiler postpones the
                # product towards the matrix (wanted at the update only) and
                # holds the chunk's float32 logits for it through the
                # backward pass
                dh_rows, dw = jax.lax.optimization_barrier((dh_rows, dw))
            ce.append(ce_rows)
            dh.append(dh_rows)
        with loss_scope():
            ce = jnp.concatenate(ce)
            return jnp.sum(weights * ce), (ce, dw, jnp.concatenate(dh))

    def backward(kept, g):
        ce, dw, dh = kept
        # the hidden state's gradient leaves its product's float32 here, as
        # the transposed product's result did; the rest is the cotangent's
        with logits_scope():
            dh = (g * dh).astype(h.dtype)
        with loss_scope():
            return dh, (g * dw).astype(w.dtype), None, g * ce

    operator.defvjp(forward, backward)
    return operator(h, w, labels, weights)


def cross_entropy(logits: jax.Array, labels: jax.Array, weights: jax.Array
                  ) -> jax.Array:
    """Door B: ``sum_i weights_i CE(logits_i, labels_i)``, a float32 scalar,
    from float32 ``logits`` [..., V], integer ``labels`` [...] and float32
    ``weights`` [...]. Differentiable in ``logits`` and ``weights``; the
    caller names the scope."""
    _count_call("B", 1)
    return _whole(logits, labels, weights)


@jax.custom_vjp
def _whole(logits, labels, weights):
    return jnp.sum(weights * _row_losses(logits, labels)[0])


def _whole_forward(logits, labels, weights):
    ce, dlogits = _rows(logits, labels, weights, F32)
    return jnp.sum(weights * ce), (ce, dlogits)


def _whole_backward(kept, g):
    ce, dlogits = kept
    return g * dlogits, None, g * ce


_whole.defvjp(_whole_forward, _whole_backward)
