"""The state-space-dual scan of Mamba-2, chunked: the first recurrence here.

A Mamba-2 head carries a state ``S`` in R^(P x N) along the sequence
(arXiv:2405.21060; step 5 of ``models/nemotron_h.py``'s mixer):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

with ``S_{-1} = 0``, ``A < 0`` a scalar a head, ``dt_t > 0`` a scalar a head
and position, ``x_t`` in R^P, and ``B_t``, ``C_t`` in R^N shared by the heads
of a group. Run position by position that is ``T`` dependent steps of
element-wise work. :func:`ssd_chunked` is section 6 of the paper instead: the
sequence in chunks of ``L`` positions, and with ``a`` the running sum of
``dt A`` inside a chunk,

- inside a chunk the masked products ``(M o C B^T) (dt x)``,
  ``M_ts = exp(a_t - a_s)`` for ``s <= t`` and 0 above the diagonal;
- a chunk's own end state ``sum_s exp(a_L - a_s) dt_s x_s (x) B_s``;
- the states carried from chunk to chunk, a ``lax.scan`` over the ``T / L``
  chunks: ``S <- exp(a_L) S + (the chunk's own)``;
- what the state a chunk starts from gives each of its positions,
  ``exp(a_t) S C_t``.

Three of the four are matrix products (``einsum``, bf16 inputs where the
caller's are, float32 accumulation); the carry is ``T / L`` steps. Every
exponent is a difference ``a_t - a_s`` with ``s <= t`` of a sum of
non-positive terms, masked *before* ``exp``: nothing is exponentiated that
can be positive, whatever ``dt`` is. ``dt``, ``A``, the running sums, the
decays and the carried state are float32 whatever the inputs' dtype (the
repo's dtype policy; ``tests/test_ssd.py`` shows a bf16 running sum failing
the tolerance the policy holds). Plain ``jax.numpy`` that autodiff takes the
gradient of, static shapes, no kernel: the Pallas kernel for it is
``ROADMAP.md`` work, and this function is what it will be held to.

Shapes: ``x`` [B, T, H, P]; ``dt`` [B, T, H] (already positive: the caller
applies its softplus); ``A`` [H] (negative); ``B``, ``C`` [B, T, G, N] with
``H`` a multiple of ``G`` (head ``h`` reads group ``h // (H / G)``); ``D``
[H]. ``T`` must be a multiple of ``chunk``: another length is an error, not
padding. Returns ``y`` [B, T, H, P] in ``x``'s dtype and the states at each
chunk's end, float32 [B, T / chunk, H, P, N] (the last is the state after
the whole sequence).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.profiler.annotate import ssm_scope


def _running_sum_last(a: jax.Array) -> jax.Array:
    """The inclusive sum of ``dt A`` along a chunk's positions (the last
    axis), in float32. Apart so that a test can show what a bf16 sum
    costs."""
    return jnp.cumsum(a.astype(jnp.float32), axis=-1)


def _count_chunks(chunks: int):
    """Monitoring, at trace time as ``hvd_flash_block_visits`` is: the
    (batch, head, chunk) triples of what was just traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_ssd_chunks_total",
        "chunks of the state-space-dual scan traced (chunks x heads x "
        "batch)").inc(chunks)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, chunk: int = 128
                ) -> Tuple[jax.Array, jax.Array]:
    b, t, h, p = x.shape
    g, n = B.shape[-2:]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the "
                         f"scan's chunk ({chunk})")
    if h % g:
        raise ValueError(f"{h} heads cannot share {g} groups of B and C")
    if dt.shape != (b, t, h) or C.shape != B.shape or \
            B.shape[:2] != (b, t) or A.shape != (h,) or D.shape != (h,):
        raise ValueError(
            f"x {x.shape} wants dt [B, T, H], A and D [H], B and C "
            f"[B, T, G, N]; got {dt.shape}, {A.shape}, {D.shape}, "
            f"{B.shape}, {C.shape}")
    nc, r = t // chunk, h // g
    _count_chunks(b * h * nc)
    f32 = jnp.float32

    with ssm_scope("ssm_scan"):
        # [B, chunks, L, ...]; heads as (group, head in group). The
        # [L, L] matrices keep their two positions last, heads before them
        xc = x.reshape(b, nc, chunk, g, r, p)
        Bc = B.reshape(b, nc, chunk, g, n)
        Cc = C.reshape(b, nc, chunk, g, n)
        dtc = dt.astype(f32).reshape(b, nc, chunk, g, r) \
            .transpose(0, 1, 3, 4, 2)                     # [B, c, G, R, L]
        a = _running_sum_last(dtc * A.astype(f32).reshape(g, r, 1))
        a_end = a[..., -1]                                # [B, c, G, R]

        def by_position(v):  # [B, c, G, R, L] -> [B, c, L, G, R, 1]
            return v.transpose(0, 1, 4, 2, 3)[..., None]

        # inside a chunk: (M o C B^T)(dt x); the mask comes before exp
        pos = jnp.arange(chunk)
        below = pos[:, None] >= pos[None, :]
        diff = a[..., :, None] - a[..., None, :]          # [.., t, s]
        decay = jnp.exp(jnp.where(below, diff, -jnp.inf))
        cb = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc,
                        preferred_element_type=f32)
        m = (cb[:, :, :, None] * decay * dtc[..., None, :]).astype(x.dtype)
        y = jnp.einsum("bcgrts,bcsgrp->bctgrp", m, xc,
                       preferred_element_type=f32)

        # a chunk's own end state: sum_s exp(a_L - a_s) dt_s x_s (x) B_s
        to_end = by_position(jnp.exp(a_end[..., None] - a) * dtc)
        own = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                         xc * to_end.astype(x.dtype), Bc,
                         preferred_element_type=f32)

        # carried from chunk to chunk, in float32
        def carry(state, chunk_terms):
            decay_c, own_c = chunk_terms
            state = state * decay_c[..., None, None] + own_c
            return state, state
        _, ends = lax.scan(
            carry, jnp.zeros((b, g, r, p, n), f32),
            (jnp.exp(a_end).swapaxes(0, 1), own.swapaxes(0, 1)))
        ends = ends.swapaxes(0, 1)                        # [B, c, G, R, P, N]
        starts = jnp.concatenate(
            [jnp.zeros_like(ends[:, :1]), ends[:, :-1]], axis=1)

        # what the state a chunk starts from gives its positions
        y += jnp.einsum("bctgn,bcgrpn->bctgrp", Cc, starts.astype(x.dtype),
                        preferred_element_type=f32) * by_position(jnp.exp(a))
        y += xc.astype(f32) * D.astype(f32).reshape(g, r, 1)
    return (y.reshape(b, t, h, p).astype(x.dtype),
            ends.reshape(b, nc, h, p, n))
