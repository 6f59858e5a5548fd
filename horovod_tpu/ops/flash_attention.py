"""Fused flash attention as a Pallas TPU kernel — forward AND backward.

The hot op of the transformer path (BASELINE config 3): computes
softmax(QK^T)V blockwise in VMEM with online log-sum-exp accumulation, so
the [T, T] score matrix never exists in HBM — the kernel streams K/V blocks
through the MXU and keeps the fp32 accumulators on chip.

Six design points make this the building block the rest of the framework
composes with:

- **log-sum-exp residual**: ``return_lse=True`` also returns the per-row
  lse, which is exactly what an online-softmax *merge* needs. That is how
  ``parallel/sp.py:ring_attention`` uses this kernel as its within-shard
  engine: each ring step produces (o, lse) for one K/V shard and the
  results merge exactly.
- **global position offsets**: ``q_offset``/``k_offset`` (traced scalars,
  staged into SMEM) shift the causal mask to global coordinates, so a
  sequence-sharded rank can attend its local q block against a rotating
  remote K/V shard. Blocks entirely in the future cost zero work — the k
  loop's *traced* upper bound excludes them. (Blocks entirely in the past
  still compute their all-true mask: a second, unmasked loop body was
  measured on the v5e and gained nothing, ``PERF.md`` §6, PR 27;
  :func:`block_plan` counts the three kinds of block.)
- **a window beside the causal mask**: ``window=W`` (static) keeps
  ``0 <= q_pos - k_pos < W`` in the same global positions, for models that
  mix window layers with full ones. The far edge is a second diagonal: the
  forward and dq kernels start at the first k block it touches, the dk/dv
  kernel ends at the last q tile that still sees its k tile, blocks wholly
  behind the window are never loaded, and the one loop body masks both
  edges. A windowed call runs the same bodies under kernel functions of its
  own names (``_fwd_window_kernel``, ``_bwd_dq_window_kernel``,
  ``_bwd_dkv_window_kernel``), so the compiled text and a device trace tell
  window layers from causal ones; without a window the traced bodies are
  what they were.
- **a causal edge rounded to blocks of tokens**: ``block_mask=(G, edge)``
  (static) compares block indices ``b(i) = i // G`` instead of positions:
  ``"le"`` keeps ``b(k) <= b(q)`` (block-causal), ``"lt"`` keeps
  ``b(k) < b(q)`` (the blocks before the query's own). They are the two
  masks a block-diffusion objective lays over the clean stream's keys
  (:func:`blockdiff_attention`, which adds the ``G x G`` tiles of a noised
  block on itself). The same bodies again, under
  ``_fwd_blockdiff_kernel``, ``_bwd_dq_blockdiff_kernel`` and
  ``_bwd_dkv_blockdiff_kernel``: the edge is a row of ``block_q`` values
  compared with a column of key positions, and the loops' bounds are the
  causal ones moved by a block (``"lt"``), so a tile no pair of which is
  visible is never loaded.
- **queries and keys of one width, values of another**: ``q``/``k``
  ``[.., d]`` against ``v`` ``[.., dv]`` (multi-head latent attention trains
  with 192 and 128: a head's key is its own 128 beside one rotary 64 that
  all heads share). Nothing in the bodies assumes one width: the scores
  contract over ``d``, the output and ``dv`` accumulate ``dv`` wide, ``dq``
  and ``dk`` ``d`` wide, ``delta`` sums over ``dv``. Such a call runs the
  same bodies under ``_fwd_latent_kernel``, ``_bwd_dq_latent_kernel`` and
  ``_bwd_dkv_latent_kernel``, so a reader that costs ``_fwd_kernel`` by one
  head width never meets it; with equal widths the program is what it was.
- **custom VJP**: backward is two Pallas kernels (dq gridded over q tiles,
  dk/dv gridded over k tiles) recomputing probabilities from the saved lse,
  the standard flash backward. The lse output is differentiable too
  (d lse/d s = softmax prob), so gradients flow through ring-attention
  merges.
- **the probabilities are the MXU's stationary operand**: each kernel's
  time on the v5e follows the instruction bundles of its loop body, and
  what fills them is register spills around the [block, block] fp32 score
  tile, not arithmetic. So each kernel picks the orientation of that tile
  that keeps its row statistics along lanes and lets the narrow [block, d]
  operand stream past the probabilities: the forward and dq kernels work on
  ``k q^T`` and accumulate ``v^T p^T`` / ``k^T ds^T`` as [d, block_q]; the
  dk/dv kernel works on ``q k^T`` and accumulates [d, block_k] for heads up
  to ``_NARROW_HEAD``, and on ``k q^T`` with plain [block_k, d] sums for
  wider ones. No score tile is ever transposed; ``sm_scale`` multiplies
  the [block, d] operand of the scores where that is exact (the fp32 scores
  otherwise) and the fp32 ``dq``/``dk`` sums instead of ``ds``; a
  fully-masked row is one select on its ``lse``.

Layout: [batch, seq, heads, head_dim] in, same out; k and v may hold fewer
heads than q (grouped-query attention). Internally every array is
transposed to [batch * heads, seq, head_dim] and each (batch, query head)
pair is one grid row; k and v keep their own heads and the index maps send a
group's query heads to one key head, so no key head is written out once a
query head and a kernel fetches it once a group (:func:`_head_spec`).
Pure-JAX reference semantics are tested against in interpret mode (CPU) and
the kernel compile-checks on the real chip.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.kernel_call import (NEG_INF, NN, NT, TN, dot,
                                         on_this_platform, scalar_spec)
from horovod_tpu.profiler.annotate import attn_part_scope

# What this file does around a kernel's call, and not in it, is named in the
# device trace (``profiler/annotate.ATTN_PART_SCOPES``); the ``pallas_call``s
# themselves carry no part and no ``name=``: readers know a kernel by its
# function.
_kernel_io = functools.partial(attn_part_scope, "attn_kernel_io")

# Crossover of the auto-router (:func:`attention`): fewer keys than this go
# to :func:`xla_attention`; override with HOROVOD_FLASH_MIN_SEQ. Where the
# two paths cross is NOT measured on today's code: no cell runs one length
# on both. Measured on each side: at 512 the XLA path in row blocks reads
# 55.1% `mfu_device` (47.3 as one dense product), the kernels at 2048 read
# 53.1 on the same model and tokens a step (``PERF.md`` §6, PR 49).
DEFAULT_FLASH_MIN_SEQ = 1024


# What the backward kernels need of the forward besides its inputs, by the
# names a recomputation policy keeps them under
# (``jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS)``): a
# recomputed block then runs the forward kernel once a step, not twice.
FLASH_RESIDUALS = ("flash_out", "flash_lse")

# Heads at most this wide fill half of the MXU's 128 columns or less: an
# accumulation with the head width as its output's columns wastes the rest
# of every pass (see ``_bwd_dkv_body``).
_NARROW_HEAD = 64


def _pos(off_f32, base, shape, dim):
    """Global positions (fp32 — exact for T < 2^24) of a tile. The iota is
    integer (TPU's tpu.iota only produces ints) then cast."""
    iota = lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)
    return off_f32 + base + iota


def _visible(q_off, k_off, q_base, k_base, shape, q_dim, window=None):
    """``q_pos >= k_pos`` over a score tile whose q positions run along
    ``q_dim`` (0: scores as ``q k^T``, 1: transposed, ``k q^T``); with a
    ``window`` (static) ``0 <= q_pos - k_pos < window``."""
    q_pos = _pos(q_off, q_base, shape, q_dim)
    k_pos = _pos(k_off, k_base, shape, 1 - q_dim)
    if window is None:
        return q_pos >= k_pos
    ahead = q_pos - k_pos
    return (ahead >= 0) & (ahead < window)


def _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k):
    """Traced count of k blocks a causal q tile can see: blocks entirely in
    the tile's future are excluded from the loop outright (shared by the
    forward and dq kernels — they must agree on visited blocks)."""
    max_q_pos = q_off + (qi + 1) * block_q - 1
    eff = jnp.floor((max_q_pos - k_off) / block_k) + 1
    return jnp.clip(eff, 0, num_k).astype(jnp.int32)


def _window_first_k(q_off, k_off, qi, block_q, block_k, num_k, window):
    """Traced index of the first k block a q tile's window still touches:
    the block that holds ``q_tile_first - window + 1``. Blocks before it lie
    wholly behind the window and are never loaded (shared by the forward and
    dq kernels, like :func:`_causal_num_k`)."""
    far_edge = q_off + qi * block_q - (window - 1)
    first = jnp.floor((far_edge - k_off) / block_k)
    return jnp.clip(first, 0, num_k).astype(jnp.int32)


def _window_num_q(q_off, k_off, kj, block_q, block_k, num_q, window):
    """Traced count of q tiles up to the last one that still sees a k tile
    through the window: the tile that holds ``k_tile_last + window - 1``
    (the dk/dv kernel's upper bound)."""
    last_q_pos = k_off + (kj + 1) * block_k - 1 + (window - 1)
    eff = jnp.floor((last_q_pos - q_off) / block_q) + 1
    return jnp.clip(eff, 0, num_q).astype(jnp.int32)


BLOCK_EDGES = ("le", "lt")  # b(k) <= b(q), b(k) < b(q)


def _behind(block_mask) -> int:
    """How far the last key a query tile sees lies behind the tile's last
    position, where the tile ends on a block's end: 0 keys under ``"le"``
    (the causal bound), ``G`` under ``"lt"``."""
    return 0 if block_mask[1] == "le" else block_mask[0]


def _visible_blocks(q_base, k_base, shape, q_dim, block_mask):
    """``b(k) <= b(q)`` (``"le"``) or ``b(k) < b(q)`` (``"lt"``), ``b(i) = i
    // G``, over a score tile laid out as :func:`_visible`'s: the last key a
    query sees, ``G b(q) + G - 1`` or ``G b(q) - 1``, is computed along the
    tile's q axis alone (a row or a column of the tile, not the tile) and
    compared with the keys' positions along the other. Positions are fp32
    (exact below 2^24); ``floor((q + 0.5) / G)`` is ``b(q)`` for whatever
    ``G`` up to 2^22 positions, an inexact reciprocal included."""
    group, edge = block_mask
    q_shape = tuple(n if d == q_dim else 1 for d, n in enumerate(shape))
    k_shape = tuple(1 if d == q_dim else n for d, n in enumerate(shape))
    q_pos = (q_base + lax.broadcasted_iota(jnp.int32, q_shape, q_dim)
             ).astype(jnp.float32)
    k_pos = (k_base + lax.broadcasted_iota(jnp.int32, k_shape, 1 - q_dim)
             ).astype(jnp.float32)
    first = jnp.floor((q_pos + 0.5) * (1.0 / group)) * group
    return k_pos <= first + (group - 1 if edge == "le" else -1)


def _blocks_num_k(qi, block_q, block_k, num_k, block_mask):
    """:func:`_causal_num_k` under a block mask (no offsets, ``G`` divides
    the tiles): the k blocks up to the one that holds the last key the q
    tile's last block sees."""
    last = (qi + 1) * block_q - 1 - _behind(block_mask)
    return jnp.clip(last // block_k + 1, 0, num_k).astype(jnp.int32)


def _blocks_first_q(kj, block_q, block_k, num_q, block_mask):
    """The first q tile one of whose blocks sees the k tile's first key
    (the dk/dv kernel's lower bound under a block mask)."""
    first = (kj * block_k + _behind(block_mask)) // block_q
    return jnp.clip(first, 0, num_q).astype(jnp.int32)


def _scale_operand(x, sm_scale: float):
    """``(x * sm_scale, True)`` where that is exact: a power of two (heads
    of 64: 0.125) multiplies an operand of any float dtype without rounding,
    so the scale goes onto the [block, d] operand once a tile instead of onto
    the [block, block] scores every block. ``(x, False)`` otherwise: the
    scores take it in fp32, never a re-rounded operand."""
    if math.frexp(sm_scale)[0] != 0.5:
        return x, False
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype), True


def _safe_lse(lse):
    """A fully-masked row has lse = NEG_INF, and exp(s - lse) would
    overflow. -NEG_INF instead makes every exp(s - lse) exactly 0: such rows
    get zero gradients from one select a row, none over the tile."""
    return jnp.where(lse > NEG_INF / 2, lse, -NEG_INF)


def _fwd_body(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
              block_q: int, block_k: int, causal: bool, sm_scale: float,
              kv_len: int, window: Optional[int] = None,
              block_mask: Optional[tuple] = None):
    """One q tile against the k blocks it sees, on transposed scores
    ``k q^T`` [block_k, block_q]: a q row's statistics then lie along lanes
    ([1, block_q], four registers where a [block_q, 1] column takes 64), the
    reductions run down sublanes, ``lse`` leaves as the row it is stored as,
    and ``v^T p^T`` [d, block_q] streams the narrow operand past the
    probabilities instead of filling half the MXU's columns with ``d``."""
    qi = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    q, scaled = _scale_operand(q_ref[0], sm_scale)  # [block_q, d]

    def body(kj, carry):
        m, l, acc = carry  # [1, block_q], [1, block_q], [d_v, block_q]
        k = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = dot(k, q, NT)
        if not scaled:
            s = s * sm_scale
        if block_mask is not None:  # static, like the window
            s = jnp.where(_visible_blocks(qi * block_q, kj * block_k,
                                          s.shape, 1, block_mask), s, NEG_INF)
        elif causal:
            s = jnp.where(_visible(q_off, k_off, qi * block_q, kj * block_k,
                                   s.shape, 1, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)  # q rows fully at NEG_INF decay to ~0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + dot(v, p.astype(v.dtype), TN)
        return m_new, l, acc

    num_k = kv_len // block_k
    first_k = 0
    if window is not None:  # static: without one the body is what it was
        first_k = _window_first_k(q_off, k_off, qi, block_q, block_k, num_k,
                                  window)
    if block_mask is not None:
        num_k = _blocks_num_k(qi, block_q, block_k, num_k, block_mask)
    elif causal:
        num_k = _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k)
    m, l, acc = lax.fori_loop(
        first_k, num_k, body,
        (jnp.full((1, block_q), NEG_INF, jnp.float32),
         jnp.zeros((1, block_q), jnp.float32),
         jnp.zeros((v_ref.shape[-1], block_q), jnp.float32)))
    # a row that saw only masked scores carries m = NEG_INF and the mean of
    # the v rows it visited: it is dead, output 0 and lse = NEG_INF, like a
    # row whose every block was skipped
    live = m > NEG_INF / 2
    o = jnp.where(live, acc / jnp.maximum(l, 1e-30), 0.0)
    o_ref[0] = o.T.astype(o_ref.dtype)
    # lse rides a full-row (1, 1, Tq) block revisited across q tiles — TPU
    # lowering wants the last two block dims tiling-aligned or equal to the
    # array dims, which a (1, block_q) block is not.
    lse_ref[0, :, pl.ds(qi * block_q, block_q)] = jnp.where(
        live, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)


def _bwd_dq_body(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 corr_ref, dq_ref, *, block_q: int, block_k: int,
                 causal: bool, sm_scale: float, kv_len: int,
                 window: Optional[int] = None,
                 block_mask: Optional[tuple] = None):
    """dq for one q tile: loop k tiles, recompute p from lse, accumulate
    ``k^T ds^T`` [d, block_q], all on transposed scores like the forward.
    ``corr`` is (dlse - delta) precomputed on host-side JAX; it and ``lse``
    broadcast as the rows they are stored as. ds goes to the MXU unscaled
    and the fp32 sum takes ``sm_scale`` once."""
    qi = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    q, scaled = _scale_operand(q_ref[0], sm_scale)
    do = do_ref[0]
    lse = _safe_lse(lse_ref[0, :, pl.ds(qi * block_q, block_q)])
    corr = corr_ref[0, :, pl.ds(qi * block_q, block_q)]  # [1, block_q]

    def body(kj, dq):
        k = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = dot(k, q, NT)
        if not scaled:
            s = s * sm_scale
        p = jnp.exp(s - lse)
        if block_mask is not None:
            p = jnp.where(_visible_blocks(qi * block_q, kj * block_k,
                                          s.shape, 1, block_mask), p, 0.0)
        elif causal:
            p = jnp.where(_visible(q_off, k_off, qi * block_q, kj * block_k,
                                   s.shape, 1, window), p, 0.0)
        ds = p * (dot(v, do, NT) + corr)
        return dq + dot(k, ds.astype(k.dtype), TN)

    num_k = kv_len // block_k
    first_k = 0
    if window is not None:
        first_k = _window_first_k(q_off, k_off, qi, block_q, block_k, num_k,
                                  window)
    if block_mask is not None:
        num_k = _blocks_num_k(qi, block_q, block_k, num_k, block_mask)
    elif causal:
        num_k = _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k)
    dq = lax.fori_loop(first_k, num_k, body,
                       jnp.zeros((q.shape[-1], block_q), jnp.float32))
    dq_ref[0] = (dq * sm_scale).T.astype(dq_ref.dtype)


def _bwd_dkv_body(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                  corr_ref, dk_ref, dv_ref, *, block_q: int, block_k: int,
                  causal: bool, sm_scale: float, q_len: int,
                  window: Optional[int] = None,
                  block_mask: Optional[tuple] = None):
    """dk/dv for one k tile: loop q tiles (starting past fully-causal-masked
    ones), recompute p, accumulate p^T @ do and ds^T @ q.

    Neither accumulation transposes a score tile. Narrow heads take scores
    as ``q k^T`` and sum ``do^T p`` and ``q^T ds`` [d, block_k]: the head
    width streams past the probabilities (``lse``/``corr`` turn into columns
    once a block, a row of block_q values). Wider heads fill the MXU's
    columns themselves: scores transposed, ``k q^T``, the sums plain
    ``p^T do`` and ``ds^T q`` [block_k, d], ``lse``/``corr`` rows as
    stored. Which is faster where: ``PERF.md`` §6, PR 27."""
    kj = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    k, scaled = _scale_operand(k_ref[0], sm_scale)  # [block_k, d]
    v = v_ref[0]
    narrow = k.shape[-1] <= _NARROW_HEAD

    def summed(probs, rows):
        """``probs^T @ rows`` over the q positions, as the sums lie:
        [d, block_k] for narrow heads, [block_k, d] otherwise."""
        return dot(rows, probs, TN) if narrow else dot(probs, rows, NN)

    def zeros(d):
        return jnp.zeros((d, block_k) if narrow else (block_k, d),
                         jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = _safe_lse(lse_ref[0, :, pl.ds(i * block_q, block_q)])
        corr = corr_ref[0, :, pl.ds(i * block_q, block_q)]  # [1, block_q]
        if narrow:  # scores [block_q, block_k]
            lse, corr = lse[0][:, None], corr[0][:, None]
            s, dp = dot(q, k, NT), dot(do, v, NT)
        else:       # scores [block_k, block_q]
            s, dp = dot(k, q, NT), dot(v, do, NT)
        if not scaled:
            s = s * sm_scale
        p = jnp.exp(s - lse)
        if block_mask is not None:
            p = jnp.where(_visible_blocks(i * block_q, kj * block_k, s.shape,
                                          0 if narrow else 1, block_mask),
                          p, 0.0)
        elif causal:
            p = jnp.where(_visible(q_off, k_off, i * block_q, kj * block_k,
                                   s.shape, 0 if narrow else 1, window),
                          p, 0.0)
        ds = p * (dp + corr)
        return (dk + summed(ds.astype(q.dtype), q),
                dv + summed(p.astype(do.dtype), do))

    num_q = q_len // block_q
    start = 0
    if block_mask is not None:
        start = _blocks_first_q(kj, block_q, block_k, num_q, block_mask)
    elif causal:
        # first q tile whose max q position reaches this k tile's start
        min_k_pos = k_off + kj * block_k
        s0 = jnp.floor((min_k_pos - q_off) / block_q)
        start = jnp.clip(s0, 0, num_q).astype(jnp.int32)
    if window is not None:
        num_q = _window_num_q(q_off, k_off, kj, block_q, block_k, num_q,
                              window)
    dk, dv = lax.fori_loop(start, num_q, body,
                           (zeros(k.shape[-1]), zeros(v.shape[-1])))
    dk = dk * sm_scale
    if narrow:
        dk, dv = dk.T, dv.T
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# The kernel functions proper are thin: a call's name in the compiled text
# and in a device trace is that of the ``*_kernel`` function it was traced
# through (the Mosaic bytecode carries the frames), and a windowed call has a
# name of its own, as has one under a block mask and one whose values are
# not as wide as its queries and keys (``latent``). A trace then tells
# window, block-diffusion and latent layers from causal ones, and a reader
# that costs ``_fwd_kernel`` at the causal pair count and one head width
# never meets their calls. The bodies above are shared; ``window`` and
# ``block_mask`` are static in them, and the widths are the blocks' own.
def _fwd_kernel(*refs, **static):
    _fwd_body(*refs, **static)


def _bwd_dq_kernel(*refs, **static):
    _bwd_dq_body(*refs, **static)


def _bwd_dkv_kernel(*refs, **static):
    _bwd_dkv_body(*refs, **static)


def _fwd_window_kernel(*refs, **static):
    _fwd_body(*refs, **static)


def _bwd_dq_window_kernel(*refs, **static):
    _bwd_dq_body(*refs, **static)


def _bwd_dkv_window_kernel(*refs, **static):
    _bwd_dkv_body(*refs, **static)


def _fwd_blockdiff_kernel(*refs, **static):
    _fwd_body(*refs, **static)


def _bwd_dq_blockdiff_kernel(*refs, **static):
    _bwd_dq_body(*refs, **static)


def _bwd_dkv_blockdiff_kernel(*refs, **static):
    _bwd_dkv_body(*refs, **static)


def _fwd_latent_kernel(*refs, **static):
    _fwd_body(*refs, **static)


def _bwd_dq_latent_kernel(*refs, **static):
    _bwd_dq_body(*refs, **static)


def _bwd_dkv_latent_kernel(*refs, **static):
    _bwd_dkv_body(*refs, **static)


def _bh_first(x):  # [B, T, H, D] -> [B*H, T, D]
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _head_spec(rows: int, d: int, tiled: bool, group: int = 1):
    """The ``(1, rows, d)`` block of a grid row's head in a ``[B * heads, T,
    d]`` array (:func:`_bh_first`). A grid row is one batch element's one
    query head, ``bh = b * H + j``; the grid's second index counts blocks of
    ``rows`` positions where ``tiled``, else the block is the head's whole
    length. ``group``: the array is k or v at its own ``Hkv = H / group``
    heads, and query head ``j`` reads key head ``j // group``, row ``b * Hkv
    + j // group = bh // group``. Consecutive grid rows of a group ask for
    the same block, which the pipeline then does not fetch again: a key head
    is read once for its whole group. With equal heads the map computes
    nothing, the call those models always made."""
    return pl.BlockSpec(
        (1, rows, d),
        lambda bh, t: (bh if group == 1 else lax.div(bh, group),
                       t if tiled else 0, 0))


def _bh_last(x, batch: int):  # [B*H, T, D] -> [B, T, H, D]
    _, t, d = x.shape
    return x.reshape(batch, -1, t, d).transpose(0, 2, 1, 3)


def _sum_groups(x, group: int):
    """dk or dv as the dk/dv kernel wrote it, one ``[Tk, d]`` a query head
    (``[B * H, Tk, d]``), as the key heads' ``[B * H / group, Tk, d]``: one
    float32 sum over each group's ``group`` neighbouring rows."""
    if group == 1:
        return x
    _, t, d = x.shape
    return jnp.sum(x.reshape(-1, group, t, d), axis=1,
                   dtype=jnp.float32).astype(x.dtype)


_DEFAULT_VMEM_BLOCKS = 10 << 20  # what the compiler's own limit is left to


def _vmem_params(*blocks):
    """Room in VMEM for a call whose ``blocks`` ((shape, dtype) each, held
    twice by the pipeline) outgrow the compiler's default scoped limit of
    16 MiB: a kernel keeps one (batch, head)'s whole k and v (dk/dv: q and
    do) resident, 4 MiB each at 16 384 positions of 128. A block lies in
    VMEM with its minor dimension in whole registers of 128 lanes, so a head
    of 64 takes the room of one of 128 there (16 384 positions of 64 asked
    for 16.75 MiB under the default). None up to 8192 positions of 128 or
    of 64, the shapes that compile under the default: their calls are what
    they were."""
    held = 2 * sum(math.prod(shape[:-1]) * -(-shape[-1] // 128) * 128
                   * jnp.dtype(dtype).itemsize for shape, dtype in blocks)
    if held <= _DEFAULT_VMEM_BLOCKS:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(int(1.25 * held) + (8 << 20), 100 << 20))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
           interpret, window=None, block_mask=None):
    o, lse, _ = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                           block_q, block_k, interpret, window, block_mask)
    return o, lse


def _kernel(plain, windowed, blockdiff, latent, window, block_mask, widths,
            **static):
    """The kernel function of a call: ``plain`` as it always was, with a
    window ``windowed``, under a block mask ``blockdiff``, and ``latent``
    where ``widths`` (of q/k, of v) differ under the plain causal or full
    mask: the same body under a name of its own."""
    if block_mask is not None:
        return functools.partial(blockdiff, block_mask=block_mask, **static)
    if window is not None:
        return functools.partial(windowed, window=window, **static)
    return functools.partial(latent if widths[0] != widths[1] else plain,
                             **static)


# The three calls proper, each under ``jax.jit`` and reached through
# ``kernel_call.on_this_platform``: a kernel's body is traced once a
# signature (shapes, dtypes and the static values below) a process, however
# many layers call it, and Mosaic or interpret mode is the choice of the
# platform lowered for. They take and return ``[B * heads, T, D]``
# (:func:`_bh_first`); a group's size is read from the heads of q and k.
_STATIC = ("causal", "sm_scale", "block_q", "block_k", "window", "block_mask",
           "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_call(q_off, k_off, qb, kb, vb, *, causal, sm_scale, block_q,
              block_k, window, block_mask, interpret):
    bh, tq, d = qb.shape
    tk, dv = kb.shape[1], vb.shape[-1]
    group = bh // kb.shape[0]  # k and v at their own heads: see _head_spec
    return pl.pallas_call(
        _kernel(_fwd_kernel, _fwd_window_kernel, _fwd_blockdiff_kernel,
                _fwd_latent_kernel, window, block_mask, (d, dv),
                block_q=block_q, block_k=block_k, causal=causal,
                sm_scale=sm_scale, kv_len=tk),
        grid=(bh, tq // block_q),
        in_specs=[
            scalar_spec(), scalar_spec(),
            _head_spec(block_q, d, tiled=True),
            _head_spec(tk, d, tiled=False, group=group),
            _head_spec(tk, dv, tiled=False, group=group),
        ],
        out_specs=[
            _head_spec(block_q, dv, tiled=True),
            _head_spec(1, tq, tiled=False),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), qb.dtype),
            jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32),
        ],
        compiler_params=_vmem_params(
            ((block_q, d), qb.dtype), ((tk, d), kb.dtype),
            ((tk, dv), vb.dtype), ((block_q, dv), qb.dtype),
            ((1, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dq_call(q_off, k_off, qb, kb, vb, dob, lse, corr, *, causal,
                 sm_scale, block_q, block_k, window, block_mask, interpret):
    bh, tq, d = qb.shape
    tk, dv = kb.shape[1], vb.shape[-1]
    group = bh // kb.shape[0]
    row = _head_spec(1, tq, tiled=False)  # lse, corr
    return pl.pallas_call(
        _kernel(_bwd_dq_kernel, _bwd_dq_window_kernel,
                _bwd_dq_blockdiff_kernel, _bwd_dq_latent_kernel, window,
                block_mask, (d, dv), block_q=block_q, block_k=block_k,
                causal=causal, sm_scale=sm_scale, kv_len=tk),
        grid=(bh, tq // block_q),
        in_specs=[
            scalar_spec(), scalar_spec(),
            _head_spec(block_q, d, tiled=True),
            _head_spec(tk, d, tiled=False, group=group),
            _head_spec(tk, dv, tiled=False, group=group),
            _head_spec(block_q, dv, tiled=True),
            row, row,
        ],
        out_specs=_head_spec(block_q, d, tiled=True),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), qb.dtype),
        compiler_params=_vmem_params(
            ((2 * block_q, d), qb.dtype), ((tk, d), kb.dtype),
            ((tk, dv), vb.dtype), ((block_q, dv), qb.dtype),
            ((2, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb, dob, lse, corr)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dkv_call(q_off, k_off, qb, kb, vb, dob, lse, corr, *, causal,
                  sm_scale, block_q, block_k, window, block_mask, interpret):
    """One (batch, query head) a grid row here too, q and do of that head
    resident: a key head's tile is read by each row of its group, which
    writes a dk and dv of its own."""
    bh, tq, d = qb.shape
    tk, dv = kb.shape[1], vb.shape[-1]
    group = bh // kb.shape[0]
    row = _head_spec(1, tq, tiled=False)
    return pl.pallas_call(
        _kernel(_bwd_dkv_kernel, _bwd_dkv_window_kernel,
                _bwd_dkv_blockdiff_kernel, _bwd_dkv_latent_kernel, window,
                block_mask, (d, dv), block_q=block_q, block_k=block_k,
                causal=causal, sm_scale=sm_scale, q_len=tq),
        grid=(bh, tk // block_k),
        in_specs=[
            scalar_spec(), scalar_spec(),
            _head_spec(tq, d, tiled=False),
            _head_spec(block_k, d, tiled=True, group=group),
            _head_spec(block_k, dv, tiled=True, group=group),
            _head_spec(tq, dv, tiled=False),
            row, row,
        ],
        out_specs=[
            _head_spec(block_k, d, tiled=True),
            _head_spec(block_k, dv, tiled=True),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), kb.dtype),
            jax.ShapeDtypeStruct((bh, tk, dv), vb.dtype),
        ],
        compiler_params=_vmem_params(
            ((tq, d), qb.dtype), ((2 * block_k, d), kb.dtype),
            ((2 * block_k, dv), vb.dtype), ((tq, dv), qb.dtype),
            ((2, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb, dob, lse, corr)


def _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
               interpret, window=None, block_mask=None):
    b, tq, h, _ = q.shape
    with _kernel_io():
        qb, kb, vb = _bh_first(q), _bh_first(k), _bh_first(v)
    o, lse = on_this_platform(
        functools.partial(_fwd_call, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, window=window,
                          block_mask=block_mask),
        q_off, k_off, qb, kb, vb, interpret=interpret)
    with _kernel_io():
        o_out = checkpoint_name(_bh_last(o, b), FLASH_RESIDUALS[0])
        lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
        lse_out = lse.reshape(b, h, tq)
    return o_out, lse_out, (q, k, v, o_out, lse, q_off, k_off)


def _flash_fwd_vjp(q, k, v, q_off, k_off, causal, sm_scale, block_q,
                   block_k, interpret, window=None, block_mask=None):
    o, lse_out, res = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                                 block_q, block_k, interpret, window,
                                 block_mask)
    return (o, lse_out), res


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, window,
               block_mask, res, cots):
    q, k, v, o, lse, q_off, k_off = res
    do, dlse = cots
    b, tq, h, _ = q.shape
    group = h // k.shape[2]
    with _kernel_io():
        dob = _bh_first(do.astype(q.dtype))
        ob = _bh_first(o)
        # delta_i = sum_j do_ij o_ij;  ds = p * (dp + dlse - delta) * scale
        delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                        axis=-1)  # [BH, Tq]
        # dlse arrives [B, H, Tq], which is (B*H, Tq)-contiguous already
        corr = (dlse.reshape(b * h, tq).astype(jnp.float32) - delta
                if dlse is not None else -delta)
        corr = corr.reshape(b * h, 1, tq)  # full-row blocks, like lse
        args = (q_off, k_off, _bh_first(q), _bh_first(k), _bh_first(v), dob,
                lse, corr)
    static = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, window=window, block_mask=block_mask)
    dq = on_this_platform(functools.partial(_bwd_dq_call, **static), *args,
                          interpret=interpret)
    dk, dvv = on_this_platform(functools.partial(_bwd_dkv_call, **static),
                               *args, interpret=interpret)
    with _kernel_io():
        return (_bh_last(dq, b), _bh_last(_sum_groups(dk, group), b),
                _bh_last(_sum_groups(dvv, group), b),
                jnp.zeros_like(q_off), jnp.zeros_like(k_off))


_flash.defvjp(_flash_fwd_vjp, _flash_bwd)


def _pick_block(t: int, preferred: int) -> int:
    b = min(preferred, t)
    while t % b:
        b -= 1  # powers of two hit immediately
    if b < min(128, preferred, t):
        # a degenerate auto-shrunk divisor (prime/odd-factor T) would
        # compile into a pathologically fine-grained grid; fail loudly.
        # Explicitly requested small blocks (preferred <= b) stay allowed.
        raise ValueError(
            f"sequence length {t} has no block divisor >= 128; pad the "
            f"sequence (largest divisor found: {b})")
    return b


def block_plan(tq: int, tk: int, block_q: int, block_k: int, causal: bool,
               q_offset: int = 0, k_offset: int = 0,
               window: Optional[int] = None,
               block_mask: Optional[tuple] = None) -> dict:
    """Block visits of one (batch, head), the same for all three kernels:
    every score of an ``interior`` block is visible, the diagonal crosses a
    ``diagonal`` block (half its work is masked away), ``skipped`` blocks
    lie wholly in the future and are never loaded. Pure arithmetic on
    static values, the kernels' own bounds. The kernels run one masked body
    over interior and diagonal blocks alike (``PERF.md`` §6, PR 27).

    With a ``window`` two kinds more: a ``window_edge`` block is crossed by
    the window's far edge alone (one the causal edge crosses too counts as
    ``diagonal``), a ``skipped_behind`` block lies wholly behind the window
    and is never loaded either. The five counts sum to the grid.

    Under a ``block_mask`` the three kinds of the causal plan, by the mask's
    own edge: ``interior`` where every key's block is visible to every
    query's, ``diagonal`` where the edge crosses, ``skipped`` where no pair
    is visible (never loaded); they sum to the grid."""
    num_q, num_k = tq // block_q, tk // block_k
    if block_mask is not None:
        return _blocks_block_plan(num_q, num_k, block_q, block_k, block_mask)
    if window is not None:
        return _window_block_plan(num_q, num_k, block_q, block_k,
                                  q_offset - k_offset, window)
    if not causal:
        return {"interior": num_q * num_k, "diagonal": 0, "skipped": 0}
    interior = seen = 0
    for qi in range(num_q):
        first = q_offset + qi * block_q - k_offset   # relative to k[0]
        n_seen = min(max((first + block_q - 1) // block_k + 1, 0), num_k)
        interior += min(max((first + 1) // block_k, 0), n_seen)
        seen += n_seen
    return {"interior": interior, "diagonal": seen - interior,
            "skipped": num_q * num_k - seen}


def _window_block_plan(num_q: int, num_k: int, block_q: int, block_k: int,
                       ahead: int, window: int) -> dict:
    """:func:`block_plan` under ``0 <= q_pos - k_pos < window``, a block at
    a time from the least and the largest ``q_pos - k_pos`` it holds
    (``ahead``: the first query's position less the first key's)."""
    plan = dict.fromkeys(("interior", "diagonal", "window_edge", "skipped",
                          "skipped_behind"), 0)
    for qi in range(num_q):
        for kj in range(num_k):
            least = ahead + qi * block_q - ((kj + 1) * block_k - 1)
            largest = ahead + (qi + 1) * block_q - 1 - kj * block_k
            if largest < 0:
                kind = "skipped"
            elif least >= window:
                kind = "skipped_behind"
            elif least < 0:
                kind = "diagonal"
            elif largest >= window:
                kind = "window_edge"
            else:
                kind = "interior"
            plan[kind] += 1
    return plan


def _blocks_block_plan(num_q: int, num_k: int, block_q: int, block_k: int,
                       block_mask: tuple) -> dict:
    """:func:`block_plan` under a block mask, a tile at a time from the last
    key its first and its last query see."""
    group, edge = block_mask
    shift = group - 1 if edge == "le" else -1
    plan = dict.fromkeys(("interior", "diagonal", "skipped"), 0)
    for qi in range(num_q):
        least = qi * block_q // group * group + shift
        largest = ((qi + 1) * block_q - 1) // group * group + shift
        for kj in range(num_k):
            if kj * block_k > largest:
                kind = "skipped"
            elif (kj + 1) * block_k - 1 <= least:
                kind = "interior"
            else:
                kind = "diagonal"
            plan[kind] += 1
    return plan


WINDOW_KIND = "window_"  # a window call's blocks, in the counter below
BLOCKDIFF_KIND = "blockdiff_"  # and a call's under a block mask
LATENT_KIND = "latent_"  # and a call's whose values are narrower than its keys


def _noised_key_tiles(seq: int, block_q: int, block_k: int) -> int:
    """Tiles of the two quadrants of the ``[2 seq, 2 seq]`` grid whose keys
    are the noised stream's."""
    return 2 * (seq // block_q) * (seq // block_k)


def blockdiff_block_plan(seq: int, block_q: int, block_k: int,
                         group: int) -> dict:
    """Tiles of the ``[2 seq, 2 seq]`` grid of one (batch, head) of
    :func:`blockdiff_attention`, by what its two kernel calls do with them:
    the clean queries' ``"le"`` call and the noised queries' ``"lt"`` call
    over the clean keys (``interior``, ``diagonal``, ``skipped`` of
    :func:`block_plan`, summed), and ``noised_keys``: the two quadrants whose
    keys are the noised stream's, which no kernel loads (clean queries see
    none of them; a noised query sees its own block's ``group`` keys, through
    :func:`block_diagonal_attention`). The four counts sum to the grid."""
    plan = {"noised_keys": _noised_key_tiles(seq, block_q, block_k)}
    for edge in BLOCK_EDGES:
        for kind, n in block_plan(seq, seq, block_q, block_k, True,
                                  block_mask=(group, edge)).items():
            plan[kind] = plan.get(kind, 0) + n
    return plan


def _count_block_visits(plan: dict, batch_heads: int, prefix: str = ""):
    """Monitoring, at trace time like ``collectives._count_trace``: the
    blocks of each kind in what was just traced. A windowed call counts
    under kinds of its own (``window_interior`` ... ``window_edge`` ...
    ``window_skipped_behind``): the share of its grid it never loads is then
    read apart from the causal calls'; so does a call under a block mask
    (``blockdiff_interior``, ``blockdiff_diagonal``, ``blockdiff_skipped``,
    and ``blockdiff_noised_keys`` from :func:`blockdiff_attention`) and one
    of unequal widths (``latent_interior``, ``latent_diagonal``,
    ``latent_skipped``)."""
    from horovod_tpu.metrics.registry import get_registry
    for kind, visits in plan.items():
        if prefix and not kind.startswith(prefix):
            kind = prefix + kind
        get_registry().counter(
            "hvd_flash_block_visits",
            "flash-attention block visits traced, by kind of block",
            kind=kind).inc(visits * batch_heads)


def _count_call(group: int):
    """Monitoring, at trace time beside :func:`_count_block_visits`: one
    count a :func:`flash_attention` call traced, by the query heads a key
    head serves (1: as many key heads as query heads)."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_flash_calls_total",
        "flash-attention calls traced, by query heads to a key head",
        kv_group=str(group)).inc()


def _count_latent_call(qk_dim: int, v_dim: int):
    """Beside :func:`_count_call`: one count a traced call whose values are
    not as wide as its queries and keys, by the two widths."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_latent_calls_total",
        "flash-attention calls traced with q/k of one width and v of "
        "another (latent attention)",
        qk_dim=str(qk_dim), v_dim=str(v_dim)).inc()


def _static_offset(offset) -> Optional[int]:
    """The offset as a Python int, or None where it is traced."""
    if offset is None:
        return 0
    if isinstance(offset, jax.core.Tracer):
        return None
    return int(offset)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    q_offset=None, k_offset=None,
                    return_lse: bool = False,
                    window: Optional[int] = None,
                    block_mask: Optional[Tuple[int, str]] = None):
    """softmax(QK^T)V without materializing the score matrix.

    q: [B, Tq, H, D]; k/v: [B, Tk, Hkv, D(v)], ``H`` a multiple of ``Hkv``
    (grouped-query attention: query head ``j`` on key head ``j // (H /
    Hkv)``). ``Dv`` need not be ``D`` (latent attention: 192 and 128): the
    output and ``dv`` are ``Dv`` wide, the default scale is ``D ** -0.5``,
    and the kernels run under ``_fwd_latent_kernel``, ... with their blocks
    counted under ``latent_*`` kinds and ``hvd_latent_calls_total``. k and v
    are handed to the kernels at their own heads: the index
    maps send a group's query heads to one key head, which a kernel then
    fetches once a group, and nothing repeats them in HBM; dk and dv are
    written a query head and summed over each group in float32
    (``hvd_flash_calls_total{kv_group}`` counts the calls traced). Whatever
    the head width, every array is transposed to ``[B * H, T, D]`` around a
    call: the TPU compiler keeps the activations between the matmuls with
    the positions in lanes, so an operand crosses to the kernels' row-major
    blocks once whichever way the heads lie, and these transposes ride in
    the neighbouring fusions (``PERF.md`` §6, PR 41: reading ``[B, T, H *
    D]`` in place was built and compiled to more copies, not fewer). Block
    sizes shrink to divisors of the sequence lengths automatically (static
    shapes are the XLA contract anyway). ``q_offset``/``k_offset`` are global
    sequence positions of element 0 (traced scalars allowed) for causal
    masking of sequence-sharded blocks. ``return_lse=True`` also returns the
    per-row
    log-sum-exp, shaped [B, H, Tq], for online-softmax merging; both
    outputs are differentiable. ``interpret=None`` leaves Mosaic or interpret
    mode to the platform the program is lowered for (``ops/kernel_call.py``:
    the same call runs in CPU tests, and a compile for a described chip holds
    the kernels); a bool forces one.

    ``window=W`` (static, causal only) narrows the mask to
    ``0 <= q_pos - k_pos < W`` in global positions: a query sees ``W`` keys,
    its own among them. The kernels then start at the first k block the
    window's far edge touches and the dk/dv kernel ends at the last q tile
    that still sees its k tile (:func:`block_plan` counts the kinds), under
    kernel names of their own (``_fwd_window_kernel``, ...). ``window=None``
    is the program without one.

    ``block_mask=(G, edge)`` (static, causal only, no window and no offsets)
    rounds the causal edge to blocks of ``G`` positions, ``b(i) = i // G``:
    ``"le"`` keeps ``b(k) <= b(q)``, ``"lt"`` keeps ``b(k) < b(q)`` (a query
    of block 0 then sees no key: output 0, ``lse = NEG_INF``). ``G`` divides
    both tiles. The kernels run under ``_fwd_blockdiff_kernel``, ...;
    ``(1, "le")`` is the causal mask. ``None`` is the program without one.
    """
    b, tq, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    window = _checked_window(window, causal)
    block_mask = _checked_block_mask(
        block_mask, causal, window, q_offset is None and k_offset is None)
    _count_call(_kv_group(q, k, v))
    latent = d != v.shape[-1] and window is None and block_mask is None
    if latent:
        _count_latent_call(d, v.shape[-1])
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(k.shape[1], block_k)
    if block_mask is not None and (block_q % block_mask[0]
                                   or block_k % block_mask[0]):
        raise ValueError(
            f"blocks of {block_mask[0]} positions do not divide the tiles "
            f"({block_q} queries, {block_k} keys) of {tq} x {k.shape[1]}")
    q_off = (jnp.zeros((1,), jnp.float32) if q_offset is None
             else jnp.asarray(q_offset, jnp.float32).reshape(1))
    k_off = (jnp.zeros((1,), jnp.float32) if k_offset is None
             else jnp.asarray(k_offset, jnp.float32).reshape(1))
    offsets = _static_offset(q_offset), _static_offset(k_offset)
    if None not in offsets:
        _count_block_visits(
            block_plan(tq, k.shape[1], block_q, block_k, causal, *offsets,
                       window=window, block_mask=block_mask), b * h,
            BLOCKDIFF_KIND if block_mask is not None else
            WINDOW_KIND if window is not None else
            LATENT_KIND if latent else "")
    o, lse = _flash(q, k, v, q_off, k_off, causal, scale, block_q, block_k,
                    interpret, window, block_mask)
    return (o, lse) if return_lse else o


def _checked_block_mask(block_mask, causal: bool, window,
                        no_offsets: bool = True) -> Optional[tuple]:
    """The block mask as a static ``(G, edge)``, or None; it rounds a causal
    edge, in positions that start at 0 on both sides."""
    if block_mask is None:
        return None
    group, edge = block_mask
    if not causal or window is not None or not no_offsets \
            or int(group) < 1 or edge not in BLOCK_EDGES:
        raise ValueError(
            f"block_mask={block_mask!r} keeps b(k) <= b(q) ('le') or b(k) < "
            f"b(q) ('lt') with b(i) = i // G, G >= 1: it needs causal=True "
            f"(got {causal}), no window (got {window}) and no q_offset or "
            f"k_offset")
    return int(group), edge


def _checked_window(window, causal: bool) -> Optional[int]:
    """The window as a static int, or None; it narrows a causal mask."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError(
            f"window={window!r} keeps 0 <= q_pos - k_pos < window: it needs "
            f"causal=True (got {causal}) and at least 1 key")
    return int(window)


# Rows of queries a causal :func:`xla_attention` call takes at a time, each
# against the keys its mask lets it see. Chosen on the chip at `gpt2s-t512`'s
# shape ([32, 512, 12, 64]) from 64 / 128 / 256: a step of 121.9 / 116.1 /
# 118.1 ms against 135.2 as one block (``PERF.md`` §6, PR 49). A multiple of
# 128 keeps the slices of k and v on lane boundaries where the compiler
# holds the positions in lanes; at 64 the copies around them cost 4.6 ms a
# step more than at 128.
XLA_CAUSAL_BLOCK = 128


def _xla_blocks(tq: int, tk: int, block: int, causal: bool,
                window: Optional[int], block_mask: Optional[tuple]):
    """``(r0, r1, k0, k1)`` of every row block of :func:`xla_attention`:
    rows ``[r0, r1)`` against keys ``[k0, k1)``, the least range that holds
    every key the mask shows one of those rows. Static arithmetic on the
    mask the call states; without a causal mask one block, all of it."""
    if not causal:
        return ((0, tq, 0, tk),)
    blocks = []
    for r0 in range(0, tq, block):
        r1 = min(r0 + block, tq)
        k0, k1 = 0, r1
        if window is not None:
            k0 = max(0, r0 - window + 1)
        if block_mask is not None:
            group, edge = block_mask
            k1 = min(tk, -(-r1 // group) * group) if edge == "le" \
                else (r1 - 1) // group * group
        blocks.append((r0, r1, k0, k1))
    return tuple(blocks)


def xla_score_plan(tq: int, tk: int, block: int, causal: bool,
                   window: Optional[int] = None,
                   block_mask: Optional[tuple] = None) -> dict:
    """Scores of one (batch, head) of an :func:`xla_attention` call in row
    blocks of ``block``: ``computed``, the entries of the products it makes,
    and ``visible``, those its mask keeps. Pure arithmetic on static values,
    like :func:`block_plan`; ``computed / visible`` is what the path pays
    for the mask's dead scores (2.0 for one block of a long causal call)."""
    computed = sum((r1 - r0) * (k1 - k0) for r0, r1, k0, k1 in
                   _xla_blocks(tq, tk, block, causal, window, block_mask))
    if not causal:
        return {"computed": computed, "visible": tq * tk}
    if block_mask is not None:
        group, edge = block_mask
        seen = [(i // group + (edge == "le")) * group for i in range(tq)]
    else:
        seen = [i + 1 if window is None else min(i + 1, window)
                for i in range(tq)]
    return {"computed": computed, "visible": sum(min(n, tk) for n in seen)}


def _count_xla_scores(plan: dict, batch_heads: int):
    """Monitoring, at trace time like :func:`_count_block_visits`: the
    scores of the :func:`xla_attention` call just traced, by kind."""
    from horovod_tpu.metrics.registry import get_registry
    for kind, scores in plan.items():
        get_registry().counter(
            "hvd_xla_attention_scores_total",
            "scores of the XLA attention calls traced: computed by the "
            "products, visible under the mask",
            kind=kind).inc(scores * batch_heads)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = False,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None,
                  block_mask: Optional[Tuple[int, str]] = None) -> jax.Array:
    """Plain XLA dot attention, the path :func:`attention` takes below the
    router's threshold.

    Same [B, T, H, D] layout and numerics contract as
    :func:`flash_attention` (matmuls in the input dtype, fp32 softmax), so
    the router can swap between them freely. ``window`` and ``block_mask``
    as :func:`flash_attention`'s.

    Under a causal mask the queries are taken in row blocks of
    ``XLA_CAUSAL_BLOCK``, each against the keys it can see and no further
    (:func:`_xla_blocks`), and the outputs are joined along ``T``: the
    scores above the diagonal, half of ``[T, T]``, are neither computed nor
    kept for the backward pass. Inside a block's range the mask is applied
    by positions, so the same terms enter every row's softmax as in one
    dense product (the masked ones were ``exp(-inf) = 0``). A call of at
    most one block, and every call without a causal mask, is that one dense
    product. :func:`xla_score_plan` counts what is computed, and
    ``hvd_xla_attention_scores_total{kind=computed|visible}`` records it at
    trace time.

    Measured on the chip (``PERF.md`` §6, PR 49; `gpt2s-t512`, 32 x 512
    tokens through twelve layers of 12 heads of 64): attention 70.5 ms of a
    135.2 ms step as one dense product, 49.3 of 116.1 in blocks of 128
    (52.7 of 118.1 at 256, 51.4 of 121.9 at 64). Where this path and the
    kernels cross has not been measured (``DEFAULT_FLASH_MIN_SEQ``).
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    window = _checked_window(window, causal)
    block_mask = _checked_block_mask(block_mask, causal, window)
    if causal and tq != tk:
        raise ValueError(
            "xla_attention supports causal only for self-attention "
            f"(Tq == Tk), got {tq} vs {tk}; use flash_attention with "
            "q_offset/k_offset for sharded causal blocks")
    _count_xla_scores(xla_score_plan(tq, tk, XLA_CAUSAL_BLOCK, causal,
                                     window, block_mask), b * h)
    blocks = _xla_blocks(tq, tk, XLA_CAUSAL_BLOCK, causal, window,
                         block_mask)
    if len(blocks) == 1:  # the one dense product, in the caller's own trace
        return _xla_block(q, k, v, scale, causal, window, block_mask, 0, 0)
    return _xla_rows(q, k, v, scale, window, block_mask, blocks)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _xla_rows(q, k, v, scale: float, window, block_mask, blocks: tuple):
    """A causal call's row blocks, joined along ``T``. Under ``jax.jit`` so
    that the layers of a model trace, differentiate and lower the blocks
    once a signature and not once a layer (four blocks a layer doubled
    `gpt2s-t512`'s trace and lowering without it, ``PERF.md`` §6, PR 49)."""
    return jnp.concatenate(
        [_xla_block(q[:, r0:r1], k[:, k0:k1], v[:, k0:k1], scale, True,
                    window, block_mask, r0, k0)
         for r0, r1, k0, k1 in blocks], axis=1)


def _xla_block(q, k, v, scale: float, causal: bool, window, block_mask,
               r0: int, k0: int) -> jax.Array:
    """The rows ``q`` (positions from ``r0``) against the keys ``k``, ``v``
    (positions from ``k0``): scores, softmax and ``p v`` of
    :func:`xla_attention`, the mask by global positions."""
    if not k.shape[1]:  # rows of block 0 under "lt" see no key: output 0
        return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)
    # Matmuls stay in the input dtype (bf16 rides the fast MXU path, same
    # as the flash kernel) with fp32 accumulation; only the softmax runs
    # in fp32.
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = r0 + jnp.arange(q.shape[1])[:, None]
        k_pos = k0 + jnp.arange(k.shape[1])[None, :]
        if block_mask is not None:  # the edge, by blocks of G positions
            group, edge = block_mask
            mask = k_pos // group <= q_pos // group if edge == "le" \
                else k_pos // group < q_pos // group
        else:
            mask = k_pos <= q_pos
        if window is not None:  # the window's far edge: a second diagonal
            mask &= q_pos - k_pos < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if block_mask is not None and block_mask[1] == "lt" \
            and r0 < block_mask[0]:  # block 0 sees no key: output 0
        p = jnp.where(jnp.any(mask, axis=-1)[None, None, :, None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_min_seq() -> int:
    """The routing crossover (elements of Tk), env-overridable."""
    from horovod_tpu.common.env_registry import env_int
    return env_int("HOROVOD_FLASH_MIN_SEQ", DEFAULT_FLASH_MIN_SEQ)


def _kv_group(q, k, v) -> int:
    """Query heads to a key head (grouped-query attention: query head ``j``
    on key head ``j // group``)."""
    group, rest = divmod(q.shape[2], k.shape[2])
    if rest or v.shape[2] != k.shape[2]:
        raise ValueError(
            f"{q.shape[2]} query heads are no multiple of {k.shape[2]} key "
            f"and {v.shape[2]} value heads")
    return group


def _repeat_kv(q, k, v):
    """The key and value heads repeated to the query heads, for the XLA
    paths, which take equal heads only; their gradient is the sum over each
    group."""
    group = _kv_group(q, k, v)
    if group == 1:
        return k, v
    return tuple(jnp.repeat(x, group, axis=2) for x in (k, v))


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False,
              sm_scale: Optional[float] = None,
              min_flash_seq: Optional[int] = None,
              window: Optional[int] = None,
              block_mask: Optional[Tuple[int, str]] = None,
              **flash_kwargs) -> jax.Array:
    """Length-routed attention: XLA dot attention below the crossover,
    the Pallas flash kernel at/above it.

    The threshold is ``DEFAULT_FLASH_MIN_SEQ`` (what has been measured on
    each side of it is said there; where the two paths cross has not been).
    Routing keys on the KV length
    (the side that grows the score matrix). Semantics-bearing flash-only
    features (``return_lse``, ``q_offset``/``k_offset``) force the flash
    path regardless of length — the XLA path cannot honor them, and
    silently dropping them would change the return contract or the causal
    mask (ring attention relies on exactly these). ``window`` (see
    :func:`flash_attention`) is part of the mask, and both paths honour it,
    as they do ``block_mask``.

    Grouped-query attention: ``k`` and ``v`` may hold fewer heads than
    ``q`` where ``q``'s are a multiple; query head ``j`` attends key head
    ``j // (Hq / Hkv)``. The flash path takes them as they are: the kernels
    read a key head once for its whole group and nothing repeats it in HBM
    (:func:`flash_attention`). The XLA path repeats the key heads to the
    query heads (their gradient is the sum over each group).
    """
    if flash_kwargs.get("return_lse") or \
            flash_kwargs.get("q_offset") is not None or \
            flash_kwargs.get("k_offset") is not None:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               window=window, block_mask=block_mask,
                               **flash_kwargs)
    threshold = min_flash_seq if min_flash_seq is not None else \
        flash_min_seq()
    if k.shape[1] < threshold:
        # flash_kwargs here can only hold tuning knobs (block sizes /
        # interpret), which have no meaning for the XLA formulation.
        with _kernel_io():
            k, v = _repeat_kv(q, k, v)
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window, block_mask=block_mask)
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           window=window, block_mask=block_mask,
                           **flash_kwargs)


def merge_attention(o_a: jax.Array, lse_a: jax.Array,
                    o_b: jax.Array, lse_b: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Exactly merge two attention partials (normalized outputs + lse) over
    disjoint key sets — the online-softmax combine ring attention runs per
    step. o: [B, T, H, Dv], lse: [B, H, T]."""
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(m > NEG_INF / 2, m, 0.0)
    wa = jnp.exp(lse_a - m_safe)
    wb = jnp.exp(lse_b - m_safe)
    denom = jnp.maximum(wa + wb, 1e-30)
    # weights arrive [B, H, T]; outputs are [B, T, H, Dv]
    fa = (wa / denom).transpose(0, 2, 1)[..., None]
    fb = (wb / denom).transpose(0, 2, 1)[..., None]
    o = o_a.astype(jnp.float32) * fa + o_b.astype(jnp.float32) * fb
    lse = jnp.where(m > NEG_INF / 2, m + jnp.log(denom), NEG_INF)
    return o.astype(o_a.dtype), lse


def block_diagonal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             group: int, sm_scale: Optional[float] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """Attention of every block of ``group`` positions on itself, both
    directions: ``b(k) == b(q)``. ``T / group`` tiles of ``group x group``
    scores, a few einsums and no kernel. q: [B, T, H, D]; k, v: [B, T, Hkv,
    D(v)] with ``H`` a multiple of ``Hkv`` (query head ``j`` on key head
    ``j // (H / Hkv)``, nothing repeated). Returns (o [B, T, H, Dv] in q's
    dtype, lse [B, H, T] float32) as ``flash_attention(return_lse=True)``
    does, for :func:`merge_attention`; matmuls in the input dtype with
    float32 accumulation, the softmax in float32."""
    b, t, h, d = q.shape
    hk = k.shape[2]
    if t % group or h % hk or v.shape[2] != hk:
        raise ValueError(
            f"{t} positions in blocks of {group}, {h} query heads over "
            f"{hk} key and {v.shape[2]} value heads")
    scale = sm_scale if sm_scale is not None else d ** -0.5
    n = t // group
    qb = q.reshape(b, n, group, hk, h // hk, d)
    kb, vb = (x.reshape(b, n, group, hk, x.shape[-1]) for x in (k, v))
    s = jnp.einsum("bnqhgd,bnkhd->bnhgqk", qb, kb,
                   preferred_element_type=jnp.float32) * scale
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bnhgqk,bnkhd->bnqhgd", p.astype(v.dtype), vb,
                   preferred_element_type=jnp.float32)
    return (o.reshape(b, t, h, v.shape[-1]).astype(q.dtype),
            lse.transpose(0, 2, 3, 1, 4).reshape(b, h, t))


def blockdiff_mask(seq: int, group: int) -> jax.Array:
    """[2 seq, 2 seq] bool, the block-diffusion mask over the rows of one
    sequence's two streams (the noised one first): a noised query sees its
    own noised block and the clean blocks before it, a clean query the
    clean blocks up to its own, nobody else a noised key."""
    block = jnp.arange(2 * seq) % seq // group
    noised = jnp.arange(2 * seq) < seq
    q_b, k_b = block[:, None], block[None, :]
    q_n, k_n = noised[:, None], noised[None, :]
    return (q_n & k_n & (k_b == q_b)) | (q_n & ~k_n & (k_b < q_b)) | \
        (~q_n & ~k_n & (k_b <= q_b))


def blockdiff_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        group: int, sm_scale: Optional[float] = None,
                        min_flash_seq: Optional[int] = None,
                        **flash_kwargs) -> jax.Array:
    """Attention of a block-diffusion training pass (BD3-LM,
    arXiv:2503.09573): the two streams of every sequence in one array.

    q: [B, 2L, H, D]; k, v: [B, 2L, Hkv, D(v)]. Rows ``:L`` are the noised
    stream ``xt``, rows ``L:`` the clean stream ``x0`` of the same ``L``
    positions, in blocks of ``group``: ``b(i) = i // group``.

        query in xt, key in xt :  visible iff b(k) == b(q)
        query in xt, key in x0 :  visible iff b(k) <  b(q)
        query in x0, key in x0 :  visible iff b(k) <= b(q)
        query in x0, key in xt :  never

    At or above the router's crossover (``L`` keys a call) this is two
    calls of the kernels over the clean keys (at their own ``Hkv`` heads:
    :func:`flash_attention` reads a key head once a group), the clean
    queries under ``block_mask=(group, "le")`` and the noised ones under
    ``(group, "lt")``, and :func:`block_diagonal_attention` of the noised
    stream on itself, merged with the second call's ``(o, lse)`` by
    :func:`merge_attention` (a noised row of block 0 has ``lse = NEG_INF``
    from the kernel and keeps its own block's result). No ``[2L, 2L]`` or
    ``[L, L]`` array exists; the noised stream's keys are never handed to a
    kernel. Below the crossover one :func:`xla_attention`-like pass under
    :func:`blockdiff_mask`. Returns [B, 2L, H, Dv].
    """
    b, rows, h, d = q.shape
    seq = rows // 2
    if rows % 2 or seq % group:
        raise ValueError(f"{rows} rows are not two streams of whole blocks "
                         f"of {group}")
    scale = sm_scale if sm_scale is not None else d ** -0.5
    threshold = min_flash_seq if min_flash_seq is not None else \
        flash_min_seq()
    if seq < threshold:
        with _kernel_io():
            kr, vr = _repeat_kv(q, k, v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(blockdiff_mask(seq, group)[None, None], s, NEG_INF)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(s, axis=-1).astype(v.dtype), vr,
                          preferred_element_type=jnp.float32).astype(q.dtype)
    flash = functools.partial(flash_attention, causal=True, sm_scale=scale,
                              **flash_kwargs)
    with _kernel_io():
        k_clean, v_clean = k[:, seq:], v[:, seq:]  # their own heads, as held
        q_clean = q[:, seq:]
    clean = flash(q_clean, k_clean, v_clean, block_mask=(group, "le"))
    with _kernel_io():
        q_noised = q[:, :seq]
    past, past_lse = flash(q_noised, k_clean, v_clean, return_lse=True,
                           block_mask=(group, "lt"))
    with attn_part_scope("attn_self_block"):
        own, own_lse = block_diagonal_attention(q[:, :seq], k[:, :seq],
                                                v[:, :seq], group, scale)
    _count_block_visits(
        {"noised_keys": _noised_key_tiles(
            seq, _pick_block(seq, flash_kwargs.get("block_q", 512)),
            _pick_block(seq, flash_kwargs.get("block_k", 512)))},
        b * h, BLOCKDIFF_KIND)
    with attn_part_scope("attn_merge"):
        noised, _ = merge_attention(past, past_lse, own, own_lse)
        return jnp.concatenate([noised, clean], axis=1)
