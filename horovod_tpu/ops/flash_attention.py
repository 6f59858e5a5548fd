"""Fused flash attention as a Pallas TPU kernel — forward AND backward.

The hot op of the transformer path (BASELINE config 3): computes
softmax(QK^T)V blockwise in VMEM with online log-sum-exp accumulation, so
the [T, T] score matrix never exists in HBM — the kernel streams K/V blocks
through the MXU and keeps the fp32 accumulators on chip.

Five design points make this the building block the rest of the framework
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

Layout: [batch, seq, heads, head_dim] in, same out; internally each
(batch, head) pair is one grid row. Pure-JAX reference semantics are tested
against in interpret mode (CPU) and the kernel compile-checks on the real
chip.
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

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free

# Short-sequence crossover for the auto-router (:func:`attention`). An
# earlier chip run, no longer on file, had plain XLA dot attention ahead of
# the Pallas kernel at seq 128 on BERT-Base (the score tiles are too small
# to fill the grid) and flash ahead from ~2k through 8k; not measured on
# today's code. Sequences shorter than this route to XLA; override with
# HOROVOD_FLASH_MIN_SEQ.
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


_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims):
    # Matmuls run in the input dtype (bf16 rides the fast MXU path; fp32
    # inputs keep full precision) and accumulate in fp32 via
    # preferred_element_type — casting inputs up to fp32 would force 3-pass
    # fp32 MXU matmuls and ~30% more step time.
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_body(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
              block_q: int, block_k: int, causal: bool, sm_scale: float,
              kv_len: int, window: Optional[int] = None):
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
        s = _dot(k, q, _NT)
        if not scaled:
            s = s * sm_scale
        if causal:
            s = jnp.where(_visible(q_off, k_off, qi * block_q, kj * block_k,
                                   s.shape, 1, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)  # q rows fully at NEG_INF decay to ~0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * alpha + _dot(v, p.astype(v.dtype), _TN)
        return m_new, l, acc

    num_k = kv_len // block_k
    first_k = 0
    if window is not None:  # static: without one the body is what it was
        first_k = _window_first_k(q_off, k_off, qi, block_q, block_k, num_k,
                                  window)
    if causal:
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
                 window: Optional[int] = None):
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
        s = _dot(k, q, _NT)
        if not scaled:
            s = s * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(_visible(q_off, k_off, qi * block_q, kj * block_k,
                                   s.shape, 1, window), p, 0.0)
        ds = p * (_dot(v, do, _NT) + corr)
        return dq + _dot(k, ds.astype(k.dtype), _TN)

    num_k = kv_len // block_k
    first_k = 0
    if window is not None:
        first_k = _window_first_k(q_off, k_off, qi, block_q, block_k, num_k,
                                  window)
    if causal:
        num_k = _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k)
    dq = lax.fori_loop(first_k, num_k, body,
                       jnp.zeros((q.shape[-1], block_q), jnp.float32))
    dq_ref[0] = (dq * sm_scale).T.astype(dq_ref.dtype)


def _bwd_dkv_body(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                  corr_ref, dk_ref, dv_ref, *, block_q: int, block_k: int,
                  causal: bool, sm_scale: float, q_len: int,
                  window: Optional[int] = None):
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
        return _dot(rows, probs, _TN) if narrow else _dot(probs, rows, _NN)

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
            s, dp = _dot(q, k, _NT), _dot(do, v, _NT)
        else:       # scores [block_k, block_q]
            s, dp = _dot(k, q, _NT), _dot(v, do, _NT)
        if not scaled:
            s = s * sm_scale
        p = jnp.exp(s - lse)
        if causal:
            p = jnp.where(_visible(q_off, k_off, i * block_q, kj * block_k,
                                   s.shape, 0 if narrow else 1, window),
                          p, 0.0)
        ds = p * (dp + corr)
        return (dk + summed(ds.astype(q.dtype), q),
                dv + summed(p.astype(do.dtype), do))

    num_q = q_len // block_q
    start = 0
    if causal:
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
# name of its own. A trace then tells window layers from causal ones, and a
# reader that costs ``_fwd_kernel`` at the causal pair count never meets a
# window call. The bodies above are shared; ``window`` is static in them.
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


def _bh_first(x):  # [B, T, H, D] -> [B*H, T, D]
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


_DEFAULT_VMEM_BLOCKS = 10 << 20  # what the compiler's own limit is left to


def _vmem_params(*blocks):
    """Room in VMEM for a call whose ``blocks`` ((shape, dtype) each, held
    twice by the pipeline) outgrow the compiler's default scoped limit of
    16 MiB: a kernel keeps one (batch, head)'s whole k and v (dk/dv: q and
    do) resident, 4 MiB each at 16 384 positions of 128. None up to 8192
    positions of 128, the shapes that compile under the default: their calls
    are what they were."""
    held = 2 * sum(math.prod(shape) * jnp.dtype(dtype).itemsize
                   for shape, dtype in blocks)
    if held <= _DEFAULT_VMEM_BLOCKS:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(int(1.25 * held) + (8 << 20), 100 << 20))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
           interpret, window=None):
    o, lse, _ = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                           block_q, block_k, interpret, window)
    return o, lse


def _kernel(plain, windowed, window, **static):
    """The kernel function of a call: ``plain`` as it always was, or with a
    window ``windowed``, the same body under its own name."""
    if window is None:
        return functools.partial(plain, **static)
    return functools.partial(windowed, window=window, **static)


def _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
               interpret, window=None):
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    qb, kb, vb = _bh_first(q), _bh_first(k), _bh_first(v)
    grid = (b * h, tq // block_q)
    kernel = _kernel(_fwd_kernel, _fwd_window_kernel, window,
                     block_q=block_q, block_k=block_k, causal=causal,
                     sm_scale=sm_scale, kv_len=tk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _scalar_spec(), _scalar_spec(),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, tk, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, tk, dv), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, i: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32),
        ],
        compiler_params=_vmem_params(
            ((block_q, d), q.dtype), ((tk, d), k.dtype), ((tk, dv), v.dtype),
            ((block_q, dv), q.dtype), ((1, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb)
    o_out = checkpoint_name(o.reshape(b, h, tq, dv).transpose(0, 2, 1, 3),
                            FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    lse_out = lse.reshape(b, h, tq)
    return o_out, lse_out, (q, k, v, o_out, lse, q_off, k_off)


def _flash_fwd_vjp(q, k, v, q_off, k_off, causal, sm_scale, block_q,
                   block_k, interpret, window=None):
    o, lse_out, res = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                                 block_q, block_k, interpret, window)
    return (o, lse_out), res


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, window, res,
               cots):
    q, k, v, o, lse, q_off, k_off = res
    do, dlse = cots
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    dob = _bh_first(do.astype(q.dtype))
    ob = _bh_first(o)
    # delta_i = sum_j do_ij o_ij;  ds = p * (dp + dlse - delta) * scale
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)  # [BH, Tq]
    # dlse arrives [B, H, Tq], which is (B*H, Tq)-contiguous already
    corr = (dlse.reshape(b * h, tq).astype(jnp.float32) - delta
            if dlse is not None else -delta)
    corr = corr.reshape(b * h, 1, tq)  # full-row blocks, like lse
    qb, kb, vb = _bh_first(q), _bh_first(k), _bh_first(v)

    dq = pl.pallas_call(
        _kernel(_bwd_dq_kernel, _bwd_dq_window_kernel, window,
                block_q=block_q, block_k=block_k, causal=causal,
                sm_scale=sm_scale, kv_len=tk),
        grid=(b * h, tq // block_q),
        in_specs=[
            _scalar_spec(), _scalar_spec(),
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, tk, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, tk, dv), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, block_q, dv), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        compiler_params=_vmem_params(
            ((2 * block_q, d), q.dtype), ((tk, d), k.dtype),
            ((tk, dv), v.dtype), ((block_q, dv), q.dtype),
            ((2, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb, dob, lse, corr)

    dk, dvv = pl.pallas_call(
        _kernel(_bwd_dkv_kernel, _bwd_dkv_window_kernel, window,
                block_q=block_q, block_k=block_k, causal=causal,
                sm_scale=sm_scale, q_len=tq),
        grid=(b * h, tk // block_k),
        in_specs=[
            _scalar_spec(), _scalar_spec(),
            pl.BlockSpec((1, tq, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, tq, dv), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, j: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, j: (bh, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, tk, dv), v.dtype),
        ],
        compiler_params=_vmem_params(
            ((tq, d), q.dtype), ((2 * block_k, d), k.dtype),
            ((2 * block_k, dv), v.dtype), ((tq, dv), q.dtype),
            ((2, tq), jnp.float32)),
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb, dob, lse, corr)

    def back(x, t):  # [BH, T, D] -> [B, T, H, D]
        return x.reshape(b, h, t, x.shape[-1]).transpose(0, 2, 1, 3)

    return (back(dq, tq), back(dk, tk), back(dvv, tk),
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
               window: Optional[int] = None) -> dict:
    """Block visits of one (batch, head), the same for all three kernels:
    every score of an ``interior`` block is visible, the diagonal crosses a
    ``diagonal`` block (half its work is masked away), ``skipped`` blocks
    lie wholly in the future and are never loaded. Pure arithmetic on
    static values, the kernels' own bounds. The kernels run one masked body
    over interior and diagonal blocks alike (``PERF.md`` §6, PR 27).

    With a ``window`` two kinds more: a ``window_edge`` block is crossed by
    the window's far edge alone (one the causal edge crosses too counts as
    ``diagonal``), a ``skipped_behind`` block lies wholly behind the window
    and is never loaded either. The five counts sum to the grid."""
    num_q, num_k = tq // block_q, tk // block_k
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


WINDOW_KIND = "window_"  # a window call's blocks, in the counter below


def _count_block_visits(plan: dict, batch_heads: int, windowed: bool):
    """Monitoring, at trace time like ``collectives._count_trace``: the
    blocks of each kind in what was just traced. A windowed call counts
    under kinds of its own (``window_interior`` ... ``window_edge`` ...
    ``window_skipped_behind``): the share of its grid it never loads is then
    read apart from the causal calls'."""
    from horovod_tpu.metrics.registry import get_registry
    for kind, visits in plan.items():
        if windowed and not kind.startswith(WINDOW_KIND):
            kind = WINDOW_KIND + kind
        get_registry().counter(
            "hvd_flash_block_visits",
            "flash-attention block visits traced, by kind of block",
            kind=kind).inc(visits * batch_heads)


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
                    window: Optional[int] = None):
    """softmax(QK^T)V without materializing the score matrix.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D(v)]. Block sizes shrink to divisors
    of the sequence lengths automatically (static shapes are the XLA
    contract anyway). ``q_offset``/``k_offset`` are global sequence
    positions of element 0 (traced scalars allowed) for causal masking of
    sequence-sharded blocks. ``return_lse=True`` also returns the per-row
    log-sum-exp, shaped [B, H, Tq], for online-softmax merging; both
    outputs are differentiable. ``interpret=None`` auto-selects interpret
    mode off-TPU so the same call runs in CPU tests.

    ``window=W`` (static, causal only) narrows the mask to
    ``0 <= q_pos - k_pos < W`` in global positions: a query sees ``W`` keys,
    its own among them. The kernels then start at the first k block the
    window's far edge touches and the dk/dv kernel ends at the last q tile
    that still sees its k tile (:func:`block_plan` counts the kinds), under
    kernel names of their own (``_fwd_window_kernel``, ...). ``window=None``
    is the program without one.
    """
    b, tq, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    window = _checked_window(window, causal)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(k.shape[1], block_k)
    q_off = (jnp.zeros((1,), jnp.float32) if q_offset is None
             else jnp.asarray(q_offset, jnp.float32).reshape(1))
    k_off = (jnp.zeros((1,), jnp.float32) if k_offset is None
             else jnp.asarray(k_offset, jnp.float32).reshape(1))
    offsets = _static_offset(q_offset), _static_offset(k_offset)
    if None not in offsets:
        _count_block_visits(
            block_plan(tq, k.shape[1], block_q, block_k, causal, *offsets,
                       window=window), b * h, window is not None)
    o, lse = _flash(q, k, v, q_off, k_off, causal, scale, block_q, block_k,
                    interpret, window)
    return (o, lse) if return_lse else o


def _checked_window(window, causal: bool) -> Optional[int]:
    """The window as a static int, or None; it narrows a causal mask."""
    if window is None:
        return None
    if not causal or int(window) < 1:
        raise ValueError(
            f"window={window!r} keeps 0 <= q_pos - k_pos < window: it needs "
            f"causal=True (got {causal}) and at least 1 key")
    return int(window)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = False,
                  sm_scale: Optional[float] = None,
                  window: Optional[int] = None) -> jax.Array:
    """Plain XLA dot attention — the short-sequence winner.

    Same [B, T, H, D] layout and numerics contract as
    :func:`flash_attention` (matmuls in the input dtype, fp32 softmax), so
    the router can swap between them freely. At short T the [T, T] score
    matrix is small enough that XLA's fused softmax beats the Pallas
    kernel's grid setup cost. ``window`` as :func:`flash_attention`'s.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
    window = _checked_window(window, causal)
    # Matmuls stay in the input dtype (bf16 rides the fast MXU path, same
    # as the flash kernel) with fp32 accumulation; only the softmax runs
    # in fp32. Upcasting the operands would cost ~4x MXU throughput and 2x
    # HBM traffic on the [B, H, T, T] scores — the short-seq regime this
    # path exists to win.
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        if tq != tk:
            raise ValueError(
                "xla_attention supports causal only for self-attention "
                f"(Tq == Tk), got {tq} vs {tk}; use flash_attention with "
                "q_offset/k_offset for sharded causal blocks")
        mask = jnp.tril(jnp.ones((tq, tk), bool))
        if window is not None:  # the window's far edge: a second diagonal
            mask &= ~jnp.tril(jnp.ones((tq, tk), bool), -window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_min_seq() -> int:
    """The routing crossover (elements of Tk), env-overridable."""
    from horovod_tpu.common.env_registry import env_int
    return env_int("HOROVOD_FLASH_MIN_SEQ", DEFAULT_FLASH_MIN_SEQ)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False,
              sm_scale: Optional[float] = None,
              min_flash_seq: Optional[int] = None,
              window: Optional[int] = None,
              **flash_kwargs) -> jax.Array:
    """Length-routed attention: XLA dot attention below the crossover,
    the Pallas flash kernel at/above it.

    A kernel built for long context has nothing to amortize on tiny score
    tiles (an earlier chip run, no longer on file, had ``use_flash=True``
    costing 16% at seq 128; not measured on today's code). This router
    keeps the long-context path without making short-sequence models pay
    for it. Routing keys on the KV length
    (the side that grows the score matrix). Semantics-bearing flash-only
    features (``return_lse``, ``q_offset``/``k_offset``) force the flash
    path regardless of length — the XLA path cannot honor them, and
    silently dropping them would change the return contract or the causal
    mask (ring attention relies on exactly these). ``window`` (see
    :func:`flash_attention`) is part of the mask, and both paths honour it.

    Grouped-query attention: ``k`` and ``v`` may hold fewer heads than
    ``q`` where ``q``'s are a multiple; query head ``j`` attends key head
    ``j // (Hq / Hkv)``. The key heads are repeated to the query heads here,
    before either path (their gradient is the sum over each group): the
    kernels take equal heads only, and reading a key head once for its whole
    group inside them is ``ROADMAP.md`` work.
    """
    if k.shape[2] != q.shape[2]:
        group, rest = divmod(q.shape[2], k.shape[2])
        if rest or v.shape[2] != k.shape[2]:
            raise ValueError(
                f"{q.shape[2]} query heads are no multiple of "
                f"{k.shape[2]} key and {v.shape[2]} value heads")
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    if flash_kwargs.get("return_lse") or \
            flash_kwargs.get("q_offset") is not None or \
            flash_kwargs.get("k_offset") is not None:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               window=window, **flash_kwargs)
    threshold = min_flash_seq if min_flash_seq is not None else \
        flash_min_seq()
    if k.shape[1] < threshold:
        # flash_kwargs here can only hold tuning knobs (block sizes /
        # interpret), which have no meaning for the XLA formulation.
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                             window=window)
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                           window=window, **flash_kwargs)


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
