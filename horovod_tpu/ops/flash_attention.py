"""Fused flash attention as a Pallas TPU kernel — forward AND backward.

The hot op of the transformer path (BASELINE config 3): computes
softmax(QK^T)V blockwise in VMEM with online log-sum-exp accumulation, so
the [T, T] score matrix never exists in HBM — the kernel streams K/V blocks
through the MXU and keeps the fp32 accumulators on chip.

Three design points make this the building block the rest of the framework
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
  loop's *traced* upper bound excludes them.
- **custom VJP**: backward is two Pallas kernels (dq gridded over q tiles,
  dk/dv gridded over k tiles) recomputing probabilities from the saved lse,
  the standard flash backward. The lse output is differentiable too
  (d lse/d s = softmax prob), so gradients flow through ring-attention
  merges.

Layout: [batch, seq, heads, head_dim] in, same out; internally each
(batch, head) pair is one grid row. Pure-JAX reference semantics are tested
against in interpret mode (CPU) and the kernel compile-checks on the real
chip.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
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


def _pos(off_f32, base, shape, dim):
    """Global positions (fp32 — exact for T < 2^24) of a tile. The iota is
    integer (TPU's tpu.iota only produces ints) then cast."""
    iota = lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)
    return off_f32 + base + iota


def _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k):
    """Traced count of k blocks a causal q tile can see: blocks entirely in
    the tile's future are excluded from the loop outright (shared by the
    forward and dq kernels — they must agree on visited blocks)."""
    max_q_pos = q_off + (qi + 1) * block_q - 1
    eff = jnp.floor((max_q_pos - k_off) / block_k) + 1
    return jnp.clip(eff, 0, num_k).astype(jnp.int32)


def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                block_q: int, block_k: int, causal: bool, sm_scale: float,
                kv_len: int):
    qi = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    # Matmuls run in the input dtype (bf16 rides the fast MXU path; fp32
    # inputs keep full precision) and accumulate in fp32 via
    # preferred_element_type — casting inputs up to fp32 would force 3-pass
    # fp32 MXU matmuls and ~30% more step time.
    q = q_ref[0]  # [block_q, d]
    d_v = v_ref.shape[-1]

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    o = jnp.zeros((block_q, d_v), jnp.float32)
    q_pos = _pos(q_off, qi * block_q, (block_q, block_k), 0)

    def body(kj, carry):
        m, l, o = carry
        k = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            k_pos = _pos(k_off, kj * block_k, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_i = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_i)
        p = jnp.exp(s - m_new)  # rows fully at NEG_INF decay to ~0
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        o = o * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, o

    num_k = kv_len // block_k
    if causal:
        num_k = _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k)
    m, l, o = lax.fori_loop(0, num_k, body, (m, l, o))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), NEG_INF)
    # lse rides a full-row (1, 1, Tq) block revisited across q tiles — TPU
    # lowering wants the last two block dims tiling-aligned or equal to the
    # array dims, which a (1, block_q) block is not.
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = lse[:, 0]


def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   corr_ref, dq_ref, *, block_q: int, block_k: int,
                   causal: bool, sm_scale: float, kv_len: int):
    """dq for one q tile: loop k tiles, recompute p from lse, accumulate
    ds @ k. ``corr`` is (dlse - delta) precomputed on host-side JAX."""
    qi = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
    corr = corr_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
    live = lse > NEG_INF / 2  # fully-masked rows produce zero grads
    q_pos = _pos(q_off, qi * block_q, (block_q, block_k), 0)

    def body(kj, dq):
        k = k_ref[0, pl.ds(kj * block_k, block_k), :]
        v = v_ref[0, pl.ds(kj * block_k, block_k), :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.where(live, jnp.exp(s - lse), 0.0)
        if causal:
            k_pos = _pos(k_off, kj * block_k, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp + corr) * sm_scale).astype(k.dtype)
        return dq + lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    num_k = kv_len // block_k
    if causal:
        num_k = _causal_num_k(q_off, k_off, qi, block_q, block_k, num_k)
    dq = lax.fori_loop(0, num_k, body,
                       jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    corr_ref, dk_ref, dv_ref, *, block_q: int, block_k: int,
                    causal: bool, sm_scale: float, q_len: int):
    """dk/dv for one k tile: loop q tiles (starting past fully-causal-masked
    ones), recompute p, accumulate p^T @ do and ds^T @ q."""
    kj = pl.program_id(1)
    q_off, k_off = qo_ref[0], ko_ref[0]
    k = k_ref[0]  # [block_k, d]
    v = v_ref[0]
    k_pos = _pos(k_off, kj * block_k, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        corr = corr_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        live = lse > NEG_INF / 2
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        p = jnp.where(live, jnp.exp(s - lse), 0.0)
        if causal:
            q_pos = _pos(q_off, i * block_q, (block_q, block_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dv = dv + lax.dot_general(p.astype(do.dtype), do,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = (p * (dp + corr) * sm_scale).astype(q.dtype)
        dk = dk + lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    num_q = q_len // block_q
    start = 0
    if causal:
        # first q tile whose max q position reaches this k tile's start
        min_k_pos = k_off + kj * block_k
        s0 = jnp.floor((min_k_pos - q_off) / block_q)
        start = jnp.clip(s0, 0, num_q).astype(jnp.int32)
    dk, dv = lax.fori_loop(
        start, num_q, body,
        (jnp.zeros((block_k, k.shape[-1]), jnp.float32),
         jnp.zeros((block_k, v.shape[-1]), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bh_first(x):  # [B, T, H, D] -> [B*H, T, D]
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
           interpret):
    o, lse, _ = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                           block_q, block_k, interpret)
    return o, lse


def _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale, block_q, block_k,
               interpret):
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    qb, kb, vb = _bh_first(q), _bh_first(k), _bh_first(v)
    grid = (b * h, tq // block_q)
    kernel = functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                               causal=causal, sm_scale=sm_scale, kv_len=tk)
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
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb)
    o_out = o.reshape(b, h, tq, dv).transpose(0, 2, 1, 3)
    lse_out = lse.reshape(b, h, tq)
    return o_out, lse_out, (q, k, v, o_out, lse, q_off, k_off)


def _flash_fwd_vjp(q, k, v, q_off, k_off, causal, sm_scale, block_q,
                   block_k, interpret):
    o, lse_out, res = _flash_fwd(q, k, v, q_off, k_off, causal, sm_scale,
                                 block_q, block_k, interpret)
    return (o, lse_out), res


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret, res, cots):
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
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale, kv_len=tk),
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
        interpret=interpret,
    )(q_off, k_off, qb, kb, vb, dob, lse, corr)

    dk, dvv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, sm_scale=sm_scale, q_len=tq),
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


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    q_offset=None, k_offset=None,
                    return_lse: bool = False):
    """softmax(QK^T)V without materializing the score matrix.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D(v)]. Block sizes shrink to divisors
    of the sequence lengths automatically (static shapes are the XLA
    contract anyway). ``q_offset``/``k_offset`` are global sequence
    positions of element 0 (traced scalars allowed) for causal masking of
    sequence-sharded blocks. ``return_lse=True`` also returns the per-row
    log-sum-exp, shaped [B, H, Tq], for online-softmax merging; both
    outputs are differentiable. ``interpret=None`` auto-selects interpret
    mode off-TPU so the same call runs in CPU tests.
    """
    b, tq, h, d = q.shape
    scale = sm_scale if sm_scale is not None else d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(k.shape[1], block_k)
    q_off = (jnp.zeros((1,), jnp.float32) if q_offset is None
             else jnp.asarray(q_offset, jnp.float32).reshape(1))
    k_off = (jnp.zeros((1,), jnp.float32) if k_offset is None
             else jnp.asarray(k_offset, jnp.float32).reshape(1))
    o, lse = _flash(q, k, v, q_off, k_off, causal, scale, block_q, block_k,
                    interpret)
    return (o, lse) if return_lse else o


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = False,
                  sm_scale: Optional[float] = None) -> jax.Array:
    """Plain XLA dot attention — the short-sequence winner.

    Same [B, T, H, D] layout and numerics contract as
    :func:`flash_attention` (matmuls in the input dtype, fp32 softmax), so
    the router can swap between them freely. At short T the [T, T] score
    matrix is small enough that XLA's fused softmax beats the Pallas
    kernel's grid setup cost.
    """
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else d ** -0.5
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
    mask (ring attention relies on exactly these).
    """
    if flash_kwargs.get("return_lse") or \
            flash_kwargs.get("q_offset") is not None or \
            flash_kwargs.get("k_offset") is not None:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               **flash_kwargs)
    threshold = min_flash_seq if min_flash_seq is not None else \
        flash_min_seq()
    if k.shape[1] < threshold:
        # flash_kwargs here can only hold tuning knobs (block sizes /
        # interpret), which have no meaning for the XLA formulation.
        return xla_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
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
