"""Model and kernel operation counts, from shapes alone.

The yardstick's own arithmetic: nothing here reads ``cost_analysis()`` (it
does not see inside a ``tpu_custom_call``) or the program's
``profiler/flops.py`` (its ResNet constant is the multiply-add count). One
multiply-add is two FLOPs. Training is forward plus backward, taken as three
times the forward's matrix work (one product forward, two backward);
recomputation, the optimizer, normalisation and element-wise work are not
model FLOPs.
"""

from __future__ import annotations

import math

TRAIN_OVER_FORWARD = 3


# -- ResNet-50 v1.5 ---------------------------------------------------------

RESNET50_STAGES = (3, 4, 6, 3)


def _same(size: int, stride: int) -> int:
    """Output size of a SAME-padded convolution or pool."""
    return math.ceil(size / stride)


def resnet50_forward_macs(image: int = 224, classes: int = 1000,
                          in_channels: int = 3, filters: int = 64) -> int:
    """Multiply-adds of one image's forward pass: the convolutions and the
    head. ``in_channels`` is the image's own 3: channels a program pads the
    stem with are zeros, not model work."""
    macs = 0
    size = _same(image, 2)
    macs += size * size * 7 * 7 * in_channels * filters          # stem
    size = _same(size, 2)                                         # max pool
    cin = filters
    for stage, blocks in enumerate(RESNET50_STAGES):
        mid = filters * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = _same(size, stride)
            macs += size * size * cin * mid                       # 1x1
            macs += out * out * 3 * 3 * mid * mid                 # 3x3, strided (v1.5)
            macs += out * out * mid * 4 * mid                     # 1x1
            if block == 0:
                macs += out * out * cin * 4 * mid                 # projection
            cin, size = 4 * mid, out
    return macs + cin * classes                                   # head


def resnet50_train_flops_per_image(image: int = 224, classes: int = 1000) -> float:
    return float(TRAIN_OVER_FORWARD * 2 * resnet50_forward_macs(image, classes))


# -- decoder-only transformer (GPT-2) ----------------------------------------

def gpt_forward_matmul_flops_per_token(layers: int, hidden: int, mlp: int,
                                       vocab: int) -> int:
    """Weight matrix products of one token's forward pass: q, k, v and out
    (4 h^2), the two MLP products (2 h mlp) in each layer, and the tied head
    (h vocab). The embedding look-ups are gathers, not products."""
    return 2 * (layers * (4 * hidden * hidden + 2 * hidden * mlp)
                + hidden * vocab)


def attended_pairs(seq: int, causal: bool) -> int:
    """(query, key) pairs that are not masked, in one sequence."""
    return seq * (seq + 1) // 2 if causal else seq * seq


def gpt_forward_attention_flops_per_seq(layers: int, hidden: int, seq: int,
                                        causal: bool = True) -> int:
    """QK^T and PV of one sequence's forward pass over the unmasked pairs:
    two products of ``hidden`` multiply-adds a pair (all heads together)."""
    return layers * 2 * 2 * attended_pairs(seq, causal) * hidden


def gpt_train_flops_per_token(layers: int, hidden: int, mlp: int, vocab: int,
                              seq: int, causal: bool = True) -> float:
    forward = (gpt_forward_matmul_flops_per_token(layers, hidden, mlp, vocab)
               + gpt_forward_attention_flops_per_seq(layers, hidden, seq,
                                                     causal) / seq)
    return float(TRAIN_OVER_FORWARD * forward)


# -- the three flash-attention kernels ---------------------------------------

# matrix products each kernel's call needs per unmasked pair, each of
# ``head_dim`` multiply-adds: forward s = qk^T, o = pv; dq: s, dp = do v^T,
# dq = ds k; dk/dv: s, dv = p^T do, dp, dk = ds^T q. The backward kernels
# have only q, k, v, do and the row statistics to start from, so recomputing
# s (and dp, twice) is what their calls need: it counts for a kernel's
# roofline, and not for the model's FLOPs.
FLASH_PRODUCTS = {"_fwd_kernel": 2, "_bwd_dq_kernel": 3, "_bwd_dkv_kernel": 4}
# [batch*heads, seq, head_dim] arrays read and written once, and
# [batch*heads, seq] float32 rows (lse, corr) read or written once
FLASH_ARRAYS = {"_fwd_kernel": (4, 1), "_bwd_dq_kernel": (5, 2),
                "_bwd_dkv_kernel": (6, 2)}


def flash_kernel_cost(kernel: str, batch: int, seq: int, heads: int,
                      head_dim: int, causal: bool,
                      dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one call of ``kernel`` needs at these shapes."""
    pairs = batch * heads * attended_pairs(seq, causal)
    flops = FLASH_PRODUCTS[kernel] * 2 * head_dim * pairs
    arrays, rows = FLASH_ARRAYS[kernel]
    elements = batch * heads * seq
    return flops, elements * (arrays * head_dim * dtype_bytes + rows * 4)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which peak bounds it."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
