"""Model step: device self time a step of an attention operator's parts that
are neither its kernels nor its projections: the per-head norms, rotary, what
surrounds a kernel call (layouts, casts, ``delta``, the sum over a group), a
noised block on itself and its merge (``attn_qk_norm``, ``attn_rope``,
``mla_rope``, ``attn_kernel_io``, ``attn_self_block``, ``attn_merge``),
forward, recomputed forward and backward (harness/attn_parts.py). None where
the step writes none of the shared names."""

from harness import attn_parts


def read(trace, run):
    return attn_parts.group_ms(trace, run, "outside")
