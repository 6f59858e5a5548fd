"""Model step: device self time a step of the attention operators'
projections with the reshape to and from heads (``attn_qkv_proj``,
``attn_out_proj``; the latent operator's ``mla_q_proj``, ``mla_kv_proj``,
``mla_out_proj``), forward, recomputed forward and backward
(harness/attn_parts.py). None where the step writes none of the shared
names."""

from harness import attn_parts


def read(trace, run):
    return attn_parts.group_ms(trace, run, "projections")
