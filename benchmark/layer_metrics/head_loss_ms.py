"""Model step: device self time a step of the head and the loss: the final
norm's output times the head up to the float32 logits (``head_logits``), the
cross-entropy and what else the model's loss adds (``head_loss``; a
configuration's own loss as the ``loss`` block of harness/phases.py), a
multi-token-prediction module's head and loss (``mtp_head``), a diffusion
objective's loss (``diffusion_loss``), forward and backward
(harness/attn_parts.py). None where the step writes none of the shared
names."""

from harness import attn_parts


def read(trace, run):
    return attn_parts.group_ms(trace, run, "head")
