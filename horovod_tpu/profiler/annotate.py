"""Trace annotations bridging the framework into JAX profiler traces.

Two distinct mechanisms, matching where the work actually happens:

- :func:`step_phase` — the compiled training step's own phases
  (:data:`PHASES`), as ``phase_<name>`` named scopes: the step builders of
  ``parallel/dp.py`` and ``parallel/zero.py`` write them, so every device
  operation of a step says whether it is forward/backward, gradient
  exchange, optimizer update, parameter gather or output sync.
- :func:`family_scope` — the parts of a layer, an operator or a head, one
  family of names each (:data:`FAMILIES`: the table says which families
  there are; the comment above each tuple of names says what a name covers
  and which file writes it). ``moe_scope``, ``ssm_scope``,
  ``shortconv_scope``, ``attn_scope``, ``attn_part_scope``, ``mla_scope``,
  ``mtp_scope``, ``diffusion_scope``, ``head_scope``, ``outgate_scope`` and
  ``postnorm_scope`` are that one function with its family bound, one for
  each of the table's eleven families. A new model writes the names that are
  here (every attention operator's parts are :data:`ATTN_PART_SCOPES`,
  every head's :data:`HEAD_SCOPES`); a family of its own is for an operator
  no other model has.
- :func:`collective_scope` — ``jax.named_scope`` for code that runs INSIDE a
  jitted program (the in-jit collectives of ``parallel/collectives.py``).
  The scope becomes HLO op-name metadata, so the device trace of a
  step shows ``hvd_allreduce_average/...`` spans on the TPU lanes.
- :func:`host_annotation` — ``jax.profiler.TraceAnnotation`` for host-side
  work (eager engine enqueue, negotiation wait, the data-plane execute
  callback). These appear on the Python/host threads of the same JAX
  profiler trace, which is what lets :mod:`~horovod_tpu.profiler.trace_merge`
  line engine activity up beside device activity. :func:`step_annotation`
  is the same for one whole training step (``hvd.step``, with its number).

Both degrade to cheap no-ops when jax is not importable — the torch/TF
frontends and the engine executor (``common/eager.py``) must stay usable in
jax-free processes (reference analog: the timeline is always-on
infrastructure, never a hard dependency).
"""

from __future__ import annotations

import contextlib
import functools


@contextlib.contextmanager
def _null_scope():
    yield


# The phases of one compiled training step. The scope's prefix is ``phase_``
# and never ``hvd_``: readers of the compiled text name a collective by the
# first ``hvd_*`` scope of its op_name, which has to stay the collective's.
PHASES = ("forward_backward", "grad_exchange", "optimizer_update",
          "param_gather", "output_sync")
PHASE_PREFIX = "phase_"
# The parts of one expert layer (``parallel/ep.moe_topk``), under the step's
# ``phase_forward_backward``. Neither ``phase_`` nor ``hvd_``: the phase and
# collective readers key on those prefixes.
MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared")
# The parts of one Mamba-2 mixer (``models/nemotron_h.py``; ``ssm_scan`` is
# ``ops/ssd.ssd_scan``), under the same phase and with a prefix of their
# own for the same reason.
SSM_SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
              "ssm_out_proj")
# The parts of one gated short-convolution operator (``models/lfm2.py``):
# ``shortconv_mix`` is the two gates and the taps (``ops/short_conv.py``) and
# nothing else, the other two the projections on either side of it.
SHORTCONV_SCOPES = ("shortconv_in_proj", "shortconv_mix",
                    "shortconv_out_proj")
# The kind of an attention call in a model that mixes them
# (``models/smallthinker.py``): rotary, the key heads' repeat and the
# kernels of a full-causal or of a window layer; ``attn_blockdiff`` is the
# noised and the clean stream of a block-diffusion pass
# (``models/sdar.py``: per-stream rotary, the repeat, the kernels under the
# block mask, a noised block on itself, the merge).
# ``attn_latent`` is the one call of a latent attention operator
# (``models/joyai_flash.py``): q and k of 192, v of 128, nothing else under
# it (rotary and the key's build are ``mla_rope``).
ATTN_SCOPES = ("attn_full", "attn_window", "attn_blockdiff", "attn_latent")
# The parts of one attention operator that are not its kernels (below the
# router's threshold: not ``xla_attention``'s scores, softmax and ``p v``),
# the same names in every model. ``attn_qkv_proj`` and ``attn_out_proj``: the
# projections with the reshape to and from heads; ``attn_qk_norm``: the
# per-head norms of q and k; ``attn_rope``: rotary (the models write these
# four; the rotation is ``ops/rotary.rotary``, whose forward kernel's call
# and whose own backward, traced where the call site was, both name it).
# ``attn_kernel_io``: what ``ops/flash_attention.py`` does around a
# kernel call (the operands' way to ``[B * heads, T, D]`` and back, casts,
# the backward's ``delta``, the sum of dk and dv over a group, the repeat of
# the key heads on the XLA path, the slices of a block-diffusion pass's
# clean keys); ``attn_self_block`` and ``attn_merge``: a noised block on
# itself, and its merge with the kernels' result and the streams' join. A
# part never encloses another part; it may sit inside or outside a kind
# (:data:`ATTN_SCOPES`), and a flash kernel's call is under no part.
ATTN_PART_SCOPES = ("attn_qkv_proj", "attn_qk_norm", "attn_rope",
                    "attn_kernel_io", "attn_self_block", "attn_merge",
                    "attn_out_proj")
# A model's head: ``head_logits`` is the final norm's output times the (tied
# or untied) head up to the float32 logits, written by the model;
# ``head_loss`` the cross-entropy and whatever else the model's own loss
# function adds over the logits (a multi-token-prediction module's head and
# loss stay ``mtp_head``, a diffusion objective's loss ``diffusion_loss``).
HEAD_SCOPES = ("head_logits", "head_loss")
# The parts of one multi-head latent attention operator around that call
# (``models/joyai_flash.py``): ``mla_q_proj`` is the query's down-projection,
# its norm and its up-projection; ``mla_kv_proj`` the same for the latent of
# keys and values; ``mla_rope`` the rotation of the queries' rotary part and
# of the one rotary key, the splits, and whatever builds the key the kernels
# take; ``mla_out_proj`` the out-projection.
MLA_SCOPES = ("mla_q_proj", "mla_kv_proj", "mla_rope", "mla_out_proj")
# The parts of one multi-token-prediction module (``models/joyai_flash.py``):
# ``mtp_merge`` is the two norms, the embedding's second gather and the
# projection of the two halves; ``mtp_block`` the module's own layer (whose
# operations also carry their ``mla_*`` / ``moe_*`` scopes); ``mtp_head`` the
# module's norm, its logits over the shared head and its cross-entropy.
MTP_SCOPES = ("mtp_merge", "mtp_block", "mtp_head")
# The two ends of a block-diffusion objective (``models/sdar.py``).
DIFFUSION_SCOPES = ("diffusion_noise", "diffusion_loss")
# A gate on an attention operator's output (``models/trinity.py``):
# ``outgate_proj`` is the gate's own projection of the layer's normed input,
# as wide as the queries; ``outgate_mul`` its sigmoid and the product with
# the kernels' output, between the call and ``attn_out_proj``. No attention
# part: ``benchmark/harness/attn_parts.py``'s groups are the shared names'.
OUTGATE_SCOPES = ("outgate_proj", "outgate_mul")
# The norm of a branch's OUTPUT and its addition to the residual stream
# (``models/trinity.py``: four norms a layer): ``postnorm_attn`` after the
# attention operator, ``postnorm_ff`` after the feed-forward.
POSTNORM_SCOPES = ("postnorm_attn", "postnorm_ff")
# Host spans the step wrapper (``metrics.timed_step``) writes.
STEP_SPAN = "hvd.step"
STEP_DISPATCH_SPAN = "hvd.step.dispatch"


def step_phase(name: str):
    """Name the enclosed traced ops as one phase of the training step
    (``phase_<name>`` in HLO op-name metadata; the program is unchanged)."""
    if name not in PHASES:
        raise ValueError(f"unknown step phase {name!r}; one of {PHASES}")
    return collective_scope(PHASE_PREFIX + name)


# family -> (what an error message calls it, its names)
FAMILIES = {
    "moe": ("expert-layer", MOE_SCOPES),
    "ssm": ("state-space mixer", SSM_SCOPES),
    "shortconv": ("short-convolution", SHORTCONV_SCOPES),
    "attn": ("attention", ATTN_SCOPES),
    "attn_part": ("attention-part", ATTN_PART_SCOPES),
    "mla": ("latent-attention", MLA_SCOPES),
    "mtp": ("multi-token-prediction", MTP_SCOPES),
    "diffusion": ("diffusion", DIFFUSION_SCOPES),
    "head": ("head", HEAD_SCOPES),
    "outgate": ("output-gate", OUTGATE_SCOPES),
    "postnorm": ("post-norm", POSTNORM_SCOPES),
}


def family_scope(family: str, name: str):
    """Name the enclosed traced ops as the part ``name`` of ``family``
    (:data:`FAMILIES`); a name the family does not list is an error."""
    what, names = FAMILIES[family]
    if name not in names:
        raise ValueError(f"unknown {what} scope {name!r}; one of {names}")
    return collective_scope(name)


moe_scope = functools.partial(family_scope, "moe")
ssm_scope = functools.partial(family_scope, "ssm")
shortconv_scope = functools.partial(family_scope, "shortconv")
attn_scope = functools.partial(family_scope, "attn")
attn_part_scope = functools.partial(family_scope, "attn_part")
mla_scope = functools.partial(family_scope, "mla")
mtp_scope = functools.partial(family_scope, "mtp")
diffusion_scope = functools.partial(family_scope, "diffusion")
head_scope = functools.partial(family_scope, "head")
outgate_scope = functools.partial(family_scope, "outgate")
postnorm_scope = functools.partial(family_scope, "postnorm")


def collective_scope(name: str):
    """Name the enclosed traced ops in HLO metadata (device-trace visible).

    Usable as a context manager around collective construction inside a
    jitted/shard_mapped function."""
    try:
        import jax
    except ImportError:
        return _null_scope()
    return jax.named_scope(name)


def host_annotation(name: str, **kwargs):
    """Annotate a host-side span in the JAX profiler trace (no-op without
    jax, and free when no trace is being collected)."""
    try:
        import jax
        annotation = jax.profiler.TraceAnnotation
    except (ImportError, AttributeError):
        return _null_scope()
    try:
        return annotation(name, **kwargs)
    except Exception:
        return _null_scope()


def step_annotation(step_num: int):
    """Annotate one whole training step on the host (XProf/Perfetto group
    device work by it): ``jax.profiler.StepTraceAnnotation``, a no-op
    without jax and a flag test when no trace is being collected."""
    try:
        import jax
        annotation = jax.profiler.StepTraceAnnotation
    except (ImportError, AttributeError):
        return _null_scope()
    return annotation(STEP_SPAN, step_num=step_num)
