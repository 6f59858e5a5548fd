"""Trace annotations bridging the framework into JAX profiler traces.

Two distinct mechanisms, matching where the work actually happens:

- :func:`step_phase` — the compiled training step's own phases
  (:data:`PHASES`), as ``phase_<name>`` named scopes: the step builders of
  ``parallel/dp.py`` and ``parallel/zero.py`` write them, so every device
  operation of a step says whether it is forward/backward, gradient
  exchange, optimizer update, parameter gather or output sync.
- :func:`moe_scope` — the parts of an expert layer (:data:`MOE_SCOPES`:
  router, dispatch, experts, combine, written by ``parallel/ep.py``'s
  dropless layer; ``moe_shared``, the shared expert every token visits, by
  the model that has one).
- :func:`ssm_scope` — the parts of a Mamba-2 mixer (:data:`SSM_SCOPES`:
  in-projection, causal conv, the chunked scan of ``ops/ssd.py``, gated
  group norm, out-projection), written by ``models/nemotron_h.py``.
- :func:`shortconv_scope` — the parts of a gated short-convolution operator
  (:data:`SHORTCONV_SCOPES`: in-projection, the two gates and the taps of
  ``ops/short_conv.py``, out-projection), written by ``models/lfm2.py``.
- :func:`mla_scope` — the parts of a multi-head latent attention operator
  around its attention call (:data:`MLA_SCOPES`: the query's and the
  key-value's low-rank projections with their inner norms, rotary with
  whatever builds the kernels' key, the out-projection), written by
  ``models/joyai_flash.py``.
- :func:`mtp_scope` — the parts of a multi-token-prediction module
  (:data:`MTP_SCOPES`: the merge of the next token's embedding with the
  stack's output, the module's block, its head and loss), written by
  ``models/joyai_flash.py``.
- :func:`attn_scope` — the kind of an attention call (:data:`ATTN_SCOPES`:
  full causal or window, written by ``models/smallthinker.py``, whose
  layers mix the two, and ``models/lfm2.py``'s attention layers; the two streams of a block-diffusion pass, by
  ``models/sdar.py``; a latent call, q/k wider than v, by
  ``models/joyai_flash.py``).
- :func:`diffusion_scope` — the two ends of a block-diffusion objective
  (:data:`DIFFUSION_SCOPES`: the noising of a batch and the weighted loss
  over its masked positions), written by ``models/sdar.py``.
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
# Host spans the step wrapper (``metrics.timed_step``) writes.
STEP_SPAN = "hvd.step"
STEP_DISPATCH_SPAN = "hvd.step.dispatch"


def step_phase(name: str):
    """Name the enclosed traced ops as one phase of the training step
    (``phase_<name>`` in HLO op-name metadata; the program is unchanged)."""
    if name not in PHASES:
        raise ValueError(f"unknown step phase {name!r}; one of {PHASES}")
    return collective_scope(PHASE_PREFIX + name)


def moe_scope(name: str):
    """Name the enclosed traced ops as one part of an expert layer."""
    if name not in MOE_SCOPES:
        raise ValueError(f"unknown expert-layer scope {name!r}; one of "
                         f"{MOE_SCOPES}")
    return collective_scope(name)


def ssm_scope(name: str):
    """Name the enclosed traced ops as one part of a state-space mixer."""
    if name not in SSM_SCOPES:
        raise ValueError(f"unknown state-space mixer scope {name!r}; one of "
                         f"{SSM_SCOPES}")
    return collective_scope(name)


def shortconv_scope(name: str):
    """Name the enclosed traced ops as one part of a gated short-convolution
    operator."""
    if name not in SHORTCONV_SCOPES:
        raise ValueError(f"unknown short-convolution scope {name!r}; one of "
                         f"{SHORTCONV_SCOPES}")
    return collective_scope(name)


def attn_scope(name: str):
    """Name the enclosed traced ops as an attention call of one kind."""
    if name not in ATTN_SCOPES:
        raise ValueError(f"unknown attention scope {name!r}; one of "
                         f"{ATTN_SCOPES}")
    return collective_scope(name)


def mla_scope(name: str):
    """Name the enclosed traced ops as one part of a latent attention
    operator."""
    if name not in MLA_SCOPES:
        raise ValueError(f"unknown latent-attention scope {name!r}; one of "
                         f"{MLA_SCOPES}")
    return collective_scope(name)


def mtp_scope(name: str):
    """Name the enclosed traced ops as one part of a multi-token-prediction
    module."""
    if name not in MTP_SCOPES:
        raise ValueError(f"unknown multi-token-prediction scope {name!r}; "
                         f"one of {MTP_SCOPES}")
    return collective_scope(name)


def diffusion_scope(name: str):
    """Name the enclosed traced ops as one end of a diffusion objective."""
    if name not in DIFFUSION_SCOPES:
        raise ValueError(f"unknown diffusion scope {name!r}; one of "
                         f"{DIFFUSION_SCOPES}")
    return collective_scope(name)


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
