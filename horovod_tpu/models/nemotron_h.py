"""Hybrid state-space / sparse-expert decoder LM (Nemotron-H): a layer is a
Mamba-2 mixer, an expert layer or attention, never two of them.

NVIDIA-Nemotron-3-Nano-30B-A3B (``config.json``, ``model_type``
``nemotron_h``; transformers' ``modeling_nemotron_h.py``; the Nemotron-H
family, arXiv:2504.03624). Every layer ``i`` is

    x <- x + Mixer_i(RMSNorm(x))

with the residual in the activations' dtype (``residual_in_fp32`` false) and
the mixer's kind read off ``hybrid_override_pattern[i]``:

**``M``, Mamba-2 mixer** (arXiv:2405.21060; ``H`` heads of ``P``, ``G``
groups of state size ``N``; no bias in the projections, one in the conv):
``[z | xBC | dt] = u W_in``, split in that order (``H P``, ``H P + 2 G N``,
``H``); ``xBC <- silu(conv1d(xBC))``, depthwise and causal over ``K``
positions, zeros before the sequence; ``xBC`` splits into ``x`` [H, P] and
``B``, ``C`` [G, N], head ``h`` reading group ``h // (H / G)``;
``dt = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; the scan
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``
(``ops/ssd.ssd_scan``); ``y <- RMSNorm_groups(y * silu(z))``, the gate
first, then RMSNorm over each of the ``G`` groups of ``H P / G`` channels with
one scale of ``H P``; ``out = y W_out``.

**``*``, attention**: ``q`` in ``heads`` heads and ``k``, ``v`` in
``kv_heads`` heads of ``head_dim``, no bias, **no rotary and no other
position signal** (the layers before it carry order), causal
``softmax(q k^T / sqrt(head_dim)) v`` with query head ``j`` on key head
``j // (heads / kv_heads)`` through the length-routed
``ops/flash_attention.attention``, ``W_o``.

**``E``, experts** (``parallel/ep.moe_dropless``): scores
``s = sigmoid(x W_r)`` in float32 over all ``experts``; the choice is the top
``k`` of ``s + b`` with ``b`` the correction bias below; the weights are
``s`` at the chosen experts, without ``b``, over their sum + 1e-20, times
``routed_scale``. Expert ``e`` is ``W_down^e relu(W_up^e x)^2``; one shared
expert of the same form and its own width sees every token (scope
``moe_shared``): ``out = sum_chosen w_e f_e(x) + f_s(x)``.
``experts_held = (first, count)`` makes the layer one chip's share of an
expert-parallel deployment: it routes over all ``experts`` and holds, and
computes, ``count`` of them; ``None`` holds all.

**The state.** ``b`` (``e_score_correction_bias``) is trained by a rule and
not by a gradient (DeepSeek-V3's auxiliary-loss-free balancing,
arXiv:2412.19437), so it is the model's state and no parameter: the
collection ``router_state`` holds, for each expert layer's ``gate``, ``b``
and ``load``, the pairs each expert was sent in the previous step (zeros
before the first). With the collection mutable a call begins with
``b <- b + bias_update_rate * sign(mean(load) - load)`` and leaves its own
``load`` as float32, which ``dp.make_stateful_train_step``'s state sync
averages over chips: under DP the rule sees the global load. Without it
mutable (evaluation) ``b`` is used as it stands.

Embedding -> blocks -> RMSNorm -> untied head with float32 logits. Modules
keep the class's name first (``NemotronHBlock_3/NemotronHMamba2Mixer_0``),
which is how a device trace tells the kinds apart; inside a mixer the names
are the published checkpoint's (``in_proj``, ``conv1d``, ``A_log``, ``D``,
``dt_bias``, ``norm``, ``out_proj``; ``q_proj`` .. ``o_proj``; ``gate`` with
``weight`` and ``e_score_correction_bias``, ``experts`` and
``shared_experts`` with ``up_proj`` and ``down_proj``). The Mamba-2 parts
run under ``profiler/annotate.SSM_SCOPES``. ``remat`` names the kinds whose
blocks are recomputed in backward (``nn.remat``).

The repo's dtype policy: float32 parameters; ``dtype`` (bf16) activations
and matmul inputs with float32 accumulation; float32 for router logits and
scores, norm statistics, the conv's sum, ``dt``, ``A``, every cumulative
sum, decay and the carried state of the scan, logits and loss.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.ops.head_loss import head_cross_entropy
from horovod_tpu.ops.ssd import ssd_scan
from horovod_tpu.ops.ssm_ends import causal_conv_silu, gated_group_norm
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import (attn_part_scope, head_scope,
                                           moe_scope, ssm_scope)

INIT = nn.initializers.normal(stddev=0.02)  # transformers' initializer_range
KINDS = "ME*"
ROUTER_STATE = "router_state"


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=INIT,
                    name=name)


def _dt_bias_init(dt_min: float, dt_max: float, dt_floor: float):
    """The inverse softplus of a log-uniform draw in [dt_min, dt_max],
    floored at ``dt_floor`` (``NemotronHMamba2Mixer``)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(dt_max) - math.log(dt_min))
                     + math.log(dt_min))
        dt = jnp.maximum(dt, dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _conv_init(kernel: int):
    """torch's ``nn.Conv1d`` default for one input channel a group:
    uniform in +-1/sqrt(kernel), weights and bias alike."""
    bound = kernel ** -0.5
    return lambda key, shape, dtype=jnp.float32: jax.random.uniform(
        key, shape, dtype, -bound, bound)


class CausalConv1d(nn.Module):
    """Depthwise causal convolution over time, then silu:
    ``y_t[c] = silu(b[c] + sum_j w[j, c] x_{t-K+1+j}[c])``, zeros before
    ``t = 0``; the sum runs in float32. Over the channels of ``x`` from
    ``at`` on, returned in runs of ``widths`` (``ops/ssm_ends.
    causal_conv_silu``: a kernel a pass and run, which reads its channels
    where they lie in ``x`` and writes each run as the array the scan
    takes)."""
    kernel: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, at: int, widths: Tuple[int, ...]):
        channels = sum(widths)
        w = self.param("kernel", _conv_init(self.kernel),
                       (self.kernel, channels), jnp.float32)
        b = self.param("bias", _conv_init(self.kernel), (channels,),
                       jnp.float32)
        return causal_conv_silu(x, w, b, self.dtype, at, widths)


class GatedGroupRMSNorm(nn.Module):
    """``RMSNorm_groups(y * silu(z))``: the gate first, then RMSNorm over
    each group of channels, one scale over all of them (float32
    statistics); ``z`` is the channels of its argument from ``at`` on
    (``ops/ssm_ends.gated_group_norm``: a kernel a pass)."""
    groups: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, z, at: int = 0):
        scale = self.param("scale", nn.initializers.ones, (y.shape[-1],),
                           jnp.float32)
        return gated_group_norm(y, z, scale, self.groups, self.eps,
                                self.dtype, at)


class NemotronHMamba2Mixer(nn.Module):
    heads: int
    head_dim: int
    state: int
    groups: int
    conv_kernel: int = 4
    chunk: int = 128
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        b, t, hidden = u.shape
        h, p, g, n = self.heads, self.head_dim, self.groups, self.state
        d_in, d_bc = h * p, g * n
        a_log = self.param(
            "A_log", lambda key, shape: jnp.log(
                jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), (h,))
        d_skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(self.dt_min, self.dt_max, self.dt_floor),
            (h,), jnp.float32)
        # [z | x | B | C | dt] along the channels; the two ends read their
        # runs of it in place
        with ssm_scope("ssm_in_proj"):
            proj = _dense(2 * d_in + 2 * d_bc + h, self.dtype, "in_proj")(u)
        with ssm_scope("ssm_conv"):
            x, bmat, cmat = CausalConv1d(
                self.conv_kernel, self.dtype, name="conv1d")(
                proj, d_in, (d_in, d_bc, d_bc))
            dt = jax.nn.softplus(
                proj[..., 2 * d_in + 2 * d_bc:].astype(jnp.float32)
                + dt_bias)
        y = ssd_scan(
            x.reshape(b, t, h, p), dt, -jnp.exp(a_log),
            bmat.reshape(b, t, g, n), cmat.reshape(b, t, g, n), d_skip,
            chunk=self.chunk)
        with ssm_scope("ssm_gate_norm"):
            y = GatedGroupRMSNorm(g, self.eps, self.dtype, name="norm")(
                y.reshape(b, t, d_in), proj)
        with ssm_scope("ssm_out_proj"):
            return _dense(hidden, self.dtype, "out_proj")(y)


class NemotronHAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, hidden = x.shape

        def heads_of(name, count):
            return _dense(count * self.head_dim, self.dtype, name)(x) \
                .reshape(b, t, count, self.head_dim)
        with attn_part_scope("attn_qkv_proj"):
            q, k, v = (heads_of("q_proj", self.heads),
                       heads_of("k_proj", self.kv_heads),
                       heads_of("v_proj", self.kv_heads))
        o = attention(q, k, v, causal=True)
        with attn_part_scope("attn_out_proj"):
            return _dense(hidden, self.dtype, "o_proj")(
                o.reshape(b, t, self.heads * self.head_dim))


class NemotronHTopkRouter(nn.Module):
    """``gate``: the router's matrix, and in ``router_state`` the correction
    bias with the load its rule reads. Returns the routing function
    ``parallel/ep.moe_dropless`` takes and the bias it routes with."""
    experts: int
    experts_per_token: int
    routed_scale: float
    bias_update_rate: float

    @nn.compact
    def __call__(self, hidden: int):
        weight = self.param("weight", INIT, (hidden, self.experts),
                            jnp.float32)
        zeros = functools.partial(jnp.zeros, (self.experts,), jnp.float32)
        bias = self.variable(ROUTER_STATE, "e_score_correction_bias", zeros)
        load = self.variable(ROUTER_STATE, "load", zeros)
        training = self.is_mutable_collection(ROUTER_STATE) and \
            not self.is_initializing()
        if training:
            bias.value = bias.value + self.bias_update_rate * jnp.sign(
                load.value.mean() - load.value)
        route = functools.partial(
            ep.route_sigmoid_topk, w_router=weight, bias=bias.value,
            k=self.experts_per_token, scale=self.routed_scale)
        return route, load if training else None


class NemotronHMLP(nn.Module):
    """``down_proj(relu(up_proj(x))^2)``: the shared expert."""
    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = _dense(self.width, self.dtype, "up_proj")(x)
        return _dense(x.shape[-1], self.dtype, "down_proj")(
            jnp.square(jax.nn.relu(h)))


class NemotronHExperts(nn.Module):
    """The routed experts held here, stacked: ``up_proj`` [held, d, f],
    ``down_proj`` [held, f, d]."""
    held: int
    width: int

    @nn.compact
    def __call__(self, hidden: int):
        return (self.param("up_proj", INIT, (self.held, hidden, self.width),
                           jnp.float32),
                self.param("down_proj", INIT,
                           (self.held, self.width, hidden), jnp.float32))


class NemotronHMoE(nn.Module):
    experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 2.5
    bias_update_rate: float = 1e-3
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        held = self.experts_held[1] if self.experts_held else self.experts
        route, load = NemotronHTopkRouter(
            self.experts, self.experts_per_token, self.routed_scale,
            self.bias_update_rate, name="gate")(d)
        weights = NemotronHExperts(held, self.expert_dim, name="experts")(d)
        out, stats = ep.moe_dropless(
            x.reshape(-1, d).astype(self.dtype), route, ep.relu2_expert,
            tuple(w.astype(self.dtype) for w in weights),
            held=self.experts_held)
        if load is not None:
            load.value = stats.expert_tokens.astype(jnp.float32)
        with moe_scope("moe_shared"):
            shared = NemotronHMLP(self.shared_dim, self.dtype,
                                  name="shared_experts")(x)
        return out.reshape(x.shape) + shared


class NemotronHBlock(nn.Module):
    """``x + Mixer(RMSNorm(x))``; ``mixer`` constructs the layer's kind."""
    mixer: Callable
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        return x + self.mixer()(h)


class NemotronHDecoder(nn.Module):
    """Causal LM: embedding -> one block a character of ``pattern`` ->
    RMSNorm -> untied head. Returns float32 logits [B, T, vocab], or with
    ``head=False`` the normed hidden state [B, T, hidden] they are the
    product of (:func:`nemotron_h_loss` runs the head itself); apply with
    ``mutable=["router_state"]`` to train the correction biases."""

    pattern: str = "MEMEM*EME"
    vocab: int = 131072
    hidden: int = 2688
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    state: int = 128
    groups: int = 8
    conv_kernel: int = 4
    chunk: int = 128
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    experts: int = 128
    experts_per_token: int = 6
    expert_dim: int = 1856
    shared_dim: int = 3712
    routed_scale: float = 2.5
    bias_update_rate: float = 1e-3
    experts_held: Optional[Tuple[int, int]] = None
    dt_limits: Tuple[float, float, float] = (0.001, 0.1, 1e-4)
    eps: float = 1e-5
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, head: bool = True):
        unknown = set(self.pattern + self.remat) - set(KINDS)
        if unknown or not self.pattern:
            raise ValueError(
                f"a layer is one of {KINDS!r} (Mamba-2 mixer, experts, "
                f"attention); pattern {self.pattern!r} and remat "
                f"{self.remat!r} name {sorted(unknown)}")
        mixers = {
            "M": functools.partial(
                NemotronHMamba2Mixer, self.mamba_heads, self.mamba_head_dim,
                self.state, self.groups, self.conv_kernel, self.chunk,
                *self.dt_limits, self.eps, self.dtype),
            "*": functools.partial(
                NemotronHAttention, self.heads, self.kv_heads, self.head_dim,
                self.dtype),
            "E": functools.partial(
                NemotronHMoE, self.experts, self.experts_per_token,
                self.expert_dim, self.shared_dim, self.routed_scale,
                self.bias_update_rate, self.experts_held, self.dtype),
        }
        x = nn.Embed(self.vocab, self.hidden, dtype=self.dtype,
                     embedding_init=INIT)(tokens)
        for i, kind in enumerate(self.pattern):
            block = nn.remat(NemotronHBlock) if kind in self.remat \
                else NemotronHBlock
            # named here: nn.remat's class would name itself otherwise
            x = block(mixers[kind], self.eps, self.dtype,
                      name=f"NemotronHBlock_{i}")(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm_f")(x)
        if not head:
            return x
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        with head_scope("head_logits"):
            return nn.Dense(
                self.vocab, use_bias=False, dtype=self.dtype,
                kernel_init=INIT, dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32),
                name="LmHead")(x)


NEMOTRON_3_NANO_PATTERN = \
    "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def Nemotron3Nano30B(**kw) -> NemotronHDecoder:
    """NVIDIA-Nemotron-3-Nano-30B-A3B geometry (31.6 B parameters, 3.2 B
    active a token): 52 layers, 23 Mamba-2, 23 expert, 6 attention."""
    return NemotronHDecoder(pattern=NEMOTRON_3_NANO_PATTERN, **kw)


def NemotronHTiny(**kw) -> NemotronHDecoder:
    """Every kind of layer at widths a CPU trains in seconds."""
    sizes = dict(pattern="ME*E", vocab=256, hidden=32, mamba_heads=4,
                 mamba_head_dim=8, state=8, groups=2, chunk=16, heads=4,
                 kv_heads=2, head_dim=8, experts=8, experts_per_token=2,
                 expert_dim=16, shared_dim=32)
    return NemotronHDecoder(**{**sizes, **kw})


def nemotron_h_loss(model: NemotronHDecoder, params, router_state, tokens,
                    labels):
    """Mean next-token cross-entropy, no auxiliary term: balance is the
    bias rule's. Returns ``(loss, (new router_state, aux))`` as
    ``dp.make_stateful_train_step`` takes them; ``aux["expert_tokens"]`` is
    this step's load, float32 [expert layers, experts].

    Door A of ``ops/head_loss.py``: the loss holds the model and its
    parameters, so it stops the decoder before its head and hands the hidden
    state and ``LmHead``'s kernel to ``head_cross_entropy``; no [B T, vocab]
    array stands between the forward and the backward pass."""
    hidden, new_state = model.apply(
        {"params": params, ROUTER_STATE: router_state}, tokens, head=False,
        mutable=[ROUTER_STATE])
    rows = labels.size
    loss = head_cross_entropy(
        hidden.reshape(rows, -1), params["LmHead"]["kernel"],
        labels.reshape(rows), jnp.ones(rows, jnp.float32)) / rows
    new_state = new_state.get(ROUTER_STATE, {})  # none without an E layer
    # NemotronHBlock_<i>, in layer order (a tree's keys come sorted as text)
    blocks = sorted(new_state, key=lambda name: int(name.rsplit("_", 1)[1]))
    loads = [new_state[b]["NemotronHMoE_0"]["gate"]["load"] for b in blocks]
    return loss, (new_state, {"expert_tokens": jnp.stack(loads)
                              if loads else jnp.zeros((0, model.experts))})
