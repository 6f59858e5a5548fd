"""What the flash-attention test files share (``test_flash_*.py``,
``test_attention_router.py``): the dense references the kernels are held to,
seeded inputs, the tolerance by dtype, and the gradient of a weighted output
as ONE jitted program. No test is collected from this file.

The kernels run in interpret mode here, and what a case costs is the
programs it compiles: an eager ``jax.grad`` dispatches (and compiles) its
operations one by one, several seconds a case. So a case takes the output and
the three gradients from one ``jax.jit`` (:func:`out_and_grads`), and what
several cases of a grid share (the reference of a mask at a head width,
whatever the kernels' tiles) is computed once a process
(``functools.lru_cache`` on the cases' own parameters: the inputs are made
from fixed seeds).
"""

import numpy as np
import jax
import jax.numpy as jnp

B, T, H, D = 2, 256, 4, 64


def dense(q, k, v, causal):
    s = np.einsum("bqhd,bkhd->bhqk", q, k).astype(np.float64) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((T, T), bool))
        s = np.where(mask[None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64))


def dense_causal(q, k, v, q_off=0, k_off=0, window=None):
    """(o, lse) of causal attention at global positions, in float32; a row
    that sees no key gives o = 0 and lse = NEG_INF, as the kernel does.
    With a ``window`` the explicit mask ``0 <= q_pos - k_pos < window``."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    ahead = (q_off + jnp.arange(q.shape[1])[:, None]
             - k_off - jnp.arange(k.shape[1])[None, :])
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    live = seen.any(-1)[None, None, :]
    s = jnp.where(seen[None, None], s, -1e30)
    p = jnp.where(live[..., None], jax.nn.softmax(s, -1), 0.0)
    lse = jnp.where(live, jax.scipy.special.logsumexp(s, axis=-1), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def qkv(seed, shape, dtype):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


def assert_close(got, want, dtype):
    tol = dict(rtol=2e-3, atol=2e-4) if dtype == jnp.float32 else \
        dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def out_and_grads(attend, q, k, v, dout):
    """``(out, (dq, dk, dv))`` of ``sum(o * dout)``, the sum in float32, one
    jitted program a call: ``out`` is what ``attend(q, k, v)`` returns, ``o``
    or ``(o, lse)`` (the lse rides along and takes no cotangent)."""
    def loss(q, k, v):
        out = attend(q, k, v)
        o = out[0] if isinstance(out, tuple) else out
        return jnp.sum(o.astype(jnp.float32) * dout), out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return out, grads


# ring attention's three cases, and a shard boundary inside a block
RING_SHARDS = {"before": (512, 0), "across": (256, 256), "after": (0, 512),
               "across_inside_a_block": (96, 0)}


def block_visits():
    from horovod_tpu.metrics.registry import get_registry
    return {kind: get_registry().counter("hvd_flash_block_visits",
                                         kind=kind).value
            for kind in ("interior", "diagonal", "skipped")}


def dense_window(q, k, v, window, q_off=0, k_off=0):
    return dense_causal(q, k, v, q_off, k_off, window)


def dense_masked(q, k, v, seen):
    """(o, lse) under an explicit boolean mask [Tq, Tk], in float32, key
    heads repeated; a row that sees no key gives o = 0 and lse = NEG_INF."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    live = seen.any(-1)[None, None, :]
    s = jnp.where(seen[None, None], s, -1e30)
    p = jnp.where(live[..., None], jax.nn.softmax(s, -1), 0.0)
    lse = jnp.where(live, jax.scipy.special.logsumexp(s, axis=-1), -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v), lse


def block_edge(t, group, edge):
    """The mask by hand: block indices compared, not positions."""
    b = np.arange(t) // group
    return jnp.asarray(b[None, :] <= b[:, None] if edge == "le"
                       else b[None, :] < b[:, None])


def equations(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs too, however
    deep (``custom_vjp``, ``jit``, a kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub)


def kernel_functions(fn, *args) -> list:
    """The kernel function of every ``pallas_call`` that tracing ``fn(*args)``
    holds, by name: what the compiled text of a chip names a call by (in
    interpret mode there is no such text)."""
    return [eqn.params["jaxpr"].debug_info.func_src_info.split(" ")[0]
            for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]
