#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the training path starts on the chip.

One process drives the path a user takes (``hvd.init()`` -> ``hvd.mesh()`` ->
``dp.make_stateful_train_step`` / ``dp.make_train_step`` -> ``dp.replicate``,
``dp.shard_batch`` -> a few steps) at the full width of ResNet-50 and GPT-2
small, with random weights made from ``--seed``, and checks what comes out.

    python chip_smoke.py             one chip: device, resnet50,
                                     gpt2_small_flash, flash_vs_reference,
                                     engine
    python chip_smoke.py --chips 4   the path across chips and what it is
                                     compared with, and no one-chip phase

Each phase prints one JSON line (name, seconds, compile seconds, programs
compiled, persistent-cache hits and misses, what it checked). A phase that
fails raises: nothing is caught, the exit code is non-zero and no result
line is printed. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Without a TPU the run ends in the device phase. ``--rehearse`` is for the
sandbox: the same phases at tiny sizes on whatever platform JAX finds
(kernels in interpret mode off-TPU). It never prints ``"ok": true``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import math
import sys
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

# Tolerances, set beforehand from the dtype. bf16 keeps 8 significand bits.
BF16_EPS = 2.0 ** -8
# flash vs XLA attention: both round p, ds and the outputs to bf16, at
# different points; the error is taken relative to the reference's largest
# magnitude.
FLASH_TOL = 4 * BF16_EPS
# four chips vs one: the same bf16/fp32 arithmetic for each example on both
# sides, but two programs, which the compiler fuses differently: it may skip
# a bf16 rounding in one and keep it in the other.
LOSS_RTOL = 1e-3
UPDATE_TOL = 4 * BF16_EPS  # relative L2 error of the parameter update

# collectives by their StableHLO name (lowered text) and HLO name (compiled)
COLLECTIVES = {"all_reduce": "all-reduce", "reduce_scatter": "reduce-scatter",
               "all_gather": "all-gather"}


class Sizes(NamedTuple):
    resnet_per_chip: int
    resnet_image: int
    gpt_per_chip: int
    gpt_layers: int
    steps: int  # after the compiling one
    flash_shape: tuple  # (B, T, H, D)


REAL = Sizes(resnet_per_chip=128, resnet_image=224, gpt_per_chip=8,
             gpt_layers=12, steps=3, flash_shape=(2, 2048, 12, 64))
# Widths stay full; images, batches and depth shrink. T stays 1024 so the
# attention router still takes the flash path.
TINY = Sizes(resnet_per_chip=2, resnet_image=32, gpt_per_chip=1,
             gpt_layers=2, steps=2, flash_shape=(1, 256, 2, 64))
GPT_SEQ = 1024


def _check(cond, message):
    if not cond:
        raise RuntimeError(message)


def _compiled():
    """(backend-compile seconds, programs, cache hits, cache misses) so
    far, from the program's own compile log."""
    from horovod_tpu.metrics import compile_log
    found = compile_log.report()
    return (found["stages"]["backend"]["seconds"],
            found["stages"]["backend"]["count"],
            found["programs"]["hit"], found["programs"]["miss"])


def _host(tree):
    """Host copies: donation cannot reach them."""
    return jax.tree_util.tree_map(np.asarray, tree)


def _all_changed(before, after, what):
    """Fail unless every leaf of ``after`` differs from ``before``'s."""
    after_leaves = jax.tree_util.tree_leaves(after)
    same = [jax.tree_util.keystr(path) for (path, b), a in zip(
        jax.tree_util.tree_flatten_with_path(before)[0], after_leaves)
        if not np.any(np.asarray(a) != np.asarray(b))]
    _check(not same, f"{what}: {len(same)} of {len(after_leaves)} leaves "
                     f"did not change: {same}")
    return f"{len(after_leaves)}/{len(after_leaves)}"


def _trained(params0, out, what):
    """What a few steps leave behind: a gradient reached every parameter
    (every array of the optimizer state has a non-zero entry) and parameters
    moved. Not every leaf has to differ: ResNet-50 starts each block's last
    BatchNorm scale at zero, so the scales inside the branch, which are 1.0,
    at first get updates below fp32's resolution at 1.0 (on the chip 18 of
    161 leaves were bit-equal after four steps, their gradients near 3e-7).
    """
    state = [(jax.tree_util.keystr(path), np.asarray(x)) for path, x in
             jax.tree_util.tree_flatten_with_path(out.opt_state)[0]]
    arrays = [(path, x) for path, x in state if x.ndim]
    dead = [path for path, x in arrays if not x.any()]
    _check(arrays and not dead,
           f"{what}: no gradient reached {dead or 'the optimizer state'}")
    flags = [bool(np.any(np.asarray(a) != b)) for b, a in zip(
        jax.tree_util.tree_leaves(params0),
        jax.tree_util.tree_leaves(out.params))]
    _check(any(flags), f"{what}: no parameter changed")
    return {"optimizer_state_arrays_nonzero": f"{len(arrays)}/{len(arrays)}",
            "params_changed": f"{sum(flags)}/{len(flags)}"}


# -- the two jobs ----------------------------------------------------------

class Job(NamedTuple):
    loss_fn: object
    optimizer: object
    params: object       # host
    model_state: object  # host; None for a stateless model
    batch: object        # host, the global batch


def resnet50_job(sizes, n_chips, seed) -> Job:
    """ResNet-50 under ``models/resnet.py``'s policy: bf16 compute, fp32
    params and BN statistics, NHWC, stem padded to 8; SGD with momentum."""
    from horovod_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16,
                     param_dtype=jnp.float32, input_layout="NHWC",
                     pad_stem_to=8)
    px = sizes.resnet_image
    variables = jax.jit(lambda k, x: model.init(k, x, train=True))(
        jax.random.key(seed), jnp.zeros((8, px, px, 3), jnp.bfloat16))

    def loss_fn(params, model_state, batch, rng):
        logits, new_state = model.apply(
            {"params": params, "batch_stats": model_state},
            batch["image"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
        return loss, (new_state["batch_stats"], {})

    rs = np.random.RandomState(seed)
    n = sizes.resnet_per_chip * n_chips
    batch = {"image": rs.rand(n, px, px, 3).astype(jnp.bfloat16),
             "label": rs.randint(0, 1000, n).astype(np.int32)}
    return Job(loss_fn, optax.sgd(0.05, momentum=0.9),
               _host(variables["params"]), _host(variables["batch_stats"]),
               batch)


def gpt2_small_job(sizes, n_chips, seed, optimizer) -> Job:
    """GPT-2 small at its published widths (768 hidden, 12 heads, vocab
    50257) and context (1024): next-token loss on random tokens."""
    from horovod_tpu.models import GptSmall

    model = GptSmall()
    _check((model.hidden, model.heads, model.vocab, model.max_len) ==
           (768, 12, 50257, GPT_SEQ), "GptSmall is not at GPT-2 small widths")
    if sizes.gpt_layers != model.layers:
        model = model.clone(layers=sizes.gpt_layers)
    rs = np.random.RandomState(seed)
    n = sizes.gpt_per_chip * n_chips
    tokens = rs.randint(0, model.vocab, (n, GPT_SEQ)).astype(np.int32)
    params = jax.jit(model.init)(jax.random.key(seed), tokens[:1])["params"]

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
        return loss, {}

    batch = {"tokens": tokens, "labels": np.roll(tokens, -1, axis=1)}
    return Job(loss_fn, optimizer, _host(params), None, batch)


# -- the run ----------------------------------------------------------------

class Smoke:
    """One run: its sizes and its seed. The program's compile log listens
    from here on, before ``hvd.init()`` would register it: the device phase
    comes first."""

    def __init__(self, sizes, seed, rehearse):
        from horovod_tpu.metrics import compile_log
        self.sizes, self.seed, self.rehearse = sizes, seed, rehearse
        compile_log.install()

    def check_on_chip(self, cond, message):
        """A check only the TPU backend can meet: a kernel's custom call,
        memory statistics, a collective in the compiled step."""
        _check(cond or self.rehearse, message)

    @contextlib.contextmanager
    def phase(self, name):
        """Time one phase; print its JSON line if it ends without raising."""
        checked = {}
        before, t0 = _compiled(), time.perf_counter()
        yield checked
        seconds = time.perf_counter() - t0
        delta = [a - b for a, b in zip(_compiled(), before)]
        print(json.dumps({
            "phase": name, "seconds": round(seconds, 3),
            "compile_seconds": round(delta[0], 3),
            "programs_compiled": delta[1], "cache_hits": delta[2],
            "cache_misses": delta[3], "checked": checked}), flush=True)

    def run_steps(self, step, state, batch, what):
        """Call ``step`` once to compile and ``sizes.steps`` times more,
        waiting for each; return the last output and the losses. ``state``
        is the step's leading arguments, replaced from each output (the step
        donates them). Fails on a loss that is not finite and on a program
        compiled after the first step."""
        key = jax.random.key(1)
        losses, later = [], 0
        for i in range(1 + self.sizes.steps):
            before = _compiled()[1]
            out = jax.block_until_ready(step(*state, batch, key))
            if i:
                later += _compiled()[1] - before
            state = tuple(out[:len(state)])
            losses.append(float(out.loss))
            _check(math.isfinite(losses[-1]),
                   f"{what}: loss at step {i} is {losses[-1]}")
        _check(later == 0,
               f"{what}: {later} programs compiled after the first step")
        return out, losses

    # -- one chip -----------------------------------------------------------

    def device(self, n_chips, cache_dir):
        devices = jax.devices()
        d0 = devices[0]
        if d0.platform != "tpu" and not self.rehearse:
            sys.exit(f"chip_smoke: no TPU: jax.devices() is {devices}")
        if len(devices) != n_chips:
            sys.exit(f"chip_smoke: --chips {n_chips} but JAX finds "
                     f"{len(devices)} device(s)")
        with self.phase("device") as checked:
            try:
                libtpu = importlib.metadata.version("libtpu")
            except importlib.metadata.PackageNotFoundError:
                libtpu = None
            checked.update(
                platform=d0.platform, device_kind=d0.device_kind,
                count=len(devices), jax=jax.__version__,
                jaxlib=importlib.metadata.version("jaxlib"), libtpu=libtpu,
                compile_cache_dir=cache_dir)
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": len(devices)}

    def resnet50(self, mesh, name="resnet50"):
        from horovod_tpu.parallel import dp

        sizes, n_chips = self.sizes, mesh.devices.size
        with self.phase(name) as checked:
            job = resnet50_job(sizes, n_chips, self.seed)
            step = dp.make_stateful_train_step(job.loss_fn, job.optimizer,
                                               mesh, donate=True)
            batch = dp.shard_batch(job.batch, mesh)
            state = (dp.replicate(job.params, mesh),
                     dp.replicate(job.optimizer.init(job.params), mesh),
                     dp.replicate(job.model_state, mesh))
            if n_chips > 1:
                checked.update(
                    self.spread(step, state, batch, ["all_reduce"], name))
            out, losses = self.run_steps(step, state, batch, name)
            checked.update(
                images_per_chip=sizes.resnet_per_chip,
                image=sizes.resnet_image, steps=len(losses), losses=losses,
                loss_finite=True, **_trained(job.params, out, name),
                batch_stats_changed=_all_changed(
                    job.model_state, out.model_state, f"{name} batch_stats"),
                compiles_after_first_step=0)
            if n_chips > 1:
                checked.update(self.on_every_chip(out.params, name))

    def gpt2_small_flash(self, mesh):
        from horovod_tpu.parallel import dp

        sizes, name = self.sizes, "gpt2_small_flash"
        with self.phase(name) as checked:
            job = gpt2_small_job(sizes, mesh.devices.size, self.seed,
                                 optax.adamw(1e-4))
            step = dp.make_train_step(job.loss_fn, job.optimizer, mesh)
            batch = dp.shard_batch(job.batch, mesh)
            state = (dp.replicate(job.params, mesh),
                     dp.replicate(job.optimizer.init(job.params), mesh))
            # The lowered step names what it calls: the compiled Pallas
            # kernel is a tpu_custom_call; interpret mode and the XLA path
            # leave none.
            calls = step.lower(*state, batch, jax.random.key(1)).as_text() \
                .count("tpu_custom_call")
            self.check_on_chip(
                calls >= 3 * sizes.gpt_layers,
                f"{name}: {calls} tpu_custom_call in the lowered step, "
                f"expected {3 * sizes.gpt_layers} (forward, dq and dk/dv in "
                "each layer)")
            out, losses = self.run_steps(step, state, batch, name)
            checked.update(
                sequences_per_chip=sizes.gpt_per_chip, seq_len=GPT_SEQ,
                layers=sizes.gpt_layers, tpu_custom_calls=calls,
                steps=len(losses), losses=losses, loss_finite=True,
                **_trained(job.params, out, name),
                compiles_after_first_step=0)

    def flash_vs_reference(self):
        from horovod_tpu.ops.flash_attention import (flash_attention,
                                                     xla_attention)

        with self.phase("flash_vs_reference") as checked:
            rs = np.random.RandomState(self.seed)
            shape = self.sizes.flash_shape
            q, k, v, w = (jnp.asarray(rs.randn(*shape), jnp.bfloat16)
                          for _ in range(4))

            def run(attn):
                def f(q, k, v):
                    o = attn(q, k, v, causal=True)
                    return jnp.sum(o.astype(jnp.float32) * w), o
                (_, o), grads = jax.jit(jax.value_and_grad(
                    f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
                return (o,) + grads

            errors = {}
            for name, got, want in zip(("out", "dq", "dk", "dv"),
                                       run(flash_attention),
                                       run(xla_attention)):
                got, want = (np.asarray(x, np.float32) for x in (got, want))
                _check(np.isfinite(got).all(), f"flash {name} is not finite")
                errors[name] = float(np.abs(got - want).max() /
                                     np.abs(want).max())
                _check(errors[name] <= FLASH_TOL,
                       f"flash {name} differs from xla_attention by "
                       f"{errors[name]:.4g} of its largest magnitude "
                       f"(tolerance {FLASH_TOL:.4g})")
            checked.update(shape=list(shape), dtype="bfloat16", causal=True,
                           max_error_over_max_magnitude=errors,
                           tolerance=FLASH_TOL)

    def engine(self):
        """The eager path's round trip, device -> host -> engine -> device,
        on a library built here from engine/src."""
        from horovod_tpu.common.eager import EagerExecutor
        from horovod_tpu.engine import OP_ALLREDUCE, EngineSession, bindings
        from horovod_tpu.parallel.collectives import Sum

        with self.phase("engine") as checked:
            t0 = time.perf_counter()
            lib = bindings.build_library(force=True)
            build_seconds = time.perf_counter() - t0
            # whole numbers below 2**24: every sum is exact in fp32
            x = jnp.arange(1 << 16, dtype=jnp.float32).reshape(256, 256)
            group = f"smoke-{uuid.uuid4().hex[:8]}"
            sessions = [EngineSession(rank=r, size=2, transport="loopback",
                                      group=group, cycle_time_ms=1.0)
                        for r in range(2)]

            def work(r, ex):
                h = ex.submit("smoke", OP_ALLREDUCE, np.asarray(x) + r,
                              reduce_op=Sum)
                ex.session.wait(h, timeout=30.0)
                return jax.device_put(ex.take_result("smoke"))

            try:
                # the ranks must submit together; a result re-raises what
                # its thread raised
                with ThreadPoolExecutor(len(sessions)) as pool:
                    futures = [pool.submit(work, r, EagerExecutor(s))
                               for r, s in enumerate(sessions)]
                    results = [f.result(timeout=60.0) for f in futures]
            finally:
                for s in sessions:
                    s._lib.hvdtpu_shutdown(s._session)
                for s in sessions:
                    s.destroy()
            for r, got in enumerate(results):
                _check(bool(jnp.array_equal(got, 2 * x + 1)),
                       f"engine allreduce on rank {r} is not 2x+1")
            checked.update(library=str(lib),
                           build_seconds=round(build_seconds, 3), ranks=2,
                           transport="loopback", elements=x.size,
                           allreduce_exact=True)

    # -- four chips ---------------------------------------------------------

    def spread(self, step, state, batch, asked, what):
        """That the work is spread, before it runs: every batch leaf on every
        chip, the collectives the step asks for in its lowered text, and what
        the compiler made of them in the compiled text. Compiles the step;
        its first call then finds the program already built."""
        n = len(jax.devices())
        for leaf in jax.tree_util.tree_leaves(batch):
            _check(len(leaf.sharding.device_set) == n,
                   f"{what}: a batch leaf is on "
                   f"{len(leaf.sharding.device_set)} of {n} devices")
        lowered = step.lower(*state, batch, jax.random.key(1))
        low_text, text = lowered.as_text(), lowered.compile().as_text()
        in_lowered = [c for c in COLLECTIVES if f"stablehlo.{c}" in low_text]
        in_compiled = [c for c in COLLECTIVES.values() if c in text]
        _check(all(c in in_lowered for c in asked),
               f"{what}: the lowered step has {in_lowered}, expected {asked}")
        # For a 2x2 v5e the compiler turns a reduce-scatter into an
        # all-reduce and a dynamic-slice, so either name stands for it.
        self.check_on_chip(
            all(COLLECTIVES[c] in in_compiled or
                (c == "reduce_scatter" and "all-reduce" in in_compiled)
                for c in asked),
            f"{what}: the compiled step has {in_compiled}, expected {asked}")
        return {"batch_devices": n, "collectives_lowered": in_lowered,
                "collectives_compiled": in_compiled}

    def on_every_chip(self, params, what):
        """After the steps: memory in use on every chip, and every parameter
        bit-identical on all of them (each addressable shard is read)."""
        devices = jax.devices()
        # the CPU backend reports no memory statistics
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        self.check_on_chip(all(in_use),
                           f"{what}: bytes_in_use per chip is {in_use}")
        for leaf in jax.tree_util.tree_leaves(params):
            copies = [np.asarray(s.data).tobytes()
                      for s in leaf.addressable_shards]
            _check(len(copies) == len(devices) and
                   all(c == copies[0] for c in copies[1:]),
                   f"{what}: a parameter differs between chips")
        return {"bytes_in_use": in_use,
                "params_identical_across_chips": True}

    def four_chips(self, mesh):
        """One GPT-2 small step by DP and by ZeRO-1 on the four-chip mesh
        against the same global batch on one chip, then ResNet-50 for the
        shape of the real job. The comparison uses SGD with momentum: its
        update is linear in the averaged gradient, where Adam's normalised
        step would hide a wrong scale."""
        from horovod_tpu.parallel import dp, mesh as mesh_lib, zero

        sizes, key = self.sizes, jax.random.key(1)
        job = gpt2_small_job(sizes, mesh.devices.size, self.seed,
                             optax.sgd(0.05, momentum=0.9))

        with self.phase("gpt2_small_one_chip_reference") as checked:
            mesh1 = mesh_lib.data_parallel_mesh(jax.devices()[:1])
            step = dp.make_train_step(
                _in_slices(job.loss_fn, mesh.devices.size), job.optimizer,
                mesh1)
            ref = jax.block_until_ready(step(
                dp.replicate(job.params, mesh1),
                dp.replicate(job.optimizer.init(job.params), mesh1),
                dp.shard_batch(job.batch, mesh1), key))
            ref_loss, ref_params = float(ref.loss), _host(ref.params)
            del ref
            _check(math.isfinite(ref_loss), f"reference loss is {ref_loss}")
            checked.update(global_batch=len(job.batch["tokens"]),
                           seq_len=GPT_SEQ, layers=sizes.gpt_layers,
                           loss=ref_loss)

        def compare(name, sharded_update, asked):
            with self.phase(name) as checked:
                step = dp.make_train_step(job.loss_fn, job.optimizer, mesh,
                                          sharded_update=sharded_update)
                params = dp.replicate(job.params, mesh)
                opt_state = \
                    zero.sharded_opt_init(job.optimizer, params, mesh) \
                    if sharded_update else \
                    dp.replicate(job.optimizer.init(job.params), mesh)
                batch = dp.shard_batch(job.batch, mesh)
                checked.update(self.spread(step, (params, opt_state), batch,
                                           asked, name))
                out = jax.block_until_ready(
                    step(params, opt_state, batch, key))
                loss = float(out.loss)
                loss_error = abs(loss - ref_loss) / abs(ref_loss)
                _check(loss_error <= LOSS_RTOL,
                       f"{name}: loss {loss} against {ref_loss} on one chip")
                error = _update_error(job.params, _host(out.params),
                                      ref_params)
                _check(error <= UPDATE_TOL,
                       f"{name}: the parameter update differs from one "
                       f"chip's by {error:.4g} (relative L2, tolerance "
                       f"{UPDATE_TOL:.4g})")
                checked.update(
                    loss=loss, loss_relative_error=loss_error,
                    loss_rtol=LOSS_RTOL, update_relative_l2_error=error,
                    update_tolerance=UPDATE_TOL,
                    **self.on_every_chip(out.params, name))

        compare("gpt2_small_dp4", False, ["all_reduce"])
        compare("gpt2_small_zero1", True, ["reduce_scatter", "all_gather"])
        self.resnet50(mesh, name="resnet50_dp4")


def _in_slices(loss_fn, n):
    """The same loss over the same batch, taken ``n`` equal slices in turn
    with each slice's forward recomputed in the backward pass, so that one
    chip can hold a batch made for ``n``: the mean of the slices' means is
    the batch's mean."""
    def sliced(params, batch, rng):
        slices = jax.tree_util.tree_map(
            lambda x: x.reshape(n, -1, *x.shape[1:]), batch)
        losses = jax.lax.map(
            lambda b: jax.checkpoint(loss_fn)(params, b, rng)[0], slices)
        return losses.mean(), {}
    return sliced


def _update_error(params0, got, want):
    """Relative L2 error of the update ``got - params0`` against
    ``want - params0``, over the whole tree."""
    num = den = 0.0
    for p0, a, b in zip(*(jax.tree_util.tree_leaves(t)
                          for t in (params0, got, want))):
        ua = np.asarray(a, np.float64) - p0
        ub = np.asarray(b, np.float64) - p0
        num += float(np.sum((ua - ub) ** 2))
        den += float(np.sum(ub ** 2))
    _check(den > 0, "the reference step did not move the parameters")
    return math.sqrt(num / den)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the path across four chips and what it is "
                         "compared with, and no one-chip phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX finds; never "
                         "prints \"ok\": true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import horovod_tpu as hvd
    from horovod_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    smoke = Smoke(TINY if args.rehearse else REAL, args.seed, args.rehearse)
    device = smoke.device(args.chips, cache_dir)
    hvd.init()
    mesh = hvd.mesh()
    if args.chips == 4:
        smoke.four_chips(mesh)
    else:
        smoke.resnet50(mesh)
        smoke.gpt2_small_flash(mesh)
        smoke.flash_vs_reference()
        smoke.engine()
    hvd.shutdown()
    # without --rehearse the device phase let nothing but a TPU through
    print(json.dumps({"ok": not args.rehearse, "device": device}))


if __name__ == "__main__":
    main()
