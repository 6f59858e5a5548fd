#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``benchmark/configs/<config>.json`` and ``.py``) under a traffic mix
(``benchmark/traffic/<mix>.json``). One process: ``hvd.init()``, weights and
batch made on the device from ``--seed``, the step a user gets from
``dp.make_*train_step(donate=True)``, the float32 reference check, warm-up,
then timed blocks of steps for ``--seconds`` (see harness/timing.py). The
last line of standard output is the result, one JSON object; earlier lines
are facts of the run. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` profiles a few blocks and reports its per-layer metrics.

Without a TPU, with fewer chips than the cell asks for, or on a device kind
that ``harness/peaks.json`` does not list, the run exits non-zero and prints
no result. ``--rehearse`` is for the sandbox: the files' ``rehearse`` sizes
on whatever JAX finds; its last line says ``"rehearsal": true`` and is no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # as close to the start of the process as it gets

import argparse  # noqa: E402
import functools  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import horovod_tpu as hvd  # noqa: E402
from harness import (compile_log, hlo_text, kernels, peaks,  # noqa: E402
                     roofline, spec as spec_lib, timing, trace_reduce)
from harness.job import Run  # noqa: E402
from horovod_tpu.common.compile_cache import (  # noqa: E402
    enable_compile_cache)
from horovod_tpu.jax.compression import Compression  # noqa: E402
from horovod_tpu.parallel import dp, mesh as mesh_lib, zero  # noqa: E402

# four chips against one: two programs that the compiler fuses differently
# (chip_smoke.py's LOSS_RTOL, PR 21 measured 4.7e-6)
SHARD_LOSS_RTOL = 1e-3
REHEARSAL_PEAKS = "TPU v5 lite"  # stands in where the rehearsal has no chip


def say(**facts):
    """An earlier line: facts of the run, never the result."""
    print(json.dumps(facts), flush=True)


def fail(message):
    sys.exit(f"benchmark/run.py: {message}")


class Cell:
    """One run of one cell."""

    def __init__(self, args):
        self.args = args
        self.spec = spec_lib.load()
        self.cell = spec_lib.workload(self.spec, args.workload)
        self.traffic = spec_lib.traffic(self.cell["traffic"], args.rehearse)
        config, builder = spec_lib.config(self.spec, self.cell["config"],
                                          args.rehearse)
        self.chips = int(self.cell["chips"])
        if self.chips != int(self.traffic["chips"]):
            fail(f"{args.workload} asks for {self.chips} chips, its traffic "
                 f"mix for {self.traffic['chips']}")

        devices = jax.devices()
        if devices[0].platform != "tpu" and not args.rehearse:
            fail(f"no TPU: jax.devices() is {devices}")
        if len(devices) < self.chips:
            fail(f"{args.workload} needs {self.chips} chip(s), JAX finds "
                 f"{len(devices)}")
        self.devices = devices[:self.chips]
        kind = self.devices[0].device_kind
        try:
            self.peaks = peaks.load(kind)
        except peaks.UnknownDevice as e:
            if not args.rehearse:
                fail(str(e))
            self.peaks = peaks.load(REHEARSAL_PEAKS)

        self.cache_dir = enable_compile_cache()
        # every program, however small, is found again by the next run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.log = compile_log.CompileLog()

        hvd.init(devices=self.devices)
        self.mesh = hvd.mesh()
        self.init_s = time.perf_counter() - T0

        self.job = spec_lib.load_module(builder).build(config, self.traffic)
        self.block_steps = int(self.traffic["block_steps"])
        self.per_chip = int(self.traffic["per_chip_batch"])
        self.items_per_step_per_chip = \
            self.per_chip * self.job.items_per_example
        self.key_params, self.key_batch = jax.random.split(
            jax.random.key(args.seed))
        self.step_key = jax.random.key(1)
        self.setup_marks = {}      # phase -> seconds, for PERF.md's split
        self.windows_s = 0.0       # host seconds spent inside timed windows
        self.programs_in_windows = 0
        self.dispatch_seconds = []
        self.checks = {}
        self.compared = {}         # name -> [number, its limit]

    def mark(self, name, since):
        self.setup_marks[name] = round(
            self.setup_marks.get(name, 0.0) + time.perf_counter() - since, 3)

    # -- state ------------------------------------------------------------------

    def make_state(self, program):
        """Fresh parameters and optimizer state on ``program``'s mesh, from
        the seed: the same values every time."""
        mesh, job, t0 = program.mesh, self.job, time.perf_counter()
        replicated = mesh_lib.replicated(mesh)
        params, model_state = jax.jit(
            job.init, out_shardings=replicated)(self.key_params)
        params = dp.replicate(params, mesh)
        if program.step_kwargs.get("sharded_update"):
            bucket = {k: v for k, v in program.step_kwargs.items()
                      if k == "bucket_bytes"}
            opt_state = zero.sharded_opt_init(job.optimizer, params, mesh,
                                              **bucket)
        else:
            opt_state = jax.jit(job.optimizer.init,
                                out_shardings=replicated)(params)
        state = (params, opt_state)
        if job.stateful:
            state += (dp.replicate(model_state, mesh),)
        jax.block_until_ready(state)
        self.mark("weights_s", t0)
        return state

    def global_batch(self):
        n = self.per_chip * self.chips
        return jax.jit(functools.partial(self.job.make_batch, n=n))(
            self.key_batch)

    # -- correctness (1): the float32 reference -----------------------------------

    def check_reference(self, batch):
        """Loss and the gradients of the named leaves from the program's own
        loss function against the plain float32 reference, on a seeded
        sample of the cell's batch, on one chip."""
        job, t0 = self.job, time.perf_counter()
        device = self.devices[0]
        sample = jax.device_put(jax.tree_util.tree_map(
            lambda x: x[:job.sample_examples], batch), device)
        params, model_state = jax.jit(job.init)(self.key_params)
        params = jax.device_put(job.check_params(params), device)

        # the sample, the state and the key are arguments, not constants of
        # the programs: another seed then finds both in the compile cache
        def program_loss(p, model_state, sample, key):
            if job.stateful:
                return job.loss_fn(p, model_state, sample, key)[0]
            return job.loss_fn(p, sample, key)[0]

        def reference_loss(p, model_state, sample, key):
            return job.reference_loss(p, model_state, sample)

        def loss_and_picked_gradients(loss):
            def run(*args):
                value, grads = jax.value_and_grad(loss)(*args)
                picked = []
                for path in job.check_leaves:
                    leaf = grads
                    for k in path:
                        leaf = leaf[k]
                    picked.append(leaf)
                return value, picked
            return jax.jit(run)

        args = (params, model_state, sample, self.step_key)
        got_loss, got = loss_and_picked_gradients(program_loss)(*args)
        want_loss, want = loss_and_picked_gradients(reference_loss)(*args)
        got_loss, want_loss = float(got_loss), float(want_loss)
        loss_error = abs(got_loss - want_loss) / abs(want_loss)
        errors = {}
        for path, a, b in zip(job.check_leaves, got, want):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            if a.shape != b.shape:  # the stem's padded channels
                a = a[tuple(slice(0, n) for n in b.shape)]
            errors["/".join(path)] = float(
                np.linalg.norm(a - b) / np.linalg.norm(b))
        tol = job.tolerance
        classes = {}  # name under ``compared`` -> (limit, the leaves' errors)
        for path, error in zip(job.check_leaves, errors.values()):
            under, limit = tol.gradient_limit(path)
            name = "gradient_relative_l2_error" + (under and "." + under)
            classes.setdefault(name, (limit, []))[1].append(error)
        ok = math.isfinite(got_loss) and loss_error <= tol.loss_rtol and \
            all(e <= limit for limit, found in classes.values()
                for e in found)
        self.checks["reference"] = ok
        self.compared["loss_relative_error"] = [loss_error, tol.loss_rtol]
        for name, (limit, found) in classes.items():  # a NaN is the largest
            self.compared[name] = [
                max(found, key=lambda e: (e != e, e)), limit]
        say(check="float32 reference", ok=ok,
            sample_examples=job.sample_examples, loss_program=got_loss,
            loss_reference=want_loss, loss_relative_error=loss_error,
            loss_rtol=tol.loss_rtol, gradient_relative_l2_error=errors,
            gradient_tolerance=tol.grad_rel_l2,
            gradient_tolerance_under=tol.grad_rel_l2_under,
            tolerance_reason=tol.reason)
        del got, want, params, sample, args
        self.mark("reference_check_s", t0)

    def timed(self, program, state, seconds):
        state, window = timing.run_window(
            program.call, state, seconds, self.block_steps, self.log)
        self.windows_s += window.ended - window.started
        self.programs_in_windows += window.programs_compiled
        self.dispatch_seconds += window.dispatch_seconds
        return state, window

    def peak_bytes(self):
        """The peak on the fullest chip, from the runtime's own counters:
        the allocator's high-water mark of arrays plus the high-water mark
        of what it reserved for the programs' temporaries. On the v5e
        ``peak_bytes_in_use`` holds the arrays alone (0.38 GB for ResNet-50
        at 256 images) and ``peak_bytes_reserved`` the temporaries (9.12 GB,
        where the compiler counts 9.16; my chip run, PR 22)."""
        stats = [d.memory_stats() or {} for d in self.devices]
        return max(int(s.get("peak_bytes_in_use", 0)) +
                   int(s.get("peak_bytes_reserved", 0)) for s in stats)


class Program:
    """The step a user gets from ``dp.make_*train_step`` on one mesh, its
    compiled text, and the batch it runs on."""
    def __init__(self, cell, mesh, label):
        self.cell, self.mesh, self.label = cell, mesh, label
        job = cell.job
        self.step_kwargs = dict(cell.traffic.get("step", {}))
        kwargs = dict(self.step_kwargs)
        if isinstance(kwargs.get("compression"), str):
            kwargs["compression"] = getattr(Compression,
                                            kwargs["compression"])
        make = dp.make_stateful_train_step if job.stateful else \
            dp.make_train_step
        self.step = make(job.loss_fn, job.optimizer, mesh, donate=True,
                         **kwargs)
        self.n_state = 3 if job.stateful else 2
        self.batch = None
        self.hlo = None

    def compile(self, state):
        """Ahead of time, once: the text that is inspected is the text of
        the executable the persistent cache then hands the step's first
        call."""
        t0 = time.perf_counter()
        compiled = self.step.lower(*state, self.batch,
                                   self.cell.step_key).compile()
        text = compiled.as_text()
        self.hlo = hlo_text.HloIndex(text)
        memory = compiled.memory_analysis()
        ring = 2 * (self.mesh.devices.size - 1) / self.mesh.devices.size
        say(program=self.label, module=self.hlo.module,
            chips=int(self.mesh.devices.size),
            tpu_custom_calls=len(self.hlo.kernels()),
            collectives={k: {"count": c, "payload_bytes": b,
                             "ring_wire_bytes_per_chip": int(ring * b)}
                         for k, (c, b) in
                         self.hlo.collective_payload().items()},
            argument_bytes=getattr(memory, "argument_size_in_bytes", None),
            temp_bytes=getattr(memory, "temp_size_in_bytes", None))
        self.cell.mark("lower_and_compile_s", t0)

    def call(self, state):
        out = self.step(*state, self.batch, self.cell.step_key)
        return tuple(out[:self.n_state]), out.loss


def warm_up(cell, program, state):
    """The blocks before the window: the first call loads the executable
    the ahead-of-time compile left in the cache. Returns the state and the
    losses, fetched."""
    t0 = time.perf_counter()
    window = timing.Window()
    for _ in range(int(cell.traffic["warmup_blocks"])):
        state = timing.run_block(program.call, state, cell.block_steps,
                                 window)
    losses = [float(x) for x in window.losses]
    cell.mark("warmup_s", t0)
    return state, losses


def one_chip_reference(cell, batch, seconds):
    """The same per-chip batch on one chip of the host, in the same run:
    step 0 of every shard from the same weights (their mean is what the
    mesh's step 0 must give), then a timed window on the first shard.
    Returns (block rates, the window's losses, the shards' losses); the
    one-chip state is gone when it returns, so that the peak is one job's."""
    mesh1 = mesh_lib.data_parallel_mesh(cell.devices[:1])
    one = Program(cell, mesh1, "one_chip_reference")
    shards = [dp.shard_batch(jax.tree_util.tree_map(
        lambda x, i=i: x[i * cell.per_chip:(i + 1) * cell.per_chip], batch),
        mesh1) for i in range(cell.chips)]
    state = cell.make_state(one)
    one.batch = shards[0]
    one.compile(state)
    t0 = time.perf_counter()
    shard_losses = []
    for shard in shards:
        one.batch = shard
        state, loss = one.call(state)
        shard_losses.append(float(loss))
        del state  # donated and spent: fresh weights for the next shard
        state = cell.make_state(one)
    one.batch = shards[0]
    cell.mark("shard_losses_s", t0)
    state, _ = warm_up(cell, one, state)
    state, window = cell.timed(one, state, seconds)
    say(phase="one_chip_reference", shard_losses=shard_losses,
        blocks=len(window.block_seconds),
        peak_bytes_in_use=cell.peak_bytes())
    return (timing.block_rates(window, cell.items_per_step_per_chip,
                               cell.block_steps),
            window.losses, shard_losses)


def run(args):
    cell = Cell(args)
    job, traffic = cell.job, cell.traffic
    say(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rehearse=args.rehearse, cache_dir=cell.cache_dir,
        init_s=cell.init_s, model_flops_per_item=job.model_flops_per_item,
        unit=job.unit, items_per_step_per_chip=cell.items_per_step_per_chip,
        **job.facts)

    t0 = time.perf_counter()
    batch = jax.block_until_ready(cell.global_batch())
    cell.mark("batch_s", t0)
    cell.check_reference(batch)

    seconds = float(args.seconds)
    if args.trace:
        seconds = max(seconds / 4, 2.0)  # the traced stretch comes on top
    reference = traffic.get("one_chip_reference") if cell.chips > 1 else None
    one_rates, one_losses, shard_losses = None, [], None
    if reference:
        share = float(reference["share_of_seconds"])
        one_rates, one_losses, shard_losses = one_chip_reference(
            cell, batch, seconds * share)
        seconds *= 1 - share

    main = Program(cell, cell.mesh, "step")
    state = cell.make_state(main)
    main.batch = dp.shard_batch(batch, cell.mesh)
    del batch
    main.compile(state)
    state, warm_losses = warm_up(cell, main, state)

    # correctness (2): training moves, and stays finite
    cell.checks["warmup_loss"] = all(map(math.isfinite, warm_losses)) and \
        warm_losses[-1] < warm_losses[0]
    cell.compared["warmup_loss_last_less_step_0"] = [
        warm_losses[-1] - warm_losses[0], 0.0]
    say(check="loss after warm-up", ok=cell.checks["warmup_loss"],
        steps=len(warm_losses), loss_step_0=warm_losses[0],
        loss_last=warm_losses[-1])
    if shard_losses is not None:
        # correctness (3): the mesh's step 0 is the mean of the shards'
        want = statistics.fmean(shard_losses)
        error = abs(warm_losses[0] - want) / abs(want)
        cell.checks["shard_mean"] = error <= SHARD_LOSS_RTOL
        cell.compared["shard_mean_relative_error"] = [error, SHARD_LOSS_RTOL]
        cell.checks["all_reduce"] = args.rehearse or \
            "all-reduce" in main.hlo.collective_payload()
        say(check="step 0 against the shards' mean on one chip",
            ok=cell.checks["shard_mean"], loss=warm_losses[0],
            shard_mean=want, relative_error=error, rtol=SHARD_LOSS_RTOL,
            all_reduce_in_compiled_text=cell.checks["all_reduce"])
    # correctness (4): the kernels this cell's metrics read are in the
    # step, and no flash kernel where the job says XLA attention
    missing = kernels.missing(job, main.hlo)
    unasked = kernels.unasked(job, main.hlo)
    cell.checks["kernels"] = args.rehearse or not (missing or unasked)
    cell.compared.update(
        required_kernels_missing=[0 if args.rehearse else len(missing), 0],
        flash_kernels_not_asked_for=[len(unasked), 0])
    say(check="the kernels this cell's metrics read are in the compiled "
        "step", ok=cell.checks["kernels"], required=kernels.required(job),
        missing=missing, not_asked_for=unasked,
        tpu_custom_calls=kernels.inventory(main.hlo))

    setup_s = time.perf_counter() - T0 - cell.windows_s
    state, window = cell.timed(main, state, seconds)
    rates = timing.block_rates(window, cell.items_per_step_per_chip,
                               cell.block_steps)
    trace = None
    if args.trace:
        state, trace = traced_stretch(cell, main, state)

    # after the windows: losses, memory, and the chips' parameters
    losses = [float(x) for x in one_losses + window.losses]
    failed = sum(not math.isfinite(x) for x in losses)
    peak = cell.peak_bytes()
    if cell.chips > 1:
        cell.checks["params_identical"] = identical_on_every_chip(
            state[0], cell.mesh)
        say(check="parameters bit-identical on every chip",
            ok=cell.checks["params_identical"])
    del state
    cell.checks["no_compile_in_window"] = cell.programs_in_windows == 0
    cell.checks["finite"] = failed == 0
    cell.compared.update(programs_in_windows=[cell.programs_in_windows, 0],
                         losses_not_finite=[failed, 0])

    q1, median, q3 = timing.quartiles(rates)
    values = {f"{job.unit}_per_s_per_chip": median,
              "peak_hbm_gb": peak / 1e9, "setup_s": setup_s}
    spans = {"rate_quartiles": [q1, median, q3],
             "rate_spread": (q3 - q1) / median, "blocks": len(rates),
             "steps": window.steps}
    if one_rates:
        r1, rmed, r3 = timing.quartiles(one_rates)
        values["scaling_efficiency"] = 100.0 * median / rmed
        spans.update(one_chip_rate_quartiles=[r1, rmed, r3],
                     one_chip_blocks=len(one_rates))
    say(window=spans, setup_split=cell.setup_marks, setup_s=setup_s,
        init_s=cell.init_s, compile_s=cell.log.seconds,
        programs=cell.log.programs, cache_hits=cell.log.hits,
        cache_misses=cell.log.misses,
        programs_in_windows=cell.programs_in_windows,
        host_dispatch_ms_median=1e3 * statistics.median(
            cell.dispatch_seconds),
        loss_first=losses[0], loss_last=losses[-1], checks=cell.checks,
        memory_stats=cell.devices[0].memory_stats() or {})

    device = {"platform": cell.devices[0].platform,
              "kind": cell.devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": peak}
    result = {"correct": all(cell.checks.values()),
              "attempted": len(losses), "failed": failed, "metrics": {},
              "device": device}
    if args.trace:
        report_per_layer(cell, main, trace, result)
    else:
        for metric in spec_lib.metrics(cell.spec, "end_to_end",
                                       args.workload):
            if metric["name"] not in values:
                fail(f"{args.workload} reports no {metric['name']}")
            result["metrics"][metric["name"]] = {
                "value": float(values[metric["name"]]),
                "unit": metric["unit"]}
    if args.rehearse:
        result["rehearsal"] = True  # sizes and platform of the sandbox
    # each number compared beside its limit: last on the line, and last on
    # standard error
    result["compared"] = {
        name: [x if math.isfinite(x) else repr(x) for x in pair]
        for name, pair in cell.compared.items()}  # "nan" keeps the line JSON
    hvd.shutdown()
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} limit {limit}", file=sys.stderr)
    print(f"correct: {result['correct']} checks: {cell.checks}",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def report_per_layer(cell, program, trace, result):
    """Each per-layer metric of the cell from its reader; busy and window
    seconds and the breakdown from the reduced trace."""
    facts = Run(job=cell.job, chips=cell.chips, block_steps=cell.block_steps,
                peaks=cell.peaks, hlo=program.hlo,
                program=program.hlo.module, init_s=cell.init_s,
                compile_s=cell.log.seconds,
                programs_after_warmup=cell.programs_in_windows,
                dispatch_seconds=cell.dispatch_seconds,
                items_per_step_per_chip=cell.items_per_step_per_chip)
    for metric in spec_lib.metrics(cell.spec, "per_layer",
                                   cell.args.workload):
        value = spec_lib.layer_reader(metric["name"])(trace, facts)
        if value is not None:
            result["metrics"][metric["name"]] = {
                "value": float(value), "unit": metric["unit"]}
    if trace.devices:
        busy_s, window_s = trace_reduce.busy_and_window_seconds(trace)
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown(trace, program.hlo)
        say(flash_bound=roofline.flash_bound(facts),
            step_device_ms=1e3 * trace_reduce.median_step_seconds(
                trace, program.hlo.module))
    elif not cell.args.rehearse:
        fail("the traced run found no device plane in its trace")


def identical_on_every_chip(params, mesh) -> bool:
    """Every parameter bit for bit the same on all chips of the mesh,
    compared on the devices: the largest and the smallest of each bit
    pattern over the mesh are equal."""
    axes = tuple(mesh.axis_names)

    def local(tree):
        same = jnp.bool_(True)
        for leaf in jax.tree_util.tree_leaves(tree):
            bits = jax.lax.bitcast_convert_type(
                leaf, jnp.uint32 if leaf.dtype.itemsize == 4 else jnp.uint16)
            same &= jnp.all(jax.lax.pmax(bits, axes) ==
                            jax.lax.pmin(bits, axes))
        return same
    return bool(jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))(params))


def traced_stretch(cell, program, state):
    """Profile a few blocks of the steady state with the benchmark's own
    host spans around block, dispatch and sync; reduce the trace."""
    log_dir = HERE / ".trace" / cell.args.workload
    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # the spans below are enough
    options.host_tracer_level = 2
    window = timing.Window()
    programs_before = cell.log.programs
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        for _ in range(int(cell.traffic["trace_blocks"])):
            state = timing.run_block(program.call, state, cell.block_steps,
                                     window, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    cell.programs_in_windows += cell.log.programs - programs_before
    path = trace_reduce.newest_xplane(str(log_dir))
    if cell.args.keep_trace:
        keep = Path(cell.args.keep_trace)
        keep.mkdir(parents=True, exist_ok=True)
        with open(path, "rb") as src, gzip.open(
                keep / f"{cell.args.workload}.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    trace = trace_reduce.load(path)
    say(traced_blocks=len(window.block_seconds), xplane=str(
        Path(path).relative_to(HERE)), device_planes=len(trace.devices),
        host_spans=len(trace.host))
    return state, trace


def breakdown(trace, hlo) -> dict:
    """The ten device operations that took most time, under names that stay:
    a kernel by its function, a collective by its ``hvd_*`` scope, the rest
    by the kind of HLO instruction; and the idle gaps by what the host was
    doing."""
    def name(span):
        ins = hlo.get(span.name)
        if ins is None:
            return "not in the step: " + span.name.split(".")[0]
        if hlo.is_kernel(ins):
            return hlo.kernel_name(ins)
        if hlo.is_collective(ins):
            return f"{hlo.scope(ins) or 'collective'} ({ins.opcode})"
        return hlo.category(ins)

    def top(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(trace_reduce.op_seconds_by(trace, name)),
            "idle_gaps": top(trace_reduce.idle_by_host_span(trace))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the files' rehearse sizes on whatever JAX finds; "
                         "the last line is marked and is no result")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also copy the traced run's .xplane.pb to DIR")
    run(ap.parse_args(argv))


if __name__ == "__main__":
    main()
