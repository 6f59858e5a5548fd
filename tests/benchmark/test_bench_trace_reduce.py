"""The trace reduction on hand-built traces with known answers: busy and
idle as a union, gaps labelled by the ``bench.*`` host span that covers
them, nesting, launch gaps, exposed collective time, and the readers of the
per-layer metrics on top of them."""

import base64
import dataclasses
import importlib.util
import os

import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
from harness import flops, hlo_text, kernels, roofline, trace_reduce as tr
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

MS = 1e6  # nanoseconds


def body(*names):
    """A stand-in for a Mosaic body: base64 of bytes that hold the names."""
    return base64.b64encode(b"\0".join(n.encode() for n in names)).decode()


HLO = f"""HloModule jit__local_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {{
  %p0 = bf16[8,8]{{1,0}} parameter(0)
  ROOT %convolution.1 = bf16[8,8]{{1,0}} convolution(%p0, %p0), dim_labels=bf_io->bf
}}

%fused_computation.2 (p1: f32[8]) -> f32[8] {{
  %p1 = f32[8]{{0}} parameter(0)
  ROOT %add.1 = f32[8]{{0}} add(%p1, %p1)
}}

ENTRY %main.1_spmd (a: bf16[8,8], b: f32[8]) -> f32[8] {{
  %a = bf16[8,8]{{1,0}} parameter(0)
  %b = f32[8]{{0}} parameter(1)
  %fusion.1 = bf16[8,8]{{1,0}} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="jit(_local_step)/dot_general"}}
  %fusion.2 = f32[8]{{0}} fusion(%b), kind=kLoop, calls=%fused_computation.2
  %Attn.1 = (bf16[24,1024,64]{{2,1,0}}, f32[24,1,1024]{{2,1,0}}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="jit(_local_step)/jvp(M)/Attn/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{body("_fwd_kernel")}"}}}}
  %Attn.2 = bf16[24,1024,64]{{2,1,0}} custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="jit(_local_step)/transpose(jvp(M))/Attn/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{body("_fwd_kernel", "_bwd_dq_kernel")}"}}}}
  %Attn.3 = (bf16[24,1024,64]{{2,1,0}}, bf16[24,1024,64]{{2,1,0}}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={{op_name="jit(_local_step)/transpose(jvp(M))/Attn/pallas_call"}}, backend_config={{"custom_call_config":{{"body":"{body("_fwd_kernel", "_bwd_dkv_kernel")}"}}}}
  %other.1 = f32[8]{{0}} custom-call(%b), custom_call_target="Sharding"
  %psum.1 = f32[1000]{{0}} all-reduce(%b), channel_id=1, replica_groups={{{{0,1,2,3}}}}, to_apply=%add, metadata={{op_name="jit(_local_step)/shard_map/hvd_allreduce_average/psum"}}
  %ag-start.1 = (f32[8]{{0}}, f32[32]{{0}}) all-gather-start(%b), dimensions={{0}}, metadata={{op_name="jit(_local_step)/hvd_allgather/all_gather"}}
  ROOT %ag-done.1 = f32[32]{{0}} all-gather-done(%ag-start.1)
}}
"""


@pytest.fixture(scope="module")
def hlo():
    return hlo_text.HloIndex(HLO)


def reader(name):
    path = os.path.join(bench_paths.BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -- interval arithmetic ------------------------------------------------------

@pytest.mark.parametrize("intervals,want", [
    ([(0, 10), (5, 20), (30, 40)], [(0, 20), (30, 40)]),
    ([(5, 6), (0, 10)], [(0, 10)]),
    ([(0, 1), (1, 2)], [(0, 2)]),          # touching spans are one
    ([(3, 3), (4, 2)], []),                # empty and inverted spans vanish
])
def test_union(intervals, want):
    assert tr.union(intervals) == want


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_self_seconds_takes_nested_operations_out():
    ops = [Span("while.1", 0, 100), Span("fusion.1", 10, 40),
           Span("fusion.2", 50, 70), Span("fusion.3", 100, 130)]
    got = {s.name: sec for s, sec in tr.self_seconds(ops)}
    assert got == pytest.approx({"while.1": 50e-9, "fusion.1": 30e-9,
                                 "fusion.2": 20e-9, "fusion.3": 30e-9})


# -- a trace of two blocks of two steps ---------------------------------------

def two_block_trace():
    """One chip. Block 1: host 0-100 ms, steps on the device at 10-40 and
    42-72 ms; block 2: host 110-200 ms, steps at 120-150 and 151-181 ms.
    Each step is three operations back to back."""
    dev = DeviceTrace(0)
    for start in (10, 42, 120, 151):
        dev.modules.append(Span("jit__local_step(7)", start * MS,
                                (start + 30) * MS))
        dev.ops += [Span("fusion.1", start * MS, (start + 12) * MS),
                    Span("Attn.1", (start + 12) * MS, (start + 24) * MS),
                    Span("fusion.2", (start + 24) * MS, (start + 30) * MS)]
    dev.modules.append(Span("jit_other(3)", 300 * MS, 301 * MS))
    host = []
    for lo, hi in ((0, 100), (110, 200)):
        host += [Span("bench.block", lo * MS, hi * MS),
                 Span("bench.dispatch", lo * MS, (lo + 4) * MS),
                 Span("bench.dispatch", (lo + 4) * MS, (lo + 8) * MS),
                 Span("bench.sync", (lo + 8) * MS, hi * MS)]
    return Trace([dev], sorted(host, key=lambda s: s.start))


def test_busy_and_idle_are_a_union_over_the_traced_stretch():
    busy_s, window_s = tr.busy_and_window_seconds(two_block_trace())
    assert window_s == pytest.approx(0.200)      # first block start to last end
    assert busy_s == pytest.approx(4 * 0.030)    # four steps of 30 ms


def test_overlapping_operations_count_once():
    dev = DeviceTrace(0, ops=[Span("a", 0, 10 * MS), Span("b", 5 * MS, 20 * MS)])
    block = Span("bench.block", 0, 25 * MS)
    busy_s, window_s = tr.busy_and_window_seconds(Trace([dev], [block]))
    assert (busy_s, window_s) == pytest.approx((0.020, 0.025))
    with pytest.raises(ValueError, match="no bench.block"):
        tr.busy_and_window_seconds(Trace([dev], []))


def test_idle_time_is_labelled_by_the_host_span_that_covers_it():
    idle = tr.idle_by_host_span(two_block_trace())
    # the device idles 0-10, 40-42, 72-120, 150-151 and 181-200 ms; the host
    # dispatches 0-8 and 110-118, waits 8-100 and 118-200, and is between
    # its blocks 100-110
    assert idle["bench.dispatch"] == pytest.approx(0.008 + 0.008)
    assert idle["bench.sync"] == pytest.approx(
        0.002 + 0.002 + 0.028 + 0.002 + 0.001 + 0.019)
    assert idle[tr.BETWEEN_BLOCKS] == pytest.approx(0.010)
    assert sum(idle.values()) == pytest.approx(0.200 - 0.120)


def test_step_runs_and_launch_gaps_inside_a_block():
    trace = two_block_trace()
    assert len(tr.step_runs(trace.devices[0], "jit__local_step")) == 4
    assert tr.median_step_seconds(trace, "jit__local_step") == \
        pytest.approx(0.030)
    # the gap across the sync (72 -> 120 ms) is left out
    assert tr.launch_gaps_seconds(trace, "jit__local_step", 2) == \
        pytest.approx([0.002, 0.001])


def test_operations_by_name(hlo):
    seconds = tr.op_seconds_by(
        two_block_trace(), lambda s: hlo.category(hlo.get(s.name)))
    assert seconds == pytest.approx({"convolution/dot fusion": 4 * 0.012,
                                     "pallas kernel": 4 * 0.012,
                                     "loop fusion": 4 * 0.006})


# -- collectives ---------------------------------------------------------------

def test_exposed_collective_time_is_what_no_compute_overlaps(hlo):
    """A synchronous all-reduce of 10 ms with nothing beside it, and an
    asynchronous all-gather in flight for 20 ms of which compute covers 15."""
    dev = DeviceTrace(0, ops=[
        Span("fusion.1", 0, 10 * MS), Span("psum.1", 10 * MS, 20 * MS),
        Span("ag-start.1", 20 * MS, 21 * MS), Span("fusion.2", 21 * MS, 36 * MS),
        Span("ag-done.1", 36 * MS, 40 * MS)],
        modules=[Span("jit__local_step(1)", 0, 40 * MS)])

    def is_collective(name):
        return hlo.is_collective(hlo.get(name))

    def pair_of(name):
        return {"ag-start.1": "ag", "ag-done.1": "ag"}.get(name)
    collective = tr.collective_intervals(dev, is_collective, pair_of)
    assert collective == [(10 * MS, 40 * MS)]
    compute = [(s.start, s.end) for s in dev.ops if not is_collective(s.name)]
    assert tr.total(tr.exposed(collective, compute)) == pytest.approx(15 * MS)


def make_run(hlo, **over):
    from harness.job import Job, Tolerance
    job = Job(unit="tokens", items_per_example=1024, stateful=False,
              init=None, loss_fn=None, optimizer=None, make_batch=None,
              model_flops_per_item=1e9, reference_loss=None, check_leaves=(),
              sample_examples=1, tolerance=Tolerance(0, 0, ""),
              flash_call=(2, 1024, 12, 64, True), flash_layers=1)
    facts = dict(job=job, chips=1, block_steps=2,
                 peaks={"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12},
                 hlo=hlo, program="jit__local_step", init_s=1.5,
                 compile_s=2.5, programs_after_warmup=0,
                 dispatch_seconds=[0.001, 0.003, 0.002],
                 items_per_step_per_chip=2048)
    facts.update(over)
    return Run(**facts)


def test_collective_readers_on_the_step(hlo):
    dev = DeviceTrace(0, ops=[
        Span("fusion.1", 0, 10 * MS), Span("psum.1", 10 * MS, 20 * MS),
        Span("ag-start.1", 20 * MS, 21 * MS), Span("fusion.2", 21 * MS, 36 * MS),
        Span("ag-done.1", 36 * MS, 40 * MS)],
        modules=[Span("jit__local_step(1)", 0, 40 * MS)])
    trace, run = Trace([dev], []), make_run(hlo)
    assert reader("collective_ms")(trace, run) == pytest.approx(30.0)
    assert reader("exposed_collective_ms")(trace, run) == pytest.approx(15.0)


# -- the text index -----------------------------------------------------------

def test_kernels_are_found_by_their_function_name(hlo):
    names = {k.name: hlo.kernel_name(k) for k in hlo.kernels()}
    # a body may carry names of helpers traced for an earlier kernel: the
    # name found in the fewest bodies is the kernel's own
    assert names == {"Attn.1": "_fwd_kernel", "Attn.2": "_bwd_dq_kernel",
                     "Attn.3": "_bwd_dkv_kernel"}
    assert not hlo.is_kernel(hlo.get("other.1"))
    assert hlo.module == "jit__local_step"


# -- the kernels ``correct`` asks the compiled step for -------------------------

FWD, DQ, DKV = "_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel"
RAGGED = ["ragged-dot-none"] * 9 + ["ragged-dot-metadata"] * 2


def step_text(calls):
    """A step's text with one ``tpu_custom_call`` for each of ``calls``: a
    ``*_kernel`` name is a Pallas kernel (the name in its Mosaic body, a
    scope in its ``op_name``), any other the compiler's own call, whose
    instruction and ``op_name`` carry that word and its body no name."""
    lines = []
    for i, name in enumerate(calls):
        if name.endswith("_kernel"):
            # the backward kernels' bodies carry the forward's name too
            inner = (name,) if name == FWD else (FWD, name)
            lines.append(
                f'  %Attn.{i} = bf16[8,8]{{1,0}} custom-call(%a), '
                f'custom_call_target="tpu_custom_call", metadata={{op_name='
                f'"jit(_local_step)/jvp(M)/Attn/pallas_call"}}, '
                f'backend_config={{"custom_call_config":{{"body":'
                f'"{body(*inner)}"}}}}')
        else:
            lines.append(
                f'  %{name}.{i} = bf16[8,8]{{1,0}} custom-call(%a), '
                f'custom_call_target="tpu_custom_call", '
                f'metadata={{op_name="{name}"}}, backend_config='
                f'{{"custom_call_config":{{"body":"{body("tile")}"}}}}')
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main.1_spmd (a: bf16[8,8]) -> bf16[8,8] {\n"
            "  %a = bf16[8,8]{1,0} parameter(0)\n" + "\n".join(lines) +
            "\n  ROOT %out.1 = bf16[8,8]{1,0} copy(%a)\n}\n")


@pytest.mark.parametrize("calls,flash_layers,want_missing,want_unasked", [
    pytest.param([FWD, DQ, DKV, "_ssd_scan_kernel"], 1, {}, {},
                 id="flash_kernels_and_one_of_another_name_pass"),
    pytest.param([FWD, DQ], 1, {DKV: [0, 1]}, {},
                 id="one_flash_name_missing_fails"),
    pytest.param([FWD, FWD, DQ, DQ, DKV], 2, {DKV: [1, 2]}, {},
                 id="fewer_than_flash_layers_of_one_name_fails"),
    pytest.param(["_grouped_matmul_kernel", "_ssd_scan_kernel"] + RAGGED[:4],
                 None, {}, {},
                 id="no_flash_call_and_kernels_of_other_names_pass"),
    pytest.param([], None, {}, {}, id="no_flash_call_and_no_kernel_passes"),
    pytest.param([FWD, DQ, DKV, "_grouped_matmul_kernel"] + RAGGED[:4],
                 None, {}, {FWD: 1, DQ: 1, DKV: 1},
                 id="no_flash_call_and_the_flash_kernels_fails"),
    pytest.param([FWD, FWD] + RAGGED, None, {}, {FWD: 2},
                 id="no_flash_call_and_one_flash_name_fails"),
    pytest.param(RAGGED, 1, {FWD: [0, 1], DQ: [0, 1], DKV: [0, 1]}, {},
                 id="only_the_compilers_ragged_dots_with_flash_call_fails"),
    pytest.param([FWD, DQ, DKV] + RAGGED, 1, {}, {},
                 id="flash_kernels_beside_the_compilers_calls_pass"),
    pytest.param([FWD, FWD, DQ, DKV] + RAGGED[:3], 1, {}, {},
                 id="a_recomputed_forward_is_more_than_asked_and_passes"),
    pytest.param([FWD, DQ, DKV] * 12, 12, {}, {},
                 id="twelve_layers_thirty_six_kernels_pass"),
])
def test_the_kernels_a_cells_metrics_read_are_in_the_step(
        calls, flash_layers, want_missing, want_unasked):
    """``harness/kernels.missing`` and ``unasked`` are what ``run.py`` puts
    into ``correct``: each name of ``flops.FLASH_PRODUCTS`` at least
    ``flash_layers`` times where the job names flash shapes, none of them
    where it names none; other kernels and other counts neither pass nor
    fail it."""
    index = hlo_text.HloIndex(step_text(calls))
    over = {"flash_call": None, "flash_layers": 0} if flash_layers is None \
        else {"flash_layers": flash_layers}
    job = dataclasses.replace(make_run(index).job, **over)
    assert len(index.kernels()) == len(calls)
    assert kernels.inventory(index) == {
        name: calls.count(name) for name in sorted(set(calls))}
    assert kernels.required(job) == (
        {} if flash_layers is None
        else dict.fromkeys(flops.FLASH_PRODUCTS, flash_layers))
    assert kernels.missing(job, index) == want_missing
    assert kernels.unasked(job, index) == want_unasked


def test_a_compilers_call_is_named_by_its_one_word(hlo):
    """``ragged-dot-none.7`` and ``ragged-dot-none.12`` are one name in the
    inventory and in the breakdown; a call with no ``op_name`` at all falls
    back to its instruction's name without the number."""
    index = hlo_text.HloIndex(step_text(RAGGED).replace(
        'metadata={op_name="ragged-dot-metadata"}, ', "", 1))
    names = [index.kernel_name(k) for k in index.kernels()]
    assert names == RAGGED
    assert kernels.inventory(hlo) == {FWD: 1, DQ: 1, DKV: 1}
    assert roofline.FLASH_KERNELS == tuple(flops.FLASH_PRODUCTS) == \
        (FWD, DQ, DKV)


def test_flash_time_share_counts_the_flash_kernels_alone():
    """A step of a flash kernel, one of the compiler's ``ragged-dot`` calls
    and a kernel of another name, 10 ms each, and 10 ms of a copy: the
    share is the flash kernel's 25%, not the custom calls' 75%."""
    index = hlo_text.HloIndex(step_text(
        [FWD, "ragged-dot-none", "_ssd_scan_kernel"]))
    spans = ["Attn.0", "ragged-dot-none.1", "Attn.2", "out.1"]
    dev = DeviceTrace(0, ops=[
        Span(name, i * 10 * MS, (i + 1) * 10 * MS)
        for i, name in enumerate(spans)],
        modules=[Span("jit__local_step(1)", 0, 40 * MS)])
    trace = Trace([dev], [Span("bench.block", 0, 40 * MS)])
    run = make_run(index)
    assert reader("flash_time_share")(trace, run) == pytest.approx(25.0)
    cost = flops.flash_kernel_cost(FWD, 2, 1024, 12, 64, True)
    assert reader("flash_roofline")(trace, run) == pytest.approx(
        100 * max(cost[0] / 100e12, cost[1] / 1e12) / 0.010)
    share = dataclasses.replace(run.job, flash_call=None, flash_layers=0)
    assert reader("flash_time_share")(
        trace, dataclasses.replace(run, job=share)) == pytest.approx(25.0)


def test_collectives_scopes_and_payload(hlo):
    assert [c.name for c in hlo.collectives()] == ["psum.1", "ag-start.1"]
    assert hlo.scope(hlo.get("psum.1")) == "hvd_allreduce_average"
    assert hlo.scope(hlo.get("ag-start.1")) == "hvd_allgather"
    assert hlo.collective_payload() == {"all-reduce": [1, 4000],
                                        "all-gather": [1, 128]}


@pytest.mark.parametrize("shape,want", [
    ("f32[124439808]{0:T(1024)}", 4 * 124439808),
    ("(bf16[192,1024,64]{2,1,0:T(8,128)(2,1)}, f32[192,1,1024]{2,1,0})",
     2 * 192 * 1024 * 64 + 4 * 192 * 1024),
    ("f32[]{:T(128)}", 4),
])
def test_shape_bytes(shape, want):
    assert hlo_text.shape_bytes(shape) == want


# -- readers -------------------------------------------------------------------

def test_readers_on_the_two_block_trace(hlo):
    trace, run = two_block_trace(), make_run(hlo)
    assert reader("device_idle_share")(trace, run) == pytest.approx(40.0)
    assert reader("launch_gap_ms")(trace, run) == pytest.approx(1.5)
    # 2048 tokens x 1e9 FLOPs in 30 ms against 100 TFLOP/s
    assert reader("mfu_device")(trace, run) == \
        pytest.approx(100 * 2048e9 / 0.030 / 100e12)
    assert reader("mxu_op_share")(trace, run) == pytest.approx(40.0)
    assert reader("flash_time_share")(trace, run) == pytest.approx(40.0)
    assert reader("host_dispatch_ms")(trace, run) == pytest.approx(2.0)
    assert reader("init_s")(trace, run) == 1.5
    assert reader("compile_s")(trace, run) == 2.5
    assert reader("programs_after_warmup")(trace, run) == 0.0


def test_flash_roofline_reader(hlo):
    trace, run = two_block_trace(), make_run(hlo)
    cost = flops.flash_kernel_cost("_fwd_kernel", 2, 1024, 12, 64, True)
    least = max(cost[0] / 100e12, cost[1] / 1e12)
    # one forward kernel a step, 12 ms of device time each
    assert reader("flash_fwd_roofline")(trace, run) == \
        pytest.approx(100 * least / 0.012)
    assert reader("flash_roofline")(trace, run) == \
        pytest.approx(100 * least / 0.012)
    assert reader("flash_bwd_dq_roofline")(trace, run) is None


def test_readers_return_nothing_without_a_device_trace(hlo):
    run = make_run(hlo)
    for name in ("device_idle_share", "launch_gap_ms", "mfu_device",
                 "mxu_op_share", "flash_time_share", "flash_roofline",
                 "collective_ms", "exposed_collective_ms"):
        assert reader(name)(Trace(), run) is None, name
        assert reader(name)(None, run) is None, name


# -- reading the profiler's own format ------------------------------------------

XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 10000000 duration_ps: 20000000 } }
  lines { name: "Steps" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__local_step(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(bf16[8,8]{1,0} %a), kind=kOutput, calls=%fused_computation.1" } }
  event_metadata { key: 3 value { id: 3 name: "psum.1" } }
}
planes {
  name: "/host:CPU"
  lines { name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.block" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(step)" } }
}
"""


def test_from_profile_reads_device_planes_and_bench_spans():
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    trace = tr.from_profile(profile)
    assert [d.ordinal for d in trace.devices] == [0]
    assert [s.name for s in trace.devices[0].ops] == ["fusion.1", "psum.1"]
    assert [s.name for s in trace.devices[0].modules] == \
        ["jit__local_step(7)"]
    assert [s.name for s in trace.host] == ["bench.block"]
    op = trace.devices[0].ops[1]
    assert (op.start, op.end) == pytest.approx((11000.0, 31000.0))
    busy_s, window_s = tr.busy_and_window_seconds(trace)
    assert window_s == pytest.approx(50e-6)
    assert busy_s == pytest.approx(30e-6)
