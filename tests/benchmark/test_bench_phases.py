"""The readers of the program's own marks, on hand-built traces and a
hand-written compiled text with known answers: phase, direction and block of
an instruction, the inheritance rule for operations the compiler made, parts
that add up to the busy time, the step wrapper's ``hvd.*`` host spans, and
the six per-layer readers on top of them."""

import time

import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
from harness import hlo_text, phases, program_spans as ps
from harness import spec as spec_lib
from harness import trace_reduce
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

MS = 1e6  # nanoseconds
STEP = "jit(_local_step)/shard_map"
FB = STEP + "/phase_forward_backward"


def line(name, op_name=None, opcode="add"):
    meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = f32[8]{{0}} {opcode}(%a, %a){meta}\n"


INSTRUCTIONS = [
    # name, op_name, phase, direction, block
    ("attn.1", FB + "/jvp(GptDecoder)/EncoderBlock_0/FlashSelfAttention_0/"
     "pallas_call", "forward_backward", "forward",
     "EncoderBlock/FlashSelfAttention"),
    ("head.1", FB + "/jvp(GptDecoder)/Embed_0.attend/dot_general",
     "forward_backward", "forward", "Embed.attend"),
    ("pos.1", FB + "/jvp(GptDecoder)/add", "forward_backward", "forward",
     "GptDecoder"),
    ("loss.1", FB + "/jvp(jit(take_along_axis))/gather", "forward_backward",
     "forward", "loss"),
    ("dense.1", FB + "/transpose(jvp(GptDecoder))/EncoderBlock_11/Dense_1/"
     "dot_general", "forward_backward", "backward", "EncoderBlock/Dense"),
    ("dkv.1", FB + "/transpose(phase_forward_backward)/jvp(GptDecoder)/"
     "EncoderBlock_7/FlashSelfAttention_0/pallas_call", "forward_backward",
     "backward", "EncoderBlock/FlashSelfAttention"),
    ("conv.1", "jit(_local_step)/phase_forward_backward/transpose(jvp("
     "ResNet))/BottleneckBlock_3/Conv_0/conv_general_dilated",
     "forward_backward", "backward", "BottleneckBlock/Conv"),
    ("lossb.1", FB + "/transpose(jvp())/mul", "forward_backward",
     "backward", "loss"),
    ("psum.1", STEP + "/phase_grad_exchange/hvd_allreduce_average/psum",
     "grad_exchange", "forward", None),
    ("pack.1", STEP + "/phase_grad_exchange/reshape", "grad_exchange",
     "forward", None),
    ("adam.1", STEP + "/phase_optimizer_update/add", "optimizer_update",
     "forward", None),
    ("sync.1", STEP + "/phase_output_sync/hvd_allreduce_average/psum",
     "output_sync", "forward", None),
    ("gather.1", STEP + "/phase_param_gather/all_gather", "param_gather",
     "forward", None),
    ("param.1", "params['EncoderBlock_0']['Dense_0']['kernel']", None,
     "forward", None),
    ("dus.1", None, None, "forward", None),
]


def text(instructions):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" +
            "".join(line(name, op_name) for name, op_name, *_ in
                    instructions) + "}\n")


@pytest.fixture(scope="module")
def hlo():
    return hlo_text.HloIndex(text(INSTRUCTIONS))


def run_of(hlo):
    return Run(job=None, chips=1, block_steps=2, peaks={}, hlo=hlo,
               program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=1.0)


reader = spec_lib.layer_reader


def ops(*named):
    return [Span(name, start * MS, end * MS) for name, start, end in named]


# -- an instruction's phase, direction and block -------------------------------

@pytest.mark.parametrize("name,op_name,phase,way,block", INSTRUCTIONS,
                         ids=[i[0] for i in INSTRUCTIONS])
def test_phase_direction_and_block(hlo, name, op_name, phase, way, block):
    ins = hlo.get(name)
    assert phases.phase_of(ins) == phase
    assert phases.direction(ins) == way
    assert phases.block_of(ins) == block


def test_an_event_that_is_not_in_the_text_has_no_phase(hlo):
    assert hlo.get("fusion.999") is None
    assert phases.phase_of(None) is None
    # the collective keeps its own name: its first hvd_* scope is its own
    assert hlo.scope(hlo.get("psum.1")) == "hvd_allreduce_average"


# -- the inheritance rule, and parts that add up -------------------------------

def two_steps():
    """Two step runs on one chip. In the first: an unnamed operation before
    any phase (forward), forward, backward, an unnamed one after it
    (backward), the exchange with the packed buffer's unnamed
    dynamic-update-slice inside it, the update. The second run starts
    afresh: its leading unnamed operation is forward again, not the update
    of the run before. One more operation runs outside every step run."""
    return DeviceTrace(0, ops=ops(
        ("copy-start.7", 0, 1),      # before any phase: forward, inherited
        ("attn.1", 1, 11),           # forward 10
        ("dense.1", 11, 31),         # backward 20
        ("copy-done.7", 31, 33),     # inherits backward 2
        ("pack.1", 33, 36),          # grad_exchange 3
        ("dus.1", 36, 40),           # inherits grad_exchange 4
        ("psum.1", 40, 45),          # grad_exchange 5
        ("adam.1", 45, 50),          # optimizer_update 5
        ("param.1", 52, 53),         # outside every step run: not counted
        ("dus.1", 60, 62),           # second run, before any phase: forward
        ("attn.1", 62, 70),          # forward 8
        ("dense.1", 70, 90),         # backward 20
        ("adam.1", 90, 96),          # optimizer_update 6
        ("sync.1", 96, 97),          # output_sync 1
    ), modules=[Span("jit__local_step(1)", 0, 50 * MS),
                Span("jit__local_step(1)", 60 * MS, 97 * MS)])


def host_blocks(*blocks):
    return [Span("bench.block", lo * MS, hi * MS) for lo, hi in blocks]


@pytest.fixture()
def trace():
    return Trace(devices=[two_steps()], host=host_blocks((0, 100)))


def test_an_unnamed_operation_inherits_the_phase_before_it(hlo, trace):
    found = phases.reduce(trace, hlo, hlo.module)
    assert found.steps == 2
    per_step_ms = {k: 1e3 * v for k, v in found.seconds.items()}
    assert per_step_ms == pytest.approx({
        "forward": (1 + 10 + 2 + 8) / 2, "backward": (20 + 2 + 20) / 2,
        "grad_exchange": (3 + 4 + 5) / 2, "optimizer_update": (5 + 6) / 2,
        "output_sync": 1 / 2})
    inherited_ms = {k: 1e3 * v for k, v in found.inherited.items()}
    assert inherited_ms == pytest.approx({
        "forward": (1 + 2) / 2, "backward": 2 / 2, "grad_exchange": 4 / 2})


def test_parts_add_up_to_the_busy_time_inside_step_runs(hlo, trace):
    # a while loop holds its body's operations: self time, counted once
    trace.devices[0].ops += ops(("loss.1", 62, 66), ("lossb.1", 63, 64))
    found = phases.reduce(trace, hlo, hlo.module)
    assert 1e3 * found.busy == pytest.approx((50 + 37) / 2)
    assert found.total == pytest.approx(found.busy)
    # attn.1 62-70 keeps 4 ms of its own; loss.1 3, lossb.1 1
    assert 1e3 * found.blocks["EncoderBlock/FlashSelfAttention"][0] == \
        pytest.approx((10 + 4) / 2)
    assert [1e3 * x for x in found.blocks["loss"]] == \
        pytest.approx([3 / 2, 1 / 2])


def test_chips_are_averaged(hlo):
    second = two_steps()
    second.ordinal = 1
    second.ops = [s._replace(name="adam.1") if s.name == "psum.1" else s
                  for s in second.ops]
    trace = Trace(devices=[two_steps(), second],
                  host=host_blocks((0, 100)))
    found = phases.reduce(trace, hlo, hlo.module)
    # chip 0: exchange 12, update 11; chip 1: exchange 7, update 16
    assert 1e3 * found.seconds["grad_exchange"] == pytest.approx(
        (12 + 7) / 4)
    assert 1e3 * found.seconds["optimizer_update"] == pytest.approx(
        (11 + 16) / 4)
    assert found.total == pytest.approx(found.busy)


def test_a_fusion_that_holds_another_phase_is_counted_whole_and_named():
    """The weight gradient's matmul with the optimizer's update in its
    epilogue: backward by its own op_name, and printed apart."""
    fused = hlo_text.HloIndex(
        "HloModule jit__local_step, is_scheduled=true\n\n"
        "%fused_computation.1 (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n" +
        line("dw.1", FB + "/transpose(jvp(M))/Dense_0/dot_general") +
        line("adam.2", STEP + "/phase_optimizer_update/add") + "}\n\n"
        "ENTRY %main (a: f32[8]) -> f32[8] {\n"
        "  %a = f32[8]{0} parameter(0)\n"
        "  %fusion.1 = f32[8]{0} fusion(%a), kind=kOutput, "
        "calls=%fused_computation.1, metadata={op_name=\"" + FB +
        "/transpose(jvp(M))/Dense_0/dot_general\"}\n" +
        line("adam.1", STEP + "/phase_optimizer_update/add") + "}\n")
    assert phases.guests(fused, fused.get("fusion.1")) == \
        ("optimizer_update",)
    trace = Trace(devices=[DeviceTrace(
        0, ops=ops(("fusion.1", 0, 8), ("adam.1", 8, 10)),
        modules=[Span("jit__local_step(1)", 0, 10 * MS)])],
        host=host_blocks((0, 10)))
    found = phases.reduce(trace, fused, fused.module)
    assert {k: 1e3 * v for k, v in found.seconds.items()} == pytest.approx(
        {"backward": 8.0, "optimizer_update": 2.0})
    assert {k: 1e3 * v for k, v in found.fused_across.items()} == \
        pytest.approx({"backward+optimizer_update": 8.0})


def test_a_text_without_phases_reads_nothing(trace, capsys):
    bare = hlo_text.HloIndex(text(
        [(n, o.replace("phase_", "") if o else o, *rest)
         for n, o, *rest in INSTRUCTIONS]))
    assert not phases.has_phases(bare)
    assert phases.seconds_per_step(trace, run_of(bare)) is None
    assert phases.inherited_share(trace, run_of(bare)) is None
    assert capsys.readouterr().out == ""


# -- the step wrapper's spans --------------------------------------------------

def program_spans():
    """Three calls of a wrapped step: hvd.step holds hvd.step.dispatch; the
    first call lies before the traced stretch."""
    def call(start, number, before=0.2, after=0.1, inside=1.0):
        return [ps.ProgramSpan(ps.STEP, start * MS,
                               (start + before + inside + after) * MS,
                               number),
                ps.ProgramSpan(ps.DISPATCH, (start + before) * MS,
                               (start + before + inside) * MS)]
    return call(-5, 6) + call(2, 7) + call(10, 8, before=0.4, after=0.3)


def test_wrapper_self_seconds(trace):
    own = ps.wrapper_self_seconds(trace, program_spans())
    assert [1e3 * x for x in own] == pytest.approx([0.3, 0.7])
    assert [s.step_num for s in ps.steps_in_stretch(
        trace, program_spans())] == [7, 8]


def test_idle_by_program_span(trace):
    # the chip is idle 50-52, 53-60 and 97-100 of the stretch 0-100
    spans = [ps.ProgramSpan(ps.STEP, 48 * MS, 56 * MS, 1),
             ps.ProgramSpan(ps.DISPATCH, 52 * MS, 55 * MS)]
    idle = ps.idle_by_program_span(trace, spans)
    assert {k: 1e3 * v for k, v in idle.items()} == pytest.approx({
        ps.DISPATCH: 2.0, ps.STEP: 2.0 + 1.0, ps.OUTSIDE: 4.0 + 3.0})


@pytest.mark.parametrize("seed", range(4))
def test_split_by_is_intersect_and_subtract(seed):
    import random
    rnd = random.Random(seed)

    def disjoint(n):
        xs = sorted(rnd.sample(range(300), 2 * n))
        return [(xs[2 * i], xs[2 * i + 1]) for i in range(n)]
    for _ in range(200):
        idle, cover = disjoint(rnd.randint(0, 14)), disjoint(rnd.randint(0, 9))
        if cover and rnd.random() < 0.3:   # covers may overlap
            cover.append((cover[0][0], cover[0][1] + 40))
        inside, outside = ps.split_by(idle, cover)
        assert inside == trace_reduce.intersect(idle, cover)
        assert outside == trace_reduce.subtract(idle, cover)


def test_idle_by_program_span_at_a_chip_trace_s_size():
    """Four chips' traced stretch holds 67 000 idle gaps a chip (my chip run,
    PR 24): the reduction is one sweep, not the gaps' number squared (it took
    272 s that way, and the driver's limit is 360 s for the whole run)."""
    n = 60_000
    ops = [Span("fusion.1", 10 * i, 10 * i + 9) for i in range(n)]
    trace = Trace(devices=[DeviceTrace(0, ops, [])],
                  host=[Span("bench.block", 0, 10 * n)])
    spans = [ps.ProgramSpan(ps.STEP, 1000 * i, 1000 * i + 900, i)
             for i in range(n // 100)]
    spans += [ps.ProgramSpan(ps.DISPATCH, s.start + 100, s.start + 200)
              for s in spans]
    began = time.perf_counter()
    idle = ps.idle_by_program_span(trace, spans)
    assert time.perf_counter() - began < 5.0
    assert idle == pytest.approx({
        ps.DISPATCH: n // 100 * 10 / 1e9, ps.STEP: n // 100 * 80 / 1e9,
        ps.OUTSIDE: n // 100 * 10 / 1e9})


def _profile(log_dir, steps, pause):
    """A real profile on the CPU: one bench.block span around ``steps``
    hvd.step spans, each around an hvd.step.dispatch span."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=options)
    try:
        time.sleep(pause)
        with jax.profiler.TraceAnnotation("bench.block"):
            for i in range(steps):
                with jax.profiler.StepTraceAnnotation(ps.STEP, step_num=i):
                    with jax.profiler.TraceAnnotation(ps.DISPATCH):
                        time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()


def test_load_for_picks_the_profile_the_trace_came_from(tmp_path):
    from harness import trace_reduce
    _profile(tmp_path / "cell-a", 3, 0.0)
    mine = trace_reduce.load(trace_reduce.newest_xplane(
        str(tmp_path / "cell-a")))
    _profile(tmp_path / "cell-b", 5, 0.01)   # newer, and another run's
    assert ps.profiles(tmp_path)[0].startswith(str(tmp_path / "cell-b"))
    spans = ps.load_for(mine, root=tmp_path)
    assert [s.step_num for s in spans if s.name == ps.STEP] == [0, 1, 2]
    assert sum(s.name == ps.DISPATCH for s in spans) == 3
    assert ps.load_for(mine, root=tmp_path) is spans   # kept for the trace
    own = ps.wrapper_self_seconds(mine, spans)
    assert len(own) == 3 and all(0 <= x < 0.05 for x in own)
    stranger = Trace(host=host_blocks((1, 2)))
    assert ps.load_for(stranger, root=tmp_path) == []


# -- the six readers -----------------------------------------------------------

READERS = ["forward_ms", "backward_ms", "grad_exchange_ms",
           "optimizer_update_ms", "phase_inherited_share",
           "step_wrapper_self_ms"]


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_without_a_device_plane(hlo, name):
    read = reader(name)
    assert read(None, run_of(hlo)) is None
    cpu = Trace(devices=[], host=host_blocks((0, 100)))
    assert read(cpu, run_of(hlo)) is None


@pytest.mark.parametrize("name,want", [
    ("forward_ms", 10.5), ("backward_ms", 21.0), ("grad_exchange_ms", 6.0),
    ("optimizer_update_ms", 5.5),
    ("phase_inherited_share", 100.0 * (1.5 + 1 + 2) / 43.5),
])
def test_reader_on_the_hand_built_trace(hlo, trace, capsys, name, want):
    assert reader(name)(trace, run_of(hlo)) == pytest.approx(want)
    said = capsys.readouterr().out.strip().splitlines()
    assert len(said) == 1 and said[0].startswith('{"phases_ms"')
    # a second reader of the same run prints nothing more
    assert reader("forward_ms")(trace, run_of(hlo)) == pytest.approx(10.5)
    assert capsys.readouterr().out == ""


def test_wrapper_reader_takes_the_median(hlo, trace, monkeypatch, capsys):
    monkeypatch.setattr(ps, "load_for",
                        lambda trace, root=None: program_spans())
    assert reader("step_wrapper_self_ms")(trace, run_of(hlo)) == \
        pytest.approx(0.5)
    assert '"program_spans"' in capsys.readouterr().out
    monkeypatch.setattr(ps, "load_for", lambda trace, root=None: [])
    assert reader("step_wrapper_self_ms")(trace, run_of(hlo)) is None
