"""The four readers of the program's compile log
(``layer_metrics/hvd_init_s.py``, ``step_trace_lower_s.py``,
``step_traces.py``, ``step_recompiles.py``) on a hand-built log, on an empty
one, on a trace without a device plane, and the earlier line
``compile_log`` of a traced rehearsal."""

import json

import jax
import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
from bench_run import result_line, run_cell
from harness import program_compile_log
from harness import spec as spec_lib
from harness.trace_reduce import DeviceTrace, Trace
from horovod_tpu import metrics
from horovod_tpu.metrics import compile_log

EVENT = {"trace": "/jax/core/compile/jaxpr_trace_duration",
         "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
         "backend": "/jax/core/compile/backend_compile_duration"}
CHIP = Trace(devices=[DeviceTrace(0)])
READERS = ["hvd_init_s", "step_trace_lower_s", "step_traces",
           "step_recompiles", "step_recompiles.images"]


def feed(stage, fun_name, start, end, inside=()):
    jax.monitoring.record_scalar(EVENT[stage], start, fun_name=fun_name)
    for args in inside:
        feed(*args)
    jax.monitoring.record_event_time_span(EVENT[stage], start, end,
                                          fun_name=fun_name)


@pytest.fixture
def log(monkeypatch):
    compile_log.clear()
    compile_log.install()
    monkeypatch.setattr(program_compile_log, "_said", [])
    yield compile_log
    compile_log.uninstall()
    compile_log.clear()


@pytest.fixture
def built(log):
    """A run's log by hand: the import, ``hvd.init``, the reference check's
    program, and a step program traced ahead of time (a kernel's jit inside
    it, twice), lowered, compiled, and looked up again by its first call."""
    log.record("hvd.import", 100.0, 0.5)
    log.record("hvd.init", 101.0, 0.25)
    log.step_function("_local_step")
    feed("trace", "run", 0.0, 3.0)
    feed("lower", "jit(run)", 3.0, 4.0)
    feed("backend", "jit(run)", 4.0, 6.0)
    feed("trace", "_local_step", 10.0, 14.0, [
        ("trace", "_ssd_fwd", 11.0, 12.0), ("trace", "_ssd_fwd", 12.5, 13.0)])
    feed("lower", "jit(_local_step)", 14.0, 16.0,
         [("trace", "rule", 14.5, 15.0)])
    feed("backend", "jit(_local_step)", 16.0, 21.0)
    feed("trace", "_local_step", 30.0, 30.001)
    return log


@pytest.mark.parametrize("name,want", [
    ("hvd_init_s", 0.75),             # hvd.import 0.5 + hvd.init 0.25
    ("step_trace_lower_s", 6.001),    # 4 + 2 + 0.001, nested ones once
    ("step_traces", 2.0),             # the real one and the look-up
])
def test_reader_on_the_hand_built_log(built, capsys, name, want):
    read = spec_lib.layer_reader(name)
    assert read(CHIP, None) == pytest.approx(want)
    (said,) = capsys.readouterr().out.strip().splitlines()
    facts = json.loads(said)["compile_log"]
    assert facts["step"]["programs"] == facts["step"]["lowerings"] == 1
    assert facts["step"]["backend_s"] == pytest.approx(5.0)
    assert facts["by_stage_s"] == pytest.approx(facts["union_of_kept_s"])
    assert facts["by_function_s"] == pytest.approx(facts["union_s"])
    assert facts["union_s"] == pytest.approx(17.001)
    assert list(facts["top_functions"]) == ["_local_step", "run"]
    assert facts["top_nested"]["_ssd_fwd"] == {"count": 2, "seconds": 1.5}
    assert facts["spans"] == facts["kept"] == 12
    # a second reader of the same run prints nothing more
    assert read(CHIP, None) == pytest.approx(want)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("name", ["step_recompiles", "step_recompiles.images"])
def test_recompiles_reader_sums_the_programs_counter(built, name):
    read = spec_lib.layer_reader(name)
    metrics.timed_step(lambda: None, "bench_compile_log_test")
    before = read(CHIP, None)
    assert before is not None and before >= 0.0
    metrics.get_registry().counter(
        "hvd_step_recompiles_total", framework="bench_compile_log_test").inc()
    assert read(CHIP, None) == before + 1


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_on_an_empty_log(log, name):
    assert spec_lib.layer_reader(name)(CHIP, None) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_without_a_device_plane(built, name, capsys):
    """A rehearsal's line keeps the metrics its tests pin; the earlier line
    is printed all the same."""
    read = spec_lib.layer_reader(name)
    assert read(Trace(), None) is None and read(None, None) is None
    assert capsys.readouterr().out.startswith('{"compile_log"')


@pytest.mark.parametrize("name", READERS)
def test_reader_returns_none_where_the_program_has_no_log(monkeypatch, name):
    monkeypatch.setattr(program_compile_log, "module", lambda: None)
    assert spec_lib.layer_reader(name)(CHIP, None) is None


def test_hvd_init_s_needs_the_init_span(log):
    log.record("hvd.import", 100.0, 0.5)
    feed("trace", "f", 0.0, 1.0)
    assert spec_lib.layer_reader("hvd_init_s")(CHIP, None) is None
    assert spec_lib.layer_reader("step_traces")(CHIP, None) == 0.0


def test_traced_rehearsal_prints_the_compile_log():
    """One one-chip cell end to end: the earlier line holds what the four
    readers read (the step traced once ahead of time and looked up once by
    its first call, lowered and compiled once, no recompile), and the
    self seconds add up to the union of the intervals."""
    result, earlier = result_line(run_cell(
        "--workload", "gpt2s-t512", "--rehearse", "--seconds", "1",
        "--trace", "1"))
    assert result["metrics"]["programs_after_warmup"]["value"] == 0.0
    (facts,) = [e["compile_log"] for e in earlier if "compile_log" in e]
    assert facts["recompiles"] == 0.0
    assert facts["step"]["traces"] == 2
    assert facts["step"]["lowerings"] == facts["step"]["programs"] == 1
    assert facts["step"]["trace_lower_s"] > 0
    step = facts["top_functions"]["_local_step"]
    assert step["step"] is True and step["programs"] == 1
    assert facts["top_functions"]["run"]["programs"] == 2  # the check's two
    assert set(facts["program_spans"]) == {"hvd.import", "hvd.init",
                                           "hvd.init.mesh"}
    split = next(e for e in earlier if "setup_split" in e)
    assert facts["program_spans"]["hvd.import"] + \
        facts["program_spans"]["hvd.init"] <= split["init_s"]
    assert facts["step"]["trace_lower_s"] + facts["step"]["backend_s"] <= \
        split["setup_split"]["lower_and_compile_s"] + \
        split["setup_split"]["warmup_s"]
    assert facts["spans"] == facts["kept"]
    for total in (facts["by_stage_s"], facts["by_function_s"],
                  facts["union_s"]):
        assert total == pytest.approx(facts["union_of_kept_s"], rel=0.01)
    assert sum(facts["programs"].values()) == \
        facts["stages"]["backend"]["count"]
