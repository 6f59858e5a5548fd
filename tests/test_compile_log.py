"""The program's compile log (``horovod_tpu/metrics/compile_log.py``) on the
CPU: spans with parents, self times that add up to the union of the
intervals, the step's functions, a recompile that names its step in the
registry, the journal and a profile, and the log's own lifetime."""

import glob
import os
import threading

import jax
import jax.numpy as jnp
import optax
import pytest
from jax._src import monitoring as jax_monitoring

import horovod_tpu as hvd
from horovod_tpu import metrics
from horovod_tpu.common import journal
from horovod_tpu.metrics import compile_log
from horovod_tpu.parallel import dp, mesh as mesh_lib

# before any test of this file clears the log: the package's own import
IMPORT_SECONDS = compile_log.report()["program_spans"].get("hvd.import")

EVENT = {"trace": "/jax/core/compile/jaxpr_trace_duration",
         "lower": "/jax/core/compile/jaxpr_to_mlir_module_duration",
         "backend": "/jax/core/compile/backend_compile_duration"}


@pytest.fixture
def log():
    compile_log.clear()
    compile_log.install()
    yield compile_log
    compile_log.uninstall()
    compile_log.clear()


def feed(stage, fun_name, start, end, inside=(), cache=None):
    """One span as JAX announces it: the start, what happens inside (other
    spans, a cache event), the end."""
    jax.monitoring.record_scalar(EVENT[stage], start, fun_name=fun_name)
    for args in inside:
        feed(*args)
    if cache:
        jax.monitoring.record_event(f"/jax/compilation_cache/cache_{cache}")
    jax.monitoring.record_event_time_span(EVENT[stage], start, end,
                                          fun_name=fun_name)


def counter(name, **labels):
    return metrics.snapshot_value(metrics.get_registry().snapshot(), name,
                                  **labels) or 0.0


def by_name(found, fun_name, name=compile_log.TRACE):
    return [s for s in found if (s.name, s.fun_name) == (name, fun_name)]


def stage_and_function_seconds(report):
    return (sum(s["seconds"] for s in report["stages"].values()),
            sum(v for e in report["functions"].values()
                for k, v in e.items() if k.endswith("_s")))


# -- spans, parents, self time ----------------------------------------------------

@pytest.mark.parametrize("spans,self_s,parents", [
    # three in a row: nothing nested
    ([("trace", "f", 0.0, 1.0), ("lower", "jit(f)", 1.0, 3.0),
      ("backend", "jit(f)", 3.0, 7.0)],
     {"f": [1.0, 2.0, 4.0]}, {}),
    # a kernel's jit traced twice inside the step's trace, a helper in it
    ([("trace", "step", 0.0, 10.0, [
        ("trace", "kernel", 1.0, 4.0, [("trace", "helper", 2.0, 3.0)]),
        ("trace", "kernel", 5.0, 6.0)])],
     {"step": [6.0], "kernel": [2.0, 1.0], "helper": [1.0]},
     {"kernel": "step", "helper": "kernel"}),
    # a lowering rule that traces a function
    ([("lower", "jit(step)", 0.0, 5.0, [("trace", "rule", 1.0, 2.5)])],
     {"step": [3.5], "rule": [1.5]}, {"rule": "step"}),
])
def test_parents_and_self_seconds_add_up_to_the_union(log, spans, self_s,
                                                      parents):
    for args in spans:
        feed(*args)
    found = log.spans()
    for fun_name, want in self_s.items():
        got = [s.self_s for s in found if s.fun_name == fun_name]
        assert got == pytest.approx(want)
    for child, parent in parents.items():
        for s in found:
            if s.fun_name == child:
                assert s.parent.fun_name == parent
    assert all(s.parent is None for s in found
               if s.fun_name not in parents)
    union = log.union_seconds(found)
    report = log.report()
    assert sum(s.self_s for s in found) == pytest.approx(union)
    assert report["union_s"] == pytest.approx(union)
    for total in stage_and_function_seconds(report):
        assert total == pytest.approx(union)
    nested = {s.fun_name for s in found if s.parent is not None}
    assert set(report["nested"]) == nested
    assert set(report["functions"]) == {
        s.fun_name for s in found if s.parent is None}


def test_real_nested_jits(log):
    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    outer(jnp.ones(3)).block_until_ready()
    found = log.spans()
    (top,) = by_name(found, "outer")
    inners = by_name(found, "inner")
    assert len(inners) == 2 and all(s.parent is top for s in inners)
    assert top.top_level and not inners[0].top_level
    assert top.self_s < top.seconds  # its children's time is theirs
    assert len(by_name(found, "outer", log.LOWER)) == 1
    assert len(by_name(found, "outer", log.BACKEND)) == 1
    assert all(s.cause is None and not s.step for s in found)
    union = log.union_seconds(found)
    for total in stage_and_function_seconds(log.report()):
        assert total == pytest.approx(union, rel=0.01)
    entry = log.report()["functions"]["outer"]
    assert entry["traces"] == 1 and entry["programs"] == 1
    assert entry["trace_s"] == pytest.approx(top.seconds)


@pytest.mark.parametrize("fun_name,function", [
    ("jit(_local_step)", "_local_step"), ("pmap(f)", "f"), ("f", "f"),
    ("jit(<lambda>)", "<lambda>")])
def test_a_module_is_named_by_its_function(fun_name, function):
    assert compile_log._function(fun_name) == function


@pytest.mark.parametrize("cache,outcome", [
    ("hits", "hit"), ("misses", "miss"), (None, "off")])
def test_a_program_is_counted_by_what_the_cache_did(log, cache, outcome):
    before = counter(log.PROGRAMS_TOTAL, cache=outcome)
    seconds = counter(log.SECONDS_TOTAL, stage="backend")
    feed("backend", "jit(f)", 0.0, 2.0, cache=cache)
    assert log.spans()[-1].cache == outcome
    assert log.report()["programs"][outcome] == 1
    assert counter(log.PROGRAMS_TOTAL, cache=outcome) == before + 1
    assert counter(log.SECONDS_TOTAL, stage="backend") == \
        pytest.approx(seconds + 2.0)


def test_top_level_traces_are_counted_by_function(log):
    before = counter(log.TRACES_TOTAL, fun_name="only_outer")
    nested = counter(log.TRACES_TOTAL, fun_name="only_inner")
    feed("trace", "only_outer", 0.0, 2.0,
         [("trace", "only_inner", 0.5, 1.0)])
    assert counter(log.TRACES_TOTAL, fun_name="only_outer") == before + 1
    assert counter(log.TRACES_TOTAL, fun_name="only_inner") == nested


def test_a_span_that_began_before_the_log_listened(log):
    jax.monitoring.record_event_time_span(EVENT["trace"], 5.0, 6.0,
                                          fun_name="early")
    (found,) = log.spans()
    assert (found.fun_name, found.seconds, found.parent) == ("early", 1.0,
                                                             None)
    assert compile_log.OPEN.stack == []


def test_the_list_is_bounded_and_the_totals_are_not(log):
    extra = 10
    for i in range(log.MAX_SPANS + extra):
        feed("trace", "many", float(i), i + 0.5)
    found, report = log.spans(), log.report()
    assert len(found) == report["kept"] == log.MAX_SPANS
    assert found[0].start == float(extra)  # the oldest went
    assert report["spans"] == log.MAX_SPANS + extra
    assert report["stages"]["trace"]["seconds"] == pytest.approx(
        0.5 * (log.MAX_SPANS + extra))


def test_another_threads_compile_is_not_the_open_steps(log):
    done = threading.Event()

    def elsewhere():
        feed("trace", "theirs", 0.0, 1.0)
        done.set()

    def call():
        worker = threading.Thread(target=elsewhere)
        worker.start()
        assert done.wait(10)
        worker.join(10)
        feed("trace", "mine", 0.0, 1.0)

    metrics.timed_step(call, "jax")()
    (mine,), (theirs,) = (by_name(log.spans(), n) for n in ("mine", "theirs"))
    assert (mine.cause, mine.step_num) == ("hvd.step", 0)
    assert (theirs.cause, theirs.step_num) == (None, None)
    assert mine.thread != theirs.thread


# -- the program's own spans --------------------------------------------------------

def test_the_packages_import_is_a_span():
    assert IMPORT_SECONDS is not None and 0 < IMPORT_SECONDS < 60


def test_hvd_init_is_a_span_with_the_parts_that_ran(log):
    hvd.shutdown()
    hvd.init()
    try:
        own = log.report()["program_spans"]
        assert set(own) == {"hvd.init", "hvd.init.mesh"}  # no engine here
        assert own["hvd.init"] >= own["hvd.init.mesh"] > 0
        (mesh,) = [s for s in log.spans() if s.name == "hvd.init.mesh"]
        assert mesh.parent.name == mesh.cause == "hvd.init"
        assert mesh.parent.self_s == pytest.approx(
            mesh.parent.seconds - mesh.seconds)
    finally:
        hvd.shutdown()


def test_a_compile_inside_hvd_init_is_caused_by_it(log):
    with log.span("hvd.init"):
        feed("trace", "f", 0.0, 1.0)
    (found,) = by_name(log.spans(), "f")
    assert found.cause == "hvd.init" and found.top_level
    assert found.parent.name == "hvd.init"
    assert log.report()["functions"]["f"]["traces"] == 1


def listeners():
    return [jax_monitoring.get_scalar_listeners().count(compile_log._on_start),
            jax_monitoring.get_event_listeners().count(compile_log._on_event),
            jax_monitoring.get_event_time_span_listeners().count(
                compile_log._on_span)]


def test_init_registers_the_listeners_once_and_shutdown_takes_them_off():
    hvd.shutdown()
    compile_log.uninstall()
    assert listeners() == [0, 0, 0]
    hvd.init()
    hvd.init()
    compile_log.install()
    assert listeners() == [1, 1, 1]
    hvd.shutdown()
    assert listeners() == [0, 0, 0]
    hvd.shutdown()
    compile_log.uninstall()  # a second time: nothing to take off
    assert listeners() == [0, 0, 0]


# -- the step's functions, and a recompile --------------------------------------------

def make_step():
    mesh = mesh_lib.data_parallel_mesh(jax.devices())

    def loss_fn(params, batch, rng):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2), {}

    optimizer = optax.sgd(0.1)
    params = dp.replicate({"w": jnp.ones((8, 4))}, mesh)
    state = (params, dp.replicate(optimizer.init(params), mesh))

    def batch(rows):
        return dp.shard_batch({"x": jnp.ones((rows, 8)),
                               "y": jnp.zeros((rows, 4))}, mesh)

    return dp.make_train_step(loss_fn, optimizer, mesh), state, batch


def run(step, state, batches):
    key = jax.random.key(0)
    for batch in batches:
        out = step(*state, batch, key)
        state = (out.params, out.opt_state)
    jax.block_until_ready(state)
    return state


def test_the_steps_spans_are_marked_and_its_children_too(log):
    step, state, batch = make_step()
    step.lower(*state, batch(16), jax.random.key(0))

    @jax.jit
    def bystander(x):
        return x + 1

    bystander(jnp.ones(2))
    found = log.spans()
    (top,) = [s for s in by_name(found, "_local_step") if s.top_level]
    children = [s for s in found if s.parent is top]
    assert top.step and children and all(s.step for s in children)
    assert top.cause is None  # a caller's own .lower()
    (lowered,) = by_name(found, "_local_step", log.LOWER)
    assert lowered.step
    assert not any(s.step for s in found if s.fun_name == "bystander")
    report = log.report()
    assert report["step"]["traces"] == report["step"]["lowerings"] == 1
    assert report["step"]["programs"] == 0  # lowered, never compiled
    assert report["step"]["trace_lower_s"] == pytest.approx(
        top.seconds + lowered.seconds)
    assert report["functions"]["_local_step"]["step"] is True
    assert report["functions"]["bystander"]["step"] is False


def recompiles():
    return counter(compile_log.RECOMPILES_TOTAL, framework="jax")


def test_the_counter_is_there_before_any_recompile():
    metrics.timed_step(lambda: None, "compile_log_test")
    assert metrics.snapshot_value(
        metrics.get_registry().snapshot(), compile_log.RECOMPILES_TOTAL,
        framework="compile_log_test") == 0.0


def test_the_first_calls_compile_is_no_recompile(log):
    step, state, batch = make_step()
    before = recompiles()
    run(step, state, [batch(16)] * 3)
    assert recompiles() == before
    first = [s for s in log.spans() if s.cause == "hvd.step"]
    assert first and {s.step_num for s in first} == {0}
    assert log.report()["step"]["programs"] == 1
    assert compile_log.OPEN.step is None


def host_events(log_dir, prefix):
    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        yield e


def test_a_recompile_names_its_step_everywhere(log, tmp_path, monkeypatch):
    """A second batch shape at the fourth call: one recompile in the
    registry, one journal event with ``step=3`` and the function, and in a
    running profile an ``hvd.step.recompiled`` mark inside ``hvd.step``
    number 3."""
    journal_dir, profile_dir = tmp_path / "journal", tmp_path / "profile"
    monkeypatch.setenv("HOROVOD_JOURNAL_DIR", str(journal_dir))
    journal._reset_for_tests()
    step, state, batch = make_step()
    batches = [batch(16)] * 3 + [batch(32)] + [batch(16)]
    before = recompiles()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(profile_dir), profiler_options=options)
    try:
        run(step, state, batches)
    finally:
        jax.profiler.stop_trace()
        journal._reset_for_tests()  # closes the writer

    assert recompiles() == before + 1
    (program,) = [s for s in log.spans() if s.name == log.BACKEND and
                  s.cause == "hvd.step" and s.step_num == 3]
    assert program.fun_name == "_local_step" and program.step
    assert log.report()["step"]["programs"] == 2

    events = [e for e in journal.iter_journal(str(journal_dir))
              if e["component"] == "step_compiler"]
    assert [e["event"] for e in events] == ["recompile"]
    (event,) = events
    detail = event["detail"]
    assert event["step"] == 3 and detail["fun_name"] == "_local_step"
    assert detail["cache"] in ("hit", "miss", "off")
    assert detail["trace_s"] > 0 and detail["lower_s"] > 0
    assert detail["backend_s"] == pytest.approx(program.seconds)

    found = list(host_events(profile_dir, "hvd.step"))
    (mark,) = [e for e in found if e.name == log.RECOMPILED_MARK]
    assert dict(mark.stats)["step_num"] == 3
    assert dict(mark.stats)["fun_name"] == "_local_step"
    (third,) = [e for e in found if e.name == "hvd.step" and
                dict(e.stats)["step_num"] == 3]
    assert third.start_ns <= mark.start_ns
    assert mark.start_ns + mark.duration_ns <= \
        third.start_ns + third.duration_ns


def test_without_a_journal_directory_no_file_is_written(log, tmp_path,
                                                        monkeypatch):
    monkeypatch.delenv("HOROVOD_JOURNAL_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    journal._reset_for_tests()
    step, state, batch = make_step()
    before = recompiles()
    run(step, state, [batch(16), batch(32)])
    assert recompiles() == before + 1
    assert journal._WRITER is None
    assert os.listdir(tmp_path) == []


def test_a_step_that_raises_closes_its_call(log):
    def broken():
        raise ValueError("no")

    step = metrics.timed_step(broken, "jax")
    with pytest.raises(ValueError):
        step()
    assert compile_log.OPEN.step is None
    feed("trace", "afterwards", 0.0, 1.0)
    assert log.spans()[-1].cause is None
