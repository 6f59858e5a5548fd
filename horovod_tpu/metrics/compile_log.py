"""The program's own compile log: what the path to the first step, and a
recompile in the middle of a job, were made of.

``hvd.init()`` registers the log's listeners on ``jax.monitoring`` (once;
``hvd.shutdown()`` takes them off). JAX announces the start of every trace
(``/jax/core/compile/jaxpr_trace_duration``), lowering
(``jaxpr_to_mlir_module_duration``) and backend compile
(``backend_compile_duration``) as a scalar event and its end as a time span
with ``fun_name``, and whether a program came from the persistent cache as
``/jax/compilation_cache/cache_hits`` / ``cache_misses`` inside its backend
span. Each becomes a :class:`Span`, beside the program's own spans
``hvd.import`` and ``hvd.init*``, all on ``time.time()``:

- ``parent``: the span that was open on the same thread when this one began
  (a kernel's ``jax.jit`` traced inside the step's trace is the step's
  child). Self time is a span's duration less what its children cover, so
  stages and functions add up to the union of the intervals and a nested
  trace is counted once.
- ``cause``: the program span open on that thread: ``hvd.init``, or
  ``hvd.step`` with its ``step_num`` (``metrics._TimedStep`` stores itself
  in :data:`OPEN` around each call); None for a caller's own ``.lower()``.
- ``step``: the function is one ``dp._jit_step`` jitted
  (:func:`step_function`), or a descendant of one.

The log keeps the newest :data:`MAX_SPANS` spans (:func:`spans`) and running
totals of all of them (:func:`report`), so the totals stay exact however
long the job and however many small functions a trace holds.

A backend compile whose cause is a step call that is not its wrapper's first
is a *recompile*: ``hvd_step_recompiles_total{framework}``, one journal event
(``component="step_compiler"``, ``event="recompile"``; free while
``HOROVOD_JOURNAL_DIR`` is unset) and an ``hvd.step.recompiled`` mark in a
running ``jax.profiler`` trace, inside that call's ``hvd.step`` span.

The registry gets ``hvd_compile_seconds_total{stage}`` (self seconds),
``hvd_compile_programs_total{cache}`` (``hit``: loaded from the persistent
cache; ``miss``: compiled and written to it; ``off``: compiled and not
written) and ``hvd_compile_traces_total{fun_name}`` for top-level traces.
"""

from __future__ import annotations

import collections
import contextlib
import copy
import re
import threading
import time
from typing import Optional

from horovod_tpu.common import journal
from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.profiler.annotate import host_annotation

COMPILE_PREFIX = "hvd.compile."
STAGES = ("trace", "lower", "backend")
TRACE, LOWER, BACKEND = (COMPILE_PREFIX + stage for stage in STAGES)
RECOMPILED_MARK = "hvd.step.recompiled"
SECONDS_TOTAL = "hvd_compile_seconds_total"
PROGRAMS_TOTAL = "hvd_compile_programs_total"
TRACES_TOTAL = "hvd_compile_traces_total"
RECOMPILES_TOTAL = "hvd_step_recompiles_total"
MAX_SPANS = 32768  # a benchmark cell's set-up makes 2 600 to 16 700

_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": TRACE,
           "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
           "/jax/core/compile/backend_compile_duration": BACKEND}
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}
# lowering and compiling name a program by its module, "jit(f)"
_MODULE_NAME = re.compile(r"^\w+\((.*)\)$")

# Per thread: ``step`` the ``metrics._TimedStep`` whose call is open (or
# None), ``stack`` the open spans, innermost last.
OPEN = threading.local()


class Span:
    __slots__ = ("name", "stage", "fun_name", "start", "end", "parent",
                 "cause", "step_num", "step", "cache", "thread", "covered")

    def __init__(self, name, fun_name, start, parent, cause, step_num):
        self.name, self.fun_name, self.start = name, fun_name, start
        # trace, lower or backend; None for a span of the program's own
        self.stage = name[len(COMPILE_PREFIX):] if name.startswith(
            COMPILE_PREFIX) else None
        self.end = start
        self.parent, self.cause, self.step_num = parent, cause, step_num
        self.step = fun_name in _step_functions or bool(
            parent is not None and parent.step)
        self.cache = None   # of a backend span: hit, miss or off
        self.thread = threading.get_ident()
        self.covered = 0.0  # seconds of this span its children cover

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        return self.seconds - self.covered

    @property
    def top_level(self) -> bool:
        """No trace, lowering or compile encloses it."""
        return self.parent is None or self.parent.stage is None

    def __repr__(self):
        return (f"Span({self.name} {self.fun_name!r} {self.seconds:.6f}s "
                f"cause={self.cause} step_num={self.step_num})")


class _Totals:
    """Seconds and counts of every span recorded, kept or not."""

    def __init__(self):
        self.spans = 0
        self.stages = {k: {"count": 0, "seconds": 0.0} for k in STAGES}
        self.union_s = 0.0
        self.programs = dict.fromkeys(("hit", "miss", "off"), 0)
        self.functions, self.nested, self.program_spans = {}, {}, {}
        self.step = {"trace_lower_s": 0.0, "backend_s": 0.0, "traces": 0,
                     "lowerings": 0, "programs": 0}

    def add(self, span):
        self.spans += 1
        stage = span.stage
        if stage is None:
            self.program_spans[span.name] = self.program_spans.get(
                span.name, 0.0) + span.seconds
            return
        self.stages[stage]["count"] += 1
        self.stages[stage]["seconds"] += span.self_s
        if stage == "backend":
            self.programs[span.cache] += 1
        root = span
        while not root.top_level:
            root = root.parent
        entry = self.functions.setdefault(root.fun_name, {
            "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "traces": 0,
            "programs": 0, "step": root.step})
        entry[root.stage + "_s"] += span.self_s
        if span is root:
            self.union_s += span.seconds
            entry["traces"] += stage == "trace"
            entry["programs"] += stage == "backend"
        else:
            inner = self.nested.setdefault(span.fun_name,
                                           {"count": 0, "seconds": 0.0})
            inner["count"] += 1
            inner["seconds"] += span.self_s
        if span.step:
            self.step["backend_s" if stage == "backend" else
                      "trace_lower_s"] += span.self_s
            if span is root:
                self.step["traces"] += stage == "trace"
                self.step["lowerings"] += stage == "lower"
                self.step["programs"] += stage == "backend"


_lock = threading.Lock()   # the three below, and the listeners' registration
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_totals = _Totals()
_installed = False
_step_functions = set()


def _stack() -> list:
    try:
        return OPEN.stack
    except AttributeError:
        OPEN.stack = []
        return OPEN.stack


def _begin(name, fun_name, start) -> Span:
    stack = _stack()
    step = getattr(OPEN, "step", None)
    if step is not None:
        cause, step_num = "hvd.step", step.step_num
    else:  # the outermost open span of the program's own, if any
        cause = stack[0].name if stack and stack[0].stage is None else None
        step_num = None
    span = Span(name, fun_name, start, stack[-1] if stack else None, cause,
                step_num)
    stack.append(span)
    return span


def _end(span, end):
    stack = _stack()
    if span in stack:  # and whatever it left open above itself
        del stack[stack.index(span):]
    span.end = end
    if span.parent is not None:
        span.parent.covered += span.seconds
    with _lock:
        _spans.append(span)
        _totals.add(span)


@contextlib.contextmanager
def span(name: str):
    """One of the program's own spans (``hvd.init`` and its children),
    around the enclosed statements."""
    start, t0 = time.time(), time.perf_counter()
    opened = _begin(name, "", start)
    try:
        yield opened
    finally:
        _end(opened, start + time.perf_counter() - t0)


def record(name: str, start: float, seconds: float):
    """A span of the program's own that its caller timed (``hvd.import``:
    the package's ``__init__`` cannot hold a ``with``)."""
    _end(_begin(name, "", start), start + seconds)


def step_function(name: Optional[str]):
    """``name`` is a function ``dp._jit_step`` jitted: its traces, lowerings
    and compiles, and what they hold, are the step's."""
    if name:
        _step_functions.add(name)


# -- jax.monitoring's side ----------------------------------------------------

def _function(fun_name) -> str:
    found = _MODULE_NAME.match(str(fun_name))
    return found.group(1) if found else str(fun_name)


def _on_start(event, value, fun_name="", **_):
    name = _EVENTS.get(event)
    if name is not None:
        _begin(name, _function(fun_name), value)


def _on_event(event, **_):
    found = _CACHE.get(event)
    if found is not None:
        stack = _stack()
        if stack and stack[-1].name == BACKEND:
            stack[-1].cache = found


def _on_span(event, start, end, fun_name="", **_):
    name = _EVENTS.get(event)
    if name is None:
        return
    stack, fun_name = _stack(), _function(fun_name)
    if stack and (stack[-1].name, stack[-1].fun_name) == (name, fun_name):
        found = stack[-1]
    else:  # began before the listeners were registered
        found = _begin(name, fun_name, start)
    if name == BACKEND:
        found.cache = found.cache or "off"
    _end(found, end)
    stage, registry = found.stage, get_registry()
    registry.counter(SECONDS_TOTAL, "self seconds of JAX's traces, "
                     "lowerings and backend compiles", stage=stage).inc(
                         found.self_s)
    if name == TRACE and found.top_level:
        registry.counter(TRACES_TOTAL, "traces that no other trace, lowering "
                         "or compile encloses", fun_name=fun_name).inc()
    recompile = found.cause == "hvd.step" and found.step_num > 0
    if recompile:
        _pending(found)[stage] += found.self_s
    if name == BACKEND:
        registry.counter(PROGRAMS_TOTAL, "programs built or loaded from the "
                         "persistent cache", cache=found.cache).inc()
        if recompile:
            _recompiled(found)


def _pending(found) -> dict:
    """Stage seconds of the recompile in progress in this step call."""
    key = (id(OPEN.step), found.step_num)
    if getattr(OPEN, "pending_of", None) != key:
        OPEN.pending_of = key
        OPEN.pending = dict.fromkeys(STAGES, 0.0)
    return OPEN.pending


def _recompiled(found):
    stages = {f"{k}_s": v for k, v in _pending(found).items()}
    OPEN.pending_of = None
    get_registry().counter(
        RECOMPILES_TOTAL, "programs compiled inside a step call that was "
        "not its wrapper's first", framework=OPEN.step.framework).inc()
    journal.emit("step_compiler", "recompile", step=found.step_num,
                 fun_name=found.fun_name, cache=found.cache, **stages)
    with host_annotation(RECOMPILED_MARK, step_num=found.step_num,
                         fun_name=found.fun_name):
        pass


def install():
    """Register the listeners; a second call registers nothing."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_time_span_listener(_on_span)
        _installed = True


def uninstall():
    """Take the listeners off; spans and totals stay to be read."""
    global _installed
    with _lock:
        if not _installed:
            return
        from jax import monitoring
        monitoring.unregister_scalar_listener(_on_start)
        monitoring.unregister_event_listener(_on_event)
        monitoring.unregister_event_time_span_listener(_on_span)
        _installed = False


# -- the reader's side ----------------------------------------------------------

def spans() -> list:
    """The newest :data:`MAX_SPANS` spans, by their end."""
    with _lock:
        return list(_spans)


def clear():
    """Forget the spans and the totals (tests; an operator's fresh start)."""
    global _totals
    with _lock:
        _spans.clear()
        _totals = _Totals()


def union_seconds(found) -> float:
    """Seconds the intervals of ``found`` cover, each thread's alone."""
    total, by_thread = 0.0, collections.defaultdict(list)
    for s in found:
        by_thread[s.thread].append((s.start, s.end))
    for intervals in by_thread.values():
        reach = float("-inf")
        for start, end in sorted(intervals):
            total += max(end, reach) - max(start, reach)
            reach = max(end, reach)
    return total


def report() -> dict:
    """Seconds and counts of every span recorded: ``stages`` (self seconds),
    ``functions`` (by top-level function: the self seconds of all it
    encloses, under the stage of the enclosing span), ``nested`` (by
    function, the spans that another encloses), ``step`` (the step
    functions' part), ``programs`` (by cache outcome), ``program_spans``
    (``hvd.import``, ``hvd.init*``). ``union_s`` is what the top-level
    compile spans cover: the stages' and the functions' seconds each add up
    to it. ``spans`` were recorded, ``kept`` are still in :func:`spans`."""
    with _lock:
        found = copy.deepcopy(vars(_totals))
        found["kept"] = len(_spans)
    return found
