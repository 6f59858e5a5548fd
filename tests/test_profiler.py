"""Profiler subsystem (horovod_tpu/profiler): the engine-timeline +
JAX-trace merge bridge, the flight dumps' Perfetto export, and the conv-path
mixed-precision policy regression (bf16 compute must keep BN statistics in
fp32). FLOP counting is the benchmark's (tests/benchmark/test_bench_flops.py)."""

import glob
import json
import os
import threading
import uuid

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.profiler import flight
from horovod_tpu.profiler import trace_merge


# ---------------------------------------------------------------------------
# Trace merge bridge


ENGINE_EVENTS = (
    '[\n'
    '{"ph":"B","name":"NEGOTIATE_ALLREDUCE","pid":0,"tid":"grad/w",'
    '"ts":10},\n'
    '{"ph":"i","name":"0","pid":0,"tid":"grad/w","ts":12,"s":"t"},\n'
    '{"ph":"E","name":"","pid":0,"tid":"grad/w","ts":20}'
)


def test_engine_timeline_tolerant_parse(tmp_path):
    clean = tmp_path / "clean.json"
    clean.write_text(ENGINE_EVENTS + "\n]\n")
    assert len(trace_merge.load_engine_timeline(clean)) == 3
    # killed process: no closing bracket, trailing comma
    torn = tmp_path / "torn.json"
    torn.write_text(ENGINE_EVENTS + ",")
    events = trace_merge.load_engine_timeline(torn)
    assert len(events) == 3
    assert events[0]["name"] == "NEGOTIATE_ALLREDUCE"
    # killed MID-RECORD: the partial tail is dropped, complete events kept
    mid = tmp_path / "mid.json"
    mid.write_text(ENGINE_EVENTS + ',\n{"ph":"B","na')
    assert len(trace_merge.load_engine_timeline(mid)) == 3
    # nothing complete at all
    empty = tmp_path / "empty.json"
    empty.write_text('[\n{"ph":"B","na')
    assert trace_merge.load_engine_timeline(empty) == []


def test_merge_normalizes_engine_lanes(tmp_path):
    timeline = tmp_path / "t.json"
    timeline.write_text(ENGINE_EVENTS + "\n]\n")
    out = tmp_path / "merged.json"
    merged = trace_merge.merge_traces(timeline, None, out, offset_us=5.0)
    data = json.loads(out.read_text())
    assert data == merged
    evs = data["traceEvents"]
    # engine events got the engine pid, integer tids, shifted timestamps
    engine = [e for e in evs if e.get("ph") in "BEi"]
    assert engine and all(e["pid"] == trace_merge.DEFAULT_ENGINE_PID
                          for e in engine)
    assert all(isinstance(e["tid"], int) for e in engine)
    assert engine[0]["ts"] == 15.0
    # lane name preserved via thread_name metadata
    metas = [e for e in evs if e.get("ph") == "M"]
    assert any(e["name"] == "thread_name" and
               e["args"]["name"] == "grad/w" for e in metas)


def test_merge_with_empty_or_absent_jax_trace(tmp_path):
    """Merging with no JAX side must still produce a loadable trace:
    absent logdir, empty logdir, empty dict, empty list — none may crash
    or drop the engine events (ISSUE 7 satellite)."""
    timeline = tmp_path / "t.json"
    timeline.write_text(ENGINE_EVENTS + "\n]\n")
    empty_dir = tmp_path / "empty_logdir"
    empty_dir.mkdir()
    for jax_side in (None, str(tmp_path / "never_created"), str(empty_dir),
                     {}, []):
        merged = trace_merge.merge_traces(timeline, jax_side)
        engine = [e for e in merged["traceEvents"] if e.get("ph") in "BEi"]
        assert len(engine) == 3, f"jax_side={jax_side!r}"
    # and a trace file that exists but holds no events
    hollow = tmp_path / "hollow.trace.json"
    hollow.write_text('{"traceEvents": []}')
    merged = trace_merge.merge_traces(timeline, str(hollow))
    assert [e for e in merged["traceEvents"] if e.get("ph") in "BEi"]


def test_flight_perfetto_two_ranks_distinct_pids(tmp_path):
    """Two ranks with IDENTICAL tensor names and raw pids must land in
    distinct per-rank process groups — overlapping pids in the source
    dumps may not collide in the merged trace (ISSUE 7 satellite)."""
    def dump(rank):
        return {"rank": rank, "size": 2, "origin_unix_us": 1_000_000,
                "events": [
                    {"i": 0, "phase": "CYCLE", "name": "", "ts_us": 0.0,
                     "cycle": 1},
                    {"i": 1, "phase": "ENQUEUE", "name": "grad/w",
                     "ts_us": 10.0},
                    {"i": 2, "phase": "NEGOTIATE", "name": "grad/w",
                     "ts_us": 20.0},
                    {"i": 3, "phase": "EXEC", "name": "grad/w",
                     "ts_us": 30.0},
                    {"i": 4, "phase": "DONE", "name": "grad/w",
                     "ts_us": 40.0},
                ]}

    out = tmp_path / "flight.trace.json"
    merged = flight.to_perfetto({0: dump(0), 1: dump(1)}, str(out))
    assert json.loads(out.read_text()) == merged
    span_pids = {e["pid"] for e in merged["traceEvents"]
                 if e.get("ph") in "BEi"}
    assert len(span_pids) == 2, "each rank needs its own process group"
    # both process groups carry the shared lane name without clashing
    names = [e for e in merged["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"]
    assert sum(e["args"]["name"] == "grad/w" for e in names) == 2


def test_flight_alignment_degrades_without_cycle_anchors(tmp_path):
    """A dump folder where one rank recorded zero CYCLE anchors (tiny
    ring, wedged rank) must fall back to the wall-clock origin instead of
    crashing, for the analyzer AND the Perfetto emitter."""
    with_anchor = {"rank": 0, "size": 2, "origin_unix_us": 1_000_000,
                   "events": [
                       {"i": 0, "phase": "CYCLE", "name": "", "ts_us": 50.0,
                        "cycle": 1},
                       {"i": 1, "phase": "ENQUEUE", "name": "g",
                        "ts_us": 60.0},
                       {"i": 2, "phase": "DONE", "name": "g",
                        "ts_us": 80.0},
                   ]}
    # rank 1 booted 2500us later (wall clock) and has no CYCLE events
    anchorless = {"rank": 1, "size": 2, "origin_unix_us": 1_002_500,
                  "events": [
                      {"i": 0, "phase": "ENQUEUE", "name": "g",
                       "ts_us": 10.0},
                      {"i": 1, "phase": "DONE", "name": "g",
                       "ts_us": 30.0},
                  ]}
    for rank, d in ((0, with_anchor), (1, anchorless)):
        (tmp_path / f"flight_rank{rank}.json").write_text(json.dumps(d))
    dumps = flight.load_dumps(tmp_path)
    offsets = flight.align_clocks(dumps)
    assert offsets[0] == 0.0
    assert offsets[1] == pytest.approx(2500.0)
    verdict = flight.analyze(dumps)
    assert set(verdict["clock_offsets_us"]) == {0, 1}
    merged = flight.to_perfetto(dumps, str(tmp_path / "out.trace.json"))
    assert merged["traceEvents"]


def test_merged_trace_engine_beside_device_activity(tmp_path):
    """The VERDICT-item-10 smoke: a REAL engine timeline (loopback
    sessions running an allreduce through the C++ data plane) merged with
    a REAL JAX profiler trace into one loadable Perfetto JSON."""
    from horovod_tpu.engine import EngineSession
    from horovod_tpu.common import eager

    timeline_path = tmp_path / "engine_timeline.json"
    group = f"trace-{uuid.uuid4().hex[:8]}"
    n = 2
    sessions = [EngineSession(rank=r, size=n, transport="loopback",
                              group=group, cycle_time_ms=1.0)
                for r in range(n)]
    try:
        for s in sessions:
            s.start_timeline(str(timeline_path))  # coordinator-only write
        executors = [eager.EagerExecutor(s) for s in sessions]

        profile_dir = tmp_path / "jaxprof"
        with jax.profiler.trace(str(profile_dir)):
            jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()

            def work(ex):
                h = ex.submit("grad/w", eager.OP_ALLREDUCE,
                              np.ones(8, np.float32))
                ex.session.wait(h, timeout=0.0)
                ex.take_result("grad/w")

            threads = [threading.Thread(target=work, args=(ex,))
                       for ex in executors]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for s in sessions:
            s.stop_timeline()
    finally:
        # Two-phase teardown (all ranks shutdown, THEN all destroy) — the
        # repo-wide idiom for multi-rank loopback groups (see
        # tests/test_eager_ops.py): a rank destroyed while peers are still
        # shutting down would wedge the loopback hub.
        for s in sessions:
            s._lib.hvdtpu_shutdown(s._session)
        for s in sessions:
            s.destroy()

    assert timeline_path.exists()
    jax_trace = trace_merge.find_jax_trace(profile_dir)
    assert jax_trace is not None, (
        f"no jax trace under {profile_dir}: "
        f"{glob.glob(str(profile_dir / '**' / '*'), recursive=True)}")
    out = tmp_path / "merged.trace.json"
    merged = trace_merge.merge_traces(timeline_path, profile_dir, out)

    data = json.loads(out.read_text())  # loadable
    evs = data["traceEvents"]
    engine_evs = [e for e in evs
                  if e.get("pid") == trace_merge.DEFAULT_ENGINE_PID and
                  e.get("ph") in "BEi"]
    other_evs = [e for e in evs
                 if e.get("pid") != trace_merge.DEFAULT_ENGINE_PID]
    assert engine_evs, "engine timeline events missing from merged trace"
    assert other_evs, "jax profiler events missing from merged trace"
    # the negotiation phases the reference timeline contract promises
    names = {e.get("name", "") for e in engine_evs}
    assert any(n.startswith("NEGOTIATE_") or n.startswith("COMMUNICATE_")
               or n in ("QUEUE", "EXEC") for n in names), names
    assert merged["metadata"]["engine_pid"] == trace_merge.DEFAULT_ENGINE_PID


# ---------------------------------------------------------------------------
# Conv-path mixed-precision policy regression


def _tiny_resnet(**kw):
    from horovod_tpu.models.resnet import ResNet, ResNetBlock
    return ResNet(stage_sizes=[1, 1], block_cls=ResNetBlock, num_classes=10,
                  num_filters=8, **kw)


def test_bf16_policy_keeps_bn_statistics_fp32():
    model = _tiny_resnet(dtype=jnp.bfloat16, param_dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3), jnp.bfloat16)
    variables = jax.jit(lambda k, x: model.init(k, x, train=True))(
        jax.random.key(0), x)

    def dtypes(tree):
        return {leaf.dtype for leaf in jax.tree_util.tree_leaves(tree)}

    assert dtypes(variables["params"]) == {jnp.dtype(jnp.float32)}
    assert dtypes(variables["batch_stats"]) == {jnp.dtype(jnp.float32)}

    # one train-mode apply: the UPDATED running stats must still be fp32
    # and finite (the stat reduction ran in fp32, not bf16)
    logits, mutated = jax.jit(lambda v, x: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables, x)
    assert dtypes(mutated["batch_stats"]) == {jnp.dtype(jnp.float32)}
    assert all(bool(jnp.all(jnp.isfinite(leaf)))
               for leaf in jax.tree_util.tree_leaves(mutated["batch_stats"]))
    assert logits.dtype == jnp.float32


def test_nchw_input_layout_matches_nhwc():
    """NCHW enforcement is a single entry transpose: identical params,
    identical outputs."""
    nhwc = _tiny_resnet(dtype=jnp.float32)
    nchw = _tiny_resnet(dtype=jnp.float32, input_layout="NCHW")
    x = jnp.asarray(np.random.RandomState(0).rand(2, 16, 16, 3), jnp.float32)
    variables = nhwc.init(jax.random.key(0), x)
    y_nhwc = nhwc.apply(variables, x)
    y_nchw = nchw.apply(variables, jnp.transpose(x, (0, 3, 1, 2)))
    np.testing.assert_allclose(np.asarray(y_nhwc), np.asarray(y_nchw),
                               rtol=1e-6)
    with pytest.raises(ValueError):
        _tiny_resnet(input_layout="NHCW").init(jax.random.key(0), x)


def test_stem_channel_padding_is_exact():
    """Zero-padded input channels contribute exactly nothing: the padded
    conv with the original kernel embedded reproduces the unpadded conv."""
    from horovod_tpu.models.resnet import pad_channels_to_multiple

    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.rand(2, 8, 8, 3), jnp.float32)
    xp = pad_channels_to_multiple(x, 8)
    assert xp.shape == (2, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(xp[..., :3]), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(xp[..., 3:]), 0.0)
    assert pad_channels_to_multiple(xp, 8) is xp  # already aligned: no-op

    kernel = jnp.asarray(rs.rand(3, 3, 3, 4), jnp.float32)
    kernel_padded = jnp.concatenate(
        [kernel, jnp.asarray(rs.rand(3, 3, 5, 4), jnp.float32)], axis=2)
    dn = jax.lax.conv_dimension_numbers(x.shape, kernel.shape,
                                        ("NHWC", "HWIO", "NHWC"))
    y = jax.lax.conv_general_dilated(x, kernel, (1, 1), "SAME",
                                     dimension_numbers=dn)
    yp = jax.lax.conv_general_dilated(xp, kernel_padded, (1, 1), "SAME",
                                      dimension_numbers=dn)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yp), rtol=1e-5)
