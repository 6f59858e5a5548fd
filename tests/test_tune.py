"""Frontend exposed-comm autotuner (horovod_tpu/tune): search convergence
on a synthetic cost model, accuracy-guard rollback, telemetry/publish
contract, and the bounded CPU smoke session (slow)."""

import json
import math

import pytest

from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.tune.search import CoordinateSearch
from horovod_tpu.tune.space import (Knob, config_key, default_config,
                                    default_space)
from horovod_tpu.tune.tuner import TuningSession

KIB, MIB = 1024, 1024 * 1024
OPT_BUCKET = 2 * MIB  # sits on the 4-point log grid of [256K, 64M]


def bucket_cost(config):
    """Synthetic objective: convex in log2(bucket_bytes) with the optimum
    at OPT_BUCKET; bucket=0 (no overlap) pays a flat penalty; the other
    knobs are cost-flat. Deterministic, noiseless."""
    b = int(config["bucket_bytes"])
    if b <= 0:
        return 0.020
    return 0.001 * abs(math.log2(b) - math.log2(OPT_BUCKET)) + 0.002


class FakeKV:
    def __init__(self):
        self.data = {}

    def put_json(self, key, value, **kw):
        self.data[key] = value

    def get_json(self, key, **kw):
        return self.data.get(key)


def drive(ts, cost, losses=None, max_epochs=80):
    """Run a TuningSession against a synthetic cost model: objectives come
    from ``cost(config)``, probe losses from ``losses(config)``."""
    ts._measure = lambda: (cost(ts.config), "synthetic")
    epochs = 0
    while not ts.converged and epochs < max_epochs:
        for _ in range(ts._epoch_steps):
            loss = losses(ts.config) if losses else None
            ts.on_step(loss=loss)
        epochs += 1
    return epochs


# ---------------------------------------------------------------------------
# space / search


def test_knob_grid_is_deterministic_and_bounded():
    k = Knob("bucket_bytes", "log_int", 0, lo=256 * KIB, hi=64 * MIB,
             extra=(0,))
    g = k.grid(4)
    assert g == k.grid(4)
    assert g[0] == 0 and OPT_BUCKET in g
    assert all(v == 0 or 256 * KIB <= v <= 64 * MIB for v in g)
    c = Knob("compression", "choice", "none",
             choices=("none", "bf16", "int8"))
    assert c.grid() == ("none", "bf16", "int8")
    assert set(c.neighbors("bf16")) == {"none", "int8"}


def test_search_recovers_known_optimal_bucket_within_budget():
    """The ISSUE-11 acceptance: a known-optimal bucket size is recovered
    on the synthetic cost model within the sample budget. The optimum is
    on the sweep grid, so `1 incumbent + |grid|` samples suffice."""
    space = (Knob("bucket_bytes", "log_int", 0, lo=256 * KIB, hi=64 * MIB,
                  extra=(0,)),)
    search = CoordinateSearch(space, budget=8, grid_points=4)
    n = 0
    while True:
        cand = search.propose()
        if cand is None:
            break
        search.observe(cand, bucket_cost(cand))
        n += 1
    assert search.best["bucket_bytes"] == OPT_BUCKET
    assert n <= 8
    assert search.best_objective == pytest.approx(0.002)
    assert search.converged


def test_search_is_deterministic():
    space = default_space()
    a, b = (CoordinateSearch(space, budget=12) for _ in range(2))
    for _ in range(12):
        ca, cb = a.propose(), b.propose()
        assert ca == cb
        if ca is None:
            break
        a.observe(ca, bucket_cost(ca))
        b.observe(cb, bucket_cost(cb))
    assert a.best == b.best


def test_search_ban_evicts_incumbent():
    space = (Knob("compression", "choice", "none",
                  choices=("none", "bf16", "int8"), guarded=True),)
    s = CoordinateSearch(space, budget=6)
    costs = {"none": 3.0, "bf16": 2.0, "int8": 1.0}
    while True:
        c = s.propose()
        if c is None:
            break
        s.observe(c, costs[c["compression"]])
    assert s.best["compression"] == "int8"
    s.ban("compression", "int8")
    assert s.best["compression"] == "bf16"
    assert s.best_objective == 2.0


def test_config_key_stable():
    space = default_space()
    cfg = default_config(space)
    assert config_key(cfg, space) == config_key(dict(cfg), space)


# ---------------------------------------------------------------------------
# the tuning session loop


def test_tuning_session_converges_publishes_and_logs(tmp_path):
    kv = FakeKV()
    reg = MetricsRegistry()
    log = tmp_path / "tune.csv"
    space = (Knob("bucket_bytes", "log_int", 0, lo=256 * KIB,
                  hi=64 * MIB, extra=(0,)),)
    ts = TuningSession(engine=None, registry=reg, kv=kv, job="smoketest",
                       space=space, epoch_steps=2, samples=10,
                       warmup_epochs=1, log_path=str(log))
    drive(ts, bucket_cost)
    assert ts.converged
    assert ts.config["bucket_bytes"] == OPT_BUCKET
    # KV publish: the converged record under tune_config/<job>
    rec = kv.data["tune_config/smoketest"]
    assert rec["config"]["bucket_bytes"] == OPT_BUCKET
    assert rec["objective_seconds"] == pytest.approx(0.002)
    assert rec["samples"] <= 10
    # CSV log: one row per sample, converged marker at the end
    text = log.read_text()
    assert text.startswith("objective_seconds,source,bucket_bytes")
    assert "# converged" in text
    assert len([ln for ln in text.splitlines()
                if ln and not ln.startswith(("objective", "#"))]) == \
        rec["samples"]
    # gauges hvd-top --tune scrapes
    snap = reg.snapshot()
    by_name = {m["name"]: m["samples"][0]["value"]
               for m in snap["metrics"] if m.get("samples")
               and "value" in m["samples"][0]}
    assert by_name["hvd_tune_phase"] == 3  # converged
    assert by_name["hvd_tune_bucket_bytes"] == OPT_BUCKET
    assert by_name["hvd_tune_best_objective_seconds"] == \
        pytest.approx(0.002)
    assert by_name["hvd_tune_samples_total"] == rec["samples"]


def test_tuning_session_staged_recompile_signal():
    """on_step returns the config exactly when an in-jit knob changed —
    the staged-recompile trigger — and step_kwargs maps it to
    make_train_step arguments."""
    from horovod_tpu.jax.compression import Compression
    space = (Knob("bucket_bytes", "log_int", 0, lo=256 * KIB,
                  hi=64 * MIB, extra=(0,)),
             Knob("compression", "choice", "none",
                  choices=("none", "bf16"), guarded=False),)
    ts = TuningSession(engine=None, registry=MetricsRegistry(),
                       space=space, epoch_steps=2, samples=8,
                       warmup_epochs=0)
    ts._measure = lambda: (bucket_cost(ts.config), "synthetic")
    rebuilds = []
    for _ in range(40):
        if ts.converged:
            break
        before = dict(ts.config)
        out = [ts.on_step() for _ in range(ts._epoch_steps)]
        changed = [o for o in out if o is not None]
        if changed:
            rebuilds.append(changed[-1])
            assert any(changed[-1][k] != before.get(k)
                       for k in ("bucket_bytes", "compression"))
    assert rebuilds, "the search never exercised an in-jit knob change"
    kw = ts.step_kwargs({"bucket_bytes": 4096, "compression": "bf16"})
    assert kw == {"bucket_bytes": 4096,
                  "compression": Compression.bf16}
    assert ts.step_kwargs({"bucket_bytes": 0,
                           "compression": "none"}) == \
        {"bucket_bytes": 0, "compression": None}


def test_accuracy_guard_rolls_back_int8():
    """int8 looks fastest on the objective but degrades the probe loss
    beyond tolerance → banned, rolled back, never the converged choice."""
    space = (Knob("compression", "choice", "none",
                  choices=("none", "bf16", "int8"), guarded=True),)

    def cost(config):
        return {"none": 0.010, "bf16": 0.008, "int8": 0.001}[
            config["compression"]]

    def losses(config):
        return 1.5 if config["compression"] == "int8" else 1.0

    ts = TuningSession(engine=None, registry=MetricsRegistry(),
                       space=space, epoch_steps=2, samples=10,
                       warmup_epochs=0, accuracy_tolerance=0.02)
    drive(ts, cost, losses=losses)
    assert ts.converged
    assert ts.config["compression"] == "bf16"
    assert ("compression", "int8") in ts._search._banned
    banned_rows = [t for t in ts._search.trace
                   if t["objective"] == float("inf")]
    assert banned_rows and \
        banned_rows[0]["config"]["compression"] == "int8"


def test_accuracy_guard_tolerates_within_bound():
    """A guarded choice whose loss stays within tolerance is kept."""
    space = (Knob("compression", "choice", "none",
                  choices=("none", "int8"), guarded=True),)

    def cost(config):
        return {"none": 0.010, "int8": 0.001}[config["compression"]]

    def losses(config):
        return 1.009 if config["compression"] == "int8" else 1.0

    ts = TuningSession(engine=None, registry=MetricsRegistry(),
                       space=space, epoch_steps=2, samples=8,
                       warmup_epochs=0, accuracy_tolerance=0.02)
    drive(ts, cost, losses=losses)
    assert ts.converged
    assert ts.config["compression"] == "int8"


def test_wall_time_fallback_scores_two_step_epochs():
    """Engine-less sessions at the epoch_steps floor (2) must still get a
    finite wall-time objective — a single inter-step diff beats scoring
    every epoch +inf and 'converging' on garbage."""
    space = (Knob("bucket_bytes", "log_int", 0, lo=256 * KIB,
                  hi=64 * MIB, extra=(0,)),)
    ts = TuningSession(engine=None, registry=MetricsRegistry(),
                       space=space, epoch_steps=2, samples=6,
                       warmup_epochs=0)
    for _ in range(60):
        if ts.converged:
            break
        ts.on_step()
    assert ts.converged
    assert ts._search.best_objective is not None
    assert ts._search.best_objective != float("inf")
    assert all(t["objective"] != float("inf")
               for t in ts._search.trace)


def test_follower_adopts_leader_epoch_configs():
    kv = FakeKV()
    kv.put_json("tune_epoch/default/1",
                {"config": {"bucket_bytes": 4096, "compression": "none"},
                 "converged": False})
    ts = TuningSession(engine=None, registry=MetricsRegistry(), kv=kv,
                       space=default_space(engine_knobs=False),
                       epoch_steps=2, samples=4, warmup_epochs=0,
                       leader=False)
    out = [ts.on_step() for _ in range(2)]
    assert out[-1] is not None and out[-1]["bucket_bytes"] == 4096
    assert ts.config["bucket_bytes"] == 4096


# ---------------------------------------------------------------------------
# the bounded CPU smoke session (the `make tune-smoke` payload)


@pytest.mark.slow
def test_tune_smoke_session_cuts_exposed_comm(monkeypatch):
    """The real closed loop on the real engine: the converged config must
    cut exposed comm vs the untuned bucket_bytes=0 baseline (the CPU
    -backend acceptance figure)."""
    from horovod_tpu.tune import smoke
    out = smoke.run_smoke(world=2, epoch_steps=4, samples=8,
                          warmup_epochs=1, scale=32,
                          compute_seconds=0.03)
    assert out["converged"]
    assert out["before"] and out["after"]
    assert out["search_trace_len"] <= 8
    assert out["exposed_comm_drop_pct"] is not None
    # the smoke's compute/wire shape gives ~90% in practice; 20% is the
    # loaded-CI floor
    assert out["exposed_comm_drop_pct"] >= 20.0
    assert out["converged_config"]["bucket_bytes"] > 0


@pytest.mark.slow
def test_tune_smoke_cli(monkeypatch, capsys):
    from horovod_tpu.tune import smoke
    rc = smoke.main(["--steps", "12", "--epoch-steps", "4",
                     "--scale", "32", "--json"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["exposed_comm_drop_pct"] > 0
