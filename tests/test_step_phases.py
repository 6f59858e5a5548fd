"""The compiled step names its phases and the step wrapper its spans.

``parallel/dp.py`` and ``parallel/zero.py`` write each part of the training
step under a ``phase_<name>`` named scope (``profiler/annotate.PHASES``);
``metrics.timed_step`` writes an ``hvd.step`` span holding an
``hvd.step.dispatch`` span while a profiler trace is being collected. The
scopes are metadata: the values a step computes do not change by a bit.
"""

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.parallel import dp, mesh as mesh_lib, zero
from horovod_tpu.profiler import annotate

BUILDERS = {"plain": dp.make_train_step,
            "stateful": dp.make_stateful_train_step}
VARIANTS = {"allreduce": {}, "bucketed": {"bucket_bytes": 256},
            "sharded_update": {"sharded_update": True}}
CASES = [(b, v) for b in BUILDERS for v in VARIANTS]
# a name stack as the lowered text's locations print it
NAME_STACK = re.compile(r'"([^"]*(?:phase_|hvd_)[^"]*)"')


@pytest.fixture(scope="module")
def mesh4(devices):
    return mesh_lib.data_parallel_mesh(devices[:4])


def _mlp(p, x):
    return jnp.tanh(x @ p["w1"] + p["b1"]) @ p["w2"]


def _loss(p, batch, rng):
    loss = jnp.mean((_mlp(p, batch["x"]) - batch["y"]) ** 2)
    return loss, {"seen": jnp.int32(batch["x"].shape[0]), "loss": loss}


def _stateful_loss(p, model_state, batch, rng):
    out = _mlp(p, batch["x"])
    new_state = {"mean": 0.9 * model_state["mean"] + 0.1 * jnp.mean(out)}
    return jnp.mean((out - batch["y"]) ** 2), (new_state, {})


def _job(builder, variant, mesh):
    """(step, its arguments before the batch, batch) of a two-layer MLP."""
    key = jax.random.key(3)
    k1, k2, kx, ky = jax.random.split(key, 4)
    params = dp.replicate({"w1": 0.3 * jax.random.normal(k1, (8, 16)),
                           "b1": jnp.zeros((16,)),
                           "w2": 0.3 * jax.random.normal(k2, (16, 4))}, mesh)
    optimizer = optax.adam(1e-2)
    kwargs = VARIANTS[variant]
    if kwargs.get("sharded_update"):
        opt_state = zero.sharded_opt_init(optimizer, params, mesh)
    else:
        opt_state = dp.replicate(optimizer.init(params), mesh)
    state = (params, opt_state)
    if builder == "stateful":
        state += (dp.replicate({"mean": jnp.zeros(())}, mesh),)
    loss_fn = _stateful_loss if builder == "stateful" else _loss
    step = BUILDERS[builder](loss_fn, optimizer, mesh, donate=False,
                             **kwargs)
    batch = dp.shard_batch({"x": jax.random.normal(kx, (16, 8)),
                            "y": jax.random.normal(ky, (16, 4))}, mesh)
    return step, state, batch


def _train(builder, variant, mesh, steps=3):
    step, state, batch = _job(builder, variant, mesh)
    losses = []
    for _ in range(steps):
        out = step(*state, batch, jax.random.key(0))
        state = tuple(out[:len(state)])
        losses.append(np.asarray(out.loss))
    return losses, jax.tree_util.tree_map(np.asarray, state[0])


@pytest.mark.parametrize("builder,variant", CASES)
def test_lowered_step_holds_its_phases(mesh4, builder, variant):
    step, state, batch = _job(builder, variant, mesh4)
    text = step.lower(*state, batch, jax.random.key(0)).as_text(
        debug_info=True)
    stacks = set(NAME_STACK.findall(text))
    expected = {"forward_backward", "grad_exchange", "optimizer_update",
                "output_sync"}
    if variant == "sharded_update":
        expected.add("param_gather")
    found = {p for p in annotate.PHASES
             if any(f"phase_{p}" in s for s in stacks)}
    assert found == expected
    collectives = [s for s in stacks
                   if re.search(r"hvd_(allreduce|reducescatter)_", s)]
    assert collectives
    for stack in collectives:
        before = re.split(r"hvd_(?:allreduce|reducescatter)_", stack)[0]
        assert "phase_grad_exchange" in before or \
            "phase_output_sync" in before, stack
    # readers name a collective by the first hvd_* scope: it is its own
    for stack in stacks:
        first = re.search(r"hvd_[A-Za-z0-9_]+", stack)
        if first:
            assert re.match(r"hvd_(allreduce|reducescatter|allgather)",
                            first.group(0)), stack
    kind = "reducescatter" if variant == "sharded_update" else "allreduce"
    assert any(f"phase_grad_exchange/hvd_{kind}_" in s for s in stacks)


@pytest.mark.parametrize("builder,variant", CASES)
def test_phases_change_no_value(mesh4, monkeypatch, builder, variant):
    losses, params = _train(builder, variant, mesh4)
    for module in (dp, zero):
        monkeypatch.setattr(module, "step_phase",
                            lambda name: contextlib.nullcontext())
    bare_losses, bare_params = _train(builder, variant, mesh4)
    assert [x.tobytes() for x in losses] == \
        [x.tobytes() for x in bare_losses]
    assert losses[-1] < losses[0]
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(bare_params)):
        assert got.tobytes() == want.tobytes()


def test_an_unknown_phase_is_an_error():
    with pytest.raises(ValueError, match="unknown step phase"):
        annotate.step_phase("hvd_everything")
    assert all(not p.startswith("hvd_") for p in annotate.PHASES)
    assert annotate.PHASE_PREFIX == "phase_"


@pytest.mark.parametrize("scope,names,prefix,known,unknown", [
    (annotate.moe_scope, annotate.MOE_SCOPES, "moe_", "moe_shared",
     "moe_everything"),
    (annotate.ssm_scope, annotate.SSM_SCOPES, "ssm_", "ssm_scan",
     "ssm_everything"),
    (annotate.shortconv_scope, annotate.SHORTCONV_SCOPES, "shortconv_",
     "shortconv_mix", "shortconv_everything"),
    (annotate.mla_scope, annotate.MLA_SCOPES, "mla_", "mla_rope",
     "mla_everything"),
    (annotate.mtp_scope, annotate.MTP_SCOPES, "mtp_", "mtp_merge",
     "mtp_everything")],
    ids=["moe", "ssm", "shortconv", "mla", "mtp"])
def test_layer_scopes_take_their_names_and_refuse_others(
        scope, names, prefix, known, unknown):
    """The expert layer's, the state-space mixer's, the short-convolution
    operator's, the latent attention operator's and the
    multi-token-prediction module's parts: a name of the
    list is written into the traced operations' ``op_name``; any other name
    is an error. Neither prefix is a phase's or a collective's."""
    assert known in names
    assert all(n.startswith(prefix) for n in names)
    with pytest.raises(ValueError, match="unknown .* scope"):
        scope(unknown)

    def f(x):
        with scope(known):
            return x * 2.0
    text = jax.jit(f).lower(jnp.ones((4,))).as_text(debug_info=True)
    assert known in text


def test_wrapped_step_writes_one_span_a_call(mesh4, tmp_path):
    from jax.profiler import ProfileData
    step, state, batch = _job("plain", "allreduce", mesh4)
    out = step(*state, batch, jax.random.key(0))  # compiled outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            out = step(out.params, out.opt_state, batch, jax.random.key(0))
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("hvd.step")]
    steps = sorted((e for e in events if e.name == annotate.STEP_SPAN),
                   key=lambda e: e.start_ns)
    dispatches = [e for e in events
                  if e.name == annotate.STEP_DISPATCH_SPAN]
    assert len(steps) == 3 and len(dispatches) == 3
    assert [dict(e.stats)["step_num"] for e in steps] == [1, 2, 3]
    for s in steps:
        inside = [d for d in dispatches if s.start_ns <= d.start_ns and
                  d.start_ns + d.duration_ns <= s.start_ns + s.duration_ns]
        assert len(inside) == 1


def test_the_compile_cache_keys_on_the_scopes(monkeypatch, tmp_path):
    """JAX strips op_name before hashing a program for its persistent
    cache: a step compiled before the scopes would be handed to the step
    with them, names and all. ``enable_compile_cache`` keys on them, and
    cuts the checkout's own path from the source files the metadata names,
    so the same tree at another path still hits."""
    from horovod_tpu.common import compile_cache
    flag = "jax_compilation_cache_include_metadata_in_key"
    cut = "jax_hlo_source_file_canonicalization_regex"
    before = {name: getattr(jax.config, name) for name in (flag, cut)}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update(cut, None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert getattr(jax.config, flag) is True
        here = str(compile_cache.ROOT / "horovod_tpu" / "parallel" / "dp.py")
        assert re.sub(getattr(jax.config, cut), "", here) == \
            "horovod_tpu/parallel/dp.py"
        assert re.sub(getattr(jax.config, cut), "", "/opt" + here) == \
            "/opt" + here
        jax.config.update(cut, "mine")   # a user's own pattern stays
        compile_cache.enable_compile_cache()
        assert getattr(jax.config, cut) == "mine"
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
