"""TensorFlow frontend: op numerics, custom gradients, DistributedOptimizer
and DistributedGradientTape training, keras callbacks — run across real
processes over the TCP controller (the analog of the reference's
test/parallel/test_tensorflow2.py)."""

import pytest

import os
import subprocess
import sys
import textwrap

from conftest import free_port

# TF import + graph-mode session tests push the file past the ~3 min tier-1 per-file budget (ISSUE 2 satellite: tier-1 runs -m 'not slow')
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["HVDTPU_REPO"])
    import numpy as np
    import tensorflow as tf
    tf.keras.utils.set_random_seed(1234)
    import horovod_tpu.tensorflow as hvd
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
""")


def _run_workers(tmp_path, body: str, size: int, timeout: int = 300):
    script = tmp_path / "worker.py"
    script.write_text(PRELUDE + textwrap.dedent(body) + textwrap.dedent("""
        hvd.shutdown()
        print(f"tf worker {rank} OK")
    """))
    port = free_port()
    procs = []
    for r in range(size):
        env = dict(os.environ,
                   HVDTPU_REPO=REPO,
                   HOROVOD_RANK=str(r), HOROVOD_SIZE=str(size),
                   HOROVOD_LOCAL_RANK=str(r), HOROVOD_LOCAL_SIZE=str(size),
                   HOROVOD_CONTROLLER_ADDR="127.0.0.1",
                   HOROVOD_CONTROLLER_PORT=str(port),
                   JAX_PLATFORMS="cpu",
                   TF_CPP_MIN_LOG_LEVEL="3")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = [p.communicate(timeout=timeout)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"tf worker {r} OK" in out
    return outs


def test_tf_ops_numerics(tmp_path):
    _run_workers(tmp_path, """
        # allreduce sum/average/min/max
        t = tf.constant([1.0, 2.0]) * float(rank + 1)
        s = hvd.allreduce(t, op=hvd.Sum).numpy()
        assert np.allclose(s, np.array([1.0, 2.0]) * 6), s
        a = hvd.allreduce(t, op=hvd.Average).numpy()
        assert np.allclose(a, np.array([1.0, 2.0]) * 2), a
        mn = hvd.allreduce(t, op=hvd.Min).numpy()
        assert np.allclose(mn, [1.0, 2.0]), mn
        mx = hvd.allreduce(t, op=hvd.Max).numpy()
        assert np.allclose(mx, [3.0, 6.0]), mx

        # dtypes incl. bf16/f16/int
        for dtype in (tf.float16, tf.bfloat16, tf.int32, tf.int64):
            x = tf.cast(tf.fill([4], rank + 1), dtype)
            out = hvd.allreduce(x, op=hvd.Sum).numpy()
            assert np.allclose(np.asarray(out, np.float64), 6.0), (dtype, out)

        # grouped with compression
        outs = hvd.grouped_allreduce(
            [tf.fill([2], float(rank)), tf.fill([3], float(rank * 2))],
            op=hvd.Average, compression=hvd.Compression.bf16)
        assert np.allclose(outs[0].numpy(), 1.0), outs[0]
        assert np.allclose(outs[1].numpy(), 2.0), outs[1]

        # allgather with unequal first dims
        g = hvd.allgather(tf.fill([rank + 1, 2], float(rank))).numpy()
        assert g.shape == (6, 2), g.shape
        exp = np.concatenate([np.full((r + 1, 2), float(r)) for r in range(3)])
        assert np.allclose(g, exp), g

        # broadcast
        b = hvd.broadcast(tf.fill([3], float(rank + 10)), 1).numpy()
        assert np.allclose(b, 11.0), b

        # alltoall with uneven splits: rank r sends r+1 rows to each peer
        rows = 3 * (rank + 1)
        t = tf.reshape(tf.fill([rows], float(rank)), (rows, 1))
        out = hvd.alltoall(t, splits=[rank + 1] * 3).numpy()
        exp = np.concatenate([np.full((r + 1, 1), float(r)) for r in range(3)])
        assert np.allclose(out, exp), out

        # object transport
        obj = hvd.broadcast_object({"epoch": 7} if rank == 0 else None)
        assert obj == {"epoch": 7}, obj
        gathered = hvd.allgather_object(("r", rank))
        assert gathered == [("r", r) for r in range(3)], gathered

        # join returns last joined rank
        j = hvd.join()
        assert 0 <= j < size, j
    """, size=3)


def test_tf_gradients(tmp_path):
    _run_workers(tmp_path, """
        # allreduce grad = mirror allreduce
        v = tf.Variable([1.0 + rank, 2.0])
        with tf.GradientTape() as tape:
            y = tf.reduce_sum(hvd.allreduce(v * v, op=hvd.Sum))
        g = tape.gradient(y, v).numpy()
        # d/dv sum_r allreduce(v^2) = size * 2v (each rank's loss sees it)
        assert np.allclose(g, 2 * v.numpy() * size), g

        # allgather grad: allreduce-sum then slice own rows
        w = tf.Variable(tf.fill([rank + 1, 2], 1.0 + rank))
        with tf.GradientTape() as tape:
            out = hvd.allgather(w)
            y = tf.reduce_sum(out * 3.0)
        g = tape.gradient(y, w).numpy()
        assert g.shape == (rank + 1, 2), g.shape
        assert np.allclose(g, 3.0 * size), g

        # broadcast grad: reduce to root, zeros elsewhere
        u = tf.Variable([2.0])
        with tf.GradientTape() as tape:
            y = tf.reduce_sum(hvd.broadcast(u, 1) * (rank + 1.0))
        g = tape.gradient(y, u).numpy()
        exp = 1.0 + 2.0 if rank == 1 else 0.0  # sum of (r+1) = 6 at root
        assert np.allclose(g, 6.0 if rank == 1 else 0.0), g

        # alltoall grad routes back along recv splits
        rows = 2 * size
        t = tf.Variable(tf.reshape(tf.range(rows, dtype=tf.float32),
                                   (rows, 1)))
        with tf.GradientTape() as tape:
            y = tf.reduce_sum(hvd.alltoall(t) * (rank + 1.0))
        g = tape.gradient(y, t).numpy()
        exp = np.repeat(np.arange(1.0, size + 1.0), 2).reshape(rows, 1)
        assert np.allclose(g, exp), g
    """, size=3)


def test_tf_tape_and_optimizer_training(tmp_path):
    _run_workers(tmp_path, """
        # rank-dependent init diverges; broadcast_variables restores lockstep
        model = tf.keras.Sequential([tf.keras.layers.Dense(1)])
        model.build((None, 4))
        model.variables[0].assign_add(tf.fill(model.variables[0].shape,
                                              float(rank)))
        hvd.broadcast_variables(model.variables, root_rank=0)

        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(0.05))
        rng = np.random.RandomState(42 + rank)  # different shards per rank
        Wt = np.array([[1.0], [-2.0], [0.5], [3.0]], np.float32)
        losses = []
        for step in range(30):
            X = rng.randn(16, 4).astype(np.float32)
            Y = X @ Wt
            with tf.GradientTape() as tape:
                pred = model(X, training=True)
                loss = tf.reduce_mean(tf.square(pred - Y))
            tape = hvd.DistributedGradientTape(tape)
            grads = tape.gradient(loss, model.trainable_variables)
            opt.apply_gradients(zip(grads, model.trainable_variables))
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.2, losses[::10]

        # weights identical across ranks after synced training
        flat = np.concatenate([v.numpy().ravel() for v in model.variables])
        gathered = hvd.allgather_object(flat.tolist())
        for other in gathered:
            assert np.allclose(flat, np.asarray(other), atol=1e-5)
    """, size=2)


def test_keras_fit_with_callbacks(tmp_path):
    _run_workers(tmp_path, """
        import horovod_tpu.keras as hvdk
        model = tf.keras.Sequential([tf.keras.layers.Dense(1)])
        model.build((None, 2))
        # rank-skewed init; the broadcast callback must align it on batch 1
        model.variables[0].assign_add(tf.fill(model.variables[0].shape,
                                              float(rank) * 0.5))
        base_lr = 0.05
        model.compile(optimizer=hvdk.DistributedOptimizer(
            tf.keras.optimizers.SGD(base_lr)), loss="mse")
        rng = np.random.RandomState(7 + rank)
        X = rng.randn(64, 2).astype(np.float32)
        Y = (X @ np.array([[1.0], [2.0]], np.float32)).astype(np.float32)
        cbs = [hvdk.callbacks.BroadcastGlobalVariablesCallback(0),
               hvdk.callbacks.MetricAverageCallback(),
               hvdk.callbacks.LearningRateWarmupCallback(
                   base_lr, warmup_epochs=2, steps_per_epoch=8)]
        hist = model.fit(X, Y, epochs=3, batch_size=8, callbacks=cbs,
                         verbose=0)
        # metric averaging: every rank logs the same (averaged) loss
        losses = hist.history["loss"]
        gathered = hvd.allgather_object([round(float(x), 6) for x in losses])
        assert all(g == gathered[0] for g in gathered), gathered
        assert losses[-1] < losses[0], losses
        # weights in lockstep after fit
        flat = np.concatenate([v.numpy().ravel() for v in model.variables])
        for other in hvd.allgather_object(flat.tolist()):
            assert np.allclose(flat, np.asarray(other), atol=1e-5)
        # warmup ended at size-scaled lr
        lr = float(model.optimizer.learning_rate.numpy())
        assert abs(lr - base_lr) < 1e-6, lr
    """, size=2)


def test_tf_sync_batch_norm(tmp_path):
    _run_workers(tmp_path, """
        from horovod_tpu.tensorflow.sync_batch_norm import \\
            SyncBatchNormalization
        bn = SyncBatchNormalization(momentum=0.9)
        # rank-specific shards; global batch stats must match concatenation
        x = tf.constant(np.arange(8, dtype=np.float32).reshape(4, 2)
                        + 10 * rank)
        y = bn(x, training=True).numpy()
        full = np.concatenate([np.arange(8).reshape(4, 2) + 10 * r
                               for r in range(2)]).astype(np.float32)
        mu, var = full.mean(0), full.var(0)
        exp = (np.asarray(x) - mu) / np.sqrt(var + bn.epsilon)
        assert np.allclose(y, exp, atol=1e-4), (y, exp)
        assert np.allclose(bn.moving_mean.numpy(), mu * 0.1, atol=1e-4)
    """, size=2)


def test_tf_elastic_state(tmp_path):
    _run_workers(tmp_path, """
        from horovod_tpu.tensorflow.elastic import TensorFlowKerasState
        model = tf.keras.Sequential([tf.keras.layers.Dense(2)])
        model.build((None, 3))
        model.variables[0].assign(tf.fill(model.variables[0].shape,
                                          float(rank + 1)))
        st = TensorFlowKerasState(model=model, epoch=10 * (rank + 1))
        st.sync()
        # rank0's weights + tracked kwargs everywhere
        assert np.allclose(model.variables[0].numpy(), 1.0)
        assert st.epoch == 10, st.epoch
        # commit/restore round-trip
        st.commit()
        model.variables[0].assign(tf.zeros_like(model.variables[0]))
        st.restore()
        assert np.allclose(model.variables[0].numpy(), 1.0)
    """, size=2)


def test_tf_function_bpps_and_sparse(tmp_path):
    _run_workers(tmp_path, """
        # graph-safe gradient aggregation: bpps=2 inside tf.function
        # (reference: tensorflow/gradient_aggregation.py — tf.Variable
        # counters + tf.cond, not python state)
        v = tf.Variable([1.0])
        opt = hvd.DistributedOptimizer(tf.keras.optimizers.SGD(1.0),
                                       backward_passes_per_step=2)

        @tf.function
        def train_step():
            with tf.GradientTape() as t:
                loss = tf.reduce_sum(v) * (rank + 1.0)
            g = t.gradient(loss, [v])
            opt.apply_gradients(zip(g, [v]))

        for _ in range(4):
            train_step()
        # per boundary: sum over 2 passes of avg_r(rank+1) = 2 * 1.5 = 3
        assert np.allclose(v.numpy(), [1.0 - 2 * 3.0]), v.numpy()
        assert int(opt.iterations.numpy()) == 4, opt.iterations

        # sparse gradients: IndexedSlices ride allgather, not densify
        emb = tf.Variable(tf.ones([6, 2]))
        tape = hvd.DistributedGradientTape(tf.GradientTape())
        with tape:
            rows = tf.gather(emb, [rank, rank])  # rank r touches row r
            loss = tf.reduce_sum(rows) * (rank + 1.0)
        g = tape.gradient(loss, [emb])[0]
        assert isinstance(g, tf.IndexedSlices), type(g)
        idx = np.asarray(g.indices.numpy())
        vals = np.asarray(g.values.numpy())
        assert sorted(idx.tolist()) == [0, 0, 1, 1], idx
        # average divides gathered values by size
        dense = np.zeros((6, 2), np.float32)
        np.add.at(dense, idx, vals)
        exp = np.zeros((6, 2), np.float32)
        exp[0] = 2 * 1.0 / size
        exp[1] = 2 * 2.0 / size
        assert np.allclose(dense, exp), dense

        # sparse_as_dense path densifies before the grouped allreduce
        tape2 = hvd.DistributedGradientTape(tf.GradientTape(),
                                            sparse_as_dense=True)
        with tape2:
            loss = tf.reduce_sum(tf.gather(emb, [0])) * (rank + 1.0)
        g2 = tape2.gradient(loss, [emb])[0]
        assert not isinstance(g2, tf.IndexedSlices), type(g2)

        # symbolic alltoall splits inside tf.function
        @tf.function
        def a2a(x, sp):
            return hvd.alltoall(x, splits=sp)

        t = tf.fill([size], float(rank))
        out = a2a(t, tf.ones([size], tf.int32))
        assert np.allclose(out.numpy(), np.arange(size, dtype=np.float32)), \\
            out.numpy()
    """, size=2)


def test_keras_load_model_resumes_distributed(tmp_path):
    """save -> hvd.keras.load_model -> continue training across 2
    processes: the saved optimizer (incl. iteration count and momentum
    slots) comes back wrapped in DistributedOptimizer (reference:
    keras/__init__.py:147-181)."""
    _run_workers(tmp_path, """
        import horovod_tpu.keras as hvd_keras

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(4, activation="relu",
                                   input_shape=(3,)),
             tf.keras.layers.Dense(1)])
        opt = hvd_keras.DistributedOptimizer(
            tf.keras.optimizers.SGD(0.05, momentum=0.9))
        model.compile(optimizer=opt, loss="mse")
        hvd_keras.broadcast_variables(model.weights, root_rank=0)

        rs = np.random.RandomState(7)
        x = rs.rand(16, 3).astype("float32")
        y = rs.rand(16, 1).astype("float32")
        model.fit(x, y, epochs=1, batch_size=8, verbose=0)
        iters_before = int(model.optimizer.iterations.numpy())
        assert iters_before > 0

        import tempfile
        path = os.path.join(
            tempfile.gettempdir(),
            f"m{rank}_{os.environ['HOROVOD_CONTROLLER_PORT']}.keras")
        model.save(path)

        loaded = hvd_keras.load_model(path)
        os.unlink(path)
        # the restored optimizer is distributed (our wrapper attribute)
        assert hasattr(loaded.optimizer, "_hvd_state"), \
            type(loaded.optimizer)
        # iteration count survived the round trip
        assert int(loaded.optimizer.iterations.numpy()) == iters_before
        # weights identical across ranks and to the saved model
        for a, b in zip(model.get_weights(), loaded.get_weights()):
            assert np.allclose(a, b)

        # continue training: gradients are combined across ranks — all
        # ranks end with identical weights even on different data
        x2 = rs.rand(8, 3).astype("float32") + rank
        l0 = float(loaded.evaluate(x, y, verbose=0))
        loaded.fit(x2, y[:8], epochs=2, batch_size=8, verbose=0)
        w = loaded.get_weights()[0]
        digest = hvd_keras.allgather(
            tf.constant(w.ravel()[None, :4])).numpy()
        for r in range(1, size):
            assert np.allclose(digest[r], digest[0], atol=1e-6), digest
    """, size=2)
