"""Spark/Ray integration layers, driven by process-backed scheduler fakes.

Neither pyspark nor ray is installed here; the fakes implement exactly the
scheduler surface the adapters consume (barrier mapPartitionsWithIndex /
remote actors + get) and run every task in a real separate process, so the
engine rendezvous and collectives execute for real — the analog of the
reference's test/integration/test_spark.py run() coverage with the
scheduler replaced.
"""

import os
import subprocess
import sys
import tempfile

import cloudpickle
import pytest

# spark-session-backed integration runs push the file past the ~3 min tier-1 per-file budget (ISSUE 2 satellite: tier-1 runs -m 'not slow')
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# This module is not importable from the spawned task processes; ship its
# functions by value, as a user's notebook-defined fn would be.
cloudpickle.register_pickle_by_value(sys.modules[__name__])


class _ProcCall:
    """One function call in a fresh process; result via pickle file."""

    def __init__(self, fn, args=(), kwargs=None):
        self._td = tempfile.TemporaryDirectory(prefix="hvdtpu_fake_")
        payload = os.path.join(self._td.name, "call.pkl")
        self._out = os.path.join(self._td.name, "out.pkl")
        with open(payload, "wb") as f:
            cloudpickle.dump((fn, args, kwargs or {}), f)
        code = (
            "import sys, cloudpickle\n"
            f"sys.path.insert(0, {REPO!r})\n"
            f"sys.path.insert(0, {os.path.join(REPO, 'tests')!r})\n"
            f"fn, args, kwargs = cloudpickle.load(open({payload!r}, 'rb'))\n"
            "res = fn(*args, **kwargs)\n"
            f"cloudpickle.dump(res, open({self._out!r}, 'wb'))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self._proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT)

    def get(self, timeout=180):
        out, _ = self._proc.communicate(timeout=timeout)
        if self._proc.returncode != 0:
            raise RuntimeError(f"task failed:\n{out.decode()}")
        with open(self._out, "rb") as f:
            return cloudpickle.load(f)


# -- fake Spark --------------------------------------------------------------


class _FakeMapped:
    def __init__(self, indices, f):
        self._indices, self._f = indices, f

    def collect(self):
        def one(i, f):
            return list(f(i, iter(())))
        calls = [_ProcCall(one, (i, self._f)) for i in self._indices]
        pairs = []
        for c in calls:
            pairs.extend(c.get())
        return pairs


class _FakeBarrierRDD:
    def __init__(self, indices):
        self._indices = indices

    def mapPartitionsWithIndex(self, f):
        return _FakeMapped(self._indices, f)


class _FakeRDD:
    def __init__(self, indices):
        self._indices = indices

    def barrier(self):
        return _FakeBarrierRDD(self._indices)


class FakeSparkContext:
    defaultParallelism = 2

    def parallelize(self, seq, n):
        assert len(list(seq)) == n
        return _FakeRDD(list(seq))


# -- fake Ray ----------------------------------------------------------------


class _FakeMethod:
    def __init__(self, actor, name):
        self._actor, self._name = actor, name

    def remote(self, *args, **kwargs):
        def call(cls, ctor_args, name, margs, mkwargs):
            obj = cls(*ctor_args)
            return getattr(obj, name)(*margs, **mkwargs)
        return _ProcCall(call, (self._actor._cls, self._actor._ctor_args,
                                self._name, args, kwargs))


class _FakeActor:
    def __init__(self, cls, ctor_args):
        self._cls, self._ctor_args = cls, ctor_args

    def __getattr__(self, name):
        return _FakeMethod(self, name)


class _FakeActorClass:
    def __init__(self, cls):
        self._cls = cls

    def options(self, **_kw):
        return self

    def remote(self, *args):
        return _FakeActor(self._cls, args)


class FakeRay:
    @staticmethod
    def remote(cls):
        return _FakeActorClass(cls)

    @staticmethod
    def get(refs):
        return [r.get() for r in refs]


# -- the worker function both jobs run ---------------------------------------


def _train_fn(scale):
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    hvd.init()
    total = float(np.asarray(hvd_jax.allreduce(
        np.asarray([float(hvd.rank() + 1)], np.float32), op=hvd_jax.Sum))[0])
    obj = hvd_jax.broadcast_object({"seed": 7} if hvd.rank() == 0 else None)
    out = (hvd.rank(), hvd.size(), total * scale, obj)
    hvd.shutdown()
    return out


def test_spark_run_on_barrier_stage():
    import horovod_tpu.spark as hvd_spark
    results = hvd_spark.run(_train_fn, args=(10.0,), num_proc=2,
                            spark_context=FakeSparkContext(),
                            controller_addr="127.0.0.1")
    assert results == [(r, 2, 30.0, {"seed": 7}) for r in range(2)], results


def test_spark_default_parallelism():
    import horovod_tpu.spark as hvd_spark
    results = hvd_spark.run(_train_fn, args=(1.0,),
                            spark_context=FakeSparkContext(),
                            controller_addr="127.0.0.1")
    assert len(results) == 2 and results[0][1] == 2


def test_ray_executor_lifecycle():
    from horovod_tpu.ray import RayExecutor
    ex = RayExecutor(num_workers=2, controller_addr="127.0.0.1",
                     ray_module=FakeRay()).start()
    results = ex.run(_train_fn, args=(2.0,))
    assert results == [(r, 2, 6.0, {"seed": 7}) for r in range(2)], results
    ex.shutdown()
    with pytest.raises(RuntimeError, match="start"):
        ex.run(_train_fn, args=(1.0,))


def test_local_process_backend():
    """The built-in fallback backend works standalone."""
    from horovod_tpu.runner.cluster_job import (ClusterJobSpec,
                                                run_local_processes)
    spec = ClusterJobSpec(2, controller_addr="127.0.0.1")
    results = run_local_processes(spec, _train_fn, (3.0,), {})
    assert results == [(r, 2, 9.0, {"seed": 7}) for r in range(2)], results


def test_dynamic_endpoint_negotiation():
    """Without controller_addr, rank 0's task allocates the controller
    ports on its own host and publishes them via the driver KV — the
    driver never free_port()s for a host it may not share (the Spark/Ray
    multi-node TOCTOU)."""
    from horovod_tpu.runner.cluster_job import (ClusterJobSpec,
                                                run_local_processes)
    from horovod_tpu.runner.http_kv import KVServer
    kv = KVServer().start()
    try:
        spec = ClusterJobSpec(2, rendezvous=("127.0.0.1", kv.port))
        assert spec.controller_port is None  # no driver-side allocation
        env0 = spec.worker_env(0)
        assert "HOROVOD_CONTROLLER_PORT" not in env0
        assert env0["HOROVOD_CLUSTER_JOB"] == spec.job_id
        results = run_local_processes(spec, _train_fn, (4.0,), {})
        assert results == [(r, 2, 12.0, {"seed": 7}) for r in range(2)], \
            results
        # rank 0 published the endpoint under the job's (round-scoped) key
        info = kv.get_json(f"cluster/{spec.job_id}/r0/controller")
        assert info and info["port"] != info["data_port"]
    finally:
        kv.stop()


# -- fake elastic Ray --------------------------------------------------------


class _FakeElasticRef:
    def __init__(self, cmd, env):
        full = dict(os.environ)
        full.update(env)
        self._proc = subprocess.Popen(cmd, env=full,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT)

    def done(self):
        return self._proc.poll() is not None


class _FakeRemoteFn:
    """Emulates @ray.remote(max_retries=0) def _exec(cmd, env)."""

    def options(self, **_kw):
        return self

    def remote(self, cmd, env):
        return _FakeElasticRef(cmd, env)


class FakeElasticRay:
    """The slice of the ray API ElasticRayExecutor consumes, with tasks as
    real local subprocesses — the driver, generations, KV results, and
    run_task all execute for real."""

    util = None  # no NodeAffinitySchedulingStrategy: soft pinning skipped

    @staticmethod
    def remote(*_a, **_kw):
        # @ray.remote(max_retries=0) form: returns a decorator
        return lambda _fn: _FakeRemoteFn()

    @staticmethod
    def nodes():
        return [{"Alive": True, "NodeManagerAddress": "localhost",
                 "Resources": {"CPU": 2.0}, "NodeID": "fake-node"}]

    @staticmethod
    def wait(refs, timeout=0):
        import time
        deadline = time.monotonic() + (timeout or 0)
        while True:
            ready = [r for r in refs if r.done()]
            if ready or time.monotonic() >= deadline:
                return ready, [r for r in refs if not r.done()]
            time.sleep(0.05)

    @staticmethod
    def get(ref):
        return ref._proc.wait()

    @staticmethod
    def cancel(ref, force=False):
        if ref._proc.poll() is None:
            (ref._proc.kill if force else ref._proc.terminate)()


def _elastic_train_fn():
    import numpy as np
    import horovod_tpu as hvd
    import horovod_tpu.jax as hvd_jax
    hvd.init()
    total = float(np.asarray(hvd_jax.allreduce(
        np.asarray([1.0], np.float32), op=hvd_jax.Sum))[0])
    out = (hvd.rank(), hvd.size(), total)
    hvd.shutdown()
    return out


def test_elastic_ray_executor():
    from horovod_tpu.ray import ElasticRayExecutor, RayHostDiscovery

    discovery = RayHostDiscovery(cpus_per_slot=1, ray_module=FakeElasticRay)
    assert discovery.find_available_hosts_and_slots() == {"localhost": 2}

    settings = ElasticRayExecutor.create_settings(min_np=2, max_np=2)
    ex = ElasticRayExecutor(settings, override_discovery=discovery,
                            ray_module=FakeElasticRay).start()
    results = ex.run(_elastic_train_fn)
    assert results == [(0, 2, 2.0), (1, 2, 2.0)], results


# -- real schedulers (run when installed) ------------------------------------


def test_real_pyspark_barrier_run(tmp_path):
    pyspark = pytest.importorskip("pyspark")
    import horovod_tpu.spark as hvd_spark
    spark = pyspark.sql.SparkSession.builder \
        .master("local[2]").appName("hvdtpu-test").getOrCreate()
    try:
        results = hvd_spark.run(_train_fn, args=(10.0,), num_proc=2,
                                spark_context=spark.sparkContext)
        assert results == [(r, 2, 30.0, {"seed": 7}) for r in range(2)]
    finally:
        spark.stop()


def test_real_ray_executor():
    ray = pytest.importorskip("ray")
    from horovod_tpu.ray import RayExecutor
    ray.init(num_cpus=2, include_dashboard=False,
             ignore_reinit_error=True)
    try:
        ex = RayExecutor(num_workers=2, ray_module=ray).start()
        results = ex.run(_train_fn, args=(2.0,))
        assert results == [(r, 2, 6.0, {"seed": 7}) for r in range(2)]
        ex.shutdown()
    finally:
        ray.shutdown()
