"""Bounded CPU-backend tuning session (``make tune-smoke`` and the
slow-marked pytest wrapper).

A real closed loop on the real engine — no TPU needed: ``world`` loopback
engine ranks run a synthetic training step whose backward produces a
ResNet-50-shaped gradient set bucket by bucket (compute slices interleave
with bucket submissions, emulating the backward's production order), the
eager allreduce carries the exchange, the flight ring black-boxes every
step, and the PR-7 attribution decomposition yields the exposed-comm
objective the :class:`~horovod_tpu.tune.tuner.TuningSession` optimizes.

The "before" epoch is the untuned baseline — ``bucket_bytes=0``, i.e. the
legacy shape where the whole exchange is submitted after backward
finishes and nothing overlaps — measured with the same harness as the
converged "after" epoch, so the reported exposed-comm drop is an
apples-to-apples measurement of what the tuner bought (the CPU-backend
acceptance figure when no TPU is attached: >= 30% drop).

Usage::

    python -m horovod_tpu.tune.smoke [--steps 20] [--json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional

import numpy as np


def resnet50_shaped_sizes(scale: int = 16) -> List[int]:
    """A deterministic gradient-size distribution shaped like ResNet-50's
    ~160 leaves (a few multi-MB conv kernels, a long tail of small
    BN/bias vectors), scaled down by ``scale`` so the smoke stays CPU
    -sized. Head-of-list = input side; the harness walks it reversed
    (backward order)."""
    sizes: List[int] = [9408]  # stem conv
    stages = ((64, 256, 3), (128, 512, 4), (256, 1024, 6), (512, 2048, 3))
    for width, out_ch, blocks in stages:
        for _ in range(blocks):
            sizes += [out_ch * width, width * width * 9, width * out_ch]
            sizes += [width, width, out_ch, out_ch]  # BN scale/bias pairs
    sizes += [2048 * 1000, 1000]  # fc
    return [max(64, s // scale) for s in sizes]


def _bucketize(payload, bucket_bytes: int) -> List[List[int]]:
    """Partition the payload leaves with THE training-path planner
    (parallel/bucketing.plan_buckets) so the smoke's measured partition
    can never diverge from what `make_train_step(bucket_bytes=)` does."""
    from horovod_tpu.parallel.bucketing import plan_buckets
    return [list(b.indices) for b in plan_buckets(payload, bucket_bytes)]


class _Harness:
    """The multi-rank step driver. One thread per rank; a barrier keeps
    every rank reading the same shared config for the same step (the
    leader mutates it only at epoch boundaries, before re-entering the
    barrier)."""

    def __init__(self, world: int = 2, scale: int = 16,
                 compute_seconds: float = 0.04):
        from horovod_tpu.engine import EngineSession
        from horovod_tpu.jax.mpi_ops import EagerExecutor
        self.world = world
        self.sizes = resnet50_shaped_sizes(scale)
        self.compute_seconds = compute_seconds
        group = f"tune-smoke-{uuid.uuid4().hex[:8]}"
        self.sessions = [EngineSession(rank=r, size=world,
                                       transport="loopback", group=group,
                                       cycle_time_ms=1.0)
                         for r in range(world)]
        self.executors = [EagerExecutor(s) for s in self.sessions]
        self.config: Dict[str, object] = {"bucket_bytes": 0}
        self.step_id = 0
        self._payload = [np.full((s,), 0.5, np.float32)
                         for s in self.sizes]

    def close(self):
        for s in self.sessions:
            s._lib.hvdtpu_shutdown(s._session)
        for s in self.sessions:
            s.destroy()

    def run_epoch(self, steps: int, on_step=None) -> None:
        """Run ``steps`` lockstep steps across all ranks; ``on_step`` (the
        tuner hook) fires on the leader thread after each step, before the
        next barrier, so config changes land at step boundaries."""
        barrier = threading.Barrier(self.world)
        errors: List[BaseException] = []

        def work(rank: int):
            from horovod_tpu.jax.mpi_ops import _OP_ALLREDUCE
            from horovod_tpu.parallel.collectives import Sum
            ex = self.executors[rank]
            session = self.sessions[rank]
            try:
                for _ in range(steps):
                    barrier.wait()
                    buckets = _bucketize(self._payload,
                                         int(self.config["bucket_bytes"]))
                    sid = self.step_id + 1
                    session.step_begin(sid)
                    slice_s = self.compute_seconds / max(len(buckets), 1)
                    handles = []
                    for bi, idxs in enumerate(buckets):
                        # the compute slice that produces this bucket's
                        # grads, THEN the exchange — overlap comes from the
                        # engine executing earlier buckets meanwhile
                        time.sleep(slice_s)
                        payload = self._payload[idxs[0]] if len(idxs) == 1 \
                            else np.concatenate([self._payload[i]
                                                 for i in idxs])
                        name = f"g/b{bi:03d}"
                        handles.append((name, ex.submit(
                            name, _OP_ALLREDUCE, payload, reduce_op=Sum)))
                    for name, h in handles:
                        session.wait(h, timeout=60.0)
                        ex.take_result(name)
                    session.step_end(sid)
                    if rank == 0:
                        self.step_id = sid
                        if on_step is not None:
                            on_step()
            except BaseException as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                try:
                    barrier.abort()
                except Exception:  # noqa: BLE001
                    pass

        threads = [threading.Thread(target=work, args=(r,))
                   for r in range(self.world)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def measure(self, first_step: int, last_step: int) -> Optional[dict]:
        """Mean decomposition of rank 0's completed step windows in
        [first_step, last_step] from the flight ring."""
        from horovod_tpu.obs import attribution
        dump = self.sessions[0].flight_dump()
        if not dump:
            return None
        windows = [w for w in attribution.decompose_rank(dump)
                   if first_step <= w["step"] <= last_step]
        if not windows:
            return None
        n = len(windows)
        return {
            "steps": n,
            "step_s": sum(w["step_s"] for w in windows) / n,
            "exposed_comm_s": sum(w["exposed_comm_s"] for w in windows) / n,
            "exposed_comm_ratio": (
                sum(w["exposed_comm_s"] for w in windows) /
                max(sum(w["step_s"] for w in windows), 1e-9)),
            "overlapped_comm_s": sum(w["overlapped_comm_s"]
                                     for w in windows) / n,
        }


def run_smoke(world: int = 2, epoch_steps: int = 5, samples: int = 12,
              warmup_epochs: int = 1, scale: int = 16,
              compute_seconds: float = 0.04,
              log_path: Optional[str] = None) -> dict:
    """One bounded tuning session; returns its record (before/after
    exposed comm, converged config, search trace length)."""
    # The engine reads HOROVOD_TUNE at session creation (cpp scope); the
    # smoke owns its sessions, so it pins the knob for them (and restores
    # the caller's value on the way out — the pytest wrapper runs in-process).
    prev_tune = os.environ.get("HOROVOD_TUNE")  # hvd-lint: disable=HVL004
    os.environ["HOROVOD_TUNE"] = "1"  # hvd-lint: disable=HVL004
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.tune.space import Knob, default_space
    from horovod_tpu.tune.tuner import TuningSession

    h = _Harness(world=world, scale=scale,
                 compute_seconds=compute_seconds)
    try:
        # -- before: the untuned baseline (no buckets, engine defaults) --
        h.config = {"bucket_bytes": 0}
        h.run_epoch(epoch_steps + 1)
        before = h.measure(2, h.step_id)  # skip the cold first step

        # -- the tuning session ------------------------------------------
        space = default_space(engine_knobs=True, compression=False)
        # narrower bucket span: the scaled-down payload saturates earlier
        space = tuple(
            Knob("bucket_bytes", "log_int", 0, lo=64 * 1024,
                 hi=8 << 20, extra=(0,)) if k.name == "bucket_bytes" else k
            for k in space)
        ts = TuningSession(engine=h.sessions[0],
                           registry=MetricsRegistry(),
                           space=space, epoch_steps=epoch_steps,
                           samples=samples, warmup_epochs=warmup_epochs,
                           log_path=log_path or "")

        def on_step():
            ts.on_step()
            # the harness's "staged recompile": re-read the in-jit bucket
            # config at the step boundary (rank threads are parked at the
            # barrier while this runs on the leader thread)
            h.config = dict(ts.config)

        total_epochs = samples + warmup_epochs + 2
        for _ in range(total_epochs):
            if ts.converged:
                break
            h.run_epoch(epoch_steps, on_step=on_step)

        # -- after: one clean epoch under the converged config -----------
        h.config = dict(ts.config)
        first_after = h.step_id + 2  # skip the recompile-analog step
        h.run_epoch(epoch_steps + 1)
        after = h.measure(first_after, h.step_id)

        drop = None
        if before and after and before["exposed_comm_s"] > 0:
            drop = 1.0 - after["exposed_comm_s"] / before["exposed_comm_s"]
        return {
            "world": world,
            "grad_leaves": len(h.sizes),
            "grad_bytes": int(sum(h.sizes) * 4),
            "epoch_steps": epoch_steps,
            "sample_budget": samples,
            "samples_used": ts._search.samples,
            "search_trace_len": len(ts._search.trace),
            "converged": ts.converged,
            "converged_config": dict(ts.config),
            "best_objective_seconds": ts._search.best_objective,
            "before": before,
            "after": after,
            "exposed_comm_drop_pct": round(100.0 * drop, 2)
            if drop is not None else None,
            "method": (
                "2-rank loopback engine; ResNet-50-shaped gradient set "
                "(scaled) submitted bucket-by-bucket with interleaved "
                "compute slices; objective = mean exposed-comm seconds "
                "from the flight-ring step decomposition "
                "(obs/attribution); before = bucket_bytes=0 + engine "
                "defaults, after = the converged configuration"),
        }
    finally:
        h.close()
        if prev_tune is None:  # hvd-lint: disable=HVL004
            os.environ.pop("HOROVOD_TUNE", None)
        else:
            os.environ["HOROVOD_TUNE"] = prev_tune  # hvd-lint: disable=HVL004


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="hvd-tune-smoke",
        description="bounded CPU-backend tuning session (real engine, "
                    "real attribution)")
    parser.add_argument("--steps", type=int, default=20,
                        help="tuning sample budget + epoch sizing bound")
    parser.add_argument("--epoch-steps", type=int, default=5)
    parser.add_argument("--scale", type=int, default=16,
                        help="gradient-size divisor vs real ResNet-50")
    parser.add_argument("--json", action="store_true",
                        help="print the full record as one JSON line")
    args = parser.parse_args(argv)
    out = run_smoke(epoch_steps=args.epoch_steps,
                    samples=max(2, args.steps - args.epoch_steps),
                    scale=args.scale)
    if args.json:
        print(json.dumps(out))
    else:
        print(json.dumps(out, indent=2))
    ok = out["exposed_comm_drop_pct"] is not None and \
        out["exposed_comm_drop_pct"] > 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
