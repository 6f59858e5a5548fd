"""Load generation + latency microbenches for the serving plane.

Two instruments:

- :func:`run_load` — open-loop offered load against
  any ``submit(payload) -> result`` callable (the local frontend handler,
  an HTTP client, the router). Open-loop matters: a closed loop slows its
  own arrival rate when the server saturates and can never show the
  backpressure knee; here arrivals keep coming at the offered rate and the
  rejected/expired counts + p99 show graceful degradation (bounded queue,
  fast 429s) instead of collapse.

- :func:`small_allreduce_latency` — the small-tensor cost-cliff
  regression microbench: the p50 latency of a sub-threshold (≤ 4 KiB)
  allreduce issued alongside a bulk tensor, measured with
  ``HOROVOD_SERVING_MODE`` off (the small tensor fuses behind the bulk
  one and pays its exec time) vs on (express lane). This is the measured
  evidence that serving mode removed the cliff.
"""

from __future__ import annotations

import os
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    if not values:
        return None
    xs = sorted(values)
    idx = min(int(q * len(xs)), len(xs) - 1)
    return xs[idx]


def run_load(submit: Callable[[dict], dict], offered_qps: float,
             duration_sec: float, make_payload: Callable[[int], dict],
             max_dispatchers: int = 32) -> Dict[str, object]:
    """Offer ``offered_qps`` for ``duration_sec`` against ``submit``.

    ``submit`` must be blocking and return a result dict with a
    ``status`` key (``ok``/``rejected``/``expired``/``failed``); raising
    counts as ``failed``. A fixed dispatcher pool drains the arrival
    schedule; when the pool can't keep up (server slower than offered
    load), arrivals back up client-side and the achieved rate drops —
    which is the saturation signal, reported honestly rather than by
    slowing the offered clock."""
    n = max(1, int(offered_qps * duration_sec))
    interval = 1.0 / offered_qps
    t0 = time.monotonic()
    schedule = [t0 + i * interval for i in range(n)]
    cursor = {"i": 0}
    lock = threading.Lock()
    latencies: List[float] = []
    counts = {"ok": 0, "rejected": 0, "expired": 0, "failed": 0}

    def dispatch():
        while True:
            with lock:
                i = cursor["i"]
                if i >= n:
                    return
                cursor["i"] = i + 1
                due = schedule[i]
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            start = time.monotonic()
            try:
                result = submit(make_payload(i))
                status = result.get("status", "failed")
            except Exception:  # noqa: BLE001 — a refused dispatch is a
                status = "failed"  # data point, not a bench crash
            took = time.monotonic() - start
            with lock:
                counts[status] = counts.get(status, 0) + 1
                if status == "ok":
                    latencies.append(took)

    workers = [threading.Thread(target=dispatch, daemon=True)
               for _ in range(min(max_dispatchers, n))]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    wall = time.monotonic() - t0
    # a submit that returned a non-terminal status (e.g. a client-side
    # wait timeout handing back "running") must not vanish from the
    # accounting: requests == ok + rejected + expired + failed + unsettled
    unsettled = sum(v for k, v in counts.items()
                    if k not in ("ok", "rejected", "expired", "failed"))
    return {
        "offered_qps": round(offered_qps, 2),
        "duration_sec": round(wall, 2),
        "requests": n,
        "completed_ok": counts["ok"],
        "rejected": counts["rejected"],
        "expired": counts["expired"],
        "failed": counts["failed"],
        "unsettled": unsettled,
        "achieved_qps": round(counts["ok"] / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(percentile(latencies, 0.50) * 1e3, 2)
        if latencies else None,
        "p99_ms": round(percentile(latencies, 0.99) * 1e3, 2)
        if latencies else None,
    }


def shared_prefix_trace(seed: int = 0, requests: int = 256,
                        tenants: int = 4, prefix_len: int = 96,
                        tail_len: int = 16, max_new_tokens: int = 16,
                        vocab: int = 256,
                        tenant_mix: Optional[Sequence[float]] = None
                        ) -> List[dict]:
    """Seeded, replayable shared-prefix request trace — the first brick
    of the ROADMAP trace-driven loadgen item, shared by the smoke and the
    tests.

    Each tenant has one fixed ``prefix_len``-token system prompt; every
    request is that prefix plus a fresh ``tail_len``-token user turn.
    ``tenant_mix`` weights the tenant draw (default is zipf-ish: tenant 0
    dominates — the million-users-one-system-prompt shape where prefix
    reuse pays). Identical ``(seed, knobs)`` always reproduce the exact
    same token streams, so a regression is re-runnable bit-for-bit.
    """
    rng = np.random.RandomState(seed)
    prefixes = [rng.randint(0, vocab, prefix_len).tolist()
                for _ in range(tenants)]
    mix = np.asarray(tenant_mix if tenant_mix is not None
                     else [1.0 / (i + 1) for i in range(tenants)], float)
    mix = mix / mix.sum()
    out: List[dict] = []
    for _ in range(requests):
        t = int(rng.choice(tenants, p=mix))
        tail = rng.randint(0, vocab, tail_len).tolist()
        out.append({"tenant": f"tenant{t}",
                    "tokens": prefixes[t] + tail,
                    "max_new_tokens": int(max_new_tokens)})
    return out


# ---------------------------------------------------------------------------
# small-tensor latency microbench (the serving-mode cost-cliff regression)


def _exec_callback(lib, session, dtype_ids):
    """Data-plane callback sized from the response metadata — runs the real
    loopback combine so bulk responses cost real exec time."""

    def cb(resp):
        elems = 0
        for shape in resp.get("shapes", []):
            n = 1
            for d in shape:
                n *= d
            elems += n
        buf = np.ones(max(elems, 1), np.float32)
        return lib.hvdtpu_data_allreduce(
            session._session, buf.ctypes.data, buf.size,
            dtype_ids["float32"], 0, 1.0, 1.0)

    return cb


def small_allreduce_latency(serving_mode: bool, ranks: int = 2,
                            small_elems: int = 256,
                            big_elems: int = 1 << 22,
                            iters: int = 15) -> Dict[str, object]:
    """p50/mean latency (ms) of a small allreduce (``small_elems`` fp32 —
    1 KiB at the default, well under HOROVOD_LOW_LATENCY_THRESHOLD) whose
    negotiation cycle also carries a bulk ``big_elems`` tensor.

    Without serving mode the two fuse (same reduce params, under the
    fusion threshold) and the small tensor's completion waits on the fused
    exec; with it, the small response rides the express lane ahead of the
    bulk one. In-process loopback ranks, so this measures engine protocol
    + host data plane, no network."""
    from horovod_tpu.common.env_registry import env_raw
    from horovod_tpu.engine import bindings
    prev = env_raw("HOROVOD_SERVING_MODE")
    os.environ["HOROVOD_SERVING_MODE"] = "1" if serving_mode else "0"
    try:
        group = f"servebench-{uuid.uuid4().hex[:8]}"
        sessions = [bindings.EngineSession(
            rank=r, size=ranks, transport="loopback", group=group,
            cycle_time_ms=1.0, stall_warning_sec=60.0)
            for r in range(ranks)]
        lib = bindings.load_library()
        for s in sessions:
            s.set_execute_callback(_exec_callback(lib, s,
                                                  bindings.DTYPE_IDS))
        small_lat: List[float] = []
        barrier = threading.Barrier(ranks)

        def run(rank: int, s):
            from horovod_tpu.engine.bindings import OP_ALLREDUCE
            for i in range(iters):
                barrier.wait()
                # small submitted first so both tensors deterministically
                # land in the same negotiation cycle (the fused-mode cliff
                # needs them co-negotiated; queue order does not affect
                # fusion)
                t0 = time.perf_counter()
                hs = s.enqueue(f"small.{i}", OP_ALLREDUCE, "float32",
                               [small_elems])
                hb = s.enqueue(f"bulk.{i}", OP_ALLREDUCE, "float32",
                               [big_elems])
                s.wait(hs, timeout=60.0)
                dt = time.perf_counter() - t0
                if rank == 0:
                    small_lat.append(dt)
                s.wait(hb, timeout=60.0)

        threads = [threading.Thread(target=run, args=(r, s), daemon=True)
                   for r, s in enumerate(sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        counters = sessions[0].metrics().get("counters", {})
        for s in sessions:
            s._lib.hvdtpu_shutdown(s._session)
        for s in sessions:
            s.destroy()
        return {
            "serving_mode": serving_mode,
            "small_bytes": small_elems * 4,
            "bulk_bytes": big_elems * 4,
            "iters": iters,
            "p50_ms": round(percentile(small_lat, 0.5) * 1e3, 3),
            "mean_ms": round(float(np.mean(small_lat)) * 1e3, 3),
            "low_latency_responses":
                counters.get("low_latency_responses", 0),
            "fused_responses": counters.get("fused_responses", 0),
        }
    finally:
        if prev is None:
            os.environ.pop("HOROVOD_SERVING_MODE", None)
        else:
            os.environ["HOROVOD_SERVING_MODE"] = prev
