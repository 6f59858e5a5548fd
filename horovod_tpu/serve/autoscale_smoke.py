"""Closed-loop autoscale smoke: load trace in, fleet-size trace out.

``make autoscale-smoke`` and ``tests/test_autoscale.py`` run this:
an in-process serving fleet (one ContinuousBatcher + ServingLoop + token
bucket/priority admission per worker, fronted by the real RequestRouter)
driven by the REAL :class:`~horovod_tpu.runner.elastic.autoscaler.Autoscaler`
— the same policy object, KV decision records (a live in-memory KVServer,
epoch-claimed writes) and decide→drain→resize→ack machine the elastic
driver runs. Only the actuation surface differs: ``scale_up`` spawns an
in-process worker after a short simulated provisioning delay, and
``start_drain`` marks the victim draining in the router table *immediately*
(the PR-15 announce satellite), lets it finish everything accepted, then
removes it.

Two canned traces:

- ``flash`` — steady base load, a flash crowd several times one worker's
  capacity, then recession: the loop must scale up under the crowd, hold
  p99 inside the SLO bound, and drain back down afterwards. With
  ``chaos_kill`` a worker is SIGKILL-equivalently dropped *while the
  scale-up resize is in flight*; the router re-routes its in-flight
  requests (no-silent-loss) and the fleet still converges.
- ``diurnal`` — a rise-and-fall staircase (the day curve compressed to
  seconds): the fleet should follow it up and back down without flapping.

Acceptance, computed over the run and printed as JSON:
**accepted-request loss == 0** (no failed requests, router lost counter
pinned at zero — 429s/sheds are backpressure, not loss), **p99 within the
SLO bound** in every completed-load window, **a scale-up AND a
drain-based scale-down** in the decision log, and **no flapping** (no
opposite-direction decisions closer than one hysteresis window).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Dict, List, Optional

from horovod_tpu.metrics.registry import MetricsRegistry
from horovod_tpu.runner.elastic.autoscaler import (Autoscaler,
                                                   AutoscalePolicy,
                                                   WorkerSLO,
                                                   worker_slo_from_snapshot)
from horovod_tpu.serve.admission import AdmissionController
from horovod_tpu.serve.batcher import AdmissionRejected, ContinuousBatcher
from horovod_tpu.serve.executor import ServingLoop, make_toy_step
from horovod_tpu.serve.loadgen import run_load
from horovod_tpu.serve.router import NoWorkersError, RequestRouter


class FleetWorker:
    """One in-process serving worker: batcher + decode loop + admission,
    with a dict-in/dict-out ``handle`` standing in for the HTTP frontend
    (same verdicts, no sockets)."""

    def __init__(self, wid: str, service_ms: float, max_batch: int,
                 queue_depth: int, deadline_ms: float,
                 max_new_tokens: int):
        self.id = wid
        self.registry = MetricsRegistry()
        self.batcher = ContinuousBatcher(
            max_batch=max_batch, queue_depth=queue_depth,
            default_deadline_ms=deadline_ms, max_len=64,
            max_new_tokens_cap=max_new_tokens, registry=self.registry)
        base_step = make_toy_step()
        delay = service_ms / 1e3

        def step(tokens, lengths):
            time.sleep(delay)  # the simulated forward-pass cost
            return base_step(tokens, lengths)

        self.loop = ServingLoop(step, self.batcher,
                                registry=self.registry).start()
        self.admission = AdmissionController(registry=self.registry)
        self.killed = threading.Event()
        self._deadline_s = deadline_ms / 1e3

    def handle(self, payload: dict) -> dict:
        """The frontend contract; raises (transport-style) when killed so
        the router's no-silent-loss retry re-routes."""
        if self.killed.is_set():
            raise ConnectionError(f"worker {self.id} is dead")
        verdict = self.admission.admit(
            payload, self.batcher.pending() /
            max(self.batcher.queue_depth, 1))
        if not verdict.ok:
            return {"status": "rejected", "error": verdict.reason,
                    "retry_after_seconds": verdict.retry_after_seconds}
        try:
            req = self.batcher.submit(
                payload.get("tokens", [1, 2, 3]),
                max_new_tokens=payload.get("max_new_tokens"),
                request_id=payload.get("id"))
        except AdmissionRejected as e:
            return {"status": "rejected", "error": str(e)}
        deadline = time.monotonic() + self._deadline_s + 5.0
        while not req.wait(0.05):
            if self.killed.is_set():
                raise ConnectionError(
                    f"worker {self.id} died with request in flight")
            if time.monotonic() > deadline:
                self.batcher.complete(req, "failed", "worker wedged")
                break
        if self.killed.is_set():
            raise ConnectionError(f"worker {self.id} died at completion")
        return req.result()

    def slo(self) -> WorkerSLO:
        slo = worker_slo_from_snapshot(self.id, self.registry.snapshot())
        return slo if slo is not None else WorkerSLO(self.id, 0.0, None,
                                                    None, 0.0)

    def kill(self):
        """The chaos leg: everything in flight raises back to the router
        (which re-routes it), nothing is silently dropped."""
        self.killed.set()
        self.loop.stop()

    def stop(self):
        self.loop.stop()


class SimFleet:
    """The Autoscaler's ``fleet_ops`` over in-process workers + a real
    RequestRouter (immediate-drain announce included)."""

    def __init__(self, service_ms: float = 40.0, max_batch: int = 2,
                 queue_depth: int = 16, deadline_ms: float = 8000.0,
                 max_new_tokens: int = 4, spawn_delay: float = 0.3):
        self.registry = MetricsRegistry()
        self.router = RequestRouter(retry_limit=3, registry=self.registry)
        self.workers: Dict[str, FleetWorker] = {}
        self.draining: set = set()
        self._cfg = dict(service_ms=service_ms, max_batch=max_batch,
                         queue_depth=queue_depth, deadline_ms=deadline_ms,
                         max_new_tokens=max_new_tokens)
        self.spawn_delay = spawn_delay
        self.generation = 0
        self._n = 0
        self._lock = threading.Lock()
        self._spawn_threads: List[threading.Thread] = []

    # -- router table ---------------------------------------------------------

    def _publish(self):
        with self._lock:
            self.generation += 1
            entries = []
            for wid, w in self.workers.items():
                if w.killed.is_set():
                    continue
                e = {"id": wid, "addr": "local", "port": 0,
                     "generation": self.generation}
                if wid in self.draining:
                    e["draining"] = True
                entries.append(e)
            gen = self.generation
        self.router.update_workers(entries, gen)

    def _add_worker(self):
        with self._lock:
            wid = f"w{self._n}"
            self._n += 1
            self.workers[wid] = FleetWorker(wid, **self._cfg)
        self._publish()

    # -- fleet_ops (the Autoscaler drives these) ------------------------------

    def scale_up(self):
        def spawn():
            time.sleep(self.spawn_delay)  # simulated provisioning
            self._add_worker()

        t = threading.Thread(target=spawn, daemon=True)
        t.start()
        self._spawn_threads.append(t)

    def start_drain(self, victim: str):
        with self._lock:
            if victim not in self.workers or victim in self.draining:
                return
            self.draining.add(victim)
        self._publish()  # the announce: no new placements from here on

        def drain():
            w = self.workers.get(victim)
            if w is not None:
                w.loop.drain(timeout=30.0)
                w.stop()
            with self._lock:
                self.workers.pop(victim, None)
                self.draining.discard(victim)
            self._publish()

        threading.Thread(target=drain, daemon=True).start()

    # -- chaos / observation --------------------------------------------------

    def kill(self, wid: str) -> bool:
        with self._lock:
            w = self.workers.get(wid)
            if w is None or wid in self.draining:
                return False
        w.kill()
        self._publish()
        return True

    def accepting_ids(self) -> List[str]:
        with self._lock:
            return [wid for wid, w in self.workers.items()
                    if wid not in self.draining and not w.killed.is_set()]

    def fleet_slos(self) -> List[WorkerSLO]:
        with self._lock:
            live = [(wid, w) for wid, w in self.workers.items()
                    if wid not in self.draining and not w.killed.is_set()]
        return [w.slo() for _wid, w in live]

    def draining_keys(self) -> List[str]:
        with self._lock:
            return list(self.draining)

    def submit(self, payload: dict) -> dict:
        rid = str(payload.get("id") or id(payload))
        payload = dict(payload, id=rid)
        try:
            return self.router.submit(
                rid, payload,
                lambda w, p: self.workers[w.id].handle(p))
        except NoWorkersError:
            return {"status": "failed", "error": "no accepting worker"}

    def lost_requests(self) -> float:
        from horovod_tpu.metrics import snapshot_value
        return snapshot_value(self.registry.snapshot(),
                              "hvd_serve_lost_total") or 0.0

    def close(self):
        for t in self._spawn_threads:
            t.join(timeout=5.0)
        with self._lock:
            workers = list(self.workers.values())
        for w in workers:
            w.stop()


TRACES = {
    # (offered_qps_multiplier_of_capacity, seconds_multiplier) phases;
    # capacity here is ONE worker's measured ceiling
    "flash": [(0.4, 1.0), (2.4, 2.0), (0.15, 2.5)],
    "diurnal": [(0.3, 1.0), (0.8, 1.0), (1.6, 1.5), (0.8, 1.0),
                (0.08, 2.5)],
}


def run_smoke(trace: str = "flash", chaos_kill: bool = False,
              seconds_scale: float = 3.0, service_ms: float = 40.0,
              max_batch: int = 2, max_new_tokens: int = 4,
              p99_bound_ms: float = 2500.0, queue_bound: int = 4,
              max_workers: int = 4, interval: float = 0.25,
              kv_dir: Optional[str] = None) -> dict:
    """One closed loop: trace → fleet resize decisions → acceptance
    flags. ``seconds_scale`` stretches every phase (CI uses small values;
    the Makefile default gives the policy room to breathe)."""
    from horovod_tpu.runner.http_kv import KVServer

    fleet = SimFleet(service_ms=service_ms, max_batch=max_batch,
                     max_new_tokens=max_new_tokens)
    fleet._add_worker()
    # one worker's theoretical ceiling: max_batch concurrent requests,
    # each costing max_new_tokens decode steps of service_ms
    capacity = max_batch / (max_new_tokens * service_ms / 1e3)
    policy = AutoscalePolicy(
        min_workers=1, max_workers=max_workers,
        queue_bound=float(queue_bound), p99_bound_ms=p99_bound_ms,
        idle_occupancy=0.25, up_windows=2, down_windows=4,
        up_cooldown=2 * interval, down_cooldown=8 * interval)
    kv = KVServer(port=0, kv_dir=kv_dir).start()
    scaler = Autoscaler(fleet, kv=kv, epoch=kv.epoch, policy=policy,
                        registry=fleet.registry)

    stop = threading.Event()
    fleet_trace: List[dict] = []
    t0 = time.monotonic()

    def tick_loop():
        while not stop.is_set():
            try:
                scaler.tick(fleet.fleet_slos(), fleet.draining_keys())
            except Exception as e:  # noqa: BLE001 — record, keep looping
                fleet_trace.append({"t": round(time.monotonic() - t0, 2),
                                    "error": repr(e)})
            fleet_trace.append({
                "t": round(time.monotonic() - t0, 2),
                "fleet": len(fleet.accepting_ids()),
                "draining": len(fleet.draining_keys()),
            })
            stop.wait(interval)

    ticker = threading.Thread(target=tick_loop, daemon=True)
    ticker.start()

    chaos = {"requested": chaos_kill, "killed": None}
    if chaos_kill:
        def chaos_loop():
            # SIGKILL-equivalent drop of the ORIGINAL worker the moment
            # the scale-up's spawn lands (the resize window): its
            # in-flight requests re-route to the joiner, the continued
            # pressure re-grows the fleet
            saw_up = False
            while not stop.is_set():
                pending = scaler.pending
                if pending and pending.get("action") == "up":
                    saw_up = True
                if saw_up and len(fleet.accepting_ids()) >= 2:
                    victim = sorted(fleet.accepting_ids())[0]
                    fleet.kill(victim)
                    chaos["killed"] = victim
                    chaos["at_state"] = (pending or {}).get("state",
                                                            "acked")
                    chaos["t"] = round(time.monotonic() - t0, 2)
                    return
                time.sleep(0.02)

        threading.Thread(target=chaos_loop, daemon=True).start()

    def make_payload(i):
        return {"tokens": [(i * 7 + j) % 61 for j in range(8)],
                "max_new_tokens": max_new_tokens,
                "priority": ("batch", "standard", "premium")[i % 3]}

    windows = []
    try:
        for mult, dur in TRACES[trace]:
            qps = max(1.0, round(capacity * mult, 1))
            win = run_load(fleet.submit, qps, dur * seconds_scale,
                           make_payload)
            win["fleet_at_end"] = len(fleet.accepting_ids())
            windows.append(win)
    finally:
        # let in-flight drains/spawns settle before judging the run
        deadline = time.monotonic() + 10.0
        while (fleet.draining_keys() or
               (scaler.pending is not None)) and \
                time.monotonic() < deadline:
            time.sleep(0.1)
        stop.set()
        ticker.join(timeout=5.0)
        fleet.close()
        kv.stop()

    decisions = [{k: d.get(k) for k in ("seq", "action", "victim",
                                        "reason", "state", "outcome",
                                        "ts")}
                 for d in scaler.decisions]
    # flapping check: opposite-direction decisions closer together than
    # one hysteresis window are exactly what the hysteresis must prevent
    hysteresis_s = policy.down_windows * interval
    flap = False
    for a, b in zip(scaler.decisions, scaler.decisions[1:]):
        if a["action"] != b["action"] and \
                b["ts"] - a["ts"] < hysteresis_s:
            flap = True
    from horovod_tpu.metrics import snapshot_value
    rerouted = snapshot_value(fleet.registry.snapshot(),
                              "hvd_serve_rerouted_total") or 0.0
    loss = sum(w["failed"] for w in windows) + fleet.lost_requests()
    p99s = [w["p99_ms"] for w in windows if w["p99_ms"] is not None]
    fleet_sizes = [p["fleet"] for p in fleet_trace if "fleet" in p]
    return {
        "trace": trace,
        "single_worker_capacity_qps": round(capacity, 1),
        "p99_bound_ms": p99_bound_ms,
        "windows": windows,
        "decisions": decisions,
        "fleet_trace": fleet_trace,
        "fleet_max": max(fleet_sizes) if fleet_sizes else 0,
        "fleet_final": fleet_sizes[-1] if fleet_sizes else 0,
        "chaos": chaos,
        "scale_up_seen": any(d["action"] == "up" for d in decisions),
        "scale_down_seen": any(d["action"] == "down" for d in decisions),
        "max_p99_ms": max(p99s) if p99s else None,
        "p99_within_bound": bool(p99s) and max(p99s) <= p99_bound_ms,
        "accepted_loss": loss,
        "no_flap": not flap,
        "rerouted": rerouted,
    }


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="hvd-autoscale-smoke",
        description="bounded closed-loop autoscale demo: loadgen flash "
                    "crowd -> scale-up -> recede -> drain-based "
                    "scale-down, with an optional chaos kill mid-resize")
    parser.add_argument("--trace", choices=sorted(TRACES), default="flash")
    parser.add_argument("--chaos-kill", action="store_true")
    parser.add_argument("--seconds-scale", type=float, default=3.0)
    args = parser.parse_args(argv)
    result = run_smoke(trace=args.trace, chaos_kill=args.chaos_kill,
                       seconds_scale=args.seconds_scale)
    print(json.dumps(result, indent=2))
    ok = (result["accepted_loss"] == 0 and result["no_flap"] and
          result["scale_up_seen"] and result["scale_down_seen"] and
          result["p99_within_bound"])
    if args.chaos_kill:
        # the chaos leg must actually have run: a kill landed and its
        # in-flight requests were re-routed (not merely not-lost)
        ok = ok and result["chaos"]["killed"] is not None and \
            result["rerouted"] > 0
    if not ok:
        print("autoscale smoke FAILED acceptance", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
