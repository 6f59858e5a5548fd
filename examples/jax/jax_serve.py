"""End-to-end serving-plane walkthrough in one process.

Boots the full stack from docs/DESIGN.md "Serving plane" — two serve
workers (continuous batcher -> serving loop -> HTTP frontend) running a
tiny tensor-parallel LM whose activation reductions ride the EQuARX int8
quantized allreduce, behind a routed ingress frontend — then exercises
the request lifecycle over real HTTP:

1. normal generation through the ingress (least-loaded placement);
2. backpressure: a worker with a tiny admission queue answers 429, not a
   timeout, once the queue is full;
3. drain-on-departure: one worker drains (healthz flips to 503, accepted
   work finishes) and the router re-routes traffic to the survivor — no
   accepted request is lost.

Run:  python examples/jax/jax_serve.py
(CPU-friendly: forces an 8-device virtual host mesh when no accelerator
is attached.)
"""

import json
import os
import threading
import time
from urllib import request as urlrequest

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

from horovod_tpu.serve import (ContinuousBatcher, RequestRouter,  # noqa: E402
                               ServeFrontend, ServingLoop, make_tp_lm_step)


def http_json(port, path, payload=None, timeout=30.0):
    """(status_code, decoded_json) against a local frontend."""
    url = f"http://127.0.0.1:{port}{path}"
    req = urlrequest.Request(
        url, data=json.dumps(payload).encode() if payload is not None
        else None,
        headers={"Content-Type": "application/json"} if payload is not None
        else {})
    try:
        with urlrequest.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urlrequest.HTTPError as e:  # type: ignore[attr-defined]
        return e.code, json.loads(e.read())


def main():
    # One TP step function shared by both workers (same weights — seed 0 —
    # so either placement returns the same tokens).
    step_fn, info = make_tp_lm_step(compression="int8", vocab=512,
                                    hidden=64, mlp_dim=256, layers=2)
    print(f"tensor-parallel LM: tp_world={info['tp_world']}, "
          f"activation wire int8 savings "
          f"{info['wire']['int8_savings_x']}x vs fp32", flush=True)

    workers = []
    for i in range(2):
        batcher = ContinuousBatcher(max_batch=4, queue_depth=4,
                                    default_deadline_ms=5000.0, max_len=256)
        loop = ServingLoop(step_fn, batcher).start()
        fe = ServeFrontend(batcher=batcher, port=0).start()
        workers.append((batcher, loop, fe))

    router = RequestRouter(retry_limit=2)
    router.update_workers(
        [{"id": f"w{i}", "addr": "127.0.0.1", "port": fe.port}
         for i, (_, _, fe) in enumerate(workers)], generation=0)
    ingress = ServeFrontend(router=router, port=0).start()
    print(f"ingress on :{ingress.port}, workers on "
          f"{[fe.port for _, _, fe in workers]}", flush=True)

    try:
        # 1. Generate through the ingress.
        code, resp = http_json(ingress.port, "/v1/generate",
                               {"prompt": "the quick brown fox",
                                "max_new_tokens": 6})
        assert code == 200 and resp["status"] == "ok", (code, resp)
        print(f"generate -> {resp['tokens']} "
              f"({resp['latency_ms']:.1f} ms)", flush=True)

        # 2. Backpressure: flood one worker with concurrent requests.
        # 4 slots + a 4-deep queue can hold 8; the rest get a 429 NOW
        # (bounded queue), never an open-ended timeout.
        w_port = workers[0][2].port
        codes = []

        def flood(i):
            code, _ = http_json(w_port, "/v1/generate",
                                {"tokens": [i % 256] * 8,
                                 "max_new_tokens": 32,
                                 "deadline_ms": 10000.0}, timeout=30.0)
            codes.append(code)

        threads = [threading.Thread(target=flood, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rejected = sum(1 for c in codes if c == 429)
        completed = sum(1 for c in codes if c == 200)
        assert rejected > 0, "bounded queue never pushed back"
        assert completed > 0, "backpressure must shed load, not collapse"
        print(f"backpressure: {completed} completed, {rejected} rejected "
              f"with 429 (queue bounded at 4)", flush=True)

        # 3. Drain: worker 0 leaves the rotation. Its accepted work
        # finishes; new traffic lands on worker 1.
        router.update_workers([{"id": "w1", "addr": "127.0.0.1",
                                "port": workers[1][2].port}], generation=1)
        workers[0][2].set_draining(True)
        workers[0][1].drain(timeout=30.0)
        code, _ = http_json(workers[0][2].port, "/healthz")
        assert code == 503, "draining worker must fail its health check"
        code, resp = http_json(ingress.port, "/v1/generate",
                               {"prompt": "after the resize",
                                "max_new_tokens": 4})
        assert code == 200 and resp["status"] == "ok", (code, resp)
        print("drain: worker 0 drained (healthz 503), traffic re-routed "
              "to worker 1", flush=True)

        # Health summary from the shared stats endpoint (both workers live
        # in this process, so /stats reflects the combined registry).
        time.sleep(0.1)
        _, stats = http_json(workers[1][2].port, "/stats")
        print(json.dumps({"process_stats": stats}), flush=True)
        print("done: serving plane OK (generate + backpressure + drain)",
              flush=True)
    finally:
        ingress.stop()
        for _, loop, fe in workers:
            loop.drain(timeout=10.0)
            loop.stop()
            fe.stop()


if __name__ == "__main__":
    main()
