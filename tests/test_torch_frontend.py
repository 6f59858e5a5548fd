"""PyTorch frontend: op numerics per dtype, autograd mirrors, in-place
variants, DistributedOptimizer training loop, sync BN, elastic sampler —
the analog of the reference's test/parallel/test_torch.py patterns run
across real processes over the TCP controller."""

import os
import subprocess
import sys
import textwrap

import pytest
import torch

from conftest import free_port

# per-dtype torch op matrix pushes the file past the ~3 min tier-1 per-file budget (ISSUE 2 satellite: tier-1 runs -m 'not slow')
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_workers(tmp_path, body: str, size: int, timeout: int = 180):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        sys.path.insert(0, os.environ["HVDTPU_REPO"])
        import numpy as np
        import torch
        torch.manual_seed(1234)
        import horovod_tpu.torch as hvd
        hvd.init()
        rank, size = hvd.rank(), hvd.size()
    """) + textwrap.dedent(body) + textwrap.dedent("""
        hvd.shutdown()
        print(f"torch worker {rank} OK")
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
                   JAX_PLATFORMS="cpu")
        procs.append(subprocess.Popen([sys.executable, str(script)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=timeout)
        outs.append(out.decode())
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"torch worker {r} OK" in out
    return outs


def test_torch_ops_numerics(tmp_path):
    """Every op × dtype against locally computed expectations (reference:
    test_torch.py test_horovod_allreduce & friends)."""
    _run_workers(tmp_path, """
        # allreduce per dtype
        for dt in (torch.float32, torch.float64, torch.int32, torch.int64,
                   torch.float16, torch.bfloat16):
            x = (torch.arange(6).reshape(2, 3) + rank).to(dt)
            out = hvd.allreduce(x, op=hvd.Sum, name=f"ar.{dt}")
            exp = sum((torch.arange(6).reshape(2, 3) + r) for r in range(size))
            assert out.dtype == dt, (out.dtype, dt)
            assert torch.allclose(out.double(), exp.double(), rtol=1e-2), \
                (dt, out)

        # average + pre/postscale
        x = torch.full((4,), float(rank))
        out = hvd.allreduce(x, op=hvd.Average, prescale_factor=2.0,
                            postscale_factor=0.5)
        exp = 0.5 * 2.0 * sum(range(size)) / size
        assert torch.allclose(out, torch.full((4,), exp)), out

        # min/max/product
        x = torch.tensor([float(rank + 1), -float(rank + 1)])
        assert torch.allclose(hvd.allreduce(x, op=hvd.Min),
                              torch.tensor([1.0, -float(size)]))
        assert torch.allclose(hvd.allreduce(x, op=hvd.Max),
                              torch.tensor([float(size), -1.0]))

        # in-place
        x = torch.full((3,), float(rank))
        y = hvd.allreduce_(x, op=hvd.Sum)
        assert y is x and torch.allclose(x, torch.full((3,), float(sum(range(size)))))

        # allgather, ragged rows
        x = torch.full((rank + 1, 2), float(rank))
        out = hvd.allgather(x)
        exp = torch.cat([torch.full((r + 1, 2), float(r)) for r in range(size)])
        assert torch.allclose(out, exp), out

        # broadcast from nonzero root, in-place and out-of-place
        x = torch.full((2, 2), float(rank))
        out = hvd.broadcast(x, root_rank=1)
        assert torch.allclose(out, torch.full((2, 2), 1.0))
        hvd.broadcast_(x, root_rank=1)
        assert torch.allclose(x, torch.full((2, 2), 1.0))

        # alltoall with uneven splits
        splits = [[1, 2, 1], [2, 1, 1], [1, 1, 2]][rank]
        rows = sum(splits)
        x = (torch.arange(rows, dtype=torch.float32)[:, None]
             + 10.0 * rank) * torch.ones(1, 2)
        out = hvd.alltoall(x, splits=splits)
        all_splits = [[1, 2, 1], [2, 1, 1], [1, 1, 2]]
        chunks = []
        for src in range(size):
            srows = sum(all_splits[src])
            sx = (torch.arange(srows, dtype=torch.float32)[:, None]
                  + 10.0 * src) * torch.ones(1, 2)
            start = sum(all_splits[src][:rank])
            chunks.append(sx[start:start + all_splits[src][rank]])
        assert torch.allclose(out, torch.cat(chunks)), out

        # grouped allreduce
        outs = hvd.grouped_allreduce(
            [torch.full((2,), float(rank)), torch.full((3,), 2.0 * rank)],
            op=hvd.Average)
        assert torch.allclose(outs[0], torch.full((2,), sum(range(size)) / size))
        assert torch.allclose(outs[1], torch.full((3,), 2.0 * sum(range(size)) / size))

        # compression on the wire
        x = torch.full((8,), float(rank))
        out = hvd.allreduce(x, op=hvd.Sum, compression=hvd.Compression.fp16)
        assert out.dtype == torch.float32
        assert torch.allclose(out, torch.full((8,), float(sum(range(size)))))

        # object transport + parameter broadcast
        obj = hvd.broadcast_object({"lr": 0.1, "rank_was": 0} if rank == 0
                                   else None, root_rank=0)
        assert obj == {"lr": 0.1, "rank_was": 0}
        gathered = hvd.allgather_object(("r", rank))
        assert gathered == [("r", r) for r in range(size)]

        model = torch.nn.Linear(4, 2)
        with torch.no_grad():
            for p in model.parameters():
                p.fill_(float(rank + 1))
        hvd.broadcast_parameters(model.state_dict(), root_rank=2)
        for p in model.parameters():
            assert torch.allclose(p, torch.full_like(p, 3.0)), p

        # join with uneven work: ranks 0,1 do one more allreduce
        if rank != 2:
            out = hvd.allreduce(torch.ones(2), op=hvd.Sum, name="tail")
            assert torch.allclose(out, torch.full((2,), 2.0)), out
        last = hvd.join()
        assert 0 <= last < size
    """, size=3)


def test_torch_autograd_mirrors(tmp_path):
    """Gradients of the sync ops are the mirror collectives (reference:
    test_torch.py test_horovod_allreduce_grad / allgather_grad /
    broadcast_grad)."""
    _run_workers(tmp_path, """
        # allreduce grad: d(sum over ranks)/dx = allreduce(upstream, Sum)
        x = torch.full((3,), float(rank), requires_grad=True)
        y = hvd.allreduce(x, op=hvd.Sum)
        y.backward(torch.ones(3))
        assert torch.allclose(x.grad, torch.full((3,), float(size))), x.grad

        # allgather grad: own slice of the summed upstream
        x = torch.full((rank + 1, 2), 1.0, requires_grad=True)
        out = hvd.allgather(x)
        g = torch.arange(out.numel(), dtype=torch.float32).reshape(out.shape)
        out.backward(g)
        offset = sum(r + 1 for r in range(rank))
        exp = size * g[offset:offset + rank + 1]
        assert torch.allclose(x.grad, exp), (x.grad, exp)

        # broadcast grad: reduced to root, zero elsewhere
        x = torch.full((2,), float(rank + 1), requires_grad=True)
        out = hvd.broadcast(x, root_rank=1)
        out.backward(torch.ones(2))
        if rank == 1:
            assert torch.allclose(x.grad, torch.full((2,), float(size)))
        else:
            assert torch.allclose(x.grad, torch.zeros(2))
    """, size=2)


def test_torch_distributed_optimizer_training(tmp_path):
    """The reference's essence: a torch training loop wrapped with
    DistributedOptimizer trains in lockstep — params stay bit-identical
    across ranks and match a single-process run on the combined batch."""
    _run_workers(tmp_path, """
        torch.manual_seed(7)
        model = torch.nn.Sequential(
            torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters())
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        hvd.broadcast_optimizer_state(opt, root_rank=0)

        # fixed synthetic dataset, sharded by rank
        g = torch.Generator().manual_seed(99)
        X = torch.randn(32, 8, generator=g)
        W = torch.randn(8, 1, generator=g)
        Y = X @ W + 0.1 * torch.randn(32, 1, generator=g)
        Xr, Yr = X[rank::size], Y[rank::size]

        losses = []
        for step in range(20):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(Xr), Yr)
            loss.backward()
            opt.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses

        # params identical across ranks after distributed training
        blob = b"".join(p.detach().numpy().tobytes()
                        for p in model.parameters())
        import hashlib
        digests = hvd.allgather_object(hashlib.sha256(blob).hexdigest())
        assert len(set(digests)) == 1, digests
    """, size=2)


def test_torch_sharded_distributed_optimizer(tmp_path):
    """ZeRO-1 weight-update sharding (sharded=True): ranks own disjoint
    ~1/N param partitions, optimizer state materializes only for owned
    params, and post-step broadcasts keep ranks bit-identical."""
    _run_workers(tmp_path, """
        torch.manual_seed(7)
        model = torch.nn.Sequential(
            torch.nn.Linear(8, 16), torch.nn.ReLU(), torch.nn.Linear(16, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.05, momentum=0.9)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters(), sharded=True)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)

        g = torch.Generator().manual_seed(99)
        X = torch.randn(32, 8, generator=g)
        W = torch.randn(8, 1, generator=g)
        Y = X @ W + 0.1 * torch.randn(32, 1, generator=g)
        Xr, Yr = X[rank::size], Y[rank::size]

        losses = []
        for step in range(20):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(Xr), Yr)
            loss.backward()
            opt.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses

        # every param has exactly one owner, owners partition the set
        owners = opt._owner
        n_params = sum(1 for _ in model.parameters())
        assert len(owners) == n_params
        counts = hvd.allgather_object(
            sum(1 for o in owners.values() if o == rank))
        assert sum(counts) == n_params, counts
        # momentum state exists ONLY for owned params (the 1/N memory win)
        stateful = sum(1 for p in owners if len(opt.state[p]) > 0)
        assert stateful == counts[rank], (stateful, counts)

        # params identical across ranks after sharded training
        blob = b"".join(p.detach().numpy().tobytes()
                        for p in model.parameters())
        import hashlib
        digests = hvd.allgather_object(hashlib.sha256(blob).hexdigest())
        assert len(set(digests)) == 1, digests
    """, size=2)


def test_torch_backward_passes_per_step_and_fp16(tmp_path):
    _run_workers(tmp_path, """
        torch.manual_seed(3)
        model = torch.nn.Linear(4, 1)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters(),
            backward_passes_per_step=2,
            compression=hvd.Compression.fp16)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)

        X = torch.randn(8, 4, generator=torch.Generator().manual_seed(5))
        Y = X.sum(dim=1, keepdim=True)
        for step in range(4):
            # two local accumulation passes per optimizer step
            loss1 = torch.nn.functional.mse_loss(model(X[rank::size][:2]),
                                                 Y[rank::size][:2])
            loss1.backward()
            loss2 = torch.nn.functional.mse_loss(model(X[rank::size][2:]),
                                                 Y[rank::size][2:])
            loss2.backward()
            opt.step()
            opt.zero_grad()

        import hashlib
        blob = b"".join(p.detach().numpy().tobytes()
                        for p in model.parameters())
        digests = hvd.allgather_object(hashlib.sha256(blob).hexdigest())
        assert len(set(digests)) == 1, digests
    """, size=2)


def test_torch_adasum_optimizer(tmp_path):
    """Adasum path: LR applied before reduction, deltas combined
    scale-invariantly (reference: optimizer.py:270-440)."""
    _run_workers(tmp_path, """
        torch.manual_seed(11)
        model = torch.nn.Linear(4, 1, bias=False)
        opt = torch.optim.SGD(model.parameters(), lr=0.05)
        opt = hvd.DistributedOptimizer(
            opt, named_parameters=model.named_parameters(), op=hvd.Adasum)
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        X = torch.randn(8, 4, generator=torch.Generator().manual_seed(5))
        Y = X @ torch.ones(4, 1)
        first = None
        for step in range(10):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(X[rank::size]),
                                                Y[rank::size])
            if first is None:
                first = float(loss)
            loss.backward()
            opt.step()
        assert float(loss) < first, (first, float(loss))
        import hashlib
        blob = model.weight.detach().numpy().tobytes()
        digests = hvd.allgather_object(hashlib.sha256(blob).hexdigest())
        assert len(set(digests)) == 1, digests
    """, size=2)


def test_torch_sync_batch_norm(tmp_path):
    """SyncBatchNorm over 2 ranks == plain BatchNorm over the concatenated
    batch (reference: test_torch.py test_sync_batch_norm)."""
    _run_workers(tmp_path, """
        g = torch.Generator().manual_seed(21)
        full = torch.randn(8, 3, 4, generator=g)
        local = full[rank * 4:(rank + 1) * 4].clone().requires_grad_(True)

        sbn = hvd.SyncBatchNorm(3, momentum=0.1)
        out = sbn(local)
        # reference computation: plain BN1d over the full batch
        bn = torch.nn.BatchNorm1d(3, momentum=0.1)
        exp = bn(full)
        assert torch.allclose(out, exp[rank * 4:(rank + 1) * 4],
                              rtol=1e-4, atol=1e-5), (out, exp)
        assert torch.allclose(sbn.running_mean, bn.running_mean, rtol=1e-5)
        assert torch.allclose(sbn.running_var, bn.running_var, rtol=1e-5)

        # grads flow through the synchronized stats
        out.sum().backward()
        assert local.grad is not None and torch.isfinite(local.grad).all()
    """, size=2)


def test_elastic_sampler_exactly_once():
    """Mid-epoch resize: union of processed + remaining re-partition covers
    every sample exactly once (reference: torch/elastic/sampler.py)."""
    import horovod_tpu.torch as hvd
    from horovod_tpu.torch.elastic import ElasticSampler

    os.environ.pop("HOROVOD_RANK", None)
    os.environ.pop("HOROVOD_SIZE", None)
    hvd.init(start_engine=False)
    try:
        dataset = list(range(20))
        # world of 2: simulate both ranks in one process
        import horovod_tpu.common.basics as basics
        ctx = basics._context()
        ctx.size = 2
        samplers = []
        for r in range(2):
            ctx.rank = r
            s = ElasticSampler(dataset, shuffle=True, seed=42)
            samplers.append(s)
        processed = set()
        # each rank processes its first 2 batches of 2 before the resize
        for r, s in enumerate(samplers):
            ctx.rank = r
            for b in range(2):
                batch = s.indices[b * 2:(b + 1) * 2]
                s.record_batch(b, 2)
                assert not (processed & set(batch)), "sample replayed"
                processed |= set(batch)
        # resize 2 -> 3: merge processed sets (the sync() union), re-partition
        merged = set()
        for s in samplers:
            merged |= s.processed_indices
        assert merged == processed
        ctx.size = 3
        new_samplers = []
        for r in range(3):
            ctx.rank = r
            s = ElasticSampler(dataset, shuffle=True, seed=42)
            s.processed_indices = set(merged)
            s.reset()
            new_samplers.append(s)
        seen = []
        for s in new_samplers:
            seen.extend(s.indices)
        # padding may duplicate a few; the *set* must be exactly the remainder
        assert set(seen) == set(dataset) - processed, (seen, processed)
        for s in new_samplers:
            assert len(s) == len(new_samplers[0])  # lockstep batch counts
        # epoch rollover clears tracking
        s = new_samplers[0]
        s.set_epoch(1)
        assert s.processed_indices == set()
        assert len(set(s.indices)) == len(s.indices)
    finally:
        hvd.shutdown()


def test_torch_single_process_fallbacks():
    """size-1 (no engine): ops are local identities, optimizer trains."""
    import horovod_tpu.torch as hvd

    os.environ.pop("HOROVOD_RANK", None)
    os.environ.pop("HOROVOD_SIZE", None)
    hvd.init(start_engine=False)
    try:
        x = torch.tensor([1.0, 2.0])
        assert torch.allclose(hvd.allreduce(x, op=hvd.Average), x)
        assert torch.allclose(hvd.allgather(x), x)
        assert torch.allclose(hvd.broadcast(x, 0), x)
        h = hvd.allreduce_async(x, op=hvd.Sum)
        assert hvd.poll(h)
        assert torch.allclose(hvd.synchronize(h), x)
        assert hvd.join() == -1

        model = torch.nn.Linear(2, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        loss = model(torch.randn(4, 2)).sum()
        loss.backward()
        opt.step()
    finally:
        hvd.shutdown()
