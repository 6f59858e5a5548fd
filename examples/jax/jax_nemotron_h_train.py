"""Data-parallel training of a hybrid state-space / sparse-expert decoder
(Nemotron-H) through the stateful step.

The model's routers balance their experts with a correction bias that a
rule trains, not a gradient, so the bias is model *state*: the language-model
counterpart of ResNet's BatchNorm statistics on
``dp.make_stateful_train_step``, whose state sync averages each step's expert
load over the replicas.

Run: ``python examples/jax/jax_nemotron_h_train.py`` (one process over the
devices JAX finds; ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
gives the CPU four).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.metrics.registry import get_registry
from horovod_tpu.models import NemotronHTiny, nemotron_h_loss
from horovod_tpu.parallel import dp, ep


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-per-replica", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=64)
    args = p.parse_args()

    hvd.init()
    mesh = hvd.mesh()
    replicas = mesh.devices.size

    # Mamba-2, experts, attention, experts; each replica holds experts 0-1
    # of 8 as one chip of an expert-parallel deployment does, and walks
    # its share of the sorted (token, slot) pairs in static tiles
    model = NemotronHTiny(experts_held=(0, 2))
    corpus = np.random.RandomState(0).randint(
        0, model.vocab, (args.batch_per_replica * replicas, args.seq_len))
    tokens = jnp.asarray(corpus, jnp.int32)
    variables = model.init(jax.random.key(0), tokens[:1])
    optimizer = optax.adamw(3e-3)

    def loss_fn(params, router_state, batch, rng):
        return nemotron_h_loss(model, params, router_state, batch["tokens"],
                               batch["labels"])

    step = dp.make_stateful_train_step(loss_fn, optimizer, mesh,
                                       donate=False)
    params = dp.replicate(variables["params"], mesh)
    opt_state = dp.replicate(optimizer.init(variables["params"]), mesh)
    router_state = dp.replicate(variables["router_state"], mesh)
    batch = dp.shard_batch(
        {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}, mesh)

    first = last = None
    for i in range(args.steps):
        out = step(params, opt_state, router_state, batch, jax.random.key(i))
        params, opt_state, router_state = \
            out.params, out.opt_state, out.model_state
        last = float(out.loss)
        first = last if first is None else first
    biases = [np.asarray(x) for path, x in
              jax.tree_util.tree_flatten_with_path(router_state)[0]
              if "bias" in jax.tree_util.keystr(path)]
    load = np.asarray(out.aux["expert_tokens"])
    largest = max(np.abs(b).max() for b in biases)
    # the state carries the mean load over the replicas: of the tiles a
    # share's walk was built with, the ones a replica worked in
    tiles = [ep.share_tiles(layer, model.experts_held,
                            model.experts_per_token,
                            args.batch_per_replica * args.seq_len,
                            record=True,
                            widths=(model.hidden, model.expert_dim))
             for layer in load]
    rows = {kind: get_registry().counter(
        "hvd_moe_share_rows_total", kind=kind).value
        for kind in ("held", "computed", "fetched")}
    if hvd.rank() == 0:
        print(f"replicas {replicas}; loss {first:.4f} -> {last:.4f}; "
              f"largest correction bias {largest:.4f}; "
              f"expert load of the last step, first expert layer: "
              f"{load[0].astype(int).tolist()}; live tiles of those built, "
              f"by expert layer: {tiles}; rows of the held experts' pairs "
              f"{rows['held']:.0f}, rows the grouped matmuls computed for "
              f"them (widths that are no whole 128s: every slot of the live "
              f"tiles) {rows['computed']:.0f}, "
              f"rows the way back to the tokens fetched to place them, at "
              f"most {rows['fetched']:.0f}")
    assert last < first, (first, last)
    if hvd.rank() == 0:
        print(f"done: final loss {last:.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
