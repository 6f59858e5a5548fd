#!/usr/bin/env python3
"""Compile a cell's step at its real size for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python benchmark/compile_check.py --workload gpt2s-t512

The third rehearsal of the on-chip-measurement guide: the TPU compiler that
is installed here builds the step for ``v5e:2x2`` devices that are described
and not attached. It prints one JSON line: bytes of arguments, outputs and
temporaries on each chip (``memory_analysis()``), the ``tpu_custom_call``s
(their count, and by name what ``harness/kernels.py`` finds and asks for)
and the collectives of the compiled text. Nothing runs, so it gives no time.
Record the line under ``memory_analysis`` in the cell's traffic file: a
description of the program as it stood, which no run reads as a limit.
``--text FILE`` also writes the compiled text.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--chips", type=int, default=None,
                    help="default: the traffic mix's")
    ap.add_argument("--text", default=None)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harness import hlo_text, kernels, spec as spec_lib
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.parallel import dp, mesh as mesh_lib

    spec = spec_lib.load()
    cell = spec_lib.workload(spec, args.workload)
    traffic = spec_lib.traffic(cell["traffic"])
    config, builder = spec_lib.config(spec, cell["config"])
    job = spec_lib.load_module(builder).build(config, traffic)
    chips = args.chips or int(traffic["chips"])

    # the router asks jax.default_backend(), which is the CPU here
    fa.flash_attention = functools.partial(fa.flash_attention,
                                           interpret=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = mesh_lib.data_parallel_mesh(topo.devices[:chips])

    def on_mesh(tree, partition):
        sharding = NamedSharding(mesh, partition)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    key = jax.eval_shape(lambda: jax.random.key(0))
    params, model_state = jax.eval_shape(job.init, key)
    batch = jax.eval_shape(
        functools.partial(job.make_batch,
                          n=int(traffic["per_chip_batch"]) * chips), key)
    make = dp.make_stateful_train_step if job.stateful else \
        dp.make_train_step
    step = make(job.loss_fn, job.optimizer, mesh, donate=True,
                **traffic.get("step", {}))
    state = [on_mesh(params, P()),
             on_mesh(jax.eval_shape(job.optimizer.init, params), P())]
    if job.stateful:
        state.append(on_mesh(model_state, P()))
    compiled = step.lower(*state, on_mesh(batch, P(dp.DP_AXES)),
                          on_mesh(key, P())).compile()
    text = compiled.as_text()
    if args.text:
        Path(args.text).write_text(text)
    memory = compiled.memory_analysis()
    index = hlo_text.HloIndex(text)
    print(json.dumps({
        "workload": args.workload, "compiled_for": "v5e:2x2 (described)",
        "chips": chips,
        "argument_bytes": memory.argument_size_in_bytes,
        "output_bytes": memory.output_size_in_bytes,
        "alias_bytes": memory.alias_size_in_bytes,
        "temp_bytes": memory.temp_size_in_bytes,
        "tpu_custom_calls": len(index.kernels()),
        "kernels": kernels.inventory(index),
        "kernels_missing": kernels.missing(job, index),
        "kernels_not_asked_for": kernels.unasked(job, index),
        "collectives": index.collective_payload(),
        "model_flops_per_item": job.model_flops_per_item}))


if __name__ == "__main__":
    main()
