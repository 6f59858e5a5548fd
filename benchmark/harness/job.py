"""What a configuration's ``build(config, traffic)`` hands the harness."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence


@dataclass
class Tolerance:
    """Agreement with the float32 reference, fixed beforehand from the dtype."""
    loss_rtol: float
    grad_rel_l2: float
    reason: str
    # a key of a leaf's path -> the limit of the leaves under that key,
    # where a class of leaves reads apart from the rest on sound runs (a
    # router's near-ties); every other leaf takes ``grad_rel_l2``
    grad_rel_l2_under: dict = field(default_factory=dict)

    def gradient_limit(self, path) -> tuple:
        """(the key of ``grad_rel_l2_under`` the leaf is under or "", the
        leaf's limit)."""
        for key in path:
            if key in self.grad_rel_l2_under:
                return key, self.grad_rel_l2_under[key]
        return "", self.grad_rel_l2


@dataclass
class Job:
    unit: str                    # "tokens" or "images": the items counted
    items_per_example: int       # tokens in a sequence, 1 for an image
    stateful: bool               # True: loss_fn takes and returns model state
    init: Callable               # key -> (params, model_state or None); jitted by the harness
    loss_fn: Callable            # the program's own loss, as dp.make_*train_step takes it
    optimizer: Any               # optax GradientTransformation
    make_batch: Callable         # (key, n_examples) -> batch pytree; jitted by the harness
    model_flops_per_item: float  # forward + backward, from harness/flops.py
    reference_loss: Callable     # plain float32 jax.numpy: (params, model_state, batch) -> loss
    check_leaves: Sequence[tuple]  # key paths of the leaves whose gradients are compared
    sample_examples: int         # examples in the reference's sample
    tolerance: Tolerance
    # flash kernel calls of one step on one chip, for the roofline and for
    # harness/kernels.py, which asks the compiled step for them:
    # (batch, seq, heads, head_dim, causal) or None where no kernel runs
    flash_call: Optional[tuple] = None
    flash_layers: int = 0
    # the parameters the reference check runs on, from the initial ones
    check_params: Callable = lambda params: params
    facts: dict = field(default_factory=dict)  # printed on an earlier line
    # A described fact and no limit: the tpu_custom_call count of the step
    # as the configuration's author saw it compile. Nothing under benchmark/
    # reads it (PR 32 took it out of ``correct``); the two expert
    # configurations still state it for tests/test_olmoe.py and
    # tests/test_tpu_compile.py, the program's own tests, which a benchmark
    # PR may not edit. It goes when they hold their counts as literals.
    expected_custom_calls: Optional[int] = None


@dataclass
class Run:
    """What a per-layer metric's ``read(trace, run)`` may look at, besides
    the reduced trace: the facts of this run that are not in the trace."""
    job: Job
    chips: int
    block_steps: int
    peaks: dict                  # the device's row of peaks.json
    hlo: Any                     # harness.hlo_text.HloIndex of the step
    program: str                 # the compiled step's module name
    init_s: float                # process start -> hvd.mesh()
    compile_s: float             # backend-compile seconds of the whole run
    programs_after_warmup: int   # programs built inside the timed windows
    dispatch_seconds: Sequence[float]  # host time of each step call
    items_per_step_per_chip: float
