from horovod_tpu.ops.fusion import fused_apply, fused_apply_tree  # noqa: F401
from horovod_tpu.ops.head_loss import (  # noqa: F401
    cross_entropy,
    head_cross_entropy,
)
