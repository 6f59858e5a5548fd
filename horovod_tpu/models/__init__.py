from horovod_tpu.models.mnist import MnistConvNet  # noqa: F401
# the operator the losses below call
from horovod_tpu.ops.head_loss import (  # noqa: F401
    cross_entropy,
    head_cross_entropy,
)
from horovod_tpu.models.gpt import (  # noqa: F401
    GptDecoder,
    GptMedium,
    GptSmall,
)
from horovod_tpu.models.joyai_flash import (  # noqa: F401
    JoyaiFlashDecoder,
    JoyaiFlashTiny,
    JoyaiLlmFlash,
    joyai_flash_loss,
)
from horovod_tpu.models.lfm2 import (  # noqa: F401
    Lfm2_8B_A1B,
    Lfm2MoeDecoder,
    Lfm2Tiny,
    lfm2_loss,
)
from horovod_tpu.models.nemotron_h import (  # noqa: F401
    Nemotron3Nano30B,
    NemotronHDecoder,
    NemotronHTiny,
    nemotron_h_loss,
)
from horovod_tpu.models.olmoe import (  # noqa: F401
    Olmoe1B7B,
    OlmoeDecoder,
    olmoe_loss,
)
from horovod_tpu.models.sdar import (  # noqa: F401
    Sdar30BA3B,
    SdarMoeDecoder,
    SdarTiny,
    sdar_loss,
    sdar_noise,
)
from horovod_tpu.models.smallthinker import (  # noqa: F401
    SmallThinker21BA3B,
    SmallThinkerDecoder,
    SmallThinkerTiny,
    smallthinker_loss,
)
from horovod_tpu.models.trinity import (  # noqa: F401
    TrinityDecoder,
    TrinityMini,
    TrinityTiny,
    trinity_loss,
)
from horovod_tpu.models.transformer import (  # noqa: F401
    BertBase,
    BertEncoder,
    BertLarge,
)
from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
