"""In-repo model zoo (BASELINE.json configs).

The reference keeps GPT/Llama/ERNIE/MoE/UNet in PaddleNLP/PaddleMIX; this repo
supplies minimal pretrain-grade implementations as the config matrix demands:
GPT-2 (345M single-device), Llama-2 (7B/65B hybrid), Mixtral-style MoE
(expert parallel), SD UNet (conv+attn).
"""

from paddle_tpu.models.gpt import GPTConfig, GPTModel, GPTPretrainModel  # noqa: F401
from paddle_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    LlamaModel,
    LlamaForCausalLM,
)
from paddle_tpu.models.mixtral import (  # noqa: F401
    MixtralConfig,
    MixtralModel,
    MixtralForCausalLM,
)
from paddle_tpu.models.ernie import (  # noqa: F401
    ErnieConfig,
    ErnieModel,
    ErnieForPretraining,
)
from paddle_tpu.models.unet import UNetConfig, UNetModel  # noqa: F401
from paddle_tpu.models.xing4 import Xing4Config, Xing4ForCausalLM  # noqa: F401
from paddle_tpu.models.minicpm_sala import (  # noqa: F401
    MiniCPMSALAConfig,
    MiniCPMSALAForCausalLM,
)
