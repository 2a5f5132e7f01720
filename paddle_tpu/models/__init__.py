"""Model zoo beyond vision: LLM/MoE/diffusion families."""
from .llama import (LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM,  # noqa: F401
                    LlamaModel)
from .bert import (BertConfig, BertForPretraining,  # noqa: F401
                   BertForSequenceClassification, BertModel)
from .gpt_moe import MoEConfig, MoEForCausalLM  # noqa: F401
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
from . import generation  # noqa: F401
from .generation import generate  # noqa: F401
from .sdar_moe import (DroplessMoE, SDARMoEConfig,  # noqa: F401
                       SDARMoEForCausalLM)
from .afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: F401
from .glm4_moe_lite import (Glm4MoeLiteConfig,  # noqa: F401
                            Glm4MoeLiteForCausalLM)
from .mimo_v2 import MimoV2Config, MimoV2ForCausalLM  # noqa: F401
