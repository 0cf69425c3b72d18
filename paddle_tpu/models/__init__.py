"""Model zoo: flagship language models built on paddle_tpu.nn.

Reference anchor: the fleet GPT benchmark models driven by
meta_parallel/pipeline_parallel.py + mpu layers in the reference repo.
"""
from .gpt import (  # noqa: F401
    GPTConfig, GPTDecoderLayer, GPTEmbeddings, GPTModel, GPTForPretraining,
    GPTPretrainingCriterion, GPTHybridTrainStep, GPTGenerator,
    gpt_tiny_config,
    gpt_345m_config, gpt_1p3b_config, gpt_13b_config,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining, BertPretrainingCriterion,
    bert_tiny_config, bert_base_config,
)
from .ernie import (  # noqa: F401
    ErnieMoeConfig, ErnieMoeModel, ErnieMoeForPretraining,
    ErnieMoeGenerator,
    ernie_moe_tiny_config, ernie_moe_base_config,
)
from .sdar import (  # noqa: F401
    SdarMoeConfig, sdar_moe_tiny_config, init_sdar_weights,
)

# Phi-4-mini-flash and GLM-5.2 are found on first use (see
# serving/__init__.py)
_LAZY = {"Phi4FlashConfig": "phi4flash", "phi4flash_tiny_config": "phi4flash",
         "init_phi4flash_weights": "phi4flash", "Glm5Config": "glm5",
         "glm5_tiny_config": "glm5", "init_glm5_weights": "glm5"}


def __getattr__(name):
    if name in _LAZY:
        from importlib import import_module
        return getattr(import_module("." + _LAZY[name], __name__), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
