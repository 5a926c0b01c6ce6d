from torchft_tpu.models import cnn, dsv2, granite, ling, mellum, moe, nemotron, olmoe, ouro, sdar
from torchft_tpu.models.cnn import CNNConfig, tiny_cnn_config
from torchft_tpu.models.moe import MoEConfig, tiny_moe_config
from torchft_tpu.models.olmoe import OlmoeConfig, tiny_olmoe_config
from torchft_tpu.models.transformer import (
    TransformerConfig,
    big_config,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    param_sharding_rules,
    tiny_config,
)

__all__ = [
    "CNNConfig",
    "MoEConfig",
    "OlmoeConfig",
    "TransformerConfig",
    "big_config",
    "cnn",
    "dsv2",
    "tiny_cnn_config",
    "forward",
    "granite",
    "init_params",
    "ling",
    "loss_fn",
    "make_train_step",
    "mellum",
    "moe",
    "nemotron",
    "olmoe",
    "ouro",
    "param_sharding_rules",
    "sdar",
    "tiny_config",
    "tiny_moe_config",
    "tiny_olmoe_config",
]
