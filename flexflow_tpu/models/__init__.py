"""Model zoo: builder functions reproducing every reference example family
(SURVEY §2.6: AlexNet, ResNet-50, resnext-50, InceptionV3, Transformer/BERT,
DLRM, XDL, candle_uno, MLP_Unify, MNIST MLP, MoE) on the FFModel API, plus
the TPU-native flagship Transformer LM used by bench.py.
"""

from .alexnet import build_alexnet
from .candle_uno import build_candle_uno
from .dlrm import DLRMConfig, build_dlrm
from .inception import build_inception_v3
from .mlp import build_mlp_unify, build_mnist_mlp
from .moe import MoeConfig, build_moe
from .resnet import build_resnet50, build_resnext50
from .transformer import (
    TRANSFORMER_LM_ZOO,
    TransformerConfig,
    TransformerLMConfig,
    build_transformer,
    build_transformer_lm,
    build_transformer_lm_pipelined,
    command_a_plus_lm_config,
    deepseek_v32_lm_config,
    evabyte_lm_config,
    jamba_lm_config,
    keye_vl2_lm_config,
    lfm2_moe_lm_config,
    mimo_v2_flash_lm_config,
    mistral_small4_lm_config,
    olmoe_lm_config,
    solar_open2_lm_config,
    transformer_lm_param_count,
    transformer_lm_state_bytes_per_chip,
)
from .xdl import build_xdl
