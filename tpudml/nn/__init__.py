from tpudml.nn.layers import (
    Activation,
    AvgPool,
    BatchNorm,
    Conv2D,
    Dense,
    Dropout,
    Flatten,
    GatedGroupRMSNorm,
    GatedMLP,
    LayerNorm,
    MaxPool,
    Module,
    RMSNorm,
    Sequential,
)
from tpudml.nn.attention import MultiHeadAttention, dot_product_attention
from tpudml.nn.mamba import Mamba2
from tpudml.nn.moe import MoELayer, SigmoidMoE, load_balancing_loss

__all__ = [
    "Module",
    "Dense",
    "Conv2D",
    "MaxPool",
    "AvgPool",
    "Flatten",
    "Activation",
    "BatchNorm",
    "Dropout",
    "LayerNorm",
    "RMSNorm",
    "GatedGroupRMSNorm",
    "GatedMLP",
    "Sequential",
    "MultiHeadAttention",
    "dot_product_attention",
    "MoELayer",
    "SigmoidMoE",
    "Mamba2",
    "load_balancing_loss",
]
