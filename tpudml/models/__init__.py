from tpudml.models.hybrid import HybridLM
from tpudml.models.lenet import LeNet
from tpudml.models.mlp import ForwardMLP
from tpudml.models.resnet import ResNet, ResNet18, ResNet34, ResNet50
from tpudml.models.staged import StagedModel, lenet_stages
from tpudml.models.transformer import (
    TransformerBlock,
    TransformerEmbed,
    TransformerHead,
    TransformerLM,
)

__all__ = [
    "HybridLM",
    "LeNet",
    "ForwardMLP",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "StagedModel",
    "lenet_stages",
    "TransformerBlock",
    "TransformerEmbed",
    "TransformerHead",
    "TransformerLM",
]
