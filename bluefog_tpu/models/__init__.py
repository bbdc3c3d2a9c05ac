"""Model zoo for bluefog_tpu benchmarks, examples, and tests.

The reference framework has no model code of its own — its examples pull
torchvision models (reference: examples/pytorch_benchmark.py uses
``torchvision.models.resnet50``, examples/pytorch_mnist.py defines a small
CNN). A standalone TPU framework cannot lean on torchvision, so the
equivalents live here as flax modules designed for the MXU: bfloat16 compute
with float32 parameters/batch-stats, channel counts that are multiples of
128 where the architecture allows, and no data-dependent Python control flow.
"""

from .config_lm import (ConfigLM, LMConfig, exit_distribution, label_cross_entropy,
                        looped_exit_loss, moe_choices, moe_counters, next_token_loss)
from .mlp import MLP, LeNet5
from .fold import fold_batchnorm
from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101
from .transformer import MoEBlock, MoETransformerLM, TransformerLM, apply_rope
from .vgg import VGG, VGG11, VGG16, VGG19

__all__ = [
    "ConfigLM",
    "LMConfig",
    "exit_distribution",
    "label_cross_entropy",
    "looped_exit_loss",
    "moe_choices",
    "moe_counters",
    "next_token_loss",
    "MLP",
    "LeNet5",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "fold_batchnorm",
    "MoEBlock",
    "MoETransformerLM",
    "TransformerLM",
    "apply_rope",
    "VGG",
    "VGG11",
    "VGG16",
    "VGG19",
]
