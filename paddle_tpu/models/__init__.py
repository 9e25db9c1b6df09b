"""Model zoo.

Reference parity: `python/paddle/vision/models/` (LeNet, ResNet, VGG,
MobileNet) plus transformer language models matching the reference's
ERNIE/GPT fleet examples.
"""
from .lenet import LeNet  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152  # noqa: F401
from .gpt import GPT, GPTConfig  # noqa: F401
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig  # noqa: F401
from .bert import Bert, BertConfig  # noqa: F401
from .ernie import Ernie, ErnieConfig, ErnieForPretraining  # noqa: F401
from .wide_deep import WideDeep  # noqa: F401
from .deepfm import DeepFM  # noqa: F401
