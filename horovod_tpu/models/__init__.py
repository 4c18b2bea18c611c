"""Model zoo for benchmarks and examples (reference context: the models exercised
by Horovod's examples/ and docs/benchmarks.rst)."""

from .mlp import MLP  # noqa: F401
from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,  # noqa: F401
                     ResNet152)
from ..ops.attention import default_attention  # noqa: F401
from .vgg import VGG, VGG16, VGG19  # noqa: F401
from .inception import InceptionV3  # noqa: F401
