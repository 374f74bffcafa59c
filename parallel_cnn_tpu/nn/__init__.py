"""Generic NN layer library — the model-zoo substrate.

The reference hard-codes one network as four global `Layer` objects and a
fixed kernel wiring (Sequential/Main.cpp:17-20,59-144); growing past LeNet
(BASELINE.json configs: CIFAR CNN, ResNet-18/50) needs real composable
layers. This package is a deliberately small functional module system:

- a layer is a `Module` with `init(key, in_shape) -> (params, state)` and
  `apply(params, state, x, train) -> (y, state)`; params and state are
  plain pytrees (state = BatchNorm running stats — kept separate so the
  optimizer never sees it);
- everything composes through `Sequential`; models are plain data, no
  metaclasses, no tracing magic — friendly to jit/vmap/shard_map/pjit.

NHWC layouts throughout (channels-last is the TPU-native conv layout) and
He/LeCun inits; compute stays f32/bf16-polymorphic via the input dtype.
"""

from parallel_cnn_tpu.nn.core import Module, Sequential  # noqa: F401
from parallel_cnn_tpu.nn.layers import (  # noqa: F401
    AvgPool,
    BatchNorm,
    Conv2D,
    Dense,
    DropPath,
    Embedding,
    Flatten,
    GatedMLP,
    GELU,
    GlobalAvgPool,
    LayerNorm,
    LayerScale,
    MaxPool,
    ReLU,
    RMSNorm,
)
from parallel_cnn_tpu.nn import (  # noqa: F401
    afmoe,
    bailing_hybrid,
    cifar,
    convnext,
    glm_moe,
    keye_vl,
    ouro,
    resnet,
    sdar_moe,
    vgg,
)
