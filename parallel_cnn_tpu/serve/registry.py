"""Model registry: name → a uniform inference handle.

The trainers speak two dialects — the reference-parity LeNet is a bare
params pytree with a functional forward (models/lenet_ref + ops/reference),
the zoo models are nn.core.Module values with (params, model_state) and an
`apply`. Serving wants neither distinction: the engine needs exactly
``init(key) -> (params, model_state)`` and
``forward(params, model_state, x) -> outputs`` plus the per-sample input
shape, so every registered model is wrapped into that shape here.

Registered names match the CLI's --model choices, so any checkpoint the
trainers produce has a handle that can serve it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple


@dataclasses.dataclass(frozen=True)
class ModelHandle:
    """Uniform inference surface over one model family member.

    - ``init(key) -> (params, model_state)`` — fresh weights, and the
      restore TEMPLATE for checkpoint loading (leaf shapes/dtypes).
    - ``forward(params, model_state, x) -> y`` — eval-mode batched
      forward ((n, *in_shape) → (n, n_outputs)); pure and jit/AOT-safe.
    - ``in_shape`` — per-sample input shape (no batch dim).
    """

    name: str
    in_shape: Tuple[int, ...]
    n_outputs: int
    init: Callable[[Any], Tuple[Any, Any]]
    forward: Callable[[Any, Any, Any], Any]


def _lenet_handle() -> ModelHandle:
    import jax

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.ops import reference as ops

    def init(key):
        return lenet_ref.init(key), {}

    def forward(params, state, x):
        del state  # stateless model; uniform signature
        return jax.vmap(lambda s: ops.forward(params, s).out_f)(x)

    return ModelHandle("lenet_ref", (28, 28), 10, init, forward)


def _zoo_handle(name: str, factory, in_shape, n_outputs) -> ModelHandle:
    model = factory()

    def init(key):
        params, state, _ = model.init(key, in_shape)
        return params, state

    def forward(params, state, x):
        # train=False: BatchNorm evaluates from running stats — the
        # folded per-channel scale/shift form — and conv_backend="pallas"
        # layers take the fused single-kernel epilogue path
        # (nn/layers.py ConvBNAct).
        return model.apply(params, state, x, train=False)[0]

    return ModelHandle(name, in_shape, n_outputs, init, forward)


def available() -> Tuple[str, ...]:
    return ("lenet_ref", "cifar_cnn", "resnet18", "resnet34", "resnet50",
            "vgg16", "convnext_b")


def get(name: str, conv_backend: str = "xla") -> ModelHandle:
    """Handle for a registered model name.

    ``conv_backend`` applies to the resnet/vgg families (same rule as
    the training CLI); other names require the default "xla".
    """
    if name == "lenet_ref":
        if conv_backend != "xla":
            raise ValueError(
                "conv_backend='pallas' applies to the resnet/vgg models"
            )
        return _lenet_handle()

    from parallel_cnn_tpu.nn import cifar, convnext, resnet, vgg

    zoo: Dict[str, Tuple[Callable, Tuple[int, ...], int]] = {
        "cifar_cnn": (lambda: cifar.cifar_cnn(), cifar.IN_SHAPE, 10),
        "resnet18": (lambda: resnet.resnet18(
            10, cifar_stem=True, conv_backend=conv_backend
        ), cifar.IN_SHAPE, 10),
        "resnet34": (lambda: resnet.resnet34(
            10, cifar_stem=True, conv_backend=conv_backend
        ), cifar.IN_SHAPE, 10),
        "resnet50": (lambda: resnet.resnet50(
            10, cifar_stem=True, conv_backend=conv_backend
        ), cifar.IN_SHAPE, 10),
        "vgg16": (lambda: vgg.vgg16(10, conv_backend=conv_backend),
                  cifar.IN_SHAPE, 10),
        "convnext_b": (lambda: convnext.convnext_b(10), cifar.IN_SHAPE, 10),
    }
    if name not in zoo:
        raise KeyError(
            f"unknown model {name!r}; registered: {', '.join(available())}"
        )
    if name in ("cifar_cnn", "convnext_b") and conv_backend != "xla":
        raise ValueError(
            "conv_backend='pallas' applies to the resnet/vgg models"
        )
    factory, in_shape, n_out = zoo[name]
    return _zoo_handle(name, factory, in_shape, n_out)
