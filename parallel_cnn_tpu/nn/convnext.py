"""ConvNeXt (Liu et al. 2022, "A ConvNet for the 2020s", arXiv:2201.03545,
section 2 / Fig. 4 and Appendix A) — the modernised conv net of the zoo,
built from this package's NHWC layers.

    stem            LN(Conv 4x4 stride 4)
    stage i         depths[i] blocks at width dims[i]
    between stages  Conv 2x2 stride 2 (LN(x))
    head            global average pool -> LN -> Linear

    block at width C:
        x + DropPath_p(gamma * Linear_{4C->C}(GELU(Linear_{C->4C}(
                LN(DWConv7x7(x))))))

The depthwise conv pads 3 and has a bias (``Conv2D(groups=C)``); LayerNorm
is over the channel axis (eps 1e-6); GELU is the erf form, one float32
``erf`` an element each way (nn/layers.py:GELU); ``gamma`` starts
at ``layer_scale_init``; the drop rate ``p`` rises linearly over all blocks
from 0 to ``drop_path_rate``. Weights are drawn as the authors do
(``trunc_normal_(std=.02)``), biases start at 0. The two pointwise layers
are ``Dense`` over the channel axis, as the authors write them (a matmul
at every position).

Scopes (op_name metadata read back by obs/programs.py, and the names
benchmark/shapes/convnext.py lists): ``stem``, ``s<stage>b<block>``,
``down<stage>``, ``gap``, ``norm``, ``fc``; inside a block ``dw``,
``norm``, ``expand``, ``act``, ``reduce``, ``scale``, ``drop``, ``add``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax

from parallel_cnn_tpu.nn.core import Module, Sequential, Shape
from parallel_cnn_tpu.nn.layers import (
    GELU,
    Conv2D,
    Dense,
    DropPath,
    GlobalAvgPool,
    LayerNorm,
    LayerScale,
)

INIT_STD = 0.02
LN_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Block(Module):
    """One ConvNeXt block: depthwise 7x7 -> LN -> 4x expansion -> GELU ->
    reduction -> LayerScale -> DropPath -> + x. Only ``drop`` has state
    (its key, nn/layers.py:DropPath)."""

    features: int
    drop_rate: float = 0.0
    layer_scale_init: float = 1e-6

    def _branch(self) -> Sequential:
        c = self.features
        return Sequential(
            [
                Conv2D(c, kernel=(7, 7), groups=c, init_std=INIT_STD),
                LayerNorm(LN_EPS),
                Dense(4 * c, init_std=INIT_STD),
                GELU(),
                Dense(c, init_std=INIT_STD),
                LayerScale(self.layer_scale_init),
                DropPath(self.drop_rate),
            ],
            ["dw", "norm", "expand", "act", "reduce", "scale", "drop"],
        )

    def init(self, key, in_shape: Shape):
        if in_shape[-1] != self.features:
            raise ValueError(
                f"a ConvNeXt block keeps its width: {in_shape[-1]} channels "
                f"in, {self.features} features"
            )
        branch = self._branch()
        params, state, _ = branch.init(key, in_shape)
        names = branch.scope_names()
        return (
            {n: p for n, p in zip(names, params) if p},
            {n: s for n, s in zip(names, state) if s},
            in_shape,
        )

    def apply(self, params, state, x, train: bool = False):
        branch = self._branch()
        names = branch.scope_names()
        y, new = branch.apply(
            [params.get(n, {}) for n in names],
            [state.get(n, {}) for n in names],
            x, train,
        )
        with jax.named_scope("add"):
            y = x + y
        return y, {n: s for n, s in zip(names, new) if s}


def convnext(
    depths: Sequence[int],
    dims: Sequence[int],
    num_classes: int,
    drop_path_rate: float = 0.0,
    layer_scale_init: float = 1e-6,
) -> Sequential:
    if len(depths) != len(dims):
        raise ValueError(f"{len(depths)} depths for {len(dims)} widths")
    total = sum(depths)
    layers = [
        Sequential(
            [
                Conv2D(dims[0], kernel=(4, 4), strides=(4, 4),
                       padding="VALID", init_std=INIT_STD),
                LayerNorm(LN_EPS),
            ],
            ["conv", "norm"],
        )
    ]
    names = ["stem"]
    at = 0
    for i, (depth, dim) in enumerate(zip(depths, dims)):
        if i:
            layers.append(
                Sequential(
                    [
                        LayerNorm(LN_EPS),
                        Conv2D(dim, kernel=(2, 2), strides=(2, 2),
                               padding="VALID", init_std=INIT_STD),
                    ],
                    ["norm", "conv"],
                )
            )
            names.append(f"down{i + 1}")
        for j in range(depth):
            # torch.linspace(0, rate, total)[at], as the authors spread it
            rate = drop_path_rate * at / max(total - 1, 1)
            layers.append(Block(dim, rate, layer_scale_init))
            names.append(f"s{i + 1}b{j + 1}")
            at += 1
    layers += [
        GlobalAvgPool(),
        LayerNorm(LN_EPS),
        Dense(num_classes, init_std=INIT_STD),
    ]
    names += ["gap", "norm", "fc"]
    return Sequential(layers, names)


def convnext_b(
    num_classes: int = 1000,
    drop_path_rate: float = 0.5,
    layer_scale_init: float = 1e-6,
) -> Sequential:
    """ConvNeXt-B: depths 3-3-27-3, widths 128-256-512-1024 (88.6 M
    parameters, 15.35 GMACs forward at 224x224); stochastic depth 0.5 is
    the paper's ImageNet-1K setting for -B (Table 5)."""
    return convnext(
        (3, 3, 27, 3), (128, 256, 512, 1024), num_classes,
        drop_path_rate, layer_scale_init,
    )
