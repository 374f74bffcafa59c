"""ResNet family (BASELINE.json configs #4/#5: ResNet-18 on CIFAR-10,
ResNet-50 on ImageNet-1k) — He et al. 2016, built from this package's
layers with a functional residual-block module.

CIFAR variants use the 3×3/stride-1 stem (no maxpool); ImageNet variants
the 7×7/stride-2 stem + 3×3 maxpool, per the paper.

Round 6: blocks are built from `ConvBNAct` units (conv→BN→[+residual]→
relu as one module) so the branch TAILS — the BN, the shortcut add, and
the post-add ReLU — execute inside the conv kernel's epilogue on the
pallas backend in inference mode (`ops.pallas_conv.conv2d_fused`): one
HBM round-trip per layer instead of three-to-four. Both backends share
the module structure, so parameter trees stay identical across
conv_backend choices (the cross-backend parity tests zip leaves
strictly)."""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax

from parallel_cnn_tpu.nn.core import Module, Sequential, Shape
from parallel_cnn_tpu.nn.layers import (
    ConvBNAct,
    Dense,
    GlobalAvgPool,
    MaxPool,
)


@dataclasses.dataclass(frozen=True)
class BasicBlock(Module):
    """Two 3×3 convs + identity/projection shortcut (ResNet-18/34).

    The shortcut feeds the tail ConvBNAct as its fused residual: the
    add and the post-add ReLU live in the second conv's epilogue."""

    features: int
    stride: int = 1
    conv_backend: str = "xla"

    def _parts(self):
        head = ConvBNAct(
            self.features, strides=(self.stride, self.stride),
            backend=self.conv_backend,
        )
        tail = ConvBNAct(self.features, backend=self.conv_backend)
        proj = ConvBNAct(
            self.features, kernel=(1, 1),
            strides=(self.stride, self.stride), relu=False,
            backend=self.conv_backend,
        )
        return head, tail, proj

    def init(self, key, in_shape: Shape):
        head, tail, proj = self._parts()
        k1, k2 = jax.random.split(key)
        k1a, k1b = jax.random.split(k1)
        hp, hs, mid_shape = head.init(k1a, in_shape)
        tp, ts, out_shape = tail.init(k1b, mid_shape)
        params = {"main": [hp, tp]}
        state = {"main": [hs, ts]}
        if self.stride != 1 or in_shape[-1] != self.features:
            pp, ps, _ = proj.init(k2, in_shape)
            params["proj"] = [pp]
            state["proj"] = [ps]
        return params, state, out_shape

    def apply(self, params, state, x, train: bool = False):
        head, tail, proj = self._parts()
        # Branch scopes carry the names `_parts()` gives (and
        # benchmark/flops.py uses for the same convs).
        if "proj" in params:
            with jax.named_scope("proj"):
                sc, ps = proj.apply(
                    params["proj"][0], state["proj"][0], x, train
                )
        else:
            sc = x
        with jax.named_scope("head"):
            y, hs = head.apply(
                params["main"][0], state["main"][0], x, train
            )
        with jax.named_scope("tail"):
            y, ts = tail.apply(
                params["main"][1], state["main"][1], y, train, residual=sc
            )
        new_state = {"main": [hs, ts]}
        if "proj" in params:
            new_state["proj"] = [ps]
        return y, new_state


@dataclasses.dataclass(frozen=True)
class Bottleneck(Module):
    """1×1 → 3×3 → 1×1(×4) bottleneck (ResNet-50/101/152); the wide
    final 1×1's epilogue carries the shortcut add + ReLU."""

    features: int  # bottleneck width; output is 4× this
    stride: int = 1
    conv_backend: str = "xla"
    EXPANSION = 4

    def _parts(self):
        out_ch = self.features * self.EXPANSION
        reduce = ConvBNAct(
            self.features, kernel=(1, 1), backend=self.conv_backend
        )
        mid = ConvBNAct(
            self.features, strides=(self.stride, self.stride),
            backend=self.conv_backend,
        )
        expand = ConvBNAct(
            out_ch, kernel=(1, 1), backend=self.conv_backend
        )
        proj = ConvBNAct(
            out_ch, kernel=(1, 1),
            strides=(self.stride, self.stride), relu=False,
            backend=self.conv_backend,
        )
        return reduce, mid, expand, proj

    def init(self, key, in_shape: Shape):
        reduce, mid, expand, proj = self._parts()
        k1, k2 = jax.random.split(key)
        ka, kb, kc = jax.random.split(k1, 3)
        rp, rs, s1 = reduce.init(ka, in_shape)
        mp, ms, s2 = mid.init(kb, s1)
        ep, es, out_shape = expand.init(kc, s2)
        params = {"main": [rp, mp, ep]}
        state = {"main": [rs, ms, es]}
        if self.stride != 1 or in_shape[-1] != self.features * self.EXPANSION:
            pp, ps, _ = proj.init(k2, in_shape)
            params["proj"] = [pp]
            state["proj"] = [ps]
        return params, state, out_shape

    def apply(self, params, state, x, train: bool = False):
        reduce, mid, expand, proj = self._parts()
        if "proj" in params:
            with jax.named_scope("proj"):
                sc, ps = proj.apply(
                    params["proj"][0], state["proj"][0], x, train
                )
        else:
            sc = x
        with jax.named_scope("reduce"):
            y, rs = reduce.apply(
                params["main"][0], state["main"][0], x, train
            )
        with jax.named_scope("mid"):
            y, ms = mid.apply(params["main"][1], state["main"][1], y, train)
        with jax.named_scope("expand"):
            y, es = expand.apply(
                params["main"][2], state["main"][2], y, train, residual=sc
            )
        new_state = {"main": [rs, ms, es]}
        if "proj" in params:
            new_state["proj"] = [ps]
        return y, new_state


def _stage(
    block_cls, features: int, count: int, stride: int, conv_backend: str
) -> Sequence[Module]:
    return [
        block_cls(features, stride if i == 0 else 1, conv_backend)
        for i in range(count)
    ]


def _resnet(
    block_cls,
    stage_sizes: Sequence[int],
    num_classes: int,
    cifar_stem: bool,
    conv_backend: str = "xla",
) -> Sequential:
    if cifar_stem:
        stem = [ConvBNAct(64, backend=conv_backend)]
    else:
        # Round 4: the 7×7-stride-2 stem joined the pallas kernel
        # library's coverage (ops/pallas_conv.py generalized tap
        # geometry), so conv_backend="pallas" now puts EVERY conv in
        # ResNet-50 on hand-written kernels; round 6 band-tiles its
        # rows so the 224² layout compiles in minutes and fuses its
        # BN+ReLU tail in eval. MaxPool stays XLA (pooling, not conv).
        stem = [
            ConvBNAct(64, kernel=(7, 7), strides=(2, 2),
                      backend=conv_backend),
            MaxPool(window=(3, 3), strides=(2, 2), padding="SAME"),
        ]
    layers = list(stem)
    # Scope names (Sequential.apply): s<stage>b<block>, 1-based.
    names = ["stem", "pool"][: len(stem)]
    for i, (features, count) in enumerate(zip((64, 128, 256, 512), stage_sizes)):
        layers += _stage(
            block_cls, features, count, 1 if i == 0 else 2, conv_backend
        )
        names += [f"s{i + 1}b{j + 1}" for j in range(count)]
    layers += [GlobalAvgPool(), Dense(num_classes)]
    names += ["gap", "fc"]
    return Sequential(layers, names)


def resnet18(
    num_classes: int = 10, cifar_stem: bool = True, conv_backend: str = "xla"
) -> Sequential:
    return _resnet(BasicBlock, (2, 2, 2, 2), num_classes, cifar_stem, conv_backend)


def resnet34(
    num_classes: int = 10, cifar_stem: bool = True, conv_backend: str = "xla"
) -> Sequential:
    return _resnet(BasicBlock, (3, 4, 6, 3), num_classes, cifar_stem, conv_backend)


def resnet50(
    num_classes: int = 1000, cifar_stem: bool = False, conv_backend: str = "xla"
) -> Sequential:
    return _resnet(Bottleneck, (3, 4, 6, 3), num_classes, cifar_stem, conv_backend)


def num_params(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))
