"""Standard layers, NHWC, TPU-first.

Convs lower to `lax.conv_general_dilated` with NHWC/HWIO dimension numbers
— channels-last keeps the channel dim on the lane axis of the MXU so XLA
tiles 8×128 without transposes. BatchNorm means are plain batch means: in
GSPMD data-parallel training (jit + batch sharded over the mesh's data
axis) XLA turns them into global cross-replica means automatically — no
explicit psum needed (contrast the reference's hand-placed MPI_Reduce per
kernel, MPI/layer.h)."""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from parallel_cnn_tpu.nn.core import Module, Shape


def _he_normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


@dataclasses.dataclass(frozen=True)
class Conv2D(Module):
    """features × (kh, kw) conv, stride/padding configurable, He init.

    backend="pallas" routes supported shapes (square odd k ∈ {1,3,5,7},
    stride 1/2, SAME — every conv in the ResNet and VGG families, 7×7-s2
    stem included) through the hand-written tapped-matmul kernels in
    ops/pallas_conv.py — the zoo's native-kernel path (BASELINE.json
    config #4). Unsupported shapes raise at construction-use time rather
    than silently falling back, so a "pallas" model is what it claims
    to be.
    """

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    use_bias: bool = True
    backend: str = "xla"

    def init(self, key, in_shape: Shape):
        h, w, c = in_shape
        kh, kw = self.kernel
        wkey, _ = jax.random.split(key)
        fan_in = kh * kw * c
        params = {
            "w": _he_normal(wkey, (kh, kw, c, self.features), fan_in, jnp.float32)
        }
        if self.use_bias:
            params["b"] = jnp.zeros((self.features,), jnp.float32)
        out = lax.conv_general_shape_tuple(
            (1, h, w, c),
            (kh, kw, c, self.features),
            self.strides,
            self.padding,
            ("NHWC", "HWIO", "NHWC"),
        )
        return params, {}, tuple(out[1:])

    def apply(self, params, state, x, train: bool = False):
        if self.backend == "pallas":
            from parallel_cnn_tpu.ops import pallas_conv

            if not pallas_conv.supports(self.kernel, self.strides, self.padding):
                raise ValueError(
                    f"pallas conv backend does not cover kernel={self.kernel} "
                    f"strides={self.strides} padding={self.padding!r}"
                )
            y = pallas_conv.conv2d(
                x, params["w"].astype(x.dtype), self.strides[0]
            )
        else:
            y = lax.conv_general_dilated(
                x,
                params["w"].astype(x.dtype),
                self.strides,
                self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class Dense(Module):
    features: int

    def init(self, key, in_shape: Shape):
        (d,) = in_shape
        wkey, _ = jax.random.split(key)
        params = {
            "w": _he_normal(wkey, (d, self.features), d, jnp.float32),
            "b": jnp.zeros((self.features,), jnp.float32),
        }
        return params, {}, (self.features,)

    def apply(self, params, state, x, train: bool = False):
        return x @ params["w"].astype(x.dtype) + params["b"].astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class BatchNorm(Module):
    """Running-stats batch norm; stats update only when train=True.

    The batch mean/var are global under GSPMD data parallelism (XLA
    all-reduces them when the batch is sharded) — true sync-BN for free.
    """

    momentum: float = 0.9
    eps: float = 1e-5

    def init(self, key, in_shape: Shape):
        c = in_shape[-1]
        params = {
            "scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
        }
        state = {
            "mean": jnp.zeros((c,), jnp.float32),
            "var": jnp.ones((c,), jnp.float32),
        }
        return params, state, in_shape

    def apply(self, params, state, x, train: bool = False):
        axes = tuple(range(x.ndim - 1))
        if train:
            mean = jnp.mean(x.astype(jnp.float32), axis=axes)
            var = jnp.var(x.astype(jnp.float32), axis=axes)
            m = self.momentum
            state = {
                "mean": m * state["mean"] + (1 - m) * mean,
                "var": m * state["var"] + (1 - m) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
        # Statistics stay f32 (the reductions above consume the upcast
        # without materializing it), but the normalization's ELEMENTWISE
        # arithmetic runs at x's dtype: the previous form
        # ((x.astype(f32) − mean)·inv + bias).astype upcast the whole
        # (B,H,W,C) activation to f32 — doubling the elementwise HBM
        # traffic of every BN in bf16 mode, a candidate in the ResNet-50
        # MFU gap (VERDICT r3 weak #2). Order matters for bf16 rounding:
        # subtract mean FIRST so the product (x−mean)·inv rounds at the
        # O(1) normalized magnitude, not at |x·inv| ~ |mean/std| (a
        # folded y = x·inv + shift form measured 2-4× worse channel
        # rounding for large-|mean| channels). f32 inputs are bit-
        # identical to the old path (the casts are no-ops).
        inv = lax.rsqrt(var + self.eps) * params["scale"]
        y = (
            (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
            + params["bias"].astype(x.dtype)
        )
        return y, state


@dataclasses.dataclass(frozen=True)
class ConvBNAct(Module):
    """Conv2D(use_bias=False) → BatchNorm → (+ residual) → optional ReLU
    as ONE module, so backend="pallas" can execute the entire layer tail
    as a single fused kernel (`ops.pallas_conv.conv2d_fused`) in
    inference mode: the running-stats BN folds to per-channel
    scale/shift, and the residual add + ReLU ride the conv kernel's f32
    accumulator before its only HBM write — one round-trip per layer
    instead of three-to-four (≙ the reference CUDA kernels' fused
    bias+activation, CUDA/layer.cu:151-165).

    Training keeps the exact unfused composition: train-mode BN
    statistics are reductions OVER the conv output, so a one-pass
    fusion is mathematically impossible without changing the batch-stat
    semantics (docs/kernel_authoring.md). Gradients through the fused
    eval path (e.g. frozen-BN fine-tuning) are exact — conv2d_fused
    carries a full custom VJP.

    `apply(..., residual=sc)` computes relu?(bn(conv(x)) + sc); the
    fused-vs-unfused numerics differ only by f32 fold rounding (the
    fused epilogue runs entirely on the f32 accumulator).
    """

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    relu: bool = True
    momentum: float = 0.9
    eps: float = 1e-5
    backend: str = "xla"

    def _conv(self) -> Conv2D:
        return Conv2D(
            self.features,
            kernel=self.kernel,
            strides=self.strides,
            padding="SAME",
            use_bias=False,
            backend=self.backend,
        )

    def _bn(self) -> BatchNorm:
        return BatchNorm(momentum=self.momentum, eps=self.eps)

    def init(self, key, in_shape: Shape):
        k1, k2 = jax.random.split(key)
        cp, _, shape = self._conv().init(k1, in_shape)
        bp, bs, shape = self._bn().init(k2, shape)
        return {"conv": cp, "bn": bp}, {"bn": bs}, shape

    def apply(self, params, state, x, train: bool = False, residual=None):
        if self.backend == "pallas" and not train:
            from parallel_cnn_tpu.ops import pallas_conv

            if pallas_conv.supports(self.kernel, self.strides, "SAME"):
                bn_s = state["bn"]
                # Folded inference-mode BN: y = conv·scale + shift.
                scale = params["bn"]["scale"] * lax.rsqrt(
                    bn_s["var"] + self.eps
                )
                shift = params["bn"]["bias"] - bn_s["mean"] * scale
                y = pallas_conv.conv2d_fused(
                    x,
                    params["conv"]["w"].astype(x.dtype),
                    scale,
                    shift,
                    residual,
                    self.strides[0],
                    self.relu,
                )
                return y, state
        # Scopes are metadata (the op_name obs/programs.py reads back).
        with jax.named_scope("conv"):
            y, _ = self._conv().apply(params["conv"], {}, x, train)
        with jax.named_scope("bn"):
            y, bn_s = self._bn().apply(params["bn"], state["bn"], y, train)
        if residual is not None:
            with jax.named_scope("add"):
                y = y + residual
        if self.relu:
            with jax.named_scope("act"):
                y = jax.nn.relu(y)
        return y, {"bn": bn_s}


@dataclasses.dataclass(frozen=True)
class ReLU(Module):
    def init(self, key, in_shape: Shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        return jax.nn.relu(x), state


def _pool_out(in_shape: Shape, window, strides, padding) -> Shape:
    h, w, c = in_shape
    if padding == "SAME":
        oh = -(-h // strides[0])
        ow = -(-w // strides[1])
    else:
        oh = (h - window[0]) // strides[0] + 1
        ow = (w - window[1]) // strides[1] + 1
    return (oh, ow, c)


@dataclasses.dataclass(frozen=True)
class MaxPool(Module):
    window: Tuple[int, int] = (2, 2)
    strides: Tuple[int, int] = (2, 2)
    padding: str = "VALID"

    def init(self, key, in_shape: Shape):
        return {}, {}, _pool_out(in_shape, self.window, self.strides, self.padding)

    def apply(self, params, state, x, train: bool = False):
        y = lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            (1, *self.window, 1),
            (1, *self.strides, 1),
            self.padding,
        )
        return y, state


@dataclasses.dataclass(frozen=True)
class AvgPool(Module):
    window: Tuple[int, int] = (2, 2)
    strides: Tuple[int, int] = (2, 2)
    padding: str = "VALID"

    def init(self, key, in_shape: Shape):
        return {}, {}, _pool_out(in_shape, self.window, self.strides, self.padding)

    def apply(self, params, state, x, train: bool = False):
        dims = (1, *self.window, 1)
        strides = (1, *self.strides, 1)
        y = lax.reduce_window(
            x, jnp.zeros((), x.dtype), lax.add, dims, strides, self.padding
        )
        if self.padding == "SAME":
            # Edge windows overlap padding: divide by the per-window count
            # of VALID elements, not the full window size.
            ones = jnp.ones(x.shape[1:3], x.dtype)[None, :, :, None]
            counts = lax.reduce_window(
                ones, jnp.zeros((), x.dtype), lax.add, dims, strides,
                self.padding,
            )
            return y / counts, state
        return y / (self.window[0] * self.window[1]), state


@dataclasses.dataclass(frozen=True)
class GlobalAvgPool(Module):
    def init(self, key, in_shape: Shape):
        return {}, {}, (in_shape[-1],)

    def apply(self, params, state, x, train: bool = False):
        return jnp.mean(x, axis=(1, 2)), state


@dataclasses.dataclass(frozen=True)
class Flatten(Module):
    def init(self, key, in_shape: Shape):
        size = 1
        for d in in_shape:
            size *= d
        return {}, {}, (size,)

    def apply(self, params, state, x, train: bool = False):
        return x.reshape(x.shape[0], -1), state
