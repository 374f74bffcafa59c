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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from parallel_cnn_tpu.nn.core import Module, Shape
from parallel_cnn_tpu.ops import pallas_rope


def _he_normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.sqrt(2.0 / fan_in)


def _weight(key, shape, fan_in, init_std):
    """He normal, or (``init_std`` given) the ViT/ConvNeXt convention: a
    normal of that std cut at +-2 in absolute terms, as timm's
    ``trunc_normal_(std=.02)`` draws it."""
    if init_std is None:
        return _he_normal(key, shape, fan_in, jnp.float32)
    cut = 2.0 / init_std
    return init_std * jax.random.truncated_normal(
        key, -cut, cut, shape, jnp.float32)


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

    ``groups`` > 1 is a grouped conv (``groups == in channels ==
    features``: depthwise): the weight is ``(kh, kw, cin / groups,
    features)`` and each output channel reads its own group's inputs
    only. XLA only: the pallas kernels have no grouped form.
    """

    features: int
    kernel: Tuple[int, int] = (3, 3)
    strides: Tuple[int, int] = (1, 1)
    padding: str = "SAME"
    use_bias: bool = True
    backend: str = "xla"
    groups: int = 1
    init_std: Optional[float] = None

    def init(self, key, in_shape: Shape):
        h, w, c = in_shape
        kh, kw = self.kernel
        if c % self.groups or self.features % self.groups:
            raise ValueError(
                f"groups={self.groups} divides neither {c} input channels "
                f"nor {self.features} features evenly"
            )
        wkey, _ = jax.random.split(key)
        cin = c // self.groups
        fan_in = kh * kw * cin
        params = {
            "w": _weight(wkey, (kh, kw, cin, self.features), fan_in,
                         self.init_std)
        }
        if self.use_bias:
            params["b"] = jnp.zeros((self.features,), jnp.float32)
        # (the shape rule knows no groups: one group's channels stand in)
        out = lax.conv_general_shape_tuple(
            (1, h, w, cin),
            (kh, kw, cin, self.features),
            self.strides,
            self.padding,
            ("NHWC", "HWIO", "NHWC"),
        )
        return params, {}, tuple(out[1:])

    def apply(self, params, state, x, train: bool = False):
        if self.backend == "pallas":
            from parallel_cnn_tpu.ops import pallas_conv

            if self.groups != 1 or not pallas_conv.supports(
                    self.kernel, self.strides, self.padding):
                raise ValueError(
                    f"pallas conv backend does not cover kernel={self.kernel} "
                    f"strides={self.strides} padding={self.padding!r} "
                    f"groups={self.groups}"
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
                feature_group_count=self.groups,
            )
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


@dataclasses.dataclass(frozen=True)
class Dense(Module):
    """Affine map over the LAST axis of an input of any rank: a classifier
    head on ``(N, d)``, a pointwise (1x1) layer on ``(N, H, W, d)``."""

    features: int
    init_std: Optional[float] = None
    use_bias: bool = True

    def init(self, key, in_shape: Shape):
        d = in_shape[-1]
        wkey, _ = jax.random.split(key)
        params = {"w": _weight(wkey, (d, self.features), d, self.init_std)}
        if self.use_bias:
            params["b"] = jnp.zeros((self.features,), jnp.float32)
        return params, {}, (*in_shape[:-1], self.features)

    def apply(self, params, state, x, train: bool = False):
        y = x @ params["w"].astype(x.dtype)
        if self.use_bias:
            y = y + params["b"].astype(x.dtype)
        return y, state


def _batch_moments(x):
    """Per-channel batch mean and biased variance of ``x`` (N, ..., C) in
    float32, from ONE read of ``x``: E[x] and E[x^2] of every sample over
    its positions — two reductions that need nothing from each other, so
    XLA fuses the pair into the epilogue of the conv that produces ``x``
    and no BatchNorm owns a pass over the activation, forward or backward
    (`jnp.var`'s mean((x - mean)^2) must wait for the mean and read ``x``
    from HBM again, once in each direction). The samples' moments, (N, C),
    are then combined as Chan et al. combine partitions: the spread inside
    each sample plus the spread of the samples' means, so E[x^2] - E[x]^2
    cancels over one sample's positions only and the combination not at
    all. The error in a channel's variance is about 6e-8 * sqrt(positions)
    * mean^2 / var; the clamp covers a constant channel."""
    xf = x.astype(jnp.float32)
    inner = tuple(range(1, x.ndim - 1))
    mean_n = jnp.mean(xf, axis=inner)
    mean2_n = jnp.mean(jnp.square(xf), axis=inner)
    mean = jnp.mean(mean_n, axis=0)
    var = (jnp.mean(mean2_n - jnp.square(mean_n), axis=0)
           + jnp.mean(jnp.square(mean_n - mean), axis=0))
    return mean, jnp.maximum(var, 0.0)


@dataclasses.dataclass(frozen=True)
class BatchNorm(Module):
    """Running-stats batch norm; stats update only when train=True.

    The batch mean/var are global under GSPMD data parallelism (XLA
    all-reduces them when the batch is sharded) — true sync-BN for free.
    Both batch moments come from one read of ``x`` (`_batch_moments`), so
    that they fuse into the conv that produces it and no BatchNorm costs
    a pass over the activation (tests/test_zoo_loader_compile.py).
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
        if train:
            mean, var = _batch_moments(x)
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


@dataclasses.dataclass(frozen=True)
class LayerNorm(Module):
    """Normalise over the LAST axis (channels-last LayerNorm, as ConvNeXt
    applies it at every position), then scale and shift. Mean and variance
    are taken in float32; the output is at ``x.dtype``."""

    eps: float = 1e-6

    def init(self, key, in_shape: Shape):
        c = in_shape[-1]
        params = {
            "scale": jnp.ones((c,), jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32),
        }
        return params, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        return (y * params["scale"] + params["bias"]).astype(x.dtype), state


_SQRT_HALF = 0.7071067811865476
_INV_SQRT_2PI = 0.3989422804014327
# x / sqrt 2 = -4: below it Phi(x) < 8e-9, and a float32 1 + erf has
# stopped moving (at 0 on the TPU, at 1.8e-7 in XLA:CPU's erf).
_GELU_TAIL = -5.656854249492381


@dataclasses.dataclass(frozen=True)
class RMSNorm(Module):
    """Root-mean-square norm over the LAST axis with a learned gain (Zhang
    & Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * scale``. The mean and
    the division are float32 whatever ``x.dtype`` is; the result is cast
    to ``x.dtype`` before the gain, as the published decoder code of the
    models that use it does."""

    eps: float = 1e-5

    def init(self, key, in_shape: Shape):
        return {"scale": jnp.ones((in_shape[-1],), jnp.float32)}, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        xf = x.astype(jnp.float32)
        inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + self.eps)
        return (xf * inv).astype(x.dtype) * params["scale"].astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class Embedding(Module):
    """``rows`` learned vectors of ``features``; integer ids ``(N, ...)`` in,
    ``(N, ..., features)`` out at ``dtype`` (ids carry no float dtype, so
    the layer names the one the model computes at). ``rows`` may be a
    slice of a published vocabulary: the ids are then the slice's own."""

    rows: int
    features: int
    init_std: float = 0.02
    dtype: str = "bfloat16"

    def init(self, key, in_shape: Shape):
        w = self.init_std * jax.random.normal(
            key, (self.rows, self.features), jnp.float32)
        return {"w": w}, {}, (*in_shape, self.features)

    def apply(self, params, state, x, train: bool = False):
        return params["w"][x].astype(self.dtype), state


def gated_unit(a, up, limit: float = 0.0):
    """A gated MLP's inside, ``silu(a) * up()``; with a ``limit`` L > 0,
    ``silu(min(a, L)) * clip(up(), -L, L)`` (0: no limit, and no op for
    one). ``up`` is called after the gate's activation: the order the
    layers' lowered text has had since before the clamp came."""
    if limit > 0:
        return jax.nn.silu(jnp.minimum(a, limit)) * jnp.clip(up(), -limit, limit)
    return jax.nn.silu(a) * up()


@dataclasses.dataclass(frozen=True)
class GatedMLP(Module):
    """``down(silu(gate x) * up x)`` over the last axis (Shazeer 2020,
    "GLU variants"), ``width`` wide inside, no biases; ``limit`` clamps the
    inside (`gated_unit`)."""

    width: int
    init_std: Optional[float] = None
    limit: float = 0.0

    def init(self, key, in_shape: Shape):
        d, f = in_shape[-1], self.width
        kg, ku, kd = jax.random.split(key, 3)
        params = {
            "gate": _weight(kg, (d, f), d, self.init_std),
            "up": _weight(ku, (d, f), d, self.init_std),
            "down": _weight(kd, (f, d), f, self.init_std),
        }
        return params, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        gate, up, down = (
            params[n].astype(x.dtype) for n in ("gate", "up", "down"))
        return gated_unit(x @ gate, lambda: x @ up, self.limit) @ down, state


def causal_conv(x, taps):
    """Causal depthwise 1-D convolution along the axis before the last:
    ``y[..., t, c] = sum_i taps[i, ..., c] * x[..., t - (K - 1) + i, c]``
    with ``x`` read as 0 before position 0, for ``taps (K, ..., c)`` whose
    middle axes broadcast against ``x``'s leading ones (a tap a channel,
    no bias). ``K`` shifted copies and multiply-adds in float32, rounded
    once: at a handful of taps that is all a ``lax.conv`` would do."""
    k, s = taps.shape[0], x.shape[-2]
    xf = x.astype(jnp.float32)
    pad = [(0, 0)] * x.ndim
    pad[-2] = (k - 1, 0)
    back = jnp.pad(xf, pad)
    y = sum(taps[i].astype(jnp.float32)[..., None, :]
            * lax.slice_in_dim(back, i, i + s, axis=x.ndim - 2)
            for i in range(k))
    return y.astype(x.dtype)


def row_major(x):
    """``x`` with its layout in memory pinned to the order of its axes,
    the last one minor. A projection's output that a kernel reads next
    (`rope`'s, the attention cores') is then written that way by the
    matmul itself: left to choose, the TPU's compiler laid ``q (N, H, S,
    D)`` out position-minor for the norm in between and re-laid all of it
    out ahead of the kernel, forward, rematerialised forward and backward
    (PERF.md section 6, PR 42). The cotangent is pinned the same way."""
    return with_layout_constraint(x, Layout(major_to_minor=tuple(range(x.ndim))))


def rope(x, theta: float, positions: Optional[pallas_rope.Axes] = None):
    """Rotary position embedding (Su et al. 2021) of ``x`` ``(..., S, d)``
    along the axis before the last: feature ``i`` is paired with
    ``i + d/2`` (the "rotate half" convention) and the pair turned by
    ``position * theta ** (-2i / d)`` — the position 0..S-1, or, with
    ``positions`` (ops/pallas_rope.py:Axes, static), the one of the axis
    that pair i turns by. Angles and the turn are float32, the result is
    cast back to ``x.dtype``. Shapes that ``pallas_rope.tile`` takes are
    turned by its kernel where the program is lowered for a TPU, and by
    the plain body below everywhere else."""
    if pallas_rope.tile(*x.shape[-2:]) is None:
        return _rope(x, theta, positions)
    return pallas_rope.turn(x, theta, _rope, positions)


def _rope(x, theta: float, positions: Optional[pallas_rope.Axes] = None):
    """`rope`'s plain body: two halves turned and joined, in plain XLA."""
    d = x.shape[-1]
    cos, sin = pallas_rope.cos_sin(x.shape[-2], d, theta, positions)
    xf = x.astype(jnp.float32)
    a, b = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def _normal_cdf(xf):
    """Phi(x) = 0.5 * (1 + erf(x / sqrt 2)) of a float32 array."""
    return 0.5 * (1.0 + lax.erf(xf * _SQRT_HALF))


def _gelu_f32(xf):
    """x * Phi(x) of a float32 array. ``x`` is held at ``_GELU_TAIL`` in the
    product: where a float32 ``erf`` stops a few 1e-8 short of -1, that
    times -1e30 is no longer small, and so the result stays within 6e-7 of
    0 however far ``x`` goes. (A ``max``, not a select on ``x < tail``:
    the backward would share that predicate, and XLA then keeps it from
    the forward as an array of its own.)"""
    return jnp.maximum(xf, _GELU_TAIL) * _normal_cdf(xf)


def _gelu_slope_f32(xf):
    """d/dx of x * Phi(x) = Phi(x) + x * phi(x), phi the normal density,
    of a float32 array."""
    return _normal_cdf(xf) + xf * (_INV_SQRT_2PI * jnp.exp(-0.5 * xf * xf))


@jax.custom_vjp
def _gelu(x):
    return _gelu_f32(x.astype(jnp.float32)).astype(x.dtype)


def _gelu_fwd(x):
    return _gelu(x), x


def _gelu_bwd(x, dy):
    slope = _gelu_slope_f32(x.astype(jnp.float32))
    return ((dy.astype(jnp.float32) * slope).astype(x.dtype),)


_gelu.defvjp(_gelu_fwd, _gelu_bwd)


@dataclasses.dataclass(frozen=True)
class GELU(Module):
    """Exact GELU, ``x * Phi(x)``, as ``0.5 * x * (1 + erf(x / sqrt 2))``:
    the form of the authors' ``torch.nn.GELU`` and of the benchmark's
    float32 reference, not the tanh approximation.

    Why ``erf`` and not ``jax.nn.gelu(approximate=False)``: that is
    ``0.5 * x * erfc(-x / sqrt 2)``, and ``erfc`` is no HLO op. JAX expands
    it, before any backend sees it, into both of its branches and a select
    (an ``exp``, two divides and three polynomials for every element, and
    as much again in the gradient), while ``lax.erf`` stays one native op.
    ConvNeXt-B's pointwise fusions are bound by the vector unit, so those
    ops are step time (PERF.md section 5, bottleneck 7).

    The arithmetic is float32 whatever ``x.dtype`` is, cast once at the
    end. The backward is a ``custom_vjp`` that keeps one residual, the
    input ``x`` at its own dtype, and computes
    ``dx = dy * (Phi(x) + x * phi(x))`` from it (one ``erf``, one ``exp``),
    in float32, cast once. Forward-mode differentiation (``jax.jvp``) is
    not defined for it; no step needs it.

    The far negative tail: in float32 ``1 + erf(z)`` cancels, so below
    x = -5.4 the output is 0 to within 6e-7 where the true value lies
    between -3e-7 and -0, however far ``x`` goes (the guard in
    ``_gelu_f32``); the ``erfc`` form followed the tail down to the
    smallest bf16 numbers. Everywhere else the result before the cast is
    within 5e-7 + 2e-7 |x| of the float64 value (tests/test_convnext.py
    checks every finite bf16 input)."""

    def init(self, key, in_shape: Shape):
        return {}, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        return _gelu(x), state


@dataclasses.dataclass(frozen=True)
class LayerScale(Module):
    """Per-channel learned gain on a residual branch (Touvron et al. 2021),
    initialised small so that a deep net starts near the identity."""

    init_value: float = 1e-6

    def init(self, key, in_shape: Shape):
        c = in_shape[-1]
        return {"gamma": jnp.full((c,), self.init_value, jnp.float32)}, {}, in_shape

    def apply(self, params, state, x, train: bool = False):
        return x * params["gamma"].astype(x.dtype), state


@dataclasses.dataclass(frozen=True)
class DropPath(Module):
    """Stochastic depth (Huang et al. 2016) on a residual branch: in
    training each SAMPLE's branch is dropped with probability ``rate`` and
    the kept ones are scaled by ``1 / (1 - rate)``; the identity when
    ``train=False`` or ``rate == 0``.

    The layer is random in training, and its key lives in its ``state``
    (the Module protocol's slot for non-trainables, as BatchNorm's running
    statistics do): ``init`` stores the raw key data of its init key, and
    each training ``apply`` does exactly

        carry, draw = jax.random.split(jax.random.wrap_key_data(state["key"]))
        keep = jax.random.bernoulli(draw, 1 - rate, (n, 1, ..., 1))
        y = x * keep / (1 - rate);   new state = {"key": key_data(carry)}

    so a train step stays ``(state, x, y)``, gradient accumulation draws a
    fresh mask per microbatch (the state threads through them), a
    checkpoint carries the stream, and a resumed run continues it. The
    plain reference (benchmark/reference/convnext.py) repeats these calls
    to reproduce the masks from the state it is handed."""

    rate: float = 0.0

    def init(self, key, in_shape: Shape):
        return {}, {"key": jax.random.key_data(key)}, in_shape

    def apply(self, params, state, x, train: bool = False):
        if not train or self.rate == 0.0:
            return x, state
        carry, draw = jax.random.split(jax.random.wrap_key_data(state["key"]))
        keep = jax.random.bernoulli(
            draw, 1.0 - self.rate, (x.shape[0],) + (1,) * (x.ndim - 1))
        y = x * (keep.astype(x.dtype) / (1.0 - self.rate))
        return y, {"key": jax.random.key_data(carry)}


def has_random_state(model_state) -> bool:
    """True where a model's state holds a layer's PRNG key data (a
    ``DropPath``): an unsigned-integer leaf. Step builders that average
    the state over shards use this to refuse such a model by name."""
    return any(
        jnp.issubdtype(getattr(leaf, "dtype", jnp.float32), jnp.unsignedinteger)
        for leaf in jax.tree_util.tree_leaves(model_state)
    )


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
