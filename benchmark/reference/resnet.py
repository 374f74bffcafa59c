"""Plain reference for the ResNet configurations: forward pass, softmax
cross-entropy, gradients and the SGD-momentum update in straightforward
`jax.numpy`, float32, at the highest matmul precision. It imports nothing
from the program; it reads the configuration's `arch` and consumes the
program's parameter pytree as plain nested lists and dicts:

    params = [stem, {}, block, block, ..., {}, head]
    stem / every conv unit = {"conv": {"w": HWIO}, "bn": {"scale", "bias"}}
    block = {"main": [unit, ...], "proj": [unit]}     ("proj" where present)
    head = {"w": (features, classes), "b": (classes,)}
    state mirrors it with {"bn": {"mean", "var"}} per unit.

A convolution is written as what it is — every output pixel the dot
product of its window of the padded input with the weight matrix — so that
the check does not lean on the same conv primitive as the code under test.

Departures from He et al. 2016, all taken from the program and listed in
the configuration files under `assumed`: SAME padding as XLA defines it
(extra padding goes high), the stride of a bottleneck on its 3x3 conv
("v1.5"), weight decay on every parameter.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _same_pad(side: int, k: int, stride: int) -> Tuple[int, int]:
    out = -(-side // stride)
    total = max((out - 1) * stride + k - side, 0)
    return total // 2, total - total // 2


def _windows(x, k: int, stride: int, fill: float):
    """The k*k strided windows of the SAME-padded input, one per kernel
    tap, each (n, out_h, out_w, c), in tap order (row-major)."""
    _, h, wd, _ = x.shape
    ho, wo = -(-h // stride), -(-wd // stride)
    x = jnp.pad(x, ((0, 0), _same_pad(h, k, stride), _same_pad(wd, k, stride),
                    (0, 0)), constant_values=fill)
    return [x[:, i:i + (ho - 1) * stride + 1:stride,
              j:j + (wo - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]


def conv(x, w, stride: int):
    """NHWC x HWIO -> NHWC, SAME padding: every output pixel is the dot
    product of its k*k*cin window with the (k*k*cin, cout) weight matrix."""
    k, _, cin, cout = w.shape
    patches = jnp.concatenate(_windows(x, k, stride, 0.0), axis=-1)
    return patches @ w.reshape(k * k * cin, cout)


def maxpool(x, window: int, stride: int):
    return functools.reduce(jnp.maximum, _windows(x, window, stride, -jnp.inf))


def batchnorm(p, s, x, train: bool, momentum: float, eps: float):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.mean((x - mean) ** 2, axis=(0, 1, 2))
        s = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
             "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var = s["mean"], s["var"]
    y = (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    return y, s


def _unit(p, s, x, stride, train, bn, relu=True, residual=None):
    """conv -> BatchNorm -> (+ residual) -> ReLU."""
    y = conv(x, p["conv"]["w"], stride)
    y, bs = batchnorm(p["bn"], s["bn"], y, train, bn["momentum"], bn["eps"])
    if residual is not None:
        y = y + residual
    if relu:
        y = jnp.maximum(y, 0.0)
    return y, {"bn": bs}


def _block(kind, p, s, x, stride, train, bn):
    new: Dict[str, Any] = {}
    if "proj" in p:
        sc, ps = _unit(p["proj"][0], s["proj"][0], x, stride, train, bn,
                       relu=False)
        new["proj"] = [ps]
    else:
        sc = x
    main, ms = p["main"], s["main"]
    if kind == "bottleneck":
        y, s0 = _unit(main[0], ms[0], x, 1, train, bn)
        y, s1 = _unit(main[1], ms[1], y, stride, train, bn)
        y, s2 = _unit(main[2], ms[2], y, 1, train, bn, residual=sc)
        new["main"] = [s0, s1, s2]
    else:
        y, s0 = _unit(main[0], ms[0], x, stride, train, bn)
        y, s1 = _unit(main[1], ms[1], y, 1, train, bn, residual=sc)
        new["main"] = [s0, s1]
    return y, new


def forward(arch: Dict, params, state, x, train: bool):
    """Logits and the new BatchNorm state. `x` is NHWC float32."""
    bn, stem = arch["bn"], arch["stem"]
    i = 0
    x, s = _unit(params[i], state[i], x, stem["stride"], train, bn)
    new_state = [s]
    i += 1
    if stem.get("maxpool"):
        x = maxpool(x, stem["maxpool"]["window"], stem["maxpool"]["stride"])
        new_state.append({})
        i += 1
    for si, count in enumerate(arch["stage_blocks"]):
        for bi in range(count):
            stride = 2 if (si > 0 and bi == 0) else 1
            x, s = _block(arch["block"], params[i], state[i], x, stride,
                          train, bn)
            new_state.append(s)
            i += 1
    x = jnp.mean(x, axis=(1, 2))
    new_state.append({})
    i += 1
    head = params[i]
    new_state.append({})
    return x @ head["w"] + head["b"], new_state


def loss_fn(arch, params, state, x, y):
    logits, new_state = forward(arch, params, state, x, True)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), new_state


def sgd_step(arch, params, state, mom, x, y, lr, momentum, weight_decay):
    """One SGD-momentum step with L2 weight decay folded into the
    gradient: g += wd * p; m = momentum * m + g; p -= lr * m.
    Returns (loss before the update, params, state, momentum)."""
    (loss, new_state), grads = jax.value_and_grad(
        functools.partial(loss_fn, arch), has_aux=True)(params, state, x, y)
    tm = jax.tree_util.tree_map
    grads = tm(lambda g, p: g + weight_decay * p, grads, params)
    mom = tm(lambda m, g: momentum * m + g, mom, grads)
    params = tm(lambda p, m: p - lr * m, params, mom)
    return loss, params, new_state, mom


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32), tree)


def train_losses(arch, params, state, x, y, *, lr, momentum, weight_decay,
                 steps: int = 2):
    """Losses of the first `steps` SGD steps on one fixed batch."""
    params, state, x = _f32(params), _f32(state), jnp.asarray(x, F32)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = jax.jit(functools.partial(
        sgd_step, arch, lr=lr, momentum=momentum, weight_decay=weight_decay))
    losses = []
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            loss, params, state, mom = step(params, state, mom, x, y)
            losses.append(float(loss))
    return losses


def eval_logits(arch, params, state, x):
    """Eval-mode logits (running BatchNorm statistics)."""
    params, state, x = _f32(params), _f32(state), jnp.asarray(x, F32)
    fwd = jax.jit(lambda p, s, a: forward(arch, p, s, a, False)[0])
    with jax.default_matmul_precision("highest"):
        return fwd(params, state, x)
