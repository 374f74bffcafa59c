"""Plain reference for the ConvNeXt configurations (Liu et al. 2022,
arXiv:2201.03545, section 2 and Appendix A): forward pass, softmax
cross-entropy, gradients and the AdamW update in straightforward
`jax.numpy`, float32, at the highest matmul precision, by the contract in
`benchmark/reference/__init__.py`. It imports nothing from the program and
nothing from `optax`; it reads the configuration's `arch` and consumes the
program's pytrees as plain nested lists and dicts:

    params = [stem, block * depths[0], down, block * depths[1], ...,
              {}, norm, head]
    stem   = [{"w": HWIO, "b"}, {"scale", "bias"}]       conv, LayerNorm
    down   = [{"scale", "bias"}, {"w": HWIO, "b"}]       LayerNorm, conv
    block  = {"dw": {"w": (k, k, 1, C), "b"}, "norm": {"scale", "bias"},
              "expand": {"w": (C, 4C), "b"}, "reduce": {"w": (4C, C), "b"},
              "scale": {"gamma": (C,)}}
    {}     the global average; norm = {"scale", "bias"}; head = {"w", "b"}
    state mirrors it: [{}, {}] for stem and down, {"drop": {"key": raw
    uint32 key data}} for a block, {} for the last three.

Written as what each layer is, so that the check does not lean on the
primitives of the code under test: a patch conv (kernel == stride) as a
reshape into patches and one matmul; the depthwise conv as the sum of its
k * k shifted slices of the zero-padded input, each times its tap's
per-channel weight; LayerNorm, erf-GELU and AdamW spelled out.

Stochastic depth is random in training. The program keeps each block's
key in the model state and documents the calls it makes
(nn/layers.py:DropPath); `_drop_path` repeats them, so the reference
drops the same samples' branches as the system does and hands on the same
advanced keys.

Departures from the paper, all taken from the program and listed in the
configuration file under `assumed`: no weight decay on parameters of rank
< 2 (the authors' implementation; the paper is silent); drop rates spread
linearly over the blocks from 0 to `drop_path_rate`, one draw per sample,
kept branches scaled by 1 / (1 - rate) (the authors' implementation); no
label smoothing, mixup, cutmix or EMA; a constant learning rate.
"""

from __future__ import annotations

import functools
import json
from typing import List

import jax
import jax.numpy as jnp

F32 = jnp.float32

# The lower-precision control of benchmark/tools/compare_reference.py
# (`--round float8_e4m3fn`): when set, both operands of every matmul and
# conv are rounded through this dtype, forward and backward — the reference
# computed one precision below the bf16 the configuration trains in, which
# a cell's `check` bounds have to call not correct. None in every run of a
# cell. `_programs.cache_clear()` after changing it.
ROUND = None


def _r(a):
    return a if ROUND is None else a.astype(ROUND).astype(F32)


def patch_conv(x, w, b):
    """k x k conv at stride k, no padding: every output pixel is the dot
    product of its own k * k * cin patch with the (k*k*cin, cout) matrix."""
    k, _, cin, cout = w.shape
    n, h, wd, _ = x.shape
    x = x.reshape(n, h // k, k, wd // k, k, cin).transpose(0, 1, 3, 2, 4, 5)
    return _r(x.reshape(n, h // k, wd // k, k * k * cin)) @ _r(w.reshape(-1, cout)) + b


def depthwise_conv(x, w, b):
    """Depthwise k x k conv, stride 1, zero padding k // 2: the sum over
    the k * k taps of the shifted input times that tap's channel weights."""
    k = w.shape[0]
    _, h, wd, _ = x.shape
    pad = k // 2
    xp, w = _r(jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))), _r(w)
    y = jnp.zeros_like(x)
    for i in range(k):
        for j in range(k):
            y = y + xp[:, i:i + h, j:j + wd, :] * w[i, j, 0, :]
    return y + b


def layer_norm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / jnp.sqrt(2.0).astype(F32)))


def _drop_path(key_data, y, rate, train):
    """The program's calls, repeated (nn/layers.py:DropPath). `rate` may
    be traced (a stage's blocks run as one scanned body, below); the
    program's first block has rate 0 and neither masks nor advances its
    key, which the two `where`s keep."""
    if not train:
        return y, key_data
    carry, draw = jax.random.split(jax.random.wrap_key_data(key_data))
    keep = jax.random.bernoulli(draw, 1.0 - rate, (y.shape[0], 1, 1, 1))
    dropped = y * keep.astype(F32) / (1.0 - rate)
    return (jnp.where(rate > 0, dropped, y),
            jnp.where(rate > 0, jax.random.key_data(carry), key_data))


def _block(arch, p, key_data, x, rate, train):
    y = depthwise_conv(x, p["dw"]["w"], p["dw"]["b"])
    y = layer_norm(p["norm"], y, arch["ln_eps"])
    y = gelu(_r(y) @ _r(p["expand"]["w"]) + p["expand"]["b"])
    y = (_r(y) @ _r(p["reduce"]["w"]) + p["reduce"]["b"]) * p["scale"]["gamma"]
    y, key_data = _drop_path(key_data, y, rate, train)
    return x + y, key_data


def drop_rates(arch) -> List[float]:
    total = sum(arch["depths"])
    return [arch["drop_path_rate"] * i / max(total - 1, 1) for i in range(total)]


def _stage(arch, blocks, keys, rates, x, train):
    """The blocks of one stage, one after the other. They have the same
    shapes, so they run as ONE scanned body over their stacked leaves:
    the same arithmetic in the same order as a Python loop, and a program
    a ninth the size (ConvNeXt-B's 36 unrolled blocks of 49 slices each,
    with their gradients, took the chip's compiler five minutes in every
    run's set-up). Returns the output and each block's advanced key."""
    stacked = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *blocks)

    def body(x, block):
        p, key_data, rate = block
        x, key_data = _block(arch, p, key_data, x, rate, train)
        return x, key_data

    # `checkpoint`: the backward pass keeps each block's input and computes
    # the block again (the same numbers), not every intermediate of every
    # block in float32 — those were 11.9 GB reserved on the chip, more than
    # the cell's own step, and made the check the run's memory peak.
    return jax.lax.scan(
        jax.checkpoint(body), x,
        (stacked, jnp.stack(keys), jnp.asarray(rates, F32)))


def forward(arch, params, state, x, train: bool):
    """Logits and the new model state. `x` is NHWC float32."""
    eps = arch["ln_eps"]
    rates = drop_rates(arch)
    new_state = []
    i = 0
    for si, depth in enumerate(arch["depths"]):
        p = params[i]
        if si == 0:
            x = layer_norm(p[1], patch_conv(x, p[0]["w"], p[0]["b"]), eps)
        else:
            x = patch_conv(layer_norm(p[0], x, eps), p[1]["w"], p[1]["b"])
        new_state.append([{}, {}])
        i += 1
        at = i - si - 1  # blocks before this stage
        x, keys = _stage(
            arch, params[i:i + depth],
            [s["drop"]["key"] for s in state[i:i + depth]],
            rates[at:at + depth], x, train)
        new_state += [{"drop": {"key": keys[j]}} for j in range(depth)]
        i += depth
    x = layer_norm(params[i + 1], jnp.mean(x, axis=(1, 2)), eps)
    head = params[i + 2]
    return _r(x) @ _r(head["w"]) + head["b"], new_state + [{}, {}, {}]


def loss_fn(arch, params, state, x, y):
    logits, new_state = forward(arch, params, state, x, True)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), new_state


def adamw_step(arch, params, state, m, v, t, x, y, *, lr, b1, b2, eps,
               weight_decay):
    """One AdamW step (Loshchilov & Hutter 2019), bias-corrected, decay
    decoupled from the gradient and applied to leaves of rank >= 2 only:
        m = b1 m + (1 - b1) g;  v = b2 v + (1 - b2) g^2
        u = (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)
        p -= lr * (u + weight_decay * p)        [decay where p.ndim >= 2]
    Returns (loss before the update, params, state, m, v)."""
    (loss, new_state), grads = jax.value_and_grad(
        functools.partial(loss_fn, arch), has_aux=True)(params, state, x, y)
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def update(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            u = u + weight_decay * p
        return p - lr * u

    return loss, tm(update, params, m, v), new_state, m, v


def _f32(tree):
    """Floating leaves to float32; key data stays what it is."""
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, F32)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else jnp.asarray(a), tree)


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    """The three jitted programs of one architecture, traced once a
    process (a 36-block net with 49 slices a block takes a while to trace).
    The hyperparameters are traced arguments of `step`: one program serves
    every learning rate a caller tries."""
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)
    return {
        "step": jax.jit(functools.partial(adamw_step, arch)),
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "logits": jax.jit(lambda p, s, a: forward(arch, p, s, a, False)[0]),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def _train(arch, params, state, x, y, *, steps, lr, kind, b1, b2, eps,
           weight_decay):
    if kind != "adamw":
        raise ValueError(f"the ConvNeXt reference writes out AdamW, not {kind!r}")
    params, state, x = _f32(params), _f32(state), jnp.asarray(x, F32)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    step = _program(arch, "step")
    losses = []
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            loss, params, state, m, v = step(
                params, state, m, v, jnp.float32(t), x, y, lr=lr, b1=b1,
                b2=b2, eps=eps, weight_decay=weight_decay)
            losses.append(float(loss))
    return losses, params


def train_losses(arch, params, state, x, y, *, steps: int = 2, **hyper):
    """Losses of the first `steps` AdamW steps on one fixed batch, each
    read before its update. `hyper`: lr, kind, b1, b2, eps, weight_decay."""
    return _train(arch, params, state, x, y, steps=steps, **hyper)[0]


def train_params(arch, params, state, x, y, *, steps: int = 2, **hyper):
    """The parameters after `steps` AdamW steps (what the tests compare
    the system's update with)."""
    return _train(arch, params, state, x, y, steps=steps, **hyper)[1]


def loss_and_grads(arch, params, state, x, y):
    """Training-mode loss (masks from the state's keys) and its gradient
    with respect to every parameter leaf."""
    params, state, x = _f32(params), _f32(state), jnp.asarray(x, F32)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = _program(arch, "grads")(params, state, x, y)
    return loss, grads


def eval_logits(arch, params, state, x):
    """Eval-mode logits (no dropped paths)."""
    params, state, x = _f32(params), _f32(state), jnp.asarray(x, F32)
    with jax.default_matmul_precision("highest"):
        return _program(arch, "logits")(params, state, x)
