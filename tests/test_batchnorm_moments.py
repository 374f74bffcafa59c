"""BatchNorm takes both batch moments from one read of `x`
(`layers._batch_moments`): E[x] and E[x^2] of every sample over its
positions, combined over the batch without further cancellation, so that
the reductions fuse into the conv that produces `x`
(tests/test_compiled_conv_programs.py holds the compiled form). What one read
costs is the cancellation in E[x^2] - E[x]^2 inside a sample, which grows
with a channel's |mean| / std: here the layer is held to the two-pass
float32 formula `mean((x - mean)^2)` over a grid of that ratio, for
float32 and bf16 inputs, in the variance, the output and the input
gradient.

Through ratio 10 (conv outputs ahead of a BatchNorm read 2 to 6 on the
benchmark's data, PERF.md section 6) the bounds are tight; at 100 they
document the cancellation: 6e-8 * 1e4 * sqrt(64 positions) is the 1e-3
the variance is off by there.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from parallel_cnn_tpu.nn import layers

SHAPE = (32, 8, 8, 16)  # 2,048 elements a channel
AXES = (0, 1, 2)
RATIOS = (0, 1, 10, 100)
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}

# against the two-pass value: variance (relative); output (absolute, it is
# O(1)); input gradient (relative to its largest element). About four times
# what this backend reads (1.2e-6 / 1.1e-6 / 1.1e-5 / 1.2e-3 in the variance).
VAR_RTOL = {0: 5e-6, 1: 5e-6, 10: 5e-5, 100: 5e-3}
OUT_ATOL = {0: 1e-5, 1: 2e-5, 10: 2e-4, 100: 2e-2}
GRAD_RTOL = {0: 2e-6, 1: 2e-6, 10: 2e-5, 100: 2e-3}
# bf16: the elementwise arithmetic rounds at 2^-8, so a variance that moved
# in its sixth digit may flip one rounding: one bf16 ulp of an output of
# size <= 8, and the gradient's own rounding (7e-3 read at every ratio)
BF16_OUT_ATOL = 0.0625
BF16_GRAD_RTOL = 0.02


def _two_pass(params, x, eps):
    """The layer as it was: the same elementwise arithmetic at x.dtype,
    statistics by the two-pass float32 formula."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=AXES)
    var = jnp.mean(jnp.square(xf - mean), axis=AXES)
    inv = lax.rsqrt(var + eps) * params["scale"]
    y = ((x - mean.astype(x.dtype)) * inv.astype(x.dtype)
         + params["bias"].astype(x.dtype))
    return y, mean, var


def _case(ratio, dtype, seed=0):
    rng = np.random.default_rng(seed)
    c = SHAPE[-1]
    std = np.linspace(0.5, 4.0, c).astype(np.float32)
    sign = np.where(np.arange(c) % 2, 1.0, -1.0).astype(np.float32)
    x = rng.standard_normal(SHAPE).astype(np.float32) * std + ratio * std * sign
    params = {
        "scale": jnp.asarray(rng.uniform(0.5, 1.5, c).astype(np.float32)),
        "bias": jnp.asarray(rng.standard_normal(c).astype(np.float32)),
    }
    cot = jnp.asarray(rng.standard_normal(SHAPE).astype(np.float32))
    return params, jnp.asarray(x).astype(dtype), cot.astype(dtype)


def _f32(a):
    return np.asarray(a, np.float32)


grid = pytest.mark.parametrize("ratio", RATIOS)
dtypes = pytest.mark.parametrize("dtype", list(DTYPES))


@grid
@dtypes
def test_the_variance_is_the_two_pass_variance(ratio, dtype):
    bn = layers.BatchNorm(momentum=0.0)  # new state = the batch statistics
    params, x, _ = _case(ratio, DTYPES[dtype])
    state = bn.init(jax.random.key(0), SHAPE[1:])[1]
    _, new = bn.apply(params, state, x, train=True)
    _, mean, var = _two_pass(params, x, bn.eps)
    np.testing.assert_allclose(_f32(new["mean"]), _f32(mean), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(_f32(new["var"]), _f32(var), rtol=VAR_RTOL[ratio])
    assert float(jnp.min(new["var"])) >= 0.0


@grid
@dtypes
def test_the_output_is_the_two_pass_output(ratio, dtype):
    bn = layers.BatchNorm()
    params, x, _ = _case(ratio, DTYPES[dtype])
    state = bn.init(jax.random.key(0), SHAPE[1:])[1]
    y, _ = bn.apply(params, state, x, train=True)
    want, _, _ = _two_pass(params, x, bn.eps)
    assert y.dtype == x.dtype
    atol = OUT_ATOL[ratio] if dtype == "f32" else BF16_OUT_ATOL
    np.testing.assert_allclose(_f32(y), _f32(want), atol=atol, rtol=0)


@grid
@dtypes
def test_the_input_gradient_is_the_two_pass_gradient(ratio, dtype):
    bn = layers.BatchNorm()
    params, x, cot = _case(ratio, DTYPES[dtype])
    state = bn.init(jax.random.key(0), SHAPE[1:])[1]

    def through(f):
        return jax.grad(
            lambda x: jnp.sum((f(x) * cot).astype(jnp.float32)))(x)

    got = through(lambda x: bn.apply(params, state, x, train=True)[0])
    want = through(lambda x: _two_pass(params, x, bn.eps)[0])
    assert got.dtype == x.dtype
    rtol = GRAD_RTOL[ratio] if dtype == "f32" else BF16_GRAD_RTOL
    np.testing.assert_allclose(
        _f32(got), _f32(want), atol=rtol * float(np.max(np.abs(_f32(want)))), rtol=0)


@pytest.mark.parametrize("value", [0.0, 3.0, -1e3, 65504.0])
@dtypes
def test_a_constant_channel_has_variance_zero_not_less(value, dtype):
    """E[x^2] - E[x]^2 of a constant rounds to either side of 0: the clamp
    keeps rsqrt(var + eps) real, and the output is the bias."""
    bn = layers.BatchNorm(momentum=0.0)
    params, state, _ = bn.init(jax.random.key(0), SHAPE[1:])
    params = dict(params, bias=jnp.full((SHAPE[-1],), 0.5, jnp.float32))
    x = jnp.full(SHAPE, value, DTYPES[dtype])
    y, new = bn.apply(params, state, x, train=True)
    var = _f32(new["var"])
    assert (var >= 0.0).all()
    assert (var <= 1e-6 * max(1.0, value * value)).all()
    assert np.isfinite(_f32(y)).all()
    np.testing.assert_allclose(_f32(y), 0.5, atol=1e-2 if value else 0)


@pytest.mark.parametrize("momentum", [0.9, 0.99, 0.0])
def test_the_running_statistics_follow_the_batch_statistics(momentum):
    bn = layers.BatchNorm(momentum=momentum)
    params, x, _ = _case(1, jnp.float32, seed=1)
    state = {"mean": jnp.full((SHAPE[-1],), 2.0), "var": jnp.full((SHAPE[-1],), 3.0)}
    _, new = bn.apply(params, state, x, train=True)
    _, mean, var = _two_pass(params, x, bn.eps)
    np.testing.assert_allclose(
        _f32(new["mean"]), momentum * 2.0 + (1 - momentum) * _f32(mean), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        _f32(new["var"]), momentum * 3.0 + (1 - momentum) * _f32(var), rtol=1e-4)
    # eval reads them and leaves them alone
    y, same = bn.apply(params, new, x, train=False)
    assert same is new
    want = (x - new["mean"]) * lax.rsqrt(new["var"] + bn.eps) * params["scale"] + params["bias"]
    np.testing.assert_allclose(_f32(y), _f32(want), atol=1e-5)


@pytest.mark.parametrize("shape", [(64, 16), (16, 12, 16), (8, 4, 6, 16), (1, 8, 8, 16)],
                         ids=["rows", "sequence", "image", "one-sample"])
def test_any_rank_reduces_over_all_but_the_last_axis(shape):
    """(N, C) has no positions inside a sample: the combination over the
    batch is then the whole formula, and it is the two-pass one."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 2.0 + 3.0)
    mean, var = layers._batch_moments(x)
    axes = tuple(range(len(shape) - 1))
    np.testing.assert_allclose(_f32(mean), _f32(jnp.mean(x, axis=axes)), rtol=1e-5)
    np.testing.assert_allclose(_f32(var), _f32(jnp.var(x, axis=axes)), rtol=2e-5)
