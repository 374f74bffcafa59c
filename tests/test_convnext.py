"""ConvNeXt in the program: the layers it forced into nn/layers.py
(grouped conv, Dense over the last axis, LayerNorm, GELU, LayerScale,
DropPath with its key in the layer's state), nn/convnext.py at the
published size, AdamW in zoo.make_optimizer, and what must not have moved
for the models that were there (the lowered step of the ResNets and VGG).
The comparison with the plain reference is tests/benchmark/
test_convnext_reference.py."""

import collections
import functools
import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from parallel_cnn_tpu import config as config_lib
from parallel_cnn_tpu import plan as plan_lib
from parallel_cnn_tpu.nn import convnext, layers, resnet, vgg
from parallel_cnn_tpu.nn.core import Sequential
from parallel_cnn_tpu.nn.layers import (
    GELU,
    Conv2D,
    Dense,
    DropPath,
    GlobalAvgPool,
    LayerNorm,
    LayerScale,
    has_random_state,
)
from parallel_cnn_tpu.train import checkpoint, zoo

TINY = dict(depths=(1, 1, 2, 1), dims=(8, 16, 32, 64), num_classes=10)


def _plain_reference():
    """benchmark/reference/convnext.py: plain float32, imports nothing of
    the program."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark.reference import convnext as reference
    return reference


def tiny(drop_path_rate=0.5, layer_scale_init=1.0):
    return convnext.convnext(**TINY, drop_path_rate=drop_path_rate,
                             layer_scale_init=layer_scale_init)


# ------------------------------------------------------------ grouped conv

def _grouped_conv(x, w, groups):
    """SAME k x k conv, stride 1, as the sum over taps and groups of the
    shifted input's group channels times that tap's (cin/g, cout/g) matrix."""
    k, _, cg, cout = w.shape
    n, h, wd, _ = x.shape
    og = cout // groups
    xp = jnp.pad(x, ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0)))
    outs = []
    for g in range(groups):
        y = jnp.zeros((n, h, wd, og), x.dtype)
        for i in range(k):
            for j in range(k):
                y = y + (xp[:, i:i + h, j:j + wd, g * cg:(g + 1) * cg]
                         @ w[i, j, :, g * og:(g + 1) * og])
        outs.append(y)
    return jnp.concatenate(outs, axis=-1)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_grouped_conv_forward_and_both_gradients_match_the_sum_of_slices(groups):
    c = 8
    layer = Conv2D(c, kernel=(7, 7), groups=groups)
    params, state, out_shape = layer.init(jax.random.key(0), (12, 12, c))
    assert params["w"].shape == (7, 7, c // groups, c) and out_shape == (12, 12, c)
    x = jax.random.normal(jax.random.key(1), (2, 12, 12, c))
    dy = jax.random.normal(jax.random.key(2), (2, 12, 12, c))

    def system(w, x):
        return layer.apply({"w": w, "b": params["b"]}, state, x)[0]

    def plain(w, x):
        return _grouped_conv(x, w, groups) + params["b"]

    got, vjp = jax.vjp(jax.jit(system), params["w"], x)
    want, ref_vjp = jax.vjp(jax.jit(plain), params["w"], x)
    # float32 sums of 49 * cin/groups products in another order: 1e-5
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(vjp(dy), ref_vjp(dy)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)


def test_groups_that_do_not_divide_are_refused_at_init():
    with pytest.raises(ValueError, match="groups=3"):
        Conv2D(8, groups=3).init(jax.random.key(0), (4, 4, 6))
    with pytest.raises(ValueError, match="groups=4"):
        Conv2D(6, groups=4).init(jax.random.key(0), (4, 4, 8))


def test_a_grouped_conv_on_the_pallas_backend_raises_and_does_not_fall_back():
    layer = Conv2D(8, kernel=(3, 3), groups=8, backend="pallas")
    params, state, _ = layer.init(jax.random.key(0), (8, 8, 8))
    with pytest.raises(ValueError, match="groups=8"):
        layer.apply(params, state, jnp.zeros((1, 8, 8, 8)))


# ------------------------------------------------ the other new layers

def test_dense_applies_over_the_last_axis_of_any_rank():
    layer = Dense(5)
    params, _, shape = layer.init(jax.random.key(0), (3, 4, 7))
    assert params["w"].shape == (7, 5) and shape == (3, 4, 5)
    x = jax.random.normal(jax.random.key(1), (2, 3, 4, 7))
    y, _ = layer.apply(params, {}, x)
    np.testing.assert_allclose(
        y, jnp.einsum("nhwc,cf->nhwf", x, params["w"]) + params["b"], rtol=1e-6)
    flat, _, shape1 = layer.init(jax.random.key(0), (7,))
    assert shape1 == (5,)  # a 1-D input as before, same draw
    np.testing.assert_array_equal(flat["w"], params["w"])


def test_layernorm_normalises_the_last_axis_in_float32():
    layer = LayerNorm(1e-6)
    params, state, _ = layer.init(jax.random.key(0), (4, 4, 16))
    params = {"scale": params["scale"] * 1.5, "bias": params["bias"] + 0.25}
    x = 3.0 + 2.0 * jax.random.normal(jax.random.key(1), (2, 4, 4, 16))
    y, _ = layer.apply(params, state, x)
    xn = np.asarray(x, np.float64)
    want = ((xn - xn.mean(-1, keepdims=True))
            / np.sqrt(xn.var(-1, keepdims=True) + 1e-6) * 1.5 + 0.25)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    yb, _ = layer.apply(params, state, x.astype(jnp.bfloat16))
    assert yb.dtype == jnp.bfloat16 and state == {}
    np.testing.assert_allclose(yb.astype(np.float32), want, atol=0.05)


def test_gelu_is_the_erf_form():
    x = jnp.linspace(-4.0, 4.0, 33)
    y, _ = GELU().apply({}, {}, x)
    want = [0.5 * v * (1 + math.erf(v / math.sqrt(2))) for v in np.asarray(x)]
    np.testing.assert_allclose(y, want, atol=1e-6)
    tanh = jax.nn.gelu(x, approximate=True)
    assert float(jnp.max(jnp.abs(y - tanh))) > 1e-4  # not the approximation


# ------------------- GELU: one erf in float32, and a backward of its own

_erfc = np.vectorize(math.erfc)


def _every_finite_bf16():
    x = np.arange(1 << 16, dtype=np.uint16).view(jnp.bfloat16)
    return x[np.isfinite(x.astype(np.float32))]


def _gelu_f64(x):
    """x * Phi(x) in float64; the erfc form follows the negative tail."""
    return 0.5 * x * _erfc(-x / math.sqrt(2.0))


def _gelu_slope_f64(x):
    return (0.5 * _erfc(-x / math.sqrt(2.0))
            + x * np.exp(-0.5 * x * x) / math.sqrt(2.0 * math.pi))


def _forward_excess(form):
    """By how much ``form`` (float32 in, float32 out) misses float64 GELU
    beyond what the layer promises before its cast, over every finite bf16
    input: 5e-7 + 2e-7 |x| for x >= 0, 1e-6 absolute for x < 0."""
    xb = _every_finite_bf16()
    assert xb.size == 65280
    x = xb.astype(np.float64)
    got = np.asarray(jax.jit(form)(jnp.asarray(xb.astype(np.float32))), np.float64)
    allowed = np.where(x >= 0, 5e-7 + 2e-7 * np.abs(x), 1e-6)
    return np.abs(got - _gelu_f64(x)) - allowed


def test_gelu_before_its_cast_is_float64_gelu_on_every_finite_bf16_input():
    assert float(np.max(_forward_excess(layers._gelu_f32))) <= 0.0
    # the guard: a float32 erf stops short of -1, and times -1e30 that shows
    # (XLA:CPU's 1 + erf(-large) is 1.8e-7: -9e22 without the guard)
    far = layers._gelu_f32(jnp.asarray([-1e30, -3e38, -6.0], jnp.float32))
    assert float(jnp.max(jnp.abs(far))) <= 1e-6


def test_the_tanh_form_fails_the_forward_bound_the_erf_form_passes():
    excess = _forward_excess(lambda x: jax.nn.gelu(x, approximate=True))
    assert float(np.max(excess)) > 1e-4  # 3e-4 off near |x| = 2


def test_gelu_after_its_cast_is_the_correctly_rounded_bf16_value():
    """For every input >= -4 within one bf16 ulp, and equal on >= 99.9 %
    (XLA flushes subnormal results to zero on the CPU and on the chip, so
    the float64 value is flushed the same way); below -4 what a float32
    1 + erf leaves."""
    xb = _every_finite_bf16()
    x = xb.astype(np.float64)
    y, _ = jax.jit(lambda v: GELU().apply({}, {}, v))(jnp.asarray(xb))
    assert y.dtype == jnp.bfloat16
    y = np.asarray(y).astype(np.float64)
    tiny = 2.0 ** -126
    want = _gelu_f64(x)
    ref = np.where(np.abs(want) < tiny, 0.0, want).astype(jnp.bfloat16).astype(np.float64)
    ref = np.where(np.abs(ref) < tiny, 0.0, ref)
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), tiny))) - 7)
    body = x >= -4.0
    assert np.all(np.abs(y - ref)[body] <= ulp[body])
    assert np.mean((y == ref)[body]) >= 0.999
    assert float(np.max(np.abs(y - want)[~body])) <= 1e-6


def test_gelu_derivative_before_its_cast_is_float64_on_every_finite_bf16_input():
    xb = _every_finite_bf16()
    got = jax.jit(layers._gelu_slope_f32)(jnp.asarray(xb.astype(np.float32)))
    want = _gelu_slope_f64(xb.astype(np.float64))
    assert float(np.max(np.abs(np.asarray(got, np.float64) - want))) <= 5e-7


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_backward_of_gelu_is_its_own_and_casts_once(dtype):
    """The cotangent is dy * (Phi(x) + x phi(x)) computed in float32 from
    the saved input, at the input's dtype."""
    x = jnp.asarray(_every_finite_bf16()).astype(dtype)
    dy = jnp.linspace(-2.0, 2.0, x.size).astype(dtype)
    y, vjp = jax.vjp(lambda v: GELU().apply({}, {}, v)[0], x)
    (dx,) = vjp(dy)
    assert dx.dtype == dtype and y.dtype == dtype
    want = dy.astype(jnp.float32) * layers._gelu_slope_f32(x.astype(jnp.float32))
    np.testing.assert_array_equal(dx, want.astype(dtype))


def test_gelu_gradient_in_float32_is_the_reference_gradient():
    reference = _plain_reference()
    x = jnp.linspace(-9.0, 9.0, 3601)
    got = jax.grad(lambda v: jnp.sum(GELU().apply({}, {}, v)[0]))(x)
    want = jax.grad(lambda v: jnp.sum(reference.gelu(v)))(x)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    y, _ = GELU().apply({}, {}, x)
    np.testing.assert_allclose(y, reference.gelu(x), rtol=0, atol=1e-6)


def test_forward_mode_through_gelu_is_a_clear_error():
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda v: GELU().apply({}, {}, v)[0],
                (jnp.ones(3),), (jnp.ones(3),))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gelu_saves_its_input_and_nothing_else(dtype):
    x = jnp.ones((2, 4, 4, 8), dtype)
    _, vjp = jax.vjp(lambda v: GELU().apply({}, {}, v)[0], x)
    saved = jax.tree_util.tree_leaves(vjp)
    assert [(r.shape, r.dtype) for r in saved] == [(x.shape, x.dtype)]
    # what autodiff keeps of the erfc form: three arrays of that size
    _, vjp = jax.vjp(lambda v: jax.nn.gelu(v, approximate=False), x)
    assert sum(r.shape == x.shape for r in jax.tree_util.tree_leaves(vjp)) == 3


def test_layerscale_is_one_gain_a_channel_from_its_initial_value():
    params, state, shape = LayerScale(1e-6).init(jax.random.key(0), (4, 4, 8))
    assert state == {} and shape == (4, 4, 8)
    np.testing.assert_array_equal(params["gamma"], np.full(8, 1e-6, np.float32))
    x = jnp.ones((2, 4, 4, 8))
    y, _ = LayerScale().apply({"gamma": jnp.arange(8.0)}, {}, x)
    np.testing.assert_array_equal(y[0, 0, 0], np.arange(8.0))


# ----------------------------------------------------------------- DropPath

def _drop(rate=0.5, n=64, seed=3):
    layer = DropPath(rate)
    _, state, _ = layer.init(jax.random.key(seed), (2, 2, 4))
    return layer, state, jnp.ones((n, 2, 2, 4))


def test_droppath_is_the_identity_in_evaluation_and_at_rate_zero():
    layer, state, x = _drop(0.5)
    y, s = layer.apply({}, state, x, train=False)
    assert y is x and s is state
    zero, state0, x = _drop(0.0)
    y, s = zero.apply({}, state0, x, train=True)
    assert y is x and s is state0


def test_droppath_drops_whole_samples_and_rescales_the_kept_ones():
    layer, state, x = _drop(0.25, n=4096)
    y, new = layer.apply({}, state, x, train=True)
    per_sample = np.asarray(y).reshape(4096, -1)
    assert set(np.unique(per_sample)) == {0.0, np.float32(1 / 0.75)}
    assert (per_sample.min(1) == per_sample.max(1)).all()  # one draw a sample
    assert abs((per_sample[:, 0] > 0).mean() - 0.75) < 0.03
    assert abs(float(y.mean()) - 1.0) < 0.04  # the expectation is kept
    assert new["key"].dtype == jnp.uint32 and has_random_state(new)


def test_droppath_same_key_same_masks_and_the_next_step_draws_others():
    layer, state, x = _drop()
    y1, s1 = layer.apply({}, state, x, train=True)
    again, s1b = layer.apply({}, state, x, train=True)
    np.testing.assert_array_equal(y1, again)
    np.testing.assert_array_equal(s1["key"], s1b["key"])
    y2, s2 = layer.apply({}, s1, x, train=True)
    assert not np.array_equal(y1, y2)
    assert not np.array_equal(s1["key"], s2["key"])
    assert not np.array_equal(state["key"], s1["key"])


def test_droppath_follows_the_calls_its_docstring_writes_down():
    layer, state, x = _drop(0.5, n=16)
    carry, draw = jax.random.split(jax.random.wrap_key_data(state["key"]))
    keep = jax.random.bernoulli(draw, 0.5, (16, 1, 1, 1))
    y, new = layer.apply({}, state, x, train=True)
    np.testing.assert_array_equal(y, x * keep / 0.5)
    np.testing.assert_array_equal(new["key"], jax.random.key_data(carry))


def _keys(model_state):
    return [np.asarray(s["drop"]["key"]) for s in model_state
            if isinstance(s, dict) and "drop" in s]


@pytest.fixture(scope="module")
def stepped():
    """`keys(accum, steps)`: the DropPaths' keys of `tiny()` as `init_state`
    drew them (`steps` 0) or after `steps` train steps of `accum`
    microbatches on one batch: each run made once, and one step program an
    `accum`, for the tests that read them."""
    model = tiny()
    opt = zoo.make_optimizer(lr=1e-3, kind="adamw")
    x = jax.random.normal(jax.random.key(2), (8, 32, 32, 3))
    y = jnp.arange(8) % 10
    program = functools.lru_cache(maxsize=None)(
        lambda accum: zoo.make_train_step(model, opt, accum, None))

    @functools.lru_cache(maxsize=None)
    def keys(accum, steps):
        state = zoo.init_state(model, jax.random.key(1), (32, 32, 3), opt)
        assert has_random_state(state.model_state)
        for _ in range(steps):
            state, _ = program(accum)(state, x, y)  # (state, x, y): no key argument
        return _keys(state.model_state)

    return keys


def test_the_train_step_threads_the_keys_and_keeps_its_signature(stepped):
    start, after = stepped(1, 0), stepped(1, 1)
    assert len(start) == 5
    np.testing.assert_array_equal(after[0], start[0])  # block 1: rate 0
    assert all(not np.array_equal(a, b) for a, b in zip(after[1:], start[1:]))


def test_accumulation_draws_a_fresh_mask_for_each_microbatch(stepped):
    """accum_steps=2 advances every key twice: the second microbatch is
    drawn from the state the first one returned."""
    twice = stepped(1, 2)
    for a, b in zip(stepped(2, 1), twice):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(twice[1], stepped(1, 1)[1])


def test_the_keys_round_trip_through_a_checkpoint(tmp_path):
    model = tiny()
    opt = zoo.make_optimizer(lr=1e-3, kind="adamw")
    state = zoo.init_state(model, jax.random.key(1), (32, 32, 3), opt)
    path = str(tmp_path / "ckpt_1.npz")
    checkpoint.save(path, state, checkpoint.TrainState(epoch=1))
    like = zoo.init_state(model, jax.random.key(2), (32, 32, 3), opt)
    assert not np.array_equal(_keys(like.model_state)[1],
                              _keys(state.model_state)[1])
    back, tstate = checkpoint.restore(path, like)
    assert tstate.epoch == 1
    for a, b in zip(_keys(back.model_state), _keys(state.model_state)):
        assert a.dtype == np.uint32
        np.testing.assert_array_equal(a, b)


def test_a_killed_and_resumed_run_continues_the_mask_stream(tmp_path):
    """As the ResNet case in tests/test_zoo.py: killed after epoch 1 and
    resumed, a tiny ConvNeXt under AdamW lands on the uninterrupted run —
    which it can only do if the checkpoint carried the DropPath keys."""
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar

    imgs, labels = synthetic.make_image_dataset(64, seed=4)
    model = tiny()
    kw = dict(in_shape=cifar.IN_SHAPE, batch_size=16, lr=1e-3, kind="adamw",
              weight_decay=0.05, seed=9, verbose=False)
    continuous, c_losses = zoo.train(model, imgs, labels, epochs=2, **kw)
    ckpt = str(tmp_path / "ckpts")
    zoo.train(model, imgs, labels, epochs=1, checkpoint_dir=ckpt, **kw)
    resumed, r_losses = zoo.train(model, imgs, labels, epochs=2,
                                  checkpoint_dir=ckpt, resume=True, **kw)
    np.testing.assert_allclose(r_losses, c_losses, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(continuous),
                    jax.tree_util.tree_leaves(resumed), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)
    # and another stream gives another run: the masks matter at gamma = 1
    other, o_losses = zoo.train(model, imgs, labels, epochs=2,
                                **dict(kw, seed=10))
    assert abs(o_losses[1] - c_losses[1]) > 1e-4


@pytest.mark.parametrize("impl", ["psum", "ring"])
def test_the_explicit_collective_step_refuses_a_random_layer_by_name(
        host_devices, impl):
    model = tiny()
    opt = zoo.make_optimizer(lr=1e-3, kind="adamw")
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    state = zoo.init_state(model, jax.random.key(1), (32, 32, 3), opt)
    step = zoo.make_train_step(model, opt, 1, mesh,
                               comm=config_lib.CommConfig(impl=impl))
    x = jnp.zeros((4, 32, 32, 3))
    with pytest.raises(zoo.RandomLayerUnsupported, match="DropPath"):
        step(state, x, jnp.zeros((4,), jnp.int32))
    # the GSPMD step on the same mesh runs it
    gspmd = zoo.make_train_step(model, opt, 1, mesh)
    _, loss = gspmd(state, x, jnp.zeros((4,), jnp.int32))
    assert math.isfinite(float(loss))


# ------------------------------------------------- the model as published

@pytest.fixture(scope="module")
def published():
    model = convnext.convnext_b()
    shapes = jax.eval_shape(lambda k: model.init(k, (224, 224, 3)),
                            jax.random.key(0))
    return model, shapes


def test_convnext_b_has_the_published_parameters_and_shape(published):
    model, (params, state, _) = published
    count = sum(l.size for l in jax.tree_util.tree_leaves(params))
    assert count == 88_591_464
    blocks = [l for l in model.layers if isinstance(l, convnext.Block)]
    assert [sum(b.features == d for b in blocks)
            for d in (128, 256, 512, 1024)] == [3, 3, 27, 3]
    assert len(_keys_shape(state)) == 36
    assert params[-1]["w"].shape == (1024, 1000)
    assert params[0][0]["w"].shape == (4, 4, 3, 128)
    assert params[1]["dw"]["w"].shape == (7, 7, 1, 128)
    assert params[1]["expand"]["w"].shape == (128, 512)
    assert params[1]["reduce"]["w"].shape == (512, 128)


def _keys_shape(state):
    return [s["drop"]["key"] for s in state if isinstance(s, dict) and "drop" in s]


def test_convnext_b_names_its_layers_as_the_benchmark_lists_them(published):
    model, _ = published
    names = model.scope_names()
    assert names[:2] == ["stem", "s1b1"] and names[-3:] == ["gap", "norm", "fc"]
    assert [n for n in names if n.startswith("down")] == ["down2", "down3", "down4"]
    assert names.index("down3") + 27 == names.index("s3b27")
    assert model.layers[1]._branch().scope_names() == [
        "dw", "norm", "expand", "act", "reduce", "scale", "drop"]


def test_convnext_b_spreads_stochastic_depth_linearly_to_one_half(published):
    model, _ = published
    rates = [l.drop_rate for l in model.layers if isinstance(l, convnext.Block)]
    assert rates[0] == 0.0 and rates[-1] == 0.5 and len(rates) == 36
    np.testing.assert_allclose(rates, np.linspace(0.0, 0.5, 36), atol=1e-12)


def test_convnext_initialises_as_published():
    model = tiny(layer_scale_init=1e-6)
    params, _, _ = model.init(jax.random.key(0), (32, 32, 3))
    block = params[5]  # s3b1, width 32
    np.testing.assert_array_equal(block["scale"]["gamma"],
                                  np.full(32, 1e-6, np.float32))
    assert all(float(jnp.abs(block[k]["b"]).max()) == 0.0
               for k in ("dw", "expand", "reduce"))
    big = convnext.Block(256)
    p, _, _ = big.init(jax.random.key(1), (4, 4, 256))
    assert abs(float(p["expand"]["w"].std()) - 0.02) < 5e-4
    assert abs(float(p["reduce"]["w"].std()) - 0.02) < 5e-4


def test_a_block_keeps_its_width_and_its_evaluation_is_deterministic():
    with pytest.raises(ValueError, match="keeps its width"):
        convnext.Block(16).init(jax.random.key(0), (4, 4, 8))
    model = tiny()
    params, state, shape = model.init(jax.random.key(0), (32, 32, 3))
    assert shape == (10,)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    a, s = model.apply(params, state, x, train=False)
    b, _ = model.apply(params, s, x, train=False)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(_keys(s)[1], _keys(state)[1])


# -------------------------------------------------------------- optimizer

def test_adamw_matches_a_hand_written_update_on_a_three_leaf_tree():
    hyper = dict(lr=3e-3, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.05)
    opt = zoo.make_optimizer(kind="adamw", **hyper)
    rng = np.random.default_rng(0)
    params = {"w": rng.normal(size=(3, 4)), "b": rng.normal(size=(4,)),
              "k": rng.normal(size=(2, 2, 1, 4))}
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    grads = [jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), jnp.float32), params)
        for _ in range(3)]
    state = opt.init(params)
    got = params
    want = {k: np.asarray(v, np.float64) for k, v in params.items()}
    m = {k: np.zeros_like(v) for k, v in want.items()}
    v = {k: np.zeros_like(v) for k, v in want.items()}
    for t, g in enumerate(grads, start=1):
        updates, state = opt.update(g, state, got)
        got = optax.apply_updates(got, updates)
        for k in want:
            gk = np.asarray(g[k], np.float64)
            m[k] = 0.9 * m[k] + 0.1 * gk
            v[k] = 0.999 * v[k] + 0.001 * gk * gk
            u = (m[k] / (1 - 0.9 ** t)) / (np.sqrt(v[k] / (1 - 0.999 ** t)) + 1e-8)
            if want[k].ndim >= 2:  # decoupled decay, not on the bias
                u = u + 0.05 * want[k]
            want[k] = want[k] - 3e-3 * u
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=2e-6, atol=2e-7)


def test_an_unknown_optimizer_kind_is_a_value_error_that_names_the_known():
    with pytest.raises(ValueError, match=r"'lion'.*sgd, adamw"):
        zoo.make_optimizer(kind="lion")
    assert zoo.OPTIMIZERS == ("sgd", "adamw")


def test_sgd_is_still_what_no_kind_gives():
    params = {"w": jnp.ones((2, 2))}
    grads = {"w": jnp.full((2, 2), 0.5)}
    for opt in (zoo.make_optimizer(0.1, 0.9, 1e-4),
                zoo.make_optimizer(0.1, 0.9, 1e-4, kind="sgd")):
        updates, _ = opt.update(grads, opt.init(params), params)
        np.testing.assert_allclose(updates["w"], -0.1 * (0.5 + 1e-4), rtol=1e-6)


@pytest.mark.parametrize("zero", [2, 3])
def test_update_on_arrival_and_zero_refuse_another_kind_by_name(
        host_devices, zero):
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar

    imgs, labels = synthetic.make_image_dataset(16, seed=1)
    mesh = plan_lib.ExecutionPlan(data=2).validate().make_mesh(
        devices=host_devices[:2])
    with pytest.raises(ValueError, match=r"kind='adamw'"):
        zoo.train(cifar.cifar_cnn(), imgs, labels, in_shape=cifar.IN_SHAPE,
                  batch_size=8, kind="adamw", mesh=mesh,
                  comm=config_lib.CommConfig(impl="ring"),
                  fused=config_lib.FusedStepConfig(update=True, zero=zero),
                  verbose=False)


def test_train_hands_the_optimizer_keywords_on_and_journals_the_optimizer(tmp_path):
    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar

    class Journal:
        enabled = True

        def __init__(self):
            self.events = []

        def emit(self, kind, **fields):
            self.events.append((kind, fields))

    journal = Journal()
    obs = obs_lib.Obs(obs_lib.Tracer(), obs_lib.MetricsRegistry(), journal,
                      enabled=True)
    imgs, labels = synthetic.make_image_dataset(16, seed=1)
    model = tiny()
    state, losses = zoo.train(
        model, imgs, labels, in_shape=cifar.IN_SHAPE, batch_size=8, lr=1e-3,
        kind="adamw", b1=0.8, b2=0.99, eps=1e-6, weight_decay=0.05,
        verbose=False, obs=obs)
    assert math.isfinite(losses[0])
    adam = [s for s in jax.tree_util.tree_leaves(
        state.opt_state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    assert len(adam) == 1 and int(adam[0].count) == 2
    (fields,) = [f for k, f in journal.events if k == "zoo_optimizer"]
    n = sum(p.size for p in jax.tree_util.tree_leaves(state.params))
    assert fields["optimizer"] == "adamw" and fields["params"] == n
    assert fields["state_bytes"] >= 2 * 4 * n  # two float32 moments


# ------------------- what was there lowers to the program it lowered to

# sha256 of `make_train_step(...).lower(...).as_text()` (StableHLO without
# locations: what JAX's compile cache keys on, scopes and line numbers
# stripped), under this suite's 8 virtual CPU devices. First read at the
# parent of the PR that brought ConvNeXt (5efafec): `Conv2D` and `Dense`
# gained arguments there, and with their defaults every model that was
# there must trace to the same program, or the benchmark's existing cells
# recompile and may move. A PR that changes the step on purpose updates
# these and says so in PERF.md: PR 28 did, for the four models with a
# BatchNorm (both moments from one read of the activation), and added
# `convnext_tiny`, which has none and read the same at PR 28's parent
# (abfaa9e) — its cell was that PR's control. PR 30 changed
# `convnext_tiny` on purpose (GELU as one float32 `erf` with a backward of
# its own, nn/layers.py:GELU; c52d3188… before) and left the other four,
# which hold no GELU, as they read: they are that PR's control.
LOWERED = {
    "resnet18": "dd1a211746370c7de65724ed87ce673a3640d9bc399944d46c45cd1a618feed5",
    "resnet18_dp4": "8602311ce3688ba0ed93997fbccec2c92b94b657c16226be05baeb40ba0368b6",
    "resnet50_accum2": "a9efdb2e8ad0bcb697939f6644079d82ca7482d250ff19a193d5b7e606b2e4df",
    "vgg16": "bb89676f38d9e22aaf08652e497b0d0ff7a48c9d68519804518b43a196b3e33a",
    "convnext_tiny": "8550d8b7c25f69a2420d6513ccdfe41887e70a1aec3e4f9283fff7906ea18feb",
}


def _lower_step(model, accum=1, mesh=None):
    opt = zoo.make_optimizer(lr=0.1, momentum=0.9, weight_decay=1e-4)
    state = jax.eval_shape(
        lambda k: zoo.init_state(model, k, (32, 32, 3), opt), jax.random.key(0))
    return zoo.make_train_step(model, opt, accum, mesh).lower(
        state, jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.bfloat16),
        jax.ShapeDtypeStruct((8,), jnp.int32))


@pytest.mark.parametrize("name", list(LOWERED))
def test_the_lowered_step_of_the_models_that_were_there_is_unchanged(
        host_devices, name):
    model, data_mesh, accum = {
        "resnet18": (resnet.resnet18(10, cifar_stem=False), 0, 1),
        "resnet18_dp4": (resnet.resnet18(10, cifar_stem=False), 4, 1),
        "resnet50_accum2": (resnet.resnet50(10, cifar_stem=True), 0, 2),
        "vgg16": (vgg.vgg16(10), 0, 1),
        "convnext_tiny": (tiny(drop_path_rate=0.1), 0, 1),
    }[name]
    mesh = plan_lib.ExecutionPlan(data=data_mesh).validate().make_mesh(
        devices=host_devices[:data_mesh]) if data_mesh else None
    text = _lower_step(model, accum, mesh).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[name]


def test_the_lowered_convnext_step_holds_two_erf_a_block_and_no_erfc():
    """The mechanism engages on every call, so its proof is the program:
    in the lowered train step (StableHLO with its name stacks) every
    block's `act` scope holds one `erf` forward and one backward, one
    `exponential` (backward: the normal density), no divide (`erf`
    itself lowers to one clamped rational polynomial, so one divide at
    the most after that) and no compare or select (the far-tail guard is
    a `maximum`: a predicate that forward and backward share is one XLA
    keeps as an array of its own), and `erfc` appears nowhere, neither as
    `chlo.erfc` nor as the expansion that brings three divides and four
    selects with it."""
    from parallel_cnn_tpu.obs import programs

    text = _lower_step(tiny(drop_path_rate=0.1)).as_text(debug_info=True)
    assert not re.search(r"chlo\.erfc|/erfc\b", text)
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    counts = collections.Counter()
    for op, loc in re.findall(
            r"= (?:chlo|stablehlo)\.(erf|divide|exponential|select|compare|maximum) "
            r".*loc\((#loc\d+)\)",
            text):
        scope, phase = programs.scope_of(names.get(loc, ""))
        if scope.endswith("/act"):
            counts[scope, phase, op] += 1
    blocks = [f"s{i + 1}b{j + 1}/act" for i, d in enumerate(TINY["depths"])
              for j in range(d)]
    assert {k[0] for k in counts} == set(blocks)
    for scope in blocks:
        for phase in ("fwd", "bwd"):
            assert counts[scope, phase, "erf"] == 1
            assert not any(counts[scope, phase, op]
                           for op in ("divide", "compare", "select"))
        assert counts[scope, "fwd", "maximum"] == 1  # the far-tail guard
        assert counts[scope, "bwd", "maximum"] == 0
        assert counts[scope, "fwd", "exponential"] == 0
        assert counts[scope, "bwd", "exponential"] == 1


# --------------------------------------------------------- the front doors

def test_serving_a_convnext_returns_the_reference_logits(monkeypatch):
    """serve/registry.py's handle for a ConvNeXt through Engine: the same
    forward as training's, DropPath off, nothing to fold for LayerNorm."""
    from parallel_cnn_tpu.serve import registry
    from parallel_cnn_tpu.serve.engine import Engine

    # the registry's own construction, at a size a CPU test can serve
    monkeypatch.setattr(convnext, "convnext_b",
                        lambda n: tiny(layer_scale_init=1.0))
    handle = registry.get("convnext_b")
    assert handle.in_shape == (32, 32, 3) and "convnext_b" in registry.available()
    eng = Engine(handle, max_batch=4, seed=5)
    x = np.random.default_rng(0).random((3, 32, 32, 3), dtype=np.float32)
    got = eng.predict(x)
    arch = dict(depths=TINY["depths"], dims=TINY["dims"], ln_eps=1e-6,
                drop_path_rate=0.5)
    want = _plain_reference().eval_logits(arch, eng._params, eng._state, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_sequential_still_composes_the_new_layers_by_index_name():
    model = Sequential([Conv2D(4, groups=1), LayerNorm(), GELU(),
                        GlobalAvgPool(), Dense(3)])
    assert model.scope_names()[1:3] == ["1.LayerNorm", "2.GELU"]
    params, state, shape = model.init(jax.random.key(0), (8, 8, 3))
    y, _ = model.apply(params, state, jnp.ones((2, 8, 8, 3)))
    assert y.shape == (2, 3) and shape == (3,)
