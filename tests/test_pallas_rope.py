"""ops/pallas_rope.py — RoPE's turn as one kernel — on the CPU: the kernel
in interpret mode against `nn/layers.py:rope`'s plain body, values and
gradients, alone and through the two attention layers that take it; what
`tile` refuses, and that `rope` then runs the plain body. That Mosaic
takes the kernel at the cells' shapes, and what surrounds it in a
compiled layer, is tests/test_compiled_glm_sdar_programs.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_glm_moe import one_rounding

from parallel_cnn_tpu.nn import afmoe, layers, sdar_moe
from parallel_cnn_tpu.ops import pallas_rope

THETA = 1e4
# (..., S, d): SDAR's two halves of q and of k's 4 heads, afmoe's q and k,
# a head two registers wide, rows no multiple of a block's 8
SHAPES = {"sdar_q": (2, 8, 2, 128, 128), "sdar_k": (2, 4, 2, 128, 128),
          "afmoe_q": (1, 8, 512, 128), "afmoe_k": (1, 4, 256, 128),
          "d256": (2, 3, 128, 256)}


def _draw(shape, dtype, key=0):
    return jax.random.normal(jax.random.key(key), shape, jnp.float32).astype(dtype)


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel in interpret mode wherever `tile` takes the shapes, and
    the (shape, back) it ran at, in order (the platform would send a CPU
    to `otherwise`)."""
    ran = []

    def either(x, *, theta, back, otherwise, positions=None):
        ran.append((x.shape, back))
        return pallas_rope.rotate(x, theta=theta, back=back, interpret=True,
                                  positions=positions)

    monkeypatch.setattr(pallas_rope, "either", either)
    return ran


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernel_turns_as_the_plain_body_does_both_ways(
        shape, dtype, interpreted):
    """Values, and the gradient under a cotangent of `x`'s dtype, to one
    rounding of float32 products summed in another order: `rope` with the
    kernel where the platform would put it, against the plain body and
    autodiff of it."""
    x, dy = (_draw(SHAPES[shape], dtype, k) for k in (0, 1))
    want, plain_vjp = jax.vjp(lambda x: layers._rope(x, THETA), x)
    got, kernel_vjp = jax.vjp(lambda x: layers.rope(x, THETA), x)
    assert interpreted == [(x.shape, False)]
    assert got.shape == x.shape and got.dtype == x.dtype
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - x.astype(jnp.float32)))) > 0.1
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(jnp.float32),
                               **one_rounding(dtype))
    (dx,), (want_dx,) = kernel_vjp(dy), plain_vjp(dy)
    assert interpreted == [(x.shape, False), (x.shape, True)]
    assert dx.dtype == x.dtype
    np.testing.assert_allclose(dx.astype(jnp.float32), want_dx.astype(jnp.float32),
                               **one_rounding(dtype))
    # position 0 of every row is not turned
    np.testing.assert_array_equal(got[..., 0, :], x[..., 0, :])


@pytest.mark.parametrize("shape", ["sdar_q", "d256"])
def test_the_backward_is_the_turn_by_the_negative_angles(shape):
    """`rotate(back=True)` is the transpose of the turn, which is its
    inverse: a turn then its backward is the identity to rounding, and it
    is the plain body fed `-sin` (the halves swapped, turned and swapped
    back)."""
    x = _draw(SHAPES[shape], jnp.float32)
    there = pallas_rope.rotate(x, theta=THETA, interpret=True)
    back = pallas_rope.rotate(there, theta=THETA, back=True, interpret=True)
    np.testing.assert_allclose(back, x, atol=2e-6)
    half = x.shape[-1] // 2
    swap = lambda a: jnp.concatenate([a[..., half:], a[..., :half]], -1)  # noqa: E731
    np.testing.assert_allclose(
        pallas_rope.rotate(x, theta=THETA, back=True, interpret=True),
        swap(layers._rope(swap(x), THETA)), atol=2e-6)
    cos, sin = pallas_rope.tables(x.shape[-2], x.shape[-1], THETA)
    assert cos.shape == sin.shape == x.shape[-2:] and cos.dtype == jnp.float32
    np.testing.assert_array_equal(sin[:, :half], -sin[:, half:])


@pytest.mark.parametrize("s,d,want", [
    (4096, 128, 512), (16384, 128, 512), (768, 256, 256), (128, 128, 128),
    (4096, 64, None), (4096, 192, None), (520, 128, None), (64, 128, None)])
def test_tile_takes_whole_registers_and_whole_tiles_only(s, d, want):
    assert pallas_rope.tile(s, d) == want


@pytest.mark.parametrize("shape", [(2, 5, 256, 64), (2, 1, 520, 128), (64, 8)],
                         ids=["glm_64_wide", "no_whole_tile", "toy"])
def test_what_tile_refuses_runs_the_plain_body(shape, interpreted):
    x = _draw(shape, jnp.bfloat16)
    np.testing.assert_array_equal(layers.rope(x, THETA), layers._rope(x, THETA))
    assert interpreted == []


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rope_hands_the_kernel_what_tile_takes_and_a_cpu_the_plain_body(dtype):
    """On this CPU `rope` lowers to the plain body and its transpose (to a
    multiply-add's contraction: `rope` runs them jitted), though the shapes
    tile; the kernel's call is there, both ways, for a TPU to take."""
    x, dy = (_draw(SHAPES["afmoe_q"], dtype, k) for k in (0, 1))
    want, plain_vjp = jax.vjp(lambda x: layers._rope(x, THETA), x)
    got, vjp = jax.vjp(lambda x: layers.rope(x, THETA), x)
    for a, b in ((got, want), (vjp(dy)[0], plain_vjp(dy)[0])):
        assert a.dtype == b.dtype == x.dtype
        np.testing.assert_allclose(a.astype(jnp.float32), b.astype(jnp.float32),
                                   **one_rounding(dtype))
    for fn in (lambda x: layers.rope(x, THETA),
               lambda x: jax.vjp(lambda x: layers.rope(x, THETA), x)[1](x)):
        jaxpr = str(jax.make_jaxpr(fn)(x))
        assert "platform_index" in jaxpr and pallas_rope.NAME in jaxpr


def _layer_case(which):
    if which == "sdar":
        att, s = sdar_moe.GQA(heads=4, kv_heads=2, head_dim=128, block=4,
                              q_block=64), 256
        turned = [((2, 4, 2, 128, 128), False), ((2, 2, 2, 128, 128), False)]
    else:
        att, s = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=128, window=32,
                                q_block=64), 128
        turned = [((2, 4, 128, 128), False), ((2, 2, 128, 128), False)]
    params = att.init(jax.random.key(3), (s, 32))[0]
    return att, params, _draw((2, s, 32), jnp.float32, 4), turned


@pytest.mark.parametrize("which", ["sdar", "afmoe"])
def test_an_attention_layer_is_the_same_layer_on_either_path(which, interpreted):
    """SDAR's `GQA` (the two-halves view) and afmoe's `GatedGQA` at a head
    128 wide, rematerialised as a decoder layer is: output and every
    gradient with the kernel are those with the plain body."""
    att, params, x, turned = _layer_case(which)

    def run():
        def loss(p, x):
            out = jax.checkpoint(lambda p, x: att.apply(p, {}, x, True)[0])(p, x)
            return jnp.sum(out ** 2), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(params, x)
        return out, grads

    got = run()
    # q and k forward, again in the rematerialised forward, then backward
    assert interpreted[:2] == turned
    assert sorted(interpreted) == sorted(
        2 * turned + [(shape, True) for shape, _ in turned])
    del interpreted[:]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_rope, "tile", lambda s, d: None)
        want = run()
    assert interpreted == []
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        np.testing.assert_allclose(a, b, atol=2e-5 * scale)
