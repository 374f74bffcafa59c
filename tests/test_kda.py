"""ops/kda.py (the delta rule with a channel-wise decay as a chunked scan)
against the recurrence a position at a time, float32 on the CPU: forward
and the five gradients at two (chunk, sub-chunk) pairs, the decay at its
floor and at none, what beta = 0 and alpha = 1 reduce it to, the shapes it
refuses; and nn/layers.py:causal_conv against shifted adds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.nn import layers
from parallel_cnn_tpu.ops import kda

N, H, S, DK, DV = 2, 2, 256, 32, 16
NAMES = ("q", "k", "v", "g", "beta")


def drawn(seed=0, s=S, low=-5.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (N, H, s, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (N, H, s, DK)))
    v = jax.random.normal(ks[2], (N, H, s, DV))
    g = low * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (N, H, s, DK)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (N, H, s)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (N, H, s, DV))


def grads(fn, args, w, **kw):
    return jax.jit(jax.grad(lambda *a: jnp.sum(fn(*a, **kw) * w),
                            argnums=tuple(range(5))))(*args)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, atol=tol * float(jnp.max(jnp.abs(want))))


PAIRS = [dict(chunk=64, subchunk=16), dict(chunk=32, subchunk=8)]


@pytest.fixture(scope="module")
def recurrence():
    args, w = drawn()
    return args, w, kda.recurrent_kda(*args), grads(kda.recurrent_kda, args, w)


@pytest.mark.parametrize("kw", PAIRS, ids=["c64_s16", "c32_s8"])
def test_the_chunked_scan_gives_the_recurrences_output(recurrence, kw):
    args, _, want, _ = recurrence
    got = jax.jit(lambda *a: kda.chunked_kda(*a, **kw))(*args)
    assert got.shape == (N, H, S, DV) and got.dtype == jnp.float32
    close(got, want)
    assert float(jnp.max(jnp.abs(want))) > 0.05


@pytest.mark.parametrize("kw", PAIRS, ids=["c64_s16", "c32_s8"])
@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_the_chunked_scans_gradient_is_the_recurrences(recurrence, kw, leaf):
    args, w, _, want = recurrence
    got = grads(kda.chunked_kda, args, w, **kw)
    assert float(jnp.max(jnp.abs(want[leaf]))) > 1e-3
    close(got[leaf], want[leaf])


@pytest.mark.parametrize("level", [-5.0, 0.0], ids=["floor", "none"])
def test_every_gate_at_the_floor_and_at_zero(level):
    """At -5 a channel's decay over a sub-chunk is e^-80 and the two
    factors reach e^+-40: finite, and the recurrence's numbers, forward
    and backward. At 0 (alpha = 1) it is the plain delta rule."""
    (q, k, v, g, beta), w = drawn(1)
    args = (q, k, v, jnp.full_like(g, level), beta)
    got = kda.chunked_kda(*args)
    close(got, kda.recurrent_kda(*args))
    for a, b in zip(grads(kda.chunked_kda, args, w),
                    grads(kda.recurrent_kda, args, w), strict=True):
        assert bool(jnp.all(jnp.isfinite(a)))
        # (at the floor the factors are e^+-40 and the sums lose five bits)
        close(a, b, tol=2e-5 if level == 0.0 else 2e-4)
    if level == 0.0:
        # the plain delta rule, written out: S += beta k (v - S^T k)^T
        state, outs = jnp.zeros((N, H, DK, DV)), []
        for t in range(64):
            k_t = k[:, :, t]
            miss = v[:, :, t] - jnp.einsum("nhk,nhkv->nhv", k_t, state)
            state = state + beta[:, :, t, None, None] * k_t[..., None] * miss[
                :, :, None, :]
            outs.append(jnp.einsum("nhk,nhkv->nhv", q[:, :, t], state))
        close(got[:, :, :64], jnp.stack(outs, axis=2))


def test_beta_zero_writes_nothing_and_beta_one_stores_the_value():
    (q, k, v, g, beta), _ = drawn(2)
    assert float(jnp.max(jnp.abs(kda.chunked_kda(
        q, k, v, g, jnp.zeros_like(beta))))) == 0.0
    # beta = 1, no decay, a unit key read back at once: o_t = (q_t . k_t) v_t
    # plus what earlier keys left along q_t; with q = k the read is v_t itself
    got = kda.chunked_kda(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    close(got, v, tol=1e-4)


def test_a_later_position_never_reaches_an_earlier_output(recurrence):
    args, _, want, _ = recurrence
    cut = 100
    q, k, v, g, beta = args
    moved = (q, k.at[:, :, cut:].multiply(-1.0), v.at[:, :, cut:].add(1.0),
             g.at[:, :, cut:].multiply(0.5), beta.at[:, :, cut:].multiply(0.5))
    got = kda.chunked_kda(*moved)
    np.testing.assert_array_equal(got[:, :, :64], kda.chunked_kda(*args)[:, :, :64])
    close(got[:, :, :cut], want[:, :, :cut])
    assert float(jnp.max(jnp.abs(got[:, :, cut:] - want[:, :, cut:]))) > 0.01


def test_bfloat16_inputs_change_rounding_only(recurrence):
    args, _, want, _ = recurrence
    q, k, v, g, beta = args
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    got = kda.chunked_kda(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert 1e-6 < gap < 2e-2 * float(jnp.max(jnp.abs(want)))


def test_shapes_off_the_chunk_are_refused_by_name():
    (q, k, v, g, beta), _ = drawn(3, s=96)
    with pytest.raises(ValueError, match="96 positions are no multiple of the chunk"):
        kda.chunked_kda(q, k, v, g, beta)
    with pytest.raises(ValueError, match="no multiple of the sub-chunk"):
        kda.chunked_kda(q, k, v, g, beta, chunk=32, subchunk=12)
    with pytest.raises(ValueError, match="no power of two"):
        kda.chunked_kda(q, k, v, g, beta, chunk=48, subchunk=16)
    close(kda.chunked_kda(q, k, v, g, beta, chunk=32, subchunk=16),
          kda.recurrent_kda(q, k, v, g, beta))


@pytest.mark.parametrize("s,want", [(8192, (32, 4)), (256, (1, 4)),
                                    (384, (2, 3)), (704, (11, 1)),
                                    (64, (1, 1))])
def test_the_spans_divide_the_chunks_and_the_kept_states_follow(s, want):
    assert kda.SPAN == 4
    assert kda.spans(s) == kda.spans(s, 64) == want
    assert kda.state_bytes(s, 32, 128, 128) == want[0] * 32 * 65536
    assert kda.spans(s // 2, 32) == want
    assert kda.state_bytes(8192, 32, 128, 128) == 64 << 20


# ------------------------------------------------------- the short conv

def test_the_causal_conv_is_four_shifted_adds():
    x = jax.random.normal(jax.random.key(0), (2, 3, 40, 8))  # (N, H, S, D)
    taps = jax.random.normal(jax.random.key(1), (4, 3, 8))
    got = layers.causal_conv(x, taps)
    want = np.zeros(x.shape, np.float32)
    xs, ts = np.asarray(x), np.asarray(taps)
    for t in range(40):
        for i in range(4):
            at = t - 3 + i
            if at >= 0:
                want[:, :, t] += ts[i][None] * xs[:, :, at]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # rows ahead of heads too: a tap a channel
    flat = layers.causal_conv(x[:, 0], taps[:, 0])
    np.testing.assert_allclose(flat, want[:, 0], atol=1e-5)


def test_the_causal_conv_is_causal_to_the_position():
    x = jax.random.normal(jax.random.key(2), (1, 2, 32, 4))
    taps = jax.random.normal(jax.random.key(3), (4, 2, 4))
    moved = x.at[:, :, 17:].add(3.0)
    a, b = layers.causal_conv(x, taps), layers.causal_conv(moved, taps)
    np.testing.assert_array_equal(a[:, :, :17], b[:, :, :17])
    assert float(jnp.min(jnp.max(jnp.abs(a - b)[:, :, 17:21], axis=(0, 1, 3)))) > 0
    # the last tap is the position's own: one tap of 1 is the identity
    one = jnp.zeros((4, 2, 4)).at[3].set(1.0)
    np.testing.assert_array_equal(layers.causal_conv(x, one), x)
    low = layers.causal_conv(x.astype(jnp.bfloat16), taps)
    assert low.dtype == jnp.bfloat16
