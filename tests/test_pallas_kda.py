"""ops/pallas_kda.py — the delta rule's chunked scan as two kernels — on the
CPU: the kernels in interpret mode, driven through `kda.chunked_kda` and
its `custom_vjp` where the platform would put them, against the
recurrence a position at a time (the tolerances tests/test_kda.py holds
the plain body to) and against the plain body (float32 rounding); two
spans, so the carried state and `d_state` cross a grid step. What
`tiles` refuses, and that `chunked_kda` then runs the plain body. That
Mosaic takes the kernels at the cell's shapes, and what surrounds them in
a compiled step, is tests/test_compiled_trinity_ling_programs.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.ops import kda, pallas_kda

N, H, S, D = 1, 2, 512, 128
NAMES = ("q", "k", "v", "g", "beta")


def drawn(seed=0, s=S, d=D, low=-5.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (N, H, s, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (N, H, s, d)))
    v = jax.random.normal(ks[2], (N, H, s, d))
    g = low * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (N, H, s, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (N, H, s)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (N, H, s, d))


def grads(fn, args, w):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        argnums=tuple(range(5))))(*args)


def close(got, want, tol=2e-5):
    np.testing.assert_allclose(got, want, atol=tol * float(jnp.max(jnp.abs(want))))


def _interpret(monkeypatch):
    """The kernels in interpret mode wherever `tiles` takes the shapes (the
    platform would send a CPU to the plain body), and the directions that
    ran, in order."""
    ran = []

    def either(*operands, chunk, subchunk, back):
        ran.append("bwd" if back else "fwd")
        sizes = dict(chunk=chunk, subchunk=subchunk, interpret=True)
        if back:
            return pallas_kda.backward(*operands, **sizes)
        return pallas_kda.forward(*operands, span=kda.SPAN, **sizes)

    monkeypatch.setattr(kda, "_either", either)
    return ran


@pytest.fixture
def interpreted(monkeypatch):
    return _interpret(monkeypatch)


@pytest.fixture(scope="module")
def both():
    """One draw: the kernels' output and gradients through `chunked_kda`,
    the recurrence's, and the plain body's."""
    args, w = drawn()
    with pytest.MonkeyPatch.context() as patch:
        ran = _interpret(patch)
        got = kda.chunked_kda(*args), grads(kda.chunked_kda, args, w)
    assert ran == ["fwd", "fwd", "bwd"]
    return dict(args=args, w=w, kernel=got,
                recurrence=(kda.recurrent_kda(*args),
                            grads(kda.recurrent_kda, args, w)),
                plain=(kda.chunked_kda(*args), grads(kda.chunked_kda, args, w)))


def test_the_kernel_gives_the_recurrences_output(both):
    got, want = both["kernel"][0], both["recurrence"][0]
    assert got.shape == (N, H, S, D) and got.dtype == jnp.float32
    close(got, want)
    assert float(jnp.max(jnp.abs(want))) > 0.05
    # the second span starts from a state: it is no copy of a first one
    assert float(jnp.max(jnp.abs(want[:, :, S // 2:]))) > 0.05


@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_the_kernels_gradient_is_the_recurrences(both, leaf):
    got, want = both["kernel"][1][leaf], both["recurrence"][1][leaf]
    assert got.shape == want.shape and got.dtype == want.dtype
    assert float(jnp.max(jnp.abs(want))) > 1e-3
    close(got, want)
    # `d_state` crossed the grid step: the first span's gradient is there
    assert float(jnp.max(jnp.abs(got[:, :, :S // 2]))) > 1e-3


@pytest.mark.parametrize("leaf", [None, *range(5)], ids=["o", *NAMES])
def test_the_kernel_is_the_plain_body_to_float32_rounding(both, leaf):
    pick = (lambda r: r[0]) if leaf is None else (lambda r: r[1][leaf])
    close(pick(both["kernel"]), pick(both["plain"]),
          tol=5e-6 if leaf is None else 2e-5)


def test_the_kept_states_are_the_plain_bodys(both):
    sizes = dict(chunk=kda.CHUNK, subchunk=kda.SUBCHUNK)
    _, want = kda._scan_forward(*both["args"], **sizes)
    _, got = pallas_kda.forward(*both["args"], span=kda.SPAN, interpret=True,
                                **sizes)
    assert got.shape == want.shape == (2, N, H, D, D)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(got[0], 0.0)
    close(got[1], want[1])


@pytest.mark.parametrize("level", [-5.0, 0.0], ids=["floor", "none"])
def test_every_gate_at_the_floor_and_at_zero(level, interpreted):
    """tests/test_kda.py's for the plain body: at -5 the two factors reach
    e^+-40, finite, and the recurrence's numbers in both directions."""
    (q, k, v, g, beta), w = drawn(1)
    args = (q, k, v, jnp.full_like(g, level), beta)
    close(kda.chunked_kda(*args), kda.recurrent_kda(*args))
    for a, b in zip(grads(kda.chunked_kda, args, w),
                    grads(kda.recurrent_kda, args, w), strict=True):
        assert bool(jnp.all(jnp.isfinite(a)))
        close(a, b, tol=2e-5 if level == 0.0 else 2e-4)
    assert interpreted == ["fwd", "fwd", "bwd"]


def test_beta_zero_writes_nothing_and_beta_one_stores_the_value(interpreted):
    (q, k, v, g, beta), _ = drawn(2)
    assert float(jnp.max(jnp.abs(kda.chunked_kda(
        q, k, v, g, jnp.zeros_like(beta))))) == 0.0
    got = kda.chunked_kda(k, k, v, jnp.zeros_like(g), jnp.ones_like(beta))
    close(got, v, tol=1e-4)
    assert interpreted == ["fwd", "fwd"]


def test_bfloat16_inputs_change_rounding_only(both, interpreted):
    q, k, v, g, beta = both["args"]
    low = tuple(a.astype(jnp.bfloat16) for a in (q, k, v))
    got, pull = jax.vjp(lambda q, k, v: kda.chunked_kda(q, k, v, g, beta), *low)
    assert got.dtype == jnp.bfloat16
    want = both["recurrence"][0]
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert 1e-6 < gap < 2e-2 * float(jnp.max(jnp.abs(want)))
    back = pull(both["w"].astype(jnp.bfloat16))
    assert [d.dtype for d in back] == [jnp.bfloat16] * 3
    for d, exact in zip(back, both["recurrence"][1]):
        gap = float(jnp.max(jnp.abs(d.astype(jnp.float32) - exact)))
        assert 1e-6 < gap < 5e-2 * float(jnp.max(jnp.abs(exact)))
    assert interpreted == ["fwd", "bwd"]


@pytest.mark.parametrize("s,d,sizes", [
    (256, 64, {}), (256, 128, dict(chunk=32, subchunk=8)), (192, 128, {})],
    ids=["heads_64_wide", "c32_s8", "s_192"])
def test_shapes_the_kernels_do_not_take_run_the_plain_body_and_say_so(
        s, d, sizes, interpreted):
    chunk, sub = sizes.get("chunk", kda.CHUNK), sizes.get("subchunk", kda.SUBCHUNK)
    assert not pallas_kda.tiles(s, d, d, chunk, sub, kda.SPAN)
    assert kda.core(s, d, d, "tpu", **sizes) == "xla"
    args, w = drawn(3, s=s, d=d)
    got = grads(lambda *a: kda.chunked_kda(*a, **sizes), args, w)
    assert interpreted == []
    close(got[3], grads(kda.recurrent_kda, args, w)[3])


def test_shapes_that_tile_run_the_kernel_on_a_tpu_alone():
    assert pallas_kda.tiles(8192, 128, 128, 64, 16, 4)
    assert pallas_kda.tiles(512, 256, 128, 64, 16, 4)
    assert kda.core(8192, 128, 128, "tpu") == "pallas"
    assert kda.core(8192, 128, 128, "cpu") == "xla"
    assert kda.core(8192 + 64, 128, 128, "tpu") == "xla"
    assert pallas_kda.NAME == "kda_scan"
