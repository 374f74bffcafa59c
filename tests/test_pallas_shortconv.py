"""ops/pallas_shortconv.py — the short convolution, its SiLU, the L2 norm
and the scale as one kernel a direction — on the CPU: the kernels in
interpret mode against `nn/bailing_hybrid.py`'s plain composition, values
and the three gradients, alone and through a `KDA` layer; causality to the
position across a tile's and a chunk's edge; what `tile` refuses, and that
`short_conv` then composes plainly — as it does while a stage's name holds
another function. That Mosaic takes the kernels at the cell's shapes, and
what surrounds them in a compiled step, is tests/test_compiled_trinity_ling_programs.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.nn import bailing_hybrid as bh, layers
from parallel_cnn_tpu.ops import pallas_shortconv as sc
from token_family import jitted

K = 4
# (N, H, S, D): three tiles of 128, a chunk each; three blocks of three
# heads in one tile of 256, two chunks; a head two registers wide
SHAPES = {"three_tiles": (2, 3, 384, 128), "three_head_blocks": (1, 9, 256, 128),
          "d256": (1, 2, 256, 256)}
# three tiles of 256 in chunks of 128: edges of both kinds
EDGES = (1, 2, 768, 128)
# q's, k's and v's calls in `KDA.apply`
KINDS = {"unit_scaled": (True, 128 ** -0.5), "unit": (True, 1.0),
         "plain": (False, 1.0)}


def _draw(shape, dtype=jnp.float32, key=0):
    n, h, s, d = shape
    keys = jax.random.split(jax.random.key(key), 3)
    x, dy = (jax.random.normal(k, shape, jnp.float32).astype(dtype)
             for k in keys[:2])
    taps = jax.random.uniform(keys[2], (K, h, d), jnp.float32, -1.0, 1.0) * 0.5
    return x, taps, dy


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def chips_reciprocal(monkeypatch):
    """`_sigmoid` divides by the unit's approximate reciprocal and one
    Newton step, which squares the error: the chip's is good to 1e-5, so
    the step leaves float32's rounding (against float64 on the chip: 3.2e-7
    with it, 3.3e-7 with a division; PERF.md section 6, PR 46). Off the
    chip Pallas stands in for the unit with a division in bf16, 2^-9, whose
    square is 1.2e-5: a float32 division stands in here, so that the
    tolerances of this file are what the kernels hold on the chip."""
    monkeypatch.setattr(sc.pl, "reciprocal", lambda x, approx=False: 1.0 / x)


@pytest.fixture(autouse=True)
def _the_chips_reciprocal(monkeypatch):
    chips_reciprocal(monkeypatch)


def interpret(monkeypatch):
    """The kernels in interpret mode (under `chips_reciprocal`) wherever
    `tile` takes the shapes, and the (shape, unit, back) they ran at, in
    order (the platform would send a CPU to `otherwise`)."""
    chips_reciprocal(monkeypatch)
    ran = []

    def either(*operands, unit, scale, back, otherwise):
        ran.append((operands[0].shape, unit, back))
        kernel = sc.backward if back else sc.forward
        return kernel(*operands, unit=unit, scale=scale, interpret=True)

    monkeypatch.setattr(sc, "either", either)
    return ran


@pytest.fixture
def interpreted(monkeypatch):
    return interpret(monkeypatch)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_kernels_are_the_plain_composition_and_its_gradients(
        shape, kind, dtype, interpreted):
    """`short_conv` with the kernels where the platform would put them
    against the composition in float32 of the same inputs and autodiff of
    it: float32 inputs to 1e-5 of the largest value, bf16 inputs to ONE
    rounding of the float32 result (the composition in bf16 rounds four
    times), `dtaps` — float32 sums whatever `x` is — to 1e-5."""
    unit, scale = KINDS[kind]
    x, taps, dy = _draw(SHAPES[shape], dtype)
    exact = lambda x, t: bh._short_conv_plain(  # noqa: E731
        x.astype(jnp.float32), t, unit, scale)
    want, pull = jax.vjp(exact, x, taps)
    want_dx, want_dtaps = pull(dy.astype(jnp.float32))
    got, kernel_pull = jax.vjp(lambda x, t: bh.short_conv(x, t, unit, scale),
                               x, taps)
    assert interpreted == [(x.shape, unit, False)]
    dx, dtaps = kernel_pull(dy)
    assert interpreted == [(x.shape, unit, False), (x.shape, unit, True)]
    assert (got.dtype, dx.dtype, dtaps.dtype) == (x.dtype, x.dtype, taps.dtype)
    assert got.shape == dx.shape == x.shape and dtaps.shape == taps.shape
    for a, b in ((got, want), (dx, want_dx)):
        b = _f32(b)
        if dtype == "bfloat16":
            np.testing.assert_allclose(_f32(a), b, rtol=2.0 ** -7,
                                       atol=1e-6 * np.abs(b).max())
        else:
            np.testing.assert_allclose(_f32(a), b, atol=1e-5 * np.abs(b).max())
    np.testing.assert_allclose(dtaps, want_dtaps,
                               atol=1e-5 * float(jnp.max(jnp.abs(want_dtaps))))
    # the composition as `KDA` ran it, roundings and all, is a few last
    # places of `dtype` away and no further
    plain = bh._short_conv_plain(x, taps, unit, scale)
    assert float(jnp.max(jnp.abs(plain.astype(jnp.float32)))) > 0.1 * scale
    np.testing.assert_allclose(_f32(got), _f32(plain), rtol=2.0 ** -5, atol=1e-5)


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "plain"])
def test_a_position_moves_nothing_before_it_across_a_tile_and_at_zero(unit):
    """Tiles of 256 in chunks of 128: moving `x[t]` changes `y[t .. t + 3]`
    (with the norm: those rows) and no row before `t`, to the bit, for a
    `t` at a sequence's start, in the last rows of a chunk and of a tile
    and in the first of the next; position 0 sees zeros before it."""
    assert sc.tile(EDGES[2], EDGES[3], K) == 2 * sc.CHUNK == 256
    x, taps, _ = _draw(EDGES)
    run = lambda x: sc.forward(x, taps, unit=unit, interpret=True)  # noqa: E731
    y = run(x)
    for t in (0, 125, 127, 128, 253, 255, 256, 383, 384, 511, 512, 767):
        moved = run(x.at[:, :, t].add(1.0))
        changed = np.flatnonzero(np.any(_f32(moved != y), axis=(0, 1, 3)))
        assert changed.tolist() == list(range(t, min(t + K, 768))), (t, changed)
    a = taps[K - 1][None] * x[:, :, 0]
    a = a * jax.nn.sigmoid(a)
    if unit:
        a = a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + sc.L2_EPS)
    np.testing.assert_allclose(y[:, :, 0], a, atol=1e-6)


@pytest.mark.parametrize("unit", [True, False], ids=["unit", "plain"])
def test_a_cotangent_reaches_the_three_positions_before_it_and_no_other(unit):
    """`dx` of a tile's (and a chunk's) last three rows sees the next
    one's `dy`: moving `dy[t]` changes `dx[t - 3 .. t]` and nothing else,
    to the bit, and `dtaps` with it; behind the last tile lie zeros."""
    x, taps, dy = _draw(EDGES)
    run = lambda dy: sc.backward(x, taps, dy, unit=unit, interpret=True)  # noqa: E731
    dx, dtaps = run(dy)
    for t in (0, 2, 128, 130, 256, 257, 384, 512, 514, 767):
        moved, moved_taps = run(dy.at[:, :, t].add(1.0))
        changed = np.flatnonzero(np.any(_f32(moved != dx), axis=(0, 1, 3)))
        assert changed.tolist() == list(range(max(t - K + 1, 0), t + 1)), (t, changed)
        assert float(jnp.max(jnp.abs(moved_taps - dtaps))) > 1e-3
    # the last position's cotangent comes back through the last tap alone
    alone = jnp.zeros_like(dy).at[:, :, -1].set(dy[:, :, -1])
    last, _ = run(alone)
    want = jax.vjp(lambda x: bh._short_conv_plain(x, taps, unit, 1.0), x)[1](alone)[0]
    np.testing.assert_allclose(last, want, atol=1e-6)
    assert float(jnp.max(jnp.abs(last[:, :, :-K]))) == 0.0


def test_a_sequence_owes_nothing_to_the_one_before_it():
    """The view ahead of a sequence's first tile is clamped onto the tile
    itself and read as zeros, and the rows carried back from a tile stop at
    a (sequence, head block)'s last: each sequence of a batch, and each
    block of heads, is the one it is alone."""
    x, taps, dy = _draw(SHAPES["three_head_blocks"])
    x, dy = jnp.concatenate([x, 2 * x]), jnp.concatenate([dy, -dy])
    y = sc.forward(x, taps, unit=True, interpret=True)
    dx, dtaps = sc.backward(x, taps, dy, unit=True, interpret=True)
    parts = []
    for n in range(2):
        np.testing.assert_array_equal(
            sc.forward(x[n:n + 1], taps, unit=True, interpret=True), y[n:n + 1])
        one, part = sc.backward(x[n:n + 1], taps, dy[n:n + 1], unit=True,
                                interpret=True)
        np.testing.assert_array_equal(one, dx[n:n + 1])
        parts.append(part)
    np.testing.assert_allclose(dtaps, parts[0] + parts[1], atol=1e-5)


@pytest.mark.parametrize("s,d,k,want", [
    (8192, 128, 4, 512), (768, 256, 4, 256), (384, 128, 2, 128),
    (128, 128, 9, 128), (8192, 64, 4, None), (8192, 192, 4, None),
    (520, 128, 4, None), (64, 128, 4, None), (8192, 128, 10, None)])
def test_tile_takes_whole_registers_whole_tiles_and_taps_within_the_edge(
        s, d, k, want):
    assert sc.tile(s, d, k) == want
    assert sc.core(s, d, k, "tpu") == ("pallas" if want else "xla")
    assert sc.core(s, d, k, "cpu") == "xla"


@pytest.mark.parametrize("shape", [(2, 2, 128, 64), (1, 2, 520, 128), (2, 2, 128, 16)],
                         ids=["64_wide", "no_whole_tile", "toy"])
def test_what_tile_refuses_composes_plainly(shape, interpreted):
    x, taps, _ = _draw(shape, jnp.bfloat16)
    np.testing.assert_array_equal(
        bh.short_conv(x, taps, True, 0.25),
        bh._unit(jax.nn.silu(layers.causal_conv(x, taps))) * 0.25)
    assert interpreted == []


@pytest.mark.parametrize("stage", ["causal_conv", "_unit"])
def test_a_replaced_stage_composes_plainly_and_is_the_one_that_runs(
        stage, interpreted, monkeypatch):
    """The kernels are `causal_conv` and `_unit` as the module had them at
    import: with either name holding another function — what a comparison's
    planted fault does — shapes that tile compose plainly, through that
    function."""
    x, taps, _ = _draw(SHAPES["three_tiles"])
    clean = bh.short_conv(x, taps, True, 1.0)
    assert len(interpreted) == 1
    with pytest.MonkeyPatch.context() as planted:
        if stage == "causal_conv":
            planted.setattr(bh, stage, lambda x, taps: x)
            want = bh._unit(jax.nn.silu(x))
        else:
            planted.setattr(bh, stage, lambda x: x)
            want = jax.nn.silu(layers.causal_conv(x, taps))
        got = bh.short_conv(x, taps, True, 1.0)
    assert len(interpreted) == 1
    np.testing.assert_array_equal(got, want)
    assert float(jnp.max(jnp.abs(got - clean))) > 0.1
    np.testing.assert_array_equal(bh.short_conv(x, taps, True, 1.0), clean)
    assert len(interpreted) == 2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_hands_the_kernels_what_tile_takes_and_a_cpu_the_plain_body(dtype):
    """On this CPU `short_conv` lowers to the composition and autodiff of
    it, though the shapes tile; the kernels' calls are there, both ways,
    for a TPU to take."""
    x, taps, dy = _draw(SHAPES["three_tiles"], dtype)
    fn = lambda x, t: bh.short_conv(x, t, True, 0.5)  # noqa: E731
    want, plain_pull = jax.vjp(
        lambda x, t: bh._short_conv_plain(x, t, True, 0.5), x, taps)
    got, pull = jax.vjp(fn, x, taps)
    for a, b in ((got, want), *zip(pull(dy), plain_pull(dy))):
        assert a.dtype == b.dtype
        # (jitted, the composition keeps some of its roundings in float32)
        np.testing.assert_allclose(
            _f32(a), _f32(b), rtol=0, atol=(2.0 ** -6 if dtype == "bfloat16"
                                            else 1e-5) * float(jnp.max(jnp.abs(b))))
    for traced, name in ((fn, "short_conv_fwd"),
                         (lambda x, t: jax.vjp(fn, x, t)[1](x), "short_conv_bwd")):
        jaxpr = str(jax.make_jaxpr(traced)(x, taps))
        assert "platform_index" in jaxpr and name in jaxpr


def test_forward_mode_through_short_conv_is_a_clear_error():
    x, taps, _ = _draw(SHAPES["d256"])
    with pytest.raises(TypeError, match="custom_vjp"):
        jax.jvp(lambda x: bh.short_conv(x, taps, True, 1.0), (x,), (x,))


def test_the_kernels_constants_are_the_layers():
    assert bh.L2_EPS == sc.L2_EPS == 1e-6
    assert sc.EDGE >= bh.KDA().taps - 1 and sc.HALO % 16 == 0
    assert all(ts % sc.CHUNK == 0 and sc.CHUNK % sc.HALO == 0
               for ts in (512, 256, 128))


def test_a_linear_layer_is_the_same_layer_on_either_path(interpreted):
    """`KDA` at a head 128 wide, rematerialised as a decoder layer is:
    output and every gradient with the kernels are those with the
    composition, to float32 rounding; `q`, `k`, `v` forward, again in the
    rematerialised forward, then backward."""
    att = bh.KDA(heads=2, head_dim=128)
    s = 128
    params = att.init(jax.random.key(3), (s, 32))[0]
    x = jax.random.normal(jax.random.key(4), (2, s, 32), jnp.float32)

    def run():  # a new function a call: traced under what is patched now
        def loss(p, x):
            out = jax.checkpoint(lambda p, x: att.apply(p, {}, x, True)[0])(p, x)
            return jnp.sum(out ** 2), out
        (_, out), grads = jitted(
            jax.value_and_grad(loss, (0, 1), has_aux=True), params, x)
        return out, grads

    got = run()
    shape = (2, 2, s, 128)
    calls = [(shape, True, False), (shape, True, False), (shape, False, False)]
    assert interpreted[:3] == calls
    assert sorted(interpreted) == sorted(
        2 * calls + [(shape, unit, True) for _, unit, _ in calls])
    del interpreted[:]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sc, "tile", lambda s, d, k: None)
        want = run()
    assert interpreted == []
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0
        np.testing.assert_allclose(a, b, atol=2e-5 * scale)
