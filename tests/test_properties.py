"""Property-based tests (hypothesis) for the framework's pure contracts:
the idx-ubyte parser (C1's format surface), the augmentation geometry,
and the kernel-library block-sizing invariants the Pallas grids rely on."""

import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package"
)
from hypothesis import example, given, settings, strategies as st  # noqa: E402

from parallel_cnn_tpu.data import mnist
from parallel_cnn_tpu.data.augment import random_crop_flip
from parallel_cnn_tpu.ops.pallas import _batch_block
from parallel_cnn_tpu.ops import pallas_conv as pc


def _idx3_bytes(images: np.ndarray) -> bytes:
    n, h, w = images.shape
    return struct.pack(">iiii", 2051, n, h, w) + images.tobytes()


def _idx1_bytes(labels: np.ndarray) -> bytes:
    return struct.pack(">ii", 2049, labels.shape[0]) + labels.tobytes()


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 8),
    data=st.data(),
)
def test_idx_roundtrip_arbitrary_pixels(tmp_path_factory, n, data):
    """Any 28x28 uint8 payload roundtrips: count preserved, pixels /255
    in [0,1], labels byte-exact — the mnist.h:100-149 contract."""
    rng = np.random.default_rng(data.draw(st.integers(0, 2**32 - 1)))
    imgs = rng.integers(0, 256, (n, 28, 28), dtype=np.uint8)
    labs = rng.integers(0, 10, (n,), dtype=np.uint8)
    d = tmp_path_factory.mktemp("idx")
    ip, lp = str(d / "im.idx3"), str(d / "la.idx1")
    open(ip, "wb").write(_idx3_bytes(imgs))
    open(lp, "wb").write(_idx1_bytes(labs))

    out = mnist.load_idx_images(ip)
    assert out.shape == (n, 28, 28) and out.dtype == np.float32
    np.testing.assert_allclose(out, imgs.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(mnist.load_idx_labels(lp), labs)


@settings(max_examples=20, deadline=None)
@given(magic=st.integers(0, 2**31 - 1))
def test_idx_bad_magic_is_typed_error(tmp_path_factory, magic):
    """Every non-2051 magic raises MnistError (≙ mnist.h's −2 code path),
    never garbage data."""
    if magic == 2051:
        magic += 1
    d = tmp_path_factory.mktemp("bad")
    p = str(d / "bad.idx3")
    open(p, "wb").write(struct.pack(">iiii", magic, 1, 28, 28) + b"\0" * 784)
    with pytest.raises(mnist.MnistError):
        mnist.load_idx_images(p)


@settings(max_examples=15, deadline=None)
@given(
    b=st.integers(1, 6),
    h=st.integers(4, 12),
    w=st.integers(4, 12),
    c=st.integers(1, 3),
    pad=st.integers(0, 3),
    seed=st.integers(0, 1000),
)
def test_augment_pixels_come_from_padded_input(b, h, w, c, pad, seed):
    """Every augmented pixel value exists in {0} ∪ input values (crops
    read only the zero-padded input; flips permute), and shape/dtype are
    preserved — for arbitrary geometry, not just the CIFAR shape."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(0.5, 1.0, (b, h, w, c)).astype(np.float32))
    out = random_crop_flip(jax.random.key(seed), x, pad=pad)
    assert out.shape == x.shape and out.dtype == x.dtype
    allowed = set(np.asarray(x).ravel().tolist()) | {0.0}
    assert set(np.asarray(out).ravel().tolist()) <= allowed


@settings(max_examples=50, deadline=None)
@given(n=st.integers(1, 4096), want=st.integers(1, 512))
def test_batch_block_is_a_divisor_within_bound(n, want):
    bb = _batch_block(n, want)
    assert 1 <= bb <= min(n, want)
    assert n % bb == 0


@settings(max_examples=50, deadline=None)
@given(
    n=st.sampled_from([32, 64, 128, 256, 512, 1024]),
    rows=st.integers(16, 1300),
    cin=st.sampled_from([3, 64, 128, 256, 512]),
    cout=st.sampled_from([64, 128, 256, 512]),
    taps=st.sampled_from([1, 9]),
    esz=st.sampled_from([2, 4]),
)
# hypothesis's falsifying example for the old "budget respected whenever
# want >= 1" claim: one image + double-buffered weights model 39.5 MB, over
# the 32 MB budget, and no smaller block exists.
@example(n=32, rows=672, cin=512, cout=512, taps=9, esz=4)
def test_pick_bb_divides_batch_and_respects_budget(n, rows, cin, cout, taps, esz):
    """What _pick_bb promises: bb divides n; the block's sublane dim obeys
    Mosaic's dtype tile rule (legality BEATS the VMEM target — the
    documented trade-off behind the sublane-tile fix); the budget is
    respected WHEN a legal divisor that fits it exists, and the largest
    such is taken; otherwise the smallest legal block is."""
    w_bytes = taps * cin * cout * 4
    bb = pc._pick_bb(
        n, rows, [cin], [cin] * taps, [cout], esz, esz, w_bytes
    )
    assert 1 <= bb <= n and n % bb == 0
    tile = 32 // esz
    assert (bb * rows) % tile == 0 or bb == n
    per_img = rows * (
        esz * (2 * cin + taps * cin) + esz * 2 * cout + 4 * 2 * cout
    )
    legal = [
        d for d in range(1, n + 1)
        if n % d == 0 and ((d * rows) % tile == 0 or d == n)
    ]
    fits = [d for d in legal
            if d * per_img + 2 * w_bytes <= pc._VMEM_BUDGET]
    assert bb == (max(fits) if fits else min(legal))


def test_pick_bb_over_the_hard_limit_is_never_silent(caplog):
    """A block that models past _VMEM_LIMIT is returned (something must
    run) but always with a warning naming the predicted Mosaic OOM —
    including when the ONLY legal block is bb=1, the case the old
    early-return skipped."""
    import logging

    with caplog.at_level(logging.WARNING, "parallel_cnn_tpu.ops.pallas_conv"):
        bb = pc._pick_bb(4, 100_000, [512], [512] * 9, [512], 4, 4, 0)
    assert bb == 1
    assert any("expect a Mosaic OOM" in r.getMessage()
               for r in caplog.records)


@settings(max_examples=100, deadline=None)
@given(
    k=st.sampled_from([3, 5, 7]),
    h=st.integers(2, 40),
    w=st.integers(2, 40),
)
def test_s1_tap_layout_slice_legality(k, h, w):
    """The pad-H-only layout invariants every stride-1 kernel relies on:
    with rows = (Ttop+h+Tbot)·w, center [lo, nb-tail), every tap slice
    [lo+off, hi+off) stays inside an nb-row block, real rows are inside
    the center region, and semantically-zero reads land on pad rows."""
    taps = pc._s1_taps(k, w)
    flat = [a * w + b for a, b, _ in taps]
    rows, t_top, lo, tail = pc._layout(h, w, flat)
    t_bot = rows // w - h - t_top
    assert t_top >= 0 and t_bot >= 0
    nb = 3 * rows  # any multiple: block = bb images
    hi = nb - tail
    assert 0 <= lo + min(flat) and hi + max(flat) <= nb
    # real rows of every image in the block sit inside [lo, hi)
    for img in range(3):
        first = img * rows + t_top * w
        last = img * rows + (t_top + h) * w - 1
        assert lo <= first and last < hi
    # semantically-zero reads land on the image's OWN pad rows: a tap
    # read from any real row never reaches outside this image's padded
    # span (where it could alias a neighbor's real data)
    assert t_top * w + min(flat) >= 0
    assert (t_top + h) * w - 1 + max(flat) < rows


@settings(max_examples=100, deadline=None)
@given(
    k=st.sampled_from([3, 5, 7]),
    oy=st.integers(0, 5),
    ox=st.integers(0, 5),
)
def test_s2_phase_taps_match_conv_index_equation(k, oy, ox):
    """Derive both mappings INDEPENDENTLY from the stride-2 SAME conv
    index equation u = 2·o + d − pad_lo (pad_lo = (k−2)//2, XLA's even-dim
    placement) and check _s2_phase_taps against it — forward: tap (dy,dx)
    at output (oy,ox) must read phase (u%2, v%2) at phase-pixel
    (u//2, v//2); inverse (dgrad): the same tap must route that
    contribution from dout(oy,ox) back onto the dx-output phase of the
    input pixel it consumed, at the offset that reconstructs (oy,ox)."""
    pl = (k - 2) // 2
    fwd = {slot: (ph, a, b) for ph, a, b, slot in pc._s2_phase_taps(k)}
    inv = {slot: (ph, a, b) for ph, a, b, slot in
           pc._s2_phase_taps(k, inverse=True)}
    assert set(fwd) == set(inv) == set(range(k * k))
    for dy in range(k):
        for dx in range(k):
            slot = dy * k + dx
            u, v = 2 * oy + dy - pl, 2 * ox + dx - pl  # input pixel read
            fph, fa, fb = fwd[slot]
            assert fph == (u % 2) * 2 + (v % 2)
            assert (oy + fa, ox + fb) == (u // 2, v // 2)
            iph, ia, ib = inv[slot]
            # dgrad writes dx at input pixel (u,v): phase = its parity,
            # phase-pixel (u//2, v//2), reading dout at (oy, ox)
            assert iph == (u % 2) * 2 + (v % 2)
            assert (u // 2 + ia, v // 2 + ib) == (oy, ox)


@settings(max_examples=5, deadline=None)
@given(
    arch=st.lists(
        st.tuples(
            st.sampled_from([1, 3, 5]),       # kernel
            st.sampled_from([1, 1, 2]),       # stride (1 weighted 2:1)
            st.sampled_from([4, 6, 8]),       # features
        ),
        min_size=1, max_size=3,
    ),
    seed=st.integers(0, 2**16),
)
def test_random_conv_stack_pallas_matches_xla(arch, seed):
    """Architecture-space differential (r5): a random Conv2D(+ReLU) stack
    built from nn.layers must produce the same loss and gradients whether
    its convs run on the hand-written Pallas kernels or XLA — the
    composed-geometry analog of the per-op CASES in test_pallas_conv."""
    from parallel_cnn_tpu.nn.core import Sequential
    from parallel_cnn_tpu.nn.layers import Conv2D, ReLU

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 3)).astype(np.float32))

    def build(backend):
        layers = []
        for k, s, f in arch:
            layers += [Conv2D(f, kernel=(k, k), strides=(s, s),
                              backend=backend), ReLU()]
        return Sequential(layers)

    outs = {}
    grads = {}
    for backend in ("xla", "pallas"):
        m = build(backend)
        params, state, _ = m.init(jax.random.key(seed % 97), (8, 8, 3))

        def loss(p):
            y, _ = m.apply(p, state, x, train=True)
            return jnp.sum(jnp.sin(y))

        outs[backend], grads[backend] = jax.value_and_grad(loss)(params)

    np.testing.assert_allclose(
        float(outs["pallas"]), float(outs["xla"]), rtol=1e-5, atol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(grads["pallas"]),
        jax.tree_util.tree_leaves(grads["xla"]),
        strict=True,
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        )
