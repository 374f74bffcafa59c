"""Model-zoo tests: layer library, CIFAR CNN, ResNets, the GSPMD DP
trainer, and gradient accumulation (BASELINE.json configs #3-#5)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.config import MeshConfig
from parallel_cnn_tpu.data import synthetic
from parallel_cnn_tpu.nn import cifar, layers, resnet
from parallel_cnn_tpu.parallel import mesh as mesh_lib
from parallel_cnn_tpu.train import zoo


def _shapes(model, in_shape):
    """`init` traced, nothing drawn: the parameters and the state as shapes,
    and the output's shape."""
    said = []

    def init(key):
        params, state, out_shape = model.init(key, in_shape)
        said.append(out_shape)
        return params, state

    return (*jax.eval_shape(init, jax.random.key(0)), said[0])


def test_layer_shapes():
    model = cifar.cifar_cnn()
    params, state, out_shape = _shapes(model, cifar.IN_SHAPE)
    assert out_shape == (10,)
    x = jax.ShapeDtypeStruct((4, 32, 32, 3), jnp.float32)
    logits, _ = jax.eval_shape(model.apply, params, state, x)
    assert logits.shape == (4, 10)


@pytest.mark.parametrize(
    "factory,in_shape,expected_params",
    [
        # torchvision resnet18 (ImageNet stem, 1000 classes): 11,689,512
        (lambda: resnet.resnet18(1000, cifar_stem=False), (64, 64, 3), 11_689_512),
        # torchvision resnet34 (1000 classes): 21,797,672
        (lambda: resnet.resnet34(1000, cifar_stem=False), (64, 64, 3), 21_797_672),
        # torchvision resnet50 (1000 classes): 25,557,032
        (lambda: resnet.resnet50(1000), (64, 64, 3), 25_557_032),
    ],
)
def test_resnet_param_counts_match_torchvision(factory, in_shape, expected_params):
    params, state, out_shape = _shapes(factory(), in_shape)
    assert out_shape == (1000,)
    assert resnet.num_params(params) == expected_params


def test_resnet18_cifar_forward_and_bn_state():
    model = resnet.resnet18(10, cifar_stem=True)
    params, state = jax.jit(lambda k: model.init(k, (32, 32, 3))[:2])(
        jax.random.key(0))
    x = jnp.asarray(np.random.default_rng(0).uniform(size=(2, 32, 32, 3)), jnp.float32)
    apply = jax.jit(model.apply, static_argnames="train")
    logits, new_state = apply(params, state, x, train=True)
    assert logits.shape == (2, 10)
    # train=True must move BN running stats; train=False must not
    before = jax.tree_util.tree_leaves(state)
    after = jax.tree_util.tree_leaves(new_state)
    assert any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(before, after, strict=True)
    )
    _, frozen_state = apply(params, new_state, x, train=False)
    for a, b in zip(
        jax.tree_util.tree_leaves(new_state),
        jax.tree_util.tree_leaves(frozen_state),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cifar_cnn_learns_synthetic():
    imgs, labels = synthetic.make_image_dataset(512, seed=1)
    # lr 0.02: at 0.05 the effective step (lr/(1-momentum) = 0.5) blows
    # the first epoch up to loss ~13 before recovering; the spike poisons
    # the BatchNorm running variance (it decays only as 0.9^k), so eval-
    # mode accuracy stays at chance while train-mode hits 99%.
    state, losses = zoo.train(
        cifar.cifar_cnn(),
        imgs,
        labels,
        in_shape=cifar.IN_SHAPE,
        epochs=3,
        batch_size=64,
        lr=0.02,
        verbose=False,
    )
    assert losses[-1] < losses[0] * 0.7, losses
    ev = zoo.make_eval_step(cifar.cifar_cnn())
    correct = int(
        ev(state.params, state.model_state, jnp.asarray(imgs[:256]), jnp.asarray(labels[:256]))
    )
    assert correct > 128  # way above the 10% chance floor


def test_zoo_bf16_compute_trains():
    """bf16 inputs drive bf16 compute through every nn layer (params cast
    to x.dtype in apply; f32 BatchNorm stats, f32 loss) — the zoo's mixed-
    precision mode, the dtype the TPU bench's MXU rows run in."""
    imgs, labels = synthetic.make_image_dataset(256, seed=4)
    model = cifar.cifar_cnn()
    # lr 0.01: repeated steps on one batch with momentum diverge at 0.05
    # in f32 and bf16 alike — this test pins dtype behavior, not tuning.
    opt = zoo.make_optimizer(0.01)
    st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
    step = zoo.make_train_step(model, opt)
    x = jnp.asarray(imgs[:128]).astype(jnp.bfloat16)
    y = jnp.asarray(labels[:128])
    losses = []
    for _ in range(4):
        st, loss = step(st, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    # bf16 compute actually happened: the network's outputs are bf16
    logits, _ = model.apply(st.params, st.model_state, x, train=False)
    assert logits.dtype == jnp.bfloat16
    # master weights AND BatchNorm running stats stay f32
    assert all(
        l.dtype == jnp.float32 for l in jax.tree_util.tree_leaves(st.params)
    )
    assert all(
        l.dtype == jnp.float32
        for l in jax.tree_util.tree_leaves(st.model_state)
    )


def test_grad_accumulation_matches_full_batch():
    """accum_steps=4 must produce the same update as one full batch (BN
    stats aside — compare params only, loss to tolerance)."""
    imgs, labels = synthetic.make_image_dataset(64, seed=2)
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    model = cifar.cifar_cnn()
    opt = zoo.make_optimizer(lr=0.1, momentum=0.0)

    def one_step(accum):
        st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
        step = zoo.make_train_step(model, opt, accum_steps=accum)
        st, loss = step(st, x, y)
        return st, float(loss)

    s1, l1 = one_step(1)
    s4, l4 = one_step(4)
    # BN batch stats differ between one batch of 64 and four of 16, which
    # perturbs the backward; tolerances reflect that equivalence gap.
    np.testing.assert_allclose(l1, l4, rtol=0.05)
    flat1 = jax.tree_util.tree_leaves(s1.params)
    flat4 = jax.tree_util.tree_leaves(s4.params)
    for a, b in zip(flat1, flat4, strict=True):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2, rtol=0.5
        )


def test_zoo_dp_mesh_runs_and_matches_single_device():
    """GSPMD DP on the 8-device CPU mesh computes the same step as one
    device (same global batch, compiler-inserted collectives)."""
    imgs, labels = synthetic.make_image_dataset(64, seed=3)
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    model = cifar.cifar_cnn()
    opt = zoo.make_optimizer(lr=0.1, momentum=0.0)

    mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
    step_dp = zoo.make_train_step(model, opt, mesh=mesh)
    st_dp, loss_dp = step_dp(st, x, y)

    st1 = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
    step_1 = zoo.make_train_step(model, opt)
    st_1, loss_1 = step_1(st1, x, y)

    np.testing.assert_allclose(float(loss_dp), float(loss_1), rtol=1e-5)
    # f32 reduction order differs between the sharded (all-reduce tree) and
    # single-device sums; 5e-4 abs covers that cross-sharding noise.
    for a, b in zip(
        jax.tree_util.tree_leaves(st_dp.params),
        jax.tree_util.tree_leaves(st_1.params),
        strict=True,
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_resnet50_imagenet_shape_smoke():
    """Config #5 smoke: ResNet-50, ImageNet-ish input, grad accumulation."""
    model = resnet.resnet50(num_classes=100)
    imgs, labels = synthetic.make_image_dataset(
        8, hw=(64, 64), classes=100, seed=4
    )
    opt = zoo.make_optimizer(lr=0.01)
    st = zoo.init_state(model, jax.random.key(0), (64, 64, 3), opt)
    step = zoo.make_train_step(model, opt, accum_steps=2)
    st, loss = step(st, jnp.asarray(imgs), jnp.asarray(labels))
    assert np.isfinite(float(loss))


@pytest.mark.slow
def test_resnet18_kill_and_resume_matches_continuous(tmp_path):
    """Full-ZooState checkpointing (params + SGD momentum + BN running
    stats): a run killed after epoch 1 and resumed must land bit-near the
    uninterrupted 2-epoch run — VERDICT r1 #8's zoo-scale resume story."""
    from parallel_cnn_tpu.utils.metrics import MetricsLogger

    imgs, labels = synthetic.make_image_dataset(128, seed=4)
    model = resnet.resnet18(10, cifar_stem=True)
    kw = dict(
        in_shape=cifar.IN_SHAPE,
        batch_size=32,
        lr=0.05,
        seed=9,
        verbose=False,
        eval_data=(imgs[:64], labels[:64]),
    )

    continuous, c_losses = zoo.train(model, imgs, labels, epochs=2, **kw)

    ckpt = str(tmp_path / "zoo_ckpts")
    metrics = MetricsLogger(path=str(tmp_path / "zoo.jsonl"))
    zoo.train(model, imgs, labels, epochs=1, checkpoint_dir=ckpt,
              metrics=metrics, **kw)  # "killed" after epoch 1
    resumed, r_losses = zoo.train(
        model, imgs, labels, epochs=2, checkpoint_dir=ckpt, resume=True,
        metrics=metrics, **kw,
    )
    metrics.close()

    assert len(r_losses) == 2
    np.testing.assert_allclose(r_losses, c_losses, rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(continuous),
        jax.tree_util.tree_leaves(resumed),
        strict=True,
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )
    # metrics sink captured per-epoch records incl. in-loop accuracy
    recs = [json.loads(l) for l in open(str(tmp_path / "zoo.jsonl"))]
    assert all(r["event"] == "zoo_epoch" for r in recs)
    assert all("accuracy" in r and "loss" in r for r in recs)
    assert [r["epoch"] for r in recs] == [1, 2]


def test_augment_random_crop_flip_contract():
    """Shape/dtype preserved; keyed determinism; pad=0 is flip-only (every
    output is the input or its mirror); crops are translations of the
    zero-padded input (probed via a coordinate-ramp image)."""
    from parallel_cnn_tpu.data import augment

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(size=(8, 16, 16, 3)).astype(np.float32))
    k = jax.random.key(7)

    out = augment.random_crop_flip(k, x, pad=2)
    assert out.shape == x.shape and out.dtype == x.dtype
    # same key -> identical; different key -> different
    assert np.array_equal(np.asarray(out), np.asarray(augment.random_crop_flip(k, x, pad=2)))
    assert not np.array_equal(
        np.asarray(out), np.asarray(augment.random_crop_flip(jax.random.key(8), x, pad=2))
    )

    # pad=0: flip-only — each image is itself or its horizontal mirror
    f = np.asarray(augment.random_crop_flip(k, x, pad=0))
    xn = np.asarray(x)
    for i in range(xn.shape[0]):
        assert np.array_equal(f[i], xn[i]) or np.array_equal(f[i], xn[i, :, ::-1, :])

    # crop geometry: a ramp image's interior values shift by integer
    # offsets in [-pad, pad] (un-mirroring first if needed)
    ramp = jnp.broadcast_to(
        (jnp.arange(16)[:, None, None] * 100 + jnp.arange(16)[None, :, None]).astype(jnp.float32),
        (4, 16, 16, 1),
    )
    c = np.asarray(augment.random_crop_flip(jax.random.key(3), ramp, pad=2))
    for i in range(4):
        img = c[i, :, :, 0]
        rimg = np.asarray(ramp)[i, :, :, 0]
        candidates = [img, img[:, ::-1]]
        ok = False
        for cand in candidates:
            # interior pixel (8,8) encodes its source coordinate
            v = cand[8, 8]
            dy, dx = int(v // 100) - 8, int(v % 100) - 8
            if abs(dy) <= 2 and abs(dx) <= 2:
                src = np.zeros((20, 20))
                src[2:18, 2:18] = rimg
                win = src[2 + dy : 18 + dy, 2 + dx : 18 + dx]
                if np.array_equal(cand, win):
                    ok = True
                    break
        assert ok, f"image {i} is not a crop/flip of the padded input"


def test_zoo_trains_with_augmentation_and_cosine_schedule():
    """The production-trainer combo: on-device crop+flip augmentation and
    warmup+cosine LR — trains end-to-end and still learns."""
    imgs, labels = synthetic.make_image_dataset(256, seed=5)
    state, losses = zoo.train(
        cifar.cifar_cnn(),
        imgs,
        labels,
        in_shape=cifar.IN_SHAPE,
        epochs=3,
        batch_size=64,
        lr=0.05,
        lr_schedule="cosine",
        warmup_steps=2,
        augment=True,
        verbose=False,
    )
    assert losses[-1] < losses[0], losses


def test_make_optimizer_schedules_shape_the_updates():
    """Warmup makes the first update smaller than the post-warmup one;
    cosine makes the final update smaller than the peak one."""
    params = {"w": jnp.ones((4,))}
    g = {"w": jnp.full((4,), 0.5)}

    def update_norms(opt, n):
        st = opt.init(params)
        norms = []
        for _ in range(n):
            up, st = opt.update(g, st, params)
            norms.append(float(jnp.linalg.norm(up["w"])))
        return norms

    warm = update_norms(zoo.make_optimizer(0.1, momentum=0.0, warmup_steps=4), 6)
    assert warm[0] < warm[5] and warm[5] == pytest.approx(0.1 * 0.5 * 2, rel=1e-5)

    cos = update_norms(
        zoo.make_optimizer(0.1, momentum=0.0, schedule="cosine", warmup_steps=2, total_steps=10), 10
    )
    assert max(cos) == pytest.approx(max(cos[:4]))  # peak near warmup end
    assert cos[-1] < max(cos) * 0.2  # decayed

    with pytest.raises(ValueError):
        zoo.make_optimizer(0.1, schedule="cosine")
    with pytest.raises(ValueError):
        zoo.make_optimizer(0.1, schedule="nope")


def test_resume_continues_cosine_schedule_and_augment_stream(tmp_path):
    """The docstring's resume guarantees, pinned: the cosine schedule's
    step count rides in opt_state and the augmentation keys derive from
    (seed, global step), so a run resumed from the epoch-1 checkpoint must
    reproduce the uninterrupted run's epoch 2 exactly. The kill is
    simulated by deleting the epoch-2 checkpoint and resuming from the
    epoch-1 one — same `epochs` both times, so the schedule horizon
    matches a genuinely killed run (unlike training with fewer epochs,
    which would build a shorter cosine horizon)."""
    import os

    imgs, labels = synthetic.make_image_dataset(128, seed=6)
    model = resnet.resnet18(10, cifar_stem=True)
    ckpt = str(tmp_path / "sched_ckpts")
    kw = dict(
        in_shape=cifar.IN_SHAPE,
        epochs=2,
        batch_size=32,
        lr=0.05,
        lr_schedule="cosine",
        warmup_steps=2,
        augment=True,
        seed=11,
        verbose=False,
        checkpoint_dir=ckpt,
    )

    continuous, c_losses = zoo.train(model, imgs, labels, **kw)

    os.remove(os.path.join(ckpt, "ckpt_2.npz"))  # "killed" during epoch 2
    resumed, r_losses = zoo.train(model, imgs, labels, resume=True, **kw)

    assert len(r_losses) == 2
    np.testing.assert_allclose(r_losses, c_losses, rtol=1e-4)
    for a, b in zip(
        jax.tree_util.tree_leaves(continuous),
        jax.tree_util.tree_leaves(resumed),
        strict=True,
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
        )


def test_zoo_augment_composes_with_dp_mesh():
    """Augmentation is traced inside the GSPMD-sharded step, so it must
    run with the batch sharded over the data axis (each device augments
    its own shard) — the composition cell behind make_train_step's
    docstring claim."""
    imgs, labels = synthetic.make_image_dataset(256, seed=7)
    mesh = mesh_lib.make_mesh(MeshConfig(data=4, model=1))
    # lr 0.005: crop+flip jitter on the asymmetric synthetic prototypes
    # roughly doubles the effective class count, and with momentum 0.9 any
    # lr ≥ 0.01 diverges inside the 8 steps this test runs.
    state, losses = zoo.train(
        cifar.cifar_cnn(),
        imgs,
        labels,
        in_shape=cifar.IN_SHAPE,
        epochs=2,
        batch_size=64,
        lr=0.005,
        augment=True,
        mesh=mesh,
        verbose=False,
    )
    assert len(losses) == 2 and all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_zoo_native_loader_trains():
    """loader="native" feeds the zoo trainer from the C++ prefetch ring
    (or its bit-identical NumPy twin without a toolchain) — the data
    runtime serving the shapes the rest of the framework reached
    (VERDICT r3 next #5). Determinism: two runs with the same seed give
    the same loss trajectory."""
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar

    imgs, labels = synthetic.make_image_dataset(96, seed=4)
    model = cifar.cifar_cnn()

    def run():
        # lr 0.01: batch 32 with momentum 0.9 diverges at 0.05 within the
        # 6 steps this test runs (loss doubles instead of halving).
        _, losses = zoo.train(
            model, imgs, labels, in_shape=cifar.IN_SHAPE,
            epochs=2, batch_size=32, lr=0.01, seed=11,
            loader="native", verbose=False,
        )
        return losses

    l1, l2 = run(), run()
    assert len(l1) == 2 and all(np.isfinite(l) for l in l1)
    assert l1 == l2
    assert l1[1] < l1[0]  # it actually learns


def test_vgg16_param_counts_match_torchvision():
    """VGG-16 (round 4: the classic plain-conv zoo family). Learnable
    param counts vs torchvision's canonical models (BN running stats are
    buffers there and live in `state` here — excluded both sides):
    vgg16 = 138,357,544; vgg16_bn = 138,365,992."""
    from parallel_cnn_tpu.nn import vgg

    for bn, expected in ((False, 138_357_544), (True, 138_365_992)):
        m = vgg.vgg16(1000, batch_norm=bn, cifar_head=False)
        # eval_shape: counting ~138M params must not materialize ~550 MB
        # of He samples per variant — shapes alone carry the count.
        params, _, _ = jax.eval_shape(
            lambda k, m=m: m.init(k, (224, 224, 3)), jax.random.key(0)
        )
        # (no out_shape assert: eval_shape abstracts the static ints; the
        # classifier head is pinned by the 4096·1000+1000 term anyway)
        assert resnet.num_params(params) == expected


def test_vgg16_cifar_trains():
    """Compact-head VGG-16 runs a real train step at CIFAR shape, on both
    conv backends (every conv is 3x3 stride-1 — the pallas kernel
    family's cheapest case)."""
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar, vgg

    imgs, labels = synthetic.make_image_dataset(16, seed=6)
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    losses = {}
    for backend in ("xla", "pallas"):
        m = vgg.vgg16(10, conv_backend=backend)
        opt = zoo.make_optimizer(0.05)
        st = zoo.init_state(m, jax.random.key(0), cifar.IN_SHAPE, opt)
        st, loss = zoo.make_train_step(m, opt)(st, x, y)
        losses[backend] = float(loss)
        assert np.isfinite(losses[backend])
    assert abs(losses["xla"] - losses["pallas"]) < 1e-3


def test_batchnorm_normalizes_and_bf16_tracks_f32():
    """Pin BatchNorm's numerics directly (the integration tests only
    assert loss-goes-down): train-mode output is ~N(0,1) per channel at
    default scale/bias, matches the textbook formula, and the bf16 path
    (elementwise arithmetic at x.dtype, f32 statistics) tracks f32."""
    bn = layers.BatchNorm()
    params, state, _ = bn.init(jax.random.key(0), (8, 8, 16))
    rng = np.random.default_rng(3)
    # per-channel means/stds far from 0/1, incl. a large-|mean| channel
    base = rng.standard_normal((32, 8, 8, 16)).astype(np.float32)
    offsets = np.linspace(-50.0, 50.0, 16, dtype=np.float32)
    scales = np.linspace(0.5, 4.0, 16, dtype=np.float32)
    x = jnp.asarray(base * scales + offsets)

    y, new_state = bn.apply(params, state, x, train=True)
    ym = np.asarray(jnp.mean(y, axis=(0, 1, 2)))
    yv = np.asarray(jnp.var(y, axis=(0, 1, 2)))
    np.testing.assert_allclose(ym, np.zeros(16), atol=1e-4)
    np.testing.assert_allclose(yv, np.ones(16), rtol=1e-3)
    # textbook formula at f32
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    ref = (x - mean) / jnp.sqrt(var + bn.eps)
    # 1e-4 while the layer took its variance in two passes. Both moments
    # now come from one read (layers._batch_moments), and the channels with
    # |mean| / std = 100 here pay 6e-8 * 1e4 * sqrt(64 positions) = 5e-4
    # of their variance to cancellation: 3.7e-4 read in the output.
    # tests/test_batchnorm_moments.py holds the bounds by ratio.
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3)
    # running stats moved toward the batch stats
    assert float(jnp.max(jnp.abs(new_state["mean"] - 0.1 * mean))) < 1e-3

    y16, _ = bn.apply(params, state, x.astype(jnp.bfloat16), train=True)
    assert y16.dtype == jnp.bfloat16
    # The bf16 error floor here is the INPUT's own quantization: for the
    # worst channel (|mean|=50, std=0.5) x carries ulp(50)/std = 0.5
    # normalized units of noise before BN does anything. Measured max
    # error: 0.28 for the subtract-first arithmetic (vs 0.40 for the
    # rejected x·inv + shift folding, which also rounds the product at
    # |x·inv| and the shift at |mean·inv|); the bound keeps headroom
    # over the input floor without admitting a 2× regression.
    np.testing.assert_allclose(
        np.asarray(y16, np.float32), np.asarray(y), atol=0.35
    )


# ------------------------------------- the device loader: store + select_batch

def _eager_batch(images, labels, perm, i, batch):
    """The loop's indexing before `select_batch`: the batches it must equal."""
    idx = perm[i * batch : (i + 1) * batch]
    return images[idx], labels[idx]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "in_shape,layout",
    [((32, 32, 3), "rows128"), ((16, 16, 8), "rows128"),
     ((28, 28, 1), "indexed"), ((8, 8, 3), "indexed")],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else v,
)
def test_select_batch_equals_eager_indexing_on_both_sides_of_the_rule(
        in_shape, layout, dtype):
    # neither a whole number of 128-sample blocks (the conversion's) nor of
    # 128-sample pieces (the selection's): both last ones overlap
    n, batch = 400, 176
    rng = np.random.default_rng(7)
    images = jnp.asarray(rng.uniform(size=(n, *in_shape)), dtype)
    labels = jnp.asarray(rng.integers(0, 10, n), jnp.int32)
    store, got_layout, over = zoo.resident_store(images)
    assert over is None
    assert got_layout == layout
    f = int(np.prod(in_shape))
    assert store.shape == ((n, 1, f // 128, 128) if layout == "rows128" else images.shape)
    assert store.dtype == images.dtype
    for epoch in range(2):
        perm = jax.random.permutation(jax.random.key(5 + epoch), n)
        for i in range(n // batch):
            bx, by = zoo.select_batch(store, labels, perm, i, batch=batch,
                                      in_shape=in_shape)
            wx, wy = _eager_batch(images, labels, perm, i, batch)
            assert bx.shape == wx.shape and bx.dtype == wx.dtype
            assert np.array_equal(np.asarray(bx, np.float32), np.asarray(wx, np.float32))
            assert np.array_equal(np.asarray(by), np.asarray(wy))


def _train_two_epochs(images, labels, **kw):
    return zoo.train(
        cifar.cifar_cnn(), images, labels, in_shape=cifar.IN_SHAPE, epochs=2,
        batch_size=32, lr=0.02, seed=3, verbose=False, **kw)


def test_device_loader_losses_equal_eager_indexing_to_the_bit(monkeypatch):
    imgs, labels = synthetic.make_image_dataset(128, seed=4)  # 32x32x3: rows128
    _, losses = _train_two_epochs(imgs, labels)
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    monkeypatch.setattr(
        zoo, "select_batch",
        lambda store, lab, perm, i, *, batch, in_shape, over: _eager_batch(x, y, perm, i, batch))
    _, eager = _train_two_epochs(imgs, labels)
    assert losses == eager and all(np.isfinite(losses))


def test_select_batch_is_one_executable_and_the_loader_says_its_layout(tmp_path):
    from parallel_cnn_tpu import obs as obs_lib

    imgs, labels = synthetic.make_image_dataset(128, seed=4)
    zoo.select_batch.clear_cache()
    journal = obs_lib.EventJournal(str(tmp_path / "zoo.jsonl"))
    bundle = obs_lib.Obs(obs_lib.NOOP_TRACER, obs_lib.MetricsRegistry(), journal,
                         enabled=True)
    _train_two_epochs(imgs, labels, obs=bundle)
    assert zoo.select_batch._cache_size() == 1  # the step index is traced
    journal.close()
    events = [e for e in obs_lib.read_journal(journal.path) if e["kind"] == "zoo_loader"]
    assert len(events) == 1
    assert (events[0]["layout"], events[0]["rows"], events[0]["row_bytes"]) == (
        "rows128", 128, 32 * 32 * 3 * 4)


@pytest.mark.parametrize(
    "in_shape,over_mesh",
    [((32, 32, 3), True),    # 8 slabs of 4 image rows = 3 lane rows each
     ((16, 16, 8), True),    # 2 image rows = 2 lane rows
     ((4, 32, 8), False),    # rows128, but 4 image rows do not cut 8 ways
     ((28, 28, 1), False)],  # indexed: never over the mesh
    ids=["32x32x3", "16x16x8", "4x32x8", "28x28x1"],
)
@pytest.mark.parametrize("hier", [False, True], ids=["data8", "host2xdata4"])
def test_select_batch_over_a_mesh_equals_eager_indexing(in_shape, over_mesh, hier):
    """Under a mesh the rows128 store lies across it where a sample cuts
    into slabs of whole 128-lane rows; the batch then leaves already in
    `shard_batch`'s sharding, and is the same batch."""
    n, batch = 400, 176
    mesh = (mesh_lib.make_hier_mesh(n_hosts=2) if hier
            else mesh_lib.make_mesh(MeshConfig(data=8, model=1)))
    rng = np.random.default_rng(11)
    images = jnp.asarray(rng.uniform(size=(n, *in_shape)), jnp.bfloat16)
    labels = jnp.asarray(rng.integers(0, 10, n), jnp.int32)
    store, _, over = zoo.resident_store(images, mesh)
    assert (over is mesh) == over_mesh
    want = mesh_lib.batch_sharding(mesh)
    perm = jax.random.permutation(jax.random.key(9), n)
    for i in range(n // batch):
        bx, by = zoo.select_batch(store, labels, perm, i, batch=batch,
                                  in_shape=in_shape, over=over)
        wx, wy = _eager_batch(images, labels, perm, i, batch)
        assert np.array_equal(np.asarray(bx, np.float32), np.asarray(wx, np.float32))
        assert np.array_equal(np.asarray(by), np.asarray(wy))
        if over_mesh:
            assert bx.sharding.is_equivalent_to(want, bx.ndim)
            assert by.sharding.is_equivalent_to(want, by.ndim)
            assert max(s.data.shape[0] for s in bx.addressable_shards) == batch // 8
    if over_mesh:  # 1/8 of the set a chip: its slab of every sample, in whole tiles
        assert {s.data.shape for s in store.addressable_shards} == {(n, 1, 8, 128)}


def test_device_loader_under_a_mesh_places_batch_and_state_as_before(monkeypatch):
    class Rec:
        def record(self, **rec):
            self.last = rec

    imgs, labels = synthetic.make_image_dataset(128, seed=4)
    mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    rec = Rec()
    _, losses = _train_two_epochs(imgs, labels, mesh=mesh, metrics=rec)
    everywhere = ",".join(str(d.id) for d in sorted(jax.devices(), key=lambda d: d.id))
    assert rec.last["state_devices"] == everywhere
    assert rec.last["batch_devices"] == everywhere
    # and the losses are those of batches indexed eagerly and laid out by
    # shard_batch alone, to the bit
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    monkeypatch.setattr(
        zoo, "select_batch",
        lambda store, lab, perm, i, *, batch, in_shape, over: _eager_batch(x, y, perm, i, batch))
    _, eager = _train_two_epochs(imgs, labels, mesh=mesh)
    assert losses == eager and all(np.isfinite(losses))
