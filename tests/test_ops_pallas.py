"""Differential tests: Pallas kernel path (path B) vs jnp reference path (path A).

SURVEY.md §7 stage 4: the Pallas kernels must reproduce the same reference
numerics contract (§2.1) as ops/reference.py — these tests diff every stage
and the full batched grad computation. On CPU the kernels run in Pallas
interpret mode (ops/pallas.py:_interpret); the same code compiles
via Mosaic on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.models import lenet_ref
from parallel_cnn_tpu.ops import pallas as pk
from parallel_cnn_tpu.ops import reference as ops

BATCH = 8


@pytest.fixture(scope="module")
def params():
    return lenet_ref.init(jax.random.key(7))


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(42)
    xs = jnp.asarray(rng.uniform(0, 1, (BATCH, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (BATCH,)).astype(np.int32))
    return xs, ys


def tree_allclose(a, b, atol=1e-5):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    for x, y in zip(flat_a, flat_b, strict=True):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol, rtol=1e-5)


def test_conv_fwd_matches_reference(params, batch):
    xs, _ = batch
    pre, out = pk.conv_fwd(xs, params["c1"]["w"], params["c1"]["b"])
    ref_pre = jax.vmap(
        lambda x: ops.conv_c1_forward(x, params["c1"]["w"], params["c1"]["b"])
    )(xs)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(ref_pre), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jax.nn.sigmoid(ref_pre)), atol=1e-6
    )


def test_pool_window_pack_roundtrip(batch):
    xs, _ = batch
    t = jnp.broadcast_to(xs[:, None, :24, :24], (BATCH, 6, 24, 24))
    assert jnp.allclose(pk.unpack_pool_windows(pk.pack_pool_windows(t)), t)


def test_full_forward_matches_reference(params, batch):
    xs, _ = batch
    acts = pk.forward(params, xs)
    ref_acts = jax.vmap(lambda x: ops.forward(params, x))(xs)
    for got, want, name in zip(acts, ref_acts, ops.Activations._fields):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, err_msg=name
        )


def test_predict_matches_reference(params, batch):
    xs, _ = batch
    np.testing.assert_array_equal(
        np.asarray(pk.predict(params, xs)),
        np.asarray(jax.vmap(lambda x: ops.predict(params, x))(xs)),
    )


def test_batched_grads_match_reference(params, batch):
    xs, ys = batch
    err_p, grads_p = pk.batched_value_and_ref_grads(params, xs, ys)
    errs, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(
        params, xs, ys
    )
    err_a = jnp.mean(errs)
    grads_a = jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
    np.testing.assert_allclose(float(err_p), float(err_a), atol=1e-6)
    tree_allclose(grads_p, grads_a, atol=1e-5)


def test_batched_grads_jit_compatible(params, batch):
    """The Pallas path must compose with jit (and therefore scan/shard_map)."""
    xs, ys = batch
    err_j, grads_j = jax.jit(pk.batched_value_and_ref_grads)(params, xs, ys)
    err_e, grads_e = pk.batched_value_and_ref_grads(params, xs, ys)
    np.testing.assert_allclose(float(err_j), float(err_e), atol=1e-6)
    tree_allclose(grads_j, grads_e, atol=1e-6)


def test_staged_tier_matches_fused_tier(params, batch):
    """The per-op kernel library (staged tier, one pallas_call per
    reference kernel) and the fused megakernel must agree — the same
    differential the reference implies between its Sequential and CUDA
    backends, here between our two compiled tiers."""
    xs, ys = batch
    err_s, grads_s = pk.staged_value_and_ref_grads(params, xs, ys)
    err_f, grads_f = pk.fused_value_and_ref_grads(params, xs, ys)
    np.testing.assert_allclose(float(err_s), float(err_f), atol=1e-6)
    tree_allclose(grads_s, grads_f, atol=1e-5)


def test_fused_multi_grid_step_accumulation(monkeypatch):
    """Shrink FUSED_BLOCK so the fused tier runs a MULTI-step grid with a
    padded tail (grid=3 with 2 pad rows) — exercising the cross-grid-step
    accumulator init/accumulate logic and the Mp persistence that the
    single-block small-batch tests never reach (on TPU chip_smoke.py's
    lenet leg runs grid=16 at b2048; this is the CPU-harness equivalent)."""
    monkeypatch.setattr(pk, "FUSED_BLOCK", 4)
    params = lenet_ref.init(jax.random.key(3))
    rng = np.random.default_rng(9)
    n = 10  # pads to 12 = 3 blocks of 4
    xs = jnp.asarray(rng.uniform(0, 1, (n, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    err_f, grads_f = pk.fused_value_and_ref_grads(params, xs, ys)
    errs, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(
        params, xs, ys
    )
    np.testing.assert_allclose(float(err_f), float(jnp.mean(errs)), atol=1e-6)
    tree_allclose(
        grads_f, jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
    )


def test_uneven_batch_pads_and_masks():
    """Batches that don't tile CONV_BLOCK are zero-padded; the pad rows must
    contribute exactly nothing to the error or any gradient."""
    params = lenet_ref.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.uniform(0, 1, (6, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (6,)).astype(np.int32))
    err_p, grads_p = pk.batched_value_and_ref_grads(params, xs, ys)
    errs, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(
        params, xs, ys
    )
    np.testing.assert_allclose(float(err_p), float(jnp.mean(errs)), atol=1e-6)
    tree_allclose(
        grads_p, jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)
    )
    acts = pk.forward(params, xs)
    assert acts.out_f.shape == (6, 10)


def test_fused_bf16_store_vs_f32_store(monkeypatch):
    """Compiled-mode guard for the fused path's bf16 x25 store (ADVICE r3).

    The "zero numerics cost" claim rests on an XLA lowering detail:
    conv_general_dilated_patches' MXU passes already quantize to bf16
    under Precision.DEFAULT, so storing x25 in bf16 changes nothing. If a
    future XLA lowers patch extraction as pure data movement, the cast
    silently becomes a real precision loss — this test diffs the grads of
    the bf16-store vs forced-f32-store fused step ON-CHIP and fails if
    they drift past f32-reassociation noise. TPU-only: in interpret mode
    the bf16 store is disabled by construction (both runs identical).
    """
    from parallel_cnn_tpu.utils.backend import is_tpu

    if not is_tpu():
        pytest.skip("compiled-Mosaic lowering guard; interpret mode "
                    "disables the bf16 store by construction")
    params = lenet_ref.init(jax.random.key(5))
    rng = np.random.default_rng(11)
    xs = jnp.asarray(rng.uniform(0, 1, (128, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (128,)).astype(np.int32))
    err_bf16, grads_bf16 = pk.fused_value_and_ref_grads(params, xs, ys)
    monkeypatch.setattr(pk, "_FORCE_X25_F32", True)
    err_f32, grads_f32 = pk.fused_value_and_ref_grads(params, xs, ys)
    np.testing.assert_allclose(float(err_bf16), float(err_f32), atol=1e-5)
    tree_allclose(grads_bf16, grads_f32, atol=1e-4)
