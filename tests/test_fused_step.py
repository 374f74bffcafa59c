"""Round-7 fused training step: update kernels vs optax/oracle, fused
tail vs the unfused composition, update-on-arrival end-to-end parity,
and the dynamic loss-scaling overflow/skip policy.

Tolerance notes: the f32 update kernels compute the same expressions as
optax/XLA but compile separately, so FMA contraction can differ by an
ulp — float comparisons are to a few-ulp relative tolerance, never
bit-exact across compilers. What IS bit-exact is pinned as such: a
skipped overflow step must leave params/momentum bit-identical, and the
LeNet fused step reproduces the unfused `apply_grad ∘ mean` composition
exactly on this toolchain. The oracle comparisons reuse
test_ops_reference's float64-NumPy tolerance (atol 2e-4). bf16 rows are
bounded at ≤1e-2 relative, the documented activation-path error budget.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import oracle
from parallel_cnn_tpu.config import CommConfig, FusedStepConfig, MeshConfig
from parallel_cnn_tpu.ops import pallas_tail, pallas_update
from parallel_cnn_tpu.parallel import mesh as mesh_lib
from parallel_cnn_tpu.resilience.sentinel import Sentinel
from parallel_cnn_tpu.train import step as step_lib
from parallel_cnn_tpu.train import zoo

pytestmark = pytest.mark.fused_step


def tree_allclose(a, b, atol=1e-5):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.allclose(np.asarray(x), np.asarray(y), atol=atol)
        for x, y in zip(la, lb)
    )


def tree_bitequal(a, b):
    return all(
        bool(jnp.all(x == y))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


def tree_copy(t):
    return jax.tree_util.tree_map(jnp.copy, t)


# ---------------------------------------------------------------------------
# Fused update kernels (ops/pallas_update.py)
# ---------------------------------------------------------------------------


class TestUpdateKernels:
    def test_fused_sgd_matches_expression(self, rng):
        n = 5 * 128 + 37  # odd tail exercises the lane padding
        p = jnp.asarray(rng.normal(size=n).astype(np.float32))
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        got = pallas_update.fused_sgd(p, g, lr=0.05, scale=0.25)
        want = p - 0.05 * (g * 0.25)
        # atol floors the comparison at an ulp of the operand magnitude:
        # the session rng's stream position varies with which tests ran
        # before this one, and elements near zero can turn the 1-2 ulp
        # FMA-contraction diffs (module docstring) into >3e-7 relative.
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=3e-7, atol=1e-6
        )

    def test_fused_sgd_momentum_matches_optax(self, rng):
        n = 3 * 128 + 5
        p = jnp.asarray(rng.normal(size=n).astype(np.float32))
        m = jnp.asarray(rng.normal(size=n).astype(np.float32))
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        lr, beta = 0.05, 0.9
        tx = optax.sgd(lr, momentum=beta)
        state = tx.init(p)
        state = jax.tree_util.tree_map(
            lambda leaf: m if leaf.shape == m.shape else leaf, state
        )
        upd, _ = tx.update(g, state, p)
        p_want = optax.apply_updates(p, upd)
        m_want = g + beta * m
        p_got, m_got = pallas_update.fused_sgd_momentum(
            p, m, g, lr=lr, momentum=beta, scale=1.0
        )
        # atol floors the comparisons at an ulp of the operand magnitude —
        # elements near zero make a pure-relative bound meaningless (the
        # only differences are FMA-contraction ulps; see module docstring).
        np.testing.assert_allclose(
            np.asarray(m_got), np.asarray(m_want), rtol=3e-7, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(p_got), np.asarray(p_want), rtol=3e-7, atol=1e-6
        )

    def test_scale_folds_into_gradient(self, rng):
        n = 128
        p = jnp.asarray(rng.normal(size=n).astype(np.float32))
        m = jnp.zeros((n,), jnp.float32)
        g = jnp.asarray(rng.normal(size=n).astype(np.float32))
        # scale applies to g BEFORE the momentum blend (grad unscaling),
        # not to the final update — pin it against the wrong placement.
        p_got, m_got = pallas_update.fused_sgd_momentum(
            p, m, g, lr=0.1, momentum=0.9, scale=0.5
        )
        np.testing.assert_allclose(
            np.asarray(m_got), np.asarray(g * 0.5), rtol=3e-7
        )
        np.testing.assert_allclose(
            np.asarray(p_got), np.asarray(p - 0.1 * (g * 0.5)), rtol=3e-7,
            atol=1e-7,
        )

    def test_tree_sgd_structure_and_values(self, rng):
        params = {
            "a": jnp.asarray(rng.normal(size=(7, 11)).astype(np.float32)),
            "b": [
                jnp.asarray(rng.normal(size=(130,)).astype(np.float32)),
                jnp.float32(rng.normal()),
            ],
        }
        grads = jax.tree_util.tree_map(
            lambda p: jnp.asarray(
                rng.normal(size=p.shape).astype(np.float32)
            ),
            params,
        )
        out = pallas_update.tree_sgd(params, grads, lr=0.1, scale=0.5)
        assert jax.tree_util.tree_structure(out) == (
            jax.tree_util.tree_structure(params)
        )
        want = jax.tree_util.tree_map(
            lambda p, g: p - 0.1 * (g * 0.5), params, grads
        )
        for x, y in zip(
            jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(want)
        ):
            assert x.shape == y.shape
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=3e-7, atol=1e-7
            )


# ---------------------------------------------------------------------------
# LeNet engine: fused_batched_step vs the unfused step and the oracle
# ---------------------------------------------------------------------------


def _lenet_batch(rng, n=8):
    x = rng.uniform(0.0, 1.0, (n, 28, 28))
    y = rng.integers(0, 10, (n,))
    return x, y


class TestLenetFusedStep:
    def test_bit_matches_unfused_step_f32(self, rng):
        params = oracle.random_params(np.random.default_rng(3))
        x, y = _lenet_batch(rng)
        jx = jnp.asarray(x, jnp.float32)
        jy = jnp.asarray(y, jnp.int32)
        p_ref, e_ref = step_lib.batched_step(
            tree_copy(params), jx, jy, 0.1
        )
        p_fused, e_fused = step_lib.fused_batched_step(
            tree_copy(params), jx, jy, 0.1
        )
        assert float(e_ref) == float(e_fused)
        assert tree_bitequal(p_ref, p_fused)

    def test_matches_float64_oracle(self, rng):
        src = np.random.default_rng(4)
        params = oracle.random_params(src)
        x, y = _lenet_batch(rng)
        # float64 NumPy reference: mean of per-sample reference grads,
        # then the reference's ascent update p += DT·mean_g.
        gsum = None
        for i in range(x.shape[0]):
            acts = oracle.forward(params, x[i])
            _, g = oracle.backward(params, acts, int(y[i]))
            gsum = (
                g
                if gsum is None
                else {
                    lk: {k: gsum[lk][k] + g[lk][k] for k in g[lk]}
                    for lk in g
                }
            )
        n = x.shape[0]
        want = {
            lk: {
                k: params[lk][k] + oracle.DT * (gsum[lk][k] / n)
                for k in params[lk]
            }
            for lk in params
        }
        got, _ = step_lib.fused_batched_step(
            tree_copy(params),
            jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.int32),
            oracle.DT,
        )
        for lk in want:
            for k in want[lk]:
                np.testing.assert_allclose(
                    np.asarray(got[lk][k]), want[lk][k],
                    rtol=0, atol=2e-4, err_msg=f"update {lk}/{k}",
                )

    def test_bf16_within_documented_bound(self, rng):
        params = oracle.random_params(np.random.default_rng(5))
        x, y = _lenet_batch(rng)
        jx = jnp.asarray(x, jnp.float32)
        jy = jnp.asarray(y, jnp.int32)
        _, e32 = step_lib.fused_batched_step(
            tree_copy(params), jx, jy, 0.1
        )
        _, e16 = step_lib.fused_batched_step(
            tree_copy(params), jx, jy, 0.1, compute_dtype="bfloat16"
        )
        np.testing.assert_allclose(float(e16), float(e32), rtol=1e-2)

    def test_batched_step_fn_dispatch(self):
        assert step_lib.batched_step_fn("reference") is (
            step_lib.batched_step
        )
        assert step_lib.batched_step_fn("reference", fused=True) is (
            step_lib.fused_batched_step
        )
        # The Pallas megakernel step is one fused program already — the
        # fused flag must not reroute it.
        assert step_lib.batched_step_fn("pallas", fused=True) is (
            step_lib.pallas_batched_step
        )


# ---------------------------------------------------------------------------
# Fused loss tail (ops/pallas_tail.py)
# ---------------------------------------------------------------------------


def _tail_data(rng, B=16, H=8, W=8, C=128, K=10, relu_ties=True):
    x = rng.normal(size=(B, H, W, C)).astype(np.float32)
    if relu_ties:
        # Post-ReLU zeros make max-pool ties COMMON — the tie-routing
        # cases where a wrong gradient rule diverges from XLA.
        x = np.maximum(x, 0.0)
    D = (H // 2) * (W // 2) * C
    w = (rng.normal(size=(D, K)) * 0.01).astype(np.float32)
    b = (rng.normal(size=(K,)) * 0.01).astype(np.float32)
    y = rng.integers(0, K, (B,)).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), jnp.asarray(y)


def _unfused_max2_loss(x, w, b, y):
    pooled = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
    )
    logits = pooled.reshape(x.shape[0], -1) @ w + b
    return zoo.cross_entropy(logits, y)


class TestFusedTail:
    def test_max2_loss_and_grads_match_unfused_f32(self, rng):
        x, w, b, y = _tail_data(rng)
        lf, gf = jax.value_and_grad(
            lambda x, w, b: pallas_tail.fused_tail_loss(
                x, w, b, y, pool="max2"
            ),
            argnums=(0, 1, 2),
        )(x, w, b)
        lu, gu = jax.value_and_grad(
            _unfused_max2_loss, argnums=(0, 1, 2)
        )(x, w, b, y)
        assert abs(float(lf) - float(lu)) <= 1e-5
        for a, bb, name in zip(gf, gu, ("dx", "dw", "db")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=0, atol=1e-5,
                err_msg=name,
            )

    def test_gap_matches_unfused(self, rng):
        x, _, b, y = _tail_data(rng, relu_ties=False)
        C, K = x.shape[-1], 10
        w = jnp.asarray((rng.normal(size=(C, K)) * 0.01).astype(np.float32))

        def unfused(x, w, b):
            logits = jnp.mean(x, axis=(1, 2)) @ w + b
            return zoo.cross_entropy(logits, y)

        lf, gf = jax.value_and_grad(
            lambda x, w, b: pallas_tail.fused_tail_loss(
                x, w, b, y, pool="gap"
            ),
            argnums=(0, 1, 2),
        )(x, w, b)
        lu, gu = jax.value_and_grad(unfused, argnums=(0, 1, 2))(x, w, b)
        assert abs(float(lf) - float(lu)) <= 1e-5
        for a, bb in zip(gf, gu):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=0, atol=1e-5
            )

    @pytest.mark.parametrize("pool,batch", [
        ("max2", 8), ("gap", 8), ("none", 8),
        # A batch that is not a sublane-tile multiple is zero-padded
        # inside the kernel wrapper; the pad rows must not reach the mean.
        ("max2", 6), ("gap", 3),
    ])
    def test_kernel_path_matches_xla_path(self, rng, monkeypatch, pool,
                                          batch):
        # _FORCE_KERNEL runs the Pallas kernel (interpret mode on CPU)
        # where the platform rule would pick the XLA twin — the
        # differential test of the kernel itself.
        x, w, b, y = _tail_data(rng, B=batch)
        d = {"max2": w.shape[0], "gap": x.shape[-1],
             "none": x[0].size}[pool]
        w = jnp.asarray(
            (rng.normal(size=(d, w.shape[1])) * 0.01).astype(np.float32)
        )
        f = jax.value_and_grad(
            lambda x, w, b: pallas_tail.fused_tail_loss(
                x, w, b, y, pool=pool
            ),
            argnums=(0, 1, 2),
        )
        l_xla, g_xla = f(x, w, b)
        monkeypatch.setattr(pallas_tail, "_FORCE_KERNEL", True)
        l_k, g_k = jax.jit(f)(x, w, b)
        assert abs(float(l_xla) - float(l_k)) <= 1e-5
        for a, bb in zip(g_xla, g_k):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(bb), rtol=0, atol=1e-5
            )

    def test_bf16_within_documented_bound(self, rng):
        x, w, b, y = _tail_data(rng)
        l32 = pallas_tail.fused_tail_loss(x, w, b, y, pool="max2")
        l16 = pallas_tail.fused_tail_loss(
            x.astype(jnp.bfloat16),
            w.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            y,
            pool="max2",
        )
        np.testing.assert_allclose(float(l16), float(l32), rtol=1e-2)

    def test_split_tail_recognition(self):
        from parallel_cnn_tpu.nn import cifar, core, layers

        split = pallas_tail.split_tail(cifar.cifar_cnn())
        assert split is not None and split.pool == "max2"
        gap_model = core.Sequential(
            [layers.Conv2D(4, (3, 3)), layers.GlobalAvgPool(),
             layers.Dense(10)]
        )
        assert pallas_tail.split_tail(gap_model).pool == "gap"
        flat_model = core.Sequential(
            [layers.Conv2D(4, (3, 3)), layers.Flatten(), layers.Dense(10)]
        )
        assert pallas_tail.split_tail(flat_model).pool == "none"
        # Unsupported heads degrade (vgg16's full FC head ends
        # Dense→ReLU→Dense — no pool/flatten suffix to fuse).
        no_match = core.Sequential(
            [layers.Flatten(), layers.Dense(16), layers.ReLU(),
             layers.Dense(10)]
        )
        assert pallas_tail.split_tail(no_match) is None


# ---------------------------------------------------------------------------
# Zoo end-to-end: fused tail + update-on-arrival vs the unfused ring step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh8(host_devices):
    return mesh_lib.make_mesh(MeshConfig(data=8, model=1))


def _tiny_model():
    from parallel_cnn_tpu.nn import core, layers

    return core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.BatchNorm(), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])


TINY_SHAPE = (8, 8, 3)
_COMM = dict(impl="ring", bucket_bytes=2048, overlap=True)


def _tiny_batch(rng, n=16):
    x = jnp.asarray(rng.normal(size=(n,) + TINY_SHAPE).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    return x, y


def _run_unfused(mesh, x, y, steps=3, lr=0.05, momentum=0.9, fused=None):
    model = _tiny_model()
    opt = zoo.make_optimizer(lr=lr, momentum=momentum)
    st = zoo.init_state(model, jax.random.key(7), TINY_SHAPE, opt)
    step = zoo.make_train_step(
        model, opt, accum_steps=2, mesh=mesh, comm=CommConfig(**_COMM),
        fused=fused,
    )
    losses = []
    for _ in range(steps):
        st, loss = step(st, x, y)
        losses.append(float(loss))
    return st, losses


def _run_fused_update(mesh, x, y, steps=3, lr=0.05, momentum=0.9,
                      act_dtype="float32"):
    model = _tiny_model()
    comm = CommConfig(**_COMM)
    fused = FusedStepConfig(update=True, tail=True, act_dtype=act_dtype)
    st, n_buckets = zoo.init_fused_state(
        model, jax.random.key(7), TINY_SHAPE, n_data=8, fused=fused,
        bucket_bytes=comm.bucket_bytes,
    )
    step = zoo.make_fused_train_step(
        model, lr=lr, momentum=momentum, accum_steps=2, mesh=mesh,
        augment=None, comm=comm, fused=fused, n_buckets=n_buckets,
    )
    losses = []
    for _ in range(steps):
        st, loss = step(st, x, y)
        losses.append(float(loss))
    return st, losses


class TestFusedZooStep:
    def test_fused_tail_matches_unfused_f32(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        _, base = _run_unfused(mesh8, x, y)
        _, tail = _run_unfused(
            mesh8, x, y,
            fused=FusedStepConfig(update=False, tail=True,
                                  act_dtype="float32"),
        )
        assert max(abs(a - b) for a, b in zip(base, tail)) <= 1e-5

    def test_update_on_arrival_matches_unfused_f32(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        st_u, base = _run_unfused(mesh8, x, y)
        st_f, fused = _run_fused_update(mesh8, x, y)
        assert max(abs(a - b) for a, b in zip(base, fused)) <= 1e-5
        assert tree_allclose(st_u.params, st_f.params, atol=1e-5)
        assert tree_allclose(st_u.model_state, st_f.model_state, atol=1e-5)

    def test_update_on_arrival_bf16_within_bound(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        _, base = _run_unfused(mesh8, x, y)
        _, fused = _run_fused_update(mesh8, x, y, act_dtype="bfloat16")
        assert max(abs(a - b) for a, b in zip(base, fused)) <= 1e-2

    def test_overflow_skips_and_rescales(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        model = _tiny_model()
        comm = CommConfig(**_COMM)
        fused = FusedStepConfig(update=True, tail=True,
                                act_dtype="bfloat16")
        st, nb = zoo.init_fused_state(
            model, jax.random.key(7), TINY_SHAPE, n_data=8, fused=fused,
            bucket_bytes=comm.bucket_bytes,
        )
        step = zoo.make_fused_train_step(
            model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh8,
            augment=None, comm=comm, fused=fused, n_buckets=nb,
        )
        scale0 = float(st.opt_state.scale)
        assert scale0 == fused.loss_scale
        p0 = tree_copy(st.params)
        st, _ = step(st, x.at[0, 0, 0, 0].set(jnp.inf), y)
        # Overflow: update dropped bit-exactly, scale backed off, skip
        # counter advanced — a handled event, not a divergence.
        assert tree_bitequal(st.params, p0)
        assert all(bool(jnp.all(m == 0)) for m in st.opt_state.mom)
        assert float(st.opt_state.scale) == scale0 * fused.backoff
        assert int(st.opt_state.skipped) == 1
        assert int(st.opt_state.good_steps) == 0
        # Clean batch: training resumes, params move, scale holds.
        st, loss = step(st, x, y)
        assert np.isfinite(loss)
        assert not tree_bitequal(st.params, p0)
        assert float(st.opt_state.scale) == scale0 * fused.backoff
        assert int(st.opt_state.skipped) == 1
        assert int(st.opt_state.good_steps) == 1

    def test_make_train_step_rejects_update(self, mesh8):
        model = _tiny_model()
        opt = zoo.make_optimizer()
        with pytest.raises(ValueError, match="update-on-arrival"):
            zoo.make_train_step(
                model, opt, mesh=mesh8, comm=CommConfig(**_COMM),
                fused=FusedStepConfig(update=True),
            )


# ---------------------------------------------------------------------------
# ZeRO-3: just-in-time parameter gathering (train/zoo.py zero3_*)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def hier_mesh(host_devices):
    return mesh_lib.make_hier_mesh(n_hosts=2)


def _run_zero3(mesh, x, y, steps=3, lr=0.05, momentum=0.9,
               act_dtype="float32", impl="ring", hosts=1):
    model = _tiny_model()
    comm = CommConfig(
        impl=impl, bucket_bytes=2048, overlap=True,
        hosts=hosts if impl == "hierarchical" else None,
    )
    fused = FusedStepConfig(update=True, tail=True, act_dtype=act_dtype,
                            zero=3)
    n_host = hosts if impl == "hierarchical" else 1
    st, plan = zoo.init_zero3_state(
        model, jax.random.key(7), TINY_SHAPE, n_data=8 // n_host,
        fused=fused, bucket_bytes=comm.bucket_bytes, n_host=n_host,
    )
    step = zoo.make_zero3_train_step(
        model, lr=lr, momentum=momentum, accum_steps=2, mesh=mesh,
        augment=None, comm=comm, fused=fused, plan=plan,
    )
    losses = []
    for _ in range(steps):
        st, loss = step(st, x, y)
        losses.append(float(loss))
    return st, plan, losses


def _f32_view_tree():
    """All-f32 params-like tree with the bucketizer's hard shapes:
    scalars, odd lengths, an empty leaf, nesting."""
    return {
        "conv": {"w": jnp.arange(7 * 3 * 5, dtype=jnp.float32).reshape(7, 3, 5),
                 "b": jnp.arange(13, dtype=jnp.float32) * 0.5},
        "scalar": jnp.float32(3.25),
        "empty": jnp.zeros((0, 4), jnp.float32),
        "odd": [jnp.linspace(-1.0, 1.0, 9, dtype=jnp.float32),
                (jnp.full((2, 2), -2.0, jnp.float32),)],
    }


class TestZero3Step:
    def test_zero3_matches_zero2_losses_and_params(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        st2, base = _run_fused_update(mesh8, x, y)
        st3, plan, z3 = _run_zero3(mesh8, x, y)
        # Same microbatch schedule, same update-on-arrival kernels — the
        # only move is WHEN the param all-gather runs (tail -> head).
        assert max(abs(a - b) for a, b in zip(base, z3)) <= 1e-6
        full = zoo.zero3_full_params(st3, plan)
        assert tree_allclose(st2.params, full, atol=1e-5)
        assert tree_allclose(st2.model_state, st3.model_state, atol=1e-5)

    def test_zero3_hier_matches_flat(self, mesh8, hier_mesh, rng):
        x, y = _tiny_batch(rng)
        _, _, flat = _run_zero3(mesh8, x, y)
        _, _, hier = _run_zero3(
            hier_mesh, x, y, impl="hierarchical", hosts=2
        )
        assert max(abs(a - b) for a, b in zip(flat, hier)) <= 1e-5

    def test_zero3_bf16_within_bound(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        _, base = _run_unfused(mesh8, x, y)
        _, _, z3 = _run_zero3(mesh8, x, y, act_dtype="bfloat16")
        assert max(abs(a - b) for a, b in zip(base, z3)) <= 1e-2

    def test_resident_state_is_sharded(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        st, plan, _ = _run_zero3(mesh8, x, y, steps=1)
        for rows, mom in zip(st.params, st.opt_state.mom):
            assert rows.shape[0] == plan.shards == 8
            assert mom.shape == rows.shape

    def test_zero3_overflow_skips_bit_exactly(self, mesh8, rng):
        x, y = _tiny_batch(rng)
        model = _tiny_model()
        comm = CommConfig(**_COMM)
        fused = FusedStepConfig(update=True, tail=True,
                                act_dtype="bfloat16", zero=3)
        st, plan = zoo.init_zero3_state(
            model, jax.random.key(7), TINY_SHAPE, n_data=8, fused=fused,
            bucket_bytes=comm.bucket_bytes,
        )
        step = zoo.make_zero3_train_step(
            model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh8,
            augment=None, comm=comm, fused=fused, plan=plan,
        )
        scale0 = float(st.opt_state.scale)
        p0 = tree_copy(st.params)
        st, _ = step(st, x.at[0, 0, 0, 0].set(jnp.inf), y)
        assert tree_bitequal(st.params, p0)
        assert all(bool(jnp.all(m == 0)) for m in st.opt_state.mom)
        assert float(st.opt_state.scale) == scale0 * fused.backoff
        assert int(st.opt_state.skipped) == 1

    def test_zero3_requires_explicit_collectives(self, mesh8):
        model = _tiny_model()
        fused = FusedStepConfig(update=True, tail=True, zero=3)
        st, plan = zoo.init_zero3_state(
            model, jax.random.key(7), TINY_SHAPE, n_data=8, fused=fused,
            bucket_bytes=2048,
        )
        with pytest.raises(ValueError, match="ring"):
            zoo.make_zero3_train_step(
                model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh8,
                augment=None, comm=CommConfig(impl="psum"), fused=fused,
                plan=plan,
            )

    def test_zero_level_config_gating(self):
        with pytest.raises(ValueError, match="update"):
            FusedStepConfig(update=False, zero=3)
        with pytest.raises(ValueError, match="zero"):
            FusedStepConfig(update=True, zero=1)


class TestZero3Views:
    def test_view_round_trip_is_bit_exact_across_world_sizes(self):
        view = {
            "params": _f32_view_tree(),
            "model_state": {"bn": jnp.linspace(0.0, 1.0, 4)},
            "mom": jax.tree_util.tree_map(
                lambda l: l * 0.25, _f32_view_tree()
            ),
            "scale": jnp.float32(8.0),
            "good_steps": jnp.int32(5),
            "skipped": jnp.int32(1),
        }
        for n_host, n_data in ((1, 8), (2, 4), (1, 4), (4, 2)):
            st, plan = zoo.zero3_from_view(
                view, n_data=n_data, bucket_bytes=64, n_host=n_host
            )
            assert plan.shards == n_host * n_data
            back = zoo.zero3_full_view(st, plan, n_host=n_host)
            assert tree_bitequal(view["params"], back["params"])
            assert tree_bitequal(view["mom"], back["mom"])
            assert float(back["scale"]) == 8.0
            assert int(back["good_steps"]) == 5

    def test_init_full_params_round_trip(self):
        model = _tiny_model()
        fused = FusedStepConfig(update=True, tail=True, zero=3)
        params0, _, _ = model.init(jax.random.key(7), TINY_SHAPE)
        st, plan = zoo.init_zero3_state(
            model, jax.random.key(7), TINY_SHAPE, n_data=4, fused=fused,
            bucket_bytes=2048, n_host=2,
        )
        assert tree_bitequal(params0, zoo.zero3_full_params(st, plan,
                                                            n_host=2))


class TestShardedCheckpoint:
    def _trained_view(self, mesh8, rng, steps=2):
        x, y = _tiny_batch(rng)
        st, plan, _ = _run_zero3(mesh8, x, y, steps=steps)
        return zoo.zero3_full_view(st, plan)

    def test_save_reshard_restore_bit_exact(self, mesh8, rng, tmp_path):
        from parallel_cnn_tpu.train import checkpoint

        view8 = self._trained_view(mesh8, rng)
        path = str(tmp_path / "ckpt_1.npz")
        checkpoint.save_sharded(
            path, view8, checkpoint.TrainState(epoch=1),
            world_size=8, bucket_bytes=2048,
        )
        view, tstate, zmeta = checkpoint.restore_sharded(path, view8)
        assert tstate.epoch == 1
        assert zmeta == {"world_size": 8, "bucket_bytes": 2048, "rank": 0}
        assert tree_bitequal(view8, view)
        # Re-shard the restored view for DIFFERENT world sizes and come
        # back: shard<->full is reshape/transpose/slice only, so every
        # lap is bit-exact.
        for n_host, n_data in ((1, 4), (2, 4), (2, 2)):
            st, plan = zoo.zero3_from_view(
                view, n_data=n_data, bucket_bytes=2048, n_host=n_host
            )
            back = zoo.zero3_full_view(st, plan, n_host=n_host)
            assert tree_bitequal(view8["params"], back["params"])
            assert tree_bitequal(view8["mom"], back["mom"])

    def test_plain_readers_reject_sharded_with_typed_error(
        self, mesh8, rng, tmp_path
    ):
        from parallel_cnn_tpu.train import checkpoint

        view8 = self._trained_view(mesh8, rng, steps=1)
        path = str(tmp_path / "ckpt_1.npz")
        checkpoint.save_sharded(path, view8, world_size=8,
                                bucket_bytes=2048)
        with pytest.raises(ValueError, match="use restore_sharded"):
            checkpoint.restore(path, view8)
        with pytest.raises(ValueError, match="use restore_sharded"):
            checkpoint.load_params(path, view8["params"])

    def test_restore_sharded_rejects_plain(self, tmp_path):
        from parallel_cnn_tpu.train import checkpoint

        path = str(tmp_path / "ckpt_1.npz")
        tree = {"w": jnp.ones((4,), jnp.float32)}
        checkpoint.save(path, tree)
        with pytest.raises(ValueError, match="not a sharded checkpoint"):
            checkpoint.restore_sharded(path, tree)


# ---------------------------------------------------------------------------
# Sentinel loss-scaling policy (resilience/sentinel.py:check_scaled)
# ---------------------------------------------------------------------------


class TestSentinelLossScaling:
    def test_handled_overflow_is_healthy_with_reason(self):
        s = Sentinel()
        params = {"w": jnp.ones((4,), jnp.float32)}
        v = s.check_scaled(
            loss=float("inf"), params=params,
            skipped_before=2, skipped_now=3, scale=16384.0,
        )
        assert v.healthy
        assert "overflow handled" in v.reason
        assert "16384" in v.reason

    def test_unhandled_nonfinite_stays_unhealthy(self):
        s = Sentinel()
        params = {"w": jnp.ones((4,), jnp.float32)}
        v = s.check_scaled(
            loss=float("nan"), params=params,
            skipped_before=3, skipped_now=3, scale=1.0,
        )
        assert not v.healthy

    def test_poisoned_params_stay_unhealthy_even_if_skipped(self):
        s = Sentinel()
        params = {"w": jnp.array([1.0, jnp.nan], jnp.float32)}
        v = s.check_scaled(
            loss=1.0, params=params,
            skipped_before=0, skipped_now=1, scale=8.0,
        )
        assert not v.healthy

    def test_healthy_passthrough(self):
        s = Sentinel()
        v = s.check_scaled(
            loss=0.5, params={"w": jnp.ones((2,), jnp.float32)},
            skipped_before=0, skipped_now=0,
        )
        assert v.healthy and v.reason == ""


# ---------------------------------------------------------------------------
# Config gating (acceptance: nothing changes unless explicitly enabled)
# ---------------------------------------------------------------------------


class TestFusedConfigGating:
    def test_from_env_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("PCNN_FUSED_STEP", raising=False)
        monkeypatch.delenv("PCNN_ACT_DTYPE", raising=False)
        assert FusedStepConfig.from_env() is None
        # PCNN_ACT_DTYPE alone must NOT enable the fused path.
        monkeypatch.setenv("PCNN_ACT_DTYPE", "bfloat16")
        assert FusedStepConfig.from_env() is None

    def test_from_env_enabled(self, monkeypatch):
        monkeypatch.setenv("PCNN_FUSED_STEP", "1")
        monkeypatch.setenv("PCNN_ACT_DTYPE", "float32")
        cfg = FusedStepConfig.from_env()
        assert cfg is not None and cfg.act_dtype == "float32"
        monkeypatch.setenv("PCNN_FUSED_STEP", "0")
        assert FusedStepConfig.from_env() is None

    def test_validation(self):
        with pytest.raises(ValueError):
            FusedStepConfig(act_dtype="float16")
        with pytest.raises(ValueError):
            FusedStepConfig(loss_scale=0.5)
        with pytest.raises(ValueError):
            FusedStepConfig(backoff=1.5)
