"""Bucketed gradient collectives (parallel/collectives.py): bucketizer
round-trip, ring reduce-scatter/all-gather ≡ psum, bf16-on-the-wire, and
the explicit-comm zoo step end-to-end on the 8-device host platform.

Tolerance note (the f32 exact-sum caveat): psum and the ring REASSOCIATE
the same f32 summands differently (XLA's reduction tree vs n sequential
chunk adds), so float comparisons here are to roundoff tolerance — ~1e-6
relative for unit-scale operands, ≤1e-5 loss delta end-to-end — never
bit-exact. Integer buckets ARE exact (addition associates). bf16 wire
adds a per-hop requantization bounded end-to-end at ≤1e-2 loss delta.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from parallel_cnn_tpu.config import CommConfig, MeshConfig
from parallel_cnn_tpu.parallel import collectives, mesh as mesh_lib

pytestmark = pytest.mark.comm

AXIS = mesh_lib.DATA_AXIS


def tree_allclose(a, b, atol=1e-5):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    return all(
        np.allclose(np.asarray(x), np.asarray(y), atol=atol)
        for x, y in zip(flat_a, flat_b)
    )


def arbitrary_tree():
    """Scalars, odd shapes, an empty leaf, mixed dtypes, nested containers
    — the shapes a real grad pytree plus metadata could throw at the
    bucketizer."""
    return {
        "conv": {"w": jnp.arange(7 * 3 * 5, dtype=jnp.float32).reshape(7, 3, 5),
                 "b": jnp.arange(13, dtype=jnp.float32) * 0.5},
        "scalar": jnp.float32(3.25),
        "count": jnp.int32(7),
        "steps": jnp.arange(11, dtype=jnp.int32),
        "empty": jnp.zeros((0, 4), jnp.float32),
        "half": [jnp.ones((9,), jnp.bfloat16) * 1.5,
                 (jnp.full((2, 2), -2.0, jnp.float32),)],
    }


class TestBucketizer:
    def test_round_trip_is_exact(self):
        tree = arbitrary_tree()
        # Tiny bucket budget forces many buckets; shards=8 forces padding.
        plan = collectives.plan_buckets(tree, bucket_bytes=64, shards=8)
        back = collectives.unflatten_buckets(
            collectives.flatten_buckets(tree, plan), plan
        )
        a = jax.tree_util.tree_leaves_with_path(tree)
        b = jax.tree_util.tree_leaves_with_path(back)
        assert [p for p, _ in a] == [p for p, _ in b]
        for (_, x), (_, y) in zip(a, b):
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_round_trip_single_large_bucket(self):
        tree = arbitrary_tree()
        plan = collectives.plan_buckets(tree, bucket_bytes=1 << 20, shards=4)
        back = collectives.unflatten_buckets(
            collectives.flatten_buckets(tree, plan), plan
        )
        assert tree_allclose(tree, back, atol=0)
        # One bucket per dtype at this budget — and never a mixed one.
        assert plan.n_buckets == len(set(plan.bucket_dtypes))

    def test_bucket_sizes_pad_to_shards(self):
        for shards in (1, 3, 8):
            plan = collectives.plan_buckets(
                arbitrary_tree(), bucket_bytes=128, shards=shards
            )
            assert all(s % shards == 0 for s in plan.bucket_sizes)
        # Padding accounted: total capacity covers every placed element.
        placed = sum(s.size for s in plan.slots if s.bucket >= 0)
        assert sum(plan.bucket_sizes) >= placed

    def test_oversized_leaf_gets_own_bucket(self):
        tree = {"big": jnp.zeros((1000,), jnp.float32),
                "small": jnp.ones((3,), jnp.float32)}
        plan = collectives.plan_buckets(tree, bucket_bytes=256, shards=1)
        big_slot = plan.slots[0]
        assert big_slot.size == 1000 and big_slot.offset == 0
        # No other leaf shares the oversized bucket.
        assert all(s.bucket != big_slot.bucket
                   for s in plan.slots if s is not big_slot)

    def test_dtypes_never_share_a_bucket(self):
        plan = collectives.plan_buckets(
            arbitrary_tree(), bucket_bytes=1 << 20, shards=1
        )
        for slot in plan.slots:
            if slot.bucket >= 0:
                assert plan.bucket_dtypes[slot.bucket] == slot.dtype

    def test_structure_mismatch_raises(self):
        plan = collectives.plan_buckets({"a": jnp.zeros((4,))})
        with pytest.raises(ValueError, match="leaves"):
            collectives.flatten_buckets(
                {"a": jnp.zeros((4,)), "b": jnp.zeros((4,))}, plan
            )


@pytest.fixture(scope="module")
def mesh8(host_devices):
    return mesh_lib.make_mesh(MeshConfig(data=8, model=1))


def _run_sharded(mesh8, body, x, check=False):
    f = jax.shard_map(
        body, mesh=mesh8, in_specs=(P(AXIS),), out_specs=P(),
        check_vma=check,
    )
    return jax.jit(f)(x)


class TestRingCollectives:
    N = 8

    def test_ring_allreduce_matches_psum(self, mesh8, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 640,)).astype(np.float32))
        ref = _run_sharded(
            mesh8, lambda s: jax.lax.psum(s, AXIS), x, check=True
        )
        out = _run_sharded(
            mesh8,
            lambda s: collectives.ring_all_reduce(s, AXIS, self.N), x,
        )
        # Reassociated f32 sums: roundoff-tolerance, not bit-equal (see
        # module docstring).
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5
        )

    def test_reduce_scatter_all_gather_compose(self, mesh8, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 320,)).astype(np.float32))
        ref = _run_sharded(mesh8, lambda s: jax.lax.psum(s, AXIS), x,
                           check=True)

        def rs_ag(s):
            shard = collectives.ring_reduce_scatter(s, AXIS, self.N)
            return collectives.ring_all_gather(shard, AXIS, self.N)

        out = _run_sharded(mesh8, rs_ag, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5
        )

    def test_bf16_wire_close_to_f32(self, mesh8, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 320,)).astype(np.float32))
        ref = _run_sharded(mesh8, lambda s: jax.lax.psum(s, AXIS), x,
                           check=True)
        out = _run_sharded(
            mesh8,
            lambda s: collectives.ring_all_reduce(
                s, AXIS, self.N, wire_dtype="bfloat16"
            ),
            x,
        )
        scale = float(np.max(np.abs(np.asarray(ref))))
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
        assert err / scale < 2e-2

    def test_integer_buckets_sum_exactly(self, mesh8):
        x = jnp.arange(self.N * 24, dtype=jnp.int32)
        ref = _run_sharded(mesh8, lambda s: jax.lax.psum(s, AXIS), x,
                           check=True)
        out = _run_sharded(
            mesh8,
            lambda s: collectives.ring_all_reduce(s, AXIS, self.N), x,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_tree_all_reduce_ring_matches_psum(self, mesh8, rng):
        """Odd per-leaf shapes exercise bucket padding inside shard_map."""
        def make_tree(s):
            return {"a": s[:37].reshape(37), "b": s[37:40] * 2.0,
                    "c": s[40] * 3.0}  # scalar leaf included

        comm = CommConfig(impl="ring", bucket_bytes=64)
        x = jnp.asarray(rng.normal(size=(self.N * 41,)).astype(np.float32))
        ref = _run_sharded(
            mesh8, lambda s: jax.lax.psum(make_tree(s), AXIS), x, check=True
        )
        out = _run_sharded(
            mesh8,
            lambda s: collectives.tree_all_reduce(
                make_tree(s), AXIS, self.N, comm
            ),
            x,
        )
        assert tree_allclose(ref, out, atol=1e-5)


@pytest.fixture(scope="module")
def hier_mesh(host_devices):
    """2 emulated hosts x 4 devices — the CPU stand-in for a 2-process
    pod slice (same mesh axes, same per-axis rings)."""
    return mesh_lib.make_hier_mesh(n_hosts=2)


def _run_hier(mesh, body, x, out_specs=P(), check=False):
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P((mesh_lib.HOST_AXIS, AXIS)),), out_specs=out_specs,
        check_vma=check,
    )
    return jax.jit(f)(x)


class TestHierarchicalCollectives:
    NH, ND = 2, 4
    N = NH * ND

    def test_hier_allreduce_matches_psum(self, hier_mesh, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 320,)).astype(np.float32))
        ref = _run_hier(
            hier_mesh,
            lambda s: jax.lax.psum(s, (mesh_lib.HOST_AXIS, AXIS)), x,
            check=True,
        )
        out = _run_hier(
            hier_mesh,
            lambda s: collectives.hier_all_reduce(
                s, mesh_lib.HOST_AXIS, self.NH, AXIS, self.ND
            ),
            x,
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5
        )

    def test_hier_rs_ag_compose(self, hier_mesh, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 80,)).astype(np.float32))
        ref = _run_hier(
            hier_mesh,
            lambda s: jax.lax.psum(s, (mesh_lib.HOST_AXIS, AXIS)), x,
            check=True,
        )

        def rs_ag(s):
            shard = collectives.hier_reduce_scatter(
                s, mesh_lib.HOST_AXIS, self.NH, AXIS, self.ND
            )
            return collectives.hier_all_gather(
                shard, mesh_lib.HOST_AXIS, self.NH, AXIS, self.ND
            )

        out = _run_hier(hier_mesh, rs_ag, x)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-5
        )

    def test_hier_rs_placement_matches_shard_rows(self, hier_mesh):
        """The resident-shard layout contract ZeRO-3 relies on: stacking
        each device's reduce-scattered chunk in P((host, data)) row order
        reproduces hier_shard_rows of the full reduction, exactly (integer
        payload — addition associates)."""
        x = jnp.arange(self.N * 16, dtype=jnp.int32)

        def rs(s):
            shard = collectives.hier_reduce_scatter(
                s, mesh_lib.HOST_AXIS, self.NH, AXIS, self.ND
            )
            return shard[None, :]

        rows = _run_hier(
            hier_mesh, rs, x, out_specs=P((mesh_lib.HOST_AXIS, AXIS)),
        )
        # in_specs splits x into N distinct per-device shards; the
        # reduction sums them elementwise, then the scatter lays the sum
        # out exactly as hier_shard_rows does.
        summed = jnp.asarray(np.asarray(x).reshape(self.N, -1).sum(axis=0))
        want = collectives.hier_shard_rows(summed, self.NH, self.ND)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))

    def test_shard_rows_round_trip(self, rng):
        bucket = jnp.asarray(rng.normal(size=(48,)).astype(np.float32))
        for nh, nd in ((1, 4), (2, 4), (4, 2), (2, 2)):
            rows = collectives.hier_shard_rows(bucket, nh, nd)
            assert rows.shape == (nh * nd, 48 // (nh * nd))
            back = collectives.hier_unshard_rows(rows, nh, nd)
            np.testing.assert_array_equal(np.asarray(back), np.asarray(bucket))

    def test_shard_rows_rejects_indivisible(self):
        with pytest.raises(ValueError, match="divide"):
            collectives.hier_shard_rows(jnp.zeros((10,)), 2, 2)

    def test_tree_all_reduce_hier_matches_psum(self, hier_mesh, rng):
        def make_tree(s):
            return {"a": s[:37], "b": s[37:40] * 2.0, "c": s[40] * 3.0}

        comm = CommConfig(impl="hierarchical", bucket_bytes=64, hosts=2)
        x = jnp.asarray(rng.normal(size=(self.N * 41,)).astype(np.float32))
        ref = _run_hier(
            hier_mesh,
            lambda s: jax.lax.psum(make_tree(s),
                                   (mesh_lib.HOST_AXIS, AXIS)),
            x, check=True,
        )
        out = _run_hier(
            hier_mesh,
            lambda s: collectives.tree_all_reduce(
                make_tree(s), AXIS, self.ND, comm,
                host_axis=mesh_lib.HOST_AXIS, host_size=self.NH,
            ),
            x,
        )
        assert tree_allclose(ref, out, atol=1e-5)

    def test_tree_all_reduce_hier_requires_host_axis(self):
        comm = CommConfig(impl="hierarchical")
        with pytest.raises(ValueError, match="host"):
            collectives.tree_all_reduce(
                {"a": jnp.zeros((8,))}, AXIS, 8, comm
            )

    def test_hier_bf16_wire_close_to_f32(self, hier_mesh, rng):
        x = jnp.asarray(rng.normal(size=(self.N * 160,)).astype(np.float32))
        ref = _run_hier(
            hier_mesh,
            lambda s: jax.lax.psum(s, (mesh_lib.HOST_AXIS, AXIS)), x,
            check=True,
        )
        out = _run_hier(
            hier_mesh,
            lambda s: collectives.hier_all_reduce(
                s, mesh_lib.HOST_AXIS, self.NH, AXIS, self.ND,
                wire_dtype="bfloat16",
            ),
            x,
        )
        scale = float(np.max(np.abs(np.asarray(ref))))
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
        assert err / scale < 2e-2


def tiny_model():
    from parallel_cnn_tpu.nn import core, layers

    return core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.BatchNorm(), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])


TINY_SHAPE = (8, 8, 3)


def tiny_batch(rng, n=16):
    x = jnp.asarray(rng.normal(size=(n,) + TINY_SHAPE).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    return x, y


def run_zoo_steps(mesh, comm, x, y, steps=3, accum=2, augment=None):
    from parallel_cnn_tpu.train import zoo

    model = tiny_model()
    opt = zoo.make_optimizer(lr=0.05)
    st = zoo.init_state(model, jax.random.key(7), TINY_SHAPE, opt)
    step = zoo.make_train_step(
        model, opt, accum_steps=accum, mesh=mesh, augment=augment, comm=comm
    )
    loss = None
    for i in range(steps):
        key = jax.random.key(100 + i) if augment is not None else None
        st, loss = step(st, x, y, key)
    return st, float(loss)


class TestExplicitCommStep:
    """The zoo accum×mesh leg on the explicit collective path: ring and
    bf16-wire parity vs psum END TO END (loss + params), the acceptance
    contract of ISSUE 4."""

    def test_ring_matches_psum_loss_and_params(self, mesh8, rng):
        x, y = tiny_batch(rng)
        st_p, loss_p = run_zoo_steps(mesh8, CommConfig(impl="psum"), x, y)
        st_r, loss_r = run_zoo_steps(
            mesh8, CommConfig(impl="ring", bucket_bytes=2048), x, y
        )
        assert abs(loss_r - loss_p) <= 1e-5
        assert tree_allclose(st_r.params, st_p.params, atol=1e-5)
        assert tree_allclose(st_r.model_state, st_p.model_state, atol=1e-5)

    def test_ring_overlap_off_matches_psum(self, mesh8, rng):
        x, y = tiny_batch(rng)
        _, loss_p = run_zoo_steps(mesh8, CommConfig(impl="psum"), x, y)
        _, loss_r = run_zoo_steps(
            mesh8,
            CommConfig(impl="ring", bucket_bytes=2048, overlap=False), x, y,
        )
        assert abs(loss_r - loss_p) <= 1e-5

    def test_bf16_wire_end_to_end_loss_parity(self, mesh8, rng):
        x, y = tiny_batch(rng)
        _, loss_p = run_zoo_steps(mesh8, CommConfig(impl="psum"), x, y)
        _, loss_b = run_zoo_steps(
            mesh8,
            CommConfig(impl="ring", bucket_bytes=2048,
                       wire_dtype="bfloat16"),
            x, y,
        )
        assert abs(loss_b - loss_p) <= 1e-2

    def test_augment_key_crosses_the_shard_map(self, mesh8, rng):
        from parallel_cnn_tpu.data import augment as aug_lib

        def aug(key, x):
            return aug_lib.random_crop_flip(key, x, pad=1)

        x, y = tiny_batch(rng)
        _, loss = run_zoo_steps(
            mesh8, CommConfig(impl="ring", bucket_bytes=2048), x, y,
            steps=2, augment=aug,
        )
        assert np.isfinite(loss)

    def test_comm_requires_mesh(self):
        from parallel_cnn_tpu.train import zoo

        model = tiny_model()
        opt = zoo.make_optimizer()
        with pytest.raises(ValueError, match="requires a mesh"):
            zoo.make_train_step(model, opt, comm=CommConfig())

    def test_comm_excludes_model_axis(self, mesh8):
        from parallel_cnn_tpu.train import zoo

        model = tiny_model()
        opt = zoo.make_optimizer()
        with pytest.raises(ValueError, match="model_axis"):
            zoo.make_train_step(
                model, opt, mesh=mesh8, model_axis=True, comm=CommConfig()
            )


class TestHierarchicalCommStep:
    """The zoo step over the two-level rings, end to end. Parity baseline
    is psum ON THE SAME (host, device) mesh — identical batch
    decomposition, so BN's shard-local batch stats see the same shards
    and the only difference left is the collective algorithm."""

    def test_hier_matches_psum_loss_and_params(self, hier_mesh, rng):
        x, y = tiny_batch(rng)
        st_p, loss_p = run_zoo_steps(
            hier_mesh, CommConfig(impl="psum"), x, y
        )
        st_h, loss_h = run_zoo_steps(
            hier_mesh,
            CommConfig(impl="hierarchical", bucket_bytes=2048, hosts=2),
            x, y,
        )
        assert abs(loss_h - loss_p) <= 1e-5
        assert tree_allclose(st_h.params, st_p.params, atol=1e-5)
        assert tree_allclose(st_h.model_state, st_p.model_state, atol=1e-5)

    def test_hier_bf16_wire_end_to_end_loss_parity(self, hier_mesh, rng):
        x, y = tiny_batch(rng)
        _, loss_p = run_zoo_steps(hier_mesh, CommConfig(impl="psum"), x, y)
        _, loss_b = run_zoo_steps(
            hier_mesh,
            CommConfig(impl="hierarchical", bucket_bytes=2048,
                       wire_dtype="bfloat16", hosts=2),
            x, y,
        )
        assert abs(loss_b - loss_p) <= 1e-2

    def test_hierarchical_requires_host_mesh(self, mesh8, rng):
        x, y = tiny_batch(rng)
        with pytest.raises(ValueError, match="host"):
            run_zoo_steps(
                mesh8, CommConfig(impl="hierarchical"), x, y, steps=1
            )

    def test_ring_rejected_on_host_mesh(self, hier_mesh, rng):
        x, y = tiny_batch(rng)
        with pytest.raises(ValueError, match="hierarchical"):
            run_zoo_steps(
                hier_mesh, CommConfig(impl="ring"), x, y, steps=1
            )


class TestLenetDPComm:
    def test_dp_step_ring_matches_psum(self, mesh8, rng):
        from parallel_cnn_tpu.models import lenet_ref
        from parallel_cnn_tpu.parallel import data_parallel

        gb = 16
        x = jnp.asarray(rng.uniform(0, 1, (gb, 28, 28)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 10, (gb,)).astype(np.int32))

        outs = {}
        for name, comm in (
            ("psum", None),
            ("ring", CommConfig(impl="ring", bucket_bytes=4096)),
        ):
            params = mesh_lib.replicate(mesh8, lenet_ref.init(jax.random.key(0)))
            step = data_parallel.make_dp_step(
                mesh8, dt=0.1, global_batch=gb, comm=comm
            )
            xs, ys = mesh_lib.shard_batch(mesh8, (x, y))
            outs[name] = step(params, xs, ys)
        p_psum, err_psum = outs["psum"]
        p_ring, err_ring = outs["ring"]
        assert abs(float(err_ring) - float(err_psum)) <= 1e-5
        assert tree_allclose(p_ring, p_psum, atol=1e-5)
