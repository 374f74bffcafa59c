"""Elastic-runtime tests (resilience/elastic.py + the serve failover path).

The contract under test, end to end:

- **Loss parity** — a run that resizes its ZeRO-3 world in flight
  (topology lap (1,8) → (2,4) → (1,4) → (1,8)) matches a fixed-mesh run
  on the same data to ≤1e-5. This needs the two parity preconditions the
  module docstrings pin: f32 activations (bf16 gradient rounding is
  partition-dependent, ~1e-3) and a BatchNorm-free model (ring-comm BN
  batch stats are per-shard — train/zoo.py documents it — so a stateful
  model is genuinely world-size dependent).
- **Bit-exactness** — a reshard that takes zero optimizer steps is a
  pure reshape/transpose/slice round trip, bitwise equal in both
  directions and across topologies.
- **Triggers** — preempt resize requests, seeded chaos ``resize@``
  injections (clamped to min_world), and the planned schedule all feed
  ``ElasticController.pending`` in that priority order and are consumed
  exactly once.
- **Recovery** — when the live shards are unreachable, the controller
  falls back to the newest loadable sharded ring checkpoint; unusable
  files are skipped with the typed ShardedCheckpointError naming the
  file, writer rank, and world size.
- **Serving** — a replica killed mid-traffic (``kill-replica@SEQ``) is
  evicted, its in-flight batch retried on a survivor within deadline,
  and a replacement re-pinned, with the request conservation law intact:
  submitted == completed + shed + expired + failed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.config import (
    CommConfig,
    ElasticConfig,
    FusedStepConfig,
    MeshConfig,
)
from parallel_cnn_tpu.nn import core, layers
from parallel_cnn_tpu.parallel import mesh as mesh_lib
from parallel_cnn_tpu.resilience import chaos as chaos_lib
from parallel_cnn_tpu.resilience import preempt
from parallel_cnn_tpu.resilience.elastic import (
    ElasticController,
    ElasticError,
)
from parallel_cnn_tpu.resilience.rollback import CheckpointRing
from parallel_cnn_tpu.train import checkpoint, zoo

pytestmark = pytest.mark.elastic

TINY_SHAPE = (8, 8, 3)
_COMM = dict(impl="ring", bucket_bytes=2048, overlap=True)
# f32 activations: THE parity precondition (see module docstring).
_FUSED = FusedStepConfig(update=True, tail=True, act_dtype="float32",
                         zero=3)


def _nobn_model():
    """BatchNorm-free tiny model: the second parity precondition."""
    return core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])


def _data(n=64, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n,) + TINY_SHAPE).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    return x, y


def _init8(model, comm):
    return zoo.init_zero3_state(
        model, jax.random.key(7), TINY_SHAPE, n_data=8, fused=_FUSED,
        bucket_bytes=comm.bucket_bytes,
    )


def _make_step(model, mesh, comm, plan, lr=0.05):
    return zoo.make_zero3_train_step(
        model, lr=lr, momentum=0.9, accum_steps=2, mesh=mesh,
        augment=None, comm=comm, fused=_FUSED, plan=plan,
    )


def _full_np(state, plan, n_host=1):
    return jax.tree_util.tree_map(
        np.asarray, zoo.zero3_full_params(state, plan, n_host=n_host)
    )


def _view_np(state, plan, n_host=1):
    return jax.tree_util.tree_map(
        np.asarray, zoo.zero3_full_view(state, plan, n_host=n_host)
    )


def tree_bitequal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


# -- the tentpole: resize-lap loss parity -------------------------------


@pytest.mark.parametrize(
    "desync", [False, True], ids=["lap", "seeded_desync_must_fail"]
)
def test_resize_lap_matches_fixed_mesh(host_devices, desync):
    """(1,8) → (2,4) → (1,4) → (1,8): six optimizer steps with a
    topology change every two, vs the same six steps on a fixed (1,8)
    mesh. Same data, same seeds, global batch fixed → trajectories agree
    to ≤1e-5 (observed ~1e-7: reduction-order roundoff only). With one
    resident parameter moved by 1e-3 right after the first reshard the
    same comparison must fail, or the bound proves nothing."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    x, y = _data(96)
    batches = [(x[i * 16:(i + 1) * 16], y[i * 16:(i + 1) * 16])
               for i in range(6)]

    # Fixed-mesh baseline.
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    st, plan = _init8(model, comm)
    step = _make_step(model, mesh8, comm, plan)
    fixed = []
    for bx, by in batches:
        st, l = step(st, bx, by, None)
        fixed.append(float(l))
    fixed_params = _full_np(st, plan)

    # Elastic lap: resize before steps 2 and 4, back to (1,8) at 6.
    laps = {2: (8, 2), 4: (4, 1)}  # step -> (world, n_hosts); 6 below
    ctl = ElasticController(ElasticConfig(), world=8)
    st, plan = _init8(model, comm)
    mesh, ecomm = mesh8, comm
    step = _make_step(model, mesh, comm, plan)
    elastic = []
    n_host = 1
    for i, (bx, by) in enumerate(batches):
        if i in laps:
            world, n_hosts = laps[i]
            st, plan, mesh, ecomm = ctl.resize(
                i, world, state=st, plan=plan, comm=ecomm,
                n_hosts=n_hosts,
            )
            n_host = ctl.n_hosts
            if desync and i == 2:
                bumped = st.params[0].at[0, 0].add(1e-3)
                st = dataclasses.replace(
                    st, params=[bumped] + list(st.params[1:])
                )
            step = _make_step(model, mesh, ecomm, plan)
        st, l = step(st, bx, by, None)
        elastic.append(float(l))
    max_dloss = max(abs(a - b) for a, b in zip(fixed, elastic))
    if desync:
        assert max_dloss > 1e-5, (max_dloss, fixed, elastic)
        return
    # The closing (1,4) → (1,8) leg after the last step.
    st, plan, mesh, ecomm = ctl.resize(
        6, 8, state=st, plan=plan, comm=ecomm, n_hosts=1,
    )
    n_host = ctl.n_hosts

    assert [e.new_world for e in ctl.events] == [8, 4, 8]
    assert [e.new_hosts for e in ctl.events] == [2, 1, 1]
    assert max_dloss <= 1e-5, (max_dloss, fixed, elastic)
    got = _full_np(st, plan, n_host=n_host)
    for a, b in zip(
        jax.tree_util.tree_leaves(fixed_params),
        jax.tree_util.tree_leaves(got),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_pure_reshard_is_bitexact(host_devices):
    """A resize with zero optimizer steps in between is a pure layout
    round trip: full views agree BITWISE across 8 → 4 → (2,4) → 8."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    x, y = _data(16)
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    st, plan = _init8(model, comm)
    step = _make_step(model, mesh8, comm, plan)
    st, _ = step(st, x, y, None)  # non-trivial momentum + params
    v8 = _view_np(st, plan)

    st4, plan4 = zoo.zero3_from_view(
        v8, n_data=4, bucket_bytes=comm.bucket_bytes
    )
    assert plan4.shards == 4
    assert tree_bitequal(_view_np(st4, plan4), v8)

    st24, plan24 = zoo.zero3_from_view(
        v8, n_data=4, bucket_bytes=comm.bucket_bytes, n_host=2
    )
    assert plan24.shards == 8
    assert tree_bitequal(_view_np(st24, plan24, n_host=2), v8)

    st8, plan8 = zoo.zero3_from_view(
        _view_np(st24, plan24, n_host=2), n_data=8,
        bucket_bytes=comm.bucket_bytes,
    )
    assert tree_bitequal(_view_np(st8, plan8), v8)


def test_controller_pure_reshard_no_step(host_devices):
    """The controller's own resize (snapshot → re-mesh → reshard), with
    no optimizer step around it, is bit-exact too — including the comm
    impl switch to hierarchical and back."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    st, plan = _init8(model, comm)
    v0 = _view_np(st, plan)
    ctl = ElasticController(ElasticConfig(), world=8)

    st, plan, mesh, comm2 = ctl.resize(
        0, 8, state=st, plan=plan, comm=comm, n_hosts=2,
    )
    assert comm2.impl == "hierarchical" and comm2.hosts == 2
    assert mesh_lib.HOST_AXIS in mesh.axis_names
    assert tree_bitequal(_view_np(st, plan, n_host=2), v0)

    st, plan, mesh, comm3 = ctl.resize(
        0, 4, state=st, plan=plan, comm=comm2, n_hosts=1,
    )
    assert comm3.impl == "ring" and comm3.hosts is None
    assert mesh_lib.HOST_AXIS not in mesh.axis_names
    assert tree_bitequal(_view_np(st, plan), v0)


# -- scaling policy ------------------------------------------------------


def test_scaling_policy_math():
    """LR/global-batch rescale: "global" holds both fixed; "per-device"
    holds the per-device batch and scales LR linearly with the world."""
    g = ElasticController(ElasticConfig(scaling="global"), world=8)
    g.world = 4  # post-shrink
    assert g.lr_for(0.1) == pytest.approx(0.1)
    assert g.global_batch_for(64) == 64

    p = ElasticController(ElasticConfig(scaling="per-device"), world=8)
    p.world = 4
    assert p.lr_for(0.1) == pytest.approx(0.05)
    assert p.global_batch_for(64) == 32  # 8 per device, 4 devices
    p.world = 16
    assert p.lr_for(0.1) == pytest.approx(0.2)
    assert p.global_batch_for(64) == 128


# -- triggers ------------------------------------------------------------


def test_chaos_resize_trigger_and_clamp(host_devices):
    """A seeded chaos resize@STEP:-K fires once at STEP, is clamped to
    min_world, and records its source."""
    monkey = chaos_lib.ChaosMonkey.from_spec("resize@3:-6")
    ctl = ElasticController(
        ElasticConfig(min_world=4), world=8, chaos=monkey,
    )
    assert ctl.pending(2) is None
    assert ctl.pending(3) == 4  # 8 - 6 = 2, clamped up to min_world
    assert ctl._last_source == "chaos"
    monkey2 = chaos_lib.ChaosMonkey.from_spec("resize@0:+4")
    ctl2 = ElasticController(ElasticConfig(), world=8, chaos=monkey2)
    # Device ADD beyond the reachable 8 virtual devices clamps back down
    # to a no-op, which is consumed and skipped.
    assert ctl2.pending(0) is None
    assert ctl2.pending(1) is None  # fired exactly once


def test_schedule_and_signal_triggers(host_devices):
    """Planned schedule entries pop in step order; a preempt resize
    request outranks them and is consumed exactly once."""
    ctl = ElasticController(
        ElasticConfig(schedule="2:4,5:8"), world=8,
    )
    assert ctl.pending(0) is None
    assert ctl.pending(2) == 4
    assert ctl._last_source == "schedule"
    ctl.world = 4  # as if the resize happened
    preempt.request_resize(6)
    try:
        assert ctl.pending(3) == 6  # signal wins over the 5:8 entry
        assert ctl._last_source == "signal"
    finally:
        preempt.clear_resize()
    assert ctl.pending(5) == 8  # the schedule entry is still there
    ctl.world = 8
    assert ctl.pending(7) is None  # schedule exhausted


def test_chaos_grammar():
    """The one-place chaos grammar: resize@STEP:±K and kill-replica@SEQ
    parse; malformed specs raise with the full grammar in the message."""
    m = chaos_lib.ChaosMonkey.from_spec("resize@40:-2")
    assert m.resize_delta == (40, -2)
    assert m.resize_at(39) is None
    assert m.resize_at(40) == -2
    assert m.resize_at(41) is None  # fires once

    m2 = chaos_lib.ChaosMonkey.from_spec("resize@0:+3")
    assert m2.resize_delta == (0, 3)

    k = chaos_lib.ChaosMonkey.from_spec("kill-replica@5")
    assert k.kill_replica_seq == 5
    assert not k.kill_replica_at(4)
    assert k.kill_replica_at(5)
    assert not k.kill_replica_at(6)  # fires once

    for bad in ("resize@", "resize@3", "resize@3:0", "resize@x:-1",
                "kill-replica@", "kill-replica@x", "explode@7"):
        with pytest.raises(ValueError):
            chaos_lib.ChaosMonkey.from_spec(bad)


# -- end-to-end through zoo.train ---------------------------------------


def test_zoo_train_elastic_schedule_parity(host_devices):
    """zoo.train with an elastic schedule (8 → 4 mid-epoch-1, back to 8
    in epoch 2) matches the fixed-mesh run: same per-epoch losses to
    ≤1e-5 and same final params."""
    comm = CommConfig(**_COMM)
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    x, y = _data(64)
    common = dict(
        in_shape=TINY_SHAPE, epochs=2, batch_size=16, lr=0.05,
        momentum=0.9, accum_steps=2, mesh=mesh8, comm=comm, fused=_FUSED,
        seed=0, verbose=False,
    )
    st_fix, hist_fix = zoo.train(_nobn_model(), x, y, **common)
    st_ela, hist_ela = zoo.train(
        _nobn_model(), x, y,
        elastic=ElasticConfig(schedule="2:4,5:8"), **common,
    )
    losses_fix = [h["loss"] if isinstance(h, dict) else h
                  for h in hist_fix]
    losses_ela = [h["loss"] if isinstance(h, dict) else h
                  for h in hist_ela]
    max_d = max(abs(a - b) for a, b in zip(losses_fix, losses_ela))
    assert max_d <= 1e-5, (max_d, losses_fix, losses_ela)

    from parallel_cnn_tpu.parallel import collectives

    p0, _, _ = _nobn_model().init(jax.random.key(0), TINY_SHAPE)
    plan = collectives.plan_buckets(p0, comm.bucket_bytes, shards=8)
    for a, b in zip(
        jax.tree_util.tree_leaves(_full_np(st_fix, plan)),
        jax.tree_util.tree_leaves(_full_np(st_ela, plan)),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_zoo_train_chaos_resize(host_devices):
    """A chaos-injected device loss (resize@1:-4) mid-run shrinks the
    world to 4 and the run completes with finite losses."""
    comm = CommConfig(**_COMM)
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    x, y = _data(64)
    st, hist = zoo.train(
        _nobn_model(), x, y, in_shape=TINY_SHAPE, epochs=1,
        batch_size=16, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh8,
        comm=comm, fused=_FUSED, seed=0, verbose=False,
        elastic=ElasticConfig(),
        chaos=chaos_lib.ChaosMonkey.from_spec("resize@1:-4"),
    )
    losses = [h["loss"] if isinstance(h, dict) else h for h in hist]
    assert all(np.isfinite(losses))
    # The post-resize state is a 4-shard layout: each bucket's resident
    # rows have leading dim 4.
    assert all(p.shape[0] == 4 for p in st.params)


def test_zoo_train_elastic_requires_zero3(host_devices):
    """--elastic without the ZeRO-3 step is a config error, not a silent
    fixed-mesh run."""
    x, y = _data(32)
    with pytest.raises(ValueError, match="ZeRO-3"):
        zoo.train(
            _nobn_model(), x, y, in_shape=TINY_SHAPE, epochs=1,
            batch_size=16, seed=0, verbose=False,
            elastic=ElasticConfig(),
        )


# -- recovery: ring fallback + typed sharded-checkpoint errors ----------


def test_restore_sharded_typed_errors(tmp_path, host_devices):
    """restore_sharded names the file, writer rank, and world size on a
    mismatch — and refuses unsharded files with the same typed error."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    st, plan = _init8(model, comm)
    view = _view_np(st, plan)
    good = str(tmp_path / "good.npz")
    checkpoint.save_sharded(good, view, world_size=8,
                            bucket_bytes=comm.bucket_bytes)
    got, _, zmeta = checkpoint.restore_sharded(good, view)
    assert zmeta["world_size"] == 8 and zmeta["rank"] == 0
    assert tree_bitequal(got, view)

    # Unsharded file → typed refusal carrying the path.
    plain = str(tmp_path / "plain.npz")
    checkpoint.save(plain, view["params"])
    with pytest.raises(checkpoint.ShardedCheckpointError) as ei:
        checkpoint.restore_sharded(plain, view)
    assert ei.value.path == plain

    # Structure mismatch → the error names rank + world size.
    wrong = dict(view, params={"not": np.zeros((2, 2), np.float32)})
    with pytest.raises(checkpoint.ShardedCheckpointError) as ei:
        checkpoint.restore_sharded(good, wrong)
    assert ei.value.rank == 0
    assert ei.value.world_size == 8
    assert "world size=8" in str(ei.value)


def test_partial_ring_recovery(tmp_path, host_devices):
    """A ring holding [corrupt newest, unsharded middle, good oldest]
    recovers from the oldest file — skipping, not dying on, the two
    unusable ones."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    st, plan = _init8(model, comm)
    view = _view_np(st, plan)
    ring = CheckpointRing(str(tmp_path), keep=0)

    checkpoint.save_sharded(ring.path_for(0), view, world_size=8,
                            bucket_bytes=comm.bucket_bytes)
    checkpoint.save(ring.path_for(1), view["params"])  # unsharded
    with open(ring.path_for(2), "wb") as f:
        f.write(b"not an npz")  # torn write

    got = ring.restore_latest_sharded(view)
    assert got is not None
    rview, _, zmeta, path = got
    assert path == ring.path_for(0)
    assert zmeta["world_size"] == 8
    assert tree_bitequal(rview, view)

    # All-unusable ring → None (the controller turns this into a typed
    # ElasticError).
    empty_ring = CheckpointRing(str(tmp_path / "empty"), keep=0)
    assert empty_ring.restore_latest_sharded(view) is None


def test_resize_falls_back_to_ring(tmp_path, host_devices, monkeypatch):
    """When the live snapshot raises (unreachable shards), resize
    reshards from the newest loadable ring checkpoint and flags the
    event; with no usable ring it raises the typed ElasticError."""
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))
    st, plan = _init8(model, comm)
    x, y = _data(16)
    step = _make_step(model, mesh8, comm, plan)
    st, _ = step(st, x, y, None)
    view = _view_np(st, plan)

    ring = CheckpointRing(str(tmp_path), keep=0)
    checkpoint.save_sharded(ring.path_for(0), view, world_size=8,
                            bucket_bytes=comm.bucket_bytes)

    def boom(*a, **k):
        raise RuntimeError("shard buffers deleted (device lost)")

    monkeypatch.setattr(zoo, "zero3_full_view", boom)

    ctl = ElasticController(ElasticConfig(), world=8, ring=ring)
    ctl.register_template(view)  # pre-monkeypatch template shape
    st4, plan4, mesh4, _ = ctl.resize(
        1, 4, state=st, plan=plan, comm=comm,
    )
    assert plan4.shards == 4
    assert ctl.events[-1].from_ring
    monkeypatch.undo()
    assert tree_bitequal(_view_np(st4, plan4), view)

    # No ring at all → typed, actionable failure.
    ctl2 = ElasticController(ElasticConfig(), world=8)
    monkeypatch.setattr(zoo, "zero3_full_view", boom)
    with pytest.raises(ElasticError, match="checkpoint ring"):
        ctl2.resize(1, 4, state=st, plan=plan, comm=comm)


# -- serving: chaos replica failover ------------------------------------


def _serve_stack(n_replicas, chaos=None, obs=None):
    from parallel_cnn_tpu.config import ServeConfig
    from parallel_cnn_tpu.serve.batcher import serve_stack
    from parallel_cnn_tpu.serve.registry import ModelHandle
    from parallel_cnn_tpu.serve.telemetry import ServeStats

    model = _nobn_model()

    def init(key):
        params, state, _ = model.init(key, TINY_SHAPE)
        return params, state

    def forward(params, state, xx):
        return model.apply(params, state, xx, train=False)[0]

    handle = ModelHandle("tiny", TINY_SHAPE, 10, init, forward)
    cfg = ServeConfig(
        n_replicas=n_replicas, max_batch=8, max_wait_ms=5.0,
        queue_depth=64, deadline_ms=30_000.0, precompile=False,
    )
    stats = ServeStats()
    pool, batcher = serve_stack(handle, cfg, stats=stats, chaos=chaos,
                                obs=obs)
    return pool, batcher, stats


@pytest.mark.serve
def test_kill_replica_failover_no_lost_requests(host_devices):
    """chaos kill-replica@1 mid-traffic: every request still completes
    within its (generous) deadline, conservation holds, and the pool is
    back to full strength (the dead slot re-pinned)."""
    chaos = chaos_lib.ChaosMonkey.from_spec("kill-replica@1")
    pool, batcher, stats = _serve_stack(2, chaos=chaos)
    rng = np.random.default_rng(0)
    with batcher:
        futs = [
            batcher.submit(
                rng.normal(size=TINY_SHAPE).astype(np.float32)
            )
            for _ in range(40)
        ]
        ys = [f.result(timeout=60) for f in futs]  # raises on any loss
    assert all(yy.shape == (10,) for yy in ys)
    assert chaos.kill_replica_fired
    assert pool.alive() == [0, 1]
    s = stats.snapshot()
    assert s["submitted"] == 40
    assert (s["completed"] + s["shed"] + s["expired"] + s["failed"]
            == s["submitted"])
    assert s["completed"] == 40  # zero deadline-violating losses


@pytest.mark.serve
def test_kill_replica_single_pool_respawns_as_survivor(host_devices):
    """With ONE replica there is no survivor to retry on: the failover
    path respawns the dead slot and retries there — still zero losses."""
    chaos = chaos_lib.ChaosMonkey.from_spec("kill-replica@0")
    pool, batcher, stats = _serve_stack(1, chaos=chaos)
    rng = np.random.default_rng(1)
    with batcher:
        futs = [
            batcher.submit(
                rng.normal(size=TINY_SHAPE).astype(np.float32)
            )
            for _ in range(8)
        ]
        for f in futs:
            f.result(timeout=60)
    assert pool.alive() == [0]
    s = stats.snapshot()
    assert s["completed"] == s["submitted"] == 8


@pytest.mark.serve
@pytest.mark.obs
def test_failover_journal_events_and_conservation(tmp_path, host_devices):
    """The obs journal across a failover carries replica_evicted /
    replica_respawned and still satisfies the conservation law."""
    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import ObsConfig
    from parallel_cnn_tpu.obs import events as events_lib

    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path)), run="serve-test"
    )
    chaos = chaos_lib.ChaosMonkey.from_spec("kill-replica@1")
    pool, batcher, stats = _serve_stack(2, chaos=chaos, obs=bundle)
    rng = np.random.default_rng(2)
    with batcher:
        futs = [
            batcher.submit(
                rng.normal(size=TINY_SHAPE).astype(np.float32)
            )
            for _ in range(24)
        ]
        for f in futs:
            f.result(timeout=60)
    counts = bundle.journal.counts()
    bundle.finish()
    assert counts.get("replica_evicted") == 1
    assert counts.get("replica_respawned") == 1
    assert counts.get("failover", 0) >= 1
    assert events_lib.conservation(counts) is None


# -- obs events across a training resize --------------------------------


def test_resize_events_in_journal(tmp_path, host_devices):
    """resize_begin/resize_done bracket every resize with old/new world
    + host coordinates and the trigger source."""
    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import ObsConfig
    from parallel_cnn_tpu.obs import events as events_lib

    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path)), run="elastic-test"
    )
    model = _nobn_model()
    comm = CommConfig(**_COMM)
    st, plan = _init8(model, comm)
    ctl = ElasticController(ElasticConfig(), world=8, obs=bundle)
    st, plan, _, comm2 = ctl.resize(0, 4, state=st, plan=plan, comm=comm)
    ctl.resize(1, 8, state=st, plan=plan, comm=comm2)
    paths = bundle.finish()
    recs = events_lib.read_journal(paths["journal"])
    begins = [r for r in recs if r["kind"] == "resize_begin"]
    dones = [r for r in recs if r["kind"] == "resize_done"]
    assert len(begins) == len(dones) == 2
    assert begins[0]["old_world"] == 8 and begins[0]["new_world"] == 4
    assert dones[1]["old_world"] == 4 and dones[1]["new_world"] == 8
    assert all(r["source"] == "direct" for r in begins)
    assert not any(r["from_ring"] for r in dones)
