"""Epoch drivers (≙ learn() / test(), Sequential/Main.cpp:146-214).

Reproduces the reference's observable behavior — "Learning", per-epoch
`error: %e` lines, threshold early-stop, final `Error Rate: %.2lf%%` — on
top of jitted epoch programs, with correct (block_until_ready) timing
instead of the reference's un-synced clock() spans (SURVEY.md §5).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.config import Config
from parallel_cnn_tpu.data import pipeline
from parallel_cnn_tpu.models import lenet_ref
from parallel_cnn_tpu.parallel import data_parallel, intra_op, mesh as mesh_lib
from parallel_cnn_tpu.resilience import preempt
from parallel_cnn_tpu.resilience.rollback import (
    RollbackController,
    tree_copy,
)
from parallel_cnn_tpu.resilience.sentinel import DivergenceError, Sentinel
from parallel_cnn_tpu.train import step as step_lib
from parallel_cnn_tpu.utils.timing import Stopwatch

log = logging.getLogger(__name__)


@dataclass
class TrainResult:
    params: step_lib.Params
    epoch_errors: List[float] = field(default_factory=list)
    seconds: float = 0.0
    stopped_early: bool = False
    # Fault-tolerance outcomes (resilience/): how many divergences were
    # rolled back, and whether a preemption signal stopped the run early
    # (the last finished epoch is checkpointed; --resume continues it).
    rollbacks: int = 0
    preempted: bool = False


def _native_batcher_cls(tc):
    """The native Batcher class when configured and buildable, else None."""
    if tc.batch_size <= 1 or tc.prefetch == "off":
        return None
    try:
        from parallel_cnn_tpu.data import native
    except ImportError:
        if tc.prefetch == "native":
            raise
        return None
    return native.Batcher


def _maybe_mesh(cfg: Config):
    """Build the training mesh when the config asks for one, else None.

    Opt-in: the default MeshConfig (data=None, model=1) means single-device
    training — setting either axis (cli --mesh-data/--mesh-model) routes
    the minibatch path through the mesh (≙ the reference's MPI driver being
    an actually-launchable program, MPI/Main.cpp:43-53).
    """
    mc, tc = cfg.mesh, cfg.train
    if mc.data is None and mc.model == 1:
        return None
    if tc.batch_size == 1:
        raise ValueError(
            "mesh training is the minibatch throughput mode; batch_size=1 "
            "strict parity is inherently sequential and single-device"
        )
    # Mesh construction routes through the ExecutionPlan — the single
    # resolution site (plan.build_plan → plan.make_mesh); no direct
    # mesh_lib constructor calls here.
    from parallel_cnn_tpu import plan as plan_lib

    eplan = plan_lib.build_plan(cfg).validate()
    mesh = eplan.make_mesh()
    if mesh is None:
        return None
    if (mesh_lib.DATA_AXIS not in mesh.axis_names
            or mesh_lib.MODEL_AXIS not in mesh.axis_names):
        raise ValueError(
            "the reference trainer drives a flat (data, model) mesh only; "
            f"the resolved plan built axes {tuple(mesh.axis_names)} — "
            "drop the pipeline/hierarchical knobs for this model"
        )
    if tc.ops == "pallas" and mesh.shape[mesh_lib.MODEL_AXIS] > 1:
        raise ValueError(
            "ops='pallas' composes with the data axis only (the fused "
            "kernel is batch-local); use --mesh-model 1 or ops='reference'"
        )
    n_data, n_model = mesh.shape[mesh_lib.DATA_AXIS], mesh.shape[mesh_lib.MODEL_AXIS]
    if 6 % n_model:
        raise ValueError(
            f"model axis {n_model} must divide the 6 conv filters "
            "(legal: 1, 2, 3, 6 — parallel/intra_op.py PARAM_SPECS)"
        )
    if tc.batch_size % n_data:
        raise ValueError(
            f"batch_size {tc.batch_size} must divide evenly over the "
            f"data axis ({n_data})"
        )
    return mesh


def _fixed_shape_batches(train, tc, epoch_seed, batcher_cls, steps_per_epoch):
    """One epoch of fixed-shape (drop-tail) batches, native ring when built,
    bit-identical NumPy twin otherwise ("off" keeps PCG order)."""
    if batcher_cls is not None and steps_per_epoch > 0:
        with batcher_cls(
            train.images, train.labels, tc.batch_size,
            seed=epoch_seed, shuffle=tc.shuffle,
        ) as batcher:
            for _ in range(steps_per_epoch):
                yield next(batcher)
    elif tc.prefetch == "auto":
        yield from pipeline.native_semantics_batches(
            train, tc.batch_size, shuffle=tc.shuffle, seed=epoch_seed
        )
    else:
        yield from pipeline.epoch_batches(
            train, tc.batch_size, shuffle=tc.shuffle, seed=epoch_seed,
            drop_remainder=True,
        )


def learn(
    cfg: Config,
    train: pipeline.Dataset,
    params: Optional[step_lib.Params] = None,
    verbose: bool = True,
    epoch_offset: int = 0,
    epoch_callback=None,
    chaos=None,
    ring=None,
    obs: Optional["obs_lib.Obs"] = None,
) -> TrainResult:
    """≙ learn() (Sequential/Main.cpp:146-184): epoch loop with mean
    err-norm metric and threshold early-stop.

    batch_size == 1 → strict-parity scan (per-sample SGD, the reference
    trajectory); batch_size > 1 → minibatch steps.

    `epoch_offset` shifts the per-epoch derived seeds so a resumed run
    shuffles exactly like the continuous run it restarts (pass the number
    of epochs already completed). `epoch_callback(epoch, params, err)` —
    with `epoch` global (offset included, 1-based) — fires after every
    epoch; use it for mid-training checkpoints and metrics.

    Fault tolerance (cfg.resilience): each epoch's loss and params pass
    the health sentinel; a non-finite result triggers the configured
    policy (raise / skip / rollback with LR backoff, bounded by
    max_rollbacks). A rollback restores the in-memory last-good snapshot
    (or `ring`, a resilience.CheckpointRing, across processes) and
    retries the SAME epoch — the per-epoch derived seed makes the retry
    deterministic. A preemption signal (resilience/preempt) stops the
    loop at the next epoch boundary, after `epoch_callback` has flushed
    its checkpoint. `chaos` is a resilience.ChaosMonkey used by the fault
    -injection tests; it is consulted after every optimizer step (the
    strict-parity scan counts as one) and at every epoch boundary.
    """
    tc = cfg.train
    res = cfg.resilience
    # Host-side observability: spans wrap dispatch/readback only, journal
    # events mark epoch outcomes — nothing enters the jitted bodies.
    obs = obs if obs is not None else obs_lib.NOOP
    if params is None:
        params = lenet_ref.init(jax.random.key(tc.seed))
    else:
        # The jitted steps donate params' buffers to XLA; copy so the
        # caller's pytree stays alive after training on device backends.
        params = jax.tree_util.tree_map(jnp.array, params)
    if verbose:
        print("Learning")

    result = TrainResult(params)
    sw = Stopwatch()
    if tc.batch_size == 1:
        images = jnp.asarray(train.images)
        labels = jnp.asarray(train.labels)

    batcher_cls = _native_batcher_cls(tc)
    steps_per_epoch = len(train) // tc.batch_size if tc.batch_size > 1 else 0
    # Which kernel library executes the minibatch step (cfg.train.ops):
    # path A (jnp/lax) or path B (Pallas/Mosaic). A kernel that cannot
    # compile fails the run; there is no quiet degrade to path A.
    batched_step = step_lib.batched_step_fn(
        tc.ops, fused=cfg.fused is not None,
    )

    # dt is a local because auto-rollback may scale it (res.lr_backoff);
    # the jitted steps take it as a static arg, so a changed dt is just
    # one extra compile on the (rare) recovery path.
    dt = tc.dt

    sentinel = Sentinel() if res.policy != "off" else None
    controller = None
    if res.policy == "rollback":
        controller = RollbackController(
            max_rollbacks=res.max_rollbacks,
            lr_backoff=res.lr_backoff,
            ring=ring,
        )
    last_good = None

    # Mesh routing (cfg.mesh, opt-in): DP when model axis is 1, hybrid
    # DP×intra-op otherwise. Params move into their mesh layout once; each
    # batch is shard-put over the data axis.
    mesh = _maybe_mesh(cfg)
    mesh_step = None
    build_mesh_step = None
    if mesh is not None:
        if steps_per_epoch == 0:
            raise ValueError(
                f"batch_size {tc.batch_size} exceeds dataset size {len(train)}"
            )
        if mesh.shape[mesh_lib.MODEL_AXIS] > 1:
            params = intra_op.shard_params(mesh, params)

            def build_mesh_step(dt_):
                return intra_op.make_2d_step(
                    mesh, dt=dt_, global_batch=tc.batch_size,
                    compute_dtype=tc.dtype, comm=cfg.comm,
                )
        else:
            params = mesh_lib.replicate(mesh, params)

            def build_mesh_step(dt_):
                # cfg.comm routes the gradient allreduce through
                # parallel/collectives.py (psum vs bucketed ring ± bf16
                # wire); None keeps the historical monolithic psum.
                return data_parallel.make_dp_step(
                    mesh, dt=dt_, global_batch=tc.batch_size,
                    compute_dtype=tc.dtype, ops_path=tc.ops, comm=cfg.comm,
                )

        mesh_step = build_mesh_step(dt)
        if verbose:
            print(f"mesh: {dict(mesh.shape)}")

    if sentinel is not None:
        # The pre-training state is the first "last good": a divergence in
        # epoch 0 still has something to skip/roll back to.
        last_good = tree_copy(params)
        if controller is not None:
            controller.commit(params)

    def _chaos_step(p, e):
        return chaos.after_step(p, e) if chaos is not None else (p, e)

    epoch = 0
    _chaos_logged = False
    while epoch < tc.epochs:
        # Per-epoch derived seed: every path reshuffles each epoch (and all
        # paths draw the same epoch boundary semantics — an epoch is one
        # pass from index 0, shuffled or in file order).
        epoch_seed = tc.seed + epoch_offset + epoch
        with sw, obs.span(
            "train.epoch", cat="train", epoch=epoch_offset + epoch + 1
        ):
            if tc.batch_size == 1:
                if tc.shuffle:
                    perm = jnp.asarray(
                        np.random.default_rng(epoch_seed).permutation(
                            len(train)
                        )
                    )
                    ex, ey = images[perm], labels[perm]
                else:
                    ex, ey = images, labels
                params, err = _chaos_step(
                    *step_lib.scan_epoch(params, ex, ey, dt)
                )
            elif steps_per_epoch > 0 and (
                mesh_step is not None
                or batcher_cls is not None
                or tc.prefetch == "auto"
            ):
                # Fixed-shape (drop-tail) minibatch epoch: native prefetch
                # ring when built, its bit-identical NumPy twin otherwise
                # ("auto" reproducibility contract). Mesh mode shards each
                # batch over the data axis.
                errs = []
                for bx, by in _fixed_shape_batches(
                    train, tc, epoch_seed, batcher_cls, steps_per_epoch
                ):
                    if mesh_step is not None:
                        # Shard straight from host NumPy: wrapping in
                        # jnp.asarray first would commit the full batch to
                        # device 0 and pay a second transfer to reshard.
                        xs_, ys_ = mesh_lib.shard_batch(mesh, (bx, by))
                        params, e = _chaos_step(*mesh_step(params, xs_, ys_))
                    else:
                        params, e = _chaos_step(*batched_step(
                            params,
                            jnp.asarray(bx),
                            jnp.asarray(by),
                            dt,
                            compute_dtype=tc.dtype,
                        ))
                    errs.append(e)
                err = jnp.mean(jnp.stack(errs))
            else:
                errs, weights = [], []
                # drop_remainder=False: the tail batch runs at its own
                # (smaller) shape — one extra XLA compile, no dropped data.
                for bx, by in pipeline.epoch_batches(
                    train,
                    tc.batch_size,
                    shuffle=tc.shuffle,
                    seed=epoch_seed,
                    drop_remainder=False,
                ):
                    params, e = _chaos_step(*batched_step(
                        params,
                        jnp.asarray(bx),
                        jnp.asarray(by),
                        dt,
                        compute_dtype=tc.dtype,
                    ))
                    errs.append(e)
                    weights.append(bx.shape[0])
                w = jnp.asarray(weights, jnp.float32)
                err = jnp.sum(jnp.stack(errs) * w) / jnp.sum(w)
            with obs.span("train.readback", cat="train"):
                err = float(err)  # blocks: everything above is async

        if sentinel is not None:
            verdict = sentinel.check(loss=err, params=params)
            if not verdict.healthy:
                g_epoch = epoch_offset + epoch + 1
                if obs.enabled:
                    obs.event(
                        "verdict", healthy=False, epoch=g_epoch,
                        reason=verdict.reason, policy=res.policy,
                    )
                if res.policy == "raise":
                    raise DivergenceError(
                        f"epoch {g_epoch}: {verdict.reason}"
                    )
                if res.policy == "skip":
                    log.warning(
                        "sentinel: %s at epoch %d — discarding the "
                        "epoch's update, continuing from last-good",
                        verdict.reason, g_epoch,
                    )
                    params = tree_copy(last_good)
                    epoch += 1
                    continue
                # rollback: restore newest healthy state, scale the LR,
                # retry the SAME epoch (bounded by max_rollbacks).
                params, _ = controller.rollback(
                    like=params, reason=f"epoch {g_epoch}: {verdict.reason}"
                )
                result.rollbacks = controller.rollbacks
                if obs.enabled:
                    obs.event(
                        "rollback", epoch=g_epoch,
                        rollbacks=controller.rollbacks,
                        lr_scale=controller.lr_scale,
                    )
                new_dt = tc.dt * controller.lr_scale
                if new_dt != dt:
                    dt = new_dt
                    if build_mesh_step is not None:
                        mesh_step = build_mesh_step(dt)
                continue
            last_good = tree_copy(params)
            if controller is not None:
                controller.commit(params)

        result.epoch_errors.append(err)
        if obs.enabled:
            obs.event(
                "epoch", epoch=epoch_offset + epoch + 1, loss=err,
                seconds=sw.total,
            )
        if epoch_callback is not None:
            epoch_callback(epoch_offset + epoch + 1, params, err)
        if chaos is not None:
            if obs.enabled and chaos.nan_fired and not _chaos_logged:
                _chaos_logged = True
                obs.event(
                    "chaos", injected="nan", epoch=epoch_offset + epoch + 1
                )
            chaos.at_epoch(epoch_offset + epoch + 1)
        if verbose:
            # ≙ fprintf at Sequential/Main.cpp:174
            print(f"error: {err:e}, time_on_cpu: {sw.total:f}")
        if err < tc.threshold:
            result.stopped_early = True
            if verbose:
                # ≙ Sequential/Main.cpp:177
                print("Training complete, error less than threshold\n")
            break
        if preempt.requested():
            # The epoch_callback above already flushed this epoch's
            # checkpoint; stop at the boundary and let the driver exit
            # cleanly (--resume continues bit-exactly).
            result.preempted = True
            if obs.enabled:
                obs.event("preempt", epoch=epoch_offset + epoch + 1)
            if verbose:
                print(
                    f"preemption: stopping after epoch "
                    f"{epoch_offset + epoch + 1} (checkpoint flushed)"
                )
            break
        epoch += 1

    result.params = params
    result.seconds = sw.total
    if verbose:
        print(f"\n Time - {sw.total:f}")  # ≙ Sequential/Main.cpp:183
    return result


def test(
    params: step_lib.Params,
    test_ds: pipeline.Dataset,
    batch_size: int = 1000,
    verbose: bool = True,
) -> float:
    """≙ test() (Sequential/Main.cpp:202-214): % misclassified on the test
    split, evaluated in on-device batches rather than per-sample."""
    n = len(test_ds)
    errors = 0
    for i in range(0, n, batch_size):
        x = jnp.asarray(test_ds.images[i : i + batch_size])
        y = jnp.asarray(test_ds.labels[i : i + batch_size])
        errors += int(step_lib.error_count(params, x, y))
    rate = errors / n * 100.0
    if verbose:
        print(f"Error Rate: {rate:.2f}%")  # ≙ Sequential/Main.cpp:212-213
    return rate


def run(cfg: Config, verbose: bool = True) -> float:
    """≙ main() (Sequential/Main.cpp:44-57): loaddata → learn → test."""
    train_ds, test_ds = pipeline.load_train_test(cfg.data)
    result = learn(cfg, train_ds, verbose=verbose)
    return test(result.params, test_ds, verbose=verbose)
