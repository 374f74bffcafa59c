"""Data-parallel training over the mesh's ``data`` axis.

This is the TPU-native realization of what the reference's MPI backend was
*meant* to be (SURVEY.md §2.3): the BASELINE.json north star describes
"batch-partition + gradient MPI_Allreduce"; the actual MPI code instead
partitions each kernel's output index space and root-reduces 16 times per
sample (MPI/layer.h:195,…,727) with no broadcast back (bug B7). Here:

- the epoch tensor is sharded once over the data axis (one H2D transfer,
  not 60k — contrast CUDA/layer.cu:60-63),
- each device computes reference-contract grads on its local shard via the
  same single-sample ops, vmapped,
- ONE `psum` per step reduces the grad pytree over ICI — a true allreduce,
  so every device holds identical updated params (B7 impossible),
- the whole step is a single jitted shard_map program; XLA overlaps the
  collective with compute where profitable.

Semantics note (SURVEY.md §7 "hard parts"): DP is minibatch SGD — it cannot
reproduce the reference's per-sample update trajectory, which is inherently
sequential. The strict-parity path stays on one device
(train/step.py:scan_epoch); DP is the throughput mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from parallel_cnn_tpu.ops import reference as ops
from parallel_cnn_tpu.ops.activations import apply_grad
from parallel_cnn_tpu.parallel import collectives
from parallel_cnn_tpu.parallel.mesh import DATA_AXIS

Params = ops.Params


def _local_grads(params: Params, x: jax.Array, y: jax.Array,
                 compute_dtype=None, ops_path: str = "reference"):
    """Per-device shard: reference grads summed over the local batch —
    shared with the single-device minibatch step (one numerics definition
    for both modes; the bf16 and Pallas routing lives there too)."""
    # Deferred import: train/__init__ pulls in trainer, which imports this
    # package — a top-level import here would run during that partial init.
    from parallel_cnn_tpu.train.step import local_grad_sums

    return local_grad_sums(params, x, y, compute_dtype, ops_path)


def _dp_update(params: Params, x: jax.Array, y: jax.Array, dt: float,
               global_batch: int, compute_dtype=None,
               ops_path: str = "reference", comm=None, axis_size: int = 1):
    """One DP update on a device's shard (runs inside shard_map): local
    reference grads → ONE allreduce over ICI (≙ the MPI backend's 16
    root-only reduces per SAMPLE, MPI/layer.h) → mean → `p += dt·g`. The
    allreduce broadcasts too, so every device ends the step with identical
    params. ``comm`` selects the algorithm (collectives.tree_all_reduce):
    None/psum keeps the monolithic psum, impl="ring" goes bucketed ring
    RS+AG, optionally bf16-on-the-wire."""
    err_sum, grad_sum = _local_grads(params, x, y, compute_dtype, ops_path)
    err_sum = jax.lax.psum(err_sum, DATA_AXIS)  # scalar: bucketing is noise
    grad_sum = collectives.tree_all_reduce(grad_sum, DATA_AXIS, axis_size, comm)
    mean_grads = jax.tree_util.tree_map(lambda g: g / global_batch, grad_sum)
    return apply_grad(params, mean_grads, dt), err_sum / global_batch


def make_dp_step(mesh: Mesh, dt: float, global_batch: int,
                 compute_dtype: str | None = None, ops_path: str = "reference",
                 comm=None):
    """Build the jitted DP train step for a fixed global batch size.

    Returns step(params, x, y) -> (params, mean_err) where x:(B,28,28) and
    y:(B,) are sharded over the data axis and params are replicated
    (f32 master weights regardless of compute_dtype). ``comm`` (a
    config.CommConfig) picks the gradient-allreduce algorithm; None is the
    historical monolithic psum.
    """

    n_data = mesh.shape[DATA_AXIS]

    def shard_body(params: Params, x: jax.Array, y: jax.Array):
        # Shapes are static at trace time: a batch that doesn't match the
        # baked-in global_batch would silently mis-scale the grad mean.
        if x.shape[0] * n_data != global_batch:
            raise ValueError(
                f"batch {x.shape[0] * n_data} != global_batch {global_batch}"
            )
        return _dp_update(params, x, y, dt, global_batch, compute_dtype,
                          ops_path, comm, n_data)

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P()),
        # pallas_call's out_shape carries no varying-mesh-axes info, and
        # ring ppermute outputs are per-device values, so the replication
        # checker cannot see through either; the differential tests pin
        # the semantics instead.
        check_vma=(ops_path != "pallas"
                   and (comm is None or comm.impl != "ring")),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_dp_eval(mesh: Mesh):
    """Sharded misclassification count: each device classifies its shard of
    the test set, psum the error count (≙ test(), Sequential/Main.cpp:202-211).

    Takes a validity mask so a set padded up to an even data-axis split
    (mesh.pad_to_multiple) never counts its pad rows as real samples.
    """

    def shard_body(params: Params, x: jax.Array, y: jax.Array, mask: jax.Array):
        pred = jax.vmap(ops.predict, in_axes=(None, 0))(params, x)
        return jax.lax.psum(jnp.sum((pred != y) & mask), DATA_AXIS)

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
    )
    return jax.jit(sharded)


def make_dp_epoch(mesh: Mesh, dt: float, global_batch: int):
    """A full DP epoch as one jitted lax.scan over pre-sharded batches.

    images: (S, B, 28, 28), labels: (S, B) with the B axis sharded over
    ``data`` — the whole epoch runs on-device with no host round-trips,
    the batched counterpart of train/step.py:scan_epoch.
    """

    n_data = mesh.shape[DATA_AXIS]

    def shard_body(params: Params, images: jax.Array, labels: jax.Array):
        if images.shape[1] * n_data != global_batch:
            raise ValueError(
                f"batch {images.shape[1] * n_data} != global_batch {global_batch}"
            )

        def body(p, xy):
            x, y = xy
            return _dp_update(p, x, y, dt, global_batch)

        params, errs = jax.lax.scan(body, params, (images, labels))
        return params, jnp.mean(errs)

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(P(), P(None, DATA_AXIS), P(None, DATA_AXIS)),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,))
