"""Jit-compiled train steps (≙ the body of learn(), Sequential/Main.cpp:146-184).

Two modes, per SURVEY.md §7 "hard parts":

- **Strict parity** (`scan_epoch` / `sgd_step`): batch size 1, weights
  updated after every sample — the reference's exact optimization
  trajectory (Sequential/Main.cpp:157-171). On TPU the 60k-iteration Python
  loop becomes ONE `lax.scan` inside jit: the whole epoch is a single XLA
  program, no host round-trips.

- **Throughput** (`batched_step`): per-sample reference grads computed with
  `vmap`, averaged over the batch, one update per batch. This changes the
  optimization trajectory (minibatch vs per-sample SGD) — a deliberate,
  documented equivalence gap; it is the mode that feeds the MXU batched
  convs and the data-parallel mesh path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from parallel_cnn_tpu.ops import reference as ops
from parallel_cnn_tpu.ops.activations import apply_grad

Params = ops.Params


def local_grad_sums(params: Params, x: jax.Array, y: jax.Array,
                    compute_dtype=None, ops_path: str = "reference"):
    """Reference-contract grads SUMMED over a batch: (err_sum, grad_sums).

    The shared grad engine for minibatch training — `batched_step` divides
    by the local batch, the data-parallel shard bodies
    (parallel/data_parallel.py) psum the sums over ICI and divide by the
    GLOBAL batch, so both modes share one numerics definition.

    compute_dtype="bfloat16" runs the forward/backward in bf16 (params
    stay f32 master weights in the caller; the cast here is local) and
    returns f32 sums — cross-device collectives and updates are always
    f32. ops_path="pallas" computes the grads in the fused Mosaic
    megakernel (ops/pallas.py); the kernel is batch-local, so every
    composition is just this call.
    """
    cdt = jnp.dtype(compute_dtype or "float32")
    cparams = jax.tree_util.tree_map(lambda p: p.astype(cdt), params)
    cx = x.astype(cdt)
    if ops_path == "pallas":
        if cdt != jnp.float32:
            raise ValueError(
                "ops_path='pallas' computes f32 (the fused kernel casts its "
                "inputs); a bf16 request would be silently mislabeled"
            )
        from parallel_cnn_tpu.ops import pallas as pk

        n_local = x.shape[0]
        err_mean, mean_grads = pk.fused_value_and_ref_grads(cparams, cx, y)
        sum_grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * n_local, mean_grads
        )
        return err_mean.astype(jnp.float32) * n_local, sum_grads
    errs, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(
        cparams, cx, y
    )
    sum_grads = jax.tree_util.tree_map(
        lambda g: jnp.sum(g.astype(jnp.float32), axis=0), grads
    )
    return jnp.sum(errs.astype(jnp.float32)), sum_grads


def sgd_step(params: Params, x: jax.Array, y: jax.Array, dt: float) -> Tuple[Params, jax.Array]:
    """One per-sample step: forward → hand-written backward → p += dt·g
    (≙ one iteration of the loop at Sequential/Main.cpp:157-171)."""
    err, grads = ops.value_and_ref_grads(params, x, y)
    return apply_grad(params, grads, dt), err


@functools.partial(jax.jit, static_argnames=("dt",), donate_argnums=(0,))
def scan_epoch(params: Params, images: jax.Array, labels: jax.Array, dt: float) -> Tuple[Params, jax.Array]:
    """A full per-sample-SGD epoch as one `lax.scan` (strict parity mode).

    Returns (params, mean err-norm) — the per-epoch metric printed by
    learn() (`err /= train_cnt`, Sequential/Main.cpp:173-174).
    """

    def body(p, xy):
        x, y = xy
        p, err = sgd_step(p, x, y, dt)
        return p, err

    params, errs = jax.lax.scan(body, params, (images, labels))
    return params, jnp.mean(errs)


@functools.partial(
    jax.jit, static_argnames=("dt", "compute_dtype"), donate_argnums=(0,)
)
def batched_step(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    dt: float,
    compute_dtype: str | None = None,
) -> Tuple[Params, jax.Array]:
    """Minibatch step: vmapped reference grads, mean-reduced over the batch.

    x: (B, 28, 28), y: (B,). The mean (not sum) keeps the effective step
    size comparable to the per-sample mode across batch sizes.

    compute_dtype="bfloat16" runs the forward/backward mixed-precision:
    params stay float32 master weights, the compute path (and therefore
    the MXU convs/contractions) runs bf16, and grads are cast back to f32
    for the update. A documented throughput-mode deviation from the f32
    reference numerics (SURVEY.md §2.1) — the strict-parity per-sample
    path stays f32-only.
    """
    err_sum, grad_sums = local_grad_sums(params, x, y, compute_dtype)
    n = x.shape[0]
    mean_grads = jax.tree_util.tree_map(lambda g: g / n, grad_sums)
    return apply_grad(params, mean_grads, dt), err_sum / n


@functools.partial(
    jax.jit, static_argnames=("dt", "compute_dtype"), donate_argnums=(0,)
)
def fused_batched_step(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    dt: float,
    compute_dtype: str | None = None,
) -> Tuple[Params, jax.Array]:
    """`batched_step` with the round-7 fused bucket update: same
    `local_grad_sums` engine, but the per-leaf `p += dt·g` tree pass is
    replaced by ONE ops.pallas_update kernel per gradient bucket
    (tree_sgd) — the single-device consumer of the update-on-arrival
    kernels. The batch mean rides in the kernel's scalar operand
    (scale=1/B) and the reference's gradient-ASCENT convention maps to
    lr=−dt, so the update is `p − (−dt)·(g_sum/B)` — numerically the
    `apply_grad ∘ mean` composition, bit-compared in
    tests/test_fused_step.py.
    """
    from parallel_cnn_tpu.ops import pallas_update

    err_sum, grad_sums = local_grad_sums(params, x, y, compute_dtype)
    n = x.shape[0]
    params = pallas_update.tree_sgd(
        params, grad_sums, lr=-dt, scale=1.0 / n
    )
    return params, err_sum / n


@functools.partial(
    jax.jit, static_argnames=("dt", "compute_dtype"), donate_argnums=(0,)
)
def pallas_batched_step(
    params: Params,
    x: jax.Array,
    y: jax.Array,
    dt: float,
    compute_dtype: str | None = None,
) -> Tuple[Params, jax.Array]:
    """`batched_step` on the Pallas kernel path (ops/pallas.py, path B).

    Same reference numerics contract, but every FLOP-bearing stage runs in
    a hand-written Mosaic kernel (≙ the CUDA driver wiring its kernels into
    learn(), CUDA/main.cu:56-163). Differentially tested against
    `batched_step` in tests/test_train.py.
    """
    from parallel_cnn_tpu.ops import pallas as pk

    cdt = jnp.dtype(compute_dtype or "float32")
    if cdt != jnp.float32:
        # The fused megakernel casts inputs to f32 internally — honoring a
        # bf16 request silently would mislabel the run (config.py rejects
        # the combination at the driver level; this guards direct callers).
        raise ValueError("the pallas path computes f32; use ops='reference' for bf16")
    cparams = jax.tree_util.tree_map(lambda p: p.astype(cdt), params)
    err, mean_grads = pk.batched_value_and_ref_grads(cparams, x.astype(cdt), y)
    mean_grads = jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), mean_grads
    )
    return apply_grad(params, mean_grads, dt), err.astype(jnp.float32)


def batched_step_fn(ops_path: str, fused: bool = False):
    """The minibatch step for a TrainConfig.ops value.

    ``ops_path="pallas"`` IS the Pallas step: a Mosaic compile failure
    fails the run with the compiler's text — asking for Pallas and
    silently training on XLA is what this function must never do.

    ``fused=True`` (cfg.fused, i.e. --fused-step / PCNN_FUSED_STEP)
    selects the fused bucket-update step on the reference grad engine;
    the Pallas megakernel path keeps its own update (its step is one
    fused program already).
    """
    if ops_path == "pallas":
        return pallas_batched_step
    return fused_batched_step if fused else batched_step


@jax.jit
def classify_batch(params: Params, x: jax.Array) -> jax.Array:
    """≙ classify() (Sequential/Main.cpp:186-200), vectorized: argmax of the
    10 sigmoid outputs for a batch of images."""
    return jax.vmap(ops.predict, in_axes=(None, 0))(params, x)


@jax.jit
def error_count(params: Params, x: jax.Array, y: jax.Array) -> jax.Array:
    """Misclassification count on a batch (≙ test()'s error accumulation,
    Sequential/Main.cpp:202-211)."""
    return jnp.sum(classify_batch(params, x) != y)
