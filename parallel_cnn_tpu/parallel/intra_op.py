"""Intra-op (model-axis) decomposition — the TPU-native analog of the MPI
kernel library (SURVEY.md §2.2 C15, §2.3).

The reference's MPI backend partitions each kernel's *output index space*
across ranks and sums partial results with `MPI_Reduce` (partition formula
at MPI/layer.h:172-175; 16 reduce sites). Translated to a TPU mesh, the
same capability becomes *sharded parameters + XLA collectives over ICI*,
composed with data parallelism on a 2-D (data, model) mesh — the "hybrid"
the reference names only as future work (README.md:24, PDF §8):

- conv c1: the 6 filters are sharded over ``model`` — each device computes
  its feature maps only (≙ the MPI split of fp_c1's output space,
  MPI/layer.h:162-201, minus bugs B1/B2).
- pool s1: channel-local, so it inherits the conv's channel sharding with
  NO communication (the reference re-reduces every kernel anyway — 18
  collectives per sample, PDF §7.1's scalability killer; here the only
  forward collective is the FC psum).
- fc f: the 216-wide contraction is sharded over ``model`` (the flattened
  (6,6,6) input is channel-major, so the channel shard IS a contiguous
  slice of the contraction dim); partial products are `psum`ed — the
  direct, correct form of the MPI partial-result+reduce pattern
  (MPI/layer.h:345-368), with the broadcast-back the reference forgot (B7).

Backward follows the same shardings; only three collectives appear per
step and XLA schedules them onto ICI: psum(pre_f), psum(g_w_s1 ⊕ g_b_s1 ⊕
misc scalars), psum over the data axis for DP.

Legal model-axis sizes divide 6 (the filter count): 1, 2, 3, 6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parallel_cnn_tpu.ops import reference as ops
from parallel_cnn_tpu.ops.activations import (
    apply_grad,
    error_norm,
    make_error,
    sigmoid,
    sigmoid_grad_from_preact,
)
from parallel_cnn_tpu.parallel import collectives
from parallel_cnn_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Params = ops.Params

# How the params pytree is laid out over the (data, model) mesh: conv
# filters and the FC contraction dim ride the model axis, everything else
# is replicated.
PARAM_SPECS: Params = {
    "c1": {"w": P(MODEL_AXIS), "b": P(MODEL_AXIS)},
    "s1": {"w": P(), "b": P()},
    "f": {"w": P(None, MODEL_AXIS), "b": P()},
}


def param_shardings(mesh: Mesh) -> Params:
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        PARAM_SPECS,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_params(mesh: Mesh, params: Params) -> Params:
    """Place a (host or replicated) params pytree into its 2-D layout.

    Copies first: the train step donates params, and device_put may alias
    the source buffer when it already lives on a mesh device.
    """
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(jnp.array(x), s), params, param_shardings(mesh)
    )


def _forward_local(params: Params, x: jax.Array):
    """Single-sample forward on one (data, model) shard.

    x: (28, 28) replicated over model; params already model-sharded, so
    w_c1 is (6/m, 5, 5) and w_f is (10, 216/m) *inside* shard_map.
    """
    pre_c1 = ops.conv_c1_forward(x, params["c1"]["w"], params["c1"]["b"])
    out_c1 = sigmoid(pre_c1)                       # (6/m, 24, 24) local channels
    pre_s1 = ops.pool_s1_forward(out_c1, params["s1"]["w"], params["s1"]["b"])
    out_s1 = sigmoid(pre_s1)                       # (6/m, 6, 6) local channels
    # Sharded 216-contraction: local (10, 216/m) @ local (216/m,) then psum
    # — partial-product + allreduce, the corrected MPI fp_preact_f pattern.
    partial = params["f"]["w"] @ out_s1.reshape(-1)
    pre_f = lax.psum(partial, MODEL_AXIS) + params["f"]["b"]
    out_f = sigmoid(pre_f)
    return pre_c1, out_c1, pre_s1, out_s1, pre_f, out_f


def _backward_local(params: Params, x, acts, label):
    """Reference-contract backward (ops/reference.py:backward) under the
    model sharding. Collectives: one fused psum for the shared-kernel grads."""
    pre_c1, out_c1, pre_s1, out_s1, pre_f, out_f = acts
    cm = out_c1.shape[0]

    d_pre_f = make_error(out_f, label)             # replicated over model
    err = error_norm(d_pre_f)

    # FC grads: outer product is naturally sharded over the contraction dim.
    g_w_f = jnp.outer(d_pre_f, out_s1.reshape(-1))     # (10, 216/m) local
    g_b_f = d_pre_f

    # Pool backward: each model shard only needs ITS columns of w_f.
    d_out_s1 = (params["f"]["w"].T @ d_pre_f).reshape(cm, 6, 6)
    d_pre_s1 = d_out_s1 * sigmoid_grad_from_preact(pre_s1)
    # Shared 4×4 kernel + scalar bias: contractions over ALL channels →
    # psum over model (≙ MPI bp_weight_s1's reduce, minus bug B5).
    out_c1_windows = out_c1.reshape(cm, 6, 4, 6, 4)
    g_w_s1_partial = jnp.einsum("mxy,mxiyj->ij", d_pre_s1, out_c1_windows)
    g_b_s1_partial = jnp.sum(d_pre_s1) / ops.POOL_BIAS_NORM
    g_w_s1, g_b_s1 = lax.psum((g_w_s1_partial, g_b_s1_partial), MODEL_AXIS)

    # Conv backward: channel-local throughout (filters are model-sharded).
    d_out_c1 = jnp.einsum(
        "mxy,ij->mxiyj", d_pre_s1, params["s1"]["w"]
    ).reshape(cm, 24, 24)
    d_pre_c1 = d_out_c1 * sigmoid_grad_from_preact(pre_c1)
    patches = lax.conv_general_dilated_patches(
        x[None, None, :, :], (5, 5), (1, 1), "VALID"
    )[0]                                            # (25, 24, 24), replicated
    g_w_c1 = (
        jnp.einsum("mxy,pxy->mp", d_pre_c1, patches).reshape(cm, 5, 5)
        / ops.CONV_NORM
    )
    g_b_c1 = jnp.sum(d_pre_c1, axis=(1, 2)) / ops.CONV_NORM

    grads: Params = {
        "c1": {"w": g_w_c1, "b": g_b_c1},
        "s1": {"w": g_w_s1, "b": g_b_s1},
        "f": {"w": g_w_f, "b": g_b_f},
    }
    return err, grads


def _sample_grads(params: Params, x: jax.Array, y: jax.Array):
    acts = _forward_local(params, x)
    return _backward_local(params, x, acts, y)


def make_2d_step(mesh: Mesh, dt: float, global_batch: int,
                 compute_dtype: str | None = None, comm=None):
    """Hybrid DP×model-parallel train step over the full 2-D mesh.

    params follow PARAM_SPECS; x:(B,28,28) / y:(B,) are sharded over the
    data axis and replicated over model. One jitted program; grads are
    allreduced over ``data`` (DP) while activations/grads inside each
    sample are decomposed over ``model`` (intra-op). ``comm`` (a
    config.CommConfig) picks the data-axis grad-reduce algorithm
    (collectives.tree_all_reduce); None is the historical monolithic
    psum. The model-axis activation collectives always stay psum — they
    are small and latency-bound, exactly where a ring loses.

    compute_dtype="bfloat16": the per-sample forward/backward (including
    the model-axis activation psum) runs bf16; grads are cast back to f32
    BEFORE the data-axis reduce, and params stay f32 master weights — the
    same mixed-precision recipe as train/step.py batched_step, composed
    with both mesh axes.
    """

    n_data = mesh.shape[DATA_AXIS]
    cdt = jnp.dtype(compute_dtype or "float32")

    def shard_body(params: Params, x: jax.Array, y: jax.Array):
        if x.shape[0] * n_data != global_batch:
            raise ValueError(
                f"batch {x.shape[0] * n_data} != global_batch {global_batch}"
            )
        cparams = jax.tree_util.tree_map(lambda p: p.astype(cdt), params)
        errs, grads = jax.vmap(_sample_grads, in_axes=(None, 0, 0))(
            cparams, x.astype(cdt), y
        )
        err_sum = lax.psum(jnp.sum(errs.astype(jnp.float32)), DATA_AXIS)
        local_sums = jax.tree_util.tree_map(
            lambda g: jnp.sum(g.astype(jnp.float32), axis=0), grads
        )
        grad_sum = collectives.tree_all_reduce(
            local_sums, DATA_AXIS, n_data, comm
        )
        mean_grads = jax.tree_util.tree_map(lambda g: g / global_batch, grad_sum)
        return apply_grad(params, mean_grads, dt), err_sum / global_batch

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(PARAM_SPECS, P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(PARAM_SPECS, P()),
        check_vma=(comm is None or comm.impl != "ring"),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def make_2d_forward(mesh: Mesh):
    """Batched model-parallel inference over the 2-D mesh → (B, 10) outputs."""

    def shard_body(params: Params, x: jax.Array):
        out = jax.vmap(lambda s: _forward_local(params, s)[-1])(x)
        return out

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(PARAM_SPECS, P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
    )
    return jax.jit(sharded)
