"""Generic trainer for the model zoo (CIFAR CNN, ResNets — BASELINE.json
configs #3-#5): softmax cross-entropy + optax SGD/momentum, data-parallel
via GSPMD, optional gradient accumulation.

Parallelism style contrast (both are first-class in this framework):
- the reference-parity path uses *explicit* shard_map + psum
  (parallel/intra_op.py) — the corrected analog of the reference's
  hand-placed per-kernel MPI_Reduce;
- this zoo path uses *compiler* parallelism: one jit with the batch
  sharded over the mesh's ``data`` axis and params replicated. XLA/GSPMD
  inserts the gradient all-reduce (and makes BatchNorm's batch means
  global) automatically — the idiomatic TPU answer when you don't need
  per-op control.

Gradient accumulation (config #5: "ResNet-50 … DP + grad accumulation")
is an unrolled, barrier-sequenced microbatch loop inside the same jitted
step (see microbatch_grads for why not lax.scan).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.nn.core import Module
from parallel_cnn_tpu.nn.layers import has_random_state
from parallel_cnn_tpu.parallel import mesh as mesh_lib
from parallel_cnn_tpu.parallel.mesh import DATA_AXIS, HOST_AXIS, STAGE_AXIS


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ZooState:
    """Everything a training step threads through (a pytree — jit-able,
    donate-able, checkpoint-able as a unit)."""

    params: Any
    model_state: Any  # BatchNorm running stats etc.
    opt_state: Any


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedOptState:
    """Optimizer state of the update-on-arrival step (round 7).

    The momentum lives PERSISTENTLY SHARDED — one ``(n_data, bucket_len //
    n_data)`` f32 leaf per collectives bucket, each device owning its own
    row — because the fused step only ever touches the local shard: the
    reduce-scattered gradient shard updates it in place and the updated
    *param* shard is what the final all-gather ships. The dynamic
    loss-scale state (scale / good-step counter / skip counter) rides in
    the same pytree so it checkpoints, donates, and resumes with the rest
    of ZooState.
    """

    mom: Any                 # per-bucket momentum shards, (n_data, L) f32
    scale: jax.Array         # f32 scalar: current dynamic loss scale
    good_steps: jax.Array    # i32: overflow-free steps since last change
    skipped: jax.Array       # i32: total updates dropped on overflow


def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels
    ).mean()


def _build_loss_fn(model: Module, fused) -> Callable:
    """The zoo loss closure, with the round-7 fused-step refinements.

    fused=None reproduces the historical loss exactly. With a
    config.FusedStepConfig: (a) ``act_dtype="bfloat16"`` casts the input
    and every float param leaf to bf16 at the TOP of the traced loss —
    the f32 masters live outside the graph, and the cast's transpose
    returns f32 gradients, so the optimizer math stays master-precision;
    (b) ``tail=True`` routes a recognized pool→flatten→Dense suffix
    through ops.pallas_tail.fused_tail_loss (custom VJP emitting dlogits
    directly), degrading to the unfused composition — with a one-time
    note — when the model's head doesn't match a supported pattern.
    """
    own = getattr(model, "loss", None)
    if own is not None:
        # A model whose loss is more than the cross-entropy of its output
        # (nn/glm_moe.py: a second prediction head on the hidden states,
        # balance terms of its layers, logits a block at a time) owns it:
        # `loss(params, model_state, x, y) -> (loss, new model state)`.
        if fused is not None:
            raise ValueError(
                f"{type(model).__name__} computes its own loss; the fused "
                "tail and the bf16 cast of `fused=` do not apply to it")
        return own
    if fused is None:
        def loss_fn(params, model_state, x, y):
            logits, new_state = model.apply(params, model_state, x,
                                            train=True)
            return cross_entropy(logits, y), new_state

        return loss_fn

    from parallel_cnn_tpu.ops import pallas_tail

    act = jnp.dtype(fused.act_dtype)
    split = pallas_tail.split_tail(model) if fused.tail else None
    if fused.tail and split is None:
        print("fused-step: model tail not fusable; keeping unfused tail")

    def loss_fn(params, model_state, x, y):
        if act != jnp.float32:
            x = x.astype(act)
            params = jax.tree_util.tree_map(
                lambda p: p.astype(act)
                if jnp.issubdtype(p.dtype, jnp.floating)
                else p,
                params,
            )
        if split is None:
            logits, new_state = model.apply(params, model_state, x,
                                            train=True)
            return cross_entropy(logits, y), new_state
        feats = x
        new_states = []
        for layer, p, s in zip(
            model.layers[: split.trunk],
            params[: split.trunk],
            model_state[: split.trunk],
            strict=True,
        ):
            feats, s = layer.apply(p, s, feats, train=True)
            new_states.append(s)
        # The fused tail replaces layers[trunk:]; those layers carry no
        # state (empty dicts) — append them unchanged so the new state
        # list keeps Sequential's aligned structure.
        new_states.extend(model_state[split.trunk :])
        dense = params[-1]
        loss = pallas_tail.fused_tail_loss(
            feats, dense["w"], dense["b"], y, pool=split.pool
        )
        return loss, new_states

    return loss_fn


OPTIMIZERS = ("sgd", "adamw")


def make_optimizer(
    lr: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    schedule: str = "constant",
    warmup_steps: int = 0,
    total_steps: Optional[int] = None,
    kind: str = "sgd",
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """The zoo's optimizer under an LR schedule.

    kind: "sgd" — SGD(+``momentum``) with ``weight_decay`` added to the
    gradient of every parameter (L2); "adamw" — Adam(``b1``, ``b2``,
    ``eps``, bias-corrected) with DECOUPLED decay, ``p -= lr * (adam +
    weight_decay * p)``, applied to parameters of rank >= 2 only (conv and
    linear weights; biases, norm scales and LayerScale gains are not
    decayed — the ConvNeXt authors' implementation; their paper is
    silent). ``momentum`` is SGD's, ``b1``/``b2``/``eps`` are AdamW's.

    schedule: "constant" (optional linear warmup over `warmup_steps`) or
    "cosine" (linear warmup then cosine decay to 0 over `total_steps` —
    required for cosine, since the decay horizon must be known at trace
    time; the step count lives in the optimizer state, so it checkpoints
    and resumes with the rest of ZooState).
    """
    if kind not in OPTIMIZERS:
        raise ValueError(
            f"unknown optimizer kind {kind!r}; known: {', '.join(OPTIMIZERS)}"
        )
    if schedule == "cosine":
        if not total_steps:
            raise ValueError("schedule='cosine' needs total_steps")
        lr = optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=lr,
            warmup_steps=warmup_steps,
            decay_steps=total_steps,
        )
    elif schedule == "constant":
        if warmup_steps:
            lr = optax.linear_schedule(0.0, lr, warmup_steps)
    else:
        raise ValueError(f"unknown schedule {schedule!r}")
    if kind == "adamw":
        return optax.adamw(
            lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            mask=lambda params: jax.tree_util.tree_map(
                lambda p: p.ndim >= 2, params
            ),
        )
    txs = []
    if weight_decay:
        txs.append(optax.add_decayed_weights(weight_decay))
    txs.append(optax.sgd(lr, momentum=momentum))
    return optax.chain(*txs)


def init_state(
    model: Module,
    key: jax.Array,
    in_shape: Tuple[int, ...],
    optimizer: optax.GradientTransformation,
) -> ZooState:
    params, model_state, _ = model.init(key, in_shape)
    return ZooState(params, model_state, optimizer.init(params))


def init_fused_state(
    model: Module,
    key: jax.Array,
    in_shape: Tuple[int, ...],
    *,
    n_data: int,
    fused,
    bucket_bytes: int,
) -> Tuple[ZooState, int]:
    """(ZooState for the update-on-arrival step, bucket count).

    Momentum is allocated per collectives bucket in its SHARDED layout
    (see FusedOptState) — the bucket plan from the params tree is
    identical to the one the step derives from the gradient tree (same
    structure, shapes, dtypes), so shard lengths line up by construction.
    The loss scale starts at ``fused.loss_scale`` on the bf16 path and at
    1.0 for f32 (where scaling is the identity).
    """
    from parallel_cnn_tpu.parallel import collectives

    params, model_state, _ = model.init(key, in_shape)
    plan = collectives.plan_buckets(params, bucket_bytes, shards=n_data)
    buckets = collectives.flatten_buckets(params, plan)
    mom = [
        jnp.zeros((n_data, b.shape[0] // n_data), jnp.float32)
        for b in buckets
    ]
    scale0 = fused.loss_scale if fused.act_dtype == "bfloat16" else 1.0
    opt = FusedOptState(
        mom=mom,
        scale=jnp.float32(scale0),
        good_steps=jnp.int32(0),
        skipped=jnp.int32(0),
    )
    return ZooState(params, model_state, opt), len(buckets)


def make_train_step(
    model: Module,
    optimizer: optax.GradientTransformation,
    accum_steps: int = 1,
    mesh: Optional[Mesh] = None,
    augment: Optional[Callable] = None,
    model_axis: bool = False,
    comm=None,
    fused=None,
) -> Callable:
    """Build the jitted train step: (state, x, y) -> (state, loss), or
    (state, x, y, key) -> (state, loss) when `augment` is given.

    accum_steps > 1 splits the batch into microbatches scanned inside the
    step (one optimizer update per call — effective batch preserved, peak
    activation memory divided). With a mesh, x/y are constrained to the
    ``data`` axis and params to replicated — GSPMD handles the rest.
    `augment` is a traced (key, x) -> x transform (data/augment.py) that
    runs on-device inside the same jitted program, after the sharding
    constraint — so under a mesh each device augments only its own batch
    shard.

    model_axis=True additionally shards params, optimizer state, and BN
    running stats over the mesh's ``model`` axis by the filter/channel
    rule (parallel/zoo_sharding.py) — hybrid DP×model-parallel training
    on the 2-D mesh, the zoo-scale extension of the reference's per-kernel
    intra-op decomposition (MPI/layer.h:162-201). Requires ``mesh``.

    ``comm`` (a config.CommConfig) switches DP to the EXPLICIT collective
    path (_make_comm_step): the step becomes a shard_map over the data
    axis and the gradient reduce goes through parallel/collectives.py —
    monolithic psum, or bucketed ring reduce-scatter/all-gather with
    optional bf16 wire and microbatch comm/compute overlap. Requires
    ``mesh``; mutually exclusive with model_axis (the explicit path is
    data-axis only — GSPMD keeps owning the 2-D decomposition).

    ``fused`` (a config.FusedStepConfig) applies the round-7 fused-tail /
    bf16-activation loss refinements (_build_loss_fn). On the bf16 path a
    STATIC loss scale protects the half-precision backward: the loss is
    scaled before differentiation and grads/loss unscaled by the exact
    power-of-two reciprocal right after each microbatch backward — the
    accumulation and optax math run in the unscaled domain, numerically
    identical to unscaled f32 up to bf16 rounding. The DYNAMIC scaling
    policy (skip + rescale on overflow) needs the update-on-arrival step:
    ``fused.update=True`` is rejected here — build via
    ``make_fused_train_step`` (train() dispatches automatically).
    """
    if model_axis and mesh is None:
        raise ValueError("model_axis=True requires a mesh")
    if fused is not None and fused.update:
        raise ValueError(
            "fused.update (update-on-arrival) requires the explicit "
            "ring-collective step — use make_fused_train_step / "
            "train(..., fused=...), or pass fused with update=False"
        )
    if comm is not None:
        if mesh is None:
            raise ValueError("comm (explicit collectives) requires a mesh")
        if model_axis:
            raise ValueError(
                "comm is the explicit data-parallel collective path; "
                "model_axis sharding stays on the GSPMD path (comm=None)"
            )
        return _make_comm_step(model, optimizer, accum_steps, mesh,
                               augment, comm, fused)

    loss_fn = _build_loss_fn(model, fused)
    scale = (
        float(fused.loss_scale)
        if fused is not None and fused.act_dtype == "bfloat16"
        else 1.0
    )

    def grad_fn(params, model_state, bx, by):
        if scale == 1.0:
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, bx, by)
            return loss, new_state, grads

        def scaled(params, model_state, bx, by):
            loss, new_state = loss_fn(params, model_state, bx, by)
            return loss * scale, (loss, new_state)

        grads, (loss, new_state) = jax.grad(scaled, has_aux=True)(
            params, model_state, bx, by
        )
        # 1/scale is an exact power of two: unscaling is bit-lossless.
        grads = jax.tree_util.tree_map(lambda g: g * (1.0 / scale), grads)
        return loss, new_state, grads

    def microbatch_grads(params, model_state, x, y):
        if accum_steps == 1:
            return grad_fn(params, model_state, x, y)

        if x.shape[0] % accum_steps:
            raise ValueError(
                f"batch size {x.shape[0]} must be a multiple of "
                f"accum_steps {accum_steps} (no silent sample dropping)"
            )
        mb = x.shape[0] // accum_steps
        # UNROLLED microbatch loop, not lax.scan. accum_steps is a small
        # static int, and scan costs real performance here: under GSPMD
        # the scanned-loop program EXECUTES pathologically on XLA:CPU
        # (measured: 416 s/step vs 14.7 s unrolled for the 6-conv CIFAR
        # CNN at batch 512 on an 8-virtual-device mesh — same loss), and
        # on TPU a length-2..8 unroll lets the scheduler overlap microbatch
        # boundaries. The optimization_barrier between microbatches keeps
        # accumulation's reason to exist: without it XLA may hoist every
        # microbatch's forward ahead of the backwards, restoring
        # full-batch peak activation memory.
        gsum = None
        lsum = jnp.float32(0.0)
        for i in range(accum_steps):
            bx = x[i * mb : (i + 1) * mb]
            by = y[i * mb : (i + 1) * mb]
            if gsum is not None:
                bx, gsum, lsum, model_state = jax.lax.optimization_barrier(
                    (bx, gsum, lsum, model_state)
                )
            loss, model_state, grads = grad_fn(params, model_state, bx, by)
            gsum = (
                grads
                if gsum is None
                else jax.tree_util.tree_map(jnp.add, gsum, grads)
            )
            lsum = lsum + loss
        grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
        return lsum / accum_steps, model_state, grads

    # Layer state that a whole optimizer step settles, once its last
    # microbatch has run (nn/glm_moe.py: the experts' selection bias).
    finish_step = getattr(model, "finish_step", None)

    def step(state: ZooState, x, y, key=None):
        if augment is not None and key is None:
            raise ValueError(
                "this train step was built with `augment`; call it as "
                "step(state, x, y, key) with a fresh PRNG key per step"
            )
        if mesh is not None:
            data_sh = NamedSharding(mesh, P(DATA_AXIS))
            x = jax.lax.with_sharding_constraint(x, data_sh)
            y = jax.lax.with_sharding_constraint(y, data_sh)
            if model_axis:
                # Filter/channel sharding over the model axis for params,
                # optimizer state AND BN running stats; grads/updates
                # inherit the layout, XLA places the collectives.
                from parallel_cnn_tpu.parallel import zoo_sharding

                state = ZooState(
                    zoo_sharding.constrain(state.params, mesh),
                    zoo_sharding.constrain(state.model_state, mesh),
                    zoo_sharding.constrain(state.opt_state, mesh),
                )
            else:
                # Pin params replicated so the gradient all-reduce lands
                # over the data axis even under future multi-axis meshes.
                from parallel_cnn_tpu.parallel import zoo_sharding

                state = ZooState(
                    zoo_sharding.constrain_replicated(state.params, mesh),
                    state.model_state,
                    state.opt_state,
                )
        if augment is not None:
            x = augment(key, x)
        # `grad` / `optimizer` (and the layer scopes nn/ opens under
        # `grad`) are op_name metadata: obs/programs.py reads them back
        # from the compiled program to tell forward, backward and update.
        with jax.named_scope("grad"):
            loss, model_state, grads = microbatch_grads(
                state.params, state.model_state, x, y
            )
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        if finish_step is not None:
            model_state = finish_step(model_state)
        return ZooState(params, model_state, opt_state), loss

    return jax.jit(step, donate_argnums=(0,))


class RandomLayerUnsupported(ValueError):
    """A step factory that cannot carry a random layer's key was handed a
    model that has one (nn/layers.py:DropPath)."""


def refuse_random_layers(model_state, step: str) -> None:
    """The explicit shard_map steps run one body per shard and average the
    model state over the shards: every shard would draw the same
    per-sample masks from a layer's key, and a mean of key data means
    nothing. They refuse such a model by name; the GSPMD step
    (`make_train_step` without ``comm``) is one program over the global
    batch and needs neither."""
    if has_random_state(model_state):
        raise RandomLayerUnsupported(
            f"the {step} does not run a model with a layer that is random "
            "in training (DropPath): use the default GSPMD step "
            "(comm=None, no fused.update, no pipeline)"
        )


class StepStateUnsupported(ValueError):
    """A step factory that averages the model state over shards was handed
    a model whose state a whole step settles (`finish_step`)."""


def refuse_step_state(model: Module, step: str) -> None:
    """A model with a `finish_step` (nn/glm_moe.py:GlmMoe) keeps counts of
    the step's tokens in its state and moves its experts' selection bias
    by their sign once a step: the explicit shard_map steps `pmean` the
    state over shards, which makes fractions of the counts and a bias no
    shard computed. They refuse such a model by name, as they refuse a
    random layer; the GSPMD step calls `finish_step` itself."""
    if hasattr(model, "finish_step"):
        raise StepStateUnsupported(
            f"the {step} does not run {type(model).__name__}: its layers "
            "keep per-step counts and a selection bias in the model state, "
            "which only the default GSPMD step (comm=None, no "
            "fused.update, no pipeline) settles"
        )


def _make_comm_step(
    model: Module,
    optimizer: optax.GradientTransformation,
    accum_steps: int,
    mesh: Mesh,
    augment: Optional[Callable],
    comm,
    fused=None,
) -> Callable:
    """Explicit-collective DP train step (comm= on make_train_step).

    Where the default zoo path hands GSPMD one jitted program and lets
    XLA insert the gradient all-reduce, this path IS the shard_map: each
    device runs the microbatch loop on its batch shard and the gradient
    reduce is written out explicitly via parallel/collectives.py —
    psum (baseline) or bucketed ring reduce-scatter/all-gather, optional
    bf16-on-the-wire.

    Overlap schedule (comm.impl="ring", comm.overlap, accum_steps > 1):
    microbatch i's grad buckets are reduce-scattered the moment its
    backward finishes, and the running sum is kept SHARDED (1/n of the
    grad memory); one all-gather after the last microbatch rematerializes
    full grads for the optimizer. The inter-microbatch
    `optimization_barrier` deliberately EXCLUDES the shard accumulators —
    serializing them would chain every collective behind the next
    microbatch's input and un-overlap the schedule; the barrier keeps its
    activation-memory role through (bx, lsum, model_state) only.

    Semantics deltas vs the GSPMD path, both deliberate and documented
    (docs/collectives.md): BatchNorm batch statistics are computed per
    data shard (the classic large-scale DP recipe; GSPMD's are global),
    with the running stats pmean'd so checkpoints stay replicated; the
    epoch loss is likewise the pmean of shard losses. psum and ring run
    the SAME body, so an impl ablation isolates the collective algorithm.

    On a (host, device) mesh (mesh.make_hier_mesh) the batch shards over
    BOTH axes and impl="hierarchical" routes each bucket through the
    two-level ring (collectives.hier_*) — intra-host RS, inter-host shard
    exchange, intra-host AG; impl="psum" reduces over the axis pair (the
    parity baseline that shares the mesh decomposition, hence the same
    shard-local BN statistics). The flat impl="ring" is single-axis and
    is rejected on a hierarchical mesh.
    """
    refuse_step_state(model, "explicit-collective step (comm=...)")
    from parallel_cnn_tpu.parallel import collectives

    has_host = HOST_AXIS in mesh.axis_names
    if comm.impl == "hierarchical" and not has_host:
        raise ValueError(
            "comm.impl='hierarchical' needs a (host, device) mesh — build "
            "it with mesh.make_hier_mesh (comm.hosts / PCNN_COMM_HOSTS "
            "emulates the host axis inside one process)"
        )
    if comm.impl == "ring" and has_host:
        raise ValueError(
            "comm.impl='ring' is the flat single-axis ring; on a "
            "(host, device) mesh use impl='hierarchical' (or 'psum')"
        )
    n_data = mesh.shape[DATA_AXIS]
    n_host = mesh.shape[HOST_AXIS] if has_host else 1
    n_total = n_host * n_data
    raxes = (HOST_AXIS, DATA_AXIS) if has_host else DATA_AXIS
    host_kw = dict(host_axis=HOST_AXIS, host_size=n_host) if has_host else {}
    batch_spec = P((HOST_AXIS, DATA_AXIS)) if has_host else P(DATA_AXIS)
    wire = collectives.wire_dtype_arg(comm)
    use_ring = comm.impl in ("ring", "hierarchical")
    overlap = use_ring and comm.overlap and accum_steps > 1

    loss_fn = _build_loss_fn(model, fused)
    scale = (
        float(fused.loss_scale)
        if fused is not None and fused.act_dtype == "bfloat16"
        else 1.0
    )

    def grad_fn(params, model_state, bx, by):
        # Static loss scaling for the bf16 path — same discipline as
        # make_train_step's grad_fn (exact power-of-two unscale per
        # microbatch, accumulation in the unscaled domain).
        if scale == 1.0:
            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, bx, by)
            return loss, new_state, grads

        def scaled(params, model_state, bx, by):
            loss, new_state = loss_fn(params, model_state, bx, by)
            return loss * scale, (loss, new_state)

        grads, (loss, new_state) = jax.grad(scaled, has_aux=True)(
            params, model_state, bx, by
        )
        grads = jax.tree_util.tree_map(lambda g: g * (1.0 / scale), grads)
        return loss, new_state, grads

    def shard_body(state: ZooState, x, y, key_data=None):
        params, model_state = state.params, state.model_state
        refuse_random_layers(model_state, "explicit-collective step (comm=...)")
        if not use_ring:
            # With the replication checker on, params arrive typed
            # "unvarying" over the mesh and jax.grad of a shard-varying
            # loss w.r.t. them already returns the cross-shard SUM (the
            # transpose of the implicit broadcast is a psum). The explicit
            # collective below would then reduce a second time — an
            # N-times gradient. Mark the params device-varying so grads
            # stay LOCAL and the one written-out reduce is the only one:
            # psum and ring run the same body, as documented above. Only
            # the differentiated copy is cast; the optimizer below keeps
            # updating the replicated originals.
            grad_params = jax.lax.pcast(params, raxes, to="varying")
        else:
            grad_params = params
        if augment is not None:
            # Typed keys don't cross the shard_map boundary portably; the
            # raw key data does. Fold in the device index so each shard
            # draws its own augmentation stream (the GSPMD path gets the
            # same effect from batch-position-dependent crop draws).
            dev_idx = jax.lax.axis_index(DATA_AXIS)
            if has_host:
                dev_idx = jax.lax.axis_index(HOST_AXIS) * n_data + dev_idx
            key = jax.random.wrap_key_data(key_data)
            key = jax.random.fold_in(key, dev_idx)
            x = augment(key, x)
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"per-device batch {x.shape[0]} must be a multiple of "
                f"accum_steps {accum_steps} (no silent sample dropping)"
            )
        mb = x.shape[0] // accum_steps
        lsum = jnp.float32(0.0)
        gsum = None       # unreduced accumulator (non-overlap schedules)
        shard_acc = None  # reduce-scattered accumulator (overlap schedule)
        plan = None
        for i in range(accum_steps):
            bx = x[i * mb : (i + 1) * mb]
            by = y[i * mb : (i + 1) * mb]
            if i:
                # Same microbatch sequencing as microbatch_grads — but
                # shard_acc stays OUT of the barrier: the in-flight
                # reduce-scatters must remain schedulable alongside this
                # microbatch's compute (the whole point of overlap).
                if gsum is None:
                    bx, lsum, model_state = jax.lax.optimization_barrier(
                        (bx, lsum, model_state)
                    )
                else:
                    bx, gsum, lsum, model_state = jax.lax.optimization_barrier(
                        (bx, gsum, lsum, model_state)
                    )
            with jax.named_scope("grad"):
                loss, model_state, grads = grad_fn(
                    grad_params, model_state, bx, by
                )
            lsum = lsum + loss
            if overlap:
                if plan is None:
                    plan = collectives.plan_buckets(
                        grads, comm.bucket_bytes, shards=n_total
                    )
                shards = collectives.reduce_scatter_buckets(
                    collectives.flatten_buckets(grads, plan),
                    DATA_AXIS, n_data, wire, **host_kw,
                )
                shard_acc = (
                    shards
                    if shard_acc is None
                    else [a + b for a, b in zip(shard_acc, shards)]
                )
            else:
                gsum = (
                    grads
                    if gsum is None
                    else jax.tree_util.tree_map(jnp.add, gsum, grads)
                )
        if overlap:
            buckets = collectives.all_gather_buckets(
                shard_acc, DATA_AXIS, n_data, wire, **host_kw
            )
            grads = collectives.unflatten_buckets(buckets, plan)
        else:
            grads = collectives.tree_all_reduce(
                gsum, DATA_AXIS, n_data, comm, **host_kw
            )
        # Each microbatch loss/grad is a LOCAL-shard mean; the collective
        # summed over n_total devices, so the global mean divides by both.
        grads = jax.tree_util.tree_map(
            lambda g: g / (accum_steps * n_total), grads
        )
        loss = jax.lax.pmean(lsum / accum_steps, raxes)
        model_state = jax.lax.pmean(model_state, raxes)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, params
            )
            params = optax.apply_updates(params, updates)
        return ZooState(params, model_state, opt_state), loss

    specs = dict(
        mesh=mesh,
        out_specs=(P(), P()),
        # ppermute outputs are per-device values the replication checker
        # cannot prove replicated (they are — RS+AG leaves every device
        # with identical sums; tests/test_collectives.py pins it).
        check_vma=not use_ring,
    )
    if augment is not None:
        sharded = jax.shard_map(
            shard_body, in_specs=(P(), batch_spec, batch_spec, P()),
            **specs,
        )

        def step(state: ZooState, x, y, key=None):
            if key is None:
                raise ValueError(
                    "this train step was built with `augment`; call it as "
                    "step(state, x, y, key) with a fresh PRNG key per step"
                )
            return sharded(state, x, y, jax.random.key_data(key))

    else:
        sharded = jax.shard_map(
            shard_body, in_specs=(P(), batch_spec, batch_spec), **specs
        )

        def step(state: ZooState, x, y, key=None):
            return sharded(state, x, y)

    return jax.jit(step, donate_argnums=(0,))


def make_fused_train_step(
    model: Module,
    *,
    lr: float,
    momentum: float,
    accum_steps: int,
    mesh: Mesh,
    augment: Optional[Callable],
    comm,
    fused,
    n_buckets: int,
) -> Callable:
    """Update-on-arrival train step (round 7): the optimizer disappears
    into the collective schedule.

    Extends _make_comm_step's overlap path (ring RS per microbatch,
    sharded accumulator) past the gradient: when the LAST microbatch's
    reduce-scatter lands, each device holds the fully-summed gradient
    shard of every bucket — so instead of all-gathering gradients and
    running a tree-wide optax pass behind the barrier, bucket b's
    param+momentum shard update (ops.pallas_update.fused_sgd_momentum,
    ZeRO-2 style: each device owns 1/n of params' update work) launches
    the moment ITS sum is final, overlapped with the other buckets'
    in-flight collectives, and the final all-gather ships already-UPDATED
    parameter shards. Same wire volume as the gradient all-gather it
    replaces — but the parameter AG always rides f32, regardless of
    comm.wire_dtype: quantizing it would corrupt the f32 masters, while
    the gradient RS tolerates bf16 wire (f32 accumulation, documented
    error bound).

    Dynamic loss scaling (fused.act_dtype="bfloat16"): the loss is scaled
    by the TRACED scale riding in FusedOptState; after the last RS each
    device checks its gradient shards for non-finites and a pmin agrees
    globally. On overflow every shard update is dropped via jnp.where
    (params, momentum, and BN stats stay bit-identical — a skipped step,
    not a rollback) and the scale backs off by ``fused.backoff``
    (clamped ≥1); after ``fused.growth_interval`` clean steps it doubles.
    The unscale multiplier 1/(scale·accum·n_data) folds loss-scale,
    accumulation, and device count into the fused kernel's single scalar
    operand. The resilience sentinel reads the skip counter via
    Sentinel.check_scaled so a handled overflow reports healthy.

    Supports constant-LR SGD(+momentum) — lr/momentum are baked into the
    kernels as static scalars; train() rejects schedules/weight-decay on
    this path.
    """
    refuse_step_state(model, "update-on-arrival step (fused.update)")
    from parallel_cnn_tpu.ops import pallas_update
    from parallel_cnn_tpu.parallel import collectives

    if comm is None or comm.impl != "ring":
        raise ValueError(
            "update-on-arrival requires comm.impl='ring' (the bucketed "
            "reduce-scatter is what produces the per-device shards)"
        )
    n_data = mesh.shape[DATA_AXIS]
    wire = collectives.wire_dtype_arg(comm)
    loss_fn = _build_loss_fn(model, fused)
    dynamic = fused.act_dtype == "bfloat16"

    def shard_body(state: ZooState, x, y, key_data=None):
        refuse_random_layers(state.model_state, "update-on-arrival step (fused.update)")
        params, model_state = state.params, state.model_state
        opt = state.opt_state
        scale = opt.scale
        if augment is not None:
            key = jax.random.wrap_key_data(key_data)
            key = jax.random.fold_in(key, jax.lax.axis_index(DATA_AXIS))
            x = augment(key, x)
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"per-device batch {x.shape[0]} must be a multiple of "
                f"accum_steps {accum_steps} (no silent sample dropping)"
            )
        mb = x.shape[0] // accum_steps

        def scaled(params, model_state, bx, by):
            loss, new_state = loss_fn(params, model_state, bx, by)
            return loss * scale, (loss, new_state)

        lsum = jnp.float32(0.0)
        shard_acc = None
        plan = None
        for i in range(accum_steps):
            bx = x[i * mb : (i + 1) * mb]
            by = y[i * mb : (i + 1) * mb]
            if i:
                # shard_acc stays OUT of the barrier, exactly as in
                # _make_comm_step's overlap schedule: the in-flight
                # reduce-scatters must overlap this microbatch's compute.
                bx, lsum, model_state = jax.lax.optimization_barrier(
                    (bx, lsum, model_state)
                )
            grads, (loss, model_state) = jax.grad(scaled, has_aux=True)(
                params, model_state, bx, by
            )
            lsum = lsum + loss  # UNSCALED loss for reporting
            if plan is None:
                plan = collectives.plan_buckets(
                    grads, comm.bucket_bytes, shards=n_data
                )
            shards = collectives.reduce_scatter_buckets(
                collectives.flatten_buckets(grads, plan),
                DATA_AXIS, n_data, wire,
            )
            shard_acc = (
                shards
                if shard_acc is None
                else [a + b for a, b in zip(shard_acc, shards)]
            )
        # Overflow check on the SHARDS (1/n of the gradient bytes), with
        # one pmin to agree globally — every device must take the same
        # apply-vs-skip branch or params would diverge across the ring.
        finite = jnp.stack(
            [jnp.all(jnp.isfinite(s)) for s in shard_acc]
        ).all()
        ok = jax.lax.pmin(finite.astype(jnp.int32), DATA_AXIS) > 0
        gscale = 1.0 / (scale * (accum_steps * n_data))
        idx = jax.lax.axis_index(DATA_AXIS)
        pbuckets = collectives.flatten_buckets(params, plan)
        new_pb = []
        new_mom = []
        for b, gsh in enumerate(shard_acc):
            psh = jnp.take(
                pbuckets[b].reshape(n_data, -1), idx, axis=0
            )
            msh = opt.mom[b][0]  # sharded in: local (1, L) row
            p_new, m_new = pallas_update.fused_sgd_momentum(
                psh, msh, gsh, lr=lr, momentum=momentum, scale=gscale
            )
            p_new = jnp.where(ok, p_new, psh)
            m_new = jnp.where(ok, m_new, msh)
            new_mom.append(m_new[None, :])
            # Param all-gather: ALWAYS f32 wire (master precision).
            new_pb.append(
                collectives.ring_all_gather(p_new, DATA_AXIS, n_data, None)
            )
        params = collectives.unflatten_buckets(new_pb, plan)
        new_state = jax.lax.pmean(model_state, DATA_AXIS)
        model_state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old),
            new_state, state.model_state,
        )
        loss = jax.lax.pmean(lsum / accum_steps, DATA_AXIS)
        if dynamic:
            new_scale = jnp.where(
                ok, scale, jnp.maximum(scale * fused.backoff, 1.0)
            )
            good = jnp.where(ok, opt.good_steps + 1, 0)
            grow = good >= fused.growth_interval
            new_scale = jnp.where(grow, new_scale * 2.0, new_scale)
            good = jnp.where(grow, jnp.int32(0), good)
        else:
            new_scale, good = scale, opt.good_steps
        skipped = opt.skipped + (1 - ok.astype(jnp.int32))
        opt = FusedOptState(
            mom=new_mom, scale=new_scale, good_steps=good, skipped=skipped
        )
        return ZooState(params, model_state, opt), loss

    state_spec = ZooState(
        params=P(),
        model_state=P(),
        opt_state=FusedOptState(
            mom=[P(DATA_AXIS)] * n_buckets,
            scale=P(),
            good_steps=P(),
            skipped=P(),
        ),
    )
    specs = dict(
        mesh=mesh,
        out_specs=(state_spec, P()),
        check_vma=False,  # ppermute outputs, as in _make_comm_step
    )
    if augment is not None:
        sharded = jax.shard_map(
            shard_body,
            in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS), P()),
            **specs,
        )

        def step(state: ZooState, x, y, key=None):
            if key is None:
                raise ValueError(
                    "this train step was built with `augment`; call it as "
                    "step(state, x, y, key) with a fresh PRNG key per step"
                )
            return sharded(state, x, y, jax.random.key_data(key))

    else:
        sharded = jax.shard_map(
            shard_body,
            in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS)),
            **specs,
        )

        def step(state: ZooState, x, y, key=None):
            return sharded(state, x, y)

    return jax.jit(step, donate_argnums=(0,))


def init_zero3_state(
    model: Module,
    key: jax.Array,
    in_shape: Tuple[int, ...],
    *,
    n_data: int,
    fused,
    bucket_bytes: int,
    n_host: int = 1,
):
    """(ZooState for the ZeRO-3 step, BucketPlan).

    Unlike init_fused_state (ZeRO-2: replicated params, sharded momentum),
    BOTH params and momentum live permanently as 1/n bucket shards:
    ``ZooState.params`` is a list of per-bucket ``(n_host*n_data, L)``
    rows in shard_map's P((host, data)) row order
    (collectives.hier_shard_rows — with n_host=1 that's the plain flat
    layout), each device owning one row. The full param pytree exists only
    transiently inside the step, rebuilt by the just-in-time all-gathers;
    host-side consumers (eval, checkpointing) go through
    zero3_full_params / zero3_full_view.
    """
    from parallel_cnn_tpu.parallel import collectives

    params, model_state, _ = model.init(key, in_shape)
    n_shards = n_host * n_data
    plan = collectives.plan_buckets(params, bucket_bytes, shards=n_shards)
    pshards = [
        collectives.hier_shard_rows(b, n_host, n_data)
        for b in collectives.flatten_buckets(params, plan)
    ]
    mom = [jnp.zeros(p.shape, jnp.float32) for p in pshards]
    scale0 = fused.loss_scale if fused.act_dtype == "bfloat16" else 1.0
    opt = FusedOptState(
        mom=mom,
        scale=jnp.float32(scale0),
        good_steps=jnp.int32(0),
        skipped=jnp.int32(0),
    )
    return ZooState(pshards, model_state, opt), plan


def zero3_full_params(state: ZooState, plan, *, n_host: int = 1):
    """Rematerialize the full param pytree from ZeRO-3 resident shards —
    a pure reshuffle (no collectives), world-size independent and exact.
    Host-side companion of the step's just-in-time gathers, used by eval
    and checkpointing."""
    from parallel_cnn_tpu.parallel import collectives

    n_data = plan.shards // n_host
    buckets = [
        collectives.hier_unshard_rows(rows, n_host, n_data)
        for rows in state.params
    ]
    return collectives.unflatten_buckets(buckets, plan)


def zero3_full_view(state: ZooState, plan, *, n_host: int = 1):
    """The device-count-INDEPENDENT view of a ZeRO-3 training state:
    params and momentum as ordinary pytrees (momentum unflattened through
    the same plan, so its leaves mirror the param structure — exact for
    the all-f32 zoo models) plus the loss-scale scalars. This is what
    checkpoint.save_sharded persists; restoring on a different world size
    is just re-sharding this view (zero3_from_view) with a new plan —
    bit-exact, because shard↔full is reshape/transpose/slice only."""
    from parallel_cnn_tpu.parallel import collectives

    n_data = plan.shards // n_host
    mom_buckets = [
        collectives.hier_unshard_rows(rows, n_host, n_data)
        for rows in state.opt_state.mom
    ]
    return {
        "params": zero3_full_params(state, plan, n_host=n_host),
        "model_state": state.model_state,
        "mom": collectives.unflatten_buckets(mom_buckets, plan),
        "scale": state.opt_state.scale,
        "good_steps": state.opt_state.good_steps,
        "skipped": state.opt_state.skipped,
    }


def zero3_from_view(view, *, n_data: int, bucket_bytes: int,
                    n_host: int = 1):
    """Inverse of zero3_full_view for a (possibly different) world size:
    re-plan the buckets for n_host*n_data shards and lay params/momentum
    back out as resident rows. (ZooState, BucketPlan)."""
    from parallel_cnn_tpu.parallel import collectives

    params = view["params"]
    plan = collectives.plan_buckets(params, bucket_bytes,
                                    shards=n_host * n_data)
    pshards = [
        collectives.hier_shard_rows(b, n_host, n_data)
        for b in collectives.flatten_buckets(params, plan)
    ]
    mom = [
        collectives.hier_shard_rows(b, n_host, n_data).astype(jnp.float32)
        for b in collectives.flatten_buckets(view["mom"], plan)
    ]
    opt = FusedOptState(
        mom=mom,
        scale=jnp.asarray(view["scale"], jnp.float32),
        good_steps=jnp.asarray(view["good_steps"], jnp.int32),
        skipped=jnp.asarray(view["skipped"], jnp.int32),
    )
    return ZooState(pshards, view["model_state"], opt), plan


def make_zero3_train_step(
    model: Module,
    *,
    lr: float,
    momentum: float,
    accum_steps: int,
    mesh: Mesh,
    augment: Optional[Callable],
    comm,
    fused,
    plan,
) -> Callable:
    """ZeRO-3 train step: params never exist whole in persistent state.

    Extends make_fused_train_step (ZeRO-2 update-on-arrival) in both
    directions of the step:

    - HEAD — just-in-time parameter gathering. The resident state is the
      per-bucket shard rows of init_zero3_state; the step opens with one
      all-gather per bucket (ALWAYS f32 on the wire — these are the
      master weights; comm.wire_dtype compresses gradients only) and
      unflattens the transient full pytree the microbatch loop consumes.
      The per-bucket gathers are mutually independent and independent of
      every other bucket's unflatten/first-use, so XLA overlaps the
      gather of bucket k+1 with the consumption of bucket k — and, on
      the first microbatch, with the head of forward compute.
    - TAIL — update-on-arrival WITHOUT the trailing all-gather: bucket
      b's fused_sgd_momentum launches the moment its reduce-scattered
      gradient sum lands, updating the local param+momentum rows in
      place; the updated shards ARE the next step's resident state. The
      wire volume the ZeRO-2 step spends on its trailing param AG moves
      to this step's head gather — per-step total is unchanged, resident
      param memory drops to 1/n.

    Works over the flat ring (comm.impl="ring") or the two-level
    hierarchical ring (comm.impl="hierarchical" on a make_hier_mesh
    mesh); shard rows are laid out so each device's row is exactly the
    sub-chunk the configured ring delivers/collects (hier_shard_rows).
    Dynamic loss scaling follows make_fused_train_step: overflow skips
    the update via jnp.where agreement over all batch axes.
    """
    refuse_step_state(model, "ZeRO-3 step (fused.zero=3)")
    from parallel_cnn_tpu.ops import pallas_update
    from parallel_cnn_tpu.parallel import collectives

    if comm is None or comm.impl not in ("ring", "hierarchical"):
        raise ValueError(
            "ZeRO-3 requires the explicit bucketed collectives — "
            "comm.impl='ring' or 'hierarchical'"
        )
    has_host = HOST_AXIS in mesh.axis_names
    if comm.impl == "hierarchical" and not has_host:
        raise ValueError(
            "comm.impl='hierarchical' needs a (host, device) mesh — build "
            "it with mesh.make_hier_mesh"
        )
    if comm.impl == "ring" and has_host:
        raise ValueError(
            "comm.impl='ring' is the flat single-axis ring; on a "
            "(host, device) mesh use impl='hierarchical'"
        )
    n_data = mesh.shape[DATA_AXIS]
    n_host = mesh.shape[HOST_AXIS] if has_host else 1
    n_total = n_host * n_data
    raxes = (HOST_AXIS, DATA_AXIS) if has_host else DATA_AXIS
    host_kw = dict(host_axis=HOST_AXIS, host_size=n_host) if has_host else {}
    batch_spec = P((HOST_AXIS, DATA_AXIS)) if has_host else P(DATA_AXIS)
    row_spec = P((HOST_AXIS, DATA_AXIS)) if has_host else P(DATA_AXIS)
    if plan.shards != n_total:
        raise ValueError(
            f"bucket plan was laid out for {plan.shards} shards but the "
            f"mesh has {n_total} batch-parallel devices — rebuild with "
            "init_zero3_state/zero3_from_view for this mesh"
        )
    wire = collectives.wire_dtype_arg(comm)
    loss_fn = _build_loss_fn(model, fused)
    dynamic = fused.act_dtype == "bfloat16"

    def shard_body(state: ZooState, x, y, key_data=None):
        refuse_random_layers(state.model_state, "ZeRO-3 step (fused.zero=3)")
        opt = state.opt_state
        scale = opt.scale
        # Just-in-time parameter gathering: local shard rows -> transient
        # full pytree. f32 wire unconditionally (master weights).
        full_buckets = collectives.all_gather_buckets(
            [rows[0] for rows in state.params],
            DATA_AXIS, n_data, None, **host_kw,
        )
        params = collectives.unflatten_buckets(full_buckets, plan)
        model_state = state.model_state
        if augment is not None:
            dev_idx = jax.lax.axis_index(DATA_AXIS)
            if has_host:
                dev_idx = jax.lax.axis_index(HOST_AXIS) * n_data + dev_idx
            key = jax.random.wrap_key_data(key_data)
            key = jax.random.fold_in(key, dev_idx)
            x = augment(key, x)
        if x.shape[0] % accum_steps:
            raise ValueError(
                f"per-device batch {x.shape[0]} must be a multiple of "
                f"accum_steps {accum_steps} (no silent sample dropping)"
            )
        mb = x.shape[0] // accum_steps

        def scaled(params, model_state, bx, by):
            loss, new_state = loss_fn(params, model_state, bx, by)
            return loss * scale, (loss, new_state)

        lsum = jnp.float32(0.0)
        shard_acc = None
        for i in range(accum_steps):
            bx = x[i * mb : (i + 1) * mb]
            by = y[i * mb : (i + 1) * mb]
            if i:
                # shard_acc stays OUT of the barrier, exactly as in the
                # ZeRO-2 overlap schedule.
                bx, lsum, model_state = jax.lax.optimization_barrier(
                    (bx, lsum, model_state)
                )
            grads, (loss, model_state) = jax.grad(scaled, has_aux=True)(
                params, model_state, bx, by
            )
            lsum = lsum + loss  # UNSCALED loss for reporting
            shards = collectives.reduce_scatter_buckets(
                collectives.flatten_buckets(grads, plan),
                DATA_AXIS, n_data, wire, **host_kw,
            )
            shard_acc = (
                shards
                if shard_acc is None
                else [a + b for a, b in zip(shard_acc, shards)]
            )
        finite = jnp.stack(
            [jnp.all(jnp.isfinite(s)) for s in shard_acc]
        ).all()
        ok = jax.lax.pmin(finite.astype(jnp.int32), raxes) > 0
        gscale = 1.0 / (scale * (accum_steps * n_total))
        new_psh = []
        new_mom = []
        for b, gsh in enumerate(shard_acc):
            psh = state.params[b][0]  # sharded in: local (1, L) row
            msh = opt.mom[b][0]
            p_new, m_new = pallas_update.fused_sgd_momentum(
                psh, msh, gsh, lr=lr, momentum=momentum, scale=gscale
            )
            # No trailing all-gather: the updated shard rows ARE the
            # resident state the next step's head gather will collect.
            new_psh.append(jnp.where(ok, p_new, psh)[None, :])
            new_mom.append(jnp.where(ok, m_new, msh)[None, :])
        new_state = jax.lax.pmean(model_state, raxes)
        model_state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old),
            new_state, state.model_state,
        )
        loss = jax.lax.pmean(lsum / accum_steps, raxes)
        if dynamic:
            new_scale = jnp.where(
                ok, scale, jnp.maximum(scale * fused.backoff, 1.0)
            )
            good = jnp.where(ok, opt.good_steps + 1, 0)
            grow = good >= fused.growth_interval
            new_scale = jnp.where(grow, new_scale * 2.0, new_scale)
            good = jnp.where(grow, jnp.int32(0), good)
        else:
            new_scale, good = scale, opt.good_steps
        skipped = opt.skipped + (1 - ok.astype(jnp.int32))
        opt = FusedOptState(
            mom=new_mom, scale=new_scale, good_steps=good, skipped=skipped
        )
        return ZooState(new_psh, model_state, opt), loss

    state_spec = ZooState(
        params=[row_spec] * plan.n_buckets,
        model_state=P(),
        opt_state=FusedOptState(
            mom=[row_spec] * plan.n_buckets,
            scale=P(),
            good_steps=P(),
            skipped=P(),
        ),
    )
    specs = dict(
        mesh=mesh,
        out_specs=(state_spec, P()),
        check_vma=False,  # ppermute outputs, as in _make_comm_step
    )
    if augment is not None:
        sharded = jax.shard_map(
            shard_body,
            in_specs=(state_spec, batch_spec, batch_spec, P()),
            **specs,
        )

        def step(state: ZooState, x, y, key=None):
            if key is None:
                raise ValueError(
                    "this train step was built with `augment`; call it as "
                    "step(state, x, y, key) with a fresh PRNG key per step"
                )
            return sharded(state, x, y, jax.random.key_data(key))

    else:
        sharded = jax.shard_map(
            shard_body,
            in_specs=(state_spec, batch_spec, batch_spec),
            **specs,
        )

        def step(state: ZooState, x, y, key=None):
            return sharded(state, x, y)

    return jax.jit(step, donate_argnums=(0,))


def make_eval_step(model: Module) -> Callable:
    """(params, model_state, x, y) -> correct-prediction count.

    train=False is what routes conv_backend="pallas" ResNets through the
    FUSED conv epilogues (nn.layers.ConvBNAct → ops.pallas_conv
    .conv2d_fused): folded running-stats BN + shortcut add + ReLU run in
    each conv kernel's output block, one HBM round-trip per layer. The
    train step keeps the exact unfused composition — train-mode BN
    statistics are reductions over the conv output, so a one-pass
    fusion would change the batch-stat math (docs/kernel_authoring.md).
    """

    @jax.jit
    def eval_step(params, model_state, x, y):
        logits, _ = model.apply(params, model_state, x, train=False)
        return jnp.sum(jnp.argmax(logits, axis=-1) == y)

    return eval_step


def evaluate(
    model: Module,
    state: ZooState,
    images,
    labels,
    batch_size: int = 256,
    eval_step: Optional[Callable] = None,
) -> float:
    """Accuracy (%) over an in-memory eval split, in on-device batches.

    Pass a prebuilt ``eval_step`` when calling in a loop — each
    make_eval_step closure is its own jit cache key, so rebuilding per
    call would recompile the eval graph every epoch.
    """
    ev = eval_step if eval_step is not None else make_eval_step(model)
    n = images.shape[0]
    correct = 0
    for i in range(0, n, batch_size):
        x = jnp.asarray(images[i : i + batch_size])
        y = jnp.asarray(labels[i : i + batch_size])
        correct += int(ev(state.params, state.model_state, x, y))
    return correct / n * 100.0


def _device_ids(tree) -> str:
    """Comma-joined sorted ids of every device holding a shard of any
    array in tree."""
    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            ids.update(d.id for d in leaf.sharding.device_set)
    return ",".join(str(i) for i in sorted(ids))


def _catalog_step(step, state, bx, by, key, obs) -> None:
    """Record the step's compiled program in `obs.programs` (tracing on
    only): the device trace names ops by HLO instruction, and this is what
    maps an instruction to its layer scope and phase. Under the span
    `zoo.catalog`: tracing's own cost, which an untraced run does not pay.

    Called once the first epoch's steps are dispatched, with the state
    the last of them RETURNED: the same jitted function is lowered and
    compiled once more for exactly the arguments the loop's next call
    has — under a mesh the second program (state replicated), the one
    every step but the first runs. The loop has asked for that program
    itself by then (at its second step, as an untraced run does), so jit
    serves this request from memory; only an epoch of ONE step leaves the
    mesh's second program to be compiled or loaded here. Lowering takes
    the arrays themselves (nothing executes, nothing is donated):
    `ShapeDtypeStruct`s of the same shapes and shardings lower to another
    compile-cache key, and the request becomes a second full compile
    (39 s for ResNet-50 on the v5e, PERF.md PR 24)."""
    from parallel_cnn_tpu.obs import programs

    if hasattr(step, "lower"):  # else: no single jitted function to name
        with obs.span("zoo.catalog", cat="setup"):
            programs.record(
                f"jit_{getattr(step, '__name__', 'step')}",
                step.lower(state, bx, by, key).compile(),
            )


def _native_epoch_batches(np_images, np_labels, batch_size, steps, seed):
    """One epoch of host batches from the C++ prefetch ring, or from its
    bit-identical NumPy twin when the native toolchain is unavailable
    (tests/test_native.py pins the two equal batch-for-batch)."""
    try:
        from parallel_cnn_tpu.data import native as native_mod
    except ImportError:
        from parallel_cnn_tpu.data import pipeline

        ds = pipeline.Dataset(np_images, np_labels)
        yield from pipeline.native_semantics_batches(
            ds, batch_size, shuffle=True, seed=seed
        )
        return
    import itertools

    with native_mod.Batcher(
        np_images, np_labels, batch_size, seed=seed, shuffle=True
    ) as it:
        yield from itertools.islice(it, steps)


# A TPU tile is (8, 128). The device lays an NHWC image set out with the
# BATCH on the lanes (bf16[n,224,224,3] gets {0,2,3,1:T(8,128)(2,1)}), so
# gathering samples from it copies the whole set into a batch-major layout
# first, every step. A store of shape (n, shards, rows, 128) with rows a
# multiple of 8 keeps each sample as whole contiguous tiles, and the same
# gather reads only what it returns. (With rows not a multiple of 8 the
# device puts the samples on the sublanes instead: the same trouble one
# level down.)
_SUBLANES, _LANES = 8, 128


def _in_blocks(total, body, init):
    """``body(at, size, acc)`` over ``total`` samples, at most 128 at a
    time: a loop over the whole blocks at offsets ``k * 128`` (which XLA
    can see are tile-aligned: a block written at an offset it cannot see
    through takes 2.6 times as long on the chip), then a static last block
    that starts early enough to be whole and rewrites a few samples of
    the one before with the same values."""
    size = min(total, _LANES)
    acc = jax.lax.fori_loop(
        0, total // size, lambda k, acc: body(k * size, size, acc), init
    )
    return body(total - size, size, acc) if total % size else acc


@jax.jit
def _rows128(images):
    """(n, ...) -> (n, 1, rows, 128), each sample zero-padded to whole
    tiles, a block of samples at a time so that the re-layout's
    temporaries are one block's, not the data set's."""
    n = images.shape[0]
    rows = math.prod(images.shape[1:]) // _LANES
    pad = -rows % _SUBLANES

    def block(at, size, store):
        part = jax.lax.dynamic_slice_in_dim(images, at, size, 0)
        part = part.reshape(size, 1, rows, _LANES)
        # Padded here, and not by a narrower update of a zeroed store: XLA
        # then keeps the store row-major through the loop (else it builds
        # it batch-minor and re-lays all of it out once more at the end).
        part = jnp.pad(part, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return jax.lax.dynamic_update_slice_in_dim(store, part, at, 0)

    return _in_blocks(
        n, block, jnp.empty((n, 1, rows + pad, _LANES), images.dtype)
    )


def _batch_shards(mesh):
    """(how many ways `mesh_lib.batch_sharding` cuts a batch, over which
    mesh axes)."""
    axes = mesh_lib.batch_sharding(mesh).spec[0]
    names = axes if isinstance(axes, tuple) else (axes,)
    return math.prod(mesh.shape[a] for a in names), axes


def resident_store(images, mesh=None):
    """What the device loader keeps in HBM for `select_batch`: ``(store,
    layout, over)``. Samples of a whole number of 128-lane rows (ImageNet
    224x224x3, CIFAR 32x32x3) are stored row-major, ``(n, shards, rows,
    128)`` ("rows128"); any other sample size stays as it came
    ("indexed"). Under a ``mesh`` whose batch shards cut a sample's
    leading axis into slabs of whole rows, the store lies across it, each
    chip holding its slab of every sample (``over`` is that mesh): 1/shards
    of the set a chip, and `select_batch` then emits the batch already
    laid out as `mesh_lib.shard_batch` would. Otherwise ``shards`` is 1
    and ``over`` None. The rule is on the shapes alone."""
    feat = math.prod(images.shape[1:])
    if images.shape[0] == 0 or feat % _LANES:
        return jnp.asarray(images), "indexed", None
    shards, axes = _batch_shards(mesh) if mesh is not None else (1, None)
    if shards == 1 or images.shape[1] % shards or (feat // shards) % _LANES:
        return _rows128(jnp.asarray(images)), "rows128", None
    # Each chip is sent its slab of every sample and re-lays it out itself.
    slabs = jax.device_put(images, NamedSharding(mesh, P(None, axes)))
    return jax.jit(jax.shard_map(
        _rows128, mesh=mesh, in_specs=P(None, axes),
        out_specs=P(None, axes, None, None),
        check_vma=False,  # its loop starts from zeros no chip varies
    ))(slabs), "rows128", mesh


def _fetch(store, idx, in_shape):
    """Samples ``idx`` of a store (or of one chip's slabs of it), shaped
    ``(len(idx), *in_shape)``, fetched a block at a time — one lane tile
    of the batch-minor layout the step takes its images in: the re-layout
    of a block (77 MB for 256 images of 224x224x3 in bf16 still fits,
    154 MB does not) then stays in the chip's fast memory, which halves
    the time at batch 512 and 1,024."""
    rows = math.prod(in_shape) // _LANES  # without a rows128 store's padding

    def block(at, size, out):
        part = store[jax.lax.dynamic_slice_in_dim(idx, at, size)]
        if part.shape[1:] != in_shape:
            part = part[:, 0, :rows].reshape(size, *in_shape)
        return jax.lax.dynamic_update_slice_in_dim(out, part, at, 0)

    return _in_blocks(
        idx.shape[0], block,
        jnp.empty((idx.shape[0], *in_shape), store.dtype),
    )


@functools.partial(jax.jit, static_argnames=("batch", "in_shape", "over"))
def select_batch(store, labels, perm, i, *, batch, in_shape, over=None):
    """Batch ``i`` of an epoch shuffled by ``perm``: samples
    ``perm[i*batch:(i+1)*batch]`` of `resident_store`'s array, shaped
    ``(batch, *in_shape)``, and their labels. One program a step (``i`` is
    traced); its module name, ``jit_select_batch``, is what the
    benchmark's ``batch_select_ms`` and the ledger's breakdown read.

    With the store ``over`` a mesh every chip fetches its slab of the
    whole batch and one all-to-all trades slabs for samples, so the batch
    leaves in `mesh_lib.batch_sharding` and no chip ever holds all of it."""
    idx = jax.lax.dynamic_slice(perm, (i * batch,), (batch,))
    if over is None:
        return _fetch(store, idx, in_shape), labels[idx]
    shards, axes = _batch_shards(over)
    slab = (in_shape[0] // shards, *in_shape[1:])
    images = jax.shard_map(
        lambda rows, idx: jax.lax.all_to_all(
            _fetch(rows, idx, slab), axes, 0, 1, tiled=True
        ),
        mesh=over, in_specs=(P(None, axes, None, None), P()),
        out_specs=P(axes),
        check_vma=False,  # _fetch's loop starts from a buffer no chip varies
    )(store, idx)
    return images, jax.lax.with_sharding_constraint(
        labels[idx], mesh_lib.batch_sharding(over)
    )


def train(
    model: Module,
    images,
    labels,
    *,
    in_shape: Tuple[int, ...],
    epochs: int = 1,
    batch_size: int = 128,
    lr: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    kind: str = "sgd",
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    lr_schedule: str = "constant",
    warmup_steps: int = 0,
    augment: bool = False,
    augment_pad: int = 4,
    accum_steps: int = 1,
    mesh: Optional[Mesh] = None,
    model_axis: bool = False,
    comm=None,
    fused=None,
    seed: int = 0,
    verbose: bool = True,
    eval_data: Optional[Tuple[Any, Any]] = None,
    eval_batch_size: int = 256,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    metrics=None,
    loader: str = "device",
    profile_trace_dir: Optional[str] = None,
    resilience=None,
    chaos=None,
    obs: Optional["obs_lib.Obs"] = None,
    elastic=None,
    pipeline=None,
    plan=None,
    replan: bool = False,
):
    """Epoch driver for zoo models on an in-memory dataset.

    Production surface (fills the SURVEY.md §5 checkpoint gap at zoo
    scale — the reference's weights "live only in process memory"):

    - ``checkpoint_dir``: after every epoch, atomically persist the FULL
      ``ZooState`` (params + optimizer momentum + BatchNorm running stats)
      via train/checkpoint.py; ``resume=True`` restarts from the latest
      checkpoint and — because epoch shuffles derive from ``seed + epoch``
      — continues on the exact trajectory of an uninterrupted run
      (kill-and-resume tested in tests/test_zoo.py).
    - ``eval_data=(images, labels)``: in-loop accuracy after each epoch.
    - ``metrics``: a utils.metrics.MetricsLogger; per-epoch records.
    - ``kind``/``b1``/``b2``/``eps``: make_optimizer's optimizer choice
      ("sgd" | "adamw") and AdamW's constants. The update-on-arrival
      steps (``fused.update``: ZeRO-2/3, alone or under the pipeline)
      carry their own SGD-momentum kernels and refuse any other ``kind``.
    - ``lr_schedule``/``warmup_steps``: make_optimizer's schedule knobs;
      the cosine horizon is the full run (epochs × steps-per-epoch), and
      the schedule's step count rides in opt_state, so resume continues
      the decay where the killed run stopped.
    - ``augment=True``: CIFAR-recipe random crop (±``augment_pad``) +
      horizontal flip, traced into the train step (data/augment.py);
      per-step keys derive from ``seed`` and the global step index, so
      the augmentation stream is also resume-reproducible.
    - ``profile_trace_dir``: after training, capture a jax.profiler
      trace of 3 steady-state steps of THE SAME jitted step the run
      trained with (augmentation, schedule, accumulation, and mesh
      included — no separate reconstruction that could drift), compile
      excluded. Open in XProf/TensorBoard; this is the single-chip MFU
      attribution tool.
    - ``loader``: "device" (default) keeps the dataset in HBM and takes
      each shuffled batch from it with one jitted program a step
      (`select_batch`). What stays resident is `resident_store`'s array:
      samples of ``F`` elements with ``F % 128 == 0`` (ImageNet 224x224x3,
      CIFAR 32x32x3) are stored row-major as ``(n, shards, rows, 128)``,
      so selecting a batch reads a batch and not the data set (an NHWC
      set lies batch-minor on the device, and a gather from it
      re-lays-out all of it every step); any other ``F`` (MNIST's 784) is
      kept as it came. Under a ``mesh`` whose batch shards cut a
      sample's leading axis into slabs of whole 128-element rows
      (224 rows over 4 or 8 chips), each chip holds its slab of every
      sample, 1/shards of the set, and the batch is emitted in
      `mesh_lib.shard_batch`'s layout by one all-to-all; if not (or
      under ``elastic``, whose mesh can change) the store stays on one
      device and `shard_batch` lays each batch out as before. Batches
      are the same either way: rows ``perm[i*B:(i+1)*B]`` of the
      ``seed + epoch`` permutation. The loop holds the store only, so
      host arrays passed in are resident once; a caller who passes
      device arrays and keeps them holds the
      set twice. "native" feeds batches from the C++
      prefetch ring (data/native.py — a worker thread assembles the next
      shuffled batch while the device trains, now shape-generic beyond
      28×28). The ring is recreated per epoch with seed
      ``seed + epoch + 1`` (the +1 keeps epoch 0 off the Batcher's
      seed-0 "default seed" replacement path), so the shuffle stream is
      resume-reproducible like the device path
      (though the two paths draw from different PRNGs — both
      deterministic, trajectories differ). Falls back to the
      bit-identical NumPy twin (pipeline.native_semantics_batches) when
      the C++ toolchain is unavailable — same batches either way.

    - ``model_axis=True`` (requires ``mesh``): filter/channel sharding
      of params/optimizer/BN stats over the mesh's ``model`` axis
      (parallel/zoo_sharding.py) composed with DP — hybrid 2-D training.

    - ``comm`` (a config.CommConfig; requires ``mesh``, excludes
      ``model_axis``): route DP through the explicit collective path
      (parallel/collectives.py) — psum or bucketed ring RS/AG with
      optional bf16 wire and microbatch overlap; see _make_comm_step for
      the (documented) BatchNorm batch-stat semantics delta vs GSPMD.

    - ``fused`` (a config.FusedStepConfig): the round-7 fused training
      step. ``fused.tail`` routes a recognized model head through the
      fused pool→FC→softmax-CE kernel; ``act_dtype="bfloat16"`` runs
      bf16 activations over f32 masters with loss scaling; and
      ``fused.update`` dispatches to make_fused_train_step —
      update-on-arrival over the ring collectives (requires ``mesh`` +
      ``comm.impl="ring"``, constant-LR SGD(+momentum); degrades to
      update=False with a note when the comm prerequisites are absent).
      Under fused.update the sentinel treats an in-step loss-scale skip
      as handled (Sentinel.check_scaled), not as a divergence.

    - ``resilience`` (a config.ResilienceConfig): health-sentinel policy
      over the epoch loss and params — and, when ``check_every_steps``
      is set, every N optimizer steps (each check is a host sync; the
      default 0 keeps step dispatch fully asynchronous). "skip" discards
      a poisoned epoch; "rollback" restores the last-good ``ZooState``
      and retries the epoch (deterministic: shuffles derive from
      ``seed + epoch``), bounded by ``max_rollbacks``. LR backoff does
      not apply here — the zoo LR is baked into the jitted optimizer
      schedule, so rollback retries at the same LR. ``ring_size`` prunes
      the per-epoch checkpoints to the newest N. A preemption signal
      (resilience/preempt) stops the loop at the next epoch boundary
      after the checkpoint flush. ``chaos`` is the fault injector used
      by tests/test_resilience.py.

    - ``elastic`` (a config.ElasticConfig): in-flight re-mesh + ZeRO-3
      reshard (resilience/elastic.py). Requires the ZeRO-3 step
      (``fused.zero=3``) — its world-size-independent full view is what
      makes resharding possible. Before each optimizer step the loop
      polls the ElasticController (preempt resize channel → chaos
      ``resize@STEP:±K`` → planned schedule); on a trigger it quiesces,
      reshards state for the surviving topology, rebuilds the jitted
      step, and continues. Under ``scaling="global"`` (default) the
      global batch and LR are held fixed — the loss trajectory tracks a
      fixed-mesh run to reduction-order roundoff; ``"per-device"`` holds
      the per-device batch fixed and scales the LR linearly, applied at
      the next epoch boundary (the epoch's batch generator is fixed-size
      mid-epoch).

    - ``plan`` (a plan.ExecutionPlan): the resolved execution contract
      this run trains under. Its fingerprint is stamped into every
      checkpoint so resume refuses files written under a different
      contract (``replan=True`` — the CLI's ``--replan`` — waives the
      check; the elastic reshard path is exempt by construction). Under
      elastic training the plan also gates recompile-once: resizes
      derive a new plan via ``plan.derive_resized``, and plan-equality
      keys a jitted-step cache, so resizing back to a previously seen
      topology reuses the compiled step instead of re-jitting
      (journaled as ``plan_step_cache`` hit/miss).

    - ``pipeline`` (a config.PipelineConfig; requires a
      mesh.make_pipeline_mesh (stage, data) mesh): 1F1B microbatch
      pipelining (train/pipeline_schedule.py) — model layers partition
      over the stage axis by the cost-model splitter, activations and
      cotangents move through full-ring stage ppermutes, gradients
      still reduce over the data axis with the explicit collectives.
      ``accum_steps`` is the microbatch count M. Composes with the
      ZeRO-2 fused tail (``fused.update``, zero=2); excludes
      model_axis, augment, elastic/ZeRO-3, and the fused bf16 loss
      tail (bf16 stage compute is ``pipeline.act_dtype`` instead). A
      chaos ``slow-stage@STEP:MS`` spec stalls the trainer once at the
      step-STEP dispatch boundary (journaled ``chaos_slow_stage``) —
      the 1F1B schedule is a synchronous tick rendezvous, so one slow
      stage stretches the whole pipeline's step.

    Returns (ZooState, list of per-epoch mean losses).
    """
    if loader not in ("device", "native"):
        raise ValueError(f"unknown loader {loader!r}")
    # Host-side observability (obs/): spans wrap batch fetch, step
    # dispatch, and the per-epoch readback; journal events mark epoch
    # outcomes, sentinel verdicts, and the comm bucket plan. The default
    # NOOP bundle makes all of it free.
    obs = obs if obs is not None else obs_lib.NOOP
    # The resolved ExecutionPlan (plan/) travels under a distinct name:
    # `z3_plan` below is the ZeRO-3 *bucket* plan, a different object.
    exec_plan = plan
    _plan_fp = exec_plan.fingerprint() if exec_plan is not None else None
    steps = images.shape[0] // batch_size
    if steps == 0:
        raise ValueError(
            f"dataset of {images.shape[0]} samples yields zero batches "
            f"of {batch_size}"
        )
    if fused is not None and fused.update:
        if (mesh is None or comm is None
                or comm.impl not in ("ring", "hierarchical")):
            if verbose:
                print(
                    "fused-step: update-on-arrival needs mesh + "
                    "comm.impl='ring'/'hierarchical'; falling back to "
                    "fused tail only"
                )
            # zero=3 requires update=True (config invariant) — the
            # fallback drops both together.
            fused = dataclasses.replace(fused, update=False, zero=2)
        elif comm.impl == "hierarchical" and fused.zero != 3:
            raise ValueError(
                "ZeRO-2 update-on-arrival rides the flat ring; on a "
                "hierarchical mesh use fused.zero=3 (whose resident "
                "shards follow the two-level ring), or comm.impl='ring' "
                "on a flat mesh"
            )
        elif model_axis:
            raise ValueError(
                "fused.update is the explicit data-parallel path; "
                "model_axis stays on GSPMD (set update=False)"
            )
        elif (lr_schedule != "constant" or warmup_steps or weight_decay
              or kind != "sgd"):
            raise ValueError(
                "fused.update supports constant-LR SGD(+momentum) only — "
                f"lr schedules/warmup/weight decay/kind={kind!r} need the "
                "optax path (set update=False)"
            )
    use_fused_update = fused is not None and fused.update
    use_zero3 = use_fused_update and fused.zero == 3
    if pipeline is not None:
        if mesh is None or STAGE_AXIS not in mesh.axis_names:
            raise ValueError(
                "pipeline training requires a (stage, data) mesh — "
                "build it with mesh.make_pipeline_mesh(pipeline.stages)"
            )
        if model_axis:
            raise ValueError(
                "pipeline partitions layers over the stage axis; "
                "model_axis filter sharding stays on the GSPMD path "
                "(drop one of the two)"
            )
        if augment:
            raise ValueError(
                "pipeline training does not thread augmentation keys "
                "through the 1F1B schedule yet — drop --augment"
            )
        if use_zero3:
            raise ValueError(
                "pipeline composes with ZeRO-2 only: ZeRO-3's just-in-"
                "time head gathers contradict per-stage param residency "
                "(docs/pipeline.md)"
            )
        if fused is not None and not use_fused_update:
            # The fused bf16/tail refinements ride _build_loss_fn, which
            # the per-stage schedule replaces; bf16 stage compute is
            # pipeline.act_dtype instead.
            fused = None
    if elastic is not None and elastic.enabled and not use_zero3:
        raise ValueError(
            "elastic training requires the ZeRO-3 step (fused.zero=3 "
            "with mesh + ring/hierarchical comm) — its world-size-"
            "independent full view is what makes in-flight resharding "
            "possible; enable it or drop --elastic"
        )
    # Set-up as spans (cat "setup": zoo.init, zoo.build_step, zoo.restore,
    # zoo.store, and zoo.catalog in _catalog_step) and as counts: what the
    # process's compile log (obs/compiles.py) has seen when this call
    # starts is what the first epoch record's `compiles` is counted from.
    t_call_us = time.perf_counter_ns() / 1e3
    compiles_seen = obs_lib.compiles.requests()
    z3_plan = None
    z3_host = 1
    with obs.span("zoo.init", cat="setup"):
        if use_zero3:
            if HOST_AXIS in mesh.axis_names:
                z3_host = mesh.shape[HOST_AXIS]
            state, z3_plan = init_zero3_state(
                model, jax.random.key(seed), in_shape,
                n_data=mesh.shape[DATA_AXIS], fused=fused,
                bucket_bytes=comm.bucket_bytes, n_host=z3_host,
            )
        elif use_fused_update:
            state, n_buckets = init_fused_state(
                model, jax.random.key(seed), in_shape,
                n_data=mesh.shape[DATA_AXIS], fused=fused,
                bucket_bytes=comm.bucket_bytes,
            )
        else:
            optimizer = make_optimizer(
                lr, momentum, weight_decay,
                schedule=lr_schedule, warmup_steps=warmup_steps,
                total_steps=steps * epochs if lr_schedule == "cosine" else None,
                kind=kind, b1=b1, b2=b2, eps=eps,
            )
            state = init_state(
                model, jax.random.key(seed), in_shape, optimizer)
    if obs.enabled:
        obs.event(
            "zoo_optimizer",
            # `optimizer`, not `kind`: that is the journal's own word for
            # the event's name.
            optimizer="sgd" if use_fused_update else kind,
            params=sum(p.size for p in jax.tree_util.tree_leaves(state.params)),
            state_bytes=sum(
                a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(state.opt_state)
            ),
        )
        if hasattr(model, "describe"):
            held_on = next(iter(
                jax.tree_util.tree_leaves(state.params)[0].devices()))
            # (a model may name the event itself: nn/ouro.py, `zoo_loop`)
            obs.event(
                getattr(model, "setup_event", "zoo_moe"), **model.describe(
                    batch_size * math.prod(in_shape), in_shape[-1],
                    held_on.platform))
    aug_fn = None
    if augment:
        from parallel_cnn_tpu.data import augment as aug_lib

        def aug_fn(key, x):
            return aug_lib.random_crop_flip(key, x, pad=augment_pad)

    with obs.span("zoo.build_step", cat="setup"):
        if pipeline is not None:
            from parallel_cnn_tpu.train.pipeline_schedule import (
                make_pipeline_step,
            )

            step = make_pipeline_step(
                model,
                None if use_fused_update else optimizer,
                accum_steps=accum_steps, mesh=mesh, pipeline=pipeline,
                in_shape=in_shape, comm=comm,
                fused=fused if use_fused_update else None,
                lr=lr, momentum=momentum,
            )
        elif use_zero3:
            step = make_zero3_train_step(
                model, lr=lr, momentum=momentum, accum_steps=accum_steps,
                mesh=mesh, augment=aug_fn, comm=comm, fused=fused,
                plan=z3_plan,
            )
        elif use_fused_update:
            step = make_fused_train_step(
                model, lr=lr, momentum=momentum, accum_steps=accum_steps,
                mesh=mesh, augment=aug_fn, comm=comm, fused=fused,
                n_buckets=n_buckets,
            )
        else:
            step = make_train_step(
                model, optimizer, accum_steps, mesh, aug_fn,
                model_axis=model_axis, comm=comm, fused=fused,
            )
        ev_step = make_eval_step(model) if eval_data is not None else None

    if (obs.enabled and comm is not None
            and comm.impl in ("ring", "hierarchical")):
        # Journal the bucket schedule once, host-side, from the same
        # planner the jitted step uses — per-bucket *arrival* happens
        # inside the compiled program where the host cannot observe it,
        # so the plan (count, sizes, dtypes) is the honest signal.
        from parallel_cnn_tpu.parallel import collectives

        n_shards = mesh.shape[DATA_AXIS]
        if HOST_AXIS in mesh.axis_names:
            n_shards *= mesh.shape[HOST_AXIS]
        _plan = collectives.plan_buckets(
            state.params, comm.bucket_bytes, shards=n_shards
        )
        obs.event(
            "comm_plan", impl=comm.impl, n_buckets=_plan.n_buckets,
            bucket_bytes=comm.bucket_bytes, shards=n_shards,
        )
        for _bi, (_sz, _dt) in enumerate(
            zip(_plan.bucket_sizes, _plan.bucket_dtypes)
        ):
            obs.event("comm_bucket", bucket=_bi, elements=_sz, dtype=_dt)

    from parallel_cnn_tpu.resilience import preempt
    from parallel_cnn_tpu.resilience.rollback import (
        CheckpointRing,
        RollbackController,
        tree_copy,
    )
    from parallel_cnn_tpu.resilience.sentinel import DivergenceError, Sentinel

    res = resilience
    sentinel = Sentinel() if res is not None and res.policy != "off" else None
    _skip_seen = (
        int(state.opt_state.skipped)
        if isinstance(state.opt_state, FusedOptState)
        else 0
    )

    def health_check(loss_val, st):
        # Under the fused dynamic-loss-scale step, an overflow the step
        # already absorbed (skip counter advanced, masters finite) is
        # healthy — route through check_scaled instead of check.
        nonlocal _skip_seen
        if isinstance(st.opt_state, FusedOptState):
            sk = int(st.opt_state.skipped)
            if obs.enabled and sk != _skip_seen:
                obs.event(
                    "loss_scale", skipped=sk,
                    scale=float(st.opt_state.scale),
                )
            v = sentinel.check_scaled(
                loss=loss_val, params=st.params,
                skipped_before=_skip_seen, skipped_now=sk,
                scale=float(st.opt_state.scale),
            )
            _skip_seen = sk
            if v.healthy and v.reason and verbose:
                print(f"sentinel: {v.reason}")
            return v
        return sentinel.check(loss=loss_val, params=st.params)

    controller = None
    if sentinel is not None and res.policy == "rollback":
        controller = RollbackController(max_rollbacks=res.max_rollbacks)
    ring = None
    if checkpoint_dir:
        saver = None
        if use_zero3:
            from parallel_cnn_tpu.train import checkpoint

            def saver(path, st, tstate):
                # Ring files carry the world-size-independent full view,
                # marked sharded so resume re-shards for the new mesh and
                # plain restore/load_params refuse with the typed error.
                # Reads z3_plan/z3_host from the enclosing scope at CALL
                # time: after an elastic resize rebinds them, ring files
                # carry the post-resize world (plan.shards == world).
                checkpoint.save_sharded(
                    path, zero3_full_view(st, z3_plan, n_host=z3_host),
                    tstate, world_size=z3_plan.shards,
                    bucket_bytes=comm.bucket_bytes,
                    plan_fingerprint=_plan_fp,
                )
        elif _plan_fp:
            from parallel_cnn_tpu.train import checkpoint

            def saver(path, st, tstate):
                # Stamp the plan fingerprint so restore refuses files
                # written under a different execution contract.
                checkpoint.save(
                    path, st, tstate, plan_fingerprint=_plan_fp
                )

        ring = CheckpointRing(
            checkpoint_dir, keep=res.ring_size if res is not None else 0,
            saver=saver,
        )

    start_epoch = 0
    losses: list = []
    accs: list = []
    if checkpoint_dir and resume:
        from parallel_cnn_tpu.train import checkpoint

        path = checkpoint.latest(checkpoint_dir)
        if path:
            with obs.span("zoo.restore", cat="setup"):
                if use_zero3:
                    # Sharded resume: restore the world-size-independent
                    # view and re-shard it for THIS run's mesh (reshard-
                    # on-restore — the writing run's world size is
                    # irrelevant).
                    template = zero3_full_view(
                        state, z3_plan, n_host=z3_host)
                    # The elastic reshard path recomputes sharding from
                    # the world-size-independent view anyway — exempt from
                    # the plan-fingerprint gate (ring files written after
                    # a resize carry the derived plan's fingerprint).
                    view, tstate, _ = checkpoint.restore_sharded(
                        path, template, plan_fingerprint=_plan_fp,
                        replan=replan or (
                            elastic is not None and elastic.enabled),
                    )
                    state, z3_plan = zero3_from_view(
                        view, n_data=mesh.shape[DATA_AXIS],
                        bucket_bytes=comm.bucket_bytes, n_host=z3_host,
                    )
                else:
                    # `state` is the restore template: full-state
                    # structure (params + opt_state + BN stats) validated
                    # leaf-for-leaf.
                    state, tstate = checkpoint.restore(
                        path, state, plan_fingerprint=_plan_fp,
                        replan=replan,
                    )
            start_epoch = tstate.epoch
            losses = list(tstate.epoch_errors)
            accs = list(tstate.extra.get("epoch_accs", []))
            if verbose:
                print(f"resumed from {path} (epoch {start_epoch})")

    elastic_ctl = None
    if elastic is not None and elastic.enabled:
        from parallel_cnn_tpu.resilience.elastic import ElasticController

        # Built AFTER ring creation and resume so the controller gets the
        # ring for its snapshot fallback and a template from the state
        # that will actually train (the view structure is world-size
        # independent, so it never goes stale across resizes).
        elastic_ctl = ElasticController(
            elastic, world=z3_plan.shards, n_hosts=z3_host,
            chaos=chaos, ring=ring, obs=obs, exec_plan=exec_plan,
        )
        elastic_ctl.register_template(
            zero3_full_view(state, z3_plan, n_host=z3_host)
        )
    # Recompile-once across elastic resizes: jitted steps keyed by the
    # (hashable) derived ExecutionPlan + LR. Primed with the initial
    # topology's derived plan so resizing BACK to the starting world is
    # a cache hit — derive_resized is deterministic, so equal topology
    # ⟹ equal plan ⟹ same jitted step.
    _step_cache: dict = {}
    if elastic_ctl is not None and exec_plan is not None:
        from parallel_cnn_tpu import plan as plan_lib

        _step_cache[
            (plan_lib.derive_resized(
                exec_plan, z3_plan.shards, n_hosts=z3_host), lr)
        ] = step

    n, row_shape = images.shape[0], tuple(images.shape[1:])
    if loader == "native":
        import numpy as _np

        with obs.span("zoo.store", cat="setup"):
            np_images = _np.ascontiguousarray(images, dtype=_np.float32)
            np_labels = _np.ascontiguousarray(labels, dtype=_np.int32)
    else:
        with obs.span("zoo.store", cat="setup"):
            # Across the mesh unless it can change under the loop
            # (elastic): the store has to outlive any one mesh.
            store, layout, over = resident_store(
                images, mesh if elastic_ctl is None else None
            )
            # What every chip reads whole is put on every chip once, not
            # moved there by each call of select_batch.
            everywhere = jnp.asarray if over is None else functools.partial(
                jax.device_put, device=mesh_lib.replicated(over))
            labels = everywhere(jnp.asarray(labels))
        if obs.enabled:
            obs.event(
                "zoo_loader", layout=layout, rows=n,
                row_bytes=math.prod(row_shape) * store.dtype.itemsize,
                shards=1 if over is None else _batch_shards(over)[0],
            )
        # The store is the loop's only copy: a caller who passed host
        # arrays holds the set once, one who passed device arrays twice.
        images = None
    aug_base = jax.random.key(seed ^ 0x5EED)
    if sentinel is not None:
        last_good = tree_copy(state)
        if controller is not None:
            controller.commit(state)
    epoch = start_epoch
    # Monotone optimizer-step id across epochs (and rollback retries) —
    # what elastic triggers (resize@STEP, schedule STEP:WORLD) reference.
    opt_steps = start_epoch * steps
    _chaos_logged = False
    _cataloged = False
    while epoch < epochs:
        t0 = time.perf_counter()
        # Per-epoch batch geometry: fixed at (batch_size, steps) unless
        # the elastic "per-device" policy rescales the global batch with
        # the world — applied at epoch boundaries only (the epoch's batch
        # generator is fixed-size mid-epoch).
        if elastic_ctl is not None:
            ebatch = min(elastic_ctl.global_batch_for(batch_size), n)
            esteps = max(n // ebatch, 1)
        else:
            ebatch, esteps = batch_size, steps
        # Device-side loss accumulation: one host readback per epoch, so
        # step dispatch stays asynchronous (same discipline as
        # trainer.learn's single per-epoch float()). The opt-in per-step
        # sentinel cadence (res.check_every_steps) trades that asynchrony
        # for early divergence detection.
        epoch_loss = jnp.float32(0.0)
        if loader == "native":
            batches = _native_epoch_batches(
                np_images, np_labels, ebatch, esteps, seed + epoch + 1
            )
        else:
            perm = everywhere(
                jax.random.permutation(jax.random.key(seed + epoch), n))
            batches = (
                select_batch(store, labels, perm, i, batch=ebatch,
                             in_shape=row_shape, over=over)
                for i in range(esteps)
            )
        diverged = None
        batch_iter = enumerate(batches)
        while True:
            with obs.span("zoo.data", cat="data", step=opt_steps,
                          epoch=epoch + 1):
                item = next(batch_iter, None)
            if item is None:
                break
            i, (bx, by) = item
            if elastic_ctl is not None:
                target = elastic_ctl.pending(opt_steps)
                if target is not None:
                    # Microbatch-boundary resize: reshard state for the
                    # new topology and rebuild the jitted step (jit has
                    # no baked-in in_shardings, so host batches and the
                    # fresh state reshard onto the new mesh on entry).
                    state, z3_plan, mesh, comm = elastic_ctl.resize(
                        opt_steps, target, state=state, plan=z3_plan,
                        comm=comm,
                    )
                    z3_host = elastic_ctl.n_hosts
                    # Plan-equality gates recompile-once: the resized
                    # topology maps to a derived ExecutionPlan, and an
                    # equal plan (same world/hosts/comm) at the same LR
                    # reuses the step jitted the first time we were
                    # here instead of re-tracing.
                    _ckey = None
                    if exec_plan is not None:
                        from parallel_cnn_tpu import plan as plan_lib

                        _ckey = (
                            plan_lib.derive_resized(
                                exec_plan, z3_plan.shards,
                                n_hosts=z3_host,
                            ),
                            elastic_ctl.lr_for(lr),
                        )
                        if obs.enabled:
                            obs.event(
                                "plan_step_cache",
                                hit=_ckey in _step_cache,
                                plan=_ckey[0].fingerprint(),
                                world=z3_plan.shards,
                            )
                    if _ckey is not None and _ckey in _step_cache:
                        step = _step_cache[_ckey]
                    else:
                        step = make_zero3_train_step(
                            model, lr=elastic_ctl.lr_for(lr),
                            momentum=momentum, accum_steps=accum_steps,
                            mesh=mesh, augment=aug_fn, comm=comm,
                            fused=fused, plan=z3_plan,
                        )
                        if _ckey is not None:
                            _step_cache[_ckey] = step
                    # Re-home the epoch accumulator: it is committed to
                    # the pre-resize devices, and mixing meshes in one
                    # add is an error. One host sync, inside the quiesce
                    # the resize already paid for.
                    epoch_loss = jnp.float32(float(epoch_loss))
            key = (
                jax.random.fold_in(
                    aug_base,
                    opt_steps if elastic_ctl is not None
                    else epoch * steps + i,
                )
                if aug_fn is not None
                else None
            )
            if chaos is not None and pipeline is not None:
                _stall = chaos.slow_stage_at(opt_steps)
                if _stall is not None:
                    time.sleep(_stall / 1000.0)
                    if obs.enabled:
                        obs.event(
                            "chaos_slow_stage", step=opt_steps, ms=_stall
                        )
            if mesh is not None:
                # Lay the batch out over the mesh's batch axes BEFORE the
                # step (the same placement trainer.learn uses): each
                # device receives its shard only, instead of the whole
                # batch landing on device 0 and being re-sliced inside
                # the program.
                with obs.span("zoo.shard", cat="data", step=opt_steps,
                              epoch=epoch + 1):
                    bx, by = mesh_lib.shard_batch(mesh, (bx, by))
            else:
                bx, by = jnp.asarray(bx), jnp.asarray(by)
            with obs.span("zoo.dispatch", cat="step", step=opt_steps,
                          epoch=epoch + 1):
                state, loss = step(state, bx, by, key)
            opt_steps += 1
            if chaos is not None:
                state, loss = chaos.after_step(state, loss)
                if obs.enabled and chaos.nan_fired and not _chaos_logged:
                    _chaos_logged = True
                    obs.event(
                        "chaos", injected="nan", step=i, epoch=epoch + 1
                    )
            epoch_loss = epoch_loss + loss
            if (
                sentinel is not None
                and res.check_every_steps
                and (i + 1) % res.check_every_steps == 0
            ):
                step_loss = float(loss)
                if obs.enabled:
                    # The sentinel cadence already paid the host sync, so
                    # journaling the step loss here is free of extra
                    # readbacks.
                    obs.event(
                        "step_loss", epoch=epoch + 1, step=i,
                        loss=step_loss,
                    )
                verdict = health_check(step_loss, state)
                if not verdict.healthy:
                    diverged = f"step {i} of epoch {epoch + 1}: " + (
                        verdict.reason
                    )
                    break
        if obs.enabled and not _cataloged and opt_steps > start_epoch * steps:
            # Once, when the first epoch's steps are all dispatched (while
            # the device still runs them): by now the loop has asked for
            # every program of its step itself, as an untraced run does.
            _cataloged = True
            _catalog_step(step, state, bx, by, key, obs)
        with obs.span("zoo.readback", cat="step", epoch=epoch + 1):
            mean_loss = float(epoch_loss) / max(esteps, 1)
        if diverged is None and sentinel is not None:
            verdict = health_check(mean_loss, state)
            if not verdict.healthy:
                diverged = f"epoch {epoch + 1}: {verdict.reason}"
        if diverged is not None:
            if obs.enabled:
                obs.event(
                    "verdict", healthy=False, epoch=epoch + 1,
                    reason=diverged, policy=res.policy,
                )
            if res.policy == "raise":
                raise DivergenceError(diverged)
            if res.policy == "skip":
                if verbose:
                    print(f"sentinel: {diverged} — epoch discarded")
                state = tree_copy(last_good)
                epoch += 1
                continue
            # rollback: restore the last-good ZooState and retry the same
            # epoch (same seed → same shuffle/augment stream), bounded.
            state, _ = controller.rollback(like=state, reason=diverged)
            if obs.enabled:
                obs.event(
                    "rollback", epoch=epoch + 1,
                    rollbacks=controller.rollbacks,
                )
            if verbose:
                print(
                    f"sentinel: {diverged} — rolled back "
                    f"({controller.rollbacks}/{controller.max_rollbacks})"
                )
            continue
        if sentinel is not None:
            last_good = tree_copy(state)
            if controller is not None:
                controller.commit(state)
        losses.append(mean_loss)
        seconds = time.perf_counter() - t0
        if obs.enabled:
            if t_call_us is not None:
                # Once, after the first epoch's readback: what set-up was.
                # The process's compile totals so far, and this call's
                # set-up spans by name (`init_s`, `build_step_s`, ...).
                obs.event(
                    "zoo_setup", **obs_lib.compiles.summary(),
                    **{
                        ev["name"].removeprefix("zoo.") + "_s":
                            ev["dur"] / 1e6
                        for ev in obs.tracer.events()
                        if ev.get("cat") == "setup"
                        and ev["ts"] >= t_call_us
                    },
                )
                t_call_us = None
            obs.event(
                "epoch", epoch=epoch + 1, loss=mean_loss, seconds=seconds
            )
        if eval_data is not None:
            est = state
            if use_zero3:
                # Eval consumes the full param pytree; rematerialize it
                # from the resident shards (pure reshuffle, no comm).
                est = ZooState(
                    zero3_full_params(state, z3_plan, n_host=z3_host),
                    state.model_state, None,
                )
            accs.append(
                evaluate(model, est, *eval_data,
                         batch_size=eval_batch_size, eval_step=ev_step)
            )
        if metrics is not None:
            rec = dict(event="zoo_epoch", epoch=epoch + 1,
                       loss=losses[-1], seconds=seconds)
            if eval_data is not None:
                rec["accuracy"] = accs[-1]
            # Where the work actually lives (host-side sharding metadata,
            # no sync): the platform, and the ids of the devices holding
            # the state and the last batch ("0,1,2,3" — records are flat).
            rec["platform"] = jax.devices()[0].platform
            rec["state_devices"] = _device_ids(state)
            rec["batch_devices"] = _device_ids((bx, by))
            # Compile requests since the previous record (the call's start
            # for the first): the epoch that recompiled says so, with no
            # tracing at all. Counted once an entry point has installed
            # the log (utils/backend.py:enable_compile_cache).
            compiles_now = obs_lib.compiles.requests()
            rec["compiles"] = compiles_now - compiles_seen
            compiles_seen = compiles_now
            if hasattr(model, "counters"):
                # (nn/glm_moe.py: rows held, load, overflow, the rows a sum
                # of the tokens' rows read, a value a layer)
                rec.update(model.counters(state.model_state))
            # the process's own copy, for a reader handed no recorder
            obs_lib.epochs.record(rec)
            metrics.record(**rec)
        if ring is not None:
            from parallel_cnn_tpu.train import checkpoint

            ring.save(
                epoch + 1,
                state,
                checkpoint.TrainState(
                    epoch=epoch + 1,
                    epoch_errors=list(losses),
                    extra={"epoch_accs": list(accs)},
                ),
            )
            if obs.enabled:
                obs.event("checkpoint", epoch=epoch + 1)
        if verbose:
            acc_txt = f", acc {accs[-1]:.2f}%" if eval_data is not None else ""
            print(
                f"epoch {epoch + 1}: loss {losses[-1]:.4f}{acc_txt} "
                f"({seconds:.2f}s)"
            )
        if chaos is not None:
            chaos.at_epoch(epoch + 1)
        if preempt.requested():
            # Checkpoint for this epoch is already flushed (ring.save
            # above); stop at the boundary so --resume continues exactly.
            if obs.enabled:
                obs.event("preempt", epoch=epoch + 1)
            if verbose:
                print(f"preemption: stopping after epoch {epoch + 1}")
            break
        epoch += 1

    if profile_trace_dir:
        from parallel_cnn_tpu.utils import profiling

        if loader == "native":
            bx = jnp.asarray(images[:batch_size])
            by = jnp.asarray(labels[:batch_size])
        else:
            bx, by = select_batch(store, labels, jnp.arange(n), 0,
                                  batch=batch_size, in_shape=row_shape,
                                  over=over)
        total = epochs * steps

        def pkey(i):
            return (
                jax.random.fold_in(aug_base, total + i)
                if aug_fn is not None
                else None
            )

        # One warm step outside the trace: the step is already compiled
        # from training, but a resumed-at-final-epoch run may have taken
        # zero steps in this process.
        state, loss = step(state, bx, by, pkey(0))
        jax.block_until_ready(loss)
        with profiling.xla_trace(profile_trace_dir):
            for i in range(1, 4):
                state, loss = step(state, bx, by, pkey(i))
            jax.block_until_ready(loss)
        if verbose:
            print(f"xla trace (3 steps) written to {profile_trace_dir}")
    return state, losses
