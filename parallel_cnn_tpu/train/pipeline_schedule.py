"""1F1B pipeline-parallel train step over a (stage, data) mesh.

One SPMD program: every device traces the SAME tick loop; per-stage
heterogeneity lives in `lax.switch` on the device's stage coordinate, so
the jaxpr stays a single shard_map body graftcheck can walk (the per-axis
ring-coverage, f32-wire, and cost-accountant rules all extend to the
``stage`` axis unchanged).

Schedule (parallel/pipeline.py has the closed form): forward of
microbatch m at stage s fires at tick s + 2m, its backward at tick
2S − 1 − s + 2m; both inter-stage wires are one full-ring ppermute per
tick (fwd shifts +1 over the stage axis, bwd shifts −1), with the
wrap-around hops masked at the receiver by the schedule's validity
tables. The per-stage activation stash holds at most S live microbatches
(slot m mod S — reuse-safe because Tf(s, m+S) − Tb(s, m) = 2s + 1 > 0).

The backward recomputes each stage's forward from its stashed INPUT
(activation remat — the stash holds one boundary tensor per live
microbatch instead of every intermediate). BatchNorm's train-mode output
and gradients depend only on the current batch's statistics, never on
the incoming running stats (nn/layers.py), so recomputing against the
tick-current model_state is gradient-exact.

Parity contract (tests/test_pipeline.py): the batch shards over the data
axis exactly as in the D-device flat data-parallel step, each stage's
microbatch loop visits the same shards in the same order, the stage-axis
psum only ever adds exact zeros (each layer's grad/state is owned by one
stage), and the data-axis reduce is the SAME bucketed ring collective —
so stages=2/4 match the flat ring step to reassociation-only error
(gated ≤1e-5) and stages=1 delegates to it outright (bit-exact).
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from parallel_cnn_tpu.nn.core import Module
from parallel_cnn_tpu.parallel import pipeline as pp
from parallel_cnn_tpu.parallel.mesh import (
    DATA_AXIS,
    STAGE_AXIS,
    pipeline_axis_sizes,
)
from parallel_cnn_tpu.train.zoo import (
    FusedOptState,
    ZooState,
    cross_entropy,
)


def _default_comm():
    """The data-axis gradient reduce when the caller brings no
    CommConfig: the bucketed ring — pipelining exists to compose with
    the explicit collective path, not the GSPMD one."""
    from parallel_cnn_tpu.config import CommConfig

    return CommConfig(impl="ring")


def _where_tree(pred, new, old):
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(pred, n, o), new, old
    )


def make_pipeline_step(
    model: Module,
    optimizer: Optional[optax.GradientTransformation],
    *,
    accum_steps: int,
    mesh: Mesh,
    pipeline,
    in_shape: Sequence[int],
    comm=None,
    fused=None,
    lr: float = 0.1,
    momentum: float = 0.9,
) -> Callable:
    """Build the jitted 1F1B step: (state, x, y) -> (state, loss).

    ``pipeline`` is a config.PipelineConfig; ``mesh`` a
    mesh.make_pipeline_mesh (stage, data) mesh whose stage axis matches
    ``pipeline.stages``. ``accum_steps`` doubles as the microbatch count
    M — the pipeline rides the existing grad-accumulation knob, so the
    global batch must divide by M × n_data exactly as before.

    ``fused`` (config.FusedStepConfig, zero=2 only) swaps the tree-wide
    optax pass for the ZeRO-2 tail: stage-reduced grads flatten into the
    collectives buckets, ring reduce-scatter over the data axis, the
    fused SGD+momentum kernel updates each device's param/momentum shard
    (momentum resident as (n_data, L) rows, exactly the zoo layout), and
    an always-f32 all-gather ships updated params. ZeRO-3 is rejected:
    its just-in-time head gathers assume every device materializes the
    full param tree per microbatch, which contradicts per-stage param
    residency — docs/pipeline.md states the composition matrix.

    stages=1 returns zoo.make_train_step(..., comm=...) unchanged — the
    degenerate pipeline IS the flat explicit-ring step, bit-exact by
    construction (and the graftcheck twin entry proves it traces the
    same collectives).
    """
    from parallel_cnn_tpu.parallel import collectives
    from parallel_cnn_tpu.train import zoo

    zoo.refuse_step_state(model, "pipeline step")

    comm = comm or _default_comm()
    n_stages = int(pipeline.stages)
    if fused is not None:
        if fused.zero != 2:
            raise ValueError(
                "pipeline composes with ZeRO-2 only: ZeRO-3's "
                "just-in-time head gathers contradict per-stage param "
                "residency (docs/pipeline.md)"
            )
        if not fused.update:
            raise ValueError(
                "pipeline fused mode is the ZeRO-2 update-on-arrival "
                "tail and requires fused.update=True"
            )
        if pipeline.act_dtype != "float32":
            raise ValueError(
                "pipeline fused (ZeRO-2) mode is f32-only — bf16 stage "
                "compute composes with the plain optax tail instead"
            )
    if n_stages == 1:
        if fused is not None:
            raise ValueError(
                "stages=1 delegates to the zoo step — use "
                "make_fused_train_step for the ZeRO-2 path there"
            )
        return zoo.make_train_step(
            model, optimizer, accum_steps=accum_steps, mesh=mesh,
            comm=comm,
        )

    s_mesh, n_data = pipeline_axis_sizes(mesh)
    if s_mesh != n_stages:
        raise ValueError(
            f"mesh stage axis is {s_mesh} but pipeline.stages is "
            f"{n_stages} — build the mesh with "
            f"make_pipeline_mesh({n_stages})"
        )
    n_micro = int(accum_steps)
    n_layers = len(model.layers)
    in_shape = tuple(in_shape)

    boundaries = pp.split_layers(
        model, n_stages, in_shape, microbatch=1,
        boundaries=pipeline.boundaries(),
    )
    assign = pp.stage_assignment(n_layers, boundaries)
    starts = (0,) + tuple(boundaries)
    ends = tuple(boundaries) + (n_layers,)
    # Per-sample input shape of each stage: the model input for stage 0,
    # the upstream boundary activation for the rest.
    bshapes = pp.boundary_shapes(model, in_shape, boundaries, 1)
    stage_in = (in_shape,) + tuple(sh[1:] for sh in bshapes)
    a_buf = pp.wire_numel(model, in_shape, boundaries, 1)
    fwd_mb, fwd_valid, bwd_mb, bwd_valid = pp.schedule_arrays(
        n_stages, n_micro
    )
    n_tick = fwd_mb.shape[0]
    wire_dt = jnp.dtype(pipeline.wire_dtype)
    act_dt = jnp.dtype(pipeline.act_dtype)
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    wire = collectives.wire_dtype_arg(comm)

    def run_stage(s: int, params, model_state, x, train=True):
        """Layers [starts[s], ends[s]) — returns (y, full new state)."""
        new_state = list(model_state)
        if act_dt != jnp.float32:
            # Layers cast their own params to x.dtype (nn/layers.py), so
            # bf16 stage compute needs only the input cast; the cast's
            # transpose returns f32 cotangents to the f32 masters.
            x = x.astype(act_dt)
        for j in range(starts[s], ends[s]):
            x, ns = model.layers[j].apply(
                params[j], model_state[j], x, train
            )
            new_state[j] = ns
        return x.astype(jnp.float32), new_state

    def _fwd_branch(s: int, mb: int):
        last = s == n_stages - 1

        def branch(params, model_state, x, y, fwd_in, fm):
            if s == 0:
                inp = jax.lax.dynamic_slice_in_dim(x, fm * mb, mb, 0)
            else:
                inp = pp.unpack_acts(fwd_in, (mb,) + stage_in[s])
            out, new_state = run_stage(s, params, model_state, inp)
            if last:
                by = jax.lax.dynamic_slice_in_dim(y, fm * mb, mb, 0)
                loss = cross_entropy(out, by)
                out_buf = jnp.zeros((mb, a_buf), jnp.float32)
            else:
                loss = jnp.float32(0.0)
                out_buf = pp.pack_acts(out, a_buf)
            return out_buf, new_state, loss, pp.pack_acts(inp, a_buf)

        return branch

    def _bwd_branch(s: int, mb: int):
        last = s == n_stages - 1

        def branch(params, model_state, y, stashed, bwd_in, bm):
            inp = pp.unpack_acts(stashed, (mb,) + stage_in[s])
            if last:
                by = jax.lax.dynamic_slice_in_dim(y, bm * mb, mb, 0)

                def f(p, xi):
                    out, _ = run_stage(s, p, model_state, xi)
                    return cross_entropy(out, by)

                _, vjp_fn = jax.vjp(f, params, inp)
                d_params, d_inp = vjp_fn(jnp.float32(1.0))
            else:

                def f(p, xi):
                    out, _ = run_stage(s, p, model_state, xi)
                    return pp.pack_acts(out, a_buf)

                _, vjp_fn = jax.vjp(f, params, inp)
                d_params, d_inp = vjp_fn(bwd_in)
            return pp.pack_acts(d_inp, a_buf), d_params

        return branch

    def shard_body(state: ZooState, x, y):
        params, model_state = state.params, state.model_state
        zoo.refuse_random_layers(model_state, "pipeline step")
        if x.shape[0] % n_micro:
            raise ValueError(
                f"per-device batch {x.shape[0]} must be a multiple of "
                f"accum_steps {n_micro} (no silent sample dropping)"
            )
        mb = x.shape[0] // n_micro
        fwd_branches = [_fwd_branch(s, mb) for s in range(n_stages)]
        bwd_branches = [_bwd_branch(s, mb) for s in range(n_stages)]
        my_stage = jax.lax.axis_index(STAGE_AXIS)
        fwd_in = jnp.zeros((mb, a_buf), jnp.float32)
        bwd_in = jnp.zeros((mb, a_buf), jnp.float32)
        stash = jnp.zeros((n_stages, mb, a_buf), jnp.float32)
        gsum = jax.tree_util.tree_map(jnp.zeros_like, params)
        lsum = jnp.float32(0.0)
        for t in range(n_tick):
            if t:
                # Tick sequencing, same role as the zoo microbatch
                # barrier: without it XLA may hoist forwards across the
                # 1F1B interleave and restore GPipe's M-deep stash.
                (fwd_in, bwd_in, stash, lsum, model_state, gsum) = (
                    jax.lax.optimization_barrier(
                        (fwd_in, bwd_in, stash, lsum, model_state, gsum)
                    )
                )
            fm = jnp.asarray(fwd_mb[t])[my_stage]
            fv = jnp.asarray(fwd_valid[t])[my_stage]
            bm = jnp.asarray(bwd_mb[t])[my_stage]
            bv = jnp.asarray(bwd_valid[t])[my_stage]

            out_buf, new_state, loss_t, inp_packed = jax.lax.switch(
                my_stage, fwd_branches,
                params, model_state, x, y, fwd_in, fm,
            )
            lsum = lsum + jnp.where(fv, loss_t, jnp.float32(0.0))
            model_state = _where_tree(fv, new_state, model_state)
            # Stash this tick's stage input at slot fm mod S. On idle
            # ticks fm clamps to 0 — rewrite the slot with its own
            # current value so a live entry is never clobbered.
            slot = jnp.mod(fm, n_stages)
            old_slot = jax.lax.dynamic_slice(
                stash, (slot, 0, 0), (1, mb, a_buf)
            )
            stash = jax.lax.dynamic_update_slice(
                stash,
                jnp.where(fv, inp_packed[None], old_slot),
                (slot, 0, 0),
            )

            bslot = jnp.mod(bm, n_stages)
            stashed = jax.lax.dynamic_slice(
                stash, (bslot, 0, 0), (1, mb, a_buf)
            )[0]
            d_inp, d_params = jax.lax.switch(
                my_stage, bwd_branches,
                params, model_state, y, stashed, bwd_in, bm,
            )
            gsum = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(bv, g, jnp.zeros_like(g)),
                gsum, d_params,
            )
            # Both inter-stage wires move every tick as one full stage
            # ring each (the single-cycle shape the ring-coverage rule
            # requires); wrap-around hops carry garbage the validity
            # masks above never read.
            fwd_in = jax.lax.ppermute(
                out_buf.astype(wire_dt), STAGE_AXIS, fwd_perm
            ).astype(jnp.float32)
            bwd_in = jax.lax.ppermute(
                d_inp.astype(wire_dt), STAGE_AXIS, bwd_perm
            ).astype(jnp.float32)

        # Each layer's grads are nonzero on exactly one stage row; the
        # stage psum only adds exact zeros (replicating, not reducing),
        # then the data-axis reduce is the same bucketed ring the flat
        # DP step uses — the parity surface.
        gsum = jax.lax.psum(gsum, STAGE_AXIS)
        grads = collectives.tree_all_reduce(gsum, DATA_AXIS, n_data, comm)
        grads = jax.tree_util.tree_map(
            lambda g: g / (n_micro * n_data), grads
        )
        loss = jax.lax.pmean(
            jax.lax.psum(lsum, STAGE_AXIS) / n_micro, DATA_AXIS
        )
        # model_state: owner-stage selection (non-owners never updated
        # their copy), then the data pmean the flat step also applies.
        owned = jnp.asarray(assign) == my_stage
        picked = [
            jax.tree_util.tree_map(
                lambda v: jnp.where(owned[j], v, jnp.zeros_like(v)),
                model_state[j],
            )
            for j in range(n_layers)
        ]
        model_state = jax.lax.pmean(
            jax.lax.psum(picked, STAGE_AXIS), DATA_AXIS
        )

        if fused is None:
            updates, opt_state = optimizer.update(
                grads, state.opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return ZooState(params, model_state, opt_state), loss

        # ZeRO-2 tail: shard the summed grads back out over the data
        # axis and run the fused SGD+momentum kernel on each device's
        # 1/n_data rows; the trailing param all-gather is ALWAYS f32
        # (master precision), like the zoo fused step.
        from parallel_cnn_tpu.ops import pallas_update

        opt = state.opt_state
        plan = collectives.plan_buckets(
            params, comm.bucket_bytes, shards=n_data
        )
        gb = collectives.flatten_buckets(gsum, plan)
        pb = collectives.flatten_buckets(params, plan)
        idx = jax.lax.axis_index(DATA_AXIS)
        gscale = 1.0 / (n_micro * n_data)
        new_pb = []
        new_mom = []
        for b in range(len(gb)):
            gsh = collectives.ring_reduce_scatter(
                gb[b], DATA_AXIS, n_data, wire
            )
            psh = jnp.take(pb[b].reshape(n_data, -1), idx, axis=0)
            msh = opt.mom[b][0]
            p_new, m_new = pallas_update.fused_sgd_momentum(
                psh, msh, gsh, lr=lr, momentum=momentum, scale=gscale
            )
            new_mom.append(m_new[None, :])
            new_pb.append(
                collectives.ring_all_gather(p_new, DATA_AXIS, n_data, None)
            )
        params = collectives.unflatten_buckets(new_pb, plan)
        opt = FusedOptState(
            mom=new_mom, scale=opt.scale, good_steps=opt.good_steps,
            skipped=opt.skipped,
        )
        return ZooState(params, model_state, opt), loss

    if fused is None:
        state_spec = P()
    else:
        # Bucket count from the params structure — mirror
        # init_fused_state's plan so the momentum spec lines up.
        params0, _, _ = model.init(jax.random.PRNGKey(0), in_shape)
        plan0 = collectives.plan_buckets(
            params0, comm.bucket_bytes, shards=n_data
        )
        state_spec = ZooState(
            params=P(),
            model_state=P(),
            opt_state=FusedOptState(
                mom=[P(DATA_AXIS)] * plan0.n_buckets,
                scale=P(),
                good_steps=P(),
                skipped=P(),
            ),
        )

    sharded = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(state_spec, P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(state_spec, P()),
        check_vma=False,  # ppermute outputs, as in the ring DP step
    )

    def step(state: ZooState, x, y, key=None):
        return sharded(state, x, y)

    return jax.jit(step, donate_argnums=(0,))


def stage_plan(model: Module, pipeline, in_shape: Sequence[int]):
    """(boundaries, assignment, per-stage flops) — the audit surface the
    tests check against the cost tables."""
    boundaries = pp.split_layers(
        model, pipeline.stages, tuple(in_shape), microbatch=1,
        boundaries=pipeline.boundaries(),
    )
    costs = pp.layer_costs(model, tuple(in_shape), microbatch=1)
    assign = pp.stage_assignment(len(model.layers), boundaries)
    flops = [0] * pipeline.stages
    for c in costs:
        flops[int(assign[c.index])] += c.flops
    return boundaries, assign, tuple(flops)
