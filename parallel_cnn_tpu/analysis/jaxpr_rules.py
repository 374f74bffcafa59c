"""jaxpr-level invariant analyzers.

The real train/serve entry points are traced abstractly (via
``jax.make_jaxpr`` — no kernel runs, no device memory) and the resulting
jaxprs are walked recursively (into pjit/scan/while/cond/shard_map
sub-jaxprs) checking:

- ``collective-axis``: every collective's axis name must be one of the
  package's DECLARED mesh axes (``data``/``model``/``host``/``stage`` —
  parallel/mesh.py) and exist on the nearest enclosing ``shard_map``
  mesh.  The installed JAX already refuses an axis no mesh binds at
  trace time; what only a lint sees is an ad-hoc axis — ``jax.pmap``
  (which now lowers through ``shard_map`` over a private mesh) or a
  hand-built mesh that bypasses parallel/mesh.py and with it every
  axis-keyed rule, byte table and plan check in this package.
- ``ring-permutation``: every ``ppermute`` permutation must be a single
  cycle covering all participants — and, when the enclosing shard_map
  mesh gives the axis a size, covering *every rank of its axis*
  (``set(range(size))``).  A broken ring (two sub-cycles, a dropped
  rank) reduces only part of the gradient and silently desynchronizes
  replicas — the exact class of bug arXiv:1810.11112's scheduling
  constraints exist to prevent.  Hierarchical topologies ring each
  mesh axis separately, so the requirement is per-axis: a dev-axis
  ring never names host ranks and vice versa.
- ``f32-wire`` (masters never ride bf16): two directions.
  Output side: any ``ppermute`` whose output reaches a jaxpr output
  through *layout-only* ops (reshape, slice, concatenate, dtype cast,
  …) is a param all-gather wire and must carry float32.  Input side
  (the ZeRO-3 just-in-time gathers): any ``ppermute`` fed from a jaxpr
  *input* through layout-only ops is gathering resident state — master
  weights or optimizer shards — and must equally carry float32.
  Gradient wires may be bf16 — they are produced by backward-pass
  arithmetic and consumed by optimizer arithmetic, which breaks the
  transparent chain on both sides, so they are exempt by construction.
- ``donated-reuse``: an operand donated to a pjit call may not be read
  by any later equation — donation aliases the buffer to the output.
- ``weak-type``: weak-typed entry arguments and 0-d weak constants
  captured by the trace.  Weak types re-promote per call site and a
  python scalar captured as a traced constant bakes its value into the
  executable — both are retrace/staleness hazards.
"""

from __future__ import annotations

import dataclasses
from typing import (
    Callable, Dict, Iterable, List, Optional, Sequence, Set, Tuple,
)

from parallel_cnn_tpu.analysis.diagnostics import Diagnostic, Severity

# Declared mesh axes (parallel/mesh.py DATA_AXIS/MODEL_AXIS/HOST_AXIS).
# Sizes are unknown (None) until a shard_map mesh refines them.
DECLARED_AXES = {"data", "model", "host", "stage"}

# Primitives that only rearrange/retag values: a ppermute output flowing
# through ONLY these to a jaxpr output means the wire dtype is what the
# caller receives.  convert_element_type is deliberately transparent so
# "gather bf16 then cast back to f32" is still caught — the precision
# was already lost on the wire.
_TRANSPARENT = {
    "reshape", "squeeze", "expand_dims", "transpose", "rev", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate", "pad",
    "broadcast_in_dim", "convert_element_type", "copy", "gather",
    "scatter", "select_n",
}

# Primitives carrying a mesh-axis parameter worth checking.
_AXIS_PARAM_KEYS = ("axis_name", "axes")


def _axis_names(eqn) -> Tuple[str, ...]:
    names: List[str] = []
    for key in _AXIS_PARAM_KEYS:
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, str):
                names.append(v)
            elif isinstance(v, (tuple, list)):
                names.extend(x for x in v if isinstance(x, str))
    return tuple(names)


def _sub_jaxprs(eqn) -> Iterable:
    """Inner jaxprs of an equation (pjit jaxpr, scan body, cond branches,
    shard_map body, custom_vjp calls...)."""
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item           # raw Jaxpr


def walk_jaxpr(jaxpr, visit: Callable, allowed: Dict[str, Optional[int]]) -> None:
    """Depth-first walk calling ``visit(jaxpr, eqn, allowed)``; the
    allowed-axis mapping (axis name -> size, None while unknown) is
    refined at each shard_map from its mesh — inside the body both the
    axis NAMES and their SIZES are known, which is what lets the ring
    check demand full-axis coverage per axis. Only DECLARED axes are
    ever allowed: a mesh axis outside the package's vocabulary (a pmap's
    private axis, say) stays undeclared inside its own shard_map."""
    for eqn in jaxpr.eqns:
        visit(jaxpr, eqn, allowed)
        sub_allowed = allowed
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            axis_names = getattr(mesh, "axis_names", None)
            if axis_names:
                shape = getattr(mesh, "shape", None)
                sizes = dict(shape) if shape is not None else {}
                sub_allowed = {
                    a: sizes.get(a) for a in axis_names
                    if a in DECLARED_AXES
                }
        for sub in _sub_jaxprs(eqn):
            walk_jaxpr(sub, visit, sub_allowed)


def _cycle_members(perm: Sequence[Tuple[int, int]]) -> Optional[Set[int]]:
    """The member set of ``perm`` when it is one single cycle, else None."""
    if not perm:
        return None
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    members = set(srcs) | set(dsts)
    if len(set(srcs)) != len(srcs) or len(set(dsts)) != len(dsts):
        return None
    if set(srcs) != members or set(dsts) != members:
        return None
    nxt = dict(perm)
    start = srcs[0]
    seen = set()
    cur = start
    while cur not in seen:
        seen.add(cur)
        cur = nxt[cur]
    if cur == start and seen == members:
        return members
    return None


def _is_single_cycle(perm: Sequence[Tuple[int, int]]) -> bool:
    return _cycle_members(perm) is not None


def _var_key(v) -> Optional[int]:
    # Literals have no identity across uses; Vars do.
    return id(v) if not hasattr(v, "val") else None


def _producer_map(jaxpr):
    producer = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            producer[_var_key(ov)] = eqn
    return producer


def _wire_reachable_permutes(jaxpr):
    """ppermute eqns whose outputs reach jaxpr outvars through
    transparent ops only."""
    producer = _producer_map(jaxpr)
    hits = []
    seen_eqns: Set[int] = set()
    frontier = [v for v in jaxpr.outvars]
    seen_vars: Set[int] = set()
    while frontier:
        v = frontier.pop()
        k = _var_key(v)
        if k is None or k in seen_vars:
            continue
        seen_vars.add(k)
        eqn = producer.get(k)
        if eqn is None or id(eqn) in seen_eqns:
            continue
        name = eqn.primitive.name
        if name == "ppermute":
            seen_eqns.add(id(eqn))
            hits.append(eqn)
            continue  # don't cross the wire
        if name in _TRANSPARENT:
            seen_eqns.add(id(eqn))
            frontier.extend(eqn.invars)
    return hits


def _resident_fed_permutes(jaxpr):
    """ppermute eqns fed from jaxpr invars/constvars through transparent
    ops only — the wire is moving resident state (ZeRO-3 master-weight /
    optimizer shards gathered just-in-time at the step head), not values
    computed this step.  Gradient wires are produced by backward-pass
    arithmetic, which breaks the chain, so they never match."""
    producer = _producer_map(jaxpr)
    resident = {
        _var_key(v)
        for v in (*jaxpr.invars, *jaxpr.constvars)
        if _var_key(v) is not None
    }
    memo: Dict[int, bool] = {}

    def from_resident(var) -> bool:
        k = _var_key(var)
        if k is None:
            return False
        if k in resident:
            return True
        if k in memo:
            return memo[k]
        memo[k] = False  # cycle guard (jaxprs are SSA; belt-and-braces)
        eqn = producer.get(k)
        if eqn is not None and eqn.primitive.name in _TRANSPARENT:
            memo[k] = any(from_resident(iv) for iv in eqn.invars)
        return memo[k]

    return [
        eqn for eqn in jaxpr.eqns
        if eqn.primitive.name == "ppermute"
        and any(from_resident(iv) for iv in eqn.invars)
    ]


# ---------------------------------------------------------------------------
# Rules over one traced entry point
# ---------------------------------------------------------------------------

def analyze_closed_jaxpr(name: str, closed) -> List[Diagnostic]:
    """Run all jaxpr rules over one ClosedJaxpr.  ``name`` labels the
    entry point; findings use the pseudo-file ``<jaxpr:name>``."""
    diags: List[Diagnostic] = []
    file = f"<jaxpr:{name}>"

    def visit(jaxpr, eqn, allowed: Dict[str, Optional[int]]) -> None:
        prim = eqn.primitive.name
        for axis in _axis_names(eqn):
            if axis not in allowed:
                diags.append(Diagnostic(
                    rule="collective-axis",
                    severity=Severity.ERROR,
                    file=file,
                    line=0,
                    message=f"{prim} uses axis '{axis}' which is not a "
                            "declared axis of the enclosing mesh "
                            f"(declared axes there: {sorted(allowed)})",
                ))
        if prim == "ppermute":
            perm = list(eqn.params.get("perm", ()))
            members = _cycle_members(perm)
            if members is None:
                diags.append(Diagnostic(
                    rule="ring-permutation",
                    severity=Severity.ERROR,
                    file=file,
                    line=0,
                    message=f"ppermute permutation {perm} is not a single "
                            "cycle over all participants; a broken ring "
                            "reduces only part of the gradient",
                ))
            else:
                # Per-axis coverage: on hierarchical meshes each ring
                # permutes WITHIN its own axis, so the cycle must hit
                # every rank of that axis — a ring over a subset leaves
                # the dropped ranks permanently out of the reduction.
                for axis in _axis_names(eqn):
                    size = allowed.get(axis)
                    if size is not None and members != set(range(size)):
                        diags.append(Diagnostic(
                            rule="ring-permutation",
                            severity=Severity.ERROR,
                            file=file,
                            line=0,
                            message=f"ppermute over axis '{axis}' (size "
                                    f"{size}) cycles ranks {sorted(members)} "
                                    "only; the ring must cover every rank of "
                                    "its axis — dropped ranks neither "
                                    "contribute nor receive the reduction",
                        ))
        if "donated_invars" in eqn.params:
            diags.extend(_donated_reuse(file, jaxpr, eqn))

    walk_jaxpr(closed.jaxpr, visit, {a: None for a in DECLARED_AXES})

    # f32-wire: applied per sub-jaxpr so both slices — "reaches an output
    # through transparent ops" and "fed from an input through transparent
    # ops" — respect scope boundaries.
    def wire_visit(jaxpr) -> None:
        for eqn in _wire_reachable_permutes(jaxpr):
            for ov in eqn.outvars:
                dtype = getattr(ov.aval, "dtype", None)
                if dtype is not None and str(dtype) not in ("float32", "float64"):
                    diags.append(Diagnostic(
                        rule="f32-wire",
                        severity=Severity.ERROR,
                        file=file,
                        line=0,
                        message=f"ppermute output ({dtype}) reaches a jaxpr "
                                "output through layout-only ops: a param "
                                "all-gather is riding a non-f32 wire — "
                                "masters never ride bf16",
                    ))
        for eqn in _resident_fed_permutes(jaxpr):
            for ov in eqn.outvars:
                dtype = getattr(ov.aval, "dtype", None)
                if dtype is not None and str(dtype) not in ("float32", "float64"):
                    diags.append(Diagnostic(
                        rule="f32-wire",
                        severity=Severity.ERROR,
                        file=file,
                        line=0,
                        message=f"ppermute wire ({dtype}) is fed from a "
                                "jaxpr input through layout-only ops: a "
                                "just-in-time gather of resident state "
                                "(master weights / optimizer shards) is "
                                "riding a non-f32 wire — masters never "
                                "ride bf16",
                    ))

    def _walk_all(jaxpr) -> None:
        wire_visit(jaxpr)
        for eqn in jaxpr.eqns:
            for sub in _sub_jaxprs(eqn):
                _walk_all(sub)

    _walk_all(closed.jaxpr)

    diags.extend(_weak_types(file, closed))
    return diags


def _donated_reuse(file: str, jaxpr, eqn) -> List[Diagnostic]:
    flags = eqn.params.get("donated_invars") or ()
    donated = {
        _var_key(iv)
        for iv, f in zip(eqn.invars, flags)
        if f and _var_key(iv) is not None
    }
    if not donated:
        return []
    out: List[Diagnostic] = []
    past = False
    for later in jaxpr.eqns:
        if later is eqn:
            past = True
            continue
        if not past:
            continue
        for iv in later.invars:
            if _var_key(iv) in donated:
                out.append(Diagnostic(
                    rule="donated-reuse",
                    severity=Severity.ERROR,
                    file=file,
                    line=0,
                    message=f"operand donated to '{eqn.params.get('name', 'pjit')}' "
                            f"is read again by a later '{later.primitive.name}' "
                            "equation; donation aliases the buffer to the output",
                ))
    for ov in jaxpr.outvars:
        if _var_key(ov) in donated:
            out.append(Diagnostic(
                rule="donated-reuse",
                severity=Severity.ERROR,
                file=file,
                line=0,
                message="a donated operand is returned as a jaxpr output after "
                        "donation; the caller would observe an aliased buffer",
            ))
    return out


def _weak_types(file: str, closed) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for i, v in enumerate(closed.jaxpr.invars):
        aval = v.aval
        if getattr(aval, "weak_type", False):
            diags.append(Diagnostic(
                rule="weak-type",
                severity=Severity.ERROR,
                file=file,
                line=0,
                message=f"entry argument {i} traces weak-typed ({aval}); a "
                        "python scalar argument re-promotes per call site — "
                        "pass a jnp array with an explicit dtype",
            ))
    for cv, val in zip(closed.jaxpr.constvars, closed.consts):
        aval = cv.aval
        if getattr(aval, "ndim", None) == 0 and getattr(aval, "weak_type", False):
            diags.append(Diagnostic(
                rule="weak-type",
                severity=Severity.ERROR,
                file=file,
                line=0,
                message=f"0-d weak-typed constant {val!r} captured by the "
                        "trace; its value is frozen into the executable and "
                        "its weak type re-promotes downstream dtypes",
            ))
    return diags


# ---------------------------------------------------------------------------
# Entry-point registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EntrySpec:
    """Static cost description of one traced zoo entry point.

    Everything the closed-form byte/HBM models (analysis/cost_model.py)
    need, captured at trace time from the same state/plan/config objects
    the step was built from — no re-derivation from the jaxpr, so the
    measured walk and the analytic model stay independent.
    """

    kind: str            # ring_overlap | hier_overlap | zero2_ring |
                         # zero3_ring | zero3_hier | pipeline_ring
                         # (docs/collectives.md)
    n_dev: int           # device-axis ring size D (intra-host / ICI)
    n_host: int          # host-axis ring size H (1 on flat meshes / DCN)
    accum: int           # K gradient-accumulation microbatches per step
    wire_itemsize: int   # gradient wire dtype bytes (bfloat16 = 2)
    bucket_elems: Tuple[int, ...]  # padded element count per bucket (E_b)
    resident_bytes: int  # per-device resident state bytes under the
                         # DECLARED sharding (ZeRO level applied)
    act_bytes: int       # activation high-water mark per device microbatch
    images_per_step: int  # global batch consumed by one step
    n_state_leaves: int  # leaves of the ZooState pytree (sharding_prop)
    transient_gather_bytes: int = 0  # zero3 head-gather peak (full f32
                                     # params, freed before backward)
    n_stage: int = 1     # pipeline stage-axis ring size S (1 = no pipe)
    pipe_micro: int = 0  # pipeline microbatch count M (the 1F1B tick
                         # count is 2(M+S-1); 0 on non-pipeline entries)
    stage_payload_bytes: int = 0  # one stage-wire ppermute payload:
                                  # mb*A_buf*wire itemsize (docs/pipeline.md)
    stash_bytes: int = 0  # f32 activation stash: S*mb*A_buf*4 resident
                          # across the whole tick loop


def _tree_bytes(tree) -> int:
    import jax
    import jax.numpy as jnp

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = jnp.asarray(leaf)
        total += int(arr.size) * arr.dtype.itemsize
    return total


def _activation_hwm(model, params, mstate, microbatch: int,
                    in_shape: Tuple[int, ...], act_itemsize: int) -> int:
    """Peak simultaneous (input + output) activation bytes of any single
    layer, per device microbatch, via per-layer ``jax.eval_shape`` over
    ``Sequential.layers`` — no layer runs.  ``act_itemsize`` scales the
    footprint to the step's activation dtype (bf16 entries halve it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jax.ShapeDtypeStruct((microbatch, *in_shape), jnp.float32)
    peak = 0
    for layer, p, s in zip(model.layers, params, mstate):
        y, _ = jax.eval_shape(
            lambda p_, s_, x_: layer.apply(p_, s_, x_, True), p, s, x
        )
        live = int(np.prod(x.shape) + np.prod(y.shape)) * act_itemsize
        peak = max(peak, live)
        x = jax.ShapeDtypeStruct(y.shape, y.dtype)
    return peak


def trace_tuned_entry(plan, mp, model, mesh, in_shape, global_batch: int,
                      hbm_budget: Optional[int] = None) -> Tuple:
    """Trace a tuner-chosen FLAT plan as a first-class cost entry.

    The HBM budget gate runs FIRST: an over-budget plan raises
    ``autotune.BudgetExceeded`` before any step is built, so a mutant
    plan the tuner must reject can never leak into the traced entry set
    (the anti-vacuity contract of the ``tune.chosen_plan`` entry).

    Supports the flat single-host ZeRO-0 schedules
    ``autotune.choose_for_trace`` searches over: psum (monolithic
    all-reduce — no closed-form ppermute table, spec None) and the ring
    in both overlap modes (kinds ``ring_overlap`` / ``ring_post``).
    """
    import jax
    import jax.numpy as jnp

    from parallel_cnn_tpu.analysis import autotune as autotune_lib
    from parallel_cnn_tpu.config import CommConfig
    from parallel_cnn_tpu.parallel import collectives
    from parallel_cnn_tpu.train import zoo

    n_data = mesh.shape["data"]
    autotune_lib.assert_within_budget(
        plan, mp, global_batch=global_batch, n_dev=n_data,
        hbm_budget=hbm_budget,
    )
    if plan.stages != 1 or plan.zero or plan.comm_impl == "hierarchical":
        raise ValueError(
            f"trace_tuned_entry covers flat ZeRO-0 plans, got "
            f"{plan.label()}"
        )
    micro = global_batch // (n_data * plan.accum)
    comm = (None if plan.comm_impl == "psum" else CommConfig(
        impl="ring", bucket_bytes=plan.bucket_bytes,
        wire_dtype=plan.wire_dtype, overlap=plan.overlap,
    ))
    opt = zoo.make_optimizer(0.01, momentum=0.9)
    st = zoo.init_state(model, jax.random.key(1), in_shape, opt)
    tstep = zoo.make_train_step(
        model, opt, accum_steps=plan.accum, mesh=mesh, comm=comm,
    )
    tx = jnp.zeros((global_batch, *in_shape), jnp.float32)
    ty = jnp.zeros((global_batch,), jnp.int32)
    closed = jax.make_jaxpr(tstep)(st, tx, ty)
    if comm is None:
        return ("tune.chosen_plan", closed, None)
    bplan = collectives.plan_buckets(
        st.params, comm.bucket_bytes, shards=n_data
    )
    kind = ("ring_overlap" if plan.overlap and plan.accum > 1
            else "ring_post")
    return (
        "tune.chosen_plan",
        closed,
        EntrySpec(
            kind=kind, n_dev=n_data, n_host=1, accum=plan.accum,
            wire_itemsize=2 if plan.wire_dtype == "bfloat16" else 4,
            bucket_elems=tuple(bplan.bucket_sizes),
            resident_bytes=_tree_bytes(st),
            act_bytes=_activation_hwm(
                model, st.params, st.model_state, micro, tuple(in_shape), 4
            ),
            images_per_step=global_batch,
            n_state_leaves=len(jax.tree_util.tree_leaves(st)),
        ),
    )


def trace_entry_points(
    fast: bool = False, with_specs: bool = False
) -> List[Tuple]:
    """Trace the real entry points abstractly; returns (name, ClosedJaxpr).

    ``fast`` skips the zoo steps (the most expensive traces).  Zoo traces
    also require a ≥2-device mesh; on a single device they are skipped.
    ``with_specs`` returns (name, ClosedJaxpr, EntrySpec-or-None) triples
    instead — the cost analyzers consume the spec, plain entries carry
    None.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step

    out: List[Tuple] = []

    def _finish(entries):
        if with_specs:
            return entries
        return [(n, c) for n, c, _ in entries]

    lp = lenet_ref.init(jax.random.key(0))
    lx = jnp.zeros((8, 28, 28), jnp.float32)
    ly = jnp.zeros((8,), jnp.int32)
    out.append((
        "train.batched_step",
        jax.make_jaxpr(lambda p, x, y: step.batched_step(p, x, y, 0.05))(
            lp, lx, ly
        ),
        None,
    ))
    out.append((
        "train.fused_batched_step",
        jax.make_jaxpr(
            lambda p, x, y: step.fused_batched_step(p, x, y, 0.05)
        )(lp, lx, ly),
        None,
    ))

    # Observability invariant (docs/observability.md): an obs span wraps
    # host-side dispatch only, so a step traced UNDER an open span must
    # yield a jaxpr free of callbacks/effects and clean under every rule
    # — i.e. the compiled program is identical with tracing on or off.
    # The span opens and closes on the host at trace time.
    from parallel_cnn_tpu.obs.trace import Tracer

    _obs_tracer = Tracer(process_name="graftcheck", mirror_jax=False)

    def _obs_step(p, x, y):
        with _obs_tracer.span("train.step", cat="step"):
            return step.batched_step(p, x, y, 0.05)

    out.append((
        "train.obs_batched_step",
        jax.make_jaxpr(_obs_step)(lp, lx, ly),
        None,
    ))

    from parallel_cnn_tpu.serve import registry as serve_registry

    sh = serve_registry.get("cifar_cnn")
    sp, sst = sh.init(jax.random.key(0))
    sx = jnp.zeros((4, *sh.in_shape), jnp.float32)
    out.append((
        "serve.engine_forward",
        jax.make_jaxpr(lambda p, st, v: sh.forward(p, st, v))(sp, sst, sx),
        None,
    ))

    # ExecutionPlan resolution entry: the DEFAULT resolved plan, driven
    # through the exact path the CLI takes — build_plan → validate →
    # make_mesh → zoo.make_train_step.  Single device, so the closed-form
    # row pins bytes_ici/bytes_dcn to 0 and the ratchet holds the peak
    # HBM of plan-driven step construction itself; cost_table_key() of
    # the default plan names this row, closing the plan ↦ cost-table
    # contract (docs/execution_plan.md) for plans with no collective.
    from parallel_cnn_tpu import plan as plan_lib
    from parallel_cnn_tpu.config import Config
    from parallel_cnn_tpu.nn import layers as nn_layers
    from parallel_cnn_tpu.nn.core import Sequential
    from parallel_cnn_tpu.train import zoo as zoo_lib

    eplan = plan_lib.build_plan(Config()).validate()
    pmodel = Sequential([
        nn_layers.Conv2D(4, (3, 3)),
        nn_layers.ReLU(),
        nn_layers.Flatten(),
        nn_layers.Dense(4),
    ])
    popt = zoo_lib.make_optimizer(0.01, momentum=0.9)
    pst = zoo_lib.init_state(pmodel, jax.random.key(0), (8, 8, 1), popt)
    pstep = zoo_lib.make_train_step(
        pmodel, popt, accum_steps=eplan.accum, mesh=eplan.make_mesh()
    )
    px = jnp.zeros((4, 8, 8, 1), jnp.float32)
    py = jnp.zeros((4,), jnp.int32)
    out.append((
        eplan.cost_table_key()[0],
        jax.make_jaxpr(pstep)(pst, px, py),
        EntrySpec(
            kind="ring_post", n_dev=1, n_host=1, accum=eplan.accum,
            wire_itemsize=2 if eplan.wire_dtype == "bfloat16" else 4,
            bucket_elems=(),
            resident_bytes=_tree_bytes(pst),
            act_bytes=_activation_hwm(
                pmodel, pst.params, pst.model_state, 4, (8, 8, 1), 4
            ),
            images_per_step=4,
            n_state_leaves=len(jax.tree_util.tree_leaves(pst)),
        ),
    ))

    if fast:
        return _finish(out)

    n_dev = len(jax.devices())
    if n_dev < 2:
        return _finish(out)

    from parallel_cnn_tpu.config import CommConfig, FusedStepConfig, MeshConfig
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.parallel import collectives
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.train import zoo

    mesh = mesh_lib.make_mesh(  # graftcheck: disable=mesh-outside-plan -- analyzer-internal synthetic trace mesh, not an execution path; plans fingerprint real runs only
        MeshConfig(data=n_dev, model=1), devices=jax.devices()[:n_dev]
    )
    n_data = mesh.shape["data"]
    model = cifar.cifar_cnn()
    zx = jnp.zeros((2 * n_data, *cifar.IN_SHAPE), jnp.float32)
    zy = jnp.zeros((2 * n_data,), jnp.int32)

    with mesh:
        ring_bf16 = CommConfig(impl="ring", wire_dtype="bfloat16")
        opt = zoo.make_optimizer(0.01, momentum=0.9)
        st = zoo.init_state(model, jax.random.key(1), cifar.IN_SHAPE, opt)
        comm_step = zoo.make_train_step(
            model, opt, accum_steps=2, mesh=mesh, comm=ring_bf16
        )
        # The step plans its buckets from the grad tree, which mirrors the
        # param tree leaf-for-leaf (same shapes/dtypes) — same plan here.
        plan = collectives.plan_buckets(
            st.params, ring_bf16.bucket_bytes, shards=n_data
        )
        out.append((
            "zoo.comm_step.ring_bf16",
            jax.make_jaxpr(comm_step)(st, zx, zy),
            EntrySpec(
                kind="ring_overlap", n_dev=n_data, n_host=1, accum=2,
                wire_itemsize=2, bucket_elems=tuple(plan.bucket_sizes),
                resident_bytes=_tree_bytes(st),
                act_bytes=_activation_hwm(
                    model, st.params, st.model_state, 1, cifar.IN_SHAPE, 4
                ),
                images_per_step=2 * n_data,
                n_state_leaves=len(jax.tree_util.tree_leaves(st)),
            ),
        ))

        # Sharpest wire check: activations AND gradient wire in bf16 —
        # the param all-gather must STILL carry f32 masters.
        fused = FusedStepConfig(update=True, tail=True, act_dtype="bfloat16")
        fst, n_buckets = zoo.init_fused_state(
            model, jax.random.key(1), cifar.IN_SHAPE,
            n_data=n_data, fused=fused, bucket_bytes=ring_bf16.bucket_bytes,
        )
        fused_step = zoo.make_fused_train_step(
            model, lr=0.01, momentum=0.9, accum_steps=2, mesh=mesh,
            augment=None, comm=ring_bf16, fused=fused, n_buckets=n_buckets,
        )
        # ZeRO-2: params/model_state replicated, momentum a 1/n shard.
        fmom = _tree_bytes(fst.opt_state.mom)
        out.append((
            "zoo.fused_step.ring_bf16",
            jax.make_jaxpr(fused_step)(fst, zx, zy),
            EntrySpec(
                kind="zero2_ring", n_dev=n_data, n_host=1, accum=2,
                wire_itemsize=2, bucket_elems=tuple(plan.bucket_sizes),
                resident_bytes=_tree_bytes(fst) - fmom + fmom // n_data,
                act_bytes=_activation_hwm(
                    model, fst.params, fst.model_state, 1, cifar.IN_SHAPE, 2
                ),
                images_per_step=2 * n_data,
                n_state_leaves=len(jax.tree_util.tree_leaves(fst)),
            ),
        ))

        # ZeRO-3 on the flat ring, sharpest setting again: bf16 gradient
        # wire, bf16 activations — the HEAD just-in-time param gathers
        # must still carry f32 masters (the input-side f32-wire slice).
        z3 = FusedStepConfig(
            update=True, tail=True, act_dtype="bfloat16", zero=3
        )
        zst, zplan = zoo.init_zero3_state(
            model, jax.random.key(1), cifar.IN_SHAPE,
            n_data=n_data, fused=z3, bucket_bytes=ring_bf16.bucket_bytes,
        )
        zero3_step = zoo.make_zero3_train_step(
            model, lr=0.01, momentum=0.9, accum_steps=2, mesh=mesh,
            augment=None, comm=ring_bf16, fused=z3, plan=zplan,
        )
        # ZeRO-3: params AND momentum resident as 1/n bucket-row shards;
        # the head gather's full f32 params are transient, not resident.
        zsharded = _tree_bytes(zst.params) + _tree_bytes(zst.opt_state.mom)
        out.append((
            "zoo.zero3_step.ring_bf16",
            jax.make_jaxpr(zero3_step)(zst, zx, zy),
            EntrySpec(
                kind="zero3_ring", n_dev=n_data, n_host=1, accum=2,
                wire_itemsize=2, bucket_elems=tuple(zplan.bucket_sizes),
                resident_bytes=(
                    _tree_bytes(zst) - zsharded + zsharded // n_data
                ),
                act_bytes=_activation_hwm(
                    model, zoo.zero3_full_params(zst, zplan),
                    zst.model_state, 1, cifar.IN_SHAPE, 2
                ),
                images_per_step=2 * n_data,
                n_state_leaves=len(jax.tree_util.tree_leaves(zst)),
                transient_gather_bytes=sum(zplan.bucket_sizes) * 4,
            ),
        ))

        # Autotuner chosen-plan entry (analysis/autotune.py): the flat
        # winner of the DEFAULT-profile roofline search, re-traced so the
        # plan the tuner recommends passes every jaxpr/cost rule the
        # hand-set entries do.  The HBM budget gate inside
        # trace_tuned_entry runs before the trace — an over-budget plan
        # is rejected by the tuner, never traced.
        from parallel_cnn_tpu.analysis import autotune as autotune_lib

        tuned_mp = autotune_lib.profile_module(
            model, cifar.IN_SHAPE, name="cifar_cnn"
        )
        tuned = autotune_lib.choose_for_trace(
            tuned_mp, n_dev=n_data, global_batch=8 * n_data
        )
        out.append(trace_tuned_entry(
            tuned.plan, tuned_mp, model, mesh, cifar.IN_SHAPE, 8 * n_data
        ))

    # Pipeline 1F1B entries (train/pipeline_schedule.py): the (stage,
    # data) mesh's fwd/bwd stage wires are full-cycle ppermute rings
    # fired EVERY tick — ring coverage is checked per axis, and the cost
    # accountant pins the tick count 2(M+S-1) exactly.  A small model
    # keeps the unrolled tick-loop trace cheap; the rules don't care
    # about layer count.  pipe4 sends the stage wire in bf16 — legal
    # (activations/cotangents, not masters), and a regression guard that
    # the f32-wire rule doesn't misfire through the tick switch.
    if n_dev >= 8 and n_dev % 4 == 0:
        from parallel_cnn_tpu.config import PipelineConfig
        from parallel_cnn_tpu.nn import layers as nn_layers
        from parallel_cnn_tpu.nn.core import Sequential
        from parallel_cnn_tpu.parallel import pipeline as pipe_lib
        from parallel_cnn_tpu.train import pipeline_schedule

        pmodel = Sequential([
            nn_layers.Conv2D(4, (3, 3)), nn_layers.ReLU(),
            nn_layers.MaxPool(), nn_layers.Flatten(), nn_layers.Dense(10),
        ])
        pin_shape = (8, 8, 3)
        ring_f32 = CommConfig(impl="ring")
        for tag, n_stage, stage_wire in (
            ("pipe2_ring", 2, "float32"),
            ("pipe4_ring", 4, "bfloat16"),
        ):
            n_pdata = n_dev // n_stage
            pmesh = mesh_lib.make_pipeline_mesh(n_stage)  # graftcheck: disable=mesh-outside-plan -- analyzer-internal synthetic trace mesh, not an execution path
            pcfg = PipelineConfig(stages=n_stage, wire_dtype=stage_wire)
            popt = zoo.make_optimizer(0.01, momentum=0.9)
            pst = zoo.init_state(pmodel, jax.random.key(1), pin_shape, popt)
            pstep = pipeline_schedule.make_pipeline_step(
                pmodel, popt, accum_steps=2, mesh=pmesh,
                pipeline=pcfg, in_shape=pin_shape, comm=ring_f32,
            )
            px = jnp.zeros((2 * n_pdata, *pin_shape), jnp.float32)
            py = jnp.zeros((2 * n_pdata,), jnp.int32)
            bounds, _, _ = pipeline_schedule.stage_plan(
                pmodel, pcfg, pin_shape
            )
            a_buf = pipe_lib.wire_numel(pmodel, pin_shape, bounds, 1)
            pplan = collectives.plan_buckets(
                pst.params, ring_f32.bucket_bytes, shards=n_pdata
            )
            w_stage = 2 if stage_wire == "bfloat16" else 4
            out.append((
                f"train.pipeline_step.{tag}",
                jax.make_jaxpr(pstep)(pst, px, py),
                EntrySpec(
                    kind="pipeline_ring", n_dev=n_pdata, n_host=1,
                    accum=2, wire_itemsize=4,
                    bucket_elems=tuple(pplan.bucket_sizes),
                    resident_bytes=_tree_bytes(pst),
                    act_bytes=_activation_hwm(
                        pmodel, pst.params, pst.model_state, 1,
                        pin_shape, 4
                    ),
                    images_per_step=2 * n_pdata,
                    n_state_leaves=len(jax.tree_util.tree_leaves(pst)),
                    n_stage=n_stage, pipe_micro=2,
                    stage_payload_bytes=1 * a_buf * w_stage,
                    stash_bytes=n_stage * 1 * a_buf * 4,
                ),
            ))

        # stages=1 degenerate twin: the same make_pipeline_step surface
        # delegating to the flat data-ring step — traced so the
        # degenerate path stays clean under every rule, like any entry.
        pmesh1 = mesh_lib.make_pipeline_mesh(1)  # graftcheck: disable=mesh-outside-plan -- analyzer-internal synthetic trace mesh, not an execution path
        popt = zoo.make_optimizer(0.01, momentum=0.9)
        pst1 = zoo.init_state(pmodel, jax.random.key(1), pin_shape, popt)
        pstep1 = pipeline_schedule.make_pipeline_step(
            pmodel, popt, accum_steps=2, mesh=pmesh1,
            pipeline=PipelineConfig(stages=1), in_shape=pin_shape,
            comm=ring_f32,
        )
        px1 = jnp.zeros((2 * n_dev, *pin_shape), jnp.float32)
        py1 = jnp.zeros((2 * n_dev,), jnp.int32)
        out.append((
            "train.pipeline_step.pipe1_degenerate",
            jax.make_jaxpr(pstep1)(pst1, px1, py1),
            None,
        ))

    # Async EASGD round (train/async_dp.py): the device-resident elastic
    # pull/push over the data axis — center shards rematerialized with a
    # ring all-gather, worker deltas pushed back with a ring
    # reduce-scatter.  The center is master state (same contract as the
    # ZeRO-3 param gathers), so both rings must carry f32 on the wire
    # and cover the axis with a single cycle.
    from jax.sharding import PartitionSpec as P
    from parallel_cnn_tpu.train import async_dp

    shard_len = 64
    awf = jnp.zeros((n_data, n_data * shard_len), jnp.float32)
    acs = jnp.zeros((n_data, shard_len), jnp.float32)

    def _easgd_body(wf, cs):
        new_w, new_c = async_dp.easgd_round_sharded(
            wf[0], cs[0], jnp.float32(0.5),
            axis_name="data", axis_size=n_data,
        )
        return new_w[None], new_c[None]

    easgd_round = jax.shard_map(
        _easgd_body, mesh=mesh,
        in_specs=(P("data", None), P("data", None)),
        out_specs=(P("data", None), P("data", None)),
        # ppermute outputs are per-device values the replication checker
        # cannot prove replicated — same waiver as every ring caller.
        check_vma=False,
    )
    out.append((
        "train.easgd_round",
        jax.make_jaxpr(easgd_round)(awf, acs),
        None,
    ))

    # Hierarchical two-level rings need a (host, device) mesh; 2 emulated
    # hosts over the local devices exercises every per-axis ppermute the
    # multi-host path emits (ring coverage is checked per axis).
    if n_dev >= 4 and n_dev % 2 == 0:
        hmesh = mesh_lib.make_hier_mesh(n_hosts=2, devices=jax.devices()[:n_dev])  # graftcheck: disable=mesh-outside-plan -- analyzer-internal synthetic trace mesh, not an execution path
        n_host, n_hdev = mesh_lib.hier_axis_sizes(hmesh)
        hx = jnp.zeros((2 * n_dev, *cifar.IN_SHAPE), jnp.float32)
        hy = jnp.zeros((2 * n_dev,), jnp.int32)
        with hmesh:
            hier_bf16 = CommConfig(
                impl="hierarchical", wire_dtype="bfloat16", hosts=2
            )
            opt = zoo.make_optimizer(0.01, momentum=0.9)
            hier_step = zoo.make_train_step(
                model, opt, accum_steps=2, mesh=hmesh, comm=hier_bf16
            )
            hst = zoo.init_state(model, jax.random.key(1), cifar.IN_SHAPE, opt)
            hplan = collectives.plan_buckets(
                hst.params, hier_bf16.bucket_bytes, shards=n_dev
            )
            out.append((
                "zoo.comm_step.hier_bf16",
                jax.make_jaxpr(hier_step)(hst, hx, hy),
                EntrySpec(
                    kind="hier_overlap", n_dev=n_hdev, n_host=n_host,
                    accum=2, wire_itemsize=2,
                    bucket_elems=tuple(hplan.bucket_sizes),
                    resident_bytes=_tree_bytes(hst),
                    act_bytes=_activation_hwm(
                        model, hst.params, hst.model_state, 1,
                        cifar.IN_SHAPE, 4
                    ),
                    images_per_step=2 * n_dev,
                    n_state_leaves=len(jax.tree_util.tree_leaves(hst)),
                ),
            ))

            z3h = FusedStepConfig(
                update=True, tail=True, act_dtype="bfloat16", zero=3
            )
            zsth, zplanh = zoo.init_zero3_state(
                model, jax.random.key(1), cifar.IN_SHAPE,
                n_data=n_hdev, fused=z3h,
                bucket_bytes=hier_bf16.bucket_bytes, n_host=n_host,
            )
            zero3_hier = zoo.make_zero3_train_step(
                model, lr=0.01, momentum=0.9, accum_steps=2, mesh=hmesh,
                augment=None, comm=hier_bf16, fused=z3h, plan=zplanh,
            )
            zhsharded = (
                _tree_bytes(zsth.params) + _tree_bytes(zsth.opt_state.mom)
            )
            out.append((
                "zoo.zero3_step.hier_bf16",
                jax.make_jaxpr(zero3_hier)(zsth, hx, hy),
                EntrySpec(
                    kind="zero3_hier", n_dev=n_hdev, n_host=n_host,
                    accum=2, wire_itemsize=2,
                    bucket_elems=tuple(zplanh.bucket_sizes),
                    resident_bytes=(
                        _tree_bytes(zsth) - zhsharded + zhsharded // n_dev
                    ),
                    act_bytes=_activation_hwm(
                        model, zoo.zero3_full_params(zsth, zplanh, n_host=n_host),
                        zsth.model_state, 1, cifar.IN_SHAPE, 2
                    ),
                    images_per_step=2 * n_dev,
                    n_state_leaves=len(jax.tree_util.tree_leaves(zsth)),
                    transient_gather_bytes=sum(zplanh.bucket_sizes) * 4,
                ),
            ))

    # Elastic post-resize entry (resilience/elastic.py): the step the
    # trainer recompiles AFTER an in-flight shrink — the live ZeRO-3
    # state round-tripped through zero3_full_view → zero3_from_view onto
    # half the devices. The resharded step must satisfy every invariant
    # a from-scratch step does (f32-master head gathers included): a
    # reshard that smuggled a bf16 master or broke ring coverage would
    # surface here, not at 3am on a preempted pod.
    if n_dev >= 4 and n_dev % 2 == 0:
        half = n_dev // 2
        smesh = mesh_lib.make_elastic_mesh(half, devices=jax.devices())  # graftcheck: disable=mesh-outside-plan -- analyzer-internal synthetic reshard trace, not an execution path
        view = zoo.zero3_full_view(zst, zplan)
        rst, rplan = zoo.zero3_from_view(
            view, n_data=half, bucket_bytes=ring_bf16.bucket_bytes
        )
        with smesh:
            resize_step = zoo.make_zero3_train_step(
                model, lr=0.01, momentum=0.9, accum_steps=2, mesh=smesh,
                augment=None, comm=ring_bf16, fused=z3, plan=rplan,
            )
            rx = jnp.zeros((2 * half, *cifar.IN_SHAPE), jnp.float32)
            ry = jnp.zeros((2 * half,), jnp.int32)
            out.append((
                "zoo.zero3_step.post_resize",
                jax.make_jaxpr(resize_step)(rst, rx, ry),
                None,
            ))
    return _finish(out)


def run_jaxpr_rules(fast: bool = False) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for name, closed in trace_entry_points(fast=fast):
        diags.extend(analyze_closed_jaxpr(name, closed))
    return diags
