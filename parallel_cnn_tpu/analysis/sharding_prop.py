"""Sharding-propagation verifier (graftcheck family 5).

Abstract interpretation over the ClosedJaxprs from
``jaxpr_rules.trace_entry_points(with_specs=True)``: each ``shard_map``
equation's ``in_specs``/``out_specs`` declare, per operand and per array
dimension, which mesh axes the value is split over — everything NOT named
is replicated over that axis.  Propagating the replicated-axes set
through the body gives every intermediate an inferred PartitionSpec,
which three rules check:

- ``implicit-reshard`` (error): a ZooState leaf that ENTERS the step
  sharded (its ``in_specs`` entry names mesh axes) must EXIT sharded —
  state leaves map 1:1 between ``in_specs`` and ``out_specs`` because the
  step returns ``(new_state, loss)`` with the state treedef preserved.
  A sharded-in / replicated-out leaf means a ZeRO resident shard was
  gathered and HANDED BACK replicated: GSPMD will silently materialize
  the full tensor on every device from the next step on, the exact
  regression the just-in-time gather window exists to prevent.
- ``sharding-contradiction`` (error): a ``psum``-family reduction or a
  ``ppermute`` over a mesh axis its operand is already replicated over.
  Reducing a replicated value multiplies it by the axis size (the classic
  double-psum bug); permuting one moves bytes that are identical on every
  rank.  Propagation is conservative: unknown primitives intersect their
  operands' replicated sets (any deterministic op of replicated inputs is
  replicated), ``axis_index`` varies over its axis, control-flow bodies
  (scan/while/cond) are treated as varying everywhere — so a reported
  contradiction is structural, not a propagation artifact.
- ``replicated-footprint`` (warning): an intermediate replicated over
  EVERY mesh axis whose footprint is ≥ 8 MiB — its replicated footprint
  exceeds its sharded one by the full mesh factor.  Warning severity:
  jaxpr pseudo-files cannot carry inline waivers, and transient gathers
  (ZeRO-3's step-head window) are legitimate; the gate is the cost
  accountant's peak-HBM ratchet, this is the pointer to the tensor.
"""

from __future__ import annotations

from typing import Dict, FrozenSet, List, Optional, Tuple

import numpy as np

from parallel_cnn_tpu.analysis.diagnostics import Diagnostic, Severity
from parallel_cnn_tpu.analysis.jaxpr_rules import EntrySpec, _sub_jaxprs

REPLICATED_FOOTPRINT_BYTES = 8 * 1024 * 1024

# psum-family reductions: operands must vary over the reduced axis.
_REDUCE_PRIMS = {"psum", "pmax", "pmin", "all_gather", "reduce_scatter"}


def _var_key(v) -> Optional[int]:
    return id(v) if not hasattr(v, "val") else None


def _named_axes(spec) -> FrozenSet[str]:
    """Mesh axes one shard_map ``in_specs``/``out_specs`` entry (a
    PartitionSpec: per dim None, an axis name, or a tuple of them) splits
    an operand over."""
    axes = set()
    for dim in spec:
        if dim is None:
            continue
        axes.update((dim,) if isinstance(dim, str) else dim)
    return frozenset(axes)


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = ()
    for key in ("axis_name", "axes"):
        if key in eqn.params:
            v = eqn.params[key]
            if isinstance(v, str):
                axes += (v,)
            elif isinstance(v, (tuple, list)):
                axes += tuple(x for x in v if isinstance(x, str))
    return axes


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape)) * dtype.itemsize


def _propagate(body, init_repl: Dict[int, FrozenSet[str]],
               mesh_axes: FrozenSet[str], file: str,
               diags: List[Diagnostic]) -> None:
    """Walk one shard_map body propagating replicated-axes sets and
    emitting sharding-contradiction / replicated-footprint findings."""
    repl: Dict[int, FrozenSet[str]] = dict(init_repl)

    def get(v) -> FrozenSet[str]:
        k = _var_key(v)
        if k is None:          # literal: identical on every rank
            return mesh_axes
        return repl.get(k, frozenset())

    def walk(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            axes = _eqn_axes(eqn)
            op_repl = (
                frozenset.intersection(*(get(iv) for iv in eqn.invars))
                if eqn.invars else mesh_axes
            )
            if prim in _REDUCE_PRIMS or prim == "ppermute":
                dead = [a for a in axes if a in op_repl]
                if dead:
                    verb = (
                        "reduces over" if prim in _REDUCE_PRIMS
                        else "permutes over"
                    )
                    diags.append(Diagnostic(
                        rule="sharding-contradiction",
                        severity=Severity.ERROR,
                        file=file,
                        line=0,
                        message=(
                            f"{prim} {verb} axis {dead} but its operand "
                            "is replicated over that axis — the operand "
                            "sharding contradicts the collective's axis "
                            "(double-reduce scales by the axis size; a "
                            "permute of replicated data moves identical "
                            "bytes)"
                        ),
                    ))
            if prim in _REDUCE_PRIMS:
                out_repl = op_repl | frozenset(axes)
            elif prim == "axis_index":
                out_repl = mesh_axes - frozenset(axes)
            elif prim == "ppermute":
                out_repl = op_repl
            elif prim in ("scan", "while", "cond"):
                # Control flow may mix iteration state nonuniformly;
                # treat results as varying everywhere (conservative: can
                # only SUPPRESS downstream contradictions, never invent).
                out_repl = frozenset()
            else:
                out_repl = op_repl
            for ov in eqn.outvars:
                k = _var_key(ov)
                if k is not None:
                    repl[k] = out_repl
                if (out_repl == mesh_axes and len(mesh_axes) > 0
                        and _aval_bytes(ov) >= REPLICATED_FOOTPRINT_BYTES):
                    diags.append(Diagnostic(
                        rule="replicated-footprint",
                        severity=Severity.WARNING,
                        file=file,
                        line=0,
                        message=(
                            f"intermediate of {_aval_bytes(ov)} bytes is "
                            "replicated over every mesh axis; its "
                            "replicated footprint exceeds its sharded one "
                            f"by {np.prod([1])}× the mesh size — if this "
                            "is a deliberate gather window, keep it below "
                            "the peak-HBM ratchet"
                        ),
                    ))
            if prim == "pjit":
                # Direct-call semantics: operand specs flow 1:1 into the
                # callee and results flow back.
                for sub in _sub_jaxprs(eqn):
                    for sv, iv in zip(sub.invars, eqn.invars):
                        k = _var_key(sv)
                        if k is not None:
                            repl[k] = get(iv)
                    walk(sub)
                    for ov, sv in zip(eqn.outvars, sub.outvars):
                        k = _var_key(ov)
                        if k is not None:
                            repl[k] = get(sv)
            elif prim not in ("scan", "while", "cond"):
                for sub in _sub_jaxprs(eqn):
                    walk(sub)

    walk(body)


def _body_jaxpr(eqn):
    body = eqn.params.get("jaxpr")
    return getattr(body, "jaxpr", body)  # ClosedJaxpr or raw Jaxpr


def analyze_entry_sharding(
    name: str, closed, spec: Optional[EntrySpec]
) -> List[Diagnostic]:
    """Run the sharding rules over one traced entry point."""
    diags: List[Diagnostic] = []
    file = f"<jaxpr:{name}>"

    def find_shard_maps(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                yield eqn
            else:
                for sub in _sub_jaxprs(eqn):
                    yield from find_shard_maps(sub)

    for eqn in find_shard_maps(closed.jaxpr):
        mesh = eqn.params.get("mesh")
        mesh_axes = frozenset(getattr(mesh, "axis_names", ()) or ())
        in_specs = eqn.params["in_specs"]
        out_specs = eqn.params["out_specs"]
        body = _body_jaxpr(eqn)
        if body is None or not mesh_axes:
            continue

        # implicit-reshard: state leaves are the first n_state_leaves
        # positions on BOTH sides ((state, bx, by) -> (new_state, loss)
        # preserves the ZooState treedef).
        if spec is not None and len(in_specs) >= spec.n_state_leaves \
                and len(out_specs) >= spec.n_state_leaves:
            for i in range(spec.n_state_leaves):
                ins = _named_axes(in_specs[i])
                outs = _named_axes(out_specs[i])
                if ins and not outs:
                    diags.append(Diagnostic(
                        rule="implicit-reshard",
                        severity=Severity.ERROR,
                        file=file,
                        line=0,
                        message=(
                            f"state leaf {i} enters the step sharded over "
                            f"{sorted(ins)} but exits fully replicated — "
                            "a resident shard was gathered outside the "
                            "declared just-in-time window and handed back "
                            "whole; every device now materializes the "
                            "full tensor permanently"
                        ),
                    ))

        init_repl: Dict[int, FrozenSet[str]] = {}
        for v, names in zip(body.invars, in_specs):
            k = _var_key(v)
            if k is not None:
                init_repl[k] = mesh_axes - _named_axes(names)
        _propagate(body, init_repl, mesh_axes, file, diags)

    return diags


def run_sharding_rules(
    entries: List[Tuple[str, object, Optional[EntrySpec]]]
) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for name, closed, spec in entries:
        diags.extend(analyze_entry_sharding(name, closed, spec))
    return diags
