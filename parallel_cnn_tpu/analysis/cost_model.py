"""Static comm-volume / HBM / flops accountant (graftcheck family 6).

Walks the ClosedJaxprs that ``jaxpr_rules.trace_entry_points`` already
produces and counts, per entry point and per device:

- **collective bytes**, split by mesh axis: every ``ppermute`` hop's
  payload (outvar numel × itemsize) is attributed to the inter-host DCN
  when the hop permutes the ``host`` axis and to the intra-host ICI
  otherwise (hops inside ``scan``/``while`` bodies are multiplied by the
  trip count);
- **flops** from ``dot_general`` / ``conv_general_dilated`` equations
  (informational — the roofline numerator);
- **peak resident bytes per step**: the EntrySpec's declared-sharding
  state residency (params/momentum scaled by the ZeRO level) + the
  per-layer ``eval_shape`` activation high-water mark + the 1/n gradient
  shard accumulators.  ZeRO-3's transient head-gather is reported
  separately (``transient_gather_bytes``) — it is freed before backward,
  so it is not resident across the step.

The measured ppermute byte counts are then asserted EQUAL (exact integer
equality, no tolerance) to the closed-form models in the per-impl byte
tables of docs/collectives.md — rule ``cost-model-mismatch``.  The same
rule pins the ZeRO residency ordering peak_hbm(zero3) < peak_hbm(zero2)
< peak_hbm(replicated) on the flat-ring entries.

Every ``check --cost`` run emits ``analysis/cost_report.json`` (bytes_ici,
bytes_dcn, peak_hbm, flops, analytic roofline img/s per entry) and
ratchets against ``analysis/cost_baseline.json``: an entry whose DCN
bytes or peak HBM grew past its baselined value fails check — rule
``cost-ratchet`` (missing entries pass; ``--update-cost-baseline``
rewrites the file from the current tree).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from parallel_cnn_tpu.analysis import hw_profiles
from parallel_cnn_tpu.analysis.diagnostics import Diagnostic, Severity
from parallel_cnn_tpu.analysis.jaxpr_rules import EntrySpec, _sub_jaxprs

_ANALYSIS_DIR = Path(__file__).resolve().parent
DEFAULT_COST_BASELINE = _ANALYSIS_DIR / "cost_baseline.json"
DEFAULT_COST_REPORT = _ANALYSIS_DIR / "cost_report.json"

HOST_AXIS_NAME = "host"  # parallel/mesh.py HOST_AXIS — DCN hops

# Analytic roofline constants — resolved from analysis/hw_profiles.py
# (PCNN_HW_PROFILE picks the chip; the default ``v5e-8`` row is
# byte-identical to the historically inline numbers, so existing reports
# are stable).  The module-level aliases pin the DEFAULT profile for code
# that wants the fixed yardstick; the live roofline + report read the
# *active* profile so one env var re-derives everything.
_DEFAULT_HW = hw_profiles.get_profile(hw_profiles.DEFAULT_PROFILE)
PEAK_FLOPS = _DEFAULT_HW.peak_flops          # flop/s
ICI_BYTES_PER_S = _DEFAULT_HW.ici_bytes_per_s  # bytes/s
DCN_BYTES_PER_S = _DEFAULT_HW.dcn_bytes_per_s  # bytes/s


# ---------------------------------------------------------------------------
# Measured side: jaxpr walks
# ---------------------------------------------------------------------------

def _loop_trips(eqn) -> int:
    """Static trip count of a scan/while equation (1 when unknowable —
    while loops have no static bound; the zoo steps unroll their
    microbatch loops so this stays exact for every traced entry)."""
    if eqn.primitive.name == "scan":
        return int(eqn.params.get("length", 1))
    return 1


def measured_collective_bytes(closed) -> Tuple[int, int]:
    """(bytes_ici, bytes_dcn) moved by one step, per device.

    Sums every ``ppermute`` payload: each hop sends its full outvar from
    every device simultaneously, so the per-device byte count is exactly
    the outvar footprint.  ``host``-axis permutes ride the DCN; any other
    axis rides the ICI.
    """
    ici = 0
    dcn = 0

    def walk(jaxpr, mult: int) -> None:
        nonlocal ici, dcn
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "ppermute":
                axes = eqn.params.get("axis_name", ())
                if isinstance(axes, str):
                    axes = (axes,)
                nbytes = sum(
                    int(np.prod(ov.aval.shape)) * ov.aval.dtype.itemsize
                    for ov in eqn.outvars
                )
                if HOST_AXIS_NAME in axes:
                    dcn += mult * nbytes
                else:
                    ici += mult * nbytes
            sub_mult = mult * _loop_trips(eqn)
            for sub in _sub_jaxprs(eqn):
                walk(sub, sub_mult)

    walk(closed.jaxpr, 1)
    return ici, dcn


def measured_flops(closed) -> int:
    """Multiply-add flops of the matmul/conv equations (2 × MACs).

    Informational (roofline numerator): elementwise and reduction flops
    are ignored — for conv nets the contraction terms dominate.
    """
    total = 0

    def walk(jaxpr, mult: int) -> None:
        nonlocal total
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "dot_general":
                ((lc, _), _) = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval
                out = eqn.outvars[0].aval
                contract = int(np.prod([lhs.shape[d] for d in lc]))
                total += mult * 2 * int(np.prod(out.shape)) * contract
            elif prim == "conv_general_dilated":
                rhs = eqn.invars[1].aval
                out = eqn.outvars[0].aval
                groups = int(eqn.params.get("feature_group_count", 1))
                # rhs is (spatial..., cin/groups, cout) post-dnums; the
                # product over all dims but cout is the per-output MAC
                # count regardless of layout.
                macs_per_out = int(np.prod(rhs.shape)) // max(
                    int(rhs.shape[-1]), 1
                )
                total += mult * 2 * int(np.prod(out.shape)) * macs_per_out
            sub_mult = mult * _loop_trips(eqn)
            for sub in _sub_jaxprs(eqn):
                walk(sub, sub_mult)

    walk(closed.jaxpr, 1)
    return total


# ---------------------------------------------------------------------------
# Analytic side: the closed-form byte tables (docs/collectives.md)
# ---------------------------------------------------------------------------

def expected_collective_bytes(spec: EntrySpec) -> Tuple[int, int]:
    """(bytes_ici, bytes_dcn) per device from the closed-form models.

    Per bucket of E padded elements on a D-device ring (H-host ring above
    it), one reduce-scatter or all-gather pass moves (D−1)·E/D elements on
    the device axis and (H−1)·E/(D·H) on the host axis.  With K grad-
    accumulation microbatches, w the gradient wire itemsize and 4 the f32
    master itemsize (docs/collectives.md "Exact per-impl byte tables"):

    - ring_overlap:  ICI (K+1)·(D−1)·E/D·w            (K RS + 1 grad AG)
    - hier_overlap:  ICI as ring; DCN (K+1)·(H−1)·E/(D·H)·w
    - ring_post:     ICI 2·(D−1)·E/D·w — overlap=False: ONE post-
      accumulation ring all-reduce (RS+AG), K-independent
    - hier_post:     ICI as ring_post; DCN 2·(H−1)·E/(D·H)·w
    - zero2_ring:    ICI K·(D−1)·E/D·w + (D−1)·E/D·4  (param AG f32)
    - zero3_ring:    identical to zero2_ring (head gather instead of tail)
    - zero3_hier:    ICI as zero2; DCN K·(H−1)·E/(D·H)·w + (H−1)·E/(D·H)·4
    - pipeline_ring: ICI 2·2(M+S−1)·P + 2·(D−1)·E/D·w — the 1F1B stage
      wires (2 full-cycle ppermutes per tick × T = 2(M+S−1) ticks, each
      carrying the uniform P = mb·A_buf·w_stage payload, docs/pipeline.md)
      plus ONE post-accumulation grad ring all-reduce (RS+AG) over the
      data axis
    """
    k, d, h, w = spec.accum, spec.n_dev, spec.n_host, spec.wire_itemsize
    ici = 0
    dcn = 0
    for e in spec.bucket_elems:
        dev_pass = (d - 1) * (e // d)
        host_pass = (h - 1) * (e // (d * h))
        if spec.kind == "ring_overlap":
            ici += (k + 1) * dev_pass * w
        elif spec.kind == "hier_overlap":
            ici += (k + 1) * dev_pass * w
            dcn += (k + 1) * host_pass * w
        elif spec.kind == "ring_post":
            ici += 2 * dev_pass * w
        elif spec.kind == "hier_post":
            ici += 2 * dev_pass * w
            dcn += 2 * host_pass * w
        elif spec.kind in ("zero2_ring", "zero3_ring"):
            ici += k * dev_pass * w + dev_pass * 4
        elif spec.kind == "zero3_hier":
            ici += k * dev_pass * w + dev_pass * 4
            dcn += k * host_pass * w + host_pass * 4
        elif spec.kind == "pipeline_ring":
            ici += 2 * dev_pass * w
        else:
            raise ValueError(f"unknown cost kind {spec.kind!r}")
    if spec.kind == "pipeline_ring":
        ticks = 2 * (spec.pipe_micro + spec.n_stage - 1)
        ici += 2 * ticks * spec.stage_payload_bytes
    return ici, dcn


def peak_hbm_bytes(spec: EntrySpec) -> int:
    """Peak resident bytes per device per step: declared-sharding state
    residency + activation high-water mark + the f32 1/n gradient shard
    accumulators every schedule keeps across microbatches."""
    shards = spec.n_dev * spec.n_host
    if spec.kind == "pipeline_ring":
        # The 1F1B step accumulates the FULL per-stage grad tree (the
        # stage psum adds exact zeros, so the accumulator spans every
        # bucket) and keeps the f32 activation stash live across the
        # whole tick loop.
        grad_accum = sum(spec.bucket_elems) * 4
        return (spec.resident_bytes + spec.act_bytes + grad_accum
                + spec.stash_bytes)
    grad_accum = sum(e // shards for e in spec.bucket_elems) * 4
    return spec.resident_bytes + spec.act_bytes + grad_accum


def roofline_img_s(spec: EntrySpec, flops: int,
                   ici: int, dcn: int,
                   hw: Optional[hw_profiles.HwProfile] = None) -> float:
    """Analytic images/s: the step's global batch over the slowest of the
    compute, ICI, and DCN terms (each device computes flops/shards).
    ``hw`` defaults to the active ``PCNN_HW_PROFILE`` profile."""
    hw = hw or hw_profiles.active_profile()
    shards = spec.n_dev * spec.n_host
    t_compute = (flops / max(shards, 1)) / hw.peak_flops
    t_ici = ici / hw.ici_bytes_per_s
    t_dcn = dcn / hw.dcn_bytes_per_s
    t = max(t_compute, t_ici, t_dcn)
    return spec.images_per_step / t if t > 0 else float("inf")


# ---------------------------------------------------------------------------
# Seeded mutant (anti-vacuity: check --cost-seeded must exit non-zero)
# ---------------------------------------------------------------------------

def build_seeded_entry(name: str):
    """A really-traced mutant entry that a correct gate must reject.

    ``bf16-master-gather``: resident f32 state shards all-gathered over a
    bf16 wire — masters riding bf16.  Its EntrySpec pins the f32 all-
    gather the schedule is REQUIRED to use (kind zero3_ring, accum 0), so
    the measured bf16 hop bytes contradict the closed form
    (cost-model-mismatch) on top of the f32-wire jaxpr rule.

    ``partial-stage-ring``: a stage-axis ppermute whose permutation stops
    one hop short of the cycle — the last stage's cotangent never reaches
    stage 0.  Trips ``ring-permutation`` (not a single full cycle) and,
    because its EntrySpec pins the full-ring 1F1B closed form
    (kind pipeline_ring), ``cost-model-mismatch`` as well.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from parallel_cnn_tpu.config import MeshConfig
    from parallel_cnn_tpu.parallel import collectives
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.parallel.mesh import DATA_AXIS

    if name == "partial-stage-ring":
        from parallel_cnn_tpu.parallel.mesh import (
            DATA_AXIS as _DA, STAGE_AXIS, make_pipeline_mesh,
        )

        n = len(jax.devices())
        n_stage = 2
        pmesh = make_pipeline_mesh(n_stage)  # graftcheck: disable=mesh-outside-plan -- seeded-mutant trace mesh (anti-vacuity probe), not an execution path
        a_buf = 256

        def pbody(buf):
            # One hop short of the cycle: stage S-1 sends to nobody.
            perm = [(i, i + 1) for i in range(n_stage - 1)]
            out = jax.lax.ppermute(buf, STAGE_AXIS, perm)
            return jax.lax.pmean(out, (_DA, STAGE_AXIS))

        step = jax.shard_map(
            pbody, mesh=pmesh, in_specs=(P(),), out_specs=P(),
            check_vma=False,
        )
        closed = jax.make_jaxpr(step)(jnp.zeros((1, a_buf), jnp.float32))
        spec = EntrySpec(
            kind="pipeline_ring", n_dev=n // n_stage, n_host=1, accum=2,
            wire_itemsize=4, bucket_elems=(a_buf,),
            resident_bytes=a_buf * 4, act_bytes=0, images_per_step=1,
            n_state_leaves=1, n_stage=n_stage, pipe_micro=2,
            stage_payload_bytes=a_buf * 4,
            stash_bytes=n_stage * a_buf * 4,
        )
        return (f"seeded.{name}", closed, spec)
    if name != "bf16-master-gather":
        raise ValueError(f"unknown seeded mutation {name!r}")
    n = len(jax.devices())
    mesh = mesh_lib.make_mesh(MeshConfig(data=n, model=1))  # graftcheck: disable=mesh-outside-plan -- seeded-mutant trace mesh (anti-vacuity probe), not an execution path
    elems = 1024 * n

    def body(shard):
        return collectives.ring_all_gather(
            shard, DATA_AXIS, n, "bfloat16"
        )

    step = jax.shard_map(
        body, mesh=mesh, in_specs=(P(DATA_AXIS),), out_specs=P(),
        check_vma=False,
    )
    closed = jax.make_jaxpr(step)(jnp.zeros((elems,), jnp.float32))
    spec = EntrySpec(
        kind="zero3_ring", n_dev=n, n_host=1, accum=0, wire_itemsize=2,
        bucket_elems=(elems,), resident_bytes=elems * 4 // n,
        act_bytes=0, images_per_step=1, n_state_leaves=1,
        transient_gather_bytes=elems * 4,
    )
    return (f"seeded.{name}", closed, spec)


# ---------------------------------------------------------------------------
# Baseline ratchet + report
# ---------------------------------------------------------------------------

COST_SCHEMA_VERSION = 1


class CostSchemaError(ValueError):
    """A cost artifact (baseline/report) carries the wrong schema version
    — refuse to compare keys that may mean something else."""


def _check_schema_version(data: Dict, path: Path) -> None:
    got = data.get("version")
    if got != COST_SCHEMA_VERSION:
        raise CostSchemaError(
            f"{Path(path).name}: schema version {got!r} != "
            f"{COST_SCHEMA_VERSION}; stale artifact — regenerate it "
            "(check --cost --update-cost-baseline, or `tune` for the "
            "autotune section) instead of silently comparing wrong keys"
        )


def load_cost_baseline(path: Path) -> Dict[str, Dict[str, int]]:
    """Ratchet baseline entries; missing file is an empty baseline, a
    version-mismatched file raises :class:`CostSchemaError` loudly."""
    if not Path(path).exists():
        return {}
    data = json.loads(Path(path).read_text())
    _check_schema_version(data, path)
    return dict(data.get("entries", {}))


def load_cost_report(path: Path) -> Dict:
    """The full cost report payload, schema-version checked (the
    ``--autotune`` consumer and capacity planner go through this)."""
    data = json.loads(Path(path).read_text())
    _check_schema_version(data, path)
    return data


def save_cost_baseline(path: Path, entries: Dict[str, Dict[str, int]]) -> None:
    payload = {"version": COST_SCHEMA_VERSION, "entries": entries}
    Path(path).write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def write_cost_report(path: Path, rows: Dict[str, Dict],
                      autotune: Optional[Dict] = None) -> None:
    """Write the report; an existing version-valid report's ``autotune``
    section is carried over unless a fresh one is passed in, so `check
    --cost` regeneration never clobbers the tuner's ranked table."""
    path = Path(path)
    if autotune is None and path.exists():
        try:
            prev = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            prev = {}
        if prev.get("version") == COST_SCHEMA_VERSION:
            autotune = prev.get("autotune")
    hw = hw_profiles.active_profile()
    payload = {
        "version": COST_SCHEMA_VERSION,
        "constants": {
            "hw_profile": hw.name,
            "peak_flops": hw.peak_flops,
            "ici_bytes_per_s": hw.ici_bytes_per_s,
            "dcn_bytes_per_s": hw.dcn_bytes_per_s,
        },
        "entries": rows,
    }
    if autotune is not None:
        payload["autotune"] = autotune
    path.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")


def entry_costs(name: str, closed, spec: Optional[EntrySpec]) -> Dict:
    """The cost-report row for one traced entry (measured + analytic)."""
    ici, dcn = measured_collective_bytes(closed)
    flops = measured_flops(closed)
    row = {
        "bytes_ici": ici,
        "bytes_dcn": dcn,
        "flops": flops,
    }
    if spec is not None:
        exp_ici, exp_dcn = expected_collective_bytes(spec)
        row.update(
            kind=spec.kind,
            expected_bytes_ici=exp_ici,
            expected_bytes_dcn=exp_dcn,
            peak_hbm=peak_hbm_bytes(spec),
            transient_gather_bytes=spec.transient_gather_bytes,
            roofline_img_s=round(roofline_img_s(spec, flops, ici, dcn), 1),
        )
    return row


def run_cost_rules(
    entries: List[Tuple[str, object, Optional[EntrySpec]]],
    *,
    baseline_path: Optional[Path] = None,
    update_baseline: bool = False,
    report_path: Optional[Path] = None,
) -> List[Diagnostic]:
    """Family 6 over pre-traced (name, ClosedJaxpr, EntrySpec) entries.

    Emits cost-model-mismatch (measured ≠ closed-form, exact integers;
    ZeRO peak-HBM ordering) and cost-ratchet (DCN bytes / peak HBM grew
    past cost_baseline.json) diagnostics; writes cost_report.json; with
    ``update_baseline`` rewrites the baseline from the current tree.
    """
    baseline_path = Path(baseline_path or DEFAULT_COST_BASELINE)
    report_path = Path(report_path or DEFAULT_COST_REPORT)
    diags: List[Diagnostic] = []
    rows: Dict[str, Dict] = {}
    hbm: Dict[str, int] = {}

    for name, closed, spec in entries:
        row = entry_costs(name, closed, spec)
        rows[name] = row
        file = f"<jaxpr:{name}>"
        if spec is None:
            continue
        hbm[name] = row["peak_hbm"]
        for side in ("ici", "dcn"):
            got, want = row[f"bytes_{side}"], row[f"expected_bytes_{side}"]
            if got != want:
                diags.append(Diagnostic(
                    rule="cost-model-mismatch",
                    severity=Severity.ERROR,
                    file=file,
                    line=0,
                    message=(
                        f"measured {side.upper()} bytes {got} != closed-form "
                        f"{want} for kind {spec.kind} (K={spec.accum}, "
                        f"D={spec.n_dev}, H={spec.n_host}, w="
                        f"{spec.wire_itemsize}, buckets="
                        f"{list(spec.bucket_elems)}); the schedule moved "
                        "bytes the docs/collectives.md table does not "
                        "account for (or stopped moving bytes it must)"
                    ),
                ))

    # ZeRO residency ordering on the flat-ring entries of the same model:
    # zero3 < zero2 < replicated, the memory claim ZeRO exists to make.
    order = [
        "zoo.zero3_step.ring_bf16",
        "zoo.fused_step.ring_bf16",
        "zoo.comm_step.ring_bf16",
    ]
    if all(n in hbm for n in order):
        for lo, hi in zip(order, order[1:]):
            if not hbm[lo] < hbm[hi]:
                diags.append(Diagnostic(
                    rule="cost-model-mismatch",
                    severity=Severity.ERROR,
                    file=f"<jaxpr:{lo}>",
                    line=0,
                    message=(
                        f"peak HBM ordering violated: {lo} ({hbm[lo]} B) "
                        f"must stay below {hi} ({hbm[hi]} B) — the ZeRO "
                        "level is not reducing residency"
                    ),
                ))

    try:
        baseline = load_cost_baseline(baseline_path)
    except CostSchemaError as exc:
        diags.append(Diagnostic(
            rule="cost-ratchet",
            severity=Severity.ERROR,
            file=str(baseline_path),
            line=0,
            message=str(exc),
        ))
        baseline = {}
    for name, row in rows.items():
        base = baseline.get(name)
        if not base:
            continue
        for key in ("bytes_dcn", "peak_hbm"):
            got = row.get(key)
            limit = base.get(key)
            if got is None or limit is None:
                continue
            if got > limit:
                diags.append(Diagnostic(
                    rule="cost-ratchet",
                    severity=Severity.ERROR,
                    file=f"<jaxpr:{name}>",
                    line=0,
                    message=(
                        f"{key} grew to {got} past the ratchet baseline "
                        f"{limit} ({baseline_path.name}); comm-volume and "
                        "memory regressions fail check — if intentional, "
                        "re-baseline with --update-cost-baseline"
                    ),
                ))

    if update_baseline:
        save_cost_baseline(baseline_path, {
            name: {
                "bytes_dcn": row["bytes_dcn"],
                "peak_hbm": row["peak_hbm"],
            }
            for name, row in rows.items()
            if "peak_hbm" in row
        })

    write_cost_report(report_path, rows)
    return diags
