"""ExecutionPlan subsystem tests (parallel_cnn_tpu/plan/).

The contract under test:

- **Round-trip byte-stability** — ``save(load(s))`` reproduces ``s``
  exactly; a schema-version mismatch, unknown field, or tampered
  fingerprint is a typed :class:`PlanSchemaError`, never a guess.
- **Provenance layering** — flag > env > autotune > default, decided
  per knob at the single resolution site (:func:`plan.build_plan`).
- **Legality matrix** — the checks that used to live as ad-hoc cli.py
  argument guards, now typed :class:`PlanLegalityError` for every
  consumer (CLI, plan files, tune hand-off, elastic derivation).
- **derive_resized equality** — resizing back to an already-seen world
  yields an EQUAL plan (same fingerprint), which is exactly what gates
  the elastic recompile-once step cache in zoo.train (journaled as
  ``plan_step_cache`` hit/miss).
- **Checkpoint refusal** — restore refuses a file stamped with a
  different plan fingerprint, naming BOTH fingerprints; ``--replan``
  (and the elastic reshard path) waive the check; pre-plan files load.
- **tune hand-off** — a ``tune --report`` artifact loads as a valid
  ExecutionPlan through :func:`plan.load_plan`, embedded-doc and
  legacy autotune-section formats both.
- **mesh-outside-plan** — the graftcheck rule that pins
  ``plan.make_mesh`` as the one mesh-construction site outside
  ``parallel/mesh.py``: rogue constructors are flagged, the sanctioned
  plan method is not, and waivers with a reason are honored.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from parallel_cnn_tpu import plan as plan_lib
from parallel_cnn_tpu.config import Config
from parallel_cnn_tpu.plan import (
    ExecutionPlan,
    PlanLegalityError,
    PlanMismatchError,
    PlanSchemaError,
    build_plan,
    derive_resized,
    diff_plans,
    load_plan,
    save_plan,
)

pytestmark = pytest.mark.plan


def _ring_zero3_plan(data=8):
    return ExecutionPlan(
        data=data, comm_impl="ring", bucket_bytes=2048, overlap=True,
        zero=3, fused=True, fused_update=True, act_dtype="float32",
        accum=2, param_sharding="zero3", opt_sharding="zero3",
    )


# -- serialization: byte-stable round trip + typed schema refusals ------


def test_roundtrip_byte_stable(tmp_path):
    plan = _ring_zero3_plan()
    s = plan.to_json()
    loaded = ExecutionPlan.from_json_dict(json.loads(s))
    assert loaded == plan
    assert loaded.fingerprint() == plan.fingerprint()
    assert loaded.to_json() == s  # save(load(s)) == s, byte for byte

    p = tmp_path / "plan.json"
    save_plan(p, plan)
    assert load_plan(p) == plan
    save_plan(tmp_path / "again.json", load_plan(p))
    assert (tmp_path / "again.json").read_bytes() == p.read_bytes()


def test_fingerprint_ignores_provenance():
    bare = _ring_zero3_plan()
    labeled = dataclasses.replace(
        bare, provenance=(("comm_impl", "flag"), ("zero", "env"))
    )
    assert labeled == bare
    assert labeled.fingerprint() == bare.fingerprint()
    assert hash(labeled) == hash(bare)
    # ...but any identity field shifts it.
    assert dataclasses.replace(bare, accum=4).fingerprint() \
        != bare.fingerprint()


def test_schema_version_rejected():
    doc = _ring_zero3_plan().to_json_dict()
    with pytest.raises(PlanSchemaError, match="schema version"):
        ExecutionPlan.from_json_dict({**doc, "version": 99})
    with pytest.raises(PlanSchemaError, match="schema version"):
        ExecutionPlan.from_json_dict({k: v for k, v in doc.items()
                                      if k != "version"})


def test_unknown_field_and_tamper_rejected(tmp_path):
    doc = _ring_zero3_plan().to_json_dict()
    bad = {**doc, "plan": {**doc["plan"], "warp_drive": True}}
    with pytest.raises(PlanSchemaError, match="warp_drive"):
        ExecutionPlan.from_json_dict(bad)
    # Hand-edited field under a stale fingerprint: typed refusal.
    torn = {**doc, "plan": {**doc["plan"], "accum": 16}}
    with pytest.raises(PlanSchemaError, match="fingerprint"):
        ExecutionPlan.from_json_dict(torn)
    p = tmp_path / "not_json.json"
    p.write_text("{nope")
    with pytest.raises(PlanSchemaError, match="not JSON"):
        load_plan(p)


# -- provenance layering: flag > env > autotune > default ---------------


class _Args:
    """argparse-namespace stand-in; store_true flags default False,
    value flags None — the same sentinels cli.py's parser produces."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def test_provenance_layering_field_by_field(monkeypatch):
    from parallel_cnn_tpu.config import CommConfig
    from parallel_cnn_tpu.plan import _KNOB_SOURCES

    cfg = Config().replace(comm=CommConfig(impl="ring", wire_dtype="bfloat16"))

    # Layer 0: nothing set — every knob reads [default].
    for name in _KNOB_SOURCES:
        assert build_plan(cfg).provenance_of(name) == "default", name

    # Layer 1: env var present — exactly that knob flips to [env].
    monkeypatch.setenv("PCNN_COMM_WIRE_DTYPE", "bfloat16")
    plan = build_plan(cfg)
    assert plan.provenance_of("wire_dtype") == "env"
    for name in set(_KNOB_SOURCES) - {"wire_dtype"}:
        assert plan.provenance_of(name) == "default", name

    # Layer 2: a flag on the SAME knob beats the env var; an unset value
    # flag (None) and an unset store_true flag (False) do not.
    args = _Args(comm_wire_dtype="bfloat16", comm_impl=None,
                 fused_step=False)
    plan = build_plan(cfg, args)
    assert plan.provenance_of("wire_dtype") == "flag"
    assert plan.provenance_of("comm_impl") == "default"
    assert plan.provenance_of("fused") == "default"

    # Layer 3: an autotune-filled knob reads [autotune] even though the
    # tuner wrote the value back onto args (cli.config_from_args records
    # the fill only when neither flag nor env pinned the knob — the
    # membership itself is the proof the higher layers passed).
    args = _Args(comm_wire_dtype="bfloat16",
                 _autotune_filled=("wire_dtype",))
    assert build_plan(cfg, args).provenance_of("wire_dtype") == "autotune"
    assert build_plan(
        cfg, autotune_filled=("wire_dtype",)
    ).provenance_of("wire_dtype") == "autotune"


def test_build_plan_resolves_config_sections():
    from parallel_cnn_tpu.config import (
        CommConfig, FusedStepConfig, MeshConfig, PipelineConfig,
    )

    cfg = Config().replace(
        comm=CommConfig(impl="ring", bucket_bytes=2048,
                        wire_dtype="bfloat16", overlap=False),
        fused=FusedStepConfig(update=True, tail=True,
                              act_dtype="bfloat16", zero=3),
    )
    plan = build_plan(cfg)
    assert plan.comm_impl == "ring" and plan.bucket_bytes == 2048
    assert plan.wire_dtype == "bfloat16" and plan.overlap is False
    assert plan.zero == 3 and plan.fused and plan.fused_update
    # Sharding policy follows the partitioning mode deterministically.
    assert plan.param_sharding == "zero3" and plan.opt_sharding == "zero3"

    cfg2 = Config().replace(
        mesh=MeshConfig(data=4, model=2),
    )
    plan2 = build_plan(cfg2)
    assert plan2.data == 4 and plan2.model == 2
    assert plan2.param_sharding == "model"

    cfg3 = Config().replace(
        pipeline=PipelineConfig(stages=2, split="2",
                                wire_dtype="bfloat16"),
        comm=CommConfig(impl="ring"),
    )
    plan3 = build_plan(cfg3)
    assert plan3.pipelined and plan3.stages == 2
    assert plan3.pipe_wire_dtype == "bfloat16"
    assert plan3.cost_table_key() == ("train.pipeline_step.pipe2_ring",
                                      "pipeline_ring")


# -- legality matrix: typed errors, one site ----------------------------


def test_legality_matrix_typed_errors():
    with pytest.raises(PlanLegalityError, match="explicit mesh collective"):
        ExecutionPlan(comm_impl="ring").validate()
    with pytest.raises(PlanLegalityError, match="data-parallel only"):
        ExecutionPlan(comm_impl="ring", data=4, model=2).validate()
    with pytest.raises(PlanLegalityError, match="its own"):
        ExecutionPlan(stages=2, pipelined=True, data=4,
                      comm_impl="ring").validate()
    with pytest.raises(PlanLegalityError, match="flat data axis"):
        ExecutionPlan(stages=2, pipelined=True,
                      comm_impl="hierarchical", hosts=2).validate()
    with pytest.raises(PlanLegalityError, match="ZeRO-2 only"):
        ExecutionPlan(stages=2, pipelined=True, comm_impl="ring",
                      zero=3, fused=True, fused_update=True).validate()
    with pytest.raises(PlanLegalityError, match="host axis of >= 2"):
        ExecutionPlan(comm_impl="hierarchical", hosts=1).validate()
    with pytest.raises(PlanLegalityError, match="fused"):
        ExecutionPlan(data=4, comm_impl="ring", zero=2).validate()
    with pytest.raises(PlanLegalityError, match="rides the flat ring"):
        ExecutionPlan(comm_impl="hierarchical", hosts=2, zero=2,
                      fused=True, fused_update=True).validate()
    with pytest.raises(PlanLegalityError, match="model axis"):
        ExecutionPlan(param_sharding="model").validate()
    # validate() returns self so call sites can chain.
    plan = _ring_zero3_plan()
    assert plan.validate() is plan


def test_cost_table_key_mapping():
    assert ExecutionPlan().cost_table_key() == ("plan.resolved", None)
    assert _ring_zero3_plan().cost_table_key() == \
        ("zoo.zero3_step.ring_bf16", "zero3_ring")
    hier3 = dataclasses.replace(_ring_zero3_plan(),
                                comm_impl="hierarchical", hosts=2)
    assert hier3.cost_table_key() == ("zoo.zero3_step.hier_bf16",
                                      "zero3_hier")
    ring = ExecutionPlan(data=8, comm_impl="ring", overlap=False)
    assert ring.cost_table_key() == ("zoo.comm_step.ring_bf16",
                                     "ring_post")


# -- derive_resized: plan equality is the recompile-once gate -----------


def test_derive_resized_round_trip_equality():
    base = _ring_zero3_plan()
    d8 = derive_resized(base, 8)
    d4 = derive_resized(d8, 4)
    d8_again = derive_resized(d4, 8)
    assert d4 != d8
    assert d8_again == d8
    assert d8_again.fingerprint() == d8.fingerprint()
    assert d8.elastic and d8.world() == 8 and d4.world() == 4
    # Deriving from the ORIGINAL plan or an already-derived one lands on
    # the same contract — the cache key is history-independent.
    assert derive_resized(base, 4) == d4


def test_derive_resized_topology_decision():
    hier = ExecutionPlan(comm_impl="hierarchical", hosts=2, zero=3,
                         fused=True, fused_update=True)
    d8 = derive_resized(hier, 8)
    assert d8.comm_impl == "hierarchical" and d8.hosts == 2
    assert d8.data == 4 and d8.world() == 8
    # A world the host axis no longer divides falls back to the flat
    # ring — mirroring mesh.make_elastic_mesh exactly.
    d7 = derive_resized(hier, 7)
    assert d7.comm_impl == "ring" and d7.hosts is None and d7.data == 7
    assert d7.provenance_of("comm_impl") == "elastic"
    with pytest.raises(PlanLegalityError, match=">= 1"):
        derive_resized(hier, 0)
    with pytest.raises(PlanLegalityError, match="divisible"):
        derive_resized(hier, 7, n_hosts=2)


def test_diff_plans_names_fields_and_provenance():
    a = _ring_zero3_plan()
    b = derive_resized(a, 4)
    assert diff_plans(a, a) == ""
    out = diff_plans(a, b)
    assert a.fingerprint() in out and b.fingerprint() in out
    assert "data" in out and "[elastic]" in out


# -- checkpoint fingerprint stamping + typed refusal --------------------


def test_checkpoint_plan_mismatch(tmp_path):
    from parallel_cnn_tpu.train import checkpoint

    live = _ring_zero3_plan()
    other = dataclasses.replace(live, accum=4)
    params = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, params, plan_fingerprint=live.fingerprint())

    # Same plan: loads.
    got, _ = checkpoint.restore(path, params,
                                plan_fingerprint=live.fingerprint())
    np.testing.assert_array_equal(np.asarray(got["w"]), params["w"])

    # Different plan: typed refusal naming BOTH fingerprints.
    with pytest.raises(PlanMismatchError) as ei:
        checkpoint.restore(path, params,
                           plan_fingerprint=other.fingerprint())
    assert ei.value.stored == live.fingerprint()
    assert ei.value.live == other.fingerprint()
    assert live.fingerprint() in str(ei.value)
    assert other.fingerprint() in str(ei.value)
    assert "--replan" in str(ei.value)

    # --replan waives it; a reader with no live plan never checks.
    checkpoint.restore(path, params,
                       plan_fingerprint=other.fingerprint(), replan=True)
    checkpoint.restore(path, params)

    # Files predating plan stamping (no "plan" key) always load.
    legacy = str(tmp_path / "legacy.npz")
    checkpoint.save(legacy, params)
    checkpoint.restore(legacy, params,
                       plan_fingerprint=live.fingerprint())

    with pytest.raises(PlanMismatchError):
        checkpoint.load_params(path, params,
                               plan_fingerprint=other.fingerprint())


# -- tune --report hand-off ---------------------------------------------


def test_tune_report_loads_as_valid_plan(tmp_path):
    from parallel_cnn_tpu.analysis import autotune
    from parallel_cnn_tpu.analysis.cost_model import COST_SCHEMA_VERSION

    chosen = autotune.Plan(comm_impl="ring", bucket_bytes=2048,
                           wire_dtype="bfloat16", overlap=True,
                           zero=0, accum=2, stages=1)
    eplan = chosen.to_execution_plan(n_host=1, n_dev=8)
    eplan.validate()

    # Current format: the report embeds a full plan document.
    report = tmp_path / "report.json"
    report.write_text(json.dumps({
        "version": COST_SCHEMA_VERSION,
        "autotune": {"chosen": {"plan": chosen.to_json()},
                     "n_host": 1, "n_dev": 8},
        "plan": eplan.to_json_dict(),
    }))
    assert load_plan(report) == eplan

    # Legacy format (no embedded plan): the chosen autotune section
    # converts through the thin Plan view — same ExecutionPlan.
    legacy = tmp_path / "legacy.json"
    legacy.write_text(json.dumps({
        "version": COST_SCHEMA_VERSION,
        "autotune": {"chosen": {"plan": chosen.to_json()},
                     "n_host": 1, "n_dev": 8},
    }))
    assert load_plan(legacy) == eplan
    assert load_plan(legacy).fingerprint() == eplan.fingerprint()

    # The view is a round trip: ExecutionPlan -> autotune.Plan is the
    # canonical form of what we started with.
    assert autotune.Plan.from_execution_plan(eplan) == \
        autotune._canonical(chosen)


def test_check_plan_verifies_file_offline(tmp_path):
    from parallel_cnn_tpu.analysis import checker

    p = tmp_path / "plan.json"
    save_plan(p, ExecutionPlan())
    code, report = checker.verify_plan_file(p)
    assert code == 0
    assert "plan.resolved" in report and "OK" in report
    # The default plan's cost-table row ships in the baseline.
    assert "cost baseline: present" in report

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "plan": {}}))
    code, report = checker.verify_plan_file(bad)
    assert code == 1 and "FAIL" in report

    illegal = tmp_path / "illegal.json"
    save_plan(illegal, ExecutionPlan(comm_impl="ring"))
    code, report = checker.verify_plan_file(illegal)
    assert code == 1 and "FAIL" in report


# -- mesh-outside-plan: the single-resolution-site rule -----------------


def _scan(tmp_path, source):
    from parallel_cnn_tpu.analysis.checker import run_check

    f = tmp_path / "mod.py"
    f.write_text(source)
    code, _report, diags = run_check(paths=[str(f)])
    return code, [d for d in diags if d.rule == "mesh-outside-plan"]


def test_mesh_outside_plan_rule(tmp_path):
    code, hits = _scan(
        tmp_path,
        "from parallel_cnn_tpu.parallel import mesh as mesh_lib\n"
        "m = mesh_lib.make_pipeline_mesh(2)\n"
        "n = mesh_lib.make_mesh(None)\n",
    )
    assert code != 0 and len(hits) == 2

    # The sanctioned path — plan.make_mesh() — is not a mesh
    # constructor; neither is an unrelated .make_mesh method.
    code, hits = _scan(
        tmp_path,
        "from parallel_cnn_tpu import plan as plan_lib\n"
        "eplan = plan_lib.build_plan(object()).validate()\n"
        "m = eplan.make_mesh()\n",
    )
    assert code == 0 and not hits

    # A waiver with a reason is honored (and required: test/bench sites
    # that genuinely need a raw mesh say why).
    code, hits = _scan(
        tmp_path,
        "from parallel_cnn_tpu.parallel import mesh as mesh_lib\n"
        "m = mesh_lib.make_pipeline_mesh(2)  "
        "# graftcheck: disable=mesh-outside-plan -- test fixture mesh\n",
    )
    assert code == 0
    assert all(d.waived for d in hits)


def test_package_has_single_mesh_site():
    """The tree itself: no unwaived mesh construction outside plan/."""
    from parallel_cnn_tpu.analysis import ast_rules
    from parallel_cnn_tpu.analysis.checker import _package_files
    from parallel_cnn_tpu.analysis.diagnostics import (
        apply_waivers, parse_waivers, relpath,
    )
    import ast as ast_mod

    diags, waivers = [], {}
    for p in _package_files():
        src = p.read_text()
        waivers[relpath(p)] = parse_waivers(src)
        diags.extend(ast_rules.scan_module(p, ast_mod.parse(src), src))
    mesh_diags = [d for d in apply_waivers(diags, waivers)
                  if d.rule == "mesh-outside-plan" and not d.waived]
    assert not mesh_diags, [f"{d.file}:{d.line}" for d in mesh_diags]


# -- elastic recompile-once, end to end through zoo.train ---------------


def test_elastic_recompile_once_journal(tmp_path, host_devices):
    """A resize lap 8 → 4 → 8 journals plan_step_cache miss (new world)
    then hit (the initial topology's derived plan was primed at setup)
    — plan equality, not mesh identity, gates the re-trace."""
    import jax
    import jax.numpy as jnp

    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import (
        CommConfig, ElasticConfig, FusedStepConfig, MeshConfig, ObsConfig,
    )
    from parallel_cnn_tpu.nn import core, layers
    from parallel_cnn_tpu.obs import events as events_lib
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.train import zoo

    comm = CommConfig(impl="ring", bucket_bytes=2048, overlap=True)
    fused = FusedStepConfig(update=True, tail=True, act_dtype="float32",
                            zero=3)
    eplan = _ring_zero3_plan()
    model = core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 8, 8, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (64,)).astype(np.int32))
    bundle = obs_lib.from_config(
        ObsConfig(trace=True, dir=str(tmp_path)), run="plan-test"
    )
    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))  # graftcheck: disable=mesh-outside-plan -- test fixture mesh
    zoo.train(
        model, x, y, in_shape=(8, 8, 3), epochs=2, batch_size=16,
        lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh8, comm=comm,
        fused=fused, seed=0, verbose=False, obs=bundle,
        elastic=ElasticConfig(schedule="2:4,5:8"),
        plan=eplan,
    )
    paths = bundle.finish()
    recs = events_lib.read_journal(paths["journal"])
    cache = [r for r in recs if r["kind"] == "plan_step_cache"]
    assert len(cache) == 2, cache
    assert cache[0]["world"] == 4 and cache[0]["hit"] is False
    assert cache[1]["world"] == 8 and cache[1]["hit"] is True
    # The journaled fingerprints are derive_resized's, so the hit plan
    # equals the primed initial topology's derived plan.
    assert cache[1]["plan"] == derive_resized(eplan, 8).fingerprint()
    assert cache[0]["plan"] == derive_resized(eplan, 4).fingerprint()
