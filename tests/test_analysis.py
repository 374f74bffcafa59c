"""graftcheck (ISSUE 8): every rule must trip on a seeded fixture AND
pass a clean twin — a gate that can't fail is vacuous, a gate that
can't pass is noise.

jaxpr-family fixtures build tiny real jaxprs (shard_map/pmap/jit over
the suite's 8-device virtual CPU platform); AST/concurrency fixtures
are tempfiles run through the targeted checker path; the Pallas budget
and race-harness families get one real run plus a synthetic violation.
"""

import ast
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from parallel_cnn_tpu.analysis import (
    ast_rules,
    concurrency,
    cost_model,
    jaxpr_rules,
    sharding_prop,
)
from parallel_cnn_tpu.analysis import checker
from parallel_cnn_tpu.analysis import pallas_budget as budget_mod
from parallel_cnn_tpu.analysis.checker import run_check
from parallel_cnn_tpu.analysis.diagnostics import (
    Diagnostic,
    Severity,
    apply_waivers,
    parse_waivers,
    ratchet,
    relpath,
)
from parallel_cnn_tpu.config import MeshConfig
from parallel_cnn_tpu.parallel import mesh as mesh_lib

pytestmark = pytest.mark.analysis


def _rules(diags):
    return {d.rule for d in diags}


def _by_rule(diags, rule):
    return [d for d in diags if d.rule == rule]


# ---------------------------------------------------------------------------
# jaxpr family
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh4(host_devices):
    return mesh_lib.make_mesh(MeshConfig(data=4, model=1),
                              devices=host_devices[:4])


def _shmap_jaxpr(mesh, body, x, out_specs=P("data")):
    f = jax.shard_map(
        body, mesh=mesh, in_specs=P("data"), out_specs=out_specs,
        check_vma=False,
    )
    return jax.make_jaxpr(f)(x)


def test_collective_axis_trips_on_undeclared_pmap_axis(host_devices):
    closed = jax.make_jaxpr(
        jax.pmap(lambda v: lax.psum(v, "batch"), axis_name="batch")
    )(jnp.ones((4, 2), jnp.float32))
    diags = jaxpr_rules.analyze_closed_jaxpr("fixture", closed)
    hits = _by_rule(diags, "collective-axis")
    assert hits and "batch" in hits[0].message


def test_collective_axis_clean_on_mesh_axis(mesh4):
    closed = _shmap_jaxpr(
        mesh4, lambda v: lax.psum(v, "data"),
        jnp.ones((4, 2), jnp.float32), out_specs=P(),
    )
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "collective-axis",
    )


def test_ring_permutation_trips_on_split_ring(mesh4):
    broken = [(0, 1), (1, 0), (2, 3), (3, 2)]  # two 2-cycles, not a ring
    closed = _shmap_jaxpr(
        mesh4, lambda v: lax.ppermute(v, "data", broken),
        jnp.ones((4, 2), jnp.float32),
    )
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "ring-permutation",
    )
    assert hits and "single" in hits[0].message


def test_ring_permutation_clean_on_single_cycle(mesh4):
    ring = [(i, (i + 1) % 4) for i in range(4)]
    closed = _shmap_jaxpr(
        mesh4, lambda v: lax.ppermute(v, "data", ring),
        jnp.ones((4, 2), jnp.float32),
    )
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "ring-permutation",
    )


def test_ring_permutation_trips_on_partial_axis_coverage(mesh4):
    # A perfectly valid single cycle — over only 3 of the axis's 4
    # ranks. Rank 3 never contributes or receives the reduction; only
    # the axis-size-aware check sees it.
    partial = [(0, 1), (1, 2), (2, 0)]
    closed = _shmap_jaxpr(
        mesh4, lambda v: lax.ppermute(v, "data", partial),
        jnp.ones((4, 2), jnp.float32),
    )
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "ring-permutation",
    )
    assert hits and "every rank of its axis" in hits[0].message


@pytest.fixture(scope="module")
def hier_mesh22(host_devices):
    return mesh_lib.make_hier_mesh(n_hosts=2, devices=host_devices[:4])


def _hier_jaxpr(mesh, body, x):
    f = jax.shard_map(
        body, mesh=mesh, in_specs=P(("host", "data")),
        out_specs=P(("host", "data")), check_vma=False,
    )
    return jax.make_jaxpr(f)(x)


def test_ring_permutation_clean_on_per_axis_hier_rings(hier_mesh22):
    ring2 = [(i, (i + 1) % 2) for i in range(2)]

    def hier(v):
        v = lax.ppermute(v, "data", ring2)   # intra-host ring
        return lax.ppermute(v, "host", ring2)  # inter-host ring

    closed = _hier_jaxpr(hier_mesh22, hier, jnp.ones((4, 2), jnp.float32))
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "ring-permutation",
    )


def test_ring_permutation_trips_on_global_ranks_in_hier_axis(hier_mesh22):
    # The classic flat-to-hierarchical port bug: a ring written over
    # GLOBAL ranks 0..3 issued on one axis of a 2x2 (host, device) mesh.
    # Within the 2-wide axis, ranks 2 and 3 don't exist.
    ring4 = [(i, (i + 1) % 4) for i in range(4)]
    closed = _hier_jaxpr(
        hier_mesh22, lambda v: lax.ppermute(v, "data", ring4),
        jnp.ones((4, 2), jnp.float32),
    )
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed),
        "ring-permutation",
    )
    assert hits and "axis 'data' (size 2)" in hits[0].message


def test_f32_wire_trips_on_bf16_param_gather(mesh4):
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def gather_bf16(v):
        # Param all-gather riding a bf16 wire: the ppermute output
        # reaches the jaxpr output through layout-only ops.
        return lax.ppermute(v.astype(jnp.bfloat16), "data", ring)

    closed = _shmap_jaxpr(mesh4, gather_bf16, jnp.ones((4, 2), jnp.float32))
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "f32-wire"
    )
    assert hits and "bfloat16" in hits[0].message


def test_f32_wire_clean_on_f32_gather_and_bf16_grad(mesh4):
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def mixed(v):
        gathered = lax.ppermute(v, "data", ring)  # f32 wire: fine
        # bf16 GRADIENT wire: exempt by construction — a gradient is
        # produced by backward-pass arithmetic (the square) and consumed
        # by optimizer arithmetic (the add), so the transparent chain is
        # broken on both the input and output side.
        g = lax.ppermute((v * v).astype(jnp.bfloat16), "data", ring)
        return gathered + g.astype(jnp.float32) * 0.1

    closed = _shmap_jaxpr(mesh4, mixed, jnp.ones((4, 2), jnp.float32))
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "f32-wire"
    )


def test_f32_wire_trips_on_bf16_resident_gather(mesh4):
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def head_gather(v):
        # ZeRO-3-shaped violation: resident shards (a jaxpr INPUT) cast
        # to bf16 and gathered, then consumed by step arithmetic — the
        # output-side slice never sees the wire, only the input-side
        # slice catches it.
        g = lax.ppermute(v.astype(jnp.bfloat16), "data", ring)
        return g.astype(jnp.float32) * 2.0

    closed = _shmap_jaxpr(mesh4, head_gather, jnp.ones((4, 2), jnp.float32))
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "f32-wire"
    )
    assert hits and "fed from a jaxpr input" in hits[0].message


def test_f32_wire_clean_on_f32_resident_gather(mesh4):
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def head_gather(v):
        return lax.ppermute(v, "data", ring) * 2.0

    closed = _shmap_jaxpr(mesh4, head_gather, jnp.ones((4, 2), jnp.float32))
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "f32-wire"
    )


def test_donated_reuse_trips_on_read_after_donation():
    inner = jax.jit(lambda a: a * 2.0, donate_argnums=0)

    def f(a):
        b = inner(a)
        return b + a  # reads the donated buffer

    closed = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
    assert _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "donated-reuse"
    )


def test_donated_reuse_clean_when_source_dropped():
    inner = jax.jit(lambda a: a * 2.0, donate_argnums=0)
    closed = jax.make_jaxpr(lambda a: inner(a) + 1.0)(
        jnp.ones((4,), jnp.float32)
    )
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "donated-reuse"
    )


def test_weak_type_trips_on_python_scalar_arg():
    closed = jax.make_jaxpr(lambda x: x * 0.5)(3.0)
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "weak-type"
    )
    assert hits and "entry argument 0" in hits[0].message


def test_weak_type_trips_on_captured_weak_constant():
    # This jax inlines 0-d consts as Literals in most traces, so the
    # constvar branch is exercised directly on a minimal closed-jaxpr
    # stand-in carrying one 0-d weak captured constant.
    class _Aval:
        ndim = 0
        weak_type = True

    class _Var:
        aval = _Aval()

    class _Jaxpr:
        invars = ()
        constvars = (_Var(),)
        eqns = ()
        outvars = ()

    class _Closed:
        jaxpr = _Jaxpr()
        consts = (0.5,)

    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", _Closed()), "weak-type"
    )
    assert hits and "frozen into the executable" in hits[0].message


def test_weak_type_clean_on_explicit_dtypes():
    closed = jax.make_jaxpr(
        lambda x: x * jnp.float32(0.5)
    )(jnp.ones((3,), jnp.float32))
    assert not _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "weak-type"
    )


def test_real_entry_points_are_clean():
    diags = jaxpr_rules.run_jaxpr_rules(fast=True)
    assert [d for d in diags if d.severity == Severity.ERROR] == []


def test_obs_span_is_invisible_in_the_jaxpr():
    """Clean twin of the observability invariant: tracing a step under
    an open obs span yields the byte-identical jaxpr of the bare step
    (the span lives on the host), and the fast entry set carries the
    ``train.obs_batched_step`` entry that gates this."""
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.obs.trace import Tracer
    from parallel_cnn_tpu.train import step

    lp = lenet_ref.init(jax.random.key(0))
    lx = jnp.zeros((8, 28, 28), jnp.float32)
    ly = jnp.zeros((8,), jnp.int32)
    bare = jax.make_jaxpr(
        lambda p, x, y: step.batched_step(p, x, y, 0.05)
    )(lp, lx, ly)

    tracer = Tracer(process_name="fixture", mirror_jax=False)

    def spanned(p, x, y):
        with tracer.span("train.step", cat="step"):
            return step.batched_step(p, x, y, 0.05)

    closed = jax.make_jaxpr(spanned)(lp, lx, ly)
    assert str(closed) == str(bare)
    # the span itself DID run — on the host, at trace time
    assert any(
        e.get("ph") == "X" and e["name"] == "train.step"
        for e in tracer.events()
    )
    assert not [
        d for d in jaxpr_rules.analyze_closed_jaxpr("fixture", closed)
        if d.severity == Severity.ERROR
    ]
    entries = jaxpr_rules.trace_entry_points(fast=True)
    assert "train.obs_batched_step" in {name for name, _ in entries}


def test_obs_naive_inline_timing_trips_weak_type():
    """Tripping twin: the wrong way to time a step — feeding the host
    clock INTO the traced computation — enters as a weak-typed python
    scalar argument, the retrace hazard the host-side tracer avoids."""
    import time

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step

    lp = lenet_ref.init(jax.random.key(0))
    lx = jnp.zeros((8, 28, 28), jnp.float32)
    ly = jnp.zeros((8,), jnp.int32)

    def timed_step(p, x, y, t0):
        out = step.batched_step(p, x, y, 0.05)
        return out, t0

    closed = jax.make_jaxpr(timed_step)(lp, lx, ly, time.perf_counter())
    hits = _by_rule(
        jaxpr_rules.analyze_closed_jaxpr("fixture", closed), "weak-type"
    )
    assert hits and "re-promotes per call site" in hits[0].message


# ---------------------------------------------------------------------------
# AST family (targeted checker path)
# ---------------------------------------------------------------------------

def _check_file(tmp_path, source, name="fixture.py"):
    f = tmp_path / name
    f.parent.mkdir(parents=True, exist_ok=True)
    f.write_text(textwrap.dedent(source))
    code, _report, diags = run_check(
        paths=[str(f)], baseline_path=tmp_path / "no_baseline.json"
    )
    return code, diags


def test_time_in_jit_trips(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import time
        import jax


        @jax.jit
        def step(x):
            return x * time.time()
        """)
    assert code == 1 and _by_rule(diags, "time-in-jit")


def test_time_in_jit_clean_outside_jit(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import time
        import jax


        @jax.jit
        def step(x):
            return x * 2.0


        def bench(x):
            t0 = time.time()
            step(x)
            return time.time() - t0
        """)
    assert code == 0 and not _by_rule(diags, "time-in-jit")


def test_captured_mutation_trips_on_module_list(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import jax

        TRACE_LOG = []


        @jax.jit
        def step(x):
            TRACE_LOG.append(x.shape)
            return x
        """)
    assert code == 1 and _by_rule(diags, "captured-mutation")


def test_captured_mutation_clean_on_local_and_pure_update(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import jax


        @jax.jit
        def step(opt_state, grads, optimizer):
            acc = []
            acc.append(grads)
            updates, opt_state = optimizer.update(grads, opt_state)
            return updates, opt_state
        """)
    assert code == 0 and not _by_rule(diags, "captured-mutation")


def test_donation_source_trips_on_read_after_donating_call(tmp_path):
    code, diags = _check_file(tmp_path, """\
        from parallel_cnn_tpu.train.step import batched_step


        def epoch(params, x, y):
            new_params, err = batched_step(params, x, y, 0.1)
            return params, err  # stale read of the donated pytree
        """)
    assert code == 1 and _by_rule(diags, "donation-source")


def test_donation_source_clean_on_rebind(tmp_path):
    code, diags = _check_file(tmp_path, """\
        from parallel_cnn_tpu.train.step import batched_step


        def epoch(params, x, y):
            params, err = batched_step(params, x, y, 0.1)
            return params, err
        """)
    assert code == 0 and not _by_rule(diags, "donation-source")


def test_shape_branch_warns_but_does_not_gate(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import jax


        @jax.jit
        def step(x):
            if x.shape[0] > 4:
                return x * 2.0
            return x
        """)
    hits = _by_rule(diags, "shape-branch")
    assert hits and hits[0].severity == Severity.WARNING
    assert code == 0  # warnings never gate


def test_env_outside_config_trips_in_package_clean_in_config(tmp_path):
    src = """\
        import os

        KNOB = os.environ.get("PCNN_FIXTURE_KNOB", "0")
        """
    code, diags = _check_file(
        tmp_path, src, name="parallel_cnn_tpu/knobs.py"
    )
    assert code == 1 and _by_rule(diags, "env-outside-config")
    code, diags = _check_file(
        tmp_path, src, name="parallel_cnn_tpu/config.py"
    )
    assert code == 0 and not _by_rule(diags, "env-outside-config")


# ---------------------------------------------------------------------------
# Waivers + ratchet mechanics
# ---------------------------------------------------------------------------

def test_waiver_with_reason_suppresses(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import time
        import jax


        @jax.jit
        def step(x):
            return x * time.time()  # graftcheck: disable=time-in-jit -- fixture: frozen trace-time stamp is the point
        """)
    assert code == 0
    hits = _by_rule(diags, "time-in-jit")
    assert hits and hits[0].waived and "fixture" in hits[0].waive_reason


def test_standalone_waiver_covers_next_line(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import time
        import jax


        @jax.jit
        def step(x):
            # graftcheck: disable=time-in-jit -- fixture: standalone form
            return x * time.time()
        """)
    assert code == 0 and _by_rule(diags, "time-in-jit")[0].waived


def test_bare_waiver_is_itself_an_error(tmp_path):
    code, diags = _check_file(tmp_path, """\
        import time
        import jax


        @jax.jit
        def step(x):
            return x * time.time()  # graftcheck: disable=time-in-jit
        """)
    assert code == 1 and _by_rule(diags, "bare-waiver")


def test_waiver_does_not_cover_other_lines_or_rules():
    src = "x = 1  # graftcheck: disable=time-in-jit -- only this line\n"
    waivers = {"f.py": parse_waivers(src)}
    covered = Diagnostic("time-in-jit", Severity.ERROR, "f.py", 1, "m")
    other_line = Diagnostic("time-in-jit", Severity.ERROR, "f.py", 2, "m")
    other_rule = Diagnostic("env-outside-config", Severity.ERROR, "f.py", 1, "m")
    out = apply_waivers([covered, other_line, other_rule], waivers)
    assert out[0].waived and not out[1].waived and not out[2].waived


def test_fingerprint_ignores_lines_and_message_digits():
    a = Diagnostic("r", Severity.ERROR, "f.py", 10, "donated at line 12")
    b = Diagnostic("r", Severity.ERROR, "f.py", 99, "donated at line 47")
    assert a.fingerprint() == b.fingerprint()


def test_ratchet_absorbs_exactly_baseline_count():
    mk = lambda: Diagnostic("r", Severity.ERROR, "f.py", 1, "msg 3")
    baseline = {mk().fingerprint(): 1}
    first, second = ratchet([mk(), mk()], baseline)
    assert first.baselined and not first.gates()
    assert not second.baselined and second.gates()


# ---------------------------------------------------------------------------
# Pallas budget family
# ---------------------------------------------------------------------------

def test_budget_observer_sees_real_sizing_decisions():
    records = budget_mod.collect_budget_records(fast=True)
    assert records, "no block-size decisions observed on the fast configs"
    from parallel_cnn_tpu.ops.pallas_conv import _VMEM_LIMIT

    assert all(r.modeled <= _VMEM_LIMIT for r in records)
    assert {r.tag.split("/")[0] for r in records} >= {"conv", "update", "tail"}


def test_budget_clean_on_shipped_configs():
    diags = budget_mod.run_pallas_budget(fast=True)
    assert [d for d in diags if d.severity == Severity.ERROR] == []


def test_budget_trips_on_over_limit_config(monkeypatch):
    from parallel_cnn_tpu.ops.pallas_conv import _VMEM_BUDGET, _VMEM_LIMIT

    def fake_records(fast=False):
        return [
            budget_mod.BudgetRecord(
                "fixture.oom", "conv", 64, 64, 4 * 2**20, 2**20,
                modeled=_VMEM_LIMIT + 1,
            ),
            budget_mod.BudgetRecord(
                "fixture.tight", "conv", 64, 64, 2**20, 2**20,
                modeled=_VMEM_BUDGET + 1,
            ),
        ]

    monkeypatch.setattr(budget_mod, "collect_budget_records", fake_records)
    diags = budget_mod.run_pallas_budget()
    assert [d.severity for d in _by_rule(diags, "vmem-budget")] == [
        Severity.ERROR, Severity.WARNING,
    ]
    assert "falls back to XLA" in diags[0].message


# ---------------------------------------------------------------------------
# Concurrency family: static lint
# ---------------------------------------------------------------------------

def _scan_concurrency_src(tmp_path, source):
    f = tmp_path / "conc_fixture.py"
    f.write_text(textwrap.dedent(source))
    return concurrency.scan_concurrency(f, ast.parse(f.read_text()))


def test_lock_discipline_trips_on_unguarded_rmw(tmp_path):
    diags = _scan_concurrency_src(tmp_path, """\
        import threading


        class Stats:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                self.count += 1
        """)
    hits = _by_rule(diags, "lock-discipline")
    assert hits and hits[0].severity == Severity.ERROR


def test_lock_discipline_clean_under_lock(tmp_path):
    diags = _scan_concurrency_src(tmp_path, """\
        import threading


        class Stats:
            def __init__(self):
                self._lock = threading.Lock()
                self.count = 0

            def bump(self):
                with self._lock:
                    self.count += 1
        """)
    assert not _by_rule(diags, "lock-discipline")


def test_global_mutation_trips_in_threading_module(tmp_path):
    diags = _scan_concurrency_src(tmp_path, """\
        import threading

        _REGISTRY = {}


        def register(name, fn):
            _REGISTRY[name] = fn
        """)
    assert _by_rule(diags, "global-mutation")


def test_global_mutation_ignores_non_threading_modules(tmp_path):
    diags = _scan_concurrency_src(tmp_path, """\
        _REGISTRY = {}


        def register(name, fn):
            _REGISTRY[name] = fn
        """)
    assert diags == []


# ---------------------------------------------------------------------------
# Concurrency family: seeded race harness
# ---------------------------------------------------------------------------

def test_race_harness_counters_conserve():
    stats = concurrency.run_race_harness(
        seed=0, n_threads=4, n_requests=20
    )
    assert stats["submitted"] == 80
    assert (
        stats["completed"] + stats["shed"] + stats["expired"]
        + stats["failed"] == 80
    )


def test_race_checks_clean_on_shipped_batcher():
    assert concurrency.run_race_checks(seeds=(0,)) == []


def test_race_checks_report_conservation_violation(monkeypatch):
    def broken(seed=0, **kw):
        raise AssertionError("submitted 79 != 80: lost an update")

    monkeypatch.setattr(concurrency, "run_race_harness", broken)
    diags = concurrency.run_race_checks(seeds=(0,))
    assert _by_rule(diags, "race-harness")
    assert "lost an update" in diags[0].message


# ---------------------------------------------------------------------------
# Repo-level parity/xref rules
# ---------------------------------------------------------------------------

def test_env_doc_parity_both_directions(tmp_path):
    code = tmp_path / "reader.py"
    doc = tmp_path / "doc.md"
    code.write_text('import os\nA = os.environ.get("PCNN_FIXTURE_ONLY_CODE")\n')
    doc.write_text("docs mention PCNN_FIXTURE_ONLY_DOC here\n")
    diags = ast_rules.env_doc_parity([code], [doc])
    msgs = " | ".join(d.message for d in diags)
    assert "PCNN_FIXTURE_ONLY_CODE" in msgs  # read but undocumented
    assert "PCNN_FIXTURE_ONLY_DOC" in msgs   # documented but unread


def test_env_doc_parity_clean_when_matched(tmp_path):
    code = tmp_path / "reader.py"
    doc = tmp_path / "doc.md"
    code.write_text('import os\nA = os.environ.get("PCNN_FIXTURE_KNOB")\n')
    doc.write_text("| PCNN_FIXTURE_KNOB | a documented knob |\n")
    assert ast_rules.env_doc_parity([code], [doc]) == []


def _parser_fixture(tmp_path):
    run_py = tmp_path / "run.py"
    run_py.write_text(textwrap.dedent("""\
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--md")
        """))
    return run_py


def test_doc_xref_checks_flags_paths_and_symbols(tmp_path):
    run_py = _parser_fixture(tmp_path)
    doc = tmp_path / "doc.md"
    doc.write_text(textwrap.dedent("""\
        Run `run.py --nonexistent-flag` for fun, then read `gone/old_harness.py`.
        Call `zoo.no_such_function(cfg)` to train.
        """))
    diags = ast_rules.doc_xref([doc], [run_py], repo_root=tmp_path)
    msgs = " | ".join(d.message for d in diags)
    assert "--nonexistent-flag" in msgs
    assert "gone/old_harness.py" in msgs
    assert "no_such_function" in msgs


def test_doc_xref_clean_on_valid_references(tmp_path):
    run_py = _parser_fixture(tmp_path)
    doc = tmp_path / "doc.md"
    doc.write_text(
        "Run `run.py --md` (see `run.py`) then `zoo.make_optimizer(0.1)`.\n"
    )
    assert ast_rules.doc_xref([doc], [run_py], repo_root=tmp_path) == []


def _path_diags(tmp_path, text):
    doc = tmp_path / "doc.md"
    doc.write_text(textwrap.dedent(text))
    return _by_rule(
        ast_rules.doc_xref([doc], [], repo_root=tmp_path), "doc-path-missing"
    )


def test_doc_path_takes_line_and_name_suffixes_and_the_package_short_form(
    tmp_path,
):
    (tmp_path / "parallel_cnn_tpu" / "train").mkdir(parents=True)
    (tmp_path / "parallel_cnn_tpu" / "train" / "zoo.py").write_text("")
    (tmp_path / "smoke.py").write_text("")
    assert _path_diags(tmp_path, """\
        `smoke.py`, `smoke.py:49`, `smoke.py:main`, `train/zoo.py:train` and
        `parallel_cnn_tpu/train/zoo.py:12` are files of the repo.
        """) == []
    hits = _path_diags(tmp_path, """\
        `smoke.py:49` is there, `train/zoo2.py:49` and `RECORD_r02.json` are not.
        """)
    assert [d.line for d in hits] == [1, 1]
    assert "train/zoo2.py" in hits[0].message
    assert "RECORD_r02.json" in hits[1].message


def test_doc_path_leaves_what_a_command_creates_alone(tmp_path):
    """Only a code span that is one token is a path: commands, fenced
    blocks and placeholder paths name files that exist after a run."""
    assert _path_diags(tmp_path, """\
        Run `python3 tool.py --out chiprun_out/scopes.json`; it writes
        `<dir>/trace.json` and `chiprun_out/<run>_programs.json`.

        ```bash
        python3 tool.py --out chiprun_out/scopes.json
        cat chiprun_out/scopes.json
        ```
        """) == []


_LIVE_DOCS = checker._existing(checker.LIVE_DOCS)


@pytest.fixture(scope="module")
def shipped_doc_diags():
    return checker.doc_rule_diagnostics(_LIVE_DOCS)


@pytest.mark.parametrize("doc", [relpath(p) for p in _LIVE_DOCS])
def test_shipped_docs_pass_parity_and_xref(shipped_doc_diags, doc):
    assert [d for d in shipped_doc_diags if d.file == doc] == []


def test_shipped_code_reads_no_undocumented_env_name(shipped_doc_diags):
    assert [d for d in shipped_doc_diags if not d.file.endswith(".md")] == []


# ---------------------------------------------------------------------------
# sharding-propagation + cost families (check --cost)
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(
        kind="ring_overlap", n_dev=4, n_host=1, accum=2, wire_itemsize=2,
        bucket_elems=(400,), resident_bytes=0, act_bytes=0,
        images_per_step=8, n_state_leaves=1,
    )
    base.update(kw)
    return jaxpr_rules.EntrySpec(**base)


def test_implicit_reshard_trips_on_seeded_master_gather(host_devices):
    name, closed, spec = cost_model.build_seeded_entry("bf16-master-gather")
    hits = _by_rule(
        sharding_prop.analyze_entry_sharding(name, closed, spec),
        "implicit-reshard",
    )
    assert hits and "replicated" in hits[0].message


def test_implicit_reshard_clean_on_sharded_roundtrip(mesh4):
    closed = _shmap_jaxpr(
        mesh4, lambda v: v * 2.0, jnp.zeros((8, 4), jnp.float32)
    )
    diags = sharding_prop.analyze_entry_sharding("fixture", closed, _spec())
    assert not _by_rule(diags, "implicit-reshard")


def test_sharding_contradiction_trips_on_double_psum(mesh4):
    def double(v):
        return lax.psum(lax.psum(v, "data"), "data")

    closed = _shmap_jaxpr(
        mesh4, double, jnp.zeros((8, 4), jnp.float32), out_specs=P()
    )
    hits = _by_rule(
        sharding_prop.analyze_entry_sharding("fixture", closed, None),
        "sharding-contradiction",
    )
    assert hits and "replicated over that axis" in hits[0].message


def test_sharding_contradiction_clean_on_single_psum(mesh4):
    closed = _shmap_jaxpr(
        mesh4, lambda v: lax.psum(v, "data"),
        jnp.zeros((8, 4), jnp.float32), out_specs=P()
    )
    assert not _by_rule(
        sharding_prop.analyze_entry_sharding("fixture", closed, None),
        "sharding-contradiction",
    )


def _ring_overlap_fixture(mesh):
    """A schedule whose counted bytes EQUAL the ring_overlap closed form:
    K+1 = 3 bf16 all-gathers of a 100-element shard on the 4-device ring
    = 3 * (4-1) * 100 * 2 bytes, exactly (K=2, E=400, w=2)."""
    from parallel_cnn_tpu.parallel import collectives

    def body(shard):
        for _ in range(3):
            full = collectives.ring_all_gather(shard, "data", 4, "bfloat16")
            shard = full[: shard.shape[0]]
        return shard

    return _shmap_jaxpr(mesh, body, jnp.zeros((400,), jnp.float32))


def test_cost_model_clean_on_matching_schedule(mesh4, tmp_path):
    closed = _ring_overlap_fixture(mesh4)
    diags = cost_model.run_cost_rules(
        [("fixture", closed, _spec(resident_bytes=1000))],
        baseline_path=tmp_path / "b.json",
        report_path=tmp_path / "r.json",
    )
    assert not _by_rule(diags, "cost-model-mismatch")


@pytest.mark.parametrize("mutant,jaxpr_rule", [
    ("bf16-master-gather", "f32-wire"),
    ("partial-stage-ring", "ring-permutation"),
])
def test_cost_model_mismatch_trips_on_seeded_mutant(
    host_devices, tmp_path, mutant, jaxpr_rule
):
    """Each really-traced mutant `check --cost-seeded` appends trips the
    byte table and its own jaxpr rule."""
    name, closed, spec = cost_model.build_seeded_entry(mutant)
    diags = cost_model.run_cost_rules(
        [(name, closed, spec)],
        baseline_path=tmp_path / "b.json",
        report_path=tmp_path / "r.json",
    )
    hits = _by_rule(diags, "cost-model-mismatch")
    assert hits and "closed-form" in hits[0].message
    assert _by_rule(jaxpr_rules.analyze_closed_jaxpr(name, closed), jaxpr_rule)


def test_real_zoo_entries_move_their_closed_form_bytes(host_devices, tmp_path):
    """The clean direction on the shipped tree: every traced zoo, pipeline
    and tuned entry moves exactly the bytes docs/collectives.md's tables
    give it, ZeRO's residency ordering holds, and nothing has grown past
    the shipped ratchet (what `check --cost` exits 0 on)."""
    entries = jaxpr_rules.trace_entry_points(fast=False, with_specs=True)
    assert sum(spec is not None for _, _, spec in entries) >= 8
    diags = cost_model.run_cost_rules(entries, report_path=tmp_path / "r.json")
    assert [d for d in diags if d.severity == Severity.ERROR] == []


def test_cost_ratchet_trips_on_growth_past_baseline(mesh4, tmp_path):
    closed = _ring_overlap_fixture(mesh4)
    spec = _spec(resident_bytes=1000)   # peak_hbm = 1000 + 100*4 = 1400
    cost_model.save_cost_baseline(
        tmp_path / "b.json",
        {"fixture": {"bytes_dcn": 0, "peak_hbm": 1399}},
    )
    diags = cost_model.run_cost_rules(
        [("fixture", closed, spec)],
        baseline_path=tmp_path / "b.json",
        report_path=tmp_path / "r.json",
    )
    hits = _by_rule(diags, "cost-ratchet")
    assert hits and "--update-cost-baseline" in hits[0].message


def test_cost_ratchet_clean_at_baseline_and_on_missing_entry(mesh4, tmp_path):
    closed = _ring_overlap_fixture(mesh4)
    spec = _spec(resident_bytes=1000)
    # Exactly at the recorded values: no diagnostic (ratchet is >, not >=).
    cost_model.save_cost_baseline(
        tmp_path / "b.json",
        {"fixture": {"bytes_dcn": 0, "peak_hbm": 1400}},
    )
    diags = cost_model.run_cost_rules(
        [("fixture", closed, spec)],
        baseline_path=tmp_path / "b.json",
        report_path=tmp_path / "r.json",
    )
    assert not _by_rule(diags, "cost-ratchet")
    # Entries absent from the baseline pass (they ratchet from their
    # first recorded run, they do not gate retroactively).
    cost_model.save_cost_baseline(tmp_path / "b.json", {})
    diags = cost_model.run_cost_rules(
        [("fixture", closed, spec)],
        baseline_path=tmp_path / "b.json",
        report_path=tmp_path / "r.json",
    )
    assert not _by_rule(diags, "cost-ratchet")


def test_expected_bytes_match_documented_anchors():
    """Pin the docs/collectives.md 'Exact per-impl byte tables' anchor
    numbers (single E=308400 bucket, K=2, bf16 wire, 8 devices)."""
    e = (308400,)
    assert cost_model.expected_collective_bytes(
        _spec(kind="ring_overlap", n_dev=8, bucket_elems=e)
    ) == (1619100, 0)
    assert cost_model.expected_collective_bytes(
        _spec(kind="hier_overlap", n_dev=4, n_host=2, bucket_elems=e)
    ) == (1387800, 231300)
    assert cost_model.expected_collective_bytes(
        _spec(kind="zero3_ring", n_dev=8, bucket_elems=e)
    ) == (2158800, 0)
    assert cost_model.expected_collective_bytes(
        _spec(kind="zero3_hier", n_dev=4, n_host=2, bucket_elems=e)
    ) == (1850400, 308400)
