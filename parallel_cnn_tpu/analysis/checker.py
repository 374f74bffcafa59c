"""graftcheck orchestrator: run the analyzer families, apply waivers
and the ratchet baseline, render the report, pick the exit code.

Used two ways:

- CLI: ``python -m parallel_cnn_tpu check`` (cli.py dispatch).
- Tests: ``tests/test_analysis.py`` calls :func:`run_check` /
  individual families directly.
"""

from __future__ import annotations

import ast
import json
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from parallel_cnn_tpu.analysis.diagnostics import (
    DEFAULT_BASELINE,
    Diagnostic,
    REPO_ROOT,
    Severity,
    Waiver,
    apply_waivers,
    load_baseline,
    parse_waivers,
    ratchet,
    relpath,
    render_report,
    save_baseline,
)

PACKAGE_DIR = REPO_ROOT / "parallel_cnn_tpu"

# Live documentation set for the parity/xref rules.
LIVE_DOCS = (
    "README.md",
    "docs/api.md",
    "docs/serving.md",
    "docs/collectives.md",
    "docs/fault_tolerance.md",
    "docs/kernel_authoring.md",
    "docs/static_analysis.md",
    "docs/observability.md",
    "docs/pipeline.md",
    "docs/autotuning.md",
    "docs/execution_plan.md",
    "docs/future_work.md",
)

# Host-side drivers included in the env-var scan (they read PCNN_* too).
ENV_SCAN_DRIVERS = ("__graft_entry__.py", "chip_smoke.py")

PARSER_FILES = ("parallel_cnn_tpu/cli.py", "benchmark/run.py",
                "chip_smoke.py", "parallel_cnn_tpu/analysis/checker.py")


def _package_files() -> List[Path]:
    return sorted(p for p in PACKAGE_DIR.rglob("*.py"))


def _existing(rel_paths: Sequence[str]) -> List[Path]:
    return [REPO_ROOT / r for r in rel_paths if (REPO_ROOT / r).exists()]


def doc_rule_diagnostics(doc_files: Sequence[Path]) -> List[Diagnostic]:
    """The repo-level doc rules (env-doc parity, flag/symbol/path xref)
    over ``doc_files``, against the shipped code and parsers."""
    from parallel_cnn_tpu.analysis import ast_rules

    env_code_files = _package_files() + _existing(ENV_SCAN_DRIVERS)
    return (
        ast_rules.env_doc_parity(env_code_files, doc_files)
        + ast_rules.doc_xref(doc_files, _existing(PARSER_FILES))
    )


def run_check(
    fast: bool = False,
    paths: Optional[Sequence[str]] = None,
    baseline_path: Optional[Path] = None,
    update_baseline: bool = False,
    verbose: bool = False,
    race_seeds: Tuple[int, ...] = (0, 1),
    cost: bool = False,
    cost_baseline_path: Optional[Path] = None,
    update_cost_baseline: bool = False,
    cost_report_path: Optional[Path] = None,
    cost_seeded: Optional[str] = None,
) -> Tuple[int, str, List[Diagnostic]]:
    """Run graftcheck; returns (exit_code, report, diagnostics).

    ``paths`` switches to targeted mode: ONLY the AST + concurrency
    families over exactly those files (no repo-level parity/xref, no
    jaxpr traces, no Pallas budget, no race harness) — the mode the
    rule fixtures use.
    ``fast`` keeps all families but trims the expensive configurations
    (zoo traces, deep model budgets, single race seed).
    ``cost`` adds the sharding-propagation and static-cost families
    (analysis/sharding_prop.py, analysis/cost_model.py).  They trace the
    FULL zoo entry set even under ``fast`` — the byte models are about
    the zoo collectives, there is no trimmed configuration that still
    means anything — and share one trace with each other.
    ``cost_seeded`` appends a really-traced mutant entry
    (cost_model.build_seeded_entry) to show that the gate trips; the
    mutant also runs under the jaxpr-rule families.
    """
    from parallel_cnn_tpu.analysis import ast_rules, concurrency

    diags: List[Diagnostic] = []
    waivers_by_file: Dict[str, List[Waiver]] = {}

    targeted = paths is not None
    py_files = (
        [Path(p).resolve() for p in paths] if targeted else _package_files()
    )

    for p in py_files:
        rel = relpath(p)
        try:
            source = p.read_text()
            tree = ast.parse(source)
        except OSError as e:
            diags.append(Diagnostic(
                rule="parse", severity=Severity.ERROR, file=rel, line=0,
                message=f"cannot read: {e}",
            ))
            continue
        except SyntaxError as e:
            diags.append(Diagnostic(
                rule="parse", severity=Severity.ERROR, file=rel,
                line=e.lineno or 0, message=f"syntax error: {e.msg}",
            ))
            continue
        waivers_by_file[rel] = parse_waivers(source)
        diags.extend(ast_rules.scan_module(p, tree, source))
        diags.extend(concurrency.scan_concurrency(p, tree))

    if not targeted:
        doc_files = _existing(LIVE_DOCS)
        for p in doc_files:
            waivers_by_file[relpath(p)] = parse_waivers(p.read_text())
        diags.extend(doc_rule_diagnostics(doc_files))

        from parallel_cnn_tpu.analysis import jaxpr_rules, pallas_budget

        if cost:
            from parallel_cnn_tpu.analysis import cost_model, sharding_prop

            # One full trace shared by every jaxpr-consuming family: the
            # cost/sharding analyzers need the zoo entries regardless of
            # --fast (the byte models ARE the zoo collectives).
            entries = jaxpr_rules.trace_entry_points(
                fast=False, with_specs=True
            )
            if cost_seeded:
                entries = entries + [
                    cost_model.build_seeded_entry(cost_seeded)
                ]
            for name, closed, _spec in entries:
                diags.extend(jaxpr_rules.analyze_closed_jaxpr(name, closed))
            diags.extend(sharding_prop.run_sharding_rules(entries))
            diags.extend(cost_model.run_cost_rules(
                entries,
                baseline_path=cost_baseline_path,
                update_baseline=update_cost_baseline,
                report_path=cost_report_path,
            ))
        else:
            diags.extend(jaxpr_rules.run_jaxpr_rules(fast=fast))
        diags.extend(pallas_budget.run_pallas_budget(fast=fast))
        seeds = race_seeds[:1] if fast else race_seeds
        diags.extend(concurrency.run_race_checks(seeds=seeds))

    diags = apply_waivers(diags, waivers_by_file)
    baseline = load_baseline(baseline_path)
    diags = ratchet(diags, baseline)

    if update_baseline:
        out = save_baseline(diags, baseline_path)
        # Re-ratchet against what was just written so the exit code
        # reflects the new baseline.
        for d in diags:
            d.baselined = False
        diags = ratchet(diags, load_baseline(out))

    report = render_report(diags, verbose=verbose)
    exit_code = 1 if any(d.gates() for d in diags) else 0
    return exit_code, report, diags


def verify_plan_file(
    path: Path, cost_baseline_path: Optional[Path] = None
) -> Tuple[int, str]:
    """Statically verify a plan file without running it.

    Loads the plan (schema-versioned JSON, including tune --report
    output), runs the legality matrix (plan.validate()), and resolves
    the plan's cost-table key against the cost ratchet baseline — so a
    ``tune``-emitted or hand-written plan can be vetted offline before
    any device time is spent.  Returns (exit_code, report).
    """
    from parallel_cnn_tpu import plan as plan_lib
    from parallel_cnn_tpu.analysis import cost_model

    try:
        eplan = plan_lib.load_plan(path)
    except (plan_lib.PlanSchemaError, plan_lib.PlanError, OSError,
            ValueError) as e:
        return 1, f"plan: FAIL {path}: {e}"
    try:
        eplan.validate()
    except plan_lib.PlanError as e:
        return 1, (f"plan: FAIL {path} (fingerprint "
                   f"{eplan.fingerprint()}): {e}")
    key, kind = eplan.cost_table_key()
    entries = cost_model.load_cost_baseline(
        cost_baseline_path or cost_model.DEFAULT_COST_BASELINE
    )
    lines = [
        f"plan: OK {path}",
        f"  fingerprint: {eplan.fingerprint()}",
        f"  label: {plan_lib.format_plan(eplan)}",
        f"  cost-table key: {key}"
        + (f" (closed form: {kind})" if kind else ""),
    ]
    row = entries.get(key)
    if row is not None:
        budget = ", ".join(f"{k}={v}" for k, v in sorted(row.items()))
        lines.append(f"  cost baseline: present ({budget})")
    else:
        lines.append(
            f"  cost baseline: no entry for {key!r} — run "
            "`check --cost` after tracing this topology to ratchet it"
        )
    return 0, "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Entry point behind ``python -m parallel_cnn_tpu check``."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="parallel_cnn_tpu check",
        description="graftcheck: JAX-aware static analysis "
                    "(jaxpr invariants, AST lint, Pallas VMEM budgets, "
                    "concurrency). Exit 0 = clean modulo baseline.",
    )
    ap.add_argument("--fast", action="store_true",
                    help="trim expensive configurations (zoo traces, deep "
                         "model budgets)")
    ap.add_argument("--paths", nargs="+", metavar="FILE",
                    help="targeted mode: lint ONLY these python files with "
                         "the AST/concurrency families")
    ap.add_argument("--baseline", type=Path, default=None,
                    help=f"ratchet baseline file (default {DEFAULT_BASELINE})")
    ap.add_argument("--update-baseline", action="store_true",
                    help="accept current unwaived errors into the baseline")
    ap.add_argument("--cost", action="store_true",
                    help="add the sharding-propagation + static cost "
                         "families (comm bytes vs closed form, peak HBM, "
                         "DCN/HBM ratchet); also via PCNN_CHECK_COST=1")
    ap.add_argument("--cost-baseline", type=Path, default=None,
                    metavar="PATH",
                    help="cost ratchet baseline file (default "
                         "analysis/cost_baseline.json)")
    ap.add_argument("--update-cost-baseline", action="store_true",
                    help="rewrite the cost baseline from the current tree")
    ap.add_argument("--cost-report", type=Path, default=None, metavar="PATH",
                    help="cost report output (default "
                         "analysis/cost_report.json)")
    ap.add_argument("--cost-seeded", default=None, metavar="NAME",
                    help="append a seeded mutant entry (bf16-master-gather, "
                         "partial-stage-ring) that must trip the gate")
    ap.add_argument("--plan", type=Path, default=None, metavar="PATH",
                    help="verify an ExecutionPlan file statically (schema, "
                         "legality matrix, cost-table key vs the cost "
                         "baseline) without running it; skips the analyzer "
                         "families")
    ap.add_argument("--json", type=Path, default=None, metavar="PATH",
                    help="also write diagnostics as JSON")
    ap.add_argument("--verbose", "-v", action="store_true",
                    help="include baselined and waived findings in the report")
    args = ap.parse_args(argv)

    if args.plan is not None:
        code, report = verify_plan_file(
            args.plan, cost_baseline_path=args.cost_baseline
        )
        print(report)
        return code

    code, report, diags = run_check(
        fast=args.fast,
        paths=args.paths,
        baseline_path=args.baseline,
        update_baseline=args.update_baseline,
        verbose=args.verbose,
        cost=args.cost or bool(args.cost_seeded) or args.update_cost_baseline,
        cost_baseline_path=args.cost_baseline,
        update_cost_baseline=args.update_cost_baseline,
        cost_report_path=args.cost_report,
        cost_seeded=args.cost_seeded,
    )
    if args.json:
        args.json.write_text(
            json.dumps([d.to_json() for d in diags], indent=2) + "\n"
        )
    print(report)
    return code
