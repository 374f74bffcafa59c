"""Source-level (AST) lint rules.

Per-file rules (:func:`scan_module`):

- ``time-in-jit``: wall-clock / host-RNG calls inside a jitted body.
  They execute once at trace time and are frozen into the compiled
  program — a classic silent-staleness bug.
- ``env-outside-config``: ``os.environ`` / ``os.getenv`` reads outside
  ``config.py``.  Env handling is centralized so retrace behaviour and
  documentation stay auditable; deliberate module-level knobs carry
  waivers.
- ``captured-mutation``: statements inside a jitted body that mutate an
  object captured from outside the jit scope (module global, closure
  over un-jitted code).  Trace-time mutation runs once per *compile*,
  not once per call.
- ``shape-branch`` (warning): ``if``/``while`` tests on a traced
  argument's ``.shape`` inside a jitted body — every distinct shape
  specializes a new executable, so branch-heavy shape logic multiplies
  retraces.
- ``donation-source``: a donating entry point (``batched_step`` et al.
  donate argument 0) is called and the donated buffer's name is read
  afterwards without rebinding — the classic read-after-donation UAF.
- ``mesh-outside-plan``: a ``Mesh(...)`` / ``make_*_mesh(...)`` call
  outside ``parallel_cnn_tpu/plan/`` (and the constructors' home,
  ``parallel/mesh.py``).  Topology resolves through the ExecutionPlan
  — the single mesh-construction site — so plan fingerprints stay
  truthful; test sites waive with a mandatory reason.

Repo-level rules (:func:`env_doc_parity`, :func:`doc_xref`):

- ``env-doc-parity``: every ``PCNN_*`` env var read by code must be
  documented in README/docs, and every documented var must be read
  somewhere.
- ``doc-xref``: ``--flags`` and ``module.symbol()`` references in the
  live docs must resolve against the argparse definitions / package
  modules they describe.
- ``doc-path-missing``: a backticked file name in the live docs
  (`train/zoo.py`, `chip_smoke.py:49`) must exist in the repo or, in
  the docs' short form, under ``parallel_cnn_tpu/``.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from parallel_cnn_tpu.analysis.diagnostics import (
    Diagnostic,
    REPO_ROOT,
    Severity,
    relpath,
)

# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------

def dotted_name(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ("jax.jit", "os.environ")."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


_JIT_NAMES = {"jit", "jax.jit", "pjit", "jax.pjit"}


def _is_jit_expr(node: ast.AST) -> bool:
    if dotted_name(node) in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        fn = dotted_name(node.func)
        if fn in _JIT_NAMES:
            return True  # jax.jit(f) / jax.jit(static_argnames=...)(f)
        if fn in {"functools.partial", "partial"} and node.args:
            return _is_jit_expr(node.args[0])
    return False


def jitted_functions(tree: ast.Module) -> Set[ast.FunctionDef]:
    """Functions whose bodies run under trace: decorated with (a partial
    of) jax.jit, or wrapped via ``g = jax.jit(f)``."""
    all_defs: List[ast.FunctionDef] = [
        n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
    ]
    out: Set[ast.FunctionDef] = set()
    for fd in all_defs:
        if any(_is_jit_expr(d) for d in fd.decorator_list):
            out.add(fd)
    for node in ast.walk(tree):
        # jax.jit(f, ...) wrapper form: first positional arg names a def.
        # Same-named defs are disambiguated by the nearest definition
        # textually preceding the wrap (a closure wrapped where it was
        # just defined beats a method of the same name elsewhere).
        if isinstance(node, ast.Call) and dotted_name(node.func) in _JIT_NAMES:
            if node.args and isinstance(node.args[0], ast.Name):
                candidates = [
                    d for d in all_defs
                    if d.name == node.args[0].id and d.lineno <= node.lineno
                ]
                if candidates:
                    out.add(max(candidates, key=lambda d: d.lineno))
    return out


def _function_locals(fd: ast.FunctionDef) -> Set[str]:
    """Names bound inside ``fd`` itself (params + assignments), not
    recursing into nested function bodies."""
    names: Set[str] = set()
    a = fd.args
    for arg in (
        list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        + ([a.vararg] if a.vararg else []) + ([a.kwarg] if a.kwarg else [])
    ):
        names.add(arg.arg)

    class _Binder(ast.NodeVisitor):
        def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
            if node is not fd:
                names.add(node.name)
                return  # don't descend into nested scopes
            self.generic_visit(node)

        visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

        def visit_Lambda(self, node: ast.Lambda) -> None:
            return

        def visit_ClassDef(self, node: ast.ClassDef) -> None:
            names.add(node.name)

        def visit_Assign(self, node: ast.Assign) -> None:
            for t in node.targets:
                self._bind_target(t)
            self.generic_visit(node)

        def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
            self._bind_target(node.target)
            self.generic_visit(node)

        def visit_AugAssign(self, node: ast.AugAssign) -> None:
            self._bind_target(node.target)
            self.generic_visit(node)

        def visit_NamedExpr(self, node: ast.NamedExpr) -> None:
            self._bind_target(node.target)
            self.generic_visit(node)

        def visit_For(self, node: ast.For) -> None:
            self._bind_target(node.target)
            self.generic_visit(node)

        def visit_With(self, node: ast.With) -> None:
            for item in node.items:
                if item.optional_vars is not None:
                    self._bind_target(item.optional_vars)
            self.generic_visit(node)

        def visit_Import(self, node: ast.Import) -> None:
            for al in node.names:
                names.add((al.asname or al.name).split(".")[0])

        def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
            for al in node.names:
                names.add(al.asname or al.name)

        def visit_comprehension(self, node: ast.comprehension) -> None:
            self._bind_target(node.target)
            self.generic_visit(node)

        def _bind_target(self, t: ast.AST) -> None:
            if isinstance(t, ast.Name):
                names.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    self._bind_target(e)
            elif isinstance(t, ast.Starred):
                self._bind_target(t.value)

    _Binder().visit(fd)
    return names


def _base_name(node: ast.AST) -> Optional[str]:
    """Innermost Name at the root of an attribute/subscript chain."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


# ---------------------------------------------------------------------------
# Per-file rules
# ---------------------------------------------------------------------------

_WALL_CLOCK = {
    "time.time", "time.perf_counter", "time.perf_counter_ns", "time.monotonic",
    "time.monotonic_ns", "time.process_time", "time.time_ns",
    "datetime.datetime.now", "datetime.now", "datetime.datetime.utcnow",
}
_HOST_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")

# Entry points that donate an argument: {callable name: donated arg index}.
DONATING_CALLS: Dict[str, int] = {
    "scan_epoch": 0,
    "batched_step": 0,
    "fused_batched_step": 0,
    "pallas_batched_step": 0,
}

# Method names that unambiguously mutate a container.  "update"/"add"
# are deliberately absent: they collide with pervasive pure-functional
# APIs (optax's optimizer.update, jnp's .add) — the global-mutation rule
# in concurrency.py still covers them where the receiver is provably a
# module-level container literal.
_MUTATOR_METHODS = {
    "append", "extend", "insert", "pop", "popitem", "remove", "clear",
    "setdefault", "discard", "sort",
}


def scan_module(path: Path, tree: ast.Module, source: str) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    rel = relpath(path)
    is_config = path.name == "config.py"
    in_package = "parallel_cnn_tpu" in Path(rel).parts

    # --- env-outside-config: anywhere in the package except config.py ---
    if in_package and not is_config:
        for node in ast.walk(tree):
            hit = None
            if isinstance(node, ast.Attribute) and dotted_name(node) == "os.environ":
                hit = node
            elif isinstance(node, ast.Call) and dotted_name(node.func) in (
                "os.getenv", "getenv",
            ):
                hit = node
            if hit is not None:
                diags.append(Diagnostic(
                    rule="env-outside-config",
                    severity=Severity.ERROR,
                    file=rel,
                    line=hit.lineno,
                    message="os.environ read outside config.py; route the knob "
                            "through a *Config.from_env or waive with a reason",
                ))

    # --- mesh-outside-plan: mesh construction outside the plan layer ---
    # The ExecutionPlan (parallel_cnn_tpu/plan/) is the ONE mesh
    # resolution site: every `Mesh(...)` / `make_*_mesh(...)` call
    # elsewhere builds topology the plan cannot see (fingerprints,
    # checkpoint gating, and the elastic recompile-once cache all go
    # blind). parallel/mesh.py itself (the constructors' home) is
    # exempt; test sites waive with a mandatory reason.
    rel_posix = Path(rel).as_posix()
    if not (
        "parallel_cnn_tpu/plan" in rel_posix
        or rel_posix.endswith("parallel/mesh.py")
    ):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func)
            short = fn.split(".")[-1]
            # `<plan>.make_mesh()` — a method call on an ExecutionPlan —
            # IS the sanctioned site; only the mesh-module constructors
            # (unique names, or `make_mesh` reached through the module)
            # are rogue.
            base = fn.rsplit(".", 1)[0] if "." in fn else ""
            rogue_make_mesh = short == "make_mesh" and (
                base in ("", "mesh", "mesh_lib")
                or base.endswith("parallel.mesh")
            )
            if short in (
                "Mesh", "make_hier_mesh", "make_pipeline_mesh",
                "make_elastic_mesh", "single_device_mesh",
            ) or rogue_make_mesh:
                diags.append(Diagnostic(
                    rule="mesh-outside-plan",
                    severity=Severity.ERROR,
                    file=rel,
                    line=node.lineno,
                    message=f"'{fn}(...)' constructs a mesh outside "
                            "parallel_cnn_tpu/plan/; route topology through "
                            "plan.build_plan(...).make_mesh() — the single "
                            "resolution site — or waive with a reason at a "
                            "test site",
                ))

    jits = jitted_functions(tree)

    for fd in jits:
        # Locals visible across the whole lexical jit region: the jitted
        # function plus every function nested inside it.  A name bound in
        # ANY of those scopes is trace-local; only mutations of names
        # bound outside the region (globals/closures over un-jitted
        # code) are flagged.
        region_locals: Set[str] = set()
        region_fns: List[ast.FunctionDef] = [fd]
        for node in ast.walk(fd):
            if isinstance(node, ast.FunctionDef) and node is not fd:
                region_fns.append(node)
        for f in region_fns:
            region_locals |= _function_locals(f)

        params = {
            a.arg for a in list(fd.args.posonlyargs) + list(fd.args.args)
            + list(fd.args.kwonlyargs)
        }

        for node in ast.walk(fd):
            # --- time-in-jit ---
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                if fn in _WALL_CLOCK or fn.startswith(_HOST_RNG_PREFIXES):
                    diags.append(Diagnostic(
                        rule="time-in-jit",
                        severity=Severity.ERROR,
                        file=rel,
                        line=node.lineno,
                        message=f"'{fn}()' inside jitted '{fd.name}' runs once at "
                                "trace time and is frozen into the executable",
                    ))
                # mutating method call on a captured object
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATOR_METHODS
                ):
                    base = _base_name(node.func.value)
                    if base and base not in region_locals and base != "self":
                        diags.append(Diagnostic(
                            rule="captured-mutation",
                            severity=Severity.ERROR,
                            file=rel,
                            line=node.lineno,
                            message=f"'{base}.{node.func.attr}(...)' mutates an "
                                    f"object captured from outside jitted "
                                    f"'{fd.name}'; trace-time mutation runs per "
                                    "compile, not per call",
                        ))

            # --- captured-mutation via assignment/augassign/delete ---
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = list(node.targets)
            for t in targets:
                if isinstance(t, (ast.Subscript, ast.Attribute)):
                    base = _base_name(t)
                    if base and base not in region_locals and base != "self":
                        diags.append(Diagnostic(
                            rule="captured-mutation",
                            severity=Severity.ERROR,
                            file=rel,
                            line=node.lineno,
                            message=f"write to '{base}[...]' mutates an object "
                                    f"captured from outside jitted '{fd.name}'",
                        ))

            # --- shape-branch (warning) ---
            if isinstance(node, (ast.If, ast.While)):
                for sub in ast.walk(node.test):
                    if (
                        isinstance(sub, ast.Attribute)
                        and sub.attr == "shape"
                        and isinstance(sub.value, ast.Name)
                        and sub.value.id in params
                    ):
                        diags.append(Diagnostic(
                            rule="shape-branch",
                            severity=Severity.WARNING,
                            file=rel,
                            line=node.lineno,
                            message=f"branch on '{sub.value.id}.shape' inside "
                                    f"jitted '{fd.name}': each distinct shape "
                                    "specializes a new executable",
                        ))
                        break

    # --- donation-source: read-after-donation at call sites ---
    diags.extend(_donation_reads(rel, tree))
    return diags


def _scope_walk(scope: ast.AST):
    """Yield nodes of one function scope WITHOUT descending into nested
    FunctionDef/Lambda bodies (each is its own dataflow scope)."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _donation_reads(rel: str, tree: ast.Module) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    scopes = [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.Lambda))
    ]
    for fd in scopes:
        # Collect (call lineno, donated-arg name, callee) then look for
        # later loads without an intervening rebind.  The walk stays in
        # THIS scope: a read in a sibling lambda/def is a different
        # dataflow (make_jaxpr thunks in the analyzers themselves would
        # otherwise cross-contaminate).
        events: List[Tuple[int, str, str]] = []
        rebinds: Dict[str, List[int]] = {}
        loads: Dict[str, List[int]] = {}
        for node in _scope_walk(fd):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    rebinds.setdefault(node.id, []).append(node.lineno)
                elif isinstance(node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append(node.lineno)
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                short = callee.split(".")[-1]
                if short in DONATING_CALLS:
                    idx = DONATING_CALLS[short]
                    if len(node.args) > idx and isinstance(node.args[idx], ast.Name):
                        events.append((node.lineno, node.args[idx].id, short))
        for call_line, name, callee in events:
            later_loads = [ln for ln in loads.get(name, []) if ln > call_line]
            for ln in later_loads:
                rebound_between = any(
                    call_line <= rb <= ln for rb in rebinds.get(name, [])
                )
                if not rebound_between:
                    diags.append(Diagnostic(
                        rule="donation-source",
                        severity=Severity.ERROR,
                        file=rel,
                        line=ln,
                        message=f"'{name}' is read after being donated to "
                                f"'{callee}' (line {call_line}); donated "
                                "buffers may be aliased by the output — rebind "
                                "or copy before reuse",
                    ))
                    break  # one finding per donation event
    return diags


# ---------------------------------------------------------------------------
# Repo-level rule: env-doc parity
# ---------------------------------------------------------------------------

_ENV_RE = re.compile(r"\bPCNN_[A-Z0-9_]*[A-Z0-9]\b")


def _env_vars_in(text: str) -> Dict[str, int]:
    """var -> first line it appears on."""
    out: Dict[str, int] = {}
    for i, line in enumerate(text.splitlines(), start=1):
        for m in _ENV_RE.finditer(line):
            out.setdefault(m.group(0), i)
    return out


def env_doc_parity(
    code_files: Sequence[Path], doc_files: Sequence[Path]
) -> List[Diagnostic]:
    code_sites: Dict[str, Tuple[str, int]] = {}
    for p in code_files:
        try:
            text = p.read_text()
        except OSError:
            continue
        for var, line in _env_vars_in(text).items():
            code_sites.setdefault(var, (relpath(p), line))
    doc_sites: Dict[str, Tuple[str, int]] = {}
    for p in doc_files:
        try:
            text = p.read_text()
        except OSError:
            continue
        for var, line in _env_vars_in(text).items():
            doc_sites.setdefault(var, (relpath(p), line))

    diags: List[Diagnostic] = []
    for var, (file, line) in sorted(code_sites.items()):
        if var not in doc_sites:
            diags.append(Diagnostic(
                rule="env-doc-parity",
                severity=Severity.ERROR,
                file=file,
                line=line,
                message=f"env var {var} is read by code but documented nowhere "
                        "in README.md or docs/",
            ))
    for var, (file, line) in sorted(doc_sites.items()):
        if var not in code_sites:
            diags.append(Diagnostic(
                rule="env-doc-parity",
                severity=Severity.ERROR,
                file=file,
                line=line,
                message=f"env var {var} is documented but no code reads it "
                        "(renamed or removed?)",
            ))
    return diags


# ---------------------------------------------------------------------------
# Repo-level rule: doc cross-references (flags, symbols, repo paths)
# ---------------------------------------------------------------------------

# Our flags are hyphenated; externally-owned flags quoted in docs
# (e.g. --xla_force_host_platform_device_count) use underscores and are
# skipped.
_FLAG_RE = re.compile(r"(?<![\w`-])--[a-z][a-z0-9]*(?:-[a-z0-9]+)*\b")

# A code span that is one token and looks like a file of this repo:
# `train/zoo.py`, `chip_smoke.py:49`, `serve/net.py:NetServer`.  Only a
# whole span counts — a command (a span with spaces), a fenced block and
# a placeholder path (`<dir>/trace.json`) are not tokens, so a file that
# a command creates at run time is never taken for a file of the repo.
_PATH_RE = re.compile(
    r"`([\w./-]+\.(?:py|sh|jsonl|json|md|toml))(?::[\w.-]+)?`"
)

# api.md writes calls as `alias.symbol(...)`; map the aliases it uses to
# importable modules so the references can be resolved.
_DOC_MODULE_ALIASES = {
    "trainer": "parallel_cnn_tpu.train.trainer",
    "step": "parallel_cnn_tpu.train.step",
    "zoo": "parallel_cnn_tpu.train.zoo",
    "checkpoint": "parallel_cnn_tpu.train.checkpoint",
    "mesh": "parallel_cnn_tpu.parallel.mesh",
    "collectives": "parallel_cnn_tpu.parallel.collectives",
    "data_parallel": "parallel_cnn_tpu.parallel.data_parallel",
    "intra_op": "parallel_cnn_tpu.parallel.intra_op",
    "zoo_sharding": "parallel_cnn_tpu.parallel.zoo_sharding",
    "distributed": "parallel_cnn_tpu.parallel.distributed",
    "registry": "parallel_cnn_tpu.serve.registry",
    "engine": "parallel_cnn_tpu.serve.engine",
    "batcher": "parallel_cnn_tpu.serve.batcher",
    "telemetry": "parallel_cnn_tpu.serve.telemetry",
    "loadgen": "parallel_cnn_tpu.serve.loadgen",
    "sentinel": "parallel_cnn_tpu.resilience.sentinel",
    "preempt": "parallel_cnn_tpu.resilience.preempt",
    "chaos": "parallel_cnn_tpu.resilience.chaos",
    "metrics": "parallel_cnn_tpu.utils.metrics",
    "probe": "parallel_cnn_tpu.utils.probe",
    "pallas_conv": "parallel_cnn_tpu.ops.pallas_conv",
    "pallas_update": "parallel_cnn_tpu.ops.pallas_update",
    "pallas_tail": "parallel_cnn_tpu.ops.pallas_tail",
    "obs": "parallel_cnn_tpu.obs",
    "plan": "parallel_cnn_tpu.plan",
}
_SYMBOL_RE = re.compile(r"`([a-z_][a-z0-9_]*)\.([a-z_][A-Za-z0-9_]*)\(")


def defined_cli_flags(parser_files: Sequence[Path]) -> Set[str]:
    flags: Set[str] = set()
    for p in parser_files:
        try:
            tree = ast.parse(p.read_text())
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument"
            ):
                for a in node.args:
                    if isinstance(a, ast.Constant) and isinstance(a.value, str):
                        if a.value.startswith("--"):
                            flags.add(a.value)
    return flags


def doc_xref(
    doc_files: Sequence[Path],
    parser_files: Sequence[Path],
    repo_root: Path = REPO_ROOT,
) -> List[Diagnostic]:
    import importlib

    diags: List[Diagnostic] = []
    flags = defined_cli_flags(parser_files)
    # Docs write package files in short form (`train/zoo.py`).
    path_roots = (repo_root, repo_root / "parallel_cnn_tpu")

    mod_cache: Dict[str, Optional[object]] = {}

    def _module(alias: str):
        if alias not in mod_cache:
            target = _DOC_MODULE_ALIASES.get(alias)
            if target is None:
                mod_cache[alias] = None
            else:
                try:
                    mod_cache[alias] = importlib.import_module(target)
                except Exception:
                    mod_cache[alias] = None
        return mod_cache[alias]

    for p in doc_files:
        try:
            text = p.read_text()
        except OSError:
            continue
        rel = relpath(p)
        for i, line in enumerate(text.splitlines(), start=1):
            for m in _FLAG_RE.finditer(line):
                flag = m.group(0)
                if "_" in flag:
                    continue  # externally-owned flag quoted in docs
                if flag not in flags:
                    diags.append(Diagnostic(
                        rule="doc-xref",
                        severity=Severity.ERROR,
                        file=rel,
                        line=i,
                        message=f"doc references CLI flag '{flag}' which no "
                                "argparse parser defines",
                    ))
            for m in _PATH_RE.finditer(line):
                if not any((r / m.group(1)).exists() for r in path_roots):
                    diags.append(Diagnostic(
                        rule="doc-path-missing",
                        severity=Severity.ERROR,
                        file=rel,
                        line=i,
                        message=f"doc names the file '{m.group(1)}' which is "
                                "neither in the repo nor in parallel_cnn_tpu/ "
                                "(deleted or renamed?)",
                    ))
            for m in _SYMBOL_RE.finditer(line):
                alias, symbol = m.group(1), m.group(2)
                mod = _module(alias)
                if mod is not None and not hasattr(mod, symbol):
                    diags.append(Diagnostic(
                        rule="doc-xref",
                        severity=Severity.ERROR,
                        file=rel,
                        line=i,
                        message=f"doc references '{alias}.{symbol}()' but "
                                f"{_DOC_MODULE_ALIASES[alias]} has no attribute "
                                f"'{symbol}'",
                    ))
    return diags
