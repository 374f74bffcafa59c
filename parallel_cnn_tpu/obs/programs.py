"""Catalog of compiled programs: which layer and phase each HLO
instruction belongs to.

Why it exists: on this runtime (jax 0.9.0 / libtpu 0.0.34) the device
trace names an executed op by its HLO instruction (`fusion.1923`) and
carries no `op_name`, so the `jax.named_scope`s opened in `nn/` and in
`train/zoo.py:make_train_step` never reach it. The compiled program's
own text does carry them: `compiled.as_text()` prints every instruction,
and every instruction inside a `fused_computation`, with
`metadata={op_name="jit(step)/grad/transpose(jvp(s2b1))/mid/conv/..."}`.
So device time gets a name by a join: trace (instruction name, time) x
this catalog (instruction name -> scope, phase).

`parse` is pure text work. The store (`record` / `lookup` / `clear`) is
process-wide like the compile cache it describes, lock-guarded, last
record wins. Nothing here runs inside a jitted body, and nothing here
runs at all unless a caller with tracing on records a program.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import re
import threading
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Entry:
    """One executed instruction. `scope` is the layer path with transform
    wrappers stripped (`s2b1/mid/conv`; `grad` for the loss ops that sit
    in no layer; `optimizer`; "" where neither the instruction nor its
    members carry an `op_name` under `grad` or `optimizer`). `phase` is
    `fwd` | `bwd` | `opt`, and "" exactly when `scope` is "". A fusion is
    named by its hero and its time is never split among its members."""

    scope: str
    phase: str
    opcode: str
    has_conv: bool


# `  [ROOT ]%name = <shape> opcode(operands), attributes`; the shape may
# be a tuple with spaces, so the opcode is the first ` word(` after ` = `.
_INSTR = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[^\s=]+) = .*?[\]})] "
    r"(?P<opcode>[a-z][a-z\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[^\s(]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# Attributes that name computations whose instructions execute (and show
# in a trace) on their own; a fusion's `calls=` names its members instead,
# and `to_apply=` of a reduce/scatter/sort is a scalar lambda.
_EXECUTES = re.compile(
    r"\b(?:body|condition|true_computation|false_computation|calls)="
    r"%?([^\s,{}]+)|\bbranch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"\bcalls=%?([^\s,{}]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([^\s,{}]+)")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def _split(path: str) -> List[str]:
    """Split a name stack on the `/` outside parentheses."""
    out, depth, at = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[at:i])
            at = i + 1
    out.append(path[at:])
    return out


def _unwrap(component: str) -> List[str]:
    """`transpose(jvp(s1b1))` -> [`s1b1`]; an inner `jit(relu)` names a
    jitted helper, not a layer, and is dropped."""
    m = _WRAPPED.match(component)
    if not m:
        return [component] if component else []
    if m.group(1) in ("jit", "pjit"):
        return []
    return [c for part in _split(m.group(2)) for c in _unwrap(part)]


def scope_of(op_name: str) -> Tuple[str, str]:
    """(scope, phase) of one `op_name`. The name stack reads
    `jit(<fn>)/<root>/<layer path>/<primitive>`; under the root `grad` an
    op is backward iff a `transpose(` wraps any part of its path (the
    transform wraps the outermost scope opened inside the differentiated
    function, or `grad` itself for custom-jvp ops such as relu)."""
    parts = _split(op_name)
    if len(parts) < 2 or not parts[0].startswith(("jit(", "pjit(")):
        return "", ""
    body = parts[1:]
    if not _WRAPPED.match(body[-1]):
        # the primitive's own name; a Pallas kernel's call comes with the
        # kernel's name before it (`.../core/causal_attention_fwd/
        # pallas_call`), which names the instruction, not a layer
        body = body[:-2] if body[-1] == "pallas_call" else body[:-1]
    path = [c for part in body for c in _unwrap(part)]
    if not path:
        return "", ""
    if path[0] == "optimizer":
        return "optimizer", "opt"
    if path[0] != "grad":
        return "", ""
    first, *called = _bodies(path)
    path = _collapse(_layers(first))
    for body in called:
        path += _collapse(_restarted(_layers(body)))
    return "/".join(path) or "grad", "bwd" if "transpose(" in op_name else "fwd"


# Components of a name stack that name no layer: `grad` (the step's own
# root, which `jax.checkpoint` repeats inside a rematerialised layer's
# backward), the two scopes `jax.checkpoint` opens, and (the test on "->")
# the subscripts `jnp.einsum` opens a scope with.
_TRANSFORM_SCOPES = frozenset({"grad", "checkpoint", "rematted_computation"})
_BRANCH = re.compile(r"^branch_\d+_fun$")
# A body that is traced once and called where it is used (nn/ouro.py's
# passes: one `lax.scan` body emitted four times in a row) names its ops
# behind this, and inside it the names start over.
_CALLED = "closed_call"


def _bodies(path: List[str]) -> List[List[str]]:
    """`path` cut at every `_CALLED`: what lies outside any called body,
    then each body's own names. A path without one is itself, whole."""
    out: List[List[str]] = [[]]
    for c in path:
        if c == _CALLED:
            out.append([])
        else:
            out[-1].append(c)
    return out


def _layers(path: List[str]) -> List[str]:
    """`path` without what names no layer: `_TRANSFORM_SCOPES`, and the
    pair a `lax.cond` / `switch` opens around a branch (`cond/
    branch_0_fun`: a `lax.platform_dependent` is one) — a `cond` that no
    branch follows is somebody's scope and stays."""
    out: List[str] = []
    for c in path:
        if _BRANCH.match(c) and out and out[-1] == "cond":
            out.pop()
        elif c not in _TRANSFORM_SCOPES and "->" not in c:
            out.append(c)
    return out


def _collapse(path: List[str]) -> List[str]:
    """The backward of a rematerialised layer names it twice
    (`transpose(jvp(l1))/grad/jvp(l1)/checkpoint/.../moe` -> l1, l1, moe):
    a leading run that repeats itself is said once."""
    for n in range(len(path) // 2, 0, -1):
        if path[:n] == path[n:2 * n]:
            return path[:n] + path[2 * n:]
    return path


def _restarted(path: List[str]) -> List[str]:
    """Inside a called body a `cond` says the body's names so far again
    behind itself (`l1/attn/core/cond/l1/attn/core`): they are said once."""
    for i, c in enumerate(path):
        if c == "cond" and i and path[i + 1:2 * i + 1] == path[:i]:
            return _restarted(path[:i] + path[2 * i + 1:])
    return path


def _computations(hlo_text: str):
    """name -> [(instruction, opcode, op_name, line)], and the entry's name."""
    comps: Dict[str, List[Tuple[str, str, str, str]]] = {}
    entry = current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = m.group("name")
                comps[current] = []
                if line.startswith("ENTRY"):
                    entry = current
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if m:
            found = _OP_NAME.search(line)
            comps[current].append((m.group("name"), m.group("opcode"),
                                   found.group(1) if found else "", line))
    return comps, entry


def parse(hlo_text: str) -> Dict[str, Entry]:
    """One `Entry` per instruction of the entry computation and of every
    computation that executes on its own under it (`while` bodies and
    conditions, `conditional` branches, calls), keyed by the instruction's
    name exactly as the device trace prints it (`fusion.1923`).

    A fusion takes its hero's name: the member `convolution` if it has
    one, else the fusion's own `op_name`, else the (scope, phase) most of
    its named members share."""
    comps, entry = _computations(hlo_text)
    if entry is None:
        return {}

    def members(comp: str, seen: frozenset = frozenset()):
        for name, opcode, op_name, line in comps.get(comp, ()):
            yield opcode, op_name
            inner = _CALLS.search(line) if opcode == "fusion" else None
            if inner and inner.group(1) not in seen:
                yield from members(inner.group(1), seen | {comp})

    out: Dict[str, Entry] = {}
    lines: Dict[str, str] = {}
    kernels: Dict[str, str] = {}
    todo, done = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in done:
            continue
        done.add(comp)
        for name, opcode, op_name, line in comps.get(comp, ()):
            scope, phase = scope_of(op_name)
            has_conv = opcode == "convolution"
            if opcode == "fusion":
                called = _CALLS.search(line)
                inside = list(members(called.group(1))) if called else []
                convs = [n for oc, n in inside if oc == "convolution"]
                has_conv = bool(convs)
                named = [sp for sp in map(scope_of, convs) if sp[0]]
                if named:
                    scope, phase = named[0]
                elif not scope:
                    votes = collections.Counter(
                        sp for sp in (scope_of(n) for _, n in inside) if sp[0])
                    if votes:
                        scope, phase = votes.most_common(1)[0][0]
            else:
                for m in _EXECUTES.finditer(line):
                    todo.extend(c.strip().lstrip("%") for c in
                                (m.group(1) or m.group(2)).split(",") if c.strip())
                if opcode == "call":
                    todo.extend(_TO_APPLY.findall(line))
            out[name] = Entry(scope, phase, opcode, has_conv)
            lines[name] = line
            if opcode == "custom-call" and op_name and not scope:
                kernels[name] = op_name
    # A kernel the compiler itself put in carries its own name in place
    # of a name stack (`lax.ragged_dot` becomes a `custom-call` whose
    # `op_name` is `ragged-dot-none`): it is the layer's that made its
    # operands, and says what it is (`l1/moe/experts/ragged-dot-none`).
    # A custom-call with no `op_name` at all stays unnamed.
    for name, kernel in kernels.items():
        scope, phase = _of_operands(lines[name], out, lines)
        if scope:
            out[name] = dataclasses.replace(
                out[name], scope=f"{scope}/{kernel}", phase=phase)
    return out


_OPERAND = re.compile(r"%([^\s,()]+)")


def _operands(line: str) -> List[str]:
    """Names of an instruction's operands, in order."""
    m = _INSTR.match(line)
    depth = 0
    for i in range(m.end() - 1, len(line)):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        if depth == 0:
            return _OPERAND.findall(line[m.end():i])
    return []


def _of_operands(line: str, entries: Dict[str, Entry], lines: Dict[str, str],
                 depth: int = 3) -> Tuple[str, str]:
    """(scope, phase) of the last operand that has one (a matmul's weights
    come last), looked for through operands that have none themselves
    (copies between memories, tuple elements) a few levels down."""
    for name in reversed(_operands(line)):
        entry = entries.get(name)
        if entry is None:
            continue
        if entry.scope:
            return entry.scope, entry.phase
        if depth and entry.opcode != "parameter":
            found = _of_operands(lines[name], entries, lines, depth - 1)
            if found[0]:
                return found
    return "", ""


# ------------------------------------------------------------------ store

_LOCK = threading.Lock()
_CATALOGS: Dict[str, Dict[str, Entry]] = {}


def record(name: str, compiled_or_text) -> None:
    """Parse and keep a compiled program under its module's short name
    (`jit_step`). Takes the HLO text, or anything with `as_text()` (a
    `jax.stages.Compiled`)."""
    text = (compiled_or_text if isinstance(compiled_or_text, str)
            else compiled_or_text.as_text())
    catalog = parse(text)
    with _LOCK:
        # graftcheck: disable=global-mutation -- held under this module's _LOCK, which the lint does not look for
        _CATALOGS[name] = catalog


def lookup(name: str) -> Optional[Dict[str, Entry]]:
    """The catalog of a recorded program, or None."""
    with _LOCK:
        return _CATALOGS.get(name)


def clear() -> None:
    with _LOCK:
        # graftcheck: disable=global-mutation -- held under this module's _LOCK, which the lint does not look for
        _CATALOGS.clear()


def export(path: str) -> Optional[str]:
    """Write every recorded program's catalog as JSON
    ({program: {instruction: {scope, phase, opcode, has_conv}}}): what
    names the ops of the Perfetto/XProf trace written beside it. Writes
    nothing, and returns None, where no program was recorded."""
    with _LOCK:
        payload = {n: {k: dataclasses.asdict(e) for k, e in c.items()}
                   for n, c in sorted(_CATALOGS.items())}
    if not payload:
        return None
    with open(path, "w") as f:
        json.dump(payload, f)
    return path
