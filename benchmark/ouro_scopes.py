"""The mechanisms of an `ouro` model (nn/ouro.py) by the scopes it opens:
what the readers `loop_*` group the step's device time by, and the count
of attention-core kernel calls (benchmark/scope_time.py does the join and
the sums of time; benchmark/shapes/ouro.py counts the work). The program
runs its T passes as one traced body under the scope `ut`, so a scope
holds all T passes' ops of its layer.

    stack   ut/l<i>/...       the T x L layer applications: forward,
                              rematerialised forward and backward
    core    ut/l<i>/attn/core the attention cores among them
    exits   ut/exit/..., mix  the T exits (final norm, head, the blocked
                              cross-entropy, the gate), the exit
                              distribution, its entropy and the mixture
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from benchmark import scope_time
from benchmark.bailing_hybrid_scopes import roofline  # noqa: F401  (least time of passes over a measured time)
from benchmark.shapes import ouro as shapes

_LAYER = re.compile(r"l\d+$")


def mechanisms(entry) -> tuple:
    """The mechanisms a catalog entry belongs to (a core is the stack's
    too)."""
    parts = entry.scope.split("/")
    if parts[0] == "mix" or parts[:2] == ["ut", "exit"]:
        return ("exits",)
    if len(parts) >= 2 and parts[0] == "ut" and _LAYER.match(parts[1]):
        return ("stack", "core") if parts[2:4] == ["attn", "core"] else ("stack",)
    return ()


def ms(run, name: str) -> Optional[float]:
    """ms a step in ops of one mechanism; None where nothing was read."""
    got = scope_time.split(
        run, lambda e: name if name in mechanisms(e) else None, (name,))
    return (got.get(name) or None) if got else None


def core_kernel_calls(run) -> Optional[Dict[str, float]]:
    """Executed kernel calls (`custom-call`s) a step under `ut/l<i>/attn/
    core`, by the catalog's phase (`fwd`: the forward pass's; `bwd`: the
    backward kernels and any forward the backward ran again), a mean over
    the program's runs and the devices; None where there is no trace, no
    run, no catalog or no such call (the plain path has no kernel)."""
    if run.trace is None:
        return None
    rx = re.compile(run.program)
    per_dev = []
    for d, ops in run.trace.ops.items():
        runs = sorted((s, e, n) for n, s, e in run.trace.modules.get(d, ())
                      if rx.search(n))
        if not runs:
            continue
        catalog = scope_time.catalog_of(runs[0][2])
        if catalog is None:
            return None
        seen = {"fwd": 0, "bwd": 0}
        j = 0
        for o in ops:  # in time order, as the runs are
            while j < len(runs) and runs[j][1] <= o.start:
                j += 1
            if j == len(runs):
                break
            entry = catalog.get(o.name) if runs[j][0] <= o.start else None
            if (entry is not None and entry.opcode == "custom-call"
                    and "core" in mechanisms(entry) and entry.phase in seen):
                seen[entry.phase] += 1
        per_dev.append({k: v / len(runs) for k, v in seen.items()})
    if not per_dev or not any(sum(p.values()) for p in per_dev):
        return None
    return {k: sum(p[k] for p in per_dev) / len(per_dev) for k in ("fwd", "bwd")}
