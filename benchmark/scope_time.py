"""Device time of the train step by layer scope and phase: the join of the
device trace (instruction name, time) with the program's own catalog of
its compiled step (`parallel_cnn_tpu/obs/programs.py`: instruction name ->
scope, phase), which `zoo.train` records when it is handed a tracer.

For each device: the ops that start inside a run of `run.program`, looked
up by name in the catalog of the module that matched (`jit_step(<hash>)`
-> `jit_step`), put into groups by `group_of`, each group's intervals
united, divided by the number of runs, then averaged over the devices.
Where ops of two groups overlap in time (the CPU's thunks; never on a
TPU core), the time goes to the group named first in `order`, so the
groups never add up to more than the device was busy. Time is never split
inside a fusion: a fusion is one op and has one name, its hero's.

Returns None — the metric is left out — where there is no trace, no run
of the program in it, or no catalog (a program that records none).
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Sequence

from benchmark import trace_reduce as tr

UNNAMED = "unnamed"


def catalog_of(module: str):
    """The program's catalog for a module run's name, or None where the
    program has no catalog (or no such module in it)."""
    try:
        from parallel_cnn_tpu.obs import programs
    except ImportError:  # a program from before the catalog
        return None
    return programs.lookup(module.split("(")[0])


def split(run, group_of: Callable, order: Sequence[str]) -> Optional[Dict[str, float]]:
    """ms a step and device per group. `group_of(entry)` names the group
    of a catalog entry (None: the op counts as unnamed, as does an op the
    catalog does not know)."""
    if run.trace is None:
        return None
    rx = re.compile(run.program)
    per_dev = []
    for d, ops in run.trace.ops.items():
        runs = sorted((s, e, n) for n, s, e in run.trace.modules.get(d, ())
                      if rx.search(n))
        if not runs:
            continue
        catalog = catalog_of(runs[0][2])
        if catalog is None:
            return None
        groups: Dict[str, list] = {}
        j = 0
        for o in ops:  # in time order, as the runs are
            while j < len(runs) and runs[j][1] <= o.start:
                j += 1
            if j == len(runs):
                break
            if runs[j][0] <= o.start:
                entry = catalog.get(o.name)
                group = group_of(entry) if entry is not None else None
                groups.setdefault(group or UNNAMED, []).append((o.start, o.end))
        names = [*order, *sorted(set(groups) - set(order) - {UNNAMED}), UNNAMED]
        busy = tr.union(iv for g in groups.values() for iv in g)
        took = tr.attribute(busy, groups, names)
        took.pop("unattributed", None)
        per_dev.append({g: ns / len(runs) / 1e6 for g, ns in took.items()})
    if not per_dev:
        return None
    return {g: sum(p.get(g, 0.0) for p in per_dev) / len(per_dev)
            for g in set().union(*per_dev)}


PHASES = ("fwd", "bwd", "opt")


def phases(run) -> Optional[Dict[str, float]]:
    """ms a step in ops of phase fwd / bwd / opt, and `unnamed`."""
    return split(run, lambda e: e.phase, PHASES)


def phase_ms(run, phase: str) -> Optional[float]:
    got = phases(run)
    return got.get(phase, 0.0) if got else None


def table(run) -> Optional[Dict[str, float]]:
    """ms a step by `<scope> <phase>` (what names a `fusion.N`)."""
    return split(run, lambda e: f"{e.scope} {e.phase}" if e.scope else None, ())
