"""Set-up by kind of work: what the program's own compile log
(`parallel_cnn_tpu/obs/compiles.py`: one record a trace, a lowering and a
compile, by function name, with the persistent cache's word on every
compile) holds of the time before the window. The six `setup_*` readers
under `benchmark/layer_metrics/` are sums over `records(run)`.

A record counts when it starts inside `[t_process, t_process + setup_s]`
(`benchmark/run.py`'s process start and its own `setup_s`, so the sums
are parts of that metric) and was not made by the tracing itself
(`within == "zoo.catalog"`: the compiled step recorded for the device
trace's join, which an untraced run does not pay; `zoo.train` catalogs
once epoch 1's steps are dispatched, when the loop has asked for every
program of its step itself — under a mesh there are two — so nothing an
untraced run pays lies in there). A record's `seconds`
is its own time — what ran inside it is kept apart and taken off — so no
sum counts a second twice and trace + lower + compile <= `setup_s`.

The log's starts are on `time.perf_counter`, the run's on
`time.monotonic`; on Linux both read CLOCK_MONOTONIC. Checked once;
where they differ nothing is read.

Returns None — the metric is left out — where the run has no `setup_s`,
the clocks differ, or the program has no such log (a program from before
it, or one whose entry point did not install it).
"""

from __future__ import annotations

import functools
import re
import time
from typing import List, Optional

CATALOG = "zoo.catalog"


@functools.lru_cache(maxsize=None)
def clocks_agree() -> bool:
    return min(abs(time.perf_counter() - time.monotonic())
               for _ in range(3)) < 1e-3


def records(run) -> Optional[List]:
    """The log's records of this run's set-up, oldest first, or None."""
    setup_s = run.e2e.get("setup_s")
    if setup_s is None:
        return None
    try:
        from parallel_cnn_tpu.obs import compiles
    except ImportError:  # a program from before the log
        return None
    if not compiles.installed() or not clocks_agree():
        return None
    lo = run.ctx.t_process
    return [r for r in compiles.records()
            if lo <= r.start <= lo + setup_s and r.within != CATALOG]


def seconds(run, keep) -> Optional[float]:
    """Own seconds of the set-up's records that `keep(record)` keeps."""
    got = records(run)
    return None if got is None else sum(r.seconds for r in got if keep(r))


def count(run, keep) -> Optional[int]:
    """How many of the set-up's records `keep(record)` keeps."""
    got = records(run)
    return None if got is None else sum(1 for r in got if keep(r))


def module_of(record) -> str:
    """The name the device trace and `run.program` know a record's
    program by: `jit(step)` (a lowering's, a compile's) and `step` (a
    trace's: the function itself) are both `jit_step`."""
    m = re.fullmatch(r"(\w+)\((.*)\)", record.fun_name)
    return f"{m.group(1)}_{m.group(2)}" if m else f"jit_{record.fun_name}"
