"""Seconds of set-up spent tracing, lowering and compiling (or loading) the
cell's train step, `run.program`, in every shape set-up uses it: the
check's small-batch programs and the loop's. The in-program counterpart of
`warmup_s`, from the program's compile log (benchmark/setup_time.py)."""

import re

from benchmark import setup_time


def read(run):
    rx = re.compile(run.program)
    return setup_time.seconds(
        run, lambda r: rx.search(setup_time.module_of(r)))
