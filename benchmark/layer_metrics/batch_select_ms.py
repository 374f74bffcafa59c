"""Median device-busy time inside one run of the device loader's batch
selection (`train/zoo.py:select_batch`, module `jit_select_batch`), over
the chips that ran it: `step_device_ms`'s reduction on another program.
Device trace; nothing where the program is not in the trace (a loader that
indexes eagerly, as before PR 25)."""

from types import SimpleNamespace

from benchmark.layer_metrics import step_device_ms

PROGRAM = r"^jit_select_batch\b"


def read(run):
    return step_device_ms.read(SimpleNamespace(trace=run.trace, program=PROGRAM))
