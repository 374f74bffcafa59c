"""Device time a train step spends in ops of the optimizer update (ops
under the `optimizer` scope; an update that XLA fused into a
weight-gradient fusion counts with that fusion, as backward): device
trace joined by instruction name to the program's catalog of its compiled
step (benchmark/scope_time.py)."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "opt")
