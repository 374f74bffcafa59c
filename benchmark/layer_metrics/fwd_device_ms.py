"""Device time a train step spends in ops of the forward pass (ops under
the `grad` scope with no `transpose(` in their `op_name`): device trace
joined by instruction name to the program's catalog of its compiled step
(benchmark/scope_time.py)."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "fwd")
