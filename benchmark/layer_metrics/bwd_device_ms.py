"""Device time a train step spends in ops of the backward pass (ops under
the `grad` scope whose `op_name` carries a `transpose(`): device trace
joined by instruction name to the program's catalog of its compiled step
(benchmark/scope_time.py)."""

from benchmark import scope_time


def read(run):
    return scope_time.phase_ms(run, "bwd")
