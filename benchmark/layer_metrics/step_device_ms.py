"""Median device-busy time inside one run of the train step's program
(union of the executed ops' intervals), over all chips. Device trace."""

from benchmark import common


def read(run):
    if run.trace is None:
        return None
    per_run = [ns for d in run.trace.ops
               for ns in run.trace.busy_per_run(d, run.program)]
    return common.median(per_run) / 1e6 if per_run else None
