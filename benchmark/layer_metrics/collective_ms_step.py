"""Time in collective ops (all-reduce, all-gather, reduce-scatter,
collective-permute; asynchronous ones from start to done) per train step
and chip: the union of their intervals inside the step program's runs, averaged over runs and chips. Device
trace."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    per_dev = []
    for d in run.trace.ops:
        runs = run.trace.runs(d, run.program)
        if runs:
            inside = tr.intersect(tr.union(runs), run.trace.collectives(d))
            per_dev.append(tr.total(inside) / len(runs))
    return sum(per_dev) / len(per_dev) / 1e6 if per_dev else None
