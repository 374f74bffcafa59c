"""Share of the collective time during which no compute op ran on the
same chip (so the step waited for the wire), over all chips. Device
trace."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    inside = alone = 0.0
    for d in run.trace.ops:
        coll = run.trace.collectives(d)
        inside += tr.total(coll)
        alone += tr.exposed(coll, run.trace.compute(d))
    return 100.0 * alone / inside if inside else None
