"""Share of device-busy time spent in convolution ops (by the trace's HLO
category; the rest is BatchNorm, elementwise chains, copies, the
optimizer), over all chips. Device trace."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None:
        return None
    conv = busy = 0.0
    for d in run.trace.ops:
        conv += tr.total(tr.union(run.trace.by_category(d, "conv")))
        busy += tr.total(run.trace.busy(d))
    return 100.0 * conv / busy if busy and conv else None
