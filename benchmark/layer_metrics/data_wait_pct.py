"""Share of the traced window in which the device sat idle while the
trainer loop was fetching the next batch: device idle gaps that lie inside
the program's `zoo.data` spans, over the window, averaged over the chips.

Not the spans' own length: on the chip the host spends most of the window
inside `zoo.data` blocked on the device's full queue (84 % for ResNet-50,
my chip run, PR 22) while the device is 99.6 % busy — that is backpressure,
not a wait for data."""

from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None or run.trace.window is None:
        return None
    spans = tr.union(run.trace.host.get("zoo.data", ()))
    if not spans:
        return None
    lo, hi = run.trace.window
    waited = sum(
        tr.total(tr.intersect(tr.gaps(run.trace.busy(d), lo, hi), spans))
        for d in run.trace.ops)
    return 100.0 * waited / len(run.trace.ops) / (hi - lo)
