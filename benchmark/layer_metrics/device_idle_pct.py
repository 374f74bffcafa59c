"""Share of the traced window in which no op ran on the device: 1 minus
the union of the executed ops' intervals over the window, averaged over
the chips used. Device trace."""

from benchmark import trace_reduce as tr


def read(run):
    summary = tr.device_summary(run.trace) if run.trace else None
    return summary["idle_pct_mean"] if summary else None
