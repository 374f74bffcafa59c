"""The idle share of the idlest chip (see device_idle_pct): on a mesh the
chips that wait for device 0's gather and re-layout. Device trace."""

from benchmark import trace_reduce as tr


def read(run):
    summary = tr.device_summary(run.trace) if run.trace else None
    return summary["idle_pct_worst"] if summary else None
