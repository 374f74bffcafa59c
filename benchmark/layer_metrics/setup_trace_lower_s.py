"""Seconds of set-up spent tracing functions to jaxprs and lowering them to
MLIR: host Python that no compile cache saves. Own seconds of the `trace`
and `lower` records of the program's compile log that start before the
window (benchmark/setup_time.py)."""

from benchmark import setup_time


def read(run):
    return setup_time.seconds(run, lambda r: r.kind in ("trace", "lower"))
