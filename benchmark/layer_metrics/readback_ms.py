"""Median `zoo.readback` span: the one host sync an epoch, i.e. how long
the host waited for the device to drain the steps it had queued."""

from benchmark import common


def read(run):
    spans = run.spans.get("zoo.readback")
    return 1e3 * common.median(spans) if spans else None
