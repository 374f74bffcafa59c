"""The convs' share of their roofline: the least time the chip could take
for one step's conv passes — per pass the larger of FLOPs over the peak
FLOP/s and least bytes over the peak HBM bytes/s, from the layer shapes
(benchmark/flops.py) — over the conv time measured per step in the device
trace."""

from benchmark import flops
from benchmark import trace_reduce as tr


def read(run):
    if run.trace is None or run.ctx.peak is None:
        return None
    conv = runs = 0.0
    for d in run.trace.ops:
        conv += tr.total(tr.union(run.trace.by_category(d, "conv")))
        runs += len(run.trace.runs(d, run.program))
    if not conv or not runs:
        return None
    least = flops.conv_roofline_seconds(
        run.ctx.config, run.counters["batch_per_chip"], run.ctx.peak)
    return 100.0 * least["seconds"] / (conv / runs / 1e9)
