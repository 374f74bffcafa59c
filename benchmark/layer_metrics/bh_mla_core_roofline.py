"""The full layers' attention cores' share of their roofline in the
`bailing_hybrid` cell: the least time the chip could take for one step's
`q k^T` (192 wide, as published: the zero columns the program carries are
no work) and `p v` (128) over the causal half, forward and backward
(benchmark/shapes/bailing_hybrid.py:attention_core_passes), over the time
measured in them (`bh_mla_core_device_ms`)."""

from benchmark import bailing_hybrid_scopes as scopes
from benchmark.layer_metrics import bh_mla_core_device_ms


def read(run):
    return scopes.roofline(
        run, bh_mla_core_device_ms.read(run),
        lambda: scopes.shapes.attention_core_passes(
            run.ctx.config, run.counters["batch_per_chip"]))
