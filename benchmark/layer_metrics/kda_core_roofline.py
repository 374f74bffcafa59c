"""The delta-rule cores' share of their roofline: the least time the chip
could take for one step's chunked delta rule at chunk 64 — its matmuls'
operations, and q, k, v, the log-decay and beta once in, the output once
out — forward and backward, the `linear_attention` layers
(benchmark/shapes/bailing_hybrid.py:kda_core_passes) over the time
measured in them (`kda_core_device_ms`, which holds the rematerialised
forwards too)."""

from benchmark import bailing_hybrid_scopes as scopes
from benchmark.layer_metrics import kda_core_device_ms


def read(run):
    return scopes.roofline(
        run, kda_core_device_ms.read(run),
        lambda: scopes.shapes.kda_core_passes(
            run.ctx.config, run.counters["batch_per_chip"]))
