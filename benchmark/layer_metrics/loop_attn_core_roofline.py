"""The T x L attention cores' share of their roofline: the least time the
chip could take for one step's `q k^T` and `p v` over the causal half
(8,390,656 pairs a head at 4,096 positions), forward once and backward
twice that, every pass and layer (benchmark/shapes/ouro.py:
attention_core_passes — per pass the larger of operations over the peak
bf16 FLOP/s and least bytes over the peak HBM bytes/s) over the time
measured in them (`loop_attn_core_device_ms`)."""

from benchmark import ouro_scopes as scopes
from benchmark.layer_metrics import loop_attn_core_device_ms


def read(run):
    return scopes.roofline(
        run, loop_attn_core_device_ms.read(run),
        lambda: scopes.shapes.attention_core_passes(
            run.ctx.config, run.counters["batch_per_chip"]))
