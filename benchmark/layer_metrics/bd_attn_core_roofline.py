"""The block-diffusion attention cores' share of their roofline: the least
time the chip could take for one step's `q k^T` and `p v` over the pairs
the mask ALLOWS, `L (L + B)` a head, forward and backward, all layers
(benchmark/shapes/sdar_moe.py:attention_core_passes — per pass the larger
of operations over the peak bf16 FLOP/s and least bytes over the peak HBM
bytes/s) over the time measured in them (`bd_attn_core_device_ms`). What a
kernel computes beyond the allowed pairs (the rest of a tile a block
boundary crosses) is time, not work: see `bd_attn_pairs_computed_ratio`."""

from benchmark.layer_metrics import bd_attn_core_device_ms
from benchmark.shapes import sdar_moe as shapes


def read(run):
    if run.ctx.peak is None:
        return None
    took_ms = bd_attn_core_device_ms.read(run)
    if not took_ms:
        return None
    least = shapes.least_seconds(shapes.attention_core_passes(
        run.ctx.config, run.counters["batch_per_chip"]), run.ctx.peak)
    return 100.0 * least / (took_ms / 1e3)
