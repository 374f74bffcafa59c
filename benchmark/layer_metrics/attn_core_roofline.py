"""The attention cores' share of their roofline: the least time the chip
could take for one step's `q k^T` and `p v` over the causal half, forward
and backward, all layers (benchmark/shapes/glm_moe.py:
attention_core_passes — per pass the larger of operations over the peak
bf16 FLOP/s and least bytes over the peak HBM bytes/s; rematerialised
forwards are not counted as work) over the time measured in them
(`attn_core_device_ms`). The cores are compute-bound at 4,096 positions."""

from benchmark.layer_metrics import attn_core_device_ms
from benchmark.shapes import glm_moe as shapes


def read(run):
    if run.ctx.peak is None:
        return None
    took_ms = attn_core_device_ms.read(run)
    if not took_ms:
        return None
    least = shapes.least_seconds(shapes.attention_core_passes(
        run.ctx.config, run.counters["batch_per_chip"]), run.ctx.peak)
    return 100.0 * least / (took_ms / 1e3)
