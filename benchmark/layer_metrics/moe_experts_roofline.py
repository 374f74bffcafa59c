"""The held experts' share of their roofline: the least time the chip
could take for one step's grouped matmuls over the rows the expert layers
ACTUALLY held — the program's own counter `moe_rows_held` of the newest
epoch, one count a layer (benchmark/shapes/glm_moe.py:expert_passes: gate,
up and down, each forward, data gradient and weight gradient; per pass the
larger of operations over the peak bf16 FLOP/s and least bytes over the
peak HBM bytes/s; rematerialised forwards not counted) — over the time
measured in them (`moe_experts_device_ms`)."""

from benchmark import glm_scopes
from benchmark.layer_metrics import moe_experts_device_ms
from benchmark.shapes import glm_moe as shapes


def read(run):
    rows = glm_scopes.last_epoch(run, "moe_rows_held")
    if run.ctx.peak is None or not rows:
        return None
    took_ms = moe_experts_device_ms.read(run)
    if not took_ms:
        return None
    least = shapes.least_seconds(
        shapes.expert_passes(run.ctx.config, rows), run.ctx.peak)
    return 100.0 * least / (took_ms / 1e3)
