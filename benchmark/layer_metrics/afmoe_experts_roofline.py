"""The held experts' share of their roofline in the `afmoe` cell: the
least time the chip could take for one step's grouped matmuls over the
rows the expert layers ACTUALLY held — the program's own counter
`moe_rows_held` of the newest epoch, one count a layer
(benchmark/shapes/afmoe.py:expert_passes, the `glm_moe` family's) — over
the time measured in them (`afmoe_experts_device_ms`). A held expert sees
1,024 rows a step here, an eighth of a deployment's: the grouped matmul's
groups are short, and the share reads lower than a deployment's would."""

from benchmark import glm_scopes
from benchmark.layer_metrics import afmoe_experts_device_ms
from benchmark.shapes import afmoe as shapes


def read(run):
    rows = glm_scopes.last_epoch(run, "moe_rows_held")
    if run.ctx.peak is None or not rows:
        return None
    took_ms = afmoe_experts_device_ms.read(run)
    if not took_ms:
        return None
    least = shapes.least_seconds(
        shapes.expert_passes(run.ctx.config, rows), run.ctx.peak)
    return 100.0 * least / (took_ms / 1e3)
