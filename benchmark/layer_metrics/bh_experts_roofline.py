"""The held experts' share of their roofline in the `bailing_hybrid` cell:
the least time the chip could take for one step's grouped matmuls over the
rows the expert layers ACTUALLY held — the program's own counter
`moe_rows_held` of the newest epoch, one count a layer
(benchmark/shapes/bailing_hybrid.py:expert_passes, the `glm_moe` family's)
— over the time measured in them (`bh_experts_device_ms`). A held expert
sees 128 rows a step here, 1/64 of a deployment's: the passes are bound
by the experts' weights, and the share reads far lower than a
deployment's would."""

from benchmark import bailing_hybrid_scopes as scopes
from benchmark import glm_scopes
from benchmark.layer_metrics import bh_experts_device_ms


def read(run):
    rows = glm_scopes.last_epoch(run, "moe_rows_held")
    if not rows:
        return None
    return scopes.roofline(
        run, bh_experts_device_ms.read(run),
        lambda: scopes.shapes.expert_passes(run.ctx.config, rows))
