"""The held experts' share of their roofline in the selected-key cell: the
least time the chip could take for one step's grouped matmuls over the rows
the expert layers ACTUALLY held — the program's own counter `moe_rows_held`
of the newest epoch, one count a layer (benchmark/shapes/keye_vl.py:
expert_passes, the `glm_moe` family's) — over the time measured in them
(`keye_experts_device_ms`)."""

from benchmark import keye_scopes
from benchmark.layer_metrics import keye_experts_device_ms
from benchmark.shapes import keye_vl as shapes


def read(run):
    rows = keye_scopes.last_epoch(run, "moe_rows_held")
    if not rows:
        return None
    return keye_scopes.roofline(
        run, keye_experts_device_ms.read(run),
        lambda: shapes.expert_passes(run.ctx.config, rows))
