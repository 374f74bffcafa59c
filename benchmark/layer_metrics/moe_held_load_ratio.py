"""Rows the worst expert layer held in the newest epoch's last step over
the balanced share (benchmark/shapes/glm_moe.py:held_rows times the
sequences a step: tokens x experts a token x held / routed): the program's
own counter `moe_rows_held` (one count a layer, the MTP module's last;
the largest is taken). 1 is a chip that got exactly its share; the grouped
matmuls' cost follows this number."""

from benchmark import glm_scopes
from benchmark.shapes import glm_moe as shapes


def read(run):
    rows = glm_scopes.last_epoch(run, "moe_rows_held")
    if not rows:
        return None
    share = shapes.held_rows(run.ctx.config) * run.counters["batch_per_chip"]
    return max(rows) / share
