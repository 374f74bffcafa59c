"""The busiest routed expert's load over the mean load, all published
experts (held or not), in the newest epoch's last step, of the expert
layer where it is largest: the program's own counter
`moe_load_max_over_mean`. 1 is perfect balance; it is what the selection
bias works against."""

from benchmark import glm_scopes


def read(run):
    worst = glm_scopes.last_epoch(run, "moe_load_max_over_mean")
    return max(worst) if worst else None
