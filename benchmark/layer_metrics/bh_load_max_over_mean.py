"""The busiest expert's load over the mean load, all 512 published experts
(held or not), in the newest epoch's last step, of the layer where it is
largest: the program's own counter `moe_load_max_over_mean` under the
group-limited sigmoid router, which the selection bias alone balances
(the loss has no balance term)."""

from benchmark import glm_scopes


def read(run):
    worst = glm_scopes.last_epoch(run, "moe_load_max_over_mean")
    return max(worst) if worst else None
