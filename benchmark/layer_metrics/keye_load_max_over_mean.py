"""The busiest expert's load over the mean load, all 128 published experts
(held or not), in the newest epoch's last step, of the layer where it is
largest: the program's own counter `moe_load_max_over_mean` under the
softmax router, which no selection bias balances."""

from benchmark import keye_scopes


def read(run):
    worst = keye_scopes.last_epoch(run, "moe_load_max_over_mean")
    return max(worst) if worst else None
