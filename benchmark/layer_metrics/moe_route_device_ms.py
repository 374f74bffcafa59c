"""Device time a train step spends getting tokens to the held experts and
back (scopes `.../moe/route`, `dispatch` and `combine` of nn/glm_moe.py:
float32 sigmoid scores and the top-k under the selection bias, the
balance term, the sort of the assignments into the row buffer, the gather
of the rows and the gather back with the gates), forward, rematerialised
forward and backward, all expert layers: device trace joined to the
program's catalog (benchmark/scope_time.py, benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_route")
