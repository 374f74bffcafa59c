"""Device time a train step spends getting tokens to the held experts and
back (scopes `.../moe/route`, `dispatch` and `combine`: the float32
sigmoid over 128 router outputs and the top-8 under the selection bias,
the plan of 131,072 assignments into the row buffer, the gather of the
rows and the sum of a token's rows back with the gates), forward,
rematerialised forward and backward, all expert layers: device trace
joined to the program's catalog (benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_route")
