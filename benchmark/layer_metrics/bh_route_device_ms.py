"""Device time a train step spends getting tokens to the held experts and
back in the `bailing_hybrid` cell (scopes `.../moe/route`, `dispatch` and
`combine`: the float32 sigmoid over 512 router outputs, the group limit —
two top-2s and a top-4 over 8 groups of 64 — and the top-8 under the
selection bias, the plan of 65,536 assignments into the row buffer, the
gather of the rows and the sum of a token's rows back with the gates),
forward, rematerialised forward and backward, all expert layers: device
trace joined to the program's catalog (benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_route")
