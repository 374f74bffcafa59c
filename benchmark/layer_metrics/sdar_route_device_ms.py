"""Device time a train step spends getting stream rows to the held experts
and back (scopes `.../moe/route`, `dispatch` and `combine`: the float32
softmax over 128 router outputs and the top-8, the balance term, the sort
of 262,144 assignments into the row buffer, the gather of the rows and the
gather back with the gates), forward, rematerialised forward and backward,
all layers: device trace joined to the program's catalog
(benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_route")
