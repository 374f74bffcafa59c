"""Device time a train step spends getting rows to the held experts and
back (scopes `.../moe/route`, `dispatch` and `combine`: the float32 softmax
over 128 router outputs and the top-8, the balance term, the sort of
131,072 assignments into the row buffer, the gather of the rows and the sum
back with the gates), forward, rematerialised forward and backward, all
layers: device trace joined to the program's catalog
(benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "route")
