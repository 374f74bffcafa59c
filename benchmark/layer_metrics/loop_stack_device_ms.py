"""Device time a train step spends in the T x L layer applications of an
`ouro` model (scopes `ut/l<i>/...` of nn/ouro.py: every pass over the one
stack — norms, projections, RoPE, attention cores, MLPs), forward,
rematerialised forward and backward: device trace joined by instruction
name to the program's catalog of its compiled step
(benchmark/ouro_scopes.py)."""

from benchmark import ouro_scopes


def read(run):
    return ouro_scopes.ms(run, "stack")
