"""Device time a train step spends in the T exits of an `ouro` model and
in their mixture (scopes `ut/exit/{norm,head,loss,gate}` and `mix` of
nn/ouro.py: the final norm that closes a pass, the whole head and the
blocked cross-entropy once a pass, the exit gate, the exit distribution
and its entropy), forward, rematerialised logits and backward: device
trace joined by instruction name to the program's catalog of its compiled
step (benchmark/ouro_scopes.py)."""

from benchmark import ouro_scopes


def read(run):
    return ouro_scopes.ms(run, "exits")
