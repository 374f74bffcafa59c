"""The indexer's objective `L^I` of the window's last epoch's last training
forward, a mean over the layers: the program's own counter `dsa_index_kl`
(nn/keye_vl.py: read on the device inside the step and carried in the
model's state) — that the indexer learns: it falls over the warm-up, and an
objective dropped from the loss reads its initial value for ever. None
where the program keeps no such counter."""

from benchmark import keye_scopes


def read(run):
    by_layer = keye_scopes.newest_counter("dsa_index_kl")
    return sum(by_layer) / len(by_layer) if by_layer else None
