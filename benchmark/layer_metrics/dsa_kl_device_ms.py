"""Device time a train step spends on the indexer's objective (scope
`.../attn/indexer/kl` of nn/keye_vl.py but the scores inside it: the
head-mean attention probabilities made again from `q`, `k` and the core's
log-sum-exp a block of queries at a time, `L^I` against the softmax of the
index scores over the selected keys, and the same again in the backward
with its gradient), all layers: device trace joined to the program's
catalog (benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "kl")
