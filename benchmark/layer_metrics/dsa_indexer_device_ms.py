"""Device time a train step spends in the indexer proper (scopes
`.../attn/indexer/{proj,rope,scores}` of nn/keye_vl.py:Indexer, and the
`scores` its objective makes again: the three projections of the layer's
detached input, the LayerNorm of the index key, M-RoPE over 64 columns,
and `I = sum_j w_j relu(q^I_j . k^I)` a block of queries at a time),
forward, rematerialised forward and backward, all layers: device trace
joined to the program's catalog (benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "indexer")
