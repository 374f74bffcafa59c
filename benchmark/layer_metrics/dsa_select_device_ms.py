"""Device time a train step spends selecting (scope `.../attn/indexer/
select` of nn/keye_vl.py:Indexer.choose: every query's exact top-k over its
scores, the threshold and the tie rule, the selection as bits and the
core's bias made from them), all layers — forward only but for the bias,
which a rematerialised layer makes again from the bits it kept: device
trace joined to the program's catalog (benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "select")
