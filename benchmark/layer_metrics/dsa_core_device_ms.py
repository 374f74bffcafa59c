"""Device time a train step spends in the attention cores over the selected
keys (scope `.../attn/core` of nn/sdar_moe.py:GQA under nn/keye_vl.py: `q
k^T` over grouped key/value heads, the selection's mask, the float32 softmax
and `p v`), forward and backward, all layers: device trace joined by
instruction name to the program's catalog of its compiled step
(benchmark/scope_time.py, benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "core")
