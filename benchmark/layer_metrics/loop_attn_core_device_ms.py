"""Device time a train step spends in the attention cores of all T x L
layer applications of an `ouro` model (scope `ut/l<i>/attn/core` of
nn/ouro.py: `q k^T` under the causal mask, the float32 softmax and `p v`,
16 heads over 16 key/value heads), forward and backward: device trace
joined by instruction name to the program's catalog of its compiled step
(benchmark/ouro_scopes.py)."""

from benchmark import ouro_scopes


def read(run):
    return ouro_scopes.ms(run, "core")
