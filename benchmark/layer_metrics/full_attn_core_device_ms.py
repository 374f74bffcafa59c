"""Device time a train step spends in the attention cores of the
`full_attention` layers (scope `l<i>/attn/core` of nn/afmoe.py where the
configuration's `arch.layer_types[i]` says so: `q k^T` over grouped
key/value heads under the causal mask, the float32 softmax and `p v`),
forward and backward: device trace joined by instruction name to the
program's catalog of its compiled step (benchmark/afmoe_scopes.py)."""

from benchmark import afmoe_scopes


def read(run):
    return afmoe_scopes.core_ms(run, afmoe_scopes.FULL)
