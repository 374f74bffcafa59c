"""Device time a train step spends in the attention cores of the
`full_attention` layers of a `bailing_hybrid` model (scope `l<i>/attn/core`
of nn/glm_moe.py:MLA under nn/bailing_hybrid.py: `q k^T` with q and k
carried 256 wide for their 192, the causal mask, the float32 softmax and
`p v`), forward and backward: device trace joined to the program's catalog
(benchmark/bailing_hybrid_scopes.py)."""

from benchmark import bailing_hybrid_scopes


def read(run):
    return bailing_hybrid_scopes.ms(run, "mla_core")
