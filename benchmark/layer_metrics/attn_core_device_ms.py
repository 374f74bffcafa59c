"""Device time a train step spends in the attention cores (scope
`.../attn/core` of nn/glm_moe.py: `q k^T`, the causal mask, the float32
softmax and `p v`, a block of queries at a time), forward, the
rematerialised forwards and backward, all layers and the MTP module's:
device trace joined by instruction name to the program's catalog of its
compiled step (benchmark/scope_time.py, benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "attn_core")
