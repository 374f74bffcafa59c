"""Device time a train step spends in the block-diffusion attention cores
(scope `.../attn/core` of nn/sdar_moe.py: `q k^T` over grouped key/value
heads, the block-structured mask over the two-copy stream, the float32
softmax and `p v`), forward and backward, all layers: device trace joined
by instruction name to the program's catalog of its compiled step
(benchmark/scope_time.py, benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "attn_core")
