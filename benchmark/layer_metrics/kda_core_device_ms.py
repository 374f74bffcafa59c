"""Device time a train step spends in the delta-rule cores of the
`linear_attention` layers (scope `l<i>/attn/core` of nn/bailing_hybrid.py
where the configuration's `arch.layer_types[i]` says so:
ops/kda.py:chunked_kda — the decay's running sums and factors, the pair
tables, the triangular inverse, the scan over the state), forward,
rematerialised forward and backward: device trace joined by instruction
name to the program's catalog of its compiled step
(benchmark/bailing_hybrid_scopes.py)."""

from benchmark import bailing_hybrid_scopes


def read(run):
    return bailing_hybrid_scopes.ms(run, "kda_core")
