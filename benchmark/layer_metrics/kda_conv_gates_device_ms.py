"""Device time a train step spends around the delta-rule cores in what is
no projection (scopes `l<i>/attn/{conv,gates,gate_norm}` of the
`linear_attention` layers of nn/bailing_hybrid.py: three causal 4-tap
convolutions with their SiLU, the L2 norms of q and k, the float32
log-decay gate and beta, the output's norm a head and its head-wise gate),
forward, rematerialised forward and backward: device trace joined to the
program's catalog (benchmark/bailing_hybrid_scopes.py). A fusion counts
whole under its hero, so what XLA fused of this onto a projection counts
there: this is what is left as passes of its own over the activations."""

from benchmark import bailing_hybrid_scopes


def read(run):
    return bailing_hybrid_scopes.ms(run, "kda_conv_gates")
