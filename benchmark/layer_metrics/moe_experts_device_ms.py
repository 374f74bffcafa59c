"""Device time a train step spends in the routed experts held on this chip
(scope `.../moe/experts` of nn/glm_moe.py: the three grouped matmuls
`lax.ragged_dot` over the row buffer and the `silu(gate) * up` between
them, and the casts of the held experts' weights), forward, rematerialised
forward and both gradients, all expert layers: device trace joined to the
program's catalog (benchmark/scope_time.py, benchmark/glm_scopes.py). The
shared expert is a dense layer and is not counted here."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_experts")
