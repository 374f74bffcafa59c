"""Device time a train step spends in the routed experts held on this chip
(scope `.../moe/experts` of nn/glm_moe.py:ExpertLayer under
nn/bailing_hybrid.py: the three grouped matmuls `lax.ragged_dot` over the
row buffer, 8 experts 768 wide, and the `silu(gate) * up` between them),
forward, rematerialised forward and both gradients, all expert layers:
device trace joined to the program's catalog (benchmark/glm_scopes.py)."""

from benchmark import glm_scopes


def read(run):
    return glm_scopes.ms(run, "moe_experts")
