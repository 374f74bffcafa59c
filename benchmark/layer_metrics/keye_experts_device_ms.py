"""Device time a train step spends in the routed experts held on this chip
(scope `.../moe/experts` of nn/glm_moe.py:ExpertLayer under nn/keye_vl.py:
the three grouped matmuls `lax.ragged_dot` over the row buffer, 16 experts
768 wide, and the `silu(gate) * up` between them), forward, rematerialised
forward and both gradients, all layers: device trace joined to the
program's catalog (benchmark/keye_scopes.py)."""

from benchmark import keye_scopes


def read(run):
    return keye_scopes.ms(run, "experts")
