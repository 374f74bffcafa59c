"""Forward attention-core kernel calls a traced step makes over the T x L
it needs (benchmark/shapes/ouro.py:core_calls): the executed
`custom-call`s under `ut/l<i>/attn/core` (benchmark/ouro_scopes.py:
core_kernel_calls) — those of the forward pass, and of the backward's
whatever is beyond the one backward kernel a core has (a saved core that
the backward ran again). 1.0: every pass ran its cores and none was
recomputed; under 1: a step that left a pass out; over 1: cores computed
twice. None where the step has no such kernel (the plain path)."""

from benchmark import ouro_scopes


def read(run):
    arch = run.ctx.config.get("arch", {})
    if "total_ut_steps" not in arch:
        return None
    calls = ouro_scopes.core_kernel_calls(run)
    if calls is None:
        return None
    need = ouro_scopes.shapes.core_calls(run.ctx.config)
    return (calls["fwd"] + max(calls["bwd"] - need, 0.0)) / need
