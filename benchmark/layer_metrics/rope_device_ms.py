"""Device time a train step spends turning q and k by their positions
(ops whose layer scope has `attn` in it and ends in `rope`: the `rope`
scope of nn/glm_moe.py, nn/sdar_moe.py and nn/afmoe.py), forward,
rematerialised forward and backward: device trace joined by instruction
name to the program's catalog of its compiled step
(benchmark/scope_time.py). A fusion counts whole under its hero, so what
XLA fuses of the turn onto a neighbour counts there: read `attn/qkv` and
`attn/qk_norm` of benchmark/tools/scope_table.py beside it."""

from benchmark import scope_time

GROUP = "rope"


def read(run):
    def group_of(entry):
        parts = entry.scope.split("/")
        return GROUP if parts[-1] == GROUP and "attn" in parts else None

    got = scope_time.split(run, group_of, (GROUP,))
    return (got.get(GROUP) or None) if got else None
