"""Device time a train step spends in the multi-token-prediction module
(scope `mtp/...` of nn/glm_moe.py: its embedding lookup, two norms and
projection, its decoder layer — attention and experts included — and its
pass through the shared head and loss), forward, rematerialised forward
and backward: device trace joined to the program's catalog
(benchmark/scope_time.py)."""

from benchmark import scope_time

GROUP = "mtp"


def read(run):
    got = scope_time.split(
        run, lambda e: GROUP if e.scope.split("/")[0] == GROUP else None,
        (GROUP,))
    return (got.get(GROUP) or None) if got else None
