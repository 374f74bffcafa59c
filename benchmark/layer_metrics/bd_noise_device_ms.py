"""Device time a train step spends making the block-diffusion stream
(scope `noise` of nn/sdar_moe.py: the draws of t a block and of every
token's uniform from the key in the model state, the masked copy and the
concatenation `[x^t ; x^0]`): device trace joined to the program's catalog
(benchmark/scope_time.py)."""

from benchmark import scope_time

GROUP = "noise"


def read(run):
    got = scope_time.split(
        run, lambda e: GROUP if e.scope.split("/")[0] == GROUP else None,
        (GROUP,))
    return (got.get(GROUP) or None) if got else None
