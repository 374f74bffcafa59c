"""Device time a train step spends in the depthwise convs (ops whose layer
scope ends in `dw`: nn/convnext.py's 7x7 grouped conv), forward and both
gradients: device trace joined by instruction name to the program's
catalog of its compiled step (benchmark/scope_time.py). A fusion counts
whole under its hero, so LayerNorm or bias work XLA fused onto a depthwise
conv counts here, and depthwise work fused into another layer's fusion
does not."""

from benchmark import scope_time

GROUP = "dw"


def read(run):
    got = scope_time.split(
        run, lambda e: GROUP if e.scope.split("/")[-1] == GROUP else None,
        (GROUP,))
    return (got.get(GROUP) or None) if got else None
