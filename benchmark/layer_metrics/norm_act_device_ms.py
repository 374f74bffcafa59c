"""Device time a train step spends in the normalisation and elementwise
chains of a ConvNeXt (ops whose layer scope ends in `norm`, `act`,
`scale`, `drop` or `add`: LayerNorm, GELU, LayerScale, stochastic depth
and the residual add of nn/convnext.py), forward and backward: device
trace joined by instruction name to the program's catalog of its compiled
step (benchmark/scope_time.py). A fusion counts whole under its hero, so
such work XLA fused onto a conv or a matmul counts with that layer, not
here: this is what is left as passes of its own over the activations."""

from benchmark import scope_time

GROUP = "norm_act"
SCOPES = ("norm", "act", "scale", "drop", "add")


def read(run):
    got = scope_time.split(
        run, lambda e: GROUP if e.scope.split("/")[-1] in SCOPES else None,
        (GROUP,))
    return (got.get(GROUP) or None) if got else None
