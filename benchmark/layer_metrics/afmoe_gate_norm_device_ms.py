"""Device time a train step spends in what the `afmoe` layer adds that is
no matmul (ops whose layer scope ends in `gate`, `post_norm` or `qk_norm`
of nn/afmoe.py: the sigmoid gate on the attention's output, the second
norm of each sub-layer, the norms of q and k over a head's features),
forward and backward: device trace joined by instruction name to the
program's catalog of its compiled step (benchmark/scope_time.py). A
fusion counts whole under its hero, so such work XLA fused onto a matmul
counts with that layer, not here: this is what is left as passes of its
own over the activations."""

from benchmark import scope_time

GROUP = "gate_norm"
SCOPES = ("gate", "post_norm", "qk_norm")


def read(run):
    got = scope_time.split(
        run, lambda e: GROUP if e.scope.split("/")[-1] in SCOPES else None,
        (GROUP,))
    return (got.get(GROUP) or None) if got else None
