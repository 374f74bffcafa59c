"""Device time a train step spends in the stem (7x7 conv + BatchNorm +
ReLU, scope `stem`) and the max-pool after it (`pool`), forward and
backward: device trace joined by instruction name to the program's
catalog of its compiled step (benchmark/scope_time.py)."""

from benchmark import scope_time


def read(run):
    got = scope_time.split(
        run, lambda e: "stem" if e.scope.split("/")[0] in ("stem", "pool")
        else None, ("stem",))
    return got.get("stem", 0.0) if got else None
