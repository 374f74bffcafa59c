"""Median `zoo.shard` span: host time a step to lay the batch out over
the mesh (`parallel/mesh.py:shard_batch`) before the step is dispatched.
Mesh path only; the span is in `run.spans` like every closed span."""

from benchmark import common


def read(run):
    spans = run.spans.get("zoo.shard")
    return 1e3 * common.median(spans) if spans else None
