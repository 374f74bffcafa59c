"""Requests per dispatched batch in the window: the batcher's own counters
`requests_in_batches / batches`."""


def read(run):
    c = run.counters
    return c["requests_in_batches"] / c["batches"] if c.get("batches") else None
