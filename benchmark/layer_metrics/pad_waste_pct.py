"""Share of dispatched bucket slots that held padding, from the batcher's
own counters: `padded_slots / (requests_in_batches + padded_slots)`."""


def read(run):
    c = run.counters
    slots = c.get("requests_in_batches", 0) + c.get("padded_slots", 0)
    return 100.0 * c["padded_slots"] / slots if slots else None
