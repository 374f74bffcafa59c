"""Share of the train step's device time in ops the program's catalog
gives a scope (hence a phase): fwd + bwd + opt over all of it. The
tracing's own health — what is left is ops without an `op_name` (XLA's
own copies and layout changes) and ops the catalog does not know."""

from benchmark import scope_time


def read(run):
    got = scope_time.phases(run)
    if not got:
        return None
    named = sum(got.get(p, 0.0) for p in scope_time.PHASES)
    whole = named + got.get(scope_time.UNNAMED, 0.0)
    return 100.0 * named / whole if whole else None
