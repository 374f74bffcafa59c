"""Pairs (query, key) the attention core computes in its backward over the
pairs the selection allows, one (sequence, head): the program's own
statement of its core (`describe`, what the `zoo_dsa` journal event
carries). 1 is a core that computes nothing it masks; a causal visit list
at 16,384 positions and 2,048 keys a query computes 4.4 times the allowed
pairs. None where the program has no such model or statement."""

from benchmark import keye_scopes


def read(run):
    said = keye_scopes.said(run)
    try:
        return said["attention_pairs_computed"] / said["attention_pairs_allowed"]
    except (KeyError, TypeError, ZeroDivisionError):
        return None
