"""Compile requests before the window: programs the process asked XLA for,
cache hits included (the eager initialisation alone is one a parameter
leaf). Counted from the program's compile log (benchmark/setup_time.py)."""

from benchmark import setup_time


def read(run):
    return setup_time.count(run, lambda r: r.kind == "compile")
