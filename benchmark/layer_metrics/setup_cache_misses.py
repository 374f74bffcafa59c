"""Compile requests before the window that the persistent compile cache was
asked for and did not hold: 0 on a warm machine. Counted from the
program's compile log (benchmark/setup_time.py)."""

from benchmark import setup_time


def read(run):
    return setup_time.count(run, lambda r: r.cache == "miss")
