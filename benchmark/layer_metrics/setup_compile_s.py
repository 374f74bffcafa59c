"""Seconds of set-up spent in XLA compiling programs the persistent compile
cache did not hold (it was asked and missed, or was not asked): near 0 on
a warm machine, most of a first run. From the `compile` records of the
program's compile log (benchmark/setup_time.py)."""

from benchmark import setup_time


def read(run):
    return setup_time.seconds(
        run, lambda r: r.kind == "compile" and r.cache != "hit")
