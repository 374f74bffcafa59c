"""Seconds of set-up spent on compile requests the persistent compile cache
served: reading, decompressing and loading executables. From the `compile`
records with `cache == "hit"` of the program's compile log
(benchmark/setup_time.py)."""

from benchmark import setup_time


def read(run):
    return setup_time.seconds(run, lambda r: r.cache == "hit")
