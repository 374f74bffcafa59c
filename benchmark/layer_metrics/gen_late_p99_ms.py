"""99th percentile of send time minus due time in the benchmark's own
load generator: the health of the yardstick. A generator that runs late
voids the tails."""


def read(run):
    return run.counters.get("gen_late_p99_ms")
