"""Seconds of warm-up: trace + compile, or the load from the compile cache.
Training: host time epoch 1 spent in `zoo.dispatch` beyond the same steps'
cost in the window. Serving: the AOT ladder plus one executed batch a
bucket. Source: the harness's clock around the program's own spans."""


def read(run):
    return run.counters.get("warmup_s")
