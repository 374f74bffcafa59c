"""Share of the window lost to epochs slower than the median one: 1 minus
(median epoch time x epochs) over the window's length. `train_img_s_chip`
is taken from the median epoch, so a stall it does not see shows here."""


def read(run):
    return run.counters.get("epoch_stall_pct")
