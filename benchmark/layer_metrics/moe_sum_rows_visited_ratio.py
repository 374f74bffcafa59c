"""Buffer rows one sum of the tokens' rows read over the rows the expert
layer held, the mean over the expert layers, in the window's last epoch's
last step: the program's own counters `moe_sum_rows_visited` (the windows'
rows of the fused kernel of ops/pallas_rowsum.py; every row of the buffer
where the plain scatter-add ran) and `moe_rows_held`, one value a layer.
1 is a sum that reads what carries data and nothing else; the pass it
replaced read a row an assignment slot, `T * k` over the rows held.

The runner hands its readers its own selection of the epoch record's
counters, so this one reads the program's copy of the newest record
(`parallel_cnn_tpu/obs/epochs.py`) and takes it only if it is the epoch
the runner's `moe_rows_held` ends with. None where the program keeps no
such copy or its layers no such counter (a parent of PR 37)."""

from benchmark import glm_scopes


def read(run):
    try:
        from parallel_cnn_tpu.obs import epochs

        newest = epochs.newest()
    except (ImportError, AttributeError):
        return None
    held = glm_scopes.last_epoch(run, "moe_rows_held")
    if not newest or not held or newest[-1].get("moe_rows_held") != held:
        return None
    visited = newest[-1].get("moe_sum_rows_visited")
    if not visited or len(visited) != len(held):
        return None
    ratios = [v / h for v, h in zip(visited, held) if h]
    return sum(ratios) / len(ratios) if ratios else None
