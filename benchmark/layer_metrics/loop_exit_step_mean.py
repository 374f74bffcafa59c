"""The mean exit step `sum_t t p_t` of the window's last epoch's last
training forward, between 1 and T: the program's own counter
`loop_exit_step_mean` (nn/ouro.py: the exit distribution's mean over the
step's positions, read on the device inside the step and carried in the
model's state), that the exit gate is alive — 1.875 at the initialisation
(p = 1/2, 1/4, 1/8, 1/8), T where the gate has closed every early exit.

The runner hands its readers its own selection of the epoch record's
counters, so this one reads the program's copy of the newest record
(`parallel_cnn_tpu/obs/epochs.py`). None where the program keeps no such
copy or its model no such counter."""


def read(run):
    try:
        from parallel_cnn_tpu.obs import epochs

        newest = epochs.newest()
    except (ImportError, AttributeError):
        return None
    if not newest:
        return None
    return newest[-1].get("loop_exit_step_mean")
