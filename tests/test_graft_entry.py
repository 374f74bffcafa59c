"""`__graft_entry__.py`: the two functions an earlier driver called.

`entry()` must stay jittable as it is handed out, and `dryrun_multichip`
must run one hybrid (data, model) step plus one GSPMD step on virtual
CPU devices — here the suite's own 8 (tests/conftest.py), which the dry
run judges as it finds them.
"""

import jax
import numpy as np
import pytest

import __graft_entry__ as graft


def test_entry_jits_to_finite_logits():
    fn, args = graft.entry()
    out = np.asarray(jax.jit(fn)(*args))
    assert out.shape == (8, 10)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("n_devices,mesh", [
    (8, {"data": 4, "model": 2}),
    (3, {"data": 3, "model": 1}),   # odd: the model_axis == 1 branch
])
def test_dryrun_multichip_runs_on_the_virtual_mesh(
    host_devices, capsys, n_devices, mesh
):
    graft.dryrun_multichip(n_devices)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert line.startswith(f"dryrun_multichip OK: mesh={mesh}")
    assert f"uneven batch {64 * n_devices + 3}" in line
