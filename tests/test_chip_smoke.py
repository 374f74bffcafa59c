"""chip_smoke.py's no-accelerator contract (the part a CPU can check):
without a TPU it exits non-zero within seconds, names the platform it
found, prints no result line, and compiles nothing."""

import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(cwd, script, cache_dir, *args):
    env = dict(os.environ)
    # A child of this (JAX-holding) test process must never reach for an
    # accelerator — and the CPU is exactly the case under test.
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    return proc, time.monotonic() - t0


def _assert_refused(proc, cache_dir):
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout  # no result line of any kind
    assert not os.listdir(cache_dir)  # nothing was compiled


def test_exits_nonzero_on_cpu_naming_the_platform(tmp_path):
    cache = tmp_path / "cache"
    cache.mkdir()
    proc, sec = _run(ROOT, SCRIPT, cache)
    _assert_refused(proc, cache)
    assert "platform='cpu'" in proc.stderr
    assert "platform=cpu" in proc.stdout  # the header names it too
    assert sec < 60


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    """The script by itself — no package next to it — must fail too."""
    cache = tmp_path / "cache"
    cache.mkdir()
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SCRIPT, alone / "chip_smoke.py")
    proc, _ = _run(str(alone), str(alone / "chip_smoke.py"), cache)
    _assert_refused(proc, cache)


def test_rehearsal_flag_refuses_a_wrong_leg_name(tmp_path):
    cache = tmp_path / "cache"
    cache.mkdir()
    proc, _ = _run(ROOT, SCRIPT, cache, "--rehearse-cpu", "--legs", "nope")
    _assert_refused(proc, cache)
    assert "unknown leg" in proc.stderr
