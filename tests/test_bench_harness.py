"""Benchmark-harness unit tests: the headline-selection rule the driver
artifact depends on (bench.py) and the measurement-integrity guards in
benches/run.py (the B11-class barrier/RTT lessons, round 3)."""

import importlib.util
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load("bench_mod", os.path.join(ROOT, "bench.py"))


@pytest.fixture(scope="module")
def benchrun():
    return _load("benchrun_mod", os.path.join(ROOT, "benches", "run.py"))


def test_headline_promotes_faster_parity_checked_pallas(bench):
    ips, path = bench.select_headline(1_000_000.0, 1_500_000.0, 4e-4)
    assert (ips, path) == (1_500_000.0, "pallas_fused")


@pytest.mark.parametrize(
    "pallas_ips,diff,why",
    [
        (900_000.0, 4e-4, "slower than path A"),
        (1_500_000.0, 0.5, "grad diff beyond PALLAS_PARITY_TOL"),
        (1_500_000.0, float("nan"), "NaN diff must not compare as ok"),
        (1_500_000.0, None, "diff never measured"),
        ("error: Mosaic", 4e-4, "pallas row errored"),
        (None, 4e-4, "pallas never timed (CPU rehearsal)"),
        (1_500_000.0, "error: X", "diff row errored"),
    ],
)
def test_headline_stays_on_xla_when_pallas_unproven(bench, pallas_ips, diff, why):
    ips, path = bench.select_headline(1_000_000.0, pallas_ips, diff)
    assert (ips, path) == (1_000_000.0, "xla"), why


def test_headline_tolerance_is_the_named_constant(bench):
    at = bench.PALLAS_PARITY_TOL
    assert bench.select_headline(1.0, 2.0, at)[1] == "pallas_fused"
    assert bench.select_headline(1.0, 2.0, float(np.nextafter(at, 1.0)))[1] == "xla"


def test_sync_time_raises_when_rtt_dominates(benchrun, monkeypatch):
    """A timed region smaller than the readback RTT must be an ERROR, not
    a clamped near-zero denominator reporting absurd throughput."""
    monkeypatch.setattr(benchrun, "_rtt", lambda: 1e9)

    def thunk(carry):
        return jnp.float32(0.0) if carry is None else carry + 1.0

    with pytest.raises(RuntimeError, match="readback RTT"):
        benchrun._sync_time(thunk, repeats=2)


def test_sync_time_measures_a_real_thunk(benchrun):
    # The thunk must do real work: _sync_time (correctly) REFUSES to
    # report a timed region smaller than the readback RTT, so a trivial
    # v+1 thunk would be a flake on a loaded machine.
    import jax

    m = jnp.ones((400, 400))

    @jax.jit
    def step(v):
        return (v @ m).mean() * 1e-3

    def thunk(carry):
        v = jnp.ones((400, 400)) if carry is None else jnp.full((400, 400), carry)
        return step(v)

    sec = benchrun._sync_time(thunk, repeats=3)
    assert sec > 0 and math.isfinite(sec)


def test_drain_accepts_mixed_pytrees(benchrun):
    tree = {
        "f32": jnp.ones((4, 4)),
        "i32": jnp.arange(3),
        "bf16": jnp.ones((2,), jnp.bfloat16),
        "scalar": jnp.float32(1.0),
        "static": 7,  # non-array leaf must be skipped, not crash
    }
    benchrun._drain(tree)  # completing without error is the contract


def test_bench_refuses_to_measure_without_a_tpu(bench, monkeypatch):
    """No probe child, no wait, no CPU fallback: without a TPU the device
    check exits, naming what it found — unless the caller asked for the
    CPU by name, and then the device dict says cpu."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as exc:
        bench._device()
    assert "no TPU" in str(exc.value) and "'cpu'" in str(exc.value)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    dev = bench._device()
    assert dev["platform"] == "cpu" and dev["device_count"] >= 1


def test_bench_import_starts_nothing(bench, benchrun):
    """Importing the harnesses configures no compile cache and resolves
    no platform (benches/run.py used to do both at import)."""
    import jax

    assert not hasattr(bench, "_resolve_platform")
    assert not hasattr(bench, "_enable_compile_cache")
    assert "subprocess" not in vars(bench)
    # Whatever JAX itself took from the environment — nothing of ours.
    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR"
    )
