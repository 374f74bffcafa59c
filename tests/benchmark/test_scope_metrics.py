"""The per-layer metrics that join the device trace to the program's
catalog of its compiled step (`benchmark/scope_time.py` and the readers
`fwd_device_ms`, `bwd_device_ms`, `opt_device_ms`, `stem_device_ms`,
`scope_named_pct`), and `shard_ms`: on a hand-made trace and catalog with
hand-computed numbers, and in one traced rehearsal each of the tiny
one-chip and four-device cells on the CPU."""

import gzip
import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, scope_time, trace_reduce as tr  # noqa: E402
from parallel_cnn_tpu.obs import programs  # noqa: E402

NEW = ["fwd_device_ms", "bwd_device_ms", "opt_device_ms", "stem_device_ms",
       "scope_named_pct", "shard_ms"]
PROGRAM = r"^jit_step\b"


def _read(name, run):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(run)


# ------------------------------------------------ the manifest's new entries

def test_the_six_entries_are_appended_and_nothing_else_moved():
    per_layer = common.manifest()["per_layer"]
    assert [m["name"] for m in per_layer[-6:]] == NEW
    assert all(m["moves"] == "train_img_s_chip" for m in per_layer[-6:])
    assert [m.get("workloads") for m in per_layer[-6:]] == \
        [None] * 5 + [["r50_train_dp4"]]
    assert per_layer[12]["name"] == "peak_hbm_gb" and len(per_layer) == 19


# ------------------------------------------ hand-made trace, hand-made catalog

CATALOG = """HloModule jit_step

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %f.1 = f32[8]{0} negate(%p), metadata={op_name="jit(step)/grad/jvp(stem)/conv/neg"}
  %f.2 = f32[8]{0} negate(%f.1), metadata={op_name="jit(step)/grad/jvp(s1b1)/head/bn/neg"}
  %b.1 = f32[8]{0} negate(%f.2), metadata={op_name="jit(step)/grad/transpose(jvp(s1b1))/head/bn/neg"}
  %b.2 = f32[8]{0} negate(%b.1), metadata={op_name="jit(step)/grad/transpose(jvp(pool))/neg"}
  %o.1 = f32[8]{0} negate(%b.2), metadata={op_name="jit(step)/optimizer/neg"}
  ROOT %copy.1 = f32[8]{0} copy(%o.1)
}
"""


def _op(name, start, end):
    return tr.Op(name, "other", float(start), float(end))


def _hand_made():
    """Two devices, two runs of jit_step each (0-100 and 200-300, in ms
    here scaled to ns), one run of another module between them."""
    ms = 1e6

    def dev(shift):
        ops = []
        for base in (0, 200):
            ops += [_op("f.1", (base + 0) * ms, (base + 10) * ms),     # stem fwd 10
                    _op("f.2", (base + 10) * ms, (base + 30) * ms),    # fwd 20
                    _op("b.1", (base + 30) * ms, (base + 60) * ms),    # bwd 30
                    _op("b.2", (base + 60) * ms, (base + 65) * ms),    # pool bwd 5
                    _op("o.1", (base + 70) * ms, (base + 72 + shift) * ms),  # opt 2 (+shift)
                    _op("copy.1", (base + 80) * ms, (base + 84) * ms),     # unnamed 4
                    _op("fusion.99", (base + 90) * ms, (base + 96) * ms)]  # unknown 6
        ops.insert(7, _op("f.1", 150 * ms, 160 * ms))  # inside jit_gather: not counted
        mods = [("jit_step(123)", 0.0, 100 * ms), ("jit_gather(9)", 140 * ms, 170 * ms),
                ("jit_step(123)", 200 * ms, 300 * ms)]
        return ops, mods

    ops0, mods0 = dev(0)
    ops1, mods1 = dev(2)
    trace = tr.Trace(ops={0: ops0, 1: ops1}, async_ops={},
                     modules={0: mods0, 1: mods1}, host={})
    return types.SimpleNamespace(trace=trace, spans={}, counters={}, e2e={},
                                 window_s=0.3, program=PROGRAM, device={},
                                 ctx=types.SimpleNamespace(peak=None, config={}))


def test_scope_time_on_a_hand_made_trace_gives_hand_computed_numbers():
    run = _hand_made()
    assert _read("fwd_device_ms", run) is None  # no catalog recorded yet
    programs.record("jit_step", CATALOG)
    try:
        assert scope_time.phases(run) == pytest.approx(
            {"fwd": 30.0, "bwd": 35.0, "opt": 3.0, "unnamed": 10.0})
        assert _read("fwd_device_ms", run) == pytest.approx(30.0)
        assert _read("bwd_device_ms", run) == pytest.approx(35.0)
        assert _read("opt_device_ms", run) == pytest.approx(3.0)  # (2 + 4) / 2 devices
        assert _read("stem_device_ms", run) == pytest.approx(15.0)
        assert _read("scope_named_pct", run) == pytest.approx(100 * 68 / 78)
        assert scope_time.table(run) == pytest.approx(
            {"stem/conv fwd": 10.0, "s1b1/head/bn fwd": 20.0,
             "s1b1/head/bn bwd": 30.0, "pool bwd": 5.0, "optimizer opt": 3.0,
             "unnamed": 10.0})
        # a phase no op has reads 0, not nothing: the cell still reports it
        programs.record("jit_step", CATALOG.replace("/optimizer/", "/other/"))
        assert _read("opt_device_ms", run) == 0.0
        assert scope_time.phases(run)["unnamed"] == pytest.approx(13.0)
    finally:
        programs.clear()


def test_overlapping_groups_never_add_up_to_more_than_the_device_was_busy():
    ms = 1e6
    ops = [_op("f.2", 0, 60 * ms), _op("b.1", 40 * ms, 100 * ms)]
    trace = tr.Trace(ops={0: ops}, async_ops={},
                     modules={0: [("jit_step", 0.0, 100 * ms)]}, host={})
    run = types.SimpleNamespace(trace=trace, program=PROGRAM)
    programs.record("jit_step", CATALOG)
    try:
        # the overlap (40-60) goes to the phase named first
        assert scope_time.phases(run) == pytest.approx({"fwd": 60.0, "bwd": 40.0})
    finally:
        programs.clear()


def test_readers_return_nothing_without_a_run_of_the_program_or_a_catalog():
    run = _hand_made()
    programs.record("jit_other", CATALOG)
    try:
        assert all(_read(n, run) is None for n in NEW)
        run.program = r"^jit_nothing\b"
        programs.record("jit_step", CATALOG)
        assert all(_read(n, run) is None for n in NEW)
    finally:
        programs.clear()


def test_shard_ms_is_the_median_zoo_shard_span():
    run = types.SimpleNamespace(spans={"zoo.shard": [0.001, 0.003, 0.002, 0.050]})
    assert _read("shard_ms", run) == pytest.approx(2.5)


# ----------------------------------------------------- rehearsals on the CPU

def _rehearse(tmp, workload, devices):
    flags = f"--xla_force_host_platform_device_count={devices}" if devices > 1 else ""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags,
               JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload, "--seed",
         "2718281828", "--seconds", "0.3", "--trace", "1", "--keep-trace",
         str(tmp / "trace")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    with gzip.open(tmp / "trace" / f"{workload}.xplane.pb.gz") as f:
        return line, tr.read_xplane(f.read())


@pytest.fixture(scope="module", params=[("tiny_r18_train", 1), ("tiny_r18_train_dp4", 4)],
                ids=lambda p: p[0])
def rehearsal(request, tmp_path_factory):
    workload, devices = request.param
    line, trace = _rehearse(tmp_path_factory.mktemp(workload), workload, devices)
    return workload, line, trace


def test_rehearsal_line_carries_the_new_metrics(rehearsal):
    workload, line, _ = rehearsal
    assert line["correct"] is True
    want = set(NEW) - ({"shard_ms"} if workload == "tiny_r18_train" else set())
    got = line["metrics"]
    assert want <= set(got) and ("shard_ms" in got) == ("shard_ms" in want)
    for name in want - {"stem_device_ms"}:
        assert 0 < got[name]["value"] < float("inf"), name
    assert got["scope_named_pct"]["value"] <= 100.0
    assert got["fwd_device_ms"]["unit"] == "ms" and got["scope_named_pct"]["unit"] == "%"
    assert 0 <= got["stem_device_ms"]["value"] <= (
        got["fwd_device_ms"]["value"] + got["bwd_device_ms"]["value"])


def test_rehearsal_phases_fit_inside_the_steps_busy_time(rehearsal):
    """fwd + bwd + opt against the mean device-busy time of a run of
    jit_step, read from the kept trace with the reduction the accepted
    metrics use (the mean, not `step_device_ms`'s median: four to six
    runs on a shared CPU make the two differ by more than any bound)."""
    _, line, trace = rehearsal
    per_dev = []
    for d in trace.ops:
        busy = trace.busy_per_run(d, PROGRAM)
        per_dev.append(sum(busy) / len(busy) / 1e6)
    per_run_busy = sum(per_dev) / len(per_dev)
    got = line["metrics"]
    phases = sum(got[f"{p}_device_ms"]["value"] for p in ("fwd", "bwd", "opt"))
    assert phases <= 1.02 * per_run_busy
    assert phases == pytest.approx(
        per_run_busy * got["scope_named_pct"]["value"] / 100.0, rel=0.02)
