"""`batch_select_ms` (PR 25): the manifest's appended entry, the reader on
hand-made traces with hand-computed numbers, and one traced rehearsal of
the tiny one-chip cell on the CPU, where `zoo.train`'s device loader must
show as `jit_select_batch` and no longer as an eager `jit_gather`."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import common, trace_reduce as tr  # noqa: E402

AS_PR24_LEFT_THEM = [
    "warmup_s", "data_wait_pct", "readback_ms", "epoch_stall_pct",
    "step_device_ms", "mfu_pct", "collective_ms_step", "collective_exposed_pct",
    "conv_time_pct", "conv_roofline", "device_idle_pct", "device_idle_worst_pct",
    "peak_hbm_gb", "fwd_device_ms", "bwd_device_ms", "opt_device_ms",
    "stem_device_ms", "scope_named_pct", "shard_ms"]


def _read(run):
    return importlib.import_module("benchmark.layer_metrics.batch_select_ms").read(run)


def test_the_entry_is_appended_and_nothing_before_it_moved():
    per_layer = common.manifest()["per_layer"]
    assert [m["name"] for m in per_layer[:19]] == AS_PR24_LEFT_THEM
    assert per_layer[19] == {
        "name": "batch_select_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "zoo trainer loop",
        "moves": "train_img_s_chip",
        "workloads": ["r50_train", "r18_train", "r50_train_dp4"]}
    assert per_layer[1]["layer"] == per_layer[19]["layer"]  # data_wait_pct's layer


def _trace(select_runs):
    """Two devices, a jit_step on each; `select_runs` (start, end, busy-end
    in ms) of jit_select_batch on device 0 alone, as under a mesh."""
    ms = 1e6
    ops0 = [tr.Op("fusion.1", "other", 0.0, 90 * ms)]
    mods0 = [("jit_step(1)", 0.0, 100 * ms)]
    for k, (start, end, busy_end) in enumerate(select_runs):
        mods0.append((f"jit_select_batch({7 + k})", start * ms, end * ms))
        # a gather and an overlapping copy: the union counts, not the sum
        ops0 += [tr.Op("fusion", "other", start * ms, (start + 0.5) * ms),
                 tr.Op("copy.1", "copy", (start + 0.25) * ms, busy_end * ms)]
    ops1 = [tr.Op("fusion.1", "other", 0.0, 95 * ms)]
    mods1 = [("jit_step(1)", 0.0, 100 * ms),
             ("jit_select_batchx(3)", 100 * ms, 120 * ms)]  # another program's name
    return tr.Trace(ops={0: ops0, 1: ops1}, async_ops={},
                    modules={0: mods0, 1: mods1}, host={})


@pytest.mark.parametrize("runs,want", [
    ([(100, 102, 101.0), (200, 204, 203.0)], 2.0),  # busy 1.0 and 3.0 ms: the median
    ([(100, 102, 101.5)], 1.5),
    ([], None),  # the parent: the loader indexes eagerly, nothing to read
], ids=["two-runs", "one-run", "no-run"])
def test_reader_on_a_hand_made_trace(runs, want):
    run = types.SimpleNamespace(trace=_trace(runs), program=r"^jit_step\b")
    got = _read(run)
    assert got is None if want is None else got == pytest.approx(want)


def test_reader_without_a_trace_returns_nothing():
    assert _read(types.SimpleNamespace(trace=None)) is None


def test_rehearsal_selects_batches_in_one_named_program(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny_r18_train",
         "--seed", "3141592653", "--seconds", "0.3", "--trace", "1",
         "--keep-trace", str(tmp_path / "trace")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = line["metrics"]["batch_select_ms"]
    assert got["unit"] == "ms" and 0 < got["value"] < line["metrics"]["step_device_ms"]["value"]
    import gzip

    with gzip.open(tmp_path / "trace" / "tiny_r18_train.xplane.pb.gz") as f:
        trace = tr.read_xplane(f.read())
    names = {n.split("(")[0] for mods in trace.modules.values() for n, _, _ in mods}
    assert "jit_select_batch" in names and "jit_gather" not in names
    # one selection a step, nothing else between steps but the loss's add
    steps = sum(len(trace.runs(d, r"^jit_step\b")) for d in trace.modules)
    selects = sum(len(trace.runs(d, r"^jit_select_batch\b")) for d in trace.modules)
    assert steps >= 4 and abs(selects - steps) <= 1
