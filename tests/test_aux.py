"""Aux-subsystem tests: checkpoint/resume, metrics, per-phase profiling,
CLI driver, distributed no-op init (SURVEY.md §5 gaps the framework fills).
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallel_cnn_tpu.models import lenet_ref
from parallel_cnn_tpu.train import checkpoint
from parallel_cnn_tpu.utils import profiling
from parallel_cnn_tpu.utils.metrics import MetricsLogger


def test_checkpoint_roundtrip(tmp_path):
    params = lenet_ref.init(jax.random.key(1))
    state = checkpoint.TrainState(epoch=3, epoch_errors=[0.5, 0.3, 0.2])
    path = str(tmp_path / "ckpt_3.npz")
    checkpoint.save(path, params, state)
    like = lenet_ref.init(jax.random.key(2))  # different values, same shape
    restored, rstate = checkpoint.restore(path, like)
    for a, b in zip(
        jax.tree_util.tree_leaves(params),
        jax.tree_util.tree_leaves(restored),
        strict=True,
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert rstate.epoch == 3
    assert rstate.epoch_errors == [0.5, 0.3, 0.2]


def test_checkpoint_structure_mismatch_is_error(tmp_path):
    params = lenet_ref.init(jax.random.key(1))
    path = str(tmp_path / "ckpt_1.npz")
    checkpoint.save(path, params)
    bad = {"c1": params["c1"]}  # missing layers
    with pytest.raises(ValueError, match="structure mismatch"):
        checkpoint.restore(path, bad)
    reshaped = jax.tree_util.tree_map(lambda x: x, params)
    reshaped["f"]["w"] = jnp.zeros((5, 216), jnp.float32)
    with pytest.raises(ValueError, match="expected"):
        checkpoint.restore(path, reshaped)


def test_checkpoint_latest(tmp_path):
    params = lenet_ref.init(jax.random.key(0))
    assert checkpoint.latest(str(tmp_path)) is None
    for e in (1, 2, 10):
        checkpoint.save(str(tmp_path / f"ckpt_{e}.npz"), params)
    assert checkpoint.latest(str(tmp_path)).endswith("ckpt_10.npz")


def test_metrics_logger(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with MetricsLogger(path=path) as m:
        m.record(event="epoch", epoch=1, error=jnp.float32(0.25))
        m.record(event="final", error_rate=1.5)
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["error"] == 0.25 and isinstance(lines[0]["error"], float)
    assert lines[1]["event"] == "final"
    assert m.records[0]["epoch"] == 1


def test_profile_phases_shape():
    params = lenet_ref.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.uniform(0, 1, (32, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (32,)).astype(np.int32))
    phases = profiling.profile_phases(params, xs, ys, repeats=2)
    assert set(phases) == {"conv", "pool", "fc", "grad", "total_forward"}
    assert all(v > 0 for v in phases.values())
    table = profiling.report(phases, n_images=32)
    assert "conv" in table and "images/sec" in table


def test_distributed_single_process_noop(monkeypatch):
    from parallel_cnn_tpu.parallel import distributed

    for var in ("PCNN_COORDINATOR", "PCNN_NUM_PROCESSES", "PCNN_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    assert distributed.initialize() is False
    info = distributed.process_info()
    assert info["num_processes"] == 1 and info["process_id"] == 0


def _run_cli(args, env_extra=None):
    env = dict(os.environ)
    # A child of this (JAX-holding) test process must never reach for an
    # accelerator: pin it to the CPU.
    env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    return subprocess.run(
        [sys.executable, "-m", "parallel_cnn_tpu", *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )


@pytest.mark.slow
def test_cli_end_to_end_with_checkpoint_resume(tmp_path):
    ckpt = str(tmp_path / "ckpts")
    metrics = str(tmp_path / "m.jsonl")
    base = [
        "--loader", "synthetic",
        "--synthetic-train-count", "512",
        "--synthetic-test-count", "128",
        "--batch-size", "64",
        "--epochs", "2",
        "--checkpoint-dir", ckpt,
        "--metrics", metrics,
    ]
    r = _run_cli(base)
    assert r.returncode == 0, r.stderr
    assert "Learning" in r.stdout and "Error Rate:" in r.stdout
    assert checkpoint.latest(ckpt).endswith("ckpt_2.npz")
    recs = [json.loads(l) for l in open(metrics)]
    assert recs[-1]["event"] == "final"

    # resume: asks for 3 epochs total, 2 already done → exactly 1 more
    r2 = _run_cli(base[:-4] + ["--epochs", "3", "--resume",
                               "--checkpoint-dir", ckpt, "--profile"])
    assert r2.returncode == 0, r2.stderr
    assert "resumed from" in r2.stdout
    assert r2.stdout.count("error:") == 1
    # --profile prints the per-phase table (paper Tables 4-8 shape) after
    # training — the one driver flag no CLI test exercised.
    for phase in ("conv", "pool", "fc"):
        assert phase in r2.stdout, r2.stdout[-500:]


@pytest.mark.slow
def test_cli_zoo_model(tmp_path):
    """--model routes to the zoo trainer (train/zoo.py) with per-epoch
    eval, checkpointing, and metrics — the Config.model field as a real
    driver surface."""
    ckpt = str(tmp_path / "zck")
    metrics = str(tmp_path / "zm.jsonl")
    r = _run_cli([
        "--model", "cifar_cnn",
        "--epochs", "1",
        "--batch-size", "64",
        "--synthetic-train-count", "256",
        "--synthetic-test-count", "64",
        "--checkpoint-dir", ckpt,
        "--metrics", metrics,
    ])
    assert r.returncode == 0, r.stderr
    assert "epoch 1: loss" in r.stdout and "acc" in r.stdout
    assert checkpoint.latest(ckpt) is not None
    recs = [json.loads(l) for l in open(metrics)]
    assert any(rec.get("event") == "zoo_epoch" for rec in recs)


def test_cli_zoo_native_loader():
    """--zoo-loader native feeds the zoo trainer from the C++ prefetch
    ring through the CLI (round 4: the data runtime at zoo shapes)."""
    r = _run_cli([
        "--model", "cifar_cnn",
        "--epochs", "1",
        "--batch-size", "32",
        "--synthetic-train-count", "96",
        "--synthetic-test-count", "32",
        "--zoo-loader", "native",
    ])
    assert r.returncode == 0, r.stderr
    assert "epoch 1: loss" in r.stdout


@pytest.mark.slow
def test_cli_mesh_training(tmp_path):
    """--mesh-data/--mesh-model drive learn() over the 8-device CPU mesh
    from a real subprocess (≙ mpirun launching MPI/Main.cpp:43-53) and
    match the single-device run's epoch errors exactly."""
    base = [
        "--loader", "synthetic",
        "--synthetic-train-count", "512",
        "--synthetic-test-count", "128",
        "--batch-size", "64",
        "--epochs", "1",
        "--prefetch", "off",
    ]
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    single = _run_cli(base, env_extra=env)
    assert single.returncode == 0, single.stderr
    meshed = _run_cli(base + ["--mesh-data", "4", "--mesh-model", "2"],
                      env_extra=env)
    assert meshed.returncode == 0, meshed.stderr
    assert "mesh: {'data': 4, 'model': 2}" in meshed.stdout

    def errors(out):
        return [float(l.split(",")[0].split()[1]) for l in out.splitlines()
                if l.startswith("error:")]

    def rate(out):
        return [float(l.split()[-1].rstrip("%")) for l in out.splitlines()
                if l.startswith("Error Rate:")]

    # Different reduction order (per-shard sums + psum vs one jnp.mean):
    # values agree to fp tolerance, not bit-exactly.
    np.testing.assert_allclose(errors(meshed.stdout), errors(single.stdout),
                               rtol=1e-5)
    np.testing.assert_allclose(rate(meshed.stdout), rate(single.stdout),
                               atol=0.5)


@pytest.mark.slow
def test_two_process_distributed_smoke(tmp_path):
    """Two real OS processes join via parallel/distributed.py (the mpirun
    analog) and agree on a cross-process allgather — exercising
    jax.distributed.initialize for real, not as a no-op (VERDICT r1 #10)."""
    import os
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    worker = os.path.join(os.path.dirname(__file__), "_distributed_worker.py")
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            PCNN_COORDINATOR=f"127.0.0.1:{port}",
            PCNN_NUM_PROCESSES="2",
            PCNN_PROCESS_ID=str(rank),
            # 4 virtual devices per process → an 8-device GLOBAL mesh for
            # the cross-rank DP training steps (overrides conftest's 8).
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, worker],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, err
            outs.append(out)
    finally:
        # Never orphan a rank: a hung/failed peer would otherwise sit in
        # jax.distributed.initialize forever, pinning a CPU across re-runs.
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rank, out in enumerate(outs):
        line = [l for l in out.splitlines() if l.startswith("RESULT")][0]
        # First four tokens only: under load, a worker's async log line can
        # interleave onto the tail of the RESULT line (observed once in a
        # loaded full-suite run) — the leading fields are still intact.
        _, nproc, pid, gathered = line.split()[:4]
        assert nproc == "2" and pid == str(rank)
        assert gathered == "0,1"  # the collective saw BOTH processes

    # Multi-PROCESS DP training (≙ the MPI driver training across ranks,
    # MPI/Main.cpp:43-112): both ranks ran 3 DP steps over the global
    # 8-device mesh (4 local devices each) and must agree with each other
    # AND with the single-process trajectory on this process's 8 devices.
    # (Constants mirror _distributed_worker.py — asserted below rather than
    # imported, because importing the worker would run its module-level
    # jax.config mutations in THIS process.)
    n, b = 3, 16

    trains = []
    for out in outs:
        line = [l for l in out.splitlines() if l.split()[:1] == ["TRAIN"]][0]
        trains.append([float(v) for v in line.split()[1].split(",")])
    assert trains[0] == trains[1], "ranks diverged (the reference's bug B7)"
    assert len(trains[0]) == n, "worker TRAIN_STEPS drifted from the test's"

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step as step_lib

    params = lenet_ref.init(jax.random.key(7))
    rng = np.random.default_rng(123)
    xs = rng.uniform(0, 1, (n, b, 28, 28)).astype(np.float32)
    ys = rng.integers(0, 10, (n, b)).astype(np.int32)
    ref_errs = []
    for i in range(n):
        params, e = step_lib.batched_step(
            params, jnp.asarray(xs[i]), jnp.asarray(ys[i]), 0.1
        )
        ref_errs.append(float(e))
    np.testing.assert_allclose(trains[0], ref_errs, rtol=1e-5)

    # Hybrid 2-D mesh with the MODEL axis spanning the two processes:
    # activation/grad psums are genuine cross-process collectives, and the
    # trajectory must still match the single-device batched run.
    trains2d = []
    for out in outs:
        line = [l for l in out.splitlines() if l.startswith("TRAIN2D")][0]
        trains2d.append([float(v) for v in line.split()[1].split(",")])
    assert trains2d[0] == trains2d[1]
    np.testing.assert_allclose(trains2d[0], ref_errs, rtol=1e-4)

    # Hierarchical comm step + ZeRO-3 over the REAL 2-process (host,
    # device) mesh: both ranks agree, and both trajectories match the
    # single-process zoo steps on the EMULATED 2x4 hier mesh (this
    # process's 8 devices) — same mesh decomposition, so the only
    # difference is which transport the host-axis ring hops cross.
    def _tagged(tag):
        vals = []
        for out in outs:
            line = [l for l in out.splitlines() if l.split()[:1] == [tag]][0]
            vals.append([float(v) for v in line.split()[1].split(",")])
        assert vals[0] == vals[1], f"{tag}: ranks diverged"
        return vals[0]

    hier, z3 = _tagged("TRAINHIER"), _tagged("TRAINZ3")

    from parallel_cnn_tpu.config import CommConfig, FusedStepConfig
    from parallel_cnn_tpu.nn import core, layers
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.train import zoo

    tiny_shape = (8, 8, 3)  # mirrors the worker's _tiny_model/_tiny_data
    model = core.Sequential([
        layers.Conv2D(4, (3, 3)), layers.BatchNorm(), layers.ReLU(),
        layers.MaxPool(), layers.Flatten(), layers.Dense(10),
    ])
    rng2 = np.random.default_rng(456)
    xs2 = rng2.normal(size=(n, b) + tiny_shape).astype(np.float32)
    ys2 = rng2.integers(0, 10, (n, b)).astype(np.int32)
    hmesh = mesh_lib.make_hier_mesh(n_hosts=2)
    comm = CommConfig(impl="hierarchical", bucket_bytes=2048, hosts=2)

    opt = zoo.make_optimizer(lr=0.05)
    st = zoo.init_state(model, jax.random.key(7), tiny_shape, opt)
    hstep = zoo.make_train_step(model, opt, accum_steps=2, mesh=hmesh,
                                comm=comm)
    ref_hier = []
    for i in range(n):
        st, l = hstep(st, jnp.asarray(xs2[i]), jnp.asarray(ys2[i]))
        ref_hier.append(float(l))
    np.testing.assert_allclose(hier, ref_hier, rtol=1e-5, atol=1e-6)

    fused = FusedStepConfig(update=True, tail=True, zero=3)
    zst, plan = zoo.init_zero3_state(
        model, jax.random.key(7), tiny_shape, n_data=4, fused=fused,
        bucket_bytes=comm.bucket_bytes, n_host=2,
    )
    zstep = zoo.make_zero3_train_step(
        model, lr=0.05, momentum=0.9, accum_steps=2, mesh=hmesh,
        augment=None, comm=comm, fused=fused, plan=plan,
    )
    ref_z3 = []
    for i in range(n):
        zst, l = zstep(zst, jnp.asarray(xs2[i]), jnp.asarray(ys2[i]))
        ref_z3.append(float(l))
    np.testing.assert_allclose(z3, ref_z3, rtol=1e-5, atol=1e-6)

    # Elastic resize ACROSS the process boundary (8 → 4 with two
    # survivors per process): the worker computes fixed-vs-elastic loss
    # parity and reshard bit-exactness in-process (only it can read the
    # global arrays) and reports both; ranks must agree.
    elastic_lines = []
    for out in outs:
        line = [l for l in out.splitlines()
                if l.startswith("TRAINELASTIC")][0]
        elastic_lines.append(line.split()[1:3])
    assert elastic_lines[0] == elastic_lines[1], "elastic: ranks diverged"
    max_dloss, bitexact = elastic_lines[0]
    assert float(max_dloss) <= 1e-5, \
        f"elastic resize broke loss parity: max dloss {max_dloss}"
    assert bitexact == "1", "pure reshard was not bit-exact"

    # EASGD elastic-averaging round over the real cross-process ring
    # (train/async_dp.easgd_round_sharded): ranks agree, and the summed
    # digests match the host-side numpy reference of one ρ-pull.
    async_lines = []
    for out in outs:
        line = [l for l in out.splitlines()
                if l.startswith("TRAINASYNC")][0]
        async_lines.append(line.split()[1:3])
    assert async_lines[0] == async_lines[1], "async: ranks diverged"
    got_dw, got_dc = (float(v) for v in async_lines[0])
    n_dev, shard_len, rho = 8, 32, 0.5
    arng = np.random.default_rng(99)  # mirrors train_trajectory_async
    wf = arng.normal(size=(n_dev, n_dev * shard_len)).astype(np.float32)
    cs = arng.normal(size=(n_dev, shard_len)).astype(np.float32)
    center = cs.reshape(-1)
    delta = rho * (wf - center[None, :])
    want_dw = float(np.sum(wf - delta))
    want_dc = float(np.sum(center + np.mean(delta, axis=0)))
    np.testing.assert_allclose(got_dw, want_dw, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(got_dc, want_dc, rtol=1e-4, atol=1e-3)


def test_cli_zoo_profile_writes_trace(tmp_path):
    """Zoo --profile captures a jax.profiler trace of steady-state steps
    (the MFU-attribution tool; lenet --profile prints the phase table)."""
    ckpt = str(tmp_path / "zp")
    r = _run_cli([
        "--model", "cifar_cnn",
        "--epochs", "1",
        "--batch-size", "32",
        "--synthetic-train-count", "64",
        "--synthetic-test-count", "32",
        "--checkpoint-dir", ckpt,
        "--profile",
    ])
    assert r.returncode == 0, r.stderr
    assert "xla trace (3 steps) written to" in r.stdout
    import os as _os

    trace_dir = _os.path.join(ckpt, "zoo_xla_trace")
    assert _os.path.isdir(trace_dir) and _os.listdir(trace_dir)


# ----------------------------------------------------- utils/backend.py


class _StubDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_is_tpu_is_the_platform_and_nothing_else():
    from parallel_cnn_tpu.utils import backend

    assert backend.is_tpu([_StubDevice("tpu", "TPU v5 lite")])
    # No other platform name fronts a TPU, and a kind string does not
    # make one: Pallas compiles only where platform == "tpu".
    assert not backend.is_tpu([_StubDevice("cpu", "cpu")])
    assert not backend.is_tpu([_StubDevice("gpu", "NVIDIA A100")])
    assert not backend.is_tpu([_StubDevice("proxy", "TPU v5 lite")])
    assert not backend.is_tpu([])
    assert not backend.is_tpu()  # the suite's pinned CPU platform


def test_peak_flops_refuses_unknown_device_kinds():
    from parallel_cnn_tpu.utils import backend

    assert backend.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(backend.UnknownDeviceKind, match="TPU v9"):
        backend.peak_flops("TPU v9")
    with pytest.raises(backend.UnknownDeviceKind):
        backend.peak_flops("cpu")


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch,
                                                         tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper issues NO
    config.update for the cache dir (the path is part of the cache key;
    the operator placed it); unset, it is the fixed <checkout>/.jax_cache."""
    from parallel_cnn_tpu.utils import backend

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.append((k, v))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.enable_compile_cache() == str(tmp_path)
    assert updates == []

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert backend.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]


def test_native_build_trusts_only_what_it_built(tmp_path, monkeypatch):
    """Staleness is by content stamp, not mtime: a library without a
    matching stamp (copied in from elsewhere, or sources edited since) is
    rebuilt; one this code built from these sources is not."""
    native = pytest.importorskip("parallel_cnn_tpu.data.native")

    calls = []
    real_run = native.subprocess.run

    def counting_run(cmd, *a, **k):
        calls.append(cmd)
        return real_run(cmd, *a, **k)

    src_dir = os.path.dirname(native._LIB_PATH)
    work = tmp_path / "native"
    shutil.copytree(src_dir, work)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(work))
    monkeypatch.setattr(native, "_LIB_PATH",
                        str(work / "libpcnn_native.so"))
    monkeypatch.setattr(native, "_STAMP_PATH",
                        str(work / "libpcnn_native.so.stamp"))
    monkeypatch.setattr(native.subprocess, "run", counting_run)

    os.remove(native._STAMP_PATH)      # a .so it did not build itself
    native._build()
    assert len(calls) == 1 and os.path.exists(native._STAMP_PATH)
    native._build()                    # built from these sources: trusted
    assert len(calls) == 1
    os.utime(work / "batcher.cc")      # newer mtime, same bytes: trusted
    native._build()
    assert len(calls) == 1
    with open(work / "batcher.cc", "a") as f:
        f.write("\n// edited\n")       # different bytes: rebuilt
    native._build()
    assert len(calls) == 2
    os.remove(native._LIB_PATH)        # absent: rebuilt
    native._build()
    assert len(calls) == 3
