"""Benchmark harness (≙ the reference paper's result tables, SURVEY.md §6 /
C19): per-layer phase times (Tables 4-7 shape), end-to-end epoch time and
throughput (Tables 1, 8), DP scaling over the device mesh (Tables 2-3
shape), and model-zoo configs (BASELINE.json #3-#5).

    python benches/run.py [--quick] [--json PATH] [--md PATH]

Every row reports value + unit + the reference baseline it compares
against (from BASELINE.md, measured on the reference's own hardware — a
context gap the report states rather than hides). The headline driver
contract stays in bench.py; this harness is the full table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import asdict, dataclass
from typing import List, Optional

# Runnable as a plain script: the repo root (parent of benches/) must be
# importable for `parallel_cnn_tpu`.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# The device contract (one process, no platform switching, exit non-zero
# without a TPU unless JAX_PLATFORMS=cpu) is bench.py's; main() applies it.
import bench as _bench

# Reference numbers (BASELINE.md; paper PDF §6 Tables 1-8).
SEQ_EPOCH_S = 102.317095          # Table 1 (60k samples, CPU VM)
CUDA_EPOCH_S = 2.9969857          # Table 8 (T4)
CUDA_CONV_MS = 90.173             # Table 5 (per epoch, T4)
CUDA_POOL_MS = 5.1927             # Table 6
CUDA_FC_MS = 0.386624             # Table 7
EPOCH_IMAGES = 60_000


@dataclass
class Row:
    name: str
    value: float
    unit: str
    baseline: Optional[float] = None
    baseline_src: str = ""
    speedup: Optional[float] = None
    # Same protocol as bench.py's headline: throughput
    # rows are the MEDIAN of value_samples same-session measurements with
    # the min–max range alongside; single-sample rows leave range None.
    value_range: Optional[List[float]] = None
    value_samples: int = 1

    def finish(self) -> "Row":
        if self.baseline is not None and self.value > 0:
            # value/baseline semantics depend on unit: time-like units
            # invert (smaller is better).
            if self.unit.endswith("/sec"):
                self.speedup = round(self.value / self.baseline, 2)
            else:
                self.speedup = round(self.baseline / self.value, 2)
        return self


_drain_cache: dict = {}


def _drain(tree) -> None:
    """Execution barrier for a whole pytree.

    A single-leaf readback is not enough: one leaf can complete long
    before the rest of the program (reading only ZooState's first leaf —
    an optimizer count that increments without touching the heavy compute
    — once timed ResNet-50 @224² at a physically impossible 33 ms/step).
    So: jit a scalar that consumes EVERY leaf and read that scalar back —
    the one host readback cannot materialize until the whole program has
    run."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "dtype")]
    key = tuple((l.shape, str(l.dtype)) for l in leaves)
    fn = _drain_cache.get(key)
    if fn is None:
        def _reduce(*ls):
            tot = jnp.float32(0.0)
            for l in ls:
                tot = tot + jnp.sum(jnp.abs(l.astype(jnp.float32)))
            return tot

        fn = jax.jit(_reduce)
        _drain_cache[key] = fn
    np.asarray(fn(*leaves))


_tiny_chain = jax.jit(lambda v: v + 1.0)


def _rtt() -> float:
    """Min-of-3 readback RTT on a trivial chained program (min, not mean:
    RTT jitter only ever ADDS latency, so the smallest sample is the
    least-biased estimate of the floor being subtracted)."""
    v = _tiny_chain(jnp.float32(0.0))
    np.asarray(v)
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        v = _tiny_chain(v)
        np.asarray(v)
        samples.append(time.perf_counter() - t0)
    return min(samples)


def _sync_time(thunk, repeats: int) -> float:
    """Chained-dispatch timing: warmup drained, `repeats` chained calls,
    one full drain, minus the measured readback RTT (as bench.py does —
    the RTT otherwise dominates short rows).

    When the timed region doesn't clear the RTT — a cheap row like
    --quick cifar_cnn — the
    measurement is auto-retried with the repeat count scaled up until
    compute dominates (target: elapsed >= 4× RTT), rather than raising
    and killing the whole suite. A clamped near-zero denominator would
    report absurd throughput as if legitimate, so after the retry budget
    is spent we still raise; main() converts that into a labeled error
    row instead of an aborted run."""
    out = thunk(None)
    _drain(out)
    carry = out
    for _attempt in range(4):
        t0 = time.perf_counter()
        for _ in range(repeats):
            carry = thunk(carry)
        _drain(carry)
        elapsed = time.perf_counter() - t0
        rtt = _rtt()
        corrected = elapsed - rtt
        if corrected > 0 and elapsed >= 4 * rtt:
            return corrected / repeats
        ran = repeats  # what this attempt actually executed (for the error)
        # Scale repeats so the next attempt lands ~8× over the RTT floor —
        # capped: an absurd RTT (a glitch, or a test stubbing it) must
        # exhaust the 4 attempts and raise, not spin for 8·rtt/per_rep
        # iterations.
        per_rep = max(elapsed / repeats, 1e-6)
        repeats = min(max(repeats * 2, int(8 * rtt / per_rep) + 1), 4096)
    raise RuntimeError(
        f"timed region ({elapsed * 1e3:.1f} ms over {ran} repeats, RTT "
        f"{rtt * 1e3:.1f} ms) never exceeded the readback RTT after repeat "
        "auto-scaling; the row's compute is unmeasurably small"
    )


def _n_samples() -> int:
    """Same-session sample count for throughput rows (bench.py protocol:
    ≥5 on-chip — three left the run-to-run range wider than the effect
    sizes being claimed; 3 for a CPU rehearsal, so the median+range stays
    meaningful off-TPU too)."""
    from parallel_cnn_tpu.utils.backend import canonical_platform

    return max(int(os.environ.get(
        "PCNN_BENCH_SAMPLES", "5" if canonical_platform() == "tpu" else "3"
    )), 1)


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _sampled_ips(thunk, repeats: int, images_per_call: float):
    """N independent _sync_time samples → (median img/s, [min, max], n).

    Each sample is a full warmed, chained, RTT-corrected measurement; the
    median is the row value, the range is the honesty bar on it."""
    secs = [_sync_time(thunk, repeats) for _ in range(_n_samples())]
    ips = [round(images_per_call / s, 1) for s in secs]
    return _median(ips), [min(ips), max(ips)], len(ips)


def bench_lenet_throughput(quick: bool) -> List[Row]:
    """End-to-end minibatch training throughput (≙ Table 8 / BASELINE.md
    derived ≈20k img/s CUDA)."""
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.ops import reference as ops
    from parallel_cnn_tpu.ops.activations import apply_grad

    batch = 2048
    steps = 8 if quick else 29
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.uniform(0, 1, (steps, batch, 28, 28)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, (steps, batch)).astype(np.int32))
    params = lenet_ref.init(jax.random.key(0))

    @jax.jit
    def epoch(params, images, labels):
        def body(p, xy):
            x, y = xy
            errs, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(p, x, y)
            return (
                apply_grad(p, jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads), 0.1),
                jnp.mean(errs),
            )

        p, errs = jax.lax.scan(body, params, (images, labels))
        return p, jnp.mean(errs)

    def thunk(carry):
        p = carry[0] if carry is not None else params
        return epoch(p, images, labels)

    ips, ips_range, n_s = _sampled_ips(
        thunk, repeats=2 if quick else 5, images_per_call=steps * batch
    )
    epoch_s = EPOCH_IMAGES / ips
    return [
        Row("train_throughput_batched", round(ips, 1), "images/sec",
            EPOCH_IMAGES / CUDA_EPOCH_S, "CUDA Table 8",
            value_range=ips_range, value_samples=n_s).finish(),
        Row("epoch_time_batched", round(epoch_s, 4), "sec/epoch(60k)",
            CUDA_EPOCH_S, "CUDA Table 8", value_samples=n_s).finish(),
        Row("epoch_time_vs_sequential", round(epoch_s, 4), "sec/epoch(60k)",
            SEQ_EPOCH_S, "Sequential Table 1", value_samples=n_s).finish(),
    ]


def bench_lenet_parity_epoch(quick: bool) -> List[Row]:
    """Strict-parity per-sample SGD epoch (≙ Table 1's workload: batch=1,
    60k sequential updates — as ONE lax.scan program)."""
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step as step_lib

    n = 6_000 if quick else 60_000
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.uniform(0, 1, (n, 28, 28)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    params = lenet_ref.init(jax.random.key(0))

    def thunk(carry):
        p = carry[0] if carry is not None else params
        return step_lib.scan_epoch(
            jax.tree_util.tree_map(jnp.array, p), images, labels, 0.1
        )

    sec = _sync_time(thunk, repeats=1 if quick else 2)
    epoch_s = sec * (EPOCH_IMAGES / n)
    return [
        Row("epoch_time_per_sample_sgd", round(epoch_s, 3), "sec/epoch(60k)",
            SEQ_EPOCH_S, "Sequential Table 1").finish()
    ]


def bench_phases(quick: bool) -> List[Row]:
    """Per-layer forward phases (≙ Tables 4-7). Reference CUDA rows are
    per-epoch totals on a T4; ours are scaled to the same 60k-image epoch."""
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.utils import profiling

    batch = 2048
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.uniform(0, 1, (batch, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (batch,)).astype(np.int32))
    params = lenet_ref.init(jax.random.key(0))
    phases = profiling.profile_phases(
        params, xs, ys, repeats=10 if quick else 50
    )
    scale = EPOCH_IMAGES / batch  # per-batch → per-60k-epoch
    refs = {"conv": CUDA_CONV_MS, "pool": CUDA_POOL_MS, "fc": CUDA_FC_MS}
    rows = []
    for name, sec in phases.items():
        rows.append(
            Row(f"phase_{name}", round(sec * 1e3 * scale, 3), "ms/epoch(60k)",
                refs.get(name), f"CUDA Table {dict(conv=5, pool=6, fc=7).get(name, '-')}" if name in refs else "").finish()
        )
    return rows


def bench_ops_paths(quick: bool) -> List[Row]:
    """Path A (jnp/lax) vs path B (Pallas/Mosaic kernels) on the SAME
    minibatch train step — the A-vs-B comparison the CUDA backend implies
    by wiring its kernels into its driver (CUDA/main.cu:56-163). On TPU
    path B is compiled Mosaic; elsewhere it runs the Pallas interpreter
    (orders of magnitude slower — the row still proves numerical parity)."""
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step as step_lib
    from parallel_cnn_tpu.utils.backend import canonical_platform

    batch = 2048
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (batch, 28, 28)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (batch,)).astype(np.int32))

    on_tpu = canonical_platform() == "tpu"
    repeats = (2 if quick else 5) if not on_tpu else (10 if quick else 30)
    rows = []
    paths = [("reference", step_lib.batched_step)]
    # Interpreted Pallas at batch=2048 is minutes/step on CPU; bench the
    # kernel path only where it compiles (TPU) unless explicitly forced.
    if on_tpu or os.environ.get("PCNN_BENCH_PALLAS"):
        paths.append(("pallas", step_lib.pallas_batched_step))
    else:
        print("[bench_ops_paths] pallas row skipped (no TPU; "
              "set PCNN_BENCH_PALLAS=1 to force interpret mode)", flush=True)
    for name, step in paths:
        params = lenet_ref.init(jax.random.key(0))

        def thunk(carry, step=step, params=params):
            p = carry[0] if carry is not None else params
            return step(p, x, y, 0.1)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=repeats, images_per_call=batch
        )
        rows.append(
            Row(f"ops_{name}_step", round(ips, 1), "images/sec",
                EPOCH_IMAGES / CUDA_EPOCH_S, "CUDA Table 8",
                value_range=ips_range, value_samples=n_s).finish()
        )
    return rows


def bench_dp_scaling(quick: bool) -> List[Row]:
    """DP scaling over the data mesh axis (≙ Tables 2-3's speedup/efficiency
    shape). Uses however many devices the platform exposes (8 virtual CPU
    devices under the test env; skipped on a single chip)."""
    from parallel_cnn_tpu.config import MeshConfig
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.parallel import data_parallel, mesh as mesh_lib

    n_dev = len(jax.devices())
    if n_dev < 2:
        return []
    rows = []
    global_batch = 1024
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.uniform(0, 1, (global_batch, 28, 28)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (global_batch,)).astype(np.int32))
    sizes = [d for d in (1, 2, 4, 8) if d <= n_dev]

    def time_dp(d: int, gb: int) -> float:
        """Seconds per DP step on d devices at global batch gb (shared
        scaffolding for the strong- and weak-scaling tables)."""
        mesh = mesh_lib.make_mesh(
            MeshConfig(data=d, model=1), devices=jax.devices()[:d]
        )
        step = data_parallel.make_dp_step(mesh, dt=0.1, global_batch=gb)
        params = mesh_lib.replicate(mesh, lenet_ref.init(jax.random.key(0)))
        reps = gb // x.shape[0] + 1
        xs, ys = mesh_lib.shard_batch(
            mesh,
            (jnp.tile(x, (reps, 1, 1))[:gb], jnp.tile(y, (reps,))[:gb]),
        )

        def thunk(carry, step=step, xs=xs, ys=ys, params=params):
            p = carry[0] if carry is not None else params
            return step(p, xs, ys)

        return _sync_time(thunk, repeats=3 if quick else 10)

    base_sec = None
    for d in sizes:
        sec = time_dp(d, global_batch)
        if base_sec is None:
            base_sec = sec
        rows.append(
            Row(f"dp_speedup_{d}dev", round(base_sec / sec, 3), "x vs 1dev",
                None, f"(MPI 2c: 1.53x, 4c: 1.02x — Table 2)").finish()
        )

    # Weak scaling: per-device batch FIXED (work grows with devices), the
    # regime DP actually targets — efficiency = throughput per device
    # relative to 1 device (Tables 2-3 report only strong scaling).
    per_dev = 256
    base_ips = None
    for d in sizes:
        gb = per_dev * d
        ips = gb / time_dp(d, gb)
        if base_ips is None:
            base_ips = ips
        rows.append(
            Row(f"dp_weak_efficiency_{d}dev",
                round(ips / (base_ips * d), 3), "throughput/dev vs 1dev",
                None, f"{round(ips, 0)} img/s total").finish()
        )
    return rows


def bench_comm(quick: bool) -> List[Row]:
    """Gradient-collective ablation on the zoo accum×mesh leg: the SAME
    explicit shard_map train step (cifar_cnn, accum_steps=2, all devices
    on the data axis) with only the comm algorithm varied —

      psum       monolithic lax.psum (XLA picks the algorithm),
      ring       bucketed ring reduce-scatter/all-gather with microbatch
                 comm/compute overlap (parallel/collectives.py),
      ring_bf16  ring + bf16-on-the-wire (half the ICI payload bytes).

    Because every variant shares one step body, the per-impl img/s rows
    isolate the collective schedule; the baseline_src column carries each
    variant's final-step loss delta vs psum, so the table double-checks
    the ≤1e-5 (ring) / ≤1e-2 (bf16) parity contract while it measures.

    Two further legs on the same model/batch:

    - Hierarchical: the device set re-folded into an emulated 2-host
      (host, device) mesh; `hier` / `hier_bf16` run the two-level rings
      (intra-host RS → host-axis shard exchange → all-gathers) against a
      `psum_hier` reference ON THE SAME MESH — BatchNorm batch stats are
      shard-local, so parity is only meaningful within one mesh shape.
    - ZeRO: the fused update-on-arrival step with replicated state
      (ZeRO-2, `zero2_ring`) vs resident 1/n shards + just-in-time f32
      param gathers at the step head (ZeRO-3, `zero3_ring`); the zero3
      row's baseline_src carries its throughput ratio vs zero2 — the
      memory-for-bandwidth trade's cost, which docs/collectives.md
      budgets at ≥0.9x.

    Final leg — the async straggler ablation (ASYNC_GATE, the playbook
    `async` mode's contract line): the virtual-clock harness
    (train/async_dp.py) runs sync ring vs bounded-staleness (S=2) vs
    EASGD on lenet, clean and under chaos `slow-worker@2:400`, and the
    gate demands BOTH directions — the async modes hold >= 0.8x their
    clean virtual throughput under the straggler while the sync ring is
    asserted to degrade below it (anti-vacuity), with the 3-step loss
    delta vs sync <= 1e-2 (stale clean+chaos, easgd clean) and the
    staleness ledger never exceeding S.  Virtual time is deterministic,
    so this leg is exact on CPU.

    On the 8-virtual-device CPU harness the "ICI" is shared-memory copies
    — ranking is indicative, the TPU run is the real evidence."""
    from parallel_cnn_tpu.config import CommConfig, FusedStepConfig, MeshConfig
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.train import zoo
    from parallel_cnn_tpu.parallel import mesh as mesh_lib

    n_dev = len(jax.devices())
    if n_dev < 2:
        return []
    mesh = mesh_lib.make_mesh(MeshConfig(data=n_dev, model=1))
    batch = (32 if quick else 64) * n_dev
    imgs, labels = synthetic.make_image_dataset(batch, seed=3)
    x, y = mesh_lib.shard_batch(mesh, (jnp.asarray(imgs), jnp.asarray(labels)))
    model = cifar.cifar_cnn()
    opt = zoo.make_optimizer(0.05)

    variants = [
        ("psum", CommConfig(impl="psum")),
        ("ring", CommConfig(impl="ring")),
        ("ring_bf16", CommConfig(impl="ring", wire_dtype="bfloat16")),
    ]
    rows: List[Row] = []
    losses = {}
    for name, comm in variants:
        st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
        step = zoo.make_train_step(
            model, opt, accum_steps=2, mesh=mesh, comm=comm
        )
        # Parity probe: 3 steps from identical init, BEFORE the timed
        # region mutates state (the timed thunk chains its own states).
        pst, ploss = st, None
        for _ in range(3):
            pst, ploss = step(pst, x, y)
        losses[name] = float(ploss)

        def thunk(carry, step=step, x=x, y=y):
            # step donates its state arg, so a captured init state would
            # be deleted after the first call — rebuild on each restart
            # (thunk(None) runs before _sync_time's timed region).
            s = carry[0] if carry is not None else zoo.init_state(
                model, jax.random.key(0), cifar.IN_SHAPE, opt
            )
            return step(s, x, y)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=10 if quick else 30, images_per_call=batch
        )
        dloss = losses[name] - losses["psum"]
        rows.append(
            Row(f"comm_{name}_accum_mesh_train", ips, "images/sec",
                baseline=None,
                baseline_src=(f"{n_dev}dev b{batch} accum2; "
                              f"loss-psum={dloss:+.2e}"),
                value_range=ips_range, value_samples=n_s).finish()
        )

    # --- Hierarchical leg: same devices re-folded as 2 emulated hosts ---
    if n_dev >= 4 and n_dev % 2 == 0:
        hmesh = mesh_lib.make_hier_mesh(n_hosts=2)
        hx, hy = mesh_lib.shard_batch(
            hmesh, (jnp.asarray(imgs), jnp.asarray(labels))
        )
        hier_variants = [
            ("psum_hier", CommConfig(impl="psum")),
            ("hier", CommConfig(impl="hierarchical", hosts=2)),
            ("hier_bf16",
             CommConfig(impl="hierarchical", wire_dtype="bfloat16", hosts=2)),
        ]
        for name, comm in hier_variants:
            st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
            step = zoo.make_train_step(
                model, opt, accum_steps=2, mesh=hmesh, comm=comm
            )
            pst, ploss = st, None
            for _ in range(3):
                pst, ploss = step(pst, hx, hy)
            losses[name] = float(ploss)

            def thunk(carry, step=step, hx=hx, hy=hy):
                s = carry[0] if carry is not None else zoo.init_state(
                    model, jax.random.key(0), cifar.IN_SHAPE, opt
                )
                return step(s, hx, hy)

            ips, ips_range, n_s = _sampled_ips(
                thunk, repeats=10 if quick else 30, images_per_call=batch
            )
            dloss = losses[name] - losses["psum_hier"]
            rows.append(
                Row(f"comm_{name}_accum_mesh_train", ips, "images/sec",
                    baseline=None,
                    baseline_src=(f"2host x{n_dev // 2}dev b{batch} accum2; "
                                  f"loss-psum_hier={dloss:+.2e}"),
                    value_range=ips_range, value_samples=n_s).finish()
            )

    # --- ZeRO leg: replicated fused step (ZeRO-2) vs resident shards with
    # just-in-time f32 param gathers (ZeRO-3), same ring comm/batch/lr ---
    zcomm = CommConfig(impl="ring")
    zero_ips = {}
    zero_losses = {}
    for name, zero in (("zero2_ring", 2), ("zero3_ring", 3)):
        if zero == 2:
            fused = FusedStepConfig(update=True, tail=True)
            st0, n_buckets = zoo.init_fused_state(
                model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                fused=fused, bucket_bytes=zcomm.bucket_bytes,
            )
            step = zoo.make_fused_train_step(
                model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh,
                augment=None, comm=zcomm, fused=fused, n_buckets=n_buckets,
            )

            def init_st():
                return zoo.init_fused_state(
                    model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                    fused=FusedStepConfig(update=True, tail=True),
                    bucket_bytes=zcomm.bucket_bytes,
                )[0]

        else:
            fused = FusedStepConfig(update=True, tail=True, zero=3)
            st0, plan = zoo.init_zero3_state(
                model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                fused=fused, bucket_bytes=zcomm.bucket_bytes,
            )
            step = zoo.make_zero3_train_step(
                model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh,
                augment=None, comm=zcomm, fused=fused, plan=plan,
            )

            def init_st(fused=fused):
                return zoo.init_zero3_state(
                    model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                    fused=fused, bucket_bytes=zcomm.bucket_bytes,
                )[0]

        pst, ploss = st0, None
        for _ in range(3):
            pst, ploss = step(pst, x, y)
        zero_losses[name] = float(ploss)

        def thunk(carry, step=step, init_st=init_st):
            s = carry[0] if carry is not None else init_st()
            return step(s, x, y)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=10 if quick else 30, images_per_call=batch
        )
        zero_ips[name] = ips
        if zero == 2:
            src = f"{n_dev}dev b{batch} accum2 fused"
        else:
            dloss = zero_losses[name] - zero_losses["zero2_ring"]
            ratio = ips / zero_ips["zero2_ring"]
            src = (f"{n_dev}dev b{batch} accum2 fused; "
                   f"loss-zero2={dloss:+.2e}; ips/zero2={ratio:.3f}x")
        rows.append(
            Row(f"comm_{name}_accum_mesh_train", ips, "images/sec",
                baseline=None, baseline_src=src,
                value_range=ips_range, value_samples=n_s).finish()
        )

    rows.extend(_bench_async_ablation())
    return rows


def _bench_async_ablation() -> List[Row]:
    """Sync ring vs stale-S vs EASGD under a seeded 400 ms straggler —
    the virtual-clock leg behind the ASYNC_GATE contract line (see the
    bench_comm docstring for the gate terms)."""
    import numpy as np

    from parallel_cnn_tpu.config import AsyncConfig
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
    from parallel_cnn_tpu.train import async_dp

    W, b, dt, step_ms, horizon = 4, 8, 0.05, 100.0, 1600.0
    params = lenet_ref.init(jax.random.key(7))
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.uniform(0, 1, (W, b, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (W, b)).astype(np.int32))
    ex, ey = xs.reshape(W * b, 28, 28), ys.reshape(W * b)

    modes = {
        "sync_ring": AsyncConfig(mode="off", workers=W),
        "stale2": AsyncConfig(mode="stale", staleness_bound=2, workers=W),
        "easgd": AsyncConfig(mode="easgd", easgd_period=4, easgd_rho=0.5,
                             workers=W),
    }
    rows: List[Row] = []
    ratios = {}
    max_stale = 0
    for name, acfg in modes.items():
        clean = async_dp.run_async(
            params, xs, ys, cfg=acfg, dt=dt, step_ms=step_ms,
            horizon_ms=horizon,
        )
        chaos = async_dp.run_async(
            params, xs, ys, cfg=acfg, dt=dt, step_ms=step_ms,
            horizon_ms=horizon, chaos=ChaosMonkey.from_spec("slow-worker@2:400"),
        )
        ratios[name] = chaos.throughput() / clean.throughput()
        max_stale = max(max_stale, clean.ledger.max_staleness(),
                        chaos.ledger.max_staleness())
        # Virtual img/s: microbatches × b per virtual second — exact and
        # deterministic (no wall clock anywhere in the harness).
        rows.append(
            Row(f"async_{name}_virtual", round(
                clean.throughput() * b * 1000.0, 1), "images/virtual-sec",
                baseline=None,
                baseline_src=(
                    f"{W} workers b{b} S=2 horizon {horizon:.0f}ms; "
                    f"under slow-worker@2:400: {ratios[name]:.3f}x clean"
                )).finish()
        )

    # Seeded 3-step loss deltas vs the sync ring.  EASGD-under-chaos is
    # NOT gated at 1e-2: the straggler reorders the elastic rounds, which
    # genuinely changes the center trajectory (docs/fault_tolerance.md's
    # "not preserved" list) — it is reported and sanity-bounded instead.
    sync3 = async_dp.run_async(
        params, xs, ys, cfg=modes["sync_ring"], dt=dt, step_ms=step_ms,
        max_server_steps=3,
    )
    loss_sync = float(async_dp.eval_err(sync3.params, ex, ey))
    deltas = {}
    loss_cfgs = {
        "stale_clean": (modes["stale2"], None),
        "stale_chaos": (modes["stale2"], "slow-worker@2:400"),
        "easgd_clean": (AsyncConfig(mode="easgd", easgd_period=1,
                                    easgd_rho=0.9, workers=W), None),
        "easgd_chaos": (AsyncConfig(mode="easgd", easgd_period=1,
                                    easgd_rho=0.9, workers=W),
                        "slow-worker@2:400"),
    }
    for name, (acfg, spec) in loss_cfgs.items():
        r = async_dp.run_async(
            params, xs, ys, cfg=acfg, dt=dt, step_ms=step_ms,
            max_server_steps=3,
            chaos=ChaosMonkey.from_spec(spec) if spec else None,
        )
        deltas[name] = abs(loss_sync - float(async_dp.eval_err(
            r.params, ex, ey)))
        rows.append(
            Row(f"async_loss_delta_{name}", round(deltas[name], 6),
                "|loss - sync| after 3 steps",
                baseline=None,
                baseline_src=("gate <= 1e-2" if name != "easgd_chaos"
                              else "reported; sanity bound 1e-1")).finish()
        )

    gate_ok = (
        ratios["stale2"] >= 0.8
        and ratios["easgd"] >= 0.8
        and ratios["sync_ring"] < 0.8      # anti-vacuity: sync DID stall
        and deltas["stale_clean"] <= 1e-2
        and deltas["stale_chaos"] <= 1e-2
        and deltas["easgd_clean"] <= 1e-2
        and deltas["easgd_chaos"] <= 1e-1
        and max_stale <= 2
    )
    if not gate_ok:
        rows.append(Row(
            "error_async_gate", -1.0, "error",
            baseline_src=(
                f"ratios sync {ratios['sync_ring']:.3f} (< 0.8 wanted), "
                f"stale {ratios['stale2']:.3f}, easgd {ratios['easgd']:.3f} "
                f"(>= 0.8 wanted); deltas {deltas}; max staleness "
                f"{max_stale} (<= 2)"
            ),
        ))
    print(
        f"ASYNC_GATE {'PASS' if gate_ok else 'FAIL'}: straggler ratios "
        f"sync {ratios['sync_ring']:.3f} < 0.8 <= stale "
        f"{ratios['stale2']:.3f} / easgd {ratios['easgd']:.3f}, 3-step "
        f"|dloss| stale {deltas['stale_chaos']:.2e} easgd "
        f"{deltas['easgd_clean']:.2e} (<= 1e-2), max staleness "
        f"{max_stale} <= S=2",
        flush=True,
    )
    return rows


def bench_fused(quick: bool) -> List[Row]:
    """Fused-training-step ablation (round 7), two legs.

    LeNet leg (single-device): `batched_step` vs `fused_batched_step` —
    the same local_grad_sums engine with the tree-wide `p += dt·g` pass
    replaced by one ops.pallas_update kernel per gradient bucket; the
    fused row's baseline_src carries the final-err delta (f32 — the two
    are the same math).

    Zoo leg (accum×mesh, all devices on the data axis): the comm-suite
    step body with the fused pieces layered on —

      unfused      ring RS/AG + optax (the bench_comm "ring" variant),
      fused_tail   + the fused pool→FC→softmax-CE loss tail,
      fused_upd    + update-on-arrival: per-bucket fused SGD/momentum on
                   the reduce-scattered shards, param all-gather (f32),
                   no post-barrier optimizer pass,
      fused_bf16   + bf16 activations over f32 masters with dynamic loss
                   scaling.

    Every row's baseline_src carries its 3-step-loss delta vs unfused —
    the ≤1e-5 (f32) / ≤1e-2 (bf16) parity contract rides in the table,
    like --suite comm. On the CPU harness the tail runs its XLA twin
    (same math as the Mosaic kernel; tests pin the two ≤1e-5) and
    "ICI" is shared-memory copies — ranking is indicative, the TPU run
    is the real evidence."""
    from parallel_cnn_tpu.config import CommConfig, FusedStepConfig, MeshConfig
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.train import step as step_lib, zoo
    from parallel_cnn_tpu.parallel import mesh as mesh_lib

    rows: List[Row] = []

    # --- LeNet leg: fused bucket update on the reference grad engine ---
    from parallel_cnn_tpu.models import lenet_ref

    lb = 256 if quick else 512
    limgs, llabels = synthetic.make_dataset(lb, seed=4)
    lx, ly = jnp.asarray(limgs), jnp.asarray(llabels)
    lerrs = {}
    for name, fused in (("unfused", False), ("fused", True)):
        lstep = step_lib.batched_step_fn("reference", fused=fused)
        p, err = lenet_ref.init(jax.random.key(0)), None
        for _ in range(3):
            p, err = lstep(p, lx, ly, 0.01)
        lerrs[name] = float(err)

        def lthunk(carry, lstep=lstep):
            p = carry[0] if carry is not None else lenet_ref.init(
                jax.random.key(0)
            )
            return lstep(p, lx, ly, 0.01)

        ips, ips_range, n_s = _sampled_ips(
            lthunk, repeats=10 if quick else 30, images_per_call=lb
        )
        derr = lerrs[name] - lerrs["unfused"]
        rows.append(
            Row(f"fused_lenet_{name}_batched_step", ips, "images/sec",
                baseline=None,
                baseline_src=f"b{lb} dt.01; err-unfused={derr:+.2e}",
                value_range=ips_range, value_samples=n_s).finish()
        )

    # --- Zoo leg: tail / update-on-arrival / bf16 on the mesh ---
    n_dev = len(jax.devices())
    if n_dev < 2:
        return rows
    mesh = mesh_lib.make_mesh(MeshConfig(data=n_dev, model=1))
    batch = (32 if quick else 64) * n_dev
    imgs, labels = synthetic.make_image_dataset(batch, seed=3)
    x, y = mesh_lib.shard_batch(mesh, (jnp.asarray(imgs), jnp.asarray(labels)))
    model = cifar.cifar_cnn()
    comm = CommConfig(impl="ring")
    # Gentle lr: the in-row parity probe is a numerics contract, checked
    # in a numerically sane regime (the dryrun comm leg's rationale — at
    # aggressive lr the 3-step loss inflates and bf16 activation roundoff
    # rides past the documented 1e-2 bound; observed 1.34e-2 at lr=0.05).
    # Throughput is lr-independent, so the timed rows lose nothing.
    lr, momentum = 0.01, 0.9

    variants = [
        ("unfused", None),
        ("fused_tail",
         FusedStepConfig(update=False, tail=True, act_dtype="float32")),
        ("fused_upd",
         FusedStepConfig(update=True, tail=True, act_dtype="float32")),
        ("fused_bf16",
         FusedStepConfig(update=True, tail=True, act_dtype="bfloat16")),
    ]
    losses = {}
    for name, fused in variants:
        if fused is not None and fused.update:
            st0, n_buckets = zoo.init_fused_state(
                model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                fused=fused, bucket_bytes=comm.bucket_bytes,
            )
            step = zoo.make_fused_train_step(
                model, lr=lr, momentum=momentum, accum_steps=2, mesh=mesh,
                augment=None, comm=comm, fused=fused, n_buckets=n_buckets,
            )

            def init_st(fused=fused):
                return zoo.init_fused_state(
                    model, jax.random.key(0), cifar.IN_SHAPE, n_data=n_dev,
                    fused=fused, bucket_bytes=comm.bucket_bytes,
                )[0]

        else:
            opt = zoo.make_optimizer(lr, momentum=momentum)
            st0 = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE,
                                 opt)
            step = zoo.make_train_step(
                model, opt, accum_steps=2, mesh=mesh, comm=comm, fused=fused
            )

            def init_st(opt=opt):
                return zoo.init_state(
                    model, jax.random.key(0), cifar.IN_SHAPE, opt
                )

        # Parity probe: 3 steps from identical init, BEFORE the timed
        # region mutates state (same discipline as bench_comm).
        pst, ploss = st0, None
        for _ in range(3):
            pst, ploss = step(pst, x, y)
        losses[name] = float(ploss)

        def thunk(carry, step=step, init_st=init_st):
            s = carry[0] if carry is not None else init_st()
            return step(s, x, y)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=10 if quick else 30, images_per_call=batch
        )
        dloss = losses[name] - losses["unfused"]
        rows.append(
            Row(f"fused_zoo_{name}_accum_mesh_train", ips, "images/sec",
                baseline=None,
                baseline_src=(f"{n_dev}dev b{batch} accum2; "
                              f"loss-unfused={dloss:+.2e}"),
                value_range=ips_range, value_samples=n_s).finish()
        )
    return rows


def bench_northstar(quick: bool) -> List[Row]:
    """BASELINE.json's north-star metric: epochs-to-98% test accuracy for
    the MNIST LeNet (throughput mode, shuffled minibatch SGD), plus the
    final accuracy. Runs on real MNIST when the idx image files exist;
    the reference snapshot ships labels only (SURVEY.md B15), so the
    deterministic synthetic stand-in is the default — the row name says
    which. (No published reference value exists; accuracy was never
    reported numerically, BASELINE.md.)"""
    from parallel_cnn_tpu.config import Config, DataConfig, TrainConfig
    from parallel_cnn_tpu.data import pipeline
    from parallel_cnn_tpu.train import trainer

    n_train, n_test = (10_000, 2_000) if quick else (60_000, 10_000)
    data_cfg = DataConfig(
        synthetic_train_count=n_train, synthetic_test_count=n_test
    )
    train_ds, test_ds = pipeline.load_train_test(data_cfg)
    # The pipeline tags (and integrity-logs) real idx files; rows label
    # themselves from that tag, so dropping the four files in data/ turns
    # this suite into the real-MNIST evidence automatically (README recipe).
    # BOTH splits must be real: a partial drop (train real, test fallback
    # synthetic) must never label synthetic-test accuracy as mnist evidence.
    both_real = train_ds.source == "mnist" and test_ds.source == "mnist"
    tag = "mnist" if both_real else "synthetic_mnist"
    # synthetic_* counts don't bound real idx files — cap explicitly so
    # --quick stays quick when the full dataset is present.
    train_ds = pipeline.Dataset(
        train_ds.images[:n_train], train_ds.labels[:n_train], train_ds.source
    )
    test_ds = pipeline.Dataset(
        test_ds.images[:n_test], test_ds.labels[:n_test], test_ds.source
    )

    # Two trajectories: strict parity (the reference's per-sample SGD —
    # "parity with Sequential baseline loss curve") and throughput mode
    # (minibatch; dt re-tuned to 0.4 — mean-grads at the per-sample dt=0.1
    # undertrain 32×, dt≥0.8 saturates the sigmoids to chance; full sweep
    # table in docs/dt_sweep.md).
    modes = [
        ("parity", TrainConfig(epochs=1, batch_size=1), 4),
        ("batched", TrainConfig(epochs=1, batch_size=32, dt=0.4,
                                shuffle=True, prefetch="off"), 10),
    ]
    rows = []
    for mode, tc0, max_epochs in modes:
        params = None
        epochs_to_98 = None
        acc = 0.0
        t0 = time.perf_counter()
        for epoch in range(1, max_epochs + 1):
            cfg = Config(data=data_cfg, train=tc0)
            res = trainer.learn(cfg, train_ds, params=params, verbose=False,
                                epoch_offset=epoch - 1)
            params = res.params
            acc = 100.0 - trainer.test(params, test_ds, verbose=False)
            if acc >= 98.0:
                epochs_to_98 = epoch
                break
        wall = time.perf_counter() - t0
        rows.append(
            Row(f"northstar_epochs_to_98pct_{mode}_{tag}",
                float(epochs_to_98 if epochs_to_98 is not None else -1),
                "epochs", None,
                f"acc {acc:.2f}% after {wall:.1f}s "
                "(reference never reports accuracy)").finish()
        )
        rows.append(
            Row(f"northstar_final_accuracy_{mode}_{tag}", round(acc, 2),
                "%", None, "98% target (BASELINE.json)").finish()
        )
    return rows


def bench_zoo(quick: bool) -> List[Row]:
    """Model-zoo step throughput (BASELINE.json configs #3-#5 + round-4
    additions): CIFAR CNN, ResNet-18 and VGG-16 (XLA convs and the
    Pallas conv-kernel backend), and ResNet-50 at ImageNet shape with
    gradient accumulation — on TPU also with every conv (incl. the
    7×7-s2 stem) on the Pallas kernels."""
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar, resnet, vgg
    from parallel_cnn_tpu.train import zoo

    rows = []
    batch = 256 if quick else 512
    imgs, labels = synthetic.make_image_dataset(batch, seed=1)
    x, y = jnp.asarray(imgs), jnp.asarray(labels)
    # Per-case timed repeats: scale inversely with step cost so cheap rows
    # amortize the readback RTT (cifar_cnn ~6 ms/step needs many
    # chained steps; ResNet-50 @224² ~0.5 s/step needs few).
    cases = [
        ("cifar_cnn", cifar.cifar_cnn(), cifar.IN_SHAPE, x, y, 1, 50),
        ("resnet18_cifar", resnet.resnet18(10, cifar_stem=True),
         cifar.IN_SHAPE, x, y, 1, 20),
        ("vgg16_cifar", vgg.vgg16(10), cifar.IN_SHAPE, x, y, 1, 10),
    ]
    from parallel_cnn_tpu.utils.backend import canonical_platform

    if canonical_platform() == "tpu" or os.environ.get("PCNN_BENCH_PALLAS"):
        # Compiled Mosaic only: interpret mode at bench batch sizes is
        # minutes/step on CPU (correctness covered by tests/test_pallas_conv).
        cases.append(
            ("resnet18_cifar_pallasconv",
             resnet.resnet18(10, cifar_stem=True, conv_backend="pallas"),
             cifar.IN_SHAPE, x, y, 1, 10)
        )
        cases.append(
            ("vgg16_cifar_pallasconv",
             vgg.vgg16(10, conv_backend="pallas"),
             cifar.IN_SHAPE, x, y, 1, 10)
        )
    # Config #5: ResNet-50 at ImageNet shape (synthetic stand-in — no
    # egress, BASELINE.md), microbatched via grad accumulation so the
    # effective batch exceeds single-chip activation memory. --quick
    # shrinks the spatial dims (224² ResNet-50 is minutes/step on the CPU
    # harness); the full run is the ImageNet-shape number.
    # b256×accum16 (microbatch 16) is the measured-optimal operating
    # point on one v5e: throughput saturates there at ~2450 img/s ≈ 30.8%
    # MFU while b64 leaves ~1.7× of per-step fixed-cost amortization on
    # the table (docs/resnet50_ablate_r6.md, MFU-corrected ablation grid).
    in50 = (64, 64, 3) if quick else (224, 224, 3)
    b50 = 16 if quick else 256
    imgs50, labels50 = synthetic.make_image_dataset(
        b50, hw=in50[:2], classes=100, seed=2
    )
    x50, y50 = jnp.asarray(imgs50), jnp.asarray(labels50)
    cases.append(
        ("resnet50_imagenet_accum16" if not quick else
         "resnet50_imagenet_accum4",
         resnet.resnet50(100, cifar_stem=False),
         in50, x50, y50, 4 if quick else 16, 5)
    )
    if canonical_platform() == "tpu":
        # Round 4: every ResNet-50 conv — 7×7-s2 stem included — on the
        # hand-written kernels ("entire network" at the reference's own
        # framing, PDF Table 8). TPU-only: ~60 Mosaic compiles. Measured
        # at 64×64 input, NOT 224²: the 224² stem kernel alone sat in
        # the remote Mosaic compiler >25 min without finishing (r5,
        # docs/bench_results.md) — a compile-time pathology, not a
        # run-time one — so the full-shape row would eat the suite
        # timeout. The row label carries the shape. Reuse the quick-mode
        # dataset when it already is the 64px one.
        if quick:
            x50p, y50p = x50, y50
        else:
            imgs50p, labels50p = synthetic.make_image_dataset(
                16, hw=(64, 64), classes=100, seed=2
            )
            x50p, y50p = jnp.asarray(imgs50p), jnp.asarray(labels50p)
        cases.append(
            ("resnet50_64px_accum4_pallasconv",
             resnet.resnet50(100, cifar_stem=False, conv_backend="pallas"),
             (64, 64, 3), x50p, y50p, 4, 3)
        )
    for name, model, in_shape, bx, by, accum, reps in cases:
        bsz = bx.shape[0]
        opt = zoo.make_optimizer(0.05)
        st = zoo.init_state(model, jax.random.key(0), in_shape, opt)
        step = zoo.make_train_step(model, opt, accum_steps=accum)

        def thunk(carry, step=step, st=st, bx=bx, by=by):
            s = carry[0] if carry is not None else st
            return step(s, bx, by)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=2 if quick else reps, images_per_call=bsz
        )
        rows.append(
            Row(f"zoo_{name}_train", round(ips, 1), "images/sec",
                value_range=ips_range, value_samples=n_s).finish()
        )
    return rows


def bench_serve(quick: bool) -> List[Row]:
    """Inference-serving ablation (serve/): the SAME engine + weights
    under three serving disciplines —

      batch1      sequential predict(x[None]) per request — the no-
                  batching strawman every serving system is measured
                  against,
      dynamic     one replica behind the dynamic batcher, closed-loop
                  clients (batching emerges from concurrency),
      2replicas   dynamic batching + a second engine replica (only when
                  the platform exposes ≥2 devices; on the 8-virtual-CPU
                  harness the replicas share silicon, so the row shows
                  pipeline overlap, not 2× silicon).

    Throughput rows are wall-clock request rates (host queueing included
    — that IS the serving number, unlike the chained-dispatch training
    rows), median of N with range. Each dynamic row carries client p50/
    p99 and the shed rate in the baseline_src column; at this sub-
    capacity offered load the shed rate must be 0. The parity row
    re-proves the padding contract in-suite: a padded-bucket engine
    prediction must be bit-identical to the same-bucket jit forward."""
    from parallel_cnn_tpu.config import ServeConfig
    from parallel_cnn_tpu.serve import get, loadgen, serve_stack

    handle = get("cifar_cnn")
    max_batch = 8 if quick else 16
    n_req = 96 if quick else 256
    cfg0 = ServeConfig(model="cifar_cnn", max_batch=max_batch,
                       max_wait_ms=2.0, queue_depth=max(n_req, 256))
    samples = loadgen.make_samples(64, handle.in_shape, seed=0)
    rows: List[Row] = []

    # -- parity row first: no point timing a wrong answer ---------------
    pool, batcher = serve_stack(handle, cfg0, start=False)
    e0 = pool.engines[0]
    n, b = 3, 4
    got = e0.predict(samples[:n])
    padded = np.concatenate(
        [samples[:n], np.zeros((b - n, *handle.in_shape), np.float32)]
    )
    ref = np.asarray(jax.jit(
        lambda v: handle.forward(e0._params, e0._state, v)
    )(jnp.asarray(padded)))[:n]
    if not np.array_equal(got, ref):
        raise RuntimeError(
            "serve parity violated: padded-bucket engine prediction is not "
            f"bit-identical to the same-bucket jit forward "
            f"(max |d| {float(np.max(np.abs(got - ref))):.2e})"
        )
    rows.append(
        Row("serve_parity_padded_bucket", 1.0, "bitwise-equal",
            baseline_src=f"n={n} padded into bucket {b}, cifar_cnn").finish()
    )
    batcher.close()

    def timed(run_once) -> tuple:
        """Median-of-N wall-clock req/s (+ the last run's report)."""
        rps, last = [], None
        for _ in range(_n_samples()):
            t0 = time.perf_counter()
            last = run_once()
            rps.append(round(n_req / (time.perf_counter() - t0), 1))
        return _median(rps), [min(rps), max(rps)], len(rps), last

    # -- batch=1 sequential strawman ------------------------------------
    e0.predict(samples[:1])  # warm bucket 1

    def run_batch1():
        for i in range(n_req):
            e0.predict(samples[i % len(samples)][None])
        return None

    v, rng_, n_s, _ = timed(run_batch1)
    rows.append(
        Row("serve_batch1_sequential", v, "req/sec",
            baseline_src="no batching: one predict per request",
            value_range=rng_, value_samples=n_s).finish()
    )
    batch1_rps = v

    # -- dynamic batching (1 replica, then 2 if the platform has them) --
    n_dev = len(jax.devices())
    variants = [("serve_dynamic_batch", 1)]
    if n_dev >= 2:
        variants.append(("serve_dynamic_2replicas", 2))
    else:
        print("[bench_serve] 2-replica row skipped (1 device visible; "
              "run under the 8-virtual-device CPU harness or on a multi-"
              "chip platform)", flush=True)
    for name, n_rep in variants:
        cfg = ServeConfig(model="cifar_cnn", max_batch=max_batch,
                          max_wait_ms=2.0, queue_depth=max(n_req, 256),
                          n_replicas=n_rep)
        pool, batcher = serve_stack(handle, cfg)
        try:
            def run_closed(batcher=batcher):
                return loadgen.run(
                    batcher, pattern="closed", n_requests=n_req,
                    concurrency=16, samples=samples, seed=0,
                )

            v, rng_, n_s, rep = timed(run_closed)
            lat = rep.latency.summary(scale=1e3)
            rows.append(
                Row(name, v, "req/sec",
                    baseline=batch1_rps, baseline_src=(
                        f"vs batch1; p50 {lat['p50']:.1f} ms, "
                        f"p99 {lat['p99']:.1f} ms, "
                        f"shed {rep.shed_rate:.3f}, "
                        f"occupancy {batcher.stats.mean_occupancy():.2f}"
                    ),
                    value_range=rng_, value_samples=n_s).finish()
            )
            if rep.shed_rate != 0.0:
                raise RuntimeError(
                    f"{name}: shed rate {rep.shed_rate:.3f} at sub-capacity "
                    "offered load (closed loop must never shed with "
                    "queue_depth >= n_requests)"
                )
        finally:
            batcher.close()

    rows.extend(_bench_serve_slo(quick))
    return rows


def _bench_serve_slo(quick: bool) -> List[Row]:
    """The SLO scenario sweep behind the SERVE_SLO_GATE contract line.

    Five seeded scenarios (serve/scenarios.py) against a lenet_ref
    stack with admission control on, judged by their explicit p99 /
    shed-rate / conservation gates:

      clean legs    diurnal, flash-crowd, slow-client, chaos-kill must
                    PASS their gates,
      trip leg      chaos-slow arms slow-replica@3:400 against a 150 ms
                    p99 gate — the leg passes iff the gate FAILS (the
                    anti-vacuity proof that a tripped SLO is visible),
      autoscaler    flash-crowd on a 1→2-replica pool under the control
                    loop: unrecovered shed rate must land at 0 with at
                    most one scale direction change (no flapping).

    Every leg re-checks the conservation law server-side. Any violated
    expectation appends an error row (rc 1) and flips the gate line to
    SERVE_SLO_GATE FAIL — the serve-chaos playbook mode greps for it."""
    del quick  # scenarios are fixed-duration; quick and full match
    from parallel_cnn_tpu.config import ServeConfig
    from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
    from parallel_cnn_tpu.serve import AutoScaler, get, scenarios, serve_stack

    handle = get("lenet_ref")

    def cfg(**kw):
        base = dict(model="lenet_ref", max_batch=8, max_wait_ms=2.0,
                    queue_depth=256, admission=True, slo_ms=200.0,
                    window_s=2.0)
        base.update(kw)
        return ServeConfig(**base)

    rows: List[Row] = []
    failures: List[str] = []

    def judge(leg: str, rep, want_pass: bool) -> None:
        p99 = rep.p99_ms
        rows.append(Row(
            f"serve_slo_{leg}", round(p99, 2) if p99 is not None else -1.0,
            "ms p99",
            baseline_src=(
                f"gate {rep.p99_gate_ms:.0f} ms, shed {rep.shed_rate:.3f} "
                f"(gate {rep.shed_gate:.2f}), "
                f"{'expected-trip' if not want_pass else 'clean'}, "
                f"gates {rep.gates()}"
            ),
        ).finish())
        if not rep.gates()["conservation"]:
            failures.append(f"{leg}: conservation violated {rep.server}")
        elif want_pass and not rep.passed:
            failures.append(f"{leg}: gates {rep.gates()}")
        elif not want_pass and rep.gates()["p99"]:
            failures.append(
                f"{leg}: p99 gate PASSED under an armed slow-replica "
                "stall — the gate is vacuous"
            )

    # -- clean legs ------------------------------------------------------
    pool, batcher = serve_stack(handle, cfg())
    try:
        judge("diurnal", scenarios.run("diurnal", batcher, seed=0), True)
        judge("flash_crowd",
              scenarios.run("flash-crowd", batcher, seed=1), True)
        judge("slow_client",
              scenarios.run("slow-client", batcher, seed=2), True)
    finally:
        batcher.close()

    # -- chaos legs (fresh stacks: one-shot faults, clean counters) ------
    n_rep = 2 if len(jax.devices()) >= 2 else 1
    pool, batcher = serve_stack(
        handle, cfg(n_replicas=n_rep, max_wait_ms=1.0),
        chaos=ChaosMonkey.from_spec("kill-replica@5"),
    )
    try:
        judge("chaos_kill", scenarios.run("chaos-kill", batcher, seed=3),
              True)
    finally:
        batcher.close()

    pool, batcher = serve_stack(
        handle, cfg(max_wait_ms=1.0),
        chaos=ChaosMonkey.from_spec("slow-replica@3:400"),
    )
    try:
        judge("chaos_slow_trip",
              scenarios.run("chaos-slow", batcher, seed=2), False)
        if not batcher.chaos.slow_replica_fired:
            failures.append("chaos_slow_trip: the stall never injected")
    finally:
        batcher.close()

    # -- autoscaler recovery: flash-crowd must end with 0 unrecovered ----
    # A CPU-fast stack absorbs the crowd without ever needing a second
    # replica, which would leave the scale-up path untested — so a
    # slow-replica stall is armed to push the windowed p99 over the SLO
    # deterministically: the loop MUST scale up, and the crowd must
    # still end with zero unrecovered demand and no flapping. The queue
    # is deep enough to hold the whole crowd through the stall (and
    # admission is off), so the backlog waits instead of shedding —
    # recovery is the second replica draining it.
    pool, batcher = serve_stack(
        handle, cfg(window_s=1.0, admission=False, queue_depth=2048),
        chaos=ChaosMonkey.from_spec("slow-replica@3:400"),
    )
    scaler = AutoScaler(pool, batcher, min_replicas=1, max_replicas=2,
                        slo_ms=200.0, hysteresis=2, cooldown_s=1.0,
                        interval_s=0.05)
    try:
        with scaler:
            rep = scenarios.run("flash-crowd", batcher, seed=7)
        flaps = scaler.direction_changes()
        snap = scaler.snapshot()
        rows.append(Row(
            "serve_slo_autoscaler_flash_crowd",
            round(rep.shed_rate, 4), "unrecovered shed rate",
            baseline_src=(
                f"scale_ups {snap['scale_ups']}, "
                f"scale_downs {snap['scale_downs']}, "
                f"direction changes {flaps} (<= 1), "
                f"routable {snap['routable']}"
            ),
        ).finish())
        if not rep.conservation_ok:
            failures.append(f"autoscaler: conservation {rep.server}")
        if rep.shed_rate != 0.0:
            failures.append(
                f"autoscaler: unrecovered shed rate {rep.shed_rate:.4f} "
                "after flash-crowd (scale-up did not recover demand)"
            )
        if snap["scale_ups"] < 1:
            failures.append(
                "autoscaler: no scale-up despite the armed straggler "
                "pushing windowed p99 over the SLO"
            )
        if flaps > 1:
            failures.append(f"autoscaler: {flaps} direction changes (flap)")
    finally:
        batcher.close()

    if failures:
        rows.append(Row(
            "error_serve_slo_gate", -1.0, "error",
            baseline_src="; ".join(failures),
        ))
    print(
        "SERVE_SLO_GATE "
        + ("PASS: 4 clean scenario legs, chaos-slow trip proven, "
           "autoscaler recovery flap-free"
           if not failures else "FAIL: " + "; ".join(failures)),
        flush=True,
    )
    return rows


def bench_net(quick: bool) -> List[Row]:
    """--suite net: the network front door behind SERVE_NET_GATE.

    Four measured rows plus the scenario sweep (serve/net.py,
    serve/supervisor.py — docs/serving.md "Network front door"):

      cold start      serve_stack seconds with the persistent AOT disk
                      cache empty vs populated; the warm start must
                      issue ZERO compiles (EngineStats-asserted — the
                      issue's acceptance line, not just a timing),
      wire overhead   closed-loop throughput over a loopback socket as
                      a fraction of the same batcher driven in-process,
      hot swap        seconds for the grow→drain→retire weight roll
                      under live socket traffic, failed_delta must be 0,
      scenarios       net-steady / net-slow-loris (must actually reap) /
                      net-kill-endpoint (supervised respawn, retries
                      ride through) judged by their gates, plus the
                      anti-vacuity control arm: the same kill with the
                      supervisor disabled must FAIL its gates.

    Any violated expectation appends an error row (rc 1) and flips the
    contract line to SERVE_NET_GATE FAIL — playbook.sh's net mode greps
    for it."""
    import tempfile

    from parallel_cnn_tpu.config import ServeConfig
    from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
    from parallel_cnn_tpu.resilience.retry import RetryPolicy
    from parallel_cnn_tpu.serve import (
        NetServer, Supervisor, WireStats, get, loadgen, scenarios,
        serve_stack,
    )
    from parallel_cnn_tpu.serve.engine import load_or_init

    handle = get("lenet_ref")

    def cfg(**kw):
        base = dict(model="lenet_ref", max_batch=8, max_wait_ms=2.0,
                    queue_depth=256)
        base.update(kw)
        return ServeConfig(**base)

    rows: List[Row] = []
    failures: List[str] = []

    # -- cold start: AOT disk cache cold vs warm -------------------------
    with tempfile.TemporaryDirectory(prefix="pcnn_aot_bench_") as cdir:
        t0 = time.perf_counter()
        pool, batcher = serve_stack(handle, cfg(), cache_dir=cdir)
        cold_s = time.perf_counter() - t0
        n_entries = sum(e.stats.aot_cache_misses for e in pool.engines)
        cold_compiles = sum(e.stats.aot_compiles for e in pool.engines)
        batcher.close()
        t0 = time.perf_counter()
        pool, batcher = serve_stack(handle, cfg(), cache_dir=cdir)
        warm_s = time.perf_counter() - t0
        warm_compiles = sum(e.stats.aot_compiles for e in pool.engines)
        warm_hits = sum(e.stats.aot_cache_hits for e in pool.engines)
        batcher.close()
    rows.append(Row(
        "net_cold_start_cache_cold", round(cold_s, 3), "sec",
        baseline_src=f"{cold_compiles} compiles, {n_entries} entries "
                     f"written",
    ).finish())
    rows.append(Row(
        "net_cold_start_cache_warm", round(warm_s, 3), "sec",
        baseline=round(cold_s, 3),
        baseline_src=f"cold start above; {warm_compiles} compiles, "
                     f"{warm_hits} disk hits",
    ).finish())
    if cold_compiles == 0 or n_entries == 0:
        failures.append("cold start issued no compiles / wrote no cache "
                        "entries (the cold leg is vacuous)")
    if warm_compiles != 0:
        failures.append(
            f"warm cold-start issued {warm_compiles} compiles "
            "(the acceptance line is ZERO: every bucket must "
            "deserialize from the disk tier)"
        )
    if warm_hits != n_entries:
        failures.append(
            f"warm start hit {warm_hits}/{n_entries} disk entries"
        )

    # -- one long-lived stack for the wire legs --------------------------
    pool, batcher = serve_stack(handle, cfg())
    try:
        samples = scenarios.make_samples(32, handle.in_shape, seed=0)
        n_req = 96 if quick else 256

        # In-process closed loop vs the identical loop over loopback.
        inproc = loadgen.run_closed_loop(
            batcher, samples, n_requests=n_req, concurrency=4, seed=0,
        )
        wire = WireStats()
        srv = NetServer(batcher, wire=wire, conn_deadline_ms=5000.0).start()
        try:
            netrep = loadgen.run_closed_loop_net(
                srv.address, samples, n_requests=n_req, concurrency=4,
                timeout_s=15.0, seed=0,
            )
        finally:
            srv.close()
        ratio = (netrep.throughput / inproc.throughput
                 if inproc.throughput > 0 else 0.0)
        rows.append(Row(
            "net_wire_throughput_ratio", round(ratio, 3),
            "x of in-process",
            baseline_src=(
                f"wire {netrep.throughput:.0f} req/s vs in-process "
                f"{inproc.throughput:.0f} req/s, {n_req} requests x 4 "
                f"clients, NDJSON over loopback"
            ),
        ).finish())
        if netrep.completed != n_req or inproc.completed != n_req:
            failures.append(
                f"throughput legs dropped requests (wire "
                f"{netrep.completed}/{n_req}, in-process "
                f"{inproc.completed}/{n_req})"
            )
        if not wire.balanced():
            failures.append(f"throughput leg wire ledger {wire.snapshot()}")

        # -- scenario legs ----------------------------------------------
        def judge(leg, rep, want_pass=True):
            p99 = rep.p99_ms
            rows.append(Row(
                f"net_{leg}", round(p99, 2) if p99 is not None else -1.0,
                "ms p99",
                baseline_src=(
                    f"{'expected-trip' if not want_pass else 'clean'}, "
                    f"gates {rep.gates()}, wire {rep.wire}"
                ),
            ).finish())
            if not rep.wire_ok:
                failures.append(f"{leg}: wire ledger broken {rep.wire}")
            elif want_pass and not rep.passed:
                failures.append(f"{leg}: gates {rep.gates()}")
            elif not want_pass and rep.passed:
                failures.append(
                    f"{leg}: PASSED with the supervisor disabled under an "
                    "armed kill-endpoint — the respawn gate is vacuous"
                )
            return rep

        # Clean steady state.
        wire = WireStats()
        srv = NetServer(batcher, wire=wire, conn_deadline_ms=5000.0).start()
        try:
            judge("steady", scenarios.run_net(
                "net-steady", batcher, wire=wire, server=srv, seed=0,
            ))
        finally:
            srv.close()

        # Slow loris: the stalled socket must be reaped as expired.
        wire = WireStats()
        srv = NetServer(batcher, wire=wire, conn_deadline_ms=150.0).start()
        try:
            rep = judge("slow_loris", scenarios.run_net(
                "net-slow-loris", batcher, wire=wire, server=srv,
                chaos=ChaosMonkey.from_spec("slow-loris@3:400"), seed=1,
            ))
            if rep.wire.get("reaped", 0) < 1:
                failures.append("slow_loris: the stall never reaped")
        finally:
            srv.close()

        # Supervised kill: retries ride through the respawn.
        wire = WireStats()
        armed = [ChaosMonkey.from_spec("kill-endpoint@12")]

        def factory(port, seq_start):
            m = armed.pop(0) if armed else None
            return NetServer(batcher, port=port, conn_deadline_ms=2000.0,
                             wire=wire, chaos=m, seq_start=seq_start,
                             ).start()

        sup = Supervisor(factory, policy=RetryPolicy(
            attempts=6, base_delay=0.02, max_delay=0.2, seed=0,
        )).start()
        try:
            rep = judge("kill_endpoint_supervised", scenarios.run_net(
                "net-kill-endpoint", batcher, wire=wire, supervisor=sup,
                retry=RetryPolicy(attempts=8, base_delay=0.05,
                                  max_delay=0.5, seed=1),
            ))
            if sup.respawns < 1 or sup.gave_up:
                failures.append(
                    f"kill_endpoint_supervised: respawns={sup.respawns} "
                    f"gave_up={sup.gave_up}"
                )
        finally:
            sup.close()

        # Control arm: same fault, supervision off — must trip.
        wire = WireStats()
        armed = [ChaosMonkey.from_spec("kill-endpoint@12")]
        sup = Supervisor(factory, enabled=False).start()
        try:
            judge("kill_endpoint_unsupervised_trip", scenarios.run_net(
                "net-kill-endpoint", batcher, wire=wire, supervisor=sup,
                retry=RetryPolicy(attempts=3, base_delay=0.01,
                                  max_delay=0.05, seed=1),
            ), want_pass=False)
        finally:
            sup.close()

        # Hot swap under diurnal load (last: it replaces the weights).
        wire = WireStats()
        srv = NetServer(batcher, wire=wire, conn_deadline_ms=5000.0).start()
        try:
            new_params, new_state = load_or_init(handle, seed=7)
            rep = judge("hot_swap_diurnal", scenarios.run_net(
                "net-hot-swap-diurnal", batcher, wire=wire, server=srv,
                swap_params=new_params, swap_state=new_state, seed=2,
            ))
            swap = rep.swap or {}
            rows.append(Row(
                "net_hot_swap_downtime", round(swap.get("seconds", -1.0), 3),
                "sec",
                baseline_src=(
                    f"failed_delta {swap.get('failed_delta')}, swapped "
                    f"{len(swap.get('swapped', []))}, stuck "
                    f"{swap.get('stuck')} — grow-drain-retire under live "
                    f"socket traffic"
                ),
            ).finish())
        finally:
            srv.close()
    finally:
        batcher.close()

    if failures:
        rows.append(Row(
            "error_serve_net_gate", -1.0, "error",
            baseline_src="; ".join(failures),
        ))
    print(
        "SERVE_NET_GATE "
        + ("PASS: warm cold-start compiled nothing, wire ledger balanced "
           "in every leg, loris reaped, supervised kill rode through, "
           "unsupervised trip proven, hot swap zero-failed"
           if not failures else "FAIL: " + "; ".join(failures)),
        flush=True,
    )
    return rows


def bench_cost(quick: bool) -> List[Row]:
    """--suite cost: the static cost accountant next to measured CPU rows.

    For every zoo entry point the graftcheck cost family traces
    (analysis/cost_model.py), three static rows — jaxpr-counted ICI/DCN
    bytes with the closed-form table value as the baseline column (the
    `check --cost` gate asserts these EQUAL; speedup 1.0 means the model
    is exact), and the peak-HBM accounting — then a timed img/s row of
    the SAME step configuration with the analytic roofline as baseline,
    so the model and the measurement are diffable in one place.  On the
    CPU harness the roofline is aspirational (shared-memory "ICI", no
    MXU); the static byte rows are platform-independent."""
    from parallel_cnn_tpu.analysis import cost_model, jaxpr_rules
    from parallel_cnn_tpu.config import CommConfig, FusedStepConfig, MeshConfig
    from parallel_cnn_tpu.data import synthetic
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.train import zoo
    from parallel_cnn_tpu.parallel import mesh as mesh_lib

    n_dev = len(jax.devices())
    if n_dev < 2:
        return []

    rows: List[Row] = []
    costs = {}
    for name, closed, spec in jaxpr_rules.trace_entry_points(
        fast=False, with_specs=True
    ):
        if spec is None:
            continue
        c = cost_model.entry_costs(name, closed, spec)
        costs[name] = c
        short = name.replace("zoo.", "").replace("_step", "")
        rows.append(
            Row(f"cost_{short}.ici", float(c["bytes_ici"]), "bytes/step/dev",
                baseline=float(c["expected_bytes_ici"]),
                baseline_src="closed-form table, docs/collectives.md").finish()
        )
        if c["bytes_dcn"] or c["expected_bytes_dcn"]:
            rows.append(
                Row(f"cost_{short}.dcn", float(c["bytes_dcn"]),
                    "bytes/step/dev",
                    baseline=float(c["expected_bytes_dcn"]),
                    baseline_src="closed-form table, "
                                 "docs/collectives.md").finish()
            )
        rows.append(
            Row(f"cost_{short}.peak_hbm", float(c["peak_hbm"]), "bytes/dev",
                baseline=None,
                baseline_src=(
                    f"resident+activations+grad shards; transient "
                    f"gather {c['transient_gather_bytes']} B"
                )).finish()
        )

    # --- timed legs: the same configurations the specs describe ---
    batch = 2 * n_dev
    imgs, labels = synthetic.make_image_dataset(batch, seed=3)
    model = cifar.cifar_cnn()
    ring_bf16 = CommConfig(impl="ring", wire_dtype="bfloat16")
    repeats = 5 if quick else 15

    def timed_row(entry, mesh, make_state, step):
        x, y = mesh_lib.shard_batch(
            mesh, (jnp.asarray(imgs), jnp.asarray(labels))
        )
        def thunk(carry, step=step, x=x, y=y):
            s = carry[0] if carry is not None else make_state()
            return step(s, x, y)

        ips, ips_range, n_s = _sampled_ips(
            thunk, repeats=repeats, images_per_call=batch
        )
        c = costs[entry]
        short = entry.replace("zoo.", "").replace("_step", "")
        rows.append(
            Row(f"cost_{short}.img_s", ips, "images/sec",
                baseline=c["roofline_img_s"],
                baseline_src="analytic roofline (cost_report.json)",
                value_range=ips_range, value_samples=n_s).finish()
        )

    mesh = mesh_lib.make_mesh(MeshConfig(data=n_dev, model=1))
    opt = zoo.make_optimizer(0.01, momentum=0.9)
    timed_row(
        "zoo.comm_step.ring_bf16", mesh,
        lambda: zoo.init_state(model, jax.random.key(1),
                               cifar.IN_SHAPE, opt),
        zoo.make_train_step(model, opt, accum_steps=2, mesh=mesh,
                            comm=ring_bf16),
    )
    fused = FusedStepConfig(update=True, tail=True, act_dtype="bfloat16")
    fst, n_buckets = zoo.init_fused_state(
        model, jax.random.key(1), cifar.IN_SHAPE,
        n_data=n_dev, fused=fused, bucket_bytes=ring_bf16.bucket_bytes,
    )
    del fst
    timed_row(
        "zoo.fused_step.ring_bf16", mesh,
        lambda: zoo.init_fused_state(
            model, jax.random.key(1), cifar.IN_SHAPE, n_data=n_dev,
            fused=fused, bucket_bytes=ring_bf16.bucket_bytes,
        )[0],
        zoo.make_fused_train_step(
            model, lr=0.01, momentum=0.9, accum_steps=2, mesh=mesh,
            augment=None, comm=ring_bf16, fused=fused,
            n_buckets=n_buckets,
        ),
    )
    z3 = FusedStepConfig(update=True, tail=True, act_dtype="bfloat16",
                         zero=3)
    zst, zplan = zoo.init_zero3_state(
        model, jax.random.key(1), cifar.IN_SHAPE,
        n_data=n_dev, fused=z3, bucket_bytes=ring_bf16.bucket_bytes,
    )
    del zst
    timed_row(
        "zoo.zero3_step.ring_bf16", mesh,
        lambda: zoo.init_zero3_state(
            model, jax.random.key(1), cifar.IN_SHAPE, n_data=n_dev,
            fused=z3, bucket_bytes=ring_bf16.bucket_bytes,
        )[0],
        zoo.make_zero3_train_step(
            model, lr=0.01, momentum=0.9, accum_steps=2, mesh=mesh,
            augment=None, comm=ring_bf16, fused=z3, plan=zplan,
        ),
    )
    return rows


def bench_obs(quick: bool) -> List[Row]:
    """Observability overhead gate (obs/): the SAME training step timed
    under the default no-op bundle vs a LIVE Tracer + event journal —
    spans around every dispatch, one journal record per step, exactly the
    hot-path hooks trainer/zoo wire when --trace is on.

    Rows come in traced/untraced pairs for the lenet batched step and the
    zoo CIFAR step; each traced row's baseline is its untraced twin, so
    the speedup column IS the overhead ratio. The gate: traced must hold
    >= 0.95x the untraced img/s (host-side spans are microseconds against
    multi-ms steps; losing 5% means someone put work on the step path).
    A violation appends an error-unit row (nonzero exit) and the
    OBS_GATE line flips to FAIL — the playbook greps for it."""
    import tempfile

    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.config import ObsConfig
    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.nn import cifar
    from parallel_cnn_tpu.train import step as step_lib, zoo

    obs_dir = tempfile.mkdtemp(prefix="pcnn_bench_obs_")
    rng = np.random.default_rng(0)
    repeats = 3 if quick else 6

    # -- workload 1: lenet batched step ---------------------------------
    lbatch = 1024
    lx = jnp.asarray(rng.uniform(0, 1, (lbatch, 28, 28)).astype(np.float32))
    ly = jnp.asarray(rng.integers(0, 10, (lbatch,)).astype(np.int32))
    lstep = step_lib.batched_step_fn("reference")

    def lenet_thunk(carry, bundle):
        # Fresh init per sample: the step donates its params buffers, so
        # a donated pytree can't seed the next _sync_time sample.
        p = carry[0] if carry is not None else lenet_ref.init(
            jax.random.key(0)
        )
        with bundle.span("bench.dispatch", cat="bench"):
            out = lstep(p, lx, ly, 0.1)
        if bundle.enabled:
            bundle.event("bench_step")
        return out

    # -- workload 2: zoo CIFAR CNN step ---------------------------------
    zbatch = 256
    zx = jnp.asarray(
        rng.uniform(0, 1, (zbatch, *cifar.IN_SHAPE)).astype(np.float32)
    )
    zy = jnp.asarray(rng.integers(0, 10, (zbatch,)).astype(np.int32))
    zopt = zoo.make_optimizer(0.1)
    zmodel = cifar.cifar_cnn()
    zstep = zoo.make_train_step(zmodel, zopt)

    def zoo_thunk(carry, bundle):
        st = carry[0] if carry is not None else zoo.init_state(
            zmodel, jax.random.key(1), cifar.IN_SHAPE, zopt
        )
        with bundle.span("bench.dispatch", cat="bench"):
            out = zstep(st, zx, zy)
        if bundle.enabled:
            bundle.event("bench_step")
        return out

    rows: List[Row] = []
    gate_ok = True
    for name, thunk, per_call in (
        ("lenet_step", lenet_thunk, lbatch),
        ("zoo_step", zoo_thunk, zbatch),
    ):
        bundles = {
            "untraced": obs_lib.NOOP,
            "traced": obs_lib.from_config(
                ObsConfig(trace=True, dir=obs_dir), run=f"bench_{name}"
            ),
        }
        # Interleaved sampling: alternate modes within each sample round
        # so slow host drift (thermal, co-tenant load) hits both sides
        # equally instead of biasing whichever mode ran second.
        samples = {m: [] for m in bundles}
        for _ in range(_n_samples()):
            for mode, bundle in bundles.items():
                sec = _sync_time(
                    lambda c, b=bundle, t=thunk: t(c, b), repeats
                )
                samples[mode].append(round(per_call / sec, 1))
        bundles["traced"].finish()
        ips_by_mode = {m: _median(v) for m, v in samples.items()}
        for mode in ("untraced", "traced"):
            vals = samples[mode]
            rows.append(
                Row(f"obs_{name}_{mode}", ips_by_mode[mode], "images/sec",
                    baseline=(ips_by_mode["untraced"]
                              if mode == "traced" else None),
                    baseline_src=("vs untraced twin (gate >= 0.95x)"
                                  if mode == "traced" else "no-op bundle"),
                    value_range=[min(vals), max(vals)],
                    value_samples=len(vals)).finish()
            )
        ratio = ips_by_mode["traced"] / ips_by_mode["untraced"]
        if ratio < 0.95:
            gate_ok = False
            rows.append(
                Row(f"error_obs_overhead_{name}", -1.0, "error",
                    baseline_src=(
                        f"traced {ips_by_mode['traced']} img/s is "
                        f"{ratio:.3f}x untraced "
                        f"{ips_by_mode['untraced']} (< 0.95x gate)"
                    ))
            )
    print(
        "OBS_GATE PASS" if gate_ok else
        "OBS_GATE FAIL: tracing overhead exceeded the 5% budget",
        flush=True,
    )
    return rows


def bench_elastic(quick: bool) -> List[Row]:
    """--suite elastic: resize downtime + reshard cost for the elastic
    runtime (resilience/elastic.py), gated on the contracts the tests
    pin.

    Rows: the wall-clock cost of one ElasticController.resize (quiesce →
    zero3_full_view snapshot → re-mesh → zero3_from_view reshard) in the
    shrink (8→4) and grow (4→8) directions, the snapshot alone, and the
    post-resize step throughput vs the same world trained from scratch
    (the recompile is paid once; steady-state throughput must be
    unchanged — the resharded state is the same layout a fresh init
    produces).

    The gate (ELASTIC_GATE, the playbook's contract line): an 8→4→8
    resize lap matches the fixed-mesh loss trajectory to ≤ 1e-5 and a
    zero-step reshard round trip is bit-exact. A violation appends an
    error-unit row (nonzero exit) and flips the line to FAIL.

    Needs ≥ 8 devices (the playbook mode forces 8 virtual CPU devices);
    fewer is a labeled error row, not a crash."""
    from parallel_cnn_tpu.config import (
        CommConfig, ElasticConfig, FusedStepConfig, MeshConfig,
    )
    from parallel_cnn_tpu.nn import core as nn_core, layers as nn_layers
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.resilience.elastic import ElasticController
    from parallel_cnn_tpu.train import zoo

    if len(jax.devices()) < 8:
        raise RuntimeError(
            f"elastic suite needs >= 8 devices, have {len(jax.devices())} "
            "(run under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "as benches/playbook.sh elastic does)"
        )

    # The parity preconditions (tests/test_elastic.py pins both): f32
    # activations and a BatchNorm-free model — bf16 rounding and
    # per-shard BN stats are partition-dependent, so either would turn
    # the ≤1e-5 gate into a numerics lottery.
    shape = (8, 8, 3)
    model = nn_core.Sequential([
        nn_layers.Conv2D(4, (3, 3)), nn_layers.ReLU(),
        nn_layers.MaxPool(), nn_layers.Flatten(), nn_layers.Dense(10),
    ])
    fused = FusedStepConfig(update=True, tail=True, act_dtype="float32",
                            zero=3)
    comm = CommConfig(impl="ring", bucket_bytes=2048, overlap=True)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(96, *shape)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 10, (96,)).astype(np.int32))
    batches = [(x[i * 16:(i + 1) * 16], y[i * 16:(i + 1) * 16])
               for i in range(6)]

    def init8():
        return zoo.init_zero3_state(
            model, jax.random.key(7), shape, n_data=8, fused=fused,
            bucket_bytes=comm.bucket_bytes,
        )

    def make_step(mesh, plan):
        return zoo.make_zero3_train_step(
            model, lr=0.05, momentum=0.9, accum_steps=2, mesh=mesh,
            augment=None, comm=comm, fused=fused, plan=plan,
        )

    def full_view_np(st, plan):
        return jax.tree_util.tree_map(
            np.asarray, zoo.zero3_full_view(st, plan)
        )

    mesh8 = mesh_lib.make_mesh(MeshConfig(data=8, model=1))

    # -- gate: fixed-mesh vs resize-lap loss parity ----------------------
    st, plan = init8()
    step = make_step(mesh8, plan)
    fixed = []
    for bx, by in batches:
        st, loss = step(st, bx, by, None)
        fixed.append(float(loss))

    ctl = ElasticController(ElasticConfig(), world=8)
    st, plan = init8()
    mesh = mesh8
    step = make_step(mesh, plan)
    elastic = []
    resize_ms = {}
    for i, (bx, by) in enumerate(batches):
        if i in (2, 4):
            world = 4 if i == 2 else 8
            jax.block_until_ready(jax.tree_util.tree_leaves(st))
            t0 = time.perf_counter()
            st, plan, mesh, _ = ctl.resize(
                i, world, state=st, plan=plan, comm=comm,
            )
            jax.block_until_ready(jax.tree_util.tree_leaves(st))
            resize_ms[f"{8 if world == 4 else 4}to{world}"] = round(
                (time.perf_counter() - t0) * 1e3, 2
            )
            step = make_step(mesh, plan)
        st, loss = step(st, bx, by, None)
        elastic.append(float(loss))
    lap_delta = max(abs(a - b) for a, b in zip(fixed, elastic))

    # -- gate: pure reshard bit-exactness --------------------------------
    v8 = full_view_np(st, plan)
    st4, plan4 = zoo.zero3_from_view(
        v8, n_data=4, bucket_bytes=comm.bucket_bytes
    )
    v4 = full_view_np(st4, plan4)
    bitexact = all(
        np.array_equal(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(v8),
                        jax.tree_util.tree_leaves(v4))
    )

    # -- timing rows -----------------------------------------------------
    rows: List[Row] = [
        Row(f"elastic_resize_{name}_ms", ms, "ms",
            baseline_src="quiesce + snapshot + re-mesh + reshard, "
                         "blocked end to end").finish()
        for name, ms in sorted(resize_ms.items())
    ]
    snap_st, snap_plan = init8()
    t0 = time.perf_counter()
    jax.block_until_ready(
        jax.tree_util.tree_leaves(zoo.zero3_full_view(snap_st, snap_plan))
    )
    rows.append(Row(
        "elastic_snapshot_ms",
        round((time.perf_counter() - t0) * 1e3, 2), "ms",
        baseline_src="zero3_full_view alone (the quiesce-time cost a "
                     "preemption grace window must cover)",
    ).finish())

    # Post-resize steady state vs from-scratch at the same world: the
    # resharded layout must train at the same rate.
    repeats = 4 if quick else 10
    mesh4 = mesh_lib.make_elastic_mesh(4)
    bx, by = batches[0]

    # Fresh state per sample: the zero3 step donates its input buffers,
    # so a state captured once would be deleted after the first sample's
    # warmup (same convention as bench_obs's per-sample init).
    def fresh_scratch():
        return zoo.init_zero3_state(
            model, jax.random.key(7), shape, n_data=4, fused=fused,
            bucket_bytes=comm.bucket_bytes,
        )[0]

    def fresh_resharded():
        return zoo.zero3_from_view(
            v8, n_data=4, bucket_bytes=comm.bucket_bytes
        )[0]

    scratch_plan = zoo.init_zero3_state(
        model, jax.random.key(7), shape, n_data=4, fused=fused,
        bucket_bytes=comm.bucket_bytes,
    )[1]
    ips = {}
    for name, fresh, pl in (
        ("from_scratch", fresh_scratch, scratch_plan),
        ("post_resize", fresh_resharded, plan4),
    ):
        stp = make_step(mesh4, pl)

        def thunk(carry, stp=stp, fresh=fresh):
            cur = carry[0] if carry is not None else fresh()
            return stp(cur, bx, by, None)

        med, rng_, n = _sampled_ips(thunk, repeats, bx.shape[0])
        ips[name] = med
        rows.append(Row(
            f"elastic_step4_{name}", med, "images/sec",
            baseline=(ips["from_scratch"]
                      if name == "post_resize" else None),
            baseline_src=("vs from-scratch init at world 4"
                          if name == "post_resize" else
                          "fresh world-4 init"),
            value_range=rng_, value_samples=n,
        ).finish())

    gate_ok = lap_delta <= 1e-5 and bitexact
    if not gate_ok:
        rows.append(Row(
            "error_elastic_gate", -1.0, "error",
            baseline_src=(
                f"resize-lap max |dloss| {lap_delta:.3e} (gate 1e-5), "
                f"pure reshard bitexact={bitexact}"
            ),
        ))
    print(
        f"ELASTIC_GATE {'PASS' if gate_ok else 'FAIL'}: 8-4-8 lap "
        f"|dloss| {lap_delta:.2e} (<= 1e-5), pure reshard "
        f"{'bit-exact' if bitexact else 'NOT bit-exact'}",
        flush=True,
    )
    return rows


def render_md(rows: List[Row]) -> str:
    lines = [
        "| benchmark | value | unit | reference baseline | speedup | samples |",
        "|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r.baseline is not None:
            base = f"{r.baseline} ({r.baseline_src})"
        else:
            base = r.baseline_src or "—"
        if r.value_range is not None and r.value_samples > 1:
            samples = (f"median of {r.value_samples} "
                       f"[{r.value_range[0]}–{r.value_range[1]}]")
        else:
            samples = str(r.value_samples)
        lines.append(
            f"| {r.name} | {r.value} | {r.unit} | {base} | "
            f"{r.speedup if r.speedup is not None else '—'} | {samples} |"
        )
    return "\n".join(lines)


def bench_pipeline(quick: bool) -> List[Row]:
    """--suite pipeline: the 1F1B pipeline ablation behind PIPELINE_GATE.

    One small conv model, FIXED global batch, M=4 microbatches; stages
    1/2/4 partition the 8 virtual devices into (stage, data) meshes of
    (1,8)/(2,4)/(4,2) and run train/pipeline_schedule.py's 1F1B step
    against the flat 8-device data-ring step on identical data:

    - pipe_img_s_S{S} rows time the step (baseline_src carries the
      3-step loss delta vs the flat ring — the in-row parity audit);
    - pipe_bubble_S{S} rows report the schedule's OWN idle fraction,
      counted from the (T, S) validity tables, against the closed form
      (S-1)/(S-1+M) — equal by construction of a correct 1F1B table,
      so any drift means the schedule lost work slots.

    The gate (the playbook's contract line): stages=1 bit-exact vs the
    flat ring, stages 2/4 within 1e-5, every counted bubble equal to the
    closed form.  On CPU the wall-clock rows are context, not the gate —
    8 virtual devices share the host's cores, so pipeline wall-clock
    "speedup" is meaningless here; the gate is about correctness of the
    schedule, the thing that IS portable to the TPU mesh."""
    from parallel_cnn_tpu.config import CommConfig, MeshConfig, PipelineConfig
    from parallel_cnn_tpu.nn import layers as L
    from parallel_cnn_tpu.nn.core import Sequential
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.parallel import pipeline as pipe_lib
    from parallel_cnn_tpu.train import zoo
    from parallel_cnn_tpu.train.pipeline_schedule import make_pipeline_step

    n_dev = len(jax.devices())
    if n_dev < 8:
        raise RuntimeError(
            f"--suite pipeline needs >=8 devices for the stages 1/2/4 "
            f"sweep (got {n_dev}); run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )

    model_fn = lambda: Sequential([  # noqa: E731 — fresh params per leg
        L.Conv2D(4, (3, 3)), L.ReLU(), L.MaxPool(),
        L.Flatten(), L.Dense(10),
    ])
    in_shape = (8, 8, 3)
    accum = 4
    global_batch = 64
    n_steps = 3
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n_steps, global_batch, *in_shape)).astype(np.float32)
    Y = rng.integers(0, 10, size=(n_steps, global_batch)).astype(np.int32)
    comm = CommConfig(impl="ring")

    def run_losses(step, mesh, model):
        opt = zoo.make_optimizer(0.1, momentum=0.9)
        st = mesh_lib.replicate(
            mesh, zoo.init_state(model, jax.random.key(7), in_shape, opt)
        )
        losses = []
        for i in range(n_steps):
            st, loss = step(st, jnp.asarray(X[i]), jnp.asarray(Y[i]))
            losses.append(float(loss))
        return losses, st

    # Flat 8-device data-ring reference (the thing the pipeline must
    # match numerically while spending fewer devices on the data axis).
    ref_model = model_fn()
    ref_mesh = mesh_lib.make_mesh(MeshConfig(data=n_dev, model=1))
    ref_opt = zoo.make_optimizer(0.1, momentum=0.9)
    ref_step = zoo.make_train_step(
        ref_model, ref_opt, accum_steps=accum, mesh=ref_mesh, comm=comm
    )
    ref_losses, _ = run_losses(ref_step, ref_mesh, ref_model)

    rows: List[Row] = []
    gate_ok = True
    for n_stage in (1, 2, 4):
        model = model_fn()
        pmesh = mesh_lib.make_pipeline_mesh(n_stage)
        pcfg = PipelineConfig(stages=n_stage)
        opt = zoo.make_optimizer(0.1, momentum=0.9)
        step = make_pipeline_step(
            model, opt, accum_steps=accum, mesh=pmesh,
            pipeline=pcfg, in_shape=in_shape, comm=comm,
        )
        losses, _ = run_losses(step, pmesh, model)
        delta = max(abs(a - b) for a, b in zip(losses, ref_losses))
        tol = 0.0 if n_stage == 1 else 1e-5
        if delta > tol:
            gate_ok = False

        def thunk(carry, step=step, mesh=pmesh, model=model):
            if carry is None:
                o = zoo.make_optimizer(0.1, momentum=0.9)
                st = mesh_lib.replicate(
                    mesh, zoo.init_state(model, jax.random.key(7),
                                         in_shape, o)
                )
            else:
                st = carry[0]
            return step(st, jnp.asarray(X[0]), jnp.asarray(Y[0]))

        sec = _sync_time(thunk, repeats=3 if quick else 10)
        rows.append(Row(
            f"pipe_img_s_S{n_stage}", round(global_batch / sec, 1),
            "img/sec", None,
            f"max loss delta vs flat ring {delta:.2e} (tol {tol:g})",
        ).finish())

        # Schedule-counted bubble vs the closed form — exact by
        # construction; counted from the validity tables the step itself
        # dispatches on, so the row audits the real schedule.
        fv, bv = None, None
        _, fv, _, bv = pipe_lib.schedule_arrays(n_stage, accum)
        ticks = pipe_lib.n_ticks(n_stage, accum)
        counted = 1.0 - (int(fv.sum()) + int(bv.sum())) / (ticks * n_stage)
        closed = pipe_lib.bubble_fraction(n_stage, accum)
        if abs(counted - closed) > 1e-12:
            gate_ok = False
        rows.append(Row(
            f"pipe_bubble_S{n_stage}", round(counted, 4), "idle fraction",
            None, f"closed form (S-1)/(S-1+M) = {closed:.4f}",
        ).finish())

    print(
        f"PIPELINE_GATE {'PASS' if gate_ok else 'FAIL'}: stages 1/2/4 "
        f"parity vs flat ring (bit-exact / <=1e-5) and schedule bubble "
        f"== (S-1)/(S-1+M) at M={accum}",
        flush=True,
    )
    if not gate_ok:
        raise RuntimeError("PIPELINE_GATE FAIL — see pipe_* rows")
    return rows


def bench_autotune(quick: bool) -> List[Row]:
    """--suite autotune: the cost-model autotuner behind AUTOTUNE_GATE.

    Leg 1 — ranking validation: four candidate plans that differ ONLY
    in the dimensions an 8-virtual-device CPU host can actually measure
    (accumulation factor → scan/collective pass count, pipeline stages →
    1F1B bubble) are scored by the analytic model under the ``cpu-emu``
    hardware profile and then timed for real on identical data.  The
    gate is analysis.autotune.order_gate: the measured throughput
    ordering must agree with the model on >= 75% of the pairs the model
    separates by >= 1.10x (near-ties don't vote — CPU noise can't
    adjudicate them).  The comm-impl/wire-dtype dimensions are NOT
    measured here — virtual devices share one memory bus, so wire bytes
    don't cost wall-clock; those closed forms are validated exactly, by
    byte accounting, in the graftcheck cost family (docs/autotuning.md
    "Ranking validation" has the split).  Anti-vacuity: a doctored
    table that inverts the model's predictions must FAIL the same gate.

    Leg 2 — predictive autoscaler: a flash crowd against a 1→2-replica
    lenet_ref stack with admission ON (the EWMAs the capacity planner
    reads) and a slow-replica stall arming a real capacity deficit.
    The serve SLO is set far above CPU latency so the REACTIVE
    classifier never trips — any scale-up must come from the predictive
    branch (serve/capacity.py).  Gates: >= 1 scale-up whose journal
    event carries reason="predictive", ZERO sheds journaled before the
    first scale-up (journal seq order), zero unrecovered shed rate, and
    server-side conservation.  PR 11's reactive SERVE_SLO_GATE legs run
    unchanged in --suite serve.

    Any violated expectation appends an error row (rc 1) and flips the
    contract line to AUTOTUNE_GATE FAIL — playbook.sh's tune mode greps
    for it."""
    import tempfile

    from parallel_cnn_tpu import obs as obs_lib
    from parallel_cnn_tpu.analysis import autotune as at
    from parallel_cnn_tpu.analysis import hw_profiles
    from parallel_cnn_tpu.config import (CommConfig, MeshConfig, ObsConfig,
                                         PipelineConfig, ServeConfig)
    from parallel_cnn_tpu.nn import layers as L
    from parallel_cnn_tpu.nn.core import Sequential
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.resilience.chaos import ChaosMonkey
    from parallel_cnn_tpu.serve import (AutoScaler, CapacityModel, get,
                                        scenarios, serve_stack)
    from parallel_cnn_tpu.train import zoo
    from parallel_cnn_tpu.train.pipeline_schedule import make_pipeline_step

    n_dev = len(jax.devices())
    if n_dev < 8:
        raise RuntimeError(
            f"--suite autotune needs >=8 devices (got {n_dev}); run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )

    rows: List[Row] = []
    failures: List[str] = []

    # -- leg 1: measured ranking vs the model (cpu-emu profile) ----------
    model_fn = lambda: Sequential([  # noqa: E731 — fresh params per leg
        L.Conv2D(4, (3, 3)), L.ReLU(), L.MaxPool(),
        L.Flatten(), L.Dense(10),
    ])
    in_shape = (8, 8, 3)
    global_batch = 64
    mp = at.profile_module(model_fn(), in_shape, name="bench_cnn")
    hw = hw_profiles.get_profile("cpu-emu")

    # CPU-measurable dimensions only; index 2 (k4-s2) doubles as the
    # hand-set "untuned default" row the chosen plan must beat.
    cands = (
        at.Plan(comm_impl="ring", wire_dtype="float32", overlap=True,
                accum=2),
        at.Plan(comm_impl="ring", wire_dtype="float32", overlap=True,
                accum=8),
        at.Plan(comm_impl="ring", wire_dtype="float32", overlap=False,
                accum=4, stages=2),
        at.Plan(comm_impl="ring", wire_dtype="float32", overlap=False,
                accum=4, stages=4),
    )
    default_idx = 2
    predicted = [
        at.score_plan(p, mp, hw, global_batch=global_batch,
                      n_dev=n_dev).img_s
        for p in cands
    ]

    rng = np.random.default_rng(0)
    X = rng.normal(size=(global_batch, *in_shape)).astype(np.float32)
    Y = rng.integers(0, 10, size=(global_batch,)).astype(np.int32)

    measured: List[float] = []
    for p in cands:
        model = model_fn()
        comm = CommConfig(impl="ring", wire_dtype="float32",
                          overlap=p.overlap)
        opt = zoo.make_optimizer(0.1, momentum=0.9)
        if p.stages > 1:
            mesh = mesh_lib.make_pipeline_mesh(p.stages)
            step = make_pipeline_step(
                model, opt, accum_steps=p.accum, mesh=mesh,
                pipeline=PipelineConfig(stages=p.stages),
                in_shape=in_shape, comm=comm,
            )
        else:
            mesh = mesh_lib.make_mesh(MeshConfig(data=n_dev, model=1))
            step = zoo.make_train_step(
                model, opt, accum_steps=p.accum, mesh=mesh, comm=comm
            )

        def thunk(carry, step=step, mesh=mesh, model=model):
            if carry is None:
                o = zoo.make_optimizer(0.1, momentum=0.9)
                st = mesh_lib.replicate(
                    mesh, zoo.init_state(model, jax.random.key(7),
                                         in_shape, o)
                )
            else:
                st = carry[0]
            return step(st, jnp.asarray(X), jnp.asarray(Y))

        sec = _sync_time(thunk, repeats=3 if quick else 10)
        measured.append(global_batch / sec)

    for p, pred, meas in zip(cands, predicted, measured):
        rows.append(Row(
            f"autotune_img_s_{p.label()}", round(meas, 1), "img/sec",
            None, f"model predicts {pred:.0f} img/s (cpu-emu)",
        ).finish())

    gate_ok, summary = at.order_gate(predicted, measured)
    if not gate_ok:
        failures.append(f"ranking: {summary}")
    # Anti-vacuity: inverting every prediction (1/x keeps the separation
    # ratios, flips the order) must fail the same gate.
    doctored_ok, _ = at.order_gate([1.0 / v for v in predicted], measured)
    if doctored_ok:
        failures.append(
            "ranking: the doctored (inverted) table PASSED the order "
            "gate — the gate is vacuous"
        )
    best_idx = max(range(len(cands)), key=lambda i: predicted[i])
    if measured[best_idx] < measured[default_idx]:
        failures.append(
            f"chosen plan {cands[best_idx].label()} measured "
            f"{measured[best_idx]:.0f} img/s, below the untuned default "
            f"{cands[default_idx].label()} at {measured[default_idx]:.0f}"
        )
    rows.append(Row(
        "autotune_rank_agreement", 1.0 if gate_ok else 0.0, "gate",
        None, f"{summary}; doctored table "
              f"{'FAILED (good)' if not doctored_ok else 'passed (BAD)'}",
    ).finish())

    # -- leg 2: predictive scale-up before any shed ----------------------
    handle = get("lenet_ref")
    obs_dir = tempfile.mkdtemp(prefix="pcnn_autotune_obs_")
    obs = obs_lib.from_config(
        ObsConfig(trace=True, dir=obs_dir, jax_annotations=False),
        run="autotune_pred",
    )
    # SLO far above CPU latency: the reactive classifier can never trip,
    # so any scale-up is the predictive branch's.  Deep queue + generous
    # admission budget: nothing sheds while the planner reacts.
    cfg = ServeConfig(
        model="lenet_ref", max_batch=8, max_wait_ms=1.0,
        queue_depth=2048, admission=True, slo_ms=2000.0, window_s=1.0,
    )
    pool, batcher = serve_stack(
        handle, cfg, obs=obs,
        chaos=ChaosMonkey.from_spec("slow-replica@3:400"),
    )
    capacity = CapacityModel(batcher.admission, max_batch=cfg.max_batch,
                             headroom=0.5)
    scaler = AutoScaler(pool, batcher, min_replicas=1, max_replicas=2,
                        slo_ms=cfg.slo_ms, hysteresis=2, cooldown_s=1.0,
                        interval_s=0.05, capacity=capacity, obs=obs)
    try:
        with scaler:
            rep = scenarios.run("flash-crowd", batcher, seed=7,
                                p99_ms=2000.0)
        snap = scaler.snapshot()
    finally:
        batcher.close()
    arts = obs.finish()
    events = obs_lib.read_journal(arts["journal"])
    ups = [e for e in events if e["kind"] == "scale_up"]
    first_up_seq = ups[0]["seq"] if ups else None
    sheds_before = [
        e for e in events if e["kind"] == "shed"
        and (first_up_seq is None or e["seq"] < first_up_seq)
    ]
    rows.append(Row(
        "autotune_predictive_flash_crowd", round(rep.shed_rate, 4),
        "unrecovered shed rate",
        baseline_src=(
            f"scale_ups {snap['scale_ups']} "
            f"(predictive {snap['predictive_ups']}), "
            f"sheds before first scale-up {len(sheds_before)}, "
            f"routable {snap['routable']}"
        ),
    ).finish())
    if not rep.conservation_ok:
        failures.append(f"predictive: conservation {rep.server}")
    if not ups:
        failures.append(
            "predictive: no scale-up despite the armed straggler "
            "collapsing the planner's service rate"
        )
    elif ups[0].get("reason") != "predictive":
        failures.append(
            f"predictive: first scale-up reason "
            f"{ups[0].get('reason')!r}, not 'predictive' — the reactive "
            "loop beat the planner"
        )
    if sheds_before:
        failures.append(
            f"predictive: {len(sheds_before)} sheds journaled BEFORE "
            "the first scale-up (the planner was late)"
        )
    if rep.shed_rate != 0.0:
        failures.append(
            f"predictive: unrecovered shed rate {rep.shed_rate:.4f} "
            "after the flash crowd"
        )

    if failures:
        rows.append(Row(
            "error_autotune_gate", -1.0, "error",
            baseline_src="; ".join(failures),
        ))
    print(
        "AUTOTUNE_GATE "
        + ("PASS: measured ranking agrees with the cost model, doctored "
           "table trips the gate, predictive scale-up landed before any "
           "shed"
           if not failures else "FAIL: " + "; ".join(failures)),
        flush=True,
    )
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--md", default=None)
    ap.add_argument(
        "--suite",
        default="all",
        choices=["all", "lenet", "phases", "dp", "zoo", "parity", "ops",
                 "comm", "northstar", "serve", "net", "fused", "cost",
                 "obs", "elastic", "pipeline", "autotune"],
    )
    args = ap.parse_args(argv)

    from parallel_cnn_tpu.utils.backend import enable_compile_cache

    enable_compile_cache()
    # bench.py's device contract: exits non-zero without a TPU unless the
    # caller set JAX_PLATFORMS=cpu; never switches platform itself.
    dev = _bench._device()
    platform = dev["platform"]
    print(f"[platform] {platform} device_kind={dev['device_kind']} "
          f"count={dev['device_count']}", flush=True)

    suites = {
        "lenet": bench_lenet_throughput,
        "parity": bench_lenet_parity_epoch,
        "phases": bench_phases,
        "ops": bench_ops_paths,
        "dp": bench_dp_scaling,
        "zoo": bench_zoo,
        "comm": bench_comm,
        "northstar": bench_northstar,
        "serve": bench_serve,
        "net": bench_net,
        "fused": bench_fused,
        "cost": bench_cost,
        "obs": bench_obs,
        "elastic": bench_elastic,
        "pipeline": bench_pipeline,
        "autotune": bench_autotune,
    }
    picked = suites.values() if args.suite == "all" else [suites[args.suite]]

    rows: List[Row] = []
    for fn in picked:
        # Labeled, not fatal (same convention as bench.py): one failing
        # suite must not abort the run with no rows/JSON/MD written.
        try:
            rows.extend(fn(args.quick))
            print(f"[{fn.__name__}] done", flush=True)
        except Exception as e:  # noqa: BLE001 — converted to a labeled row
            rows.append(Row(f"error_{fn.__name__}", -1.0, "error",
                            baseline=None,
                            # Error text rides the baseline-source column
                            # (render_md prints it where a baseline would
                            # go) — deliberate column reuse, not a typo.
                            baseline_src=f"{type(e).__name__}: {e}"))
            print(f"[{fn.__name__}] FAILED: {e}", flush=True)

    print(render_md(rows))
    if args.json:
        with open(args.json, "w") as f:
            json.dump([asdict(r) for r in rows], f, indent=2)
    if args.md:
        with open(args.md, "w") as f:
            f.write(
                f"# Benchmark results\n\nplatform: "
                f"{platform} ×{len(jax.devices())}\n\n"
                + render_md(rows)
                + "\n"
            )
    # Error rows are labeled in the output, but the process must still
    # exit nonzero so automation gating on exit status sees the failure.
    return 1 if any(r.unit == "error" for r in rows) else 0


if __name__ == "__main__":
    raise SystemExit(main())
