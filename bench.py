"""Headline benchmark: flagship-model training throughput on one chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...} that
names the device it ran on (`platform`, `device_kind`, `device_count`).

Baseline: the reference's best published end-to-end number — the CUDA
backend's 2,996.99 ms epoch on a T4 (PDF Table 8, BASELINE.md) ≈ 20,020
images/sec. `vs_baseline` is our images/sec over that.

Device contract: this is a device benchmark and it never picks a platform
itself. It runs in ONE process on whatever backend JAX initializes, and
exits non-zero when that is not a TPU — unless the caller explicitly set
`JAX_PLATFORMS=cpu`, in which case every number on the line is labeled
`"platform": "cpu"` (a CPU rehearsal of the script, never a device
metric). A row that raises is recorded as an `"error: …"` string on the
line AND makes the exit code non-zero.

Method: the minibatch reference-contract epoch (train/step.py:batched_step
semantics) compiled as ONE jitted lax.scan over the whole epoch — no host
round-trips, timed with a host readback barrier (the readback of a value
that depends on the whole epoch; contrast the reference's CUDA timings,
which never sync at all, SURVEY.md B11) — measured on BOTH op paths on TPU
(or with PCNN_BENCH_PALLAS set; a CPU rehearsal times path A plus the
strict-parity epoch row — see below). `value` is the fastest full-contract
path: the XLA ops (path A), or the fused Pallas megakernel (path B) when it
wins and its on-chip grad diff vs path A is within PALLAS_PARITY_TOL;
`path` labels which won, `xla_img_per_sec` / `pallas_img_per_sec` carry the
raw numbers of whatever was measured.

Also reported (extra keys, same line):
- `mfu`: analytic model FLOPs × images/sec over the chip's published bf16
  peak, looked up by `device_kind` (utils/backend.py:peak_flops — a kind
  with no published peak is an error, never a default; null off-TPU).
- `pallas_max_abs_diff`: on-chip path-A-vs-B grad parity on one batch
  (compiled-Mosaic numerics evidence, docs/kernel_authoring.md rule 5).
- `bf16_*`, `parity_epoch_s`, and `zoo_resnet18_*`: the bf16
  mixed-precision row, the strict-parity 60k-sequential-update epoch
  (vs Sequential's 102.317 s), and the MXU-saturation rows (ResNet-18
  CIFAR, XLA and Pallas-conv backends).

Optional rows run most-important-first under a wall-clock budget
(PCNN_BENCH_TIME_BUDGET, default 480 s): an external kill prints no line
at all, so rows that would blow the budget are labeled "skipped: time
budget" instead of being attempted.
"""

from __future__ import annotations

import json
import os
import sys
import time

CUDA_BASELINE_IMG_PER_SEC = 60_000 / 2.9969857  # PDF Table 8, BASELINE.md

BATCH = 2048
STEPS_PER_EPOCH = 29  # 29*2048 ≈ 59k ≈ one MNIST epoch
TIMED_REPEATS = 5

# Analytic training FLOPs per image (MACs×2), SURVEY.md §3.1 loop nests:
#   forward   conv 6·24·24·25 + pool 216·16 + fc 10·216            = 92,016 MACs
#   backward  fc wgrad 10·216 + fc dgrad 10·216 + pool wgrad 216·16
#             + pool scatter 216·16 + conv wgrad 6·25·576          = 97,632 MACs
# (elementwise sigmoid/σ′/bias work excluded — contraction FLOPs only,
# matching how MFU is conventionally counted.)
MACS_FWD = 6 * 24 * 24 * 25 + 216 * 16 + 10 * 216
MACS_BWD = 10 * 216 + 10 * 216 + 216 * 16 + 216 * 16 + 6 * 25 * 576
FLOPS_PER_IMAGE = 2 * (MACS_FWD + MACS_BWD)

# ResNet-18 (cifar_stem) analytic training FLOPs per image: forward conv/fc
# MACs summed over the graph (stem 3·3·3·64·32² = 1.77M; stage1 4×3·3·64²·32²;
# stages 2-4 each 134.2M incl. downsample 1×1; fc 512·10) = 555,422,720 MACs,
# ×2 FLOP/MAC ×3 for fwd+bwd (bwd ≈ 2× fwd, the standard accounting).
RESNET18_TRAIN_FLOPS_PER_IMAGE = 2 * 3 * 555_422_720

# Zoo-row batch sizes (both labeled in the JSON line): 1024 is the MFU
# knee for the XLA-conv row (39%/49%/51% at 512/1024/2048); the
# Pallas-conv row stays at 512 to bound its ~40 Mosaic kernel compiles
# (throughput there is block-size-insensitive).
ZOO_BATCH = 1024
ZOO_PALLAS_BATCH = 512

# Max on-chip |grad_A − grad_B| admitted before the fused Pallas path is
# barred from the headline (docs/bench_results.md states this rule; keep
# them in sync). Measured diff is ~4e-4 — pure f32 reassociation.
PALLAS_PARITY_TOL = 1e-2


def select_headline(xla_ips, pallas_ips, pallas_diff):
    """(images/sec, path-label) for the headline `value`.

    Headline = the framework's fastest full-contract path. The fused
    Pallas megakernel (path B) carries the same reference numerics as
    path A — `pallas_diff` is the same-line on-chip evidence — so when it
    wins AND its grads match within PALLAS_PARITY_TOL, it IS the flagship
    number (exactly how the reference crowns CUDA its headline backend,
    README.md:17-18). Error strings, None, and NaN diffs all bar the
    promotion; both raw paths stay in the JSON line either way.
    """
    if (
        isinstance(pallas_ips, (int, float))
        and isinstance(pallas_diff, float)
        and pallas_diff <= PALLAS_PARITY_TOL  # False for NaN
        and pallas_ips > xla_ips
    ):
        return pallas_ips, "pallas_fused"
    return xla_ips, "xla"


def _device() -> dict:
    """The device this process runs on, as JAX reports it — or exit.

    No probe child, no wait, no platform switch: whatever backend JAX
    initializes in THIS process is the one measured. Anything but a TPU
    is refused unless the caller asked for the CPU by name."""
    import jax

    d = jax.devices()
    dev = {
        "platform": d[0].platform,
        "device_kind": d[0].device_kind,
        "device_count": len(d),
    }
    if dev["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: no TPU (found platform={dev['platform']!r}, "
            f"device_kind={dev['device_kind']!r}); refusing to measure. "
            "Set JAX_PLATFORMS=cpu for a labeled CPU rehearsal."
        )
    return dev


def _readback(x) -> float:
    """Execution barrier: the host readback of a value that depends on
    the whole timed program."""
    return float(x)


_drain_cache: dict = {}


def _drain_all(tree) -> None:
    """Full-pytree barrier in ONE host readback: jit a scalar that consumes
    every leaf and read that back. Per-leaf np.asarray would pay one
    device→host round trip per leaf (ZooState has 100+ leaves) inside a
    timed region; a single-leaf readback is the opposite hazard (it only
    drains that leaf's dependency cone). Same design as
    benches/run.py:_drain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

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


def _time_epochs(epoch_fn, params, images, labels) -> float:
    """Seconds for TIMED_REPEATS chained epochs, readback-RTT-corrected.

    Warmup compiles + runs once; params chain through the repeats so every
    execution depends on the previous one.
    """
    p, err = epoch_fn(params, images, labels)
    _readback(err)

    t0 = time.perf_counter()
    for _ in range(TIMED_REPEATS):
        p, err = epoch_fn(p, images, labels)
    _readback(err)
    elapsed = time.perf_counter() - t0

    # Subtract one readback RTT, measured on a trivial chained program.
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda v: v + 1.0)
    v = tiny(jnp.float32(0.0))
    _readback(v)
    t0 = time.perf_counter()
    v = tiny(v)
    _readback(v)
    rtt = time.perf_counter() - t0
    return max(elapsed - rtt, 1e-9)


def main() -> int:
    """Run the rows, print the one JSON line; the return value is the
    process exit code (1 when any row recorded an error)."""
    from parallel_cnn_tpu.utils.backend import enable_compile_cache, peak_flops

    enable_compile_cache()
    time_budget = float(os.environ.get("PCNN_BENCH_TIME_BUDGET", "480"))
    dev = _device()
    platform = dev["platform"]
    # The published peak for THIS chip, or an error for a kind the table
    # does not know (UnknownDeviceKind) — never an assumed peak.
    peak = peak_flops(dev["device_kind"]) if platform == "tpu" else None

    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.ops import pallas as pk
    from parallel_cnn_tpu.ops import reference as ops
    from parallel_cnn_tpu.ops.activations import apply_grad

    rng = np.random.default_rng(0)
    images = jnp.asarray(
        rng.uniform(0, 1, (STEPS_PER_EPOCH, BATCH, 28, 28)).astype(np.float32)
    )
    labels = jnp.asarray(
        rng.integers(0, 10, (STEPS_PER_EPOCH, BATCH)).astype(np.int32)
    )
    params = lenet_ref.init(jax.random.key(0))

    def make_epoch(batch_grads):
        @jax.jit
        def epoch(params, images, labels):
            def body(p, xy):
                x, y = xy
                err, mean_grads = batch_grads(p, x, y)
                return apply_grad(p, mean_grads, 0.1), err

            p, errs = jax.lax.scan(body, params, (images, labels))
            return p, jnp.mean(errs)

        return epoch

    def make_batch_grads(dtype):
        """Minibatch reference grads at a compute dtype — the same
        mixed-precision recipe as train/step.py batched_step (f32 master
        weights; bf16 casts are traced no-ops when dtype is f32)."""
        cdt = jnp.dtype(dtype)

        def batch_grads(p, x, y):
            cp = jax.tree_util.tree_map(lambda v: v.astype(cdt), p)
            errs, grads = jax.vmap(
                ops.value_and_ref_grads, in_axes=(None, 0, 0)
            )(cp, x.astype(cdt), y)
            return (
                jnp.mean(errs).astype(jnp.float32),
                jax.tree_util.tree_map(
                    lambda g: jnp.mean(g.astype(jnp.float32), axis=0), grads
                ),
            )

        return batch_grads

    # Wall-clock budget for the optional rows: the driver runs this script
    # with a finite patience, and an external kill prints NO line at all
    # (the round-1 failure). Rows run most-important-first and each checks
    # the remaining budget; a skipped row is labeled, never silent.
    t_start = time.perf_counter()

    def time_left() -> float:
        return time_budget - (time.perf_counter() - t_start)

    SKIPPED = "skipped: time budget"

    n_images = STEPS_PER_EPOCH * BATCH * TIMED_REPEATS

    # The headline is the MEDIAN of N same-session samples, with the
    # min–max range reported alongside. Each sample is a full _time_epochs
    # measurement (warmed, chained, RTT-corrected). N=5 on-chip; N=3 for a
    # CPU rehearsal.
    def median(xs):
        s = sorted(xs)
        n = len(s)
        return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])

    n_samples = int(os.environ.get(
        "PCNN_BENCH_SAMPLES", "5" if platform == "tpu" else "3"
    ))

    def sample_ips(epoch_fn, n):
        out = []
        for _ in range(max(n, 1)):
            out.append(round(n_images / _time_epochs(
                epoch_fn, params, images, labels
            ), 1))
            if time_left() < 120:
                break  # keep remaining budget for the other rows
        return out

    xla_samples = sample_ips(make_epoch(make_batch_grads("float32")), n_samples)
    img_per_sec = median(xla_samples)

    # Path B: the same epoch on the FUSED Pallas megakernel — compiled
    # Mosaic when platform == "tpu" (ops/pallas.py:_interpret). Never allowed
    # to take down the headline number.
    pallas_img_per_sec = None
    pallas_samples = None
    pallas_max_abs_diff = None
    if platform == "tpu" or os.environ.get("PCNN_BENCH_PALLAS"):
        if time_left() < 60:
            pallas_img_per_sec = SKIPPED
        else:
            try:
                pallas_samples = sample_ips(
                    make_epoch(pk.batched_value_and_ref_grads), n_samples
                )
                pallas_img_per_sec = round(median(pallas_samples), 1)
            except Exception as e:  # labeled, not fatal
                pallas_img_per_sec = f"error: {type(e).__name__}: {e}"[:200]
            # On-chip A-vs-B grad parity on one batch (kernel_authoring.md
            # rule 5: interpret-mode tests can't catch Mosaic lowering
            # gaps — this line is the compiled-numerics evidence). Own try
            # block: a parity-check failure must not discard a measured
            # throughput.
            try:
                ba = make_batch_grads("float32")
                _, grads_a = jax.jit(ba)(params, images[0], labels[0])
                _, grads_b = jax.jit(pk.batched_value_and_ref_grads)(
                    params, images[0], labels[0]
                )
                pallas_max_abs_diff = float(
                    jax.tree_util.tree_reduce(
                        jnp.maximum,
                        jax.tree_util.tree_map(
                            lambda a, b: jnp.max(jnp.abs(a - b)),
                            grads_a, grads_b,
                        ),
                    )
                )
                # A drift past tolerance is labeled by pallas_max_abs_diff
                # itself (its own JSON field); the throughput stays.
            except Exception as e:
                pallas_max_abs_diff = f"error: {type(e).__name__}: {e}"[:200]

    xla_img_per_sec = img_per_sec
    img_per_sec, path = select_headline(
        img_per_sec, pallas_img_per_sec, pallas_max_abs_diff
    )
    headline_samples = pallas_samples if path == "pallas_fused" else xla_samples

    # The strict-parity epoch (≙ the reference's Table-1 workload: 60k
    # SEQUENTIAL per-sample SGD updates as one lax.scan) — the most
    # reference-faithful perf comparison the framework owns, carried in
    # the driver line against Sequential's 102.317 s. Runs on EVERY
    # platform (cheap even on CPU).
    parity_epoch_s = None
    if time_left() < 60:
        parity_epoch_s = SKIPPED
    else:
        try:
            parity_epoch_s = _bench_parity_epoch()
        except Exception as e:  # labeled, not fatal
            parity_epoch_s = f"error: {type(e).__name__}: {e}"[:200]

    # On a CPU rehearsal the throughput numbers are not device evidence,
    # but the line can still CERTIFY the kernel formulations: an
    # interpret-mode fwd+grad parity diff of the zoo Pallas conv library
    # (ops/pallas_conv.py custom_vjp) vs XLA autodiff, on a tiny shape
    # (VERDICT r4 next #7). TPU lines carry compiled-numerics parity
    # already (pallas_max_abs_diff + the zoo pallas row).
    pallas_conv_parity = None
    if platform != "tpu":
        if time_left() < 45:
            pallas_conv_parity = SKIPPED
        else:
            try:
                pallas_conv_parity = _pallas_conv_parity()
            except Exception as e:  # labeled, not fatal
                pallas_conv_parity = f"error: {type(e).__name__}: {e}"[:200]

    # The MXU-saturation row (VERDICT r2 next #2): ResNet-18 (cifar_stem)
    # bf16 training throughput + analytic-FLOPs MFU — LeNet's 379-kFLOP
    # graph can't exercise the MXU; this is the number a TPU framework's
    # ceiling is judged on. Batch 1024: measured 39%/49%/51% MFU at
    # 512/1024/2048 — 1024 captures the knee without 2048's memory and
    # compile cost.
    zoo_img_per_sec = None
    zoo_mfu = None
    zoo_pallasconv_img_per_sec = None
    if platform == "tpu" or os.environ.get("PCNN_BENCH_ZOO"):
        if time_left() < 90:
            zoo_img_per_sec = SKIPPED
        else:
            try:
                zoo_img_per_sec, zoo_mfu = _bench_resnet18(
                    batch=ZOO_BATCH, peak=peak
                )
            except Exception as e:  # labeled, not fatal
                zoo_img_per_sec = f"error: {type(e).__name__}: {e}"[:200]

    # bf16 throughput mode (train/step.py batched_step compute_dtype):
    # f32 master weights, bf16 compute on the MXU — the documented
    # trajectory-deviating mode, reported alongside the f32 headline.
    bf16_img_per_sec = None
    if platform == "tpu" or os.environ.get("PCNN_BENCH_BF16"):
        if time_left() < 45:
            bf16_img_per_sec = SKIPPED
        else:
            try:
                bf16_compute = _time_epochs(
                    make_epoch(make_batch_grads("bfloat16")),
                    params, images, labels,
                )
                bf16_img_per_sec = round(n_images / bf16_compute, 1)
            except Exception as e:
                bf16_img_per_sec = f"error: {type(e).__name__}: {e}"[:200]

    # Config #4's native-kernel cell, LAST (most expensive, ~40 Mosaic
    # kernel compiles): the same ResNet-18 with EVERY conv routed through
    # the Pallas tapped-matmul kernels (ops/pallas_conv.py). Compiled
    # Mosaic only — interpret mode at this scale is hours on CPU. Batch
    # 512 (not 1024): compile cost dominates this row and throughput is
    # block-size-insensitive (ops/pallas_conv.py _VMEM_BUDGET note).
    if platform == "tpu":
        if time_left() < 330:
            zoo_pallasconv_img_per_sec = SKIPPED
        else:
            try:
                zoo_pallasconv_img_per_sec, _ = _bench_resnet18(
                    conv_backend="pallas", batch=ZOO_PALLAS_BATCH
                )
            except Exception as e:
                zoo_pallasconv_img_per_sec = f"error: {type(e).__name__}: {e}"[:200]

    # MFU against the chip's published bf16 peak (looked up by
    # device_kind above); null off-TPU — a CPU run has no device metric.
    mfu = (
        round(FLOPS_PER_IMAGE * img_per_sec / peak, 8)
        if peak is not None
        else None
    )
    line = {
        "metric": "train_throughput_lenet_ref",
        "value": round(img_per_sec, 1),
        # A CPU rehearsal's rate is never written under the device
        # metric's unit.
        "unit": ("images/sec/chip" if platform == "tpu"
                 else "images/sec/cpu-rehearsal"),
        "vs_baseline": round(img_per_sec / CUDA_BASELINE_IMG_PER_SEC, 2),
        "platform": platform,
        "device_kind": dev["device_kind"],
        "device_count": dev["device_count"],
        "path": path,
        "value_median": round(img_per_sec, 1),
        "value_range": (
            [min(headline_samples), max(headline_samples)]
            if headline_samples else None
        ),
        "value_samples": len(headline_samples) if headline_samples else 0,
        "mfu": mfu,
        "mfu_peak_flops": peak,
        "flops_per_image": FLOPS_PER_IMAGE,
        "xla_img_per_sec": round(xla_img_per_sec, 1),
        "xla_samples": xla_samples,
        "pallas_img_per_sec": pallas_img_per_sec,
        "pallas_samples": pallas_samples,
        "pallas_max_abs_diff": pallas_max_abs_diff,
        "bf16_img_per_sec": bf16_img_per_sec,
        "parity_epoch_s": parity_epoch_s,
        "parity_vs_sequential_102.3s": (
            round(102.317095 / parity_epoch_s, 1)
            if isinstance(parity_epoch_s, float)
            else None
        ),
        "zoo_resnet18_bf16_img_per_sec": zoo_img_per_sec,
        "zoo_resnet18_bf16_mfu": zoo_mfu,
        "zoo_resnet18_batch": ZOO_BATCH,
        "zoo_resnet18_pallasconv_bf16_img_per_sec": zoo_pallasconv_img_per_sec,
        "zoo_resnet18_pallasconv_batch": ZOO_PALLAS_BATCH,
        "pallas_conv_parity": pallas_conv_parity,
    }
    print(json.dumps(line))
    # A row that raised is on the line as "error: …"; it also fails the run.
    errored = sorted(
        k for k, v in line.items()
        if isinstance(v, str) and v.startswith("error:")
    )
    if errored:
        print(f"bench.py: rows errored: {', '.join(errored)}", file=sys.stderr)
    return 1 if errored else 0


def _bench_parity_epoch() -> float:
    """Seconds for the 60k-update strict-parity epoch (2 chained runs,
    full-readback barrier — benches/run.py --suite parity methodology)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.train import step as step_lib

    n = 60_000
    rng = np.random.default_rng(0)
    images = jnp.asarray(rng.uniform(0, 1, (n, 28, 28)).astype(np.float32))
    labels = jnp.asarray(rng.integers(0, 10, (n,)).astype(np.int32))
    p = lenet_ref.init(jax.random.key(0))

    p, err = step_lib.scan_epoch(p, images, labels, 0.1)
    _drain_all((p, err))
    t0 = time.perf_counter()
    reps = 2
    for _ in range(reps):
        p, err = step_lib.scan_epoch(p, images, labels, 0.1)
    _drain_all((p, err))
    return round((time.perf_counter() - t0) / reps, 4)


def _pallas_conv_parity() -> float:
    """Max |pallas − XLA| over fwd + all grads of the zoo conv library on
    tiny shapes (stride 1 AND 2, the two code paths of
    ops/pallas_conv.py), interpret mode on CPU — the correctness
    certificate a CPU rehearsal line carries for the hand-written kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.ops import pallas_conv

    rng = np.random.default_rng(5)
    worst = 0.0
    for stride in (1, 2):
        x = jnp.asarray(rng.standard_normal((2, 8, 8, 8)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)).astype(np.float32))

        def f_pallas(x, w, stride=stride):
            return pallas_conv.conv2d(x, w, stride)

        def f_xla(x, w, stride=stride):
            return jax.lax.conv_general_dilated(
                x, w, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        ya, vjp_a = jax.vjp(f_pallas, x, w)
        yb, vjp_b = jax.vjp(f_xla, x, w)
        # Random cotangent → dgrad + wgrad exercised as the linear maps
        # they are (a sum-of-squares loss would amplify f32 roundoff of
        # the large reduction into the certificate).
        ct = jnp.asarray(rng.standard_normal(ya.shape).astype(np.float32))
        diffs = [float(jnp.max(jnp.abs(ya - yb)))] + [
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(vjp_a(ct), vjp_b(ct))
        ]
        worst = max(worst, *diffs)
    return worst


def _bench_resnet18(conv_backend: str = "xla", batch: int = 1024,
                    peak=None):
    """(images/sec, MFU) for resnet18(cifar_stem) bf16 training.

    ≙ the paper's "entire network" row (PDF Table 8) at a scale that can
    saturate the MXU. bf16 compute via input dtype (nn layers follow
    x.dtype; f32 master params, f32 BatchNorm statistics), MFU against
    ``peak`` (the chip's published bf16 peak; None off-TPU → MFU None)
    with analytic model FLOPs.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.nn import cifar, resnet
    from parallel_cnn_tpu.train import zoo

    steps = 10
    rng = np.random.default_rng(2)
    x = jnp.asarray(
        rng.uniform(0, 1, (batch,) + cifar.IN_SHAPE).astype(np.float32)
    ).astype(jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10, (batch,)).astype(np.int32))

    model = resnet.resnet18(10, cifar_stem=True, conv_backend=conv_backend)
    opt = zoo.make_optimizer(0.05)
    st = zoo.init_state(model, jax.random.key(0), cifar.IN_SHAPE, opt)
    step = zoo.make_train_step(model, opt)

    # Full-pytree barrier (ONE readback): the final step's loss depends
    # only on that step's forward, so a single-leaf readback would stop
    # the clock before the last backward + optimizer update (~2/3 of one
    # step) finishes — the partial-barrier hazard benches/run.py._drain
    # documents.
    st, loss = step(st, x, y)
    _drain_all(st)
    t0 = time.perf_counter()
    for _ in range(steps):
        st, loss = step(st, x, y)
    _drain_all(st)
    sec = time.perf_counter() - t0
    ips = steps * batch / sec
    mfu = (
        round(RESNET18_TRAIN_FLOPS_PER_IMAGE * ips / peak, 6)
        if peak is not None else None
    )
    return round(ips, 1), mfu


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # never exit silent: one labeled JSON line, always
        print(
            json.dumps(
                {
                    "metric": "train_throughput_lenet_ref",
                    "value": None,
                    "unit": "images/sec/chip",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {e}"[:500],
                }
            )
        )
        raise SystemExit(1)
    raise SystemExit(code)
