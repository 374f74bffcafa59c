"""ResNet-50 @224² single-chip MFU ablation (VERDICT r4 next #4).

Attribution by ablation: vary one axis at a time around the
config-#5 operating point (batch 64, grad accumulation 4 → microbatch
16, bf16 inputs) and read where the step time goes.

    PYTHONPATH=. python benches/resnet50_ablate.py [--steps 6]

Rows:
  accum sweep  — b64 at accum {4, 2, 1}: unrolled-accumulation overhead
                 + microbatch-size MXU effect in one axis.
  dtype        — b64 accum4 with f32 inputs: the BN/elementwise dtype
                 traffic lever (nn/layers.py normalizes at x.dtype).
  batch 32     — accum {2, 1} at constant microbatch 16 vs 32.

Each row is warmed (one step + full-pytree drain) then timed over
--steps steps with the single full-drain barrier discipline
(benches/run.py._drain hazard notes). A row that raises (e.g. OOM) is
labeled in the table AND makes the exit code non-zero. MFU is against
the chip's published bf16 peak, looked up by device_kind
(utils/backend.py:peak_flops — an unknown kind is an error).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_REPO, os.path.join(_REPO, "benches")]

from run import _drain  # noqa: E402 — the documented full-pytree barrier

# fwd ≈ 4.1 GMACs = 8.2 GFLOP @224²; train ≈ 3× fwd. (The first committed
# run of this script used 4.1e9 — MACs, not FLOPs — so its MFU column
# reads exactly 2× low; throughputs unaffected.)
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 8.2e9


def measure(batch, accum, dtype, steps, peak):
    from parallel_cnn_tpu.nn import resnet
    from parallel_cnn_tpu.train import zoo

    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.uniform(0, 1, (batch, 224, 224, 3)).astype(np.float32)
    ).astype(dtype)
    y = jnp.asarray(rng.integers(0, 100, (batch,)).astype(np.int32))
    model = resnet.resnet50(100, cifar_stem=False)
    opt = zoo.make_optimizer(0.05)
    st = zoo.init_state(model, jax.random.key(0), (224, 224, 3), opt)
    step = zoo.make_train_step(model, opt, accum_steps=accum)
    st, _ = step(st, x, y)
    _drain(st)
    t0 = time.perf_counter()
    for _ in range(steps):
        st, _ = step(st, x, y)
    _drain(st)
    sec = (time.perf_counter() - t0) / steps
    ips = batch / sec
    mfu = RESNET50_TRAIN_FLOPS_PER_IMAGE * ips / peak
    return ips, mfu, sec


def _run_grid(grid, steps, peak) -> int:
    """Print the table; 1 if any row raised."""
    failed = 0
    print("| row | img/s | MFU | ms/step |")
    print("|---|---|---|---|")
    for name, b, a, dt in grid:
        try:
            ips, mfu, sec = measure(b, a, dt, steps, peak)
            print(f"| {name} | {ips:.1f} | {mfu * 100:.1f}% | "
                  f"{sec * 1e3:.1f} |", flush=True)
        except Exception as e:  # noqa: BLE001 — labeled AND counted
            failed = 1
            print(f"| {name} | error | {type(e).__name__}: {e} | |"[:300],
                  flush=True)
    return failed


# Second grid (invoked with --big): an earlier ablation saw ~flat ms/step
# across batch at fixed microbatch, i.e. MFU scaling with GLOBAL batch at
# constant microbatch. Probe the big-batch regime.
def main_big(steps, peak):
    grid = [
        ("b128_accum8_bf16 (microbatch 16)", 128, 8, jnp.bfloat16),
        ("b128_accum4_bf16 (microbatch 32)", 128, 4, jnp.bfloat16),
        ("b256_accum8_bf16 (microbatch 32)", 256, 8, jnp.bfloat16),
        ("b256_accum16_bf16 (microbatch 16)", 256, 16, jnp.bfloat16),
        ("b512_accum16_bf16 (microbatch 32)", 512, 16, jnp.bfloat16),
        ("b512_accum8_bf16 (microbatch 64)", 512, 8, jnp.bfloat16),
        ("b512_accum4_bf16 (microbatch 128)", 512, 4, jnp.bfloat16),
    ]
    return _run_grid(grid, steps, peak)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--big", action="store_true",
                    help="big-global-batch grid (dispatch-bound finding)")
    args = ap.parse_args()
    from parallel_cnn_tpu.utils.backend import enable_compile_cache, peak_flops

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[platform] {dev.platform} device_kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    peak = peak_flops(dev.device_kind)  # unknown kind (incl. cpu): error

    if args.big:
        return main_big(args.steps, peak)
    grid = [
        ("b64_accum4_bf16 (config #5 operating point)", 64, 4, jnp.bfloat16),
        ("b64_accum2_bf16 (microbatch 32)", 64, 2, jnp.bfloat16),
        ("b64_accum1_bf16 (no accumulation)", 64, 1, jnp.bfloat16),
        ("b64_accum4_f32 (dtype lever)", 64, 4, jnp.float32),
        ("b32_accum2_bf16 (microbatch 16, half batch)", 32, 2, jnp.bfloat16),
        ("b32_accum1_bf16 (microbatch 32, half batch)", 32, 1, jnp.bfloat16),
    ]
    return _run_grid(grid, args.steps, peak)


if __name__ == "__main__":
    raise SystemExit(main())
