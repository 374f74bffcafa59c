"""Mosaic capability + layout probes for the megakernel roof attack
(VERDICT r3 next #4 / docs/future_work.md §4). TPU-only: each probe
compiles a tiny Pallas kernel and reports LOWERED / REJECTED plus a
rough timing, so the round's on-chip time is spent measuring, not
authoring.

    python benches/mosaic_probe.py

Probes:
1. rank3-dot     — dot_general with a batch dim inside a TPU kernel
                   (the round-3 blocker for MXU-ing the conv taps).
2. lane-merge    — in-kernel reshape (25, Bb, 576) → (25, Bb*576)
                   (the other blocker: would let one (6,25)@(25,L) MXU
                   dot replace 150 VPU tap-FMA rows).
3. mxu-conv-L    — the (25, L=Bb*576) HOST-layout variant: one
                   (6,25)@(25,L) dot per block vs the 150-FMA loop,
                   timed head-to-head (feasibility of splitting the
                   fused kernel's conv onto the MXU with NO in-kernel
                   relayout — the (6,L) result then needs a
                   lane-split reshape to (Bb,576) per filter, probe 4).
4. lane-split    — in-kernel reshape (1, L) → (Bb, 576).

Each probe is wrapped: a Mosaic lowering rejection prints the error
class, never a crash. Exit code 0 once the probes ran (informational
tool); 2 when there is no TPU to probe — one process, no child, no
platform switching.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BB = 128
L = BB * 576


def _run(name, fn):
    try:
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn()
        jax.block_until_ready(out)
        steady = (time.perf_counter() - t0) / 10
        print(f"[{name}] LOWERED  first={first * 1e3:.1f}ms "
              f"steady={steady * 1e6:.0f}us")
        return True
    except Exception as e:  # noqa: BLE001 — report, don't crash
        msg = f"{type(e).__name__}: {e}"
        print(f"[{name}] REJECTED {msg[:300]}")
        return False


def probe_rank3_dot():
    def kernel(a_ref, b_ref, o_ref):
        # (4, 64, 128) @ (4, 128, 64) batched over dim 0
        o_ref[:] = lax.dot_general(
            a_ref[:], b_ref[:],
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

    a = jnp.ones((4, 64, 128), jnp.float32)
    b = jnp.ones((4, 128, 64), jnp.float32)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((4, 64, 64), jnp.float32),
    )(a, b)


def probe_lane_merge():
    def kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:].reshape(25, BB * 576)

    x = jnp.ones((25, BB, 576), jnp.float32)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((25, BB * 576), jnp.float32),
    )(x)


def probe_lane_split():
    def kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:].reshape(BB, 576)

    x = jnp.ones((1, L), jnp.float32)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BB, 576), jnp.float32),
    )(x)


def _mxu_conv_L_kernel(w_ref, x_ref, o_ref):
    o_ref[:] = lax.dot_general(
        w_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _vpu_conv_kernel(w_ref, x_ref, o_ref):
    # the megakernel's current form: 6 filters × 25 tap-FMAs on the VPU
    for m in range(6):
        acc = jnp.zeros((BB, 576), jnp.float32)
        for t in range(25):
            acc += w_ref[m, t] * x_ref[t]
        o_ref[m] = acc


def _mxu_conv_3d_kernel(w_ref, x_ref, o_ref):
    # (6,25) @ (25,BB,576) → (6,BB,576): rank-2 × rank-3 contraction, NO
    # batch dims and NO reshape — if Mosaic lowers this, the megakernel's
    # 150-FMA VPU conv loop swaps for one MXU dot with the SAME x layout
    # it already stages (taps-major) and the SAME output layout the pool
    # stage consumes. The r5 probes showed mxu-conv-L 7× faster than the
    # VPU loop but lane-split REJECTED; this shape needs neither reshape.
    o_ref[:] = lax.dot_general(
        w_ref[:], x_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def probe_mxu_conv_3d():
    w = jnp.ones((6, 25), jnp.float32)
    x = jnp.ones((25, BB, 576), jnp.bfloat16)
    return pl.pallas_call(
        _mxu_conv_3d_kernel,
        out_shape=jax.ShapeDtypeStruct((6, BB, 576), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(w, x)


def probe_mxu_conv_L():
    w = jnp.ones((6, 25), jnp.float32)
    x = jnp.ones((25, L), jnp.bfloat16)
    return pl.pallas_call(
        _mxu_conv_L_kernel,
        out_shape=jax.ShapeDtypeStruct((6, L), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(w, x)


def probe_vpu_conv_baseline():
    w = jnp.ones((6, 25), jnp.float32)
    x = jnp.ones((25, BB, 576), jnp.bfloat16)
    return pl.pallas_call(
        _vpu_conv_kernel,
        out_shape=jax.ShapeDtypeStruct((6, BB, 576), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024
        ),
    )(w, x)


ROWS = 1024


def _pair_dot_kernel(x_ref, w_ref, o_ref):
    # K=64, N=128 dot then lane-halves add: the N-packing candidate for
    # the zoo conv library's 64-channel stages (two taps' weights stacked
    # along N, halves summed after row-shift). Probes whether Mosaic
    # allows value slicing at a 64-lane offset (sub-lane-tile).
    out = lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = out[:, :64] + out[:, 64:]


def probe_pair_dot_laneslice():
    x = jnp.ones((ROWS, 64), jnp.bfloat16)
    w = jnp.ones((64, 128), jnp.bfloat16)
    return pl.pallas_call(
        _pair_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((ROWS, 64), jnp.float32),
    )(x, w)


def _two_dot_kernel(x_ref, w_ref, o_ref):
    # the current formulation's shape: two separate K=N=64 dots
    a = lax.dot_general(
        x_ref[:], w_ref[:, :64], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    b = lax.dot_general(
        x_ref[:], w_ref[:, 64:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] = a + b


def probe_two_dot_baseline():
    x = jnp.ones((ROWS, 64), jnp.bfloat16)
    w = jnp.ones((64, 128), jnp.bfloat16)
    return pl.pallas_call(
        _two_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((ROWS, 64), jnp.float32),
    )(x, w)


def main():
    from parallel_cnn_tpu.utils.backend import enable_compile_cache, is_tpu

    enable_compile_cache()
    if not is_tpu():
        print("mosaic_probe: needs a TPU (compiled Mosaic); found "
              f"platform={jax.devices()[0].platform!r} — nothing probed")
        return 2
    _run("rank3-dot", probe_rank3_dot)
    _run("lane-merge", probe_lane_merge)
    _run("lane-split", probe_lane_split)
    _run("vpu-conv-baseline", probe_vpu_conv_baseline)
    _run("mxu-conv-L", probe_mxu_conv_L)
    _run("mxu-conv-3d", probe_mxu_conv_3d)
    _run("pair-dot-laneslice", probe_pair_dot_laneslice)
    _run("two-dot-baseline", probe_two_dot_baseline)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
