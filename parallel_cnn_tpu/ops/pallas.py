"""Pallas TPU kernel library — the compiled-kernel path (path B).

≙ the CUDA backend's 15 ``__global__`` kernels (CUDA/layer.cu:80-368,
prototypes CUDA/layer_c.h:38-58; SURVEY.md §2.2 C17): where the reference
hand-schedules one CUDA thread per output element, this module hand-schedules
Mosaic kernels over a batch-block grid. It is the "native compiled kernel"
component of the framework — Pallas lowers to Mosaic, the TPU kernel
compiler, exactly as CUDA C++ lowers to SASS.

Two tiers, both compiled Mosaic on TPU:

1. **Per-op kernel library** (conv_fwd … conv_wgrad, staged_…): one
   pallas_call per reference kernel — the direct structural analog of the
   CUDA backend's launch-per-kernel driver (CUDA/main.cu:110-159).
2. **Fused megakernel** (`fused_value_and_ref_grads`, the product fast
   path): the ENTIRE step's math in one pallas_call — round-2 measurement
   showed the staged tier several times slower than XLA path A because
   per-call pipeline overhead + HBM round-trips dominate a 379-kFLOP
   model (no ledger number yet — ROADMAP Speed 3/4 reproduce or delete).

Design (empirically validated on TPU v5e Mosaic — see probe notes):

- **Batch is the grid.** The reference launches one kernel per *sample*
  (CUDA/main.cu:178-189 inside the 60k loop). On TPU the batch dimension is
  the only one big enough to occupy the machine, so every kernel takes a
  ``(Bb, ...)`` batch block per grid step and the gradient kernels
  *accumulate* partial sums across grid steps into their output block
  (``o_ref[...] += ...`` with a first-step zero-init) — the in-VMEM
  equivalent of the CUDA backend's ``atomicAdd`` trees
  (CUDA/layer.cu:162,196,264) with no atomics needed: the TPU grid is
  sequential on-core.
- **All contractions are rank-2 ``lax.dot_general`` on the MXU**; the 5×5
  conv is 25 unrolled tap-FMAs on the VPU (one vector op per tap, the
  systolic analog of the CUDA output-stationary loop, CUDA/layer.cu:116-130).
- **Layout packing lives in XLA, FLOPs live in Pallas.** Mosaic supports
  neither strided slices nor lane-splitting reshapes in-kernel, so the
  staged tier builds the stride-4 pool windows and im2col patch matrices
  host-side; the fused tier goes further and picks layouts that need no
  packing at all (flat-576 lanes + the Mp scatter-matmul — see the fused
  section). Scalar stores to VMEM are also rejected, and so are rank-1
  vector relayouts — every kernel value stays rank-2+, and the few
  true-scalar reductions (bias grads, error norm) stay in XLA glue.

Numerics contract is identical to ops/reference.py (SURVEY.md §2.1): same
/576 and /216 grad normalizations, same (onehot − output) error vector.
Differential tests: tests/test_ops_pallas.py diffs both tiers against the
jnp path A on an 8-device CPU harness in interpret mode; chip_smoke.py's
kernels leg diffs them compiled on the chip.

Flat layout convention: the 6×6×6 pool/FC boundary is flattened
channel-major, lane = m*36 + x*6 + y — the same C-order flatten the
reference uses for l_s1.output → fp_preact_f (Sequential/layer.h:184-198).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallel_cnn_tpu.ops import reference as ref_ops
from parallel_cnn_tpu.ops.activations import error_norm, make_error

Params = ref_ops.Params


def _interpret() -> bool:
    """The single compile-vs-interpret switch of every Pallas call in the
    package: compiled Mosaic on TPU, the interpreter everywhere else (the
    CPU test suite). True only off-TPU."""
    from parallel_cnn_tpu.utils.backend import is_tpu

    return not is_tpu()


# Test hook: force the fused path's x25 operand to stay f32 even when
# compiled (the bf16 store is a measured-zero-cost optimization that rests
# on an XLA lowering detail — see fused_value_and_ref_grads). Monkeypatched
# by test_fused_bf16_store_vs_f32_store to diff the two stores on-chip.
_FORCE_X25_F32 = False


def _batch_block(n: int, want: int = 128) -> int:
    """Largest divisor of n that is ≤ want (grid must tile the batch)."""
    b = min(n, want)
    while n % b:
        b -= 1
    return b


# VMEM budget: rank-4 (Bb,6,24,24) blocks pad their lane dim 24→128, so a
# conv-layer block costs 6·24·128·4 B ≈ 74 KB/sample and Pallas double-buffers
# every pipelined block — 32 samples keeps conv kernels ≈ 10 MB < 16 MB VMEM.
# Flat (Bb,216) blocks are ~1 KB/sample and can run much wider.
CONV_BLOCK = 32
FLAT_BLOCK = 256


def _sigmoid(v):
    # jax.nn.sigmoid — the numerically stable two-branch form, same as
    # activations.sigmoid (path A); lowers cleanly in Mosaic.
    return jax.nn.sigmoid(v)


def _pad_batch(n: int, block: int) -> int:
    """Samples of zero-padding needed to reach a multiple of `block`.

    Without padding, awkward batch sizes (primes, dataset remainders) would
    fall back to divisor-of-n blocks as small as 1 — a silent 100× grid
    blow-up. Public entry points pad instead and mask/slice the pad away.
    """
    return (-n) % block


# ---------------------------------------------------------------------------
# Forward kernels
# ---------------------------------------------------------------------------


def _conv_fwd_kernel(x_ref, w_ref, b_ref, pre_ref, out_ref):
    """≙ fp_c1 (CUDA/layer.cu:116-130) + apply_step_function (:85-95), fused.

    One grid step = one batch block. 6 filters × 25 taps unrolled: each tap
    is a (Bb, 24, 24) VPU FMA against a shifted window of the input block —
    output-stationary like the CUDA kernel, but vectorized over the batch
    instead of threaded over output pixels.
    """
    for m in range(6):
        acc = jnp.full(pre_ref.shape[:1] + (24, 24), b_ref[m, 0], pre_ref.dtype)
        for i in range(5):
            for j in range(5):
                acc = acc + w_ref[m, i, j] * x_ref[:, i : i + 24, j : j + 24]
        pre_ref[:, m] = acc
        out_ref[:, m] = _sigmoid(acc)


def conv_fwd(x: jax.Array, w: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B,28,28)·(6,5,5)+(6,) → (pre_c1, out_c1), both (B,6,24,24)."""
    n = x.shape[0]
    bb = _batch_block(n, CONV_BLOCK)
    return pl.pallas_call(
        _conv_fwd_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 28, 28), lambda g: (g, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 5, 5), lambda g: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bb, 6, 24, 24), lambda g: (g, 0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 6, 24, 24), lambda g: (g, 0, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 6, 24, 24), x.dtype),
            jax.ShapeDtypeStruct((n, 6, 24, 24), x.dtype),
        ],
        interpret=_interpret(),
    )(x, w, b.reshape(6, 1))


def pack_pool_windows(out_c1: jax.Array) -> jax.Array:
    """(B,6,24,24) → (B,16,216): stride-4 4×4 windows, tap-major sublane,
    flat channel-major window lane (t = 4i+j, lane = m*36 + x*6 + y).

    Host-side XLA relayout — the stride-4 gather Mosaic can't express
    in-kernel; 24 = 6·4 tiles exactly so it is a pure reshape+transpose.
    """
    b = out_c1.shape[0]
    win = out_c1.reshape(b, 6, 6, 4, 6, 4)          # (b, m, x, i, y, j)
    return win.transpose(0, 3, 5, 1, 2, 4).reshape(b, 16, 216)


def unpack_pool_windows(d_xw: jax.Array) -> jax.Array:
    """Inverse of pack_pool_windows: (B,16,216) → (B,6,24,24)."""
    b = d_xw.shape[0]
    win = d_xw.reshape(b, 4, 4, 6, 6, 6)            # (b, i, j, m, x, y)
    return win.transpose(0, 3, 4, 1, 5, 2).reshape(b, 6, 24, 24)


def _pool_fwd_kernel(xw_ref, w_ref, b_ref, pre_ref, out_ref):
    """≙ fp_s1 (CUDA/layer.cu:132-149) + sigmoid, fused.

    16 tap-FMAs over the packed (Bb, 16, 216) window block: tap t rides the
    sublane-adjacent dim, the 216 pool outputs ride the lane dim.
    """
    acc = jnp.full(pre_ref.shape, b_ref[0, 0], pre_ref.dtype)
    for t in range(16):
        acc = acc + w_ref[t, 0] * xw_ref[:, t, :]
    pre_ref[:] = acc
    out_ref[:] = _sigmoid(acc)


def pool_fwd(xw: jax.Array, w: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B,16,216)·(4,4)+() → (pre_s1, out_s1), both (B,216) flat channel-major."""
    n = xw.shape[0]
    bb = _batch_block(n, FLAT_BLOCK)
    return pl.pallas_call(
        _pool_fwd_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 16, 216), lambda g: (g, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((16, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 216), xw.dtype),
            jax.ShapeDtypeStruct((n, 216), xw.dtype),
        ],
        interpret=_interpret(),
    )(xw, w.reshape(16, 1), b.reshape(1, 1))


def _fc_fwd_kernel(x_ref, w_ref, b_ref, pre_ref, out_ref):
    """≙ fp_f (CUDA/layer.cu:151-165, minus bug B10's redundant launch):
    one MXU contraction (Bb,216)·(10,216)ᵀ per block + bias row."""
    acc = lax.dot_general(
        x_ref[:], w_ref[:], (((1,), (1,)), ((), ())),
        preferred_element_type=pre_ref.dtype,
        precision=lax.Precision.HIGHEST,
    ) + b_ref[:]
    pre_ref[:] = acc
    out_ref[:] = _sigmoid(acc)


def fc_fwd(x: jax.Array, w: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(B,216)·(10,216)+(10,) → (pre_f, out_f), both (B,10)."""
    n = x.shape[0]
    bb = _batch_block(n, FLAT_BLOCK)
    return pl.pallas_call(
        _fc_fwd_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((10, 216), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 10), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bb, 10), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 10), lambda g: (g, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 10), x.dtype),
            jax.ShapeDtypeStruct((n, 10), x.dtype),
        ],
        interpret=_interpret(),
    )(x, w, b.reshape(1, 10))


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _fc_bwd_kernel(d_ref, s_ref, w_ref, gw_ref, gb_ref, dout_ref):
    """≙ bp_weight_f + bp_bias_f + bp_output_s1 (CUDA/layer.cu:167-216), fused.

    Weight grad: (10,Bb)·(Bb,216) MXU outer-product partial, accumulated
    across the batch grid (≙ the CUDA atomicAdd, layer.cu:196). Also emits
    d_out_s1 = d_pre_f · W for the next stage in the same pass.
    """
    @pl.when(pl.program_id(0) == 0)
    def _():
        gw_ref[:] = jnp.zeros_like(gw_ref)
        gb_ref[:] = jnp.zeros_like(gb_ref)

    d = d_ref[:]
    gw_ref[:] += lax.dot_general(
        d, s_ref[:], (((0,), (0,)), ((), ())), preferred_element_type=gw_ref.dtype,
        precision=lax.Precision.HIGHEST,
    )
    gb_ref[:] += jnp.sum(d, axis=0, keepdims=True)
    dout_ref[:] = lax.dot_general(
        d, w_ref[:], (((1,), (0,)), ((), ())), preferred_element_type=dout_ref.dtype,
        precision=lax.Precision.HIGHEST,
    )


def fc_bwd(
    d_pre_f: jax.Array, out_s1: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(B,10),(B,216),(10,216) → (g_w_f (10,216) summed over batch,
    g_b_f (10,) summed, d_out_s1 (B,216))."""
    n = d_pre_f.shape[0]
    bb = _batch_block(n, FLAT_BLOCK)
    gw, gb, dout = pl.pallas_call(
        _fc_bwd_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 10), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((10, 216), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((10, 216), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 10), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((10, 216), d_pre_f.dtype),
            jax.ShapeDtypeStruct((1, 10), d_pre_f.dtype),
            jax.ShapeDtypeStruct((n, 216), d_pre_f.dtype),
        ],
        interpret=_interpret(),
    )(d_pre_f, out_s1, w)
    return gw, gb.reshape(10), dout


def _pool_bwd_kernel(dout_ref, pre_ref, w_ref, dpre_ref, dxw_ref):
    """≙ bp_preact_s1 + bp_output_c1 (CUDA/layer.cu:230-254), fused:
    σ′ chain through the pool preact, then scatter through the shared 4×4
    kernel into window layout (the strided scatter the CUDA kernel does
    one-thread-per-element; here one VPU row per tap)."""
    s = _sigmoid(pre_ref[:])
    dpre = dout_ref[:] * s * (1.0 - s)
    dpre_ref[:] = dpre
    for t in range(16):
        dxw_ref[:, t, :] = w_ref[t, 0] * dpre


def pool_bwd(
    d_out_s1: jax.Array, pre_s1: jax.Array, w: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(B,216),(B,216),(4,4) → (d_pre_s1 (B,216), d_xw (B,16,216))."""
    n = d_out_s1.shape[0]
    bb = _batch_block(n, FLAT_BLOCK)
    return pl.pallas_call(
        _pool_bwd_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((16, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((bb, 216), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 16, 216), lambda g: (g, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 216), d_out_s1.dtype),
            jax.ShapeDtypeStruct((n, 16, 216), d_out_s1.dtype),
        ],
        interpret=_interpret(),
    )(d_out_s1, pre_s1, w.reshape(16, 1))


def _accum_matmul_kernel(a_ref, b_ref, o_ref):
    """Grid-accumulated Aᵀ·B: the generic weight-grad contraction
    (≙ the CUDA backward weight kernels' atomicAdd reductions)."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] += lax.dot_general(
        a_ref[:], b_ref[:], (((0,), (0,)), ((), ())), preferred_element_type=o_ref.dtype,
        precision=lax.Precision.HIGHEST,
    )


def _accum_matmul(a: jax.Array, b: jax.Array, row_block: int) -> jax.Array:
    """(N,ka),(N,kb) → (ka,kb) = Σ_n a[n,:]ᵀ b[n,:], grid over row chunks."""
    n = a.shape[0]
    rb = _batch_block(n, row_block)
    ka, kb = a.shape[1], b.shape[1]
    return pl.pallas_call(
        _accum_matmul_kernel,
        grid=(n // rb,),
        in_specs=[
            pl.BlockSpec((rb, ka), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((rb, kb), lambda g: (g, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ka, kb), lambda g: (0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((ka, kb), a.dtype),
        interpret=_interpret(),
    )(a, b)


def pool_wgrad(out_c1_windows: jax.Array, d_pre_s1: jax.Array) -> jax.Array:
    """≙ bp_weight_s1 (CUDA/layer.cu:218-228): g_w_s1[i,j] = Σ_{b,w}
    d_pre_s1[b,w] · windows[b,4i+j,w], as one (B·216,16)ᵀ·(B·216,1) MXU
    contraction accumulated over row chunks."""
    b = out_c1_windows.shape[0]
    xw2 = out_c1_windows.transpose(0, 2, 1).reshape(b * 216, 16)
    dp2 = d_pre_s1.reshape(b * 216, 1)
    g = _accum_matmul(xw2, dp2, row_block=216 * 8)
    return g.reshape(4, 4)


def _sigma_prime_kernel(dout_ref, pre_ref, o_ref):
    """≙ bp_preact_c1 (CUDA/layer.cu:292-305): d_pre = d_out · σ′(pre)."""
    s = _sigmoid(pre_ref[:])
    o_ref[:] = dout_ref[:] * s * (1.0 - s)


def conv_bwd_dpre(d_out_c1: jax.Array, pre_c1: jax.Array) -> jax.Array:
    """(B,6,24,24) σ′ chain, elementwise on the VPU."""
    n = d_out_c1.shape[0]
    bb = _batch_block(n, CONV_BLOCK)
    return pl.pallas_call(
        _sigma_prime_kernel,
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec((bb, 6, 24, 24), lambda g: (g, 0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 6, 24, 24), lambda g: (g, 0, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bb, 6, 24, 24), lambda g: (g, 0, 0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(d_out_c1.shape, d_out_c1.dtype),
        interpret=_interpret(),
    )(d_out_c1, pre_c1)


def conv_wgrad(x: jax.Array, d_pre_c1: jax.Array) -> jax.Array:
    """≙ bp_weight_c1 (CUDA/layer.cu:307-335): /576-normalized correlation
    of d_pre_c1 with the input patches, as a (B·576,6)ᵀ·(B·576,25) MXU
    contraction. im2col (patch matrix) is host-side XLA."""
    b = x.shape[0]
    # (B, 25, 24, 24): feature dim = 5i+j tap order (1 input channel)
    patches = lax.conv_general_dilated_patches(x[:, None], (5, 5), (1, 1), "VALID")
    pm = patches.transpose(0, 2, 3, 1).reshape(b * 576, 25)
    dpm = d_pre_c1.transpose(0, 2, 3, 1).reshape(b * 576, 6)
    g = _accum_matmul(dpm, pm, row_block=576 * 8)  # (6, 25)
    return g.reshape(6, 5, 5) / ref_ops.CONV_NORM


# ---------------------------------------------------------------------------
# Full batched forward / backward on the Pallas path
# ---------------------------------------------------------------------------


def _forward_flat(params: Params, xs: jax.Array):
    """The shared three-stage Pallas forward pipeline (flat pool/FC layout).

    Returns (pre_c1, out_c1, xw, pre_s1, out_s1, pre_f, out_f) with the
    pool/FC stages in (B,216) flat channel-major layout. The batch must
    already be a multiple of CONV_BLOCK (public entry points pad)."""
    pre_c1, out_c1 = conv_fwd(xs, params["c1"]["w"], params["c1"]["b"])
    xw = pack_pool_windows(out_c1)
    pre_s1, out_s1 = pool_fwd(xw, params["s1"]["w"], params["s1"]["b"])
    pre_f, out_f = fc_fwd(out_s1, params["f"]["w"], params["f"]["b"])
    return pre_c1, out_c1, xw, pre_s1, out_s1, pre_f, out_f


def forward(params: Params, xs: jax.Array):
    """Batched forward through the three Pallas stages.

    Returns the same Activations tuple as ops/reference.py:forward (batched,
    pool/FC stages in flat channel-major layout reshaped back to (6,6,6))."""
    n = xs.shape[0]
    pad = _pad_batch(n, CONV_BLOCK)
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)])
    pre_c1, out_c1, _, pre_s1, out_s1, pre_f, out_f = _forward_flat(params, xs)
    np_ = n + pad
    acts = ref_ops.Activations(
        xs,
        pre_c1,
        out_c1,
        pre_s1.reshape(np_, 6, 6, 6),
        out_s1.reshape(np_, 6, 6, 6),
        pre_f,
        out_f,
    )
    if pad:
        acts = ref_ops.Activations(*(a[:n] for a in acts))
    return acts


def predict(params: Params, xs: jax.Array) -> jax.Array:
    """≙ classify (CUDA/main.cu:200-223): batched argmax over the outputs."""
    return jnp.argmax(forward(params, xs).out_f, axis=-1)


def staged_value_and_ref_grads(
    params: Params, xs: jax.Array, ys: jax.Array
) -> Tuple[jax.Array, Params]:
    """(err_mean, batch-mean reference grads) on the per-op kernel library.

    One pallas_call per reference kernel (≙ the CUDA backend's one launch
    per __global__ kernel, CUDA/main.cu:110-159) with HBM round-trips
    between stages — kept as the kernel-library composition surface and the
    differential anchor for the fused megakernel below, which is the
    product fast path. Matches jax.vmap(ops.reference.value_and_ref_grads)
    + tree-mean to fp tolerance; same reference contract (SURVEY.md §2.1).
    Batches that don't tile CONV_BLOCK are zero-padded; padded rows are
    masked out of the error vector, so every grad contribution below is
    exactly zero for them.
    """
    n = xs.shape[0]
    pad = _pad_batch(n, CONV_BLOCK)
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)])
        ys = jnp.concatenate([ys, jnp.zeros((pad,), ys.dtype)])

    pre_c1, out_c1, xw, pre_s1, out_s1, pre_f, out_f = _forward_flat(params, xs)

    # makeError + vectorNorm (host glue: O(B·10))
    d_pre_f = jax.vmap(make_error)(out_f, ys)
    if pad:
        mask = (jnp.arange(n + pad) < n).astype(d_pre_f.dtype)
        d_pre_f = d_pre_f * mask[:, None]
    err_mean = jnp.sum(jax.vmap(error_norm)(d_pre_f)) / n

    g_w_f, g_b_f, d_out_s1 = fc_bwd(d_pre_f, out_s1, params["f"]["w"])
    d_pre_s1, d_xw = pool_bwd(d_out_s1, pre_s1, params["s1"]["w"])
    g_w_s1 = pool_wgrad(xw, d_pre_s1)
    # bp_bias_s1 (CUDA/layer.cu:256-266, minus bug B9): mean over all 216
    g_b_s1 = jnp.sum(d_pre_s1) / ref_ops.POOL_BIAS_NORM

    d_out_c1 = unpack_pool_windows(d_xw)
    d_pre_c1 = conv_bwd_dpre(d_out_c1, pre_c1)
    g_w_c1 = conv_wgrad(xs, d_pre_c1)
    # bp_bias_c1 (CUDA/layer.cu:337-368): /576-normalized per-filter mean
    g_b_c1 = jnp.sum(d_pre_c1, axis=(0, 2, 3)) / ref_ops.CONV_NORM

    inv_n = 1.0 / n
    grads: Params = {
        "c1": {"w": g_w_c1 * inv_n, "b": g_b_c1 * inv_n},
        "s1": {"w": g_w_s1 * inv_n, "b": g_b_s1 * inv_n},
        "f": {"w": g_w_f * inv_n, "b": g_b_f * inv_n},
    }
    return err_mean, grads


# ---------------------------------------------------------------------------
# Fused megakernel — the whole train-step math in ONE pallas_call
# ---------------------------------------------------------------------------
#
# ≙ the CUDA backend's fused fp_f/bp_f kernels taken to their logical end
# (CUDA/layer.cu:151-198 already fuses preact+bias+activation; the rest of
# its step is 12 separate launches, CUDA/main.cu:110-159). The staged 7-call
# composition pays per-call pipeline overheads + HBM round-trips that
# dominate a 379-kFLOP model. This kernel keeps every intermediate in VMEM for the life of a
# batch block and crosses HBM exactly once per tensor.
#
# Layout strategy (the part Mosaic dictates):
# - Lane dim is the flat 24·24=576 conv pixel space — 4.5×128 exactly, so
#   VPU rows waste nothing (the staged kernels' (…,24,24) blocks pad lane
#   24→128, a 5.3× waste).
# - The input arrives pre-im2col'd in TAP-MAJOR layout (25, B, 576): each
#   tap read `x25_ref[t]` is a dense leading-dim slice (whole (Bb,576)
#   tiles), so the conv is 25 full-width FMAs per filter and the conv
#   weight grad is 25 multiply+sublane-reduce rows — no in-kernel reshapes,
#   which Mosaic would reject (lane-splitting). Measured: the batch-major
#   (Bb, 25, 576) alternative makes every tap read a strided mid-dim slice
#   and costs 30% end-to-end (940k → 1,218k img/s at Bb=64 on v5e).
# - The stride-4 "pool" is a dense (576, 36) matmul: Mp[uv, xy] =
#   w_s1[u−4x, v−4y] when (u,v) lies in window (x,y), else 0 — built ONCE
#   from iota masks at grid step 0 and reused (the TPU grid is sequential;
#   accumulator blocks persist in VMEM). Turning the sparse window scatter
#   into a small MXU matmul removes the pack/unpack relayouts entirely;
#   the transposed matmul is the backward scatter bp_output_c1.
# - Per-channel (Bb, 36) pool/FC rows tolerate lane padding (they are
#   ~0.4% of the VPU work).
# - True-scalar reductions (‖·‖₂ totals, bias grads, the 16 window-tap
#   sums) leave the kernel as small accumulator matrices and are finished
#   by O(model-size) XLA ops — Mosaic rejects scalar stores to VMEM.
# - Dots run Precision.DEFAULT, matching path A's on-chip precision (XLA
#   also runs DEFAULT): measured 13% faster than HIGHEST (6-pass f32
#   emulation) AND a TIGHTER on-chip diff vs path A (4e-4 vs 1.2e-3,
#   because both sides round the same way). CPU interpret-mode tests are
#   exact either way (no bf16 passes on CPU).


def _fused_kernel(
    x25_ref,      # (25, Bb, 576) im2col'd input block, tap-major
    y1h_ref,      # (Bb, 16) one-hot labels (10 real + 6 pad lanes)
    w_c1_ref,     # (6, 25)
    b_c1_ref,     # (6, 1)
    w_s1_ref,     # (16, 1) flat 4×4 pool kernel
    b_s1_ref,     # (1, 1)
    w_f_ref,      # (6, 36, 10) FC weight, channel-major split
    b_f_ref,      # (1, 10)
    # accumulator outputs (constant index map → persist across the grid)
    mp_ref,       # (576, 36) pool scatter matrix (built at step 0)
    err_ref,      # (1, 128) Σ per-sample ‖d_pre_f‖₂ (all lanes identical)
    gwf_ref,      # (6, 36, 10) Σ_b out_s1 ⊗ d_pre_f, channel-major
    gbf_ref,      # (1, 10) Σ_b d_pre_f
    cpool_ref,    # (576, 36) Σ_{b,m} out_c1 ⊗ d_pre_s1 (window-grad matrix)
    gbs1_ref,     # (1, 36) Σ_{b,m} d_pre_s1
    gwc1_ref,     # (150, 576) row m·25+t = Σ_b d_pre_c1[m] ⊙ x25[t]
    gbc1_ref,     # (6, 576) Σ_b d_pre_c1[m]
):
    f32 = err_ref.dtype

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # Mp[uv, xy] = Σ_t w_s1[t] · [uv in window xy at tap t]: the pool's
        # scatter structure as data, so fwd/bwd pooling are MXU matmuls.
        uv = lax.broadcasted_iota(jnp.int32, (576, 36), 0)
        xy = lax.broadcasted_iota(jnp.int32, (576, 36), 1)
        di = uv // 24 - 4 * (xy // 6)
        dj = uv % 24 - 4 * (xy % 6)
        mp = jnp.zeros((576, 36), f32)
        for t in range(16):
            mp += jnp.where((di == t // 4) & (dj == t % 4), w_s1_ref[t, 0], 0.0)
        mp_ref[:] = mp
        err_ref[:] = jnp.zeros_like(err_ref)
        gwf_ref[:] = jnp.zeros_like(gwf_ref)
        gbf_ref[:] = jnp.zeros_like(gbf_ref)
        cpool_ref[:] = jnp.zeros_like(cpool_ref)
        gbs1_ref[:] = jnp.zeros_like(gbs1_ref)
        gwc1_ref[:] = jnp.zeros_like(gwc1_ref)
        gbc1_ref[:] = jnp.zeros_like(gbc1_ref)

    mp = mp_ref[:]
    dot = functools.partial(
        lax.dot_general,
        preferred_element_type=f32,
        precision=lax.Precision.DEFAULT,
    )

    # Forward: conv → pool (Mp matmul) → FC. The conv is 25 tap-FMAs a
    # filter on the VPU (the one rank-2×rank-3 MXU dot in its place is a
    # shape cast Mosaic refuses on the v5e: docs/kernel_authoring.md).
    bb = y1h_ref.shape[0]
    outs_c1 = []
    outs_s1 = []
    pre_f = jnp.broadcast_to(b_f_ref[:], (bb, 10))
    for m in range(6):
        acc = jnp.full((bb, 576), b_c1_ref[m, 0], f32)
        for t in range(25):
            acc += w_c1_ref[m, t] * x25_ref[t]
        out_m = _sigmoid(acc)                                   # (Bb, 576)
        outs_c1.append(out_m)
        pre_s1_m = dot(out_m, mp, (((1,), (0,)), ((), ()))) + b_s1_ref[0, 0]
        out_s1_m = _sigmoid(pre_s1_m)                           # (Bb, 36)
        outs_s1.append(out_s1_m)
        pre_f = pre_f + dot(out_s1_m, w_f_ref[m], (((1,), (0,)), ((), ())))
    out_f = _sigmoid(pre_f)

    # makeError + ‖·‖₂. Lane 10 of the one-hot block is the pad-sample mask
    # (1 for real rows, 0 for zero-padded rows): it zeroes d_pre_f, and with
    # it every grad and err contribution of the pad — so no grad masking is
    # needed anywhere downstream.
    mask = y1h_ref[:, 10:11]                                    # (Bb, 1)
    d_pre_f = (y1h_ref[:, :10] - out_f) * mask                  # (Bb, 10)
    # rank-2 throughout: Mosaic rejects rank-1 vector relayouts
    norms = jnp.sqrt(jnp.sum(d_pre_f * d_pre_f, axis=1, keepdims=True))
    err_ref[:] = err_ref[:] + jnp.sum(norms)

    # FC backward (≙ bp_weight_f/bp_bias_f/bp_output_s1, fused).
    gbf_ref[:] += jnp.sum(d_pre_f, axis=0, keepdims=True)
    for m in range(6):
        out_s1_m = outs_s1[m]
        gwf_ref[m] += dot(out_s1_m, d_pre_f, (((0,), (0,)), ((), ())))
        d_out_s1_m = dot(d_pre_f, w_f_ref[m], (((1,), (1,)), ((), ())))
        d_pre_s1_m = d_out_s1_m * out_s1_m * (1.0 - out_s1_m)   # (Bb, 36)
        gbs1_ref[:] += jnp.sum(d_pre_s1_m, axis=0, keepdims=True)
        out_m = outs_c1[m]
        # window-grad matrix: finished into g_w_s1 by XLA diagonal-einsum
        cpool_ref[:] += dot(out_m, d_pre_s1_m, (((0,), (0,)), ((), ())))
        # pool scatter-back + σ′ (≙ bp_output_c1 + bp_preact_c1)
        d_out_c1_m = dot(d_pre_s1_m, mp, (((1,), (1,)), ((), ())))
        d_pre_c1_m = d_out_c1_m * out_m * (1.0 - out_m)         # (Bb, 576)
        gbc1_ref[m : m + 1, :] += jnp.sum(d_pre_c1_m, axis=0, keepdims=True)
        # conv weight grad: 25 multiply+sublane-reduce rows per filter
        # (≙ bp_weight_c1's per-tap correlation, CUDA/layer.cu:307-335)
        for t in range(25):
            r = m * 25 + t
            gwc1_ref[r : r + 1, :] += jnp.sum(
                d_pre_c1_m * x25_ref[t], axis=0, keepdims=True
            )


FUSED_BLOCK = 128  # Mosaic's scoped-VMEM accounting charges the unrolled
                   # tap loops' temporaries (measured: 25.0 MB at Bb=64,
                   # 17.2 MB at Bb=32 against the DEFAULT 16 MB scoped
                   # limit) — so the call raises vmem_limit_bytes below;
                   # v5e VMEM is 128 MB. Fewer grid steps amortize the
                   # fixed per-step accumulator RMW work: same-session
                   # on-chip epoch sweep measured 1.349/1.403/1.388 M
                   # img/s at Bb=64/128/256 — 128 is the knee.
FUSED_VMEM_LIMIT = 100 * 1024 * 1024


def _fused_call(x25, y1h, params, n_pad: int):
    bb = _batch_block(n_pad, FUSED_BLOCK)
    f32 = jnp.float32
    outs = pl.pallas_call(
        _fused_kernel,
        grid=(n_pad // bb,),
        in_specs=[
            pl.BlockSpec((25, bb, 576), lambda g: (0, g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 16), lambda g: (g, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 25), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((16, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 36, 10), lambda g: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 10), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((576, 36), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 128), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 36, 10), lambda g: (0, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 10), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((576, 36), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 36), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((150, 576), lambda g: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((6, 576), lambda g: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((576, 36), f32),   # Mp (scratch-as-output)
            jax.ShapeDtypeStruct((1, 128), f32),    # err
            jax.ShapeDtypeStruct((6, 36, 10), f32), # gwf
            jax.ShapeDtypeStruct((1, 10), f32),     # gbf
            jax.ShapeDtypeStruct((576, 36), f32),   # cpool
            jax.ShapeDtypeStruct((1, 36), f32),     # gbs1
            jax.ShapeDtypeStruct((150, 576), f32),  # gwc1 rows
            jax.ShapeDtypeStruct((6, 576), f32),    # gbc1 rows
        ],
        interpret=_interpret(),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            vmem_limit_bytes=FUSED_VMEM_LIMIT
        ),
    )(
        x25,
        y1h,
        params["c1"]["w"].reshape(6, 25).astype(f32),
        params["c1"]["b"].reshape(6, 1).astype(f32),
        params["s1"]["w"].reshape(16, 1).astype(f32),
        params["s1"]["b"].reshape(1, 1).astype(f32),
        params["f"]["w"].reshape(10, 6, 36).transpose(1, 2, 0).astype(f32),
        params["f"]["b"].reshape(1, 10).astype(f32),
    )
    return outs


def fused_value_and_ref_grads(
    params: Params, xs: jax.Array, ys: jax.Array
) -> Tuple[jax.Array, Params]:
    """(err_mean, batch-mean reference grads): the whole step's math in one
    Mosaic kernel + O(model-size) XLA finish ops.

    Differential contract: matches `staged_value_and_ref_grads` and path A
    (`jax.vmap(ops.reference.value_and_ref_grads)` + tree-mean) to fp
    tolerance — tests/test_ops_pallas.py, and on-chip in chip_smoke.py's
    kernels leg.
    """
    n = xs.shape[0]
    f32 = jnp.float32
    pad = _pad_batch(n, min(n, FUSED_BLOCK))
    if pad:
        xs = jnp.concatenate([xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)])
    n_pad = n + pad

    # Host-side prep (cheap XLA relayouts): im2col the input once, in the
    # TAP-MAJOR (25, B, 576) layout the kernel wants — tap t = 5p+q leads,
    # flat pixel uv on the lane dim.
    x25 = (
        lax.conv_general_dilated_patches(
            xs[:, None].astype(f32), (5, 5), (1, 1), "VALID"
        )
        .reshape(n_pad, 25, 576)
        .transpose(1, 0, 2)
    )
    if not _interpret() and not _FORCE_X25_F32:
        # STORE the dominant operand in bf16 (compute stays f32 — the
        # kernel's FMAs/dots promote on read). Zero numerics cost on the
        # chip: the patches conv above runs Precision.DEFAULT, whose MXU
        # passes already quantize values to bf16, so the bf16 store only
        # halves x25's HBM/VMEM traffic — measured ON-CHIP grad diff vs
        # the f32 store is exactly 0.0, and throughput goes 1.40M →
        # 1.93-3.59M img/s in an earlier builder session (not a ledger
        # number; reproduce before relying on it).
        # Interpret mode (CPU tests) keeps exact f32: there
        # the patches op is exact, so a bf16 store WOULD change numerics.
        # DEPENDENCY: "zero cost" rests on an XLA lowering detail — if
        # patch extraction is ever lowered as pure data movement (no MXU
        # pass), this cast becomes a real precision loss. Guarded by the
        # TPU-gated regression test
        # tests/test_ops_pallas.py::test_fused_bf16_store_vs_f32_store.
        x25 = x25.astype(jnp.bfloat16)
    # One-hot labels padded to 16 lanes; lane 10 doubles as the pad-sample
    # mask (1 for real rows, 0 for pad rows — zeroing d_pre_f and with it
    # every grad & err contribution of the pad).
    y1h = jnp.zeros((n_pad, 16), f32)
    y1h = y1h.at[jnp.arange(n), ys].set(1.0, mode="drop")
    y1h = y1h.at[:n, 10].set(1.0)

    (mp, err, gwf, gbf, cpool, gbs1, gwc1, gbc1) = _fused_call(
        x25, y1h, params, n_pad
    )
    del mp  # Mp is kernel-internal state; outputs are the contract below

    inv_n = 1.0 / n
    err_mean = err[0, 0] * inv_n

    # XLA finish ops — each O(model size), no batch dimension left:
    # FC weight grad arrives channel-major transposed: (6, 36, 10) → (10, 216)
    g_w_f = gwf.transpose(2, 0, 1).reshape(10, 216) * inv_n
    g_b_f = gbf.reshape(10) * inv_n
    # g_w_s1[i,j] = Σ_{x,y} cpool[(4x+i)·24+4y+j, (x,y)]: diagonal einsum
    # over the window-grad matrix (repeated labels extract the diagonal).
    g_w_s1 = jnp.einsum("xiyjxy->ij", cpool.reshape(6, 4, 6, 4, 6, 6)) * inv_n
    g_b_s1 = jnp.sum(gbs1) / ref_ops.POOL_BIAS_NORM * inv_n
    g_w_c1 = (
        jnp.sum(gwc1, axis=1).reshape(6, 5, 5) / ref_ops.CONV_NORM * inv_n
    )
    g_b_c1 = jnp.sum(gbc1, axis=1) / ref_ops.CONV_NORM * inv_n

    grads: Params = {
        "c1": {"w": g_w_c1, "b": g_b_c1},
        "s1": {"w": g_w_s1, "b": g_b_s1},
        "f": {"w": g_w_f, "b": g_b_f},
    }
    return err_mean, grads


# The product fast path (--ops pallas, train/step.py) is the
# fused megakernel; the staged per-op composition stays as the kernel
# library's differential anchor.
batched_value_and_ref_grads = fused_value_and_ref_grads
