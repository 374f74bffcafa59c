"""Fused loss-tail kernel: pool → flatten → FC → softmax-cross-entropy in
one pass, with a custom VJP whose forward emits ``dlogits`` directly
(round 7).

The unfused zoo tail materializes three intermediates to HBM between the
last conv block and the scalar loss: the pooled activations, the logits,
and the softmax probabilities (flatten is a free view). Backward then
re-reads them to form dlogits. This module collapses the whole tail into
ONE kernel per batch block: pooling, the FC contraction, and the
numerically-stable softmax-CE all run on the block's VMEM-resident f32
accumulator, and the kernel writes exactly two things — the per-sample
loss and ``dlogits = softmax(logits) − onehot`` — so backward starts from
dlogits with no softmax recompute and no intermediate round-trips.

Supported tail patterns (train/zoo.py routes through ``split_tail``):

- ``"max2"`` — MaxPool(2×2, stride 2, VALID) → Flatten → Dense: the CIFAR
  CNN head. The pool rides INTO the kernel via the 4-parity-phase trick
  (max of 4 elementwise phase views — no in-kernel strided windows), and
  the flatten→FC becomes a per-position tapped matmul
  ``Σ_p pooled_p @ w[p·C:(p+1)·C]`` (sublane slices only — no lane-merge
  reshape, which Mosaic forbids).
- ``"gap"``  — GlobalAvgPool → Dense: the ResNet/VGG head; the spatial
  mean accumulates in-kernel.
- ``"none"`` — Flatten → Dense on an already-flat input.

Backward (plain XLA on the residuals — the HBM win is the forward's):
``dW = pooledᵀ @ dl``, ``db = Σ dl``, ``dx = dl @ Wᵀ`` routed back
through the pool. The pooled activations are RECOMPUTED from the saved
primal input (cheap elementwise max / mean) rather than saved — the
standard recompute-in-backward trade that keeps the forward write-free.
Max-pool gradient routing matches XLA's select-and-scatter tie semantics
(first max in row-major window order wins) so the fused and unfused
steps track each other ≤1e-5 in f32 even through the ReLU-zero ties that
early training produces in half the windows.

Dispatch is by platform, the one thing the code can observe: on TPU the
compiled Mosaic kernel ALWAYS runs (a kernel that fails to compile fails
the run with the compiler's text — there is no XLA substitute on chip);
off TPU the SAME math runs as an XLA composition inside the same
custom_vjp (interpret-mode Pallas would only add emulation overhead to
identical semantics). Tests and the budget analyzer set the module hook
``_FORCE_KERNEL`` to run the kernel in interpret mode against the twin.

Why the kernel is written the way it is (v5e, jax 0.9.0, PR 21's chip
runs). The previous formulation — per-position mid-dim reads
``x_ref[:, p, :]`` summed one by one, a "largest divisor" batch block —
compiled, but (a) for a batch like 200 its block was ``(100, 10)``, which
the Pallas TPU lowering refuses (sublane dim neither a multiple of 8 nor
the full dim), and (b) at ResNet-50's head shape (128, 4, 4, 2048) its
gradients were 4.0e-4 relative off the XLA twin — bf16-level error in an
f32 kernel — where this formulation measures ≤ 1.1e-6. So: the batch is
zero-padded up to a sublane-tile multiple (pad rows sliced off before the
mean); the gap mean is ONE sublane reduction over the block; the max2
phases arrive POSITION-major (P, B, C) with the FC weight as (P, C, K), so
each pooled position and its weight slab is a leading-dim index — a dense
tile read; and every value stays rank-2 (the per-sample loss is a (bb, 1)
column).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallel_cnn_tpu.ops.pallas import _interpret
from parallel_cnn_tpu.ops import pallas_conv

POOLS = ("max2", "gap", "none")

# Per-block VMEM target for the tail inputs (well under the conv model's
# 32MB budget — the tail's working set is small; this just caps the batch
# block for wide final feature maps like ResNet's 4×4×512).
_TAIL_BLOCK_BYTES = 8 * 1024 * 1024


class TailSplit(NamedTuple):
    """Where a Sequential's fused-able tail starts. ``trunk`` layers run
    unfused; layers[trunk:] are replaced by one fused_tail_loss call."""

    trunk: int
    pool: str


def split_tail(model) -> Optional[TailSplit]:
    """Recognize a supported tail suffix on a Sequential, else None (the
    caller degrades to the unfused composition)."""
    from parallel_cnn_tpu.nn import core, layers

    if not isinstance(model, core.Sequential):
        return None
    ls = list(model.layers)
    if (
        len(ls) >= 3
        and isinstance(ls[-3], layers.MaxPool)
        and ls[-3].window == (2, 2)
        and ls[-3].strides == (2, 2)
        and ls[-3].padding == "VALID"
        and isinstance(ls[-2], layers.Flatten)
        and isinstance(ls[-1], layers.Dense)
    ):
        return TailSplit(len(ls) - 3, "max2")
    if (
        len(ls) >= 2
        and isinstance(ls[-2], layers.GlobalAvgPool)
        and isinstance(ls[-1], layers.Dense)
    ):
        return TailSplit(len(ls) - 2, "gap")
    if (
        len(ls) >= 2
        and isinstance(ls[-2], layers.Flatten)
        and isinstance(ls[-1], layers.Dense)
    ):
        return TailSplit(len(ls) - 2, "none")
    return None


# Test hook: run the Pallas kernel (interpret mode) off-TPU too, so the
# differential tests and analysis/pallas_budget can reach it on CPU.
_FORCE_KERNEL = False


def _use_kernel() -> bool:
    return _FORCE_KERNEL or not _interpret()


def _phases(x):
    """The 4 parity-phase views of an even-H/W NHWC tensor, in row-major
    window order — max-pool(2,2,stride 2) is their elementwise max."""
    return (
        x[:, 0::2, 0::2, :],
        x[:, 0::2, 1::2, :],
        x[:, 1::2, 0::2, :],
        x[:, 1::2, 1::2, :],
    )


def _pooled_flat(x, pool):
    """(pooled activations as (B, D), D) for the FC contraction."""
    if pool == "max2":
        p0, p1, p2, p3 = _phases(x)
        pooled = jnp.maximum(jnp.maximum(p0, p1), jnp.maximum(p2, p3))
        return pooled.reshape(pooled.shape[0], -1), pooled
    if pool == "gap":
        pooled = jnp.mean(x, axis=(1, 2))
        return pooled, pooled
    return x.reshape(x.shape[0], -1), None


def _ce_from_logits(logits32, oh):
    """(per-sample loss as a (B, 1) column, dlogits) from f32 logits — the
    shared math both the kernel and the XLA composition implement. Rank-2
    throughout: Mosaic rejects rank-1 vector relayouts."""
    m = jnp.max(logits32, axis=-1, keepdims=True)
    e = jnp.exp(logits32 - m)
    se = jnp.sum(e, axis=-1, keepdims=True)
    loss_i = jnp.log(se) + m - jnp.sum(logits32 * oh, axis=-1, keepdims=True)
    return loss_i, e / se - oh


# --------------------------------------------------------------------------
# Kernel forward (TPU; interpret mode under _FORCE_KERNEL on CPU)
# --------------------------------------------------------------------------


def _tail_kernel(*refs, pool, P):
    """One batch block: pool → tapped FC → softmax-CE → (loss_i, dlogits).

    Inputs (per pool mode):
      max2: ph00, ph01, ph10, ph11 (P, bb, C) — position-major parity
            phase views — then w (P, C, K)
      gap:  xs (bb, P, C) with P = H·W spatial positions, w (C, K)
      none: xf (bb, D), w (D, K)
    then b (1, K), oh (bb, K); outputs loss (bb, 1), dl (bb, K).
    """
    if pool == "max2":
        p00, p01, p10, p11, w_ref, b_ref, oh_ref, loss_ref, dl_ref = refs
    else:
        x_ref, w_ref, b_ref, oh_ref, loss_ref, dl_ref = refs
    acc = b_ref[...].astype(jnp.float32)  # (1, K), broadcasts over bb
    if pool == "max2":
        for p in range(P):
            pooled_p = jnp.maximum(
                jnp.maximum(p00[p], p01[p]), jnp.maximum(p10[p], p11[p])
            )
            acc = acc + jnp.dot(
                pooled_p, w_ref[p], preferred_element_type=jnp.float32
            )
    elif pool == "gap":
        # Sublane reduction over the P spatial positions of the block.
        mean = jnp.sum(x_ref[...].astype(jnp.float32), axis=1) * (1.0 / P)
        acc = acc + jnp.dot(mean.astype(x_ref.dtype), w_ref[...],
                            preferred_element_type=jnp.float32)
    else:
        acc = acc + jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)
    oh = oh_ref[...].astype(jnp.float32)
    loss_i, dl = _ce_from_logits(acc, oh)
    loss_ref[...] = loss_i
    dl_ref[...] = dl


def _tile_block(n: int, want: int, tile: int) -> int:
    """Largest multiple of ``tile`` that divides ``n`` (itself a multiple
    of ``tile``) and is ≤ max(want, tile) — a legal Mosaic sublane block."""
    best = tile
    for d in range(tile, min(n, max(want, tile)) + 1, tile):
        if n % d == 0:
            best = d
    return best


def _kernel_forward(x, w, b, oh, pool):
    B, K = oh.shape
    # Sublane tile of the narrowest dtype that rides a (bb, ·) block: the
    # f32 one-hot/loss/dlogits blocks need 8, a bf16 activation block 16.
    tile = 32 // min(x.dtype.itemsize, 4)
    Bp = -(-B // tile) * tile
    if Bp != B:
        # Zero rows: their (finite, meaningless) losses are sliced off
        # below, before the mean.
        x = jnp.concatenate(
            [x, jnp.zeros((Bp - B,) + x.shape[1:], x.dtype)]
        )
        oh = jnp.concatenate([oh, jnp.zeros((Bp - B, K), oh.dtype)])
    if pool == "max2":
        # Position-major: XLA fuses the transpose into the strided phase
        # copy it has to make anyway.
        ins = [
            p.reshape(Bp, -1, p.shape[-1]).transpose(1, 0, 2)
            for p in _phases(x)
        ]
        P, C = ins[0].shape[0], ins[0].shape[2]
        per_img = 4 * P * C * x.dtype.itemsize
        wk = w.reshape(P, C, K)
    elif pool == "gap":
        xs = x.reshape(Bp, -1, x.shape[-1])
        P, C = xs.shape[1], xs.shape[2]
        per_img = P * C * x.dtype.itemsize
        ins, wk = [xs], w
    else:
        xf = x.reshape(Bp, -1)
        P, C = 1, xf.shape[1]
        per_img = C * x.dtype.itemsize
        ins, wk = [xf], w
    bb = _tile_block(Bp, min(128, _TAIL_BLOCK_BYTES // max(per_img, 1)), tile)
    if pallas_conv._budget_observer is not None:
        # Same shape of report as _pick_bb: double-buffered input blocks,
        # whole-weight residency, double-buffered oh/loss/dl blocks.
        w_bytes = w.size * w.dtype.itemsize + K * 4
        modeled = (
            2 * bb * per_img + 2 * w_bytes
            + 2 * bb * K * oh.dtype.itemsize          # one-hot block
            + 2 * bb * (K + 1) * 4                    # dl + loss outputs
        )
        pallas_conv._budget_observer(
            f"tail/{pool}", B, bb, per_img, w_bytes, modeled
        )
    if pool == "max2":
        in_specs = [
            pl.BlockSpec((P, bb, C), lambda i: (0, i, 0),
                         memory_space=pltpu.VMEM)
            for _ in ins
        ]
    elif pool == "gap":
        in_specs = [pl.BlockSpec((bb, P, C), lambda i: (i, 0, 0),
                                 memory_space=pltpu.VMEM)]
    else:
        in_specs = [pl.BlockSpec((bb, C), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)]
    in_specs += [
        pl.BlockSpec(wk.shape, lambda i, nd=wk.ndim: (0,) * nd,
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, K), lambda i: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bb, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
    ]
    loss_i, dl = pl.pallas_call(
        functools.partial(_tail_kernel, pool=pool, P=P),
        grid=(Bp // bb,),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((bb, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, K), jnp.float32),
        ),
        compiler_params=pallas_conv._compiler_params(),
        interpret=_interpret(),
    )(*ins, wk, b.reshape(1, K), oh)
    return loss_i[:B], dl[:B]


# --------------------------------------------------------------------------
# custom_vjp wiring (one cached closure per pool mode)
# --------------------------------------------------------------------------


def _forward(x, w, b, oh, pool):
    if _use_kernel():
        loss_i, dl = _kernel_forward(x, w, b, oh, pool)
    else:
        flat, _ = _pooled_flat(x, pool)
        logits = flat @ w + b
        loss_i, dl = _ce_from_logits(logits.astype(jnp.float32),
                                     oh.astype(jnp.float32))
    return jnp.mean(loss_i), dl


def _backward(pool, x, w, dl_scaled):
    """Shared cotangent math from dlogits (already gbar/B-scaled, f32)."""
    flat, pooled = _pooled_flat(x, pool)
    dw = (flat.astype(jnp.float32).T @ dl_scaled).astype(w.dtype)
    db = jnp.sum(dl_scaled, axis=0).astype(w.dtype)
    dflat = dl_scaled @ w.astype(jnp.float32).T  # (B, D|C) f32
    if pool == "gap":
        B, H, W, C = x.shape
        dx = jnp.broadcast_to(
            dflat[:, None, None, :] / (H * W), (B, H, W, C)
        ).astype(x.dtype)
    elif pool == "max2":
        dpool = dflat.reshape(pooled.shape)
        p0, p1, p2, p3 = _phases(x)
        # First-match tie routing in row-major window order — XLA's
        # select-and-scatter semantics, so ReLU-zero ties route
        # identically to the unfused max-pool gradient.
        m0 = p0 == pooled
        m1 = (p1 == pooled) & ~m0
        m2 = (p2 == pooled) & ~(m0 | m1)
        m3 = (p3 == pooled) & ~(m0 | m1 | m2)
        dx = jnp.zeros(x.shape, jnp.float32)
        z = jnp.zeros((), jnp.float32)
        dx = dx.at[:, 0::2, 0::2, :].set(jnp.where(m0, dpool, z))
        dx = dx.at[:, 0::2, 1::2, :].set(jnp.where(m1, dpool, z))
        dx = dx.at[:, 1::2, 0::2, :].set(jnp.where(m2, dpool, z))
        dx = dx.at[:, 1::2, 1::2, :].set(jnp.where(m3, dpool, z))
        dx = dx.astype(x.dtype)
    else:
        dx = dflat.reshape(x.shape).astype(x.dtype)
    return dx, dw, db


@functools.lru_cache(maxsize=None)
def _tail_fn(pool: str):
    @jax.custom_vjp
    def tail(x, w, b, oh):
        return _forward(x, w, b, oh, pool)[0]

    def fwd(x, w, b, oh):
        loss, dl = _forward(x, w, b, oh, pool)
        return loss, (x, w, dl)

    def bwd(res, gbar):
        x, w, dl = res
        dl_scaled = dl * (gbar.astype(jnp.float32) / dl.shape[0])
        dx, dw, db = _backward(pool, x, w, dl_scaled)
        return dx, dw, db, jnp.zeros((dl.shape[0], w.shape[-1]), jnp.float32)

    tail.defvjp(fwd, bwd)
    return tail


def fused_tail_loss(x, w, b, labels, *, pool: str = "none") -> jax.Array:
    """Mean softmax-CE loss of the fused tail — a drop-in for
    ``cross_entropy(Dense.apply(...pool/flatten...), labels)``.

    x: tail input — (B, H, W, C) for "max2"/"gap" (H, W even for max2),
    (B, D) or (B, H, W, C) for "none". w: (D, K) Dense weight in flatten
    order, b: (K,). labels: (B,) int class ids. Returns the f32 scalar
    mean loss; its VJP emits dlogits from the forward.
    """
    if pool not in POOLS:
        raise ValueError(f"unknown pool {pool!r} (one of {POOLS})")
    if pool == "max2" and (x.shape[1] % 2 or x.shape[2] % 2):
        raise ValueError(
            f"max2 tail needs even spatial dims, got {x.shape[1:3]}"
        )
    oh = jax.nn.one_hot(labels, w.shape[-1], dtype=jnp.float32)
    return _tail_fn(pool)(x, w, b, oh)
