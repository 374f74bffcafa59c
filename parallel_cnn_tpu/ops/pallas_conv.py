"""Pallas conv kernels for the model zoo (BASELINE.json config #4:
"ResNet-18 on CIFAR-10 with Pallas conv kernels").

≙ the CUDA backend's hand-written conv kernels (CUDA/layer.cu:116-130)
generalized beyond the fixed LeNet shapes: a TPU-native conv as
**shift-and-matmul** — NHWC with channels on the lane axis, the conv's
taps each ONE large MXU matmul over a row-shifted view of the flattened
input:

    out_flat[r, :] = Σ_t  in_flat[r + off_t, :] @ W_t        (C × Cout)

Round-4 formulation (replaces round 3's full-perimeter-pad layout,
VERDICT r3 next #2 — the prior analysis lives in docs/future_work.md §1):

- **Pad H only.** The flat layout per image is ((T_top+H+T_bot)·W, C) —
  zero rows above/below sized by the tap reach, no W padding. Horizontal
  taps then wrap across row boundaries at the image edge; a per-tap
  COLUMN MASK (built in-kernel from a broadcasted_iota row index mod W —
  VPU-cheap) zeroes the wrapped lanes, which is exactly the SAME-padding
  semantics (the masked-out values are the zero pads). Row waste drops
  from (H+2)(W+2)/HW to (H+4)/H for 3×3 — 2.25× → 2.0× at 4×4,
  1.56× → 1.5× at 8×8 — and every tap slice stays dense.

- **Stride 2 computes ONLY the real output rows** via phase
  decomposition (was: stride-1 everything, then subsample — 4× waste on
  every downsample conv, ≈15% of ResNet-18 FLOPs and more of
  ResNet-50). For even H,W (every stride-2 conv in the ResNet
  families), split x into its 4 parity phases x_pq[i,j] = x[2i+p,2j+q];
  each tap (dy,dx) of a k-odd kernel then reads exactly one phase at a
  small dense offset:

      out[oy,ox] += W[dy,dx] · x_{(dy-pl)%2, (dx-pl)%2}[oy+a, ox+b]
      a = (dy-pl-(dy-pl)%2)/2,  b likewise,  pl = (k-2)//2 (XLA pad_lo)

  so the tapped-matmul kernel runs over ~(Hh+pad)·Wh rows per image —
  the true output size plus pad rows — instead of (H+pad)·(W+pad). The
  backward splits the same way: dgrad's four output phases each take
  the tap subset with matching parity (ONE kernel call, one pass over
  dout, four output refs), and wgrad contracts dout against the
  forward's phase tensors. Odd spatial dims (no zoo model hits them)
  fall back to stride-1 + phase-correct subsample for k=3.

- **k ∈ {1, 3, 5, 7}**: the tap geometry is computed, not hard-coded,
  so ResNet-50's 7×7-stride-2 stem runs on the same kernel family
  (taps' column masks generalize to multi-column shifts; pad rows size
  themselves from the tap reach).

The same generic kernel body serves all three conv derivatives:
- forward:  taps over x (1 ref) or its phases (4 refs), weights (C, Cout)
- dgrad:    taps over dout with negated/phase offsets, weights W_tᵀ
- wgrad:    per-tap  x_shiftᵀ @ dout  (C, Cout), accumulated across the
            batch grid into a (T, C, Cout) block (≙ the CUDA atomicAdd
            weight-grad trees, without atomics: the TPU grid is
            sequential)

wired together with `jax.custom_vjp`, so `jax.grad` through the zoo
trainer uses Pallas for every conv FLOP.

Round-6 additions (ISSUE 2, the round-5 verdict's perf mandate):

- **Fused epilogues** (≙ the reference CUDA kernels' fused
  bias+activation, CUDA/layer.cu:151-165): `conv2d_fused` applies
  per-channel scale+shift (folded inference-mode BN), an optional
  residual add, and ReLU on the f32 accumulator INSIDE the kernel's
  output block, before the single HBM write — one round-trip per layer
  tail instead of three-to-four. The VJP recomputes the cheap
  elementwise tail in XLA from the saved conv output (ReLU mask +
  residual pass-through) and routes the conv cotangent through the
  existing `_conv2d_bwd` kernels.

- **Double-buffered weight streaming**: when cout is large
  (multiple of `_COUT_TILE`) the weight stack no longer sits resident;
  a second, minor grid dimension walks cout tiles and Pallas's grid
  pipeline prefetches tile j+1's weight block while tile j multiplies.
  The x blocks keep a constant index along that dimension, so Mosaic
  skips their re-DMA. `_pick_bb` counts both in-flight weight buffers
  (the existing `2·w_bytes` term) against the per-tile bytes.

- **Row-band spatial tiling**: layouts whose per-image flat rows
  exceed `_MAX_ROWS_PER_IMG` (the 7×7-s2 stem at 224²: 49 taps ×
  12880 rows was Mosaic-compile-pathological, >25 min) are split into
  H-bands with a real-data halo; each band is its own kernel call and
  the results concatenate along H. Interior halo rows read true
  neighbor pixels, exterior ones the usual zero pads, so the math is
  exact — only compile-unit size changes.

Scope (documented, enforced): odd kernel 1/3/5/7, stride 1 or 2, SAME
padding, NHWC; stride-2 for k>3 requires even spatial dims. Anything
else is refused by `supports()` and `nn.layers.Conv2D(backend="pallas")`
raises — a "pallas" model never quietly runs an XLA conv, and a kernel
Mosaic cannot compile fails the run with the compiler's text.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Shared with the LeNet kernel library: the one compile-vs-interpret
# switch (compiled on TPU only), and batch blocks must divide the batch.
from parallel_cnn_tpu.ops.pallas import _batch_block, _interpret  # noqa: E402

log = logging.getLogger(__name__)


# Scoped-VMEM model for choosing how many images ride one grid step.
# The block's true footprint is NOT just the double-buffered in/out
# pipeline buffers: Mosaic materializes each of the T unrolled tap slices
# (a (center-rows, Cin) copy per tap) plus the f32 accumulator, and on
# v5e that stack is what OOMs first. Blocks are sized against a MODERATE
# budget, not the whole limit: measured on the chip (round 3), ResNet-18
# pallas-conv throughput is identical at bb=8 and bb=32 (6898 vs 6899
# img/s — the per-tap matmuls are already MXU-sized) while Mosaic compile
# time grows with block bytes, so big blocks only buy slower builds. The
# raised limit stays as safety margin over the model.
_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_LIMIT = 100 * 1024 * 1024

# Weight-streaming tile (lanes): couts that are a strict multiple get a
# second grid dimension walking cout tiles — the grid pipeline then
# double-buffers the weight DMA (prefetch tile j+1 while j multiplies)
# instead of holding the whole stack resident. 0 disables.
_COUT_TILE = int(os.environ.get("PCNN_PALLAS_COUT_TILE", "256"))  # graftcheck: disable=env-outside-config -- import-time tiling knob read once into a module constant

# Row-band tiling threshold: per-image flat rows above this split into
# H-bands, each its own kernel call (Mosaic compile time scales with
# taps × rows; the 224² stem's 49 × 12880 was pathological). 6144 keeps
# every ≤64² zoo shape single-band.
_MAX_ROWS_PER_IMG = int(os.environ.get("PCNN_PALLAS_MAX_ROWS_PER_IMG",  # graftcheck: disable=env-outside-config -- import-time tiling knob read once into a module constant
                                       "6144"))


class Epilogue(NamedTuple):
    """Static spec for the in-kernel output-block epilogue.

    The kernel applies, on the f32 accumulator and in this order:
    ``z = acc·scale + shift``  (per-channel, folded inference-mode BN),
    ``z += residual``          (if ``residual``),
    ``z = max(z, 0)``          (if ``relu``),
    then writes ``z`` as the (only) y output. ``emit_preact`` adds a
    second output carrying the raw conv accumulator — the VJP's saved
    activation — at the cost of the extra HBM write, so the primal
    (inference) call never pays it."""

    relu: bool = True
    residual: bool = False
    emit_preact: bool = False

# A tap: (input_ref_index, flat_row_offset, column_shift, weight_slot).
# column_shift is the tap's horizontal pixel shift: output rows whose
# pixel column j has j+shift outside [0, W) read a wrapped element and
# are masked to zero — the SAME-padding semantics.
Tap = Tuple[int, int, int, int]


def _col_masks(taps_per_out, w_col: int, lo: int, hi: int):
    """(rows, 1) validity masks keyed by column shift. Row index is
    block-local; every layout here has rows-per-image divisible by
    w_col and blocks start on image boundaries, so (row % w_col) IS the
    pixel column."""
    shifts = {s for taps in taps_per_out for (_, _, s, _) in taps if s}
    if not shifts:
        return {}
    col = lax.broadcasted_iota(jnp.int32, (hi - lo, 1), 0) + lo
    col = lax.rem(col, w_col)
    return {
        s: (col >= -s) & (col < w_col - s)
        for s in shifts
    }


def _plan_taps(entry):
    """Flatten a plan entry back to (ridx, off, shift, slot) tap views
    (slot unused) — lets _col_masks collect the shift set uniformly."""
    if entry[0] == "s":
        return [entry[1]]
    _, ridx, off1, s1, off2, s2, _pslot = entry
    return [(ridx, off1, s1, -1), (ridx, off2, s2, -1)]


def _build_plan(taps_per_out, w_stack, cout):
    """Greedily pair each output's taps (within a shared input ref) for
    the N-packing path when 2·cout fits the 128-lane tile; returns
    (plan_per_out, wp_stack or None). Odd taps stay single."""
    if cout > 64:
        return (
            [[("s", t) for t in taps] for taps in taps_per_out],
            None,
        )
    plans = []
    pair_ws = []
    for taps in taps_per_out:
        plan = []
        pending = {}
        for t in taps:
            r = t[0]
            if r in pending:
                t1 = pending.pop(r)
                pslot = len(pair_ws)
                pair_ws.append(
                    jnp.concatenate(
                        [w_stack[t1[3]], w_stack[t[3]]], axis=-1
                    )
                )
                plan.append(("p", r, t1[1], t1[2], t[1], t[2], pslot))
            else:
                pending[r] = t
        plan.extend(("s", t) for t in pending.values())
        plans.append(plan)
    if not pair_ws:
        return plans, None
    return plans, jnp.stack(pair_ws)


def _tap_kernel(plan_per_out, w_col, lo, tail, n_in, have_pairs, ep, *refs):
    """Generic multi-ref, multi-output tapped matmul.

    refs = (x_ref_0..x_ref_{n_in-1}, w_ref[, wp_ref][, ss_ref][,
    res_ref], o_ref_0..). With an `ep: Epilogue`, ss_ref is an (8, cout)
    f32 block (row 0 scale, row 1 shift; 8 rows keep the f32 sublane
    tile legal when cout-tiling blocks it) and res_ref shares the output
    flat layout — its halo rows, like the output's, are never touched.
    Plan entries per output:
      ("s", (ridx, off, shift, slot))  —
        acc += mask ⊙ (x_refs[ridx][lo+off : hi+off] @ w_ref[slot])
      ("p", ridx, off1, s1, off2, s2, pslot)  —  N-PAIRED taps (r5,
        the MXU K=N=64 attack): two taps sharing an input ref compute as
        ONE dot against their weights stacked along N —
        big = x_refs[ridx][0:nb] @ wp_ref[pslot]        (nb, 2·cout)
        acc += mask1 ⊙ big[lo+off1 : hi+off1, :cout]
             + mask2 ⊙ big[lo+off2 : hi+off2, cout:]
        For cout ≤ 64 stages this doubles MXU lane fill (N 64 → 128) and
        halves the dot count; the row shifts move to the CONSUMING
        slices, which are free sublane slices. The 64-offset lane slice
        is validated on-chip (mosaic_probe pair-dot-laneslice, r5).
    Rows outside [lo, hi) are pad/garbage rows the wrappers slice away —
    they are left unwritten. hi = nb - tail keeps every tap slice inside
    the block, and pair dots read [0, nb) which covers every
    [lo+off, hi+off) by the same invariant.
    """
    x_refs = refs[:n_in]
    w_ref = refs[n_in]
    i = n_in + 1
    wp_ref = None
    if have_pairs:
        wp_ref = refs[i]
        i += 1
    ss_ref = res_ref = None
    if ep is not None:
        ss_ref = refs[i]
        i += 1
        if ep.residual:
            res_ref = refs[i]
            i += 1
    o_refs = refs[i:]
    nb = o_refs[0].shape[0]
    lo_, hi = lo, nb - tail
    masks = _col_masks(
        [[t for e in plan for t in _plan_taps(e)] for plan in plan_per_out],
        w_col, lo_, hi,
    )
    for o_ref, plan in zip(o_refs, plan_per_out):
        cout = o_ref.shape[1]
        acc = None
        for entry in plan:
            if entry[0] == "s":
                ridx, off, shift, slot = entry[1]
                part = lax.dot_general(
                    x_refs[ridx][lo_ + off : hi + off, :],
                    w_ref[slot],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                if shift:
                    part = jnp.where(masks[shift], part, 0.0)
            else:
                _, ridx, off1, s1, off2, s2, pslot = entry
                big = lax.dot_general(
                    x_refs[ridx][:, :],
                    wp_ref[pslot],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                p1 = big[lo_ + off1 : hi + off1, :cout]
                if s1:
                    p1 = jnp.where(masks[s1], p1, 0.0)
                p2 = big[lo_ + off2 : hi + off2, cout:]
                if s2:
                    p2 = jnp.where(masks[s2], p2, 0.0)
                part = p1 + p2
            acc = part if acc is None else acc + part
        if ep is None:
            o_ref[lo_:hi, :] = acc.astype(o_ref.dtype)
            continue
        # Fused epilogue, all on the f32 accumulator before the single
        # HBM write: (1, cout) × (rows, cout) broadcasts are the same
        # rank-2 VPU shape the column masks use (lane-major variant).
        z = acc * ss_ref[0:1, :] + ss_ref[1:2, :]
        if ep.residual:
            z = z + res_ref[lo_:hi, :].astype(jnp.float32)
        if ep.relu:
            z = jnp.maximum(z, 0.0)
        o_ref[lo_:hi, :] = z.astype(o_ref.dtype)
        if ep.emit_preact:
            o_refs[1][lo_:hi, :] = acc.astype(o_refs[1].dtype)


def _wgrad_tap_kernel(taps, w_col, lo, tail, n_in, *refs):
    """gw[slot] += x_refs[ridx][center+off]ᵀ @ (mask ⊙ g[center]),
    accumulated across the sequential batch grid. g's pad rows are zero
    (the wrappers embed dout with zero pads), so only the column-wrap
    contributions need masking."""
    x_refs = refs[:n_in]
    g_ref = refs[n_in]
    gw_ref = refs[n_in + 1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        gw_ref[:] = jnp.zeros_like(gw_ref)

    nb = g_ref.shape[0]
    lo_, hi = lo, nb - tail
    masks = _col_masks((taps,), w_col, lo_, hi)
    g = g_ref[lo_:hi, :]
    g_by_shift = {0: g}
    for s, m in masks.items():
        g_by_shift[s] = jnp.where(m, g, 0.0)
    for ridx, off, shift, slot in taps:
        gw_ref[slot] += lax.dot_general(
            x_refs[ridx][lo_ + off : hi + off, :],
            g_by_shift[shift],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(gw_ref.dtype)


# Observation hook for the static budget verifier (analysis/pallas_budget):
# when set, every block-size decision reports its VMEM model here, so
# `python -m parallel_cnn_tpu check` evaluates the same formula the
# kernels size with — no drift between the lint model and the runtime
# model is possible.  (tag, n, bb, per_img, w_bytes, modeled_bytes).
_budget_observer = None


def _vmem_per_img(
    rows: int,
    cins: Sequence[int],
    tap_cins: Sequence[int],
    couts: Sequence[int],
    esz: int,
    out_esz: int,
    pair_temps: int = 0,
) -> int:
    """Modeled VMEM bytes one image contributes to a pipeline block:
    double-buffered in/out blocks, Mosaic's materialized per-tap slice
    copies (input dtype), f32 accumulator + per-tap dot result, and the
    N-pair packing temporaries (see _pick_bb's docstring for the r5
    accounting notes)."""
    cout = sum(couts)
    return rows * (
        esz * (2 * sum(cins) + sum(tap_cins))
        + out_esz * 2 * cout
        + 4 * 2 * cout
        # N-pair packing (r5): each paired dot materializes a full-rows
        # (nb, 2·cout) f32 `big`; count every pair as simultaneously
        # live (conservative — Mosaic's scoped-stack accounting proved
        # 1.7MB tighter than the pre-pairing model at the stem shape).
        + 4 * 2 * max(couts, default=0) * pair_temps
    )


def _pick_bb(
    n: int,
    rows: int,
    cins: Sequence[int],
    tap_cins: Sequence[int],
    couts: Sequence[int],
    esz: int,
    out_esz: int,
    w_bytes: int,
    pair_temps: int = 0,
    tag: str = "conv",
) -> int:
    """Images per grid step under the VMEM model: double-buffered in/out
    pipeline blocks, Mosaic's materialized per-tap slice copies (input
    dtype), f32 accumulator + per-tap dot result, minus the
    double-buffered weight block. ``tag`` labels the over-budget logs —
    the fused-update kernels (ops/pallas_update.py) size their blocks
    through this same model (their momentum buffer rides in cins/couts,
    charged like any other double-buffered pipeline operand) and get the
    same warning/debug trail.

    Mosaic tiling constraint (r5 on-chip finding — interpret-mode tests
    can't catch it): a block's SUBLANE dim (bb·rows) must be a multiple
    of the dtype's sublane tile — 32/itemsize, i.e. 8 for f32, 16 for
    bf16 — unless the block spans the whole array (bb == n). With odd
    rows (e.g. ResNet-50's 224²-input deep blocks: 9·7 = 63 flat rows
    per image) a VMEM-picked bb of 4 yields a rejected 252-row block.
    The in- and out-blocks share the bb·rows sublane dim at their own
    dtypes, so the strictest (smallest-itemsize) tile governs. Pick the
    largest legal divisor under the VMEM target, else the smallest legal
    one above it (bb == n is always legal)."""
    per_img = _vmem_per_img(
        rows, cins, tap_cins, couts, esz, out_esz, pair_temps
    )
    tile = 32 // min(esz, out_esz)
    legal = [
        d for d in range(1, n + 1)
        if n % d == 0 and ((d * rows) % tile == 0 or d == n)
    ]
    fits = [d for d in legal if d * per_img + 2 * w_bytes <= _VMEM_BUDGET]
    # No legal divisor under the budget (the tiling constraint forces a
    # bigger block, or one image plus its weights already exceeds it):
    # take the smallest legal block and say how far over the model lands.
    # Over budget is fine (the limit leaves headroom) but worth a debug
    # trace; over the hard limit predicts a Mosaic scoped-VMEM OOM and is
    # never silent.
    bb = max(fits) if fits else min(legal)
    modeled = bb * per_img + 2 * w_bytes
    if _budget_observer is not None:
        _budget_observer(tag, n, bb, per_img, w_bytes, modeled)
    if modeled > _VMEM_LIMIT:
        log.warning(
            "pallas %s block bb=%d models %.1fMB VMEM, over the %.0fMB "
            "limit — expect a Mosaic OOM at this shape",
            tag, bb, modeled / 2**20, _VMEM_LIMIT / 2**20,
        )
    elif modeled > _VMEM_BUDGET:
        log.debug(
            "pallas %s block bb=%d models %.1fMB VMEM, over the %.0fMB "
            "budget (no legal block fits it)",
            tag, bb, modeled / 2**20, _VMEM_BUDGET / 2**20,
        )
    return bb


def _compiler_params():
    return None if _interpret() else pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT
    )


def _tapped_matmul(
    x_flats: Sequence[jax.Array],
    w_stack: jax.Array,
    taps_per_out,
    rows_per_img: int,
    w_col: int,
    lo: int,
    tail: int,
    couts: Sequence[int],
    out_dtype,
    *,
    epilogue: Optional[Epilogue] = None,
    ss: Optional[jax.Array] = None,
    res_flat: Optional[jax.Array] = None,
) -> List[jax.Array]:
    """Run the generic forward/dgrad kernel over the batch grid.

    With `epilogue`, `ss` is the (8, cout) f32 scale/shift block and
    `res_flat` (iff epilogue.residual) shares the OUTPUT flat layout;
    outputs become [y] or [y, preact].

    Weight streaming: when every output shares one cout that is a
    strict multiple of `_COUT_TILE` (and the N-pair path is off — that
    path only exists at cout ≤ 64), the grid gains a minor cout-tile
    dimension. The weight blocks walk tiles along it while the x-block
    index map stays constant, so Pallas's grid pipeline prefetches the
    NEXT weight tile during the current tile's dots and skips the x
    re-DMA — double-buffered weight streaming with no kernel-body
    change. `_pick_bb`'s `2·w_bytes` term then counts the two in-flight
    per-tile buffers instead of a resident full stack."""
    n = x_flats[0].shape[0] // rows_per_img
    n_in = len(x_flats)
    cins = [x.shape[1] for x in x_flats]
    tap_cins = [
        cins[ridx] for taps in taps_per_out for (ridx, _, _, _) in taps
    ]
    esz = x_flats[0].dtype.itemsize
    # N-pair packing (r5): only when every output shares one cout ≤ 64 —
    # then two taps ride one K×128 dot (see _tap_kernel's plan docs).
    # Plan before picking bb: the pair temps count in the VMEM model.
    if len(set(couts)) == 1:
        plan_per_out, wp_stack = _build_plan(
            taps_per_out, w_stack, couts[0]
        )
    else:
        plan_per_out = [[("s", t) for t in taps] for taps in taps_per_out]
        wp_stack = None
    have_pairs = wp_stack is not None
    max_pairs = max(
        (sum(1 for e in plan if e[0] == "p") for plan in plan_per_out),
        default=0,
    )
    cout0 = couts[0]
    tile_c = 0
    if (
        _COUT_TILE
        and not have_pairs
        and len(set(couts)) == 1
        and cout0 % _COUT_TILE == 0
        and cout0 > _COUT_TILE
        and w_stack.shape[-1] == cout0
    ):
        tile_c = _COUT_TILE
    lane = tile_c or cout0
    out_couts = list(couts)
    if epilogue is not None and epilogue.emit_preact:
        out_couts = out_couts + [cout0]
    # Both weight stacks ride the grid double-buffered: the paired
    # (wp_stack) bytes count against VMEM exactly like the singles.
    # Under cout tiling only one TILE's bytes is in flight (×2 buffers).
    w_bytes = w_stack.size * w_stack.dtype.itemsize
    if have_pairs:
        w_bytes += wp_stack.size * wp_stack.dtype.itemsize
    if tile_c:
        w_bytes = (w_bytes * tile_c) // cout0
    if epilogue is not None:
        w_bytes += 8 * lane * 4  # the (8, lane) f32 scale/shift block
    model_cins = list(cins)
    if res_flat is not None:
        model_cins.append(lane)  # residual rides the input pipeline
    bb = _pick_bb(
        n, rows_per_img, model_cins, tap_cins,
        [lane] * len(out_couts),
        esz,
        jnp.dtype(out_dtype).itemsize,
        w_bytes,
        pair_temps=max_pairs,
    )
    w_inputs = [w_stack] + ([wp_stack] if have_pairs else [])
    extras = []
    extra_specs = []
    if tile_c:
        nct = cout0 // tile_c
        grid = (n // bb, nct)  # minor dim last → weight tiles stream
        x_map = lambda g, j: (g, 0)  # noqa: E731 — constant along j
        out_map = lambda g, j: (g, j)  # noqa: E731
        w_specs = [
            pl.BlockSpec(
                w.shape[:-1] + (tile_c,),
                lambda g, j, nd=w.ndim: (0,) * (nd - 1) + (j,),
                memory_space=pltpu.VMEM,
            )
            for w in w_inputs
        ]
        ss_spec = pl.BlockSpec((8, tile_c), lambda g, j: (0, j),
                               memory_space=pltpu.VMEM)
    else:
        grid = (n // bb,)
        x_map = lambda g: (g, 0)  # noqa: E731
        out_map = lambda g: (g, 0)  # noqa: E731
        w_specs = [
            pl.BlockSpec(w.shape, lambda g, nd=w.ndim: (0,) * nd,
                         memory_space=pltpu.VMEM)
            for w in w_inputs
        ]
        ss_spec = pl.BlockSpec((8, cout0), lambda g: (0, 0),
                               memory_space=pltpu.VMEM)
    if epilogue is not None:
        extras.append(ss)
        extra_specs.append(ss_spec)
        if epilogue.residual:
            extras.append(res_flat)
            extra_specs.append(
                pl.BlockSpec((bb * rows_per_img, lane), out_map,
                             memory_space=pltpu.VMEM)
            )
    outs = pl.pallas_call(
        functools.partial(
            _tap_kernel, plan_per_out, w_col, lo, tail, n_in, have_pairs,
            epilogue,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (bb * rows_per_img, c), x_map,
                memory_space=pltpu.VMEM,
            )
            for c in cins
        ] + w_specs + extra_specs,
        out_specs=[
            pl.BlockSpec(
                (bb * rows_per_img, tile_c or c), out_map,
                memory_space=pltpu.VMEM,
            )
            for c in out_couts
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * rows_per_img, c), out_dtype)
            for c in out_couts
        ],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
    )(*x_flats, *w_inputs, *extras)
    return outs


def _tapped_wgrad(
    x_flats: Sequence[jax.Array],
    g_flat: jax.Array,
    taps,
    rows_per_img: int,
    w_col: int,
    lo: int,
    tail: int,
    n_slots: int,
) -> jax.Array:
    n = g_flat.shape[0] // rows_per_img
    n_in = len(x_flats)
    cins = [x.shape[1] for x in x_flats]
    cout = g_flat.shape[1]
    cin = cins[0]
    tap_cins = [cins[r] for (r, _, _, _) in taps]
    # VMEM model note: g appears in BOTH the input list (cins + [cout])
    # and the f32-accumulator term ([cout]) — in wgrad g is an input, so
    # the [cout] accumulator it models does not exist. The overcount is
    # intentional slack (picks a smaller bb than strictly needed, never a
    # too-large one); round-4 advisor finding, kept as-is by choice.
    bb = _pick_bb(
        n, rows_per_img, cins + [cout], tap_cins, [cout],
        x_flats[0].dtype.itemsize, 4,
        n_slots * cin * cout * 4,
    )
    return pl.pallas_call(
        functools.partial(_wgrad_tap_kernel, taps, w_col, lo, tail, n_in),
        grid=(n // bb,),
        in_specs=[
            pl.BlockSpec(
                (bb * rows_per_img, c), lambda g: (g, 0),
                memory_space=pltpu.VMEM,
            )
            for c in cins
        ] + [
            pl.BlockSpec(
                (bb * rows_per_img, cout), lambda g: (g, 0),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (n_slots, cin, cout), lambda g: (0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, cin, cout), jnp.float32),
        interpret=_interpret(),
        compiler_params=_compiler_params(),
    )(*x_flats, g_flat)


# ---------------------------------------------------------------------------
# Tap geometry. All wrappers express their taps as (ref, a_off, b_off):
# a vertical pixel offset, a horizontal pixel offset, against a flat
# per-image layout of ((T_top + H + T_bot)·W) rows. _layout sizes the
# zero-pad rows from the tap reach so (a) every in-kernel slice stays
# inside the block and (b) semantically-zero reads (SAME padding rows)
# land on physical zero rows; column validity is the kernel's mask.
# ---------------------------------------------------------------------------


def _layout(h: int, w: int, flat_offs: Sequence[int]):
    """(rows_per_img, top_pad_rows, lo, tail) for a tap-offset set."""
    t_top = max(0, -(min(flat_offs) // w))  # ceil(-min/w) for min<0
    t_bot = max(0, -((-max(flat_offs)) // w))  # ceil(max/w)
    rows = (t_top + h + t_bot) * w
    return rows, t_top, t_top * w, t_bot * w


def _flatten_padded(x: jax.Array, t_top: int, t_bot: int) -> jax.Array:
    b, h, w, c = x.shape
    if t_top or t_bot:
        x = jnp.pad(x, ((0, 0), (t_top, t_bot), (0, 0), (0, 0)))
    return x.reshape(b * (h + t_top + t_bot) * w, c)


def _bands(h: int, rows_single: int, t_top: int, t_bot: int,
           w_col: int) -> List[Tuple[int, int]]:
    """Split output H-rows [0, h) into bands whose flat layouts stay
    under _MAX_ROWS_PER_IMG (Mosaic compile time scales with
    taps × rows — the 224² stem pathology). Bands are ceil-equal so at
    most two distinct kernel shapes compile."""
    if rows_single <= _MAX_ROWS_PER_IMG:
        return [(0, h)]
    cap_h = max(1, _MAX_ROWS_PER_IMG // w_col - t_top - t_bot)
    n_bands = -(-h // cap_h)
    hb = -(-h // n_bands)
    return [(r0, min(r0 + hb, h)) for r0 in range(0, h, hb)]


def _flatten_band(x: jax.Array, r0: int, r1: int, t_top: int,
                  t_bot: int) -> jax.Array:
    """Flat rows for output band [r0, r1): input H-rows
    [r0−t_top, r1+t_bot) with REAL interior halo rows and zero pads only
    outside the image — so for the full band (0, h) this IS
    _flatten_padded, and interior band edges read true neighbor pixels
    (exactness; column wrap stays the kernel mask's job)."""
    b, h, w, c = x.shape
    lo = r0 - t_top
    hi = r1 + t_bot
    pt, pb = max(0, -lo), max(0, hi - h)
    xs = x[:, max(lo, 0):min(hi, h)]
    if pt or pb:
        xs = jnp.pad(xs, ((0, 0), (pt, pb), (0, 0), (0, 0)))
    return xs.reshape(b * (hi - lo) * w, c)


def _banded_matmul(
    x_list: Sequence[jax.Array],
    w_stack: jax.Array,
    taps_per_out,
    h: int,
    wd: int,
    t_top: int,
    t_bot: int,
    couts: Sequence[int],
    out_dtype,
    *,
    epilogue: Optional[Epilogue] = None,
    ss: Optional[jax.Array] = None,
    res: Optional[jax.Array] = None,
) -> List[jax.Array]:
    """Run _tapped_matmul over row bands of the (phase-)images in
    x_list; returns per-output (b, h', wd, cout) arrays with the pad
    rows sliced away and bands concatenated along H."""
    b = x_list[0].shape[0]
    rows_single = (t_top + h + t_bot) * wd
    parts = []
    for r0, r1 in _bands(h, rows_single, t_top, t_bot, wd):
        hb = r1 - r0
        rows = (t_top + hb + t_bot) * wd
        outs = _tapped_matmul(
            [_flatten_band(x, r0, r1, t_top, t_bot) for x in x_list],
            w_stack, taps_per_out, rows, wd, t_top * wd, t_bot * wd,
            couts, out_dtype,
            epilogue=epilogue, ss=ss,
            res_flat=(
                None if res is None
                else _flatten_band(res, r0, r1, t_top, t_bot)
            ),
        )
        parts.append([
            o.reshape(b, rows // wd, wd, o.shape[1])[:, t_top:t_top + hb]
            for o in outs
        ])
    if len(parts) == 1:
        return parts[0]
    return [jnp.concatenate(ps, axis=1) for ps in zip(*parts)]


def _flatten_band_zero(x: jax.Array, r0: int, r1: int, t_top: int,
                       t_bot: int) -> jax.Array:
    """Band flattening with ZERO halo rows (vs _flatten_band's real
    ones): the cotangent side of banded wgrad. _wgrad_tap_kernel's
    center slice spans every image in a multi-image block, interior
    pad rows included — its correctness invariant is that g is zero
    there, which real-data halos would break (each band's weight-grad
    contribution is the sum over THAT band's output rows only)."""
    b, h, w, c = x.shape
    xs = x[:, r0:r1]
    if t_top or t_bot:
        xs = jnp.pad(xs, ((0, 0), (t_top, t_bot), (0, 0), (0, 0)))
    return xs.reshape(b * (r1 - r0 + t_top + t_bot) * w, c)


def _banded_wgrad(
    x_list: Sequence[jax.Array],
    g: jax.Array,
    taps,
    h: int,
    wd: int,
    t_top: int,
    t_bot: int,
    n_slots: int,
) -> jax.Array:
    """Per-band _tapped_wgrad calls summed in f32 — bands partition g's
    center rows exactly, so the per-band weight grads add. x bands carry
    real interior halos (the tap reads are data); g bands carry ZERO
    halos (the kernel's pad-rows-are-zero invariant)."""
    rows_single = (t_top + h + t_bot) * wd
    gw = None
    for r0, r1 in _bands(h, rows_single, t_top, t_bot, wd):
        hb = r1 - r0
        rows = (t_top + hb + t_bot) * wd
        part = _tapped_wgrad(
            [_flatten_band(x, r0, r1, t_top, t_bot) for x in x_list],
            _flatten_band_zero(g, r0, r1, t_top, t_bot),
            taps, rows, wd, t_top * wd, t_bot * wd, n_slots,
        )
        gw = part if gw is None else gw + part
    return gw


def _s1_taps(k: int, w: int):
    """Stride-1 tap set for odd k: (a_off, b_off) = (dy-p, dx-p)."""
    p = (k - 1) // 2
    return [
        (dy - p, dx - p, dy * k + dx) for dy in range(k) for dx in range(k)
    ]


def _s2_phase_taps(k: int, inverse: bool = False):
    """Stride-2 tap set (even dims): tap (dy,dx) → phase + offsets.

    XLA's SAME stride-2 placement for even dims puts pad_lo = (k-2)//2
    zero rows/cols before the image, i.e. out[o] is centered so the tap
    reads u = 2o + d - pad_lo. Phase = u parity; offset = (d-pl-phase)/2.
    `inverse` derives dgrad's mapping: output-phase p takes taps with
    d ≡ p + pl (mod 2) at offset -(…) — returned as (out_phase, a, b,
    slot) tuples instead.
    """
    pl_ = (k - 2) // 2
    taps = []
    for dy in range(k):
        for dx in range(k):
            slot = dy * k + dx
            if not inverse:
                py, ay = (dy - pl_) % 2, (dy - pl_ - (dy - pl_) % 2) // 2
                px, ax = (dx - pl_) % 2, (dx - pl_ - (dx - pl_) % 2) // 2
                taps.append((py * 2 + px, ay, ax, slot))
            else:
                # dx_phase (p,q) ← taps with dy ≡ p+pl, dx ≡ q+pl (mod 2)
                py = (dy + pl_) % 2
                px = (dx + pl_) % 2
                ay = -((dy - pl_ - ((dy - pl_) % 2)) // 2)
                ax = -((dx - pl_ - ((dx - pl_) % 2)) // 2)
                taps.append((py * 2 + px, ay, ax, slot))
    return taps


def _phases(x: jax.Array) -> List[jax.Array]:
    return [x[:, p::2, q::2, :] for p in (0, 1) for q in (0, 1)]


def _conv_s1(x: jax.Array, w: jax.Array, epilogue=None, ss=None,
             res=None) -> List[jax.Array]:
    b, h, wd, cin = x.shape
    k, cout = w.shape[0], w.shape[3]
    taps_ab = _s1_taps(k, wd)
    flat_offs = [a * wd + bo for a, bo, _ in taps_ab]
    _, t_top, _, tail = _layout(h, wd, flat_offs)
    taps = tuple(
        (0, a * wd + bo, bo, slot) for (a, bo, slot) in taps_ab
    )
    return _banded_matmul(
        [x], w.reshape(k * k, cin, cout).astype(x.dtype), (taps,),
        h, wd, t_top, tail // wd, [cout], x.dtype,
        epilogue=epilogue, ss=ss, res=res,
    )


def _dgrad_s1(g: jax.Array, w: jax.Array) -> jax.Array:
    """dx[a,b] = Σ_t W[dy,dx]·g[a−(dy−p), b−(dx−p)]: same kernel with
    negated offsets, transposed tap weights."""
    b, h, wd, cout = g.shape
    k, cin = w.shape[0], w.shape[2]
    taps_ab = [(-a, -bo, slot) for (a, bo, slot) in _s1_taps(k, wd)]
    flat_offs = [a * wd + bo for a, bo, _ in taps_ab]
    _, t_top, _, tail = _layout(h, wd, flat_offs)
    taps = tuple((0, a * wd + bo, bo, slot) for (a, bo, slot) in taps_ab)
    wt = w.reshape(k * k, cin, cout).transpose(0, 2, 1).astype(g.dtype)
    return _banded_matmul(
        [g], wt, (taps,), h, wd, t_top, tail // wd, [cin], g.dtype,
    )[0]


def _wgrad_s1(x: jax.Array, g: jax.Array, k: int) -> jax.Array:
    b, h, wd, cin = x.shape
    cout = g.shape[3]
    taps_ab = _s1_taps(k, wd)
    flat_offs = [a * wd + bo for a, bo, _ in taps_ab]
    _, t_top, _, tail = _layout(h, wd, flat_offs)
    taps = tuple((0, a * wd + bo, bo, slot) for (a, bo, slot) in taps_ab)
    gw = _banded_wgrad([x], g, taps, h, wd, t_top, tail // wd, k * k)
    return gw.reshape(k, k, cin, cout)


def _conv_s2_even(x: jax.Array, w: jax.Array, epilogue=None, ss=None,
                  res=None) -> List[jax.Array]:
    b, h, wd, cin = x.shape
    k, cout = w.shape[0], w.shape[3]
    hh, wh = h // 2, wd // 2
    taps_pab = _s2_phase_taps(k)
    flat_offs = [a * wh + bo for _, a, bo, _ in taps_pab]
    _, t_top, _, tail = _layout(hh, wh, flat_offs)
    taps = tuple(
        (ph, a * wh + bo, bo, slot) for (ph, a, bo, slot) in taps_pab
    )
    return _banded_matmul(
        _phases(x), w.reshape(k * k, cin, cout).astype(x.dtype), (taps,),
        hh, wh, t_top, tail // wh, [cout], x.dtype,
        epilogue=epilogue, ss=ss, res=res,
    )


def _dgrad_s2_even(g, w, h: int, wd: int) -> jax.Array:
    """The four dx phases each take the tap subset with matching parity:
    one kernel call, one pass over dout, four output refs."""
    b = g.shape[0]
    k, cin, cout = w.shape[0], w.shape[2], w.shape[3]
    hh, wh = h // 2, wd // 2
    inv = _s2_phase_taps(k, inverse=True)
    flat_offs = [a * wh + bo for _, a, bo, _ in inv]
    _, t_top, _, tail = _layout(hh, wh, flat_offs)
    taps_per_out = tuple(
        tuple(
            (0, a * wh + bo, bo, slot)
            for (ph, a, bo, slot) in inv
            if ph == out_phase
        )
        for out_phase in range(4)
    )
    wt = w.reshape(k * k, cin, cout).transpose(0, 2, 1).astype(g.dtype)
    ps = _banded_matmul(
        [g], wt, taps_per_out, hh, wh, t_top, tail // wh,
        [cin] * 4, g.dtype,
    )
    # Interleave phases back: columns then rows (pure XLA relayout).
    row0 = jnp.stack([ps[0], ps[1]], axis=3).reshape(b, hh, wd, cin)
    row1 = jnp.stack([ps[2], ps[3]], axis=3).reshape(b, hh, wd, cin)
    return jnp.stack([row0, row1], axis=2).reshape(b, h, wd, cin)


def _wgrad_s2_even(x: jax.Array, g: jax.Array, k: int) -> jax.Array:
    b, h, wd, cin = x.shape
    cout = g.shape[3]
    hh, wh = h // 2, wd // 2
    taps_pab = _s2_phase_taps(k)
    flat_offs = [a * wh + bo for _, a, bo, _ in taps_pab]
    _, t_top, _, tail = _layout(hh, wh, flat_offs)
    taps = tuple(
        (ph, a * wh + bo, bo, slot) for (ph, a, bo, slot) in taps_pab
    )
    gw = _banded_wgrad(
        _phases(x), g, taps, hh, wh, t_top, tail // wh, k * k,
    )
    return gw.reshape(k, k, cin, cout)


# ---------------------------------------------------------------------------
# 1×1 convs: plain matmuls. Stride 2 subsamples FIRST (exact for SAME
# k=1 at any parity: out[o] = x[2o]), so no stride waste exists at all.
# ---------------------------------------------------------------------------


def _conv_1x1(x: jax.Array, w: jax.Array, epilogue=None, ss=None,
              res=None) -> List[jax.Array]:
    b, h, wd, cin = x.shape
    cout = w.shape[3]
    return _banded_matmul(
        [x], w.reshape(1, cin, cout).astype(x.dtype),
        (((0, 0, 0, 0),),),
        h, wd, 0, 0, [cout], x.dtype,
        epilogue=epilogue, ss=ss, res=res,
    )


def _wgrad_1x1(x: jax.Array, g: jax.Array) -> jax.Array:
    b, h, wd, cin = x.shape
    cout = g.shape[3]
    gw = _banded_wgrad([x], g, ((0, 0, 0, 0),), h, wd, 0, 0, 1)
    return gw.reshape(1, 1, cin, cout)


def _s2_offsets(h: int, w: int, k: int) -> Tuple[int, int]:
    """Subsample phase matching XLA's SAME stride-2 window placement.

    XLA splits SAME padding as pad_lo = pad_total // 2; for k=3 an
    even-sized dim gets pad_total=1 → pad_lo=0, so output o is centered
    at 2o+1 — phase 1 of the (symmetrically padded) stride-1 output. Odd
    dims (and all k=1 cases) get phase 0.
    """
    if k == 1:
        return 0, 0
    return (1 if h % 2 == 0 else 0), (1 if w % 2 == 0 else 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def conv2d(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    """SAME conv via the Pallas tapped-matmul kernels; stride ∈ {1, 2},
    odd k ∈ {1, 3, 5, 7}."""
    return _forward(x, w, stride)


def _forward(x, w, stride):
    k = w.shape[0]
    if k == 1:
        if stride == 2:
            x = x[:, ::2, ::2, :]
        return _conv_1x1(x, w)[0]
    if stride == 1:
        return _conv_s1(x, w)[0]
    if x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
        return _conv_s2_even(x, w)[0]
    # Odd spatial dims at stride 2 (no zoo model hits this): stride-1 +
    # subsample at XLA's window phase. k-generic: for SAME padding with
    # odd k, pad_top(stride1) − pad_top(stride2) is 0 on odd dims and 1
    # on even dims for EVERY odd k ≥ 3 (pad_total is k−1 vs k−1 / k−2),
    # which is exactly _s2_offsets' per-dim formula — so the fallback
    # covers k ∈ {3, 5, 7} alike (closes the supports()/apply gap the
    # round-4 advisor flagged: supports() said yes for k>3 stride-2 but
    # this path raised on odd dims).
    o = _conv_s1(x, w)[0]
    oy, ox = _s2_offsets(x.shape[1], x.shape[2], k)
    return o[:, oy::2, ox::2, :]


def _conv2d_fwd(x, w, stride):
    return _forward(x, w, stride), (x, w)


def _conv2d_bwd(stride, res, g):
    x, w = res
    b, h, wd, cin = x.shape
    k = w.shape[0]
    cout = w.shape[3]
    if k == 1:
        if stride == 2:
            xs = x[:, ::2, ::2, :]
            dxs = _conv_1x1(g, w.transpose(0, 1, 3, 2))[0]
            dx = (
                jnp.zeros((b, h, wd, cin), x.dtype)
                .at[:, ::2, ::2, :]
                .set(dxs.astype(x.dtype))
            )
            gw = _wgrad_1x1(xs, g)
        else:
            dx = _conv_1x1(g, w.transpose(0, 1, 3, 2))[0]
            gw = _wgrad_1x1(x, g)
        return dx.astype(x.dtype), gw.astype(w.dtype)
    if stride == 2 and h % 2 == 0 and wd % 2 == 0:
        dx = _dgrad_s2_even(g, w, h, wd)
        gw = _wgrad_s2_even(x, g, k)
        return dx.astype(x.dtype), gw.astype(w.dtype)
    if stride == 2:
        # Odd-dim fallback (k-generic): scatter dout onto the stride-1
        # grid at the forward's phase, then stride-1 grads.
        oy, ox = _s2_offsets(h, wd, k)
        gfull = jnp.zeros((b, h, wd, cout), g.dtype)
        g = gfull.at[:, oy::2, ox::2, :].set(g)
    dx = _dgrad_s1(g, w)
    gw = _wgrad_s1(x, g, k)
    return dx.astype(x.dtype), gw.astype(w.dtype)


conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


# ---------------------------------------------------------------------------
# Fused conv epilogue (ISSUE 2 tentpole): relu?(conv·scale + shift
# [+ residual]) in ONE kernel pass — the elementwise tail rides the f32
# accumulator in VMEM instead of three-to-four extra HBM round-trips.
# ---------------------------------------------------------------------------


def _make_ss(scale: jax.Array, shift: jax.Array) -> jax.Array:
    """(8, cout) f32 scale/shift block: row 0 scale, row 1 shift. Eight
    rows keep the f32 sublane tile legal when cout-tiling blocks it."""
    cout = scale.shape[0]
    ss = jnp.zeros((8, cout), jnp.float32)
    return (
        ss.at[0].set(scale.astype(jnp.float32))
        .at[1].set(shift.astype(jnp.float32))
    )


def _fused_forward(x, w, scale, shift, residual, stride, relu,
                   want_preact):
    """Dispatch conv2d_fused over the same geometry split as _forward;
    returns (y, preact-or-None)."""
    k = w.shape[0]
    ep = Epilogue(
        relu=relu,
        residual=residual is not None,
        emit_preact=want_preact,
    )
    ss = _make_ss(scale, shift)
    if k == 1:
        xs = x[:, ::2, ::2, :] if stride == 2 else x
        outs = _conv_1x1(xs, w, epilogue=ep, ss=ss, res=residual)
    elif stride == 1:
        outs = _conv_s1(x, w, epilogue=ep, ss=ss, res=residual)
    elif x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
        outs = _conv_s2_even(x, w, epilogue=ep, ss=ss, res=residual)
    else:
        # Odd-dim stride-2 (outside every zoo model): conv in-kernel via
        # the stride-1 fallback, epilogue in XLA — still one conv pass.
        c = _forward(x, w, stride)
        z = c.astype(jnp.float32) * scale.astype(jnp.float32)
        z = z + shift.astype(jnp.float32)
        if residual is not None:
            z = z + residual.astype(jnp.float32)
        if relu:
            z = jnp.maximum(z, 0.0)
        return z.astype(x.dtype), (c if want_preact else None)
    if want_preact:
        return outs[0], outs[1]
    return outs[0], None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def conv2d_fused(
    x: jax.Array,
    w: jax.Array,
    scale: jax.Array,
    shift: jax.Array,
    residual: Optional[jax.Array] = None,
    stride: int = 1,
    relu: bool = True,
) -> jax.Array:
    """``relu?(conv2d(x, w, stride)·scale + shift [+ residual])`` with
    the whole elementwise tail fused into the conv kernel's output
    block (≙ the reference CUDA kernels' fused bias+activation).

    scale/shift are per-channel f32 — fold inference-mode BN as
    ``scale = γ·rsqrt(var+ε)``, ``shift = β − mean·scale``. residual
    (optional) must have the conv OUTPUT shape. The primal pays exactly
    one HBM write; under `jax.grad` the fwd rule additionally saves the
    raw conv output so the bwd rule can rebuild the ReLU mask and route
    the conv cotangent through the existing `_conv2d_bwd` kernels, with
    residual grads passing straight through."""
    y, _ = _fused_forward(x, w, scale, shift, residual, stride, relu,
                          False)
    return y


def _conv2d_fused_fwd(x, w, scale, shift, residual, stride, relu):
    y, c = _fused_forward(x, w, scale, shift, residual, stride, relu,
                          True)
    return y, (x, w, scale, shift, residual, c)


def _conv2d_fused_bwd(stride, relu, saved, g):
    x, w, scale, shift, residual, c = saved
    cf = c.astype(jnp.float32)
    s = scale.astype(jnp.float32)
    z = cf * s + shift.astype(jnp.float32)
    if residual is not None:
        z = z + residual.astype(jnp.float32)
    gz = g.astype(jnp.float32)
    if relu:
        # where(z > 0): zero subgradient at z == 0, matching
        # jax.nn.relu's custom JVP (the unfused reference composition).
        gz = jnp.where(z > 0, gz, 0.0)
    d_shift = jnp.sum(gz, axis=(0, 1, 2)).astype(shift.dtype)
    d_scale = jnp.sum(gz * cf, axis=(0, 1, 2)).astype(scale.dtype)
    g_c = (gz * s).astype(x.dtype)
    dx, dw = _conv2d_bwd(stride, (x, w), g_c)
    d_res = None if residual is None else gz.astype(residual.dtype)
    return dx, dw, d_scale, d_shift, d_res


conv2d_fused.defvjp(_conv2d_fused_fwd, _conv2d_fused_bwd)


def supports(kernel: Tuple[int, int], strides: Tuple[int, int], padding: str) -> bool:
    """Shapes this kernel library covers; Conv2D(backend="pallas") raises
    on anything else."""
    return (
        kernel in ((1, 1), (3, 3), (5, 5), (7, 7))
        and kernel[0] == kernel[1]
        and strides in ((1, 1), (2, 2))
        and padding == "SAME"
    )
