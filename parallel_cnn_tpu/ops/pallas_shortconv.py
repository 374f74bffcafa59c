"""A short causal depthwise convolution with its SiLU, the L2 norm of a
head's features and a scale as one fused TPU kernel a direction
(Pallas/Mosaic): for head-major `x (N, H, S, D)`, `taps (K, H, D)` float32,
a flag `unit` and a static `scale`,

    c[t] = sum_i taps[i] * x[t - (K - 1) + i]     (x read as 0 before 0)
    a    = c * sigmoid(c)
    y    = scale * a * rsqrt(sum_D a^2 + 1e-6)    (unit)  |  scale * a

everything in float32 in VMEM from `x`'s block, rounded ONCE to `x.dtype`
— what `nn/bailing_hybrid.py:KDA` composes from `layers.causal_conv`,
`jax.nn.silu` and `_unit`, which round after the convolution, in the
SiLU, after the norm and after the scale. Compiled for the chip, that
composition's backward was autodiff's transpose of a pad, four slices and
a sum over float32 copies of the array: some sixty passes over it, 10.4
ms a layer for bytes that cross HBM in 0.74 (PERF.md section 6, PR 46).

  forward   grid (N, head blocks, position tiles), all parallel. A grid
            step takes `(hb, ts, D)` of `x` and, as a second view of `x`,
            the `HALO` rows before the tile (its index map clamped at 0;
            the first tile reads them as 0): the `K - 1` positions a tile's
            first rows reach back to. Each element of `x` is read once
            (plus `HALO / ts` of it again) and each of `y` written once.
  backward  ONE kernel, the same grid walked from the LAST position tile
            (the index maps reverse it; that axis is sequential). It
            makes `c`, `sigmoid(c)`, `a` and the norm again from `x` —
            nothing of `x`'s size is kept: the residuals are `x` and
            `taps` — and takes the cotangent back through them:

                da = scale * r * (dy - yh * sum_D dy yh)    yh = a r  (unit)
                dc = da * s * (1 + c * (1 - s))             s = sigmoid(c)
                dx[t]    = sum_i taps[i] * dc[t + (K - 1) - i]
                dtaps[i] = sum_{n,t} dc[t] * x[t - (K - 1) + i]

            `dx` reaches `K - 1` positions AHEAD: the first rows of `dc` of
            the tile after this one, which the previous grid step left in
            a VMEM scratch (zeros behind the last tile). `dtaps` is
            accumulated in float32 in an output block that stays in VMEM
            along the position axis — eight partial sums a (head, tap), one
            a sublane, so that a tile adds whole registers and never
            reduces across sublanes — and is written once a (sequence,
            head block); the eight, and the sequences, are summed outside
            (0.5 MB). HBM sees `x` and `dy` once in and `dx` once out.

The rows a tile borrows are 8 float32 sublanes beside it (`_views`): `K -
1 <= 8`. Inside a tile a head is worked `CHUNK` rows at a time (a chunk
borrows from its neighbour as a tile does from the halo or the scratch);
the chunks are unrolled, the heads of a block are a `lax.fori_loop`: with
the heads unrolled too a kernel's body was 32 copies of a chunk's, and
the three signatures of a layer added a third to the tracing of the
cell's step, which is set-up's time (PERF.md section 6, PR 46).
`forward`, `backward` and `either` are `jax.jit`s of their own: a model
calls each three times a layer with one or two signatures, and jit's
caches make that one trace a process (PERF.md section 6, PR 37 and PR 42).

Which path runs is decided by what the code can see, never by an option:
`tile(S, D, K)` gives the position tile for shapes the kernels take — `D`
whole 128-lane registers (the norm's sum is over a block's lanes), `S` a
whole number of tiles, `K - 1` rows within the borrowed eight — and None
otherwise; where the shapes tile, the platform is decided where the
program is LOWERED (`lax.platform_dependent` in `either`): the kernels for
a TPU, the caller's plain composition and autodiff of it for anything else.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows of the view of `x` ahead of a tile: one whole sublane tile of bf16
# (two of float32), of which the last `EDGE` are read.
HALO = 16
EDGE = 8
# Heads a block: 8 x 512 x 128 is 1 MB of bf16 a block; the backward holds
# three such arrays double-buffered and works a head at a time in float32.
HEADS = 8
L2_EPS = 1e-6  # nn/bailing_hybrid.py's
NAME = "short_conv"  # the kernels are NAME_fwd and NAME_bwd in a program's text
VMEM_LIMIT_BYTES = 48 << 20
# Rows worked at a time inside a tile: few enough that a chunk's float32
# intermediates stay in registers.
CHUNK = 128


def tile(s: int, d: int, k: int) -> Optional[int]:
    """The positions a block of the kernels holds for `s` positions of `d`
    features under `k` taps, or None where they do not take the shapes."""
    if d % LANES or not 1 <= k <= EDGE + 1:
        return None
    return next((ts for ts in (512, 256, 128) if s % ts == 0), None)


def core(s: int, d: int, k: int, platform: str) -> str:
    """What runs the shapes in a program lowered for `platform`: `"pallas"`
    (these kernels: a TPU and shapes they take) or `"xla"` (the caller's
    plain composition)."""
    return "pallas" if platform == "tpu" and tile(s, d, k) else "xla"


def _views(x, edge, shifts):
    """`x (T, D)` moved `by` rows down its first axis, for each `by` of
    `shifts`: row `t` holds `x[t - by]`, and where that leaves `x` the row
    of `edge (EDGE, D)` that lies there — the rows before `x` if the
    shifts are positive, the rows after it if they are negative. One
    sublane rotation of `x` and `edge` joined (whole registers), of which
    the rows that wrapped are the ones dropped: on the chip a third faster
    than an unaligned slice of the same join (PERF.md section 6, PR 46)."""
    rows = x.shape[0]
    after = min(shifts) < 0
    joined = jnp.concatenate([x, edge] if after else [edge, x])
    first = 0 if after else EDGE
    return [x if by == 0 else
            pltpu.roll(joined, by % (rows + EDGE), 0)[first:first + rows]
            for by in shifts]


def _sigmoid(c):
    """`1 / (1 + e^-c)` of a float32 array, the division as the unit's
    approximate reciprocal and one Newton step (float32-exact: the error
    is squared), `e^-c` held under float32's range so that the step never
    meets an infinity: three operations where a division is a dozen, a
    tenth of a forward call on the chip (PERF.md section 6, PR 46)."""
    d = 1.0 + jnp.exp(jnp.minimum(-c, 80.0))
    r = pl.reciprocal(d, approx=True)
    return r * (2.0 - d * r)


def _activated(x, before, taps, *, unit: bool, scale: float):
    """One head's tile forward, float32: `x (T, D)`, the `EDGE` rows
    `before` it, `taps` a list of K `(1, D)` rows. Returns the K shifted
    views of `x`, `c`, `sigmoid(c)`, `a` and the factor `(T, 1)` (or the
    scale) that makes `y` of `a`."""
    k = len(taps)
    views = _views(x, before, [k - 1 - i for i in range(k)])
    c = taps[0] * views[0]
    for w, view in zip(taps[1:], views[1:]):
        c = c + w * view
    s = _sigmoid(c)
    a = c * s
    if not unit:
        return views, c, s, a, scale
    r = lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
    return views, c, s, a, r * scale


def _rows(ref, h: int, lo: int, hi: int):
    """Rows `[lo, hi)` of head `h` of a block, float32."""
    return ref[0, h, lo:hi].astype(jnp.float32)


def _before(x_ref, halo_ref, h: int, lo: int, first):
    """The `EDGE` rows ahead of row `lo` of head `h`'s tile, float32: the
    tile's own, or (`lo` 0) the halo's, zeros where the tile is the
    sequence's `first`."""
    if lo:
        return _rows(x_ref, h, lo - HALO, lo)[HALO - EDGE:]
    return jnp.where(first, 0.0, _rows(halo_ref, h, HALO - EDGE, HALO))


def _fwd_kernel(x_ref, halo_ref, taps_ref, y_ref, *, unit: bool, scale: float):
    first = pl.program_id(2) == 0
    _, heads, ts, _ = x_ref.shape
    step = min(CHUNK, ts)

    def head(h, _):
        taps = [taps_ref[i, h] for i in range(taps_ref.shape[0])]
        for lo in range(0, ts, step):
            *_, a, factor = _activated(
                _rows(x_ref, h, lo, lo + step),
                _before(x_ref, halo_ref, h, lo, first), taps,
                unit=unit, scale=scale)
            y_ref[0, h, lo:lo + step] = (a * factor).astype(y_ref.dtype)

    lax.fori_loop(0, heads, head, None)  # one traced body (module docstring)


def _bwd_kernel(x_ref, halo_ref, taps_ref, dy_ref, dx_ref, dtaps_ref, ahead_ref,
                *, unit: bool, scale: float):
    j = pl.program_id(2)
    first = j == pl.num_programs(2) - 1  # the walk ends at the first tile

    @pl.when(j == 0)
    def _():
        ahead_ref[...] = jnp.zeros_like(ahead_ref)
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    k = taps_ref.shape[0]
    _, heads, ts, d = x_ref.shape
    step = min(CHUNK, ts)

    def head(h, _):
        taps = [taps_ref[i, h] for i in range(k)]
        after, sums = ahead_ref[h], [0.0] * k
        for lo in range(ts - step, -1, -step):
            views, c, s, a, factor = _activated(
                _rows(x_ref, h, lo, lo + step),
                _before(x_ref, halo_ref, h, lo, first), taps,
                unit=unit, scale=scale)
            da = _rows(dy_ref, h, lo, lo + step)
            if unit:
                yh = a * (factor * (1.0 / scale))
                da = da - yh * jnp.sum(da * yh, axis=-1, keepdims=True)
            dc = (da * factor) * (s * (1.0 + c * (1.0 - s)))
            ahead = _views(dc, after, [i + 1 - k for i in range(k)])
            dx = taps[0] * ahead[0]
            for w, view in zip(taps[1:], ahead[1:]):
                dx = dx + w * view
            dx_ref[0, h, lo:lo + step] = dx.astype(dx_ref.dtype)
            # a sum a sublane: whole registers added, no sublane reduced
            sums = [acc + jnp.sum((dc * view).reshape(-1, EDGE, d), axis=0)
                    for acc, view in zip(sums, views)]
            after = dc[:EDGE]
        ahead_ref[h] = after
        for i, acc in enumerate(sums):
            dtaps_ref[0, i, h] += acc

    lax.fori_loop(0, heads, head, None)


def _specs(x, k: int, ts: int, back: bool):
    """(grid, the specs of an `x`-shaped array, the view of `x` ahead of a
    tile and the taps; the tile walked from the last if `back`) for `x (N,
    H, S, D)`."""
    n, heads, s, d = x.shape
    hb = max(h for h in range(1, HEADS + 1) if heads % h == 0)
    tiles = s // ts
    at = (lambda j: tiles - 1 - j) if back else (lambda j: j)
    block = pl.BlockSpec((1, hb, ts, d), lambda n, h, j: (n, h, at(j), 0))
    halo = pl.BlockSpec(
        (1, hb, HALO, d),
        lambda n, h, j: (n, h, jnp.maximum(at(j) * (ts // HALO) - 1, 0), 0))
    taps = pl.BlockSpec((k, hb, 1, d), lambda n, h, j: (0, h, 0, 0))
    return (n, heads // hb, tiles), hb, block, halo, taps


@functools.partial(jax.jit, static_argnames=("unit", "scale", "interpret"))
def forward(x, taps, *, unit: bool, scale: float = 1.0, interpret: bool = False):
    """The forward kernel: `y (N, H, S, D)` in `x.dtype`. `tile` took the
    shapes; `taps (K, H, D)`."""
    k, heads, d = taps.shape
    grid, _, block, halo, tap_rows = _specs(x, k, tile(x.shape[2], d, k), False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, unit=unit, scale=scale),
        grid=grid,
        in_specs=[block, halo, tap_rows],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=NAME + "_fwd",
    )(x, x, taps.astype(jnp.float32).reshape(k, heads, 1, d))


@functools.partial(jax.jit, static_argnames=("unit", "scale", "interpret"))
def backward(x, taps, dy, *, unit: bool, scale: float = 1.0,
             interpret: bool = False):
    """The backward kernel: (`dx` in `x.dtype`, `dtaps` in `taps.dtype`)
    from the forward's inputs and `dy (N, H, S, D)`."""
    k, heads, d = taps.shape
    n = x.shape[0]
    grid, hb, block, halo, tap_rows = _specs(x, k, tile(x.shape[2], d, k), True)
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, unit=unit, scale=scale),
        grid=grid,
        in_specs=[block, halo, tap_rows, block],
        out_specs=[block, pl.BlockSpec((1, k, hb, EDGE, d),
                                       lambda n, h, j: (n, 0, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, k, heads, EDGE, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, EDGE, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=NAME + "_bwd",
    )(x, x, taps.astype(jnp.float32).reshape(k, heads, 1, d), dy)
    return dx, jnp.sum(sums, axis=(0, 3)).astype(taps.dtype)


@functools.partial(jax.jit, static_argnames=("unit", "scale", "back", "otherwise"))
def either(*operands, unit: bool, scale: float, back: bool, otherwise: Callable):
    """One direction — `(x, taps)` forward, `(x, taps, dy)` backward — by
    the kernel where the program is lowered for a TPU; elsewhere by
    `otherwise(x, taps, unit, scale)`, the caller's plain-XLA form of the
    same function, or autodiff of it. A `jax.jit` with the caller's
    function as a static argument: both branches are traced once a
    signature, not at every call site of a step."""
    plain = lambda x, taps: otherwise(x, taps, unit, scale)  # noqa: E731
    if back:
        kernel = functools.partial(backward, unit=unit, scale=scale)
        default = lambda x, taps, dy: jax.vjp(plain, x, taps)[1](dy)  # noqa: E731
    else:
        kernel, default = functools.partial(forward, unit=unit, scale=scale), plain
    return lax.platform_dependent(*operands, tpu=kernel, default=default)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def short_conv(x, taps, unit: bool, scale: float, otherwise: Callable):
    """`y` of the module docstring for shapes `tile` took, `otherwise(x,
    taps, unit, scale)` being the same function in plain XLA (a function
    that stays the same object from call to call: it is `either`'s static
    argument). The rules, not autodiff, meet the platform's branch, and
    the backward keeps `x` and `taps` alone."""
    return either(x, taps, unit=unit, scale=scale, back=False,
                  otherwise=otherwise)


def _forward_rule(x, taps, unit, scale, otherwise):
    return either(x, taps, unit=unit, scale=scale, back=False,
                  otherwise=otherwise), (x, taps)


def _backward_rule(unit, scale, otherwise, kept, dy):
    return either(*kept, dy, unit=unit, scale=scale, back=True,
                  otherwise=otherwise)


short_conv.defvjp(_forward_rule, _backward_rule)
