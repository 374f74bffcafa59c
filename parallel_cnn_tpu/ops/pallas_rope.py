"""Rotary position embedding's turn as one fused TPU kernel
(Pallas/Mosaic): for `x (..., S, d)`, feature `i` paired with `i + d/2`
and the pair turned by `position * theta ** (-2i / d)`,

    y = x * [cos | cos] + roll(x, d/2 features) * [-sin | sin]

the products and the sum in float32, rounded once to `x.dtype` — what
`nn/layers.py:rope`'s plain body computes with two slices and a
concatenation. Every element is read once and written once, in either
direction: the plain body, compiled for the chip around a projection
that leaves `q` position-minor, moved ten times the bytes (a float32
copy of `q`, two lane-padded 64-wide halves and their join; PERF.md
section 6, PR 42).

  tables   (plain XLA, float32 `(S, d)`, from float32 angles as the plain
           body makes them) `C = [cos | cos]` and `S = [-sin | sin]`.
  kernel   the leading axes flatten to rows `(B, S, d)` (free: they are
           major). A block `(hb, ts, d)` of `hb` rows at `ts` positions
           goes to float32 in VMEM, is rolled by `d/2` lanes
           (`pltpu.roll`) and combined with the tables' block. The grid
           is (position tiles, row blocks), the rows INNER: a table
           block's index does not change along the inner axis, so the
           pipeline fetches it once a position tile and not once a block.

The backward is the same kernel with `-S`, the turn by the negative
angle: `turn` is a `jax.custom_vjp` that keeps nothing of `x`'s size, and
one kernel body serves the forward, a rematerialised forward and the
backward.

`rotate` and `either` are `jax.jit`s of their own: a model turns `q` and
`k` in every layer with the same shapes, and jit's caches make that one
trace a process (PERF.md section 6, PR 37: a kernel traced afresh at every
call site of six unrolled layers added a third to the step's tracing
time).

Which path runs is decided by what the code can see, never by an option:
`tile(S, d)` gives the position tile for shapes the kernel takes — `d`
whole 128-lane registers, so that a half is a whole number of lanes to
roll by and no lane is empty, and `S` a whole number of tiles — and None
otherwise (GLM-4.7-Flash's 64-wide turn: half-empty lanes, and a layout
the plain body's neighbours fuse with); where the shapes tile, the
platform is decided where the program is LOWERED (`lax.platform_dependent`
in `either`): the kernel for a TPU, the plain body for anything else.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Rows a block: 8 x 512 x 128 is 1 MB of bf16 in and out and 2 MB of
# float32 between, double-buffered well inside the default VMEM limit.
ROWS = 8
NAME = "rope_turn"


def tile(s: int, d: int) -> Optional[int]:
    """The positions a block of the kernel holds for `S` positions of `d`
    features, or None where it does not take the shapes."""
    if d % LANES:
        return None
    return next((ts for ts in (512, 256, 128) if s % ts == 0), None)


@dataclasses.dataclass(frozen=True)
class Axes:
    """Positions on more than one axis (M-RoPE, Qwen2-VL arXiv:2409.12191
    section 2.1), one layout for every sequence, static: `sections` says
    how many of a head's `d / 2` frequencies, in order, turn by each axis'
    position (time, height, width: `(16, 24, 24)` of 64); `spans` lists the
    images, `(start, t, h, w)` each — `t * h * w` cells from index `start`,
    the width running fastest. Text counts on all axes alike; an image that
    starts at running position r puts its cell `(i_t, i_h, i_w)` at `(r +
    i_t, r + i_h, r + i_w)`, and what follows resumes at the largest
    position so far plus one. No span: every axis reads 0..S-1, and the
    turn is `rope`'s own, bit for bit."""

    sections: Tuple[int, ...]
    spans: Tuple[Tuple[int, int, int, int], ...] = ()

    def over(self, sections) -> "Axes":
        """The same positions under other `sections` (a narrower head's)."""
        return dataclasses.replace(self, sections=tuple(sections))

    def rows(self, s: int):
        """The positions of indices 0..S-1 on every axis, int `(3, S)`."""
        return _rows(self.spans, s)

    def of_pairs(self, s: int, d: int):
        """float32 `(S, d/2)`: the position that turns pair i at index t."""
        if sum(self.sections) != d // 2:
            raise ValueError(f"sections {self.sections} are not the "
                             f"{d // 2} pairs of {d} features")
        # the program's constant is the three rows of integers: written out
        # pair by pair in float32 the table was 4 MB at 16,384 positions, in
        # every one of a step's 52 turns (490 MB of lowered text, PR 51)
        rows = jnp.asarray(self.rows(s), jnp.int32)
        return jnp.concatenate(
            [jnp.broadcast_to(row[:, None], (s, n))
             for row, n in zip(rows, self.sections)], axis=1).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _rows(spans, s: int):
    rows = np.zeros((3, s), np.int64)
    at = following = 0  # the index reached, and the position it takes
    for start, t, h, w in sorted(spans):
        if start < at or start + t * h * w > s:
            raise ValueError(f"image spans {spans} overlap or pass {s}")
        rows[:, at:start] = following + np.arange(start - at)
        r = following + start - at
        cell = np.indices((t, h, w)).reshape(3, -1)
        rows[:, start:start + cell.shape[1]] = r + cell
        at, following = start + cell.shape[1], r + max(t, h, w)
    rows[:, at:] = following + np.arange(s - at)
    return rows


def cos_sin(s: int, d: int, theta: float, positions: Optional[Axes] = None):
    """(cos, sin), float32 `(S, d/2)`, of the angles `position * theta **
    (-2i / d)` of pairs i < d/2 — at positions 0..S-1, or where `positions`
    puts each pair's."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    at = (jnp.arange(s, dtype=jnp.float32)[:, None] if positions is None
          else positions.of_pairs(s, d))
    ang = at * inv
    return jnp.cos(ang), jnp.sin(ang)


def tables(s: int, d: int, theta: float, positions: Optional[Axes] = None):
    """(`[cos | cos]`, `[-sin | sin]`), float32 `(S, d)`."""
    cos, sin = cos_sin(s, d, theta, positions)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _kernel(x_ref, cos_ref, sin_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    half = x.shape[-1] // 2
    out_ref[...] = (x * cos_ref[...] + pltpu.roll(x, half, 2) * sin_ref[...]
                    ).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("theta", "back", "interpret", "positions"))
def rotate(x, *, theta: float, back: bool = False, interpret: bool = False,
           positions: Optional[Axes] = None):
    """The kernel: `x (..., S, d)` turned by its positions' angles, by
    their negatives if `back`, in `x.dtype`. `tile(S, d)` is not None."""
    s, d = x.shape[-2:]
    ts = tile(s, d)
    rows = x.reshape(-1, s, d)
    hb = max(h for h in range(1, ROWS + 1) if rows.shape[0] % h == 0)
    cos, sin = tables(s, d, theta, positions)
    table = pl.BlockSpec((ts, d), lambda j, i: (j, 0))
    block = pl.BlockSpec((hb, ts, d), lambda j, i: (i, j, 0))
    return pl.pallas_call(
        _kernel,
        grid=(s // ts, rows.shape[0] // hb),
        in_specs=[block, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(rows.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=NAME,
    )(rows, cos, -sin if back else sin).reshape(x.shape)


@functools.partial(
    jax.jit, static_argnames=("theta", "back", "otherwise", "positions"))
def either(x, *, theta: float, back: bool, otherwise: Callable,
           positions: Optional[Axes] = None):
    """`rotate` where the program is lowered for a TPU; elsewhere
    `otherwise(x, theta)` — the caller's plain-XLA form of the same turn —
    or, if `back`, its transpose (the turn is linear). A `jax.jit` with
    the caller's function as a static argument: both branches are traced
    once a signature, not at every call site of a step."""
    plain = functools.partial(otherwise, theta=theta)
    if positions is not None:
        plain = functools.partial(plain, positions=positions)
    if back:
        transposed = jax.linear_transpose(plain, x)
        plain = lambda d: transposed(d)[0]  # noqa: E731
    return lax.platform_dependent(
        x, default=plain,
        tpu=lambda x: rotate(x, theta=theta, back=back, positions=positions))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def turn(x, theta: float, otherwise: Callable,
         positions: Optional[Axes] = None):
    """`x` turned by its positions' angles (0..S-1, or what `positions`
    says, which `otherwise` then takes too), `otherwise(x, theta)` being the
    same turn in plain XLA (a function that stays the same object from call
    to call: it is `either`'s static argument). The backward is the turn
    of the cotangent by the negative angles. The rules, not autodiff, meet
    the platform's branch: a branch differentiated at each of a step's
    call sites cost the step's tracing more than the kernel did (PERF.md
    section 6, PR 42)."""
    return either(x, theta=theta, back=False, otherwise=otherwise,
                  positions=positions)


def _forward_rule(x, theta, otherwise, positions):
    return either(x, theta=theta, back=False, otherwise=otherwise,
                  positions=positions), None


def _backward_rule(theta, otherwise, positions, _, d_out):
    return (either(d_out, theta=theta, back=True, otherwise=otherwise,
                   positions=positions),)


turn.defvjp(_forward_rule, _backward_rule)
