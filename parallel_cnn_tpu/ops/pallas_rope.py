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

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
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


def cos_sin(s: int, d: int, theta: float):
    """(cos, sin), float32 `(S, d/2)`, of the angles `position * theta **
    (-2i / d)` of positions 0..S-1 and pairs i < d/2."""
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def tables(s: int, d: int, theta: float):
    """(`[cos | cos]`, `[-sin | sin]`), float32 `(S, d)`."""
    cos, sin = cos_sin(s, d, theta)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([-sin, sin], axis=-1))


def _kernel(x_ref, cos_ref, sin_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    half = x.shape[-1] // 2
    out_ref[...] = (x * cos_ref[...] + pltpu.roll(x, half, 2) * sin_ref[...]
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("theta", "back", "interpret"))
def rotate(x, *, theta: float, back: bool = False, interpret: bool = False):
    """The kernel: `x (..., S, d)` turned by its positions' angles, by
    their negatives if `back`, in `x.dtype`. `tile(S, d)` is not None."""
    s, d = x.shape[-2:]
    ts = tile(s, d)
    rows = x.reshape(-1, s, d)
    hb = max(h for h in range(1, ROWS + 1) if rows.shape[0] % h == 0)
    cos, sin = tables(s, d, theta)
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


@functools.partial(jax.jit, static_argnames=("theta", "back", "otherwise"))
def either(x, *, theta: float, back: bool, otherwise: Callable):
    """`rotate` where the program is lowered for a TPU; elsewhere
    `otherwise(x, theta)` — the caller's plain-XLA form of the same turn —
    or, if `back`, its transpose (the turn is linear). A `jax.jit` with
    the caller's function as a static argument: both branches are traced
    once a signature, not at every call site of a step."""
    plain = functools.partial(otherwise, theta=theta)
    if back:
        transposed = jax.linear_transpose(plain, x)
        plain = lambda d: transposed(d)[0]  # noqa: E731
    return lax.platform_dependent(
        x, tpu=lambda x: rotate(x, theta=theta, back=back), default=plain)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def turn(x, theta: float, otherwise: Callable):
    """`x` turned by its positions' angles, `otherwise(x, theta)` being the
    same turn in plain XLA (a function that stays the same object from call
    to call: it is `either`'s static argument). The backward is the turn
    of the cotangent by the negative angles. The rules, not autodiff, meet
    the platform's branch: a branch differentiated at each of a step's
    call sites cost the step's tracing more than the kernel did (PERF.md
    section 6, PR 42)."""
    return either(x, theta=theta, back=False, otherwise=otherwise)


def _forward_rule(x, theta, otherwise):
    return either(x, theta=theta, back=False, otherwise=otherwise), None


def _backward_rule(theta, otherwise, _, d_out):
    return (either(d_out, theta=theta, back=True, otherwise=otherwise),)


turn.defvjp(_forward_rule, _backward_rule)
