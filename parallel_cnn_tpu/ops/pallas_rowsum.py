"""The sum of a token's rows out of an expert layer's row buffer, as one
fused TPU kernel (Pallas/Mosaic): for a buffer `x (rows, d)` that
`nn/glm_moe.py:ExpertLayer.plan` laid out — held expert by held expert,
within an expert a token before a later one, a token at most once an
expert — and `slot_row (held, T)`, the row that holds token `t`'s
assignment to held expert `e` (-1: none),

    out[t] = sum_e  weight[e, t] * x[slot_row[e, t]]        (T, d)

over the `e` that have a row, the products and the sum in float32,
rounded once to `x.dtype`; `weight=None` is a weight of 1. Both
directions of the layer are this sum: the forward's way back from the
buffer (rows weighted by their gates) and the backward of the gather
into it (rows as they are).

Every row is read where it lies, once or a little more, and never by
assignment slot. The plan makes the rows of `tb` consecutive tokens in
one expert ONE CONTIGUOUS RANGE of the buffer (`bounds[i, e]` to
`bounds[i + 1, e]`), so the sum is a grouped matmul and no scatter:

  schedule  (plain XLA, integers a few thousand long, made once a layer
            beside the plan) cuts the buffer into fixed windows of `w`
            rows, lists for every token tile the windows its ranges
            touch, expert by expert, and packs that list into chunks of
            `g` windows — a tile has as many chunks as its rows need (one
            crowded expert: more), and at least one.
  kernel    grid over the chunks, the lists scalar-prefetched. A chunk's
            `g` windows are `g` blocks of the same buffer, fetched through
            index maps that read the list (so the pipeline double-buffers
            them across chunks, and a slot that repeats its window fetches
            nothing). Per slot a `(w, tb)` selection — the weight where
            `slot_row[e]` of the tile's token IS that window's row, else 0
            — the `g` of them stacked to `(g w, tb)`, the windows to
            `(g w, d)`, ONE product on the MXU contracting the rows, into a
            float32 `(tb, d)` accumulator that lives in VMEM until the
            tile's last chunk writes it.

A row the selection does not name contributes an exact 0; rows past the
live ones (`live`) hold nothing defined and are SELECTED away before the
product (0 x NaN is NaN), as is what a last, partial window reads past
the buffer's end. bf16 x bf16 products are exact in float32, so this is
the arithmetic of `einsum("tk,tkd->td")` over a token's slots in another
order.

`schedule` and `sums` are `jax.jit`s of their own: a model calls each
once a layer (and `sums` twice) with the same shapes, and jit's caches
make that one trace a process and one lowering a program — traced afresh
in every layer, the kernel's sixteen unrolled slots added a third to the
step's tracing and lowering time (PERF.md section 6, PR 37).

Which execution runs is decided by what the code can see, never by an
option: `tiles(T, rows, d, held)` gives the tile sizes for shapes the
kernel takes and None otherwise, and where the shapes tile the platform
is decided where the program is LOWERED (`lax.platform_dependent` in
`token_sums`): the kernel for a TPU, the caller's `otherwise` — the same
sum in plain XLA over buffer rows — for anything else.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_LIMIT_BYTES = 64 << 20
# A slot of a chunk that holds no window.
EMPTY = -1

_TN = (((0,), (0,)), ((), ()))  # a (k, m) x b (k, n) -> (m, n)


class Tiles(NamedTuple):
    """`tb` tokens a tile, windows of `w` buffer rows, `g` windows a chunk."""

    tb: int
    w: int
    g: int


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["slot_row", "tile", "edge", "window", "expert", "live",
                 "visited"],
    meta_fields=["tiles"])
@dataclasses.dataclass(frozen=True)
class Schedule:
    """What one layer's two sums read (`schedule`), made for `tiles`.
    `slot_row (held, T)`; per chunk `tile` (its token tile) and `edge` (1:
    the tile's first chunk, 2: its last, 3: both, 0: neither, EMPTY: no
    chunk, the grid's unused tail); per slot `c * g + i`, `window` (the block of `w`
    rows it fetches; an empty slot repeats its last one) and `expert`
    (whose `slot_row` names the rows; EMPTY: nothing); `live`, the buffer's
    live rows, as `(1,)`; `visited`, the rows the listed windows hold."""

    tiles: Tiles
    slot_row: jax.Array
    tile: jax.Array
    edge: jax.Array
    window: jax.Array
    expert: jax.Array
    live: jax.Array
    visited: jax.Array


def tiles(t: int, rows: int, d: int, held: int) -> Optional[Tiles]:
    """The tile sizes the kernel runs `T` tokens, a buffer of `rows` rows
    of width `d` and `held` experts at, or None where it does not take
    the shapes."""
    if d % LANES or t % 256 or rows < 16 or not 1 <= held <= 64:
        return None
    return Tiles(256 if t % 512 else 512, 16, 16)


def chunks(t: int, rows: int, held: int, tl: Tiles) -> int:
    """The grid: the most chunks any plan can need. A range of n > 0 rows
    touches at most (n - 1) // w + 2 windows and the ranges hold `rows`
    rows among them; a tile rounds its windows up to whole chunks."""
    nt = t // tl.tb
    return nt + -(-(-(-rows // tl.w) + 2 * nt * held) // tl.g)


@functools.partial(jax.jit, static_argnames=("rows", "tl"))
def schedule(place, ends, rows: int, tl: Tiles) -> Schedule:
    """The lists of one plan: `place (T, held)` the row a token's
    assignment to a held expert takes or would take (the expert's first
    row plus the earlier tokens that chose it), `ends (held,)` the experts'
    last rows, both before the buffer's `rows` cut them off; whether the
    token chose the expert is `place[t + 1] > place[t]`. Every look-up
    into a table is a comparison and a sum (the tables have `T / tb` rows
    or `held` columns): on the v5e an indexed read of a few thousand
    scalars costs more than all the rest of this function (PERF.md
    section 6, PR 37, call 1)."""
    t, held = place.shape
    nt, c_max = t // tl.tb, chunks(t, rows, held, tl)
    after = jnp.concatenate([place[1:], ends[None, :]])
    slot_row = jnp.where((after > place) & (place < rows), place, EMPTY).T
    bounds = jnp.minimum(
        jnp.concatenate([place[:: tl.tb], ends[None, :]]), rows)
    lo, hi = bounds[:-1], bounds[1:]                       # (nt, held)
    first = lo // tl.w
    n = jnp.where(hi > lo, (hi - 1) // tl.w - first + 1, 0)
    upto = jnp.cumsum(n, axis=1)
    n_chunks = jnp.maximum(1, -(-upto[:, -1] // tl.g))
    chunk_end = jnp.cumsum(n_chunks)
    c = jnp.arange(c_max, dtype=jnp.int32)
    tile = jnp.minimum(
        jnp.sum(c[:, None] >= chunk_end[None, :], axis=1), nt - 1)
    # a chunk's tile's row of every table: [its first chunk, its chunks,
    # each expert's windows up to and with it, their first window less
    # those before it]
    tables = jnp.concatenate(
        [(chunk_end - n_chunks)[:, None], n_chunks[:, None], upto,
         first - (upto - n)], axis=1)
    mine = jnp.sum(jnp.where(
        tile[:, None, None] == jnp.arange(nt)[None, :, None], tables[None], 0),
        axis=1)
    in_tile, upto_c = c - mine[:, 0], mine[:, 2: 2 + held]
    there = c < chunk_end[-1]
    edge = jnp.where(
        there, (in_tile == 0) + 2 * (in_tile == mine[:, 1] - 1), EMPTY)
    # the q-th window of its tile, counted through the tile's experts
    q = in_tile[:, None] * tl.g + jnp.arange(tl.g, dtype=jnp.int32)[None, :]
    active = there[:, None] & (q < upto_c[:, -1:])
    expert = jnp.minimum(
        jnp.sum(q[:, :, None] >= upto_c[:, None, :], axis=2), held - 1)
    window = q + jnp.sum(jnp.where(
        expert[:, :, None] == jnp.arange(held), mine[:, None, 2 + held:], 0),
        axis=2)
    # An empty slot keeps the window it had, so the pipeline fetches
    # nothing for it: the running maximum of (chunk, window) as one key.
    span = 1 << max(1, -(-rows // tl.w)).bit_length()
    window = jnp.maximum(lax.cummax(jnp.where(
        active, c[:, None] * span + window, EMPTY), axis=0), 0) % span
    as_list = lambda a: a.reshape(-1).astype(jnp.int32)  # noqa: E731
    return Schedule(
        tl, slot_row.astype(jnp.int32), tile.astype(jnp.int32),
        edge.astype(jnp.int32), as_list(window),
        as_list(jnp.where(active, expert, EMPTY)),
        jnp.minimum(ends[-1], rows).astype(jnp.int32).reshape(1),
        (jnp.sum(active) * tl.w).astype(jnp.int32))


def _kernel(tile_ref, edge_ref, window_ref, expert_ref, live_ref, *refs,
            tl: Tiles, weighted: bool):
    del tile_ref
    slot_ref = refs[0]
    weight_ref = refs[1] if weighted else None
    windows = refs[1 + weighted: 1 + weighted + tl.g]
    out_ref, acc_ref = refs[-2:]
    c = pl.program_id(0)
    edge = edge_ref[c]
    dtype = out_ref.dtype
    exact = (lax.Precision.HIGHEST if dtype == jnp.float32
             else lax.Precision.DEFAULT)

    def chunk():
        row = lax.broadcasted_iota(jnp.int32, (tl.w, 1), 0)
        select, data = [], []
        for i in range(tl.g):
            e = expert_ref[c * tl.g + i]
            start = window_ref[c * tl.g + i] * tl.w
            at = jnp.maximum(e, 0)
            # the tile's tokens' rows in this window, counted from its start
            theirs = jnp.where(e >= 0, slot_ref[pl.ds(at, 1), :] - start, EMPTY)
            if weighted:
                sel = jnp.where(row == theirs, weight_ref[pl.ds(at, 1), :], 0.0)
            else:
                sel = (row == theirs).astype(jnp.float32)
            select.append(sel.astype(dtype))
            data.append(jnp.where(row + start < live_ref[0], windows[i][...],
                                  jnp.zeros((), dtype)))
        return lax.dot_general(
            jnp.concatenate(select, axis=0), jnp.concatenate(data, axis=0),
            _TN, precision=exact, preferred_element_type=jnp.float32)

    @pl.when(edge >= 0)
    def _():
        part = chunk()

        @pl.when(edge == 3)
        def _():
            out_ref[...] = part.astype(dtype)

        @pl.when(edge == 1)
        def _():
            acc_ref[...] = part

        @pl.when(edge == 0)
        def _():
            acc_ref[...] += part

        @pl.when(edge == 2)
        def _():
            out_ref[...] = (acc_ref[...] + part).astype(dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sums(x, weight, sched: Schedule, *, interpret: bool = False):
    """The kernel: `(T, d)` sums in `x.dtype` of the buffer `x (rows, d)`
    under `sched`; `weight (held, T)` float32 (values that `x.dtype`
    holds) or None."""
    tl, d = sched.tiles, x.shape[1]
    held, t = sched.slot_row.shape
    weighted = weight is not None
    per_tile = pl.BlockSpec((held, tl.tb), lambda c, tile, *_: (0, tile[c]))

    def window(i):
        return pl.BlockSpec(
            (tl.w, d), lambda c, tile, edge, window, *_: (window[c * tl.g + i], 0))

    return pl.pallas_call(
        functools.partial(_kernel, tl=tl, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(sched.tile.shape[0],),
            in_specs=[per_tile] * (1 + weighted) + [
                window(i) for i in range(tl.g)],
            out_specs=pl.BlockSpec((tl.tb, d), lambda c, tile, *_: (tile[c], 0)),
            scratch_shapes=[pltpu.VMEM((tl.tb, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_token_sums",
    )(sched.tile, sched.edge, sched.window, sched.expert, sched.live,
      sched.slot_row, *([weight] if weighted else []), *([x] * tl.g))


def token_sums(x, weight, sched: Schedule, otherwise: Callable):
    """`sums` where the program is lowered for a TPU, `otherwise(x)` — the
    caller's plain-XLA form of the same sums — elsewhere."""
    return lax.platform_dependent(
        x, weight, sched, tpu=sums, default=lambda x, *_: otherwise(x))
