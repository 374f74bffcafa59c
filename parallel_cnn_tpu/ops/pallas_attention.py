"""Attention cores as fused TPU kernels (Pallas/Mosaic), masks of one
family: the causal core (`causal_attention`, below); under its own
heading, the block-diffusion core over grouped key/value heads
(`block_diffusion_attention`), whose kernels take the mask as a schedule;
and, at the end of the file, two more schedules of that second pair, the
causal and the sliding-window core over grouped heads
(`grouped_causal_attention`). All share this arithmetic, these roundings,
the residuals' names and the rule that decides what runs. The causal
core: for head-major bf16 `q, k (N, H, S, Dk)` and `v (N, H, S, Dv)`,

    out = softmax_causal(q k^T * scale) v

with the scores in float32, the softmax statistics and every accumulator
in float32, and the probabilities cast to `v.dtype` before `p v` — what
`nn/glm_moe.py:_attend` computes a block of queries at a time in plain
XLA. Two roundings are not that path's: `p` is cast before it is
normalised (the division by the row's sum is the accumulator's, at the
end), and the backward casts `ds` to `q.dtype` before the `dk` and `dq`
products, where the blocks' autodiff hands the dots a float32 `ds`. Here
the scores never leave VMEM:

  forward   grid (N, H, query tile, key tile): an online softmax over the
            key tiles at or below the diagonal. A tile wholly above it is
            not visited (its grid step does nothing and fetches nothing:
            the index maps stay on the diagonal's tile), a tile the
            diagonal crosses is masked in the kernel. Writes `out` and
            the rows' log-sum-exp `lse (N, H, S)` float32.
  backward  grid (N, H, key tile, query tile), from `q, k, v, out, lse,
            d_out` alone: `delta = sum(out * d_out)` (plain XLA, one pass),
            then per visited tile the scores again, transposed (keys on
            the sublanes, so `lse` and `delta` are rows that broadcast
            down them), `p`, `dv += p^T d_out`, `dp`, `ds = p (dp -
            delta) scale` (cast to `q.dtype`), `dk += ds^T q`, `dq += ds
            k`. `dk`/`dv` accumulate in VMEM over the inner query tiles;
            `dq` of a whole (sequence, head) accumulates in one float32
            VMEM buffer `(S, Dk)` over all of its tiles and is written
            once.

`causal_attention` is the `jax.custom_vjp` over both. Its forward rule
names `out` and `lse` `"attn_core"` (`jax.ad_checkpoint.checkpoint_name`)
ON THE VALUES IT RETURNS AS RESIDUALS: a layer rematerialised under
`save_only_these_names("attn_core", ...)` (nn/glm_moe.py:GlmMoe._run,
whose policy also keeps the expert layer's `"moe_plan"`) keeps both, so
its backward re-runs no forward kernel.

Which execution runs is decided by what the code can see, never by an
option. `tile(S, Dk, Dv)` is the tile for shapes the kernels take (`S` a
multiple of it, head widths multiples of 128 lanes, `dq`'s buffer within
VMEM) and None otherwise — the caller then keeps its own blocked path.
Where the shapes tile, the platform is decided where the program is
LOWERED (`jax.lax.platform_dependent`): the kernels for a TPU, the
caller's `otherwise` (the same mathematics in plain XLA) for anything
else, so an off-chip compile for a described TPU gets the kernels and a
CPU host with tiling shapes still runs.

Tiles were swept on the v5e once, at 4 x 20 x 4,096 x 256 (PERF.md section
6, PR 33: forward / backward 9.64 / 15.54 ms at 256, 6.26 / 12.07 at 512,
5.99 / 12.32 at 1,024; the blocked XLA form 22.2 / 47.1 forward / both),
and are fixed here as a function of `(S, head widths)`. The grid stays at
that tile under every mask; what a step of the second pair computes of a
tile that the mask's edge crosses is under that pair's heading.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
RESIDUAL_NAME = "attn_core"
# exp(MASKED - m) is 0.0 exactly for every finite m, and MASKED - MASKED
# is 0, not NaN (a -inf would make one of a fully masked stretch).
MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)
# What one (sequence, head)'s float32 `dq` may take of VMEM in the backward.
DQ_BUFFER_BYTES = 16 << 20
VMEM_LIMIT_BYTES = 64 << 20

_NN = (((1,), (0,)), ((), ()))  # a (m, k) x b (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))  # a (m, d) x b (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # a (k, m) x b (k, n) -> (m, n)


def _dot(a, b, dims):
    """One MXU product with a float32 result. The precision is said, not
    taken from `jax_default_matmul_precision`: the operands are what the
    configuration states, and Mosaic refuses a bf16 product asked for at
    `highest`."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def tile(s: int, qk_width: int, v_width: int) -> Optional[int]:
    """The square tile (queries x keys) the kernels run `S` positions of
    these head widths at, or None where they do not take the shapes."""
    if qk_width % LANES or v_width % LANES or s * qk_width * 4 > DQ_BUFFER_BYTES:
        return None
    for t in (512, 256, 128):
        if s % t == 0:
            return t
    return None


def tiles_visited(s: int, t: int) -> int:
    """Of the `ceil(S/t)^2` tiles of one (sequence, head)'s score square,
    those at or below the diagonal: what a causal core computes."""
    n = -(-s // t)
    return n * (n + 1) // 2


def _causal(s, rows_are_keys: bool):
    """A square tile ON the diagonal: `s` where key <= query, MASKED
    elsewhere (both indices count from the tile's own corner)."""
    r = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    c = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(r <= c if rows_are_keys else c <= r, s, MASKED)


def _lanes(x, width: int):
    """A lane-replicated column `(rows, LANES)` as `(rows, width)`."""
    return x if width == LANES else jnp.tile(x, (1, width // LANES))


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, t: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(on_diagonal: bool):
        s = _dot(q_ref[0, 0], k_ref[0, 0], _NT) * scale
        if on_diagonal:
            s = _causal(s, rows_are_keys=False)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
        p = jnp.exp(s - _lanes(m_next, t))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_prev + p.sum(axis=-1)[:, None]
        m_ref[...] = m_next
        v = v_ref[0, 0]
        acc_ref[...] = (_lanes(alpha, acc_ref.shape[-1]) * acc_ref[...]
                        + _dot(p.astype(v.dtype), v, _NN))

    pl.when(ki < qi)(functools.partial(step, False))

    @pl.when(ki == qi)
    def _():
        step(True)
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...] / _lanes(l, acc_ref.shape[-1])
                       ).astype(o_ref.dtype)
        # the statistics are columns, `lse` is stored as a row
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).T[:1]


def forward(q, k, v, *, scale: float, t: int, interpret: bool = False):
    """(out (N, H, S, Dv) in `v.dtype`, lse (N, H, S) float32)."""
    n, h, s, dk = q.shape
    dv = v.shape[-1]
    at_q = lambda n, h, qi, ki: (n, h, qi, 0)  # noqa: E731
    # above the diagonal nothing is fetched: the map stays where it was
    at_k = lambda n, h, qi, ki: (n, h, jnp.minimum(ki, qi), 0)  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, t=t),
        grid=(n, h, s // t, s // t),
        in_specs=[pl.BlockSpec((1, 1, t, dk), at_q),
                  pl.BlockSpec((1, 1, t, dk), at_k),
                  pl.BlockSpec((1, 1, t, dv), at_k)],
        out_specs=[pl.BlockSpec((1, 1, t, dv), at_q),
                   pl.BlockSpec((1, 1, 1, t), lambda n, h, qi, ki: (n, h, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct((n, h, s, dv), v.dtype),
                   jax.ShapeDtypeStruct((n, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((t, LANES), jnp.float32),
                        pltpu.VMEM((t, LANES), jnp.float32),
                        pltpu.VMEM((t, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="causal_attention_fwd",
    )(q, k, v)
    return out, lse.reshape(n, h, s)


# --------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc,
                *, scale: float, t: int):
    ki, qi = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1

    @pl.when((ki == 0) & (qi == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(on_diagonal: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        # keys on the sublanes: (keys, queries)
        s = _dot(k, q, _NT) * scale
        if on_diagonal:
            s = _causal(s, rows_are_keys=True)
        p = jnp.exp(s - lse_ref[0, 0])
        dv_acc[...] += _dot(p.astype(do.dtype), do, _NN)
        ds = (p * (_dot(v, do, _NT) - delta_ref[0, 0]) * scale).astype(q.dtype)
        dk_acc[...] += _dot(ds, q, _NN)
        rows = pl.ds(pl.multiple_of(qi * t, t), t)
        dq_acc[rows, :] += _dot(ds, k, _TN)

    pl.when(qi > ki)(functools.partial(step, False))
    pl.when(qi == ki)(functools.partial(step, True))

    @pl.when(qi == last)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((ki == last) & (qi == last))
    def _():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def backward(q, k, v, out, lse, d_out, *, scale: float, t: int,
             interpret: bool = False):
    """(dq, dk, dv) in the dtypes of `q, k, v`."""
    n, h, s, dk = q.shape
    dv = v.shape[-1]
    delta = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32),
                    axis=-1).reshape(n, h, 1, s)
    # a query tile above the diagonal is not fetched: the map waits on
    # the diagonal's
    at_q = lambda n, h, ki, qi: (n, h, jnp.maximum(qi, ki), 0)  # noqa: E731
    at_row = lambda n, h, ki, qi: (n, h, 0, jnp.maximum(qi, ki))  # noqa: E731
    at_k = lambda n, h, ki, qi: (n, h, ki, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, t=t),
        grid=(n, h, s // t, s // t),
        in_specs=[pl.BlockSpec((1, 1, t, dk), at_q),
                  pl.BlockSpec((1, 1, t, dk), at_k),
                  pl.BlockSpec((1, 1, t, dv), at_k),
                  pl.BlockSpec((1, 1, t, dv), at_q),
                  pl.BlockSpec((1, 1, 1, t), at_row),
                  pl.BlockSpec((1, 1, 1, t), at_row)],
        out_specs=[pl.BlockSpec((1, 1, s, dk), lambda n, h, ki, qi: (n, h, 0, 0)),
                   pl.BlockSpec((1, 1, t, dk), at_k),
                   pl.BlockSpec((1, 1, t, dv), at_k)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((s, dk), jnp.float32),
                        pltpu.VMEM((t, dk), jnp.float32),
                        pltpu.VMEM((t, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="causal_attention_bwd",
    )(q, k, v, d_out, lse.reshape(n, h, 1, s), delta)


# ------------------------------------------------------------ custom_vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention(q, k, v, scale: float, t: int, otherwise: Callable):
    """`out (N, H, S, Dv)` of head-major `q, k, v` whose shapes `tile`
    accepted (`t`): the kernels where the program is lowered for a TPU,
    `otherwise(q, k, v)` — the caller's plain-XLA form — elsewhere."""
    return _forward_rule(q, k, v, scale, t, otherwise)[0]


def _forward_rule(q, k, v, scale, t, otherwise):
    def plain(q, k, v):
        n, h, s, _ = q.shape
        return otherwise(q, k, v), jnp.zeros((n, h, s), jnp.float32)

    out, lse = lax.platform_dependent(
        q, k, v, tpu=functools.partial(forward, scale=scale, t=t), default=plain)
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    return out, (q, k, v, out, lse)


def _backward_rule(scale, t, otherwise, residuals, d_out):
    def plain(q, k, v, out, lse, d_out):
        return jax.vjp(otherwise, q, k, v)[1](d_out)

    return lax.platform_dependent(
        *residuals, d_out,
        tpu=functools.partial(backward, scale=scale, t=t), default=plain)


causal_attention.defvjp(_forward_rule, _backward_rule)


# ===================================================== block diffusion
#
# The second mask of the family, with the second head layout. A block-
# diffusion model (nn/sdar_moe.py) trains on the stream `[x^t ; x^0]`: `l`
# noised positions, then the same `l` positions clean, in blocks of
# `block`. With beta(i) the block of position i (within its half), query i
# may see key j iff
#
#     both noised and beta(i) == beta(j)          (SAME)
#     i noised, j clean and beta(j) <  beta(i)    (BEFORE)
#     both clean and beta(j) <= beta(i)           (UPTO)
#
# and a clean query never sees a noised key. `q (N, H, 2l, D)` is `H`
# query heads over `k, v (N, KV, 2l, D)`: query head a reads key/value head
# `a // (H // KV)`, through the index maps alone.
#
# The mask reaches the kernels as static structure: a schedule (this
# mask's is `schedule(l, t)`; `scheduled_forward` / `scheduled_backward`
# take any) lists, query tile by query tile, the tiles that hold an allowed
# pair and how each is masked (FULL: not at all), each kind one of the
# `kinds` the call dispatches over; the three lists are scalar-prefetched,
# the grid's last axis walks them, and the index maps read the tile to
# fetch from them — a tile the mask excludes is no grid step at all. A tile
# that a block boundary crosses is masked in the kernel from the positions
# of its rows and columns (`t % block == 0`, so a tile's own corner starts a
# block and local indices do) — and computed in part: such a step cuts its
# tile into squares of 128 (`sub_squares`, static like the kinds), runs the
# tile's body over the squares that hold an allowed pair alone, gathered
# into a few rectangles (`_parts`) that it lays side by side — one pass of
# the arithmetic between the products, whatever the cut — and masks only the
# squares the boundary crosses. A skipped square's rows keep their
# statistics and their accumulators, which is what its `exp(MASKED - m) = 0`
# left of them at a whole product's cost; every allowed pair's `p` is the
# number it was. The backward cuts every kind but FULL (4 squares of 16
# under SAME, 10 under the triangular kinds), the forward SAME alone: where
# cutting pays was timed on the v5e a step of each kind and direction
# (PERF.md section 6, PR 49) and is a function of the kind, the direction
# and the shapes here, no option.
#
# A grid step carries `G = heads_a_step(group, t, D, S)` query heads of ONE
# key/value head (blocks `(1, G, t, D)` of `q`, `out`, `d_out`, `dq`, `(1, G,
# 1, t)` of `lse`, `delta`; the K/V tile fetched once for the `G`): what a
# step costs before it computes a pair — about 0.9 us of the forward's 1.12
# and 1 of the backward's 2.18 at one head a step — is paid once for the
# heads that share its key tile. `G` is the largest of 8, 4, 2 that divides
# the group and keeps the backward step inside `VMEM_LIMIT_BYTES`, from the
# v5e's timings at the three cells' shapes (PERF.md section 6, PR 50: a
# call at `sdar_bd_train`'s shape 11.36 / 19.88 ms forward / backward at one
# head a step, 9.81 / 18.07 at two, 8.85 / 17.04 at four, 8.46 / 16.53 at
# eight); a group of one gets 1 and, equation for equation, the kernels of
# one head a step. How the heads share a step was timed too, a direction at
# a time:
#
#   forward   grid (N, H / G, step): the online softmax over a query tile's
#             listed key tiles, A HEAD AFTER A HEAD in one step — `G` chains
#             of product, row maximum, `exp`, row sum, product, each on its
#             head's rows of the statistics `(G, t, .)`. A FULL step (most
#             steps are) is `G` bodies, which the scheduler runs one under
#             another (8.46 ms with every kind so; the heads stacked down
#             the rows of ONE chain read 9.73, a loop over them 9.53); a
#             boundary's kinds are one body in a `fori_loop` over the heads
#             (as bodies they were three quarters of the program's
#             equations, which set-up traces and lowers, for a thirtieth of
#             the forward's time: 8.88 ms as it ships). `out` and `lse`
#             written at the tile's last step.
#   backward  grid (N, KV, group / G, step), the same list: scores
#             transposed (keys on the sublanes) as in the causal backward,
#             THE HEADS SIDE BY SIDE ALONG THE LANES — `s` and `dp` `(t, G
#             t)` from one product each, one pass of `exp`, `dp - delta`,
#             `ds`, and `dv += p dO`, `dk += ds q` ONE product each over the
#             `G t` queries: the sum over the step's heads is the
#             contraction's, one read-modify-write of the accumulators where
#             there were `G` (16.53 ms stacked, 16.53 as `G` bodies a step:
#             the smaller program ships). `dq` of a query tile accumulates
#             `(G, t, D)` over its key tiles; `dk`, `dv` of one (sequence,
#             key/value head) accumulate in two float32 VMEM buffers `(S,
#             D)` over every step of every query head of the group and are
#             written once.
#
# `attention_tiles_visited` and `pairs_computed` go on counting one head's
# schedule steps and pairs, whatever `G` is.

FULL, SAME, BEFORE, UPTO, AFTER, DATA = 0, 1, 2, 3, 4, 5
BD_KINDS = (FULL, SAME, BEFORE, UPTO)
# An entry of the third table: the kind's place among the kinds its call
# dispatches over (two bits: a schedule holds at most four), and two flags.
_FIRST, _LAST = 4, 8  # a query tile's first/last step


def bd_tile(l: int, block: int, width: int) -> Optional[int]:
    """The square tile the block-diffusion kernels run a stream of `2 l`
    positions at (blocks of `block`, heads `width` wide), or None where
    they do not take the shapes: a tile holds whole blocks and lies in one
    half, and `dk` and `dv` of one (sequence, head) fit their buffers."""
    if width % LANES or 2 * (2 * l * width * 4) > DQ_BUFFER_BYTES:
        return None
    for t in (512, 256, 128):
        if l % t == 0 and t % block == 0:
            return t
    return None


def heads_a_step(group: int, t: int, width: int, s: int,
                 data: bool = False) -> int:
    """The query heads of one key/value head that a grid step of the pair
    carries (`group` of them read it; tiles of `t`, heads `width` wide, `s`
    positions): as many as divide the group and leave the backward step —
    the hungrier direction — inside `VMEM_LIMIT_BYTES`, up to the 8 the
    v5e was timed at (PERF.md section 6, PR 50: every doubling gained,
    both directions, all three cells' shapes). A group of one: 1, and the
    kernels a head a step. `data`: the schedule's tiles are masked by an
    array (`DATA`), whose tile the backward lays beside each head's scores."""
    # the float32 `dk`, `dv` accumulators and their two-deep bf16 output blocks
    whole = 2 * s * width * 4 + 2 * 2 * s * width * 2
    # a head's `q`, `d_out`, `dq` blocks two deep and float32 `dq` accumulator,
    # and its scores, `dp` and `p` / `ds` of one pass
    a_head = (3 * 2 * t * width * 2 + t * width * 4
              + (4 if data else 3) * t * t * 4)
    for g in (8, 4, 2):
        if group % g == 0 and whole + g * a_head <= VMEM_LIMIT_BYTES:
            return g
    return 1


def schedule(l: int, t: int):
    """[(query tile, key tile, kind)] of the stream's `(2l / t)^2` tiles
    that hold an allowed pair, query-major. A query tile's first entry
    leaves no row without a visible key (the online softmax starts from
    it): a noised tile's is its own noised tile, a clean tile's holds
    key 0."""
    half = l // t
    steps = []
    for i in range(half):
        steps.append((i, i, SAME))
        steps += [(i, half + j, FULL) for j in range(i)]
        steps.append((i, half + i, BEFORE))
    for i in range(half):
        steps += [(half + i, half + j, FULL) for j in range(i)]
        steps.append((half + i, half + i, UPTO))
    return steps


def bd_tiles_visited(l: int, t: int) -> int:
    """Of the `(2l / t)^2` tiles of one (sequence, head)'s score square,
    those the block-diffusion kernels compute."""
    return len(schedule(l, t))


def _tables(steps, kinds):
    what = []
    for i, (qi, _, kind) in enumerate(steps):
        first = i == 0 or steps[i - 1][0] != qi
        last = i == len(steps) - 1 or steps[i + 1][0] != qi
        what.append(kinds.index(kind) | (_FIRST if first else 0)
                    | (_LAST if last else 0))
    as_i32 = lambda xs: jnp.asarray(xs, jnp.int32)  # noqa: E731
    return (as_i32([s[0] for s in steps]), as_i32([s[1] for s in steps]),
            as_i32(what))


def _seen(shape, kind: int, block: int, rows_are_keys: bool):
    """bool `shape`, a tile whose corner starts a block on both sides: where
    the kind's rule holds between the query's block and the key's (blocks
    of 1: between the query's offset in its tile and the key's in its own)."""
    shift = block.bit_length() - 1  # a power of two: it divides the tile
    r = lax.broadcasted_iota(jnp.int32, shape, 0) >> shift
    c = lax.broadcasted_iota(jnp.int32, shape, 1) >> shift
    qb, kb = (c, r) if rows_are_keys else (r, c)
    return {SAME: kb == qb, BEFORE: kb < qb, UPTO: kb <= qb, AFTER: kb > qb}[kind]


def _each_kind(what, kinds, step):
    """Run `step(kind)` for the one of the call's `kinds` that this grid
    step's entry names."""
    for i, kind in enumerate(kinds):
        pl.when((what & 3) == i)(functools.partial(step, kind))


def sub_squares(kind: int, block: int, t: int, backward: bool):
    """(sub, {(a, b): masked}): the side of the squares a step of `kind`
    cuts its `t x t` tile into, and of those squares (query group a, key
    group b) the ones that hold an allowed pair, which are all it computes
    — `masked` where one holds an excluded pair as well, which are all it
    masks. `sub` is 128 (lanes and sublanes; a block if that is wider),
    or `t`, the tile whole, where cutting does not pay: a FULL step, and
    forward every kind but SAME — timed on the v5e a step of each kind
    and direction (PERF.md section 6, PR 49): the forward's floor a step
    is above what the triangular kinds' three eighths would save."""
    if kind in (FULL, DATA):  # (a DATA tile's mask is an array's, all of it)
        return t, {(0, 0): False}
    sub = min(t, max(LANES, block)) if backward or kind == SAME else t
    lo = lambda g: g * sub // block  # noqa: E731 a group's first block, and
    hi = lambda g: ((g + 1) * sub - 1) // block  # noqa: E731 its last
    some = {SAME: lambda a, b: lo(b) <= hi(a) and lo(a) <= hi(b),
            BEFORE: lambda a, b: lo(b) < hi(a),
            UPTO: lambda a, b: lo(b) <= hi(a),
            AFTER: lambda a, b: hi(b) > lo(a)}[kind]
    every = {SAME: lambda a, b: lo(a) == hi(a) == lo(b) == hi(b),
             BEFORE: lambda a, b: hi(b) < lo(a),
             UPTO: lambda a, b: hi(b) <= lo(a),
             AFTER: lambda a, b: lo(b) > hi(a)}[kind]
    groups = range(t // sub)
    return sub, {(a, b): not every(a, b)
                 for a in groups for b in groups if some(a, b)}


def pairs_computed(steps, block: int, t: int, backward: bool) -> int:
    """The (query, key) pairs a kernel executes under the schedule `steps`,
    one (sequence, head): the squares `sub_squares` keeps of each step's
    tile. (`bd_tiles_visited` and `causal_tiles_visited` count grid
    steps.)"""
    cut = (sub_squares(kind, block, t, backward) for _, _, kind in steps)
    return sum(len(kept) * sub * sub for sub, kept in cut)


def _parts(kind: int, block: int, t: int, backward: bool):
    """(sub, [(queries, keys, masked)]) — the kept squares of a step of
    `kind` gathered into rectangles, one product each: two slices of the
    tile's rows, and the corners (query, key) inside of the squares that
    the mask (`_seen`) has to see. Backward a rectangle a key group (the group's
    `sub` keys by the queries that see one of them), which the kernel lays
    side by side along the lanes; forward a rectangle a query group — the
    tile itself, or a SAME tile's squares, of one width, which it lays
    side by side down the rows."""
    sub, kept = sub_squares(kind, block, t, backward)
    span = lambda g0, g1: slice(g0 * sub, (g1 + 1) * sub)  # noqa: E731
    parts = []
    for g in range(t // sub):
        along = sorted(a if backward else b for a, b in kept
                       if (b if backward else a) == g)
        if not along:
            continue  # a group with no key, or no query, in this tile
        first, last = along[0], along[-1]
        assert along == list(range(first, last + 1)), (kind, block, t)
        if backward:
            parts.append((span(first, last), span(g, g),
                          [((a - first) * sub, 0) for a in along if kept[a, g]]))
        else:
            parts.append((span(g, g), span(first, last),
                          [(0, (b - first) * sub) for b in along if kept[g, b]]))
    return sub, parts


def _beside(xs, axis: int):
    """The arrays `xs` laid side by side along `axis` (one: itself)."""
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


def _masked_at(s, at: int, sub: int, seen, heads: int = 1):
    """`s` — a rectangle of `_parts`, one square high, of each of `heads`
    heads side by side along the lanes — with every head's `sub`-square
    that starts at its column `at` under the kind's rule, which `seen(shape)`
    says from the square's own corner; every other entry as it is."""
    if s.shape[1] == sub:
        return jnp.where(seen(s.shape), s, MASKED)
    cut = functools.partial(lax.slice_in_dim, s, axis=1)
    pieces, done = [], 0
    for lo in range(at, s.shape[1], s.shape[1] // heads):
        square = cut(lo, lo + sub)
        square = jnp.where(seen(square.shape), square, MASKED)
        pieces += ([cut(done, lo)] if done < lo else []) + [square]
        done = lo + sub
    if done < s.shape[1]:
        pieces.append(cut(done, s.shape[1]))
    return jnp.concatenate(pieces, axis=1)


def _of_heads(ref, heads: int, rows: slice):
    """Rows `rows` of every one of the `heads` heads of a block `(1, G, t,
    d)`, a head after a head: `(heads * rows, d)`."""
    if heads == 1:
        return ref[0, 0, rows]
    x = ref[0, :, rows]
    return x.reshape(heads * x.shape[1], x.shape[2])


def _buffer(heads: int, *shape):
    """A float32 VMEM buffer of `shape` a head of a step's `heads`: with a
    leading axis of heads, but for one head (the buffer it had)."""
    return pltpu.VMEM(shape if heads == 1 else (heads, *shape), jnp.float32)


def _bd_fwd_kernel(qt_ref, kt_ref, what_ref, q_ref, k_ref, v_ref, *rest,
                   scale: float, t: int, block: int, kinds, heads: int):
    # (a schedule with DATA tiles brings the array that masks them)
    *bias_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    what = what_ref[pl.program_id(2)]

    @pl.when((what & _FIRST) != 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def each_head(body, unrolled: bool):
        if heads == 1 or unrolled:
            for g in range(heads):
                body(g)
        else:
            lax.fori_loop(0, heads, lambda g, _: body(g), None)

    def head(kind: int, g):
        sub, parts = _parts(kind, block, t, backward=False)
        seen = functools.partial(_seen, kind=kind, block=block, rows_are_keys=False)
        # the rectangles' scores side by side down the rows (one rectangle,
        # or squares of one width on rows that follow one another): one
        # update of the statistics whatever the cut
        squares = []
        for rows, keys, masked in parts:
            s = _dot(q_ref[0, g, rows], k_ref[0, 0, keys], _NT) * scale
            for _, at in masked:
                s = _masked_at(s, at, sub, seen)
            if kind == DATA:
                s = s + bias_ref[0][0].astype(jnp.float32)
            squares.append(s)
        s = _beside(squares, axis=0)
        rows = slice(parts[0][0].start, parts[-1][0].stop)
        mine = rows if heads == 1 else (g, rows)  # its rows of the statistics
        m_prev, l_prev = m_ref[mine], l_ref[mine]
        m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
        p = jnp.exp(s - _lanes(m_next, s.shape[-1]))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[mine] = alpha * l_prev + p.sum(axis=-1)[:, None]
        m_ref[mine] = m_next
        p = p.astype(v_ref.dtype)
        pv = _beside([_dot(p[r.start - rows.start:r.stop - rows.start],
                           v_ref[0, 0, keys], _NN) for r, keys, _ in parts], axis=0)
        acc_ref[mine] = _lanes(alpha, acc_ref.shape[-1]) * acc_ref[mine] + pv

    def step(kind: int):
        # a head after a head, each its own chain of product, maximum, `exp`,
        # sum, product. FULL — the kind most steps are — as `G` bodies, which
        # the scheduler runs one under another; a boundary's kinds as one body
        # in a loop, whose iterations do not overlap: `G` bodies of each were
        # three quarters of the program for a thirtieth of its time, and
        # set-up traces and lowers a program's equations (PERF.md section 6,
        # PR 50)
        each_head(functools.partial(head, kind), unrolled=kind in (FULL, DATA))

    _each_kind(what, kinds, step)

    @pl.when((what & _LAST) != 0)
    def _():
        def close(g):
            mine = Ellipsis if heads == 1 else g
            l = l_ref[mine]
            o_ref[0, g] = (acc_ref[mine] / _lanes(l, acc_ref.shape[-1])
                           ).astype(o_ref.dtype)
            # the statistics are columns, `lse` is stored as a row
            lse_ref[0, g] = (m_ref[mine] + jnp.log(l)).T[:1]

        each_head(close, unrolled=False)


def scheduled_forward(q, k, v, steps, *, scale: float, block: int, t: int,
                      kinds=BD_KINDS, name: str = "block_diffusion_attention_fwd",
                      heads: Optional[int] = None, interpret: bool = False,
                      bias=None):
    """(out (N, H, S, D) in `v.dtype`, lse (N, H, S) float32) of `q (N, H,
    S, D)` over `k, v (N, KV, S, D)` under the schedule `steps` ([(query
    tile, key tile, kind)], query-major, a query tile's first entry
    leaving no row without a visible key), its kinds among `kinds`; a grid
    step carries `heads` query heads of one key/value head (what
    `heads_a_step` says of the shapes, unless a test says). `bias (N, S, S)`
    (queries, keys) is added to the scores of the tiles of kind DATA: 0
    where a pair is allowed, `MASKED` where it is not."""
    n, h, s, d = q.shape
    group = h // k.shape[1]
    heads = heads or heads_a_step(group, t, d, s, bias is not None)
    assert group % heads == 0, (group, heads)
    assert (bias is not None) == (DATA in kinds), kinds
    tables = _tables(steps, kinds)
    masks, mask_specs = ((), ()) if bias is None else ((bias,), (pl.BlockSpec(
        (1, t, t), lambda n, h, i, qt, kt, what: (n, qt[i], kt[i])),))
    # `h` counts blocks of `heads` heads, `group // heads` of them a key/value head
    at_q = lambda n, h, i, qt, kt, what: (n, h, qt[i], 0)  # noqa: E731
    at_k = lambda n, h, i, qt, kt, what: (n, h // (group // heads), kt[i], 0)  # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(_bd_fwd_kernel, scale=scale, t=t, block=block,
                          kinds=kinds, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, h // heads, tables[0].shape[0]),
            in_specs=[pl.BlockSpec((1, heads, t, d), at_q),
                      pl.BlockSpec((1, 1, t, d), at_k),
                      pl.BlockSpec((1, 1, t, d), at_k), *mask_specs],
            out_specs=[pl.BlockSpec((1, heads, t, d), at_q),
                       pl.BlockSpec((1, heads, 1, t),
                                    lambda n, h, i, qt, kt, what: (n, h, 0, qt[i]))],
            scratch_shapes=[_buffer(heads, t, LANES), _buffer(heads, t, LANES),
                            _buffer(heads, t, d)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, v.dtype),
                   jax.ShapeDtypeStruct((n, h, 1, s), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*tables, q, k, v, *masks)
    return out, lse.reshape(n, h, s)


@functools.partial(jax.jit, static_argnames=("scale", "l", "block", "t", "interpret"))
def bd_forward(q, k, v, *, scale: float, l: int, block: int, t: int,
               interpret: bool = False):
    """(out (N, H, 2l, D) in `v.dtype`, lse (N, H, 2l) float32). A `jax.jit`
    of its own, as the three below: a model calls the pair once a layer
    with one signature, and a body of rectangles traced and lowered at
    every call site is set-up time (PERF.md section 6, PR 49)."""
    return scheduled_forward(q, k, v, schedule(l, t), scale=scale, block=block,
                             t=t, interpret=interpret)


def _bd_bwd_kernel(qt_ref, kt_ref, what_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, *rest, scale: float, t: int, block: int,
                   kinds, heads: int):
    # (a schedule with DATA tiles brings the array that masks them, keys down
    # its rows)
    *bias_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    g, i = pl.program_id(2), pl.program_id(3)
    what = what_ref[i]
    end = (g == pl.num_programs(2) - 1) & (i == pl.num_programs(3) - 1)

    @pl.when((g == 0) & (i == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((what & _FIRST) != 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(kind: int):
        sub, parts = _parts(kind, block, t, backward=True)
        seen = functools.partial(_seen, kind=kind, block=block, rows_are_keys=True)
        if heads > 1:
            # one mask for every square of the step that a boundary crosses,
            # whichever head's (a head a step keeps a mask a square: the
            # program it had)
            seen = functools.cache(seen)
        # keys on the sublanes: (keys, queries), the rectangles (a key group
        # each, or the tile) side by side along the lanes, each the step's
        # heads' queries a head after a head, for one pass of the arithmetic
        # between the products
        squares, dps, cols, width = [], [], [], 0
        for rows, keys, masked in parts:
            s = _dot(k_ref[0, 0, keys], _of_heads(q_ref, heads, rows), _NT) * scale
            for at, _ in masked:
                s = _masked_at(s, at, sub, seen, heads)
            if kind == DATA:  # the one tile, beside every head's scores
                s = s + _beside([bias_ref[0][0].astype(jnp.float32)] * heads,
                                axis=1)
            squares.append(s)
            dps.append(_dot(v_ref[0, 0, keys], _of_heads(do_ref, heads, rows), _NT))
            cols.append(slice(width, width + s.shape[1]))
            width += s.shape[1]
        along = lambda ref: _beside(  # noqa: E731
            [ref[0, j, :, rows] for rows, _, _ in parts for j in range(heads)],
            axis=1)
        p = jnp.exp(_beside(squares, axis=1) - along(lse_ref))
        ds = (p * (_beside(dps, axis=1) - along(delta_ref)) * scale
              ).astype(q_ref.dtype)
        p = p.astype(do_ref.dtype)
        for (rows, keys, _), c in zip(parts, cols):
            at = kt_ref[i] * t + keys.start if keys.start else kt_ref[i] * t
            at = pl.ds(pl.multiple_of(at, math.gcd(t, keys.start)),
                       keys.stop - keys.start)
            # the sum over the step's heads is the products' own: one
            # contraction over every head's queries, one read-modify-write
            dv_acc[at, :] += _dot(p[:, c], _of_heads(do_ref, heads, rows), _NN)
            dk_acc[at, :] += _dot(ds[:, c], _of_heads(q_ref, heads, rows), _NN)
            if heads == 1:
                dq_acc[rows] += _dot(ds[:, c], k_ref[0, 0, keys], _TN)
            else:  # a head after a head, as the product's rows are
                dq_acc[:, rows] += _dot(ds[:, c], k_ref[0, 0, keys], _TN).reshape(
                    heads, rows.stop - rows.start, -1)

    _each_kind(what, kinds, step)

    @pl.when((what & _LAST) != 0)
    def _():
        dq_ref[(0, 0) if heads == 1 else 0] = dq_acc[...].astype(dq_ref.dtype)

    @pl.when(end)
    def _():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def scheduled_backward(q, k, v, out, lse, d_out, steps, *, scale: float,
                       block: int, t: int, kinds=BD_KINDS,
                       name: str = "block_diffusion_attention_bwd",
                       heads: Optional[int] = None, interpret: bool = False,
                       bias_t=None):
    """(dq, dk, dv) in the dtypes of `q, k, v` under the schedule `steps`
    (`scheduled_forward`); `dk`, `dv` summed over each key/value head's
    group of query heads, `heads` of them a grid step. `bias_t (N, S, S)`:
    the forward's `bias` with the keys ahead of the queries, as the
    backward's scores lie."""
    n, h, s, d = q.shape
    kv = k.shape[1]
    group = h // kv
    heads = heads or heads_a_step(group, t, d, s, bias_t is not None)
    assert group % heads == 0, (group, heads)
    assert (bias_t is not None) == (DATA in kinds), kinds
    masks, mask_specs = ((), ()) if bias_t is None else ((bias_t,), (pl.BlockSpec(
        (1, t, t), lambda n, c, g, i, qt, kt, what: (n, kt[i], qt[i])),))
    blocks = group // heads  # of `heads` query heads, a key/value head
    delta = jnp.sum(out.astype(jnp.float32) * d_out.astype(jnp.float32),
                    axis=-1).reshape(n, h, 1, s)
    tables = _tables(steps, kinds)
    at_q = lambda n, c, g, i, qt, kt, what: (n, c * blocks + g, qt[i], 0)  # noqa: E731
    at_row = lambda n, c, g, i, qt, kt, what: (n, c * blocks + g, 0, qt[i])  # noqa: E731
    at_k = lambda n, c, g, i, qt, kt, what: (n, c, kt[i], 0)  # noqa: E731
    whole = lambda n, c, g, i, qt, kt, what: (n, c, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bd_bwd_kernel, scale=scale, t=t, block=block,
                          kinds=kinds, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n, kv, blocks, tables[0].shape[0]),
            in_specs=[pl.BlockSpec((1, heads, t, d), at_q),
                      pl.BlockSpec((1, 1, t, d), at_k),
                      pl.BlockSpec((1, 1, t, d), at_k),
                      pl.BlockSpec((1, heads, t, d), at_q),
                      pl.BlockSpec((1, heads, 1, t), at_row),
                      pl.BlockSpec((1, heads, 1, t), at_row), *mask_specs],
            out_specs=[pl.BlockSpec((1, heads, t, d), at_q),
                       pl.BlockSpec((1, 1, s, d), whole),
                       pl.BlockSpec((1, 1, s, d), whole)],
            scratch_shapes=[_buffer(heads, t, d),
                            pltpu.VMEM((s, d), jnp.float32),
                            pltpu.VMEM((s, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*tables, q, k, v, d_out, lse.reshape(n, h, 1, s), delta, *masks)


@functools.partial(jax.jit, static_argnames=("scale", "l", "block", "t", "interpret"))
def bd_backward(q, k, v, out, lse, d_out, *, scale: float, l: int, block: int,
                t: int, interpret: bool = False):
    """(dq, dk, dv) in the dtypes of `q, k, v`; `dk`, `dv` summed over each
    key/value head's group of query heads."""
    return scheduled_backward(q, k, v, out, lse, d_out, schedule(l, t),
                              scale=scale, block=block, t=t, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def block_diffusion_attention(q, k, v, scale: float, l: int, block: int,
                              t: int, otherwise: Callable):
    """`out (N, H, 2l, D)` of head-major `q (N, H, 2l, D)`, `k, v (N, KV,
    2l, D)` whose shapes `bd_tile` accepted (`t`): the kernels where the
    program is lowered for a TPU, `otherwise(q, k, v)` — the caller's
    plain-XLA form of the same mask — elsewhere. Residuals as
    `causal_attention` names them."""
    return _bd_forward_rule(q, k, v, scale, l, block, t, otherwise)[0]


def _bd_forward_rule(q, k, v, scale, l, block, t, otherwise):
    def plain(q, k, v):
        return otherwise(q, k, v), jnp.zeros(q.shape[:3], jnp.float32)

    out, lse = lax.platform_dependent(
        q, k, v, default=plain,
        tpu=functools.partial(bd_forward, scale=scale, l=l, block=block, t=t))
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    return out, (q, k, v, out, lse)


def _bd_backward_rule(scale, l, block, t, otherwise, residuals, d_out):
    def plain(q, k, v, out, lse, d_out):
        return jax.vjp(otherwise, q, k, v)[1](d_out)

    return lax.platform_dependent(
        *residuals, d_out, default=plain,
        tpu=functools.partial(bd_backward, scale=scale, l=l, block=block, t=t))


block_diffusion_attention.defvjp(_bd_forward_rule, _bd_backward_rule)


# ============================================= causal over grouped heads
#
# Two more schedules of the pair above, over one copy of the sequence: `q
# (N, H, S, D)` over `k, v (N, KV, S, D)` where query i sees key j iff
#
#     j <= i                        every key up to its own (`window` None)
#     i - window < j <= i           the `window` keys that end with its own
#
# (nn/afmoe.py mixes layers of both in one model). A window is a schedule,
# not a third pair of kernels: `causal_schedule` lists, for every query
# tile, its own tile first (UPTO at blocks of 1: the causal rule, under
# which every row sees its own key), then the tiles between, FULL, and —
# where the window's far end falls `window / t` tiles back — that tile
# under the one rule this mask adds, AFTER (a key strictly after the
# query's offset in its own tile: the keys of that tile still inside the
# window). A tile wholly outside the band is no grid step, forward or
# backward, and a call dispatches over the kinds its own schedule holds
# (two without a window, three with one).

def causal_tile(s: int, window: Optional[int], width: int) -> Optional[int]:
    """The square tile the kernels run `s` positions of heads `width` wide
    at under a window of `window` keys (None: every key before), or None
    where they do not take the shapes: the window's far end falls on a
    tile's edge, and `dk` and `dv` of one (sequence, key/value head) fit
    their buffers."""
    if width % LANES or 2 * (s * width * 4) > DQ_BUFFER_BYTES:
        return None
    for t in (512, 256, 128):
        if s % t == 0 and (window is None or (window > 0 and window % t == 0)):
            return t
    return None


def causal_schedule(s: int, t: int, window: Optional[int] = None):
    """[(query tile, key tile, kind)] of the `(s / t)^2` tiles that hold a
    pair the mask allows, query-major; a query tile's first entry is its
    own tile, where every row sees its own key."""
    n = s // t
    w = n if window is None else window // t
    steps = []
    for i in range(n):
        steps.append((i, i, UPTO))
        steps += [(i, j, FULL) for j in range(max(i - w + 1, 0), i)]
        if i >= w:
            steps.append((i, i - w, AFTER))
    return steps


def causal_tiles_visited(s: int, t: int, window: Optional[int] = None) -> int:
    """Of the `(s / t)^2` tiles of one (sequence, head)'s score square,
    those the kernels compute under this mask."""
    return len(causal_schedule(s, t, window))


def _causal_call(s: int, t: int, window: Optional[int]):
    """What both directions hand the pair: the schedule, and as keywords
    the tile, the mask's blocks of 1 and the kinds the schedule holds."""
    steps = causal_schedule(s, t, window)
    return steps, dict(
        t=t, block=1, kinds=tuple(sorted({kind for _, _, kind in steps})))


@functools.partial(jax.jit, static_argnames=("scale", "window", "t", "interpret"))
def gc_forward(q, k, v, *, scale: float, window: Optional[int], t: int,
               interpret: bool = False):
    """(out (N, H, S, D) in `v.dtype`, lse (N, H, S) float32)."""
    steps, call = _causal_call(q.shape[2], t, window)
    return scheduled_forward(q, k, v, steps, scale=scale, interpret=interpret,
                             name="grouped_causal_attention_fwd", **call)


@functools.partial(jax.jit, static_argnames=("scale", "window", "t", "interpret"))
def gc_backward(q, k, v, out, lse, d_out, *, scale: float,
                window: Optional[int], t: int, interpret: bool = False):
    """(dq, dk, dv) in the dtypes of `q, k, v`."""
    steps, call = _causal_call(q.shape[2], t, window)
    return scheduled_backward(q, k, v, out, lse, d_out, steps, scale=scale,
                              interpret=interpret,
                              name="grouped_causal_attention_bwd", **call)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def grouped_causal_attention(q, k, v, scale: float, window: Optional[int],
                             t: int, otherwise: Callable):
    """`out (N, H, S, D)` of head-major `q (N, H, S, D)`, `k, v (N, KV, S,
    D)` whose shapes `causal_tile` accepted (`t`), each query over the
    `window` keys that end with its own (None: every key up to its own):
    the kernels where the program is lowered for a TPU, `otherwise(q, k,
    v)` — the caller's plain-XLA form of the same mask — elsewhere.
    Residuals as `causal_attention` names them."""
    return _gc_forward_rule(q, k, v, scale, window, t, otherwise)[0]


def _gc_forward_rule(q, k, v, scale, window, t, otherwise):
    def plain(q, k, v):
        return otherwise(q, k, v), jnp.zeros(q.shape[:3], jnp.float32)

    out, lse = lax.platform_dependent(
        q, k, v, default=plain,
        tpu=functools.partial(gc_forward, scale=scale, window=window, t=t))
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    return out, (q, k, v, out, lse)


def _gc_backward_rule(scale, window, t, otherwise, residuals, d_out):
    def plain(q, k, v, out, lse, d_out):
        return jax.vjp(otherwise, q, k, v)[1](d_out)

    return lax.platform_dependent(
        *residuals, d_out, default=plain,
        tpu=functools.partial(gc_backward, scale=scale, window=window, t=t))


grouped_causal_attention.defvjp(_gc_forward_rule, _gc_backward_rule)


# ======================================= a selection over grouped heads
#
# The mask that is data (nn/keye_vl.py: a learned indexer chooses, for every
# query, the keys it attends to): the causal schedule again, every tile of it
# of one more kind, DATA, whose rule is no arithmetic on the tile's place but
# an array the call brings — `bias (N, S, S)`, queries by keys, one for all
# heads, 0 where the pair is allowed and `MASKED` where it is not (the causal
# rule folded in), added to the tile's scores; the backward reads it with the
# keys ahead (`bias_t`), as its scores lie. A row whose tile holds none of
# its keys passes through `exp(MASKED - m) = 0` like any masked stretch, and a
# row that has seen no key YET carries sums that the first allowed key's
# `alpha = 0` wipes. Every causal tile is a grid step: which tiles a
# selection leaves empty is known on the device alone (PERF.md section 7).

def selected_schedule(s: int, t: int):
    """[(query tile, key tile, DATA)] of the causal tiles, query-major."""
    return [(i, j, DATA) for i, j, _ in causal_schedule(s, t)]


@functools.partial(jax.jit, static_argnames=("scale", "t", "interpret"))
def sel_forward(q, k, v, bias, *, scale: float, t: int, interpret: bool = False):
    """(out (N, H, S, D) in `v.dtype`, lse (N, H, S) float32)."""
    return scheduled_forward(
        q, k, v, selected_schedule(q.shape[2], t), scale=scale, block=1, t=t,
        kinds=(DATA,), name="selected_attention_fwd", interpret=interpret,
        bias=bias)


@functools.partial(jax.jit, static_argnames=("scale", "t", "interpret"))
def sel_backward(q, k, v, bias_t, out, lse, d_out, *, scale: float, t: int,
                 interpret: bool = False):
    """(dq, dk, dv) in the dtypes of `q, k, v`."""
    return scheduled_backward(
        q, k, v, out, lse, d_out, selected_schedule(q.shape[2], t), scale=scale,
        block=1, t=t, kinds=(DATA,), name="selected_attention_bwd",
        interpret=interpret, bias_t=bias_t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def selected_attention(q, k, v, bias, scale: float, t: int, otherwise: Callable):
    """(`out (N, H, S, D)`, `lse (N, H, S)` float32 — the rows' log-sum-exp
    over their allowed keys, for a caller that wants the probabilities
    again; no gradient passes it) of head-major `q (N, H, S, D)`, `k, v (N,
    KV, S, D)` whose shapes `causal_tile(S, None, D)` accepted (`t`) under
    `bias (N, S, S)` (above; no gradient either): the kernels where the
    program is lowered for a TPU, `otherwise(q, k, v, bias)` — the caller's
    plain-XLA form, which returns both — elsewhere. Residuals as
    `causal_attention` names them."""
    return _sel_forward_rule(q, k, v, bias, scale, t, otherwise)[0]


def _sel_forward_rule(q, k, v, bias, scale, t, otherwise):
    out, lse = lax.platform_dependent(
        q, k, v, bias, default=otherwise,
        tpu=functools.partial(sel_forward, scale=scale, t=t))
    out = checkpoint_name(out, RESIDUAL_NAME)
    lse = checkpoint_name(lse, RESIDUAL_NAME)
    return (out, lse), (q, k, v, bias, out, lse)


def _sel_backward_rule(scale, t, otherwise, residuals, cotangents):
    q, k, v, bias, out, lse = residuals
    d_out, _ = cotangents

    def plain(q, k, v, bias, out, lse, d_out):
        return jax.vjp(lambda q, k, v: otherwise(q, k, v, bias)[0], q, k, v)[1](d_out)

    def kernels(q, k, v, bias, out, lse, d_out):
        return sel_backward(q, k, v, jnp.swapaxes(bias, 1, 2), out, lse, d_out,
                            scale=scale, t=t)

    return (*lax.platform_dependent(
        q, k, v, bias, out, lse, d_out, default=plain, tpu=kernels),
        jnp.zeros_like(bias))


selected_attention.defvjp(_sel_forward_rule, _sel_backward_rule)
