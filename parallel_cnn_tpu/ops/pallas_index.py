"""The lightning indexer's scores as one fused TPU kernel a direction
(Pallas/Mosaic): for index queries `q (N, H, rows, d)` — one block of
queries, every index head —, weights `w (N, rows, H)`, index keys `k (N,
keys, d)` shared by the heads, the block's first position `at` and a
static `c`,

    z[h]   = q[h] k^T                           (rows, keys) a head
    I      = c * sum_h relu(z[h]) * w[:, h]     (N, rows, keys) float32

the products from the operands' dtype with float32 accumulation, the ReLU,
the weighing and the sum over the heads in float32 — what
`nn/keye_vl.py:Indexer.scores` composes in plain XLA, which for a TPU
writes `z`, a float32 `(H, rows, keys)` array, to HBM and reads it back:
268 MB a block of 256 queries at 16,384 keys and sixteen heads, 21 GB a
pass over a layer's blocks for products the MXU does in 3.5 ms (PERF.md
section 5). Here `z` never leaves VMEM:

  forward   grid (N, key tiles). The block's queries, all heads (`(H, rows,
            d)`: 512 KB of bf16), and the weights stay in VMEM along the
            key axis; a grid step takes a tile of `t` keys, forms `z[h]`
            head by head on the MXU and adds `relu(z[h]) * w[:, h]` to its
            float32 `(rows, t)` output block, which leaves once, times `c`.
  backward  ONE kernel, the same grid, from the cotangent `dI (N, rows,
            keys)` float32 and the forward's operands alone — `z` is made
            again, nothing of its size is kept. With

                g[h] = c * dI * w[:, h] * [z[h] > 0]      (float32)

            (`jax.nn.relu`'s gradient: 0 at `z = 0`), rounded to the
            operands' dtype as an MXU operand — on the v5e the plain
            path's products, a float32 `g` beside a bf16 operand at
            `DEFAULT` precision, are equal to the bit to the products of
            `g` rounded to bf16 (PERF.md section 6, PR 52) —

                dq[h]    = g[h] k
                dk^T     = sum_h q[h]^T g[h]
                dw[:, h] = c * sum_keys dI * relu(z[h])

            `dq` and `dw` accumulate in float32 over the key tiles in
            output blocks that stay in VMEM (`dw` as 128 partial sums a
            (query, head), one a lane, so that a tile adds whole registers
            and reduces across no lane; the sum over them is outside, 2
            MB); `dk` is a tile's own, made TRANSPOSED from `q^T` (handed
            in: 0.5 MB turned outside) so that `g`, a tile's largest
            array, is never turned on the transpose unit, and turned back
            outside. Returned in the operands' dtypes.

A block of queries at `at` has no use for a key after its last query, `at
+ rows - 1`: both of the indexer's callers mask those. A key tile that
lies wholly past it is SKIPPED — `at` is a scalar the grid is handed ahead
of its steps (scalar prefetch), the step fetches nothing (the index maps
stay on the last tile that is needed) and writes zeros — so what
`index_scores` returns there is unspecified: zeros from the kernels, the
scores themselves from the plain form. Its callers mask both.

Inside a step the heads are a `lax.fori_loop` whose turn writes out
several: eight forward, four backward (`_turns`). On the v5e a forward pass
over a layer's blocks read 8.3 ms with one head a turn and the sum its
carry (the loop copies 1 MB a turn), 6.9 with the sum in the output block,
4.8 at eight a turn and 4.6 with all sixteen written out — one head's
product overlaps the head before's vector work — and the backward 11.1,
9.9 at four and 9.5 (PERF.md section 6, PR 52). All sixteen written out
were 36 MB of code a step program — its 96 kernel calls each carry their
own — and put the cell's peak memory above its parent's; eight and four
leave the program 3 MB smaller than the parent's for 1 ms a layer, 0.9 % of
the cell's rate. Mosaic (jax 0.9.0) unrolls a loop wholly or not at all,
hence the turns.
`forward`, `backward` and `either` are `jax.jit`s of their own: a model
calls them from four scans a layer (one a band of keys), and jit's caches
make that one trace a signature and process.

Which path runs is decided by what the code can see, never by an option:
`tile(rows, keys, d)` gives the key tile for shapes the kernels take and
None otherwise; where the shapes tile, the platform is decided where the
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
NAME = "index_scores"  # the kernels are NAME_fwd and NAME_bwd in a program's text
VMEM_LIMIT_BYTES = 64 << 20
# Key tiles, the widest that divides the keys: on the v5e at the cell's
# shapes 512 a step was 16 % slower forward than 1,024 and 2,048 5 % faster
# for twice the VMEM and a coarser skip (PERF.md section 6, PR 52).
TILES = (1024, 512, 256)

# Heads a turn of a kernel's loop over them writes out (module docstring).
FORWARD_HEADS_A_TURN = 8
BACKWARD_HEADS_A_TURN = 4

_NN = (((1,), (0,)), ((), ()))  # a (m, k) x b (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))  # a (m, d) x b (n, d) -> (m, n)


def _dot(a, b, dims):
    """One MXU product with a float32 result, the precision said (Mosaic
    refuses a bf16 product asked for at `highest`)."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.DEFAULT,
                           preferred_element_type=jnp.float32)


def tile(rows: int, keys: int, d: int) -> Optional[int]:
    """The keys a grid step of the kernels holds for a block of `rows`
    queries against `keys` keys of `d` features, or None where they do not
    take the shapes: whole registers of queries (they are the lanes of the
    backward's `q^T`) and of keys, a contraction the MXU takes whole."""
    if rows % LANES or d % 64:
        return None
    return next((t for t in TILES if keys % t == 0), None)


def _turns(heads: int, most: int, body, carry=None):
    """`body(h, carry) -> carry` for every head: a `lax.fori_loop` whose turn
    writes out the most heads, up to `most`, that divide them (module
    docstring)."""
    group = max(g for g in range(1, most + 1) if heads % g == 0)

    def turn(j, carry):
        for u in range(group):
            carry = body(j * group + u, carry)
        return carry

    return lax.fori_loop(0, heads // group, turn, carry)


def _fwd_kernel(last_ref, q_ref, w_ref, k_ref, i_ref, *, c: float):
    needed = pl.program_id(1) <= last_ref[0]

    @pl.when(needed)
    def _():
        k = k_ref[0]
        i_ref[...] = jnp.zeros_like(i_ref)

        def head(h, _):
            i_ref[0] += jnp.maximum(_dot(q_ref[0, h], k, _NT), 0.0) * w_ref[0, h]

        _turns(q_ref.shape[1], FORWARD_HEADS_A_TURN, head)
        i_ref[0] = i_ref[0] * c

    @pl.when(jnp.logical_not(needed))
    def _():
        i_ref[...] = jnp.zeros_like(i_ref)


def _bwd_kernel(last_ref, q_ref, qt_ref, w_ref, di_ref, k_ref, dq_ref, dw_ref,
                dkt_ref, *, c: float):
    j = pl.program_id(1)
    needed = j <= last_ref[0]

    @pl.when(j == 0)
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(needed)
    def _():
        k = k_ref[0]
        di = di_ref[0] * c

        def head(h, dkt):
            z = _dot(q_ref[0, h], k, _NT)
            g = jnp.where(z > 0.0, di * w_ref[0, h], 0.0).astype(k.dtype)
            dq_ref[0, h] += _dot(g, k, _NN)
            weighed = jnp.maximum(z, 0.0) * di
            # a sum a lane: whole registers added, no lane reduced
            dw_ref[0, h] += sum(weighed[:, lo:lo + LANES]
                                for lo in range(0, di.shape[1], LANES))
            # `dk^T = q^T g`: `g^T q` would turn `g`, this tile's largest
            # array, on the transpose unit, a head at a time
            return dkt + _dot(qt_ref[0, h], g, _NN)

        dkt = _turns(q_ref.shape[1], BACKWARD_HEADS_A_TURN, head,
                     jnp.zeros(dkt_ref.shape[1:], jnp.float32))
        dkt_ref[0] = dkt.astype(dkt_ref.dtype)

    @pl.when(jnp.logical_not(needed))
    def _():
        dkt_ref[...] = jnp.zeros_like(dkt_ref)


def _whole(*shape):
    """The spec of an array `(N, *shape)` that stays in VMEM along the keys."""
    return pl.BlockSpec((1, *shape), lambda n, j, last: (n,) + (0,) * len(shape))


def _call(kernel, name: str, q, k, at, t: int, c: float, more_in, out_specs,
          out_shape, interpret: bool):
    """`kernel` over the grid `(N, key tiles)`, called: `q (N, H, rows, d)`
    whole, `more_in` (pairs of spec and operand), then `k (N, keys, d)` a
    tile of `t` keys a step; ahead of the steps, the last key tile the block
    at `at` needs. Index maps take `(n, j, last)`."""
    n, heads, rows, d = q.shape
    specs, operands = zip(*more_in)
    # past the last tile needed a step fetches nothing: the map stays on it
    key_tiles = pl.BlockSpec(
        (1, t, d), lambda n, j, last: (n, jnp.minimum(j, last[0]), 0))
    return pl.pallas_call(
        functools.partial(kernel, c=c),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, k.shape[1] // t),
            in_specs=[_whole(heads, rows, d), *specs, key_tiles],
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(jnp.reshape((at + rows - 1) // t, (1,)).astype(jnp.int32), q, *operands, k)


def _query_tiles(rows: int, t: int):
    """The spec of a `(N, rows, keys)` array a tile of `t` keys a step."""
    return pl.BlockSpec((1, rows, t), lambda n, j, last: (n, 0, j))


def _columns(weight):
    """(spec, `weight (N, rows, H)` as float32 columns `(N, H, rows, 1)`): a
    head's is what a tile's `(rows, t)` scores are weighed by, lane for
    lane."""
    _, rows, heads = weight.shape
    return (_whole(heads, rows, 1),
            jnp.swapaxes(weight, 1, 2).astype(jnp.float32)[..., None])


@functools.partial(jax.jit, static_argnames=("c", "t", "interpret"))
def forward(q, weight, k, at, *, c: float, t: int, interpret: bool = False):
    """The forward kernel: `I (N, rows, keys)` float32. `tile` took the
    shapes (`t`); `at`: the block's first position, an integer scalar."""
    n, _, rows, _ = q.shape
    return _call(
        _fwd_kernel, NAME + "_fwd", q, k, at, t, c, [_columns(weight)],
        _query_tiles(rows, t),
        jax.ShapeDtypeStruct((n, rows, k.shape[1]), jnp.float32), interpret)


@functools.partial(jax.jit, static_argnames=("c", "t", "interpret"))
def backward(q, weight, k, at, d_i, *, c: float, t: int, interpret: bool = False):
    """The backward kernel: (`dq`, `dw`, `dk`) in the dtypes of `q`,
    `weight`, `k` from the forward's operands and `d_i (N, rows, keys)`."""
    n, heads, rows, d = q.shape
    keys = k.shape[1]
    dq, dw, dkt = _call(
        _bwd_kernel, NAME + "_bwd", q, k, at, t, c,
        [(_whole(heads, d, rows), jnp.swapaxes(q, 2, 3)), _columns(weight),
         (_query_tiles(rows, t), d_i.astype(jnp.float32))],
        [_whole(heads, rows, d), _whole(heads, rows, LANES),
         # every tile is written: zeros past the last one needed
         pl.BlockSpec((1, d, t), lambda n, j, last: (n, 0, j))],
        [jax.ShapeDtypeStruct(q.shape, jnp.float32),
         jax.ShapeDtypeStruct((n, heads, rows, LANES), jnp.float32),
         jax.ShapeDtypeStruct((n, d, keys), k.dtype)], interpret)
    dw = jnp.swapaxes(jnp.sum(dw, axis=-1), 1, 2)
    return dq.astype(q.dtype), dw.astype(weight.dtype), jnp.swapaxes(dkt, 1, 2)


@functools.partial(jax.jit, static_argnames=("c", "t", "back", "otherwise"))
def either(*operands, c: float, t: int, back: bool, otherwise: Callable):
    """One direction — `(q, weight, k, at)` forward, `(q, weight, k, at,
    d_i)` backward — by the kernel where the program is lowered for a TPU;
    elsewhere by `otherwise(q, weight, k)`, the caller's plain-XLA form of
    the same function, or autodiff of it. A `jax.jit` with the caller's
    function as a static argument: both branches are traced once a
    signature, not at every call site of a step."""
    if back:
        kernel = functools.partial(backward, c=c, t=t)
        default = lambda q, weight, k, at, d_i: jax.vjp(  # noqa: E731
            otherwise, q, weight, k)[1](d_i)
    else:
        kernel = functools.partial(forward, c=c, t=t)
        default = lambda q, weight, k, at: otherwise(q, weight, k)  # noqa: E731
    return lax.platform_dependent(*operands, tpu=kernel, default=default)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def index_scores(q, weight, k, at, c: float, t: int, otherwise: Callable):
    """`I` of the module docstring for shapes `tile` took (`t`), a block of
    queries whose first stands at `at`; `otherwise(q, weight, k)` is the
    same function in plain XLA (a function that stays the same object from
    call to call: it is `either`'s static argument). Columns past `at +
    rows - 1` are the caller's to mask (module docstring). The rules, not
    autodiff, meet the platform's branch, and the backward keeps the
    operands alone."""
    return either(q, weight, k, at, c=c, t=t, back=False, otherwise=otherwise)


def _forward_rule(q, weight, k, at, c, t, otherwise):
    return (either(q, weight, k, at, c=c, t=t, back=False, otherwise=otherwise),
            (q, weight, k, at))


def _backward_rule(c, t, otherwise, kept, d_i):
    return (*either(*kept, d_i, c=c, t=t, back=True, otherwise=otherwise), None)


index_scores.defvjp(_forward_rule, _backward_rule)
