"""The delta rule's chunked scan (ops/kda.py: the mathematics, the chunk,
the sub-chunk, the span and what bounds the decay are that module's) as
two fused TPU kernels (Pallas/Mosaic) whose tables and state never leave
VMEM. For head-major `q, k (N, H, S, Dk)`, `v (N, H, S, Dv)`, a float32
log-decay `g (N, H, S, Dk)` and `beta (N, H, S)`:

  forward   grid (N, H, spans), the spans in order ("arbitrary"). A grid
            step takes one span (`span` chunks of `chunk` positions) of
            one head as blocks of `q, k, v, g` straight from the
            head-major arrays and `beta` as a row; the float32 `(Dk, Dv)`
            state lives in a VMEM scratch, zeroed at a head's first span.
            The step first writes the state it starts from (the backward's
            residual), then walks its chunks two at a time: their tables
            (the running decay, the two pair tables around sub-chunk
            midpoints, the triangular inverse by squarings, `T V`, `T (K .
            e^G)`) are values in VMEM, then each chunk's three products
            with the state and the state's update. HBM sees `q, k, v, g,
            beta` once in, `o` and the span-start states once out.
  backward  the same grid walked from the last span (the index maps
            reverse it), `d_state` in the scratch. A step reloads its
            span-start state, runs its chunks forward again — tables and
            chunk-start states stay in VMEM as the residuals of
            `jax.vjp` of `_two_chunks`, a pure function of loaded values —
            then pulls the pairs back from the last: autodiff's transpose
            of the forward's arithmetic but for the triangular inverse,
            whose backward is its own (`_inverse`), with nothing written
            between. HBM sees the forward's inputs, the kept states and
            `d_o` once in and the five gradients once out.

**Two chunks side by side.** A chunk's tables are `(64, 64)`: half a
register's lanes, a quarter of a 128 x 128 MXU pass, and the chain of ten
dependent products that inverts `I - N` pays a pass's fixed cost for each.
So two consecutive chunks' tables stand side by side in the lanes, `(64,
128)`: `X @ _diagonal(Y)` multiplies each chunk's `X` by its own `Y`, one
product of full registers for two chunks; a sub-chunk's pair terms are
one product of both chunks' rows against both chunks' keys, of which
`_side_by_side` keeps the two diagonal blocks; and block-diagonal `(128,
128)`, `T` and `B` multiply both chunks' 128 rows at once. The zeros are
exact and the sums' terms are the plain body's; only the products with the
state stay a chunk at a time (the second chunk's start from the first's).
On the chip at `(1, 32, 8192, 128)` that took a layer's forward from 8.7
to 6.3 ms and its backward from 17.4 to 14.1 (the plain body: 8.1 and
27.1; PERF.md section 6, PR 44).

What a kernel body may not do on Mosaic shaped the function: every
product is rank-2 a side; there is no `cumsum` (the running sum of `g` is
a block-lower-triangular matrix of ones times `g`, exact at the highest
precision: the ones are bf16 numbers); no rank-1 value and no
lane-splitting reshape (`beta` arrives as a `(1, span * chunk)` row and a
chunk's column of it — and the column of `e^G_end` that scales the
state's rows — is picked out of the broadcast row by an iota mask and a
lane sum, which is exact); the sub-chunks are static row slices at
multiples of 16, whole float32 sublane tiles; moving a table between its
side-by-side and block-diagonal forms is a sublane concatenation or two
row slices under a lane mask, never a lane shift.

Precision is `ops/kda.py`'s: float32 factors and pair tables, the running
sum, `A`, `B`, the inverse, `T V` and `T (K . e^G)` at
`Precision.HIGHEST` (six bf16 passes); the products with the state say no
precision and so take the one the step is lowered with, as the plain
body's do (Mosaic rounds float32 operands to bf16 unless asked for more).

`forward` and `backward` are `jax.jit`s of their own: a model calls each
once a layer with one signature, and jit's caches make that one trace a
process (PERF.md section 6, PR 37 and PR 42). `tiles` says which shapes
the kernels take; ops/kda.py asks it and decides the platform where the
program is lowered.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
NAME = "kda_scan"  # the kernels are NAME_fwd and NAME_bwd in a program's text
# A span's blocks are under 2 MB double-buffered; what the limit holds is
# the backward's residuals, a few dozen (128, 128) float32 values a pair of
# chunks.
VMEM_LIMIT_BYTES = 64 << 20
_EXACT = lax.Precision.HIGHEST
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)

_NN = (((1,), (0,)), ((), ()))  # a (m, k) x b (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))  # a (m, d) x b (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # a (k, m) x b (k, n) -> (m, n)


def tiles(s: int, dk: int, dv: int, chunk: int, subchunk: int,
          span: int) -> bool:
    """Whether the kernels take `s` positions of heads `dk` and `dv` wide
    at this chunk, sub-chunk and span: whole 128-lane registers a head,
    the one (chunk, sub-chunk) Mosaic has compiled and the chip has run
    them at (two chunks' tables fill a register's lanes), whole pairs of
    chunks a span, and `s` a whole number of spans."""
    return (dk % LANES == 0 and dv % LANES == 0
            and (chunk, subchunk) == (64, 16)
            and span % 2 == 0 and s % (span * chunk) == 0)


def _dot(a, b, dims):
    """A product of the tables: float32 at the highest precision."""
    return lax.dot_general(a, b, dims, precision=_EXACT,
                           preferred_element_type=jnp.float32)


def _state_dot(a, b, dims):
    """A product with the state: it says no precision and so takes the one
    the step is lowered with, as the plain body's do."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _column(row, first: int, n: int):
    """Lanes `[first, first + n)` of `row (1, w)` as a column `(n, 1)`:
    the broadcast row under an iota mask, summed along the lanes (every
    sum has one term)."""
    at = (n, row.shape[1])
    here = lax.broadcasted_iota(jnp.int32, at, 1) == (
        lax.broadcasted_iota(jnp.int32, at, 0) + first)
    return jnp.sum(jnp.where(here, row, 0.0), axis=1, keepdims=True)


def _second(shape, c: int):
    """Where the lanes of `shape` are the second chunk's (from `c` on)."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) >= c


def _side_by_side(x):
    """The two diagonal blocks of `x (2R, 2C)` side by side `(R, 2C)`: the
    first chunk's rows against the first chunk's lanes, then the second's
    against the second's."""
    r, c = x.shape[0] // 2, x.shape[1] // 2
    return jnp.where(_second((r, 2 * c), c), x[r:], x[:r])


def _diagonal(x, c: int):
    """`_side_by_side` back: two `(C, C)` tables side by side as one
    block-diagonal `(2C, 2C)`."""
    at = (2 * c, 2 * c)
    same = (lax.broadcasted_iota(jnp.int32, at, 0) >= c) == _second(at, c)
    return jnp.where(same, jnp.concatenate([x, x]), 0.0)


@jax.custom_vjp
def _inverse(nil):
    """`(I - N)^-1` of two chunks' strictly lower triangular `N (C, C)`
    side by side `(C, 2C)`: `N^C = 0`, so it is `(I + N)(I + N^2)(I +
    N^4)...`, `log2(C) - 1` squarings and as many products (ops/kda.py,
    "`T`"); a product with the block diagonal of its right factor squares
    both chunks' at once, on full registers. The backward is the inverse's
    own — `dN = M^T dM M^T` for `M = (I - N)^-1`: three products where the
    transpose of the squarings is twenty, and nothing kept but `M`."""
    c = nil.shape[0]
    col = lax.broadcasted_iota(jnp.int32, nil.shape, 1)
    eye = jnp.where(lax.broadcasted_iota(jnp.int32, nil.shape, 0)
                    == col - jnp.where(col >= c, c, 0), 1.0, 0.0
                    ).astype(nil.dtype)
    inv = eye + nil
    for _ in range(c.bit_length() - 2):
        nil = _dot(nil, _diagonal(nil, c), _NN)
        inv = _dot(inv, _diagonal(eye + nil, c), _NN)
    return inv


def _inverse_forward(nil):
    inv = _inverse(nil)
    return inv, inv


def _inverse_backward(inv, d_inv):
    c = inv.shape[0]
    # `M^T (dM M^T)` of every pair of blocks; the two on the diagonal
    return (_side_by_side(
        _dot(inv, _dot(d_inv, _diagonal(inv, c), _NT), _TN)),)


_inverse.defvjp(_inverse_forward, _inverse_backward)


def _pair_tables(kf, qf, run, c: int, subchunk: int):
    """`sum_c x_tc e^(G_tc - G_jc) k_jc` for `x` the keys and the queries
    `(2C, Dk)` of two chunks, each chunk's table in its half of the lanes:
    ((C, 2C), (C, 2C)), unmasked but for the keys after `t`'s sub-chunk,
    whose factor is 0 (set before the exponential). The decay is split
    around the middle of `t`'s sub-chunk (ops/kda.py, "The pair terms").
    One product a sub-chunk: both chunks' rows against both chunks' keys
    on one set of weights, of which the two diagonal blocks are kept (the
    other two pair a chunk's rows with the other's keys: finite, by the
    same bound, and dropped)."""
    at = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    of_keys, of_queries = [], []
    for a in range(c // subchunk):
        rows, keys = [], []
        for first in (0, c):
            sub = slice(first + a * subchunk, first + (a + 1) * subchunk)
            own = slice(first, first + c)
            ref = first + a * subchunk + (subchunk - 1) // 2
            mid = run[ref:ref + 1]
            left = jnp.exp(run[sub] - mid)
            rows += [kf[sub] * left, qf[sub] * left]
            keys.append(kf[own] * jnp.exp(jnp.where(
                at < (a + 1) * subchunk, mid - run[own], -jnp.inf)))
        both = _side_by_side(_dot(jnp.concatenate(rows),
                                  jnp.concatenate(keys), _NT))
        of_keys.append(both[:subchunk])
        of_queries.append(both[subchunk:])
    return jnp.concatenate(of_keys), jnp.concatenate(of_queries)


def _two_chunks(state, q, k, v, g, beta, *, first: int, chunk: int,
                subchunk: int):
    """Two consecutive chunks from `state (Dk, Dv)` float32: `q, k (2C,
    Dk)`, `v (2C, Dv)`, `g (2C, Dk)` float32, and `beta (1, W)` float32,
    the span's row whose lanes from `first` are these chunks'. (state
    after them, `o (2C, Dv)` float32): ops/kda.py's `_tables` and two turns
    of `_span`'s loop for one head, every value rank 2. The chunks' `(C,
    C)` tables stand side by side in the lanes `(C, 2C)`, so the pair
    tables, `N` and the chain of squarings are made once for both on full
    registers; block-diagonal `(2C, 2C)`, `T` and `B` multiply both chunks'
    rows in one product."""
    f32 = jnp.float32
    c = chunk
    kf, qf, vf = k.astype(f32), q.astype(f32), v.astype(f32)
    row = lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 0)
    col = lax.broadcasted_iota(jnp.int32, (2 * c, 2 * c), 1)
    same = (row >= c) == (col >= c)
    # the running sum of `g` inside each chunk: ones are bf16 numbers
    run = _dot(jnp.where(same & (row >= col), 1.0, 0.0).astype(f32), g, _NN)
    t = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    second = _second((c, 2 * c), c)
    j = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) - jnp.where(second, c, 0)
    columns = [_column(beta, first + h * c, c) for h in range(2)]
    of_keys, of_queries = _pair_tables(kf, qf, run, c, subchunk)
    inv = _diagonal(_inverse(jnp.where(
        t > j, -jnp.where(second, columns[1], columns[0]) * of_keys, 0.0)), c)
    decay = jnp.exp(run)
    b = jnp.concatenate(columns)
    tv = _dot(inv, b * vf, _NN)
    tk = _dot(inv, b * (kf * decay), _NN)
    reads = _diagonal(jnp.where(t >= j, of_queries, 0.0), c)
    qd, outs = qf * decay, []
    for h in range(2):
        rows = slice(h * c, (h + 1) * c)
        last = run[(h + 1) * c - 1:(h + 1) * c]
        u = tv[rows] - _state_dot(tk[rows], state, _NN)
        # (the other chunk's half of `reads[rows]` is 0: any finite rows do)
        outs.append(_state_dot(qd[rows], state, _NN) + _state_dot(
            reads[rows], jnp.concatenate([u, u]), _NN))
        state = state * _column(jnp.exp(last), 0, last.shape[1]) + _state_dot(
            kf[rows] * jnp.exp(last - run[rows]), u, _TN)
    return state, jnp.concatenate(outs)


def _pairs(refs, chunk: int, subchunk: int):
    """(rows, function of the state, its other arguments) for each pair of
    chunks of a span whose blocks `refs` (`q, k, v, g, beta`) hold."""
    *wide, beta_ref = refs
    beta = beta_ref[0, 0].astype(jnp.float32)
    for first in range(0, beta.shape[1], 2 * chunk):
        rows = slice(first, first + 2 * chunk)
        yield rows, functools.partial(
            _two_chunks, first=first, chunk=chunk, subchunk=subchunk
        ), (*(ref[0, 0, rows] for ref in wide), beta)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, start_ref,
                state_ref, *, chunk: int, subchunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    start_ref[0, 0, 0] = state
    for rows, step, args in _pairs((q_ref, k_ref, v_ref, g_ref, beta_ref),
                                   chunk, subchunk):
        state, o = step(state, *args)
        o_ref[0, 0, rows] = o.astype(o_ref.dtype)
    state_ref[...] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref,
                *, chunk: int, subchunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    state, pulls = start_ref[0, 0, 0], []
    for rows, step, args in _pairs((q_ref, k_ref, v_ref, g_ref, beta_ref),
                                   chunk, subchunk):
        (state, _), pull = jax.vjp(step, state, *args)
        pulls.append((rows, pull))
    d_state, d_beta = dstate_ref[...], jnp.zeros(beta_ref.shape[2:], jnp.float32)
    for rows, pull in reversed(pulls):
        d_state, *wide, at_beta = pull(
            (d_state, do_ref[0, 0, rows].astype(jnp.float32)))
        for ref, d in zip((dq_ref, dk_ref, dv_ref, dg_ref), wide):
            ref[0, 0, rows] = d.astype(ref.dtype)
        d_beta += at_beta
    dbeta_ref[0, 0] = d_beta.astype(dbeta_ref.dtype)
    dstate_ref[...] = d_state


def _specs(s, dk, dv, chunk, span, back: bool):
    """The block of a grid step `(n, h, j)` in each kind of array: the
    wide ones of `dk` and of `dv` features, `beta`'s row, the kept states;
    the `j`-th span from the last if `back`."""
    steps, width = s // (span * chunk), span * chunk
    at = (lambda j: steps - 1 - j) if back else (lambda j: j)
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (1, 1, width, d), lambda n, h, j: (n, h, at(j), 0))
    return (wide(dk), wide(dv),
            pl.BlockSpec((1, 1, 1, width), lambda n, h, j: (n, h, 0, at(j))),
            pl.BlockSpec((1, 1, 1, dk, dv), lambda n, h, j: (at(j), n, h, 0, 0)))


@functools.partial(jax.jit, static_argnames=("chunk", "subchunk", "span",
                                             "interpret"))
def forward(q, k, v, g, beta, *, chunk: int, subchunk: int, span: int,
            interpret: bool = False):
    """(`o (N, H, S, Dv)` in `v.dtype`, the states the spans start from
    `(S / (span * chunk), N, H, Dk, Dv)` float32). `tiles` took the
    shapes; `g` is float32."""
    n, h, s, dk = q.shape
    dv = v.shape[-1]
    steps = s // (span * chunk)
    keys, values, row, kept = _specs(s, dk, dv, chunk, span, back=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, subchunk=subchunk),
        grid=(n, h, steps),
        in_specs=[keys, keys, values, keys, row],
        out_specs=[values, kept],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((steps, n, h, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=NAME + "_fwd",
    )(q, k, v, g, beta.reshape(n, h, 1, s))


@functools.partial(jax.jit, static_argnames=("chunk", "subchunk", "interpret"))
def backward(q, k, v, g, beta, starts, d_o, *, chunk: int, subchunk: int,
             interpret: bool = False):
    """The gradients of `q, k, v, g, beta`, each in its input's dtype, from
    the forward's inputs, its kept `starts` and `d_o (N, H, S, Dv)`."""
    n, h, s, dk = q.shape
    dv = v.shape[-1]
    steps = starts.shape[0]
    span = s // (steps * chunk)
    keys, values, row, kept = _specs(s, dk, dv, chunk, span, back=True)
    *wide, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, subchunk=subchunk),
        grid=(n, h, steps),
        in_specs=[keys, keys, values, keys, row, kept, values],
        out_specs=[keys, keys, values, keys, row],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, g)]
        + [jax.ShapeDtypeStruct((n, h, 1, s), beta.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name=NAME + "_bwd",
    )(q, k, v, g, beta.reshape(n, h, 1, s), starts, d_o)
    return (*wide, d_beta.reshape(n, h, s))
