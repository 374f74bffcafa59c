"""The delta rule with a channel-wise decay (KDA: Kimi Linear
arXiv:2510.26692 section 3; the chunked WY form is Gated DeltaNet's,
arXiv:2412.06464) as a chunked scan: this module's plain XLA body
anywhere, ops/pallas_kda.py's two kernels where the shapes tile and the
program is lowered for a TPU ("Which body runs", below). For head-major
`q, k (N, H, S, Dk)`, `v (N, H, S, Dv)`, a log-decay `g (N, H, S, Dk)` <= 0 in
float32 and `beta (N, H, S)`, every (sequence, head) runs

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                              S_0 = 0, S (Dk, Dv)

`recurrent_kda` is that, a position at a time. `chunked_kda` gives the
same `o` and never holds a state per token. With `G_t` the running sum of
`g` inside a chunk of `chunk` positions that starts from state `S`,

    U = T (V - (K . e^G) S)     T = (I + Diag(beta) A)^-1 Diag(beta)
    O = (Q . e^G) S + B U       A_tj = sum_c k_tc k_jc e^(G_tc - G_jc), j <  t
    S' = Diag(e^G_end) S        B_tj = sum_c q_tc k_jc e^(G_tc - G_jc), j <= t
         + (K . e^(G_end - G))^T U

`A`, `B`, `T`, `T V` and `T (K . e^G)` do not depend on `S`: they are made
for every chunk at once, in batched matmuls; only the three products with
`S` and `B U` run chunk after chunk.

**The pair terms.** `e^(G_t - G_j)` differs by channel, so `A` and `B` are
matmuls only once the decay is split between the two sides around a
reference point `r`: `(k_t . e^(G_t - G_r)) . (k_j . e^(G_r - G_j))`. The
reference is the middle of the `subchunk` positions that hold `t`: both
factors are then within `e^(+-subchunk * g_min / 2)` for a `j` in the
same sub-chunk, and the right one at most 1 before it (a `j` after `t`'s
sub-chunk is no pair: its factor is 0, set before the exponential so
that no gradient sees an infinity); a product of the two, for a pair the
mask then drops, is within `e^(-subchunk * g_min)`. The CALLER bounds
`g`: with `g >= -5` and 16 positions the factors stay inside `e^(+-40)`
and every product inside `e^80`, which float32 holds (3.4e38 is e^88.7)
with no feature of `k` pushed under its smallest normal number (around
the sub-chunk's start the left factor went down to `e^-80` = 1.8e-35);
a `g` that can go lower needs a shorter sub-chunk. The factors and the
pair products are float32.

**`T`.** `N = -Diag(beta) A` is strictly lower triangular, so `N^chunk =
0` and `(I - N)^-1 = (I + N)(I + N^2)(I + N^4)...`: `log2(chunk)` squarings
and as many products, all matmuls, float32 at the highest precision (a
rounding in `T` reaches every later position of the chunk).

**The backward** is a `jax.custom_vjp`'s. The chunks are walked `SPAN`
(4: measured beside 8 and 16, PERF.md section 6) at a time — by one
`lax.scan` whose body is the tables of those chunks, then their state
updates, or by a kernel's sequential grid axis; the forward keeps the
state each step started from (`S / (chunk * SPAN)` states of `H * Dk * Dv
* 4` bytes a sequence: `state_bytes`), the backward walks the steps from
the last, makes each one's tables and updates again from its kept state
and transposes them (autodiff of the span's body), so one span's tables
exist at a time and no state a chunk or a position ever does. The forward
names its output and the kept states `"attn_core"`
(`jax.ad_checkpoint.checkpoint_name`, on the values it returns as
residuals, as ops/pallas_attention.py's kernels name theirs): a layer
rematerialised under `save_only_these_names("attn_core", ...)` keeps
both, and its backward runs each span's forward once, not twice.

**Which body runs** is what the code can see, never an option. Where
`pallas_kda.tiles` takes the shapes (`Dk`, `Dv` whole 128-lane registers,
chunk 64 and sub-chunk 16, `S` a whole number of spans) each rule hands
its direction to `_either`, a `jax.jit` of its own around
`lax.platform_dependent`: the kernel where the program is LOWERED for a
TPU, the plain body for anything else; shapes that do not tile run the
plain body everywhere (`core` says which, for `describe`). One algorithm
and one set of constants: the kernels compute these same tables at these
same precisions, a span a grid step, and what differs is where the
intermediates live — in VMEM, where the plain body writes a span's
float32 tables (≈ 0.25 GB) to HBM and reads them back op by op. The kept
states and the names are the same in both, so a step's residuals do not
depend on the platform.

Shapes: `S` a multiple of `chunk`, `chunk` of `subchunk`, `chunk` a power
of two; anything else is refused by name. `q`, `k`, `v` may be bfloat16:
the products with the state take them as they are, with float32 sums.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from parallel_cnn_tpu.ops import pallas_kda

CHUNK, SUBCHUNK, SPAN = 64, 16, 4
RESIDUAL_NAME = "attn_core"  # ops/pallas_attention.py's: one policy keeps both
_EXACT = lax.Precision.HIGHEST


def recurrent_kda(q, k, v, g, beta):
    """`o (N, H, S, Dv)` float32 of the recurrence itself, a position at a
    time, everything in float32 (module docstring): what `chunked_kda`
    computes, written as what it is."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    n, h, _, dk = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("nhk,nhkv->nhv", k_t, state, precision=_EXACT)
        state = state + jnp.einsum(
            "nhk,nhv->nhkv", k_t, b_t[..., None] * (v_t - seen),
            precision=_EXACT)
        return state, jnp.einsum("nhk,nhkv->nhv", q_t, state, precision=_EXACT)

    along = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    _, o = lax.scan(step, jnp.zeros((n, h, dk, v.shape[-1]), f32),
                    tuple(map(along, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 2)


def spans(s: int, chunk: int = CHUNK) -> Tuple[int, int]:
    """(scan steps, chunks a step) for `s` positions: the most chunks a
    step, up to `SPAN`, that divide the sequence's."""
    if s % chunk:
        raise ValueError(
            f"chunked_kda: {s} positions are no multiple of the chunk "
            f"({chunk})")
    chunks = s // chunk
    m = next(m for m in range(min(SPAN, chunks), 0, -1) if chunks % m == 0)
    return chunks // m, m


def state_bytes(s: int, heads: int, dk: int, dv: int,
                chunk: int = CHUNK) -> int:
    """Bytes of the states one sequence's backward keeps: one float32
    `(heads, dk, dv)` at the start of every scan step."""
    return spans(s, chunk)[0] * heads * dk * dv * 4


def _tables(q, k, v, g, beta, subchunk: int):
    """What a chunk needs that does not depend on the state it starts
    from. Arguments `(..., C, D)` (`beta (..., C)`), a chunk along the axis
    before the last. Returns `q . e^G`, `B`, `T V`, `T (K . e^G)`, `K .
    e^(G_end - G)` and `e^G_end`, all float32."""
    f32 = jnp.float32
    c, dk = k.shape[-2:]
    subs = c // subchunk
    kf, qf = k.astype(f32), q.astype(f32)
    run = jnp.cumsum(g, axis=-2)  # G_t, this position's decay included
    by_sub = run.reshape(*run.shape[:-2], subs, subchunk, dk)
    # G in the middle of each sub-chunk: the reference points
    mid = by_sub[..., (subchunk - 1) // 2, :]  # (..., subs, dk)
    left = jnp.exp(by_sub - mid[..., None, :])
    # e^(G_r - G_j) for a j up to the end of r's sub-chunk, else 0
    at = jnp.arange(c)
    pairs = at[None, :] < (jnp.arange(subs)[:, None] + 1) * subchunk
    right = jnp.exp(jnp.where(
        pairs[..., None], mid[..., :, None, :] - run[..., None, :, :],
        -jnp.inf))  # (..., subs, C, dk)
    keys = kf[..., None, :, :] * right

    def pair(rows):
        """sum_c rows_tc e^(G_tc - G_jc) k_jc for every t, j of a chunk."""
        rows = rows.reshape(by_sub.shape) * left
        out = jnp.einsum("...aic,...ajc->...aij", rows, keys, precision=_EXACT)
        return out.reshape(*out.shape[:-3], c, c)

    lower = at[:, None] > at[None, :]
    b = beta.astype(f32)
    nil = jnp.where(lower, -b[..., :, None] * pair(kf), 0.0)  # N
    eye = jnp.eye(c, dtype=f32)
    inv = eye + nil
    for _ in range(c.bit_length() - 2):  # (I+N)(I+N^2)...(I+N^(C/2))
        nil = jnp.matmul(nil, nil, precision=_EXACT)
        inv = jnp.matmul(inv, eye + nil, precision=_EXACT)
    t = inv * b[..., None, :]
    decay = jnp.exp(run)
    tv = jnp.matmul(t, v.astype(f32), precision=_EXACT)
    tk = jnp.matmul(t, kf * decay, precision=_EXACT)
    reads = jnp.where(lower | (at[:, None] == at[None, :]), pair(qf), 0.0)
    last = run[..., -1:, :]
    return qf * decay, reads, tv, tk, kf * jnp.exp(last - run), jnp.exp(
        last[..., 0, :])


def _span(subchunk: int, state, block):
    """A scan step's `m` chunks from `state (N, H, Dk, Dv)`: `block` holds
    `q, k, v, g (N, H, m, C, D)` and `beta (N, H, m, C)`. (state after them, their
    `o (N, H, m, C, Dv)` float32)."""
    qd, reads, tv, tk, kd, end = _tables(*block, subchunk)
    outs = []
    for i in range(qd.shape[2]):
        u = tv[:, :, i] - jnp.matmul(tk[:, :, i], state)
        outs.append(jnp.matmul(qd[:, :, i], state)
                    + jnp.matmul(reads[:, :, i], u))
        state = state * end[:, :, i, :, None] + jnp.einsum(
            "nhck,nhcv->nhkv", kd[:, :, i], u)
    return state, jnp.stack(outs, axis=2)


def _blocks(a, steps: int, m: int, chunk: int):
    """(N, H, S, ...) -> (steps, N, H, m, C, ...): a scan step's chunks."""
    n, h = a.shape[:2]
    return jnp.moveaxis(a.reshape(n, h, steps, m, chunk, *a.shape[3:]), 2, 0)


def _whole(a):
    """`_blocks` back: (steps, N, H, m, C, ...) -> (N, H, S, ...)."""
    a = jnp.moveaxis(a, 0, 2)
    return a.reshape(*a.shape[:2], -1, *a.shape[5:])


def core(s: int, dk: int, dv: int, platform: str, chunk: int = CHUNK,
         subchunk: int = SUBCHUNK) -> str:
    """What runs `s` positions of heads `dk` and `dv` wide in a program
    lowered for `platform`: `"pallas"` (ops/pallas_kda.py's kernels: a TPU
    and shapes they take) or `"xla"` (this module's scan)."""
    takes = pallas_kda.tiles(s, dk, dv, chunk, subchunk, SPAN)
    return "pallas" if takes and platform == "tpu" else "xla"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def chunked_kda(q, k, v, g, beta, chunk: int = CHUNK,
                subchunk: int = SUBCHUNK):
    """`o (N, H, S, Dv)` in `v.dtype` of the module docstring's recurrence
    by chunks. `g` is float32 and bounded below by the caller so that
    `subchunk * min(g)` stays above float32's range (-88)."""
    return _forward(q, k, v, g, beta, chunk, subchunk)[0]


def _scan_forward(q, k, v, g, beta, *, chunk, subchunk):
    """The plain body: (`o`, the states the scan's steps start from)."""
    n, h, s, dk = q.shape
    steps, m = spans(s, chunk)

    def body(state, block):
        after, o = _span(subchunk, state, block)
        return after, (o.astype(v.dtype), state)

    _, (o, starts) = lax.scan(
        body, jnp.zeros((n, h, dk, v.shape[-1]), jnp.float32),
        tuple(_blocks(a, steps, m, chunk) for a in (q, k, v, g, beta)))
    return _whole(o), starts


def _scan_backward(q, k, v, g, beta, starts, d_o, *, chunk, subchunk):
    """The plain body's: a span at a time from the last, its tables and
    state updates again from the state it started from, then their
    transpose."""
    inputs = (q, k, v, g, beta)
    steps, m = starts.shape[0], q.shape[2] // (starts.shape[0] * chunk)

    def body(d_state, at):
        block, start, d_out = at
        _, pull = jax.vjp(functools.partial(_span, subchunk), start, block)
        return pull((d_state, d_out.astype(jnp.float32)))

    _, d_blocks = lax.scan(
        body, jnp.zeros_like(starts[0]),
        (tuple(_blocks(a, steps, m, chunk) for a in inputs), starts,
         _blocks(d_o, steps, m, chunk)), reverse=True)
    return tuple(_whole(d).astype(a.dtype) for d, a in zip(d_blocks, inputs))


@functools.partial(jax.jit, static_argnames=("chunk", "subchunk", "back"))
def _either(*operands, chunk: int, subchunk: int, back: bool):
    """One direction of shapes `pallas_kda.tiles` took: the kernel where
    the program is lowered for a TPU, the plain body elsewhere. A
    `jax.jit` of its own: both branches are traced once a signature, not
    in every layer of a step."""
    sizes = dict(chunk=chunk, subchunk=subchunk)
    if back:
        kernel = functools.partial(pallas_kda.backward, **sizes)
        plain = functools.partial(_scan_backward, **sizes)
    else:
        kernel = functools.partial(pallas_kda.forward, span=SPAN, **sizes)
        plain = functools.partial(_scan_forward, **sizes)
    return lax.platform_dependent(*operands, tpu=kernel, default=plain)


def _run(back: bool, chunk: int, subchunk: int, *operands):
    """One direction on its operands (`q, k, v, g, beta`, and backward the
    kept states and `d_o`): through `_either` where `pallas_kda.tiles`
    takes the shapes, the plain body otherwise."""
    (_, _, s, dk), dv = operands[0].shape, operands[2].shape[-1]
    sizes = dict(chunk=chunk, subchunk=subchunk)
    if pallas_kda.tiles(s, dk, dv, chunk, subchunk, SPAN):
        return _either(*operands, back=back, **sizes)
    return (_scan_backward if back else _scan_forward)(*operands, **sizes)


def _forward(q, k, v, g, beta, chunk, subchunk):
    if chunk % subchunk or chunk & (chunk - 1):
        raise ValueError(
            f"chunked_kda: a chunk of {chunk} positions is no power of two "
            f"or no multiple of the sub-chunk ({subchunk})")
    spans(q.shape[2], chunk)  # refuses positions off the chunk by name
    g = g.astype(jnp.float32)
    o, starts = _run(False, chunk, subchunk, q, k, v, g, beta)
    # What a rematerialised layer keeps (`save_only_these_names`, as the
    # attention kernels' output and log-sum-exp): its backward then runs no
    # forward scan but the spans' own.
    o = checkpoint_name(o, RESIDUAL_NAME)
    starts = checkpoint_name(starts, RESIDUAL_NAME)
    return o, (q, k, v, g, beta, starts)


def _backward(chunk, subchunk, residuals, d_o):
    return _run(True, chunk, subchunk, *residuals, d_o)


chunked_kda.defvjp(_forward, _backward)
