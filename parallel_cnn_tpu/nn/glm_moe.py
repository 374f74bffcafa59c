"""GLM-4.7-Flash (`model_type: glm4_moe_lite`; config.json at
huggingface.co/zai-org/GLM-4.7-Flash) — the zoo's decoder-only language
model: latent attention with a query latent (MLA, DeepSeek-V2
arXiv:2405.04434 section 2.1), fine-grained routed experts beside a shared
one under sigmoid scores and a selection bias that the step balances
(DeepSeek-V3 arXiv:2412.19437 section 2.1.2, `topk_method: noaux_tc`), and
one multi-token-prediction module (ibid. section 2.2).

    x      = Emb(t)                                   tokens (N, S) int
    layer  : h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
             FFN = GatedMLP(dense_width) in the first `first_dense` layers,
             the expert layer after them
    logits = RMSNorm(x_L) W_head                      (untied)

    Attn   : c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (nope | rope)
             [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb -> heads x (nope | v_dim)
             RoPE on q_pe and on the one k_pe every head shares
             softmax_causal((q_nope k_nope + q_pe k_pe) / sqrt(nope + rope))
             heads concatenated through W_o; no biases anywhere
    Expert : s = sigmoid(x W_g) in float32; chosen = top-k of s + b
             g_i = scaling * s_i / sum_chosen s_j   (from s, not s + b)
             y = sum_chosen g_i E_i(x) + E_shared(x), every E a GatedMLP
    MTP    : h' = [RMSNorm(Emb(t_{i+1})) | RMSNorm(x_L,i)] W_eh, one expert
             decoder layer, the model's final norm and head, scored
             against t_{i+2}
    loss   = CE + mtp_weight * CE_mtp + balance * sum_layers sum_i f_i P_i

The expert layer is told which experts it HOLDS (`held`, ids of the
published `n_routed`): it routes over all of them, normalises the gates
over all the chosen, and adds only what its own experts give — one
chip's part of an expert-parallel layer, without the exchange. Nothing
stands in for the absent experts. Tokens are never dropped: the held
assignments are counted into a static row buffer (`rows`; default every
assignment, at which nothing can overflow), expert by expert and token
by token (`ExpertLayer.plan`: running sums over a (tokens, held) table
give every slot its row, one sort of those rows gives every row its
slot), and multiplied by `lax.ragged_dot`, a grouped matmul whose
cost follows the rows held; rows that a smaller buffer cannot take are
COUNTED (`overflow_rows`). Whatever moves a token's row into the buffer
or back, forward and backward, is sized by `rows`, not by the
assignments: the plan knows the slot in every row, and the sum of a
token's rows out of the buffer (`_token_sums`: `_combine` forward,
`_gather_rows` backward) reads each buffer row where it lies — the fused
kernel of ops/pallas_rowsum.py where the shapes tile and the step is
lowered for a TPU, a float32 scatter-add of the rows elsewhere.

State the step updates without a gradient (`ExpertLayer.init`): the
selection bias `b`, the step's load per expert, and the counters read at
the end of an epoch. `GlmMoe.finish_step` closes a step: `b += bias_step
* sign(mean load - load)` over the step's tokens, all microbatches.

The model owns its loss (`GlmMoe.loss`, the seam `train/zoo.py:
_build_loss_fn` looks for): it needs the hidden states for the MTP
module, computes the logits a block of positions at a time, and adds the
layers' balance terms. In training every decoder layer is rematerialised
(`jax.checkpoint`) and keeps one activation, its attention core's output
(with the rows' log-sum-exp where the core is the fused kernels of
ops/pallas_attention.py: `MLA.core` — shapes that tile, a step lowered
for a TPU; elsewhere a block of queries at a time in plain XLA, each
block rematerialised), and what its expert layer decided (`"moe_plan"`:
the chosen ids, the gates, the buffer's plan and the fused sum's lists
— integers, what the backward reads of them), so a step routes and plans
once; so is every block of logits.

Scopes (obs/programs.py; benchmark/shapes/glm_moe.py lists the same):
`embed`, `l<i>/attn/{norm,q,kv,rope,core,o}`, `l<i>/mlp/...` or
`l<i>/moe/{norm,route,dispatch,experts,combine,shared}`, `mtp/{embed,
norm,proj,l0/...}`, `norm`, `head`, `loss`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from parallel_cnn_tpu.nn.core import Module, Shape
from parallel_cnn_tpu.nn.layers import (
    Embedding,
    GatedMLP,
    RMSNorm,
    _weight,
    gated_unit,
    rope,
)
from parallel_cnn_tpu.ops import pallas_attention, pallas_rope, pallas_rowsum

INIT_STD = 0.02


def _ones(n: int):
    """A gain of its own: a leaf shared by two layers cannot be donated."""
    return jnp.ones((n,), jnp.float32)


def _norm(eps, scale, x):
    return RMSNorm(eps).apply({"scale": scale}, {}, x)[0]


def _head_gated(out, gate):
    """`out (N, H, S, D)` times the sigmoid (float32) of `gate (N, H, S)`,
    a gate a head."""
    return out * jax.nn.sigmoid(
        gate.astype(jnp.float32)).astype(out.dtype)[..., None]


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _attend(q, k, v, start: int, scale: float):
    """Queries `start`... of a sequence (N, heads, q, ·) against the keys
    up to their own position (N, heads, k, ·): scores and softmax in
    float32, (N, heads, q, v_dim) out. Rematerialised: the backward
    recomputes the block's scores."""
    s = jnp.einsum("nhqd,nhkd->nhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    qi = start + jnp.arange(q.shape[2])
    s = jnp.where(qi[:, None] >= jnp.arange(k.shape[2])[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", p.astype(v.dtype), v)


@dataclasses.dataclass(frozen=True)
class MLA(Module):
    """Multi-head latent attention in its training form: keys and values
    are expanded per head from the latent (the latent form is a cache's
    concern). Causal; the scores of a whole sequence never exist at once
    and the upper triangle is not computed: a tile at a time inside the
    fused kernels where `core` says so, else a block of `q_block` queries
    at a time against the keys up to the block's end.

    What a factory may choose beside the widths: `q_rank=None`, no query
    latent (one projection `q` and no norm on the way); `gated`, the
    core's output times the sigmoid of one more projection of the layer's
    input, a gate a head, before `o`; `interleaved`, RoPE's pairs are the
    ADJACENT features (2i, 2i + 1) of `q_pe` and `k_pe` — turned as
    `rope` turns its halves, over the even features then the odd ones: a
    score is a sum over features, so the same order on both sides leaves
    every one of them as it is, and the order is taken from the weights'
    columns, not from the activations."""

    heads: int = 20
    q_rank: Optional[int] = 768
    kv_rank: int = 512
    nope: int = 192
    rope_dim: int = 64
    v_dim: int = 256
    theta: float = 1e6
    eps: float = 1e-5
    q_block: int = 512
    gated: bool = False
    interleaved: bool = False

    def init(self, key, in_shape: Shape):
        d, h = in_shape[-1], self.heads
        wide = h * (self.nope + self.rope_dim)
        shapes = {"q": (d, wide)} if self.q_rank is None else {
            "q_a": (d, self.q_rank), "q_b": (self.q_rank, wide)}
        shapes.update({
            "kv_a": (d, self.kv_rank + self.rope_dim),
            "kv_b": (self.kv_rank, h * (self.nope + self.v_dim)),
            "o": (h * self.v_dim, d),
        })
        if self.gated:
            shapes["gate"] = (d, h)
        # (five keys whatever is asked: a leaf's draw is its place among them)
        keys = jax.random.split(key, max(len(shapes), 5))
        params = {n: _weight(k, s, s[0], INIT_STD)
                  for (n, s), k in zip(shapes.items(), keys)}
        if self.q_rank is not None:
            params["q_norm"] = _ones(self.q_rank)
        params["kv_norm"] = _ones(self.kv_rank)
        return params, {}, in_shape

    @property
    def qk_width(self) -> int:
        """A head's `q` and `k` as the core is handed them: `nope +
        rope_dim`, and zero columns up to whole 128-lane registers where
        that is none (192 -> 256: a zero column adds nothing to a score)."""
        lanes = pallas_attention.LANES
        return -(-(self.nope + self.rope_dim) // lanes) * lanes

    def core(self, s: int) -> Tuple[str, int]:
        """(`"fused"` | `"blocks"`, the tile's side) for `s` positions: what
        the shapes allow. The fused kernels run where the step is lowered
        for a TPU (ops/pallas_attention.py); elsewhere the same shapes run
        the blocks."""
        t = pallas_attention.tile(s, self.qk_width, self.v_dim)
        return ("blocks", self.q_block) if t is None else ("fused", t)

    def _pe_order(self, w):
        """The last axis' `rope_dim` features of a weight in the order
        `rope` pairs them (`interleaved`: even ones, then odd ones)."""
        if not self.interleaved:
            return w
        r = self.rope_dim
        order = jnp.concatenate([jnp.arange(0, r, 2), jnp.arange(1, r, 2)])
        return jnp.concatenate(
            [w[..., :-r], jnp.take(w[..., -r:], order, axis=-1)], axis=-1)

    def _blocks(self, q, k, v):
        """(N, heads, S, ·) in and out: `q_block` queries at a time."""
        scale = (self.nope + self.rope_dim) ** -0.5
        return jnp.concatenate([
            _attend(q[:, :, a: a + self.q_block], k[:, :, : a + self.q_block],
                    v[:, :, : a + self.q_block], a, scale)
            for a in range(0, q.shape[2], self.q_block)], axis=2)

    def apply(self, params, state, x, train: bool = False):
        """Heads ahead of positions throughout, as the fused core wants
        them: the projections hand over and take back (N, heads, S, ·)
        through their matmuls' own output layouts, so no tensor is
        transposed on the way in or out."""
        w = {k: v.astype(x.dtype) for k, v in params.items()}
        n, s, _ = x.shape
        h, nope = self.heads, self.nope
        with jax.named_scope("q"):
            if self.q_rank is None:
                q = jnp.einsum("nsm,mhd->nhsd", x, self._pe_order(
                    w["q"].reshape(-1, h, nope + self.rope_dim)))
            else:
                q = jnp.einsum(
                    "nsr,rhd->nhsd",
                    _norm(self.eps, w["q_norm"], x @ w["q_a"]),
                    self._pe_order(w["q_b"].reshape(
                        self.q_rank, h, nope + self.rope_dim)))
        with jax.named_scope("kv"):
            ckv = x @ self._pe_order(w["kv_a"])
            k_pe = ckv[:, None, :, self.kv_rank:]
            c = _norm(self.eps, w["kv_norm"], ckv[..., : self.kv_rank])
            kv_b = w["kv_b"].reshape(self.kv_rank, h, nope + self.v_dim)
            k_nope = jnp.einsum("nsr,rhd->nhsd", c, kv_b[..., :nope])
            v = jnp.einsum("nsr,rhd->nhsd", c, kv_b[..., nope:])
        with jax.named_scope("rope"):
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], self.theta)], -1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(rope(k_pe, self.theta),
                                          (n, h, s, self.rope_dim))], -1)
        with jax.named_scope("core"):
            # The one activation a rematerialised layer keeps (`GlmMoe._run`;
            # the kernels' is named inside `causal_attention`, with its rows'
            # log-sum-exp): recomputing it is a third pass over the scores.
            kind, t = self.core(s)
            if kind == "fused":
                spare = self.qk_width - (nope + self.rope_dim)
                if spare:
                    q, k = (jnp.pad(a, ((0, 0),) * 3 + ((0, spare),))
                            for a in (q, k))
                out = pallas_attention.causal_attention(
                    q, k, v, (nope + self.rope_dim) ** -0.5, t, self._blocks)
            else:
                out = checkpoint_name(self._blocks(q, k, v), "attn_core")
        if self.gated:
            with jax.named_scope("gate"):
                out = _head_gated(out, jnp.einsum("nsm,mh->nhs", x, w["gate"]))
        with jax.named_scope("o"):
            return jnp.einsum("nhsd,hdm->nsm", out,
                              w["o"].reshape(h, self.v_dim, -1)), state


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["rank", "in_buffer", "row_of", "row_live", "sizes",
                 "overflow", "sums"],
    meta_fields=["tokens", "per_token"])
@dataclasses.dataclass(frozen=True)
class Plan:
    """Where an expert layer's `tokens * per_token` assignments lie in its
    row buffer (`ExpertLayer.plan`): `rank (T, k)` the row of each slot and
    `in_buffer (T, k)` whether the slot has one; `row_of (rows,)` the flat
    slot `t * k + j` in each row and `row_live (rows,)`; `sizes (held,)`
    the live rows an expert; `overflow`, the held assignments past the
    buffer; `sums`, what the fused sum of a token's rows reads
    (ops/pallas_rowsum.py:Schedule) where the shapes tile, else None.
    `rank` is 0 where `in_buffer` is not, and `row_of` where `row_live` is
    not. A backward is handed the part it reads (`keeping`)."""

    tokens: int
    per_token: int
    rank: Optional[jax.Array]
    in_buffer: Optional[jax.Array]
    row_of: jax.Array
    row_live: jax.Array
    sizes: Optional[jax.Array]
    overflow: Optional[jax.Array]
    sums: Optional[pallas_rowsum.Schedule]

    def keeping(self, *fields: str) -> "Plan":
        """This plan's rows (`row_of`, `row_live`, `sums`) and `fields`."""
        return dataclasses.replace(self, **{
            f: None for f in ("rank", "in_buffer", "sizes", "overflow")
            if f not in fields})


def _token_sums(x, plan: Plan, gates=None):
    """`out[t] = sum of gates[t, j] * x[rank[t, j]]` over a token's slots
    `in_buffer` (`gates=None`: of `x[rank[t, j]]`): the buffer's rows `x
    (rows, d)` back onto their tokens, products and sum in float32,
    rounded once. Made from the buffer's rows, each sent to the token it
    holds (`row_of // k`), never from the `T * k` slots: seven of eight
    slots of a share have no row. Rows that are not live hold nothing
    defined: they are selected away, never multiplied by a zero. Where the
    plan has a schedule and the program is lowered for a TPU, the fused
    kernel; elsewhere a float32 scatter-add of the rows as they lie."""
    t, k = plan.tokens, plan.per_token

    def as_they_lie(x):
        rows = x.astype(jnp.float32)
        if gates is not None:
            rows = rows * gates.reshape(-1)[plan.row_of][:, None].astype(
                jnp.float32)
        rows = jnp.where(plan.row_live[:, None], rows, 0)
        return jnp.zeros((t, x.shape[1]), jnp.float32).at[
            plan.row_of // k].add(rows).astype(x.dtype)

    if plan.sums is None:
        return as_they_lie(x)
    weight = None
    if gates is not None:
        # a token's gate for each held expert, (held, T), tokens along the
        # lanes: compared and summed slot by slot, not looked up
        weight = sum(
            jnp.where(plan.in_buffer[None, :, j]
                      & (plan.rank[None, :, j] == plan.sums.slot_row),
                      gates[None, :, j].astype(jnp.float32), 0)
            for j in range(k))
    return pallas_rowsum.token_sums(x, weight, plan.sums, as_they_lie)


@jax.custom_vjp
def _gather_rows(x, plan: Plan):
    """`x[row_of // k]`, the tokens' rows `x (T, d)` into the row buffer.
    Its gradient is the sum of a token's rows (`_token_sums`), in float32
    over the live rows: autodiff's own transpose adds in `x.dtype` and adds
    the dead rows into token 0."""
    return x[plan.row_of // plan.per_token]


def _gather_rows_fwd(x, plan):
    return x[plan.row_of // plan.per_token], plan.keeping()


def _gather_rows_bwd(plan, dy):
    return _token_sums(dy, plan), None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine(ys, gates, plan: Plan, gate_grad: bool = True):
    """`y[t] = sum_j gates[t, j] * ys[rank[t, j]]` over the slots
    `in_buffer` (buffer rows back onto their tokens: `_token_sums`). The
    plan knows the slot in each live row (`row_of`), so the gradients are
    made in buffer space too: a row's is its gate times its token's `dy`,
    a gate's (`gate_grad`; else none, and the backward keeps no array with
    an entry a slot) the product of its row with its token's `dy` — one
    gather of `rows` rows of `dy`, and no array with a row an assignment."""
    return _token_sums(ys, plan, gates)


def _combine_fwd(ys, gates, plan, gate_grad):
    kept = plan.keeping("rank", "in_buffer") if gate_grad else plan.keeping()
    # (the sum itself, not `_combine`: behind a second custom_vjp call a
    # rematerialised backward would compute everything the call is handed)
    return _token_sums(ys, plan, gates), (ys, gates, kept)


def _combine_bwd(gate_grad, res, dy):
    ys, gates, plan = res
    dy_rows = dy[plan.row_of // plan.per_token]
    d_ys = jnp.where(plan.row_live[:, None],
                     gates.reshape(-1)[plan.row_of][:, None] * dy_rows, 0)
    if not gate_grad:
        return d_ys.astype(ys.dtype), jnp.zeros_like(gates), None
    d_row = jnp.einsum("rd,rd->r", dy_rows, ys,
                       preferred_element_type=jnp.float32)
    d_gates = jnp.where(plan.in_buffer, d_row[plan.rank], 0)
    return d_ys.astype(ys.dtype), d_gates.astype(gates.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _rows_visited(plan: Plan, rows: int):
    """The buffer rows one `_token_sums` reads: the windows' of the fused
    kernel where that runs, else every row."""
    if plan.sums is None:
        return jnp.asarray(rows, jnp.int32)
    return lax.platform_dependent(
        plan.sums.visited, tpu=lambda visited: visited,
        default=lambda visited: jnp.full_like(visited, rows))


def _planned(value):
    """Names what the expert layer decides — integers, and the gates: a
    rematerialised layer keeps these (`GlmMoe._run`), so its backward
    neither routes nor plans again."""
    return checkpoint_name(value, "moe_plan")


@dataclasses.dataclass(frozen=True)
class ExpertLayer(Module):
    """Routed experts held here beside the whole shared expert (module
    docstring). `held`: the published ids of the experts this layer
    holds, in the order of its stacked weights; `rows`: the row buffer.
    `apply` is `route` (who goes where, at what gate), `plan` (which row
    of the buffer), a gather of `rows` token rows, three grouped matmuls
    and `_combine` (the buffer's rows summed onto their tokens); the work
    of all of them follows `rows` and the experts held, forward and
    backward.

    What the factory chooses: `scoring` (`"sigmoid"`, each expert's own, or
    `"softmax"` over all `n_routed`; either in float32, the gates the
    chosen scores over their sum times `scaling`), `n_shared` (0: no
    shared expert, and no leaf for one), `bias_step` (0: the selection
    bias stays where it is), `n_group` / `topk_group` (the selection's
    group limit, DeepSeek-V3's `noaux_tc`: the `n_routed` scores in
    `n_group` runs of neighbours, a group scored by the sum of its two
    largest `s + b`, only the `topk_group` best groups' experts can be
    chosen; 1 / 1: no limit, and no op for one), `limit` / `shared_limit`
    (`layers.gated_unit`'s clamp in the routed experts and in the shared
    one; 0: none). The balance term is `sum_i f_i P_i` a sequence either
    way — under softmax scores `P` is the scores' mean.

    `gate_grad=False` is for a share that trains: a gate's gradient is
    `<dL/dy, E_i(x)>`, and a share has that product for the experts it
    holds alone, so the scores learn that only those lower the loss and
    the router moves the tokens onto them (at 1e-4 the held eight of 64
    took 1.4-2.2 times their share within 32 steps; PERF.md section 6,
    PR 32). The other seven eighths of that gradient come back with the
    exchange across chips; a share without the exchange leaves the
    gates' gradient out whole. The forward is the same either way."""

    width: int = 1536
    n_routed: int = 64
    per_token: int = 4
    held: Tuple[int, ...] = tuple(range(64))
    n_shared: int = 1
    scaling: float = 1.8
    rows: Optional[int] = None
    bias_step: float = 1e-3
    balance: float = 1e-4
    gate_grad: bool = True
    scoring: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    limit: float = 0.0
    shared_limit: float = 0.0

    def __post_init__(self):
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring {self.scoring!r}: sigmoid or softmax")
        if (self.n_routed % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or self.topk_group * (self.n_routed // self.n_group)
                < self.per_token):
            raise ValueError(
                f"{self.topk_group} of {self.n_group} groups over "
                f"{self.n_routed} experts, {self.per_token} a token: the "
                f"groups divide the experts and those kept hold a token's")
        held = tuple(self.held)
        if (not held or len(set(held)) != len(held)
                or min(held) < 0 or max(held) >= self.n_routed):
            raise ValueError(
                f"held experts {held} are not distinct ids of the "
                f"{self.n_routed} routed experts")
        object.__setattr__(self, "held", held)

    def _shared(self) -> GatedMLP:
        return GatedMLP(self.n_shared * self.width, INIT_STD,
                        self.shared_limit)

    def _in_kept_groups(self, biased):
        """`biased (T, n_routed)` with the experts of every group but the
        `topk_group` best (by the sum of a group's two largest) at -inf."""
        t = biased.shape[0]
        groups = biased.reshape(t, self.n_group, -1)
        best = lax.top_k(groups, min(2, groups.shape[-1]))[0].sum(axis=-1)
        _, kept = lax.top_k(best, self.topk_group)
        keep = jax.nn.one_hot(kept, self.n_group, dtype=jnp.bool_).any(axis=1)
        return jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(t, -1)

    def init(self, key, in_shape: Shape):
        d, f = in_shape[-1], self.width
        rkey, ekey, skey = jax.random.split(key, 3)
        # An expert's weights are drawn from its published id: every share
        # of one layer, and the uncut layer, hold the same expert 5.
        one = GatedMLP(f, INIT_STD)
        each = [one.init(jax.random.fold_in(ekey, i), in_shape)[0]
                for i in self.held]
        params = {
            "router": _weight(rkey, (d, self.n_routed), d, INIT_STD),
            "experts": {n: jnp.stack([e[n] for e in each])
                        for n in ("gate", "up", "down")},
        }
        if self.n_shared:
            params["shared"] = self._shared().init(skey, in_shape)[0]
        state = {
            "bias": jnp.zeros((self.n_routed,), jnp.float32),
            # assignments per expert (all `n_routed`) since the step began
            "load": jnp.zeros((self.n_routed,), jnp.float32),
            # of the last finished step
            "rows_held": jnp.zeros((), jnp.int32),
            "load_max_over_mean": jnp.zeros((), jnp.float32),
            # since init: held assignments the row buffer could not take
            "overflow_rows": jnp.zeros((), jnp.int32),
            # the last forward's balance term, for the model's loss
            "balance": jnp.zeros((), jnp.float32),
            # the buffer rows one sum of a token's rows read in it
            "sum_rows_visited": jnp.zeros((), jnp.int32),
        }
        return params, state, in_shape

    def route(self, router, bias, xt, n: int):
        """(ids (T, k), gates (T, k) float32, load (n_routed,), balance
        term) of tokens `xt` (T, d) that are `n` sequences."""
        k, e = self.per_token, self.n_routed
        score = (jax.nn.sigmoid if self.scoring == "sigmoid"
                 else functools.partial(jax.nn.softmax, axis=-1))
        s = score(jnp.dot(
            xt.astype(jnp.float32), router, precision=lax.Precision.HIGHEST))
        biased = s + bias
        if self.n_group > 1:
            biased = self._in_kept_groups(biased)
        _, ids = lax.top_k(biased, k)
        ids = _planned(ids)
        chosen = jnp.take_along_axis(s, ids, axis=1)
        gates = self.scaling * chosen / jnp.sum(chosen, axis=1, keepdims=True)
        if not self.gate_grad:
            gates = lax.stop_gradient(gates)
        picked = jax.nn.one_hot(ids, e, dtype=jnp.float32).sum(axis=1)
        # Sequence-wise balance (DeepSeek-V3 eq. 17-20): f_i the share of
        # a sequence's assignments expert i got (times n_routed), P_i its
        # mean normalised score; only P carries a gradient, so of all this
        # a rematerialised backward computes the scores again, no more.
        per_seq = picked.reshape(n, -1, e)
        gates = _planned(gates)
        f = _planned(per_seq.sum(axis=1) * (e / (k * per_seq.shape[1])))
        p = (s / jnp.sum(s, axis=1, keepdims=True)).reshape(n, -1, e).mean(axis=1)
        balance = self.balance * jnp.mean(jnp.sum(f * p, axis=1))
        return ids, gates, picked.sum(axis=0), balance

    def plan(self, ids, rows: int, tiles=None) -> Plan:
        """Where the assignments `ids` (T, k) go in a buffer of `rows` rows
        (`Plan`), held assignments expert by expert in the order of `held`,
        a token before a later one; with `tiles`
        (ops/pallas_rowsum.py:tiles), what the fused sum reads too. Counted:
        a token picks an expert at most once, so the tokens of an expert
        are a boolean column, a slot's place among them is that column's
        running sum, and an expert's first row is the sum of the columns
        before it. Named for a rematerialised layer to keep: what the
        backward reads, which without the gates' gradient leaves out the
        slots' own rows."""
        t, k = ids.shape
        hit = ids[:, :, None] == jnp.asarray(self.held, ids.dtype)  # (T, k, held)
        mine = hit.any(axis=1).astype(jnp.int32)
        counts = mine.sum(axis=0)
        ends = jnp.cumsum(counts)
        place = (ends - counts)[None, :] + jnp.cumsum(mine, axis=0) - mine
        rank = jnp.sum(jnp.where(hit, place[:, None, :], 0), axis=2)
        in_buffer = hit.any(axis=2) & (rank < rows)
        rank = jnp.where(in_buffer, rank, 0)
        # The inverse: the slots in the order of their rows (those without a
        # row last). On the v5e this sort of t * k keys costs a sixth of the
        # scatter that writes every slot into its row (PERF.md, PR 35).
        row_of = jnp.argsort(jnp.where(in_buffer, rank, t * k).reshape(-1))[
            :rows].astype(jnp.int32)
        live = jnp.minimum(ends, rows)
        row_live = jnp.arange(rows) < live[-1]
        by_slot = (rank, in_buffer)
        if self.gate_grad:
            by_slot = tuple(map(_planned, by_slot))
        sums = None if tiles is None else jax.tree_util.tree_map(
            _planned, pallas_rowsum.schedule(place, ends, rows, tiles))
        return Plan(
            t, k, *by_slot, _planned(jnp.where(row_live, row_of, 0)),
            _planned(row_live), _planned(jnp.diff(live, prepend=0)),
            ends[-1] - live[-1], sums)

    def apply(self, params, state, x, train: bool = False):
        n, s, d = x.shape
        t, k = n * s, self.per_token
        rows = t * k if self.rows is None else min(self.rows, t * k)
        xt = x.reshape(t, d)
        with jax.named_scope("route"):
            ids, gates, load, balance = self.route(
                params["router"], state["bias"], xt, n)
        with jax.named_scope("dispatch"):
            plan = self.plan(
                ids, rows, pallas_rowsum.tiles(t, rows, d, len(self.held)))
            xs = _gather_rows(xt, plan)
        with jax.named_scope("experts"):
            w = {m: params["experts"][m].astype(x.dtype)
                 for m in ("gate", "up", "down")}
            hidden = gated_unit(
                lax.ragged_dot(xs, w["gate"], plan.sizes),
                lambda: lax.ragged_dot(xs, w["up"], plan.sizes), self.limit)
            ys = lax.ragged_dot(hidden, w["down"], plan.sizes)
        with jax.named_scope("combine"):
            y = _combine(ys, gates.astype(x.dtype), plan, self.gate_grad)
        if self.n_shared:
            with jax.named_scope("shared"):
                y = y + self._shared().apply(params["shared"], {}, xt)[0]
        if train:
            state = dict(
                state,
                load=state["load"] + load,
                overflow_rows=state["overflow_rows"] + plan.overflow,
                balance=balance,
                sum_rows_visited=_rows_visited(plan, rows),
            )
        return y.reshape(n, s, d), state

    def finish_step(self, state):
        load = state["load"]
        mean = jnp.mean(load)
        return dict(
            state,
            bias=state["bias"] + self.bias_step * jnp.sign(mean - load),
            load=jnp.zeros_like(load),
            rows_held=jnp.sum(load[jnp.asarray(self.held)]).astype(jnp.int32),
            load_max_over_mean=jnp.max(load) / jnp.maximum(mean, 1.0),
        )


@dataclasses.dataclass(frozen=True)
class DecoderLayer(Module):
    """Pre-norm decoder layer around an attention module (`MLA`;
    nn/sdar_moe.py:GQA). Only an `ExpertLayer` has state."""

    attn: Module
    ffn: Module
    eps: float = 1e-5

    @property
    def ffn_scope(self) -> str:
        return "moe" if isinstance(self.ffn, ExpertLayer) else "mlp"

    def init(self, key, in_shape: Shape):
        akey, fkey = jax.random.split(key)
        ffn, state, _ = self.ffn.init(fkey, in_shape)
        params = {"attn_norm": _ones(in_shape[-1]),
                  "attn": self.attn.init(akey, in_shape)[0],
                  "ffn_norm": _ones(in_shape[-1]), "ffn": ffn}
        return params, state, in_shape

    def apply(self, params, state, x, train: bool = False):
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                y = _norm(self.eps, params["attn_norm"].astype(x.dtype), x)
            h = x + self.attn.apply(params["attn"], {}, y, train)[0]
        with jax.named_scope(self.ffn_scope):
            with jax.named_scope("norm"):
                y = _norm(self.eps, params["ffn_norm"].astype(x.dtype), h)
            y, state = self.ffn.apply(params["ffn"], state, y, train)
        return h + y, state


@dataclasses.dataclass(frozen=True)
class GlmMoe(Module):
    """The language model (module docstring). `in_shape` is `(S,)`; `x`
    is token ids `(N, S)`; `apply` returns float32 logits `(N, S, vocab)`
    (small sizes only: all of them at once); `loss(params, state, x, y)`
    takes `y[n, i]` = the token after `x[n, i]`."""

    vocab: int
    hidden: int
    dense_width: int
    n_layers: int
    attn: MLA
    experts: ExpertLayer
    first_dense: int = 1
    mtp_modules: int = 1
    mtp_weight: float = 0.3
    eps: float = 1e-5
    dtype: str = "bfloat16"
    loss_block: int = 2048

    # what a rematerialised layer keeps of its forward, by name
    kept_names: ClassVar[Tuple[str, ...]] = ("attn_core", "moe_plan")

    def __post_init__(self):
        if self.mtp_modules not in (0, 1):
            raise ValueError(
                f"{self.mtp_modules} multi-token-prediction modules: 0 or 1")

    def _embed(self) -> Embedding:
        return Embedding(self.vocab, self.hidden, INIT_STD, self.dtype)

    def _layers(self) -> List[DecoderLayer]:
        dense = GatedMLP(self.dense_width, INIT_STD)
        return [
            DecoderLayer(self.attn, dense if i < self.first_dense
                         else self.experts, self.eps)
            for i in range(self.n_layers)
        ]

    def _mtp_layer(self) -> DecoderLayer:
        return DecoderLayer(self.attn, self.experts, self.eps)

    def init(self, key, in_shape: Shape):
        d = self.hidden
        hid = (*in_shape, d)
        ekey, hkey, mkey, *lkeys = jax.random.split(key, 3 + self.n_layers)
        inits = [l.init(k, hid) for l, k in zip(self._layers(), lkeys)]
        params = {
            "embed": self._embed().init(ekey, in_shape)[0],
            "layers": [p for p, _, _ in inits],
            "norm": _ones(d),
            "head": _weight(hkey, (d, self.vocab), d, INIT_STD),
        }
        state = {"layers": [s for _, s, _ in inits]}
        if self.mtp_modules:
            pkey, lkey = jax.random.split(mkey)
            layer, state["mtp"], _ = self._mtp_layer().init(lkey, hid)
            params["mtp"] = {
                "enorm": _ones(d), "hnorm": _ones(d),
                "proj": _weight(pkey, (2 * d, d), 2 * d, INIT_STD),
                "layer": layer,
            }
        return params, state, (*in_shape, self.vocab)

    def _run(self, layer: DecoderLayer, params, state, x, train: bool):
        fn = lambda p, s, x: layer.apply(p, s, x, train)  # noqa: E731
        if train:
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.save_only_these_names(
                    *self.kept_names))
        return fn(params, state, x)

    def hidden_states(self, params, state, x, train: bool = False):
        """(the residual stream after every decoder layer, the layers' new
        states)."""
        with jax.named_scope("embed"):
            h = self._embed().apply(params["embed"], {}, x)[0]
        hidden, new = [], []
        for i, (layer, p, s) in enumerate(zip(
                self._layers(), params["layers"], state["layers"], strict=True)):
            with jax.named_scope(f"l{i}"):
                h, s = self._run(layer, p, s, h, train)
            hidden.append(h)
            new.append(s)
        return hidden, new

    def _trunk(self, params, state, x, train: bool):
        hidden, new = self.hidden_states(params, state, x, train)
        return hidden[-1], new

    def _mtp(self, params, state, h, y, train: bool):
        """Hidden states of the module that, at position i, has seen the
        trunk's state there and token i + 1 (`y[:, i]`)."""
        p = params["mtp"]
        with jax.named_scope("embed"):
            e = self._embed().apply(params["embed"], {}, y)[0]
        with jax.named_scope("norm"):
            both = jnp.concatenate(
                [_norm(self.eps, p["enorm"].astype(h.dtype), e),
                 _norm(self.eps, p["hnorm"].astype(h.dtype), h)], axis=-1)
        with jax.named_scope("proj"):
            h2 = both @ p["proj"].astype(h.dtype)
        with jax.named_scope("l0"):
            return self._run(self._mtp_layer(), p["layer"], state, h2, train)

    def _logits(self, params, h):
        with jax.named_scope("norm"):
            h = _norm(self.eps, params["norm"].astype(h.dtype), h)
        with jax.named_scope("head"):
            return jnp.matmul(h, params["head"].astype(h.dtype),
                              preferred_element_type=jnp.float32)

    def apply(self, params, state, x, train: bool = False):
        h, layers = self._trunk(params, state, x, train)
        return self._logits(params, h), dict(state, layers=layers)

    def _cross_entropy(self, params, h, y, keep, weight=None,
                       summed: bool = True):
        """Sum over the kept positions of the cross-entropy of `h`
        (N, S, d) through the final norm and the head against `y` (times
        `weight`, a float32 a position, where one is given), a block of
        `loss_block` positions at a time: the logits of all positions
        never exist at once, forward or backward. Not `summed`: every
        position's own, float32 `(N, S)`, 0 where it is not kept — for a
        loss whose weights are themselves functions of the parameters
        (nn/ouro.py)."""

        @jax.checkpoint
        def block(head, h, y, keep, *weight):
            z = self._logits(head, h)
            with jax.named_scope("loss"):
                nll = (jax.nn.logsumexp(z, axis=-1)
                       - jnp.take_along_axis(z, y[:, None], axis=1)[:, 0])
                for w in weight:
                    nll = nll * w
                nll = jnp.where(keep, nll, 0.0)
                return jnp.sum(nll) if summed else nll

        head = {"norm": params["norm"], "head": params["head"]}
        rows = [h.reshape(-1, h.shape[-1]), y.reshape(-1), keep.reshape(-1)]
        if weight is not None:
            rows.append(weight.reshape(-1))
        blocks = (block(head, *(r[a: a + self.loss_block] for r in rows))
                  for a in range(0, rows[0].shape[0], self.loss_block))
        return sum(blocks) if summed else jnp.concatenate(
            list(blocks)).reshape(y.shape)

    def loss(self, params, state, x, y):
        """(loss, new state) of a training forward: the mean next-token
        cross-entropy, `mtp_weight` times the MTP module's (position i
        against `y[:, i + 1]`, the last position has no target), and the
        balance terms of every expert layer."""
        n, s = x.shape
        h, layers = self._trunk(params, state, x, True)
        every = jnp.ones((n, s), bool)
        total = self._cross_entropy(params, h, y, every) / (n * s)
        new = dict(state, layers=layers)
        moe = [st for st in layers if st]
        if self.mtp_modules:
            with jax.named_scope("mtp"):
                h2, new["mtp"] = self._mtp(params, state["mtp"], h, y, True)
                after = jnp.concatenate([y[:, 1:], y[:, :1]], axis=1)
                keep = every.at[:, -1].set(False)
                total = total + self.mtp_weight * self._cross_entropy(
                    params, h2, after, keep) / max(n * (s - 1), 1)
            moe.append(new["mtp"])
        return total + sum(st["balance"] for st in moe), new

    def _expert_states(self, state) -> List[Dict]:
        return [s for s in state["layers"] if s] + (
            [state["mtp"]] if self.mtp_modules else [])

    def finish_step(self, state):
        """Close an optimizer step (after the last microbatch's forward):
        every expert layer moves its selection bias by the step's load and
        starts counting again."""
        fin = self.experts.finish_step
        new = dict(state, layers=[fin(s) if s else s for s in state["layers"]])
        if self.mtp_modules:
            new["mtp"] = fin(state["mtp"])
        return new

    def counters(self, state) -> Dict[str, List[float]]:
        """The expert layers' counters, one value a layer (the MTP
        module's last), as an epoch record carries them."""
        read = (("moe_rows_held", "rows_held", int),
                ("moe_load_max_over_mean", "load_max_over_mean", float),
                ("moe_overflow_rows", "overflow_rows", int),
                ("moe_sum_rows_visited", "sum_rows_visited", int))
        got = jax.device_get([[s[key] for _, key, _ in read]
                              for s in self._expert_states(state)])
        return {name: [cast(layer[i]) for layer in got]
                for i, (name, _, cast) in enumerate(read)}

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """What the `zoo_moe` journal event says once at set-up, of steps
        of `seq_len`-token sequences: what the shapes allow on `platform`,
        the platform of the devices that hold the state (`zoo.train`
        compiles its step for those; the program itself decides where it
        is lowered, and a step lowered for another platform than the
        caller names here runs the other core and the other turn). The
        attention tiles are one core's, one (sequence, head)'s: the part
        of the score square that is computed. `rope_turn` is `"kernel"`
        where `layers.rope` runs ops/pallas_rope.py, else `"plain"`."""
        ex = self.experts
        assignments = tokens_per_step * ex.per_token
        kind, t = self.attn.core(seq_len)
        turn = pallas_rope.tile(seq_len, self.attn.rope_dim)
        if platform != "tpu":
            kind, t, turn = "blocks", self.attn.q_block, None
        return dict(
            attention_core=kind,
            rope_turn="plain" if turn is None else "kernel",
            attention_tiles_visited=pallas_attention.tiles_visited(seq_len, t),
            attention_tiles_total=(-(-seq_len // t)) ** 2,
            experts_held=len(ex.held), experts_published=ex.n_routed,
            experts_per_token=ex.per_token, expert_layers=len(
                self._layers()) - self.first_dense + self.mtp_modules,
            row_buffer=assignments if ex.rows is None
            else min(ex.rows, assignments),
            tokens_per_step=tokens_per_step,
        )


def glm_moe_lite(
    *,
    vocab_size: int,
    hidden_size: int,
    intermediate_size: int,
    moe_intermediate_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    q_lora_rank: int,
    kv_lora_rank: int,
    qk_nope_head_dim: int,
    qk_rope_head_dim: int,
    v_head_dim: int,
    n_routed_experts: int,
    num_experts_per_tok: int,
    n_shared_experts: int = 1,
    routed_scaling_factor: float = 1.0,
    first_k_dense_replace: int = 1,
    num_nextn_predict_layers: int = 1,
    rope_theta: float = 1e6,
    rms_norm_eps: float = 1e-5,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    bias_update_speed: float = 1e-3,
    balance_weight: float = 1e-4,
    mtp_weight: float = 0.3,
    gate_gradient: bool = True,
    dtype: str = "bfloat16",
    q_block: int = 512,
    loss_block: int = 2048,
) -> GlmMoe:
    """A `glm4_moe_lite` decoder by its config.json's keys. `held_experts`
    (default: all) is the share of the routed experts this model holds;
    `gate_gradient=False` leaves the gates' gradient out of a share that
    trains without the exchange (`ExpertLayer`)."""
    held = range(n_routed_experts) if held_experts is None else held_experts
    return GlmMoe(
        vocab=vocab_size, hidden=hidden_size, dense_width=intermediate_size,
        n_layers=num_hidden_layers,
        attn=MLA(num_attention_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta, rms_norm_eps, q_block),
        experts=ExpertLayer(
            moe_intermediate_size, n_routed_experts, num_experts_per_tok,
            tuple(held), n_shared_experts, routed_scaling_factor, row_buffer,
            bias_update_speed, balance_weight, gate_gradient),
        first_dense=first_k_dense_replace,
        mtp_modules=num_nextn_predict_layers, mtp_weight=mtp_weight,
        eps=rms_norm_eps, dtype=dtype, loss_block=loss_block,
    )


def glm_4_7_flash(
    num_hidden_layers: int = 47,
    vocab_size: int = 154880,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    **overrides,
) -> GlmMoe:
    """GLM-4.7-Flash at its published widths (30 B parameters whole, 3 B
    active a token): hidden 2,048, 20 heads of 192 + 64 / 256 over a
    768-wide query latent and a 512-wide key/value latent, one dense
    layer 10,240 wide, then 64 routed experts 1,536 wide, 4 a token
    (gates scaled 1.8), beside 1 shared, and 1 MTP module. Depth, the
    vocabulary's rows and the experts held are the caller's cut: one chip
    of an eight-way expert-parallel group holds `held_experts=range(8)`
    and 19,360 rows."""
    kwargs = dict(
        vocab_size=vocab_size, hidden_size=2048, intermediate_size=10240,
        moe_intermediate_size=1536, num_hidden_layers=num_hidden_layers,
        num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        n_routed_experts=64, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1,
        num_nextn_predict_layers=1, rope_theta=1e6, rms_norm_eps=1e-5,
        held_experts=held_experts, row_buffer=row_buffer,
    )
    kwargs.update(overrides)
    return glm_moe_lite(**kwargs)
