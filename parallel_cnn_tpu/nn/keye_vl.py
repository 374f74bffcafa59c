"""Keye-VL-2.0-30B-A3B's language model (`model_type: KeyeVL2`; config.json
at huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B) — the zoo's decoder whose
attention mask is DATA: a Qwen3-MoE trunk (grouped-query attention with an
RMSNorm over each head's features of q and k, 128 softmax-routed experts, 8
a token, no shared expert) in which a learned indexer scores every earlier
key for every query and the query attends to its `topk` best alone
(DeepSeek-V3.2-Exp's lightning indexer, its report's section 2.1, as
`sa_config` sizes it), the indexer learning from the attention it steered;
positions turn on three axes (M-RoPE, Qwen2-VL arXiv:2409.12191 section
2.1), because images stand in the sequence.

    h_0    = Emb(tokens)                                       (no scale)
    layer  : a = x + Attn(RMSNorm(x));  x' = a + Experts(RMSNorm(a))
    logits = RMSNorm(x_L) W_head                               (untied)

    Attn, on u = RMSNorm(x):
      q = RMSNorm_q(u W_q) a head, k = RMSNorm_k(u W_k) a head, v = u W_v
      q, k turned by M-RoPE                       (nn/sdar_moe.py:GQA)
      indexer, on u' = stop_gradient(u):
        q^I = u' W_q^I -> (H^I, d^I);  k^I = LayerNorm(u' W_k^I) -> (d^I),
        one for all index heads;  w = u' W_w -> (H^I)
        q^I, k^I turned by M-RoPE over their d^I columns
        I[t,s] = (H^I d^I)^(-1/2) sum_j w[t,j] ReLU(q^I[t,j] . k^I[s]), s <= t
      S_t = the min(t + 1, topk) keys s <= t with the largest I[t,s]
            (one selection for every head; ties: the lower s)
      o[t,h] = sum_{s in S_t} softmax_{s in S_t}(q[t,h] . k[s,g(h)] / sqrt D)
               v[s,g(h)];  out = concat_h(o) W_o
      no gradient passes through S_t.
    indexer's objective, a layer (V3.2-Exp's sparse-training stage):
      P[t,s] = stop_gradient(mean_h softmax_{s in S_t}(q[t,h] . k[s,g(h)]
               / sqrt D))                          (sums to 1 over S_t)
      L^I = mean_t sum_{s in S_t} P[t,s] (ln P[t,s]
            - ln softmax_{s in S_t}(I[t,:])[s])
    loss = mean next-token CE + the layers' balance terms
           + index_weight * sum_layers L^I

`u'` and `P` are detached and nothing differentiates `S_t`, so the one
scalar trains the trunk on the cross-entropy (and the balance terms) alone
and the indexer's four leaves on `L^I` alone.

How the selection reaches the core: as an array. `Indexer.choose` makes the
scores a block of queries at a time (never `(S, S)` in float32 at once) —
`Indexer.block_scores`: where the shapes tile and the step is lowered for a
TPU one kernel a direction that forms the sixteen heads' products, their
ReLU, the weighing and the sum over the heads in VMEM and writes `I (rows,
keys)` alone (ops/pallas_index.py:index_scores; it skips the key tiles past
the block's last query, which `choose` and the objective mask anyway), else
`Indexer.scores`, the equation in plain XLA, whose float32 `(H^I, rows,
keys)` products cross HBM —,
finds every row's exact `topk`-th largest as a threshold `tau` with the tie
rule as a second number (`cut`: the last index taken among the scores that
equal `tau`) by bisection over the scores' bits (`_best`: what a stable
sort would take, without the sort), and keeps the selection — `s <= t` and
`I > tau or (I == tau and s <= cut)` — as bits, 32 keys a word: `(N, S, S /
32)` words are what a rematerialised layer keeps of it (`"dsa_select"`), so
a backward neither scores nor selects again, and reads exactly the
forward's choice (a threshold kept in its place would meet recomputed
scores, and a last bit's difference drops the very key that set it). From
the bits it writes `bias (N, S, S)` bf16: 0 where selected, a large
negative number elsewhere. The core is ops/pallas_attention.py:selected_attention — the
scheduled kernel pair under the causal visit list, every tile of kind
DATA, which adds the tile of `bias` to its scores — where the shapes tile
and the step is lowered for a TPU, else `GQA._chosen`, the same in plain
XLA. `L^I` needs the head-mean probabilities, which a flash core never
emits: `Indexer.report` makes them again from `q`, `k` and the core's
log-sum-exp, a block of queries and a key/value group at a time, in the
forward and once more in the backward (`_index_kl`, a `custom_vjp` that
keeps arrays of `(S, .)` alone).

What nn/glm_moe.py and nn/sdar_moe.py have is used as it is: `GlmMoe`
(embedding, the layers' rematerialisation, final norm, head, the blocked
cross-entropy, `finish_step`), `DecoderLayer`'s frame, `ExpertLayer`, `GQA`
with its `select` and `positions`.

State the step writes without a gradient, read at the end of an epoch by
`counters`: a layer's `L^I`, the mean number of keys a query selected and
the share of the core's causal tiles that hold a selected pair.

Scopes: `embed`, `l<i>/attn/{norm,qkv,qk_norm,rope,core,o}`,
`l<i>/attn/indexer/{proj,rope,scores,select,kl}`,
`l<i>/moe/{norm,route,dispatch,experts,combine}`, `norm`, `head`, `loss`.
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
from parallel_cnn_tpu.nn.glm_moe import (
    INIT_STD,
    DecoderLayer,
    ExpertLayer,
    GlmMoe,
    _norm,
    _ones,
)
from parallel_cnn_tpu.nn.layers import LayerNorm, _weight, rope
from parallel_cnn_tpu.nn.sdar_moe import GQA
from parallel_cnn_tpu.ops import pallas_attention, pallas_index, pallas_rope

SELECTION_NAME = "dsa_select"


def pairs_allowed(s: int, topk: int) -> int:
    """Pairs (query, key) of `s` positions a selection of `topk` allows, one
    sequence (every head the same): query t keeps `min(t + 1, topk)`."""
    k = min(topk, s)
    return k * (k + 1) // 2 + (s - k) * k


def _bands(s: int, rows: int) -> List[Tuple[int, int]]:
    """[(first query, end)] — the sequence in quarters where whole blocks of
    `rows` queries divide them, else whole: a band's queries are scored
    against the keys before its end, in blocks of one shape."""
    n = 4 if s % (4 * rows) == 0 else 1
    return [(i * s // n, (i + 1) * s // n) for i in range(n)]


def _blocks(fn, carry, s: int, rows: int):
    """`fn(carry, first query, keys) -> (carry, ys)` over every block of
    `rows` queries, a `lax.scan` a band: (carry, ys joined along their
    first axis — a block's own leading axis is its queries')."""
    out = []
    for lo, hi in _bands(s, rows):
        carry, ys = lax.scan(
            lambda c, i, lo=lo, hi=hi: fn(c, lo + i * rows, hi), carry,
            jnp.arange((hi - lo) // rows))
        out.append(jax.tree_util.tree_map(
            lambda y: y.reshape(-1, *y.shape[2:]), ys))
    return carry, jax.tree_util.tree_map(
        lambda *ys: jnp.concatenate(ys, axis=0), *out)


def _rows_of(x, at, rows: int, axis: int):
    return lax.dynamic_slice_in_dim(x, at, rows, axis)


def _best(scores, keep):
    """bool like `scores (N, q, k)` float32: a row's `keep (q, 1)` largest
    entries, equal ones the lower index first — exactly what a stable sort
    (or `lax.top_k`) would take, in the total order both use (-0.0 below
    0.0) — found by bisection, not by sorting: the scores as unsigned
    integers of that order, the `keep`-th largest of
    a row built a bit at a time from counts (32 passes), then, among the
    entries EQUAL to it, the index of the last one taken the same way (a
    bit of the index a pass). On the v5e the thresholds of 16,384 rows of
    16,384 take 9 ms so, and 148 ms as `lax.top_k`'s sort (PERF.md section
    6, PR 51)."""
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    order = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    count = lambda hit: jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)  # noqa: E731

    def bit_of_threshold(b, t):
        higher = t | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        return jnp.where(count(order >= higher) >= keep, higher, t)

    tau = lax.fori_loop(0, 32, bit_of_threshold,
                        jnp.zeros((*scores.shape[:-1], 1), jnp.uint32))
    above, equal = order > tau, order == tau
    still = keep - count(above)  # of the equal ones, how many are taken: >= 1
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    width = max(scores.shape[-1] - 1, 1).bit_length()

    def bit_of_cut(b, c):
        further = c | (jnp.int32(1) << (width - 1 - b))
        return jnp.where(count(equal & (at < further)) < still, further, c)

    # the largest index with fewer than `still` equal entries before it: the
    # last one taken
    cut = lax.fori_loop(0, width, bit_of_cut, jnp.zeros_like(still))
    return above | (equal & (at <= cut))


@dataclasses.dataclass(frozen=True)
class Indexer(Module):
    """The lightning indexer of one attention layer (module docstring):
    `heads` index heads of `head_dim` over one shared index key, `topk`
    keys kept a query. `GQA` calls `project` (the layer's detached input to
    `q^I, k^I, w`), `choose` (the selection, as the core's `bias`) and
    `report` (`L^I` and the counters); `scores` is the one place the
    equation of `I` is written, and `block_scores` what `choose` and the
    objective call for a block of queries: ops/pallas_index.py's kernel pair
    where the shapes tile and the step is lowered for a TPU, with `scores`
    as its plain form everywhere else. `rows`: the queries a block of any
    of them holds."""

    heads: int = 16
    head_dim: int = 64
    topk: int = 2048
    theta: float = 1e7
    eps: float = 1e-6
    rows: int = 256

    def init(self, key, in_shape: Shape):
        d = in_shape[-1]
        shapes = {"q": (d, self.heads * self.head_dim), "k": (d, self.head_dim),
                  "w": (d, self.heads)}
        params = {n: _weight(k, s, s[0], INIT_STD)
                  for (n, s), k in zip(shapes.items(), jax.random.split(key, 3))}
        params["k_norm"] = LayerNorm(self.eps).init(key, (self.head_dim,))[0]
        state = {"kl": jnp.zeros((), jnp.float32),
                 "keys_selected_mean": jnp.zeros((), jnp.float32),
                 "tiles_touched_ratio": jnp.zeros((), jnp.float32)}
        return params, state, in_shape

    def _rows(self, s: int) -> int:
        return self.rows if s % self.rows == 0 else s

    def project(self, params, x, positions: Optional[pallas_rope.Axes]):
        """(`q^I (N, H^I, S, d^I)`, `k^I (N, S, d^I)`, `w (N, S, H^I)`) of
        the layer's (detached) input `x (N, S, d)`, in `x.dtype`."""
        w = {k: v.astype(x.dtype) for k, v in params.items() if k != "k_norm"}
        with jax.named_scope("proj"):
            q = jnp.einsum("nsm,mhd->nhsd", x,
                           w["q"].reshape(-1, self.heads, self.head_dim))
            k = LayerNorm(self.eps).apply(params["k_norm"], {}, x @ w["k"])[0]
            weight = x @ w["w"]
        with jax.named_scope("rope"):
            if positions is not None:
                # the trunk's sections, halved with the head's width
                positions = positions.over(
                    n * self.head_dim // (2 * sum(positions.sections))
                    for n in positions.sections)
            return (rope(q, self.theta, positions),
                    rope(k, self.theta, positions), weight)

    def scores(self, q, weight, k):
        """`I (N, q, k)` float32 of index queries `q (N, H^I, q, d^I)` with
        weights `weight (N, q, H^I)` against index keys `k (N, k, d^I)`: the
        products from `q.dtype` operands, accumulated, weighed and summed in
        float32. (The causal rule is the caller's.)"""
        with jax.named_scope("scores"):
            z = jnp.einsum("nhqd,nkd->nhqk", q, k,
                           preferred_element_type=jnp.float32)
            weight = jnp.swapaxes(weight, 1, 2).astype(jnp.float32)
            return jnp.sum(jax.nn.relu(z) * weight[..., None], axis=1) * (
                self.heads * self.head_dim) ** -0.5

    def scores_tile(self, s: int) -> Optional[int]:
        """The key tile ops/pallas_index.py's kernels run a sequence of `s`
        positions at, or None where they do not take the shapes of one of
        its bands."""
        rows = self._rows(s)
        tiles = {pallas_index.tile(rows, keys, self.head_dim)
                 for _, keys in _bands(s, rows)}
        return None if None in tiles else min(tiles)

    def block_scores(self, q, weight, k, at):
        """`scores` of the block of queries whose first stands at `at` (an
        integer scalar), of which the callers read no key after the block's
        last query: for shapes `pallas_index.tile` takes, where the step is
        lowered for a TPU, one kernel a direction that keeps the heads'
        products in VMEM and skips the key tiles past the block
        (ops/pallas_index.py); else `scores` — and `scores` too while the
        class holds another function under that name than at import (a
        test's or a comparison tool's own equation runs as it is written)."""
        t = pallas_index.tile(q.shape[2], k.shape[1], q.shape[3])
        if t is None or Indexer.scores is not _SCORES:
            return self.scores(q, weight, k)
        with jax.named_scope("scores"):
            return pallas_index.index_scores(
                q, weight, k, at, (self.heads * self.head_dim) ** -0.5, t,
                self.scores)

    def choose(self, index, tile: int):
        """(`bias (N, S, S)` in `q^I`'s dtype — queries by keys, 0 where the
        query attends to the key, `pallas_attention.MASKED` where not — and
        the counters `(keys selected a query, mean; tiles of `tile` that hold
        a selected pair, over the causal tiles)`) of `project`'s three, a
        block of queries at a time: the scores, every row's exact top-k
        (`_best`), and the row's selection as BITS
        (32 keys a word), which is what a rematerialised layer keeps
        (`"dsa_select"`: 1 / 32 of a byte a pair, exactly the forward's
        choice whatever a recomputed score's last bit would say)."""
        q, k, weight = jax.tree_util.tree_map(lax.stop_gradient, index)
        n, _, s, _ = q.shape
        rows = self._rows(s)
        tile = min(tile, s)
        high = min(tile, rows)  # of a tile's rows, those one block holds
        assert rows % high == 0 and tile % high == 0, (rows, tile)
        # bit j of word w is key `j * words + w`: the bits of a word lie
        # `words` keys apart, so that unpacking is a broadcast along a major
        # axis and no `(S, S)` array of words
        words = -(-s // 32)
        bit = jnp.arange(32, dtype=jnp.uint32)[:, None]

        def block(_, at, keys):
            i = self.block_scores(_rows_of(q, at, rows, 2),
                                  _rows_of(weight, at, rows, 1), k[:, :keys], at)
            with jax.named_scope("select"):
                at_key = jnp.arange(keys)[None, :]
                at_query = at + jnp.arange(rows)[:, None]
                causal = at_key <= at_query
                taken = causal & _best(jnp.where(causal, i, -jnp.inf),
                                       jnp.minimum(at_query + 1, self.topk))
                wide = jnp.pad(taken, ((0, 0), (0, 0), (0, 32 * words - keys)))
                bits = jnp.sum(wide.reshape(n, rows, 32, words).astype(jnp.uint32)
                               << bit, axis=2, dtype=jnp.uint32)
                touched = wide[..., :s].reshape(
                    n, rows // high, high, s // tile, tile).any(axis=(2, 4))
                return None, (jnp.swapaxes(bits, 0, 1),
                              jnp.sum(taken, axis=-1, dtype=jnp.int32).T,
                              jnp.swapaxes(touched, 0, 1))

        _, (bits, kept, touched) = _blocks(block, None, s, rows)
        with jax.named_scope("select"):
            bits = checkpoint_name(jnp.swapaxes(bits, 0, 1), SELECTION_NAME)
            # a word's bit j is the key `j * words` further on: 32 stretches
            # of `words` keys side by side, each written as bf16 as it is made
            # (one broadcast of the words and a shift wrote `(S, S)` words out)
            allowed, masked = (jnp.asarray(v, q.dtype)
                               for v in (0.0, pallas_attention.MASKED))
            bias = jnp.concatenate(
                [jnp.where((bits >> j) & 1 != 0, allowed, masked)
                 for j in range(32)], axis=-1)[..., :s]
            touched = touched.reshape(s // tile, tile // high, n, -1).any(axis=1)
            causal_tiles = n * pallas_attention.tiles_visited(s, tile)
            return bias, (jnp.mean(kept.astype(jnp.float32)),
                          jnp.sum(touched, dtype=jnp.float32) / causal_tiles)

    def report(self, index, q, k, lse, bias, scale: float, counts):
        """The layer's state after a forward: `L^I` (the one entry with a
        gradient, to the indexer's leaves alone) of `project`'s three
        against the core's probabilities — made again from its (detached)
        `q (N, H, S, D)`, `k (N, KV, S, D)` and `lse (N, H, S)` under `bias`
        — and `choose`'s counters."""
        with jax.named_scope("kl"):
            kl = _index_kl(self, scale, index, *jax.tree_util.tree_map(
                lax.stop_gradient, (q, k, lse, bias)))
        kept, touched = counts
        return {"kl": kl, "keys_selected_mean": lax.stop_gradient(kept),
                "tiles_touched_ratio": lax.stop_gradient(touched)}

    def kl_of_block(self, scale, qi, weight, ki, q, k, lse, bias, at):
        """Sum over a block's queries of `sum_s P (ln P - ln softmax(I))`
        over their selected keys: index queries `qi (N, H^I, q, d^I)`,
        weights `weight (N, q, H^I)`, index keys `ki (N, k, d^I)`; the
        core's `q (N, KV, G, q, D)`, `k (N, KV, k, D)`, `lse (N, KV, G, q)`;
        `bias (N, q, k)`; `at`: the block's first position."""
        taken = bias == 0
        score = jnp.where(taken, self.block_scores(qi, weight, ki, at), -jnp.inf)
        log_q = jax.nn.log_softmax(score, axis=-1)

        def of_group(p, group):
            qg, kg, lg = group
            s = jnp.einsum("ngqd,nkd->ngqk", qg, kg,
                           preferred_element_type=jnp.float32) * scale
            return p + jnp.sum(jnp.exp(s - lg[..., None]), axis=1), None

        # a key/value head's query heads at a time: (G, q, k) float32
        p, _ = lax.scan(of_group, jnp.zeros(bias.shape, jnp.float32), (
            jnp.swapaxes(q, 0, 1), jnp.swapaxes(k, 0, 1), jnp.swapaxes(lse, 0, 1)))
        p = jnp.where(taken, p / (q.shape[1] * q.shape[2]), 0.0)
        seen = p > 0
        return jnp.sum(jnp.where(
            seen, p * (jnp.log(jnp.where(seen, p, 1.0))
                       - jnp.where(seen, log_q, 0.0)), 0.0))


# The equation of `I` as this module has it: what the kernels implement
# (`Indexer.block_scores`).
_SCORES = Indexer.scores


def _kl_blocks(indexer: Indexer, scale, index, q, k, lse, bias, fn, carry):
    """`fn(carry, block's arguments of Indexer.kl_of_block)` over every
    block of queries (`_blocks`)."""
    qi, ki, weight = index
    n, h, s, d = q.shape
    kv = k.shape[1]
    rows = indexer._rows(s)
    q = q.reshape(n, kv, h // kv, s, d)
    lse = lse.reshape(n, kv, h // kv, s)

    def block(carry, at, keys):
        return fn(carry, _rows_of(qi, at, rows, 2), _rows_of(weight, at, rows, 1),
                  ki[:, :keys], _rows_of(q, at, rows, 3), k[:, :, :keys],
                  _rows_of(lse, at, rows, 3), _rows_of(bias, at, rows, 1)[..., :keys],
                  at)

    return _blocks(block, carry, s, rows)


def _index_kl_sum(indexer: Indexer, scale: float, index, q, k, lse, bias):
    fn = lambda total, *block: (  # noqa: E731
        total + indexer.kl_of_block(scale, *block), ())
    total, _ = _kl_blocks(indexer, scale, index, q, k, lse, bias, fn,
                          jnp.zeros((), jnp.float32))
    return total / (q.shape[0] * q.shape[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _index_kl(indexer: Indexer, scale: float, index, q, k, lse, bias):
    """`L^I`: the mean over queries of `Indexer.kl_of_block`, a block at a
    time. Its gradient is the indexer's (`index`); `q, k, lse, bias` are
    constants of it. The backward makes each block again and differentiates
    it there, so nothing of a block's size is kept."""
    return _index_kl_sum(indexer, scale, index, q, k, lse, bias)


def _index_kl_fwd(indexer, scale, index, q, k, lse, bias):
    return (_index_kl_sum(indexer, scale, index, q, k, lse, bias),
            (index, q, k, lse, bias))


def _index_kl_bwd(indexer, scale, residuals, g):
    index, q, k, lse, bias = residuals
    ki = index[1]
    g = g / (q.shape[0] * q.shape[2])
    grad = jax.grad(lambda *block: g * indexer.kl_of_block(scale, *block),
                    argnums=(0, 1, 2))

    def fn(d_ki, *block):
        d_q, d_w, d_k = grad(*block)
        d_ki = d_ki.at[:, :d_k.shape[1]].add(d_k.astype(jnp.float32))
        return d_ki, (jnp.moveaxis(d_q, 2, 0), jnp.moveaxis(d_w, 1, 0))

    d_ki, (d_qi, d_w) = _kl_blocks(indexer, scale, index, q, k, lse, bias, fn,
                                   jnp.zeros(ki.shape, jnp.float32))
    zeros = jax.tree_util.tree_map(jnp.zeros_like, (q, k, lse, bias))
    return ((jnp.moveaxis(d_qi, 0, 2), d_ki.astype(ki.dtype),
             jnp.moveaxis(d_w, 0, 1)), *zeros)


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


@dataclasses.dataclass(frozen=True)
class IndexedLayer(DecoderLayer):
    """`DecoderLayer` around an attention that reports (`GQA` with a
    `select`): the layer's state is its expert layer's with the attention's
    report under `"dsa"`."""

    def init(self, key, in_shape: Shape):
        akey, fkey = jax.random.split(key)
        ffn, state, _ = self.ffn.init(fkey, in_shape)
        attn, report, _ = self.attn.init(akey, in_shape)
        params = {"attn_norm": _ones(in_shape[-1]), "attn": attn,
                  "ffn_norm": _ones(in_shape[-1]), "ffn": ffn}
        return params, dict(state, dsa=report), in_shape

    def apply(self, params, state, x, train: bool = False):
        with jax.named_scope("attn"):
            with jax.named_scope("norm"):
                y = _norm(self.eps, params["attn_norm"].astype(x.dtype), x)
            y, dsa = self.attn.apply(params["attn"], state["dsa"], y, train)
            h = x + y
        with jax.named_scope(self.ffn_scope):
            with jax.named_scope("norm"):
                y = _norm(self.eps, params["ffn_norm"].astype(x.dtype), h)
            y, state = self.ffn.apply(params["ffn"], state, y, train)
        return h + y, dict(state, dsa=dsa)


@dataclasses.dataclass(frozen=True)
class KeyeVL(GlmMoe):
    """The language model (module docstring): `GlmMoe` with no dense layer
    and no MTP module, `GQA` under an `Indexer` for its attention, and one
    more term a layer in its loss. `in_shape`, `x`, `y` and `apply` as
    `GlmMoe`."""

    index_weight: float = 1.0

    setup_event: ClassVar[str] = "zoo_dsa"
    kept_names: ClassVar[Tuple[str, ...]] = (
        *GlmMoe.kept_names, SELECTION_NAME)

    def __post_init__(self):
        super().__post_init__()
        if self.first_dense or self.mtp_modules:
            raise ValueError("every keye_vl layer is sparse and the model has "
                             "no multi-token-prediction module")
        if self.attn.select is None:
            raise ValueError("the attention selects its keys (`GQA.select`)")

    def _layers(self) -> List[DecoderLayer]:
        return [IndexedLayer(self.attn, self.experts, self.eps)] * self.n_layers

    def loss_parts(self, params, state, x, y):
        """((cross-entropy, balance terms, `sum_layers L^I`), new state) of a
        training forward."""
        n, s = x.shape
        h, layers = self._trunk(params, state, x, True)
        ce = self._cross_entropy(params, h, y, jnp.ones((n, s), bool)) / (n * s)
        return (ce, sum(st["balance"] for st in layers),
                sum(st["dsa"]["kl"] for st in layers)), dict(state, layers=layers)

    def loss(self, params, state, x, y):
        """(loss, new state) of a training forward: the mean next-token
        cross-entropy, the expert layers' balance terms and `index_weight`
        times the layers' `L^I`."""
        (ce, balance, index), new = self.loss_parts(params, state, x, y)
        return ce + balance + self.index_weight * index, new

    def counters(self, state) -> Dict[str, object]:
        """`GlmMoe.counters` and the selection's, one value a layer."""
        read = (("dsa_index_kl", "kl"),
                ("dsa_keys_selected_mean", "keys_selected_mean"),
                ("dsa_tiles_touched_ratio", "tiles_touched_ratio"))
        got = jax.device_get([s["dsa"] for s in state["layers"]])
        return dict(super().counters(state), **{
            name: [float(layer[key]) for layer in got] for name, key in read})

    def describe(self, tokens_per_step: int, seq_len: int,
                 platform: str) -> Dict[str, object]:
        """`GlmMoe.describe` and the selection: per layer and (sequence,
        head), the pairs it allows and the pairs the core executes in each
        direction on `platform` — every pair of the causal visit list's
        tiles under the kernels, every pair of a block of queries by the
        keys up to its end on the plain path — and what makes the index
        scores there (`index_scores_core`: `"pallas"`, ops/pallas_index.py's
        kernels at `index_scores_tile` keys a grid step, or `"xla"`)."""
        att, pick = self.attn, self.attn.select
        said = super().describe(tokens_per_step, seq_len, platform)
        fused = said["attention_core"] == "fused"
        t = att.core(seq_len)[1] if fused else min(att.q_block, seq_len)
        if fused:
            steps = pallas_attention.selected_schedule(seq_len, t)
            forward, backward = (
                pallas_attention.pairs_computed(steps, 1, t, direction)
                for direction in (False, True))
        else:
            forward = backward = sum(
                t * (a + t) for a in range(0, seq_len, t))
        axes = att.positions
        scores_tile = pick.scores_tile(seq_len) if platform == "tpu" else None
        said.update(
            index_scores_core="xla" if scores_tile is None else "pallas",
            index_scores_tile=scores_tile,
            layers=self.n_layers, topk=pick.topk, index_heads=pick.heads,
            index_head_dim=pick.head_dim, index_weight=self.index_weight,
            attention_core_kind=(
                "causal visit list, the selection a mask in the tile (DATA)"
                if fused else "blocks of queries under the selection's mask"),
            attention_tile=t,
            attention_heads_a_step=att.heads_a_step(seq_len, platform),
            attention_pairs_allowed=pairs_allowed(seq_len, pick.topk),
            attention_pairs_causal=seq_len * (seq_len + 1) // 2,
            attention_pairs_computed=backward,
            attention_pairs_computed_forward=forward,
            selection_saved=f"a bit a pair, {-(-seq_len // 32) * 4} bytes a "
                            f"query ({SELECTION_NAME})",
            rope_axes=1 if axes is None else len(axes.sections),
            rope_sections=None if axes is None else list(axes.sections),
            image_spans=[] if axes is None else [list(sp) for sp in axes.spans])
        return said


def keye_vl(
    *,
    vocab_size: int,
    hidden_size: int,
    moe_intermediate_size: int,
    num_hidden_layers: int,
    num_attention_heads: int,
    num_key_value_heads: int,
    head_dim: int,
    num_experts: int,
    num_experts_per_tok: int,
    indexer_num_heads: int,
    indexer_head_dim: int,
    topk: int,
    mrope_section: Sequence[int],
    image_spans: Sequence[Sequence[int]] = (),
    indexer_num_kv_heads: int = 1,
    rope_theta: float = 1e7,
    rms_norm_eps: float = 1e-6,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    balance_weight: float = 1e-3,
    index_weight: float = 1.0,
    gate_gradient: bool = True,
    dtype: str = "bfloat16",
    q_block: int = 512,
    index_block: int = 256,
    loss_block: int = 2048,
) -> KeyeVL:
    """A `KeyeVL2` language model by its config.json's keys (`sa_config`'s
    and `rope_scaling.mrope_section` by their own names; `norm_topk_prob:
    true`, every layer sparse). `image_spans`: the job's one sequence
    layout, `(start, t, h, w)` an image (ops/pallas_rope.py:Axes).
    `held_experts`, `row_buffer` and `gate_gradient` as `glm_moe_lite` has
    them."""
    if indexer_num_kv_heads != 1:
        raise ValueError("the index heads share one index key "
                         "(indexer_num_kv_heads: 1)")
    held = range(num_experts) if held_experts is None else held_experts
    return KeyeVL(
        vocab=vocab_size, hidden=hidden_size, dense_width=0,
        n_layers=num_hidden_layers,
        attn=GQA(num_attention_heads, num_key_value_heads, head_dim, 1,
                 rope_theta, rms_norm_eps, q_block,
                 select=Indexer(indexer_num_heads, indexer_head_dim, topk,
                                rope_theta, rms_norm_eps, index_block),
                 positions=pallas_rope.Axes(
                     tuple(mrope_section),
                     tuple(tuple(sp) for sp in image_spans))),
        experts=ExpertLayer(
            moe_intermediate_size, num_experts, num_experts_per_tok,
            tuple(held), n_shared=0, scaling=1.0, rows=row_buffer,
            bias_step=0.0, balance=balance_weight, gate_grad=gate_gradient,
            scoring="softmax"),
        first_dense=0, mtp_modules=0, eps=rms_norm_eps, dtype=dtype,
        loss_block=loss_block, index_weight=index_weight,
    )


def keye_vl2_30b_a3b(
    num_hidden_layers: int = 48,
    vocab_size: int = 151936,
    held_experts: Optional[Sequence[int]] = None,
    row_buffer: Optional[int] = None,
    image_spans: Sequence[Sequence[int]] = (),
    gate_gradient: bool = True,
    **overrides,
) -> KeyeVL:
    """Keye-VL-2.0-30B-A3B's language model at its published widths (30 B
    parameters whole, 3 B active a token): hidden 2,048, 32 query heads
    over 4 key/value heads of 128, 128 experts 768 wide, 8 a token, no
    shared expert; 16 index heads of 64 over one index key, 2,048 keys
    kept a query; M-RoPE in sections 16 / 24 / 24 at theta 1e7. Depth, the
    vocabulary's rows and the experts held are the caller's cut: one chip
    of an eight-way expert-parallel group holds `held_experts=range(16)`
    and 18,992 rows. The vision tower is not here: image positions read
    embedding rows."""
    kwargs = dict(
        vocab_size=vocab_size, hidden_size=2048, moe_intermediate_size=768,
        num_hidden_layers=num_hidden_layers, num_attention_heads=32,
        num_key_value_heads=4, head_dim=128, num_experts=128,
        num_experts_per_tok=8, indexer_num_heads=16, indexer_head_dim=64,
        indexer_num_kv_heads=1, topk=2048, mrope_section=(16, 24, 24),
        image_spans=image_spans, rope_theta=1e7, rms_norm_eps=1e-6,
        held_experts=held_experts, row_buffer=row_buffer,
        gate_gradient=gate_gradient,
    )
    kwargs.update(overrides)
    return keye_vl(**kwargs)
