"""Plain reference for the `keye_vl` family (Keye-VL-2.0-30B-A3B's language
model, `model_type: KeyeVL2`: a Qwen3-MoE decoder whose attention keeps, for
every query, the `topk` keys a learned indexer scores highest — DeepSeek-
V3.2-Exp's lightning indexer, its report's section 2.1 — under M-RoPE,
Qwen2-VL arXiv:2409.12191 section 2.1): the positions, the indexer, an exact
top-k by sorting, the attention over the selected keys, the indexer's own
objective, the experts, the loss, gradients and the AdamW update in
straightforward `jax.numpy`, float32, at the highest matmul precision, by
the contract in `benchmark/reference/__init__.py`. It imports nothing from
the program; what it shares with the `glm_moe` and `sdar_moe` references (a
rounded matmul, RMSNorm, the gated MLP, the share's expert layer, the
cross-entropy, AdamW spelled out) it imports from those files. It reads the
configuration's `arch` and the program's pytrees:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V)}
    layer  = {"attn_norm": (d,), "attn": {"q": (d, H D), "k": (d, KV D),
              "v": (d, KV D), "o": (H D, d), "q_norm": (D,), "k_norm": (D,),
              "indexer": {"q": (d, HI dI), "k": (d, dI), "w": (d, HI),
              "k_norm": {"scale": (dI,), "bias": (dI,)}}},
              "ffn_norm": (d,), "ffn": {"router": (d, E), "experts":
              {"gate": (held, d, f), "up", "down": (held, f, d)}}}
    state is not read.

The equations, in the order of the issue that brought the family:

1. Positions (`positions`). Index t of a sequence has a position on three
   axes (time, height, width). Text advances all three alike; an image
   span `[start, t, h, w]` of `arch["mrope_layout"]` that begins at running
   position r puts its cell (i_t, i_h, i_w), the width fastest, at (r +
   i_t, r + i_h, r + i_w), and what follows resumes at the largest
   position so far plus one. Pair i of a head's D/2 (feature i with i +
   D/2) turns by the position of axis(i) times theta^(-2i / D), axis(i)
   by `mrope_section` in order: the first 16 pairs by time, the next 24 by
   height, the last 24 by width; the indexer's 32 pairs by the same
   sections halved.
2. Attention. Pre-norm; q as H heads, k and v as KV heads of D; RMSNorm
   over each head's D features of q and k; M-RoPE; query head a reads
   key/value head a // (H / KV). The indexer reads the layer's normed
   input DETACHED: q^I = u W_q^I as HI heads of dI, k^I = LayerNorm(u
   W_k^I), one for all heads, w = u W_w; M-RoPE on both; I[t, s] = (HI
   dI)^(-1/2) sum_j w[t, j] relu(q^I[t, j] . k^I[s]). S_t: the min(t + 1,
   topk) keys s <= t of the largest I[t, s], ties to the lower s — every
   key's rank in a stable sort of the row, descending. The softmax is over
   S_t alone (a block of queries against ALL keys under the mask), one
   selection for every head, and no gradient passes the selection.
3. The indexer's objective: P[t, s] = the heads' mean probability
   (detached), L^I = mean_t sum_{s in S_t} P (ln P - ln softmax_{S_t}(I)).
4. Experts: `sdar_moe`'s share of a softmax-routed layer as it is.
5. Loss: the final norm and the head, the mean next-token cross-entropy,
   plus `balance_weight * sum_i f_i P_i` and `index_weight * L^I` of every
   layer.

The loops whose turns are alike (layers, held experts, blocks of queries)
are `lax.scan`s / `lax.map`s, as in the `glm_moe` reference and for its
reason; `jax.checkpoint` around them changes no value.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference import glm_moe as base
from benchmark.reference.glm_moe import (  # noqa: F401  (the tools' handles)
    F32,
    adamw,
    first_adamw,
    mm,
    nll,
    rms_norm,
)
from benchmark.reference.sdar_moe import experts, held_rows, unused_leaves

Q_BLOCK = 128


def positions(layout, s: int) -> np.ndarray:
    """int (3, S): the position of every index on (time, height, width)."""
    rows = np.zeros((3, s), np.int64)
    at = following = 0
    for start, t, h, w in sorted(layout):
        rows[:, at:start] = following + np.arange(start - at)
        r = following + (start - at)
        cells = np.stack(np.meshgrid(
            np.arange(t), np.arange(h), np.arange(w), indexing="ij")).reshape(3, -1)
        rows[:, start:start + t * h * w] = r + cells
        at, following = start + t * h * w, r + max(t, h, w)
    rows[:, at:] = following + np.arange(s - at)
    return rows


def rotary(x, where, sections, theta):
    """x (N, S, heads, D) at positions `where` (3, S): feature i and i + D/2
    are one pair, turned by the position of its axis times theta^(-2i / D);
    `sections` pairs in a row belong to each axis."""
    r = x.shape[-1]
    axis = np.repeat(np.arange(len(sections)), sections)
    assert axis.shape[0] == r // 2, (sections, r)
    freq = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    angle = jnp.asarray(where[axis].T, F32) * freq[None, :]  # (S, D/2)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def index_scores(arch, qi, w, ki):
    """I (N, q, S) of index queries qi (N, q, HI, dI) with weights w (N, q,
    HI) against index keys ki (N, S, dI)."""
    hi, di = arch["indexer_num_heads"], arch["indexer_head_dim"]
    z = jnp.einsum("nqhd,nkd->nqhk", base._r(qi), base._r(ki))
    return jnp.sum(w[..., None] * jnp.maximum(z, 0.0), axis=2) / jnp.sqrt(F32(hi * di))


def selected(score, at, topk: int):
    """bool like `score` (N, q, S): key s is one of the `topk` best of the
    keys s <= t of its row (query t = at + row), ties to the lower s."""
    s = score.shape[-1]
    causal = jnp.arange(s)[None, :] <= (at + jnp.arange(score.shape[1]))[:, None]
    masked = jnp.where(causal[None], score, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)  # best first
    rank = jnp.argsort(order, axis=-1, stable=True)     # every key's place
    return causal[None] & (rank < topk)


@functools.partial(jax.checkpoint, static_argnums=(0,))
def _attend(arch, at, q, k, v, qi, w, ki):
    """A block of queries from index `at` against ALL keys: (out (N, B, H,
    D), the block's sum of the indexer's objective, keys selected)."""
    n, b, h, d = q.shape
    group = h // k.shape[2]
    score = index_scores(arch, qi, w, ki)
    take = selected(lax.stop_gradient(score), at, arch["topk"])
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", base._r(q), base._r(k)) / jnp.sqrt(F32(d))
    scores = jnp.where(take[:, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("nhqk,nkhd->nqhd", base._r(p), base._r(v))
    # the indexer's objective against the attention it steered
    target = lax.stop_gradient(jnp.mean(p, axis=1))  # (N, B, S)
    log_i = jnp.where(take, score, -jnp.inf)
    log_i = log_i - jnp.max(log_i, axis=-1, keepdims=True)
    log_i = log_i - jnp.log(jnp.sum(jnp.exp(log_i), axis=-1, keepdims=True))
    seen = take & (target > 0)
    kl = jnp.sum(jnp.where(
        seen, target * (jnp.log(jnp.where(seen, target, 1.0))
                        - jnp.where(seen, log_i, 0.0)), 0.0))
    return out, kl, jnp.sum(take)


def indexed(arch, ix, u, where):
    """(q^I (N, S, HI, dI), k^I (N, S, dI), w (N, S, HI)) of the layer's
    normed input u (N, S, d): the trunk's sections halved with the width."""
    n, s, _ = u.shape
    hi, di = arch["indexer_num_heads"], arch["indexer_head_dim"]
    eps, theta = arch["rms_norm_eps"], F32(arch["rope_theta"])
    narrow = [n_ * di // arch["head_dim"] for n_ in arch["mrope_section"]]
    qi = rotary(mm(u, ix["q"]).reshape(n, s, hi, di), where, narrow, theta)
    ki = rotary(layer_norm(mm(u, ix["k"]), ix["k_norm"], eps)[:, :, None, :],
                where, narrow, theta)[:, :, 0, :]
    return qi, ki, mm(u, ix["w"])


def attention(arch, p, x):
    """(what the attention adds (N, S, d), L^I, keys selected a query) of
    the layer's normed input x (N, S, d)."""
    n, s, _ = x.shape
    h, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    eps, theta = arch["rms_norm_eps"], F32(arch["rope_theta"])
    where = positions(arch["mrope_layout"], s)
    sections = arch["mrope_section"]
    q = rms_norm(mm(x, p["q"]).reshape(n, s, h, d), p["q_norm"], eps)
    k = rms_norm(mm(x, p["k"]).reshape(n, s, kv, d), p["k_norm"], eps)
    v = mm(x, p["v"]).reshape(n, s, kv, d)
    q, k = rotary(q, where, sections, theta), rotary(k, where, sections, theta)
    qi, ki, w = indexed(arch, p["indexer"], lax.stop_gradient(x), where)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    cut = lambda a, at: lax.dynamic_slice_in_dim(a, at, block, axis=1)  # noqa: E731
    out, kl, kept = lax.map(
        lambda at: _attend(arch, at, cut(q, at), k, v, cut(qi, at), cut(w, at), ki),
        jnp.arange(0, s, block))  # (blocks, N, block, H, D)
    return (mm(jnp.swapaxes(out, 0, 1).reshape(n, s, h * d), p["o"]),
            jnp.sum(kl) / (n * s), jnp.sum(kept) / (n * s))


def decoder_layer(arch, p, x):
    eps = arch["rms_norm_eps"]
    a, kl, kept = attention(arch, p["attn"], rms_norm(x, p["attn_norm"], eps))
    h = x + a
    y, balance, load = experts(arch, p["ffn"], rms_norm(h, p["ffn_norm"], eps))
    return h + y, balance, load, kl, kept


def trunk(arch, params, tokens):
    """(hidden states after every layer, balance, loads, each layer's L^I,
    each layer's mean keys selected)."""
    layer = jax.checkpoint(functools.partial(decoder_layer, arch))

    def turn(h, p):
        h, b, load, kl, kept = layer(p, h)
        return h, (h, b, load, kl, kept)

    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *params["layers"])
    _, (after, terms, loads, kls, kept) = lax.scan(
        turn, params["embed"]["w"][tokens], stacked)
    return list(after), jnp.sum(terms), list(loads), kls, kept


def logits_of(arch, params, h):
    return mm(rms_norm(h, params["norm"], arch["rms_norm_eps"]), params["head"])


def loss_fn(arch, params, x, y):
    """(loss, (terms, loads)): y[n, i] is the token after x[n, i]."""
    hidden, balance, loads, kls, kept = trunk(arch, params, x)
    main = jnp.mean(nll(logits_of(arch, params, hidden[-1]), y))
    index = jnp.sum(kls)
    terms = {"main": main, "balance": balance, "index": index,
             "index_by_layer": kls, "keys_selected_mean": kept}
    return main + balance + arch["index_weight"] * index, (terms, loads)


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)
    return {
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "loss": jax.jit(loss),
        "hidden": jax.jit(lambda p, x: trunk(arch, p, x)[0]),
        "logits": jax.jit(lambda p, x: logits_of(arch, p, trunk(arch, p, x)[0][-1])),
        "adamw": jax.jit(adamw),
        "first_adamw": jax.jit(first_adamw),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def train_report(arch, params, state, x, y, *, steps: int = 2, lr, kind, b1,
                 b2, eps, weight_decay, first_grads: bool = False) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch,
    each read before its update; `rows_held`: per step, each layer's count
    of assignments to a held expert; `terms`: per step the loss's parts
    (with every layer's `L^I` and mean keys selected); `unused_leaves`;
    with `first_grads`, step 1's gradient of every parameter leaf too
    (the parameters' pytree, in bfloat16 and on the host: what
    `benchmark/runners/train_zoo_tokens_gradnorm.py` reads a direction and a
    length from). The last step's update is not made."""
    if kind != "adamw":
        raise ValueError(f"the keye_vl reference writes out AdamW, not {kind!r}")
    params = base._f32(params)
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    out = {"losses": [], "rows_held": [], "terms": [], "unused_leaves": []}
    m1 = m2 = None
    with jax.default_matmul_precision("highest"):
        for step in range(1, steps + 1):
            (loss, (terms, loads)), grads = _program(arch, "grads")(params, x, y)
            out["losses"].append(float(loss))
            out["rows_held"].append(held_rows(arch, loads))
            out["terms"].append(jax.tree_util.tree_map(
                lambda a: a.tolist(), jax.device_get(terms)))
            out["unused_leaves"].append(unused_leaves(grads))
            if first_grads and step == 1:
                out["first_grads"] = jax.device_get(jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.bfloat16), grads))
            if step == steps:
                break
            if steps == 2:
                params = _program(arch, "first_adamw")(params, grads, **hyper)
            else:
                if m1 is None:
                    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
                    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
                params, m1, m2 = _program(arch, "adamw")(
                    params, grads, m1, m2, F32(step), **hyper)
            del grads
    return out


def train_losses(arch, params, state, x, y, *, steps: int = 2, **hyper):
    return train_report(arch, params, state, x, y, steps=steps, **hyper)["losses"]


def loss_and_grads(arch, params, state, x, y):
    """(loss, gradient of every parameter leaf) of one training forward."""
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = _program(arch, "grads")(base._f32(params), x, y)
    return loss, grads


def loss_terms(arch, params, state, x, y) -> Dict:
    """The loss's parts (`loss_fn`'s terms, and `loss` itself)."""
    with jax.default_matmul_precision("highest"):
        loss, (terms, _) = _program(arch, "loss")(base._f32(params), x, y)
    return dict(terms, loss=loss)


def hidden_states(arch, params, state, x):
    """The residual stream after every decoder layer."""
    with jax.default_matmul_precision("highest"):
        return _program(arch, "hidden")(base._f32(params), x)


def eval_logits(arch, params, state, x):
    with jax.default_matmul_precision("highest"):
        return _program(arch, "logits")(base._f32(params), x)


def selected_sets(arch, params, x):
    """bool (L, N, S, S): every layer's selection (small sizes only), from
    the layers' inputs as the forward makes them."""
    params = base._f32(params)
    with jax.default_matmul_precision("highest"):
        hidden = _program(arch, "hidden")(params, x)
        inputs = [params["embed"]["w"][x]] + list(hidden[:-1])
        out = []
        for p, h in zip(params["layers"], inputs):
            u = rms_norm(h, p["attn_norm"], arch["rms_norm_eps"])
            qi, ki, w = indexed(arch, p["attn"]["indexer"], u,
                                positions(arch["mrope_layout"], u.shape[1]))
            out.append(selected(index_scores(arch, qi, w, ki), 0, arch["topk"]))
    return jnp.stack(out)
