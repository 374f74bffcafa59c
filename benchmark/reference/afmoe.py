"""Plain reference for the `afmoe` family (Trinity-Mini, `model_type:
afmoe`: sliding-window and full attention mixed over grouped key/value
heads, a gated attention output, a norm on both sides of each sub-layer,
sigmoid-routed experts beside a shared one): forward pass, loss,
gradients, the AdamW update and the selection-bias update in
straightforward `jax.numpy`, float32, at the highest matmul precision, by
the contract in `benchmark/reference/__init__.py`. It imports nothing from
the program; what it shares with the `glm_moe` reference (a rounded
matmul, RMSNorm, the gated MLP, top-k as rounds of argmax, the
cross-entropy, AdamW spelled out) it imports from that file. It reads the
configuration's `arch` and the program's pytrees:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V)}
    layer  = {"attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm":
              (d,), "attn": {"q": (d, H D), "k": (d, KV D), "v": (d, KV D),
              "gate": (d, H D), "o": (H D, d), "q_norm": (D,), "k_norm":
              (D,)}, "ffn": ffn}
    ffn    = {"gate": (d, f), "up": (d, f), "down": (f, d)}   dense layers
           | {"router": (d, E), "experts": {"gate": (held, d, f), "up",
              "down": (held, f, d)}, "shared": {"gate", "up", "down"}}
    state  = {"layers": [{} | moe] * L};  moe = {"bias": (E,), ...}: only
              `bias` is read.

The equations, in the order of the issue that brought the family
(`arch.layer_types[l]` is the kind of layer l):

1. `h = E[x] * embed_scale` (sqrt(hidden): `mup_enabled`).
2. Attention: `a = RMSNorm(h)`; q as H heads, k, v as KV heads, g as H
   heads of D; q and k RMS-normalised over each head's D features; RoPE
   (rotate-half) on q and k where the layer is `sliding_attention`, and
   nowhere in a `full_attention` layer; query head a reads key/value head
   a // (H / KV); key j seen by query i iff `j <= i` (full) or `i -
   window < j <= i` (sliding), written as that rule over the whole (S, S)
   square, a block of queries against ALL keys at a time;
   `h <- h + RMSNorm(W_o (o * sigmoid(g)))`.
3. Feed-forward: `m = RMSNorm(h)`; a dense layer is the gated MLP; an
   expert layer `shared(m) + sum_held gate_e E_e(m)`: scores `sigmoid(m
   W_r)`, the k largest of `scores + b` over all E, gates `route_scale *
   s / sum_chosen s`, each HELD expert applied to every token and kept
   where the token chose it; `h <- h + RMSNorm(f)`.
4. `logits = RMSNorm(h) W_head`; loss the mean next-token cross-entropy
   (plus `balance_weight * sum_i f_i P_i` a layer, 0 in the published
   recipe), a block of positions at a time.
5. After every step `b += load_balance_coeff * sign(mean load - load)`.

Runs of layers that are alike (same kind, same feed-forward) are
`lax.scan`s, as are the held experts and the blocks of queries, for the
`glm_moe` reference's reason; `jax.checkpoint` around them changes no
value: it keeps 16,384 positions inside the chip's memory.
"""

from __future__ import annotations

import functools
import itertools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import glm_moe as base
from benchmark.reference.glm_moe import (  # noqa: F401  (the tools' handles)
    F32,
    adamw,
    first_adamw,
    gated_mlp,
    mm,
    nll,
    rms_norm,
    top_k,
)

Q_BLOCK = 256
LOSS_BLOCK = 2048
SLIDING = "sliding_attention"


def rotary(x, theta):
    """x (N, S, heads, D) at positions 0..S-1: feature i and i + D/2 are
    one pair, turned by position * theta^(-2i / D)."""
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def seen(s: int, window, first, block: int):
    """bool (block, S): may query `first + r` see key j. `window` None:
    every key up to its own; else the `window` keys that end with it."""
    i = first + jnp.arange(block)[:, None]
    j = jnp.arange(s)[None, :]
    return (j <= i) if window is None else (j <= i) & (i - window < j)


@functools.partial(jax.checkpoint, static_argnums=(4,))
def _attend(q, k, v, first, window):
    """Queries `first`... q (N, B, H, D) against ALL keys k, v (N, S, KV,
    D), masked."""
    n, b, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", base._r(q), base._r(k)) / jnp.sqrt(F32(d))
    scores = jnp.where(seen(k.shape[1], window, first, b)[None, None], scores,
                       -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    w = jnp.exp(scores)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("nhqk,nkhd->nqhd", base._r(w), base._r(v))


def attention(arch, kind: str, p, x):
    n, s, _ = x.shape
    h, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    eps = arch["rms_norm_eps"]
    sliding = kind == SLIDING
    q = rms_norm(mm(x, p["q"]).reshape(n, s, h, d), p["q_norm"], eps)
    k = rms_norm(mm(x, p["k"]).reshape(n, s, kv, d), p["k_norm"], eps)
    v = mm(x, p["v"]).reshape(n, s, kv, d)
    g = mm(x, p["gate"])
    if sliding:
        theta = F32(arch["rope_theta"])
        q, k = rotary(q, theta), rotary(k, theta)
    window = arch["sliding_window"] if sliding else None
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    out = lax.map(
        lambda at: _attend(lax.dynamic_slice_in_dim(q, at, block, axis=1), k, v,
                           at, window),
        jnp.arange(0, s, block))  # (blocks, N, block, H, D)
    out = jnp.swapaxes(out, 0, 1).reshape(n, s, h * d)
    return mm(out * (1.0 / (1.0 + jnp.exp(-g))), p["o"])


def experts(arch, p, bias, x):
    """(y, balance term, load (E,)) of the expert layer on x (N, S, d)."""
    n, s, d = x.shape
    e, k = arch["router_experts"], arch["num_experts_per_tok"]
    xt = x.reshape(n * s, d)
    score = 1.0 / (1.0 + jnp.exp(-jnp.matmul(xt, p["router"])))  # never rounded
    ids = top_k(score + bias[None, :], k)
    chosen = jnp.take_along_axis(score, ids, axis=1)
    gates = arch["route_scale"] * chosen / jnp.sum(chosen, axis=1, keepdims=True)
    if not arch.get("gate_gradient", True):
        gates = lax.stop_gradient(gates)  # a share without the exchange

    @jax.checkpoint
    def add_expert(y, held):
        w, i = held  # one expert's weights and its published id
        gate = jnp.sum(jnp.where(ids == i, gates, 0.0), axis=1)
        return y + gate[:, None] * gated_mlp(w, xt), None

    y, _ = lax.scan(add_expert, gated_mlp(p["shared"], xt),
                    (p["experts"], jnp.asarray(arch["held_experts"])))
    took = jnp.sum(ids[:, :, None] == jnp.arange(e)[None, None, :], axis=1)
    took = took.astype(F32).reshape(n, s, e)
    f = jnp.sum(took, axis=1) * (e / (k * s))
    share = (score / jnp.sum(score, axis=1, keepdims=True)).reshape(n, s, e)
    balance = arch["balance_weight"] * jnp.mean(
        jnp.sum(f * jnp.mean(share, axis=1), axis=1))
    return y.reshape(n, s, d), balance, jnp.sum(took, axis=(0, 1))


def decoder_layer(arch, kind: str, p, bias, x):
    """(x', balance, load); `bias` is None for a dense layer."""
    eps = arch["rms_norm_eps"]
    a = attention(arch, kind, p["attn"], rms_norm(x, p["attn_norm"], eps))
    h = x + rms_norm(a, p["attn_post_norm"], eps)
    m = rms_norm(h, p["ffn_norm"], eps)
    if bias is None:
        f, balance, load = gated_mlp(p["ffn"], m), F32(0.0), None
    else:
        f, balance, load = experts(arch, p["ffn"], bias, m)
    return h + rms_norm(f, p["ffn_post_norm"], eps), balance, load


def trunk(arch, params, state, x):
    """(hidden states after every layer, balance, [load per expert layer])."""
    h = params["embed"]["w"][x] * F32(arch["embed_scale"])
    layers = list(zip(arch["layer_types"], params["layers"], state["layers"],
                      strict=True))
    hidden, balance, loads = [], F32(0.0), []
    # runs of layers that are alike: one compiled body a run
    for (kind, sparse), run in itertools.groupby(
            layers, key=lambda l: (l[0], bool(l[2]))):
        run = list(run)
        layer = jax.checkpoint(functools.partial(decoder_layer, arch, kind))

        def turn(h, layer_of, layer=layer, sparse=sparse):
            p, bias = layer_of
            h, b, load = layer(p, bias if sparse else None, h)
            return h, (h, b, load)

        stacked = jax.tree_util.tree_map(
            lambda *a: jnp.stack(a),
            *[(p, st["bias"] if sparse else F32(0.0)) for _, p, st in run])
        h, (after, terms, per_layer) = lax.scan(turn, h, stacked)
        hidden += list(after)
        balance = balance + jnp.sum(terms)
        if sparse:
            loads += list(per_layer)
    return hidden, balance, loads


def logits_of(arch, params, h):
    return mm(rms_norm(h, params["norm"], arch["rms_norm_eps"]), params["head"])


def loss_fn(arch, params, state, x, y):
    """(loss, (terms, loads)): the mean next-token cross-entropy, a block
    of positions at a time, plus the layers' balance terms."""
    hidden, balance, loads = trunk(arch, params, state, x)
    h = hidden[-1].reshape(-1, hidden[-1].shape[-1])
    block = LOSS_BLOCK if h.shape[0] % LOSS_BLOCK == 0 else h.shape[0]

    @jax.checkpoint
    def part(at):
        z = logits_of(arch, params, lax.dynamic_slice_in_dim(h, at, block))
        return jnp.sum(nll(z, lax.dynamic_slice_in_dim(y.reshape(-1), at, block)))

    main = jnp.sum(lax.map(part, jnp.arange(0, h.shape[0], block))) / h.shape[0]
    return main + balance, ({"main": main, "balance": balance}, loads)


def moved_bias(arch, state, loads):
    """b += load_balance_coeff * sign(mean load - load), every expert
    layer (the order of `loads`)."""
    left = list(loads)

    def move(st):
        load = left.pop(0)
        return dict(st, bias=st["bias"] + arch["load_balance_coeff"]
                    * jnp.sign(jnp.mean(load) - load))

    return dict(state, layers=[move(st) if st else st for st in state["layers"]])


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)
    return {
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "logits": jax.jit(lambda p, s, x: logits_of(
            arch, p, trunk(arch, p, s, x)[0][-1])),
        "hidden": jax.jit(lambda p, s, x: trunk(arch, p, s, x)[0]),
        "adamw": jax.jit(adamw),
        "first_adamw": jax.jit(first_adamw),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def held_rows(arch, loads) -> List[int]:
    held = jnp.asarray(arch["held_experts"])
    return [int(jnp.sum(load[held])) for load in loads]


def train_report(arch, params, state, x, y, *, steps: int = 2, lr, kind, b1,
                 b2, eps, weight_decay, first_grads: bool = False) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch,
    each read before its update; `rows_held`: per step, each expert
    layer's count of assignments to a held expert; `terms`: per step the
    loss's terms; with `first_grads`, step 1's gradient of every parameter
    leaf too (`first_grads`, the parameters' pytree, in bfloat16 and on the
    host: a direction, for `benchmark/runners/train_zoo_tokens_grad.py`). The last
    step's update is not made; with two steps no moment is ever kept."""
    if kind != "adamw":
        raise ValueError(f"the afmoe reference writes out AdamW, not {kind!r}")
    params, state = base._f32(params), base._f32(state)
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    grads_of = _program(arch, "grads")
    out = {"losses": [], "rows_held": [], "terms": []}
    m = v = None
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            (loss, (terms, loads)), grads = grads_of(params, state, x, y)
            out["losses"].append(float(loss))
            out["rows_held"].append(held_rows(arch, loads))
            out["terms"].append({k: float(val) for k, val in terms.items()})
            if first_grads and t == 1:
                # on the host: beside them step 2's program does not fit the chip
                out["first_grads"] = jax.device_get(jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.bfloat16), grads))
            if t == steps:
                break
            if steps == 2:
                params = _program(arch, "first_adamw")(params, grads, **hyper)
            else:
                if m is None:
                    m = jax.tree_util.tree_map(jnp.zeros_like, params)
                    v = jax.tree_util.tree_map(jnp.zeros_like, params)
                params, m, v = _program(arch, "adamw")(
                    params, grads, m, v, F32(t), **hyper)
            del grads
            state = moved_bias(arch, state, loads)
    return out


def train_losses(arch, params, state, x, y, *, steps: int = 2, **hyper):
    return train_report(arch, params, state, x, y, steps=steps, **hyper)["losses"]


def loss_and_grads(arch, params, state, x, y):
    """(loss, gradient of every parameter leaf) of one training forward."""
    params, state = base._f32(params), base._f32(state)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = _program(arch, "grads")(params, state, x, y)
    return loss, grads


def hidden_states(arch, params, state, x):
    """The residual stream after every decoder layer."""
    params, state = base._f32(params), base._f32(state)
    with jax.default_matmul_precision("highest"):
        return _program(arch, "hidden")(params, state, x)


def eval_logits(arch, params, state, x):
    """Logits (N, S, V) of every position (the model has no mode)."""
    params, state = base._f32(params), base._f32(state)
    with jax.default_matmul_precision("highest"):
        return _program(arch, "logits")(params, state, x)
