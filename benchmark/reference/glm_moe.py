"""Plain reference for the `glm_moe` family (GLM-4.7-Flash, `model_type:
glm4_moe_lite`; MLA as DeepSeek-V2 arXiv:2405.04434 section 2.1, experts,
balance and multi-token prediction as DeepSeek-V3 arXiv:2412.19437 sections
2.1.2 and 2.2): forward pass, the three loss terms, gradients, the AdamW
update and the selection-bias update in straightforward `jax.numpy`,
float32, at the highest matmul precision, by the contract in
`benchmark/reference/__init__.py`. It imports nothing from the program and
nothing from `optax`; it reads the configuration's `arch` and consumes the
program's pytrees as plain nested lists and dicts:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V), "mtp": {"enorm", "hnorm", "proj": (2d, d),
              "layer": layer}}
    layer  = {"attn_norm": (d,), "attn": {"q_a", "q_norm", "q_b", "kv_a",
              "kv_norm", "kv_b", "o"}, "ffn_norm": (d,), "ffn": ffn}
    ffn    = {"gate": (d, f), "up": (d, f), "down": (f, d)}   dense layers
           | {"router": (d, E), "experts": {"gate": (H, d, f), "up",
              "down": (H, f, d)}, "shared": {"gate", "up", "down"}}
    state  = {"layers": [{} | moe] * L, "mtp": moe};  moe = {"bias": (E,),
              ...}: only `bias` is read; what else the program keeps there
              (its counters) is handed back untouched.

E = `router_experts` scores a token, H = `len(held_experts)` experts whose
weights are here, in the order of `held_experts`. The SHARE is the
program's: route over all E, normalise the gates over all the chosen, add
only what the held experts give; what an absent expert would add is left
out, and the partial result goes on to the next layer.

Written as what each piece is, so that the check does not lean on the
primitives of the code under test: the experts as a loop over the held
ones, each applied to EVERY token and kept where the token chose it (no
sort, no row buffer, no grouped matmul); attention as the full masked
(S, S) softmax, a block of queries against all keys at a time; the top-k
as k rounds of argmax; the cross-entropy over all logits at once; AdamW
and the bias update spelled out. `jax.checkpoint` around a layer and a
block of queries changes no value: it keeps 4,096 positions inside the
chip's memory. The three loops whose turns are alike (the expert layers
after the leading dense ones, the held experts, the blocks of queries)
are `lax.scan`s, so that each body is compiled once: unrolled, the
program was 1.2 GB of code for the chip and 200 s of compiling in every
run (PERF.md section 6, PR 32).

Departures from the published descriptions, all the program's and listed
in the configuration file under `assumed`: u, alpha and lambda; weight
decay on leaves of rank >= 2 only; a constant learning rate; no clipping.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
Q_BLOCK = 512

# The lower-precision control (benchmark/tools/compare_glm_moe.py
# `--round float8_e4m3fn`): when set, both operands of every matmul are
# rounded through this dtype, forward and backward: the reference computed
# one precision below the bf16 the configuration trains in, which a cell's
# `check` has to call not correct. None in every run of a cell.
# `_programs.cache_clear()` after changing it.
ROUND = None


def _r(a):
    return a if ROUND is None else a.astype(ROUND).astype(F32)


def mm(a, b):
    return jnp.matmul(_r(a), _r(b))


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated_mlp(p, x):
    return mm(silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def rotary(x, theta):
    """x (N, S, heads, r): feature i and feature i + r/2 are one pair,
    turned by position * theta^(-2i / r)."""
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@jax.checkpoint
def _attend(q, k, v, first):
    """Queries `first`... (N, B, H, D) against ALL keys, masked."""
    scores = jnp.einsum("nqhd,nkhd->nhqk", _r(q), _r(k)) / jnp.sqrt(
        F32(q.shape[-1]))
    q_at = first + jnp.arange(q.shape[1])[:, None]
    k_at = jnp.arange(k.shape[1])[None, :]
    scores = jnp.where(k_at <= q_at, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    w = jnp.exp(scores)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("nhqk,nkhd->nqhd", _r(w), _r(v))


def attention(arch, p, x):
    n, s, _ = x.shape
    h, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    rank, theta = arch["kv_lora_rank"], F32(arch["rope_theta"])
    q = mm(rms_norm(mm(x, p["q_a"]), p["q_norm"], eps), p["q_b"])
    q = q.reshape(n, s, h, nope + rope)
    latent = mm(x, p["kv_a"])
    k_pe = rotary(latent[..., rank:].reshape(n, s, 1, rope), theta)
    kv = mm(rms_norm(latent[..., :rank], p["kv_norm"], eps), p["kv_b"])
    kv = kv.reshape(n, s, h, nope + arch["v_head_dim"])
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (n, s, h, rope))], axis=-1)
    v = kv[..., nope:]
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    out = lax.map(
        lambda at: _attend(lax.dynamic_slice_in_dim(q, at, block, axis=1),
                           k, v, at),
        jnp.arange(0, s, block))  # (blocks, N, block, H, v_dim)
    return mm(jnp.swapaxes(out, 0, 1).reshape(n, s, -1), p["o"])


def top_k(scores, k):
    """Indices of the k largest of each row, largest first, the lower
    index first among equals: k rounds of argmax."""
    picked = []
    for _ in range(k):
        i = jnp.argmax(scores, axis=-1)
        picked.append(i)
        scores = jnp.where(
            jnp.arange(scores.shape[-1])[None, :] == i[:, None], -jnp.inf, scores)
    return jnp.stack(picked, axis=-1)


def experts(arch, p, bias, x):
    """(y, balance term, load (E,)) of the expert layer on x (N, S, d)."""
    n, s, d = x.shape
    e, k = arch["router_experts"], arch["num_experts_per_tok"]
    xt = x.reshape(n * s, d)
    score = 1.0 / (1.0 + jnp.exp(-jnp.matmul(xt, p["router"])))  # never rounded
    ids = top_k(score + bias[None, :], k)
    chosen = jnp.take_along_axis(score, ids, axis=1)
    gates = arch["routed_scaling_factor"] * chosen / jnp.sum(
        chosen, axis=1, keepdims=True)
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


def decoder_layer(arch, p, bias, x):
    """(x', balance, load); `bias` is None for a dense layer."""
    eps = arch["rms_norm_eps"]
    h = x + attention(arch, p["attn"], rms_norm(x, p["attn_norm"], eps))
    z = rms_norm(h, p["ffn_norm"], eps)
    if bias is None:
        return h + gated_mlp(p["ffn"], z), F32(0.0), None
    y, balance, load = experts(arch, p["ffn"], bias, z)
    return h + y, balance, load


def _bias(state):
    return state["bias"] if state else None


def trunk(arch, params, state, x):
    """(hidden states after every layer, balance, [load per expert layer])."""
    h = params["embed"]["w"][x]
    layer = jax.checkpoint(functools.partial(decoder_layer, arch))
    pairs = list(zip(params["layers"], state["layers"], strict=True))
    dense = [p for p, st in pairs if not st]
    sparse = pairs[len(dense):]
    if not all(st for _, st in sparse):
        raise ValueError("a dense layer after an expert layer")
    hidden, balance, loads = [], F32(0.0), []
    for p in dense:
        h, _, _ = layer(p, None, h)
        hidden.append(h)
    if sparse:
        def turn(h, layer_of):
            h, b, load = layer(*layer_of, h)
            return h, (h, b, load)

        stacked = jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *[(p, st["bias"]) for p, st in sparse])
        _, (after, terms, per_layer) = lax.scan(turn, h, stacked)
        hidden += list(after)
        balance = jnp.sum(terms)
        loads = list(per_layer)
    return hidden, balance, loads


def logits_of(arch, params, h):
    return mm(rms_norm(h, params["norm"], arch["rms_norm_eps"]), params["head"])


def nll(logits, y):
    m = jnp.max(logits, axis=-1, keepdims=True)
    lse = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    return lse - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]


def loss_fn(arch, params, state, x, y):
    """(loss, (terms, loads)): main + mtp_weight * MTP + balance."""
    eps = arch["rms_norm_eps"]
    hidden, balance, loads = trunk(arch, params, state, x)
    main = jnp.mean(nll(logits_of(arch, params, hidden[-1]), y))
    mtp = F32(0.0)
    if arch["num_nextn_predict_layers"]:
        p = params["mtp"]
        both = jnp.concatenate(
            [rms_norm(params["embed"]["w"][y], p["enorm"], eps),
             rms_norm(hidden[-1], p["hnorm"], eps)], axis=-1)
        h2, b, load = jax.checkpoint(functools.partial(decoder_layer, arch))(
            p["layer"], _bias(state["mtp"]), mm(both, p["proj"]))
        balance = balance + b
        loads.append(load)
        # position i has seen token i + 1 and is scored against token i + 2
        mtp = jnp.mean(nll(logits_of(arch, params, h2[:, :-1]), y[:, 1:]))
    terms = {"main": main, "mtp": mtp, "balance": balance}
    return main + arch["mtp_weight"] * mtp + balance, (terms, loads)


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, F32)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else jnp.asarray(a), tree)


def adamw(params, grads, m, v, t, *, lr, b1, b2, eps, weight_decay):
    """One AdamW update (Loshchilov & Hutter 2019), bias-corrected, decay
    decoupled and on leaves of rank >= 2 only; (params, m, v)."""
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def update(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            u = u + weight_decay * p
        return p - lr * u

    return tm(update, params, m, v), m, v


def first_adamw(params, grads, *, lr, b1, b2, eps, weight_decay):
    """The first update alone, from zero moments, without keeping them:
    m / (1 - b1) = g and v / (1 - b2) = g^2 exactly."""
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g / (jnp.abs(g) + eps)
                               + (weight_decay * p if p.ndim >= 2 else 0.0)),
        params, grads)


def moved_bias(arch, state, loads):
    """b += u * sign(mean load - load), every expert layer, the MTP
    module's last (the order of `loads`)."""
    left = list(loads)

    def move(st):
        load = left.pop(0)
        return dict(st, bias=st["bias"] + arch["bias_update_speed"]
                    * jnp.sign(jnp.mean(load) - load))

    new = dict(state, layers=[move(st) if st else st for st in state["layers"]])
    if arch["num_nextn_predict_layers"]:
        new["mtp"] = move(state["mtp"])
    return new


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
                 b2, eps, weight_decay) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch,
    each read before its update; `rows_held`: per step, each expert
    layer's count of assignments to a held expert (a routing flip shows
    here, an arithmetic fault in the loss alone); `terms`: per step the
    three terms. The last step's update is not made; with two steps no
    moment is ever kept."""
    if kind != "adamw":
        raise ValueError(f"the glm_moe reference writes out AdamW, not {kind!r}")
    params, state = _f32(params), _f32(state)
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
    params, state = _f32(params), _f32(state)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = _program(arch, "grads")(params, state, x, y)
    return loss, grads


def hidden_states(arch, params, state, x):
    """The residual stream after every decoder layer."""
    params, state = _f32(params), _f32(state)
    with jax.default_matmul_precision("highest"):
        return _program(arch, "hidden")(params, state, x)


def eval_logits(arch, params, state, x):
    """Logits (N, S, V) of every position (the model has no mode)."""
    params, state = _f32(params), _f32(state)
    with jax.default_matmul_precision("highest"):
        return _program(arch, "logits")(params, state, x)
