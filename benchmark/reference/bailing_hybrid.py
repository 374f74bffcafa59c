"""Plain reference for the `bailing_hybrid` family (Ling-3.0-flash,
`model_type: bailing_hybrid`: a delta rule with a channel-wise decay, KDA,
Kimi Linear arXiv:2510.26692 section 3, in five layers of six; latent
attention without a query latent, DeepSeek-V2 arXiv:2405.04434 section
2.1, in the sixth; sigmoid-routed experts under a group limit,
DeepSeek-V3 arXiv:2412.19437 section 2.1.2): forward pass, loss,
gradients, the AdamW update and the selection-bias update in
straightforward `jax.numpy`, float32, at the highest matmul precision, by
the contract in `benchmark/reference/__init__.py`. It imports nothing from
the program; what it shares with the `glm_moe` reference (a rounded
matmul, RMSNorm, a blocked causal softmax, the cross-entropy, AdamW
and the selection bias's move spelled out) it imports from that file. It reads the configuration's
`arch` and the program's pytrees:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V), "mtp": {"enorm", "hnorm", "proj": (2d, d),
              "layer": layer}}          ("mtp" where the arch builds one)
    layer  = {"attn_norm", "ffn_norm": (d,), "attn": kda | mla, "ffn": ffn}
    kda    = {"q", "k", "v", "f": (d, H D), "o": (H D, d), "beta", "gate":
              (d, H), "q_conv", "k_conv", "v_conv": (K, H D), "a_log":
              (H,), "f_bias": (H D,), "o_norm": (D,)}
    mla    = {"q": (d, H (nope + rope)), "kv_a": (d, rank + rope),
              "kv_norm": (rank,), "kv_b": (rank, H (nope + v)), "o": (H v,
              d), "gate": (d, H)}
    ffn    = {"gate": (d, f), "up": (d, f), "down": (f, d)}   dense layers
           | {"router": (d, E), "experts": {"gate": (held, d, f), "up",
              "down": (held, f, d)}, "shared": {"gate", "up", "down"}}
    state  = {"layers": [{} | moe] * L, ...};  moe = {"bias": (E,), ...}:
              only `bias` is read.

The equations (`arch.layer_types[l]` is the kind of layer l):

1. `h = E[x]`; a layer is `h <- h + Attn(RMSNorm(h))`, `h <- h +
   FFN(RMSNorm(h))`.
2. `linear_attention`: `q, k, v = SiLU(Conv(x W_q)), SiLU(Conv(x W_k)),
   SiLU(Conv(x W_v))`, the convolution K shifted copies of its input times
   a tap a channel, added (position t reads t - K + 1 .. t); q and k over
   their Euclidean norm a head (`x / sqrt(sum x^2 + 1e-6)`), q times
   D^-1/2; `g = lower_bound * sigmoid(exp(A_h) (x W_f + b_f))` a channel;
   `beta = sigmoid(x W_beta)` a head; then, a position at a time from
   `S = 0`: `S <- Diag(e^g_t) S`; `S <- S + k_t (beta_t (v_t - S^T
   k_t))^T` — which is `(I - beta k k^T) Diag(e^g) S + beta k v^T` —
   and `o_t = S^T q_t`; `y = W_o [RMSNorm(o) over a head's D, gain (D,),
   times sigmoid(x W_g) a head]`. No chunks anywhere: a `lax.scan` over
   positions, `jax.checkpoint`ed a block of positions at a time so that
   its backward holds block-start states only.
3. `full_attention`: `q = x W_q` as H heads of `nope + rope`; `[c | k_pe] =
   x W_kva`; `[k_nope | v] = RMSNorm(c) W_kvb` a head; RoPE over ADJACENT
   pairs (2i, 2i + 1) of `q_pe` and of the `k_pe` all heads share; the
   full masked (S, S) softmax of `q k / sqrt(nope + rope)`, a block of
   queries against all keys at a time; times `sigmoid(x W_g)` a head;
   `W_o`.
4. Experts: `s = sigmoid(m W_r)`; `s' = s + b`; the E experts in `n_group`
   runs of neighbours, a group's score the sum of its two largest `s'`,
   the `topk_group` best groups stay; of their experts the k largest
   `s'` are chosen (by `jnp.argsort`); gates `routed_scaling_factor * s /
   sum_chosen s`; each HELD expert applied to every token and kept where
   the token chose it, plus the shared expert. A gated MLP with a limit
   L > 0 is `down(silu(min(a, L)) * clip(u, -L, L))` (`arch.
   expert_swiglu_limit_list[l]`, `share_expert_swiglu_limit_list[l]`).
5. `logits = RMSNorm(h) W_head`; loss the mean next-token cross-entropy, a
   block of positions at a time, plus `mtp_weight` times the MTP
   module's (a `full_attention` expert layer on `[RMSNorm(E[y]) |
   RMSNorm(h)] W_proj`, scored against the token after next) where
   `arch.num_nextn_predict_layers` builds one, plus `balance_weight *
   sum_i f_i P_i` a layer (0 in the cell).
6. After every step `b += bias_update_speed * sign(mean load - load)`.

The held experts, the blocks of queries and the positions of the
recurrence are `lax.scan`s, for the `glm_moe` reference's reason (one
compiled body a loop); the seven layers are not (`trunk`).
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import glm_moe as base
from benchmark.reference.glm_moe import (  # noqa: F401  (the tools' handles)
    F32,
    _attend,
    _r,
    adamw,
    first_adamw,
    held_rows,
    mm,
    moved_bias,
    nll,
    rms_norm,
    silu,
)

Q_BLOCK = 256
LOSS_BLOCK = 2048
SCAN_BLOCK = 64
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def gated_mlp(p, x, limit: float = 0.0):
    a, u = mm(x, p["gate"]), mm(x, p["up"])
    if limit > 0:
        a, u = jnp.minimum(a, limit), jnp.clip(u, -limit, limit)
    return mm(silu(a) * u, p["down"])


def short_conv(x, taps):
    """x (N, S, C), taps (K, C): `y_t = sum_i taps[i] x_(t - K + 1 + i)`, K
    shifted copies added; positions before 0 read as 0."""
    k, s = taps.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for i in range(k):
        back = k - 1 - i
        y = y + taps[i] * jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return y


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """q, k, g (N, S, H, D), v (N, S, H, Dv), beta (N, S, H): `o (N, S, H,
    Dv)` of the recurrence, a position at a time (module docstring 2)."""
    n, s, h, d = q.shape

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("nhk,nhkv->nhv", _r(k_t), _r(state))
        state = state + k_t[..., None] * (
            b_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.einsum("nhk,nhkv->nhv", _r(q_t), _r(state))

    @jax.checkpoint
    def block(state, at):
        return lax.scan(position, state, at)

    size = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s

    def by_block(a):  # (N, S, ...) -> (blocks, size, N, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(s // size, size, *a.shape[1:])

    _, o = lax.scan(block, jnp.zeros((n, h, d, v.shape[-1]), F32),
                    tuple(map(by_block, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(s, n, h, -1), 0, 1)


def log_decay(arch, p, x):
    """g (N, S, H, D): `lower_bound * sigmoid(exp(A_h) (x W_f + b_f))`."""
    n, s, _ = x.shape
    h, d = arch["num_attention_heads"], arch["head_dim"]
    z = (mm(x, p["f"]) + p["f_bias"]).reshape(n, s, h, d)
    return arch["kda_lower_bound"] * sigmoid(
        jnp.exp(p["a_log"])[None, None, :, None] * z)


def linear_attention(arch, p, x):
    n, s, _ = x.shape
    h, d = arch["num_attention_heads"], arch["head_dim"]
    q, k, v = (silu(short_conv(mm(x, p[name]), p[f"{name}_conv"])).reshape(
        n, s, h, d) for name in ("q", "k", "v"))
    q, k = unit(q) / jnp.sqrt(F32(d)), unit(k)
    beta = sigmoid(mm(x, p["beta"]))
    o = delta_rule(q, k, v, log_decay(arch, p, x), beta)
    o = rms_norm(o, p["o_norm"], arch["rms_norm_eps"])
    o = o * sigmoid(mm(x, p["gate"]))[..., None]
    return mm(o.reshape(n, s, h * d), p["o"])


def rotary_pairs(x, theta):
    """x (N, S, heads, r) at positions 0..S-1: features 2i and 2i + 1 are
    one pair, turned by position * theta^(-2i / r)."""
    s, r = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    angle = jnp.arange(s, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def full_attention(arch, p, x):
    n, s, _ = x.shape
    h, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    rank, theta = arch["kv_lora_rank"], F32(arch["rope_theta"])
    q = mm(x, p["q"]).reshape(n, s, h, nope + rope)
    latent = mm(x, p["kv_a"])
    k_pe = rotary_pairs(latent[..., rank:].reshape(n, s, 1, rope), theta)
    kv = mm(rms_norm(latent[..., :rank], p["kv_norm"], eps), p["kv_b"])
    kv = kv.reshape(n, s, h, nope + arch["v_head_dim"])
    q = jnp.concatenate(
        [q[..., :nope], rotary_pairs(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (n, s, h, rope))], axis=-1)
    v = kv[..., nope:]
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    out = lax.map(
        lambda at: _attend(lax.dynamic_slice_in_dim(q, at, block, axis=1),
                           k, v, at),
        jnp.arange(0, s, block))  # (blocks, N, block, H, v_dim): 1/sqrt(192)
    out = jnp.swapaxes(out, 0, 1).reshape(n, s, h, -1)
    out = out * sigmoid(mm(x, p["gate"]))[..., None]
    return mm(out.reshape(n, s, -1), p["o"])


def choose(arch, biased):
    """ids (T, k) of the experts chosen from `biased (T, E)` under the
    group limit: sorts, no top-k primitive."""
    t, e = biased.shape
    groups = biased.reshape(t, arch["n_group"], -1)
    if arch["n_group"] > 1:
        ranked = jnp.sort(groups, axis=-1)
        best = ranked[..., -1] + ranked[..., -2]
        order = jnp.argsort(-best, axis=-1)  # stable: the lower group first
        place = jnp.argsort(order, axis=-1)  # a group's place in that order
        groups = jnp.where((place < arch["topk_group"])[..., None], groups,
                           -jnp.inf)
    return jnp.argsort(-groups.reshape(t, e), axis=-1)[
        :, : arch["num_experts_per_tok"]]


def experts(arch, p, bias, x, limit: float, shared_limit: float):
    """(y, balance term, load (E,)) of the expert layer on x (N, S, d)."""
    n, s, d = x.shape
    e, k = arch["router_experts"], arch["num_experts_per_tok"]
    xt = x.reshape(n * s, d)
    score = sigmoid(jnp.matmul(xt, p["router"]))  # never rounded
    ids = choose(arch, score + bias[None, :])
    chosen = jnp.take_along_axis(score, ids, axis=1)
    gates = arch["routed_scaling_factor"] * chosen / jnp.sum(
        chosen, axis=1, keepdims=True)
    if not arch.get("gate_gradient", True):
        gates = lax.stop_gradient(gates)  # a share without the exchange

    @jax.checkpoint
    def add_expert(y, held):
        w, i = held  # one expert's weights and its published id
        gate = jnp.sum(jnp.where(ids == i, gates, 0.0), axis=1)
        return y + gate[:, None] * gated_mlp(w, xt, limit), None

    y, _ = lax.scan(add_expert, gated_mlp(p["shared"], xt, shared_limit),
                    (p["experts"], jnp.asarray(arch["held_experts"])))
    took = jnp.sum(ids[:, :, None] == jnp.arange(e)[None, None, :], axis=1)
    took = took.astype(F32).reshape(n, s, e)
    f = jnp.sum(took, axis=1) * (e / (k * s))
    share = (score / jnp.sum(score, axis=1, keepdims=True)).reshape(n, s, e)
    balance = arch["balance_weight"] * jnp.mean(
        jnp.sum(f * jnp.mean(share, axis=1), axis=1))
    return y.reshape(n, s, d), balance, jnp.sum(took, axis=(0, 1))


def decoder_layer(arch, kind: str, limits, p, bias, x):
    """(x', balance, load); `bias` is None for a dense layer; `limits` the
    layer's (routed, shared) clamps."""
    eps = arch["rms_norm_eps"]
    attend = linear_attention if kind == LINEAR else full_attention
    h = x + attend(arch, p["attn"], rms_norm(x, p["attn_norm"], eps))
    m = rms_norm(h, p["ffn_norm"], eps)
    if bias is None:
        return h + gated_mlp(p["ffn"], m), F32(0.0), None
    y, balance, load = experts(arch, p["ffn"], bias, m, *limits)
    return h + y, balance, load


def limits_of(arch, i: int):
    return tuple(float((arch.get(name) or [0.0] * (i + 1))[i]) for name in (
        "expert_swiglu_limit_list", "share_expert_swiglu_limit_list"))


def trunk(arch, params, state, x):
    """(hidden states after every layer, balance, [load per expert layer]).
    A layer after a layer, each rematerialised: stacked for a `lax.scan`
    the five alike layers' weights and their gradients would be 4.2 GB of
    copies beside the 3.3 GB of parameters and as much of gradients, and
    the chip holds the program's own parameters then too."""
    h = params["embed"]["w"][x]
    hidden, balance, loads = [], F32(0.0), []
    for i, (kind, p, st) in enumerate(zip(
            arch["layer_types"], params["layers"], state["layers"],
            strict=True)):
        layer = jax.checkpoint(functools.partial(
            decoder_layer, arch, kind, limits_of(arch, i)))
        h, b, load = layer(p, st["bias"] if st else None, h)
        hidden.append(h)
        balance = balance + b
        if st:
            loads.append(load)
    return hidden, balance, loads


def logits_of(arch, params, h):
    return mm(rms_norm(h, params["norm"], arch["rms_norm_eps"]), params["head"])


def _mean_nll(arch, params, h, y):
    """Mean cross-entropy of `h (N, S', d)` against `y (N, S')`, a block of
    positions at a time."""
    h, y = h.reshape(-1, h.shape[-1]), y.reshape(-1)
    block = LOSS_BLOCK if h.shape[0] % LOSS_BLOCK == 0 else h.shape[0]

    @jax.checkpoint
    def part(at):
        z = logits_of(arch, params, lax.dynamic_slice_in_dim(h, at, block))
        return jnp.sum(nll(z, lax.dynamic_slice_in_dim(y, at, block)))

    return jnp.sum(lax.map(part, jnp.arange(0, h.shape[0], block))) / h.shape[0]


def loss_fn(arch, params, state, x, y):
    """(loss, (terms, loads)): main + mtp_weight * MTP + balance."""
    eps = arch["rms_norm_eps"]
    hidden, balance, loads = trunk(arch, params, state, x)
    main = _mean_nll(arch, params, hidden[-1], y)
    mtp = F32(0.0)
    if arch["num_nextn_predict_layers"]:
        p = params["mtp"]
        both = jnp.concatenate(
            [rms_norm(params["embed"]["w"][y], p["enorm"], eps),
             rms_norm(hidden[-1], p["hnorm"], eps)], axis=-1)
        h2, b, load = jax.checkpoint(functools.partial(
            decoder_layer, arch, FULL, (0.0, 0.0)))(
                p["layer"], state["mtp"]["bias"], mm(both, p["proj"]))
        balance = balance + b
        loads.append(load)
        # position i has seen token i + 1 and is scored against token i + 2
        mtp = _mean_nll(arch, params, h2[:, :-1], y[:, 1:])
    terms = {"main": main, "mtp": mtp, "balance": balance}
    return main + arch["mtp_weight"] * mtp + balance, (terms, loads)


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)
    return {
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "loss": jax.jit(loss),
        "logits": jax.jit(lambda p, s, x: logits_of(
            arch, p, trunk(arch, p, s, x)[0][-1])),
        "hidden": jax.jit(lambda p, s, x: trunk(arch, p, s, x)[0]),
        "adamw": jax.jit(adamw),
        "first_adamw": jax.jit(first_adamw),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def train_report(arch, params, state, x, y, *, steps: int = 2, lr, kind, b1,
                 b2, eps, weight_decay, first_grads: bool = False) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch,
    each read before its update; `rows_held`: per step, each expert
    layer's count of assignments to a held expert; `terms`: per step the
    loss's terms; with `first_grads`, step 1's gradient of every parameter
    leaf too (the parameters' pytree, in bfloat16 and on the host: a
    direction, for `benchmark/runners/train_zoo_tokens_grad.py`). The last
    step's update is not made, nor its gradient; with two steps no moment
    is ever kept."""
    if kind != "adamw":
        raise ValueError(
            f"the bailing_hybrid reference writes out AdamW, not {kind!r}")
    params, state = base._f32(params), base._f32(state)
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    grads_of = _program(arch, "grads")
    out = {"losses": [], "rows_held": [], "terms": []}
    m = v = None
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            if t == steps and not (first_grads and t == 1):
                # no update follows: the forward alone (beside the caller's
                # parameters and the updated ones, a third set of that size
                # and the backward's temporaries do not fit the chip)
                (loss, (terms, loads)), grads = _program(arch, "loss")(
                    params, state, x, y), None
            else:
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
