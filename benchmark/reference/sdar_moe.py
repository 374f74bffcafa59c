"""Plain reference for the `sdar_moe` family (SDAR-30B-A3B-Chat,
`model_type: sdar_moe`: a Qwen3-MoE decoder trained by block diffusion,
SDAR arXiv:2510.06303, BD3-LM arXiv:2503.09573): the noise, the two-copy
stream and its mask, the forward pass, the weighted loss, gradients and
the AdamW update in straightforward `jax.numpy`, float32, at the highest
matmul precision, by the contract in `benchmark/reference/__init__.py`.
It imports nothing from the program; what it shares with the `glm_moe`
reference (a rounded matmul, RMSNorm, the gated MLP, top-k as rounds of
argmax, the cross-entropy, AdamW spelled out) it imports from that file.
It reads the configuration's `arch` and the program's pytrees:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V)}
    layer  = {"attn_norm": (d,), "attn": {"q": (d, H D), "k": (d, KV D),
              "v": (d, KV D), "o": (H D, d), "q_norm": (D,), "k_norm": (D,)},
              "ffn_norm": (d,), "ffn": {"router": (d, E), "experts":
              {"gate": (held, d, f), "up", "down": (held, f, d)}}}
    state  = {"layers": [...], "noise": {"key": raw key data, "draws": the
              forwards made so far, ...}}: only `noise` is read.

The equations, in the order of the issue that brought the family:

1. Noise (`noise`). A sequence of L tokens is K = L / B blocks. Forward
   number `draws` draws, from `fold_in(key, draws)` split in two, t_b ~
   U(eps, 1) a block and u_i ~ U(0, 1) a token; token i of block b is
   `[MASK]` (`mask_token_id`) iff u_i < t_b.
2. Stream and mask (`stream_mask`). Tokens `[x^t ; x^0]`, positions
   `[0..L-1 ; 0..L-1]`; with beta = position // B, query i sees key j iff
   both are noised and beta(i) = beta(j), or i is noised, j is clean and
   beta(j) < beta(i), or both are clean and beta(j) <= beta(i). Written
   as the three rules over the whole (2L, 2L) square.
3. Layer: pre-norm; q as H heads, k and v as KV heads of D; RMSNorm over
   each head's D features of q and k; RoPE (rotate-half) by position;
   query head a reads key/value head a // (H / KV); the masked softmax, a
   block of queries against ALL 2L keys at a time; then the experts: a
   float32 softmax over all E router outputs, the k largest, gates over
   their sum, and the HELD experts' part alone — each held expert applied
   to every row and kept where the row chose it.
4. Loss: the final norm and the head over the noised half, position i
   against token i, `1 / (N L) sum m / t * CE`, plus `balance_weight *
   sum_i f_i P_i` of every layer (f the share of a sequence's assignments
   expert i took times E, P its mean score), averaged over sequences.

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


def noise(arch, noise_state, forward: int, x):
    """(x^t, m (N, L) bool, t (N, L)) of the `forward`-th training forward
    (0-based) after the state that is handed in."""
    n, l = x.shape
    b = arch["block_length"]
    key = jax.random.fold_in(jax.random.wrap_key_data(noise_state["key"]),
                             noise_state["draws"] + forward)
    tkey, mkey = jax.random.split(key)
    t = jax.random.uniform(tkey, (n, l // b), F32, arch["noise_eps"], 1.0)
    t = jnp.repeat(t, b, axis=1)
    m = jax.random.uniform(mkey, (n, l), F32) < t
    return jnp.where(m, arch["mask_token_id"], x), m, t


def stream_mask(l: int, b: int):
    """bool (2L, 2L): may stream position i (row) see j (column)."""
    at = jnp.arange(2 * l)
    clean = at >= l
    beta = (at % l) // b
    qi, kj = (clean[:, None], beta[:, None]), (clean[None, :], beta[None, :])
    noised_pair = ~qi[0] & ~kj[0] & (qi[1] == kj[1])
    noised_to_clean = ~qi[0] & kj[0] & (kj[1] < qi[1])
    clean_pair = qi[0] & kj[0] & (kj[1] <= qi[1])
    return noised_pair | noised_to_clean | clean_pair


def rotary(x, positions, theta):
    """x (N, S, heads, D) at `positions` (S,): feature i and i + D/2 are
    one pair, turned by position * theta^(-2i / D)."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(r // 2, dtype=F32) * 2.0 / r)
    angle = positions.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@jax.checkpoint
def _attend(q, k, v, seen):
    """q (N, B, H, D) against all keys k, v (N, S, KV, D); `seen` (B, S)."""
    n, b, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", base._r(q), base._r(k)) / jnp.sqrt(F32(d))
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    w = jnp.exp(scores)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("nhqk,nkhd->nqhd", base._r(w), base._r(v))


def attention(arch, p, x):
    """x (N, 2L, d), the stream."""
    n, s, _ = x.shape
    h, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    eps, theta = arch["rms_norm_eps"], F32(arch["rope_theta"])
    positions = jnp.arange(s) % (s // 2)
    q = rms_norm(mm(x, p["q"]).reshape(n, s, h, d), p["q_norm"], eps)
    k = rms_norm(mm(x, p["k"]).reshape(n, s, kv, d), p["k_norm"], eps)
    v = mm(x, p["v"]).reshape(n, s, kv, d)
    q, k = rotary(q, positions, theta), rotary(k, positions, theta)
    seen = stream_mask(s // 2, arch["block_length"])
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    out = lax.map(
        lambda at: _attend(lax.dynamic_slice_in_dim(q, at, block, axis=1), k, v,
                           lax.dynamic_slice_in_dim(seen, at, block, axis=0)),
        jnp.arange(0, s, block))  # (blocks, N, block, H, D)
    return mm(jnp.swapaxes(out, 0, 1).reshape(n, s, h * d), p["o"])


def experts(arch, p, x):
    """(y, balance term, load (E,)) of the expert layer on x (N, S, d)."""
    n, s, d = x.shape
    e, k = arch["router_experts"], arch["num_experts_per_tok"]
    xt = x.reshape(n * s, d)
    z = jnp.matmul(xt, p["router"])  # never rounded
    z = jnp.exp(z - jnp.max(z, axis=1, keepdims=True))
    score = z / jnp.sum(z, axis=1, keepdims=True)
    ids = top_k(score, k)
    chosen = jnp.take_along_axis(score, ids, axis=1)
    gates = chosen / jnp.sum(chosen, axis=1, keepdims=True)
    if not arch.get("gate_gradient", True):
        gates = lax.stop_gradient(gates)  # a share without the exchange

    @jax.checkpoint
    def add_expert(y, held):
        w, i = held  # one expert's weights and its published id
        gate = jnp.sum(jnp.where(ids == i, gates, 0.0), axis=1)
        return y + gate[:, None] * gated_mlp(w, xt), None

    y, _ = lax.scan(add_expert, jnp.zeros_like(xt),
                    (p["experts"], jnp.asarray(arch["held_experts"])))
    took = jnp.sum(ids[:, :, None] == jnp.arange(e)[None, None, :], axis=1)
    took = took.astype(F32).reshape(n, s, e)
    f = jnp.sum(took, axis=1) * (e / (k * s))
    balance = arch["balance_weight"] * jnp.mean(
        jnp.sum(f * jnp.mean(score.reshape(n, s, e), axis=1), axis=1))
    return y.reshape(n, s, d), balance, jnp.sum(took, axis=(0, 1))


def decoder_layer(arch, p, x):
    eps = arch["rms_norm_eps"]
    h = x + attention(arch, p["attn"], rms_norm(x, p["attn_norm"], eps))
    y, balance, load = experts(arch, p["ffn"], rms_norm(h, p["ffn_norm"], eps))
    return h + y, balance, load


def trunk(arch, params, tokens):
    """(the stream's hidden states after every layer, balance, loads)."""
    layer = jax.checkpoint(functools.partial(decoder_layer, arch))

    def turn(h, p):
        h, b, load = layer(p, h)
        return h, (h, b, load)

    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *params["layers"])
    _, (after, terms, loads) = lax.scan(turn, params["embed"]["w"][tokens], stacked)
    return list(after), jnp.sum(terms), list(loads)


def logits_of(arch, params, h):
    return mm(rms_norm(h, params["norm"], arch["rms_norm_eps"]), params["head"])


def loss_fn(arch, params, x, xt, m, t):
    """(loss, (terms, loads)) of clean tokens `x` noised to `xt`."""
    n, l = x.shape
    hidden, balance, loads = trunk(arch, params, jnp.concatenate([xt, x], axis=1))
    ce = nll(logits_of(arch, params, hidden[-1][:, :l]), x)
    main = jnp.sum(jnp.where(m, ce / t, 0.0)) / (n * l)
    return main + balance, ({"main": main, "balance": balance}, loads)


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)
    return {
        "noise": jax.jit(functools.partial(noise, arch), static_argnums=(1,)),
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "hidden": jax.jit(lambda p, tokens: trunk(arch, p, tokens)[0]),
        "logits": jax.jit(lambda p, x: logits_of(
            arch, p, trunk(arch, p, jnp.concatenate([x, x], 1))[0][-1]
            [:, : x.shape[1]])),
        "adamw": jax.jit(adamw),
        "first_adamw": jax.jit(first_adamw),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def held_rows(arch, loads) -> List[int]:
    held = jnp.asarray(arch["held_experts"])
    return [int(jnp.sum(load[held])) for load in loads]


def unused_leaves(grads) -> List[str]:
    """Names (`jax.tree_util.keystr` of the path) of the parameter leaves
    whose gradient is zero in every element: parameters the loss does not
    depend on."""
    flags = jax.tree_util.tree_flatten_with_path(jax.device_get(
        jax.tree_util.tree_map(lambda g: ~jnp.any(g != 0), grads)))[0]
    return sorted(jax.tree_util.keystr(path) for path, f in flags if f)


def train_report(arch, params, state, x, y, *, steps: int = 2, lr, kind, b1,
                 b2, eps, weight_decay) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch
    of clean tokens `x` (`y` is not read), each under its own step's noise
    and read before its update; `rows_held`: per step, each layer's count
    of the stream's assignments to a held expert; `terms`; `masked`: per
    step the tokens masked; `unused_leaves`: per step the parameter leaves
    whose gradient is all zero (none, for a model wired whole). The last
    step's update is not made."""
    if kind != "adamw":
        raise ValueError(f"the sdar_moe reference writes out AdamW, not {kind!r}")
    params = base._f32(params)
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    out = {"losses": [], "rows_held": [], "terms": [], "masked": [],
           "unused_leaves": []}
    m1 = m2 = None
    with jax.default_matmul_precision("highest"):
        for step in range(1, steps + 1):
            xt, m, t = _program(arch, "noise")(state["noise"], step - 1, x)
            (loss, (terms, loads)), grads = _program(arch, "grads")(
                params, x, xt, m, t)
            out["losses"].append(float(loss))
            out["rows_held"].append(held_rows(arch, loads))
            out["terms"].append({k: float(v) for k, v in terms.items()})
            out["masked"].append(int(jnp.sum(m)))
            out["unused_leaves"].append(unused_leaves(grads))
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
    """(loss, gradient of every parameter leaf) of the training forward
    the program makes next from `state`."""
    params = base._f32(params)
    with jax.default_matmul_precision("highest"):
        xt, m, t = _program(arch, "noise")(state["noise"], 0, x)
        (loss, _), grads = _program(arch, "grads")(params, x, xt, m, t)
    return loss, grads


def hidden_states(arch, params, state, tokens):
    """The residual stream after every decoder layer of stream tokens
    `[x^t ; x^0]` (N, 2L), as they are handed in."""
    with jax.default_matmul_precision("highest"):
        return _program(arch, "hidden")(base._f32(params), tokens)


def eval_logits(arch, params, state, x):
    """Logits (N, L, V) of a clean sequence nothing is masked in: the
    noised half of the stream `[x ; x]`."""
    with jax.default_matmul_precision("highest"):
        return _program(arch, "logits")(base._f32(params), x)
