"""Plain reference for the `ouro` family (Ouro-2.6B, `model_type: ouro`;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741
section 3: one stack of layers run `total_ut_steps` times on the same
weights, an exit after every pass, the loss the expected cross-entropy
under a learned exit distribution less its entropy): forward pass, loss,
gradients and the AdamW update in straightforward `jax.numpy`, float32,
at the highest matmul precision, by the contract in
`benchmark/reference/__init__.py`. It imports nothing from the program;
what it shares with the `glm_moe` and `afmoe` references (a rounded
matmul, RMSNorm, the gated MLP, RoPE, a block of queries against all
keys under a mask, the cross-entropy, AdamW spelled out) it imports from
those files. It reads the configuration's `arch` and the program's
pytrees:

    params = {"embed": {"w": (V, d)}, "layers": [layer] * L, "norm": (d,),
              "head": (d, V), "exit_gate": {"w": (d, 1), "b": (1,)}}
    layer  = {"attn_norm", "attn_post_norm", "ffn_norm", "ffn_post_norm":
              (d,), "attn": {"q": (d, H D), "k": (d, KV D), "v": (d, KV D),
              "o": (H D, d)}, "ffn": {"gate": (d, f), "up": (d, f),
              "down": (f, d)}}
    state  : not read (the program keeps its counters there).

The equations, in the order of the issue that brought the family, with
`T = arch["total_ut_steps"]`:

1. `h = E[x]` (no scale).
2. A pass, written T times as a Python loop over the SAME leaves: for
   every layer `a = h + RMSNorm(W_o Attn(RMSNorm(h)))`, `h = a +
   RMSNorm(MLP(RMSNorm(a)))`; attention is q, k, v as heads of D, RoPE
   (rotate-half, the whole D) on q and k, query head i reads key/value
   head i // (H / KV), key j seen by query i iff `j <= i`, written as that
   rule over the whole (S, S) square, a block of queries against ALL keys
   at a time, softmax(q k^T / sqrt(D)) v. The pass closes with the final
   norm, `h <- RMSNorm_f(h)`, and the next pass starts from that.
3. An exit after every pass: `z_t = h W_head`, `lam_t = sigmoid(h . w_g +
   b_g)`, `ce_t` the next-token cross-entropy of every position (a block
   of positions at a time).
4. `p_t = lam_t prod_{j<t} (1 - lam_j)` for t < T and `p_T = prod_{j<T}
   (1 - lam_j)`; loss = mean over positions of `sum_t p_t ce_t - beta
   H(p)`, `H(p) = - sum_t p_t ln p_t` (0 ln 0 = 0), `beta =
   arch["entropy_weight"]`.

`jax.checkpoint` around a layer, a block of queries and a block of
logits changes no value: it keeps 4,096 positions, 32 layer applications
and four 49,152-wide exits inside the chip's memory. Passes and layers are
unrolled: stacked for a `lax.scan` the layers' weights and their
gradients would be copied (PERF.md section 6, PR 43).
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import afmoe as grouped
from benchmark.reference import glm_moe as base
from benchmark.reference.glm_moe import (  # noqa: F401  (the tools' handles)
    F32,
    adamw,
    first_adamw,
    gated_mlp,
    mm,
    nll,
    rms_norm,
)

Q_BLOCK = 256
LOSS_BLOCK = 2048


def attention(arch, p, x):
    n, s, _ = x.shape
    h, kv, d = (arch["num_attention_heads"], arch["num_key_value_heads"],
                arch["head_dim"])
    theta = F32(arch["rope_theta"])
    q = grouped.rotary(mm(x, p["q"]).reshape(n, s, h, d), theta)
    k = grouped.rotary(mm(x, p["k"]).reshape(n, s, kv, d), theta)
    v = mm(x, p["v"]).reshape(n, s, kv, d)
    block = Q_BLOCK if s % Q_BLOCK == 0 else s
    out = lax.map(
        lambda at: grouped._attend(
            lax.dynamic_slice_in_dim(q, at, block, axis=1), k, v, at, None),
        jnp.arange(0, s, block))  # (blocks, N, block, H, D)
    return mm(jnp.swapaxes(out, 0, 1).reshape(n, s, h * d), p["o"])


def decoder_layer(arch, p, x):
    eps = arch["rms_norm_eps"]
    a = attention(arch, p["attn"], rms_norm(x, p["attn_norm"], eps))
    h = x + rms_norm(a, p["attn_post_norm"], eps)
    f = gated_mlp(p["ffn"], rms_norm(h, p["ffn_norm"], eps))
    return h + rms_norm(f, p["ffn_post_norm"], eps)


def passes(arch, params, x) -> List:
    """`h^(1)` ... `h^(T)`: the state the final norm closed after every
    pass over the one stack."""
    layer = jax.checkpoint(functools.partial(decoder_layer, arch))
    h = params["embed"]["w"][x]
    closed = []
    for _ in range(arch["total_ut_steps"]):
        for p in params["layers"]:
            h = layer(p, h)
        h = rms_norm(h, params["norm"], arch["rms_norm_eps"])
        closed.append(h)
    return closed


def logits_of(params, h):
    return mm(h, params["head"])


def gate_of(params, h):
    """lam: the exit gate of every position, never rounded."""
    g = params["exit_gate"]
    return 1.0 / (1.0 + jnp.exp(-(jnp.matmul(h, g["w"])[..., 0] + g["b"][0])))


def exit_distribution(lam: List) -> List:
    """p_1 ... p_T of the gates lam_1 ... lam_T (the last is not read)."""
    stay, p = jnp.ones_like(lam[0]), []
    for l in lam[:-1]:
        p.append(l * stay)
        stay = stay * (1.0 - l)
    return [*p, stay]


def token_losses(params, h, y):
    """The next-token cross-entropy of every position of `h (N, S, d)`, a
    block of positions at a time."""
    flat, want = h.reshape(-1, h.shape[-1]), y.reshape(-1)
    block = LOSS_BLOCK if flat.shape[0] % LOSS_BLOCK == 0 else flat.shape[0]

    @jax.checkpoint
    def part(at):
        z = logits_of(params, lax.dynamic_slice_in_dim(flat, at, block))
        return nll(z, lax.dynamic_slice_in_dim(want, at, block))

    return lax.map(part, jnp.arange(0, flat.shape[0], block)).reshape(y.shape)


def loss_fn(arch, params, state, x, y):
    """(loss, terms): `expected`, `entropy` (means over the positions),
    every exit's mean cross-entropy `ce` and mean `p`."""
    closed = passes(arch, params, x)
    ce = [token_losses(params, h, y) for h in closed]
    p = exit_distribution([gate_of(params, h) for h in closed])
    expected = jnp.mean(sum(p_t * ce_t for p_t, ce_t in zip(p, ce)))
    entropy = jnp.mean(-sum(
        jnp.where(p_t > 0, p_t * jnp.log(jnp.where(p_t > 0, p_t, 1.0)), 0.0)
        for p_t in p))
    terms = {"expected": expected, "entropy": entropy,
             "ce": jnp.stack([jnp.mean(c) for c in ce]),
             "exit_p": jnp.stack([jnp.mean(p_t) for p_t in p])}
    return expected - arch["entropy_weight"] * entropy, terms


@functools.lru_cache(maxsize=None)
def _programs(arch_json: str):
    arch = json.loads(arch_json)
    loss = functools.partial(loss_fn, arch)

    def exits(params, x):
        closed = passes(arch, params, x)
        return (jnp.stack([logits_of(params, h) for h in closed]),
                jnp.stack([gate_of(params, h) for h in closed]))

    return {
        "grads": jax.jit(jax.value_and_grad(loss, has_aux=True)),
        "loss": jax.jit(loss),
        "exits": jax.jit(exits),
        "hidden": jax.jit(functools.partial(passes, arch)),
        "adamw": jax.jit(adamw),
        "first_adamw": jax.jit(first_adamw),
    }


def _program(arch, name):
    return _programs(json.dumps(arch, sort_keys=True))[name]


def train_report(arch, params, state, x, y, *, steps: int = 2, lr, kind, b1,
                 b2, eps, weight_decay, first_grads: bool = False) -> Dict[str, List]:
    """`losses`: the first `steps` AdamW steps' losses on one fixed batch,
    each read before its update; `rows_held`: an empty list a step (the
    model has no expert layer); `terms`: per step the loss's parts; with
    `first_grads`, step 1's gradient of every parameter leaf too
    (`first_grads`, the parameters' pytree, in bfloat16 and on the host:
    what `benchmark/runners/train_zoo_tokens_gradnorm.py` reads a direction and a
    length from). The last step's update is not made; with two steps no
    moment is ever kept."""
    if kind != "adamw":
        raise ValueError(f"the ouro reference writes out AdamW, not {kind!r}")
    params = base._f32(params)
    hyper = dict(lr=lr, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    grads_of = _program(arch, "grads")
    out = {"losses": [], "rows_held": [], "terms": []}
    m = v = None
    with jax.default_matmul_precision("highest"):
        for t in range(1, steps + 1):
            (loss, terms), grads = grads_of(params, state, x, y)
            out["losses"].append(float(loss))
            out["rows_held"].append([])
            out["terms"].append(jax.tree_util.tree_map(
                lambda a: a.tolist(), jax.device_get(terms)))
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
    return out


def train_losses(arch, params, state, x, y, *, steps: int = 2, **hyper):
    return train_report(arch, params, state, x, y, steps=steps, **hyper)["losses"]


def loss_and_grads(arch, params, state, x, y):
    """(loss, gradient of every parameter leaf) of one training forward."""
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = _program(arch, "grads")(
            base._f32(params), state, x, y)
    return loss, grads


def loss_terms(arch, params, state, x, y) -> Dict:
    """The loss's parts (`loss_fn`'s terms, and `loss` itself)."""
    with jax.default_matmul_precision("highest"):
        loss, terms = _program(arch, "loss")(base._f32(params), state, x, y)
    return dict(terms, loss=loss)


def hidden_states(arch, params, state, x):
    """The closed state after every pass."""
    with jax.default_matmul_precision("highest"):
        return _program(arch, "hidden")(base._f32(params), x)


def eval_exits(arch, params, state, x):
    """(logits (T, N, S, V), gates lam (T, N, S)) of every exit."""
    with jax.default_matmul_precision("highest"):
        return _program(arch, "exits")(base._f32(params), x)


def eval_logits(arch, params, state, x):
    """Logits (N, S, V) of the last exit (the model has no mode)."""
    return eval_exits(arch, params, state, x)[0][-1]
