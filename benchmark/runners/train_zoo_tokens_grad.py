"""Traffic kind `train_zoo_tokens_grad`: `train_zoo_tokens`'s run, unedited
and imported — the same job, window, result keys and counters — for a
model whose check needs one more quantity than two losses and the rows.

Why: at the published initialisation a decoder's attention scores are
noise whatever is done to q and k, so RoPE applied in a layer that should
carry no position moves the two losses and the rows held by no more than
bf16 does (6.7e-5 / 4.2e-4 and 808 rows against the clean runs' 6.2e-5 /
3.4e-4 and 717: read on the chip, PR 41, PERF.md section 6): no limit on
those separates the fault from the rounding. What does is the DIRECTION of
the gradient: a leaf whose function changed has a gradient that points
elsewhere, however little the loss moved. After step 1 AdamW's first
moment of a leaf is `(1 - b1)` times that leaf's gradient, so the check
adds

    grad gap   1 - cos(first moment after step 1, the reference's step-1
               gradient), leaf by leaf, the widest over the parameter
               leaves (two all-zero leaves agree: a router that takes no
               gradient on either side; one all-zero leaf of the two is a
               gap of 1) — within `check.grad_gap_tol`

and is otherwise `train_zoo_tokens.checker`'s comparison, limit for limit
(that function builds and frees its own state, so its loop is repeated
here around the one new reading, as `train_zoo_tokens_bd` repeats it). The
reference's `train_report(..., first_grads=True)` hands over its step-1
gradient in bfloat16. `run` is `train_zoo_tokens.run` with this module's
`checker` in its place (`in_place_of_theirs`).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict

from benchmark import token_data
from benchmark.runners import train_zoo, train_zoo_tokens
from benchmark.runners.train_zoo_tokens import cell_lr, optimizer_args


def checker(cfg, traffic, model, reference) -> Callable[[int, Dict], bool]:
    """`(seed, notes) -> correct`: `train_zoo_tokens.checker`'s check and
    the gradient's direction (module docstring)."""
    import jax
    import jax.numpy as jnp
    import optax
    from parallel_cnn_tpu.train import zoo

    chk, length = traffic["check"], traffic["sequence_length"]
    hyper = optimizer_args(cfg["optimizer"], cell_lr(cfg, traffic))
    fresh = jax.jit(lambda key: model.init(key, (length,))[:2])
    optimizer = zoo.make_optimizer(**hyper)
    moments = jax.jit(optimizer.init)
    step = zoo.make_train_step(model, optimizer, 1, None)

    def gap(m, g):
        m, g = m.astype(jnp.float32).ravel(), g.astype(jnp.float32).ravel()
        mm, gg = jnp.sum(m * m), jnp.sum(g * g)
        cos = jnp.sum(m * g) * jax.lax.rsqrt(jnp.maximum(mm * gg, 1e-60))
        return jnp.where((mm == 0) & (gg == 0), 0.0,
                         jnp.where((mm == 0) | (gg == 0), 1.0, 1.0 - cos))

    gaps = jax.jit(lambda opt_state, grads: jax.tree_util.tree_map(
        gap, optax.tree_utils.tree_get(opt_state, "mu"), grads))

    def check(seed: int, notes: Dict[str, Any]) -> bool:
        x, y = token_data.synthetic_tokens(
            jax.random.fold_in(jax.random.key(seed), 1), n=chk["batch"],
            length=length, vocab=cfg["arch"]["vocab_size"])
        params, model_state = fresh(jax.random.key(seed))
        ref = reference.train_report(
            cfg["arch"], params, model_state, x, y, steps=2, first_grads=True,
            **hyper)
        state = zoo.ZooState(params, model_state, moments(params))
        del params, model_state
        losses, rows, by_leaf = [], [], {}
        for i in range(2):
            state, loss = step(state, x, y)  # donates the state it is given
            losses.append(float(loss))
            rows.append(model.counters(state.model_state))
            if i == 0:
                flat = jax.tree_util.tree_flatten_with_path(jax.device_get(
                    gaps(state.opt_state, ref.pop("first_grads"))))[0]
                by_leaf = {jax.tree_util.keystr(p): float(v) for p, v in flat}
        del state
        held = [r["moe_rows_held"] for r in rows]
        worst = max(by_leaf, key=by_leaf.get)
        notes["check_losses"] = {"system": losses, "reference": ref["losses"]}
        notes["check_rows_held"] = {"system": held,
                                    "reference": ref["rows_held"]}
        notes["check_grad_gap"] = {
            "widest": by_leaf[worst], "leaf": worst, "leaves": len(by_leaf),
            "next": sorted(by_leaf.items(), key=lambda kv: -kv[1])[1:4]}
        notes["check_overflow_rows"] = rows[-1]["moe_overflow_rows"]
        close = all(train_zoo._close(a, b, r) for a, b, r in zip(
            losses, ref["losses"], chk["loss_rtol"], strict=True))
        same_rows = all(
            abs(a - b) <= chk["rows_tol"]
            for got, want in zip(held, ref["rows_held"], strict=True)
            for a, b in zip(got, want, strict=True))
        return (close and same_rows and by_leaf[worst] <= chk["grad_gap_tol"]
                and not any(notes["check_overflow_rows"]))

    return check


@contextlib.contextmanager
def in_place_of_theirs():
    """`train_zoo_tokens` with this module's `checker` for its own (its
    `run`, and the tools that loop over it, look the name up there)."""
    theirs = train_zoo_tokens.checker
    train_zoo_tokens.checker = checker
    try:
        yield
    finally:
        train_zoo_tokens.checker = theirs


def run(ctx) -> Dict[str, Any]:
    with in_place_of_theirs():
        return train_zoo_tokens.run(ctx)
