"""Traffic kind `train_zoo_tokens_bd`: `train_zoo_tokens`'s run, unedited
and imported — the same job, window, result keys and counters — for a
model whose check needs one more quantity than two losses and the rows.

Why: at the published initialisation a block-diffusion model's gains are
all 1 and its q and k already have an RMS near 1, so leaving the norms of
q and k out moves the two losses by less than bf16 does (8.5e-6 / 1.5e-4
against the clean runs' 5.9e-5 / 1.7e-3) and the rows held by little more
(1,425 at the least against the clean runs' 828: read on the chip, PR 34,
PERF.md section 6): no limit on those separates the fault from the
rounding. What does is exact: a parameter the step does not use takes a
gradient of zero, so after step 1 AdamW's first moment of that leaf is
zero in every element. The check adds

    unused leaves  the parameter leaves whose first moment is all zero
                   after step 1, by name: the same set as the leaves
                   whose gradient the reference finds all zero (its
                   `train_report` lists them as `unused_leaves`; both
                   sets are empty for a model that is wired whole)

and is otherwise `train_zoo_tokens.checker`'s comparison, limit for limit
(that function builds and frees its own state, so its loop is repeated
here around the one new reading). `run` is `train_zoo_tokens.run` with
this module's `checker` in its place (`in_place_of_theirs`).
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict

from benchmark import token_data
from benchmark.runners import train_zoo, train_zoo_tokens
from benchmark.runners.train_zoo_tokens import cell_lr, optimizer_args


def checker(cfg, traffic, model, reference) -> Callable[[int, Dict], bool]:
    """`(seed, notes) -> correct`: `train_zoo_tokens.checker`'s check and
    the unused leaves (module docstring)."""
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
    still = jax.jit(lambda opt_state: jax.tree_util.tree_map(
        lambda m: ~jnp.any(m != 0), optax.tree_utils.tree_get(opt_state, "mu")))

    def check(seed: int, notes: Dict[str, Any]) -> bool:
        x, y = token_data.synthetic_tokens(
            jax.random.fold_in(jax.random.key(seed), 1), n=chk["batch"],
            length=length, vocab=cfg["arch"]["vocab_size"])
        params, model_state = fresh(jax.random.key(seed))
        ref = reference.train_report(
            cfg["arch"], params, model_state, x, y, steps=2, **hyper)
        state = zoo.ZooState(params, model_state, moments(params))
        del params, model_state
        losses, rows, unused = [], [], []
        for i in range(2):
            state, loss = step(state, x, y)  # donates the state it is given
            losses.append(float(loss))
            rows.append(model.counters(state.model_state))
            if i == 0:
                flags = jax.tree_util.tree_flatten_with_path(
                    jax.device_get(still(state.opt_state)))[0]
                unused = sorted(jax.tree_util.keystr(p) for p, f in flags if f)
        del state
        held = [r["moe_rows_held"] for r in rows]
        notes["check_losses"] = {"system": losses, "reference": ref["losses"]}
        notes["check_rows_held"] = {"system": held,
                                    "reference": ref["rows_held"]}
        notes["check_unused_leaves"] = {"system": unused,
                                        "reference": ref["unused_leaves"][0]}
        notes["check_overflow_rows"] = rows[-1]["moe_overflow_rows"]
        close = all(train_zoo._close(a, b, r) for a, b, r in zip(
            losses, ref["losses"], chk["loss_rtol"], strict=True))
        same_rows = all(
            abs(a - b) <= chk["rows_tol"]
            for got, want in zip(held, ref["rows_held"], strict=True)
            for a, b in zip(got, want, strict=True))
        return (close and same_rows and unused == ref["unused_leaves"][0]
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
