"""Traffic kind `train_zoo_tokens_gradnorm`: `train_zoo_tokens`'s run,
unedited and imported — the same job, window, result keys and counters —
with `train_zoo_tokens_grad`'s check read two ways and a class of leaves
at a time.

Why (read on the chip, PR 43, PERF.md sections 2 and 6). (1) After step 1
AdamW's first moment of a leaf is `(1 - b1)` times that leaf's gradient:
`train_zoo_tokens_grad` reads its DIRECTION, and a direction is blind to a
scale, as AdamW's first update is (a gradient's sign times the rate). A
gate's scaling factor left out (`routed_scaling_factor`) moves the two
losses, the rows and every leaf's direction by no more than bf16 does and
multiplies the routed experts' gradients by 0.4: the LENGTH of the same
first moment sees that. (2) One limit over every leaf has to stand above
the leaves with the highest floor. A held expert's gradient is a sum over
the rows routed to it; bf16 flips the routing of a few rows in a hundred,
each a whole term of the sum, so the stacked experts' leaves read a gap
of 0.04-0.07 on every seed where an attention leaf reads a tenth of
that — and a limit that clears the experts' floor by a wide margin would
pass a fault that turns an attention leaf's gradient by 0.2.

So the check is `train_zoo_tokens_grad.checker`'s comparison — two steps'
losses (`loss_rtol`), every expert layer's rows held (`rows_tol`), no row
over a buffer — and, for every parameter leaf after step 1, with `m` the
first moment and `g` the reference's step-1 gradient,

    gap    1 - cos(m, g)                (two all-zero leaves agree: 0, 0;
    norm   |ln(|m| / ((1 - b1) |g|))|   one all-zero leaf of the two: 1, > 60)

each within the limits of the leaf's class: `check.grad_tols` is a list of
`{"leaves": <part of a leaf's key path>, "gap": <limit>, "norm": <limit>}`
and a leaf belongs to the first entry whose `leaves` its path holds (`""`
holds the rest, and has to come last). `notes["check_grad_gap"]` is the
sibling's (the widest gap over all leaves), `notes["check_grad_classes"]`
has each class's widest gap and widest norm reading with their leaves,
`notes["check_grad_by_leaf"]` both readings of every leaf.
That function builds and frees its own state, so its loop is repeated
here around the readings, as the sibling repeats `train_zoo_tokens`'s.
`run` is `train_zoo_tokens.run` with this module's `checker` in its place.
"""

from __future__ import annotations

import contextlib
import math
from typing import Any, Callable, Dict

from benchmark import token_data
from benchmark.runners import train_zoo, train_zoo_tokens
from benchmark.runners.train_zoo_tokens import cell_lr, optimizer_args


def class_of(path: str, tols) -> int:
    """Index of the first entry of `tols` whose `leaves` is part of `path`."""
    return next(i for i, t in enumerate(tols) if t["leaves"] in path)


def checker(cfg, traffic, model, reference) -> Callable[[int, Dict], bool]:
    """`(seed, notes) -> correct`: the sibling's check with the gradient's
    direction and length judged a class of leaves at a time (module
    docstring)."""
    import jax
    import jax.numpy as jnp
    import optax
    from parallel_cnn_tpu.train import zoo

    chk, length = traffic["check"], traffic["sequence_length"]
    tols = chk["grad_tols"]
    if tols[-1]["leaves"] != "":
        raise ValueError('check.grad_tols: the last class holds the rest: ""')
    hyper = optimizer_args(cfg["optimizer"], cell_lr(cfg, traffic))
    fresh = jax.jit(lambda key: model.init(key, (length,))[:2])
    optimizer = zoo.make_optimizer(**hyper)
    moments = jax.jit(optimizer.init)
    step = zoo.make_train_step(model, optimizer, 1, None)
    kept = 1.0 - hyper["b1"]

    def read(m, g):
        m, g = m.astype(jnp.float32).ravel(), g.astype(jnp.float32).ravel()
        mm, gg = jnp.sum(m * m), jnp.sum(g * g)
        both, one = (mm == 0) & (gg == 0), (mm == 0) | (gg == 0)
        cos = jnp.sum(m * g) * jax.lax.rsqrt(jnp.maximum(mm * gg, 1e-60))
        norm = jnp.abs(0.5 * jnp.log(jnp.maximum(mm, 1e-60) / (
            kept * kept * jnp.maximum(gg, 1e-60))))
        return jnp.stack([
            jnp.where(both, 0.0, jnp.where(one, 1.0, 1.0 - cos)),
            jnp.where(both, 0.0, norm)])

    readings = jax.jit(lambda opt_state, grads: jax.tree_util.tree_map(
        read, optax.tree_utils.tree_get(opt_state, "mu"), grads))

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
                    readings(state.opt_state, ref.pop("first_grads"))))[0]
                by_leaf = {jax.tree_util.keystr(p): (float(v[0]), float(v[1]))
                           for p, v in flat}
        del state
        held = [r["moe_rows_held"] for r in rows]
        gaps = {p: v[0] for p, v in by_leaf.items()}
        worst = max(gaps, key=gaps.get)
        classes = [dict(t, leaves_read=0, gap_widest=0.0, gap_leaf=None,
                        norm_widest=0.0, norm_leaf=None) for t in tols]
        for path, (gap, norm) in by_leaf.items():
            if not (math.isfinite(gap) and math.isfinite(norm)):
                gap = norm = math.inf  # (a NaN would lose every comparison)
            c = classes[class_of(path, tols)]
            c["leaves_read"] += 1
            if c["gap_leaf"] is None or gap > c["gap_widest"]:
                c["gap_widest"], c["gap_leaf"] = gap, path
            if c["norm_leaf"] is None or norm > c["norm_widest"]:
                c["norm_widest"], c["norm_leaf"] = norm, path
        notes["check_losses"] = {"system": losses, "reference": ref["losses"]}
        notes["check_rows_held"] = {"system": held,
                                    "reference": ref["rows_held"]}
        notes["check_grad_gap"] = {
            "widest": gaps[worst], "leaf": worst, "leaves": len(gaps),
            "next": sorted(gaps.items(), key=lambda kv: -kv[1])[1:4]}
        notes["check_grad_classes"] = classes
        notes["check_grad_by_leaf"] = by_leaf  # path -> (gap, norm)
        notes["check_overflow_rows"] = rows[-1]["moe_overflow_rows"]
        close = all(train_zoo._close(a, b, r) for a, b, r in zip(
            losses, ref["losses"], chk["loss_rtol"], strict=True))
        same_rows = all(
            abs(a - b) <= chk["rows_tol"]
            for got, want in zip(held, ref["rows_held"], strict=True)
            for a, b in zip(got, want, strict=True))
        same_grads = all(
            c["gap_widest"] <= c["gap"] and c["norm_widest"] <= c["norm"]
            for c in classes)
        return (close and same_rows and same_grads
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
