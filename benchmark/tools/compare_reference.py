"""Hold a configuration's model, as the program builds and differentiates
it, against the configuration's plain float32 reference, at the published
size and outside any timed window. Not part of any run of a cell: it is
what the builder of a `model_config` PR runs on the chip to read the
tolerances it then writes down (PERF.md section 6), and what covers the
inside of a block that a near-identity initialisation hides from a cell's
own `correct`.

    python3 benchmark/tools/compare_reference.py --config convnext_b_imagenet \
        --seeds 10 [--seed0 2701000000] [--images 8] [--act bfloat16|float32] \
        [--mode random|init] [--lr 1.25e-4 ...] [--round float8_e4m3fn] \
        [--out chiprun_out/cmp.json]

`--mode random` (default): every parameter leaf drawn at random from the
seed (`random_leaves`: He-scaled weights, gains of order 1, non-zero
biases), so that every branch carries as much as the trunk. Per seed:
evaluation logits (`train=False`), the training loss and the gradient of
every leaf — the system's own loss function (`zoo._build_loss_fn`) on
`--act` inputs against `reference.eval_logits` / `loss_and_grads` on the
same leaves, masks and images. Gaps: logits as max |difference| over the
largest |reference logit|, the loss relatively, a leaf's gradient as the
L2 norm of the difference over the L2 norm of the reference's; the worst
leaf is named. `--act float32` is the control: with the system's
activations in float32 and its matmuls at the highest precision the gaps
must close to rounding.

`--mode init`: the model's own initialisation and the cell's own check —
two steps of `zoo.make_train_step` with the configuration's optimizer at
each `--lr`, against `reference.train_losses` — to read `check.lr` and
the two `check.loss_rtol` of a traffic file, and how far the step itself
moves the loss (a bound has to sit well under that). With `--round
float8_e4m3fn` the system is left out and the reference is held against
itself computed one precision below bf16: the other reading a bound is set
from (it has to call that run not correct).

Needs a reference that provides `loss_and_grads` beside the two functions
of the contract (benchmark/reference/convnext.py does).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, data  # noqa: E402
from benchmark.runners import train_zoo  # noqa: E402


def random_leaves(params, key):
    """Every floating leaf redrawn: rank >= 2 a normal of std
    sqrt(2 / fan_in) (fan_in = all axes but the last), rank 1 a gain of
    1 + 0.1 n where the leaf is named `scale` or `gamma`, else 0.1 n."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(key, len(flat))
    out = []
    for (path, leaf), k in zip(flat, keys):
        n = jax.random.normal(k, leaf.shape, jnp.float32)
        name = str(getattr(path[-1], "key", ""))
        if leaf.ndim >= 2:
            fan_in = leaf.size // leaf.shape[-1]
            out.append(n * (2.0 / fan_in) ** 0.5)
        elif name in ("scale", "gamma"):
            out.append(1.0 + 0.1 * n)
        else:
            out.append(0.1 * n)
    return jax.tree_util.tree_unflatten(treedef, out)


def leaf_gaps(got, want):
    """{leaf path: |got - want|_2 / |want|_2} over two gradient trees."""
    import jax
    import jax.numpy as jnp

    def gap(a, b):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))

    flat = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(gap, got, want))[0]
    return {jax.tree_util.keystr(path): g for path, g in flat}


def _batch(cfg, seed, n, dtype):
    import jax

    h, w, c = cfg["input"]
    return data.synthetic_images(
        jax.random.fold_in(jax.random.key(seed), 1), n=n, hw=(h, w),
        channels=c, classes=cfg["num_classes"], dtype=dtype, chunk=n)


def random_comparer(cfg, model, reference, images, act):
    """seed -> one row of gaps. The system's three programs are jitted
    here, once, so that ten seeds trace and compile them once."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from parallel_cnn_tpu.train import zoo

    in_shape = tuple(cfg["input"])
    # The float32 control also takes the system's matmuls to the highest
    # precision: the chip's default float32 matmul rounds its operands to
    # bf16, which would leave most of the gap in place.
    precision = (jax.default_matmul_precision("highest")
                 if act == "float32" else contextlib.nullcontext())

    @jax.jit
    def leaves(seed_key, leaf_key):
        params, state, _ = model.init(seed_key, in_shape)
        return random_leaves(params, leaf_key), state

    logits_of = jax.jit(lambda p, s, a: model.apply(p, s, a, train=False)[0])
    # what the train step differentiates
    grads_of = jax.jit(jax.value_and_grad(zoo._build_loss_fn(model, None),
                                          has_aux=True))

    def compare(seed):
        x, y = _batch(cfg, seed, images, jnp.dtype(act))
        params, state = leaves(jax.random.key(seed), jax.random.key(seed + 1))
        with precision:
            logits = logits_of(params, state, x).astype(jnp.float32)
            (loss, _), grads = grads_of(params, state, x, y)
        ref_logits = reference.eval_logits(cfg["arch"], params, state, x)
        ref_loss, ref_grads = reference.loss_and_grads(
            cfg["arch"], params, state, x, y)
        gaps = leaf_gaps(grads, ref_grads)
        worst = max(gaps, key=gaps.get)
        return {
            "seed": seed,
            "logits_gap": float(jnp.max(jnp.abs(logits - ref_logits))
                                / jnp.max(jnp.abs(ref_logits))),
            "loss": [float(loss), float(ref_loss)],
            "loss_gap": abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
            "grad_gap_worst": gaps[worst], "grad_gap_worst_leaf": worst,
            "grad_gap_median": sorted(gaps.values())[len(gaps) // 2],
        }

    return compare


def compare_rounded(cfg, model, reference, seed, images, lr, dtype):
    """The lower-precision control: the reference's two losses with every
    matmul and conv operand rounded through `dtype` against the reference
    as it is, on the check's own batch and initialisation. A cell's
    `check.loss_rtol` has to sit under these gaps."""
    import jax
    import jax.numpy as jnp
    from parallel_cnn_tpu.train import zoo

    hyper = train_zoo.optimizer_args(cfg["optimizer"], lr)
    x, y = _batch(cfg, seed, images, jnp.bfloat16)
    state = jax.jit(lambda k: zoo.init_state(
        model, k, tuple(cfg["input"]), zoo.make_optimizer(**hyper)))(
            jax.random.key(seed))
    losses = {}
    for name, rounding in (("float32", None), (dtype, jnp.dtype(dtype))):
        reference.ROUND = rounding
        reference._programs.cache_clear()
        losses[name] = reference.train_losses(
            cfg["arch"], state.params, state.model_state, x, y, steps=2, **hyper)
    reference.ROUND = None
    reference._programs.cache_clear()
    return {"seed": seed, "lr": lr, **losses,
            "gaps": [abs(a - b) / abs(b)
                     for a, b in zip(losses[dtype], losses["float32"])]}


def compare_init(cfg, model, reference, seed, images, lr):
    """The cell's own check at the model's own initialisation: the harness's
    `check`, with its batch and learning rate as arguments."""
    import types

    ctx = types.SimpleNamespace(
        config=cfg, seed=seed,
        traffic={"check": {"batch": images, "lr": lr,
                           "loss_rtol": [float("inf")] * 2}})
    notes = {}
    train_zoo.check(ctx, model, None, notes)
    got, ref = notes["check_losses"]["system"], notes["check_losses"]["reference"]
    return {"seed": seed, "lr": lr, "system": got, "reference": ref,
            "gaps": [abs(a - b) / abs(b) for a, b in zip(got, ref)],
            "step_moves_loss_by": abs(ref[1] - ref[0]) / abs(ref[0])}


def note(row):
    """Each row as it comes, so that a call that is cut keeps what it had."""
    print(json.dumps(row), file=sys.stderr, flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=10)
    ap.add_argument("--seed0", type=int, default=2_701_000_000)
    ap.add_argument("--images", type=int, default=8)
    ap.add_argument("--act", choices=("bfloat16", "float32"), default="bfloat16")
    ap.add_argument("--mode", choices=("random", "init"), default="random")
    ap.add_argument("--lr", type=float, nargs="*", default=None,
                    help="--mode init: learning rates to try (default: the "
                         "configuration's lr_per_256 scaled to --images)")
    ap.add_argument("--round", default=None, metavar="DTYPE",
                    help="--mode init: instead of the system, compare the "
                         "reference computed with matmul and conv operands "
                         "rounded through DTYPE (float8_e4m3fn: the "
                         "precision below bf16) with the reference itself")
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0,
                    help="1: the configuration is one kept with the tests")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cfg = common.find_config(args.config, bool(args.rehearsal))
    from parallel_cnn_tpu.utils import backend

    backend.enable_compile_cache()
    import jax

    model = common.build_model(cfg)
    reference = common.find_reference(cfg)
    seeds = [args.seed0 + 7 * i for i in range(args.seeds)]
    if args.mode == "random":
        compare = random_comparer(cfg, model, reference, args.images, args.act)
        rows = [note(compare(s)) for s in seeds]
        widest = {k: max(r[k] for r in rows)
                  for k in ("logits_gap", "loss_gap", "grad_gap_worst")}
    else:
        lrs = args.lr or [cfg["optimizer"]["lr_per_256"] * args.images / 256]
        one = (functools.partial(compare_rounded, dtype=args.round)
               if args.round else compare_init)
        rows = [note(one(cfg, model, reference, s, args.images, lr))
                for lr in lrs for s in seeds]
        widest = {f"lr={lr:g}": {
            "gaps": [max(r["gaps"][i] for r in rows if r["lr"] == lr)
                     for i in range(2)],
            # the least gap is what a bound must stay under (--round), the
            # least move what it must stay well under (the system)
            "least_gaps": [min(r["gaps"][i] for r in rows if r["lr"] == lr)
                           for i in range(2)],
            "step_moves_loss_by": min(r.get("step_moves_loss_by", 0.0)
                                      for r in rows if r["lr"] == lr)}
            for lr in lrs}
    out = {"config": args.config, "mode": args.mode, "act": args.act,
           "images": args.images, "platform": jax.devices()[0].platform,
           "widest": widest, "rows": rows}
    print(json.dumps({k: out[k] for k in out if k != "rows"}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
