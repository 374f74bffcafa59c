"""Hold a `glm_moe` configuration's model, as the program builds, routes
and differentiates it, against its plain float32 reference
(benchmark/reference/glm_moe.py) at the published widths and the timed
sequence length, outside any timed window. `compare_reference.py`'s
sibling for this family (token sequences, a model that owns its loss,
per-layer outputs, held-row counts). Not part of any run of a cell: it is
what a builder runs on the chip to read the bounds a traffic file's
`check` is then given (PERF.md section 6).

    python3 benchmark/tools/compare_glm_moe.py --workload glm47f_train \
        --seeds 6 [--seed0 2701000000] [--mode init|layers] \
        [--controls float8_e4m3fn,rope_off,shared_dropped,mtp_off] \
        [--control-seeds 1] [--out chiprun_out/cmp.json]

`--mode init` (default): the cell's own check, a row a seed — it IS
`benchmark/runners/train_zoo_tokens.py:checker`, the comparison a run of
the cell makes, with the cell's bounds: `correct`, the two losses and
their relative gaps, each expert layer's rows held on both sides and the
widest difference. `--controls` go through the same comparison after
the seeds, in the same process, on seeds of their own, and each has to
read `correct: false` (the tool exits 1 where one reads true): a dtype
(`float8_e4m3fn`) computes the reference with every matmul's operands
rounded through it, one precision below the bf16 the configuration
trains in; `rope_off`, `shared_dropped` and `mtp_off` plant that fault
in the system (RoPE left out, the shared expert dropped, the MTP term's
weight 0), as `tests/test_glm_moe.py` plants them at toy size.

`--mode layers`: every leaf drawn at random (`random_leaves`: weights of
std 1 / sqrt(fan_in), gains 1 + 0.1 n, selection biases 0.01 n), so that
every branch carries as much as the trunk. Per seed, one sequence: the
residual stream after every decoder layer (L2 norm of the difference
over the L2 norm of the reference's), the loss (relative), and every leaf's
gradient (L2 norm of the difference over the L2 norm of the reference's;
the worst leaf is named). A token whose k-th and (k+1)-th scores lie
nearer than bf16 rounding routes differently on the two sides: the rows
held say how many did.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, token_data  # noqa: E402
from benchmark.runners import train_zoo_tokens  # noqa: E402
from benchmark.tools.compare_reference import leaf_gaps  # noqa: E402

FAULTS = ("rope_off", "shared_dropped", "mtp_off")


def random_leaves(params, state, key):
    """(params, state) with every floating leaf of the parameters redrawn
    (rank >= 2: normal of std 1 / sqrt(rows of the matmul); rank 1, all of
    them gains: 1 + 0.1 n) and every selection bias 0.01 n."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(flat) + 1)
    out = []
    for leaf, k in zip(flat, keys[1:]):
        n = jax.random.normal(k, leaf.shape, jnp.float32)
        out.append(n * leaf.shape[-2] ** -0.5 if leaf.ndim >= 2
                   else 1.0 + 0.1 * n)

    sflat, sdef = jax.tree_util.tree_flatten_with_path(state)
    biases = [
        0.01 * jax.random.normal(jax.random.fold_in(keys[0], i), leaf.shape,
                                 jnp.float32)
        if getattr(path[-1], "key", None) == "bias" else leaf
        for i, (path, leaf) in enumerate(sflat)]
    return (jax.tree_util.tree_unflatten(treedef, out),
            jax.tree_util.tree_unflatten(sdef, biases))


def _tokens(cfg, seed, n):
    import jax

    return token_data.synthetic_tokens(
        jax.random.fold_in(jax.random.key(seed), 1), n=n,
        length=cfg["input"][0], vocab=cfg["arch"]["vocab_size"])


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through
    it; everything is put back on the way out."""
    import jax.numpy as jnp
    from parallel_cnn_tpu.nn import glm_moe, layers

    rope, shared = glm_moe.rope, glm_moe.ExpertLayer._shared
    try:
        if name == "rope_off":
            glm_moe.rope = lambda x, theta: x
        elif name == "shared_dropped":
            class Nothing(layers.GatedMLP):
                def apply(self, params, state, x, train=False):
                    return x * 0, state

            glm_moe.ExpertLayer._shared = lambda self: Nothing(self.width)
        elif name == "mtp_off":
            fac = cfg["factory"]
            cfg = dict(cfg, factory=dict(
                fac, kwargs=dict(fac["kwargs"], mtp_weight=0.0)))
        else:
            reference.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        glm_moe.rope, glm_moe.ExpertLayer._shared = rope, shared
        if reference.ROUND is not None:
            reference.ROUND = None
            reference._programs.cache_clear()


def init_comparer(cfg, traffic, model, reference):
    """seed -> one row: the cell's own check and what it compared."""
    check = train_zoo_tokens.checker(cfg, traffic, model, reference)

    def compare(seed):
        notes = {}
        correct = check(seed, notes)
        losses, rows = notes["check_losses"], notes["check_rows_held"]
        return {
            "seed": seed, "correct": correct, **notes,
            "loss_gaps": [abs(a / b - 1) for a, b in zip(
                losses["system"], losses["reference"])],
            "rows_gap": max(abs(a - b) for g, w in zip(
                rows["system"], rows["reference"]) for a, b in zip(g, w)),
            "step_moves_loss": abs(
                losses["reference"][1] / losses["reference"][0] - 1)}

    return compare


def layers_comparer(cfg, model, reference):
    """seed -> one row: per-layer outputs, loss and every leaf's gradient
    of one sequence at random leaves."""
    import jax
    import jax.numpy as jnp

    length = cfg["input"][0]

    @jax.jit
    def leaves(seed_key, leaf_key):
        params, state, _ = model.init(seed_key, (length,))
        return random_leaves(params, state, leaf_key)

    hidden_of = jax.jit(lambda p, s, x: model.hidden_states(p, s, x)[0])
    grads_of = jax.jit(jax.value_and_grad(model.loss, has_aux=True))

    def compare(seed):
        x, y = _tokens(cfg, seed, 1)
        params, state = leaves(jax.random.key(seed), jax.random.key(seed + 1))
        want = reference.hidden_states(cfg["arch"], params, state, x)
        got = hidden_of(params, state, x)
        layer_gaps = [
            float(jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
                  / jnp.linalg.norm(w.ravel())) for g, w in zip(got, want)]
        del want, got
        (loss, new), grads = grads_of(params, state, x, y)
        ref_loss, ref_grads = reference.loss_and_grads(
            cfg["arch"], params, state, x, y)
        gaps = leaf_gaps(grads, ref_grads)
        worst = max(gaps, key=gaps.get)
        return {"seed": seed, "layer_gaps": layer_gaps,
                "loss": float(loss), "reference_loss": float(ref_loss),
                "loss_gap": abs(float(loss) / float(ref_loss) - 1),
                "worst_grad_gap": gaps[worst], "worst_leaf": worst,
                "median_grad_gap": common.median(list(gaps.values())),
                "leaves": len(gaps)}

    return compare


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=2701000000)
    ap.add_argument("--mode", choices=("init", "layers"), default="init")
    ap.add_argument("--controls", default="",
                    help="comma-separated: a dtype, " + ", ".join(FAULTS))
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    workload = common.find_workload(args.workload)
    traffic = common.find_traffic(workload["traffic"], workload["rehearsal"])
    cfg = common.find_config(workload["config"], workload["rehearsal"])
    from parallel_cnn_tpu.utils import backend

    backend.enable_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not workload["rehearsal"]:
        print(f"compare_glm_moe: needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        return 3
    model = common.build_model(cfg)
    reference = common.find_reference(cfg)
    if args.mode == "init":
        compare = init_comparer(cfg, traffic, model, reference)
    else:
        compare = layers_comparer(cfg, model, reference)
    rows = []
    for i in range(args.seeds):
        rows.append(compare(args.seed0 + 7919 * i))
        print(json.dumps(rows[-1]), flush=True)
    del compare
    passed = []
    for n, name in enumerate(c for c in args.controls.split(",") if c):
        with control(cfg, reference, name) as faulty:
            compare = init_comparer(cfg, traffic, faulty, reference)
            for i in range(args.control_seeds):
                rows.append(dict(compare(
                    args.seed0 + 1000000 * (n + 1) + 7919 * i), control=name))
                print(json.dumps(rows[-1]), flush=True)
                if rows[-1]["correct"]:
                    passed.append(name)
            del compare
    report = {"workload": args.workload, "mode": args.mode,
              "lr": train_zoo_tokens.cell_lr(cfg, traffic),
              "check": traffic["check"], "platform": platform,
              "controls_that_passed": passed, "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
