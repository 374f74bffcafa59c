"""Hold an `afmoe` configuration's model, as the program builds, masks,
routes and differentiates it, against its plain float32 reference
(benchmark/reference/afmoe.py) at the published widths and the timed
sequence length, outside any timed window: `compare_glm_moe.py`'s sibling
for the family whose layers differ by kind, by that file's two comparers.
Not part of any run of a cell: it is what a builder runs on the chip to
read the bounds a traffic file's `check` is then given (PERF.md section 6).

    python3 benchmark/tools/compare_afmoe.py --workload trinity_mini_train \
        --seeds 16 [--seed0 2701000000] [--seed-list 4101000021,4117000003] \
        [--mode init|layers] [--controls float8_e4m3fn,window_as_causal,\
rope_in_full,gate_off,post_norms_off,shared_dropped,absent_gates,\
embed_scale_off] [--control-seeds 1] [--out chiprun_out/cmp.json]

`--mode init` (default): the cell's own check, a row a seed — it IS
`benchmark/runners/train_zoo_tokens_grad.py:checker`, with the cell's bounds.
The seeds are `--seeds` from `--seed0` in steps of 7,919 and then every
seed of `--seed-list` (the driver's are ten digits long). `--controls` go
through the same comparison after the seeds, in the same process, on
seeds of their own, and each has to read `correct: false` (the tool exits
1 where one reads true):

    float8_e4m3fn     (any dtype) the reference with every matmul's
                      operands rounded through it, one precision below the
                      bf16 the configuration trains in
    window_as_causal  the sliding layers see every key up to the query's
                      own (the full layers' schedule of the same kernels)
    rope_in_full      RoPE applied in the full layers too (at the
                      initialisation the gradient's direction alone
                      refuses it)
    gate_off          the sigmoid gate on the attention's output dropped
    post_norms_off    the second norm of each sub-layer dropped
    shared_dropped    the shared expert dropped
    absent_gates      the gates renormalised over the chosen experts this
                      chip HOLDS, the 112 absent ones' not left in the sum
    embed_scale_off   the embedding's sqrt(hidden) left out

The faults are planted in the system. `--mode layers`: every parameter
leaf drawn at random; per seed, one sequence: the residual after every
layer, the loss, every leaf's gradient (`compare_glm_moe.layers_comparer`).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common  # noqa: E402
from benchmark.runners import train_zoo_tokens, train_zoo_tokens_grad  # noqa: E402
from benchmark.tools import compare_glm_moe  # noqa: E402

FAULTS = ("window_as_causal", "rope_in_full", "gate_off", "post_norms_off",
          "shared_dropped", "absent_gates", "embed_scale_off")


@contextlib.contextmanager
def control(cfg, reference, name):
    """The configuration's model with the fault `name` planted, or (a
    dtype's name) the clean model against a reference rounded through
    it; everything is put back on the way out."""
    import jax.numpy as jnp
    from benchmark.reference import glm_moe as rounded
    from parallel_cnn_tpu.nn import afmoe, glm_moe, layers

    expert = glm_moe.ExpertLayer
    saved = (afmoe.AfMoe.attention, afmoe._gated, afmoe.SandwichLayer._post,
             expert._shared, expert.route)
    try:
        if name == "window_as_causal":
            afmoe.AfMoe.attention = lambda self, kind: (
                dataclasses.replace(self.attn, window=None) if kind == afmoe.SLIDING
                else saved[0](self, kind))
        elif name == "rope_in_full":
            afmoe.AfMoe.attention = lambda self, kind: dataclasses.replace(
                saved[0](self, kind), rotary=True)
        elif name == "gate_off":
            afmoe._gated = lambda out, gate: out
        elif name == "post_norms_off":
            afmoe.SandwichLayer._post = lambda self, gain, y: y
        elif name == "shared_dropped":
            class Nothing(layers.GatedMLP):
                def apply(self, params, state, x, train=False):
                    return x * 0, state

            expert._shared = lambda self: Nothing(self.width)
        elif name == "absent_gates":
            route = expert.route

            def over_the_held(self, router, bias, xt, n):
                ids, gates, load, balance = route(self, router, bias, xt, n)
                here = jnp.isin(ids, jnp.asarray(self.held))
                total = jnp.sum(jnp.where(here, gates, 0), axis=1, keepdims=True)
                return (ids, self.scaling * gates / jnp.maximum(total, 1e-9),
                        load, balance)

            expert.route = over_the_held
        elif name == "embed_scale_off":
            fac = cfg["factory"]
            cfg = dict(cfg, factory=dict(
                fac, kwargs=dict(fac["kwargs"], mup_enabled=False)))
        else:
            rounded.ROUND = jnp.dtype(name)
            reference._programs.cache_clear()
        yield common.build_model(cfg)
    finally:
        (afmoe.AfMoe.attention, afmoe._gated, afmoe.SandwichLayer._post,
         expert._shared, expert.route) = saved
        if rounded.ROUND is not None:
            rounded.ROUND = None
            reference._programs.cache_clear()


def init_comparer(cfg, traffic, model, reference):
    """`compare_glm_moe.init_comparer` over this family's own check
    (`train_zoo_tokens_grad.checker`: the gradient's direction beside the
    losses and the rows)."""
    with train_zoo_tokens_grad.in_place_of_theirs():
        return compare_glm_moe.init_comparer(cfg, traffic, model, reference)


def main(argv=None) -> int:
    """`compare_glm_moe.main`'s loop — seeds, controls on seeds of their
    own, report, exit code — with this family's faults and `control`, and
    seeds that may be listed."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--seed0", type=int, default=2701000000)
    ap.add_argument("--seed-list", default="",
                    help="comma-separated seeds, after the counted ones")
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
        print(f"compare_afmoe: needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        return 3
    model = common.build_model(cfg)
    reference = common.find_reference(cfg)
    if args.mode == "init":
        compare = init_comparer(cfg, traffic, model, reference)
    else:
        compare = compare_glm_moe.layers_comparer(cfg, model, reference)
    seeds = [args.seed0 + 7919 * i for i in range(args.seeds)] + [
        int(s) for s in args.seed_list.split(",") if s]
    rows = []

    def report():
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({"workload": args.workload, "mode": args.mode,
                           "lr": train_zoo_tokens.cell_lr(cfg, traffic),
                           "check": traffic["check"], "platform": platform,
                           "controls_that_passed": passed, "rows": rows}, f)

    passed = []
    for seed in seeds:
        rows.append(compare(seed))
        print(json.dumps(rows[-1]), flush=True)
        report()
    del compare
    for n, name in enumerate(c for c in args.controls.split(",") if c):
        with control(cfg, reference, name) as faulty:
            compare = init_comparer(cfg, traffic, faulty, reference)
            for i in range(args.control_seeds):
                rows.append(dict(compare(
                    args.seed0 + 1000000 * (n + 1) + 7919 * i), control=name))
                print(json.dumps(rows[-1]), flush=True)
                if rows[-1]["correct"]:
                    passed.append(name)
                report()
            del compare
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
