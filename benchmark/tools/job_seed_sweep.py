"""Choose a token cell's `job_seed`: the first integer from 1 for which
every expert layer of the timed job (the MTP module's too) holds between
0.9 and 1.1 of the balanced share of rows from the step before the window
opens to the window's last step. Not part of any run of a cell: what a
builder runs once, on the chip, when the cell is defined (PERF.md section
6 keeps the table).

    python3 benchmark/tools/job_seed_sweep.py --workload glm47f_train \
        [--seeds 16] [--window-epochs 3] [--write 1] \
        [--out chiprun_out/sweep.json]

For each candidate it runs the job itself, as `benchmark/runners/
train_zoo_tokens.py` does: `zoo.train` on the weights, the resident
sequences and the shuffles drawn from the candidate, at the
configuration's rate after the traffic file's `warmup_epochs`, for
`--window-epochs` epochs more (what fits a 10 s window). An epoch's
record holds the rows of its last step, so a candidate is judged on the
last step of set-up and of every epoch of the window. The ratio is rows
held over `shapes.held_rows x global_batch`. Where no candidate stays
inside, the one whose worst layer lies nearest 1 is taken. A candidate
whose layers count an overflow is out. `--write 1` puts the choice into
the traffic file of this checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, token_data  # noqa: E402
from benchmark.runners.train_zoo import optimizer_args  # noqa: E402
from benchmark.runners.train_zoo_tokens import cell_lr  # noqa: E402
from benchmark.shapes import glm_moe as shapes  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--window-epochs", type=int, default=3)
    ap.add_argument("--write", type=int, choices=(0, 1), default=0)
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
        print(f"job_seed_sweep: needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        return 3
    from parallel_cnn_tpu.train import zoo

    model = common.build_model(cfg)
    length, batch = traffic["sequence_length"], traffic["global_batch"]
    warm = traffic.get("warmup_epochs", 0)
    share = shapes.held_rows(cfg) * batch
    hyper = optimizer_args(cfg["optimizer"], cell_lr(cfg, traffic))

    class Epochs(list):
        def record(self, **rec):
            self.append(rec)

    table = []
    for seed in range(1, args.seeds + 1):
        x, y = token_data.synthetic_tokens(
            jax.random.key(seed), n=traffic["sequences"], length=length,
            vocab=cfg["arch"]["vocab_size"])
        epochs = Epochs()
        zoo.train(
            model, x, y, in_shape=(length,),
            epochs=max(warm, 1) + args.window_epochs, batch_size=batch,
            accum_steps=workload.get("accum_steps", 1), **hyper,
            warmup_steps=warm * (traffic["sequences"] // batch), seed=seed,
            verbose=False, eval_data=None, checkpoint_dir=None,
            metrics=epochs, loader=traffic["loader"])
        judged = epochs[max(warm, 1) - 1:]
        ratios = [[r / share for r in e["moe_rows_held"]] for e in judged]
        flat = [r for step in ratios for r in step]
        table.append({"job_seed": seed, "ratios": ratios, "least": min(flat),
                      "most": max(flat),
                      "off": max(abs(r - 1) for r in flat),
                      "overflow": sum(judged[-1]["moe_overflow_rows"]),
                      "losses": [e["loss"] for e in judged]})
        print(json.dumps({k: table[-1][k] for k in
                          ("job_seed", "least", "most", "off", "overflow")}),
              flush=True)
        jax.clear_caches()  # the candidate's step program leaves the chip
    table_ok = [t for t in table if not t["overflow"]] or table
    inside = [t for t in table_ok if t["off"] <= 0.1]
    chosen = (inside[0] if inside else min(table_ok, key=lambda t: t["off"]))
    report = {"workload": args.workload, "platform": platform, "share": share,
              "chosen": chosen["job_seed"], "inside": bool(inside),
              "table": table}
    print(json.dumps({k: report[k] for k in ("chosen", "inside", "share")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f)
    if args.write:
        base = common.REHEARSAL if workload["rehearsal"] else common.BENCH
        path = os.path.join(base, "traffic", f"{workload['traffic']}.json")
        data = common.load_json(path)
        data["job_seed"] = chosen["job_seed"]
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
