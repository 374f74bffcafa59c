"""Name what ran: one traced run of a training cell, then the train
step's device time by layer scope and phase, the largest device ops with
the scope the program's catalog gives each (`fusion.1923` -> `s2b1/mid/conv
bwd`), the unnamed remainder by opcode, and what the tracing cost this
run (its own end-to-end numbers, the seconds the catalog took). What
PERF.md's per-scope tables are made from. Not part of any run of a cell.

    python3 benchmark/tools/scope_table.py --workload r50_train --seed 7 \
        --seconds 10 [--top 15] [--out chiprun_out/r50_train_scopes.json]

Runs `benchmark/run.py` in this process (its result line comes first, as
always) and reads the same `run` its per-layer readers are handed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run, scope_time, trace_reduce  # noqa: E402


def report(run, top: int) -> dict:
    def ranked(d):
        return sorted(d.items(), key=lambda kv: -kv[1])

    scopes = scope_time.table(run) or {}
    rest = scope_time.split(
        run, lambda e: "named" if e.scope else f"unnamed {e.opcode}", ()) or {}
    ops = []
    for key, seconds in (trace_reduce.breakdown(run.trace, top=top) or {}).get(
            "device_ops", []):
        module, _, op = key.split(" [")[0].rpartition("/")
        entry = (scope_time.catalog_of(module) or {}).get(op) if module else None
        ops.append([key, seconds, entry.scope if entry else "",
                    entry.phase if entry else ""])
    return {"scope_ms": ranked(scopes)[:top],
            "unnamed_ms": [kv for kv in ranked(rest) if kv[0] != "named"],
            "device_ops": ops,
            # what tracing costs when on: this traced run's own end-to-end
            # numbers, to lay beside an untraced run's
            "traced_e2e": dict(run.e2e)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--out", default=None, help="also write the report here")
    args = ap.parse_args(argv)

    from parallel_cnn_tpu.train import zoo

    found = {}
    readers = bench_run.layer_metrics
    record = zoo._catalog_step

    def timed_record(*a):
        t0 = time.monotonic()
        record(*a)
        found["catalog_s"] = time.monotonic() - t0  # part of the run's set-up

    zoo._catalog_step = timed_record

    def and_report(run, wanted):
        if run.trace is not None:
            found.update(report(run, args.top))
        return readers(run, wanted)

    bench_run.layer_metrics = and_report
    rc = bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                         "--seconds", str(args.seconds), "--trace", "1"])
    if rc or not found:
        return rc or 1
    print(json.dumps(found))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
