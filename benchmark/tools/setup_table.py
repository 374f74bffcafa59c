"""Where set-up goes: one traced run of a training cell, then its set-up by
phase (the `zoo.*` spans of `cat="setup"` and epoch 1's loop spans, from
the tracer the run hands the program), by kind of work (the six `setup_*`
metrics and what of `setup_s` they leave), and the functions that cost the
most seconds in the program's compile log (`fun_name`, requests, trace /
lower / compile / load seconds, hits, misses, where they happened). What
PERF.md's set-up table is made from. Not part of any run of a cell.

    python3 benchmark/tools/setup_table.py --workload r18_train --seed 7 \
        --seconds 10 [--top 20] [--out chiprun_out/r18_train_setup.json]

Runs `benchmark/run.py` in this process (its result line comes first, as
always) and reads the same `run` its per-layer readers are handed. The
per-phase view lives here until a `benchmark` PR hands the readers the
warm-up spans that `common.host_spans` already returns and the runners
drop.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, run as bench_run, setup_time  # noqa: E402

METRICS = ("setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s",
           "setup_programs", "setup_cache_misses", "setup_step_s", "warmup_s")


def phases(obs, t_process: float, setup_s: float) -> dict:
    """Seconds of the program's own spans that began before the window,
    by name: the `zoo.*` set-up spans and epoch 1's loop spans."""
    hi_us = (t_process + setup_s) * 1e6
    out: dict = {}
    for ev in obs.tracer.events():
        if ev.get("ph") == "X" and ev["ts"] <= hi_us:
            out[ev["name"]] = out.get(ev["name"], 0.0) + ev["dur"] / 1e6
    return dict(sorted(out.items()))


def functions(records, top: int) -> list:
    """The `top` programs by own seconds over all kinds: [program,
    requests, trace_s, lower_s, compile_s, load_s, hits, misses, within]."""
    rows: dict = {}
    for r in records:
        row = rows.setdefault(setup_time.module_of(r), {
            "requests": 0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "load_s": 0.0, "hits": 0, "misses": 0, "within": set()})
        row[f"{r.kind}_s"] += r.seconds
        if r.kind == "compile":
            row["requests"] += 1
            row["load_s"] += r.load_s or 0.0
            row["hits"] += r.cache == "hit"
            row["misses"] += r.cache == "miss"
        row["within"].add(r.within or "-")
    ranked = sorted(rows.items(), key=lambda kv: -(
        kv[1]["trace_s"] + kv[1]["lower_s"] + kv[1]["compile_s"]))
    return [[name, row["requests"], row["trace_s"], row["lower_s"],
             row["compile_s"], row["load_s"], row["hits"], row["misses"],
             sorted(row["within"])] for name, row in ranked[:top]]


def report(run, obs, top: int) -> dict:
    from parallel_cnn_tpu.obs import compiles

    setup_s = run.e2e["setup_s"]
    records = setup_time.records(run) or []
    metrics = {}
    for name in METRICS:
        value = importlib.import_module(
            f"benchmark.layer_metrics.{name}").read(run)
        if value is not None:
            metrics[name] = float(value)
    explained = sum(metrics.get(k, 0.0) for k in METRICS[:3])
    catalog = [r for r in compiles.records() if r.within == setup_time.CATALOG]
    return {
        "setup_s": setup_s, "metrics": metrics,
        # imports, the float32 reference, data, the warm-up steps' execution
        "remainder_s": setup_s - explained,
        "phases_s": phases(obs, run.ctx.t_process, setup_s) if obs else {},
        "functions": functions(records, top),
        # the tracing's own: left out of the metrics, paid by traced runs only
        "catalog_s": sum(r.seconds for r in catalog),
        # 0 where the loop asked for every program of its step before the
        # catalog did (zoo.train catalogs when epoch 1's steps are out)
        "catalog_requests": sum(r.kind == "compile" for r in catalog),
        "records_kept": len(compiles.records()),
        "records_in_setup": len(records),
        "traced_e2e": dict(run.e2e),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--out", default=None, help="also write the report here")
    args = ap.parse_args(argv)

    found, made = {}, []
    readers, traced_obs = bench_run.layer_metrics, common.traced_obs

    def kept_obs():
        made.append(traced_obs())
        return made[-1]

    def and_report(run, wanted):
        found.update(report(run, made[-1] if made else None, args.top))
        return readers(run, wanted)

    common.traced_obs = kept_obs
    bench_run.layer_metrics = and_report
    try:
        rc = bench_run.main(["--workload", args.workload, "--seed", str(args.seed),
                             "--seconds", str(args.seconds), "--trace", "1"])
    finally:
        common.traced_obs, bench_run.layer_metrics = traced_obs, readers
    if rc:
        return rc
    if not made or not found:
        # `run.py` no longer looks these two names up where this tool put
        # its own: say so, rather than print a table of nothing.
        print("setup_table: benchmark/run.py did not call "
              f"{'common.traced_obs' if not made else 'run.layer_metrics'} "
              "through its module; this tool has to follow it",
              file=sys.stderr)
        return 1
    print(json.dumps(found))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
