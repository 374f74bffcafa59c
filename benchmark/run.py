"""Run one cell once and print its result as one JSON object on the last
line of standard output.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: place the compile cache, find the cell's files by
name (benchmark/workloads, benchmark/traffic, benchmark/configs,
benchmark/runners, benchmark/layer_metrics), refuse a machine that is not
the one the cell asks for, hand over to the traffic kind's runner, and
report the cell's end-to-end metrics (`--trace 0`) or its per-layer metrics
(`--trace 1`) as BENCHMARK.json lists them.

Only cells kept with the tests (tests/benchmark/cells, tiny shapes, never
in BENCHMARK.json) may run without a TPU; their line says `platform: cpu`.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gzip  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common, trace_reduce  # noqa: E402


def refuse(why: str) -> int:
    print(f"benchmark/run.py: {why}", file=sys.stderr)
    return 3


def layer_metrics(run, wanted) -> dict:
    """Each per-layer metric is a reader of its own, found by name; one
    that finds nothing to read returns None and is left out."""
    out = {}
    for m in wanted:
        reader = importlib.import_module(f"benchmark.layer_metrics.{m['name']}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1, also keep the .xplane.pb here, gzipped")
    ap.add_argument("--notes", type=int, choices=(0, 1), default=0,
                    help="1: print the runner's counters to stderr")
    args = ap.parse_args(argv)

    man = common.manifest()
    workload = common.find_workload(args.workload)
    rehearsal = workload["rehearsal"]
    traffic = common.find_traffic(workload["traffic"], rehearsal)
    config = common.find_config(workload["config"], rehearsal)
    peaks = common.load_json(os.path.join(common.BENCH, "peaks.json"))
    # The program is imported only now: a directory that holds the
    # benchmark alone fails here, before any result.
    from parallel_cnn_tpu.utils import backend

    backend.enable_compile_cache()
    import jax

    # Every run is a new process: persist the small programs too (eager
    # init, gather, slices), or each run compiles them again.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        return refuse(f"cell {args.workload!r} needs a TPU; JAX found "
                      f"{platform!r}")
    if len(devices) < workload["chips"]:
        return refuse(f"cell {args.workload!r} needs {workload['chips']} "
                      f"chips; JAX found {len(devices)}")
    peak = peaks.get(devices[0].device_kind)
    if peak is None and not rehearsal:
        return refuse(f"no published peak for device_kind "
                      f"{devices[0].device_kind!r} in benchmark/peaks.json")
    devices = devices[:workload["chips"]]

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    ctx = types.SimpleNamespace(
        t_process=T_PROCESS,
        workload=workload, traffic=traffic, config=config, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), trace_dir=trace_dir,
        devices=devices, peak=peak)
    try:
        runner = importlib.import_module(f"benchmark.runners.{traffic['runner']}")
        res = runner.run(ctx)
        if args.keep_trace and res["notes"].get("xplane"):
            os.makedirs(args.keep_trace, exist_ok=True)
            with open(res["notes"]["xplane"], "rb") as src, gzip.open(
                    os.path.join(args.keep_trace,
                                 f"{args.workload}.xplane.pb.gz"), "wb") as dst:
                shutil.copyfileobj(src, dst)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    cell = workload.get("metrics_of", args.workload)
    res["e2e"]["setup_s"] = res["window_start"] - T_PROCESS
    device = common.device_report(devices)
    res["notes"]["memory_stats"] = devices[0].memory_stats()
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if not args.trace:
        line["metrics"] = {
            m["name"]: {"value": float(res["e2e"][m["name"]]), "unit": m["unit"]}
            for m in common.cell_metrics(man, cell, "end_to_end")
            if m["name"] in res["e2e"]}
    else:
        trace = res["trace"]
        run = types.SimpleNamespace(
            trace=trace, spans=res["spans"], counters=res["counters"],
            e2e=res["e2e"], window_s=res["window_s"], program=res["program"],
            device=device, ctx=ctx)
        line["metrics"] = layer_metrics(
            run, common.cell_metrics(man, cell, "per_layer"))
        summary = trace_reduce.device_summary(trace) if trace else None
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = trace_reduce.breakdown(trace)
    line["device"] = device
    if args.notes:
        print(json.dumps({"counters": res["counters"], "notes": res["notes"],
                          "e2e": res["e2e"]}, default=str), file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
