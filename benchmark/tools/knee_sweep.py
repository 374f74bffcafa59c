"""Find a serving cell's knee, once, when the cell is defined: one stack,
one open-loop window per rate, and for each the counts, the tails, the
deepest queue and how long the backlog took to drain. The highest rate
with no shed request and no backlog left at the end is the knee; the cell
then runs at about four fifths of it (the rate goes into the traffic file
as a number). Not part of any run of a cell.

    python3 benchmark/tools/knee_sweep.py --workload r50_serve_steady \
        --rates 500,1000,1500 --seconds 6 [--max-batch 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, data  # noqa: E402
from benchmark.runners import serve_open_loop  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=None)
    args = ap.parse_args(argv)
    from parallel_cnn_tpu.utils import backend

    backend.enable_compile_cache()
    import jax

    w = common.find_workload(args.workload)
    t = common.find_traffic(w["traffic"], w["rehearsal"])
    if args.max_batch:
        t["max_batch"] = args.max_batch
    cfg = common.find_config(w["config"], w["rehearsal"])
    ctx = types.SimpleNamespace(workload=w, traffic=t, config=cfg,
                                seed=args.seed, devices=jax.devices()[:1])
    pool, batcher, _, warmup_s = serve_open_loop.build(ctx)
    batcher.start()
    payloads = data.request_payloads(args.seed, t["payloads"], tuple(cfg["input"]))
    print(json.dumps({"warmup_s": warmup_s, "max_batch": t["max_batch"],
                      "device": common.device_report(ctx.devices)}), flush=True)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            batcher.stats.queue_depth_max = 0
            win = serve_open_loop.window(ctx, batcher, payloads,
                                         {"rate_rps": rate}, args.seconds)
            lat = win.pop("latency_s")
            row = {"rate_rps": rate, **{k: win[k] for k in (
                "attempted", "completed", "shed", "failed_requests", "batches",
                "requests_in_batches", "padded_slots", "drain_s",
                "gen_late_p99_ms")},
                "queue_depth_max": batcher.stats.queue_depth_max}
            if lat:
                row.update({f"p{p}_ms": 1e3 * common.percentile(lat, p)
                            for p in (50, 95, 99)})
            print(json.dumps(row), flush=True)
    finally:
        batcher.close()
    print(json.dumps({"device": common.device_report(ctx.devices)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
