"""Traffic kind `serve_open_loop`: drive `serve/batcher.py:serve_stack` —
the batcher, replica pool and engine a `serve` CLI user gets — with an
open loop of independent requests at a rate fixed in the traffic file.

Set-up (all counted in `setup_s`): the AOT ladder (`precompile`), one
executed batch per bucket, the request payloads, and the correctness
check. The window is `--seconds` of arrivals; latencies are exact, per
request, from due time to `Future.t_done`.

The engine closes over its weights, so they are constants of every bucket
executable and part of the compile cache's key: weights drawn from
`--seed` would compile the whole ladder anew in every run. The pool's own
default seed makes them (the same in every run); `--seed` draws the
payloads and the arrivals. Passing weights as arguments is the program's
to change (PERF.md, open questions).

Traffic parameters: max_batch, max_wait_ms, queue_depth, arrivals
{rate_rps, burst?}, payloads, check {groups, logit_rtol}, trace_seconds.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import common, data, loadgen
from benchmark.reference import resnet as reference

PROGRAM = r"^jit_predict\b"  # the bucket executables' module name


def _counts(stats) -> Dict[str, int]:
    return {k: getattr(stats, k) for k in (
        "submitted", "completed", "shed", "expired", "failed", "batches",
        "requests_in_batches", "padded_slots")}


def check(ctx, batcher, made, payloads, notes) -> bool:
    """Logits of seeded requests sent through the batcher, in groups whose
    sizes are no power of two so that padded buckets are covered, against
    the reference forward."""
    chk = ctx.traffic["check"]
    n = sum(chk["groups"])
    before = batcher.stats.padded_slots
    got = np.zeros((n, ctx.config["num_classes"]), np.float32)
    at = 0
    for size in chk["groups"]:
        futs = [(i, batcher.submit(payloads[i])) for i in range(at, at + size)]
        # The first group is staged before the worker starts, so it forms
        # one batch whatever the machine's timing.
        batcher.start()
        for i, f in futs:
            got[i] = f.result(timeout=120)
        at += size
    want = np.asarray(reference.eval_logits(
        ctx.config["arch"], made["params"], made["state"], payloads[:n]))
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    notes["check_logit_err"] = err
    notes["check_padded_slots"] = batcher.stats.padded_slots - before
    # The engine computes float32 requests at the TPU's default matmul
    # precision (bf16 passes); measured error is in PERF.md. Arg-max would
    # flip on rounding with random weights, so logits are compared.
    return err <= chk["logit_rtol"] and notes["check_padded_slots"] > 0


def build(ctx, obs=None):
    """The program's own stack over this configuration: (pool, batcher,
    the weights the pool made, ladder + warm-up seconds). Paused."""
    from parallel_cnn_tpu.config import ServeConfig
    from parallel_cnn_tpu.serve import batcher as batcher_lib
    from parallel_cnn_tpu.serve import registry

    cfg, t = ctx.config, ctx.traffic
    in_shape = tuple(cfg["input"])
    model = common.build_model(cfg)
    made: Dict[str, Any] = {}

    def init(key):
        params, state, _ = model.init(key, in_shape)
        made.update(params=params, state=state)
        return params, state

    def forward(params, state, x):
        return model.apply(params, state, x, train=False)[0]

    handle = registry.ModelHandle(cfg["name"], in_shape, cfg["num_classes"],
                                  init, forward)
    scfg = ServeConfig(
        model=cfg["name"], max_batch=t["max_batch"],
        max_wait_ms=t["max_wait_ms"], queue_depth=t["queue_depth"],
        n_replicas=1, deadline_ms=0.0, precompile=True, admission=False)
    t_ladder = time.monotonic()
    pool, batcher = batcher_lib.serve_stack(
        handle, scfg, devices=ctx.devices[:1], obs=obs, start=False)
    for b in pool.engines[0].buckets:
        pool.predict(np.zeros((b, *in_shape), np.float32), replica=0)
    return pool, batcher, made, time.monotonic() - t_ladder


def window(ctx, batcher, payloads, arrivals, seconds, tick=None):
    """One open-loop window; exact latencies and the batcher's own counters
    over it."""
    from parallel_cnn_tpu.serve.batcher import Overloaded

    due = loadgen.due_times(ctx.seed, arrivals, seconds)
    order = np.random.default_rng([ctx.seed, 0x0D3]).integers(
        0, len(payloads), size=len(due))
    before = _counts(batcher.stats)
    t0, sent = loadgen.run_open_loop(
        batcher.submit, payloads, due, order, refused=(Overloaded,), tick=tick)
    got = loadgen.collect(sent, timeout_s=60.0)
    after = _counts(batcher.stats)
    lat = got["latency_s"]
    done = [r.future.t_done for r in sent
            if r.future is not None and r.future.t_done is not None]
    out: Dict[str, Any] = {k: after[k] - before[k] for k in after}
    out.update(
        t0=t0, attempted=len(sent), failed_requests=got["failed"],
        latency_s=lat, latency_samples=len(lat),
        samples_beyond_p99=len(lat) - int(np.ceil(0.99 * len(lat))),
        gen_late_p99_ms=1e3 * common.percentile(got["late_s"], 99),
        gen_late_max_ms=1e3 * got["late_s"][-1],
        offered_rps=len(sent) / seconds,
        # How long after the last arrival the last answer came: a backlog
        # that grew through the window shows here.
        drain_s=(max(done) - t0 - seconds) if done else None)
    return out


def run(ctx) -> Dict[str, Any]:
    import jax

    cfg, t = ctx.config, ctx.traffic
    obs = common.traced_obs() if ctx.trace else None
    notes: Dict[str, Any] = {}
    pool, batcher, made, warmup_s = build(ctx, obs)
    try:
        payloads = data.request_payloads(ctx.seed, t["payloads"],
                                         tuple(cfg["input"]))
        correct = check(ctx, batcher, made, payloads, notes)
        compiles = common.CompileCounter()
        stopper: List[threading.Thread] = []
        tick = None
        if ctx.trace_dir:
            trace_s = t.get("trace_seconds", 3.0)

            def tick(since: float) -> None:
                # Stop the trace off the generator's thread, so the
                # generator is not late for it.
                if since >= trace_s and not stopper:
                    stopper.append(threading.Thread(
                        target=jax.profiler.stop_trace, name="stop-trace"))
                    stopper[0].start()

            jax.profiler.start_trace(ctx.trace_dir)
        pc_open_us = time.perf_counter_ns() / 1e3
        compiles.start()
        win = window(ctx, batcher, payloads, t["arrivals"], ctx.seconds, tick)
        compiles.stop()
        pc_close_us = time.perf_counter_ns() / 1e3
        if ctx.trace_dir:
            if stopper:
                stopper[0].join()
            else:
                jax.profiler.stop_trace()
    finally:
        batcher.close()
    final = _counts(batcher.stats)
    conserved = final["submitted"] == (
        final["completed"] + final["shed"] + final["expired"] + final["failed"])
    lat = win.pop("latency_s")
    correct = (correct and conserved and compiles.count == 0 and len(lat) > 0
               and len(lat) + win["failed_requests"] == win["attempted"])
    counters: Dict[str, Any] = dict(win)
    counters.update(
        warmup_s=warmup_s, compiles_in_window=compiles.count,
        ladder_compile_s=sum(pool.engines[0].stats.compile_seconds.values()),
        queue_depth_max=batcher.stats.queue_depth_max)
    e2e = {f"serve_p{p}_ms": 1e3 * common.percentile(lat, p)
           for p in (50, 95, 99)} if lat else {}
    spans = (common.host_spans(obs, pc_open_us, pc_close_us)[0]
             if obs is not None else {})
    trace = common.read_trace(ctx.trace_dir, notes)
    return {
        "correct": bool(correct), "attempted": win["attempted"],
        "failed": win["failed_requests"], "e2e": e2e,
        "window_start": win["t0"], "window_s": ctx.seconds,
        "counters": counters, "spans": spans, "trace": trace,
        "program": PROGRAM, "notes": notes,
    }
