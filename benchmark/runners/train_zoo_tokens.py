"""Traffic kind `train_zoo_tokens`: `train_zoo`'s run for a language model
— the same `train/zoo.py:train`, `Recorder`, stop by SIGTERM, result keys
and counters (imported from it, so the readers the benchmark has read this
kind unedited) — on token sequences resident on the device, with the
differences that a model whose WORK depends on its values needs.

The timed job is the same job for every `--seed`. Which tokens go to the
experts this chip holds is decided by the weights and the tokens, and the
rows the grouped matmul multiplies follow (ledger, PR 31: two sets of runs
of the same code spread by more than the bound, because each seed routed
another share of its tokens here). So the traffic file carries `job_seed`:
the timed job's initial weights, its resident sequences and its shuffles
are drawn from it. `--seed` draws the correctness check's weights and
batch, as in every cell.

The check runs first and is freed before the job's state is built (the
two do not fit one chip together): steps 1 and 2 of the cell's own step
factory, under the configuration's optimizer at the cell's own rate, on
`check.batch` sequences of the timed length against the plain reference
— the losses (`loss_rtol`, one a step: step 1 holds the three terms and
their weights, step 2 the arithmetic, the gradients and the update) and
each expert layer's count of rows held (`rows_tol`, the same for every
layer and step). The reference is given the initial parameters alone and
goes first; the system's optimizer state is built after it.

The job is warmed up inside set-up. From the initialisation AdamW's
first steps at the full rate move every weight by the rate itself, and
the rows the layers hold swing widely for a dozen steps (0.4 to 1.7 of
the share at 2e-4; PERF.md section 6, PR 32), which is what a warm-up is
for: over the first `warmup_epochs` epochs the rate rises linearly from
0 (`zoo.train(warmup_steps=...)`), and the window opens after them, at
the configuration's constant rate.

In the window: every epoch's loss finite, no compile, and no expert layer
ever counted a row its buffer could not take (`moe_overflow_rows`).

Traffic parameters: sequence_length, global_batch (sequences a step),
sequences (resident), loader, job_seed, warmup_epochs, check {batch,
loss_rtol, rows_tol}, trace_seconds. A cell may override `accum_steps`.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, Dict

from benchmark import common, token_data
from benchmark.runners import train_zoo
from benchmark.runners.train_zoo import PROGRAM, Recorder, optimizer_args


def cell_lr(cfg, traffic) -> float:
    return cfg["optimizer"]["lr_per_256"] * traffic["global_batch"] / 256


def checker(cfg, traffic, model, reference) -> Callable[[int, Dict], bool]:
    """`(seed, notes) -> correct`: the cell's check (module docstring) on
    the weights and the batch drawn from `seed`. Its programs are made
    once, so `benchmark/tools/compare_glm_moe.py` holds many seeds, a
    reference computed one precision lower and planted faults against the
    very comparison a run of the cell makes."""
    import jax
    from parallel_cnn_tpu.train import zoo

    chk, length = traffic["check"], traffic["sequence_length"]
    hyper = optimizer_args(cfg["optimizer"], cell_lr(cfg, traffic))
    fresh = jax.jit(lambda key: model.init(key, (length,))[:2])
    optimizer = zoo.make_optimizer(**hyper)
    moments = jax.jit(optimizer.init)
    step = zoo.make_train_step(model, optimizer, 1, None)

    def check(seed: int, notes: Dict[str, Any]) -> bool:
        x, y = token_data.synthetic_tokens(
            jax.random.fold_in(jax.random.key(seed), 1), n=chk["batch"],
            length=length, vocab=cfg["arch"]["vocab_size"])
        params, model_state = fresh(jax.random.key(seed))
        ref = reference.train_report(
            cfg["arch"], params, model_state, x, y, steps=2, **hyper)
        state = zoo.ZooState(params, model_state, moments(params))
        del params, model_state
        losses, rows = [], []
        for _ in range(2):
            state, loss = step(state, x, y)  # donates the state it is given
            losses.append(float(loss))
            rows.append(model.counters(state.model_state))
        del state
        held = [r["moe_rows_held"] for r in rows]
        notes["check_losses"] = {"system": losses, "reference": ref["losses"]}
        notes["check_rows_held"] = {"system": held,
                                    "reference": ref["rows_held"]}
        notes["check_overflow_rows"] = rows[-1]["moe_overflow_rows"]
        close = all(train_zoo._close(a, b, r) for a, b, r in zip(
            losses, ref["losses"], chk["loss_rtol"], strict=True))
        same_rows = all(
            abs(a - b) <= chk["rows_tol"]
            for got, want in zip(held, ref["rows_held"], strict=True)
            for a, b in zip(got, want, strict=True))
        return close and same_rows and not any(notes["check_overflow_rows"])

    return check


class WarmedRecorder(Recorder):
    """`Recorder` behind `skip` epochs that are set-up like the first: the
    window opens after epoch `skip + 1`."""

    def __init__(self, skip: int, *args):
        super().__init__(*args)
        self.skipped = []
        self._skip = skip

    def record(self, **rec) -> None:
        if len(self.skipped) < self._skip:
            self.skipped.append(rec)
        else:
            super().record(**rec)


def run(ctx) -> Dict[str, Any]:
    import jax
    from parallel_cnn_tpu.resilience import preempt
    from parallel_cnn_tpu.train import zoo

    cfg, t = ctx.config, ctx.traffic
    length, batch = t["sequence_length"], t["global_batch"]
    steps = t["sequences"] // batch
    model = common.build_model(cfg)
    notes: Dict[str, Any] = {"t_runner_s": time.monotonic() - ctx.t_process}
    correct = checker(cfg, t, model, common.find_reference(cfg))(
        ctx.seed, notes)
    notes["t_checked_s"] = time.monotonic() - ctx.t_process

    job = t["job_seed"]
    dataset = list(token_data.synthetic_tokens(
        jax.random.key(job), n=t["sequences"], length=length,
        vocab=cfg["arch"]["vocab_size"]))
    jax.block_until_ready(dataset)
    notes["t_data_s"] = time.monotonic() - ctx.t_process

    compiles = common.CompileCounter()
    warm = t.get("warmup_epochs", 0)
    rec = WarmedRecorder(max(warm - 1, 0), ctx.seconds, compiles,
                         ctx.trace_dir, t.get("trace_seconds", 3.0))
    obs = common.traced_obs() if ctx.trace else None
    hyper = optimizer_args(cfg["optimizer"], cell_lr(cfg, t))
    t_train = time.monotonic()
    with preempt.PreemptionGuard() as guard:
        _, losses = zoo.train(
            model, dataset.pop(0), dataset.pop(0), in_shape=(length,),
            epochs=10**6, batch_size=batch,
            accum_steps=ctx.workload.get("accum_steps", 1), **hyper,
            warmup_steps=warm * steps, seed=job, verbose=False,
            eval_data=None, checkpoint_dir=None, metrics=rec, loader=t["loader"], obs=obs)
    stopped = guard.preempted
    preempt.reset()

    n_epochs = len(rec.epochs) - 1
    window_s = rec.window_s
    epoch_s = common.median(rec.periods) if rec.periods else float("nan")
    finite = all(math.isfinite(v) for v in losses)
    overflow = sum(rec.epochs[-1]["moe_overflow_rows"])  # counted since init
    correct = (correct and stopped and n_epochs >= 1 and finite
               and compiles.count == 0 and overflow == 0)
    counters = {
        "epochs": n_epochs, "steps_per_epoch": steps,
        "compiles_in_window": compiles.count,
        "first_epoch_s": (rec.skipped or rec.epochs)[0]["seconds"],
        "epoch_s": rec.periods,
        "epoch_stall_pct": max(0.0, 100.0 * (1 - epoch_s * n_epochs / window_s))
        if n_epochs else None,
        "losses": losses, "train_call_s": time.monotonic() - t_train,
        "batch_per_chip": batch // len(ctx.devices),
        # the layers' own counters, a list an epoch (set-up's last epoch
        # first), one value a layer
        "moe_rows_held": [e["moe_rows_held"] for e in rec.epochs],
        "moe_load_max_over_mean": [e["moe_load_max_over_mean"]
                                   for e in rec.epochs],
        "moe_overflow_rows": rec.epochs[-1]["moe_overflow_rows"],
    }
    spans: Dict[str, Any] = {}
    if obs is not None:
        spans, before = common.host_spans(obs, rec.pc_open_us, rec.pc_close_us)
        typical = common.median(spans.get("zoo.dispatch", [0.0])) or 0.0
        counters["warmup_s"] = (sum(before.get("zoo.dispatch", []))
                                - max(warm, 1) * steps * typical)
    trace = common.read_trace(ctx.trace_dir, notes)
    return {
        "correct": bool(correct),
        "attempted": n_epochs * steps,
        "failed": 0 if finite else n_epochs * steps,
        "e2e": {"train_img_s_chip":
                steps * batch / epoch_s / len(ctx.devices)},
        "window_start": rec.t_open, "window_s": window_s,
        "counters": counters, "spans": spans, "trace": trace,
        "program": PROGRAM, "notes": notes,
    }
