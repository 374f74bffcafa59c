"""Traffic kind `train_zoo`: drive `train/zoo.py:train` itself — the
trainer loop, step factory and loader a CLI user gets — on a synthetic
data set resident on the device, and stop it the way an operator would.

Set-up (all counted in `setup_s`): the correctness check against the
plain reference, the data set, and epoch 1 of `zoo.train`, which traces
and compiles (or loads from the compile cache). The measured window is the
whole epochs after it: a recorder passed as `metrics=` sees the end of
every epoch, opens the window at the first, and when `--seconds` have
passed raises SIGTERM in-process, which `resilience/preempt` turns into a
stop at that epoch boundary.

Traffic parameters: global_batch, images, loader, mesh_data (null or the
CLI's --mesh-data), check {batch, lr, loss_rtol (one per step), dp_rtol},
trace_seconds.
A cell may override `accum_steps` (it depends on model x batch).
"""

from __future__ import annotations

import functools
import math
import signal
import time
from typing import Any, Dict, List, Optional

from benchmark import common, data
from benchmark.reference import resnet as reference

PROGRAM = r"^jit_step\b"  # the train step's module name in the trace


class Recorder:
    """What `zoo.train` is given as `metrics=`: it needs only `.record`.
    Opens the window after epoch 1, traces the first whole epochs of it
    when asked, and ends the run when the window has elapsed."""

    def __init__(self, seconds: float, compiles: common.CompileCounter,
                 trace_dir: Optional[str], trace_seconds: float):
        self.seconds = seconds
        self.compiles = compiles
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.epochs: List[Dict[str, Any]] = []
        self.t_open = self.t_close = None
        self.pc_open_us = self.pc_close_us = None  # obs spans' clock
        self.periods: List[float] = []  # one whole epoch each, window only
        self._t_out = 0.0
        self._tracing = False
        self._trace_from = 0.0

    def record(self, **rec) -> None:
        import jax

        t_in = time.monotonic()
        self.epochs.append(rec)
        if len(self.epochs) == 1:
            self.compiles.start()
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self._tracing = True
            self.t_open = self._trace_from = self._t_out = time.monotonic()
            self.pc_open_us = time.perf_counter_ns() / 1e3
            return
        if self.t_close is not None:
            return
        # From this recorder's last return to its next entry: everything
        # the trainer did for one epoch, and nothing the recorder did.
        self.periods.append(t_in - self._t_out)
        if self._tracing and t_in - self._trace_from >= self.trace_seconds:
            jax.profiler.stop_trace()
            self._tracing = False
        if sum(self.periods) >= self.seconds:
            if self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False
            self.t_close = t_in
            self.pc_close_us = time.perf_counter_ns() / 1e3
            self.compiles.stop()
            signal.raise_signal(signal.SIGTERM)
            return
        self._t_out = time.monotonic()

    @property
    def window_s(self) -> float:
        return sum(self.periods)


def _close(a: float, b: float, rtol: float) -> bool:
    return math.isfinite(a) and abs(a - b) <= rtol * abs(b)


def check(ctx, model, mesh, notes: Dict[str, Any]) -> bool:
    """Steps 1 and 2 of the cell's own step factory on a fixed seeded batch
    against the plain reference; on a mesh also step 1 against one chip,
    and placement on every device of the mesh."""
    import jax
    import jax.numpy as jnp
    from parallel_cnn_tpu.parallel import mesh as mesh_lib
    from parallel_cnn_tpu.train import zoo

    cfg, t = ctx.config, ctx.traffic
    chk, opt = t["check"], cfg["optimizer"]
    in_shape = tuple(cfg["input"])
    n = chk["batch"]
    hyper = dict(lr=chk["lr"], momentum=opt["momentum"],
                 weight_decay=opt["weight_decay"])
    x, y = data.synthetic_images(
        jax.random.fold_in(jax.random.key(ctx.seed), 1), n=n,
        hw=in_shape[:2], channels=in_shape[2], classes=cfg["num_classes"],
        dtype=jnp.bfloat16, chunk=n)
    optimizer = zoo.make_optimizer(**hyper)

    # One jitted program: the eager init is a small one per leaf.
    fresh = functools.partial(jax.jit(
        lambda key: zoo.init_state(model, key, in_shape, optimizer)),
        jax.random.key(ctx.seed))

    def two_steps(step_mesh, state):
        step = zoo.make_train_step(model, optimizer, 1, step_mesh)
        bx, by = (x, y) if step_mesh is None else mesh_lib.shard_batch(
            step_mesh, (x, y))
        out = []
        for _ in range(2):
            state, loss = step(state, bx, by)
            out.append(float(loss))
        return out, state, (bx, by)

    state0 = fresh()
    ref = reference.train_losses(cfg["arch"], state0.params,
                                 state0.model_state, x, y, steps=2, **hyper)
    got, state, batch = two_steps(mesh, state0)  # donates state0
    notes["check_losses"] = {"system": got, "reference": ref}
    # bf16 activations against float32, on 8 images: `loss_rtol` (one bound
    # per step) is about 2.5 times the widest gap seen on the chip over the
    # seeds tried (PERF.md section 2), and well under what the step itself
    # moves the loss by (12 %), so a missing update, a dropped BatchNorm, a
    # missing residual or an 8-bit matmul fails.
    ok = all(_close(a, b, r) for a, b, r in zip(got, ref, chk["loss_rtol"]))
    if mesh is not None:
        one, _, _ = two_steps(None, fresh())
        notes["check_losses"]["one_chip"] = one
        # Global BatchNorm statistics under GSPMD: only the order of the
        # reduction differs from one chip (6.6e-4 seen in PR 21).
        ok = ok and _close(got[0], one[0], chk["dp_rtol"])
        want = ",".join(str(d.id) for d in sorted(
            mesh.devices.flat, key=lambda d: d.id))
        placed = (common.device_ids(state), common.device_ids(batch))
        notes["check_placement"] = placed
        ok = ok and placed == (want, want)
    return ok


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from parallel_cnn_tpu import plan as plan_lib
    from parallel_cnn_tpu.resilience import preempt
    from parallel_cnn_tpu.train import zoo

    cfg, t = ctx.config, ctx.traffic
    opt = cfg["optimizer"]
    in_shape = tuple(cfg["input"])
    batch = t["global_batch"]
    steps = t["images"] // batch
    model = common.build_model(cfg)
    mesh = None
    if t.get("mesh_data"):
        # The mesh the CLI's --mesh-data N builds.
        mesh = plan_lib.ExecutionPlan(data=t["mesh_data"]).validate().make_mesh(
            devices=ctx.devices)
    notes: Dict[str, Any] = {"t_runner_s": time.monotonic() - ctx.t_process}
    correct = check(ctx, model, mesh, notes)
    notes["t_checked_s"] = time.monotonic() - ctx.t_process

    images, labels = data.synthetic_images(
        jax.random.key(ctx.seed), n=t["images"], hw=in_shape[:2],
        channels=in_shape[2], classes=cfg["num_classes"], dtype=jnp.bfloat16,
        chunk=math.gcd(t["images"], 256))
    jax.block_until_ready(images)
    notes["t_data_s"] = time.monotonic() - ctx.t_process

    compiles = common.CompileCounter()
    rec = Recorder(ctx.seconds, compiles, ctx.trace_dir,
                   t.get("trace_seconds", 3.0))
    obs = common.traced_obs() if ctx.trace else None
    hyper = dict(lr=opt["lr_per_256"] * batch / 256, momentum=opt["momentum"],
                 weight_decay=opt["weight_decay"])
    accum = ctx.workload.get("accum_steps", 1)
    t_train = time.monotonic()
    with preempt.PreemptionGuard() as guard:
        _, losses = zoo.train(
            model, images, labels, in_shape=in_shape, epochs=10**6,
            batch_size=batch, accum_steps=accum, mesh=mesh, **hyper,
            seed=ctx.seed, verbose=False, eval_data=None,
            checkpoint_dir=None, metrics=rec, loader=t["loader"], obs=obs)
    stopped = guard.preempted
    preempt.reset()

    n_epochs = len(rec.epochs) - 1
    window_s = rec.window_s
    # The median epoch, not the mean: a one-chip machine shares its host,
    # and a single epoch stalled for seconds (seen once in 16 runs, 5.3 s
    # against 1.86 s) would otherwise swing the whole run by a third. What
    # the slow epochs cost is reported beside it as `epoch_stall_pct`.
    epoch_s = common.median(rec.periods) if rec.periods else float("nan")
    finite = all(math.isfinite(v) for v in losses)
    correct = (correct and stopped and n_epochs >= 1 and finite
               and compiles.count == 0)
    if mesh is not None:
        want = ",".join(str(d.id) for d in sorted(ctx.devices, key=lambda d: d.id))
        last = rec.epochs[-1]
        correct = correct and (last["state_devices"], last["batch_devices"]) == (want, want)
    counters = {
        "epochs": n_epochs, "steps_per_epoch": steps,
        "compiles_in_window": compiles.count,
        "first_epoch_s": rec.epochs[0]["seconds"],
        "epoch_s": rec.periods,
        "epoch_stall_pct": max(0.0, 100.0 * (1 - epoch_s * n_epochs / window_s))
        if n_epochs else None,
        "losses": losses, "train_call_s": time.monotonic() - t_train,
        "batch_per_chip": batch // len(ctx.devices),
    }
    spans: Dict[str, List[float]] = {}
    if obs is not None:
        spans, warm = common.host_spans(obs, rec.pc_open_us, rec.pc_close_us)
        # Host time the warm-up epoch spent in dispatch beyond what the
        # same number of steps costs in the window: trace + compile, or
        # the load from the compile cache.
        typical = common.median(spans.get("zoo.dispatch", [0.0])) or 0.0
        counters["warmup_s"] = (sum(warm.get("zoo.dispatch", []))
                                - steps * typical)
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
