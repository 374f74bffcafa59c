#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # on a machine with a TPU; exit 0 = pass

One process. It imports JAX once, starts no child that needs the chip, and
drives the NORMAL entry points (`parallel_cnn_tpu.cli.main(argv)` — the same
code as `python -m parallel_cnn_tpu …`) at the full width of ResNet-50 as the
CLI builds it (cifar_stem, 10 classes, 32×32; depth uncut, weights random
from seed 0):

  train    zoo trainer, ResNet-50, 8 steps + eval + checkpoint
  serve    the serving tier on that checkpoint: AOT bucket ladder, padded-
           bucket parity probe, 64 requests
  lenet    the reference-contract LeNet trainer, batch 2048, 2 epochs
  kernels  one compiled call per Pallas family at a shape those models use,
           each compared ON CHIP with its XLA twin; plus the CLI switches
           that select them (--ops pallas, --fused-step)
  dp       (>= 4 devices) the train leg over --mesh-data 4: GSPMD, psum,
           ring, then `serve --replicas 4`; with fewer devices it prints
           "dp leg: not run, N device(s)" — the only permitted non-run

Contract: exits non-zero, and prints no result line, unless
`jax.devices()[0].platform == "tpu"` and every leg passed. The last line of
stdout on success is one JSON object

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Any failed check, exception or missing
leg makes the exit code non-zero; a leg's failure is recorded and the next
leg still runs (one run shows every failure), but nothing can turn a failed
leg green. No number printed here is a benchmark: leg times are wall time
with compilation included — set-up time, shown so a warm compile cache can
be told from a cold one.

`--rehearse-cpu` is the on-chip-measurement guide's "run the same command
tiny with JAX_PLATFORMS=cpu first": tiny sizes, Pallas in interpret mode,
EVERY output line prefixed `[cpu-rehearsal]`, never the pass line, and exit
code 3 (not 0) when the rehearsal passed. `--legs a,b` runs a subset for
the builder's own chip calls; a partial run never prints the pass line
either.

Tolerances (stated here, asserted below):
  F32_TOL  1e-4  max|Δ|/max|ref| for f32 zoo kernels vs their XLA twins.
                 Measured ≤ 8e-7 on the v5e (PR 21); a bf16 computation
                 would land near 2e-3 and fail.
  LOW_TOL  1e-2  the same ratio for bf16 kernels (measured ≤ 5.6e-3) and,
                 as max|Δ|, for the LeNet kernels, whose dots run
                 Precision.DEFAULT by design (measured 1.3e-3).
  serve parity   0.0: the padded bucket must be bit-identical to the same-
                 bucket jit forward (same program, same shape).
  dp             see _leg_dp.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

F32_TOL = 1e-4
LOW_TOL = 1e-2
# GSPMD on four chips vs one chip, first-step loss (forward at identical
# init and batch; BatchNorm statistics are global under GSPMD, so only the
# reduction order differs): relative.
DP_GSPMD_TOL = 1e-2
# psum vs ring on the same mesh and body (only the collective's summation
# order differs): relative, on the first step — later steps of an lr=0.1
# run on random data amplify rounding chaotically and are reported, not
# judged.
DP_COMM_TOL = 1e-3

ALL_LEGS = ("train", "serve", "lenet", "kernels", "dp")


class SmokeFailure(Exception):
    """A check of this script failed."""


def _check(cond: bool, msg: str) -> None:
    # Not `assert`: that is removed under -O.
    if not cond:
        raise SmokeFailure(msg)


class _Prefixed:
    """stdout wrapper that labels every line (the CPU rehearsal)."""

    def __init__(self, stream, prefix: str):
        self._s, self._p, self._bol = stream, prefix, True

    def write(self, text: str) -> int:
        for chunk in text.splitlines(True):
            if self._bol:
                self._s.write(self._p)
            self._s.write(chunk)
            self._bol = chunk.endswith("\n")
        return len(text)

    def flush(self) -> None:
        self._s.flush()

    def __getattr__(self, name):
        return getattr(self._s, name)


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def _read_jsonl(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _rel(a, b) -> float:
    """max|a-b| / max|b| over one array pair (host-side, float32)."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def _tree_rel(ta, tb) -> float:
    import jax

    return max(
        _rel(a, b)
        for a, b in zip(jax.tree_util.tree_leaves(ta),
                        jax.tree_util.tree_leaves(tb), strict=True)
    )


def _cli(argv: list) -> int:
    from parallel_cnn_tpu import cli

    _say("$ python -m parallel_cnn_tpu " + " ".join(argv))
    return cli.main(list(argv))


def _peak_bytes() -> list:
    """Per-device peak bytes; None where the backend reports no stats
    (the CPU does not; the TPU does)."""
    import jax

    return [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()
    ]


# ---------------------------------------------------------------- train


def _train(ctx, tag: str, extra: list) -> dict:
    """One zoo-trainer run through the CLI; returns its checked facts.

    `--sentinel-every 1 --trace-dir` are stated, not default: they journal
    every step's loss (one host sync per step — this is a smoke, not a
    timing), which is what "every logged loss finite" and the DP
    first-step comparison read."""
    sz = ctx["size"]
    out = os.path.join(ctx["out"], tag)
    argv = [
        "--model", "resnet50", "--epochs", "1",
        "--batch-size", str(sz["train_batch"]),
        "--synthetic-train-count", str(sz["train_count"]),
        "--synthetic-test-count", str(sz["test_count"]),
        "--checkpoint-dir", os.path.join(out, "ck"),
        "--metrics", os.path.join(out, "train.jsonl"),
        "--sentinel-every", "1", "--trace-dir", os.path.join(out, "trace"),
    ] + extra
    rc = _cli(argv)
    _check(rc == 0, f"train[{tag}] exited {rc}")
    epochs = [r for r in _read_jsonl(os.path.join(out, "train.jsonl"))
              if r.get("event") == "zoo_epoch"]
    _check(len(epochs) == 1, f"train[{tag}]: {len(epochs)} epoch records")
    steps = [r["loss"] for r in _read_jsonl(
        os.path.join(out, "trace", "zoo_journal.jsonl"))
        if r.get("kind") == "step_loss"]
    want_steps = sz["train_count"] // sz["train_batch"]
    _check(len(steps) == want_steps,
           f"train[{tag}]: {len(steps)} step losses, expected {want_steps}")
    losses = steps + [epochs[0]["loss"]]
    _check(all(_finite(v) for v in losses),
           f"train[{tag}]: non-finite loss in {losses}")
    _check(_finite(epochs[0].get("accuracy")),
           f"train[{tag}]: eval pass produced no accuracy")
    ckpt = os.path.join(out, "ck", "ckpt_1.npz")
    _check(os.path.isfile(ckpt) and os.path.getsize(ckpt) > 0,
           f"train[{tag}]: no checkpoint at {ckpt}")
    place = {k: epochs[0][k]
             for k in ("platform", "state_devices", "batch_devices")}
    _check(place["platform"] == ctx["dev"]["platform"],
           f"train[{tag}]: state on {place['platform']}")
    if tag != "train":  # only the serve legs' checkpoint is kept
        shutil.rmtree(os.path.join(out, "ck"))
    return {"step_losses": steps, "epoch_loss": epochs[0]["loss"],
            "accuracy": epochs[0]["accuracy"], "placement": place,
            "checkpoint": ckpt}


def _leg_train(ctx) -> dict:
    facts = _train(ctx, "train", [])
    _check(facts["placement"]["state_devices"] == "0"
           and facts["placement"]["batch_devices"] == "0",
           f"one-chip train leg placed work on {facts['placement']}")
    ctx["train"] = facts
    return facts


# ---------------------------------------------------------------- serve


def _serve(ctx, tag: str, extra: list, requests: int) -> dict:
    _check("train" in ctx, "serve needs the train leg's checkpoint")
    out = os.path.join(ctx["out"], f"{tag}.json")
    rc = _cli(["serve", "--model", "resnet50",
               "--checkpoint", ctx["train"]["checkpoint"],
               "--requests", str(requests), "--json", out] + extra)
    _check(rc == 0, f"serve[{tag}] exited {rc}")
    with open(out) as f:
        rep = json.load(f)
    r, t = rep["report"], rep["telemetry"]
    _check(r["completed"] == r["requests"] == requests,
           f"serve[{tag}]: {r['completed']}/{r['requests']} completed")
    _check(r["errors"] == 0 and t["failed"] == 0,
           f"serve[{tag}]: errors={r['errors']} failed={t['failed']}")
    _check(rep["parity"]["max_abs_diff"] == 0.0,
           f"serve[{tag}]: padded-bucket parity {rep['parity']}")
    _check(all(p["platform"] == ctx["dev"]["platform"]
               for p in rep["replicas"]),
           f"serve[{tag}]: replicas on {rep['replicas']}")
    return {"completed": r["completed"], "parity": rep["parity"],
            "replicas": rep["replicas"],
            "replica_batches": t["replica_batches"]}


def _leg_serve(ctx) -> dict:
    return _serve(ctx, "serve", ctx["size"]["serve_extra"],
                  ctx["size"]["serve_requests"])


# ---------------------------------------------------------------- lenet


def _lenet(ctx, tag: str, extra: list) -> list:
    sz = ctx["size"]
    out = os.path.join(ctx["out"], f"{tag}.jsonl")
    rc = _cli(["--loader", "synthetic",
               "--batch-size", str(sz["lenet_batch"]), "--epochs", "2",
               "--metrics", out] + sz["lenet_extra"] + extra)
    _check(rc == 0, f"lenet[{tag}] exited {rc}")
    errs = [r["error"] for r in _read_jsonl(out) if r.get("event") == "epoch"]
    _check(len(errs) == 2 and all(_finite(e) for e in errs),
           f"lenet[{tag}]: epoch errors {errs}")
    _check(errs[1] < errs[0], f"lenet[{tag}]: error did not fall: {errs}")
    return errs


def _leg_lenet(ctx) -> dict:
    ctx["lenet"] = _lenet(ctx, "lenet", [])
    return {"epoch_errors": ctx["lenet"]}


# -------------------------------------------------------------- kernels


def _leg_kernels(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallel_cnn_tpu.models import lenet_ref
    from parallel_cnn_tpu.ops import (
        pallas as pk,
        pallas_conv,
        pallas_tail,
        pallas_update,
        reference as ops,
    )

    sz = ctx["size"]
    rng = np.random.default_rng(0)
    facts: dict = {}
    on_chip = ctx["dev"]["platform"] == "tpu"
    _check(pk._interpret() is (not on_chip),
           "Pallas compile-vs-interpret switch disagrees with the platform")

    def arr(shape, dtype=jnp.float32, scale=1.0):
        return jnp.asarray(
            (rng.standard_normal(shape) * scale).astype(np.float32)
        ).astype(dtype)

    def judged(name, value, tol):
        facts[name] = value
        _check(_finite(value) and value <= tol,
               f"kernels: {name} = {value} exceeds {tol}")

    # -- LeNet: fused megakernel and staged per-op kernels vs path A.
    nb = sz["lenet_batch"]
    params = lenet_ref.init(jax.random.key(0))
    xs = jnp.asarray(rng.uniform(0, 1, (nb, 28, 28)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 10, (nb,)).astype(np.int32))

    @jax.jit
    def path_a(p, x, y):
        errs, grads = jax.vmap(
            ops.value_and_ref_grads, in_axes=(None, 0, 0))(p, x, y)
        return jnp.mean(errs), jax.tree_util.tree_map(
            lambda g: jnp.mean(g, 0), grads)

    def max_abs(ta, tb):
        return max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree_util.tree_leaves(ta),
                            jax.tree_util.tree_leaves(tb), strict=True)
        )

    err_a, grads_a = path_a(params, xs, ys)
    for name, fn in (("lenet_fused", pk.fused_value_and_ref_grads),
                     ("lenet_staged", pk.staged_value_and_ref_grads)):
        err_b, grads_b = jax.jit(fn)(params, xs, ys)
        judged(f"{name}_err_abs", abs(float(err_b) - float(err_a)), LOW_TOL)
        judged(f"{name}_grad_abs", max_abs(grads_b, grads_a), LOW_TOL)

    # The megakernel stores its dominant operand in bf16 when compiled, on
    # the premise that XLA's patch extraction already rounded it (see
    # fused_value_and_ref_grads). Only the chip can check that premise.
    if on_chip:
        pk._FORCE_X25_F32 = True
        try:
            _, grads_f32 = jax.jit(
                lambda p, x, y: pk.fused_value_and_ref_grads(p, x, y)
            )(params, xs, ys)
        finally:
            pk._FORCE_X25_F32 = False
        _, grads_bf = jax.jit(pk.fused_value_and_ref_grads)(params, xs, ys)
        judged("lenet_fused_bf16_store_abs", max_abs(grads_bf, grads_f32),
               F32_TOL)

    # -- Zoo conv: forward, dgrad, wgrad vs XLA's conv and its autodiff, at
    # ResNet-50 (CLI widths) layer shapes.
    cb = sz["conv_batch"]

    def xla_conv(x, w, stride):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def conv_case(name, h, cin, cout, k, stride, dtype, tol):
        x = arr((cb, h, h, cin), dtype)
        w = arr((k, k, cin, cout), dtype, 1.0 / math.sqrt(k * k * cin))

        def both(f):
            y, vjp = jax.vjp(f, x, w)
            return y, vjp

        ya, vjp_a = both(lambda x, w: pallas_conv.conv2d(x, w, stride))
        yb, vjp_b = both(lambda x, w: xla_conv(x, w, stride))
        ct = arr(yb.shape, dtype)
        (dxa, dwa), (dxb, dwb) = vjp_a(ct), vjp_b(ct)
        judged(f"conv_{name}_fwd", _rel(ya, yb), tol)
        judged(f"conv_{name}_dgrad", _rel(dxa, dxb), tol)
        judged(f"conv_{name}_wgrad", _rel(dwa, dwb), tol)

    # stage-1 mid conv; stage-2 stride-2 mid conv; stage-3 1×1 reduce;
    # the bf16 twin of the first.
    conv_case("3x3s1_c64", 32, 64, 64, 3, 1, jnp.float32, F32_TOL)
    conv_case("3x3s2_c128", 32, 128, 128, 3, 2, jnp.float32, F32_TOL)
    conv_case("1x1s1_c1024to256", 8, 1024, 256, 1, 1, jnp.float32, F32_TOL)
    conv_case("3x3s1_c64_bf16", 32, 64, 64, 3, 1, jnp.bfloat16, LOW_TOL)

    # -- Fused eval epilogue (conv·scale+shift [+res], relu) with cout-tile
    # weight streaming (cout a strict multiple of the 256-lane tile).
    def fused_case(name, h, cin, cout, k, residual, dtype, tol):
        x = arr((cb, h, h, cin), dtype)
        w = arr((k, k, cin, cout), dtype, 1.0 / math.sqrt(k * k * cin))
        scale = jnp.asarray(rng.uniform(0.5, 1.5, (cout,)).astype(np.float32))
        shift = arr((cout,))
        res = arr((cb, h, h, cout), dtype) if residual else None
        ya = jax.jit(lambda x, w: pallas_conv.conv2d_fused(
            x, w, scale, shift, res, 1, True))(x, w)

        @jax.jit
        def twin(x, w):
            y = xla_conv(x, w, 1).astype(jnp.float32) * scale + shift
            if res is not None:
                y = y + res.astype(jnp.float32)
            return jnp.maximum(y, 0.0).astype(x.dtype)

        _check(cout % pallas_conv._COUT_TILE == 0
               and cout > pallas_conv._COUT_TILE,
               f"fused case {name} does not stream cout tiles")
        judged(f"fused_{name}", _rel(ya, twin(x, w)), tol)

    # stage-3 expand (1×1, residual); stage-4 mid conv (3×3).
    fused_case("1x1_c256to1024_res", 8, 256, 1024, 1, True,
               jnp.float32, F32_TOL)
    fused_case("3x3_c512", 4, 512, 512, 3, False, jnp.float32, F32_TOL)

    # -- Fused loss tail vs its XLA twin (the same custom_vjp's other arm).
    tb = sz["tail_batch"]

    def tail_case(name, pool, shape, dtype, tol):
        x = jnp.maximum(arr(shape), 0.0).astype(dtype)
        d = {"gap": shape[3],
             "max2": (shape[1] // 2) * (shape[2] // 2) * shape[3]}[pool]
        w, b = arr((d, 10), dtype, 0.02), arr((10,), dtype, 0.02)
        y = jnp.asarray(rng.integers(0, 10, (shape[0],)).astype(np.int32))

        def loss(x, w, b):
            return pallas_tail.fused_tail_loss(x, w, b, y, pool=pool)

        f = jax.value_and_grad(loss, argnums=(0, 1, 2))
        # Both arms of the same custom_vjp, on any platform: the Pallas
        # kernel, then its XLA twin (a fresh lambda per arm re-traces).
        arms = []
        use = pallas_tail._use_kernel
        try:
            for kernel in (True, False):
                pallas_tail._use_kernel = lambda kernel=kernel: kernel
                arms.append(jax.jit(lambda x, w, b: f(x, w, b))(x, w, b))
        finally:
            pallas_tail._use_kernel = use
        (lk, gk), (lx, gx) = arms
        judged(f"tail_{name}_loss", abs(float(lk) - float(lx)), tol)
        judged(f"tail_{name}_grad", _tree_rel(gk, gx), tol)

    # ResNet-50's head (GAP over 4×4×2048) f32 and bf16; cifar_cnn's
    # (maxpool 2×2 over 8×8×128).
    tail_case("gap_f32", "gap", (tb, 4, 4, 2048), jnp.float32, F32_TOL)
    tail_case("gap_bf16", "gap", (tb, 4, 4, 2048), jnp.bfloat16, LOW_TOL)
    tail_case("max2_f32", "max2", (tb, 8, 8, 128), jnp.float32, F32_TOL)
    # A batch whose largest divisor <= 128 is not a sublane multiple: the
    # (100, 10) block it used to pick is refused by the Pallas TPU lowering
    # (PR 21); the wrapper zero-pads the batch instead.
    tail_case("gap_odd_batch", "gap", (sz["tail_odd_batch"], 4, 4, 512),
              jnp.float32, F32_TOL)

    # -- Fused bucket update vs the plain formula, at the default 4 MiB
    # bucket (1M f32) and an odd length (lane padding).
    for n in (sz["update_len"], sz["update_len"] - 5):
        p, m, g = arr((n,)), arr((n,)), arr((n,))
        s = jnp.float32(0.25)
        out = jax.jit(lambda p, g, s: pallas_update.fused_sgd(
            p, g, lr=0.1, scale=s))(p, g, s)
        judged(f"update_sgd_{n}", _rel(out, p - 0.1 * (g * 0.25)), F32_TOL)
        po, mo = jax.jit(lambda p, m, g, s: pallas_update.fused_sgd_momentum(
            p, m, g, lr=0.1, momentum=0.9, scale=s))(p, m, g, s)
        m2 = 0.9 * m + g * 0.25
        judged(f"update_mom_{n}",
               max(_rel(mo, m2), _rel(po, p - 0.1 * m2)), F32_TOL)

    # -- The CLI switches that select these kernels.
    _check("lenet" in ctx, "kernels needs the lenet leg's XLA errors")
    pallas_errs = _lenet(ctx, "lenet_pallas", ["--ops", "pallas"])
    judged("cli_ops_pallas_vs_xla",
           max(abs(a - b) for a, b in zip(pallas_errs, ctx["lenet"])),
           LOW_TOL)
    fused_errs = _lenet(ctx, "lenet_fused", ["--fused-step"])
    judged("cli_lenet_fused_step_vs_unfused",
           max(abs(a - b) for a, b in zip(fused_errs, ctx["lenet"])),
           F32_TOL)
    # Zoo --fused-step = fused tail + bf16 activations + update-on-arrival;
    # the ring it rides needs a mesh, so on one chip: a 1-device ring.
    # Gentle lr: bf16 activations on random data at the default 0.1 ride
    # the loss scale's overflow edge, which is not what this leg judges.
    facts["cli_zoo_fused_step"] = _train(
        ctx, "train_fused",
        ["--mesh-data", "1", "--comm-impl", "ring", "--fused-step",
         "--lr", "0.01"],
    )["epoch_loss"]
    return facts


# ------------------------------------------------------------------- dp


def _leg_dp(ctx) -> dict:
    """Four chips: GSPMD, psum and ring over --mesh-data 4, then four
    serving replicas.

    Judged: state AND batch laid out over four distinct devices (the
    trainer's own placement record, read from sharding metadata); every
    device's peak memory moved; GSPMD's first-step loss equals the
    one-chip leg's within DP_GSPMD_TOL (its BatchNorm statistics are
    global); psum's and ring's first-step losses equal each other within
    DP_COMM_TOL (same body — shard-local BatchNorm by design, so they are
    compared with each other, not with one chip)."""
    import jax

    n = len(jax.devices())
    if n < 4:
        _say(f"dp leg: not run, {n} device(s)")
        return {"not_run": f"{n} device(s)"}
    _check("train" in ctx, "dp needs the one-chip train leg")
    before = _peak_bytes()
    arms = {
        "gspmd": _train(ctx, "dp_gspmd", ["--mesh-data", "4"]),
        "psum": _train(ctx, "dp_psum",
                       ["--mesh-data", "4", "--comm-impl", "psum"]),
        "ring": _train(ctx, "dp_ring",
                       ["--mesh-data", "4", "--comm-impl", "ring"]),
    }
    for name, f in arms.items():
        place = f["placement"]
        _check(len(set(place["state_devices"].split(","))) == 4
               and len(set(place["batch_devices"].split(","))) == 4,
               f"dp[{name}]: not laid out over four devices: {place}")
    after = _peak_bytes()
    if ctx["dev"]["platform"] == "tpu":  # the CPU reports no memory stats
        _check(all(a is not None and a > (b or 0)
                   for a, b in zip(after[1:4], before[1:4])),
               f"dp: devices 1-3 held no new memory: {before} -> {after}")

    def first_step_rel(a, b):
        return abs(a["step_losses"][0] - b["step_losses"][0]) / abs(
            b["step_losses"][0])

    facts = {
        "gspmd_vs_one_chip": first_step_rel(arms["gspmd"], ctx["train"]),
        "psum_vs_ring": first_step_rel(arms["psum"], arms["ring"]),
        "step_losses": {k: v["step_losses"] for k, v in arms.items()},
        "one_chip_step_losses": ctx["train"]["step_losses"],
        "peak_bytes": after,
    }
    _check(facts["gspmd_vs_one_chip"] <= DP_GSPMD_TOL,
           f"dp: GSPMD first-step loss off one chip's by "
           f"{facts['gspmd_vs_one_chip']:.3e} (> {DP_GSPMD_TOL})")
    _check(facts["psum_vs_ring"] <= DP_COMM_TOL,
           f"dp: psum and ring first-step losses differ by "
           f"{facts['psum_vs_ring']:.3e} (> {DP_COMM_TOL})")

    serve4 = _serve(ctx, "serve4", ["--replicas", "4", "--max-batch", "8"],
                    ctx["size"]["serve_requests"] * 2)
    ids = sorted(p["device_id"] for p in serve4["replicas"])
    _check(len(set(ids)) == 4, f"dp: replicas share devices: {ids}")
    _check(sum(1 for c in serve4["replica_batches"].values() if c) == 4,
           f"dp: not every replica served: {serve4['replica_batches']}")
    facts["serve4"] = serve4
    return facts


LEGS = {"train": _leg_train, "serve": _leg_serve, "lenet": _leg_lenet,
        "kernels": _leg_kernels, "dp": _leg_dp}

FULL = {
    "train_batch": 128, "train_count": 1024, "test_count": 256,
    "serve_requests": 64, "serve_extra": [],
    "lenet_batch": 2048, "lenet_extra": [],
    "conv_batch": 32, "tail_batch": 128, "tail_odd_batch": 200,
    "update_len": 1 << 20,
}
# The rehearsal keeps every leg and every code path; only sizes shrink.
TINY = {
    "train_batch": 8, "train_count": 16, "test_count": 8,
    "serve_requests": 8, "serve_extra": ["--max-batch", "4"],
    "lenet_batch": 64,
    "lenet_extra": ["--synthetic-train-count", "256",
                    "--synthetic-test-count", "64"],
    "conv_batch": 2, "tail_batch": 8, "tail_odd_batch": 6,
    "update_len": 1 << 12,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal: labels every line, never "
                         "prints the pass line, exits 3 when it passed")
    ap.add_argument("--legs", default=None, metavar="A,B",
                    help=f"run a subset of {','.join(ALL_LEGS)} (partial "
                         "run: never prints the pass line)")
    args = ap.parse_args(argv)
    legs = ALL_LEGS if args.legs is None else tuple(args.legs.split(","))
    unknown = [name for name in legs if name not in LEGS]
    if unknown:
        ap.error(f"unknown leg(s) {unknown}; known: {ALL_LEGS}")
    if args.rehearse_cpu:
        sys.stdout = _Prefixed(sys.stdout, "[cpu-rehearsal] ")

    t_start = time.perf_counter()
    import jax
    import jaxlib

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    _say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
         f"platform={dev['platform']} device_kind={dev['kind']} "
         f"devices={dev['count']}")
    if args.rehearse_cpu:
        if dev["platform"] != "cpu":
            print("chip_smoke: --rehearse-cpu is a CPU run; set "
                  "JAX_PLATFORMS=cpu", file=sys.stderr)
            return 2
    elif dev["platform"] != "tpu":
        print(f"chip_smoke: no accelerator — JAX found platform="
              f"{dev['platform']!r} (device_kind={dev['kind']!r}); this "
              "script only passes on a TPU. For a labeled tiny CPU run "
              "use --rehearse-cpu.", file=sys.stderr)
        return 2

    from parallel_cnn_tpu.utils.backend import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries = len(glob.glob(os.path.join(cache_dir, "*")))
    _say(f"compile cache: {cache_dir} ({entries} entries at start)")

    # Checkpoints are ~200 MB at this width: they live in a scratch dir
    # (git-ignored, inside the checkout) that is removed at exit; only the
    # small summary stays in chiprun_out/.
    os.makedirs("chiprun_out", exist_ok=True)
    out = tempfile.mkdtemp(prefix="chip_smoke_", dir="chiprun_out")
    ctx = {"out": out, "dev": dev,
           "size": TINY if args.rehearse_cpu else FULL}
    results = {}
    try:
        for name in legs:
            t0 = time.perf_counter()
            try:
                facts, ok = LEGS[name](ctx) or {}, True
            except (SmokeFailure, SystemExit) as e:
                facts, ok = {"error": f"{type(e).__name__}: {e}"}, False
            except Exception as e:  # noqa: BLE001 — recorded, fails the run
                traceback.print_exc()
                facts, ok = {"error": f"{type(e).__name__}: {e}"[:3000]}, False
            sec = time.perf_counter() - t0
            results[name] = {"ok": ok, "wall_s": round(sec, 1), **facts}
            _say(f"leg {name}: {'PASS' if ok else 'FAIL'} in {sec:.1f} s "
                 f"wall, compile included — "
                 + json.dumps(facts, default=str)[:4000])
    finally:
        shutil.rmtree(out, ignore_errors=True)

    total = time.perf_counter() - t_start
    entries_end = len(glob.glob(os.path.join(cache_dir, "*")))
    _say(f"total {total:.1f} s wall (set-up time, not a metric); compile "
         f"cache {entries} -> {entries_end} entries")
    summary = {"device": dev, "jax": jax.__version__, "legs": results,
               "wall_s": round(total, 1), "cache_dir": cache_dir,
               "cache_entries": [entries, entries_end],
               "rehearsal": args.rehearse_cpu, "claim": None}
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)

    failed = [name for name, r in results.items() if not r["ok"]]
    if failed:
        print(f"chip_smoke: FAILED legs: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    if args.rehearse_cpu:
        _say("rehearsal passed on the CPU — this is NOT a chip pass")
        return 3
    if legs != ALL_LEGS:
        _say(f"partial run ({','.join(legs)}) passed — not a pass line")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
