"""Per-phase layer profiling (≙ the reference's per-phase accumulators and
the paper's Tables 4-8: conv / pooling / fully-connected / gradient times).

The reference times each phase with host clock() inside forward_pass/
back_pass (Sequential/Main.cpp:80-102,113-141) — and in the CUDA backend
forgets to synchronize, timing kernel *launches* (SURVEY.md B11). Here each
phase is its own jitted program timed with block_until_ready after a
warm-up compile, so numbers are device-execution time.

Phases mirror the reference decomposition:
    conv  ≙ fp_c1 + sigmoid           (Sequential/Main.cpp:80-85)
    pool  ≙ fp_s1 + sigmoid           (:87-93)
    fc    ≙ fp_preact_f/bias + sigmoid (:95-101)
    grad  ≙ the whole back_pass        (:107-144)

Also wraps `jax.profiler` tracing for real XLA-level profiles.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from parallel_cnn_tpu.ops import reference as ops


def _tree_checksum(tree) -> jax.Array:
    return sum(jnp.sum(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def _time_fn(fn: Callable, x: jax.Array, *rest, repeats: int = 10) -> float:
    """Mean seconds per call of fn(x, *rest), device-execution time.

    Method (the reference's unsync'd clock() timing is SURVEY.md B11):
    - repeats run INSIDE one program, each iteration's input chained
      through the carry (loop-variant, so XLA cannot hoist the body);
    - the barrier is a host readback (float()) of a value that depends on
      every iteration.

    Repeat-until-resolvable (fix for the `phase_fc = 0.0` rows in the
    paper tables): a microsecond phase under a ~ms dispatch + readback
    floor used to clamp to 0.0 when the overhead subtraction went negative — a
    zero that poisoned every downstream speedup column. Now the repeat
    count auto-scales (×8 per attempt) until the loop's elapsed time dominates the measured overhead, so
    the subtraction is a ≤25% correction; if even the largest loop is
    overhead-bound, the UN-subtracted mean is returned — an upper
    bound, but honest and NONZERO, so every table row computes.
    """

    def make_looped(r: int):
        @jax.jit
        def looped(x, *rest):
            def body(_, s):
                out = fn(x + s * 1e-30, *rest)
                return s + _tree_checksum(out) * 1e-30

            return jax.lax.fori_loop(0, r, body, jnp.float32(0.0))

        return looped

    # Dispatch + readback floor (~ms, which would otherwise swamp these
    # microsecond phases): measured on a trivial chained program and
    # subtracted below.
    tiny = jax.jit(lambda v: v + 1.0)
    v = tiny(jnp.float32(0.0))
    float(v)
    t0 = time.perf_counter()
    float(tiny(v))
    overhead = time.perf_counter() - t0

    r = max(repeats, 1)
    elapsed = 0.0
    for _ in range(4):
        looped = make_looped(r)
        float(looped(x + 1.0, *rest))  # compile + warm on distinct args
        t0 = time.perf_counter()
        float(looped(x, *rest))  # distinct from warm-up → real execution
        elapsed = time.perf_counter() - t0
        if elapsed > 0 and elapsed - overhead > 0 and elapsed >= 4 * overhead:
            return (elapsed - overhead) / r
        r *= 8
    r //= 8  # the repeat count the final attempt actually ran
    return max(elapsed / r, 1e-12)


def profile_phases(
    params: ops.Params, xs: jax.Array, ys: jax.Array, repeats: int = 10
) -> Dict[str, float]:
    """Per-phase mean seconds for a batch (the paper's table decomposition).

    Returns {"conv", "pool", "fc", "grad", "total_forward"}.
    """
    sigmoid = jax.nn.sigmoid

    # Timed input first: _time_fn perturbs it per loop iteration.
    def conv(x, p):
        return sigmoid(
            jax.vmap(lambda s: ops.conv_c1_forward(s, p["c1"]["w"], p["c1"]["b"]))(x)
        )

    def pool(oc, p):
        return sigmoid(
            jax.vmap(lambda s: ops.pool_s1_forward(s, p["s1"]["w"], p["s1"]["b"]))(oc)
        )

    def fc(os_, p):
        return sigmoid(
            jax.vmap(lambda s: ops.fc_forward(s, p["f"]["w"], p["f"]["b"]))(os_)
        )

    def fwd(x, p):
        return jax.vmap(lambda s: ops.forward(p, s).out_f)(x)

    def grad(x, p, y):
        _, grads = jax.vmap(ops.value_and_ref_grads, in_axes=(None, 0, 0))(p, x, y)
        return jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), grads)

    out_c1 = jax.jit(conv)(xs, params)
    out_s1 = jax.jit(pool)(out_c1, params)

    return {
        "conv": _time_fn(conv, xs, params, repeats=repeats),
        "pool": _time_fn(pool, out_c1, params, repeats=repeats),
        "fc": _time_fn(fc, out_s1, params, repeats=repeats),
        "grad": _time_fn(grad, xs, params, ys, repeats=repeats),
        "total_forward": _time_fn(fwd, xs, params, repeats=repeats),
    }


def report(phase_seconds: Dict[str, float], n_images: int) -> str:
    """Render the paper-style per-layer table (≙ PDF Table 4 shape)."""
    lines = [f"{'phase':<14}{'ms/batch':>12}{'images/sec':>14}"]
    for name, sec in phase_seconds.items():
        ips = n_images / sec if sec > 0 else float("inf")
        lines.append(f"{name:<14}{sec * 1e3:>12.3f}{ips:>14.0f}")
    return "\n".join(lines)


@contextmanager
def xla_trace(log_dir: str):
    """jax.profiler trace wrapper — open the result in XProf/TensorBoard.
    The real replacement for hand-rolled clock() spans."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
