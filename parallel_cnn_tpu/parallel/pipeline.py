"""Pipeline-parallel substrate: stage partitioning + the 1F1B schedule.

The reference's MPI backend decomposes each kernel across ranks; the
scale-out direction it gestures at (and arXiv:1711.00705 /
arXiv:1810.11112 analyze) is partitioning the MODEL across devices.
This module is the static half of that axis:

- ``split_layers`` chooses stage boundaries by balancing per-layer flops
  from the PR 8 cost accountant's measured tables
  (analysis/cost_model.measured_flops over each layer's jaxpr) — the
  same numbers `check --cost` verifies, so the splitter and the gate
  share one source of truth;
- ``schedule_events`` is the closed-form 1F1B tick table the traced step
  (train/pipeline_schedule.py) compiles against: forward of microbatch m
  at stage s fires at tick ``s + 2m``, its backward at tick
  ``2S − 1 − s + 2m``, giving warmup/steady/cooldown with at most S live
  stashed microbatches per stage and a bubble fraction of
  (S−1)/(S−1+M);
- the pack/unpack helpers flatten stage-boundary activations into one
  uniform zero-padded ``(microbatch, A_buf)`` wire buffer so every
  stage's send/recv has identical type regardless of which layer's
  output crosses the boundary (the uniformity `lax.switch` needs).

Everything here is host-side Python over static shapes — no jax tracing
happens at import, and the schedule is a pure function of (S, M) so
tests can pin its event order exactly.
"""

from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parallel_cnn_tpu.nn.core import Module


# ---------------------------------------------------------------------------
# 1F1B schedule (closed form)
# ---------------------------------------------------------------------------

class TickEvent(NamedTuple):
    """One synchronous tick: per-stage microbatch ids (None = idle).

    ``fwd[s]`` is the microbatch whose forward stage s runs this tick;
    ``bwd[s]`` the microbatch whose backward it runs. The closed form
    gives each stage disjoint fwd/bwd tick parities, so a stage never
    does both in one tick.
    """

    fwd: Tuple[Optional[int], ...]
    bwd: Tuple[Optional[int], ...]


def n_ticks(n_stages: int, n_micro: int) -> int:
    """Total ticks of the 1F1B schedule: 2·(M + S − 1)."""
    return 2 * (n_micro + n_stages - 1)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction per stage: (S−1)/(S−1+M) — the GPipe bubble law.

    Each stage works 2M of the 2(M+S−1) ticks (M forwards + M
    backwards), so the idle share is (S−1)/(M+S−1) regardless of s.
    """
    return (n_stages - 1) / (n_stages - 1 + n_micro)


def schedule_events(n_stages: int, n_micro: int) -> Tuple[TickEvent, ...]:
    """The deterministic 1F1B tick table for S stages × M microbatches.

    Closed form: Tf(s, m) = s + 2m and Tb(s, m) = 2S − 1 − s + 2m.
    Consequences the traced step and the tests rely on:

    - producer/consumer latency is exactly one tick on both wires
      (Tf(s+1, m) = Tf(s, m) + 1; Tb(s, m) = Tb(s+1, m) + 1), matching
      the one-ppermute-per-tick send/recv;
    - a stage's fwd ticks have parity s, its bwd ticks parity s+1 —
      never both in one tick;
    - stash slot ``m mod S`` is reuse-safe: Tf(s, m+S) − Tb(s, m) =
      2s + 1 > 0, so microbatch m's stashed input is consumed strictly
      before microbatch m+S overwrites the slot.
    """
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_micro < 1:
        raise ValueError(f"n_micro must be >= 1, got {n_micro}")
    events = []
    for t in range(n_ticks(n_stages, n_micro)):
        fwd: List[Optional[int]] = []
        bwd: List[Optional[int]] = []
        for s in range(n_stages):
            df = t - s
            fwd.append(df // 2 if df >= 0 and df % 2 == 0
                       and df // 2 < n_micro else None)
            db = t - (2 * n_stages - 1 - s)
            bwd.append(db // 2 if db >= 0 and db % 2 == 0
                       and db // 2 < n_micro else None)
        events.append(TickEvent(tuple(fwd), tuple(bwd)))
    return tuple(events)


def schedule_arrays(n_stages: int, n_micro: int):
    """The schedule as (T, S) numpy constants for the traced step.

    Returns (fwd_mb, fwd_valid, bwd_mb, bwd_valid): int32 microbatch ids
    (idle entries clamped to 0 — the valid masks gate every use) and
    bool validity masks. np constants, not Python ints, so the traced
    step's `where` masks never introduce weak types.
    """
    events = schedule_events(n_stages, n_micro)
    t_total = len(events)
    fwd_mb = np.zeros((t_total, n_stages), np.int32)
    fwd_valid = np.zeros((t_total, n_stages), bool)
    bwd_mb = np.zeros((t_total, n_stages), np.int32)
    bwd_valid = np.zeros((t_total, n_stages), bool)
    for t, ev in enumerate(events):
        for s in range(n_stages):
            if ev.fwd[s] is not None:
                fwd_mb[t, s] = ev.fwd[s]
                fwd_valid[t, s] = True
            if ev.bwd[s] is not None:
                bwd_mb[t, s] = ev.bwd[s]
                bwd_valid[t, s] = True
    return fwd_mb, fwd_valid, bwd_mb, bwd_valid


def stash_high_water(n_stages: int, n_micro: int) -> int:
    """Max simultaneously-stashed microbatches at any stage (simulated).

    The 1F1B bound: never exceeds n_stages (tests/test_pipeline.py pins
    it) — the whole point of 1F1B over all-forward-then-all-backward
    GPipe, whose stash grows with M instead.
    """
    peak = 0
    for s in range(n_stages):
        live = set()
        for ev in schedule_events(n_stages, n_micro):
            if ev.fwd[s] is not None:
                live.add(ev.fwd[s])
                peak = max(peak, len(live))
            if ev.bwd[s] is not None:
                live.discard(ev.bwd[s])
    return peak


# ---------------------------------------------------------------------------
# Cost-model-driven stage splitting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerCost:
    """Per-layer static cost row (the splitter's input)."""

    index: int
    name: str
    flops: int          # measured_flops of this layer's fwd jaxpr
    param_bytes: int    # trainable residency
    out_shape: Tuple[int, ...]  # batched output (microbatch leading)
    out_numel: int      # per-SAMPLE activation numel (wire payload unit)


def _tree_bytes(tree) -> int:
    return sum(
        int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def layer_costs(model: Module, in_shape: Sequence[int],
                microbatch: int = 1) -> Tuple[LayerCost, ...]:
    """Per-layer flops/bytes/output table from the cost accountant.

    Each layer's forward is traced in isolation at the microbatch shape
    and its contraction flops counted by the same
    cost_model.measured_flops walk `check --cost` uses — the splitter
    balances exactly the numbers the gate verifies. Shape-only: params
    come from a fixed-seed init and never execute.
    """
    from parallel_cnn_tpu.analysis.cost_model import measured_flops

    params, state, _ = model.init(jax.random.PRNGKey(0), tuple(in_shape))
    rows = []
    shape = tuple(in_shape)
    for i, (layer, p, s) in enumerate(zip(model.layers, params, state)):
        x = jax.ShapeDtypeStruct((microbatch,) + shape, jnp.float32)

        def fwd(xx, layer=layer, p=p, s=s):
            return layer.apply(p, s, xx, train=True)[0]

        closed = jax.make_jaxpr(fwd)(x)
        out = jax.eval_shape(fwd, x)
        rows.append(LayerCost(
            index=i,
            name=type(layer).__name__,
            flops=int(measured_flops(closed)),
            param_bytes=_tree_bytes(p),
            out_shape=tuple(out.shape),
            out_numel=int(np.prod(out.shape[1:])),
        ))
        shape = tuple(out.shape[1:])
    return tuple(rows)


def split_layers(model: Module, n_stages: int, in_shape: Sequence[int],
                 microbatch: int = 1,
                 boundaries: Sequence[int] = ()) -> Tuple[int, ...]:
    """Choose stage-start boundaries (S−1 strictly-increasing layer
    indices in [1, L−1]) for a contiguous S-way partition of the model.

    Automatic mode (no ``boundaries``): dynamic programming over
    contiguous partitions minimizing the maximum per-stage flops —
    the pipeline's steady-state throughput is set by its slowest stage —
    with maximum per-stage param bytes as the tie-break (prefer the
    split that also levels residency). Manual mode validates the given
    boundaries against the layer count and returns them sorted.
    """
    n_layers = len(model.layers)
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    if n_stages > n_layers:
        raise ValueError(
            f"cannot split {n_layers} layers into {n_stages} stages "
            "(every stage needs at least one layer)"
        )
    if boundaries:
        b = tuple(sorted(int(x) for x in boundaries))
        if len(b) != n_stages - 1:
            raise ValueError(
                f"{len(b)} boundaries cannot make {n_stages} stages "
                f"(need {n_stages - 1})"
            )
        if len(set(b)) != len(b) or b[0] < 1 or b[-1] > n_layers - 1:
            raise ValueError(
                f"boundaries {b} must be distinct layer indices in "
                f"[1, {n_layers - 1}]"
            )
        return b
    if n_stages == 1:
        return ()

    costs = layer_costs(model, in_shape, microbatch)
    flops = [c.flops for c in costs]
    pbytes = [c.param_bytes for c in costs]
    pref_f = np.concatenate([[0], np.cumsum(flops)])
    pref_b = np.concatenate([[0], np.cumsum(pbytes)])

    def seg(pref, a, b):  # cost of layers [a, b)
        return int(pref[b] - pref[a])

    # best[k][j] = (max_flops, max_bytes, boundaries) for splitting the
    # first j layers into k stages. L and S are tiny (≤ dozens), so the
    # O(S·L²) table is free.
    best = {(1, j): (seg(pref_f, 0, j), seg(pref_b, 0, j), ())
            for j in range(1, n_layers + 1)}
    for k in range(2, n_stages + 1):
        for j in range(k, n_layers + 1):
            cand = None
            for i in range(k - 1, j):
                mf, mb, bs = best[(k - 1, i)]
                key = (max(mf, seg(pref_f, i, j)),
                       max(mb, seg(pref_b, i, j)))
                if cand is None or key < cand[:2]:
                    cand = (*key, bs + (i,))
            best[(k, j)] = cand
    return best[(n_stages, n_layers)][2]


def stage_assignment(n_layers: int,
                     boundaries: Sequence[int]) -> np.ndarray:
    """Layer-index → stage-index map (int32, length n_layers)."""
    assign = np.zeros(n_layers, np.int32)
    for b in boundaries:
        assign[b:] += 1
    return assign


# ---------------------------------------------------------------------------
# Stage-boundary wire buffers
# ---------------------------------------------------------------------------

def boundary_shapes(model: Module, in_shape: Sequence[int],
                    boundaries: Sequence[int],
                    microbatch: int) -> Tuple[Tuple[int, ...], ...]:
    """Batched activation shape crossing each stage boundary: the output
    of the last layer of stages 0..S−2, at the microbatch size."""
    costs = layer_costs(model, in_shape, microbatch)
    return tuple(costs[b - 1].out_shape for b in boundaries)


def wire_numel(model: Module, in_shape: Sequence[int],
               boundaries: Sequence[int], microbatch: int) -> int:
    """A_buf: the uniform per-microbatch wire/stash width — max
    per-sample numel over every stage boundary AND the model input (the
    first-stage branch packs its image microbatch through the same
    buffer so all `lax.switch` branches stay type-uniform)."""
    numels = [int(np.prod(tuple(in_shape)))]
    costs = layer_costs(model, in_shape, microbatch)
    numels += [costs[b - 1].out_numel for b in boundaries]
    return max(numels)


def pack_acts(x: jax.Array, a_buf: int) -> jax.Array:
    """Flatten a batched activation to (batch, A_buf), zero-padded."""
    flat = x.reshape(x.shape[0], -1)
    pad = a_buf - flat.shape[1]
    if pad < 0:
        raise ValueError(
            f"activation numel {flat.shape[1]} exceeds wire width {a_buf}"
        )
    if pad == 0:
        return flat
    return jnp.pad(flat, ((0, 0), (0, pad)))


def unpack_acts(buf: jax.Array, shape: Sequence[int]) -> jax.Array:
    """Recover a batched activation from its packed wire buffer."""
    shape = tuple(shape)
    numel = int(np.prod(shape[1:]))
    return buf[:, :numel].reshape(shape)
