"""Straggler-tolerant asynchronous data parallelism.

Every other training mode in this package — flat ring, hierarchical,
ZeRO-2/3, elastic — is bulk-synchronous: the optimizer step is a barrier,
so one slow worker stalls the entire ring (exactly the tail-latency
fault the serve path defends against with ``slow-replica@`` chaos).
This module adds the two standard asynchronous escapes, selected by
``config.AsyncConfig`` (``--async-mode`` / ``PCNN_ASYNC_MODE``):

- **Bounded staleness** (mode ``stale``, stale-synchronous parallel per
  arXiv:1711.00705): a central server holds the authoritative params at
  version ``V`` (one version per optimizer step).  Each worker snapshots
  the server params at dispatch, computes its gradient against that
  snapshot, and the server applies it only while the snapshot is at most
  ``staleness_bound`` (S) versions old.  The server *pre-gates* every
  apply: if advancing ``V`` would doom any still-in-flight worker's
  snapshot past S, the ready gradients are held — the **hard barrier**
  fires only when the bound would otherwise be violated.  Every applied
  contribution is recorded in a :class:`StalenessLedger` which raises if
  a gradient older than S ever reaches the optimizer (defense in depth
  behind the scheduler's gate).  S = 0 degenerates to the synchronous
  schedule and is bit-exact with mode ``off`` by construction: both run
  the same combine-and-apply code path over the same per-worker grad
  sums in the same worker-id order.

- **EASGD elastic averaging** (mode ``easgd``, arXiv:1605.08325): each
  worker runs *independent* local SGD — no inter-worker gate at all —
  and every ``easgd_period`` local steps does an elastic round with a
  shared **center variable**: ``x_i ← x_i − ρ(x_i − c)`` and
  ``c ← c + ρ(x_i − c)``.  The center is held in the ZeRO-style bucket
  representation (``plan_buckets``/``flatten_buckets`` row shards), and
  :func:`easgd_round_sharded` is the device-resident round a real
  multi-device deployment runs — center shards pulled with a ring
  all-gather and pushed with a ring reduce-scatter, f32 on the wire,
  registered as the ``train.easgd_round`` graftcheck entry.

**What async mode does NOT preserve:** bitwise parity with the sync
ring (except stale-0).  The contract is a *bounded loss delta* instead —
tests/test_async_dp.py pins a seeded
3-step |loss − sync| ≤ 1e-2, clean and under a 400 ms straggler.

**Scheduling is a deterministic virtual clock.**  The single-process
harness simulates N logical workers with real jitted gradients but
*virtual* durations: a dispatch costs ``step_ms`` of virtual time plus
any chaos stall (``slow-worker@STEP:MS`` polls
``ChaosMonkey.slow_worker_at`` at the microbatch dispatch boundary, the
training twin of ``slow-replica@``), and completions are processed in
(virtual time, worker id) order.  No wall clocks, no unseeded
randomness — a chaos run replays exactly, so the throughput gates are
deterministic on CPU.  Throughput is microbatches applied per virtual
millisecond; under a straggler the sync ring's round time is the max
over workers (it visibly stalls) while the async modes keep the healthy
workers busy (they visibly don't).

Sentinel composition: a NaN on one stale worker (chaos ``nan@K``
poisons the K-th completed gradient) is caught host-side by the
resilience sentinel *before* the server/center sees it — the
contribution is dropped (stale: the worker re-snapshots healthy server
params; easgd: the worker is reset from the center), so the center is
never poisoned.  docs/fault_tolerance.md has the straggler state
machine (detect → bound → degrade → recover).
"""

from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from parallel_cnn_tpu.config import AsyncConfig
from parallel_cnn_tpu.obs import NOOP
from parallel_cnn_tpu.parallel import collectives
from parallel_cnn_tpu.train import step as step_lib


# --------------------------------------------------------------------------
# Staleness ledger
# --------------------------------------------------------------------------


class StalenessLedger:
    """Per-worker record of the staleness of every *applied* gradient.

    ``record`` is called at the apply boundary with the version gap
    between the server params and the snapshot the gradient was computed
    against; it raises if the gap ever exceeds the configured bound —
    the scheduler's dispatch gate makes that unreachable, the ledger
    proves it stayed unreachable.
    """

    def __init__(self, workers: int, bound: int):
        self.bound = bound
        self.entries: List[List[int]] = [[] for _ in range(workers)]

    def record(self, worker: int, staleness: int) -> None:
        if staleness < 0 or staleness > self.bound:
            raise RuntimeError(
                f"staleness bound violated: worker {worker} applied a "
                f"gradient {staleness} versions old (bound {self.bound})"
            )
        self.entries[worker].append(staleness)

    def max_staleness(self) -> int:
        return max((max(e) for e in self.entries if e), default=0)

    def total_applied(self) -> int:
        return sum(len(e) for e in self.entries)


@dataclasses.dataclass
class AsyncRunResult:
    """What one virtual-clock training run produced."""

    params: Any                 # final authoritative params (server/center)
    ledger: StalenessLedger     # empty for easgd (no versioned server)
    virtual_ms: float           # virtual time consumed
    microbatches: int           # gradient microbatches applied
    server_steps: int           # optimizer steps (stale/sync) / rounds sum
    losses: List[float]         # per-apply mean err (stale/sync)
    stragglers: int             # straggler_detected count
    dropped: int                # NaN contributions dropped by the sentinel
    easgd_rounds: int           # elastic-averaging rounds executed

    def throughput(self) -> float:
        """Microbatches per virtual millisecond (0 if nothing ran)."""
        return self.microbatches / self.virtual_ms if self.virtual_ms else 0.0


# --------------------------------------------------------------------------
# Jitted numerics — shared by every mode so parity claims are structural
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ops_path",))
def _grad_sums(params, x, y, ops_path="reference"):
    return step_lib.local_grad_sums(params, x, y, ops_path=ops_path)


@functools.partial(jax.jit, static_argnames=("n", "dt"))
def _apply_mean(params, grad_sums, n: int, dt: float):
    mean = jax.tree_util.tree_map(lambda g: g / n, grad_sums)
    return step_lib.apply_grad(params, mean, dt)


@jax.jit
def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@jax.jit
def _easgd_pull(worker_buckets, center_buckets, rho):
    """One elastic round on the bucketized representation: the worker and
    the center each move ρ of the way toward the other (arXiv:1605.08325
    eq. 5/6).  ``rho`` is a 0-d f32 array (one compile per run)."""
    deltas = [
        rho * (w - c) for w, c in zip(worker_buckets, center_buckets)
    ]
    new_w = [w - d for w, d in zip(worker_buckets, deltas)]
    new_c = [c + d for c, d in zip(center_buckets, deltas)]
    return new_w, new_c


@jax.jit
def eval_err(params, x, y):
    """Mean err of ``params`` on a fixed batch — the seeded loss metric
    the sync-vs-async delta gates compare."""
    err_sum, _ = step_lib.local_grad_sums(params, x, y)
    return err_sum / x.shape[0]


def easgd_round_sharded(
    worker_flat: jax.Array,
    center_shard: jax.Array,
    rho: jax.Array,
    *,
    axis_name: str,
    axis_size: int,
) -> Tuple[jax.Array, jax.Array]:
    """Device-resident elastic round over ``axis_name`` (call inside
    shard_map, ``check_vma=False`` like every ring caller).

    Each device holds its worker's full flat params (``worker_flat``,
    length ``axis_size * shard_len``) and a 1/n row shard of the center
    (``center_shard``).  The round is two ring collectives, both f32 on
    the wire (the center is master state, same contract as the ZeRO-3
    param gathers):

    - pull: ``ring_all_gather`` rematerializes the full center from the
      resident shards, and the worker moves ρ toward it;
    - push: the per-worker deltas are ``ring_reduce_scatter``-ed back
      onto the resident shards, so the center moves ρ toward the *mean*
      worker — the synchronous multi-worker EASGD center update.

    Registered as the ``train.easgd_round`` graftcheck entry: ring
    coverage per axis and the f32-wire rules must hold here exactly as
    they do for the gradient rings.
    """
    center = collectives.ring_all_gather(center_shard, axis_name, axis_size)
    delta = rho * (worker_flat - center)
    new_worker = worker_flat - delta
    d_shard = collectives.ring_reduce_scatter(delta, axis_name, axis_size)
    new_center_shard = center_shard + d_shard / jnp.float32(axis_size)
    return new_worker, new_center_shard


# --------------------------------------------------------------------------
# Virtual-clock scheduler
# --------------------------------------------------------------------------


def _healthy(sentinel, grads) -> bool:
    if sentinel is None:
        return True
    return bool(sentinel.check(grads=grads).healthy)


class _Dispatcher:
    """Per-run dispatch bookkeeping: the global dispatch sequence the
    chaos hook keys on, straggler detection, and the journal."""

    def __init__(self, step_ms: float, factor: float, chaos, obs):
        self.step_ms = step_ms
        self.factor = factor
        self.chaos = chaos
        self.obs = obs
        self.seq = 0
        self.stragglers = 0

    def duration(self, worker: int) -> float:
        """Virtual duration of the next dispatch (nominal + chaos stall),
        advancing the global dispatch sequence."""
        seq, self.seq = self.seq, self.seq + 1
        stall = self.chaos.slow_worker_at(seq) if self.chaos else None
        if stall:
            if self.obs.enabled:
                self.obs.event(
                    "chaos_slow_worker", seq=seq, worker=worker, ms=stall
                )
            return self.step_ms + stall
        return self.step_ms

    def completed(self, worker: int, duration: float) -> None:
        if duration > self.factor * self.step_ms:
            self.stragglers += 1
            if self.obs.enabled:
                self.obs.event(
                    "straggler_detected", worker=worker, ms=duration,
                    nominal_ms=self.step_ms,
                )


def run_async(
    params: Any,
    xs: jax.Array,
    ys: jax.Array,
    *,
    cfg: AsyncConfig,
    dt: float = 0.05,
    step_ms: float = 100.0,
    horizon_ms: Optional[float] = None,
    max_server_steps: Optional[int] = None,
    chaos=None,
    sentinel=None,
    obs=None,
    ops_path: str = "reference",
) -> AsyncRunResult:
    """Run the virtual-clock async/sync trainer to a horizon.

    ``xs``/``ys`` carry one microbatch per worker — shapes
    ``(workers, b, ...)`` / ``(workers, b)``; each worker re-reads its
    shard every local step (the shard IS its data stream, as in the
    2-process gloo harness).  Exactly one of ``horizon_ms`` (throughput
    runs) and ``max_server_steps`` (loss-trajectory runs; counts
    optimizer steps for sync/stale, per-worker local steps for easgd)
    must be given.  Gradients are real (jitted ``local_grad_sums``);
    time is virtual — see the module docstring.
    """
    if (horizon_ms is None) == (max_server_steps is None):
        raise ValueError("give exactly one of horizon_ms/max_server_steps")
    if xs.shape[0] != cfg.workers or ys.shape[0] != cfg.workers:
        raise ValueError(
            f"data leading dim {xs.shape[0]} != workers {cfg.workers}"
        )
    obs = obs or NOOP
    if cfg.mode == "easgd":
        return _run_easgd(
            params, xs, ys, cfg=cfg, dt=dt, step_ms=step_ms,
            horizon_ms=horizon_ms, max_local_steps=max_server_steps,
            chaos=chaos, sentinel=sentinel, obs=obs, ops_path=ops_path,
        )
    return _run_stale(
        params, xs, ys, cfg=cfg, dt=dt, step_ms=step_ms,
        horizon_ms=horizon_ms, max_server_steps=max_server_steps,
        chaos=chaos, sentinel=sentinel, obs=obs, ops_path=ops_path,
    )


def _run_stale(
    params, xs, ys, *, cfg, dt, step_ms, horizon_ms, max_server_steps,
    chaos, sentinel, obs, ops_path,
) -> AsyncRunResult:
    """Bounded-staleness server (and, with mode="off", the synchronous
    reference: S=0 forces the barrier every step, which reduces the
    event schedule to lockstep rounds — the sync ring in virtual time)."""
    w = cfg.workers
    bound = 0 if cfg.mode == "off" else cfg.staleness_bound
    disp = _Dispatcher(step_ms, cfg.straggler_factor, chaos, obs)
    ledger = StalenessLedger(w, bound)
    b = int(xs.shape[1])

    version = 0
    losses: List[float] = []
    dropped = 0
    microbatches = 0
    virtual_ms = 0.0

    # (completion_time, worker) min-heap; per-worker in-flight snapshots.
    heap: List[Tuple[float, int]] = []
    snap_params: Dict[int, Any] = {}
    snap_version: Dict[int, int] = {}
    dispatch_at: Dict[int, float] = {}
    # Completed-but-held contributions: worker -> (version, err_sum, grads)
    held: Dict[int, Tuple[int, Any, Any]] = {}

    def dispatch(worker: int, now: float) -> None:
        dur = disp.duration(worker)
        done = now + dur
        if horizon_ms is not None and done > horizon_ms:
            return  # would complete past the measurement horizon
        snap_params[worker] = params
        snap_version[worker] = version
        dispatch_at[worker] = now
        heapq.heappush(heap, (done, worker))

    for i in range(w):
        dispatch(i, 0.0)

    while heap:
        if max_server_steps is not None and version >= max_server_steps:
            break
        t_now, _ = heap[0]
        # Drain the whole group of completions at this virtual instant
        # (worker-id order is the heap tiebreak).
        group: List[int] = []
        while heap and heap[0][0] == t_now:
            _, worker = heapq.heappop(heap)
            group.append(worker)
        for worker in group:
            disp.completed(worker, t_now - dispatch_at[worker])
            err_sum, grads = _grad_sums(
                snap_params[worker], xs[worker], ys[worker],
                ops_path=ops_path,
            )
            if chaos is not None:
                grads, err_sum = chaos.after_step(grads, err_sum)
            if not _healthy(sentinel, grads):
                dropped += 1
                if obs.enabled:
                    obs.event(
                        "sentinel_drop", worker=worker,
                        version=snap_version[worker],
                    )
                # Re-snapshot healthy server params and go again.
                dispatch(worker, t_now)
                continue
            held[worker] = (snap_version[worker], err_sum, grads)

        # Hard barrier: applying a step bumps version; if that would doom
        # any still-in-flight snapshot past the bound, hold everything
        # until the laggard completes.
        in_flight = {wk for _, wk in heap}
        blocked = any(
            version + 1 - snap_version[j] > bound for j in in_flight
        )
        if blocked:
            if obs.enabled and held:
                obs.event(
                    "staleness", step=version, barrier=1,
                    held=len(held), t_ms=t_now,
                )
            virtual_ms = t_now
            continue
        if not held:
            virtual_ms = max(virtual_ms, t_now)
            continue

        # One optimizer step per virtual instant: combine every held
        # contribution in worker-id order (the sync ring's combine order)
        # and apply once.
        order = sorted(held)
        total_err = None
        total_grads = None
        group_stale = 0
        for worker in order:
            v, err_sum, grads = held[worker]
            staleness = version - v
            ledger.record(worker, staleness)
            group_stale = max(group_stale, staleness)
            total_err = err_sum if total_err is None else total_err + err_sum
            total_grads = (
                grads if total_grads is None else _tree_add(total_grads, grads)
            )
        n_total = b * len(order)
        params = _apply_mean(params, total_grads, n=n_total, dt=dt)
        version += 1
        microbatches += len(order)
        virtual_ms = t_now
        losses.append(float(total_err) / n_total)
        if obs.enabled:
            obs.event(
                "staleness", step=version, barrier=0,
                max_staleness=group_stale, workers=len(order), t_ms=t_now,
            )
        held.clear()
        if max_server_steps is not None and version >= max_server_steps:
            break
        for worker in order:
            dispatch(worker, t_now)

    return AsyncRunResult(
        params=params, ledger=ledger, virtual_ms=virtual_ms,
        microbatches=microbatches, server_steps=version, losses=losses,
        stragglers=disp.stragglers, dropped=dropped, easgd_rounds=0,
    )


def _run_easgd(
    params, xs, ys, *, cfg, dt, step_ms, horizon_ms, max_local_steps,
    chaos, sentinel, obs, ops_path,
) -> AsyncRunResult:
    """Elastic averaging: independent local SGD per worker, a ρ-pull
    against the bucketized center every ``easgd_period`` local steps.
    No inter-worker gate — the straggler only delays its own stream."""
    w = cfg.workers
    disp = _Dispatcher(step_ms, cfg.straggler_factor, chaos, obs)
    b = int(xs.shape[1])
    rho = jnp.float32(cfg.easgd_rho)

    plan = collectives.plan_buckets(params, shards=w)
    center = [c.astype(jnp.float32)
              for c in collectives.flatten_buckets(params, plan)]
    worker_params = [params for _ in range(w)]
    local_steps = [0] * w
    dropped = 0
    rounds = 0
    microbatches = 0
    virtual_ms = 0.0

    heap: List[Tuple[float, int]] = []

    def dispatch(worker: int, now: float) -> None:
        if max_local_steps is not None \
                and local_steps[worker] >= max_local_steps:
            return
        dur = disp.duration(worker)
        done = now + dur
        if horizon_ms is not None and done > horizon_ms:
            return
        heapq.heappush(heap, (done, worker))

    dispatch_at: Dict[int, float] = {}
    for i in range(w):
        dispatch_at[i] = 0.0
        dispatch(i, 0.0)

    while heap:
        t_now, worker = heapq.heappop(heap)
        disp.completed(worker, t_now - dispatch_at[worker])
        err_sum, grads = _grad_sums(
            worker_params[worker], xs[worker], ys[worker], ops_path=ops_path
        )
        if chaos is not None:
            grads, err_sum = chaos.after_step(grads, err_sum)
        if not _healthy(sentinel, grads):
            # Poisoned local gradient: drop it and reset the worker from
            # the (never-poisoned) center — the recover edge of the
            # straggler/fault state machine.
            dropped += 1
            worker_params[worker] = collectives.unflatten_buckets(
                center, plan
            )
            if obs.enabled:
                obs.event(
                    "sentinel_drop", worker=worker,
                    local_step=local_steps[worker],
                )
        else:
            worker_params[worker] = _apply_mean(
                worker_params[worker], grads, n=b, dt=dt
            )
            local_steps[worker] += 1
            microbatches += 1
            if local_steps[worker] % cfg.easgd_period == 0:
                with obs.span("train.easgd_round", cat="comm",
                              worker=worker):
                    wb = collectives.flatten_buckets(
                        worker_params[worker], plan
                    )
                    new_w, center = _easgd_pull(wb, center, rho)
                    worker_params[worker] = collectives.unflatten_buckets(
                        new_w, plan
                    )
                rounds += 1
                if obs.enabled:
                    obs.event(
                        "easgd_round", worker=worker, round=rounds,
                        local_step=local_steps[worker], t_ms=t_now,
                    )
        virtual_ms = max(virtual_ms, t_now)
        dispatch_at[worker] = t_now
        dispatch(worker, t_now)

    return AsyncRunResult(
        params=collectives.unflatten_buckets(center, plan),
        ledger=StalenessLedger(w, 0), virtual_ms=virtual_ms,
        microbatches=microbatches, server_steps=rounds, losses=[],
        stragglers=disp.stragglers, dropped=dropped, easgd_rounds=rounds,
    )
