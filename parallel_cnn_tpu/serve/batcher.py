"""Dynamic batcher: bounded queue → coalesce → bucket-pad → split.

Queueing model (docs/serving.md has the math):

- ``submit`` is non-blocking. A full bounded queue sheds the request
  with the typed ``Overloaded`` error — graceful degradation under
  overload (the client retries with resilience/retry.py backoff, or
  drops); the alternative (unbounded queue) converts overload into
  unbounded latency AND host OOM.
- The worker thread pops the oldest request, then coalesces followers
  until ``max_batch`` requests OR ``max_wait_ms`` since the first pop —
  whichever first. max_wait_ms is therefore the batching latency tax an
  idle-period request pays, and the knob that trades p50 latency for
  batch occupancy at load.
- Requests carry optional deadlines; overdue ones are dropped with
  ``DeadlineExceeded`` the moment the worker pops them (the coalesce-time
  sweep — under backlog an expired request frees its queue slot
  immediately instead of riding along to dispatch), with a second sweep
  at dispatch time as the final check before a device slot is spent.
- An optional admission controller (serve/admission.py) runs in front of
  the queue: ``submit`` consults it before enqueueing (predicted-late and
  degradation-ladder rejects surface as ``Overloaded`` and count as
  sheds), and the worker lets it shrink the coalescing window / cap the
  bucket under pressure. The batcher feeds queue-wait and service-time
  observations back so the controller's EWMA predictor tracks reality.
- The dispatched batch pads into the engine's power-of-two bucket and
  the result rows are split back per request. Dispatch goes through a
  pool of ``n_replicas`` runner threads, so while replica 0 computes,
  the worker is already coalescing (and dispatching to replica 1) —
  that concurrency is what turns replica sharding into throughput.
"""

from __future__ import annotations

import queue as queue_mod
import threading
import time
from typing import Any, List, Optional

import numpy as np

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.serve.engine import ReplicaDead
from parallel_cnn_tpu.serve.telemetry import ServeStats


class Overloaded(RuntimeError):
    """Request shed: the bounded request queue is full (backpressure).

    Clients should back off and retry (resilience.retry.RetryPolicy is
    the house convention — seeded, capped exponential delays) or degrade;
    the server stays healthy instead of queueing without bound."""


class DeadlineExceeded(RuntimeError):
    """Request dropped: its deadline passed before dispatch."""


class Future:
    """Minimal single-result future resolved by the batcher."""

    def __init__(self):
        self._event = threading.Event()
        self._value: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        # Observability: which replica served it, in which batch (set at
        # dispatch; None if the request died before reaching a device).
        self.replica: Optional[int] = None
        self.batch_seq: Optional[int] = None
        # Resolution instant (monotonic), so callers polling result()
        # later can still measure true latency instead of observe time.
        self.t_done: Optional[float] = None

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError("request still in flight")
        if self._error is not None:
            raise self._error
        return self._value

    def _resolve(self, value: np.ndarray) -> None:
        self._value = value
        self.t_done = time.monotonic()
        self._event.set()

    def _fail(self, err: BaseException) -> None:
        self._error = err
        self.t_done = time.monotonic()
        self._event.set()


class _Request:
    __slots__ = ("x", "deadline", "t_submit", "priority", "future")

    def __init__(self, x, deadline, t_submit, priority="guaranteed"):
        self.x = x
        self.deadline = deadline  # absolute monotonic seconds, or None
        self.t_submit = t_submit
        self.priority = priority  # "guaranteed" | "best-effort"
        self.future = Future()


class DynamicBatcher:
    """Request front-end over an engine.ReplicaPool.

    ``start=False`` builds the batcher with the worker paused — tests
    use it to stage the queue deterministically (fill, overload, expire)
    before a single batch is formed — call ``start()`` to begin serving.
    Context-manager use closes the batcher (drains nothing: in-flight
    futures fail with RuntimeError on close).
    """

    def __init__(
        self,
        pool,
        *,
        max_wait_ms: float = 2.0,
        queue_depth: int = 256,
        deadline_ms: float = 0.0,
        stats: Optional[ServeStats] = None,
        start: bool = True,
        obs: Optional["obs_lib.Obs"] = None,
        chaos=None,
        admission=None,
    ):
        self.pool = pool
        # Fault injector (resilience.chaos.ChaosMonkey): kill_replica_at
        # fires on the dispatch batch sequence number, killing the target
        # replica the instant before its predict — the mid-traffic death
        # the failover path exists for; slow_replica_at stalls it instead
        # (the straggler the SLO gate exists to catch).
        self.chaos = chaos
        # SLO admission controller (serve/admission.py), or None for the
        # historical admit-everything-until-the-queue-is-full behavior.
        self.admission = admission
        self.max_batch = pool.max_batch
        self.max_wait_s = max_wait_ms / 1e3
        self.default_deadline_s = deadline_ms / 1e3 if deadline_ms else None
        self.stats = stats if stats is not None else ServeStats()
        # Host-side observability hooks (spans around dispatch, request
        # lifecycle journal events); the default no-op bundle is free.
        self.obs = obs if obs is not None else obs_lib.NOOP
        self._queue: "queue_mod.Queue[_Request]" = queue_mod.Queue(
            maxsize=queue_depth
        )
        self._stop = threading.Event()
        self._batch_seq = 0
        # Per-replica in-flight batch counts (formed-but-unfinished):
        # the autoscaler's drain barrier — a replica retires only after
        # its count returns to zero. Guarded by _lock.
        self._lock = threading.Lock()
        self._inflight: dict = {}
        self._runners = [
            threading.Thread(
                target=self._runner_loop, name=f"serve-runner-{i}", daemon=True
            )
            for i in range(pool.n_replicas)
        ]
        # Dispatch queue: formed batches awaiting a runner. Bounded at
        # the runner count so the worker blocks forming batch k+n until
        # a replica frees up — keeping requests in the REQUEST queue
        # (where shedding and deadline drops see them) instead of
        # accumulating in a hidden second queue.
        self._dispatch: "queue_mod.Queue" = queue_mod.Queue(
            maxsize=max(pool.n_replicas, 1)
        )
        self._worker = threading.Thread(
            target=self._worker_loop, name="serve-batcher", daemon=True
        )
        self._started = False
        if start:
            self.start()

    # -- client surface -------------------------------------------------

    def submit(self, x, deadline_ms: Optional[float] = None,
               priority: str = "guaranteed") -> Future:
        """Enqueue one request (a single sample, shape == in_shape).

        Raises Overloaded immediately when the bounded queue is full —
        or, with an admission controller attached, when the controller
        predicts the deadline cannot be met / the degradation ladder is
        shedding this priority class (both count as sheds: conservation
        is submitted == completed + shed + expired + failed).
        ``deadline_ms`` is a per-request budget from now (overrides the
        batcher default; None keeps the default, 0 disables).
        ``priority`` is "guaranteed" (default) or "best-effort" — the
        class the ladder drops first under pressure."""
        if priority not in ("guaranteed", "best-effort"):
            raise ValueError(
                f"priority must be 'guaranteed' or 'best-effort', "
                f"got {priority!r}"
            )
        x = np.asarray(x, dtype=np.float32)
        if x.shape != tuple(self.pool.handle.in_shape):
            raise ValueError(
                f"expected a single sample of shape "
                f"{tuple(self.pool.handle.in_shape)}, got {x.shape}"
            )
        now = time.monotonic()
        if deadline_ms is None:
            deadline = (
                now + self.default_deadline_s
                if self.default_deadline_s
                else None
            )
        else:
            deadline = now + deadline_ms / 1e3 if deadline_ms else None
        req = _Request(x, deadline, now, priority)
        self.stats.on_submit()
        if self.obs.enabled:
            self.obs.event("submit", req=id(req.future))
            self.obs.tracer.begin_async("request", id(req.future))
        if self.admission is not None:
            reason = self.admission.admit(
                priority=priority, deadline=deadline, now=now,
                queue_depth=self._queue.qsize(),
            )
            if reason is not None:
                self.stats.on_shed()
                if self.obs.enabled:
                    self.obs.event("shed", req=id(req.future),
                                   reason=reason)
                    self.obs.tracer.end_async("request", id(req.future))
                raise Overloaded(f"admission rejected: {reason}; "
                                 "back off and retry")
        try:
            self._queue.put_nowait(req)
        except queue_mod.Full:
            self.stats.on_shed()
            if self.obs.enabled:
                self.obs.event("shed", req=id(req.future))
                self.obs.tracer.end_async("request", id(req.future))
            raise Overloaded(
                f"request queue full ({self._queue.maxsize} deep); "
                "back off and retry"
            ) from None
        return req.future

    def start(self) -> None:
        if self._started:
            return
        # graftcheck: disable=lock-discipline -- start() is single-caller by contract (constructor or the test that staged start=False)
        self._started = True
        for t in self._runners:
            t.start()
        self._worker.start()

    def close(self) -> None:
        self._stop.set()
        if self._started:
            self._worker.join(timeout=5)
            for t in self._runners:
                t.join(timeout=5)
        # Fail anything still queued so no client blocks forever.
        for q in (self._queue, self._dispatch):
            while True:
                try:
                    item = q.get_nowait()
                except queue_mod.Empty:
                    break
                reqs = item if isinstance(item, list) else [item]
                for r in reqs:
                    if isinstance(r, _Request) and not r.future.done():
                        r.future._fail(RuntimeError("batcher closed"))

    def __enter__(self) -> "DynamicBatcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- worker side ----------------------------------------------------

    def _expire_req(self, r: _Request, now: float, where: str) -> None:
        """Fail one overdue request (coalesce- or dispatch-time sweep);
        the caller already knows now > r.deadline."""
        r.future._fail(DeadlineExceeded(
            f"deadline passed {1e3 * (now - r.deadline):.1f} ms "
            f"{where}"
        ))
        self.stats.on_expired(1)
        if self.obs.enabled:
            self.obs.event("expired", req=id(r.future))
            self.obs.tracer.end_async("request", id(r.future))

    def _pop_live(self, timeout: float) -> Optional[_Request]:
        """Pop one request, expiring overdue ones immediately (the
        coalesce-time sweep): under backlog a dead request frees its
        queue slot the moment the worker sees it, instead of riding
        along to dispatch. Returns None on timeout."""
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            try:
                r = self._queue.get(timeout=max(remaining, 0.0))
            except queue_mod.Empty:
                return None
            now = time.monotonic()
            if r.deadline is not None and now > r.deadline:
                self._expire_req(r, now, "in queue (coalesce sweep)")
                continue
            return r

    def _worker_loop(self) -> None:
        while not self._stop.is_set():
            first = self._pop_live(timeout=0.05)
            if first is None:
                continue
            # The degradation ladder (admission controller) may shrink
            # the coalescing window and cap the bucket under pressure.
            wait_s = self.max_wait_s
            cap = self.max_batch
            if self.admission is not None:
                wait_s = self.admission.effective_wait_s(wait_s)
                cap = self.admission.effective_max_batch(cap)
            batch = [first]
            t0 = time.monotonic()
            with self.obs.span("serve.coalesce", cat="serve"):
                while len(batch) < cap:
                    remaining = t0 + wait_s - time.monotonic()
                    if remaining <= 0:
                        break
                    r = self._pop_live(timeout=remaining)
                    if r is None:
                        break
                    batch.append(r)
            now = time.monotonic()
            live: List[_Request] = []
            n_expired = 0
            for r in batch:
                if r.deadline is not None and now > r.deadline:
                    self._expire_req(r, now, "before dispatch")
                    n_expired += 1
                else:
                    live.append(r)
            if not live:
                continue
            replica = self.pool.next_replica()
            seq = self._batch_seq
            # graftcheck: disable=lock-discipline -- _batch_seq is read and written only by this single worker thread
            self._batch_seq += 1
            bucket = self.pool.engines[replica].bucket_for(len(live))
            self.stats.on_batch(
                n=len(live),
                bucket=bucket,
                replica=replica,
                queue_depth=self._queue.qsize(),
            )
            if self.admission is not None:
                self.admission.observe_queue_wait(
                    max(now - r.t_submit for r in live)
                )
            if self.obs.enabled:
                self.obs.event(
                    "batch", seq=seq, n=len(live), bucket=bucket,
                    replica=replica, expired=n_expired,
                )
            with self._lock:
                self._inflight[replica] = self._inflight.get(replica, 0) + 1
            # Blocks when all runners are busy — deliberate backpressure
            # (see _dispatch's bound). Bail out on close.
            queued = False
            while not self._stop.is_set():
                try:
                    self._dispatch.put((live, replica, seq), timeout=0.05)
                    queued = True
                    break
                except queue_mod.Full:
                    continue
            if not queued:
                # Closing: the batch never reached a runner; close()
                # fails its futures, but the in-flight count must not
                # leak a phantom batch.
                with self._lock:
                    self._inflight[replica] -= 1

    def _runner_loop(self) -> None:
        while not self._stop.is_set():
            try:
                live, replica, seq = self._dispatch.get(timeout=0.05)
            except queue_mod.Empty:
                continue
            try:
                self._run_batch(live, replica, seq)
            finally:
                with self._lock:
                    self._inflight[replica] -= 1

    def inflight(self, replica: int) -> int:
        """Batches formed for ``replica`` and not yet finished — the
        autoscaler's drain barrier (failover retries still count against
        the ORIGINAL replica until the batch resolves, which is the
        conservative direction for a drain)."""
        with self._lock:
            return self._inflight.get(replica, 0)

    @property
    def n_runners(self) -> int:
        with self._lock:
            return len(self._runners)

    def add_runner(self) -> None:
        """Grow the runner pool by one thread (autoscaler scale-up, after
        ReplicaPool.grow appended a replica): widens the dispatch bound
        so the new replica can hold a batch in flight concurrently."""
        with self._lock:
            i = len(self._runners)
            t = threading.Thread(
                target=self._runner_loop, name=f"serve-runner-{i}",
                daemon=True,
            )
            self._runners.append(t)
            if self._started:
                t.start()
        # queue.Queue has no resize API; maxsize is guarded by the
        # queue's OWN mutex (the one put()/get() contend on), not by
        # self._lock — taking both here would order them against the
        # worker loop, which blocks in put() while holding no lock.
        with self._dispatch.mutex:
            # graftcheck: disable=lock-discipline -- maxsize belongs to the queue's own mutex, held by this with-block
            self._dispatch.maxsize += 1
            self._dispatch.not_full.notify()

    def _run_batch(self, live: List[_Request], replica: int, seq: int) -> None:
        if self.chaos is not None:
            stall_ms = self.chaos.slow_replica_at(seq)
            if stall_ms is not None:
                # Chaos: the replica straggles — the batch (and the
                # queue behind it) eats the stall, exactly the tail
                # latency the SLO gate watches.
                if self.obs.enabled:
                    self.obs.event("chaos_slow_replica", seq=seq,
                                   replica=replica, ms=stall_ms)
                time.sleep(stall_ms / 1e3)
        if self.chaos is not None and self.chaos.kill_replica_at(seq):
            # Chaos: the replica dies the instant before its predict —
            # the dispatch already committed to it, so the failure is
            # observed exactly where a real mid-traffic device loss
            # would surface (predict raises ReplicaDead).
            self.pool.kill(replica)
        try:
            with self.obs.span(
                "serve.batch", cat="serve",
                seq=seq, replica=replica, n=len(live),
            ):
                self._resolve_batch(live, replica, seq)
        except ReplicaDead:
            self._failover(live, replica, seq)
        except BaseException as e:  # noqa: BLE001 — forwarded to clients
            self._fail_batch(live, seq, e)

    def _resolve_batch(self, live: List[_Request], replica: int,
                       seq: int) -> None:
        """Predict + resolve, the single dispatch site — _run_batch's
        normal path and _failover's retry both land here. ReplicaDead
        propagates to the caller BEFORE any future resolves (the predict
        raises up front), so a retried batch is still whole."""
        xs = np.stack([r.x for r in live])
        t_exec = time.monotonic()
        ys, _ = self.pool.predict(xs, replica=replica)
        done = time.monotonic()
        if self.admission is not None:
            self.admission.observe_service(
                self.pool.engines[replica].bucket_for(len(live)),
                done - t_exec,
            )
        for i, r in enumerate(live):
            r.future.replica = replica
            r.future.batch_seq = seq
            r.future._resolve(ys[i])
            self.stats.on_complete(done - r.t_submit)
            if self.obs.enabled:
                self.obs.event(
                    "complete", req=id(r.future), seq=seq,
                    replica=replica,
                    latency_ms=1e3 * (done - r.t_submit),
                )
                self.obs.tracer.end_async("request", id(r.future))

    def _fail_batch(self, live: List[_Request], seq: int,
                    e: BaseException) -> None:
        """The historic fail-all contract: every request in the batch
        resolves exactly once, with the error, and is counted failed."""
        self.stats.on_failed(len(live))
        for r in live:
            if not r.future.done():
                r.future._fail(e)
            if self.obs.enabled:
                self.obs.event("failed", req=id(r.future), seq=seq)
                self.obs.tracer.end_async("request", id(r.future))

    def _failover(self, live: List[_Request], dead: int, seq: int) -> None:
        """Replica ``dead`` died with this batch in flight: evict it,
        retry the still-within-deadline requests on a survivor, and
        re-pin a replacement.

        Conservation holds across the detour — every request in ``live``
        resolves exactly once: completed (retry landed), expired (its
        deadline passed before the retry could dispatch), or failed (the
        retry itself failed / no survivor was available)."""
        self.pool.evict(dead)
        if self.obs.enabled:
            self.obs.event("replica_evicted", replica=dead, seq=seq)
        now = time.monotonic()
        retry: List[_Request] = []
        n_expired = 0
        for r in live:
            if r.deadline is not None and now > r.deadline:
                r.future._fail(DeadlineExceeded(
                    f"deadline passed "
                    f"{1e3 * (now - r.deadline):.1f} ms into replica "
                    f"failover"
                ))
                n_expired += 1
                if self.obs.enabled:
                    self.obs.event("expired", req=id(r.future))
                    self.obs.tracer.end_async("request", id(r.future))
            else:
                retry.append(r)
        if n_expired:
            self.stats.on_expired(n_expired)
        respawned = False
        try:
            if retry:
                try:
                    survivor = self.pool.next_replica()
                except ReplicaDead:
                    # Single-replica pool (or total loss): the
                    # replacement IS the survivor.
                    survivor = self.pool.respawn(dead)
                    respawned = True
                    if self.obs.enabled:
                        self.obs.event(
                            "replica_respawned", replica=dead, seq=seq
                        )
                if self.obs.enabled:
                    self.obs.event(
                        "failover", seq=seq, dead=dead,
                        survivor=survivor, n=len(retry),
                        expired=n_expired,
                    )
                self._resolve_batch(retry, survivor, seq)
        except BaseException as e:  # noqa: BLE001 — forwarded to clients
            self._fail_batch(retry, seq, e)
        finally:
            if not respawned:
                self.pool.respawn(dead)
                if self.obs.enabled:
                    self.obs.event(
                        "replica_respawned", replica=dead, seq=seq
                    )


def serve_stack(
    handle,
    cfg,
    *,
    devices=None,
    stats: Optional[ServeStats] = None,
    start: bool = True,
    obs: Optional["obs_lib.Obs"] = None,
    chaos=None,
    admission=None,
    cache_dir=None,
):
    """(pool, batcher) wired from a config.ServeConfig — the one-call
    constructor the CLI, the benchmark and the tests share. ``chaos`` (a
    resilience.chaos.ChaosMonkey) arms kill-replica / slow-replica fault
    injection. ``admission`` overrides the controller instance; by
    default one is built when ``cfg.admission`` is set (the SLO surface
    — serve/admission.py). ``cache_dir`` enables the engines'
    persistent AOT-executable cache (config.NetConfig.aot_cache_dir)."""
    from parallel_cnn_tpu import plan as plan_lib
    from parallel_cnn_tpu.serve.engine import ReplicaPool

    # The serving ExecutionPlan (plan/): eval sharding is replicated
    # single-device, so the plan pins the compile/AOT policy, and its
    # fingerprint keys the engines' on-disk executable cache.
    splan = plan_lib.serve_plan(cfg, cache_dir=cache_dir)
    pool = ReplicaPool(
        handle,
        n_replicas=cfg.n_replicas,
        checkpoint=cfg.checkpoint,
        max_batch=cfg.max_batch,
        devices=devices,
        precompile=cfg.precompile,
        obs=obs,
        cache_dir=cache_dir,
        plan_fingerprint=splan.fingerprint(),
    )
    if admission is None and getattr(cfg, "admission", False):
        from parallel_cnn_tpu.serve.admission import AdmissionController

        admission = AdmissionController(
            slo_ms=cfg.slo_ms,
            queue_depth=cfg.queue_depth,
            obs=obs,
        )
    if stats is None:
        stats = ServeStats(window_s=getattr(cfg, "window_s", 10.0))
    batcher = DynamicBatcher(
        pool,
        max_wait_ms=cfg.max_wait_ms,
        queue_depth=cfg.queue_depth,
        deadline_ms=cfg.deadline_ms,
        stats=stats,
        start=start,
        obs=obs,
        chaos=chaos,
        admission=admission,
    )
    return pool, batcher
