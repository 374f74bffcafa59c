"""Inference engine: checkpoint → AOT-compiled per-bucket predict.

Design (tentpole of the serve/ subsystem):

- **Restore** goes through train/checkpoint.load_params with the handle's
  fresh init as the leaf-validated template; zoo checkpoints (full
  ZooState) restore params + BN running stats and IGNORE the optimizer
  momentum (``opt_state={}`` contributes no leaves — see load_params).
- **BN folds at compile time**: the engine closes its predict function
  over the params/model_state arrays, so inside the traced graph they are
  constants — XLA constant-folds the eval-mode BatchNorm's
  ``rsqrt(var+eps)*scale`` per-channel fold (and everything else that
  depends only on weights) once per bucket, instead of recomputing it on
  every request.
- **AOT per shape bucket**: requests pad into the nearest power-of-two
  batch bucket (1, 2, 4, …, max_batch) and each bucket is compiled ONCE
  via ``jax.jit(...).lower(...).compile()``. Steady-state requests never
  trigger a trace: a new shape can only be a new bucket, and with
  ``precompile()`` not even that. The padding cost is bounded — a bucket
  is at most 2× its smallest occupant, so padded FLOPs are < 2× useful
  FLOPs worst-case (docs/serving.md for the amortized math).
- **Device pinning**: every executable is lowered for one explicit
  device, so ReplicaPool can pin n engine copies round-robin across local
  devices and run independent batches concurrently.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from parallel_cnn_tpu import obs as obs_lib


@dataclasses.dataclass
class EngineStats:
    """AOT compile-cache counters (tests pin the hit/miss accounting).

    ``aot_hits`` counts in-memory executable reuse on the predict path;
    the ``aot_cache_*`` trio counts the persistent on-disk tier
    (hit = executable deserialized instead of compiled, miss = no entry
    on disk, corrupt = an entry existed but was torn / bit-rotted /
    fingerprint-mismatched and fell back to recompile). All mutations
    happen under the owning Engine's lock."""

    aot_compiles: int = 0
    aot_hits: int = 0
    predicts: int = 0
    compile_seconds: Dict[int, float] = dataclasses.field(default_factory=dict)
    aot_cache_hits: int = 0
    aot_cache_misses: int = 0
    aot_cache_corrupt: int = 0


class AotCacheWarning(UserWarning):
    """A persistent AOT-cache entry could not be used — torn write, byte
    corruption, or a jax/XLA/weights fingerprint mismatch. The engine
    recompiles and overwrites the entry; this warning is the typed
    signal of the degraded path (same contract as checkpoint.restore's
    typed ValueError: loud, specific, never a crash)."""


class AotCacheError(RuntimeError):
    """Internal: one on-disk AOT cache entry is unusable (the message
    says why). Callers catch this, warn :class:`AotCacheWarning`, and
    recompile — it never escapes the engine."""


#: On-disk entry magic; bump the suffix when the layout changes so an
#: old-layout entry reads as a typed mismatch, not a pickle crash.
_AOT_MAGIC = b"PCNN-AOT1\n"


class ReplicaDead(RuntimeError):
    """A replica is gone — killed by chaos (``kill-replica@SEQ``) or
    evicted after a real device failure. Carries the replica index so the
    batcher's failover path can evict/respawn exactly the dead one and
    retry the in-flight batch on a survivor."""

    def __init__(self, replica: int, message: str = ""):
        super().__init__(message or f"replica {replica} is dead")
        self.replica = replica


def load_or_init(handle, checkpoint: Optional[str] = None, seed: int = 0):
    """(params, model_state) for a handle — restored from a checkpoint
    when given, else fresh-initialized from ``seed``.

    Accepts both checkpoint dialects: a bare params pytree (the
    reference-parity LeNet path) and a full zoo ZooState (params + BN
    stats + optimizer state; the optimizer leaves are ignored — an
    inference engine must not need to reconstruct the training-time
    optimizer just to read the weights)."""
    import jax

    params, model_state = handle.init(jax.random.key(seed))
    if checkpoint is None:
        return params, model_state
    from parallel_cnn_tpu.train import checkpoint as ckpt_lib

    from parallel_cnn_tpu.train.zoo import ZooState

    if jax.tree_util.tree_leaves(model_state):
        # Stateful model (BN running stats): only the ZooState dialect
        # can carry the state, so there is nothing to guess.
        template = ZooState(params, model_state, {})
        loaded = ckpt_lib.load_params(checkpoint, template)
        return loaded.params, loaded.model_state
    # Stateless model: the file may be a bare params pytree (the lenet
    # parity trainer's dialect) OR a full ZooState whose model_state is
    # empty (zoo.train always wraps). Key layout disambiguates — try
    # bare first, fall back to the wrapped template on a leaf-set miss.
    try:
        return ckpt_lib.load_params(checkpoint, params), model_state
    except ValueError as bare_err:
        try:
            loaded = ckpt_lib.load_params(
                checkpoint, ZooState(params, model_state, {})
            )
        except ValueError:
            raise bare_err from None
        return loaded.params, loaded.model_state


def params_digest(params: Any, model_state: Any) -> str:
    """Content hash of the weights an executable was compiled against.

    The engine closes predict over the params/model_state arrays, so the
    compiled executable *is* a function of their values — a persistent
    cache entry is only valid for the exact weights it was built from
    (the hot-swap path depends on this: new checkpoint → new digest →
    stale entries read as fingerprint mismatches, never as silently
    wrong answers)."""
    import jax

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves((params, model_state)):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def bucket_for(n: int, max_batch: int) -> int:
    """Smallest power-of-two bucket holding n requests."""
    if n < 1:
        raise ValueError(f"need at least one request, got {n}")
    b = 1 << (n - 1).bit_length()
    if b > max_batch:
        raise ValueError(
            f"batch of {n} exceeds max_batch={max_batch}; split upstream"
        )
    return b


class Engine:
    """Single-replica engine: pad → AOT executable → unpad.

    Thread-safe: the compile cache is guarded, and concurrent predict()
    calls on already-compiled buckets go straight to the executable
    (jax dispatch is thread-safe).
    """

    def __init__(
        self,
        handle,
        *,
        params: Any = None,
        model_state: Any = None,
        checkpoint: Optional[str] = None,
        max_batch: int = 64,
        device=None,
        seed: int = 0,
        precompile: bool = False,
        obs: Optional["obs_lib.Obs"] = None,
        cache_dir: Optional[str] = None,
        plan_fingerprint: Optional[str] = None,
    ):
        import jax

        if max_batch < 1 or (max_batch & (max_batch - 1)):
            raise ValueError(
                f"max_batch must be a power of two >= 1, got {max_batch}"
            )
        self.handle = handle
        self.max_batch = max_batch
        self.obs = obs if obs is not None else obs_lib.NOOP
        self.device = device if device is not None else jax.devices()[0]
        if params is None:
            params, model_state = load_or_init(handle, checkpoint, seed)
        # Pin the weights to this replica's device once; the closures
        # below capture the pinned copies as trace-time constants.
        self._params = jax.device_put(params, self.device)
        self._state = jax.device_put(
            model_state if model_state is not None else {}, self.device
        )
        self.stats = EngineStats()
        self._exec: Dict[int, Any] = {}
        self._lock = threading.Lock()
        # Persistent on-disk AOT-executable tier: a respawned / grown /
        # cold-started replica deserializes its per-bucket executables
        # instead of recompiling. The fingerprint pins everything the
        # executable is a function of — an entry that does not match
        # EXACTLY falls back to recompile with a typed warning.
        self.cache_dir = cache_dir
        self.plan_fingerprint = plan_fingerprint
        self._cache_ok = cache_dir is not None
        if self._cache_ok:
            os.makedirs(cache_dir, exist_ok=True)
            self._fingerprint = {
                # The resolved ExecutionPlan's content fingerprint
                # (plan/): serving under a different plan (AOT policy,
                # eval sharding) must not reuse another plan's
                # executables.
                "plan": plan_fingerprint or "",
                "jax": jax.__version__,
                "backend": getattr(
                    getattr(self.device, "client", None),
                    "platform_version", "?",
                ),
                "platform": self.device.platform,
                "device_kind": getattr(self.device, "device_kind", "?"),
                "device": int(self.device.id),
                "model": handle.name,
                "in_shape": list(handle.in_shape),
                "params": params_digest(self._params, self._state),
            }
        if precompile:
            self.precompile()

    @property
    def buckets(self) -> List[int]:
        """The bucket ladder: 1, 2, 4, …, max_batch."""
        return [1 << i for i in range(self.max_batch.bit_length())]

    def bucket_for(self, n: int) -> int:
        return bucket_for(n, self.max_batch)

    def _compile(self, bucket: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        params, state, handle = self._params, self._state, self.handle

        def predict(x):
            return handle.forward(params, state, x)

        sds = jax.ShapeDtypeStruct(
            (bucket, *handle.in_shape), jnp.float32,
            sharding=SingleDeviceSharding(self.device),
        )
        t0 = time.perf_counter()
        with self.obs.span("serve.aot_compile", cat="serve", bucket=bucket):
            compiled = jax.jit(predict).lower(sds).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats.compile_seconds[bucket] = dt
        if self.obs.enabled:
            self.obs.event("aot_compile", bucket=bucket, seconds=dt)
        return compiled

    # -- persistent on-disk executable tier -----------------------------

    def _cache_path(self, bucket: int) -> str:
        """One entry per (model, bucket, device slot). The full
        fingerprint lives in the entry header, not the name — so a jax
        upgrade, weight change (hot-swap), or platform move reads as a
        *typed mismatch* that recompiles and overwrites in place,
        instead of silently orphaning stale files."""
        return os.path.join(
            self.cache_dir,
            f"{self.handle.name}-b{bucket}-d{self.device.id}.aotx",
        )

    def _cache_read(self, bucket: int):
        """Deserialize one entry; None on a clean miss (no file), raises
        AotCacheError on a torn / corrupt / mismatched entry."""
        path = self._cache_path(bucket)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except FileNotFoundError:
            return None
        except OSError as e:
            raise AotCacheError(f"unreadable cache entry {path}: {e}")
        if len(blob) < len(_AOT_MAGIC) + 8 or not blob.startswith(_AOT_MAGIC):
            raise AotCacheError(f"bad magic / torn header in {path}")
        off = len(_AOT_MAGIC)
        hlen = int.from_bytes(blob[off:off + 8], "big")
        off += 8
        if len(blob) < off + hlen:
            raise AotCacheError(f"torn header in {path}")
        try:
            header = json.loads(blob[off:off + hlen])
        except ValueError as e:
            raise AotCacheError(f"corrupt header in {path}: {e}")
        fp = dict(self._fingerprint, bucket=bucket)
        if header.get("fingerprint") != fp:
            raise AotCacheError(
                f"fingerprint mismatch in {path} (stale jax/XLA toolchain, "
                f"different device, or different weights)"
            )
        payload = blob[off + hlen:]
        if len(payload) != header.get("nbytes"):
            raise AotCacheError(
                f"torn payload in {path}: {len(payload)} != "
                f"{header.get('nbytes')} bytes"
            )
        if hashlib.sha256(payload).hexdigest() != header.get("sha256"):
            raise AotCacheError(f"payload checksum mismatch in {path}")
        from jax.experimental import serialize_executable as se

        try:
            raw, in_tree, out_tree = pickle.loads(payload)
            # Load for THIS replica's device only: the default is every
            # local device, which is wrong on any host with more than
            # one chip.
            return se.deserialize_and_load(
                raw, in_tree, out_tree, execution_devices=[self.device]
            )
        except Exception as e:  # noqa: BLE001 — any load failure degrades
            raise AotCacheError(f"undeserializable entry {path}: {e}")

    def _cache_load(self, bucket: int):
        """The accounting wrapper around ``_cache_read``: returns the
        executable or None, counting hit / miss / corrupt and emitting
        the matching journal event. Corruption warns AotCacheWarning —
        the caller recompiles."""
        try:
            ex = self._cache_read(bucket)
        except AotCacheError as e:
            warnings.warn(
                f"AOT cache entry unusable, recompiling bucket {bucket}: "
                f"{e}",
                AotCacheWarning,
                stacklevel=3,
            )
            with self._lock:
                self.stats.aot_cache_corrupt += 1
            if self.obs.enabled:
                self.obs.event("aot_cache_corrupt", bucket=bucket,
                               reason=str(e))
            return None
        with self._lock:
            if ex is not None:
                self.stats.aot_cache_hits += 1
            else:
                self.stats.aot_cache_misses += 1
        if self.obs.enabled:
            self.obs.event(
                "aot_cache_hit" if ex is not None else "aot_cache_miss",
                bucket=bucket,
            )
        return ex

    def _cache_store(self, bucket: int, compiled) -> None:
        """Serialize one executable atomically (tmp + rename, same torn-
        write discipline as checkpoint.save). A backend that cannot
        serialize disables the cache for this engine with one warning."""
        from jax.experimental import serialize_executable as se

        try:
            payload = pickle.dumps(se.serialize(compiled))
        except Exception as e:  # noqa: BLE001 — backend-dependent support
            with self._lock:
                self._cache_ok = False
            warnings.warn(
                f"AOT executable serialization unsupported on this "
                f"backend; persistent cache disabled: {e}",
                AotCacheWarning,
                stacklevel=3,
            )
            return
        header = json.dumps({
            "fingerprint": dict(self._fingerprint, bucket=bucket),
            "nbytes": len(payload),
            "sha256": hashlib.sha256(payload).hexdigest(),
        }).encode()
        path = self._cache_path(bucket)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(_AOT_MAGIC)
            f.write(len(header).to_bytes(8, "big"))
            f.write(header)
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def _obtain(self, bucket: int):
        """Load-or-compile one bucket (not yet in the memory map).
        Returns (executable, from_disk_cache)."""
        if self._cache_ok:
            ex = self._cache_load(bucket)
            if ex is not None:
                return ex, True
        ex = self._compile(bucket)
        if self._cache_ok:
            self._cache_store(bucket, ex)
        return ex, False

    def _executable(self, bucket: int):
        with self._lock:
            ex = self._exec.get(bucket)
            if ex is not None:
                self.stats.aot_hits += 1
                return ex
        # Compile outside the lock (minutes on big models — don't block
        # other buckets), then publish; a racing double-compile is
        # harmless and keeps the first one.
        ex, from_disk = self._obtain(bucket)
        with self._lock:
            if bucket not in self._exec:
                self._exec[bucket] = ex
                if not from_disk:
                    self.stats.aot_compiles += 1
            else:
                ex = self._exec[bucket]
            return ex

    def precompile(self) -> Dict[int, float]:
        """Compile every bucket now; returns {bucket: compile seconds}.
        Idempotent — already-cached buckets are skipped (not counted as
        hits: only predict-path lookups feed the hit counter). With a
        persistent cache attached, buckets deserialized from disk count
        as cache hits, not compiles — a warm cold start compiles
        nothing (the restart-to-first-response win the supervisor's
        crash-fast restart depends on)."""
        for b in self.buckets:
            with self._lock:
                if b in self._exec:
                    continue
            ex, from_disk = self._obtain(b)
            with self._lock:
                if b not in self._exec:
                    self._exec[b] = ex
                    if not from_disk:
                        self.stats.aot_compiles += 1
        return dict(self.stats.compile_seconds)

    def predict(self, x) -> np.ndarray:
        """(n, *in_shape) float32 → (n, n_outputs) float32.

        Pads to the nearest bucket, runs the bucket's AOT executable on
        this engine's device, and slices the padding back off. The padded
        rows run through the model and are discarded — zeros are safe
        because no eval-mode op in the registered models mixes
        information across the batch dim (BN uses running stats)."""
        import jax

        x = np.asarray(x, dtype=np.float32)
        if x.shape[1:] != tuple(self.handle.in_shape):
            raise ValueError(
                f"expected (n, {', '.join(map(str, self.handle.in_shape))}), "
                f"got {x.shape}"
            )
        n = x.shape[0]
        bucket = self.bucket_for(n)
        if n < bucket:
            pad = np.zeros((bucket - n, *x.shape[1:]), x.dtype)
            x = np.concatenate([x, pad], axis=0)
        ex = self._executable(bucket)
        y = ex(jax.device_put(x, self.device))
        with self._lock:
            self.stats.predicts += 1
        return np.asarray(y)[:n]


class ReplicaPool:
    """n_replicas engine copies pinned round-robin across local devices.

    Weights are restored/initialized ONCE on host and re-pinned per
    replica; each engine owns its per-device AOT executables, so
    independent batches dispatched to different replicas run genuinely
    concurrently (no shared compile cache, no shared device queue).
    Replica selection (`next_replica`) is a deterministic round-robin —
    tests replay it exactly.

    Failure-aware: ``kill``/``evict`` mark a replica dead (its predict
    raises ReplicaDead, round-robin skips it), ``respawn`` re-pins a
    fresh Engine from the host-side weight copies the pool keeps for
    exactly this purpose. The batcher's failover path drives the
    evict → retry-on-survivor → respawn sequence (chaos
    ``kill-replica@SEQ`` is the test harness for it).

    Elastic (serve/autoscaler.py drives these): ``grow`` adds serving
    capacity — it revives a dead slot via the respawn path when one
    exists, else appends a fresh pinned Engine; ``drain`` makes a
    replica unroutable while leaving it alive so in-flight batches
    complete; ``retire`` then frees the drained slot (a later ``grow``
    reuses it). Slot indices are stable for the pool's lifetime.
    """

    def __init__(
        self,
        handle,
        *,
        n_replicas: int = 1,
        checkpoint: Optional[str] = None,
        max_batch: int = 64,
        devices=None,
        seed: int = 0,
        precompile: bool = False,
        obs: Optional["obs_lib.Obs"] = None,
        cache_dir: Optional[str] = None,
        plan_fingerprint: Optional[str] = None,
    ):
        import jax

        if n_replicas < 1:
            raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
        devices = list(devices) if devices is not None else jax.devices()
        params, model_state = load_or_init(handle, checkpoint, seed)
        # Kept host-side for respawn: a replacement replica re-pins these
        # (the dead replica's device copies are unreachable by definition).
        self._params = params
        self._model_state = model_state
        self.devices = devices
        self._precompile = precompile
        self.obs = obs
        self.cache_dir = cache_dir
        self.plan_fingerprint = plan_fingerprint
        self.engines = [
            Engine(
                handle,
                params=params,
                model_state=model_state,
                max_batch=max_batch,
                device=devices[i % len(devices)],
                precompile=precompile,
                obs=obs,
                cache_dir=cache_dir,
                plan_fingerprint=plan_fingerprint,
            )
            for i in range(n_replicas)
        ]
        self.handle = handle
        self.max_batch = max_batch
        self._rr = 0
        self._alive = [True] * n_replicas
        self._draining = [False] * n_replicas
        self._lock = threading.Lock()

    @property
    def n_replicas(self) -> int:
        return len(self.engines)

    def alive(self) -> List[int]:
        """Indices of live replicas (draining ones included — they are
        still serving their in-flight batches)."""
        with self._lock:
            return [i for i, a in enumerate(self._alive) if a]

    def routable(self) -> List[int]:
        """Indices round-robin will hand out: alive and not draining —
        the pool's effective serving capacity (the autoscaler's sizing
        input)."""
        with self._lock:
            return [
                i for i, a in enumerate(self._alive)
                if a and not self._draining[i]
            ]

    def kill(self, i: int) -> None:
        """Mark replica ``i`` dead: its predict raises ReplicaDead and
        round-robin skips it until ``respawn``. The chaos injection point
        (``kill-replica@SEQ``) — and what ``evict`` aliases after a real
        failure."""
        with self._lock:
            self._alive[i] = False
            self._draining[i] = False

    # Eviction after an observed failure is the same state change as a
    # chaos kill — one implementation, two call sites with different
    # intents (inject vs respond).
    evict = kill

    def respawn(self, i: int, device=None) -> int:
        """Re-pin a replacement for replica ``i`` from the pool's
        host-side weights; returns ``i`` (now live again).

        ``device`` overrides the pin (default: the slot's original
        ``devices[i % len(devices)]`` assignment — on a CPU/chaos run the
        device object is still healthy; a real device loss passes the
        replacement device here). The fresh Engine has an empty AOT
        cache: buckets recompile lazily on first use (or eagerly when the
        pool was built with ``precompile=True``)."""
        with self._lock:
            params, model_state = self._params, self._model_state
        eng = Engine(
            self.handle,
            params=params,
            model_state=model_state,
            max_batch=self.max_batch,
            device=device if device is not None
            else self.devices[i % len(self.devices)],
            precompile=self._precompile,
            obs=self.obs,
            cache_dir=self.cache_dir,
            plan_fingerprint=self.plan_fingerprint,
        )
        with self._lock:
            self.engines[i] = eng
            self._alive[i] = True
            self._draining[i] = False
        return i

    def grow(self, device=None) -> int:
        """Add one serving replica; returns its slot index.

        A dead slot (killed/retired and never respawned) is revived via
        the respawn path — same machinery as failover recovery. With no
        free slot, a fresh Engine is appended, pinned to the next device
        in the round-robin placement (or ``device``). The Engine builds
        OUTSIDE the pool lock (compiles can take a while) and publishes
        atomically; existing slot indices never move."""
        with self._lock:
            free = [i for i, a in enumerate(self._alive) if not a]
        if free:
            return self.respawn(free[0], device=device)
        with self._lock:
            params, model_state = self._params, self._model_state
        eng = Engine(
            self.handle,
            params=params,
            model_state=model_state,
            max_batch=self.max_batch,
            device=device if device is not None
            else self.devices[len(self.engines) % len(self.devices)],
            precompile=self._precompile,
            obs=self.obs,
            cache_dir=self.cache_dir,
            plan_fingerprint=self.plan_fingerprint,
        )
        with self._lock:
            self.engines.append(eng)
            self._alive.append(True)
            self._draining.append(False)
            return len(self.engines) - 1

    def set_weights(self, params: Any, model_state: Any = None) -> None:
        """Swap the pool's host-side weight copies: every replica built
        FROM NOW ON (grow / respawn) serves the new weights; existing
        replicas keep serving the old ones until retired. This is the
        hot-swap primitive (serve/supervisor.py drives the rolling
        grow-new → drain-old → retire sequence around it) — deliberately
        NOT an in-place mutation of live engines, whose executables
        close over the old arrays."""
        with self._lock:
            self._params = params
            self._model_state = model_state if model_state is not None else {}

    def drain(self, i: int) -> None:
        """Make replica ``i`` unroutable while leaving it alive: no new
        batch is pinned to it, but batches already dispatched to it
        still execute. The scale-down half-step — ``retire`` completes
        it once the caller has seen the in-flight count hit zero."""
        with self._lock:
            self._draining[i] = True

    def undrain(self, i: int) -> None:
        """Abort a drain: return a still-alive replica to rotation (the
        hot-swap stuck-drain escape hatch — a swap that can't empty a
        replica's in-flight queue must put it back, not kill it)."""
        with self._lock:
            if self._alive[i]:
                self._draining[i] = False

    def retire(self, i: int) -> None:
        """Free a drained slot: the replica is gone (predict raises
        ReplicaDead) and the slot is available for a future ``grow``."""
        with self._lock:
            self._alive[i] = False
            self._draining[i] = False

    def next_replica(self) -> int:
        """Deterministic round-robin over ROUTABLE replicas (dead and
        draining slots are skipped without consuming a turn for the
        survivors)."""
        with self._lock:
            for _ in range(len(self.engines)):
                i = self._rr
                self._rr = (self._rr + 1) % len(self.engines)
                if self._alive[i] and not self._draining[i]:
                    return i
        raise ReplicaDead(-1, "no live replicas in the pool")

    def precompile(self) -> Dict[int, float]:
        out: Dict[int, float] = {}
        for e in self.engines:
            out.update(e.precompile())
        return out

    def predict(self, x, replica: Optional[int] = None) -> Tuple[np.ndarray, int]:
        """Run one batch on a replica (round-robin unless pinned).
        Returns (outputs, replica index) so callers can audit placement.
        A pinned dead replica raises ReplicaDead — the batcher failover
        trigger."""
        i = self.next_replica() if replica is None else replica
        with self._lock:
            if not self._alive[i]:
                raise ReplicaDead(i)
        return self.engines[i].predict(x), i
