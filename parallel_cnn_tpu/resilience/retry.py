"""Deterministic retry/backoff.

The reference treats every substrate call as infallible: `MPI_Init` either
works or the job dies (MPI/Main.cpp:44), a failed data read returns an
error code that main() ignores. Real long-running jobs see transient
failures — a coordinator that isn't up yet, an NFS blip during a native
build. This module gives those call sites one disciplined shape:

- ``retry_call`` — bounded, capped exponential backoff with *seeded*
  jitter: the delay sequence is a pure function of the policy, so tests
  (and post-mortems) can replay it exactly. No infinite retry loops by
  construction — attempts is a hard bound.

There is deliberately no "fall back to another implementation" wrapper:
a kernel that fails to compile must fail the run (a Pallas request that
quietly trains on XLA and exits 0 hides the device from the operator).

Pure stdlib on purpose: imported by data/native.py and parallel/mesh.py
before/without JAX.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import time
from typing import Callable, Iterator, Tuple, Type

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff with deterministic (seeded) jitter.

    The k-th delay is ``min(base_delay * multiplier**k, max_delay)``
    scaled by a jitter factor drawn uniformly from
    ``[1 - jitter, 1 + jitter]`` using ``random.Random(seed)`` — the same
    policy always produces the same delay sequence.
    """

    attempts: int = 3
    base_delay: float = 0.5
    max_delay: float = 30.0
    multiplier: float = 2.0
    jitter: float = 0.5
    seed: int = 0

    def __post_init__(self):
        if self.attempts < 1:
            raise ValueError(f"attempts must be >= 1, got {self.attempts}")
        if self.base_delay < 0 or self.max_delay < 0:
            raise ValueError("delays must be non-negative")
        if not 0.0 <= self.jitter < 1.0:
            raise ValueError(f"jitter must be in [0, 1), got {self.jitter}")
        if self.multiplier < 1.0:
            raise ValueError("multiplier must be >= 1")

    def delays(self) -> Iterator[float]:
        """The (attempts - 1) sleep durations between attempts."""
        rng = random.Random(self.seed)
        for k in range(self.attempts - 1):
            d = min(self.base_delay * self.multiplier**k, self.max_delay)
            yield d * (1.0 + self.jitter * (2.0 * rng.random() - 1.0))

    def decorrelated(self, rank: int = 0) -> "RetryPolicy":
        """Per-rank decorrelation of the SAME policy envelope.

        N workers recovering from one straggler-induced timeout all build
        the identical policy, so plain ``delays()`` has them reconnect in
        lockstep and re-stampede the coordinator.  This derives a policy
        whose jitter stream is seeded by ``(seed, rank)`` — deterministic
        per worker (replayable), decorrelated across workers (no thundering
        herd).  The backoff *envelope* — base, multiplier, and above all
        the ``max_delay`` cap — is unchanged; only the jitter draw differs.
        """
        if rank < 0:
            raise ValueError(f"rank must be >= 0, got {rank}")
        # Integer fold of (seed, rank) — stable across processes and
        # Python versions (no reliance on object hashing).
        derived = random.Random(self.seed * 1_000_003 + rank).getrandbits(32)
        return dataclasses.replace(self, seed=derived)


def retry_call(
    fn: Callable,
    *args,
    policy: RetryPolicy | None = None,
    retry_on: Tuple[Type[BaseException], ...] = (Exception,),
    sleep: Callable[[float], None] = time.sleep,
    describe: str | None = None,
    **kwargs,
):
    """Call ``fn(*args, **kwargs)``, retrying ``retry_on`` failures.

    Bounded by ``policy.attempts``; the final failure propagates
    unchanged. Pass ``sleep`` to intercept the backoff in tests.
    """
    policy = policy or RetryPolicy()
    delays = list(policy.delays())
    name = describe or getattr(fn, "__name__", repr(fn))
    for attempt in range(policy.attempts):
        try:
            return fn(*args, **kwargs)
        except retry_on as e:
            if attempt == policy.attempts - 1:
                raise
            d = delays[attempt]
            log.warning(
                "%s failed (attempt %d/%d, %s: %s); retrying in %.2fs",
                name, attempt + 1, policy.attempts, type(e).__name__, e, d,
            )
            sleep(d)
