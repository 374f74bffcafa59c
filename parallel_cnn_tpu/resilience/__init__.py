"""Fault-tolerant training runtime (SURVEY.md §5's reliability gap).

The reference is one-shot and fragile: a NaN trains forever on a dead
model, a kill loses the run, a flaky substrate call is fatal. This
package makes failure a handled event across six axes:

- ``sentinel``  — jitted loss/grad/param finiteness checks with a
                  configured policy (raise / skip / rollback);
- ``rollback``  — last-good checkpoint ring + bounded auto-rollback with
                  optional LR backoff;
- ``preempt``   — SIGTERM/SIGINT → flush a final atomic checkpoint and
                  stop at the next epoch boundary (pairs with --resume);
- ``retry``     — deterministic jittered exponential backoff;
- ``elastic``   — in-flight re-mesh + ZeRO-3 reshard on preemption
                  resize requests, chaos device loss, or device add: the
                  run continues on the surviving world instead of dying
                  (docs/fault_tolerance.md has the state machine);
- ``chaos``     — the fault-injection harness that proves every one of
                  the recovery paths end-to-end (tests/test_resilience.py,
                  tests/test_elastic.py).

Policy knobs live in config.ResilienceConfig and config.ElasticConfig;
the CLI exposes them as --sentinel / --max-rollbacks / --lr-backoff /
--sentinel-every / --keep-checkpoints / --chaos / --elastic*.
"""

from parallel_cnn_tpu.resilience.chaos import ChaosMonkey  # noqa: F401
from parallel_cnn_tpu.resilience.elastic import (  # noqa: F401
    ElasticController,
    ElasticError,
    ResizeEvent,
)
from parallel_cnn_tpu.resilience.preempt import PreemptionGuard  # noqa: F401
from parallel_cnn_tpu.resilience.retry import (  # noqa: F401
    RetryPolicy,
    retry_call,
)
from parallel_cnn_tpu.resilience.rollback import (  # noqa: F401
    CheckpointRing,
    RollbackController,
)
from parallel_cnn_tpu.resilience.sentinel import (  # noqa: F401
    DivergenceError,
    RetriesExhaustedError,
    Sentinel,
    Verdict,
    tree_all_finite,
)
