"""Elastic training runtime: in-flight re-mesh + ZeRO-3 reshard.

PR 1's resilience layer can survive a preemption (checkpoint, stop,
``--resume``); this module makes the run *resize* instead of stopping.
On a preemption-style resize request, a chaos-injected device loss, or a
device add, the ``ElasticController``:

1. **quiesces** the step loop at a microbatch boundary (the trainer polls
   ``pending()`` between optimizer steps; ``resize()`` opens with a
   ``block_until_ready`` so the last dispatched step has fully landed);
2. **snapshots** the training state through ``zoo.zero3_full_view`` — a
   pure reshape/transpose/slice of the resident shard rows, no disk
   round-trip and no collectives. When the lost rank's shards are
   unreachable (deleted buffers raise), it **falls back** to the newest
   loadable sharded checkpoint in the ring
   (``CheckpointRing.restore_latest_sharded``), losing at most the steps
   since the last ring save;
3. **re-meshes** over the surviving topology
   (``parallel.mesh.make_elastic_mesh`` — deterministic survivor order,
   hierarchical when the host axis still divides the world, flat ring
   otherwise);
4. **reshards** params + momentum with ``zoo.zero3_from_view`` for the
   new world size and hands the trainer the new (state, plan, mesh,
   comm) to rebuild its jitted step from — with per-device batch and LR
   adjusted per the configured scaling policy.

Because the full view is world-size independent and shard↔full is
layout-only, a resize that takes zero optimizer steps is **bit-exact**,
and a resized run under the default "global" scaling policy (fixed
global batch + LR) tracks the fixed-mesh loss trajectory to reduction-
order roundoff (≤1e-5, tests/test_elastic.py).

What is preserved across a resize: params, momentum, BatchNorm running
stats, the dynamic loss scale and its counters, the data order (global
batch and shuffle streams don't depend on the mesh). What is not: XLA
executables (the step recompiles for the new mesh), device placement,
and — on the ring-fallback path — the optimizer steps taken since the
last checkpoint. docs/fault_tolerance.md has the state machine.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from parallel_cnn_tpu import obs as obs_lib
from parallel_cnn_tpu.config import CommConfig, ElasticConfig
from parallel_cnn_tpu.resilience import preempt

log = logging.getLogger(__name__)


class ElasticError(RuntimeError):
    """A resize could not complete (no live state AND no loadable ring
    checkpoint) — the run cannot continue on the surviving topology."""


@dataclasses.dataclass
class ResizeEvent:
    """One completed resize, as recorded on ``ElasticController.events``."""

    step: int
    old_world: int
    new_world: int
    old_hosts: int
    new_hosts: int
    source: str  # "schedule" | "chaos" | "signal" | "direct"
    from_ring: bool = False
    seconds: float = 0.0


def _materialize(view) -> Any:
    """Host-side numpy copy of a full view — forces every buffer to be
    read NOW (an unreachable shard raises here, inside the try of the
    snapshot path, not later inside the resharded step) and doubles as
    the ring-fallback restore template."""
    return jax.tree_util.tree_map(np.asarray, view)


class ElasticController:
    """Consumes resize triggers and rebuilds (state, plan, mesh, comm).

    Trigger sources, polled per optimizer step in priority order:

    - the preempt resize channel (``preempt.request_resize(world)`` — the
      scheduler-announcement path);
    - the chaos harness (``ChaosMonkey(resize_delta=(step, ±k))``, CLI
      spec ``resize@STEP:±K`` — seeded device loss/add);
    - the planned schedule (``ElasticConfig.schedule`` "STEP:WORLD,...").

    Targets are clamped to [cfg.min_world, reachable devices]; a clamp is
    journaled on the resize_begin event rather than silently absorbed.
    The controller owns no jitted artifacts — the trainer rebuilds its
    step from what ``resize()`` returns, so the controller stays testable
    without a training loop.
    """

    def __init__(
        self,
        cfg: ElasticConfig,
        *,
        world: int,
        n_hosts: int = 1,
        chaos=None,
        ring=None,
        obs: Optional["obs_lib.Obs"] = None,
        devices: Optional[Sequence] = None,
        exec_plan=None,
    ):
        self.cfg = cfg
        self.world = world
        self.n_hosts = n_hosts
        # The ExecutionPlan this run resolved (plan/). Resizes are
        # expressed as plan derivation: derive_resized(plan, new_world)
        # → make_mesh, so topology decisions live in ONE place and the
        # trainer can key its recompile-once step cache on plan
        # equality. Defaults to an empty plan (derivation only touches
        # the topology fields).
        self.exec_plan = exec_plan
        self.world0 = world  # scaling baseline for "per-device" policy
        self.chaos = chaos
        self.ring = ring
        self.obs = obs if obs is not None else obs_lib.NOOP
        self.devices = list(devices) if devices is not None else None
        self.events: List[ResizeEvent] = []
        self._schedule = list(cfg.plan())
        self._last_source = "direct"
        self._template = None  # numpy full-view for the ring fallback

    # -- scaling policy -------------------------------------------------

    def lr_for(self, base_lr: float) -> float:
        """The LR the rebuilt step should use. "global" keeps the base LR
        (global batch unchanged → same effective step); "per-device"
        scales linearly with the world, following the linear-scaling rule
        for a global batch that grew/shrank with the fleet."""
        if self.cfg.scaling == "per-device":
            return base_lr * self.world / self.world0
        return base_lr

    def global_batch_for(self, base_batch: int) -> int:
        """The global batch for the current world. "global" keeps it
        fixed (per-device batch changes implicitly — the parity mode);
        "per-device" keeps the ORIGINAL per-device batch fixed, so the
        global batch scales with the world."""
        if self.cfg.scaling == "per-device":
            return max(1, base_batch // self.world0) * self.world
        return base_batch

    # -- trigger polling ------------------------------------------------

    def _n_reachable(self) -> int:
        return len(self.devices) if self.devices is not None \
            else len(jax.devices())

    def _clamp(self, world: int) -> int:
        return max(self.cfg.min_world, min(world, self._n_reachable()))

    def pending(self, step: int) -> Optional[int]:
        """The target world size to resize to before optimizer step
        ``step``, or None. Consumes the trigger it reports."""
        requested = None
        if preempt.resize_requested() is not None:
            requested = preempt.clear_resize()
            self._last_source = "signal"
        elif self.chaos is not None:
            delta = self.chaos.resize_at(step)
            if delta is not None:
                requested = self.world + delta
                self._last_source = "chaos"
        if requested is None and self._schedule \
                and step >= self._schedule[0][0]:
            requested = self._schedule.pop(0)[1]
            self._last_source = "schedule"
        if requested is None:
            return None
        target = self._clamp(requested)
        if target != requested:
            log.warning(
                "elastic: resize request to %d clamped to %d "
                "(min_world=%d, reachable=%d)",
                requested, target, self.cfg.min_world, self._n_reachable(),
            )
        if target == self.world:
            log.info(
                "elastic: resize to %d is a no-op at world %d — skipped",
                target, self.world,
            )
            return None
        self._requested = requested
        return target

    # -- the resize itself ----------------------------------------------

    def register_template(self, view) -> None:
        """Seed the ring-fallback restore template from a healthy full
        view (world-size independent, so it never goes stale)."""
        self._template = _materialize(view)

    def _snapshot(self, state, plan) -> Tuple[Any, bool]:
        """(numpy full view, from_ring). Live state first; the checkpoint
        ring when the live shards are unreachable."""
        from parallel_cnn_tpu.train import zoo

        try:
            view = zoo.zero3_full_view(state, plan, n_host=self.n_hosts)
            return _materialize(view), False
        except Exception as e:  # deleted/unreachable buffers, comm loss
            log.warning(
                "elastic: live snapshot failed (%s: %s) — falling back "
                "to the checkpoint ring", type(e).__name__, e,
            )
        if self.ring is None or self._template is None:
            raise ElasticError(
                "resize needs a state snapshot, but the live shards are "
                "unreachable and no checkpoint ring is configured — "
                "train with checkpoint_dir to make device loss survivable"
            )
        restored = self.ring.restore_latest_sharded(self._template)
        if restored is None:
            raise ElasticError(
                "resize needs a state snapshot, but the live shards are "
                "unreachable and no ring checkpoint loads (see the "
                "skipped-file warnings above for per-file rank/world "
                "coordinates)"
            )
        view, _state, _zmeta, path = restored
        log.warning("elastic: resharding from ring checkpoint %s", path)
        return view, True

    def resize(
        self,
        step: int,
        world: int,
        *,
        state,
        plan,
        comm: CommConfig,
        n_hosts: Optional[int] = None,
    ):
        """Reshard for ``world`` devices; (state, plan, mesh, comm).

        ``n_hosts`` pins the new host-axis size (tests exercising
        topology laps like (1,8)→(2,4)); the default keeps the current
        host count while it divides the new world, degrading to a flat
        ring otherwise. The returned comm config has its impl switched to
        match the new topology (ring ↔ hierarchical) with every other
        knob preserved.
        """
        from parallel_cnn_tpu import plan as plan_lib
        from parallel_cnn_tpu.parallel import mesh as mesh_lib
        from parallel_cnn_tpu.train import zoo

        if n_hosts is None:
            n_hosts = self.n_hosts if (
                self.n_hosts > 1 and world % self.n_hosts == 0
            ) else 1
        if world % n_hosts != 0:
            raise ValueError(
                f"elastic world {world} is not divisible by "
                f"n_hosts {n_hosts}"
            )
        t0 = time.perf_counter()
        old_world, old_hosts = self.world, self.n_hosts
        source = self._last_source
        self._last_source = "direct"
        if self.obs.enabled:
            self.obs.event(
                "resize_begin", step=step, old_world=old_world,
                new_world=world, old_hosts=old_hosts, new_hosts=n_hosts,
                requested=getattr(self, "_requested", world),
                source=source,
            )
        with self.obs.span(
            "train.resize", cat="train",
            old_world=old_world, new_world=world,
        ):
            # Quiesce: every dispatched step has landed before we read
            # the resident shards (the microbatch-boundary contract).
            try:
                jax.block_until_ready(state)
            except Exception:
                pass  # unreachable buffers fail in _snapshot, typed
            view, from_ring = self._snapshot(state, plan)
            # The resize IS a plan derivation: the new topology is
            # derive_resized(plan, world) and the mesh comes from THE
            # mesh-construction site (plan.make_mesh), not a local
            # constructor call.
            new_exec_plan = plan_lib.derive_resized(
                self.exec_plan or plan_lib.ExecutionPlan(),
                world, n_hosts=n_hosts,
            )
            mesh = new_exec_plan.make_mesh(devices=self.devices)
            has_host = mesh_lib.HOST_AXIS in mesh.axis_names
            new_comm = dataclasses.replace(
                comm,
                impl="hierarchical" if has_host else "ring",
                hosts=n_hosts if has_host else None,
            )
            new_hosts = n_hosts if has_host else 1
            new_state, new_plan = zoo.zero3_from_view(
                view, n_data=world // new_hosts,
                bucket_bytes=comm.bucket_bytes, n_host=new_hosts,
            )
        self.world, self.n_hosts = world, new_hosts
        self.exec_plan = new_exec_plan
        self._template = view  # already host-side numpy
        ev = ResizeEvent(
            step=step, old_world=old_world, new_world=world,
            old_hosts=old_hosts, new_hosts=new_hosts, source=source,
            from_ring=from_ring, seconds=time.perf_counter() - t0,
        )
        self.events.append(ev)
        if self.obs.enabled:
            self.obs.event(
                "resize_done", step=step, old_world=old_world,
                new_world=world, old_hosts=old_hosts,
                new_hosts=new_hosts, from_ring=from_ring,
                seconds=round(ev.seconds, 6), source=source,
            )
        log.warning(
            "elastic: resized %dx%d -> %dx%d at step %d (%s%s, %.3fs)",
            old_hosts, old_world // max(old_hosts, 1), new_hosts,
            world // new_hosts, step, source,
            ", from ring" if from_ring else "", ev.seconds,
        )
        return new_state, new_plan, mesh, new_comm
