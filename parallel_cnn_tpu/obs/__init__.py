"""Unified observability layer (docs/observability.md).

One :class:`Obs` bundle carries the three signal sinks every subsystem
shares — the span :class:`~parallel_cnn_tpu.obs.trace.Tracer` (Chrome
trace / Perfetto export), the
:class:`~parallel_cnn_tpu.obs.registry.MetricsRegistry`
(Prometheus-text + JSON exposition), and the
:class:`~parallel_cnn_tpu.obs.events.EventJournal` (append-only JSONL
with per-process sequence ids).  Hot paths take an ``obs=None`` keyword
and normalize with ``obs = obs or NOOP``: the default is the zero-cost
no-op bundle, so nothing is paid unless ``ObsConfig`` turned it on.

Spans wrap host-side dispatch only; nothing here ever runs inside a
jitted body (see the ``train.obs_batched_step`` jaxpr-rules entry). The
one thing tracing puts inside jitted bodies is metadata, and it lives
outside this package and is always on: the ``jax.named_scope``s of
``nn/`` and ``train/zoo.py:make_train_step``. :mod:`.programs` reads them
back from a compiled program's text — a catalog from HLO instruction to
layer scope and phase, recorded by ``zoo.train`` when tracing is on and
written by :meth:`Obs.finish` as ``<run>_programs.json`` — because this
runtime's device trace names ops by instruction and carries no
``op_name``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

from parallel_cnn_tpu.obs import compiles, epochs, programs
from parallel_cnn_tpu.obs.events import (
    NOOP_JOURNAL,
    EventJournal,
    NoopJournal,
    conservation,
    merge_journals,
    read_journal,
)
from parallel_cnn_tpu.obs.registry import Counter, Gauge, MetricsRegistry
from parallel_cnn_tpu.obs.trace import (
    NOOP_TRACER,
    NoopTracer,
    Tracer,
    validate_nesting,
)

__all__ = [
    "Obs", "NOOP", "from_config",
    "Tracer", "NoopTracer", "NOOP_TRACER", "validate_nesting",
    "MetricsRegistry", "Counter", "Gauge",
    "EventJournal", "NoopJournal", "NOOP_JOURNAL",
    "read_journal", "merge_journals", "conservation",
    "programs", "compiles", "epochs",
]


class Obs:
    """The bundle threaded through trainer/zoo/serve hot paths."""

    __slots__ = ("tracer", "registry", "journal", "cfg", "enabled",
                 "trace_path", "metrics_path")

    def __init__(self, tracer, registry, journal, cfg=None,
                 enabled: bool = False, trace_path: Optional[str] = None,
                 metrics_path: Optional[str] = None):
        self.tracer = tracer
        self.registry = registry
        self.journal = journal
        self.cfg = cfg
        self.enabled = enabled
        self.trace_path = trace_path
        self.metrics_path = metrics_path

    def span(self, name: str, cat: str = "step", **args: Any):
        return self.tracer.span(name, cat, **args)

    def event(self, kind: str, **fields: Any):
        return self.journal.emit(kind, **fields)

    def finish(self) -> Dict[str, str]:
        """Export every configured artifact; returns {kind: path}."""
        out: Dict[str, str] = {}
        if self.trace_path and self.tracer.enabled:
            # The tracer's own events, and on lanes beside them what the
            # process traced, lowered and compiled since it was made.
            out["trace"] = self.tracer.export(
                self.trace_path,
                compiles.trace_events(self.tracer.pid, since=self.tracer.made),
            )
            # Beside it, what names a device profile's ops (programs.py).
            stem = self.trace_path.removesuffix("_trace.json")
            written = programs.export(f"{stem}_programs.json")
            if written:
                out["programs"] = written
        if self.journal.enabled:
            compiles.detach(self.journal)
            self.journal.close()
            if self.journal.path:
                out["journal"] = self.journal.path
        if self.metrics_path and self.registry is not None:
            out["metrics"] = self.registry.write_json(self.metrics_path)
        return out


NOOP = Obs(NOOP_TRACER, None, NOOP_JOURNAL, cfg=None, enabled=False)


def from_config(cfg, run: str = "run", process_index: int = 0,
                mirror_jax: Optional[bool] = None) -> Obs:
    """Build the live (or no-op) bundle from an ``ObsConfig``.

    ``cfg`` is ``Optional[config.ObsConfig]`` — ``None`` or a disabled
    config returns the shared :data:`NOOP` singleton.  ``run`` names the
    artifacts (``<dir>/<run>_trace.json`` etc.) so several phases of one
    process don't clobber each other.
    """
    if cfg is None or not cfg.enabled:
        return NOOP
    if mirror_jax is None:
        mirror_jax = cfg.jax_annotations
    compiles.install()
    if cfg.trace:
        tracer = Tracer(process_name=f"pcnn:{run}", mirror_jax=mirror_jax)
        journal = EventJournal(
            os.path.join(cfg.dir, f"{run}_journal.jsonl"),
            process_index=process_index,
        )
        compiles.attach(journal)
        trace_path = os.path.join(cfg.dir, f"{run}_trace.json")
    else:
        tracer = NOOP_TRACER
        journal = NOOP_JOURNAL
        trace_path = None
    return Obs(
        tracer, MetricsRegistry(), journal, cfg=cfg, enabled=True,
        trace_path=trace_path, metrics_path=cfg.metrics_json,
    )
