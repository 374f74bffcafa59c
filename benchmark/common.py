"""What every runner and reader shares: the manifest, file lookup by the
names in it, exact percentiles, the compile counter, the device report."""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# Rehearsal cells (tiny shapes, CPU allowed, never listed in the manifest)
# live with the tests; a cell is a rehearsal by where its file lies.
REHEARSAL = os.path.join(ROOT, "tests", "benchmark", "cells")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _find(kind: str, name: str, rehearsal_ok: bool):
    bases = ((BENCH, False), (REHEARSAL, True)) if rehearsal_ok else ((BENCH, False),)
    for base, rehearsal in bases:
        path = os.path.join(base, kind, f"{name}.json")
        if os.path.exists(path):
            return load_json(path), rehearsal
    raise FileNotFoundError(f"no file {kind}/{name}.json")


def find_workload(name: str) -> Dict[str, Any]:
    """The cell's file (config, traffic, chips, why, who), found by name.
    Adds `name` and `rehearsal` (true where the file lies under tests/)."""
    w, rehearsal = _find("workloads", name, True)
    w.update(name=name, rehearsal=rehearsal)
    return w


def find_traffic(name: str, rehearsal: bool) -> Dict[str, Any]:
    """The traffic mix's parameter file; names its `runner`."""
    return _find("traffic", name, rehearsal)[0]


def find_config(name: str, rehearsal: bool) -> Dict[str, Any]:
    return _find("configs", name, rehearsal)[0]


def cell_metrics(man: Dict, cell: str, group: str) -> List[Dict]:
    """The manifest's metrics of one group that this cell reports."""
    return [m for m in man[group]
            if "workloads" not in m or cell in m["workloads"]]


def percentile(sorted_values: Sequence[float], p: float) -> float:
    """Exact nearest-rank percentile of an ascending list: the smallest
    value with at least p percent of the list at or below it."""
    if not sorted_values:
        raise ValueError("percentile of an empty list")
    rank = max(math.ceil(p / 100.0 * len(sorted_values)), 1)
    return sorted_values[rank - 1]


def median(values: Sequence[float]) -> Optional[float]:
    vs = sorted(values)
    if not vs:
        return None
    mid = len(vs) // 2
    return vs[mid] if len(vs) % 2 else 0.5 * (vs[mid - 1] + vs[mid])


class CompileCounter:
    """Counts XLA compile requests (jit cache misses; a persistent-cache
    hit counts too) through jax.monitoring. `start()` opens the window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event: str, duration: float, **kw) -> None:
        if self._on and event == self.EVENT:
            self.count += 1

    def start(self) -> None:
        self._on = True

    def stop(self) -> None:
        self._on = False


def device_report(devices) -> Dict[str, Any]:
    """Platform, kind and count as JAX reports them, and the peak bytes on
    the fullest device. On the TPU `peak_bytes_in_use` counts live arrays
    only; what a running program needs beside them (its temporaries) is
    reserved apart and shows as `peak_bytes_reserved` (ResNet-50's step at
    batch 256: 1.93 GB of arrays, 8.98 GB reserved, against 9.01 GB of
    temporaries in XLA's own memory analysis — my chip run, PR 22). The
    peak is their sum."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak}


def traced_obs():
    """An enabled `obs` bundle whose spans are mirrored into the profiler's
    trace (what a traced run hands the program; untraced runs pass none)."""
    from parallel_cnn_tpu import obs as obs_lib

    return obs_lib.Obs(obs_lib.Tracer(mirror_jax=True),
                       obs_lib.MetricsRegistry(), obs_lib.NOOP_JOURNAL,
                       enabled=True)


def host_spans(obs, lo_us: float, hi_us: float):
    """Durations (seconds) of the program's closed spans by name, split
    into those that began inside [lo_us, hi_us] on the tracer's clock and
    those that began outside it."""
    inside: Dict[str, List[float]] = {}
    outside: Dict[str, List[float]] = {}
    for ev in obs.tracer.events():
        if ev.get("ph") == "X":
            into = inside if lo_us <= ev["ts"] <= hi_us else outside
            into.setdefault(ev["name"], []).append(ev["dur"] / 1e6)
    return inside, outside


def read_trace(trace_dir: Optional[str], notes: Dict[str, Any]):
    """The reduced trace of the newest .xplane.pb under a profiler
    directory (its path goes into `notes`), or None."""
    import glob

    from benchmark import trace_reduce

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))) if trace_dir else []
    if not found:
        return None
    notes["xplane"] = found[-1]
    return trace_reduce.read_xplane(found[-1])


def build_model(config: Dict):
    """The program's own model factory, named in the configuration."""
    import importlib

    fac = config["factory"]
    return getattr(importlib.import_module(fac["module"]), fac["name"])(
        **fac["kwargs"])


def device_ids(tree) -> str:
    """Comma-joined sorted ids of every device that holds a shard of any
    array in the tree (the form the trainer's own epoch record uses)."""
    import jax

    ids = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            ids.update(d.id for d in leaf.sharding.device_set)
    return ",".join(str(i) for i in sorted(ids))
