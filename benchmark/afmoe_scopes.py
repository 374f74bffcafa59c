"""The attention cores of an `afmoe` model (nn/afmoe.py) by the KIND of
their layer: the step's device time in `l<i>/attn/core`, split by what the
configuration's `arch.layer_types[i]` says layer i is, and each kind's
share of its roofline. What the readers `win_attn_core_*` and
`full_attn_core_*` share (benchmark/scope_time.py does the join and the
sums; benchmark/shapes/afmoe.py counts the work)."""

from __future__ import annotations

import re
from typing import Optional

from benchmark import scope_time
from benchmark.shapes import afmoe as shapes

SLIDING, FULL = shapes.SLIDING, shapes.FULL
_LAYER = re.compile(r"l(\d+)$")


def core_kind(entry, layer_types) -> Optional[str]:
    """The kind of the layer whose attention core a catalog entry belongs
    to, or None for any other entry."""
    parts = entry.scope.split("/")
    if "core" not in parts or "attn" not in parts:
        return None
    at = _LAYER.match(parts[0])
    if at is None or int(at.group(1)) >= len(layer_types):
        return None
    return layer_types[int(at.group(1))]


def core_ms(run, kind: str) -> Optional[float]:
    """ms a step in the attention cores of the layers of `kind`, forward
    and backward; None where nothing was read."""
    layer_types = run.ctx.config.get("arch", {}).get("layer_types")
    if not layer_types:
        return None
    got = scope_time.split(
        run, lambda e: kind if core_kind(e, layer_types) == kind else None,
        (kind,))
    return (got.get(kind) or None) if got else None


def core_roofline(run, kind: str) -> Optional[float]:
    """The least time the chip could take for one step's `q k^T` and `p v`
    over the pairs the layers of `kind` ALLOW, forward and backward
    (benchmark/shapes/afmoe.py:attention_core_passes), over the time
    measured in them, in percent."""
    if run.ctx.peak is None:
        return None
    took_ms = core_ms(run, kind)
    if not took_ms:
        return None
    passes = [p for p in shapes.attention_core_passes(
        run.ctx.config, run.counters["batch_per_chip"])
        if p["layer_kind"] == kind]
    return 100.0 * shapes.least_seconds(passes, run.ctx.peak) / (took_ms / 1e3)
