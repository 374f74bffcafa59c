"""The mechanisms of a `bailing_hybrid` model (nn/bailing_hybrid.py) by
the scopes it opens and the KIND of the layer (`arch.layer_types[i]`):
what the readers `kda_*` and `bh_*` group the step's device time by
(benchmark/scope_time.py does the join and the sums;
benchmark/shapes/bailing_hybrid.py counts the work).

    kda_core        l<i>/attn/core of a linear layer: the chunked scan
    kda_conv_gates  l<i>/attn/{conv,gates,gate_norm} of a linear layer: the
                    three short convolutions with their SiLU and L2 norms,
                    the log-decay gate and beta, the head-wise gate and
                    the output's norm — everything of the layer that is
                    neither a projection nor the scan
    mla_core        l<i>/attn/core of a full layer: q k^T, softmax, p v
    experts, route  benchmark/glm_scopes.py's `moe_experts`, `moe_route`
"""

from __future__ import annotations

import re
from typing import Optional

from benchmark import scope_time
from benchmark.shapes import bailing_hybrid as shapes

LINEAR, FULL = shapes.LINEAR, shapes.FULL
AROUND = ("conv", "gates", "gate_norm")
_LAYER = re.compile(r"l(\d+)$")


def mechanism(entry, layer_types) -> Optional[str]:
    parts = entry.scope.split("/")
    if "attn" not in parts:
        return None
    at = _LAYER.match(parts[0])
    if at is None or int(at.group(1)) >= len(layer_types):
        return None
    kind = layer_types[int(at.group(1))]
    if "core" in parts:
        return "kda_core" if kind == LINEAR else "mla_core"
    if kind == LINEAR and any(p in parts for p in AROUND):
        return "kda_conv_gates"
    return None


def ms(run, name: str) -> Optional[float]:
    """ms a step in ops of one mechanism, forward, rematerialised forward
    and backward; None where nothing was read."""
    layer_types = run.ctx.config.get("arch", {}).get("layer_types")
    if not layer_types:
        return None
    got = scope_time.split(
        run, lambda e: name if mechanism(e, layer_types) == name else None,
        (name,))
    return (got.get(name) or None) if got else None


def roofline(run, took_ms: Optional[float], passes) -> Optional[float]:
    """The least time the chip could take for `passes()` over the
    `took_ms` a reader measured, in percent; None where nothing was
    measured (the passes are then never counted)."""
    if run.ctx.peak is None or not took_ms:
        return None
    return 100.0 * shapes.least_seconds(passes(), run.ctx.peak) / (took_ms / 1e3)
