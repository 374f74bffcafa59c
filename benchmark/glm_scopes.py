"""Which mechanism of a `glm_moe` model (nn/glm_moe.py) an entry of the
program's catalog belongs to, by the scopes the model opens: what the
readers `attn_core_*`, `moe_*` and `mtp_device_ms` group the step's
device time by (benchmark/scope_time.py does the join and the sums).

    attn_core    .../attn/core      q k^T, mask, softmax, p v
    moe_experts  .../moe/experts    the grouped matmuls over the held
                                    experts and the silu * up between them
    moe_route    .../moe/{route,dispatch,combine}   scores and top-k, the
                                    sort into the row buffer and both gathers

`lax.ragged_dot` reaches the device as a `custom-call` the compiler names
itself (`ragged-dot-none`); the catalog gives it the scope of an operand,
which may be the dispatch's or the combine's (or the bare layer's), and
appends the kernel's own name: a scope that ends in `ragged-dot...` is the
experts' whatever layer scope stands before it. Any other custom-call is
judged by its scope like every op.
"""

from __future__ import annotations

from typing import Optional

from benchmark import scope_time

ROUTE = ("route", "dispatch", "combine")


def mechanism(entry) -> Optional[str]:
    parts = entry.scope.split("/")
    if "core" in parts and "attn" in parts:
        return "attn_core"
    if parts[-1].startswith("ragged-dot"):
        return "moe_experts"
    if "moe" in parts:
        if "experts" in parts:
            return "moe_experts"
        if any(p in parts for p in ROUTE):
            return "moe_route"
    return None


def ms(run, name: str) -> Optional[float]:
    """ms a step in ops of one mechanism; None where nothing was read."""
    got = scope_time.split(
        run, lambda e: name if mechanism(e) == name else None, (name,))
    return (got.get(name) or None) if got else None


def last_epoch(run, counter: str):
    """The newest epoch's value of one of the expert layers' counters
    (a list, one value a layer), or None."""
    epochs = run.counters.get(counter)
    return epochs[-1] if epochs else None
