"""The mechanisms of a `keye_vl` model (nn/keye_vl.py) by the scopes it
opens: what the readers `dsa_*` and `keye_*` group the step's device time
by (benchmark/scope_time.py does the join and the sums of time;
benchmark/shapes/keye_vl.py counts the work).

    core      l<i>/attn/core                 the attention over the selected
                                             keys, both kernels
    indexer   l<i>/attn/indexer/{proj,rope,scores}, and `scores` under `kl`
              (the objective makes a block's scores again, and differentiates
              them there)            projections, M-RoPE, `I`, forward and backward
    select    l<i>/attn/indexer/select       top-k, threshold, the bits and
                                             the bias made from them
    kl        l<i>/attn/indexer/kl but its `scores`   the head-mean
                                             probabilities, `L^I`, its gradient
    experts, route                           as benchmark/glm_scopes.py has them
"""

from __future__ import annotations

from typing import Optional

from benchmark import glm_scopes, scope_time
from benchmark.bailing_hybrid_scopes import roofline  # noqa: F401  (least time of passes over a measured time)

last_epoch = glm_scopes.last_epoch


# An op that runs other ops (a `lax.scan`'s loop) is on the device's line for
# as long as they are, under the scope that opened it: its body's ops are
# counted where they are, so it is not.
RUNS_OTHERS = ("while", "call", "conditional")


def mechanism(entry) -> Optional[str]:
    if entry.opcode in RUNS_OTHERS:
        return None
    parts = entry.scope.split("/")
    if "indexer" in parts:
        after = parts[parts.index("indexer") + 1:]
        if "select" in after:
            return "select"
        if "kl" in after and "scores" not in after:
            return "kl"
        return "indexer"
    return {"attn_core": "core", "moe_experts": "experts",
            "moe_route": "route"}.get(glm_scopes.mechanism(entry))


def ms(run, name: str) -> Optional[float]:
    """ms a step in ops of one mechanism; None where nothing was read."""
    got = scope_time.split(
        run, lambda e: name if mechanism(e) == name else None, (name,))
    return (got.get(name) or None) if got else None


def said(run) -> Optional[dict]:
    """What the program's model says of its own core (`describe`, what the
    `zoo_dsa` journal event carries); None where the program has no such
    model or statement."""
    from benchmark import common

    try:
        seq = run.ctx.config["input"][0]
        return common.build_model(run.ctx.config).describe(
            run.counters["batch_per_chip"] * seq, seq, run.device["platform"])
    except (ImportError, AttributeError, KeyError, TypeError):
        return None


def newest_counter(name: str):
    """A counter of the newest epoch record as the program keeps it
    (`parallel_cnn_tpu/obs/epochs.py`): the runner hands its readers its
    own selection only. None where the program keeps no such copy or its
    model no such counter."""
    try:
        from parallel_cnn_tpu.obs import epochs

        newest = epochs.newest()
    except (ImportError, AttributeError):
        return None
    return newest[-1].get(name) if newest else None
