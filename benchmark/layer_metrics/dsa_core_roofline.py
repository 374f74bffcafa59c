"""The selected-key attention cores' share of their roofline: the least time
the chip could take for one step's `q k^T` and `p v` over the pairs the
selection ALLOWS — `sum_t min(t + 1, topk)` a head, forward once and
backward twice, all layers (benchmark/shapes/keye_vl.py:
attention_core_passes), whatever implements the core — over the time
measured in them (`dsa_core_device_ms`). A core that computes every causal
pair under a mask reads at most allowed / causal of its MXU share: see
`dsa_pairs_computed_ratio`."""

from benchmark import keye_scopes
from benchmark.layer_metrics import dsa_core_device_ms
from benchmark.shapes import keye_vl as shapes


def read(run):
    return keye_scopes.roofline(
        run, dsa_core_device_ms.read(run),
        lambda: shapes.attention_core_passes(
            run.ctx.config, run.counters["batch_per_chip"]))
