"""The grouped (depthwise) convs' share of their roofline: the least time
the chip could take for their three passes in one step — per pass the
larger of FLOPs over the peak bf16 FLOP/s and least bytes over the peak
HBM bytes/s (benchmark/flops.py:conv_passes of the layers with
`groups > 1`, peaks from benchmark/peaks.json) — over the time measured
in them (`dwconv_device_ms`). XLA's grouped convolution does no MXU work
worth the name, and with published peaks only, its roofline is the HBM
one: a low share says the vector unit binds, not the memory."""

from benchmark import flops
from benchmark.layer_metrics import dwconv_device_ms


def least_seconds(config, batch, peak):
    grouped = {l["name"] for l in flops.layers(config)
               if l.get("groups", 1) > 1}
    return sum(max(p["flops"] / peak["bf16_flops_per_s"],
                   p["bytes"] / peak["hbm_bytes_per_s"])
               for p in flops.conv_passes(config, batch)
               if p["name"] in grouped)


def read(run):
    if run.ctx.peak is None:
        return None
    took_ms = dwconv_device_ms.read(run)
    if not took_ms:
        return None
    least = least_seconds(run.ctx.config, run.counters["batch_per_chip"],
                          run.ctx.peak)
    return 100.0 * least / (took_ms / 1e3) if least else None
