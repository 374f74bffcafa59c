"""Model FLOP/s utilization: the FLOPs forward and backward require per
image (benchmark/flops.py, from the configuration's layer shapes) times
the measured images per second per chip, over the chip's published bf16
peak (benchmark/peaks.json)."""

from benchmark import flops


def read(run):
    rate = run.e2e.get("train_img_s_chip")
    if rate is None or run.ctx.peak is None:
        return None
    need = flops.train_flops_per_image(run.ctx.config)
    return 100.0 * need * rate / run.ctx.peak["bf16_flops_per_s"]
