"""Operations and bytes of a ResNet, counted from the layer shapes in a
configuration file (`arch`, `input`, `num_classes`) — the yardstick for
`mfu_pct` and `conv_roofline`. Nothing here imports the program.

Conventions, stated once:
- one multiply-accumulate = 2 FLOPs;
- SAME padding, so a conv's output side is ceil(input side / stride);
- the backward pass of a conv is a data gradient and a weight gradient of
  the forward's size each; the first conv needs no data gradient (nothing
  upstream of the images is trained), so it is not counted;
- BatchNorm, ReLU, pooling and the optimizer are not counted (model FLOPs,
  not hardware FLOPs);
- bytes are the least traffic each pass needs: read both operands once,
  write the result once, activations at `act_bytes`, weight gradients f32.
"""

from __future__ import annotations

import math
from typing import Dict, List


def _out(side: int, stride: int) -> int:
    return math.ceil(side / stride)


def layers(config: Dict) -> List[Dict]:
    """Every conv and the dense head, in execution order, with shapes."""
    arch = config["arch"]
    h, w, c = config["input"]
    out: List[Dict] = []

    def conv(name, k, stride, cout):
        ho, wo = _out(h, stride), _out(w, stride)
        out.append(dict(name=name, kind="conv", k=k, stride=stride,
                        h_in=h, w_in=w, cin=c, h_out=ho, w_out=wo, cout=cout))
        return ho, wo

    stem = arch["stem"]
    h, w = conv("stem", stem["kernel"], stem["stride"], stem["features"])
    c = stem["features"]
    if stem.get("maxpool"):
        h, w = _out(h, stem["maxpool"]["stride"]), _out(w, stem["maxpool"]["stride"])
    exp = arch["expansion"]
    for si, (width, count) in enumerate(zip(arch["stage_widths"], arch["stage_blocks"])):
        for bi in range(count):
            stride = 2 if (si > 0 and bi == 0) else 1
            tag = f"s{si + 1}b{bi + 1}"
            if stride != 1 or c != width * exp:
                conv(f"{tag}.proj", 1, stride, width * exp)
            if arch["block"] == "bottleneck":
                conv(f"{tag}.reduce", 1, 1, width)
                c = width
                h, w = conv(f"{tag}.mid", 3, stride, width)
                conv(f"{tag}.expand", 1, 1, width * exp)
            elif arch["block"] == "basic":
                h, w = conv(f"{tag}.head", 3, stride, width)
                c = width
                conv(f"{tag}.tail", 3, 1, width)
            else:
                raise ValueError(f"unknown block kind {arch['block']!r}")
            c = width * exp
    out.append(dict(name="fc", kind="dense", cin=c, cout=config["num_classes"]))
    return out


def macs(layer: Dict) -> int:
    """Multiply-accumulates of one layer's forward pass on one image."""
    if layer["kind"] == "dense":
        return layer["cin"] * layer["cout"]
    return (layer["h_out"] * layer["w_out"] * layer["k"] ** 2
            * layer["cin"] * layer["cout"])


def forward_macs(config: Dict) -> int:
    return sum(macs(l) for l in layers(config))


def train_flops_per_image(config: Dict) -> int:
    """Forward + backward FLOPs one image requires (see conventions)."""
    ls = layers(config)
    fwd = sum(macs(l) for l in ls)
    return 2 * (3 * fwd - macs(ls[0]))


def conv_passes(config: Dict, batch: int, act_bytes: int = 2) -> List[Dict]:
    """Each conv's forward, data-gradient and weight-gradient pass at a
    batch: its FLOPs and its least bytes."""
    out = []
    ls = layers(config)
    for i, l in enumerate(ls):
        if l["kind"] != "conv":
            continue
        flops = 2 * macs(l) * batch
        x = batch * l["h_in"] * l["w_in"] * l["cin"] * act_bytes
        y = batch * l["h_out"] * l["w_out"] * l["cout"] * act_bytes
        wts = l["k"] ** 2 * l["cin"] * l["cout"]
        out.append(dict(name=l["name"], kind="fwd", flops=flops,
                        bytes=x + y + wts * act_bytes))
        if i > 0:
            out.append(dict(name=l["name"], kind="dgrad", flops=flops,
                            bytes=x + y + wts * act_bytes))
        out.append(dict(name=l["name"], kind="wgrad", flops=flops,
                        bytes=x + y + wts * 4))
    return out


def conv_roofline_seconds(config: Dict, batch: int, peak: Dict,
                          act_bytes: int = 2) -> Dict[str, float]:
    """The least time one chip could spend in the convs of one train step:
    per pass, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s. Also says how much of that time each bound sets."""
    compute = memory = 0.0
    for p in conv_passes(config, batch, act_bytes):
        tc = p["flops"] / peak["bf16_flops_per_s"]
        tm = p["bytes"] / peak["hbm_bytes_per_s"]
        if tc >= tm:
            compute += tc
        else:
            memory += tm
    return {"seconds": compute + memory, "compute_bound_s": compute,
            "memory_bound_s": memory}
