"""Family `convnext` (Liu et al. 2022, arXiv:2201.03545): the layers of a
ConvNeXt listed from `arch.depths`, `arch.dims`, `arch.stem`,
`arch.downsample`, `arch.dw_kernel` and `arch.expansion`. The stem and the
convs between stages are non-overlapping patches (kernel == stride, no
padding); the depthwise conv pads to keep its side. Layer names are the
scopes the program opens (nn/convnext.py; PERF.md section 3).

The two pointwise layers of a block are listed as `conv` records with
`k: 1`, not as `dense` records with `rows`: the arithmetic is the same
either way (`flops.macs` agrees), but `flops.conv_passes` lists only
`conv` records, and on the TPU a matmul compiles to a `kind=kOutput`
fusion, which the trace reduction counts as conv time. Listed as `dense`,
`conv_roofline` would divide the least time of the depthwise 1.5 % of the
work by the measured time of all of it. Only the head is `dense`."""

from __future__ import annotations

from typing import Dict, List


def layers(config: Dict) -> List[Dict]:
    """Every conv, pointwise layer and the dense head, in execution order."""
    arch = config["arch"]
    h, w, c = config["input"]
    out: List[Dict] = []

    def conv(name, k, stride, cout, groups=None):
        ho, wo = h // stride, w // stride
        rec = dict(name=name, kind="conv", k=k, stride=stride, h_in=h, w_in=w,
                   cin=c, h_out=ho, w_out=wo, cout=cout)
        if groups:
            rec["groups"] = groups
        out.append(rec)
        return ho, wo, cout

    stem, down = arch["stem"], arch["downsample"]
    for si, (depth, dim) in enumerate(zip(arch["depths"], arch["dims"])):
        if si == 0:
            h, w, c = conv("stem", stem["kernel"], stem["stride"], dim)
        else:
            h, w, c = conv(f"down{si + 1}", down["kernel"], down["stride"], dim)
        for bi in range(depth):
            tag = f"s{si + 1}b{bi + 1}"
            conv(f"{tag}.dw", arch["dw_kernel"], 1, dim, groups=dim)
            _, _, c = conv(f"{tag}.expand", 1, 1, dim * arch["expansion"])
            _, _, c = conv(f"{tag}.reduce", 1, 1, dim)
    out.append(dict(name="fc", kind="dense", cin=c, cout=config["num_classes"]))
    return out
