"""Family `afmoe` (Trinity-Mini, `model_type: afmoe`): the matmuls of one
SEQUENCE of `config["input"][0]` tokens, listed from the configuration's
`arch` group in `benchmark/flops.py`'s contract (one sequence is what that
file calls an image). Layer names are the scopes the program opens
(nn/afmoe.py), `/` written `.`.

Every record is `dense`: `rows` positions times `cin x cout`. What is
counted is what this chip's share of the model needs, whatever implements
it:

- the embedding is a lookup: `rows: 0` (and, as the first record, the
  layer `train_flops_per_image` spares the data gradient);
- attention's four projections (q, k, v and the output gate) and `o`;
- attention's two products over the pairs the layer's mask ALLOWS — query
  i sees `min(i + 1, window)` keys in a `sliding_attention` layer and `i +
  1` in a `full_attention` one — never the pairs of the tiles a kernel
  visits. Per head `qk` and `pv`, `head_dim` wide; they have no weights;
- the dense layers' gated MLP; in the expert layers the router and the
  shared expert on every token, the routed experts at the HELD share
  under balanced routing (`S * 8 * 16 / 128` rows).

`attention_core_passes` (a record a layer and direction, each with its
layer's kind) and `expert_passes` give the operations and least bytes of
the two mechanisms' kernels for their roofline shares.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.shapes.glm_moe import (  # noqa: F401  (the readers' handles)
    _gated,
    expert_passes,
    held_rows,
    least_seconds,
)

SLIDING, FULL = "sliding_attention", "full_attention"


def window_of(config: Dict, kind: str) -> Optional[int]:
    return config["arch"]["sliding_window"] if kind == SLIDING else None


def pairs_allowed(config: Dict, kind: str) -> int:
    """Pairs (query, key) a layer of `kind` allows, one sequence and head."""
    s, window = config["input"][0], window_of(config, kind)
    w = s if window is None else min(window, s)
    return w * (w + 1) // 2 + (s - w) * w


def layers(config: Dict) -> List[Dict]:
    arch, s = config["arch"], config["input"][0]
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0, cin=vocab,
                            cout=d)]
    for i, kind in enumerate(arch["layer_types"]):
        tag = f"l{i}"
        for name, cin, cout in (("q", d, h * wide), ("k", d, kv * wide),
                                ("v", d, kv * wide), ("gate", d, h * wide)):
            out.append(dict(name=f"{tag}.attn.qkv.{name}", kind="dense",
                            rows=s, cin=cin, cout=cout))
        pairs = pairs_allowed(config, kind)
        out.append(dict(name=f"{tag}.attn.core.qk", kind="dense", rows=pairs,
                        cin=wide, cout=h, weights=False))
        out.append(dict(name=f"{tag}.attn.core.pv", kind="dense", rows=pairs,
                        cin=h, cout=wide, weights=False))
        out.append(dict(name=f"{tag}.attn.o", kind="dense", rows=s,
                        cin=h * wide, cout=d))
        if i < arch["num_dense_layers"]:
            _gated(out, f"{tag}.mlp", s, d, arch["intermediate_size"])
            continue
        out.append(dict(name=f"{tag}.moe.route", kind="dense", rows=s, cin=d,
                        cout=arch["router_experts"]))
        _gated(out, f"{tag}.moe.experts", held_rows(config), d,
               arch["moe_intermediate_size"], copies=len(arch["held_experts"]))
        _gated(out, f"{tag}.moe.shared", s, d,
               arch["num_shared_experts"] * arch["moe_intermediate_size"])
    out.append(dict(name="head", kind="dense", rows=s, cin=d, cout=vocab))
    return out


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's attention cores, a record per layer and direction,
    `layer` its index and `layer_kind` its kind. Operations: the two
    products over the pairs the layer's mask allows forward, twice that
    backward (dq, dk, dv and dp); rematerialised forwards are not counted.
    Least bytes: q and the output over all heads and k, v over the
    key/value heads — q, k, v read and the output written forward; those
    four and the output's gradient read and three gradients written
    backward."""
    arch, s = config["arch"], config["input"][0]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    position = sequences * s * wide * act_bytes  # one head's, all rows
    out = []
    for i, kind in enumerate(arch["layer_types"]):
        fwd = 2 * sequences * h * pairs_allowed(config, kind) * 2 * wide
        out.append(dict(name=f"core{i}", kind="fwd", layer=i, layer_kind=kind,
                        flops=fwd, bytes=position * (2 * h + 2 * kv)))
        out.append(dict(name=f"core{i}", kind="bwd", layer=i, layer_kind=kind,
                        flops=2 * fwd, bytes=position * (4 * h + 4 * kv)))
    return out
