"""Family `ouro` (Ouro-2.6B, `model_type: ouro`): the matmuls of one
SEQUENCE of `config["input"][0]` tokens, listed from the configuration's
`arch` group in `benchmark/flops.py`'s contract (one sequence is what that
file calls an image). Layer names are the scopes the program opens
(nn/ouro.py), `/` written `.`, with the pass written into the name
(`ut2.l3.attn.o`: the program runs the passes as one loop under `ut`).

The one stack is applied `T = arch["total_ut_steps"]` times a step, and
every pass ends in an exit through the whole head: a layer's records are
listed T times and the head's T times, because that is the work a step
needs — its weights are read in T places and each place costs a forward,
a data gradient and a weight gradient. A lister that counted one pass
would make `mfu_pct` read a quarter of the truth.

Every record is `dense`: `rows` positions times `cin x cout`.

- the embedding is a lookup: `rows: 0` (and, as the first record, the
  layer `train_flops_per_image` spares the data gradient);
- attention's three projections and `o`; its two products over the pairs
  the causal mask ALLOWS (`S (S + 1) / 2` a head), never the pairs of the
  tiles a kernel visits. Per head `qk` and `pv`, `head_dim` wide; they
  have no weights;
- the gated MLP;
- the exit's head. The exit gate (`hidden x 1`) and the mixture are not
  counted: model FLOPs, and 1/49,152 of the head's.

`attention_core_passes` (a record a pass, layer and direction) gives the
operations and least bytes of the T x L cores for their roofline share.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.shapes.glm_moe import _gated, least_seconds  # noqa: F401


def passes(config: Dict) -> int:
    return config["arch"]["total_ut_steps"]


def pairs_allowed(config: Dict) -> int:
    """Pairs (query, key) the causal mask allows, one sequence and head."""
    s = config["input"][0]
    return s * (s + 1) // 2


def one_pass(config: Dict, tag: str) -> List[Dict]:
    """The records of one pass over the stack and its exit, named under
    `tag`."""
    arch, s = config["arch"], config["input"][0]
    d, h, kv, wide = (arch["hidden_size"], arch["num_attention_heads"],
                      arch["num_key_value_heads"], arch["head_dim"])
    out: List[Dict] = []
    for i in range(arch["num_hidden_layers"]):
        at = f"{tag}.l{i}"
        for name, cin, cout in (("q", d, h * wide), ("k", d, kv * wide),
                                ("v", d, kv * wide)):
            out.append(dict(name=f"{at}.attn.qkv.{name}", kind="dense",
                            rows=s, cin=cin, cout=cout))
        pairs = pairs_allowed(config)
        out.append(dict(name=f"{at}.attn.core.qk", kind="dense", rows=pairs,
                        cin=wide, cout=h, weights=False))
        out.append(dict(name=f"{at}.attn.core.pv", kind="dense", rows=pairs,
                        cin=h, cout=wide, weights=False))
        out.append(dict(name=f"{at}.attn.o", kind="dense", rows=s,
                        cin=h * wide, cout=d))
        _gated(out, f"{at}.mlp", s, d, arch["intermediate_size"])
    out.append(dict(name=f"{tag}.exit.head", kind="dense", rows=s, cin=d,
                    cout=arch["vocab_size"]))
    return out


def layers(config: Dict) -> List[Dict]:
    arch = config["arch"]
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0,
                            cin=arch["vocab_size"], cout=arch["hidden_size"])]
    for t in range(passes(config)):
        out += one_pass(config, f"ut{t}")
    return out


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's attention cores, a record per pass, layer and
    direction (T x L cores): by `benchmark/shapes/afmoe.py`'s conventions.
    Operations: the two products over the pairs the causal mask allows
    forward, twice that backward (dq, dk, dv and dp); rematerialised
    forwards are not counted. Least bytes: q and the output over all heads
    and k, v over the key/value heads — q, k, v read and the output
    written forward; those four and the output's gradient read and three
    gradients written backward."""
    arch, s = config["arch"], config["input"][0]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    position = sequences * s * wide * act_bytes  # one head's, all rows
    fwd = 2 * sequences * h * pairs_allowed(config) * 2 * wide
    out = []
    for t in range(passes(config)):
        for i in range(arch["num_hidden_layers"]):
            name = f"ut{t}.core{i}"
            out.append(dict(name=name, kind="fwd", flops=fwd,
                            bytes=position * (2 * h + 2 * kv)))
            out.append(dict(name=name, kind="bwd", flops=2 * fwd,
                            bytes=position * (4 * h + 4 * kv)))
    return out


def core_calls(config: Dict) -> int:
    """Forward attention cores a step needs: one a pass and layer."""
    return passes(config) * config["arch"]["num_hidden_layers"]
