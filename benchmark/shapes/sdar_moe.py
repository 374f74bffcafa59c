"""Family `sdar_moe` (SDAR-30B-A3B-Chat, `model_type: sdar_moe`, trained
by block diffusion): the matmuls of one SEQUENCE of `config["input"][0]`
clean tokens, listed from the configuration's `arch` group in
`benchmark/flops.py`'s contract (one sequence is what that file calls an
image). Layer names are the scopes the program opens (nn/sdar_moe.py), `/`
written `.`.

A training forward runs the trunk on the stream `[x^t ; x^0]`, `2 L` rows
a sequence, and the head on the noised half alone, `L` rows. Every record
is `dense`: `rows` positions times `cin x cout`. What is counted is what
this chip's share of the model needs, whatever implements it:

- the embedding is a lookup: `rows: 0` (and, as the first record, the
  layer `train_flops_per_image` spares the data gradient);
- the routed experts at the HELD share under balanced routing: each of
  the `2 L` stream rows makes `num_experts_per_tok` assignments, `held /
  router_experts` of which land here; the router sees every stream row;
- attention's two products over the pairs the mask ALLOWS: a noised query
  sees its block's `B` noised keys and the clean keys of the blocks before
  its own, a clean query the clean keys up to its block's end — `L (L +
  B)` pairs a head, never the pairs of the tiles a kernel visits. Per head
  `qk` and `pv`, `head_dim` wide; they have no weights.

`attention_core_passes` and `expert_passes` give the operations and least
bytes of the two mechanisms' kernels for their roofline shares.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.shapes.glm_moe import (  # noqa: F401  (the readers' handles)
    _gated,
    expert_passes,
    least_seconds,
)


def pairs_allowed(config: Dict) -> int:
    """Pairs (query, key) the mask allows, one sequence and head."""
    l = config["input"][0]
    return l * (l + config["arch"]["block_length"])


def held_rows(config: Dict) -> int:
    """Stream rows the held experts of one layer multiply for one sequence
    under balanced routing."""
    arch = config["arch"]
    return (2 * config["input"][0] * arch["num_experts_per_tok"]
            * len(arch["held_experts"]) // arch["router_experts"])


def layers(config: Dict) -> List[Dict]:
    arch, l = config["arch"], config["input"][0]
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    pairs = pairs_allowed(config)
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0, cin=vocab,
                            cout=d)]
    for i in range(arch["num_hidden_layers"]):
        tag = f"l{i}"
        for name, cin, cout in (("q", d, h * wide), ("k", d, kv * wide),
                                ("v", d, kv * wide)):
            out.append(dict(name=f"{tag}.attn.qkv.{name}", kind="dense",
                            rows=2 * l, cin=cin, cout=cout))
        out.append(dict(name=f"{tag}.attn.core.qk", kind="dense", rows=pairs,
                        cin=wide, cout=h, weights=False))
        out.append(dict(name=f"{tag}.attn.core.pv", kind="dense", rows=pairs,
                        cin=h, cout=wide, weights=False))
        out.append(dict(name=f"{tag}.attn.o", kind="dense", rows=2 * l,
                        cin=h * wide, cout=d))
        out.append(dict(name=f"{tag}.moe.route", kind="dense", rows=2 * l,
                        cin=d, cout=arch["router_experts"]))
        _gated(out, f"{tag}.moe.experts", held_rows(config), d,
               arch["moe_intermediate_size"], copies=len(arch["held_experts"]))
    out.append(dict(name="head", kind="dense", rows=l, cin=d, cout=vocab))
    return out


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's attention cores, a record per layer and direction.
    Operations: the two products over the allowed pairs forward, twice that
    backward (dq, dk, dv and dp); rematerialised forwards are not counted.
    Least bytes: q and the output over all heads and k, v over the
    key/value heads, `2 L` positions each — q, k, v read and the output
    written forward; those four and the output's gradient read and three
    gradients written backward."""
    arch, l = config["arch"], config["input"][0]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    fwd = 2 * sequences * h * pairs_allowed(config) * 2 * wide
    position = sequences * 2 * l * wide * act_bytes  # one head's, all rows
    out = []
    for i in range(arch["num_hidden_layers"]):
        out.append(dict(name=f"core{i}", kind="fwd", flops=fwd,
                        bytes=position * (2 * h + 2 * kv)))
        out.append(dict(name=f"core{i}", kind="bwd", flops=2 * fwd,
                        bytes=position * (4 * h + 4 * kv)))
    return out
