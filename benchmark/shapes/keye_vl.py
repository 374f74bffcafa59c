"""Family `keye_vl` (Keye-VL-2.0-30B-A3B's language model, `model_type:
KeyeVL2`: grouped-query attention over the keys a learned indexer selects):
the matmuls of one SEQUENCE of `config["input"][0]` tokens, listed from the
configuration's `arch` group in `benchmark/flops.py`'s contract (one
sequence is what that file calls an image). Layer names are the scopes the
program opens (nn/keye_vl.py), `/` written `.`.

Every record is `dense`: `rows` positions times `cin x cout`. What is
counted is what this chip's share of the model needs, whatever implements
it:

- the embedding is a lookup: `rows: 0` (and, as the first record, the
  layer `train_flops_per_image` spares the data gradient);
- attention's three projections and `o`; the indexer's three (`q^I`, `k^I`,
  `w`);
- the indexer's scores `q^I . k^I`, `indexer_head_dim` wide, an index head
  a column: over every CAUSAL pair forward (the indexer scores every
  earlier key) and over the SELECTED pairs backward (its objective reads
  no other), which `flops.py`'s one rule — a backward is twice its forward
  — takes as a third of `causal + 2 selected` pairs a pass;
- attention's two products over the pairs the selection ALLOWS — query t
  keeps `min(t + 1, topk)` keys — never the pairs of the tiles a kernel
  visits: a core that computes every causal pair under a mask reads at
  most `allowed / causal` of its share. The probabilities the indexer's
  objective is measured against are the forward's: making them again is
  time, not work, and nothing is counted for it;
- the router on every token, the routed experts at the share of the
  assignments that falls to the held ones IN EXPECTATION, `S * 8 * 16 /
  128` = 16,384 rows a layer. That is the balanced share, and it is also
  the mean of the regime the cell runs in: the router is collapsed at the
  published initialisation (`keye_load_max_over_mean` 16.0 of 16), every
  token of layers 1-5 picks the same eight experts, each of them is held
  with probability 1 / 8, and a layer's rows are 16,384 times the number
  held — 0 to 51,814 from step to step, 17,641 a layer over the four
  epoch-end steps of the timed window (job_seed 7, my chip run, PR 51,
  call 3). A single step's `mfu_pct` is therefore over or under by the
  experts' part (2.8 of 30.9 TFLOP at 16,384 rows); the buffer the step
  sorts and sums is every assignment, 131,072 rows, and none of that is
  counted.

`attention_core_passes` and `expert_passes` give the operations and least
bytes of the two mechanisms' kernels for their roofline shares.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.shapes.glm_moe import (  # noqa: F401  (the readers' handles)
    _gated,
    expert_passes,
    held_rows,
    least_seconds,
)


def pairs_causal(config: Dict) -> int:
    s = config["input"][0]
    return s * (s + 1) // 2


def pairs_allowed(config: Dict) -> int:
    """Pairs (query, key) the selection allows, one sequence (every head
    the same): query t keeps `min(t + 1, topk)` keys."""
    s = config["input"][0]
    k = min(config["arch"]["topk"], s)
    return k * (k + 1) // 2 + (s - k) * k


def layers(config: Dict) -> List[Dict]:
    arch, s = config["arch"], config["input"][0]
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    hi, di = arch["indexer_num_heads"], arch["indexer_head_dim"]
    pairs = pairs_allowed(config)
    scored = (pairs_causal(config) + 2 * pairs) // 3
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0, cin=vocab,
                            cout=d)]
    for i in range(arch["num_hidden_layers"]):
        tag = f"l{i}"
        for name, cin, cout in (("q", d, h * wide), ("k", d, kv * wide),
                                ("v", d, kv * wide)):
            out.append(dict(name=f"{tag}.attn.qkv.{name}", kind="dense",
                            rows=s, cin=cin, cout=cout))
        for name, cout in (("q", hi * di), ("k", di), ("w", hi)):
            out.append(dict(name=f"{tag}.attn.indexer.proj.{name}",
                            kind="dense", rows=s, cin=d, cout=cout))
        out.append(dict(name=f"{tag}.attn.indexer.scores", kind="dense",
                        rows=scored, cin=di, cout=hi, weights=False))
        out.append(dict(name=f"{tag}.attn.core.qk", kind="dense", rows=pairs,
                        cin=wide, cout=h, weights=False))
        out.append(dict(name=f"{tag}.attn.core.pv", kind="dense", rows=pairs,
                        cin=h, cout=wide, weights=False))
        out.append(dict(name=f"{tag}.attn.o", kind="dense", rows=s,
                        cin=h * wide, cout=d))
        out.append(dict(name=f"{tag}.moe.route", kind="dense", rows=s, cin=d,
                        cout=arch["router_experts"]))
        _gated(out, f"{tag}.moe.experts", held_rows(config), d,
               arch["moe_intermediate_size"], copies=len(arch["held_experts"]))
    out.append(dict(name="head", kind="dense", rows=s, cin=d, cout=vocab))
    return out


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's attention cores, a record per layer and direction.
    Operations: the two products over the pairs the selection allows
    forward, twice that backward (dq, dk, dv and dp); rematerialised
    forwards are not counted. Least bytes: q and the output over all heads
    and k, v over the key/value heads — q, k, v read and the output written
    forward; those four and the output's gradient read and three gradients
    written backward (the selection itself, a bit a pair, is not counted)."""
    arch, s = config["arch"], config["input"][0]
    h, kv, wide = (arch["num_attention_heads"], arch["num_key_value_heads"],
                   arch["head_dim"])
    fwd = 2 * sequences * h * pairs_allowed(config) * 2 * wide
    position = sequences * s * wide * act_bytes  # one head's, all rows
    out = []
    for i in range(arch["num_hidden_layers"]):
        out.append(dict(name=f"core{i}", kind="fwd", layer=i, flops=fwd,
                        bytes=position * (2 * h + 2 * kv)))
        out.append(dict(name=f"core{i}", kind="bwd", layer=i, flops=2 * fwd,
                        bytes=position * (4 * h + 4 * kv)))
    return out
