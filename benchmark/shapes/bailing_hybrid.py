"""Family `bailing_hybrid` (Ling-3.0-flash, `model_type: bailing_hybrid`):
the matmuls of one SEQUENCE of `config["input"][0]` tokens, listed from
the configuration's `arch` group in `benchmark/flops.py`'s contract (one
sequence is what that file calls an image). Layer names are the scopes the
program opens (nn/bailing_hybrid.py), `/` written `.`.

Every record is `dense`: `rows` positions times `cin x cout`. What is
counted is what this chip's share of the model needs, whatever implements
it:

- the embedding is a lookup: `rows: 0` (and, as the first record, the
  layer `train_flops_per_image` spares the data gradient);
- a `linear_attention` layer's projections (q, k, v, the decay's, beta's
  and the head-wise gate's) and `o`; its core as the CHUNKED form of the
  delta rule at `arch.kda_chunk` positions C with keys Dk and values Dv
  wide: per chunk and head the pair tables `k k^T` and `q k^T` (2 C^2 Dk),
  `T V` and `T (K . decay)` (C^2 (Dv + Dk)), `B U` (C^2 Dv) and the three
  products with the (Dk, Dv) state (3 C Dk Dv) — `C (3 Dk + 2 Dv) + 3 Dk
  Dv` multiply-adds a position and head; forming `T` (a triangular solve,
  C^3 / 3) is under 2 % of that and left out, as are the 4-tap
  convolutions (no matmul). The recurrence a position at a time would
  need `3 Dk Dv` alone, on no matrix unit;
- a `full_attention` layer's q, `kv_a`, `kv_b`, gate and `o`, and its two
  products over the causal half at the PUBLISHED head widths (`q k` 192
  wide, `p v` 128), never the padded ones;
- the dense layers' gated MLP; in the expert layers the router and the
  shared expert on every token, the routed experts at the HELD share
  under balanced routing (`S * 8 * 8 / 512` rows).

`kda_core_passes`, `attention_core_passes` (the full layers') and
`expert_passes` give the operations and least bytes of the three
mechanisms' kernels for their roofline shares.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.shapes.glm_moe import (  # noqa: F401  (the readers' handles)
    _gated,
    expert_passes,
    held_rows,
    least_seconds,
)

LINEAR, FULL = "linear_attention", "full_attention"


def kda_macs_per_position(arch: Dict) -> int:
    """Multiply-adds of the chunked delta rule a position and head."""
    c, d = arch["kda_chunk"], arch["head_dim"]
    return c * (3 * d + 2 * d) + 3 * d * d


def layers(config: Dict) -> List[Dict]:
    arch, s = config["arch"], config["input"][0]
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    h, wide = arch["num_attention_heads"], arch["head_dim"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    v_dim, rank = arch["v_head_dim"], arch["kv_lora_rank"]
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0, cin=vocab,
                            cout=d)]

    def dense(name, cin, cout, rows=s, **more):
        out.append(dict(name=name, kind="dense", rows=rows, cin=cin,
                        cout=cout, **more))

    for i, kind in enumerate(arch["layer_types"]):
        tag = f"l{i}"
        if kind == LINEAR:
            for name in ("q", "k", "v"):
                dense(f"{tag}.attn.qkv.{name}", d, h * wide)
            dense(f"{tag}.attn.gates.f", d, h * wide)
            dense(f"{tag}.attn.gates.beta", d, h)
            dense(f"{tag}.attn.core.scan", h, kda_macs_per_position(arch),
                  weights=False)
            dense(f"{tag}.attn.gate_norm.gate", d, h)
            dense(f"{tag}.attn.o", h * wide, d)
        else:
            pairs = s * (s + 1) // 2
            dense(f"{tag}.attn.q", d, h * (nope + rope))
            dense(f"{tag}.attn.kv.kv_a", d, rank + rope)
            dense(f"{tag}.attn.kv.kv_b", rank, h * (nope + v_dim))
            dense(f"{tag}.attn.core.qk", nope + rope, h, rows=pairs,
                  weights=False)
            dense(f"{tag}.attn.core.pv", h, v_dim, rows=pairs, weights=False)
            dense(f"{tag}.attn.gate", d, h)
            dense(f"{tag}.attn.o", h * v_dim, d)
        if i < arch["first_k_dense_replace"]:
            _gated(out, f"{tag}.mlp", s, d, arch["intermediate_size"])
            continue
        dense(f"{tag}.moe.route", d, arch["router_experts"])
        _gated(out, f"{tag}.moe.experts", held_rows(config), d,
               arch["moe_intermediate_size"], copies=len(arch["held_experts"]))
        _gated(out, f"{tag}.moe.shared", s, d,
               arch["num_shared_experts"] * arch["moe_intermediate_size"])
    dense("head", d, vocab)
    return out


def kda_core_passes(config: Dict, sequences: int,
                    act_bytes: int = 2) -> List[Dict]:
    """One train step's delta-rule cores, a record per linear layer and
    direction. Operations: the chunked form's (`kda_macs_per_position`)
    forward, twice that backward; rematerialised forwards are not counted.
    Least bytes, whatever implements it: q, k, v at `act_bytes`, the
    float32 log-decay and beta read once and the output written once
    forward; those five and the output's gradient read and five gradients
    written backward. No state reaches HBM in the least form."""
    arch, s = config["arch"], config["input"][0]
    h, d = arch["num_attention_heads"], arch["head_dim"]
    fwd = 2 * sequences * s * h * kda_macs_per_position(arch)
    inputs = sequences * s * h * (3 * d * act_bytes + d * 4 + 4)
    output = sequences * s * h * d * act_bytes
    out = []
    for i, kind in enumerate(arch["layer_types"]):
        if kind != LINEAR:
            continue
        out.append(dict(name=f"scan{i}", kind="fwd", layer=i, flops=fwd,
                        bytes=inputs + output))
        out.append(dict(name=f"scan{i}", kind="bwd", layer=i, flops=2 * fwd,
                        bytes=2 * inputs + output))
    return out


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's softmax cores (the full layers'), a record per
    layer and direction, at the published head widths. Operations: the two
    products over the causal half forward, twice that backward. Least
    bytes: q, k, v read and the output written forward; those four and the
    output's gradient read and three gradients written backward."""
    arch, s = config["arch"], config["input"][0]
    h = arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    v = arch["v_head_dim"]
    fwd = 2 * sequences * h * (s * (s + 1) // 2) * (qk + v)
    tensors = sequences * s * h * act_bytes
    out = []
    for i, kind in enumerate(arch["layer_types"]):
        if kind != FULL:
            continue
        out.append(dict(name=f"core{i}", kind="fwd", layer=i, flops=fwd,
                        bytes=tensors * (2 * qk + 2 * v)))
        out.append(dict(name=f"core{i}", kind="bwd", layer=i, flops=2 * fwd,
                        bytes=tensors * (4 * qk + 4 * v)))
    return out
