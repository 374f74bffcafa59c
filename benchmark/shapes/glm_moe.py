"""Family `glm_moe` (GLM-4.7-Flash, `model_type: glm4_moe_lite`): the
matmuls of one SEQUENCE of `config["input"][0]` tokens, listed from the
configuration's `arch` group in `benchmark/flops.py`'s contract (one
sequence is what that file calls an image). Layer names are the scopes the
program opens (nn/glm_moe.py), `/` written `.`.

Every record is `dense`: `rows` positions times `cin x cout`. What is
counted is what this chip's share of the model needs:

- the embedding is a lookup: `rows: 0`, so it multiplies nothing (and, as
  the first record, it is the layer `train_flops_per_image` spares the
  data gradient);
- the routed experts at the HELD share under balanced routing: each token
  makes `num_experts_per_tok` assignments, `held / router_experts` of
  which land here (`rows = S * 4 * 8 / 64`); the router and the shared
  expert see every token;
- attention's two products over the causal half: `S (S + 1) / 2` pairs of
  a query and a key it may see, per head `qk` (`q . k`, 256 wide) and `pv`
  (`p v`, 256 wide). They have no weights (`weights: false`);
- the MTP module's projection, decoder layer and its pass through the
  shared head.

`attention_core_passes` and `expert_passes` give the operations and least
bytes of the two mechanisms' kernels for their roofline shares.
"""

from __future__ import annotations

from typing import Dict, List


def _attention(out: List[Dict], tag: str, arch: Dict, s: int) -> None:
    d, h = arch["hidden_size"], arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]

    def dense(name, cin, cout):
        out.append(dict(name=f"{tag}.attn.{name}", kind="dense", rows=s,
                        cin=cin, cout=cout))

    dense("q_a", d, arch["q_lora_rank"])
    dense("q_b", arch["q_lora_rank"], h * qk)
    dense("kv_a", d, arch["kv_lora_rank"] + arch["qk_rope_head_dim"])
    dense("kv_b", arch["kv_lora_rank"],
          h * (arch["qk_nope_head_dim"] + arch["v_head_dim"]))
    pairs = s * (s + 1) // 2
    out.append(dict(name=f"{tag}.attn.core.qk", kind="dense", rows=pairs,
                    cin=qk, cout=h, weights=False))
    out.append(dict(name=f"{tag}.attn.core.pv", kind="dense", rows=pairs,
                    cin=h, cout=arch["v_head_dim"], weights=False))
    dense("o", h * arch["v_head_dim"], d)


def _gated(out: List[Dict], tag: str, rows: int, d: int, width: int,
           copies: int = 1) -> None:
    for name, cin, cout in (("gate", d, width), ("up", d, width),
                            ("down", width, d)):
        out.append(dict(name=f"{tag}.{name}", kind="dense", rows=rows,
                        cin=cin, cout=cout, copies=copies))


def held_rows(config: Dict) -> int:
    """Rows the held experts of one layer multiply for one sequence under
    balanced routing."""
    arch = config["arch"]
    return (config["input"][0] * arch["num_experts_per_tok"]
            * len(arch["held_experts"]) // arch["router_experts"])


def _decoder(out: List[Dict], tag: str, config: Dict, sparse: bool) -> None:
    arch, s = config["arch"], config["input"][0]
    d = arch["hidden_size"]
    _attention(out, tag, arch, s)
    if not sparse:
        _gated(out, f"{tag}.mlp", s, d, arch["intermediate_size"])
        return
    out.append(dict(name=f"{tag}.moe.route", kind="dense", rows=s, cin=d,
                    cout=arch["router_experts"]))
    # one record for the held experts together: `rows` over all of them,
    # `copies` weights of `cin x cout`
    _gated(out, f"{tag}.moe.experts", held_rows(config), d,
           arch["moe_intermediate_size"], copies=len(arch["held_experts"]))
    _gated(out, f"{tag}.moe.shared", s, d,
           arch["n_shared_experts"] * arch["moe_intermediate_size"])


def layers(config: Dict) -> List[Dict]:
    arch, s = config["arch"], config["input"][0]
    d, vocab = arch["hidden_size"], arch["vocab_size"]
    out: List[Dict] = [dict(name="embed", kind="dense", rows=0, cin=vocab,
                            cout=d)]
    for i in range(arch["num_hidden_layers"]):
        _decoder(out, f"l{i}", config, i >= arch["first_k_dense_replace"])
    out.append(dict(name="head", kind="dense", rows=s, cin=d, cout=vocab))
    if arch["num_nextn_predict_layers"]:
        out.append(dict(name="mtp.proj", kind="dense", rows=s, cin=2 * d,
                        cout=d))
        _decoder(out, "mtp.l0", config, True)
        # the module's pass through the head it shares with the trunk
        out.append(dict(name="mtp.head", kind="dense", rows=s, cin=d,
                        cout=vocab, weights=False))
    return out


def attention_cores(config: Dict) -> int:
    arch = config["arch"]
    return arch["num_hidden_layers"] + arch["num_nextn_predict_layers"]


def attention_core_passes(config: Dict, sequences: int,
                          act_bytes: int = 2) -> List[Dict]:
    """One train step's attention cores (`q k^T`, softmax, `p v`), a
    record per layer and direction. Operations: the two products over the
    causal half forward, twice that backward (dq, dk, dv and dp); the
    rematerialised forwards are not counted. Least bytes: q, k, v read
    and the output written forward; those four and the output's gradient
    read and three gradients written backward."""
    arch, s = config["arch"], config["input"][0]
    h = arch["num_attention_heads"]
    qk = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"]
    v = arch["v_head_dim"]
    fwd = 2 * sequences * h * (s * (s + 1) // 2) * (qk + v)
    tensors = sequences * s * h * act_bytes
    out = []
    for i in range(attention_cores(config)):
        out.append(dict(name=f"core{i}", kind="fwd", flops=fwd,
                        bytes=tensors * (2 * qk + 2 * v)))
        out.append(dict(name=f"core{i}", kind="bwd", flops=2 * fwd,
                        bytes=tensors * (4 * qk + 4 * v)))
    return out


def expert_passes(config: Dict, rows_held: List[int],
                  act_bytes: int = 2) -> List[Dict]:
    """One train step's grouped matmuls over the held experts, from the
    rows each expert layer actually held (`rows_held`, one count a layer,
    the MTP module's last): gate, up and down, each forward, data gradient
    and weight gradient. Least bytes: the rows in and out at `act_bytes`,
    the held experts' weights once (bf16 read, float32 gradient written)."""
    arch = config["arch"]
    d, f = arch["hidden_size"], arch["moe_intermediate_size"]
    held = len(arch["held_experts"])
    out = []
    for i, rows in enumerate(rows_held):
        for name, cin, cout in (("gate", d, f), ("up", d, f), ("down", f, d)):
            flops = 2 * rows * cin * cout
            io = rows * (cin + cout) * act_bytes
            for kind, wbytes in (("fwd", act_bytes), ("dgrad", act_bytes),
                                 ("wgrad", 4)):
                out.append(dict(name=f"experts{i}.{name}", kind=kind,
                                flops=flops,
                                bytes=io + held * cin * cout * wbytes))
    return out


def least_seconds(passes: List[Dict], peak: Dict) -> float:
    """Per pass the larger of operations over the peak bf16 FLOP/s and
    least bytes over the peak HBM bytes/s, summed."""
    return sum(max(p["flops"] / peak["bf16_flops_per_s"],
                   p["bytes"] / peak["hbm_bytes_per_s"]) for p in passes)
