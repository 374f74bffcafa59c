"""Keye-VL-2.0-30B-A3B's programs for a described v5e (no chip, no run) at
published widths: the scheduled kernel pair takes the selection as an array
at the cell's shapes (four heads a grid step, the causal visit list's 528
tiles), the index scores' kernel pair takes a block of 256 queries' sixteen
heads against each of the four bands of keys, and a layer's step holds the
selection as bits — no array of (heads, S, S) anywhere, no float32 (S, S)
array at all, so none that outlives its layer, and no float32 array of the
sixteen index heads by a block of scores (tests/compiled_programs.py has
what the files share)."""

import re

import jax
import jax.numpy as jnp
import pytest
from compiled_programs import described_v5e
from jax.sharding import SingleDeviceSharding



@pytest.fixture(scope="module")
def topo():
    yield from described_v5e()


def test_the_selected_kernels_compile_at_the_cells_shapes(topo):
    """Mosaic takes both directions at 16,384 positions of 32 query heads
    over 4 key/value heads of 128 with the mask an operand: `dk` and `dv`
    fill their VMEM buffers, so four heads a step, not eight."""
    from parallel_cnn_tpu.ops import pallas_attention as pa

    one_chip = SingleDeviceSharding(topo.devices[0])
    n, s, h, kv, d = 1, 16384, 32, 4, 128
    t = pa.causal_tile(s, None, d)
    assert t == 512 and len(pa.selected_schedule(s, t)) == 528
    assert pa.heads_a_step(h // kv, t, d, s, data=True) == 4
    like = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    q, k, bias = like(n, h, s, d), like(n, kv, s, d), like(n, s, s)
    kw = dict(scale=d ** -0.5, t=t)
    fwd = jax.jit(lambda *a: pa.sel_forward(*a, **kw)).lower(
        q, k, k, bias).compile().as_text()
    bwd = jax.jit(lambda *a: pa.sel_backward(*a, **kw)).lower(
        q, k, k, bias, q, like(n, h, s, dtype=jnp.float32), q).compile().as_text()
    assert "selected_attention_fwd" in fwd and "selected_attention_bwd" in bwd
    assert not re.search(rf"f32\[(\d+,)*{s},{s}\]", fwd + bwd)


@pytest.mark.parametrize("keys", [4096, 8192, 12288, 16384])
def test_the_index_kernels_compile_at_the_cells_shapes(topo, keys):
    """Mosaic takes both directions for a block of 256 queries' sixteen
    index heads of 64 against a band's keys, 1,024 a grid step: the heads'
    products are VMEM's, and what crosses HBM is the operands, `I` and the
    three gradients."""
    from parallel_cnn_tpu.ops import pallas_index as pi

    one_chip = SingleDeviceSharding(topo.devices[0])
    n, h, rows, d = 1, 16, 256, 64
    t = pi.tile(rows, keys, d)
    assert t == 1024
    like = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    operands = (like(n, h, rows, d), like(n, rows, h), like(n, keys, d),
                like(dtype=jnp.int32))
    kw = dict(c=(h * d) ** -0.5, t=t)
    fwd = jax.jit(lambda *a: pi.forward(*a, **kw)).lower(
        *operands).compile().as_text()
    bwd = jax.jit(lambda *a: pi.backward(*a, **kw)).lower(
        *operands, like(n, rows, keys, dtype=jnp.float32)).compile().as_text()
    assert "index_scores_fwd" in fwd and "index_scores_bwd" in bwd
    # the float32 arrays with the heads ahead are the weights (a column a
    # head), `dq` and the weights' partial sums, a register's lanes wide:
    # none is a tile's scores
    assert {int(w) for w in re.findall(
        rf"f32\[{n},{h},{rows},(\d+)\]", fwd + bwd)} <= {d, 128, 1}


def test_an_attention_layer_keeps_the_selection_as_bits_and_no_score_square(topo):
    """The attention of one layer at published widths, forward and backward
    with both of its loss terms' gradients, 1,024 positions and 256 keys a
    query, compiled for the described chip: the arrays of (S, S) are bf16 —
    the bias the bits unpack to, and its transpose for the backward kernel —
    no array has a heads' axis before (S, S), a block of scores is (rows, S),
    and the selection a rematerialised layer keeps is (S, S / 32) words.
    The index scores are ops/pallas_index.py's kernels in every band (256,
    512, 768 and 1,024 keys: all tile), under scopes the benchmark's reader
    counts as the indexer's, and no float32 array holds the sixteen index
    heads by a block's scores."""
    from benchmark import keye_scopes
    from parallel_cnn_tpu.nn import keye_vl
    from parallel_cnn_tpu.obs import programs

    s, d = 1024, 2048
    att = keye_vl.keye_vl2_30b_a3b(
        num_hidden_layers=1, vocab_size=1024, topk=256,
        image_spans=[[256, 1, 16, 16]]).attn
    one_chip = SingleDeviceSharding(topo.devices[0])
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree_util.tree_map(like, jax.eval_shape(
        lambda k: att.init(k, (s, d))[0], jax.random.key(0)))

    def both_terms(p, x):
        run = jax.checkpoint(
            lambda p, x: att.apply(p, {}, x, True),
            policy=jax.checkpoint_policies.save_only_these_names(
                *keye_vl.KeyeVL.kept_names))
        with jax.named_scope("grad"):  # a step's root: obs/programs.py reads under it
            out, report = run(p, x)
            return jnp.sum(out.astype(jnp.float32)) + report["kl"]

    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(both_terms, argnums=(0, 1))).lower(
            params, jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip)
        ).compile().as_text()
    square = set(re.findall(rf"(\w+)\[((?:\d+,)*){s},{s}\]", text))
    assert square and {dtype for dtype, _ in square} <= {"bf16", "pred"}, square
    assert {lead for _, lead in square} <= {"", "1,"}, square
    assert re.search(rf"u32\[1,{s},{s // 32}\]", text)
    assert re.search(rf"f32\[1,256,{s}\]", text)  # a block of index scores
    assert "selected_attention_fwd" in text and "selected_attention_bwd" in text
    assert att.select.scores_tile(s) == 256
    assert not re.search(r"f32\[1,16,256,(256|512|768|1024)\]", text)
    kernels = {way: re.findall(
        rf"%(\S+) = [^\n]*custom-call\([^\n]*index_scores_{way}", text)
        for way in ("fwd", "bwd")}
    catalog = programs.parse(text)
    # a band's forward kernel in the selection and in the objective's
    # backward, beside the backward kernel (this program returns gradients
    # alone: the objective's own forward, a third call in a step, is dead)
    assert len(kernels["fwd"]) == 8 and len(kernels["bwd"]) == 4, kernels
    for name in kernels["fwd"] + kernels["bwd"]:
        assert {"indexer", "scores"} <= set(
            catalog[name].scope.split("/")), catalog[name]
        assert keye_scopes.mechanism(catalog[name]) == "indexer", catalog[name]
    assert {catalog[name].phase for name in kernels["bwd"]} == {"bwd"}
    # exact, and once: the selection is a bisection over the scores' bits
    # (two loops of counts: no sort, nothing approximate), the forward's
    # alone — a rematerialised forward unpacks the bits it kept, and neither
    # it nor the backward scores or selects again
    assert re.search(r'op_name="[^"]*/select/[^"]*while/body', text)
    assert "approx" not in text.lower() and not re.search(r"\bsort\(", text)
    assert re.search(r'op_name="[^"]*rematted[^"]*/indexer/select/concatenate', text)
    assert not re.search(r'op_name="[^"]*(transpose\(|rematted)[^"]*/select/[^"]*while', text)
    assert not re.search(r'op_name="[^"]*rematted[^"]*/indexer/[^"]*scores', text)
