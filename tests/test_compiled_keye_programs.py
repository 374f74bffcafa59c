"""Keye-VL-2.0-30B-A3B's programs for a described v5e (no chip, no run) at
published widths: the scheduled kernel pair takes the selection as an array
at the cell's shapes (four heads a grid step, the causal visit list's 528
tiles), and a layer's step holds the selection as bits — no array of (heads,
S, S) anywhere, no float32 (S, S) array at all, so none that outlives its
layer (tests/compiled_programs.py has what the files share)."""

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


def test_an_attention_layer_keeps_the_selection_as_bits_and_no_score_square(topo):
    """The attention of one layer at published widths, forward and backward
    with both of its loss terms' gradients, 1,024 positions and 256 keys a
    query, compiled for the described chip: the arrays of (S, S) are bf16 —
    the bias the bits unpack to, and its transpose for the backward kernel —
    no array has a heads' axis before (S, S), a block of scores is (rows, S),
    and the selection a rematerialised layer keeps is (S, S / 32) words."""
    from parallel_cnn_tpu.nn import keye_vl

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
    # exact, and once: the selection is a bisection over the scores' bits
    # (two loops of counts: no sort, nothing approximate), the forward's
    # alone — a rematerialised forward unpacks the bits it kept, and neither
    # it nor the backward scores or selects again
    assert re.search(r'op_name="[^"]*/select/[^"]*while/body', text)
    assert "approx" not in text.lower() and not re.search(r"\bsort\(", text)
    assert re.search(r'op_name="[^"]*rematted[^"]*/indexer/select/concatenate', text)
    assert not re.search(r'op_name="[^"]*(transpose\(|rematted)[^"]*/select/[^"]*while', text)
    assert not re.search(r'op_name="[^"]*rematted[^"]*/indexer/[^"]*scores', text)
