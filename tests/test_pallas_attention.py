"""ops/pallas_attention.py on the CPU: the kernels in Pallas interpret
mode (the code the chip runs, tile by tile) against `nn/glm_moe.py`'s
blocked `_attend` and against a one-shot float32 masked softmax; the
`custom_vjp` as a CPU host lowers it (the caller's plain form, by
`lax.platform_dependent`); which shapes tile, and that a model whose
shapes do not says so. The compiled program is held in
tests/test_compiled_glm_sdar_programs.py."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import glm_moe as ref  # noqa: E402
from parallel_cnn_tpu.nn import glm_moe  # noqa: E402
from parallel_cnn_tpu.ops import pallas_attention as pa  # noqa: E402
from token_family import pulled  # noqa: E402

# (N, S, H, D, tile): the issue's shape at the tile the module picks for it
# (one tile, all diagonal), and three tiles a side (tiles under, on and
# above the diagonal; `dq` gathered from several key tiles, `dk` and `dv`
# from several query tiles)
SHAPES = {"one-tile": (2, 512, 2, 128, pa.tile(512, 128, 128)),
          "three-a-side": (1, 384, 2, 128, 128)}


def _mla(h, d, block=128):
    return glm_moe.MLA(heads=h, nope=d - 32, rope_dim=32, v_dim=d, q_block=block)


def _blocks(q, k, v):
    return _mla(q.shape[1], q.shape[-1])._blocks(q, k, v)


def _one_shot(q, k, v):
    s = q.shape[2]
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k, preferred_element_type=jnp.float32,
                        precision="highest") * q.shape[-1] ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    return jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(scores, axis=-1),
                      v.astype(jnp.float32), precision="highest")


REFERENCES = {"blocks": _blocks, "one-shot": _one_shot}


def _draw(shape, dtype=jnp.float32, seed=0):
    n, s, h, d, _ = shape
    return [jax.random.normal(key, (n, h, s, d), jnp.float32).astype(dtype)
            for key in jax.random.split(jax.random.key(seed), 4)]


def _kernels(q, k, v, d_out, t):
    scale = q.shape[-1] ** -0.5
    out, lse = pa.forward(q, k, v, scale=scale, t=t, interpret=True)
    return out, lse, pa.backward(q, k, v, out, lse, d_out, scale=scale, t=t,
                                 interpret=True)


def _gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("reference", list(REFERENCES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_forward_kernel_agrees(shape, reference):
    q, k, v, d_out = _draw(SHAPES[shape])
    out, lse, _ = _kernels(q, k, v, d_out, SHAPES[shape][-1])
    assert out.shape == q.shape and lse.shape == q.shape[:3]
    assert lse.dtype == jnp.float32
    assert _gap(out, REFERENCES[reference](q, k, v)) < 2e-6
    # the rows' log-sum-exp is that of the visible scores
    scores = jnp.einsum("nhqd,nhkd->nhqk", q, k, precision="highest") * 128 ** -0.5
    s = q.shape[2]
    want = jax.nn.logsumexp(
        jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf), axis=-1)
    assert float(jnp.max(jnp.abs(lse - want))) < 1e-5


@pytest.mark.parametrize("reference", list(REFERENCES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_backward_kernel_agrees(shape, reference):
    q, k, v, d_out = _draw(SHAPES[shape])
    _, _, got = _kernels(q, k, v, d_out, SHAPES[shape][-1])
    want = jax.vjp(REFERENCES[reference], q, k, v)[1](d_out)
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _gap(g, w) < 5e-6, name


def test_a_row_whose_only_visible_key_is_itself():
    """Position 0 sees key 0 alone: its output is `v[0]` whatever the
    scores, its log-sum-exp its one score, and keys it does not see take
    no gradient from it."""
    shape = SHAPES["three-a-side"]
    q, k, v, _ = _draw(shape, seed=1)
    only_row_0 = jnp.zeros_like(q).at[:, :, 0].set(1.0)
    out, lse, (dq, dk, dv) = _kernels(q, k, v, only_row_0, shape[-1])
    assert float(jnp.max(jnp.abs(out[:, :, 0] - v[:, :, 0]))) == 0.0
    s00 = jnp.sum(q[:, :, 0] * k[:, :, 0], axis=-1) * 128 ** -0.5
    assert float(jnp.max(jnp.abs(lse[:, :, 0] - s00))) < 1e-5
    assert float(jnp.max(jnp.abs(dv[:, :, 0] - 1.0))) < 1e-6
    assert float(jnp.max(jnp.abs(dv[:, :, 1:]))) == 0.0
    # one key: the softmax is constant, so nothing flows into q or k
    assert float(jnp.max(jnp.abs(dq))) < 1e-6 and float(jnp.max(jnp.abs(dk))) < 1e-6
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(jnp.isfinite(lse)))


def test_bfloat16_inputs_are_accumulated_in_float32():
    """bf16 `q, k, v` (what the model hands over): the kernel's results
    lie within 2e-2 of what float32 inputs of the same values give, the
    scores and sums being float32 either way and `p` rounded to bf16
    before `p v` only."""
    shape = SHAPES["three-a-side"]
    half = _draw(shape, jnp.bfloat16, seed=2)
    full = [a.astype(jnp.float32) for a in half]
    out16, lse16, grads16 = _kernels(*half, shape[-1])
    out32, lse32, grads32 = _kernels(*full, shape[-1])
    assert out16.dtype == jnp.bfloat16 and lse16.dtype == jnp.float32
    assert all(g.dtype == jnp.bfloat16 for g in grads16)
    assert float(jnp.max(jnp.abs(lse16 - lse32))) < 1e-5  # the same float32 scores
    assert _gap(out16, out32) < 2e-2
    for g16, g32 in zip(grads16, grads32):
        assert _gap(g16, g32) < 2e-2


@pytest.mark.parametrize("s,qk,v,want", [
    (4096, 256, 256, 512), (512, 128, 128, 512), (768, 128, 256, 256),
    (384, 128, 128, 128),
    (520, 128, 128, None),    # no tile divides the sequence
    (4096, 192, 256, None),   # a head width that is no multiple of 128 lanes
    (512, 64, 64, None),
    (32768, 256, 256, None),  # dq of one (sequence, head) past its VMEM buffer
], ids=str)
def test_which_shapes_tile(s, qk, v, want):
    assert pa.tile(s, qk, v) == want
    if want:
        side = s // want
        assert pa.tiles_visited(s, want) == side * (side + 1) // 2


def test_the_published_shapes_visit_36_of_64_tiles():
    assert (pa.tile(4096, 256, 256), pa.tiles_visited(4096, 512)) == (512, 36)
    assert pa.tiles_visited(4096, 256) == 136


def _toy(**attn):
    attn = dict(dict(heads=2, q_rank=12, kv_rank=8, nope=96, rope_dim=32,
                     v_dim=128, q_block=8), **attn)
    return glm_moe.GlmMoe(
        vocab=64, hidden=32, dense_width=64, n_layers=2,
        attn=glm_moe.MLA(**attn),
        experts=glm_moe.ExpertLayer(width=16, n_routed=4, per_token=2,
                                    held=(0, 1)), dtype="float32")


@pytest.mark.parametrize("seq,attn,platform,want", [
    (512, {}, "tpu", ("fused", 1, 1)),
    (1024, {}, "tpu", ("fused", 3, 4)),
    (1024, {}, "cpu", ("blocks", 8256, 16384)),      # 128 blocks of 8 queries
    (520, {}, "tpu", ("blocks", 2145, 4225)),        # 65 blocks of 8
    (512, dict(nope=32, v_dim=64), "tpu", ("blocks", 2080, 4096)),
], ids=["fused", "fused-2-a-side", "cpu", "odd-length", "width-64"])
def test_describe_says_which_core_runs_and_what_it_visits(seq, attn, platform, want):
    model = _toy(**attn)
    said = model.describe(4 * seq, seq, platform)
    assert (said["attention_core"], said["attention_tiles_visited"],
            said["attention_tiles_total"]) == want


WIDTHS = {"tile": dict(nope=96, rope_dim=32, v_dim=128),
          "width-64": dict(nope=32, rope_dim=32, v_dim=64)}


@pytest.mark.parametrize("widths", list(WIDTHS))
def test_latent_attention_agrees_with_the_reference_around_either_core(widths):
    """`MLA.apply` — one body, heads ahead of positions — at head widths
    that tile (3 tiles a side: on this host `lax.platform_dependent` takes
    the blocks, the only place the program forks) and at ones that do not
    (no fork in the program at all): values and every gradient against the
    benchmark's independent position-major float32 reference."""
    kw = WIDTHS[widths]
    mla = glm_moe.MLA(heads=2, q_rank=12, kv_rank=8, q_block=128, **kw)
    arch = dict(num_attention_heads=2, rms_norm_eps=mla.eps, rope_theta=mla.theta,
                qk_nope_head_dim=kw["nope"], qk_rope_head_dim=32, kv_lora_rank=8,
                v_head_dim=kw["v_dim"])
    params, _, _ = mla.init(jax.random.key(0), (384, 32))
    params = {n: jax.random.normal(jax.random.key(i), p.shape) * p.shape[0] ** -0.5
              if p.ndim == 2 else 1 + 0.1 * jax.random.normal(jax.random.key(i), p.shape)
              for i, (n, p) in enumerate(sorted(params.items()))}
    x = jax.random.normal(jax.random.key(9), (2, 384, 32))
    d_out = jax.random.normal(jax.random.key(10), (2, 384, 32))
    assert mla.core(384) == (("fused", 128) if widths == "tile" else ("blocks", 128))
    forks = "stablehlo.case" in jax.jit(
        lambda p, x: mla.apply(p, {}, x)[0]).lower(params, x).as_text()
    assert forks == (widths == "tile")
    got, grads = pulled(lambda p, x: mla.apply(p, {}, x)[0], d_out, params, x)
    want, grads_want = pulled(lambda p, x: ref.attention(arch, p, x), d_out, params, x)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads_want)):
        assert _gap(g, w) < 2e-5


def test_the_custom_vjp_on_a_cpu_host_runs_the_callers_plain_form():
    """Shapes that tile, lowered for the CPU: `lax.platform_dependent`
    takes `otherwise` forward and its own vjp backward, no kernel is
    lowered, and values and gradients are the blocked path's."""
    shape = SHAPES["three-a-side"]
    q, k, v, d_out = _draw(shape, seed=3)
    blocks = _mla(2, 128)._blocks

    def fused(q, k, v):
        return pa.causal_attention(q, k, v, 128 ** -0.5, 128, blocks)

    assert "tpu_custom_call" not in jax.jit(fused).lower(q, k, v).as_text()
    got, grads = pulled(fused, d_out, q, k, v)
    want, want_grads = pulled(blocks, d_out, q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    for g, w in zip(grads, want_grads):
        assert _gap(g, w) < 1e-6


def test_a_rematerialised_layer_keeps_the_output_and_the_log_sum_exp(capsys):
    """Under `save_only_these_names("attn_core")` the residuals the
    backward rule reads — `out` and `lse` — are saved by name, so the
    rematerialised backward has no forward core left to run."""
    shape = SHAPES["three-a-side"]
    q, k, v, _ = _draw(shape, seed=4)
    blocks = _mla(2, 128)._blocks

    def loss(q, k, v):
        return jnp.sum(pa.causal_attention(q, k, v, 128 ** -0.5, 128, blocks))

    named = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(pa.RESIDUAL_NAME))
    jax.ad_checkpoint.print_saved_residuals(named, q, k, v)
    kept = capsys.readouterr().out.splitlines()
    # q, k, v as they came, out (jax keeps a named value that is also the
    # function's result behind a `reduce_precision`) and lse: nothing else
    assert sorted(line.split()[0] for line in kept) == (
        ["f32[1,2,384,128]"] * 4 + ["f32[1,2,384]"])
    assert sum("from the argument" in line for line in kept) == 3
    (lse,) = [line for line in kept if line.startswith("f32[1,2,384] ")]
    assert f"named '{pa.RESIDUAL_NAME}'" in lse


# ------------------- block diffusion: grouped key/value heads, a second mask

from parallel_cnn_tpu.nn import sdar_moe  # noqa: E402

# (N, H, KV, l, D, B, tile): grouped heads with two tiles a half (every kind
# of tile: own noised, whole clean, the clean one a noised block boundary
# crosses, the clean diagonal; `dk`/`dv` summed over a group of two); one
# key/value head a query head with wider blocks; one tile a half at the
# tile the module picks
BD_SHAPES = {"grouped": (1, 4, 2, 256, 128, 4, 128),
             "ungrouped-b32": (2, 2, 2, 256, 128, 32, 128),
             "one-tile-a-half": (1, 2, 1, 512, 128, 4, pa.bd_tile(512, 4, 128)),
             # tiles cut into sub-squares of 128 (PR 49): two 512-wide tiles a
             # half at blocks of 32; blocks as wide as a sub-square, so a SAME
             # tile's kept squares need no mask, a BEFORE tile's diagonal ones
             # hold nothing and its first row group has no key in that tile;
             # four sub-squares a 256-wide tile
             "two-tiles-b32-t512": (1, 2, 1, 1024, 128, 32, 512),
             "blocks-of-128-t512": (1, 2, 1, 512, 128, 128, 512),
             "grouped-t256": (1, 4, 2, 256, 128, 4, 256)}


def _bd_draw(shape, dtype=jnp.float32, seed=0):
    n, h, kv, l, d, _, _ = shape
    keys = jax.random.split(jax.random.key(seed), 4)
    make = lambda k, heads: jax.random.normal(  # noqa: E731
        k, (n, heads, 2 * l, d), jnp.float32).astype(dtype)
    return make(keys[0], h), make(keys[1], kv), make(keys[2], kv), make(keys[3], h)


def _bd_plain(shape):
    n, h, kv, l, d, b, _ = shape
    return sdar_moe.GQA(h, kv, d, b, q_block=64)._blocks


def _bd_one_shot(shape):
    n, h, kv, l, d, b, _ = shape

    def attend(q, k, v):
        qg = q.reshape(n, kv, h // kv, 2 * l, d)
        s = jnp.einsum("ncgqd,nckd->ncgqk", qg, k, precision="highest") * d ** -0.5
        s = jnp.where(sdar_moe.allowed(l, b), s, -jnp.inf)
        return jnp.einsum("ncgqk,nckd->ncgqd", jax.nn.softmax(s, axis=-1), v,
                          precision="highest").reshape(q.shape)

    return attend


def _bd_kernels(q, k, v, d_out, shape):
    _, _, _, l, d, b, t = shape
    kw = dict(scale=d ** -0.5, l=l, block=b, t=t, interpret=True)
    out, lse = pa.bd_forward(q, k, v, **kw)
    return out, lse, pa.bd_backward(q, k, v, out, lse, d_out, **kw)


@pytest.mark.parametrize("reference", ["blocks", "one-shot"])
@pytest.mark.parametrize("shape", list(BD_SHAPES))
def test_the_block_diffusion_kernels_agree(shape, reference):
    shape = BD_SHAPES[shape]
    q, k, v, d_out = _bd_draw(shape)
    plain = (_bd_plain if reference == "blocks" else _bd_one_shot)(shape)
    out, lse, got = _bd_kernels(q, k, v, d_out, shape)
    want, want_grads = pulled(plain, d_out, q, k, v)
    assert out.shape == q.shape and lse.shape == q.shape[:3]
    assert _gap(out, want) < 2e-6
    for name, g, w in zip(("dq", "dk", "dv"), got, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _gap(g, w) < 5e-6, name
    # the rows' log-sum-exp is that of the scores the mask allows
    n, h, kv, l, d, b, _ = shape
    scores = jnp.einsum("ncgqd,nckd->ncgqk", q.reshape(n, kv, h // kv, 2 * l, d), k,
                        precision="highest") * d ** -0.5
    lse_want = jax.nn.logsumexp(
        jnp.where(sdar_moe.allowed(l, b), scores, -jnp.inf), axis=-1)
    assert float(jnp.max(jnp.abs(lse - lse_want.reshape(lse.shape)))) < 1e-5


# (query heads a key/value head, of them a grid step): a step's arithmetic
# runs once for the heads it carries (PR 50)
HEADS_A_STEP = [(1, 1), (2, 1), (2, 2), (4, 1), (4, 2), (4, 4)]


@functools.lru_cache(maxsize=None)
def _bd_by_heads(group, heads):
    """(shape, inputs, the kernels' out, lse, (dq, dk, dv)) at two 256-wide
    tiles a half, each cut into sub-squares of 128: every kind of step."""
    shape = (1, group, 1, 512, 128, 4, 256)
    q, k, v, d_out = _bd_draw(shape, seed=5)
    steps = pa.schedule(512, 256)
    assert {kind for _, _, kind in steps} == set(pa.BD_KINDS)
    kw = dict(scale=128 ** -0.5, block=4, t=256, heads=heads, interpret=True)
    out, lse = pa.scheduled_forward(q, k, v, steps, **kw)
    return shape, (q, k, v, d_out), out, lse, pa.scheduled_backward(
        q, k, v, out, lse, d_out, steps, **kw)


@pytest.mark.parametrize("group,heads", HEADS_A_STEP, ids=lambda v: str(v))
def test_the_block_diffusion_kernels_agree_whatever_heads_a_step(group, heads):
    """SAME, FULL, BEFORE and UPTO steps, both directions, the heads of a
    step a head after a head down the rows (forward) and along the lanes
    (backward). Forward and `dq` are the one-head-a-step kernel's to the
    bit — a row's arithmetic is what it was; `dk`, `dv` are one contraction
    over the step's heads where there was one a head."""
    shape, (q, k, v, d_out), out, lse, got = _bd_by_heads(group, heads)
    want, want_grads = pulled(_bd_one_shot(shape), d_out, q, k, v)
    assert _gap(out, want) < 2e-6
    for name, g, w in zip(("dq", "dk", "dv"), got, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert _gap(g, w) < 5e-6, name
    _, _, one, lse_one, (dq_one, _, _) = _bd_by_heads(group, 1)
    assert bool(jnp.all(out == one)) and bool(jnp.all(lse == lse_one))
    assert bool(jnp.all(got[0] == dq_one))


# name: (the call, its arguments, sha256 of `jax.make_jaxpr`'s text forward and
# backward AT THE PARENT, PR 49's tree `cee35c4`, whose grid steps carried one
# head each). To re-derive after a change that means to move the kernels:
# `git archive` the tree to compare with and run this test there.
ONE_HEAD_A_STEP = {
    "block-diffusion": ("bd", dict(l=1024, block=4, t=512),
                        "0a953e869abccdbb", "e644e5a405aa4ab8"),
    "causal": ("gc", dict(window=None, t=512), "5cca8685b629b984", "044c0b1e0d5167af"),
    "window": ("gc", dict(window=1024, t=512), "ca8f38a631ccbca3", "f0643b30646ad640"),
}


@pytest.mark.parametrize("name", list(ONE_HEAD_A_STEP))
def test_a_group_of_one_traces_to_the_kernels_of_one_head_a_step(name):
    """Key/value heads that one query head reads each (`ouro_loop_train`'s
    layout) get the program they had before a step could carry several
    heads: equation for equation, the grid and the index maps included."""
    import hashlib
    call, kw, forward, backward = ONE_HEAD_A_STEP[name]
    q = jax.ShapeDtypeStruct((1, 2, 2048, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, 2, 2048), jnp.float32)
    f, b = {"bd": (pa.bd_forward, pa.bd_backward),
            "gc": (pa.gc_forward, pa.gc_backward)}[call]
    kw = dict(kw, scale=128 ** -0.5)
    assert pa.heads_a_step(1, 512, 128, 2048) == 1
    texts = (str(jax.make_jaxpr(lambda q, k, v: f(q, k, v, **kw))(q, q, q)),
             str(jax.make_jaxpr(lambda *a: b(*a, **kw))(q, q, q, q, lse, q)))
    assert all("pallas_call" in text for text in texts)
    got = tuple(hashlib.sha256(text.encode()).hexdigest()[:16] for text in texts)
    assert got == (forward, backward)


def test_the_first_noised_block_sees_itself_alone():
    """Noised block 0 has no clean block before it: its rows are a softmax
    over their own B noised keys, whatever the clean half holds, and the
    clean keys take no gradient from them."""
    shape = BD_SHAPES["grouped"]
    q, k, v, _ = _bd_draw(shape, seed=1)
    only = jnp.zeros_like(q).at[:, :, :4].set(1.0)
    out, lse, (dq, dk, dv) = _bd_kernels(q, k, v, only, shape)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(jnp.isfinite(lse)))
    s = jnp.einsum("nhqd,nhkd->nhqk", q[:, :, :4], jnp.repeat(k, 2, 1)[:, :, :4],
                   precision="highest") * 128 ** -0.5
    want = jnp.einsum("nhqk,nhkd->nhqd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, 2, 1)[:, :, :4], precision="highest")
    assert _gap(out[:, :, :4], want) < 2e-6
    assert float(jnp.max(jnp.abs(dv[:, :, 4:]))) == 0.0
    assert float(jnp.max(jnp.abs(dk[:, :, 4:]))) == 0.0
    assert float(jnp.max(jnp.abs(dq[:, :, 4:]))) == 0.0


def test_bfloat16_block_diffusion_inputs_are_accumulated_in_float32():
    shape = BD_SHAPES["grouped"]
    half = _bd_draw(shape, jnp.bfloat16, seed=2)
    full = [a.astype(jnp.float32) for a in half]
    out16, lse16, grads16 = _bd_kernels(*half, shape)
    out32, lse32, grads32 = _bd_kernels(*full, shape)
    assert out16.dtype == jnp.bfloat16 and lse16.dtype == jnp.float32
    assert all(g.dtype == jnp.bfloat16 for g in grads16)
    assert float(jnp.max(jnp.abs(lse16 - lse32))) < 1e-5
    assert _gap(out16, out32) < 2e-2
    for g16, g32 in zip(grads16, grads32):
        assert _gap(g16, g32) < 2e-2


@pytest.mark.parametrize("l,block,width,want,visited", [
    (4096, 4, 128, 512, 80),     # the cell: 8 + 36 + 36 of 256 tiles
    (4096, 1024, 128, None, None),  # a block wider than any tile
    (512, 4, 128, 512, 3), (768, 4, 128, 256, 15), (384, 32, 128, 128, 15),
    (4096, 4, 64, None, None),   # a head width that is no multiple of 128 lanes
    (4096, 3, 128, None, None),  # no tile holds whole blocks of 3
    (520, 4, 128, None, None),
    (16384, 4, 128, None, None),  # dk and dv of one head past their VMEM buffers
], ids=str)
def test_which_streams_tile_and_what_they_visit(l, block, width, want, visited):
    assert pa.bd_tile(l, block, width) == want
    if want:
        half = l // want
        assert pa.bd_tiles_visited(l, want) == visited == half * (half + 2)
        steps = pa.schedule(l, want)
        assert len(set(steps)) == len(steps) == visited
        # query-major, and a query tile's first step leaves no row empty
        assert [s[0] for s in steps] == sorted(s[0] for s in steps)
        firsts = {}
        for qi, ki, kind in steps:
            firsts.setdefault(qi, (ki, kind))
        for qi, (ki, kind) in firsts.items():
            assert (kind == pa.SAME and ki == qi) if qi < half else (
                ki == half and kind == (pa.UPTO if qi == half else pa.FULL))


def test_the_schedule_holds_every_allowed_pair_and_no_other_tile():
    l, b, t = 512, 4, 128
    seen = np.asarray(sdar_moe.allowed(l, b))
    tiles = {(qi, ki): kind for qi, ki, kind in pa.schedule(l, t)}
    for qi in range(2 * l // t):
        for ki in range(2 * l // t):
            part = seen[qi * t: (qi + 1) * t, ki * t: (ki + 1) * t]
            assert part.any() == ((qi, ki) in tiles)
            if (qi, ki) in tiles:
                assert part.all() == (tiles[qi, ki] == pa.FULL)
    assert pa.bd_tiles_visited(4096, 512) == 80
    assert 80 * 512 * 512 / (4096 * 4100) == pytest.approx(1.2488, abs=1e-4)


# ------------------- a boundary tile's allowed sub-squares only (PR 49)

def _tile_rule(kind, block, t):
    """bool (t, t), queries down: a tile's own mask, from its local blocks."""
    qb, kb = np.arange(t)[:, None] // block, np.arange(t)[None, :] // block
    return {pa.FULL: np.ones((t, t), bool), pa.SAME: kb == qb, pa.BEFORE: kb < qb,
            pa.UPTO: kb <= qb, pa.AFTER: kb > qb}[kind] & np.ones((t, t), bool)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("t", [128, 256, 512])
@pytest.mark.parametrize("block", [1, 4, 32, 128])
@pytest.mark.parametrize("kind", [pa.FULL, pa.SAME, pa.BEFORE, pa.UPTO, pa.AFTER],
                         ids=["FULL", "SAME", "BEFORE", "UPTO", "AFTER"])
def test_the_kept_sub_squares_are_the_ones_the_mask_reaches(kind, block, t, backward):
    """Against the mask pair by pair: no allowed pair lies in a sub-square
    the step skips, no excluded pair in one it computes unmasked; the
    rectangles a step computes are those squares, each once."""
    sub, kept = pa.sub_squares(kind, block, t, backward)
    assert t % sub == 0 and sub % 128 == 0 and (sub % block == 0 or kind == pa.FULL)
    # cut at 128 backward, and forward where a quarter of the tile is left
    assert sub == (128 if kind != pa.FULL and (backward or kind == pa.SAME) else t)
    seen = _tile_rule(kind, block, t)
    for a in range(t // sub):
        for b in range(t // sub):
            part = seen[a * sub:(a + 1) * sub, b * sub:(b + 1) * sub]
            assert part.any() == ((a, b) in kept), (a, b)
            if (a, b) in kept:
                assert kept[a, b] == (not part.all()), (a, b)
    if block < 128 and sub < t:  # the issue's table
        n = t // sub
        want = {pa.SAME: {(a, a) for a in range(n)},
                pa.AFTER: {(a, b) for a in range(n) for b in range(a, n)}}.get(
            kind, {(a, b) for a in range(n) for b in range(a + 1)})
        assert set(kept) == want
        assert {ab for ab, masked in kept.items() if masked} == {
            (a, a) for a in range(n)}
    covered = np.zeros((t, t), int)
    side, parts = pa._parts(kind, block, t, backward)
    assert side == sub and len(parts) <= t // sub
    for rows, keys, masked in parts:
        covered[rows, keys] += 1
        assert all(kept[(rows.start + r) // sub, (keys.start + c) // sub]
                   for r, c in masked)
    assert sum(len(masked) for _, _, masked in parts) == sum(kept.values())
    assert np.array_equal(covered > 0, np.kron(
        np.array([[(a, b) in kept for b in range(t // sub)]
                  for a in range(t // sub)]), np.ones((sub, sub), bool)))
    assert covered.max() == (1 if kept else 0)  # (a BEFORE tile of one block: nothing)


@pytest.mark.parametrize("cell,steps,block,areas,forward,allowed", [
    ("sdar_bd_train", pa.schedule(4096, 512), 4, 68, 74, 4096 * 4100),
    ("trinity_mini_train-window", pa.causal_schedule(16384, 512, 2048), 1, 127.5,
     150, 31_458_304),
    ("trinity_mini_train-full", pa.causal_schedule(16384, 512), 1, 516, 528,
     134_225_920),
    ("ouro_loop_train", pa.causal_schedule(4096, 512), 1, 33, 36, 4096 * 4097 // 2),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_pairs_the_kernels_compute_at_the_cells_shapes(cell, steps, block, areas,
                                                           forward, allowed):
    """Tile areas of 512 x 512 a (sequence, head), backward: 56 + 8 x 4/16
    + 16 x 10/16 of the 80 steps of the block-diffusion schedule, 127.5 of
    150 under the window, 516 of 528 and 33 of 36 under the causal rule.
    Forward only the SAME tiles are cut: 56 + 8 x 4/16 + 16, and every
    step of a causal schedule whole."""
    got = pa.pairs_computed(steps, block, 512, backward=True)
    assert got == areas * 512 * 512
    assert allowed <= got <= len(steps) * 512 * 512
    assert got / allowed == pytest.approx(
        {68: 1.0615, 127.5: 1.0625, 516: 1.0078, 33: 1.031}[areas], abs=1e-4)
    assert pa.pairs_computed(steps, block, 512, backward=False) == forward * 512 * 512
    assert forward == (len(steps) if block == 1 else len(steps) - 8 * 12 // 16)


@pytest.mark.parametrize("group,t,width,s,want", [
    (8, 512, 128, 8192, 8),    # sdar_bd_train
    (8, 512, 128, 16384, 8),   # trinity_mini_train, either kind of layer: 64 MB to the byte
    (1, 512, 128, 4096, 1),    # ouro_loop_train: a head a step, the kernels it had
    (8, 512, 256, 8192, 4),    # heads twice as wide leave room for four
    (8, 512, 256, 4096, 8), (16, 512, 128, 8192, 8), (4, 512, 128, 8192, 4),
    (6, 512, 128, 8192, 2), (3, 512, 128, 8192, 1), (8, 128, 128, 512, 8),
], ids=str)
def test_heads_a_step_is_a_function_of_the_shapes(group, t, width, s, want):
    """The largest of 8, 4, 2 that divides the group and whose backward step
    — the `dk`, `dv` accumulators and their output blocks, the heads' `q`,
    `d_out`, `dq` blocks, the pass's scores — fits `VMEM_LIMIT_BYTES`."""
    assert pa.heads_a_step(group, t, width, s) == want
    for g in range(1, 17):
        got = pa.heads_a_step(g, t, width, s)
        assert g % got == 0 and 1 <= got <= 8
    assert pa.heads_a_step(1, t, width, s) == 1


def _cells_models():
    from parallel_cnn_tpu.nn import afmoe, ouro
    return {
        "sdar_bd_train": (lambda: sdar_moe.sdar_30b_a3b(
            num_hidden_layers=6, vocab_size=18992, held_experts=range(16),
            row_buffer=65536, gate_gradient=False), (4 * 4096, 4096)),
        "trinity_mini_train": (lambda: afmoe.trinity_mini(
            layer_types=[afmoe.SLIDING, afmoe.FULL], num_dense_layers=1,
            vocab_size=25024, held_experts=range(16), row_buffer=32768,
            gate_gradient=False), (16384, 16384)),
        "ouro_loop_train": (lambda: ouro.ouro_2_6b(num_hidden_layers=8), (8192, 4096)),
    }


@pytest.mark.parametrize("cell,heads,steps,backward,forward", [
    ("sdar_bd_train", 8, 80, 68, 74),
    ("trinity_mini_train", {"sliding_attention": 8, "full_attention": 8},
     {"sliding_attention": 150, "full_attention": 528},
     {"sliding_attention": 127.5, "full_attention": 516},
     {"sliding_attention": 150, "full_attention": 528}),
    ("ouro_loop_train", 1, 36, 33, 36),
], ids=lambda v: v if isinstance(v, str) else "")
def test_describe_says_the_heads_a_step_and_counts_steps_a_head(cell, heads, steps,
                                                               backward, forward):
    """`attention_heads_a_step` is new (PR 50); the steps stay schedule steps
    a (sequence, head) and the pairs what one head's kernels execute,
    however many heads a grid step carries: the benchmark's readers and
    rooflines read the same work."""
    make, sizes = _cells_models()[cell]
    said, plain = (make().describe(*sizes, platform) for platform in ("tpu", "cpu"))
    area = 512 * 512
    if isinstance(heads, dict):
        assert said["attention_heads_a_step_by_kind"] == heads
        assert said["attention_heads_a_step"] == heads["full_attention"]
        assert said["attention_tiles_visited_by_kind"] == steps
        assert said["attention_pairs_computed_by_kind"] == {
            kind: int(n * area) for kind, n in backward.items()}
        assert said["attention_pairs_computed_forward_by_kind"] == {
            kind: n * area for kind, n in forward.items()}
        assert set(plain["attention_heads_a_step_by_kind"].values()) == {1}
    else:
        assert (said["attention_heads_a_step"], said["attention_tiles_visited"],
                said["attention_pairs_computed"],
                said["attention_pairs_computed_forward"]) == (
            heads, steps, backward * area, forward * area)
    assert said["attention_tile"] == 512 and said["attention_core"] == "fused"
    assert plain["attention_heads_a_step"] == 1  # no grid on the plain path


def test_the_block_diffusion_custom_vjp_on_a_cpu_host_runs_the_plain_form():
    shape = BD_SHAPES["grouped"]
    q, k, v, d_out = _bd_draw(shape, seed=3)
    plain = _bd_plain(shape)

    def fused(q, k, v):
        return pa.block_diffusion_attention(q, k, v, 128 ** -0.5, 256, 4, 128, plain)

    assert "tpu_custom_call" not in jax.jit(fused).lower(q, k, v).as_text()
    got, grads = pulled(fused, d_out, q, k, v)
    want, want_grads = pulled(plain, d_out, q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    for g, w in zip(grads, want_grads):
        assert _gap(g, w) < 1e-6


def test_a_rematerialised_block_diffusion_layer_keeps_out_and_lse(capsys):
    shape = BD_SHAPES["grouped"]
    q, k, v, _ = _bd_draw(shape, seed=4)
    plain = _bd_plain(shape)

    def loss(q, k, v):
        return jnp.sum(pa.block_diffusion_attention(
            q, k, v, 128 ** -0.5, 256, 4, 128, plain))

    named = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(pa.RESIDUAL_NAME))
    jax.ad_checkpoint.print_saved_residuals(named, q, k, v)
    kept = capsys.readouterr().out.splitlines()
    assert sorted(line.split()[0] for line in kept) == (
        ["f32[1,2,512,128]"] * 2 + ["f32[1,4,512,128]"] * 2 + ["f32[1,4,512]"])
    assert sum("from the argument" in line for line in kept) == 3
    (lse,) = [line for line in kept if line.startswith("f32[1,4,512] ")]
    assert f"named '{pa.RESIDUAL_NAME}'" in lse


def test_grouped_attention_forks_only_where_the_shapes_tile():
    fused = sdar_moe.GQA(heads=4, kv_heads=2, head_dim=128, block=4, q_block=128)
    plain = sdar_moe.GQA(heads=4, kv_heads=2, head_dim=64, block=4, q_block=128)
    assert fused.core(256) == ("fused", 256) and plain.core(256) == ("blocks", 128)
    assert fused.core(260) == ("blocks", 260)  # no turn of whole blocks divides it
    for att, forks in ((fused, True), (plain, False)):
        p, _, _ = att.init(jax.random.key(0), (512, 32))
        x = jnp.zeros((1, 512, 32))
        text = jax.jit(lambda p, x: att.apply(p, {}, x)[0]).lower(p, x).as_text()
        assert ("stablehlo.case" in text) == forks
