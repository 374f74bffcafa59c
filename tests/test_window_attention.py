"""The causal and windowed schedules of ops/pallas_attention.py's second
kernel pair on the CPU (`grouped_causal_attention`): the kernels in Pallas
interpret mode (the code the chip runs, tile by tile) against the mask
written as its rule over the whole square, for no window, a window of one
tile and of four, with and without grouped key/value heads; the schedule
against a brute-force count of the tiles that hold an allowed pair; the
window's far edge to the key; which shapes tile; the `custom_vjp` as a CPU
host lowers it; and that the block-diffusion call's tables are what they
were. The compiled program is held in tests/test_compiled_trinity_ling_programs.py's
neighbours and on the chip (PERF.md section 6, PR 41)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.reference import afmoe as ref  # noqa: E402
from parallel_cnn_tpu.nn import afmoe  # noqa: E402
from parallel_cnn_tpu.ops import pallas_attention as pa  # noqa: E402
from token_family import pulled  # noqa: E402

T = 128
# name: (S, window) at tiles of 128 — no window; a window of one tile (the
# diagonal and the trailing edge, nothing between); a window of four
WINDOWS = {"none": (512, None), "one-tile": (512, 128), "four-tiles": (768, 512)}
GROUPS = {"group-1": (2, 2), "group-8": (8, 1)}  # (H, KV)
# name: (S, window, tile) — tiles the kernels cut into sub-squares of 128 (PR
# 49): the diagonal's and the trailing edge's are computed in part
CUT = {"cut-256-two-tiles": (1024, 512, 256), "cut-512-two-tiles": (1536, 1024, 512),
       "cut-512-none": (1024, None, 512)}


def _rule(s, window):
    """bool (S, S): the mask as the issue writes it."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) if window is None else (j <= i) & (i - window < j)


def _one_shot(window):
    def attend(q, k, v):
        n, h, s, d = q.shape
        kv = k.shape[1]
        qg = q.reshape(n, kv, h // kv, s, d)
        sc = jnp.einsum("ncgqd,nckd->ncgqk", qg, k, precision="highest") * d ** -0.5
        sc = jnp.where(_rule(s, window), sc, -jnp.inf)
        return jnp.einsum("ncgqk,nckd->ncgqd", jax.nn.softmax(sc, axis=-1), v,
                          precision="highest").reshape(q.shape)

    return attend


def _draw(s, h, kv, dtype=jnp.float32, seed=0, d=128):
    keys = jax.random.split(jax.random.key(seed), 4)
    make = lambda k, heads: jax.random.normal(  # noqa: E731
        k, (1, heads, s, d), jnp.float32).astype(dtype)
    return make(keys[0], h), make(keys[1], kv), make(keys[2], kv), make(keys[3], h)


def _kernels(q, k, v, d_out, window, t=T):
    kw = dict(scale=q.shape[-1] ** -0.5, window=window, t=t, interpret=True)
    out, lse = pa.gc_forward(q, k, v, **kw)
    return out, lse, pa.gc_backward(q, k, v, out, lse, d_out, **kw)


def _gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("group", list(GROUPS))
@pytest.mark.parametrize("window", list(WINDOWS) + list(CUT))
def test_the_kernels_agree_with_the_plain_rule(window, group):
    """Forward and all three gradients; `dk`, `dv` summed over the group."""
    (s, w, *tile), (h, kv) = {**WINDOWS, **CUT}[window], GROUPS[group]
    q, k, v, d_out = _draw(s, h, kv)
    out, lse, got = _kernels(q, k, v, d_out, w, *tile)
    want, want_grads = pulled(_one_shot(w), d_out, q, k, v)
    assert out.shape == q.shape and lse.shape == q.shape[:3]
    assert _gap(out, want) < 2e-6
    for name, g, x in zip(("dq", "dk", "dv"), got, want_grads):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert _gap(g, x) < 5e-6, name
    scores = jnp.einsum("ncgqd,nckd->ncgqk", q.reshape(1, kv, h // kv, s, 128), k,
                        precision="highest") * 128 ** -0.5
    lse_want = jax.nn.logsumexp(jnp.where(_rule(s, w), scores, -jnp.inf), axis=-1)
    assert float(jnp.max(jnp.abs(lse - lse_want.reshape(lse.shape)))) < 1e-5


@functools.lru_cache(maxsize=None)
def _by_heads(group, heads):
    """(inputs, the kernels' out, lse, (dq, dk, dv)) under a window of two
    256-wide tiles over four, each boundary tile cut into sub-squares of
    128: every kind of step."""
    q, k, v, d_out = _draw(1024, group, 1, seed=6)
    steps, call = pa._causal_call(1024, 256, 512)
    assert call["kinds"] == (pa.FULL, pa.UPTO, pa.AFTER)
    kw = dict(call, scale=128 ** -0.5, heads=heads, interpret=True)
    out, lse = pa.scheduled_forward(q, k, v, steps, **kw)
    return (q, k, v, d_out), out, lse, pa.scheduled_backward(
        q, k, v, out, lse, d_out, steps, **kw)


@pytest.mark.parametrize("group,heads", [(1, 1), (2, 1), (2, 2), (4, 1), (4, 2), (4, 4)],
                         ids=lambda v: str(v))
def test_the_kernels_agree_whatever_heads_a_step(group, heads):
    """UPTO, FULL and AFTER steps, both directions, `heads` query heads of
    the key/value head a grid step (PR 50). Forward and `dq` are the
    one-head-a-step kernel's to the bit."""
    (q, k, v, d_out), out, lse, got = _by_heads(group, heads)
    want, want_grads = pulled(_one_shot(512), d_out, q, k, v)
    assert _gap(out, want) < 2e-6
    for name, g, x in zip(("dq", "dk", "dv"), got, want_grads):
        assert g.shape == x.shape and g.dtype == x.dtype
        assert _gap(g, x) < 5e-6, name
    _, one, lse_one, (dq_one, _, _) = _by_heads(group, 1)
    assert bool(jnp.all(out == one)) and bool(jnp.all(lse == lse_one))
    assert bool(jnp.all(got[0] == dq_one))


@pytest.mark.parametrize("window", list(WINDOWS))
def test_the_models_plain_path_is_the_same_rule(window):
    """`GatedGQA._blocks` (what `otherwise` is on a host that is no TPU), a
    block of queries against the keys it may see, and the reference's
    mask."""
    s, w = WINDOWS[window]
    q, k, v, d_out = _draw(s, 4, 2, seed=1)
    att = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=128, window=w, q_block=64)
    got, grads = pulled(att._blocks, d_out, q, k, v)
    want, want_grads = pulled(_one_shot(w), d_out, q, k, v)
    assert _gap(got, want) < 2e-6
    for g, x in zip(grads, want_grads):
        assert _gap(g, x) < 5e-6
    assert np.array_equal(np.asarray(ref.seen(s, w, 0, s)), _rule(s, w))


def test_a_trailing_tile_that_leaves_whole_row_groups_without_a_key():
    """The pair under the window's schedule at blocks of 128, the side of a
    sub-square: query block I sees key block J iff I - 8 < J <= I. An
    AFTER tile's last row group has no key in it (the step skips the group:
    its statistics and accumulator stay), its diagonal sub-squares hold
    nothing, and an UPTO tile's are whole."""
    s, t, window, block = 1536, 512, 1024, 128
    steps = pa.causal_schedule(s, t, window)
    sub, kept = pa.sub_squares(pa.AFTER, block, t, backward=True)
    assert sub == 128 and not any(a == 3 for a, _ in kept) and len(kept) == 6
    assert not any(kept.values())
    assert pa.sub_squares(pa.UPTO, block, t, backward=True)[1] == {
        (a, b): False for a in range(4) for b in range(a + 1)}
    # (forward both tiles are computed whole, under the mask)
    assert pa.sub_squares(pa.AFTER, block, t, backward=False) == (512, {(0, 0): True})
    q, k, v, d_out = _draw(s, 4, 2, seed=7)
    kw = dict(scale=128 ** -0.5, block=block, t=t, interpret=True,
              kinds=(pa.FULL, pa.UPTO, pa.AFTER))
    out, lse = pa.scheduled_forward(q, k, v, steps, **kw)
    got = pa.scheduled_backward(q, k, v, out, lse, d_out, steps, **kw)
    ib, jb = np.arange(s)[:, None] // block, np.arange(s)[None, :] // block
    rule = (jb <= ib) & (ib - window // block < jb)

    def plain(q, k, v):
        qg = q.reshape(1, 2, 2, s, 128)
        sc = jnp.einsum("ncgqd,nckd->ncgqk", qg, k, precision="highest") * 128 ** -0.5
        p = jax.nn.softmax(jnp.where(rule, sc, -jnp.inf), axis=-1)
        return jnp.einsum("ncgqk,nckd->ncgqd", p, v, precision="highest").reshape(q.shape)

    want, want_grads = pulled(plain, d_out, q, k, v)
    assert _gap(out, want) < 2e-6
    for name, g, x in zip(("dq", "dk", "dv"), got, want_grads):
        assert _gap(g, x) < 5e-6, name


def test_bfloat16_inputs_are_accumulated_in_float32():
    s, w = WINDOWS["four-tiles"]
    half = _draw(s, 8, 1, jnp.bfloat16, seed=2)
    full = [a.astype(jnp.float32) for a in half]
    out16, lse16, grads16 = _kernels(*half, w)
    out32, lse32, grads32 = _kernels(*full, w)
    assert out16.dtype == jnp.bfloat16 and lse16.dtype == jnp.float32
    assert all(g.dtype == jnp.bfloat16 for g in grads16)
    assert float(jnp.max(jnp.abs(lse16 - lse32))) < 1e-5
    assert _gap(out16, out32) < 2e-2
    for g16, g32 in zip(grads16, grads32):
        assert _gap(g16, g32) < 2e-2


# ------------------------------------------------------------ the schedule

@pytest.mark.parametrize("s,t,window", [
    (512, 128, None), (512, 128, 128), (768, 128, 512), (1024, 256, 256),
    (2048, 512, 512), (16384, 512, 2048), (16384, 512, None), (4096, 512, 4096),
], ids=str)
def test_the_schedule_is_the_tiles_that_hold_an_allowed_pair(s, t, window):
    steps = pa.causal_schedule(s, t, window)
    tiles = {(qi, ki): kind for qi, ki, kind in steps}
    assert len(tiles) == len(steps) == pa.causal_tiles_visited(s, t, window)
    n = s // t
    # brute force, a tile's corners: it holds an allowed pair iff its
    # nearest pair is allowed, and is whole iff its farthest ones are
    w = s if window is None else window
    for qi in range(n):
        for ki in range(n):
            q_lo, q_hi, k_lo, k_hi = qi * t, qi * t + t - 1, ki * t, ki * t + t - 1
            any_pair = k_lo <= q_hi and q_lo - w < k_hi
            every = k_hi <= q_lo and q_hi - w < k_lo
            assert any_pair == ((qi, ki) in tiles), (qi, ki)
            if any_pair:
                assert every == (tiles[qi, ki] == pa.FULL), (qi, ki)
    # query-major, a query tile's own tile first (every row sees its own
    # key there), the trailing edge last
    assert [st[0] for st in steps] == sorted(st[0] for st in steps)
    firsts = {}
    for qi, ki, kind in steps:
        firsts.setdefault(qi, (ki, kind))
    assert all(first == (qi, pa.UPTO) for qi, first in firsts.items())
    edges = [(qi, ki) for qi, ki, kind in steps if kind == pa.AFTER]
    assert edges == [(qi, qi - w // t) for qi in range(w // t, n)]
    # no tile wholly outside the band is a grid step
    assert all(qi - w // t <= ki <= qi for qi, ki, _ in steps)


def test_the_brute_force_count_of_small_squares_pair_by_pair():
    for s, t, window in ((512, 128, None), (512, 128, 128), (768, 128, 512),
                         (1024, 256, 256)):
        seen = _rule(s, window)
        got = {(qi, ki) for qi, ki, _ in pa.causal_schedule(s, t, window)}
        want = {(qi, ki) for qi in range(s // t) for ki in range(s // t)
                if seen[qi * t:(qi + 1) * t, ki * t:(ki + 1) * t].any()}
        assert got == want


def test_the_cells_window_visits_a_quarter_over_what_it_allows():
    """16,384 positions, a window of 2,048, tiles of 512: 150 tiles against
    the 528 of the causal triangle; 1.25 times the pairs the window allows,
    where walking the triangle would compute 4.4 times."""
    assert pa.causal_tile(16384, 2048, 128) == 512 == pa.causal_tile(16384, None, 128)
    assert pa.causal_tiles_visited(16384, 512, 2048) == 150
    assert pa.causal_tiles_visited(16384, 512) == 528 == pa.tiles_visited(16384, 512)
    assert afmoe.pairs_allowed(16384, 2048) == 31_458_304
    assert afmoe.pairs_allowed(16384, None) == 134_225_920
    assert 150 * 512 * 512 / 31_458_304 == pytest.approx(1.25, abs=1e-3)
    assert 528 * 512 * 512 / 31_458_304 > 4.3


@pytest.mark.parametrize("s,window,width,want", [
    (16384, 2048, 128, 512),   # the cell: dk and dv exactly fill their buffers
    (16384, None, 128, 512),
    (32768, 2048, 128, None),  # dk and dv of one head past their VMEM buffers
    (16384, 2048, 64, None),   # a head width that is no multiple of 128 lanes
    (16384, 1000, 128, None),  # the window's far end on no tile's edge
    (768, 384, 128, 128), (1024, 256, 128, 256), (520, None, 128, None),
    (512, 0, 128, None),
], ids=str)
def test_which_shapes_tile(s, window, width, want):
    assert pa.causal_tile(s, window, width) == want


def test_a_call_dispatches_over_the_kinds_its_schedule_holds():
    kinds = lambda s, t, w: pa._causal_call(s, t, w)[1]["kinds"]  # noqa: E731
    assert kinds(512, 128, None) == (pa.FULL, pa.UPTO)
    assert kinds(512, 128, 128) == (pa.UPTO, pa.AFTER)  # nothing between
    assert kinds(768, 128, 512) == (pa.FULL, pa.UPTO, pa.AFTER)
    assert kinds(128, 128, None) == (pa.UPTO,)
    # ... and the block-diffusion call over its own four, its tables what
    # they were: the kind itself beside the two flags
    assert pa.BD_KINDS == (pa.FULL, pa.SAME, pa.BEFORE, pa.UPTO) == (0, 1, 2, 3)
    steps = pa.schedule(256, 128)
    qt, kt, what = (np.asarray(a) for a in pa._tables(steps, pa.BD_KINDS))
    assert [tuple(r) for r in zip(qt, kt, what & 3)] == steps
    firsts = [i == 0 or steps[i - 1][0] != st[0] for i, st in enumerate(steps)]
    lasts = [i == len(steps) - 1 or steps[i + 1][0] != st[0]
             for i, st in enumerate(steps)]
    assert list((what & 4) != 0) == firsts and list((what & 8) != 0) == lasts
    assert pa.bd_tiles_visited(4096, 512) == 80


# ------------------------------------------------- the window's far edge

def _reach(dv_row_norms):
    return np.nonzero(np.asarray(dv_row_norms) > 0)[0]


@pytest.mark.parametrize("path", ["kernel", "blocks", "reference"])
def test_query_i_sees_key_i_minus_window_plus_one_and_not_the_one_before(path):
    """A window of w keys ends with the query's own: key i - (w - 1) is
    seen, key i - w is not — read off `dv`, the keys a single query's
    output depends on."""
    s, w, i = 512, 256, 300
    q, k, v, _ = _draw(s, 2, 1, seed=3)
    only = jnp.zeros_like(q).at[:, :, i].set(1.0)
    if path == "kernel":
        dv = _kernels(q, k, v, only, w)[2][2]
    elif path == "blocks":
        att = afmoe.GatedGQA(heads=2, kv_heads=1, head_dim=128, window=w, q_block=64)
        dv = jax.vjp(att._blocks, q, k, v)[1](only)[2]
    else:
        swap = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731 (N, S, heads, D)
        dv = swap(jax.vjp(
            lambda v: ref._attend(swap(q), swap(k), v, 0, w), swap(v))[1](swap(only))[0])
    keys = _reach(jnp.linalg.norm(dv[0, 0], axis=-1))
    assert keys[0] == i - (w - 1) == 45 and keys[-1] == i
    assert len(keys) == w
    # the cell's numbers: query i sees key i - 2047, not i - 2048
    seen = np.asarray(ref.seen(8192, 2048, 4096, 1))[0]
    assert seen[4096 - 2047] and not seen[4096 - 2048] and seen[4096]
    assert not seen[4097] and seen.sum() == 2048


def test_an_early_query_sees_what_there_is():
    """Query i < window sees keys 0..i; no row of any tile is left without
    a visible key (the online softmax starts from the diagonal)."""
    s, w = WINDOWS["four-tiles"]
    q, k, v, _ = _draw(s, 2, 1, seed=4)
    out, lse, _ = _kernels(q, k, v, jnp.zeros_like(q), w)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.all(jnp.isfinite(lse)))
    assert _gap(out[:, :, 0], jnp.broadcast_to(v[:, :, 0], out[:, :, 0].shape)) < 1e-6


# ------------------------------------------------------- the custom_vjp

def test_the_custom_vjp_on_a_cpu_host_runs_the_plain_form():
    s, w = WINDOWS["four-tiles"]
    q, k, v, d_out = _draw(s, 4, 2, seed=5)
    plain = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=128, window=w,
                           q_block=128)._blocks

    def fused(q, k, v):
        return pa.grouped_causal_attention(q, k, v, 128 ** -0.5, w, 128, plain)

    text = jax.jit(fused).lower(q, k, v).as_text()
    assert "tpu_custom_call" not in text and "stablehlo.case" in text
    got, grads = pulled(fused, d_out, q, k, v)
    want, want_grads = pulled(plain, d_out, q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0
    for g, x in zip(grads, want_grads):
        assert _gap(g, x) < 1e-6


def test_a_rematerialised_layer_keeps_out_and_lse(capsys):
    s, w = WINDOWS["one-tile"]
    q, k, v, _ = _draw(s, 4, 2, seed=6)
    plain = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=128, window=w,
                           q_block=128)._blocks

    def loss(q, k, v):
        return jnp.sum(pa.grouped_causal_attention(
            q, k, v, 128 ** -0.5, w, 128, plain))

    named = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(pa.RESIDUAL_NAME))
    jax.ad_checkpoint.print_saved_residuals(named, q, k, v)
    kept = capsys.readouterr().out.splitlines()
    assert sorted(line.split()[0] for line in kept) == (
        ["f32[1,2,512,128]"] * 2 + ["f32[1,4,512,128]"] * 2 + ["f32[1,4,512]"])
    (lse,) = [line for line in kept if line.startswith("f32[1,4,512] ")]
    assert f"named '{pa.RESIDUAL_NAME}'" in lse


def test_attention_forks_only_where_the_shapes_tile():
    fused = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=128, window=128, q_block=128)
    plain = afmoe.GatedGQA(heads=4, kv_heads=2, head_dim=64, window=128, q_block=128)
    assert fused.core(256) == ("fused", 128) and plain.core(256) == ("blocks", 128)
    assert fused.core(260) == ("blocks", 260)
    assert fused.tiles_visited(512, "tpu") == (7, 128)  # 1 + 2 + 2 + 2
    assert fused.tiles_visited(512, "cpu") == (7, 128)  # turns of 128: 255 keys
    for att, forks in ((fused, True), (plain, False)):
        p, _, _ = att.init(jax.random.key(0), (256, 32))
        x = jnp.zeros((1, 256, 32))
        text = jax.jit(lambda p, x: att.apply(p, {}, x)[0]).lower(p, x).as_text()
        assert ("stablehlo.case" in text) == forks
