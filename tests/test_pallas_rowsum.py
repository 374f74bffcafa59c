"""ops/pallas_rowsum.py — the sum of a token's rows out of an expert
layer's row buffer — on the CPU: the kernel in interpret mode at toy tile
sizes, through the two `custom_vjp`s of nn/glm_moe.py that run it
(`_combine` forward, `_gather_rows` backward), against the plain
gather-and-sum the layer ran until PR 37 and autodiff of `x[idx]`. That
Mosaic takes the kernel at the cells' shapes, and where it sits in a
compiled step, is tests/test_compiled_glm_sdar_programs.py's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_glm_moe import _plain_combine, one_rounding

from parallel_cnn_tpu.nn import glm_moe
from parallel_cnn_tpu.ops import pallas_rowsum

K, D = 3, 128
HELD = (7, 0, 5, 9)
LAYER = glm_moe.ExpertLayer(width=16, n_routed=12, per_token=K, held=HELD)
TOY = pallas_rowsum.Tiles(tb=16, w=8, g=2)


def _draw(key, t, among):
    """Assignments (T, k) among the experts `among`, one at most once."""
    return jnp.asarray(among)[jax.lax.top_k(
        jax.random.normal(jax.random.key(key), (t, len(among))), K)[1]]


def _case(name):
    """(ids (T, k), the buffer's rows, the tiles the plan is made for)."""
    everyone, absent = list(range(12)), [i for i in range(12) if i not in HELD]
    if name == "balanced":
        return _draw(4, 64, everyone), 64 * K, TOY
    if name == "an_expert_with_no_row":
        return _draw(5, 64, [i for i in everyone if i != 5]), 64 * K, TOY
    if name == "one_expert_holds_every_live_row":
        # every token's second choice is held expert 5, its others are absent
        return _draw(6, 64, absent).at[:, 1].set(5), 64 * K, TOY
    if name == "a_token_in_several_held_experts":
        return _draw(7, 64, list(HELD)), 64 * K, TOY
    if name == "overflow":
        return _draw(4, 64, everyone), 30, TOY
    if name == "rows_and_starts_off_the_window":
        return _draw(8, 64, everyone), 61, TOY
    if name == "tokens_off_the_tile":
        return _draw(9, 72, everyone), 72 * K, None
    raise KeyError(name)


CASES = ["balanced", "an_expert_with_no_row", "one_expert_holds_every_live_row",
         "a_token_in_several_held_experts", "overflow",
         "rows_and_starts_off_the_window", "tokens_off_the_tile"]


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel in interpret mode wherever a schedule exists, and a count
    of its runs (the platform would send a CPU to `otherwise`)."""
    ran = []

    def token_sums(x, weight, sched, otherwise):
        ran.append(weight is not None)
        return pallas_rowsum.sums(x, weight, sched, interpret=True)

    monkeypatch.setattr(pallas_rowsum, "token_sums", token_sums)
    return ran


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_sum_of_a_tokens_rows_is_the_plain_gather_and_sum(
        case, dtype, interpreted):
    """Forward and both gradients of `_combine`, and the gradient of
    `_gather_rows`, are those of the plain expressions, whatever the plan:
    the output and the tokens' gradient to the order of a float32 sum
    rounded once, the rows' gradient to the bit. Dead rows hold NaN and
    nothing leaks; overflow is dropped and counted as before; the windows
    read hold every live row."""
    ids, rows, tl = _case(case)
    t = ids.shape[0]
    assert (t % TOY.tb != 0) == (tl is None)
    plan = LAYER.plan(ids, rows, tl)
    live = int(plan.row_live.sum())
    held = int(np.isin(np.asarray(ids), HELD).sum())
    assert live == min(held, rows) and int(plan.overflow) == held - live
    sizes = np.asarray(plan.sizes)
    assert {"an_expert_with_no_row": sizes[2] == 0 and live > 0,
            "one_expert_holds_every_live_row": sizes[2] == live == t,
            "a_token_in_several_held_experts": held == t * K,
            "overflow": int(plan.overflow) > 0,
            "rows_and_starts_off_the_window":
                rows % TOY.w != 0 and (np.cumsum(sizes)[:-1] % TOY.w != 0).any(),
            }.get(case, True)

    ys = jax.random.normal(jax.random.key(1), (rows, D)).astype(dtype)
    ys = jnp.where(plan.row_live[:, None], ys, jnp.nan)
    gates = jax.random.uniform(jax.random.key(2), (t, K)).astype(dtype)
    dy = jax.random.normal(jax.random.key(3), (t, D)).astype(dtype)
    y, vjp = jax.vjp(lambda ys, g: glm_moe._combine(ys, g, plan), ys, gates)
    want_y, want_vjp = jax.vjp(lambda ys, g: _plain_combine(ys, g, plan), ys, gates)
    assert y.dtype == ys.dtype and not bool(jnp.isnan(y).any())
    np.testing.assert_allclose(y.astype(jnp.float32), want_y.astype(jnp.float32),
                               **one_rounding(dtype))
    (d_ys, d_gates), (want_ys, want_gates) = vjp(dy), want_vjp(dy)
    np.testing.assert_array_equal(d_ys, want_ys)
    np.testing.assert_allclose(d_gates.astype(jnp.float32),
                               want_gates.astype(jnp.float32), atol=1e-6,
                               rtol=0 if dtype == "bfloat16" else 1e-5)
    assert not bool(jnp.isnan(d_ys).any())

    x = jax.random.normal(jax.random.key(4), (t, D)).astype(dtype)
    d_xs = jnp.where(plan.row_live[:, None],
                     jax.random.normal(jax.random.key(5), (rows, D)).astype(dtype),
                     jnp.nan)
    idx = plan.row_of // K
    xs, vjp = jax.vjp(lambda x: glm_moe._gather_rows(x, plan), x)
    np.testing.assert_array_equal(xs, x[idx])
    (d_x,) = vjp(d_xs)
    (want_x,) = jax.vjp(lambda x: x.astype(jnp.float32)[idx], x)[1](
        jnp.where(plan.row_live[:, None], d_xs.astype(jnp.float32), 0))
    assert d_x.dtype == x.dtype and not bool(jnp.isnan(d_x).any())
    np.testing.assert_allclose(d_x.astype(jnp.float32), want_x.astype(dtype)
                               .astype(jnp.float32), **one_rounding(dtype))

    # the forward's sum with the gates, the backward's without: the kernel
    assert interpreted == ([] if tl is None else [True, False])
    if tl is not None:
        visited = int(plan.sums.visited)
        assert visited % tl.w == 0 and live <= visited <= rows + 2 * tl.w * (
            t // tl.tb) * len(HELD)
        assert int(plan.sums.live[0]) == live


def test_the_schedule_lists_every_live_row_once_and_no_window_twice_a_tile():
    """By hand from the lists: a tile's chunks are consecutive, begin with
    an `edge` that says first and end with one that says last; the windows
    of a tile's slots are distinct per expert, cover each of its live rows,
    and an empty slot repeats the window its slot had."""
    ids, rows, tl = _case("one_expert_holds_every_live_row")
    ids = ids.at[:, 0].set(jnp.where(jnp.arange(64) % 3 == 0, 7, ids[:, 0]))
    sched = LAYER.plan(ids, rows, tl).sums
    tile, edge = np.asarray(sched.tile), np.asarray(sched.edge)
    window = np.asarray(sched.window).reshape(-1, tl.g)
    expert = np.asarray(sched.expert).reshape(-1, tl.g)
    slot_row = np.asarray(sched.slot_row)
    n = int((edge >= 0).sum())
    assert (edge[n:] == -1).all() and (expert[n:] == -1).all()
    assert list(tile[:n]) == sorted(tile[:n]) and set(tile[:n]) == set(range(64 // tl.tb))
    for i in range(64 // tl.tb):
        mine = np.flatnonzero(tile[:n] == i)
        assert edge[mine[0]] & 1 and edge[mine[-1]] & 2
        assert all(edge[c] == 0 for c in mine[1:-1])
        seen = {(e, w) for c in mine for e, w in zip(expert[c], window[c]) if e >= 0}
        assert len(seen) == int((expert[mine] >= 0).sum())  # none twice
        rows_of_tile = slot_row[:, i * tl.tb: (i + 1) * tl.tb]
        for e in range(len(HELD)):
            for r in rows_of_tile[e][rows_of_tile[e] >= 0]:
                assert (e, r // tl.w) in seen
    later = (expert[1:n] < 0)
    assert (window[1:n][later] == window[: n - 1][later]).all()
    assert int(sched.visited) == int((expert >= 0).sum()) * tl.w


def test_shapes_decide_the_tiles_and_the_grid_bounds_any_plan():
    assert pallas_rowsum.tiles(32768, 65536, 2048, 16) == (512, 16, 16)
    assert pallas_rowsum.tiles(4352, 4096, 2048, 8) == (256, 16, 16)
    for refused in [(64, 192, 32, 3), (4000, 4096, 2048, 8), (4096, 8, 2048, 8),
                    (4096, 4096, 2048, 65)]:
        assert pallas_rowsum.tiles(*refused) is None
    # the bound is reached from below by the worst plan there is: every
    # range one row long and astride two windows cannot be, but one expert
    # crowded into every tile is
    ids, rows, tl = _case("one_expert_holds_every_live_row")
    sched = LAYER.plan(ids, rows, tl).sums
    assert int((sched.edge >= 0).sum()) <= pallas_rowsum.chunks(
        64, rows, len(HELD), tl) == sched.edge.shape[0]


def test_the_layer_runs_the_kernel_where_its_shapes_tile(interpreted, monkeypatch):
    """Through `ExpertLayer.apply` and its gradient: with tiles for its
    shapes the layer's output, state and every gradient are those of the
    same layer on the plain path, the kernel ran once forward and once
    backward, and the state's counter holds the windows' rows where the
    program is lowered for the kernel's platform (here: every row)."""
    layer = dataclasses.replace(LAYER, rows=96, scaling=1.8)
    p, st, _ = layer.init(jax.random.key(1), (16, D))
    p = jax.tree_util.tree_map(lambda a: a * 8, p)
    x = jax.random.normal(jax.random.key(2), (4, 16, D))
    cot = jax.random.normal(jax.random.key(3), (4, 16, D))

    def run():
        def loss(p, x):
            y, new = layer.apply(p, st, x, train=True)
            return jnp.sum(y * cot) + new["balance"], (y, new)
        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    want, (want_y, want_new) = run()
    assert interpreted == [] and int(want_new["sum_rows_visited"]) == 96
    monkeypatch.setattr(pallas_rowsum, "tiles", lambda t, rows, d, held: TOY)
    got, (y, new) = run()
    assert interpreted == [True, False]
    np.testing.assert_allclose(y, want_y, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()))
    assert int(new["overflow_rows"]) == int(want_new["overflow_rows"])
    assert int(new["sum_rows_visited"]) == 96  # a CPU lowers `otherwise`'s count


def test_the_layers_of_a_model_trace_the_kernel_and_the_schedule_once(monkeypatch):
    """`schedule` and `sums` are jits of their own, so six layers that call
    them with the same shapes trace each once (a kernel traced afresh a
    layer added a third to the step's tracing time: PERF.md section 6, PR
    37)."""
    traced = []
    kernel = pallas_rowsum._kernel

    def counted(*refs, **static):
        traced.append(static["weighted"])
        return kernel(*refs, **static)

    monkeypatch.setattr(pallas_rowsum, "_kernel", counted)
    ids, rows, tl = _case("balanced")
    d = 384  # a width no other test of this process has traced

    @jax.jit
    def three_layers(x, gates):
        out = 0
        for shift in range(3):
            plan = LAYER.plan(jnp.roll(ids, shift, axis=0), rows, tl)
            weight = jnp.zeros((len(HELD), ids.shape[0]), jnp.float32) + gates
            out = out + pallas_rowsum.sums(x, weight, plan.sums, interpret=True)
            out = out + pallas_rowsum.sums(x, None, plan.sums, interpret=True)
        return out

    before = pallas_rowsum.schedule._cache_size()
    three_layers(jnp.ones((rows, d), jnp.float32), jnp.float32(0.5))
    assert sorted(traced) == [False, True]
    assert pallas_rowsum.schedule._cache_size() - before <= 1
