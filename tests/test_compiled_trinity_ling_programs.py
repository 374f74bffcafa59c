"""Trinity-Mini's, Ling-3.0-flash's and Ouro-2.6B's programs compiled for a
described v5e (no chip, no run) at published widths: a window is a schedule
of the grouped causal kernel pair, the delta rule's chunked scan and the
short convolutions ahead of it are one kernel a direction, no score square
and no state a position reaches HBM, and a stack run four times is one
straight program whose every core runs once forward; and the kernels those
steps call, compiled at the cells' shapes. Each step is compiled once a file
(tests/compiled_programs.py has what the files share;
tests/test_compiled_glm_sdar_programs.py the other two families)."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from compiled_programs import _step_text, described_v5e
from jax.sharding import SingleDeviceSharding

from parallel_cnn_tpu.train import zoo


@pytest.fixture(scope="module")
def topo():
    yield from described_v5e()


# -- a window is a schedule of the second attention kernel pair (PR 41)

@pytest.mark.parametrize("shape,window,visited", [
    ((1, 16384, 32, 4), 2048, 150), ((1, 16384, 32, 4), None, 528),
    ((2, 4096, 16, 16), None, 36)], ids=["window_2048", "full", "ungrouped_4096"])
def test_the_grouped_causal_kernels_compile_at_the_cells_shapes(
        topo, shape, window, visited):
    """Mosaic takes both directions at 16,384 positions of 32 query heads
    over 4 key/value heads of 128 (`dk` and `dv` of one (sequence,
    key/value head) fill their VMEM buffers exactly), with the window and
    without, and at the looped model's 2 x 4,096 positions of 16 heads
    over 16 (a group of one); the grid's last axis is the schedule's
    length."""
    from parallel_cnn_tpu.ops import pallas_attention as pa

    one_chip = SingleDeviceSharding(topo.devices[0])
    (n, s, h, kv), d = shape, 128
    t = pa.causal_tile(s, window, d)
    assert t == 512 and pa.causal_tiles_visited(s, t, window) == visited
    like = lambda heads, *rest, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (n, heads, s, *rest), dtype, sharding=one_chip)
    q, k = like(h, d), like(kv, d)
    kw = dict(scale=d ** -0.5, window=window, t=t)
    fwd = jax.jit(lambda q, k, v: pa.gc_forward(q, k, v, **kw)).lower(
        q, k, k).compile().as_text()
    bwd = jax.jit(lambda *a: pa.gc_backward(*a, **kw)).lower(
        q, k, k, q, like(h, dtype=jnp.float32), q).compile().as_text()
    assert "grouped_causal_attention_fwd" in fwd
    assert "grouped_causal_attention_bwd" in bwd
    assert not re.search(rf"\[{s},{s}\]", fwd + bwd)


_afmoe_step = {}


def _afmoe_program(topo):
    """Trinity-Mini's GSPMD train step at published widths, two layers (a
    dense sliding one, a full one with experts), one sequence of 16,384
    tokens, compiled for one described v5e: (the text, its catalog)."""
    if not _afmoe_step:
        from parallel_cnn_tpu.nn import afmoe
        from parallel_cnn_tpu.obs import programs

        model = afmoe.trinity_mini(
            layer_types=[afmoe.SLIDING, afmoe.FULL], num_dense_layers=1,
            vocab_size=25024, held_experts=range(16), row_buffer=32768,
            gate_gradient=False)
        optimizer = zoo.make_optimizer(lr=2e-4, kind="adamw", b1=0.9, b2=0.95,
                                       weight_decay=0.1)
        with jax.default_matmul_precision("default"):
            text = _step_text(topo, model, optimizer, (16384,), 1, None, tokens=True)
        _afmoe_step.update(text=text, catalog=programs.parse(text))
    return _afmoe_step


def test_the_window_and_full_kernels_carry_their_layers_scope_and_phase(topo):
    """One forward and one backward kernel a core, each under its layer's
    `attn/core` with its phase (`win_attn_core_device_ms` and
    `full_attn_core_device_ms` read them by the layer's kind), the
    rematerialised backward re-runs no forward kernel, and the scopes the
    architecture adds are there to be read."""
    catalog = _afmoe_program(topo)["catalog"]
    for kernel, phase in (("grouped_causal_attention_fwd", "fwd"),
                          ("grouped_causal_attention_bwd", "bwd")):
        ran = sorted((e.scope, e.phase) for n, e in catalog.items()
                     if n.startswith(kernel) and e.opcode == "custom-call")
        assert ran == [("l0/attn/core", phase), ("l1/attn/core", phase)], kernel
    assert not any(n.startswith(("causal_attention", "block_diffusion"))
                   for n in catalog)
    named = {e.scope for e in catalog.values()}
    for scope in ("embed", "l0/attn/qk_norm", "l0/attn/rope", "l0/attn/post_norm",
                  "l0/mlp/post_norm", "l1/attn/qk_norm", "l1/moe/route",
                  "l1/moe/shared", "l1/moe/post_norm", "head", "loss"):
        assert scope in named, scope
    assert "l1/attn/rope" not in named  # a full layer carries no position


def test_no_tile_of_either_kinds_scores_reaches_hbm(topo):
    """Nothing `(N, 32, q, k)` or `(N, 4, 8, q, k)` with `k` of 512 keys or
    more exists anywhere in the step (a head is 128 wide): the score
    square, its window included, lives in VMEM a tile at a time."""
    text = _afmoe_program(topo)["text"]
    per_head = [(int(q), int(k)) for q, k in re.findall(
        r"(?:f32|bf16|pred)\[\d+,(?:32|4|4,8),(\d+),(\d+)\]", text)]
    assert (16384, 128) in per_head  # the pattern sees what is per head
    assert [qk for qk in per_head if qk[1] >= 512 and qk[0] >= 128] == []
    assert not re.search(r"\[16384,16384\]", text)


# -- the delta rule's chunked scan, and a linear and a full layer's step (PR 43)

def _scan_compiled(topo, direction):
    """`chunked_kda` (`direction` "fwd") or its five gradients ("bwd") at
    the cell's 8,192 positions of 32 heads of 128, bf16 `q, k, v`,
    compiled for one described v5e."""
    from parallel_cnn_tpu.ops import kda

    one_chip = SingleDeviceSharding(topo.devices[0])
    n, h, s, d = 1, 32, 8192, 128
    like = lambda *shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    q, g, beta = like(n, h, s, d), like(n, h, s, d, dtype=jnp.float32), like(
        n, h, s, dtype=jnp.float32)
    assert kda.spans(s) == (32, 4) and kda.state_bytes(s, h, d, d) == 64 << 20
    # (a function of this call's own: jit keeps no trace from another test's)
    fn = lambda *a: kda.chunked_kda(*a)  # noqa: E731
    if direction == "bwd":
        fn = jax.grad(lambda *a: jnp.sum(kda.chunked_kda(*a).astype(jnp.float32)),
                      argnums=(0, 1, 2, 3, 4))
    with jax.default_matmul_precision("default"):
        return jax.jit(fn).lower(q, q, q, g, beta).compile()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_the_chunked_scan_compiles_at_the_cells_shapes(topo, direction, monkeypatch):
    """XLA takes the plain body (what shapes the kernels refuse run, and
    anything that is no TPU) at 8,192 positions of 32 heads of 128,
    forward and backward: 32 steps of 4 chunks; what the backward keeps
    beside its inputs and outputs is the span-start states and one span's
    tables, no state a chunk (268 MB) and none a position."""
    from parallel_cnn_tpu.ops import pallas_kda

    monkeypatch.setattr(pallas_kda, "tiles", lambda *shapes: False)
    compiled = _scan_compiled(topo, direction)
    text = compiled.as_text()
    assert "while" in text and pallas_kda.NAME not in text
    # no state a position, and none a chunk
    assert not re.search(r"f32\[8192,1,32,128,128\]|f32\[128,1,32,128,128\]", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (600 << 20 if direction == "fwd" else 1536 << 20), temp


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_the_scans_kernels_compile_at_the_cells_shapes(topo, direction):
    """Mosaic takes ops/pallas_kda.py's kernels at the same shapes where
    `chunked_kda` is lowered for a TPU: the kernel by its name and no loop
    around it; no table of a chunk (float32 `(..., 64, 64)` or `(..., 64,
    128)`), no state a chunk or a position and no copy of an operand
    chunk-major among the program's arrays; beside inputs and outputs the
    program holds the span-start states (64 MB: kept for the backward, or
    written and dropped by a forward alone) and `beta` and its gradient as
    rows (67.1 and 68.3 MB read)."""
    from parallel_cnn_tpu.ops import pallas_kda

    compiled = _scan_compiled(topo, direction)
    text = compiled.as_text()
    kernels = re.findall(rf"{pallas_kda.NAME}_(?:fwd|bwd)", text)
    assert set(kernels) == ({"kda_scan_fwd"} if direction == "fwd" else {
        "kda_scan_fwd", "kda_scan_bwd"}), kernels
    assert "while" not in text
    assert not re.search(r"f32\[(\d+,)*64,(64|128)\]", text)
    assert not re.search(r"f32\[(8192|128),1,32,128,128\]", text)
    assert not re.search(r"\[32,1,32,4,64", text)  # `_blocks`' layout
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= (64 + 2) << 20, temp


_bailing_step = {}


def _bailing_program(topo):
    """Ling-3.0-flash's GSPMD train step at published widths, two layers (a
    dense linear one, a full one with experts), one sequence of 8,192
    tokens, compiled for one described v5e: (the text, its catalog)."""
    if not _bailing_step:
        from parallel_cnn_tpu.nn import bailing_hybrid as bh
        from parallel_cnn_tpu.obs import programs

        model = bh.ling_3_0_flash(
            layer_types=[bh.LINEAR, bh.FULL], num_dense_layers=1,
            vocab_size=19648, held_experts=range(8), row_buffer=8192,
            gate_gradient=False)
        optimizer = zoo.make_optimizer(lr=2e-4, kind="adamw", b1=0.9, b2=0.95,
                                       weight_decay=0.1)
        with jax.default_matmul_precision("default"):
            text = _step_text(topo, model, optimizer, (8192,), 1, None, tokens=True)
        _bailing_step.update(text=text, catalog=programs.parse(text))
    return _bailing_step


def test_the_linear_and_full_layers_carry_their_scopes_and_the_core_is_fused(topo):
    """The full layer's core is the causal kernel pair at 192 carried as
    256 (one forward and one backward kernel under `l1/attn/core`, the
    rematerialised backward re-runs no forward kernel); the linear layer's
    scan, conv, gates and gate_norm are scopes the readers find, forward
    and backward."""
    catalog = _bailing_program(topo)["catalog"]
    for kernel, phase in (("causal_attention_fwd", "fwd"),
                          ("causal_attention_bwd", "bwd")):
        ran = sorted((e.scope, e.phase) for n, e in catalog.items()
                     if n.startswith(kernel) and e.opcode == "custom-call")
        assert ran == [("l1/attn/core", phase)], (kernel, ran)
    by_scope = collections.defaultdict(set)
    for e in catalog.values():
        by_scope[e.scope].add(e.phase)
    for scope in ("l0/attn/qkv", "l0/attn/conv", "l0/attn/gates", "l0/attn/core",
                  "l0/attn/gate_norm", "l0/attn/o", "l1/attn/q", "l1/attn/kv",
                  "l1/attn/rope", "l1/attn/core", "l1/attn/gate", "l1/moe/route",
                  "l1/moe/experts", "l1/moe/shared"):
        assert {"fwd", "bwd"} <= by_scope[scope], (scope, by_scope[scope])
    assert "l0/attn/rope" not in by_scope  # a linear layer carries no position


def test_the_linear_layers_scan_is_one_kernel_a_direction_and_no_loop(topo):
    """ops/pallas_kda.py's kernels under `l0/attn/core`, the forward's once
    in the forward and the backward's once in the backward (the layer's
    rematerialisation keeps `o` and the span-start states, so no forward
    kernel runs again), and no `while` left under that scope."""
    catalog = _bailing_program(topo)["catalog"]
    for kernel, phase in (("kda_scan_fwd", "fwd"), ("kda_scan_bwd", "bwd")):
        ran = sorted((e.scope, e.phase) for n, e in catalog.items()
                     if n.startswith(kernel) and e.opcode == "custom-call")
        assert ran == [("l0/attn/core", phase)], (kernel, ran)
    assert [n for n, e in catalog.items()
            if e.scope == "l0/attn/core" and e.opcode == "while"] == []


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_the_short_conv_kernels_compile_at_the_cells_shapes(topo, direction):
    """Mosaic takes ops/pallas_shortconv.py's kernels at `q`'s shape in the
    cell, `bf16[1, 32, 8192, 128]` under 4 taps, with the norm (`q`, `k`)
    and without (`v`): a program that is the kernel, with nothing of the
    array's size beside its operands and results — the backward's only
    temporary is the taps' partial sums (eight a tap and head, 0.5 MB)."""
    from parallel_cnn_tpu.ops import pallas_shortconv as sc

    one_chip = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, 32, 128), jnp.float32, sharding=one_chip)
    assert sc.tile(8192, 128, 4) == 512
    for unit in (True, False):
        if direction == "fwd":
            compiled = sc.forward.lower(x, taps, unit=unit, scale=0.5).compile()
        else:
            compiled = sc.backward.lower(x, taps, x, unit=unit, scale=0.5).compile()
        text = compiled.as_text()
        assert f"{sc.NAME}_{direction}" in text
        assert not re.search(r"f32\[1,32,8192,128\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes <= 1 << 20


def test_the_linear_layers_short_convolutions_are_one_kernel_a_direction(topo):
    """Under `l0/attn/conv` the compiled step holds ops/pallas_shortconv.py's
    kernels and nothing of an array's size besides: the forward's three
    (`q`, `k`, `v`) in the forward and again in the backward (the layer is
    rematerialised), the backward's three, no copy of `(1, 32, 8192, 128)`
    ahead of or behind them — the projections write, and the scan reads,
    head-major row-major — and no float32 of that size."""
    step = _bailing_program(topo)
    ran = collections.Counter(
        (name.split(".")[0], e.phase) for name, e in step["catalog"].items()
        if e.scope == "l0/attn/conv" and e.opcode == "custom-call")
    assert ran == {("short_conv_fwd", "fwd"): 3, ("short_conv_fwd", "bwd"): 3,
                   ("short_conv_bwd", "bwd"): 3}, ran
    shapes = dict(re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) (?:copy|fusion)\(", step["text"], re.M))
    whole = {name: shapes[name] for name, e in step["catalog"].items()
             if e.scope == "l0/attn/conv" and "8192,128]" in shapes.get(name, "")}
    assert whole == {}, whole


def test_no_score_square_and_no_state_a_position_reaches_hbm(topo):
    text = _bailing_program(topo)["text"]
    assert not re.search(r"\[8192,8192\]", text)
    per_head = [(int(q), int(k)) for q, k in re.findall(
        r"(?:f32|bf16|pred)\[\d+,32,(\d+),(\d+)\]", text)]
    assert (8192, 256) in per_head  # q and k, 192 carried as 256
    assert [qk for qk in per_head if qk[1] >= 512 and qk[0] >= 128] == []
    assert not re.search(r"f32\[8192,1,32,128,128\]", text)


# -- a stack run several times a step is one straight program (PR 48)

def test_the_looped_models_step_runs_every_pass_and_keeps_every_core(topo):
    """Ouro-2.6B's GSPMD train step at published widths, two layers, two
    sequences of 4,096 tokens, compiled for one described v5e: no loop is
    left of the four passes; a layer's ops of all four passes lie under
    `ut/l<i>`; each of the 4 x 2 cores is one forward kernel in the forward
    pass and one backward kernel, and no backward runs a core again (the
    rematerialised layer keeps it); RoPE's turn is the kernel; the four
    exits never hold more than a block's logits."""
    from parallel_cnn_tpu.nn import ouro
    from parallel_cnn_tpu.obs import programs

    model = ouro.ouro_2_6b(num_hidden_layers=2)
    optimizer = zoo.make_optimizer(lr=2e-4, kind="adamw", b1=0.9, b2=0.95,
                                   weight_decay=0.1)
    with jax.default_matmul_precision("default"):
        text = _step_text(topo, model, optimizer, (4096,), 2, None, tokens=True)
    catalog = programs.parse(text)
    assert not any(e.opcode == "while" for e in catalog.values())
    calls = collections.Counter(
        (e.scope, e.phase) for e in catalog.values() if e.opcode == "custom-call")
    for i in (0, 1):
        assert calls[(f"ut/l{i}/attn/core", "fwd")] == 4
        assert calls[(f"ut/l{i}/attn/core", "bwd")] == 4
        # q and k, forward, rematerialised forward and backward, four passes
        assert calls[(f"ut/l{i}/attn/rope", "fwd")] == 8
        assert calls[(f"ut/l{i}/attn/rope", "bwd")] == 16
    # (a custom-call without a name stack is the compiler's own)
    assert set(s for s, _ in calls) - {""} == {
        f"ut/l{i}/attn/{what}" for i in (0, 1) for what in ("core", "rope")}
    scopes = {e.scope for e in catalog.values()}
    for want in ("embed", "ut/l0/attn/qkv", "ut/l1/mlp", "ut/l1/mlp/post_norm",
                 "ut/exit/norm", "ut/exit/head", "ut/exit/loss", "ut/exit/gate",
                 "mix", "optimizer"):
        assert want in scopes, (want, sorted(scopes))
    assert not re.search(r"f32\[8192,49152\]|\[4096,4096\]", text)
    assert re.search(r"f32\[2048,49152\]", text)
