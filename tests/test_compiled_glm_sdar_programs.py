"""GLM-4.7-Flash's and SDAR-30B-A3B's programs compiled for a described v5e
(no chip, no run) at published widths: the attention cores are two kernels
a core and no tile of scores reaches HBM, the expert layer plans once a
step and goes back in buffer space, RoPE's turn is one kernel a direction;
and the kernels those steps call, compiled at the cells' shapes. Each step
is compiled once a file (tests/compiled_programs.py has what the files
share; tests/test_compiled_trinity_ling_programs.py the other two
families)."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from compiled_programs import _instructions, _step_text, described_v5e
from jax.sharding import SingleDeviceSharding

from parallel_cnn_tpu.train import zoo


@pytest.fixture(scope="module")
def topo():
    yield from described_v5e()


# --------------- the language model's attention core is two kernels (PR 33)

_glm_step = {}


def _glm_program(topo):
    """GLM-4.7-Flash's GSPMD train step at published widths, two layers
    (one dense, one of experts) and the MTP module's — three attention
    cores —, one sequence of 4,096 tokens, compiled for one described
    v5e: (the text, its catalog)."""
    if not _glm_step:
        from parallel_cnn_tpu.nn import glm_moe
        from parallel_cnn_tpu.obs import programs

        model = glm_moe.glm_4_7_flash(num_hidden_layers=2, vocab_size=19360,
                                      held_experts=range(8), row_buffer=4096)
        optimizer = zoo.make_optimizer(lr=2e-4, kind="adamw", b1=0.9, b2=0.95,
                                       weight_decay=0.1)
        # as the chip compiles it: conftest's `highest`, which the CPU tests'
        # comparisons want, is no precision the compiler's own grouped-matmul
        # kernel takes for bf16 operands
        with jax.default_matmul_precision("default"):
            text = _step_text(topo, model, optimizer, (4096,), 1, None, tokens=True)
        _glm_step.update(text=text, catalog=programs.parse(text),
                         instructions=_instructions(text.split("ENTRY")[1]))
    return _glm_step


CORES = ("l0/attn/core", "l1/attn/core", "mtp/l0/attn/core")


def test_the_attention_kernels_carry_their_layers_scope_and_phase(topo):
    """The kernels are `custom-call`s whose `op_name` carries the name
    stack: the catalog gives each its core's scope and a phase, so
    `attn_core_device_ms` reads them."""
    catalog = _glm_program(topo)["catalog"]
    kernels = {n: e for n, e in catalog.items()
               if e.opcode == "custom-call" and "attn/core" in e.scope}
    assert len(kernels) == 6
    assert {(e.scope, e.phase) for e in kernels.values()} == {
        (scope, phase) for scope in CORES for phase in ("fwd", "bwd")}


def test_the_forward_kernel_runs_once_a_core_and_never_in_the_backward(topo):
    """A rematerialised layer keeps the core's output and log-sum-exp:
    its backward re-runs no forward kernel."""
    catalog = _glm_program(topo)["catalog"]
    for kernel, phase in (("causal_attention_fwd", "fwd"),
                          ("causal_attention_bwd", "bwd")):
        ran = sorted((e.scope, e.phase) for n, e in catalog.items()
                     if n.startswith(kernel) and e.opcode == "custom-call")
        assert ran == [(scope, phase) for scope in CORES], kernel


def test_no_tile_of_float32_scores_reaches_hbm(topo):
    """No instruction anywhere in the step, forward or backward, has a
    float32 result `(N, 20, q, k)` of a query tile by a key range (`k` of
    512 or more: a head is 256 wide, and `(N, 20, 4096, 256)` in float32
    is an activation inside a fusion, not scores)."""
    text = _glm_program(topo)["text"]
    per_head = [(int(q), int(k)) for q, k in
                re.findall(r"f32\[\d+,20,(\d+),(\d+)\]", text)]
    assert (4096, 64) in per_head  # the pattern sees what is per head: RoPE's turn
    assert [qk for qk in per_head
            if qk[1] >= 512 and qk[0] * qk[1] >= 512 * 512] == []
    # ... and no probabilities in bf16 either
    assert not re.search(r"bf16\[\d+,20,(512|4096),(512|1024|2048|4096)\]", text)


def test_no_position_major_tensor_is_transposed_for_the_kernels(topo):
    """`q`, `k`, `v` and the output are born and consumed head-major, by
    the projections' own matmuls: under `attn/*` nothing `(., 4096, 20,
    256)` is copied or transposed (nor exists at all)."""
    program = _glm_program(topo)
    moved = [(name, ins.result) for name, ins in program["instructions"].items()
             if ins.opcode in ("copy", "transpose")
             and "attn" in program["catalog"].get(name.lstrip("%")).scope
             and any(dims.endswith("4096,20,256") for _, dims in ins.result)]
    assert moved == []
    assert not re.search(r"bf16\[\d+,4096,20,256\]", program["text"])


# ------- the block-diffusion model's attention core is two kernels (PR 34)

_sdar_step = {}


def _sdar_program(topo):
    """SDAR-30B-A3B's GSPMD train step at published widths, two layers, one
    sequence of 4,096 clean tokens (a stream of 8,192), compiled for one
    described v5e: (the text, its catalog)."""
    if not _sdar_step:
        from parallel_cnn_tpu.nn import sdar_moe
        from parallel_cnn_tpu.obs import programs

        model = sdar_moe.sdar_30b_a3b(
            num_hidden_layers=2, vocab_size=18992, held_experts=range(16),
            row_buffer=16384, gate_gradient=False)
        optimizer = zoo.make_optimizer(lr=2e-4, kind="adamw", b1=0.9, b2=0.95,
                                       weight_decay=0.1)
        with jax.default_matmul_precision("default"):
            text = _step_text(topo, model, optimizer, (4096,), 1, None, tokens=True)
        _sdar_step.update(text=text, catalog=programs.parse(text))
    return _sdar_step


def test_the_block_diffusion_kernels_carry_their_layers_scope_and_phase(topo):
    """One forward and one backward kernel a core, each under its layer's
    `attn/core` with its phase (`bd_attn_core_device_ms` reads them), and
    the rematerialised backward re-runs no forward kernel."""
    catalog = _sdar_program(topo)["catalog"]
    for kernel, phase in (("block_diffusion_attention_fwd", "fwd"),
                          ("block_diffusion_attention_bwd", "bwd")):
        ran = sorted((e.scope, e.phase) for n, e in catalog.items()
                     if n.startswith(kernel) and e.opcode == "custom-call")
        assert ran == [("l0/attn/core", phase), ("l1/attn/core", phase)], kernel
    assert not any(n.startswith("causal_attention") for n in catalog)
    named = {e.scope for e in catalog.values()}
    for scope in ("noise", "l0/attn/qk_norm", "l1/moe/route", "head", "loss"):
        assert scope in named, scope


def test_no_tile_of_the_streams_scores_reaches_hbm(topo):
    """Nothing `(N, 32, q, k)` in float32 or bf16 with `k` of 512 keys or
    more exists anywhere in the step (a head is 128 wide): the (2L)^2
    square, its mask included, lives in VMEM a tile at a time."""
    text = _sdar_program(topo)["text"]
    per_head = [(int(q), int(k)) for q, k in
                re.findall(r"(?:f32|bf16|pred)\[\d+,(?:32|4),(\d+),(\d+)\]", text)]
    assert (8192, 128) in per_head  # the pattern sees what is per head
    assert [qk for qk in per_head if qk[1] >= 512 and qk[0] >= 128] == []
    assert not re.search(r"\[8192,8192\]", text)


# -- the expert layer plans once a step and goes back in buffer space (PR 35);
# -- the sum of a token's rows is one fused kernel, both directions (PR 37)

def _expert_layer_shapes(program, tokens, k, width=2048):
    """(sorts by (scope, phase), scopes and phases under which an
    instruction's result has a row an assignment — `(T * k, d)` or `(T, k,
    d)` —, the row-sum kernels' (scope, phase)) of a compiled step."""
    instructions = _instructions(program["text"].split("ENTRY")[1])
    sorts, per_assignment, kernels = collections.Counter(), set(), []
    for name, ins in instructions.items():
        entry = program["catalog"].get(name.lstrip("%"))
        if entry is None or "/moe/" not in entry.scope:
            continue
        scope = entry.scope[entry.scope.index("moe/"):]
        if ins.opcode == "sort":
            sorts[scope, entry.phase] += 1
        if any(dims in (f"{tokens * k},{width}", f"{tokens},{k},{width}")
               for _, dims in ins.result):
            per_assignment.add((scope, entry.phase))
        if ins.opcode == "custom-call" and name.lstrip("%").startswith("moe_token_sums"):
            kernels.append((scope, entry.phase))
    return sorts, per_assignment, sorted(kernels)


@pytest.mark.parametrize("which,layers,tokens,k", [
    ("sdar", 2, 8192, 8), ("glm", 2, 4096, 4)])
def test_a_step_plans_once_an_expert_layer_and_combines_back_by_rows(
        topo, which, layers, tokens, k):
    """As the chip's compiler leaves it: an expert layer sorts twice, both
    in the forward (`top_k`, and the plan's slots by row: the plan is kept
    through the layer's rematerialisation), and no instruction under any
    `moe/` scope of either phase has a row an assignment: the sum of a
    token's rows is the fused kernel, once in the forward under
    `moe/combine` and once in the backward under `moe/dispatch` (the
    rematerialised backward runs no forward sum again)."""
    program = _sdar_program(topo) if which == "sdar" else _glm_program(topo)
    sorts, per_assignment, kernels = _expert_layer_shapes(program, tokens, k)
    assert sorts == {("moe/route", "fwd"): layers,
                     ("moe/dispatch", "fwd"): layers}, sorts
    assert per_assignment == set()
    assert kernels == sorted([("moe/combine", "fwd"),
                              ("moe/dispatch", "bwd")] * layers)


@pytest.mark.parametrize("t,k,rows,held,weighted", [
    (32768, 8, 65536, 16, True), (32768, 8, 65536, 16, False),
    (16384, 4, 16384, 8, True), (4096, 4, 4096, 8, False)],
    ids=["sdar_gated", "sdar_plain", "glm_gated", "glm_check_plain"])
def test_the_row_sum_kernel_compiles_at_the_cells_shapes(
        topo, t, k, rows, held, weighted):
    """Mosaic takes the kernel of ops/pallas_rowsum.py at both token cells'
    shapes (and at the check's one sequence), bf16 rows 2,048 wide, with
    the gates and without."""
    from parallel_cnn_tpu.ops import pallas_rowsum

    one_chip = SingleDeviceSharding(topo.devices[0])
    tl = pallas_rowsum.tiles(t, rows, 2048, held)
    assert tl == pallas_rowsum.Tiles(512, 16, 16)
    shape = lambda *s, dtype=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        s, dtype, sharding=one_chip)
    c = pallas_rowsum.chunks(t, rows, held, tl)
    sched = pallas_rowsum.Schedule(
        tl, shape(held, t), shape(c), shape(c), shape(c * tl.g),
        shape(c * tl.g), shape(1), shape())
    weight = shape(held, t, dtype=jnp.float32) if weighted else None
    compiled = jax.jit(pallas_rowsum.sums).lower(
        shape(rows, 2048, dtype=jnp.bfloat16), weight, sched).compile()
    assert "moe_token_sums" in compiled.as_text()


# ------------------------------ RoPE's turn is one kernel a direction (PR 42)

def _attention_layer(topo, att, n, s, width=2048):
    """An attention layer's forward and backward as a decoder layer runs
    them (rematerialised; the cotangent an input), compiled for one
    described v5e: its text."""
    one = SingleDeviceSharding(topo.devices[0])
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)  # noqa: E731
    params = jax.tree_util.tree_map(like, jax.eval_shape(
        lambda k: att.init(k, (s, width))[0], jax.random.key(0)))
    x = jax.ShapeDtypeStruct((n, s, width), jnp.bfloat16, sharding=one)

    def layer(params, x, cotangent):
        # the scopes of a step, for the catalog: grad/l0/attn
        with jax.named_scope("grad"), jax.named_scope("l0"), jax.named_scope("attn"):
            out, vjp = jax.vjp(jax.checkpoint(
                lambda p, x: att.apply(p, {}, x, True)[0]), params, x)
            return out, vjp(cotangent)

    with jax.default_matmul_precision("default"):
        return jax.jit(layer).lower(params, x, x).compile().as_text()


def test_sdars_attention_turns_q_and_k_in_one_kernel_a_pass_and_nothing_float32(topo):
    """`GQA` at `sdar_bd_train`'s shape (4 sequences, a stream of 8,192):
    q and k are each turned by one `rope_turn` kernel in the forward, the
    rematerialised forward and the backward, under the `rope` scope; no
    instruction of the entry has a float32 result of `q`'s size (the plain
    body's `f32[4,32,8192,128]` re-layout and its 64-wide halves are
    gone), and nothing of `q`'s size is copied at all: the projection
    writes `q` row-major (`layers.row_major`), where the kernels read it."""
    from parallel_cnn_tpu.nn import sdar_moe
    from parallel_cnn_tpu.obs import programs

    text = _attention_layer(topo, sdar_moe.GQA(), 4, 8192)
    catalog = programs.parse(text)
    turns = sorted((e.scope, e.phase) for n, e in catalog.items()
                   if n.startswith("rope_turn") and e.opcode == "custom-call")
    assert turns == [("l0/attn/rope", "bwd")] * 4 + [("l0/attn/rope", "fwd")] * 2
    q_size = 4 * 32 * 8192 * 128
    instructions = _instructions(text.split("ENTRY")[1])
    wide = [(name, ins.opcode, ins.result) for name, ins in instructions.items()
            for dtype, dims in ins.result
            if dtype == "f32" and dims and np.prod(
                [int(d) for d in dims.split(",")]) >= q_size]
    assert wide == []
    assert not re.search(r"\[4,32,2,4096,64\]", text)  # no 64-wide halves
    copies = [(name, ins.result) for name, ins in instructions.items()
              if ins.opcode in ("copy", "transpose") and any(
                  dims and np.prod([int(d) for d in dims.split(",")]) >= q_size
                  for _, dims in ins.result)]
    assert copies == []


def test_glms_attention_compiles_to_what_the_plain_body_compiles_to(topo, monkeypatch):
    """`MLA`'s 64-wide turn is none of the kernel's shapes: the layer
    compiled through `rope` is, instruction for instruction, the layer
    compiled with the plain body in `rope`'s place (the parent's)."""
    from parallel_cnn_tpu.nn import glm_moe, layers

    def program():
        """Every instruction of every computation: result, opcode and
        operands by name (what is left out points into the source: the
        metadata, and the locations inside the attention kernels' payload)."""
        text = _attention_layer(topo, glm_moe.MLA(), 4, 4096)
        return text, {name: ins[:3] for name, ins in _instructions(text).items()}

    text, through_rope = program()
    assert "rope_turn" not in text and "f32[4,20,4096,64]" in text
    assert len(through_rope) > 1000
    monkeypatch.setattr(glm_moe, "rope", layers._rope)
    assert program()[1] == through_rope


@pytest.mark.parametrize("shape", [
    (4, 32, 2, 4096, 128), (4, 4, 2, 4096, 128), (1, 32, 16384, 128),
    (1, 4, 16384, 128), (1, 32, 2, 4096, 128)],
    ids=["sdar_q", "sdar_k", "trinity_q", "trinity_k", "sdar_check_q"])
def test_the_rope_kernel_compiles_at_the_cells_shapes(topo, shape):
    """Mosaic takes the kernel of ops/pallas_rope.py at both cells' shapes
    (and at the check's one sequence), both directions, and the program
    around it keeps nothing: the tables are its only other arrays."""
    from parallel_cnn_tpu.ops import pallas_rope

    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    assert pallas_rope.tile(*shape[-2:]) == 512
    for back in (False, True):
        compiled = pallas_rope.rotate.lower(x, theta=1e6, back=back).compile()
        assert pallas_rope.NAME in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes <= 2 * shape[-2] * 128 * 4
