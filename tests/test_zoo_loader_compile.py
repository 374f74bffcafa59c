"""Programs compiled for a described v5e (no chip, no run): what PR 25 found
about layouts and what PR 28 found about BatchNorm's statistics are
properties of the compiled program, so they are held here. `select_batch`
reads the store where it lies (no temporaries, no copy of the store), on
one chip and over a 2x2 mesh, and the one-time conversion works through
the set in blocks. A residual block's forward and backward have no pass
that only takes a BatchNorm's statistics: both moments of every sample
ride the epilogue of the conv that produces the activation.

One file, and the topology is described inside a fixture: only the worker
that runs this file loads the TPU compiler."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from parallel_cnn_tpu.nn import resnet
from parallel_cnn_tpu.train import zoo

IN_SHAPE = (224, 224, 3)
ROWS = 224 * 224 * 3 // 128  # 1,176: a multiple of 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled without a chip cannot be read back from the cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _entry(compiled):
    return compiled.as_text().split("ENTRY")[1]


@pytest.mark.parametrize("n,batch", [(4096, 256), (4096, 512)], ids=["b256", "b512"])
def test_select_batch_on_one_chip_reads_the_store_where_it_lies(topo, n, batch):
    one = SingleDeviceSharding(topo.devices[0])

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = zoo.select_batch.lower(
        like((n, 1, ROWS, 128), jnp.bfloat16), like((n,), jnp.int32),
        like((n,), jnp.int32), like((), jnp.int32),
        batch=batch, in_shape=IN_SHAPE).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.output_size_in_bytes == pytest.approx(batch * 150528 * 2, rel=0.01)
    entry = _entry(compiled)
    # the store is row-major, and nothing of its size is made from it
    assert re.search(rf"bf16\[{n},1,{ROWS},128\]\{{3,2,1,0:", entry)
    assert not re.search(rf"bf16\[{n},1,{ROWS},128\]\S* copy\(", entry)


def test_select_batch_over_a_mesh_keeps_a_quarter_and_trades_slabs(topo):
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    n, batch = 8192, 1024

    def like(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

    # 294 rows a slab, padded to 296: whole (8, 128) tiles, or the device
    # would lay the slabs out sample-minor and copy all of them every step
    compiled = zoo.select_batch.lower(
        like((n, 4, 296, 128), jnp.bfloat16, P(None, "data", None, None)),
        like((n,), jnp.int32, P()), like((n,), jnp.int32, P()),
        like((), jnp.int32, P()), batch=batch, in_shape=IN_SHAPE, over=mesh).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(n * 296 * 128 * 2, rel=0.01)
    assert mem.output_size_in_bytes == pytest.approx(batch // 4 * 150528 * 2, rel=0.01)
    assert mem.temp_size_in_bytes < 100e6  # a chip's slabs of the batch, once
    text = compiled.as_text()
    assert "all-to-all" in text
    assert re.search(r"bf16\[8192,1,296,128\]\{3,2,1,0:", _entry(compiled))
    assert not re.search(r"bf16\[8192,1,296,128\]\S* copy\(", text)


@pytest.mark.parametrize("shape,dtype,rows", [
    ((4096, 224, 224, 3), jnp.bfloat16, 1176),
    ((8192, 56, 224, 3), jnp.bfloat16, 296),   # one chip's slabs under the 2x2 mesh
    ((50000, 32, 32, 3), jnp.float32, 24),     # CIFAR, as the CLI feeds it
], ids=["imagenet", "imagenet-slab", "cifar"])
def test_the_conversion_works_through_the_set_in_blocks(topo, shape, dtype, rows):
    one = SingleDeviceSharding(topo.devices[0])
    compiled = zoo._rows128.lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert re.search(rf"-> \S+\[{shape[0]},1,{rows},128\]", _entry(compiled))


# ------------------------------ BatchNorm's statistics cost no pass of their own

# name: block, input of one image, batch a chip (the cells' shapes, stage 1),
# the scopes of its convs
BLOCKS = {
    "bottleneck": (resnet.Bottleneck(64), (56, 56, 256), 256,       # r50_train s1b2
                   {"reduce", "mid", "expand"}),
    "bottleneck-proj": (resnet.Bottleneck(64), (56, 56, 64), 256,   # r50_train s1b1
                        {"reduce", "mid", "expand", "proj"}),
    "basic": (resnet.BasicBlock(64), (56, 56, 64), 512,             # r18_train s1b1
              {"head", "tail"}),
}
FORWARD_CONV = re.compile(r"/jvp\((\w+)\)/conv/conv_general_dilated$")
# array shapes of the result as (dtype, dims), opcode, operand names, op_name
Instruction = collections.namedtuple("Instruction", "result opcode operands op_name")
_block_entries = {}


def _instructions(entry):
    """name -> Instruction of an entry computation's text."""
    out = {}
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([a-z][\w-]*)\((.*?)\)", line)
        if not m:
            continue
        name, result, opcode, operands = m.groups()
        op_name = re.search(r'op_name="([^"]*)"', line)
        out[name] = Instruction(
            re.findall(r"(\w+)\[([\d,]*)\]", result), opcode,
            re.findall(r"%[\w.-]+", operands), op_name.group(1) if op_name else "")
    return out


def _block_program(topo, name):
    """Forward and backward of one block in training mode, bf16 activations:
    gradients of a scalar of the output to the parameters and the input."""
    if name not in _block_entries:
        block, in_shape, batch, _ = BLOCKS[name]
        one = SingleDeviceSharding(topo.devices[0])
        params, state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            jax.eval_shape(lambda k: block.init(k, in_shape)[:2], jax.random.key(0)))

        def loss(params, state, x):
            y, new_state = block.apply(params, state, x, train=True)
            return jnp.sum(jnp.square(y.astype(jnp.float32))), new_state

        compiled = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 2), has_aux=True)).lower(
            params, state, jax.ShapeDtypeStruct(
                (batch, *in_shape), jnp.bfloat16, sharding=one)).compile()
        _block_entries[name] = _instructions(_entry(compiled))
    return _block_entries[name]


def _rank(shape):
    return shape[1].count(",") + 1 if shape[1] else 0


def _statistics_only(instructions):
    """Fusions that read a whole activation and give back per-channel
    vectors (or a vector a sample) only: a pass over the activation for
    statistics alone."""
    found = []
    for name, ins in instructions.items():
        if ins.opcode != "fusion" or not ins.result:
            continue
        reads = [s for o in ins.operands if o in instructions
                 for s in instructions[o].result]
        if (all(1 <= _rank(s) <= 2 for s in ins.result)
                and any(_rank(s) == 4 for s in reads)):
            found.append((name, ins.op_name))
    return found


@pytest.mark.parametrize("name", list(BLOCKS))
def test_no_pass_over_an_activation_takes_batchnorm_statistics_alone(topo, name):
    instructions = _block_program(topo, name)
    # the two-pass variance's mark, forward (`jvp(..)/bn/jit(_var)/reduce_sum`)
    # and backward (`transpose(jvp(..))/bn/jit(_var)/reduce_sum`)
    assert not [n for n, ins in instructions.items() if "_var" in ins.op_name]
    passes = _statistics_only(instructions)
    assert not [p for p in passes if "transpose(" not in p[1]], passes
    # backward: at most the one pass of sums a BatchNorm's gradient needs
    # (XLA fuses most of those into the conv's backward fusions as well)
    assert len(passes) <= len(BLOCKS[name][3]), passes


@pytest.mark.parametrize("name", list(BLOCKS))
def test_every_forward_conv_carries_both_moments_in_its_epilogue(topo, name):
    _, _, batch, scopes = BLOCKS[name]
    convs = {}
    for ins in _block_program(topo, name).values():
        m = FORWARD_CONV.search(ins.op_name)
        if m and ins.opcode == "fusion":
            convs[m.group(1)] = ins.result
    assert set(convs) == scopes
    for scope, result in convs.items():
        (activation,) = [dims for _, dims in result if dims.count(",") == 3]
        channels = activation.split(",")[-1]
        assert activation.split(",")[0] == str(batch)
        # every sample's sum of x and of x^2, float32, beside the bf16 activation
        moments = ("f32", f"{batch},{channels}")
        assert sorted(result) == sorted(
            [moments, moments, ("bf16", activation)]), (scope, result)


# ------------------- the catalog of the programs that were there (PR 32)

def _scope_of_at_pr30(op_name):
    """`obs/programs.py:scope_of` as PR 30 left it: only leading `grad`s
    go; nothing repeated is collapsed, no transform's scope is dropped."""
    from parallel_cnn_tpu.obs import programs

    parts = programs._split(op_name)
    if len(parts) < 2 or not parts[0].startswith(("jit(", "pjit(")):
        return "", ""
    body = parts[1:]
    if not programs._WRAPPED.match(body[-1]):
        body = body[:-1]
    path = [c for part in body for c in programs._unwrap(part)]
    if not path:
        return "", ""
    if path[0] == "optimizer":
        return "optimizer", "opt"
    if path[0] != "grad":
        return "", ""
    while path and path[0] == "grad":
        path = path[1:]
    return "/".join(path) or "grad", "bwd" if "transpose(" in op_name else "fwd"


def _step_text(topo, model, optimizer, in_shape, batch, mesh, tokens=False):
    where = (SingleDeviceSharding(topo.devices[0]) if mesh is None
             else NamedSharding(mesh, P()))
    rows = where if mesh is None else NamedSharding(mesh, P("data"))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
        jax.eval_shape(lambda k: zoo.init_state(model, k, in_shape, optimizer),
                       jax.random.key(0)))
    step = zoo.make_train_step(model, optimizer, 1, mesh)
    return step.lower(
        state, jax.ShapeDtypeStruct(
            (batch, *in_shape), jnp.int32 if tokens else jnp.bfloat16, sharding=rows),
        jax.ShapeDtypeStruct((batch, *in_shape) if tokens else (batch,), jnp.int32,
                             sharding=rows)).compile().as_text()


@pytest.mark.parametrize("name", ["convnext_b", "resnet50_dp4"])
def test_the_catalog_of_a_step_that_was_there_is_what_pr30_read(topo, name, monkeypatch):
    """PR 32 taught the catalog a rematerialised layer's name stack and a
    kernel the compiler names itself. Neither rule touches a step that was
    there: every instruction of ConvNeXt-B's step for one chip, and of
    ResNet-50's over the 2x2 mesh (collectives, the compiler's own
    custom-calls), has the scope and the phase PR 30's rules gave it."""
    from parallel_cnn_tpu import nn
    from parallel_cnn_tpu.obs import programs

    if name == "convnext_b":
        model, mesh = nn.convnext.convnext_b(num_classes=1000), None
        optimizer = zoo.make_optimizer(lr=1e-3, kind="adamw", weight_decay=0.05)
    else:
        model = resnet.resnet50(num_classes=1000, cifar_stem=False)
        mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
        optimizer = zoo.make_optimizer(lr=0.1, weight_decay=1e-4)
    text = _step_text(topo, model, optimizer, (64, 64, 3), 8, mesh)
    now = programs.parse(text)
    monkeypatch.setattr(programs, "scope_of", _scope_of_at_pr30)
    monkeypatch.setattr(programs, "_of_operands", lambda *a, **k: ("", ""))
    then = programs.parse(text)
    assert len(now) > 500 and set(now) == set(then)
    assert {n: e for n, e in now.items() if e != then[n]} == {}
    named = [e for e in now.values() if e.scope]
    assert len(named) > 1000
    if mesh is not None:
        assert any(e.opcode.startswith("all-reduce") for e in now.values())


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


# -- a window is a schedule of the second attention kernel pair (PR 41)

@pytest.mark.parametrize("window,visited", [(2048, 150), (None, 528)],
                         ids=["window_2048", "full"])
def test_the_grouped_causal_kernels_compile_at_the_cells_shapes(
        topo, window, visited):
    """Mosaic takes both directions at 16,384 positions of 32 query heads
    over 4 key/value heads of 128 (`dk` and `dv` of one (sequence,
    key/value head) fill their VMEM buffers exactly), with the window and
    without; the grid's last axis is the schedule's length."""
    from parallel_cnn_tpu.ops import pallas_attention as pa

    one_chip = SingleDeviceSharding(topo.devices[0])
    s, h, kv, d = 16384, 32, 4, 128
    t = pa.causal_tile(s, window, d)
    assert t == 512 and pa.causal_tiles_visited(s, t, window) == visited
    like = lambda heads, *rest, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (1, heads, s, *rest), dtype, sharding=one_chip)
    q, k = like(h, d), like(kv, d)
    kw = dict(scale=d ** -0.5, window=window, t=t)
    fwd = jax.jit(lambda q, k, v: pa.gc_forward(q, k, v, **kw)).lower(
        q, k, k).compile().as_text()
    bwd = jax.jit(lambda *a: pa.gc_backward(*a, **kw)).lower(
        q, k, k, q, like(h, dtype=jnp.float32), q).compile().as_text()
    assert "grouped_causal_attention_fwd" in fwd
    assert "grouped_causal_attention_bwd" in bwd
    assert not re.search(r"\[16384,16384\]", fwd + bwd)


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
