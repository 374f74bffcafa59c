"""Programs of the image models compiled for a described v5e (no chip, no
run): what PR 25 found about layouts and what PR 28 found about BatchNorm's
statistics are properties of the compiled program, so they are held here.
`select_batch` reads the store where it lies (no temporaries, no copy of
the store), on one chip and over a 2x2 mesh, and the one-time conversion
works through the set in blocks. A residual block's forward and backward
have no pass that only takes a BatchNorm's statistics: both moments of
every sample ride the epilogue of the conv that produces the activation.
And the catalog reads the steps that were there as PR 30 read them.

The topology is described inside a fixture, a file of tests/test_compiled_*
at a time (tests/compiled_programs.py): the files start early in the
alphabet because they are long."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from compiled_programs import _entry, _instructions, _rank, _step_text, described_v5e
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from parallel_cnn_tpu.nn import resnet
from parallel_cnn_tpu.train import zoo

IN_SHAPE = (224, 224, 3)
ROWS = 224 * 224 * 3 // 128  # 1,176: a multiple of 8


@pytest.fixture(scope="module")
def topo():
    yield from described_v5e()


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
_block_entries = {}


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



@pytest.mark.parametrize("name", ["convnext_b", "resnet50_dp4"])
def test_the_catalog_of_a_step_that_was_there_is_what_pr30_read(topo, name, monkeypatch):
    """PR 32 taught the catalog a rematerialised layer's name stack and a
    kernel the compiler names itself. Neither rule touches a step that was
    there: every instruction of ConvNeXt-B's step for one chip, and of
    ResNet-50's over the 2x2 mesh (collectives, the compiler's own
    custom-calls), has the scope and the phase PR 30's rules gave it.
    ConvNeXt-B at its published widths and stage depths 2-2-5-2 of 3-3-27-3:
    a name stack does not depend on how often its block is repeated, and
    eleven blocks are 2,452 instructions, 1,167 of them named (36 blocks:
    11,679 and 3,396, for three times the compile)."""
    from parallel_cnn_tpu import nn
    from parallel_cnn_tpu.obs import programs

    if name == "convnext_b":
        model, mesh = nn.convnext.convnext(
            (2, 2, 5, 2), (128, 256, 512, 1024), 1000, 0.5, 1e-6), None
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
