"""What the files of compiled programs share (tests/test_compiled_*.py; not
collected): the described v5e they compile for, a step's compiled text, and
an entry computation's instructions."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from parallel_cnn_tpu.train import zoo


def described_v5e():
    """The body of a file's module-scoped `topo` fixture: a v5e 2x2 described
    to the compiler (no chip, no run), with the compilation cache off around
    the file's tests. Every worker that runs such a file loads the TPU
    compiler; the tier-1 command allows it (`ALLOW_MULTIPLE_LIBTPU_LOAD`)."""
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


# array shapes of the result as (dtype, dims), opcode, operand names, op_name
Instruction = collections.namedtuple("Instruction", "result opcode operands op_name")



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


def _rank(shape):
    return shape[1].count(",") + 1 if shape[1] else 0


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
