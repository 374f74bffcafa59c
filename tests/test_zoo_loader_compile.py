"""The device loader's programs compiled for a described v5e (no chip, no
run): what PR 25 found about layouts is a property of the compiled program,
so it is held here. `select_batch` reads the store where it lies (no
temporaries, no copy of the store), on one chip and over a 2x2 mesh, and
the one-time conversion works through the set in blocks.

One file, and the topology is described inside a fixture: only the worker
that runs this file loads the TPU compiler."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

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
