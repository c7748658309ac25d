"""Compile each cell's timed program for a TPU v5e that is described,
not attached, and read its ``memory_analysis()``: the training cells'
round program on their mesh.  Nothing runs.  The
program's kernel dispatch asks ``jax.default_backend()``, so the test
tells it "tpu" to compile the Pallas kernels the chip runs
(``interpret=False``).

    python -m pytest -s bench/tests/test_v5e_compile.py   # prints GiB
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import model as bmodel
from bench import run as brun

BENCH = brun.load_json(brun.CHECKOUT / "BENCHMARK.json")
GIB = 2.0 ** 30
#: a v5e chip's HBM as JAX reports it (16 GiB)
HBM = 16 * GIB


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu(topo):
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mp = pytest.MonkeyPatch()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    yield topo
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def sds(x, sharding):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


@pytest.mark.parametrize("name", ["yi6b-train-h4", "yi34b-train-h1"])
def test_train_round_program(tpu, name):
    from bench.drivers import train
    cell = brun.Cell(BENCH, name)
    t = cell.traffic
    cfg = bmodel.program_config(cell.config)
    dm = bmodel.dims(cfg)
    mesh = jax.make_mesh(
        tuple(t["mesh"]), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
        devices=tpu.devices[:cell.chips])
    shard = train.shardings(cfg, mesh)
    shapes = bmodel.leaf_shapes(dm)
    params = bmodel.nest({p: jax.ShapeDtypeStruct(
        shapes[p], jnp.dtype(dm["dtype"]), sharding=shard[p])
        for p in shapes})
    init_fn, round_fn = train.program(cfg, t, mesh, params)
    init = jax.jit(init_fn).lower(params).compile()
    state = jax.tree_util.tree_map(sds, init.out_info, init.output_shardings)
    R, H = int(t["mesh"][0]), int(t["H"])
    block = {"tokens": jax.ShapeDtypeStruct(
        (H, R, int(t["batch"]), int(t["seq"]) + 1), jnp.int32,
        sharding=NamedSharding(mesh, P(None, "data")))}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    compiled = round_fn.lower(state, block, key).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\n{name}: round program {total / GIB:.2f} GiB per chip "
          f"(arguments {mem.argument_size_in_bytes / GIB:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / GIB:.2f})")
    assert total < HBM
