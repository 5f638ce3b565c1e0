"""Each cell's step compiles for a described (not attached) TPU v5e at its
real size and fits one chip's memory.

Nothing runs: this catches what only the chip's compiler refuses and
gives the compiler's memory figures, which ``PERF.md`` sets beside the
cells' reckonings (``-s`` prints them).  The topology is described inside
a fixture, never at import; all such compiles live in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from chipbench import bench
from chipbench import run as runner

HBM_BYTES = 16 * 10**9
CELLS = [w["name"] for w in bench.benchmark()["workloads"]
         if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize("name", CELLS)
def test_cell_step_compiles_for_v5e_and_fits(one_chip, name, monkeypatch):
    from repro.core import safeguard as sg
    from repro.launch import train as train_lib
    monkeypatch.setattr(sg, "_on_tpu", lambda: True)
    cell = bench.cell(name)
    tr = cell["traffic"]
    built = {}

    def build():
        # traced, so the full-size state is only shapes, never allocated
        built["trainer"] = train_lib.build_trainer(
            runner.model_config(cell["config"]), runner.train_args(tr, 1))
        return built["trainer"].state

    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    state = jax.tree.map(put, jax.eval_shape(build))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (tr["workers"], tr["batch_per_worker"], tr["seq_len"]), jnp.int32,
        sharding=one_chip)}
    compiled = built["trainer"].step_fn.lower(state, batch).compile()
    if tr["defense"] == "safeguard_double":
        assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\n{name}: arguments {mem.argument_size_in_bytes} temps "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"live {live} bytes")
    assert live < HBM_BYTES
