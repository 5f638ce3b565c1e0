"""Compiles for a described (not attached) TPU v5e: the safeguard kernels
and the whole donated smoke step of ``chip_smoke.py`` at full width.

Nothing runs, so these say nothing about results or times; they catch
what only the chip's compiler refuses (tiling, VMEM, HBM capacity) and
check that the step carries the compiled Gram kernel.  The topology is
described inside a fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker given this file loads the TPU
compiler.  All such compiles live in this one file."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke as cs
from repro.core import safeguard as sg
from repro.kernels.safeguard_filter import (fused_accumulate_sqdist,
                                            pairwise_sqdist)
from repro.models import transformer as T

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _smoke_d_pad() -> int:
    return sg.make_layout(T.init_abstract(cs.model_config())).d_padded


@pytest.mark.parametrize("kernel", ["pairwise_sqdist",
                                    "fused_accumulate_sqdist"])
def test_kernel_compiles_for_v5e_at_smoke_width(one_chip, kernel):
    buf = jax.ShapeDtypeStruct((cs.M, _smoke_d_pad()), jnp.float32,
                               sharding=one_chip)
    if kernel == "pairwise_sqdist":
        fn = lambda a: pairwise_sqdist(a, block_d=None, interpret=False)
        args = (buf,)
    else:
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt, sharding=one_chip)
        fn = lambda a, g, r, s: fused_accumulate_sqdist(a, g, r, s,
                                                        interpret=False)
        args = (buf, buf, scalar(jnp.int32), scalar(jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # rows are never padded: the kernel reads the buffer in place
    assert compiled.memory_analysis().temp_size_in_bytes < buf.size


def test_smoke_step_compiles_for_v5e_and_fits(one_chip, monkeypatch):
    monkeypatch.setattr(sg, "_on_tpu", lambda: True)
    built = {}

    def build():
        # traced, so the full-width state is only shapes, never allocated
        built["trainer"] = cs.train_lib.build_trainer(cs.model_config(),
                                                      cs.smoke_args())
        return built["trainer"].state

    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    state = jax.tree.map(put, jax.eval_shape(build))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cs.M, cs.PER_WORKER_BATCH, cs.SEQ), jnp.int32, sharding=one_chip)}
    compiled = built["trainer"].step_fn.lower(state, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the state is donated: the outputs live in the arguments' buffers
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < HBM_BYTES
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert live < HBM_BYTES


def test_scoped_smoke_step_names_its_gram_kernel_for_v5e(one_chip,
                                                          monkeypatch):
    """The v5e step of a smoke safeguard run keeps the Gram kernel's
    instruction name, ``pairwise_sqdist_kernel.N``, by which a device
    trace finds the kernel, and its ``op_name`` lies under the defense's
    distance pass."""
    from repro import configs as C
    from repro.launch import train as train_lib
    m, seq = 4, 256
    args = train_lib.parse_args([
        "--workers", str(m), "--byz", "1", "--batch", str(m), "--seq",
        str(seq), "--attack", "sign_flip", "--defense", "safeguard_double",
        "--t0", "2", "--t1", "4", "--floor", "0.01", "--lr", "0.005"])
    monkeypatch.setattr(sg, "_on_tpu", lambda: True)
    built = {}

    def build():
        built["trainer"] = train_lib.build_trainer(
            C.get_smoke("mamba2-130m"), args)
        return built["trainer"].state

    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    state = jax.tree.map(put, jax.eval_shape(build))
    batch = {"tokens": jax.ShapeDtypeStruct((m, 1, seq), jnp.int32,
                                            sharding=one_chip)}
    text = built["trainer"].step_fn.lower(state, batch).compile().as_text()
    grams = re.findall(r'^\s*%?pairwise_sqdist_kernel\.\d+ = .*'
                       r'op_name="([^"]*)"', text, flags=re.M)
    assert len(grams) == 2                           # A and B
    for op_name in grams:
        parts = op_name.split("/")
        assert "defense" in parts and "distance" in parts
        assert parts.index("defense") < parts.index("distance")
