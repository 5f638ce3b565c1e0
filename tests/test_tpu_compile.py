"""Compiles for a described (not attached) TPU v5e: the safeguard kernels
and the whole donated smoke step of ``chip_smoke.py`` at full width.

Nothing runs, so these say nothing about results or times; they catch
what only the chip's compiler refuses (tiling, VMEM, HBM capacity) and
check that the step carries the compiled Gram kernel.  The topology is
described inside a fixture, never at import, so every pytest-xdist worker
collects the same tests and only the worker given this file loads the TPU
compiler.  All such compiles live in this one file."""

import functools
import re
from unittest import mock

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


def _smoke_layout(fused: bool = False) -> sg.FlatLayout:
    """The safeguard's layout of the smoke model, as ``init_state`` lays
    it out for the fused pass (on a TPU) or for the Gram kernel."""
    cfg = sg.SafeguardConfig(m=cs.M)
    with mock.patch.object(sg, "_fused_platform", lambda: fused):
        return jax.eval_shape(functools.partial(sg.init_state, cfg),
                              T.init_abstract(cs.model_config())).layout


def _compile_smoke_step(one_chip, fused: bool):
    """The donated smoke step of ``chip_smoke.py``, built through
    ``build_trainer`` with the safeguard's state laid out for the fused
    pass (as on a TPU) or for the Gram kernel, compiled for one v5e chip.
    Traced, so the full-width state is only shapes."""
    built = {}

    def build():
        with mock.patch.object(sg, "_on_tpu", lambda: True), \
                mock.patch.object(sg, "_fused_platform", lambda: fused):
            built["trainer"] = cs.train_lib.build_trainer(cs.model_config(),
                                                          cs.smoke_args())
        return built["trainer"].state

    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    state = jax.tree.map(put, jax.eval_shape(build))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (cs.M, cs.PER_WORKER_BATCH, cs.SEQ), jnp.int32, sharding=one_chip)}
    with mock.patch.object(sg, "_on_tpu", lambda: True):
        return built["trainer"].step_fn.lower(state, batch).compile()


@pytest.fixture(scope="module")
def smoke_step(one_chip):
    """``smoke_step(fused)``: the compiled smoke step, once per layout."""
    return functools.cache(functools.partial(_compile_smoke_step, one_chip))


@pytest.mark.parametrize("kernel", ["pairwise_sqdist",
                                    "fused_accumulate_sqdist"])
def test_kernel_compiles_for_v5e_at_smoke_width(one_chip, kernel):
    lay = _smoke_layout(fused=kernel == "fused_accumulate_sqdist")
    buf = jax.ShapeDtypeStruct((cs.M, lay.d_padded), jnp.float32,
                               sharding=one_chip)
    if kernel == "pairwise_sqdist":
        fn = lambda a: pairwise_sqdist(a, block_d=None, interpret=False)
        compiled = jax.jit(fn).lower(buf).compile()
    else:
        leaves = [jax.ShapeDtypeStruct((cs.M,) + shape, jnp.dtype(dt),
                                       sharding=one_chip)
                  for shape, dt in zip(lay.shapes, lay.dtypes)]
        resets = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
        scale = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
        fn = lambda gs, a, b, r, s: fused_accumulate_sqdist(
            gs, lay.offsets, (a, b), r, s,
            align=sg._pad_multiple(lay.d), interpret=False)
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
            leaves, buf, buf, resets, scale).compile()
        mem = compiled.memory_analysis()
        # A and B are updated in place: their outputs are their arguments
        assert mem.alias_size_in_bytes >= 2 * buf.size * 4
    assert "tpu_custom_call" in compiled.as_text()
    # rows are never padded: the kernel reads the buffer in place
    assert compiled.memory_analysis().temp_size_in_bytes < buf.size


def test_smoke_step_compiles_for_v5e_and_fits(smoke_step):
    compiled = smoke_step(True)     # build_trainer's on one chip
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the state is donated: the outputs live in the arguments' buffers
    assert mem.alias_size_in_bytes > 0.99 * mem.argument_size_in_bytes
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes) < HBM_BYTES
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert live < HBM_BYTES


def test_fused_smoke_step_has_no_gram_kernel_or_accumulator_copy(
        smoke_step):
    """The fused pass updates A and B in place and forms their Grams in
    the same pass: the step runs no separate Gram kernel, copies no
    ``f32[m, d_pad]`` accumulator, and needs fewer temporaries than the
    scatter-and-Gram step of a state laid out off the TPU."""
    fused, scatter = smoke_step(True), smoke_step(False)
    text = fused.as_text()
    kernels = lambda name: re.findall(
        rf"^\s*%?{name}\.\d+ = .* custom-call\(", text, flags=re.M)
    assert kernels("fused_accumulate_sqdist_kernel")
    assert not kernels("pairwise_sqdist_kernel")
    d_pad = _smoke_layout(fused=True).d_padded
    copies = re.findall(rf"= f32\[{cs.M},{d_pad}\]\S* copy\(", text)
    assert not copies
    assert (fused.memory_analysis().temp_size_in_bytes
            < scatter.memory_analysis().temp_size_in_bytes)


def test_scoped_smoke_step_names_its_gram_kernel_for_v5e(one_chip,
                                                          monkeypatch):
    """The v5e step of a smoke safeguard run keeps the Gram kernel's
    instruction name, ``fused_accumulate_sqdist_kernel.N``, by which a
    device trace finds the kernel: one call per gradient leaf, which
    updates A and B and forms their Grams, its ``op_name`` under the
    defense's accumulate pass."""
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
    grams = re.findall(r'^\s*%?fused_accumulate_sqdist_kernel\.\d+ = .*'
                       r'op_name="([^"]*)"', text, flags=re.M)
    assert len(grams) == len(built["trainer"].state.defense_state.layout
                             .sizes)                  # one per leaf
    for op_name in grams:
        parts = op_name.split("/")
        assert "defense" in parts and "accumulate" in parts
        assert parts.index("defense") < parts.index("accumulate")


def test_held_expert_layer_compiles_for_v5e_under_worker_vmap(one_chip):
    """Granite-3.0-3B-A800M's MoE layer at one chip's share (10 of 40
    experts, published widths), its gradient vmapped over 4 workers of
    4096 tokens as the train step takes it: the TPU's ragged dot (which
    takes no batch dimension) compiles, forward and backward, for each
    worker in turn.  The compiler names each grouped matmul
    ``ragged-dot-none.N`` (its ``op_name`` too), by which a device trace
    finds them."""
    import dataclasses
    from repro import configs as C
    from repro.models import layers as L
    cfg = dataclasses.replace(C.get("granite-moe-3b-a800m"),
                              n_held_experts=10)
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(put, jax.eval_shape(
        lambda: L.moe_init(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((4, 1, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def grads(p, x):
        loss = lambda p, xi: L.moe_apply(p, cfg, xi)[0].astype(
            jnp.float32).sum()
        return jax.vmap(jax.grad(loss, argnums=(0, 1)), in_axes=(None, 0))(
            p, x)

    compiled = jax.jit(grads).lower(params, x).compile()
    dots = re.findall(r'^\s*%?ragged-dot-none[.\d]* = .*custom-call\(',
                      compiled.as_text(), flags=re.M)
    # three matmuls forward, and the rows' and the weights' cotangents of
    # each backward (the compiler may run a forward one twice)
    assert len(dots) >= 9
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < HBM_BYTES


def _granite_attention_grads(one_chip, on_tpu: bool):
    """One Granite-3.0-3B-A800M attention layer at the cell's widths (24
    query and 8 KV heads of 64, d 1536), its gradient under the layer's
    remat and vmapped over 4 workers of 4096 tokens as the train step
    takes it, compiled for one v5e chip with the backend check patched to
    ``on_tpu``."""
    from repro import configs as C
    from repro.models import layers as L
    cfg = C.get("granite-moe-3b-a800m")
    m, seq = 4, 4096
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(put, jax.eval_shape(
        lambda: L.attn_block_init(jax.random.PRNGKey(0), cfg)))
    x = jax.ShapeDtypeStruct((m, 1, seq, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    positions = jnp.arange(seq)[None]

    def grads(p, x):
        layer = jax.checkpoint(lambda p, xi: L.attn_block_apply(
            p, cfg, xi, positions=positions)[0].astype(jnp.float32).sum())
        return jax.vmap(jax.grad(layer, argnums=(0, 1)), in_axes=(None, 0))(
            p, x)

    with mock.patch.object(L, "_on_tpu", lambda: on_tpu):
        return jax.jit(grads).lower(params, x).compile()


def test_granite_attention_runs_the_pallas_kernel_for_v5e(one_chip):
    """The Granite cell's attention compiles for v5e as splash's Pallas
    kernels, the forward and the fused backward (one dkv kernel that also
    forms dq), with no loop of ``flash_attention_jnp`` left, and needs no
    more temporaries than the jnp path at the same shape."""
    kernel = _granite_attention_grads(one_chip, on_tpu=True)
    text = kernel.as_text()
    calls = lambda name: re.findall(
        rf"^\s*%?{name}[.\d]* = .*custom-call\(", text, flags=re.M)
    assert calls("splash_mha_fwd_residuals")
    assert calls("splash_mha_dkv_no_residuals")
    assert not calls("splash_mha_dq")
    assert "attn_kernel" in text
    assert "attn_jnp" not in text
    assert not re.search(r"^\s*%?while[.\d]* = ", text, flags=re.M)
    jnp_path = _granite_attention_grads(one_chip, on_tpu=False)
    assert "attn_jnp" in jnp_path.as_text()
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= jnp_path.memory_analysis().temp_size_in_bytes)
