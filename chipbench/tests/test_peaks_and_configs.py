"""The table of peaks and the configurations' sizes."""

import dataclasses
import math

import jax
import pytest

from chipbench import bench, flops


def test_known_device_has_its_peaks():
    p = bench.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no entry"):
        bench.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("name,count", [("mamba2-130m", 128_983_488)])
def test_configuration_parameter_count(name, count):
    from repro import configs as C
    from repro.models.transformer import init_abstract
    cfg_file = bench.load_json(bench.HERE / "configs" / f"{name}.json")
    prog = cfg_file["program"]
    cfg = dataclasses.replace(C.get(prog["arch"]), **prog["overrides"])
    shapes = init_abstract(cfg)
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)) == count
    assert cfg_file["param_count"] == count
    model = cfg_file["model"]
    assert (cfg.d_model, cfg.n_layers, cfg.vocab_size) == (
        model["hidden_size"], model["num_hidden_layers"], model["vocab_size"])
    ref = bench.reference(model["model_type"])
    ref_shapes = jax.eval_shape(lambda k: ref.init(model, k),
                                jax.random.PRNGKey(0))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(ref_shapes)) == count


def test_step_flops_mamba2():
    cell = bench.cell("mamba2-sg-m4-l2048")
    f = flops.step_flops(cell["config"]["model"], cell["traffic"])
    # per layer: in_proj 768 x (2 x 1536 + 2 x 128 + 24), out_proj 1536 x
    # 768; tied head 768 x 50280; SSD at Q = 256, N = 128, P = 64, H = 24
    matmul = 24 * (768 * 3352 + 1536 * 768) + 768 * 50280
    ssd = 3 * 24 * (2 * 256 * 128 + 2 * 256 * 64 * 24 + 4 * 128 * 64 * 24)
    assert f == (6 * matmul + ssd) * 4 * 2048
