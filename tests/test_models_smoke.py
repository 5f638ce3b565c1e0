"""Per-architecture smoke tests (assignment requirement): reduced configs
(2 layers, d_model <= 512, <= 4 experts), one forward + one train step on
CPU, asserting output shapes and no NaNs."""

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.configs.base import TrainConfig
from repro.core.safeguard import SafeguardConfig
from repro.models import transformer as T
from repro.optim import make_optimizer
from repro.train import init_train_state, make_train_step

ALL_ARCHS = C.ARCH_IDS + C.EXTRA_IDS
B, L = 2, 32


def make_batch(cfg, key, batch=B, seq=L):
    if cfg.embed_stub:
        return {"embeds": 0.1 * jax.random.normal(key, (batch, seq,
                                                        cfg.d_model)),
                "labels": jax.random.randint(key, (batch, seq), 0,
                                             cfg.vocab_size)}
    return {"tokens": jax.random.randint(key, (batch, seq), 0,
                                         cfg.vocab_size)}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_config_is_reduced(arch):
    cfg = C.get_smoke(arch)
    assert cfg.n_layers <= 3
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = C.get(arch)
    table = {
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "tinyllama-1.1b-swa": (22, 2048, 32, 4, 5632, 32000),
        "stablelm-1.6b": (24, 2048, 32, 32, 5632, 100352),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
    }
    nl, d, h, kv, ff, v = table[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (nl, d, h, kv, ff, v)
    assert cfg.source


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch, rng):
    cfg = C.get_smoke(arch)
    params = T.init_params(cfg, rng)
    batch = make_batch(cfg, rng)
    inputs = batch.get("tokens", batch.get("embeds"))
    logits, _, aux = T.forward(params, cfg, inputs, mode="train")
    assert logits.shape == (B, L, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux["moe_aux"]))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_safeguarded_train_step(arch, rng):
    cfg = C.get_smoke(arch)
    m = 4
    params = T.init_params(cfg, rng)
    opt = make_optimizer(TrainConfig(lr=0.01))
    sg_cfg = SafeguardConfig(m=m, T0=10, T1=20, threshold_floor=5.0)
    state = init_train_state(params, opt, sg_cfg=sg_cfg)
    step = make_train_step(lambda p, b: T.loss_fn(p, cfg, b), opt,
                           byz_mask=jnp.zeros((m,), bool), sg_cfg=sg_cfg)
    wb = jax.tree.map(
        lambda x: jnp.stack([x] * m), make_batch(cfg, rng, batch=2))
    new_state, metrics = step(state, wb)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert int(metrics["n_good"]) == m
    assert int(new_state.step) == 1
    # params actually moved
    moved = jax.tree.map(
        lambda a, b_: bool(jnp.any(a != b_)), state.params,
        new_state.params)
    assert any(jax.tree_util.tree_leaves(moved))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_decode_matches_forward(arch, rng):
    cfg = C.get_smoke(arch)
    params = T.init_params(cfg, rng)
    Lp, nd = 16, 4
    batch = make_batch(cfg, rng, seq=Lp + nd)
    seq = batch.get("tokens", batch.get("embeds"))
    full, _, _ = T.forward(params, cfg, seq, mode="train")
    last, cache = T.prefill(params, cfg, seq[:, :Lp], max_seq=Lp + nd)
    errs = [float(jnp.abs(last - full[:, Lp - 1]).max())]
    for i in range(nd):
        tok = seq[:, Lp + i:Lp + i + 1]
        lg, cache = T.decode_step(params, cfg, tok, cache)
        errs.append(float(jnp.abs(lg - full[:, Lp + i]).max()))
    assert max(errs) < 2e-4, errs


@pytest.mark.parametrize("arch", ["tinyllama-1.1b-swa", "recurrentgemma-2b",
                                  "mamba2-130m"])
def test_subquadratic_flag(arch):
    assert C.get(arch).sub_quadratic


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "granite-34b",
                                  "deepseek-v2-236b", "musicgen-medium"])
def test_full_attention_not_subquadratic(arch):
    assert not C.get(arch).sub_quadratic
