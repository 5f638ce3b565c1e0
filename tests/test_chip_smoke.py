"""``chip_smoke.py`` on the CPU: its phase functions at the SMOKE config
(the Pallas kernel interpreted), its refusal to run without a TPU, the
training CLI wiring it shares, and the compile-cache helper."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as cs
from repro import configs as C
from repro.core import safeguard as sg
from repro.launch import compile_cache
from repro.launch import train as train_lib

ROOT = Path(__file__).resolve().parents[1]


def _smoke_args(**kw):
    return cs.smoke_args(**{"steps": 12, "seq": 32, "t0": 4, "t1": 8,
                            "log_every": 4, **kw})


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               **extra)
    return env


def test_phase_one_chip_at_smoke_config():
    failures, summary = cs.phase_one_chip(C.get_smoke(cs.ARCH),
                                          _smoke_args(), on_tpu=False)
    assert failures == []
    assert summary["good"] == [False, True, True, True]
    assert summary["evicted_at"][0] >= 0
    assert summary["kernel_err"] <= cs.KERNEL_RTOL


def test_phase_multichip_on_four_cpu_devices():
    code = (
        "import chip_smoke as cs\n"
        "from repro import configs as C\n"
        "assert len(cs.jax.devices()) == 4\n"
        "args = cs.smoke_args(steps=12, seq=32, t0=4, t1=8, log_every=4)\n"
        "failures, summary = cs.phase_multichip(C.get_smoke(cs.ARCH), args)\n"
        "assert failures == [], failures\n"
        "assert summary['good'] == [False, True, True, True], summary\n"
        "print('MULTICHIP_OK')\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MULTICHIP_OK" in out.stdout


@pytest.mark.parametrize("alone", [False, True],
                         ids=["cpu_only", "script_alone"])
def test_main_fails_without_tpu_and_prints_no_result(tmp_path, alone):
    script = ROOT / "chip_smoke.py"
    env = _env()
    if alone:
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
        env.pop("PYTHONPATH")
    out = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_check_training_flags_nan_loss_and_wrong_evictions():
    good = np.array([False, True, True, True])
    rec = {"good": good, "history": [
        {"step": 5, "loss": 3.0, "caught_byz": 1.0, "evicted_honest": 0.0},
        {"step": 10, "loss": 2.5, "caught_byz": 1.0, "evicted_honest": 0.0}]}
    assert cs.check_training(rec, 1) == []
    rec["history"][0]["loss"] = float("nan")
    assert any("not finite" in f for f in cs.check_training(rec, 1))
    rec["history"][0]["loss"] = 3.0
    rec["history"][-1]["evicted_honest"] = 1.0
    assert any("eviction" in f for f in cs.check_training(rec, 1))


def test_check_kernel_catches_a_wrong_kernel(monkeypatch):
    buf = jax.random.normal(jax.random.PRNGKey(0), (4, 1024))
    failures, worst = cs.check_kernel(buf, interpret=True)
    assert failures == [] and worst <= cs.KERNEL_RTOL
    monkeypatch.setattr(cs, "pairwise_sqdist",
                        lambda a, **kw: cs.sf_ref.pairwise_sqdist(a) * 1.01)
    failures, _ = cs.check_kernel(buf, interpret=True)
    assert len(failures) == 1


def test_smoke_config_keeps_published_widths():
    cfg, full = cs.model_config(), C.get(cs.ARCH)
    assert cfg.n_layers == cs.N_LAYERS < full.n_layers
    assert cfg == full.__class__(**{**full.__dict__,
                                    "n_layers": cs.N_LAYERS})
    args = cs.smoke_args()
    assert (args.workers, args.byz, args.batch, args.seq) == (
        cs.M, cs.N_BYZ, cs.M * cs.PER_WORKER_BATCH, cs.SEQ)
    assert (args.attack, args.defense) == ("sign_flip", "safeguard")


def test_train_cli_main_runs_through_build_trainer(tmp_path):
    out = tmp_path / "hist.json"
    train_lib.main(["--steps", "4", "--batch", "8", "--seq", "16",
                    "--workers", "4", "--byz", "1", "--t0", "2",
                    "--t1", "4", "--log-every", "2", "--out", str(out)])
    assert out.exists()


@pytest.mark.parametrize("mode,sketch", [("double", False),
                                         ("single", False),
                                         ("double", True)])
def test_safeguard_state_can_be_donated(mode, sketch):
    """A and B are distinct buffers, so a step donating the whole state
    does not receive one buffer twice."""
    cfg = sg.SafeguardConfig(m=4, T0=2, T1=4, mode=mode, use_sketch=sketch,
                             sketch_k=64, sketch_reps=2)
    params = {"w": jnp.zeros((8, 16))}
    st = sg.init_state(cfg, params)
    grads = {"w": jnp.ones((4, 8, 16))}
    step = jax.jit(lambda s, g: sg.safeguard_step(s, g, cfg)[0],
                   donate_argnums=0)
    st = step(st, grads)
    assert int(st.step) == 1


def test_compile_cache_honours_env(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
