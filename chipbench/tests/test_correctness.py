"""The correctness check at a size a test run holds, on the CPU.

A sound run of each tiny cell is correct; the same run with the timed path
broken underneath (a step that returns its state unchanged; half of each
worker's batch left out of the loss) is not; and the control, the
reference with float8 operands in the program's place, fails the check.
The limits here are the tiny cells' own, set from CPU readings: the
program reads at most 3.0e-5 on ``loss``, 3.0e-3 on ``grad``, 4.9e-3 on
``update``, 1.4e-3 on ``dist`` and 2.0e-2 on ``moved_diff``; the control
at least 1.5e-4, 1.2e-2, 6.8e-3, 6.3e-3 and 5.9e-2.  The cells of
BENCHMARK.json carry theirs in ``chipbench/workloads``.
"""

import dataclasses
import gc

import jax
import pytest

from chipbench import bench, generator, oracle
from chipbench import run as runner

LIMITS = {"loss": 1e-4, "grad": 8e-3, "update": 8e-3, "dist": 4e-3, "good": 0,
          "moved_diff": 3e-2}
SAFEGUARD = ("loss", "grad", "update", "dist", "good", "moved_diff")
MEAN = ("loss", "grad", "update", "moved_diff")
MODELS = {
    "mamba2": (
        {"model_type": "mamba2", "hidden_size": 64, "expand": 2,
         "head_dim": 16, "n_groups": 1, "state_size": 16, "conv_kernel": 4,
         "chunk_size": 16, "vocab_size": 512, "num_hidden_layers": 2,
         "rms_norm_eps": 1e-6, "torch_dtype": "bfloat16"},
        {"d_model": 64, "ssm_head_dim": 16, "d_state": 16, "vocab_size": 512,
         "n_layers": 2, "ssm_chunk": 16}),
}
SEED = 2**31 + 17


PROGRAM = {"mamba2": "mamba2-130m"}
SG = "sg-m4-1x2048"
MEAN_MIX = {"defense": "mean", "attack": "none", "byzantine": 0}


def tiny_cell(arch: str, traffic: str, chips: int = 1, mix=None) -> dict:
    model, overrides = MODELS[arch]
    bm = bench.benchmark()
    tr = dict(bench.traffic(traffic), seq_len=64, log_every=4, pool=8,
              **(mix or {}))
    names = MEAN if tr["defense"] == "mean" else SAFEGUARD
    limits = {k: LIMITS[k] for k in names}
    return {"entry": {"name": f"tiny-{arch}-{traffic}", "chips": chips},
            "config": {"model": model, "param_count": 1,
                       "program": {"arch": PROGRAM[arch],
                                   "overrides": overrides}},
            "traffic": tr, "limits": limits,
            "end_to_end": bm["end_to_end"], "per_layer": bm["per_layer"]}


def run(cell):
    return runner.run_cell(cell, seed=SEED, seconds=0.5, trace=False,
                           devices=jax.devices(),
                           peaks=bench.peaks("TPU v5 lite"), t_start=0.0)


@pytest.mark.parametrize("mix", [None, MEAN_MIX], ids=["safeguard", "mean"])
def test_sound_run_is_correct(mix):
    res = run(tiny_cell("mamba2", SG, mix=mix))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "step_p90_ms",
                                   "peak_hbm_gib", "setup_s"}


def test_step_returning_its_state_unchanged_is_caught(monkeypatch):
    from repro.launch import train as train_lib
    real = train_lib.make_train_step

    def broken(*a, **kw):
        step = real(*a, **kw)

        def same_state(state, batch, *rest):
            _, metrics = step(state, batch, *rest)
            return state, metrics
        return same_state

    monkeypatch.setattr(train_lib, "make_train_step", broken)
    res = run(tiny_cell("mamba2", SG))
    assert not res["correct"]
    assert res["checks"]["update"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    from repro.models import transformer as T
    real = T.loss_fn

    def half(params, cfg, batch, **kw):
        toks = batch["tokens"]
        return real(params, cfg, {"tokens": toks[:, :toks.shape[1] // 2]},
                    **kw)

    monkeypatch.setattr(T, "loss_fn", half)
    res = run(tiny_cell("mamba2", SG))
    assert not res["correct"]
    assert res["checks"]["grad"]["value"] > LIMITS["grad"]


@pytest.mark.parametrize("mix", [None, MEAN_MIX], ids=["safeguard", "mean"])
def test_control_in_lower_precision_fails(mix):
    cell = tiny_cell("mamba2", SG, mix=mix)
    pool = generator.batches(cell["traffic"], vocab=512, seed=SEED)
    ref = oracle.follow(cell, SEED, pool[:oracle.STEPS])
    control = oracle.follow(cell, SEED, pool[:oracle.STEPS], precision="fp8")
    checks = oracle.compare(control, ref, cell["limits"])
    assert not oracle.judge(checks), checks
    gc.collect()


def test_tiny_configs_are_the_program_at_tiny_size():
    from repro import configs as C
    for arch, (model, overrides) in MODELS.items():
        cfg = dataclasses.replace(C.get(PROGRAM[arch]), **overrides)
        assert cfg.d_model == model["hidden_size"]


FOUR_CHIPS = """
import json, sys
from chipbench import run as runner
from test_correctness import run, tiny_cell
seen = {}
real = runner.start
def start(cell, seed):
    out = real(cell, seed)
    seen["B"] = len(out[0].state.defense_state.B.sharding.device_set)
    seen["batch"] = len(out[2][0]["tokens"].sharding.device_set)
    return out
runner.start = start
res = run(tiny_cell("mamba2", "sg-m4-1x2048", chips=4))
print(json.dumps({"correct": res["correct"], "count": res["device"]["count"],
                  "checks": res["checks"], **seen}))
"""


def test_four_chip_cell_runs_on_its_mesh():
    """A cell of four chips runs on the training CLI's ``(data=4,
    model=1)`` mesh, accumulators and batches over all four devices, and
    is correct: on four virtual CPU devices, in a process of its own."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(here), str(bench.ROOT), str(bench.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", FOUR_CHIPS], cwd=here, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["count"] == 4 and res["B"] == 4 and res["batch"] == 4
    assert res["correct"], res["checks"]
