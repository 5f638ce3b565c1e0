"""Chip smoke: the Byzantine training step on a TPU at TinyLlama-1.1B widths.

    python chip_smoke.py              # one chip: the main path
    python chip_smoke.py --chips 4    # sharded step on four chips vs one

The run is built through ``repro.launch.train.build_trainer`` — the same
config -> defense -> ``make_train_step`` -> ``Trainer`` wiring as the
training CLI, with the state donated to the jitted step.  One chip:
``tinyllama-1.1b`` at its published widths cut to 2 layers, m = 4 workers
(1 Byzantine, ``sign_flip``), the double safeguard with the Pallas Gram
kernel.  It fails unless the compiled step contains the kernel
(``tpu_custom_call``), every logged loss is finite, the kernel's distances
of the final short-window buffer match ``kernels/safeguard_filter/ref.py``,
and by the last step the Byzantine worker is evicted and no honest one is.

``--chips 4`` runs only the multi-chip phase: the same run on a
``(data=4, model=1)`` mesh, one worker per chip, placed by the rules of
``launch/specs.py`` with the XLA distance pass; then, once that state is
freed, the same steps on ``jax.devices()[0]`` alone.  The eviction
decisions must be identical and the first logged losses agree within
``LOSS_RTOL``.

Exits non-zero without a result line when JAX finds no TPU.  Timings are
one-off observations, not a benchmark.  The last line of stdout is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import itertools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402
from jax.sharding import AxisType                              # noqa: E402

from repro import configs as C                                 # noqa: E402
from repro.kernels.safeguard_filter import pairwise_sqdist     # noqa: E402
from repro.kernels.safeguard_filter import ref as sf_ref       # noqa: E402
from repro.launch import train as train_lib                    # noqa: E402
from repro.launch.compile_cache import enable_compile_cache    # noqa: E402

ARCH = "tinyllama-1.1b"
N_LAYERS = 2              # of the published 22
M, N_BYZ = 4, 1           # workers, Byzantine workers
PER_WORKER_BATCH, SEQ = 1, 1024
STEPS, T0, T1, FLOOR, LR = 40, 10, 40, 0.01, 0.005
LOG_EVERY = 5
# kernel vs oracle: |K - R| <= KERNEL_RTOL * (|b_i|^2 + |b_j|^2), the size
# of the terms the distance cancels.  Both sum d ~ 2e8 exact f32 products
# by f32 tree reductions; a float64 host check of a real buffer put the
# oracle at 9e-7 of that scale on a v5e, and a single running f32 sum over
# the tiles at 1.4e-3
KERNEL_RTOL = 1e-4
# sharded vs single-device loss at the first logged step, where the two
# runs differ only in the order of their reductions (6e-5 apart on a
# v5e).  Later steps are reported, not held to it: SGD on a bf16 model
# amplifies that difference along the trajectory (3.8e-2 by step 25).
LOSS_RTOL = 1e-3
# what chip 0 may still hold of the sharded run when the single-device run
# starts: nothing but stray scalars
FREED_BYTES = 2**20

CUTS = (
    ("depth", f"22 -> {N_LAYERS} layers",
     "the two f32 (m, d) safeguard accumulators grow with depth: at 2 "
     "layers (219M parameters) they are 7.0 GB of a 16 GB chip"),
    ("workers", f"m = {M} (the paper runs 10), {N_BYZ} Byzantine",
     "at m = 10 and 2 layers the accumulators alone are 17.5 GB"),
    ("batch", f"{PER_WORKER_BATCH} x {SEQ} tokens per worker",
     "at 2 x 2048 per worker the step's temporaries are 11.7 GB and it "
     "does not fit even with its state donated"),
)


def model_config():
    """``tinyllama-1.1b`` at its published widths, ``N_LAYERS`` deep."""
    return dataclasses.replace(C.get(ARCH), n_layers=N_LAYERS)


def smoke_args(*, steps=STEPS, seq=SEQ, t0=T0, t1=T1, floor=FLOOR, lr=LR,
               log_every=LOG_EVERY):
    """The training CLI's own arguments for the smoke run."""
    return train_lib.parse_args([
        "--arch", ARCH, "--full", "--workers", str(M), "--byz", str(N_BYZ),
        "--batch", str(M * PER_WORKER_BATCH), "--seq", str(seq),
        "--attack", "sign_flip", "--defense", "safeguard",
        "--t0", str(t0), "--t1", str(t1), "--floor", str(floor),
        "--lr", str(lr), "--steps", str(steps),
        "--log-every", str(log_every)])


def run_training(cfg, args, *, mesh=None):
    """Build the run through ``launch.train``, compile its donated step
    once, and take ``args.steps`` steps, timing each step (its batch
    already generated) to completion.

    Returns ``(trainer, record)``; ``record`` holds the compile seconds,
    the per-step seconds, the compiled step's HLO text, the logged
    history and the final eviction state."""
    trainer = train_lib.build_trainer(cfg, args, mesh=mesh)
    batch = next(trainer.data_iter)
    trainer.data_iter = itertools.chain([batch], trainer.data_iter)
    t = time.perf_counter()
    compiled = trainer.step_fn.lower(trainer.state, batch).compile()
    compile_s = time.perf_counter() - t

    step_s = []

    def timed_step(state, batch):
        jax.block_until_ready(batch)
        t = time.perf_counter()
        out = jax.block_until_ready(compiled(state, batch))
        step_s.append(time.perf_counter() - t)
        return out

    trainer.step_fn = timed_step
    history = trainer.run(args.steps)
    ds = trainer.state.defense_state
    return trainer, {
        "compile_s": compile_s, "step_s": step_s, "hlo": compiled.as_text(),
        "history": history, "good": np.asarray(ds.good),
        "evicted_at": np.asarray(ds.evicted_at)}


def check_training(rec, n_byz: int) -> list:
    """Finite loss at every logged step; by the last step every Byzantine
    worker (rows ``< n_byz``) is evicted and no honest one is."""
    failures = []
    bad = [r["step"] for r in rec["history"] if not np.isfinite(r["loss"])]
    if bad:
        failures.append(f"loss not finite at logged steps {bad}")
    last = rec["history"][-1]
    if int(last["caught_byz"]) != n_byz or int(last["evicted_honest"]) != 0:
        failures.append(
            f"eviction: caught_byz={last['caught_byz']} (want {n_byz}), "
            f"evicted_honest={last['evicted_honest']} (want 0), "
            f"good={rec['good'].tolist()}")
    return failures


def check_kernel(buf, *, interpret: bool):
    """The Pallas ``pairwise_sqdist`` of ``buf`` against the oracle on the
    same buffer.  Returns ``(failures, worst error / tolerance scale)``."""
    got = np.asarray(pairwise_sqdist(buf, block_d=None, interpret=interpret))
    want = np.asarray(sf_ref.pairwise_sqdist(buf))
    sq = np.diagonal(np.asarray(sf_ref.gram(buf)))
    scale = np.maximum(sq[:, None] + sq[None, :], np.finfo(np.float32).tiny)
    worst = float(np.max(np.abs(got - want) / scale))
    failures = []
    if not worst <= KERNEL_RTOL:
        failures.append(f"kernel vs ref.py: max |K - R| / (|b_i|^2 + "
                        f"|b_j|^2) = {worst!r} > {KERNEL_RTOL}")
    return failures, worst


def phase_one_chip(cfg, args, *, on_tpu: bool):
    """The main path on one device.  Returns ``(failures, summary)``."""
    trainer, rec = run_training(cfg, args)
    failures = check_training(rec, args.byz)
    if on_tpu and "tpu_custom_call" not in rec["hlo"]:
        failures.append("compiled step has no tpu_custom_call: the Gram "
                        "kernel was interpreted, not compiled")
    buf = trainer.state.defense_state.B
    del trainer                      # free params and A before the check
    kernel_failures, worst = check_kernel(buf, interpret=not on_tpu)
    failures += kernel_failures
    summary = {"compile_s": rec["compile_s"],
               "median_step_s": statistics.median(rec["step_s"]),
               "kernel_err": worst, "good": rec["good"].tolist(),
               "evicted_at": rec["evicted_at"].tolist()}
    return failures, summary


def _losses(rec):
    return np.asarray([r["loss"] for r in rec["history"]])


def phase_multichip(cfg, args, n_chips: int = 4):
    """The sharded step on ``n_chips`` devices, one worker each, then the
    same steps on ``jax.devices()[0]`` alone once the sharded state is
    freed.  Returns ``(failures, summary)``."""
    if args.workers != n_chips:
        raise ValueError(f"one worker per chip: m={args.workers}, "
                         f"chips={n_chips}")
    mesh = jax.make_mesh((n_chips, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:n_chips])
    trainer, sharded = run_training(cfg, args, mesh=mesh)
    del trainer
    gc.collect()
    left = sum(s.data.nbytes for a in jax.live_arrays()
               for s in a.addressable_shards if s.device == jax.devices()[0])
    trainer, single = run_training(cfg, args)
    del trainer
    failures = []
    if left > FREED_BYTES:
        failures.append(f"chip 0 still held {left} bytes of the sharded run "
                        "when the single-device run started")
    if "tpu_custom_call" in sharded["hlo"]:
        failures.append("sharded step holds a Pallas call; it must use the "
                        "XLA distance pass")
    for name, rec in (("sharded", sharded), ("single", single)):
        failures += [f"{name}: {f}" for f in check_training(rec, args.byz)]
    for key in ("good", "evicted_at"):
        if not np.array_equal(sharded[key], single[key]):
            failures.append(f"{key} differs: sharded "
                            f"{sharded[key].tolist()} vs single "
                            f"{single[key].tolist()}")
    rel = np.abs(_losses(sharded) - _losses(single)) / np.abs(_losses(single))
    if not rel[0] <= LOSS_RTOL:
        failures.append(f"loss differs at the first logged step: relative "
                        f"{float(rel[0])!r} > {LOSS_RTOL}")
    summary = {"sharded_compile_s": sharded["compile_s"],
               "sharded_median_step_s": statistics.median(sharded["step_s"]),
               "single_compile_s": single["compile_s"],
               "single_median_step_s": statistics.median(single["step_s"]),
               "loss_rel_diff_first_logged": float(rel[0]),
               "loss_rel_diff_max": float(rel.max()),
               "chip0_bytes_left_after_sharded": left,
               "good": sharded["good"].tolist(),
               "evicted_at": sharded["evicted_at"].tolist()}
    return failures, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase and its comparison")
    opts = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform!r}); this "
              "smoke runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device_kind: {dev.device_kind} (count {len(devices)})")
    cfg = model_config()
    print(f"model: {ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
          f"{cfg.n_kv_heads} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"dtype={jnp.dtype(cfg.dtype).name}")
    for what, cut, why in CUTS:
        print(f"cut {what}: {cut} — {why}")
    args = smoke_args()

    if opts.chips == 1:
        failures, summary = phase_one_chip(cfg, args, on_tpu=True)
    else:
        failures, summary = phase_multichip(cfg, args, opts.chips)
    for k, v in summary.items():
        print(f"{k}: {v}")
    print("step times are a one-off observation, not a benchmark")
    for i, d in enumerate(devices[:opts.chips]):
        stats = d.memory_stats() or {}
        print(f"peak_bytes_in_use[{i}]: {stats.get('peak_bytes_in_use')}")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
