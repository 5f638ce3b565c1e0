"""Per-phase device time of one cell's train step, on the chip.

    python chipbench/phase_split.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up and drives its traced window as ``run.py --trace 1``
does (the same set-up, feed, step wrapper and chunks of
``Trainer.run``), without the correctness check that follows it there.
Then it compiles the window's step once more for its HLO text and
reduces the trace by the program's own names (``scopes.py``).  The last
line of stdout is one JSON object: the window's ``tokens_per_s``,
``step_p90_ms`` and ``steps``, the per-phase metrics
(``scopes.layer_metrics``, ms per step), the reduction's per-phase keys,
the ops under no phase that took most time, and a breakdown whose ops
carry their phase and whose idle gaps are named by the innermost host
span.  Without a TPU it exits with code 2.  It stands in until
``run.py --trace 1`` prints the split itself.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                   # noqa: E402
import glob                                                       # noqa: E402
import json                                                       # noqa: E402
import sys                                                        # noqa: E402
import tempfile                                                   # noqa: E402
from pathlib import Path                                          # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench, scopes                               # noqa: E402
from chipbench import run as runner                               # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    return ap.parse_args(argv)


def split(cell: dict, *, seed: int, seconds: float) -> dict:
    import jax

    tr = cell["traffic"]
    annotate = jax.profiler.TraceAnnotation
    trainer, stepper, pool, _, phases = runner.start(cell, seed)
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        stepper.stamps, stepper.losses, stepper.steps = [], [], 0
        stepper.blocked_s = 0.0
        t0 = time.perf_counter()
        with annotate("chipbench.window"):
            while time.perf_counter() - t0 < seconds:
                stepper.sync_in = tr["log_every"]
                trainer.run(tr["log_every"], verbose=False)
        jax.profiler.stop_trace()
        hlo = stepper.fn.lower(trainer.state, pool[0]).compile().as_text()
        red = scopes.reduce(
            glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0], hlo)
    win = runner.window_metrics(stepper, t0, tr)
    ops = scopes.op_scopes(hlo)
    unscoped = sorted(((v, k) for k, v in red["op_self_s"].items()
                       if k in ops and scopes.phase_of(ops[k]) is None),
                      reverse=True)[:10]
    return {
        "tokens_per_s": win["tokens_per_s"],
        "step_p90_ms": win["step_p90_ms"], "steps": stepper.steps,
        "setup_s": t0 - T_START, "setup_phases": phases,
        "metrics": scopes.layer_metrics(red, stepper.steps),
        **{k: red[k] for k in ("window_s", "busy_s", "step_busy_s",
                               "phase_s", "scope_s", "unscoped_s",
                               "carried_s", "unmapped")},
        "breakdown": scopes.breakdown(red, ops),
        "unscoped_ops": [[k, ops[k], v] for v, k in unscoped],
        "long_gaps": [[f"{k} at {at:.3f} s", v]
                      for k, v, at in red["gaps"] if v >= 1e-3]}


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = bench.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["entry"]["chips"]:
        print(f"phase_split: cell {args.workload!r} needs "
              f"{cell['entry']['chips']} TPU chip(s)", file=sys.stderr)
        return 2
    print(json.dumps(split(cell, seed=args.seed, seconds=args.seconds)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
