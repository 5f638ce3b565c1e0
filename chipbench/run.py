"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the run through the training CLI's wiring
(``repro.launch.train.build_trainer``: weights from the seed, the donated
jitted ``make_train_step``, ``Trainer``; on a cell of several chips with
its ``(data, model)`` mesh, one worker per chip), draws the cell's pool of
token batches on the device, and takes the first steps of the correctness check
through the window's own step and feed, which also compiles the step.  The
window then drives ``Trainer.run`` in chunks until ``--seconds`` have
passed, keeping one step in flight: after dispatching step i the step
wrapper waits for step i-1 and stamps the time.  Once the window has
closed and the peak memory is read, the run is freed and the plain
reference follows the checked steps.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` traces
the window with the profiler and prints its per-layer metrics and a
breakdown.  The last line of stdout is one JSON object; the numbers of the
correctness check are the last lines of stderr and the result's last key.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                   # noqa: E402
import gc                                                         # noqa: E402
import glob                                                       # noqa: E402
import json                                                       # noqa: E402
import math                                                       # noqa: E402
import statistics                                                 # noqa: E402
import sys                                                        # noqa: E402
import tempfile                                                   # noqa: E402
from pathlib import Path                                          # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench, flops, generator, oracle, trace_reduce  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Stepper:
    """Wraps the jitted step and keeps one step in flight: after
    dispatching step i it waits for step i-1's loss and stamps the time.
    ``sync_in`` counts down the steps of a chunk; at its end the step is
    waited for at once (``Trainer.run`` reads the last step of a chunk)."""

    def __init__(self, step_fn, annotate):
        self.fn = step_fn
        self.annotate = annotate
        self.prev = None
        self.stamps = []
        self.losses = []
        self.blocked_s = 0.0
        self.steps = 0
        self.sync_in = 1
        self.record = None

    def __call__(self, state, batch):
        with self.annotate("chipbench.dispatch"):
            state, metrics = self.fn(state, batch)
        self.steps += 1
        if self.record is not None:
            self.record.append(metrics)
        self._wait()
        self.prev = metrics["loss"]
        self.sync_in -= 1
        if self.sync_in <= 0:
            self._wait()
        return state, metrics

    def _wait(self):
        if self.prev is None:
            return
        t = time.perf_counter()
        with self.annotate("chipbench.block"):
            self.losses.append(float(self.prev))
        now = time.perf_counter()
        self.blocked_s += now - t
        self.stamps.append(now)
        self.prev = None


class Feed:
    """Cycles through the pool of batches."""

    def __init__(self, pool, annotate):
        self.pool, self.annotate, self.i = pool, annotate, 0

    def __iter__(self):
        return self

    def __next__(self):
        with self.annotate("chipbench.batch"):
            batch = self.pool[self.i % len(self.pool)]
            self.i += 1
        return batch


def model_config(cfg_file: dict):
    import dataclasses
    from repro import configs as C
    prog = cfg_file["program"]
    return dataclasses.replace(C.get(prog["arch"]), **prog["overrides"])


def train_args(tr: dict, seed: int):
    from repro.launch import train as train_lib
    m = tr["workers"]
    return train_lib.parse_args([
        "--full", "--workers", str(m), "--byz", str(tr["byzantine"]),
        "--batch", str(m * tr["batch_per_worker"]),
        "--seq", str(tr["seq_len"]), "--attack", tr["attack"],
        "--defense", tr["defense"], "--t0", str(tr["t0"]),
        "--t1", str(tr["t1"]), "--floor", str(tr["floor"]),
        "--lr", str(tr["lr"]), "--log-every", str(tr["log_every"]),
        "--seed", str(seed)])


def window_metrics(stepper, t0, tr) -> dict:
    times = [b - a for a, b in zip([t0] + stepper.stamps, stepper.stamps)]
    window_s = stepper.stamps[-1] - t0
    p90 = statistics.quantiles(times, n=10)[-1] if len(times) > 1 else times[0]
    slowest = sorted(range(len(times)), key=lambda i: -times[i])[:3]
    return {"tokens_per_s": stepper.steps * flops.tokens_per_step(tr)
            / window_s, "step_p90_ms": 1e3 * p90, "window_s": window_s,
            "slowest": {f"step {i}": times[i] for i in slowest}}


def mesh_for(chips: int):
    """``None`` on one chip; on several, the ``(data=chips, model=1)`` mesh
    of the training CLI's sharded path, one worker row per chip."""
    if chips == 1:
        return None
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((chips, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:chips])


def place(pool: list, mesh, workers: int) -> list:
    """The pool's batches on ``mesh``, by the program's placement rules
    (``launch.sharding.batch_pspecs``: the worker axis on ``data``)."""
    if mesh is None:
        return pool
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import sharding as sh
    specs = sh.batch_pspecs(pool[0], mesh, workers)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, PartitionSpec))
    return [jax.device_put(b, shardings) for b in pool]


def step_live_bytes(stepper, trainer, batch) -> int:
    """Bytes the window's step holds on one chip while it runs, by the
    compiler's buffer assignment: arguments + outputs - aliased +
    temporaries.  The allocator's counter leaves out the temporaries."""
    mem = stepper.fn.lower(trainer.state, batch).compile().memory_analysis()
    if mem is None:
        raise RuntimeError("the compiled step gives no memory analysis")
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def start(cell: dict, seed: int):
    """Build the run, its feed and its step wrapper, and take the checked
    first steps.  Returns ``(trainer, stepper, pool, readings, phases)``,
    ``phases`` the host seconds of each part of this set-up."""
    import jax
    from repro.launch import train as train_lib
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program of the run, the eager set-up's small ones too, is read
    # back from the cache by the next run of the cell
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    annotate = jax.profiler.TraceAnnotation
    tr = cell["traffic"]
    phases, t = {}, time.perf_counter()

    def phase(name):
        nonlocal t
        now = time.perf_counter()
        phases[name] = now - t
        t = now

    cfg = model_config(cell["config"])
    mesh = mesh_for(cell["entry"]["chips"])
    trainer = train_lib.build_trainer(cfg, train_args(tr, seed), mesh=mesh)
    jax.block_until_ready(trainer.state)
    phase("build_trainer")
    pool = jax.block_until_ready(place(
        generator.batches(tr, vocab=cfg.vocab_size, seed=seed), mesh,
        tr["workers"]))
    phase("batches")
    trainer.data_iter = Feed(pool, annotate)
    stepper = Stepper(trainer.step_fn, annotate)
    trainer.step_fn = stepper
    stepper.record = []
    readings = oracle.program_readings(trainer, stepper.record)
    stepper.record = None
    phase("checked_steps")
    return trainer, stepper, pool, readings, phases


def run_cell(cell: dict, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float) -> dict:
    """One run of ``cell`` on ``devices``; returns the result line."""
    import jax

    tr, chips = cell["traffic"], cell["entry"]["chips"]
    annotate = jax.profiler.TraceAnnotation

    t_import = time.perf_counter() - t_start
    trainer, stepper, pool, prog, phases = start(cell, seed)
    phases = {"imports_and_devices": t_import, **phases}

    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    stepper.stamps, stepper.losses, stepper.steps = [], [], 0
    stepper.blocked_s = 0.0
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with annotate("chipbench.window"):
        while time.perf_counter() - t0 < seconds:
            stepper.sync_in = tr["log_every"]
            trainer.run(tr["log_every"], verbose=False)
    if trace:
        jax.profiler.stop_trace()
    win = window_metrics(stepper, t0, tr)
    phases["slowest_steps"] = win.pop("slowest")
    failed = sum(not math.isfinite(v) for v in stepper.losses)
    counted = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices[:chips])
    peak = max(counted, step_live_bytes(stepper, trainer, pool[0]))

    del trainer
    gc.collect()
    ref = oracle.follow(cell, seed, pool[:oracle.STEPS])
    checks = oracle.compare(prog, ref, cell["limits"])
    correct = oracle.judge(checks) and failed == 0

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": stepper.steps,
              "failed": failed}
    if not trace:
        values = {"tokens_per_s": win["tokens_per_s"],
                  "step_p90_ms": win["step_p90_ms"],
                  "peak_hbm_gib": peak / 2**30, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
    else:
        path = glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)[0]
        red = trace_reduce.reduce(path)
        tmp.cleanup()
        model = cell["config"]["model"]
        ctx = {"trace": red, "chips": chips, "peaks": peaks,
               "host": {"steps": stepper.steps,
                        "window_s": win["window_s"],
                        "blocked_s": stepper.blocked_s},
               "step_flops": flops.step_flops(model, tr),
               "gram_bytes": flops.gram_bytes(cell["config"]["param_count"],
                                              tr)}
        result["metrics"] = {}
        for m in cell["per_layer"]:
            v = bench.metric_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(red)
    result["device"] = device
    result["setup_phases"] = phases
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = bench.cell(args.workload)
    import jax
    devices = jax.devices()
    chips = cell["entry"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"chipbench: cell {args.workload!r} needs {chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks = bench.peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices, peaks=peaks,
                      t_start=T_START)
    phases = result.pop("setup_phases")
    for name, sec in phases.pop("slowest_steps").items():
        print(f"window's slowest {name}: {sec:.4f} s", file=sys.stderr)
    for name, sec in phases.items():
        print(f"setup {name}: {sec:.3f} s", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
