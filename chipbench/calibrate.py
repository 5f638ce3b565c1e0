"""Readings that the limits of a cell's correctness check are set from.

    python chipbench/calibrate.py --workload <cell> --seeds <n> [<n> ...] \\
        [--controls <k>]

For every seed, in one process: the run's checked first steps against the
reference (the program's readings, which set the lower end of each
limit), and for the first ``--controls`` seeds the control (the reference
computed with float8 operands in the program's place) and the planted
fault of half of each worker's batch left out, both against the
reference (they set the upper end).  A step that returns its state
unchanged reads 1 on ``update`` by definition and is not run.  Prints one
JSON line per seed; the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import bench, oracle  # noqa: E402
from chipbench import run as runner  # noqa: E402

def values(checks: dict) -> dict:
    return {k: c["value"] for k, c in checks.items()}


def detail(prog: dict, ref: dict) -> dict:
    """The numbers' tables, leaf by leaf, for a look at what drives one."""
    out = {}
    for name, table in oracle.tables(prog, ref).items():
        if isinstance(table, dict):
            out[name] = {k: np.asarray(v).tolist() for k, v in table.items()}
        else:
            out[name] = np.asarray(table).tolist()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    cell = bench.cell(args.workload)
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        trainer, stepper, pool, prog, _ = runner.start(cell, seed)
        del trainer, stepper
        gc.collect()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = oracle.follow(cell, seed, pool[:oracle.STEPS])
        t_ref = time.perf_counter() - t
        line = {"seed": seed,
                "program": values(oracle.compare(prog, ref, None)),
                "program_s": t_prog, "reference_s": t_ref,
                "program_detail": detail(prog, ref)}
        if i < args.controls:
            for name, kw in (("control_fp8", {"precision": "fp8"}),
                             ("half_batch", {"fault": "half_batch"})):
                got = oracle.follow(cell, seed, pool[:oracle.STEPS], **kw)
                line[name] = values(oracle.compare(got, ref, None))
                line[name + "_detail"] = detail(got, ref)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
