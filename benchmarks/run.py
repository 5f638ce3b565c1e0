"""Benchmark driver — one module per paper table/figure plus the roofline
report.  ``python -m benchmarks.run [--quick]`` prints one CSV line per
measurement (``name,...``) and writes JSON artifacts under
``experiments/bench/``.

  table1          Paper Table 1: attack x defense accuracy grid
  fig2a           Paper Fig 2(a): detection-statistic growth exponents
  fig2b           Paper Fig 2(b): periodic good-set reset (transients)
  convex_attack   Appendix C.3: burst attack vs unwindowed filter
  saddle_escape   escape-time distributions on the planted-saddle
                  testbed vs the theorem's predicted budget
  overhead        master aggregation O(md) cost per defense
  campaign        campaign engine throughput: per-loop Trainer trials vs
                  the scan+vmap engine (BENCH_campaign_throughput.json)
  trace_overhead  flight-recorder cost: full-schema trace capture vs
                  trace_zeta=False (BENCH_trace_overhead.json)
  live_overhead   live-telemetry cost: scan_trial tap_every=50/10 vs
                  untapped (BENCH_live_overhead.json)
  kernels         Pallas kernels (interpret) vs jnp reference
  roofline        three-term roofline per (arch x shape) from the dry runs
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer steps per experiment (~3x faster)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    args = ap.parse_args()
    steps = 60 if args.quick else 150

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (table1_attack_grid, fig2_detection, fig2_reset,
                            convex_attack, saddle_escape, overhead,
                            campaign_throughput, bench_kernels, roofline,
                            trace_overhead, live_overhead)
    jobs = {
        "table1": lambda: table1_attack_grid.run(steps=steps),
        "fig2a": lambda: fig2_detection.run(steps=max(steps, 120)),
        "fig2b": lambda: fig2_reset.run(steps=steps),
        "convex_attack": lambda: convex_attack.run(steps=max(steps, 150)),
        "saddle_escape": lambda: saddle_escape.run(
            steps=300 if args.quick else 400,
            seeds=2 if args.quick else 3),
        "overhead": lambda: overhead.run(quick=args.quick),
        "campaign": lambda: campaign_throughput.run(quick=args.quick),
        "trace_overhead": lambda: trace_overhead.run(
            steps=60 if args.quick else 150),
        "live_overhead": lambda: live_overhead.run(
            steps=100 if args.quick else 150),
        "kernels": bench_kernels.run,
        "roofline": roofline.run,
    }
    selected = (args.only.split(",") if args.only else list(jobs))
    for name in selected:
        t0 = time.time()
        print(f"\n===== {name} =====")
        try:
            jobs[name]()
        except Exception as e:                          # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"{name},FAILED,{e}")
            sys.exit(1)
        print(f"{name},wall_s,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
