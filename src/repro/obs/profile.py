"""Wall-clock phase attribution — the profiling hooks of the flight
recorder (DESIGN.md §15).

Answers "where did the benchmark's seconds go": compile (trace + XLA)
vs execute vs host-side work, with ``launch.hlo_analysis`` cost
attribution on the compiled program.  Two entry points:

* :class:`PhaseTimer` — a context-manager accumulator for coarse phases
  (``with pt.phase("build"): ...``); nested phases are not double
  counted because only the innermost active phase accrues time.
* :func:`profile_compiled` — AOT-compiles one jitted callable
  (``jax.jit(f).lower(*args).compile()``) so compile time is measured
  apart from the first execution (jit's usual dispatch hides it there),
  then times ``repeats`` executions, and attributes program cost via
  ``hlo_analysis.analyze_hlo`` (loop-aware FLOPs / HBM bytes — XLA's
  own ``cost_analysis`` counts while-loop bodies once).

``benchmarks/trace_overhead.py`` uses both to prove full-schema trace
capture stays within 5% of ``trace_zeta=False``
(``BENCH_trace_overhead.json``)."""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import jax


class PhaseTimer:
    """Accumulate wall-clock into named phases.

    Only the innermost active phase accrues: entering ``execute`` inside
    ``total`` pauses ``total``'s accumulation, so phase seconds are
    disjoint and sum to measured wall-clock."""

    def __init__(self):
        self.seconds: Dict[str, float] = {}
        self._stack: list = []          # [(name, started_at), ...]

    @contextlib.contextmanager
    def phase(self, name: str):
        now = time.perf_counter()
        if self._stack:                 # pause the enclosing phase
            outer, t0 = self._stack[-1]
            self.seconds[outer] = self.seconds.get(outer, 0.0) + now - t0
        self._stack.append((name, now))
        try:
            yield self
        finally:
            now = time.perf_counter()
            _, t0 = self._stack.pop()
            self.seconds[name] = self.seconds.get(name, 0.0) + now - t0
            if self._stack:             # resume the enclosing phase
                outer, _ = self._stack[-1]
                self._stack[-1] = (outer, now)

    def summary(self) -> Dict[str, float]:
        total = sum(self.seconds.values())
        out = {f"{k}_s": round(v, 6) for k, v in sorted(self.seconds.items())}
        out["total_s"] = round(total, 6)
        for k, v in sorted(self.seconds.items()):
            out[f"{k}_frac"] = round(v / total, 4) if total else 0.0
        return out


def profile_compiled(fn: Callable, *args, repeats: int = 3,
                     analyze: bool = True) -> Dict:
    """AOT compile + timed executions of one jittable callable.

    Returns ``{"lower_s", "compile_s", "execute_s" (best of repeats),
    "execute_mean_s", "hlo": {flops, hbm_bytes, ...}}``.  ``args`` are
    the concrete example arguments; results are block-until-ready'd so
    execute time is real device time, not dispatch time."""
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()

    times = []
    out = None
    for _ in range(max(1, repeats)):
        ta = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - ta)

    rec: Dict = {
        "lower_s": round(t1 - t0, 6),
        "compile_s": round(t2 - t1, 6),
        "execute_s": round(min(times), 6),
        "execute_mean_s": round(sum(times) / len(times), 6),
        "repeats": len(times),
    }
    if analyze:
        from repro.launch.hlo_analysis import analyze_hlo
        try:
            rec["hlo"] = analyze_hlo(compiled.as_text())
        except Exception as e:                            # noqa: BLE001
            rec["hlo"] = {"error": repr(e)}
    rec["_out"] = out       # callers may want the result; strip for json
    return rec


def strip_private(rec: Dict) -> Dict:
    """Drop non-serializable keys (``_out``) before json-dumping."""
    return {k: v for k, v in rec.items() if not k.startswith("_")}
