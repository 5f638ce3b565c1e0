"""Master-side aggregation overhead: the paper claims the safeguard's
O(md) processing is negligible vs the backward pass.  Times one jitted
aggregation call per defense across model sizes d (m = 10 workers).

The gradient pytree is a realistic MULTI-LEAF layered model (per-layer
weight + bias leaves), not one monolithic array — per-leaf dispatch is
exactly the overhead the flat-buffer engine (DESIGN.md §6) removes, and a
single-leaf toy model would hide it.  Three safeguard representations are
timed against each other so the flat-engine speedup is measured, not
asserted:

  safeguard_stacked    paper-faithful stacked-pytree accumulators
                       (4 tree traversals per step: 2 accumulates + 2
                       leaf-wise Grams)
  safeguard_flat       flat (m, d_pad) buffers on the pass the step picks
                       on this platform: off the TPU the in-place scatter
                       accumulate + blocked Pallas Gram kernel
                       (interpreted), on a TPU the per-leaf in-place
                       accumulate+Gram kernel
  safeguard_flat_xla   flat buffers pinned to a one-device sharding:
                       scatter accumulate + one XLA dot (the pass of a
                       sharded mesh)
  safeguard_sketch     CountSketch O(m r k) state (beyond paper)

Builds ONE record (raw rows + per-d safeguard entries with flat-vs-
stacked speedups) and writes it identically to
``experiments/bench/overhead.json`` and the committed repo-root baseline
``BENCH_safeguard_overhead.json`` — a single source of truth, never two
diverging formats.  Regenerate with ``python -m benchmarks.run --quick
--only overhead``.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import SafeguardConfig, init_state, safeguard_step
from repro.core import aggregators as agg_lib

M = 10
N_LAYERS = 24


def _time(fn, *args, iters=20):
    fn(*args)                              # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


def make_model(d_target: int, n_layers: int = N_LAYERS):
    """Layered params pytree (~d_target total): n_layers x {w: (h, h),
    b: (h,)} — the leaf structure of a real transformer stack at small h."""
    h = max(4, int((d_target / n_layers) ** 0.5))
    params = {f"layer_{i:02d}": {"w": jnp.zeros((h, h)),
                                 "b": jnp.zeros((h,))}
              for i in range(n_layers)}
    d = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    return params, d


def make_grads(params, key):
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [jax.random.normal(k, (M,) + leaf.shape)
                  for k, leaf in zip(keys, leaves)])


def _one_device_sharding():
    """The accumulators pinned to one device: the step takes the pass it
    takes on a sharded mesh."""
    return NamedSharding(Mesh(jax.devices()[:1], ("data",)), P())


# (name, SafeguardConfig fields, pin the accumulators to a sharding)
SAFEGUARD_VARIANTS = (
    ("safeguard_stacked", dict(engine="stacked"), False),
    ("safeguard_flat", dict(engine="flat"), False),
    ("safeguard_flat_xla", dict(engine="flat"), True),
    ("safeguard_sketch", dict(use_sketch=True, sketch_k=1024), False),
)


def run(out_dir: str = "experiments/bench", quick: bool = False,
        baseline_path: str = "BENCH_safeguard_overhead.json"):
    sizes = (10_000, 100_000) if quick else (10_000, 100_000, 1_000_000)
    iters = 10 if quick else 20
    rows = []
    for d_target in sizes:
        params, d = make_model(d_target)
        grads = make_grads(params, jax.random.PRNGKey(0))

        reg = agg_lib.make_registry(n_byz=4, m=M)
        for name in ("mean", "coord_median", "trimmed_mean", "geo_median",
                     "krum"):
            fn = jax.jit(reg[name].fn)
            us = _time(fn, grads, iters=iters)
            rows.append({"defense": name, "d": d, "us_per_call": us})
            print(f"overhead,{name},d={d},{us:.1f}us")

        for variant, kw, pinned in SAFEGUARD_VARIANTS:
            cfg = SafeguardConfig(m=M, T0=50, T1=200, threshold_floor=1.0,
                                  **kw)
            st = init_state(cfg, params)
            shd = _one_device_sharding() if pinned else None
            fn = jax.jit(lambda s, g: safeguard_step(s, g, cfg,
                                                     acc_sharding=shd))
            us = _time(fn, st, grads, iters=iters)
            rows.append({"defense": variant, "d": d, "us_per_call": us})
            print(f"overhead,{variant},d={d},{us:.1f}us")

    record = _build_record(rows)
    os.makedirs(out_dir, exist_ok=True)
    for path in (os.path.join(out_dir, "overhead.json"), baseline_path):
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
    return rows


def _build_record(rows):
    """The single overhead record: raw measurements plus per-d safeguard
    entries with the flat-vs-stacked speedup (the §6 measured claim).
    Written verbatim to BOTH the experiments artifact and the committed
    repo-root baseline."""
    by = {(r["defense"], r["d"]): r["us_per_call"] for r in rows}
    ds = sorted({r["d"] for r in rows})
    record = {"m": M, "n_layers": N_LAYERS, "unit": "us_per_call",
              "rows": rows, "entries": []}
    for d in ds:
        entry = {"d": d}
        for variant, _, _ in SAFEGUARD_VARIANTS:
            if (variant, d) in by:
                entry[variant] = round(by[(variant, d)], 1)
        stacked = by.get(("safeguard_stacked", d))
        flat = by.get(("safeguard_flat", d))
        if stacked and flat:
            entry["flat_speedup_vs_stacked"] = round(stacked / flat, 2)
            print(f"overhead,flat_speedup_vs_stacked,d={d},"
                  f"{stacked / flat:.2f}x")
        record["entries"].append(entry)
    return record


if __name__ == "__main__":
    run()
