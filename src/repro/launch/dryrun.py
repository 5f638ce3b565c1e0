import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x input shape)
on the production meshes and extract the roofline raw terms.

MUST be run as its own process (the lines above execute before any other
import so the CPU host platform exposes 512 placeholder devices — it is a
lowering tool on fake devices, so it pins itself to the CPU even on a
machine with a TPU).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod] [--variant exact] [--out DIR]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Artifacts: one JSON per (arch, shape, mesh, variant) under
``experiments/dryrun/`` with per-device HLO FLOPs / bytes, memory stats,
and per-collective byte counts parsed from the compiled HLO.
"""

import argparse          # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro import configs as C                    # noqa: E402
from repro.configs.base import INPUT_SHAPES       # noqa: E402
from repro.launch import mesh as mesh_lib         # noqa: E402
from repro.launch import specs as specs_lib       # noqa: E402

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Per-collective-type *output* bytes summed over ops (per device)."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for coll in _COLLECTIVES:
            # "%name = <shape> all-reduce(...)" / fusion-wrapped starts
            if re.search(rf"= [^=]*\b{coll}(-start|-done)?\(", stripped):
                lhs = stripped.split("=", 1)[0] + "=" + \
                    stripped.split("=", 1)[1].split(f"{coll}", 1)[0]
                if coll + "-done" in stripped:
                    continue          # avoid double counting start/done
                out[coll] += _shape_bytes(lhs)
                counts[coll] += 1
                break
    return out, counts


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            variant: str = "exact", out_dir: str = "experiments/dryrun",
            save: bool = True, verbose: bool = True):
    cfg = C.get(arch)
    shape = INPUT_SHAPES[shape_name]

    if shape_name == "long_500k" and not cfg.sub_quadratic:
        rec = {"arch": arch, "shape": shape_name, "variant": variant,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "skipped",
               "reason": "pure full-attention arch; 524k dense decode "
                         "cache excluded by design (DESIGN.md §7)"}
        if save:
            _write(rec, out_dir)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name}: SKIP (full attention)")
        return rec

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    t0 = time.time()
    step, args = specs_lib.build(cfg, shape, mesh, variant=variant)
    with mesh:
        lowered = jax.jit(step).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo_txt = compiled.as_text()
    colls, coll_counts = collective_bytes(hlo_txt)

    # loop-aware accounting (XLA cost_analysis counts while bodies once —
    # scanned-layer models undercount by n_layers; see hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze_hlo
    try:
        loop_aware = analyze_hlo(hlo_txt)
    except Exception as e:                                # noqa: BLE001
        loop_aware = {"error": repr(e)}

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "variant": variant,
        "status": "ok",
        "n_devices": int(mesh.devices.size),
        # raw XLA numbers (loop bodies counted once)
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
        # loop-aware per-device numbers (use these for the roofline)
        "flops_per_device_loop_aware": loop_aware.get("flops"),
        "hbm_bytes_per_device_loop_aware": loop_aware.get("hbm_bytes"),
        "collective_bytes_loop_aware": loop_aware.get("collective_bytes"),
        "collective_counts_loop_aware": loop_aware.get("collective_counts"),
        "collective_bytes": colls,
        "collective_counts": coll_counts,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }
    if save:
        _write(rec, out_dir)
    if verbose:
        live = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        la = rec.get("flops_per_device_loop_aware") or 0.0
        lac = rec.get("collective_bytes_loop_aware") or {}
        print(f"[dryrun] {arch} x {shape_name} ({mesh_name}, {variant}): "
              f"OK  flops/dev={la:.3e}  "
              f"live_mem/dev={live/2**30:.2f}GiB  "
              f"coll={ {k: f'{v/2**30:.1f}G' for k, v in lac.items() if v} }  "
              f"compile={t_compile:.1f}s")
    return rec


def _write(rec, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['variant']}"
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="exact",
                    choices=["exact", "exact16", "stacked", "sketch",
                             "mean"])
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch, shape)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        archs = C.ARCH_IDS + C.EXTRA_IDS
        shapes = list(INPUT_SHAPES)
    else:
        if not args.arch or not args.shape:
            ap.error("need --arch and --shape (or --all)")
        archs, shapes = [args.arch], [args.shape]

    failures = []
    for arch in archs:
        for shape in shapes:
            try:
                run_one(arch, shape, multi_pod=args.multi_pod,
                        variant=args.variant, out_dir=args.out)
            except Exception as e:                     # noqa: BLE001
                traceback.print_exc()
                failures.append((arch, shape, repr(e)))
                print(f"[dryrun] {arch} x {shape}: FAIL {e}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall dry runs OK")


if __name__ == "__main__":
    main()
