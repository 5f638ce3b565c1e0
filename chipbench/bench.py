"""Find a cell's files by the names in ``BENCHMARK.json``.

A configuration, a traffic mix, a cell's limits, a per-layer metric and an
architecture's reference each live in a file of their own, named after the
entry that uses them, so that a later change adds a cell by adding files
and entries without editing a file that is already here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; known: "
                   f"{sorted(e['name'] for e in entries)}")


def config(name: str) -> dict:
    """The configuration file named by the ``configs`` entry ``name``."""
    entry = _entry(benchmark()["configs"], name, "configuration")
    return load_json(ROOT / entry["file"])


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def cell(name: str) -> dict:
    """Everything one cell is run with: its ``workloads`` entry, the
    configuration, the traffic mix, the limits of its correctness check
    and the metrics it reports."""
    bench = benchmark()
    entry = _entry(bench["workloads"], name, "workload")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    return {"entry": entry, "config": config(entry["config"]),
            "traffic": traffic(entry["traffic"]),
            "limits": load_json(HERE / "workloads" / f"{name}.json")["limits"],
            "end_to_end": e2e, "per_layer": per_layer,
            "run_seconds": bench["run_seconds"]}


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx)``: the value of a per-layer
    metric, or ``None`` where the run gives it nothing to read."""
    return _load_module(HERE / "metrics" / f"{name}.py",
                        f"chipbench_metric_{name}").read


def reference(model_type: str):
    """``reference/<model_type>.py``: the plain float32 reference of one
    architecture, named by the configuration's published ``model_type``:
    ``init`` and ``loss``, and the shapes' count of its work,
    ``matmul_params`` and ``mixer_flops_per_token``."""
    return _load_module(HERE / "reference" / f"{model_type}.py",
                        f"chipbench_reference_{model_type}")


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"chipbench/peaks.json (known: "
                       f"{sorted(table['devices'])})")
    return table["devices"][device_kind]
