"""Production mesh construction (TPU v5e).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16); the paper's
"workers" map to the pod x data axes (m = 32), so the safeguard's worker
axis spans pods while tensor parallelism stays intra-pod.

Functions, not module constants — importing this module never touches jax
device state (the dry run forces a 512-device host platform *before* any
jax import; tests/benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(jax.devices())} — "
            "the dry run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before importing jax")
    import numpy as np
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), axes,
                             axis_types=(AxisType.Auto,) * len(axes))


def worker_axes(mesh) -> tuple:
    """Mesh axes that carry the safeguard worker dimension."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def n_workers(mesh) -> int:
    names = mesh.axis_names
    m = mesh.shape["data"]
    if "pod" in names:
        m *= mesh.shape["pod"]
    return m


def data_size(mesh) -> int:
    return n_workers(mesh)


def model_size(mesh) -> int:
    return mesh.shape["model"]
