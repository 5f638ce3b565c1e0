"""Sharding rules: parameter / gradient / cache PartitionSpecs.

Strategy (DESIGN.md §3): FSDP x TP —

  * every parameter leaf shards its largest eligible dim over ``model``
    (tensor parallel) and the next eligible dim over the data axes (fully
    sharded data parallel), leading layer-stack axes excluded;
  * MoE expert tensors override the heuristic: the held-expert dim goes
    to ``model``, the feature dim to data (storage: the layer gathers its
    held experts whole for its grouped matmuls);
  * stacked per-worker gradients put the worker axis on the data axes and
    keep only the ``model`` assignments of the underlying parameter — the
    worker axis *is* the data axis;
  * the flat safeguard accumulators (``(m_pad, d_pad)`` buffers, DESIGN.md
    §6) shard their worker-row axis over the data axes — each data shard
    owns its own workers' rows, so the windowed accumulate is collective-
    free and only the ``(m, m)`` distance matrix is combined across shards
    (:func:`flat_acc_pspec`); the padded feature axis goes to ``model``
    when divisible;
  * decode caches shard batch over data and the largest remaining eligible
    dim (kv-heads, latent rank, or sequence) over model.

A dim is eligible for an axis only if its size divides evenly; otherwise
the next-largest dim is tried, falling back to replication.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import mesh as mesh_lib


def _path_str(path) -> str:
    parts = []
    for p in path:
        if isinstance(p, jax.tree_util.DictKey):
            parts.append(str(p.key))
        elif isinstance(p, jax.tree_util.SequenceKey):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _has_stack_axis(pstr: str) -> bool:
    return ("blocks" in pstr and "pre_blocks" not in pstr
            and "tail_blocks" not in pstr)


def _assign(shape, skip: int, model_n: int, data_axes: Tuple[str, ...],
            data_n: int):
    """Greedy largest-divisible-dim assignment -> list of axis names."""
    spec = [None] * len(shape)
    order = sorted(range(skip, len(shape)), key=lambda i: -shape[i])
    # model axis first
    for i in order:
        if shape[i] % model_n == 0 and shape[i] >= model_n:
            spec[i] = "model"
            break
    for i in order:
        if spec[i] is None and shape[i] % data_n == 0 and shape[i] >= data_n:
            spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
            break
    return spec


# Megatron-style orientation rules: column-parallel weights shard their
# OUTPUT (last) dim over `model` (no collective in the forward matmul);
# row-parallel weights shard their INPUT (first non-stack) dim and incur
# one all-reduce/reduce-scatter after the matmul.  Without these, square
# weights (e.g. deepseek-coder's 7168x7168 wq) can end up sharded on the
# contracting dim, paying a full-activation psum per projection
# (EXPERIMENTS.md §Perf).
_COLUMN_PARALLEL = ("wq", "wk", "wv", "w_gate", "w_up", "w_uq", "w_uk",
                    "w_uv", "w_kr", "w_dq", "w_dkv", "in_proj", "w_x",
                    "w_y", "w_i", "w_r", "lm_head")
_ROW_PARALLEL = ("wo", "w_down", "out_proj", "w_o")


def param_pspec(path, leaf, mesh) -> P:
    pstr = _path_str(path)
    shape = leaf.shape
    model_n = mesh_lib.model_size(mesh)
    data_axes = mesh_lib.worker_axes(mesh)
    data_n = mesh_lib.data_size(mesh)
    skip = 1 if _has_stack_axis(pstr) and len(shape) > 1 else 0

    if len(shape) - skip <= 1:
        return P(*([None] * len(shape)))

    leaf_name = pstr.rsplit("/", 1)[-1]
    is_moe_expert = ("/moe/" in f"/{pstr}/" and len(shape) - skip == 3)
    first, last = skip, len(shape) - 1
    oriented = (last if leaf_name in _COLUMN_PARALLEL else first)
    if not is_moe_expert and (leaf_name in _COLUMN_PARALLEL
                              or leaf_name in _ROW_PARALLEL) \
            and shape[oriented] >= 1024:
        # orientation override only for substantial dims — tiny outputs
        # (MQA/GQA kv projections) do better under the size heuristic
        order = ([last, first] if leaf_name in _COLUMN_PARALLEL
                 else [first, last])
        spec = [None] * len(shape)
        for i in order:
            if shape[i] % model_n == 0 and shape[i] >= model_n:
                spec[i] = "model"
                break
        for i in (first, last):
            if spec[i] is None and shape[i] % data_n == 0 \
                    and shape[i] >= data_n:
                spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
                break
        return P(*spec)

    # MoE experts: (stack, G, d, f) / (stack, G, f, d) — the held-expert
    # axis over ``model`` at rest only: a grouped matmul cannot split its
    # groups, so ``layers.moe_apply`` gathers the layer's held experts
    # whole, and each data shard runs its own workers' matmuls
    if "/moe/" in f"/{pstr}/" and pstr.rsplit("/", 1)[-1] in (
            "w_gate", "w_up", "w_down") and len(shape) - skip == 3:
        E, a, b = shape[skip], shape[skip + 1], shape[skip + 2]
        spec = [None] * len(shape)
        if E % model_n == 0:
            spec[skip] = "model"
            if a % data_n == 0:
                spec[skip + 1] = (data_axes if len(data_axes) > 1
                                  else data_axes[0])
        else:
            return P(*_assign(shape, skip, model_n, data_axes, data_n))
        return P(*spec)

    if pstr.rsplit("/", 1)[-1] == "router":
        # replicate the (small) expert dim; shard d over data
        spec = [None] * len(shape)
        if shape[skip] % data_n == 0:
            spec[skip] = data_axes if len(data_axes) > 1 else data_axes[0]
        return P(*spec)

    return P(*_assign(shape, skip, model_n, data_axes, data_n))


def params_pspecs(abstract_params, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_pspec(path, leaf, mesh), abstract_params)


def stacked_grad_pspec(param_spec: P, mesh) -> P:
    """Worker-stacked version of a parameter spec: worker axis on the data
    axes, keep only the 'model' assignment of the tail."""
    data_axes = mesh_lib.worker_axes(mesh)
    worker = data_axes if len(data_axes) > 1 else data_axes[0]
    tail = [s if s == "model" else None for s in param_spec]
    return P(worker, *tail)


def stacked_grads_pspecs(param_specs, mesh):
    return jax.tree.map(
        lambda spec: stacked_grad_pspec(spec, mesh), param_specs,
        is_leaf=lambda x: isinstance(x, P))


def flat_acc_pspec(mesh, d_padded: int) -> P:
    """Partition spec of a flat safeguard accumulator ``(m_pad, d_pad)``:
    worker rows over the data axes (each shard owns a worker-row slice, so
    the fused accumulate-and-reset is local), the padded feature axis over
    ``model`` when divisible.  Under this spec the only cross-shard traffic
    of the safeguard pass is the tiny ``(m, m)`` Gram combine."""
    data_axes = mesh_lib.worker_axes(mesh)
    worker = data_axes if len(data_axes) > 1 else data_axes[0]
    col = "model" if d_padded % mesh_lib.model_size(mesh) == 0 else None
    return P(worker, col)


def cache_pspec(path, leaf, mesh, batch: int) -> P:
    pstr = _path_str(path)
    shape = leaf.shape
    if leaf.ndim == 0 or pstr.endswith("pos"):
        return P()
    model_n = mesh_lib.model_size(mesh)
    data_axes = mesh_lib.worker_axes(mesh)
    data_n = mesh_lib.data_size(mesh)
    skip = 1 if _has_stack_axis(pstr) else 0
    spec = [None] * len(shape)
    # batch axis -> data
    if len(shape) > skip and shape[skip] == batch and batch % data_n == 0:
        spec[skip] = data_axes if len(data_axes) > 1 else data_axes[0]
    # largest remaining divisible dim -> model
    order = sorted(range(skip + 1, len(shape)), key=lambda i: -shape[i])
    for i in order:
        if spec[i] is None and shape[i] % model_n == 0 \
                and shape[i] >= model_n:
            spec[i] = "model"
            break
    return P(*spec)


def cache_pspecs(abstract_cache, mesh, batch: int):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_pspec(path, leaf, mesh, batch),
        abstract_cache)


def batch_pspec(path, leaf, mesh, m: Optional[int]) -> P:
    """Training batches are worker-stacked (m, b, ...); serving batches are
    (B, ...).  Embedding inputs additionally shard d over model."""
    data_axes = mesh_lib.worker_axes(mesh)
    worker = data_axes if len(data_axes) > 1 else data_axes[0]
    pstr = _path_str(path)
    spec = [None] * leaf.ndim
    data_n = mesh_lib.data_size(mesh)
    if leaf.ndim and leaf.shape[0] % data_n == 0 and leaf.shape[0] > 0:
        spec[0] = worker
    if pstr.endswith("embeds"):
        model_n = mesh_lib.model_size(mesh)
        if leaf.shape[-1] % model_n == 0:
            spec[-1] = "model"
    return P(*spec)


def batch_pspecs(abstract_batch, mesh, m: Optional[int] = None):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: batch_pspec(path, leaf, mesh, m), abstract_batch)


def with_shardings(abstract_tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        abstract_tree, spec_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
